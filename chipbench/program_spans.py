"""The program's own spans, read from its process-wide metrics registry.

The program times its phases with ``repro.obs.region``, which keeps, per
span name, a count (``repro_spans_total``) and the seconds of the latest
one (``repro_span_last_seconds``) in ``repro.obs.REGISTRY``.  A training
cell runs the program in this process (``chipbench/train.py``), so after
the window the registry holds the spans of the window's last topology
update.  A program without those regions leaves the registry without them,
and the readers that use this module then give nothing.
"""
from __future__ import annotations

REFRESH = "repro.refresh_pack"
DRAIN = "repro.refresh_pack.drain"


def _series(family: str) -> dict:
    from repro.obs import REGISTRY

    fam = REGISTRY.get(family)
    return {} if fam is None else {k[0]: c.value for k, c in fam.series()}


def window_update(ctx) -> tuple | None:
    """(seconds of the window's pack refresh, seconds of its drain), or None.

    The latest refresh is the window's when the window holds an update and
    the refresh fits inside the harness's update span.  Every refresh of a
    state with backward supersets (the training cells') has a drain, so the
    latest drain is that refresh's.  A window with more than one update
    would need the window's sums, which the harness does not hand over."""
    update_s = ctx["window"].get("update_s", 0.0)
    last = _series("repro_span_last_seconds")
    refresh, drain = last.get(REFRESH), last.get(DRAIN)
    if not update_s or refresh is None or drain is None:
        return None
    if not drain <= refresh <= update_s:
        return None
    return refresh, drain
