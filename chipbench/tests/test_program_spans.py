"""The program's regions as the benchmark sees them: in a profiler trace,
on the clock of the harness's own spans, and through the two readers of the
window's topology update (``update_drain_share``, ``pack_refresh_share``)."""
import glob

import jax
import jax.numpy as jnp
import pytest

from chipbench import harness, reduce


def test_region_lands_in_the_profile_inside_the_window(tmp_path):
    from jax.profiler import ProfileData
    from repro.obs import region

    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("chipbench.window"):
            with region("repro.refresh_pack"):
                with region("repro.pack.build"):
                    jnp.ones(8).sum().block_until_ready()
    path = glob.glob(f"{tmp_path}/plugins/profile/*/*.xplane.pb")[0]
    lo, hi = reduce.reduce_profile(path).window
    host = {ev.name: (ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host") for line in plane.lines
            for ev in line.events}
    outer, inner = host["repro.refresh_pack"], host["repro.pack.build"]
    assert lo <= outer[0] <= inner[0] <= inner[1] <= outer[1] <= hi


def _update(refresh_s, drain_s):
    """One pack refresh whose drain takes ``drain_s`` of ``refresh_s``, on a
    clock the test gives, recorded in the process's registry."""
    from repro.obs import region

    def ticks(*ts):
        it = iter(ts)
        return lambda: next(it)

    with region("repro.refresh_pack", clock=ticks(0.0, refresh_s)):
        with region("repro.refresh_pack.drain", clock=ticks(0.0, drain_s)):
            pass


def _read(name, ctx):
    return harness.module("metrics", name).read(ctx)


def _ctx(update_s):
    return {"window": {"seconds": 50.0, "steps": 41, "update_s": update_s,
                       "tokens_per_step": 8192, "tokens_per_s": 6500.0,
                       "seq": 2048}}


def test_update_readers_split_the_window_refresh():
    _update(refresh_s=1.75, drain_s=1.25)
    assert _read("update_drain_share", _ctx(2.0)) == pytest.approx(2.5)
    assert _read("pack_refresh_share", _ctx(2.0)) == pytest.approx(1.0)


@pytest.mark.parametrize("update_s", [0.0, 1.5])
def test_update_readers_give_nothing_without_the_window_update(update_s):
    # no update in the window, or a refresh longer than the window's update
    # span (so not the window's): the readers leave the metrics out
    _update(refresh_s=1.75, drain_s=1.25)
    for name in ("update_drain_share", "pack_refresh_share"):
        assert _read(name, _ctx(update_s)) is None
