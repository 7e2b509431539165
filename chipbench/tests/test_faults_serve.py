"""The serving cells' comparison rejects an altered token and the
lower-precision control: on the CPU at the smoke size, the harness's own
driver with the device gate skipped, against each cell's own limit."""
import json

import pytest

from chipbench import harness
from chipbench.tests import smoke

CELLS = [c for c in [w["name"] for w in harness.benchmark()["workloads"]]
         + list(smoke.LATER) if smoke.spec(c)["driver"] == "serve"]


def _run(cell, seed, control=None):
    import jax

    spec = smoke.spec(cell)
    return harness.module("", "serve").run(
        spec, seed, 2.0, False, devices=jax.devices(),
        clock_compiles=smoke.NoCompileClock(), control=control)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct_and_control_is_not(cell, capsys):
    """The program's own tokens pass; the control, put in their place for
    the check, does not."""
    res = _run(cell, 31, control="fp8")
    assert res["attempted"] == 12 and res["failed"] == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    ref = next(x for x in lines if x.get("phase") == "reference")
    limit = smoke.spec(cell)["check"]["limits"]["served_gap"]
    assert ref["tokens"] >= 80
    assert ref["served_gap"] <= limit, ref
    assert len(set(ref["buckets"])) > 1, ref
    assert ref["control_gap"] > limit, ref
    assert not res["correct"], res["checks"]
    assert res["checks"]["served_gap"]["value"] == ref["control_gap"]


def test_altered_token_is_not_correct(monkeypatch):
    """One slot's token is altered where the decode step produces it."""
    from repro.serving import engine as engine_mod

    real = engine_mod._decode_fn

    def altered(cfg, greedy, faulty=False):
        fn = real(cfg, greedy, faulty)

        def step(*args):
            nxt, *rest = fn(*args)
            return (nxt.at[0].set((nxt[0] + 1) % cfg.vocab_size), *rest)

        return step

    monkeypatch.setattr(engine_mod, "_decode_fn", altered)
    res = _run(CELLS[0], 32)
    assert not res["correct"], res["checks"]
    assert res["checks"]["served_gap"]["value"] > 0
