"""The training cell's comparison rejects a broken timed path and the
lower-precision control: on the CPU at the smoke size, the harness's own
driver with the device gate skipped, against the cell's own limits."""
import json

import pytest

from chipbench import harness
from chipbench.tests import smoke


@pytest.fixture(autouse=True)
def cpu_peaks(monkeypatch):
    monkeypatch.setattr(harness, "peaks", lambda kind: {
        "bf16_flops": 1e12, "hbm_bytes_per_s": 1e11})


def _run(seed, make_train_step=None, control=None, make_rigl_step=None):
    import jax

    spec = smoke.spec("danube-train.rigl")
    return harness.module("", "train").run(
        spec, seed, 0.2, False, devices=jax.devices(),
        clock_compiles=smoke.NoCompileClock(), control=control,
        make_train_step=make_train_step, make_rigl_step=make_rigl_step)


def _unchanged(cfg, opt, lr):
    """A step that computes its metrics and returns its state unchanged."""
    from repro.training import make_train_step

    step = make_train_step(cfg, opt, lr)

    def broken(state, batch):
        _, m = step(state, batch)
        return state, m

    return broken


def _half_batch(cfg, opt, lr):
    """Half of the batch left out, the mean taken over the rest."""
    from repro.training import make_train_step

    step = make_train_step(cfg, opt, lr)

    def broken(state, batch):
        n = batch["tokens"].shape[0] // 2
        return step(state, {k: v[:n] for k, v in batch.items()})

    return broken


def test_sound_run_is_correct_and_control_is_not(capsys):
    """The program's own readings pass; the control, put in their place
    for the check, does not, nor do the faults planted in the reference."""
    res = _run(21, control="fp8")
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    by = {x["phase"]: x for x in lines if "phase" in x}
    limits = smoke.spec("danube-train.rigl")["check"]["limits"]
    assert all(by["reference"][k] <= limits[k] for k in limits), by["reference"]
    assert any(by["control"][k] > limits[k] for k in limits), by["control"]
    assert not res["correct"], res["checks"]
    for fault in ("masks_unchanged", "grow_altered", "half_batch"):
        assert not by["fault_" + fault]["correct"], by["fault_" + fault]


@pytest.mark.parametrize("fault", [_unchanged, _half_batch])
def test_broken_step_is_not_correct(fault):
    res = _run(22, make_train_step=fault)
    assert not res["correct"], res["checks"]


def _masks_unchanged(cfg, algo, lr):
    """A topology update that computes its loss and leaves every mask as
    it was."""
    from repro.training import make_rigl_step

    step = make_rigl_step(cfg, algo, lr)

    def broken(state, batch):
        new, m = step(state, batch)
        return dict(new, masks=state["masks"], params=state["params"]), m

    return broken


def test_update_that_leaves_the_masks_is_not_correct():
    res = _run(23, make_rigl_step=_masks_unchanged)
    assert not res["correct"], res["checks"]
    assert res["checks"]["update_size"]["value"] > res["checks"][
        "update_size"]["limit"]
