"""The float32 reference against the program's own forward pass, on the CPU
at the smoke size: dense and masked weights, and a bfloat16 variant that the
tolerance has to reject."""
import dataclasses

import jax
import numpy as np
import pytest

from chipbench.reference import danube as ref
from repro.configs import get_config
from repro.models.model import _logits, lm_forward

# float32 on both sides: the two sum in different orders, nothing more
RTOL = 1e-4


def _smoke(window=16):
    cfg = dataclasses.replace(
        get_config("h2o-danube-1.8b", smoke=True), dtype="float32",
        window=window)
    m = {k: getattr(cfg, k) for k in (
        "n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
        "vocab_size", "window", "rope_theta", "norm_eps")}
    m["embed_scale"] = float(np.sqrt(cfg.d_model))
    return cfg, m


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.mark.parametrize("masked", [False, True])
def test_reference_matches_program_forward(masked):
    cfg, m = _smoke()
    params, masks = jax.jit(
        lambda k: ref.make_weights(k, k, m, 0.5 if masked else 0.0, 16)
    )(jax.random.PRNGKey(3))
    toks = jax.random.randint(jax.random.PRNGKey(4), (40,), 0, m["vocab_size"])
    h = ref.hidden(params, masks, m, toks)
    want = ref.logits(params, m, h)
    eff = jax.tree_util.tree_map(
        lambda w, k: w if k is None else w * k, params, masks,
        is_leaf=lambda x: x is None)
    hp, _, _ = lm_forward(eff, cfg, {"tokens": toks[None]})
    got = _logits(eff, cfg, hp)[0, :, : m["vocab_size"]]
    assert _rel(got, want) < RTOL
    if masked:
        density = np.mean([np.asarray(k).mean() for k in
                           jax.tree_util.tree_leaves(masks)])
        assert 0.3 < density < 0.7
    bf16 = ref.logits(params, m, ref.hidden(params, masks, m, toks, "bf16"),
                      "bf16")
    assert _rel(bf16, want) > 10 * RTOL


def test_window_is_applied():
    """Past the window the reference must drop the oldest keys, as the
    program's sliding-window layers do."""
    cfg, m = _smoke(window=8)
    params, masks = jax.jit(lambda k: ref.make_weights(k, k, m, 0.5, 16))(
        jax.random.PRNGKey(5))
    toks = jax.random.randint(jax.random.PRNGKey(6), (32,), 0, m["vocab_size"])
    want = ref.logits(params, m, ref.hidden(params, masks, m, toks))
    eff = jax.tree_util.tree_map(
        lambda w, k: w if k is None else w * k, params, masks,
        is_leaf=lambda x: x is None)
    hp, _, _ = lm_forward(eff, cfg, {"tokens": toks[None]})
    got = _logits(eff, cfg, hp)[0, :, : m["vocab_size"]]
    assert _rel(got, want) < RTOL
    m_wide = dict(m, window=0)
    wide = ref.logits(params, m_wide, ref.hidden(params, masks, m_wide, toks))
    assert _rel(wide, want) > 1e-2


def test_erk_blocks_keep_the_density():
    m = {"d_model": 2560, "n_heads": 32, "n_kv_heads": 8, "head_dim": 80,
         "d_ff": 6912}
    counts = ref.erk_blocks(m, 0.8, 128)
    shapes = ref.layer_shapes(m)
    kept = sum(counts[k] * 128 * 128 for k in counts)
    total = sum(a * b for a, b in shapes.values())
    assert abs(kept / total - 0.2) < 0.005
    # ERK: the narrow K/V projections are denser than the square ones
    dens = {k: counts[k] * 128 * 128 / (a * b) for k, (a, b) in shapes.items()}
    assert dens[("attn", "wk")] > dens[("attn", "wq")] > dens[("mlp", "wi")]
