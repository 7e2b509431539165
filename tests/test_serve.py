"""Serving correctness: prefill + decode must reproduce full-forward logits."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models import init_lm, lm_decode, lm_forward, lm_prefill
from repro.models.model import _logits

CAUSAL_ARCHS = [a for a in ARCH_IDS if a != "hubert-xlarge"]
B, S = 2, 32


@pytest.mark.parametrize("arch", CAUSAL_ARCHS)
def test_prefill_decode_matches_forward(arch):
    cfg = get_config(arch, smoke=True)
    cfg = dataclasses.replace(cfg, dtype="float32", moe_capacity_factor=16.0)
    key = jax.random.PRNGKey(0)
    params, _, _ = init_lm(key, cfg)
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    batch = {"tokens": tokens}
    if cfg.frontend == "patch":
        batch["patches"] = jax.random.normal(
            key, (B, cfg.n_patches, cfg.frontend_dim), jnp.float32
        )
    h, _, _ = lm_forward(params, cfg, batch)
    full = _logits(params, cfg, h)

    pre = dict(batch)
    pre["tokens"] = tokens[:, : S - 1]
    max_len = S + (cfg.n_patches if cfg.frontend == "patch" else 0)
    logits_p, caches = lm_prefill(params, cfg, pre, max_len=max_len)
    assert float(jnp.max(jnp.abs(logits_p[:, 0] - full[:, -2]))) < 2e-4

    logits_d, caches = lm_decode(params, cfg, caches, tokens[:, S - 1 :], pos=max_len - 1)
    assert float(jnp.max(jnp.abs(logits_d[:, 0] - full[:, -1]))) < 2e-4


def test_multi_step_decode_chain():
    """Greedy decode token-by-token == teacher-forced forward on same tokens."""
    cfg = dataclasses.replace(
        get_config("gemma3-4b", smoke=True), dtype="float32"
    )
    key = jax.random.PRNGKey(1)
    params, _, _ = init_lm(key, cfg)
    tokens = jax.random.randint(key, (1, 24), 0, cfg.vocab_size)
    h, _, _ = lm_forward(params, cfg, {"tokens": tokens})
    full = _logits(params, cfg, h)

    _, caches = lm_prefill(params, cfg, {"tokens": tokens[:, :8]}, max_len=24)
    for t in range(8, 24):
        logits, caches = lm_decode(params, cfg, caches, tokens[:, t : t + 1], pos=t)
        err = float(jnp.max(jnp.abs(logits[:, 0] - full[:, t])))
        assert err < 5e-4, (t, err)


def test_windowed_cache_is_small():
    """SWA archs allocate only window-sized caches (long-context feasibility)."""
    from repro.models import init_caches

    cfg = get_config("h2o-danube-1.8b", smoke=True)  # all-local, window=16
    caches = init_caches(cfg, batch=2, max_len=4096)
    assert caches[0]["kv"]["k"].shape[1] == cfg.window


def test_recurrent_cache_constant_size():
    cfg = get_config("xlstm-1.3b", smoke=True)
    from repro.models import init_caches

    c1 = init_caches(cfg, 2, 128)
    c2 = init_caches(cfg, 2, 1 << 19)
    s1 = sum(x.size for x in jax.tree_util.tree_leaves(c1))
    s2 = sum(x.size for x in jax.tree_util.tree_leaves(c2))
    assert s1 == s2  # O(1) state independent of context length


def test_grok_softcap_serve_parity():
    """final_softcap must reach EVERY serving entry point, not just lm_loss:
    teacher-forced full-forward logits vs lm_prefill / lm_decode /
    lm_prefill_suffix on the grok smoke config — which also routes attention
    through flash_tight with an in-kernel logit_softcap, so this is the
    end-to-end 'grok cell serves on the tight softcapped flash path' check."""
    from repro.models import init_paged_caches, lm_prefill_into, lm_prefill_suffix

    cfg = get_config("grok-1-314b", smoke=True)
    cfg = dataclasses.replace(cfg, dtype="float32", moe_capacity_factor=16.0)
    assert cfg.sparse.attn_kernel == "flash_tight"
    assert cfg.logit_softcap and cfg.final_softcap
    key = jax.random.PRNGKey(3)
    params, _, _ = init_lm(key, cfg)
    S_, ctx = 32, 16
    tokens = jax.random.randint(key, (1, S_), 0, cfg.vocab_size)
    h, _, _ = lm_forward(params, cfg, {"tokens": tokens})
    full = _logits(params, cfg, h)
    # the cap itself must be live end to end: tanh bounds every true logit
    assert float(jnp.max(jnp.abs(full[..., : cfg.vocab_size]))) <= cfg.final_softcap

    logits_p, caches = lm_prefill(
        params, cfg, {"tokens": tokens[:, : S_ - 1]}, max_len=S_
    )
    assert float(jnp.max(jnp.abs(logits_p[:, 0] - full[:, -2]))) < 2e-4
    assert float(jnp.max(jnp.abs(logits_p[..., : cfg.vocab_size]))) <= cfg.final_softcap

    logits_d, _ = lm_decode(params, cfg, caches, tokens[:, S_ - 1 :], pos=S_ - 1)
    assert float(jnp.max(jnp.abs(logits_d[:, 0] - full[:, -1]))) < 2e-4

    # shared-prefix suffix path: prefix pages via paged admission, then only
    # the suffix runs through the model (flash history attention + softcaps)
    page = 8
    n_blocks = {"global": S_ // page, "local": S_ // page}
    paged = init_paged_caches(cfg, 1, S_, n_blocks, page)
    table = jnp.arange(S_ // page, dtype=jnp.int32)
    _, paged = lm_prefill_into(
        params, cfg, paged, {"tokens": tokens[:, :ctx]}, jnp.int32(0),
        max_len=S_, tables={"global": table},
    )
    logits_s, _ = lm_prefill_suffix(
        params, cfg, paged, {"tokens": tokens[:, ctx:]}, table, jnp.int32(ctx)
    )
    assert float(jnp.max(jnp.abs(logits_s[:, 0] - full[:, -1]))) < 2e-4


@pytest.mark.parametrize("kernel", ["dense", "masked", "block_sparse"])
def test_init_serving_state_matches_train_init(kernel):
    """Serving init holds the params and masks init_train_state draws from
    the same seed and nothing else (no optimizer state, no backward
    supersets), and prefills to the training state's logits."""
    import numpy as np

    from repro.configs.base import SparseConfig
    from repro.launch.serve import configure_kernel, init_serving_state
    from repro.optim import OptConfig
    from repro.training import init_train_state

    cfg = dataclasses.replace(
        get_config("h2o-danube-1.8b", smoke=True), dtype="float32",
        sparse=SparseConfig(sparsity=0.8, method="rigl"),
    )
    cfg = configure_kernel(cfg, kernel=kernel, block=16)
    params, masks, pack = init_serving_state(cfg, seed=2)
    state, _, _ = init_train_state(jax.random.PRNGKey(2), cfg, OptConfig())

    def same(a, b):
        la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
        assert len(la) == len(lb)
        for x, y in zip(la, lb):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    same(params, state["params"])
    if kernel == "dense":
        assert masks is None and pack is None
        train_masks, train_pack = None, None
    else:
        same(masks, state["masks"])
        train_masks, train_pack = state["masks"], state.get("pack")
        assert "bwd_masks" in state  # kernel-dispatch RigL trains with them
        assert (pack is None) == (kernel == "masked")

    tokens = jax.random.randint(jax.random.PRNGKey(5), (1, 24), 0, cfg.vocab_size)
    want, _ = lm_prefill(
        state["params"], cfg, {"tokens": tokens}, 32, masks=train_masks,
        pack=train_pack,
    )
    got, _ = lm_prefill(params, cfg, {"tokens": tokens}, 32, masks=masks, pack=pack)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
