"""Unified LM: block composer + train forward / prefill / decode.

Supports the assigned families:
  transformer (dense GQA, SWA, local:global, parallel-block, MoE, encoder-only,
  VLM/audio frontend stubs), xlstm (mLSTM/sLSTM mix), hymba (parallel attn+SSM).

Params are nested dicts; ``init_lm`` returns (params, logical_axes, sparse_flags)
— axes drive sharding, sparse_flags mark RigL-managed weights.  The layer stack
is a python list (unrolled at trace time — exact cost_analysis); ``scan_layers``
switches to a stacked lax.scan for the full-depth memory proof on homogeneous
stacks.

Sparse-kernel dispatch is TOTAL: ``lm_forward``/``lm_loss``/``lm_prefill``/
``lm_decode`` take an optional ``masks`` pytree mirroring params.  When given,
EVERY sparsifiable weight einsum in EVERY family — transformer attention +
MLP, hymba SSM projections, xLSTM mLSTM/sLSTM projections (incl. the grouped
per-head recurrence), MoE expert banks + shared experts — routes through the
Pallas sparse kernels selected by ``cfg.sparse.kernel`` ('masked' fused-mask
matmul, 'block_sparse' block skipping; grouped variants for weight banks)
with custom-VJP backward kernels — masked weights are never materialized in
HBM, fwd or bwd.  The only non-dispatched params are genuinely non-matmul
leaves (scan carries, gates, convs, routers), which are dense and unmasked by
construction; ``layers.assert_total_dispatch`` turns any silent w*m fallback
into a loud error (see docs/kernels.md#dispatch-coverage).  masks=None keeps
the legacy contract (callers pre-mask via core.apply_masks).

All four entry points also take ``pack`` — a PackState pytree (core/pack.py)
mirroring the masks — which sizes every block_sparse kernel grid to the TRUE
active-block count instead of the in-jit worst case.  The train/serve drivers
carry it in state and refresh it only on RigL topology updates; see
docs/kernels.md for the end-to-end lifecycle.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.masks import apply_masks
from . import attention as A
from . import ssm as S
from . import xlstm as X
from .layers import P, linear, linear_init, rmsnorm, rmsnorm_init, split_params
from .mlp import mlp, mlp_init
from .moe import moe, moe_init

__all__ = [
    "init_lm",
    "lm_forward",
    "lm_loss",
    "init_caches",
    "init_paged_caches",
    "cache_group",
    "lm_prefill",
    "lm_prefill_into",
    "lm_prefill_suffix",
    "lm_decode",
    "logits_all_finite",
    "stack_layer_params",
]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def padded_vocab(cfg) -> int:
    """Vocab padded to a multiple of 256 so the vocab dim always shards on a
    16-way model axis (MaxText-style). Pad logits are masked to -inf in
    _logits, so the model is mathematically identical to the exact vocab."""
    return ((cfg.vocab_size + 255) // 256) * 256


def _layer_init(key, cfg, i):
    ks = jax.random.split(key, 4)
    p: dict[str, Any] = {}
    if cfg.block_type == "xlstm":
        p["ln1"] = rmsnorm_init(cfg.d_model)
        if cfg.is_slstm(i):
            p["slstm"] = X.slstm_init(ks[0], cfg)
        else:
            p["mlstm"] = X.mlstm_init(ks[0], cfg)
        return p

    p["ln1"] = rmsnorm_init(cfg.d_model)
    p["attn"] = A.attn_init(ks[0], cfg)
    if cfg.block_type == "hymba":
        p["ssm"] = S.ssm_init(ks[1], cfg)
        p["attn_norm"] = rmsnorm_init(cfg.d_model)
        p["ssm_norm"] = rmsnorm_init(cfg.d_model)
    if not cfg.parallel_block:
        p["ln2"] = rmsnorm_init(cfg.d_model)
    if cfg.post_norms:
        p["ln1_post"] = rmsnorm_init(cfg.d_model)
        p["ln2_post"] = rmsnorm_init(cfg.d_model)
    if cfg.n_experts:
        p["moe"] = moe_init(ks[2], cfg)
    elif cfg.d_ff:
        p["mlp"] = mlp_init(ks[2], cfg.d_model, cfg.d_ff, cfg.mlp_kind)
    return p


def init_lm(key, cfg, *, return_bundles: bool = False):
    """Returns (params, axes, sparse_flags) trees."""
    ks = jax.random.split(key, cfg.n_layers + 4)
    tree: dict[str, Any] = {}
    d = cfg.d_model
    pv = padded_vocab(cfg)
    if cfg.frontend == "none":
        tree["embed"] = {
            "table": P(
                (0.02 * jax.random.normal(ks[-1], (pv, d))).astype(jnp.float32),
                ("vocab", "embed"),
                False,
            )
        }
    else:
        # frontend stub: precomputed patch/frame embeddings -> linear proj
        tree["frontend_proj"] = linear_init(
            ks[-2], cfg.frontend_dim, d, ("frontend", "embed"), sparse=False
        )
        if cfg.frontend == "patch":  # VLM also embeds text tokens
            tree["embed"] = {
                "table": P(
                    (0.02 * jax.random.normal(ks[-1], (pv, d))).astype(jnp.float32),
                    ("vocab", "embed"),
                    False,
                )
            }
    tree["layers"] = [_layer_init(ks[i], cfg, i) for i in range(cfg.n_layers)]
    tree["ln_f"] = rmsnorm_init(d)
    if not cfg.tie_embeddings or cfg.frontend == "frames":
        tree["head"] = linear_init(
            ks[-3], d, pv, ("embed", "vocab"), sparse=False
        )
    if return_bundles:
        return tree
    return split_params(tree)


def stack_layer_params(layers: list):
    """List of per-layer trees -> single tree stacked on a leading 'layers' dim."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _sub(masks, key):
    """Mask subtree lookup tolerating masks=None (legacy pre-masked path)."""
    return None if masks is None else masks[key]


def _local_masked(p, masks, key, *, kernel):
    # NOTE: kernel is REQUIRED (no default) so the pre-total-dispatch call
    # shape `_local_masked(p, masks, key)` is a TypeError, not a silent
    # guard bypass.
    """Materialize w*m for a sparse submodule WITHOUT kernel dispatch.

    Since the total-dispatch PR, every matmul-bearing subtree (attn/mlp/ssm/
    xlstm/moe) threads masks into its own ``layers.linear``/``grouped_linear``
    calls, so this helper only remains for genuinely non-matmul leaves (scan
    carries, gates, convs — all dense and unmasked by construction) and as the
    loud guard: in kernel mode, routing a subtree that still carries mask
    leaves through here would silently fall back to dense w*m in HBM — the
    exact failure the total-dispatch contract forbids — so it raises instead.
    """
    if masks is None:
        return p[key]
    m = masks[key]
    if kernel in ("masked", "block_sparse") and any(
        l is not None
        for l in jax.tree_util.tree_leaves(m, is_leaf=lambda x: x is None)
    ):
        raise RuntimeError(
            f"_local_masked({key!r}): subtree carries mask leaves but "
            "cfg.sparse.kernel is set — this would silently materialize w*m "
            "instead of dispatching to the Pallas kernels. Thread masks= "
            "into the submodule (see docs/kernels.md#dispatch-coverage)"
        )
    return apply_masks(p[key], m)


def _block(p, x, cfg, i, *, positions=None, masks=None, pack=None,
           attn_sched=None, history=None):
    """Full-sequence block (train/prefill). Returns (x, kv_or_state, moe_aux).

    masks: this layer's mask subtree.  None => legacy behaviour (params are
    already w*m).  Given => EVERY sparsifiable matmul of the block —
    attention, MLP, SSM, mLSTM/sLSTM (grouped recurrence) and MoE banks —
    dispatches to the Pallas sparse kernels (cfg.sparse.kernel) and never
    materializes masked weights.
    pack: this layer's PackState subtree (mirrors masks) — block_sparse grids
    run at the true active-block count instead of the padded worst case.
    attn_sched: {kind: AttnSchedule} for cfg.sparse.attn_kernel='flash_tight'
    (models/attention.py::attn_schedules) — shared across layers of the same
    kind; None lets the attention build its schedule lazily at trace time.
    history: this layer's paged-prefix dict for suffix-only prefill
    (models/attention.py::attention ``history``) — shared-prefix serving.
    """
    aux = jnp.float32(0.0)
    if cfg.block_type == "xlstm":
        h = rmsnorm(p["ln1"], x, cfg.norm_eps)
        if cfg.is_slstm(i):
            o, state = X.slstm(
                p["slstm"], h, cfg,
                masks=_sub(masks, "slstm"), pack=_sub(pack, "slstm"),
            )
        else:
            o, state = X.mlstm(
                p["mlstm"], h, cfg, chunk=cfg.q_chunk,
                masks=_sub(masks, "mlstm"), pack=_sub(pack, "mlstm"),
            )
        return x + o, state, aux

    kind = cfg.layer_kind(i)
    h = rmsnorm(p["ln1"], x, cfg.norm_eps)
    attn_out, kv = A.attention(
        p["attn"], h, cfg, kind=kind, positions=positions, q_chunk=cfg.q_chunk,
        masks=_sub(masks, "attn"), pack=_sub(pack, "attn"),
        sched=None if attn_sched is None else attn_sched.get(kind),
        history=history,
    )
    state: Any = kv
    if cfg.block_type == "hymba":
        ssm_out, ssm_h = S.ssm(
            p["ssm"], h, cfg, chunk=cfg.q_chunk,
            masks=_sub(masks, "ssm"), pack=_sub(pack, "ssm"),
        )
        attn_out = 0.5 * (
            rmsnorm(p["attn_norm"], attn_out, cfg.norm_eps)
            + rmsnorm(p["ssm_norm"], ssm_out, cfg.norm_eps)
        )
        state = (kv, ssm_h, h)  # h tail needed for the conv state at prefill

    if cfg.post_norms:
        attn_out = rmsnorm(p["ln1_post"], attn_out, cfg.norm_eps)

    if cfg.parallel_block:
        ff_in = h
    else:
        x = x + attn_out
        ff_in = rmsnorm(p["ln2"], x, cfg.norm_eps)

    if cfg.n_experts:
        ff_out, aux = moe(
            p["moe"], ff_in, cfg,
            masks=_sub(masks, "moe"), pack=_sub(pack, "moe"),
        )
    elif cfg.d_ff:
        ff_out = mlp(
            p["mlp"], ff_in, cfg.mlp_kind, masks=_sub(masks, "mlp"),
            kernel=cfg.sparse.kernel, block=cfg.sparse.kernel_block,
            pack=_sub(pack, "mlp"),
        )
    else:
        ff_out = 0.0
    if cfg.post_norms and cfg.d_ff:
        ff_out = rmsnorm(p["ln2_post"], ff_out, cfg.norm_eps)

    if cfg.parallel_block:
        return x + attn_out + ff_out, state, aux
    return x + ff_out, state, aux


def _sp_constraint(x, cfg):
    """Megatron-style sequence parallelism: shard the residual stream's seq
    dim over the model axis between layers.  GSPMD then turns the TP psums
    into reduce-scatter + all-gather pairs (half the ICI bytes) and the remat
    residual saves shrink by the TP degree.  Takes the ambient mesh
    (jax.set_mesh — the dry-run provides one); without one there is nothing
    to shard over and x passes through.  Under a mesh, a constraint the mesh
    cannot meet raises."""
    if not getattr(cfg, "seq_shard_activations", False):
        return x
    if jax.sharding.get_abstract_mesh().empty:
        return x
    from jax.sharding import PartitionSpec as P

    U = P.UNCONSTRAINED
    return jax.lax.with_sharding_constraint(x, P(U, "model", U))


def _embed_inputs(params, cfg, batch):
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    if cfg.frontend == "frames":
        return linear(params["frontend_proj"], batch["frames"].astype(dt))
    x = params["embed"]["table"].astype(dt)[batch["tokens"]]
    x = x * np.sqrt(cfg.d_model)
    if cfg.frontend == "patch" and "patches" in batch:
        # decode steps omit "patches": the prompt's patch KV lives in the cache
        pe = linear(params["frontend_proj"], batch["patches"].astype(dt))
        x = jnp.concatenate([pe, x], axis=1)
    return x


def _logits(params, cfg, h):
    dt = h.dtype
    if "head" in params:
        out = linear(params["head"], h, dt)
    else:
        out = h @ params["embed"]["table"].astype(dt).T
    out = out.astype(jnp.float32)
    if cfg.final_softcap:
        c = cfg.final_softcap
        out = c * jnp.tanh(out / c)
    if out.shape[-1] != cfg.vocab_size:  # mask vocab-padding slots
        pad = out.shape[-1] - cfg.vocab_size
        neg = jnp.full((pad,), -1e30, out.dtype)
        out = jnp.concatenate(
            [out[..., : cfg.vocab_size], jnp.broadcast_to(neg, (*out.shape[:-1], pad))],
            axis=-1,
        )
    return out


def lm_forward(
    params, cfg, batch, *, collect_states: bool = False, masks=None, pack=None,
    attn_sched=None, positions=None, histories=None,
):
    """Full-sequence forward -> (hidden (B,S,d), states per layer, moe_aux).

    masks: mask pytree mirroring params (kernel-dispatch mode).  None keeps
    the legacy contract: callers pass pre-masked effective weights.
    pack: PackState pytree mirroring masks (core/pack.py) — block_sparse
    kernel grids are sized to the true active-block count (tight grids).
    attn_sched: {kind: AttnSchedule} for attn_kernel='flash_tight' (see
    models/attention.py::attn_schedules).  Unlike pack, schedules are
    STATIC-shape-derived, so None just builds them lazily at trace time —
    passing them is for explicit per-session threading (launch/serve.py).
    positions: absolute RoPE positions ((S,) or (B, S)); None = arange(S).
    histories: per-layer paged-prefix dicts for suffix-only prefill
    (lm_prefill_suffix) — ``batch`` is then the SUFFIX and ``positions``
    must carry its absolute offsets.  Unrolled collect_states path only.
    """
    if histories is not None:
        assert collect_states and not cfg.scan_layers, (
            "histories (suffix prefill) runs the unrolled collect_states path"
        )
        if attn_sched is None:
            attn_sched = {}  # self-phase flash scheds build lazily per shape
    x = _embed_inputs(params, cfg, batch)
    S_ = x.shape[1]
    if attn_sched is None:
        attn_sched = A.attn_schedules(cfg, S_)
    if positions is None:
        positions = jnp.arange(S_)
    aux_total = jnp.float32(0.0)
    states = []

    def _per_layer(tree):
        return tree["layers"] if tree is not None else [None] * cfg.n_layers

    if cfg.scan_layers:
        assert masks is None and pack is None, (
            "scan_layers (dry-run memory proof) does not thread masks/pack; "
            "pre-mask the stacked params instead"
        )
        x, states, aux_total = _forward_scanned(params, cfg, x, positions)
    elif cfg.remat and not collect_states:
        # checkpoint REGIONS of remat_group layers (sqrt-style remat): only
        # the region inputs are saved; kv/ssm states stay internal so they
        # are not forced live (outputs of a checkpoint are always saved).
        g = max(cfg.remat_group, 1)
        layer_ps = params["layers"]
        layer_ms = _per_layer(masks)
        layer_pk = _per_layer(pack)
        policy = (
            jax.checkpoint_policies.checkpoint_dots
            if getattr(cfg, "remat_policy", "none") == "dots"
            else None
        )

        def region(i0, ps, ms, pks, x_):
            aux_ = jnp.float32(0.0)
            for j, (p, m, pk) in enumerate(zip(ps, ms, pks)):
                x_, _, a = _block(
                    p, x_, cfg, i0 + j, positions=positions, masks=m, pack=pk,
                    attn_sched=attn_sched,
                )
                aux_ = aux_ + a
            return x_, aux_

        for i0 in range(0, cfg.n_layers, g):
            ps = layer_ps[i0 : i0 + g]
            ms = layer_ms[i0 : i0 + g]
            pks = layer_pk[i0 : i0 + g]
            x = _sp_constraint(x, cfg)
            x, aux = jax.checkpoint(
                functools.partial(region, i0), policy=policy
            )(ps, ms, pks, x)
            aux_total = aux_total + aux
    else:
        layer_ms = _per_layer(masks)
        layer_pk = _per_layer(pack)
        for i, p in enumerate(params["layers"]):
            x = _sp_constraint(x, cfg)
            x, st, aux = _block(
                p, x, cfg, i, positions=positions, masks=layer_ms[i],
                pack=layer_pk[i], attn_sched=attn_sched,
                history=None if histories is None else histories[i],
            )
            aux_total = aux_total + aux
            if collect_states:
                states.append(st)
    h = rmsnorm(params["ln_f"], x, cfg.norm_eps)
    return h, states, aux_total


def _forward_scanned(params, cfg, x, positions):
    """Homogeneous stacks only: lax.scan over stacked layer params."""
    assert cfg.pattern_period == 1 and cfg.block_type == "transformer", (
        "scan_layers requires a homogeneous transformer stack"
    )
    stacked = params["layers_stacked"]

    def body(carry, layer_p):
        x, aux = carry
        x, _, a = _block(layer_p, x, cfg, 0, positions=positions)
        return (x, aux + a), None

    if cfg.remat:
        body = jax.checkpoint(body)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0.0)), stacked)
    return x, [], aux


def lm_loss(params, cfg, batch, masks=None, pack=None, attn_sched=None):
    """Mean next-token xent (chunked over seq to bound the logits buffer).

    masks != None => kernel-dispatch mode: params are RAW (unmasked) and the
    sparse topology is enforced inside the matmul kernels; jax.grad of this
    w.r.t. params then yields the paper's SPARSE gradient directly (the
    custom-VJP wgrad kernels fuse the g⊙m product).
    pack: PackState pytree (core/pack.py) — tight block_sparse grids in both
    the forward and the custom-VJP backward kernels.
    attn_sched: flash_tight KV-block schedules ({kind: sched}); None builds
    lazily — training with attn_kernel set runs flash fwd AND bwd (the loss
    is differentiated through the attention custom VJP, no jnp fallback).
    """
    h, _, aux = lm_forward(
        params, cfg, batch, masks=masks, pack=pack, attn_sched=attn_sched
    )
    targets = batch["targets"]
    # frontend==patch: loss only over the text positions (last T slots)
    if cfg.frontend == "patch":
        h = h[:, -targets.shape[1] :]
    B, S_, _ = h.shape
    n_chunks = max(1, cfg.loss_chunks)
    assert S_ % n_chunks == 0
    step = S_ // n_chunks
    total = jnp.float32(0.0)
    for s in range(0, S_, step):
        logits = _logits(params, cfg, h[:, s : s + step])
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = targets[:, s : s + step]
        picked = jnp.take_along_axis(logits, tgt[..., None], axis=-1)[..., 0]
        total = total + jnp.sum(lse - picked)
    loss = total / (B * S_)
    return loss + 0.01 * aux


# ---------------------------------------------------------------------------
# serving: prefill + decode
# ---------------------------------------------------------------------------

def init_caches(cfg, batch: int, max_len: int):
    """Per-layer cache pytree (shapes differ per layer kind — unrolled only)."""
    caches = []
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    for i in range(cfg.n_layers):
        if cfg.block_type == "xlstm":
            if cfg.is_slstm(i):
                caches.append({"slstm": X.init_slstm_state(cfg, batch)})
            else:
                caches.append({"mlstm": X.init_mlstm_state(cfg, batch)})
            continue
        kind = cfg.layer_kind(i)
        c: dict[str, Any] = {"kv": A.init_kv_cache(cfg, kind, batch, max_len, dt)}
        if cfg.block_type == "hymba":
            c["ssm"] = S.init_ssm_state(cfg, batch)
        caches.append(c)
    return caches


def cache_group(cfg, i: int) -> str:
    """Which page-pool GROUP layer i's KV cache belongs to ('global' at size
    max_len, 'local' ring at min(window, max_len)) — layers sharing a cache
    geometry share one physical page id space (serving/block_pool.py)."""
    return (
        "local"
        if (cfg.layer_kind(i) == "local" and cfg.window)
        else "global"
    )


def init_paged_caches(cfg, batch: int, max_len: int, n_blocks: dict,
                      page_size: int):
    """Paged variant of ``init_caches``: KV leaves become page POOLS.

    n_blocks: {'global': N, 'local': N} physical pages per cache group —
    every layer of a group addresses the same id space through the group's
    block table (serving/engine.py owns the tables; this is just storage).
    Recurrent per-slot states (hymba SSM, xLSTM carries) have no
    positional axis to page, so they stay slot-batched exactly as in
    ``init_caches`` — only position-indexed KV is pooled.
    """
    caches = []
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    for i in range(cfg.n_layers):
        if cfg.block_type == "xlstm":
            if cfg.is_slstm(i):
                caches.append({"slstm": X.init_slstm_state(cfg, batch)})
            else:
                caches.append({"mlstm": X.init_mlstm_state(cfg, batch)})
            continue
        c: dict[str, Any] = {
            "kv": A.init_kv_pool(cfg, n_blocks[cache_group(cfg, i)],
                                 page_size, dt)
        }
        if cfg.block_type == "hymba":
            c["ssm"] = S.init_ssm_state(cfg, batch)
        caches.append(c)
    return caches


def lm_prefill(params, cfg, batch, max_len: int, *, masks=None, pack=None,
               attn_sched=None, n_valid=None):
    """Run the prompt, return (last-position logits, filled caches).

    pack: PackState pytree — prefill's block_sparse projections/MLPs run
    tight grids (see lm_decode for the per-token decode counterpart).
    attn_sched: flash_tight KV-block schedules for the prompt length
    ({kind: sched}, models/attention.py::attn_schedules) — serve threads one
    per session; None builds lazily.  Decode does NOT take schedules: a
    single-query step is a matvec over the (already window-bounded ring)
    cache — there is no dead score BLOCK to skip, so attn_decode stays on
    the jnp path by design (docs/kernels.md#attention-schedules).

    n_valid (traced int): sequence positions >= n_valid are END-PADDING —
    the serving engine buckets prompt lengths so one jitted trace serves a
    range of lengths (serving/engine.py).  Padding is exact for causal
    attention-only stacks: pads are strictly FUTURE positions (causal masks
    keep them out of every true query's softmax), their K/V writes are
    dropped by the masked fill (attention.py::fill_kv_cache — on a wrapped
    ring a pad write would clobber still-needed true K/V), and the returned
    logits come from position n_valid - 1, not the padded tail.  It is NOT
    exact for recurrent carries (hymba SSM h, xLSTM states — the final carry
    would include pad steps) or MoE routing (pad tokens would consume expert
    capacity), so the engine only buckets plain-transformer non-MoE configs;
    passing n_valid == S is exact for every family (and is how the engine's
    unbucketed configs exercise this path).
    """
    assert cfg.causal, "prefill/decode undefined for encoder-only models"
    h, states, _ = lm_forward(
        params, cfg, batch, collect_states=True, masks=masks, pack=pack,
        attn_sched=attn_sched,
    )
    B = h.shape[0]
    S_ = h.shape[1]
    caches = init_caches(cfg, B, max_len)
    layer_ms = masks["layers"] if masks is not None else [None] * cfg.n_layers
    layer_pk = pack["layers"] if pack is not None else [None] * cfg.n_layers
    for i, st in enumerate(states):
        if cfg.block_type == "xlstm":
            key = "slstm" if cfg.is_slstm(i) else "mlstm"
            caches[i][key] = st
            continue
        if cfg.block_type == "hymba":
            kv, ssm_h, pre = st
            caches[i]["ssm"]["h"] = ssm_h
            # conv state: last 3 *pre-conv* inner activations — the in_proj
            # recompute dispatches like any other sparse matmul
            ssm_p = params["layers"][i]["ssm"]
            m_ssm = _sub(layer_ms[i], "ssm")
            pk_ssm = _sub(layer_pk[i], "ssm")
            u_raw = linear(
                ssm_p["in_proj"], pre,
                mask=None if m_ssm is None else m_ssm["in_proj"]["w"],
                kernel=cfg.sparse.kernel, block=cfg.sparse.kernel_block,
                pack=None if pk_ssm is None else pk_ssm["in_proj"]["w"],
            )[..., : cfg.ssm_d_inner]
            conv_src = (
                u_raw[:, -3:, :] if n_valid is None
                else jax.lax.dynamic_slice_in_dim(u_raw, n_valid - 3, 3, 1)
            )
            caches[i]["ssm"]["conv"] = conv_src.astype(
                caches[i]["ssm"]["conv"].dtype
            )
        else:
            kv = st
        k, v = kv
        caches[i]["kv"] = A.fill_kv_cache(caches[i]["kv"], k, v, 0,
                                          n_valid=n_valid)
    h_last = (
        h[:, -1:] if n_valid is None
        else jax.lax.dynamic_slice_in_dim(h, n_valid - 1, 1, 1)
    )
    logits = _logits(params, cfg, h_last)
    return logits, caches


def lm_prefill_into(params, cfg, caches, batch, slot, max_len: int, *,
                    masks=None, pack=None, attn_sched=None, n_valid=None,
                    tables=None):
    """Prefill ONE prompt and scatter its state into batched caches at ``slot``.

    The continuous-batching admission path (serving/engine.py): ``caches`` is
    the engine's capacity-sized cache pytree (init_caches(cfg, capacity,
    max_len)), ``batch`` a single-prompt batch (B=1 tokens, optional patches),
    ``slot`` a traced int32 — one jitted trace per prompt LENGTH serves every
    slot.  Runs the ordinary ``lm_prefill`` at B=1 (so ring alignment, the
    hymba conv-state recompute and the xLSTM carries are all the battle-tested
    code path), then row-scatters every cache leaf into ``slot`` with a
    dynamic_update_slice — overwriting whatever the slot's previous (finished)
    request left behind.  Stale positions BEYOND the new prompt are not
    cleared: attn_decode's per-row validity mask (``arange(size) <= pos``)
    guarantees a position is never attended before the ring write that owns
    it, so recycled slots are reuse-safe by construction (tested in
    tests/test_serving_engine.py).

    Returns (last-position logits (1, 1, V), updated caches) — the logits
    produce the request's FIRST generated token, so a gen-N request costs
    exactly N-1 decode steps.

    ``n_valid``: traced count of TRUE (non-padding) sequence positions —
    the engine pads prompts up to a length bucket so one trace serves a
    range of lengths (see lm_prefill for exactness conditions and
    serving/engine.py for the bucketing policy).

    ``tables``: {'global'/'local': (T_g,) int32} page tables for THIS
    request's row — switches ``caches`` to the paged layout
    (init_paged_caches): KV leaves scatter page-wise through the table
    (attention.py::fill_kv_pool — unowned sentinel entries drop), recurrent
    leaves still row-scatter at ``slot``.  The interior prefill is the SAME
    B=1 contiguous-row pass either way, so ring alignment, bucketed-pad
    drops and recurrent recomputes are identical to the contiguous engine —
    which is what makes paged admission token-identical to contiguous.
    """
    logits, row = lm_prefill(
        params, cfg, batch, max_len=max_len, masks=masks, pack=pack,
        attn_sched=attn_sched, n_valid=n_valid,
    )

    def scatter(dst, src):
        return jax.lax.dynamic_update_slice(
            dst, src.astype(dst.dtype), (slot,) + (0,) * (dst.ndim - 1)
        )

    if tables is None:
        return logits, jax.tree_util.tree_map(scatter, caches, row)
    new = []
    for i, (c, r) in enumerate(zip(caches, row)):
        c = dict(c)
        for key in c:
            if key == "kv":
                c["kv"] = A.fill_kv_pool(
                    c["kv"], r["kv"], tables[cache_group(cfg, i)]
                )
            else:
                c[key] = jax.tree_util.tree_map(scatter, c[key], r[key])
        new.append(c)
    return logits, new


def lm_prefill_suffix(params, cfg, caches, batch, table, ctx, *, masks=None,
                      pack=None, n_valid=None):
    """Prefill only the SUFFIX of a prompt whose first ``ctx`` positions are
    already cached in the paged pools (shared-prefix admission,
    serving/engine.py): the whole point of prefix sharing is that the shared
    pages' K/V are never recomputed.

    caches: paged (init_paged_caches); table: (T_g,) int32 — the request's
    GLOBAL-group page table (shared/forked prefix pages first, fresh pages
    after; unowned tail = sentinel); ctx: traced int32 valid cached prefix
    length; batch: B=1 suffix tokens starting at absolute position ctx
    (bucket-padded — ``n_valid`` true suffix count).  Suffix queries attend
    [table-gathered prefix, causal self] (attention.py::
    _attend_with_history) with RoPE at ctx + arange(S), then the suffix K/V
    scatter block-relative at positions ctx.. (fill_kv_pool_suffix).
    Returns (logits at suffix position n_valid - 1, new caches).

    All-global causal transformer stacks only — no recurrent carries to
    replay and no MoE routing over pad tokens; the engine gates prefix
    sharing to exactly these configs.
    """
    assert cfg.causal and cfg.block_type == "transformer", (
        "suffix prefill: all-global causal transformer stacks only"
    )
    tokens = batch["tokens"]
    S_ = tokens.shape[1]
    positions = ctx + jnp.arange(S_)
    histories = [
        {"pool": caches[i]["kv"], "table": table[None], "ctx": ctx}
        for i in range(cfg.n_layers)
    ]
    h, states, _ = lm_forward(
        params, cfg, batch, collect_states=True, masks=masks, pack=pack,
        positions=positions, histories=histories,
    )
    new = []
    for i, st in enumerate(states):
        k, v = st
        new.append({
            "kv": A.fill_kv_pool_suffix(
                caches[i]["kv"], k, v, table, ctx,
                S_ if n_valid is None else n_valid,
            )
        })
    h_last = (
        h[:, -1:] if n_valid is None
        else jax.lax.dynamic_slice_in_dim(h, n_valid - 1, 1, 1)
    )
    logits = _logits(params, cfg, h_last)
    return logits, new


def logits_all_finite(logits):
    """Per-row all-finite reduction over a step's logits — the serving
    engine's in-flight failure detector (docs/serving.md#failure-model).

    logits: (B, V) or (B, 1, V) float.  Returns (B,) bool — True iff every
    logit of the row is finite.  Vocab-padding slots are masked to the
    FINITE sentinel -1e30 by ``_logits`` (never -inf), so a healthy forward
    is all-finite by construction and any NaN/Inf in a row is a real
    numerical fault on that slot.  Computed INSIDE the engine's jitted
    decode/prefill (serving/engine.py::_decode_fn) so the fast path stays
    one dispatch; the host reads one extra (B,) bool per step.
    """
    return jnp.all(jnp.isfinite(logits), axis=tuple(range(1, logits.ndim)))


def _gate_rows(active, new, old):
    """Freeze inactive batch rows of a recurrent-state pytree.

    ``active``: (B,) bool (None => passthrough).  Selects ``new`` rows where
    active, ``old`` rows where not — the recurrent twin of attn_decode's
    dropped cache writes, so a dead slot's decode step is a no-op on EVERY
    piece of per-slot state (KV cache, SSM h/conv, m/sLSTM carries).
    """
    if active is None:
        return new

    def sel(n, o):
        a = active.reshape((active.shape[0],) + (1,) * (n.ndim - 1))
        return jnp.where(a, n, o)

    return jax.tree_util.tree_map(sel, new, old)


def lm_decode(params, cfg, caches, tokens, pos, *, masks=None, pack=None,
              active=None, tables=None):
    """One decode step. tokens: (B, 1) int32; pos: traced scalar OR (B,).

    ``tables``: {'global'/'local': (B, T_g) int32} per-slot block tables —
    switches ``caches`` to the PAGED layout (init_paged_caches): each
    layer's KV step scatter-writes through its group's table and attends
    the table-gathered contiguous view, bit-identical to the contiguous
    cache (attention.py::attn_decode).  Requires per-slot ``pos``.

    Returns (logits (B,1,V), new caches).  With ``masks``, projections and
    MLPs decode through the Pallas sparse kernels (cfg.sparse.kernel) — the
    serve path is weight-bound, so block skipping cuts HBM traffic by the
    block density directly.  ``pack`` (PackState, core/pack.py) additionally
    sizes every block_sparse grid to the true active count; it is computed
    once per topology on the host and REUSED by every decode step — decode
    never re-packs.

    Per-slot decode (serving/engine.py): ``pos`` as a (B,) VECTOR steps every
    batch row at its own depth in one launch (per-row RoPE, ring slots and
    validity masks — see attention.py::attn_decode); ``active`` (B,) bool
    marks live slots — inactive rows' KV writes are dropped, their
    recurrent states (SSM/xLSTM) frozen, and their tokens excluded from MoE
    routing (a stale token must not consume per-expert capacity and perturb
    active rows' logits — moe.py), so a parked slot is bit-untouched AND
    side-effect-free until a new request is admitted into it
    (lm_prefill_into).  The scalar form is the legacy lockstep contract,
    unchanged.
    """
    assert cfg.causal
    x = _embed_inputs(params, cfg, {"tokens": tokens})
    new_caches = []
    layer_ms = masks["layers"] if masks is not None else [None] * cfg.n_layers
    layer_pk = pack["layers"] if pack is not None else [None] * cfg.n_layers
    for i, p in enumerate(params["layers"]):
        m = layer_ms[i]
        pk = layer_pk[i]
        c = dict(caches[i])
        if cfg.block_type == "xlstm":
            h = rmsnorm(p["ln1"], x, cfg.norm_eps)
            if cfg.is_slstm(i):
                o, new_st = X.slstm_decode(
                    p["slstm"], h, c["slstm"], cfg,
                    masks=_sub(m, "slstm"), pack=_sub(pk, "slstm"),
                )
                c["slstm"] = _gate_rows(active, new_st, c["slstm"])
            else:
                o, new_st = X.mlstm_decode(
                    p["mlstm"], h, c["mlstm"], cfg,
                    masks=_sub(m, "mlstm"), pack=_sub(pk, "mlstm"),
                )
                c["mlstm"] = _gate_rows(active, new_st, c["mlstm"])
            x = x + o
            new_caches.append(c)
            continue

        kind = cfg.layer_kind(i)
        h = rmsnorm(p["ln1"], x, cfg.norm_eps)
        attn_out, c["kv"] = A.attn_decode(
            p["attn"], h, c["kv"], pos, cfg, kind=kind, masks=_sub(m, "attn"),
            pack=_sub(pk, "attn"), active=active,
            table=None if tables is None else tables[cache_group(cfg, i)],
        )
        if cfg.block_type == "hymba":
            ssm_out, new_ssm = S.ssm_decode(
                p["ssm"], h, c["ssm"], cfg,
                masks=_sub(m, "ssm"), pack=_sub(pk, "ssm"),
            )
            c["ssm"] = _gate_rows(active, new_ssm, c["ssm"])
            attn_out = 0.5 * (
                rmsnorm(p["attn_norm"], attn_out, cfg.norm_eps)
                + rmsnorm(p["ssm_norm"], ssm_out, cfg.norm_eps)
            )
        if cfg.post_norms:
            attn_out = rmsnorm(p["ln1_post"], attn_out, cfg.norm_eps)
        if cfg.parallel_block:
            ff_in = h
        else:
            x = x + attn_out
            ff_in = rmsnorm(p["ln2"], x, cfg.norm_eps)
        if cfg.n_experts:
            # active threads into routing: a dead slot's stale token must not
            # consume per-expert capacity C (cross-token state — see moe.py)
            ff_out, _ = moe(
                p["moe"], ff_in, cfg, masks=_sub(m, "moe"),
                pack=_sub(pk, "moe"), active=active,
            )
        elif cfg.d_ff:
            ff_out = mlp(
                p["mlp"], ff_in, cfg.mlp_kind, masks=_sub(m, "mlp"),
                kernel=cfg.sparse.kernel, block=cfg.sparse.kernel_block,
                pack=_sub(pk, "mlp"),
            )
        else:
            ff_out = 0.0
        if cfg.post_norms and cfg.d_ff:
            ff_out = rmsnorm(p["ln2_post"], ff_out, cfg.norm_eps)
        x = (x + attn_out + ff_out) if cfg.parallel_block else (x + ff_out)
        new_caches.append(c)

    h = rmsnorm(params["ln_f"], x, cfg.norm_eps)
    return _logits(params, cfg, h), new_caches
