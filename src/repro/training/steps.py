"""Step functions: sparse train step, RigL update step, serve steps.

Two compiled functions (paper Appendix H cost structure):

  train_step  — every step: masked fwd/bwd, optimizer on MASKED grads.
                One backward gives both gradients: we differentiate w.r.t. the
                effective weights w_eff = w * m, so the gradient is dense;
                g_sparse = g_dense * m feeds the optimizer.  Under pjit the
                dense gradient is a global (mesh-wide) array — the paper's
                Appendix M replica-sync bugs are impossible by construction.

  rigl_step   — every delta_t steps (t < T_end): same backward, then
                drop/grow (core.rigl), zero-init grown weights, reset their
                optimizer state.  Per Algorithm 1 the update step does NOT
                also take an optimizer step.

Kernel dispatch (cfg.sparse.kernel != 'dense'): train_step switches to the
Pallas sparse kernels — raw params + mask threading, no apply_masks, sparse
fwd AND bwd (kernels/).  The dense-gradient side channel every grow score
needs (|g| for rigl, |momentum| for snfs) comes from the Top-KAST backward
superset (core/rigl.py, docs/training.md#topkast): the state carries
``bwd_masks`` — per-layer B = A ∪ top-Δ exploration — and the pack routes
the wgrad kernels onto B's wider grid, so the gradient arriving at the
optimizer (and the SNFS momentum buffer) is the dense gradient restricted to
B with ZERO dense matmuls anywhere, every step AND at topology updates.
``method='topkast'`` additionally trains the exploration set B\\A itself
(optimizer on g⊙B) and drops/grows by magnitude within B.  Without kernel
dispatch the legacy cost split applies: rigl_step runs a dense backward,
amortized over delta_t >= 100 steps (paper Appendix H).
"""
from __future__ import annotations

import dataclasses
import functools
import inspect
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from ..core import (
    LayerSpec,
    SparseAlgo,
    UpdateSchedule,
    apply_masks,
    build_bwd_carrier,
    build_pack_state,
    dense_to_sparse_grad,
    get_distribution,
    init_masks,
    is_pack_entry,
    pack_mismatch,
    refresh_pack_state,
    rigl_update,
    snip_masks,
    topkast_backward_masks,
    tree_paths,
    validate_pack,
)
from ..core.pruning import PruningSchedule, prune_step
from ..models import init_lm, lm_loss
from ..obs import region
from ..optim import (
    LRSchedule,
    OptConfig,
    apply_opt,
    apply_opt_fused,
    init_opt,
    reset_connections,
    reset_new_connections,
)

__all__ = [
    "sparsity_map",
    "init_weights",
    "init_train_state",
    "make_train_step",
    "make_rigl_step",
    "make_prune_fn",
    "snip_init",
    "refresh_pack",
    "refresh_superset",
    "needs_bwd_masks",
]


def sparsity_map(cfg, params, sparse_flags) -> dict[str, float]:
    """Per-path target sparsities from the config's distribution."""
    flat_p = tree_paths(params)
    flat_f = tree_paths(sparse_flags)
    # official-code semantics: the distribution (and its nnz budget) is solved
    # over the MASKED layers only — embeddings/norms/biases are outside it.
    specs = [
        LayerSpec(name, flat_p[name].shape) for name, flag in flat_f.items() if flag
    ]
    sp = cfg.sparse
    dist = get_distribution(sp.distribution, specs, sp.sparsity, dense_first=False)
    return dist


def make_algo(cfg, total_steps: int) -> SparseAlgo:
    sp = cfg.sparse
    return SparseAlgo(
        method=sp.method,
        schedule=UpdateSchedule(
            delta_t=sp.delta_t,
            t_end=int(sp.t_end_fraction * total_steps),
            alpha=sp.alpha,
        ),
        grow_init=sp.grow_init,
        block_shape=sp.block_shape,
        backward_extra=getattr(sp, "backward_extra", 0.1),
    )


def needs_bwd_masks(sp) -> bool:
    """Does this config's state carry Top-KAST backward supersets?

    Yes for method='topkast' (any kernel: its optimizer trains B, its grow
    set lives inside B) and for rigl/snfs under kernel dispatch (the superset
    gradient is their dense-side grow-score channel — the sparse backward
    never computes a dense gradient, docs/training.md#topkast).
    """
    if sp.method == "pruning" or sp.sparsity == 0.0:
        return False
    dispatch = sp.kernel in ("masked", "block_sparse")
    return sp.method == "topkast" or (
        dispatch and sp.method in ("rigl", "snfs")
    )


def init_weights(k_params, k_masks, cfg):
    """Fresh sparse weights -> (params, masks, axes, sparse_flags).

    The weights and topology half of ``init_train_state`` (which passes the
    first two of its three key splits), shared with serving's init
    (launch/serve.py::init_serving_state), which needs no optimizer state.
    Params are zeroed off-mask.
    """
    params, axes, sparse_flags = init_lm(k_params, cfg)
    if cfg.param_dtype == "bfloat16":
        # pure-bf16 weights (f32 optimizer master state lives in opt_state
        # unless OptConfig.state_dtype says otherwise) — needed to fit the
        # 314B grok cell in 16G HBM; see EXPERIMENTS.md.
        params = jax.tree_util.tree_map(
            lambda p: p.astype(jnp.bfloat16) if p.dtype == jnp.float32 else p,
            params,
        )
    sp = cfg.sparse
    if sp.method == "pruning" or sp.sparsity == 0.0:
        # dense start: all-ones masks on sparsifiable layers (pruning tightens)
        masks = jax.tree_util.tree_map(
            lambda p, f: jnp.ones(p.shape, jnp.bool_) if f else None,
            params,
            sparse_flags,
        )
    else:
        smap = sparsity_map(cfg, params, sparse_flags)
        if sp.kernel == "block_sparse":
            from ..configs.base import validate_sparse_kernel

            validate_sparse_kernel(sp)  # clean error when block_shape unset
            # static shape check: random_block_mask silently falls back to
            # elementwise masks on non-divisible layers, which the block
            # kernel would execute WRONGLY (whole blocks run unmasked) —
            # fail loudly instead of training a corrupted topology.  2-D
            # weights dispatch through the plain kernels; 3-D weight BANKS
            # (MoE experts, xLSTM per-head recurrences) dispatch through the
            # grouped kernels, whose blocks tile the trailing two dims.
            bs = sp.block_shape
            flat_p = tree_paths(params)
            bad = [
                name
                for name in smap
                if len(flat_p[name].shape) not in (2, 3)
                or flat_p[name].shape[-2] % bs[0]
                or flat_p[name].shape[-1] % bs[1]
            ]
            if bad:
                raise ValueError(
                    f"sparse.kernel='block_sparse' with block_shape={bs} "
                    f"does not tile these sparsifiable layers: {bad}; "
                    "choose a block edge dividing every layer dim"
                )
        # block-aligned init when block mode is on, so the topology is
        # executable by the block-sparse kernel from the very first step
        masks = init_masks(k_masks, params, smap, block_shape=sp.block_shape)
        # zero-out masked weights at init so nnz(w) matches the mask; the
        # donation masks in place, so init never holds two copies of params
        params = jax.jit(apply_masks, donate_argnums=0)(params, masks)
    return params, masks, axes, sparse_flags


def init_train_state(key, cfg, opt_cfg: OptConfig, *, loss_fn=None):
    """State dict: step/params/masks/opt/rng (+dense_mom for SNFS)."""
    k1, k2, k3 = jax.random.split(key, 3)
    params, masks, axes, sparse_flags = init_weights(k1, k2, cfg)
    sp = cfg.sparse
    state = {
        "step": jnp.zeros((), jnp.int32),
        "params": params,
        "masks": masks,
        "opt": init_opt(opt_cfg, params),
        "rng": k3,
        # lifetime count of steps whose loss/grads were non-finite and whose
        # optimizer update was therefore SKIPPED (params bit-unchanged) —
        # see make_train_step; checkpointed so restarts keep the tally
        "nonfinite_steps": jnp.zeros((), jnp.int32),
    }
    if needs_bwd_masks(sp):
        # Top-KAST backward supersets B ⊇ A (core/rigl.py): the wgrad side
        # channel for every grow score under kernel dispatch, and the trained
        # exploration set for method='topkast'.  Refreshed alongside the pack
        # after every topology update (refresh_superset).
        state["bwd_masks"] = topkast_backward_masks(
            params, masks, sp.backward_extra, jax.random.fold_in(k2, 1),
            block_shape=sp.block_shape,
        )
    if sp.kernel == "block_sparse" and sp.block_shape is not None:
        # host-packed tight-grid topology, carried in state + checkpointed.
        # INVARIANT: pack always describes state["masks"] — every rigl_step
        # must be followed by refresh_pack() (launch/train.py does this); the
        # train step's pack_stale metric reports any violation.
        state["pack"] = build_pack_state(
            masks, sp.block_shape, slack=getattr(sp, "pack_width_slack", 0.0),
            bwd_masks=state.get("bwd_masks"),
        )
    elif sp.kernel == "masked" and "bwd_masks" in state:
        # masked kernel needs no CSC pack — the superset rides along as the
        # elementwise carrier the Top-KAST masked VJP fuses (core/pack.py)
        state["pack"] = build_bwd_carrier(state["bwd_masks"])
    if sp.method == "snfs":
        state["dense_mom"] = jax.tree_util.tree_map(jnp.zeros_like, params)
    return state, axes, sparse_flags


def refresh_superset(state, cfg, obs=None):
    """Redraw the Top-KAST backward supersets from the CURRENT masks/params.

    Called from refresh_pack right after every topology update.  For
    method='topkast' the exploration set is itself trained, so connections
    LEAVING the superset (B_old \\ B_new) are zeroed and their optimizer
    state reset — preserving the invariant that weights outside B are exactly
    0 (which is what makes ``grown`` connections zero-initialized for free).
    For rigl/snfs under dispatch the optimizer only ever touches A, so the
    redraw just moves the gradient side-channel.  SNFS's dense-momentum
    buffer is masked to the new superset either way: coordinates without a
    gradient channel must not carry stale momentum into grow scores.
    No-op for states without backward masks.

    Regions (obs/trace.py): ``repro.refresh_pack.drain`` is the host reading
    the step counter, which waits for the step that produced the state (the
    update step); ``repro.refresh_pack.superset`` dispatches the redraw,
    which runs on the device while the host goes on.
    """
    if "bwd_masks" not in state:
        return state
    sp = cfg.sparse
    with region("repro.refresh_pack.drain", obs=obs):
        step = int(state["step"])
    with region("repro.refresh_pack.superset", obs=obs):
        key = jax.random.fold_in(state["rng"], 2 ** 20 + step)
        new_b = topkast_backward_masks(
            state["params"], state["masks"], sp.backward_extra, key,
            block_shape=sp.block_shape,
        )
    new_state = dict(state, bwd_masks=new_b)
    if sp.method == "topkast":
        leavers = jax.tree_util.tree_map(
            lambda o, n: None if o is None else o.astype(bool) & ~n.astype(bool),
            state["bwd_masks"],
            new_b,
            is_leaf=lambda x: x is None,
        )
        new_state["params"] = jax.tree_util.tree_map(
            lambda w, l: w if l is None else jnp.where(l, 0, w).astype(w.dtype),
            state["params"],
            leavers,
            is_leaf=lambda x: x is None,
        )
        new_state["opt"] = reset_connections(state["opt"], leavers)
    if "dense_mom" in state:
        new_state["dense_mom"] = jax.tree_util.tree_map(
            lambda mo, b: mo if b is None else mo * b.astype(mo.dtype),
            state["dense_mom"],
            new_b,
            is_leaf=lambda x: x is None,
        )
    return new_state


def refresh_pack(state, cfg, obs=None):
    """Refresh superset + re-pack state["pack"] from state["masks"].

    Call right after EVERY topology-update step (host-side, amortized over
    delta_t).  First redraws the backward supersets (refresh_superset), then
    rebuilds the pack the kernels consume — the block_sparse CSC/CSR (+
    superset bidx view) or the masked-kernel bwd_mask carrier.  No-op for
    states without a pack.
    Widths never shrink (core/pack.py), so the jitted train step only
    retraces when a layer's max active-block count grows past its packed
    width — bounded drift, not per-update churn.
    ``cfg.sparse.pack_width_slack`` > 0 additionally rounds refreshed widths
    up to the next slack step (core.pack.slack_width), trading a few padded
    grid iterations for fewer retraces when production topologies drift.

    The whole refresh is the region ``repro.refresh_pack``, the parent of
    refresh_superset's and the pack's phase regions and of
    ``repro.pack.validate`` (docs/observability.md#span-taxonomy); ``obs``
    is an optional Observability handle that also puts them in its ring.
    """
    with region("repro.refresh_pack", obs=obs):
        state = refresh_superset(state, cfg, obs)
        if "pack" not in state:
            return state
        if cfg.sparse.kernel == "masked":
            return dict(state, pack=build_bwd_carrier(state["bwd_masks"]))
        pack = refresh_pack_state(
            state["masks"], cfg.sparse.block_shape, prev=state["pack"],
            slack=getattr(cfg.sparse, "pack_width_slack", 0.0),
            bwd_masks=state.get("bwd_masks"), obs=obs,
        )
        # integrity guard (core/pack.py::validate_pack): a refresh that
        # produced inconsistent CSC/CSR books would make every subsequent
        # kernel launch execute the wrong topology — cheap host-side check,
        # loud failure
        with region("repro.pack.validate", obs=obs):
            validate_pack(pack, where="refresh_pack")
        return dict(state, pack=pack)


def make_train_step(
    cfg,
    opt_cfg: OptConfig,
    lr_sched: LRSchedule,
    *,
    loss_fn: Callable | None = None,
    snfs_momentum: float = 0.9,
):
    """Build the hot-path step.

    With ``cfg.sparse.kernel`` in {'masked', 'block_sparse'} the step runs in
    KERNEL-DISPATCH mode: the loss is computed on RAW params with the mask
    pytree threaded into the model, every dispatched matmul (fwd and bwd)
    executes through the Pallas sparse kernels, and ``apply_masks`` is never
    called — the masked weight copy w⊙m is never materialized in HBM.  The
    gradient that comes back is already the paper's sparse gradient (the
    custom-VJP wgrad kernels fuse g⊙m), so the optimizer path is unchanged.

    SNFS needs a dense-gradient side channel every step for its momentum
    buffer; under dispatch the state's Top-KAST backward superset provides it
    (the wgrad kernels return the dense gradient restricted to B ⊇ A — see
    needs_bwd_masks), so snfs runs on the sparse kernels too.  For
    method='topkast' the optimizer itself trains the superset: grads (and
    weight decay) are masked by ``bwd_masks`` instead of ``masks``.

    With kernel='block_sparse' the state additionally carries
    ``state["pack"]`` (PackState, core/pack.py): the host-packed tight block
    topology is threaded into the kernels so every grid launches the TRUE
    active-block count instead of the worst-case padded width.  The step
    reports a ``pack_stale`` metric — nonzero iff the pack no longer matches
    the masks (i.e. a rigl_step ran without refresh_pack()).
    """
    dispatch = cfg.sparse.kernel not in (None, "dense")
    is_topkast = cfg.sparse.method == "topkast"
    if dispatch:
        from ..configs.base import validate_sparse_kernel

        validate_sparse_kernel(cfg.sparse)
    fused = dispatch and getattr(cfg.sparse, "fused_epilogue", False)
    if getattr(cfg.sparse, "fused_epilogue", False):
        # the fused path replaces the wgrad cotangent with the NEW MOMENTUM
        # (kernels/masked_matmul.py fused_* docstrings) — it only exists for
        # plain SGD+momentum single-microbatch steps; anything else would
        # silently compute a different update, so refuse loudly instead.
        bad = []
        if not dispatch:
            bad.append("kernel dispatch off (sparse.kernel is dense/None)")
        if opt_cfg.kind != "sgd":
            bad.append(f"optimizer kind {opt_cfg.kind!r} (need plain sgd)")
        if opt_cfg.nesterov:
            bad.append("nesterov (the kernel epilogue emits plain momentum)")
        if opt_cfg.grad_clip:
            bad.append("grad_clip (the raw gradient never exists to clip)")
        if max(getattr(cfg, "microbatches", 1), 1) != 1:
            bad.append("microbatches > 1 (the epilogue folds mom ONCE/step)")
        if cfg.sparse.method == "snfs":
            bad.append("method='snfs' (its dense-momentum buffer needs the "
                       "raw superset gradient every step)")
        if getattr(cfg, "bf16_grads", False):
            bad.append("bf16_grads (cotangent dtype must match the weights)")
        if cfg.dtype != "float32" and opt_cfg.state_dtype != "bfloat16":
            bad.append(
                f"compute dtype {cfg.dtype!r} with f32 optimizer state (the "
                "kernel would nearest-round momentum to the compute dtype; "
                "use dtype='float32', or opt in to bf16 momentum via "
                "OptConfig.state_dtype='bfloat16' for in-kernel stochastic "
                "rounding)"
            )
        if bad:
            raise ValueError(
                "sparse.fused_epilogue=True is unsupported with: "
                + "; ".join(bad)
            )
    if loss_fn is None:
        loss_fn = lambda p, b, masks=None, pack=None: lm_loss(
            p, cfg, b, masks=masks, pack=pack
        )
    elif dispatch and "masks" not in inspect.signature(loss_fn).parameters:
        raise ValueError(
            "kernel dispatch needs a loss_fn accepting masks= (raw params + "
            "mask threading); got one without it"
        )
    # PackState (tight block_sparse grids) is an optimization, not a contract:
    # custom loss_fns without a pack= parameter just fall back to the padded
    # traced pack.
    loss_accepts_pack = "pack" in inspect.signature(loss_fn).parameters
    if fused and not loss_accepts_pack:
        raise ValueError(
            "sparse.fused_epilogue=True needs a loss_fn accepting pack= — "
            "the momentum/seed epilogue operands ride in on the pack entries"
        )
    mb = max(getattr(cfg, "microbatches", 1), 1)
    acc_dt = jnp.bfloat16 if getattr(cfg, "grad_accum_dtype", "") == "bfloat16" else jnp.float32

    def _grads(w_eff, batch, masks=None, pack=None):
        if masks is None:
            loss_fn_ = loss_fn
        elif pack is not None and loss_accepts_pack:
            loss_fn_ = lambda p, b: loss_fn(p, b, masks=masks, pack=pack)
        else:
            loss_fn_ = lambda p, b: loss_fn(p, b, masks=masks)
        if mb == 1:
            return jax.value_and_grad(loss_fn_)(w_eff, batch)
        # gradient accumulation: one microbatch's activations live at a time
        bsz = jax.tree_util.tree_leaves(batch)[0].shape[0] // mb
        init = (
            jnp.float32(0.0),
            jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, acc_dt), w_eff),
        )

        def acc(carry, sub):
            loss_acc, g_acc = carry
            li, gi = jax.value_and_grad(loss_fn_)(w_eff, sub)
            g_acc = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(acc_dt), g_acc, gi
            )
            return loss_acc + li, g_acc

        if getattr(cfg, "scan_microbatches", False):
            # small-HLO form (production + full-depth dry-run compile);
            # cost_analysis counts the body once, so roofline lowering uses
            # the unrolled branch below instead (DESIGN.md §8).
            stacked = jax.tree_util.tree_map(
                lambda x: x.reshape(mb, bsz, *x.shape[1:]), batch
            )
            (loss_acc, g_acc), _ = jax.lax.scan(
                lambda c, s: (acc(c, s), None), init, stacked
            )
        else:
            loss_acc, g_acc = init
            for i in range(mb):
                sub = jax.tree_util.tree_map(
                    lambda x: jax.lax.dynamic_slice_in_dim(x, i * bsz, bsz, 0),
                    batch,
                )
                loss_acc, g_acc = acc((loss_acc, g_acc), sub)
        inv = 1.0 / mb
        return loss_acc * inv, jax.tree_util.tree_map(lambda g: g * inv, g_acc)

    def train_step(state, batch):
        # KERNEL DISPATCH: raw params + mask threading; no apply_masks — w⊙m
        # lives only inside the kernels' VMEM pipelines and the returned
        # gradient is already masked (custom-VJP wgrad).  Legacy: pre-masked
        # effective weights, dense XLA matmuls.
        src = (
            state["params"]
            if dispatch
            else apply_masks(state["params"], state["masks"])
        )
        if getattr(cfg, "bf16_grads", False):
            # single downcast => bf16 cotangents => bf16 DP grad all-reduce
            src = jax.tree_util.tree_map(
                lambda w: w.astype(jnp.bfloat16)
                if w.dtype == jnp.float32
                else w,
                src,
            )
        if dispatch and needs_bwd_masks(cfg.sparse):
            # trace-time totality guard: EVERY dispatched layer must carry a
            # backward-superset pack view, else its wgrad would silently run
            # on the forward topology (or a dense matmul) instead of B's grid
            from ..models.layers import assert_total_dispatch

            assert_total_dispatch(
                state["masks"], (), kernel=cfg.sparse.kernel,
                where="train_step", pack=state.get("pack"), require_bwd=True,
            )
        pack = state.get("pack") if dispatch else None
        if fused:
            # FUSED EPILOGUE (docs/kernels.md#fused-epilogue): merge the SGD
            # operands into each dispatched pack entry.  layers.py routes
            # entries carrying "mom" onto the fused wgrad kernels, whose
            # weight cotangent IS the new momentum m_new = mu*mom + dw + wd*w
            # (masked to the wgrad support) — the raw dw never round-trips
            # through HBM.  mu/wd/sr are python statics baked into the trace;
            # mom/seed are traced operands.
            mu_, wd_ = opt_cfg.momentum, opt_cfg.weight_decay
            sr_ = opt_cfg.state_dtype == "bfloat16"
            is_none = lambda x: x is None
            flat_m, treedef = jax.tree_util.tree_flatten(
                state["masks"], is_leaf=is_none
            )
            flat_pe = (
                jax.tree_util.tree_leaves(pack, is_leaf=is_pack_entry)
                if pack is not None
                else [None] * len(flat_m)
            )
            flat_mom = jax.tree_util.tree_flatten(
                state["opt"]["momentum"], is_leaf=is_none
            )[0]
            entries = []
            for i, (m, pe, mo) in enumerate(zip(flat_m, flat_pe, flat_mom)):
                if m is None:
                    entries.append(None)
                    continue
                seed = (
                    state["step"] * jnp.int32(1000003) + jnp.int32(i)
                ).reshape(1)
                entries.append(
                    dict(pe or {})
                    | {"mom": mo, "seed": seed, "mu": mu_, "wd": wd_, "sr": sr_}
                )
            pack = jax.tree_util.tree_unflatten(treedef, entries)
        loss, g_dense = _grads(
            src,
            batch,
            masks=state["masks"] if dispatch else None,
            pack=pack,
        )
        # topkast trains the whole backward superset B (exploration set gets
        # optimizer updates); every other method optimizes A only.
        opt_masks = (
            state["bwd_masks"]
            if is_topkast and "bwd_masks" in state
            else state["masks"]
        )
        g_sparse = dense_to_sparse_grad(g_dense, opt_masks)
        # weight decay on ACTIVE weights only (inactive must stay untouched).
        # In dispatch mode src is RAW, so decay through the mask: m is bool,
        # the product w*m here is a grad-sized elementwise op, not a second
        # resident weight copy.
        if opt_cfg.weight_decay:
            wd = opt_cfg.weight_decay

            def _decay(g, w, m):
                if fused and m is not None:
                    # wd on dispatched leaves is folded into the kernel
                    # epilogue (g here is already m_new = mu*mom + dw + wd*w)
                    return g
                w_act = w if m is None else w * m.astype(w.dtype)
                return g + wd * w_act.astype(g.dtype)

            if dispatch or is_topkast:
                # decay over the OPTIMIZED support: A for rigl/set/snfs,
                # the backward superset B for topkast (its exploration
                # weights are trained, so they decay too); raw params carry
                # the B-supported values even in legacy mode.
                g_sparse = jax.tree_util.tree_map(
                    _decay, g_sparse, state["params"], opt_masks,
                    is_leaf=lambda x: x is None,
                )
            else:
                g_sparse = jax.tree_util.tree_map(
                    lambda g, w: g + wd * w.astype(g.dtype), g_sparse, src
                )
        lr = lr_sched(state["step"])
        # NOTE: in fused mode the dispatched leaves of g_sparse are the NEW
        # MOMENTUM (the raw gradient never exists in HBM), so grad_norm
        # reports the momentum-update norm there.  The nonfinite guard below
        # stays valid: m_new is finite iff the gradient contribution is.
        gnorm = jnp.sqrt(
            sum(
                jnp.sum(jnp.square(g.astype(jnp.float32)))
                for g in jax.tree_util.tree_leaves(g_sparse)
            )
        )
        # non-finite guard: a NaN/Inf loss or gradient must not touch the
        # params — one poisoned batch would otherwise destroy the run (and
        # under kernel dispatch, silently corrupt the sparse topology's
        # weights).  gnorm is finite iff every grad leaf is, so one scalar
        # decides; the update is SELECTED rather than branched so the step
        # stays a single XLA program (the skip costs one where() per leaf).
        ok = jnp.isfinite(loss) & jnp.isfinite(gnorm)
        opt_nowd = dataclasses.replace(opt_cfg, weight_decay=0.0)
        if fused:
            # dispatched leaves already carry m_new; plain leaves (embeddings,
            # norms) get the standard SGD+momentum update inside apply_opt_fused
            fused_flags = jax.tree_util.tree_map(
                lambda m: m is not None, opt_masks, is_leaf=lambda x: x is None
            )
            new_params, new_opt = apply_opt_fused(
                opt_nowd, g_sparse, state["opt"], state["params"], lr,
                fused_flags,
            )
        else:
            new_params, new_opt = apply_opt(
                opt_nowd, g_sparse, state["opt"], state["params"], lr
            )
        keep = lambda new, old: jax.tree_util.tree_map(
            lambda n, o: jnp.where(ok, n, o), new, old
        )
        nonfinite_steps = (
            state.get("nonfinite_steps", jnp.zeros((), jnp.int32))
            + (~ok).astype(jnp.int32)
        )
        new_state = dict(
            state,
            step=state["step"] + 1,  # the step index advances regardless
            params=keep(new_params, state["params"]),
            opt=keep(new_opt, state["opt"]),
            nonfinite_steps=nonfinite_steps,
        )
        if "dense_mom" in state:  # SNFS tracks dense-gradient momentum
            new_state["dense_mom"] = keep(
                jax.tree_util.tree_map(
                    lambda m, g: snfs_momentum * m + g.astype(m.dtype),
                    state["dense_mom"],
                    g_dense,
                ),
                state["dense_mom"],
            )
        metrics = {
            "loss": loss,
            "lr": lr,
            "grad_norm": gnorm,
            "nonfinite_steps": nonfinite_steps,
        }
        if dispatch and "pack" in state and cfg.sparse.kernel == "block_sparse":
            # staleness canary: #blocks where the packed topology disagrees
            # with the masks (incl. the superset bidx view when present).
            # Nonzero means a rigl_step ran without refresh_pack() and the
            # kernels execute a STALE topology — cheap to compute (tiny block
            # grids), surfaced every step.
            metrics["pack_stale"] = pack_mismatch(
                state["masks"], state["pack"], cfg.sparse.block_shape,
                bwd_masks=state.get("bwd_masks"),
            )
        return new_state, metrics

    return train_step


def make_rigl_step(cfg, algo: SparseAlgo, lr_sched: LRSchedule, *, loss_fn=None):
    """Topology-update step.

    Without kernel dispatch this is the paper's amortized DENSE backward
    (apply_masks + XLA matmuls): grow needs |dense grad| at inactive
    coordinates, which the sparse kernels never compute; delta_t >= 100
    amortizes the cost (Appendix H).

    Under kernel dispatch with backward supersets in the state
    (needs_bwd_masks) the update stays on the sparse kernels end-to-end: the
    backward returns the dense gradient restricted to B ⊇ A — exactly the
    grow-score channel rigl needs (and the momentum snfs accumulated every
    step) — so NO dense gradient is ever materialized.  Grow candidates are
    thereby restricted to the superset: coordinates outside B carry no
    gradient signal and score zero.  For method='topkast' the drop/grow is
    magnitude-driven inside B and needs no gradient at all (rigl_update).
    """
    dispatch = cfg.sparse.kernel not in (None, "dense")
    if loss_fn is None:
        loss_fn = lambda p, b, masks=None, pack=None: lm_loss(
            p, cfg, b, masks=masks, pack=pack
        )
    sig = inspect.signature(loss_fn).parameters
    accepts_masks = "masks" in sig
    accepts_pack = "pack" in sig

    def rigl_step(state, batch):
        if dispatch and accepts_masks and "bwd_masks" in state:
            # sparse backward on the superset-routed kernels: g_dense below
            # is the dense gradient ⊙ B, computed with zero dense matmuls
            pack = state.get("pack")
            if pack is not None and accepts_pack:
                lf = lambda p, b: loss_fn(
                    p, b, masks=state["masks"], pack=pack
                )
            else:
                lf = lambda p, b: loss_fn(p, b, masks=state["masks"])
            loss, g_dense = jax.value_and_grad(lf)(state["params"], batch)
        else:
            w_eff = apply_masks(state["params"], state["masks"])
            loss, g_dense = jax.value_and_grad(loss_fn)(w_eff, batch)
        key = jax.random.fold_in(state["rng"], state["step"])
        new_params, new_masks, grown = rigl_update(
            state["params"],
            state["masks"],
            g_dense,
            state["step"],
            algo,
            key,
            dense_momentum=state.get("dense_mom"),
            lr=float(lr_sched.base_lr),
            bwd_masks=state.get("bwd_masks"),
        )
        new_opt = reset_new_connections(state["opt"], grown)
        new_state = dict(
            state,
            step=state["step"] + 1,
            params=new_params,
            masks=new_masks,
            opt=new_opt,
        )
        return new_state, {"loss": loss}

    return rigl_step


def make_prune_fn(cfg, sched: PruningSchedule):
    def fn(state):
        new_params, new_masks = prune_step(
            state["params"], state["masks"], state["step"], sched
        )
        return dict(state, params=new_params, masks=new_masks)

    return fn


def snip_init(state, cfg, batch, *, loss_fn=None, saliency="weight_times_grad"):
    """Replace masks with one-shot SNIP masks computed on one batch."""
    loss_fn = loss_fn or (lambda p, b: lm_loss(p, cfg, b))
    _, axes, sparse_flags = init_lm(jax.random.PRNGKey(0), cfg)
    smap = sparsity_map(cfg, state["params"], sparse_flags)
    g = jax.grad(loss_fn)(state["params"], batch)
    masks = snip_masks(state["params"], g, smap, saliency=saliency)
    params = apply_masks(state["params"], masks)
    return dict(state, params=params, masks=masks)
