"""Dry-run machinery: lower + compile every (arch x shape x mesh) cell and
extract memory / cost / collective statistics for the roofline analysis.

No jax device state is touched at import time — launch/dryrun.py (the CLI
entry) sets XLA_FLAGS for 512 host devices before importing anything.

Methodology (DESIGN.md §8): XLA cost_analysis counts lax.scan bodies once and
is reported per-device, so per-layer costs come from *unrolled* depth-(1,2)
lowerings per layer-kind (exact for python-loop models), extrapolated
linearly: total = base + sum_k count_k * delta_k.  The full-depth compile
provides the memory proof + shardability guarantee for every cell.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis.hlo import collective_bytes
from ..analysis.roofline import roofline_terms
from ..configs import SHAPES, SKIPS, get_config
from ..core import tree_paths
from ..models import init_caches, init_lm, lm_decode, lm_prefill
from ..optim import LRSchedule, OptConfig
from ..training import init_train_state, make_train_step, make_rigl_step, make_algo, sparsity_map
from .mesh import dp_axes
from .sharding import batch_shardings, cache_axes, param_shardings, state_shardings

__all__ = ["input_specs", "run_cell", "layer_kind_counts"]

ARTIFACTS = pathlib.Path("artifacts/dryrun")


# ---------------------------------------------------------------------------
# abstract inputs
# ---------------------------------------------------------------------------

def input_specs(cfg, shape) -> dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for every model input of a shape cell."""
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    sds = jax.ShapeDtypeStruct
    if cfg.frontend == "frames":
        b = {"frames": sds((B, S, cfg.frontend_dim), jnp.bfloat16)}
        if shape.kind == "train":
            b["targets"] = sds((B, S), i32)
        return b
    s_text = S - (cfg.n_patches if cfg.frontend == "patch" else 0)
    b = {"tokens": sds((B, s_text), i32)}
    if cfg.frontend == "patch":
        b["patches"] = sds((B, cfg.n_patches, cfg.frontend_dim), jnp.bfloat16)
    if shape.kind == "train":
        b["targets"] = sds((B, s_text), i32)
    return b


def _probe_cfg(cfg):
    """Same structure, tiny dims — for extracting the logical-axes tree."""
    return dataclasses.replace(
        cfg,
        d_model=cfg.n_heads * 4,
        head_dim=4,
        d_ff=8 if cfg.d_ff else 0,
        moe_d_ff=8 if cfg.moe_d_ff else 0,
        vocab_size=64,
        frontend_dim=8 if cfg.frontend_dim else 0,
        ssm_d_inner=16 if cfg.ssm_d_inner else 0,
        ssm_state=2 if cfg.ssm_state else 0,
        remat=False,
    )


def get_axes(cfg):
    _, axes, flags = init_lm(jax.random.PRNGKey(0), _probe_cfg(cfg))
    return axes, flags


def abstract_state(cfg, opt_cfg: OptConfig):
    key = jax.random.PRNGKey(0)
    return jax.eval_shape(lambda: init_train_state(key, cfg, opt_cfg)[0])


def active_param_count(cfg, state_abs) -> dict[str, float]:
    """Exact N_total / N_active (per-token) from shapes + the sparsity map."""
    params = state_abs["params"]
    flat = tree_paths(params)
    _, flags = get_axes(cfg)
    flat_flags = tree_paths(flags)
    smap = sparsity_map(cfg, params, flags) if cfg.sparse.sparsity else {}
    total = active = everything = sparsifiable = 0.0
    for name, leaf in flat.items():
        size = float(np.prod(leaf.shape))
        everything += size
        if flat_flags.get(name):
            sparsifiable += size
        if name == "embed/table":
            continue  # lookup, not matmul (6ND convention)
        nnz = size * (1.0 - smap.get(name, 0.0))
        frac = 1.0
        if "/moe/" in name and ("wi/" in name or "wg/" in name or "wo/" in name) and "shared" not in name:
            frac = cfg.top_k / cfg.n_experts  # routed experts: top_k of E active
        total += size
        active += nnz * frac
    if cfg.tie_embeddings and cfg.frontend != "frames":
        d = cfg.d_model
        total += d * cfg.vocab_size
        active += d * cfg.vocab_size
    return {
        "total": total,
        "active": active,
        "all_leaves": everything,
        "sparsifiable": sparsifiable,
    }


# ---------------------------------------------------------------------------
# layer-kind decomposition for cost extrapolation
# ---------------------------------------------------------------------------

def layer_kind_counts(cfg) -> dict[str, int]:
    counts: dict[str, int] = {}
    for i in range(cfg.n_layers):
        if cfg.block_type == "xlstm":
            k = "slstm" if cfg.is_slstm(i) else "mlstm"
        else:
            k = cfg.layer_kind(i)
        counts[k] = counts.get(k, 0) + 1
    return counts


def _kind_cfg(cfg, kind: str, n_layers: int):
    """A config with n_layers layers, all of the given kind."""
    kw: dict[str, Any] = {"n_layers": n_layers}
    if cfg.block_type == "xlstm":
        kw["slstm_every"] = 1 if kind == "slstm" else 0
    else:
        kw["attn_pattern"] = (kind,)
        kw["global_layer_ids"] = ()
    return dataclasses.replace(cfg, **kw)


# ---------------------------------------------------------------------------
# step builders per shape kind
# ---------------------------------------------------------------------------

def _train_setup(cfg, shape, mesh, opt_cfg):
    state_abs = abstract_state(cfg, opt_cfg)
    axes, _ = get_axes(cfg)
    st_sh = state_shardings(state_abs, axes, mesh, fsdp=cfg.fsdp)
    batch_abs = input_specs(cfg, shape)
    b_sh = batch_shardings(batch_abs, mesh)
    lr = LRSchedule(base_lr=0.1, warmup_steps=100, total_steps=32000)
    step = make_train_step(cfg, opt_cfg, lr)
    jitted = jax.jit(step, in_shardings=(st_sh, b_sh), donate_argnums=0)
    return jitted, (state_abs, batch_abs)


def _rigl_setup(cfg, shape, mesh, opt_cfg):
    """The every-delta_t connectivity-update step (drop/grow incl. ranking)."""
    state_abs = abstract_state(cfg, opt_cfg)
    axes, _ = get_axes(cfg)
    st_sh = state_shardings(state_abs, axes, mesh, fsdp=cfg.fsdp)
    batch_abs = input_specs(cfg, shape)
    b_sh = batch_shardings(batch_abs, mesh)
    lr = LRSchedule(base_lr=0.1, warmup_steps=100, total_steps=32000)
    algo = make_algo(cfg, 32000)
    step = make_rigl_step(cfg, algo, lr)
    jitted = jax.jit(step, in_shardings=(st_sh, b_sh), donate_argnums=0)
    return jitted, (state_abs, batch_abs)


def _decode_setup(cfg, shape, mesh, opt_cfg):
    params_abs = jax.eval_shape(
        lambda: init_lm(jax.random.PRNGKey(0), cfg)[0]
    )
    axes, _ = get_axes(cfg)
    p_shapes = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params_abs
    )
    p_sh = param_shardings(axes, p_shapes, mesh, fsdp=cfg.fsdp)
    B, S = shape.global_batch, shape.seq_len
    caches_abs = jax.eval_shape(lambda: init_caches(cfg, B, S))
    c_axes = cache_axes(cfg)
    c_sh = param_shardings(c_axes, caches_abs, mesh, fsdp=False)
    tok_abs = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    tok_sh = batch_shardings(tok_abs, mesh)
    pos_abs = jax.ShapeDtypeStruct((), jnp.int32)
    from jax.sharding import NamedSharding, PartitionSpec

    rep = NamedSharding(mesh, PartitionSpec())

    def serve_step(params, caches, tok, pos):
        return lm_decode(params, cfg, caches, tok, pos)

    jitted = jax.jit(
        serve_step, in_shardings=(p_sh, c_sh, tok_sh, rep), donate_argnums=1
    )
    return jitted, (params_abs, caches_abs, tok_abs, pos_abs)


def _prefill_setup(cfg, shape, mesh, opt_cfg):
    params_abs = jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), cfg)[0])
    axes, _ = get_axes(cfg)
    p_shapes = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params_abs
    )
    p_sh = param_shardings(axes, p_shapes, mesh, fsdp=cfg.fsdp)
    batch_abs = input_specs(cfg, shape)
    b_sh = batch_shardings(batch_abs, mesh)

    if cfg.causal:
        def prefill_step(params, batch):
            return lm_prefill(params, cfg, batch, max_len=shape.seq_len)
    else:
        # encoder-only (hubert): "prefill" = full bidirectional inference
        from ..models import lm_forward
        from ..models.model import _logits

        def prefill_step(params, batch):
            h, _, _ = lm_forward(params, cfg, batch)
            return _logits(params, cfg, h)

    jitted = jax.jit(prefill_step, in_shardings=(p_sh, b_sh))
    return jitted, (params_abs, batch_abs)


_SETUPS = {
    "train": _train_setup,
    "decode": _decode_setup,
    "prefill": _prefill_setup,
    "rigl_update": _rigl_setup,
}


def _lower_cost(cfg, shape, mesh, opt_cfg, kind: str | None = None):
    """(flops, bytes, coll_bytes) per device for this exact cfg."""
    setup = _SETUPS[kind or shape.kind]
    jitted, abstract = setup(cfg, shape, mesh, opt_cfg)
    with jax.set_mesh(mesh):  # ambient mesh for in-model SP constraints
        lowered = jitted.lower(*abstract)
        compiled = lowered.compile()
    ca = compiled.cost_analysis() or {}
    coll = collective_bytes(compiled.as_text())
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "coll": coll,
        "compiled": compiled,
    }


# ---------------------------------------------------------------------------
# the cell runner
# ---------------------------------------------------------------------------

def run_cell(
    arch: str,
    shape_name: str,
    mesh,
    *,
    opt_cfg: OptConfig | None = None,
    full_depth: bool = True,
    proof_only: bool = False,
    cfg_overrides: dict | None = None,
    save: bool = True,
    tag: str = "",
    step_kind: str | None = None,  # e.g. "rigl_update" on a train shape
) -> dict:
    shape = SHAPES[shape_name]
    skip = SKIPS.get((arch, shape_name))
    if skip:
        art = {"arch": arch, "shape": shape_name, "skipped": skip}
        if save:
            desc = "x".join(f"{k}{v}" for k, v in mesh.shape.items())
            _save(art, arch, shape_name, desc, tag)
        return art

    cfg = get_config(arch)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    opt_cfg = opt_cfg or OptConfig(
        kind="sgd",
        momentum=0.9,
        weight_decay=1e-4,
        # bf16-weight models also keep bf16 momentum (grok-1 HBM budget)
        state_dtype="bfloat16" if cfg.param_dtype == "bfloat16" else "float32",
    )
    mesh_desc = "x".join(f"{k}{v}" for k, v in mesh.shape.items())
    chips = int(np.prod(list(mesh.shape.values())))
    t_start = time.time()

    # --- per-layer-kind cost deltas (unrolled depth 1 vs 2) ---
    counts = layer_kind_counts(cfg)
    base = None
    per_kind: dict[str, dict] = {}
    if proof_only:
        counts = {}
        base = {"flops": 0.0, "bytes": 0.0, "coll": 0}
    for kind in counts:
        c1 = _lower_cost(_kind_cfg(cfg, kind, 1), shape, mesh, opt_cfg, kind=step_kind)
        c2 = _lower_cost(_kind_cfg(cfg, kind, 2), shape, mesh, opt_cfg, kind=step_kind)
        delta = {
            "flops": c2["flops"] - c1["flops"],
            "bytes": c2["bytes"] - c1["bytes"],
            "coll": c2["coll"].get("total", 0) - c1["coll"].get("total", 0),
        }
        per_kind[kind] = delta
        if base is None:
            base = {
                "flops": c1["flops"] - delta["flops"],
                "bytes": c1["bytes"] - delta["bytes"],
                "coll": c1["coll"].get("total", 0) - delta["coll"],
                "coll_breakdown_l2": c2["coll"],
            }

    tot = {
        k: base[k] + sum(per_kind[kd][k] * counts[kd] for kd in counts)
        for k in ("flops", "bytes", "coll")
    }

    # --- full-depth compile: shardability proof + collective schedule ---
    mem = {}
    full_coll = {}
    compile_s = None
    if full_depth:
        t0 = time.time()
        cfg_full = dataclasses.replace(cfg, scan_microbatches=True)
        full = _lower_cost(cfg_full, shape, mesh, opt_cfg, kind=step_kind)
        compile_s = time.time() - t0
        ma = full["compiled"].memory_analysis()
        # NOTE: CPU-backend temp bytes assume NO buffer reuse (remat-blind);
        # treated as an upper bound only — see the analytic model below.
        mem = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes_noreuse_upper_bound": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
        }
        full_coll = full["coll"]

    # --- model flops + memory model + roofline ---
    state_abs = abstract_state(cfg, opt_cfg)
    n = active_param_count(cfg, state_abs)
    from ..analysis.memory_model import memory_model

    mem["model"] = memory_model(
        cfg,
        shape,
        dict(mesh.shape),
        n["all_leaves"],
        n["sparsifiable"],
        opt_slots=2 if opt_cfg.kind == "adam" else 1,
        opt_state_bytes=2 if opt_cfg.state_dtype == "bfloat16" else 4,
    )
    mem["fits_16g_hbm"] = mem["model"]["total"] < 16 * 2**30
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 6.0 * n["active"] * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 2.0 * n["active"] * tokens
    else:
        model_flops = 2.0 * n["active"] * shape.global_batch

    rl = roofline_terms(
        tot["flops"], tot["bytes"], tot["coll"], chips=chips,
        model_flops_total=model_flops,
    )
    # HLO "bytes accessed" counts every (unfused-on-CPU) op's operands; a
    # fused TPU execution touches far less HBM. Bracket with an analytic
    # minimum: params+opt traffic once per step, residual stream 3x (fwd,
    # bwd, remat), weights re-read per microbatch under fsdp gathers.
    if shape.kind == "train":
        mbs = max(cfg.microbatches, 1)
        pbytes = 2.0 if cfg.param_dtype == "bfloat16" else 4.0
        fsdp_div = (mesh.shape.get("data", 1) if cfg.fsdp else 1) * mesh.shape.get("model", 1)
        dpn = chips // mesh.shape.get("model", 1)
        toks_dev = shape.global_batch * shape.seq_len / dpn
        traffic_min = (
            n["all_leaves"] / fsdp_div * (3 * pbytes + 4.0)  # w read(xmb amortized w/ cache)+grad+opt
            + n["all_leaves"] / fsdp_div * 2.0 * (mbs - 1)  # bf16 regathers per extra microbatch
            + 6.0 * cfg.n_layers * toks_dev * cfg.d_model * 2.0
        )
        rl["memory_s_lower_bound"] = traffic_min / 819e9
        rl["hbm_traffic_min_bytes"] = traffic_min

    art = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_desc,
        "chips": chips,
        "kind": shape.kind,
        "counts": counts,
        "per_kind_deltas": per_kind,
        "base": {k: base[k] for k in ("flops", "bytes", "coll")},
        "per_device": tot,
        "collectives_full": full_coll,
        "memory": mem,
        "params": n,
        "roofline": rl,
        "full_compile_s": compile_s,
        "wall_s": time.time() - t_start,
        "tag": tag,
    }
    if save:
        _save(art, arch, shape_name, mesh_desc, tag)
    return art


def _save(art, arch, shape_name, mesh_desc, tag=""):
    ARTIFACTS.mkdir(parents=True, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    path = ARTIFACTS / f"{arch}__{shape_name}__{mesh_desc}{suffix}.json"
    path.write_text(json.dumps(art, indent=1, default=str))
    return path
