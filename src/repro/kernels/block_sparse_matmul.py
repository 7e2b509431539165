"""Block-sparse matmul — the TPU-native execution of RigL sparsity (fwd+bwd).

Unstructured sparsity cannot skip work on a 128x128 systolic MXU, so the TPU
adaptation constrains RigL's drop/grow to (bk x bn)-aligned weight blocks
(core.rigl block_shape mode).  These kernels then *skip inactive blocks
entirely* in every pass of training:

  forward  out = x @ w_bs        CSC packing: per N-block, its active K-blocks
                                 (scalar-prefetched; BlockSpec index_map DMAs
                                 only active w-tiles from HBM)
  dgrad    dx  = g @ w_bsᵀ       CSR packing: per K-block, its active N-blocks
                                 — inactive N-blocks are skipped, so the
                                 backward input-grad is as sparse as the fwd
  wgrad    dw  = xᵀ @ g          computed ONLY for active (bk x bn) blocks,
                                 emitted PACKED as (nnb*max_k, bk, bn); the
                                 VJP wrapper scatters the packed blocks into
                                 the dense (K, N) cotangent (zeros outside the
                                 topology) that the RigL-side optimizer sees.

HBM traffic and MXU work in fwd AND bwd all scale with (1 - block_sparsity) —
the "sparse primitives" scenario (3) of the paper's Discussion, realized for
TPU for the full train step, not just inference.

Packing comes in two flavours:
  * ``pack_block_mask`` / ``pack_block_mask_rows`` — host-side numpy,
    vectorized (argsort-based), tight max-count; amortized over delta_t >= 100
    steps per topology update.
  * ``pack_block_mask_traced`` / ``pack_block_mask_rows_traced`` — jnp,
    jit-safe with a STATIC padded count (worst case: the full block-grid dim).
    Padded grid slots clamp their index_map to the last active block, so they
    re-DMA nothing and @pl.when skips their compute; the only cost is empty
    grid iterations.

Grid: (M/bm, N/bn, max_active_k); zero-count columns clamp to block 0 and are
fully masked by @pl.when (the clamp keeps indices non-negative — see _clamp).

Each ``pallas_call`` is named ``<variant>_<pass>``, and the name is its op's
name in a compiled program and a profiler trace: ``block_sparse_matmul_fwd``,
``_dx`` and ``_dw``, with the variants ``grouped_``, ``topkast_``,
``topkast_grouped_``, ``fused_`` and ``fused_grouped_`` in front.  Every name
holds ``block_sparse_matmul``, which is what trace readers match.

Grouped variant (``grouped_block_sparse_matmul``): a leading group dim G is
prepended to everything — x (G, M, K), w (G, K, N), stacked per-group packs
(idx (G, N/bn, width), shared width = max over groups) — and the grid grows a
leading G dimension, so ALL groups execute in ONE kernel launch.  This is how
MoE's per-expert ``ecd,edf->ecf`` expert banks and xLSTM's per-head
``bnh,nhk->bnk`` recurrent projections run block-sparse (models/moe.py,
models/xlstm.py via layers.grouped_linear): no per-expert launch loop, no
concatenated block-diagonal weights.  Same custom-VJP structure (grouped
dgrad/wgrad kernels + per-group scatter).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .masked_matmul import sr_to_bf16

__all__ = [
    "block_sparse_matmul",
    "grouped_block_sparse_matmul",
    "topkast_block_sparse_matmul",
    "topkast_grouped_block_sparse_matmul",
    "fused_block_sparse_matmul",
    "fused_grouped_block_sparse_matmul",
    "pack_block_mask",
    "pack_block_mask_rows",
    "pack_block_mask_traced",
    "pack_block_mask_rows_traced",
    "pack_group_mask",
    "pack_group_mask_rows",
    "pack_group_mask_traced",
    "pack_group_mask_rows_traced",
    "pack_host",
    "unpack_block_mask",
]


# ---------------------------------------------------------------------------
# packing (CSC for fwd/wgrad, CSR for dgrad)
# ---------------------------------------------------------------------------

def _pack_np(bm, max_count=None):
    """Per-COLUMN active row ids of a bool matrix, argsort-vectorized.

    bm: (R, C) bool -> (idx (C, max_count) int32, counts (C,) int32).
    Slots beyond a column's count are 0 (consumers mask on counts).
    """
    bm = np.asarray(bm, bool)
    counts = bm.sum(axis=0).astype(np.int32)
    if max_count is None:
        max_count = max(int(counts.max(initial=0)), 1)
    elif int(counts.max(initial=0)) > max_count:
        # truncating would SILENTLY drop active blocks from the matmul —
        # the output would be wrong with no runtime signal, so fail loudly
        raise ValueError(
            f"pack_block_mask: max_count={max_count} < max active blocks per "
            f"column ({int(counts.max())}). Truncating the pack would drop "
            "active blocks from the matmul and corrupt the output. Repack "
            "with a wider max_count (PackState does this automatically on "
            "refresh — see docs/kernels.md#packing-and-truncation)"
        )
    # stable ascending argsort of ~bm puts active rows first, in row order
    order = np.argsort(~bm, axis=0, kind="stable")
    idx = order[:max_count].T.astype(np.int32)
    idx = np.where(np.arange(max_count)[None, :] < counts[:, None], idx, 0)
    return idx, counts


def _pack_jnp(bm, max_count):
    """Trace-safe twin of _pack_np (max_count must be static)."""
    counts = jnp.sum(bm, axis=0).astype(jnp.int32)
    order = jnp.argsort(~bm, axis=0, stable=True)
    idx = order[:max_count].T.astype(jnp.int32)
    idx = jnp.where(jnp.arange(max_count)[None, :] < counts[:, None], idx, 0)
    return idx, counts


def pack_block_mask(block_mask, max_count=None):
    """block_mask: (K/bk, N/bn) bool -> CSC (indices (N/bn, max_k), counts).

    Static (host-side) packing: RigL updates the topology every delta_t >= 100
    steps, so the packing is amortized over >= 100 matmuls.  ``max_count``
    pins the padded width (pass a fixed bound to avoid retraces when the
    per-column max drifts across topology updates).
    """
    idx, cnt = _pack_np(block_mask, max_count)
    return jnp.asarray(idx), jnp.asarray(cnt)


def pack_block_mask_rows(block_mask, max_count=None):
    """block_mask: (K/bk, N/bn) bool -> CSR (indices (K/bk, max_n), counts).

    The dgrad kernel's view: per K-block row, the active N-blocks to visit.
    """
    idx, cnt = _pack_np(np.asarray(block_mask).T, max_count)
    return jnp.asarray(idx), jnp.asarray(cnt)


def pack_block_mask_traced(block_mask):
    """jit-safe CSC pack; padded width = K/bk (static worst case)."""
    return _pack_jnp(block_mask, block_mask.shape[0])


def pack_block_mask_rows_traced(block_mask):
    """jit-safe CSR pack; padded width = N/bn (static worst case)."""
    return _pack_jnp(block_mask.T, block_mask.shape[1])


def pack_host(block_mask, max_count=None):
    """Host CSC pack, as numpy arrays, of a (K/bk, N/bn) block mask or,
    per group at one shared width, of a (G, K/bk, N/bn) stack: what
    ``pack_block_mask`` / ``pack_group_mask`` put on the device.  PackState
    (core/pack.py) packs with it and uploads the arrays itself."""
    bm = np.asarray(block_mask, bool)
    if bm.ndim == 2:
        return _pack_np(bm, max_count)
    assert bm.ndim == 3, bm.shape
    if max_count is None:
        max_count = max(int(bm.sum(axis=1).max(initial=0)), 1)
    packed = [_pack_np(b, max_count) for b in bm]
    return (np.stack([i for i, _ in packed]),
            np.stack([c for _, c in packed]))


def pack_group_mask(block_masks, max_count=None):
    """Stacked per-group CSC pack of a (G, K/bk, N/bn) bool block-mask stack.

    Returns (idx (G, N/bn, width) int32, cnt (G, N/bn) int32) with ONE shared
    ``width`` (``max_count`` or the max active-K count over all groups and
    columns) so a single grouped kernel grid covers every group.  Groups with
    no active blocks at all are legal here — their counts are all zero and the
    grouped kernel writes zeros for them (a dead MoE expert behaves like an
    empty column, see docs/kernels.md#empty-columns-and-dead-layers); the
    bank-level dead check lives in core.pack.pack_entry.  Like
    ``pack_block_mask``, a ``max_count`` below some column's true count raises
    rather than silently truncating the matmul.
    """
    bms = np.asarray(block_masks, bool)
    assert bms.ndim == 3, bms.shape
    idx, cnt = pack_host(bms, max_count)
    return jnp.asarray(idx), jnp.asarray(cnt)


def pack_group_mask_rows(block_masks, max_count=None):
    """Stacked per-group CSR pack — the grouped dgrad kernel's view."""
    return pack_group_mask(
        np.asarray(block_masks).transpose(0, 2, 1), max_count
    )


def pack_group_mask_traced(block_masks):
    """jit-safe stacked CSC pack; padded width = K/bk (static worst case)."""
    return jax.vmap(lambda b: _pack_jnp(b, b.shape[0]))(block_masks)


def pack_group_mask_rows_traced(block_masks):
    """jit-safe stacked CSR pack; padded width = N/bn (static worst case)."""
    return jax.vmap(lambda b: _pack_jnp(b.T, b.shape[1]))(block_masks)


def unpack_block_mask(block_idx, block_cnt, n_rows: int):
    """CSC ``(idx, cnt)`` -> (n_rows, n_cols) bool block mask (traced-safe).

    Inverse of pack_block_mask (padded slots contribute nothing).  Shared by
    the VJP's CSR fallback derivation below and PackState's staleness check
    (core/pack.py) — one reconstruction definition, kept in sync by
    construction.
    """
    n_cols, width = block_idx.shape
    valid = jnp.arange(width)[None, :] < block_cnt[:, None]
    cols = jnp.broadcast_to(jnp.arange(n_cols)[:, None], block_idx.shape)
    return jnp.zeros((n_rows, n_cols), bool).at[block_idx, cols].max(valid)


def _clamp(idx_ref, cnt_ref, row, s):
    """Active-block id for slot s of packed row `row`, clamped non-negative.

    Padded slots (s >= cnt) clamp to the LAST active id, so consecutive grid
    steps see an unchanged index and Pallas skips the re-DMA; cnt == 0 rows
    clamp to 0 (guarded off by @pl.when in the kernel body).
    """
    return idx_ref[row, jnp.maximum(jnp.minimum(s, cnt_ref[row] - 1), 0)]


def _gclamp(idx_ref, cnt_ref, g, row, s):
    """_clamp for stacked (G, rows, width) packs: group g's row/slot lookup."""
    return idx_ref[
        g, row, jnp.maximum(jnp.minimum(s, cnt_ref[g, row] - 1), 0)
    ]


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _fwd_kernel(idx_ref, cnt_ref, x_ref, w_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    j = pl.program_id(1)

    @pl.when(k < cnt_ref[j])
    def _accum():
        acc_ref[...] += jnp.dot(
            x_ref[...], w_ref[...], preferred_element_type=jnp.float32
        )

    @pl.when(k == n_k - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _dx_kernel(ridx_ref, rcnt_ref, g_ref, w_ref, o_ref, acc_ref, *, n_s: int):
    """dx (bm, bk) += g (bm, bn) @ w (bk, bn)ᵀ over ACTIVE N-blocks only."""
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    k = pl.program_id(1)

    @pl.when(s < rcnt_ref[k])
    def _accum():
        acc_ref[...] += jax.lax.dot_general(
            g_ref[...], w_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(s == n_s - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _dw_kernel(idx_ref, cnt_ref, x_ref, g_ref, o_ref, acc_ref, *, n_m: int):
    """Packed wgrad: slot (j, s) holds xᵀ @ g for active block (idx[j,s], j).

    Inactive/padded slots store zeros (their x-tile is a clamped re-load of an
    arbitrary valid block, so the accumulate is guarded off too).
    """
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    j, s = pl.program_id(0), pl.program_id(1)

    @pl.when(s < cnt_ref[j])
    def _accum():
        acc_ref[...] += jax.lax.dot_general(
            x_ref[...], g_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(i == n_m - 1)
    def _store():
        o_ref[...] = jnp.where(
            s < cnt_ref[j], acc_ref[...], jnp.zeros_like(acc_ref)
        ).astype(o_ref.dtype)[None]


# ---------------------------------------------------------------------------
# pallas_call wrappers
# ---------------------------------------------------------------------------

def _fwd_call(x, w, block_idx, block_cnt, bm, bn, bk, interpret,
              name="block_sparse_matmul"):
    from jax.experimental.pallas import tpu as pltpu

    M, K = x.shape
    N = w.shape[1]
    max_k = block_idx.shape[1]
    grid = (M // bm, N // bn, max_k)

    def x_map(m, n, k, idx_ref, cnt_ref):
        return (m, _clamp(idx_ref, cnt_ref, n, k))

    def w_map(m, n, k, idx_ref, cnt_ref):
        return (_clamp(idx_ref, cnt_ref, n, k), n)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), x_map),
            pl.BlockSpec((bk, bn), w_map),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k, *_: (m, n)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_fwd_kernel, n_k=max_k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        interpret=interpret,
        name=f"{name}_fwd",
    )(block_idx, block_cnt, x, w)


def _dx_call(g, w, row_idx, row_cnt, bm, bn, bk, interpret, out_dtype,
             name="block_sparse_matmul"):
    from jax.experimental.pallas import tpu as pltpu

    M, N = g.shape
    K = w.shape[0]
    max_n = row_idx.shape[1]
    grid = (M // bm, K // bk, max_n)

    def g_map(m, k, s, ridx_ref, rcnt_ref):
        return (m, _clamp(ridx_ref, rcnt_ref, k, s))

    def w_map(m, k, s, ridx_ref, rcnt_ref):
        return (k, _clamp(ridx_ref, rcnt_ref, k, s))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), g_map),
            pl.BlockSpec((bk, bn), w_map),
        ],
        out_specs=pl.BlockSpec((bm, bk), lambda m, k, s, *_: (m, k)),
        scratch_shapes=[pltpu.VMEM((bm, bk), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_dx_kernel, n_s=max_n),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, K), out_dtype),
        interpret=interpret,
        name=f"{name}_dx",
    )(row_idx, row_cnt, g, w)


def _dw_call(x, g, block_idx, block_cnt, bm, bn, bk, interpret,
             name="block_sparse_matmul"):
    from jax.experimental.pallas import tpu as pltpu

    M, K = x.shape
    N = g.shape[1]
    nnb = N // bn
    max_k = block_idx.shape[1]
    n_m = M // bm
    grid = (nnb, max_k, n_m)

    def x_map(j, s, i, idx_ref, cnt_ref):
        return (i, _clamp(idx_ref, cnt_ref, j, s))

    def g_map(j, s, i, idx_ref, cnt_ref):
        return (i, j)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), x_map),
            pl.BlockSpec((bm, bn), g_map),
        ],
        out_specs=pl.BlockSpec(
            (1, bk, bn), lambda j, s, i, *_: (j * max_k + s, 0, 0)
        ),
        scratch_shapes=[pltpu.VMEM((bk, bn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_dw_kernel, n_m=n_m),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nnb * max_k, bk, bn), jnp.float32),
        interpret=interpret,
        name=f"{name}_dw",
    )(block_idx, block_cnt, x, g)


def _scatter_packed_dw(packed, block_idx, block_cnt, nkb, bk, bn, dtype):
    """Packed (nnb*max_k, bk, bn) wgrad blocks -> dense (K, N) cotangent.

    This is the "scatter on the RigL-update side": the kernel only ever
    computes/stores active blocks; the dense layout (zeros outside the
    topology) is materialized here, where the optimizer consumes it.
    """
    nnb, max_k = block_idx.shape
    packed = packed.reshape(nnb, max_k, bk, bn)
    valid = (jnp.arange(max_k)[None, :] < block_cnt[:, None])[..., None, None]
    packed = jnp.where(valid, packed, 0.0)
    cols = jnp.broadcast_to(jnp.arange(nnb)[:, None], block_idx.shape)
    # .add (not .set): padded slots alias block (0, j) but are already zeroed
    grid_ = jnp.zeros((nkb, nnb, bk, bn), packed.dtype)
    grid_ = grid_.at[block_idx, cols].add(packed)
    return grid_.transpose(0, 2, 1, 3).reshape(nkb * bk, nnb * bn).astype(dtype)


# ---------------------------------------------------------------------------
# custom VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _block_sparse_matmul(
    x, w, block_idx, block_cnt, row_idx, row_cnt, bm, bn, bk, interpret
):
    return _fwd_call(x, w, block_idx, block_cnt, bm, bn, bk, interpret)


def _bs_fwd(x, w, block_idx, block_cnt, row_idx, row_cnt, bm, bn, bk, interpret):
    out = _fwd_call(x, w, block_idx, block_cnt, bm, bn, bk, interpret)
    return out, (x, w, block_idx, block_cnt, row_idx, row_cnt)


def _bs_bwd(bm, bn, bk, interpret, res, g):
    x, w, block_idx, block_cnt, row_idx, row_cnt = res
    K, N = w.shape
    nkb = K // bk

    dx = _dx_call(g, w, row_idx, row_cnt, bm, bn, bk, interpret, x.dtype)
    packed = _dw_call(x, g, block_idx, block_cnt, bm, bn, bk, interpret)
    dw = _scatter_packed_dw(packed, block_idx, block_cnt, nkb, bk, bn, w.dtype)

    z = lambda a: np.zeros(a.shape, jax.dtypes.float0)
    return dx, dw, z(block_idx), z(block_cnt), z(row_idx), z(row_cnt)


_block_sparse_matmul.defvjp(_bs_fwd, _bs_bwd)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def block_sparse_matmul(
    x,
    w,
    block_idx,
    block_cnt,
    row_idx=None,
    row_cnt=None,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool = False,
):
    """x: (M, K) @ block-sparse w: (K, N) -> (M, N).

    block_idx: (N/bn, max_k) int32 — active K-block ids per N-block (CSC).
    block_cnt: (N/bn,) int32 — number of active K-blocks per N-block.
    row_idx/row_cnt: optional CSR view ((K/bk, max_n) / (K/bk,)) consumed by
    the dgrad kernel.  Pass the host-packed (tight) CSR from a PackState
    entry so the backward dx grid is also sized to the true active count;
    when omitted, it is derived here from the CSC pack at the static
    worst-case width N/bn (padded dgrad grid — correct, just longer).  The
    derivation is dead-code-eliminated whenever the call is not
    differentiated (e.g. serving).

    Differentiable: jax.grad routes through the CSR dgrad kernel (skips
    inactive N-blocks) and the packed-active-block wgrad kernel.
    """
    M, K = x.shape
    K2, N = w.shape
    assert K == K2 and N % bn == 0 and K % bk == 0 and M % bm == 0
    if row_idx is None:
        bmask = unpack_block_mask(block_idx, block_cnt, K // bk)
        row_idx, row_cnt = _pack_jnp(bmask.T, N // bn)
    return _block_sparse_matmul(
        x, w, block_idx, block_cnt, row_idx, row_cnt, bm, bn, bk, interpret
    )


# ---------------------------------------------------------------------------
# grouped kernels: one grid launch for a whole (G, K, N) weight bank
# ---------------------------------------------------------------------------

def _g_fwd_kernel(idx_ref, cnt_ref, x_ref, w_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    g, j = pl.program_id(0), pl.program_id(2)

    @pl.when(k < cnt_ref[g, j])
    def _accum():
        acc_ref[...] += jnp.dot(
            x_ref[0], w_ref[0], preferred_element_type=jnp.float32
        )

    @pl.when(k == n_k - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)[None]


def _g_dx_kernel(ridx_ref, rcnt_ref, g_ref, w_ref, o_ref, acc_ref, *, n_s: int):
    """Grouped dgrad: dx[g] (bm, bk) += g[g] (bm, bn) @ w[g] (bk, bn)ᵀ."""
    s = pl.program_id(3)

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    g, k = pl.program_id(0), pl.program_id(2)

    @pl.when(s < rcnt_ref[g, k])
    def _accum():
        acc_ref[...] += jax.lax.dot_general(
            g_ref[0], w_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(s == n_s - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)[None]


def _g_dw_kernel(idx_ref, cnt_ref, x_ref, g_ref, o_ref, acc_ref, *, n_m: int):
    """Grouped packed wgrad: slot (g, j, s) holds x[g]ᵀ @ g[g] for active
    block (idx[g, j, s], j) of group g; padded slots store zeros."""
    i = pl.program_id(3)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    g, j, s = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(s < cnt_ref[g, j])
    def _accum():
        acc_ref[...] += jax.lax.dot_general(
            x_ref[0], g_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(i == n_m - 1)
    def _store():
        o_ref[...] = jnp.where(
            s < cnt_ref[g, j], acc_ref[...], jnp.zeros_like(acc_ref)
        ).astype(o_ref.dtype)[None, None]


def _g_fwd_call(x, w, block_idx, block_cnt, bm, bn, bk, interpret,
                name="grouped_block_sparse_matmul"):
    from jax.experimental.pallas import tpu as pltpu

    G, M, K = x.shape
    N = w.shape[2]
    max_k = block_idx.shape[2]
    grid = (G, M // bm, N // bn, max_k)

    def x_map(g, m, n, k, idx_ref, cnt_ref):
        return (g, m, _gclamp(idx_ref, cnt_ref, g, n, k))

    def w_map(g, m, n, k, idx_ref, cnt_ref):
        return (g, _gclamp(idx_ref, cnt_ref, g, n, k), n)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), x_map),
            pl.BlockSpec((1, bk, bn), w_map),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda g, m, n, k, *_: (g, m, n)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_g_fwd_kernel, n_k=max_k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((G, M, N), x.dtype),
        interpret=interpret,
        name=f"{name}_fwd",
    )(block_idx, block_cnt, x, w)


def _g_dx_call(g_, w, row_idx, row_cnt, bm, bn, bk, interpret, out_dtype,
               name="grouped_block_sparse_matmul"):
    from jax.experimental.pallas import tpu as pltpu

    G, M, N = g_.shape
    K = w.shape[1]
    max_n = row_idx.shape[2]
    grid = (G, M // bm, K // bk, max_n)

    def g_map(g, m, k, s, ridx_ref, rcnt_ref):
        return (g, m, _gclamp(ridx_ref, rcnt_ref, g, k, s))

    def w_map(g, m, k, s, ridx_ref, rcnt_ref):
        return (g, k, _gclamp(ridx_ref, rcnt_ref, g, k, s))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bn), g_map),
            pl.BlockSpec((1, bk, bn), w_map),
        ],
        out_specs=pl.BlockSpec((1, bm, bk), lambda g, m, k, s, *_: (g, m, k)),
        scratch_shapes=[pltpu.VMEM((bm, bk), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_g_dx_kernel, n_s=max_n),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((G, M, K), out_dtype),
        interpret=interpret,
        name=f"{name}_dx",
    )(row_idx, row_cnt, g_, w)


def _g_dw_call(x, g_, block_idx, block_cnt, bm, bn, bk, interpret,
               name="grouped_block_sparse_matmul"):
    from jax.experimental.pallas import tpu as pltpu

    G, M, K = x.shape
    N = g_.shape[2]
    nnb = N // bn
    max_k = block_idx.shape[2]
    n_m = M // bm
    grid = (G, nnb, max_k, n_m)

    def x_map(g, j, s, i, idx_ref, cnt_ref):
        return (g, i, _gclamp(idx_ref, cnt_ref, g, j, s))

    def g_map(g, j, s, i, idx_ref, cnt_ref):
        return (g, i, j)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), x_map),
            pl.BlockSpec((1, bm, bn), g_map),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, bk, bn), lambda g, j, s, i, *_: (g, j * max_k + s, 0, 0)
        ),
        scratch_shapes=[pltpu.VMEM((bk, bn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_g_dw_kernel, n_m=n_m),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((G, nnb * max_k, bk, bn), jnp.float32),
        interpret=interpret,
        name=f"{name}_dw",
    )(block_idx, block_cnt, x, g_)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _grouped_block_sparse_matmul(
    x, w, block_idx, block_cnt, row_idx, row_cnt, bm, bn, bk, interpret
):
    return _g_fwd_call(x, w, block_idx, block_cnt, bm, bn, bk, interpret)


def _gbs_fwd(x, w, block_idx, block_cnt, row_idx, row_cnt, bm, bn, bk, interpret):
    out = _g_fwd_call(x, w, block_idx, block_cnt, bm, bn, bk, interpret)
    return out, (x, w, block_idx, block_cnt, row_idx, row_cnt)


def _gbs_bwd(bm, bn, bk, interpret, res, g):
    x, w, block_idx, block_cnt, row_idx, row_cnt = res
    K, N = w.shape[1], w.shape[2]
    nkb = K // bk

    dx = _g_dx_call(g, w, row_idx, row_cnt, bm, bn, bk, interpret, x.dtype)
    packed = _g_dw_call(x, g, block_idx, block_cnt, bm, bn, bk, interpret)
    dw = jax.vmap(
        lambda p_, i_, c_: _scatter_packed_dw(p_, i_, c_, nkb, bk, bn, w.dtype)
    )(packed, block_idx, block_cnt)

    z = lambda a: np.zeros(a.shape, jax.dtypes.float0)
    return dx, dw, z(block_idx), z(block_cnt), z(row_idx), z(row_cnt)


_grouped_block_sparse_matmul.defvjp(_gbs_fwd, _gbs_bwd)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def grouped_block_sparse_matmul(
    x,
    w,
    block_idx,
    block_cnt,
    row_idx=None,
    row_cnt=None,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool = False,
):
    """Grouped x: (G, M, K) @ block-sparse w: (G, K, N) -> (G, M, N).

    The grouped twin of ``block_sparse_matmul``: one kernel launch executes
    every group's block-sparse matmul (grid gains a leading G dim), driven by
    STACKED packs — ``block_idx (G, N/bn, width)`` / ``block_cnt (G, N/bn)``
    from ``pack_group_mask`` (shared width = max over groups).  This is the
    execution path for MoE expert banks (``ecd,edf->ecf``) and xLSTM per-head
    recurrent projections (``bnh,nhk->bnk`` after moving heads to the group
    dim) — see layers.grouped_linear.

    row_idx/row_cnt: optional stacked CSR ((G, K/bk, row_width) / (G, K/bk))
    for a tight grouped dgrad grid; derived at the worst-case width N/bn when
    omitted (dead-code-eliminated if never differentiated).

    Differentiable: grouped custom-VJP dgrad/wgrad kernels; the packed wgrad
    blocks are scattered per group into the dense (G, K, N) cotangent.
    """
    G, M, K = x.shape
    G2, K2, N = w.shape
    assert G == G2 and K == K2, (x.shape, w.shape)
    assert N % bn == 0 and K % bk == 0 and M % bm == 0, (M, K, N, bm, bn, bk)
    if row_idx is None:
        bmask = jax.vmap(
            lambda i_, c_: unpack_block_mask(i_, c_, K // bk)
        )(block_idx, block_cnt)
        row_idx, row_cnt = pack_group_mask_rows_traced(bmask)
    return _grouped_block_sparse_matmul(
        x, w, block_idx, block_cnt, row_idx, row_cnt, bm, bn, bk, interpret
    )


# ---------------------------------------------------------------------------
# Top-KAST split-topology VJP: forward/dgrad on the tight k-grid,
# wgrad on the top-(k+delta) backward-superset grid (docs/training.md#topkast)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10, 11))
def _topkast_block_sparse_matmul(
    x, w, block_idx, block_cnt, row_idx, row_cnt, bwd_idx, bwd_cnt,
    bm, bn, bk, interpret,
):
    return _fwd_call(x, w, block_idx, block_cnt, bm, bn, bk, interpret,
                     name="topkast_block_sparse_matmul")


def _tk_fwd(
    x, w, block_idx, block_cnt, row_idx, row_cnt, bwd_idx, bwd_cnt,
    bm, bn, bk, interpret,
):
    out = _fwd_call(x, w, block_idx, block_cnt, bm, bn, bk, interpret,
                    name="topkast_block_sparse_matmul")
    return out, (x, w, block_idx, block_cnt, row_idx, row_cnt, bwd_idx, bwd_cnt)


def _tk_bwd(bm, bn, bk, interpret, res, g):
    x, w, block_idx, block_cnt, row_idx, row_cnt, bwd_idx, bwd_cnt = res
    K, N = w.shape
    nkb = K // bk

    # dx on the FORWARD topology (y only saw w ⊙ A), wgrad on the SUPERSET:
    # dw is exactly the dense gradient restricted to B's support, the
    # side-channel the rigl/snfs grow scores consume.
    dx = _dx_call(g, w, row_idx, row_cnt, bm, bn, bk, interpret, x.dtype,
                  name="topkast_block_sparse_matmul")
    packed = _dw_call(x, g, bwd_idx, bwd_cnt, bm, bn, bk, interpret,
                      name="topkast_block_sparse_matmul")
    dw = _scatter_packed_dw(packed, bwd_idx, bwd_cnt, nkb, bk, bn, w.dtype)

    z = lambda a: np.zeros(a.shape, jax.dtypes.float0)
    return (
        dx, dw, z(block_idx), z(block_cnt), z(row_idx), z(row_cnt),
        z(bwd_idx), z(bwd_cnt),
    )


_topkast_block_sparse_matmul.defvjp(_tk_fwd, _tk_bwd)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def topkast_block_sparse_matmul(
    x,
    w,
    block_idx,
    block_cnt,
    bwd_idx,
    bwd_cnt,
    row_idx=None,
    row_cnt=None,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool = False,
):
    """Top-KAST matmul: forward on A's CSC, weight gradient on B ⊇ A's CSC.

    Same kernels as ``block_sparse_matmul`` — the split is purely in which
    pack drives the wgrad grid.  bwd_idx/bwd_cnt are the superset CSC view of
    a PackState entry (``bidx``/``bcnt``, core/pack.py); forward and dgrad
    keep the tight idx/ridx views, so the per-step cost of the exploration
    set is ONE wider wgrad grid, nothing else.  dw is dense-laid-out but
    supported only on B — zero dense-gradient materialization.
    """
    M, K = x.shape
    K2, N = w.shape
    assert K == K2 and N % bn == 0 and K % bk == 0 and M % bm == 0
    if row_idx is None:
        bmask = unpack_block_mask(block_idx, block_cnt, K // bk)
        row_idx, row_cnt = _pack_jnp(bmask.T, N // bn)
    return _topkast_block_sparse_matmul(
        x, w, block_idx, block_cnt, row_idx, row_cnt, bwd_idx, bwd_cnt,
        bm, bn, bk, interpret,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10, 11))
def _topkast_grouped_block_sparse_matmul(
    x, w, block_idx, block_cnt, row_idx, row_cnt, bwd_idx, bwd_cnt,
    bm, bn, bk, interpret,
):
    return _g_fwd_call(x, w, block_idx, block_cnt, bm, bn, bk, interpret,
                       name="topkast_grouped_block_sparse_matmul")


def _gtk_fwd(
    x, w, block_idx, block_cnt, row_idx, row_cnt, bwd_idx, bwd_cnt,
    bm, bn, bk, interpret,
):
    out = _g_fwd_call(x, w, block_idx, block_cnt, bm, bn, bk, interpret,
                      name="topkast_grouped_block_sparse_matmul")
    return out, (x, w, block_idx, block_cnt, row_idx, row_cnt, bwd_idx, bwd_cnt)


def _gtk_bwd(bm, bn, bk, interpret, res, g):
    x, w, block_idx, block_cnt, row_idx, row_cnt, bwd_idx, bwd_cnt = res
    K, N = w.shape[1], w.shape[2]
    nkb = K // bk

    dx = _g_dx_call(g, w, row_idx, row_cnt, bm, bn, bk, interpret, x.dtype,
                    name="topkast_grouped_block_sparse_matmul")
    packed = _g_dw_call(x, g, bwd_idx, bwd_cnt, bm, bn, bk, interpret,
                        name="topkast_grouped_block_sparse_matmul")
    dw = jax.vmap(
        lambda p_, i_, c_: _scatter_packed_dw(p_, i_, c_, nkb, bk, bn, w.dtype)
    )(packed, bwd_idx, bwd_cnt)

    z = lambda a: np.zeros(a.shape, jax.dtypes.float0)
    return (
        dx, dw, z(block_idx), z(block_cnt), z(row_idx), z(row_cnt),
        z(bwd_idx), z(bwd_cnt),
    )


_topkast_grouped_block_sparse_matmul.defvjp(_gtk_fwd, _gtk_bwd)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def topkast_grouped_block_sparse_matmul(
    x,
    w,
    block_idx,
    block_cnt,
    bwd_idx,
    bwd_cnt,
    row_idx=None,
    row_cnt=None,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool = False,
):
    """Grouped Top-KAST matmul: per-group forward on A, wgrad on B ⊇ A.

    The grouped twin of ``topkast_block_sparse_matmul`` for MoE expert banks
    and xLSTM per-head recurrences — stacked packs, one launch, wgrad driven
    by the stacked superset CSC (``bidx (G, N/bn, bwidth)``).
    """
    G, M, K = x.shape
    G2, K2, N = w.shape
    assert G == G2 and K == K2, (x.shape, w.shape)
    assert N % bn == 0 and K % bk == 0 and M % bm == 0, (M, K, N, bm, bn, bk)
    if row_idx is None:
        bmask = jax.vmap(
            lambda i_, c_: unpack_block_mask(i_, c_, K // bk)
        )(block_idx, block_cnt)
        row_idx, row_cnt = pack_group_mask_rows_traced(bmask)
    return _topkast_grouped_block_sparse_matmul(
        x, w, block_idx, block_cnt, row_idx, row_cnt, bwd_idx, bwd_cnt,
        bm, bn, bk, interpret,
    )


# ---------------------------------------------------------------------------
# fused wgrad -> optimizer epilogue (docs/kernels.md#fused-epilogue)
#
# Block-sparse twin of masked_matmul.fused_masked_matmul: the packed wgrad
# kernel DMAs the matching w/mom tiles alongside x/g and stores
# m_new = mu*mom + xᵀg + wd*w per active block — the packed blocks leaving
# the kernel ARE the new SGD momentum (optionally stochastically rounded onto
# the bf16 grid), scattered into the dense (K, N) cotangent layout the
# optimizer consumes.  The raw dw never round-trips HBM.  One custom-VJP
# covers plain AND Top-KAST: the wgrad grid is driven by whichever pack the
# wrapper selects (tight CSC, or the B ⊇ A superset ``bidx``/``bcnt``).
# ---------------------------------------------------------------------------

def _dw_fused_kernel(
    idx_ref, cnt_ref, seed_ref, x_ref, g_ref, w_ref, mom_ref, o_ref, acc_ref,
    *, n_m: int, ncols: int, mu: float, wd: float, sr: bool,
):
    i = pl.program_id(2)
    j, s = pl.program_id(0), pl.program_id(1)
    # block row id for the sr element-coordinate hash; read at top level
    # (program_id/scalar reads inside a pl.when branch fail interpret lowering)
    kb = _clamp(idx_ref, cnt_ref, j, s)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(s < cnt_ref[j])
    def _accum():
        acc_ref[...] += jax.lax.dot_general(
            x_ref[...], g_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(i == n_m - 1)
    def _store():
        m_new = (
            mu * mom_ref[...].astype(jnp.float32)
            + acc_ref[...]
            + wd * w_ref[...].astype(jnp.float32)
        )
        # padded slots alias a clamped block's w/mom tiles — zero them BEFORE
        # sr (sr_to_bf16(0) == 0 exactly, so zeros stay zeros)
        m_new = jnp.where(s < cnt_ref[j], m_new, jnp.zeros_like(m_new))
        if sr:
            bkk, bnn = m_new.shape
            rows = jax.lax.broadcasted_iota(jnp.uint32, m_new.shape, 0)
            cols = jax.lax.broadcasted_iota(jnp.uint32, m_new.shape, 1)
            ku, ju = jnp.uint32(kb), jnp.uint32(j)
            gid = (ku * bkk + rows) * jnp.uint32(ncols) + (ju * bnn + cols)
            m_new = sr_to_bf16(m_new, seed_ref[0], gid)
        o_ref[...] = m_new.astype(o_ref.dtype)[None]


def _dw_fused_call(
    x, g, wg_idx, wg_cnt, w, mom, seed, mu, wd, sr, bm, bn, bk, interpret,
    name="fused_block_sparse_matmul",
):
    from jax.experimental.pallas import tpu as pltpu

    M, K = x.shape
    N = g.shape[1]
    nnb = N // bn
    max_k = wg_idx.shape[1]
    n_m = M // bm
    grid = (nnb, max_k, n_m)

    def x_map(j, s, i, idx_ref, cnt_ref, seed_ref):
        return (i, _clamp(idx_ref, cnt_ref, j, s))

    def g_map(j, s, i, idx_ref, cnt_ref, seed_ref):
        return (i, j)

    def wm_map(j, s, i, idx_ref, cnt_ref, seed_ref):
        return (_clamp(idx_ref, cnt_ref, j, s), j)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), x_map),
            pl.BlockSpec((bm, bn), g_map),
            pl.BlockSpec((bk, bn), wm_map),
            pl.BlockSpec((bk, bn), wm_map),
        ],
        out_specs=pl.BlockSpec(
            (1, bk, bn), lambda j, s, i, *_: (j * max_k + s, 0, 0)
        ),
        scratch_shapes=[pltpu.VMEM((bk, bn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(
            _dw_fused_kernel, n_m=n_m, ncols=N, mu=mu, wd=wd, sr=sr
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nnb * max_k, bk, bn), jnp.float32),
        interpret=interpret,
        name=f"{name}_dw",
    )(wg_idx, wg_cnt, seed, x, g, w, mom)


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(10, 11, 12, 13, 14, 15, 16)
)
def _fused_block_sparse_matmul(
    x, w, block_idx, block_cnt, row_idx, row_cnt, wg_idx, wg_cnt, mom, seed,
    mu, wd, sr, bm, bn, bk, interpret,
):
    return _fwd_call(x, w, block_idx, block_cnt, bm, bn, bk, interpret,
                     name="fused_block_sparse_matmul")


def _fbs_fwd(
    x, w, block_idx, block_cnt, row_idx, row_cnt, wg_idx, wg_cnt, mom, seed,
    mu, wd, sr, bm, bn, bk, interpret,
):
    out = _fwd_call(x, w, block_idx, block_cnt, bm, bn, bk, interpret,
                    name="fused_block_sparse_matmul")
    return out, (
        x, w, block_idx, block_cnt, row_idx, row_cnt, wg_idx, wg_cnt, mom, seed
    )


def _fbs_bwd(mu, wd, sr, bm, bn, bk, interpret, res, g):
    (
        x, w, block_idx, block_cnt, row_idx, row_cnt, wg_idx, wg_cnt, mom, seed
    ) = res
    K = w.shape[0]
    nkb = K // bk

    dx = _dx_call(g, w, row_idx, row_cnt, bm, bn, bk, interpret, x.dtype,
                  name="fused_block_sparse_matmul")
    packed = _dw_fused_call(
        x, g, wg_idx, wg_cnt, w, mom, seed, mu, wd, sr, bm, bn, bk, interpret
    )
    m_new = _scatter_packed_dw(packed, wg_idx, wg_cnt, nkb, bk, bn, w.dtype)

    z = lambda a: np.zeros(a.shape, jax.dtypes.float0)
    return (
        dx, m_new, z(block_idx), z(block_cnt), z(row_idx), z(row_cnt),
        z(wg_idx), z(wg_cnt), jnp.zeros_like(mom), z(seed),
    )


_fused_block_sparse_matmul.defvjp(_fbs_fwd, _fbs_bwd)


@functools.partial(
    jax.jit, static_argnames=("mu", "wd", "sr", "bm", "bn", "bk", "interpret")
)
def fused_block_sparse_matmul(
    x,
    w,
    block_idx,
    block_cnt,
    mom,
    seed,
    bwd_idx=None,
    bwd_cnt=None,
    row_idx=None,
    row_cnt=None,
    *,
    mu: float,
    wd: float,
    sr: bool,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool = False,
):
    """``block_sparse_matmul`` whose weight COTANGENT is the new SGD momentum.

    Forward/dgrad identical to ``block_sparse_matmul``.  The packed wgrad
    kernel stores m_new = mu*mom + xᵀg + wd*w per active block of the wgrad
    pack — ``bwd_idx``/``bwd_cnt`` (Top-KAST superset B) when given, else the
    forward CSC — scattered to the dense (K, N) layout (zeros off-support;
    momentum there is pinned to zero, the documented fused semantic).  seed:
    (1,) int32 per-leaf counter; sr=True stochastically rounds m_new onto the
    bf16 grid in-kernel (masked_matmul.sr_to_bf16).  Consumed via
    ops.fused_block_sparse_linear + optim.apply_opt_fused.
    """
    M, K = x.shape
    K2, N = w.shape
    assert K == K2 and N % bn == 0 and K % bk == 0 and M % bm == 0
    assert mom.shape == w.shape, (mom.shape, w.shape)
    if row_idx is None:
        bmask = unpack_block_mask(block_idx, block_cnt, K // bk)
        row_idx, row_cnt = _pack_jnp(bmask.T, N // bn)
    if bwd_idx is None:
        bwd_idx, bwd_cnt = block_idx, block_cnt
    return _fused_block_sparse_matmul(
        x, w, block_idx, block_cnt, row_idx, row_cnt, bwd_idx, bwd_cnt,
        mom, seed, mu, wd, sr, bm, bn, bk, interpret,
    )


def _g_dw_fused_kernel(
    idx_ref, cnt_ref, seed_ref, x_ref, g_ref, w_ref, mom_ref, o_ref, acc_ref,
    *, n_m: int, nrows: int, ncols: int, mu: float, wd: float, sr: bool,
):
    i = pl.program_id(3)
    g, j, s = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    kb = _gclamp(idx_ref, cnt_ref, g, j, s)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(s < cnt_ref[g, j])
    def _accum():
        acc_ref[...] += jax.lax.dot_general(
            x_ref[0], g_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(i == n_m - 1)
    def _store():
        m_new = (
            mu * mom_ref[0].astype(jnp.float32)
            + acc_ref[...]
            + wd * w_ref[0].astype(jnp.float32)
        )
        m_new = jnp.where(s < cnt_ref[g, j], m_new, jnp.zeros_like(m_new))
        if sr:
            bkk, bnn = m_new.shape
            rows = jax.lax.broadcasted_iota(jnp.uint32, m_new.shape, 0)
            cols = jax.lax.broadcasted_iota(jnp.uint32, m_new.shape, 1)
            gu, ku, ju = jnp.uint32(g), jnp.uint32(kb), jnp.uint32(j)
            gid = (gu * nrows + ku * bkk + rows) * jnp.uint32(ncols) + (
                ju * bnn + cols
            )
            m_new = sr_to_bf16(m_new, seed_ref[0], gid)
        o_ref[...] = m_new.astype(o_ref.dtype)[None, None]


def _g_dw_fused_call(
    x, g_, wg_idx, wg_cnt, w, mom, seed, mu, wd, sr, bm, bn, bk, interpret,
    name="fused_grouped_block_sparse_matmul",
):
    from jax.experimental.pallas import tpu as pltpu

    G, M, K = x.shape
    N = g_.shape[2]
    nnb = N // bn
    max_k = wg_idx.shape[2]
    n_m = M // bm
    grid = (G, nnb, max_k, n_m)

    def x_map(g, j, s, i, idx_ref, cnt_ref, seed_ref):
        return (g, i, _gclamp(idx_ref, cnt_ref, g, j, s))

    def g_map(g, j, s, i, idx_ref, cnt_ref, seed_ref):
        return (g, i, j)

    def wm_map(g, j, s, i, idx_ref, cnt_ref, seed_ref):
        return (g, _gclamp(idx_ref, cnt_ref, g, j, s), j)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), x_map),
            pl.BlockSpec((1, bm, bn), g_map),
            pl.BlockSpec((1, bk, bn), wm_map),
            pl.BlockSpec((1, bk, bn), wm_map),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, bk, bn), lambda g, j, s, i, *_: (g, j * max_k + s, 0, 0)
        ),
        scratch_shapes=[pltpu.VMEM((bk, bn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(
            _g_dw_fused_kernel, n_m=n_m, nrows=K, ncols=N, mu=mu, wd=wd, sr=sr
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((G, nnb * max_k, bk, bn), jnp.float32),
        interpret=interpret,
        name=f"{name}_dw",
    )(wg_idx, wg_cnt, seed, x, g_, w, mom)


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(10, 11, 12, 13, 14, 15, 16)
)
def _fused_grouped_block_sparse_matmul(
    x, w, block_idx, block_cnt, row_idx, row_cnt, wg_idx, wg_cnt, mom, seed,
    mu, wd, sr, bm, bn, bk, interpret,
):
    return _g_fwd_call(x, w, block_idx, block_cnt, bm, bn, bk, interpret,
                       name="fused_grouped_block_sparse_matmul")


def _gfbs_fwd(
    x, w, block_idx, block_cnt, row_idx, row_cnt, wg_idx, wg_cnt, mom, seed,
    mu, wd, sr, bm, bn, bk, interpret,
):
    out = _g_fwd_call(x, w, block_idx, block_cnt, bm, bn, bk, interpret,
                      name="fused_grouped_block_sparse_matmul")
    return out, (
        x, w, block_idx, block_cnt, row_idx, row_cnt, wg_idx, wg_cnt, mom, seed
    )


def _gfbs_bwd(mu, wd, sr, bm, bn, bk, interpret, res, g):
    (
        x, w, block_idx, block_cnt, row_idx, row_cnt, wg_idx, wg_cnt, mom, seed
    ) = res
    K = w.shape[1]
    nkb = K // bk

    dx = _g_dx_call(g, w, row_idx, row_cnt, bm, bn, bk, interpret, x.dtype,
                    name="fused_grouped_block_sparse_matmul")
    packed = _g_dw_fused_call(
        x, g, wg_idx, wg_cnt, w, mom, seed, mu, wd, sr, bm, bn, bk, interpret
    )
    m_new = jax.vmap(
        lambda p_, i_, c_: _scatter_packed_dw(p_, i_, c_, nkb, bk, bn, w.dtype)
    )(packed, wg_idx, wg_cnt)

    z = lambda a: np.zeros(a.shape, jax.dtypes.float0)
    return (
        dx, m_new, z(block_idx), z(block_cnt), z(row_idx), z(row_cnt),
        z(wg_idx), z(wg_cnt), jnp.zeros_like(mom), z(seed),
    )


_fused_grouped_block_sparse_matmul.defvjp(_gfbs_fwd, _gfbs_bwd)


@functools.partial(
    jax.jit, static_argnames=("mu", "wd", "sr", "bm", "bn", "bk", "interpret")
)
def fused_grouped_block_sparse_matmul(
    x,
    w,
    block_idx,
    block_cnt,
    mom,
    seed,
    bwd_idx=None,
    bwd_cnt=None,
    row_idx=None,
    row_cnt=None,
    *,
    mu: float,
    wd: float,
    sr: bool,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool = False,
):
    """Grouped ``fused_block_sparse_matmul`` (MoE banks / xLSTM heads)."""
    G, M, K = x.shape
    G2, K2, N = w.shape
    assert G == G2 and K == K2, (x.shape, w.shape)
    assert N % bn == 0 and K % bk == 0 and M % bm == 0, (M, K, N, bm, bn, bk)
    assert mom.shape == w.shape, (mom.shape, w.shape)
    if row_idx is None:
        bmask = jax.vmap(
            lambda i_, c_: unpack_block_mask(i_, c_, K // bk)
        )(block_idx, block_cnt)
        row_idx, row_cnt = pack_group_mask_rows_traced(bmask)
    if bwd_idx is None:
        bwd_idx, bwd_cnt = block_idx, block_cnt
    return _fused_grouped_block_sparse_matmul(
        x, w, block_idx, block_cnt, row_idx, row_cnt, bwd_idx, bwd_cnt,
        mom, seed, mu, wd, sr, bm, bn, bk, interpret,
    )
