"""Jitted public wrappers around the Pallas kernels.

``interpret=None`` auto-selects (``auto_interpret``): compiled on TPU,
interpret (python-executed kernel bodies) on the CPU backend, where the tests
validate kernel semantics against ref.py; any other backend raises.  The
BlockSpec tiling targets TPU v5e VMEM (128-aligned tiles);
tests/test_tpu_compile.py compiles the main-path kernels for a described v5e.

Both linear wrappers are fully differentiable (the underlying kernels carry
custom-VJP Pallas backward passes) and accept NON-ALIGNED leading dims: the
flattened batch*seq rows are zero-padded up to the M tile and trimmed after,
so odd shapes (e.g. decode with batch 4, or batch*seq not a 128 multiple)
dispatch without caller-side padding.  The row tile is sized from the rows
(``_row_tile``): whole ``kernel_block[0]`` granules up to the VMEM budget, so
a 2048-token microbatch is one row tile.  ``masked_linear`` additionally pads
K/N when they don't divide the tile; ``block_sparse_linear`` requires aligned
K/N because the block mask's grid is defined by them.

``block_sparse_linear`` accepts its topology three ways, in priority order:
a precomputed ``pack=(idx, cnt)`` (tight grid, zero per-call packing cost —
this is what PackState in the train/serve state provides, core/pack.py); a
concrete block mask (host-side numpy packing, tight max-count — eval /
one-off calls); or a traced block mask (jit-safe jnp packing with a static
worst-case count — correct anywhere, but every grid is padded to K/bk with
empty iterations).  docs/kernels.md documents the whole path end-to-end.

The ``grouped_*`` wrappers are the weight-BANK twins (leading group dim G,
one launch for all groups): MoE per-expert einsums and xLSTM per-head
recurrences dispatch through them (layers.grouped_linear), with the same
three topology sources (grouped PackState entry / concrete / traced mask).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.metrics import REGISTRY
from .block_sparse_matmul import (
    block_sparse_matmul,
    fused_block_sparse_matmul,
    fused_grouped_block_sparse_matmul,
    grouped_block_sparse_matmul,
    pack_block_mask,
    pack_block_mask_rows,
    pack_block_mask_rows_traced,
    pack_block_mask_traced,
    pack_group_mask,
    pack_group_mask_rows,
    pack_group_mask_rows_traced,
    pack_group_mask_traced,
    topkast_block_sparse_matmul,
    topkast_grouped_block_sparse_matmul,
)
from .masked_matmul import (
    fused_grouped_masked_matmul,
    fused_masked_matmul,
    grouped_masked_matmul,
    masked_matmul,
    topkast_grouped_masked_matmul,
    topkast_masked_matmul,
)
from .topk_threshold import N_BINS, histogram_abs

__all__ = [
    "masked_linear",
    "block_sparse_linear",
    "grouped_masked_linear",
    "grouped_block_sparse_linear",
    "topkast_masked_linear",
    "topkast_grouped_masked_linear",
    "fused_masked_linear",
    "fused_grouped_masked_linear",
    "fused_block_sparse_linear",
    "fused_grouped_block_sparse_linear",
    "topk_threshold",
    "auto_interpret",
]


def auto_interpret() -> bool:
    """False on TPU (compiled Mosaic kernels), True on the CPU backend
    (interpret mode, for tests).  Any other backend raises: running kernel
    bodies in Python there would hide a mis-detected device."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on 'tpu' or interpreted on 'cpu'; "
        f"the default backend is {backend!r}"
    )


def _round_up(n: int, mult: int) -> int:
    return ((n + mult - 1) // mult) * mult


# Largest row tile, in rows: the kernels' VMEM budget.  Per row of tile, at
# 128 x 128 blocks in bf16, fwd and dgrad hold a double-buffered input row
# (2 x bk x 2 B), a double-buffered output row (2 x bn x 2 B) and an f32
# accumulator row (bn x 4 B): 1.5 KiB; wgrad holds two double-buffered input
# rows: 1 KiB.  2048 rows take 3 MiB (5 MiB with f32 operands), well inside
# the v5e's 16 MiB default scoped VMEM, and a 2048-token microbatch runs as
# one row tile.
_MAX_ROW_TILE = 2048


def _row_tile(M: int, bm: int) -> tuple[int, int]:
    """(row tile, padded M) for M rows; bm (``kernel_block[0]``) is the
    granule the tile grows by.

    Rows below one granule shrink the tile to the 16-padded row count (16 =
    bf16 sublane min) instead of padding a tiny batch all the way to bm.
    Otherwise M pads to whole granules, and the tile is the largest whole
    number of granules that divides the padded M and stays within
    ``_MAX_ROW_TILE``: the whole padded M where it fits.  Every grid step of
    the kernels then covers as many rows as VMEM allows, and the padding is
    never more than one granule's.  The choice is published as the gauge
    ``kernel_row_tile{rows}`` (rows = padded M) while the call is traced.
    """
    tile = min(bm, _round_up(M, 16))
    Mp = _round_up(M, tile)
    n = Mp // tile
    most = max(_MAX_ROW_TILE // tile, 1)
    tile *= max(d for d in range(1, min(n, most) + 1) if n % d == 0)
    REGISTRY.gauge(
        "kernel_row_tile", "row tile of the block-sparse and masked kernels",
        labels=("rows",),
    ).labels(Mp).set(tile)
    return tile, Mp


def _pad_rows(x2, Mp: int):
    M = x2.shape[0]
    return x2 if Mp == M else jnp.pad(x2, ((0, Mp - M), (0, 0)))


def masked_linear(x, w, mask, *, block=(128, 128, 128), interpret=None):
    """out = x @ (w*mask) with the mask fused into the matmul pipeline.

    mask: (K, N) bool, ANY sparsity pattern (no block alignment needed) —
    the mask is applied to each weight tile inside VMEM, so the masked weight
    copy w*m is never written to (or re-read from) HBM.  Differentiable: the
    custom-VJP backward fuses the mask into dgrad (dx = g @ (w*m)T) and wgrad
    (dw = (xT @ g) * m), so cotangents off-mask are exactly zero.
    block: (bm, bn, bk) VMEM tile sizes; non-aligned M/K/N are zero-padded up
    to the (clamped) tiles and trimmed after.  interpret=None auto-selects
    compiled-on-TPU / interpret-elsewhere.
    """
    interpret = auto_interpret() if interpret is None else interpret
    bm, bn, bk = block
    *lead, K = x.shape
    N = w.shape[1]
    x2 = x.reshape(-1, K)
    M = x2.shape[0]
    bm_eff, Mp = _row_tile(M, bm)
    x2 = _pad_rows(x2, Mp)
    # pad K/N up to their (clamped) tiles; zero pad-weights contribute nothing
    Kp = _round_up(K, min(bk, K))
    Np = _round_up(N, min(bn, N))
    if Kp != K:
        x2 = jnp.pad(x2, ((0, 0), (0, Kp - K)))
    if (Kp, Np) != (K, N):
        w = jnp.pad(w, ((0, Kp - K), (0, Np - N)))
        mask = jnp.pad(mask, ((0, Kp - K), (0, Np - N)))
    out = masked_matmul(
        x2, w, mask, bm=bm_eff, bn=bn, bk=bk, interpret=interpret
    )
    return out[:M, :N].reshape(*lead, N)


def topkast_masked_linear(
    x, w, mask, bwd_mask, *, block=(128, 128, 128), interpret=None
):
    """out = x @ (w*mask), weight gradient masked by bwd_mask ⊇ mask.

    The Top-KAST split of ``masked_linear`` (docs/training.md#topkast): the
    forward and dgrad fuse the tight mask A; the wgrad kernel fuses the
    backward superset B, so dw is the dense gradient restricted to B with no
    dense matmul anywhere.  Padding/trimming identical to ``masked_linear``
    (both masks are padded with zeros, preserving A ⊆ B).
    """
    interpret = auto_interpret() if interpret is None else interpret
    bm, bn, bk = block
    *lead, K = x.shape
    N = w.shape[1]
    x2 = x.reshape(-1, K)
    M = x2.shape[0]
    bm_eff, Mp = _row_tile(M, bm)
    x2 = _pad_rows(x2, Mp)
    Kp = _round_up(K, min(bk, K))
    Np = _round_up(N, min(bn, N))
    if Kp != K:
        x2 = jnp.pad(x2, ((0, 0), (0, Kp - K)))
    if (Kp, Np) != (K, N):
        w = jnp.pad(w, ((0, Kp - K), (0, Np - N)))
        mask = jnp.pad(mask, ((0, Kp - K), (0, Np - N)))
        bwd_mask = jnp.pad(bwd_mask, ((0, Kp - K), (0, Np - N)))
    out = topkast_masked_matmul(
        x2, w, mask, bwd_mask, bm=bm_eff, bn=bn, bk=bk, interpret=interpret
    )
    return out[:M, :N].reshape(*lead, N)


def topkast_grouped_masked_linear(
    x, w, mask, bwd_mask, *, block=(128, 128, 128), interpret=None
):
    """Grouped Top-KAST masked linear: per-group forward ⊙ A, wgrad ⊙ B."""
    interpret = auto_interpret() if interpret is None else interpret
    bm, bn, bk = block
    G, M, K = x.shape
    N = w.shape[2]
    bm_eff, Mp = _row_tile(M, bm)
    if Mp != M:
        x = jnp.pad(x, ((0, 0), (0, Mp - M), (0, 0)))
    Kp = _round_up(K, min(bk, K))
    Np = _round_up(N, min(bn, N))
    if Kp != K:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, Kp - K)))
    if (Kp, Np) != (K, N):
        w = jnp.pad(w, ((0, 0), (0, Kp - K), (0, Np - N)))
        mask = jnp.pad(mask, ((0, 0), (0, Kp - K), (0, Np - N)))
        bwd_mask = jnp.pad(bwd_mask, ((0, 0), (0, Kp - K), (0, Np - N)))
    out = topkast_grouped_masked_matmul(
        x, w, mask, bwd_mask, bm=bm_eff, bn=bn, bk=bk, interpret=interpret
    )
    return out[:, :M, :N]


def block_sparse_linear(
    x, w, block_mask=None, *, block=(128, 128, 128), interpret=None, pack=None
):
    """out = x @ w_blocksparse, skipping inactive (bk x bn) weight blocks.

    Exactly one topology source must be usable:

    pack: precomputed packing — a PackState entry dict (core/pack.py,
        ``{"idx", "cnt", "ridx", "rcnt", ...}``) or a bare ``(idx, cnt)``
        CSC tuple from ``pack_block_mask``.  This is the TIGHT-GRID path:
        the forward/wgrad grid's third dim is ``idx.shape[1]`` (the true max
        active-block count), not the worst case, and an entry's host-packed
        CSR (``ridx``/``rcnt``) makes the dgrad grid tight too (a bare CSC
        tuple falls back to a worst-case-width derived CSR for dgrad).
        Train/serve state carries these packs and refreshes them only on
        RigL topology updates, so the per-call cost is zero.  ``block_mask``
        is ignored.
    block_mask: (K/bk, N/bn) bool fallback when no pack is given —
        concrete (host-side numpy packing, tight width: eval/one-off calls) or
        traced (jit-safe jnp packing, STATIC worst-case width K/bk: correct
        anywhere, but pads the grid with empty iterations).

    The padded and tight paths are bit-identical: both visit the active blocks
    of each column in ascending K-block order, and padded slots neither DMA
    nor accumulate (see docs/kernels.md#tight-vs-padded-grids).

    Differentiable (custom-VJP dgrad/wgrad kernels); leading dims of ``x`` are
    flattened and zero-padded to the M tile; K and N must be tile-aligned.
    """
    interpret = auto_interpret() if interpret is None else interpret
    bm, bn, bk = block
    *lead, K = x.shape
    bk, bn = min(bk, K), min(bn, w.shape[1])
    ridx = rcnt = bidx = bcnt = None
    if pack is not None:
        if isinstance(pack, dict):
            idx, cnt = pack["idx"], pack["cnt"]
            ridx, rcnt = pack.get("ridx"), pack.get("rcnt")
            bidx, bcnt = pack.get("bidx"), pack.get("bcnt")
        else:
            idx, cnt = pack
    elif block_mask is None:
        raise ValueError(
            "block_sparse_linear needs a topology: pass block_mask= or a "
            "precomputed pack=(idx, cnt) — see docs/kernels.md#packing"
        )
    elif isinstance(block_mask, jax.core.Tracer):
        idx, cnt = pack_block_mask_traced(block_mask)
        ridx, rcnt = pack_block_mask_rows_traced(block_mask)
    else:
        idx, cnt = pack_block_mask(np.asarray(block_mask))
        ridx, rcnt = pack_block_mask_rows(np.asarray(block_mask))
    x2 = x.reshape(-1, K)
    M = x2.shape[0]
    bm_eff, Mp = _row_tile(M, bm)
    x2 = _pad_rows(x2, Mp)
    if bidx is not None:
        # Top-KAST superset pack: wgrad runs on the wider (k+Δ) CSC view.
        out = topkast_block_sparse_matmul(
            x2, w, idx, cnt, bidx, bcnt, ridx, rcnt,
            bm=bm_eff, bn=bn, bk=bk, interpret=interpret,
        )
    else:
        out = block_sparse_matmul(
            x2, w, idx, cnt, ridx, rcnt, bm=bm_eff, bn=bn, bk=bk,
            interpret=interpret,
        )
    return out[:M].reshape(*lead, w.shape[1])


def grouped_masked_linear(x, w, mask, *, block=(128, 128, 128), interpret=None):
    """out[g] = x[g] @ (w[g]*mask[g]) for every group g, ONE kernel launch.

    x: (G, M, K); w, mask: (G, K, N) -> (G, M, N).  The grouped twin of
    ``masked_linear`` for weight BANKS — MoE per-expert ``ecd,edf->ecf``
    einsums (G = experts) and xLSTM per-head ``bnh,nhk->bnk`` recurrences
    (G = heads, after layers.grouped_linear's reshape shim).  Any mask
    pattern; per-group w*m only ever exists tile-wise in VMEM.
    Differentiable (grouped custom-VJP dgrad/wgrad kernels); M is padded to
    the (clamped) row tile and K/N to their tiles, exactly like
    ``masked_linear``.
    """
    interpret = auto_interpret() if interpret is None else interpret
    bm, bn, bk = block
    G, M, K = x.shape
    N = w.shape[2]
    bm_eff, Mp = _row_tile(M, bm)
    if Mp != M:
        x = jnp.pad(x, ((0, 0), (0, Mp - M), (0, 0)))
    Kp = _round_up(K, min(bk, K))
    Np = _round_up(N, min(bn, N))
    if Kp != K:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, Kp - K)))
    if (Kp, Np) != (K, N):
        w = jnp.pad(w, ((0, 0), (0, Kp - K), (0, Np - N)))
        mask = jnp.pad(mask, ((0, 0), (0, Kp - K), (0, Np - N)))
    out = grouped_masked_matmul(
        x, w, mask, bm=bm_eff, bn=bn, bk=bk, interpret=interpret
    )
    return out[:, :M, :N]


def grouped_block_sparse_linear(
    x, w, block_mask=None, *, block=(128, 128, 128), interpret=None, pack=None
):
    """out[g] = x[g] @ w_blocksparse[g], one launch over the whole bank.

    x: (G, M, K); w: (G, K, N) -> (G, M, N).  Topology sources mirror
    ``block_sparse_linear``, stacked over the group dim:

    pack: a grouped PackState entry (core/pack.py — ``idx (G, N/bn, width)``
        etc., per-expert CSC + CSR at one shared width) or a bare stacked
        ``(idx, cnt)`` tuple from ``pack_group_mask``.  Tight grids, zero
        per-call packing cost — the hot path.
    block_mask: (G, K/bk, N/bn) bool fallback — concrete (host numpy pack,
        tight shared width) or traced (jit-safe, worst-case width K/bk).

    A group with zero active blocks outputs zeros (a dead expert behaves like
    an empty column — docs/kernels.md#empty-columns-and-dead-layers).
    Differentiable; M is padded to the row tile; K and N must be
    tile-aligned.
    """
    interpret = auto_interpret() if interpret is None else interpret
    bm, bn, bk = block
    G, M, K = x.shape
    N = w.shape[2]
    bk, bn = min(bk, K), min(bn, N)
    ridx = rcnt = bidx = bcnt = None
    if pack is not None:
        if isinstance(pack, dict):
            idx, cnt = pack["idx"], pack["cnt"]
            ridx, rcnt = pack.get("ridx"), pack.get("rcnt")
            bidx, bcnt = pack.get("bidx"), pack.get("bcnt")
        else:
            idx, cnt = pack
    elif block_mask is None:
        raise ValueError(
            "grouped_block_sparse_linear needs a topology: pass block_mask= "
            "or a precomputed stacked pack=(idx, cnt) — see "
            "docs/kernels.md#packing"
        )
    elif isinstance(block_mask, jax.core.Tracer):
        idx, cnt = pack_group_mask_traced(block_mask)
        ridx, rcnt = pack_group_mask_rows_traced(block_mask)
    else:
        idx, cnt = pack_group_mask(np.asarray(block_mask))
        ridx, rcnt = pack_group_mask_rows(np.asarray(block_mask))
    bm_eff, Mp = _row_tile(M, bm)
    if Mp != M:
        x = jnp.pad(x, ((0, 0), (0, Mp - M), (0, 0)))
    if bidx is not None:
        out = topkast_grouped_block_sparse_matmul(
            x, w, idx, cnt, bidx, bcnt, ridx, rcnt,
            bm=bm_eff, bn=bn, bk=bk, interpret=interpret,
        )
    else:
        out = grouped_block_sparse_matmul(
            x, w, idx, cnt, ridx, rcnt, bm=bm_eff, bn=bn, bk=bk,
            interpret=interpret,
        )
    return out[:, :M]


def fused_masked_linear(
    x, w, mask, mom, seed, *, mu, wd, sr, bwd_mask=None,
    block=(128, 128, 128), interpret=None,
):
    """``masked_linear`` whose weight cotangent is the new SGD momentum.

    The fused-epilogue hot path (docs/kernels.md#fused-epilogue): identical
    forward/dgrad to ``masked_linear``/``topkast_masked_linear``, but the
    wgrad kernel stores m_new = (mu*mom + xᵀg + wd*w) ⊙ wgrad_mask, where
    wgrad_mask is ``bwd_mask`` (Top-KAST superset B) when given, else
    ``mask``.  mom rides the same pad/trim as w (zero-padded; the pad VJP
    trims the cotangent back to (K, N)).  sr=True stochastically rounds the
    emitted momentum onto the bf16 grid in-kernel.
    """
    interpret = auto_interpret() if interpret is None else interpret
    bm, bn, bk = block
    *lead, K = x.shape
    N = w.shape[1]
    wgm = mask if bwd_mask is None else bwd_mask
    x2 = x.reshape(-1, K)
    M = x2.shape[0]
    bm_eff, Mp = _row_tile(M, bm)
    x2 = _pad_rows(x2, Mp)
    Kp = _round_up(K, min(bk, K))
    Np = _round_up(N, min(bn, N))
    if Kp != K:
        x2 = jnp.pad(x2, ((0, 0), (0, Kp - K)))
    if (Kp, Np) != (K, N):
        pad2 = lambda a: jnp.pad(a, ((0, Kp - K), (0, Np - N)))
        w, mask, wgm, mom = pad2(w), pad2(mask), pad2(wgm), pad2(mom)
    out = fused_masked_matmul(
        x2, w, mask, wgm, mom, seed, mu=mu, wd=wd, sr=sr,
        bm=bm_eff, bn=bn, bk=bk, interpret=interpret,
    )
    return out[:M, :N].reshape(*lead, N)


def fused_grouped_masked_linear(
    x, w, mask, mom, seed, *, mu, wd, sr, bwd_mask=None,
    block=(128, 128, 128), interpret=None,
):
    """Grouped ``fused_masked_linear`` (weight banks, one launch)."""
    interpret = auto_interpret() if interpret is None else interpret
    bm, bn, bk = block
    G, M, K = x.shape
    N = w.shape[2]
    wgm = mask if bwd_mask is None else bwd_mask
    bm_eff, Mp = _row_tile(M, bm)
    if Mp != M:
        x = jnp.pad(x, ((0, 0), (0, Mp - M), (0, 0)))
    Kp = _round_up(K, min(bk, K))
    Np = _round_up(N, min(bn, N))
    if Kp != K:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, Kp - K)))
    if (Kp, Np) != (K, N):
        pad3 = lambda a: jnp.pad(a, ((0, 0), (0, Kp - K), (0, Np - N)))
        w, mask, wgm, mom = pad3(w), pad3(mask), pad3(wgm), pad3(mom)
    out = fused_grouped_masked_matmul(
        x, w, mask, wgm, mom, seed, mu=mu, wd=wd, sr=sr,
        bm=bm_eff, bn=bn, bk=bk, interpret=interpret,
    )
    return out[:, :M, :N]


def fused_block_sparse_linear(
    x, w, mom, seed, *, mu, wd, sr, block=(128, 128, 128), interpret=None,
    pack=None, block_mask=None,
):
    """``block_sparse_linear`` whose weight cotangent is the new SGD momentum.

    Topology sources mirror ``block_sparse_linear`` (PackState entry dict /
    bare (idx, cnt) / block_mask); an entry carrying ``bidx``/``bcnt`` runs
    the wgrad-epilogue on the Top-KAST superset B, exactly like the unfused
    topkast route.  mom: dense-laid-out (K, N) momentum (supported on the
    wgrad topology); K/N must be tile-aligned.
    """
    interpret = auto_interpret() if interpret is None else interpret
    bm, bn, bk = block
    *lead, K = x.shape
    bk, bn = min(bk, K), min(bn, w.shape[1])
    ridx = rcnt = bidx = bcnt = None
    if pack is not None:
        if isinstance(pack, dict):
            idx, cnt = pack["idx"], pack["cnt"]
            ridx, rcnt = pack.get("ridx"), pack.get("rcnt")
            bidx, bcnt = pack.get("bidx"), pack.get("bcnt")
        else:
            idx, cnt = pack
    elif block_mask is None:
        raise ValueError(
            "fused_block_sparse_linear needs a topology: pass block_mask= or "
            "a precomputed pack=(idx, cnt) — see docs/kernels.md#packing"
        )
    elif isinstance(block_mask, jax.core.Tracer):
        idx, cnt = pack_block_mask_traced(block_mask)
        ridx, rcnt = pack_block_mask_rows_traced(block_mask)
    else:
        idx, cnt = pack_block_mask(np.asarray(block_mask))
        ridx, rcnt = pack_block_mask_rows(np.asarray(block_mask))
    x2 = x.reshape(-1, K)
    M = x2.shape[0]
    bm_eff, Mp = _row_tile(M, bm)
    x2 = _pad_rows(x2, Mp)
    out = fused_block_sparse_matmul(
        x2, w, idx, cnt, mom, seed, bwd_idx=bidx, bwd_cnt=bcnt,
        row_idx=ridx, row_cnt=rcnt, mu=mu, wd=wd, sr=sr,
        bm=bm_eff, bn=bn, bk=bk, interpret=interpret,
    )
    return out[:M].reshape(*lead, w.shape[1])


def fused_grouped_block_sparse_linear(
    x, w, mom, seed, *, mu, wd, sr, block=(128, 128, 128), interpret=None,
    pack=None, block_mask=None,
):
    """Grouped ``fused_block_sparse_linear`` (MoE banks / xLSTM heads)."""
    interpret = auto_interpret() if interpret is None else interpret
    bm, bn, bk = block
    G, M, K = x.shape
    N = w.shape[2]
    bk, bn = min(bk, K), min(bn, N)
    ridx = rcnt = bidx = bcnt = None
    if pack is not None:
        if isinstance(pack, dict):
            idx, cnt = pack["idx"], pack["cnt"]
            ridx, rcnt = pack.get("ridx"), pack.get("rcnt")
            bidx, bcnt = pack.get("bidx"), pack.get("bcnt")
        else:
            idx, cnt = pack
    elif block_mask is None:
        raise ValueError(
            "fused_grouped_block_sparse_linear needs a topology: pass "
            "block_mask= or a precomputed stacked pack=(idx, cnt) — see "
            "docs/kernels.md#packing"
        )
    elif isinstance(block_mask, jax.core.Tracer):
        idx, cnt = pack_group_mask_traced(block_mask)
        ridx, rcnt = pack_group_mask_rows_traced(block_mask)
    else:
        idx, cnt = pack_group_mask(np.asarray(block_mask))
        ridx, rcnt = pack_group_mask_rows(np.asarray(block_mask))
    bm_eff, Mp = _row_tile(M, bm)
    if Mp != M:
        x = jnp.pad(x, ((0, 0), (0, Mp - M), (0, 0)))
    out = fused_grouped_block_sparse_matmul(
        x, w, idx, cnt, mom, seed, bwd_idx=bidx, bwd_cnt=bcnt,
        row_idx=ridx, row_cnt=rcnt, mu=mu, wd=wd, sr=sr,
        bm=bm_eff, bn=bn, bk=bk, interpret=interpret,
    )
    return out[:, :M]


def topk_threshold(x, k: int, *, refine: bool = True, interpret=None):
    """Threshold t s.t. |{i: |x_i| >= t}| ~= k, via streaming histogram.

    One pass + optional one refinement pass over the bracketing bin;
    |count - k| <= occupancy of one (refined) bin.
    """
    interpret = auto_interpret() if interpret is None else interpret
    hi = jnp.max(jnp.abs(x)).astype(jnp.float32) + 1e-12
    hist = histogram_abs(x, hi, interpret=interpret)[0]
    # cumulative count from the TOP bin down
    desc = jnp.cumsum(hist[::-1])
    bin_from_top = jnp.argmax(desc >= k)  # first bin where count >= k
    lo_edge = (N_BINS - 1 - bin_from_top) * (hi / N_BINS)
    if not refine:
        return lo_edge
    # refinement: histogram only the bracketing bin's range
    upper = lo_edge + hi / N_BINS
    in_above = jnp.sum(jnp.abs(x.astype(jnp.float32)) >= upper)
    sub = jnp.where(
        (jnp.abs(x.astype(jnp.float32)) >= lo_edge)
        & (jnp.abs(x.astype(jnp.float32)) < upper),
        jnp.abs(x.astype(jnp.float32)) - lo_edge,
        -1.0,
    )
    hist2 = histogram_abs(
        jnp.where(sub >= 0, sub, 2 * hi), hi / N_BINS, interpret=interpret
    )[0]
    need = k - in_above
    desc2 = jnp.cumsum(hist2[::-1])
    b2 = jnp.argmax(desc2 >= need)
    return lo_edge + (N_BINS - 1 - b2) * (hi / N_BINS / N_BINS)
