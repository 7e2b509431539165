"""Flash attention (fwd + custom-VJP bwd) on tight, schedule-driven grids.

Online-softmax tiling (Dao et al., adapted to TPU): grid (batch*heads, Sq/bq,
width) with the KV loop innermost; running (max, sum, acc) live in VMEM
scratch across KV steps.  ``width`` is the grid-clipping piece: instead of
launching the full Sk/bk KV range and @pl.when-guarding dead blocks (which
still DMAs K/V for them — the wasted-DMA note of the original kernel), the
third grid dimension walks a host-built AttnSchedule (core/attn_sched.py):
per q-block row, only its LIVE KV blocks, scalar-prefetched so the BlockSpec
index_map DMAs exactly the K/V tiles the mask family admits.  Causal,
sliding-window and causal+window masks at long context thus skip both the
grid iterations AND the DMA of dead score blocks — the same tight-grid
machinery the weight kernels get from core/pack.py.

Backward is a custom-VJP Pallas kernel pair reusing the same schedule:

  dq     grid (BH, n_q, width)       — the forward schedule (per-q live KV)
  dk/dv  grid (B*KV, n_k, G, q_width) — the TRANSPOSED schedule (per-KV live
                                    q), one kernel producing both cotangents;
                                    the G axis sums each KV tile's cotangent
                                    over its GQA query-group members

GQA is folded into the BlockSpec index maps (``kv_groups``): K/V stay at
their true KV-head count and q row b reads KV row b // G, so no repeated
K/V copy is ever materialized.  ``logit_softcap`` (gemma/grok) is applied
inside the online softmax, fwd and bwd.

with the standard flash backward recomputation: p = exp(s - lse) from the
saved per-row logsumexp, delta = rowsum(do * o) precomputed in jnp.  Training
therefore no longer falls back to the pure-jnp chunked attention path —
scores never visit HBM in the forward OR the backward.

The padded variant (``tight=False``) runs the SAME kernels on a schedule
whose width is padded to the dense worst case Sk/bk — bit-identical outputs,
longer grid — mirroring the tight-vs-padded weight-pack duality.  ``ref.py``'s
``flash_attention_ref`` is the jnp oracle for all mask families.

Each ``pallas_call`` is named, and the name is its op's name in a compiled
program and a profiler trace: ``flash_fwd``, ``flash_bwd_dq``,
``flash_bwd_dkv`` and ``flash_paged_fwd``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.attn_sched import paged_prefix_schedule, sched_for
from .block_sparse_matmul import _clamp

__all__ = ["flash_attention", "flash_attention_paged", "effective_blocks"]

NEG_INF = -1e30
EPS = 1e-30


def _round_up(n: int, mult: int) -> int:
    return ((n + mult - 1) // mult) * mult


def effective_blocks(
    sq: int, sk: int, bq: int = 128, bk: int = 128
) -> tuple[int, int]:
    """The (bq, bk) ``flash_attention`` will actually run for these lengths
    (tiles clamp to the 16-padded length for short sequences).  Schedule
    builders must use THIS so a pre-built sched matches the kernel's grid."""
    return min(bq, _round_up(sq, 16)), min(bk, _round_up(sk, 16))


def _capped(u, softcap):
    """Gemma/grok-style logit soft-capping s = c * tanh(u / c), applied to the
    RAW scaled scores BEFORE the mask clamp (a NEG_INF-clamped score must stay
    NEG_INF, not saturate to ±c).  softcap == 0.0 disables (python-static, so
    uncapped kernels compile without the tanh).  Returns (s, t) with
    t = tanh(u / c) — the backward reuses t for ds/du = 1 - t²."""
    if not softcap:
        return u, None
    t = jnp.tanh(u / softcap)
    return softcap * t, t


def _score_mask(qb, kb, *, bq, bk, causal, window, q_offset, sk):
    """(bq, bk) bool mask for score block (qb, kb), or None when every
    position is live (interior full-attention block on aligned shapes)."""
    if not causal and not window and sk % bk == 0:
        return None
    qpos = q_offset + qb * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    if sk % bk:  # zero-padded tail keys must never win the softmax
        mask &= kpos < sk
    return mask


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _fwd_kernel(
    kv_idx_ref, kv_cnt_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
    m_ref, l_ref, acc_ref, *, width, bq, bk, causal, window, q_offset, sk,
    scale, softcap,
):
    s_id = pl.program_id(2)

    @pl.when(s_id == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qb = pl.program_id(1)
    kb = _clamp(kv_idx_ref, kv_cnt_ref, qb, s_id)

    @pl.when(s_id < kv_cnt_ref[qb])
    def _step():
        q = q_ref[0]  # (bq, d)
        k = k_ref[0]  # (bk, d)
        v = v_ref[0]
        s, _ = _capped(
            jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale,
            softcap,
        )
        mask = _score_mask(
            qb, kb, bq=bq, bk=bk, causal=causal, window=window,
            q_offset=q_offset, sk=sk,
        )
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if mask is not None:
            # a fully-masked ROW of a live block has s == m_new == NEG_INF,
            # where exp(s - m_new) = 1 would corrupt l; zero masked slots so
            # dead rows keep l == 0 (and thus output zeros, see _finish)
            p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(s_id == width - 1)
    def _finish():
        l_raw = l_ref[...]
        l = jnp.maximum(l_raw, EPS)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        # per-row logsumexp residual for the backward recomputation; rows
        # with NO live key get +1e30 so the backward's exp(s - lse) is
        # exactly zero for them instead of overflowing
        lse_ref[0] = jnp.where(l_raw > 0.0, m_ref[...] + jnp.log(l), -NEG_INF)


def _dq_kernel(
    kv_idx_ref, kv_cnt_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dq_ref, acc_ref, *, width, bq, bk, causal, window, q_offset, sk, scale,
    softcap,
):
    """dq (bq, d) += (p * (do@vT - delta)) @ k * scale over live KV blocks.
    With softcap, ds additionally carries the cap's chain factor 1 - t²."""
    s_id = pl.program_id(2)

    @pl.when(s_id == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qb = pl.program_id(1)
    kb = _clamp(kv_idx_ref, kv_cnt_ref, qb, s_id)

    @pl.when(s_id < kv_cnt_ref[qb])
    def _step():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s, t = _capped(
            jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale,
            softcap,
        )
        mask = _score_mask(
            qb, kb, bq=bq, bk=bk, causal=causal, window=window,
            q_offset=q_offset, sk=sk,
        )
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse_ref[0])  # masked slots: exp(-inf) = 0
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0]) * scale
        if t is not None:
            ds = ds * (1.0 - t * t)
        acc_ref[...] += jnp.dot(
            ds.astype(k.dtype), k, preferred_element_type=jnp.float32
        )

    @pl.when(s_id == width - 1)
    def _finish():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(
    q_idx_ref, q_cnt_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dk_ref, dv_ref, dk_acc, dv_acc, *, q_width, groups, bq, bk, causal,
    window, q_offset, sk, scale, softcap,
):
    """One kernel for both KV cotangents, walking the TRANSPOSED schedule:
    dv (bk, d) += pT @ do;  dk (bk, d) += dsT @ q * scale.

    Grid (B*KV, n_k, G, q_width): under GQA folding a KV tile's cotangent is
    the SUM over its G query-group members, so the group dim is one more
    accumulated grid axis — the (bk, d) K/V tile and the dk/dv accumulators
    stay resident across the (gm, s) inner loops while the q-side tiles walk
    row b*G + gm of the folded (BH, ...) layout.  G == 1 recovers the plain
    MHA backward exactly."""
    gm = pl.program_id(2)
    s_id = pl.program_id(3)

    @pl.when((gm == 0) & (s_id == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    kb = pl.program_id(1)
    qb = _clamp(q_idx_ref, q_cnt_ref, kb, s_id)

    @pl.when(s_id < q_cnt_ref[kb])
    def _step():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s, t = _capped(
            jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale,
            softcap,
        )
        mask = _score_mask(
            qb, kb, bq=bq, bk=bk, causal=causal, window=window,
            q_offset=q_offset, sk=sk,
        )
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse_ref[0])
        dv_acc[...] += jnp.dot(
            p.T.astype(do.dtype), do, preferred_element_type=jnp.float32
        )
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0]) * scale
        if t is not None:
            ds = ds * (1.0 - t * t)
        dk_acc[...] += jnp.dot(
            ds.T.astype(q.dtype), q, preferred_element_type=jnp.float32
        )

    @pl.when((gm == groups - 1) & (s_id == q_width - 1))
    def _finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call wrappers
# ---------------------------------------------------------------------------

def _fwd_call(q, k, v, kv_idx, kv_cnt, bq, bk, causal, window, q_offset, sk,
              scale, softcap, kv_groups, interpret):
    BH, Sqp, d = q.shape
    width = kv_idx.shape[1]
    n_q = Sqp // bq
    grid = (BH, n_q, width)

    def kv_map(b, qb, s, idx_ref, cnt_ref):
        # GQA fold: query row b of the (B*H, ...) layout reads KV row
        # b // G of the UNREPEATED (B*KV, ...) layout — the G query heads of
        # a group share the same physical tiles, so the G-fold repeated K/V
        # copy (and its HBM write + re-read) never exists
        return (b // kv_groups, _clamp(idx_ref, cnt_ref, qb, s), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, qb, s, *_: (b, qb, 0)),
            pl.BlockSpec((1, bk, d), kv_map),
            pl.BlockSpec((1, bk, d), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, qb, s, *_: (b, qb, 0)),
            # per-row lse (and delta in the backward) carry a trailing unit
            # dim: Mosaic needs a block's last two dims divisible by (8, 128)
            # or equal to the array's, which a (1, bq) row block is not
            pl.BlockSpec((1, bq, 1), lambda b, qb, s, *_: (b, qb, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _fwd_kernel, width=width, bq=bq, bk=bk, causal=causal,
            window=window, q_offset=q_offset, sk=sk, scale=scale,
            softcap=softcap,
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sqp, d), q.dtype),
            jax.ShapeDtypeStruct((BH, Sqp, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(kv_idx, kv_cnt, q, k, v)


def _paged_kernel(
    kv_idx_ref, table_ref, ctx_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
    m_ref, l_ref, acc_ref, *, n_pages, bq, bs, scale, softcap,
):
    """Prefix phase of suffix-only prefill over a PAGED KV cache.

    Grid (B, H, n_q, n_pages): step s of q row qb visits logical prefix
    page kv_idx[qb, s]; the BlockSpec index map routes it through the
    scalar-prefetched block table to a physical pool page (GQA folded:
    kv head = h // G in the map, no K/V repeat).  Liveness is dynamic —
    only ceil(ctx[b] / bs) leading pages hold valid prefix keys — so the
    walk clips in-flight via @pl.when, and within the boundary page
    kpos >= ctx masks to NEG_INF.  Every prefix key precedes every suffix
    query, so there is no causal masking here; rows with ctx == 0 emit
    zeros with lse = NEG_INF (NOT the fwd kernel's +1e30 sentinel: the
    logsumexp MERGE with the self phase needs exp(lse - m) to underflow
    to exactly 0 for the empty phase).
    """
    b = pl.program_id(0)
    s_id = pl.program_id(3)

    @pl.when(s_id == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qb = pl.program_id(2)
    j = kv_idx_ref[qb, s_id]  # logical page index (kpos = j * bs + lane)
    ctx = ctx_ref[b]
    n_live = (ctx + bs - 1) // bs

    @pl.when(s_id < n_live)
    def _step():
        q = q_ref[0, 0]  # (bq, d)
        k = k_ref[0, 0]  # (bs, d)
        v = v_ref[0, 0]
        s, _ = _capped(
            jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale,
            softcap,
        )
        kpos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (bq, bs), 1)
        mask = kpos < ctx
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(s_id == n_pages - 1)
    def _finish():
        l_raw = l_ref[...]
        l = jnp.maximum(l_raw, EPS)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.where(l_raw > 0.0, m_ref[...] + jnp.log(l), NEG_INF)


def _paged_call(q, pk, pv, kv_idx, table, ctx, bq, scale, softcap, interpret):
    """q: (B, H, Sqp, d); pk/pv: pool TRANSPOSED to (N, KV, bs, d) so each
    grid step DMAs one (bs, d) page tile; table: (B, T); ctx: (B,)."""
    B, H, Sqp, d = q.shape
    N, KV, bs, _ = pk.shape
    G = H // KV
    n_pages = kv_idx.shape[1]
    grid = (B, H, Sqp // bq, n_pages)

    def q_map(b, h, qb, s, *_):
        return (b, h, qb, 0)

    def kv_map(b, h, qb, s, idx_ref, tab_ref, ctx_ref):
        # padded steps (s >= live count) re-see the last live page: index
        # unchanged => Pallas skips the re-DMA (same idiom as _clamp); the
        # min() guards the n_blocks SENTINEL on unowned table entries
        n_live = (ctx_ref[b] + bs - 1) // bs
        j = idx_ref[qb, jnp.maximum(jnp.minimum(s, n_live - 1), 0)]
        return (jnp.minimum(tab_ref[b, j], N - 1), h // G, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), q_map),
            pl.BlockSpec((1, 1, bs, d), kv_map),
            pl.BlockSpec((1, 1, bs, d), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), q_map),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, qb, s, *_: (b, h, qb, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _paged_kernel, n_pages=n_pages, bq=bq, bs=bs, scale=scale,
            softcap=softcap,
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sqp, d), q.dtype),
            jax.ShapeDtypeStruct((B, H, Sqp, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_paged_fwd",
    )(kv_idx, table, ctx, q, pk, pv)


@functools.partial(
    jax.jit, static_argnames=("bq", "scale", "softcap", "interpret")
)
def _paged_jit(q, pk, pv, kv_idx, table, ctx, *, bq, scale, softcap,
               interpret):
    return _paged_call(
        q, pk, pv, kv_idx, table, ctx, bq, scale, softcap, interpret
    )


def flash_attention_paged(
    q, pool_k, pool_v, table, ctx, *, bq: int = 128, softcap: float = 0.0,
    interpret=None,
):
    """Suffix queries attending a paged KV prefix through a block table.

    q: (B, H, Sq, hd) roped suffix queries; pool_k/pool_v: (N, bs, KV, hd)
    paged caches (models/attention.py::init_kv_pool); table: (B, T) int32
    physical page ids (the sentinel id N marks unowned entries — never
    live, clamped in the index map); ctx: (B,) int32 valid prefix lengths.
    Returns (o: (B, H, Sq, hd), lse: (B, H, Sq) f32) — the PREFIX phase of
    shared-prefix suffix prefill; models/attention.py merges it with the
    causal self phase by logsumexp.  Rows with ctx == 0 return zeros with
    lse = -1e30 (weight exactly 0 in the merge).  Forward-only: serving
    prefill never differentiates.
    """
    from .ops import auto_interpret

    interpret = auto_interpret() if interpret is None else interpret
    B, H, Sq, d = q.shape
    bs = pool_k.shape[1]
    bq = min(bq, _round_up(Sq, 16))
    Sqp = _round_up(Sq, bq)
    if Sqp != Sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Sqp - Sq), (0, 0)))
    sched = paged_prefix_schedule(Sqp, int(table.shape[1]), bq, int(bs))
    o, lse = _paged_jit(
        q,
        pool_k.transpose(0, 2, 1, 3),
        pool_v.transpose(0, 2, 1, 3),
        jnp.asarray(sched["kv_idx"]),
        jnp.asarray(table, jnp.int32),
        jnp.asarray(ctx, jnp.int32),
        bq=bq,
        scale=float(1.0 / np.sqrt(d)),
        softcap=float(softcap),
        interpret=interpret,
    )
    return o[:, :, :Sq], lse[:, :, :Sq, 0]


def _dq_call(q, k, v, do, lse, delta, kv_idx, kv_cnt, bq, bk, causal, window,
             q_offset, sk, scale, softcap, kv_groups, interpret):
    BH, Sqp, d = q.shape
    width = kv_idx.shape[1]
    grid = (BH, Sqp // bq, width)

    def q_map(b, qb, s, *_):
        return (b, qb, 0)

    def row_map(b, qb, s, *_):
        return (b, qb, 0)

    def kv_map(b, qb, s, idx_ref, cnt_ref):
        # same GQA fold as the forward: K/V stay at their true KV-head count
        return (b // kv_groups, _clamp(idx_ref, cnt_ref, qb, s), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), q_map),
            pl.BlockSpec((1, bk, d), kv_map),
            pl.BlockSpec((1, bk, d), kv_map),
            pl.BlockSpec((1, bq, d), q_map),
            pl.BlockSpec((1, bq, 1), row_map),
            pl.BlockSpec((1, bq, 1), row_map),
        ],
        out_specs=pl.BlockSpec((1, bq, d), q_map),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(
            _dq_kernel, width=width, bq=bq, bk=bk, causal=causal,
            window=window, q_offset=q_offset, sk=sk, scale=scale,
            softcap=softcap,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((BH, Sqp, d), q.dtype),
        interpret=interpret,
        name="flash_bwd_dq",
    )(kv_idx, kv_cnt, q, k, v, do, lse, delta)


def _dkv_call(q, k, v, do, lse, delta, q_idx, q_cnt, bq, bk, causal, window,
              q_offset, sk, scale, softcap, kv_groups, interpret):
    # k/v (and dk/dv) live at the true KV-head count B*KV = BH // G; the
    # grid grows a GROUP axis between the KV-block and schedule dims so each
    # KV tile's cotangent accumulates over its G query-group members while
    # the (bk, d) tile and both accumulators stay VMEM-resident
    BKV, Skp, d = k.shape
    q_width = q_idx.shape[1]
    grid = (BKV, Skp // bk, kv_groups, q_width)

    def q_map(b, kb, gm, s, idx_ref, cnt_ref):
        return (b * kv_groups + gm, _clamp(idx_ref, cnt_ref, kb, s), 0)

    def row_map(b, kb, gm, s, idx_ref, cnt_ref):
        return (b * kv_groups + gm, _clamp(idx_ref, cnt_ref, kb, s), 0)

    def kv_map(b, kb, gm, s, *_):
        return (b, kb, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), q_map),
            pl.BlockSpec((1, bk, d), kv_map),
            pl.BlockSpec((1, bk, d), kv_map),
            pl.BlockSpec((1, bq, d), q_map),
            pl.BlockSpec((1, bq, 1), row_map),
            pl.BlockSpec((1, bq, 1), row_map),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), kv_map),
            pl.BlockSpec((1, bk, d), kv_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _dkv_kernel, q_width=q_width, groups=kv_groups, bq=bq, bk=bk,
            causal=causal, window=window, q_offset=q_offset, sk=sk,
            scale=scale, softcap=softcap,
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((BKV, Skp, d), k.dtype),
            jax.ShapeDtypeStruct((BKV, Skp, d), v.dtype),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q_idx, q_cnt, q, k, v, do, lse, delta)


# ---------------------------------------------------------------------------
# custom VJP
# ---------------------------------------------------------------------------

@functools.partial(
    jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11, 12, 13, 14, 15, 16)
)
def _flash(q, k, v, kv_idx, kv_cnt, q_idx, q_cnt, bq, bk, causal, window,
           q_offset, sk, scale, softcap, kv_groups, interpret):
    out, _ = _fwd_call(
        q, k, v, kv_idx, kv_cnt, bq, bk, causal, window, q_offset, sk, scale,
        softcap, kv_groups, interpret,
    )
    return out


def _flash_fwd(q, k, v, kv_idx, kv_cnt, q_idx, q_cnt, bq, bk, causal, window,
               q_offset, sk, scale, softcap, kv_groups, interpret):
    out, lse = _fwd_call(
        q, k, v, kv_idx, kv_cnt, bq, bk, causal, window, q_offset, sk, scale,
        softcap, kv_groups, interpret,
    )
    return out, (q, k, v, out, lse, kv_idx, kv_cnt, q_idx, q_cnt)


def _flash_bwd(bq, bk, causal, window, q_offset, sk, scale, softcap,
               kv_groups, interpret, res, do):
    q, k, v, out, lse, kv_idx, kv_cnt, q_idx, q_cnt = res
    # delta_i = sum_j p_ij * dp_ij = rowsum(do * o): O(S*d) in jnp, f32
    delta = jnp.sum(
        do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1,
        keepdims=True,
    )
    dq = _dq_call(
        q, k, v, do, lse, delta, kv_idx, kv_cnt, bq, bk, causal, window,
        q_offset, sk, scale, softcap, kv_groups, interpret,
    )
    dk, dv = _dkv_call(
        q, k, v, do, lse, delta, q_idx, q_cnt, bq, bk, causal, window,
        q_offset, sk, scale, softcap, kv_groups, interpret,
    )
    z = lambda a: np.zeros(a.shape, jax.dtypes.float0)
    return dq, dk, dv, z(kv_idx), z(kv_cnt), z(q_idx), z(q_cnt)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(
    jax.jit,
    static_argnames=(
        "bq", "bk", "causal", "window", "q_offset", "sk", "scale", "softcap",
        "kv_groups", "interpret",
    ),
)
def _flash_jit(q, k, v, kv_idx, kv_cnt, q_idx, q_cnt, *, bq, bk, causal,
               window, q_offset, sk, scale, softcap, kv_groups, interpret):
    return _flash(
        q, k, v, kv_idx, kv_cnt, q_idx, q_cnt, bq, bk, causal, window,
        q_offset, sk, scale, softcap, kv_groups, interpret,
    )


def _pad_width(idx: jnp.ndarray, to: int) -> jnp.ndarray:
    """Pad a schedule's width up to the dense worst case (padded-grid mode).
    Slots beyond cnt are clamped by the kernels, so the fill value is inert."""
    pad = to - idx.shape[1]
    if pad <= 0:
        return idx
    return jnp.pad(idx, ((0, 0), (0, pad)))


@functools.partial(
    jax.jit,
    static_argnames=(
        "bq", "bk", "causal", "window", "q_offset", "sk", "scale", "softcap",
        "kv_groups", "interpret",
    ),
)
def _fwd_jit(q, k, v, kv_idx, kv_cnt, *, bq, bk, causal, window, q_offset,
             sk, scale, softcap, kv_groups, interpret):
    return _fwd_call(
        q, k, v, kv_idx, kv_cnt, bq, bk, causal, window, q_offset, sk, scale,
        softcap, kv_groups, interpret,
    )


def flash_attention(
    q, k, v, *, causal: bool = True, window: int = 0, sched=None,
    tight: bool = True, bq: int = 128, bk: int = 128, softcap: float = 0.0,
    kv_groups: int = 1, interpret=None, return_lse: bool = False,
):
    """q: (BH, Sq, d); k, v: (BH/kv_groups, Sk, d) -> (BH, Sq, d).
    Differentiable.

    Softmax attention with scores only ever materialized tile-wise in VMEM,
    fwd and bwd (custom-VJP Pallas kernel pair).  The mask family is
    (causal, window) with models/attention.py::_make_mask semantics: query
    row r sits at absolute position ``Sk - Sq + r`` (right-aligned — 0 offset
    for the ubiquitous Sq == Sk), keys at their column index; ``window`` masks
    keys at or below ``qpos - window``.  A row with no live key (possible
    only in degenerate window-family shapes) outputs zeros, NOT the
    uniform-softmax artifact the NEG_INF-clamped jnp reference produces.

    sched: an AttnSchedule (core/attn_sched.py) built for EXACTLY this
    (Sq, Sk, bq, bk, causal, window); None builds one lazily (memoized,
    trace-time — schedules are static-shape-derived, so this is free).
    tight=True launches the schedule's tight grid (width = max live KV blocks
    per q row); tight=False pads the width to the dense worst case Sk/bk —
    bit-identical output, every slot beyond a row's count an empty iteration
    (the old @pl.when-only behaviour, kept as the padded baseline).

    softcap: gemma/grok-style logit soft-capping c*tanh(s/c) applied to the
    scaled scores inside the online softmax (0.0 disables).  Exact in the
    custom VJP too — ds carries the cap's 1 - tanh² chain factor — so capped
    configs train on the flash path with no dense fallback.

    kv_groups: GQA group fold.  G > 1 takes k/v at their TRUE KV-head count
    (BH/G, Sk, d) — q row b reads KV row b // G via the BlockSpec index maps,
    so the G-fold repeated K/V copy `_flash_attend` used to materialize (and
    its HBM write + re-read) never exists.  dk/dv grow a group grid axis and
    accumulate each KV tile's cotangent over its G group members in VMEM —
    the repeat-path's G-fold dk/dv output plus jnp segment-sum disappears
    too.  G == 1 is the plain MHA layout, bit-identical to before.

    Non-aligned Sq/Sk are zero-padded up to the (clamped) block sizes and
    trimmed after; padded keys are masked in-kernel, padded query rows cost
    dead rows in the boundary block only.  interpret=None auto-selects
    (compiled on TPU, interpret elsewhere).

    return_lse=True additionally returns the per-row logsumexp (BH, Sq) f32
    (+1e30 on rows with no live key) for phase-merging with another
    attention partial (flash_attention_paged) — FORWARD-ONLY: this path
    bypasses the custom VJP, so don't differentiate through it.
    """
    from .ops import auto_interpret

    interpret = auto_interpret() if interpret is None else interpret
    BH, Sq, d = q.shape
    Sk = k.shape[1]
    kv_groups = int(kv_groups)
    if BH % kv_groups or k.shape[0] != BH // kv_groups:
        raise ValueError(
            f"flash_attention: q has {BH} batch*head rows but k/v have "
            f"{k.shape[0]} with kv_groups={kv_groups} — expected "
            "k.shape[0] == q.shape[0] // kv_groups (UNREPEATED KV heads)"
        )
    bq, bk = effective_blocks(Sq, Sk, bq, bk)
    Sqp, Skp = _round_up(Sq, bq), _round_up(Sk, bk)
    q_offset = Sk - Sq
    if sched is None:
        sched = sched_for(Sq, Sk, bq, bk, causal, window, q_offset)
    else:
        got = (sched["sq"], sched["sk"], sched["bq"], sched["bk"],
               sched["causal"], sched["window"], sched["q_offset"])
        want = (Sq, Sk, bq, bk, bool(causal), int(window), q_offset)
        if got != want:
            raise ValueError(
                f"flash_attention: sched built for {got} but called with "
                f"{want} — schedules are per (shape, blocks, mask family); "
                "see docs/kernels.md#attention-schedules"
            )
    kv_idx, kv_cnt = sched["kv_idx"], sched["kv_cnt"]
    q_idx, q_cnt = sched["q_idx"], sched["q_cnt"]
    if not tight:  # padded baseline: dense-worst-case grid, same schedule
        kv_idx = _pad_width(kv_idx, Skp // bk)
        q_idx = _pad_width(q_idx, Sqp // bq)
    if Sqp != Sq:
        q = jnp.pad(q, ((0, 0), (0, Sqp - Sq), (0, 0)))
    if Skp != Sk:
        k = jnp.pad(k, ((0, 0), (0, Skp - Sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Skp - Sk), (0, 0)))
    if return_lse:
        out, lse = _fwd_jit(
            q, k, v, kv_idx, kv_cnt, bq=bq, bk=bk, causal=bool(causal),
            window=int(window), q_offset=q_offset, sk=Sk,
            scale=float(1.0 / np.sqrt(d)), softcap=float(softcap),
            kv_groups=kv_groups, interpret=interpret,
        )
        return out[:, :Sq], lse[:, :Sq, 0]
    out = _flash_jit(
        q, k, v, kv_idx, kv_cnt, q_idx, q_cnt, bq=bq, bk=bk,
        causal=bool(causal), window=int(window), q_offset=q_offset, sk=Sk,
        scale=float(1.0 / np.sqrt(d)), softcap=float(softcap),
        kv_groups=kv_groups, interpret=interpret,
    )
    return out[:, :Sq]
