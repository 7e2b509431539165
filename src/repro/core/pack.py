"""PackState — host-packed block topology carried in train/serve state.

The block-sparse kernels (kernels/block_sparse_matmul.py) are driven by a CSC
packing of the block-activity mask: per N-block column, the ids of its active
K-blocks (``idx (N/bn, width) int32``) and how many are real (``cnt (N/bn,)``).
The kernel grid's third dimension is ``width`` — every padded slot is a
launched-but-skipped grid iteration.  Inside jit the mask is a tracer, so the
trace-safe pack must pad ``width`` to the STATIC worst case (K/bk), which makes
every grid as expensive (in iterations) as a dense one.

PackState fixes that: the packing is computed HOST-SIDE (numpy, tight width)
from the concrete masks, stored in the train/serve state as a pytree mirroring
the mask tree, and threaded through the model into
``ops.block_sparse_linear(pack=...)``.  Both the train step and prefill/decode
then launch grids sized to the true active-block count.  RigL only changes the
topology every ``delta_t`` steps, so the pack is refreshed exactly there —
the host repack is amortized over >= delta_t matmuls (paper Appendix H
cost-structure argument, applied to grid shape instead of gradient cost).

Lifecycle (documented end-to-end in docs/kernels.md):

  init      training/steps.py::init_train_state builds ``state["pack"]`` when
            cfg.sparse.kernel == 'block_sparse'
  train     training/steps.py::make_train_step threads state["pack"] into the
            loss (models/model.py -> layers.linear -> ops.block_sparse_linear)
  update    launch/train.py refreshes the pack right after every rigl_step —
            a rigl_step WITHOUT a refresh leaves the pack stale, which the
            ``pack_stale`` train-step metric (pack_mismatch below) surfaces
  ckpt      the pack is ordinary int32 leaves in the state pytree, so
            checkpoint/ persists and restores it with everything else
  serve     launch/serve.py threads the serve state's pack (built by
            init_train_state, or restored with a checkpoint) into every
            prefill/decode call — packed once per topology, reused per token

Entry layout (one per packable mask leaf, ``None`` elsewhere):

  {"idx":  (N/bn, width) int32,   # active K-block ids per N-block, CSC —
   "cnt":  (N/bn,) int32,         #   drives the fwd and wgrad kernel grids
   "ridx": (K/bk, row_width) i32, # active N-block ids per K-block, CSR —
   "rcnt": (K/bk,) int32,         #   drives the custom-VJP dgrad grid
   "nnz":  () int32,              # total active blocks (bookkeeping/bench)
   "nkb":  () int32}              # K/bk — the CSC padded worst-case width

Grouped weight banks (3-D masks: MoE per-expert (E, d, ff), xLSTM per-head
(nh, hd, 4hd)) carry the same entry with a leading group dim on idx/cnt/
ridx/rcnt — per-group CSC/CSR at ONE shared width, consumed by the grouped
kernels in a single launch (docs/kernels.md#grouped-packs).

Top-KAST backward-superset pair (docs/training.md#topkast): when the state
carries backward masks B ⊇ A (method='topkast', or rigl/snfs under kernel
dispatch — core/rigl.py ``topkast_backward_masks``), every entry additionally
packs B's CSC as a SECOND, wider view:

  {"bidx": (N/bn, bwidth) int32,  # superset K-block ids — drives the wgrad
   "bcnt": (N/bn,) int32,         #   grid, so dw covers the whole (k+Δ) set
   "bnnz": () int32}              # superset active blocks

The forward/dgrad grids keep running on the tight idx/ridx views; only wgrad
widens to bidx — ops.block_sparse_linear routes to the Top-KAST custom VJP
exactly when these fields are present.  ``pack_entry`` refuses a superset
that does not contain the forward topology (the containment is what makes
the superset gradient exact on B's support).  With kernel='masked' the
analogous carrier entry is just ``{"bwd_mask": bool (K, N)}``
(``build_bwd_carrier``): the masked kernels take elementwise masks directly,
no packing needed.

Width policy: ``width = max_j cnt[j]`` (tight; same for ``row_width`` over
``rcnt``), but never below the width of ``prev`` when refreshing — widths only
ever grow within a run, so jit retraces on topology updates are bounded by the
drift toward the worst case instead of happening on every shrink/grow wiggle.
``SparseConfig.pack_width_slack`` adds hysteresis on top: widths round UP to
the next multiple of ``ceil(slack * worst_case)`` (never down), so a topology
whose per-column max wiggles by a block or two per refresh stays on ONE packed
shape — a few padded (empty) grid iterations bought against a jit retrace per
update.  Grouped banks feel this most: their shared width is the max over ALL
experts/heads, so any one lopsided group used to widen (and retrace) the whole
bank.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import REGISTRY, region
from .masks import block_mask_of, path_name

__all__ = [
    "build_pack_state",
    "build_bwd_carrier",
    "refresh_pack_state",
    "pack_entry",
    "pack_mismatch",
    "pack_stats",
    "publish_pack_gauges",
    "is_pack_entry",
    "slack_width",
    "validate_pack",
    "PackIntegrityError",
]


class PackIntegrityError(ValueError):
    """A PackState entry violates its CSC/CSR structural invariants.

    Raised by ``validate_pack`` — a corrupted pack (truncated rows,
    out-of-range block ids, count/nnz drift) would otherwise make the
    block-sparse kernels silently execute the WRONG topology: wrong answers
    with no error, the exact failure the serving engine's integrity guard
    (docs/serving.md#failure-model) exists to make loud.
    """


def is_pack_entry(x) -> bool:
    """Leaf predicate for pack pytrees (an entry dict or a None leaf).

    Covers the block-sparse CSC/CSR entries, the masked-kernel
    backward-superset carrier (``{"bwd_mask": ...}``, build_bwd_carrier), and
    the fused-epilogue entries the train step builds per-trace by merging
    ``{"mom", "seed", "mu", "wd", "sr"}`` into either of the above
    (training/steps.py — layers.linear routes on the ``mom`` key).
    """
    return x is None or (
        isinstance(x, dict)
        and (("idx" in x and "cnt" in x) or "bwd_mask" in x or "mom" in x)
    )


# Param subtrees whose 2-D weight einsums dispatch through layers.linear /
# layers.grouped_linear and therefore consume packs (models/).  Since the
# total-dispatch PR this covers EVERY model family: transformer attention +
# MLP, hymba's SSM projections, xLSTM's mLSTM/sLSTM projections (incl. the
# grouped per-head recurrence), and MoE expert banks + shared experts
# (grouped per-expert CSC/CSR — see docs/kernels.md#grouped-packs).  The
# remaining non-matmul leaves (scan carries, gates, convs, routers) are dense
# and never masked, so they have no entries by construction.
DISPATCHED_SUBTREES = ("attn", "mlp", "ssm", "slstm", "mlstm", "moe")


def _dispatched(name: str) -> bool:
    return any(part in DISPATCHED_SUBTREES for part in name.split("/"))


def _packable(m, block_shape) -> bool:
    bk, bn = block_shape
    return (
        m is not None
        and m.ndim in (2, 3)
        and m.shape[-2] % bk == 0
        and m.shape[-1] % bn == 0
    )


def slack_width(width: int, worst: int, slack: float) -> int:
    """Round a packed width UP to the next hysteresis step, capped at worst.

    The step is ``ceil(slack * worst)`` (worst = the padded worst-case width,
    K/bk): slack=0 keeps the exact tight width; slack=0.25 quantizes widths to
    quarters of the dense grid, so a refresh only changes the packed SHAPE
    (and thus retraces the jitted step) when the true width crosses a quarter
    boundary.  Never rounds down — composing with the never-shrink floor.
    """
    if slack <= 0.0 or width >= worst:
        return min(width, worst)
    step = max(int(np.ceil(slack * worst)), 1)
    return min(-(-width // step) * step, worst)


def pack_entry(
    mask, block_shape, *, min_width: int = 0, min_row_width: int = 0,
    slack: float = 0.0, name: str = "?", bwd_mask=None, min_bwd_width: int = 0,
    obs=None,
):
    """Host-pack ONE mask leaf into a PackState entry (CSC + CSR views).

    2-D masks pack as before; 3-D masks (grouped weight banks — MoE experts,
    xLSTM per-head recurrences) pack PER GROUP over the trailing two dims,
    stacked at one shared width (``idx (G, N/bn, width)`` etc.) so the
    grouped kernels execute the whole bank in one launch.

    Raises loudly (rather than packing an all-zero topology) when the layer
    has no active blocks at all: the block-sparse forward would silently
    output zeros for the whole layer, which is never what a sparsity
    distribution intends — see docs/kernels.md#empty-columns-and-dead-layers.
    Individual all-zero COLUMNS are fine (the kernel writes zeros for them),
    and so is an all-zero GROUP of a grouped bank: a dead expert/head outputs
    zeros, which is semantically well-defined under MoE routing — only the
    bank-level all-zero case raises.

    bwd_mask: the layer's Top-KAST backward superset B ⊇ A — packed as a
    second CSC view (``bidx``/``bcnt``/``bnnz``) driving the wgrad grid.
    Raises PackIntegrityError when B does not contain the forward mask at
    block granularity: a forward-active block missing from the wgrad grid
    would silently zero that block's gradient (the exact silent-wrong-answer
    class validate_pack exists to make loud).

    Its phases are ``region``s (obs/trace.py; ``obs`` an optional
    Observability handle): ``repro.pack.to_host`` fetches the mask and
    superset leaves (waiting for them) and counts the bytes in
    ``repro_pack_bytes_to_host_total``; ``repro.pack.build`` packs in numpy;
    ``repro.pack.to_device`` puts the packed arrays on the device.
    """
    with region("repro.pack.to_host", obs=obs):
        m = np.asarray(mask, bool)
        b = None if bwd_mask is None else np.asarray(bwd_mask, bool)
    fetched = sum(x.nbytes for x in (mask, bwd_mask) if isinstance(x, jax.Array))
    if fetched:
        (obs.metrics if obs is not None else REGISTRY).counter(
            "repro_pack_bytes_to_host_total",
            "mask bytes fetched to the host to pack them",
        ).inc(fetched)
    with region("repro.pack.build", obs=obs):
        host = _pack_host_entry(
            m, b, block_shape, min_width=min_width,
            min_row_width=min_row_width, slack=slack, name=name,
            min_bwd_width=min_bwd_width,
        )
    with region("repro.pack.to_device", obs=obs):
        return {k: jnp.asarray(v) for k, v in host.items()}


def _pack_host_entry(m, b, block_shape, *, min_width, min_row_width, slack,
                     name, min_bwd_width):
    """pack_entry's numpy half: host masks -> the entry's arrays."""
    from ..kernels.block_sparse_matmul import pack_host

    bm = block_mask_of(m, block_shape)
    nkb, nnb = bm.shape[-2], bm.shape[-1]
    total = int(bm.sum())
    if total == 0:
        raise ValueError(
            f"PackState: layer {name!r} has ZERO active blocks — the "
            "block-sparse kernel would output all-zeros for it. This almost "
            "always means the sparsity distribution assigned (near-)1.0 "
            "sparsity to a layer smaller than one block; see "
            "docs/kernels.md#empty-columns-and-dead-layers"
        )
    width = slack_width(
        max(int(bm.sum(axis=-2).max()), 1, min_width), nkb, slack
    )
    row_width = slack_width(
        max(int(bm.sum(axis=-1).max()), 1, min_row_width), nnb, slack
    )
    idx, cnt = pack_host(bm, width)
    ridx, rcnt = pack_host(np.swapaxes(bm, -1, -2), row_width)
    entry = {
        "idx": idx,
        "cnt": cnt,
        "ridx": ridx,
        "rcnt": rcnt,
        "nnz": np.int32(total),
        "nkb": np.int32(nkb),
    }
    if b is not None:
        bbm = block_mask_of(b, block_shape)
        if np.any(bm & ~bbm):
            raise PackIntegrityError(
                f"PackState: layer {name!r} backward superset does not "
                "contain its forward topology — wgrad would silently zero "
                "forward-active blocks; the superset must be rebuilt from "
                "the CURRENT masks (core/rigl.py::topkast_backward_masks)"
            )
        bwidth = slack_width(
            max(int(bbm.sum(axis=-2).max()), 1, min_bwd_width), nkb, slack
        )
        bidx, bcnt = pack_host(bbm, bwidth)
        entry |= {"bidx": bidx, "bcnt": bcnt, "bnnz": np.int32(int(bbm.sum()))}
    return entry


def build_pack_state(
    masks, block_shape, *, prev=None, slack: float = 0.0, bwd_masks=None,
    obs=None,
):
    """Masks pytree -> PackState pytree (same structure; entry or None leaves).

    masks must be CONCRETE (host) arrays — this runs outside jit, on the
    amortized topology-update path, never in the per-step hot loop.
    prev: a previous PackState; per-layer widths are kept >= prev's widths so
    the packed shapes (and thus the jitted train step) stay stable when a
    topology update shrinks some column's count.
    slack: width hysteresis (SparseConfig.pack_width_slack) — widths round up
    to the next ``slack_width`` step so drifting topologies retrace less.
    bwd_masks: Top-KAST backward supersets mirroring masks; packed entries
    additionally carry the superset CSC (``bidx``/``bcnt``/``bnnz``) driving
    the wgrad grid (docs/training.md#topkast).
    obs: optional Observability handle for pack_entry's phase regions.  The
    leaves are packed one at a time, so a leaf's fetch waits only for that
    leaf (a superset still being drawn overlaps the packing before it).
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        masks, is_leaf=lambda x: x is None
    )
    flat_b = (
        jax.tree_util.tree_flatten(bwd_masks, is_leaf=lambda x: x is None)[0]
        if bwd_masks is not None
        else [None] * len(flat)
    )
    prev_leaves = (
        jax.tree_util.tree_leaves(prev, is_leaf=is_pack_entry)
        if prev is not None
        else [None] * len(flat)
    )
    entries = []
    for (path, m), bw, pe in zip(flat, flat_b, prev_leaves):
        name = path_name(path)
        if not _packable(m, block_shape) or not _dispatched(name):
            entries.append(None)
            continue
        min_w = int(pe["idx"].shape[-1]) if pe is not None else 0
        min_rw = (
            int(pe["ridx"].shape[-1]) if pe is not None and "ridx" in pe else 0
        )
        min_bw = (
            int(pe["bidx"].shape[-1]) if pe is not None and "bidx" in pe else 0
        )
        entries.append(
            pack_entry(
                m, block_shape, min_width=min_w, min_row_width=min_rw,
                slack=slack, name=name, bwd_mask=bw, min_bwd_width=min_bw,
                obs=obs,
            )
        )
    return jax.tree_util.tree_unflatten(treedef, entries)


def build_bwd_carrier(bwd_masks):
    """Backward supersets -> masked-kernel carrier pack (docs/training.md).

    kernel='masked' takes elementwise masks directly, so the Top-KAST
    superset needs no CSC packing — each dispatched leaf just rides along as
    ``{"bwd_mask": bool (..., K, N)}``; layers.linear routes to the Top-KAST
    masked VJP when it sees this entry.  Leaves outside the dispatched
    subtrees (or dense ``None`` leaves) carry ``None``, mirroring
    ``build_pack_state``'s gating.
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        bwd_masks, is_leaf=lambda x: x is None
    )
    entries = []
    for path, m in flat:
        if m is None or not _dispatched(path_name(path)):
            entries.append(None)
            continue
        entries.append({"bwd_mask": jnp.asarray(m, bool)})
    return jax.tree_util.tree_unflatten(treedef, entries)


def refresh_pack_state(
    masks, block_shape, *, prev, slack: float = 0.0, bwd_masks=None, obs=None
):
    """Re-pack after a topology update (call right after every rigl_step).

    Same as build_pack_state but prev is required — refreshing without the
    previous pack would let widths shrink and retrigger jit compilation on
    every update.
    """
    return build_pack_state(
        masks, block_shape, prev=prev, slack=slack, bwd_masks=bwd_masks,
        obs=obs,
    )


def pack_mismatch(masks, pack, block_shape, bwd_masks=None):
    """Traced-safe exact staleness check: #blocks where pack != masks.

    Returns an int32 scalar, 0 iff every pack entry encodes exactly the block
    mask of its layer (the entry is scattered back to a block mask via
    kernels.block_sparse_matmul.unpack_block_mask — the same reconstruction
    the VJP's CSR fallback uses).  Cost: one elementwise any-reduce over each
    mask (O(#sparsifiable params), no batch/seq factor) plus tiny block-grid
    compares — the train step already does O(#params) elementwise mask work
    every step (dense_to_sparse_grad), so reporting this as the per-step
    ``pack_stale`` metric is noise next to the M-scaled matmuls.  A nonzero
    value means a rigl_step ran without refresh_pack_state and the kernels
    are executing a stale topology (docs/kernels.md#staleness).

    bwd_masks: when given (Top-KAST superset pairs), entries carrying a
    ``bidx`` view are also checked against the block mask of their backward
    superset — a stale wgrad grid is just as silently wrong as a stale
    forward grid.
    """
    from ..kernels.block_sparse_matmul import unpack_block_mask

    flat_m = jax.tree_util.tree_flatten(masks, is_leaf=lambda x: x is None)[0]
    flat_b = (
        jax.tree_util.tree_flatten(bwd_masks, is_leaf=lambda x: x is None)[0]
        if bwd_masks is not None
        else [None] * len(flat_m)
    )
    flat_e = jax.tree_util.tree_leaves(pack, is_leaf=is_pack_entry)
    total = jnp.int32(0)

    def _recount(idx, cnt, bm):
        if idx.ndim == 3:  # grouped bank: per-group reconstruction
            rec = jax.vmap(
                lambda i_, c_: unpack_block_mask(i_, c_, bm.shape[-2])
            )(idx, cnt)
        else:
            rec = unpack_block_mask(idx, cnt, bm.shape[0])
        return jnp.sum(rec != bm).astype(jnp.int32)

    for m, bw, e in zip(flat_m, flat_b, flat_e):
        if e is None or not _packable(m, block_shape):
            continue
        total = total + _recount(e["idx"], e["cnt"], block_mask_of(m, block_shape))
        if bw is not None and "bidx" in e:
            total = total + _recount(
                e["bidx"], e["bcnt"], block_mask_of(bw, block_shape)
            )
    return total


def validate_pack(pack, *, where: str = "pack") -> int:
    """Host-side CSC/CSR integrity check over every PackState entry.

    Verifies, per packed leaf (2-D and grouped 3-D entries alike):

      * shape coherence — ``cnt`` matches ``idx`` minus its width dim, same
        for ``rcnt``/``ridx``, and the CSR view has one row per K-block
        (``ridx.shape[-2] == nkb``);
      * counts within capacity — ``0 <= cnt <= width`` and
        ``0 <= rcnt <= row_width`` (a truncated pack shows up as a count
        claiming more slots than the index rows hold);
      * live indices in range — every index slot BELOW its column's count
        holds a block id inside the grid (``idx`` in ``[0, nkb)``, ``ridx``
        in ``[0, nnb)``); padded slots beyond the count are ignored;
      * nnz consistency — ``sum(cnt) == nnz == sum(rcnt)`` (the CSC and CSR
        views must describe the SAME topology).

    Raises ``PackIntegrityError`` naming the layer and the violated
    invariant; returns the number of entries checked.  Cost is O(block
    grid) numpy on the host — nothing per-token: callers run it at engine
    construction and after every ``refresh_pack`` (training/steps.py), the
    same amortized points that build packs in the first place.
    """
    if pack is None:
        return 0
    flat, _ = jax.tree_util.tree_flatten_with_path(pack, is_leaf=is_pack_entry)
    checked = 0
    for path, e in flat:
        if e is None:
            continue
        name = f"{where}:{path_name(path)}"

        def fail(msg):
            raise PackIntegrityError(
                f"PackState integrity violation at {name}: {msg} — the "
                "block-sparse kernels would execute a corrupted topology "
                "(silent wrong answers); see docs/serving.md#failure-model"
            )

        if "bwd_mask" in e:  # masked-kernel superset carrier — no CSC fields
            if np.asarray(e["bwd_mask"]).dtype != np.bool_:
                fail("bwd_mask carrier is not a bool array")
            checked += 1
            continue
        for k in ("idx", "cnt", "ridx", "rcnt", "nnz", "nkb"):
            if k not in e:
                fail(f"entry is missing field {k!r}")
        idx = np.asarray(e["idx"])
        cnt = np.asarray(e["cnt"])
        ridx = np.asarray(e["ridx"])
        rcnt = np.asarray(e["rcnt"])
        nnz = int(e["nnz"])
        nkb = int(e["nkb"])
        if idx.shape[:-1] != cnt.shape:
            fail(f"idx {idx.shape} does not extend cnt {cnt.shape}")
        if ridx.shape[:-1] != rcnt.shape:
            fail(f"ridx {ridx.shape} does not extend rcnt {rcnt.shape}")
        if ridx.shape[-2] != nkb:
            fail(f"CSR has {ridx.shape[-2]} rows, expected nkb={nkb}")
        width, row_width = idx.shape[-1], ridx.shape[-1]
        nnb = cnt.shape[-1]
        if cnt.size and (cnt.min() < 0 or cnt.max() > width):
            fail(
                f"cnt out of range [0, width={width}] "
                f"(max {int(cnt.max())} — truncated pack?)"
            )
        if rcnt.size and (rcnt.min() < 0 or rcnt.max() > row_width):
            fail(
                f"rcnt out of range [0, row_width={row_width}] "
                f"(max {int(rcnt.max())} — truncated pack?)"
            )
        live = np.arange(width) < cnt[..., None]
        if np.any(live & ((idx < 0) | (idx >= nkb))):
            fail(f"live CSC index outside the K-block grid [0, {nkb})")
        rlive = np.arange(row_width) < rcnt[..., None]
        if np.any(rlive & ((ridx < 0) | (ridx >= nnb))):
            fail(f"live CSR index outside the N-block grid [0, {nnb})")
        csum, rsum = int(cnt.sum()), int(rcnt.sum())
        if csum != nnz or rsum != nnz:
            fail(
                f"nnz inconsistency: sum(cnt)={csum}, sum(rcnt)={rsum}, "
                f"recorded nnz={nnz}"
            )
        if "bidx" in e:  # Top-KAST superset CSC — same invariants, wider view
            bidx = np.asarray(e["bidx"])
            bcnt = np.asarray(e["bcnt"])
            bnnz = int(e["bnnz"])
            bwidth = bidx.shape[-1]
            if bidx.shape[:-1] != bcnt.shape:
                fail(f"bidx {bidx.shape} does not extend bcnt {bcnt.shape}")
            if bcnt.size and (bcnt.min() < 0 or bcnt.max() > bwidth):
                fail(
                    f"bcnt out of range [0, bwidth={bwidth}] "
                    f"(max {int(bcnt.max())} — truncated superset pack?)"
                )
            blive = np.arange(bwidth) < bcnt[..., None]
            if np.any(blive & ((bidx < 0) | (bidx >= nkb))):
                fail(f"live superset index outside the K-block grid [0, {nkb})")
            if int(bcnt.sum()) != bnnz:
                fail(
                    f"superset nnz inconsistency: sum(bcnt)={int(bcnt.sum())}, "
                    f"recorded bnnz={bnnz}"
                )
            if bnnz < nnz:
                fail(
                    f"superset smaller than forward topology (bnnz={bnnz} < "
                    f"nnz={nnz}) — B must contain A"
                )
            # Containment: every forward-active block must appear live in the
            # superset CSC, else wgrad silently zeros it.  Padded slots
            # scatter into a dummy trailing column so they can't clobber
            # block 0.
            fwd = np.zeros((*cnt.shape, nkb + 1), bool)
            np.put_along_axis(fwd, np.where(live, idx, nkb), live, axis=-1)
            fwd = fwd[..., :nkb]
            sup = np.zeros((*bcnt.shape, nkb + 1), bool)
            np.put_along_axis(sup, np.where(blive, bidx, nkb), blive, axis=-1)
            sup = sup[..., :nkb]
            if np.any(fwd & ~sup):
                fail(
                    "forward-active block missing from the backward superset "
                    "CSC — B does not contain A"
                )
        checked += 1
    return checked


def pack_stats(pack) -> dict[str, Any]:
    """Host-side bookkeeping: per-layer grid width vs the padded worst case,
    plus block-grid densities — ``density`` is live forward blocks over the
    full (nkb x cols x groups) block grid, ``superset_density`` the same for
    the Top-KAST backward superset B (None when the entry carries no
    superset).  These feed the live ``kernel_*`` gauges
    (docs/observability.md#metric-catalog), so the tight-grid win and the
    B-vs-A overhead are visible during a run, not only in kernel_bench."""
    out: dict[str, Any] = {"layers": {}}
    tight = padded = 0
    nnz_total = bnnz_total = cells_total = bcells_total = 0
    flat, _ = jax.tree_util.tree_flatten_with_path(pack, is_leaf=is_pack_entry)
    for path, e in flat:
        if e is None:
            continue
        name = path_name(path)
        width = int(e["idx"].shape[-1])
        nkb = int(e["nkb"])
        groups = int(e["idx"].shape[0]) if e["idx"].ndim == 3 else 1
        cols = int(e["cnt"].shape[-1])
        nnz = int(e["nnz"])
        cells = nkb * cols * groups
        bnnz = int(e["bnnz"]) if "bidx" in e else None
        out["layers"][name] = {
            "width": width,
            "worst_case": nkb,
            "grid_fraction": width / nkb,
            "row_width": int(e["ridx"].shape[-1]) if "ridx" in e else None,
            "nnz_blocks": nnz,
            "cols": cols,
            "groups": groups,
            "density": nnz / cells if cells else 0.0,
            "superset_density": (
                bnnz / cells if bnnz is not None and cells else None
            ),
        }
        tight += width * groups
        padded += nkb * groups
        nnz_total += nnz
        cells_total += cells
        if bnnz is not None:
            bnnz_total += bnnz
            bcells_total += cells
    out["grid_iters_tight"] = tight
    out["grid_iters_padded"] = padded
    out["grid_fraction"] = tight / padded if padded else 1.0
    out["density"] = nnz_total / cells_total if cells_total else 0.0
    out["superset_density"] = (
        bnnz_total / bcells_total if bcells_total else None
    )
    return out


def publish_pack_gauges(metrics, pack) -> None:
    """Set the kernel_* gauges on a metrics registry (duck-typed: any object
    with ``gauge(name, help, labels)``) from ``pack_stats``: runtime grid
    fraction plus forward/superset block densities, per layer and under the
    ``_total`` aggregate label.  Both the serving engine (construction — its
    pack is engine-lifetime constant) and the trainer (every refresh_pack)
    publish through this one helper, so the catalog names stay identical
    across the two paths (docs/observability.md#metric-catalog)."""
    if pack is None:
        return
    st = pack_stats(pack)
    gf = metrics.gauge("kernel_grid_fraction",
                       "packed grid width / padded worst case",
                       labels=("layer",))
    dn = metrics.gauge("kernel_block_density",
                       "live forward blocks / full block grid",
                       labels=("layer",))
    sd = metrics.gauge("kernel_superset_density",
                       "Top-KAST backward-superset blocks / full block grid",
                       labels=("layer",))
    gf.labels("_total").set(st["grid_fraction"])
    dn.labels("_total").set(st["density"])
    if st["superset_density"] is not None:
        sd.labels("_total").set(st["superset_density"])
    for name, ls in st["layers"].items():
        gf.labels(name).set(ls["grid_fraction"])
        dn.labels(name).set(ls["density"])
        if ls["superset_density"] is not None:
            sd.labels(name).set(ls["superset_density"])
