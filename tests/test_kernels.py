"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU): shape/dtype
sweeps + hypothesis mask patterns."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, settings, st

from repro.kernels import ref
from repro.kernels.ops import (
    _MAX_ROW_TILE,
    _row_tile,
    block_sparse_linear,
    grouped_block_sparse_linear,
    masked_linear,
    topk_threshold,
)

pytestmark = pytest.mark.kernels

SHAPES = [(128, 128, 128), (256, 384, 128), (128, 512, 256)]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_masked_matmul_sweep(shape, dtype):
    M, K, N = shape
    key = jax.random.PRNGKey(hash(shape) % 2**31)
    x = jax.random.normal(key, (M, K)).astype(dtype)
    w = jax.random.normal(jax.random.fold_in(key, 1), (K, N)).astype(dtype)
    m = jax.random.uniform(jax.random.fold_in(key, 2), (K, N)) > 0.8
    out = masked_linear(x, w, m, interpret=True)
    expect = ref.masked_matmul_ref(x, w, m)
    tol = 2e-5 * K if dtype == jnp.float32 else 2e-2 * np.sqrt(K)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32), atol=tol
    )



@pytest.mark.parametrize(
    "backend,interpret", [("cpu", True), ("tpu", False), ("gpu", None)]
)
def test_auto_interpret_by_backend(monkeypatch, backend, interpret):
    """Interpret mode on the CPU backend only, compiled kernels on TPU, and
    an error anywhere else: no silent Python kernel bodies on a device."""
    from repro.kernels import ops

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match="gpu"):
            ops.auto_interpret()
    else:
        assert ops.auto_interpret() is interpret

@pytest.mark.parametrize("density", [0.0, 0.25, 0.75, 1.0])
def test_block_sparse_matmul_densities(density):
    M, K, N, bk, bn = 128, 512, 256, 128, 128
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (M, K), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (K, N), jnp.float32)
    bm = jax.random.uniform(jax.random.fold_in(key, 2), (K // bk, N // bn)) < density
    out = block_sparse_linear(x, w, bm, interpret=True)
    expect = ref.block_sparse_matmul_ref(x, w, bm, bk, bn)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=1e-3)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_property_block_sparse_random_masks(seed):
    M, K, N, bk, bn = 128, 256, 256, 128, 128
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (M, K), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (K, N), jnp.float32)
    bm = jax.random.uniform(jax.random.fold_in(key, 2), (K // bk, N // bn)) < 0.5
    out = block_sparse_linear(x, w, bm, interpret=True)
    expect = ref.block_sparse_matmul_ref(x, w, bm, bk, bn)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=1e-3)


# ---------------------------------------------------------------------------
# backward kernels (custom VJP) vs jax.grad of the dense-masked reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(128, 128, 128), (256, 384, 128), (100, 64, 96)])
def test_masked_matmul_grad_vs_ref(shape):
    """jax.grad through the Pallas dgrad/wgrad kernels == grad of ref (1e-4);
    last shape exercises the non-aligned-M padding path."""
    M, K, N = shape
    key = jax.random.PRNGKey(1 + hash(shape) % 2**31)
    x = jax.random.normal(key, (M, K), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (K, N), jnp.float32)
    m = jax.random.uniform(jax.random.fold_in(key, 2), (K, N)) > 0.8

    f_k = lambda x, w: jnp.sum(jnp.sin(masked_linear(x, w, m, interpret=True)))
    f_r = lambda x, w: jnp.sum(jnp.sin(ref.masked_matmul_ref(x, w, m)))
    gx_k, gw_k = jax.grad(f_k, argnums=(0, 1))(x, w)
    gx_r, gw_r = jax.grad(f_r, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx_k), np.asarray(gx_r), atol=1e-4)
    np.testing.assert_allclose(np.asarray(gw_k), np.asarray(gw_r), atol=1e-4)
    # the wgrad kernel fuses g*m: cotangent is exactly zero off-mask
    assert float(jnp.max(jnp.abs(jnp.where(m, 0.0, gw_k)))) == 0.0


@pytest.mark.parametrize("density", [0.0, 0.3, 0.7])
def test_block_sparse_grad_vs_ref(density):
    M, K, N, bk, bn = 100, 256, 256, 64, 64
    key = jax.random.PRNGKey(17)
    x = jax.random.normal(key, (M, K), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (K, N), jnp.float32)
    bm = jax.random.uniform(jax.random.fold_in(key, 2), (K // bk, N // bn)) < density
    dense_mask = jnp.repeat(jnp.repeat(bm, bk, axis=0), bn, axis=1)

    f_k = lambda x, w: jnp.sum(
        jnp.cos(block_sparse_linear(x, w, bm, block=(128, bn, bk), interpret=True))
    )
    f_r = lambda x, w: jnp.sum(jnp.cos(ref.block_sparse_matmul_ref(x, w, bm, bk, bn)))
    gx_k, gw_k = jax.grad(f_k, argnums=(0, 1))(x, w)
    gx_r, gw_r = jax.grad(f_r, argnums=(0, 1))(x, w)
    # rtol for f32 accumulation-order noise on O(10) grads over K=256
    np.testing.assert_allclose(
        np.asarray(gx_k), np.asarray(gx_r), rtol=1e-4, atol=1e-4
    )
    np.testing.assert_allclose(
        np.asarray(gw_k), np.asarray(gw_r), rtol=1e-4, atol=1e-4
    )
    # packed wgrad scatters ONLY active blocks; everything else exactly zero
    assert float(jnp.max(jnp.abs(jnp.where(dense_mask, 0.0, gw_k)))) == 0.0


@pytest.mark.parametrize("M", [8, 100, 128, 129, 512, 2048, 3000, 8192])
def test_row_tile(M):
    """The row tile grows by whole granules up to the VMEM budget, and pads
    no more rows than a fixed granule-sized tile would."""
    granule = 128
    tile, Mp = _row_tile(M, granule)
    fixed = min(granule, -(-M // 16) * 16)
    assert Mp == -(-M // fixed) * fixed  # the fixed tile's padding, no more
    assert Mp % tile == 0 and tile <= max(_MAX_ROW_TILE, fixed)
    if M >= granule:
        assert tile % granule == 0
    expect = {8: 16, 100: 112, 512: 512, 2048: 2048, 3000: 1536,
              8192: _MAX_ROW_TILE}
    if M in expect:
        assert tile == expect[M]


@pytest.mark.parametrize("tile", [512, 128])
@pytest.mark.parametrize("path", ["plain", "topkast", "grouped"])
def test_block_sparse_row_tiles_vs_ref(monkeypatch, path, tile):
    """512 rows at a 128-row granule run as one 512-row tile, or, under a
    128-row budget, as four; forward and jax.grad (dgrad, and wgrad reduced
    over the row tiles) match the oracle either way."""
    from repro.core.pack import pack_entry
    from repro.kernels import ops

    M, K, N, bs = 512, 256, 192, 64
    monkeypatch.setattr(ops, "_MAX_ROW_TILE", tile)
    assert _row_tile(M, 128) == (tile, M)
    key = jax.random.PRNGKey(29)
    lead = (2,) if path == "grouped" else ()
    # activations at the scale a normalized layer feeds (1/sqrt(K)), so f32
    # rounding over 512 rows stays below the tolerances whatever the tile
    x = jax.random.normal(key, (*lead, M, K), jnp.float32) / np.sqrt(K)
    w = jax.random.normal(jax.random.fold_in(key, 1), (*lead, K, N), jnp.float32)
    blocks = jax.random.uniform(
        jax.random.fold_in(key, 2), (*lead, K // bs, N // bs)) < 0.5
    grow = jax.random.uniform(
        jax.random.fold_in(key, 3), (*lead, K // bs, N // bs)) < 0.3
    expand = lambda b: jnp.repeat(jnp.repeat(b, bs, axis=-2), bs, axis=-1)
    block = (128, bs, bs)
    if path == "grouped":
        kern = lambda x, w: grouped_block_sparse_linear(
            x, w, blocks, block=block, interpret=True)
        oracle = lambda x, w: ref.grouped_block_sparse_matmul_ref(
            x, w, blocks, bs, bs)
        wgrad_mask = expand(blocks)
        tol = dict(rtol=1e-5, atol=1e-5)
    else:
        pack = pack_entry(  # Top-KAST: wgrad runs on the superset's blocks
            np.asarray(expand(blocks)), (bs, bs),
            bwd_mask=np.asarray(expand(blocks | grow))
            if path == "topkast" else None,
        )
        assert ("bidx" in pack) == (path == "topkast")
        kern = lambda x, w: block_sparse_linear(
            x, w, pack=pack, block=block, interpret=True)
        oracle = lambda x, w: ref.block_sparse_matmul_ref(x, w, blocks, bs, bs)
        wgrad_mask = expand(blocks | grow if path == "topkast" else blocks)
        tol = dict(rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(kern(x, w)), np.asarray(oracle(x, w)), atol=1e-3
    )
    loss = lambda f: lambda x, w: jnp.sum(jnp.cos(f(x, w)))
    gx_k, gw_k = jax.grad(loss(kern), argnums=(0, 1))(x, w)
    gx_r = jax.grad(loss(oracle))(x, w)
    # wgrad is the dense gradient at the forward's weights on its own blocks
    w_fwd = w * expand(blocks).astype(w.dtype)
    dense = lambda we: jnp.sum(jnp.cos(jnp.einsum("...mk,...kn->...mn", x, we)))
    gw_r = jax.grad(dense)(w_fwd) * wgrad_mask.astype(w.dtype)
    np.testing.assert_allclose(np.asarray(gx_k), np.asarray(gx_r), **tol)
    np.testing.assert_allclose(np.asarray(gw_k), np.asarray(gw_r), **tol)
    assert float(jnp.max(jnp.abs(jnp.where(wgrad_mask, 0.0, gw_k)))) == 0.0


def test_block_sparse_grad_traced_mask_under_jit():
    """Training hot path: the block mask is a traced array inside jit."""
    K, N, bk, bn = 128, 128, 32, 32
    key = jax.random.PRNGKey(23)
    x = jax.random.normal(key, (64, K), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (K, N), jnp.float32)
    bm = jax.random.uniform(jax.random.fold_in(key, 2), (K // bk, N // bn)) < 0.5

    gfn = jax.jit(
        jax.grad(
            lambda w, bmask: jnp.sum(
                block_sparse_linear(x, w, bmask, block=(128, bn, bk), interpret=True)
            )
        )
    )
    gw = gfn(w, bm)
    gr = jax.grad(
        lambda w: jnp.sum(ref.block_sparse_matmul_ref(x, w, bm, bk, bn))
    )(w)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(gr), atol=1e-4)


def test_masked_linear_nonaligned_forward():
    """Satellite: odd batch*seq (and odd K/N) pad/trim instead of asserting."""
    key = jax.random.PRNGKey(5)
    for (M, K, N) in [(4, 128, 128), (100, 100, 200), (129, 64, 96)]:
        x = jax.random.normal(key, (M, K), jnp.float32)
        w = jax.random.normal(jax.random.fold_in(key, 1), (K, N), jnp.float32)
        m = jax.random.uniform(jax.random.fold_in(key, 2), (K, N)) > 0.5
        out = masked_linear(x, w, m, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref.masked_matmul_ref(x, w, m)), atol=1e-3
        )


def test_block_sparse_linear_nonaligned_m():
    key = jax.random.PRNGKey(6)
    K, N, bk, bn = 256, 128, 64, 64
    x = jax.random.normal(key, (2, 25, K), jnp.float32)  # M=50, not 128-aligned
    w = jax.random.normal(jax.random.fold_in(key, 1), (K, N), jnp.float32)
    bm = jax.random.uniform(jax.random.fold_in(key, 2), (K // bk, N // bn)) < 0.5
    out = block_sparse_linear(x, w, bm, block=(128, bn, bk), interpret=True)
    expect = ref.block_sparse_matmul_ref(x.reshape(-1, K), w, bm, bk, bn)
    np.testing.assert_allclose(
        np.asarray(out).reshape(-1, N), np.asarray(expect), atol=1e-3
    )


def test_pack_block_mask_vectorized_semantics():
    """The argsort pack reproduces the per-column loop semantics exactly."""
    from repro.kernels.block_sparse_matmul import (
        pack_block_mask, pack_block_mask_rows, pack_block_mask_traced)

    rng = np.random.RandomState(0)
    for _ in range(20):
        bm = rng.rand(rng.randint(1, 9), rng.randint(1, 9)) < rng.rand()
        idx, cnt = pack_block_mask(bm)
        idx, cnt = np.asarray(idx), np.asarray(cnt)
        assert idx.shape == (bm.shape[1], max(int(bm.sum(0).max(initial=0)), 1))
        for j in range(bm.shape[1]):
            act = np.nonzero(bm[:, j])[0]
            assert cnt[j] == len(act)
            np.testing.assert_array_equal(idx[j, : len(act)], act)
            assert (idx[j, len(act):] == 0).all()
        # CSR rows pack == CSC pack of the transpose
        ridx, rcnt = pack_block_mask_rows(bm)
        idx_t, cnt_t = pack_block_mask(bm.T)
        np.testing.assert_array_equal(np.asarray(ridx), np.asarray(idx_t))
        np.testing.assert_array_equal(np.asarray(rcnt), np.asarray(cnt_t))
        # traced variant agrees on the shared (padded) prefix
        jidx, jcnt = pack_block_mask_traced(jnp.asarray(bm))
        np.testing.assert_array_equal(np.asarray(jcnt), cnt)
        np.testing.assert_array_equal(
            np.asarray(jidx)[:, : idx.shape[1]], idx
        )


@pytest.mark.parametrize("n,k", [(65536, 1000), (100_000, 5000), (200_000, 100)])
def test_topk_threshold_accuracy(n, k):
    x = jax.random.normal(jax.random.PRNGKey(0), (n,), jnp.float32)
    t = topk_threshold(x, k, interpret=True)
    cnt = int(jnp.sum(jnp.abs(x) >= t))
    assert abs(cnt - k) <= max(0.05 * k, 8), (cnt, k)
    exact = float(ref.kth_value_ref(x, k))
    assert abs(float(t) - exact) < 0.05 * max(exact, 1e-3)


def test_topk_threshold_matches_rigl_drop():
    """The kernel's threshold reproduces the exact-rank drop decision for
    all but a ~1% boundary band (RigL is robust to that)."""
    x = jax.random.normal(jax.random.PRNGKey(7), (50_000,), jnp.float32)
    k = 10_000
    t = topk_threshold(x, k, interpret=True)
    kernel_keep = np.asarray(jnp.abs(x) >= t)
    exact_keep = np.zeros(50_000, bool)
    exact_keep[np.argsort(-np.abs(np.asarray(x)))[:k]] = True
    disagree = (kernel_keep != exact_keep).mean()
    assert disagree < 0.02


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("shape", [(2, 256, 64), (4, 128, 128)])
def test_flash_attention_vs_ref(causal, shape):
    from repro.kernels.flash_attention import flash_attention

    BH, S, d = shape
    key = jax.random.PRNGKey(11)
    q = jax.random.normal(key, shape, jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), shape, jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2), shape, jnp.float32)
    out = flash_attention(q, k, v, causal=causal, bq=128, bk=128, interpret=True)
    expect = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=2e-5)


def test_flash_attention_bf16():
    from repro.kernels.flash_attention import flash_attention

    key = jax.random.PRNGKey(12)
    shape = (2, 256, 64)
    q = jax.random.normal(key, shape).astype(jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(key, 1), shape).astype(jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(key, 2), shape).astype(jnp.bfloat16)
    out = flash_attention(q, k, v, interpret=True)
    expect = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32), atol=3e-2
    )
