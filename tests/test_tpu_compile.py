"""Compile the main-path Pallas kernels for a described TPU v5e.

Every other kernel test runs in interpret mode on the CPU, which applies none
of the chip's rules: block shapes aligned to the (8, 128) tiling, the VMEM
budget.  These tests hand the kernels (``interpret=False``) to the TPU
compiler at h2o-danube-1.8b widths, for a ``v5e:2x2`` topology described by
``jax.experimental.topologies`` — no chip is attached and nothing runs.  Each
asserts that the executable holds the Mosaic kernel (``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and under pytest-xdist
only the worker given this file reaches the fixture.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.pack import pack_entry
from repro.kernels import (
    block_sparse_linear,
    fused_masked_linear,
    masked_linear,
)
from repro.kernels.flash_attention import flash_attention, flash_attention_paged
from repro.obs import REGISTRY

pytestmark = pytest.mark.kernels

# h2o-danube-1.8b: d_model 2560, d_ff 6912, 32 query heads over 8 KV heads of
# 80 dims, sliding window 4096; training rows of one 4096-token sequence
D, F, H, KV, HD, WINDOW, ROWS = 2560, 6912, 32, 8, 80, 4096, 4096
BLOCK = 128


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
        yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _abstract(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: _spec(np.shape(a), jnp.asarray(a).dtype, sharding), tree
    )


def _compiled_text(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _mask(density=0.2, seed=0):
    """Random 128-block topology over (D, F), as an element mask."""
    rng = np.random.default_rng(seed)
    blocks = rng.random((D // BLOCK, F // BLOCK)) < density
    return np.kron(blocks, np.ones((BLOCK, BLOCK), bool))


def _pack(**kw):
    """PackState entry of ``_mask()``."""
    return pack_entry(_mask(), (BLOCK, BLOCK), **kw)


def _row_tile_gauge(rows):
    return REGISTRY.get("kernel_row_tile").labels(rows).value


def _fwd_and_vjp(linear_fn):
    """(out, d/dx, d/dw) of sum(linear_fn(x, w)) — forward plus the custom
    VJP's dgrad and wgrad kernels in one program."""

    def f(x, w, *rest):
        loss = lambda x, w: jnp.sum(linear_fn(x, w, *rest).astype(jnp.float32))
        return jax.value_and_grad(loss, argnums=(0, 1))(x, w)

    return f


def test_masked_linear_fwd_vjp_compiles(one_chip):
    _compiled_text(
        _fwd_and_vjp(lambda x, w, m: masked_linear(x, w, m, interpret=False)),
        _spec((ROWS, D), jnp.bfloat16, one_chip),
        _spec((D, F), jnp.bfloat16, one_chip),
        _spec((D, F), jnp.bool_, one_chip),
    )


def test_block_sparse_linear_fwd_vjp_compiles(one_chip):
    pack = _abstract(_pack(), one_chip)
    _compiled_text(
        _fwd_and_vjp(
            lambda x, w, pk: block_sparse_linear(
                x, w, block=(128, BLOCK, BLOCK), pack=pk, interpret=False
            )
        ),
        _spec((ROWS, D), jnp.bfloat16, one_chip),
        _spec((D, F), jnp.bfloat16, one_chip),
        pack,
    )


@pytest.mark.parametrize("rows,tile", [(2048, 2048), (8192, 2048)])
def test_block_sparse_worst_case_widths_fwd_vjp_compiles(one_chip, rows, tile):
    """A training microbatch (2048 rows) and RigL's update step over the
    whole batch (8192 rows), at worst-case packed widths (slack 1.0) with the
    Top-KAST superset driving wgrad: the row tile grows to the VMEM budget."""
    pack = _pack(slack=1.0, bwd_mask=_mask() | _mask(0.1, seed=1))
    assert pack["idx"].shape[1] == D // BLOCK  # worst-case widths
    assert pack["ridx"].shape[1] == F // BLOCK
    assert pack["bidx"].shape[1] == D // BLOCK
    text = _compiled_text(
        _fwd_and_vjp(
            lambda x, w, pk: block_sparse_linear(
                x, w, block=(128, BLOCK, BLOCK), pack=pk, interpret=False
            )
        ),
        _spec((rows, D), jnp.bfloat16, one_chip),
        _spec((D, F), jnp.bfloat16, one_chip),
        _abstract(pack, one_chip),
    )
    assert text.count("tpu_custom_call") >= 3  # fwd, dx, dw
    assert _row_tile_gauge(rows) == tile


def test_block_sparse_decode_rows_compile(one_chip):
    """M=8: one decode step of an 8-slot engine (rows pad to a 16-row tile)."""
    pack = _abstract(_pack(), one_chip)
    _compiled_text(
        lambda x, w, pk: block_sparse_linear(
            x, w, block=(128, BLOCK, BLOCK), pack=pk, interpret=False
        ),
        _spec((8, D), jnp.bfloat16, one_chip),
        _spec((D, F), jnp.bfloat16, one_chip),
        pack,
    )
    assert _row_tile_gauge(16) == 16


def test_flash_tight_fwd_vjp_compiles(one_chip):
    """Sliding-window GQA attention over one 4096-token sequence, the
    flash_tight schedule, forward plus the dq and dk/dv kernels."""

    def f(q, k, v):
        loss = lambda q, k, v: jnp.sum(
            flash_attention(
                q, k, v, causal=True, window=WINDOW, tight=True,
                kv_groups=H // KV, interpret=False,
            ).astype(jnp.float32)
        )
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    text = _compiled_text(
        f,
        _spec((H, ROWS, HD), jnp.bfloat16, one_chip),
        _spec((KV, ROWS, HD), jnp.bfloat16, one_chip),
        _spec((KV, ROWS, HD), jnp.bfloat16, one_chip),
    )
    assert text.count("tpu_custom_call") >= 3  # fwd, dq, dk/dv


def test_flash_attention_paged_compiles(one_chip):
    """Suffix prefill over a paged prefix: 512 suffix queries against a
    4096-slot history in 16-token pages (the engine's default page size)."""
    page, sq = 16, 512
    n_pages = WINDOW // page
    _compiled_text(
        lambda q, pk, pv, table, ctx: flash_attention_paged(
            q, pk, pv, table, ctx, interpret=False
        ),
        _spec((1, H, sq, HD), jnp.bfloat16, one_chip),
        _spec((n_pages, page, KV, HD), jnp.bfloat16, one_chip),
        _spec((n_pages, page, KV, HD), jnp.bfloat16, one_chip),
        _spec((1, n_pages), jnp.int32, one_chip),
        _spec((1,), jnp.int32, one_chip),
    )


def test_fused_epilogue_vjp_compiles(one_chip):
    """The fused SGD epilogue: the weight cotangent is the new momentum."""

    def f(x, w, m, mom, seed):
        loss = lambda x, w: jnp.sum(
            fused_masked_linear(
                x, w, m, mom, seed, mu=0.9, wd=1e-4, sr=False,
                interpret=False,
            ).astype(jnp.float32)
        )
        return jax.value_and_grad(loss, argnums=(0, 1))(x, w)

    _compiled_text(
        f,
        _spec((ROWS, D), jnp.bfloat16, one_chip),
        _spec((D, F), jnp.bfloat16, one_chip),
        _spec((D, F), jnp.bool_, one_chip),
        _spec((D, F), jnp.float32, one_chip),
        _spec((1,), jnp.int32, one_chip),
    )
