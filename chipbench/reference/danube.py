"""Plain float32 reference of the danube decoder, and the seeded weights.

Everything here is ``jax.numpy``; nothing is imported from the program.  The
benchmark makes one set of weights from ``--seed`` (``make_weights``) in the
parameter layout the program takes, hands them to the program for the timed
run, and makes them again from the same seed for this reference once the
window has closed.

The forward pass follows the published h2o-danube-1.8b (arXiv:2401.16818, a
Mistral-style decoder: RMSNorm, rotary positions, grouped-query attention
with a 4096-token sliding window, SwiGLU), with the departures this repo's
model makes and the configuration file lists under ``reduced``:

- token embeddings are multiplied by ``embed_scale`` (sqrt(d_model));
- the vocabulary is padded to a multiple of 256; pad logits never win.

Masks are applied as ``w * m``.  ``precision`` selects how every matrix
product is computed:

- ``"f32"``: float32 at ``jax.default_matmul_precision("highest")``;
- ``"bf16"``: inputs rounded to bfloat16, float32 accumulation;
- ``"fp8"``: inputs scaled per tensor into float8_e4m3fn and rounded, float32
  accumulation -- the control that the comparison has to reject.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG = -1e30
FP8_MAX = 448.0  # largest finite float8_e4m3fn


def padded_vocab(vocab: int) -> int:
    return -(-vocab // 256) * 256


# --------------------------------------------------------------------------
# weights and masks
# --------------------------------------------------------------------------

SPARSE = (("attn", "wq"), ("attn", "wk"), ("attn", "wv"), ("attn", "wo"),
          ("mlp", "wi"), ("mlp", "wg"), ("mlp", "wo"))


def layer_shapes(m: dict) -> dict:
    d, H, KV, hd, ff = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                        m["head_dim"], m["d_ff"])
    return {
        ("attn", "wq"): (d, H * hd), ("attn", "wk"): (d, KV * hd),
        ("attn", "wv"): (d, KV * hd), ("attn", "wo"): (H * hd, d),
        ("mlp", "wi"): (d, ff), ("mlp", "wg"): (d, ff), ("mlp", "wo"): (ff, d),
    }


def erk_blocks(m: dict, sparsity: float, block: int) -> dict:
    """Active blocks per sparse matrix: Erdos-Renyi-Kernel densities
    (density proportional to (n_in + n_out) / (n_in * n_out), scaled so the
    sparse matrices keep ``1 - sparsity`` of their weights; a density that
    would pass 1 is held at 1 and the rest rescaled), rounded to whole
    ``block`` x ``block`` blocks.  The same for every layer."""
    shapes = layer_shapes(m)
    size = {k: a * b for k, (a, b) in shapes.items()}
    raw = {k: (a + b) / (a * b) for k, (a, b) in shapes.items()}
    dense: set = set()
    while True:
        free = [k for k in shapes if k not in dense]
        if not free:
            break
        budget = (1 - sparsity) * sum(size.values())
        budget -= sum(size[k] for k in dense)
        eps = budget / sum(raw[k] * size[k] for k in free)
        over = {k for k in free if eps * raw[k] >= 1.0}
        if not over:
            break
        dense |= over
    out = {}
    for k, (a, b) in shapes.items():
        n = (a // block) * (b // block)
        dens = 1.0 if k in dense else eps * raw[k]
        out[k] = max(1, min(n, int(round(dens * n))))
    return out


def make_weights(key, mask_key, m: dict, sparsity: float, block: int):
    """(params, masks) in the program's layout, on the device: weights from
    ``key``, the block topology from ``mask_key``.

    Call under ``jax.jit`` (with ``m`` and the rest static) so they are
    made in one program.  Scales as the program's own initialiser draws
    them: embeddings N(0, 0.02), projections N(0, 1/fan_in), feed-forward
    and head truncated normals of std 1/sqrt(fan_in); norm scales 1.
    Weights off the mask are zero."""
    d, V = m["d_model"], padded_vocab(m["vocab_size"])
    counts = erk_blocks(m, sparsity, block)
    shapes = layer_shapes(m)
    keys = jax.random.split(key, m["n_layers"] + 2)
    mask_keys = jax.random.split(mask_key, m["n_layers"])

    def block_mask(k, shape, n_on):
        nk, nn = shape[0] // block, shape[1] // block
        order = jnp.argsort(jax.random.uniform(k, (nk * nn,)))
        blk = jnp.zeros((nk * nn,), bool).at[order[:n_on]].set(True)
        blk = blk.reshape(nk, nn)
        return jnp.repeat(jnp.repeat(blk, block, 0), block, 1)

    def weight(k, shape, trunc):
        std = 1.0 / math.sqrt(shape[0])
        if trunc:
            return std * jax.random.truncated_normal(k, -2.0, 2.0, shape)
        return std * jax.random.normal(k, shape)

    layers, layer_masks = [], []
    for i in range(m["n_layers"]):
        ks = jax.random.split(keys[i], len(SPARSE))
        mks = jax.random.split(mask_keys[i], len(SPARSE))
        p = {"ln1": {"scale": jnp.ones((d,))}, "ln2": {"scale": jnp.ones((d,))},
             "attn": {}, "mlp": {}}
        mk = {"ln1": {"scale": None}, "ln2": {"scale": None},
              "attn": {}, "mlp": {}}
        for j, name in enumerate(SPARSE):
            shape = shapes[name]
            mask = block_mask(mks[j], shape, counts[name])
            w = weight(ks[j], shape, trunc=name[0] == "mlp")
            p[name[0]][name[1]] = {"w": w * mask}
            mk[name[0]][name[1]] = {"w": mask}
        layers.append(p)
        layer_masks.append(mk)
    params = {
        "embed": {"table": 0.02 * jax.random.normal(keys[-1], (V, d))},
        "layers": layers,
        "ln_f": {"scale": jnp.ones((d,))},
        "head": {"w": weight(keys[-2], (d, V), trunc=True)},
    }
    masks = {"embed": {"table": None}, "layers": layer_masks,
             "ln_f": {"scale": None}, "head": {"w": None}}
    return params, masks


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _round(x, precision):
    """``x`` rounded to ``precision`` and back to float32.  The gradient
    passes straight through, so a lower-precision forward pass trains."""
    if precision == "f32":
        return x
    return x + jax.lax.stop_gradient(_rounded(x, precision) - x)


def _rounded(x, precision):
    if precision == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "fp8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
        return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    raise ValueError(f"unknown precision {precision!r}")


def _mm(x, w, precision):
    return jnp.matmul(_round(x, precision), _round(w, precision),
                      precision=jax.lax.Precision.HIGHEST)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, m, q_block, precision):
    """q (S, H, hd); k, v (S, KV, hd): causal, sliding window, in blocks
    of ``q_block`` queries so the scores never exceed (H, q_block, S)."""
    S, H, hd = q.shape
    KV = k.shape[1]
    G = H // KV
    k = jnp.repeat(k, G, axis=1)
    v = jnp.repeat(v, G, axis=1)
    window = m["window"]
    outs = []
    kpos = jnp.arange(S)
    for q0 in range(0, S, q_block):
        qb = q[q0:q0 + q_block]
        qpos = q0 + jnp.arange(qb.shape[0])
        s = jnp.einsum("qhd,khd->hqk", _round(qb, precision),
                       _round(k, precision),
                       precision=jax.lax.Precision.HIGHEST) / math.sqrt(hd)
        live = kpos[None, :] <= qpos[:, None]
        if window:
            live &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(live[None], s, NEG)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", _round(p, precision),
                               _round(v, precision),
                               precision=jax.lax.Precision.HIGHEST))
    return jnp.concatenate(outs, 0)


def _layer(x, p, mk, m, pos, precision, q_block):
    eps, H, KV, hd = m["norm_eps"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    S = x.shape[0]
    w = lambda grp, name: p[grp][name]["w"] * mk[grp][name]["w"]
    h = _rmsnorm(x, p["ln1"]["scale"], eps)
    q = _mm(h, w("attn", "wq"), precision).reshape(S, H, hd)
    k = _mm(h, w("attn", "wk"), precision).reshape(S, KV, hd)
    v = _mm(h, w("attn", "wv"), precision).reshape(S, KV, hd)
    q, k = _rope(q, pos, m["rope_theta"]), _rope(k, pos, m["rope_theta"])
    o = _attention(q, k, v, m, q_block, precision).reshape(S, H * hd)
    x = x + _mm(o, w("attn", "wo"), precision)
    h = _rmsnorm(x, p["ln2"]["scale"], eps)
    g = jax.nn.silu(_mm(h, w("mlp", "wg"), precision))
    x = x + _mm(g * _mm(h, w("mlp", "wi"), precision), w("mlp", "wo"),
                precision)
    return x


def hidden(params, masks, m, tokens, precision="f32", q_block=512):
    """Final-norm hidden states (S, d) of one sequence of token ids."""
    S = tokens.shape[0]
    pos = jnp.arange(S)
    x = params["embed"]["table"][tokens] * m["embed_scale"]
    with jax.default_matmul_precision("highest"):
        for p, mk in zip(params["layers"], masks["layers"]):
            x = _layer(x, p, mk, m, pos, precision, q_block)
        return _rmsnorm(x, params["ln_f"]["scale"], m["norm_eps"])


def logits(params, m, h, precision="f32"):
    """(n, d) hidden states -> (n, vocab_size) float32 logits."""
    with jax.default_matmul_precision("highest"):
        out = _mm(h, params["head"]["w"], precision)
    return out[:, : m["vocab_size"]]


def loss(params, masks, m, tokens, targets, precision="f32", q_block=512):
    """Mean next-token cross-entropy over a (B, S) batch, one row at a
    time under rematerialisation so a row's activations are the largest
    live set."""
    total = jnp.float32(0.0)
    row = jax.checkpoint(
        lambda p, t, y: _row_loss(p, masks, m, t, y, precision, q_block))
    for b in range(tokens.shape[0]):
        total = total + row(params, tokens[b], targets[b])
    return total / tokens.size


def _row_loss(params, masks, m, tokens, targets, precision, q_block):
    lg = logits(params, m, hidden(params, masks, m, tokens, precision,
                                  q_block), precision)
    lse = jax.nn.logsumexp(lg, -1)
    picked = jnp.take_along_axis(lg, targets[:, None], -1)[:, 0]
    return jnp.sum(lse - picked)
