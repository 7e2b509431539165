"""The work a step requires, counted from shapes and the masks' active
blocks -- never from a kernel's grid or the program's compiled code, so it
reads the same whatever implements it.

FLOPs count a multiply-add as two.  Bytes are the least a step must move
through HBM: each weight it reads once, in bfloat16 (the compute type the
configurations state), and its activations, caches and results once.
"""
from __future__ import annotations

from chipbench.reference.danube import erk_blocks, layer_shapes

BF16 = 2
F32 = 4


def active_blocks(conf: dict) -> dict:
    """Active blocks per sparse matrix of one layer.  The benchmark's masks
    hold exactly these counts, and a topology update keeps them."""
    sp = conf["sparse"]
    return erk_blocks(conf["model"], sp["sparsity"], sp["block"])


def sparse_weights(conf: dict) -> int:
    """Active (kept) weights of the sparse matrices of all layers."""
    b = conf["sparse"]["block"]
    return conf["model"]["n_layers"] * sum(active_blocks(conf).values()) * b * b


def head_weights(conf: dict) -> int:
    m = conf["model"]
    return m["d_model"] * m["vocab_size"]


def attn_flops(conf: dict, keys: int) -> int:
    """Scores and weighted values of one query over ``keys`` keys, all
    layers and heads."""
    m = conf["model"]
    return 4 * m["n_layers"] * m["n_heads"] * m["head_dim"] * keys


def live_keys(n: int, window: int) -> int:
    """Query-key pairs a causal sliding-window mask keeps for ``n`` tokens."""
    w = window or n
    if n <= w:
        return n * (n + 1) // 2
    return w * (w + 1) // 2 + (n - w) * w


# -- serving ---------------------------------------------------------------

def decode_step(conf: dict, decodes) -> tuple:
    """(flops, bytes) of one decode step; ``decodes`` lists (prompt_len,
    tokens_before) of each active request.  Its query attends every cached
    position of the request and itself."""
    m = conf["model"]
    rows = len(decodes)
    W, Hd = sparse_weights(conf), head_weights(conf)
    keys = [min(p + g, m["window"] or p + g) for p, g in decodes]
    flops = 2 * rows * (W + Hd) + sum(attn_flops(conf, k) for k in keys)
    kv = 2 * m["n_layers"] * m["n_kv_heads"] * m["head_dim"] * BF16
    bytes_ = BF16 * (W + Hd) + kv * sum(keys)
    return flops, bytes_


def prefill(conf: dict, n: int) -> tuple:
    """(flops, bytes) of one prefill of ``n`` prompt tokens: every layer
    over every token, logits for the last position only."""
    m = conf["model"]
    W, Hd = sparse_weights(conf), head_weights(conf)
    flops = 2 * n * W + 2 * Hd + attn_flops(conf, live_keys(n, m["window"]))
    kv = 2 * m["n_layers"] * m["n_kv_heads"] * m["head_dim"] * BF16
    bytes_ = BF16 * (W + Hd) + kv * n
    return flops, bytes_


def bsmm_decode(conf: dict, rows: int) -> tuple:
    """(flops, bytes) of the block-sparse products of one decode step over
    ``rows`` active requests: the active blocks read once in bfloat16."""
    m = conf["model"]
    W = sparse_weights(conf)
    act = 0
    for k_, n_ in layer_shapes(m).values():
        act += rows * (k_ + n_) * BF16
    return 2 * rows * W, BF16 * W + m["n_layers"] * act


def flash_prefill(conf: dict, n: int) -> tuple:
    """(flops, bytes) of the attention of one prefill of ``n`` tokens over
    the live 128 x 128 score blocks of the causal sliding-window mask:
    queries, keys, values and outputs moved once."""
    m = conf["model"]
    bq = 128
    nb = -(-n // bq)
    w = m["window"]
    live = 0
    for qb in range(nb):
        q_lo, q_hi = qb * bq, min(n, (qb + 1) * bq) - 1
        for kb in range(qb + 1):
            k_lo, k_hi = kb * bq, min(n, (kb + 1) * bq) - 1
            if k_lo > q_hi:
                continue
            if w and k_hi <= q_lo - w:
                continue
            live += 1
    H, KV, hd, L = m["n_heads"], m["n_kv_heads"], m["head_dim"], m["n_layers"]
    flops = 4 * L * H * hd * live * bq * bq
    bytes_ = L * (2 * H + 2 * KV) * n * hd * BF16
    return flops, bytes_


# -- training --------------------------------------------------------------

def train_flops_per_token(conf: dict, seq: int) -> float:
    """Forward and backward FLOPs one token requires: 6 x the active sparse
    weights, 6 x the dense head, and attention over the causal window
    (forward once, backward twice).  Recomputation is not counted."""
    m = conf["model"]
    W, Hd = sparse_weights(conf), head_weights(conf)
    attn = 3 * attn_flops(conf, live_keys(seq, m["window"])) / seq
    return 6 * (W + Hd) + attn


def bsmm_train(conf: dict, tokens: int) -> tuple:
    """(flops, bytes) of the block-sparse products of one training step
    over ``tokens`` rows: forward, input gradient and weight gradient on
    the active blocks.  Bytes: each product reads its two operands and
    writes its result once (weight gradients in float32)."""
    m = conf["model"]
    b = conf["sparse"]["block"]
    counts = active_blocks(conf)
    flops = bytes_ = 0
    for key, (k_, n_) in layer_shapes(m).items():
        w = counts[key] * b * b
        flops += 3 * 2 * tokens * w
        fwd = tokens * k_ * BF16 + w * BF16 + tokens * n_ * BF16
        dgrad = tokens * n_ * BF16 + w * BF16 + tokens * k_ * BF16
        wgrad = tokens * (k_ + n_) * BF16 + w * F32
        bytes_ += fwd + dgrad + wgrad
    return m["n_layers"] * flops, m["n_layers"] * bytes_


def least_time(flops: float, bytes_: float, peaks: dict) -> tuple:
    """(seconds, bound): the larger of compute and memory time at peak."""
    tc = flops / peaks["bf16_flops"]
    tm = bytes_ / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
