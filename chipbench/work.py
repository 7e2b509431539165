"""The work a step requires, counted from shapes and the masks' active
blocks -- never from a kernel's grid or the program's compiled code, so it
reads the same whatever implements it.

Shapes, active blocks, rows and windows come from the configuration's own
reference module (``harness.reference(conf)``), by the contract in
``chipbench/harness.py``: ``layer_shapes`` and ``erk_blocks`` always,
``rows_per_token`` and ``attn_windows`` where a matrix's products do not
see every token or a layer's window is not the configuration's.

FLOPs count a multiply-add as two.  Bytes are the least a step must move
through HBM: each weight it reads once, in bfloat16 (the compute type the
configurations state), and its activations, caches and results once.
"""
from __future__ import annotations

import math

from chipbench import harness

BF16 = 2
F32 = 4


def active_blocks(conf: dict) -> dict:
    """Active blocks per sparse matrix of one layer, summed over a bank.
    The benchmark's masks hold exactly these counts, and a topology update
    keeps them."""
    sp = conf["sparse"]
    return harness.reference(conf).erk_blocks(
        conf["model"], sp["sparsity"], sp["block"])


def _matrices(conf: dict) -> dict:
    """Each sparse matrix of one layer -> (groups, k, n, active blocks, rows
    per token): a bank of ``groups`` (k, n) matrices whose product with
    each group sees ``rows per token`` rows for every token (1 unless the
    reference says otherwise)."""
    ref = harness.reference(conf)
    m = conf["model"]
    counts = active_blocks(conf)
    rows = ref.rows_per_token(m) if hasattr(ref, "rows_per_token") else {}
    out = {}
    for key, shape in ref.layer_shapes(m).items():
        *lead, k_, n_ = shape
        out[key] = (math.prod(lead), k_, n_, counts[key], rows.get(key, 1))
    return out


def attn_windows(conf: dict) -> list:
    """The window of each attention layer (0: the whole causal context)."""
    ref = harness.reference(conf)
    m = conf["model"]
    if hasattr(ref, "attn_windows"):
        return list(ref.attn_windows(m))
    return [m["window"]] * m["n_layers"]


def sparse_weights(conf: dict) -> int:
    """Active (kept) weights of the sparse matrices of all layers."""
    b = conf["sparse"]["block"]
    return conf["model"]["n_layers"] * sum(active_blocks(conf).values()) * b * b


def token_weights(conf: dict) -> float:
    """Active weights a token's rows pass through, all layers: each
    matrix's active weights times its rows per token."""
    b = conf["sparse"]["block"]
    per_layer = sum(blocks * rows
                    for _, _, _, blocks, rows in _matrices(conf).values())
    return conf["model"]["n_layers"] * per_layer * b * b


def head_weights(conf: dict) -> int:
    m = conf["model"]
    return m["d_model"] * m["vocab_size"]


def attn_flops(conf: dict, keys: int) -> int:
    """Scores and weighted values of ``keys`` query-key pairs, summed over
    the attention layers, all heads."""
    m = conf["model"]
    return 4 * m["n_heads"] * m["head_dim"] * keys


def causal_keys(conf: dict, n: int) -> int:
    """Query-key pairs of ``n`` tokens, summed over the attention layers,
    each under its own window."""
    return sum(live_keys(n, w) for w in attn_windows(conf))


def live_keys(n: int, window: int) -> int:
    """Query-key pairs a causal sliding-window mask keeps for ``n`` tokens."""
    w = window or n
    if n <= w:
        return n * (n + 1) // 2
    return w * (w + 1) // 2 + (n - w) * w


def _kv_bytes(conf: dict) -> int:
    """Bytes of one cached position's keys and values in one layer."""
    m = conf["model"]
    return 2 * m["n_kv_heads"] * m["head_dim"] * BF16


# -- serving ---------------------------------------------------------------

def decode_step(conf: dict, decodes) -> tuple:
    """(flops, bytes) of one decode step; ``decodes`` lists (prompt_len,
    tokens_before) of each active request.  Its query attends every cached
    position of the request and itself that each layer's window keeps."""
    rows = len(decodes)
    T, W, Hd = token_weights(conf), sparse_weights(conf), head_weights(conf)
    windows = attn_windows(conf)
    keys = sum(min(p + g, w or p + g) for p, g in decodes for w in windows)
    flops = 2 * rows * (T + Hd) + attn_flops(conf, keys)
    bytes_ = BF16 * (W + Hd) + _kv_bytes(conf) * keys
    return flops, bytes_


def prefill(conf: dict, n: int) -> tuple:
    """(flops, bytes) of one prefill of ``n`` prompt tokens: every layer
    over every token, logits for the last position only."""
    T, W, Hd = token_weights(conf), sparse_weights(conf), head_weights(conf)
    flops = 2 * n * T + 2 * Hd + attn_flops(conf, causal_keys(conf, n))
    bytes_ = BF16 * (W + Hd) + _kv_bytes(conf) * len(attn_windows(conf)) * n
    return flops, bytes_


def bsmm_decode(conf: dict, rows: int) -> tuple:
    """(flops, bytes) of the block-sparse products of one decode step over
    ``rows`` active requests: the active blocks read once in bfloat16."""
    act = 0
    for groups, k_, n_, _, per_token in _matrices(conf).values():
        act += rows * per_token * groups * (k_ + n_) * BF16
    W = sparse_weights(conf)
    return (2 * rows * token_weights(conf),
            BF16 * W + conf["model"]["n_layers"] * act)


def _live_blocks(n: int, window: int, bq: int = 128) -> int:
    """Live ``bq`` x ``bq`` score blocks of ``n`` tokens under a causal
    sliding-window mask (``window`` 0: causal only)."""
    live = 0
    for qb in range(-(-n // bq)):
        q_lo, q_hi = qb * bq, min(n, (qb + 1) * bq) - 1
        for kb in range(qb + 1):
            k_lo, k_hi = kb * bq, min(n, (kb + 1) * bq) - 1
            if k_lo > q_hi:
                continue
            if window and k_hi <= q_lo - window:
                continue
            live += 1
    return live


def flash_prefill(conf: dict, n: int) -> tuple:
    """(flops, bytes) of the attention of one prefill of ``n`` tokens over
    the live 128 x 128 score blocks of each layer's causal sliding-window
    mask: queries, keys, values and outputs moved once."""
    m = conf["model"]
    bq = 128
    windows = attn_windows(conf)
    live = sum(_live_blocks(n, w, bq) for w in windows)
    H, KV, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    flops = 4 * H * hd * live * bq * bq
    bytes_ = len(windows) * (2 * H + 2 * KV) * n * hd * BF16
    return flops, bytes_


# -- training --------------------------------------------------------------

def train_flops_per_token(conf: dict, seq: int) -> float:
    """Forward and backward FLOPs one token requires: 6 x the active sparse
    weights its rows pass through, 6 x the dense head, and attention over
    each layer's causal window (forward once, backward twice).
    Recomputation is not counted."""
    T, Hd = token_weights(conf), head_weights(conf)
    attn = 3 * attn_flops(conf, causal_keys(conf, seq)) / seq
    return 6 * (T + Hd) + attn


def bsmm_train(conf: dict, tokens: int) -> tuple:
    """(flops, bytes) of the block-sparse products of one training step
    over ``tokens`` tokens: forward, input gradient and weight gradient on
    the active blocks, each group of a bank over its rows.  Bytes: each
    product reads its two operands and writes its result once (weight
    gradients in float32)."""
    b = conf["sparse"]["block"]
    flops = bytes_ = 0
    for groups, k_, n_, blocks, per_token in _matrices(conf).values():
        w = blocks * b * b
        rows = tokens * per_token
        flops += 3 * 2 * rows * w
        # every group reads and writes its own rows' activations
        rows *= groups
        fwd = rows * k_ * BF16 + w * BF16 + rows * n_ * BF16
        dgrad = rows * n_ * BF16 + w * BF16 + rows * k_ * BF16
        wgrad = rows * (k_ + n_) * BF16 + w * F32
        bytes_ += fwd + dgrad + wgrad
    L = conf["model"]["n_layers"]
    return L * flops, L * bytes_


def least_time(flops: float, bytes_: float, peaks: dict) -> tuple:
    """(seconds, bound): the larger of compute and memory time at peak."""
    tc = flops / peaks["bf16_flops"]
    tm = bytes_ / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
