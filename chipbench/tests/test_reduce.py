"""The trace reduction and the work counts, against hand counts."""
import pathlib
import types

import pytest

from chipbench import harness, reduce, work

DATA = pathlib.Path(__file__).parent / "data"


def _toy():
    # window [0, 100) ns; two programs; host spans around steps
    return reduce.Reduced({
        "ops": [["fusion.1", 10, 10], ["bsmm_fwd.3", 15, 10],
                ["flash_fwd.2", 40, 20], ["fusion.7", 90, 20]],
        "modules": [["jit__decode", 5, 30], ["jit__prefill", 38, 30]],
        "spans": [["chipbench.window", 0, 100], ["chipbench.step", 2, 40],
                  ["chipbench.step", 45, 40]],
        "window": [0, 100],
    })


def test_union_of_overlapping_intervals():
    assert reduce.union([(0, 10), (5, 20), (30, 40)], 0, 100) == 30
    assert reduce.union([(0, 10), (5, 20), (30, 40)], 8, 35) == 17
    assert reduce.union([], 0, 10) == 0


def test_idle_share_is_one_minus_busy_union():
    t = _toy()
    # busy: [10, 25) + [40, 60) + [90, 100) clipped = 15 + 20 + 10
    assert t.busy_s() == pytest.approx(45e-9)
    assert t.window_s() == pytest.approx(100e-9)
    assert t.idle_share() == pytest.approx(0.55)


def test_kernel_time_by_pattern_inside_programs():
    t = _toy()
    decode = t.module_runs(r"_decode")
    assert [n for n, _, _ in decode] == ["jit__decode"]
    assert t.kernel_s(r"bsmm", decode) == pytest.approx(10e-9)
    assert t.kernel_s(r"flash", decode) == 0
    assert t.kernel_s(r"flash", t.module_runs(r"_prefill")) == pytest.approx(
        20e-9)


def test_host_time_less_device_busy():
    t = _toy()
    spans = t.host_spans("chipbench.step")
    # [2, 42): busy 15 + 2 ; [45, 85): busy 15
    assert [t.busy_within(s, s + d) for _, s, d in spans] == pytest.approx(
        [17e-9, 15e-9])


def test_breakdown_top_ops_and_gaps_by_host_span():
    b = _toy().breakdown()
    ops = dict(b["device_ops"])
    assert ops["fusion"] == pytest.approx(30e-9)  # fusion.1 + fusion.7
    assert ops["bsmm_fwd"] == pytest.approx(10e-9)
    gaps = b["idle_gaps"]
    # gaps: [0,10) [25,40) [60,90); the longest, [60,90), sits in the
    # second step's span
    assert gaps[0] == ["chipbench.step", pytest.approx(30e-9)]
    assert len(gaps) == 3
    assert gaps[-1][0] == "chipbench.step"  # [0,10) is inside the first


# -- work counts --------------------------------------------------------

TOY = {"reference": "danube",
       "model": {"n_layers": 1, "d_model": 256, "n_heads": 2, "n_kv_heads": 1,
                 "head_dim": 128, "d_ff": 256, "vocab_size": 512,
                 "window": 0},
       "sparse": {"sparsity": 0.0, "block": 128}}


def test_active_weights_by_hand():
    # dense: 5 square 256x256 matrices and two 256x128 ones
    assert work.sparse_weights(TOY) == 5 * 256 * 256 + 2 * 256 * 128
    half = dict(TOY, sparse={"sparsity": 0.5, "block": 128})
    blocks = work.active_blocks(half)
    assert sum(blocks.values()) * 128 * 128 == work.sparse_weights(half)
    assert 10 <= sum(blocks.values()) <= 14  # about half of 24 blocks


def test_live_keys_by_hand():
    assert work.live_keys(4, 0) == 1 + 2 + 3 + 4
    assert work.live_keys(5, 2) == 1 + 2 + 2 + 2 + 2
    assert work.live_keys(3, 8) == 6


def test_flash_live_blocks_by_hand():
    m = TOY["model"]
    # 256 tokens: blocks (0,0), (1,0), (1,1) live; the upper one dead
    flops, bytes_ = work.flash_prefill(TOY, 256)
    assert flops == 4 * m["n_heads"] * m["head_dim"] * 3 * 128 * 128
    assert bytes_ == (2 * 2 + 2 * 1) * 256 * 128 * 2
    w = dict(TOY, model=dict(m, window=128))
    # with a 128 window, block (1,0) still holds live pairs; a third row's
    # (2,0) does not
    f3 = work.flash_prefill(w, 384)[0] / (4 * 2 * 128 * 128 * 128)
    assert f3 == 5  # (0,0) (1,0) (1,1) (2,1) (2,2)


def test_decode_and_prefill_by_hand():
    W = work.sparse_weights(TOY)
    Hd = 256 * 512
    f, b = work.decode_step(TOY, [(10, 1), (20, 3)])
    keys = 11 + 23
    assert f == 2 * 2 * (W + Hd) + 4 * 2 * 128 * keys
    assert b == 2 * (W + Hd) + 2 * 1 * 128 * 2 * keys
    f, _ = work.prefill(TOY, 6)
    assert f == 2 * 6 * W + 2 * Hd + 4 * 2 * 128 * 21


def test_training_counts_active_weights_not_dense():
    sparse = dict(TOY, sparse={"sparsity": 0.8, "block": 128},
                  model=dict(TOY["model"], d_model=1280, d_ff=2560,
                             n_heads=10))
    dense = dict(sparse, sparse={"sparsity": 0.0, "block": 128})
    Hd = 1280 * 512
    attn = lambda c: work.train_flops_per_token(c, 256) - 6 * (
        work.sparse_weights(c) + Hd)
    assert attn(sparse) == attn(dense) > 0
    kept = work.sparse_weights(sparse) / work.sparse_weights(dense)
    assert kept == pytest.approx(0.2, abs=0.02)
    f, b = work.bsmm_train(sparse, 100)
    assert f == 6 * 100 * work.sparse_weights(sparse)


# Danube's counts as they were when work.py still read danube's shapes
# directly: reading them through the configuration's reference keeps them.
DANUBE = {
    "danube-1.8b-train": {
        ("sparse_weights",): 55443456,
        ("train_flops_per_token", 2048): 950071296.0,
        ("train_flops_per_token", 8192): 1201698816.0,
        ("bsmm_train", 8192): (2725156749312, 9301917696),
        ("bsmm_decode", 1): (110886912, 111247360),
        ("bsmm_decode", 16): (1774190592, 116654080),
        ("prefill", 1020): (134596771840, 285171712),
        ("prefill", 5000): (1049945702400, 325926912),
        ("flash_prefill", 1020): (24159191040, 52224000),
        ("flash_prefill", 5000): (531502202880, 256000000),
        ("decode_step", ((1020, 10), (3000, 2000))): (759414784, 327217152),
    },
    "danube-1.8b-serve": {
        ("sparse_weights",): 332660736,
        ("train_flops_per_token", 2048): 3242827776.0,
        ("train_flops_per_token", 8192): 4752592896.0,
        ("bsmm_train", 8192): (16350940495872, 55811506176),
        ("bsmm_decode", 1): (665321472, 667484160),
        ("bsmm_decode", 16): (10645143552, 699924480),
        ("prefill", 1020): (806761431040, 891830272),
        ("prefill", 5000): (6298855014400, 1136361472),
        ("flash_prefill", 1020): (144955146240, 313344000),
        ("flash_prefill", 5000): (3189013217280, 1536000000),
        ("decode_step", ((1020, 10), (3000, 2000))): (2918088704, 1144102912),
    },
}


@pytest.mark.parametrize("config,call,want", [
    (c, call, want) for c, calls in DANUBE.items()
    for call, want in calls.items()])
def test_danube_counts_are_pinned(config, call, want):
    conf = harness.load_json(harness.HERE / "configs" / f"{config}.json")
    name, *args = call
    assert getattr(work, name)(conf, *args) == want


# One attention matrix and a bank of 4 experts, 2 of them routed per token;
# one layer attends over the whole causal context, the other over 128 keys.
STUB = types.SimpleNamespace(
    layer_shapes=lambda m: {"attn": (256, 256), "experts": (4, 256, 512)},
    erk_blocks=lambda m, sparsity, block: {"attn": 2, "experts": 6},
    rows_per_token=lambda m: {"experts": 0.5},
    attn_windows=lambda m: [0, 128],
)
STUB_CONF = {"reference": "stub", "sparse": {"sparsity": 0.5, "block": 128},
             "model": dict(TOY["model"], n_layers=2)}


def test_grouped_counts_by_hand(monkeypatch):
    monkeypatch.setattr(harness, "reference", lambda conf: STUB)
    c, B = STUB_CONF, 128 * 128
    attn_w, bank_w = 2 * B, 6 * B  # active weights of one layer
    assert work.sparse_weights(c) == 2 * (attn_w + bank_w)
    # a token passes the attention matrix once and half of the bank's
    # weights (2 of 4 experts)
    T = 2 * (attn_w + bank_w / 2)
    Hd = 256 * 512
    assert work.token_weights(c) == T
    # attention: causal over 256 keys, and 128 keys past the window
    keys = 256 * 257 // 2 + (128 * 129 // 2 + 128 * 128)
    assert work.train_flops_per_token(c, 256) == 6 * (T + Hd) + (
        3 * 4 * 2 * 128 * keys / 256)
    # 100 tokens: the attention matrix's products over 100 rows, each
    # expert's over 50, 200 rows in all through the bank
    f, b = work.bsmm_train(c, 100)
    assert f == 2 * 3 * 2 * (100 * attn_w + 50 * bank_w)
    attn_b = 2 * (100 * 256 * 2 + attn_w * 2 + 100 * 256 * 2) + (
        100 * 512 * 2 + attn_w * 4)
    bank_b = 2 * (200 * 256 * 2 + bank_w * 2 + 200 * 512 * 2) + (
        200 * 768 * 2 + bank_w * 4)
    assert b == 2 * (attn_b + bank_b)
    f, b = work.bsmm_decode(c, 4)
    assert f == 2 * 4 * T
    assert b == 2 * 2 * (attn_w + bank_w) + 2 * (
        4 * 512 * 2 + 4 * 0.5 * 4 * 768 * 2)
    # decode at 200 cached keys: 200 in one layer, 128 in the other
    f, b = work.decode_step(c, [(200, 0)])
    assert f == 2 * (T + Hd) + 4 * 2 * 128 * (200 + 128)
    assert b == 2 * (2 * (attn_w + bank_w) + Hd) + 2 * 128 * 2 * (200 + 128)
    f, _ = work.prefill(c, 6)
    assert f == 2 * 6 * T + 2 * Hd + 4 * 2 * 128 * (21 + 21)
    # live score blocks of 256 tokens: 3 causal, and 3 under the window
    f, b = work.flash_prefill(c, 256)
    assert f == 4 * 2 * 128 * 6 * 128 * 128
    assert b == 2 * (2 * 2 + 2 * 1) * 256 * 128 * 2


def test_least_time_names_its_bound():
    peaks = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_time(1000, 10, peaks) == (10.0, "compute")
    assert work.least_time(10, 1000, peaks) == (100.0, "memory")


# -- a recorded trace ---------------------------------------------------
# Two engine steps of danube-serve.chat on one TPU v5e, as reduce_profile
# read them (ops, modules, harness spans; times shifted to start at 0): a
# decode-only step, then a step that prefills one prompt and decodes.


@pytest.fixture(scope="module")
def recorded():
    return reduce.load(DATA / "serve_trace.json")


def test_recorded_programs_and_kernels(recorded):
    decode = recorded.module_runs(r"_decode")
    prefill = recorded.module_runs(r"_prefill")
    assert len(decode) == 2 and len(prefill) == 1
    # 24 layers x 7 sparse projections, each one block-sparse kernel call
    for run in decode + prefill:
        ops = recorded.ops_within([run])
        assert sum("block_sparse_matmul" in n for n, _, _ in ops) == 168
    flash = [n for n, _, _ in recorded.ops_within(prefill) if "flash" in n]
    assert len(flash) == 24  # one flash_tight call per layer
    assert recorded.kernel_s(r"block_sparse_matmul", decode) > 0


def test_recorded_idle_share_by_a_timeline(recorded):
    """Busy time against a 100 ns timeline painted op by op."""
    lo, hi = recorded.window
    res = 100
    painted = bytearray((hi - lo) // res + 1)
    for _, s, d in recorded.ops:
        a, b = max(s, lo), min(s + d, hi)
        for t in range((a - lo) // res, (b - lo) // res):
            painted[t] = 1
    busy = sum(painted) * res * 1e-9
    assert recorded.busy_s() == pytest.approx(busy, rel=2e-3)
    assert 0.0 < recorded.idle_share() < 0.5


def test_recorded_host_spans_and_breakdown(recorded):
    spans = recorded.host_spans("chipbench.step")
    assert len(spans) == 2
    for _, s, d in spans:
        assert 0 < recorded.busy_within(s, s + d) < d * 1e-9
    b = recorded.breakdown()
    fams = [k for k, _ in b["device_ops"]]
    assert "block_sparse_matmul" in fams and "_flash_jit" in fams
    assert len(b["idle_gaps"]) == 10
    assert all(label.startswith("chipbench.") for label, _ in b["idle_gaps"])
