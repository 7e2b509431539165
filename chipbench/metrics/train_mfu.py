"""Model FLOP/s utilisation of training: the FLOPs a token requires (6 x
the active sparse weights, 6 x the dense head, and attention; no
recomputation -- chipbench/work.py::train_flops_per_token) times the traced
run's tokens per second, over the chip's bf16 peak (%)."""
from chipbench import work


def read(ctx):
    w = ctx["window"]
    per_token = work.train_flops_per_token(ctx["conf"], w["seq"])
    return 100.0 * per_token * w["tokens_per_s"] / ctx["peaks"]["bf16_flops"]
