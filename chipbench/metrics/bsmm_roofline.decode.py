"""Roofline share of the block-sparse matmul kernel in decode: the least
time its required work takes at the chip's peaks (active bf16 blocks read
once; memory bound at decode's few rows -- chipbench/work.py::bsmm_decode)
over the summed device time of the kernel's events inside the decode
program (%)."""
from chipbench import work

PROGRAM = r"_decode"
KERNEL = r"block_sparse_matmul"


def read(ctx):
    tr = ctx["trace"]
    kernel_s = tr.kernel_s(KERNEL, tr.module_runs(PROGRAM))
    steps = [s for s in ctx["steps"] if s[2]]
    if not kernel_s or not steps:
        return None
    least = sum(work.least_time(*work.bsmm_decode(ctx["conf"], len(s[2])),
                                ctx["peaks"])[0] for s in steps)
    return 100.0 * least / kernel_s
