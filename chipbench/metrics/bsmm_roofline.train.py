"""Roofline share of the block-sparse matmul kernels in training: the least
time of the traced train steps' forward, input-gradient and weight-gradient
products on the active blocks at the chip's peaks
(chipbench/work.py::bsmm_train; compute bound at 2048-row microbatches)
over the summed device time of the kernels' events inside the train step
program (%)."""
from chipbench import work

PROGRAM = r"train_step"
KERNEL = r"block_sparse_matmul"


def read(ctx):
    tr = ctx["trace"]
    runs = tr.module_runs(PROGRAM)
    kernel_s = tr.kernel_s(KERNEL, runs)
    if not runs or not kernel_s:
        return None
    per_step = work.least_time(
        *work.bsmm_train(ctx["conf"], ctx["window"]["tokens_per_step"]),
        ctx["peaks"])[0]
    return 100.0 * per_step * len(runs) / kernel_s
