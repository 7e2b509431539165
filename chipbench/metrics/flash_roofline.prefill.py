"""Roofline share of the flash attention kernel in prefill: the least time
the live 128 x 128 score blocks of each traced prefill's causal
sliding-window mask take at the chip's peaks, at the true prompt length
(chipbench/work.py::flash_prefill), over the summed device time of the
kernel's events inside the prefill programs (%)."""
from chipbench import work

PROGRAM = r"_prefill"
KERNEL = r"flash"


def read(ctx):
    tr = ctx["trace"]
    kernel_s = tr.kernel_s(KERNEL, tr.module_runs(PROGRAM))
    lens = [n for s in ctx["steps"] for n in s[3]]
    if not kernel_s or not lens:
        return None
    least = sum(work.least_time(*work.flash_prefill(ctx["conf"], n),
                                ctx["peaks"])[0] for n in lens)
    return 100.0 * least / kernel_s
