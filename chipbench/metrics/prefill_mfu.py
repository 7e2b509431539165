"""Share of the chip's bf16 peak that prefill's required work reaches: the
FLOPs of the traced prefills at their true prompt lengths
(chipbench/work.py::prefill) over the device time of the engine's prefill
programs (%)."""
from chipbench import work

PROGRAM = r"_prefill"


def read(ctx):
    runs = ctx["trace"].module_runs(PROGRAM)
    lens = [n for s in ctx["steps"] for n in s[3]]
    if not runs or not lens:
        return None
    flops = sum(work.prefill(ctx["conf"], n)[0] for n in lens)
    device_s = sum(d for _, _, d in runs) * 1e-9
    return 100.0 * flops / (device_s * ctx["peaks"]["bf16_flops"])
