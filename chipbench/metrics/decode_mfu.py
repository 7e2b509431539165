"""Share of the chip's bf16 peak that decode's required work reaches: the
FLOPs the traced decode steps require (chipbench/work.py::decode_step) over
the device time of the engine's decode program (%)."""
from chipbench import work

PROGRAM = r"_decode"


def read(ctx):
    runs = ctx["trace"].module_runs(PROGRAM)
    steps = [s for s in ctx["steps"] if s[2]]
    if not runs or not steps:
        return None
    flops = sum(work.decode_step(ctx["conf"], s[2])[0] for s in steps)
    device_s = sum(d for _, _, d in runs) * 1e-9
    return 100.0 * flops / (device_s * ctx["peaks"]["bf16_flops"])
