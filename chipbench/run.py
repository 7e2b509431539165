"""Run one benchmark cell on the chip and print its result.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with a TPU.  The cell's
entry in BENCHMARK.json and its files under chipbench/ say what runs; its
inputs and weights come from ``--seed``.  The last line of standard output
is the result as JSON; earlier lines report set-up, the window and the
check.  Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.

``--control fp8`` (not used by the benchmark's own runs) puts the reference
in fp8, the control the comparison must reject, in the program's place for
the check: its readings go into ``checks`` and the run reads not correct.
For training it also logs the readings of faults planted in the
reference.
"""
from __future__ import annotations

import argparse
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parent / "src")]


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--control", default=None, choices=("fp8",))
    args = p.parse_args()
    if args.seed < 0:
        p.error("--seed must be >= 0")

    from chipbench import harness

    spec = harness.cell(args.workload)
    devices = harness.device_gate(spec["chips"])
    import repro  # noqa: F401  (the program under test must be present)

    harness.log(compile_cache=harness.enable_compile_cache(),
                workload=args.workload, seed=args.seed, seconds=args.seconds,
                trace=args.trace)
    clock = harness.CompileClock()
    driver = harness.module("", spec["driver"])
    result = driver.run(spec, args.seed, args.seconds, bool(args.trace),
                        devices=devices, clock_compiles=clock,
                        control=args.control)
    return harness.finish(result)


if __name__ == "__main__":
    sys.exit(main())
