"""Read a cell's compared numbers over many seeds in one process.

    python chipbench/readings.py --workload <cell> --seconds <s> \
        --seeds <n> [<n> ...] [--rates <r> ...] \
        [--control fp8 --control-seeds <n> ...]

Each seed is one run of the cell's own driver, as ``run.py`` makes it, with
a short window; programs compiled for the first seed serve the rest, so a
dozen seeds cost one set-up's compilation.  ``--control`` runs the
lower-precision control (and the planted faults) on ``--control-seeds``.
``--rates``, one to a seed, runs a serving cell at other request rates than
its own: a sweep for the knee.
Prints one JSON line per run, ``{"seed", "control", "correct", "checks"}``,
after the driver's own lines.  Not part of the benchmark's runs: the limits
in a cell's ``check`` are set from what it prints (PERF.md).
"""
from __future__ import annotations

import argparse
import copy
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parent / "src")]


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", type=int, nargs="*", default=[])
    p.add_argument("--rates", type=float, nargs="*", default=[])
    p.add_argument("--control", default=None, choices=("fp8",))
    p.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = p.parse_args()

    from chipbench import harness

    spec = harness.cell(args.workload)
    devices = harness.device_gate(spec["chips"])
    harness.enable_compile_cache()
    clock = harness.CompileClock()
    driver = harness.module("", spec["driver"])
    if args.rates and len(args.rates) != len(args.seeds):
        p.error("--rates needs one rate to each of --seeds")
    rates = args.rates or [None] * len(args.seeds)
    runs = [(s, None, r) for s, r in zip(args.seeds, rates)]
    runs += [(s, args.control, None) for s in args.control_seeds]
    for seed, control, rate in runs:
        cell = copy.deepcopy(spec)
        if rate is not None:
            cell["mix"]["rate"] = rate
        res = driver.run(cell, seed, args.seconds, False, devices=devices,
                         clock_compiles=clock, control=control)
        print(json.dumps({"seed": seed, "control": control,
                          "rate": cell["mix"].get("rate"),
                          "correct": res["correct"],
                          "attempted": res["attempted"],
                          "failed": res["failed"],
                          "metrics": res["metrics"],
                          "checks": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
