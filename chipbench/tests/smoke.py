"""Smoke-size cells for the CPU tests: the harness's own drivers, cut to
sizes interpret-mode kernels can run, with the device gate skipped."""
import copy

from chipbench import harness

SMOKE_MODEL = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
               "head_dim": 16, "d_ff": 128, "vocab_size": 1024, "window": 64,
               "embed_scale": 8.0}
SMOKE_PROGRAM = {"q_chunk": 64, "remat": False}
# The training cell's limits are set from readings at its own size; a
# 2-layer, 64-wide model rounds more per number it sums, so the smoke run has
# its own, set the same way from CPU readings at this size (PERF.md):
# sound runs read loss 4.6e-4, grad 2.9e-3, change 3.5e-3; the fp8 control
# 2.6e-3, 4.6e-2, 2.0e-2.  Topology, on three seeds: sound runs and the
# control read update_size and grow_order 0; an update that leaves the
# masks reads update_size 0.7-1.0, a random grow grow_order 1.0.
SMOKE_TRAIN_LIMITS = {"loss": 1.5e-3, "grad": 1e-2, "change": 1e-2,
                      "topology_counts": 0, "update_size": 0.1,
                      "grow_order": 0.05}


# Cells whose files are kept under chipbench/ for a later benchmark PR but
# are not in BENCHMARK.json yet (PERF.md, Open questions): their entries.
LATER = {"danube-serve.chat": {"config": "danube-1.8b-serve",
                               "traffic": "chat", "chips": 1}}


def spec(cell: str, **traffic) -> dict:
    bench = harness.benchmark()
    if cell in LATER:
        bench = dict(bench, workloads=[dict(name=cell, **LATER[cell])])
    s = harness.cell(cell, bench)
    conf = copy.deepcopy(s["conf"])
    conf["model"].update(SMOKE_MODEL)
    conf["program"] = dict(conf.get("program", {}), **SMOKE_PROGRAM)
    conf["sparse"]["block"] = 16
    if "serve" in conf:
        conf["serve"] = {"capacity": 4, "max_len": 128}
    if "train" in conf:
        conf["program"]["microbatches"] = 2
    s = copy.deepcopy(s)
    s["conf"] = conf
    if s["driver"] == "serve":
        s["mix"].update(
            rate=6.0,
            prompt={"dist": "lognormal", "median": 24, "sigma": 0.6,
                    "min": 8, "max": 60},
            output={"dist": "lognormal", "median": 12, "sigma": 0.5,
                    "min": 4, "max": 32})
        s["check"].update(served_tokens=100, max_requests=8)
        s["drain_s"] = 30
        s["trace"] = {"start_s": 0.5, "seconds": 1.0}
    else:
        s["mix"].update(batch=4, seq=32)
        s["check"] = {"limits": dict(SMOKE_TRAIN_LIMITS)}
        s["trace"] = {"start_s": 0.0, "seconds": 1.0}
    s["mix"].update(traffic)
    return s


class NoCompileClock:
    def take(self):
        return {}
