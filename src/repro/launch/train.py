"""Fault-tolerant training driver.

  PYTHONPATH=src python -m repro.launch.train --arch h2o-danube-1.8b --smoke \
      --steps 300 --method rigl --sparsity 0.8 --workdir /tmp/run

Fault tolerance model (designed for 1000+ preemptible nodes):
  - the outer loop survives worker exceptions: on failure it restores the
    newest valid checkpoint and resumes (``--max-restarts``);
  - checkpoints are atomic + bit-packed masks + async (checkpoint/);
  - data is stateless (pure function of step) — no data-state to recover and
    any replacement host can serve any shard => stragglers can be replaced
    mid-run without a pipeline rewind;
  - ``--preempt-at`` kills the process mid-run once (integration tests assert
    bitwise-identical resume);
  - elastic restarts: restore() reshards onto whatever mesh exists now.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import time

import jax
import numpy as np

from ..configs import get_config
from ..configs.base import SparseConfig
from ..core import TopologyTrace, mask_stats, publish_pack_gauges
from ..core.pruning import PruningSchedule
from ..obs import Observability, jit_retraces, region
from ..checkpoint.checkpoint import Checkpointer
from ..data import batch_for
from ..optim import LRSchedule, OptConfig
from ..training import (
    init_train_state,
    make_algo,
    make_prune_fn,
    make_rigl_step,
    make_train_step,
    refresh_pack,
    snip_init,
)
from .compile_cache import enable_compile_cache

__all__ = ["train_loop", "main"]


class SimulatedPreemption(RuntimeError):
    pass


def train_loop(
    cfg,
    *,
    steps: int,
    batch: int,
    seq: int,
    workdir: str,
    opt_cfg: OptConfig | None = None,
    lr_sched: LRSchedule | None = None,
    ckpt_every: int = 100,
    preempt_at: int | None = None,
    learnable: bool = True,
    log_every: int = 50,
    seed: int = 0,
    obs=None,
    flusher=None,
):
    """One worker attempt. Raises on (simulated) failure; restartable.

    ``obs`` (optional repro.obs.Observability) turns on the training side
    of the observability layer (docs/observability.md): per-step train_step
    spans + a loss/gnorm counter track on the tracer, train_* gauges/
    histograms and topology-distance series in the metrics registry, and
    kernel_* pack gauges re-published after every refresh_pack.  The step
    spans and refresh_pack's phases are regions (obs/trace.py), so a
    profiler trace shows them too, with or without ``obs``.  ``flusher``
    (repro.obs.PeriodicFlusher, usually ``obs.flusher(...)`` — built by
    main() from --trace-out/--metrics-out) is pumped at log cadence and
    force-flushed before return, so a live run's files stay current.
    """
    workdir = pathlib.Path(workdir)
    opt_cfg = opt_cfg or OptConfig(kind="adam", weight_decay=0.0, grad_clip=1.0)
    lr_sched = lr_sched or LRSchedule(
        kind="warmup_cosine", base_lr=3e-3, warmup_steps=min(100, steps // 10 + 1),
        total_steps=steps,
    )
    algo = make_algo(cfg, steps)
    state, axes, flags = init_train_state(jax.random.PRNGKey(seed), cfg, opt_cfg)

    ckpt = Checkpointer(workdir / "ckpt", every=ckpt_every)
    restored, rstep = ckpt.restore_or_none(state)
    if restored is not None:
        state = restored
        # re-pack against the RESTORED masks: covers pre-PackState
        # checkpoints (restore falls back to the template pack) and any
        # width drift between the fresh-init template and the saved run
        state = refresh_pack(state, cfg)
        print(f"[train] restored checkpoint at step {rstep}")

    train_step = jax.jit(make_train_step(cfg, opt_cfg, lr_sched), donate_argnums=0)
    rigl_step = jax.jit(make_rigl_step(cfg, algo, lr_sched), donate_argnums=0)
    prune_sched = PruningSchedule(
        cfg.sparse.sparsity, begin_step=steps // 8, end_step=int(steps * 0.75),
        prune_every=max(cfg.sparse.delta_t * 10, 1),
    )
    prune_fn = jax.jit(make_prune_fn(cfg, prune_sched)) if cfg.sparse.method == "pruning" else None

    sp = cfg.sparse
    if sp.method == "snip" and int(state["step"]) == 0:
        state = snip_init(state, cfg, batch_for(cfg, 0, batch, seq, learnable=learnable))
        state = refresh_pack(state, cfg)  # snip replaced the masks

    metrics_log = []
    topo_log = []  # per-update records, kept apart from the loss log
    topo_trace = TopologyTrace()  # graph-distance telemetry (core/topology.py)
    om = None
    if obs is not None:
        m = obs.metrics
        obs.trace.thread_name(0, "train")
        om = {
            "loss": m.gauge("train_loss", "last logged training loss"),
            "lr": m.gauge("train_lr", "current learning rate"),
            "gnorm": m.gauge("train_grad_norm", "last logged gradient norm"),
            "stale": m.gauge("train_pack_stale",
                             "pack blocks differing from the masks (must be 0)"),
            "nonfinite": m.gauge("train_nonfinite_steps",
                                 "skipped non-finite optimizer updates"),
            "steps": m.counter("train_steps_total", "optimizer steps run"),
            "topo": m.counter("train_topology_updates_total",
                              "drop/grow topology updates applied"),
            "step_s": m.histogram("train_step_seconds",
                                  "host time of a step: its dispatch and "
                                  "the wait for its step counter"),
            "dist": m.gauge("train_topology_distance",
                            "last topology-update distance by metric",
                            labels=("metric",)),
            "retraces": m.gauge("train_retraces",
                                "jit retraces of the train/update steps"),
        }
        publish_pack_gauges(m, state.get("pack"))
    # the ring's time axis is the regions' default clock, so the spans that
    # refresh_pack opens nest inside the update step's
    clock = time.perf_counter
    t0 = clock()
    step = int(state["step"])
    while step < steps:
        is_update = (
            sp.method in ("rigl", "set", "snfs", "topkast")
            and step > 0
            and step % sp.delta_t == 0
            and step < algo.schedule.t_end
        )
        # the step as a region: a profiler trace and --trace-out both show
        # it, from the batch to int(state["step"]), which waits for the step
        with region("topology_update_step" if is_update else "train_step",
                    obs=obs, cat="train") as span:
            b = batch_for(cfg, step, batch, seq, learnable=learnable)
            if is_update:
                prev_masks = topo_trace.snapshot(state["masks"])
                state, m = rigl_step(state, b)
                # topology changed: re-pack the tight-grid block topology NOW
                # so the next delta_t train/serve steps run grids sized to the
                # new active counts (host-side, amortized — see core/pack.py)
                state = refresh_pack(state, cfg, obs)
                rec = topo_trace.record(prev_masks, state["masks"], step=step)
                topo_log.append({"step": step, "topology": rec})
                if om is not None:
                    om["topo"].inc()
                    for k in ("jaccard_dist", "graph_edit_dist", "nhd"):
                        om["dist"].labels(k).set(rec[k])
                    obs.trace.instant(
                        "topology_update", clock(), tid=0, cat="train",
                        args={"step": step, **{k: rec[k] for k in
                              ("dropped", "grown", "jaccard_dist", "nhd")}},
                    )
                    # the drop/grow moved blocks: re-publish the pack gauges
                    publish_pack_gauges(obs.metrics, state.get("pack"))
            else:
                state, m = train_step(state, b)
            if prune_fn is not None and step % prune_sched.prune_every == 0:
                state = prune_fn(state)
                state = refresh_pack(state, cfg, obs)  # pruning moved the masks
                if om is not None:
                    publish_pack_gauges(obs.metrics, state.get("pack"))
            step = int(state["step"])
            span.args["step"] = step
        if om is not None:
            om["step_s"].observe(span.seconds)
            om["steps"].inc()
        if preempt_at is not None and step == preempt_at:
            ckpt.maybe_save(state, step, force=True)
            ckpt.wait()
            raise SimulatedPreemption(f"preempted at step {step}")
        if step % log_every == 0 or step == steps:
            loss = float(m["loss"])
            rec = {"step": step, "loss": loss}
            if "lr" in m:  # topology-update steps report loss only
                rec["lr"] = float(m["lr"])
                rec["grad_norm"] = float(m["grad_norm"])
            # compile-counter: growth during steady state (after the first
            # log interval) is the pack-width-hysteresis regression signal
            rec["n_retraces"] = jit_retraces(train_step, rigl_step)
            if om is not None:
                tnow = clock()
                om["loss"].set(loss)
                om["retraces"].set(rec["n_retraces"])
                track = {"loss": loss}
                if "lr" in m:
                    om["lr"].set(float(m["lr"]))
                    om["gnorm"].set(float(m["grad_norm"]))
                    track["grad_norm"] = float(m["grad_norm"])
                if "nonfinite_steps" in m:
                    om["nonfinite"].set(int(m["nonfinite_steps"]))
                obs.trace.counter("train", tnow, track, tid=0)
                if flusher is not None:
                    flusher.maybe_flush(tnow)
            if "pack_stale" in m:
                # staleness is sticky until the next refresh, so checking at
                # log cadence (not every step) still catches a missed
                # refresh_pack — and a nonzero value means the kernels are
                # executing the WRONG topology: fail fast, don't mistrain
                rec["pack_stale"] = stale = int(m["pack_stale"])
                if om is not None:
                    om["stale"].set(stale)
                if stale:
                    raise RuntimeError(
                        f"PackState is stale ({stale} blocks differ from the "
                        f"masks) at step {step} — a topology update ran "
                        "without refresh_pack(); see docs/kernels.md#staleness"
                    )
            metrics_log.append(rec)
            print(f"[train] step {step:6d} loss {loss:.4f} ({(clock() - t0):.1f}s)")
        ckpt.maybe_save(state, step)
    ckpt.maybe_save(state, step, force=True)
    ckpt.wait()
    if flusher is not None:
        flusher.close(clock())
    stats = mask_stats(state["masks"])
    (workdir / "result.json").write_text(
        json.dumps({
            "metrics": metrics_log,
            "sparsity": stats["sparsity"],
            "nnz": stats["nnz"],
            "topology": topo_trace.summary(),
            "topology_updates": topo_log,
        })
    )
    return state, metrics_log


def run_with_restarts(max_restarts: int = 3, **kw):
    """The fault-tolerance wrapper a cluster scheduler would drive."""
    attempt = 0
    while True:
        try:
            return train_loop(**kw)
        except SimulatedPreemption as e:
            attempt += 1
            print(f"[train] {e}; restart {attempt}/{max_restarts}")
            kw["preempt_at"] = None  # only preempt once in tests
            if attempt > max_restarts:
                raise


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="h2o-danube-1.8b")
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--steps", type=int, default=300)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=64)
    p.add_argument("--method", default="rigl",
                   choices=["rigl", "set", "snfs", "topkast", "static", "snip",
                            "pruning", "dense"])
    p.add_argument("--sparsity", type=float, default=0.8)
    p.add_argument("--distribution", default="erk", choices=["uniform", "er", "erk"])
    p.add_argument("--delta-t", type=int, default=100)
    p.add_argument("--alpha", type=float, default=0.3)
    p.add_argument(
        "--kernel", default="dense", choices=["dense", "masked", "block_sparse"],
        help="execution path for sparsifiable matmuls (Pallas sparse kernels)",
    )
    p.add_argument(
        "--block", type=int, default=128,
        help="block edge for --kernel block_sparse (sets block_shape + tiles)",
    )
    p.add_argument("--workdir", default="/tmp/repro_train")
    p.add_argument("--preempt-at", type=int, default=None)
    p.add_argument("--max-restarts", type=int, default=3)
    p.add_argument(
        "--trace-out", default=None, metavar="PATH",
        help="write a Chrome-trace JSON here (open in Perfetto / "
             "chrome://tracing; docs/observability.md)",
    )
    p.add_argument(
        "--metrics-out", default=None, metavar="PATH",
        help="write Prometheus text-exposition metrics here "
             "(rewritten at log cadence)",
    )
    args = p.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    method = args.method
    sparsity = 0.0 if method == "dense" else args.sparsity
    if method == "dense":
        method = "static"
    sparse_kw = dict(
        sparsity=sparsity, method=method,
        distribution=args.distribution, delta_t=args.delta_t, alpha=args.alpha,
        kernel=args.kernel,
    )
    if args.kernel == "block_sparse":
        # block-sparse execution needs a block-aligned topology (core.rigl
        # block mode) matching the kernel tiles
        sparse_kw["block_shape"] = (args.block, args.block)
        sparse_kw["kernel_block"] = (128, args.block, args.block)
    cfg = dataclasses.replace(cfg, sparse=SparseConfig(**sparse_kw))
    obs = flusher = None
    if args.trace_out or args.metrics_out:
        obs = Observability(pid=1, process_name="train")
        flusher = obs.flusher(
            metrics_path=args.metrics_out, trace_path=args.trace_out,
        )
    run_with_restarts(
        max_restarts=args.max_restarts,
        cfg=cfg,
        steps=args.steps,
        batch=args.batch,
        seq=args.seq,
        workdir=args.workdir,
        preempt_at=args.preempt_at,
        obs=obs,
        flusher=flusher,
    )


if __name__ == "__main__":
    main()
