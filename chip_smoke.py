"""Drive the main path once on a TPU: sparse serving and RigL training.

    python chip_smoke.py              # one chip: serve phase, then train phase
    python chip_smoke.py --chips 4    # four chips: sharded train step only

Run from the root of a checkout on a TPU host.  JAX must find a TPU: the
script exits non-zero before any phase otherwise (it has no CPU path).
Weights and data are random, drawn from ``--seed``.  Everything runs in this
one process, which holds the chip.  Earlier lines report each phase as JSON
(results, compile seconds, peak device bytes: observations, not benchmark
metrics); the last line is ``{"ok": true, "device": {...}}``.  A failed check
raises, so any failed phase exits non-zero.

One chip, h2o-danube-1.8b:
  serve  full depth and width, block-sparse weights (128 blocks, sparsity
         0.8), flash_tight prefill attention, through init_serving_state and
         ServeEngine: 16 staggered requests (512-1024 prompt tokens, 32 new
         tokens each) on 8 slots of 4096 positions.  Checks every request
         ends DONE with in-range tokens, that the compiled prefill and decode
         executables hold the Pallas kernels, and that one prompt's prefill
         logits match kernel='dense' / attn_kernel='dense' on the same
         weights within the bf16 tolerance below.
  train  launch/train.py::train_loop with RigL on block-sparse kernels at
         full width, depth cut to TRAIN_LAYERS: 5 steps with delta_t=2, so
         one topology update and its pack refresh happen.  Checks the loss
         is finite and the pack never goes stale.
Four chips (--chips 4):
  mesh   the depth-cut train step with kernel='dense' masks on a (data=2,
         model=2) mesh, sharded by launch/sharding.py, against the same
         steps on one device; losses must agree to MESH_RTOL.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

ARCH = "h2o-danube-1.8b"
TRAIN_LAYERS = 4  # of 24: the Adam train state of the full depth overflows 16 GB
# Pallas-vs-dense prefill logits, relative L2 error.  Both paths round every
# matmul input to bf16 (8 significant bits, ~4e-3 per rounding) but in a
# different order, through 24 layers; a wrong kernel gives errors of order 1.
LOGITS_RTOL = 5e-2
MESH_RTOL = 2e-3  # sharded vs single-device loss, as tests/test_sharding.py


def _device_gate(n_chips: int):
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found {devices[0].platform!r}")
    if len(devices) < n_chips:
        sys.exit(f"chip_smoke: needs {n_chips} chips; JAX found {len(devices)}")
    return devices[:n_chips]


class CompileClock:
    """XLA compile requests, their seconds (persistent-cache reads
    included) and the cache hits among them, read from JAX's monitoring
    events; ``take`` returns the counts since the last take."""

    def __init__(self):
        self._reset()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _reset(self):
        self.seconds, self.requests, self.cache_hits = 0.0, 0, 0

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.requests += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def take(self) -> dict:
        out = {
            "compile_s": self.seconds,
            "compile_requests": self.requests,
            "cache_hits": self.cache_hits,
        }
        self._reset()
        return out


def _peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _report(phase: str, result: dict) -> None:
    print(json.dumps({"phase": phase, **result}), flush=True)


def assert_kernels_compiled(text: str, what: str) -> None:
    if "tpu_custom_call" not in text:
        raise AssertionError(f"{what}: no Pallas kernel (tpu_custom_call)")


def prefill_logits_error(cfg, params, masks, pack, tokens, max_len):
    """Relative L2 error of the Pallas path's prefill logits against the same
    weights and masks under kernel='dense', attn_kernel='dense'."""
    from repro.launch.serve import configure_kernel
    from repro.models import lm_prefill

    def logits(c, pk):
        fn = jax.jit(
            lambda p, m, pk, b: lm_prefill(p, c, b, max_len, masks=m, pack=pk)[0]
        )
        out = fn(params, masks, pk, {"tokens": jnp.asarray(tokens)[None]})
        # vocab-padding slots hold -1e30 on both paths: compare real tokens
        return np.asarray(out[..., : cfg.vocab_size], np.float64).ravel()

    got = logits(cfg, pack)
    want = logits(configure_kernel(cfg, kernel="dense", attn_kernel="dense"), None)
    return {
        "logits_rel_l2": float(np.linalg.norm(got - want) / np.linalg.norm(want)),
        "logits_max_abs_err": float(np.max(np.abs(got - want))),
        "logits_max_abs": float(np.max(np.abs(want))),
        "same_first_token": bool(np.argmax(got) == np.argmax(want)),
    }


def serve_phase(cfg, *, n_requests, prompt_lens, gen, capacity, max_len,
                seed, clock, device):
    from repro.launch.serve import init_serving_state, staggered_requests
    from repro.serving import ServeEngine, Status
    from repro.serving.sampler import request_key

    t0 = time.perf_counter()
    params, masks, pack = init_serving_state(cfg, seed)
    jax.block_until_ready((params, masks))
    res = {"init_s": time.perf_counter() - t0}

    reqs = staggered_requests(
        cfg, n_requests, prompt_lens=prompt_lens, gen_lens=(gen,), seed=seed
    )
    longest = max(reqs, key=lambda r: r.prompt_len)
    err = prefill_logits_error(cfg, params, masks, pack, longest.tokens, max_len)
    res.update(err, logits_rtol=LOGITS_RTOL)
    rel = err["logits_rel_l2"]
    if not rel <= LOGITS_RTOL:
        raise AssertionError(
            f"serve: Pallas prefill logits differ from dense by rel L2 "
            f"{rel:.3g} > {LOGITS_RTOL}"
        )

    engine = ServeEngine(
        cfg, params, capacity=capacity, max_len=max_len, masks=masks, pack=pack
    )
    for r in reqs:
        if not engine.submit(r):
            raise AssertionError(f"serve: request {r.rid} shed at submit")
    stats = engine.run()
    bad = [
        r.rid for r in reqs
        if r.status is not Status.DONE or len(r.generated) != gen
        or not all(0 <= t < cfg.vocab_size for t in r.generated)
    ]
    if bad:
        raise AssertionError(f"serve: requests not DONE with {gen} in-range "
                             f"tokens: {bad}")
    res.update(
        requests_done=len(reqs), tokens=stats["tokens"],
        decode_steps=stats["decode_steps"], prefills=stats["prefills"],
        wall_s=stats["wall_s"],
    )

    # the executables the engine ran, lowered again from the same arguments
    # (the compile cache serves them back) to read their HLO
    plen = longest.prompt_len
    prefill_args = (
        engine.params, engine.masks, engine.pack, engine.caches,
        {"tokens": jnp.zeros((1, engine._padded_len(plen)), jnp.int32)},
        jnp.int32(0), jnp.int32(plen), jnp.asarray(request_key(seed)),
        jnp.float32(0.0), jnp.int32(0), None,
    )
    decode_args = (
        engine.params, engine.masks, engine.pack, engine.caches,
        jnp.asarray(engine.cur_tok[:, None]), jnp.asarray(engine.pos),
        jnp.asarray(engine.active), jnp.asarray(engine.base_keys),
        jnp.asarray(engine.gen_idx), jnp.asarray(engine.temp),
        jnp.asarray(engine.topk), None,
    )
    for what, fn, args in (
        ("prefill", engine._prefill_for(plen, True), prefill_args),
        ("decode", engine._decode[True], decode_args),
    ):
        assert_kernels_compiled(fn.lower(*args).compile().as_text(), what)
    res["kernels_in_executables"] = ["prefill", "decode"]
    res.update(clock.take(), peak_bytes=_peak_bytes(device))
    return res


def train_phase(cfg, *, steps, batch, seq, seed, clock, device):
    from repro.launch.train import train_loop

    with tempfile.TemporaryDirectory() as workdir:
        t0 = time.perf_counter()
        _, log = train_loop(
            cfg, steps=steps, batch=batch, seq=seq, workdir=workdir,
            log_every=1, seed=seed,
        )
        wall = time.perf_counter() - t0
        summary = json.loads((pathlib.Path(workdir) / "result.json").read_text())
    losses = [r["loss"] for r in log]
    stale = [r["pack_stale"] for r in log if "pack_stale" in r]
    updates = [u["step"] for u in summary["topology_updates"]]
    if not np.all(np.isfinite(losses)):
        raise AssertionError(f"train: non-finite loss {losses}")
    if not stale or any(stale):
        raise AssertionError(f"train: pack_stale {stale}")
    if len(updates) != 1:
        raise AssertionError(f"train: topology updates at steps {updates}")
    return {
        "losses": losses, "pack_stale": stale, "topology_updates": updates,
        "sparsity": summary["sparsity"], "wall_s": wall, **clock.take(),
        "peak_bytes": _peak_bytes(device),
    }


def mesh_phase(cfg, devices, *, steps, batch, seq, seed, clock):
    """Losses of the same train steps on one device and on a (data=2,
    model=2) mesh of four."""
    from repro.data import batch_for
    from repro.launch.mesh import make_local_mesh
    from repro.launch.sharding import batch_shardings, state_shardings
    from repro.optim import LRSchedule, OptConfig
    from repro.training import init_train_state, make_train_step

    opt = OptConfig(kind="sgd", momentum=0.9, weight_decay=0.0)
    lr = LRSchedule(kind="constant", base_lr=1e-2, warmup_steps=0)
    step_fn = make_train_step(cfg, opt, lr)

    def run(mesh):
        state, axes, _ = init_train_state(jax.random.PRNGKey(seed), cfg, opt)
        if mesh is None:
            state = jax.device_put(state, devices[0])
        else:
            state = jax.device_put(state, state_shardings(state, axes, mesh))
        fn = jax.jit(step_fn)
        losses = []
        for t in range(steps):
            b = batch_for(cfg, t, batch, seq, learnable=True)
            b = jax.device_put(
                b, devices[0] if mesh is None else batch_shardings(b, mesh)
            )
            state, m = fn(state, b)
            losses.append(float(m["loss"]))
        return losses

    single = run(None)
    sharded = run(make_local_mesh(2, 2))
    rel = [abs(a - b) / abs(a) for a, b in zip(single, sharded)]
    if not max(rel) <= MESH_RTOL:
        raise AssertionError(
            f"mesh: sharded losses {sharded} vs single-device {single}"
        )
    return {
        "mesh": {"data": 2, "model": 2}, "single": single, "sharded": sharded,
        "max_rel_diff": max(rel), "rtol": MESH_RTOL, **clock.take(),
        "peak_bytes": [_peak_bytes(d) for d in devices],
    }


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--chips", type=int, default=1, choices=(1, 4))
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()

    devices = _device_gate(args.chips)
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))
    from repro.configs import get_config
    from repro.configs.base import SparseConfig
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.serve import configure_kernel

    print(json.dumps({"compile_cache": enable_compile_cache()}), flush=True)
    clock = CompileClock()
    full = get_config(ARCH)
    cut = dataclasses.replace(full, n_layers=TRAIN_LAYERS)
    print(json.dumps({
        "cut": f"{ARCH} train configs: n_layers {full.n_layers} -> "
               f"{TRAIN_LAYERS}; widths, vocab and window as published",
    }), flush=True)

    if args.chips == 4:
        mesh_cfg = dataclasses.replace(cut, sparse=SparseConfig(sparsity=0.8))
        _report("mesh", mesh_phase(
            mesh_cfg, devices, steps=3, batch=8, seq=1024, seed=args.seed,
            clock=clock,
        ))
    else:
        serve_cfg = configure_kernel(
            dataclasses.replace(full, sparse=SparseConfig(sparsity=0.8)),
            kernel="block_sparse", block=128, attn_kernel="flash_tight",
        )
        _report("serve", serve_phase(
            serve_cfg, n_requests=16, prompt_lens=(512, 640, 768, 896, 1024),
            gen=32, capacity=8, max_len=4096, seed=args.seed, clock=clock,
            device=devices[0],
        ))
        train_cfg = configure_kernel(
            dataclasses.replace(
                cut, sparse=SparseConfig(sparsity=0.8, method="rigl", delta_t=2)
            ),
            kernel="block_sparse", block=128, attn_kernel="flash_tight",
        )
        _report("train", train_phase(
            train_cfg, steps=5, batch=4, seq=1024, seed=args.seed, clock=clock,
            device=devices[0],
        ))

    d = jax.devices()[0]
    print(json.dumps({
        "ok": True,
        "device": {"platform": d.platform, "kind": d.device_kind,
                   "count": len(jax.devices())},
    }))


if __name__ == "__main__":
    main()
