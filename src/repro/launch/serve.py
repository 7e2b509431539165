"""Serving driver: continuous-batching engine (default) or lockstep baseline.

  PYTHONPATH=src python -m repro.launch.serve --arch h2o-danube-1.8b --smoke \
      --capacity 4 --requests 16 --arrival-rate 50 --kernel block_sparse

The sparse model serves through the SAME masks it was trained with — test
FLOPs scale with (1-S) exactly as the paper's Figure 2 test columns.

``main`` drives the continuous-batching ``ServeEngine``
(serving/engine.py): a Poisson stream of staggered-length requests admitted
into a fixed slot pool, per-slot decode, slot recycling — so throughput is
not bottlenecked on the slowest request of a fixed batch.  ``--lockstep``
runs the legacy fixed-batch ``serve_session`` instead (the baseline
benchmarks/serve_bench.py compares against).

With ``--kernel`` (or cfg.sparse.kernel) set, prefill and every decode step
route the projections/MLPs through the Pallas sparse kernels instead of
pre-materializing w*m: decode is weight-bound, so block_sparse's skipped
blocks translate ~1:1 into HBM-traffic (and so latency) savings at the
kernel level.  block_sparse additionally threads the serve state's PackState
(host-packed (idx, cnt), core/pack.py) through every call, so the kernel
grids launch the TRUE active-block count — for the engine that means packed
ONCE at construction, reused by every prefill and decode step.
"""
from __future__ import annotations

import argparse
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config
from ..core import build_pack_state
from ..data import batch_for
from ..models import attn_schedules, lm_decode, lm_prefill
from ..training import init_weights
from .compile_cache import enable_compile_cache

__all__ = [
    "serve_session",
    "staggered_requests",
    "configure_kernel",
    "init_serving_state",
    "main",
]


@functools.lru_cache(maxsize=None)
def _session_fns(cfg, max_len: int, s_prefill: int):
    """Jitted (prefill, decode) for one (config, shape) — cached at module
    level (ModelConfig is a frozen, hashable dataclass) so REPEATED sessions
    of the same shape reuse the compiled executables instead of re-tracing
    per call.  The AttnSchedule is likewise built once per shape."""
    sched = attn_schedules(cfg, s_prefill)
    prefill = jax.jit(
        lambda p, m, pk, b: lm_prefill(
            p, cfg, b, max_len=max_len, masks=m, pack=pk, attn_sched=sched
        )
    )
    decode = jax.jit(
        lambda p, m, pk, c, t, pos: lm_decode(p, cfg, c, t, pos, masks=m, pack=pk),
        donate_argnums=(3,),
    )
    return prefill, decode


def serve_session(
    cfg,
    params,
    *,
    batch: int,
    prompt_len: int,
    gen: int,
    max_len: int | None = None,
    masks=None,
    pack=None,
):
    """Greedy batched generation. Returns (tokens (B, prompt+gen), stats).

    masks=None expects pre-masked params (legacy).  With masks, params are
    raw and serving dispatches through cfg.sparse.kernel (see lm_decode).
    pack: PackState (core/pack.py) — the serve state's host-packed block
    topology.  Packed ONCE per topology, threaded into prefill and reused by
    every decode step, so block_sparse grids launch the true active-block
    count instead of the in-jit padded worst case.
    With cfg.sparse.attn_kernel='flash_tight', the session also builds its
    AttnSchedules ONCE for the prompt length (models/attention.py::
    attn_schedules) and threads them into prefill — prefill's attention
    launches only live KV blocks.  Decode takes no schedule: the per-token
    step is a matvec over the ring-bounded cache (nothing block-shaped to
    skip).
    """
    max_len = max_len or (prompt_len + gen)
    prompt = batch_for(cfg, 0, batch, prompt_len + 1, learnable=True)
    prompt = {k: v for k, v in prompt.items() if k != "targets"}
    if "tokens" in prompt:
        prompt["tokens"] = prompt["tokens"][:, :prompt_len]

    # prefill sequence length as the model actually embeds it (mirrors
    # models/model.py::_embed_inputs: VLM prompts prepend their patch
    # embeddings to the text tokens; frames replace tokens outright)
    if "tokens" in prompt:
        s_prefill = prompt["tokens"].shape[1] + (
            cfg.n_patches if "patches" in prompt else 0
        )
    else:
        s_prefill = prompt["frames"].shape[1]
    prefill, decode = _session_fns(cfg, max_len, s_prefill)

    t0 = time.time()
    logits, caches = prefill(params, masks, pack, prompt)
    jax.block_until_ready(logits)
    t_prefill = time.time() - t0

    tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    out = [tok]
    n_patches = cfg.n_patches if cfg.frontend == "patch" else 0
    t0 = time.time()
    for i in range(gen - 1):
        logits, caches = decode(params, masks, pack, caches, tok, prompt_len + n_patches + i)
        tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        out.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.time() - t0
    toks = jnp.concatenate(out, axis=1)
    # tok_per_s counts ALL gen generated tokens — the first one is produced
    # from the prefill logits (argmax above), so the prefill time that bought
    # it is in the denominator; gen-1 decode steps produce the rest.
    return toks, {
        "prefill_s": t_prefill,
        "decode_s_per_tok": t_decode / max(gen - 1, 1),
        "tok_per_s": batch * gen / max(t_prefill + t_decode, 1e-9),
    }


def staggered_requests(cfg, n: int, *, prompt_lens=(16, 32), gen_lens=(8, 16, 32, 64),
                       arrival_rate: float = 0.0, seed: int = 0,
                       temperature: float = 0.0, top_k: int = 0):
    """Synthetic staggered-length workload for the continuous-batching engine.

    Request i cycles through ``prompt_lens``/``gen_lens`` (deliberately
    mismatched cycle lengths => a staggered mix) with Poisson arrival offsets
    at ``arrival_rate`` req/s (0 => burst at t=0).  Shared by the serve CLI,
    benchmarks/serve_bench.py and examples/serve_continuous.py.
    """
    from ..serving import Request, poisson_arrivals

    rng = np.random.default_rng(seed)
    arrivals = poisson_arrivals(n, arrival_rate, seed)
    reqs = []
    for i in range(n):
        L = int(prompt_lens[i % len(prompt_lens)])
        kw = {}
        if cfg.frontend == "patch":
            kw["patches"] = rng.standard_normal(
                (cfg.n_patches, cfg.frontend_dim)
            ).astype(np.float32)
        reqs.append(
            Request(
                rid=i,
                tokens=rng.integers(0, cfg.vocab_size, size=L).astype(np.int32),
                max_new_tokens=int(gen_lens[i % len(gen_lens)]),
                temperature=temperature, top_k=top_k, seed=seed + i,
                arrival=float(arrivals[i]), **kw,
            )
        )
    return reqs


def configure_kernel(cfg, *, kernel=None, block=None, attn_kernel=None):
    """Apply CLI kernel overrides to cfg.sparse (the one definition shared
    by the serve CLI and benchmarks/serve_bench.py — block_sparse couples
    block_shape to the kernel tiles, which must never be spelled twice)."""
    if kernel is None and attn_kernel is None:
        return cfg
    import dataclasses

    sp = cfg.sparse
    if kernel == "block_sparse":
        e = block or sp.kernel_block[2]
        sp = dataclasses.replace(
            sp, kernel="block_sparse", block_shape=(e, e),
            kernel_block=(sp.kernel_block[0], e, e),
        )
    elif kernel is not None:
        sp = dataclasses.replace(sp, kernel=kernel)
    if attn_kernel is not None:
        sp = dataclasses.replace(sp, attn_kernel=attn_kernel)
    return dataclasses.replace(cfg, sparse=sp)


def init_serving_state(cfg, seed: int = 0):
    """Fresh weights ready to serve -> (params, masks, pack).

    The same params and masks ``init_train_state`` draws from this seed, and
    nothing a server does not read: no optimizer state, no Top-KAST backward
    supersets.  Kernel-dispatch modes serve RAW weights + masks (w*m never
    materialized; block_sparse also carries the host-packed tight-grid
    topology — a restored checkpoint carries its own).  Dense mode serves the
    weights as init left them, zeroed off-mask (masks/pack None).
    """
    k_params, k_masks, _ = jax.random.split(jax.random.PRNGKey(seed), 3)
    params, masks, _, _ = init_weights(k_params, k_masks, cfg)
    sp = cfg.sparse
    if sp.kernel not in ("masked", "block_sparse"):
        return params, None, None
    pack = None
    if sp.kernel == "block_sparse" and sp.block_shape is not None:
        pack = build_pack_state(masks, sp.block_shape, slack=sp.pack_width_slack)
    return params, masks, pack


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="h2o-danube-1.8b")
    p.add_argument("--smoke", action="store_true")
    # continuous-batching engine (default mode)
    p.add_argument("--capacity", type=int, default=4,
                   help="engine slot-pool size (the decode batch)")
    p.add_argument("--requests", type=int, default=16,
                   help="number of staggered-length requests to serve")
    p.add_argument("--arrival-rate", type=float, default=0.0,
                   help="Poisson arrival rate, req/s (0 = burst at t=0)")
    p.add_argument("--max-len", type=int, default=128,
                   help="per-slot cache length (prompt + generation bound)")
    # fault-tolerance knobs (docs/serving.md#failure-model)
    p.add_argument("--queue-limit", type=int, default=None,
                   help="max queued requests before submit sheds (backpressure; "
                   "default unbounded)")
    p.add_argument("--deadline", type=float, default=None,
                   help="admission deadline in seconds from arrival; requests "
                   "still queued past it are SHED (default none)")
    p.add_argument("--max-retries", type=int, default=0,
                   help="quarantine-retry budget per request: non-finite slots "
                   "re-queue with backoff this many times before FAILED")
    # paged KV cache (docs/serving.md#paged-kv-cache)
    p.add_argument("--paged", action="store_true",
                   help="page the KV caches: per-slot block tables over "
                   "fixed-size KV pools (serving/block_pool.py)")
    p.add_argument("--page-size", type=int, default=16,
                   help="tokens per KV page (must divide --max-len and each "
                   "local ring length)")
    p.add_argument("--n-blocks", type=int, default=None,
                   help="global page-pool size (default: capacity * max_len "
                   "/ page_size, i.e. no oversubscription)")
    p.add_argument("--prefix-cache", type=int, default=0,
                   help="max LRU-registered shared prefixes for COW prefix "
                   "reuse (0 = off; needs --paged and an all-global "
                   "transformer config)")
    # lockstep baseline (legacy fixed-batch driver)
    p.add_argument("--lockstep", action="store_true",
                   help="run the fixed-batch serve_session baseline instead")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=48)
    p.add_argument("--gen", type=int, default=32)
    p.add_argument(
        "--kernel", default=None, choices=["dense", "masked", "block_sparse"],
        help="override cfg.sparse.kernel for serving",
    )
    p.add_argument(
        "--block", type=int, default=None,
        help="block edge for --kernel block_sparse (sets block_shape + tiles)",
    )
    p.add_argument(
        "--attn-kernel", default=None,
        choices=["dense", "flash", "flash_tight"],
        help="override cfg.sparse.attn_kernel: prefill attention via the "
        "Pallas flash kernels (flash_tight = live-KV-block grids)",
    )
    # observability exports (docs/observability.md)
    p.add_argument(
        "--trace-out", default=None, metavar="PATH",
        help="write the engine's Chrome-trace JSON here (open in Perfetto / "
        "chrome://tracing; docs/observability.md)",
    )
    p.add_argument(
        "--metrics-out", default=None, metavar="PATH",
        help="write Prometheus text-exposition metrics here after the run",
    )
    args = p.parse_args()
    enable_compile_cache()
    cfg = configure_kernel(
        get_config(args.arch, smoke=args.smoke), kernel=args.kernel,
        block=args.block, attn_kernel=args.attn_kernel,
    )
    params, masks, pack = init_serving_state(cfg)

    if args.lockstep:
        toks, stats = serve_session(
            cfg, params, batch=args.batch, prompt_len=args.prompt_len,
            gen=args.gen, masks=masks, pack=pack,
        )
        print(
            f"lockstep  kernel={cfg.sparse.kernel}  "
            f"attn_kernel={cfg.sparse.attn_kernel}  "
            f"generated shape: {toks.shape}"
        )
        for k, v in stats.items():
            print(f"  {k}: {v:.4f}")
        return

    from ..serving import ServeEngine

    obs = None
    if args.trace_out or args.metrics_out:
        from ..obs import Observability

        obs = Observability(process_name="serve")
    engine = ServeEngine(
        cfg, params, capacity=args.capacity, max_len=args.max_len,
        masks=masks, pack=pack, queue_limit=args.queue_limit,
        deadline=args.deadline, max_retries=args.max_retries,
        paged=args.paged, page_size=args.page_size, n_blocks=args.n_blocks,
        prefix_cache=args.prefix_cache, obs=obs,
    )
    n_shed_at_submit = 0
    for req in staggered_requests(
        cfg, args.requests, arrival_rate=args.arrival_rate
    ):
        if not engine.submit(req):
            n_shed_at_submit += 1  # backpressure: bounded queue said no
    if n_shed_at_submit:
        print(f"backpressure: {n_shed_at_submit} requests shed at submit "
              f"(--queue-limit {args.queue_limit})")
    stats = engine.run()
    if obs is not None:
        flusher = obs.flusher(
            metrics_path=args.metrics_out, trace_path=args.trace_out,
        )
        flusher.close(stats["wall_s"])
        if args.trace_out:
            print(f"trace written to {args.trace_out}")
        if args.metrics_out:
            print(f"metrics written to {args.metrics_out}")
    print(
        f"engine  kernel={cfg.sparse.kernel}  "
        f"attn_kernel={cfg.sparse.attn_kernel}  capacity={args.capacity}"
    )
    for k, v in stats.items():
        print(f"  {k}: {v:.4f}" if isinstance(v, float) else f"  {k}: {v}")


if __name__ == "__main__":
    main()
