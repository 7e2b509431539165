"""Serving cells: open-loop traffic through ``ServeEngine.step`` on the wall
clock, request books that balance, and a check of the served tokens against
the float32 reference.

The window lasts ``seconds``.  Every request the cell's mix schedules inside
it is attempted: it is submitted when it falls due, and the engine is
stepped on the wall clock.  When the window closes nothing new is sent, and
stepping goes on until every attempted request is terminal or the cell's
drain limit has passed.  A request still unfinished then is failed: it
counts under ``failed`` and misses every latency limit.  Every attempted
request ends DONE or failed; one that the engine holds in no state at all
is lost, and a lost request makes the run incorrect.

Each token is stamped with the host clock after the ``step()`` that
returned it.  Time to first token is that stamp less the scheduled
arrival; the gaps between a request's stamps are its inter-token gaps.
"""
from __future__ import annotations

import gc
import math
import time

import numpy as np

from chipbench import harness

PAD = 1024  # reference sequences are padded to a multiple of this


class Books:
    """Per-request state of one window, kept by the harness, not the engine."""

    def __init__(self, reqs):
        self.reqs = reqs
        self.stamps = {r.rid: [] for r in reqs}
        self.steps = []  # (t0, t1, decodes [(prompt_len, n_before)], prefills [n])
        self.slot = {}  # rid -> the slot that served its first token
        self.lag = 0.0
        self.at_close = None  # (queued, in slots) when the window closed

    def stamp(self, inflight, t0, t1):
        decodes, prefills = [], []
        for r in inflight:
            s = self.stamps[r.rid]
            n = len(r.generated)
            if n < len(s):  # a quarantine retry restarted the stream
                s.clear()
            before = len(s)
            if n > before:
                admitted = before == 0
                if admitted:
                    prefills.append(r.prompt_len)
                    self.slot[r.rid] = r.slot
                if n - before - admitted > 0:
                    decodes.append((r.prompt_len, before))
                s.extend([t1] * (n - before))
        self.steps.append((t0, t1, decodes, prefills))


def _terminal(r):
    from repro.serving.queue import TERMINAL

    return r.status in TERMINAL


def drive(engine, reqs, seconds, drain_s, *, clock=None, sleep=time.sleep,
          on_step=None, annotate=False):
    """Run one window over ``reqs`` (arrival-sorted Requests, arrivals in
    seconds from the window's start).  Returns the Books.

    ``on_step(now)`` is called before each step (the traced run starts and
    stops its profiler from there); ``annotate`` wraps each step in a
    profiler TraceAnnotation.  ``clock`` returns seconds since the window
    opened (the wall clock by default) and ``sleep`` waits on it."""
    import jax

    if clock is None:
        start = time.perf_counter()
        clock = lambda: time.perf_counter() - start
    books = Books(reqs)
    inflight = []
    i, n = 0, len(reqs)
    while True:
        now = clock()
        while i < n and reqs[i].arrival <= now:
            books.lag = max(books.lag, now - reqs[i].arrival)
            engine.submit(reqs[i])
            inflight.append(reqs[i])
            i += 1
        if now >= seconds and books.at_close is None:
            books.at_close = (len(engine.queue), int(engine.active.sum()))
        if now >= seconds and i == n:
            if not inflight or now >= seconds + drain_s:
                break
        if on_step is not None:
            on_step(now)
        if engine.active.any() or len(engine.queue):
            t0 = clock()
            if annotate:
                with jax.profiler.TraceAnnotation("chipbench.step"):
                    engine.step(now, clock)
            else:
                engine.step(now, clock)
            books.stamp(inflight, t0, clock())
            inflight = [r for r in inflight if not _terminal(r)]
        else:
            due = reqs[i].arrival if i < n else seconds
            sleep(max(0.0, min(due, seconds) - clock()))
    return books


def account(engine, books) -> dict:
    """Balance the books: DONE, failed (FAILED, SHED, or unfinished at the
    drain limit) and lost (in no state the engine holds)."""
    from repro.serving.queue import Status

    waiting = {id(r) for r in engine.queue._waiting}
    in_slot = {id(r) for r in engine.slot_req if r is not None}
    done, failed, lost, unfinished = [], [], [], []
    for r in books.reqs:
        if r.status is Status.DONE:
            # no eos in the mix: a DONE request carries all its tokens
            (done if len(r.generated) == r.max_new_tokens else lost).append(r)
        elif r.status in (Status.FAILED, Status.SHED):
            failed.append(r)
        elif r.status is Status.QUEUED and id(r) in waiting:
            unfinished.append(r)
        elif r.status in (Status.PREFILL, Status.DECODE) and id(r) in in_slot:
            unfinished.append(r)
        else:
            lost.append(r)
    return {"done": done, "failed": failed + unfinished + lost,
            "unfinished": unfinished, "lost": lost}


def attained(books, acct, slo: dict) -> float:
    """Share of attempted requests that finished with time to first token
    and mean inter-token gap within the cell's ``slo`` (a failed request
    misses)."""
    ok = {id(r) for r in acct["done"]}
    met = 0
    for r in books.reqs:
        s = books.stamps[r.rid]
        if id(r) not in ok or not s:
            continue
        gap = (s[-1] - s[0]) / (len(s) - 1) if len(s) > 1 else 0.0
        met += (s[0] - r.arrival <= slo["ttft_s"]
                and gap <= slo["mean_gap_s"])
    return met / max(1, len(books.reqs))


def latencies(books, acct) -> dict:
    ok = {id(r) for r in acct["done"]}
    ttft, gaps = [], []
    for r in books.reqs:
        s = books.stamps[r.rid]
        ttft.append(s[0] - r.arrival if id(r) in ok and s else math.inf)
        gaps.extend(np.diff(s).tolist())
    return {"ttft_s": ttft, "gaps_s": gaps}


def step_times(books) -> dict:
    """Host seconds of the window's steps, by what they held."""
    only = [t1 - t0 for t0, t1, d, p in books.steps if d and not p]
    pre = [t1 - t0 for t0, t1, d, p in books.steps if p]
    q = lambda v, p_: harness.nearest_rank(v, p_) if v else None
    return {"decode_only_steps": len(only), "decode_only_s_p50": q(only, 50),
            "decode_only_s_p95": q(only, 95), "prefill_steps": len(pre),
            "prefill_step_s_p50": q(pre, 50), "prefill_step_s_p95": q(pre, 95)}


def warm(engine, prompt_lengths) -> int:
    """Compile the prefill bucket of every prompt length given, and the
    decode step, by serving one short request per bucket on a virtual
    clock.  Returns the number of buckets."""
    from repro.serving import Request

    longest = {}
    for L in sorted(set(int(x) for x in prompt_lengths)):
        longest[engine._padded_len(L)] = L
    reqs = [Request(rid=-1 - j, tokens=np.zeros(L, np.int32), max_new_tokens=2)
            for j, L in enumerate(sorted(longest.values()))]
    for r in reqs:
        engine.submit(r)
    while not all(_terminal(r) for r in reqs):
        engine.step(0.0)
    return len(reqs)


def sample(acct, seed, bucket, served_tokens, max_requests):
    """DONE requests to check, drawn from the seed: the one that served the
    most tokens, then one from each other prefill bucket (``bucket`` maps a
    prompt length to its bucket) that the window served, then others until
    ``served_tokens`` are covered; at most ``max_requests``."""
    done = sorted(acct["done"], key=lambda r: r.rid)
    if not done:
        return []
    rng = np.random.default_rng([seed, 7])
    first = max(done, key=lambda r: (len(r.generated), r.prompt_len))
    by_bucket = {}
    for r in done:
        by_bucket.setdefault(bucket(r.prompt_len), []).append(r)
    out = [first]
    for b in sorted(by_bucket):
        rs = [r for r in by_bucket[b] if r is not first]
        if b != bucket(first.prompt_len) and rs and len(out) < max_requests:
            out.append(rs[rng.integers(len(rs))])
    n = sum(len(r.generated) for r in out)
    rest = [r for r in done if r not in out]
    rest = [rest[j] for j in rng.permutation(len(rest))]
    for r in rest:
        if n >= served_tokens or len(out) >= max_requests:
            break
        out.append(r)
        n += len(r.generated)
    return out


def reference_gaps(conf, key, picked, max_len, *, control=None):
    """Widest gap by which a served token's reference logit lies below the
    reference's best, over the picked requests; with ``control`` also the
    gap of the token the ``control`` precision puts first.  Layer by layer,
    one request at a time, each sequence padded to a multiple of ``PAD``
    tokens so that a few programs serve every length."""
    import jax
    import jax.numpy as jnp

    ref = harness.reference(conf)
    m = conf["model"]
    params, masks = harness.make_weights(conf, key)
    max_new = max(len(r.generated) for r in picked)
    layer = jax.jit(ref._layer, static_argnums=(3, 5, 6))

    def head(x, rows, norm, w, precision):
        h = ref._rmsnorm(x[rows], norm, m["norm_eps"])
        return ref.logits({"head": {"w": w}}, m, h, precision)

    head = jax.jit(head, static_argnums=(4,))
    mk = _hashable(m)
    out = {"served_gap": 0.0, "tokens": 0, "argmax_agree": 0}
    if control:
        out["control_gap"] = 0.0
    for r in picked:
        n_seq = r.prompt_len + len(r.generated) - 1
        length = min(max_len, -(-n_seq // PAD) * PAD)
        seq = np.zeros(length, np.int32)
        toks = np.concatenate([r.tokens, np.asarray(r.generated[:-1])])
        seq[: len(toks)] = toks
        n = len(r.generated)
        rows = np.full(max_new, r.prompt_len - 1, np.int32)
        rows[:n] = r.prompt_len - 1 + np.arange(n)
        served = np.asarray(r.generated)
        x = params["embed"]["table"][jnp.asarray(seq)] * m["embed_scale"]
        xs = {"f32": x}
        if control:
            xs[control] = x
        for p, mask in zip(params["layers"], masks["layers"]):
            for prec in xs:
                with jax.default_matmul_precision("highest"):
                    xs[prec] = layer(xs[prec], p, mask, mk,
                                     jnp.arange(length), prec, 512)
        last = (params["ln_f"]["scale"], params["head"]["w"])
        want = np.asarray(head(xs["f32"], jnp.asarray(rows), *last, "f32"))[:n]
        best = want.max(-1)
        gap = best - want[np.arange(n), served]
        out["served_gap"] = max(out["served_gap"], float(gap.max()))
        out["tokens"] += n
        out["argmax_agree"] += int((want.argmax(-1) == served).sum())
        if control:
            low = np.asarray(head(xs[control], jnp.asarray(rows), *last,
                                  control))[:n]
            cgap = best - want[np.arange(n), low.argmax(-1)]
            out["control_gap"] = max(out["control_gap"], float(cgap.max()))
    return out


class _hashable(dict):
    """A model dict that jit can take as a static argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def run(spec, seed, seconds, trace, *, devices, clock_compiles, control=None):
    import jax
    from repro.core import build_pack_state
    from repro.serving import Request, ServeEngine

    from chipbench import reduce
    from chipbench.traffic import open_loop

    conf = spec["conf"]
    t0 = time.perf_counter()
    cfg = harness.model_config(conf)
    key = harness.seed_key(seed, "weights")
    params, masks = harness.make_weights(conf, key)
    t_weights = time.perf_counter()
    pack = build_pack_state(masks, cfg.sparse.block_shape,
                            slack=cfg.sparse.pack_width_slack)
    t_pack = time.perf_counter()
    engine = ServeEngine(cfg, params, masks=masks, pack=pack, **conf["serve"])
    del params, masks, pack
    plan = open_loop.generate(spec["mix"], seed, seconds, cfg.vocab_size)
    buckets = warm(engine, [len(p.tokens) for p in plan])
    setup_s = time.perf_counter() - t0
    harness.log(phase="setup", setup_s=setup_s, weights_s=t_weights - t0,
                pack_s=t_pack - t_weights, prefill_buckets=buckets,
                **clock_compiles.take())

    reqs = [Request(rid=p.rid, tokens=p.tokens, max_new_tokens=p.max_new_tokens,
                    arrival=p.arrival, seed=p.rid) for p in plan]
    tracer = reduce.Tracer(spec["trace"], seconds) if trace else None
    books = drive(engine, reqs, seconds, spec["drain_s"],
                  on_step=tracer.on_step if tracer else None,
                  annotate=bool(trace))
    window_compiles = clock_compiles.take()
    acct = account(engine, books)
    lat = latencies(books, acct)
    device = harness.device_info(devices)
    harness.log(phase="window", attempted=len(reqs), done=len(acct["done"]),
                failed=len(acct["failed"]), unfinished=len(acct["unfinished"]),
                lost=len(acct["lost"]), generator_lag_s=books.lag,
                queued_at_close=books.at_close[0],
                in_slots_at_close=books.at_close[1],
                slo=spec.get("slo"), slo_attained=attained(
                    books, acct, spec["slo"]) if "slo" in spec else None,
                decode_steps=engine.n_steps, prefills=engine.n_prefills,
                **step_times(books),
                **{"window_" + k: v for k, v in window_compiles.items()})
    metrics = {}
    if not trace:
        values = {"ttft_p90_ms": 1e3 * harness.nearest_rank(lat["ttft_s"], 90),
                  "itl_p95_ms": 1e3 * harness.nearest_rank(lat["gaps_s"], 95)}
        for m in spec["end_to_end"]:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    breakdown = None
    if trace:
        red = tracer.reduced(math.inf)
        traced = [s for s in books.steps
                  if tracer.t_on <= s[0] and s[1] <= tracer.t_off]
        ctx = {"conf": conf, "trace": red, "steps": traced,
               "peaks": harness.peaks(device["kind"])}
        metrics = reduce.read_metrics(spec, ctx)
        device.update(busy_s=red.busy_s(), window_s=red.window_s())
        breakdown = red.breakdown()

    # the program's state goes before the reference runs
    bucket = {r.prompt_len: engine._padded_len(r.prompt_len)
              for r in reqs}.__getitem__
    del engine
    gc.collect()
    live = sum(a.nbytes for a in jax.live_arrays())
    check = spec["check"]
    picked = sample(acct, seed, bucket, check["served_tokens"],
                    check["max_requests"])
    t_ref = time.perf_counter()
    gaps = (reference_gaps(conf, key, picked, conf["serve"]["max_len"],
                           control=control)
            if picked else {"served_gap": math.inf, "tokens": 0})
    harness.log(phase="reference", requests=len(picked), live_bytes_before=live,
                reference_s=time.perf_counter() - t_ref,
                buckets=[bucket(r.prompt_len) for r in picked],
                slots=[books.slot.get(r.rid) for r in picked], **gaps)
    limits = check["limits"]
    # with ``control`` the control's readings stand in the program's place
    served = gaps["control_gap" if control and picked else "served_gap"]
    checks = {
        "served_gap": {"value": served, "limit": limits["served_gap"]},
        "lost_requests": {"value": len(acct["lost"]), "limit": 0},
    }
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    if not trace:
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    result = {"correct": correct, "attempted": len(reqs),
              "failed": len(acct["failed"]), "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result
