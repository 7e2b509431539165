"""Observability tier: metrics/trace/export semantics + instrumentation.

What this tier pins (docs/observability.md):

  * registry semantics — counter monotonicity, histogram ``le`` bucket
    math, label-series memoization, idempotent registration with loud
    kind/schema mismatches;
  * export fidelity — the Prometheus text exposition ROUND-TRIPS (every
    rendered sample parses back to the exact value the registry held), the
    Chrome trace file is schema-valid for Perfetto, the ring truncates
    oldest-first without losing track-name metadata;
  * instrumentation honesty — a seeded virtual-clock engine run produces
    BIT-IDENTICAL metric snapshots and trace events across two runs
    (metrics as regression oracle, not just dashboard feed), quarantine
    instants mirror both ``engine.quarantine_log`` and the FaultInjector's
    fired log, and instrumentation never perturbs token streams;
  * regions — ``region`` spans under a virtual clock are bit-identical
    across runs, nest by parent, and feed the span counters;
    ``refresh_pack`` records each of its phases and the bytes it fetches.
"""
import dataclasses
import json
import math

import jax
import pytest

from repro.configs import get_config
from repro.configs.base import SparseConfig
from repro.models import init_lm
from repro.obs import (
    REGISTRY,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    Observability,
    PeriodicFlusher,
    SpanTracer,
    exponential_buckets,
    median,
    median_by,
    parse_prometheus_text,
    percentile,
    prometheus_text,
    region,
    summarize,
)
from repro.optim import OptConfig
from repro.serving import FaultInjector, ServeEngine, Status, burst_storm
from repro.training import init_train_state, refresh_pack

pytestmark = pytest.mark.obs


# ---------------------------------------------------------------------------
# units: registry semantics
# ---------------------------------------------------------------------------


def test_counter_monotone():
    c = Counter()
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError, match="negative"):
        c.inc(-1)


def test_gauge_set_inc_dec():
    g = Gauge()
    g.set(4.0)
    g.inc()
    g.dec(2.0)
    assert g.value == 3.0


def test_exponential_buckets_validation():
    assert exponential_buckets(1.0, 2.0, 3) == (1.0, 2.0, 4.0)
    for bad in [dict(start=0), dict(factor=1.0), dict(count=0)]:
        kw = dict(start=1e-3, factor=2.0, count=4)
        kw.update(bad)
        with pytest.raises(ValueError):
            exponential_buckets(**kw)


def test_histogram_le_bucket_semantics():
    h = Histogram(bounds=(1.0, 2.0, 4.0))
    # le (<=) semantics: a value ON a bound lands in that bound's bucket
    for v in [0.5, 1.0, 1.5, 2.0, 4.0, 9.0]:
        h.observe(v)
    assert h.counts == [2, 2, 1, 1]  # (..1], (1..2], (2..4], (4..inf)
    assert h.count == 6
    assert h.sum == pytest.approx(18.0)
    assert h.cumulative() == [(1.0, 2), (2.0, 4), (4.0, 5), (math.inf, 6)]


def test_histogram_bound_validation():
    with pytest.raises(ValueError):
        Histogram(bounds=())
    with pytest.raises(ValueError):
        Histogram(bounds=(1.0, 1.0))
    with pytest.raises(ValueError):
        Histogram(bounds=(1.0, math.inf))


def test_family_label_series_memoized():
    reg = MetricsRegistry()
    fam = reg.counter("reqs_total", "requests", labels=("status",))
    a = fam.labels("DONE")
    assert fam.labels("DONE") is a  # one child per label tuple, kept
    a.inc()
    fam.labels("SHED").inc(2)
    snap = reg.snapshot()["reqs_total"]
    assert snap["kind"] == "counter"
    assert [(s["labels"], s["value"]) for s in snap["series"]] == [
        ({"status": "DONE"}, 1.0),
        ({"status": "SHED"}, 2.0),
    ]
    with pytest.raises(ValueError, match="label"):
        fam.inc()  # label-free proxy is guarded on labeled families
    with pytest.raises(ValueError):
        fam.labels("a", "b")  # wrong arity


def test_registry_idempotent_and_loud_on_mismatch():
    reg = MetricsRegistry()
    a = reg.counter("x_total")
    assert reg.counter("x_total") is a  # get-or-create: two engines share
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x_total")
    with pytest.raises(ValueError, match="already registered"):
        reg.counter("x_total", labels=("k",))
    with pytest.raises(ValueError, match="invalid metric name"):
        reg.counter("bad name")


# ---------------------------------------------------------------------------
# export: Prometheus round-trip, Chrome schema, ring, flusher
# ---------------------------------------------------------------------------


def test_prometheus_round_trip():
    reg = MetricsRegistry()
    reg.counter("reqs_total", "terminal requests", labels=("status",))
    reg.get("reqs_total").labels("DONE").inc(7)
    reg.get("reqs_total").labels('weird "quoted"\nvalue').inc()
    reg.gauge("occupancy", "slots").set(3)          # integer renders bare
    reg.gauge("ratio").set(0.1 + 0.2)               # float must round-trip
    reg.gauge("edge").set(math.inf)
    h = reg.histogram("wait_seconds", "queue wait", buckets=(0.1, 1.0))
    for v in [0.05, 0.1, 0.5, 30.0]:
        h.observe(v)

    text = prometheus_text(reg.snapshot())
    parsed = parse_prometheus_text(text)

    assert parsed["#types"] == {
        "reqs_total": "counter", "occupancy": "gauge", "ratio": "gauge",
        "edge": "gauge", "wait_seconds": "histogram",
    }
    assert parsed["reqs_total"][frozenset({("status", "DONE")})] == 7
    assert parsed["reqs_total"][
        frozenset({("status", 'weird "quoted"\nvalue')})
    ] == 1
    assert parsed["occupancy"][frozenset()] == 3
    assert parsed["ratio"][frozenset()] == 0.1 + 0.2  # exact, not approx
    assert parsed["edge"][frozenset()] == math.inf
    # cumulative buckets match Histogram.cumulative exactly
    buckets = parsed["wait_seconds_bucket"]
    assert buckets[frozenset({("le", "0.1")})] == 2
    assert buckets[frozenset({("le", "1")})] == 3
    assert buckets[frozenset({("le", "+Inf")})] == 4
    assert parsed["wait_seconds_count"][frozenset()] == 4
    assert parsed["wait_seconds_sum"][frozenset()] == pytest.approx(30.65)
    # integers render bare ('3', not '3.0') — what real exporters emit
    assert "occupancy 3\n" in text


def test_chrome_trace_schema(tmp_path):
    tr = SpanTracer(pid=0, process_name="serve")
    tr.thread_name(0, "engine")
    tr.thread_name(1, "slot0")
    tr.span("prefill", 1.5, 2.5, tid=1, cat="serve", args={"rid": 0})
    tr.span("clamped", 2.0, 1.0)  # inverted interval clamps to dur=0
    tr.instant("quarantine", 3.0, tid=1, cat="chaos")
    tr.counter("occupancy", 3.0, {"active": 2})
    path = tmp_path / "trace.json"
    tr.to_chrome(path)

    doc = json.loads(path.read_text())
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    # metadata first (viewers see names before the events that use them)
    assert [e["name"] for e in evs[:3]] == [
        "process_name", "thread_name", "thread_name"
    ]
    for e in evs:
        assert e["ph"] in {"X", "i", "C", "M"}
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        if e["ph"] != "M":
            assert isinstance(e["ts"], int) and e["ts"] >= 0
    span = next(e for e in evs if e["name"] == "prefill")
    assert span["ts"] == 1_500_000 and span["dur"] == 1_000_000  # µs ints
    assert next(e for e in evs if e["name"] == "clamped")["dur"] == 0
    inst = next(e for e in evs if e["name"] == "quarantine")
    assert inst["ph"] == "i" and inst["s"] == "t"


def test_ring_truncates_oldest_keeps_metadata():
    tr = SpanTracer(capacity=4, process_name="serve")
    tr.thread_name(0, "engine")
    for i in range(10):
        tr.instant(f"ev{i}", float(i))
    assert tr.n_emitted == 10 and tr.n_dropped == 6
    assert [e["name"] for e in tr.events] == ["ev6", "ev7", "ev8", "ev9"]
    # metadata rows are exempt from the ring — track names survive eviction
    names = [e["name"] for e in tr.chrome_events()]
    assert names[:2] == ["process_name", "thread_name"]


def test_periodic_flusher_rate_limit_and_incremental_sink(tmp_path):
    reg = MetricsRegistry()
    reg.counter("x_total").inc()
    tr = SpanTracer()
    for i in range(3):
        tr.instant(f"a{i}", float(i))
    fl = PeriodicFlusher(
        registry=reg, tracer=tr,
        metrics_path=tmp_path / "m.prom", trace_path=tmp_path / "t.json",
        interval=5.0,
    )
    assert fl.maybe_flush(0.0) is True
    assert fl.maybe_flush(3.0) is False  # inside the interval: rate-limited
    tr.instant("b", 4.0)
    reg.counter("x_total").inc()
    assert fl.maybe_flush(6.0) is True
    fl.close(now=6.0)
    assert fl.n_flushes == 3

    parsed = parse_prometheus_text((tmp_path / "m.prom").read_text())
    assert parsed["x_total"][frozenset()] == 2
    events = json.loads((tmp_path / "t.json").read_text())["traceEvents"]
    assert [e["name"] for e in events] == ["a0", "a1", "a2", "b"]


# ---------------------------------------------------------------------------
# regions: live spans on the profiler's clock, fed to counters and the ring
# ---------------------------------------------------------------------------


def _ticks(*ts):
    it = iter(ts)
    return lambda: next(it)


def _region_run():
    obs = Observability(metrics=MetricsRegistry(), pid=1, process_name="train")
    # reads: outer enter, inner enter/exit, inner enter/exit, outer exit
    clock = _ticks(10.0, 10.5, 11.25, 12.0, 13.5, 14.0)
    with region("repro.outer", obs=obs, clock=clock, cat="train",
                step=3) as outer:
        for _ in range(2):
            with region("repro.outer.inner", obs=obs, clock=clock):
                pass
        outer.args["done"] = True
    return obs, outer


def test_region_virtual_clock_bit_identical():
    obs1, outer = _region_run()
    obs2, _ = _region_run()
    assert obs1.metrics.snapshot() == obs2.metrics.snapshot()
    assert obs1.trace.chrome_events() == obs2.trace.chrome_events()

    assert outer.seconds == 4.0
    inner, last = obs1.trace.find("repro.outer.inner"), obs1.trace.find(
        "repro.outer")
    assert [(e["ts"], e["dur"], e["args"]) for e in inner] == [
        (10_500_000, 750_000, {"parent": "repro.outer"}),
        (12_000_000, 1_500_000, {"parent": "repro.outer"}),
    ]
    assert last == [{"ph": "X", "name": "repro.outer", "cat": "train",
                     "pid": 1, "tid": 0, "ts": 10_000_000,
                     "dur": 4_000_000, "args": {"step": 3, "done": True}}]
    m = obs1.metrics
    assert m.get("repro_span_seconds_total").labels(
        "repro.outer.inner").value == 2.25
    assert m.get("repro_spans_total").labels("repro.outer.inner").value == 2
    assert m.get("repro_span_last_seconds").labels(
        "repro.outer.inner").value == 1.5
    assert m.get("repro_span_seconds_total").labels("repro.outer").value == 4.0


def test_region_without_handle_counts_in_process_registry():
    spans = REGISTRY.counter("repro_spans_total", "", ("span",))
    before = spans.labels("repro.test.bare").value
    with pytest.raises(KeyError):
        with region("repro.test.bare", clock=_ticks(1.0, 3.0)):
            raise KeyError("the region ends and the error goes on")
    assert spans.labels("repro.test.bare").value == before + 1
    assert REGISTRY.get("repro_span_last_seconds").labels(
        "repro.test.bare").value == 2.0
    # a clock that steps back makes a zero-length span, not a negative one
    with region("repro.test.bare", clock=_ticks(5.0, 4.0)) as r:
        pass
    assert r.seconds == 0.0


def test_refresh_pack_records_each_phase_and_bytes():
    cfg = get_config("h2o-danube-1.8b", smoke=True)
    sp = SparseConfig(
        sparsity=0.8, method="rigl", delta_t=10, alpha=0.3,
        kernel="block_sparse", block_shape=(16, 16),
        kernel_block=(128, 16, 16),
    )
    cfg = dataclasses.replace(cfg, dtype="float32", sparse=sp)
    st, _, _ = init_train_state(jax.random.PRNGKey(0), cfg,
                                OptConfig(kind="adam"))
    obs = Observability(metrics=MetricsRegistry())
    st = refresh_pack(st, cfg, obs)

    packed = [e for e in jax.tree_util.tree_leaves(
        st["pack"], is_leaf=lambda x: x is None or "idx" in x) if e is not None]
    leaves = jax.tree_util.tree_leaves
    counts = {e["name"]: 0 for e in obs.trace.events}
    for e in obs.trace.events:
        counts[e["name"]] += 1
    assert counts == {
        "repro.refresh_pack.drain": 1, "repro.refresh_pack.superset": 1,
        "repro.pack.to_host": len(packed), "repro.pack.build": len(packed),
        "repro.pack.to_device": len(packed), "repro.pack.validate": 1,
        "repro.refresh_pack": 1,
    }
    parents = {e["name"]: e.get("args", {}).get("parent")
               for e in obs.trace.events}
    assert parents.pop("repro.refresh_pack") is None
    assert set(parents.values()) == {"repro.refresh_pack"}
    # every packed leaf's mask and superset were fetched, and counted
    fetched = obs.metrics.get("repro_pack_bytes_to_host_total")._default()
    assert fetched.value == sum(
        x.nbytes for x in leaves(st["masks"]) + leaves(st["bwd_masks"]))
    # the phases do not overlap and lie inside the refresh: their (self)
    # times sum to no more than the parent's
    secs = {k: c.value for (k,), c in
            obs.metrics.get("repro_span_seconds_total").series()}
    parent = secs.pop("repro.refresh_pack")
    assert 0 < sum(secs.values()) <= parent
    top = obs.trace.find("repro.refresh_pack")[0]
    for e in obs.trace.events:
        assert top["ts"] <= e["ts"] and e["ts"] + e["dur"] <= (
            top["ts"] + top["dur"] + 1)  # µs rounding


# ---------------------------------------------------------------------------
# stats_util: empty-population safety, shared percentile math
# ---------------------------------------------------------------------------


def test_stats_util_empty_safe():
    assert percentile([], 50) == 0.0
    assert median([]) == 0.0
    s = summarize([])
    assert s["n"] == 0 and s["mean"] == 0.0 and s["p95"] == 0.0


def test_stats_util_values():
    xs = [3.0, 1.0, 2.0, 4.0]
    assert median(xs) == 2.5
    s = summarize(xs, qs=(50,))
    assert s == {"n": 4, "mean": 2.5, "min": 1.0, "max": 4.0,
                 "p50": pytest.approx(2.5)}
    runs = [{"tok_per_s": t} for t in (5.0, 1.0, 3.0, 4.0)]
    # even count takes the upper-middle run (matches serve_bench's median)
    assert median_by(runs, "tok_per_s")["tok_per_s"] == 4.0


# ---------------------------------------------------------------------------
# instrumented engine: determinism, correlation, zero perturbation
# ---------------------------------------------------------------------------


def _cfg():
    return dataclasses.replace(
        get_config("h2o-danube-1.8b", smoke=True), dtype="float32"
    )


def _drain(engine, dt=1.0, max_steps=2000):
    now = 0.0
    for _ in range(max_steps):
        if not (len(engine.queue) or engine.active.any()):
            return now
        engine.step(now)
        now += dt
    raise AssertionError("engine failed to drain")


def _streams(engine):
    return {r.rid: list(r.generated) for r in engine.queue.done
            if r.status is Status.DONE}


@pytest.fixture(scope="module")
def served():
    """(cfg, params) with every jit this module dispatches already warm, so
    the seeded-determinism runs see flat retrace counters."""
    cfg = _cfg()
    params, _, _ = init_lm(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(cfg, params, capacity=3, max_len=32)
    for r in burst_storm(cfg, 4, prompt_len=8, max_new_tokens=6):
        eng.submit(r)
    _drain(eng)
    return cfg, params


def _obs_run(cfg, params, *, n=4, **kw):
    obs = Observability(metrics=MetricsRegistry(), process_name="serve")
    eng = ServeEngine(cfg, params, capacity=3, max_len=32, obs=obs, **kw)
    for r in burst_storm(cfg, n, prompt_len=8, max_new_tokens=6):
        eng.submit(r)
    _drain(eng)
    return obs, eng


def test_metrics_deterministic_across_seeded_runs(served):
    cfg, params = served
    obs1, eng1 = _obs_run(cfg, params)
    obs2, eng2 = _obs_run(cfg, params)
    # the whole snapshot — counters, gauges AND timing histograms — is
    # bit-identical under the virtual clock: metrics as regression oracle
    assert obs1.metrics.snapshot() == obs2.metrics.snapshot()
    assert obs1.trace.chrome_events() == obs2.trace.chrome_events()
    assert _streams(eng1) == _streams(eng2)
    done = obs1.metrics.get("serve_requests_total").labels("DONE")
    assert done.value == 4.0
    tokens = obs1.metrics.get("serve_tokens_total")._default().value
    assert tokens == sum(len(s) for s in _streams(eng1).values())


def test_instrumentation_never_perturbs_streams(served):
    cfg, params = served
    bare = ServeEngine(cfg, params, capacity=3, max_len=32)
    for r in burst_storm(cfg, 4, prompt_len=8, max_new_tokens=6):
        bare.submit(r)
    _drain(bare)
    _, inst = _obs_run(cfg, params)
    assert _streams(bare) == _streams(inst)


def test_quarantine_trace_matches_injector_and_books(served):
    cfg, params = served
    # capacity 3, burst of 6: rids 0-2 hold slots 0-2 at step 2, so the
    # poisoning deterministically hits rid 0 (tests/test_serving_faults.py)
    inj = FaultInjector().poison_logits(step=2, slot=0)
    obs, eng = _obs_run(cfg, params, n=6, faults=inj, max_retries=0)

    assert eng.quarantine_log == [(2, 0, 0, 0, "decode")]
    quar = obs.trace.find("quarantine")
    assert [
        (e["args"]["step"], e["args"]["rid"], e["args"]["slot"],
         e["args"]["attempt"], e["args"]["where"])
        for e in quar
    ] == [tuple(q) for q in eng.quarantine_log]
    assert quar[0]["tid"] == 0 + 1  # slot s annotates on track s+1
    fired = obs.trace.find("fault_injected")
    assert [(e["args"]["step"], e["args"]["targeted"]) for e in fired] == [
        (step, list(plan)) for kind, step, plan in inj.log if kind == "decode"
    ]
    assert fired[0]["args"]["active"] == [{"slot": 0, "rid": 0, "attempt": 0}]
    snap = obs.metrics.snapshot()["serve_quarantine_total"]
    assert [(s["labels"], s["value"]) for s in snap["series"]] == [
        ({"where": "decode"}, 1.0), ({"where": "prefill"}, 0.0),
    ]


def test_stats_n_retraces_flat_when_warm(served):
    cfg, params = served
    _, eng = _obs_run(cfg, params)
    stats = eng.stats(0.0)
    # every shape this workload dispatches was compiled by the fixture:
    # steady-state traffic must not climb the retrace counter
    assert stats["n_retraces"] == 0
    gauge = eng.obs.metrics.get("serve_retraces")._default()
    assert gauge.value == 0.0
