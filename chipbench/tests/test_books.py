"""The serving window's request books, on the CPU at the smoke size.

The chat cell's generator, with the seed of the traced run whose books did
not balance (1334858714), replayed through the harness's serving loop with
the host slowed, so that requests are still queued and in flight when the
window closes and some are unfinished at the drain limit.  Every attempted
request must end DONE or failed, none lost, and every DONE request must
carry the tokens an undisturbed run gives it -- with the profiler off and
on."""
import numpy as np
import pytest

from chipbench import harness, reduce, serve
from chipbench.tests import smoke
from chipbench.traffic import open_loop

SEED = 1334858714
WINDOW_S = 2.0
DRAIN_S = 0.5
SLOW_S = 0.15  # host seconds every engine step takes: the host falls behind


@pytest.fixture(scope="module")
def cell():
    from repro.core import build_pack_state

    spec = smoke.spec("danube-serve.chat")
    conf = spec["conf"]
    cfg = harness.model_config(conf)
    params, masks = harness.make_weights(conf, harness.seed_key(SEED, "weights"))
    pack = build_pack_state(masks, cfg.sparse.block_shape)
    return spec, cfg, (params, masks, pack)


def _engine(cell):
    from repro.serving import ServeEngine

    spec, cfg, (params, masks, pack) = cell
    engine = ServeEngine(cfg, params, masks=masks, pack=pack,
                         **spec["conf"]["serve"])
    serve.warm(engine, [r.prompt_len for r in _requests(cell)])
    return engine


def _requests(cell):
    from repro.serving import Request

    spec, cfg, _ = cell
    plan = open_loop.generate(spec["mix"], SEED, WINDOW_S, cfg.vocab_size)
    return [Request(rid=p.rid, tokens=p.tokens, max_new_tokens=p.max_new_tokens,
                    arrival=p.arrival, seed=p.rid) for p in plan]


@pytest.fixture(scope="module")
def undisturbed(cell):
    """Every request of the window served to its end, host at full speed."""
    engine = _engine(cell)
    reqs = _requests(cell)
    books = serve.drive(engine, reqs, WINDOW_S, drain_s=600.0)
    acct = serve.account(engine, books)
    assert len(acct["done"]) == len(reqs)
    return {r.rid: list(r.generated) for r in reqs}


class SlowHost:
    """A virtual clock on which every engine step takes SLOW_S."""

    def __init__(self, engine):
        self.t = 0.0
        step = engine.step

        def slow(now, clock=None):
            self.t += SLOW_S
            return step(now, clock)

        engine.step = slow

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += dt


@pytest.mark.parametrize("traced", [False, True])
def test_books_balance_with_requests_in_flight(cell, undisturbed, traced,
                                               monkeypatch):
    engine = _engine(cell)
    host = SlowHost(engine)
    reqs = _requests(cell)
    seen_at_close = {}

    def watch(now):
        if now >= WINDOW_S and not seen_at_close:
            seen_at_close["queued"] = len(engine.queue)
            seen_at_close["active"] = int(engine.active.sum())

    tracer = None
    if traced:
        tracer = reduce.Tracer({"start_s": 0.3, "seconds": 1.0}, WINDOW_S)

    def on_step(now):
        watch(now)
        if tracer is not None:
            tracer.on_step(now)

    books = serve.drive(engine, reqs, WINDOW_S, DRAIN_S, clock=host,
                        sleep=host.sleep, on_step=on_step, annotate=traced)
    if traced:
        red = tracer.reduced(float("inf"))
        assert red.host_spans("chipbench.step")
    acct = serve.account(engine, books)
    # requests were waiting and decoding when the window closed, and some
    # were still unfinished at the drain limit
    assert seen_at_close["queued"] + seen_at_close["active"] > 0
    assert acct["unfinished"]
    assert not acct["lost"]
    assert len(reqs) == len(acct["done"]) + len(acct["failed"])
    assert {id(r) for r in acct["done"]}.isdisjoint(
        id(r) for r in acct["failed"])
    assert acct["done"]
    for r in acct["done"]:
        assert r.generated == undisturbed[r.rid]
    lat = serve.latencies(books, acct)
    assert len(lat["ttft_s"]) == len(reqs)
    n_failed = sum(np.isinf(lat["ttft_s"]))
    assert n_failed == len(acct["failed"])


def test_a_request_in_no_state_is_lost(cell):
    """The books catch a request the engine no longer holds anywhere."""
    engine = _engine(cell)
    reqs = _requests(cell)[:3]
    for r in reqs:
        r.arrival = 0.0
        engine.submit(r)
    engine.queue._waiting.remove(reqs[1])  # dropped by a faulty scheduler
    books = serve.Books(reqs)
    acct = serve.account(engine, books)
    assert acct["lost"] == [reqs[1]]
    assert len(reqs) == len(acct["done"]) + len(acct["failed"])


def test_nearest_rank_counts_failed_as_inf():
    v = [1.0] * 8 + [float("inf")] * 2
    assert harness.nearest_rank(v, 80) == 1.0
    assert harness.nearest_rank(v, 90) == float("inf")
    assert harness.nearest_rank([3.0, 1.0, 2.0], 50) == 2.0
