"""Continuous-batching engine: per-slot decode equivalence, slot recycling,
sampler determinism, request lifecycle.

The load-bearing contract: a request served through the engine — admitted
into an arbitrary slot of a shared cache, stepped with per-slot positions
alongside unrelated requests, possibly into a RECYCLED slot — produces
token-for-token what a dedicated single-request lockstep session (scalar-pos
lm_prefill + lm_decode, greedy) produces.  Checked for kernel='dense' and
kernel='block_sparse' (PackState threaded once per engine).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SparseConfig, get_config
from repro.models import init_caches, init_lm, lm_decode, lm_prefill, lm_prefill_into
from repro.optim import OptConfig
from repro.serving import Request, RequestQueue, ServeEngine, Status, poisson_arrivals
from repro.serving.sampler import request_key, sample_tokens, step_keys
from repro.training import init_train_state

pytestmark = pytest.mark.serve

BLOCK = 16


def _cfg():
    """All-local SWA smoke config (window=16) — ring wraparound territory."""
    return dataclasses.replace(
        get_config("h2o-danube-1.8b", smoke=True), dtype="float32"
    )


def _bs_state():
    cfg = dataclasses.replace(
        _cfg(),
        sparse=SparseConfig(
            sparsity=0.8, method="rigl", kernel="block_sparse",
            block_shape=(BLOCK, BLOCK), kernel_block=(128, BLOCK, BLOCK),
        ),
    )
    st, _, _ = init_train_state(jax.random.PRNGKey(0), cfg, OptConfig())
    return cfg, st


def _params(cfg, seed=0):
    params, _, _ = init_lm(jax.random.PRNGKey(seed), cfg)
    return params


def _prompt(cfg, length, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size, size=length).astype(np.int32)


def _lockstep_tokens(cfg, params, tokens, gen, max_len, *, masks=None, pack=None):
    """Greedy single-request reference: scalar-pos prefill + decode chain."""
    L = int(tokens.shape[0])
    logits, caches = lm_prefill(
        params, cfg, {"tokens": jnp.asarray(tokens)[None]}, max_len=max_len,
        masks=masks, pack=pack,
    )
    tok = int(jnp.argmax(logits[0, -1]))
    out = [tok]
    for i in range(gen - 1):
        logits, caches = lm_decode(
            params, cfg, caches, jnp.asarray([[tok]], jnp.int32), pos=L + i,
            masks=masks, pack=pack,
        )
        tok = int(jnp.argmax(logits[0, -1]))
        out.append(tok)
    return out


# ---------------------------------------------------------------------------
# per-slot decode primitive: staggered vector-pos batch == scalar sessions
# ---------------------------------------------------------------------------

def test_per_slot_decode_matches_scalar_sessions_with_ring_wrap():
    """Three staggered requests + one dead slot, decoded past cfg.window so
    every ring cache wraps, bit-match independent scalar-pos sessions; the
    dead slot's cache rows stay bit-untouched."""
    cfg = _cfg()
    assert cfg.window == 16
    params = _params(cfg)
    max_len, gen = 48, 24  # prompts 4/7/11 + 24 tokens: wraps window=16
    prompts = [_prompt(cfg, L, seed=L) for L in (4, 7, 11)]
    refs = [
        _lockstep_tokens(cfg, params, t, gen, max_len) for t in prompts
    ]

    cap = 4  # slot 3 stays dead throughout
    caches = init_caches(cfg, cap, max_len)
    pos = np.zeros(cap, np.int32)
    active = np.zeros(cap, bool)
    cur = np.zeros(cap, np.int32)
    outs = [[] for _ in range(cap)]
    for s, t in enumerate(prompts):
        logits, caches = lm_prefill_into(
            params, cfg, caches, {"tokens": jnp.asarray(t)[None]},
            jnp.int32(s), max_len,
        )
        cur[s] = int(jnp.argmax(logits[0, -1]))
        outs[s].append(int(cur[s]))
        pos[s], active[s] = t.shape[0], True

    dead_before = jax.tree_util.tree_map(
        lambda x: np.asarray(x[3]).copy(), caches
    )
    for _ in range(gen - 1):
        logits, caches = lm_decode(
            params, cfg, caches, jnp.asarray(cur)[:, None],
            pos=jnp.asarray(pos), active=jnp.asarray(active),
        )
        nxt = np.asarray(jnp.argmax(logits[:, -1], -1), np.int32)
        pos[active] += 1
        cur[active] = nxt[active]
        for s in np.nonzero(active)[0]:
            outs[s].append(int(nxt[s]))

    for s in range(3):
        assert outs[s] == refs[s], f"slot {s} diverged from scalar session"
    dead_after = jax.tree_util.tree_map(lambda x: np.asarray(x[3]), caches)
    for b, a in zip(
        jax.tree_util.tree_leaves(dead_before),
        jax.tree_util.tree_leaves(dead_after),
    ):
        np.testing.assert_array_equal(b, a, err_msg="dead slot state changed")


def test_active_mask_requires_vector_pos():
    cfg = _cfg()
    params = _params(cfg)
    caches = init_caches(cfg, 2, 8)
    with pytest.raises(ValueError, match="active"):
        lm_decode(
            params, cfg, caches, jnp.zeros((2, 1), jnp.int32), pos=0,
            active=jnp.ones((2,), bool),
        )


@pytest.mark.parametrize("arch", ["hymba-1.5b", "xlstm-1.3b", "qwen2-moe-a2.7b"])
def test_per_slot_decode_recurrent_and_moe_families(arch):
    """Vector-pos + active decode matches scalar sessions for the SSM-hybrid,
    xLSTM (recurrent states gated per-row) and MoE families.

    moe_capacity_factor=16.0 makes expert capacity NON-binding: capacity C
    scales with the decode batch, so when C binds, ACTIVE requests batched
    together can contend for expert slots in a way their solo lockstep
    sessions cannot — batch-vs-solo token identity for MoE holds only while
    capacity doesn't bind (docs/serving.md).  Dead-slot isolation is the
    separate, unconditional invariant: see
    test_moe_dead_slots_cannot_contend_expert_capacity.
    """
    cfg = dataclasses.replace(
        get_config(arch, smoke=True), dtype="float32", moe_capacity_factor=16.0
    )
    params = _params(cfg)
    max_len, gen = 32, 6
    prompts = [_prompt(cfg, L, seed=10 + L) for L in (3, 8)]
    refs = [_lockstep_tokens(cfg, params, t, gen, max_len) for t in prompts]

    cap = 3
    caches = init_caches(cfg, cap, max_len)
    pos = np.zeros(cap, np.int32)
    active = np.zeros(cap, bool)
    cur = np.zeros(cap, np.int32)
    outs = [[] for _ in range(cap)]
    for s, t in enumerate(prompts):
        logits, caches = lm_prefill_into(
            params, cfg, caches, {"tokens": jnp.asarray(t)[None]},
            jnp.int32(s), max_len,
        )
        cur[s] = int(jnp.argmax(logits[0, -1]))
        outs[s].append(int(cur[s]))
        pos[s], active[s] = t.shape[0], True
    for _ in range(gen - 1):
        logits, caches = lm_decode(
            params, cfg, caches, jnp.asarray(cur)[:, None],
            pos=jnp.asarray(pos), active=jnp.asarray(active),
        )
        nxt = np.asarray(jnp.argmax(logits[:, -1], -1), np.int32)
        pos[active] += 1
        cur[active] = nxt[active]
        for s in np.nonzero(active)[0]:
            outs[s].append(int(nxt[s]))
    for s in range(2):
        assert outs[s] == refs[s], f"{arch}: slot {s} diverged"


def test_moe_dead_slots_cannot_contend_expert_capacity():
    """Dead slots must be MoE-routing no-ops at the DEFAULT capacity factor.

    Expert capacity C is shared by every row of the decode batch with rank
    priority to lower indices, so without masking a parked slot's stale
    token at a LOW index could push an active request's token out of
    capacity and change its logits (the regression this pins down: active
    logits shifted by ~1 and flipped argmax).  lm_decode threads ``active``
    into moe(), forcing dead rows out of routing entirely — active logits
    must be bit-identical no matter what garbage dead slots hold."""
    cfg = dataclasses.replace(get_config("qwen2-moe-a2.7b", smoke=True),
                              dtype="float32")
    params = _params(cfg)
    cap, max_len = 8, 16
    # sanity: capacity binds at this batch (one expert CAN overflow) — at a
    # non-binding C this test would pass vacuously
    C = max(
        int(np.ceil(cap * cfg.top_k / cfg.n_experts * cfg.moe_capacity_factor)),
        min(cap, 4),
    )
    assert C < cap, "default-capacity config drifted: C no longer binds"

    caches = init_caches(cfg, cap, max_len)
    pos = np.zeros(cap, np.int32)
    active = np.zeros(cap, bool)
    cur = np.zeros(cap, np.int32)
    for i in range(4):  # active requests in HIGH slots 4..7; 0..3 stay dead
        s = 4 + i
        t = _prompt(cfg, 4, seed=40 + i)
        logits, caches = lm_prefill_into(
            params, cfg, caches, {"tokens": jnp.asarray(t)[None]},
            jnp.int32(s), max_len,
        )
        cur[s] = int(jnp.argmax(logits[0, -1]))
        pos[s], active[s] = 4, True

    def active_logits(dead_tok, dead_pos):
        tok = cur.copy()
        tok[:4] = dead_tok
        p = pos.copy()
        p[:4] = dead_pos
        logits, _ = lm_decode(
            params, cfg, caches, jnp.asarray(tok)[:, None],
            pos=jnp.asarray(p), active=jnp.asarray(active),
        )
        return np.asarray(logits[4:, -1])

    ref = active_logits(0, 0)
    for dead_tok, dead_pos in ((1, 0), (97, 3), (cfg.vocab_size - 1, 9)):
        got = active_logits(dead_tok, dead_pos)
        np.testing.assert_array_equal(
            got, ref,
            err_msg="dead-slot contents leaked into active rows' logits "
                    "(expert-capacity contention)",
        )


# ---------------------------------------------------------------------------
# engine: recycling, lifecycle, equivalence (dense + block_sparse)
# ---------------------------------------------------------------------------

def test_engine_recycles_slots_and_matches_lockstep():
    """More requests than capacity: every slot is reused at least once and
    every request is token-identical to its dedicated lockstep session."""
    cfg = _cfg()
    params = _params(cfg)
    max_len = 64
    shapes = [(4, 6), (7, 20), (11, 3), (5, 12), (9, 25), (6, 1)]
    reqs = [
        Request(rid=i, tokens=_prompt(cfg, L, seed=i), max_new_tokens=g)
        for i, (L, g) in enumerate(shapes)
    ]
    refs = {
        r.rid: _lockstep_tokens(cfg, params, r.tokens, r.max_new_tokens, max_len)
        for r in reqs
    }
    engine = ServeEngine(cfg, params, capacity=2, max_len=max_len)
    for r in reqs:
        engine.submit(r)
    stats = engine.run()
    assert stats["requests"] == len(reqs)
    assert stats["prefills"] == len(reqs)
    # recycling really happened: every admission reused one of the 2 slots
    admitted_slots = [s for _, s in engine.slot_history]
    assert len(admitted_slots) == 6 and set(admitted_slots) == {0, 1}
    assert max(admitted_slots.count(s) for s in (0, 1)) >= 2
    # ...and saved decode steps vs padding to the slowest (25-token) request
    assert stats["decode_steps"] < sum(g for _, g in shapes)
    for r in reqs:
        assert r.status is Status.DONE
        assert r.generated == refs[r.rid], f"request {r.rid} diverged"
        assert r.latency is not None and r.latency >= 0.0


def test_engine_equivalence_block_sparse_pack_threaded():
    """Acceptance: engine outputs == lockstep sessions under kernel-dispatch
    serving (raw weights + masks + PackState packed once per engine)."""
    cfg, st = _bs_state()
    params, masks, pack = st["params"], st["masks"], st["pack"]
    max_len = 48
    shapes = [(4, 5), (9, 14), (6, 8), (5, 18)]
    reqs = [
        Request(rid=i, tokens=_prompt(cfg, L, seed=20 + i), max_new_tokens=g)
        for i, (L, g) in enumerate(shapes)
    ]
    refs = {
        r.rid: _lockstep_tokens(
            cfg, params, r.tokens, r.max_new_tokens, max_len,
            masks=masks, pack=pack,
        )
        for r in reqs
    }
    engine = ServeEngine(
        cfg, params, capacity=2, max_len=max_len, masks=masks, pack=pack
    )
    for r in reqs:
        engine.submit(r)
    engine.run()
    for r in reqs:
        assert r.generated == refs[r.rid], f"request {r.rid} diverged"


def test_engine_eos_and_max_tokens_lifecycle():
    cfg = _cfg()
    params = _params(cfg)
    prompt = _prompt(cfg, 6, seed=3)
    ref = _lockstep_tokens(cfg, params, prompt, 12, 48)

    # eos = the first token of the reference stream that is new to it after
    # the first position: generation stops at its first occurrence (eos kept)
    stop = next(i for i in range(1, len(ref)) if ref[i] not in ref[:i])
    eos = ref[stop]
    r_eos = Request(rid=0, tokens=prompt, max_new_tokens=12, eos_id=eos)
    # max_new_tokens=1 finishes straight from the prefill logits
    r_one = Request(rid=1, tokens=prompt, max_new_tokens=1)
    engine = ServeEngine(cfg, params, capacity=2, max_len=48)
    engine.submit(r_eos)
    engine.submit(r_one)
    stats = engine.run()
    assert r_eos.generated == ref[: stop + 1]
    assert r_one.generated == ref[:1]
    assert stats["requests"] == 2

    # oversize requests are rejected at submit, not at decode time
    with pytest.raises(ValueError, match="max_len"):
        engine.submit(Request(rid=2, tokens=_prompt(cfg, 40, 0), max_new_tokens=20))


def test_engine_respects_arrival_times():
    """A request whose arrival is in the future is not admitted early."""
    cfg = _cfg()
    params = _params(cfg)
    early = Request(rid=0, tokens=_prompt(cfg, 4, 0), max_new_tokens=4)
    late = Request(
        rid=1, tokens=_prompt(cfg, 4, 1), max_new_tokens=2, arrival=1e9
    )
    engine = ServeEngine(cfg, params, capacity=2, max_len=32)
    engine.submit(early)
    engine.submit(late)
    for _ in range(10):  # virtual clock never reaches `late`
        engine.step(now=0.0)
    assert early.status is Status.DONE
    assert late.status is Status.QUEUED and not engine.active.any()
    engine.step(now=2e9)
    assert late.status in (Status.DECODE, Status.DONE)


# ---------------------------------------------------------------------------
# prefill bucketing + greedy fast path
# ---------------------------------------------------------------------------

def test_padded_prefill_into_matches_exact_with_ring_wrap():
    """Bucketed prefill (end-padding + masked fill + n_valid logits) must
    match the exact-length path — including when the padding wraps a ring
    cache (L=20, window=16, padded to 32: unmasked pad writes would clobber
    still-needed true K/V at slots p % 16, a CATASTROPHIC >O(1) error).

    Tolerance note: the padded trace reduces attention softmaxes over a
    different (larger, masked) extent, so XLA's reduction order differs and
    float32 results carry ~1e-7 noise vs the exact trace — mathematically
    identical, not bit-identical.  Greedy TOKEN identity (the engine's
    observable contract) is asserted engine-vs-lockstep in
    test_engine_buckets_prompt_lengths_to_bounded_traces."""
    cfg = _cfg()
    params = _params(cfg)
    t = _prompt(cfg, 20, seed=9)
    max_len = 48
    ca = init_caches(cfg, 2, max_len)
    la, ca = lm_prefill_into(
        params, cfg, ca, {"tokens": jnp.asarray(t)[None]}, jnp.int32(1),
        max_len,
    )
    padded = np.zeros(32, np.int32)
    padded[:20] = t
    cb = init_caches(cfg, 2, max_len)
    lb, cb = lm_prefill_into(
        params, cfg, cb, {"tokens": jnp.asarray(padded)[None]}, jnp.int32(1),
        max_len, n_valid=jnp.int32(20),
    )
    np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                               rtol=1e-4, atol=1e-5)
    for x, y in zip(jax.tree_util.tree_leaves(ca), jax.tree_util.tree_leaves(cb)):
        np.testing.assert_allclose(
            np.asarray(x[1]), np.asarray(y[1]), rtol=1e-4, atol=1e-5,
            err_msg="padded prefill produced a different slot cache",
        )


def test_engine_buckets_prompt_lengths_to_bounded_traces():
    """Real traffic has arbitrary prompt lengths: the engine pads each to a
    power-of-two bucket, so many distinct lengths share one jitted prefill
    trace (bounded compile count + bounded lru_cache) AND still match their
    lockstep references exactly."""
    from repro.serving.engine import _prefill_fn

    cfg = _cfg()
    params = _params(cfg)
    max_len = 96  # unique cache key: isolates this test's miss count
    reqs = [
        Request(rid=i, tokens=_prompt(cfg, L, seed=50 + i), max_new_tokens=3)
        for i, L in enumerate((5, 6, 7, 8))  # all bucket to 8
    ]
    refs = {
        r.rid: _lockstep_tokens(cfg, params, r.tokens, r.max_new_tokens, max_len)
        for r in reqs
    }
    engine = ServeEngine(cfg, params, capacity=2, max_len=max_len)
    before = _prefill_fn.cache_info().misses
    for r in reqs:
        engine.submit(r)
    engine.run()
    assert _prefill_fn.cache_info().misses - before == 1, (
        "4 prompt lengths in one bucket must share one prefill trace"
    )
    assert engine.n_prefills == 4
    for r in reqs:
        assert r.generated == refs[r.rid], f"request {r.rid} diverged"


def test_greedy_steps_take_argmax_fast_path():
    """All-greedy traffic (the CLI default) must dispatch the argmax-only
    decode variant on every step; a stochastic slot in the batch selects the
    full sampler."""
    cfg = _cfg()
    params = _params(cfg)
    e1 = ServeEngine(cfg, params, capacity=2, max_len=32)
    e1.submit(Request(rid=0, tokens=_prompt(cfg, 4, seed=0), max_new_tokens=6))
    e1.run()
    assert e1.n_steps > 0 and e1.n_greedy_steps == e1.n_steps

    e2 = ServeEngine(cfg, params, capacity=2, max_len=32)
    e2.submit(Request(rid=0, tokens=_prompt(cfg, 4, seed=0), max_new_tokens=6,
                      temperature=0.8, seed=1))
    e2.run()
    assert e2.n_steps > 0 and e2.n_greedy_steps == 0


# ---------------------------------------------------------------------------
# sampler
# ---------------------------------------------------------------------------

def test_sampler_greedy_is_argmax_and_topk1_matches():
    logits = jnp.asarray(np.random.default_rng(0).standard_normal((5, 33)),
                         jnp.float32)
    keys = jnp.asarray(np.stack([request_key(i) for i in range(5)]))
    zero = jnp.zeros((5,))
    greedy = sample_tokens(logits, keys, zero, jnp.zeros((5,), jnp.int32))
    np.testing.assert_array_equal(
        np.asarray(greedy), np.asarray(jnp.argmax(logits, -1))
    )
    # top_k=1 at any temperature can only pick the argmax
    topk1 = sample_tokens(
        logits, keys, jnp.full((5,), 0.7), jnp.ones((5,), jnp.int32)
    )
    np.testing.assert_array_equal(np.asarray(topk1), np.asarray(greedy))


def test_sampler_determinism_and_slot_independence():
    """Same (weights, prompt, seed) => same tokens, regardless of slot,
    capacity, or batch company; different seeds diverge."""
    cfg = _cfg()
    params = _params(cfg)

    def run(capacity, seed, fillers):
        engine = ServeEngine(cfg, params, capacity=capacity, max_len=48)
        engine.submit(Request(
            rid=0, tokens=_prompt(cfg, 5, seed=7), max_new_tokens=10,
            temperature=0.8, top_k=12, seed=seed,
        ))
        for j in range(fillers):  # occupy lower slots with other traffic
            engine.submit(Request(
                rid=10 + j, tokens=_prompt(cfg, 3 + j, seed=j),
                max_new_tokens=6, temperature=1.3, seed=100 + j,
            ))
        engine.run()
        return [r for r in engine.queue.done if r.rid == 0][0].generated

    a = run(capacity=2, seed=1, fillers=0)
    b = run(capacity=2, seed=1, fillers=0)
    assert a == b, "same seed must reproduce the same stream"
    assert len(a) == 10
    c = run(capacity=4, seed=1, fillers=3)
    assert a == c, "slot index / batch company must not perturb sampling"
    d = run(capacity=2, seed=2, fillers=0)
    assert a != d, "different seeds should diverge (astronomically likely)"


def test_step_keys_fold_per_row():
    base = jnp.asarray(np.stack([request_key(3), request_key(3)]))
    k0 = step_keys(base, jnp.asarray([0, 1], jnp.int32))
    ref0 = jax.random.fold_in(jnp.asarray(request_key(3)), 0)
    ref1 = jax.random.fold_in(jnp.asarray(request_key(3)), 1)
    np.testing.assert_array_equal(np.asarray(k0[0]), np.asarray(ref0))
    np.testing.assert_array_equal(np.asarray(k0[1]), np.asarray(ref1))


# ---------------------------------------------------------------------------
# queue plumbing
# ---------------------------------------------------------------------------

def test_queue_fifo_and_arrival_gating():
    q = RequestQueue()
    for i, arr in enumerate([0.0, 0.5, 2.0]):
        q.submit(Request(rid=i, tokens=np.zeros(2, np.int32),
                         max_new_tokens=1, arrival=arr))
    assert q.pop_ready(0.0).rid == 0
    assert q.pop_ready(0.0) is None  # rid=1 hasn't arrived yet
    assert q.next_arrival() == 0.5
    assert q.pop_ready(1.0).rid == 1
    assert q.pop_ready(1.0) is None
    assert q.pop_ready(3.0).rid == 2
    with pytest.raises(ValueError, match="max_new_tokens"):
        q.submit(Request(rid=9, tokens=np.zeros(2, np.int32), max_new_tokens=0))


def test_queue_out_of_order_submission():
    """A late-arriving request submitted FIRST must not block one that has
    already arrived (the waiting list orders by arrival, not submission)."""
    q = RequestQueue()
    q.submit(Request(rid=0, tokens=np.zeros(2, np.int32), max_new_tokens=1,
                     arrival=5.0))
    q.submit(Request(rid=1, tokens=np.zeros(2, np.int32), max_new_tokens=1,
                     arrival=0.0))
    assert q.next_arrival() == 0.0
    assert q.pop_ready(1.0).rid == 1
    assert q.pop_ready(1.0) is None
    assert q.pop_ready(6.0).rid == 0


def test_poisson_arrivals_shape_and_burst():
    a = poisson_arrivals(10, 0.0)
    np.testing.assert_array_equal(a, np.zeros(10))
    b = poisson_arrivals(100, 50.0, seed=1)
    assert b.shape == (100,) and np.all(np.diff(b) >= 0)
    assert 100 / 50.0 * 0.3 < b[-1] < 100 / 50.0 * 3.0  # ~n/rate seconds


# ---------------------------------------------------------------------------
# paged KV cache: block-table engine == contiguous engine == lockstep
# ---------------------------------------------------------------------------

def _drain(engine, max_steps=2000):
    while len(engine.queue) or engine.active.any():
        engine.step(0.0)
        max_steps -= 1
        assert max_steps > 0, "engine failed to drain"
    return {r.rid: list(r.generated) for r in engine.queue.done}


def _run_both(cfg, params, reqs, *, capacity, max_len, page_size=8,
              masks=None, pack=None, **paged_kw):
    """(contiguous streams, paged streams, paged engine) on one workload."""
    import copy
    base = ServeEngine(cfg, params, capacity=capacity, max_len=max_len,
                       masks=masks, pack=pack)
    for r in copy.deepcopy(reqs):
        base.submit(r)
    paged = ServeEngine(cfg, params, capacity=capacity, max_len=max_len,
                        masks=masks, pack=pack, paged=True,
                        page_size=page_size, **paged_kw)
    for r in reqs:
        paged.submit(r)
    return _drain(base), _drain(paged), paged


@pytest.mark.paged
def test_paged_engine_identical_with_ring_wrap_and_recycling():
    """Acceptance: the paged engine (all-local SWA config — every cache a
    ring that WRAPS past cfg.window) is token-identical to the contiguous
    engine AND to dedicated lockstep sessions, across slot recycling; the
    pools drain to empty afterwards."""
    cfg = _cfg()
    assert cfg.window == 16
    params = _params(cfg)
    max_len = 64
    shapes = [(4, 24), (7, 20), (11, 3), (5, 12), (9, 25), (6, 1)]
    reqs = [
        Request(rid=i, tokens=_prompt(cfg, L, seed=i), max_new_tokens=g)
        for i, (L, g) in enumerate(shapes)
    ]
    refs = {
        r.rid: _lockstep_tokens(cfg, params, r.tokens, r.max_new_tokens, max_len)
        for r in reqs
    }
    base, paged, eng = _run_both(cfg, params, reqs, capacity=2, max_len=max_len)
    assert base == refs and paged == refs
    # slots really recycled through the page pools
    slots = [s for _, s in eng.slot_history]
    assert len(slots) == 6 and set(slots) == {0, 1}
    eng.check_pool_accounting()
    for pool in eng.pools.values():
        assert pool.n_live == 0  # every page returned on release


@pytest.mark.paged
@pytest.mark.parametrize("arch", ["hymba-1.5b", "xlstm-1.3b", "qwen2-moe-a2.7b"])
def test_paged_engine_recurrent_and_moe_families(arch):
    """Paged == contiguous token streams for the SSM-hybrid (paged KV +
    slot-batched recurrent state side by side), xLSTM (no KV at all — the
    paged engine degenerates gracefully) and MoE families."""
    cfg = dataclasses.replace(
        get_config(arch, smoke=True), dtype="float32", moe_capacity_factor=16.0
    )
    params = _params(cfg)
    shapes = [(3, 6), (8, 4), (5, 7)]
    reqs = [
        Request(rid=i, tokens=_prompt(cfg, L, seed=30 + i), max_new_tokens=g)
        for i, (L, g) in enumerate(shapes)
    ]
    base, paged, eng = _run_both(cfg, params, reqs, capacity=2, max_len=32)
    assert base == paged
    eng.check_pool_accounting()


@pytest.mark.paged
def test_paged_engine_block_sparse_pack_threaded():
    """Paged addressing composes with kernel-dispatch serving: raw weights +
    masks + PackState, tokens identical to the contiguous engine."""
    cfg, st = _bs_state()
    params, masks, pack = st["params"], st["masks"], st["pack"]
    shapes = [(4, 5), (9, 14), (6, 8)]
    reqs = [
        Request(rid=i, tokens=_prompt(cfg, L, seed=20 + i), max_new_tokens=g)
        for i, (L, g) in enumerate(shapes)
    ]
    base, paged, eng = _run_both(
        cfg, params, reqs, capacity=2, max_len=48, masks=masks, pack=pack
    )
    assert base == paged
    eng.check_pool_accounting()


def _shared_prefix_reqs(cfg, prefix, n, *, gen=6, seed=0, rid0=0):
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        suffix = rng.integers(0, cfg.vocab_size,
                              size=int(rng.integers(1, 12))).astype(np.int32)
        reqs.append(Request(
            rid=rid0 + i, tokens=np.concatenate([prefix, suffix]),
            max_new_tokens=gen, share_prefix_len=len(prefix),
        ))
    return reqs


@pytest.mark.paged
def test_shared_prefix_admission_token_identical_with_cow():
    """Shared-prefix requests (one 24-token template, random suffixes,
    page_size 8 => 3 shared pages) decode token-identical to a no-sharing
    paged engine; the prefix cache takes hits, refcounts prove sharing, and
    the boundary-page COW fork fires for a whole-prompt-prefix request."""
    cfg = dataclasses.replace(
        get_config("mistral-large-123b", smoke=True), dtype="float32"
    )
    params = _params(cfg)
    prefix = _prompt(cfg, 24, seed=99)
    reqs = _shared_prefix_reqs(cfg, prefix, 6, seed=4)
    # rid 6: prompt == prefix exactly -> ctx clips to prompt_len-1, which is
    # page-UNALIGNED: the last shared page must FORK, not be written through
    reqs.append(Request(rid=6, tokens=prefix.copy(), max_new_tokens=4,
                        share_prefix_len=24))
    base, shared, eng = _run_both(
        cfg, params, reqs, capacity=2, max_len=64, prefix_cache=4
    )
    assert base == shared
    assert eng.n_prefix_hits >= 5  # first request misses + registers
    assert eng.pools["global"].n_forks >= 1
    eng.check_pool_accounting()
    # only the registered prefix entry still holds pages
    held = sum(len(e.pages) for e in eng._prefix_entries.values())
    assert eng.pools["global"].n_live == len(
        set().union(*(e.pages for e in eng._prefix_entries.values()))
    ) and held == 24 // 8
    # refcount evidence DURING service: admit two sharers, stop mid-flight
    eng2 = ServeEngine(cfg, params, capacity=2, max_len=64, paged=True,
                       page_size=8, prefix_cache=4)
    for r in _shared_prefix_reqs(cfg, prefix, 2, gen=20, seed=8, rid0=50):
        eng2.submit(r)
    eng2.step(0.0)
    shared_pages = next(iter(eng2._prefix_entries.values())).pages
    # cache ref + both slots' refs on every fully-shared page
    assert all(eng2.pools["global"].refcount[p] == 3 for p in shared_pages[:-1])
    eng2.check_pool_accounting()


@pytest.mark.paged
def test_paged_pool_capacity_bounds_submit_and_defers_admission():
    """submit() enforces the PAGE bound (an undersized pool rejects what the
    max_len row bound would admit); admission under pool pressure defers
    (requeue) instead of deadlocking and completes once pages free."""
    cfg = dataclasses.replace(
        get_config("mistral-large-123b", smoke=True), dtype="float32"
    )
    params = _params(cfg)
    # pool of 6 pages @ 8 = 48 positions, but max_len 64 rows
    eng = ServeEngine(cfg, params, capacity=2, max_len=64, paged=True,
                      page_size=8, n_blocks=6)
    # 49 positions -> 7 pages > 6: reject at submit even though 49 <= 64
    with pytest.raises(ValueError, match="pages"):
        eng.submit(Request(rid=0, tokens=_prompt(cfg, 41, 1),
                           max_new_tokens=8))
    # exact-capacity boundary: 48 positions == 6 pages is admissible
    fits = Request(rid=1, tokens=_prompt(cfg, 40, 2), max_new_tokens=8)
    # ...but only alone: this second request must WAIT for the first
    waits = Request(rid=2, tokens=_prompt(cfg, 8, 3), max_new_tokens=8)
    refs = {
        r.rid: _lockstep_tokens(cfg, params, r.tokens, r.max_new_tokens, 64)
        for r in (fits, waits)
    }
    assert eng.submit(fits) and eng.submit(waits)
    eng.step(0.0)
    assert fits.slot is not None and waits.slot is None  # deferred, not shed
    assert waits.status is Status.QUEUED
    streams = _drain(eng)
    assert streams == refs
    eng.check_pool_accounting()
    assert eng.pools["global"].n_live == 0


@pytest.mark.paged
def test_paged_engine_rejects_bad_geometry():
    cfg = _cfg()
    params = _params(cfg)
    with pytest.raises(ValueError, match="page_size"):
        ServeEngine(cfg, params, capacity=2, max_len=40, paged=True,
                    page_size=12)  # 12 divides neither ring 16 nor row 40
    with pytest.raises(ValueError, match="paged"):
        ServeEngine(cfg, params, capacity=2, max_len=32, prefix_cache=2)
    with pytest.raises(ValueError, match="all-global"):
        # danube is all-LOCAL: ring caches cannot host shared prefixes
        ServeEngine(cfg, params, capacity=2, max_len=32, paged=True,
                    page_size=8, prefix_cache=2)
