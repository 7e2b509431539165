"""Training cells: RigL on the block-sparse kernels, composed as the
program's ``train_loop`` composes it (``make_train_step``, ``make_rigl_step``
and ``refresh_pack`` every ``delta_t`` steps), without its checkpoints and
logging.

Set-up builds one state from the seed and drives it through its first three
steps, which the float32 reference follows, and compiles RigL's update step
ahead.  The window opens on the same state with RigL's topology update (the
run starts at ``first_step`` so that its fourth step is one) and runs steps
until ``seconds`` have passed, with at most one step
queued behind the one running, and closes when the last step dispatched has
finished.  Throughput is the tokens of every step in the window over the
window.

After the window the reference follows the three checked steps and then
makes the window's topology update itself, from its own gradient of the
update's batch: the masks the program left are held to the block counts,
the size of the update and the order of the grown blocks by |gradient|
(``topology``).
"""
from __future__ import annotations

import functools
import gc
import json
import math
import time

import numpy as np

from chipbench import harness

CHECKED_STEPS = 3


def _is_update(cfg, algo, step: int) -> bool:
    sp = cfg.sparse
    return (sp.method in ("rigl", "set", "snfs", "topkast") and step > 0
            and step % sp.delta_t == 0 and step < algo.schedule.t_end)


def build_state(cfg, opt, params, masks, step, rng):
    """The program's train state around the benchmark's weights, as
    ``init_train_state`` assembles it."""
    import jax.numpy as jnp
    from repro.core import build_pack_state, topkast_backward_masks
    from repro.optim import init_opt
    from repro.training.steps import needs_bwd_masks

    sp = cfg.sparse
    state = {"step": jnp.int32(step), "params": params, "masks": masks,
             "opt": init_opt(opt, params), "rng": rng,
             "nonfinite_steps": jnp.zeros((), jnp.int32)}
    if needs_bwd_masks(sp):
        import jax

        state["bwd_masks"] = topkast_backward_masks(
            params, masks, sp.backward_extra, jax.random.fold_in(rng, 1),
            block_shape=sp.block_shape)
    state["pack"] = build_pack_state(
        masks, sp.block_shape, slack=sp.pack_width_slack,
        bwd_masks=state.get("bwd_masks"))
    return state


def leaf_norms(tree, scale=1.0):
    """Per-leaf float32 L2 norms of a pytree, as a flat dict."""
    import jax
    import jax.numpy as jnp

    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) * scale for p, x in flat}


def gap(got: dict, want: dict) -> tuple:
    """Worst leaf of |got - want| / max(want, median leaf of want), over the
    leaves whose reference value is at least a thousandth of the median
    (leaves that move by round-off alone are left out).  -> (gap, leaf)."""
    med = float(np.median(list(want.values())))
    worst, where = 0.0, None
    for k, w in want.items():
        if w < 1e-3 * med:
            continue
        g = abs(got[k] - w) / max(w, med)
        if g > worst:
            worst, where = g, k
    return worst, where


def reference_steps(conf, params, masks, batches, opt, lr, first_step,
                    precision="f32", update=None):
    """The float32 reference's first steps: losses, the first gradient as
    Adam gets it (masked, clipped), and each leaf's change after all of
    them.  Plain Adam, written out here.  A batch's gradient is summed one
    row at a time, so one row's activations are the largest live set.

    With ``update``, the batch of the topology update that follows them,
    also the dense gradient of that batch at the parameters the steps
    leave, as per-block sums of |gradient| (``scores``) and of |weight|
    (``mags``) of the sparse leaves."""
    import jax
    import jax.numpy as jnp

    ref = harness.reference(conf)
    m = conf["model"]
    is_none = lambda x: x is None
    tmap = jax.tree_util.tree_map

    def row(p, mk, tokens, targets, acc):
        loss, g = jax.value_and_grad(ref.loss)(
            p, mk, m, tokens[None], targets[None], precision)
        return acc[0] + loss, tmap(jnp.add, acc[1], g)

    row = jax.jit(row, donate_argnums=(4,))

    def clip(g, mk, n):
        g = tmap(lambda x, k: x / n if k is None else x * k / n, g, mk,
                 is_leaf=is_none)
        norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree_util.tree_leaves(g)))
        scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(norm, 1e-9))
        return tmap(lambda x: x * scale, g)

    def adam(p, mo, v, g, count, lr_t):
        b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
        mo = tmap(lambda a, x: b1 * a + (1 - b1) * x, mo, g)
        v = tmap(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
        c1, c2 = 1 - b1 ** count, 1 - b2 ** count
        p = tmap(lambda w, a, s: w - lr_t * (a / c1) / (jnp.sqrt(s / c2) + eps),
                 p, mo, v)
        return p, mo, v

    def grad_sum(p, mk, b):
        acc = (jnp.float32(0.0), tmap(jnp.zeros_like, params))
        for r in range(b["tokens"].shape[0]):
            acc = row(p, mk, b["tokens"][r], b["targets"][r], acc)
        return acc

    clip = jax.jit(clip, donate_argnums=(0,))
    adam = jax.jit(adam, donate_argnums=(0, 1, 2))
    start = {k: x for k, x in leaf_values(params).items()}
    p = tmap(jnp.copy, params)
    mo = tmap(jnp.zeros_like, params)
    v = tmap(jnp.zeros_like, params)
    losses, first = [], None
    for t, b in enumerate(batches):
        acc = grad_sum(p, masks, b)
        rows = b["tokens"].shape[0]
        # each row's loss is its mean over tokens: the batch's is their mean
        losses.append(float(acc[0]) / rows)
        g = clip(acc[1], masks, rows)
        if t == 0:
            first = {k: float(x) for k, x in leaf_norms(g).items()}
        p, mo, v = adam(p, mo, v, g, t + 1, lr(first_step + t))
    del mo, v
    change = {}
    for k, x in leaf_values(p).items():
        change[k] = float(jnp.sqrt(jnp.sum(jnp.square(x - start[k]))))
    out = {"loss": losses, "grad": first, "change": change}
    if update is not None:
        # every mask on: the gradient of the masked weights themselves,
        # inactive ones included, which is what growth ranks
        ones = tmap(lambda k: None if k is None else jnp.ones_like(k), masks,
                    is_leaf=is_none)
        g = grad_sum(p, ones, update)[1]
        block = conf["sparse"]["block"]
        sparse = set(leaf_values(masks))
        out["scores"] = host_blocks(pool_blocks(g, block, sparse))
        out["mags"] = host_blocks(pool_blocks(p, block, sparse))
    return out


def leaf_values(tree) -> dict:
    import jax

    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): x for p, x in flat}


@functools.lru_cache(maxsize=None)
def _pool(block: int):
    import jax
    import jax.numpy as jnp

    def pool(x):
        *lead, a, c = x.shape
        x = jnp.abs(x.astype(jnp.float32))
        return jnp.sum(x.reshape(*lead, a // block, block, c // block, block),
                       axis=(-3, -1))

    return jax.jit(pool)


def pool_blocks(tree, block: int, only=None) -> dict:
    """Per-leaf sums of |x| over ``block`` x ``block`` blocks of the last
    two dims, any leading dims kept (a bank (E, a, c) -> (E, a/b, c/b)), of
    the leaves of a pytree (those named in ``only``), on the device, keyed
    as ``leaf_values``; dispatched without waiting.  A leaf that does not
    tile into such blocks is an error, never skipped."""
    pool = _pool(block)
    out = {}
    for k, x in leaf_values(tree).items():
        if only is not None and k not in only:
            continue
        if x.ndim < 2 or x.shape[-2] % block or x.shape[-1] % block:
            raise ValueError(f"chipbench: sparse leaf {k} of shape {x.shape} "
                             f"does not tile into {block} x {block} blocks")
        out[k] = pool(x)
    return out


def host_blocks(blocks: dict) -> dict:
    return {k: np.asarray(x) for k, x in blocks.items()}


def update_fraction(conf: dict, step: int) -> np.float32:
    """The share of each layer's active blocks RigL's update at ``step``
    replaces: alpha / 2 * (1 + cos(pi * step / t_end)) (arXiv:1911.11134,
    eq. 1), with t_end the configuration's share of the schedule's steps;
    in float32, as the update is reckoned."""
    sp, lr = conf["sparse"], conf["train"]["lr"]
    f32 = np.float32
    t_end = int(sp["t_end_fraction"] * lr["total_steps"])
    return f32(0.5 * sp["alpha"]) * (f32(1.0) + np.cos(
        f32(np.pi) * f32(step) / f32(t_end)))


def reference_update(before: dict, superset: dict, mags: dict,
                     scores: dict, fraction) -> dict:
    """RigL's update on the reference's numbers, per leaf, as the
    configuration states it: of each layer's n active blocks keep the
    n - k largest by summed |weight|, k = floor(fraction * n), then grow
    the k best by summed |gradient| among the rest of the backward
    superset (freshly dropped blocks may grow again).  A leaf is one unit,
    a bank of experts too: its blocks are ranked together with one k, as
    the program's ``_drop_grow`` ranks them.  -> {leaf: (k, new block
    mask)}."""
    out = {}
    for name, act in before.items():
        a = act.reshape(-1)
        n = int(a.sum())
        k = int(np.floor(np.float32(fraction) * np.float32(n)))
        mag = np.where(a, mags[name].reshape(-1), -np.inf)
        kept = np.zeros(a.size, bool)
        kept[np.argsort(-mag, kind="stable")[: n - k]] = True
        s = np.where(superset[name].reshape(-1), scores[name].reshape(-1), 0.0)
        s = np.where(kept, -np.inf, s)
        grown = np.zeros(a.size, bool)
        grown[np.argsort(-s, kind="stable")[:k]] = True
        out[name] = (k, (kept | grown).reshape(act.shape))
    return out


def topology(after: dict, topo: dict, ref_update: dict) -> dict:
    """The masks an update left, against the reference's update.

    - ``topology_counts``: over the leaves, the most blocks by which the
      masks before the update differ from the configuration's, the active
      count after it from the count before, and the backward superset from
      the active blocks plus ceil(backward_extra x blocks), with the active
      blocks outside it (0 when sound).
    - ``update_size``: |blocks grown new - the reference's| over the
      blocks the schedule replaces, summed over the leaves.
    - ``grow_order``: over the leaves, the most by which a block left
      inactive outscores the lowest block grown new, by the reference's
      summed |gradient| on the superset (0 off it, as growth sees it), over
      the best such score: growth by |gradient| ranks each grown block
      above each one left out.  Only blocks inactive before the update
      count, so which of two nearly equal weights was dropped does not
      move it."""
    before, superset = topo["before"], topo["superset"]
    counts, grown_got, grown_ref, ks, order = 0, 0, 0, 0, 0.0
    for name, b in before.items():
        a, sup, want = after[name], superset[name], topo["ref_masks"][name]
        k, ref_after = ref_update[name]
        width = min(b.size, int(want.sum()) + math.ceil(
            topo["backward_extra"] * b.size))
        counts = max(counts, int(
            (b != want).sum() + abs(int(a.sum()) - int(want.sum()))
            + abs(int(sup.sum()) - width) + (b & ~sup).sum()))
        new = a & ~b
        grown_got += int(new.sum())
        grown_ref += int((ref_after & ~b).sum())
        ks += k
        s = np.where(sup, topo["scores"][name], 0.0)
        left = ~b & ~new
        best = float(s[~b].max()) if (~b).any() else 0.0
        if new.any() and left.any() and best > 0:
            gap = max(0.0, float(s[left].max() - s[new].min()))
            order = max(order, gap / best)
    return {"topology_counts": counts,
            "update_size": abs(grown_got - grown_ref) / max(ks, 1),
            "grow_order": order}


@functools.lru_cache(maxsize=None)
def _programs(conf_json: str, make_train_step, make_rigl_step):
    """The cell's jitted step and update step, made once per process so
    that runs of several seeds in one process trace them once."""
    import jax
    from repro.optim import LRSchedule, OptConfig
    from repro.training import make_algo

    conf = json.loads(conf_json)
    tr = conf["train"]
    cfg = harness.model_config(conf)
    opt = OptConfig(**tr["optimizer"])
    lr = LRSchedule(**tr["lr"])
    algo = make_algo(cfg, lr.total_steps)
    train_step = jax.jit(make_train_step(cfg, opt, lr), donate_argnums=0)
    rigl_step = jax.jit(make_rigl_step(cfg, algo, lr), donate_argnums=0)
    return cfg, opt, lr, algo, train_step, rigl_step, {}


def run(spec, seed, seconds, trace, *, devices, clock_compiles, control=None,
        make_train_step=None, make_rigl_step=None):
    import jax
    from repro.training import make_rigl_step as program_rigl_step
    from repro.training import make_train_step as program_train_step
    from repro.training import refresh_pack

    from chipbench import reduce
    from chipbench.traffic import token_batches

    conf, data = spec["conf"], spec["mix"]
    tr = conf["train"]
    block = conf["sparse"]["block"]
    t0 = time.perf_counter()
    cfg, opt, lr, algo, train_step, rigl_step, compiled = _programs(
        json.dumps(conf, sort_keys=True),
        make_train_step or program_train_step,
        make_rigl_step or program_rigl_step)
    key = harness.seed_key(seed, "weights")
    data_key = harness.seed_key(seed, "data")
    params, masks = harness.make_weights(conf, key)
    state = build_state(cfg, opt, params, masks, tr["first_step"],
                        harness.seed_key(seed, "rng"))
    del params, masks
    tokens_per_step = data["batch"] * data["seq"]

    def batch(step):
        return token_batches.batch(data, data_key, step, cfg.vocab_size)

    # the checked steps, through the window's own call and feed
    step = tr["first_step"]
    losses = []
    for j in range(CHECKED_STEPS):
        state, mt = train_step(state, batch(step))
        losses.append(float(mt["loss"]))
        if j == 0:
            first = jax.jit(lambda o: leaf_norms(o, 1.0 / (1.0 - opt.b1)))(
                state["opt"]["m"])
            first = {k: float(x) for k, x in first.items()}
        step += 1
    start_params, _ = harness.make_weights(conf, key)
    change = jax.jit(lambda a, b: leaf_norms(
        jax.tree_util.tree_map(lambda x, y: x - y, a, b)))(
            state["params"], start_params)
    change = {k: float(x) for k, x in change.items()}
    del start_params
    # the topology the window's update starts from, and its candidates
    before = pool_blocks(state["masks"], block)
    superset = pool_blocks(state["bwd_masks"], block)

    # RigL's update step, compiled ahead for this state's shapes, and one
    # pack refresh whose result is dropped: the window's first step is the
    # topology update at step ``first_step + 3``, and nothing compiles there
    if "rigl" not in compiled:
        compiled["rigl"] = rigl_step.lower(state, batch(step)).compile()
    rigl_step = compiled["rigl"]
    jax.block_until_ready(refresh_pack(state, cfg)["pack"])
    setup_s = time.perf_counter() - t0
    harness.log(phase="setup", setup_s=setup_s, first_window_step=step,
                update_in_window_every=cfg.sparse.delta_t,
                **clock_compiles.take())

    # the window
    tracer = reduce.Tracer(spec["trace"], seconds) if trace else None
    nonfinite0 = int(state["nonfinite_steps"])
    update_step = step
    after = None  # the masks the window's first update left, block by block
    spans = []  # (kind, t0, t1) on the window's clock
    n_steps = 0
    prev = None
    start = time.perf_counter()
    clock = lambda: time.perf_counter() - start
    while clock() < seconds:
        if tracer is not None:
            tracer.on_step(clock())
        ts = clock()
        b = batch(step)
        if _is_update(cfg, algo, step):
            with jax.profiler.TraceAnnotation("chipbench.update"):
                state, mt = rigl_step(state, b)
                state = refresh_pack(state, cfg)
                if after is None:
                    after = pool_blocks(state["masks"], block)
            spans.append(("update", ts, clock()))
        else:
            with jax.profiler.TraceAnnotation("chipbench.step"):
                state, mt = train_step(state, b)
                if prev is not None:
                    prev.block_until_ready()
            spans.append(("step", ts, clock()))
        prev = mt["loss"]
        step += 1
        n_steps += 1
    jax.block_until_ready(state)
    window_s = clock()
    window_compiles = clock_compiles.take()
    failed = int(state["nonfinite_steps"]) - nonfinite0
    stale = int(mt.get("pack_stale", 0))
    tokens_per_s = n_steps * tokens_per_step / window_s
    update_s = sum(e - s for k, s, e in spans if k == "update")
    device = harness.device_info(devices)
    harness.log(phase="window", steps=n_steps,
                updates=sum(k == "update" for k, _, _ in spans),
                window_s=window_s, update_s=update_s, last_step=step,
                **{"window_" + k: v for k, v in window_compiles.items()})
    metrics = {}
    breakdown = None
    if trace:
        red = tracer.reduced(window_s)
        ctx = {"conf": conf, "trace": red,
               "peaks": harness.peaks(device["kind"]),
               "window": {"seconds": window_s, "steps": n_steps,
                          "tokens_per_step": tokens_per_step,
                          "tokens_per_s": tokens_per_s, "update_s": update_s,
                          "seq": data["seq"]}}
        metrics = reduce.read_metrics(spec, ctx)
        device.update(busy_s=red.busy_s(), window_s=red.window_s())
        breakdown = red.breakdown()
    else:
        metrics = {"train_tokens_per_s": {"value": tokens_per_s,
                                          "unit": "tokens/s"},
                   "setup_s": {"value": setup_s, "unit": "s"}}

    masks_of = lambda d: {k: x > 0 for k, x in host_blocks(d).items()}
    before, superset = masks_of(before), masks_of(superset)
    # no update in the window leaves the masks as they were
    after = before if after is None else masks_of(after)
    # the program's state goes before the reference runs
    del state, mt, prev
    gc.collect()
    live = sum(a.nbytes for a in jax.live_arrays())
    t_ref = time.perf_counter()
    params, masks = harness.make_weights(conf, key)
    batches = [batch(tr["first_step"] + j) for j in range(CHECKED_STEPS)]
    want = reference_steps(conf, params, masks, batches, tr["optimizer"], lr,
                           tr["first_step"], update=batch(update_step))
    topo = {"before": before, "superset": superset,
            "ref_masks": {k: x > 0 for k, x in host_blocks(
                pool_blocks(masks, block)).items()},
            "scores": want["scores"],
            "backward_extra": conf["sparse"]["backward_extra"]}
    fraction = update_fraction(conf, update_step)
    ref_update = reference_update(before, superset, want["mags"],
                                  want["scores"], fraction)
    got = {"loss": losses, "grad": first, "change": change}
    readings = dict(compare(got, want), **topology(after, topo, ref_update))
    harness.log(phase="reference", reference_s=time.perf_counter() - t_ref,
                live_bytes_before=live, update_fraction=fraction,
                update_blocks=sum(k for k, _ in ref_update.values()),
                losses=losses, reference_losses=want["loss"], **readings)
    limits = spec["check"]["limits"]
    if control:
        # the reference in lower precision put in the program's place: its
        # steps, and its own update from its own gradient
        low = reference_steps(conf, params, masks, batches, tr["optimizer"],
                              lr, tr["first_step"], precision=control,
                              update=batch(update_step))
        low_update = reference_update(before, superset, low["mags"],
                                      low["scores"], fraction)
        readings = dict(compare(low, want), **topology(
            {k: m for k, (_, m) in low_update.items()}, topo, ref_update))
        harness.log(phase="control", precision=control, **readings)
        for name, fault in faults(before, superset, ref_update, seed).items():
            r = topology(fault, topo, ref_update)
            harness.log(phase="fault_" + name, **r,
                        correct=all(r[k] <= limits[k] for k in r))
        # half of each batch left out, the mean taken over the rest
        half = [{k: v[: v.shape[0] // 2] for k, v in b.items()}
                for b in batches]
        r = compare(reference_steps(conf, params, masks, half,
                                    tr["optimizer"], lr, tr["first_step"]),
                    want)
        harness.log(phase="fault_half_batch", **r, correct=all(
            r[k] <= limits[k] for k in ("loss", "grad", "change")))
    checks = {k: {"value": readings[k], "limit": limits[k]}
              for k in ("loss", "grad", "change", "topology_counts",
                        "update_size", "grow_order")}
    checks["pack_stale"] = {"value": stale, "limit": 0}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    result = {"correct": correct, "attempted": n_steps, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def faults(before, superset, ref_update, seed) -> dict:
    """Masks that faulty updates would leave, made from the reference's
    update: one that leaves every mask as it was, and one that grows the
    right number of blocks, drawn at random from the superset."""
    rng = np.random.default_rng([seed, 11])
    altered = {}
    for name, (_, ref_after) in ref_update.items():
        b, cand = before[name], superset[name] & ~before[name]
        new = ref_after & ~b
        pick = rng.choice(np.flatnonzero(cand), int(new.sum()), replace=False)
        out = (ref_after & b).reshape(-1)
        out[pick] = True
        altered[name] = out.reshape(b.shape)
    return {"masks_unchanged": dict(before), "grow_altered": altered}


def compare(got: dict, want: dict) -> dict:
    loss = max(abs(a - b) / abs(b) for a, b in zip(got["loss"], want["loss"]))
    grad, grad_leaf = gap(got["grad"], want["grad"])
    change, change_leaf = gap(got["change"], want["change"])
    return {"loss": loss, "grad": grad, "change": change,
            "grad_leaf": grad_leaf, "change_leaf": change_leaf}
