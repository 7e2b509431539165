"""RigL's check covers grouped expert banks: on the CPU at the
qwen2-moe-a2.7b smoke size (6 experts, moe_d_ff 32, block 16), the harness's
reference update, made from the magnitudes and gradient scores the program
ranks, leaves what the program's ``rigl_update`` leaves, bank by bank, and
faults planted in a bank read over the training cell's limits."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness
from repro.configs import get_config
from repro.configs.base import SparseConfig
from repro.core import rigl_update, topkast_backward_masks
from repro.training.steps import init_weights, make_algo

BLOCK = 16
STEP = 100  # the training cell's update step
# At the cell's 0.8, ERK leaves a smoke bank 4 of its 48 blocks and one to
# regrow; at 0.5 each bank regrows several, spread over its experts.
SPARSITY = 0.5


def _train():
    return harness.module("", "train")


@pytest.fixture(scope="module")
def update():
    """The program's update of block masks that hold the expert banks, and
    the harness's pooled view of it and its reference update."""
    train = _train()
    conf = harness.cell("danube-train.rigl")["conf"]
    sp = conf["sparse"]
    cfg = dataclasses.replace(
        get_config("qwen2-moe-a2.7b", smoke=True),
        sparse=SparseConfig(
            sparsity=SPARSITY, distribution=sp["distribution"],
            method=sp["method"], backward_extra=sp["backward_extra"],
            delta_t=sp["delta_t"], alpha=sp["alpha"],
            t_end_fraction=sp["t_end_fraction"], kernel="block_sparse",
            block_shape=(BLOCK, BLOCK), kernel_block=(BLOCK,) * 3))
    assert (cfg.n_experts, cfg.moe_d_ff) == (6, 32)
    key = jax.random.PRNGKey(7)
    params, masks, _, _ = init_weights(key, jax.random.fold_in(key, 1), cfg)
    bwd = topkast_backward_masks(params, masks, sp["backward_extra"],
                                 jax.random.fold_in(key, 2),
                                 block_shape=cfg.sparse.block_shape)
    leaves = jax.tree_util.tree_leaves(params)
    tdef = jax.tree_util.tree_structure(params)
    grads = jax.tree_util.tree_unflatten(tdef, [
        jax.random.normal(jax.random.fold_in(key, 10 + i), x.shape)
        for i, x in enumerate(leaves)])
    # the program grows by the gradient its superset carries, the harness by
    # the dense gradient restricted to the superset
    carried = jax.tree_util.tree_map(
        lambda g, b: g if b is None else g * b, grads, bwd,
        is_leaf=lambda x: x is None)
    algo = make_algo(cfg, conf["train"]["lr"]["total_steps"])
    fraction = train.update_fraction(conf, STEP)
    assert fraction == np.float32(algo.schedule.fraction(jnp.int32(STEP)))
    _, new_masks, _ = rigl_update(params, masks, carried, jnp.int32(STEP),
                                  algo, jax.random.fold_in(key, 3),
                                  bwd_masks=bwd)
    blocks = lambda tree, only=None: {
        k: x > 0 for k, x in train.host_blocks(
            train.pool_blocks(tree, BLOCK, only)).items()}
    before, superset, after = blocks(masks), blocks(bwd), blocks(new_masks)
    sparse = set(before)
    mags = train.host_blocks(train.pool_blocks(params, BLOCK, sparse))
    scores = train.host_blocks(train.pool_blocks(grads, BLOCK, sparse))
    ref_update = train.reference_update(before, superset, mags, scores,
                                        fraction)
    topo = {"before": before, "superset": superset, "ref_masks": before,
            "scores": scores, "backward_extra": sp["backward_extra"]}
    return {"masks": masks, "before": before, "superset": superset,
            "after": after, "ref_update": ref_update, "topo": topo}


def _banks(u):
    return [k for k, x in u["before"].items() if x.ndim == 3]


def test_check_pools_every_sparse_leaf_banks_included(update):
    flat = jax.tree_util.tree_flatten_with_path(update["masks"])[0]
    want = {jax.tree_util.keystr(p) for p, x in flat if x is not None}
    assert set(update["before"]) == want
    banks = _banks(update)
    assert len(banks) == 3 * get_config("qwen2-moe-a2.7b", smoke=True).n_layers
    assert all(update["before"][k].shape[0] == 6 for k in banks)


def test_program_update_of_banks_reads_zero(update):
    r = _train().topology(update["after"], update["topo"],
                          update["ref_update"])
    assert r == {"topology_counts": 0, "update_size": 0.0, "grow_order": 0.0}
    # the banks were updated, not carried through unchanged
    assert any((update["after"][k] != update["before"][k]).any()
               for k in _banks(update))


def _one_expert_unchanged(u, seed):
    """Of the bank with the most blocks grown in one expert, that expert's
    blocks left as they were (``seed`` is not used)."""
    after = {k: m for k, (_, m) in u["ref_update"].items()}
    grown = {(k, e): int((after[k][e] & ~u["before"][k][e]).sum())
             for k in _banks(u) for e in range(after[k].shape[0])}
    k, e = max(grown, key=grown.get)
    after[k] = after[k].copy()
    after[k][e] = u["before"][k][e]
    return after


def _random_grow_in_banks(u, seed):
    """``faults()``'s random grow, in the banks only."""
    after = {k: m for k, (_, m) in u["ref_update"].items()}
    altered = _train().faults(u["before"], u["superset"], u["ref_update"],
                              seed)["grow_altered"]
    after.update({k: altered[k] for k in _banks(u)})
    return after


@pytest.mark.parametrize("fault,seed", [(_one_expert_unchanged, 0),
                                        (_random_grow_in_banks, 1),
                                        (_random_grow_in_banks, 2)])
def test_bank_faults_read_over_the_limits(update, fault, seed):
    limits = harness.cell("danube-train.rigl")["check"]["limits"]
    r = _train().topology(fault(update, seed), update["topo"],
                          update["ref_update"])
    assert any(r[k] > limits[k] for k in r), r


@pytest.mark.parametrize("shape", [(24, 32), (2, 32, 24), (32,)])
def test_leaf_that_cannot_be_pooled_raises(shape):
    tree = {"bank": {"w": jnp.zeros(shape)}}
    with pytest.raises(ValueError, match=r"\['bank'\]\['w'\]"):
        _train().pool_blocks(tree, BLOCK)
