"""Sharding resolver unit tests + multi-device equivalence (subprocess)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.launch.sharding import resolve_spec


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


MESH = FakeMesh({"data": 16, "model": 16})
POD = FakeMesh({"pod": 2, "data": 16, "model": 16})


def test_heads_sharded_when_divisible():
    spec = resolve_spec(("embed", "heads"), (2560, 8192), MESH)
    assert spec[1] == "model" and spec[0] is None


def test_fused_head_dim_shards_when_divisible():
    # internvl2: 14 heads x 64 = 896 IS divisible by 16 (mid-head split —
    # GSPMD reshards at the head reshape; compiles for every cell)
    spec = resolve_spec(("embed", "heads"), (896, 896), MESH)
    assert spec[1] == "model"


def test_nondivisible_dim_replicated():
    spec = resolve_spec(("embed", "heads"), (100, 100), MESH)
    assert spec == (None, None)


def test_experts_get_model_axis_when_divisible():
    spec = resolve_spec(("experts", "embed", "moe_mlp"), (16, 1024, 4096), MESH)
    assert spec[0] == "model" and spec[2] is None  # model used once


def test_grok_fallback_intra_expert_tp():
    # 8 experts don't divide 16 -> ff dim gets the model axis instead
    spec = resolve_spec(("experts", "embed", "moe_mlp"), (8, 6144, 32768), MESH)
    assert spec[0] is None and spec[2] == "model"


def test_fsdp_shards_embed_dim():
    spec = resolve_spec(("embed", "mlp"), (12288, 28672), MESH, fsdp=True)
    assert spec == ("data", "model")


def test_fsdp_skips_tiny_vectors():
    spec = resolve_spec(("embed",), (2560,), MESH, fsdp=True)
    assert spec == (None,)


def test_kv_seq_fallback_for_nondivisible_kv_heads():
    # mistral decode: kv=8 not divisible by model=16 -> shard cache seq dim
    spec = resolve_spec(
        ("act_batch", "act_kv_seq", "kv_heads", "head_dim"),
        (128, 32768, 8, 128),
        MESH,
    )
    assert spec[0] == "data" and spec[1] == "model" and spec[2] is None


def test_long_context_batch1_uses_all_axes_for_seq():
    spec = resolve_spec(
        ("act_batch", "act_kv_seq", "kv_heads", "head_dim"),
        (1, 524288, 8, 80),
        MESH,
    )
    assert spec[1] == ("data", "model")


def test_multipod_batch_over_pod_and_data():
    spec = resolve_spec(("act_batch", None, None), (256, 4096, 896), POD)
    assert spec[0] == ("pod", "data")


DIST_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, json
    import jax, jax.numpy as jnp
    from repro.configs import get_config
    from repro.configs.base import SparseConfig
    from repro.data import batch_for
    from repro.launch.mesh import make_local_mesh
    from repro.launch.sharding import batch_shardings, state_shardings
    from repro.optim import LRSchedule, OptConfig
    from repro.training import init_train_state, make_train_step

    cfg = get_config("h2o-danube-1.8b", smoke=True)
    cfg = dataclasses.replace(cfg, dtype="float32",
                              sparse=SparseConfig(sparsity=0.5))
    opt = OptConfig(kind="sgd", momentum=0.9, weight_decay=0.0)
    lr = LRSchedule(kind="constant", base_lr=1e-2, warmup_steps=0)

    def run(mesh_shape):
        state, axes, _ = init_train_state(jax.random.PRNGKey(0), cfg, opt)
        losses = []
        step = make_train_step(cfg, opt, lr)
        if mesh_shape:
            mesh = make_local_mesh(*mesh_shape)
            st_sh = state_shardings(state, axes, mesh)
            state = jax.device_put(state, st_sh)
            fn = jax.jit(step)
        else:
            fn = jax.jit(step)
        for t in range(5):
            b = batch_for(cfg, t, 8, 64, learnable=True)
            if mesh_shape:
                b = jax.device_put(b, batch_shardings(b, mesh))
            state, m = fn(state, b)
            losses.append(float(m["loss"]))
        return losses

    single = run(None)
    multi = run((2, 4))
    print(json.dumps({"single": single, "multi": multi}))
    """
)


@pytest.mark.slow
def test_distributed_matches_single_device(tmp_path):
    """DP=2 x TP=4 must reproduce single-device training losses."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    out = subprocess.run(
        [sys.executable, "-c", DIST_SCRIPT],
        capture_output=True,
        text=True,
        env=env,
        timeout=900,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    data = json.loads(out.stdout.strip().splitlines()[-1])
    for a, b in zip(data["single"], data["multi"]):
        assert a == pytest.approx(b, rel=2e-3), data
