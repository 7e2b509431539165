"""Elastic restart: checkpoint on one device topology, resume on another.

  PYTHONPATH=src python examples/elastic_restart.py

Phase 1 trains on 1 device and checkpoints. Phase 2 (8 virtual CPU
devices) restores the SAME checkpoint onto a 2x4 (data x model) mesh via
restore(shardings=...) and continues training — the cluster shrank/grew and
training just continues.  Each phase is a child process and this parent never
imports JAX, so on a TPU host phase 1 holds the chip alone and releases it.
"""
import os
import pathlib
import subprocess
import sys
import tempfile
import textwrap

workdir = pathlib.Path(tempfile.mkdtemp(prefix="elastic_"))
env = dict(os.environ)
env["PYTHONPATH"] = str(pathlib.Path(__file__).resolve().parents[1] / "src")

PHASE1 = textwrap.dedent("""
    import dataclasses, jax
    from repro.configs import get_config
    from repro.configs.base import SparseConfig
    from repro.launch.train import train_loop
    cfg = dataclasses.replace(get_config("h2o-danube-1.8b", smoke=True),
                              sparse=SparseConfig(sparsity=0.8, delta_t=20))
    train_loop(cfg, steps=40, batch=8, seq=64, workdir=r"%s", ckpt_every=20, log_every=20)
    print("phase1 devices:", len(jax.devices()))
""")

PHASE2 = textwrap.dedent("""
    import os
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, jax
    from repro.configs import get_config
    from repro.configs.base import SparseConfig
    from repro.checkpoint import restore
    from repro.data import batch_for
    from repro.launch.mesh import make_local_mesh
    from repro.launch.sharding import batch_shardings, state_shardings
    from repro.optim import LRSchedule, OptConfig
    from repro.training import init_train_state, make_train_step

    cfg = dataclasses.replace(get_config("h2o-danube-1.8b", smoke=True),
                              sparse=SparseConfig(sparsity=0.8, delta_t=20))
    opt = OptConfig(kind="adam", grad_clip=1.0, weight_decay=0.0)
    like, axes, _ = init_train_state(jax.random.PRNGKey(0), cfg, opt)
    mesh = make_local_mesh(2, 4)
    sh = state_shardings(like, axes, mesh)
    state, step = restore(like, r"%s/ckpt", shardings=sh)
    print(f"phase2: restored step {step} onto {len(jax.devices())} devices, mesh {dict(mesh.shape)}")
    fn = jax.jit(make_train_step(cfg, opt, LRSchedule(base_lr=1e-3)))
    for t in range(step, step + 10):
        b = jax.device_put(batch_for(cfg, t, 8, 64, learnable=True), batch_shardings(
            batch_for(cfg, t, 8, 64, learnable=True), mesh))
        state, m = fn(state, b)
    print(f"phase2: continued to step {int(state['step'])} loss {float(m['loss']):.4f}")
""")

for i, script in enumerate((PHASE1 % workdir, PHASE2 % workdir), 1):
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=900)
    if out.returncode != 0:
        print(out.stderr[-2000:])
        sys.exit(1)
    print("\n".join(l for l in out.stdout.splitlines() if "phase" in l or "train" in l))
print("elastic restart OK: 1 device -> 2x4 mesh")
