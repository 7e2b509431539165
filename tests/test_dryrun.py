"""Dry-run machinery: HLO collective parser + a mini-mesh cell (subprocess)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.analysis.hlo import collective_bytes
from repro.analysis.roofline import roofline_terms


def test_collective_parser_kinds_and_groups():
    hlo = """
  %all-reduce.5 = f32[2,4096,2560]{2,1,0} all-reduce(%fusion.1), channel_id=5, replica_groups=[4,2]<=[8], use_global_device_ids=true, to_apply=%add.1
  %all-gather.2 = bf16[8,128]{1,0} all-gather(%p.2), channel_id=3, replica_groups=[2,4]<=[8], dimensions={0}
  %reduce-scatter.1 = f32[16]{0} reduce-scatter(%x), channel_id=9, replica_groups=[1,8]<=[8], to_apply=%add
  %all-reduce-start.1 = f32[4]{0} all-reduce-start(%y), channel_id=11, replica_groups=[1,8]<=[8], to_apply=%add
  %all-reduce-done.1 = f32[4]{0} all-reduce-done(%all-reduce-start.1)
    """
    cb = collective_bytes(hlo)
    assert cb["all-reduce"] == 2 * 4096 * 2560 * 4 + 4 * 4  # incl. -start once
    assert cb["all-gather"] == 8 * 128 * 2 // 4  # operand = result / group(4)
    assert cb["reduce-scatter"] == 16 * 4 * 8  # operand = result * group(8)
    assert cb["total"] == sum(v for k, v in cb.items() if k != "total")


def test_roofline_terms_dominance():
    r = roofline_terms(1e15, 1e12, 1e9, chips=256, model_flops_total=6e17)
    assert r["dominant"] == "compute"
    assert r["compute_s"] == pytest.approx(1e15 / 197e12)
    r2 = roofline_terms(1e12, 1e13, 1e9, chips=256)
    assert r2["dominant"] == "memory"


MINI = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    from repro.launch import dryrun_lib
    from repro.launch.mesh import make_local_mesh
    mesh = make_local_mesh(2, 4)
    art = dryrun_lib.run_cell("h2o-danube-1.8b", "train_4k", mesh, save=False,
                              cfg_overrides={"n_layers": 2, "microbatches": 1})
    print(json.dumps({
        "flops": art["per_device"]["flops"],
        "coll": art["per_device"]["coll"],
        "dominant": art["roofline"]["dominant"],
        "fits": art["memory"]["fits_16g_hbm"],
    }))
""")


@pytest.mark.slow
def test_mini_mesh_cell():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    out = subprocess.run([sys.executable, "-c", MINI], capture_output=True,
                         text=True, env=env, timeout=1800)
    assert out.returncode == 0, out.stderr[-3000:]
    d = json.loads(out.stdout.strip().splitlines()[-1])
    assert d["flops"] > 1e9
    assert d["coll"] > 0, "DP/TP must produce collectives"
    assert d["dominant"] in ("compute", "memory", "collective")
