"""What every cell shares: files, the device, compilation, seeds, results.

The harness is driven by data.  ``BENCHMARK.json`` names the cells and
metrics; a cell's file is ``chipbench/workloads/<cell>.json``, its
configuration ``chipbench/configs/<config>.json``, its traffic generator
``chipbench/traffic/<kind>.py`` and its driver ``chipbench/<driver>.py``;
a per-layer metric is read by ``chipbench/metrics/<name>.py``.

A configuration names its plain reference, ``chipbench/reference/<name>.py``
(``reference``), which imports nothing of the program.  ``m`` below is the
configuration's ``model``, ``block`` and ``sparsity`` its ``sparse``
settings.  A training cell and the work counts (``chipbench/work.py``) take
from it:

- ``make_weights(key, mask_key, m, sparsity, block) -> (params, masks)``,
  traced under ``jax.jit`` with all but the two raw keys static: float32
  weights from ``key`` in the program's parameter layout, and a mask tree
  of the same structure whose leaf is ``None`` for a dense weight and, for
  a sparse one, a bool array of its shape made of whole ``block`` x
  ``block`` blocks of its last two dims (any leading dims are the groups of
  a bank, each with its own blocks), drawn from ``mask_key`` with
  ``erk_blocks``' counts in every layer; weights off the mask are zero;
- ``loss(params, masks, m, tokens, targets, precision) -> float32 scalar``:
  the mean next-token cross-entropy of (B, S) int32 ``tokens`` against
  ``targets``, with the weights as ``w * mask``; ``precision`` is ``"f32"``
  (float32 at ``highest``) or the control's lower one (``"fp8"``);
- ``layer_shapes(m) -> {matrix: (k, n) or (groups, k, n)}``: the sparse
  matrices of one layer, the same in every layer;
- ``erk_blocks(m, sparsity, block) -> {matrix: int}``: active blocks of
  each, summed over a bank's groups;
- optional ``rows_per_token(m) -> {matrix: number}``: for a matrix whose
  products do not see every token, the rows each group's product sees per
  token (a bank of experts with k of E routed per token: k / E); a matrix
  it leaves out sees every token once;
- optional ``attn_windows(m) -> [int]``: the window of each attention
  layer, 0 for the whole causal context; without it, ``m["window"]`` in
  each of ``m["n_layers"]`` layers.

The serving driver also runs the reference layer by layer (``_layer``,
``_rmsnorm``, ``logits``; ``chipbench/serve.py::reference_gaps``).
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import math
import pathlib
import sys

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
CHECKOUT = HERE.parent
CACHE_DIR = CHECKOUT / ".jax_cache"


def load_json(path) -> dict:
    return json.loads(pathlib.Path(path).read_text())


def benchmark() -> dict:
    return load_json(CHECKOUT / "BENCHMARK.json")


def cell(name: str, bench: dict | None = None) -> dict:
    """The cell's entry in BENCHMARK.json merged with its own file, and its
    configuration's file under ``conf``."""
    bench = bench or benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"chipbench: no workload {name!r} in BENCHMARK.json")
    spec = dict(load_json(HERE / "workloads" / f"{name}.json"), **entry)
    spec["conf"] = load_json(HERE / "configs" / f"{entry['config']}.json")
    spec["end_to_end"] = [
        m for m in bench["end_to_end"]
        if name in m.get("workloads", [name])]
    spec["per_layer"] = [
        m for m in bench["per_layer"]
        if name in m.get("workloads", [name])]
    return spec


def module(kind: str, name: str):
    """chipbench/<kind>/<name>.py (or chipbench/<name>.py for kind '')."""
    path = HERE / kind / f"{name}.py" if kind else HERE / f"{name}.py"
    mod_name = f"chipbench_{kind}_{name}".replace(".", "_").replace("-", "_")
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


# --------------------------------------------------------------------------
# device, compilation
# --------------------------------------------------------------------------

def device_gate(chips: int):
    """The cell's devices; exits non-zero without a TPU or enough chips."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chipbench: needs a TPU; JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        raise SystemExit(
            f"chipbench: needs {chips} chips; JAX found {len(devices)}")
    return devices[:chips]


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout, for every
    program, however quickly it compiled, with no size cap: a capped cache
    evicts a cell's larger programs and compiles them again every run."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return str(CACHE_DIR)


class CompileClock:
    """XLA compile requests, their seconds (cache reads included) and the
    persistent-cache hits among them, from JAX's monitoring events;
    ``take`` returns the counts since the last take."""

    def __init__(self):
        import jax

        self._reset()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _reset(self):
        self.seconds, self.requests, self.cache_hits = 0.0, 0, 0

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.requests += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def take(self) -> dict:
        out = {"compile_s": self.seconds, "compiles": self.requests,
               "cache_hits": self.cache_hits}
        self._reset()
        return out


def device_info(devices) -> dict:
    d = devices[0]
    peak = [(x.memory_stats() or {}).get("peak_bytes_in_use") for x in devices]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices),
            "memory_peak_bytes": max((p for p in peak if p is not None),
                                     default=None)}


def peaks(kind: str) -> dict:
    table = load_json(HERE / "peaks.json")["devices"]
    if kind not in table:
        raise SystemExit(f"chipbench: no peaks for device kind {kind!r} in "
                         "chipbench/peaks.json")
    return table[kind]


# --------------------------------------------------------------------------
# configurations, seeds, weights
# --------------------------------------------------------------------------

def model_config(conf: dict):
    """The program's ModelConfig for a configuration file: the repo's
    config for ``arch`` with the file's sizes and settings in place."""
    from repro.configs import get_config
    from repro.configs.base import SparseConfig

    base = get_config(conf["arch"])
    fields = {f.name for f in dataclasses.fields(base)}
    model = {k: v for k, v in conf["model"].items() if k in fields}
    extra = set(conf["model"]) - fields - {"embed_scale"}
    if extra:
        raise SystemExit(f"chipbench: unknown model keys {sorted(extra)}")
    if not math.isclose(conf["model"]["embed_scale"],
                        math.sqrt(conf["model"]["d_model"])):
        raise SystemExit("chipbench: the program scales embeddings by "
                         "sqrt(d_model); embed_scale must say so")
    sp = dict(conf["sparse"])
    block = sp.pop("block")
    sp.pop("mask_seed")
    sparse = SparseConfig(block_shape=(block, block),
                          kernel_block=(block, block, block), **sp)
    return dataclasses.replace(base, **model, **conf.get("program", {}),
                               sparse=sparse)


def seed_key(seed: int, purpose: str):
    """A raw (2,) uint32 JAX key for one use of the run's seed (any whole
    number >= 0): the seed's full width reaches the key."""
    import jax.numpy as jnp

    words = np.random.SeedSequence(
        [seed, *purpose.encode()]).generate_state(2)
    return jnp.asarray(words, jnp.uint32)


def reference(conf: dict):
    return importlib.import_module(f"chipbench.reference.{conf['reference']}")


def make_weights(conf: dict, key):
    """(params, masks) in one jitted call: weights from the seed's key, the
    block topology from the configuration's ``mask_seed``.  The topology
    sets the packed kernel grids' shapes, so a fixed one lets every run of
    a cell find its programs in the cache."""
    import jax

    ref = reference(conf)
    sp = conf["sparse"]
    mask_key = seed_key(sp["mask_seed"], "masks")
    fn = jax.jit(lambda k, mk: ref.make_weights(
        k, mk, conf["model"], sp["sparsity"], sp["block"]))
    params, masks = fn(key, mask_key)
    jax.block_until_ready((params, masks))
    return params, masks


# --------------------------------------------------------------------------
# statistics, results
# --------------------------------------------------------------------------

def nearest_rank(values, p: float) -> float:
    """The p-th percentile by nearest rank: the smallest value with at
    least p% of the sample at or below it.  +inf values count as such."""
    v = sorted(values)
    if not v:
        return math.nan
    return float(v[max(0, math.ceil(p / 100.0 * len(v)) - 1)])


def log(**kw) -> None:
    """An earlier line of the run's standard output."""
    print(json.dumps(kw, default=float), flush=True)


def finish(result: dict) -> int:
    """Print the checks as the last lines of standard error, then the
    result as the last line of standard output."""
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
