"""Production meshes.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the batch is additionally
sharded over the slow inter-pod axis, while TP and FSDP stay *intra-pod* so
every weight collective rides the fast ICI links.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — smoke tests see 1 CPU device, the dry-run
sets XLA_FLAGS=--xla_force_host_platform_device_count=512 before first init.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_local_mesh", "dp_axes"]


def _mesh(shape, axes):
    # Auto axes: the model is written for GSPMD propagation (shardings from
    # launch/sharding.py on the inputs, none in the types); jax.make_mesh's
    # default Explicit axes would demand an out_sharding on every ambiguous
    # gather and reshape
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_local_mesh(n_data: int = 1, n_model: int = 1):
    """Small mesh over whatever devices exist (tests / CPU runs)."""
    return _mesh((n_data, n_model), ("data", "model"))


def dp_axes(mesh) -> tuple[str, ...]:
    """Mesh axes carrying the batch dimension."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
