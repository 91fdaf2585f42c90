"""Production meshes.

Single pod: 16x16 = 256 chips (data x model).
Multi-pod:  2x16x16 = 512 chips (pod x data x model) — the 'pod' axis is pure
data parallelism across pods (gradient all-reduce crosses the inter-pod
links once per step); 'model' carries tensor/expert parallelism inside a pod.

Defined as FUNCTIONS so importing this module never touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model_parallel: int = 1):
    """A mesh over whatever devices exist (CPU smoke tests / examples)."""
    n = len(jax.devices())
    mp = model_parallel if n % model_parallel == 0 else 1
    return jax.make_mesh((n // mp, mp), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
