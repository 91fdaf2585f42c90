"""Minimal pure-functional parameter system.

Params are nested dicts of jnp arrays. Every model declares a *spec tree* of
`ParamSpec(shape, dtype, axes, init)` where `axes` are logical sharding axes
('data' / 'model' / 'expert' / None per dim); `init_from_specs` materializes
real arrays (smoke tests / training), `init_sharded` the same arrays placed
by those axes on a mesh (launchers, serving), `abstract_from_specs`
ShapeDtypeStructs with NamedShardings (dry-run: no allocation).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: tuple[int, ...]
    dtype: Any = jnp.bfloat16
    axes: tuple[str | None, ...] | None = None   # logical sharding per dim
    init: str = "normal"                          # normal | zeros | ones
    scale: float | None = None                    # None -> 1/sqrt(fan_in)

    def __post_init__(self):
        if self.axes is not None and len(self.axes) != len(self.shape):
            raise ValueError(f"axes {self.axes} rank != shape {self.shape}")


def is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def spec_tree_map(fn: Callable[[ParamSpec], Any], tree):
    return jax.tree.map(fn, tree, is_leaf=is_spec)


def init_from_specs(specs, key: jax.Array, dtype_override=None):
    """Materialize a spec tree into real parameter arrays (deterministic)."""
    leaves, treedef = jax.tree.flatten(specs, is_leaf=is_spec)
    out = []
    for i, spec in enumerate(leaves):
        k = jax.random.fold_in(key, i)
        dtype = dtype_override or spec.dtype
        if spec.init == "zeros":
            out.append(jnp.zeros(spec.shape, dtype))
        elif spec.init == "ones":
            out.append(jnp.ones(spec.shape, dtype))
        else:
            fan_in = spec.shape[0] if len(spec.shape) == 1 else math.prod(spec.shape[:-1])
            scale = spec.scale if spec.scale is not None else 1.0 / math.sqrt(max(fan_in, 1))
            out.append((jax.random.normal(k, spec.shape, jnp.float32) * scale).astype(dtype))
    return jax.tree.unflatten(treedef, out)


def init_sharded(specs, key: jax.Array, mesh):
    """`init_from_specs` as one program whose outputs carry each leaf's
    `NamedSharding` on `mesh`: every device draws only its own shards (no
    stacking on device 0), and the float32 draw fuses into the cast instead
    of a whole float32 leaf sitting on the device. The values are those of
    `init_from_specs` up to the rounding of a last bit in the fused cast."""
    from repro.sharding.rules import tree_shardings
    return jax.jit(lambda k: init_from_specs(specs, k),
                   out_shardings=tree_shardings(specs, mesh))(key)


def abstract_from_specs(specs):
    """ShapeDtypeStruct tree (no device allocation) for .lower()."""
    return spec_tree_map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), specs)


def count_params(specs) -> int:
    leaves = jax.tree.leaves(specs, is_leaf=is_spec)
    return int(sum(math.prod(s.shape) for s in leaves))


def param_bytes(specs) -> int:
    leaves = jax.tree.leaves(specs, is_leaf=is_spec)
    return int(sum(math.prod(s.shape) * np.dtype(s.dtype).itemsize for s in leaves))


def stacked(spec: ParamSpec, n: int) -> ParamSpec:
    """Stack a per-layer spec along a leading layer axis (for lax.scan)."""
    axes = (None,) + spec.axes if spec.axes is not None else None
    return dataclasses.replace(spec, shape=(n,) + spec.shape, axes=axes)


def stack_specs(specs, n: int):
    return spec_tree_map(lambda s: stacked(s, n), specs)
