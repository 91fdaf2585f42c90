"""Pallas wavefront resource-update kernel for the batched fitness path.

One step of `repro.core.vectorized.BatchedFitness` must FCFS-serialize the
current wavefront's items on every contended resource (cores, bus/link
channels, the DRAM port) for every genome of the population at once: a
`(P x R)` block of independent queues, each served in a fixed item order.
The queue recurrence ``f_k = max(f_{k-1}, r_k) + d_k`` is associative once
rewritten over prefix sums (see `repro.kernels.ref.serialize_prefix_ref`),
so the whole update is cumsum/cummax/add over the item axis — exactly the
row-block shape Pallas wants: each grid step loads a `(rows, W)` tile of
release/duration rows plus its `(rows, 1)` availability column into VMEM
and writes the serialized finish times back.

Mosaic has no lowering for `cumsum`/`cummax`, so both prefix ops are
in-register Hillis-Steele scans: ceil(log2 W) steps of a lane rotation
(`pltpu.roll`) masked by an iota. The adds happen in exactly the order of
`repro.kernels.ref.prefix_sum`, so kernel and reference agree bit for bit.
The kernel is interpreted only on the CPU (`repro.backend.pallas_interpret`)
and compiles natively everywhere else.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.backend import pallas_interpret

NEG = -1e30     # prefix-max identity ("not queued"), as in the reference


def _scan(x, op, identity):
    """Inclusive prefix `op` over the lane axis of a (rows, W) tile."""
    w = x.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    k = 1
    while k < w:
        x = op(x, jnp.where(lane >= k, pltpu.roll(x, k, 1), identity))
        k *= 2
    return x


def _serialize_kernel(free_ref, rel_ref, dur_ref, fin_ref, free_out_ref):
    d = dur_ref[...]
    s = _scan(d, jnp.add, 0.0)
    g = rel_ref[...] - (s - d)
    run = jnp.maximum(_scan(g, jnp.maximum, NEG), free_ref[...])
    fin = s + run
    fin_ref[...] = fin
    free_out_ref[...] = fin[:, -1:]


def serialize_prefix(free0, release, dur, *, block_rows: int = 128,
                     interpret: bool | None = None):
    """Pallas twin of `repro.kernels.ref.serialize_prefix_ref`.

    ``free0``: (..., R); ``release``/``dur``: (..., R, W) -> ``(finish
    (..., R, W), new_free (..., R))``. Leading axes are flattened to queue
    rows and processed in `block_rows` tiles.
    """
    if interpret is None:
        interpret = pallas_interpret()
    w = release.shape[-1]
    lead = release.shape[:-1]
    rel = release.reshape(-1, w)
    d = dur.reshape(-1, w)
    fr = free0.reshape(-1, 1)
    rows = rel.shape[0]
    br = min(block_rows, rows)
    pad = (-rows) % br
    if pad:
        rel = jnp.pad(rel, ((0, pad), (0, 0)), constant_values=0.0)
        d = jnp.pad(d, ((0, pad), (0, 0)), constant_values=0.0)
        fr = jnp.pad(fr, ((0, pad), (0, 0)), constant_values=0.0)
    fin, free = pl.pallas_call(
        _serialize_kernel,
        grid=(rel.shape[0] // br,),
        in_specs=[pl.BlockSpec((br, 1), lambda i: (i, 0)),
                  pl.BlockSpec((br, w), lambda i: (i, 0)),
                  pl.BlockSpec((br, w), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((br, w), lambda i: (i, 0)),
                   pl.BlockSpec((br, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct(rel.shape, rel.dtype),
                   jax.ShapeDtypeStruct((rel.shape[0], 1), rel.dtype)],
        interpret=interpret,
    )(fr, rel, d)
    if pad:
        fin, free = fin[:rows], free[:rows]
    return fin.reshape(*lead, w), free[:, 0].reshape(*lead)
