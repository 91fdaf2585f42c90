"""Plain per-CN cost: the cycles and energy of one computation node (CN) on
one core, worked out from the layer's loop extents, the CN's output tile
and the core's definition (Stream's step 3, the ZigZag-lite analytical
model: spatial unrolling, register-level reuse, the cheaper of an
output-stationary and a weight-stationary loop order, and the DATE'22
stall model on the core's SRAM port).

A copy of the repository's cost arithmetic, taken when this benchmark was
written and kept here so that no later change to the program moves it. It
reads only plain fields: the layer's `op`, `dims` and `bits`, the CN's
`out_rect.ranges`, and the core's dataflow, memories and energies. It calls
nothing of the program's cost model, so a fault in the program's per-CN
costs shows as a gap.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import numpy as np

LOOP_DIMS = ("B", "K", "C", "OY", "OX", "FY", "FX")
INPUT_REUSE = ("K",)                  # one input broadcast to all K columns
WEIGHT_REUSE = ("B", "OY", "OX")      # weights shared across output pixels
OUTPUT_REDUCE = ("C", "FY", "FX")     # partial sums accumulate across these
ELEMENTWISE = ("add", "concat", "pool")


class Cost(NamedTuple):
    cycles: float
    compute: float      # pJ in the PE array
    sram: float         # pJ of on-core activation and weight SRAM traffic


def sram_pj_per_bit(size_bytes: int) -> float:
    """CACTI-style read energy per bit of an SRAM of `size_bytes`."""
    return 0.010 * math.sqrt(max(size_bytes, 256) / 1024.0)


def supports(core, op: str) -> bool:
    if core.core_type == "simd":
        return op in ELEMENTWISE
    return op in ("conv", "dwconv", "fc") + ELEMENTWISE


def cn_dims(layer, cn) -> dict[str, int]:
    """Loop extents of a CN: its output tile, the layer's reduction dims."""
    dims = {d: b - a for d, a, b in cn.out_rect.ranges}
    for d in ("C", "FY", "FX"):
        dims[d] = int(layer.dims.get(d, 1))
    if layer.op in ("dwconv",) + ELEMENTWISE:
        dims["C"] = 1
    return {k: int(dims.get(k, 1)) for k in LOOP_DIMS}


def cn_cost(d: dict, op: str, core, bits: int) -> Cost:
    unroll = dict(core.dataflow)
    n_pe = math.prod(u for _, u in core.dataflow)
    act_pj = (core.act_energy_override
              if core.act_energy_override is not None
              else sram_pj_per_bit(core.act_mem_bytes))
    w_pj = (core.weight_energy_override
            if core.weight_energy_override is not None
            else sram_pj_per_bit(core.weight_mem_bytes))
    if op in ELEMENTWISE:
        work = d["B"] * d["K"] * d["OY"] * d["OX"] * (
            d["FY"] * d["FX"] if op == "pool" else 1)
        ideal = math.ceil(work / n_pe)
        sram_bits = work * bits + d["B"] * d["K"] * d["OY"] * d["OX"] * bits
        stall = max(1.0, (sram_bits / max(ideal, 1))
                    / core.sram_bw_bits_per_cc)
        return Cost(ideal * stall * core.latency_overhead,
                    work * core.mac_energy_pj * 0.2, sram_bits * act_pj + 0.0)

    macs = math.prod(d.values())
    if core.core_type == "aimc":
        rows = math.prod(u for dim, u in core.dataflow
                         if dim in OUTPUT_REDUCE)
        activations = (math.ceil(d["C"] * d["FY"] * d["FX"] / rows)
                       * math.ceil(d["K"] / unroll.get("K", 1))
                       * d["B"] * d["OY"] * d["OX"])
        ideal = activations * core.aimc_cc_per_op
    else:
        ideal = 1
        for dim, ext in d.items():
            ideal *= math.ceil(ext / unroll.get(dim, 1))

    in_reads = macs / max(math.prod(min(unroll.get(x, 1), d[x])
                                    for x in INPUT_REUSE), 1)
    out_elems = d["B"] * d["K"] * d["OY"] * d["OX"]
    # output-stationary: partial sums stay in registers, weights reused only
    # across the unrolled output dims; weight-stationary: weights read once,
    # partial sums round-trip the SRAM per residual reduction step
    w_os = macs / max(math.prod(min(unroll.get(x, 1), d[x])
                                for x in WEIGHT_REUSE), 1)
    t_red = math.prod(math.ceil(d[x] / unroll.get(x, 1)) for x in OUTPUT_REDUCE)
    w_ws = d["K"] * d["C"] * d["FY"] * d["FX"]
    best = None
    for w_reads, out_rw in ((w_os, out_elems),
                            (w_ws, out_elems * max(1, 2 * t_red - 1))):
        in_bits = in_reads * bits
        w_bits = 0.0 if core.core_type == "aimc" else w_reads * bits
        out_bits = out_rw * bits
        sram_bits = in_bits + w_bits + out_bits
        stall = max(1.0, (sram_bits / max(ideal, 1))
                    / core.sram_bw_bits_per_cc)
        cand = (ideal * stall * core.latency_overhead, sram_bits, in_bits,
                w_bits, out_bits)
        best = cand if best is None else min(best, cand)
    cycles, _, in_bits, w_bits, out_bits = best
    return Cost(cycles, macs * core.mac_energy_pj,
                (in_bits + out_bits) * act_pj + w_bits * w_pj)


class PlainCost:
    """Per-CN costs of one workload on one accelerator."""

    def __init__(self, workload, accelerator):
        self.workload, self.accelerator = workload, accelerator

    def cost(self, cn, core_id: int) -> Cost | None:
        layer = self.workload.layers[cn.layer]
        core = self.accelerator.cores[core_id]
        if not supports(core, layer.op):
            return None
        return cn_cost(cn_dims(layer, cn), layer.op, core, layer.bits)

    def tables(self, graph):
        """(n_cns, n_cores) cycles, energy and feasibility of every CN."""
        n, C = len(graph.cns), self.accelerator.n_cores
        cycles, energy = np.zeros((n, C)), np.zeros((n, C))
        feasible = np.zeros((n, C), dtype=bool)
        for i, cn in enumerate(graph.cns):
            for c in range(C):
                cost = self.cost(cn, c)
                if cost is not None:
                    feasible[i, c] = True
                    cycles[i, c] = cost.cycles
                    energy[i, c] = cost.compute + cost.sram
        return cycles, energy, feasible
