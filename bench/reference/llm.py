"""Plain reference for the explorer on LLM graphs: what the CN graph and the
per-CN costs of an activation-operand `matmul`, a causal layer and a routed
layer must be, worked out from the layers' own fields and equations.

It adds to the plain references beside it and edits none:

* `PlainLLMCost`: the plain per-CN cost (`cn_cost.PlainCost`) with the
  `matmul` op, whose operand B is another layer's output: read from the
  activation SRAM at its energy, one matrix per head (B), reused across the
  query rows only. A causal layer whose keys lie on C reduces over its
  band's key prefix: the C of a CN producing rows [a, b) is b, taken here
  from its output rows, not from the CN's own record.
* `problem`: the CN graph built afresh by the program's builders, checked
  here: every layer's CNs tile its output (a causal layer's staircase
  extent: band [a, b) holds keys [0, b)); every data edge equals what the
  plain rules say its consumer reads of its producer (`expected_reads`:
  operand A reads its band, operand B the causal prefix [0, b) or all
  rows, a routed layer the rows of its own tokens, a token-space layer the
  routed rows of its band's tokens, each over its channel slice); and
  every CN's weights and its read, fresh and discardable inputs equal the
  count from those reads (`check_cn_inputs`).
* `schedule` and `FitnessReference`: the plain scheduler and fitness
  beside this file (`stream_schedule`, `fitness`) with what a transfer of
  an LLM graph ships: the elements a consumer reads that its core does not
  hold yet, so consumers on one core that read different channels or rows
  of one producer each ship their own part.
* `equation_macs`: the plain loop-nest count of an MLA + MoE prefill, per
  layer, from the model's equations (arXiv:2405.04434) and the routing.
"""
from __future__ import annotations

import functools
import heapq
import math
from collections import OrderedDict

import numpy as np

from bench.reference import cn_cost as plain
from bench.reference import fitness as plain_fitness
from bench.reference import stream_schedule as plain_schedule
from bench.reference.fitness import NEG, fcfs

OPERAND_B_REUSE = ("OY", "OX")   # operand B is per head: reused across rows


def supports(core, op: str) -> bool:
    if op == "matmul":
        return core.core_type == "digital"
    return plain.supports(core, op)


def matmul_cost(d: dict, core, bits: int) -> plain.Cost:
    """Cycles and energy of a matmul CN with loop extents `d` on `core`:
    the conv arithmetic of `cn_cost.cn_cost` with operand B in place of the
    weights (reuse across OY, OX; B x K x C elements held stationary; read
    at the activation SRAM's energy)."""
    unroll = dict(core.dataflow)
    act_pj = (core.act_energy_override
              if core.act_energy_override is not None
              else plain.sram_pj_per_bit(core.act_mem_bytes))
    macs = math.prod(d.values())
    ideal = 1
    for dim, ext in d.items():
        ideal *= math.ceil(ext / unroll.get(dim, 1))
    in_reads = macs / max(math.prod(min(unroll.get(x, 1), d[x])
                                    for x in plain.INPUT_REUSE), 1)
    out_elems = d["B"] * d["K"] * d["OY"] * d["OX"]
    b_os = macs / max(math.prod(min(unroll.get(x, 1), d[x])
                                for x in OPERAND_B_REUSE), 1)
    t_red = math.prod(math.ceil(d[x] / unroll.get(x, 1))
                      for x in plain.OUTPUT_REDUCE)
    b_ws = d["B"] * d["K"] * d["C"] * d["FY"] * d["FX"]
    best = None
    for b_reads, out_rw in ((b_os, out_elems),
                            (b_ws, out_elems * max(1, 2 * t_red - 1))):
        sram_bits = in_reads * bits + b_reads * bits + out_rw * bits
        stall = max(1.0, (sram_bits / max(ideal, 1))
                    / core.sram_bw_bits_per_cc)
        cand = (ideal * stall * core.latency_overhead, sram_bits,
                in_reads * bits, b_reads * bits, out_rw * bits)
        best = cand if best is None else min(best, cand)
    cycles, _, in_bits, b_bits, out_bits = best
    return plain.Cost(cycles, macs * core.mac_energy_pj,
                      (in_bits + b_bits + out_bits) * act_pj)


def cn_dims(layer, cn) -> dict[str, int]:
    """Loop extents of a CN; a causal layer with keys on C reduces over the
    key prefix [0, b) of its band [a, b)."""
    dims = plain.cn_dims(layer, cn)
    if layer.causal == "C":
        dims["C"] = dict((d, b) for d, _, b in cn.out_rect.ranges)["OY"]
    return dims


class PlainLLMCost(plain.PlainCost):
    """`cn_cost.PlainCost` with the matmul op and causal reductions; costs
    memoised per (loop extents, op, core), which is all they depend on."""

    def __init__(self, workload, accelerator):
        super().__init__(workload, accelerator)
        self._memo: dict = {}

    def cost(self, cn, core_id: int):
        layer = self.workload.layers[cn.layer]
        core = self.accelerator.cores[core_id]
        if not supports(core, layer.op):
            return None
        d = cn_dims(layer, cn)
        key = (tuple(d.values()), layer.op, core_id, layer.bits)
        if key not in self._memo:
            self._memo[key] = (
                matmul_cost(d, core, layer.bits) if layer.op == "matmul"
                else plain.cn_cost(d, layer.op, core, layer.bits))
        return self._memo[key]


# ---- the problem instance ------------------------------------------------

def problem(workload, accelerator, granularity):
    """The CN graph, built afresh and checked (`check_tiling`,
    `check_operand_edges`, `check_cn_inputs`), and the plain costs, which
    carry what each data edge reads (`reads`, from `expected_reads`) for
    the plain schedule and fitness."""
    from repro.core.cn import identify_cns
    from repro.core.depgraph import build_cn_graph
    from repro.core.stream_api import hw_min_tiles
    cns = identify_cns(workload, granularity, hw_min_tiles(accelerator))
    graph = build_cn_graph(workload, cns)
    check_tiling(workload, graph.cns)
    reads = check_operand_edges(workload, graph)
    check_cn_inputs(workload, graph, reads)
    cost = PlainLLMCost(workload, accelerator)
    cost.reads = reads
    return graph, cost


def _ranges(rect) -> dict:
    return {d: (a, b) for d, a, b in rect.ranges}


def check_tiling(workload, cns) -> None:
    """A layer's CN tiles lie in its output and cover it once; a causal
    layer with keys on K covers its staircase: the tile of rows [a, b)
    spans keys [0, b) exactly, and the tiles' volumes add to the sum of
    B x b x (b - a) x OX over its bands."""
    out_dims = ("B", "K", "OY", "OX")
    covered: dict[int, int] = {}
    want: dict[int, int] = {}
    for cn in cns:
        layer = workload.layers[cn.layer]
        tile = {d: (0, layer.d(d)) for d in out_dims}
        tile.update(_ranges(cn.out_rect))
        for d, (a, b) in tile.items():
            if not 0 <= a < b <= layer.d(d):
                raise ValueError(f"CN {cn.id}: {d} [{a}, {b}) outside "
                                 f"{layer.name}")
        vol = math.prod(b - a for a, b in tile.values())
        covered[cn.layer] = covered.get(cn.layer, 0) + vol
        if layer.causal == "K":
            a, b = tile["OY"]
            if tile["K"] != (0, b):
                raise ValueError(f"CN {cn.id} of {layer.name}: keys "
                                 f"{tile['K']} for rows [{a}, {b})")
            want[cn.layer] = want.get(cn.layer, 0) + vol
    for lid, layer in workload.layers.items():
        full = math.prod(layer.d(d) for d in out_dims)
        if covered.get(lid) != want.get(lid, full):
            raise ValueError(f"{layer.name}: CN tiles cover "
                             f"{covered.get(lid)} of {want.get(lid, full)}")


def _token_ids(layer, a: int, b: int) -> np.ndarray:
    """Token ids of a layer's rows [a, b): its routed rows, or the rows."""
    if layer.rows is None:
        return np.arange(a, b)
    return np.asarray(layer.rows[a:b])


def expected_reads(workload, layer, by_layer) -> dict:
    """(producer CN, consumer CN) -> (token ids, channel lo, channel hi,
    elements per token and channel) of what each CN of `layer` reads of
    each producer CN, from the plain rules: per input, the token rows read
    (band, causal prefix, all rows; through the row maps) that the
    producer's CN holds, over the channel slice read that it holds, times
    the producer's B and OX."""
    if layer.stride != 1 or layer.padding or layer.d("FY") != 1 \
            or layer.d("FX") != 1:
        raise ValueError(f"{layer.name}: the plain rules read token rows "
                         f"pointwise")
    n = len(layer.inputs)
    reads = layer.reads or (None,) * n
    roles = layer.roles or ("a",) * n
    out: dict = {}
    for p, rd, role in zip(layer.inputs, reads, roles):
        prod = workload.layers[p]
        lo, hi = rd if rd is not None else (
            (0, layer.d("C")) if layer.op in ("conv", "fc")
            else (0, prod.d("K")) if layer.op == "matmul"
            else (0, layer.d("K")))
        # do this input's channels run along the causal key axis?
        on_key = role == "a" and (
            layer.causal == "C" if layer.op == "matmul"
            else layer.causal == "K")
        for v in by_layer[layer.id]:
            a, b = _ranges(v.out_rect)["OY"]
            if role == "b":
                need = np.arange(b if layer.causal else prod.d("OY"))
            else:
                need = _token_ids(layer, a, b)
            c1 = min(hi, lo + b) if on_key else hi
            for u in by_layer[p]:
                held = _ranges(u.out_rect)
                pa, pb = held["OY"]
                k0, k1 = held.get("K", (0, prod.d("K")))
                rows = np.intersect1d(need, _token_ids(prod, pa, pb))
                c_lo, c_hi = max(lo, k0), min(c1, k1, prod.d("K"))
                if rows.size and c_hi > c_lo:
                    out[(u.id, v.id)] = (rows, c_lo, c_hi,
                                         prod.d("B") * prod.d("OX"))
    return out


def read_bytes(workload, graph, reads) -> dict:
    """Bytes of each read of `reads` (`expected_reads`)."""
    layer_of = {cn.id: cn.layer for cn in graph.cns}
    return {key: rows.size * (hi - lo) * plane
            * workload.layers[layer_of[key[0]]].bits // 8
            for key, (rows, lo, hi, plane) in reads.items()}


def check_operand_edges(workload, graph) -> dict:
    """Every data edge of the graph is exactly what `expected_reads` says
    its consumer reads of its producer; returns those reads."""
    by_layer: dict[int, list] = {}
    for cn in graph.cns:
        by_layer.setdefault(cn.layer, []).append(cn)
    layer_of = {cn.id: cn.layer for cn in graph.cns}
    reads: dict = {}
    for lid, layer in workload.layers.items():
        want = expected_reads(workload, layer, by_layer)
        reads.update(want)
        want_b = read_bytes(workload, graph, want)
        got = {(u, v): b for (u, v), b in graph.edge_bytes.items()
               if layer_of[v] == lid and layer_of[u] != lid and b}
        if got != want_b:
            bad = sorted(set(got.items()) ^ set(want_b.items()))[:4]
            raise ValueError(f"{layer.name}: operand edges differ from the "
                             f"plain rules, e.g. {bad}")
    return reads


def check_cn_inputs(workload, graph, reads) -> None:
    """Each CN's weights, and the input elements it reads, reads first
    (not read by the previous CN of its layer) and may discard (not read
    by the next), equal the plain rules' count from `reads`."""
    ins: dict[int, list] = {}
    for (u, v), r in reads.items():
        ins.setdefault(v, []).append((u, r))
    by_layer: dict[int, list] = {}
    for cn in graph.cns:
        by_layer.setdefault(cn.layer, []).append(cn)

    def shared(x, y) -> int:
        """Input elements that CNs x and y both read."""
        if y is None:
            return 0
        total = 0
        for u, (rows, lo, hi, plane) in ins.get(x.id, ()):
            for u2, (rows2, lo2, hi2, _) in ins.get(y.id, ()):
                if u2 == u:
                    total += (np.intersect1d(rows, rows2).size * plane
                              * max(0, min(hi, hi2) - max(lo, lo2)))
        return total

    for lid, cns in by_layer.items():
        layer = workload.layers[lid]
        weights = (0 if layer.op not in ("conv", "fc") else
                   layer.d("K") * layer.d("C") * layer.d("FY")
                   * layer.d("FX") * layer.bits // 8)
        for t, cn in enumerate(cns):
            if not layer.inputs:
                continue
            vol = sum(rows.size * (hi - lo) * plane
                      for _, (rows, lo, hi, plane) in ins.get(cn.id, ()))
            prev = cns[t - 1] if t else None
            nxt = cns[t + 1] if t + 1 < len(cns) else None
            want = (weights, vol - shared(cn, prev), vol - shared(cn, nxt))
            got = (cn.weight_bytes, cn.new_inputs, cn.discardable_inputs)
            if got != want:
                raise ValueError(f"CN {cn.id} of {layer.name}: weights, "
                                 f"fresh and discardable inputs {got}, "
                                 f"plain {want}")


# ---- the plain schedule ------------------------------------------------------

def _missing(shipped: list, rows, lo: int, hi: int) -> int:
    """Elements (per unit of plane) of `rows` x channels [lo, hi) that no
    (rows, lo, hi) entry of `shipped` covers."""
    if not shipped:
        return rows.size * (hi - lo)
    cuts = sorted({lo, hi, *(c for _, a, b in shipped for c in (a, b)
                             if lo < c < hi)})
    total = 0
    for c0, c1 in zip(cuts, cuts[1:]):
        have = [r for r, a, b in shipped if a <= c0 and c1 <= b]
        left = np.setdiff1d(rows, np.concatenate(have)) if have else rows
        total += left.size * (c1 - c0)
    return total


def schedule(graph, cost_model, allocation, accelerator,
             priority: str = "latency") -> tuple[float, float]:
    """(latency in cycles, energy in pJ) of one allocation: the plain
    scheduler of `stream_schedule`, with what an LLM graph's transfers
    ship: an edge ships the elements it reads (`cost_model.reads`) that
    its consumer's core does not hold yet, and waits for that core's last
    arrival from the producer when it holds them all."""
    if accelerator.topology is not None:
        raise NotImplementedError("the reference models the flat bus only")
    reads = cost_model.reads
    cns = graph.cns
    n = len(cns)
    bits_of = [cost_model.workload.layers[cn.layer].bits for cn in cns]
    alloc = np.asarray(allocation, dtype=np.int64)
    core_of = np.array([alloc[cn.layer] for cn in cns], dtype=np.int64)
    seg_of = plain_schedule.segments(cost_model.workload, alloc,
                                     accelerator)[[cn.layer for cn in cns]]
    seg_barrier: dict[int, float] = {0: 0.0}
    frontier = 0.0
    core_free = np.zeros(accelerator.n_cores)
    bus_free = 0.0
    dram_free = 0.0
    last_end = 0.0
    finish = np.zeros(n)
    shared_l1 = accelerator.comm_style == "shared_mem"
    if shared_l1:
        act_cap = np.zeros(accelerator.n_cores)
        act_cap[0] = sum(c.act_mem_bytes for c in accelerator.cores)
    else:
        act_cap = np.array([c.act_mem_bytes for c in accelerator.cores],
                           dtype=np.float64)
    act_used = np.zeros(accelerator.n_cores)
    w_cap = [c.weight_mem_bytes for c in accelerator.cores]
    resident = [OrderedDict() for _ in accelerator.cores]
    resident_used = np.zeros(accelerator.n_cores)
    sent_to: dict[tuple[int, int], float] = {}
    shipped: dict[tuple[int, int], list] = {}
    spilled: dict[int, float] = {}
    energy = {"compute": 0.0, "sram": 0.0, "bus": 0.0, "dram": 0.0}
    bus_bw = accelerator.bus_bw_bits_per_cc
    dram_bw = accelerator.dram_bw_bits_per_cc

    def dram_xfer(nbytes: float, earliest: float = 0.0) -> float:
        nonlocal dram_free, last_end
        if nbytes <= 0:
            return earliest
        start = max(dram_free, earliest)
        dram_free = start + nbytes * 8.0 / dram_bw
        energy["dram"] += nbytes * 8.0 * accelerator.dram_energy_pj_per_bit
        last_end = max(last_end, dram_free)
        return dram_free

    def alloc_act(core: int, nbytes: float, t: float, producer: int) -> None:
        if nbytes <= 0:
            return
        if shared_l1:
            core = 0
        kept = min(nbytes, max(act_cap[core] - act_used[core], 0.0))
        act_used[core] += kept
        if nbytes - kept > 0:
            spilled[producer] = spilled.get(producer, 0.0) + nbytes - kept
            dram_xfer(nbytes - kept, t)

    def free_act(core: int, nbytes: float) -> None:
        if nbytes <= 0:
            return
        if shared_l1:
            core = 0
        act_used[core] -= min(nbytes, act_used[core])

    indeg = np.array([len(p) for p in graph.preds], dtype=np.int64)
    heap: list = []

    def push(i: int) -> None:
        cn = cns[i]
        if priority == "latency":
            key = max((finish[u] for u in graph.preds[i]), default=0.0)
        else:
            key = -float(cn.layer)
        heapq.heappush(heap, (int(seg_of[i]), key, cn.layer, cn.intra_rank,
                              i))

    for i in range(n):
        if indeg[i] == 0:
            push(i)
    scheduled = 0
    while heap:
        i = heapq.heappop(heap)[-1]
        cn = cns[i]
        core = int(core_of[i])
        seg = int(seg_of[i])
        if seg not in seg_barrier:
            seg_barrier[seg] = frontier
        cost = cost_model.cost(cn, core)
        if cost is None:
            raise ValueError(f"CN of layer {cn.layer} on incompatible core")

        data_ready = 0.0
        for u in graph.preds[i]:
            e_bytes = graph.edge_bytes[(u, i)]
            u_core = int(core_of[u])
            if u_core == core or e_bytes == 0 or shared_l1:
                data_ready = max(data_ready, finish[u])
            else:
                rows, lo, hi, plane = reads[(u, i)]
                have = shipped.setdefault((u, core), [])
                missing = _missing(have, rows, lo, hi)
                if not missing:
                    data_ready = max(data_ready, sent_to[(u, core)])
                else:
                    have.append((rows, lo, hi))
                    fresh = missing * plane * bits_of[u] // 8
                    start = max(bus_free, finish[u])
                    bus_free = end_t = start + fresh * 8.0 / bus_bw
                    energy["bus"] += fresh * 8.0 * \
                        accelerator.bus_energy_pj_per_bit
                    last_end = max(last_end, end_t)
                    alloc_act(core, fresh, start, u)
                    free_act(u_core, fresh)
                    sent_to[(u, core)] = end_t
                    data_ready = max(data_ready, end_t)
            sp = spilled.get(u, 0.0)
            if sp > 0:
                data_ready = max(data_ready,
                                 dram_xfer(min(sp, e_bytes), finish[u]))

        if not cost_model.workload.layers[cn.layer].inputs:
            nbytes = cn.new_inputs * cn.in_bits / 8.0
            dur = nbytes * 8.0 / dram_bw
            done = dram_xfer(nbytes, max(0.0, core_free[core]
                                         - dur * plain_schedule.PREFETCH_DEPTH))
            alloc_act(core, nbytes, done, i)
            data_ready = max(data_ready, done)

        weight_ready = 0.0
        wb = cn.weight_bytes
        if wb > 0:
            hold = min(wb, w_cap[core]) if w_cap[core] > 0 else 0
            if cn.layer not in resident[core]:
                while resident_used[core] + hold > w_cap[core] \
                        and resident[core]:
                    resident_used[core] -= resident[core].popitem(
                        last=False)[1]
                resident[core][cn.layer] = hold
                resident_used[core] += hold
                weight_ready = dram_xfer(wb, 0.0)

        start = max(core_free[core], data_ready, weight_ready,
                    seg_barrier[seg])
        end = start + cost.cycles
        core_free[core] = end
        finish[i] = end
        frontier = max(frontier, end)
        energy["compute"] += cost.compute
        energy["sram"] += cost.sram
        alloc_act(core, cn.out_bytes, start, i)
        free_act(core, cn.discardable_inputs * cn.in_bits / 8.0)
        scheduled += 1
        for v in graph.succs[i]:
            indeg[v] -= 1
            if indeg[v] == 0:
                push(v)

    if scheduled != n:
        raise RuntimeError(f"scheduled {scheduled}/{n} CNs: a cycle?")
    latency = float(max(finish.max() if n else 0.0, last_end))
    return latency, float(energy["compute"] + energy["sram"] + energy["bus"]
                          + energy["dram"])


# ---- the plain fitness ------------------------------------------------------

def read_pieces(graph, reads):
    """The reads cut into pieces: per producer CN, its (token, channel)
    elements grouped by the set of its successor slots (positions in its
    successor list) whose reads cover them. Returns (producer, sorted
    reader slots, bytes) per piece."""
    layer_bits = {cn.id: cn.out_bits for cn in graph.cns}
    pieces = []
    for u, succ in enumerate(graph.succs):
        mine = [(s, reads[(u, v)]) for s, v in enumerate(succ)
                if (u, v) in reads]
        if not mine:
            continue
        cuts = sorted({c for _, (_, lo, hi, _) in mine for c in (lo, hi)})
        owners: dict = {}
        for s, (rows, lo, hi, plane) in mine:
            for c0, c1 in zip(cuts, cuts[1:]):
                if lo <= c0 and c1 <= hi:
                    for r in rows.tolist():
                        owners.setdefault((r, c0, c1, plane), []).append(s)
        sizes: dict = {}
        for (_, c0, c1, plane), slots in owners.items():
            key = tuple(slots)
            sizes[key] = sizes.get(key, 0) + (c1 - c0) * plane
        for slots, elems in sizes.items():
            pieces.append((u, slots, elems * layer_bits[u] / 8.0))
    return pieces


class FitnessReference(plain_fitness.FitnessReference):
    """`fitness.FitnessReference` with what an LLM graph's transfers ship:
    each piece (`read_pieces`) of a producer's output crosses to a core
    with its first reader there, in successor-slot order, that sits on
    another core than the producer, and no other reader there pays for it.
    The rest of its `_score` is the parent's, copied."""

    def __init__(self, graph, cost_model, accelerator):
        super().__init__(graph, cost_model, accelerator)
        pieces = read_pieces(graph, cost_model.reads)
        width = max(len(s) for _, s, _ in pieces)
        slot = np.full((len(pieces), width), -1, dtype=np.int64)
        for k, (u, slots, _) in enumerate(pieces):
            slot[k, :len(slots)] = [u * self.S + s for s in slots]
        self.t["piece_slot"] = slot
        self.t["piece_b"] = np.array([b for *_, b in pieces])

    def _fresh8(self, j, core_ng, p, F):
        """8 x the bytes each data edge ships, (n+1, D, P): a reader of a
        piece pays for it when it sits on another core than the producer
        and no earlier reader of the piece sits on its core and another
        core than the producer."""
        import jax.numpy as jnp
        n = self.n
        scr = core_ng[j["succ_ids"]]                         # (n+1, S, P)
        crossing = (j["succ_b"][:, :, None] > 0) & (scr != core_ng[:, None])
        cross = jnp.concatenate([crossing.reshape(-1, p),
                                 jnp.zeros((1, p), bool)])
        cores = jnp.concatenate([scr.reshape(-1, p),
                                 jnp.full((1, p), -1, scr.dtype)])
        slot = j["piece_slot"]                               # (Np, R)
        fresh = jnp.zeros(cross.shape, F)
        for k in range(slot.shape[1]):
            pays = cross[slot[:, k]]
            for e in range(k):
                pays = pays & ~(cross[slot[:, e]]
                                & (cores[slot[:, e]] == cores[slot[:, k]]))
            fresh = fresh.at[slot[:, k]].add(
                jnp.where(pays, j["piece_b"][:, None], 0.0))
        fresh = fresh[:-1].reshape(n + 1, self.S, p)
        return 8.0 * fresh[j["pred_ids"], j["edge_slot"]]

    def _score(self, genomes, dtype):
        """`fitness.FitnessReference._score`, copied, with `_fresh8` for
        the bytes each edge ships."""
        import jax
        import jax.numpy as jnp
        F = jnp.dtype(dtype)
        j = {k: jnp.asarray(v, F if v.dtype.kind == "f" else None)
             for k, v in self.t.items()}
        n, C = self.n, self.C
        p = genomes.shape[0]
        seg_gl = self._segments(genomes, j, F)
        core_ng = genomes.T[j["layer_pad"]]                  # (n+1, P)
        seg_ng = seg_gl.T[j["layer_pad"]]
        ids = jnp.arange(n + 1)[:, None]
        cyc_ng = j["cyc_nc"][ids, core_ng]
        ecs_ng = j["ecs_nc"][ids, core_ng]

        fresh8 = self._fresh8(j, core_ng, p, F)

        wf, member = j["wf"], j["member"]
        cyc_x, seg_x, cw_x = cyc_ng[wf], seg_ng[wf], core_ng[wf]
        on = ((cw_x[:, None] == jnp.arange(C)[None, :, None, None])
              & member[:, None, :, None])                    # (L, C, W, P)
        xs = {"wf": wf, "member": member, "cyc": cyc_x, "seg": seg_x,
              "dram": j["dram_off"], "tot": j["dram_tot"], "on": on,
              "pu": j["wf_pred"]}
        comm = not self.shared_l1
        pucn = core_ng[j["pred_ids"]]                        # (n+1, D, P)
        crossn = (j["pred_b"][:, :, None] > 0) & (pucn != core_ng[:, None])
        f8n = fresh8 * crossn
        if comm:
            occn = jnp.sum(f8n[..., None]
                           * j["route_inv"][pucn, core_ng[:, None]], axis=1)
            xs["cross"] = crossn[wf]                         # (L, W, D, P)
            xs["occ"] = jnp.moveaxis(occn, 2, 1)[wf].transpose(0, 2, 1, 3)
        aw = jnp.broadcast_to(j["alloc_b"][:, :, None], cyc_x.shape)
        fw = jnp.broadcast_to(j["disc_b"][:, :, None], cyc_x.shape)
        if comm:
            aw = aw + (jnp.sum(f8n, axis=1) / 8.0)[wf]
        aw = jnp.where(member[:, :, None], aw, 0.0)
        if self.shared_l1:
            onm = (member[:, None, :, None]
                   & (jnp.arange(C)[None, :, None, None] == 0))
            xs["mw"] = jnp.zeros_like(cw_x)
        else:
            onm = on
            xs["mw"] = cw_x
        xs["aw"] = aw
        xs["ac"] = jnp.sum(jnp.where(onm, aw[:, None], 0.0), axis=2)
        fc = jnp.sum(jnp.where(onm, fw[:, None], 0.0), axis=2)
        if comm:
            fbe = f8n / 8.0
            lvl_t = j["lvl_oh"].T
            fc = fc + jnp.stack([jnp.matmul(
                lvl_t, jnp.sum(jnp.where(pucn == c, fbe, 0.0), axis=1),
                precision=jax.lax.Precision.HIGHEST) for c in range(C)],
                axis=1)
        xs["fc"] = fc

        def step(state, x):
            (finish, core_free, chan_free, dram_free, seg_front, used,
             spilled, dram_x) = state
            pf = finish[x["pu"]]                             # (W, D, P)
            if comm:
                base = jnp.max(jnp.where(x["cross"], NEG, pf), axis=1,
                               initial=0.0)
                rel_b = jnp.max(jnp.where(x["cross"], pf, NEG), axis=1,
                                initial=NEG)
                occ = x["occ"]                               # (1, W, P)
                fin_ch, chan_free = fcfs(
                    chan_free, jnp.where(occ > 0, rel_b[None], NEG), occ)
                arr = jnp.max(jnp.where(occ > 0, fin_ch, NEG), axis=0)
                data_ready = jnp.maximum(base, arr)
            else:
                data_ready = jnp.max(pf, axis=1, initial=0.0)
            ready = jnp.maximum(data_ready,
                                dram_free[None] + x["dram"][:, None])
            dram_free = dram_free + x["tot"]
            fronts = seg_front
            k = 1
            while k < fronts.shape[0]:
                fronts = jnp.maximum(fronts, jnp.concatenate(
                    [jnp.full((k, p), NEG, F), fronts[:-k]], axis=0))
                k *= 2
            ex = jnp.concatenate([jnp.full((1, p), NEG, F), fronts[:-1]])
            ready = jnp.maximum(ready,
                                jnp.take_along_axis(ex, x["seg"], axis=0))
            mem = x["member"][:, None]
            on_core = x["on"]
            fin_c, core_free = fcfs(
                core_free, jnp.where(on_core, ready[None], NEG),
                jnp.where(on_core, x["cyc"][None], 0.0))
            fin_w = jnp.sum(jnp.where(on_core, fin_c, 0.0), axis=0)
            alloc_c = x["ac"]
            over = jnp.clip(used + alloc_c - j["act_cap"][:, None], 0.0,
                            alloc_c)
            frac = over / jnp.maximum(alloc_c, 1.0)
            frac_w = jnp.take_along_axis(frac, x["mw"], axis=0)
            spilled = spilled.at[x["wf"]].add(
                jnp.where(mem, x["aw"] * frac_w, 0.0))
            dram_x = dram_x + jnp.sum(over, axis=0)
            used = jnp.maximum(jnp.minimum(used + alloc_c - over,
                                           j["act_cap"][:, None])
                               - x["fc"], 0.0)
            finish = finish.at[x["wf"]].set(fin_w)
            seg_front = seg_front.at[x["seg"], jnp.arange(p)[None]].max(
                jnp.where(mem, fin_w, NEG))
            return (finish, core_free, chan_free, dram_free, seg_front,
                    used, spilled, dram_x), None

        z = functools.partial(jnp.zeros, dtype=F)
        state = (z((n + 1, p)), z((C, p)), z((1, p)), z(p),
                 z((self.n_layers, p)), z((C, p)), z((n + 1, p)), z(p))
        (finish, _, chan_free, dram_free, _, _, spilled, dram_x), _ = \
            jax.lax.scan(step, state, xs)
        dram_x = dram_x + jnp.sum(jnp.minimum(
            spilled[j["pred_ids"]], j["pred_b"][:, :, None]), axis=(0, 1))
        latency = jnp.maximum(jnp.max(finish, axis=0),
                              dram_free + dram_x * self.cc_per_byte)
        latency = jnp.maximum(latency, jnp.max(chan_free, axis=0))
        energy = (jnp.sum(ecs_ng[:n], axis=0) + self.e_const
                  + dram_x * self.e_per_byte)
        if comm:
            energy = energy + jnp.sum(
                f8n * j["route_e"][pucn, core_ng[:, None]], axis=(0, 1))
        return latency, energy


# ---- the model's equations -------------------------------------------------

def equation_macs(cfg, seq_len: int, n_layers: int,
                  routes: dict[int, list]) -> dict[str, int]:
    """MACs of each priced layer of an MLA + MoE prefill, from the
    equations: a linear map d_in -> d_out over r rows costs r x d_in x
    d_out; causal attention pairs each query with the keys up to it,
    T(T+1)/2 pairs per head, each a (nope + rope)-long dot product for the
    scores and a v-long accumulation for the context. `routes[i]` lists the
    routed tokens of each expert of MoE layer i."""
    m, e = cfg.mla, cfg.moe
    d, h, t = cfg.d_model, cfg.n_heads, seq_len
    pairs = h * t * (t + 1) // 2
    out = {}
    for i in range(n_layers):
        p = f"L{i}."
        out.update({
            p + "q_proj": t * d * h * (m["qk_nope"] + m["qk_rope"]),
            p + "kv_a_proj": t * d * (m["kv_lora"] + m["qk_rope"]),
            p + "kv_b_proj": t * m["kv_lora"] * h * (m["qk_nope"]
                                                     + m["v_dim"]),
            p + "scores": pairs * (m["qk_nope"] + m["qk_rope"]),
            p + "context": pairs * m["v_dim"],
            p + "o_proj": t * h * m["v_dim"] * d,
        })
        if i < e["first_dense_layers"]:
            out[p + "gate_up"] = t * d * 2 * e["d_ff_dense"]
            out[p + "down"] = t * e["d_ff_dense"] * d
            continue
        fs = e["n_shared"] * e["d_ff_expert"]
        out[p + "router"] = t * d * e["n_routed"]
        out[p + "shared.gate_up"] = t * d * 2 * fs
        out[p + "shared.down"] = t * fs * d
        for x, toks in enumerate(routes[i]):
            if len(toks):
                out[f"{p}expert{x}.gate_up"] = len(toks) * d * 2 \
                    * e["d_ff_expert"]
                out[f"{p}expert{x}.down"] = len(toks) * e["d_ff_expert"] * d
    return out
