"""Plain Stream scheduler: the reference an explore cell's exact metrics are
compared with.

A copy of the repository's first, object-and-dict implementation of
Stream's step 5 (list scheduling of computation nodes on a multi-core
accelerator), cut to what the latency and energy need: FCFS cores, the
shared bus, the DRAM port with just-in-time input prefetch, on-core weight
residency with FIFO eviction, activation spills, and fused stacks bounded
by weight capacity. The per-CN costs are its own
(`bench.reference.cn_cost`). The CN graph, the partition of each layer
into tiles and the edges between them, is problem data built afresh by the
repository's builders (`identify_cns`, `build_cn_graph`), checked here to
tile every layer's output exactly; nothing of the scheduler under test or
of its cost model is used.
"""
from __future__ import annotations

import heapq
from collections import OrderedDict

import numpy as np

from bench.reference.cn_cost import PlainCost

PREFETCH_DEPTH = 4.0  # external-input staging depth (quad-buffered)


def segments(workload, allocation, accelerator) -> np.ndarray:
    """Fused stacks: a greedy cut wherever a core's accumulated weight
    footprint would overflow its weight memory."""
    alloc = np.asarray(allocation, dtype=np.int64).tolist()
    caps = [c.weight_mem_bytes for c in accelerator.cores]
    acc_w: dict[int, float] = {}
    seg = 0
    seg_of = np.zeros(len(workload.layers), dtype=np.int64)
    for lid, layer in enumerate(workload.layers.values()):
        wb, core = layer.weight_bytes, alloc[lid]
        cap = caps[core]
        if wb > 0 and cap > 0:
            hold = min(wb, cap)
            if acc_w.get(core, 0.0) + hold > cap and acc_w.get(core, 0.0) > 0:
                seg += 1
                acc_w = {}
            acc_w[core] = acc_w.get(core, 0.0) + hold
        seg_of[lid] = seg
    return seg_of


def schedule(graph, cost_model, allocation, accelerator,
             priority: str = "latency") -> tuple[float, float]:
    """(latency in cycles, energy in pJ) of one layer-to-core allocation."""
    if accelerator.topology is not None:
        raise NotImplementedError("the reference models the flat bus only")
    cns = graph.cns
    n = len(cns)
    alloc = np.asarray(allocation, dtype=np.int64)
    core_of = np.array([alloc[cn.layer] for cn in cns], dtype=np.int64)
    seg_of = segments(cost_model.workload, alloc, accelerator)[
        [cn.layer for cn in cns]]
    seg_barrier: dict[int, float] = {0: 0.0}
    frontier = 0.0

    core_free = np.zeros(accelerator.n_cores)
    bus_free = 0.0
    dram_free = 0.0
    last_end = 0.0          # latest end of any transfer
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
    remaining_new: dict[int, int] = {}
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
            elif (u, core) in sent_to:
                data_ready = max(data_ready, sent_to[(u, core)])
            else:
                rem = remaining_new.get(u)
                if rem is None:
                    rem = cns[u].out_bytes
                fresh = min(e_bytes, rem)
                remaining_new[u] = rem - fresh
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
                                         - dur * PREFETCH_DEPTH))
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
    return latency, float(sum(energy.values()))


def problem(workload, accelerator, granularity):
    """The problem instance, built afresh: the CN graph, checked to tile
    each layer's output, and the plain per-CN costs."""
    from repro.core.cn import identify_cns
    from repro.core.depgraph import build_cn_graph
    from repro.core.stream_api import hw_min_tiles
    cns = identify_cns(workload, granularity, hw_min_tiles(accelerator))
    graph = build_cn_graph(workload, cns)
    check_tiling(workload, graph.cns)
    return graph, PlainCost(workload, accelerator)


def check_tiling(workload, cns) -> None:
    """Every CN's output tile lies inside its layer's output, and a layer's
    tiles add up to that output: with no overlap, they cover it once."""
    out_dims = ("B", "K", "OY", "OX")
    covered: dict[int, int] = {}
    for cn in cns:
        layer = workload.layers[cn.layer]
        tile = {d: (0, int(layer.dims.get(d, 1))) for d in out_dims}
        tile.update({d: (a, b) for d, a, b in cn.out_rect.ranges})
        for d, (a, b) in tile.items():
            if not 0 <= a < b <= int(layer.dims.get(d, 1)):
                raise ValueError(f"CN {cn.id}: {d} [{a}, {b}) outside its "
                                 f"layer {layer.name}")
        covered[cn.layer] = covered.get(cn.layer, 0) + int(np.prod(
            [b - a for a, b in tile.values()]))
    for lid, layer in workload.layers.items():
        want = int(np.prod([int(layer.dims.get(d, 1)) for d in out_dims]))
        if covered.get(lid) != want:
            raise ValueError(f"layer {layer.name}: CN tiles cover "
                             f"{covered.get(lid)} of {want} outputs")
