"""Stream: one-call design-space-exploration entry point (paper Fig. 3).

    result = explore(workload, accelerator, granularity="line",
                     objective="edp", priority="latency")

runs Steps 1-5: CN identification (HW-dataflow-aware minimum tiles), R-tree
dependency generation, intra-core cost extraction, GA layer-core allocation
(NSGA-II on [latency, energy]), and prioritized multi-core scheduling.

This module is the *single-point* compatibility surface.  The sweep-native
API — `ArchSpec`, `DesignSpace`, `ExplorationSession` with parallel
executors and a persistent result store — lives in `repro.api`; the
functions here delegate to a shared default `ExplorationSession`, which owns
the graph/engine caches that older revisions kept as module globals.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.core.depgraph import CNGraph
from repro.core.ga import GAResult
from repro.core.scheduler import ScheduleEngine, ScheduleResult
from repro.core.workload import Workload
from repro.hw.accelerator import Accelerator


def core_symmetry_canonicalize(accelerator: Accelerator):
    """Canonical-form function exploiting identical-core symmetry.

    On a homogeneous multi-core, relabeling the identical cores of an
    allocation cannot change the schedule's latency/energy bit-for-bit: the
    cost tables, weight/activation capacities and AiMC flags of equal cores
    are equal, the bus and DRAM ports are shared, and the event loop touches
    core ids only through those per-core arrays — a permutation of identical
    cores permutes the loop state exactly. Cores are canonicalized to their
    group's member ids in order of first appearance, which is *prefix-
    stable*: the canonical form of a genome prefix depends only on that
    prefix, so GA offspring share canonical allocation prefixes with their
    parents and the scheduler's segment checkpoints hit across the whole
    symmetry class. Returns None when every core is unique; otherwise a
    function that canonicalizes a whole `(K, G)` genome matrix in one
    vectorized pass (a `(G,)` genome or a list also works), returning int64
    of the input's shape.

        >>> from repro.hw.catalog import mc_hom_tpu
        >>> canon = core_symmetry_canonicalize(mc_hom_tpu())
        >>> canon([[3, 2, 3, 4], [1, 1, 0, 2]]).tolist()
        [[0, 1, 0, 4], [0, 0, 1, 2]]

    Cores are grouped by their *content* — the `name` label cannot affect
    any cost or capacity, so "tpu0" and "tpu1" with equal specs are one
    group.  With a cluster topology, groups are additionally split by
    cluster: two content-equal cores on different chiplets are *not*
    interchangeable (their transfers take different routes), so only
    within-cluster permutations are canonicalized."""
    topo = accelerator.topology
    if topo is None:
        cluster_of = [0] * accelerator.n_cores
    else:
        c2c = topo.core_to_cluster()
        cluster_of = [c2c[c.name] for c in accelerator.cores]
    groups: dict = {}
    for i, c in enumerate(accelerator.cores):
        groups.setdefault((cluster_of[i], dataclasses.replace(c, name="")),
                          []).append(i)
    sym_groups = [np.array(m, dtype=np.int64) for m in groups.values()
                  if len(m) > 1]
    if not sym_groups:
        return None

    identity = np.arange(accelerator.n_cores, dtype=np.int64)

    def canonicalize(genomes) -> np.ndarray:
        """Canonical form of a (K, G) genome matrix, a (G,) genome or a
        list of core ids, in one pass over the whole batch: within each
        group, the members are ranked by the column of their first
        appearance in the row (G when absent), and member i is relabeled
        members[rank of i] through a per-row table."""
        a = np.asarray(genomes, dtype=np.int64)
        rows = np.atleast_2d(a)
        n_rows, n_genes = rows.shape
        table = np.tile(identity, (n_rows, 1))  # (K, n_cores) relabeling
        for members in sym_groups:
            eq = rows == members[:, None, None]  # (k, K, G)
            first = np.where(eq.any(axis=2), eq.argmax(axis=2), n_genes)
            rank = np.argsort(np.argsort(first, axis=0, kind="stable"),
                              axis=0)
            table[:, members] = members[rank].T
        return np.take_along_axis(table, rows, axis=1).reshape(a.shape)

    return canonicalize


def hw_min_tiles(accelerator: Accelerator) -> dict[str, int]:
    """HW-dataflow awareness: CNs minimally encompass every dim spatially
    unrolled in any core (paper Sec. III-A principle 2)."""
    out: dict[str, int] = {}
    for core in accelerator.cores:
        for dim, u in core.dataflow:
            if dim in ("OY", "OX"):
                out[dim] = max(out.get(dim, 1), u)
    return out


@dataclasses.dataclass
class StreamResult:
    schedule: ScheduleResult
    allocation: np.ndarray
    ga: GAResult | None
    graph: CNGraph
    runtime_s: float
    granularity: object

    @property
    def latency_cc(self) -> float:
        return self.schedule.latency_cc

    @property
    def energy_pj(self) -> float:
        return self.schedule.energy_pj

    @property
    def edp(self) -> float:
        return self.schedule.edp

    @property
    def peak_mem_bytes(self) -> float:
        return self.schedule.peak_mem_bytes


def _session():
    # imported lazily to keep `repro.core` importable without (and before)
    # the `repro.api` package — see the import-order note in repro.api.session
    from repro.api.session import default_session
    return default_session()


def build_graph(workload: Workload, accelerator: Accelerator, granularity,
                use_rtree: bool = True) -> CNGraph:
    return _session().graph(workload, accelerator, granularity,
                            use_rtree=use_rtree)


def evaluate_allocation(
    workload: Workload,
    accelerator: Accelerator,
    allocation,
    granularity="line",
    priority: str = "latency",
    graph: CNGraph | None = None,
    engine: ScheduleEngine | None = None,
) -> ScheduleResult:
    """Schedule a fixed layer-core allocation (used by validation benches).

    Pass `engine` (from a previous call or `ScheduleEngine(...)`) to reuse the
    precomputed CSR graph + cost tables across many allocations."""
    return _session().evaluate_allocation(
        workload, accelerator, allocation, granularity=granularity,
        priority=priority, graph=graph, engine=engine)


def evaluate_allocations(
    workload: Workload,
    accelerator: Accelerator,
    allocations,
    granularity="line",
    priority: str = "latency",
) -> np.ndarray:
    """Population-batched fitness: (P, G) allocation matrix -> (P, 2)
    [latency_cc, energy_pj], scheduled through one shared engine whose
    segment-prefix checkpoints are reused across the whole batch."""
    return _session().evaluate_allocations(
        workload, accelerator, allocations, granularity=granularity,
        priority=priority)


def explore(
    workload: Workload,
    accelerator: Accelerator,
    granularity="line",
    objective: str = "edp",            # 'edp' | 'latency' | 'energy'
    priority: str = "latency",
    pop_size: int = 24,
    generations: int = 16,
    seed: int = 0,
    initial_allocations=(),
    prefilter: bool | None = None,
) -> StreamResult:
    return _session().explore(
        workload, accelerator, granularity=granularity, objective=objective,
        priority=priority, pop_size=pop_size, generations=generations,
        seed=seed, initial_allocations=initial_allocations,
        prefilter=prefilter)


def explore_granularity(
    workload: Workload,
    accelerator: Accelerator,
    granularities=None,   # default: repro.api.session.DEFAULT_GRANULARITIES
    objective: str = "edp",
    **kw,
) -> dict:
    """Co-explore scheduling granularity with allocation (paper Sec. V
    summary: "quantitatively and automatically co-explore the optimal
    scheduling granularity"). Returns {granularity: StreamResult} plus the
    objective-best key under 'best' — legacy shape; prefer
    `ExplorationSession.explore_granularity`, which returns a typed
    `GranularitySweep` instead of mixing the winner into the results dict."""
    kw = dict(kw, objective=objective)
    if granularities is not None:
        kw["granularities"] = granularities
    sweep = _session().explore_granularity(workload, accelerator, **kw)
    results: dict = dict(sweep.results)
    results["best"] = sweep.best_label
    return results
