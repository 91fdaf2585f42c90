"""Driver of `explore_llm` cells: Stream's explorer on an LLM prefill graph
(`repro.serve.prefill`: attention's scores and context as activation-operand
`matmul` layers over causal prefixes, routed experts over their own token
rows), through the same path as `explore` cells
(`ExplorationSession.explore`, the batched fitness and the exact
scheduler).

The set-up and the window are those of `bench/kinds/explore.py`, which
this driver imports: the engine and the fitness built and warmed, one
one-generation exploration, then complete explorations back to back for
the window. What differs:

* the check compares with the plain LLM reference
  (`bench/reference/llm.py`), which also checks the CN graph's causal
  tiling, operand edges and CN input volumes against the plain rules:
  `exact_gap` against its plain scheduler with the plain matmul costs,
  `fitness_gap` against its plain fitness on that problem, both shipping
  over the bus what each consumer reads that its core does not hold;
* with `--trace 1` the session carries the program's wall tracer
  (`repro.obs.realtime.wall_tracer`) from the start of set-up: its spans
  join the harness's (`cn.graph` in set-up, the GA's and the engine's in
  the window) and its counters' deltas over the window go to the record
  (`counters`), where the per-layer readers find them. The window's
  explorations are the same with it or without it.
"""
from __future__ import annotations

import math
import time

import numpy as np

from bench import stats
from bench.kinds import explore
from bench.kinds.explore import (check_sample, chunk_sizes, fitness_rows,
                                 problem, rel_gap)


def reference(config: dict):
    """(graph, plain costs, accelerator) of the cell, built and checked by
    the plain LLM reference."""
    from bench.reference import llm
    w, acc, gran = problem(config)
    graph, cost = llm.problem(w, acc, gran)
    return graph, cost, acc


def exact_gap(ref, priority: str, explorations: list) -> float:
    """Widest relative gap between an exploration's reported latency or
    energy, of its result and of each member of its Pareto front, and the
    plain scheduler's for the same allocation on `ref` (`reference`)."""
    from bench.reference import llm
    graph, cost, acc = ref
    worst = 0.0
    for r in explorations:
        got = [(r.allocation, (r.latency_cc, r.energy_pj))]
        if r.ga is not None:
            got += list(zip(r.ga.pareto_genomes, r.ga.pareto_objs))
        for alloc, reported in got:
            want = llm.schedule(graph, cost, alloc, acc, priority)
            worst = max(worst, rel_gap(reported, want))
    return worst


def fitness_gap(ref, calls: list, rows: int, dtype: str = "float64",
                scored=None) -> float:
    """Widest relative gap between the chip's scores of the sampled
    populations (`calls`: (genomes, scores)) and the plain fitness on `ref`
    (`reference`) in `dtype`, each population padded as `scores` pads it
    to `rows`; with no population to compare it reads infinite."""
    from bench.reference.llm import FitnessReference
    if not calls:
        return math.inf
    plain = FitnessReference(*ref)
    worst = 0.0
    for genomes, got in calls:
        k = len(genomes)
        pad = np.concatenate([genomes,
                              np.repeat(genomes[-1:], max(rows - k, 0), 0)])
        want = plain.scores(pad, dtype)[:k]
        if scored is not None:
            got = scored(pad)[:k]
        worst = max(worst, rel_gap(got, want))
    return worst


def build(run):
    """Session (with the wall tracer when traced), engine and fitness of
    the cell, warmed, as `explore.build` makes them."""
    from repro.api.session import ExplorationSession
    from repro.core.allocator import feasible_cores_per_layer
    from repro.core.vectorized import get_batched_fitness
    config, traffic = run.cell.config, run.cell.traffic
    tracer = None
    if run.traced:
        from repro.obs.realtime import wall_tracer
        tracer = wall_tracer()
    w, acc, gran = problem(config)
    session = ExplorationSession(prefilter=True,
                                 prefilter_keep=traffic["prefilter_keep"],
                                 tracer=tracer)
    engine = session.engine(w, acc, gran)
    bf = get_batched_fitness(engine, priority=config["priority"],
                             strict_layers=gran == "layer")
    run.log(f"set-up: engine and fitness built at "
            f"{time.perf_counter() - run.t_start!r} s: {engine.n} CNs, "
            f"{len(w)} layers, {engine.graph.n_edges()} edges, "
            f"{bf.n_wavefronts} wavefronts of width <= {bf.width}")
    feas = feasible_cores_per_layer(w, acc)
    rng = np.random.default_rng(0)
    for k in chunk_sizes(traffic["pop_size"], bf.max_batch):
        bf.scores(np.stack([[f[rng.integers(len(f))] for f in feas]
                            for _ in range(k)]))
    run.log(f"set-up: fitness chunks warm at "
            f"{time.perf_counter() - run.t_start!r} s")
    explore_kw = dict(granularity=gran, objective=config["objective"],
                      priority=config["priority"],
                      pop_size=traffic["pop_size"])
    session.explore(w, acc, generations=1, seed=0, **explore_kw)
    return session, engine, bf, (w, acc), dict(
        explore_kw, generations=traffic["generations"])


def run(run):
    cell, seed = run.cell, run.seed
    session, engine, bf, prob, explore_kw = build(run)
    tracer = session.tracer
    before = dict(tracer.snapshot()["counters"]) if tracer else {}
    done, calls = explore.explore_window(run, session, engine, bf, prob,
                                         explore_kw)
    elapsed = run.t_end - run.t0
    run.log(f"window: {len(done)} explorations in {elapsed!r} s, "
            f"{len(calls)} fitness calls")
    run.record.update({
        "fitness_rows": [min(bf.max_batch, explore._pow2_at_least(len(g)))
                         for g, _ in calls],
        "fitness_shape": {"n_wavefronts": bf.n_wavefronts,
                          "width": bf.width, "n_cores": bf.n_cores,
                          "n_chan": max(bf.n_chan, 1)},
    })
    if tracer is not None:
        after = tracer.snapshot()["counters"]
        run.record["counters"] = {k: v - before.get(k, 0.0)
                                  for k, v in after.items()}
        run.spans.events.extend((e.name, e.t0, e.t1) for e in tracer.events)
    run.read_memory()

    limits = cell.config["limits"]
    t_ref = time.perf_counter()
    sample = check_sample(seed, calls, bf.max_batch)
    ref = reference(cell.config)
    checks = {"exact_gap": (exact_gap(ref, cell.config["priority"], done),
                            limits["exact_gap"]),
              "fitness_gap": (fitness_gap(ref, sample,
                                          fitness_rows(cell, bf)),
                              limits["fitness_gap"])}
    run.log(f"reference: {len(done)} explorations and {len(sample)} fitness "
            f"calls (rows {sorted({len(g) for g, _ in sample})}) compared "
            f"in {time.perf_counter() - t_ref!r} s")
    return {"e2e": {"explore_points_per_s": stats.rate(len(done), elapsed)},
            "attempted": len(done), "failed": 0, "checks": checks}
