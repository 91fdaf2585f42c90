"""Driver of `explore` cells: Stream's design-space explorer,
`repro.api.session.ExplorationSession.explore`, at the traffic's GA budget.

Set-up builds the session's CN graph and schedule engine and the batched
fitness, scores a random population at every power-of-two chunk size
`BatchedFitness.scores` can use for this budget (from the GA's prefilter
floor of 8 rows to the population, capped at the fitness's batch limit),
and runs one one-generation exploration. The window then runs complete
explorations back to back, each with its own GA seed drawn from the run's
seed, until `--seconds` have passed; the one running then is finished.

The harness wraps the scheduler's `schedule` (`explore.exact`: every exact
evaluation and the final schedule) and the fitness's `scores`
(`explore.fitness`: the blocking call into the chip) on their instances.
After the window it checks every exploration's reported latency and energy,
and its Pareto front's, against the plain scheduler
(`bench/reference/stream_schedule.py`), and the window's chip scores
against the plain fitness in float64 (`bench/reference/fitness.py`): one
fitness call of each chunk size the window ran, drawn from the seed.
"""
from __future__ import annotations

import math
import time

import numpy as np

from bench import stats
from bench.traffic import ga_seeds, seed_generator

PREFILTER_MIN_BATCH = 8     # GeneticAllocator's default prefilter floor


def _pow2_at_least(k: int) -> int:
    return 1 << max(k - 1, 0).bit_length()


def problem(config: dict):
    from repro.configs import paper_workloads
    from repro.hw import catalog
    return (getattr(paper_workloads, config["workload"])(),
            getattr(catalog, config["accelerator"])(),
            tuple(config["granularity"]))


def chunk_sizes(pop_size: int, max_batch: int) -> list[int]:
    """Every chunk `BatchedFitness.scores` can run for offspring batches of
    PREFILTER_MIN_BATCH .. pop_size novel rows."""
    top = min(max_batch, _pow2_at_least(pop_size))
    return [1 << b for b in range(PREFILTER_MIN_BATCH.bit_length() - 1,
                                  top.bit_length())]


def rel_gap(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.abs(b)))


def exact_gap(config: dict, explorations: list) -> float:
    """Widest relative gap between an exploration's reported latency or
    energy, of its result and of each member of its Pareto front, and the
    plain scheduler's for the same allocation."""
    from bench.reference import stream_schedule as ref
    w, acc, gran = problem(config)
    graph, cost = ref.problem(w, acc, gran)
    worst = 0.0
    for r in explorations:
        got = [(r.allocation, (r.latency_cc, r.energy_pj))]
        if r.ga is not None:
            got += list(zip(r.ga.pareto_genomes, r.ga.pareto_objs))
        for alloc, reported in got:
            want = ref.schedule(graph, cost, alloc, acc, config["priority"])
            worst = max(worst, rel_gap(reported, want))
    return worst


def fitness_gap(config: dict, calls: list, rows: int,
                dtype: str = "float64", scored=None) -> float:
    """Widest relative gap between the chip's scores of the sampled
    populations (`calls`: (genomes, scores)) and the plain fitness in
    `dtype`, each population padded as `scores` pads it to `rows`. With
    no population to compare it reads infinite."""
    from bench.reference import stream_schedule
    from bench.reference.fitness import FitnessReference
    if not calls:
        return math.inf
    w, acc, gran = problem(config)
    ref = FitnessReference(*stream_schedule.problem(w, acc, gran), acc)
    worst = 0.0
    for genomes, got in calls:
        k = len(genomes)
        pad = np.concatenate([genomes,
                              np.repeat(genomes[-1:], max(rows - k, 0), 0)])
        want = ref.scores(pad, dtype)[:k]
        if scored is not None:
            got = scored(pad)[:k]
        worst = max(worst, rel_gap(got, want))
    return worst


def fitness_rows(cell, bf) -> int:
    """Rows of the largest chunk `scores` runs for this budget."""
    return min(bf.max_batch, _pow2_at_least(cell.traffic["pop_size"]))


def build(run):
    """Session, engine and fitness of the cell, warmed."""
    from repro.api.session import ExplorationSession
    from repro.core.allocator import feasible_cores_per_layer
    from repro.core.vectorized import get_batched_fitness
    config, traffic = run.cell.config, run.cell.traffic
    w, acc, gran = problem(config)
    session = ExplorationSession(prefilter=True,
                                 prefilter_keep=traffic["prefilter_keep"])
    engine = session.engine(w, acc, gran)
    bf = get_batched_fitness(engine, priority=config["priority"],
                             strict_layers=gran == "layer")
    t = time.perf_counter()
    run.log(f"set-up: engine and fitness built at {t - run.t_start!r} s")
    feas = feasible_cores_per_layer(w, acc)
    rng = np.random.default_rng(0)
    for k in chunk_sizes(traffic["pop_size"], bf.max_batch):
        bf.scores(np.stack([[f[rng.integers(len(f))] for f in feas]
                            for _ in range(k)]))
    t = time.perf_counter()
    run.log(f"set-up: fitness chunks warm at {t - run.t_start!r} s")
    explore = dict(granularity=gran, objective=config["objective"],
                   priority=config["priority"],
                   pop_size=traffic["pop_size"])
    session.explore(w, acc, generations=1, seed=0, **explore)
    return session, engine, bf, (w, acc), dict(
        explore, generations=traffic["generations"])


def explore_window(run, session, engine, bf, problem, explore):
    """Explorations back to back for the window, each with the next GA
    seed drawn from the run's seed; returns the results and the window's
    fitness calls as (genomes, scores)."""
    spans = run.spans
    engine.schedule = spans.wrap("explore.exact", engine.schedule)
    calls = []
    scores = bf.scores

    def timed_scores(genomes):
        with spans.span("explore.fitness"):
            out = scores(genomes)
        calls.append((np.array(genomes), out))
        return out
    bf.scores = timed_scores

    w, acc = problem
    seeds = ga_seeds(run.seed)
    done = []
    with run.window():
        deadline = run.t0 + run.seconds
        while time.perf_counter() < deadline:
            with spans.span("explore.point"):
                done.append(session.explore(w, acc, seed=next(seeds),
                                            **explore))
    return done, calls


def check_sample(seed: int, calls: list, max_batch: int) -> list:
    """The fitness calls the check compares: of each chunk size that the
    window's calls ran, one drawn from the seed."""
    by_rows: dict[int, list] = {}
    for call in calls:
        rows = min(max_batch, _pow2_at_least(len(call[0])))
        by_rows.setdefault(rows, []).append(call)
    pick = seed_generator(seed, "check")
    return [group[int(pick.integers(len(group)))]
            for _, group in sorted(by_rows.items())]


def run(run):
    cell, seed = run.cell, run.seed
    session, engine, bf, problem, explore = build(run)
    done, calls = explore_window(run, session, engine, bf, problem, explore)
    elapsed = run.t_end - run.t0
    run.log(f"window: {len(done)} explorations in {elapsed!r} s, "
            f"{len(calls)} fitness calls")
    run.record.update({
        "fitness_rows": [min(bf.max_batch, _pow2_at_least(len(g)))
                         for g, _ in calls],
        "fitness_shape": {"n_wavefronts": bf.n_wavefronts,
                          "width": bf.width, "n_cores": bf.n_cores,
                          "n_chan": max(bf.n_chan, 1)},
    })
    run.read_memory()

    limits = cell.config["limits"]
    t_ref = time.perf_counter()
    sample = check_sample(seed, calls, bf.max_batch)
    checks = {"exact_gap": (exact_gap(cell.config, done),
                            limits["exact_gap"]),
              "fitness_gap": (fitness_gap(cell.config, sample,
                                          fitness_rows(cell, bf)),
                              limits["fitness_gap"])}
    run.log(f"reference: {len(done)} explorations and {len(sample)} fitness "
            f"calls (rows {sorted({len(g) for g, _ in sample})}) compared "
            f"in {time.perf_counter() - t_ref!r} s")
    return {"e2e": {"explore_points_per_s": stats.rate(len(done), elapsed)},
            "attempted": len(done), "failed": 0, "checks": checks}
