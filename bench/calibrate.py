"""Readings that the limits of `correct` are set from, and the control run
through the harness. Needs a TPU; the benchmark's own runs never run the
control.

    python3 bench/calibrate.py --workload <name> --seeds 11,12,13 --seconds 8
    python3 bench/calibrate.py --workload <name> --seeds 14 --seconds 8 \\
        --control fitness

Without `--control`, for each seed it builds the cell afresh, runs a short
window at the cell's own load and prints one JSON line: the program's
compared numbers, as a run computes them, and the controls' on the same
explorations and populations:

- `control_fitness_gap`: the plain fitness in bfloat16 in place of the
  chip's float32 scores;
- `control_exact_gap`: the batched fitness's scores reported in place of
  the exact scheduler's for each exploration's allocation (the guarantee
  that every reported metric is exact, broken).

With `--control fitness` or `--control exact`, each seed is one whole run
of the harness (`bench.run.execute`) with that control put in the
program's place underneath, and the line printed is the run's result: its
`correct` has to read false.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import cells, run as bench_run  # noqa: E402


def readings(run, kind) -> dict:
    import numpy as np
    from bench.reference import stream_schedule
    from bench.reference.fitness import FitnessReference
    cell, seed = run.cell, run.seed
    session, engine, bf, problem, explore = kind.build(run)
    scores = bf.scores
    done, calls = kind.explore_window(run, session, engine, bf, problem,
                                      explore)
    sample = kind.check_sample(seed, calls, bf.max_batch)
    rows = kind.fitness_rows(cell, bf)
    acc = problem[1]
    graph, cost = stream_schedule.problem(*kind.problem(cell.config))
    ref = FitnessReference(graph, cost, acc)
    exact_ctrl = max(kind.rel_gap(
        scores(np.asarray(r.allocation)[None])[0],
        stream_schedule.schedule(graph, cost, r.allocation, acc,
                                 cell.config["priority"])) for r in done)
    return {
        "exact_gap": kind.exact_gap(cell.config, done),
        "fitness_gap": kind.fitness_gap(cell.config, sample, rows),
        "control_fitness_gap": kind.fitness_gap(
            cell.config, sample, rows,
            scored=lambda g: ref.scores(g, "bfloat16")),
        "control_exact_gap": exact_ctrl,
        "explorations": len(done), "fitness_calls": len(calls),
        "sampled_rows": sorted(len(g) for g, _ in sample),
    }


def with_control(kind, control: str):
    """The kind driver, with `control` in the program's place once the
    cell is built: `fitness`, the plain fitness in bfloat16 scores every
    population; `exact`, the batched fitness's scores are reported in
    place of every exact schedule's latency and energy."""
    import numpy as np
    from bench.reference import stream_schedule
    from bench.reference.fitness import FitnessReference
    build = kind.build

    def controlled(run):
        session, engine, bf, problem, explore = build(run)
        if control == "fitness":
            ref = FitnessReference(*stream_schedule.problem(
                *kind.problem(run.cell.config)), problem[1])

            def scores(genomes):
                g = np.asarray(genomes)
                rows = min(bf.max_batch, kind._pow2_at_least(len(g)))
                pad = np.concatenate(
                    [g, np.repeat(g[-1:], max(rows - len(g), 0), 0)])
                return ref.scores(pad, "bfloat16")[:len(g)]
            bf.scores = scores
        elif control == "exact":
            schedule, approx = engine.schedule, bf.scores

            def reported(allocation, *a, **kw):
                res = schedule(allocation, *a, **kw)
                lat, en = approx(np.asarray(allocation)[None])[0]
                res.latency_cc, res.energy_pj = float(lat), float(en)
                return res
            engine.schedule = reported
        else:
            raise ValueError(f"no control {control!r}")
        return session, engine, bf, problem, explore

    kind.build = controlled
    return kind


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--control", choices=("fitness", "exact"))
    args = ap.parse_args(argv)
    bench_run.use_cache()
    cell = cells.resolve(args.workload)
    try:
        devices = bench_run.chips(cell.chips)
    except bench_run.NoChip as e:
        bench_run.log(f"bench/calibrate.py: {e}")
        return 3
    from repro.backend import enable_compilation_cache
    enable_compilation_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        if args.control:
            kind = with_control(cells.kind_driver(cell), args.control)
            out = bench_run.execute(cell, seed, args.seconds, False, devices,
                                    time.perf_counter(), kind=kind)
            out["control"] = args.control
        else:
            run = bench_run.Run(cell, seed, args.seconds, False, devices,
                                time.perf_counter())
            out = readings(run, cells.kind_driver(cell))
        print(json.dumps(dict(out, seed=seed, workload=cell.name)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
