"""Benchmark driver: one module per paper table/figure + the TPU-side
roofline/planner/kernel benches.

  PYTHONPATH=src python -m benchmarks.run           # quick mode
  PYTHONPATH=src python -m benchmarks.run --full    # full GA budgets
  PYTHONPATH=src python -m benchmarks.run --only exploration

Each bench module is imported lazily (a missing optional dependency fails
that bench alone, not the suite) and its wall time + returned metrics are
written to ``BENCH_<slug>.json`` so the performance trajectory is tracked
across PRs.
"""
from __future__ import annotations

import argparse
import importlib
import json
import sys
import time


def _jsonable(obj):
    """Best-effort conversion of bench results to JSON (tuple keys become
    'a/b' strings, numpy scalars/arrays become numbers/lists)."""
    if isinstance(obj, dict):
        return {"/".join(map(str, k)) if isinstance(k, tuple) else str(k):
                _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if hasattr(obj, "tolist"):  # numpy array / scalar
        return _jsonable(obj.tolist())
    if hasattr(obj, "item"):
        return obj.item()
    if isinstance(obj, (str, int, float, bool)) or obj is None:
        return obj
    return repr(obj)


# (slug, human name, module, run kwargs builder)
BENCHES = [
    ("validation", "validation (paper Table I)",
     "benchmarks.bench_validation", lambda a: {}),
    ("rtree", "rtree (paper Sec. III-B)",
     "benchmarks.bench_rtree", lambda a: {"full": a.full}),
    ("scheduler_priority", "scheduler priority (paper Fig. 7)",
     "benchmarks.bench_scheduler_priority", lambda a: {}),
    ("scheduler_throughput", "scheduler throughput (engine vs seed impl)",
     "benchmarks.bench_scheduler_throughput", lambda a: {"full": a.full}),
    ("ga_allocation", "ga allocation (paper Fig. 12)",
     "benchmarks.bench_ga_allocation", lambda a: {"full": a.full}),
    ("granularity", "granularity co-exploration (paper Fig. 4)",
     "benchmarks.bench_granularity", lambda a: {}),
    ("exploration", "exploration (paper Figs. 13-15)",
     "benchmarks.bench_exploration",
     lambda a: {"full": a.full, "workers": a.workers}),
    ("exploration_chiplets", "exploration: chiplet partitions (topology axis)",
     "benchmarks.bench_exploration_chiplets",
     lambda a: {"full": a.full, "workers": a.workers}),
    ("sweep_runtime", "sweep runtime: serial vs pooled vs sharded executors",
     "benchmarks.bench_sweep_runtime",
     lambda a: {"full": a.full, "workers": a.workers}),
    ("serving", "closed-loop serving (SLO-vs-QPS curves)",
     "benchmarks.bench_serving", lambda a: {"full": a.full}),
    ("obs", "observability: tracer overhead (sim-time channel)",
     "benchmarks.bench_obs", lambda a: {"full": a.full}),
    ("kernels", "kernels (Pallas blocks)",
     "benchmarks.bench_kernels", lambda a: {}),
    ("pipeline_plan", "pipeline planner (beyond-paper)",
     "benchmarks.bench_pipeline_plan", lambda a: {}),
    ("roofline_1pod", "roofline single-pod (dry-run reports)",
     "benchmarks.bench_roofline", lambda a: {"mesh": "16x16"}),
    ("roofline_2pod", "roofline multi-pod (dry-run reports)",
     "benchmarks.bench_roofline", lambda a: {"mesh": "2x16x16"}),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default="",
                    help="comma-separated bench slugs/names (substring match); "
                         "a token matching nothing is an error")
    ap.add_argument("--list", action="store_true",
                    help="print the registered bench slugs and exit")
    ap.add_argument("--workers", type=int, default=0,
                    help="exploration sweep: process-executor worker count "
                         "(0 = in-process serial)")
    ap.add_argument("--no-json", action="store_true",
                    help="skip writing BENCH_<slug>.json files")
    args = ap.parse_args()

    if args.list:
        width = max(len(b[0]) for b in BENCHES)
        for slug, name, _, _ in BENCHES:
            print(f"{slug:{width}s}  {name}")
        return

    from repro.backend import enable_compilation_cache
    enable_compilation_cache()
    t00 = time.perf_counter()
    failures = []
    only = [t.strip() for t in args.only.split(",") if t.strip()]
    slugs = {b[0] for b in BENCHES}

    def _matches(t: str, slug: str, name: str) -> bool:
        if t == slug:
            return True
        # substring match, but a token naming an exact slug never
        # spills onto other benches ('exploration' vs 'granularity
        # co-exploration')
        return t not in slugs and (t in name or t in slug)

    def _selected(slug: str, name: str) -> bool:
        return not only or any(_matches(t, slug, name) for t in only)

    # a typo'd slug must fail loudly, not silently run zero benches
    unmatched = [t for t in only
                 if not any(_matches(t, slug, name)
                            for slug, name, _, _ in BENCHES)]
    if unmatched:
        sys.exit(f"error: --only token(s) {unmatched} match no bench; "
                 f"registered slugs: {', '.join(b[0] for b in BENCHES)} "
                 "(see --list)")

    for slug, name, module, kwargs_of in BENCHES:
        if not _selected(slug, name):
            continue
        print(f"\n{'=' * 72}\n# {name}\n{'=' * 72}", flush=True)
        t0 = time.perf_counter()
        result, error = None, None
        try:
            mod = importlib.import_module(module)
            result = mod.run(**kwargs_of(args))
        except Exception as e:  # keep the suite going; report at the end
            print(f"BENCH FAILED: {name}: {e!r}", flush=True)
            failures.append(name)
            error = repr(e)
        wall = time.perf_counter() - t0
        print(f"[{name}: {wall:.1f}s]", flush=True)
        if not args.no_json:
            payload = {"bench": slug, "name": name, "wall_s": wall,
                       "mode": "full" if args.full else "quick",
                       "error": error, "metrics": _jsonable(result)}
            with open(f"BENCH_{slug}.json", "w") as f:
                json.dump(payload, f, indent=2)
    print(f"\ntotal: {time.perf_counter() - t00:.1f}s"
          + (f"  FAILURES: {failures}" if failures else "  (all benches ok)"))


if __name__ == "__main__":
    main()
