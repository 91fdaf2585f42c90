"""Run one cell of `BENCHMARK.json` on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (imports, building the program's state, warming every shape the
cell uses) ends when the window opens; `setup_s` is the time from the
process's start to then. The window runs the cell's traffic for
`--seconds` seconds. Then the run checks what the window produced against
the plain reference. The last lines of standard error give each compared
number beside its limit; the last line of standard output is one JSON
object: `correct`, `attempted`, `failed`, `metrics` and `device`, with
`--trace 1` also `breakdown`, and last the compared numbers under `checks`.

With `--trace 0` the metrics are the cell's end-to-end metrics; with
`--trace 1` the window runs under the profiler and the metrics are the
cell's per-layer metrics, each read by `bench/metrics/<name>.py`.

The run needs as many TPU chips as the cell asks for, and exits with 3,
printing no result, where JAX finds fewer.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import cells, trace, work  # noqa: E402
from bench.record import CompileCounter, Spans  # noqa: E402

# the persistent compilation cache, at a fixed path inside the checkout
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / "bench_out" / "trace"


class NoChip(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Run:
    """One run of a cell: its arguments, spans, compile counter, window
    and what the per-layer readers read (`record`)."""

    def __init__(self, cell, seed: int, seconds: float, traced: bool,
                 devices, t_start: float):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.traced, self.devices, self.t_start = traced, devices, t_start
        self.spans = Spans()
        self.compiles = CompileCounter()
        self.record: dict = {}
        self.trace: dict = {}
        self.memory_peak_bytes = None
        self.log = log

    @contextlib.contextmanager
    def window(self):
        """The measured window. Set-up ends as it opens, with set-up's
        garbage collected; compilations in it are counted; with --trace 1
        it runs under the profiler."""
        gc.collect()
        self.setup_s = time.perf_counter() - self.t_start
        self.compiles.armed = True
        ctx = (trace.capture(TRACE_DIR, self.trace) if self.traced
               else contextlib.nullcontext())
        with ctx:
            self.t0 = time.perf_counter()
            yield
            self.t_end = time.perf_counter()
        self.compiles.armed = False

    def read_memory(self) -> None:
        """Peak bytes on the fullest chip: read before the reference runs,
        since a process's peak never falls again."""
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in self.devices]
        peaks = [p for p in peaks if p is not None]
        self.memory_peak_bytes = max(peaks) if peaks else None

    def reduced_trace(self) -> dict:
        host = trace.host_spans(self.trace, self.spans.events)
        return trace.reduce(self.trace, host)


def use_cache() -> None:
    """JAX's persistent compilation cache at the checkout's fixed
    `.jax_cache`, whatever the environment names: the program's entry
    point (`repro.backend.enable_compilation_cache`) takes it from there."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def chips(n: int):
    """The first n TPU chips, or NoChip."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU, only {devices[0].platform!r}")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips; JAX found {len(devices)}")
    return devices[:n]


def execute(cell, seed: int, seconds: float, traced: bool, devices,
            t_start: float, kind=None) -> dict:
    """Run the cell on `devices` and return the result line's object;
    `kind` stands in for the cell's driver where given."""
    run = Run(cell, seed, seconds, traced, devices, t_start)
    out = (kind or cells.kind_driver(cell)).run(run)
    checks = out["checks"]
    correct = (out["failed"] == 0
               and all(v <= lim for v, lim in checks.values()))
    log(f"compilations in the window: {run.compiles.count}")
    metrics = {}
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics,
              "device": device_info(devices)}
    result["device"]["memory_peak_bytes"] = run.memory_peak_bytes
    if not traced:
        values = dict(out["e2e"], setup_s=run.setup_s)
        for m in cell.end_to_end:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        red = run.reduced_trace()
        result["device"]["busy_s"] = red["busy_s"]
        result["device"]["window_s"] = red["window_s"]
        result["breakdown"] = red["breakdown"]
        rec = dict(run.record, trace=red, cell=cell, spans=run.spans,
                   window=(run.t0, run.t_end),
                   peaks=work.peaks(devices[0].device_kind))
        for m in cell.per_layer:
            value = cells.metric_reader(cell.root, m["name"])(rec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    for name, (v, lim) in checks.items():
        log(f"check {name}: {v!r} (limit {lim!r})")
    result["checks"] = {n: {"value": v, "limit": lim}
                        for n, (v, lim) in checks.items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    use_cache()
    cell = cells.resolve(args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    try:
        devices = chips(cell.chips)
    except NoChip as e:
        log(f"bench/run.py: {e}")
        return 3
    from repro.backend import enable_compilation_cache
    enable_compilation_cache()
    log(f"set-up: chips found at {time.perf_counter() - T_START!r} s")
    result = execute(cell, args.seed, args.seconds, bool(args.trace),
                     devices, T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
