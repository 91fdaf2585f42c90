"""Shared set-up of the benchmark's CPU tests: a copy of the benchmark's
files in a temporary directory, with small cells added by files only."""
from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

TINY_EXPLORE_TRAFFIC = {"pop_size": 16, "generations": 3,
                        "prefilter_keep": 0.5}


def copy_bench(dest: Path) -> Path:
    """BENCHMARK.json and bench/ copied to `dest`."""
    shutil.copytree(ROOT / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", dest / "BENCHMARK.json")
    return dest


def add_cell(root: Path, cell: str, config: str, config_body: dict | None,
             traffic: str, traffic_body: dict, like: str) -> None:
    """A new cell from new files: a configuration (unless `config_body` is
    None, for one that exists), a traffic mix, and entries that report
    every metric the cell `like` reports."""
    bm = json.loads((root / "BENCHMARK.json").read_text())
    if config_body is not None:
        path = f"bench/configs/{config}.json"
        (root / path).write_text(json.dumps(config_body))
        bm["configs"].append({"name": config, "source": "test",
                              "file": path, "reduced": [], "why": "test"})
    (root / "bench" / "traffic" / f"{traffic}.json").write_text(
        json.dumps(traffic_body))
    bm["workloads"].append({"name": cell, "config": config,
                            "traffic": traffic, "chips": 1, "why": "test"})
    for m in bm["end_to_end"] + bm["per_layer"]:
        if like in m.get("workloads", []):
            m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(bm))


def tiny_root(dest: Path) -> Path:
    """The benchmark with a small cell added by files only, `tiny.explore`
    (resnet18 on MC:Hetero, pop 16, 3 generations)."""
    copy_bench(dest)
    add_cell(dest, "tiny.explore", "explore-resnet18-hetero", None,
             "tiny_explore", TINY_EXPLORE_TRAFFIC, like="explore.exact")
    return dest


def serialize_on_host(monkeypatch) -> None:
    """Score populations with the chip's contention model (`serialize`)
    through the jnp path, as a CPU can: the reference follows that model."""
    from repro.core import vectorized
    orig = vectorized.get_batched_fitness

    def fitness(engine, priority="latency", segment=True,
                strict_layers=False, use_pallas=None, contention=None):
        return orig(engine, priority, segment=segment,
                    strict_layers=strict_layers, use_pallas=False,
                    contention="serialize")
    monkeypatch.setattr(vectorized, "get_batched_fitness", fitness)


def run_cell(root: Path, cell: str, seconds: float = 1.5,
             seed: int = 3_000_000_123, control: str | None = None) -> dict:
    """A whole run of `cell` on the first CPU device, past the look for a
    chip; with `control`, that control in the program's place."""
    import jax
    from bench import calibrate, cells
    from bench import run as bench_run
    c = cells.resolve(cell, root)
    kind = (calibrate.with_control(cells.kind_driver(c), control)
            if control else None)
    return bench_run.execute(c, seed, seconds, False, jax.devices()[:1],
                             time.perf_counter(), kind=kind)
