"""Resolve a cell of `BENCHMARK.json` to the files that define it.

A cell names a configuration and a traffic mix. The configuration's file is
the one its `configs` entry gives; the traffic mix is
`bench/traffic/<traffic>.json`; the driver is `bench/kinds/<kind>.py`, where
`kind` comes from the configuration's file; each per-layer metric is read by
`bench/metrics/<metric>.py`. Nothing here knows a cell by name, so a later
change adds a cell, a configuration, a traffic mix or a metric by adding
files and entries only.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


@dataclasses.dataclass(frozen=True)
class Cell:
    root: Path
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple      # metric entries this cell reports with --trace 0
    per_layer: tuple       # metric entries this cell reports with --trace 1

    @property
    def kind(self) -> str:
        return self.config["kind"]


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def resolve(name: str, root: Path = ROOT) -> Cell:
    """The cell called `name`, with its configuration and traffic loaded."""
    root = Path(root)
    bm = load_benchmark(root)
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bm["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = tuple(m for m in bm["end_to_end"] if _reports(m, name))
    moved = {m["name"] for m in e2e}
    # a per-layer metric without `workloads` is reported wherever the
    # end-to-end metric it moves is
    per_layer = tuple(
        m for m in bm["per_layer"]
        if (name in m["workloads"] if "workloads" in m
            else m["moves"] in moved))
    return Cell(root=root, name=name, config_name=w["config"],
                traffic_name=w["traffic"], chips=int(w["chips"]),
                config=config, traffic=traffic, end_to_end=e2e,
                per_layer=per_layer)


def _load(path: Path, module_name: str):
    if not path.exists():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(module_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[module_name] = mod
    spec.loader.exec_module(mod)
    return mod


def kind_driver(cell: Cell):
    """`bench/kinds/<kind>.py` of the cell's configuration."""
    return _load(cell.root / "bench" / "kinds" / f"{cell.kind}.py",
                 f"bench_kind_{cell.kind}")


def metric_reader(root: Path, metric: str):
    """`read(record)` of `bench/metrics/<metric>.py`."""
    mod = _load(Path(root) / "bench" / "metrics" / f"{metric}.py",
                "bench_metric_" + metric.replace(".", "_").replace("-", "_"))
    return mod.read
