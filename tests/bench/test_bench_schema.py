"""BENCHMARK.json against the benchmark's contract, and the harness's
resolution of every cell from files found by name."""
import json
import re

import pytest

import benchtools
from bench import cells

BM = json.loads((benchtools.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys_and_command():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(BM["command"]) <= 32
    assert BM["command"][1] == "bench/run.py"
    for p in BM["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and ".." not in p
        assert (benchtools.ROOT / p).is_dir()
    assert isinstance(BM["run_seconds"], int)
    assert 1 <= BM["run_seconds"] <= 51


def test_a_full_check_fits_its_time():
    runs = 2 + 14 * 24
    assert runs * (BM["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_units_and_entry_keys():
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in BM[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads", "end_to_end", "per_layer"):
        assert len({e["name"] for e in BM[k]}) == len(BM[k])
    for c in BM["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert all(NAME.match(k) for k in c["reduced"])
    for w in BM["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and NAME.match(w["traffic"])
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for m in BM["end_to_end"] + BM["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in BM["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BM["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    assert len(json.dumps(BM)) <= 64 * 1024


def test_every_metric_is_reported_where_it_is_listed():
    cell_names = {w["name"] for w in BM["workloads"]}
    e2e = {m["name"]: m for m in BM["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    layers: dict[str, set] = {}
    for m in BM["per_layer"]:
        assert m["moves"] in e2e
        moved_in = set(e2e[m["moves"]].get("workloads", cell_names))
        assert set(m["workloads"]) <= moved_in
        assert set(m["workloads"]) <= cell_names
        layers.setdefault(m["layer"], set()).add(m["name"])
    for w in cell_names:
        c = cells.resolve(w, benchtools.ROOT)
        assert len(c.end_to_end) >= 2 and c.per_layer
    four = sum(w["chips"] == 4 for w in BM["workloads"])
    assert four <= max(1, len(BM["workloads"]) // 2)


def test_configs_are_used_and_their_files_lie_under_paths():
    used = {w["config"] for w in BM["workloads"]}
    files = [c["file"] for c in BM["configs"]]
    assert used == {c["name"] for c in BM["configs"]}
    assert len(set(files)) == len(files)
    for f in files:
        assert any(f.startswith(p + "/") for p in BM["paths"])
        body = json.loads((benchtools.ROOT / f).read_text())
        assert (benchtools.ROOT / "bench" / "kinds" /
                f"{body['kind']}.py").exists()
        assert body["reduced"] == [] and "limits" in body


@pytest.mark.parametrize("cell", [w["name"] for w in BM["workloads"]])
def test_each_cell_resolves_from_its_files(cell):
    c = cells.resolve(cell, benchtools.ROOT)
    assert cells.kind_driver(c).run
    for m in c.per_layer:
        assert callable(cells.metric_reader(benchtools.ROOT, m["name"]))
