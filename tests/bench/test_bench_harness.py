"""The harness: it refuses to run without a TPU, and a new configuration,
traffic mix or per-layer metric is added by new files only."""
import json

import benchtools
from bench import cells
from bench import run as bench_run


def test_refuses_to_start_without_a_tpu(capsys, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(benchtools.ROOT / ".jax_cache"))
    rc = bench_run.main(["--workload", "explore.exact", "--seed", "1",
                         "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc == 3
    assert out.out == ""                      # no result line
    assert "no TPU" in out.err


def test_a_cell_is_added_by_new_files_only(tmp_path, monkeypatch):
    root = benchtools.copy_bench(tmp_path)
    # a configuration, a traffic mix and a per-layer metric, each a new file
    config = json.loads((root / "bench/configs/explore-resnet18-hetero.json")
                        .read_text())
    config.update(workload="squeezenet", accelerator="mc_hom_tpu")
    benchtools.add_cell(root, "tiny.squeeze", "explore-squeezenet-hom",
                        config, "tiny_squeeze",
                        benchtools.TINY_EXPLORE_TRAFFIC, like="explore.exact")
    (root / "bench" / "metrics" / "tiny.calls.py").write_text(
        "def read(rec):\n    return float(len(rec['fitness_rows']))\n")
    bm = json.loads((root / "BENCHMARK.json").read_text())
    bm["per_layer"].append({"name": "tiny.calls", "unit": "calls",
                            "better": "lower", "source": "host_clock",
                            "layer": "batched fitness",
                            "moves": "explore_points_per_s",
                            "workloads": ["tiny.squeeze"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))

    c = cells.resolve("tiny.squeeze", root)
    assert c.kind == "explore" and c.config["workload"] == "squeezenet"
    assert c.traffic == benchtools.TINY_EXPLORE_TRAFFIC
    assert {m["name"] for m in c.end_to_end} == {"setup_s",
                                                 "explore_points_per_s"}
    assert "tiny.calls" in {m["name"] for m in c.per_layer}
    read = cells.metric_reader(root, "tiny.calls")
    assert read({"fitness_rows": [8, 16]}) == 2.0
    # the repository's own cells still resolve, and are not given it
    assert "tiny.calls" not in {
        m["name"] for m in cells.resolve("explore.exact", root).per_layer}

    benchtools.serialize_on_host(monkeypatch)
    res = benchtools.run_cell(root, "tiny.squeeze")
    assert res["correct"], res
    assert set(res["metrics"]) == {"setup_s", "explore_points_per_s"}
    assert res["metrics"]["explore_points_per_s"]["value"] > 0
    assert list(res)[-1] == "checks"
