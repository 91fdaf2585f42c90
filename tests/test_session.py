"""ExplorationSession: cache behaviour, executor equivalence, and the
persistent result store."""
import numpy as np
import pytest

from repro.api import (DesignSpace, ExplorationSession, FifoCache, GAConfig,
                       ProcessExecutor, ResultStore)
from repro.configs.paper_workloads import fsrcnn, resnet18
from repro.hw.catalog import mc_hetero, mc_hom_tpu, sc_tpu

pytestmark = pytest.mark.tier1

GA = GAConfig(pop_size=4, generations=2)


def _small_space(**kw):
    base = dict(workloads={"fsrcnn": fsrcnn()},
                archs={"SC:TPU": sc_tpu, "MC:HomTPU": mc_hom_tpu},
                granularities=["layer", ("tile", 8, 1)], ga=GA)
    base.update(kw)
    return DesignSpace(**base)


# ---------------------------------------------------------------------------
# FIFO cache primitive
# ---------------------------------------------------------------------------

def test_fifo_cache_eviction_order_and_counters():
    c = FifoCache(limit=2)
    c.put("a", 1), c.put("b", 2)
    assert c.get("a") == 1 and c.hits == 1
    c.put("c", 3)                      # full: evicts 'a' (oldest inserted,
    assert "a" not in c                # despite being the most recently used)
    assert c.get("b") == 2 and c.get("c") == 3
    assert c.get("a") is None and c.misses == 1
    c.put("b", 20)                     # overwrite: no eviction
    assert len(c) == 2 and c.get("b") == 20


# ---------------------------------------------------------------------------
# session-owned graph/engine caches
# ---------------------------------------------------------------------------

def test_cache_hits_across_repeated_runs():
    s = ExplorationSession()
    space_lat = _small_space()
    space_mem = _small_space(priorities=["memory"])  # new points, same graphs
    s.run(space_lat)
    stats0 = s.cache_stats
    assert stats0["graph_misses"] > 0 and stats0["engine_misses"] > 0
    s.run(space_mem)
    stats1 = s.cache_stats
    assert stats1["graph_misses"] == stats0["graph_misses"]
    assert stats1["engine_misses"] == stats0["engine_misses"]
    assert stats1["engine_hits"] > stats0["engine_hits"]


def test_identical_run_serves_from_store_without_scheduling():
    s = ExplorationSession()
    space = _small_space()
    first = s.run(space)
    assert first.n_scheduled == len(first) > 0
    again = s.run(space)
    assert again.n_scheduled == 0
    assert again.n_from_store == len(first)
    assert all(r.from_store for r in again.records)
    a = [(r.latency_cc, r.energy_pj, r.edp) for r in first.records]
    b = [(r.latency_cc, r.energy_pj, r.edp) for r in again.records]
    assert a == b


def test_fifo_eviction_at_session_cache_limit():
    s = ExplorationSession(cache_limit=2)
    w, acc = resnet18(), mc_hetero()
    for g in (("tile", 8, 1), ("tile", 16, 1), ("tile", 32, 1)):
        s.graph(w, acc, g)
    assert s.cache_stats["graph_entries"] == 2
    # oldest granularity was evicted: re-requesting it is a miss
    misses = s.cache_stats["graph_misses"]
    s.graph(w, acc, ("tile", 8, 1))
    assert s.cache_stats["graph_misses"] == misses + 1
    # newest granularity survived: hit
    hits = s.cache_stats["graph_hits"]
    s.graph(w, acc, ("tile", 32, 1))
    assert s.cache_stats["graph_hits"] == hits + 1


# ---------------------------------------------------------------------------
# persistent on-disk store
# ---------------------------------------------------------------------------

def test_disk_store_makes_rerun_incremental(tmp_path):
    space = _small_space()
    s1 = ExplorationSession(cache_dir=str(tmp_path))
    first = s1.run(space)
    assert first.n_scheduled == len(first) > 0
    assert (tmp_path / ResultStore.FILENAME).exists()

    s2 = ExplorationSession(cache_dir=str(tmp_path))  # fresh process stand-in
    again = s2.run(space)
    assert again.n_scheduled == 0 and again.n_from_store == len(first)
    assert [(r.latency_cc, r.energy_pj) for r in again.records] == \
           [(r.latency_cc, r.energy_pj) for r in first.records]

    # a changed space (different GA seed) is new content: scheduled again
    moved = _small_space(ga=GAConfig(pop_size=4, generations=2, seed=7))
    assert s2.run(moved).n_scheduled == len(first)


def test_store_records_survive_json_round_trip(tmp_path):
    space = _small_space()
    s = ExplorationSession(cache_dir=str(tmp_path))
    rec = s.run(space).records[0]
    loaded = ResultStore(str(tmp_path)).get(rec.key)
    assert loaded == rec
    assert loaded.spec is not None and loaded.spec["workload"] == "fsrcnn"
    assert loaded.allocation == rec.allocation


# ---------------------------------------------------------------------------
# executors: parallel must reproduce serial bit-for-bit
# ---------------------------------------------------------------------------

def test_process_executor_bit_identical_to_serial():
    space = _small_space()
    serial = ExplorationSession().run(space, executor="serial")
    parallel = ExplorationSession().run(space, executor="process",
                                        max_workers=2)
    assert parallel.n_scheduled == serial.n_scheduled == len(serial)
    for a, b in zip(serial.records, parallel.records):
        assert a.key == b.key
        assert (a.latency_cc, a.energy_pj, a.edp) == \
               (b.latency_cc, b.energy_pj, b.edp)
        assert a.allocation == b.allocation


def test_unknown_executor_rejected():
    with pytest.raises(ValueError):
        ExplorationSession().run(_small_space(), executor="quantum")


@pytest.mark.parametrize("executor", ["process", ProcessExecutor(2)],
                         ids=["name", "instance"])
def test_process_executor_refuses_prefilter(executor):
    """The batched fitness must run in the process that holds the device:
    a prefiltered sweep refuses process workers instead of silently
    scoring without it."""
    with pytest.raises(ValueError, match="prefilter"):
        ExplorationSession(prefilter=True).run(_small_space(),
                                               executor=executor)


# ---------------------------------------------------------------------------
# queries
# ---------------------------------------------------------------------------

def test_best_pareto_pivot_queries():
    s = ExplorationSession()
    sweep = s.run(_small_space())
    best = sweep.best("edp")
    assert best.edp == min(r.edp for r in sweep.records)
    front = sweep.pareto(("latency_cc", "energy_pj"))
    assert best in front or any(
        r.latency_cc <= best.latency_cc and r.energy_pj <= best.energy_pj
        for r in front)
    for r in sweep.records:   # no front member is dominated
        for f in front:
            assert not (r.latency_cc < f.latency_cc
                        and r.energy_pj < f.energy_pj)
    table = s.pivot(rows="arch", cols="granularity", value="edp", agg=min)
    assert set(table) == {"SC:TPU", "MC:HomTPU"}
    assert set(table["SC:TPU"]) == {"layer", "tile8x1"}


def test_wrapper_explore_matches_session_explore():
    from repro.core import explore
    w, acc = fsrcnn(), sc_tpu()
    a = explore(w, acc, granularity=("tile", 8, 1), pop_size=4, generations=2)
    b = ExplorationSession().explore(w, acc, granularity=("tile", 8, 1),
                                     pop_size=4, generations=2)
    assert a.latency_cc == b.latency_cc and a.energy_pj == b.energy_pj
    assert np.array_equal(a.allocation, b.allocation)


def test_granularity_sweep_typed_result():
    s = ExplorationSession()
    sweep = s.explore_granularity(fsrcnn(), sc_tpu(),
                                  granularities=("layer", ("tile", 8, 1)),
                                  pop_size=4, generations=2)
    assert set(sweep.results) == {"layer", "tile8x1"}
    assert sweep.best_label in sweep.results
    assert sweep.best is sweep.results[sweep.best_label]
    # legacy wrapper keeps the stringly dict shape for old callers
    from repro.core.stream_api import explore_granularity
    legacy = explore_granularity(fsrcnn(), sc_tpu(),
                                 granularities=("layer", ("tile", 8, 1)),
                                 pop_size=4, generations=2)
    assert legacy["best"] in ("layer", "tile8x1")
