"""Pooled `ScheduleEngine.evaluate_population` (`repro.core.schedpool`):
replica engines in worker processes score a batch bit-identically to the
serial loop, keep the engine's counters exact, load no JAX, stay out of
small batches and of pool workers, survive a lost worker, keep one pool a
process and leave no process behind. Over ResNet-18 and a small
DeepSeek-V2-Lite prefill (footprints, causal and dispatch edges), both on
MC:Hetero, with 2 workers. `tests/conftest.py` keeps every other test's
batches serial; these tests start pools through the engine's private
limits."""
import dataclasses
import gc
import multiprocessing
from concurrent.futures import ProcessPoolExecutor

import numpy as np
import pytest

from repro.api.session import ExplorationSession
from repro.configs.deepseek_v2_lite import CONFIG
from repro.configs.paper_workloads import resnet18
from repro.core import CostModel, schedpool
from repro.core.allocator import feasible_cores_per_layer
from repro.core.scheduler import ScheduleEngine
from repro.hw.catalog import mc_hetero
from repro.obs import Tracer
from repro.serve.prefill import mla_moe_prefill

pytestmark = pytest.mark.tier1

SMALL_LLM = dataclasses.replace(
    CONFIG, d_model=64, n_heads=4, head_dim=16,
    mla={"kv_lora": 16, "qk_nope": 16, "qk_rope": 8, "v_dim": 16},
    moe={"n_routed": 8, "top_k": 2, "n_shared": 1, "d_ff_expert": 32,
         "first_dense_layers": 1, "d_ff_dense": 128})
# (workload, granularity, schedule options): the small LLM's weights fit
# one fused stack, so only cuts at every layer give it snapshots to resume
PROBLEMS = {
    "r18": (resnet18, ("tile", 32, 1), {}),
    "llm": (lambda: mla_moe_prefill(SMALL_LLM, 64, n_layers=3, seed=0),
            ("tile", 4, 1), {"strict_layers": True}),
}
COUNTERS = ("engine.schedules", "engine.cns_walked", "engine.cns_resumed",
            "engine.edges_walked", "engine.operand_edges")


@pytest.fixture(scope="module", params=sorted(PROBLEMS))
def problem(request):
    make, gran, kw = PROBLEMS[request.param]
    w, acc = make(), mc_hetero()
    return w, acc, gran, ExplorationSession().graph(w, acc, gran), kw


def engine_of(problem, pooled: bool) -> ScheduleEngine:
    """A fresh engine (no cache shares it); pooled ones start a 2-worker
    pool on their first batch, whatever its size."""
    w, acc, _, graph, _ = problem
    engine = ScheduleEngine(graph, CostModel(w, acc), acc)
    engine.tracer = Tracer()
    if pooled:
        engine._pool_min_walks, engine._pool_cap = 0, 2
    return engine


def batches(problem, seed: int = 0) -> list[np.ndarray]:
    """A cold population, then offspring that keep its first genes (they
    resume from its snapshots) and others that keep none."""
    w, acc = problem[:2]
    feas = feasible_cores_per_layer(w, acc)
    rng = np.random.default_rng(seed)

    def draw(k):
        return np.array([[f[rng.integers(len(f))] for f in feas]
                         for _ in range(k)], dtype=np.int64)
    pop = draw(12)
    tail = draw(12)
    cut = rng.integers(len(feas) // 2, len(feas), size=12)
    kids = np.where(np.arange(len(feas)) < cut[:, None], pop, tail)
    return [pop, np.concatenate([kids, draw(4)])]


def test_pooled_batches_equal_serial_bit_for_bit(problem):
    serial, pooled = engine_of(problem, False), engine_of(problem, True)
    kw = problem[4]
    for genomes in batches(problem):
        want = serial.evaluate_population(genomes, **kw)
        got = pooled.evaluate_population(genomes, **kw)
        assert got.tobytes() == want.tobytes()
    assert pooled._pool.workers == 2 and serial._pool is None
    assert pooled.ckpt_stats["resume_hits"] > 0     # partly resumed
    pooled._pool.close()


def test_exploration_is_identical_with_the_pool_on_and_off(problem):
    w, acc, gran = problem[:3]
    out = []
    for pooled in (False, True):
        session = ExplorationSession()
        engine = session.engine(w, acc, gran)
        if pooled:
            engine._pool_min_walks, engine._pool_cap = 0, 2
        out.append(session.explore(w, acc, granularity=gran, pop_size=8,
                                   generations=4, seed=5))
        assert (engine._pool is not None) == pooled
        if pooled:
            engine._pool.close()
    a, b = out
    assert a.ga.history == b.ga.history
    assert a.ga.pareto_genomes.tobytes() == b.ga.pareto_genomes.tobytes()
    assert a.ga.pareto_objs.tobytes() == b.ga.pareto_objs.tobytes()
    assert a.ga.best_genome.tobytes() == b.ga.best_genome.tobytes()
    assert a.ga.best_objs.tobytes() == b.ga.best_objs.tobytes()
    assert a.ga.evaluations == b.ga.evaluations
    assert (a.latency_cc, a.energy_pj) == (b.latency_cc, b.energy_pj)


def test_counters_stay_exact(problem):
    """Without checkpoints every row walks every CN, so the pooled
    counters equal the serial ones; with them, walked + resumed CNs are
    rows x CNs and the summed replica counters account for each row."""
    serial, pooled = engine_of(problem, False), engine_of(problem, True)
    genomes, kw = batches(problem)[0], problem[4]
    serial.evaluate_population(genomes, checkpoint=False, **kw)
    pooled.evaluate_population(genomes, checkpoint=False, **kw)
    want = serial.tracer.snapshot()["counters"]
    got = pooled.tracer.snapshot()["counters"]
    assert {k: got[k] for k in COUNTERS} == {k: want[k] for k in COUNTERS}
    assert got["engine.pooled_rows"] == len(genomes)
    assert "engine.pooled_rows" not in want
    assert [e.name for e in pooled.tracer.events] == ["engine.pool"]
    assert (want["engine.operand_edges"] > 0) == (problem[3].footprints
                                                  is not None)

    pooled.reset_checkpoints()
    pooled.tracer = Tracer()
    rows = 0
    for b in batches(problem, seed=1):
        pooled.evaluate_population(b, **kw)
        rows += len(b)
    c, ck = pooled.tracer.snapshot()["counters"], pooled.ckpt_stats
    n = pooled.n
    assert c["engine.cns_walked"] + c["engine.cns_resumed"] == rows * n
    assert c["engine.schedules"] == c["engine.pooled_rows"] == rows
    assert ck["resume_hits"] + ck["cold_starts"] == rows
    assert ck["cns_skipped"] == c["engine.cns_resumed"] > 0
    assert ck["cns_scheduled"] == c["engine.cns_walked"]
    pooled._pool.close()


def test_workers_load_no_jax_and_start_no_pool(problem):
    engine = engine_of(problem, True)
    pool = engine._pool_for(1)
    probe = "'jax' in __import__('sys').modules"
    assert pool._run([(eval, (probe,))] * 2) == [False, False]
    assert pool._run([(schedpool.pool_workers, (8,))] * 2) == [0, 0]
    pool.close()


def test_sweep_workers_start_no_pool():
    with ProcessPoolExecutor(
            1, mp_context=multiprocessing.get_context("spawn")) as ex:
        assert ex.submit(schedpool.pool_workers, 8).result(timeout=60) == 0


def test_small_batches_stay_serial(problem):
    engine = engine_of(problem, False)
    genomes = batches(problem)[0]
    walks = len(genomes) * engine.n
    engine._pool_min_walks, engine._pool_cap = walks + 1, 2
    engine.evaluate_population(genomes)
    assert engine._pool is None
    assert "engine.pooled_rows" not in engine.tracer.snapshot()["counters"]
    engine._pool_min_walks = walks
    engine.evaluate_population(genomes)
    assert engine.tracer.snapshot()["counters"]["engine.pooled_rows"] == len(
        genomes)
    engine._pool.close()


def test_a_process_keeps_one_pool(problem):
    """A second engine's pool closes the first's; the first engine starts
    its own again on its next batch, and `reset_checkpoints` closes it."""
    first, second = engine_of(problem, True), engine_of(problem, True)
    genomes = batches(problem)[0]
    want = engine_of(problem, False).evaluate_population(genomes)
    first.evaluate_population(genomes)
    old = first._pool
    assert second.evaluate_population(genomes).tobytes() == want.tobytes()
    assert old.closed and all(p.poll() is not None for p in old._procs)
    assert all(p.poll() is None for p in second._pool._procs)
    assert first.evaluate_population(genomes).tobytes() == want.tobytes()
    assert second._pool.closed and not first._pool.closed
    procs = first._pool._procs
    first.reset_checkpoints()
    assert first._pool.closed and all(p.poll() is not None for p in procs)


@pytest.mark.parametrize("files,cpus", [
    ({}, None),                                          # no cgroup files
    ({"cpu.max": "max 100000\n"}, None),                 # v2, no quota
    ({"cpu.max": "150000 100000\n"}, 1),                 # v2, 1.5 CPUs
    ({"cpu.max": "300000 100000\n"}, 3),
    ({"cpu/cpu.cfs_quota_us": "-1\n",                    # v1, no quota
      "cpu/cpu.cfs_period_us": "100000\n"}, None),
    ({"cpu/cpu.cfs_quota_us": "200000\n",
      "cpu/cpu.cfs_period_us": "100000\n"}, 2),
])
def test_cpu_limit_reads_the_cgroup_quota(tmp_path, monkeypatch, files, cpus):
    monkeypatch.setattr(schedpool.os, "sched_getaffinity",
                        lambda pid: set(range(16)))
    for name, text in files.items():
        (tmp_path / name).parent.mkdir(exist_ok=True)
        (tmp_path / name).write_text(text)
    assert schedpool.cpu_limit(str(tmp_path)) == (16 if cpus is None
                                                  else cpus)


@pytest.mark.parametrize("cpus,want", [(1, 0), (2, 0), (3, 2), (9, 8),
                                       (64, 8)])
def test_workers_follow_the_spare_cpus(monkeypatch, cpus, want):
    monkeypatch.setattr(schedpool, "cpu_limit", lambda: cpus)
    assert schedpool.pool_workers(8) == want


def test_a_lost_worker_costs_no_result(problem):
    serial, pooled = engine_of(problem, False), engine_of(problem, True)
    first, second = batches(problem)
    pooled.evaluate_population(first)
    old = pooled._pool
    old._procs[1].kill()
    old._procs[1].wait(timeout=30)
    assert (pooled.evaluate_population(second).tobytes()
            == serial.evaluate_population(second).tobytes())
    assert old.closed and all(p.poll() is not None for p in old._procs)
    pooled.evaluate_population(first)               # a new pool
    assert pooled._pool is not old and not pooled._pool.closed
    pooled._pool.close()


def test_no_worker_outlives_its_engine(problem):
    engine = engine_of(problem, True)
    engine.evaluate_population(batches(problem)[0])
    procs = engine._pool._procs
    assert all(p.poll() is None for p in procs)
    del engine
    gc.collect()
    assert [p.wait(timeout=30) for p in procs] == [0, 0]
