"""`correct` of an `explore_llm` cell on the CPU, at a small size of the
DeepSeek-V2-Lite graph (d_model 64, 4 heads, kv_lora 16, 8 experts top 2
plus 1 shared, 64 tokens in 4 bands, 3 layers): true for the program as it
is; false with the timed path broken underneath or with a control in the
program's place; and the plain reference refuses a CN graph whose operand
B ignores the causal prefix."""
import dataclasses
import json

import numpy as np
import pytest

import benchtools

CONFIG = "explore-tinyllm"
CELL = "tiny.explore_llm"
TRAFFIC = {"pop_size": 16, "generations": 2, "prefilter_keep": 0.5}


def tiny_prefill():
    from repro.configs.deepseek_v2_lite import CONFIG as full
    from repro.serve.prefill import mla_moe_prefill
    cfg = dataclasses.replace(
        full, d_model=64, n_heads=4, head_dim=16,
        mla={"kv_lora": 16, "qk_nope": 16, "qk_rope": 8, "v_dim": 16},
        moe={"n_routed": 8, "top_k": 2, "n_shared": 1, "d_ff_expert": 32,
             "first_dense_layers": 1, "d_ff_dense": 128})
    return mla_moe_prefill(cfg, 64, n_layers=3)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    dest = benchtools.copy_bench(tmp_path_factory.mktemp("bench"))
    body = json.loads((dest / "bench" / "configs" /
                       "explore-dsv2lite-prefill-hetero.json").read_text())
    body.update(workload="tiny_prefill", granularity=["tile", 4, 1])
    benchtools.add_cell(dest, CELL, CONFIG, body, "tiny_llm", TRAFFIC,
                        like="explore.dsv2lite")
    return dest


@pytest.fixture(autouse=True)
def tiny_workload(monkeypatch):
    from repro.configs import paper_workloads
    monkeypatch.setattr(paper_workloads, "tiny_prefill", tiny_prefill,
                        raising=False)
    benchtools.serialize_on_host(monkeypatch)


def _run(root, **kw):
    return benchtools.run_cell(root, CELL, **kw)


def test_sound_run_is_correct(root):
    res = _run(root)
    assert res["correct"], res
    assert set(res["checks"]) == {"exact_gap", "fitness_gap"}
    assert res["metrics"]["explore_points_per_s"]["value"] > 0


def _exact_altered(monkeypatch):
    """The exact scheduler's energy comes out 0.1% high."""
    from repro.core.scheduler import ScheduleEngine
    real = ScheduleEngine.schedule

    def altered(self, *a, **kw):
        res = real(self, *a, **kw)
        res.energy_pj *= 1.001
        return res
    monkeypatch.setattr(ScheduleEngine, "schedule", altered)


def _scores_altered(monkeypatch):
    """The batched fitness's latencies come out 1% long."""
    from repro.core.vectorized import BatchedFitness
    real = BatchedFitness.scores

    def altered(self, genomes):
        out = real(self, genomes).copy()
        out[:, 0] *= 1.01
        return out
    monkeypatch.setattr(BatchedFitness, "scores", altered)


def _matmul_rows_halved(monkeypatch):
    """The cost model prices every matmul CN on half its query rows."""
    from repro.core.costmodel import CostModel
    real = CostModel.cn_dims

    def first_band(self, cn):
        dims = real(self, cn)
        if self.workload.layers[cn.layer].op == "matmul":
            dims["OY"] = dims["OY"] // 2
        return dims
    monkeypatch.setattr(CostModel, "cn_dims", first_band)


def _shipped_once_per_core(monkeypatch):
    """The CN graph carries no footprints, so the schedulers ship a
    producer's output to a core once, sized by its first consumer there."""
    from repro.core import depgraph
    monkeypatch.setattr(depgraph, "_footprints", lambda *a: None)


@pytest.mark.parametrize("fault", [_exact_altered, _scores_altered,
                                   _matmul_rows_halved,
                                   _shipped_once_per_core])
def test_broken_timed_path_is_not_correct(root, monkeypatch, fault):
    fault(monkeypatch)
    res = _run(root, seed=6_000_000_013)
    assert not res["correct"], res
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def test_controls_fail_the_limits(root):
    """The limits' upper readings: the plain fitness in bfloat16 in place
    of the chip's float32 scores, and the batched fitness's scores
    reported in place of the exact schedule's, both read above them."""
    from bench import cells
    from bench.kinds import explore_llm
    from bench.reference.llm import FitnessReference, schedule
    from repro.core.allocator import feasible_cores_per_layer
    from repro.core.vectorized import get_batched_fitness
    from repro.api.session import ExplorationSession
    config = cells.resolve(CELL, root).config
    limits = config["limits"]
    w, acc, gran = explore_llm.problem(config)
    problem = explore_llm.reference(config)
    graph, cost, _ = problem
    ref = FitnessReference(*problem)
    feas = feasible_cores_per_layer(w, acc)
    rng = np.random.default_rng(7)
    genomes = np.stack([[f[rng.integers(len(f))] for f in feas]
                        for _ in range(16)])
    bf = get_batched_fitness(ExplorationSession().engine(w, acc, gran),
                             contention="serialize", use_pallas=False)
    calls = [(genomes, bf.scores(genomes))]
    assert explore_llm.fitness_gap(problem, calls, 16) \
        <= limits["fitness_gap"]
    bf16 = explore_llm.fitness_gap(
        problem, calls, 16, scored=lambda g: ref.scores(g, "bfloat16"))
    assert bf16 > limits["fitness_gap"]
    exact = [schedule(graph, cost, g, acc) for g in genomes]
    approx = bf.scores(genomes)
    assert max(explore_llm.rel_gap(a, e) for a, e in zip(approx, exact)) \
        > limits["exact_gap"]


def test_reference_refuses_operand_b_without_the_causal_prefix(
        root, monkeypatch):
    """A CN graph whose operand B reads all key rows (no causal prefix)
    fails the plain reference's edge check."""
    from bench import cells
    from bench.kinds import explore_llm
    from repro.core import cn
    real = cn.input_rows

    def all_rows(workload, layer, p, role, a, b):
        if role == "b":
            b = layer.d("OY")
        return real(workload, layer, p, role, a, b)
    monkeypatch.setattr(cn, "input_rows", all_rows)
    with pytest.raises(ValueError, match="plain rules"):
        explore_llm.reference(cells.resolve(CELL, root).config)


def test_reference_refuses_cn_inputs_off_the_plain_rules(root,
                                                         monkeypatch):
    """CNs whose attention operands are all discardable at every band (K
    and V not kept live to the last band that reads them) fail the plain
    reference's input-volume check."""
    import dataclasses as dc

    from bench import cells
    from bench.kinds import explore_llm
    from repro.core import cn
    real = cn.identify_cns

    def discard_early(workload, *a, **kw):
        return [dc.replace(c, discardable_inputs=c.new_inputs)
                if workload.layers[c.layer].op == "matmul" else c
                for c in real(workload, *a, **kw)]
    monkeypatch.setattr(cn, "identify_cns", discard_early)
    with pytest.raises(ValueError, match="discardable inputs"):
        explore_llm.reference(cells.resolve(CELL, root).config)


def test_traced_run_records_what_the_new_readers_read(root, monkeypatch):
    """With --trace 1 the kind carries the program's wall tracer: the
    record holds the window's counter deltas and the spans, set-up's
    `cn.graph` among them, and the two new readers read them; untraced,
    they read nothing. (The profiler itself is left out: a CPU trace holds
    no device.)"""
    import contextlib
    import time

    import jax
    from bench import cells
    from bench import run as bench_run
    monkeypatch.setattr(bench_run.trace, "capture",
                        lambda *a: contextlib.nullcontext())
    cell = cells.resolve(CELL, root)
    readings = {}
    for traced in (True, False):
        run = bench_run.Run(cell, 4_000_000_007, 1.0, traced,
                            jax.devices()[:1], time.perf_counter())
        out = cells.kind_driver(cell).run(run)
        assert out["attempted"] > 0
        rec = dict(run.record, spans=run.spans, window=(run.t0, run.t_end))
        readings[traced] = {
            m: cells.metric_reader(root, m)(rec)
            for m in ("engine.edges_per_cn", "setup.cn_graph_s")}
    edges, graph_s = (readings[True]["engine.edges_per_cn"],
                      readings[True]["setup.cn_graph_s"])
    assert 1.0 < edges < 20.0 and 0.0 < graph_s < 30.0
    assert readings[False] == {"engine.edges_per_cn": None,
                               "setup.cn_graph_s": None}
