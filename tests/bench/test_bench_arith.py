"""The benchmark's arithmetic: rates over whole windows, the traffic
generator, the plain per-CN costs, the fitness sample, and the analytic
work counts."""
import dataclasses

import numpy as np
import pytest

import benchtools  # noqa: F401  (puts the repo root on sys.path)
from bench import stats, traffic, work


def test_rate_is_over_the_whole_window():
    assert stats.rate(450, 30.0) == 15.0
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)


def test_ga_seeds_are_deterministic_per_seed():
    seed = 2**31 + 12345                          # beyond 32 signed bits
    a, b, c = (traffic.ga_seeds(s) for s in (seed, seed, seed + 1))
    xa = [next(a) for _ in range(50)]
    assert xa == [next(b) for _ in range(50)]
    assert xa != [next(c) for _ in range(50)]
    assert len(set(xa)) == 50 and all(0 <= x < 2**31 for x in xa)
    # a stream of its own: the GA seeds do not move the check's draw
    assert traffic.seed_generator(seed, "check").integers(2**31) == \
        traffic.seed_generator(seed, "check").integers(2**31)


@pytest.mark.parametrize("workload,accelerator", [
    ("resnet18", "mc_hetero"), ("squeezenet", "mc_hom_tpu"),
    ("resnet18", "diana")])
def test_plain_costs_match_the_program_cost_tables(workload, accelerator):
    """The reference's own per-CN arithmetic reads what the program's cost
    model reads, bit for bit, on digital, SIMD and analog in-memory
    cores."""
    from repro.configs import paper_workloads
    from repro.core import CostModel
    from repro.hw import catalog
    from bench.reference import stream_schedule
    w = getattr(paper_workloads, workload)()
    acc = getattr(catalog, accelerator)()
    graph, plain = stream_schedule.problem(w, acc, ("tile", 32, 1))
    cycles, energy, feasible = plain.tables(graph)
    tab = CostModel(w, acc).precompute(graph, acc)
    per_cn = tab.sig_of_cn
    assert (feasible == tab.feasible[per_cn]).all()
    assert np.array_equal(cycles, np.where(feasible, tab.cycles[per_cn], 0.0))
    assert np.array_equal(energy, np.where(
        feasible, (tab.e_compute + tab.e_sram)[per_cn], 0.0))


def test_tiling_check_refuses_a_graph_that_misses_outputs():
    from repro.configs import paper_workloads
    from repro.core.cn import Rect
    from repro.hw import catalog
    from bench.reference import stream_schedule
    w, acc = paper_workloads.resnet18(), catalog.mc_hetero()
    graph, _ = stream_schedule.problem(w, acc, ("tile", 32, 1))
    cns = list(graph.cns)
    cn = cns[5]
    ranges = tuple((d, a, b - 1 if d == "OY" and b - a > 1 else b)
                   for d, a, b in cn.out_rect.ranges)
    cns[5] = dataclasses.replace(cn, out_rect=Rect(ranges))
    with pytest.raises(ValueError, match="cover"):
        stream_schedule.check_tiling(w, cns)


def test_check_sample_takes_one_call_of_each_chunk_size():
    from bench.kinds import explore
    calls = [(np.zeros((k, 3), np.int32), None)
             for k in (5, 8, 9, 16, 17, 30, 200, 300, 9, 12)]
    for seed in (1, 2**40 + 1):
        sample = explore.check_sample(seed, calls, max_batch=256)
        rows = [min(256, explore._pow2_at_least(len(g))) for g, _ in sample]
        assert rows == [8, 16, 32, 256]
    assert explore.check_sample(3, calls, 256) == \
        explore.check_sample(3, calls, 256)
    assert explore.fitness_gap({}, [], 8) == float("inf")


def test_serialize_prefix_counts_by_hand():
    # 3 queues of 5 items: 6 operations and 12 bytes an item, 8 a queue
    assert work.serialize_prefix(3, 5) == {"flops": 90, "bytes": 204}


def test_peaks_are_keyed_by_device_kind():
    p = work.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("cpu")
    assert work.roofline_s(197e12, 0.0, p) == 1.0
    assert work.roofline_s(0.0, 819e9 * 2, p) == 2.0
