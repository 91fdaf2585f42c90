"""Identical-core symmetry canonicalization of whole genome matrices.

`core_symmetry_canonicalize` maps a `(K, G)` genome matrix to its canonical
form in one vectorized pass. Covered here:
  * it equals the per-genome loop it replaced (kept below as the oracle)
    element for element, on flat, homogeneous and chiplet accelerators,
    for matrices, single genomes, lists and genome prefixes;
  * a seeded exploration with it is bit-identical to one with the oracle
    (GA result, memo counters, engine checkpoint counters);
  * the GA and the session count the rows and batches they canonicalize.
"""
import dataclasses

import numpy as np
import pytest

import repro.api.session as session_mod
from repro.api import ExplorationSession
from repro.configs.paper_workloads import squeezenet
from repro.core.ga import GeneticAllocator
from repro.core.stream_api import core_symmetry_canonicalize
from repro.hw.catalog import mc_hetero, mc_hom_tpu, with_chiplets
from repro.obs import Tracer

pytestmark = pytest.mark.tier1


def _per_row_canonicalize(accelerator):
    """The per-genome loop the vectorized canonicalizer replaced: members of
    a symmetry group take the group's ids in order of first appearance."""
    topo = accelerator.topology
    if topo is None:
        cluster_of = [0] * accelerator.n_cores
    else:
        c2c = topo.core_to_cluster()
        cluster_of = [c2c[c.name] for c in accelerator.cores]
    groups: dict = {}
    for i, c in enumerate(accelerator.cores):
        groups.setdefault((cluster_of[i], dataclasses.replace(c, name="")),
                          []).append(i)
    sym = {i: tuple(members) for members in
           (m for m in groups.values() if len(m) > 1) for i in members}
    if not sym:
        return None

    def canonicalize(genome) -> np.ndarray:
        remap: dict[int, int] = {}
        next_slot: dict[tuple, int] = {}
        out = np.empty(len(genome), dtype=np.int64)
        for idx, g in enumerate(genome):
            g = int(g)
            members = sym.get(g)
            if members is not None:
                m = remap.get(g)
                if m is None:
                    k = next_slot.get(members, 0)
                    m = members[k]
                    next_slot[members] = k + 1
                    remap[g] = m
                g = m
            out[idx] = g
        return out

    return canonicalize


def _oracle_matrix(accelerator):
    """The oracle applied row by row to a (K, G) matrix (the old callers'
    `np.stack([canon(g) for g in genomes])`)."""
    row = _per_row_canonicalize(accelerator)
    if row is None:
        return None
    return lambda genomes: np.stack([row(g) for g in genomes])


ACCELERATORS = {
    "mc-hetero": mc_hetero,
    "mc-hom-tpu": mc_hom_tpu,
    "mc-hom-tpu-2-chiplets": lambda: with_chiplets(mc_hom_tpu(), 2),
}


@pytest.mark.parametrize("k", [1, 7, 512])
@pytest.mark.parametrize("acc_name", sorted(ACCELERATORS))
def test_matrix_canonicalize_matches_per_row_oracle(acc_name, k):
    acc = ACCELERATORS[acc_name]()
    canon, oracle = core_symmetry_canonicalize(acc), \
        _per_row_canonicalize(acc)
    assert canon is not None and oracle is not None
    rng = np.random.default_rng(k)
    genomes = rng.integers(0, acc.n_cores, size=(k, 21))
    before = genomes.copy()
    out = canon(genomes)
    assert out.dtype == np.int64 and out.shape == genomes.shape
    assert np.array_equal(out, np.stack([oracle(g) for g in genomes]))
    assert np.array_equal(genomes, before)  # the input is left as it was
    # a single genome, a list and every prefix of it
    g = genomes[-1]
    assert np.array_equal(canon(g), oracle(g))
    assert np.array_equal(canon(g.tolist()), oracle(g.tolist()))
    for p in range(1, len(g) + 1):
        assert np.array_equal(canon(g[:p]), oracle(g[:p]))
        assert np.array_equal(canon(g[:p]), canon(g)[:p])  # prefix-stable
        assert np.array_equal(canon(genomes[:, :p]), out[:, :p])


def _explore(monkeypatch, acc_fn, make_canon):
    monkeypatch.setattr(session_mod, "core_symmetry_canonicalize",
                        make_canon)
    w, acc, gran = squeezenet(), acc_fn(), ("tile", 16, 1)
    sess = ExplorationSession(prefilter=True, prefilter_keep=0.5)
    res = sess.explore(w, acc, granularity=gran, pop_size=16,
                       generations=4, seed=3)
    return res, dict(sess.engine(w, acc, gran).ckpt_stats)


@pytest.mark.parametrize("acc_name", ["mc-hetero", "mc-hom-tpu"])
def test_exploration_identical_with_per_row_oracle(monkeypatch, acc_name):
    acc_fn = ACCELERATORS[acc_name]
    new, new_ck = _explore(monkeypatch, acc_fn, core_symmetry_canonicalize)
    old, old_ck = _explore(monkeypatch, acc_fn, _oracle_matrix)
    assert new_ck == old_ck and new_ck["resume_hits"] > 0
    a, b = new.ga, old.ga
    assert np.array_equal(a.pareto_genomes, b.pareto_genomes)
    assert np.array_equal(a.pareto_objs, b.pareto_objs)
    assert np.array_equal(a.best_genome, b.best_genome)
    assert a.history == b.history
    assert (a.evaluations, a.queries, a.cache_hits, a.prefilter_screened,
            a.prefilter_pruned) == (b.evaluations, b.queries, b.cache_hits,
                                    b.prefilter_screened, b.prefilter_pruned)
    assert a.cache_hits > 0
    assert (new.latency_cc, new.energy_pj) == (old.latency_cc, old.energy_pj)
    assert np.array_equal(new.allocation, old.allocation)


def _recording(canon, calls):
    def canonicalize(genomes):
        calls.append(len(genomes))
        return canon(genomes)
    return canonicalize


def test_ga_counts_canonical_rows_and_calls():
    acc = mc_hom_tpu()
    calls: list[int] = []
    tr = Tracer()
    feas = [list(range(acc.n_cores))] * 8
    ga = GeneticAllocator(
        8, feas, lambda g: (float(np.sum(g)) + 1.0, float(g[0]) + 1.0),
        pop_size=12, generations=3, seed=0,
        canonicalize=_recording(core_symmetry_canonicalize(acc), calls),
        tracer=tr)
    ga.run()
    counters = tr.snapshot()["counters"]
    # the initial population, then one union per generation
    assert calls == [12] + [24] * 3
    assert counters["ga.canonical_rows"] == sum(calls)
    assert counters["ga.canonical_calls"] == len(calls)


def test_session_counts_canonical_rows_and_calls(monkeypatch):
    calls: list[int] = []
    monkeypatch.setattr(
        session_mod, "core_symmetry_canonicalize",
        lambda acc: _recording(core_symmetry_canonicalize(acc), calls))
    tr = Tracer()
    w, acc = squeezenet(), mc_hom_tpu()
    ExplorationSession(prefilter=True, prefilter_keep=0.5, tracer=tr) \
        .explore(w, acc, granularity=("tile", 16, 1), pop_size=16,
                 generations=3, seed=0)
    counters = tr.snapshot()["counters"]
    # the GA's keys, the prefilter and the exact evaluator, one call each
    # per batch: whole matrices, never one row at a time
    assert counters["ga.canonical_rows"] == sum(calls)
    assert counters["ga.canonical_calls"] == len(calls)
    assert max(calls) == 32 and len(calls) < sum(calls) / 4
