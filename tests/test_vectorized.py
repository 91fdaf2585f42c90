"""Batched approximate fitness (`repro.core.vectorized`) contract tests.

The vectorized path is a *ranking* approximation, never a metric source,
so the assertions here are the contract's load-bearing pieces: positive
rank correlation with the exact engine across priorities, heterogeneous
cores and 1/2/4-chiplet topologies; a latency lower bound that provably
never exceeds the exact schedule; an exact-rescore oracle bit-identical
to `engine.evaluate`; Pallas-kernel / pure-jnp agreement; and golden
bit-identity of `explore(prefilter=True)` against the unfiltered search
on the committed seed/budget combos.
"""
import numpy as np
import pytest

from repro.configs.paper_workloads import squeezenet
from repro.core import CostModel, build_graph
from repro.core.allocator import feasible_cores_per_layer
from repro.core.ga import GeneticAllocator
from repro.core.scheduler import ScheduleEngine
from repro.core.vectorized import (BIG, NEG, BatchedFitness, core_select,
                                   get_batched_fitness, rank_correlation)
from repro.hw import catalog
from repro.hw.catalog import (mc_hetero, mc_hom_tpu, mc_hom_tpu_chip2,
                              mc_hom_tpu_chip4)

pytestmark = pytest.mark.tier1

GRAN = ("tile", 8, 1)  # coarse bands: small graphs keep the jit traces fast


def _engine(acc):
    w = squeezenet()
    g = build_graph(w, acc, GRAN)
    return w, ScheduleEngine(g, CostModel(w, acc), acc)


def _population(w, acc, k, seed=0, spread=False):
    rng = np.random.default_rng(seed)
    feas = feasible_cores_per_layer(w, acc)
    pop = [np.array([f[rng.integers(len(f))] for f in feas])
           for _ in range(k)]
    if spread:
        # clearly-bad genomes (every layer piled on one core) widen the
        # exact-latency spread past the near-ties of a random homogeneous
        # population — the regime a prefilter must actually rank
        for c in range(acc.n_cores):
            pop.append(np.array([c if c in f else f[0] for f in feas]))
    return np.stack(pop)


@pytest.fixture(scope="module", params=["mc_hetero", "chip1", "chip2",
                                        "chip4"])
def arch_setup(request):
    acc = {"mc_hetero": mc_hetero, "chip1": mc_hom_tpu,
           "chip2": mc_hom_tpu_chip2, "chip4": mc_hom_tpu_chip4}[
               request.param]()
    w, engine = _engine(acc)
    return w, acc, engine


@pytest.mark.parametrize("priority", ["latency", "memory"])
def test_rank_correlation_and_lower_bound(arch_setup, priority):
    """Across hetero cores and 1/2/4-chiplet topologies, both priorities:
    approximate scores rank positively against the exact engine and the
    latency lower bound stays below every exact latency.

    Correlation thresholds are regime-dependent: on the heterogeneous
    quad-core allocation dominates the schedule and the approximation
    ranks near-perfectly; on homogeneous (chiplet) architectures a
    memory-prioritized exact schedule reorders CNs far from wavefront
    order, so only the latency-prioritized ranking is asserted there —
    the lower-bound guarantee holds unconditionally."""
    w, acc, engine = arch_setup
    hetero = acc.name == mc_hetero().name
    pop = _population(w, acc, 24, spread=True)
    bf = get_batched_fitness(engine, priority=priority)
    exact = engine.evaluate_population(pop, priority)
    approx = bf.scores(pop)
    assert approx.shape == exact.shape
    assert np.all(np.isfinite(approx)) and np.all(approx > 0)
    if hetero:
        assert rank_correlation(approx[:, 0], exact[:, 0]) > 0.5
        assert rank_correlation(approx[:, 1], exact[:, 1]) > 0.5
    elif priority == "latency":
        assert rank_correlation(approx[:, 0], exact[:, 0]) > 0.3
        assert rank_correlation(approx[:, 1], exact[:, 1]) > 0.25
    lb = bf.latency_lower_bound(pop)
    assert np.all(lb <= exact[:, 0] * (1 + 1e-9))
    assert np.all(lb > 0)


def test_rescore_is_exact_oracle(arch_setup):
    """`rescore` (the prefilter's survivor path) is bit-identical to the
    engine, and a degenerate 1-genome batch matches `engine.evaluate`."""
    w, acc, engine = arch_setup
    pop = _population(w, acc, 6, seed=3)
    assert np.array_equal(get_batched_fitness(engine).rescore(pop),
                          engine.evaluate_population(pop, "latency"))
    one = pop[0]
    lat, en = engine.evaluate(one)
    assert tuple(get_batched_fitness(engine).rescore(one)[0]) == (lat, en)


def test_batch_size_invariance():
    """Scores are per-genome: chunk padding and batch shape cannot change
    a genome's value."""
    acc = mc_hetero()
    w, engine = _engine(acc)
    pop = _population(w, acc, 16, seed=5)
    bf = get_batched_fitness(engine)
    full = bf.scores(pop)
    np.testing.assert_allclose(bf.scores(pop[:5]), full[:5], rtol=1e-12)
    np.testing.assert_allclose(bf.scores(pop[7:8]), full[7:8], rtol=1e-12)


def test_pallas_serialize_matches_reference():
    """The Pallas wavefront kernel (interpret mode on CPU) and the pure-jnp
    closed form agree on random FCFS queues."""
    import jax.numpy as jnp

    from repro.kernels.ref import serialize_prefix_ref
    from repro.kernels.wavefront import serialize_prefix

    rng = np.random.default_rng(11)
    free0 = jnp.asarray(rng.uniform(0, 50, size=(4, 3)))
    release = jnp.asarray(rng.uniform(0, 100, size=(4, 3, 7)))
    dur = jnp.asarray(rng.uniform(0, 10, size=(4, 3, 7)))
    fin_p, free_p = serialize_prefix(free0, release, dur, interpret=True)
    fin_r, free_r = serialize_prefix_ref(free0, release, dur)
    # float32 prefix ops associate differently between the two lowerings
    np.testing.assert_allclose(np.asarray(fin_p), np.asarray(fin_r),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(free_p), np.asarray(free_r),
                               rtol=1e-5)


def test_use_pallas_consistency():
    """Full scores agree between the Pallas serialization kernel
    (interpreted on CPU) and the pure-jnp reference path."""
    acc = mc_hetero()
    w, engine = _engine(acc)
    pop = _population(w, acc, 8, seed=7)
    on = BatchedFitness(engine, use_pallas=True)
    off = BatchedFitness(engine, use_pallas=False)
    np.testing.assert_allclose(on.scores(pop), off.scores(pop), rtol=1e-9)


def test_contention_models_both_rank(arch_setup):
    """The contention model (per-resource prefix serialization, on the CPU
    through its jnp twin) produces finite, positively-ranking scores."""
    w, acc, engine = arch_setup
    pop = _population(w, acc, 24, seed=9, spread=True)
    exact = engine.evaluate_population(pop, "latency")
    s = get_batched_fitness(engine).scores(pop)
    assert np.all(np.isfinite(s)) and np.all(s > 0)
    assert rank_correlation(s[:, 0], exact[:, 0]) > 0.25


def test_get_batched_fitness_takes_only_the_serialize_model():
    """The `contention` keyword names the one model; any other raises."""
    acc = mc_hetero()
    _, engine = _engine(acc)
    bf = get_batched_fitness(engine)
    assert get_batched_fitness(engine, contention="serialize") is bf
    with pytest.raises(ValueError, match="contention"):
        get_batched_fitness(engine, contention="backlog")


def test_prefilter_keep_one_is_noop():
    """`prefilter_keep=1.0` disables pruning: identical GA outcome and no
    screening counted."""
    acc = mc_hetero()
    w, engine = _engine(acc)
    feas = feasible_cores_per_layer(w, acc)
    bf = get_batched_fitness(engine)

    def _run(**kw):
        engine.reset_checkpoints()
        return GeneticAllocator(
            n_genes=len(feas), feasible_cores=feas,
            evaluate_population=lambda M: engine.evaluate_population(
                M, "latency"),
            pop_size=10, generations=4, seed=0, **kw).run()

    base = _run()
    keep_all = _run(prefilter=bf.prefilter("edp"), prefilter_keep=1.0)
    assert np.array_equal(base.best_genome, keep_all.best_genome)
    assert np.array_equal(base.best_objs, keep_all.best_objs)
    assert keep_all.prefilter_screened == 0
    assert keep_all.prefilter_pruned == 0


def test_explore_prefilter_bit_identity():
    """Golden: on the committed seed/budget combos, `explore` with the
    prefilter enabled reproduces the unfiltered search bit-for-bit — with
    the prefilter actually firing."""
    from repro.api.session import ExplorationSession

    sess = ExplorationSession()
    w, acc = squeezenet(), mc_hetero()
    engine = sess.engine(w, acc, ("tile", 32, 1))
    for seed in (0, 1):
        runs = {}
        for pf in (False, True):
            engine.reset_checkpoints()
            runs[pf] = sess.explore(
                w, acc, granularity=("tile", 32, 1), objective="edp",
                priority="latency", pop_size=16, generations=8, seed=seed,
                prefilter=pf)
        r0, r1 = runs[False], runs[True]
        assert r1.ga.prefilter_screened > 0
        assert r1.ga.prefilter_pruned > 0
        assert r0.latency_cc == r1.latency_cc
        assert r0.energy_pj == r1.energy_pj
        assert r0.peak_mem_bytes == r1.peak_mem_bytes
        assert np.array_equal(r0.allocation, r1.allocation)
        assert r1.ga.evaluations <= r0.ga.evaluations


# ---- core-axis lookups by selection ------------------------------------------

def _core_table(rng, shape, dtype):
    """A table whose entries include `BIG` and `NEG`, or a bool table."""
    if dtype is bool:
        return rng.random(shape) < 0.5
    t = rng.uniform(0.0, 1e6, size=shape).astype(np.float32)
    t.flat[::3] = BIG
    t.flat[1::5] = NEG
    return t


@pytest.mark.parametrize("dtype", [np.float32, bool])
@pytest.mark.parametrize("use", ["column", "route", "held"])
@pytest.mark.parametrize("n_cores", [2, 5, 17])
def test_core_select_equals_fancy_indexing(n_cores, use, dtype):
    """`core_select` returns the table's own bits at the index shapes of
    its uses: a per-CN column picked by the CN's core, a core-pair table
    picked by each edge's producer and consumer cores, and a per-core
    (pieces, rows) table picked by each reader's core, the padding reader
    one past the last core."""
    import jax.numpy as jnp
    rng = np.random.default_rng(n_cores)
    n, d, p = 23, 4, 8
    core = rng.integers(n_cores, size=(n, p))
    if use == "column":
        table = _core_table(rng, (n, n_cores), dtype)       # (CN, core)
        want = np.take_along_axis(table, core, axis=1)
        got = core_select(jnp.asarray(core), jnp.asarray(table.T[:, :, None]))
    elif use == "route":
        table = _core_table(rng, (n_cores, n_cores, 3), dtype)
        pu = rng.integers(n_cores, size=(n, d, p))          # producers' cores
        want = table[pu, core[:, None]]                     # (n, d, p, 3)
        cols = [core_select(jnp.asarray(core)[..., None], jnp.asarray(t))
                [:, None] for t in table]
        got = core_select(jnp.asarray(pu)[..., None], cols)
    else:
        table = _core_table(rng, (n, n_cores + 1, p), dtype)
        table[:, n_cores] = table[:, n_cores - 1]           # the pad's entry
        c_k = rng.integers(n_cores + 1, size=(n, p))
        want = np.take_along_axis(table, c_k[:, None], axis=1)[:, 0]
        got = core_select(jnp.asarray(c_k),
                          [jnp.asarray(table[:, c]) for c in range(n_cores)])
    got = np.asarray(got)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.tobytes() == want.tobytes()


# `BatchedFitness.scores` of `_population(w, acc, 8, seed=17)` as float32
# hex, row-major (latency, energy), keyed by (graph, architecture)
GOLDEN_SCORES = {
    ("llm", "mc_hetero"): """
        0x1.ae31a8p+20 0x1.d36e5ap+23 0x1.6ff9p+20 0x1.b317d6p+23
        0x1.0404b4p+21 0x1.d6e146p+23 0x1.9c5fdp+20 0x1.c69ae4p+23
        0x1.c5ea78p+20 0x1.f867c8p+23 0x1.8d61cp+20 0x1.b1cb6p+23
        0x1.9812p+20 0x1.c8f9c4p+23 0x1.8df9f8p+20 0x1.bf75aap+23""",
    ("llm", "mc_hetero_chip2"): """
        0x1.ae458p+20 0x1.dd0a5ap+23 0x1.702a8p+20 0x1.bf111ep+23
        0x1.03f148p+21 0x1.e1e91ep+23 0x1.9c9a2p+20 0x1.d34abcp+23
        0x1.c5ae08p+20 0x1.fef5dcp+23 0x1.8dd1p+20 0x1.beb0d2p+23
        0x1.97b4p+20 0x1.d42e3ep+23 0x1.8e60f8p+20 0x1.cae182p+23""",
    ("squeezenet", "aimc_4x4"): """
        0x1.bcb19cp+20 0x1.9d1dc8p+26 0x1.025ef6p+21 0x1.9d1dc8p+26
        0x1.f9256cp+20 0x1.9d1dc8p+26 0x1.02107ep+21 0x1.9d1dc8p+26
        0x1.ecb0ecp+20 0x1.9d1dc8p+26 0x1.102e46p+21 0x1.9d1dc8p+26
        0x1.c14b0cp+20 0x1.9d1dc8p+26 0x1.0a36e6p+21 0x1.9d1dc8p+26""",
    ("squeezenet", "mc_hetero"): """
        0x1.64a354p+23 0x1.2609eap+30 0x1.b9e93cp+21 0x1.f97a5cp+29
        0x1.31d8aap+21 0x1.e07ed4p+29 0x1.c86bfep+21 0x1.128b44p+30
        0x1.198ffp+22 0x1.0c06eep+30 0x1.0c97ep+22 0x1.0b20dcp+30
        0x1.1b099cp+22 0x1.2731b2p+30 0x1.e1783ep+21 0x1.1d6e3p+30""",
    ("squeezenet", "mc_hetero_chip2"): """
        0x1.645d5cp+23 0x1.272f64p+30 0x1.bc71a8p+21 0x1.fd9996p+29
        0x1.32e696p+21 0x1.e3cbcp+29 0x1.c81afep+21 0x1.1475e2p+30
        0x1.19f498p+22 0x1.0dc66p+30 0x1.0cd04cp+22 0x1.0d0f36p+30
        0x1.1b5a7cp+22 0x1.28711cp+30 0x1.e1c5b6p+21 0x1.1f3244p+30""",
}


def _golden_engine(graph, arch):
    acc = getattr(catalog, arch)()
    if graph == "squeezenet":
        return _engine(acc)
    from test_llm_workload import graph_of, small
    w = small()
    return w, ScheduleEngine(graph_of(w, acc), CostModel(w, acc), acc)


@pytest.mark.parametrize("graph,arch", sorted(GOLDEN_SCORES))
def test_scores_bit_identical_to_the_golden(graph, arch):
    """Squeezenet (bus transfers, fused segments, spills) and the small
    DeepSeek-V2-Lite graph (footprint pieces) on MC:Hetero and on its
    2-chiplet partition (3 channels, multi-hop routes), and squeezenet on
    the 17-core shared-L1 AiMC 4x4 (the longest select chains)."""
    w, engine = _golden_engine(graph, arch)
    pop = _population(w, engine.accelerator, 8, seed=17)
    got = BatchedFitness(engine).scores(pop)
    want = np.array([float.fromhex(h) for h in
                     GOLDEN_SCORES[graph, arch].split()]).reshape(8, 2)
    assert got.tobytes() == want.tobytes()
