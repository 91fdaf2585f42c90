"""DeepSeek-V2-Lite's prefill as a Stream workload (`repro.serve.prefill`):
the activation-operand `matmul` with causal prefixes and the routed row
maps, through the IR, the CN graph, the exact scheduler and the batched
fitness, at a small size (d_model 64, 4 heads, kv_lora 16, 8 experts top 2
plus 1 shared, 64 tokens in 4 bands) and, for the counts, at published
widths."""
import dataclasses
import json
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.configs.deepseek_v2_lite import CONFIG, HF_CONFIG
from repro.core import CostModel
from repro.core.allocator import feasible_cores_per_layer
from repro.core.cn import identify_cns
from repro.core.depgraph import build_cn_graph
from repro.core.scheduler import ScheduleEngine, schedule_reference
from repro.core.stream_api import hw_min_tiles
from repro.core.workload import Workload
from repro.hw.catalog import mc_hetero
from repro.serve.prefill import mla_moe_prefill, route_tokens

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.reference import llm as plain  # noqa: E402

pytestmark = pytest.mark.tier1

SMALL = dataclasses.replace(
    CONFIG, d_model=64, n_heads=4, head_dim=16,
    mla={"kv_lora": 16, "qk_nope": 16, "qk_rope": 8, "v_dim": 16},
    moe={"n_routed": 8, "top_k": 2, "n_shared": 1, "d_ff_expert": 32,
         "first_dense_layers": 1, "d_ff_dense": 128})
T, GRAN, N_LAYERS = 64, ("tile", 4, 1), 3


def small(seed: int = 0) -> Workload:
    return mla_moe_prefill(SMALL, T, n_layers=N_LAYERS, seed=seed)


def graph_of(w, acc=None):
    acc = acc or mc_hetero()
    return build_cn_graph(w, identify_cns(w, GRAN, hw_min_tiles(acc)))


def by_name(w) -> dict:
    return {l.name: l for l in w.layers.values()}


def routes_of(w, seed: int = 0) -> dict:
    e = SMALL.moe
    return {i: route_tokens(T, e["n_routed"], e["top_k"], seed, i)
            for i in range(e["first_dense_layers"], N_LAYERS)}


# ---- IR ---------------------------------------------------------------------

def test_ir_round_trip_and_cache_key():
    w = small()
    back = Workload.from_dict(json.loads(json.dumps(w.to_dict())))
    assert back.cache_key() == w.cache_key()
    for a, b in zip(w.layers.values(), back.layers.values()):
        assert (a.reads, a.roles, a.causal, a.rows) == \
            (b.reads, b.roles, b.causal, b.rows)
    names = by_name(w)
    assert names["L0.scores"].roles == ("a", "a", "b", "b")
    assert names["L0.context"].causal == "C"
    assert names["L1.expert0.gate_up"].rows is not None
    # the routing is part of the content: another draw, another key
    assert small(seed=1).cache_key() != w.cache_key()
    # a layer that sets none of the fields keeps the IR's old form
    plain_layer = names["L0.q_proj"]
    assert not plain_layer.mapped
    assert set(w.to_dict()["layers"][plain_layer.id]) == {
        "name", "op", "dims", "stride", "padding", "inputs", "bits"}
    assert len(w.cache_key()[1][plain_layer.id]) == 7


def test_ir_refuses_malformed_fields():
    w = Workload("bad")
    x = w.add("x", "pool", {"K": 4, "OY": 4})
    with pytest.raises(ValueError, match="role"):
        w.add("m", "matmul", {"K": 4, "C": 4, "OY": 4}, inputs=(x,))
    with pytest.raises(ValueError, match="rows"):
        w.add("r", "conv", {"K": 4, "C": 4, "OY": 2}, inputs=(x,),
              rows=(3, 1))
    with pytest.raises(ValueError, match="reads"):
        w.add("s", "pool", {"K": 4, "OY": 4}, inputs=(x,),
              reads=((0, 4), (0, 4)))


# ---- CNs and edges ------------------------------------------------------------

def test_causal_cns_tile_the_staircase():
    w = small()
    graph = graph_of(w)
    names = by_name(w)
    plain.check_tiling(w, graph.cns)
    cns = {}
    for cn in graph.cns:
        cns.setdefault(cn.layer, []).append(cn)
    h, c_qk, v = 4, 16 + 8, 16
    for name, key in (("L0.scores", "K"), ("L0.softmax", "K"),
                      ("L0.context", "C")):
        layer = names[name]
        bands = [dict((d, (a, b)) for d, a, b in cn.out_rect.ranges)["OY"]
                 for cn in cns[layer.id]]
        assert bands == [(0, 16), (16, 32), (32, 48), (48, 64)]
        for cn, (a, b) in zip(cns[layer.id], bands):
            ranges = cn.out_rect.as_dict()
            if key == "K":
                assert ranges["K"] == (0, b)
            else:
                assert ranges["K"] == (0, v) and cn.reduce == (("C", b),)
        per_key = {"L0.scores": h * c_qk, "L0.softmax": h,
                   "L0.context": h * v}[name]
        assert sum(cn.macs for cn in cns[layer.id]) == per_key * sum(
            b * (b - a) for a, b in bands)
        # the layer's own count is the token-exact triangle
        assert layer.macs == per_key * T * (T + 1) // 2


def test_operand_edges_equal_the_plain_rules():
    w = small()
    graph = graph_of(w)
    plain.check_operand_edges(w, graph)
    names = by_name(w)
    layer_of = [cn.layer for cn in graph.cns]
    first = {lid: layer_of.index(lid) for lid in set(layer_of)}
    kv_b, scores = names["L0.kv_b_proj"].id, names["L0.scores"].id
    nope = 4 * 16
    for t in range(4):
        v = first[scores] + t
        preds = {u: b for u, b in graph.edge_bytes.items()
                 if u[1] == v and layer_of[u[0]] == kv_b}
        # query band t reads the keys of bands 0..t: 16 rows each
        assert sorted(u for u, _ in preds) == [first[kv_b] + j
                                               for j in range(t + 1)]
        assert set(preds.values()) == {16 * nope}
    # K/V stay live until the last band that reads them
    ctx = [cn for cn in graph.cns if cn.layer == names["L0.context"].id]
    assert [cn.discardable_inputs > 0 for cn in ctx] == [True] * 4
    v_bytes = [cn.discardable_inputs - 4 * 16 * cn.out_rect.as_dict()["OY"][1]
               for cn in ctx]
    assert v_bytes == [0, 0, 0, T * 4 * 16]


def test_routed_cns_cover_their_rows_and_the_combine_gathers_them():
    w = small()
    graph = graph_of(w)
    names = by_name(w)
    layer_of = [cn.layer for cn in graph.cns]
    d, e = 64, 8
    for x, toks in enumerate(routes_of(w)[1]):
        gu = names.get(f"L1.expert{x}.gate_up")
        if not len(toks):
            assert gu is None
            continue
        assert gu.rows == tuple(toks.tolist()) and gu.d("OY") == len(toks)
        rows = [r for cn in graph.cns if cn.layer == gu.id
                for r in range(*cn.out_rect.as_dict()["OY"])]
        assert rows == list(range(len(toks)))
        into = sum(b for (u, v), b in graph.edge_bytes.items()
                   if layer_of[v] == gu.id and layer_of[u] != gu.id)
        assert into == len(toks) * (d + e)          # normed rows + scores
        down = names[f"L1.expert{x}.down"]
        out = sum(b for (u, v), b in graph.edge_bytes.items()
                  if layer_of[u] == down.id
                  and layer_of[v] == names["L1.combine"].id)
        assert out == len(toks) * d


# ---- scheduling -------------------------------------------------------------

def _assert_identical(a, b):
    assert a.latency_cc == b.latency_cc
    assert a.energy_pj == b.energy_pj
    assert a.energy_breakdown == b.energy_breakdown
    assert a.peak_mem_bytes == b.peak_mem_bytes
    assert a.mem_events == b.mem_events
    assert a.comm_intervals == b.comm_intervals
    assert a.dram_intervals == b.dram_intervals


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_engine_bit_equal_to_the_references(seed):
    """On random allocations and routings: bit-equal to the seed's oracle
    scheduler, and in latency and energy to the plain LLM scheduler with
    the plain matmul costs (`bench/reference/llm.py`); resumed from a
    layer-barrier snapshot, bit-equal to a cold schedule."""
    w, acc = small(seed), mc_hetero()
    graph = graph_of(w, acc)
    cm = CostModel(w, acc)
    engine = ScheduleEngine(graph, cm, acc)
    _, plain_cost = plain.problem(w, acc, GRAN)
    feas = feasible_cores_per_layer(w, acc)
    rng = np.random.default_rng(seed)
    for _ in range(3):
        alloc = np.array([f[rng.integers(len(f))] for f in feas])
        res = engine.schedule(alloc)
        _assert_identical(res, schedule_reference(graph, cm, alloc, acc))
        assert (res.latency_cc, res.energy_pj) == plain.schedule(
            graph, plain_cost, alloc, acc)
        assert engine.evaluate(alloc) == (res.latency_cc, res.energy_pj)
        assert res.comm_intervals           # operands cross cores
        # a mutated tail resumes from a snapshot holding what each core
        # already has of each producer
        engine.evaluate(alloc, strict_layers=True)
        tail = alloc.copy()
        tail[-5:] = [f[rng.integers(len(f))] for f in feas[-5:]]
        hits = engine.ckpt_stats["resume_hits"]
        got = engine.evaluate(tail, strict_layers=True)
        assert engine.ckpt_stats["resume_hits"] == hits + 1
        cold = engine.schedule(tail, strict_layers=True)
        assert got == (cold.latency_cc, cold.energy_pj)


def _shipped(res, graph, alloc) -> dict:
    """Bytes that crossed the bus, per (producer layer, consumer core)."""
    out: dict = {}
    for _, _, u, v, nbytes in res.comm_intervals:
        key = (graph.cns[u].layer, int(alloc[graph.cns[v].layer]))
        out[key] = out.get(key, 0) + nbytes
    return out


def test_consumers_on_one_core_each_ship_their_part():
    """Consumers on one core that read different parts of one producer CN
    each ship their own part: scores (K) and context (V) of kv_b_proj, the
    kv RMSNorm and k_rope of kv_a_proj's two slices, and two experts' routed
    rows of the normed tokens and router scores, their shared tokens once."""
    w, acc = small(), mc_hetero()
    graph = graph_of(w, acc)
    names = by_name(w)
    engine = ScheduleEngine(graph, CostModel(w, acc), acc)
    simd, tpu0, tpu1 = 4, 2, 3
    alloc = np.array([simd if l.op in ("pool", "add") else tpu0
                      for l in w.layers.values()])
    alloc[names["L0.scores"].id] = alloc[names["L0.context"].id] = tpu1
    x, y = [i for i, toks in enumerate(routes_of(w)[1]) if len(toks)][:2]
    for e in (x, y):
        alloc[names[f"L1.expert{e}.gate_up"].id] = tpu1
    res = engine.schedule(alloc)
    assert (res.latency_cc, res.energy_pj) == plain.schedule(
        *plain.problem(w, acc, GRAN), alloc, acc)
    got = _shipped(res, graph, alloc)

    def out_bytes(name):
        return names[name].out_bytes

    assert got[(names["L0.kv_b_proj"].id, tpu1)] == out_bytes("L0.kv_b_proj")
    assert got[(names["L0.kv_a_proj"].id, simd)] == out_bytes("L0.kv_a_proj")
    both = np.union1d(*(routes_of(w)[1][e] for e in (x, y))).size
    assert both < sum(len(routes_of(w)[1][e]) for e in (x, y))
    assert got[(names["L1.ffn_norm"].id, tpu1)] == both * 64
    assert got[(names["L1.router"].id, tpu1)] == both * 8

def test_batched_fitness_within_float32_of_the_plain_fitness():
    from repro.core.vectorized import BatchedFitness
    w, acc = small(), mc_hetero()
    graph = graph_of(w, acc)
    engine = ScheduleEngine(graph, CostModel(w, acc), acc)
    bf = BatchedFitness(engine, use_pallas=False)
    ref = plain.FitnessReference(*plain.problem(w, acc, GRAN), acc)
    feas = feasible_cores_per_layer(w, acc)
    rng = np.random.default_rng(5)
    pop = np.stack([[f[rng.integers(len(f))] for f in feas]
                    for _ in range(8)])
    np.testing.assert_allclose(bf.scores(pop), ref.scores(pop), rtol=1e-5)


# ---- counts -------------------------------------------------------------------

def test_layer_macs_and_weights_equal_the_equations():
    w = small()
    names = by_name(w)
    want = plain.equation_macs(SMALL, T, N_LAYERS, routes_of(w))
    for name, macs in want.items():
        layer = names[name]
        assert layer.macs == macs, name
        if layer.op == "conv":
            # a linear map's weights: d_in x d_out bytes at 8 bits
            assert layer.weight_bytes == macs // layer.d("OY"), name
        else:
            assert layer.weight_bytes == 0
    priced = {l.name for l in w.layers.values()
              if l.op in ("conv", "matmul")}
    assert priced == set(want)


def test_published_widths_and_linear_macs_per_token():
    """At published widths: each layer's shapes, and the linear MACs per
    token of an MoE layer from the config's own keys (~83 M)."""
    from repro.configs.paper_workloads import deepseek_v2_lite_prefill
    c = HF_CONFIG
    hid, heads = c["hidden_size"], c["num_attention_heads"]
    nope, rope, v = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"])
    lora, fe, k = (c["kv_lora_rank"], c["moe_intermediate_size"],
                   c["num_experts_per_tok"])
    per_token = (hid * heads * (nope + rope) + hid * (lora + rope)
                 + lora * heads * (nope + v) + heads * v * hid
                 + hid * c["n_routed_experts"]
                 + 3 * hid * c["n_shared_experts"] * fe + k * 3 * hid * fe)
    assert 83.0e6 < per_token < 83.2e6
    w = deepseek_v2_lite_prefill()
    t = 4096
    for i in range(1, 5):
        linear = sum(l.macs for l in w.layers.values()
                     if l.name.startswith(f"L{i}.") and l.op == "conv")
        assert linear == per_token * t
    names = by_name(w)
    assert names["L0.gate_up"].d("K") == 2 * c["intermediate_size"]
    assert names["L1.scores"].dims == {"B": heads, "K": t, "C": nope + rope,
                                       "OY": t, "OX": 1}
    assert names["L1.context"].dims == {"B": heads, "K": v, "C": t,
                                        "OY": t, "OX": 1}
    assert names["L1.kv_b_proj"].d("C") == lora
    experts = [l for l in w.layers.values() if l.rows is not None]
    assert len(experts) == 4 * 2 * c["n_routed_experts"]
    # the benchmark's configuration file carries the config as it is run
    body = json.loads((ROOT / "bench" / "configs" /
                       "explore-dsv2lite-prefill-hetero.json").read_text())
    assert {key: body[key] for key in c} == dict(c, num_hidden_layers=5)
    assert len({l.name.split(".")[0] for l in w.layers.values()} - {
        "embed"}) == body["num_hidden_layers"]


def test_routing_draw():
    e = HF_CONFIG["n_routed_experts"]
    k = HF_CONFIG["num_experts_per_tok"]
    loads = []
    for layer in (1, 2):
        routes = route_tokens(4096, e, k, 0, layer)
        hits = np.zeros(4096, int)
        for toks in routes:
            assert np.all(np.diff(toks) > 0)
            hits[toks] += 1
        assert np.all(hits == k)                 # k distinct experts each
        loads.append([len(r) for r in routes])
        assert 2.1 < max(loads[-1]) / np.mean(loads[-1]) < 2.8
    assert loads[0] != loads[1]                  # a fresh draw per layer


def test_explores_through_the_session():
    """The configuration runs through `ExplorationSession.explore` at
    published widths, with no side script."""
    from repro.api.session import ExplorationSession
    from repro.configs.paper_workloads import deepseek_v2_lite_prefill
    w = deepseek_v2_lite_prefill()
    res = ExplorationSession().explore(w, mc_hetero(),
                                       granularity=("tile", 8, 1),
                                       pop_size=4, generations=1, seed=0)
    assert len(res.allocation) == len(w) == 602
    assert res.graph.n == 4816 and res.latency_cc > 0 < res.energy_pj
