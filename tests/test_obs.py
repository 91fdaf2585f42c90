"""Observability subsystem (`repro.obs`): sim-time tracer semantics, trace
export byte-determinism (repeated runs, serial vs process executors),
bottleneck-report consistency against `ScheduleResult` metrics and the
analytical lower bound, bit-identity of content-keyed records under
tracing, heartbeat metric embedding, and the sweep_top fleet dashboard."""
import importlib.util
import json
import os

import numpy as np
import pytest

from repro.api import (DesignSpace, ExplorationSession, FaultInjector,
                       GAConfig, HeartbeatMonitor, build_manifest, run_shard)
from repro.configs.paper_workloads import fsrcnn, squeezenet
from repro.core import CostModel, build_graph
from repro.core.allocator import manual_pingpong
from repro.core.scheduler import ScheduleEngine
from repro.core.vectorized import get_batched_fitness
from repro.hw.catalog import mc_hom_tpu, mc_hom_tpu_chip4
from repro.obs import (NULL_TRACER, InMemorySink, JsonlSink, Tracer,
                       bottleneck_report, chrome_trace_json,
                       schedule_trace_events, serving_trace_events,
                       trace_schedule, validate_trace_events,
                       write_chrome_trace)
from repro.obs.realtime import wall_tracer
from repro.serve.arrivals import poisson_trace
from repro.serve.simulator import PhaseCosts, simulate

pytestmark = pytest.mark.tier1

GA = GAConfig(pop_size=4, generations=2)


def _space():
    return DesignSpace(workloads={"fsrcnn": fsrcnn()},
                       archs={"MC:HomTPU": mc_hom_tpu},
                       granularities=["layer", ("tile", 8, 1)], ga=GA)


def _chip4_engine():
    w, acc = fsrcnn(), mc_hom_tpu_chip4()
    graph = build_graph(w, acc, ("tile", 8, 1))
    return w, acc, ScheduleEngine(graph, CostModel(w, acc), acc)


def _load_tool(name):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(root, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# tracer / sinks
# ---------------------------------------------------------------------------

def test_tracer_nested_spans_and_metrics():
    tr = Tracer()
    with tr.span("outer", point="k0"):
        with tr.span("inner"):
            tr.count("n")
        tr.observe("v", 3.0)
        tr.observe("v", 5.0)
    assert [(e.name, e.depth) for e in tr.events] == \
        [("inner", 1), ("outer", 0)]
    assert tr.events[1].attrs == {"point": "k0"}
    assert tr.events[1].t0 < tr.events[0].t0  # outer opened first
    snap = tr.snapshot()
    assert snap["counters"] == {"n": 1.0}
    assert snap["histograms"]["v"] == {
        "count": 2, "total": 8.0, "mean": 4.0, "min": 3.0, "max": 5.0}


def test_span_closed_on_exception():
    tr = Tracer()
    with pytest.raises(ValueError):
        with tr.span("boom"):
            raise ValueError("x")
    assert [e.name for e in tr.events] == ["boom"]


def test_explicit_sim_cycle_spans():
    tr = Tracer()
    tr.add_span("segment", 0.0, 128.0, seg=0)
    ev = tr.events[0]
    assert (ev.t0, ev.t1, ev.duration, ev.attrs["seg"]) == \
        (0.0, 128.0, 128.0, 0)


def test_jsonl_sink_byte_identical(tmp_path):
    paths = [str(tmp_path / f"{i}.jsonl") for i in (0, 1)]
    for path in paths:
        tr = Tracer(sink=JsonlSink(path))
        with tr.span("a", k=1):
            pass
        tr.add_span("b", 2.0, 4.0)
        tr.close()
    blobs = [open(p, "rb").read() for p in paths]
    assert blobs[0] == blobs[1] and blobs[0]
    assert [json.loads(line)["name"]
            for line in blobs[0].decode().splitlines()] == ["a", "b"]


def test_null_tracer_is_inert():
    with NULL_TRACER.span("x", a=1):
        NULL_TRACER.count("n", 5)
        NULL_TRACER.observe("v", 1.0)
    NULL_TRACER.add_span("y", 0.0, 1.0)
    assert NULL_TRACER.events == []
    assert NULL_TRACER.snapshot() == {"counters": {}, "histograms": {}}


def test_wall_tracer_uses_wall_clock():
    # the REALTIME channel: spans carry monotonically advancing wall times
    from repro.obs.realtime import wall_clock, wall_tracer
    assert wall_clock() <= wall_clock()
    tracer = wall_tracer()
    with tracer.span("op"):
        pass
    (ev,) = tracer.events
    assert ev.name == "op" and ev.t1 >= ev.t0 >= 0.0


# ---------------------------------------------------------------------------
# trace export: schema, lanes, byte determinism
# ---------------------------------------------------------------------------

def test_schedule_trace_byte_identical_across_runs():
    blobs = []
    for _ in range(2):  # fresh engine each run: no shared state
        _, acc, engine = _chip4_engine()
        events, result = trace_schedule(engine,
                                        manual_pingpong(fsrcnn(), acc))
        assert validate_trace_events(events) == []
        blobs.append(chrome_trace_json(events))
    assert blobs[0] == blobs[1]
    assert json.loads(blobs[0])["traceEvents"]  # loadable, non-empty


def test_schedule_trace_lanes_and_segments():
    w, acc, engine = _chip4_engine()
    events, result = trace_schedule(engine, manual_pingpong(w, acc))
    lanes = {e["tid"]: e["args"]["name"] for e in events
             if e["ph"] == "M" and e["name"] == "thread_name"}
    names = list(lanes.values())
    # one lane per core, at least one channel lane, one DRAM lane,
    # one segment-marker lane
    assert sum(n.startswith("core") for n in names) == len(acc.cores)
    assert any(n.startswith("chan") or n == "bus" for n in names)
    assert "dram" in names and "segments" in names
    seg_names = [e["name"] for e in events
                 if e["ph"] == "X" and lanes[e["tid"]] == "segments"]
    assert seg_names and all(n.startswith("segment ") for n in seg_names)
    # every compute interval landed on its core's lane
    for i, intervals in enumerate(result.core_intervals):
        lane_events = [e for e in events
                       if e["ph"] == "X" and e.get("tid") == i]
        assert len(lane_events) == len(intervals)
    # activation counters present and running totals never negative
    counters = [e for e in events if e["ph"] == "C"]
    assert counters and all(e["args"]["bytes"] >= -1e-9 for e in counters)


def test_trace_export_identical_across_executors(tmp_path):
    space = DesignSpace(workloads={"fsrcnn": fsrcnn()},
                        archs={"MC:HomTPU": mc_hom_tpu},
                        granularities=[("tile", 8, 1)], ga=GA)
    by_exec = {}
    for executor in ("serial", "process"):
        sweep = ExplorationSession().run(space, executor=executor,
                                         max_workers=2)
        assert sweep.n_failed == 0
        _, acc, engine = _chip4_engine()
        blobs = [chrome_trace_json(
            trace_schedule(engine, np.asarray(r.allocation))[0])
            for r in sweep.records]
        by_exec[executor] = blobs
    assert by_exec["serial"] == by_exec["process"]


def test_write_chrome_trace_and_validate(tmp_path):
    path = str(tmp_path / "t.json")
    write_chrome_trace([{"name": "a", "ph": "X", "pid": 0, "tid": 0,
                         "ts": 0.0, "dur": 1.0}], path)
    assert json.load(open(path))["traceEvents"][0]["name"] == "a"
    assert validate_trace_events([{"ph": "Z"}]) == \
        ["event 0: unknown ph 'Z'"]
    assert validate_trace_events(
        [{"name": "c", "ph": "C", "pid": 0, "ts": 0.0,
          "args": {"v": "nan-string"}}]) == \
        ["event 0: counter without numeric args"]


# ---------------------------------------------------------------------------
# serving trace
# ---------------------------------------------------------------------------

def test_serving_steps_and_trace():
    costs = PhaseCosts(prefill_cc=100.0, prefill_pj=2.0,
                       decode_cc=10.0, decode_pj=1.0)
    trace = poisson_trace(2000.0, 8, seed=0, decode_tokens=4)
    sim = simulate(trace, costs, batch_slots=2)
    assert len(sim.steps) == sim.n_prefill_steps + sim.n_decode_steps
    assert all(t1 > t0 and kind in ("prefill", "decode")
               and 0 < n <= sim.batch_slots
               for (t0, t1, kind, n) in sim.steps)
    events = serving_trace_events(sim)
    assert validate_trace_events(events) == []
    engine_lane = [e for e in events if e["ph"] == "X" and e["tid"] == 0]
    assert len(engine_lane) == len(sim.steps)
    # one queue-or-serve lifecycle lane per request
    serve_spans = [e for e in events
                   if e["ph"] == "X" and e["name"] == "serve"]
    assert len(serve_spans) == sim.n_requests
    occupancy = [e for e in events if e["ph"] == "C"]
    assert len(occupancy) == len(sim.steps)


def test_serving_tracer_and_bit_identity():
    costs = PhaseCosts(prefill_cc=100.0, prefill_pj=2.0,
                       decode_cc=10.0, decode_pj=1.0)
    trace = poisson_trace(1000.0, 6, seed=1, decode_tokens=3)
    plain = simulate(trace, costs, batch_slots=3)
    tr = Tracer()
    traced = simulate(trace, costs, batch_slots=3, tracer=tr)
    assert plain.to_dict() == traced.to_dict()
    counters = tr.snapshot()["counters"]
    assert counters["serving.requests"] == 6
    assert counters["serving.prefill_steps"] == plain.n_prefill_steps
    assert counters["serving.decode_steps"] == plain.n_decode_steps


# ---------------------------------------------------------------------------
# bottleneck report
# ---------------------------------------------------------------------------

def test_report_consistent_with_schedule_result():
    w, acc, engine = _chip4_engine()
    alloc = manual_pingpong(w, acc)
    result = engine.schedule(alloc, "latency")
    bf = get_batched_fitness(engine, priority="latency")
    lb = float(bf.latency_lower_bound(np.asarray(alloc)[None, :])[0])
    rep = bottleneck_report(result, lower_bound_cc=lb)
    assert rep.makespan_cc == result.latency_cc
    assert rep.energy_pj == result.energy_pj
    # busy fractions are exactly core_busy / makespan
    assert np.allclose(rep.core_busy_frac,
                       np.asarray(result.core_busy) / result.latency_cc)
    assert all(0.0 <= f <= 1.0 for f in rep.core_busy_frac)
    # floors: per-core from core_busy, dram/comm from interval sums
    for i, busy in enumerate(result.core_busy):
        assert rep.floors_cc[f"core{i}"] == float(busy)
    assert rep.dram_busy_cc == pytest.approx(
        sum(e - s for (s, e, _k, _b) in result.dram_intervals))
    # stall accounting: every floor and the analytical bound are true
    # lower bounds on the achieved makespan
    assert lb <= result.latency_cc
    assert max(rep.floors_cc.values()) <= rep.makespan_cc + 1e-9
    assert rep.bound_cc <= rep.makespan_cc + 1e-9
    assert rep.slack_cc == pytest.approx(rep.makespan_cc - rep.bound_cc)
    # renderings are consistent and deterministic
    assert json.loads(rep.to_json()) == rep.to_dict()
    assert rep.to_text() == bottleneck_report(
        result, lower_bound_cc=lb).to_text()
    assert rep.critical_resource in rep.floors_cc or \
        rep.critical_resource == "analytical"


# ---------------------------------------------------------------------------
# tracing is pure observation: bit-identity of content-keyed outputs
# ---------------------------------------------------------------------------

def _content(record) -> dict:
    d = record.to_dict()
    d.pop("runtime_s")   # operator wall timing: excluded from content keys
    return d


@pytest.mark.parametrize("make_tracer", [Tracer, wall_tracer],
                         ids=["logical", "wall"])
def test_tracing_keeps_records_bit_identical(make_tracer):
    plain = ExplorationSession().run(_space())
    tr = make_tracer()
    traced = ExplorationSession(tracer=tr).run(_space())
    assert [_content(r) for r in plain.records] == \
        [_content(r) for r in traced.records]
    counters = tr.snapshot()["counters"]
    assert counters["sweep.computed"] == traced.n_scheduled
    assert counters["engine.schedules"] > 0
    assert counters["ga.generations"] > 0


def test_ga_generation_spans_and_store_hit_counter(tmp_path):
    # a logical clock that keeps the counters as they stood at each read,
    # so each span's two ends give the counter deltas over it
    at: list[dict] = []

    def clock():
        at.append(dict(tr.metrics.counters))
        return float(len(at) - 1)
    tr = Tracer(clock=clock)
    sess = ExplorationSession(cache_dir=str(tmp_path), tracer=tr)
    sess.run(_space())
    gens = [e for e in tr.events if e.name == "ga.generation"]
    assert gens
    for g in gens:
        children = [e for e in tr.events if e.depth == g.depth + 1
                    and g.t0 < e.t0 < g.t1]
        assert children and all(e.t1 < g.t1 for e in children)
        assert {e.name for e in children} <= {
            "ga.variation", "ga.prefilter", "ga.exact", "ga.select"}
        assert [e.name for e in children].count("ga.variation") == 1
        assert [e.name for e in children].count("ga.select") == 2
        before, after = at[int(g.t0)], at[int(g.t1)]
        for attr in ("evaluations", "cache_hits", "prefilter_pruned"):
            name = f"ga.{attr}"
            assert g.attrs[attr] == after.get(name, 0) - before.get(name, 0)
        assert after["ga.generations"] - before.get("ga.generations", 0) == 1
        assert "best" in g.attrs
    before = tr.snapshot()["counters"].get("sweep.store_hits", 0)
    sweep2 = sess.run(_space())   # warm store: all points served from disk
    after = tr.snapshot()["counters"]["sweep.store_hits"]
    assert after - before == sweep2.n_from_store == len(sweep2.records)
    snap = sess.metrics_snapshot()
    assert snap["store_records"] == len(sweep2.records)
    assert snap["store_failures"] == 0
    assert snap["sweep.store_hits"] == after


# ---------------------------------------------------------------------------
# wall spans of one exploration: the tree, the counters, the profiler
# ---------------------------------------------------------------------------

# each span's possible parents (None: a root)
_PARENTS = {"session.explore": {None},
            "cn.graph": {"session.explore"},
            "ga.generation": {"session.explore"},
            "ga.exact": {"session.explore", "ga.generation"},
            "ga.variation": {"ga.generation"},
            "ga.prefilter": {"ga.generation"},
            "ga.select": {"ga.generation"},
            "fitness.scores": {"ga.prefilter"},
            "fitness.wait": {"fitness.scores"},
            "engine.schedule": {"ga.exact", "session.explore"}}


def _explore_traced(tracer, seeds=(0,)):
    """Prefiltered explorations of squeezenet on MC:HomTPU, whose fused
    stacks give the engine checkpoints to resume from."""
    w, acc, gran = squeezenet(), mc_hom_tpu(), ("tile", 16, 1)
    sess = ExplorationSession(prefilter=True, prefilter_keep=0.5,
                              tracer=tracer)
    results = [sess.explore(w, acc, granularity=gran, pop_size=16,
                            generations=3, seed=seed) for seed in seeds]
    return sess, sess.engine(w, acc, gran), results


def _children(events) -> dict:
    """Index of each span's parent (None: a root) -> its children's
    indices, in the order they closed: spans of one depth are disjoint."""
    kids: dict = {}
    for i, e in enumerate(events):
        (p,) = [j for j, q in enumerate(events) if q.depth == e.depth - 1
                and q.t0 <= e.t0 and e.t1 <= q.t1] or [None]
        assert (events[p].name if p is not None else None) in \
            _PARENTS[e.name], e
        kids.setdefault(p, []).append(i)
    return kids


def test_wall_spans_nest_per_generation():
    tr = wall_tracer()
    _, _, (res,) = _explore_traced(tr)
    ev = tr.events
    assert {e.name for e in ev} == set(_PARENTS)
    kids = _children(ev)
    (root,) = kids[None]
    assert ev[root].attrs == {"seed": 0}
    gens = [i for i in kids[root] if ev[i].name == "ga.generation"]
    assert len(gens) == len(res.ga.history)
    # the session builds its CN graph inside the first exploration
    assert [ev[i].name for i in kids[root]] == (
        ["cn.graph", "ga.exact"] + ["ga.generation"] * len(gens)
        + ["engine.schedule"])
    for g in gens:
        names = [ev[i].name for i in kids[g]]
        assert names[0] == "ga.variation" and names.count("ga.select") == 2
        scheduled = [k for i in kids[g] if ev[i].name == "ga.exact"
                     for k in kids[i]]
        assert len(scheduled) == ev[g].attrs["evaluations"]
    screens = [i for i, e in enumerate(ev) if e.name == "fitness.scores"]
    assert screens and all(
        kids[i] and {ev[k].name for k in kids[i]} == {"fitness.wait"}
        for i in screens)


def test_counters_equal_the_ga_and_engine_counts():
    tr = Tracer()
    sess, engine, results = _explore_traced(tr, seeds=(0, 1))
    c = tr.snapshot()["counters"]
    for attr in ("evaluations", "cache_hits", "prefilter_pruned"):
        assert c[f"ga.{attr}"] == sum(getattr(r.ga, attr) for r in results)
    assert c["ga.prefilter_pruned"] > 0
    # every GA evaluation schedules one genome, plus one final schedule per
    # exploration, which records its trace and so is not checkpointed
    assert c["engine.schedules"] == c["ga.evaluations"] + len(results)
    ck = engine.ckpt_stats
    assert c["engine.cns_resumed"] == ck["cns_skipped"] > 0
    assert c["engine.cns_walked"] == \
        ck["cns_scheduled"] + engine.n * len(results)
    assert "engine.cns" not in c and tr.snapshot()["histograms"] == {}
    # on checkpointed schedules alone the counters are the engine's own
    engine.reset_checkpoints()
    tr2 = engine.tracer = Tracer()
    engine.evaluate_population(results[0].ga.pareto_genomes)
    c2 = tr2.snapshot()["counters"]
    assert c2["engine.cns_walked"] == engine.ckpt_stats["cns_scheduled"]
    assert c2["engine.cns_resumed"] == engine.ckpt_stats["cns_skipped"]


def test_wall_spans_are_profiler_annotations(tmp_path):
    import jax
    from jax.profiler import ProfileData
    tr = wall_tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        _explore_traced(tr)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    lines = [[e.name for e in line.events]
             for plane in ProfileData.from_file(str(path)).planes
             if plane.name.startswith("/host:") for line in plane.lines]
    (line,) = [names for names in lines if "ga.generation" in names]
    for name in ("ga.generation", "ga.variation", "ga.select",
                 "ga.prefilter", "ga.exact", "engine.schedule"):
        assert line.count(name) == sum(e.name == name for e in tr.events)


# ---------------------------------------------------------------------------
# heartbeat metrics + quarantine-exit heartbeat
# ---------------------------------------------------------------------------

def test_heartbeat_embeds_metrics_snapshot(tmp_path):
    path = str(tmp_path / "hb.json")
    hb = HeartbeatMonitor(path, total=3,
                          metrics=lambda: {"store_records": 2,
                                           "sweep.computed": 2.0})
    hb.update_failure("boom")
    beat = json.load(open(path))
    assert beat["metrics"] == {"store_records": 2, "sweep.computed": 2.0}
    assert beat["points_per_s"] >= 0.0
    hb.finalize("done")
    assert json.load(open(path))["status"] == "done"


def test_run_shard_heartbeat_has_metrics(tmp_path):
    sweep = run_shard(build_manifest(_space()),
                      cache_dir=str(tmp_path / "store"),
                      heartbeat=str(tmp_path / "hb.json"))
    beat = json.load(open(tmp_path / "hb.json"))
    assert beat["status"] == "done"
    assert beat["metrics"]["store_records"] == len(sweep)
    assert beat["metrics"]["store_failures"] == 0
    assert "points_per_s" in beat


def test_run_shard_quarantine_exit_stamps_heartbeat(tmp_path):
    # every attempt faults, no retries: the exit-3 path must still leave
    # a terminal heartbeat naming the quarantine outcome
    sweep = run_shard(build_manifest(_space()),
                      cache_dir=str(tmp_path / "store"),
                      fault_injector=FaultInjector(seed=0,
                                                   exception_rate=1.0),
                      heartbeat=str(tmp_path / "hb.json"))
    assert len(sweep.records) == 0 and sweep.n_failed > 0
    beat = json.load(open(tmp_path / "hb.json"))
    assert beat["status"] == "quarantined"
    assert beat["failed"] == sweep.n_failed
    assert beat["metrics"]["store_failures"] == sweep.n_failed


# ---------------------------------------------------------------------------
# sweep_top dashboard
# ---------------------------------------------------------------------------

def test_sweep_top_fleet_view(tmp_path):
    top = _load_tool("sweep_top")
    beats, stores = [], []
    for k, status in enumerate(("running", "done")):
        shard = tmp_path / f"shard{k}"
        shard.mkdir()
        beat = {"status": status, "done": 3 + k, "failed": k, "total": 8,
                "shard_index": k, "n_shards": 2, "seq": 4,
                "updated_unix": 0.0, "points_per_s": 1.5,
                "metrics": {"store_records": 3 + k}}
        (shard / "heartbeat.json").write_text(json.dumps(beat))
        rows = [{"key": f"k{k}{i}", "edp": 10.0 * (k + 1) + i,
                 "latency_cc": 5.0 + i} for i in range(3)]
        (shard / "records.jsonl").write_text(
            "\n".join(json.dumps(r) for r in rows) + "\n"
            + '{"torn line')          # in-flight append: must be skipped
        beats.append(str(shard / "heartbeat.json"))
        stores.append(str(shard))
    snap = top.fleet_snapshot(beats, stores)
    t = snap["totals"]
    assert (t["done"], t["failed"], t["total"], t["live"]) == (7, 1, 16, 2)
    assert t["records"] == 6 and t["best_edp"] == 10.0
    assert t["points_per_s"] == pytest.approx(3.0)
    text = top.render(snap)
    assert "fleet: 2/2 live" in text and "done 7/16" in text
    # discovery finds the same fleet from the root directory
    d_beats, d_stores = top.discover(str(tmp_path))
    assert d_beats == sorted(beats) and d_stores == sorted(stores)
    # missing heartbeat renders as a dead shard, not a crash
    snap2 = top.fleet_snapshot(beats + [str(tmp_path / "nope.json")], stores)
    assert snap2["totals"]["live"] == 2
    assert "no beat" in top.render(snap2)
    assert top.read_heartbeat(str(tmp_path / "nope.json")) is None
    assert top.tail_store(str(tmp_path / "empty")) == {
        "records": 0, "best_edp": None, "best_latency_cc": None}


def test_trace_export_tool_is_deterministic(tmp_path):
    tool = _load_tool("trace_export")
    blobs = []
    for sub in ("a", "b"):
        paths = tool.export_all(str(tmp_path / sub))
        blobs.append({name: open(p, "rb").read()
                      for name, p in paths.items()})
    assert blobs[0] == blobs[1]
    for name in ("schedule", "serving"):
        doc = json.loads(blobs[0][name])
        assert doc["traceEvents"]
    report = json.loads(blobs[0]["report_json"])
    assert report["slack_cc"] >= 0.0


# ---------------------------------------------------------------------------
# CN graph span and the engine's edge counters
# ---------------------------------------------------------------------------
def _tiny_prefill():
    import dataclasses
    from repro.configs.deepseek_v2_lite import CONFIG
    from repro.serve.prefill import mla_moe_prefill
    cfg = dataclasses.replace(
        CONFIG, d_model=64, n_heads=4, head_dim=16,
        mla={"kv_lora": 16, "qk_nope": 16, "qk_rope": 8, "v_dim": 16},
        moe={"n_routed": 8, "top_k": 2, "n_shared": 1, "d_ff_expert": 32,
             "first_dense_layers": 1, "d_ff_dense": 128})
    return mla_moe_prefill(cfg, 64, n_layers=2)


def test_cn_graph_span_once_per_engine_build():
    from repro.hw.catalog import mc_hetero
    tr = Tracer()
    sess = ExplorationSession(tracer=tr)
    w, gran = _tiny_prefill(), ("tile", 4, 1)
    sess.engine(w, mc_hetero(), gran)
    sess.engine(w, mc_hetero(), gran)          # cached: no new graph
    sess.engine(w, mc_hom_tpu(), gran)         # new engine, cached graph
    sess.engine(fsrcnn(), mc_hom_tpu(), gran)  # a second graph
    names = [e.name for e in tr.events]
    assert names == ["cn.graph", "cn.graph"]
    assert all(e.t1 > e.t0 and e.depth == 0 for e in tr.events)


def test_edge_counters_equal_the_graphs_counts_for_a_cold_schedule():
    from repro.core.workload import ACT_OPERAND_OPS
    from repro.hw.catalog import mc_hetero
    w, acc = _tiny_prefill(), mc_hetero()
    graph = build_graph(w, acc, ("tile", 4, 1))
    engine = ScheduleEngine(graph, CostModel(w, acc), acc)
    tr = engine.tracer = Tracer()
    engine.schedule(manual_pingpong(w, acc), record=False, checkpoint=False)
    c = tr.snapshot()["counters"]
    assert c["engine.edges_walked"] == graph.n_edges()
    layers = w.layers
    operand = sum(
        1 for (u, v) in graph.edge_bytes
        if graph.cns[u].layer != graph.cns[v].layer and (
            layers[graph.cns[v].layer].op in ACT_OPERAND_OPS
            or layers[graph.cns[v].layer].rows is not None
            or layers[graph.cns[u].layer].rows is not None))
    assert 0 < c["engine.operand_edges"] == operand < graph.n_edges()


def test_edge_counters_absent_without_a_tracer():
    w, acc, engine = _chip4_engine()
    assert engine.tracer is None
    engine.schedule(manual_pingpong(w, acc), record=False)
    tr = engine.tracer = Tracer()
    engine.schedule(manual_pingpong(w, acc), record=False, checkpoint=False)
    c = tr.snapshot()["counters"]
    # a CNN has edges but no activation-operand or routed layer
    assert c["engine.edges_walked"] == engine.graph.n_edges() > 0
    assert c["engine.operand_edges"] == 0
    engine.tracer = None
    res = engine.schedule(manual_pingpong(w, acc), record=False)
    assert res.latency_cc > 0 and tr.snapshot()["counters"] == c
