"""The per-layer readers of the program's own spans and counters
(`ga.*`, `engine.*`), on a synthetic record, and the trace reduction
naming idle gaps by those spans on a synthetic compact trace."""
import pytest

import benchtools
from bench import cells, trace
from bench.record import Spans

MS = 1_000_000   # ns
READERS = ["ga.variation_share", "ga.select_share", "ga.screen_self_share",
           "ga.exact_evals_per_point", "engine.us_per_cn",
           "engine.resume_share"]

# one exploration in a 10 s window (seconds on the host's clock), harness
# spans around the program's: explore.point > session.explore > ...
HARNESS = [("explore.point", 1.0, 9.0), ("explore.exact", 1.1, 2.0),
           ("explore.fitness", 3.0, 3.5), ("explore.exact", 4.1, 5.0),
           ("explore.exact", 8.0, 8.9)]
PROGRAM = [("session.explore", 1.0, 9.0),
           ("ga.exact", 1.05, 2.05), ("engine.schedule", 1.11, 1.99),
           ("ga.generation", 2.1, 7.9),
           ("ga.variation", 2.1, 2.6),
           ("ga.prefilter", 2.7, 3.8), ("fitness.scores", 3.0, 3.5),
           ("fitness.wait", 3.2, 3.5),
           ("ga.select", 3.9, 4.0),
           ("ga.exact", 4.05, 5.05), ("engine.schedule", 4.11, 4.99),
           ("ga.select", 5.1, 5.4),
           ("engine.schedule", 8.01, 8.89)]
COUNTERS = {"ga.evaluations": 40.0, "ga.cache_hits": 8.0,
            "engine.schedules": 41.0, "engine.cns_walked": 9000.0,
            "engine.cns_resumed": 3000.0}


def _record(program=True) -> dict:
    spans = Spans()
    spans.events = list(HARNESS) + (list(PROGRAM) if program else [])
    rec = {"spans": spans, "window": (0.0, 10.0)}
    if program:
        rec["counters"] = dict(COUNTERS)
    return rec


@pytest.mark.parametrize("name,want", [
    ("ga.variation_share", 5.0),
    ("ga.select_share", 4.0),
    ("ga.screen_self_share", 6.0),      # 1.1 s less 0.5 s of scores
    ("ga.exact_evals_per_point", 40.0),
    ("engine.us_per_cn", 1e6 * 2.64 / 9000),
    ("engine.resume_share", 25.0),
])
def test_reader_on_a_synthetic_record(name, want):
    read = cells.metric_reader(benchtools.ROOT, name)
    assert read(_record()) == pytest.approx(want)


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_nothing_without_the_programs_spans(name):
    """A run whose program records no spans or counters, as the harness
    keeps them today, gives no value and raises nothing."""
    assert cells.metric_reader(benchtools.ROOT, name)(_record(False)) is None


def test_idle_gaps_are_named_by_the_programs_innermost_span():
    # host 0..10 s <-> trace 0..10000 ms; the device busy at 1.5-1.6 s
    # (in a schedule), 3.1-3.3 s (the fitness call) and 6.0-6.1 s (GA)
    dev = {"name": "/device:TPU:0", "modules": [],
           "ops": [["%fusion.1 fusion f32[8]", 1500 * MS, 100 * MS],
                   ["%cc wavefront_serialize", 3100 * MS, 200 * MS],
                   ["%fusion.2 fusion f32[8]", 6000 * MS, 100 * MS]]}
    tr = {"window_ns": [0, 10_000 * MS], "host_window": [0.0, 10.0],
          "devices": [dev]}
    host = trace.host_spans(tr, HARNESS + PROGRAM)
    idle = trace.reduce(tr, host)["idle_by_host"]
    # gaps and the innermost span at their middles: 0-1.5 (0.75: host
    # idle), 1.6-3.1 (2.35: ga.variation), 3.3-6.0 (4.65: engine.schedule),
    # 6.1-10 (8.05: engine.schedule)
    assert idle == pytest.approx({"host idle": 1.5, "ga.variation": 1.5,
                                  "engine.schedule": 2.7 + 3.9})
    assert "explore.exact" not in idle
