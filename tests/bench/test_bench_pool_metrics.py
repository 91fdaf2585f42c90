"""The readers of the exact scheduler's pool: `engine.pooled_share`, the
share of the window's exact schedules that the program's replica engines
scored (its `engine.pooled_rows` and `engine.schedules` counters), and
`engine.pool_share`, the share of the window in its `engine.pool` spans;
each reads nothing where the program keeps no such counter or span."""
import pytest

import benchtools
from bench import cells
from bench.record import Spans


def read(name, counters=None, spans=()):
    rec = {"window": (0.0, 10.0), "spans": Spans()}
    rec["spans"].events = list(spans)
    if counters is not None:
        rec["counters"] = counters
    return cells.metric_reader(benchtools.ROOT, name)(rec)


@pytest.mark.parametrize("counters,want", [
    ({"engine.schedules": 96.0, "engine.pooled_rows": 72.0}, 75.0),
    ({"engine.schedules": 96.0, "engine.pooled_rows": 0.0}, 0.0),
    ({"engine.schedules": 96.0}, None),     # a program without the pool
    ({"engine.schedules": 0.0, "engine.pooled_rows": 0.0}, None),
    (None, None),                           # a run with no program tracer
])
def test_reader(counters, want):
    assert read("engine.pooled_share", counters) == (
        want if want is None else pytest.approx(want))


@pytest.mark.parametrize("spans,want", [
    # two pooled batches in the window, one before it, one across its end
    ([("engine.pool", -1.0, -0.5), ("engine.pool", 1.0, 2.5),
      ("engine.schedule", 2.6, 2.9), ("engine.pool", 4.0, 5.0),
      ("engine.pool", 9.5, 11.0)], 30.0),
    ([("engine.schedule", 1.0, 2.0)], None),   # a program without the pool
    ([], None),
])
def test_pool_share_reader(spans, want):
    assert read("engine.pool_share", spans=spans) == (
        want if want is None else pytest.approx(want))
