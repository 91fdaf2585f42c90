"""Wall-time observability — the REALTIME half of the two-channel split.

This is the only module of `repro.obs` allowed to read the wall clock
(it is pinned REALTIME in `repro.analysis.staticcheck.tiers`, so the
linter's wall-clock rule does not apply here).  A wall tracer times the
same spans the program opens through `Tracer.span` on any clock —
`session.explore`, the GA's `ga.generation` and its `ga.variation`,
`ga.prefilter`, `ga.exact` and `ga.select` children, `fitness.scores`
and `fitness.wait`, `engine.schedule` — in `time.perf_counter` seconds,
the clock the chip benchmark's window and trace mapping read: its
per-layer readers of the GA and the exact scheduler read these spans
and counters, and the spans can name the device's idle gaps.  Each span
also opens a `jax.profiler.TraceAnnotation` of its name, so a profile
taken while it runs (TensorBoard, Perfetto) shows the spans on the host
thread on the profiler's own clock; with no profiler session the
annotation does nothing.  jax is imported when a wall tracer is built,
never at this module's import.

Wall spans are machine-dependent: nothing recorded through a wall tracer
may reach content-keyed records or golden traces.  Everything
deterministic stays on the sim-time channel (`repro.obs.tracing` with
the default logical clock or explicit simulated-cycle spans).

    >>> tr = wall_tracer()
    >>> with tr.span("io"):
    ...     pass
    >>> ev = tr.events[0]
    >>> ev.t1 >= ev.t0
    True
"""
from __future__ import annotations

import time

from repro.obs.events import Sink
from repro.obs.tracing import Tracer


def wall_clock() -> float:
    """Monotonic wall seconds (the REALTIME channel's time base).

        >>> wall_clock() <= wall_clock()
        True
    """
    return time.perf_counter()


def wall_tracer(sink: Sink | None = None) -> Tracer:
    """A `Tracer` on the monotonic wall clock whose spans are mirrored as
    `jax.profiler.TraceAnnotation`s of the same names.

    Spans from a wall tracer measure real elapsed seconds and are
    therefore machine-dependent; confine their output to logs,
    dashboards and benchmark readings, never to content-keyed stores.

        >>> tr = wall_tracer()
        >>> tr.count("pool.dispatch")
        >>> tr.snapshot()["counters"]
        {'pool.dispatch': 1.0}
    """
    from jax.profiler import TraceAnnotation
    return Tracer(sink=sink, clock=time.perf_counter,
                  annotate=TraceAnnotation)
