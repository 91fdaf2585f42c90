"""What a run records besides its metrics: host spans and compilations.

The harness puts its own spans around the calls into each layer of the
program (`Spans.span`, `Spans.wrap`). Spans are kept on the host's clock;
the trace reduction maps them onto the profiler's clock by the window's
two ends (`bench.trace.capture`), to name what the host did in each idle
gap of the device.
"""
from __future__ import annotations

import contextlib
import functools
import time


class Spans:
    """Host spans `(name, start, end)` on `time.perf_counter`'s clock.

    A span nested in one of the same name is not recorded again, so a
    wrapped method that calls itself through the wrapper counts once."""

    def __init__(self):
        self.events: list[tuple[str, float, float]] = []
        self._open: dict[str, int] = {}

    @contextlib.contextmanager
    def span(self, name: str):
        if self._open.get(name):
            yield
            return
        self._open[name] = 1
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self._open[name] = 0
            self.events.append((name, t0, time.perf_counter()))

    def wrap(self, name: str, fn):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with self.span(name):
                return fn(*args, **kwargs)
        return wrapped

    def total(self, name: str, t0: float, t1: float) -> float:
        """Seconds that spans called `name` cover inside [t0, t1]."""
        return sum(max(0.0, min(e, t1) - max(s, t0))
                   for n, s, e in self.events if n == name)


class CompileCounter:
    """Counts programs that XLA compiles, or loads from the persistent
    cache, while it is armed: the window should count none."""

    _listening = False
    _counters: list["CompileCounter"] = []

    def __init__(self):
        self.armed = False
        self.count = 0
        if not CompileCounter._listening:
            import jax
            jax.monitoring.register_event_duration_secs_listener(
                CompileCounter._on_duration)
            jax.monitoring.register_event_listener(CompileCounter._on_event)
            CompileCounter._listening = True
        CompileCounter._counters.append(self)

    @classmethod
    def _bump(cls):
        for c in cls._counters:
            if c.armed:
                c.count += 1

    @classmethod
    def _on_duration(cls, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            cls._bump()

    @classmethod
    def _on_event(cls, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cls._bump()
