"""Share of the traced window in the exact scheduler's pooled batches:
the program's `engine.pool` spans (`ScheduleEngine.evaluate_population`
sending a batch to replica engines in host worker processes and waiting
for its rows). The harness's `explore.exact` span wraps only the parent's
own `schedule` calls, so the exact scheduler's time is the two together.
None where the run recorded no such span (a program without the pool)."""


def read(rec):
    lo, hi = rec["window"]
    own = rec["spans"].total("engine.pool", lo, hi)
    return 100.0 * own / (hi - lo) if own else None
