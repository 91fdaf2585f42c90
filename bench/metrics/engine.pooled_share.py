"""Share of the exact scheduler's schedules in the traced window that
replica engines in host worker processes scored: the program's
`engine.pooled_rows` / `engine.schedules` counters. None where the run
kept no such counters (a program without the pool)."""


def read(rec):
    c = rec.get("counters") or {}
    pooled, schedules = c.get("engine.pooled_rows"), c.get("engine.schedules")
    if pooled is None or not schedules:
        return None
    return 100.0 * pooled / schedules
