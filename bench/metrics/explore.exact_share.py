"""Share of the traced window that the harness's `explore.exact` spans
cover: the host's time in the exact scheduler (`repro.core.scheduler.ScheduleEngine.schedule`, every GA evaluation and the final schedule)."""


def read(rec):
    lo, hi = rec["window"]
    return 100.0 * rec["spans"].total("explore.exact", lo, hi) / (hi - lo)
