"""Share of the traced window that the harness's `explore.fitness` spans
cover: the host's time in the blocking batched-fitness call (`repro.core.vectorized.BatchedFitness.scores`), device time included."""


def read(rec):
    lo, hi = rec["window"]
    return 100.0 * rec["spans"].total("explore.fitness", lo, hi) / (hi - lo)
