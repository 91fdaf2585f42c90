"""Share of the traced window in explorations (`explore.point` spans) but
in neither the exact scheduler nor the batched fitness: the GA and NSGA-II
(`repro.core.ga`), genome canonicalisation and the session's own work."""


def read(rec):
    lo, hi = rec["window"]
    s = rec["spans"]
    own = (s.total("explore.point", lo, hi) - s.total("explore.exact", lo, hi)
           - s.total("explore.fitness", lo, hi))
    return 100.0 * own / (hi - lo)
