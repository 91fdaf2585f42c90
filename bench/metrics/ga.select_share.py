"""Share of the traced window in NSGA-II selection: the program's
`ga.select` spans (union keys, dedup, non-dominated sort, crowding and
survivors in `repro.core.ga.GeneticAllocator.run`; the union's exact
evaluation lies outside them). None where the run recorded no such
span."""


def read(rec):
    lo, hi = rec["window"]
    own = rec["spans"].total("ga.select", lo, hi)
    return 100.0 * own / (hi - lo) if own else None
