"""Share of the traced window in the GA's variation: the program's
`ga.variation` spans (tournament, crossover and mutation in
`repro.core.ga.GeneticAllocator.run`). None where the run recorded no
such span."""


def read(rec):
    lo, hi = rec["window"]
    own = rec["spans"].total("ga.variation", lo, hi)
    return 100.0 * own / (hi - lo) if own else None
