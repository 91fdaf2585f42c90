"""Share of the traced window in the GA's offspring screen outside the
batched fitness: the program's `ga.prefilter` spans (novel-key lookup,
the session's canonicalisation, the approximate NSGA ranking) less the
`fitness.scores` spans inside them. None where the run recorded no
`ga.prefilter` span."""
from bisect import bisect_right


def read(rec):
    lo, hi = rec["window"]
    events = rec["spans"].events
    screens = sorted((s, e) for n, s, e in events if n == "ga.prefilter")
    if not screens:
        return None
    starts = [s for s, _ in screens]
    own = rec["spans"].total("ga.prefilter", lo, hi)
    for n, s, e in events:
        if n == "fitness.scores":
            i = bisect_right(starts, s) - 1
            if i >= 0 and e <= screens[i][1]:
                own -= max(0.0, min(e, hi) - max(s, lo))
    return 100.0 * own / (hi - lo)
