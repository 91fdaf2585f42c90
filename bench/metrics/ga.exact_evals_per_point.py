"""Exact evaluations the GA asks for per design point: the program's
`ga.evaluations` counter over the window (memo misses scheduled by the
exact engine, initial population included) over the `session.explore`
spans that closed in it. None where the run kept no such counter or
span."""


def read(rec):
    lo, hi = rec["window"]
    evals = (rec.get("counters") or {}).get("ga.evaluations")
    points = sum(1 for n, _, e in rec["spans"].events
                 if n == "session.explore" and lo <= e <= hi)
    if evals is None or not points:
        return None
    return evals / points
