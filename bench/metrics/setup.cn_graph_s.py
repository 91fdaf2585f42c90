"""Seconds of set-up spent building the CN graph: the program's `cn.graph`
spans (CN identification and dependency edges, in
`ExplorationSession.graph`) that closed before the window opened. None
where the run kept no such span."""


def read(rec):
    lo, _ = rec["window"]
    spans = [(s, e) for n, s, e in rec["spans"].events
             if n == "cn.graph" and e <= lo]
    if not spans:
        return None
    return sum(e - s for s, e in spans)
