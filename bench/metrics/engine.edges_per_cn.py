"""Dependency edges the exact scheduler resolves per CN it walks: the
program's `engine.edges_walked` counter over its `engine.cns_walked` counter
in the traced window (CNs scheduled from a cold start or past a resumed
checkpoint, and the in-edges of those CNs). Causal attention and the MoE
dispatch raise it; it sets how much of `engine.us_per_cn` is edge work.
None where the run kept no such counters."""


def read(rec):
    c = rec.get("counters") or {}
    edges, walked = c.get("engine.edges_walked"), c.get("engine.cns_walked")
    if edges is None or not walked:
        return None
    return edges / walked
