"""Share of the CNs the exact scheduler was asked for that it resumed from
a segment checkpoint instead of walking: the program's
`engine.cns_resumed` / (`engine.cns_resumed` + `engine.cns_walked`)
counters over the traced window. None where the run kept no such
counter."""


def read(rec):
    c = rec.get("counters") or {}
    resumed, walked = c.get("engine.cns_resumed"), c.get("engine.cns_walked")
    if resumed is None or walked is None or not resumed + walked:
        return None
    return 100.0 * resumed / (resumed + walked)
