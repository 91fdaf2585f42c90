"""Microseconds of the exact scheduler per CN it walks: the program's
`engine.schedule` spans in the traced window over its `engine.cns_walked`
counter there (CNs scheduled from a cold start or past a resumed
checkpoint), a cost that stays put when the GA asks for more or fewer
schedules. None where the run kept no such span or counter."""


def read(rec):
    lo, hi = rec["window"]
    walked = (rec.get("counters") or {}).get("engine.cns_walked")
    busy = rec["spans"].total("engine.schedule", lo, hi)
    if not walked or not busy:
        return None
    return 1e6 * busy / walked
