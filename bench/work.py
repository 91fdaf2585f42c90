"""Analytic work: the operations and bytes an algorithm needs for one call,
from its shapes alone, whatever implements it; and the chip's peaks.

    >>> serialize_prefix(rows=2, width=4)
    {'flops': 48, 'bytes': 112}
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of `device_kind`; an unknown device is
    an error, never a default."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of operations over
    peak bf16 FLOP/s and bytes over peak HBM bytes/s."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])


def serialize_prefix(rows: int, width: int) -> dict:
    """FCFS serialization of `rows` queues of `width` items (float32).

    Per item: one add of the prefix sum, two for `r - (S - d)`, one max of
    the running maximum, one max against the queue's free time and one add
    for the finish time: 6 operations; it reads its release and duration
    and writes its finish, 12 bytes. Per queue: the free time is read and
    written, 8 bytes."""
    items = rows * width
    return {"flops": 6 * items, "bytes": 12 * items + 8 * rows}
