"""The traffic generator: what an explore cell's window asks of the
explorer, drawn from the run's seed.

A mix's file (`bench/traffic/<name>.json`) gives the GA budget of every
exploration (`pop_size`, `generations`, `prefilter_keep`); the window runs
explorations back to back, each with the next GA seed of `ga_seeds`.

    >>> a, b = ga_seeds(2**40 + 3), ga_seeds(2**40 + 3)
    >>> [next(a) for _ in range(3)] == [next(b) for _ in range(3)]
    True
"""
from __future__ import annotations

import numpy as np


def seed_generator(seed: int, stream: str) -> np.random.Generator:
    """A generator for one named stream of a run's draws: any whole seed,
    also one beyond 64 bits, with streams independent of each other."""
    words = [int(seed) >> (32 * i) & 0xFFFFFFFF
             for i in range(max(1, (int(seed).bit_length() + 31) // 32))]
    tag = [ord(c) for c in stream]
    return np.random.default_rng(np.random.SeedSequence(words + [0] + tag))


def ga_seeds(seed: int):
    """Endless GA seeds, one per exploration, in [0, 2**31)."""
    rng = seed_generator(seed, "ga")
    while True:
        yield int(rng.integers(0, 2**31))
