"""Arithmetic over whole windows.

    >>> rate(300, 30.0)
    10.0
"""
from __future__ import annotations


def rate(count: float, window_s: float) -> float:
    """Work per second over a whole window."""
    if window_s <= 0:
        raise ValueError("a window has positive length")
    return count / window_s

