"""Arithmetic of the readers of the engine's own counters."""
from __future__ import annotations


def per(run: dict, counter: str, events: str, scale: float = 1e-3):
    """``counter`` times ``scale`` per event of ``events``, both counted
    over the same part of the window; None where the program keeps no such
    counter or no event happened."""
    counters = run["counters"]
    n = counters.get(events, 0)
    if counter not in counters or n <= 0:
        return None
    return counters[counter] * scale / n
