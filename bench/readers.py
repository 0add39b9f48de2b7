"""Arithmetic the metric readers share."""
from __future__ import annotations

import numpy as np


def percentile(values, q: float):
    """The ``q``-th percentile of ``values``; None where there are none."""
    values = np.asarray(values, float)
    return float(np.percentile(values, q)) if len(values) else None


def device_ms_per(run: dict, programs, counter: str):
    """Device milliseconds of ``programs`` in the traced window, per event
    of ``counter`` in the same window; None where either is absent."""
    trace = run.get("trace")
    if not trace:
        return None
    found = [s for name, s in trace["programs"].items() if name in programs]
    n = run["counters"].get(counter, 0)
    if not found or n <= 0:
        return None
    return sum(found) * 1e3 / n


def idle_share(run: dict):
    """1 - device busy / traced window."""
    trace = run.get("trace")
    if not trace or trace["window_s"] <= 0:
        return None
    return 1.0 - trace["busy_s"] / trace["window_s"]
