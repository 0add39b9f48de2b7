"""The chip the run stands on: the device check, the peak table and JAX's
compile events."""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def require_devices(chips: int) -> list:
    """The first ``chips`` TPU devices, whose kind the peak table knows."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's devices are {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX finds "
                     f"{len(devs)}")
    peaks(devs[0].device_kind)
    return devs[:chips]


def peaks(kind: str) -> dict:
    """Published peaks of one chip of ``kind``; a kind missing from the
    table is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in {PEAKS.name}")
    return table[kind]


def describe(devs: list) -> dict:
    """``platform``, ``kind``, ``count`` and the peak bytes in use on the
    fullest chip."""
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


class CompileClock:
    """Programs the process had to compile or read back from the persistent
    cache, from JAX's monitoring events: ``backend_compile_duration`` fires
    for either (a program that was ready in memory fires none), and
    ``cache_hits`` for a read-back alone."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_loads = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_loads += 1
