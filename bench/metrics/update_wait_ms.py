"""Milliseconds a mutation waits in the engine's queue, from its enqueue to
the start of the drain that applies it: the engine's ``update_wait_us`` per
op applied."""
from bench.counts import per


def read(run):
    return per(run, "update_wait_us", "updates_applied")
