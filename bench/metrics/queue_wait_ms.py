"""Milliseconds a query waits in the engine's queue, from its submit to the
dispatch of its batch: the engine's ``query_wait_us`` per query served."""
from bench.counts import per


def read(run):
    return per(run, "query_wait_us", "queries_served")
