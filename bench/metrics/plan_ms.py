"""Host milliseconds per pump of the query planner's consult (its index
statistics and their device-to-host reads, once per new epoch): the engine's
``plan_us`` per pump."""
from bench.counts import per


def read(run):
    return per(run, "plan_us", "pumps")
