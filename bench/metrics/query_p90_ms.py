"""90th percentile of query latency over every query due in the window,
from its due time to its answer."""
from bench.readers import percentile


def read(run):
    return percentile(run["query_latency_ms"], 90)
