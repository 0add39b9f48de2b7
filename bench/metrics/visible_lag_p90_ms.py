"""90th percentile over the mutations due in the window, from due time to
the publish of the epoch that holds the whole mutation."""
from bench.readers import percentile


def read(run):
    return percentile(run["mutation_lag_ms"], 90)
