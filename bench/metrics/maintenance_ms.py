"""Device ms per pump of the maintenance consult and its passes: the health
sweep, the Definition-1 re-checks, repair and consolidation."""
from bench.readers import device_ms_per

PROGRAMS = ("index_health", "indegree_unreachable", "repair_unreachable",
            "consolidate_deletes")


def read(run):
    return device_ms_per(run, PROGRAMS, "pumps")
