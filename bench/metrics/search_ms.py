"""Device ms per query batch of the graph tier."""
from bench.readers import device_ms_per

PROGRAMS = ("batch_knn", "batch_dual_search")


def read(run):
    return device_ms_per(run, PROGRAMS, "batches_dispatched")
