"""Device ms per drain of the wave ingest: the delete phase and the wave
programs."""
from bench.readers import device_ms_per

PROGRAMS = ("_apply_wave", "_apply_deletes_jit")


def read(run):
    return device_ms_per(run, PROGRAMS, "update_drains")
