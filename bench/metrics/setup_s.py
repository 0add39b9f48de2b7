"""Set-up seconds: process start to the window's opening (data, build,
warm-up, and compilation where the cache misses)."""


def read(run):
    return run["setup_s"]
