"""Pumps per second of the window (a metric the tests add as a file)."""


def read(run):
    return run["n_window"] / run["window_s"]
