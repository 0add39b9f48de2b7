"""The paper's Definition-1 count of the final index (``correct`` holds it
to the configuration's limit)."""


def read(run):
    return run["oracle"]["unreachable_def1"]
