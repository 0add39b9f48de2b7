"""Mean recall@10 of every answered query against exact search over the
live set of the epoch that served it."""


def read(run):
    return run["oracle"]["recall_at_10"]
