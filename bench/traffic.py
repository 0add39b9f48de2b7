"""The one traffic generator: a mix file's parameters and a seed in, a
schedule out.

A mix (``traffic/<name>.json``) has up to two parts:

``queries``
    ``rate_per_s`` single k-NN queries, open loop. The count is fixed at
    ``rate_per_s * seconds`` and the arrival times are a Poisson process
    conditioned on that count (sorted uniform times), so every seed sends the
    same amount of work in another order. Query vectors are fresh points of
    uniformly drawn clusters.

``mutations``
    ``loop: "open"``: ``rate_per_s`` units at times drawn as for queries.
    ``loop: "closed"``: a job that keeps at least ``backlog_ops`` ops queued;
    ``pool_units`` units are drawn up front. A unit is one entry of
    ``pattern``, cycled: ``delete`` (a loaded label), ``insert`` (a fresh
    label and a fresh point) or ``reembed`` (a delete of a loaded label and a
    replace that brings its new vector under the same label). Deleted
    labels and fresh points come cluster by cluster: ``per_cluster`` of one
    cluster, then of the next, round robin (the order of the Big-ANN
    streaming track's clustered runbook). Re-embedded labels are drawn
    uniformly with replacement.

Every vector the schedule will need lives in one host table, ``rows``:
the loaded points first, then the fresh points, then the query points.
Labels ``0..loaded-1`` are the loaded rows; fresh inserts take the labels
after them.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from .data import clustered_vectors

DELETE, INSERT, REPLACE = 0, 1, 2
UNIT_OPS = {"delete": 1, "insert": 1, "reembed": 2}


@dataclasses.dataclass
class Schedule:
    rows: np.ndarray          # f32[R, d]: every vector of the run
    loaded: int               # rows[:loaded] are built into the index
    row_label: np.ndarray     # i64[R]: the label each row is stored under
    q_due: np.ndarray         # f64[nq]: seconds after the window opens
    q_row: np.ndarray         # i64[nq]: row of each query
    unit_due: np.ndarray      # f64[nu]: open loop; empty for a closed loop
    unit_ops: list            # per unit: ((kind, label, row), ...)
    closed_backlog: int       # closed loop: ops to keep queued (0: open)
    pattern_len: int          # units in one cycle of the pattern
    ops_per_cycle: int        # ops in one cycle of the pattern


def arrival_times(n: int, seconds: float, rng) -> np.ndarray:
    """``n`` sorted arrival times in ``[0, seconds)``."""
    return np.sort(rng.uniform(0.0, seconds, n))


def _round_robin(groups: list[np.ndarray], start: int, per: int, n: int):
    """Up to ``n`` items: ``per`` from group ``start``, then ``per`` from the
    next group, and so on around, until ``n`` or every group is spent."""
    out, pos, g = [], [0] * len(groups), start
    while len(out) < n and any(p < len(x) for p, x in zip(pos, groups)):
        take = groups[g][pos[g]:pos[g] + per][:n - len(out)]
        out.extend(take.tolist())
        pos[g] += len(take)
        g = (g + 1) % len(groups)
    return np.asarray(out, np.int64)


def make_schedule(config: dict, traffic: dict, seed: int,
                  seconds: float) -> Schedule:
    data = config["data"]
    n, d = int(config["loaded"]), int(config["dim"])
    K, scale = int(data["clusters"]), float(data["scale"])
    X, cluster, centres = clustered_vectors(n, d, K, seed=seed, scale=scale)
    rng = np.random.default_rng([seed, 1])

    def fresh(clusters: np.ndarray) -> np.ndarray:
        noise = rng.normal(size=(len(clusters), d))
        return (centres[clusters] + scale * noise).astype(np.float32)

    qspec = traffic.get("queries") or {}
    nq = int(round(float(qspec.get("rate_per_s", 0)) * seconds))
    q_due = arrival_times(nq, seconds, rng)

    mspec = traffic["mutations"]
    loop = mspec["loop"]
    pattern = list(mspec["pattern"])
    if loop == "open":
        nu = int(round(float(mspec["rate_per_s"]) * seconds))
        unit_due = arrival_times(nu, seconds, rng)
    elif loop == "closed":
        nu = int(mspec["pool_units"])
        unit_due = np.zeros(0)
    else:
        raise ValueError(f"unknown mutation loop {loop!r}")
    kinds = [pattern[i % len(pattern)] for i in range(nu)]
    unknown = set(kinds) - set(UNIT_OPS)
    if unknown:
        raise ValueError(f"unknown unit kinds {sorted(unknown)}")

    per = int(mspec.get("per_cluster", 512))
    by_cluster = [rng.permutation(np.nonzero(cluster == c)[0])
                  for c in range(K)]
    n_del = kinds.count("delete")
    del_labels = _round_robin(by_cluster, int(rng.integers(K)), per, n_del)
    if len(del_labels) < n_del:
        raise ValueError(f"{n_del} deletes asked of {n} loaded points")

    n_ins = kinds.count("insert")
    first = int(rng.integers(K))
    reps = -(-n_ins // (per * K)) if n_ins else 0
    order = [(first + j) % K for j in range(K)] * reps
    ins_rows = fresh(np.repeat(order, per)[:n_ins].astype(np.int64))

    n_re = kinds.count("reembed")
    re_labels = rng.integers(0, n, n_re)
    re_rows = fresh(cluster[re_labels])

    q_rows = fresh(rng.integers(0, K, nq))

    rows = np.concatenate([X, ins_rows, re_rows, q_rows])
    ins0, re0 = n, n + n_ins
    q0 = re0 + n_re
    row_label = np.concatenate([np.arange(n), np.arange(n, n + n_ins),
                                re_labels, np.full(nq, -1)])
    unit_ops, i_del, i_ins, i_re = [], 0, 0, 0
    for kind in kinds:
        if kind == "delete":
            lbl = int(del_labels[i_del])
            unit_ops.append(((DELETE, lbl, -1),))
            i_del += 1
        elif kind == "insert":
            unit_ops.append(((INSERT, n + i_ins, ins0 + i_ins),))
            i_ins += 1
        else:
            lbl = int(re_labels[i_re])
            unit_ops.append(((DELETE, lbl, -1), (REPLACE, lbl, re0 + i_re)))
            i_re += 1
    return Schedule(
        rows=rows, loaded=n, row_label=row_label.astype(np.int64),
        q_due=q_due, q_row=np.arange(q0, q0 + nq), unit_due=unit_due,
        unit_ops=unit_ops,
        closed_backlog=int(mspec.get("backlog_ops", 0)) if loop == "closed"
        else 0,
        pattern_len=len(pattern),
        ops_per_cycle=sum(UNIT_OPS[k] for k in pattern))
