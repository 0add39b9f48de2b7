"""What each published epoch must hold, and the comparison that decides
``correct``.

``Mirror`` replays the mutations on the host in the order the engine drains
them (first in, first out) and keeps, for every epoch that was published, the
row each label is stored under (``-1``: not live). ``judge`` compares every
answer served in a run with the reference over the live set of the epoch
that served it, and the index left at the end with the mirror's last epoch
and with the paper's Definition 1: after the maintenance passes that rewrote
the graph, no live point may be left without an in-edge.
"""
from __future__ import annotations

import dataclasses
from collections import deque

import numpy as np

from .traffic import DELETE


class Mirror:
    def __init__(self, loaded: int, n_labels: int):
        self.row_of = np.full(n_labels, -1, np.int64)
        self.row_of[:loaded] = np.arange(loaded)
        self.epochs = {0: self.row_of.copy()}
        self.fifo: deque = deque()          # (kind, label, row, unit id)

    def submit(self, ops, unit: int) -> None:
        for kind, label, row in ops:
            self.fifo.append((kind, label, row, unit))

    def drained(self, n: int) -> list[int]:
        """Apply the next ``n`` submitted ops; returns their unit ids."""
        units = []
        for _ in range(n):
            kind, label, row, unit = self.fifo.popleft()
            self.row_of[label] = -1 if kind == DELETE else row
            units.append(unit)
        return units

    def published(self, epoch: int) -> None:
        self.epochs[epoch] = self.row_of.copy()


def definition1_count(levels, deleted, neighbors, entry) -> int:
    """The paper's Definition-1 count: live points, other than the entry
    point, with no in-edge on any layer. Dense numpy arithmetic, the same as
    the dense reference of the program's reachability tests."""
    src = (levels >= 0)[None, :, None] & (neighbors >= 0)
    indegree = np.bincount(neighbors[src], minlength=len(levels))
    unreach = (levels >= 0) & ~deleted & (indegree == 0)
    if entry >= 0:
        unreach[int(entry)] = False
    return int(unreach.sum())


@dataclasses.dataclass
class Served:
    """The answers of a run: one row per query that was due."""
    q_row: np.ndarray         # i64[nq]
    labels: np.ndarray        # i64[nq, k]; -1 where nothing came
    dists: np.ndarray         # f64[nq, k]
    epoch: np.ndarray         # i64[nq]: epoch the answer names; -1 unanswered
    expected_epoch: np.ndarray  # i64[nq]: epoch published when it was served


def judge(reference, rows: np.ndarray, row_label: np.ndarray,
          mirror: Mirror, served: Served, final: dict, k: int) -> dict:
    """Every number that ``correct`` compares; the limits are the
    configuration's.

    ``final`` holds the host copy of the index at the end of the run
    (``labels``, ``levels``, ``deleted``, ``vectors``, ``neighbors``,
    ``entry``)."""
    answered = served.epoch >= 0
    hits = np.zeros(len(served.q_row))
    not_live = 0
    worst = 0.0
    norms = (rows * rows).sum(1)
    for e in np.unique(served.epoch[answered]):
        sel = np.nonzero(served.epoch == e)[0]
        row_of = mirror.epochs.get(int(e))
        if row_of is None:           # an epoch that was never published
            not_live += int((served.labels[sel] >= 0).sum())
            continue
        live = np.zeros(len(rows), bool)
        live[row_of[row_of >= 0]] = True
        Q = rows[served.q_row[sel]]
        gt = row_label[reference.knn(rows, live, Q, k, norms)]
        got = served.labels[sel]
        for i in range(len(sel)):
            hits[sel[i]] = len(set(gt[i].tolist())
                               & set(got[i][got[i] >= 0].tolist()))
        ok = (got >= 0) & (got < len(row_of))
        r = np.where(ok, row_of[np.clip(got, 0, len(row_of) - 1)], -1)
        not_live += int(((got >= 0) & (r < 0)).sum())
        true = reference.sqdist(Q, rows[np.clip(r, 0, None)])
        err = np.abs(served.dists[sel] - true) / np.maximum(true, 1e-12)
        if (r >= 0).any():
            worst = max(worst, float(err[r >= 0].max()))

    live_now = {int(l) for l in np.nonzero(mirror.row_of >= 0)[0]}
    slot_live = (final["levels"] >= 0) & ~final["deleted"]
    slot_labels = final["labels"][slot_live]
    held = set(slot_labels.tolist())
    mismatch = len(held ^ live_now) + (len(slot_labels) - len(held))
    both = np.asarray(sorted(held & live_now), np.int64)
    slot_of = {int(l): s for s, l in zip(np.nonzero(slot_live)[0],
                                         slot_labels)}
    stored = final["vectors"][[slot_of[int(l)] for l in both]] \
        if len(both) else np.zeros((0, rows.shape[1]), rows.dtype)
    want = rows[mirror.row_of[both]] if len(both) else stored
    return {
        "recall_at_10": float(hits[answered].sum() / (k * max(answered.sum(),
                                                              1))),
        "answer_dist_rel_err": worst,
        "not_live_answers": not_live,
        "unanswered": int((~answered).sum()),
        "wrong_epoch": int((answered & (served.epoch
                                        != served.expected_epoch)).sum()),
        "final_label_mismatch": int(mismatch),
        "final_vector_mismatch": int(np.any(stored != want, axis=1).sum()),
        "unreachable_def1": definition1_count(
            final["levels"], final["deleted"], final["neighbors"],
            int(final["entry"])),
    }


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``(correct, checks)``: each number beside its limit. A limit is
    ``[">=", x]`` or ``["<=", x]``."""
    checks, ok = {}, True
    for name, (op, lim) in limits.items():
        v = numbers[name]
        good = v >= lim if op == ">=" else v <= lim
        ok &= bool(good)
        checks[name] = {"value": v, "limit": f"{op} {lim}",
                        "ok": bool(good)}
    return ok, checks
