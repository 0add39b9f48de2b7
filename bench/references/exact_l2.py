"""Plain reference of the ``l2`` configurations: exact k-NN by squared L2.

The same semantics as the served index (``space="l2"``, k nearest live
points by squared Euclidean distance), by brute force in numpy. Candidates
come from a float32 scan (``bench.data.brute_force_knn``'s arithmetic) and
are re-ranked in float64, so near-ties at the k-th place are settled exactly.
"""
from __future__ import annotations

import numpy as np

#: candidates kept from the float32 scan before the float64 re-rank
SLACK = 16


def sqdist(Q: np.ndarray, V: np.ndarray) -> np.ndarray:
    """float64 squared L2 from ``Q[n, d]`` to ``V[n, m, d]``: ``[n, m]``."""
    diff = V.astype(np.float64) - Q[:, None, :].astype(np.float64)
    return np.einsum("nmd,nmd->nm", diff, diff)


def knn(X: np.ndarray, live: np.ndarray, Q: np.ndarray, k: int,
        norms: np.ndarray | None = None, block: int = 256) -> np.ndarray:
    """Row ids ``[q, k]`` of the ``k`` nearest rows of ``X`` among those with
    ``live`` set, nearest first. ``norms``: ``(X * X).sum(1)``, where the
    caller has it."""
    if norms is None:
        norms = (X * X).sum(1)
    xn = np.where(live, norms, np.inf).astype(np.float32)
    c = min(k + SLACK, int(live.sum()))
    out = np.empty((Q.shape[0], k), np.int64)
    for i in range(0, Q.shape[0], block):
        q = Q[i:i + block]
        d = xn[None, :] - 2.0 * (q @ X.T)
        cand = np.argpartition(d, c - 1, axis=1)[:, :c]
        exact = sqdist(q, X[cand])
        order = np.argsort(exact, axis=1, kind="stable")[:, :k]
        out[i:i + block] = np.take_along_axis(cand, order, 1)
    return out
