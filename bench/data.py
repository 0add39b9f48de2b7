"""Seeded vectors and the exact k-NN reference, kept with the benchmark.

``clustered_vectors`` draws the same mixture of Gaussians, number for number,
as ``repro.data.clustered_vectors`` and also returns each row's cluster and
the cluster centres, which the traffic needs. ``brute_force_knn`` is the same
blocked squared-L2 search as ``repro.data.brute_force_knn``. Both are copies,
so that a change to the program's data module cannot move the yardstick.
"""
from __future__ import annotations

import numpy as np


def clustered_vectors(n: int, d: int, n_clusters: int = 32, seed: int = 0,
                      scale: float = 0.15):
    """``(X[n, d] f32, cluster[n], centres[n_clusters, d] f64)``: points
    around unit-norm centres."""
    rng = np.random.default_rng(seed)
    centres = rng.normal(size=(n_clusters, d))
    centres /= np.linalg.norm(centres, axis=1, keepdims=True)
    assign = rng.integers(0, n_clusters, size=n)
    X = centres[assign] + scale * rng.normal(size=(n, d))
    return X.astype(np.float32), assign, centres


def brute_force_knn(X: np.ndarray, Q: np.ndarray, k: int) -> np.ndarray:
    """Exact ground truth ids ``[q, k]`` by squared L2, in blocks of 256
    queries."""
    out = np.empty((Q.shape[0], k), np.int64)
    xn = (X * X).sum(1)
    for i in range(0, Q.shape[0], 256):
        q = Q[i:i + 256]
        d = xn[None, :] - 2 * q @ X.T
        if k < X.shape[0]:
            top = np.argpartition(d, k - 1, axis=1)[:, :k]
            order = np.argsort(np.take_along_axis(d, top, 1), axis=1)
            out[i:i + 256] = np.take_along_axis(top, order, 1)
        else:
            out[i:i + 256] = np.argsort(d, axis=1)[:, :k]
    return out
