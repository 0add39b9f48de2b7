"""Shared numeric utilities for the tensorised HNSW core.

Everything here is pure jnp, shape-static, and jit/vmap friendly. Distance
kernels live in :mod:`~repro.core.metrics` (pluggable l2/ip/cosine spaces);
the squared-L2 names are re-exported here for the pre-metric-registry call
sites.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .metrics import (dist_pairwise, dist_point, sqdist_pairwise,  # noqa: F401
                      sqdist_point)

# legacy alias (seed name for the L2 pairwise kernel)
pairwise_sqdist = sqdist_pairwise

INF = jnp.float32(jnp.inf)
INVALID = jnp.int32(-1)


def pow2_at_least(n: int) -> int:
    """Smallest power of two >= n (host-side; capacities are always pow2)."""
    n = max(int(n), 1)
    return 1 << (n - 1).bit_length()


def masked_gather_rows(X: jax.Array, ids: jax.Array) -> jax.Array:
    """Gather rows ``X[ids]`` treating negative ids as index 0 (caller masks)."""
    return X[jnp.clip(ids, 0, X.shape[0] - 1)]


def dedup_ids(ids: jax.Array, dists: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Invalidate duplicate ids in a flat candidate list.

    Keeps the first occurrence in id-sorted order; duplicates become
    ``(-1, INF)``. Invalid (-1) entries stay invalid.
    """
    # sorts carry their payload instead of argsort + gather / scatter,
    # which run element by element when vmapped on TPU
    s, perm = jax.lax.sort((ids, jnp.arange(ids.shape[0])), num_keys=1,
                           is_stable=True)
    dup_sorted = jnp.concatenate([jnp.array([False]), (s[1:] == s[:-1]) & (s[1:] >= 0)])
    # unsort the dup mask back to original positions
    _, dup = jax.lax.sort((perm, dup_sorted), num_keys=1)
    ids = jnp.where(dup, INVALID, ids)
    dists = jnp.where(dup, INF, dists)
    return ids, dists


def topk_by_distance(ids: jax.Array, dists: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """Sort candidates ascending by distance, return the first ``k``."""
    order = jnp.argsort(dists)
    return ids[order][:k], dists[order][:k]


def scatter_or(dst: jax.Array, idx: jax.Array, valid: jax.Array) -> jax.Array:
    """``dst[idx] |= valid`` for a bool array, dropping invalid indices."""
    safe = jnp.where(valid, idx, dst.shape[0])  # OOB index -> dropped
    return dst.at[safe].set(True, mode="drop")
