"""Neighbour-selection heuristics: HNSW Algorithm 4 generalised with alpha-RNG.

The alpha-RNG rule (DiskANN RobustPrune, used by the paper with alpha in
{1.0, 1.1}): scanning candidates in ascending distance-to-query order, keep
candidate ``c`` iff for every already-selected ``r``:

    alpha * d(r, c) > d(q, c)

With alpha = 1 this is exactly the original HNSW select-neighbours heuristic.

Implementation: a ``while_loop`` over sorted candidates that terminates as
soon as ``m_out`` are selected (or candidates run out), computing dominance
distances LAZILY against the <= m_out selected vectors only — mirroring
hnswlib's lazy evaluation. Worst case O(C * m_out * d) instead of the
O(C^2 * d) pairwise matrix, and typically far less via the early exit.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import INF, INVALID, dedup_ids
from .metrics import dist_point


def select_neighbors(
    q: jax.Array,             # [d] query vector (used only via cand_dists)
    cand_ids: jax.Array,      # [C] int32, -1 = invalid
    cand_vecs: jax.Array,     # [C, d] candidate vectors (garbage ok if invalid)
    cand_dists: jax.Array,    # [C] f32 distance(q, candidate), INF = invalid
    m_out: int,
    alpha: float = 1.0,
    space: str = "l2",
) -> tuple[jax.Array, jax.Array]:
    """Select up to ``m_out`` neighbours by the alpha-RNG rule.

    Returns ``(ids[m_out], dists[m_out])`` padded with (-1, INF), sorted by
    ascending distance to the query. ``space`` picks the metric for the
    candidate-to-candidate dominance distances (must match ``cand_dists``).
    """
    C, d = cand_vecs.shape
    cand_ids, cand_dists = dedup_ids(cand_ids, cand_dists)
    # sort ids with their keys; vectors are read one row per step below
    # instead of gathering the whole [C, d] block into sorted order
    dq, ids, order = jax.lax.sort(
        (cand_dists, cand_ids, jnp.arange(C)), num_keys=1, is_stable=True)

    def cond(state):
        i, selected, sel_vecs, count = state
        # stop when filled, exhausted, or remaining candidates are invalid
        return (i < C) & (count < m_out) & (dq[jnp.minimum(i, C - 1)] < INF)

    def body(state):
        i, selected, sel_vecs, count = state
        v = cand_vecs[order[i]]
        dd = dist_point(space, v, sel_vecs)                   # d(r, c_i)
        active = jnp.arange(m_out) < count
        dom = jnp.any(active & (alpha * dd <= dq[i]))
        keep = (~dom) & (dq[i] < INF)
        # masked writes, not a cond + dynamic_update_slice / scatter: under
        # vmap those become a serial loop over the lanes
        sel_vecs = jnp.where(((jnp.arange(m_out) == count) & keep)[:, None],
                             v[None, :], sel_vecs)
        selected = selected | ((jnp.arange(C) == i) & keep)
        return i + 1, selected, sel_vecs, count + keep.astype(jnp.int32)

    init = (jnp.int32(0), jnp.zeros((C,), jnp.bool_),
            jnp.zeros((m_out, d), cand_vecs.dtype), jnp.int32(0))
    _, selected, _, _ = jax.lax.while_loop(cond, body, init)

    key, ids = jax.lax.sort((jnp.where(selected, dq, INF), ids), num_keys=1,
                            is_stable=True)
    out_ids = jnp.where(key < INF, ids, INVALID)[:m_out]
    return out_ids, key[:m_out]


def alpha_rng_select(
    cand_ids: jax.Array,      # [C] int32, -1 = invalid
    cand_dists: jax.Array,    # [C] f32 distance to the query point
    cand_vecs: jax.Array,     # [C, d] candidate vectors
    m_out: int,
    alpha: float,
    space: str = "l2",
) -> tuple[jax.Array, jax.Array]:
    """Back-compat wrapper (vector-based since the lazy-scan rewrite)."""
    return select_neighbors(None, cand_ids, cand_vecs, cand_dists, m_out,
                            alpha, space)
