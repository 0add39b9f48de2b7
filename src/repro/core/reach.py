"""Unreachable-point detection.

Two criteria, both jit-able:

  * ``indegree_unreachable`` — the paper's Definition 1 verbatim: a live point
    with zero in-edges on every layer (and not the entry point). Computed as a
    scatter-add of every out-edge, O(L*N*M0).
  * ``bfs_unreachable`` — graph-search reachability: BFS fix-point from the
    entry point descending through all layers (a superset of what HNSW search
    can visit). This replaces the paper's K=|P| search sweep with a
    deterministic, collective-friendly propagation (see DESIGN.md §2).

Both sweeps walk compacted slot lists in blocks of :data:`SWEEP_ROWS` rows,
so each out-edge is touched once per sweep: rows with no edges on a layer
(free slots, points below that layer) are never read, and the BFS expands
each reached point once per layer through a work queue. A dense fix-point
would scatter all ``N * M0`` edges of a layer per BFS level, which the TPU
lowers to a sort of that many elements each time.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .index import HNSWIndex

#: slots expanded per step of a reachability sweep (rows gathered per step)
SWEEP_ROWS = 2048


def _live(index: HNSWIndex) -> jax.Array:
    return (index.levels >= 0) & ~index.deleted


def _compact(mask: jax.Array, pad: int) -> tuple[jax.Array, jax.Array]:
    """(ids, n): the set slots of ``mask`` in ascending order, then
    ``len(mask)`` as filler, with ``pad`` more fillers so that a block read
    at any offset up to ``n`` stays in bounds."""
    N = mask.shape[0]
    ids = jax.lax.sort(jnp.where(mask, jnp.arange(N, dtype=jnp.int32), N))
    return (jnp.concatenate([ids, jnp.full((pad,), N, jnp.int32)]),
            jnp.sum(mask, dtype=jnp.int32))


def _rows(nbrs_layer: jax.Array, ids: jax.Array, start) -> jax.Array:
    """[B, M0] out-edges of the block of ``ids`` at ``start``; filler ids
    and empty entries read as ``N``."""
    N = nbrs_layer.shape[0]
    src = jax.lax.dynamic_slice(ids, (start,), (min(SWEEP_ROWS, N),))
    nb = nbrs_layer[jnp.minimum(src, N - 1)]
    return jnp.where((src < N)[:, None] & (nb >= 0), nb, N)


@jax.jit
def indegree(index: HNSWIndex) -> jax.Array:
    """Total in-edge count per slot across all layers (from any valid slot)."""
    L, N, M0 = index.neighbors.shape
    B = min(SWEEP_ROWS, N)
    src_exists = index.levels >= 0
    counts = jnp.zeros((N,), jnp.int32)
    for layer in range(L):
        nbrs = index.neighbors[layer]                      # [N, M0]
        ids, n = _compact(src_exists & jnp.any(nbrs >= 0, axis=1), B)

        def block(b, counts, nbrs=nbrs, ids=ids):
            tgt = _rows(nbrs, ids, b * B).reshape(-1)
            return counts.at[tgt].add(1, mode="drop")

        counts = jax.lax.fori_loop(0, (n + B - 1) // B, block, counts)
    return counts


@jax.jit
def indegree_unreachable(index: HNSWIndex) -> jax.Array:
    """bool[N]: live, not entry, zero in-edges on every layer (Definition 1)."""
    return definition1(index, indegree(index))


def definition1(index: HNSWIndex, deg: jax.Array) -> jax.Array:
    """Definition 1 from an already computed :func:`indegree`."""
    unreach = _live(index) & (deg == 0)
    return unreach.at[jnp.clip(index.entry, 0)].set(False)


def _bfs_layer(nbrs_layer: jax.Array, reached: jax.Array) -> jax.Array:
    """Closure of ``reached`` under one layer's out-edges.

    A work queue of slot ids: it starts as the reached slots, and each step
    expands the next block, appending the targets not reached yet (sorted
    and deduplicated) at the tail, until the head meets the tail.
    """
    N, M0 = nbrs_layer.shape
    B = min(SWEEP_ROWS, N)
    E = B * M0
    queue, tail = _compact(reached, E)
    pos = jnp.arange(E, dtype=jnp.int32)

    def cond(state):
        head, tail, _, _ = state
        return head < tail

    def body(state):
        head, tail, queue, reached = state
        tgt = _rows(nbrs_layer, queue, head).reshape(-1)
        tgt = jnp.where(reached[jnp.minimum(tgt, N - 1)], N, tgt)
        tgt = jax.lax.sort(tgt)
        first = (tgt < N) & (tgt != jnp.roll(tgt, 1).at[0].set(-1))
        new = jax.lax.sort(jnp.where(first, tgt, N))
        n_new = jnp.sum(first, dtype=jnp.int32)
        # distinct, ascending indices: no sort inside the scatter
        reached = reached.at[jnp.where(pos < n_new, new, N + pos)].set(
            True, mode="drop", indices_are_sorted=True, unique_indices=True)
        queue = jax.lax.dynamic_update_slice(queue, new, (tail,))
        return jnp.minimum(head + B, tail), tail + n_new, queue, reached

    state = (jnp.int32(0), tail, queue, reached)
    return jax.lax.while_loop(cond, body, state)[3]


@jax.jit
def bfs_reachable(index: HNSWIndex) -> jax.Array:
    """bool[N]: slots visitable by descending search from the entry point."""
    L, N, M0 = index.neighbors.shape
    reached = jnp.zeros((N,), jnp.bool_).at[jnp.clip(index.entry, 0)].set(
        index.entry >= 0)
    for layer in range(L - 1, -1, -1):
        reached = _bfs_layer(index.neighbors[layer], reached)
    return reached


@jax.jit
def bfs_unreachable(index: HNSWIndex) -> jax.Array:
    """bool[N]: live points that descending graph search can never visit."""
    return _live(index) & ~bfs_reachable(index)


@jax.jit
def count_unreachable(index: HNSWIndex) -> jax.Array:
    """(definition1_count, bfs_count) — the paper reports Definition 1."""
    return (jnp.sum(indegree_unreachable(index)),
            jnp.sum(bfs_unreachable(index)))
