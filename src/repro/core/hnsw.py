"""HNSW construction: fresh insert + incremental build (Malkov-Yashunin Alg. 1).

TPU adaptation notes:
  * per-layer control flow is a static Python loop over ``num_layers`` with
    ``lax.cond`` masking, so the whole insert is one fixed-shape jit program;
  * reverse-edge shrinking is vmapped over the selected neighbour slots — each
    overflowing row is re-pruned with the alpha-RNG heuristic from a small
    ``[M0+1, M0+1]`` pairwise matrix (one fused matmul per insert, not per pair).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from .common import INF, INVALID
from .metrics import dist_point
from .index import HNSWIndex, HNSWParams, empty_index, sample_level
from .prune import select_neighbors
from .search import _descend, search_layer


def _pad_row(sel_ids: jax.Array, width: int) -> jax.Array:
    """Pad/truncate a selected id list to a full row of ``width``."""
    row = jnp.full((width,), INVALID, jnp.int32)
    n = min(sel_ids.shape[0], width)
    return row.at[:n].set(sel_ids[:n])


def add_reverse_edges(params: HNSWParams, nbrs_layer: jax.Array,
                      vectors: jax.Array, pid: jax.Array,
                      sel_ids: jax.Array, layer: int,
                      alpha: float) -> jax.Array:
    """Add ``e -> pid`` for every selected neighbour e, shrinking full rows.

    ``nbrs_layer``: [N, M0] adjacency of one layer. Returns the updated layer.
    Vectorised over the selected slots; rows are re-pruned when over capacity.
    """
    m_l = params.m_for_layer(layer)
    M0 = params.M0

    def one(e):
        e_c = jnp.clip(e, 0)
        row = nbrs_layer[e_c]                                 # [M0]
        already = jnp.any(row == pid)
        degree = jnp.sum(row >= 0)
        has_space = degree < m_l
        # append path: first free slot
        free_pos = jnp.argmax(row < 0)
        appended = row.at[free_pos].set(pid)
        # shrink path: re-prune row + pid to m_l
        cand_ids = jnp.concatenate([row, jnp.array([pid], jnp.int32)])
        cand_vecs = vectors[jnp.clip(cand_ids, 0)]
        q = vectors[e_c]
        cand_d = jnp.where(cand_ids >= 0,
                           dist_point(params.space, q, cand_vecs), INF)
        sel, _ = select_neighbors(q, cand_ids, cand_vecs, cand_d, m_l, alpha,
                                  params.space)
        shrunk = _pad_row(sel, M0)
        new_row = jnp.where(already, row, jnp.where(has_space, appended, shrunk))
        return jnp.where(e >= 0, new_row, row), e_c

    new_rows, targets = jax.vmap(one)(sel_ids)                # [S, M0], [S]
    safe = jnp.where(sel_ids >= 0, targets, nbrs_layer.shape[0])
    return nbrs_layer.at[safe].set(new_rows, mode="drop")


def connect_at_layer(params: HNSWParams, nbrs: jax.Array, index: HNSWIndex,
                     x: jax.Array, pid: jax.Array, ep: jax.Array, layer: int,
                     alpha: float):
    """Search + select + wire one layer for point ``pid`` with vector ``x``.

    ``nbrs`` is the ``[L, N, M0]`` adjacency being rewired; returns it with
    the next entry point. The search walks ``nbrs`` itself (with
    ``index``'s other fields), so no second adjacency stays live.
    """
    m_l = params.m_for_layer(layer)
    view = dataclasses.replace(index, neighbors=nbrs)
    ids, dists = search_layer(params, view, x, ep, layer,
                              params.ef_construction)
    ok = (ids >= 0) & (ids != pid)
    # prefer live candidates; when EVERY candidate is mark-deleted, link
    # through the deleted ones anyway (hnswlib semantics) — otherwise the
    # new point comes up with zero edges and is unreachable from the entry
    alive = ok & ~index.deleted[jnp.clip(ids, 0)]
    ok = jnp.where(jnp.any(alive), alive, ok)
    dists = jnp.where(ok, dists, INF)
    ids = jnp.where(ok, ids, INVALID)

    cand_vecs = index.vectors[jnp.clip(ids, 0)]
    sel, _ = select_neighbors(x, ids, cand_vecs, dists, m_l, alpha,
                              params.space)

    layer_nbrs = nbrs[layer].at[pid].set(_pad_row(sel, params.M0))
    layer_nbrs = add_reverse_edges(params, layer_nbrs, index.vectors, pid,
                                   sel, layer, alpha)

    next_ep = jnp.where(ids[jnp.argmin(dists)] >= 0,
                        jnp.clip(ids[jnp.argmin(dists)], 0), ep)
    return nbrs.at[layer].set(layer_nbrs), next_ep


def insert(params: HNSWParams, index: HNSWIndex, x: jax.Array,
           pid: jax.Array, label: jax.Array,
           level_override: jax.Array | None = None) -> HNSWIndex:
    """Insert vector ``x`` into slot ``pid`` with external ``label``."""
    pid = jnp.asarray(pid, jnp.int32)
    label = jnp.asarray(label, jnp.int32)
    key, sub = jax.random.split(index.rng)
    lvl = sample_level(sub, params) if level_override is None else jnp.asarray(
        level_override, jnp.int32)

    # payload writes are safe up-front: a free slot has no in-edges
    vectors = index.vectors.at[pid].set(x.astype(index.vectors.dtype))
    labels = index.labels.at[pid].set(label)
    base = HNSWIndex(vectors, labels, index.levels, index.neighbors,
                     index.deleted, index.entry, index.max_layer, index.count,
                     key)

    def empty_case(ix: HNSWIndex) -> HNSWIndex:
        return HNSWIndex(ix.vectors, ix.labels,
                         ix.levels.at[pid].set(lvl),
                         ix.neighbors,
                         ix.deleted.at[pid].set(False),
                         jnp.int32(pid), lvl.astype(jnp.int32), jnp.int32(1),
                         ix.rng)

    def nonempty_case(ix: HNSWIndex) -> HNSWIndex:
        nbrs = ix.neighbors
        # greedy descent through layers above the insertion level
        ep = _descend(params, ix, x, lvl)
        # connect at layers min(lvl, max_layer)..0
        for layer in range(params.num_layers - 1, -1, -1):
            active = (layer <= lvl) & (layer <= ix.max_layer)

            def do(nbrs_ep, layer=layer):
                return connect_at_layer(params, nbrs_ep[0], ix, x, pid,
                                        nbrs_ep[1], layer, params.alpha)

            nbrs, ep = jax.lax.cond(active, do, lambda t: t, (nbrs, ep))
        new_entry = jnp.where(lvl > ix.max_layer, pid, ix.entry).astype(jnp.int32)
        new_max = jnp.maximum(ix.max_layer, lvl).astype(jnp.int32)
        return HNSWIndex(ix.vectors, ix.labels,
                         ix.levels.at[pid].set(lvl),
                         nbrs,
                         ix.deleted.at[pid].set(False),
                         new_entry, new_max, ix.count + 1, ix.rng)

    return jax.lax.cond(base.count == 0, empty_case, nonempty_case, base)


@partial(jax.jit, static_argnames=("params",))
def insert_jit(params: HNSWParams, index: HNSWIndex, x: jax.Array,
               pid: jax.Array, label: jax.Array) -> HNSWIndex:
    return insert(params, index, x, pid, label)


#: ``build(execution="auto")`` routes to the wave builder at/above this size
#: — where O(log n) waves beat the fori_loop even including compile time;
#: below it the single-program sequential builder compiles far cheaper
WAVE_BUILD_MIN_N = 1024


def build(params: HNSWParams, vectors: jax.Array,
          labels: jax.Array | None = None, seed: int = 0,
          capacity: int | None = None,
          execution: str = "auto") -> HNSWIndex:
    """Build an index over ``vectors[n, d]``; point ``i`` lands in slot ``i``.

    ``execution="wave"`` constructs in ``O(log n)`` geometrically-growing
    conflict-free waves (:func:`~repro.core.batch_update.build_batch` — a
    bounded set of compiled wave programs instead of ``n`` sequential
    insert steps); ``execution="sequential"`` keeps the original jitted
    ``fori_loop`` insert-at-a-time builder (the parity baseline).
    ``"auto"`` (default) picks waves from :data:`WAVE_BUILD_MIN_N` points —
    below that the fori_loop's single cheap compile wins wall-clock.
    """
    if execution not in ("auto", "wave", "sequential"):
        raise ValueError(f"unknown build execution {execution!r}; expected "
                         f"'auto', 'wave', or 'sequential'")
    if execution == "auto":
        execution = "wave" if vectors.shape[0] >= WAVE_BUILD_MIN_N \
            else "sequential"
    if execution == "wave":
        from .batch_update import build_batch
        return build_batch(params, vectors, labels, seed=seed,
                           capacity=capacity)
    n, d = vectors.shape
    capacity = capacity or n
    labels = jnp.arange(n, dtype=jnp.int32) if labels is None else labels

    index = empty_index(params, capacity, d, seed, dtype=vectors.dtype)

    @partial(jax.jit, static_argnames=())
    def run(index, vectors, labels):
        def body(i, ix):
            return insert(params, ix, vectors[i], i, labels[i])
        return jax.lax.fori_loop(0, n, body, index)

    return run(index, vectors, labels)
