"""Layered beam search over the tensorised HNSW graph.

``search_layer`` is the paper's K-NN-SEARCH building block (HNSW Algorithm 2)
re-thought for TPU: a fixed-size sorted beam replaces the two heaps, neighbour
expansion is a dense ``[M0, d]`` gather + contraction, and the candidate/result
split is implicit — any unexpanded entry inside the sorted top-ef beam is a
candidate; entries pushed past ef by the merge-sort are exactly the ones the
classical algorithm would discard (`c > f` break).

Distances dispatch statically on ``params.space`` through the metric
registry (:mod:`~repro.core.metrics`), so each space compiles its own
program with the kernel inlined.

Filtered search: an optional slot-level ``allow`` mask threads a SECOND
fixed-size beam through the traversal — the walk still expands through
disallowed points (they carry graph connectivity, like markDeleted points),
but only allowed points are merged into the result beam. That is hnswlib's
filter-functor semantics pushed into candidate scoring: predicate kNN keeps
full recall instead of post-filtering k results down to a remnant.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .common import INF, INVALID
from .index import HNSWIndex, HNSWParams
from .metrics import dist_point


def greedy_layer(params: HNSWParams, index: HNSWIndex, q: jax.Array,
                 ep: jax.Array, layer: int,
                 active: jax.Array | bool = True) -> jax.Array:
    """ef=1 greedy descent within one layer; returns the improved entry point.

    ``active=False`` makes the walk take no step (``ep`` comes back
    clipped). The flag seeds the loop's own predicate instead of wrapping
    the call in a ``lax.cond``: under ``vmap`` a cond with a batched
    predicate becomes a select over both branches' operands, which
    broadcasts the whole index to every lane.
    """
    nbrs_l = index.neighbors[layer]

    def cond(state):
        _, _, improved = state
        return improved

    def body(state):
        cur, cur_d, _ = state
        nbrs = nbrs_l[cur]
        valid = nbrs >= 0
        nv = index.vectors[jnp.clip(nbrs, 0)]
        nd = jnp.where(valid, dist_point(params.space, q, nv), INF)
        j = jnp.argmin(nd)
        best_d = nd[j]
        improved = best_d < cur_d
        cur = jnp.where(improved, jnp.clip(nbrs, 0)[j], cur)
        cur_d = jnp.minimum(best_d, cur_d)
        return cur, cur_d, improved

    d0 = dist_point(params.space, q, index.vectors[jnp.clip(ep, 0)])
    cur, _, _ = jax.lax.while_loop(
        cond, body, (jnp.clip(ep, 0), d0, jnp.asarray(active, jnp.bool_)))
    return cur


def search_layer(params: HNSWParams, index: HNSWIndex, q: jax.Array,
                 ep: jax.Array, layer: int, ef: int,
                 max_steps: int | None = None,
                 allow: jax.Array | None = None) -> tuple[jax.Array, jax.Array]:
    """Beam search at ``layer``; returns ``(ids[ef], dists[ef])`` sorted asc.

    Traverses through deleted points (hnswlib semantics) — the caller filters
    deleted ids out of returned results. With ``allow`` (bool[N] slot mask),
    traversal is unchanged but the returned beam contains only allowed slots.
    """
    M0 = params.M0
    steps_cap = max_steps if max_steps is not None else params.steps_for(ef)
    nbrs_l = index.neighbors[layer]
    filtered = allow is not None

    ep = jnp.clip(ep, 0)
    d0 = dist_point(params.space, q, index.vectors[ep])
    dists = jnp.full((ef,), INF).at[0].set(d0)
    ids = jnp.full((ef,), INVALID, jnp.int32).at[0].set(ep)
    expanded = jnp.zeros((ef,), jnp.bool_)
    if filtered:
        ep_ok = allow[ep]
        res_d = jnp.full((ef,), INF).at[0].set(jnp.where(ep_ok, d0, INF))
        res_i = jnp.full((ef,), INVALID, jnp.int32).at[0].set(
            jnp.where(ep_ok, ep, INVALID))
    else:
        res_d = res_i = None

    def frontier(dists, ids, expanded):
        return jnp.where(expanded | (ids < 0), INF, dists)

    def cond(state):
        dists, ids, expanded, steps = state[:4]
        return (jnp.min(frontier(dists, ids, expanded)) < INF) & (steps < steps_cap)

    def body(state):
        dists, ids, expanded, steps = state[:4]
        f = frontier(dists, ids, expanded)
        i = jnp.argmin(f)
        cur = jnp.clip(ids[i], 0)
        expanded = expanded | (jnp.arange(ef) == i)

        nbrs = nbrs_l[cur]                            # [M0]
        valid = nbrs >= 0
        nc = jnp.clip(nbrs, 0)
        # a neighbour already in the beam is not new. One seen and dropped
        # before scores no better now, and the beam's worst distance only
        # falls, so the merge drops it again: no visited set over all N
        # slots is needed (under vmap the loop would carry one per lane and
        # select over it on every step)
        fresh = valid & ~jnp.any(nc[:, None] == ids[None, :], axis=1)

        nv = index.vectors[nc]                        # [M0, d]
        nd = jnp.where(fresh, dist_point(params.space, q, nv), INF)

        all_d = jnp.concatenate([dists, nd])
        all_i = jnp.concatenate([ids, jnp.where(fresh, nc, INVALID)])
        all_e = jnp.concatenate([expanded, jnp.zeros((M0,), jnp.bool_)])
        # one stable sort carries ids and flags with the keys: an argsort
        # plus gathers becomes a serial per-element gather under vmap
        all_d, all_i, all_e = jax.lax.sort((all_d, all_i, all_e),
                                           num_keys=1, is_stable=True)
        out = (all_d[:ef], all_i[:ef], all_e[:ef], steps + 1)
        if filtered:
            res_d, res_i = state[4:]
            a_ok = (fresh & allow[nc]
                    & ~jnp.any(nc[:, None] == res_i[None, :], axis=1))
            rd = jnp.concatenate([res_d, jnp.where(a_ok, nd, INF)])
            ri = jnp.concatenate([res_i, jnp.where(a_ok, nc, INVALID)])
            rd, ri = jax.lax.sort((rd, ri), num_keys=1, is_stable=True)
            out = out + (rd[:ef], ri[:ef])
        return out

    init = (dists, ids, expanded, jnp.int32(0))
    if filtered:
        init = init + (res_d, res_i)
    final = jax.lax.while_loop(cond, body, init)
    if filtered:
        return final[5], final[4]
    return final[1], final[0]


def _descend(params: HNSWParams, index: HNSWIndex, q: jax.Array,
             down_to_layer: jax.Array) -> jax.Array:
    """Greedy descent from the top layer to (but not including) ``down_to_layer``."""
    ep = jnp.clip(index.entry, 0)
    for layer in range(params.num_layers - 1, 0, -1):
        active = (layer <= index.max_layer) & (layer > down_to_layer)
        ep = greedy_layer(params, index, q, ep, layer, active)
    return ep


def knn_search(params: HNSWParams, index: HNSWIndex, q: jax.Array,
               k: int, ef: int | None = None,
               allow: jax.Array | None = None
               ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Full HNSW k-NN query. Returns ``(labels[k], slot_ids[k], dists[k])``.

    Deleted and free slots are excluded from results (but traversed through).
    ``allow`` (bool[N] over slots) restricts results to allowed slots without
    hurting traversal — see :func:`search_layer`.
    """
    ef = ef or params.ef_search
    ef = max(ef, k)
    ep = _descend(params, index, q, jnp.int32(0))
    ids, dists = search_layer(params, index, q, ep, 0, ef, allow=allow)
    ok = (ids >= 0) & ~index.deleted[jnp.clip(ids, 0)] & (index.levels[jnp.clip(ids, 0)] >= 0)
    dists = jnp.where(ok, dists, INF)
    ids = jnp.where(ok, ids, INVALID)
    order = jnp.argsort(dists)
    ids_k = ids[order][:k]
    dists_k = dists[order][:k]
    labels_k = jnp.where(ids_k >= 0, index.labels[jnp.clip(ids_k, 0)], INVALID)
    return labels_k, ids_k, dists_k


@partial(jax.jit, static_argnames=("params", "k", "ef"))
def batch_knn(params: HNSWParams, index: HNSWIndex, Q: jax.Array,
              k: int, ef: int | None = None,
              allow: jax.Array | None = None):
    """vmapped batched query: ``Q[b, d] -> (labels[b,k], ids[b,k], dists[b,k])``.

    ``allow`` is one slot mask shared by the whole batch (a per-query mask
    would defeat the fixed-shape bucketing — split batches by predicate
    instead).
    """
    return jax.vmap(lambda q: knn_search(params, index, q, k, ef, allow))(Q)
