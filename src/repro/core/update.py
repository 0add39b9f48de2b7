"""Real-time update algorithms: markDelete + replaced_update family.

This module is the paper's primary contribution:

  * ``hnsw_ru``     — baseline hnswlib ``replaced_update``: repair EVERY one-hop
                      neighbour of the deleted point from the shared one-hop ∪
                      two-hop candidate pool (O(M^3)/layer).
  * ``mn_ru_alpha`` — repair only MUTUAL neighbours, same shared two-hop pool.
  * ``mn_ru_beta``  — mutual neighbours, per-vertex pool N(v) ∪ N(d) ∪ {new},
                      alpha = 1.0 (paper Algorithm 2, O(M^2)/layer).
  * ``mn_ru_gamma`` — beta with alpha-RNG alpha = 1.1.
  * ``mn_thn_ru``   — gamma + also repair two-hop vertices that point at d.

All variants finish with the layer-inheriting re-insert (paper Algorithm 3).

TPU adaptation: the shared two-hop candidate pool means ONE
``[C, d] @ [d, C]`` MXU matmul amortises the pairwise distances across all
repairs; per-vertex pools are vmapped. No per-pair distance calls anywhere.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .common import INF, INVALID, dedup_ids
from .index import HNSWIndex, HNSWParams
from .hnsw import _pad_row, add_reverse_edges, connect_at_layer, insert
from .metrics import dist_point
from .prune import alpha_rng_select, select_neighbors
from .search import _descend, search_layer
from .strategies import (BUILTIN_STRATEGIES, UpdateStrategy,  # noqa: F401
                         get_executor, get_strategy, list_strategies,
                         register_executor, register_strategy)

# back-compat alias: the variant family now lives in core.strategies
VARIANTS = BUILTIN_STRATEGIES


def slot_of_label(index: HNSWIndex, label: jax.Array) -> jax.Array:
    """Return the slot holding ``label`` (-1 if absent). O(N) masked scan."""
    hits = (index.labels == label) & (index.levels >= 0)
    slot = jnp.argmax(hits)
    return jnp.where(hits[slot], slot, INVALID).astype(jnp.int32)


def mark_delete(index: HNSWIndex, label: jax.Array) -> HNSWIndex:
    """Paper 'Deletion': flag the point; it stays traversable until replaced."""
    slot = slot_of_label(index, jnp.asarray(label, jnp.int32))
    deleted = index.deleted.at[jnp.where(slot >= 0, slot, index.capacity)].set(
        True, mode="drop")
    return HNSWIndex(index.vectors, index.labels, index.levels, index.neighbors,
                     deleted, index.entry, index.max_layer, index.count,
                     index.rng)


@jax.jit
def mark_delete_jit(index: HNSWIndex, label: jax.Array) -> HNSWIndex:
    return mark_delete(index, label)


def _reuse_cursor(index: HNSWIndex, salt: jax.Array) -> jax.Array:
    """Deterministic rotating offset for slot reuse.

    Always taking the LOWEST eligible slot hammers one graph region under
    replace-heavy tapes (every reused slot — and therefore every repair —
    lands in the same low-id neighbourhoods, skewing hotspots). Folding the
    level-sampling key with the allocation count and a caller salt (the
    current eligible-slot count, so back-to-back replaces rotate too)
    yields a pseudo-random start that is a pure function of the index
    state: same index in, same slot out, under jit and across hosts.
    """
    key = jax.random.fold_in(index.rng, index.count)
    key = jax.random.fold_in(key, salt)
    return jax.random.randint(key, (), 0, index.capacity, jnp.int32)


def _first_slot_from(mask: jax.Array, start: jax.Array,
                     capacity: int) -> jax.Array:
    """First True slot at/after ``start`` in rotated order (wrapping)."""
    rank = (jnp.arange(capacity, dtype=jnp.int32) - start) % capacity
    cand = jnp.where(mask, rank, capacity)
    m = jnp.min(cand)
    return jnp.where(m == capacity, INVALID,
                     (start + m) % capacity).astype(jnp.int32)


def first_deleted_slot(index: HNSWIndex) -> jax.Array:
    """Next mark-deleted slot to reuse (-1 if none), cursor-rotated."""
    live_deleted = index.deleted & (index.levels >= 0)
    start = _reuse_cursor(index, jnp.sum(live_deleted).astype(jnp.int32))
    return _first_slot_from(live_deleted, start, index.capacity)


def first_free_slot(index: HNSWIndex) -> jax.Array:
    """Next free slot for a fresh insert (-1 if full), cursor-rotated."""
    free = index.levels < 0
    start = _reuse_cursor(index, jnp.sum(free).astype(jnp.int32))
    return _first_slot_from(free, start, index.capacity)


def num_deleted(index: HNSWIndex) -> jax.Array:
    return jnp.sum(index.deleted & (index.levels >= 0))


# ---------------------------------------------------------------------------
# repair phase
# ---------------------------------------------------------------------------

def _repair_layer(params: HNSWParams, nbrs: jax.Array, vectors: jax.Array,
                  deleted: jax.Array, pid: jax.Array, layer: int,
                  variant: str) -> jax.Array:
    """Repair the neighbourhood around replaced slot ``pid`` at one layer.

    ``nbrs``: full [L, N, M0] adjacency (returns updated copy).
    ``vectors[pid]`` already holds the NEW point's vector; edges touching
    ``pid`` therefore reference the newly inserted point ("label" in Alg. 2).
    """
    strategy = get_strategy(variant)
    if strategy.repair_fn is not None:
        return strategy.repair_fn(params, nbrs, vectors, deleted, pid, layer,
                                  strategy)
    repair_kind = strategy.repair_set
    pool_kind = strategy.candidate_pool
    r_alpha = strategy.repair_alpha
    M0 = params.M0
    m_l = params.m_for_layer(layer)
    N = vectors.shape[0]
    layer_nbrs = nbrs[layer]

    N1 = layer_nbrs[pid]                                  # [M0] one-hop of d
    n1c = jnp.clip(N1, 0)
    valid1 = (N1 >= 0) & ~deleted[n1c]
    rows1 = layer_nbrs[n1c]                               # [M0, M0]
    mutual = jnp.any(rows1 == pid, axis=1) & valid1       # v with edge v->d

    # --- repair set P ----------------------------------------------------
    if repair_kind == "one_hop":
        p_ids = jnp.where(valid1, N1, INVALID)
    elif repair_kind == "mutual":
        p_ids = jnp.where(mutual, N1, INVALID)
    elif repair_kind == "mutual_thn":
        two_hop = rows1.reshape(-1)                       # [M0*M0]
        thc = jnp.clip(two_hop, 0)
        th_valid = (two_hop >= 0) & ~deleted[thc]
        th_valid &= jnp.repeat(valid1, M0)                # parent edge valid
        th_points_at_d = jnp.any(layer_nbrs[thc] == pid, axis=1)
        th_ids = jnp.where(th_valid & th_points_at_d, two_hop, INVALID)
        # compact to a bounded repair budget (3*M0): the mutual two-hop set
        # is tiny in practice, but vmapping all M0^2 masked slots makes the
        # batched dominance scan pay for every lane (DESIGN.md §7)
        th_ids, _ = dedup_ids(th_ids, jnp.where(th_ids >= 0, 0.0, INF))
        order = jnp.argsort(th_ids < 0, stable=True)      # valid first
        th_ids = th_ids[order][:3 * M0]
        p_ids = jnp.concatenate([jnp.where(mutual, N1, INVALID), th_ids])
    else:
        raise ValueError(repair_kind)

    # --- candidate pools + per-vertex prune -------------------------------
    if pool_kind == "two_hop":
        two_hop = rows1.reshape(-1)
        th_valid = (two_hop >= 0) & jnp.repeat(valid1, M0)
        pool = jnp.concatenate([jnp.where(valid1, N1, INVALID),
                                jnp.where(th_valid, two_hop, INVALID),
                                jnp.array([pid], jnp.int32)])          # [C]
        poolc = jnp.clip(pool, 0)
        pool_ok = (pool >= 0) & ~deleted[poolc]
        pool_vecs = vectors[poolc]                                      # [C, d]

        def repair_one(v):
            vc = jnp.clip(v, 0)
            dq = dist_point(params.space, vectors[vc], pool_vecs)
            ok = pool_ok & (pool != v)
            dq = jnp.where(ok, dq, INF)
            ids = jnp.where(ok, pool, INVALID)
            sel, _ = alpha_rng_select(ids, dq, pool_vecs, m_l, r_alpha,
                                      params.space)
            new_row = _pad_row(sel, M0)
            return jnp.where(v >= 0, new_row, layer_nbrs[vc]), vc
    else:  # per_vertex: C(v) = N(v) ∪ N(d) ∪ {new}
        def repair_one(v):
            vc = jnp.clip(v, 0)
            own = layer_nbrs[vc]                                       # [M0]
            pool = jnp.concatenate([own, N1, jnp.array([pid], jnp.int32)])
            poolc = jnp.clip(pool, 0)
            ok = (pool >= 0) & ~deleted[poolc] & (pool != v)
            pool_vecs = vectors[poolc]
            dq = jnp.where(ok, dist_point(params.space, vectors[vc],
                                          pool_vecs), INF)
            ids = jnp.where(ok, pool, INVALID)
            sel, _ = select_neighbors(vectors[vc], ids, pool_vecs, dq, m_l,
                                      r_alpha, params.space)
            new_row = _pad_row(sel, M0)
            return jnp.where(v >= 0, new_row, layer_nbrs[vc]), vc

    new_rows, targets = jax.vmap(repair_one)(p_ids)
    safe = jnp.where(p_ids >= 0, targets, N)
    layer_nbrs = layer_nbrs.at[safe].set(new_rows, mode="drop")
    return nbrs.at[layer].set(layer_nbrs)


# ---------------------------------------------------------------------------
# layer-inheriting re-insert (paper Algorithm 3)
# ---------------------------------------------------------------------------

def _update_reinsert(params: HNSWParams, index: HNSWIndex, x: jax.Array,
                     pid: jax.Array, insert_alpha: float) -> HNSWIndex:
    """Re-link slot ``pid`` (already holding vector x) at its inherited level."""
    lvl = index.levels[pid]
    nbrs = index.neighbors
    ep = _descend(params, index, x, lvl)

    for layer in range(params.num_layers - 1, -1, -1):
        active = layer <= lvl

        def do(nbrs_ep, layer=layer):
            return connect_at_layer(params, nbrs_ep[0], index, x, pid,
                                    nbrs_ep[1], layer, insert_alpha)

        nbrs, ep = jax.lax.cond(active, do, lambda t: t, (nbrs, ep))

    return HNSWIndex(index.vectors, index.labels, index.levels, nbrs,
                     index.deleted, index.entry, index.max_layer, index.count,
                     index.rng)


# ---------------------------------------------------------------------------
# replaced_update entry point
# ---------------------------------------------------------------------------

def replaced_update(params: HNSWParams, index: HNSWIndex, x: jax.Array,
                    label: jax.Array, variant: str = "mn_ru_gamma") -> HNSWIndex:
    """Insert ``x`` reusing the first deleted slot (paper Algorithms 2+3).

    Falls back to a fresh insert into a free slot when no deleted point
    exists (paper line: "Perform normal insertion").
    """
    get_strategy(variant)   # uniform unknown-strategy error, fail-fast
    label = jnp.asarray(label, jnp.int32)
    d_slot = first_deleted_slot(index)

    def fresh(ix: HNSWIndex) -> HNSWIndex:
        pid = first_free_slot(ix)

        def do(ix):
            return insert(params, ix, x, jnp.clip(pid, 0), label)
        return jax.lax.cond(pid >= 0, do, lambda ix: ix, ix)

    def replace(ix: HNSWIndex) -> HNSWIndex:
        pid = d_slot
        vectors = ix.vectors.at[pid].set(x.astype(ix.vectors.dtype))
        labels = ix.labels.at[pid].set(label)
        deleted = ix.deleted.at[pid].set(False)
        lvl_d = ix.levels[pid]
        nbrs = ix.neighbors
        for layer in range(params.num_layers):
            active = layer <= lvl_d
            nbrs = jax.lax.cond(
                active,
                lambda nbrs, layer=layer: _repair_layer(
                    params, nbrs, vectors, deleted, pid, layer, variant),
                lambda nbrs: nbrs, nbrs)
        repaired = HNSWIndex(vectors, labels, ix.levels, nbrs, deleted,
                             ix.entry, ix.max_layer, ix.count, ix.rng)
        return _update_reinsert(params, repaired, x, pid, params.alpha)

    return jax.lax.cond(d_slot >= 0, replace, fresh, index)


@partial(jax.jit, static_argnames=("params", "variant"))
def replaced_update_jit(params: HNSWParams, index: HNSWIndex, x: jax.Array,
                        label: jax.Array, variant: str = "mn_ru_gamma"):
    return replaced_update(params, index, x, label, variant)


# ---------------------------------------------------------------------------
# fused mixed-op tape (serving write path)
# ---------------------------------------------------------------------------

OP_NOP = 0      # padding — leaves the index untouched
OP_DELETE = 1   # mark_delete(label)
OP_REPLACE = 2  # replaced_update(x, label) — reuses a deleted slot, else fresh
OP_INSERT = 3   # fresh insert of (x, label) into the first free slot

OP_NAMES = {OP_NOP: "nop", OP_DELETE: "delete", OP_REPLACE: "replace",
            OP_INSERT: "insert"}


def apply_update_batch_sequential(params: HNSWParams, index: HNSWIndex,
                                  ops: jax.Array, labels: jax.Array,
                                  X: jax.Array,
                                  variant: str = "mn_ru_gamma") -> HNSWIndex:
    """The sequential tape executor: one ``lax.scan`` step per op, in order.

    Semantically identical to issuing the ops one at a time — this is the
    parity baseline the wave executor is tested against, and the traceable
    fallback (it composes under jit/scan, unlike the host-driven waves):

      OP_DELETE  == mark_delete
      OP_REPLACE == replaced_update (same deleted-slot reuse + fresh
                    fallback)
      OP_INSERT  == insert into the first free slot (no-op when full)
      OP_NOP     == padding
    """
    get_strategy(variant)   # uniform unknown-strategy error, fail-fast
    ops = jnp.asarray(ops, jnp.int32)
    labels = jnp.asarray(labels, jnp.int32)

    def body(ix, tape):
        op, lbl, x = tape

        def nop(ix):
            return ix

        def dele(ix):
            return mark_delete(ix, lbl)

        def repl(ix):
            return replaced_update(params, ix, x, lbl, variant)

        def ins(ix):
            pid = first_free_slot(ix)

            def do(ix):
                return insert(params, ix, x, jnp.clip(pid, 0), lbl)
            return jax.lax.cond(pid >= 0, do, lambda ix: ix, ix)

        return jax.lax.switch(jnp.clip(op, 0, 3), (nop, dele, repl, ins),
                              ix), ()

    index, _ = jax.lax.scan(body, index, (ops, labels, X))
    return index


register_executor("sequential", apply_update_batch_sequential)

_apply_update_batch_sequential_jit = jax.jit(
    apply_update_batch_sequential, static_argnames=("params", "variant"))


def _wave_effective(ops, index: HNSWIndex, variant: str,
                    execution: str) -> bool:
    """Resolve the execution for one tape: the wave executor needs a
    concrete (host) tape AND index, and only implements the declarative
    repair configs — custom ``repair_fn`` strategies and traced
    tapes/indexes (callers jitting around the whole apply) route back to
    the sequential scan, everything else rides the waves."""
    if execution != "wave":
        return False
    if get_strategy(variant).repair_fn is not None:
        return False
    return not (isinstance(ops, jax.core.Tracer)
                or isinstance(index.count, jax.core.Tracer))


def apply_update_batch(params: HNSWParams, index: HNSWIndex, ops: jax.Array,
                       labels: jax.Array, X: jax.Array,
                       variant: str = "mn_ru_gamma",
                       execution: str = "wave") -> HNSWIndex:
    """Apply a padded tape of mixed {delete, replace, insert} ops.

    ``ops[T]`` holds OP_* codes, ``labels[T]`` the per-op label, ``X[T, d]``
    the per-op vector (ignored for delete/nop). ``execution`` picks the
    tape executor from the registry (:mod:`~repro.core.strategies`):

      * ``"wave"`` (default) — the conflict-free vectorized wave executor
        (:mod:`~repro.core.batch_update`): deletes apply in one vectorized
        pass, inserts/replaces in ``O(waves)`` compiled programs instead of
        ``O(T)`` scan steps. Per-label outcomes match the sequential tape;
        graph edge sets are recall-equivalent, not bit-identical.
      * ``"sequential"`` — one ``lax.scan`` step per op, bit-for-bit the
        one-at-a-time semantics (kept for parity testing; also the
        automatic fallback for traced tapes and custom ``repair_fn``
        strategies, which the batched repair sweep cannot honour).
    """
    get_strategy(variant)   # uniform unknown-strategy error, fail-fast
    exec_fn = get_executor(execution)
    if execution == "wave" and not _wave_effective(ops, index, variant,
                                                   execution):
        exec_fn = get_executor("sequential")
    return exec_fn(params, index, ops, labels, X, variant)


def apply_update_batch_jit(params: HNSWParams, index: HNSWIndex,
                           ops: jax.Array, labels: jax.Array, X: jax.Array,
                           variant: str = "mn_ru_gamma",
                           execution: str = "wave") -> HNSWIndex:
    """Jit-backed :func:`apply_update_batch`: the wave path jits each phase
    internally; the sequential path runs the cached jitted scan."""
    get_strategy(variant)
    if execution == "wave":
        if _wave_effective(ops, index, variant, execution):
            return get_executor("wave")(params, index, ops, labels, X,
                                        variant)
        execution = "sequential"  # traced args / custom repair_fn fallback
    if execution == "sequential":
        return _apply_update_batch_sequential_jit(params, index, ops, labels,
                                                  X, variant)
    return get_executor(execution)(params, index, ops, labels, X, variant)


@partial(jax.jit, static_argnames=("params", "variant"))
def delete_and_update_batch(params: HNSWParams, index: HNSWIndex,
                            del_labels: jax.Array, new_X: jax.Array,
                            new_labels: jax.Array,
                            variant: str = "mn_ru_gamma") -> HNSWIndex:
    """One compiled program: mark ``del_labels`` deleted, then replace each
    with a row of ``new_X`` (scan-fused, amortises dispatch for benchmarks)."""

    def del_body(ix, lbl):
        return mark_delete(ix, lbl), ()

    index, _ = jax.lax.scan(del_body, index, del_labels)

    def upd_body(ix, xl):
        x, lbl = xl
        return replaced_update(params, ix, x, lbl, variant), ()

    index, _ = jax.lax.scan(upd_body, index, (new_X, new_labels))
    return index
