"""Wave-scheduled batch update executor: conflict-free vectorized ingest.

The sequential op tape (``core.update.apply_update_batch``) executes one
insert/replace per ``lax.scan`` step — every op pays its own greedy descent,
beam search, and wiring, so ingest throughput is flat no matter how large
the drained tape is. This module replaces that hot path with the structure
JAX rewards: batch the tape into a few *waves* and run every op in a wave
simultaneously with ``vmap`` + segment ops against a frozen pre-wave
snapshot (FreshDiskANN's batched-consolidation discipline applied to the
write path).

Pipeline (one drained ``{op, label, vector}`` tape):

  1. **Tape compiler** (:func:`compile_tape`, host side) — dedupe duplicate
     labels (last-write-wins), split the tape into phases: all deletes
     first, then the insert/replace set sliced into *conflict-free waves*
     (every wave assigns distinct target slots to distinct labels; wave
     sizes grow with the graph so point ``i`` always wires against a graph
     of comparable size — ``O(log N)`` waves for a full build).
  2. **Delete phase** (:func:`_apply_deletes_jit`) — one vectorized
     label-match marks every deleted slot at once.
  3. **Wave executor** (:func:`_apply_wave_jit`) — per wave, one compiled
     program: vectorized slot assignment (replaces reuse mark-deleted
     slots, cursor-rotated), batched level sampling from one folded PRNG,
     a batched strategy-driven repair of the neighbourhoods around every
     replaced slot, ``vmap``ped greedy descent + ``search_layer`` + α-RNG
     neighbour selection against the frozen snapshot, then a vectorized
     commit: all forward rows scatter at once and the colliding reverse
     ``(target, candidate)`` pairs are resolved by a lexsort/segment-rank
     dominance pass instead of ``vmap``-over-single-insert.
  4. **:func:`build_batch`** — the same executor pointed at an empty index:
     the whole build runs in ``O(log N)`` waves rather than ``N`` scan
     steps (``core.hnsw.build`` routes here by default).

Semantics vs the sequential tape (``execution="sequential"`` keeps the old
scan bit-for-bit for parity testing):

  * per-label outcomes match: a delete marks the slot, a replace reuses a
    deleted slot (inheriting its level, paper Algorithm 3) with the update
    strategy's neighbourhood repair, an insert fills a free slot, and a
    full index drops the op;
  * *graphs differ*: wave members wire against the pre-wave snapshot, so
    edge sets are not bit-identical to one-at-a-time application — recall
    parity (benchmarks/ingest_bench.py gates ±0.01) is the contract;
  * duplicate labels inside one tape collapse last-write-wins (the
    sequential tape would burn two slots and orphan the first);
  * strategies with a custom ``repair_fn`` are routed back to the
    sequential executor by ``apply_update_batch`` — the batched repair
    sweep only implements the declarative (repair_set, candidate_pool)
    configs.
"""
from __future__ import annotations

import dataclasses
import os
from concurrent.futures import ThreadPoolExecutor
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .common import INF, INVALID, dedup_ids, pow2_at_least, span
from .hnsw import _pad_row, insert_jit
from .index import HNSWIndex, HNSWParams, empty_index, sample_levels
from .metrics import dist_pairwise, dist_point
from .prune import select_neighbors
from .search import _descend, search_layer
from .strategies import get_strategy, register_executor
from .update import (OP_DELETE, OP_INSERT, OP_NOP, OP_REPLACE, _reuse_cursor,
                     first_free_slot)

#: default smallest wave — below this the vmap lanes don't amortise dispatch
MIN_WAVE = 8
#: default largest wave — caps per-wave memory (candidate matrices are [W, N])
MAX_WAVE = 1024
#: candidate tier crossover: ``W * N`` at/below this uses the exact scan tier
#: (one [W, N] distance contraction — the planner's crossover lesson applied
#: to construction); above it the vmapped beam-search tier bounds memory
SCAN_TIER_MAX_ELEMS = 1 << 25
#: sort-key penalty that ranks mark-deleted candidates after every live one
#: while keeping them finite (the all-deleted link-through fallback)
_DELETED_PENALTY = jnp.float32(1e30)


# ---------------------------------------------------------------------------
# tape compiler (host side)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class WavePlan:
    """A compiled tape: one delete phase + conflict-free insert/replace waves.

    ``waves`` holds ``(ops, labels, X)`` numpy triples (unpadded — the
    executor pads each wave to its pow2 bucket so compiled program count
    stays ``log2(max_wave)`` per dtype). ``deduped`` counts ops dropped by
    last-write-wins label collapsing.
    """
    del_labels: np.ndarray
    waves: tuple[tuple[np.ndarray, np.ndarray, np.ndarray], ...]
    deduped: int = 0

    @property
    def num_waves(self) -> int:
        return len(self.waves)

    @property
    def num_deletes(self) -> int:
        return int(self.del_labels.shape[0])

    @property
    def num_writes(self) -> int:
        return sum(int(o.shape[0]) for o, _, _ in self.waves)


def _dedup_last_write_wins(ops: np.ndarray, labels: np.ndarray):
    """Collapse duplicate labels: per label keep the LAST op; any label with
    an earlier op (or an explicit delete) also emits a delete so the final
    write never coexists with a stale live slot. Returns
    ``(del_labels, write_indices, n_dropped)`` with write order preserved."""
    keep = ops != OP_NOP
    n_live = int(keep.sum())
    # fast path: all labels distinct and no deletes -> nothing to collapse
    live_labels = labels[keep]
    if (len(np.unique(live_labels)) == n_live
            and not np.any(ops[keep] == OP_DELETE)):
        return (np.empty((0,), np.int32), np.nonzero(keep)[0], 0)

    last: dict[int, int] = {}
    n_ops: dict[int, int] = {}
    saw_delete: set[int] = set()
    for i in np.nonzero(keep)[0]:
        lbl = int(labels[i])
        last[lbl] = int(i)
        n_ops[lbl] = n_ops.get(lbl, 0) + 1
        if ops[i] == OP_DELETE:
            saw_delete.add(lbl)
    del_labels, write_idx = [], []
    for lbl, i in last.items():          # dict order == first occurrence
        if ops[i] == OP_DELETE:
            del_labels.append(lbl)
        else:
            if lbl in saw_delete or n_ops[lbl] > 1:
                del_labels.append(lbl)
            write_idx.append(i)
    write_idx.sort()                     # tape order among surviving writes
    return (np.asarray(del_labels, np.int32),
            np.asarray(write_idx, np.int64), n_live - len(last))


def compile_tape(ops, labels, X, *, built: int, min_wave: int = MIN_WAVE,
                 max_wave: int = MAX_WAVE) -> WavePlan:
    """Group a drained tape into a delete phase + conflict-free waves.

    ``built`` is the current allocated-slot count — wave ``k``'s width is
    ``min(remaining, max(min_wave, graph_size_so_far), max_wave)`` so early
    waves on a small graph stay small (quality) and steady-state ingest
    collapses into one or two waves (throughput). Waves are conflict-free
    by construction: labels are distinct after last-write-wins dedup and
    the executor assigns every wave member a distinct target slot.
    """
    ops = np.asarray(ops, np.int32).reshape(-1)
    labels = np.asarray(labels, np.int32).reshape(-1)
    X = np.asarray(X, np.float32)
    del_labels, write_idx, dropped = _dedup_last_write_wins(ops, labels)

    waves = []
    lo, g = 0, max(int(built), 0)
    while lo < len(write_idx):
        w = 1 if g == 0 else min(len(write_idx) - lo,
                                 max(min_wave, g), max_wave)
        sel = write_idx[lo:lo + w]
        waves.append((ops[sel], labels[sel], X[sel]))
        g += w
        lo += w
    return WavePlan(del_labels, tuple(waves), dropped)


# ---------------------------------------------------------------------------
# delete phase (device)
# ---------------------------------------------------------------------------

@jax.jit
def _apply_deletes_jit(index: HNSWIndex, del_labels: jax.Array) -> HNSWIndex:
    """Vectorized markDelete: every allocated slot whose label is in
    ``del_labels`` is flagged at once (padding label -1 never matches).

    Membership is a binary search of each slot's label in the sorted
    delete list, so memory stays O(N + D) rather than a [D, N] compare."""
    srt = jnp.sort(del_labels)
    pos = jnp.clip(jnp.searchsorted(srt, index.labels), 0,
                   srt.shape[0] - 1)
    hit = (srt[pos] == index.labels) & (index.labels >= 0)
    hit &= index.levels >= 0
    return dataclasses.replace(index, deleted=index.deleted | hit)


# ---------------------------------------------------------------------------
# wave executor building blocks (device)
# ---------------------------------------------------------------------------

def _ranked_slots(mask: jax.Array, start: jax.Array):
    """Slots where ``mask`` in rotated order starting at ``start``; returns
    ``(order[N], count)`` — ``order[:count]`` are the eligible slots."""
    N = mask.shape[0]
    rank = (jnp.arange(N, dtype=jnp.int32) - start) % N
    order = jnp.argsort(jnp.where(mask, rank, N))
    return order.astype(jnp.int32), jnp.sum(mask).astype(jnp.int32)


def _group_pairs_by_target(e_ids: jax.Array, cands: jax.Array,
                           dists: jax.Array, N: int, K: int):
    """Resolve colliding ``(target, candidate)`` pairs into per-target lists.

    Lexsort the flat pair list by (target, distance), compute each pair's
    rank inside its target segment with a cummax scan, and scatter the
    ``K`` nearest candidates per target into dense ``[N, K]`` id/dist
    buffers (-1 / inf padded). Invalid pairs carry target ``N`` and drop.
    This replaces the sequential executor's one-insert-at-a-time
    ``add_reverse_edges`` with a single dominance-ordered pass.
    """
    P = e_ids.shape[0]
    order = jnp.lexsort((dists, e_ids))
    e_s, c_s, d_s = e_ids[order], cands[order], dists[order]
    idx = jnp.arange(P, dtype=jnp.int32)
    is_start = jnp.concatenate([jnp.ones((1,), jnp.bool_),
                                e_s[1:] != e_s[:-1]])
    rank = idx - jax.lax.cummax(jnp.where(is_start, idx, 0))
    ok = (e_s >= 0) & (e_s < N) & (rank < K)
    tgt = jnp.where(ok, e_s, N)
    col = jnp.clip(rank, 0, K - 1)
    out_ids = jnp.full((N, K), INVALID, jnp.int32).at[tgt, col].set(
        jnp.where(ok, c_s, INVALID), mode="drop")
    out_d = jnp.full((N, K), INF).at[tgt, col].set(
        jnp.where(ok, d_s, INF), mode="drop")
    return out_ids, out_d


def _scatter_mask(targets: jax.Array, valid: jax.Array, N: int) -> jax.Array:
    flat_t = jnp.where(valid, targets, N).reshape(-1)
    return jnp.zeros((N,), jnp.bool_).at[flat_t].set(True, mode="drop")


def _batched_rng_prune(cand_ids: jax.Array, cand_vecs: jax.Array,
                       cand_d: jax.Array, m_out: int, alpha: float,
                       space: str) -> jax.Array:
    """Single-pass batched α-RNG over ``[A, C]`` candidate lists.

    The matrix form of RobustPrune: sort each lane by distance, build the
    ``[C, C]`` candidate-pairwise matrix in one contraction, and prune any
    candidate α-dominated by ANY closer candidate (kept or not — slightly
    more pessimistic than the sequential greedy scan, which only lets KEPT
    candidates dominate). Lanes short of ``m_out`` survivors backfill with
    the nearest pruned candidates, so full rows stay full. Exact duplicates
    dominate each other at distance 0, so later copies always prune.
    Returns ``(ids[A, m_out], dists[A, m_out])`` padded with (-1, inf) —
    survivors in ascending-distance order, then any backfill.
    """
    A, C = cand_ids.shape
    order = jnp.argsort(cand_d, axis=1)
    ids = jnp.take_along_axis(cand_ids, order, 1)
    dq = jnp.take_along_axis(cand_d, order, 1)
    vecs = jnp.take_along_axis(cand_vecs, order[..., None], 1)
    pair = jax.vmap(lambda V: dist_pairwise(space, V, V))(vecs)  # [A, C, C]
    closer = jnp.triu(jnp.ones((C, C), jnp.bool_), k=1)          # i before j
    valid = dq < INF
    dom = closer[None] & valid[:, :, None] & (alpha * pair <= dq[:, None, :])
    # fixed-point refinement toward the greedy scan: only KEPT candidates
    # may dominate. Start optimistic and iterate — each round reuses the
    # one [C, C] contraction above, and dominance chains longer than the
    # round count are rare in practice (the greedy solution is the fixed
    # point; two rounds close most of the pessimism gap at negligible cost)
    keep = valid
    for _ in range(2):
        keep = valid & ~jnp.any(dom & keep[:, :, None], axis=1)
    rank = jnp.where(keep, 0, C) + jnp.arange(C)   # keeps first, both sorted
    order2 = jnp.argsort(rank, axis=1)
    ids2 = jnp.take_along_axis(ids, order2, 1)[:, :m_out]
    d2 = jnp.take_along_axis(dq, order2, 1)[:, :m_out]
    ok2 = jnp.take_along_axis(valid, order2, 1)[:, :m_out]
    return jnp.where(ok2, ids2, INVALID), jnp.where(ok2, d2, INF)


def _repair_wave_layer(params: HNSWParams, layer_nbrs: jax.Array,
                       vectors: jax.Array, alive: jax.Array, R: jax.Array,
                       r_list: jax.Array, strategy, layer: int) -> jax.Array:
    """Strategy-driven repair of the neighbourhoods around every replaced
    slot, one vectorized pass per layer (the batched analogue of
    ``core.update._repair_layer``).

    ``R`` marks the slots whose point was just replaced (vectors already
    hold the NEW points); ``r_list[Wr]`` is the compacted slot-id list
    (capacity-padded). The repair SET follows the strategy — one-hop
    neighbours of any replaced slot (``hnsw_ru``), only mutual ones
    (``mn_ru_*``), mutual plus two-hop vertices pointing back
    (``mn_thn_ru``) — and every repaired vertex re-selects from the pooled
    ``N(v) ∪ ⋃_{d ∈ N(v) ∩ R} N(d) ∪ {replaced slots pointing at v}``
    candidates under the strategy's α-RNG, reduced to the ``3*M0`` nearest
    by one batched distance contraction first (the consolidation idiom).
    """
    N, M0 = layer_nbrs.shape
    Wr = r_list.shape[0]
    m_l = params.m_for_layer(layer)
    r_alpha = strategy.repair_alpha

    rc = jnp.clip(layer_nbrs, 0)
    valid = layer_nbrs >= 0
    edge_to_R = valid & R[rc]                               # v -> some d in R
    points_at_R = jnp.any(edge_to_R, axis=1)

    rows_R = layer_nbrs[jnp.clip(r_list, 0, N - 1)]         # [Wr, M0]
    rows_R_ok = (rows_R >= 0) & (r_list < N)[:, None]
    out_of_R = _scatter_mask(jnp.clip(rows_R, 0), rows_R_ok, N)

    if strategy.repair_set == "one_hop":
        repair = out_of_R
        a_cap = Wr * M0
    elif strategy.repair_set == "mutual":
        repair = out_of_R & points_at_R
        a_cap = Wr * M0
    else:  # mutual_thn: + two-hop vertices that point back at a replaced slot
        oh_list = jnp.nonzero(out_of_R, size=min(N, Wr * M0),
                              fill_value=N)[0]
        rows_oh = layer_nbrs[jnp.clip(oh_list, 0, N - 1)]
        rows_oh_ok = (rows_oh >= 0) & (oh_list < N)[:, None]
        two_hop = _scatter_mask(jnp.clip(rows_oh, 0), rows_oh_ok, N)
        repair = (out_of_R | two_hop) & points_at_R
        a_cap = min(N, Wr * M0 * (M0 + 1))
    repair &= alive & ~R
    a_cap = min(N, a_cap)

    # replaced slots that point at v — so non-mutual one-hop vertices still
    # see the new point as a candidate (sequential pools include pid)
    in_ids, _ = _group_pairs_by_target(
        jnp.where(rows_R_ok, rows_R, N).reshape(-1),
        jnp.broadcast_to(r_list[:, None], (Wr, M0)).reshape(-1),
        jnp.zeros((Wr * M0,)), N, max(M0 // 4, 4))

    aff = jnp.nonzero(repair, size=a_cap, fill_value=N)[0]
    affc = jnp.clip(aff, 0, N - 1)

    def pool_one(v):
        own = layer_nbrs[v]                                 # [M0]
        ownc = jnp.clip(own, 0)
        is_r = (own >= 0) & R[ownc]
        # the sequential pool is per-(v, d): N(v) ∪ N(d) ∪ {new}. Batch
        # against the FIRST replaced out-neighbour's old row — a vertex
        # pointing at several replaced slots still sees every new point
        # through is_r + in_ids, and the bounded pool keeps the sweep
        # O(M0) wide instead of O(M0^2)
        j = jnp.argmax(is_r)
        drow = jnp.where(jnp.any(is_r), layer_nbrs[ownc[j]],
                         jnp.full((M0,), INVALID, jnp.int32))
        pool = jnp.concatenate([own, drow, in_ids[v]])
        pc = jnp.clip(pool, 0)
        ok = (pool >= 0) & alive[pc] & (pool != v)
        dq = jnp.where(ok, dist_point(params.space, vectors[v], vectors[pc]),
                       INF)
        return dedup_ids(jnp.where(ok, pool, INVALID), dq)

    pool_ids, pool_d = jax.vmap(pool_one)(affc)         # [A, 2*M0 + M0/4]
    sel, _ = _batched_rng_prune(pool_ids, vectors[jnp.clip(pool_ids, 0)],
                                pool_d, m_l, r_alpha, params.space)
    new_rows = jnp.full((aff.shape[0], M0), INVALID, jnp.int32
                        ).at[:, :m_l].set(sel)
    return layer_nbrs.at[jnp.where(aff < N, aff, N)].set(
        new_rows, mode="drop")


def _merge_reverse_layer(params: HNSWParams, layer_nbrs: jax.Array,
                         vectors: jax.Array, new_ids: jax.Array,
                         new_d: jax.Array, a_cap: int,
                         layer: int) -> jax.Array:
    """Fold the per-target reverse-candidate lists into the adjacency.

    Rows with head-room append every (deduped) candidate — hnswlib's
    unconditional append — and full rows re-select from row ∪ candidates
    under α-RNG, exactly the shrink rule ``add_reverse_edges`` applies one
    insert at a time. Only affected rows (compacted to ``a_cap``) pay."""
    N, M0 = layer_nbrs.shape
    K = new_ids.shape[1]
    m_l = params.m_for_layer(layer)

    affected = jnp.any(new_ids >= 0, axis=1)
    aff = jnp.nonzero(affected, size=min(N, a_cap), fill_value=N)[0]
    affc = jnp.clip(aff, 0, N - 1)

    rows = layer_nbrs[affc]                                 # [A, M0]
    cands, cand_d = new_ids[affc], new_d[affc]              # [A, K]
    dup = jnp.any(cands[:, :, None] == rows[:, None, :], axis=2)
    ok_c = (cands >= 0) & ~dup
    cands = jnp.where(ok_c, cands, INVALID)
    cand_d = jnp.where(ok_c, cand_d, INF)
    n_new = jnp.sum(ok_c, axis=1)
    degree = jnp.sum(rows >= 0, axis=1)

    # head-room rows append every candidate (hnswlib's unconditional append)
    pos = degree[:, None] + jnp.cumsum(ok_c.astype(jnp.int32), axis=1) - 1
    arow = jnp.arange(aff.shape[0])[:, None]
    appended = rows.at[arow, jnp.where(ok_c, pos, M0)].set(cands, mode="drop")

    # full rows re-select from row ∪ candidates under the batched α-RNG
    row_d = jnp.where(rows >= 0,
                      jax.vmap(lambda v, r: dist_point(
                          params.space, vectors[v],
                          vectors[jnp.clip(r, 0)]))(affc, rows), INF)
    all_ids = jnp.concatenate([rows, cands], axis=1)        # [A, M0+K]
    all_d = jnp.concatenate([row_d, cand_d], axis=1)
    sel, _ = _batched_rng_prune(all_ids, vectors[jnp.clip(all_ids, 0)],
                                all_d, m_l, params.alpha, params.space)
    shrunk = jnp.full((aff.shape[0], M0), INVALID, jnp.int32
                      ).at[:, :m_l].set(sel)

    merged = jnp.where((degree + n_new <= m_l)[:, None], appended, shrunk)
    merged = jnp.where((n_new > 0)[:, None], merged, rows)
    return layer_nbrs.at[jnp.where(aff < N, aff, N)].set(
        merged, mode="drop")


# ---------------------------------------------------------------------------
# candidate tiers: exact scan (planner-style) vs vmapped beam search
# ---------------------------------------------------------------------------

def _upper_cap(W: int, M: int, layer: int) -> int:
    """Static lane bound for layers > 0: levels are Geometric(1/M), so the
    expected active-lane count at ``layer`` is ``W / M**layer`` — bound it
    at mean + 4σ (pow2-rounded) and the overflow probability is negligible;
    an overflowing lane just skips its wiring at that layer (it stays fully
    wired below, exactly like a point whose upper row pruned empty)."""
    mean = W / (M ** layer)
    return int(min(W, pow2_at_least(int(np.ceil(mean + 4 * np.sqrt(mean)
                                                + 4)))))


def _scan_candidates(params: HNSWParams, vectors: jax.Array,
                     levels: jax.Array, deleted: jax.Array, xq: jax.Array,
                     pid: jax.Array, lvl: jax.Array, active: jax.Array,
                     max_layer: jax.Array) -> list:
    """Exact-scan candidate tier: ONE ``[W, N]`` distance contraction serves
    every layer (the query planner's small-index crossover lesson applied
    to construction — a matmul beats ``W`` beam walks until ``W * N``
    outgrows :data:`SCAN_TIER_MAX_ELEMS`).

    Per layer: slots at that layer rank by true distance with mark-deleted
    candidates penalised behind every live one (the all-deleted
    link-through fallback), top-``ef`` feeds the exact α-RNG
    ``select_neighbors``. Wave-mates are eligible candidates — their
    vectors and levels are already staged — so a wave interconnects
    internally, which the frozen-snapshot beam tier cannot do. Layers > 0
    run on lanes compacted to :func:`_upper_cap`.
    """
    N = vectors.shape[0]
    W = xq.shape[0]
    D = dist_pairwise(params.space, xq, vectors)                  # [W, N]
    D = D.at[jnp.arange(W), jnp.clip(pid, 0)].set(INF)            # never self
    del_pen = jnp.where(deleted, _DELETED_PENALTY, 0.0)[None, :]
    ef = min(max(params.ef_construction, params.M0), N)

    sel_layers = []
    for layer in range(params.num_layers - 1, -1, -1):
        m_l = params.m_for_layer(layer)
        act_l = active & (lvl >= layer) & (layer <= max_layer)
        elig = (levels >= layer)[None, :]
        if layer > 0:
            lane = jnp.nonzero(act_l, size=_upper_cap(W, params.M, layer),
                               fill_value=W)[0]
            lc = jnp.clip(lane, 0, W - 1)
            Dl, xs = D[lc], xq[lc]
        else:
            lane, Dl, xs = None, D, xq
        negk, ids = jax.lax.top_k(-jnp.where(elig, Dl + del_pen, INF), ef)
        dq = jnp.take_along_axis(Dl, ids, 1)
        ok = negk > -INF
        alive_c = ok & ~deleted[jnp.clip(ids, 0)]
        ok = jnp.where(jnp.any(alive_c, axis=1, keepdims=True), alive_c, ok)
        dq = jnp.where(ok, dq, INF)
        idsm = jnp.where(ok, ids, INVALID)
        sel_c, seld_c = _batched_rng_prune(idsm, vectors[jnp.clip(ids, 0)],
                                           dq, m_l, params.alpha,
                                           params.space)
        if lane is None:
            sel, seld = sel_c, seld_c
        else:
            safe_lane = jnp.where(lane < W, lane, W)
            sel = jnp.full((W, m_l), INVALID, jnp.int32).at[safe_lane].set(
                sel_c, mode="drop")
            seld = jnp.full((W, m_l), INF).at[safe_lane].set(
                seld_c, mode="drop")
        sel_layers.append((layer, m_l, sel, seld, act_l))
    return sel_layers


def _beam_candidates(params: HNSWParams, view: HNSWIndex, xq: jax.Array,
                     pid: jax.Array, lvl: jax.Array,
                     active: jax.Array) -> list:
    """Beam-search candidate tier: batched greedy ``_descend`` plus a
    ``vmap``ped ``search_layer`` per layer against the frozen pre-wave
    snapshot. Memory stays O(W·ef) — the tier for waves whose ``[W, N]``
    scan matrix would not fit (:data:`SCAN_TIER_MAX_ELEMS`). Wave-mates are
    only reachable through pre-existing edges here, so the scan tier is
    preferred whenever it fits."""
    vectors, deleted = view.vectors, view.deleted
    eps = jax.vmap(lambda x, l: _descend(params, view, x, l))(
        xq, jnp.maximum(lvl, 0))
    sel_layers = []
    for layer in range(params.num_layers - 1, -1, -1):
        m_l = params.m_for_layer(layer)
        act_l = active & (lvl >= layer) & (layer <= view.max_layer)

        def search_one(x, ep, p, layer=layer, m_l=m_l):
            ids, dists = search_layer(params, view, x, ep, layer,
                                      params.ef_construction)
            ok = (ids >= 0) & (ids != p)
            # prefer live candidates; all-deleted links through (hnswlib)
            alive_c = ok & ~deleted[jnp.clip(ids, 0)]
            ok = jnp.where(jnp.any(alive_c), alive_c, ok)
            dists = jnp.where(ok, dists, INF)
            ids = jnp.where(ok, ids, INVALID)
            sel, seld = select_neighbors(x, ids, vectors[jnp.clip(ids, 0)],
                                         dists, m_l, params.alpha,
                                         params.space)
            j = jnp.argmin(dists)
            next_ep = jnp.where(ids[j] >= 0, jnp.clip(ids[j], 0), ep)
            return sel, seld, next_ep

        sel, seld, next_eps = jax.vmap(search_one)(xq, eps, pid)
        eps = jnp.where(act_l, next_eps, eps)
        sel_layers.append((layer, m_l, sel, seld, act_l))
    return sel_layers


# ---------------------------------------------------------------------------
# the wave executor (device)
# ---------------------------------------------------------------------------

def _apply_wave(params: HNSWParams, index: HNSWIndex, ops: jax.Array,
                labels: jax.Array, X: jax.Array, variant: str,
                rotate_slots: bool, do_repair: bool,
                candidates: str = "scan") -> HNSWIndex:
    """Apply one conflict-free wave of insert/replace ops in a single
    compiled program (see the module docstring for the phase breakdown)."""
    strategy = get_strategy(variant)
    N, M0, L = index.capacity, params.M0, params.num_layers
    W = ops.shape[0]
    dtype = index.vectors.dtype

    # --- vectorized slot assignment (distinct slots per wave member) -------
    is_replace = ops == OP_REPLACE
    is_write = is_replace | (ops == OP_INSERT)
    live_del = index.deleted & (index.levels >= 0)
    free = index.levels < 0
    if rotate_slots:
        start_d = _reuse_cursor(index, jnp.sum(live_del).astype(jnp.int32))
        start_f = _reuse_cursor(index, jnp.sum(free).astype(jnp.int32))
    else:
        start_d = start_f = jnp.int32(0)
    del_order, n_del = _ranked_slots(live_del, start_d)
    free_order, n_free = _ranked_slots(free, start_f)

    r_idx = jnp.cumsum(is_replace.astype(jnp.int32)) - 1
    reuse_rep = is_replace & (r_idx < n_del)
    needs_free = is_write & ~reuse_rep
    f_idx = jnp.cumsum(needs_free.astype(jnp.int32)) - 1
    got_free = needs_free & (f_idx < n_free)
    # capacity-pressure fallback: a write with no free slot left reuses a
    # deleted slot the replaces didn't claim (the sequential tape would
    # silently drop the op — conserving the write keeps delete→insert
    # tapes label-conserving on a full index)
    n_rep_used = jnp.minimum(jnp.sum(is_replace.astype(jnp.int32)), n_del)
    need_fb = needs_free & ~got_free
    fb_idx = jnp.cumsum(need_fb.astype(jnp.int32)) - 1
    got_fb = need_fb & (n_rep_used + fb_idx < n_del)
    reuse = reuse_rep | got_fb            # both inherit the slot's level
    pid = jnp.where(
        reuse_rep, del_order[jnp.clip(r_idx, 0, N - 1)],
        jnp.where(got_free, free_order[jnp.clip(f_idx, 0, N - 1)],
                  jnp.where(got_fb,
                            del_order[jnp.clip(n_rep_used + fb_idx, 0,
                                               N - 1)],
                            INVALID))).astype(jnp.int32)
    active = is_write & (pid >= 0)        # an exhausted index drops the op
    safe_pid = jnp.where(active, pid, N)

    # --- batched level sampling; replaces inherit (paper Algorithm 3) ------
    key, sub = jax.random.split(index.rng)
    fresh_lvl = sample_levels(sub, params, W)
    lvl = jnp.where(reuse, index.levels[jnp.clip(pid, 0)], fresh_lvl)
    lvl = jnp.where(active, lvl, -1)

    xq = X.astype(dtype)
    vectors = index.vectors.at[safe_pid].set(xq, mode="drop")
    slot_labels = index.labels.at[safe_pid].set(labels, mode="drop")
    levels = index.levels.at[safe_pid].set(lvl, mode="drop")
    deleted = index.deleted.at[safe_pid].set(False, mode="drop")

    # --- batched strategy repair around the replaced slots -----------------
    nbrs = index.neighbors
    if do_repair:
        R = _scatter_mask(pid, reuse, N)
        r_list = jnp.nonzero(R, size=min(N, W), fill_value=N)[0]
        alive = (levels >= 0) & ~deleted
        for layer in range(L):
            nbrs = nbrs.at[layer].set(_repair_wave_layer(
                params, nbrs[layer], vectors, alive, R, r_list, strategy,
                layer))

    # --- batched candidate generation + α-RNG neighbour selection ----------
    if candidates == "scan":
        sel_layers = _scan_candidates(params, vectors, levels, deleted, xq,
                                      pid, lvl, active, index.max_layer)
    else:
        view = HNSWIndex(vectors, slot_labels, levels, nbrs, deleted,
                         index.entry, index.max_layer, index.count, key)
        sel_layers = _beam_candidates(params, view, xq, pid, lvl, active)

    # --- vectorized commit: forward scatter + segment-resolved reverse -----
    for layer, m_l, sel, seld, act_l in sel_layers:
        layer_nbrs = nbrs[layer]
        rows = jax.vmap(lambda s: _pad_row(s, M0))(sel)
        layer_nbrs = layer_nbrs.at[jnp.where(act_l, pid, N)].set(
            rows, mode="drop")
        pair_ok = act_l[:, None] & (sel >= 0)
        # a target takes at most m_l/2 new reverse edges per wave (nearest
        # first — the segment rank orders by distance); only lanes that can
        # be active at this layer contribute pairs
        lanes = W if layer == 0 else _upper_cap(W, params.M, layer)
        new_ids, new_d = _group_pairs_by_target(
            jnp.where(pair_ok, sel, N).reshape(-1),
            jnp.broadcast_to(pid[:, None], sel.shape).reshape(-1),
            jnp.where(pair_ok, seld, INF).reshape(-1), N,
            max(m_l // 2, 4))
        layer_nbrs = _merge_reverse_layer(params, layer_nbrs, vectors,
                                          new_ids, new_d, lanes * m_l, layer)
        nbrs = nbrs.at[layer].set(layer_nbrs)

    # --- entry / max_layer / count invariants ------------------------------
    wave_max = jnp.max(jnp.where(active, lvl, -1)).astype(jnp.int32)
    top = pid[jnp.argmax(jnp.where(active, lvl, -1))]
    new_entry = jnp.where(wave_max > index.max_layer, top,
                          index.entry).astype(jnp.int32)
    new_max = jnp.maximum(index.max_layer, wave_max).astype(jnp.int32)
    new_count = (index.count
                 + jnp.sum(active & ~reuse)).astype(jnp.int32)
    return HNSWIndex(vectors, slot_labels, levels, nbrs, deleted, new_entry,
                     new_max, new_count, key)


_apply_wave_jit = jax.jit(
    _apply_wave, static_argnames=("params", "variant", "rotate_slots",
                                  "do_repair", "candidates"))


# ---------------------------------------------------------------------------
# host drivers
# ---------------------------------------------------------------------------

def _pad_pow2(a: np.ndarray, fill, min_len: int = 1) -> np.ndarray:
    b = max(pow2_at_least(len(a)), min_len)
    if b == len(a):
        return a
    pad_shape = (b - len(a),) + a.shape[1:]
    return np.concatenate([a, np.full(pad_shape, fill, a.dtype)])


def apply_plan(params: HNSWParams, index: HNSWIndex, plan: WavePlan,
               variant: str = "mn_ru_gamma",
               rotate_slots: bool = True) -> HNSWIndex:
    """Execute a compiled :class:`WavePlan`: the delete phase, then every
    wave through :func:`_apply_wave_jit` (each padded to its pow2 bucket so
    ragged tapes reuse a bounded set of compiled programs)."""
    get_strategy(variant)
    with span("waves", waves=plan.num_waves):
        if plan.num_deletes:
            index = _apply_deletes_jit(
                index, jnp.asarray(_pad_pow2(plan.del_labels, -1)))
        waves = list(plan.waves)
        allocated = int(index.count)    # ONE host sync; waves book-keep below
        if waves and allocated == 0:
            # empty-graph bootstrap: the first point inserts sequentially (it
            # has nothing to search against), the rest ride the waves
            ops0, labels0, X0 = waves[0]
            p0 = first_free_slot(index) if rotate_slots else jnp.int32(0)
            index = insert_jit(params, index, jnp.asarray(X0[0]),
                               jnp.clip(p0, 0), jnp.int32(labels0[0]))
            waves[0] = (ops0[1:], labels0[1:], X0[1:])
            allocated = 1
        N = index.capacity
        calls = []
        for ops_w, labels_w, X_w in waves:
            if not len(ops_w):
                continue
            ops_p = _pad_pow2(ops_w, OP_NOP)
            tier = "scan" if len(ops_p) * N <= SCAN_TIER_MAX_ELEMS else "beam"
            # the repair sweep must also run when inserts can spill into
            # mark-deleted slots (capacity pressure) — those reuse a slot with
            # live in-edges exactly like a replace does. ``allocated`` is an
            # upper bound maintained host-side (as if every write allocated),
            # so the check can only over-trigger the sweep, never miss it —
            # and the wave loop never blocks on a per-wave device sync
            may_reuse = bool(np.any(ops_w == OP_REPLACE)) \
                or len(ops_w) > N - allocated
            calls.append(((jnp.asarray(ops_p),
                           jnp.asarray(_pad_pow2(labels_w, -1)),
                           jnp.asarray(_pad_pow2(X_w, 0.0))),
                          (variant, rotate_slots, may_reuse, tier)))
            allocated = min(N, allocated + len(ops_w))
        for program, (args, _) in zip(_wave_programs(params, index, calls),
                                      calls):
            index = program(index, *args)
        return index


#: compiled wave programs, keyed by :func:`_wave_programs`
_WAVE_PROGRAMS: dict = {}


def compiled_wave_programs() -> int:
    """How many wave programs this process has compiled."""
    return len(_WAVE_PROGRAMS)


def _wave_programs(params: HNSWParams, index: HNSWIndex, calls) -> list:
    """The compiled :func:`_apply_wave_jit` program of each wave call.

    The programs a plan still lacks are lowered one after another and then
    compiled side by side: XLA compiles outside the GIL, and a cold build
    needs about a dozen wave programs of 10-20 s each on a TPU.
    """
    def key(args, static):
        leaves = jax.tree.leaves((index, args))
        return (params, static, tuple((a.shape, a.dtype, a.weak_type,
                                       a.sharding) for a in leaves))

    keys = [key(args, static) for args, static in calls]
    missing = {k: call for k, call in zip(keys, calls)
               if k not in _WAVE_PROGRAMS}
    if missing:
        with span("wave_compile", programs=len(missing)):
            lowered = [_apply_wave_jit.lower(params, index, *args, *static)
                       for args, static in missing.values()]
            workers = min(len(lowered), os.cpu_count() or 1, 8)
            with ThreadPoolExecutor(workers) as pool:
                compiled = pool.map(lambda low: low.compile(), lowered)
                _WAVE_PROGRAMS.update(zip(missing, compiled))
    return [_WAVE_PROGRAMS[k] for k in keys]


def apply_update_batch_wave(params: HNSWParams, index: HNSWIndex, ops,
                            labels, X, variant: str = "mn_ru_gamma",
                            min_wave: int = MIN_WAVE,
                            max_wave: int = MAX_WAVE) -> HNSWIndex:
    """Wave-executed drop-in for ``apply_update_batch``: compile the tape,
    run the phases. Host-side — the tape must be concrete (the serving
    scheduler and the facade both call it with host arrays)."""
    plan = compile_tape(np.asarray(ops), np.asarray(labels), np.asarray(X),
                        built=int(index.count), min_wave=min_wave,
                        max_wave=max_wave)
    return apply_plan(params, index, plan, variant)


def build_batch(params: HNSWParams, vectors, labels=None, seed: int = 0,
                capacity: int | None = None, min_wave: int = MIN_WAVE,
                max_wave: int = MAX_WAVE) -> HNSWIndex:
    """Construct a whole index in ``O(log N)`` geometrically-growing waves
    (the batch analogue of ``core.hnsw.build``'s ``N``-step insert loop).

    Slots are assigned in ascending order (no reuse-cursor rotation), so a
    fresh build places point ``i`` in slot ``i`` exactly like the
    sequential builder.
    """
    vectors = jnp.asarray(vectors)
    n, d = vectors.shape
    capacity = capacity or n
    labels = jnp.arange(n, dtype=jnp.int32) if labels is None else labels
    index = empty_index(params, capacity, d, seed, dtype=vectors.dtype)
    plan = compile_tape(np.full((n,), OP_INSERT, np.int32),
                        np.asarray(labels, np.int32), np.asarray(vectors),
                        built=0, min_wave=min_wave, max_wave=max_wave)
    return apply_plan(params, index, plan, rotate_slots=False)


register_executor("wave", apply_update_batch_wave)
