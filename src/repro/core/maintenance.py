"""Online index maintenance: consolidation, unreachable repair, health.

The paper diagnoses two failure modes of HNSW under real-time updates —
performance degradation as mark-deleted slots accumulate, and unreachable
points (Definition 1) left behind by neighbourhood churn. The rest of the
repo *detects* both (``core/reach.py``, the serving engine's
``unreachable_indegree`` gauge); this module *fixes* them online, without
the full blocking rebuild that used to be the only reclamation path:

  * :func:`consolidate_deletes` — FreshDiskANN-style batched delete
    consolidation: ONE vectorized pass finds every live vertex with an edge
    into a mark-deleted slot, re-prunes each from its ``N(v) ∪ ⋃ N(d)``
    candidate pool (one batched distance contraction + a vmapped alpha-RNG
    sweep, no per-op ``lax.scan``), then clears the deleted slots
    (``levels = -1``) so they become free capacity.
  * :func:`repair_unreachable` — batch re-link every unreachable live
    point (Definition-1 ∪ BFS) through the layer-inheriting reinsert path,
    then force an in-edge into every remaining orphan without taking
    another point's last one, driving the Definition-1 count to zero.
    Inside a maintenance consult it takes the health sweep's mask instead
    of sweeping again, and leaves the Definition-1 count of its output.
  * :func:`index_health` — a jit-able :class:`IndexHealth` report (live /
    deleted / unreachable counts, in-degree histogram, the repair mask)
    that :class:`MaintenancePolicy` consumes to decide *when* the passes
    run — between serving ``pump()`` ticks off-snapshot, or transparently
    behind the facade's mutation calls.
  * :func:`rebuild_index` — the full rebuild over live points, kept as the
    escape hatch (``VectorIndex.compact()`` routes here).

Consolidation vs rebuild trade-off: consolidation touches only the
affected neighbourhoods (one compiled sweep over the slot array), so it is
far cheaper than re-running ``build``'s sequential insert loop — but it
inherits the existing graph topology. A long-degraded graph still benefits
from an occasional :func:`rebuild_index`. See docs/MAINTENANCE.md.
"""
from __future__ import annotations

import contextvars
import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .common import INF, INVALID, pow2_at_least, span
from .index import HNSWIndex, HNSWParams, empty_index
from .metrics import dist_point
from .prune import alpha_rng_select
from .reach import bfs_unreachable, definition1, indegree, \
    indegree_unreachable


# ---------------------------------------------------------------------------
# health report
# ---------------------------------------------------------------------------

#: in-degree histogram bin splits: bin b counts live points whose total
#: in-degree falls in [HIST_SPLITS[b-1], HIST_SPLITS[b]) — i.e. the bins are
#: 0, 1, [2,4), [4,8), [8,16), [16,32), [32,64), 64+. Bin 0 is exactly the
#: paper's Definition-1 precondition (zero in-edges).
HIST_SPLITS = (1, 2, 4, 8, 16, 32, 64)


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["capacity", "allocated", "live", "deleted",
                 "unreachable_def1", "unreachable_bfs", "max_layer",
                 "indegree_hist", "repair_mask"],
    meta_fields=[],
)
@dataclasses.dataclass
class IndexHealth:
    """Jit-able index health report (all fields are device scalars/arrays)."""
    capacity: jax.Array          # i32[] slot-array length N
    allocated: jax.Array         # i32[] slots with levels >= 0
    live: jax.Array              # i32[] allocated and not mark-deleted
    deleted: jax.Array           # i32[] allocated and mark-deleted
    unreachable_def1: jax.Array  # i32[] paper Definition 1 count
    unreachable_bfs: jax.Array   # i32[] BFS-unreachable count
    max_layer: jax.Array         # i32[] current top layer (-1 = empty)
    indegree_hist: jax.Array     # i32[len(HIST_SPLITS)+1] live in-degree bins
    repair_mask: jax.Array       # bool[N] live points that fail Definition 1
                                 # or that the BFS does not reach

    def fetch(self) -> IndexHealth:
        """The report with every count read to the host in one transfer;
        the repair mask stays on the device."""
        counts = jax.device_get(dataclasses.replace(self, repair_mask=None))
        return dataclasses.replace(counts, repair_mask=self.repair_mask)

    @property
    def deleted_frac(self) -> float:
        """Mark-deleted fraction of allocated slots (0 when empty)."""
        return float(self.deleted) / max(float(self.allocated), 1.0)

    def asdict(self) -> dict:
        """Host-side summary (python scalars; JSON/metrics friendly)."""
        return {
            "capacity": int(self.capacity),
            "allocated": int(self.allocated),
            "live": int(self.live),
            "deleted": int(self.deleted),
            "deleted_frac": self.deleted_frac,
            "unreachable_def1": int(self.unreachable_def1),
            "unreachable_bfs": int(self.unreachable_bfs),
            "max_layer": int(self.max_layer),
            "indegree_hist": np.asarray(self.indegree_hist).tolist(),
        }

    def __repr__(self) -> str:
        return (f"IndexHealth(live={int(self.live)}, "
                f"deleted={int(self.deleted)} "
                f"({self.deleted_frac:.1%} of allocated), "
                f"unreachable_def1={int(self.unreachable_def1)}, "
                f"unreachable_bfs={int(self.unreachable_bfs)})")


@jax.jit
def index_health(index: HNSWIndex) -> IndexHealth:
    """Gather the :class:`IndexHealth` report in one jitted program.

    A handful of O(N) reductions plus the BFS reachability fix-point —
    cheap next to one update drain, which is why the maintenance policy can
    afford to consult it every cycle. The union of both criteria is the
    mask :func:`repair_unreachable` re-links, so a pass on the same index
    need not sweep again.
    """
    alloc = index.levels >= 0
    live = alloc & ~index.deleted
    deg = indegree(index)
    def1 = definition1(index, deg)
    bfs = bfs_unreachable(index)
    nbins = len(HIST_SPLITS) + 1
    b = jnp.searchsorted(jnp.asarray(HIST_SPLITS, jnp.int32), deg,
                         side="right")
    hist = jnp.sum(live & (b == jnp.arange(nbins)[:, None]), axis=1,
                   dtype=jnp.int32)
    return IndexHealth(
        capacity=jnp.int32(index.capacity),
        allocated=jnp.sum(alloc).astype(jnp.int32),
        live=jnp.sum(live).astype(jnp.int32),
        deleted=jnp.sum(alloc & index.deleted).astype(jnp.int32),
        unreachable_def1=jnp.sum(def1, dtype=jnp.int32),
        unreachable_bfs=jnp.sum(bfs, dtype=jnp.int32),
        max_layer=index.max_layer.astype(jnp.int32),
        indegree_hist=hist,
        repair_mask=def1 | bfs,
    )


# ---------------------------------------------------------------------------
# batched delete consolidation (FreshDiskANN-style)
# ---------------------------------------------------------------------------

#: rows re-pruned per step of the consolidation sweep. Each step gathers
#: the vectors of ``block * (M0 + M0**2)`` pool candidates, so the block
#: (not the capacity) bounds the pass's temporary memory
CONSOLIDATE_BLOCK = 1024


def _consolidate_layer(params: HNSWParams, layer_nbrs: jax.Array,
                       vectors: jax.Array, live: jax.Array,
                       del_mask: jax.Array, layer: int) -> jax.Array:
    """Re-prune every live row with an edge into a deleted slot (one layer).

    ``layer_nbrs``: [N, M0] adjacency of one layer; returns the repaired
    copy. Affected vertices re-select from ``N(v) ∪ ⋃_{d∈N(v)∩D} N(d)``,
    reduced to the ``3*M0`` nearest candidates by ONE batched distance
    contraction before the (vmapped) alpha-RNG dominance sweep — the sweep
    is the expensive part, so the pre-reduction keeps its lane count
    bounded by the degree, not the pool square.

    Only the affected rows are visited: their ids are compacted to the
    front of one list and a ``fori_loop`` re-prunes them
    :data:`CONSOLIDATE_BLOCK` rows at a time, so time scales with the
    affected count and memory with the block. Every pool reads the
    pre-pass adjacency, so the result does not depend on the block order.
    """
    N, M0 = layer_nbrs.shape
    m_l = params.m_for_layer(layer)
    block = min(N, CONSOLIDATE_BLOCK)

    rc = jnp.clip(layer_nbrs, 0)
    edge_to_del = (layer_nbrs >= 0) & del_mask[rc]            # [N, M0]
    affected = live & jnp.any(edge_to_del, axis=1)            # [N]
    n_blocks = (jnp.sum(affected) + block - 1) // block
    rows_all = jnp.nonzero(affected, size=N + (-N) % block,
                           fill_value=N)[0].astype(jnp.int32)
    k_sel = min(M0 + M0 * M0, 3 * M0)

    def repair_one(v, vpool):
        pc = jnp.clip(vpool, 0)
        ok = (vpool >= 0) & live[pc] & (vpool != v)
        dq = jnp.where(ok, dist_point(params.space, vectors[v], vectors[pc]),
                       INF)
        ids = jnp.where(ok, vpool, INVALID)
        # ONE contraction ranked the whole pool; keep the k_sel nearest so
        # the dominance sweep below scans a bounded candidate list
        order = jnp.argsort(dq)[:k_sel]
        sel, _ = alpha_rng_select(ids[order], dq[order],
                                  vectors[pc[order]], m_l, params.alpha,
                                  params.space)
        row = jnp.full((M0,), INVALID, jnp.int32).at[:m_l].set(sel[:m_l])
        return row

    def repair_block(b, out):
        rows = jax.lax.dynamic_slice(rows_all, (b * block,), (block,))
        vc = jnp.clip(rows, 0, N - 1)
        own = layer_nbrs[vc]                                  # [B, M0]
        # candidate pool: own row ∪ rows of its deleted neighbours
        ext = jnp.where(edge_to_del[vc][:, :, None],
                        layer_nbrs[jnp.clip(own, 0)], INVALID)
        pool = jnp.concatenate([own, ext.reshape(block, M0 * M0)], axis=1)
        new_rows = jax.vmap(repair_one)(vc, pool)
        return out.at[rows].set(new_rows, mode="drop")

    return jax.lax.fori_loop(0, n_blocks, repair_block, layer_nbrs)


def _consolidate(params: HNSWParams, index: HNSWIndex,
                 del_mask: jax.Array) -> HNSWIndex:
    alloc = index.levels >= 0
    live = alloc & ~index.deleted
    nbrs = index.neighbors
    for layer in range(params.num_layers):
        nbrs = nbrs.at[layer].set(_consolidate_layer(
            params, nbrs[layer], index.vectors, live, del_mask, layer))

    # clear the consolidated slots: they become free capacity (levels = -1)
    labels = jnp.where(del_mask, INVALID, index.labels)
    levels = jnp.where(del_mask, -1, index.levels)
    deleted = index.deleted & ~del_mask
    nbrs = jnp.where(del_mask[None, :, None], INVALID, nbrs)

    # re-derive the entry invariant: entry lives at the top remaining layer
    live_new = levels >= 0
    lvl_masked = jnp.where(live_new, levels, -1)
    top = jnp.argmax(lvl_masked).astype(jnp.int32)
    new_max = lvl_masked[top].astype(jnp.int32)
    keep = (index.entry >= 0) & live_new[jnp.clip(index.entry, 0)] \
        & (lvl_masked[jnp.clip(index.entry, 0)] == new_max)
    entry = jnp.where(new_max < 0, INVALID,
                      jnp.where(keep, index.entry, top)).astype(jnp.int32)
    count = jnp.sum(live_new).astype(jnp.int32)
    return HNSWIndex(index.vectors, labels, levels, nbrs, deleted, entry,
                     new_max, count, index.rng)


@partial(jax.jit, static_argnames=("params",))
def consolidate_deletes(params: HNSWParams, index: HNSWIndex) -> HNSWIndex:
    """Batched delete consolidation: repair all affected neighbourhoods in
    one pass, then reclaim every mark-deleted slot as free capacity.

    FreshDiskANN's consolidation discipline on the tensorised index: every
    live vertex ``v`` with an edge into the deleted set ``D`` re-selects
    its row from ``N(v) ∪ ⋃_{d ∈ N(v) ∩ D} N(d) \\ D`` under the alpha-RNG
    rule (``params.alpha``), vectorized across ALL vertices and repaired
    layer by layer — no per-op ``lax.scan``, one compiled sweep regardless
    of how many deletes accumulated. Deleted slots then drop out of the
    graph entirely (``levels = -1``, rows cleared, labels freed), the entry
    point / ``max_layer`` / ``count`` invariants are re-derived, and the
    freed slots are reusable by any later insert.

    Idempotent: with no mark-deleted slots the index is returned untouched.
    Consolidation can orphan a point whose only in-edges ran through
    ``D`` — run :func:`repair_unreachable` after (the policy driver does).
    """
    del_mask = index.deleted & (index.levels >= 0)
    return jax.lax.cond(
        jnp.any(del_mask),
        lambda ix: _consolidate(params, ix, del_mask),
        lambda ix: ix, index)


# ---------------------------------------------------------------------------
# unreachable-point repair
# ---------------------------------------------------------------------------

def _force_in_edges(params: HNSWParams, index: HNSWIndex
                    ) -> tuple[HNSWIndex, jax.Array]:
    """Connectivity backstop: give every Definition-1 orphan an in-edge.

    Re-linking point A can cost point B its last in-edge (A's reverse
    edges re-prune full rows, and A's own row is rewritten), so a repair
    pass alone can leave orphans behind pass after pass. Here each orphan
    is linked from the nearest of its layer-0 out-neighbours whose row has
    a free slot or holds a point with another in-edge to spare (the
    farthest such point is evicted) — the keep-connected override hnswlib
    applies, with in-degrees kept up to date so that no link taken here
    orphans anyone. Returns the index and its :func:`indegree`.
    """
    L, N, M0 = index.neighbors.shape
    deg = indegree(index)
    mask = definition1(index, deg)
    order = jnp.argsort(jnp.where(mask, jnp.arange(N), N))   # orphans first

    def body(i, carry):
        nbrs, deg = carry
        pid = order[i]
        owners = nbrs[0, pid]                                 # [M0]
        oc = jnp.clip(owners, 0)
        rows = nbrs[0, oc]                                    # [M0, M0]
        rc = jnp.clip(rows, 0)
        spare = (rows >= 0) & (rows != pid) & (deg[rc] >= 2)
        d = jax.vmap(lambda o, r: dist_point(params.space, index.vectors[o],
                                             index.vectors[r]))(oc, rc)
        score = jnp.where(rows < 0, INF, jnp.where(spare, d, -INF))
        usable = ((owners >= 0) & (owners != pid) & (index.levels[oc] >= 0)
                  & jnp.any(score > -INF, axis=1))
        j = jnp.argmax(usable)                 # nearest usable owner
        k = jnp.argmax(score[j])               # free slot, else farthest
        ok = usable[j] & (deg[pid] == 0)
        victim = rows[j, k]
        nbrs = nbrs.at[0, oc[j], k].set(jnp.where(ok, pid, victim))
        deg = deg.at[pid].add(ok.astype(jnp.int32))
        deg = deg.at[jnp.clip(victim, 0)].add(
            -(ok & (victim >= 0)).astype(jnp.int32))
        return nbrs, deg

    nbrs, deg = jax.lax.fori_loop(0, jnp.sum(mask, dtype=jnp.int32), body,
                                  (index.neighbors, deg))
    return dataclasses.replace(index, neighbors=nbrs), deg


def _repair(params: HNSWParams, index: HNSWIndex, mask: jax.Array
            ) -> tuple[HNSWIndex, jax.Array]:
    """Re-link every point of ``mask``, then the backstop: the repaired
    index and its Definition-1 count, read off the in-degree the backstop
    keeps current."""
    # local import: update.py imports nothing from this module, so the
    # dependency stays one-directional at runtime (both live in core)
    from .update import _update_reinsert

    N = index.capacity
    order = jnp.argsort(jnp.where(mask, jnp.arange(N), N))   # unreachable first
    n_u = jnp.sum(mask).astype(jnp.int32)

    def body(i, ix):
        pid = order[i]
        return _update_reinsert(params, ix, ix.vectors[pid], pid, params.alpha)

    out, deg = _force_in_edges(params, jax.lax.fori_loop(0, n_u, body, index))
    return out, jnp.sum(definition1(out, deg), dtype=jnp.int32)


# the trace names a program after its function, and the maintenance layer's
# device time is read under the public name
_repair.__name__ = "repair_unreachable"
_repair = jax.jit(_repair, static_argnames=("params",))


@dataclasses.dataclass
class _Known:
    """What a maintenance consult knows of ``index`` without sweeping it:
    its Definition-1 count and, until a pass changes it, its repair mask;
    and how many passes took such a mask."""
    index: HNSWIndex
    def1: int | jax.Array
    mask: jax.Array | None
    mask_reused: int = 0


# set by run_maintenance around its repair passes; repair_unreachable keeps
# its public signature and reads the consult's sweep from here
_consult: contextvars.ContextVar[_Known | None] = contextvars.ContextVar(
    "repro_maintenance_consult", default=None)


def repair_unreachable(params: HNSWParams, index: HNSWIndex) -> HNSWIndex:
    """Batch re-link every unreachable live point back into the graph.

    Takes the union of the paper's Definition-1 criterion
    (:func:`~repro.core.reach.indegree_unreachable`) and BFS
    unreachability, :attr:`IndexHealth.repair_mask`, then re-links each
    point through the layer-inheriting reinsert path (paper Algorithm 3:
    greedy descent above its level, beam search + alpha-RNG select +
    reverse edges at its levels), followed by the :func:`_force_in_edges`
    backstop for the Definition-1 orphans the re-links left or made. One
    compiled program; the loop bounds are the (traced) unreachable counts.

    Inside :func:`run_maintenance` a pass on the index the consult swept
    takes that sweep's mask, and each pass leaves the Definition-1 count of
    its output for the check after it: neither sweeps again. Anywhere else
    the mask comes from an :func:`index_health` sweep of ``index``.

    The backstop leaves a Definition-1 orphan only when none of its
    layer-0 out-neighbours can take it without orphaning another point;
    :func:`run_maintenance` / ``VectorIndex.repair_unreachable`` re-check
    and run further passes for that case.
    """
    known = _consult.get()
    if known is not None and known.index is index \
            and known.mask is not None:
        mask = known.mask
        known.mask_reused += 1
    else:
        mask = index_health(index).repair_mask
    out, def1 = _repair(params, index, mask)
    if known is not None:
        known.index, known.def1, known.mask = out, def1, None
    return out


# ---------------------------------------------------------------------------
# full rebuild (the old VectorIndex.compact) — kept as the escape hatch
# ---------------------------------------------------------------------------

def rebuild_index(params: HNSWParams, index: HNSWIndex,
                  capacity: int | None = None, seed: int = 0) -> HNSWIndex:
    """Full blocking rebuild over live points only (host-side).

    The graph is reconstructed from scratch — deleted points no longer
    pollute neighbourhoods and accumulated topology damage is erased — at
    the cost of ``build``'s sequential insert loop. ``capacity`` defaults
    to the current one and may shrink as long as the live set fits
    (pow2-rounded). This is ``VectorIndex.compact()``'s engine; prefer
    :func:`consolidate_deletes` for routine online reclamation.
    """
    from .hnsw import build

    mask = np.asarray((index.levels >= 0) & ~index.deleted)
    vecs = np.asarray(index.vectors)[mask]
    labels = np.asarray(index.labels)[mask]
    live = int(mask.sum())
    new_cap = pow2_at_least(max(capacity or index.capacity, live, 1))
    if live == 0:
        return empty_index(params, new_cap, index.dim, seed,
                           dtype=index.vectors.dtype)
    return build(params, jnp.asarray(vecs, index.vectors.dtype),
                 jnp.asarray(labels), seed=seed, capacity=new_cap)


# ---------------------------------------------------------------------------
# policy: when to run which pass
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MaintenancePolicy:
    """Health-driven trigger thresholds for the online maintenance passes.

    Consumed by the serving engine (consulted between ``pump()`` ticks,
    passes run on the back buffer and swap in as a new epoch) and by the
    facade (consulted after mutation batches). All knobs are documented in
    docs/MAINTENANCE.md.
    """
    deleted_frac: float = 0.25   # consolidate at/above this mark-deleted
                                 # fraction of allocated slots
    min_deleted: int = 32        # ... and only once this many slots are
                                 # mark-deleted (skip trivia)
    unreachable: int = 0         # repair when the Definition-1 count
                                 # exceeds this
    check_every: int = 64        # facade: consult health every N applied
                                 # ops (the engine has its own pump-scale
                                 # cadence knob, ServingEngine's
                                 # maintain_every)
    repair_passes: int = 3       # max repair sweeps per trigger (re-checked
                                 # between sweeps; converges in 1-2)

    def __post_init__(self):
        if not 0.0 < self.deleted_frac <= 1.0:
            raise ValueError(f"deleted_frac must be in (0, 1], got "
                             f"{self.deleted_frac}")
        if self.check_every < 1 or self.repair_passes < 0:
            raise ValueError("check_every must be >= 1 and repair_passes "
                             ">= 0")

    def should_consolidate(self, h: IndexHealth) -> bool:
        return (int(h.deleted) >= max(self.min_deleted, 1)
                and h.deleted_frac >= self.deleted_frac)

    def should_repair(self, h: IndexHealth) -> bool:
        return int(h.unreachable_def1) > self.unreachable


def run_maintenance(params: HNSWParams, index: HNSWIndex,
                    policy: MaintenancePolicy,
                    health: IndexHealth | None = None
                    ) -> tuple[HNSWIndex, dict]:
    """One policy consult + any due passes (host-side driver).

    Returns ``(index, report)`` where ``report`` records what ran:
    ``{"consolidated": bool, "reclaimed": int, "repair_passes": int,
    "unreachable_def1": int, "mask_reused": int, "recheck_sweeps": int}``.
    Repair follows consolidation because clearing deleted slots can orphan
    points whose in-edges ran through them; the repair loop re-checks the
    Definition-1 count between sweeps and stops at ``policy.repair_passes``.

    ``health`` (default: swept here) must describe ``index``. Its sweep
    stands until the index changes: its count is the check before the
    first pass, and that pass takes its mask (``mask_reused``). A
    consolidation changes the index, so one :func:`index_health` sweep
    then serves as both (``recheck_sweeps``). The check after each pass
    reads the count the pass left; a second pass sweeps for its own mask.
    """
    h = health if health is not None else index_health(index).fetch()
    report = {"consolidated": False, "reclaimed": 0, "repair_passes": 0,
              "unreachable_def1": int(h.unreachable_def1),
              "mask_reused": 0, "recheck_sweeps": 0}
    if policy.should_consolidate(h):
        with span("consolidate"):
            index = consolidate_deletes(params, index)
        report["consolidated"] = True
        report["reclaimed"] = int(h.deleted)
        with span("recheck"):
            h = index_health(index).fetch()
        report["recheck_sweeps"] += 1
    elif not policy.should_repair(h):
        return index, report
    known = _Known(index, int(h.unreachable_def1), h.repair_mask)
    token = _consult.set(known)
    try:
        def1 = known.def1
        for _ in range(policy.repair_passes):
            if def1 <= policy.unreachable:
                break
            with span("repair"):
                index = repair_unreachable(params, index)
            report["repair_passes"] += 1
            with span("recheck"):
                # a repair that bypassed the consult left no count
                if known.index is not index:
                    known.index, known.mask = index, None
                    known.def1 = jnp.sum(indegree_unreachable(index))
                    report["recheck_sweeps"] += 1
                def1 = int(known.def1)
    finally:
        _consult.reset(token)
    report["unreachable_def1"] = def1
    report["mask_reused"] = known.mask_reused
    return index, report
