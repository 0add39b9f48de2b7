"""ServingEngine: micro-batched queries over epoch snapshots + fused writes.

One object ties the serving substrate together:

  * reads  — :class:`MicroBatcher` coalesces single queries and serves them
             against the published :class:`EpochSnapshot`; each dispatched
             bucket is routed by the query execution planner
             (``mode="auto"``): HNSW beam search — dualSearch when a backup
             index is enabled — or the exact Pallas scan tier when the
             snapshot is small or churn-heavy (``mode=`` pins a tier);
  * writes — :class:`UpdateScheduler` queues delete/replace/insert ops and
             drains the whole backlog into the back buffer in one call:
             ``execution="wave"`` (default) compiles the tape into
             conflict-free vectorized waves (``core.batch_update`` —
             ``waves_per_pump`` in :class:`PumpStats`/metrics counts the
             dispatched wave programs), ``execution="sequential"`` keeps
             the one-op-per-scan-step tape;
  * maintenance — tau-triggered backup rebuilds over unreachable points,
             folded into the cycle instead of blocking a write call, plus
             (with ``maintenance=MaintenancePolicy(...)``) health-driven
             delete consolidation and unreachable-point repair
             (:mod:`repro.core.maintenance`): the passes run on the back
             buffer — never the published snapshot — and swap in as a new
             epoch, which also re-keys the batcher's planner stats cache
             so ``mode="auto"`` re-routes once the deleted fraction drops;
  * publication — ``SnapshotStore.publish()`` swaps the back buffer in,
             bumping the epoch.

The event loop is ONE deterministic method, :meth:`pump`:

    serve pending queries (old snapshot) -> drain updates -> maybe rebuild
    backup -> publish new snapshot

so tests and drivers can single-step the engine without threads — queries
submitted before a pump are guaranteed to be served against the pre-pump
epoch, never a half-applied write batch.

Sharded mode: pass ``mesh=`` (and a stacked index from
``core.distributed.build_sharded``) and the engine reroutes queries through
``sharded_batch_knn`` (one all_gather merge per batch) and updates through
``sharded_update`` (SPMD-routed per op). Backup/dualSearch is single-host
only for now.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.common import span, span_tags, trace_gc
from repro.core.index import HNSWIndex, HNSWParams, empty_index
from repro.core.maintenance import (MaintenancePolicy, index_health,
                                    run_maintenance)
from repro.core.reach import count_unreachable
from repro.core.update import OP_DELETE, OP_INSERT, OP_NOP

from .batcher import MicroBatcher, QueryTicket
from .metrics import MetricsRegistry
from .snapshot import EpochSnapshot, SnapshotStore
from .update_queue import UpdateOp, UpdateScheduler


@dataclasses.dataclass(frozen=True)
class PumpStats:
    """What one deterministic event-loop step did."""
    epoch: int
    queries_served: int
    updates_applied: int
    backup_rebuilt: bool
    update_backlog: int
    maintenance_ran: bool = False
    waves_per_pump: int = 0    # wave programs the drain dispatched (0 when
                               # nothing drained or execution="sequential")


class ServingEngine:
    def __init__(self, params: HNSWParams, index: HNSWIndex, *, k: int = 10,
                 ef: int | None = None, variant: str = "mn_ru_gamma",
                 max_batch: int = 64, max_ops_per_drain: int = 128,
                 tau: int = 0, backup_capacity: int = 0,
                 backup_params: HNSWParams | None = None,
                 mesh=None, axis: str = "data",
                 track_unreachable: bool = False,
                 mode: str = "auto", planner=None,
                 maintenance: MaintenancePolicy | None = None,
                 maintain_every: int = 1,
                 execution: str = "wave",
                 metrics: MetricsRegistry | None = None):
        self.params = params
        self.k = k
        self.ef = ef
        self.variant = variant
        self.execution = execution
        self.mesh = mesh
        self.axis = axis
        self.track_unreachable = track_unreachable
        self.maintenance = maintenance
        # cadence is in PUMPS here (one pump drains up to max_ops_per_drain
        # ops); the policy's check_every stays an op-count knob for the
        # facade's mutation path and is NOT reused in the engine
        if maintain_every < 1:
            raise ValueError("maintain_every must be >= 1")
        self.maintain_every = maintain_every
        self._pumps_since_maintenance = 0
        self._last_health = None     # health of the staged index, when fresh
        self._dirty_since_consult = True   # writes since the last consult
        self.metrics = metrics or MetricsRegistry()
        self.dim = int(index.vectors.shape[-1])
        trace_gc()

        sharded = mesh is not None
        use_backup = tau > 0 and backup_capacity > 0
        if sharded and mode == "exact":
            raise ValueError("the exact scan tier is not supported in "
                             "sharded mode yet — use mode='auto' or "
                             "'graph' (auto pins the graph tier)")
        if sharded and use_backup:
            raise ValueError("backup/dualSearch is not supported in sharded "
                             "mode yet — drop tau/backup_capacity")
        if sharded and maintenance is not None:
            # consolidation/repair are single-graph passes; stacked-index
            # maintenance is a follow-up
            raise ValueError("maintenance policies are not supported in "
                             "sharded mode yet — drop maintenance=")
        backup = None
        if use_backup:
            backup = empty_index(backup_params or params, backup_capacity,
                                 self.dim, 1, dtype=index.vectors.dtype)

        self.store = SnapshotStore(index, backup)
        # sharded mode pins the graph tier (the stacked index's exact scan
        # is a follow-up); single-host dispatch consults the query planner
        self.batcher = MicroBatcher(
            params, k, ef, max_batch, metrics=self.metrics,
            search_fn=self._sharded_search if sharded else None,
            backup_params=backup_params, mode="graph" if sharded else mode,
            planner=planner)
        self.scheduler = UpdateScheduler(
            params, self.dim, variant, max_ops_per_drain, tau=tau,
            backup_params=backup_params, backup_capacity=backup_capacity,
            metrics=self.metrics, execution=execution,
            apply_fn=self._sharded_apply if sharded else None)

    # -- sharded routing ----------------------------------------------------
    def _sharded_search(self, snapshot: EpochSnapshot, Q):
        from repro.core.distributed import sharded_batch_knn
        return sharded_batch_knn(self.params, snapshot.index, Q, self.k,
                                 self.mesh, self.axis, self.ef)

    def _sharded_apply(self, index, ops, labels, X):
        """Route each tape op to its owning shard (uniform SPMD no-op
        elsewhere). One collective program per op — batching collectives is
        a follow-up; correctness-first."""
        from repro.core.distributed import sharded_update
        ops_np = np.asarray(ops)
        labels_np = np.asarray(labels)
        for i in range(ops_np.shape[0]):
            op = int(ops_np[i])
            if op == OP_NOP:
                continue
            if op == OP_DELETE:
                dl, nl = jnp.int32(labels_np[i]), jnp.int32(-1)
            else:
                dl, nl = jnp.int32(-1), jnp.int32(labels_np[i])
            index = sharded_update(self.params, index, dl, X[i], nl,
                                   self.mesh, self.axis, self.variant,
                                   fresh_insert=(op == OP_INSERT))
        return index

    # -- client API ---------------------------------------------------------
    def search(self, q) -> QueryTicket:
        """Enqueue one query; served at the next ``pump()``."""
        return self.batcher.submit(q)

    def delete(self, label: int) -> None:
        self.scheduler.delete(label)

    def update(self, vector, label: int) -> None:
        """replaced_update: new point reuses a deleted slot (paper Alg. 2+3)."""
        self.scheduler.replace(vector, label)

    def insert(self, vector, label: int) -> None:
        self.scheduler.insert(vector, label)

    def submit_update(self, op: UpdateOp) -> None:
        self.scheduler.submit(op)

    @property
    def epoch(self) -> int:
        return self.store.epoch

    @property
    def update_backlog(self) -> int:
        return self.scheduler.backlog

    @property
    def query_backlog(self) -> int:
        return self.batcher.pending

    def snapshot(self) -> EpochSnapshot:
        return self.store.current()

    # -- the event loop -----------------------------------------------------
    def pump(self, max_updates: int | None = None) -> PumpStats:
        """One deterministic serve/maintain/publish step.

        Each step runs inside a ``repro.*`` host span (:func:`span`), all
        tagged ``pump=<n>`` with this pump's number (the ``pumps`` counter
        once it returns)."""
        snap = self.store.current()
        n = self.metrics.counter("pumps").value + 1
        with span_tags(pump=n), span("pump", epoch=snap.epoch):
            return self._pump(snap, max_updates)

    def _pump(self, snap: EpochSnapshot, max_updates: int | None
              ) -> PumpStats:
        served = self.batcher.flush(snap)

        new_index, applied = self.scheduler.drain(self.store.working_index(),
                                                  max_updates)
        waves = self.scheduler.last_drain_waves if applied else 0
        if applied:
            self.store.stage(index=new_index)

        backup = self.scheduler.maybe_rebuild(self.store.working_index())
        rebuilt = backup is not None
        if rebuilt:
            self.store.stage(backup=backup)

        if applied:                    # main-index writes age the health
            self._dirty_since_consult = True
            self._last_health = None
        maintained = self._maybe_maintain()

        with span("publish"):
            out = self.store.publish()
            if self.track_unreachable and out.epoch != snap.epoch:
                self._gauge_unreachable(out.index)

        self.metrics.counter("pumps").inc()
        self.metrics.set_gauge("epoch", out.epoch)
        self.metrics.set_gauge("waves_per_pump", waves)
        self.metrics.set_gauge("update_lag_ops", self.scheduler.backlog)
        return PumpStats(epoch=out.epoch, queries_served=len(served),
                         updates_applied=applied, backup_rebuilt=rebuilt,
                         update_backlog=self.scheduler.backlog,
                         maintenance_ran=maintained, waves_per_pump=waves)

    def _gauge_unreachable(self, index: HNSWIndex) -> None:
        if self.mesh is not None:
            u_ind, u_bfs = self._sharded_count_unreachable(index)
        elif self._last_health is not None:
            # the maintenance consult already swept this exact index —
            # don't run the O(L*N*M0) reachability fix-point twice
            u_ind = int(self._last_health.unreachable_def1)
            u_bfs = int(self._last_health.unreachable_bfs)
        else:
            u_ind, u_bfs = count_unreachable(index)
        self.metrics.set_gauge("unreachable_indegree", int(u_ind))
        self.metrics.set_gauge("unreachable_bfs", int(u_bfs))
        self.metrics.histogram("unreachable_per_epoch").observe(int(u_ind))

    def _sharded_count_unreachable(self, stacked: HNSWIndex):
        """Per-shard reachability sweeps summed into the global gauges.

        ``count_unreachable`` expects one [L, N, M0] adjacency; a stacked
        index vmaps it over the shard axis (each shard is an independent
        sub-graph with its own entry point) and the counts sum — labels are
        partitioned by ``label % nshards`` so no point is double-counted.
        """
        u_ind, u_bfs = jax.vmap(count_unreachable)(stacked)
        return int(jnp.sum(u_ind)), int(jnp.sum(u_bfs))

    def _maybe_maintain(self) -> bool:
        """Policy-gated consolidation/repair on the back buffer.

        Runs between the drain and the publish: the working (shadow) index
        is consolidated/repaired off-snapshot and staged, so readers only
        ever see the result as a whole new epoch. The batcher's per-epoch
        planner stats are invalidated explicitly as well — the very next
        bucket must re-consult ``choose_tier`` against the maintained
        state (e.g. route back to the graph tier once the deleted
        fraction drops).
        """
        if self.maintenance is None:
            self._last_health = None
            return False
        self._pumps_since_maintenance += 1
        if self._pumps_since_maintenance < self.maintain_every:
            return False
        if not self._dirty_since_consult:
            # no writes since the last consult: the health of an unchanged
            # index is unchanged — idle pumps must not pay the O(L*N*M0)
            # reachability sweep (``_last_health`` stays valid too)
            return False
        self._pumps_since_maintenance = 0
        with span("maintain"):
            return self._maintain()

    def _maintain(self) -> bool:
        with span("health"):
            # one device-to-host read of every count the policy consults;
            # the repair mask stays on the device for the first pass
            h = index_health(self.store.working_index()).fetch()
        new_index, report = run_maintenance(
            self.params, self.store.working_index(), self.maintenance,
            health=h)
        if not (report["consolidated"] or report["repair_passes"]):
            # nothing ran: h still describes the index about to publish —
            # keep it so the unreachable gauges can reuse the sweep
            self._last_health = h
            self._dirty_since_consult = False
            return False
        # maintenance itself rewrote the index: the next consult must
        # re-sweep, and the cached health no longer matches
        self._last_health = None
        self._dirty_since_consult = True
        self.store.stage(index=new_index)
        self.batcher.invalidate_stats()
        if report["consolidated"]:
            self.metrics.counter("maintenance_consolidations").inc()
            self.metrics.counter("maintenance_slots_reclaimed").inc(
                report["reclaimed"])
        self.metrics.counter("maintenance_repair_passes").inc(
            report["repair_passes"])
        self.metrics.counter("maintenance_mask_reused").inc(
            report["mask_reused"])
        self.metrics.counter("maintenance_recheck_sweeps").inc(
            report["recheck_sweeps"])
        self.metrics.set_gauge("maintenance_unreachable_def1",
                               report["unreachable_def1"])
        return True

    def drain_all(self, max_pumps: int = 1_000) -> list[PumpStats]:
        """Pump until both queues are empty (or ``max_pumps``)."""
        stats = []
        for _ in range(max_pumps):
            stats.append(self.pump())
            if self.update_backlog == 0 and self.query_backlog == 0:
                break
        return stats

    def stats(self) -> dict:
        return self.metrics.to_dict()
