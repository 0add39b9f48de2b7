"""`VectorIndex`: the hnswlib-class facade over the tensorised MN-RU core.

One object is the public surface for everything the repo can do to a vector
index — the free functions (``build`` / ``batch_knn`` / ``replaced_update_jit``
/ ``apply_update_batch``), the metric registry, the update-strategy registry,
capacity growth, and the serving engine all sit behind it:

    from repro import api

    vi = api.create(space="cosine", dim=64, capacity=1000)
    vi.add_items(X, labels)                       # grows past capacity
    labels, dists = vi.knn_query(Q, k=10, ef=64)  # planner-routed (auto)
    labels, dists = vi.knn_query(Q, k=10, mode="exact")   # Pallas scan tier
    labels, dists = vi.knn_query(Q, k=10, filter=allowed_labels)
    vi.mark_deleted(stale_labels)
    vi.replace_items(fresh_X, fresh_labels)       # paper Alg. 2+3 repair
    vi.health()                                   # IndexHealth report
    vi.consolidate()                              # reclaim deleted slots online
    vi.repair_unreachable()                       # Definition-1 count -> 0
    vi.save("index.npz"); vi = api.VectorIndex.load("index.npz")
    engine = vi.serve(k=10, tau=400, backup_capacity=256)

Pass ``maintenance=MaintenancePolicy(...)`` and the facade (and any engine
it spawns via ``.serve()``) runs consolidation/repair automatically when
the health report crosses the policy thresholds (docs/MAINTENANCE.md).

Design notes:

  * capacities are powers of two — construction rounds up, ``add_items``
    past capacity triggers a pow2 repack through
    :func:`~repro.core.index.resize_index` — so the per-capacity jit
    specialisations stay at one program per doubling, not per size;
  * mutations ride the fused op tape (``apply_update_batch``) through the
    wave-parallel batch executor (``core.batch_update``): one call per
    mutation batch, deletes vectorized, inserts/replaces in pow2-bucketed
    conflict-free waves — the same compiled programs the serving engine
    drains, so an interactive facade session and a production engine share
    caches; bulk ``add_items`` on an empty index builds in ``O(log n)``
    waves via ``build_batch``;
  * ``cosine`` unit-normalises vectors AND queries at ingest (the metric
    registry's ``normalize_ingest`` flag); the core only ever sees the
    cheap ``1 - <q, x>`` kernel;
  * the facade is a host-side convenience shell: the underlying pytree is
    exposed as ``.index`` / ``.params`` for anything that wants to drop to
    the functional core (sharding, custom jits, checkpoints).
"""
from __future__ import annotations

import dataclasses
import json

import jax.numpy as jnp
import numpy as np

from repro.core.hnsw import build as _build
from repro.core.index import (HNSWIndex, HNSWParams, empty_index,
                              resize_index)
from repro.core.common import pow2_at_least as _pow2_at_least
from repro.core.maintenance import (IndexHealth, MaintenancePolicy,
                                    consolidate_deletes, index_health,
                                    rebuild_index,
                                    repair_unreachable as _repair_unreachable,
                                    run_maintenance)
from repro.core.metrics import get_metric, normalize_rows
from repro.core.planner import (DEFAULT_PLANNER, PlanDecision, PlannerConfig,
                                choose_tier, index_stats, plan_and_search)
from repro.core.reach import indegree_unreachable
from repro.core.strategies import get_strategy
from repro.core.update import (OP_DELETE, OP_INSERT, OP_REPLACE, OP_NOP,
                               apply_update_batch_jit, num_deleted)

_SAVE_VERSION = 1
_MAX_TAPE = 128          # mutation tape chunk cap (pow2; bounds compile count)


class VectorIndex:
    """A metric-space vector database over one HNSW pytree.

    Constructor arguments mirror hnswlib's ``Index(space, dim)`` +
    ``init_index``; :func:`create` is the one-call convenience wrapper.
    """

    def __init__(self, space: str = "l2", dim: int = 0, capacity: int = 1024,
                 M: int = 8, M0: int | None = None, num_layers: int = 4,
                 ef_construction: int = 64, ef_search: int = 32,
                 alpha: float = 1.0, strategy: str = "mn_ru_gamma",
                 seed: int = 0, dtype=jnp.float32,
                 planner: PlannerConfig | None = None,
                 maintenance: MaintenancePolicy | None = None,
                 _index: HNSWIndex | None = None,
                 _next_label: int = 0):
        if dim <= 0:
            raise ValueError(f"dim must be positive, got {dim}")
        self.metric = get_metric(space)          # validates the space
        get_strategy(strategy)                   # fail-fast, uniform error
        self.strategy = strategy
        self.planner = planner if planner is not None else DEFAULT_PLANNER
        self.maintenance = maintenance
        self._ops_since_maintenance = 0
        self.params = HNSWParams(
            M=M, M0=M0 if M0 is not None else 2 * M, num_layers=num_layers,
            ef_construction=ef_construction, ef_search=ef_search,
            alpha=alpha, space=space)
        self._seed = seed
        self._index = _index if _index is not None else empty_index(
            self.params, _pow2_at_least(capacity), dim, seed, dtype=dtype)
        self._next_label = _next_label

    # -- introspection ------------------------------------------------------

    @property
    def space(self) -> str:
        return self.params.space

    @property
    def dim(self) -> int:
        return self._index.dim

    @property
    def capacity(self) -> int:
        return self._index.capacity

    @property
    def index(self) -> HNSWIndex:
        """The underlying functional pytree (escape hatch to the core)."""
        return self._index

    @property
    def count(self) -> int:
        """Live (queryable) points: allocated and not mark-deleted."""
        return int(jnp.sum((self._index.levels >= 0) & ~self._index.deleted))

    @property
    def deleted_count(self) -> int:
        return int(num_deleted(self._index))

    def __len__(self) -> int:
        return self.count

    def __repr__(self) -> str:
        return (f"VectorIndex(space={self.space!r}, dim={self.dim}, "
                f"count={self.count}, capacity={self.capacity}, "
                f"strategy={self.strategy!r})")

    def _used_slots(self) -> int:
        """Allocated slots (live + mark-deleted) — what capacity bounds."""
        return int(jnp.sum(self._index.levels >= 0))

    # -- ingest helpers -----------------------------------------------------

    def _prep_vectors(self, X) -> np.ndarray:
        X = np.asarray(X, np.float32)
        if X.ndim == 1:
            X = X[None, :]
        if X.ndim != 2 or X.shape[1] != self.dim:
            raise ValueError(f"expected vectors of shape [n, {self.dim}], "
                             f"got {X.shape}")
        if self.metric.normalize_ingest:
            X = normalize_rows(X)
        return X

    def _prep_labels(self, labels, n: int) -> np.ndarray:
        """Validate labels WITHOUT side effects; callers bump the counter
        via :meth:`_commit_labels` only once the whole call will succeed."""
        if labels is None:
            labels = np.arange(self._next_label, self._next_label + n,
                               dtype=np.int32)
        labels = np.atleast_1d(np.asarray(labels, np.int32))
        if labels.shape != (n,):
            raise ValueError(f"expected {n} labels, got shape {labels.shape}")
        if np.any(labels < 0):
            raise ValueError("labels must be non-negative")
        if len(np.unique(labels)) != n:
            raise ValueError("duplicate labels within one call")
        return labels

    def _commit_labels(self, labels: np.ndarray) -> None:
        self._next_label = max(self._next_label, int(labels.max()) + 1)

    def _apply_tape(self, ops: np.ndarray, labels: np.ndarray,
                    X: np.ndarray) -> None:
        """Apply a mixed mutation tape through the wave-parallel executor.

        The whole tape goes down in ONE call — the executor dedupes
        duplicate labels (last-write-wins), applies deletes in one
        vectorized pass, and splits inserts/replaces into pow2-bucketed
        conflict-free waves itself, so the old host-side ``_MAX_TAPE``
        chunk loop is gone from the hot path. Strategies with a custom
        ``repair_fn`` can't ride the batched repair sweep; they keep the
        sequential scan in pow2 chunks (the parity path).
        """
        if len(ops) == 0:
            return
        if get_strategy(self.strategy).repair_fn is None:
            self._index = apply_update_batch_jit(
                self.params, self._index, ops, labels, X, self.strategy,
                execution="wave")
            return
        for lo in range(0, len(ops), _MAX_TAPE):
            o = ops[lo:lo + _MAX_TAPE]
            l = labels[lo:lo + _MAX_TAPE]
            x = X[lo:lo + _MAX_TAPE]
            b = _pow2_at_least(len(o))
            if b > len(o):                       # pad to the pow2 bucket
                o = np.concatenate([o, np.full(b - len(o), OP_NOP, np.int32)])
                l = np.concatenate([l, np.full(b - len(l), -1, np.int32)])
                x = np.concatenate([x, np.zeros((b - len(x), self.dim),
                                                np.float32)])
            self._index = apply_update_batch_jit(
                self.params, self._index, jnp.asarray(o), jnp.asarray(l),
                jnp.asarray(x), self.strategy, execution="sequential")

    def _maybe_maintain(self, n_ops: int) -> None:
        """Policy-gated online maintenance behind the mutation calls.

        With ``maintenance=MaintenancePolicy(...)`` the facade consults
        :func:`~repro.core.maintenance.index_health` every
        ``policy.check_every`` applied ops and runs the due passes
        (consolidation, then repair) in place — the caller just sees
        deleted slots turn back into free capacity.
        """
        if self.maintenance is None:
            return
        self._ops_since_maintenance += n_ops
        if self._ops_since_maintenance < self.maintenance.check_every:
            return
        self._ops_since_maintenance = 0
        self._index, _ = run_maintenance(self.params, self._index,
                                         self.maintenance)

    # -- writes -------------------------------------------------------------

    def add_items(self, X, labels=None) -> np.ndarray:
        """Insert new points; auto-grows past capacity. Returns the labels.

        ``labels`` defaults to an auto-incrementing counter. Labels must be
        fresh — use :meth:`replace_items` to overwrite an existing label
        (delete + replaced_update).
        """
        X = self._prep_vectors(X)
        n = X.shape[0]
        if n == 0:
            return np.empty((0,), np.int32)
        labels = self._prep_labels(labels, n)

        idx_labels = np.asarray(self._index.labels)
        alloc = np.asarray(self._index.levels) >= 0
        clash = np.intersect1d(labels, idx_labels[alloc])
        if clash.size:
            raise ValueError(
                f"labels already present: {clash[:8].tolist()}"
                f"{'...' if clash.size > 8 else ''} — use replace_items()")

        used = self._used_slots()
        if used + n > self.capacity:
            self.grow(used + n)

        if used == 0:
            # bulk path: one fori_loop build program instead of n tape steps
            self._index = _build(
                self.params, jnp.asarray(X, self._index.vectors.dtype),
                jnp.asarray(labels), seed=self._seed,
                capacity=self.capacity)
        else:
            self._apply_tape(np.full(n, OP_INSERT, np.int32), labels, X)
        self._commit_labels(labels)
        self._maybe_maintain(n)
        return labels

    def mark_deleted(self, labels) -> None:
        """markDelete: flag points; they stay traversable until replaced
        (or until maintenance consolidates them away)."""
        labels = np.atleast_1d(np.asarray(labels, np.int32))
        self._apply_tape(np.full(len(labels), OP_DELETE, np.int32), labels,
                         np.zeros((len(labels), self.dim), np.float32))
        self._maybe_maintain(len(labels))

    def replace_items(self, X, labels) -> np.ndarray:
        """replaced_update (paper Alg. 2+3): each point reuses a deleted slot
        with strategy-driven neighbourhood repair, falling back to a fresh
        insert when no deleted slot exists. Auto-grows if the fallback would
        run out of free slots.

        Upsert semantics: a label that is already present (live OR pending
        deletion) is overwritten — its old slot is marked deleted and
        un-labelled first, so every label maps to at most one allocated
        slot."""
        X = self._prep_vectors(X)
        n = X.shape[0]
        if n == 0:
            return np.empty((0,), np.int32)
        labels = self._prep_labels(labels, n)

        idx_labels = np.asarray(self._index.labels)
        alloc = np.asarray(self._index.levels) >= 0
        clash = alloc & np.isin(idx_labels, labels)
        if clash.any():
            slots = jnp.asarray(np.nonzero(clash)[0])
            self._index = dataclasses.replace(
                self._index,
                labels=self._index.labels.at[slots].set(-1),
                deleted=self._index.deleted.at[slots].set(True))

        free = self.capacity - self._used_slots()
        fallback_inserts = max(0, n - self.deleted_count)
        if fallback_inserts > free:
            self.grow(self._used_slots() + fallback_inserts)
        self._apply_tape(np.full(n, OP_REPLACE, np.int32), labels, X)
        self._commit_labels(labels)
        self._maybe_maintain(n)
        return labels

    # -- capacity -----------------------------------------------------------

    def grow(self, min_capacity: int | None = None) -> int:
        """Repack into the next pow2 capacity ≥ ``min_capacity`` (default:
        double). Slot ids, the graph, and all labels are preserved; jitted
        programs recompile once per doubling. Returns the new capacity."""
        target = 2 * self.capacity if min_capacity is None else min_capacity
        new_cap = max(_pow2_at_least(target), self.capacity)
        self._index = resize_index(self._index, new_cap)
        return self.capacity

    def compact(self, capacity: int | None = None) -> int:
        """Full blocking rebuild over live points only
        (:func:`~repro.core.maintenance.rebuild_index`).

        The graph is reconstructed (fresh build — deleted points no longer
        pollute neighbourhoods and accumulated topology damage is erased),
        the capacity defaults to the current one and may be shrunk as long
        as the live set fits. Returns the new capacity. For routine online
        reclamation prefer :meth:`consolidate` (or an automatic
        ``maintenance=`` policy) — it repairs only the affected
        neighbourhoods at a fraction of the cost."""
        self._index = rebuild_index(self.params, self._index,
                                    capacity=capacity, seed=self._seed)
        return self.capacity

    # -- maintenance --------------------------------------------------------

    def health(self) -> IndexHealth:
        """The :class:`~repro.core.maintenance.IndexHealth` report: live /
        deleted / unreachable counts, deleted fraction, in-degree
        histogram. ``health().asdict()`` gives plain python scalars."""
        return index_health(self._index)

    def consolidate(self) -> int:
        """Batched delete consolidation
        (:func:`~repro.core.maintenance.consolidate_deletes`): repair every
        neighbourhood that points into the mark-deleted set in one
        vectorized pass, then reclaim the deleted slots as free capacity —
        no rebuild, no epoch of downtime. Returns the number of slots
        reclaimed."""
        reclaimed = self.deleted_count
        self._index = consolidate_deletes(self.params, self._index)
        return reclaimed

    def repair_unreachable(self, max_passes: int = 3) -> int:
        """Re-link unreachable live points
        (:func:`~repro.core.maintenance.repair_unreachable`), re-checking
        between sweeps, until the paper's Definition-1 count hits zero or
        ``max_passes`` is exhausted. Returns the remaining Definition-1
        count (0 on success)."""
        for _ in range(max_passes):
            if int(jnp.sum(indegree_unreachable(self._index))) == 0:
                return 0
            self._index = _repair_unreachable(self.params, self._index)
        return int(jnp.sum(indegree_unreachable(self._index)))

    # -- reads --------------------------------------------------------------

    def _filter_to_slot_mask(self, filter) -> np.ndarray:
        idx_labels = np.asarray(self._index.labels)
        live = (np.asarray(self._index.levels) >= 0) \
            & ~np.asarray(self._index.deleted)
        if callable(filter):
            allow = np.zeros(self.capacity, bool)
            lv = np.nonzero(live)[0]
            allow[lv] = [bool(filter(int(l))) for l in idx_labels[lv]]
        else:
            allowed = np.atleast_1d(np.asarray(filter)).astype(np.int64)
            allow = live & np.isin(idx_labels, allowed)
        return allow

    def knn_query(self, Q, k: int = 10, ef: int | None = None,
                  filter=None, mode: str = "auto"
                  ) -> tuple[np.ndarray, np.ndarray]:
        """Batched k-NN: ``Q[b, d] -> (labels[b, k], dists[b, k])``.

        ``mode`` picks the execution tier (see docs/QUERY_PLANNER.md):
        ``"auto"`` (default) lets the planner route the batch — HNSW beam
        search normally, the exact Pallas scan tier when the index is
        small, churn-heavy (high mark-deleted fraction), or the filter is
        very selective; ``"graph"`` / ``"exact"`` force a tier.
        ``mode="exact"`` is recall-exact by construction (numpy brute-force
        parity) at linear cost in capacity.

        ``filter`` restricts results to a label predicate — an array of
        allowed labels or a ``label -> bool`` callable. On the graph tier
        it is evaluated INSIDE the beam search (disallowed points are
        traversed for connectivity but never occupy result slots), so
        predicate recall doesn't decay the way post-filtering k results
        would; on the exact tier it masks slots inside the streaming top-k
        reduction. Distances are in the index's metric (squared L2 for
        ``l2``, ``1 - <q, x>`` for ``ip``/``cosine``); missing results pad
        with label -1 / dist inf.
        """
        Q = self._prep_vectors(Q)
        ef = max(ef if ef is not None else self.params.ef_search, k)
        allow = None
        if filter is not None:
            mask = self._filter_to_slot_mask(filter)
            # selective predicates thin the result beam — widen ef by the
            # inverse selectivity (pow2, capped at 4x so the compiled-
            # program count stays bounded); highly selective filters should
            # still pass a larger ef explicitly (or let the planner route
            # them to the exact tier, which needs no boost)
            n_allowed = max(int(np.asarray(mask).sum()), 1)
            boost = _pow2_at_least(-(-self.capacity // n_allowed))
            ef = min(ef * min(boost, 4), _pow2_at_least(self.capacity))
            allow = jnp.asarray(mask)
        labels, _, dists, _ = plan_and_search(
            self.params, self._index, jnp.asarray(Q), k, ef, allow,
            mode=mode, config=self.planner)
        return np.asarray(labels), np.asarray(dists)

    def plan(self, filter=None) -> PlanDecision:
        """Explain what ``knn_query(mode="auto")`` would do right now:
        returns the :class:`~repro.core.planner.PlanDecision` (tier, the
        triggering heuristic, and the index statistics it saw)."""
        allow = None
        if filter is not None:
            allow = jnp.asarray(self._filter_to_slot_mask(filter))
        return choose_tier(index_stats(self._index, allow), self.planner)

    # -- persistence --------------------------------------------------------

    def save(self, path: str) -> None:
        """One-file npz snapshot: arrays + json meta (params, strategy)."""
        meta = {
            "version": _SAVE_VERSION,
            "params": dataclasses.asdict(self.params),
            "strategy": self.strategy,
            "next_label": int(self._next_label),
        }
        ix = self._index
        np.savez_compressed(
            path, meta=np.bytes_(json.dumps(meta).encode()),
            vectors=np.asarray(ix.vectors), labels=np.asarray(ix.labels),
            levels=np.asarray(ix.levels), neighbors=np.asarray(ix.neighbors),
            deleted=np.asarray(ix.deleted), entry=np.asarray(ix.entry),
            max_layer=np.asarray(ix.max_layer), count=np.asarray(ix.count),
            rng=np.asarray(ix.rng))

    @classmethod
    def load(cls, path: str) -> "VectorIndex":
        with np.load(path) as z:
            meta = json.loads(bytes(z["meta"]).decode())
            if meta.get("version") != _SAVE_VERSION:
                raise ValueError(f"unsupported save version "
                                 f"{meta.get('version')!r} in {path}")
            p = meta["params"]
            index = HNSWIndex(
                vectors=jnp.asarray(z["vectors"]),
                labels=jnp.asarray(z["labels"]),
                levels=jnp.asarray(z["levels"]),
                neighbors=jnp.asarray(z["neighbors"]),
                deleted=jnp.asarray(z["deleted"]),
                entry=jnp.asarray(z["entry"]),
                max_layer=jnp.asarray(z["max_layer"]),
                count=jnp.asarray(z["count"]),
                rng=jnp.asarray(z["rng"]))
        return cls(space=p["space"], dim=index.dim, M=p["M"], M0=p["M0"],
                   num_layers=p["num_layers"],
                   ef_construction=p["ef_construction"],
                   ef_search=p["ef_search"], alpha=p["alpha"],
                   strategy=meta["strategy"], _index=index,
                   _next_label=meta["next_label"])

    # -- serving ------------------------------------------------------------

    def serve(self, **engine_kwargs):
        """Hand the current index state to a :class:`ServingEngine`.

        The engine takes over: it owns an (immutable-snapshot) copy of the
        state and drains its own update queue; subsequent facade mutations
        do NOT flow into a live engine. The engine inherits this index's
        metric space (queries/updates are normalised for ``cosine``),
        update strategy unless overridden via ``variant=``, and query
        planner config unless overridden via ``planner=`` (``mode=`` pins
        an execution tier for all served buckets).
        """
        from repro.serving import ServingEngine
        engine_kwargs.setdefault("variant", self.strategy)
        engine_kwargs.setdefault("planner", self.planner)
        if engine_kwargs.get("mesh") is None:
            # sharded engines don't support maintenance passes yet — an
            # inherited policy must not make .serve(mesh=...) raise
            engine_kwargs.setdefault("maintenance", self.maintenance)
        return ServingEngine(self.params, self._index, **engine_kwargs)


def create(space: str = "l2", dim: int = 0, capacity: int = 1024,
           M: int = 8, ef_construction: int = 64,
           strategy: str = "mn_ru_gamma", **kwargs) -> VectorIndex:
    """One-call constructor (the ISSUE's ``create(space, dim, capacity, M,
    ef_construction, strategy)``); extra kwargs pass through to
    :class:`VectorIndex`."""
    return VectorIndex(space=space, dim=dim, capacity=capacity, M=M,
                       ef_construction=ef_construction, strategy=strategy,
                       **kwargs)
