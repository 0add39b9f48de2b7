"""Update scheduler: accumulate mutations, drain them as fused op tapes.

Writers never touch the index directly — they enqueue :class:`UpdateOp`\\ s
(``delete`` / ``replace`` / ``insert``) and the engine's maintenance cycle
drains the whole backlog in one call. ``execution="wave"`` (default) hands
the drained tape to the wave-parallel batch executor
(:mod:`repro.core.batch_update`): duplicate labels collapse last-write-wins,
deletes apply in one vectorized pass, and the insert/replace set runs as
``O(waves)`` conflict-free vectorized waves. ``execution="sequential"``
keeps the original one-op-per-``lax.scan``-step tape for parity testing.
Tapes are bucketed to power-of-two lengths, and the compiled apply fn for
each ``(bucket, variant, execution, dtype)`` is memoized in a BOUNDED LRU
(``apply_cache_max``). On the sequential path each entry owns a private
``jax.jit`` wrapper, so evicting it actually frees the per-bucket compiled
scan; the wave path shares ONE entry per (variant, dtype) — its compiled
programs live in the executor's own pow2-width-bounded cache
(``core.batch_update``). The live entry count is exported as the
``apply_cache_size`` gauge.

The scheduler also owns the paper's tau counter (Fig. 4 upper layer): every
``tau`` replace/insert ops it rebuilds the unreachable-point backup index via
``core.backup.rebuild_backup`` — folded into the maintenance cycle, off the
query path, instead of blocking inside the write call like
``DualIndexManager`` does.
"""
from __future__ import annotations

import dataclasses
import time
from collections import OrderedDict, deque
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.backup import rebuild_backup
from repro.core.batch_update import (apply_plan, compile_tape,
                                     compiled_wave_programs)
from repro.core.common import span
from repro.core.index import HNSWIndex, HNSWParams
from repro.core.metrics import get_metric, normalize_rows
from repro.core.strategies import get_executor, get_strategy
from repro.core.update import (OP_DELETE, OP_INSERT, OP_NOP, OP_REPLACE,
                               apply_update_batch_sequential)

from .batcher import bucket_size, pow2_floor
from .metrics import MetricsRegistry

_KIND_TO_OP = {"delete": OP_DELETE, "replace": OP_REPLACE,
               "insert": OP_INSERT}


@dataclasses.dataclass(frozen=True)
class UpdateOp:
    """One queued mutation. ``vector`` is None for deletes."""
    kind: str                       # "delete" | "replace" | "insert"
    label: int
    vector: np.ndarray | None = None
    enqueued_t: float = dataclasses.field(
        default_factory=time.perf_counter, compare=False)

    def __post_init__(self):
        if self.kind not in _KIND_TO_OP:
            raise ValueError(f"unknown op kind {self.kind!r}")
        if self.kind != "delete" and self.vector is None:
            raise ValueError(f"{self.kind} op needs a vector")

    @property
    def opcode(self) -> int:
        return _KIND_TO_OP[self.kind]


class UpdateScheduler:
    """FIFO op queue + fused drain + tau-triggered backup rebuilds.

    ``apply_fn(index, ops[T], labels[T], X[T, d]) -> index`` can be injected
    (the engine's sharded path does) — the default is the jitted op-tape
    scan.
    """

    def __init__(self, params: HNSWParams, dim: int,
                 variant: str = "mn_ru_gamma", max_ops_per_drain: int = 128,
                 tau: int = 0, backup_params: HNSWParams | None = None,
                 backup_capacity: int = 0,
                 metrics: MetricsRegistry | None = None,
                 apply_fn: Callable | None = None,
                 execution: str = "wave", apply_cache_max: int = 16):
        if max_ops_per_drain < 1:
            raise ValueError("max_ops_per_drain must be >= 1")
        if apply_cache_max < 1:
            raise ValueError("apply_cache_max must be >= 1")
        # fail at construction, not minutes later at the first drain — one
        # registry lookup is THE validation (uniform error message)
        get_strategy(variant)
        get_executor(execution)
        self._normalize = get_metric(params.space).normalize_ingest
        self.params = params
        self.dim = dim
        self.variant = variant
        self.execution = execution
        self.max_ops_per_drain = pow2_floor(max_ops_per_drain)
        self.tau = tau
        self.backup_params = backup_params or params
        self.backup_capacity = backup_capacity
        self.metrics = metrics or MetricsRegistry()
        self._apply_fn = apply_fn or self._default_apply
        self.apply_cache_max = apply_cache_max
        self._apply_cache: OrderedDict[tuple, Callable] = OrderedDict()
        self.last_drain_waves = 0   # wave programs in the latest drain
        self._queue: deque[UpdateOp] = deque()
        self._ru_ops = 0          # replace/insert ops applied (tau counter)
        self._rebuilds = 0

    # -- submission ---------------------------------------------------------
    def submit(self, op: UpdateOp) -> None:
        self._queue.append(op)
        self.metrics.counter("updates_submitted").inc()

    def delete(self, label: int) -> None:
        self.submit(UpdateOp("delete", int(label)))

    def replace(self, vector, label: int) -> None:
        self.submit(UpdateOp("replace", int(label), self._ingest(vector)))

    def insert(self, vector, label: int) -> None:
        self.submit(UpdateOp("insert", int(label), self._ingest(vector)))

    def _ingest(self, vector) -> np.ndarray:
        """Metric-aware ingest: cosine unit-normalises before the core."""
        v = np.asarray(vector, np.float32)
        return normalize_rows(v) if self._normalize else v

    @property
    def backlog(self) -> int:
        return len(self._queue)

    @property
    def applied_ru_ops(self) -> int:
        return self._ru_ops

    @property
    def rebuilds(self) -> int:
        return self._rebuilds

    # -- drain --------------------------------------------------------------
    def _make_apply_fn(self) -> Callable:
        """Build the apply fn one cache entry owns.

        Wave path: compile the tape (dedup + wave split) and run the plan —
        the per-width wave programs live in the executor's own bounded
        pow2 cache. Sequential path: a FRESH ``jax.jit`` wrapper per cache
        entry, so evicting the entry really frees the per-bucket compiled
        scan instead of leaking it into a process-global cache."""
        wave = (self.execution == "wave"
                and get_strategy(self.variant).repair_fn is None)
        if wave:
            def fn(index, ops, labels, X):
                with span("tape") as s:
                    plan = compile_tape(ops, labels, X,
                                        built=int(index.count))
                    s.set_metadata(waves=plan.num_waves)
                self.last_drain_waves = plan.num_waves + (
                    1 if plan.num_deletes else 0)
                if plan.deduped:
                    self.metrics.counter("updates_deduped").inc(plan.deduped)
                n0 = compiled_wave_programs()
                index = apply_plan(self.params, index, plan, self.variant)
                self.metrics.counter("wave_programs_compiled").inc(
                    compiled_wave_programs() - n0)
                return index
            return fn
        jfn = jax.jit(apply_update_batch_sequential,
                      static_argnames=("params", "variant"))

        def fn(index, ops, labels, X):
            self.last_drain_waves = 0
            return jfn(self.params, index, jnp.asarray(ops),
                       jnp.asarray(labels), jnp.asarray(X), self.variant)
        return fn

    def _default_apply(self, index: HNSWIndex, ops, labels, X) -> HNSWIndex:
        """Memoized per-``(bucket, variant, execution, dtype)`` dispatch.

        The wave path's closure is tape-length-agnostic (the executor
        buckets wave widths itself), so it shares one entry across buckets
        instead of crowding out sequential entries that own compiled
        scans."""
        wave = (self.execution == "wave"
                and get_strategy(self.variant).repair_fn is None)
        key = (None if wave else len(ops), self.variant, self.execution,
               str(np.asarray(X).dtype))
        fn = self._apply_cache.get(key)
        if fn is None:
            while len(self._apply_cache) >= self.apply_cache_max:
                self._apply_cache.popitem(last=False)   # evict the coldest
            fn = self._apply_cache[key] = self._make_apply_fn()
        else:
            self._apply_cache.move_to_end(key)
        self.metrics.set_gauge("apply_cache_size", len(self._apply_cache))
        return fn(index, ops, labels, X)

    def drain(self, index: HNSWIndex,
              max_ops: int | None = None) -> tuple[HNSWIndex, int]:
        """Apply up to ``max_ops`` queued ops in FIFO order; returns
        ``(new_index, n_applied)``. The tape is padded with OP_NOP to the
        power-of-two bucket, so queue raggedness never recompiles."""
        limit = min(max_ops if max_ops is not None else self.max_ops_per_drain,
                    self.max_ops_per_drain)
        take = min(len(self._queue), limit)
        if take == 0:
            return index, 0
        with span("drain", ops=take):
            now = time.perf_counter()
            batch = [self._queue.popleft() for _ in range(take)]

            b = bucket_size(take, self.max_ops_per_drain)
            ops = np.full((b,), OP_NOP, np.int32)
            labels = np.full((b,), -1, np.int32)
            X = np.zeros((b, self.dim), np.float32)
            for i, op in enumerate(batch):
                ops[i] = op.opcode
                labels[i] = op.label
                if op.vector is not None:
                    X[i] = op.vector

            t0 = time.perf_counter()
            index = self._apply_fn(index, ops, labels, X)
            self.metrics.histogram("drain_latency_ms").observe(
                (time.perf_counter() - t0) * 1e3)
        self.metrics.counter("update_wait_us").inc(
            round(sum(now - op.enqueued_t for op in batch) * 1e6))
        self._ru_ops += sum(1 for op in batch if op.kind != "delete")
        self.metrics.counter("updates_applied").inc(take)
        self.metrics.counter("update_drains").inc()
        self.metrics.histogram("waves_per_drain").observe(
            self.last_drain_waves)
        return index, take

    # -- maintenance --------------------------------------------------------
    @property
    def rebuild_due(self) -> bool:
        return (self.tau > 0 and self.backup_capacity > 0
                and self._ru_ops // self.tau > self._rebuilds)

    def maybe_rebuild(self, index: HNSWIndex) -> HNSWIndex | None:
        """Tau-triggered backup rebuild over current unreachable points.

        Returns the fresh backup index, or None when not due. Called from
        the engine's maintenance cycle so it never blocks a write
        submission.
        """
        if not self.rebuild_due:
            return None
        t0 = time.perf_counter()
        with span("backup"):
            backup = rebuild_backup(self.backup_params, index,
                                    self.backup_capacity,
                                    jnp.uint32(self._rebuilds + 1))
            backup.vectors.block_until_ready()
        # one drain can cross several tau thresholds — catch the counter up
        # so idle pumps don't rebuild the identical index again
        self._rebuilds = self._ru_ops // self.tau
        self.metrics.counter("backup_rebuilds").inc()
        self.metrics.histogram("rebuild_latency_ms").observe(
            (time.perf_counter() - t0) * 1e3)
        return backup


from repro.core.strategies import variants_deprecation_shim as _shim

__getattr__ = _shim(__name__)
