"""Drive the serving engine's main path once on a TPU, at deployment capacity.

Deployment: the shapes of the NeurIPS'23 Big-ANN streaming track, MSTuring
clustered runbook: 100-d f32 vectors under L2, with deletes and inserts
interleaved with searches. The vectors are synthetic clustered Gaussians
made from ``--seed`` (``repro.data.clustered_vectors``); nothing is
downloaded. Every answer is checked against the numpy brute-force
reference (``repro.data.exact_knn``) over the live set of the epoch that
served it.

    python chip_smoke.py              # one chip
    python chip_smoke.py --sharded    # the sharded engine, all local chips

One chip, in one process on ``jax.devices()[0]``:

1. device check: exits non-zero unless JAX's first device is a TPU;
2. build: ``repro.api.create(...).add_items`` over ``--n`` vectors into
   ``--capacity`` slots (2^20). The default ``--n`` is 262,144, the
   deployment's 1,000,000 cut by halves (capacity is not): on one v5e
   chip a cold 1M run did not finish within 1150 s, and at 524,288 the
   build alone took 559 s (242 s of it compiling); then one timed
   ``health()`` reachability sweep;
3. serve with churn: ``.serve()`` with dualSearch (``tau`` /
   ``backup_capacity``) and a ``MaintenancePolicy`` at the engine's own
   cadence (consulted after every pump that wrote; the backup rebuilt
   every ``--churn`` replaces); each round queues ``--churn`` deletes +
   ``--churn`` replaces and ``--queries`` single queries, then pumps
   until both queues are empty. Graph-tier recall@10 must reach 0.90;
4. exact tier: ``knn_query(mode="exact")`` on the churned index must give
   the reference's answers (up to distance ties), and its compiled program
   must hold the Pallas kernel (``tpu_custom_call``);
5. maintenance: ``--tail-deletes`` more deletes, then ``consolidate()``
   and ``repair_unreachable()``; the Definition-1 unreachable count must
   be 0 and graph recall must still hold.

``--sharded`` runs only the sharded path: ``build_sharded`` over one shard
per chip (16,384 points each by default, so each shard goes through the
wave build), ``shard_index``, per-device memory, ``ServingEngine(mesh=...)``,
a few churn rounds and recall against the reference.

Every result line names what it measured; the last line of stdout is one
JSON object with the device. Any failed check exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

K = 10
DIM = 100
GRAPH_RECALL_MIN = 0.90
#: index parameters of the deployment
INDEX = dict(M=16, M0=32, num_layers=4, ef_construction=128)
BACKUP = 1024               # backup-index slots (dualSearch)
DRAIN = 512                 # update ops per pump


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def require_tpu():
    """The first device, which must be a TPU: nothing runs on the CPU."""
    import jax
    dev = jax.devices()[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())}", flush=True)
    check(dev.platform == "tpu", f"no TPU: JAX's first device is "
                                 f"{dev.platform!r}")
    return dev


class CompileClock:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax
        self.secs = 0.0
        self.count = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            self.count += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self):
        return self.secs, self.count

    def since(self, mark) -> str:
        return (f"compile_s={self.secs - mark[0]:.3f} "
                f"compiles={self.count - mark[1]}")


class LiveSet:
    """Host mirror of the live labels; label ``i`` is row ``i`` of ``X``."""

    def __init__(self, X: np.ndarray, n: int):
        self.X = X
        self.live = np.zeros(X.shape[0], bool)
        self.live[:n] = True

    def ground_truth(self, Q: np.ndarray) -> np.ndarray:
        from repro.data import exact_knn
        rows = np.nonzero(self.live)[0]
        return rows[exact_knn(self.X[rows], Q, K)]

    def sqdist(self, Q: np.ndarray, labels: np.ndarray) -> np.ndarray:
        """Squared L2 in float64 from each query to its labels."""
        V = self.X[np.clip(labels, 0, None)].astype(np.float64)
        d = ((V - Q[:, None, :].astype(np.float64)) ** 2).sum(-1)
        return np.where(labels >= 0, d, np.inf)


def recall(found: np.ndarray, gt: np.ndarray) -> float:
    return float(np.mean([len(set(f.tolist()) & set(g.tolist())) / K
                          for f, g in zip(found, gt)]))


def same_as_reference(live: LiveSet, Q, found, gt, tol=1e-4) -> bool:
    """Exact answers up to ties: the sorted true distances of what was
    found equal those of the reference's neighbours."""
    a = np.sort(live.sqdist(Q, found), axis=1)
    b = np.sort(live.sqdist(Q, gt), axis=1)
    return bool(np.all(np.abs(a - b) <= tol * np.maximum(1.0, b)))


def pump_until_empty(engine) -> list[float]:
    """Pump until both queues are empty; returns each pump's ms (each
    ends with the published index on the device)."""
    import jax
    times = []
    while True:
        t0 = time.perf_counter()
        engine.pump()
        jax.block_until_ready(engine.snapshot().index)
        times.append((time.perf_counter() - t0) * 1e3)
        if engine.update_backlog == 0 and engine.query_backlog == 0:
            return times


def queue_churn(engine, live: LiveSet, rng, next_label: int, churn: int):
    """Queue ``churn`` deletes of live labels and ``churn`` replaces that
    bring new labels in, interleaved so each drain holds both."""
    dels = rng.choice(np.nonzero(live.live)[0], size=churn, replace=False)
    news = np.arange(next_label, next_label + churn)
    for d, n in zip(dels, news):
        engine.delete(int(d))
        engine.update(live.X[n], int(n))
    return dels, news


def run_one_chip(args, clock: CompileClock) -> None:
    import jax
    import jax.numpy as jnp
    from repro import api
    from repro.core.batch_update import compile_tape
    from repro.core.planner import exact_scan
    from repro.core.update import OP_INSERT
    from repro.data import clustered_vectors

    n, churn, nq, rounds = args.n, args.churn, args.queries, args.rounds
    total = n + rounds * churn + (rounds + 3) * nq
    X = clustered_vectors(total, DIM, seed=args.seed)
    Q_all = X[n + rounds * churn:]
    X = X[:n + rounds * churn]
    live = LiveSet(X, n)
    rng = np.random.default_rng(args.seed)

    # --- build --------------------------------------------------------------
    vi = api.create(space="l2", dim=DIM, capacity=args.capacity,
                    ef_search=args.ef,
                    seed=args.seed, **INDEX)
    waves = compile_tape(np.full(n, OP_INSERT, np.int32),
                         np.arange(n, dtype=np.int32), X[:n],
                         built=0).num_waves
    mark = clock.mark()
    t0 = time.perf_counter()
    vi.add_items(X[:n], np.arange(n))
    jax.block_until_ready(vi.index)
    print(f"build: n={n} capacity={vi.capacity} waves={waves} "
          f"build_s={time.perf_counter() - t0:.3f} {clock.since(mark)}",
          flush=True)

    # --- one reachability sweep ---------------------------------------------
    mark = clock.mark()
    t0 = time.perf_counter()
    h = jax.block_until_ready(vi.health())
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    h = jax.block_until_ready(vi.health())
    warm_ms = (time.perf_counter() - t0) * 1e3
    print(f"health: first_s={first_s:.3f} warm_ms={warm_ms:.3f} "
          f"unreachable_def1={int(h.unreachable_def1)} "
          f"unreachable_bfs={int(h.unreachable_bfs)} {clock.since(mark)}",
          flush=True)

    # --- serve with churn ---------------------------------------------------
    # the engine's own cadence: the policy is consulted after every pump
    # that wrote, and the backup is rebuilt after every ``churn`` replaces
    engine = vi.serve(k=K, ef=args.ef, tau=churn,
                      backup_capacity=BACKUP, max_batch=64,
                      max_ops_per_drain=DRAIN,
                      maintenance=api.MaintenancePolicy())
    del vi                   # the engine owns the index from here on
    next_label = n
    for r in range(rounds + 1):
        Q = Q_all[r * nq:(r + 1) * nq]
        if r < rounds:
            dels, news = queue_churn(engine, live, rng, next_label, churn)
            next_label += churn
        tickets = [engine.search(q) for q in Q]
        gt = live.ground_truth(Q)     # queries see the pre-round epoch
        mark = clock.mark()
        t0 = time.perf_counter()
        pump_ms = pump_until_empty(engine)
        wall = time.perf_counter() - t0
        found = np.stack([t.result()[0] for t in tickets])
        check(len({t.epoch for t in tickets}) == 1,
              "one flush served several epochs")
        rec = recall(found, gt)
        if r < rounds:
            live.live[dels] = False
            live.live[news] = True
        m = engine.metrics
        print(f"serve round {r}: ops={2 * churn if r < rounds else 0} "
              f"queries={nq} pumps={len(pump_ms)} "
              f"pump_ms={[round(t, 3) for t in pump_ms]} "
              f"round_s={wall:.3f} epoch={engine.epoch} "
              f"graph_recall@{K}={rec:.4f} "
              f"tier_graph={m.counter('tier_graph_batches').value} "
              f"tier_exact={m.counter('tier_exact_batches').value} "
              f"backup_rebuilds={m.counter('backup_rebuilds').value} "
              f"repair_passes="
              f"{m.counter('maintenance_repair_passes').value} "
              f"{clock.since(mark)}", flush=True)
        check(rec >= GRAPH_RECALL_MIN,
              f"graph recall@{K} {rec:.4f} < {GRAPH_RECALL_MIN} "
              f"(round {r}, ef={args.ef})")
    check(engine.metrics.counter("tier_graph_batches").value > 0,
          "the graph tier never served")

    # --- exact tier ---------------------------------------------------------
    vi = api.VectorIndex(space="l2", dim=DIM, ef_search=args.ef,
                         _index=engine.snapshot().index, **INDEX)
    del engine
    Q = Q_all[(rounds + 1) * nq:(rounds + 2) * nq]
    gt = live.ground_truth(Q)
    mark = clock.mark()
    t0 = time.perf_counter()
    found, _ = vi.knn_query(Q, k=K, mode="exact")
    exact_s = time.perf_counter() - t0
    hlo = exact_scan.lower(vi.params, vi.index, jnp.asarray(Q), K) \
        .compile().as_text()
    rec = recall(found, gt)
    exact_ok = same_as_reference(live, Q, found, gt)
    print(f"exact tier: queries={nq} exact_recall@{K}={rec:.4f} "
          f"matches_reference={exact_ok} "
          f"tpu_custom_call={'tpu_custom_call' in hlo} "
          f"first_call_s={exact_s:.3f} {clock.since(mark)}", flush=True)
    check(exact_ok, "exact tier differs from the numpy reference")
    check("tpu_custom_call" in hlo,
          "the exact tier's program holds no Pallas TPU kernel")

    # --- maintenance --------------------------------------------------------
    tail = rng.choice(np.nonzero(live.live)[0], size=args.tail_deletes,
                      replace=False)
    mark = clock.mark()
    t0 = time.perf_counter()
    vi.mark_deleted(tail)
    live.live[tail] = False
    reclaimed = vi.consolidate()
    jax.block_until_ready(vi.index)
    t1 = time.perf_counter()
    def1 = vi.repair_unreachable()
    jax.block_until_ready(vi.index)
    t2 = time.perf_counter()
    Q = Q_all[(rounds + 2) * nq:]
    gt = live.ground_truth(Q)
    found, _ = vi.knn_query(Q, k=K, mode="graph")
    rec = recall(found, gt)
    print(f"maintenance: tail_deletes={args.tail_deletes} "
          f"reclaimed={reclaimed} consolidate_s={t1 - t0:.3f} "
          f"repair_s={t2 - t1:.3f} unreachable_def1={def1} "
          f"live={vi.count} graph_recall@{K}={rec:.4f} "
          f"{clock.since(mark)}", flush=True)
    check(reclaimed == args.tail_deletes,
          f"reclaimed {reclaimed} of {args.tail_deletes} deleted slots")
    check(def1 == 0, f"Definition-1 unreachable count {def1} after repair")
    check(vi.count == int(live.live.sum()),
          f"index holds {vi.count} live points, reference "
          f"{int(live.live.sum())}")
    check(rec >= GRAPH_RECALL_MIN,
          f"graph recall@{K} {rec:.4f} < {GRAPH_RECALL_MIN} after "
          "maintenance")


def run_sharded(args, clock: CompileClock) -> None:
    import jax
    from repro.core.distributed import build_sharded, shard_index
    from repro.core.index import HNSWParams
    from repro.data import clustered_vectors
    from repro.serving import ServingEngine

    nshards = len(jax.devices())
    mesh = jax.make_mesh((nshards,), ("data",))
    params = HNSWParams(ef_search=args.ef, **INDEX)
    n, churn, nq, rounds = args.n, args.churn, args.queries, args.rounds
    X = clustered_vectors(n + rounds * churn + (rounds + 1) * nq, DIM,
                          seed=args.seed)
    Q_all = X[n + rounds * churn:]
    X = X[:n + rounds * churn]
    live = LiveSet(X, n)
    rng = np.random.default_rng(args.seed)

    mark = clock.mark()
    t0 = time.perf_counter()
    stacked = build_sharded(params, X[:n], nshards=nshards,
                            capacity=args.shard_capacity, seed=args.seed)
    stacked = shard_index(stacked, mesh, "data")
    jax.block_until_ready(stacked)
    print(f"sharded build: n={n} shards={nshards} "
          f"capacity_per_shard={args.shard_capacity} "
          f"build_s={time.perf_counter() - t0:.3f} {clock.since(mark)}",
          flush=True)
    for d in jax.devices():
        s = d.memory_stats() or {}
        print(f"memory {d}: bytes_in_use={s.get('bytes_in_use')} "
              f"peak_bytes_in_use={s.get('peak_bytes_in_use')} "
              f"bytes_limit={s.get('bytes_limit')}", flush=True)
    shard_dev = {d.id for d in stacked.vectors.sharding.device_set}
    check(len(shard_dev) == nshards,
          f"the index sits on {len(shard_dev)} of {nshards} devices")

    engine = ServingEngine(params, stacked, k=K, ef=args.ef, mesh=mesh,
                           max_batch=64, max_ops_per_drain=DRAIN)
    del stacked              # the engine owns the index from here on
    next_label = n
    for r in range(rounds + 1):
        Q = Q_all[r * nq:(r + 1) * nq]
        if r < rounds:
            dels, news = queue_churn(engine, live, rng, next_label, churn)
            next_label += churn
        tickets = [engine.search(q) for q in Q]
        gt = live.ground_truth(Q)
        mark = clock.mark()
        t0 = time.perf_counter()
        pump_ms = pump_until_empty(engine)
        wall = time.perf_counter() - t0
        found = np.stack([t.result()[0] for t in tickets])
        rec = recall(found, gt)
        if r < rounds:
            live.live[dels] = False
            live.live[news] = True
        print(f"sharded round {r}: ops={2 * churn if r < rounds else 0} "
              f"queries={nq} pumps={len(pump_ms)} "
              f"pump_ms={[round(t, 3) for t in pump_ms]} "
              f"round_s={wall:.3f} epoch={engine.epoch} "
              f"graph_recall@{K}={rec:.4f} {clock.since(mark)}", flush=True)
        check(rec >= GRAPH_RECALL_MIN,
              f"sharded recall@{K} {rec:.4f} < {GRAPH_RECALL_MIN} "
              f"(round {r})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--sharded", action="store_true",
                    help="run only the sharded engine, one shard per chip")
    ap.add_argument("--n", type=int, default=None,
                    help="vectors loaded before serving (default 262,144;"
                         " --sharded: 16,384 per shard)")
    ap.add_argument("--rounds", type=int, default=3,
                    help="churn rounds (one more query round follows)")
    ap.add_argument("--churn", type=int, default=None,
                    help="deletes and as many replaces per round "
                         "(default 1024; --sharded: 64)")
    ap.add_argument("--queries", type=int, default=256,
                    help="single queries per round")
    ap.add_argument("--tail-deletes", type=int, default=8192,
                    help="deletes before consolidation")
    ap.add_argument("--ef", type=int, default=128,
                    help="ef_search (at 64, graph recall@10 of the "
                         "262,144-point build on one TPU v5e was 0.8719)")
    ap.add_argument("--capacity", type=int, default=1 << 20,
                    help="index slots (one chip)")
    ap.add_argument("--shard-capacity", type=int, default=1 << 20,
                    help="slots per shard (--sharded)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    dev = require_tpu()
    import jax
    if args.n is None:
        args.n = 16_384 * len(jax.devices()) if args.sharded else 262_144
    if args.churn is None:
        args.churn = 64 if args.sharded else 1024
    cache_dir = enable_compile_cache()
    clock = CompileClock()
    print(f"compile cache: {cache_dir}", flush=True)
    t0 = time.perf_counter()
    (run_sharded if args.sharded else run_one_chip)(args, clock)
    print(f"total_s={time.perf_counter() - t0:.3f} "
          f"compile_s={clock.secs:.3f} compiles={clock.count} "
          f"cache_hits={clock.hits} cache_misses={clock.misses}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
