"""Online maintenance subsystem: consolidation, repair, health, policy."""
import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro import api
from repro.core import (MaintenancePolicy, consolidate_deletes,
                        count_unreachable, index_health, run_maintenance)
from repro.core.maintenance import HIST_SPLITS
from repro.data import clustered_vectors


def _tree_equal(a, b):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert jnp.array_equal(la, lb)


def _brute_recall(X, live, Q, k, lab, space):
    """recall@k of ``lab`` vs numpy brute force over the live rows of X."""
    Xl, Ql = X[live], Q
    if space == "cosine":
        Xl = Xl / (np.linalg.norm(Xl, axis=1, keepdims=True) + 1e-12)
        Ql = Q / (np.linalg.norm(Q, axis=1, keepdims=True) + 1e-12)
    if space == "l2":
        D = ((Ql[:, None, :] - Xl[None, :, :]) ** 2).sum(-1)
    else:
        D = 1.0 - Ql @ Xl.T
    gt = live[np.argsort(D, axis=1)[:, :k]]
    return float(np.mean([len(set(lab[i]) & set(gt[i])) / k
                          for i in range(len(Q))]))


def _orphan(vi, n_orphans):
    """Strip every in-edge of the first ``n_orphans`` live slots."""
    ix = vi.index
    live = np.asarray((ix.levels >= 0) & ~ix.deleted)
    slots = np.nonzero(live)[0]
    slots = slots[slots != int(ix.entry)][:n_orphans]
    nb = ix.neighbors
    for s in slots:
        nb = jnp.where(nb == int(s), -1, nb)
    vi._index = dataclasses.replace(ix, neighbors=nb)
    return ix.labels[jnp.asarray(slots)]


# ---------------------------------------------------------------------------
# consolidation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("space", ["l2", "ip", "cosine"])
def test_consolidate_recall_parity_all_spaces(space):
    n, dim, k = 320, 16, 10
    X = clustered_vectors(n, dim, seed=4)
    vi = api.create(space=space, dim=dim, capacity=n)
    vi.add_items(X)
    rng = np.random.default_rng(0)
    dels = rng.choice(n, n // 2, replace=False).astype(np.int32)
    vi.mark_deleted(dels)
    live = np.setdiff1d(np.arange(n), dels)
    Q = clustered_vectors(24, dim, seed=5)

    reclaimed = vi.consolidate()
    assert reclaimed == len(dels)
    assert vi.deleted_count == 0
    assert vi._used_slots() == len(live)       # slots actually freed

    lab, _ = vi.knn_query(Q, k=k, mode="graph")
    assert not (set(lab.ravel().tolist()) & set(dels.tolist()))
    rec = _brute_recall(X, live, Q, k, lab, space)

    # parity oracle: a fresh build over the same live set
    vi_fresh = api.create(space=space, dim=dim, capacity=n)
    vi_fresh.add_items(X[live], live.astype(np.int32))
    lab_f, _ = vi_fresh.knn_query(Q, k=k, mode="graph")
    rec_fresh = _brute_recall(X, live, Q, k, lab_f, space)
    assert rec >= rec_fresh - 0.05, (rec, rec_fresh)


def test_consolidate_frees_capacity_for_inserts():
    n, dim = 128, 8
    X = clustered_vectors(n, dim, seed=1)
    vi = api.create(space="l2", dim=dim, capacity=n)
    vi.add_items(X)
    vi.mark_deleted(np.arange(0, n, 2).astype(np.int32))
    cap = vi.capacity
    vi.consolidate()
    # the freed slots absorb fresh inserts without growing
    vi.add_items(clustered_vectors(n // 2, dim, seed=2))
    assert vi.capacity == cap
    assert vi.count == n


def test_consolidate_idempotent_and_noop_when_clean():
    n, dim = 200, 8
    vi = api.create(space="l2", dim=dim, capacity=n)
    vi.add_items(clustered_vectors(n, dim, seed=3))
    clean = vi.index
    _tree_equal(consolidate_deletes(vi.params, clean), clean)

    vi.mark_deleted(np.arange(40).astype(np.int32))
    once = consolidate_deletes(vi.params, vi.index)
    twice = consolidate_deletes(vi.params, once)
    _tree_equal(once, twice)


def test_consolidate_everything_empties_index():
    n, dim = 64, 8
    vi = api.create(space="l2", dim=dim, capacity=n)
    vi.add_items(clustered_vectors(n, dim, seed=6))
    vi.mark_deleted(np.arange(n).astype(np.int32))
    vi.consolidate()
    h = index_health(vi.index)
    assert int(h.allocated) == 0 and int(h.max_layer) == -1
    assert int(vi.index.entry) == -1
    # and the index is still usable: a fresh add starts it over
    vi.add_items(clustered_vectors(5, dim, seed=7))
    assert vi.count == 5


# ---------------------------------------------------------------------------
# unreachable repair
# ---------------------------------------------------------------------------

def test_repair_unreachable_drives_def1_to_zero():
    n, dim = 300, 16
    X = clustered_vectors(n, dim, seed=8)
    vi = api.create(space="l2", dim=dim, capacity=n)
    vi.add_items(X)
    orphaned = np.asarray(_orphan(vi, 6))
    def1, _ = count_unreachable(vi.index)
    assert int(def1) >= 6

    left = vi.repair_unreachable()
    assert left == 0
    def1, _ = count_unreachable(vi.index)
    assert int(def1) == 0
    # the repaired points are findable by graph search again
    rows = np.asarray(vi.index.labels).tolist()
    q = X[[rows.index(int(l)) for l in orphaned]]
    lab, _ = vi.knn_query(q, k=1, mode="graph")
    assert set(lab[:, 0].tolist()) == set(int(l) for l in orphaned)


def test_repair_noop_on_healthy_index(small_params, small_data):
    from repro.core import build, repair_unreachable
    index = build(small_params, jnp.asarray(small_data[:200]))
    def1, _ = count_unreachable(index)
    assert int(def1) == 0
    _tree_equal(repair_unreachable(small_params, index), index)


# ---------------------------------------------------------------------------
# health report
# ---------------------------------------------------------------------------

def test_health_report_fields():
    n, dim = 256, 8
    vi = api.create(space="l2", dim=dim, capacity=n)
    vi.add_items(clustered_vectors(n, dim, seed=9))
    vi.mark_deleted(np.arange(64).astype(np.int32))
    h = vi.health()
    assert int(h.capacity) == vi.capacity
    assert int(h.allocated) == n
    assert int(h.live) == n - 64
    assert int(h.deleted) == 64
    assert h.deleted_frac == pytest.approx(64 / n)
    assert int(h.indegree_hist.sum()) == int(h.live)   # live points binned
    assert h.indegree_hist.shape == (len(HIST_SPLITS) + 1,)
    d = h.asdict()
    assert d["live"] == n - 64 and isinstance(d["indegree_hist"], list)


def test_health_def1_equals_hist_bin_zero_minus_entry():
    n, dim = 200, 8
    vi = api.create(space="l2", dim=dim, capacity=n)
    vi.add_items(clustered_vectors(n, dim, seed=10))
    _orphan(vi, 4)
    h = vi.health()
    # Definition 1 = live, zero in-edges, not the entry point
    assert int(h.unreachable_def1) >= 4
    assert int(h.unreachable_def1) <= int(h.indegree_hist[0])


# ---------------------------------------------------------------------------
# policy
# ---------------------------------------------------------------------------

def test_policy_validation():
    with pytest.raises(ValueError):
        MaintenancePolicy(deleted_frac=0.0)
    with pytest.raises(ValueError):
        MaintenancePolicy(check_every=0)


def test_policy_autoruns_in_facade():
    n, dim = 200, 8
    vi = api.create(space="l2", dim=dim, capacity=n,
                    maintenance=MaintenancePolicy(deleted_frac=0.3,
                                                  min_deleted=8,
                                                  check_every=1))
    vi.add_items(clustered_vectors(n, dim, seed=11))
    vi.mark_deleted(np.arange(100).astype(np.int32))
    assert vi.deleted_count == 0          # consolidated behind the call
    assert vi.count == n - 100


def test_run_maintenance_below_threshold_is_noop(small_params):
    vi = api.create(space="l2", dim=8, capacity=64)
    vi.add_items(clustered_vectors(64, 8, seed=12))
    vi.mark_deleted(np.arange(4).astype(np.int32))
    policy = MaintenancePolicy(deleted_frac=0.5, min_deleted=32)
    ix, report = run_maintenance(vi.params, vi.index, policy)
    assert not report["consolidated"] and report["repair_passes"] == 0
    _tree_equal(ix, vi.index)


def test_engine_maintenance_swaps_epoch_and_invalidates_stats():
    n, dim = 192, 8
    X = clustered_vectors(n, dim, seed=13)
    vi = api.create(space="l2", dim=dim, capacity=n,
                    maintenance=MaintenancePolicy(deleted_frac=0.3,
                                                  min_deleted=8,
                                                  check_every=1))
    vi.add_items(X)
    eng = vi.serve(k=3, max_ops_per_drain=256)
    for l in range(100):
        eng.delete(l)
    st = eng.pump()
    assert st.maintenance_ran and st.epoch == 1
    snap = eng.snapshot()
    assert int(jnp.sum(snap.index.deleted & (snap.index.levels >= 0))) == 0
    assert eng.batcher._stats_cache is None        # planner must re-consult
    assert eng.metrics.counter("maintenance_consolidations").value == 1
    # served results post-maintenance exclude the deleted labels
    t = eng.search(X[150])
    eng.pump()
    assert all(l >= 100 for l in t.result()[0].tolist())
    # idle pumps stop consulting once the index is clean + unchanged: the
    # pump right after maintenance re-sweeps (the passes rewrote the
    # index), every later idle pump skips the health sweep entirely
    eng.pump()
    assert not eng._dirty_since_consult
    st_idle = eng.pump()
    assert not st_idle.maintenance_ran and not eng._dirty_since_consult


def test_sharded_serve_drops_inherited_policy():
    """.serve(mesh=...) must not raise when the facade holds a policy."""
    import jax as _jax
    from jax.sharding import Mesh
    vi = api.create(space="l2", dim=8, capacity=64,
                    maintenance=MaintenancePolicy())
    vi.add_items(clustered_vectors(32, 8, seed=21))
    mesh = Mesh(np.array(_jax.devices()[:1]), ("data",))
    eng = vi.serve(k=3, mesh=mesh)
    assert eng.maintenance is None


def test_engine_sharded_maintenance_rejected():
    import jax as _jax
    from jax.sharding import Mesh
    from repro.core import HNSWParams
    from repro.core.distributed import build_sharded
    from repro.serving import ServingEngine
    p = HNSWParams(M=4, M0=8, num_layers=2, ef_construction=16, ef_search=16)
    stacked = build_sharded(p, jnp.asarray(clustered_vectors(32, 8, seed=0)),
                            nshards=1, capacity=32)
    mesh = Mesh(np.array(_jax.devices()[:1]), ("data",))
    with pytest.raises(ValueError, match="maintenance"):
        ServingEngine(p, stacked, mesh=mesh,
                      maintenance=MaintenancePolicy())


def test_engine_sharded_track_unreachable_gauge():
    """Satellite: sharded engines now sum per-shard unreachable counts."""
    import jax as _jax
    from jax.sharding import Mesh
    from repro.core import HNSWParams
    from repro.core.distributed import build_sharded
    from repro.serving import ServingEngine
    X = clustered_vectors(64, 8, seed=14)
    p = HNSWParams(M=4, M0=8, num_layers=3, ef_construction=32, ef_search=32)
    stacked = build_sharded(p, jnp.asarray(X), nshards=1, capacity=96)
    mesh = Mesh(np.array(_jax.devices()[:1]), ("data",))
    eng = ServingEngine(p, stacked, k=3, mesh=mesh, track_unreachable=True)
    eng.delete(3)
    eng.insert(X[10] + 0.01, 200)
    t = eng.search(X[5])
    eng.pump()
    t.result()
    gauges = eng.stats()["gauges"]
    assert "unreachable_indegree" in gauges and "unreachable_bfs" in gauges
    assert gauges["unreachable_indegree"] >= 0


# ---------------------------------------------------------------------------
# interleaved churn property
# ---------------------------------------------------------------------------

def test_interleaved_update_consolidate_never_loses_live_labels():
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    dim = 8
    base = clustered_vectors(64, dim, seed=15)

    @settings(max_examples=10, deadline=None)
    @given(st.lists(st.sampled_from(["delete", "replace", "consolidate",
                                     "repair"]),
                    min_size=1, max_size=12))
    def run(ops):
        vi = api.create(space="l2", dim=dim, capacity=64)
        vi.add_items(base)
        live = set(range(64))
        nxt = 64
        rng = np.random.default_rng(17)
        for op in ops:
            if op == "delete" and len(live) > 8:
                dels = rng.choice(sorted(live), 4, replace=False)
                vi.mark_deleted(dels.astype(np.int32))
                live -= set(int(d) for d in dels)
            elif op == "replace":
                news = list(range(nxt, nxt + 3))
                nxt += 3
                vi.replace_items(clustered_vectors(3, dim, seed=nxt), news)
                live |= set(news)
            elif op == "consolidate":
                vi.consolidate()
            else:
                vi.repair_unreachable(max_passes=2)
            ix = vi.index
            mask = np.asarray((ix.levels >= 0) & ~ix.deleted)
            got = set(np.asarray(ix.labels)[mask].tolist())
            assert got == live, (op, live - got, got - live)

    run()


def _backstop_graph():
    """Slot 6 is the one orphan; slot 4 is the farthest entry of owner 0's
    full row but has no other in-edge."""
    from repro.core.index import HNSWIndex, HNSWParams
    params = HNSWParams(M=4, M0=4, num_layers=1)
    rows = {0: [1, 2, 3, 4], 4: [5], 5: [1, 2, 3], 6: [0]}
    nbrs = np.full((1, 8, 4), -1, np.int32)
    for src, tgt in rows.items():
        nbrs[0, src, :len(tgt)] = tgt
    pos = np.array([0.0, 1.0, 2.0, 3.0, 10.0, 5.0, 0.5, 0.0], np.float32)
    idx = HNSWIndex(
        vectors=jnp.asarray(pos[:, None]),
        labels=jnp.arange(8, dtype=jnp.int32),
        levels=jnp.asarray([0] * 7 + [-1], jnp.int32),
        neighbors=jnp.asarray(nbrs), deleted=jnp.zeros(8, bool),
        entry=jnp.int32(0), max_layer=jnp.int32(0), count=jnp.int32(7),
        rng=jnp.zeros(2, jnp.uint32))
    return params, idx


def test_backstop_keeps_every_last_in_edge():
    """The orphan backstop links an orphan from its nearest out-neighbour
    and evicts the farthest entry that has another in-edge, never a
    point's last one: slot 4 is the farthest entry of owner 0's full row
    but has no other in-edge, so slot 3 is evicted instead. The in-degree
    it hands back is that of its output."""
    from repro.core.maintenance import _force_in_edges
    from repro.core.reach import indegree, indegree_unreachable
    params, idx = _backstop_graph()
    assert np.nonzero(np.asarray(indegree_unreachable(idx)))[0].tolist() \
        == [6]
    out, deg = _force_in_edges(params, idx)
    assert np.asarray(out.neighbors)[0, 0].tolist() == [1, 2, 6, 4]
    assert not np.asarray(indegree_unreachable(out)).any()
    assert np.array_equal(deg, indegree(out))


# ---------------------------------------------------------------------------
# one reachability sweep per consult
# ---------------------------------------------------------------------------

#: (seed, M, M0, points stripped of their in-edges): with M0 = 4 one pass
#: repairs them all; with M = M0 = 2 the backstop leaves orphans behind
ORPHANED = {"repaired_in_one_pass": (1, 2, 4, 24),
            "backstop_leaves_orphans": (0, 2, 2, 24)}


def _orphaned(case):
    seed, M, M0, n_orphans = ORPHANED[case]
    vi = api.create(space="l2", dim=4, capacity=64, M=M, M0=M0,
                    ef_construction=8, ef_search=8)
    vi.add_items(clustered_vectors(64, 4, seed=seed))
    _orphan(vi, n_orphans)
    return vi.params, vi.index


@pytest.mark.parametrize("case", ["backstop_graph", *ORPHANED])
def test_repair_from_health_mask_equals_its_own_sweep(case):
    """The mask health hands over is the union the repair used to sweep for
    itself; re-linking from it gives the same index, and the count the
    pass returns is the Definition-1 count of its output."""
    from repro.core.maintenance import _repair, repair_unreachable
    from repro.core.reach import bfs_unreachable, indegree_unreachable
    params, idx = _backstop_graph() if case == "backstop_graph" \
        else _orphaned(case)
    h = index_health(idx)
    assert int(h.unreachable_def1) > 0
    swept = indegree_unreachable(idx) | bfs_unreachable(idx)
    assert jnp.array_equal(h.repair_mask, swept)
    out, left = _repair(params, idx, h.repair_mask)
    _tree_equal(out, _repair(params, idx, swept)[0])
    _tree_equal(out, repair_unreachable(params, idx))
    assert int(left) == int(indegree_unreachable(out).sum())
    assert (int(left) > 0) == (case == "backstop_leaves_orphans")


def _old_consult(params, idx, policy):
    """The consult as it ran before the sweep was carried forward: a fresh
    Definition-1 sweep before and after every pass."""
    from repro.core.maintenance import repair_unreachable
    from repro.core.reach import indegree_unreachable
    passes, def1 = 0, int(indegree_unreachable(idx).sum())
    while passes < policy.repair_passes and def1 > policy.unreachable:
        idx = repair_unreachable(params, idx)
        passes += 1
        def1 = int(indegree_unreachable(idx).sum())
    return idx, passes, def1


def _count_sweeps(monkeypatch):
    """Count the eager sweeps the maintenance module dispatches, by name (a
    call traced inside a program is not one)."""
    from repro.core import maintenance
    calls = []
    for name in ("index_health", "indegree_unreachable"):
        def counted(index, _sweep=getattr(maintenance, name), _name=name):
            if not isinstance(index.neighbors, jax.core.Tracer):
                calls.append(_name)
            return _sweep(index)
        monkeypatch.setattr(maintenance, name, counted)
    return calls


@pytest.mark.parametrize("case", sorted(ORPHANED))
def test_run_maintenance_reuses_the_consults_mask(case, monkeypatch):
    params, idx = _orphaned(case)
    policy = MaintenancePolicy()
    h = index_health(idx).fetch()
    old, passes, def1 = _old_consult(params, idx, policy)
    sweeps = _count_sweeps(monkeypatch)
    new, report = run_maintenance(params, idx, policy, h)
    assert report["mask_reused"] == 1 and report["recheck_sweeps"] == 0
    # only a pass after the first sweeps, for its own mask
    assert sweeps == ["index_health"] * (passes - 1)
    assert (report["repair_passes"], report["unreachable_def1"]) \
        == (passes, def1)
    assert not report["consolidated"] and passes >= 1
    _tree_equal(new, old)


def test_run_maintenance_after_consolidation_sweeps_again(monkeypatch):
    """A consolidation changes the index: one health sweep of the result is
    the check before the first pass and that pass's mask; the consult's own
    mask is not used."""
    params, idx = _orphaned("repaired_in_one_pass")
    policy = MaintenancePolicy(deleted_frac=0.1, min_deleted=8)
    live = np.nonzero(np.asarray((idx.levels >= 0) & ~idx.deleted))[0]
    idx = dataclasses.replace(idx, deleted=idx.deleted.at[live[-16:]].set(
        True))
    h = index_health(idx).fetch()
    consolidated = consolidate_deletes(params, idx)
    assert not jnp.array_equal(h.repair_mask,
                               index_health(consolidated).repair_mask)
    old, passes, def1 = _old_consult(params, consolidated, policy)
    sweeps = _count_sweeps(monkeypatch)
    out, report = run_maintenance(params, idx, policy, h)
    assert report["consolidated"] and report["repair_passes"] == passes >= 1
    assert report["recheck_sweeps"] == 1 and report["mask_reused"] == 1
    assert sweeps == ["index_health"] * passes
    assert report["unreachable_def1"] == def1 \
        == int(count_unreachable(out)[0]) == 0
    _tree_equal(out, old)


def test_run_maintenance_repairs_through_repair_unreachable(monkeypatch):
    """Every pass goes through ``repair_unreachable``: with it a no-op the
    consult changes nothing and reports the orphans it left, without a
    sweep of its own."""
    from repro.core import maintenance
    params, idx = _orphaned("repaired_in_one_pass")
    h = index_health(idx).fetch()
    monkeypatch.setattr(maintenance, "repair_unreachable",
                        lambda params, index: index)
    sweeps = _count_sweeps(monkeypatch)
    out, report = run_maintenance(params, idx, MaintenancePolicy(), h)
    assert out is idx and not sweeps
    assert report["repair_passes"] == MaintenancePolicy().repair_passes
    assert report["unreachable_def1"] == int(h.unreachable_def1) > 0
    assert report["mask_reused"] == report["recheck_sweeps"] == 0


def test_engine_counts_reused_masks_and_recheck_sweeps():
    n, dim = 192, 8
    X = clustered_vectors(n + 4, dim, seed=13)
    vi = api.create(space="l2", dim=dim, capacity=256)
    vi.add_items(X[:n])
    _orphan(vi, 3)
    eng = vi.serve(k=3, maintenance=MaintenancePolicy())
    eng.insert(X[n], n)
    assert eng.pump().maintenance_ran
    c = eng.stats()["counters"]
    assert c["maintenance_mask_reused"] == 1
    assert c["maintenance_repair_passes"] >= 1
    assert c["maintenance_recheck_sweeps"] == 0
    assert eng.stats()["gauges"]["maintenance_unreachable_def1"] == 0
