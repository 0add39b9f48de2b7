"""Unreachability detection vs numpy brute force + crafted graphs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (HNSWParams, bfs_reachable, bfs_unreachable,
                        empty_index, indegree, indegree_unreachable)


def _craft(params, n, edges_by_layer, entry, levels):
    idx = empty_index(params, n, 4, seed=0)
    nbrs = np.full((params.num_layers, n, params.M0), -1, np.int32)
    for layer, edges in edges_by_layer.items():
        for src, tgts in edges.items():
            nbrs[layer, src, :len(tgts)] = tgts
    return idx.__class__(
        vectors=jnp.zeros((n, 4)), labels=jnp.arange(n, dtype=jnp.int32),
        levels=jnp.asarray(levels, jnp.int32), neighbors=jnp.asarray(nbrs),
        deleted=jnp.zeros(n, bool), entry=jnp.int32(entry),
        max_layer=jnp.int32(max(edges_by_layer) if edges_by_layer else 0),
        count=jnp.int32(n), rng=jnp.zeros(2, jnp.uint32))


def test_indegree_counts(small_params):
    # 0 -> 1 -> 2, 3 isolated (has out-edge to 0 so not "free")
    idx = _craft(small_params, 4, {0: {0: [1], 1: [2], 3: [0]}}, entry=0,
                 levels=[0, 0, 0, 0])
    deg = np.asarray(indegree(idx))
    assert deg.tolist() == [1, 1, 1, 0]
    unreach = np.asarray(indegree_unreachable(idx))
    assert unreach.tolist() == [False, False, False, True]


def test_bfs_vs_indegree_difference(small_params):
    """A cycle detached from the entry: indeg > 0 everywhere but BFS says
    unreachable — Definition 1 underestimates; BFS is the stronger check."""
    idx = _craft(small_params, 5,
                 {0: {0: [1], 1: [0], 2: [3], 3: [4], 4: [2]}},
                 entry=0, levels=[0] * 5)
    ind = np.asarray(indegree_unreachable(idx))
    assert not ind[2] and not ind[3] and not ind[4]     # Definition 1 misses
    bfs = np.asarray(bfs_unreachable(idx))
    assert bfs[2] and bfs[3] and bfs[4]                 # BFS catches
    assert not bfs[0] and not bfs[1]


def test_bfs_descends_layers(small_params):
    """Entry on layer 1 reaches layer-0-only nodes through the descent."""
    idx = _craft(small_params, 3,
                 {1: {0: [1]}, 0: {1: [2], 0: [1]}},
                 entry=0, levels=[1, 1, 0])
    reach = np.asarray(bfs_reachable(idx))
    assert reach.all()


def test_build_graph_fully_reachable(small_params, small_index):
    from repro.core import count_unreachable
    u_ind, u_bfs = count_unreachable(small_index)
    # fresh builds should have (near) zero unreachable points
    assert int(u_ind) <= 2
    assert int(u_bfs) <= 6


def _numpy_reach(levels, nbrs, entry):
    """Dense BFS fix-point per layer, top down (the reference)."""
    L, N, _ = nbrs.shape
    reached = np.zeros(N, bool)
    if entry >= 0:
        reached[entry] = True
    for layer in range(L - 1, -1, -1):
        while True:
            t = nbrs[layer][reached].reshape(-1)
            new = reached.copy()
            new[t[t >= 0]] = True
            if (new == reached).all():
                break
            reached = new
    return reached


@pytest.mark.parametrize("n,m0,rows", [(5, 2, 2), (300, 4, 16),
                                       (5000, 8, 64)])
def test_sweeps_match_dense_reference(monkeypatch, n, m0, rows):
    """The blocked sweeps (queue BFS, compacted in-degree) give the dense
    reference's answers on random graphs, with blocks smaller than the
    frontier so the queue wraps over many steps."""
    from repro.core import reach
    monkeypatch.setattr(reach, "SWEEP_ROWS", rows)
    rng = np.random.default_rng(n)
    L = 3
    levels = np.minimum(rng.geometric(0.5, n) - 1, L - 1).astype(np.int32)
    levels[rng.random(n) < 0.2] = -1
    nbrs = rng.integers(-1, n, (L, n, m0)).astype(np.int32)
    nbrs[rng.random((L, n, m0)) < 0.5] = -1
    entry = int(np.argmax(levels))
    idx = _craft(HNSWParams(M=m0, M0=m0, num_layers=L), n, {}, entry, levels)
    idx = idx.__class__(**{**idx.__dict__, "neighbors": jnp.asarray(nbrs)})
    # fresh jits: SWEEP_ROWS is read at trace time
    got = np.asarray(jax.jit(reach.bfs_reachable.__wrapped__)(idx))
    assert (got == _numpy_reach(levels, nbrs, entry)).all()
    src = (levels >= 0)[None, :, None] & (nbrs >= 0)
    want = np.bincount(nbrs[src], minlength=n)
    got = np.asarray(jax.jit(reach.indegree.__wrapped__)(idx))
    assert (got == want).all()
