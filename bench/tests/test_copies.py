"""Each piece copied into the benchmark agrees with its original on a small
input. The originals are loaded here only; the benchmark never imports
them."""
import importlib.util
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from bench import data, oracle
from bench.device import CompileClock
from bench.references import exact_l2

REPO = Path(__file__).resolve().parents[2]


def _chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke_original",
                                                  REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("n,d,k,seed",
                         [(500, 16, 8, 0), (3000, 100, 32, 2**33 + 7)])
def test_clustered_vectors_match_original(n, d, k, seed):
    from repro.data import clustered_vectors
    X, cluster, centres = data.clustered_vectors(n, d, k, seed=seed)
    assert np.array_equal(X, clustered_vectors(n, d, k, seed=seed))
    # the cluster ids are the ones the points were drawn around
    near = np.argmin(((X[:, None, :] - centres[None]) ** 2).sum(-1), axis=1)
    assert (near == cluster).mean() > 0.99


def test_brute_force_knn_matches_original():
    from repro.data import brute_force_knn
    X, _, _ = data.clustered_vectors(2000, 24, 8, seed=3)
    Q, _, _ = data.clustered_vectors(300, 24, 8, seed=4)
    assert np.array_equal(data.brute_force_knn(X, Q, 10),
                          brute_force_knn(X, Q, 10))


def test_reference_matches_exact_knn_over_live_rows():
    from repro.data import exact_knn
    X, _, _ = data.clustered_vectors(3000, 32, 8, seed=5)
    Q, _, _ = data.clustered_vectors(200, 32, 8, seed=6)
    live = np.random.default_rng(0).random(3000) < 0.7
    rows = np.nonzero(live)[0]
    want = rows[exact_knn(X[rows], Q, 10)]
    got = exact_l2.knn(X, live, Q, 10)
    dw = exact_l2.sqdist(Q, X[want])
    dg = exact_l2.sqdist(Q, X[got])
    assert np.allclose(np.sort(dg, 1), np.sort(dw, 1), rtol=1e-6)
    assert (got == want).mean() > 0.999


def test_recall_matches_chip_smoke():
    smoke = _chip_smoke()
    rng = np.random.default_rng(1)
    gt = np.stack([rng.permutation(40)[:10] for _ in range(30)])
    found = np.where(rng.random((30, 10)) < 0.3, gt + 100, gt)
    mine = np.mean([len(set(f) & set(g)) / 10 for f, g in zip(found, gt)])
    assert mine == pytest.approx(smoke.recall(found, gt))


def test_compile_clock_counts_like_chip_smoke():
    import jax
    smoke = _chip_smoke()
    theirs, mine = smoke.CompileClock(), CompileClock()
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
    assert mine.compiles == theirs.count >= 1
    assert mine.compile_s == pytest.approx(theirs.secs)


def test_definition1_matches_program_and_dense_reference():
    from repro.core import HNSWParams, empty_index, indegree_unreachable
    rng = np.random.default_rng(7)
    n, L, m0 = 400, 3, 4
    levels = np.minimum(rng.geometric(0.5, n) - 1, L - 1).astype(np.int32)
    levels[rng.random(n) < 0.2] = -1
    nbrs = rng.integers(-1, n, (L, n, m0)).astype(np.int32)
    nbrs[rng.random((L, n, m0)) < 0.85] = -1
    deleted = rng.random(n) < 0.1
    entry = int(np.argmax(levels))
    idx = empty_index(HNSWParams(M=m0, M0=m0, num_layers=L), n, 4, seed=0)
    idx = idx.__class__(**{**idx.__dict__, "levels": jnp.asarray(levels),
                           "neighbors": jnp.asarray(nbrs),
                           "deleted": jnp.asarray(deleted),
                           "entry": jnp.int32(entry)})
    want = int(np.asarray(indegree_unreachable(idx)).sum())
    assert want > 0
    assert oracle.definition1_count(levels, deleted, nbrs, entry) == want
