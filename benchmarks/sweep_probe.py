"""Time one reachability sweep (``index_health``) at deployment capacity.

The graph is synthetic and HNSW-shaped: ``--n`` allocated slots out of
``--capacity``, levels drawn as HNSW draws them (``M=16``), and each point
at layer ``l`` linked to ``M0`` (layer 0) or ``M`` random points at or
above ``l``. Beside the sweep it times the two dense steps that the
reachability code used to repeat: a scatter of every layer-0 edge into a
``bool[N]`` (one BFS level of a dense fix-point) and a scatter-add of every
layer-0 edge (one layer of the in-degree count), and prints how many BFS
levels a dense fix-point would take on this graph (from a host BFS).

    python benchmarks/sweep_probe.py [--n 262144] [--capacity 1048576]

Every line names the device it ran on. Exits non-zero off the TPU.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

M, M0, L = 16, 32, 4


def synthetic_graph(n: int, capacity: int, seed: int):
    """(levels i32[N], neighbors i32[L, N, M0]) on the host."""
    rng = np.random.default_rng(seed)
    levels = np.full(capacity, -1, np.int32)
    levels[:n] = np.minimum(
        np.floor(-np.log(rng.random(n)) / np.log(M)), L - 1).astype(np.int32)
    nbrs = np.full((L, capacity, M0), -1, np.int32)
    for layer in range(L):
        ids = np.nonzero(levels >= layer)[0]
        width = M0 if layer == 0 else M
        nbrs[layer, ids, :width] = ids[rng.integers(0, len(ids),
                                                    (len(ids), width))]
    return levels, nbrs


def dense_levels(levels, nbrs, entry: int) -> list[int]:
    """BFS levels per layer (top first) that a dense fix-point runs,
    counting the last step that finds nothing new."""
    reached = np.zeros(levels.shape[0], bool)
    reached[entry] = True
    out = []
    for layer in range(L - 1, -1, -1):
        steps = 0
        while True:
            steps += 1
            t = nbrs[layer][reached].reshape(-1)
            t = t[t >= 0]
            new = reached.copy()
            new[t] = True
            if (new == reached).all():
                break
            reached = new
        out.append(steps)
    return out


def timed(fn, *args, reps: int = 3) -> tuple[float, list[float]]:
    """(first-call s, warm ms per rep); every call ends on the device."""
    import jax
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    first = time.perf_counter() - t0
    warm = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        warm.append((time.perf_counter() - t0) * 1e3)
    return first, warm


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--n", type=int, default=262_144)
    ap.add_argument("--capacity", type=int, default=1 << 20)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from repro.core.index import HNSWIndex
    from repro.core.maintenance import index_health
    from repro.launch.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    where = (f"platform={dev.platform} kind={dev.device_kind} "
             f"count={len(jax.devices())}")
    print(f"device: {where}", flush=True)
    if dev.platform != "tpu":
        print("no TPU: JAX's first device is " + dev.platform,
              file=sys.stderr)
        return 1
    enable_compile_cache()

    N = args.capacity
    levels, nbrs = synthetic_graph(args.n, N, args.seed)
    entry = int(np.argmax(levels))
    index = HNSWIndex(
        vectors=jnp.zeros((N, 1), jnp.float32),
        labels=jnp.arange(N, dtype=jnp.int32),
        levels=jnp.asarray(levels), neighbors=jnp.asarray(nbrs),
        deleted=jnp.zeros((N,), bool), entry=jnp.int32(entry),
        max_layer=jnp.int32(levels.max()), count=jnp.int32(args.n),
        rng=jnp.zeros((2,), jnp.uint32))

    first, warm = timed(index_health, index)
    h = index_health(index)
    out = {"device": where, "n": args.n, "capacity": N,
           "index_health_first_s": round(first, 3),
           "index_health_warm_ms": [round(t, 3) for t in warm],
           "unreachable_def1": int(h.unreachable_def1),
           "unreachable_bfs": int(h.unreachable_bfs)}
    print(json.dumps(out), flush=True)

    flat = index.neighbors[0].reshape(-1)
    tgt = jnp.where(flat >= 0, flat, N)

    @jax.jit
    def dense_bfs_step(reached, tgt):
        return reached.at[tgt].set(True, mode="drop")

    @jax.jit
    def dense_indegree_layer(counts, tgt):
        return counts.at[tgt].add(1, mode="drop")

    r0 = jnp.zeros((N,), bool)
    c0 = jnp.zeros((N,), jnp.int32)
    _, step_ms = timed(dense_bfs_step, r0, tgt)
    _, add_ms = timed(dense_indegree_layer, c0, tgt)
    print(json.dumps({
        "device": where, "edges_per_dense_step": int(tgt.shape[0]),
        "dense_bfs_step_warm_ms": [round(t, 3) for t in step_ms],
        "dense_indegree_layer_warm_ms": [round(t, 3) for t in add_ms],
        "dense_bfs_levels_per_layer": dense_levels(levels, nbrs, entry)}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
