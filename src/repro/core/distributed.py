"""Shared-nothing sharded ANN index: shard_map search + routed updates.

Each device along the sharding axis owns ``N/shards`` vectors plus a private
HNSW sub-graph; label ownership is ``label % nshards``. A global query fans
out to all shards (queries are replicated), produces per-shard top-k, and a
single fused all_gather + merge yields the global top-k — one collective per
batch, not per query.

Updates are uniform SPMD: every shard executes the update op, non-owners
mask to a no-op (no host-side control flow divergence), which is what keeps
the program identical across 1000+ nodes.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from .common import INF, INVALID
from .index import HNSWIndex, HNSWParams, empty_index
from .hnsw import build, insert
from .search import knn_search
from .update import first_free_slot, mark_delete, replaced_update


def build_sharded(params: HNSWParams, vectors, labels=None, *, nshards: int,
                  seed: int = 0, capacity: int | None = None):
    """Build ``nshards`` sub-indexes (host-side), stacked on a leading axis.

    Labels are assigned round-robin (label % nshards == shard) so update
    routing is a pure function of the label. ``capacity`` is the PER-SHARD
    slot count (default: exactly full); oversize it to leave free slots for
    fresh inserts.
    """
    n, d = vectors.shape
    labels = jnp.arange(n, dtype=jnp.int32) if labels is None else labels
    per = -(-n // nshards)
    cap = capacity if capacity is not None else per
    if cap < per:
        raise ValueError(f"per-shard capacity {cap} < {per} needed for "
                         f"{n} vectors on {nshards} shards")
    stacked = []
    for s in range(nshards):
        sel = jnp.nonzero(labels % nshards == s, size=per, fill_value=-1)[0]
        ok = sel >= 0
        v = vectors[jnp.clip(sel, 0)]
        l = jnp.where(ok, labels[jnp.clip(sel, 0)], INVALID)
        # build over the valid prefix (round-robin => prefix-dense)
        count = int(ok.sum())
        idx = build(params, v[:count], l[:count], seed=seed + s, capacity=cap)
        stacked.append(idx)
    return jax.tree.map(lambda *xs: jnp.stack(xs), *stacked)


def _auto_axes(mesh: Mesh) -> Mesh:
    """``mesh`` with every axis typed ``Auto``.

    ``jax.make_mesh`` types its axes ``Explicit`` by default, which puts
    the sharding into each array's type: the shard-local conds
    of :func:`sharded_update` and plain indexing of a stacked index along
    the shard axis then fail to resolve. Each shard here is an
    independent sub-index, so nothing needs sharding in types.
    """
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def shard_index(stacked: HNSWIndex, mesh: Mesh, axis: str) -> HNSWIndex:
    """Place a stacked index so its leading (shard) dim maps to ``axis``."""
    sh = NamedSharding(_auto_axes(mesh), P(axis))
    return jax.tree.map(lambda x: jax.device_put(x, sh), stacked)


@partial(jax.jit, static_argnames=("params", "k", "mesh", "axis", "ef"))
def sharded_batch_knn(params: HNSWParams, stacked: HNSWIndex, Q: jax.Array,
                      k: int, mesh: Mesh, axis: str = "data",
                      ef: int | None = None):
    """Global top-k over a sharded index: local search + one all_gather merge.

    Q is replicated; returns ``(labels[b, k], dists[b, k])`` with global labels.
    """
    nshards = mesh.shape[axis]

    def local(idx_shard, Q):
        idx = jax.tree.map(lambda x: x[0], idx_shard)   # strip shard dim

        def one(q):
            lbl, _, dist = knn_search(params, idx, q, k, ef)
            return lbl, dist

        lbl, dist = jax.vmap(one)(Q)                    # [b, k] each
        # fuse per-shard results into one collective
        lbl_g = jax.lax.all_gather(lbl, axis)           # [S, b, k]
        dist_g = jax.lax.all_gather(dist, axis)
        lbl_g = jnp.moveaxis(lbl_g, 0, 1).reshape(Q.shape[0], nshards * k)
        dist_g = jnp.moveaxis(dist_g, 0, 1).reshape(Q.shape[0], nshards * k)
        dist_g = jnp.where(lbl_g < 0, INF, dist_g)
        order = jnp.argsort(dist_g, axis=1)
        top = jnp.take_along_axis(dist_g, order, 1)[:, :k]
        top_l = jnp.take_along_axis(lbl_g, order, 1)[:, :k]
        return top_l, top

    specs = jax.tree.map(lambda _: P(axis), stacked)
    fn = jax.shard_map(local, mesh=_auto_axes(mesh), in_specs=(specs, P()),
                       out_specs=(P(), P()), check_vma=False)
    return fn(stacked, Q)


@partial(jax.jit, static_argnames=("params", "mesh", "axis", "variant",
                                   "fresh_insert"))
def sharded_update(params: HNSWParams, stacked: HNSWIndex,
                   del_label: jax.Array, x: jax.Array, new_label: jax.Array,
                   mesh: Mesh, axis: str = "data",
                   variant: str = "mn_ru_gamma", fresh_insert: bool = False):
    """Route one delete+replace to the owning shard; others no-op (SPMD).

    A negative ``del_label`` / ``new_label`` disables that half of the op, so
    the serving layer can route pure deletes (``new_label=-1``) and pure
    inserts (``del_label=-1``) through the same compiled program.
    ``fresh_insert=True`` makes the new-label half a plain insert into the
    owner's first free slot instead of a replaced_update (never consumes a
    deleted slot).
    """
    nshards = mesh.shape[axis]

    def local(idx_shard, del_label, x, new_label):
        idx = jax.tree.map(lambda x: x[0], idx_shard)
        sid = jax.lax.axis_index(axis)
        own_del = (del_label >= 0) & ((del_label % nshards) == sid)
        own_new = (new_label >= 0) & ((new_label % nshards) == sid)

        idx = jax.lax.cond(own_del, lambda i: mark_delete(i, del_label),
                           lambda i: i, idx)

        if fresh_insert:
            def do_new(i):
                pid = first_free_slot(i)
                return jax.lax.cond(
                    pid >= 0,
                    lambda ix: insert(params, ix, x, jnp.clip(pid, 0),
                                      new_label),
                    lambda ix: ix, i)
        else:
            def do_new(i):
                return replaced_update(params, i, x, new_label, variant)
        idx = jax.lax.cond(own_new, do_new, lambda i: i, idx)
        return jax.tree.map(lambda a: a[None], idx)

    specs = jax.tree.map(lambda _: P(axis), stacked)
    fn = jax.shard_map(local, mesh=_auto_axes(mesh),
                       in_specs=(specs, P(), P(), P()),
                       out_specs=specs, check_vma=False)
    return fn(stacked, del_label, x, new_label)
