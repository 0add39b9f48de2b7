"""Streaming fused distance + top-k Pallas kernel (metric-parameterized).

The retrieval hot path (1 query batch x 10^6 candidates) must never
materialise the full [Q, N] distance matrix (N=10^6 @ f32 = 4 MB *per query
row*). This kernel streams candidate tiles of Y through VMEM and maintains a
running [bq, k] top-k buffer in the output block — the same online-reduction
structure as FlashAttention's running softmax, applied to selection.

Distances dispatch statically on ``metric`` (one compiled program per form):

  * ``"l2"`` — squared L2 via the matmul identity ||q||^2 + ||y||^2 - 2 q.y
    (MXU contraction + VPU row norms);
  * ``"ip"`` — inner-product distance ``1 - q.y`` (cosine distance when the
    caller ingest-normalised, which is the registry's ``cosine`` contract).

A per-candidate validity mask rides along as an ``i32[1, N]`` input (1 =
candidate may appear in results). This is how the exact scan tier excludes
free slots, mark-deleted points, and filter-disallowed points *inside* the
running reduction: masked columns score ``+inf`` so they never displace a
live candidate, and unfilled output slots keep the ``(inf, -1)`` sentinel.

Grid/tiling: grid = (Q/bq, N/bn), candidate axis innermost so the output
block (the running buffer) stays VMEM-resident across the sweep. Per step
the kernel sees ``q[bq, d]``, ``y[bn, d]``, ``mask[1, bn]`` blocks. The
top-k merge is k rounds of masked min-extraction over the [bq, k] buffer
and the [bq, bn] tile — pure VPU elementwise/reduce ops (no gather, sort,
scan or lane concatenation), so it lowers cleanly to
Mosaic. Padding contract: Q and N must divide their blocks exactly (the
``ops.topk_dist`` wrapper pads and passes ``n_real``; padded candidate
columns are masked by the global-id bound). Interpret-mode fallback: pass
``interpret=True`` (the wrapper auto-selects it off-TPU) to run the same
kernel through the Pallas interpreter — numerics identical, tiling ignored.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_INF = float("inf")
_METRIC_FORMS = ("l2", "ip")


def _merge_topk(buf_v, buf_i, tile_v, tile_i, k):
    """k rounds of masked min-extraction over the running buffer and a tile.

    ``buf_v/buf_i`` [bq, k] and ``tile_v/tile_i`` [bq, bn] -> ([bq,k],[bq,k]).
    Each round takes the row minimum and, among the entries that hit it,
    the one with the smallest id: finite entries carry distinct real ids
    and the buffer's ids all precede the tile's, so this is the same
    lowest-column tie order as an argmin over the concatenated row. The
    two halves are reduced separately and the output is assembled with
    iota masks, so the kernel needs no lane-axis concatenation or scan.

    An extraction that only finds ``inf`` (fewer than k eligible candidates
    so far) emits the ``(inf, -1)`` sentinel — never a real id — so masked
    or already-extracted columns can't leak into unfilled output slots.
    """
    big = jnp.iinfo(jnp.int32).max
    col = jax.lax.broadcasted_iota(jnp.int32, buf_v.shape, 1)
    out_v = jnp.full(buf_v.shape, _INF, jnp.float32)
    out_i = jnp.full(buf_i.shape, -1, jnp.int32)
    for r in range(k):
        m = jnp.minimum(jnp.min(buf_v, axis=1, keepdims=True),
                        jnp.min(tile_v, axis=1, keepdims=True))     # [bq, 1]
        sel = jnp.minimum(
            jnp.min(jnp.where(buf_v == m, buf_i, big), axis=1, keepdims=True),
            jnp.min(jnp.where(tile_v == m, tile_i, big), axis=1,
                    keepdims=True))
        finite = m < _INF
        out_v = jnp.where(col == r, m, out_v)
        out_i = jnp.where(col == r, jnp.where(finite, sel, -1), out_i)
        buf_v = jnp.where(finite & (buf_i == sel), _INF, buf_v)
        tile_v = jnp.where(finite & (tile_i == sel), _INF, tile_v)
    return out_v, out_i


def _topk_dist_kernel(q_ref, y_ref, m_ref, od_ref, oi_ref, *, k, bn, n_real,
                      metric):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        od_ref[...] = jnp.full_like(od_ref, _INF)
        oi_ref[...] = jnp.full_like(oi_ref, -1)

    q = q_ref[...].astype(jnp.float32)                              # [bq, d]
    y = y_ref[...].astype(jnp.float32)                              # [bn, d]
    # full f32 contraction: the TPU's default precision rounds f32 operands
    # to bf16, and the exact tier is held to an f32 brute-force reference
    # (how far a default-precision pass strays from it is not measured)
    qy = jax.lax.dot_general(
        q, y, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)
    if metric == "l2":
        qq = jnp.sum(q * q, axis=1, keepdims=True)
        yy = jnp.sum(y * y, axis=1, keepdims=True)
        d = jnp.maximum(qq + yy.T - 2.0 * qy, 0.0)                  # [bq, bn]
    else:                                                           # "ip"
        d = 1.0 - qy

    gid = j * bn + jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)  # global ids
    ok = (gid < n_real) & (m_ref[...] > 0)       # [1, bn] mask broadcasts
    d = jnp.where(ok, d, _INF)                   # padding + masked-out slots

    nv, ni = _merge_topk(od_ref[...], oi_ref[...], d, gid, k)
    od_ref[...] = nv
    oi_ref[...] = ni


@functools.partial(jax.jit, static_argnames=("k", "bq", "bn", "interpret",
                                             "n_real", "metric"))
def topk_dist_pallas(Q: jax.Array, Y: jax.Array, mask: jax.Array, *, k: int,
                     n_real: int, metric: str = "l2",
                     bq: int = 8, bn: int = 512,
                     interpret: bool = False):
    """``(dists[q,k], ids[q,k])`` of the k nearest *unmasked* Y rows.

    Block-spec tiling: grid (Q/bq, N/bn), candidate axis innermost; the
    ``[bq, k]`` running top-k output blocks stay VMEM-resident across the
    candidate sweep, with ``q[bq, d]`` / ``y[bn, d]`` / ``mask[1, bn]``
    input blocks per step. Padding contract: Q and N must divide ``bq`` /
    ``bn`` exactly — use :func:`repro.kernels.topk_dist.ops.topk_dist` for
    the padding wrapper (padded candidates are excluded via the ``n_real``
    bound). ``mask`` is ``i32[1, N]`` (nonzero = eligible); rows with fewer
    than k eligible candidates pad with ``(inf, -1)``. ``interpret=True``
    runs the same kernel through the Pallas interpreter (the off-TPU
    fallback the wrapper auto-selects).
    """
    if metric not in _METRIC_FORMS:
        raise ValueError(f"unsupported kernel metric form {metric!r}; "
                         f"expected one of {_METRIC_FORMS}")
    nq, d = Q.shape
    N, _ = Y.shape
    grid = (nq // bq, N // bn)
    kern = functools.partial(_topk_dist_kernel, k=k, bn=bn, n_real=n_real,
                             metric=metric)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=(
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((nq, k), jnp.float32),
            jax.ShapeDtypeStruct((nq, k), jnp.int32),
        ),
        interpret=interpret,
    )(Q, Y, mask)
