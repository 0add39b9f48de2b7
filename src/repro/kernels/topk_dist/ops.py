"""Jit wrapper for the streaming top-k kernel: padding, masks, dispatch."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .topk_dist import topk_dist_pallas
from .ref import topk_dist_ref


@functools.partial(jax.jit, static_argnames=("k", "bq", "bn", "interpret",
                                             "use_ref", "metric"))
def topk_dist(Q: jax.Array, Y: jax.Array, k: int, *, metric: str = "l2",
              mask: jax.Array | None = None, bq: int = 8,
              bn: int = 512, interpret: bool | None = None,
              use_ref: bool = False):
    """k nearest rows of ``Y[N, d]`` per query row of ``Q[q, d]``.

    Returns ``(dists[q, k], ids[q, k])`` sorted ascending, in the requested
    ``metric`` form (``"l2"`` squared L2, ``"ip"`` ``1 - <q, y>``; the
    registry's ``cosine`` space routes here as ``"ip"`` after ingest
    normalisation). ``mask`` (bool/int ``[N]``, nonzero = eligible)
    restricts results without restricting the streamed sweep — how the
    exact scan tier skips deleted / filtered-out slots. Rows with fewer
    than k eligible candidates pad with ``(inf, -1)``.

    Padding contract: pads Q/Y/mask freely to block multiples (Q to whole
    ``bq``-row blocks, whatever the batch size); padded candidates are
    masked inside the kernel via the real-N bound, padded query rows are
    sliced off the output. ``interpret=None`` auto-selects
    the Pallas interpreter off-TPU; ``use_ref=True`` routes to the jnp
    oracle (identical semantics, XLA-fused instead of hand-tiled).
    """
    if use_ref:
        return topk_dist_ref(Q, Y, k, metric=metric, mask=mask)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    nq, d = Q.shape
    N, _ = Y.shape
    if nq == 0:                              # empty batch: nothing to scan
        return (jnp.zeros((0, k), jnp.float32),
                jnp.full((0, k), -1, jnp.int32))
    # Q pads up to whole (bq, d) blocks: a block row count that is not a
    # multiple of 8 does not tile the TPU's sublanes
    bq_ = bq
    bn_ = min(bn, N)
    pad_q = (-nq) % bq_
    pad_n = (-N) % bn_
    Qp = jnp.pad(Q, ((0, pad_q), (0, 0)))
    Yp = jnp.pad(Y, ((0, pad_n), (0, 0))) if pad_n else Y
    if mask is None:
        mp = jnp.ones((1, N + pad_n), jnp.int32)
    else:
        mp = jnp.pad(mask.reshape(1, -1).astype(jnp.int32), ((0, 0),
                                                             (0, pad_n)))
    dists, ids = topk_dist_pallas(Qp, Yp, mp, k=k, n_real=N, metric=metric,
                                  bq=bq_, bn=bn_, interpret=interpret)
    return dists[:nq], ids[:nq]
