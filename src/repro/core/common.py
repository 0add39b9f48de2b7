"""Shared numeric utilities for the tensorised HNSW core.

Everything here is pure jnp, shape-static, and jit/vmap friendly. Distance
kernels live in :mod:`~repro.core.metrics` (pluggable l2/ip/cosine spaces);
the squared-L2 names are re-exported here for the pre-metric-registry call
sites. The one host-side helper is :func:`span`, the program's named spans
in the profiler's trace.
"""
from __future__ import annotations

import contextlib
import contextvars
import gc

import jax
import jax.numpy as jnp

from .metrics import (dist_pairwise, dist_point, sqdist_pairwise,  # noqa: F401
                      sqdist_point)

# legacy alias (seed name for the L2 pairwise kernel)
pairwise_sqdist = sqdist_pairwise

INF = jnp.float32(jnp.inf)
INVALID = jnp.int32(-1)


# ---------------------------------------------------------------------------
# host spans
# ---------------------------------------------------------------------------

#: metadata every span opened in this context carries (the engine's pump)
_SPAN_TAGS: contextvars.ContextVar[dict] = contextvars.ContextVar(
    "repro_span_tags", default={})


def span(name: str, **meta):
    """A host span ``repro.<name>`` in the profiler's own trace, on the
    device trace's clock. ``meta`` (and any :func:`span_tags` in force)
    arrive as event stats, not in the name. With no profiler session a span
    costs about a microsecond, so spans are always on."""
    return jax.profiler.TraceAnnotation("repro." + name,
                                        **{**_SPAN_TAGS.get(), **meta})


@contextlib.contextmanager
def span_tags(**tags):
    """Add ``tags`` to the metadata of every span opened inside."""
    token = _SPAN_TAGS.set({**_SPAN_TAGS.get(), **tags})
    try:
        yield
    finally:
        _SPAN_TAGS.reset(token)


_gc_open: list = []      # the span of the collection in progress, if any


def _gc_span(phase: str, info: dict) -> None:
    if phase == "start":
        s = span("gc", gen=info["generation"])
        s.__enter__()
        _gc_open.append(s)
    elif _gc_open:
        _gc_open.pop().__exit__(None, None, None)


def trace_gc() -> None:
    """Mark each Python GC pause with a ``repro.gc`` span (process-wide,
    installed once)."""
    if _gc_span not in gc.callbacks:
        gc.callbacks.append(_gc_span)


def pow2_at_least(n: int) -> int:
    """Smallest power of two >= n (host-side; capacities are always pow2)."""
    n = max(int(n), 1)
    return 1 << (n - 1).bit_length()


def masked_gather_rows(X: jax.Array, ids: jax.Array) -> jax.Array:
    """Gather rows ``X[ids]`` treating negative ids as index 0 (caller masks)."""
    return X[jnp.clip(ids, 0, X.shape[0] - 1)]


def dedup_ids(ids: jax.Array, dists: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Invalidate duplicate ids in a flat candidate list.

    Keeps the first occurrence in id-sorted order; duplicates become
    ``(-1, INF)``. Invalid (-1) entries stay invalid.
    """
    # sorts carry their payload instead of argsort + gather / scatter,
    # which run element by element when vmapped on TPU
    s, perm = jax.lax.sort((ids, jnp.arange(ids.shape[0])), num_keys=1,
                           is_stable=True)
    dup_sorted = jnp.concatenate([jnp.array([False]), (s[1:] == s[:-1]) & (s[1:] >= 0)])
    # unsort the dup mask back to original positions
    _, dup = jax.lax.sort((perm, dup_sorted), num_keys=1)
    ids = jnp.where(dup, INVALID, ids)
    dists = jnp.where(dup, INF, dists)
    return ids, dists


def topk_by_distance(ids: jax.Array, dists: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """Sort candidates ascending by distance, return the first ``k``."""
    order = jnp.argsort(dists)
    return ids[order][:k], dists[order][:k]


def scatter_or(dst: jax.Array, idx: jax.Array, valid: jax.Array) -> jax.Array:
    """``dst[idx] |= valid`` for a bool array, dropping invalid indices."""
    safe = jnp.where(valid, idx, dst.shape[0])  # OOB index -> dropped
    return dst.at[safe].set(True, mode="drop")
