"""The plain exact reference, and the lower-precision control.

Neither imports anything of raft_tpu. ``exact_knn`` is squared-L2 top-k
in float32 at HIGHEST precision, one row block at a time, merged across
blocks (``lax.top_k``). ``int8_knn`` is the same search with rows and
queries rounded to int8 under one symmetric scale: the control, the
nearest precision below the bfloat16 the configurations store, which the
comparison in :mod:`benchmark.correct` must reject.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


@functools.partial(jax.jit, static_argnames=("k",))
def _merge(d0, i0, d1, i1, *, k):
    d = jnp.concatenate([d0, d1], axis=1)
    i = jnp.concatenate([i0, i1], axis=1)
    neg, pos = lax.top_k(-d, k)
    return -neg, jnp.take_along_axis(i, pos, axis=1)


@functools.partial(jax.jit, static_argnames=("k", "size"))
def _exact_block(x, q, start, *, k, size):
    xb = lax.dynamic_slice_in_dim(x, start, size).astype(jnp.float32)
    dots = lax.dot_general(q, xb, (((1,), (1,)), ((), ())),
                           precision=lax.Precision.HIGHEST)
    d2 = (jnp.sum(q * q, axis=1)[:, None]
          + jnp.sum(xb * xb, axis=1)[None, :] - 2.0 * dots)
    neg, pos = lax.top_k(-d2, k)
    return -neg, pos + start


@functools.partial(jax.jit, static_argnames=("k", "size"))
def _int8_block(x, q8, scale, start, *, k, size):
    xb = lax.dynamic_slice_in_dim(x, start, size).astype(jnp.float32)
    x8 = jnp.clip(jnp.round(xb / scale), -127, 127).astype(jnp.int8)
    dots = lax.dot_general(q8, x8, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.int32)
    qn = jnp.sum(q8.astype(jnp.int32) ** 2, axis=1)
    xn = jnp.sum(x8.astype(jnp.int32) ** 2, axis=1)
    d2 = (qn[:, None] + xn[None, :] - 2 * dots).astype(jnp.float32)
    neg, pos = lax.top_k(-d2, k)
    return -neg * scale * scale, pos + start


def _blockwise(block_fn, n, k, block):
    best = None
    for start in range(0, n, block):
        part = block_fn(jnp.int32(start), min(block, n - start))
        best = part if best is None else _merge(*best, *part, k=k)
    return best


def exact_knn(x, q, k: int, block: int):
    """Exact squared-L2 top-k of ``q`` over the rows of ``x`` (float32,
    HIGHEST). Returns host arrays (dists (m, k), ids (m, k))."""
    q = jax.device_put(jnp.asarray(q, jnp.float32), x.device)
    d, i = _blockwise(lambda s, size: _exact_block(x, q, s, k=k, size=size),
                      x.shape[0], k, block)
    return np.asarray(d), np.asarray(i)


def int8_knn(x, q, k: int, block: int):
    """The control: exact top-k with rows and queries rounded to int8
    under one symmetric scale (max |x| / 127), distances in int32 and
    scaled back. Returns host arrays (dists (m, k), ids (m, k))."""
    scale = (jnp.max(jnp.abs(x)).astype(jnp.float32) / 127.0)
    q = jax.device_put(jnp.asarray(q, jnp.float32), x.device)
    q8 = jnp.clip(jnp.round(q / scale), -127, 127).astype(jnp.int8)
    d, i = _blockwise(
        lambda s, size: _int8_block(x, q8, scale, s, k=k, size=size),
        x.shape[0], k, block)
    return np.asarray(d), np.asarray(i)


@jax.jit
def _row_dists(x, q, ids):
    rows = x[jnp.clip(ids, 0, x.shape[0] - 1)].astype(jnp.float32)
    return jnp.sum((q[:, None, :] - rows) ** 2, axis=-1)


def true_dists(x, q, ids):
    """Exact squared-L2 distance of each query to each of its given row
    ids, in float32 by differences (ids out of range are clipped here;
    the comparison marks them apart). Returns a host array."""
    q = jax.device_put(jnp.asarray(q, jnp.float32), x.device)
    ids = jax.device_put(jnp.asarray(ids, jnp.int32), x.device)
    return np.asarray(_row_dists(x, q, ids))
