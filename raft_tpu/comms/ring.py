"""Ring-dataflow distributed algorithms — the library's analog of ring
attention / context parallelism applied to the *points* axis
(SURVEY.md §5 "ring-style exchange of query/index blocks over ICI for
out-of-HBM kNN"; the reference has no counterpart — its MNMG kNN
replicates queries and allgathers results, knn_brute_force_faiss.cuh:365).

Why a ring: with BOTH queries and index sharded, the allgather pattern
needs every device to hold all P index shards' results (P·m·k) and the
full query set. The ring keeps each device's working set at one query
shard + one index shard: each of P steps computes a fused local top-k
against the resident index shard, folds it into the running result, and
``ppermute``-rotates the index shard to the next neighbor — overlapping
compute with ICI transfer exactly like ring attention overlaps KV-block
rotation with attention compute.

Memory per device: O(n_q/P · k + n/P · d) instead of O(n_q · k · P).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from raft_tpu.comms.comms import Comms
from raft_tpu.distance.distance_type import resolve_metric
from raft_tpu.spatial.knn import _knn_single_part
from raft_tpu.spatial.selection import merge_topk

__all__ = ["ring_knn", "ring_pairwise_distance"]


def _shard_rows(comms: Comms, x):
    x = np.asarray(x)
    n = x.shape[0]
    sz = comms.size
    pad = (-n) % sz
    if pad:
        x = np.concatenate([x, np.zeros((pad,) + x.shape[1:], x.dtype)])
    sharding = NamedSharding(comms.mesh, P(comms.axis, *([None] * (x.ndim - 1))))
    return jax.device_put(x, sharding), n


def ring_knn(
    comms: Comms,
    index,
    queries,
    k: int,
    *,
    metric="l2_sqrt_expanded",
    p: float = 2.0,
    block_n: int = 4096,
) -> Tuple[jax.Array, jax.Array]:
    """Fully-sharded brute-force kNN: queries AND index row-sharded; index
    shards rotate around the ring; every device folds each visiting shard
    into its queries' running top-k.

    Returns (dists (m, k), ids (m, k)) row-sharded like the queries (global
    row ids).
    """
    metric = resolve_metric(metric)
    xs, n = _shard_rows(comms, index)
    qs, m = _shard_rows(comms, queries)
    sz = comms.size
    shard_rows = xs.shape[0] // sz
    ax = comms.device_comms()
    ring_next = [(i, (i + 1) % sz) for i in range(sz)]

    def body_fn(q_loc, x_loc):
        rank = ax.get_rank()

        def step(carry, s):
            rv, ri, blk, owner = carry
            d_loc, i_loc = _knn_single_part(
                q_loc, blk, k, metric, p, block_n, None
            )
            gidx = i_loc + owner * shard_rows
            d_loc = jnp.where(gidx < n, d_loc, jnp.inf)
            rv, ri = merge_topk(rv, ri, d_loc, gidx, select_min=True)
            # rotate: my shard goes to rank+1; I receive from rank-1,
            # whose shard id is owner-1 of mine
            blk = lax.ppermute(blk, ax.axis, ring_next)
            owner = (owner - 1) % sz
            return (rv, ri, blk, owner), None

        init = (
            jnp.full((q_loc.shape[0], k), jnp.inf, jnp.float32),
            jnp.zeros((q_loc.shape[0], k), jnp.int32),
            x_loc,
            rank,
        )
        (rv, ri, _, _), _ = lax.scan(step, init, jnp.arange(sz))
        return rv, ri

    sm = comms.shard_map(
        body_fn,
        in_specs=(P(comms.axis, None), P(comms.axis, None)),
        out_specs=(P(comms.axis, None), P(comms.axis, None)),
    )
    dists, ids = jax.jit(sm)(qs, xs)
    return dists[:m], ids[:m]


def ring_pairwise_distance(
    comms: Comms,
    x,
    y,
    *,
    metric="l2_sqrt_expanded",
    p: float = 2.0,
) -> jax.Array:
    """Distributed full distance matrix with both operands row-sharded:
    y-shards rotate around the ring; each device fills its (m/P, n) row
    block column-stripe by column-stripe (the 2D-blocked "tensor parallel"
    analog of the distance matrix, SURVEY.md §2 classification #4).

    Returns the (m, n) matrix row-sharded over the mesh.
    """
    metric = resolve_metric(metric)
    xs, m = _shard_rows(comms, x)
    ys, n = _shard_rows(comms, y)
    sz = comms.size
    y_shard = ys.shape[0] // sz
    ax = comms.device_comms()
    ring_next = [(i, (i + 1) % sz) for i in range(sz)]

    from raft_tpu.spatial.knn import _block_dist

    def body_fn(x_loc, y_loc):
        rank = ax.get_rank()
        mq = x_loc.shape[0]

        def step(carry, s):
            out, blk, owner = carry
            d = _block_dist(x_loc, blk, metric, p)       # (mq, y_shard)
            out = lax.dynamic_update_slice(
                out, d.astype(out.dtype), (0, owner * y_shard)
            )
            blk = lax.ppermute(blk, ax.axis, ring_next)
            owner = (owner - 1) % sz
            return (out, blk, owner), None

        init = (
            jnp.zeros((mq, sz * y_shard), jnp.float32),
            y_loc,
            rank,
        )
        (out, _, _), _ = lax.scan(step, init, jnp.arange(sz))
        return out

    sm = comms.shard_map(
        body_fn,
        in_specs=(P(comms.axis, None), P(comms.axis, None)),
        out_specs=P(comms.axis, None),
    )
    out = jax.jit(sm)(xs, ys)
    return out[:m, :n]
