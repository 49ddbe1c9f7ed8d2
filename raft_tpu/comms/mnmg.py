"""Multi-node-multi-device algorithms — the OPMG pattern over a mesh.

Analog of the reference's MNMG consumers (SURVEY.md §2 parallelism kinds
#3): data pre-partitioned across workers, each runs the single-device
primitive on its shard, results combined with communicator collectives —
kNN via local top-k + allgather + ``knn_merge_parts``
(knn_brute_force_faiss.cuh:289-368 multi-partition search), k-means via
psum centroid allreduce (the NCCL-allreduce pattern cuML's MNMG kmeans
builds on these comms).

All functions take a :class:`Comms` whose mesh carries the data axis; they
run one ``shard_map`` so every collective rides ICI/DCN picked by XLA.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from raft_tpu.cluster.kmeans import KMeansOutput, KMeansParams, _update_centroids
from raft_tpu.comms.comms import Comms
from raft_tpu.distance.distance_type import resolve_metric
from raft_tpu.distance.fused_l2_nn import fused_l2_nn
from raft_tpu.spatial.knn import _knn_single_part
from raft_tpu.spatial.selection import select_k

__all__ = ["mnmg_knn", "mnmg_kmeans_fit"]


def _shard_rows(comms: Comms, x):
    """Place a host array row-sharded over the comms axis (pads to a
    multiple of the mesh size; returns (sharded, orig_rows))."""
    x = np.asarray(x)
    n = x.shape[0]
    sz = comms.size
    pad = (-n) % sz
    if pad:
        x = np.concatenate([x, np.zeros((pad,) + x.shape[1:], x.dtype)])
    sharding = NamedSharding(comms.mesh, P(comms.axis, *([None] * (x.ndim - 1))))
    return jax.device_put(x, sharding), n


def mnmg_knn(
    comms: Comms,
    index,
    queries,
    k: int,
    *,
    metric="l2_sqrt_expanded",
    p: float = 2.0,
    block_n: int = 4096,
) -> Tuple[jax.Array, jax.Array]:
    """Distributed brute-force kNN: the index is row-sharded across the mesh,
    queries are replicated; each device searches its shard, then an
    allgather + merge produces the global top-k on every device
    (reference: per-partition search on pool streams + ``knn_merge_parts``,
    knn_brute_force_faiss.cuh:289-368).

    Returns (distances (m, k), indices (m, k)) with global row ids.
    """
    metric = resolve_metric(metric)
    xs, n = _shard_rows(comms, index)
    queries = jnp.asarray(np.asarray(queries))
    shard_rows = xs.shape[0] // comms.size
    ax = comms.device_comms()

    def body(idx_shard, q):
        rank = ax.get_rank()
        d_loc, i_loc = _knn_single_part(
            q, idx_shard, k, metric, p, block_n, None
        )
        # padded tail rows of the last shard must not win the merge
        gidx = i_loc + rank * shard_rows
        d_loc = jnp.where(gidx < n, d_loc, jnp.inf)
        pd = ax.allgather(d_loc)     # (P, m, k): all_gather stacks ranks
        pi = ax.allgather(gidx)
        flat_d = pd.transpose(1, 0, 2).reshape(q.shape[0], -1)
        flat_i = pi.transpose(1, 0, 2).reshape(q.shape[0], -1)
        return select_k(flat_d, k, indices=flat_i)

    sm = comms.shard_map(
        body, in_specs=(P(comms.axis, None), P(None, None)),
        out_specs=(P(None, None), P(None, None)),
    )
    return jax.jit(sm)(xs, queries)


def mnmg_kmeans_fit(
    comms: Comms,
    x,
    params: Optional[KMeansParams] = None,
    **kw,
) -> KMeansOutput:
    """Distributed lloyd: rows sharded over the mesh; assignment is local
    (fused MXU distance+argmin per shard), the centroid update and residual
    are ``psum`` allreduces — the TPU version of MNMG kmeans over
    raft::comms (NCCL allreduce of per-worker centroid sums).

    Init: each rank contributes a deterministic local sample; the pooled
    (P·k, d) candidates are k-means++-seeded identically on every rank.

    Returns KMeansOutput with replicated centroids and row-sharded labels.
    """
    if params is None:
        params = KMeansParams(**kw)
    k = params.n_clusters
    xs, n = _shard_rows(comms, x)
    sz = comms.size
    shard_rows = xs.shape[0] // sz
    ax = comms.device_comms()

    def fit_local(x_loc):
        rank = ax.get_rank()
        rows = rank * shard_rows + jnp.arange(shard_rows)
        valid = rows < n

        # ---- init: distributed k-means++ over the FULL sharded dataset
        # (reference initializeCentroids runs over all rows; here each step
        # samples ∝ the global min-dist² by (a) allgathering per-rank mass,
        # (b) locating the owner rank on the global CDF, (c) inverse-CDF
        # sampling inside the owner shard, (d) masked-psum broadcast of the
        # chosen point — chooseNewCentroid:357 made rank-symmetric.)
        key = jax.random.PRNGKey(params.seed)
        d = x_loc.shape[1]

        def pick(i, d2):
            mass = jnp.where(valid, d2, 0.0)
            local_tot = jnp.sum(mass)
            tots = ax.allgather(local_tot)                    # (P,)
            cum = jnp.cumsum(tots)
            u = jax.random.uniform(jax.random.fold_in(key, i), ()) * cum[-1]
            owner = jnp.clip(
                jnp.searchsorted(cum, u, side="right"), 0, sz - 1
            )
            u_loc = u - (cum[owner] - tots[owner])
            cdf = jnp.cumsum(mass)
            loc_idx = jnp.clip(
                jnp.searchsorted(cdf, u_loc), 0, shard_rows - 1
            )
            cand = x_loc[loc_idx]
            return lax.psum(
                jnp.where(rank == owner, cand, jnp.zeros_like(cand)),
                ax.axis,
            )

        def init_step(i, carry):
            cents, d2 = carry
            nxt = pick(i, d2)
            cents = cents.at[i].set(nxt)
            nd = jnp.sum((x_loc - nxt) ** 2, axis=1)
            return cents, jnp.minimum(d2, nd)

        cents0 = jnp.zeros((k, d), x_loc.dtype)
        d2_0 = jnp.where(valid, 1.0, 0.0)  # first seed: uniform over rows
        first = pick(0, d2_0)
        cents0 = cents0.at[0].set(first)
        d2_1 = jnp.sum((x_loc - first) ** 2, axis=1)
        cents0, _ = lax.fori_loop(1, k, init_step, (cents0, d2_1))

        def assign(cents):
            minv, mini = fused_l2_nn(x_loc, cents)
            return mini, minv

        def reseed_empty(cents, counts, minv):
            # global reseed matching the single-device path (reference
            # detail/kmeans.cuh:882-896): empty centroids jump onto the
            # globally farthest points. Each rank contributes its local
            # top-k farthest rows; an allgather builds the global pool and
            # every rank picks the same winners (deterministic). ``minv``
            # is REUSED from this iteration's assignment — recomputing it
            # would cost another full (m, k, d) pass (the structure the
            # single-device _lloyd documents).
            mv = jnp.where(valid, minv, -jnp.inf)
            kk = min(k, x_loc.shape[0])
            lv, li = lax.top_k(mv, kk)
            cand = x_loc[li]                          # (kk, d)
            all_v = ax.allgather(lv, tiled=True)      # (P*kk,)
            all_c = ax.allgather(cand, tiled=True)    # (P*kk, d)
            far = jnp.argsort(-all_v)
            empty_rank = jnp.cumsum(counts == 0) - 1
            take = jnp.where(
                counts == 0,
                far[jnp.clip(empty_rank, 0, all_v.shape[0] - 1)],
                0,
            )
            return jnp.where(
                (counts == 0)[:, None], all_c[take].astype(cents.dtype), cents
            )

        def step(state):
            # ONE fused assignment per iteration (the _lloyd structure,
            # kmeans.py): it yields the labels, the residual of the
            # current centroids, AND the farthest-point pool for empty
            # reseeding — the previous assign/reseed/re-assign structure
            # paid 3 full (m, k, d) passes per iteration
            it, cents, _, res, _ = state
            labels, minv = assign(cents)
            labels_upd = jnp.where(valid, labels, k)  # padded rows -> dropped
            sums, counts = _update_centroids(
                x_loc, labels_upd, k, params.block_rows,
                params.compute_dtype,
            )
            sums = ax.allreduce(sums)
            counts = ax.allreduce(counts)
            new_cents = (sums / jnp.maximum(counts, 1.0)[:, None]).astype(
                x_loc.dtype
            )
            new_cents = reseed_empty(new_cents, counts, minv)
            new_res = ax.allreduce(
                jnp.sum(jnp.where(valid, minv, 0.0))
            )
            return it + 1, new_cents, res, new_res, labels

        def cond(state):
            it, _, prev, res, _ = state
            return (it < params.max_iter) & (jnp.abs(prev - res) / n > params.tol)

        labels0 = jnp.zeros((shard_rows,), jnp.int32)
        state = (
            jnp.int32(0), cents0, jnp.float32(-jnp.inf), jnp.float32(jnp.inf),
            labels0,
        )
        it, cents, _, res, _ = lax.while_loop(cond, step, state)
        labels, minv = assign(cents)
        res = ax.allreduce(jnp.sum(jnp.where(valid, minv, 0.0)))
        return cents, labels.astype(jnp.int32), res, it

    sm = comms.shard_map(
        fit_local,
        in_specs=(P(comms.axis, None),),
        out_specs=(P(None, None), P(comms.axis), P(), P()),
    )
    cents, labels, res, it = jax.jit(sm)(xs)
    return KMeansOutput(cents, labels[:n], res, it)
