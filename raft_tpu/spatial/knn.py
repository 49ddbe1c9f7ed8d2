"""Brute-force k-nearest-neighbors — analog of the reference kNN layer
(cpp/include/raft/spatial/knn/knn.cuh:195+ ``brute_force_knn``,
detail/knn_brute_force_faiss.cuh:220-395 ``brute_force_knn_impl``,
detail/fused_l2_knn.cuh:196,947 fused distance+select kernel,
detail/haversine_distance.cuh:61-152, detail/epsilon_neighborhood.cuh).

TPU design: the search streams over index blocks with a fused
distance→top-k→merge loop (``lax.scan``), so the full m×n distance matrix
never exists in HBM — the same memory behavior as the reference's fused
L2 kNN kernel, generalised to every metric. Expanded metrics ride the MXU
per block; the per-block top-k is ``lax.top_k``; the running 2k merge is the
``knn_merge_parts`` primitive applied streaming.

Multi-partition search (the reference's multi-GPU-partition path,
knn_brute_force_faiss.cuh:289-368) runs each partition's search and merges
with index translations.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax

from raft_tpu import errors
from raft_tpu.distance.distance_type import DistanceType, resolve_metric
from raft_tpu.distance.pairwise import (
    _expanded_impl,
    _unexpanded_impl,
    haversine_distance,
)
from raft_tpu.distance.distance_type import EXPANDED_METRICS
from raft_tpu.spatial.selection import select_k, merge_topk, chunk_min_select_k
from raft_tpu.spatial.fused_knn import (
    fused_l2_knn, fused_knn_supported,
)

__all__ = [
    "brute_force_knn",
    "knn_merge_parts",
    "haversine_knn",
    "epsilon_neighborhood",
]


def _block_dist(queries, yblk, metric, p):
    if metric == DistanceType.Haversine:
        return haversine_distance(queries, yblk)
    if metric in EXPANDED_METRICS:
        return _expanded_impl(metric, queries, yblk, None)
    return _unexpanded_impl(metric, queries, yblk, p, None)


@functools.partial(
    jax.jit,
    static_argnames=("k", "metric", "p", "block_n", "block_q", "exact"),
)
def _knn_single_part(
    queries,
    index,
    k: int,
    metric: DistanceType,
    p: float,
    block_n: int,
    block_q: Optional[int],
    exact: bool = True,
):
    """Fused streaming kNN against one index partition.

    ``exact=False`` swaps the per-block selection for the TPU hardware
    approx-top-k (lax.approx_min_k, ~0.95 per-block recall, ~5x cheaper
    selection) — the fast path for recall-tolerant workloads.
    """
    m, d = queries.shape
    n = index.shape[0]
    bn = max(k, min(block_n, n))
    nb = -(-n // bn)
    pad = nb * bn - n
    ip = jnp.pad(index, ((0, pad), (0, 0)))
    iblocks = ip.reshape(nb, bn, d)
    starts = jnp.arange(nb) * bn

    def one_query_block(qblk):
        def body(carry, blk):
            rv, ri = carry
            yb, j0 = blk
            dmat = _block_dist(qblk, yb, metric, p)
            cols = j0 + jnp.arange(bn)[None, :]
            dmat = jnp.where(cols < n, dmat, jnp.inf)
            if exact:
                # exact chunked selection: ~25% cheaper than top_k on wide
                # blocks (falls back to top_k for narrow/ragged ones)
                bv, bi = chunk_min_select_k(dmat, k)
            else:
                bv, bi = lax.approx_min_k(dmat, k)
            out = merge_topk(rv, ri, bv, bi + j0, select_min=True)
            return out, None

        init = (
            jnp.full((qblk.shape[0], k), jnp.inf, jnp.float32),
            jnp.zeros((qblk.shape[0], k), jnp.int32),
        )
        (vals, idxs), _ = lax.scan(body, init, (iblocks, starts))
        return vals, idxs.astype(jnp.int32)

    if block_q is None or block_q >= m:
        return one_query_block(queries)

    qb = -(-m // block_q)
    qpad = qb * block_q - m
    qp = jnp.pad(queries, ((0, qpad), (0, 0)))
    vals, idxs = lax.map(
        one_query_block, qp.reshape(qb, block_q, d)
    )
    return (
        vals.reshape(qb * block_q, k)[:m],
        idxs.reshape(qb * block_q, k)[:m],
    )


def knn_merge_parts(
    part_dists,
    part_indices,
    *,
    translations: Optional[Sequence[int]] = None,
    select_min: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    """Merge P per-partition sorted k-lists per query into one
    (reference knn.cuh ``knn_merge_parts``, kernel
    knn_brute_force_faiss.cuh:52-148): stack (P, m, k) results, offset each
    partition's indices by its translation, re-select top-k.
    """
    part_dists = jnp.asarray(part_dists)
    part_indices = jnp.asarray(part_indices)
    P, m, k = part_dists.shape
    if translations is not None:
        offs = jnp.asarray(translations, jnp.int32).reshape(P, 1, 1)
        part_indices = part_indices + offs
    flat_d = part_dists.transpose(1, 0, 2).reshape(m, P * k)
    flat_i = part_indices.transpose(1, 0, 2).reshape(m, P * k)
    return select_k(flat_d, k, select_min=select_min, indices=flat_i)


def brute_force_knn(
    index: Union[jax.Array, List],
    queries,
    k: int,
    *,
    metric="l2_sqrt_expanded",
    p: float = 2.0,
    translations: Optional[Sequence[int]] = None,
    block_n: int = 4096,
    block_q: Optional[int] = None,
    exact: bool = True,
    use_fused: Optional[bool] = None,
    compute_dtype=None,
    extra_chunks: Optional[int] = None,
    index_norms: Optional[Sequence] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Brute-force kNN over one or more index partitions.

    Mirrors ``raft::spatial::knn::brute_force_knn`` (knn.cuh:195): ``index``
    may be a list of row partitions; results carry global row ids via
    ``translations`` (default: running offsets, reference
    knn_brute_force_faiss.cuh:240-254).

    ``use_fused=None`` (auto) routes large L2-family searches on TPU to the
    fused Pallas chunk-min kernel (:mod:`raft_tpu.spatial.fused_knn`, the
    analog of the reference's fused_l2_knn.cuh fast path, measured 13x the
    scan path at SIFT-1M shape); other metrics/shapes take the streaming
    scan path. ``compute_dtype``/``extra_chunks`` tune the fused path
    (fused_l2_knn docs); ``compute_dtype=bfloat16`` with bf16 partitions
    is the HBM-resident big-index mode; a ~14 GB index runs as 3-4 bf16
    partitions so that each call's padded copy fits beside it (the
    10M x 768 BASELINE regime).

    ``index_norms``: optional per-partition precomputed squared row norms
    (list matching ``index``); repeated searches against a fixed index
    then skip one full index read per call (fused path only — the
    reference's stored-norms argument, knn_brute_force_faiss.cuh:318-330).

    Returns (distances (m, k), indices (m, k)), best-first.
    """
    metric = resolve_metric(metric)
    queries = jnp.asarray(queries)
    errors.check_matrix(queries, "queries")
    parts = index if isinstance(index, (list, tuple)) else [index]
    errors.expects(len(parts) > 0, "index: need at least one partition")
    parts = [jnp.asarray(pt) for pt in parts]
    for i, pt in enumerate(parts):
        errors.check_matrix(pt, f"index[{i}]")
        errors.check_same_cols(queries, pt, "queries", f"index[{i}]")
    total_rows = sum(pt.shape[0] for pt in parts)
    errors.check_k(k, total_rows, "total index size")
    errors.expects(
        translations is None or len(translations) == len(parts),
        "translations: %d offsets for %d partitions",
        0 if translations is None else len(translations), len(parts),
    )

    if translations is None:
        offs, acc = [], 0
        for pt in parts:
            offs.append(acc)
            acc += pt.shape[0]
    else:
        offs = list(translations)

    def _routes_fused(pt) -> bool:
        m, d = queries.shape
        n = pt.shape[0]
        fused_ok = exact and fused_knn_supported(metric, m, n, d, k)
        if use_fused or (
            use_fused is None
            and fused_ok
            and n >= 65536
            and jax.default_backend() == "tpu"
        ):
            if not fused_ok:
                raise ValueError(
                    f"use_fused=True but fused path unsupported for "
                    f"metric={metric} m={m} n={n} d={d} k={k} exact={exact}"
                )
            return True
        return False

    routes = [_routes_fused(pt) for pt in parts]
    # fused tuning args must not be dropped SILENTLY: error only when no
    # partition takes the fused path (mixed partition sets legitimately
    # route small tails to the scan path while the args apply to the
    # rest). Checked BEFORE any search runs — not after paying for the
    # full dispatch.
    errors.expects(
        (compute_dtype is None and extra_chunks is None
         and index_norms is None) or any(routes),
        "compute_dtype/extra_chunks/index_norms tune the fused path, but "
        "every partition routed to the scan path; pass use_fused=True to "
        "force fused, or drop the tuning args",
    )

    if index_norms is not None and not isinstance(
        index_norms, (list, tuple)
    ):
        # mirror the bare-array index form: a single norms vector wraps
        # into the single-partition list
        index_norms = [index_norms]
    errors.expects(
        index_norms is None or len(index_norms) == len(parts),
        "index_norms: %d norm vectors for %d partitions",
        0 if index_norms is None else len(index_norms), len(parts),
    )
    if index_norms is not None:
        # mixed routing: norms tune only the fused kernel — a norms
        # vector on a scan-routed partition quietly does nothing, so
        # say so (the all-scan case errors above)
        from raft_tpu.core import logger

        for pi, (routed, nv) in enumerate(zip(routes, index_norms)):
            if not routed and nv is not None:
                logger.warn(
                    "brute_force_knn: index_norms[%d] ignored — "
                    "partition %d routes to the scan path (norms tune "
                    "only the fused kernel)", pi, pi,
                )

    def _search_part(pt, fused, norms):
        if fused:
            kw = {}
            if compute_dtype is not None:
                kw["compute_dtype"] = compute_dtype
            if extra_chunks is not None:
                kw["extra_chunks"] = extra_chunks
            return fused_l2_knn(
                queries, pt, k, metric=metric, index_norms=norms, **kw
            )
        return _knn_single_part(
            queries, pt, k, metric, p, block_n, block_q, exact
        )

    norms_list = (
        list(index_norms) if index_norms is not None else [None] * len(parts)
    )
    results = [
        _search_part(pt, f, nr)
        for pt, f, nr in zip(parts, routes, norms_list)
    ]
    if len(parts) == 1:
        d0, i0 = results[0]
        return d0, i0 + jnp.int32(offs[0])

    pd = jnp.stack([r[0] for r in results])
    pi = jnp.stack([r[1] for r in results])
    return knn_merge_parts(pd, pi, translations=offs)


def haversine_knn(index, queries, k: int) -> Tuple[jax.Array, jax.Array]:
    """kNN under the haversine metric on (lat, lon) radian pairs
    (reference detail/haversine_distance.cuh:61-152 ``haversine_knn``).

    Returns (distances, indices) like the reference (out ordering d, i).
    """
    return brute_force_knn(index, queries, k, metric=DistanceType.Haversine)


@functools.partial(jax.jit, static_argnames=())
def _eps_impl(x, y, eps_sq):
    d2 = _unexpanded_impl(DistanceType.L2Unexpanded, x, y, 2.0, None)
    adj = d2 <= eps_sq
    vd = jnp.sum(adj, axis=1, dtype=jnp.int32)
    return adj, vd


def epsilon_neighborhood(x, y, eps: float) -> Tuple[jax.Array, jax.Array]:
    """Boolean adjacency of pairs within L2 distance ``eps`` plus per-row
    degree counts (reference
    spatial/knn/epsilon_neighborhood.cuh ``epsUnexpL2SqNeighborhood``:
    adjacency computed on squared distances, vertex degrees as the side
    output). ``eps`` is the unsquared radius.
    """
    x = jnp.asarray(x)
    y = jnp.asarray(y)
    return _eps_impl(x, y, jnp.float32(eps) ** 2)
