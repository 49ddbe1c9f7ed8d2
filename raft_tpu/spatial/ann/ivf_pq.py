"""IVF-PQ ANN index — first-class TPU implementation (the reference wraps
FAISS GpuIndexIVFPQ, cpp/include/raft/spatial/knn/detail/
ann_quantized_faiss.cuh:115-206 + ``IVFPQParam`` ann_common.h; native here).

Build: coarse k-means → per-list residuals → product quantization: the d
dims split into M subspaces, each with its own 2^bits-entry codebook
trained by k-means on residual sub-vectors (a Python loop of M small
k-means fits — M is single-digit-to-low-tens, and each fit reuses the
jitted kmeans program across subspaces of equal shape). Codes pack to
(n, M) uint8.

Search (ADC — asymmetric distance computation): per (query, probed list) a
(M, 2^bits) lookup table of squared sub-distances between the query
residual and every codebook entry — one batched MXU/VPU computation — then
candidate scores are M gathered-LUT sums, and ``lax.top_k`` selects.

Refinement (``refine_ratio`` > 1, the FAISS IndexRefineFlat niche the
reference's FAISS build exposes downstream): the index keeps the raw
vectors in list-sorted order; search takes the top ``refine_ratio * k``
ADC candidates, rescores them with exact f32 L2 (a c ≪ n gather + MXU
batched dot), and re-selects k — recovering near-exact recall at PQ
speed."""

from __future__ import annotations

import dataclasses
import functools
import math
import typing
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from raft_tpu import compat, errors
from raft_tpu.cluster.kmeans import KMeansParams, kmeans_fit, kmeans_predict
from raft_tpu.spatial.ann.common import (
    ListStorage,
    build_list_storage,
    split_oversized_lists as _split_oversized_lists,
)

__all__ = [
    "IVFPQParams", "IVFPQIndex", "ivf_pq_build", "ivf_pq_search",
    "ivf_pq_search_grouped",
]


@dataclasses.dataclass(frozen=True)
class IVFPQParams:
    """Analog of IVFPQParam (reference ann_common.h: nlist, M=n_subquantizers,
    n_bits, usePrecomputedTables)."""

    n_lists: int = 64
    pq_dim: int = 8           # M subspaces (reference n_subquantizers)
    pq_bits: int = 8          # 2^bits codebook entries
    kmeans_n_iters: int = 20
    pq_kmeans_n_iters: int = 20
    seed: int = 0
    store_raw: bool = True    # keep raw vectors for exact refinement
    kmeans_init: str = "k-means++"  # "random": cheap coarse/code books
    # Training-set cap for the coarse quantizer + PQ codebooks: datasets
    # beyond this size train on a uniform subsample and encode in
    # streaming blocks (the 10M+ regime; quantizer quality saturates far
    # below that — FAISS trains its 100M indexes the same way). None =
    # max(2^20, 64 * n_lists).
    train_size: typing.Optional[int] = None
    encode_block: int = 1 << 20  # rows per streaming-encode block
    # Longest allowed inverted list: lists beyond the cap are split into
    # sublists sharing the parent's centroid (probing spends adjacent
    # top-k slots on them — centroid distances tie). Padded-list compute
    # in the grouped searches scales with n_lists * max_list, so one
    # swollen list (a dense cluster swallowed whole) would otherwise tax
    # every list block. Tradeoff: a heavily split cluster consumes
    # several of a query's n_probes slots (raise n_probes on very skewed
    # data). None = auto, max(256, 2 * ceil(n / n_lists)) — applied only
    # on the large-n blocked-build path, where the padding tax is the
    # scaling blocker; small one-shot builds split only when an explicit
    # cap is given. 0 = off.
    max_list_cap: typing.Optional[int] = None


@compat.register_dataclass
@dataclasses.dataclass
class IVFPQIndex:
    centroids: jax.Array      # (n_lists, d)
    codebooks: jax.Array      # (M, 2^bits, ds)
    codes_sorted: jax.Array   # (n + 1, M) uint8 — sentinel row appended
    storage: ListStorage
    # (n + 1, d) raw vectors in list-sorted order (sentinel row appended),
    # or None when built with store_raw=False (pure-PQ memory footprint)
    vectors_sorted: typing.Optional[jax.Array]
    pq_dim: int = dataclasses.field(metadata=dict(static=True))
    pq_bits: int = dataclasses.field(metadata=dict(static=True))

    def warmup(self, nq: int, *, k: int = 10, n_probes: int = 8,
               qcap=None, list_block: int = 8, refine_ratio: float = 2.0,
               refine_dataset=None, exact_selection: bool = False,
               approx_recall_target: float = 0.95,
               stream_partials=None,
               use_pallas: typing.Optional[bool] = None,
               audit: bool = False) -> int:
        """Pre-compile the grouped serving program for (nq, d) float32
        batches by dispatching one all-zeros batch through the exact
        serving entry (in-process jit cache + persistent compilation
        cache when enabled) — the PQ sibling of
        :meth:`raft_tpu.spatial.ann.ivf_flat.IVFFlatIndex.warmup`.

        Returns the shape-only-resolved qcap; pass exactly that integer
        on serving dispatches (see IVFFlatIndex.warmup for why).
        ``audit=True`` runs the jaxpr-level program auditor over the
        warmed program and raises on findings
        (:mod:`raft_tpu.analysis.program`; see IVFFlatIndex.warmup)."""
        from raft_tpu.spatial.ann.common import static_qcap

        qc = static_qcap(qcap, nq, n_probes, self.centroids.shape[0])
        q0 = jnp.zeros((nq, self.centroids.shape[1]), jnp.float32)
        out = ivf_pq_search_grouped(
            self, q0, k, n_probes=n_probes, qcap=qc,
            list_block=list_block, refine_ratio=refine_ratio,
            refine_dataset=refine_dataset,
            exact_selection=exact_selection,
            approx_recall_target=approx_recall_target,
            stream_partials=stream_partials,
            use_pallas=use_pallas,
        )
        jax.block_until_ready(out)
        if audit:
            from raft_tpu.analysis.program import audit_warmed
            from raft_tpu.analysis.program.registry import (
                trace_pq_grouped,
            )

            refine_active = (
                self.vectors_sorted is not None
                or refine_dataset is not None
            ) and refine_ratio > 1.0
            up = _resolve_adc_engine(
                use_pallas, refine_active, self.pq_dim, self.pq_bits, qc
            )
            audit_warmed(trace_pq_grouped(
                self, nq, k, n_probes, qc, list_block=list_block,
                refine_ratio=refine_ratio,
                exact_selection=exact_selection,
                approx_recall_target=approx_recall_target,
                use_pallas=up, name="ivf_pq_grouped_warm",
            ))
        return qc


def _cdiv_host(a: int, b: int) -> int:
    return -(-a // b)




def _train_pq_codebooks(xt, coarse, params, ds, n_codes):
    """PQ codebooks from the TRAINING SUBSAMPLE's residuals only — the
    shared quantizer-training tail of the blocked single-chip build and
    the distributed per-rank build (comms/mnmg_ivf.py). ``coarse`` must
    have been fit on ``xt`` (its labels ARE the subsample assignments —
    no second (train_n, n_lists, d) pass)."""
    from raft_tpu.cluster.kmeans import kmeans_fit_batched

    M = params.pq_dim
    train_n = xt.shape[0]
    res_t = xt - coarse.centroids[coarse.labels]
    sub_t = res_t.reshape(train_n, M, ds).transpose(1, 0, 2)  # (M, tn, ds)
    outs = kmeans_fit_batched(
        sub_t,
        KMeansParams(
            n_clusters=n_codes,
            max_iter=params.pq_kmeans_n_iters,
            seed=params.seed + 1,
            init=params.kmeans_init,
            compute_dtype="bfloat16",
        ),
    )
    return outs.centroids                                     # (M, K, ds)


def _encode_rows(blk, coarse_centroids, codebooks, M, ds):
    """Label + PQ-encode one row block against replicated quantizers —
    the per-block body of the streaming encode, shared with the
    distributed build's per-rank shard_map encode."""
    lbl = kmeans_predict(blk, coarse_centroids)
    res = blk - coarse_centroids[lbl]
    s = res.reshape(blk.shape[0], M, ds).transpose(1, 0, 2)
    codes = jax.vmap(kmeans_predict)(s, codebooks).T.astype(jnp.uint8)
    return lbl.astype(jnp.int32), codes


@functools.partial(jax.jit, static_argnames=("M", "ds"))
def _encode_block_jit(blk, coarse_centroids, codebooks, M, ds):
    """Module-level jit of :func:`_encode_rows`: quantizers are ARGUMENTS
    (not trace-time constants), so a same-shape rebuild reuses the
    compiled executable — the warm-build path the bench's
    ``build_warm_s`` measures."""
    return _encode_rows(blk, coarse_centroids, codebooks, M, ds)


def _train_pq_and_encode_blocked(x, xt, coarse, params, ds, n_codes):
    """Subsample-trained codebooks + streaming full-dataset encode.

    PQ codebooks train on the residuals of the training subsample only;
    the full dataset is then labeled and coded in ``encode_block``-row
    blocks by one jitted program (block shape is static, so every block
    reuses the same executable). Peak transient memory is
    O(encode_block * d) instead of O(n * d) — the property that lets a
    16 GB chip build a 10M+ index.
    """
    n, d = x.shape
    M = params.pq_dim
    codebooks = _train_pq_codebooks(xt, coarse, params, ds, n_codes)

    def encode_one(blk):
        return _encode_block_jit(blk, coarse.centroids, codebooks, M, ds)

    B = params.encode_block
    lbl_parts, code_parts = [], []
    for s0 in range(0, n, B):
        blk = x[s0:min(s0 + B, n)]
        if blk.shape[0] < B:
            blk = jnp.pad(blk, ((0, B - blk.shape[0]), (0, 0)))
        lbl, codes = encode_one(blk)
        take = min(B, n - s0)
        lbl_parts.append(lbl[:take])
        code_parts.append(codes[:take])
    labels = jnp.concatenate(lbl_parts)
    codes = jnp.concatenate(code_parts)
    return labels, codes, codebooks


def _train_coarse(x, params: IVFPQParams):
    """Training-subsample selection + coarse quantizer fit — the shared
    front of the single-chip and sharded (comms/mnmg_ivf.py) builds.

    Large-n path (the DEEP-100M regime): train on a uniform subsample,
    encode the full dataset later in streaming blocks — the same
    train-on-subsample / add-in-batches structure FAISS uses under the
    reference (ann_quantized_faiss.cuh:115-206 wraps GpuIndexIVFPQ whose
    train() subsamples internally). One-shot training never needs more
    rows than saturates quantizer quality.

    ``x`` may be a host np.ndarray (the sharded build keeps the full
    dataset on host): subsample selection then happens host-side so only
    train_n rows ever materialize on device. Returns (xt, coarse, train_n).
    """
    n = x.shape[0]
    train_n = min(
        n,
        params.train_size
        if params.train_size is not None
        else max(1 << 20, 64 * params.n_lists),
    )
    if train_n < n:
        sel = jax.random.permutation(jax.random.PRNGKey(params.seed), n)[
            :train_n
        ]
        if isinstance(x, np.ndarray):
            xt = jnp.asarray(x[np.sort(np.asarray(sel))])
        else:
            xt = jnp.take(x, jnp.sort(sel), axis=0)
    else:
        xt = jnp.asarray(x)

    coarse = kmeans_fit(
        xt,
        KMeansParams(
            n_clusters=params.n_lists,
            max_iter=params.kmeans_n_iters,
            seed=params.seed,
            init=params.kmeans_init,
            # quantizer training tolerates bf16-rounded centroid updates
            # (intra-cluster averaging washes out operand rounding) and
            # the 2x MXU rate matters at the 10M-build scale
            compute_dtype="bfloat16",
        ),
    )
    return xt, coarse, train_n


def ivf_pq_build(x, params: IVFPQParams = IVFPQParams()) -> IVFPQIndex:
    x = jnp.asarray(x)
    errors.check_matrix(x, "x", min_rows=2)
    n, d = x.shape
    M = params.pq_dim
    errors.check_k(params.n_lists, n, "n_lists vs dataset rows")
    errors.expects(d % M == 0, "d=%d not divisible by pq_dim=%d", d, M)
    errors.expects(
        1 <= params.pq_bits <= 8,
        "pq_bits=%d out of range [1, 8] — codes are stored as uint8",
        params.pq_bits,
    )
    ds = d // M
    n_codes = 1 << params.pq_bits

    xt, coarse, train_n = _train_coarse(x, params)

    blocked = train_n < n or n > params.encode_block
    if params.max_list_cap is not None:
        cap = params.max_list_cap
    else:
        # auto cap only where it is the scaling blocker (see IVFPQParams)
        cap = max(256, 2 * _cdiv_host(n, params.n_lists)) if blocked else 0

    if blocked:
        labels, codes, codebooks = _train_pq_and_encode_blocked(
            x, xt, coarse, params, ds, n_codes
        )
        labels_np, cents_out = np.asarray(labels), coarse.centroids
        if cap:
            labels_np, cents_out = _split_oversized_lists(
                labels_np, cents_out, cap
            )
        storage = build_list_storage(labels_np, cents_out.shape[0])
        codes_sorted = jnp.concatenate(
            [jnp.take(codes, storage.sorted_ids, axis=0),
             jnp.zeros((1, M), jnp.uint8)]
        )
        vectors_sorted = None
        if params.store_raw:
            vectors_sorted = jnp.concatenate(
                [jnp.take(x, storage.sorted_ids, axis=0),
                 jnp.zeros((1, d), x.dtype)]
            )
        return IVFPQIndex(
            cents_out, codebooks, codes_sorted, storage,
            vectors_sorted, M, params.pq_bits,
        )

    labels = coarse.labels
    residuals = x - coarse.centroids[labels]

    # batched PQ codebook training across the M subspaces
    sub = residuals.reshape(n, M, ds).transpose(1, 0, 2)   # (M, n, ds)

    if n >= n_codes:
        # ONE vmapped Lloyd over all M subspaces: the per-subspace matmuls
        # are skinny ((n, ds) x (ds, K) with ds in the single digits —
        # poor MXU fill); batching them into (M, n, K) contractions keeps
        # the MXU busy and replaces M sequential fits with one program
        from raft_tpu.cluster.kmeans import kmeans_fit_batched

        outs = kmeans_fit_batched(
            sub,
            KMeansParams(
                n_clusters=n_codes,
                max_iter=params.pq_kmeans_n_iters,
                seed=params.seed + 1,
                init=params.kmeans_init,
            ),
        )
        codebooks = outs.centroids                          # (M, K, ds)
        # vmapped encode: one dispatch (M sequential predicts measured
        # ~9 s of pure dispatch overhead at the 500k bench shape)
        codes = (
            jax.vmap(kmeans_predict)(sub, codebooks).T.astype(jnp.uint8)
        )                                                   # (n, M)
    else:
        # tiny datasets (n < 2^bits): per-subspace fits with inf padding
        def fit_sub(subx, seed):
            out = kmeans_fit(
                subx,
                KMeansParams(
                    n_clusters=min(n_codes, subx.shape[0]),
                    max_iter=params.pq_kmeans_n_iters,
                    seed=seed,
                    init=params.kmeans_init,
                ),
            )
            cents = out.centroids
            pad = n_codes - cents.shape[0]
            if pad > 0:
                cents = jnp.concatenate(
                    [cents, jnp.full((pad, ds), jnp.inf, cents.dtype)]
                )
            return cents

        codebooks = jnp.stack(
            [fit_sub(sub[m], params.seed + m) for m in range(M)]
        )                                                   # (M, K, ds)

        def encode_sub(subx, cb):
            return kmeans_predict(subx, jnp.where(jnp.isfinite(cb), cb, 1e30))

        codes = jnp.stack(
            [encode_sub(sub[m], codebooks[m]) for m in range(M)], axis=1
        ).astype(jnp.uint8)                                 # (n, M)

    labels_np, cents_out = np.asarray(labels), coarse.centroids
    if cap:
        labels_np, cents_out = _split_oversized_lists(
            labels_np, cents_out, cap
        )
    storage = build_list_storage(labels_np, cents_out.shape[0])
    codes_sorted = jnp.concatenate(
        [codes[storage.sorted_ids], jnp.zeros((1, M), jnp.uint8)]
    )
    vectors_sorted = None
    if params.store_raw:
        vectors_sorted = jnp.concatenate(
            [x[storage.sorted_ids], jnp.zeros((1, d), x.dtype)]
        )
    return IVFPQIndex(
        cents_out, codebooks, codes_sorted, storage, vectors_sorted,
        M, params.pq_bits,
    )


@functools.partial(
    jax.jit, static_argnames=("k", "n_probes", "block_q", "refine_ratio")
)
def ivf_pq_search(
    index: IVFPQIndex, queries, k: int, *, n_probes: int = 8,
    block_q: int = 256, refine_ratio: float = 2.0,
    refine_dataset=None,
) -> Tuple[jax.Array, jax.Array]:
    """ADC search; returns (squared L2 dists, original row ids).
    Query batches run in ``block_q`` blocks so the per-(query, list) LUTs
    and the (q, p, L, M) code gather stay HBM-bounded.

    ``refine_ratio`` > 1 (and an index built with ``store_raw``) rescores
    the top ``ceil(refine_ratio * k)`` ADC candidates with exact f32
    distances before the final k-selection; returned distances are then
    exact. ``refine_ratio <= 1`` returns raw ADC approximations.

    ``refine_dataset``: the original (n, d) dataset, enabling exact
    refinement for an index built with ``store_raw=False`` (codes-only
    memory, M bytes/row) — the caller keeps the dataset, the index stays
    small; candidates gather through ``storage.sorted_ids``. Ignored when
    the index stores raw vectors."""
    from raft_tpu.spatial.ann.common import (
        check_candidate_pool, coarse_probe, map_query_blocks,
        score_l2_candidates, select_candidates,
    )

    q = jnp.asarray(queries)
    errors.check_matrix(q, "queries")
    errors.check_same_cols(q, index.centroids, "queries", "index")
    d = q.shape[1]
    M = index.pq_dim
    ds = d // M
    check_candidate_pool(k, n_probes, index.storage)
    refine = (
        index.vectors_sorted is not None or refine_dataset is not None
    ) and refine_ratio > 1.0
    c = max(k, min(int(math.ceil(refine_ratio * k)),
                   n_probes * index.storage.max_list))
    f32 = jnp.float32
    cents = index.centroids.astype(f32)
    cb = jnp.where(jnp.isfinite(index.codebooks), index.codebooks, 0.0)
    cb_n = jnp.sum(cb * cb, axis=2)                          # (M, K)

    def one_block(qb):
        nq = qb.shape[0]
        qf = qb.astype(f32)
        probes, _ = coarse_probe(qf, cents, n_probes)        # (q, p)

        # LUTs: residual of q wrt each probed centroid, per subspace vs
        # codebook; (q, p, d) residuals -> (q, p, M, ds)
        res = qf[:, None, :] - cents[probes]
        res = res.reshape(nq, n_probes, M, ds)
        dots = jnp.einsum("qpmd,mkd->qpmk", res, cb,
                          preferred_element_type=f32)
        res_n = jnp.sum(res * res, axis=3)                   # (q, p, M)
        lut = res_n[..., None] + cb_n[None, None] - 2.0 * dots  # (q,p,M,K)

        # candidates: padded probed lists, gather codes, sum LUT entries
        cand_pos = index.storage.list_index[probes]          # (q, p, L)
        codes = index.codes_sorted[cand_pos].astype(jnp.int32)  # (q,p,L,M)
        # dist[q,p,l] = sum_m lut[q,p,m,codes[q,p,l,m]]
        lut_t = lut.transpose(0, 1, 3, 2)                    # (q, p, K, M)
        # the INTENTIONAL per-query LUT gather, kept for small-batch
        # latency. Proved bounded by the program auditor: the
        # `ivf_pq_per_query` entry in ci/checks/program_contracts.json
        # pins this program's peak per-equation intermediate at the
        # block_q-blocked (blk, p, L, M) gather tile — the
        # materialization-model pass would flag any regression that
        # widens it (docs/static_analysis.md "Two tiers"), so the AST
        # grandfather entry is retired for this inline proof.
        gath = jnp.take_along_axis(  # jaxlint: disable=adc-gather
            lut_t, codes, axis=2
        )                                                    # (q, p, L, M)
        d2 = jnp.sum(gath, axis=3)                           # (q, p, L)

        valid = cand_pos < index.storage.n
        d2 = jnp.where(valid, d2, jnp.inf).reshape(nq, -1)
        flat_pos = cand_pos.reshape(nq, -1)

        if not refine:
            return select_candidates(index.storage, flat_pos, d2, k)

        # refinement: top-c by ADC score, exact f32 rescore, re-select k
        adc, cpos = jax.lax.top_k(-d2, c)                    # (q, c)
        rpos = jnp.take_along_axis(flat_pos, cpos, axis=1)   # (q, c)
        raw = _gather_refine_rows(index, refine_dataset, rpos, f32)
        exact = score_l2_candidates(
            qf, raw, jnp.isfinite(-adc) & (rpos < index.storage.n)
        )
        return select_candidates(index.storage, rpos, exact, k)

    return map_query_blocks(one_block, q, block_q)


def _gather_refine_rows(index, refine_dataset, rpos, f32):
    """Candidate raw vectors for exact refinement: from the index's
    list-sorted copy when stored, else from the caller-held dataset via
    the sorted-order -> original-id map (codes-only indexes)."""
    if index.vectors_sorted is not None:
        return index.vectors_sorted[rpos].astype(f32)
    oid = index.storage.sorted_ids[
        jnp.clip(rpos, 0, index.storage.n - 1)
    ]
    return jnp.take(refine_dataset, oid, axis=0).astype(f32)


def _resolve_adc_engine(use_pallas, refine_active: bool, pq_dim: int,
                        pq_bits: int, qcap: int) -> bool:
    """Resolve the ``use_pallas`` knob of the grouped searches to a
    concrete engine choice (a trace-time static).

    ``None`` (auto): the Pallas ADC engine (spatial/ann/pq_kernel) on a
    TPU backend whenever the exact-refine tail is active and the config
    fits the kernel's VMEM plan; the XLA one-hot path otherwise — so
    ``JAX_PLATFORMS=cpu`` never imports, let alone compiles, the kernel
    unless a caller opts in explicitly. ``True`` validates the
    requirements and raises with the reason when they do not hold
    (explicit opt-in must not silently fall back)."""
    if use_pallas is None:
        if jax.default_backend() != "tpu" or not refine_active:
            return False
        from raft_tpu.spatial.ann import scan_core
        from raft_tpu.spatial.ann.pq_kernel import pq_adc_supported

        return scan_core.auto_kernel(
            pq_adc_supported(pq_dim, pq_bits, qcap), "ivf_pq",
            f"pq_dim={pq_dim} pq_bits={pq_bits} qcap={qcap}",
        )
    if use_pallas:
        from raft_tpu.spatial.ann.pq_kernel import pq_adc_supported

        errors.expects(
            refine_active,
            "use_pallas=True requires the exact refine tail "
            "(refine_ratio > 1 and stored raw vectors or a "
            "refine_dataset): the kernel emits sub-chunk ADC minima to "
            "build the refine pool, not per-row ADC distances",
        )
        errors.expects(
            pq_adc_supported(pq_dim, pq_bits, qcap),
            "use_pallas=True unsupported at pq_dim=%d pq_bits=%d qcap=%d "
            "(one LUT block + one-hot tile exceeds the kernel's VMEM "
            "plan); use the one-hot path", pq_dim, pq_bits, qcap,
        )
    return bool(use_pallas)


# refine-pool gather budget per lax.map block on the Pallas path: the
# (blk_q, c*8, d) raw-row gather stays under this regardless of nq
_REFINE_BLOCK_BYTES = 256 << 20


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "n_probes", "qcap", "list_block", "refine_ratio",
        "exact_selection", "approx_recall_target", "stream_partials",
        "use_pallas", "pallas_interpret",
    ),
)
def _pq_grouped_impl(index, q, k, n_probes, qcap, list_block, refine_ratio,
                     refine_dataset=None, probes=None,
                     exact_selection=False, approx_recall_target=0.95,
                     stream_partials=None, use_pallas=False,
                     pallas_interpret=False, row_mask=None):
    # ``row_mask``: optional (n + 1,) RUNTIME live mask over slab
    # positions (tombstone deletion, spatial/ann/mutation.py). The
    # one-hot engine folds it into the scan's validity mask; the Pallas
    # kernel path applies it at the exact-refine tail instead (the
    # kernel emits sub-chunk minima, so a tombstoned row can still crowd
    # a pool slot there — it can never SURFACE, and compaction bounds
    # the density; docs/mutation.md). Runtime input: flips never
    # recompile.
    from raft_tpu.spatial.ann.common import (
        coarse_probe, invert_probe_map_ranked, regroup_pairs,
        score_l2_candidates, select_candidates,
    )

    storage = index.storage
    n_lists = index.centroids.shape[0]
    L = storage.max_list
    nq, d = q.shape
    p = n_probes
    M = index.pq_dim
    ds = d // M
    K = 1 << index.pq_bits
    f32 = jnp.float32
    bf16 = jnp.bfloat16
    qf = q.astype(f32)
    cents = index.centroids.astype(f32)
    cb = jnp.where(jnp.isfinite(index.codebooks), index.codebooks, 0.0)
    cb_n = jnp.sum(cb * cb, axis=2)                          # (M, K)

    if probes is None:
        probes, _ = coarse_probe(qf, cents, p)               # (nq, p)
    qmat, rmat, l_flat, slot = invert_probe_map_ranked(
        probes, n_lists, qcap
    )

    q_pad = jnp.concatenate([qf, jnp.zeros((1, d), f32)])    # sentinel query
    # per-(list, query) partial width: must cover the REFINE pool, not just
    # k — on clustered data a query's home list can hold most of the
    # global top-c ADC candidates, and truncating it to k caps recall
    # (measured: 0.73 vs 0.95 at the 500k bench shape with kk = k)
    refine = (
        index.vectors_sorted is not None or refine_dataset is not None
    ) and refine_ratio > 1.0
    kk = min(max(k, int(math.ceil(refine_ratio * k)) if refine else k), L)
    # the Pallas ADC engine builds the refine pool from sub-chunk minima,
    # so it only applies when the exact refine tail runs (entry points
    # enforce this; the AND is belt-and-braces)
    use_kernel = bool(use_pallas) and refine

    def block_luts(lblk):
        """Per-(list, query-slot) ADC lookup tables for one list block —
        residual of each slot's query vs THIS list's centroid, scored
        against every codebook entry, INCLUDING the residual-norm
        constant (so summed/contracted entries are complete squared
        distances, comparable across lists in the pooled selection).
        The single LUT authority for BOTH ADC engines: the one-hot
        contraction and the Pallas kernel must never drift.
        Returns (qids (LB, qcap), lut (LB, qcap, M, K) f32)."""
        LB = lblk.shape[0]
        qids = qmat[lblk]                                    # (LB, qcap)
        qv = q_pad[qids]                                     # (LB, qcap, d)
        res = qv - cents[lblk][:, None, :]                   # (LB, qcap, d)
        res = res.reshape(LB, qcap, M, ds)
        dots = jnp.einsum("bqmd,mkd->bqmk", res, cb,
                          preferred_element_type=f32)
        res_n = jnp.sum(res * res, axis=3)                   # (LB, qcap, M)
        return qids, res_n[..., None] + cb_n[None, None] - 2.0 * dots

    def block_fn(lblk):                                      # (LB,) list ids
        LB = lblk.shape[0]
        qids, lut = block_luts(lblk)                         # (LB, qcap, M, K)

        # Each list is CONTIGUOUS in sorted storage, so its codes read as
        # one dynamic_slice slab — row-granular list_index gathers of
        # M-byte code rows measured ~50x slower at the 10M x 96 shape
        # (the same contiguity the fused-kNN phase-2 DMA exploits).
        offs = storage.list_offsets[lblk]                    # (LB,)
        szs = storage.list_sizes[lblk]
        o_c = jnp.minimum(offs, storage.n + 1 - L)           # slice clamp
        codes = jax.vmap(
            lambda s: lax.dynamic_slice(index.codes_sorted, (s, 0), (L, M))
        )(o_c)                                               # (LB, L, M) u8
        pos = o_c[:, None] + jnp.arange(L, dtype=jnp.int32)[None, :]
        in_list = (pos >= offs[:, None]) & (pos < (offs + szs)[:, None])
        if row_mask is not None:
            in_list = in_list & (row_mask[pos] > 0)

        # THE grouped-PQ trick: dist[b,q,l] = sum_m lut[b,q,m,codes[b,l,m]]
        # is a matmul between the flattened LUT and the one-hot code
        # matrix — dense MXU work replacing the per-candidate (q,p,L,M)
        # random gather that bounds the per-query path
        onehot = (
            codes[..., None] == jnp.arange(K, dtype=jnp.uint8)
        ).astype(bf16)                                       # (LB, L, M, K)
        # the INTENTIONAL legacy one-hot engine, kept as the
        # use_pallas=False CPU/interpret fallback. Proved pinned by the
        # program auditor: the `ivf_pq_grouped_onehot` entry in
        # ci/checks/program_contracts.json snapshots this engine's
        # scan-path f32 tiles and peak intermediate bytes, and the
        # Pallas serving entry (`ivf_pq_grouped_pallas`) pins ZERO wide
        # tiles — a new one-hot spelling anywhere else fails the AST
        # rule outright now that the baseline entry is retired for this
        # inline proof (docs/static_analysis.md "Two tiers").
        d2 = jax.lax.dot_general(  # jaxlint: disable=adc-gather
            lut.reshape(LB, qcap, M * K).astype(bf16),
            onehot.reshape(LB, L, M * K),
            (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=f32,
        )                                                    # (LB, qcap, L)

        invalid = (qids >= nq)[:, :, None] | (~in_list)[:, None, :]
        d2 = jnp.where(invalid, jnp.inf, d2)
        # per-(list, slot) partial selection: when exact refinement runs
        # downstream, use the TPU hardware approx top-k (lax.approx_min_k,
        # ~0.95 per-call recall) — this selection only shapes the ADC
        # candidate pool, and exact lax.top_k here measured ~14x the cost
        # of everything else in the block at the 10M shape. The UNREFINED
        # path keeps exact selection: its per-block picks ARE the results.
        # ``exact_selection`` restores exact candidate selection without
        # disabling refinement; ``approx_recall_target`` tunes the
        # approximate stages' per-call recall.
        if refine and not exact_selection:
            vals, sel = lax.approx_min_k(
                d2, kk, recall_target=approx_recall_target
            )                                                # (LB, qcap, kk)
        else:
            nv, sel = lax.top_k(-d2, kk)
            vals = -nv
        # kk-wide selection remap, not a LUT gather:
        memp = jnp.take_along_axis(  # jaxlint: disable=adc-gather
            jnp.broadcast_to(pos[:, None, :], d2.shape),
            sel.astype(jnp.int32), axis=2,
        )
        return vals, memp

    if use_kernel:
        from raft_tpu.spatial.ann import pq_kernel, scan_core

        sub = pq_kernel.SUBCHUNK
        # the shared rounding + profile pq_adc_supported validated the
        # VMEM plan with (tile_profile auto-selects the latency plan for
        # qcap-1/8 serving shapes — docs/ivf_scale.md "One scan-kernel
        # core")
        q_kpad = scan_core.pad_queries(qcap)
        # capped at the code slab's own lane-rounded height (see the
        # flat twin: a wide profile start must not widen the per-list
        # window past max_list)
        l_tile = pq_kernel.plan_l_tile(
            M * K, q_kpad,
            l_tile=-(-L // scan_core.LANE) * scan_core.LANE,
            profile=scan_core.tile_profile(qcap),
        )
        l_pad = -(-L // l_tile) * l_tile
        nsc = l_pad // sub
        rows = index.codes_sorted.shape[0]    # n + 1 (sentinel row)
        rows_pad = max(rows, l_pad)
        # tiny indexes whose whole slab is shorter than one padded list
        # window: extend the slab so the clamped dynamic_slice stays in
        # range (static condition — big indexes never pay the copy)
        codes_src = (
            index.codes_sorted if rows_pad == rows
            else jnp.pad(index.codes_sorted,
                         ((0, rows_pad - rows), (0, 0)))
        )

        def block_fn_pallas(lblk):            # (LB,) list ids
            LB = lblk.shape[0]
            _, lut = block_luts(lblk)         # shared LUT authority
            lutf = lut.reshape(LB, qcap, M * K)
            if q_kpad > qcap:
                lutf = jnp.pad(
                    lutf, ((0, 0), (0, q_kpad - qcap), (0, 0))
                )
            offs = storage.list_offsets[lblk]                # (LB,)
            szs = storage.list_sizes[lblk]
            o_c = jnp.minimum(offs, rows_pad - l_pad)        # slice clamp
            codes_t = jax.vmap(
                lambda s: lax.dynamic_slice(codes_src, (s, 0), (l_pad, M))
            )(o_c).transpose(0, 2, 1)                        # (LB, M, l_pad)
            lo = offs - o_c
            bounds = jnp.stack([lo, lo + szs], axis=1)       # (LB, 2)
            mins = pq_kernel.pq_adc_subchunk_min(
                lutf.astype(jnp.bfloat16), codes_t, bounds,
                interpret=pallas_interpret, l_tile=l_tile,
            )[:, :qcap]                                      # (LB, qcap, nsc)
            # positions are NOT returned: a sub-chunk's slab base is
            # fully derivable from (probe slot, chunk index) after
            # selection, so the kernel path pools VALUES ONLY — half
            # the pool memory and scatter traffic of the legacy path
            return mins

        width, scan_fn = nsc, block_fn_pallas
    else:
        width, scan_fn = kk, block_fn

    # pad the list axis up to a multiple of list_block (clamped ids — the
    # padded slots recompute the last list; regroup never references
    # them, and the streamed scatter re-writes identical values)
    # instead of shrinking list_block, which collapses to 1-list blocks
    # when n_lists is prime-ish (e.g. after oversized-list splitting)
    nl_pad = -(-n_lists // list_block) * list_block
    lids = jnp.minimum(
        jnp.arange(nl_pad, dtype=jnp.int32), n_lists - 1
    ).reshape(-1, list_block)

    if stream_partials is None:
        # auto: stream once the materialized partials pass ~2 GB. qcap
        # must cover the HOT list, so on skewed probe maps
        # n_lists * qcap can exceed the true pair count nq * p by 30x+ —
        # the buffer compile-OOM'd at 11.8 GB at 3M x 768 rr=16
        # (docs/ivf_scale.md; VERDICT r4 weak-5). The kernel path pools
        # values only (no int32 positions), hence the smaller footprint.
        per_entry = 4 if use_kernel else 8
        stream_partials = n_lists * qcap * width * per_entry > (1 << 31)
    if stream_partials:
        # stream list blocks through the query-major pool: scatter each
        # block's (LB, qcap, width) partials straight to their (query,
        # probe-rank) rows via the slot inverse — peak extra memory is
        # ONE block's partials, the reference's grid-stride bounding of
        # the same intermediate (pairwise_distance_base.cuh:122-134)
        if use_kernel:
            def scan_body_v(pvc, lblk):
                v = scan_fn(lblk)
                qi, ri = qmat[lblk], rmat[lblk]      # sentinels drop
                return pvc.at[qi, ri].set(v, mode="drop"), None

            pv, _ = lax.scan(
                scan_body_v,
                jnp.full((nq, p, width), jnp.inf, jnp.float32), lids,
            )
            pv, pm = pv.reshape(nq, p * width), None
        else:
            def scan_body(carry, lblk):
                pvc, pmc = carry
                v, mp = scan_fn(lblk)
                qi, ri = qmat[lblk], rmat[lblk]      # sentinels drop
                pvc = pvc.at[qi, ri].set(v, mode="drop")
                pmc = pmc.at[qi, ri].set(mp, mode="drop")
                return (pvc, pmc), None

            init = (
                jnp.full((nq, p, width), jnp.inf, jnp.float32),
                jnp.full((nq, p, width), storage.n, jnp.int32),
            )
            (pv, pm), _ = lax.scan(scan_body, init, lids)
            pv = pv.reshape(nq, p * width)
            pm = pm.reshape(nq, p * width)
    elif use_kernel:
        vals = lax.map(scan_fn, lids)
        vals = vals.reshape(nl_pad, qcap, width)[:n_lists]
        # values-only regroup (the slot inverse of regroup_pairs)
        ok = slot < qcap
        safe_slot = jnp.minimum(slot, qcap - 1)
        pv = jnp.where(
            ok[:, None], vals[l_flat, safe_slot], jnp.inf
        ).reshape(nq, p * width)
        pm = None
    else:
        vals, mem = lax.map(scan_fn, lids)
        vals = vals.reshape(nl_pad, qcap, width)[:n_lists]
        mem = mem.reshape(nl_pad, qcap, width)[:n_lists]
        pv, pm = regroup_pairs(vals, mem, l_flat, slot, nq, p, qcap)

    if not refine:
        return select_candidates(storage, pm, pv, k)

    if use_kernel:
        # kernel path: pool entries are SUB-CHUNK minima. Select the
        # top-c sub-chunks — the fused_knn cover argument at 8-row
        # granularity: every ADC-rank-c row lives in a sub-chunk whose
        # minimum is <= the c-th best ADC value, so the selected
        # sub-chunks' rows are a SUPERSET of the one-hot path's top-c
        # row pool at the same refine_ratio — then rescore their rows
        # with exact f32 (refine semantics and precision unchanged).
        # Clamp to the pool width LAST: a large k (> p*width) must not
        # ask top_k for more sub-chunks than exist — the clamped pool
        # still covers k rows (c*8 = p*l_pad >= p*max_list >= k, the
        # check_candidate_pool precondition).
        c = min(p * width, max(k, int(math.ceil(refine_ratio * k))))
        if exact_selection:
            nv, cpos = lax.top_k(-pv, c)
            nadc = -nv
        else:
            nadc, cpos = lax.approx_min_k(
                pv, c, recall_target=approx_recall_target
            )                                                # (nq, c)
        cpos = cpos.astype(jnp.int32)
        # slab positions are DERIVED, not pooled: pool index -> (probe
        # slot, chunk), and the sub-chunk's base replays the block's
        # clamped dynamic-slice origin o_c = min(offset, rows_pad-l_pad)
        offs_q = storage.list_offsets[probes]                # (nq, p)
        szs_q = storage.list_sizes[probes]
        slot_sel = cpos // width
        off_sel = jnp.take_along_axis(offs_q, slot_sel, axis=1)
        end_sel = off_sel + jnp.take_along_axis(szs_q, slot_sel, axis=1)
        base_sel = (
            jnp.minimum(off_sel, rows_pad - l_pad)
            + sub * (cpos % width)
        )                                                    # (nq, c)
        # per-row validity: a sub-chunk window can overhang its list's
        # tail into the NEXT list's slab rows — mask against the exact
        # [offset, offset+size) range of the probe slot it came from
        rows_sel = base_sel[:, :, None] + jnp.arange(sub, dtype=jnp.int32)
        validf = (
            (rows_sel >= off_sel[:, :, None])
            & (rows_sel < end_sel[:, :, None])
            & (jnp.isfinite(nadc) & (nadc < pq_kernel.BIG))[:, :, None]
        )
        if row_mask is not None:
            # tombstones are applied per ROW at the refine tail on the
            # kernel path (the in-kernel sub-chunk minima are unmasked)
            validf = validf & (
                row_mask[jnp.clip(rows_sel, 0, storage.n)] > 0
            )
        validf = validf.reshape(nq, c * sub)
        rpos = rows_sel.reshape(nq, c * sub)

        def refine_blk(args):
            qb, rp, vl = args
            raw = _gather_refine_rows(
                index, refine_dataset, jnp.clip(rp, 0, storage.n), f32
            )
            exact = score_l2_candidates(qb, raw, vl & (rp < storage.n))
            return select_candidates(storage, rp, exact, k)

        # block the (blk_q, c*8, d) raw-row gather over queries so the
        # 8x-wider kernel-path pool never materializes a multi-GB
        # transient at serving batch sizes (zero-padded rows compute on
        # all-invalid candidates and are sliced away)
        blk_q = max(8, min(nq, _REFINE_BLOCK_BYTES // (c * sub * d * 4)))
        from raft_tpu.spatial.ann.common import map_query_blocks

        return map_query_blocks(refine_blk, (qf, rpos, validf), blk_q)

    # exact refinement: top-c of the pooled ADC candidates, f32 rescore
    # (pool selection rides the hardware approx top-k too — same
    # already-approximate-stage argument as the per-block selection)
    c = max(k, min(int(math.ceil(refine_ratio * k)), p * kk))
    if exact_selection:
        nv, cpos = lax.top_k(-pv, c)
        nadc = -nv                                           # min-k convention
    else:
        nadc, cpos = lax.approx_min_k(
            pv, c, recall_target=approx_recall_target
        )                                                    # (nq, c)
    adc = -nadc
    rpos = jnp.take_along_axis(pm, cpos.astype(jnp.int32), axis=1)
    raw = _gather_refine_rows(index, refine_dataset, rpos, f32)
    exact = score_l2_candidates(
        qf, raw, jnp.isfinite(-adc) & (rpos < storage.n)
    )
    return select_candidates(storage, rpos, exact, k)


def ivf_pq_search_grouped(
    index: IVFPQIndex, queries, k: int, *, n_probes: int = 8,
    qcap: typing.Union[int, str, None] = None, list_block: int = 8,
    refine_ratio: float = 2.0, refine_dataset=None,
    exact_selection: bool = False, approx_recall_target: float = 0.95,
    stream_partials: typing.Optional[bool] = None,
    qcap_max_drop_frac: typing.Optional[float] = None,
    use_pallas: typing.Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Throughput-mode IVF-PQ search, grouped by LIST (the PQ counterpart
    of :func:`ivf_flat_search_grouped`; SURVEY.md §7 hard part №3).

    Two structural wins over :func:`ivf_pq_search` at large batch:

    * each list's codes are loaded ONCE per batch (not once per probing
      query), and
    * the ADC table lookup ``sum_m lut[q, m, codes[l, m]]`` is computed as
      a matmul between the flattened per-query LUT (qcap, M*2^bits) and the
      one-hot code matrix (L, M*2^bits) — dense MXU work replacing the
      random gather that bounds the per-query path (measured: the gather
      moves ~6 GB per 4096-query batch at the 500k x 96 bench shape).

    The bf16 one-hot contraction only affects ADC *candidate ranking*;
    ``refine_ratio`` > 1 rescores the top candidates with exact f32
    distances (HIGHEST precision), so returned distances are exact.
    WITHOUT refinement (``refine_ratio <= 1``, or a codes-only index and
    no ``refine_dataset``) the returned distances carry the bf16 ADC
    rounding — coarser than :func:`ivf_pq_search`'s f32 per-query LUT
    path, which ``approx_knn_search``'s auto mode may select at small
    batch; pass an explicit ``mode=`` there if bit-stable unrefined
    distances across batch sizes matter.

    ``qcap`` caps queries per list (static shape); overflow pairs are
    dropped. Default (``qcap=None``): auto-sized from the actual probe
    map so at most 2% of (query, probe) pairs drop, with any residual
    logged — never silent (common.resolve_qcap). The auto path costs one
    eager coarse probe + host sync per call, and a shifting query mix
    that crosses a qcap doubling boundary recompiles the grouped
    program — serving workloads that need fully-async dispatch should
    pass an explicit ``qcap`` and audit it with common.probe_drop_stats.
    ``qcap="throughput"`` picks ~0.75x the mean probe occupancy (block
    compute is linear in qcap; measured 4.6x QPS at flat recall on
    clustered workloads — common.throughput_qcap documents when it is
    NOT safe).

    ``refine_dataset``: caller-held (n, d) dataset enabling exact
    refinement for codes-only (``store_raw=False``) indexes — see
    :func:`ivf_pq_search`.

    Candidate selection inside the REFINED path uses the TPU hardware
    approximate top-k (``lax.approx_min_k``) at two stages (per-block and
    pooled) — a throughput choice that slightly thins the ADC candidate
    pool. ``exact_selection=True`` restores exact ``lax.top_k`` at both
    stages without disabling refinement (the pre-r03 behavior);
    ``approx_recall_target`` tunes the approximate stages instead
    (default 0.95). Unrefined searches always select exactly.

    ``stream_partials``: stream list blocks through the query-major
    candidate pool instead of materializing the (n_lists, qcap, kk)
    per-block partials — bounds HBM to one block's partials + the pool
    when hot-list-skewed probe maps force qcap far above the mean
    occupancy (the 3M x 768 rr=16 regime that otherwise compile-OOMs at
    11.8 GB). ``None`` (default) auto-streams past a ~2 GB partials
    footprint; the materialized path is kept for small buffers where the
    one-shot regroup measures faster.

    ``use_pallas`` selects the ADC engine (docs/ivf_scale.md "ADC in
    VMEM"): ``None`` (auto) runs the Pallas sub-chunk-min kernel
    (spatial/ann/pq_kernel) on a TPU backend whenever the exact refine
    tail is active and the config fits its VMEM plan — the one-hot code
    expansion then lives only in VMEM and only (qcap, max_list/8)
    sub-chunk minima reach HBM, instead of the XLA path's one-hot +
    distance-tile round trips. ``False`` pins the XLA one-hot path (the
    CPU/interpret fallback — bit-stable with previous releases);
    ``True`` opts in explicitly (interpret mode off-TPU) and raises when
    the requirements do not hold. Returned candidates are value-exact
    between engines at the same refine_ratio (the kernel's refine pool
    is a superset — sub-chunk cover); tied candidates may order
    differently.
    """
    from raft_tpu.spatial.ann.common import (
        check_candidate_pool, resolve_qcap_arg,
    )

    q = jnp.asarray(queries)
    errors.check_matrix(q, "queries")
    errors.check_same_cols(q, index.centroids, "queries", "index")
    check_candidate_pool(k, n_probes, index.storage)
    errors.expects(
        0.0 < approx_recall_target <= 1.0,
        "approx_recall_target=%s out of range (0, 1]", approx_recall_target,
    )
    n_lists = index.centroids.shape[0]
    qcap, probes = resolve_qcap_arg(
        qcap, q, index.centroids, n_lists, n_probes,
        max_drop_frac=qcap_max_drop_frac,
    )
    list_block = max(1, min(list_block, n_lists))
    refine_active = (
        index.vectors_sorted is not None or refine_dataset is not None
    ) and refine_ratio > 1.0
    use_pallas = _resolve_adc_engine(
        use_pallas, refine_active, index.pq_dim, index.pq_bits, qcap
    )
    return _pq_grouped_impl(
        index, q, k, n_probes, qcap, list_block, refine_ratio,
        refine_dataset=refine_dataset, probes=probes,
        exact_selection=exact_selection,
        approx_recall_target=approx_recall_target,
        stream_partials=stream_partials,
        use_pallas=use_pallas,
        pallas_interpret=jax.default_backend() != "tpu",
    )
