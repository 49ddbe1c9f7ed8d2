"""IVF-SQ (scalar quantization) — analog of the reference's
GpuIndexIVFScalarQuantizer wrap (ann_quantized_faiss.cuh:143-160
``QuantizerType`` QT_8bit family; native here).

Vectors are affinely mapped to int8 per dimension (global min/max train
pass, the QT_8bit scheme); lists and search reuse the IVF-Flat machinery
with dequantization fused into the candidate scoring. Since ISSUE 11 the
grouped (list-major) search runs through the ONE grouped scan body
(:func:`raft_tpu.spatial.ann.ivf_flat._grouped_impl` in SQ mode) and its
``use_pallas`` path through the int8 in-kernel dequant+scan engine
(:mod:`raft_tpu.spatial.ann.sq_kernel`): int8 slab tiles cross HBM at one
byte per element — HALF the bf16 flat engine's slab traffic — and expand
to bf16 only in VMEM, with the exact-f32 rerank tail dequantizing through
the same affine map.
"""

from __future__ import annotations

import dataclasses
import typing
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu import compat, errors

from raft_tpu.cluster.kmeans import KMeansParams, kmeans_fit
from raft_tpu.spatial.ann.common import (
    ListStorage,
    build_list_storage,
    place_row_store,
    split_oversized_lists,
)

__all__ = [
    "IVFSQParams", "IVFSQIndex", "ivf_sq_build", "ivf_sq_search",
    "ivf_sq_search_grouped", "sq_decode", "sq_encode",
]


def sq_encode(x, vmin, vscale):
    """THE QT_8bit affine encoder — ``clip(round((x - vmin) / vscale)
    - 128)`` as int8, per dimension over the LAST axis (any leading
    shape). The one spelling shared by the single-chip build, the
    distributed build's per-rank encode, and compaction's re-encode;
    its inverse is :func:`sq_decode` (and, column-wise in-kernel,
    ``sq_kernel._dequant_tile``) — the pair must never drift."""
    x = jnp.asarray(x)
    shape = (1,) * (x.ndim - 1) + (-1,)
    vmin = jnp.asarray(vmin, jnp.float32).reshape(shape)
    vscale = jnp.asarray(vscale, jnp.float32).reshape(shape)
    return jnp.clip(
        jnp.round((x.astype(jnp.float32) - vmin) / vscale) - 128,
        -128, 127,
    ).astype(jnp.int8)


def sq_decode(codes_f32, vmin, vscale):
    """THE QT_8bit affine decoder — ``y = (code + 128)·vscale + vmin``
    in f32, per dimension over the LAST axis. ``codes_f32``: codes
    already widened to f32 (callers widen once at their gather/slice).
    Shared by the grouped body's XLA scan + rerank tail, the per-query
    search, and compaction; the in-kernel column-layout spelling with
    the single bf16 round is ``sq_kernel._dequant_tile``."""
    shape = (1,) * (codes_f32.ndim - 1) + (-1,)
    return (
        (codes_f32 + 128.0) * jnp.reshape(vscale, shape)
        + jnp.reshape(vmin, shape)
    )


@dataclasses.dataclass(frozen=True)
class IVFSQParams:
    n_lists: int = 64
    kmeans_n_iters: int = 20
    seed: int = 0
    # see IVFFlatParams.max_list_cap (common.split_oversized_lists)
    max_list_cap: typing.Optional[int] = None


@compat.register_dataclass
@dataclasses.dataclass
class IVFSQIndex:
    centroids: jax.Array      # (n_lists, d)
    codes_sorted: jax.Array   # (n + 1, d) int8
    vmin: jax.Array           # (d,)
    vscale: jax.Array         # (d,)
    storage: ListStorage

    def warmup(self, nq: int, *, k: int = 10, n_probes: int = 8,
               qcap=None, list_block: int = 32,
               stream_partials=None,
               use_pallas: typing.Optional[bool] = None,
               rerank_ratio: float = 4.0, audit: bool = False) -> int:
        """Pre-compile the grouped SQ serving program for (nq, d) float32
        batches — the SQ sibling of :meth:`IVFFlatIndex.warmup`: one
        all-zeros batch is dispatched through
        :func:`ivf_sq_search_grouped` and blocked on, so the first real
        batch pays dispatch, not trace+compile. ``qcap`` resolves
        SHAPE-ONLY (:func:`...ann.common.static_qcap`) and the resolved
        value is returned; pass exactly that integer on every serving
        dispatch (docs/serving.md). ``audit=True`` runs the jaxpr-level
        program auditor over the warmed program and raises on findings
        (:mod:`raft_tpu.analysis.program`; see IVFFlatIndex.warmup).
        Sets the ``ivf_row_store_row_major`` gauge (``engine="sq"``) off
        the code store's layout, as the flat warmup does."""
        from raft_tpu.spatial.ann.common import (
            set_row_store_gauge, static_qcap,
        )

        set_row_store_gauge("sq", self.codes_sorted)
        qc = static_qcap(qcap, nq, n_probes, self.centroids.shape[0])
        q0 = jnp.zeros((nq, self.centroids.shape[1]), jnp.float32)
        out = ivf_sq_search_grouped(
            self, q0, k, n_probes=n_probes, qcap=qc,
            list_block=list_block, stream_partials=stream_partials,
            use_pallas=use_pallas, rerank_ratio=rerank_ratio,
        )
        jax.block_until_ready(out)
        if audit:
            from raft_tpu.analysis.program import audit_warmed
            from raft_tpu.analysis.program.registry import (
                trace_flat_grouped,
            )

            up = _resolve_sq_engine(
                use_pallas, self.centroids.shape[1], qc
            )
            audit_warmed(trace_flat_grouped(
                _flat_view(self), nq, k, n_probes, qc,
                list_block=list_block, use_pallas=up,
                rerank_ratio=rerank_ratio,
                dequant=(jnp.asarray(self.vmin, jnp.float32),
                         jnp.asarray(self.vscale, jnp.float32)),
                name="ivf_sq_grouped_warm",
                extra_meta={"int8_slab": True},
            ))
        return qc


def ivf_sq_build(x, params: IVFSQParams = IVFSQParams()) -> IVFSQIndex:
    x = jnp.asarray(x)
    out = kmeans_fit(
        x,
        KMeansParams(
            n_clusters=params.n_lists,
            max_iter=params.kmeans_n_iters,
            seed=params.seed,
            # quantizer training tolerates bf16-rounded centroid updates
            compute_dtype="bfloat16",
        ),
    )
    vmin = jnp.min(x, axis=0)
    vmax = jnp.max(x, axis=0)
    vscale = jnp.maximum(vmax - vmin, 1e-12) / 255.0
    codes = sq_encode(x, vmin, vscale)
    labels_np, cents = np.asarray(out.labels), out.centroids
    if params.max_list_cap:
        labels_np, cents = split_oversized_lists(
            labels_np, cents, params.max_list_cap
        )
    storage = build_list_storage(labels_np, cents.shape[0])
    codes_sorted = place_row_store(jnp.concatenate(
        [codes[storage.sorted_ids], jnp.zeros((1, x.shape[1]), jnp.int8)]
    ))
    return IVFSQIndex(cents, codes_sorted, vmin, vscale, storage)


def _resolve_sq_engine(use_pallas, d: int, qcap: int) -> bool:
    """Resolve the ``use_pallas`` knob of the grouped SQ searches to a
    concrete engine choice (a trace-time static) — the SQ sibling of
    :func:`raft_tpu.spatial.ann.ivf_flat._resolve_scan_engine`, backed by
    the SAME shared planner (``scan_core.plan_l_tile`` through the SQ
    engine's byte model).

    ``None`` (auto): the int8 Pallas dequant+scan engine (spatial/ann/
    sq_kernel) on a TPU backend whenever the config fits the kernel's
    VMEM plan; the XLA dequant scan otherwise — ``JAX_PLATFORMS=cpu``
    never imports the kernel module unless a caller opts in explicitly.
    ``True`` validates the planner requirement and raises NAMING it when
    it does not hold (explicit opt-in must not silently fall back).
    ``False`` pins the XLA dequant scan."""
    if use_pallas is None:
        if jax.default_backend() != "tpu":
            return False
        from raft_tpu.spatial.ann import scan_core
        from raft_tpu.spatial.ann.sq_kernel import sq_scan_supported

        return scan_core.auto_kernel(
            sq_scan_supported(d, qcap), "ivf_sq", f"d={d} qcap={qcap}"
        )
    if use_pallas:
        from raft_tpu.spatial.ann.sq_kernel import sq_scan_supported

        errors.expects(
            sq_scan_supported(d, qcap),
            "use_pallas=True unsupported at d=%d qcap=%d: "
            "sq_kernel.sq_scan_supported is False — one int8 slab tile "
            "+ its in-VMEM bf16 dequant + the query block exceed the "
            "shared planner's VMEM budget (scan_core.plan_l_tile "
            "returned None even at the 128-row floor); use the XLA "
            "dequant scan (use_pallas=False)", d, qcap,
        )
    return bool(use_pallas)


def _flat_view(index: IVFSQIndex):
    """The IVF-Flat pytree view of an SQ index: the ONE grouped scan
    body (:func:`...ivf_flat._grouped_impl`) consumes it with the
    ``dequant`` runtime pair carrying the affine map. ``data_sorted``
    holds the int8 codes — the XLA path dequantizes sliced slab blocks,
    the kernel path hands them to ``sq_kernel`` untouched."""
    from raft_tpu.spatial.ann.ivf_flat import IVFFlatIndex

    return IVFFlatIndex(
        centroids=index.centroids,
        data_sorted=index.codes_sorted,
        storage=index.storage,
        metric="sqeuclidean",     # SQ distances are squared, like PQ's
    )


def ivf_sq_search_grouped(
    index: IVFSQIndex, queries, k: int, *, n_probes: int = 8,
    qcap: typing.Union[int, str, None] = None, list_block: int = 32,
    stream_partials: typing.Optional[bool] = None,
    qcap_max_drop_frac: typing.Optional[float] = None,
    use_pallas: typing.Optional[bool] = None,
    rerank_ratio: float = 4.0,
) -> Tuple[jax.Array, jax.Array]:
    """Throughput-mode (list-major) IVF-SQ search — the SQ instantiation
    of the ONE grouped scan body shared with IVF-Flat
    (:func:`raft_tpu.spatial.ann.ivf_flat._grouped_impl` with the
    ``dequant`` runtime pair; ISSUE 11). Returns (squared L2 distances
    over the dequantized vectors, row ids), exactly the per-query
    :func:`ivf_sq_search` semantics at the grouped engine's throughput.

    ``use_pallas`` selects the scan engine (docs/ivf_scale.md "One
    scan-kernel core"): ``None`` (auto) runs the int8 Pallas
    dequant+scan kernel (spatial/ann/sq_kernel) on a TPU backend
    whenever the config fits its VMEM plan — int8 slab tiles cross HBM
    at one byte per element and expand to bf16 only in VMEM, and the
    top-``c`` sub-chunks' rows are rescored against f32-dequantized
    values at HIGHEST precision, so returned distances are exactly the
    XLA path's. ``False`` pins the XLA dequant scan (the CPU fallback);
    ``True`` opts in explicitly (interpret mode off-TPU) and raises
    naming the unmet planner requirement when it does not hold.
    ``rerank_ratio`` sizes the kernel path's rerank pool, as in the
    flat engine."""
    q = jnp.asarray(queries)
    errors.check_matrix(q, "queries")
    errors.check_same_cols(q, index.centroids, "queries", "index")
    storage = index.storage
    if k > storage.max_list:
        # a single list cannot fill a per-list top-k row
        errors.expects(
            not use_pallas,
            "use_pallas=True: k=%d > max_list=%d routes to the "
            "per-query SQ search, which has no kernel path; lower k or "
            "rebuild with fewer lists", k, storage.max_list,
        )
        return ivf_sq_search(index, q, k, n_probes=n_probes)
    n_lists = storage.list_index.shape[0]
    from raft_tpu.spatial.ann.common import resolve_qcap_arg
    from raft_tpu.spatial.ann.ivf_flat import _grouped_impl

    qcap, probes = resolve_qcap_arg(
        qcap, q, index.centroids, n_lists, n_probes,
        max_drop_frac=qcap_max_drop_frac,
    )
    list_block = max(1, min(list_block, n_lists))
    use_pallas = _resolve_sq_engine(
        use_pallas, index.centroids.shape[1], qcap
    )
    return _grouped_impl(
        _flat_view(index), q, k, n_probes, qcap, list_block,
        probes=probes, stream_partials=stream_partials,
        use_pallas=use_pallas,
        pallas_interpret=jax.default_backend() != "tpu",
        rerank_ratio=float(rerank_ratio),
        dequant=(jnp.asarray(index.vmin, jnp.float32),
                 jnp.asarray(index.vscale, jnp.float32)),
    )


def ivf_sq_search(
    index: IVFSQIndex, queries, k: int, *, n_probes: int = 8,
    block_q: int = 512, use_pallas: typing.Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Per-query IVF-SQ search (dequantization fused into candidate
    scoring). The Pallas int8 dequant+scan engine lives in the GROUPED
    search (:func:`ivf_sq_search_grouped` — the kernel scans whole
    list slabs, which the per-query candidate gather never forms), so
    ``use_pallas`` here exists only to fail LOUDLY: ``True`` raises
    pointing at the grouped entry instead of silently serving the
    gather-bound path; ``None``/``False`` run the XLA path."""
    errors.expects(
        not use_pallas,
        "use_pallas=True: the per-query SQ search has no kernel path — "
        "the int8 dequant+scan engine (spatial/ann/sq_kernel) scans "
        "whole list slabs, which only the list-major grouped search "
        "forms; use ivf_sq_search_grouped(use_pallas=True)",
    )
    return _sq_search_impl(index, queries, k, n_probes=n_probes,
                           block_q=block_q)


@functools.partial(jax.jit, static_argnames=("k", "n_probes", "block_q"))
def _sq_search_impl(
    index: IVFSQIndex, queries, k: int, *, n_probes: int = 8,
    block_q: int = 512,
) -> Tuple[jax.Array, jax.Array]:
    from raft_tpu.spatial.ann.common import (
        check_candidate_pool, coarse_probe, map_query_blocks,
        score_l2_candidates, select_candidates,
    )

    q = jnp.asarray(queries)
    check_candidate_pool(k, n_probes, index.storage)

    def one_block(qb):
        qf = qb.astype(jnp.float32)
        probes, _ = coarse_probe(qf, index.centroids, n_probes)
        cand_pos = index.storage.list_index[probes].reshape(qb.shape[0], -1)
        codes = index.codes_sorted[cand_pos].astype(jnp.float32)
        # dequantization fused into candidate scoring
        cand = sq_decode(codes, index.vmin, index.vscale)
        d2 = score_l2_candidates(qf, cand, cand_pos < index.storage.n)
        return select_candidates(index.storage, cand_pos, d2, k)

    return map_query_blocks(one_block, q, block_q)
