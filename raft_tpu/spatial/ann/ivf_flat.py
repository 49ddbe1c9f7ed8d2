"""IVF-Flat ANN index — first-class TPU implementation (the reference wraps
FAISS GpuIndexIVFFlat, cpp/include/raft/spatial/knn/detail/
ann_quantized_faiss.cuh:115-206 ``approx_knn_build_index``/``approx_knn_search``
with ``IVFFlatParam`` ann_common.h; here native, per the north star).

Build: k-means coarse quantizer → vectors permuted into contiguous lists
(:mod:`common`). Search: (1) one MXU gram scores queries × centroids,
(2) top-nprobe lists per query, (3) rectangular gather of the padded probed
lists, (4) batched MXU distance on the candidates, (5) ``lax.top_k``.
Everything static-shape; sentinel slots score +inf.
"""

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
from raft_tpu.cluster.kmeans import KMeansParams, kmeans_fit
from raft_tpu.spatial.ann.common import (
    ListStorage,
    build_list_storage,
    place_row_store,
    set_row_store_gauge,
    split_oversized_lists,
)

__all__ = [
    "GROUPED_SCOPES",
    "IVFFlatParams",
    "IVFFlatIndex",
    "ivf_flat_build",
    "ivf_flat_search",
    "ivf_flat_search_grouped",
]


# the named parts of the grouped search program (``jax.named_scope``
# names: each device op's metadata carries its part, so a profile can sum
# device time per part; docs/observability.md "Spans and scopes")
SCOPE_PROBE = "ivf.probe"            # coarse probe, probe-map inversion
SCOPE_LIST_SLABS = "ivf.list_slabs"  # per-list slabs, query gather + pad
SCOPE_SCAN = "ivf.scan"              # the scan kernel or XLA tile math
SCOPE_MERGE = "ivf.merge"            # regroup, top-c, rerank, final top-k
GROUPED_SCOPES = (SCOPE_PROBE, SCOPE_LIST_SLABS, SCOPE_SCAN, SCOPE_MERGE)


@dataclasses.dataclass(frozen=True)
class IVFFlatParams:
    """Analog of IVFFlatParam (reference ann_common.h: nlist, nprobe)."""

    n_lists: int = 64
    kmeans_n_iters: int = 20
    seed: int = 0
    kmeans_init: str = "k-means++"  # "random": cheap coarse quantizer
    # Longest allowed inverted list — grouped-search compute scales with
    # n_lists * max_list, so one swollen list taxes every list block
    # (common.split_oversized_lists; measured +54% QPS on the PQ bench
    # config). None/0 = off.
    max_list_cap: typing.Optional[int] = None


@compat.register_dataclass
@dataclasses.dataclass
class IVFFlatIndex:
    centroids: jax.Array      # (n_lists, d)
    data_sorted: jax.Array    # (n + 1, d) — last row is the sentinel (zeros)
    storage: ListStorage
    metric: str = dataclasses.field(metadata=dict(static=True))

    def warmup(self, nq: int, *, k: int = 10, n_probes: int = 8,
               qcap=None, list_block: int = 32,
               stream_partials=None,
               use_pallas: typing.Optional[bool] = None,
               rerank_ratio: float = 4.0, audit: bool = False) -> int:
        """Pre-compile the grouped serving program for (nq, d) float32
        batches: one all-zeros batch is dispatched through the exact
        serving entry and blocked on, populating the in-process jit cache
        AND (when :func:`raft_tpu.core.enable_compilation_cache` is on)
        the persistent compilation cache — so the first real query batch
        pays dispatch, not trace+compile (docs/serving.md).

        ``qcap`` resolves SHAPE-ONLY (:func:`...ann.common.static_qcap`:
        ``None`` -> the 2x-mean default, ``"throughput"`` -> the 0.75x-mean
        throughput cap, an int as-is) and the resolved value is returned:
        pass exactly that integer on every serving dispatch — the warmed
        program is keyed on it, and the data-dependent ``qcap=None`` auto
        path would both host-sync and possibly compile a second program.

        ``audit=True`` additionally traces the warmed program through the
        jaxpr-level program auditor (:mod:`raft_tpu.analysis.program`;
        docs/static_analysis.md "Two tiers") and raises listing the
        findings if it violates the serving-tier invariants — the
        in-process spot check of the CI gate ``ci/run.sh programs``.

        Sets the ``ivf_row_store_row_major`` gauge (``engine="flat"``) to
        1 when the warmed program takes the row store row-major, as
        :func:`...common.place_row_store` leaves it at build.
        """
        from raft_tpu.spatial.ann.common import static_qcap

        set_row_store_gauge("flat", self.data_sorted)
        qc = static_qcap(qcap, nq, n_probes, self.centroids.shape[0])
        q0 = jnp.zeros((nq, self.centroids.shape[1]), jnp.float32)
        out = ivf_flat_search_grouped(
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

            # the wrapper's own engine resolution — the audited statics
            # must be the warmed program's statics
            up = _resolve_scan_engine(
                use_pallas, self.centroids.shape[1], qc
            )
            audit_warmed(trace_flat_grouped(
                self, nq, k, n_probes, qc, list_block=list_block,
                use_pallas=up, rerank_ratio=rerank_ratio,
                name="ivf_flat_grouped_warm",
            ))
        return qc


def ivf_flat_build(x, params: IVFFlatParams = IVFFlatParams(), *,
                   metric: str = "l2") -> IVFFlatIndex:
    """Build (reference approx_knn_build_index:115 — FAISS train+add;
    here kmeans + list permutation)."""
    x = jnp.asarray(x)
    errors.check_matrix(x, "x", min_rows=2)
    errors.check_k(params.n_lists, x.shape[0], "n_lists vs dataset rows")
    out = kmeans_fit(
        x,
        KMeansParams(
            n_clusters=params.n_lists,
            max_iter=params.kmeans_n_iters,
            seed=params.seed,
            init=params.kmeans_init,
            # quantizer training tolerates bf16-rounded centroid updates
            # (cluster averaging washes out operand rounding)
            compute_dtype="bfloat16",
        ),
    )
    labels_np, cents = np.asarray(out.labels), out.centroids
    if params.max_list_cap:
        labels_np, cents = split_oversized_lists(
            labels_np, cents, params.max_list_cap
        )
    storage = build_list_storage(labels_np, cents.shape[0])
    data_sorted = place_row_store(jnp.concatenate(
        [x[storage.sorted_ids], jnp.zeros((1, x.shape[1]), x.dtype)]
    ))
    return IVFFlatIndex(cents, data_sorted, storage, metric)


@functools.partial(jax.jit, static_argnames=("k", "n_probes", "block_q"))
def ivf_flat_search(
    index: IVFFlatIndex, queries, k: int, *, n_probes: int = 8,
    block_q: int = 512,
) -> Tuple[jax.Array, jax.Array]:
    """Search (reference approx_knn_search:169). Returns (dists, ids) with
    original row ids; L2 metric family (squared distances like FAISS's
    default compute, sqrt applied for metric='l2'). Query batches are
    processed in ``block_q`` blocks to bound the candidate-gather HBM."""
    from raft_tpu.spatial.ann.common import (
        check_candidate_pool, coarse_probe, map_query_blocks,
        score_l2_candidates, select_candidates,
    )

    q = jnp.asarray(queries)
    errors.check_matrix(q, "queries")
    errors.check_same_cols(q, index.centroids, "queries", "index")
    check_candidate_pool(k, n_probes, index.storage)

    def one_block(qb):
        qf = qb.astype(jnp.float32)
        probes, _ = coarse_probe(qf, index.centroids, n_probes)
        cand_pos = index.storage.list_index[probes].reshape(qb.shape[0], -1)
        cand_vecs = index.data_sorted[cand_pos].astype(jnp.float32)
        d2 = score_l2_candidates(qf, cand_vecs, cand_pos < index.storage.n)
        return select_candidates(index.storage, cand_pos, d2, k)

    vals, ids = map_query_blocks(one_block, q, block_q)
    if index.metric == "l2":
        vals = jnp.sqrt(jnp.maximum(vals, 0.0))
    return vals, ids


def _resolve_scan_engine(use_pallas, d: int, qcap: int) -> bool:
    """Resolve the ``use_pallas`` knob of the grouped flat searches to a
    concrete engine choice (a trace-time static) — the flat sibling of
    :func:`raft_tpu.spatial.ann.ivf_pq._resolve_adc_engine`.

    ``None`` (auto): the Pallas flat-scan engine (spatial/ann/
    flat_kernel) on a TPU backend whenever the config fits the kernel's
    VMEM plan; the XLA scan otherwise — so ``JAX_PLATFORMS=cpu`` never
    imports, let alone compiles, the kernel unless a caller opts in
    explicitly. ``True`` validates the requirements and raises with the
    reason when they do not hold (explicit opt-in must not silently fall
    back). Unlike the PQ resolver there is no refine precondition: the
    flat index always stores its raw rows, so the kernel path's exact
    f32 rerank tail is always available."""
    if use_pallas is None:
        if jax.default_backend() != "tpu":
            return False
        from raft_tpu.spatial.ann import scan_core
        from raft_tpu.spatial.ann.flat_kernel import flat_scan_supported

        return scan_core.auto_kernel(
            flat_scan_supported(d, qcap), "ivf_flat", f"d={d} qcap={qcap}"
        )
    if use_pallas:
        from raft_tpu.spatial.ann.flat_kernel import flat_scan_supported

        errors.expects(
            flat_scan_supported(d, qcap),
            "use_pallas=True unsupported at d=%d qcap=%d (one query "
            "block + slab tile exceeds the kernel's VMEM plan); use the "
            "XLA scan (use_pallas=False)", d, qcap,
        )
    return bool(use_pallas)


# rerank-pool gather budget per lax.map block on the Pallas path: the
# (blk_q, c*8, d) raw-row gather stays under this regardless of nq
_RERANK_BLOCK_BYTES = 256 << 20


@functools.partial(
    jax.jit,
    static_argnames=("k", "n_probes", "qcap", "list_block",
                     "stream_partials", "use_pallas", "pallas_interpret",
                     "rerank_ratio"),
)
def _grouped_impl(index, q, k, n_probes, qcap, list_block, probes=None,
                  stream_partials=None, row_mask=None, use_pallas=False,
                  pallas_interpret=False, rerank_ratio=4.0,
                  dequant=None):
    # ``row_mask``: optional (n + 1,) RUNTIME live mask over slab
    # positions (the tombstone-deletion input of the mutation tier,
    # raft_tpu/spatial/ann/mutation.py — the shard_mask trick applied to
    # rows). 0 = tombstoned: the row scores +inf and can never surface.
    # A runtime input, so tombstone flips never recompile. On the Pallas
    # path it is applied per ROW at the exact rerank tail (the in-kernel
    # sub-chunk minima are unmasked — a dead row can crowd a pool slot,
    # never surface; the PQ precedent, docs/mutation.md).
    #
    # ``dequant``: optional ``(vmin, vscale)`` (d,) runtime pair — the
    # IVF-SQ mode of the ONE grouped scan body (ISSUE 11):
    # ``index.data_sorted`` then holds int8 QT_8bit codes and every row
    # the scan or the rerank tail touches is mapped through
    # ``y = (code + 128) · vscale + vmin`` first. The XLA path
    # dequantizes the gathered slab block (the lax fallback — it pays
    # the f32 expansion in HBM); the kernel path routes through the
    # int8 in-kernel engine (spatial/ann/sq_kernel), where the slab
    # crosses HBM at one byte per element and expands only in VMEM.
    storage = index.storage
    n_lists = storage.list_index.shape[0]
    L = storage.max_list
    nq, d = q.shape
    p = n_probes
    f32 = jnp.float32

    def dq_rows(rows_f32):
        """Affine-dequantize gathered/sliced rows when the scan runs in
        SQ mode (no-op for the flat engine) — the XLA/rerank side runs
        through THE shared decoder (ivf_sq.sq_decode)."""
        if dequant is None:
            return rows_f32
        from raft_tpu.spatial.ann.ivf_sq import sq_decode

        return sq_decode(rows_f32, dequant[0], dequant[1])

    from raft_tpu.spatial.ann.common import (
        coarse_probe, invert_probe_map_ranked,
    )

    with jax.named_scope(SCOPE_PROBE):
        qf = q.astype(f32)
        if probes is None:
            probes, _ = coarse_probe(qf, index.centroids, p)  # (nq, p)
        # invert the probe map: for each list, the (padded) set of
        # queries probing it (shared grouped-search machinery, common.py)
        qmat, rmat, l_flat, slot = invert_probe_map_ranked(
            probes, n_lists, qcap
        )

    with jax.named_scope(SCOPE_LIST_SLABS):
        q_pad = jnp.concatenate([qf, jnp.zeros((1, d), f32)])  # sentinel
        qn_pad = jnp.concatenate(
            [jnp.sum(qf * qf, axis=1), jnp.zeros((1,), f32)]
        )

    def block_fn(lblk):                                      # (LB,) list ids
        with jax.named_scope(SCOPE_LIST_SLABS):
            qids = qmat[lblk]                                # (LB, qcap)
            qv = q_pad[qids]                                 # (LB, qcap, d)
            qnv = qn_pad[qids]                               # (LB, qcap)
            # lists are CONTIGUOUS in sorted storage: read each as one
            # dynamic_slice slab instead of row-granular list_index
            # gathers (d*4-byte rows measured ~50x slower at 10M-scale
            # shapes)
            offs = storage.list_offsets[lblk]                # (LB,)
            szs = storage.list_sizes[lblk]
            o_c = jnp.minimum(offs, storage.n + 1 - L)       # slice clamp
            mv = dq_rows(jax.vmap(
                lambda s: lax.dynamic_slice(index.data_sorted, (s, 0),
                                            (L, d))
            )(o_c).astype(f32))                              # (LB, L, d)
            pos = o_c[:, None] + jnp.arange(L, dtype=jnp.int32)[None, :]
            in_list = (pos >= offs[:, None]) & (pos < (offs + szs)[:, None])
            if row_mask is not None:
                in_list = in_list & (row_mask[pos] > 0)
        with jax.named_scope(SCOPE_SCAN):
            mn = jnp.sum(mv * mv, axis=2)                    # (LB, L)
            dots = jnp.einsum(
                "bqd,bld->bql", qv, mv, preferred_element_type=f32,
                precision=lax.Precision.HIGHEST,
            )  # MXU batched; HIGHEST keeps f32 operands un-rounded so
            #    grouped scores match the per-query path bit-for-near
            #    (measured: DEFAULT rounds operands and perturbs ~1e-3 of
            #    neighbor orderings)
            d2 = qnv[:, :, None] + mn[:, None, :] - 2.0 * dots
            invalid = (qids >= nq)[:, :, None] | (~in_list)[:, None, :]
            d2 = jnp.where(invalid, jnp.inf, d2)
            # the INTENTIONAL legacy materialized-tile scan, kept as the
            # use_pallas=False bit-stable engine and the CPU fallback —
            # the Pallas sub-chunk-min path above it is the fixed
            # spelling (docs/static_analysis.md "Baseline burn-down"):
            vals, sel = lax.top_k(-d2, k)  # jaxlint: disable=wide-distance-materialize
            # k-wide selection remap, not a LUT gather:
            memp = jnp.take_along_axis(  # jaxlint: disable=adc-gather
                jnp.broadcast_to(pos[:, None, :], d2.shape), sel, axis=2
            )
            return -vals, memp

    use_kernel = bool(use_pallas)
    if use_kernel:
        from raft_tpu.spatial.ann import scan_core

        if dequant is None:
            from raft_tpu.spatial.ann import flat_kernel as kmod
        else:
            # the SQ mode of the one grouped body: int8 slabs DMA'd to
            # VMEM at one byte per element, dequantized there (the
            # sq_kernel module docstring carries the full argument)
            from raft_tpu.spatial.ann import sq_kernel as kmod

        sub = scan_core.SUBCHUNK
        # the SAME rounding + profile the engine's *_supported predicate
        # validated the VMEM plan with, so the resolver's approval and
        # this plan cannot drift. tile_profile(qcap) auto-selects the
        # latency plan (1024-row start) for the qcap-1/8 open-loop
        # serving shapes — the p99 regime stops paying throughput-shape
        # tiles (docs/ivf_scale.md "One scan-kernel core").
        q_kpad = scan_core.pad_queries(qcap)
        # cap the plan at the list slab's own (lane-rounded) height: a
        # wide profile start must never widen the per-list window past
        # max_list — that would double slab DMA + masked-garbage compute
        # on small-list indexes in exactly the latency regime the wide
        # start targets
        l_tile = kmod.plan_l_tile(
            d, q_kpad, l_tile=-(-L // scan_core.LANE) * scan_core.LANE,
            profile=scan_core.tile_profile(qcap),
        )
        l_pad = -(-L // l_tile) * l_tile
        nsc = l_pad // sub
        rows = index.data_sorted.shape[0]     # n + 1 (sentinel row)
        rows_pad = max(rows, l_pad)
        # tiny indexes whose whole slab is shorter than one padded list
        # window: extend the slab so the clamped dynamic_slice stays in
        # range (static condition — big indexes never pay the copy)
        with jax.named_scope(SCOPE_LIST_SLABS):
            data_src = (
                index.data_sorted if rows_pad == rows
                else jnp.pad(index.data_sorted,
                             ((0, rows_pad - rows), (0, 0)))
            )

        def block_fn_pallas(lblk):            # (LB,) list ids
            with jax.named_scope(SCOPE_LIST_SLABS):
                qids = qmat[lblk]                            # (LB, qcap)
                qv = q_pad[qids]                             # (LB, qcap, d)
                if q_kpad > qcap:
                    qv = jnp.pad(qv, ((0, 0), (0, q_kpad - qcap), (0, 0)))
                offs = storage.list_offsets[lblk]            # (LB,)
                szs = storage.list_sizes[lblk]
                o_c = jnp.minimum(offs, rows_pad - l_pad)    # slice clamp
                slabs_t = jax.vmap(
                    lambda s: lax.dynamic_slice(data_src, (s, 0),
                                                (l_pad, d))
                )(o_c).transpose(0, 2, 1)                    # (LB, d, l_pad)
                lo = offs - o_c
                bounds = jnp.stack([lo, lo + szs], axis=1)   # (LB, 2)
            with jax.named_scope(SCOPE_SCAN):
                if dequant is None:
                    mins = kmod.flat_scan_subchunk_min(
                        qv, slabs_t, bounds,
                        interpret=pallas_interpret, l_tile=l_tile,
                    )
                else:
                    mins = kmod.sq_scan_subchunk_min(
                        qv, slabs_t.astype(jnp.int8), bounds,
                        dequant[0], dequant[1],
                        interpret=pallas_interpret, l_tile=l_tile,
                    )
                # positions are NOT returned: a sub-chunk's slab base is
                # fully derivable from (probe slot, chunk index) after
                # selection, so the kernel path pools VALUES ONLY — half
                # the pool memory and scatter traffic of the legacy path
                return mins[:, :qcap]                        # (LB, qcap, nsc)

        width, scan_fn = nsc, block_fn_pallas
    else:
        width, scan_fn = k, block_fn

    # pad the list axis up to a multiple of list_block (clamped ids — the
    # padded slots recompute the last list; regroup never references
    # them, and the streamed scatter re-writes identical values) instead
    # of shrinking list_block, which collapses to 1-list blocks when
    # n_lists is prime-ish (e.g. after oversized-list splitting)
    nl_pad = -(-n_lists // list_block) * list_block
    if stream_partials is None:
        # auto: stream once materialized (n_lists, qcap, width) partials
        # pass ~2 GB (same skewed-qcap blow-up bound as the PQ grouped
        # search); the kernel path pools values only (no int32
        # positions), hence the smaller footprint
        per_entry = 4 if use_kernel else 8
        stream_partials = n_lists * qcap * width * per_entry > (1 << 31)

    # the list loop (and its bookkeeping) is the scan; each body's slab
    # work and streamed scatter name their own parts
    with jax.named_scope(SCOPE_SCAN):
        lids = jnp.minimum(
            jnp.arange(nl_pad, dtype=jnp.int32), n_lists - 1
        ).reshape(-1, list_block)
        if stream_partials:
            if use_kernel:
                def scan_body_v(pvc, lblk):
                    v = scan_fn(lblk)
                    with jax.named_scope(SCOPE_MERGE):
                        qi, ri = qmat[lblk], rmat[lblk]  # sentinels drop
                        return pvc.at[qi, ri].set(v, mode="drop"), None

                with jax.named_scope(SCOPE_MERGE):
                    init_v = jnp.full((nq, p, width), jnp.inf, jnp.float32)
                pv, _ = lax.scan(scan_body_v, init_v, lids)
            else:
                def scan_body(carry, lblk):
                    pvc, pmc = carry
                    v, mp = scan_fn(lblk)
                    with jax.named_scope(SCOPE_MERGE):
                        qi, ri = qmat[lblk], rmat[lblk]  # sentinels drop
                        pvc = pvc.at[qi, ri].set(v, mode="drop")
                        pmc = pmc.at[qi, ri].set(mp, mode="drop")
                        return (pvc, pmc), None

                with jax.named_scope(SCOPE_MERGE):
                    init = (
                        jnp.full((nq, p, k), jnp.inf, jnp.float32),
                        jnp.full((nq, p, k), storage.n, jnp.int32),
                    )
                (pv, pm), _ = lax.scan(scan_body, init, lids)
        elif use_kernel:
            vals = lax.map(scan_fn, lids)
        else:
            vals, mem = lax.map(scan_fn, lids)

    with jax.named_scope(SCOPE_MERGE):
        if stream_partials:
            pv = pv.reshape(nq, p * width)
            pm = None if use_kernel else pm.reshape(nq, p * k)
        elif use_kernel:
            vals = vals.reshape(nl_pad, qcap, width)[:n_lists]
            # values-only regroup (the slot inverse of regroup_pairs)
            ok = slot < qcap
            safe_slot = jnp.minimum(slot, qcap - 1)
            pv = jnp.where(
                ok[:, None], vals[l_flat, safe_slot], jnp.inf
            ).reshape(nq, p * width)
            pm = None
        else:
            vals = vals.reshape(nl_pad, qcap, k)[:n_lists]
            mem = mem.reshape(nl_pad, qcap, k)[:n_lists]

            # per-pair result gather (original query-major order), then
            # the final selection
            from raft_tpu.spatial.ann.common import regroup_pairs

            pv, pm = regroup_pairs(vals, mem, l_flat, slot, nq, p, qcap)

        if use_kernel:
            # kernel path: pool entries are SUB-CHUNK minima. Select the
            # top-c sub-chunks — the fused_knn chunk-cover argument at 8-row
            # granularity: every rank-c row lives in a sub-chunk whose
            # minimum is <= the c-th best scanned value, so the selected
            # sub-chunks' rows cover the top-c rows — then rescore their
            # rows with EXACT f32 at HIGHEST precision (the distance tile
            # never round-trips HBM; returned distances are exact). Clamp
            # to the pool width LAST: a large k (> p*width) must not ask
            # top_k for more sub-chunks than exist — the clamped pool still
            # covers k rows (c*8 = p*l_pad >= p*max_list >= k, the
            # check_candidate_pool precondition).
            from raft_tpu.spatial.ann.common import (
                map_query_blocks, score_l2_candidates, select_candidates,
            )

            c = min(p * width, max(k, int(math.ceil(rerank_ratio * k))))
            nv, cpos = lax.top_k(-pv, c)
            nadc = -nv                                           # (nq, c)
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
                & (jnp.isfinite(nadc)
                   & (nadc < scan_core.BIG))[:, :, None]
            )
            if row_mask is not None:
                # tombstones are applied per ROW at the rerank tail on the
                # kernel path (the in-kernel sub-chunk minima are unmasked)
                validf = validf & (
                    row_mask[jnp.clip(rows_sel, 0, storage.n)] > 0
                )
            validf = validf.reshape(nq, c * sub)
            rpos = rows_sel.reshape(nq, c * sub)

            def rerank_blk(args):
                qb, rp, vl = args
                raw = dq_rows(
                    data_src[jnp.clip(rp, 0, storage.n)].astype(f32)
                )
                exact = score_l2_candidates(qb, raw, vl & (rp < storage.n))
                return select_candidates(storage, rp, exact, k)

            # block the (blk_q, c*8, d) raw-row gather over queries so the
            # 8x-wider kernel-path pool never materializes a multi-GB
            # transient at serving batch sizes (zero-padded rows compute on
            # all-invalid candidates and are sliced away)
            blk_q = max(8, min(nq, _RERANK_BLOCK_BYTES // (c * sub * d * 4)))
            return map_query_blocks(rerank_blk, (qf, rpos, validf), blk_q)

        fvals, fpos = lax.top_k(-pv, k)
        fmem = jnp.take_along_axis(pm, fpos, axis=1)
        ids = storage.sorted_ids[jnp.clip(fmem, 0, storage.n - 1)]
        ids = jnp.where(jnp.isfinite(-fvals), ids, -1).astype(jnp.int32)
        return -fvals, ids


def ivf_flat_search_grouped(
    index: IVFFlatIndex, queries, k: int, *, n_probes: int = 8,
    qcap: typing.Union[int, str, None] = None, list_block: int = 32,
    stream_partials: typing.Optional[bool] = None,
    qcap_max_drop_frac: typing.Optional[float] = None,
    use_pallas: typing.Optional[bool] = None,
    rerank_ratio: float = 4.0,
) -> Tuple[jax.Array, jax.Array]:
    """Throughput-mode IVF search, grouped by LIST instead of by query —
    the query-side "sorted-by-list batching" (SURVEY.md §7 hard part №3).

    ``ivf_flat_search`` gathers each probing query's lists independently,
    so a list's vectors are re-read once per probing query — random gathers
    dominate at large batch and dense brute force wins. Here the probe map
    is inverted: one sweep over lists, each list's vectors loaded ONCE per
    batch and scored against all its (padded, ``qcap``-capped) probing
    queries with a batched MXU contraction; per-(list, query) top-k results
    are then redistributed pair-wise and reduced per query. Compute is
    ~n_probes/n_lists of brute force while traffic stays one dataset sweep.

    ``qcap`` caps queries per list (static shape); lists probed by more
    than ``qcap`` queries drop the overflow. Default (``qcap=None``):
    auto-sized from the actual probe map so at most 2% of (query, probe)
    pairs drop, with any residual drop logged — never silent
    (:func:`raft_tpu.spatial.ann.common.resolve_qcap`). The auto path
    costs one eager coarse probe + host sync per call, and a shifting
    query mix that crosses a qcap doubling boundary recompiles the
    grouped program — serving workloads that need fully-async dispatch
    should pass an explicit ``qcap`` (taken as-is) and audit it with
    :func:`raft_tpu.spatial.ann.common.probe_drop_stats`.
    ``qcap="throughput"`` picks ~0.75x the mean probe occupancy — see
    :func:`raft_tpu.spatial.ann.common.throughput_qcap` for when that
    trade is and is not safe.

    ``use_pallas`` selects the scan engine (docs/ivf_scale.md "Flat scan
    in VMEM"): ``None`` (auto) runs the Pallas sub-chunk-min kernel
    (spatial/ann/flat_kernel) on a TPU backend whenever the config fits
    its VMEM plan — the bf16 slab tiles then live only in VMEM, only
    (qcap, max_list/8) sub-chunk minima reach HBM, and the top-``c``
    sub-chunks' rows are rescored in exact f32 (HIGHEST) before the
    final selection, so returned distances stay exact. ``False`` pins
    the XLA scan (the CPU fallback — bit-stable with previous
    releases); ``True`` opts in explicitly (interpret mode off-TPU) and
    raises when the requirements do not hold. Returned candidates are
    value-exact between engines (the kernel's rerank pool covers the
    top-k by the sub-chunk cover argument at ``rerank_ratio`` margin);
    tied candidates may order differently, and distances agree to the
    last ulp (bitwise on integer-exact data — the tier-1 pin).
    ``rerank_ratio`` sizes the rerank pool (top ``ceil(rerank_ratio*k)``
    sub-chunks, clamped to the pool width); kernel path only.

    Exactness: with ``qcap`` large enough this returns exactly what
    ``ivf_flat_search`` returns for the same ``n_probes`` (tested).
    """
    q = jnp.asarray(queries)
    nq = q.shape[0]
    storage = index.storage
    if k > storage.max_list:
        # a single list cannot fill a per-list top-k row
        errors.expects(
            not use_pallas,
            "use_pallas=True: k=%d > max_list=%d routes to the per-query "
            "search, which has no kernel path; lower k or rebuild with "
            "fewer lists", k, storage.max_list,
        )
        return ivf_flat_search(index, q, k, n_probes=n_probes)
    check = k <= n_probes * storage.max_list
    if not check:
        raise ValueError("k exceeds candidate pool; raise n_probes")
    n_lists = storage.list_index.shape[0]
    from raft_tpu.spatial.ann.common import resolve_qcap_arg

    qcap, probes = resolve_qcap_arg(
        qcap, q, index.centroids, n_lists, n_probes,
        max_drop_frac=qcap_max_drop_frac,
    )
    list_block = max(1, min(list_block, n_lists))
    use_pallas = _resolve_scan_engine(
        use_pallas, index.centroids.shape[1], qcap
    )
    vals, ids = _grouped_impl(
        index, q, k, n_probes, qcap, list_block, probes=probes,
        stream_partials=stream_partials,
        use_pallas=use_pallas,
        pallas_interpret=jax.default_backend() != "tpu",
        rerank_ratio=float(rerank_ratio),
    )
    if index.metric == "l2":
        vals = jnp.sqrt(jnp.maximum(vals, 0.0))
    return vals, ids
