"""Fused distance + k-selection kNN — the TPU-native analog of the
reference's crown-jewel fused L2 kNN kernel
(cpp/include/raft/spatial/knn/detail/fused_l2_knn.cuh:196 ``fusedL2kNN``:
tiled distance + in-register warp-select in one kernel, never materializing
the m*n distance matrix).

TPU formulation — two phases, exact:

* **Phase 1 (Pallas, MXU+VPU)**: grid over (query-block, index-block)
  tiles; each step computes the L2 score tile ``||y||^2 - 2 x.y`` on the
  MXU and immediately min-reduces it over 128-column chunks in VMEM. Only
  the (m, n/128) chunk-min matrix is ever written to HBM — a 128x traffic
  reduction over the XLA path, whose ``top_k`` cannot fuse into the matmul
  and therefore round-trips every (m, bn) distance tile through HBM.
  This is the same memory behavior the reference buys with warp-select in
  registers.

* **Phase 2 (XLA)**: exact candidate cover. Every true top-k neighbor
  lives in a chunk whose minimum is <= the kth best distance, so the top-k
  chunks by minimum contain all true top-k columns (the
  ``chunk_min_select_k`` exactness argument). Gather those k*128 candidate
  columns per query, recompute exact f32 distances (k*128 << n work), and
  run the final top-k.

Phase 1 may run the gram in bf16 (2x MXU rate, half the index HBM
traffic); this only perturbs *chunk ranking* near ties — phase 2 rescoring
is always f32, so errors can only appear if a true top-k chunk falls out
of the top-k chunk-min list by a bf16-rounding margin. ``compute_dtype``
defaults to f32 for exactness; the bench exposes the bf16 variant
separately.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from raft_tpu.distance.distance_type import DistanceType

__all__ = ["fused_l2_knn", "fused_knn_supported", "KNN_SCOPES"]

# the named parts of the brute-force program (``jax.named_scope`` names:
# each device op's metadata carries its part, so a profile can sum device
# time per part; docs/observability.md "Spans and scopes")
SCOPE_PREPARE = "knn.prepare"        # row pad, casts, norms, query pads
SCOPE_CHUNK_MINS = "knn.chunk_mins"  # the phase-1 chunk-min kernel
SCOPE_SELECT = "knn.select"          # top-c chunks by their minima
SCOPE_RESCORE = "knn.rescore"        # DMA or gather rescore, final top-k
KNN_SCOPES = (SCOPE_PREPARE, SCOPE_CHUNK_MINS, SCOPE_SELECT, SCOPE_RESCORE)

_CHUNK = 128  # lane width: one chunk-min per vreg row per reduce


def _cdiv(a, b):
    return -(-a // b)


def _round_up(a, b):
    return _cdiv(a, b) * b


def _chunkmin_kernel(y_ref, qt_ref, o_ref, *, nc, n_valid, compute_dtype):
    """One (bn, bm) transposed score tile -> (bn/128, bm) chunk minima.

    y_ref (bn, d) index rows; qt_ref (d, bm) feature-major queries so the
    gram is a natural MXU contraction; o_ref (nc, bm).
    The tile is computed transposed — scores (bn, bm) — so the 128-column
    chunk reduction runs over *sublanes* (cheap VPU shape) and the output
    keeps queries on the 128-aligned lane axis.
    The index tile is cast to ``compute_dtype`` here, in VMEM: a cast
    in XLA writes a converted copy of the whole index to HBM (12.5M x 96
    bf16 -> f32 is 6.4 GB with lane padding). The row norms are computed
    here from the tile too: an (n, 1) f32 norms operand is laid out 128
    lanes wide in HBM (another 6.4 GB at 12.5M rows; a v5e compile of
    the kernel that took one showed 9.6 GB of temporaries).
    Rows at or past ``n_valid`` (the padding) score BIG, so they never
    win a chunk.
    Scores drop the per-query ||x||^2 term — constant within a query, so
    chunk *ranking* (all phase 1 is for) is unchanged.
    """
    y = y_ref[:]
    g = jnp.dot(y.astype(compute_dtype), qt_ref[:],
                preferred_element_type=jnp.float32)
    yf = y.astype(jnp.float32)
    bn, bm = g.shape
    row = pl.program_id(1) * bn + lax.broadcasted_iota(jnp.int32, (bn, 1), 0)
    yn = jnp.where(row < n_valid, jnp.sum(yf * yf, axis=1, keepdims=True),
                   jnp.float32(1e30))
    scores = yn - 2.0 * g  # (bn, bm) MXU
    o_ref[:, :] = jnp.min(scores.reshape(nc, _CHUNK, bm), axis=1)


@functools.partial(
    jax.jit,
    static_argnames=("n_valid", "bm", "bn", "compute_dtype", "interpret"),
)
def _chunk_mins(q, yp, *, n_valid, bm, bn, compute_dtype, interpret):
    """Phase 1 driver: (m, d) x (npad, d) -> (m, npad/128) chunk minima;
    rows at or past ``n_valid`` are padding."""
    m, d = q.shape
    npad = yp.shape[0]
    mp = _round_up(m, bm)
    nc_tile = bn // _CHUNK

    qtp = jnp.pad(q, ((0, mp - m), (0, 0))).T.astype(compute_dtype)

    kernel = functools.partial(_chunkmin_kernel, nc=nc_tile, n_valid=n_valid,
                               compute_dtype=compute_dtype)
    out = pl.pallas_call(
        kernel,
        grid=(mp // bm, npad // bn),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
            pl.BlockSpec((d, bm), lambda i, j: (0, i)),
        ],
        out_specs=pl.BlockSpec((nc_tile, bm), lambda i, j: (j, i)),
        out_shape=jax.ShapeDtypeStruct((npad // _CHUNK, mp), jnp.float32),
        interpret=interpret,
        name="fused_knn_chunk_mins",
    )(yp, qtp)
    return out[:, :m].T


_QBLK = 8  # phase-2 query rows per VMEM block (sublane granule)


def _rescore_dma_kernel(cids_ref, q_ref, y_hbm, o_ref, slabs, sems,
                        *, c, grp):
    """Phase-2 scores for ONE query: grid (m,), per-step double-buffered
    groups of ``grp`` candidate-chunk DMAs from HBM picked by the
    prefetched chunk ids; VPU computes ``sum(slab * (slab - 2 q))`` =
    ||y||^2 - 2 x.y per candidate row (the per-query ||x||^2 constant is
    added by the caller). This is the gather the reference gets from
    coalesced global loads in its fused kernel: each DMA is one 128-row
    contiguous slab straight out of the index's native layout — no
    relayout copy of a multi-GB index ever exists (the XLA gather
    fallback below measured ~49 GB/s on 196 KB slabs; this kernel
    measured ~504 GB/s at the 3M x 768 bf16 shape)."""
    i = pl.program_id(0)
    ngroups = c // grp

    def copy_l(slot, g, l):
        cid = cids_ref[i, g * grp + l]
        return pltpu.make_async_copy(
            y_hbm.at[pl.ds(cid * _CHUNK, _CHUNK), :],
            slabs.at[pl.ds((slot * grp + l) * _CHUNK, _CHUNK), :],
            sems.at[slot, l],
        )

    def start_group(slot, g):
        for l in range(grp):
            copy_l(slot, g, l).start()

    def wait_group(slot, g):
        for l in range(grp):
            copy_l(slot, g, l).wait()

    start_group(0, 0)
    q = q_ref[pl.ds(lax.rem(i, _QBLK), 1), :].astype(jnp.float32)  # (1, d)

    def body(g, _):
        slot = lax.rem(g, 2)

        @pl.when(g + 1 < ngroups)
        def _():
            start_group(lax.rem(g + 1, 2), g + 1)

        wait_group(slot, g)
        blk = slabs[
            pl.ds(slot * grp * _CHUNK, grp * _CHUNK), :
        ].astype(jnp.float32)
        o_ref[pl.ds(g * grp * _CHUNK, grp * _CHUNK)] = jnp.sum(
            blk * (blk - 2.0 * q), axis=1
        )
        return 0

    lax.fori_loop(0, ngroups, body, 0)


def _rescore_group_size(d: int, itemsize: int) -> int:
    """Chunks per DMA group: largest power of two <= 8 whose
    double-buffered slab scratch (2 * grp * 128 * d * itemsize) stays
    within ~8 MiB of VMEM (wide-d safety; grp must divide the padded
    candidate count, which is a multiple of 8)."""
    grp = 8
    while grp > 1 and 2 * grp * _CHUNK * d * itemsize > 8 * 2**20:
        grp //= 2
    return grp


def _rescore_scores(q, cids, yp, *, c, interpret):
    """(m, c) candidate chunk ids -> (m, c*128) f32 scores
    ``||y||^2 - 2 x.y`` via the manual-DMA kernel. m and c must be
    multiples of _QBLK / 8 respectively (caller pads)."""
    m, d = q.shape
    grp = _rescore_group_size(d, yp.dtype.itemsize)
    kern = functools.partial(_rescore_dma_kernel, c=c, grp=grp)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(m,),
            in_specs=[
                pl.BlockSpec((_QBLK, d), lambda i, cr: (i // _QBLK, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((c * _CHUNK,), lambda i, cr: (i,)),
            scratch_shapes=[
                pltpu.VMEM((2 * grp * _CHUNK, d), yp.dtype),
                pltpu.SemaphoreType.DMA((2, grp)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((m * c * _CHUNK,), jnp.float32),
        interpret=interpret,
    )(cids, q, yp)
    return out.reshape(m, c * _CHUNK)


@functools.partial(
    jax.jit,
    static_argnames=("k", "metric", "bm", "bn", "bq2", "extra_chunks",
                     "compute_dtype", "interpret", "gather_rows",
                     "rescore_rows"),
)
def _fused_l2_knn_impl(
    queries,
    index,
    k: int,
    metric: DistanceType,
    *,
    bm: int,
    bn: int,
    bq2: int,
    extra_chunks: int,
    compute_dtype,
    interpret: bool,
    gather_rows=None,
    index_norms=None,
    rescore_rows: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    m, d = queries.shape
    n = index.shape[0]
    npad = _round_up(n, bn)
    # Padded rows score +BIG in phase 1 (never win a chunk) and +BIG in
    # phase 2 rescoring (never selected); BIG is finite to keep inf-inf
    # NaNs out of the VPU.
    BIG = jnp.float32(1e30)
    with jax.named_scope(SCOPE_PREPARE):
        q = jnp.asarray(queries, jnp.float32)
        # The index keeps its storage dtype (bf16 storage halves HBM for
        # the 10M x 768 regime — no f32 copy is ever materialized;
        # accumulations below are f32 via preferred_element_type).
        y = jnp.asarray(index)
        # trace-level skip when already aligned: a zero-width jnp.pad of a
        # multi-GB index is not reliably elided and would copy it (fatal
        # for the HBM-resident big-index regime)
        yp = y if npad == n else jnp.pad(y, ((0, npad - n), (0, 0)))

    with jax.named_scope(SCOPE_CHUNK_MINS):
        cmins = _chunk_mins(
            q, yp, n_valid=n, bm=bm, bn=bn,
            compute_dtype=compute_dtype, interpret=interpret,
        )  # (m, nC)

    # phase 2: top-c chunks per query -> gather WHOLE chunks -> exact rescore.
    # c = k + extra_chunks: with exact arithmetic the top-k chunks suffice
    # (exact cover), but phase-1 f32 expanded-form rounding can flip chunk
    # ranks near the boundary; the margin makes a miss require a true chunk
    # to be outranked by `extra_chunks` spurious ones, far beyond the
    # rounding scale.
    nC = cmins.shape[1]
    c = min(nC, k + extra_chunks)

    # Preferred rescore: the manual-DMA Pallas kernel — gathers each
    # candidate chunk as one contiguous 128-row slab directly from the
    # index's native layout (no relayout copy, ~10x the XLA gather; see
    # _rescore_dma_kernel). Requires the padded candidate count to be a
    # multiple of 8 (1-D output tiling); query batches beyond the
    # per-call SMEM bound tile into <= rescore_rows-row kernel calls so
    # the throughput case (big m) keeps the DMA path. `gather_rows`
    # explicitly pins the XLA fallback variants (exercised by tests).
    cpad = _round_up(c, 8)
    mp8 = _round_up(m, _QBLK)
    # per-call tile bound: the scalar-prefetch SMEM footprint — the
    # prefetched (rows, cpad) chunk-id operand costs
    # round_up(cpad, 128)*4 bytes/row of the ~1 MiB SMEM (measured:
    # 2000 rows compile at cpad=24, 2048 do not); budget 3/4 MiB to
    # leave slack for Mosaic's own SMEM. `rescore_rows` lowers it
    # (tests force the tiled path at small m).
    smem_rows = (768 * 1024) // (_round_up(cpad, 128) * 4)
    if rescore_rows is not None:
        smem_rows = min(smem_rows, rescore_rows)
    use_dma = (
        gather_rows is None
        and cpad <= nC
        # Mosaic slab slices must be lane-aligned: narrower / ragged
        # feature dims take the XLA gather fallback (small-d regime,
        # where the chunk-major gather is cheap anyway)
        and d % _CHUNK == 0
        # the SMEM bound cannot hold even one _QBLK-row tile (very
        # large cpad): take the XLA gather path rather than clamping the
        # tile past the bound, which recreates the scalar-prefetch
        # compile failure the tiling exists to avoid
        and smem_rows >= _QBLK
    )
    if use_dma:
        with jax.named_scope(SCOPE_SELECT):
            _, cids = lax.top_k(-cmins, cpad)           # (m, cpad)
            cpds = (cids if mp8 == m
                    else jnp.pad(cids, ((0, mp8 - m), (0, 0))))
            cpds = cpds.astype(jnp.int32)
        with jax.named_scope(SCOPE_PREPARE):
            qpad = q if mp8 == m else jnp.pad(q, ((0, mp8 - m), (0, 0)))
            qn = jnp.sum(q * q, axis=-1)
        with jax.named_scope(SCOPE_RESCORE):
            blk = smem_rows // _QBLK * _QBLK
            if mp8 <= blk:
                scores = _rescore_scores(
                    qpad, cpds, yp, c=cpad, interpret=interpret
                )[:m]
            else:
                # batches past the per-call budget run the SAME kernel
                # via lax.map over uniform blk-row tiles: one compiled
                # program regardless of m (an unrolled Python loop would
                # emit one pallas_call per tile and blow up the HLO at
                # large m)
                tiles = _cdiv(mp8, blk)
                pad2 = tiles * blk - mp8
                qt = jnp.pad(qpad, ((0, pad2), (0, 0))).reshape(
                    tiles, blk, d)
                ct = jnp.pad(cpds, ((0, pad2), (0, 0))).reshape(
                    tiles, blk, cpad
                )
                scores = jax.lax.map(
                    lambda t: _rescore_scores(
                        t[0], t[1], yp, c=cpad, interpret=interpret
                    ),
                    (qt, ct),
                ).reshape(tiles * blk, cpad * _CHUNK)[:m]  # (m, cpad*128)
            d2 = qn[:, None] + scores
            col = (cids[:, :, None] * _CHUNK
                   + jnp.arange(_CHUNK)[None, None, :]
                   ).reshape(m, cpad * _CHUNK)
            d2 = jnp.where(col >= n, BIG, d2)
            negv, pos = lax.top_k(-d2, k)
            vals = -negv
            idxs = jnp.take_along_axis(col, pos, axis=1)
            vals = jnp.maximum(vals, 0.0)
            if metric == DistanceType.L2SqrtExpanded:
                vals = jnp.sqrt(vals)
            return vals, idxs.astype(jnp.int32)

    # XLA gather fallback (interpret-pinned variants, tiny chunk counts).
    # Gather granularity matters: one chunk = 128 contiguous index rows
    # (a 64 KB row after the reshape below), which is the efficient TPU
    # gather regime — per-row gathers of the same candidates measured ~7x
    # slower.
    with jax.named_scope(SCOPE_SELECT):
        _, cids = lax.top_k(-cmins, c)                  # (m, c)

    # Chunk-granular gather ((nC, 128*d) reshape) is the fast path — one
    # 64 KB contiguous row per candidate chunk, measured ~7x per-row
    # gathers. But the reshape RELAYOUTS the whole index (a full copy):
    # fatal when the index is HBM-resident at the multi-GB scale, so big
    # indexes gather 128 rows per chunk from the original layout instead.
    big_index = (
        gather_rows
        if gather_rows is not None
        else npad * d * y.dtype.itemsize > (2 << 30)
    )
    mp2 = _round_up(m, bq2)
    with jax.named_scope(SCOPE_PREPARE):
        if not big_index:
            ychunks = yp.reshape(nC, _CHUNK * d)
        # caller-precomputed norms skip a full index read here — the
        # analog of the reference storing norms with the index
        # (knn_brute_force_faiss.cuh:318-330 norms argument)
        yn = (
            jnp.asarray(index_norms, jnp.float32)
            if index_norms is not None
            else jnp.einsum("nd,nd->n", y, y,
                            preferred_element_type=jnp.float32)
        )
        ynp = (yn if npad == n
               else jnp.pad(yn, (0, npad - n), constant_values=BIG))
        ynchunks = ynp.reshape(nC, _CHUNK)

        qn = jnp.sum(q * q, axis=-1)
        qb = jnp.pad(q, ((0, mp2 - m), (0, 0))).reshape(mp2 // bq2, bq2, d)
        qnb = jnp.pad(qn, (0, mp2 - m)).reshape(mp2 // bq2, bq2)
    with jax.named_scope(SCOPE_SELECT):
        cb = jnp.pad(cids, ((0, mp2 - m), (0, 0))).reshape(
            mp2 // bq2, bq2, c)

    def rescore(args):
        qblk, qnblk, cblk = args                   # (bq2, d), (bq2,), (bq2, c)
        flat = cblk.reshape(-1)
        if big_index:
            rows = (
                flat[:, None] * _CHUNK + jnp.arange(_CHUNK)[None, :]
            ).reshape(-1)                          # (bq2*c*128,)
            yv = jnp.take(yp, rows, axis=0).reshape(bq2, c * _CHUNK, d)
        else:
            yv = jnp.take(ychunks, flat, axis=0).reshape(bq2, c * _CHUNK, d)
        ynv = jnp.take(ynchunks, flat, axis=0).reshape(bq2, c * _CHUNK)
        # In the opted-in bf16 compute mode with bf16 storage, feed the
        # dot bf16 query operands (f32 accumulate) so XLA cannot
        # materialize an f32 upcast of the gathered block; the ~0.4%
        # query-side rounding is within that mode's contract. f32 compute
        # keeps full-precision queries (phase-2 exactness argument).
        bf16_mode = (
            jnp.dtype(compute_dtype) == jnp.bfloat16
            and y.dtype == jnp.bfloat16
        )
        dots = jnp.einsum(
            "qd,qcd->qc", qblk.astype(y.dtype) if bf16_mode else qblk, yv,
            preferred_element_type=jnp.float32,
        )
        d2 = qnblk[:, None] + ynv - 2.0 * dots
        vals, pos = lax.top_k(-d2, k)
        # global column = chunk id * 128 + offset within chunk
        which = jnp.take_along_axis(cblk, pos // _CHUNK, axis=1)
        idx = which * _CHUNK + pos % _CHUNK
        return -vals, idx

    with jax.named_scope(SCOPE_RESCORE):
        vals, idxs = lax.map(rescore, (qb, qnb, cb))
        vals = vals.reshape(mp2, k)[:m]
        idxs = idxs.reshape(mp2, k)[:m]

        vals = jnp.maximum(vals, 0.0)
        if metric == DistanceType.L2SqrtExpanded:
            vals = jnp.sqrt(vals)
        return vals, idxs.astype(jnp.int32)


_L2_FAMILY = (
    DistanceType.L2Expanded,
    DistanceType.L2SqrtExpanded,
    DistanceType.L2Unexpanded,
)

def _plan_blocks(m: int, n: int, d: int, bm: int = 1024, bn: int = 2048):
    """Resolve phase-1 tile sizes: VMEM-bounded for wide d, 128-aligned."""
    bn = min(bn, _round_up(n, _CHUNK))
    bm = min(bm, _round_up(m, 128))  # queries ride the lane axis: 128-aligned
    # keep the phase-1 working set (score tile + double-buffered operand
    # tiles) inside VMEM for wide d
    while bn > 256 and (bn * bm * 4 + 8 * d * (bn + bm)) > 12 * 2**20:
        bn //= 2
        if bm > 256:
            bm //= 2
    return bm, bn


def fused_knn_supported(
    metric: DistanceType, m: int, n: int, d: int, k: int
) -> bool:
    """Shapes/metrics where the fused path applies and is expected to win:
    large n (the chunk-min traffic saving is the point), k small enough
    that the candidate set k*128 stays << n, and an L2-family metric
    (identical ranking; final op differs)."""
    return (
        metric in _L2_FAMILY
        and n // _CHUNK >= max(k, 32)   # enough chunks for exact cover
        and k <= 128
        and d <= 4096
        and m >= 1
    )


def fused_l2_knn(
    queries,
    index,
    k: int,
    *,
    metric: DistanceType = DistanceType.L2SqrtExpanded,
    bm: int = 1024,
    bn: int = 2048,
    bq2: int = 40,
    extra_chunks: int = 8,
    compute_dtype=jnp.float32,
    interpret: Optional[bool] = None,
    gather_rows: Optional[bool] = None,
    init: Optional[Tuple[jax.Array, jax.Array]] = None,
    index_norms: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Exact fused kNN for the L2 metric family. Returns (dists (m, k),
    indices (m, k)) best-first, matching ``brute_force_knn``.

    ``compute_dtype=bfloat16`` halves phase-1 index traffic and doubles MXU
    rate; chunk ranking then carries bf16 error, so pair it with a larger
    ``extra_chunks`` (the bench uses 32) for near-exact recall.

    ``init``: optional previous top-k ``(dists (m, k), ids (m, k))`` to
    warm-start from — the analog of the reference's previous-top-k warm
    path (fused_l2_knn.cuh:947 ``rowMajorQuery``). The result is the
    merged best-of-both, so a multi-partition search can thread results
    partition to partition; the caller owns id translation (as in the
    reference, knn_brute_force_faiss.cuh:240-254).

    ``index_norms``: optional precomputed ``sum(index**2, axis=1)`` (f32,
    shape (n,)), read by the XLA gather rescore (feature dims that are
    not a multiple of 128, or ``gather_rows``), which otherwise re-reads
    the whole index once per call for norms — the reference stores norms
    with the index for the same reason (knn_brute_force_faiss.cuh:318-330).
    Phase 1 computes norms from the tiles it already reads.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    queries = jnp.asarray(queries)
    index = jnp.asarray(index)
    m, d = queries.shape
    n = index.shape[0]
    if not fused_knn_supported(metric, m, n, d, k):
        raise ValueError(
            f"fused kNN unsupported for metric={metric} m={m} n={n} d={d} k={k}"
        )
    bm, bn = _plan_blocks(m, n, d, bm, bn)
    if index_norms is not None:
        index_norms = jnp.asarray(index_norms)
        errors_ok = index_norms.ndim == 1 and index_norms.shape[0] == n
        if not errors_ok:
            raise ValueError(
                f"index_norms must have shape ({n},), got {index_norms.shape}"
            )
    vals, idxs = _fused_l2_knn_impl(
        queries, index, k, metric,
        bm=bm, bn=bn, bq2=bq2, extra_chunks=extra_chunks,
        compute_dtype=jnp.dtype(compute_dtype),
        interpret=interpret, gather_rows=gather_rows,
        index_norms=index_norms,
    )
    if init is not None:
        from raft_tpu.spatial.selection import merge_topk

        init_d, init_i = init
        vals, idxs = merge_topk(
            vals, idxs, jnp.asarray(init_d), jnp.asarray(init_i, jnp.int32),
            select_min=True,
        )
    return vals, idxs
