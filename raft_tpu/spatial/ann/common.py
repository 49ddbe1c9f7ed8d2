"""Shared inverted-list storage — the TPU-native layout under every ANN
index (analog of the FAISS inverted lists the reference wraps,
cpp/include/raft/spatial/knn/detail/ann_quantized_faiss.cuh + ann_common.h;
here first-class, no FAISS).

Layout decision (hard part №3, SURVEY.md §7: "irregular gathers →
sorted-by-list batching"): vectors are permuted so each list is contiguous,
plus a dense (n_lists, max_list_size) row-id matrix padded with a sentinel.
Probing gathers whole padded lists — rectangular, static-shape, MXU-friendly
— and masks sentinel slots with +inf at scoring time.
"""

from __future__ import annotations

import dataclasses
import weakref

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Format, Layout

from raft_tpu import compat

__all__ = [
    "CoarseIndex", "ListStorage", "build_coarse_index",
    "build_list_storage", "coarse_probe_recall", "default_coarse_geometry",
    "is_row_major", "n_super_probes", "place_row_store",
    "probe_flop_accounting", "set_row_store_gauge",
    "split_oversized_lists", "static_qcap",
    "two_level_probe", "two_level_probe_kernel_supported",
]


@compat.register_dataclass
@dataclasses.dataclass
class ListStorage:
    """Sorted-by-list container.

    sorted_ids[i] = original row id of the i-th vector in list-sorted order;
    list_index[l, j] = position (into the sorted order) of the j-th member
    of list l, or ``n`` (sentinel) when padded.
    """

    sorted_ids: jax.Array     # (n,) int32
    list_offsets: jax.Array   # (n_lists + 1,) int32
    list_index: jax.Array     # (n_lists, max_list) int32, sentinel = n
    list_sizes: jax.Array     # (n_lists,) int32
    n: int = dataclasses.field(metadata=dict(static=True))
    max_list: int = dataclasses.field(metadata=dict(static=True))


@compat.register_dataclass
@dataclasses.dataclass
class CoarseIndex:
    """Two-level coarse quantizer over a centroid set — the sub-linear
    replacement for the flat query x all-centroids probe scan at
    deployment scale (~65k global centroids), after RAFT's own
    balanced-hierarchical coarse quantizer in ``ivf_pq``/
    ``kmeans_balanced``.

    The n_cents centroids are clustered into ~sqrt(n_cents)
    super-centroids; each super cluster's member centroids are stored as
    one padded rectangular block (the same sorted-by-list layout decision
    as :class:`ListStorage` — rectangular block gathers, MXU-friendly,
    sentinel-masked). Probing scores queries against the small super set,
    gathers the top super clusters' member blocks, and exactly reranks
    only those candidates (:func:`two_level_probe`) — ~5x fewer
    centroid-scoring FLOPs than the flat scan at 65k centroids
    (:func:`probe_flop_accounting`), recall guarded by the ``overprobe``
    knob and audited by :func:`coarse_probe_recall`.
    """

    super_cents: jax.Array   # (n_super, d) f32 super-centroids
    member_ids: jax.Array    # (n_super, max_members) int32, sentinel n_cents
    cents_padded: jax.Array  # (n_super, max_members, d) f32 member rows
    n_cents: int = dataclasses.field(metadata=dict(static=True))
    n_super: int = dataclasses.field(metadata=dict(static=True))
    max_members: int = dataclasses.field(metadata=dict(static=True))
    # the caller-facing build arguments (n_super, member_cap,
    # kmeans_n_iters, seed) as PASSED — None where defaulted — so a
    # rebuild over a different centroid set (expand_probe_set) replays
    # the user's tuning instead of silently reverting to defaults while
    # scale-dependent defaults still re-derive
    build_args: tuple = dataclasses.field(
        default=(None, None, 10, 0), metadata=dict(static=True)
    )


def default_coarse_geometry(n_cents: int):
    """(n_super, member_cap) defaults: ~sqrt(n_cents) super clusters,
    members capped at ceil(1.5 x mean) via the shared oversized-list
    split — the cap bounds ``max_members`` so the probe-FLOP win holds
    under cluster skew (a swollen super cluster would tax every probe's
    rectangular member gather, exactly the padded-list tax)."""
    n_super = max(1, min(n_cents, int(round(n_cents ** 0.5))))
    mean = -(-n_cents // n_super)
    return n_super, max(8, -(-3 * mean // 2))


def n_super_probes(n_probes: int, n_super: int,
                   overprobe: float = 2.0) -> int:
    """How many super clusters a two-level probe scans: ``ceil(overprobe
    x n_probes)``, clamped to the super count. With ``overprobe >= 1``
    (enforced) and no empty super clusters (the build drops them), the
    selected supers always contribute >= n_probes valid candidate
    centroids, so the reranked top-n_probes never contains a padding
    sentinel. Small indexes degenerate exactly: once the clamp engages
    every super is scanned and the probe equals the flat scan."""
    from raft_tpu import errors

    errors.expects(
        overprobe >= 1.0,
        "overprobe=%s < 1 would under-fill the candidate set (fewer "
        "valid candidates than n_probes)", overprobe,
    )
    return max(1, min(n_super, int(np.ceil(overprobe * n_probes))))


def build_coarse_index(centroids, *, n_super=None, member_cap=None,
                       kmeans_n_iters: int = 10,
                       seed: int = 0) -> CoarseIndex:
    """Cluster a centroid set into a :class:`CoarseIndex` (host-side —
    coarse-index construction is offline, like every index build).

    Reuses :func:`raft_tpu.cluster.kmeans.kmeans_fit` for the super
    clustering (bf16 compute — quantizer-training precision) and
    :func:`split_oversized_lists` for the member cap; empty super
    clusters are dropped so every probed super contributes candidates.
    """
    from raft_tpu import errors
    from raft_tpu.cluster.kmeans import KMeansParams, kmeans_fit

    cents = jnp.asarray(centroids, jnp.float32)
    errors.expects(
        cents.ndim == 2 and cents.shape[0] >= 1,
        "centroids: expected a (n >= 1, d) matrix, got shape %s",
        tuple(cents.shape),
    )
    build_args = (
        None if n_super is None else int(n_super),
        None if member_cap is None else int(member_cap),
        int(kmeans_n_iters), int(seed),
    )
    n, d = cents.shape
    ns_default, cap_default = default_coarse_geometry(n)
    if n_super is None:
        n_super = ns_default
    n_super = max(1, min(int(n_super), n))
    if member_cap is None:
        member_cap = cap_default
    out = kmeans_fit(
        cents,
        KMeansParams(
            n_clusters=n_super, max_iter=kmeans_n_iters, seed=seed,
            init="random", compute_dtype="bfloat16",
        ),
    )
    labels = np.asarray(out.labels)
    sup = out.centroids
    if member_cap:
        labels, sup = split_oversized_lists(labels, sup, int(member_cap))
    sup_np = np.asarray(sup, np.float32)
    ns = sup_np.shape[0]
    sizes = np.bincount(labels, minlength=ns)
    keep = np.nonzero(sizes > 0)[0]
    order = np.argsort(labels, kind="stable")
    offsets = np.concatenate([[0], np.cumsum(sizes)])
    mm = max(int(sizes.max()), 1)
    member = np.full((keep.size, mm), n, np.int32)
    for row, s in enumerate(keep.tolist()):
        cnt = int(sizes[s])
        member[row, :cnt] = order[offsets[s]:offsets[s] + cnt]
    cents_np = np.asarray(cents, np.float32)
    cpad = cents_np[np.minimum(member, n - 1)]
    return CoarseIndex(
        super_cents=jnp.asarray(sup_np[keep]),
        member_ids=jnp.asarray(member),
        cents_padded=jnp.asarray(cpad),
        n_cents=n,
        n_super=int(keep.size),
        max_members=mm,
        build_args=build_args,
    )


def two_level_probe(qf, super_cents, member_ids, cents_padded,
                    n_cents: int, n_probes: int, n_sup_probes: int,
                    block_q: int = 256, precision=None,
                    use_pallas: bool = False,
                    pallas_interpret: bool = False):
    """Sub-linear coarse probe: score queries against the super-centroid
    set, gather the top ``n_sup_probes`` super clusters' member blocks,
    and exactly rerank only those candidate centroids. Returns
    (probes (nq, p) int32, d2 (nq, p) f32 best-first candidate
    distances) — a drop-in for step (1)-(2) of :func:`coarse_probe` at a
    fraction of its FLOPs (:func:`probe_flop_accounting`).

    Plain ops only (top_k / take / einsum at the same default matmul
    precision as the flat scan), so the probe keeps its speed inside
    shard_map and produces identical replicated probes on every chip.
    Queries are processed in ``block_q`` blocks (:func:`map_query_blocks`)
    so the (block, S·max_members, d) candidate gather stays HBM-bounded.
    When ``n_sup_probes`` covers every super cluster the probe reranks
    every centroid — exactly the flat scan's candidate set.

    ``use_pallas=True`` (ISSUE 11) routes BOTH probe stages through the
    shared scan-kernel core (:mod:`raft_tpu.spatial.ann.scan_core`) so
    neither wide distance tile materializes in HBM inside a fused
    serving program: the super scan runs as a one-slab sub-chunk-min
    kernel (only (block, n_super/8) minima leave VMEM, the covered 8-row
    granules reranked in exact f32), and the member stage runs the ONE
    grouped scan body (``ivf_flat._grouped_impl``) over a mini flat
    index whose "lists" are the super clusters and whose slab is the
    padded member-centroid block — the same kernel, planner, masking,
    and exact rerank tail as the engines themselves. Falls back to this
    legacy path when :func:`two_level_probe_kernel_supported` says the
    geometry does not fit (the fused bodies pass their own ``use_pallas``
    static through, so probe engine choice can never flip at runtime).
    Results match the legacy probe's selected lists exactly whenever the
    mini grouped body's probe qcap drops no (query, super) pairs (a
    4x-mean shape-only cap, double the engines' default — the probe has
    no per-call audit, so it buys margin statically; slots fill in
    probe-RANK order, so a hot super that still overflows drops each
    query's marginal last-rank pairs first, never its top picks). On a
    query-skewed workload audit the kernelized probe with
    :func:`coarse_probe_recall(..., use_pallas=True)` before enabling
    it — or pin ``use_pallas=False`` on the probe-carrying search.
    """
    f32 = jnp.float32
    qf = jnp.asarray(qf).astype(f32)
    ns, mm, d = cents_padded.shape
    S = max(1, min(int(n_sup_probes), ns))
    # the kernel path serves the DEFAULT-precision probe only: a caller
    # pinning `precision` (the ball-cover exactness discipline) asked
    # for that exact matmul mode, which the bf16 scan stage cannot
    # honor — fall through to the legacy path instead of silently
    # ignoring the pin
    if use_pallas and precision is None and \
            two_level_probe_kernel_supported(
                d, qf.shape[0], n_probes, ns, mm, S, block_q
            ):
        return _two_level_probe_kernel(
            qf, super_cents, member_ids, cents_padded, n_cents,
            n_probes, S, block_q, pallas_interpret,
        )

    def blk(qb):
        bq = qb.shape[0]
        sup, _ = coarse_probe(qb, super_cents, S, precision)  # (bq, S)
        cand_ids = jnp.take(member_ids, sup, axis=0).reshape(bq, S * mm)
        cand = jnp.take(cents_padded, sup, axis=0).reshape(bq, S * mm, d)
        valid = cand_ids < n_cents
        qn = jnp.sum(qb * qb, axis=1)
        cvn = jnp.sum(cand * cand, axis=2)
        dots = jnp.einsum(
            "qcd,qd->qc", cand, qb, preferred_element_type=f32,
            precision=precision,
        )
        d2 = jnp.where(valid, qn[:, None] + cvn - 2.0 * dots, jnp.inf)
        vals, pos = jax.lax.top_k(-d2, n_probes)
        probes = jnp.take_along_axis(cand_ids, pos, axis=1)
        # a +inf slot can only surface when fewer than n_probes valid
        # candidates exist (overprobe < 1 misuse); clamp its sentinel id
        # so downstream owner[probe] gathers stay in range
        probes = jnp.where(jnp.isfinite(-vals), probes, 0)
        return -vals, probes.astype(jnp.int32)

    vals, probes = map_query_blocks(blk, qf, block_q)
    return probes, vals


def _probe_qcap(nq: int, n_sup_probes: int, n_super: int) -> int:
    """The mini grouped body's queries-per-super cap for the kernelized
    two-level probe: 4x the mean per-super occupancy (DOUBLE the
    engines' 2x-mean default — the probe has no per-call resolve_qcap
    audit, so it buys margin with shape math instead), 8-aligned,
    clamped to nq. Shape-only, so the fused programs stay free of host
    syncs and the cap is a trace-time static. Slots fill in probe-RANK
    order (invert_probe_map_ranked), so when a hot super still
    overflows — every query crowding the same few supers — each query
    KEEPS the supers it ranked highest and loses marginal last-rank
    pairs first; audit a skewed workload with
    :func:`coarse_probe_recall(..., use_pallas=True)` before enabling
    the kernelized probe on it."""
    return min(nq, 2 * default_qcap(nq, n_sup_probes, n_super))


def two_level_probe_kernel_supported(d: int, nq: int, n_probes: int,
                                     n_super: int, max_members: int,
                                     n_sup_probes: int,
                                     block_q: int = 256) -> bool:
    """Whether the kernelized two-level probe applies at this geometry
    (all static ints — evaluable at trace time inside a fused body):
    both stages' (query block, tile) steps must fit the shared planner's
    VMEM budget (``flat_scan_supported`` — the probe reuses the flat
    engine's byte model), and the reranked member pool must be able to
    fill a top-``n_probes`` row. When False, ``use_pallas=True`` on
    :func:`two_level_probe` silently serves the legacy path — the probe
    is an internal stage, and the engines' own ``use_pallas=True``
    contract (raise on unsupported) applies to the scan they were asked
    to kernelize, not to this auxiliary geometry."""
    if d < 1 or n_super < 1 or max_members < 1:
        return False
    from raft_tpu.spatial.ann.flat_kernel import flat_scan_supported

    s1_block = min(block_q, max(nq, 1))
    return (
        n_probes <= n_sup_probes * max_members
        and flat_scan_supported(d, s1_block)
        and flat_scan_supported(
            d, _probe_qcap(nq, n_sup_probes, n_super)
        )
    )


def _two_level_probe_kernel(qf, super_cents, member_ids, cents_padded,
                            n_cents: int, n_probes: int, S: int,
                            block_q: int, interpret: bool):
    """The ``use_pallas`` body of :func:`two_level_probe` — both stages
    through the shared scan-kernel core (module docstring of
    ``scan_core``; the caller has already validated
    :func:`two_level_probe_kernel_supported`)."""
    from raft_tpu.spatial.ann import flat_kernel, scan_core
    from raft_tpu.spatial.ann.ivf_flat import _grouped_impl

    f32 = jnp.float32
    nq = qf.shape[0]
    ns, mm, d = cents_padded.shape
    sub = scan_core.SUBCHUNK
    sup_f = jnp.asarray(super_cents, f32)

    # ---- stage 1: the super scan as a one-slab sub-chunk-min kernel.
    # The (block, n_super) distance tile never materializes: the kernel
    # emits (block, ns_pad/8) minima, the top-c granules' 8 rows are
    # reranked in exact f32 (HIGHEST), and the top-S supers come from
    # that rerank — the engines' own two-phase recipe applied to the
    # probe itself. c = 2S margin: the bf16 scan only perturbs granule
    # ranking near the boundary (the cover argument at 8-row grain).
    s1_block = min(block_q, max(nq, 1))
    q_kpad1 = scan_core.pad_queries(s1_block)
    # capped at the super set's own lane-rounded height (the small-slab
    # rule — see ivf_flat._grouped_impl), under the profile the block
    # size selects (the qcap-1/8 latency dispatches get the wide tile
    # in the probe stage too)
    l_tile1 = flat_kernel.plan_l_tile(
        d, q_kpad1, l_tile=-(-ns // scan_core.LANE) * scan_core.LANE,
        profile=scan_core.tile_profile(s1_block),
    )
    ns_pad = -(-ns // l_tile1) * l_tile1
    sup_t = jnp.pad(
        sup_f.T, ((0, 0), (0, ns_pad - ns))
    )[None]                                       # (1, d, ns_pad)
    s1_bounds = jnp.asarray([[0, ns]], jnp.int32)
    width1 = ns_pad // sub
    c1 = min(width1, 2 * S)

    def super_blk(qb):
        bq = qb.shape[0]
        qv = qb if bq == q_kpad1 else jnp.pad(
            qb, ((0, q_kpad1 - bq), (0, 0))
        )
        mins = flat_kernel.flat_scan_subchunk_min(
            qv[None], sup_t, s1_bounds,
            interpret=interpret, l_tile=l_tile1,
        )[0, :bq]                                 # (bq, width1)
        nv, cpos = jax.lax.top_k(-mins, c1)
        rows = (
            cpos[:, :, None] * sub
            + jnp.arange(sub, dtype=jnp.int32)[None, None, :]
        ).reshape(bq, c1 * sub)                   # candidate super rows
        valid = (
            (rows < ns)
            & (jnp.isfinite(-nv) & (-nv < scan_core.BIG))[
                :, :, None
            ].repeat(sub, axis=2).reshape(bq, c1 * sub)
        )
        cand = sup_f[jnp.clip(rows, 0, ns - 1)]   # (bq, c1*8, d)
        exact = score_l2_candidates(qb, cand, valid)
        sv, spos = jax.lax.top_k(-exact, S)
        sup_sel = jnp.take_along_axis(rows, spos, axis=1)
        return -sv, jnp.minimum(sup_sel, ns - 1).astype(jnp.int32)

    _, sup = map_query_blocks(super_blk, qf, s1_block)   # (nq, S)

    # ---- stage 2: the member gather + exact rerank as the ONE grouped
    # scan body over a mini flat index — "lists" are super clusters,
    # the slab is the flattened padded member block (build_coarse_index
    # packs each super's valid members first, so [s*mm, s*mm + size_s)
    # is exactly list s's valid range), sorted_ids map slab positions
    # back to centroid ids. The member-block distance tile lives only in
    # VMEM; the rerank tail's exact f32 distances are the returned probe
    # distances (squared, like the legacy probe's).
    from raft_tpu.spatial.ann.ivf_flat import IVFFlatIndex

    sizes = jnp.sum(member_ids < n_cents, axis=1).astype(jnp.int32)
    offsets = (jnp.arange(ns + 1, dtype=jnp.int32) * mm)
    # one pad op appends the sentinel row the grouped body's shape
    # contract needs (the reshape itself is a view). This is a fixed
    # ~ns*mm*d*4-byte per-dispatch copy; carrying the sentinel inside
    # CoarseIndex would remove it at the cost of a serialization-format
    # change — revisit if the probe stage shows up in latency traces.
    data_sorted = jnp.pad(
        cents_padded.reshape(ns * mm, d).astype(f32), ((0, 1), (0, 0))
    )
    storage = ListStorage(
        sorted_ids=member_ids.reshape(ns * mm).astype(jnp.int32),
        list_offsets=offsets,
        # only the leading axis is read on the grouped path (it carries
        # the list count)
        list_index=jnp.zeros((ns, 1), jnp.int32),
        list_sizes=sizes,
        n=ns * mm,
        max_list=mm,
    )
    mini = IVFFlatIndex(
        centroids=sup_f, data_sorted=data_sorted, storage=storage,
        metric="sqeuclidean",
    )
    d2, probes = _grouped_impl(
        mini, qf, n_probes, S, _probe_qcap(nq, S, ns),
        max(1, min(8, ns)), probes=sup,
        use_pallas=True, pallas_interpret=interpret, rerank_ratio=2.0,
    )
    # the legacy probe's sentinel clamp: a +inf slot (fewer than
    # n_probes valid candidates) maps to id 0 so downstream
    # owner[probe] gathers stay in range
    probes = jnp.where(jnp.isfinite(d2), probes, 0)
    return probes.astype(jnp.int32), d2


def coarse_probe_recall(queries, centroids, coarse: "CoarseIndex",
                        n_probes: int, *, overprobe: float = 2.0,
                        block_q: int = 256,
                        use_pallas: bool = False) -> float:
    """The two-level probe's recall guardrail: fraction of the flat
    scan's probed lists the two-level probe reproduces on ``queries``
    (eager, host sync — an audit, not a serving-path call). Bench
    workloads must stay within 0.01 of the flat probe; sweep
    ``overprobe`` up when they don't. ``use_pallas=True`` audits the
    KERNELIZED probe instead (interpret mode off-TPU) — run it on a
    representative batch before enabling the kernel probe on a
    query-skewed workload, where the probe's shape-only qcap can drop
    marginal (query, super) pairs the legacy path keeps
    (``_probe_qcap``)."""
    qf = jnp.asarray(queries, jnp.float32)
    flat, _ = coarse_probe(qf, jnp.asarray(centroids, jnp.float32),
                           n_probes)
    S = n_super_probes(n_probes, coarse.n_super, overprobe)
    two, _ = two_level_probe(
        qf, coarse.super_cents, coarse.member_ids, coarse.cents_padded,
        coarse.n_cents, n_probes, S, block_q,
        use_pallas=use_pallas,
        pallas_interpret=jax.default_backend() != "tpu",
    )
    a, b = np.asarray(flat), np.asarray(two)
    hits = sum(
        len(set(x.tolist()) & set(y.tolist())) for x, y in zip(a, b)
    )
    return hits / a.size


def probe_flop_accounting(coarse: "CoarseIndex", n_probes: int, *,
                          overprobe: float = 2.0) -> dict:
    """Per-query centroid-scoring MAC counts, from shapes alone:
    ``flat`` = the brute scan over all n_cents centroids, ``two_level`` =
    super scan + worst-case member rerank. The acceptance check for the
    two-level probe (>= 4x fewer FLOPs at ~65k centroids) reads
    ``ratio`` from here."""
    d = coarse.super_cents.shape[1]
    S = n_super_probes(n_probes, coarse.n_super, overprobe)
    flat = 2.0 * coarse.n_cents * d
    two = 2.0 * (coarse.n_super + S * coarse.max_members) * d
    return {"flat": flat, "two_level": two, "ratio": flat / two}


def coarse_probe(qf, centroids, n_probes: int, precision=None):
    """Score queries against list centroids on the MXU and return the
    ``n_probes`` closest lists per query.

    Returns (probes (nq, p) int32, centroid_d2 (nq, n_lists) f32) — the
    shared step (1)-(2) of every IVF-family search. ``precision``: matmul
    precision for the gram (None = XLA default, the fast path; ball
    cover's exactness certificate passes HIGHEST so bf16 operand rounding
    cannot falsely certify).

    Selection: on wide centroid sets (the 32k-list 100M-scale probe) the
    exact two-stage chunk-min select measures ~1.75x ``lax.top_k``
    (selection.py chunk_min_select_k — value-exact; tied distances may
    order differently than top_k's lowest-index tiebreak; plain ops so
    it keeps its speed inside shard_map too); the guard keeps narrow
    probes (bench-shape 2-4k lists, where the candidate gather covers
    most of the row anyway) on the direct path.
    """
    f32 = jnp.float32
    cents = centroids.astype(f32)
    qn = jnp.sum(qf * qf, axis=1)
    cn = jnp.sum(cents * cents, axis=1)
    g = jax.lax.dot_general(
        qf, cents, (((1,), (1,)), ((), ())), preferred_element_type=f32,
        precision=precision,
    )
    d2 = qn[:, None] + cn[None, :] - 2.0 * g
    nl = d2.shape[1]
    if nl % 128 == 0 and nl // 128 >= 4 * n_probes:
        from raft_tpu.spatial.selection import chunk_min_select_k

        _, probes = chunk_min_select_k(d2, n_probes)
    else:
        _, probes = jax.lax.top_k(-d2, n_probes)
    return probes, d2


def score_l2_candidates(qf, cand, valid):
    """Batched |q - c|² over gathered candidates (nq, C, d), +inf where
    ``valid`` is False — the shared step (4). HIGHEST precision: this is
    the *exact* scoring primitive (refinement, final IVF distances), so
    operands must not be rounded by the default matmul precision."""
    f32 = jnp.float32
    qn = jnp.sum(qf * qf, axis=1)
    cvn = jnp.sum(cand * cand, axis=2)
    dots = jnp.einsum(
        "qcd,qd->qc", cand, qf, preferred_element_type=f32,
        precision=jax.lax.Precision.HIGHEST,
    )
    return jnp.where(valid, qn[:, None] + cvn - 2.0 * dots, jnp.inf)


def select_candidates(storage: ListStorage, cand_pos, d2, k: int):
    """top-k over candidate scores + remap to original row ids (-1 for
    padding that survives into the top-k) — the shared step (5)."""
    vals, pos = jax.lax.top_k(-d2, k)
    vals = -vals
    ids = storage.sorted_ids[
        jnp.clip(
            jnp.take_along_axis(cand_pos, pos, axis=1), 0, storage.n - 1
        )
    ]
    ids = jnp.where(jnp.isfinite(vals), ids, -1)
    return vals, ids.astype(jnp.int32)


def map_query_blocks(fn, queries, block_q: int):
    """Process queries in fixed-size blocks via ``lax.map`` so the
    (block, n_probes·max_list, d) candidate gather stays HBM-bounded
    regardless of batch size. ``fn(q_block) -> (vals, ids)``.

    ``queries`` may also be a TUPLE of arrays sharing the query leading
    axis (e.g. queries + per-query candidate positions + validity masks
    — the Pallas refine tail); each is zero-padded and blocked
    identically and ``fn`` receives the tuple of blocks. Padded rows'
    outputs are sliced away, so pad VALUES only need to be safe to
    compute on, never correct."""
    multi = isinstance(queries, tuple)
    arrs = queries if multi else (queries,)
    nq = arrs[0].shape[0]
    if block_q >= nq:
        return fn(queries)
    nb = -(-nq // block_q)
    pad = nb * block_q - nq
    blocked = tuple(
        jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
            nb, block_q, *a.shape[1:]
        )
        for a in arrs
    )
    vals, ids = jax.lax.map(fn, blocked if multi else blocked[0])
    return (
        vals.reshape(nb * block_q, -1)[:nq],
        ids.reshape(nb * block_q, -1)[:nq],
    )


def invert_probe_map(probes, n_lists: int, qcap: int):
    """Invert a (nq, p) query→list probe map into a list→queries matrix —
    the shared first step of every LIST-MAJOR (grouped, throughput-mode)
    IVF search (SURVEY.md §7 hard part №3 "sorted-by-list batching").

    Returns (qmat (n_lists, qcap) int32 padded with nq,
             l_flat (nq*p,) the probed list of each (query, probe) pair,
             slot (nq*p,) that pair's row in qmat — >= qcap if dropped).

    Slots within a list fill in PROBE-RANK order: when a hot list
    overflows ``qcap`` (clustered queries concentrate their top probes),
    the dropped pairs are the marginal last-rank probes, not arbitrary
    queries — measured +0.11 recall@10 at a clustered 100k x 64 shape
    versus query-id-ordered filling.
    """
    qmat, _, l_flat, slot = invert_probe_map_ranked(probes, n_lists, qcap)
    return qmat, l_flat, slot


def invert_probe_map_ranked(probes, n_lists: int, qcap: int):
    """:func:`invert_probe_map` plus ``rmat`` (n_lists, qcap): the probe
    RANK of each slot's (query, list) pair (sentinel ``p`` when padded) —
    the slot -> (query, rank) inverse that lets a STREAMED grouped search
    scatter each list block's partials straight into the query-major
    (nq, p, kk) pool instead of materializing (n_lists, qcap, kk)."""
    nq, p = probes.shape
    l_flat = probes.reshape(-1)                              # (nq*p,)
    q_flat = jnp.repeat(jnp.arange(nq, dtype=jnp.int32), p)
    rank_flat = jnp.tile(jnp.arange(p, dtype=jnp.int32), nq)
    # two-pass stable sort = lexicographic (list, rank) order without a
    # composite key that could overflow int32 at billion-scale indexes
    by_rank = jnp.argsort(rank_flat, stable=True)
    order = by_rank[jnp.argsort(l_flat[by_rank], stable=True)]
    sl = l_flat[order]
    sq = q_flat[order]
    starts = jnp.searchsorted(sl, jnp.arange(n_lists, dtype=sl.dtype))
    slot_sorted = (
        jnp.arange(nq * p, dtype=jnp.int32) - starts[sl].astype(jnp.int32)
    )
    qmat = jnp.full((n_lists, qcap), nq, jnp.int32).at[
        sl, slot_sorted
    ].set(sq, mode="drop")                                   # (n_lists, qcap)
    rmat = jnp.full((n_lists, qcap), p, jnp.int32).at[
        sl, slot_sorted
    ].set(rank_flat[order], mode="drop")
    slot = jnp.zeros((nq * p,), jnp.int32).at[order].set(slot_sorted)
    return qmat, rmat, l_flat, slot


def regroup_pairs(vals, mem, l_flat, slot, nq: int, p: int, qcap: int):
    """Redistribute per-(list, query-slot) top-k results back to
    query-major order: (n_lists, qcap, k) -> (nq, p*k) candidate pool
    (+inf where the pair overflowed qcap) — the shared tail of grouped
    searches."""
    k = vals.shape[-1]
    ok = slot < qcap
    safe_slot = jnp.minimum(slot, qcap - 1)
    pv = jnp.where(ok[:, None], vals[l_flat, safe_slot], jnp.inf)
    pm = mem[l_flat, safe_slot]
    return pv.reshape(nq, p * k), pm.reshape(nq, p * k)


def default_qcap(nq: int, n_probes: int, n_lists: int) -> int:
    """2x the mean per-list probe occupancy, 8-aligned (the grouped
    searches' default static queries-per-list cap)."""
    mean_occ = max(1, (nq * n_probes + n_lists - 1) // n_lists)
    return min(nq, -(-2 * mean_occ // 8) * 8)


def throughput_qcap(nq: int, n_probes: int, n_lists: int) -> int:
    """~0.75x the mean per-list probe occupancy, 8-aligned — the
    throughput-mode cap (``qcap="throughput"``).

    Grouped block compute is LINEAR in qcap, and slots fill in
    probe-RANK order, so an aggressive cap drops only the marginal
    last-rank (query, probe) pairs. Measured (docs/ivf_scale.md "The
    qcap occupancy tax"): recall FLAT while QPS rose 11.2k -> 52.1k at
    500k x 96 (knee at 0.75x mean) and 7.6k -> 12.7k at 10M x 96 (knee
    at 0.75x mean again). NOT universally safe — on workloads whose hot
    lists collect top-RANK probes the drops cost recall (the 3M x 768
    diagnosis measured a 0.68 ceiling) — so it is opt-in; audit with
    :func:`probe_drop_stats` + measured recall."""
    mean_occ = max(1, (nq * n_probes + n_lists - 1) // n_lists)
    # 8-align UPWARD: flooring could land 20-45% below the measured
    # 0.75x-mean knee on non-divisible occupancies and silently cost
    # the recall the benchmarks say is safe
    return min(nq, max(8, -(-(3 * mean_occ // 4) // 8) * 8))


class _AuditRegistry:
    """(n_lists, n_probes, qcap, nq) signatures whose throughput-mode drop
    fraction has already been audited+logged this process, keyed by the
    centroids ARRAY — the audit's eager probe + host sync must not tax
    EVERY serving dispatch, but each distinct index deserves its own
    first-call audit.

    The key is a weakref to the centroids array, not ``id()`` alone: a
    freed index's id is eligible for reuse, and a bare-id registry would
    silently skip the audit on a NEW same-shape index that happened to
    land on a recycled id (the build-free-rebuild serving pattern). Dead
    entries evict themselves via the weakref callback."""

    def __init__(self):
        self._by_id: dict = {}    # id(arr) -> (weakref, set of sigs)

    def _sigs(self, arr):
        ent = self._by_id.get(id(arr))
        if ent is not None and ent[0]() is arr:
            return ent[1]
        return None

    def seen(self, arr, sig) -> bool:
        sigs = self._sigs(arr)
        return sigs is not None and sig in sigs

    def add(self, arr, sig) -> None:
        sigs = self._sigs(arr)
        if sigs is None:
            key = id(arr)

            def _evict(_, key=key, reg=self._by_id):
                reg.pop(key, None)

            try:
                ref = weakref.ref(arr, _evict)
            except TypeError:
                # non-weakrefable array type: hold it strongly (matches
                # the old id()-keyed lifetime, minus the reuse hazard)
                ref = (lambda a: (lambda: a))(arr)
            sigs = set()
            self._by_id[key] = (ref, sigs)
        sigs.add(sig)

    def clear(self) -> None:
        """Forget every audit (tests re-arming the first-call audit)."""
        self._by_id.clear()


_THROUGHPUT_AUDITED = _AuditRegistry()


def _eager_probe(q, centroids, n_probes: int, coarse=None,
                 overprobe: float = 2.0):
    """The eager (qcap-sizing / audit) probe: the two-level probe when a
    :class:`CoarseIndex` is supplied — the flat scan it replaces costs
    exactly the ~65k-centroid matmul the coarse index exists to avoid,
    and the drop stats should reflect the probe map actually served —
    else the flat scan."""
    qf = jnp.asarray(q, jnp.float32)
    if coarse is not None:
        probes, _ = two_level_probe(
            qf, coarse.super_cents, coarse.member_ids,
            coarse.cents_padded, coarse.n_cents, n_probes,
            n_super_probes(n_probes, coarse.n_super, overprobe),
        )
        return probes
    probes, _ = coarse_probe(qf, centroids, n_probes)
    return probes


def resolve_qcap_arg(qcap, q, centroids, n_lists: int, n_probes: int,
                     max_drop_frac=None, coarse=None,
                     overprobe: float = 2.0):
    """Shared qcap-argument resolution of every grouped search entry
    point: ``None`` -> the recall-safe auto path (:func:`auto_qcap`),
    ``"throughput"`` -> :func:`throughput_qcap`, an integer -> as-is.
    Returns (qcap int, probes_or_none). ``coarse``/``overprobe``: the
    eager sizing/audit probes route through the two-level probe when the
    caller's index carries one (:func:`_eager_probe`) — the auto paths
    must not reintroduce the flat scan the coarse index removes.

    ``qcap="throughput"`` guardrail (VERDICT r4 weak-4: the mode
    measured a silent 0.27 recall cost on a rank-concentrated 3M x 768
    workload): the FIRST call per (n_lists, n_probes, qcap, nq)
    signature eagerly probes and logs the dropped-pair fraction through
    the library logger — visible, but not a per-dispatch tax. Passing
    ``max_drop_frac`` upgrades the audit to EVERY call and falls back to
    the auto-sized qcap whenever the throughput cap would drop more than
    that fraction (trading the mode's speed for bounded drops). Under a
    jax trace the values are unavailable and the audit is skipped."""
    from raft_tpu import errors

    if qcap == "throughput":
        nq = q.shape[0]
        qc = throughput_qcap(nq, n_probes, n_lists)
        # the centroids array fingerprints the INDEX, not just the shape —
        # a second same-shape index with a hot-skewed distribution must be
        # audited too (the array is alive as long as its index is; the
        # registry keys it by weakref so a recycled id cannot alias)
        sig = (n_lists, n_probes, qc, nq)
        traced = isinstance(q, jax.core.Tracer) or isinstance(
            centroids, jax.core.Tracer
        )
        if traced or (
            max_drop_frac is None
            and _THROUGHPUT_AUDITED.seen(centroids, sig)
        ):
            return qc, None
        from raft_tpu.core import logger

        probes = _eager_probe(q, centroids, n_probes, coarse, overprobe)
        stats = probe_drop_stats(probes, n_lists, qc)
        _THROUGHPUT_AUDITED.add(centroids, sig)
        if max_drop_frac is not None and stats["frac"] > max_drop_frac:
            qc2 = resolve_qcap(
                probes, n_lists, nq, n_probes, max_drop_frac=max_drop_frac
            )
            logger.warn(
                "qcap='throughput' (=%d) would drop %.2f%% of probe "
                "pairs (> max_drop_frac=%.2f%%); falling back to "
                "auto-sized qcap=%d",
                qc, 100.0 * stats["frac"], 100.0 * max_drop_frac, qc2,
            )
            return qc2, probes
        if stats["dropped"]:
            logger.warn(
                "qcap='throughput' (=%d) drops %d/%d probe pairs "
                "(%.2f%%) on this workload; recall dips when hot lists "
                "collect top-RANK probes — audit measured recall / "
                "probe_drop_stats, or pass max_drop_frac to bound drops "
                "(docs/ivf_scale.md 'The qcap occupancy tax')",
                qc, stats["dropped"], stats["total"],
                100.0 * stats["frac"],
            )
        # probes are NOT handed back: audited and non-audited calls must
        # present the same input pytree to the jitted impl (probes=None),
        # or the first serving call after the audit would recompile the
        # whole grouped program with an extra traced argument
        return qc, None
    if qcap is None:
        return auto_qcap(
            q, centroids, n_lists, n_probes, coarse=coarse,
            overprobe=overprobe,
        )
    errors.expects(
        isinstance(qcap, (int, np.integer)) and not isinstance(qcap, bool),
        "qcap must be an int, None, or 'throughput'; got %r", qcap,
    )
    return int(qcap), None


def probe_drop_stats(probes, n_lists: int, qcap: int):
    """Dropped (query, probe) pairs for a probe map under a ``qcap``:
    slots fill in probe-rank order, so exactly ``max(0, occupancy - qcap)``
    pairs per list overflow. Returns {"dropped", "total", "frac"} — the
    diagnostic for unexplained grouped-search recall dips (a user with
    adversarially clustered queries sees the drop fraction here instead
    of guessing)."""
    occ = np.bincount(
        np.asarray(probes).reshape(-1), minlength=n_lists
    )
    total = int(occ.sum())
    dropped = int(np.maximum(occ - qcap, 0).sum())
    return {
        "dropped": dropped,
        "total": total,
        "frac": dropped / max(total, 1),
    }


def resolve_qcap(probes, n_lists: int, nq: int, n_probes: int,
                 max_drop_frac: float = 0.02) -> int:
    """Auto-size ``qcap`` from the ACTUAL probe map: start at the 2x-mean
    default and double (8-aligned) until the dropped-pair fraction is at
    most ``max_drop_frac`` (or every query fits). Logs the residual drop
    fraction through the library logger so truncation is never silent.

    Under a jax trace (a user wrapping the search in jax.jit) the probe
    values are unavailable; falls back to the static 2x-mean default —
    the pre-auto behavior — rather than failing at trace time."""
    from raft_tpu.core import logger

    if isinstance(probes, jax.core.Tracer):
        return default_qcap(nq, n_probes, n_lists)

    qcap = default_qcap(nq, n_probes, n_lists)
    while True:
        stats = probe_drop_stats(probes, n_lists, qcap)
        if stats["frac"] <= max_drop_frac or qcap >= nq:
            break
        qcap = min(nq, -(-2 * qcap // 8) * 8)
    if stats["dropped"]:
        logger.warn(
            "grouped search qcap=%d drops %d/%d probe pairs (%.3f%%); "
            "clustered queries overflow hot lists — raise qcap or "
            "max_drop_frac to trade memory for recall",
            qcap, stats["dropped"], stats["total"], 100.0 * stats["frac"],
        )
    return qcap


def auto_qcap(q, centroids, n_lists: int, n_probes: int, coarse=None,
              overprobe: float = 2.0):
    """Shared qcap=None path of the grouped searches: eagerly probe
    (two-level when ``coarse`` is supplied — :func:`_eager_probe`), size
    qcap from the actual map (:func:`resolve_qcap`), and hand the probes
    back for reuse — or None under an outer jit, where the impl must
    recompute them. Returns (qcap, probes_or_none)."""
    nq = q.shape[0]
    probes = _eager_probe(q, centroids, n_probes, coarse, overprobe)
    qcap = resolve_qcap(probes, n_lists, nq, n_probes)
    if isinstance(probes, jax.core.Tracer):
        return qcap, None
    return qcap, probes


def static_qcap(qcap, nq: int, n_probes: int, n_lists: int) -> int:
    """SHAPE-ONLY qcap resolution — the warm-up (AOT) sibling of
    :func:`resolve_qcap_arg`: ``None`` -> :func:`default_qcap`,
    ``"throughput"`` -> :func:`throughput_qcap`, an int -> as-is. Never
    inspects a probe map, so it needs no queries, no dispatch, and no
    host sync — ``index.warmup(nq)`` resolves its program's qcap here and
    hands the value back for the caller to pass explicitly on every
    serving dispatch (the data-dependent ``qcap=None`` auto path at serve
    time may resolve differently and would compile a second program)."""
    from raft_tpu import errors

    if qcap is None:
        return default_qcap(nq, n_probes, n_lists)
    if qcap == "throughput":
        return throughput_qcap(nq, n_probes, n_lists)
    errors.expects(
        isinstance(qcap, (int, np.integer)) and not isinstance(qcap, bool),
        "qcap must be an int, None, or 'throughput'; got %r", qcap,
    )
    return int(qcap)


def check_candidate_pool(k: int, n_probes: int, storage: ListStorage):
    if k > n_probes * storage.max_list:
        raise ValueError(
            f"k={k} exceeds the candidate pool "
            f"(n_probes*max_list = {n_probes * storage.max_list}); "
            "raise n_probes"
        )


def split_oversized_lists(labels_np, centroids, cap: int):
    """Split every list longer than ``cap`` into contiguous sublists that
    share the parent's centroid (appended as duplicate centroid rows).

    Grouped (list-major) search compute scales with n_lists * max_list,
    so one swollen list — a dense cluster swallowed whole — taxes every
    list block (measured: capping the one 1500-row list at the 500k x 96
    IVF-PQ bench config bought +54% QPS at identical recall). Tradeoff: a
    heavily split cluster consumes several of a query's n_probes slots
    (centroid distances tie), so raise n_probes on very skewed data.

    Host-side, vectorized — build is offline. Returns (labels, centroids);
    no-op when nothing exceeds the cap."""
    n_lists = centroids.shape[0]
    sizes = np.bincount(labels_np, minlength=n_lists)
    extra = np.maximum(0, -(-sizes // cap) - 1)               # sublists - 1
    if not extra.any():
        return labels_np, centroids
    order = np.argsort(labels_np, kind="stable")
    lbl_sorted = labels_np[order]
    offsets = np.concatenate([[0], np.cumsum(sizes)])
    rank = np.arange(labels_np.shape[0]) - offsets[lbl_sorted]
    sub = rank // cap                                         # 0..extra[l]
    base = n_lists + np.concatenate([[0], np.cumsum(extra)[:-1]])
    new_sorted = np.where(
        sub == 0, lbl_sorted, base[lbl_sorted] + sub - 1
    ).astype(labels_np.dtype)
    out = np.empty_like(labels_np)
    out[order] = new_sorted
    dup = np.repeat(np.arange(n_lists), extra)
    centroids = jnp.concatenate(
        [centroids, jnp.take(centroids, jnp.asarray(dup), axis=0)]
    )
    return out, centroids


def build_list_storage(assignments, n_lists: int) -> ListStorage:
    """Host-side build (index construction is offline, like the reference's
    index build path)."""
    a = np.asarray(assignments)
    n = a.shape[0]
    order = np.argsort(a, kind="stable").astype(np.int32)
    sizes = np.bincount(a, minlength=n_lists).astype(np.int32)
    offsets = np.concatenate([[0], np.cumsum(sizes)]).astype(np.int32)
    max_list = max(int(sizes.max()), 1)
    list_index = np.full((n_lists, max_list), n, np.int32)
    for l in range(n_lists):
        cnt = sizes[l]
        list_index[l, :cnt] = np.arange(offsets[l], offsets[l] + cnt)
    return ListStorage(
        jnp.asarray(order),
        jnp.asarray(offsets),
        jnp.asarray(list_index),
        jnp.asarray(sizes),
        n,
        max_list,
    )


def _layout(x):
    """``x``'s device layout, or None (a host array, a tracer)."""
    return getattr(getattr(x, "format", None), "layout", None)


def is_row_major(x) -> bool:
    """Whether the device array ``x`` lies row-major: JAX
    ``major_to_minor == (0, 1, ...)``, HLO ``{..., 1, 0}``. An array with
    no concrete layout reads False."""
    layout = _layout(x)
    return layout is not None \
        and layout.major_to_minor == tuple(range(np.ndim(x)))


def place_row_store(x):
    """Place an index's row store (``data_sorted``, SQ ``codes_sorted``)
    row-major on its device(s), keeping its sharding; ``x`` as is when it
    already lies so, or has no concrete layout.

    The TPU's default layout for a tall array whose minor dimension is
    under 128 lanes, e.g. ``bf16[n, 96]``, is column-major (``{0,1}``): it
    stores the rows without lane padding. The grouped search program
    (:func:`...ivf_flat._grouped_impl`) reads rows: the list loop's
    dynamic slices and the rerank gather. Handed a column-major store, XLA
    copies the whole index to row-major twice per run, once for each.
    ``jit`` takes a committed argument's own layout as its entry layout,
    so a store placed row-major once, at build and before ``warmup``, is
    read as it lies by every program warmed on it, and no run copies it.
    On the CPU, and for shapes the TPU already stores row-major, this is
    a no-op.

    Memory: the row-major store costs its lane padding (96 of 128 lanes
    at d = 96, 4/3 of the column-major bytes). That is the footprint of
    one of the two whole-index copies every run made before, so the
    resident store never raises a run's peak above what the program
    already needed. The re-placement itself holds both layouts once, at
    build.

    The relayout program is kept out of the persistent compile cache:
    JAX loads a cached program without its output layouts (seen on the
    v5e and on XLA:CPU), so a cached relayout would return row-major
    bytes labelled with the default layout, and the program compiled for
    that label then refuses them (v5e) or misreads them (CPU)."""
    if _layout(x) is None or is_row_major(x):
        return x
    row_major = Layout(major_to_minor=tuple(range(x.ndim)))
    with compat.persistent_cache_min_compile_time(float("inf")):
        return jax.device_put(x, Format(row_major, x.sharding))


def set_row_store_gauge(engine: str, store) -> None:
    """Record whether ``store`` lies row-major (1) or not (0) on the
    ``ivf_row_store_row_major`` gauge; read off the array, no compile (the
    ``warmup`` of the flat and SQ indexes)."""
    from raft_tpu.obs import metrics as obs_metrics

    obs_metrics.default_registry().gauge(
        "ivf_row_store_row_major", engine=engine
    ).set(float(is_row_major(store)))
