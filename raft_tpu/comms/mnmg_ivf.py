"""Multi-chip sharded IVF-PQ — the DEEP-100M regime (docs/ivf_scale.md).

The reference carries 100M-row ANN through FAISS GpuIndexIVFPQ
(cpp/include/raft/spatial/knn/detail/ann_quantized_faiss.cuh:115-206) and
merges multi-partition results with ``knn_merge_parts``
(knn_brute_force_faiss.cuh:289-368). Here the same capability is a mesh
program:

* **Shard lists, replicate quantizers.** Coarse centroids + PQ codebooks
  (a few MB) replicate to every chip; the inverted lists shard by list id
  (greedy LPT assignment balances rows/chip; ``max_list_cap`` bounds
  skew). Each chip's shard is a complete single-chip inverted-list
  layout: contiguous codes, shard-local raw vectors for refinement, and
  ``sorted_ids`` carrying GLOBAL row ids.
* **Queries replicate; lists never move.** Every chip probes the GLOBAL
  centroid set (replicated compute — identical probes everywhere), keeps
  the probes it owns, and runs the UNCHANGED single-chip grouped ADC
  kernel (:func:`raft_tpu.spatial.ann.ivf_pq._pq_grouped_impl`) against
  its shard — unowned probe slots route to an empty sentinel list.
* **Merge is a k-way top-k.** One ``all_gather`` of the (nq, k)
  per-chip results + ``select_k`` yields the global top-k on every chip
  (the ``knn_merge_parts`` pattern, same as :func:`mnmg_knn`).

Per-chip refinement rescores that chip's top-c ADC candidates against its
OWN raw rows (lists and their vectors co-shard), so the merge sees exact
f32 distances and no raw vector ever crosses the interconnect.
Collectives per batch: one (nq, k) value + one (nq, k) id all_gather —
trivial next to ADC compute (docs/ivf_scale.md "The 100M multi-chip
design").
"""

from __future__ import annotations

import dataclasses
import functools
import typing

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from raft_tpu import compat, errors
from raft_tpu.comms.comms import AxisComms, Comms
from raft_tpu.comms.multihost import (
    comms_levels,
    hier_axes,
    hierarchical_merge_select_k,
    host_aware_offset,
)
from raft_tpu.cluster.kmeans import KMeansParams, kmeans_fit
from raft_tpu.resilience.degraded import (
    PartialSearchResult,
    mask_invalid_rows,
    probe_coverage,
    resolve_shard_mask,
    sanitize_query_rows,
)
from raft_tpu.resilience.replica import resolve_route
from raft_tpu.spatial.ann.common import (
    CoarseIndex,
    ListStorage,
    build_coarse_index,
    coarse_probe,
    n_super_probes,
    place_row_store,
    resolve_qcap_arg,
    two_level_probe,
)
from raft_tpu.spatial.ann.ivf_pq import (
    IVFPQIndex,
    IVFPQParams,
    _cdiv_host,
    _encode_rows,
    _pq_grouped_impl,
    _train_pq_codebooks,
)
from raft_tpu.spatial.selection import merge_parts_select_k

__all__ = [
    "MnmgIVFPQIndex", "attach_coarse_index", "expand_probe_set",
    "mnmg_ivf_pq_build", "mnmg_ivf_pq_build_distributed",
    "mnmg_ivf_pq_search", "place_index", "recover_rank",
    "replicate_index", "reshard_index", "shard_rows",
]

# query-block size of the in-program two-level probe's candidate rerank
# (the (block, S*max_members, d) gather stays HBM-bounded at any nq)
_PROBE_BLOCK_Q = 256


@compat.register_dataclass
@dataclasses.dataclass
class MnmgIVFPQIndex:
    """List-sharded IVF-PQ index over a comms mesh.

    Stacked arrays carry a leading mesh axis (one slab per chip, sharded
    ``P(axis, ...)``); quantizers and the ownership maps are replicated.
    ``sorted_ids`` hold GLOBAL row ids so per-chip results merge without
    translation. Shards support the grouped (list-major) search only.
    """

    centroids: jax.Array       # (n_lists_g, d) replicated
    codebooks: jax.Array       # (M, 2^bits, ds) replicated
    owner: jax.Array           # (n_lists_g,) int32 — owning rank per list
    local_id: jax.Array        # (n_lists_g,) int32 — list id on its owner
    local_cents: jax.Array     # (P, nl_pad, d) — per-chip centroid slab
    codes_sorted: jax.Array    # (P, n_pad + 1, M) uint8
    vectors_sorted: typing.Optional[jax.Array]  # (P, n_pad + 1, d) | None
    sorted_ids: jax.Array      # (P, n_pad) int32 GLOBAL row ids
    list_offsets: jax.Array    # (P, nl_pad + 1) int32
    list_sizes: jax.Array      # (P, nl_pad) int32
    pq_dim: int = dataclasses.field(metadata=dict(static=True))
    pq_bits: int = dataclasses.field(metadata=dict(static=True))
    n_pad: int = dataclasses.field(metadata=dict(static=True))
    nl_pad: int = dataclasses.field(metadata=dict(static=True))
    max_list: int = dataclasses.field(metadata=dict(static=True))
    n_rows: int = dataclasses.field(metadata=dict(static=True))
    # R-way striped replica layout (resilience/replica.py): each rank's
    # slab holds `replication` segments of nl_pad/replication lists —
    # segment 0 its own primary shard, segment j the shard
    # (rank - j*replica_offset) % P. 1 = unreplicated (the build output;
    # replicate with place_index(..., replication=R))
    replication: int = dataclasses.field(
        default=1, metadata=dict(static=True)
    )
    replica_offset: int = dataclasses.field(
        default=1, metadata=dict(static=True)
    )
    # optional two-level coarse quantizer over the GLOBAL probe set
    # (attach_coarse_index); the fused search probes through it when
    # present instead of brute-scanning every centroid
    coarse: typing.Optional[CoarseIndex] = None

    def warmup(self, comms: "Comms", nq: int, *, k: int = 10,
               n_probes: int = 8, qcap=None, list_block: int = 8,
               refine_ratio: float = 2.0, exact_selection: bool = True,
               approx_recall_target: float = 0.95,
               donate_queries: bool = False,
               shard_mask=None, failover=None, overprobe: float = 2.0,
               merge_ways: typing.Optional[int] = None,
               use_pallas: typing.Optional[bool] = None,
               mutation=None, wire: str = "bf16",
               audit: bool = False) -> int:
        """Pre-compile the sharded serving program for (nq, d) float32
        batches: one all-zeros batch runs through
        :func:`mnmg_ivf_pq_search` and is blocked on, so the first real
        batch pays dispatch, not trace+compile (and the compile lands in
        the persistent cache when enabled — docs/serving.md).

        Returns the shape-only-resolved qcap
        (:func:`raft_tpu.spatial.ann.common.static_qcap`); pass exactly
        that integer (and the same ``donate_queries``) on serving
        dispatches — the compiled program is keyed on both. Pass
        ``shard_mask=True`` to warm the RESILIENT variant instead (the
        ``shard_mask=``/``PartialSearchResult`` program —
        docs/robustness.md); the mask AND the replica-failover route
        are runtime inputs, so one warm-up covers every later health
        and failover state.

        ``audit=True`` re-traces the warmed fused program through the
        jaxpr-level program auditor (:mod:`raft_tpu.analysis.program`;
        docs/static_analysis.md "Two tiers") and raises listing the
        findings when it violates the serving-tier invariants — wide
        cross-host collectives, an uncompressed DCN wire, scan-path f32
        tiles, 64-bit dtypes, or (with ``donate_queries=True``) queries
        the lowering does not actually donate."""
        from raft_tpu.spatial.ann.common import static_qcap

        qc = static_qcap(qcap, nq, n_probes, self.centroids.shape[0])
        q0 = jnp.zeros((nq, self.centroids.shape[1]), jnp.float32)
        out = mnmg_ivf_pq_search(
            comms, self, q0, k, n_probes=n_probes, qcap=qc,
            list_block=list_block, refine_ratio=refine_ratio,
            exact_selection=exact_selection,
            approx_recall_target=approx_recall_target,
            donate_queries=donate_queries, shard_mask=shard_mask,
            failover=failover, overprobe=overprobe,
            merge_ways=merge_ways, use_pallas=use_pallas,
            mutation=mutation, wire=wire,
        )
        jax.block_until_ready(out)
        if audit:
            from raft_tpu.analysis.program import audit_warmed
            from raft_tpu.analysis.program.registry import (
                record_from_traced,
            )

            fn, args, _ = _prepare_pq_search(
                comms, self, q0, k, n_probes=n_probes, qcap=qc,
                list_block=list_block, refine_ratio=refine_ratio,
                exact_selection=exact_selection,
                approx_recall_target=approx_recall_target,
                donate_queries=donate_queries, shard_mask=shard_mask,
                failover=failover, overprobe=overprobe,
                merge_ways=merge_ways, use_pallas=use_pallas,
                mutation=mutation, wire=wire,
            )
            h = hier_axes(comms.mesh, comms.axis)
            # the wrapper's own engine resolution: in kernel mode the
            # wide tile is a finding, in XLA-fallback mode intentional
            # (docs/ivf_scale.md)
            from raft_tpu.spatial.ann.ivf_pq import _resolve_adc_engine

            up = _resolve_adc_engine(
                use_pallas,
                self.vectors_sorted is not None and refine_ratio > 1.0,
                self.pq_dim, self.pq_bits, qc,
            )
            audit_warmed(record_from_traced(
                "mnmg_ivf_pq_warm", fn.trace(*args),
                {
                    "nq": nq, "k": k, "n_probes": n_probes, "qcap": qc,
                    "max_list": int(self.max_list),
                    "allow_wide_tile": not up,
                    "expect_donated_queries": bool(donate_queries),
                    "dcn_axes": () if h is None else (h[0],),
                    "dcn_wire": wire,
                },
            ))
        return qc


# bounded cache of compiled build-phase shard_map programs keyed on
# (kind, mesh, axis, statics): the single-chip build reuses executables
# through module-level jits (_encode_block_jit), and a distributed
# same-shape rebuild deserves the same — without this every build
# re-traced and re-compiled all four phase programs (~130 s of the 150 s
# warm mnmg build at the 500k bench shape was recompilation)
_PROGRAM_CACHE: dict = {}


def _cached_program(key, make):
    fn = _PROGRAM_CACHE.get(key)
    if fn is None:
        if len(_PROGRAM_CACHE) >= 64:
            _PROGRAM_CACHE.pop(next(iter(_PROGRAM_CACHE)))
        fn = _PROGRAM_CACHE[key] = make()
    return fn


def _slab_height(loads, max_list=None) -> int:
    """Bucketed per-rank slab height (n_pad) shared by the distributed
    builds, :func:`reshard_index` and :func:`replicate_index`. The raw
    max-load is data-dependent, so a same-shape rebuild (or a reshard)
    would shift n_pad by a handful of rows and recompile BOTH the
    assembly program and every search program keyed on it. Given the
    largest list ``max_list`` of an LPT placement (``_lpt_assign``), the
    height is that placement's bound instead: a rank holds at most the
    mean load plus one list. With a list cap, ``max_list`` is the cap as
    soon as any list splits, so the height follows rows, cap and chips
    alone. Either way it is rounded up to a coarse bucket (<= ~6% slab
    padding), which keeps the statics — and the compiled programs —
    stable."""
    if max_list is None:
        raw_npad = max(int(np.max(loads)), 1)
    else:
        raw_npad = _cdiv_host(int(np.sum(loads)), len(loads)) + int(max_list)
    bucket = 256 if raw_npad < (1 << 17) else 4096
    return _cdiv_host(raw_npad, bucket) * bucket


def _rank_slab_maps(owner, local_id, sizes, cents, n_ranks: int,
                    nl_pad: int, d: int):
    """Per-rank (offsets, sizes, centroids) slabs from a list→rank
    assignment (owner -1 = unowned, left out of every slab). The single
    layout authority for builds AND reshards — both must produce
    byte-identical slab geometry for a given assignment."""
    offs_sh = np.zeros((n_ranks, nl_pad + 1), np.int32)
    szs_sh = np.zeros((n_ranks, nl_pad), np.int32)
    lcents_sh = np.zeros((n_ranks, nl_pad, d), np.float32)
    for r in range(n_ranks):
        mine = np.nonzero(owner == r)[0]
        lid = local_id[mine]
        szs_sh[r, lid] = sizes[mine]
        offs_sh[r] = np.concatenate([[0], np.cumsum(szs_sh[r])])
        lcents_sh[r, lid] = cents[mine]
    return offs_sh, szs_sh, lcents_sh


def _lpt_assign(sizes: np.ndarray, n_ranks: int):
    """Greedy longest-processing-time list→rank assignment: biggest list
    to the least-loaded rank. Returns (owner (nl,), local_id (nl,),
    rows_per_rank (P,), lists_per_rank (P,))."""
    nl = sizes.shape[0]
    owner = np.empty(nl, np.int32)
    local_id = np.empty(nl, np.int32)
    loads = np.zeros(n_ranks, np.int64)
    counts = np.zeros(n_ranks, np.int32)
    for l in np.argsort(-sizes, kind="stable"):
        r = int(np.argmin(loads))
        owner[l] = r
        local_id[l] = counts[r]
        loads[r] += int(sizes[l])
        counts[r] += 1
    return owner, local_id, loads, counts


def mnmg_ivf_pq_build(
    comms: Comms, x, params: IVFPQParams = IVFPQParams()
) -> MnmgIVFPQIndex:
    """Build a list-sharded IVF-PQ index from ONE host array.

    Convenience wrapper over :func:`mnmg_ivf_pq_build_distributed`: the
    rows are placed onto the mesh one contiguous shard at a time (host
    transient = one shard, never a second full copy), then the per-rank
    distributed pipeline runs — training on a collectively-gathered
    subsample, per-rank blocked encode, an ``all_to_all`` row exchange to
    each list's LPT owner, and device-side slab assembly. In a
    multi-process deployment each process transfers only the shards of
    its own devices; processes whose data is genuinely local should call
    the distributed entry point directly.
    """
    x = np.asarray(x)
    errors.expects(
        x.ndim == 2 and x.shape[0] >= 2,
        "x: expected a (n >= 2, d) matrix, got shape %s", tuple(x.shape),
    )
    xg, n_valid = shard_rows(comms, x)
    return mnmg_ivf_pq_build_distributed(comms, xg, params, n_valid=n_valid)


def _P3(axis):
    return P(axis, None, None)


def shard_rows(comms: Comms, x: np.ndarray):
    """Place a host (n, d) matrix as (P, n_loc, d) contiguous row shards
    over the comms mesh — one ``device_put`` per (addressable) rank, so
    the host transient is a single shard, never a second full copy.
    Returns (sharded ``jax.Array``, ``n_valid`` (P,) int32) in the layout
    :func:`mnmg_ivf_pq_build_distributed` consumes; shard row (r, j)
    corresponds to global row ``r * n_loc + j``."""
    n, d = x.shape
    Pn = comms.size
    nloc = _cdiv_host(n, Pn)
    sh = NamedSharding(comms.mesh, _P3(comms.axis))
    parts = []
    for r, dev in enumerate(comms.mesh.devices.flat):
        if dev.process_index != jax.process_index():
            continue
        blk = x[r * nloc:min(n, (r + 1) * nloc)]
        if blk.shape[0] < nloc:
            blk = np.pad(blk, ((0, nloc - blk.shape[0]), (0, 0)))
        parts.append(jax.device_put(blk[None], dev))
    xg = jax.make_array_from_single_device_arrays((Pn, nloc, d), sh, parts)
    n_valid = np.array(
        [max(0, min(nloc, n - r * nloc)) for r in range(Pn)], np.int32
    )
    return xg, n_valid


def mnmg_ivf_pq_build_distributed(
    comms: Comms, x, params: IVFPQParams = IVFPQParams(), *,
    n_valid=None,
) -> MnmgIVFPQIndex:
    """Build a list-sharded IVF-PQ index from PER-RANK row shards — no
    host ever holds more than its own rows (the DEEP-100M build path;
    VERDICT r4 item 1).

    ``x``: (P, n_loc, d) stacked row shards, one slab per mesh rank,
    sharded ``P(axis, None, None)`` (multi-process callers assemble it
    with ``jax.make_array_from_process_local_data`` /
    ``make_array_from_single_device_arrays`` from their local rows).
    ``n_valid``: (P,) valid rows per rank (rows beyond are padding and
    ignored); default all. Shard row ``(r, j)`` gets GLOBAL id
    ``sum(n_valid[:r]) + j`` — contiguous block numbering, matching the
    one-host wrapper's original row order.

    Pipeline (each phase a mesh program; host touches only O(P·n_lists)
    metadata):

    1. **Subsample + train (replicated).** Every rank contributes
       ``train_n / P`` uniformly-sampled local rows to one ``all_gather``
       — the collective analog of FAISS's subsample ``train()``
       (reference ann_quantized_faiss.cuh:115-206). Coarse k-means + PQ
       codebooks then train on the replicated subsample, identically on
       every rank.
    2. **Per-rank blocked encode** (shard_map): each rank labels + PQ-
       encodes ITS rows against the replicated quantizers in
       ``encode_block``-row blocks; global list sizes come back from one
       psum-sized allgather of the local bincounts.
    3. **Device-side list split + LPT routing.** Oversized lists split by
       GLOBAL within-list rank (per-rank prefix over the gathered count
       matrix — same sublist semantics as the single-chip
       ``split_oversized_lists``); the host computes the greedy-LPT
       ``owner``/``local_id`` maps from the split sizes (O(n_lists)).
    4. **Row exchange + slab assembly** (shard_map): every rank scatters
       its rows into per-destination slots and a short sequence of
       bounded-buffer ``all_to_all`` rounds (each padded to ~half a shard
       of rows; typically 2 rounds balanced, more only under skew) routes
       each list's rows to its owner — the ICI-native replacement for the
       reference's host-mediated Dask worker-to-worker movement
       (python/raft/raft/dask/common/comms.py:171-218). Each row carries
       its exact destination slab position (derived from its global
       within-list rank), so receivers scatter rows straight into the
       contiguous slabs the grouped search kernel consumes — no
       receive-side sort, no global-max-padded buffers.

    ``store_raw=True`` co-shards each list's raw vectors with its codes
    (shard-local exact refinement); with per-rank inputs the raw slab
    only ever exists device-side.
    """
    errors.expects(
        hasattr(x, "ndim") and x.ndim == 3,
        "x: expected (n_ranks, n_loc, d) stacked row shards, got %s",
        tuple(getattr(x, "shape", ())),
    )
    Pn, nloc, d = x.shape
    errors.expects(
        Pn == comms.size,
        "x leading axis %d != mesh size %d", Pn, comms.size,
    )
    M = params.pq_dim
    errors.expects(d % M == 0, "d=%d not divisible by pq_dim=%d", d, M)
    errors.expects(
        1 <= params.pq_bits <= 8,
        "pq_bits=%d out of range [1, 8] — codes are stored as uint8",
        params.pq_bits,
    )
    ds = d // M
    n_codes = 1 << params.pq_bits
    if n_valid is None:
        n_valid = np.full(Pn, nloc, np.int32)
    n_valid = np.asarray(n_valid, np.int32)
    n = int(n_valid.sum())
    errors.check_k(params.n_lists, n, "n_lists vs dataset rows")
    errors.expects(
        n >= n_codes,
        "n=%d rows cannot train %d-entry PQ codebooks (pq_bits=%d); "
        "lower pq_bits", n, n_codes, params.pq_bits,
    )
    nl = params.n_lists
    ax = comms.device_comms()
    sh3 = _P3(comms.axis)
    sh2 = P(comms.axis, None)
    sh1 = P(comms.axis)
    rep = P()

    # ---- phase 1: collective training subsample -> replicated quantizers
    xt, coarse = _train_coarse_distributed(
        comms, x, n_valid, n, nl, params.train_size,
        params.kmeans_n_iters, params.kmeans_init, params.seed,
    )
    codebooks = _train_pq_codebooks(xt, coarse, params, ds, n_codes)
    cents = coarse.centroids

    # ---- phase 2: per-rank blocked encode + global list sizes
    B = max(1, min(nloc, params.encode_block))
    nb = _cdiv_host(nloc, B)

    def make_enc():
        def enc_body(x_sh, nv_sh, cents_in, cbs_in):
            xb, nvr = x_sh[0], nv_sh[0]
            xp = jnp.pad(xb, ((0, nb * B - nloc), (0, 0)))
            lbl, codes = lax.map(
                lambda blk: _encode_rows(blk, cents_in, cbs_in, M, ds),
                xp.reshape(nb, B, d),
            )
            lbl = lbl.reshape(-1)[:nloc]
            codes = codes.reshape(-1, M)[:nloc]
            valid = jnp.arange(nloc, dtype=jnp.int32) < nvr
            cnt = jnp.zeros((nl + 1,), jnp.int32).at[
                jnp.where(valid, lbl, nl)
            ].add(1)[:nl]
            return lbl[None], codes[None], ax.allgather(cnt)

        return jax.jit(comms.shard_map(
            enc_body, in_specs=(sh3, sh1, rep, rep),
            out_specs=(sh2, sh3, rep),
        ))

    lbl_g, codes_g, C = _cached_program(
        ("enc", comms.mesh, comms.axis, Pn, nloc, d, B, nb, M, ds, nl,
         str(x.dtype)),
        make_enc,
    )(x, n_valid, cents, codebooks)

    cap = (
        params.max_list_cap
        if params.max_list_cap is not None
        else max(256, 2 * _cdiv_host(n, nl))
    )
    maps, slabs = _exchange_and_assemble(
        comms, x, n_valid, lbl_g, C, cents, cap,
        store_vectors=params.store_raw, codes_g=codes_g, M=M,
    )

    host = MnmgIVFPQIndex(
        centroids=maps["cents_np"],
        codebooks=np.asarray(codebooks),
        owner=maps["owner"],
        local_id=maps["local_id"],
        local_cents=maps["lcents_sh"],
        codes_sorted=slabs["codes"],
        vectors_sorted=slabs.get("vecs"),
        sorted_ids=slabs["sids"],
        list_offsets=maps["offs_sh"],
        list_sizes=maps["szs_sh"],
        pq_dim=M,
        pq_bits=params.pq_bits,
        n_pad=maps["n_pad"],
        nl_pad=maps["nl_pad"],
        max_list=maps["max_list"],
        n_rows=n,
    )
    return place_index(comms, host)


def _train_coarse_distributed(
    comms: Comms, x, n_valid, n: int, nl: int, train_size,
    kmeans_n_iters: int, kmeans_init: str, seed: int,
):
    """Phase 1 of every distributed list-sharded build (PQ and Flat):
    collective training subsample + replicated coarse k-means.

    Every NON-EMPTY rank contributes ``train_n / n_active`` uniformly
    sampled local rows to one ``all_gather`` (empty shards are filtered
    host-side — their slots would be all padding zeros and train
    centroids onto the origin; the per-active-rank quota keeps the
    training set at ``train_n`` so the caller's global-n minima hold).
    A random-permutation prefix gives exact without-replacement sampling
    on full shards; ragged shards remap out-of-range picks with a modulo
    (mild duplication). Returns (xt, coarse KMeansOutput)."""
    Pn, nloc, d = x.shape
    n_valid = np.asarray(n_valid, np.int32)
    ax = comms.device_comms()
    sh3 = _P3(comms.axis)
    sh1 = P(comms.axis)
    rep = P()
    train_n = min(
        n,
        train_size if train_size is not None else max(1 << 20, 64 * nl),
    )
    keep = np.nonzero(n_valid > 0)[0]
    t_per = _cdiv_host(train_n, max(keep.size, 1))
    key0 = jax.random.PRNGKey(seed)

    def make_sub():
        def sub_body(x_sh, nv_sh, key_in):
            xb, nvr = x_sh[0], nv_sh[0]
            key = jax.random.fold_in(key_in, ax.get_rank())
            sel = jax.random.permutation(key, nloc)[:t_per]
            sel = jnp.where(sel < nvr, sel, sel % jnp.maximum(nvr, 1))
            g = ax.allgather(jnp.take(xb, sel, axis=0))      # (P, t_per, d)
            # static keep-filter folded into the program: empty ranks'
            # all-padding slots never reach quantizer training
            return g[keep].reshape(keep.size * t_per, d)

        return jax.jit(comms.shard_map(
            sub_body, in_specs=(sh3, sh1, P(None)), out_specs=rep,
        ))

    xt = _cached_program(
        ("sub", comms.mesh, comms.axis, Pn, nloc, d, t_per,
         tuple(keep.tolist()), str(x.dtype)),
        make_sub,
    )(x, n_valid, key0)

    coarse = kmeans_fit(
        xt,
        KMeansParams(
            n_clusters=nl,
            max_iter=kmeans_n_iters,
            seed=seed,
            init=kmeans_init,
            # quantizer training tolerates bf16-rounded centroid updates
            # (intra-cluster averaging washes out operand rounding)
            compute_dtype="bfloat16",
        ),
    )
    return xt, coarse


def _exchange_and_assemble(
    comms: Comms, x, n_valid, lbl_g, C, cents, cap: int,
    store_vectors: bool, codes_g=None, M: int = 0,
):
    """Phases 3-4 of every distributed list-sharded build (PQ and Flat):

    * host-side O(n_lists) bookkeeping — oversized-list split sizes,
      greedy-LPT ``owner``/``local_id``, per-rank offset/size/centroid
      slabs;
    * device-side routing — each row's GLOBAL within-list rank (per-rank
      prefix over the gathered count matrix ``C`` + one local stable
      sort) yields its split sublist AND its exact destination slab
      position;
    * bounded-round ``all_to_all`` exchange (buffers ~half a shard of
      padded rows per payload) with positional receive-side scatter.

    ``codes_g`` (P, n_loc, M) adds the PQ code payload; ``store_vectors``
    adds the raw-row payload. Returns (maps, slabs): host metadata
    arrays + the device-sharded ``sids`` / ``codes`` / ``vecs`` slabs.
    """
    Pn, nloc, d = x.shape
    nl = C.shape[1]
    n_valid = np.asarray(n_valid, np.int32)
    n = int(n_valid.sum())
    ax = comms.device_comms()
    sh3 = _P3(comms.axis)
    sh2 = P(comms.axis, None)
    sh1 = P(comms.axis)
    rep = P()

    # ---- phase 3 (host, O(n_lists)): cap split bookkeeping + LPT maps
    C_np = np.asarray(C).astype(np.int64)                    # (P, nl) small
    sizes = C_np.sum(0)
    cents_np = np.asarray(cents, np.float32)
    if cap:
        extra = np.maximum(0, -(-sizes // cap) - 1)
        cum = np.concatenate([[0], np.cumsum(extra)])
        base_np = (nl + cum[:nl]).astype(np.int32)
        reps = np.repeat(np.arange(nl), extra)
        jidx = np.arange(int(extra.sum())) - cum[reps] + 1
        ssz = np.concatenate([
            np.minimum(sizes, cap),
            np.clip(sizes[reps] - jidx * cap, 0, cap),
        ])
        cents_np = np.concatenate([cents_np, cents_np[reps]])
    else:
        base_np = np.zeros(nl, np.int32)
        ssz = sizes

    owner, local_id, loads, lists_per = _lpt_assign(ssz, Pn)
    max_list = max(int(ssz.max()), 1)
    n_pad = _slab_height(loads, max_list)
    nl_pad = int(lists_per.max()) + 1          # +1 empty sentinel list
    offs_sh, szs_sh, lcents_sh = _rank_slab_maps(
        owner, local_id, ssz, cents_np, Pn, nl_pad, d
    )
    # each list's owner and first slab position there, padded to the
    # most lists the split can make (each split sublist holds ``cap``
    # rows, so at most n / cap of them are added), so that the route
    # program's shapes follow rows, lists and cap alone
    n_lists_max = nl + (n // cap if cap else 0)
    owner_in = np.pad(owner, (0, n_lists_max - owner.shape[0]))
    first_in = np.pad(offs_sh[owner, local_id],
                      (0, n_lists_max - owner.shape[0]))

    # ---- phase 4a: device-side routing. Each row's GLOBAL within-list
    # rank (a per-rank prefix over the phase-2 count matrix + a local
    # stable sort) yields both its split sublist AND its exact slab
    # position on the destination rank — so the exchange below needs no
    # receive-side sort and no global-max-padded buffers. The rows in
    # destination order (``dorder``) let the exchange fill each send
    # slot by a gather.
    def route_body(lbl_sh, nv_sh, C_in, owner_in, first_in, base_in):
        lbl, nvr = lbl_sh[0], nv_sh[0]
        valid = jnp.arange(nloc, dtype=jnp.int32) < nvr
        starts = (jnp.cumsum(C_in, axis=0) - C_in)[ax.get_rank()]
        key = jnp.where(valid, lbl, nl)
        order = jnp.argsort(key, stable=True)
        ksort = key[order]
        lstart = jnp.searchsorted(
            ksort, jnp.arange(nl, dtype=jnp.int32)
        ).astype(jnp.int32)
        wsort = (
            jnp.arange(nloc, dtype=jnp.int32)
            - lstart[jnp.minimum(ksort, nl - 1)]
        )
        within = jnp.zeros((nloc,), jnp.int32).at[order].set(wsort)
        gw = starts[lbl] + within          # global rank within parent list
        if cap:
            sub = gw // cap
            nlbl = jnp.where(sub == 0, lbl, base_in[lbl] + sub - 1)
            wsub = gw % cap                # rank within the split sublist
        else:
            nlbl, wsub = lbl, gw
        dest = jnp.where(valid, owner_in[nlbl], Pn)          # Pn = dropped
        # destination slab position: owner-local list offset + sublist rank
        pos = first_in[nlbl] + wsub
        # the rows bound for each dest, contiguous and in row order:
        # send slot w to dest p is row dorder[sum(dcnt[:p]) + w]
        dorder = jnp.argsort(dest, stable=True).astype(jnp.int32)
        dcnt = jnp.zeros((Pn + 1,), jnp.int32).at[dest].add(1)[:Pn]
        return pos[None], dorder[None], ax.allgather(dcnt)

    pos_g, dorder_g, C2 = _cached_program(
        ("route", comms.mesh, comms.axis, Pn, nloc, nl, cap,
         n_lists_max),
        lambda: jax.jit(comms.shard_map(
            route_body, in_specs=(sh2, sh1, rep, rep, rep, rep),
            out_specs=(sh2, sh2, rep),
        )),
    )(lbl_g, n_valid, C, owner_in, first_in, base_np)
    C2_np = np.asarray(C2)                                   # (src, dst)
    max_send = max(1, int(C2_np.max()))

    # ---- phase 4b: bounded-round all_to_all exchange + positional slab
    # scatter. Rounds bound the padded per-payload buffer to (P, ms_r) =
    # ~half a shard of rows — regardless of P (incl. the 1-device shard
    # program) and of skewed locality concentrating one source's rows on
    # one owner, where a single global-max-padded exchange would allocate
    # P x shard and OOM at the DEEP-100M shard shape. ``ms_r`` follows
    # the shape alone and the rounds run from the host, so the round
    # programs compile once per shape, whatever the data sends where.
    ms_r = min(max(nloc, 1), max(1024, _cdiv_host(max(nloc, 1), 2 * Pn)))
    n_rounds = _cdiv_host(max_send, ms_r)
    gb_np = np.concatenate([[0], np.cumsum(n_valid)[:-1]]).astype(np.int32)
    with_codes = codes_g is not None
    codes_in = (
        codes_g if with_codes
        else jnp.zeros((Pn, 1, 1), jnp.uint8)   # unused placeholder
    )
    # send slot j of dest p is flat slot p * ms_r + j: every buffer is
    # (P * ms_r, ...), viewed as (P, ms_r, ...) across the all_to_all
    n_slot = Pn * ms_r

    def swap(buf):
        """``buf``'s ms_r-row chunk p sent to rank p; chunk s of the
        result came from rank s."""
        return ax.alltoall(buf.reshape((Pn, ms_r) + buf.shape[1:])) \
            .reshape(buf.shape)

    def send_body(x_sh, codes_sh, pos_sh, dorder_sh, gb_sh, C2_in, t_in,
                  sids_sh):
        """Round ``t``: each dest's next ``ms_r`` rows. Their ids and slab
        positions are exchanged and the ids scattered into the id slab
        here; the row (and code) payloads are gathered into the send
        buffers, with the slab positions their receiver puts them at
        (``n_pad + 1``, out of the slab, for empty slots)."""
        xb, cds = x_sh[0], codes_sh[0]
        pos, dorder, gb = pos_sh[0], dorder_sh[0], gb_sh[0]
        me = ax.get_rank()
        # where each dest's rows start in dorder
        dstart = jnp.cumsum(C2_in[me]) - C2_in[me]
        slot = jnp.arange(n_slot, dtype=jnp.int32)
        slot_p, slot_j = slot // ms_r, slot % ms_r
        w0 = t_in * ms_r
        # the row in each send slot: a gather, since a scatter of n_loc
        # rows into the send buffer compiles ~10x slower on the TPU;
        # slots past a dest's count carry rows the receiver drops
        src = dorder[jnp.minimum(dstart[slot_p] + w0 + slot_j, nloc - 1)]
        rb_gid = swap(gb + src)                              # [s] = from s
        rb_pos = swap(pos[src])
        valid_r = w0 + slot_j < C2_in[slot_p, me]
        sids = sids_sh[0].at[jnp.where(valid_r, rb_pos, n_pad)].set(
            rb_gid, mode="drop")
        outs = [sids[None], jnp.where(valid_r, rb_pos, n_pad + 1)[None]]
        if with_codes:
            outs.append(jnp.take(cds, src, axis=0)[None])
        if store_vectors:
            outs.append(jnp.take(xb, src, axis=0)[None])
        return tuple(outs)

    # the payloads' exchange is a program of its own: beside the gather
    # that fills it or the scatter that empties it, an all_to_all of
    # (P * ms_r, d) rows compiles for minutes on the TPU, alone in seconds
    def swap_body(buf_sh):
        return swap(buf_sh[0])[None]

    def recv_body(slab_sh, pc_sh, rb_sh):
        return slab_sh[0].at[pc_sh[0]].set(rb_sh[0], mode="drop")[None]

    def placed(shape, dtype):
        """Zeros sharded over the mesh, made on the devices."""
        sharding = NamedSharding(
            comms.mesh, P(comms.axis, *([None] * (len(shape) - 1))))
        return _cached_program(
            ("zeros", sharding, shape, str(dtype)),
            lambda: jax.jit(lambda: jnp.zeros(shape, dtype),
                            out_shardings=sharding),
        )()

    payloads = [name for name, on in (("codes", with_codes),
                                      ("vecs", store_vectors)) if on]
    send = _cached_program(
        ("send", comms.mesh, comms.axis, Pn, nloc, d, M, ms_r, n_pad,
         with_codes, store_vectors, str(x.dtype)),
        lambda: jax.jit(comms.shard_map(
            send_body, in_specs=(sh3, sh3, sh2, sh2, sh1, rep, rep, sh2),
            out_specs=(sh2, sh2) + (sh3,) * len(payloads),
        ), donate_argnums=(7,)),
    )

    def exchange(slab, pc, buf):
        """Scatter ``buf``'s rows, swapped over the mesh, into ``slab``
        (donated) at ``pc``."""
        key = (comms.mesh, comms.axis, buf.shape, str(buf.dtype))
        swapped = _cached_program(("swap",) + key, lambda: jax.jit(
            comms.shard_map(swap_body, in_specs=(sh3,), out_specs=sh3),
            donate_argnums=(0,),
        ))(buf)
        return _cached_program(("recv", slab.shape) + key, lambda: jax.jit(
            comms.shard_map(recv_body, in_specs=(sh3, sh2, sh3),
                            out_specs=sh3),
            donate_argnums=(0,),
        ))(slab, pc, swapped)

    slabs = {"sids": placed((Pn, n_pad), jnp.int32)}
    if with_codes:
        slabs["codes"] = placed((Pn, n_pad + 1, M), jnp.uint8)
    if store_vectors:
        slabs["vecs"] = placed((Pn, n_pad + 1, d), x.dtype)
    for t in range(n_rounds):
        slabs["sids"], pc, *bufs = send(
            x, codes_in, pos_g, dorder_g, gb_np, C2, np.int32(t),
            slabs["sids"])
        for name, buf in zip(payloads, bufs):
            slabs[name] = exchange(slabs[name], pc, buf)
        del pc, bufs

    maps = {
        "cents_np": cents_np,
        "owner": owner,
        "local_id": local_id,
        "lcents_sh": lcents_sh,
        "offs_sh": offs_sh,
        "szs_sh": szs_sh,
        "n_pad": n_pad,
        "nl_pad": nl_pad,
        "max_list": max_list,
    }
    return maps, slabs


# fields whose leading axis is the mesh axis; everything else replicates
# (shared by every sharded index type — PQ and Flat)
_SHARDED_FIELDS = frozenset({
    "local_cents", "codes_sorted", "vectors_sorted", "sorted_ids",
    "list_offsets", "list_sizes",
})


def field_sharding(comms: Comms, name: str, ndim: int):
    """The NamedSharding the sharded builds give each index field (the
    single source of the field→sharding map; serialization streams
    loaded slabs straight to it)."""
    if name in _SHARDED_FIELDS:
        return NamedSharding(
            comms.mesh, P(comms.axis, *([None] * (ndim - 1)))
        )
    return NamedSharding(comms.mesh, P())


def reshard_index(comms: Comms, index, *, replication: int = 1,
                  replica_offset: typing.Optional[int] = None):
    """Re-partition a list-sharded index built for a DIFFERENT mesh size
    onto ``comms`` — the recovery path after losing (or regaining) ranks
    (docs/robustness.md): reload the checkpoint, re-shard onto whatever
    mesh survives, keep serving.

    Host-side O(n) slab rebuild: every list's rows are copied from their
    old owner's contiguous slab segment into a freshly LPT-balanced
    layout for the new rank count (``_lpt_assign`` — the same greedy
    placement the builds use, so a reshard is exactly as balanced as a
    rebuild), with the same slab-height bucketing so the search statics
    stay coarse-stable. Quantizers, global ids, per-list contents, and
    ``max_list`` are unchanged — search results are identical to the
    original mesh's (tests/test_resilience.py asserts it). ``owner=-1``
    probe-set extras (:func:`expand_probe_set`) stay unowned.

    An R-way REPLICATED input (docs/robustness.md "Replication &
    failover") is read through its primary copies — a reshard always
    de-replicates first; pass ``replication=R`` (and optionally
    ``replica_offset``) to re-replicate the fresh layout via
    :func:`replicate_index`. Returns a host-resident index;
    :func:`place_index` (which calls this automatically on a size or
    replication mismatch) handles device placement."""
    Pn = comms.size
    owner = np.asarray(index.owner)
    local_id = np.asarray(index.local_id)
    szs = np.asarray(index.list_sizes)
    offs = np.asarray(index.list_offsets)
    sids = np.asarray(index.sorted_ids)
    cents = np.asarray(index.centroids, np.float32)
    d = cents.shape[1]
    codes = getattr(index, "codes_sorted", None)
    codes = None if codes is None else np.asarray(codes)
    vecs = (
        None if index.vectors_sorted is None
        else np.asarray(index.vectors_sorted)
    )
    nl_g = owner.shape[0]
    real = np.nonzero(owner >= 0)[0]
    errors.expects(
        real.size > 0, "reshard_index: index owns no lists (owner all -1)"
    )
    sizes = np.zeros(nl_g, np.int64)
    sizes[real] = szs[owner[real], local_id[real]]
    new_owner = np.full(nl_g, -1, np.int32)
    new_lid = np.zeros(nl_g, np.int32)
    o_r, l_r, loads, lists_per = _lpt_assign(sizes[real], Pn)
    new_owner[real] = o_r
    new_lid[real] = l_r
    # the build's shared layout helpers: identical bucketing and slab
    # geometry, so statics stay stable across repeated reshards
    n_pad = _slab_height(loads, index.max_list)
    nl_pad = int(lists_per.max()) + 1          # +1 empty sentinel list
    offs_sh, szs_sh, lcents_sh = _rank_slab_maps(
        new_owner, new_lid, sizes, cents, Pn, nl_pad, d
    )

    new_sids = np.zeros((Pn, n_pad), np.int32)
    new_codes = (
        None if codes is None
        else np.zeros((Pn, n_pad + 1, codes.shape[2]), codes.dtype)
    )
    new_vecs = (
        None if vecs is None
        else np.zeros((Pn, n_pad + 1, vecs.shape[2]), vecs.dtype)
    )
    for l in real.tolist():
        sz = int(sizes[l])
        if sz == 0:
            continue
        ro, jo = int(owner[l]), int(local_id[l])
        rn, jn = int(new_owner[l]), int(new_lid[l])
        src = slice(int(offs[ro, jo]), int(offs[ro, jo]) + sz)
        dst = slice(int(offs_sh[rn, jn]), int(offs_sh[rn, jn]) + sz)
        new_sids[rn, dst] = sids[ro, src]
        if new_codes is not None:
            new_codes[rn, dst] = codes[ro, src]
        if new_vecs is not None:
            new_vecs[rn, dst] = vecs[ro, src]

    kw = dict(
        owner=new_owner, local_id=new_lid, local_cents=lcents_sh,
        sorted_ids=new_sids, list_offsets=offs_sh, list_sizes=szs_sh,
        n_pad=n_pad, nl_pad=nl_pad, replication=1, replica_offset=1,
    )
    if new_codes is not None:
        kw["codes_sorted"] = new_codes
    if new_vecs is not None:
        kw["vectors_sorted"] = new_vecs
    out = dataclasses.replace(index, **kw)
    if replication > 1:
        out = replicate_index(out, replication, offset=replica_offset)
    return out


def replicate_index(index, replication: int, *,
                    offset: typing.Optional[int] = None):
    """R-way replicate a list-sharded index's slabs for zero-coverage-
    loss failover (docs/robustness.md "Replication & failover").

    Host-side O(R·n) slab rebuild over the STRIPED placement
    (:class:`raft_tpu.resilience.ReplicaPlacement`): rank ``r``'s new
    slab is the concatenation of R segments — segment 0 its own primary
    shard's existing layout (offsets, local ids, and rows unchanged, so
    the healthy serving program needs no routing at all), segment ``j``
    an exact copy of rank ``(r - j*offset) % P``'s primary layout. The
    degraded searches' ``failover=`` route then selects at RUNTIME which
    copy serves each logical shard: with any ≤ R-1 failures per replica
    group every list stays served by exactly one live rank, coverage
    stays 1.0, and results are identical to the healthy mesh.

    Memory cost is exactly R× the slab footprint (rows, codes, ids, per-
    rank centroid tables — quantizers and ownership maps were already
    replicated). The input must be unreplicated (``replication == 1``);
    :func:`place_index(..., replication=R)` handles stripping/resharding
    first. Works on both sharded engines (field names shared). Returns a
    host-resident index."""
    from raft_tpu.resilience.replica import ReplicaPlacement

    errors.expects(
        int(getattr(index, "replication", 1) or 1) == 1,
        "replicate_index: index is already %d-way replicated — reshard "
        "first (place_index(..., replication=R) does both)",
        getattr(index, "replication", 1),
    )
    Pn = int(index.sorted_ids.shape[0])
    placement = ReplicaPlacement.striped(Pn, replication, offset)
    if replication == 1:
        return dataclasses.replace(index, replication=1, replica_offset=1)
    offs = np.asarray(index.list_offsets)
    szs = np.asarray(index.list_sizes)
    lcents = np.asarray(index.local_cents)
    sids = np.asarray(index.sorted_ids)
    codes = getattr(index, "codes_sorted", None)
    codes = None if codes is None else np.asarray(codes)
    vecs = (
        None if index.vectors_sorted is None
        else np.asarray(index.vectors_sorted)
    )
    nlp0 = int(index.nl_pad)
    d = lcents.shape[2]
    valid = offs[:, -1]                    # rows in each rank's slab
    segs = [placement.segments(r) for r in range(Pn)]
    n_pad = _slab_height(
        [int(sum(valid[s] for s in segs[r])) for r in range(Pn)]
    )
    nl_pad = replication * nlp0
    new_szs = np.zeros((Pn, nl_pad), np.int32)
    new_offs = np.zeros((Pn, nl_pad + 1), np.int32)
    new_lcents = np.zeros((Pn, nl_pad, d), lcents.dtype)
    new_sids = np.zeros((Pn, n_pad), np.int32)
    new_codes = (
        None if codes is None
        else np.zeros((Pn, n_pad + 1, codes.shape[2]), codes.dtype)
    )
    new_vecs = (
        None if vecs is None
        else np.zeros((Pn, n_pad + 1, vecs.shape[2]), vecs.dtype)
    )
    for r in range(Pn):
        # list tables: R primary tables stacked — copy j of list l lands
        # at local id j*nlp0 + local_id[l], and the cumsum over the
        # concatenated sizes places segment j's rows right after
        # segments 0..j-1's valid rows (each old table's sizes sum to
        # its valid count), so whole contiguous regions copy over
        for j, s in enumerate(segs[r]):
            new_szs[r, j * nlp0:(j + 1) * nlp0] = szs[s]
            new_lcents[r, j * nlp0:(j + 1) * nlp0] = lcents[s]
        new_offs[r] = np.concatenate([[0], np.cumsum(new_szs[r])])
        start = 0
        for s in segs[r]:
            n_s = int(valid[s])
            new_sids[r, start:start + n_s] = sids[s, :n_s]
            if new_codes is not None:
                new_codes[r, start:start + n_s] = codes[s, :n_s]
            if new_vecs is not None:
                new_vecs[r, start:start + n_s] = vecs[s, :n_s]
            start += n_s
    kw = dict(
        local_cents=new_lcents, sorted_ids=new_sids,
        list_offsets=new_offs, list_sizes=new_szs,
        n_pad=n_pad, nl_pad=nl_pad,
        replication=replication, replica_offset=placement.offset,
    )
    if new_codes is not None:
        kw["codes_sorted"] = new_codes
    if new_vecs is not None:
        kw["vectors_sorted"] = new_vecs
    return dataclasses.replace(index, **kw)


def place_index(comms: Comms, index, *,
                replication: typing.Optional[int] = None,
                replica_offset: typing.Optional[int] = None):
    """(Re-)place a sharded index's arrays onto a comms mesh: slabs shard
    over the mesh axis, quantizers and ownership maps replicate. Works on
    any sharded index dataclass (MnmgIVFPQIndex, MnmgIVFFlatIndex); used
    by the builds themselves and after
    :func:`raft_tpu.spatial.ann.load_index`. An index built for a
    DIFFERENT mesh size is re-partitioned first via
    :func:`reshard_index` — the recovery path after losing a rank
    (docs/robustness.md).

    ``replication=R`` builds (or rebuilds) the R-way striped replica
    layout (:func:`replicate_index`) so the degraded searches can fail
    over a dead rank's lists onto a live replica with zero coverage
    loss (docs/robustness.md "Replication & failover"); ``None``
    preserves the index's current replication across the placement.
    ``replica_offset`` overrides the stripe offset (default
    ``max(1, P // R)``; on a :class:`~raft_tpu.comms.comms.
    HierarchicalComms` with R ≤ the host count the default is the
    HOST-AWARE stripe instead — :func:`raft_tpu.comms.multihost.
    host_aware_offset` steps copies by whole hosts, so a whole dead
    host still leaves every shard a live copy — docs/multihost.md
    "Host-aware placement").

    An index with NO sharded fields (the graph-ANN
    :class:`~raft_tpu.spatial.ann.graph.GraphIndex` — a low-latency
    design whose working set fits one chip) replicates whole onto every
    device: every array leaf lands fully-replicated on the mesh, so the
    supervisor/result-cache tier serves it through the same placement
    entry as the IVF engines. ``replication``/``replica_offset`` are
    meaningless for (and rejected on) such an index — every rank
    already holds a full copy."""
    field_names = {f.name for f in dataclasses.fields(type(index))}
    if not (field_names & _SHARDED_FIELDS):
        errors.expects(
            replication is None and replica_offset is None,
            "place_index: index type %s has no sharded fields — it "
            "replicates whole; replication/replica_offset do not apply",
            type(index).__name__,
        )
        sh = NamedSharding(comms.mesh, P())
        kw = {}
        for f in dataclasses.fields(type(index)):
            v = getattr(index, f.name)
            if v is not None and f.metadata.get("static") is None:
                if dataclasses.is_dataclass(v):
                    v = compat.tree_map(
                        lambda a: jax.device_put(a, sh), v
                    )
                else:
                    v = jax.device_put(v, sh)
            kw[f.name] = v
        return type(index)(**kw)
    n_ranks = index.sorted_ids.shape[0]
    if replica_offset is None and replication is not None \
            and int(replication) > 1:
        n_hosts, inner_width = comms_levels(comms)
        if 1 < n_hosts and int(replication) <= n_hosts:
            replica_offset = host_aware_offset(
                comms.size, inner_width, int(replication)
            )
    cur_r = int(getattr(index, "replication", 1) or 1)
    cur_off = int(getattr(index, "replica_offset", 1) or 1)
    want_r = cur_r if replication is None else int(replication)
    if (
        n_ranks != comms.size
        or want_r != cur_r
        or (replica_offset is not None and want_r > 1
            and int(replica_offset) != cur_off)
    ):
        if n_ranks == comms.size and cur_r == 1:
            # same mesh, unreplicated input: the layout is already what
            # replicate_index consumes — skip the O(n) reshard pass
            index = replicate_index(
                index, want_r, offset=replica_offset
            )
        else:
            index = reshard_index(
                comms, index, replication=want_r,
                replica_offset=replica_offset,
            )
    kw = {}
    for f in dataclasses.fields(type(index)):
        v = getattr(index, f.name)
        if v is not None and f.metadata.get("static") is None:
            if dataclasses.is_dataclass(v):
                # nested pytree (the two-level CoarseIndex): every array
                # leaf replicates — it is probe metadata, never sharded
                sh = NamedSharding(comms.mesh, P())
                v = compat.tree_map(lambda a: jax.device_put(a, sh), v)
            else:
                v = jax.device_put(
                    v, field_sharding(comms, f.name, np.ndim(v))
                )
                if f.name == getattr(index, "ROW_STORE", None):
                    v = place_row_store(v)
        kw[f.name] = v
    return type(index)(**kw)


def recover_rank(comms: Comms, index, path, rank: int):
    """Online re-placement of ONE rank's slab content from a saved
    checkpoint — the spare/healed-rank recovery path (docs/robustness.md
    "Replication & failover"): after :class:`~raft_tpu.resilience.FailoverPlan`
    routed a dead rank's shards onto replicas, a replacement chip joins,
    its lost slabs are restored from the v2+/v3 checkpoint (CRC-verified
    by :func:`raft_tpu.spatial.ann.load_index`), health flips up, and
    the route flips back to primaries — no k-means, no re-encode, no
    row exchange, no full-index re-placement.

    The checkpoint must carry the SAME layout as the live index (mesh
    size, slab heights, replication geometry, ownership maps) — i.e. a
    checkpoint of this very build; a layout mismatch raises rather than
    splicing rows into the wrong slots (restore onto a different mesh
    goes through ``load_index(comms=)``/:func:`place_index` instead).
    Only ``rank``'s rows of the sharded slab fields are spliced in; the
    update is a functional ``.at[rank].set`` re-placed onto the mesh
    sharding. Returns the recovered index."""
    from raft_tpu.spatial.ann.serialize import load_index

    errors.expects(
        0 <= rank < comms.size,
        "recover_rank: rank %d out of range [0, %d)", rank, comms.size,
    )
    host = load_index(path)
    errors.expects(
        type(host) is type(index),
        "recover_rank: checkpoint holds a %s, live index is a %s",
        type(host).__name__, type(index).__name__,
    )
    for name in ("n_pad", "nl_pad", "max_list", "n_rows",
                 "replication", "replica_offset"):
        errors.expects(
            getattr(host, name, None) == getattr(index, name, None),
            "recover_rank: checkpoint %s=%r != live index %s=%r — not a "
            "checkpoint of this build (restore via load_index/place_index)",
            name, getattr(host, name, None), name,
            getattr(index, name, None),
        )
    errors.expects(
        host.sorted_ids.shape[0] == comms.size
        and index.sorted_ids.shape[0] == comms.size,
        "recover_rank: rank counts differ (checkpoint %d, index %d, "
        "mesh %d)", host.sorted_ids.shape[0], index.sorted_ids.shape[0],
        comms.size,
    )
    errors.expects(
        np.array_equal(np.asarray(host.owner), np.asarray(index.owner)),
        "recover_rank: checkpoint ownership map differs from the live "
        "index — its slab rows would splice into the wrong lists",
    )
    kw = {}
    for f in dataclasses.fields(type(index)):
        if f.name not in _SHARDED_FIELDS:
            continue
        cur = getattr(index, f.name)
        src = getattr(host, f.name)
        if cur is None and src is None:
            continue
        errors.expects(
            cur is not None and src is not None
            and tuple(np.shape(src)) == tuple(np.shape(cur)),
            "recover_rank: field %r shape mismatch (checkpoint %s, live "
            "%s)", f.name,
            None if src is None else tuple(np.shape(src)),
            None if cur is None else tuple(np.shape(cur)),
        )
        row = jnp.asarray(np.asarray(src)[rank])
        updated = jnp.asarray(cur).at[rank].set(row)
        kw[f.name] = jax.device_put(
            updated, field_sharding(comms, f.name, updated.ndim)
        )
    return dataclasses.replace(index, **kw)


def _merge_local_delta(qf, vals, gids, dvl, dil, k, rank, nl_pad,
                       replication, replica_offset, n_ranks, alive,
                       route):
    """Shard-local tail of the MUTATION-tier fused programs (both
    engines): exactly-score this rank's delta segments against the
    replicated queries and fold the top-k into the rank's (nq, k)
    contribution BEFORE the cross-shard merge.

    ``dvl``/``dil`` are the rank's flattened (nl_pad*cap, d)/(nl_pad*cap,)
    delta slabs. Replica discipline mirrors the main scan's serve rule:
    a delta entry is scanned only by the rank whose slab SEGMENT is
    currently serving its logical shard (healthy/all-zeros route →
    segment 0, i.e. primaries), so replicated delta copies never
    duplicate in the merge and a failover flip moves delta serving to
    the replica with the same runtime ``route`` input — tombstones and
    delta rows behave identically on primary and replica copies
    (docs/mutation.md "Sharded mutation"). The scan/fold itself is the
    single-chip tier's ``delta_merge_topk`` — one implementation."""
    from raft_tpu.spatial.ann.mutation import delta_merge_topk

    DL = dil.shape[0]
    cap = DL // nl_pad
    nlp_base = nl_pad // replication
    seg = (jnp.arange(DL, dtype=jnp.int32) // cap) // nlp_base
    if route is not None:
        shard_of = (rank - seg * replica_offset) % n_ranks
        serve = (route[shard_of] == seg) & (alive[rank] > 0)
    else:
        serve = seg == 0
    return delta_merge_topk(
        qf, vals, gids, dvl, dil, serve & (dil >= 0), k
    )


def _merge_across_shards(ax, hier, vals, gids, k, merge_ways, wire):
    """The in-program cross-shard merge tail shared by both engine
    bodies (device-side, inside shard_map).

    1-level mesh (``hier=None``): the flat deployment-width allgather +
    ``merge_parts_select_k`` — unchanged from the single-host tier.

    2-level mesh: the hierarchical ICI × DCN merge (docs/multihost.md):
    the flat stage runs at ICI width WITHIN each slice (``merge_ways``
    pads it to the per-host deployment chip count, exactly as before),
    then only each slice's top-k crosses hosts in the compressed wire
    format (:func:`raft_tpu.comms.multihost.hierarchical_merge_select_k`
    — bf16 values + int32 ids, f32 rerank tail). The DCN exchange is
    part of the one fused dispatch, so the ServingExecutor's in-flight
    window pipelines it against the next micro-batch's shard compute.
    """
    if hier is None:
        pd = ax.allgather(vals)                          # (P, nq, k)
        pi = ax.allgather(gids)
        md, mi = merge_parts_select_k(pd, pi, k, ways=merge_ways)
    else:
        outer_ax, inner_ax = hier[0], hier[1]
        inner = AxisComms(inner_ax)
        pd = inner.allgather(vals)                       # (I, nq, k)
        pi = inner.allgather(gids)
        sv, si = merge_parts_select_k(pd, pi, k, ways=merge_ways)
        md, mi = hierarchical_merge_select_k(
            AxisComms(outer_ax), sv, si, k, wire=wire or "bf16"
        )
    return md, jnp.where(jnp.isfinite(md), mi, -1)


@functools.lru_cache(maxsize=32)
def _cached_search(
    mesh: jax.sharding.Mesh, axis: str, store_raw: bool, statics: tuple,
    donate: bool = False, degraded: bool = False, mutation: bool = False,
):
    """Compile one shard_map search program per (mesh, static-config).

    Keyed on (mesh, axis) — both value-hashable — rather than the Comms
    object (identity-hashed): a caller constructing a fresh Comms per
    search still hits the cached program, and the cache never retains
    dead Comms instances.

    ``donate=True`` donates the query buffer to the runtime (serving
    dispatch: the output may alias the input's memory and no copy of the
    batch survives the call — the caller must not reuse the array).

    ``degraded=True`` compiles the resilient serving variant: TWO extra
    (P,) int32 RUNTIME inputs (so health AND failover flips never
    recompile) — ``alive`` masks a down shard's contribution to +inf
    before the merge, and ``route`` selects which replica copy serves
    each logical shard (all zeros = primaries; with an R-way replicated
    index a :class:`~raft_tpu.resilience.FailoverPlan` routes a dead
    rank's shards onto live replica segments with zero coverage loss —
    docs/robustness.md "Replication & failover"). Non-finite query rows
    are neutralized in-graph, and the program returns
    ``(dists, ids, coverage, row_valid)``
    (raft_tpu.resilience.degraded; docs/robustness.md).

    The ``use_coarse``/``overprobe``/``merge_ways`` statics select the
    probe and merge widths:
    ``use_coarse``/``overprobe`` engage the fused two-level coarse probe
    (three extra replicated CoarseIndex array inputs), and ``merge_ways``
    pads the allgathered per-shard payloads with +inf/-1 entries up to a
    deployment's shard count so the in-program ``select_k`` merge runs at
    deployment width on a smaller mesh (results are bit-identical to the
    unpadded merge — emulated absent peers contribute nothing, exactly
    like owner=-1 lists)."""
    (k, n_probes, qcap, list_block, refine_ratio, exact_selection,
     approx_recall_target, pq_dim, pq_bits, n_pad, nl_pad, max_list,
     use_coarse, overprobe, merge_ways, replication,
     replica_offset, use_pallas, pallas_interpret, wire) = statics
    comms = Comms(mesh=mesh, axis=axis)
    ax = comms.device_comms()
    n_ranks = comms.size
    # 2-level (ICI x DCN) mesh: the merge tail goes hierarchical
    # (docs/multihost.md); everything before it is per-chip and
    # unchanged. hier is a pure function of (mesh, axis) — the cache
    # key already distinguishes it.
    hier = hier_axes(mesh, axis)

    def body(*opnds):
        (cents, cbs, owner, local_id, lcents, codes_s, vecs_s, sids,
         loffs, lszs, q, sup_c, mem_i, cpad) = opnds[:14]
        rest = list(opnds[14:])
        alive = route = None
        if degraded:
            alive, route = rest[0], rest[1]
            rest = rest[2:]
        rm_s = dv_s = di_s = None
        if mutation:
            # mutation-tier runtime inputs (comms/mnmg_mutation.py):
            # per-rank tombstone row mask + flattened delta segments —
            # upsert/delete flips change VALUES only, never the program
            rm_s, dv_s, di_s = rest
        # sharded slabs arrive as (1, ...) blocks — drop the mesh axis
        lcents, codes_s, sids = lcents[0], codes_s[0], sids[0]
        loffs, lszs = loffs[0], lszs[0]
        vecs = vecs_s[0] if store_raw else None
        rank = lax.axis_index(ax.axis)

        qf = q.astype(jnp.float32)
        row_valid = None
        if degraded:
            qf, row_valid = sanitize_query_rows(qf)
        # replicated compute: identical global probes on every chip —
        # queries never move, only the (nq, k) results do
        if use_coarse:
            # use_pallas (the same static that selects the shard-local
            # scan engine) also kernelizes the probe stage: both of the
            # two-level probe's distance tiles stay in VMEM inside this
            # fused program (scan_core; auto-degrades to the legacy
            # probe when the probe geometry does not fit the plan)
            probes_g, _ = two_level_probe(
                qf, sup_c, mem_i, cpad, owner.shape[0], n_probes,
                n_super_probes(n_probes, sup_c.shape[0], overprobe),
                _PROBE_BLOCK_Q, use_pallas=use_pallas,
                pallas_interpret=pallas_interpret,
            )
        else:
            probes_g, _ = coarse_probe(qf, cents, n_probes)  # (nq, p)
        probe_owner = owner[probes_g]                        # (nq, p)
        if degraded:
            # replica-aware routing: route[s] (runtime, like alive)
            # names the copy index serving logical shard s, so the rank
            # holding that copy serves the probe from its slab segment
            # j (local id j*nlp_base + primary local id). All-zeros
            # route == primaries == the unrouted serve rule; failover
            # flips change VALUES only — never the program.
            j = route[jnp.clip(probe_owner, 0, n_ranks - 1)]
            serving = jnp.where(
                (probe_owner >= 0) & (j >= 0),
                (probe_owner + jnp.maximum(j, 0) * replica_offset)
                % n_ranks,
                -1,
            )                                # (nq, p) serving rank | -1
            own = serving == rank
            nlp_base = nl_pad // replication
            lp = jnp.where(
                own,
                jnp.maximum(j, 0) * nlp_base + local_id[probes_g],
                jnp.int32(nl_pad - 1),                       # sentinel
            )
        else:
            serving = probe_owner
            own = probe_owner == rank
            lp = jnp.where(
                own, local_id[probes_g],
                jnp.int32(nl_pad - 1),                       # sentinel
            )

        storage = ListStorage(
            sorted_ids=sids,
            list_offsets=loffs,
            list_index=jnp.zeros((1, 1), jnp.int32),  # grouped path unused
            list_sizes=lszs,
            n=n_pad,
            max_list=max_list,
        )
        shard = IVFPQIndex(
            centroids=lcents, codebooks=cbs, codes_sorted=codes_s,
            storage=storage, vectors_sorted=vecs,
            pq_dim=pq_dim, pq_bits=pq_bits,
        )
        # the UNCHANGED single-chip grouped kernel, probes pre-mapped to
        # shard-local list ids; sorted_ids are global so ids need no
        # translation downstream (use_pallas routes the shard-local ADC
        # scan through the Pallas sub-chunk-min engine INSIDE the fused
        # one-dispatch program — docs/ivf_scale.md "ADC in VMEM")
        vals, gids = _pq_grouped_impl(
            shard, qf, k, n_probes, qcap, list_block, refine_ratio,
            None, lp, exact_selection, approx_recall_target,
            use_pallas=use_pallas, pallas_interpret=pallas_interpret,
            row_mask=rm_s[0] if mutation else None,
        )
        if mutation:
            vals, gids = _merge_local_delta(
                qf, vals, gids, dv_s[0], di_s[0], k, rank, nl_pad,
                replication, replica_offset, n_ranks, alive, route,
            )
        if degraded:
            # a down shard contributes +inf distances to the merge — its
            # candidates can never displace a live shard's
            vals = jnp.where(alive[rank] > 0, vals, jnp.inf)
        # k-way merge, executed IN-PROGRAM (the cross-shard merge is
        # part of the one serving dispatch, not host composition):
        # flat allgather + select_k on a 1-level mesh (merge_ways pads
        # to deployment width with +inf/-1 absent-peer payloads), the
        # two-stage ICI x DCN merge with the compressed wire format on
        # a 2-level mesh (docs/multihost.md)
        md, mi = _merge_across_shards(
            ax, hier, vals, gids, k, merge_ways, wire
        )
        if degraded:
            # coverage counts a probe served iff SOME live rank serves
            # it under the route — a failed-over shard on a live
            # replica counts covered (coverage 1.0, zero loss)
            cov = probe_coverage(serving, alive, row_valid)
            md, mi = mask_invalid_rows(md, mi, row_valid)
            return md, mi, cov, row_valid
        return md, mi

    sharded = P(comms.axis, None, None)
    sharded2 = P(comms.axis, None)
    rep2 = P(None, None)
    rep3 = P(None, None, None)
    in_specs = (
        rep2, rep3, P(None), P(None),
        sharded, sharded,
        sharded if store_raw else rep3,
        sharded2, sharded2, sharded2, rep2,
        rep2, rep2, rep3,           # coarse: super_cents, member_ids, pad
    )
    out_specs = (rep2, rep2)
    if degraded:
        in_specs = in_specs + (P(None), P(None))     # alive, route
        out_specs = (rep2, rep2, P(None), P(None))
    if mutation:
        # row_mask, delta_vecs, delta_ids — per-rank mutation slabs
        in_specs = in_specs + (sharded2, sharded, sharded2)
    sm = comms.shard_map(body, in_specs=in_specs, out_specs=out_specs)
    # queries are positional argument 10 (the coarse arrays and, when
    # present, the alive mask + failover route and the mutation slabs
    # follow them); donation frees/aliases the batch buffer for the
    # outputs (index slabs are never donated)
    return jax.jit(sm, donate_argnums=(10,) if donate else ())


def _coarse_probe_operands(index, d):
    """The three replicated CoarseIndex operands of the fused search
    program (shape-stable placeholders when the index carries no coarse
    quantizer, so both variants present the same input pytree)."""
    if index.coarse is not None:
        c = index.coarse
        return c.super_cents, c.member_ids, c.cents_padded
    return (
        jnp.zeros((1, d), jnp.float32),
        jnp.zeros((1, 1), jnp.int32),
        jnp.zeros((1, 1, d), jnp.float32),
    )


def _mutation_operands(mutation, index, n_ranks: int):
    """Normalize a search's ``mutation=`` argument (None, an
    ``MnmgMutationState``, or an ``MnmgMutableIndex`` wrapper) to the
    three per-rank runtime operands of the mutation-tier program —
    ``(row_mask (P, n_pad+1), delta_vecs (P, nl_pad*cap, d),
    delta_ids (P, nl_pad*cap))`` — or None. Shapes are validated against
    the index layout so a state built for a different geometry cannot
    splice rows into the wrong slots."""
    if mutation is None:
        return None
    state = getattr(mutation, "state", mutation)
    rm, dv, di = state.row_mask, state.delta_vecs, state.delta_ids
    errors.expects(
        tuple(rm.shape) == (n_ranks, index.n_pad + 1),
        "mutation state row_mask shape %s does not match the index "
        "layout (%s)", tuple(rm.shape), (n_ranks, index.n_pad + 1),
    )
    errors.expects(
        dv.ndim == 3 and dv.shape[0] == n_ranks
        and dv.shape[1] % index.nl_pad == 0
        and tuple(di.shape) == tuple(dv.shape[:2]),
        "mutation state delta slabs (%s / %s) do not match the index "
        "layout (P=%d, nl_pad=%d)", tuple(dv.shape), tuple(di.shape),
        n_ranks, index.nl_pad,
    )
    return rm, dv, di


def _check_probe_args(index, nl_g, overprobe, merge_ways, merge_floor,
                      wire="bf16"):
    """Shared validation of the probe/merge knobs (both engines).
    ``merge_floor`` is the width the padded flat merge stage actually
    runs at — the mesh size on a 1-level mesh, the ICI (per-slice)
    width on a 2-level mesh, where ``merge_ways`` emulates a wider HOST,
    not a wider fleet (more hosts just ARE more DCN parts)."""
    errors.expects(
        index.coarse is None or index.coarse.n_cents == nl_g,
        "coarse index covers %d centroids but the probe set has %d — "
        "rebuild it (attach_coarse_index; expand_probe_set rebuilds "
        "automatically)",
        None if index.coarse is None else index.coarse.n_cents, nl_g,
    )
    errors.expects(
        overprobe >= 1.0,
        "overprobe=%s out of range [1, inf)", overprobe,
    )
    errors.expects(
        merge_ways is None
        or (isinstance(merge_ways, (int, np.integer))
            and merge_ways >= merge_floor),
        "merge_ways=%r must be an int >= the merge stage width (%d) — "
        "it emulates a WIDER deployment's merge, never a narrower one",
        merge_ways, merge_floor,
    )
    errors.expects(
        wire in ("bf16", "f32"),
        "wire=%r not a known cross-host wire format (bf16 | f32)", wire,
    )


def expand_probe_set(index, extra_centroids):
    """Extend a sharded index's GLOBAL probe set with centroids owned by
    no rank — the deployment view that turns the per-chip serving cost
    into ONE measured program on fewer chips than the deployment holds.

    The fused search program probes the full (replicated) centroid set
    and routes unowned probes to the empty sentinel list; centroids added
    here carry owner ``-1``, which no rank matches, so they behave
    exactly like lists owned by an absent peer chip. Searching the
    returned index on a 1-device mesh therefore runs a chip's exact share
    of a larger deployment — deployment-scale coarse probe fused with the
    shard-local search, one dispatch, no host composition. Paired with
    ``merge_ways=`` on the search, the in-program cross-shard merge also
    runs at deployment width (bench.py's
    ``measured_chip_qps``/``sharded_e2e_qps`` rows). Works on both
    sharded engines (field names are shared); slabs are aliased, not
    copied. An attached two-level coarse index
    (:func:`attach_coarse_index`) is REBUILT over the expanded probe set
    so the sub-linear probe covers the extras too.
    """
    extra = jnp.asarray(extra_centroids, jnp.float32)
    errors.expects(
        extra.ndim == 2 and extra.shape[1] == index.centroids.shape[1],
        "extra_centroids: expected (m, %d), got %s",
        index.centroids.shape[1], tuple(extra.shape),
    )
    n_extra = extra.shape[0]
    out = dataclasses.replace(
        index,
        centroids=jnp.concatenate(
            [jnp.asarray(index.centroids, jnp.float32), extra]
        ),
        owner=jnp.concatenate(
            [jnp.asarray(index.owner),
             jnp.full((n_extra,), -1, jnp.int32)]
        ),
        local_id=jnp.concatenate(
            [jnp.asarray(index.local_id),
             jnp.zeros((n_extra,), jnp.int32)]
        ),
        coarse=None,
    )
    if index.coarse is not None:
        # replay the user's coarse tuning (build_args records the
        # ORIGINAL attach_coarse_index arguments, None where defaulted,
        # so scale-dependent defaults re-derive for the wider set)
        n_sup, cap, iters, seed = index.coarse.build_args
        out = attach_coarse_index(
            out, n_super=n_sup, member_cap=cap, kmeans_n_iters=iters,
            seed=seed,
        )
    return out


def attach_coarse_index(index, *, n_super=None, member_cap=None,
                        kmeans_n_iters: int = 10, seed: int = 0):
    """Attach (or rebuild) a two-level coarse quantizer
    (:class:`raft_tpu.spatial.ann.common.CoarseIndex`) over a sharded
    index's GLOBAL probe set — the sub-linear replacement for the fused
    serving program's brute centroid scan, which at deployment scale
    (~65k global centroids) dominates the per-chip serving cost
    (BENCH_r05: probe ~50 ms of the 16k-query dispatch).

    Works on both sharded engines (field names are shared). The searches
    engage the two-level probe automatically when the index carries it;
    ``overprobe=`` on the search trades probe FLOPs for probe recall
    (audit with :func:`raft_tpu.spatial.ann.common.coarse_probe_recall`).
    Serialization carries it (format v3, older formats load with
    ``coarse=None``); :func:`expand_probe_set` rebuilds it over the
    expanded set."""
    coarse = build_coarse_index(
        index.centroids, n_super=n_super, member_cap=member_cap,
        kmeans_n_iters=kmeans_n_iters, seed=seed,
    )
    return dataclasses.replace(index, coarse=coarse)


def mnmg_ivf_pq_search(
    comms: Comms, index: MnmgIVFPQIndex, queries, k: int, *,
    n_probes: int = 8, qcap: typing.Union[int, str, None] = None,
    list_block: int = 8,
    refine_ratio: float = 2.0, exact_selection: bool = True,
    approx_recall_target: float = 0.95,
    qcap_max_drop_frac: typing.Optional[float] = None,
    donate_queries: bool = False,
    shard_mask=None,
    failover=None,
    overprobe: float = 2.0,
    merge_ways: typing.Optional[int] = None,
    use_pallas: typing.Optional[bool] = None,
    mutation=None,
    wire: str = "bf16",
):
    """Distributed grouped ADC search over a list-sharded index.

    Returns (exact-refined squared L2 distances, GLOBAL row ids), both
    (nq, k) and replicated on every chip. Semantics match
    :func:`raft_tpu.spatial.ann.ivf_pq.ivf_pq_search_grouped` on the same
    data — each probed list is searched by exactly one chip with the same
    kernel, and per-chip top-c refinement pools are supersets of the
    single-chip pool's per-list contributions, so recall parity holds
    (tests/test_mnmg_ivf.py asserts it on an 8-device mesh).

    ``exact_selection`` defaults to True here (the single-chip grouped
    search defaults to the hardware approx top-k): under shard_map's
    manual partitioning the ApproxTopK custom call loses its fast TPU
    lowering and measured 3.4x SLOWER than exact ``lax.top_k`` at the
    500k x 96 bench shape (3350 vs 11558 QPS, identical recall —
    docs/ivf_scale.md "The shard_map approx-top-k tax"). Set it False
    only after measuring on your toolchain.

    ``qcap`` as in the single-chip grouped search; the ``None`` auto path
    sizes it from the actual global probe map (one eager coarse probe +
    host sync — pass an explicit qcap for async serving dispatch), and
    ``qcap="throughput"`` picks ~0.75x the mean probe occupancy
    (common.throughput_qcap — measured 33k QPS vs 10k at the 500k bench
    shape at identical recall).

    ``donate_queries=True`` donates the query buffer (outputs may reuse
    its memory; the caller must not touch the array after the call) — the
    serving-dispatch mode, paired with an explicit integer ``qcap`` and
    :meth:`MnmgIVFPQIndex.warmup` so the dispatch is fully async with no
    host-side sync or trace (docs/serving.md).

    ``shard_mask`` selects the RESILIENT serving variant
    (docs/robustness.md): pass a per-rank validity mask — a
    :class:`raft_tpu.resilience.ShardHealth`, an array-like of (P,)
    truth values, or ``True`` for all-up — and the search degrades
    instead of failing: a down shard contributes +inf distances,
    non-finite query rows are neutralized in-graph, and the return type
    becomes :class:`raft_tpu.resilience.PartialSearchResult` carrying
    per-query ``coverage`` and the ``partial`` flag. The mask is a
    runtime input: flipping a rank's health never recompiles.

    ``failover`` (requires ``shard_mask``) routes logical shards onto
    replica copies at RUNTIME: pass a
    :class:`raft_tpu.resilience.FailoverPlan` (or a ``(P,)`` copy-index
    array) built from the same health state, and — on an R-way
    replicated index (``place_index(..., replication=R)``) — any ≤ R-1
    failures per replica group serve every list from a live replica
    segment: ``coverage`` stays 1.0 and results are identical to the
    healthy mesh. Like the mask, the route is a runtime input — failover
    flips never recompile (docs/robustness.md "Replication & failover").
    Note a failover rank scans up to R shards' worth of non-empty lists;
    its latency grows accordingly (the hedging rationale).

    ``overprobe`` (static) widens the two-level coarse probe's super
    scan when the index carries a coarse quantizer
    (:func:`attach_coarse_index`; ignored otherwise). ``merge_ways``
    (static) pads the in-program cross-shard merge to a deployment's
    shard count — results are identical (absent peers contribute
    +inf/-1), the ``select_k`` runs at deployment width.

    ``use_pallas`` (static) selects the shard-local ADC engine inside
    the fused program — auto (``None``) engages the Pallas
    sub-chunk-min kernel on TPU when refinement is active, exactly as
    :func:`~raft_tpu.spatial.ann.ivf_pq.ivf_pq_search_grouped`
    documents; the knob is a trace-time static, so like every other
    static it never varies with health/failover state (zero retraces on
    flips, trace-audited).

    ``mutation`` engages the MUTATION-tier variant
    (:mod:`raft_tpu.comms.mnmg_mutation`): pass an
    :class:`~raft_tpu.comms.mnmg_mutation.MnmgMutationState` (or the
    :class:`~raft_tpu.comms.mnmg_mutation.MnmgMutableIndex` wrapper) and
    the fused program folds the per-rank tombstone row mask into the
    shard-local scan and merges an exact scan of the rank's delta
    segments before the cross-shard merge. All mutation inputs are
    RUNTIME values — upserts, tombstone flips, and health/failover flips
    share one compiled program (docs/mutation.md "Sharded mutation").

    ``wire`` (static; 2-level meshes only) selects the cross-host wire
    format of the hierarchical merge's DCN stage when ``comms`` is a
    :class:`~raft_tpu.comms.comms.HierarchicalComms` with more than one
    slice: ``"bf16"`` (default — compressed values + the f32 rerank
    tail) or ``"f32"`` (uncompressed, bit-identical to the flat merge
    by construction). Ignored on 1-level meshes; docs/multihost.md
    states the byte model and the quantization contract.
    """
    fn, args, degraded = _prepare_pq_search(
        comms, index, queries, k, n_probes=n_probes, qcap=qcap,
        list_block=list_block, refine_ratio=refine_ratio,
        exact_selection=exact_selection,
        approx_recall_target=approx_recall_target,
        qcap_max_drop_frac=qcap_max_drop_frac,
        donate_queries=donate_queries, shard_mask=shard_mask,
        failover=failover, overprobe=overprobe, merge_ways=merge_ways,
        use_pallas=use_pallas, mutation=mutation, wire=wire,
    )
    if not degraded:
        return fn(*args)
    md, mi, cov, rv = fn(*args)
    return PartialSearchResult(
        distances=md, ids=mi, coverage=cov, row_valid=rv
    )


def _prepare_pq_search(
    comms: Comms, index: MnmgIVFPQIndex, queries, k: int, *,
    n_probes: int = 8, qcap: typing.Union[int, str, None] = None,
    list_block: int = 8,
    refine_ratio: float = 2.0, exact_selection: bool = True,
    approx_recall_target: float = 0.95,
    qcap_max_drop_frac: typing.Optional[float] = None,
    donate_queries: bool = False,
    shard_mask=None,
    failover=None,
    overprobe: float = 2.0,
    merge_ways: typing.Optional[int] = None,
    use_pallas: typing.Optional[bool] = None,
    mutation=None,
    wire: str = "bf16",
):
    """The non-dispatching front half of :func:`mnmg_ivf_pq_search`:
    validation, engine/static resolution, program-cache lookup, and
    operand assembly — returns ``(fn, args, degraded)`` with the fused
    program UN-invoked. The program auditor
    (:mod:`raft_tpu.analysis.program`) traces ``fn`` over ``args``
    abstractly and runs its cached-program census across runtime-value
    flips through THIS path, so what it audits is byte-for-byte the
    serving entry's own preparation — the two can never drift."""
    q = jnp.asarray(queries)
    errors.check_matrix(q, "queries")
    errors.check_same_cols(q, index.centroids, "queries", "index")
    errors.expects(
        k <= n_probes * index.max_list,
        "k=%d exceeds the candidate pool (n_probes*max_list=%d)",
        k, n_probes * index.max_list,
    )
    errors.expects(
        0.0 < approx_recall_target <= 1.0,
        "approx_recall_target=%s out of range (0, 1]", approx_recall_target,
    )
    nl_g = index.centroids.shape[0]
    n_hosts, inner_width = comms_levels(comms)
    _check_probe_args(
        index, nl_g, overprobe, merge_ways, inner_width, wire
    )
    qcap, _ = resolve_qcap_arg(
        qcap, q, index.centroids, nl_g, n_probes,
        max_drop_frac=qcap_max_drop_frac, coarse=index.coarse,
        overprobe=overprobe,
    )
    list_block = max(1, min(list_block, index.nl_pad))
    store_raw = index.vectors_sorted is not None
    from raft_tpu.spatial.ann.ivf_pq import _resolve_adc_engine

    use_pallas = _resolve_adc_engine(
        use_pallas, store_raw and refine_ratio > 1.0,
        index.pq_dim, index.pq_bits, qcap,
    )
    statics = (
        k, n_probes, qcap, list_block, refine_ratio, exact_selection,
        approx_recall_target, index.pq_dim, index.pq_bits, index.n_pad,
        index.nl_pad, index.max_list,
        index.coarse is not None, float(overprobe),
        None if merge_ways is None else int(merge_ways),
        int(index.replication), int(index.replica_offset),
        use_pallas, jax.default_backend() != "tpu",
        # wire only shapes 2-level programs; normalized to None on a
        # 1-level mesh so the flat program's cache key never splits
        wire if n_hosts > 1 else None,
    )
    degraded = shard_mask is not None
    errors.expects(
        failover is None or degraded,
        "failover= requires shard_mask= (the resilient serving variant "
        "carries the routing input)",
    )
    mut_args = _mutation_operands(mutation, index, comms.size)
    fn = _cached_search(
        comms.mesh, comms.axis, store_raw, statics, donate_queries,
        degraded, mut_args is not None,
    )
    vecs = (
        index.vectors_sorted if store_raw
        else jnp.zeros((comms.size, 1, 1), jnp.float32)
    )
    sup_c, mem_i, cpad = _coarse_probe_operands(
        index, index.centroids.shape[1]
    )
    args = (
        index.centroids, index.codebooks, index.owner, index.local_id,
        index.local_cents, index.codes_sorted, vecs, index.sorted_ids,
        index.list_offsets, index.list_sizes, q, sup_c, mem_i, cpad,
    )
    if not degraded:
        return fn, args + tuple(mut_args or ()), False
    alive = resolve_shard_mask(shard_mask, comms.size)
    route = resolve_route(
        failover, comms.size, int(index.replication),
        int(index.replica_offset),
    )
    return fn, args + (
        jnp.asarray(alive), jnp.asarray(route),
    ) + tuple(mut_args or ()), True
