"""Multi-chip sharded IVF-Flat — exact (uncompressed) scoring at list
granularity over a device mesh.

The 10-60M-row regime is where this engine is THE answer: raw vectors
fit the 8-chip aggregate HBM but not one chip, and at those (n, d) the
measured crossover data (docs/ivf_scale.md "High-d crossover") says
dense/exact scoring beats ADC per probed row — so a list-sharded
recall-1.0 IVF beats both a single-chip PQ index (compression it does
not need) and replicated dense scans (P x the work). The reference
carries this capability through the Flat branch of its FAISS dispatch
(cpp/include/raft/spatial/knn/detail/ann_quantized_faiss.cuh:115-142,
``IVFFlatParam``); here it is the same mesh program as the sharded PQ
index (comms/mnmg_ivf.py) with exact scoring in place of ADC:

* **Shard lists, replicate the coarse quantizer** — greedy-LPT list
  ownership, each chip holding its lists' raw rows contiguously
  (``vectors_sorted``) with GLOBAL ids.
* **Queries replicate; rows never move.** Every chip probes the global
  centroids, keeps its owned probes (sentinel list otherwise), and runs
  the UNCHANGED single-chip grouped exact kernel
  (:func:`raft_tpu.spatial.ann.ivf_flat._grouped_impl`) on its shard.
* **Merge is a k-way top-k** over one (nq, k) allgather pair.

The build reuses the whole distributed pipeline — collective subsample
training, per-rank blocked assignment, bounded-round ``all_to_all`` row
exchange with positional slab scatter — via
:func:`raft_tpu.comms.mnmg_ivf._exchange_and_assemble`; no host ever
holds more than its own row shard.
"""

from __future__ import annotations

import dataclasses
import functools
import typing

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from raft_tpu import compat, errors
from raft_tpu.cluster.kmeans import kmeans_predict
from raft_tpu.comms.comms import Comms
from raft_tpu.resilience.degraded import (
    PartialSearchResult,
    mask_invalid_rows,
    probe_coverage,
    resolve_shard_mask,
    sanitize_query_rows,
)
from raft_tpu.resilience.replica import resolve_route
from raft_tpu.comms.mnmg_ivf import (
    _cached_program,
    _cdiv_host,
    _check_probe_args,
    _coarse_probe_operands,
    _exchange_and_assemble,
    _merge_across_shards,
    _P3,
    _PROBE_BLOCK_Q,
    _train_coarse_distributed,
    place_index,
    shard_rows,
)
from raft_tpu.comms.multihost import comms_levels, hier_axes
from raft_tpu.spatial.ann.common import (
    CoarseIndex,
    ListStorage,
    coarse_probe,
    n_super_probes,
    resolve_qcap_arg,
    two_level_probe,
)
from raft_tpu.spatial.ann.ivf_flat import (
    IVFFlatIndex,
    IVFFlatParams,
    _grouped_impl,
)

__all__ = [
    "MNMG_SCOPES", "MnmgIVFFlatIndex", "MnmgIVFSQIndex",
    "PROGRAM_NAMES", "mnmg_ivf_flat_build",
    "mnmg_ivf_flat_build_distributed", "mnmg_ivf_flat_search",
    "mnmg_ivf_sq_build", "mnmg_ivf_sq_build_distributed",
    "mnmg_ivf_sq_search",
]

# the named parts the sharded search program adds around the shared
# grouped body, whose ``ivf.*`` scopes stay inside it (``jax.named_scope``
# names; docs/observability.md "Spans and scopes")
SCOPE_PROBE = "mnmg.probe"   # global coarse probe, owner and sentinel maps
SCOPE_MERGE = "mnmg.merge"   # cross-shard all-gather + select_k
MNMG_SCOPES = (SCOPE_PROBE, SCOPE_MERGE)
# the jitted mesh programs' names, by ``sq``: a profile's "XLA Modules"
# line shows each as ``jit_<name>``, apart from the single-chip
# ``jit__grouped_impl`` that runs inside them
PROGRAM_NAMES = {False: "_mnmg_flat_search", True: "_mnmg_sq_search"}


@compat.register_dataclass
@dataclasses.dataclass
class MnmgIVFFlatIndex:
    """List-sharded IVF-Flat index over a comms mesh (the exact-scoring
    sibling of :class:`raft_tpu.comms.mnmg_ivf.MnmgIVFPQIndex`; field
    names shared with it so placement/serialization machinery applies
    unchanged)."""

    # the row store each shard's grouped scan reads: place_index keeps
    # it row-major, in its sharding (spatial/ann/common.place_row_store)
    ROW_STORE: typing.ClassVar[str] = "vectors_sorted"

    centroids: jax.Array       # (n_lists_g, d) replicated
    owner: jax.Array           # (n_lists_g,) int32 — owning rank per list
    local_id: jax.Array        # (n_lists_g,) int32 — list id on its owner
    local_cents: jax.Array     # (P, nl_pad, d) — per-chip centroid slab
    vectors_sorted: jax.Array  # (P, n_pad + 1, d) raw rows, list-sorted
    sorted_ids: jax.Array      # (P, n_pad) int32 GLOBAL row ids
    list_offsets: jax.Array    # (P, nl_pad + 1) int32
    list_sizes: jax.Array      # (P, nl_pad) int32
    n_pad: int = dataclasses.field(metadata=dict(static=True))
    nl_pad: int = dataclasses.field(metadata=dict(static=True))
    max_list: int = dataclasses.field(metadata=dict(static=True))
    n_rows: int = dataclasses.field(metadata=dict(static=True))
    metric: str = dataclasses.field(metadata=dict(static=True))
    # R-way striped replica layout — see MnmgIVFPQIndex (field names and
    # semantics shared; replicate with place_index(..., replication=R))
    replication: int = dataclasses.field(
        default=1, metadata=dict(static=True)
    )
    replica_offset: int = dataclasses.field(
        default=1, metadata=dict(static=True)
    )
    # optional two-level coarse quantizer over the GLOBAL probe set
    # (raft_tpu.comms.mnmg_ivf.attach_coarse_index)
    coarse: typing.Optional[CoarseIndex] = None

    def warmup(self, comms: "Comms", nq: int, *, k: int = 10,
               n_probes: int = 8, qcap=None, list_block: int = 32,
               donate_queries: bool = False, shard_mask=None,
               failover=None, overprobe: float = 2.0,
               merge_ways: typing.Optional[int] = None,
               mutation=None, wire: str = "bf16",
               use_pallas: typing.Optional[bool] = None,
               rerank_ratio: float = 4.0, audit: bool = False) -> int:
        """Pre-compile the sharded serving program for (nq, d) float32
        batches by dispatching one all-zeros batch through
        :func:`mnmg_ivf_flat_search` — the Flat sibling of
        :meth:`raft_tpu.comms.mnmg_ivf.MnmgIVFPQIndex.warmup`.

        Returns the shape-only-resolved qcap; pass exactly that integer
        (and the same ``donate_queries``) on serving dispatches. Pass
        ``shard_mask=True`` to warm the resilient variant instead
        (docs/robustness.md); the mask and the replica-failover route
        are runtime inputs, so one warm-up covers every later health
        and failover state. ``audit=True`` re-traces the warmed fused
        program through the jaxpr-level program auditor and raises on
        findings (:mod:`raft_tpu.analysis.program`; see
        :meth:`~raft_tpu.comms.mnmg_ivf.MnmgIVFPQIndex.warmup`)."""
        from raft_tpu.spatial.ann.common import static_qcap

        qc = static_qcap(qcap, nq, n_probes, self.centroids.shape[0])
        q0 = jnp.zeros((nq, self.centroids.shape[1]), jnp.float32)
        out = mnmg_ivf_flat_search(
            comms, self, q0, k, n_probes=n_probes, qcap=qc,
            list_block=list_block, donate_queries=donate_queries,
            shard_mask=shard_mask, failover=failover,
            overprobe=overprobe, merge_ways=merge_ways,
            mutation=mutation, wire=wire, use_pallas=use_pallas,
            rerank_ratio=rerank_ratio,
        )
        jax.block_until_ready(out)
        if audit:
            _audit_flat_family_warm(
                comms, self, q0, k, sq=False, n_probes=n_probes,
                qcap=qc, list_block=list_block,
                donate_queries=donate_queries, shard_mask=shard_mask,
                failover=failover, overprobe=overprobe,
                merge_ways=merge_ways, mutation=mutation, wire=wire,
                use_pallas=use_pallas, rerank_ratio=rerank_ratio,
                name="mnmg_ivf_flat_warm",
            )
        return qc


def mnmg_ivf_flat_build(
    comms: Comms, x, params: IVFFlatParams = IVFFlatParams(), *,
    metric: str = "l2",
) -> MnmgIVFFlatIndex:
    """One-host convenience wrapper: row-shard ``x`` onto the mesh (one
    shard transient at a time, :func:`shard_rows`) and run the per-rank
    distributed build."""
    x = np.asarray(x)
    errors.expects(
        x.ndim == 2 and x.shape[0] >= 2,
        "x: expected a (n >= 2, d) matrix, got shape %s", tuple(x.shape),
    )
    xg, n_valid = shard_rows(comms, x)
    return mnmg_ivf_flat_build_distributed(
        comms, xg, params, n_valid=n_valid, metric=metric
    )


def mnmg_ivf_flat_build_distributed(
    comms: Comms, x, params: IVFFlatParams = IVFFlatParams(), *,
    n_valid=None, metric: str = "l2",
) -> MnmgIVFFlatIndex:
    """Build a list-sharded IVF-Flat index from PER-RANK row shards — the
    Flat sibling of
    :func:`raft_tpu.comms.mnmg_ivf.mnmg_ivf_pq_build_distributed` (same
    input convention: ``x`` (P, n_loc, d) sharded ``P(axis, None, None)``,
    ``n_valid`` (P,) valid rows per rank, global ids by contiguous block).

    Pipeline: collective subsample -> replicated coarse k-means ->
    per-rank blocked assignment -> shared distributed list assembly
    (:func:`_exchange_and_assemble`: oversized-list split on GLOBAL
    within-list ranks, greedy-LPT ownership, bounded-round ``all_to_all``
    row exchange, positional slab scatter). Raw rows always co-shard with
    their lists — exact scoring needs them.

    ``max_list_cap``: ``None`` here means AUTO (``max(256, 2 * n /
    n_lists)``) — the sharded grouped compute and the LPT balance both
    degrade with one swollen list; pass ``0`` to disable.
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
    errors.expects(
        metric in ("l2", "sqeuclidean"),
        "metric %r not supported (l2 | sqeuclidean)", metric,
    )
    if n_valid is None:
        n_valid = np.full(Pn, nloc, np.int32)
    n_valid = np.asarray(n_valid, np.int32)
    n = int(n_valid.sum())
    errors.check_k(params.n_lists, n, "n_lists vs dataset rows")
    nl = params.n_lists
    ax = comms.device_comms()
    sh3 = _P3(comms.axis)
    sh1 = P(comms.axis)
    sh2 = P(comms.axis, None)
    rep = P()

    # ---- phase 1: collective training subsample -> replicated coarse
    # quantizer (shared helper with the PQ build; quantizer quality
    # saturates far below shard size)
    _, coarse = _train_coarse_distributed(
        comms, x, n_valid, n, nl, None,
        params.kmeans_n_iters, params.kmeans_init, params.seed,
    )
    cents = coarse.centroids

    # ---- phase 2: per-rank blocked assignment + global list sizes
    # (shared with the SQ build — one assignment program authority)
    lbl_g, C = _assign_lists(comms, x, n_valid, cents, nl)

    cap = (
        params.max_list_cap
        if params.max_list_cap is not None
        else max(256, 2 * _cdiv_host(n, nl))
    )
    maps, slabs = _exchange_and_assemble(
        comms, x, n_valid, lbl_g, C, cents, cap,
        store_vectors=True,
    )

    host = MnmgIVFFlatIndex(
        centroids=maps["cents_np"],
        owner=maps["owner"],
        local_id=maps["local_id"],
        local_cents=maps["lcents_sh"],
        vectors_sorted=slabs["vecs"],
        sorted_ids=slabs["sids"],
        list_offsets=maps["offs_sh"],
        list_sizes=maps["szs_sh"],
        n_pad=maps["n_pad"],
        nl_pad=maps["nl_pad"],
        max_list=maps["max_list"],
        n_rows=n,
        metric=metric,
    )
    return place_index(comms, host)


def _assign_lists(comms: Comms, x, n_valid, cents, nl: int):
    """Phase 2 of the flat-family distributed builds (Flat and SQ):
    per-rank blocked nearest-centroid assignment + one allgather of the
    local bincounts. Returns (lbl_g (P, n_loc) sharded, C (P, nl)
    replicated count matrix)."""
    Pn, nloc, d = x.shape
    ax = comms.device_comms()
    sh3 = _P3(comms.axis)
    sh1 = P(comms.axis)
    sh2 = P(comms.axis, None)
    rep = P()
    B = max(1, min(nloc, 1 << 20))
    nb = _cdiv_host(nloc, B)

    def asg_body(x_sh, nv_sh, cents_in):
        xb, nvr = x_sh[0], nv_sh[0]
        xp = jnp.pad(xb, ((0, nb * B - nloc), (0, 0)))
        lbl = lax.map(
            lambda blk: kmeans_predict(blk, cents_in).astype(jnp.int32),
            xp.reshape(nb, B, d),
        ).reshape(-1)[:nloc]
        valid = jnp.arange(nloc, dtype=jnp.int32) < nvr
        cnt = jnp.zeros((nl + 1,), jnp.int32).at[
            jnp.where(valid, lbl, nl)
        ].add(1)[:nl]
        return lbl[None], ax.allgather(cnt)

    return _cached_program(
        ("asg", comms.mesh, comms.axis, Pn, nloc, d, B, nb, nl,
         str(x.dtype)),
        lambda: jax.jit(comms.shard_map(
            asg_body, in_specs=(sh3, sh1, rep), out_specs=(sh2, rep),
        )),
    )(x, n_valid, cents)


@functools.lru_cache(maxsize=32)
def _cached_search(
    mesh: jax.sharding.Mesh, axis: str, statics: tuple,
    donate: bool = False, degraded: bool = False, mutation: bool = False,
):
    """Compile one shard_map search program per (mesh, static-config);
    keyed on value-hashable (mesh, axis), not the Comms identity.
    ``donate=True`` donates the query buffer (serving dispatch; the
    caller must not reuse the array after the call). ``degraded=True``
    compiles the resilient variant — ``alive`` AND ``route`` (P,)
    runtime inputs (health mask + replica-failover copy selection,
    exactly as in the PQ engine), +inf contributions from down shards,
    in-graph query sanitization, and (dists, ids, coverage, row_valid)
    outputs (docs/robustness.md). The ``use_coarse``/``overprobe``/
    ``merge_ways`` statics select the probe/merge widths exactly as in
    the PQ engine's ``_cached_search`` (two-level coarse probe +
    deployment-width in-program merge)."""
    (k, n_probes, qcap, list_block, n_pad, nl_pad, max_list,
     use_coarse, overprobe, merge_ways, replication,
     replica_offset, use_pallas, pallas_interpret, rerank_ratio,
     wire, sq) = statics
    comms = Comms(mesh=mesh, axis=axis)
    ax = comms.device_comms()
    n_ranks = comms.size
    # 2-level (ICI x DCN) mesh -> hierarchical merge tail
    # (docs/multihost.md); a pure function of the cache key's (mesh,
    # axis)
    hier = hier_axes(mesh, axis)

    def body(*opnds):
        (cents, owner, local_id, lcents, vecs_s, sids, loffs, lszs,
         q, sup_c, mem_i, cpad) = opnds[:12]
        rest = list(opnds[12:])
        dequant = None
        if sq:
            # the SQ mode of the one fused body (ISSUE 11): vecs_s holds
            # int8 QT_8bit codes and the replicated affine pair rides as
            # two extra runtime operands — the shard-local scan routes
            # through the int8 in-kernel dequant+scan engine when
            # use_pallas holds (spatial/ann/sq_kernel)
            dequant = (rest[0], rest[1])
            rest = rest[2:]
        alive = route = None
        if degraded:
            alive, route = rest[0], rest[1]
            rest = rest[2:]
        rm_s = dv_s = di_s = None
        if mutation:
            # mutation-tier runtime inputs (comms/mnmg_mutation.py)
            rm_s, dv_s, di_s = rest
        with jax.named_scope(SCOPE_PROBE):
            lcents, vecs, sids = lcents[0], vecs_s[0], sids[0]
            loffs, lszs = loffs[0], lszs[0]
            row_mask = rm_s[0] if mutation else None
            rank = lax.axis_index(ax.axis)
            qf = q.astype(jnp.float32)
            row_valid = None
            if degraded:
                qf, row_valid = sanitize_query_rows(qf)
            # replicated compute: identical global probes on every chip
            if use_coarse:
                # use_pallas (the shard-local scan-engine static)
                # also kernelizes the probe stage through the shared
                # core — neither probe tile materializes inside the
                # fused program (auto-degrades to the legacy probe when
                # the probe geometry does not fit the plan)
                probes_g, _ = two_level_probe(
                    qf, sup_c, mem_i, cpad, owner.shape[0], n_probes,
                    n_super_probes(n_probes, sup_c.shape[0], overprobe),
                    _PROBE_BLOCK_Q, use_pallas=use_pallas,
                    pallas_interpret=pallas_interpret,
                )
            else:
                probes_g, _ = coarse_probe(qf, cents, n_probes)
            probe_owner = owner[probes_g]                    # (nq, p)
            if degraded:
                # replica-aware routing (see the PQ engine body):
                # route[s] selects the copy serving shard s — a runtime
                # input, so failover flips never retrace
                j = route[jnp.clip(probe_owner, 0, n_ranks - 1)]
                serving = jnp.where(
                    (probe_owner >= 0) & (j >= 0),
                    (probe_owner + jnp.maximum(j, 0) * replica_offset)
                    % n_ranks,
                    -1,
                )                        # (nq, p) serving rank | -1
                own = serving == rank
                nlp_base = nl_pad // replication
                lp = jnp.where(
                    own,
                    jnp.maximum(j, 0) * nlp_base + local_id[probes_g],
                    jnp.int32(nl_pad - 1),                   # sentinel
                )
            else:
                serving = probe_owner
                own = probe_owner == rank
                lp = jnp.where(
                    own, local_id[probes_g],
                    jnp.int32(nl_pad - 1),                   # sentinel
                )

            storage = ListStorage(
                sorted_ids=sids,
                list_offsets=loffs,
                # grouped search leaves list_index unused
                list_index=jnp.zeros((nl_pad, 1), jnp.int32),
                list_sizes=lszs,
                n=n_pad,
                max_list=max_list,
            )
            shard = IVFFlatIndex(
                centroids=lcents, data_sorted=vecs, storage=storage,
                metric="sqeuclidean",  # sqrt applied after the merge
            )
        # the UNCHANGED single-chip grouped exact kernel, probes
        # pre-mapped to shard-local list ids; sorted_ids are global
        # (use_pallas routes the shard-local scan through the Pallas
        # sub-chunk-min engine INSIDE the fused one-dispatch program —
        # docs/ivf_scale.md "Flat scan in VMEM")
        vals, gids = _grouped_impl(
            shard, qf, k, n_probes, qcap, list_block, probes=lp,
            row_mask=row_mask,
            use_pallas=use_pallas, pallas_interpret=pallas_interpret,
            rerank_ratio=rerank_ratio, dequant=dequant,
        )
        with jax.named_scope(SCOPE_MERGE):
            if mutation:
                from raft_tpu.comms.mnmg_ivf import _merge_local_delta

                vals, gids = _merge_local_delta(
                    qf, vals, gids, dv_s[0], di_s[0], k, rank, nl_pad,
                    replication, replica_offset, n_ranks, alive, route,
                )
            if degraded:
                # a down shard contributes +inf distances to the merge
                vals = jnp.where(alive[rank] > 0, vals, jnp.inf)
            # in-program cross-shard merge: flat allgather + select_k on
            # a 1-level mesh (merge_ways pads to deployment width with
            # +inf/-1 absent-peer payloads — identical results), the
            # two-stage ICI x DCN merge on a 2-level mesh
            # (docs/multihost.md)
            md, mi = _merge_across_shards(
                ax, hier, vals, gids, k, merge_ways, wire
            )
            if degraded:
                # a failed-over shard on a live replica counts covered
                cov = probe_coverage(serving, alive, row_valid)
                md, mi = mask_invalid_rows(md, mi, row_valid)
                return md, mi, cov, row_valid
            return md, mi

    sharded3 = P(comms.axis, None, None)
    sharded2 = P(comms.axis, None)
    rep2 = P(None, None)
    rep3 = P(None, None, None)
    in_specs = (
        rep2, P(None), P(None),
        sharded3, sharded3, sharded2, sharded2, sharded2, rep2,
        rep2, rep2, rep3,           # coarse: super_cents, member_ids, pad
    )
    if sq:
        in_specs = in_specs + (P(None), P(None))     # vmin, vscale
    out_specs = (rep2, rep2)
    if degraded:
        in_specs = in_specs + (P(None), P(None))     # alive, route
        out_specs = (rep2, rep2, P(None), P(None))
    if mutation:
        # row_mask, delta_vecs, delta_ids — per-rank mutation slabs
        in_specs = in_specs + (sharded2, sharded3, sharded2)
    sm = comms.shard_map(body, in_specs=in_specs, out_specs=out_specs)

    def program(*opnds):
        return sm(*opnds)

    # a fixed name, so that a profile tells this program's runs apart
    program.__name__ = program.__qualname__ = PROGRAM_NAMES[sq]
    # queries are positional argument 8; the coarse arrays and, when
    # present, the alive mask + failover route and the mutation slabs
    # follow them (donation: serving mode)
    return jax.jit(program, donate_argnums=(8,) if donate else ())


def mnmg_ivf_flat_search(
    comms: Comms, index: MnmgIVFFlatIndex, queries, k: int, *,
    n_probes: int = 8, qcap: typing.Union[int, str, None] = None,
    list_block: int = 32,
    qcap_max_drop_frac: typing.Optional[float] = None,
    donate_queries: bool = False,
    shard_mask=None,
    failover=None,
    overprobe: float = 2.0,
    merge_ways: typing.Optional[int] = None,
    mutation=None,
    wire: str = "bf16",
    use_pallas: typing.Optional[bool] = None,
    rerank_ratio: float = 4.0,
):
    """Distributed grouped EXACT search over a list-sharded IVF-Flat
    index. Returns (distances, GLOBAL row ids), both (nq, k) replicated
    on every chip; distances are sqrt'd for ``metric='l2'`` (squared for
    ``'sqeuclidean'``), exactly as the single-chip
    :func:`raft_tpu.spatial.ann.ivf_flat.ivf_flat_search_grouped`.
    Recall parity with the single-chip search on the same data holds by
    construction — each probed list is scored by exactly one chip with
    the same kernel (tests/test_mnmg_ivf_flat.py asserts it on an
    8-device mesh).

    ``qcap`` as in the single-chip grouped search (``None`` = recall-safe
    auto from the global probe map; ``"throughput"`` = ~0.75x mean
    occupancy — see ann.common.throughput_qcap for when that is unsafe).

    ``donate_queries=True`` donates the query buffer (outputs may reuse
    its memory; the caller must not touch the array after the call) —
    the serving-dispatch mode, paired with an explicit integer ``qcap``
    and :meth:`MnmgIVFFlatIndex.warmup` (docs/serving.md).

    ``shard_mask`` selects the RESILIENT serving variant exactly as in
    :func:`raft_tpu.comms.mnmg_ivf.mnmg_ivf_pq_search`: a per-rank
    validity mask (ShardHealth | array | True) degrades the search —
    down shards contribute +inf, bad query rows are neutralized — and
    the return type becomes
    :class:`raft_tpu.resilience.PartialSearchResult` with per-query
    ``coverage`` and the ``partial`` flag (docs/robustness.md).

    ``failover`` (requires ``shard_mask``) as in the PQ engine: a
    :class:`raft_tpu.resilience.FailoverPlan` (or ``(P,)`` copy-index
    array) routing each logical shard onto a replica copy at runtime —
    on an R-way replicated index, ≤ R-1 failures per replica group keep
    ``coverage`` at 1.0 with results identical to the healthy mesh,
    and flips never recompile.

    ``overprobe``/``merge_ways`` (both static) as in the PQ engine: the
    two-level coarse probe's super-scan width when the index carries a
    coarse quantizer, and deployment-width padding of the in-program
    cross-shard merge (identical results; absent peers contribute
    +inf/-1).

    ``mutation`` engages the mutation-tier variant exactly as in the PQ
    engine (:func:`raft_tpu.comms.mnmg_ivf.mnmg_ivf_pq_search`): pass
    an :class:`~raft_tpu.comms.mnmg_mutation.MnmgMutationState` (or its
    wrapper) and tombstones + delta segments fold into the fused
    program as runtime inputs (docs/mutation.md "Sharded mutation").

    ``use_pallas``/``rerank_ratio`` (both static) select the shard-local
    scan engine inside the fused program — auto (``None``) engages the
    Pallas sub-chunk-min flat kernel on TPU exactly as
    :func:`~raft_tpu.spatial.ann.ivf_flat.ivf_flat_search_grouped`
    documents (docs/ivf_scale.md "Flat scan in VMEM"); the knob is a
    trace-time static, so like every other static it never varies with
    health/failover/mutation state (zero retraces on flips,
    trace-audited with the kernel engaged). The mutation tier's
    ``row_mask`` folds in at the kernel path's exact rerank tail.
    """
    out = _flat_family_search(
        comms, index, queries, k, sq=False, n_probes=n_probes,
        qcap=qcap, list_block=list_block,
        qcap_max_drop_frac=qcap_max_drop_frac,
        donate_queries=donate_queries, shard_mask=shard_mask,
        failover=failover, overprobe=overprobe, merge_ways=merge_ways,
        mutation=mutation, wire=wire, use_pallas=use_pallas,
        rerank_ratio=rerank_ratio,
    )
    if index.metric != "l2":
        return out
    # sqrt after the merge; +inf slots (down shards, invalid rows) on
    # the degraded path stay +inf
    if isinstance(out, PartialSearchResult):
        return dataclasses.replace(
            out, distances=jnp.sqrt(jnp.maximum(out.distances, 0.0))
        )
    vals, ids = out
    return jnp.sqrt(jnp.maximum(vals, 0.0)), ids


def _flat_family_search(
    comms: Comms, index, queries, k: int, *, sq: bool, n_probes,
    qcap, list_block, qcap_max_drop_frac, donate_queries, shard_mask,
    failover, overprobe, merge_ways, mutation, wire, use_pallas,
    rerank_ratio,
):
    """The ONE serving wrapper behind :func:`mnmg_ivf_flat_search` and
    :func:`mnmg_ivf_sq_search`: validation chain, engine resolution,
    the ``_cached_search`` statics tuple (position-coupled to the body's
    unpack — ONE authority so the two engines can never drift), operand
    assembly (``sq=True`` appends the replicated affine pair and serves
    the int8 code slab in the ``vectors_sorted`` operand slot), and the
    degraded/failover tail. Returns squared distances; the flat wrapper
    applies its metric sqrt on top."""
    fn, args, degraded = _prepare_flat_family(
        comms, index, queries, k, sq=sq, n_probes=n_probes, qcap=qcap,
        list_block=list_block, qcap_max_drop_frac=qcap_max_drop_frac,
        donate_queries=donate_queries, shard_mask=shard_mask,
        failover=failover, overprobe=overprobe, merge_ways=merge_ways,
        mutation=mutation, wire=wire, use_pallas=use_pallas,
        rerank_ratio=rerank_ratio,
    )
    if not degraded:
        return fn(*args)
    md, mi, cov, rv = fn(*args)
    return PartialSearchResult(
        distances=md, ids=mi, coverage=cov, row_valid=rv
    )


def _prepare_flat_family(
    comms: Comms, index, queries, k: int, *, sq: bool, n_probes,
    qcap, list_block, qcap_max_drop_frac, donate_queries, shard_mask,
    failover, overprobe, merge_ways, mutation, wire, use_pallas,
    rerank_ratio,
):
    """The non-dispatching front half of :func:`_flat_family_search` —
    returns ``(fn, args, degraded)`` with the fused program UN-invoked,
    exactly like :func:`raft_tpu.comms.mnmg_ivf._prepare_pq_search`.
    The program auditor (:mod:`raft_tpu.analysis.program`) traces and
    flip-censuses through this path, so the audited preparation IS the
    serving entry's own."""
    q = jnp.asarray(queries)
    errors.check_matrix(q, "queries")
    errors.check_same_cols(q, index.centroids, "queries", "index")
    errors.expects(
        k <= n_probes * index.max_list,
        "k=%d exceeds the candidate pool (n_probes*max_list=%d)",
        k, n_probes * index.max_list,
    )
    errors.expects(
        k <= index.max_list,
        "k=%d exceeds max_list=%d — a single list cannot fill a "
        "per-list top-k row; lower k or rebuild with fewer lists",
        k, index.max_list,
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
    if sq:
        from raft_tpu.spatial.ann.ivf_sq import _resolve_sq_engine

        use_pallas = _resolve_sq_engine(
            use_pallas, index.centroids.shape[1], qcap
        )
    else:
        from raft_tpu.spatial.ann.ivf_flat import _resolve_scan_engine

        use_pallas = _resolve_scan_engine(
            use_pallas, index.centroids.shape[1], qcap
        )
    statics = (
        k, n_probes, qcap, list_block, index.n_pad, index.nl_pad,
        index.max_list,
        index.coarse is not None, float(overprobe),
        None if merge_ways is None else int(merge_ways),
        int(index.replication), int(index.replica_offset),
        use_pallas, jax.default_backend() != "tpu", float(rerank_ratio),
        # wire only shapes 2-level programs; normalized to None on a
        # 1-level mesh so the flat program's cache key never splits
        wire if n_hosts > 1 else None,
        sq,
    )
    degraded = shard_mask is not None
    errors.expects(
        failover is None or degraded,
        "failover= requires shard_mask= (the resilient serving variant "
        "carries the routing input)",
    )
    from raft_tpu.comms.mnmg_ivf import _mutation_operands

    mut_args = _mutation_operands(mutation, index, comms.size)
    fn = _cached_search(
        comms.mesh, comms.axis, statics, donate_queries, degraded,
        mut_args is not None,
    )
    sup_c, mem_i, cpad = _coarse_probe_operands(
        index, index.centroids.shape[1]
    )
    slab = index.codes_sorted if sq else index.vectors_sorted
    args = (
        index.centroids, index.owner, index.local_id, index.local_cents,
        slab, index.sorted_ids, index.list_offsets,
        index.list_sizes, q, sup_c, mem_i, cpad,
    )
    if sq:
        args = args + (
            jnp.asarray(index.vmin, jnp.float32),
            jnp.asarray(index.vscale, jnp.float32),
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


def _audit_flat_family_warm(comms, index, q0, k, *, sq, n_probes, qcap,
                            list_block, donate_queries, shard_mask,
                            failover, overprobe, merge_ways, mutation,
                            wire, use_pallas, rerank_ratio, name):
    """The flat-family ``warmup(audit=True)`` hook: re-prepare the exact
    warmed program, trace it abstractly, and run the jaxpr passes —
    raising listing the findings (:mod:`raft_tpu.analysis.program`)."""
    from raft_tpu.analysis.program import audit_warmed
    from raft_tpu.analysis.program.registry import record_from_traced

    fn, args, _ = _prepare_flat_family(
        comms, index, q0, k, sq=sq, n_probes=n_probes, qcap=qcap,
        list_block=list_block, qcap_max_drop_frac=None,
        donate_queries=donate_queries, shard_mask=shard_mask,
        failover=failover, overprobe=overprobe, merge_ways=merge_ways,
        mutation=mutation, wire=wire, use_pallas=use_pallas,
        rerank_ratio=rerank_ratio,
    )
    # the wrapper's own engine resolution decides whether the XLA
    # fallback's wide tile is intentional
    if sq:
        from raft_tpu.spatial.ann.ivf_sq import _resolve_sq_engine

        up = _resolve_sq_engine(use_pallas, index.centroids.shape[1], qcap)
    else:
        from raft_tpu.spatial.ann.ivf_flat import _resolve_scan_engine

        up = _resolve_scan_engine(use_pallas, index.centroids.shape[1],
                                  qcap)
    h = hier_axes(comms.mesh, comms.axis)
    audit_warmed(record_from_traced(
        name, fn.trace(*args),
        {
            "nq": int(q0.shape[0]), "k": k, "n_probes": n_probes,
            "qcap": qcap, "max_list": int(index.max_list),
            "allow_wide_tile": not up,
            "expect_donated_queries": bool(donate_queries),
            "dcn_axes": () if h is None else (h[0],),
            "dcn_wire": wire,
        },
    ))


# --------------------------------------------------------------- IVF-SQ
@compat.register_dataclass
@dataclasses.dataclass
class MnmgIVFSQIndex:
    """List-sharded int8 IVF-SQ index over a comms mesh — the SQ mode of
    the one fused flat-family serving program (ISSUE 11): field names
    shared with :class:`MnmgIVFFlatIndex`/``MnmgIVFPQIndex`` so the
    placement/replication/reshard/serialization machinery applies
    unchanged, with ``codes_sorted`` holding int8 QT_8bit codes (HALF
    the bf16 flat slab footprint — the win that compounds with the
    billion-vector budget math, docs/ivf_scale.md) and the replicated
    affine dequant pair ``vmin``/``vscale`` riding as runtime operands
    of the fused search."""

    centroids: jax.Array       # (n_lists_g, d) replicated
    owner: jax.Array           # (n_lists_g,) int32 — owning rank per list
    local_id: jax.Array        # (n_lists_g,) int32 — list id on its owner
    local_cents: jax.Array     # (P, nl_pad, d) — per-chip centroid slab
    codes_sorted: jax.Array    # (P, n_pad + 1, d) int8, list-sorted
    vmin: jax.Array            # (d,) f32 replicated affine offset
    vscale: jax.Array          # (d,) f32 replicated affine scale
    sorted_ids: jax.Array      # (P, n_pad) int32 GLOBAL row ids
    list_offsets: jax.Array    # (P, nl_pad + 1) int32
    list_sizes: jax.Array      # (P, nl_pad) int32
    n_pad: int = dataclasses.field(metadata=dict(static=True))
    nl_pad: int = dataclasses.field(metadata=dict(static=True))
    max_list: int = dataclasses.field(metadata=dict(static=True))
    n_rows: int = dataclasses.field(metadata=dict(static=True))
    # R-way striped replica layout — see MnmgIVFPQIndex
    replication: int = dataclasses.field(
        default=1, metadata=dict(static=True)
    )
    replica_offset: int = dataclasses.field(
        default=1, metadata=dict(static=True)
    )
    # present (always None) so reshard/replicate treat the SQ index
    # through the same field protocol as its siblings
    vectors_sorted: typing.Optional[jax.Array] = None
    # optional two-level coarse quantizer over the GLOBAL probe set
    coarse: typing.Optional[CoarseIndex] = None

    def warmup(self, comms: "Comms", nq: int, *, k: int = 10,
               n_probes: int = 8, qcap=None, list_block: int = 32,
               donate_queries: bool = False, shard_mask=None,
               failover=None, overprobe: float = 2.0,
               merge_ways: typing.Optional[int] = None,
               mutation=None, wire: str = "bf16",
               use_pallas: typing.Optional[bool] = None,
               rerank_ratio: float = 4.0, audit: bool = False) -> int:
        """Pre-compile the sharded SQ serving program for (nq, d)
        float32 batches — the SQ sibling of
        :meth:`MnmgIVFFlatIndex.warmup` (one all-zeros batch through
        :func:`mnmg_ivf_sq_search`, blocked on). Returns the
        shape-only-resolved qcap; pass exactly that integer (and the
        same ``donate_queries``) on serving dispatches. ``audit=True``
        re-traces the warmed fused program through the jaxpr-level
        program auditor and raises on findings
        (:mod:`raft_tpu.analysis.program`)."""
        from raft_tpu.spatial.ann.common import static_qcap

        qc = static_qcap(qcap, nq, n_probes, self.centroids.shape[0])
        q0 = jnp.zeros((nq, self.centroids.shape[1]), jnp.float32)
        out = mnmg_ivf_sq_search(
            comms, self, q0, k, n_probes=n_probes, qcap=qc,
            list_block=list_block, donate_queries=donate_queries,
            shard_mask=shard_mask, failover=failover,
            overprobe=overprobe, merge_ways=merge_ways,
            mutation=mutation, wire=wire, use_pallas=use_pallas,
            rerank_ratio=rerank_ratio,
        )
        jax.block_until_ready(out)
        if audit:
            _audit_flat_family_warm(
                comms, self, q0, k, sq=True, n_probes=n_probes,
                qcap=qc, list_block=list_block,
                donate_queries=donate_queries, shard_mask=shard_mask,
                failover=failover, overprobe=overprobe,
                merge_ways=merge_ways, mutation=mutation, wire=wire,
                use_pallas=use_pallas, rerank_ratio=rerank_ratio,
                name="mnmg_ivf_sq_warm",
            )
        return qc


def mnmg_ivf_sq_build(
    comms: Comms, x, params=None,
) -> MnmgIVFSQIndex:
    """One-host convenience wrapper: row-shard ``x`` onto the mesh
    (:func:`shard_rows`) and run the per-rank distributed SQ build."""
    from raft_tpu.spatial.ann.ivf_sq import IVFSQParams

    x = np.asarray(x)
    errors.expects(
        x.ndim == 2 and x.shape[0] >= 2,
        "x: expected a (n >= 2, d) matrix, got shape %s", tuple(x.shape),
    )
    xg, n_valid = shard_rows(comms, x)
    return mnmg_ivf_sq_build_distributed(
        comms, xg, params if params is not None else IVFSQParams(),
        n_valid=n_valid,
    )


def mnmg_ivf_sq_build_distributed(
    comms: Comms, x, params=None, *, n_valid=None,
) -> MnmgIVFSQIndex:
    """Build a list-sharded int8 IVF-SQ index from PER-RANK row shards —
    the SQ sibling of :func:`mnmg_ivf_flat_build_distributed` (same
    input convention and phase pipeline): collective subsample ->
    replicated coarse k-means -> per-rank blocked assignment (the SHARED
    :func:`_assign_lists` program) -> a collective masked min/max pass
    for the QT_8bit affine stats -> per-rank int8 encode -> the shared
    distributed list assembly with the int8 codes as the exchange
    payload (``_exchange_and_assemble`` carries them at one byte per
    dimension — the same wire thrift as the serving-side slab win)."""
    from raft_tpu.spatial.ann.ivf_sq import IVFSQParams

    if params is None:
        params = IVFSQParams()
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
    if n_valid is None:
        n_valid = np.full(Pn, nloc, np.int32)
    n_valid = np.asarray(n_valid, np.int32)
    n = int(n_valid.sum())
    errors.check_k(params.n_lists, n, "n_lists vs dataset rows")
    nl = params.n_lists
    ax = comms.device_comms()
    sh3 = _P3(comms.axis)
    sh1 = P(comms.axis)
    rep = P()

    # ---- phase 1: collective subsample -> replicated coarse quantizer
    _, coarse = _train_coarse_distributed(
        comms, x, n_valid, n, nl, None,
        params.kmeans_n_iters, "k-means++", params.seed,
    )
    cents = coarse.centroids

    # ---- phase 2: shared per-rank blocked assignment
    lbl_g, C = _assign_lists(comms, x, n_valid, cents, nl)

    # ---- phase 2b: QT_8bit affine stats — per-rank masked min/max +
    # one allgather reduce (padding rows beyond n_valid are neutralized,
    # so ragged shards cannot drag the range toward zero)
    def stats_body(x_sh, nv_sh):
        xb, nvr = x_sh[0].astype(jnp.float32), nv_sh[0]
        valid = (jnp.arange(nloc, dtype=jnp.int32) < nvr)[:, None]
        big = jnp.float32(3.4e38)
        mn = jnp.min(jnp.where(valid, xb, big), axis=0)
        mx = jnp.max(jnp.where(valid, xb, -big), axis=0)
        return (
            jnp.min(ax.allgather(mn), axis=0),
            jnp.max(ax.allgather(mx), axis=0),
        )

    vmin, vmax = _cached_program(
        ("sqstats", comms.mesh, comms.axis, Pn, nloc, d, str(x.dtype)),
        lambda: jax.jit(comms.shard_map(
            stats_body, in_specs=(sh3, sh1), out_specs=(rep, rep),
        )),
    )(x, n_valid)
    vscale = jnp.maximum(vmax - vmin, 1e-12) / 255.0

    # ---- phase 2c: per-rank int8 encode (elementwise — the sharding of
    # x carries through; the module-level jit reuses one compiled
    # program across same-shape rebuilds). The exchange payload is the
    # int8 pattern viewed as uint8 (modular cast, bit-preserving both
    # ways), so rows cross the interconnect at one byte per dimension.
    codes_u8 = _sq_encode_jit(x, vmin, vscale)

    cap = (
        params.max_list_cap
        if params.max_list_cap is not None
        else max(256, 2 * _cdiv_host(n, nl))
    )
    maps, slabs = _exchange_and_assemble(
        comms, x, n_valid, lbl_g, C, cents, cap,
        store_vectors=False, codes_g=codes_u8, M=d,
    )

    host = MnmgIVFSQIndex(
        centroids=maps["cents_np"],
        owner=maps["owner"],
        local_id=maps["local_id"],
        local_cents=maps["lcents_sh"],
        codes_sorted=jnp.asarray(slabs["codes"]).astype(jnp.int8),
        vmin=jnp.asarray(vmin, jnp.float32),
        vscale=jnp.asarray(vscale, jnp.float32),
        sorted_ids=slabs["sids"],
        list_offsets=maps["offs_sh"],
        list_sizes=maps["szs_sh"],
        n_pad=maps["n_pad"],
        nl_pad=maps["nl_pad"],
        max_list=maps["max_list"],
        n_rows=n,
    )
    return place_index(comms, host)


@jax.jit
def _sq_encode_jit(xx, mn, sc):
    # THE shared encoder (ivf_sq.sq_encode), viewed as uint8 for the
    # exchange payload (modular cast, bit-preserving both ways)
    from raft_tpu.spatial.ann.ivf_sq import sq_encode

    return sq_encode(xx, mn, sc).astype(jnp.uint8)


def mnmg_ivf_sq_search(
    comms: Comms, index: MnmgIVFSQIndex, queries, k: int, *,
    n_probes: int = 8, qcap: typing.Union[int, str, None] = None,
    list_block: int = 32,
    qcap_max_drop_frac: typing.Optional[float] = None,
    donate_queries: bool = False,
    shard_mask=None,
    failover=None,
    overprobe: float = 2.0,
    merge_ways: typing.Optional[int] = None,
    mutation=None,
    wire: str = "bf16",
    use_pallas: typing.Optional[bool] = None,
    rerank_ratio: float = 4.0,
):
    """Distributed grouped IVF-SQ search over a list-sharded int8 index
    — the SQ mode of the ONE fused flat-family serving program (the
    same ``_cached_search`` body as :func:`mnmg_ivf_flat_search`, with
    the replicated affine pair as two extra runtime operands). Returns
    (squared L2 distances over the dequantized vectors, GLOBAL row
    ids), both (nq, k) replicated — the single-chip
    :func:`~raft_tpu.spatial.ann.ivf_sq.ivf_sq_search_grouped`
    semantics at mesh width.

    Every serving knob matches the flat engine's and shares its runtime
    contracts: ``shard_mask``/``failover`` (degraded serving + replica
    routing as runtime inputs — health and failover flips never
    recompile, the same zero-retrace audit as the flat engine, with the
    SQ kernel engaged), ``overprobe``/``merge_ways`` (two-level probe +
    deployment-width in-program merge), ``mutation`` (per-rank
    tombstone mask + delta segments), ``wire`` (2-level meshes), and
    ``use_pallas``/``rerank_ratio`` — auto (``None``) engages the int8
    in-kernel dequant+scan engine (spatial/ann/sq_kernel) on TPU
    whenever the shared planner approves the config, scanning each
    shard's int8 slabs INSIDE the fused one-dispatch program. SQ
    distances are squared (like the single-chip engine); the shared
    wrapper :func:`_flat_family_search` holds the one statics/operand
    authority for both engines."""
    return _flat_family_search(
        comms, index, queries, k, sq=True, n_probes=n_probes,
        qcap=qcap, list_block=list_block,
        qcap_max_drop_frac=qcap_max_drop_frac,
        donate_queries=donate_queries, shard_mask=shard_mask,
        failover=failover, overprobe=overprobe, merge_ways=merge_ways,
        mutation=mutation, wire=wire, use_pallas=use_pallas,
        rerank_ratio=rerank_ratio,
    )
