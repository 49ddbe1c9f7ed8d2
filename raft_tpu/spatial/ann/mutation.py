"""Online index mutation — upsert / delete / streaming ingest for the
IVF engines (ROADMAP item 2; the reference IVF builders carry per-list
extend paths for exactly this workload — FAISS ``add_core`` under
ann_quantized_faiss.cuh — while every engine shipped here so far served
a frozen checkpoint).

The TPU-native translation keeps the padded-static-shape discipline that
made degraded search and failover free at runtime (docs/robustness.md):

* **Delta segments** — every list owns a ``(cap,)`` padded delta segment
  with STATIC capacity (:class:`DeltaStore`, the same padded-pytree
  discipline as ``sparse/coo.py`` and the list slabs). An upsert is one
  in-graph scatter into the assigned list's segment: no recompile, no
  host-side layout change, visible to the very next search (the delta is
  scanned densely — it is small by construction, and a fresh row is
  therefore visible regardless of the probe map).
* **Tombstone deletion** — a ``(n + 1,)`` runtime row mask folded into
  the grouped scans exactly like ``shard_mask`` (the same trick applied
  to rows): a delete flips one mask entry; the row scores +inf and can
  never surface. Zero retrace on delete (trace-audited).
* **Background compaction** — :func:`compact` merges full deltas and
  tombstones into fresh main slabs (host-side, like every index build),
  optionally refreshing centroids via ``cluster/kmeans.py`` WARM-STARTED
  from the current centroids, with a ``coarse_probe_recall``-style drift
  guardrail (:func:`probe_overlap`). Compaction is the ONE operation
  that may change static shapes — slab heights and ``max_list`` are
  bucketed so steady-state recompaction usually keeps the compiled
  programs — and :class:`BackgroundCompactor` runs it off-thread while
  searches continue on the old state.
* **Incremental checkpointing** — format v4 extends the CRC-manifested
  serialization to the mutation tier: a full v4 checkpoint via
  :func:`raft_tpu.spatial.ann.save_index`, plus
  :func:`save_delta_checkpoint` / :func:`apply_delta_checkpoint` that
  rewrite ONLY dirty lists' delta segments (v3/v2/v1 read-compat and
  the lowest-version writer rule are preserved in serialize.py).

docs/mutation.md states the full lifecycle contract; the sharded
(replica-routed) tier lives in :mod:`raft_tpu.comms.mnmg_mutation`.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import threading
import time
import typing

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu import compat, errors
from raft_tpu.analysis.threads import runtime as lockcheck
from raft_tpu.obs import crash as obs_crash
from raft_tpu.obs import metrics as obs_metrics
from raft_tpu.cluster.kmeans import KMeansParams, kmeans_fit, kmeans_predict
from raft_tpu.spatial.ann.common import (
    ListStorage,
    build_list_storage,
    coarse_probe,
    place_row_store,
    static_qcap,
)
from raft_tpu.spatial.ann.ivf_flat import (
    IVFFlatIndex,
    _grouped_impl,
    _resolve_scan_engine,
)
from raft_tpu.spatial.ann.ivf_pq import (
    IVFPQIndex,
    _encode_block_jit,
    _pq_grouped_impl,
    _resolve_adc_engine,
)
from raft_tpu.spatial.ann.ivf_sq import (
    IVFSQIndex,
    _flat_view,
    _resolve_sq_engine,
)

__all__ = [
    "DeltaStore",
    "MutableIndex",
    "CompactionPolicy",
    "BackgroundCompactor",
    "apply_delta_checkpoint",
    "compact",
    "compaction_stats",
    "delete",
    "delta_checkpoint_watermark",
    "lists_changed_since",
    "mutable_search",
    "mutable_warmup",
    "probe_overlap",
    "save_delta_checkpoint",
    "upsert",
    "wrap_mutable",
]


# mutation-tier telemetry (ISSUE 13, docs/observability.md): host-wall
# durations of the three ops (the upsert/delete stamps INCLUDE their ack
# sync — that is the latency an ingest client sees) plus the delta-fill
# / tombstone-pressure gauges compaction decisions read. Every series
# carries an ``index=<MutableIndex.name>`` label — a process serving
# several mutable indexes must not interleave their pressure gauges —
# and handles are cached per name so the ack path pays one dict get.
# RAFT_TPU_OBS=off no-ops them all.
_mseries_cache: dict = {}
_mseries_lock = lockcheck.make_lock("mutation._mseries_lock")


def _mseries(index_name: str) -> dict:
    s = _mseries_cache.get(index_name)
    if s is not None:
        return s
    reg = obs_metrics.default_registry()
    with _mseries_lock:
        if index_name not in _mseries_cache:
            _mseries_cache[index_name] = {
                "op_ms": {
                    op: reg.histogram("mutation_op_ms",
                                      index=index_name, op=op)
                    for op in ("upsert", "delete", "compact")
                },
                "rows": {
                    key: reg.counter("mutation_rows_total",
                                     index=index_name, op=op, result=res)
                    for key, (op, res) in {
                        "accepted": ("upsert", "accepted"),
                        "rejected": ("upsert", "rejected"),
                        "deleted": ("delete", "found"),
                        "missing": ("delete", "missing"),
                    }.items()
                },
                "compactions": reg.counter("mutation_compactions_total",
                                           index=index_name),
                "journal_compacted": reg.counter(
                    "mutation_journal_compacted_total",
                    index=index_name),
                "fill": reg.gauge("mutation_delta_fill",
                                  index=index_name),
                "max_fill": reg.gauge("mutation_delta_max_fill",
                                      index=index_name),
                "tombstone": reg.gauge("mutation_tombstone_frac",
                                       index=index_name),
            }
        return _mseries_cache[index_name]


@compat.register_dataclass
@dataclasses.dataclass
class DeltaStore:
    """Per-list delta segments with static padded capacity.

    ``counts[l]`` is the number of APPENDED rows in list ``l``'s segment
    (tombstoned delta rows still hold their slot until compaction —
    slots are append-only between compactions so upserts stay one
    in-graph scatter). ``ids`` carries the caller's GLOBAL row ids
    (``-1`` = empty slot); ``live`` drops to 0 when a delta row is
    deleted or superseded by a re-upsert. ``cap`` is the static
    capacity: a full segment REJECTS further upserts (the accepted mask
    reports it) rather than silently dropping or recompiling.
    """

    vecs: jax.Array    # (n_lists, cap, d) f32
    ids: jax.Array     # (n_lists, cap) int32, -1 = empty
    live: jax.Array    # (n_lists, cap) int8
    counts: jax.Array  # (n_lists,) int32
    cap: int = dataclasses.field(metadata=dict(static=True))


@dataclasses.dataclass
class MutableIndex:
    """A frozen IVF index plus its mutation state.

    NOT a pytree — it carries host-side bookkeeping (``dirty_lists`` for
    incremental checkpointing). The jitted mutation ops take the array
    members (``index``/``delta``/``row_mask``/``id_to_pos``) explicitly;
    every op is functional and returns fresh state.

    ``row_mask``: (n + 1,) int8 LIVE mask over main-slab positions (the
    tombstone input of the grouped scans). ``id_to_pos``: (id_span,)
    int32 map from a global row id to its main-slab position (-1 =
    not in the main slab) — what lets upsert/delete tombstone a row's
    previous main copy in-graph.
    """

    index: typing.Union[IVFFlatIndex, IVFPQIndex, IVFSQIndex]
    delta: DeltaStore
    row_mask: jax.Array   # (n + 1,) int8 live mask
    id_to_pos: jax.Array  # (id_span,) int32, -1 = absent

    def __post_init__(self):
        # host-side incremental-checkpoint bookkeeping (lists whose
        # delta segment changed since the last checkpoint write)
        self.dirty_lists: set = set()
        # host-side telemetry label (NOT serialized — a loaded
        # checkpoint re-labels at wrap/load time): the ``index=`` label
        # on every mutation_* series, so several mutable indexes in one
        # process keep distinct pressure gauges (docs/observability.md)
        self.name: str = "mutable"
        # the MUTATION EPOCH (ISSUE 15, docs/serving.md "Hot traffic"):
        # a host-side monotone counter bumped by every APPLIED
        # upsert/delete batch and by compaction — the result-cache
        # invalidation input (raft_tpu.serving.result_cache): entries
        # stamped with an older epoch die on their first post-write
        # lookup. Host state only; never serialized (a loaded
        # checkpoint restarts at 0 with an empty cache beside it).
        self.epoch: int = 0
        # the EPOCH JOURNAL (ISSUE 17, docs/tiering.md "Epoch
        # invalidation"): per-bump ``(epoch, lists|None)`` entries
        # naming which lists' serving state changed (None = everything
        # — compaction rewrote the slab). The tiered store's
        # ``sync_mutations`` reads it through
        # :func:`lists_changed_since`; bounded — entries past the cap
        # fall off and queries below the floor answer None (refresh
        # everything, the safe direction). Host state only.
        self._epoch_journal: list = []
        self._journal_floor: int = 0
        # optional host-side flight recorder (set at wrap/load time
        # like ``name``; never serialized): where journal-compaction
        # events land so a forced full tier refresh is attributable
        self.flight = None

    @property
    def n_lists(self) -> int:
        return self.index.centroids.shape[0]

    @property
    def engine(self) -> str:
        if isinstance(self.index, IVFPQIndex):
            return "pq"
        if isinstance(self.index, IVFSQIndex):
            return "sq"
        return "flat"


def _with(mindex: MutableIndex, **kw) -> MutableIndex:
    """dataclasses.replace that PRESERVES the host-side dirty set,
    telemetry label, and mutation epoch (``__post_init__`` would reset
    them; the mutation ops bump the epoch explicitly AFTER _with)."""
    out = dataclasses.replace(mindex, **kw)
    out.dirty_lists = set(mindex.dirty_lists)
    out.name = mindex.name
    out.epoch = mindex.epoch
    out._epoch_journal = list(mindex._epoch_journal)
    out._journal_floor = mindex._journal_floor
    out.flight = mindex.flight
    return out


_EPOCH_JOURNAL_CAP = 1024


def _journal_note(mindex: MutableIndex, changed) -> None:
    """Append one epoch-journal entry for ``mindex.epoch`` (call AFTER
    the bump). ``changed``: the list ids whose serving state the write
    touched, or None = everything (compaction). Bounded at
    ``_EPOCH_JOURNAL_CAP`` — dropped entries raise the floor, below
    which :func:`lists_changed_since` answers None."""
    j = mindex._epoch_journal
    j.append((mindex.epoch,
              None if changed is None else frozenset(changed)))
    if len(j) > _EPOCH_JOURNAL_CAP:
        drop = len(j) - _EPOCH_JOURNAL_CAP
        mindex._journal_floor = j[drop - 1][0]
        del j[:drop]
        # an overflow silently downgrades every reader below the new
        # floor to "refresh everything" — count it + flight-mark it so
        # a forced full resync is attributable (docs/observability.md)
        _mseries(mindex.name)["journal_compacted"].inc(drop)
        if mindex.flight is not None:
            mindex.flight.record(
                "mutation_journal_compacted", index=mindex.name,
                dropped=drop, floor=mindex._journal_floor,
                epoch=mindex.epoch)


def lists_changed_since(mindex: MutableIndex, epoch: int):
    """The tier-invalidation query (docs/tiering.md): the set of list
    ids whose serving state changed in epochs ``(epoch,
    mindex.epoch]``, or ``None`` when the answer is "assume
    everything" — a compaction sits in the window, or the window
    predates the bounded journal. An up-to-date reader gets an empty
    set. The set may OVER-approximate (a delete of an already-dead id
    can name its list) — safe for invalidation, never under-reports."""
    if epoch >= mindex.epoch:
        return set()
    if epoch < mindex._journal_floor:
        return None
    out: set = set()
    for e, changed in mindex._epoch_journal:
        if e <= epoch:
            continue
        if changed is None:
            return None
        out |= changed
    return out


def _main_slab_lists(mindex: MutableIndex, ids_np: np.ndarray) -> set:
    """Lists owning the MAIN-slab rows of the given ids (host
    searchsorted over the list offsets) — the row_mask tombstone side
    of an epoch-journal entry. Over-approximates: an id whose main row
    was already dead still names its list."""
    span = int(mindex.id_to_pos.shape[0])
    inb = (ids_np >= 0) & (ids_np < span)
    if not inb.any():
        return set()
    pos = np.asarray(mindex.id_to_pos[jnp.asarray(ids_np[inb])])
    pos = pos[pos >= 0]
    if pos.size == 0:
        return set()
    offs = np.asarray(mindex.index.storage.list_offsets)
    lists = np.searchsorted(offs, pos, side="right") - 1
    return set(int(x) for x in lists)


def wrap_mutable(index, *, delta_cap: int = 32,
                 name: str = "mutable") -> MutableIndex:
    """Wrap a frozen :class:`IVFFlatIndex` / :class:`IVFPQIndex` /
    :class:`IVFSQIndex` for online mutation. Host-side (one
    inverse-permutation pass over ``sorted_ids``); the wrapped index's
    arrays are aliased, not copied. SQ delta rows are stored as exact
    f32 until compaction re-quantizes them — a fresh row serves at full
    precision, and only the fold pays the affine rounding.

    ``delta_cap``: static per-list delta capacity. Upserts into a full
    segment are REJECTED (reported via the accepted mask) until
    compaction drains it — size it from the expected ingest rate between
    compactions (docs/mutation.md "Capacity tuning").

    ``name``: the ``index=`` label on this index's ``mutation_*``
    telemetry series (docs/observability.md) — give each mutable index
    in a process its own so their pressure gauges stay distinct. Host
    state only; never serialized."""
    errors.expects(
        isinstance(index, (IVFFlatIndex, IVFPQIndex, IVFSQIndex)),
        "wrap_mutable: expected an IVFFlatIndex, IVFPQIndex, or "
        "IVFSQIndex, got %s",
        type(index).__name__,
    )
    errors.expects(delta_cap >= 1, "delta_cap=%d < 1", delta_cap)
    storage = index.storage
    n = storage.n
    d = index.centroids.shape[1]
    nl = index.centroids.shape[0]
    sids = np.asarray(storage.sorted_ids)
    valid = sids >= 0
    span = int(sids[valid].max()) + 1 if valid.any() else 1
    # the in-graph id→position map is DENSE over [0, max_id]: global ids
    # must stay dense-ish (the builds number 0..n-1 and compaction
    # preserves ids), or the map's memory scales with the largest id,
    # not the row count — fail loudly instead of silently allocating GBs
    errors.expects(
        span <= max(1 << 22, 16 * max(n, 1)),
        "wrap_mutable: max global id %d is far beyond the row count %d "
        "— the in-graph id→pos map is dense over [0, max_id]; use "
        "dense-ish ids (docs/mutation.md)", span - 1, n,
    )
    id_to_pos = np.full(span, -1, np.int32)
    id_to_pos[sids[valid]] = np.nonzero(valid)[0].astype(np.int32)
    delta = DeltaStore(
        vecs=jnp.zeros((nl, delta_cap, d), jnp.float32),
        ids=jnp.full((nl, delta_cap), -1, jnp.int32),
        live=jnp.zeros((nl, delta_cap), jnp.int8),
        counts=jnp.zeros((nl,), jnp.int32),
        cap=int(delta_cap),
    )
    out = MutableIndex(
        index=index,
        delta=delta,
        row_mask=jnp.ones((n + 1,), jnp.int8),
        id_to_pos=jnp.asarray(id_to_pos),
    )
    out.name = str(name)
    return out


# ------------------------------------------------------------- mutation ops
@jax.jit
def _upsert_impl(centroids, delta, row_mask, id_to_pos, vecs, ids):
    """In-graph upsert of a (B, d) batch: assign each row to its nearest
    centroid, decide ACCEPTANCE first, then — for accepted rows only —
    tombstone any previous copy (main slab via ``id_to_pos``, delta via
    an id match) and scatter into the lists' delta segments. A rejected
    row is a strict NO-OP: its previous copy keeps serving (the ack
    contract — False means "compact, then retry", never a lost row).
    Everything is a runtime value — a non-full upsert never recompiles.

    Also returns ``dirty_sup`` (n_lists,) — lists whose EXISTING delta
    copy was superseded — so incremental checkpoints rewrite the old
    copy's list too, not just the new one."""
    f32 = jnp.float32
    B = ids.shape[0]
    n_lists = centroids.shape[0]
    cap = delta.ids.shape[1]
    lbl = kmeans_predict(vecs.astype(f32), centroids).astype(jnp.int32)

    # 1) acceptance: slot = current count + within-batch rank among
    # same-list rows (two-pass stable sort, the
    # invert_probe_map_ranked idiom), capped by the static capacity
    order = jnp.argsort(lbl, stable=True)
    ls = lbl[order]
    starts = jnp.searchsorted(
        ls, jnp.arange(n_lists, dtype=ls.dtype)
    ).astype(jnp.int32)
    within = jnp.zeros((B,), jnp.int32).at[order].set(
        jnp.arange(B, dtype=jnp.int32) - starts[ls]
    )
    slot = delta.counts[lbl] + within
    accepted = (slot < cap) & (ids >= 0)
    ok_ids = jnp.where(accepted, ids, -1)

    # 2) tombstone the previous MAIN copy of each ACCEPTED id
    span = id_to_pos.shape[0]
    inr = (ok_ids >= 0) & (ok_ids < span)
    pos = jnp.where(inr, id_to_pos[jnp.clip(ok_ids, 0, span - 1)], -1)
    tgt_pos = jnp.where(pos >= 0, pos, row_mask.shape[0])    # OOB drops
    row_mask = row_mask.at[tgt_pos].set(0, mode="drop")

    # 3) supersede matching EXISTING delta entries of ACCEPTED ids
    match = (delta.ids[:, :, None] == ok_ids[None, None, :]) & (
        (ok_ids >= 0)[None, None, :]
    )
    superseded = match.any(axis=2)
    dirty_sup = (superseded & (delta.live > 0)).any(axis=1)  # (n_lists,)
    live = jnp.where(superseded, 0, delta.live).astype(delta.live.dtype)

    # 4) append accepted rows
    tgt = jnp.where(accepted, slot, cap)                     # cap drops
    new = DeltaStore(
        vecs=delta.vecs.at[lbl, tgt].set(
            vecs.astype(delta.vecs.dtype), mode="drop"
        ),
        ids=delta.ids.at[lbl, tgt].set(ids, mode="drop"),
        live=live.at[lbl, tgt].set(1, mode="drop"),
        counts=delta.counts.at[lbl].add(accepted.astype(jnp.int32)),
        cap=delta.cap,
    )
    return new, row_mask, accepted, lbl, dirty_sup


@jax.jit
def _delete_impl(delta, row_mask, id_to_pos, ids):
    """In-graph tombstone delete of a (B,) id batch: flip the main-slab
    mask entry and kill matching live delta entries. Returns the new
    state plus ``found`` (the id existed live somewhere) and a per-list
    dirty flag for incremental checkpointing."""
    span = id_to_pos.shape[0]
    inr = (ids >= 0) & (ids < span)
    pos = jnp.where(inr, id_to_pos[jnp.clip(ids, 0, span - 1)], -1)
    safe = jnp.clip(pos, 0, row_mask.shape[0] - 1)
    main_found = (pos >= 0) & (row_mask[safe] > 0)
    tgt = jnp.where(pos >= 0, pos, row_mask.shape[0])        # OOB drops
    row_mask = row_mask.at[tgt].set(0, mode="drop")

    match = (delta.ids[:, :, None] == ids[None, None, :]) & (
        (ids >= 0)[None, None, :]
    )
    m_live = match & (delta.live > 0)[:, :, None]
    delta_found = m_live.any(axis=(0, 1))                    # (B,)
    dirty = m_live.any(axis=(1, 2))                          # (n_lists,)
    live = jnp.where(m_live.any(axis=2), 0, delta.live).astype(
        delta.live.dtype
    )
    return (
        dataclasses.replace(delta, live=live),
        row_mask,
        main_found | delta_found,
        dirty,
    )


def upsert(mindex: MutableIndex, vectors, ids):
    """Upsert a batch of rows. Returns ``(new_mindex, accepted)`` where
    ``accepted`` is a host (B,) bool array — True is the ACK: the row is
    durably in its list's delta segment and visible to the next search.
    False means the assigned list's segment is full (compact, then
    retry) or the id was negative — and the rejection is a strict
    NO-OP: the id's previous copy (main slab or delta) keeps serving.

    A row whose id already exists (main slab or delta) supersedes the
    old copy — the previous version is tombstoned in the same dispatch.
    Ids must be unique WITHIN one batch (duplicates both land and the
    search may surface either; split such batches). The ack requires one
    small host sync per batch — batch upserts accordingly."""
    vecs = jnp.asarray(vectors)
    idarr = jnp.asarray(ids, jnp.int32)
    errors.check_matrix(vecs, "vectors")
    errors.check_same_cols(vecs, mindex.index.centroids, "vectors", "index")
    errors.expects(
        idarr.shape == (vecs.shape[0],),
        "ids: expected shape (%d,), got %s", vecs.shape[0],
        tuple(idarr.shape),
    )
    t0 = time.perf_counter()
    delta, row_mask, accepted, lbl, dirty_sup = _upsert_impl(
        mindex.index.centroids, mindex.delta, mindex.row_mask,
        mindex.id_to_pos, vecs, idarr,
    )
    accepted_np = np.asarray(accepted)
    ms = _mseries(mindex.name)
    ms["op_ms"]["upsert"].observe((time.perf_counter() - t0) * 1e3)
    n_acc = int(accepted_np.sum())
    ms["rows"]["accepted"].inc(n_acc)
    ms["rows"]["rejected"].inc(int(accepted_np.size) - n_acc)
    out = _with(mindex, delta=delta, row_mask=row_mask)
    out.dirty_lists.update(np.asarray(lbl)[accepted_np].tolist())
    # a superseded delta copy dirties ITS list too — an incremental
    # checkpoint that misses it would resurrect the stale copy on replay
    out.dirty_lists.update(np.nonzero(np.asarray(dirty_sup))[0].tolist())
    if n_acc:
        # an APPLIED write bumps the mutation epoch — pre-write cached
        # results must go stale (docs/serving.md "Hot traffic"); an
        # all-rejected batch changed nothing and keeps the cache warm
        out.epoch = mindex.epoch + 1
        changed = set(np.asarray(lbl)[accepted_np].tolist())
        changed |= set(
            np.nonzero(np.asarray(dirty_sup))[0].tolist())
        # a superseded MAIN copy flips a tombstone in its list's slab
        # range — the tier journal must name that list too
        changed |= _main_slab_lists(
            mindex, np.asarray(idarr)[accepted_np])
        _journal_note(out, changed)
    return out, accepted_np


def delete(mindex: MutableIndex, ids):
    """Tombstone-delete a batch of ids. Returns ``(new_mindex, found)``;
    ``found[i]`` is True when the id existed live (main slab or delta).
    One runtime mask flip — never a recompile."""
    idarr = jnp.asarray(ids, jnp.int32)
    errors.expects(
        idarr.ndim == 1, "ids: expected a 1-d batch, got shape %s",
        tuple(idarr.shape),
    )
    t0 = time.perf_counter()
    delta, row_mask, found, dirty = _delete_impl(
        mindex.delta, mindex.row_mask, mindex.id_to_pos, idarr
    )
    out = _with(mindex, delta=delta, row_mask=row_mask)
    out.dirty_lists.update(np.nonzero(np.asarray(dirty))[0].tolist())
    found_np = np.asarray(found)
    if bool(found_np.any()):
        # a delete that actually removed a live row invalidates cached
        # results, exactly like an applied upsert
        out.epoch = mindex.epoch + 1
        changed = set(np.nonzero(np.asarray(dirty))[0].tolist())
        # a main-slab hit flips row_mask inside its list's slab range;
        # the journal must name that list for tier invalidation
        changed |= _main_slab_lists(mindex, np.asarray(idarr))
        _journal_note(out, changed)
    ms = _mseries(mindex.name)
    ms["op_ms"]["delete"].observe((time.perf_counter() - t0) * 1e3)
    n_found = int(found_np.sum())
    ms["rows"]["deleted"].inc(n_found)
    ms["rows"]["missing"].inc(int(found_np.size) - n_found)
    return out, found_np


# --------------------------------------------------------------- search
def delta_merge_topk(qf, vals, ids, dvec, dids, valid, k):
    """The shared exact-delta-scan + fold tail of EVERY mutable search
    (single-chip ``_mut_search_impl`` and the sharded engines'
    ``_merge_local_delta``): score the flattened (DL, d) delta rows
    exactly (HIGHEST precision — delta distances merge against the
    engines' exact/refined distances), mask by ``valid``, and fold the
    top-k into the caller's (nq, k) candidates. One implementation so
    the two tiers can never drift."""
    f32 = jnp.float32
    dv = dvec.astype(f32)
    qn = jnp.sum(qf * qf, axis=1)
    vn = jnp.sum(dv * dv, axis=1)
    dots = jax.lax.dot_general(
        qf, dv, (((1,), (1,)), ((), ())), preferred_element_type=f32,
        precision=jax.lax.Precision.HIGHEST,
    )
    d2 = jnp.where(
        valid[None, :], qn[:, None] + vn[None, :] - 2.0 * dots, jnp.inf
    )
    kd = min(k, dids.shape[0])
    nv, dp = jax.lax.top_k(-d2, kd)
    dvals = -nv
    dsel = jnp.where(jnp.isfinite(dvals), dids[dp], -1)
    fv, fp = jax.lax.top_k(-jnp.concatenate([vals, dvals], axis=1), k)
    fi = jnp.take_along_axis(
        jnp.concatenate([ids, dsel], axis=1), fp, axis=1
    )
    return -fv, fi


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "n_probes", "qcap", "list_block", "engine", "refine_ratio",
        "exact_selection", "approx_recall_target", "use_pallas",
        "pallas_interpret",
    ),
)
def _mut_search_impl(index, delta, row_mask, q, k, n_probes, qcap,
                     list_block, engine, refine_ratio, exact_selection,
                     approx_recall_target, use_pallas, pallas_interpret):
    f32 = jnp.float32
    qf = q.astype(f32)
    if engine == "flat":
        # the kernel path masks tombstones at its exact rerank tail
        # (the in-kernel sub-chunk minima are unmasked — same contract
        # as the PQ branch below; docs/mutation.md)
        mv, mi = _grouped_impl(
            index, qf, k, n_probes, qcap, list_block, row_mask=row_mask,
            use_pallas=use_pallas, pallas_interpret=pallas_interpret,
        )
    elif engine == "sq":
        # the SQ mode of the one grouped scan body: same tombstone
        # contract as the flat branch (kernel path masks per ROW at the
        # exact rerank tail, which also dequantizes through the affine
        # map — a dead row can crowd a pool slot, never surface)
        mv, mi = _grouped_impl(
            _flat_view(index), qf, k, n_probes, qcap, list_block,
            row_mask=row_mask, use_pallas=use_pallas,
            pallas_interpret=pallas_interpret,
            dequant=(index.vmin.astype(f32), index.vscale.astype(f32)),
        )
    else:
        mv, mi = _pq_grouped_impl(
            index, qf, k, n_probes, qcap, list_block, refine_ratio,
            None, None, exact_selection, approx_recall_target, None,
            use_pallas, pallas_interpret, row_mask=row_mask,
        )
    # dense exact scan of the delta segments: the delta is small by
    # construction (compaction drains it), and a dense scan makes every
    # fresh row visible regardless of the probe map — no delta probe
    # misses during ingest
    nl, cap, d = delta.vecs.shape
    dids = delta.ids.reshape(nl * cap)
    valid = (dids >= 0) & (delta.live.reshape(nl * cap) > 0)
    return delta_merge_topk(
        qf, mv, mi, delta.vecs.reshape(nl * cap, d), dids, valid, k
    )


def mutable_search(
    mindex: MutableIndex, queries, k: int, *, n_probes: int = 8,
    qcap: typing.Union[int, str, None] = None,
    list_block: typing.Optional[int] = None,
    refine_ratio: float = 2.0, exact_selection: bool = False,
    approx_recall_target: float = 0.95,
    use_pallas: typing.Optional[bool] = None,
):
    """Grouped search over a mutable index: the frozen engine's scan with
    the tombstone mask folded in, merged with a dense exact scan of the
    delta segments. Same return convention as the engine's own grouped
    search (IVF-Flat applies sqrt for ``metric='l2'``; IVF-PQ returns
    squared distances, exact when refinement is active).

    Upserts, deletes, and this search share ONE compiled program per
    static config: every mutation is a runtime value, so the
    upsert→search→delete cycle never recompiles (trace-audited in
    tests/test_mutation.py). ``qcap`` resolves SHAPE-ONLY
    (:func:`...common.static_qcap`) — the mutation tier is a serving
    workload, and the data-dependent auto path would host-sync per
    dispatch. ``use_pallas`` selects the frozen scan's engine for ALL
    THREE index kinds (the PQ ADC kernel / the flat sub-chunk-min
    kernel / the int8 SQ dequant+scan kernel); every kernel path
    applies the tombstone ``row_mask`` at its exact rerank tail — a
    dead row can crowd a pool slot, never surface. SQ returns squared
    distances over the dequantized vectors, like its grouped search."""
    q = jnp.asarray(queries)
    errors.check_matrix(q, "queries")
    errors.check_same_cols(q, mindex.index.centroids, "queries", "index")
    index = mindex.index
    engine = mindex.engine
    storage = index.storage
    errors.expects(
        k <= n_probes * storage.max_list,
        "k=%d exceeds the candidate pool (n_probes*max_list=%d)",
        k, n_probes * storage.max_list,
    )
    nl = index.centroids.shape[0]
    qc = static_qcap(qcap, q.shape[0], n_probes, nl)
    lb = list_block if list_block is not None else (8 if engine == "pq"
                                                   else 32)
    lb = max(1, min(lb, nl))
    if engine == "pq":
        refine_active = (
            index.vectors_sorted is not None and refine_ratio > 1.0
        )
        up = _resolve_adc_engine(
            use_pallas, refine_active, index.pq_dim, index.pq_bits, qc
        )
        vals, ids = _mut_search_impl(
            index, mindex.delta, mindex.row_mask, q, k, n_probes, qc, lb,
            "pq", refine_ratio, exact_selection, approx_recall_target,
            up, jax.default_backend() != "tpu",
        )
        return vals, ids
    if engine == "sq":
        up = _resolve_sq_engine(use_pallas, index.centroids.shape[1], qc)
        return _mut_search_impl(
            index, mindex.delta, mindex.row_mask, q, k, n_probes, qc, lb,
            "sq", refine_ratio, exact_selection, approx_recall_target,
            up, jax.default_backend() != "tpu",
        )
    up = _resolve_scan_engine(use_pallas, index.centroids.shape[1], qc)
    vals, ids = _mut_search_impl(
        index, mindex.delta, mindex.row_mask, q, k, n_probes, qc, lb,
        "flat", refine_ratio, exact_selection, approx_recall_target,
        up, jax.default_backend() != "tpu",
    )
    if index.metric == "l2":
        vals = jnp.sqrt(jnp.maximum(vals, 0.0))
    return vals, ids


def mutable_warmup(mindex: MutableIndex, nq: int, *, k: int = 10,
                   n_probes: int = 8, qcap=None,
                   ingest_batch: int = 0, **search_kw) -> int:
    """Pre-compile the mutable serving programs for (nq, d) batches —
    the mutation sibling of ``index.warmup(nq)``: one all-zeros search
    batch plus (when ``ingest_batch`` > 0) one all-rejected upsert and
    one no-op delete of that batch size, so the first real mixed
    read/write traffic pays dispatch, not trace+compile. Returns the
    shape-only-resolved qcap to pass on every serving dispatch."""
    d = mindex.index.centroids.shape[1]
    qc = static_qcap(qcap, nq, n_probes, mindex.n_lists)
    out = mutable_search(
        mindex, jnp.zeros((nq, d), jnp.float32), k, n_probes=n_probes,
        qcap=qc, **search_kw,
    )
    jax.block_until_ready(out)
    if ingest_batch > 0:
        # ids = -1: the dispatch runs the full program but accepts (and
        # mutates) nothing — warm-up must not consume delta slots
        z = jnp.zeros((ingest_batch, d), jnp.float32)
        neg = jnp.full((ingest_batch,), -1, jnp.int32)
        jax.block_until_ready(_upsert_impl(
            mindex.index.centroids, mindex.delta, mindex.row_mask,
            mindex.id_to_pos, z, neg,
        ))
        jax.block_until_ready(_delete_impl(
            mindex.delta, mindex.row_mask, mindex.id_to_pos, neg
        ))
    return qc


# ----------------------------------------------------------- compaction
def compaction_stats(mindex: MutableIndex) -> dict:
    """Host-side mutation-pressure stats (syncs the SMALL bookkeeping
    arrays only): delta fill fractions, live delta rows, and the
    tombstoned fraction of the main slab."""
    delta = mindex.delta
    counts = np.asarray(delta.counts)
    live = (np.asarray(delta.live) > 0) & (np.asarray(delta.ids) >= 0)
    sids = np.asarray(mindex.index.storage.sorted_ids)
    real = sids >= 0
    n_real = max(int(real.sum()), 1)
    rm = np.asarray(mindex.row_mask)[: sids.shape[0]] > 0
    dead = int((real & ~rm).sum())
    out = {
        "delta_fill": float(counts.sum() / max(counts.size * delta.cap, 1)),
        "delta_max_fill": float(counts.max() / delta.cap)
        if counts.size else 0.0,
        "delta_live_rows": int(live.sum()),
        "tombstone_frac": dead / n_real,
        "main_rows": n_real,
    }
    # the mutation-pressure gauges: every reader of these stats (the
    # BackgroundCompactor cycle, an operator poll) refreshes the live
    # values an alert can watch between compactions
    ms = _mseries(mindex.name)
    ms["fill"].set(out["delta_fill"])
    ms["max_fill"].set(out["delta_max_fill"])
    ms["tombstone"].set(out["tombstone_frac"])
    return out


@dataclasses.dataclass(frozen=True)
class CompactionPolicy:
    """When the background compactor should fold the mutation state back
    into the main slabs: any list's delta segment past ``max_fill_frac``
    of its capacity (the next upserts into it would be rejected), or the
    tombstoned fraction past ``max_tombstone_frac`` (dead rows tax every
    padded scan). ``refresh_every``: run the warm-started centroid
    refresh on every N-th compaction (0 = never)."""

    max_fill_frac: float = 0.5
    max_tombstone_frac: float = 0.25
    refresh_every: int = 4

    def should_compact(self, stats: dict) -> bool:
        return (
            stats["delta_max_fill"] >= self.max_fill_frac
            or stats["tombstone_frac"] >= self.max_tombstone_frac
        )


def probe_overlap(old_centroids, new_centroids, queries,
                  n_probes: int = 8) -> float:
    """The centroid-refresh drift guardrail (the
    ``coarse_probe_recall`` idiom applied across a refresh): mean
    per-query fraction of probed CENTROID POSITIONS shared by the old
    and refreshed centroid sets on ``queries``. Warm-started refreshes
    move centroids gently, so positions keep their identity; a low
    overlap means the refresh redistributed lists enough that recall
    should be re-measured before serving resumes (eager, host sync — an
    audit, not a serving-path call)."""
    qf = jnp.asarray(queries, jnp.float32)
    a, _ = coarse_probe(qf, jnp.asarray(old_centroids, jnp.float32),
                        n_probes)
    b, _ = coarse_probe(qf, jnp.asarray(new_centroids, jnp.float32),
                        n_probes)
    a, b = np.asarray(a), np.asarray(b)
    hits = sum(
        len(set(x.tolist()) & set(y.tolist())) for x, y in zip(a, b)
    )
    return hits / a.size


def _padded_storage(labels_np, gids, n_lists, list_bucket, row_bucket):
    """Build a ListStorage whose statics are BUCKETED: ``max_list`` and
    the slab height round up to coarse multiples so a steady-state
    compact→ingest→compact cycle usually re-lands on the same statics
    and reuses every compiled program (the ``_slab_height`` idiom from
    the sharded builds). Returns (storage, order, n_real)."""
    base = build_list_storage(labels_np, n_lists)
    n_real = labels_np.shape[0]
    ml = -(-max(int(base.max_list), 1) // list_bucket) * list_bucket
    nb = -(-max(n_real, 1) // row_bucket) * row_bucket
    # ml <= nb always: both round up, ml from a count <= n_real and
    # row_bucket is a multiple of list_bucket
    sizes = np.asarray(base.list_sizes)
    offsets = np.asarray(base.list_offsets)
    list_index = np.full((n_lists, ml), nb, np.int32)
    for l in range(n_lists):
        c = int(sizes[l])
        list_index[l, :c] = np.arange(offsets[l], offsets[l] + c)
    order = np.asarray(base.sorted_ids)              # positions into input
    sorted_gids = np.concatenate(
        [gids[order], np.full(nb - n_real, -1, np.int32)]
    )
    storage = ListStorage(
        sorted_ids=jnp.asarray(sorted_gids),
        list_offsets=jnp.asarray(offsets),
        list_index=jnp.asarray(list_index),
        list_sizes=jnp.asarray(sizes),
        n=int(nb),
        max_list=int(ml),
    )
    return storage, order, n_real


def compact(
    mindex: MutableIndex, *, refresh_centroids: bool = False,
    kmeans_n_iters: int = 4, drift_queries=None, n_probes: int = 8,
    min_probe_overlap: float = 0.5, list_bucket: int = 64,
    row_bucket: int = 256,
):
    """Merge delta segments and drop tombstoned rows into fresh main
    slabs (host-side, like every index build). Returns
    ``(new_mindex, stats)`` — the new state has empty deltas, an
    all-live mask, and every surviving row under its (possibly
    refreshed) list with its GLOBAL id preserved.

    ``refresh_centroids=True`` re-fits the coarse quantizer WARM-STARTED
    from the current centroids (``kmeans_fit(..., centroids=old)``) so
    drifted ingest re-balances lists without a from-scratch retrain;
    when ``drift_queries`` is given, the :func:`probe_overlap` drift
    guardrail ASSERTS the refreshed probe map overlaps the old one by at
    least ``min_probe_overlap`` (raise the refresh cadence — or lower
    ``kmeans_n_iters`` — when it trips). PQ codebooks are kept; survivor
    rows are re-encoded against them (requires ``store_raw``).

    Compaction is the one mutation-tier operation allowed to change
    static shapes; ``list_bucket``/``row_bucket`` coarsen ``max_list``
    and the slab height so steady-state cycles usually keep the compiled
    programs (re-run :func:`mutable_warmup` before swapping the state in
    when they do change — :class:`BackgroundCompactor` leaves the old
    state serving until then)."""
    t_compact0 = time.perf_counter()
    index = mindex.index
    engine = mindex.engine
    storage = index.storage
    d = index.centroids.shape[1]
    sids = np.asarray(storage.sorted_ids)
    rm = np.asarray(mindex.row_mask)[: sids.shape[0]] > 0
    keep = np.nonzero(rm & (sids >= 0))[0]
    if engine == "flat":
        base_rows = np.asarray(index.data_sorted)[keep]
    elif engine == "sq":
        # survivors keep their stored codes VERBATIM through the fold
        # (stashed here, re-permuted below — decode->re-encode would
        # drift a code unit when |vmin| dwarfs the dimension's range);
        # the dequantized rows are needed only for (re)assignment
        from raft_tpu.spatial.ann.ivf_sq import sq_decode

        codes_keep = np.asarray(index.codes_sorted)[keep]
        base_rows = np.asarray(sq_decode(
            codes_keep.astype(np.float32), index.vmin, index.vscale,
        ))
    else:
        errors.expects(
            index.vectors_sorted is not None,
            "compact: a codes-only IVF-PQ index cannot be compacted — "
            "survivor rows must be re-encoded from raw vectors "
            "(build with store_raw=True)",
        )
        base_rows = np.asarray(index.vectors_sorted)[keep]
    ids_main = sids[keep]
    dlive = (np.asarray(mindex.delta.live) > 0) & (
        np.asarray(mindex.delta.ids) >= 0
    )
    dvecs = np.asarray(mindex.delta.vecs)[dlive]
    ids_delta = np.asarray(mindex.delta.ids)[dlive]
    x = np.concatenate(
        [base_rows.astype(np.float32), dvecs.astype(np.float32)]
    )
    gids = np.concatenate([ids_main, ids_delta]).astype(np.int32)
    errors.expects(
        x.shape[0] >= 1,
        "compact: no rows survive (everything tombstoned) — an empty "
        "index cannot be compacted; rebuild instead",
    )
    cents_old = np.asarray(index.centroids, np.float32)
    stats = dict(compaction_stats(mindex))
    stats["survivors"] = int(x.shape[0])
    if refresh_centroids:
        out = kmeans_fit(
            jnp.asarray(x),
            KMeansParams(
                n_clusters=cents_old.shape[0], max_iter=kmeans_n_iters,
                init="random", compute_dtype="bfloat16",
            ),
            centroids=cents_old,                     # warm start
        )
        cents_new = np.asarray(out.centroids, np.float32)
        stats["refreshed"] = True
        if drift_queries is not None:
            ov = probe_overlap(cents_old, cents_new, drift_queries,
                               n_probes)
            stats["probe_overlap"] = ov
            errors.expects(
                ov >= min_probe_overlap,
                "compact: centroid refresh drifted the probe map — "
                "probe_overlap %.3f < min_probe_overlap %.3f; refresh "
                "more often (smaller drift per refresh) or re-measure "
                "recall before serving", ov, min_probe_overlap,
            )
    else:
        cents_new = cents_old
        stats["refreshed"] = False

    nl = cents_new.shape[0]
    xj = jnp.asarray(x)
    cj = jnp.asarray(cents_new)
    if engine == "pq":
        M = index.pq_dim
        ds = d // M
        lbl, codes = _encode_block_jit(xj, cj, index.codebooks, M, ds)
        labels_np = np.asarray(lbl)
        codes_np = np.asarray(codes)
    else:
        labels_np = np.asarray(kmeans_predict(xj, cj))
    st, order, n_real = _padded_storage(
        labels_np, gids, nl, list_bucket, row_bucket
    )
    nb = st.n
    pad = nb - n_real
    rows_sorted = np.concatenate(
        [x[order], np.zeros((pad + 1, d), np.float32)]
    )
    if engine == "flat":
        new_index = IVFFlatIndex(
            centroids=jnp.asarray(cents_new),
            data_sorted=place_row_store(jnp.asarray(rows_sorted.astype(
                np.asarray(index.data_sorted).dtype
            ))),
            storage=st,
            metric=index.metric,
        )
    elif engine == "sq":
        # survivors carry their stored codes verbatim; ONLY the delta
        # rows pay the quantization step they deferred at ingest,
        # against the KEPT stats through THE shared encoder (the
        # PQ-codebook rule applied to the affine map: compaction never
        # retrains the quantizer, only the coarse centroids may refresh)
        from raft_tpu.spatial.ann.ivf_sq import sq_encode

        codes_all = np.concatenate([
            codes_keep,
            np.asarray(sq_encode(dvecs, index.vmin, index.vscale)),
        ])                                       # aligned with x's rows
        codes_new = np.concatenate([
            codes_all[order],
            np.zeros((pad + 1, d), np.int8),     # pad + sentinel rows
        ])
        new_index = IVFSQIndex(
            centroids=jnp.asarray(cents_new),
            codes_sorted=place_row_store(jnp.asarray(codes_new)),
            vmin=index.vmin,
            vscale=index.vscale,
            storage=st,
        )
    else:
        codes_sorted = np.concatenate(
            [codes_np[order],
             np.zeros((pad + 1, index.pq_dim), np.uint8)]
        )
        new_index = IVFPQIndex(
            centroids=jnp.asarray(cents_new),
            codebooks=index.codebooks,
            codes_sorted=jnp.asarray(codes_sorted),
            storage=st,
            vectors_sorted=jnp.asarray(rows_sorted.astype(
                np.asarray(index.vectors_sorted).dtype
            )),
            pq_dim=index.pq_dim,
            pq_bits=index.pq_bits,
        )
    out = wrap_mutable(new_index, delta_cap=mindex.delta.cap,
                       name=mindex.name)
    out.dirty_lists = set(range(nl))   # every list changed on disk
    # compaction continues (and bumps) the epoch chain: the fold can
    # re-encode rows and refresh centroids, so pre-compaction cached
    # results must die exactly like post-upsert ones — and the counter
    # must not RESET (wrap_mutable starts at 0; a reset would mark old
    # cache entries fresh again)
    out.epoch = mindex.epoch + 1
    # compaction re-sorts every slab: a None journal entry tells
    # :func:`lists_changed_since` "everything" — tier consumers must do
    # a full host refresh, not a per-list invalidation
    _journal_note(out, None)
    stats["max_list"] = st.max_list
    stats["n_slab"] = nb
    ms = _mseries(mindex.name)
    ms["op_ms"]["compact"].observe(
        (time.perf_counter() - t_compact0) * 1e3)
    ms["compactions"].inc()
    return out, stats


class BackgroundCompactor:
    """Runs :func:`compact` off-thread while the CALLER keeps serving
    searches on the old state (state is functional — readers never see a
    half-compacted index).

    Swap protocol (docs/mutation.md "Lifecycle"): ``maybe_submit`` a
    SNAPSHOT of the current state; keep serving and BUFFER subsequent
    writes (or re-apply them after the swap — upsert/delete are
    idempotent by id); when ``poll`` returns the compacted state, warm
    it (:func:`mutable_warmup` — compaction may have re-bucketed the
    statics) and swap it in. One compaction in flight at a time."""

    def __init__(self, policy: CompactionPolicy = CompactionPolicy(),
                 **compact_kw):
        self.policy = policy
        self._kw = compact_kw
        self._lock = lockcheck.make_lock("BackgroundCompactor._lock")
        self._thread: typing.Optional[threading.Thread] = None
        self._result = None
        self._error: typing.Optional[BaseException] = None
        self._n_compactions = 0

    @property
    def busy(self) -> bool:
        with self._lock:
            return self._thread is not None and self._thread.is_alive()

    def submit(self, mindex: MutableIndex) -> bool:
        """Start a compaction of ``mindex`` (a snapshot); False when one
        is already in flight or an unpolled result is pending."""
        with self._lock:
            if (self._thread is not None and self._thread.is_alive()) or \
                    self._result is not None or self._error is not None:
                return False
            kw = dict(self._kw)
            if self.policy.refresh_every:
                due = (self._n_compactions + 1) % self.policy.refresh_every
                kw.setdefault("refresh_centroids", due == 0)

            def work():
                try:
                    res = compact(mindex, **kw)
                except BaseException as e:  # noqa: BLE001 — surfaced on poll
                    with self._lock:
                        self._error = e
                    return
                with self._lock:
                    self._result = res
                    self._n_compactions += 1

            obs_crash.install_excepthook()
            self._thread = threading.Thread(
                target=work, daemon=True, name="ann-compactor")
            self._thread.start()
            return True

    def maybe_submit(self, mindex: MutableIndex) -> bool:
        """Submit iff the policy says the state needs compaction."""
        if self.busy:
            return False
        if not self.policy.should_compact(compaction_stats(mindex)):
            return False
        return self.submit(mindex)

    def poll(self):
        """``(new_mindex, stats)`` when a compaction finished, else
        None. Re-raises a failed compaction's error."""
        with self._lock:
            if self._error is not None:
                err, self._error = self._error, None
                raise err
            if self._result is None:
                return None
            res, self._result = self._result, None
            return res

    def join(self, timeout: typing.Optional[float] = None) -> None:
        with self._lock:
            t = self._thread
        if t is not None:
            t.join(timeout)

    def stop(self, timeout_s: float = 30.0) -> None:
        """Join the in-flight compaction (bounded) and RE-RAISE a
        stored worker exception instead of dropping it — the shutdown
        analog of :meth:`poll`: a compaction that crashed after the
        caller stopped polling must not vanish with the process.
        Raises ``TimeoutError`` if the worker outlives ``timeout_s``
        (the thread ref is read under the lock; the join itself blocks
        without it)."""
        with self._lock:
            t = self._thread
        if t is not None:
            t.join(timeout_s)
            if t.is_alive():
                raise TimeoutError(
                    f"BackgroundCompactor: worker still running after "
                    f"{timeout_s:.1f}s")
        with self._lock:
            self._thread = None
            if self._error is not None:
                err, self._error = self._error, None
                raise err


# ------------------------------------------- incremental checkpoint (v4)
_DELTA_KIND = "mutation-delta"
_DELTA_VERSION = 4


def save_delta_checkpoint(mindex: MutableIndex, path,
                          *, lists=None, wal_lsn=None) -> list:
    """Write an INCREMENTAL v4 checkpoint: only dirty lists' delta
    segments (``lists`` overrides the tracked dirty set), plus the small
    full ``row_mask``/``counts`` arrays, each CRC32-manifested like the
    main serialization (docs/mutation.md "Checkpoint v4"). Pair with a
    full base checkpoint (:func:`raft_tpu.spatial.ann.save_index`, which
    stamps v4 for mutable payloads); replay newest-last with
    :func:`apply_delta_checkpoint`, which is idempotent — a duplicated
    flush re-applies to the same state. Clears the dirty set; returns
    the list ids written.

    ``wal_lsn`` (optional) stamps the durable-ingest watermark into the
    header: the checkpoint captures every WAL record up to and
    including that LSN, so recovery replays only the tail past it and
    :meth:`raft_tpu.durability.wal.WalWriter.prune` may retire
    segments behind it (docs/robustness.md "Durability"). Readable
    back via :func:`delta_checkpoint_watermark`; absent on
    non-durable-path checkpoints (older files load unchanged)."""
    from raft_tpu.spatial.ann.serialize import _array_crc

    ls = sorted(set(mindex.dirty_lists if lists is None else lists))
    delta = mindex.delta
    arrays = {
        "row_mask": np.asarray(mindex.row_mask),
        "counts": np.asarray(delta.counts),
    }
    dv = np.asarray(delta.vecs)
    di = np.asarray(delta.ids)
    dl = np.asarray(delta.live)
    for l in ls:
        errors.expects(
            0 <= l < di.shape[0],
            "save_delta_checkpoint: list %d out of range [0, %d)",
            l, di.shape[0],
        )
        arrays[f"list.{l}.vecs"] = dv[l]
        arrays[f"list.{l}.ids"] = di[l]
        arrays[f"list.{l}.live"] = dl[l]
    header = {
        "kind": _DELTA_KIND,
        "version": _DELTA_VERSION,
        "n_lists": int(di.shape[0]),
        "cap": int(delta.cap),
        "lists": [int(l) for l in ls],
        **({} if wal_lsn is None else {"wal_lsn": int(wal_lsn)}),
        "integrity": {
            key: {
                "crc32": _array_crc(arr),
                "shape": list(arr.shape),
                "dtype": str(arr.dtype),
            }
            for key, arr in arrays.items()
        },
    }
    with open(path, "wb") as f:
        np.savez(
            f,
            __header__=np.frombuffer(
                json.dumps(header).encode("utf-8"), dtype=np.uint8
            ),
            **arrays,
        )
    mindex.dirty_lists.clear()
    return ls


def delta_checkpoint_watermark(path):
    """Read a delta checkpoint's ``wal_lsn`` watermark (the highest WAL
    LSN the checkpoint captures) without loading its arrays — what
    recovery uses to start the tail replay. ``None`` when the file
    predates the durability tier or was written without a WAL."""
    try:
        with np.load(path) as npz:
            header = json.loads(bytes(npz["__header__"]).decode("utf-8"))
    except Exception as e:
        raise errors.CorruptIndexError(
            f"delta_checkpoint_watermark: header unreadable ({e})",
            field="__header__",
        ) from e
    lsn = header.get("wal_lsn")
    return None if lsn is None else int(lsn)


def apply_delta_checkpoint(mindex: MutableIndex, path) -> MutableIndex:
    """Splice a :func:`save_delta_checkpoint` file into ``mindex``
    (idempotent — set semantics per list, so a duplicated flush is
    harmless). Damage — a torn write, a duplicated/stale block beneath
    the container checksums, a future format version — raises
    :class:`raft_tpu.errors.CorruptIndexError` naming the field, exactly
    like the main ``load_index`` path; recovery then falls back to the
    base checkpoint + a replica resync (docs/mutation.md)."""
    from raft_tpu.spatial.ann.serialize import _VerifiedArchive

    try:
        npz_file = np.load(path)
    except Exception as e:
        raise errors.CorruptIndexError(
            f"apply_delta_checkpoint: archive unreadable ({e}) — torn "
            "write or not a delta checkpoint", field="__header__"
        ) from e
    with npz_file as npz:
        try:
            header = json.loads(bytes(npz["__header__"]).decode("utf-8"))
        except Exception as e:
            raise errors.CorruptIndexError(
                f"apply_delta_checkpoint: header unreadable ({e})",
                field="__header__",
            ) from e
        if header.get("kind") != _DELTA_KIND:
            raise errors.CorruptIndexError(
                f"apply_delta_checkpoint: kind {header.get('kind')!r} is "
                f"not {_DELTA_KIND!r}", field="__header__",
            )
        v = header.get("version")
        if v != _DELTA_VERSION:
            raise errors.CorruptIndexError(
                f"apply_delta_checkpoint: format version {v!r} is not "
                f"readable by this release (expected {_DELTA_VERSION}); "
                "upgrade before restoring", field="__header__",
            )
        delta = mindex.delta
        nl = delta.ids.shape[0]
        if header.get("n_lists") != nl or header.get("cap") != delta.cap:
            raise errors.CorruptIndexError(
                "apply_delta_checkpoint: geometry mismatch (checkpoint "
                f"n_lists={header.get('n_lists')} cap={header.get('cap')}"
                f", index n_lists={nl} cap={delta.cap})",
                field="__header__",
            )
        archive = _VerifiedArchive(npz, header.get("integrity"))
        row_mask = jnp.asarray(archive["row_mask"])
        if row_mask.shape != mindex.row_mask.shape:
            raise errors.CorruptIndexError(
                f"apply_delta_checkpoint: row_mask shape "
                f"{tuple(row_mask.shape)} != index "
                f"{tuple(mindex.row_mask.shape)}", field="row_mask",
            )
        counts = jnp.asarray(archive["counts"])
        dv, di, dl = delta.vecs, delta.ids, delta.live
        for l in header.get("lists", []):
            dv = dv.at[l].set(jnp.asarray(archive[f"list.{l}.vecs"]))
            di = di.at[l].set(jnp.asarray(archive[f"list.{l}.ids"]))
            dl = dl.at[l].set(jnp.asarray(archive[f"list.{l}.live"]))
        new_delta = DeltaStore(
            vecs=dv, ids=di, live=dl, counts=counts, cap=delta.cap
        )
    return _with(mindex, delta=new_delta, row_mask=row_mask)
