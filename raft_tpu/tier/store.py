"""The two-tier slab store — cold IVF lists in host RAM, a hot working
set in HBM, membership as a RUNTIME operand of the unchanged grouped
serving program (ROADMAP item 4: "break the HBM wall").

Capacity per chip is whatever fits HBM, but PR 14 measured the Zipf
skew that made the result cache worth 3.1-3.6x — and the same skew
means list accesses are heavily skewed too: most of a shard's slab is
paid for and almost never probed. The tier splits the slab by
POPULARITY instead of truncating it:

* the COLD tier is the full list-sorted slab, held once in (pinned)
  host RAM — on CPU host-sim that is a plain numpy array; on TPU the
  same buffer is what ``jax.device_put`` DMAs from;
* the HOT tier is a fixed budget of ``n_slots`` list-sized HBM slots
  (``slot_rows = max_list`` rows each, the grouped scan's own padded
  list height), plus a parallel id slab mapping hot positions back to
  original row ids.

**The serving program is untouched.** :class:`TieredListStore` builds a
synthetic :class:`~raft_tpu.spatial.ann.ivf_flat.IVFFlatIndex` VIEW
over the hot buffer — ``data_sorted`` is the hot slab,
``storage.sorted_ids`` the hot id map, ``list_offsets``/``list_sizes``
derived from the hot-slot indirection (hot list ``l`` at slot ``s`` →
offset ``s*max_list``; a cold list gets the sentinel offset with size
0) — and calls the ONE grouped scan body
(:func:`...ivf_flat._grouped_impl`) on it. Every tier array is a
runtime operand of that compiled program, so promotion/demotion flips
are ZERO-RETRACE (pinned by the ``ivf_flat_grouped_tiered``
program-contract entry and the cache-size audit in tests/test_tier.py).
An int8 SQ index tiers its CODES (``dequant`` rides along), so bytes
halve in both tiers and on the host→device bus.

**Graceful degradation.** A probe that lands on a cold list scans an
empty slot: its ``in_list`` mask is all-false, every candidate scores
+inf, and the query is answered from the hot lists it DID hit — the
grouped scan's own sentinel discipline, no new code path. The miss is
counted, recorded into the per-list load feed
(:func:`raft_tpu.resilience.replica.record_list_load`), and handed to
the async fetcher for promotion (serve-from-hot + async fill), under
the measured recall guardrail (:meth:`TieredListStore.measure_recall`,
acceptance >= 0.95 of the hot-path recall at the bench config).

**Install = copy-publish double buffer.** A slab install is one jitted
``dynamic_update_slice`` with the slot row offset as a runtime scalar
(ONE compiled install program); it produces a NEW hot buffer and the
old one stays valid for every in-flight dispatch still holding the
previous runtime snapshot — the same no-donation rule as the executor's
hedge re-stage. Snapshots (:meth:`runtime`) are taken under the store
lock, so offsets/sizes/ids/data always describe the same membership
version.

**Mutation-epoch invalidation** (the result-cache discipline,
docs/tiering.md "Epoch invalidation"): :meth:`sync_mutations` pulls the
wrapped :class:`~raft_tpu.spatial.ann.mutation.MutableIndex`'s epoch
journal. Upsert/delete change only the tombstone ``row_mask`` (delta
rows live outside the frozen slab), so the view mask is re-gathered and
re-published — a pre-write mask can never serve after the sync.
Compaction rewrites the slab itself: the journal reports "all lists"
and the store re-snapshots its host authority and invalidates EVERY hot
slot.
"""

from __future__ import annotations

import dataclasses
import time
import typing
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from raft_tpu import errors
from raft_tpu.analysis.threads import runtime as lockcheck
from raft_tpu.obs import metrics as obs_metrics
from raft_tpu.spatial.ann.common import (
    ListStorage, place_row_store, static_qcap,
)
from raft_tpu.spatial.ann.ivf_flat import IVFFlatIndex, _grouped_impl

__all__ = ["TierRuntime", "TierStats", "TieredListStore"]


@jax.jit
def _install_rows(buf, slab, row0):
    """THE slab-install program: one dynamic_update_slice with the slot
    row offset as a runtime scalar — every slot, every list compiles to
    this single program. No donation: the returned buffer is a NEW
    array and the input stays valid for in-flight dispatches holding
    the previous runtime snapshot (the copy IS the double buffer)."""
    return lax.dynamic_update_slice(buf, slab, (row0, 0))


@jax.jit
def _install_ids(buf, ids, row0):
    return lax.dynamic_update_slice(buf, ids, (row0,))


@dataclasses.dataclass(frozen=True)
class TierRuntime:
    """One consistent tier snapshot — what a dispatch closure receives
    as its ``tier=`` runtime operand (taken under the store lock, so
    the view arrays and the row mask describe the same membership
    version). All leaves are runtime operands: snapshot swaps never
    retrace."""

    view: IVFFlatIndex        # the hot-buffer view index
    row_mask: jax.Array       # (n_view + 1,) int8 hot-position live mask
    version: int              # membership version (debugging/telemetry)
    epoch: int                # mutation epoch the snapshot reflects
    # the SQ dequant pair riding the snapshot (None for flat) — part of
    # the consistent view: a host refresh that re-quantized must never
    # mix new codes with old scales
    dequant: Optional[Tuple[jax.Array, jax.Array]] = None


@dataclasses.dataclass(frozen=True)
class TierStats:
    """Host-side counters (kept live even with ``RAFT_TPU_OBS=off`` —
    the bench and the guardrail read these, not the registry)."""

    n_lists: int
    n_slots: int
    hot_lists: int
    probe_hits: int
    probe_misses: int
    fetches: int
    demotions: int
    invalidations: int
    fetch_ms_total: float
    overlapped_fetches: int
    hot_bytes: int
    epoch: int
    last_recall: Optional[float]

    @property
    def hit_rate(self) -> float:
        tot = self.probe_hits + self.probe_misses
        return self.probe_hits / tot if tot else 0.0

    @property
    def fetch_overlap_pct(self) -> float:
        return (100.0 * self.overlapped_fetches / self.fetches
                if self.fetches else 0.0)


class TieredListStore:
    """Popularity-tiered list storage over one IVF-Flat (or SQ-coded)
    index — see the module docstring for the design.

    ``index``: an :class:`IVFFlatIndex` or
    :class:`~raft_tpu.spatial.ann.ivf_sq.IVFSQIndex` (tiered through
    its flat code view; ``dequant`` rides every scan). The index's
    arrays are snapshotted to host numpy ONCE at construction — that
    host copy IS the cold tier (and the authority every promotion
    fetches from).

    ``n_slots`` / ``hbm_budget_bytes``: the hot working set, either as
    a slot count or as a byte budget for the hot data slab
    (``n_slots = budget // (max_list * d * itemsize)``, clamped to
    ``[1, n_lists]``). A budget below ``n_lists`` slots is what makes
    this a tier; the bench serves ``>= 4x`` the budget.

    ``epoch``: the mutation epoch the snapshotted state reflects (pass
    ``mindex.epoch`` when tiering an already-mutated index — e.g. the
    post-compaction rebuild — so the first :meth:`sync_mutations` is a
    no-op instead of a full invalidation).

    ``min_recall``: the measured recall guardrail —
    :meth:`measure_recall` records into the ``tier_recall`` gauge and
    counts a ``tier_recall_breaches_total`` when the measurement falls
    below it (the store never silently degrades past the guardrail
    without a metric trail).
    """

    def __init__(self, index, *, n_slots: Optional[int] = None,
                 hbm_budget_bytes: Optional[int] = None,
                 name: str = "tier", shard: int = 0,
                 epoch: int = 0,
                 min_recall: Optional[float] = None,
                 touch_decay: float = 0.9,
                 registry: "obs_metrics.MetricRegistry | None" = None,
                 flight=None,
                 clock: Callable[[], float] = time.monotonic):
        base, dequant, origin = _resolve_base(index)
        self._origin = origin
        self._dequant = dequant
        self.name = str(name)
        self.shard = int(shard)
        self.min_recall = min_recall
        self.flight = flight
        self._clock = clock

        # -- the cold tier: ONE host snapshot of the list-sorted slab
        # (pinned host RAM on TPU; plain numpy on CPU host-sim) --------
        storage = base.storage
        self._data_np = np.asarray(base.data_sorted)     # (n + 1, d)
        self._sids_np = np.asarray(storage.sorted_ids)   # (n,)
        self._offs_np = np.asarray(storage.list_offsets)
        self._szs_np = np.asarray(storage.list_sizes)
        self._cents_np = np.asarray(base.centroids, np.float32)
        self._cn2_np = np.sum(self._cents_np ** 2, axis=1)
        self._n = int(storage.n)
        self._d = int(self._data_np.shape[1])
        self._L = int(storage.max_list)
        self._n_lists = int(storage.list_index.shape[0])
        self._metric = base.metric
        # the authoritative tombstone mask (refreshed by sync_mutations)
        self._mask_np = np.ones(self._n + 1, np.int8)

        n_slots = _resolve_slots(
            n_slots, hbm_budget_bytes, self._L, self._d,
            self._data_np.dtype.itemsize, self._n_lists,
        )
        self.n_slots = n_slots
        self._n_view = n_slots * self._L

        # -- host mirrors of the membership (under _install) -----------
        self._slot_of = np.full(self._n_lists, -1, np.int32)
        self._list_at = np.full(n_slots, -1, np.int32)
        # original sorted-slab position of each hot row (the mask
        # re-gather input); n = "points at the sentinel row"
        self._hot_pos = np.full(self._n_view, self._n, np.int64)
        self._offs_host = np.full(self._n_lists + 1, self._n_view,
                                  np.int32)
        self._szs_host = np.zeros(self._n_lists, np.int32)

        # -- device state (every array a runtime operand) --------------
        self._cents_dev = jnp.asarray(base.centroids)
        # the grouped scan reads the hot buffer as its row store: keep it
        # row-major (common.place_row_store), here and after each install
        self._hot_data = place_row_store(jnp.zeros(
            (self._n_view + 1, self._d), self._data_np.dtype))
        self._hot_ids = jnp.full((self._n_view,), -1, jnp.int32)
        # only ``.shape[0]`` of list_index is read by the grouped scan
        self._dummy_index = jnp.zeros((self._n_lists, 1), jnp.int32)
        self._offs_dev = jnp.asarray(self._offs_host)
        self._szs_dev = jnp.asarray(self._szs_host)
        self._maskv_dev = jnp.ones((self._n_view + 1,), jnp.int8)

        # -- load signal + counters ------------------------------------
        self._touch = np.zeros(self._n_lists, np.float64)
        self._touch_decay = float(touch_decay)
        self._hits = 0
        self._misses = 0
        self._fetches = 0
        self._demotions = 0
        self._invalidations = 0
        self._fetch_ms = 0.0
        self._overlapped = 0
        self._version = 0
        # the mutation epoch this snapshot reflects — seed it with the
        # source MutableIndex's CURRENT epoch when tiering mutated
        # state (e.g. a post-compaction rebuild), so the first
        # sync_mutations isn't a spurious full invalidation
        self._seen_epoch = int(epoch)
        self.last_recall: Optional[float] = None
        self._fill_sink: Optional[Callable[[Sequence[int]], None]] = None

        # ``_install`` serializes EVERY membership/data change (promote,
        # demote, mask refresh, host refresh); ``_lock`` guards only the
        # published snapshot + counters. Order: _install -> _lock.
        self._install = lockcheck.make_lock("TieredListStore._install")
        self._lock = lockcheck.make_lock("TieredListStore._lock")

        reg = (obs_metrics.default_registry()
               if registry is None else registry)
        self._c_hits = reg.counter("tier_probe_hits_total", tier=name)
        self._c_misses = reg.counter("tier_probe_misses_total", tier=name)
        self._c_fetches = reg.counter("tier_fetches_total", tier=name)
        self._c_demotions = reg.counter("tier_demotions_total", tier=name)
        self._c_invalid = reg.counter("tier_invalidations_total",
                                      tier=name)
        self._c_breach = reg.counter("tier_recall_breaches_total",
                                     tier=name)
        self._g_hot = reg.gauge("tier_hot_lists", tier=name)
        self._g_bytes = reg.gauge("tier_hot_bytes", tier=name)
        self._g_recall = reg.gauge("tier_recall", tier=name)
        self._h_fetch = reg.histogram("tier_fetch_ms", tier=name)
        self._g_bytes.set(float(self._hot_data.size
                                * self._hot_data.dtype.itemsize))

    # -- snapshots -------------------------------------------------------
    def runtime(self) -> Dict[str, TierRuntime]:
        """The runtime-operand snapshot for a serving dispatch — shaped
        for :class:`~raft_tpu.serving.ServingExecutor`'s
        ``runtime_provider`` hook (merged into every dispatch's keyword
        arguments outside the executor locks)."""
        with self._lock:
            view = IVFFlatIndex(
                centroids=self._cents_dev,
                data_sorted=self._hot_data,
                storage=ListStorage(
                    sorted_ids=self._hot_ids,
                    list_offsets=self._offs_dev,
                    list_index=self._dummy_index,
                    list_sizes=self._szs_dev,
                    n=self._n_view,
                    max_list=self._L,
                ),
                metric=self._metric,
            )
            return {"tier": TierRuntime(
                view=view, row_mask=self._maskv_dev,
                version=self._version, epoch=self._seen_epoch,
                dequant=self._dequant,
            )}

    def stats(self) -> TierStats:
        with self._lock:
            return TierStats(
                n_lists=self._n_lists, n_slots=self.n_slots,
                hot_lists=int((self._slot_of >= 0).sum()),
                probe_hits=self._hits, probe_misses=self._misses,
                fetches=self._fetches, demotions=self._demotions,
                invalidations=self._invalidations,
                fetch_ms_total=self._fetch_ms,
                overlapped_fetches=self._overlapped,
                hot_bytes=int(self._hot_data.size
                              * self._hot_data.dtype.itemsize),
                epoch=self._seen_epoch, last_recall=self.last_recall,
            )

    def hot_lists(self) -> np.ndarray:
        """List ids currently hot, ascending (a host copy)."""
        with self._lock:
            return np.nonzero(self._slot_of >= 0)[0].astype(np.int32)

    def measured_load(self) -> np.ndarray:
        """The decayed per-list touch signal the promotion policy ranks
        by — same units as :func:`...replica.measured_list_load` rows
        (a host copy)."""
        with self._lock:
            return self._touch.copy()

    # -- serving ---------------------------------------------------------
    def search(self, queries, k: int, *, n_probes: int = 8,
               qcap: typing.Union[int, str, None] = None,
               list_block: int = 32,
               stream_partials: Optional[bool] = None,
               runtime: Optional[TierRuntime] = None,
               account: bool = True,
               fill: bool = True) -> Tuple[jax.Array, jax.Array]:
        """Grouped search over the HOT tier — the unchanged
        :func:`_grouped_impl` body on the hot-slot view. Probes landing
        on cold lists contribute nothing (all-+inf — the graceful
        degraded answer); when ``account`` they are counted, fed into
        the per-list load signal, and (when ``fill`` and a fetcher is
        attached) queued for async promotion.

        ``runtime``: an explicit :class:`TierRuntime` snapshot (what an
        executor dispatch received); default takes a fresh one.
        ``qcap`` resolves SHAPE-ONLY via
        :func:`...ann.common.static_qcap` — never a host sync."""
        q = jnp.asarray(queries)
        errors.check_matrix(q, "queries")
        errors.expects(
            q.shape[1] == self._d,
            "TieredListStore.search: queries d=%d != index d=%d",
            q.shape[1], self._d,
        )
        errors.expects(
            k <= self._L and k <= n_probes * self._L,
            "TieredListStore.search: k=%d exceeds the candidate pool "
            "(max_list=%d, n_probes=%d)", k, self._L, n_probes,
        )
        nq = int(q.shape[0])
        qc = static_qcap(qcap, nq, n_probes, self._n_lists)
        if account:
            self._account(np.asarray(q, np.float32), n_probes, fill)
        snap = runtime if runtime is not None \
            else self.runtime()["tier"]
        list_block = max(1, min(list_block, self._n_lists))
        lockcheck.note_dispatch("TieredListStore.search")
        vals, ids = _grouped_impl(
            snap.view, q, k, n_probes, qc, list_block,
            stream_partials=stream_partials, row_mask=snap.row_mask,
            use_pallas=False, pallas_interpret=False,
            dequant=snap.dequant,
        )
        if self._metric == "l2":
            vals = jnp.sqrt(jnp.maximum(vals, 0.0))
        return vals, ids

    def _account(self, q_np: np.ndarray, n_probes: int,
                 fill: bool) -> None:
        """Host-side probe accounting: the coarse probe replayed in
        numpy (order-only — ties may break differently from the device
        probe, which only perturbs the LOAD signal, never an answer).
        Updates hit/miss counters, the decayed touch signal, the
        per-(shard, list) load feed, and queues cold probed lists for
        async fill.

        Exactly-zero rows are treated as executor micro-batch PADDING
        and not accounted (the staging path pads partial batches with
        zeros; counting them would pin the origin's nearest lists hot
        and inflate the hit rate at low load). Their ANSWER is
        unaffected — only the load signal skips them."""
        from raft_tpu.resilience.replica import record_list_load

        live = np.any(q_np != 0.0, axis=1)
        if not live.all():
            q_np = q_np[live]
            if q_np.shape[0] == 0:
                return
        p = min(n_probes, self._n_lists)
        # order-only distance: |c|^2 - 2 q.c (the |q|^2 term is a
        # per-row constant)
        d2 = self._cn2_np[None, :] - 2.0 * (q_np @ self._cents_np.T)
        if p < self._n_lists:
            probes = np.argpartition(d2, p - 1, axis=1)[:, :p]
        else:
            probes = np.broadcast_to(
                np.arange(self._n_lists), d2.shape).copy()
        counts = np.bincount(probes.ravel(),
                             minlength=self._n_lists).astype(np.float64)
        with self._lock:
            hot = self._slot_of[probes] >= 0
            hits = int(hot.sum())
            misses = int(hot.size - hits)
            self._hits += hits
            self._misses += misses
            self._touch *= self._touch_decay
            self._touch += counts
            miss_lists = (np.unique(probes[~hot])
                          if misses else np.empty(0, np.int64))
            sink = self._fill_sink
        self._c_hits.inc(hits)
        self._c_misses.inc(misses)
        record_list_load(counts, shard=self.shard)
        if fill and sink is not None and miss_lists.size:
            sink([int(x) for x in miss_lists])

    # -- membership ------------------------------------------------------
    def promote(self, list_ids: Sequence[int], *,
                busy=False) -> int:
        """Synchronously fetch + install the given lists into free hot
        slots (already-hot ids are no-ops). Returns the number
        installed; stops early when the hot set is full — pair with
        :meth:`demote` or let :meth:`rebalance` plan swaps. ``busy``
        (bool or callable) stamps the fetch spans compute-overlapped
        (the async fetcher passes its executor-busy probe)."""
        done = 0
        with self._install:
            for lid in list_ids:
                self._check_list(lid)
                if self._slot_of[lid] >= 0:
                    continue
                free = np.nonzero(self._list_at < 0)[0]
                if free.size == 0:
                    break
                self._install_list(int(lid), int(free[0]), busy=busy)
                done += 1
            if done:
                self._publish()
        return done

    def demote(self, list_ids: Sequence[int]) -> int:
        """Flip the given hot lists cold — membership only, nothing is
        copied back (the host slab is the authority; a hot slab is
        never dirtied). Returns the number demoted."""
        done = 0
        with self._install:
            for lid in list_ids:
                self._check_list(lid)
                slot = int(self._slot_of[lid])
                if slot < 0:
                    continue
                self._evict_slot(slot)
                done += 1
                if self.flight is not None:
                    self.flight.record("tier_demote", list=int(lid),
                                       slot=slot)
            if done:
                self._publish()
        with self._lock:
            self._demotions += done
        self._c_demotions.inc(done)
        return done

    def apply_moves(self, moves: Sequence[Tuple[int, Optional[int]]],
                    *, busy=False) -> int:
        """Apply a promotion plan — ``(promote_list, victim_list|None)``
        pairs from :class:`~raft_tpu.tier.policy.PromotionPolicy` — as
        one membership transaction (one publish, one version bump).
        Returns the number of lists promoted."""
        done = 0
        with self._install:
            for lid, victim in moves:
                self._check_list(lid)
                if self._slot_of[lid] >= 0:
                    continue
                if victim is not None and self._slot_of[victim] >= 0:
                    slot = int(self._slot_of[victim])
                    self._evict_slot(slot)
                    with self._lock:
                        self._demotions += 1
                    self._c_demotions.inc()
                else:
                    free = np.nonzero(self._list_at < 0)[0]
                    if free.size == 0:
                        continue
                    slot = int(free[0])
                self._install_list(int(lid), slot, busy=busy)
                done += 1
            if done:
                self._publish()
        return done

    def rebalance(self, policy, *, busy=False) -> int:
        """Plan against the current measured load and apply — the
        periodic promotion/demotion cycle (the fetcher and the bench
        both drive this)."""
        with self._lock:
            slot_of = self._slot_of.copy()
        moves = policy.plan(self.measured_load(), slot_of, self.n_slots)
        return self.apply_moves(moves, busy=busy) if moves else 0

    def attach_fill_sink(
            self, sink: Optional[Callable[[Sequence[int]], None]],
    ) -> None:
        """Register the async-fill callback (the
        :class:`~raft_tpu.tier.fetch.SlabFetcher` attaches itself);
        ``None`` detaches."""
        with self._lock:
            self._fill_sink = sink

    # -- mutation-epoch invalidation --------------------------------------
    def sync_mutations(self, mindex) -> Optional[set]:
        """Pull a :class:`MutableIndex`'s epoch journal forward (the
        result-cache invalidation discipline, docs/tiering.md).
        Upsert/delete change only tombstones — the view mask is
        re-gathered from the fresh ``row_mask`` and re-published.
        Compaction (journal answer ``None``) rewrites the slab: the
        host authority is re-snapshotted and EVERY hot slot is
        invalidated. Returns the changed-list set (``None`` = all,
        empty = no-op)."""
        from raft_tpu.spatial.ann.mutation import lists_changed_since

        with self._install:
            epoch = int(mindex.epoch)
            if epoch == self._seen_epoch:
                return set()
            changed = lists_changed_since(mindex, self._seen_epoch)
            if changed is None:
                # full invalidation — but the CURRENT tombstones must
                # ride along (a journal-overflow None without a
                # compaction still has live deletes in row_mask)
                self._refresh_host_locked(
                    mindex.index, row_mask=np.asarray(mindex.row_mask),
                )
            else:
                with self._lock:
                    self._mask_np = np.asarray(mindex.row_mask)
                self._publish()
            with self._lock:
                self._seen_epoch = epoch
            return changed

    def refresh_host(self, index) -> None:
        """Re-snapshot the host (cold-tier) authority from ``index``
        and invalidate every hot slot — the compaction path. The index
        must keep the tier's static geometry (n, max_list, n_lists,
        dtype); a compaction that changes it needs a NEW store (the
        same statics-change rule as any serving program swap)."""
        with self._install:
            self._refresh_host_locked(index)

    def _refresh_host_locked(self, index, row_mask=None) -> None:
        base, dequant, _ = _resolve_base(index)
        storage = base.storage
        errors.expects(
            int(storage.n) == self._n
            and int(storage.max_list) == self._L
            and int(storage.list_index.shape[0]) == self._n_lists
            and np.asarray(base.data_sorted).dtype == self._data_np.dtype,
            "refresh_host: index geometry changed "
            "(n=%d max_list=%d n_lists=%d vs store n=%d max_list=%d "
            "n_lists=%d) — build a new TieredListStore",
            int(storage.n), int(storage.max_list),
            int(storage.list_index.shape[0]),
            self._n, self._L, self._n_lists,
        )
        with self._lock:
            # swap every host-authority ref in ONE critical section so
            # a concurrent fetch_slab snapshot never mixes old offsets
            # with a new slab
            self._data_np = np.asarray(base.data_sorted)
            self._sids_np = np.asarray(storage.sorted_ids)
            self._offs_np = np.asarray(storage.list_offsets)
            self._szs_np = np.asarray(storage.list_sizes)
            self._mask_np = (np.ones(self._n + 1, np.int8)
                             if row_mask is None
                             else np.asarray(row_mask, np.int8))
            self._dequant = dequant
        n_inval = int((self._list_at >= 0).sum())
        for slot in range(self.n_slots):
            if self._list_at[slot] >= 0:
                self._evict_slot(slot)
        self._publish()
        with self._lock:
            self._invalidations += n_inval
        self._c_invalid.inc(n_inval)
        if self.flight is not None and n_inval:
            self.flight.record("tier_invalidate", reason="refresh_host",
                               n_slots=n_inval)

    # -- guardrail ---------------------------------------------------------
    def measure_recall(self, queries, k: int, *, n_probes: int = 8,
                       qcap: typing.Union[int, str, None] = None,
                       list_block: int = 32) -> float:
        """Measured id-overlap recall of the TIERED answer against the
        full (all-lists-resident) grouped search at the same probes and
        tombstones — the degraded-probe guardrail. Records the
        ``tier_recall`` gauge; a measurement below ``min_recall``
        counts a breach (and a flight event). The full-path reference
        dispatches the ORIGINAL index — run this on a sampled cadence,
        not on the serving hot path."""
        q = jnp.asarray(queries)
        nq = int(q.shape[0])
        qc = static_qcap(qcap, nq, n_probes, self._n_lists)
        _, tiered_ids = self.search(
            q, k, n_probes=n_probes, qcap=qc, list_block=list_block,
            account=False, fill=False,
        )
        base, dequant, _ = _resolve_base(self._origin)
        lb = max(1, min(list_block, self._n_lists))
        with self._lock:
            full_mask = jnp.asarray(self._mask_np)
        _, full_ids = _grouped_impl(
            base, q, k, n_probes, qc, lb, row_mask=full_mask,
            use_pallas=False, pallas_interpret=False, dequant=dequant,
        )
        r = _id_recall(np.asarray(tiered_ids), np.asarray(full_ids))
        with self._lock:
            self.last_recall = r
        self._g_recall.set(r)
        if self.min_recall is not None and r < self.min_recall:
            self._c_breach.inc()
            if self.flight is not None:
                self.flight.record(
                    "tier_recall_breach", recall=round(r, 4),
                    min_recall=self.min_recall,
                )
        return r

    @property
    def degraded(self) -> bool:
        """True when the LAST measured recall sits below the guardrail
        (never measured = not degraded — measure before trusting)."""
        with self._lock:
            lr = self.last_recall
        return (self.min_recall is not None and lr is not None
                and lr < self.min_recall)

    # -- internals (under _install) ----------------------------------------
    def _check_list(self, lid: int) -> None:
        errors.expects(
            0 <= int(lid) < self._n_lists,
            "tier: list id %d out of range [0, %d)", int(lid),
            self._n_lists,
        )

    def fetch_slab(self, lid: int) -> Tuple[np.ndarray, np.ndarray,
                                            np.ndarray]:
        """Read one list's slab from the host (cold) tier: the
        ``(max_list, d)`` zero-padded rows, the ``(max_list,)`` id map
        (-1 pad), and the ``(max_list,)`` original sorted positions
        (``n`` pad — the sentinel mask row). This is THE host read the
        ``host-fetch-in-traced-body`` lint keeps out of compiled
        programs."""
        with self._lock:
            # one consistent host-authority snapshot (refresh_host
            # swaps all four refs under this lock; arrays themselves
            # are replaced, never mutated in place)
            data, sids, offs, szs = (self._data_np, self._sids_np,
                                     self._offs_np, self._szs_np)
        off = int(offs[lid])
        sz = int(szs[lid])
        slab = np.zeros((self._L, self._d), data.dtype)
        slab[:sz] = data[off:off + sz]
        ids = np.full(self._L, -1, np.int32)
        ids[:sz] = sids[off:off + sz]
        pos = np.full(self._L, self._n, np.int64)
        pos[:sz] = np.arange(off, off + sz)
        return slab, ids, pos

    def _install_list(self, lid: int, slot: int, *,
                      busy=False) -> None:
        """Fetch ``lid``'s slab and install it into ``slot`` (caller
        holds ``_install``): host read → async device_put → ONE jitted
        dynamic_update_slice per buffer → host-mirror update. The
        device arrays are PUBLISHED by the caller's :meth:`_publish`
        (one consistent snapshot per transaction). ``busy`` — bool or
        zero-arg callable sampled around the span — stamps the fetch
        compute-overlapped (the ``fetch_overlap_pct`` numerator)."""
        t0 = self._clock()
        was_busy = bool(busy() if callable(busy) else busy)
        slab, ids, pos = self.fetch_slab(lid)
        dev_slab = jax.device_put(slab)      # async H2D — the overlap
        dev_ids = jax.device_put(ids)
        row0 = jnp.int32(slot * self._L)
        with self._lock:
            cur_data, cur_ids = self._hot_data, self._hot_ids
        # the install program's output takes the device's default layout
        new_data = place_row_store(_install_rows(cur_data, dev_slab, row0))
        new_ids = _install_ids(cur_ids, dev_ids, row0)
        ms = (self._clock() - t0) * 1e3
        if callable(busy):
            was_busy = was_busy or bool(busy())
        with self._lock:
            self._hot_data = new_data
            self._hot_ids = new_ids
            self._fetches += 1
            self._fetch_ms += ms
            if was_busy:
                self._overlapped += 1
        self._slot_of[lid] = slot
        self._list_at[slot] = lid
        self._hot_pos[slot * self._L:(slot + 1) * self._L] = pos
        self._offs_host[lid] = slot * self._L
        self._szs_host[lid] = self._szs_np[lid]
        self._c_fetches.inc()
        self._h_fetch.observe(ms)
        if self.flight is not None:
            self.flight.record(
                "tier_fetch", list=int(lid), slot=int(slot),
                ms=round(ms, 3), rows=int(self._szs_np[lid]),
                overlapped=was_busy,
            )

    def _evict_slot(self, slot: int) -> None:
        """Membership-only eviction (caller holds ``_install``): the
        slot's rows stay in the buffer but no offset points at them —
        the next snapshot can never scan them."""
        lid = int(self._list_at[slot])
        if lid >= 0:
            self._slot_of[lid] = -1
            self._offs_host[lid] = self._n_view
            self._szs_host[lid] = 0
        self._list_at[slot] = -1
        self._hot_pos[slot * self._L:(slot + 1) * self._L] = self._n

    def _publish(self) -> None:
        """Push the host mirrors to fresh device arrays and swap them
        into the published snapshot atomically (caller holds
        ``_install``; readers hold ``_lock`` only)."""
        offs = jnp.asarray(self._offs_host)
        szs = jnp.asarray(self._szs_host)
        maskv = np.ones(self._n_view + 1, np.int8)
        maskv[:-1] = self._mask_np[np.minimum(self._hot_pos, self._n)]
        maskv_dev = jnp.asarray(maskv)
        with self._lock:
            self._offs_dev = offs
            self._szs_dev = szs
            self._maskv_dev = maskv_dev
            self._version += 1
            hot = int((self._slot_of >= 0).sum())
        self._g_hot.set(float(hot))

    def __repr__(self) -> str:
        st = self.stats()
        return (f"TieredListStore(name={self.name!r}, "
                f"hot={st.hot_lists}/{st.n_lists} lists in "
                f"{st.n_slots} slots, hit_rate={st.hit_rate:.3f}, "
                f"fetches={st.fetches}, epoch={st.epoch})")


# -- helpers -----------------------------------------------------------------
def _resolve_base(index):
    """``(flat_view, dequant, origin)`` for an IVFFlatIndex or an
    IVFSQIndex (tiered through its int8 code view — bytes halve in both
    tiers and on the bus)."""
    from raft_tpu.spatial.ann.ivf_sq import IVFSQIndex, _flat_view

    if isinstance(index, IVFSQIndex):
        return _flat_view(index), (
            jnp.asarray(index.vmin, jnp.float32),
            jnp.asarray(index.vscale, jnp.float32),
        ), index
    errors.expects(
        isinstance(index, IVFFlatIndex),
        "TieredListStore: expected an IVFFlatIndex or IVFSQIndex, "
        "got %s", type(index).__name__,
    )
    return index, None, index


def _resolve_slots(n_slots, budget, L, d, itemsize, n_lists) -> int:
    errors.expects(
        (n_slots is None) != (budget is None),
        "TieredListStore: pass exactly one of n_slots / "
        "hbm_budget_bytes",
    )
    if n_slots is None:
        slab = L * d * itemsize
        n_slots = max(1, int(budget) // slab)
    errors.expects(int(n_slots) >= 1,
                   "TieredListStore: n_slots=%d < 1", int(n_slots))
    return min(int(n_slots), n_lists)


def _id_recall(got: np.ndarray, ref: np.ndarray) -> float:
    """Mean per-query id overlap |got ∩ ref| / |ref| (invalid -1 rows
    excluded from the reference — a reference that itself found fewer
    than k rows never penalizes the tier)."""
    n = got.shape[0]
    tot, denom = 0.0, 0
    for i in range(n):
        r = set(int(x) for x in ref[i] if int(x) >= 0)
        if not r:
            continue
        g = set(int(x) for x in got[i] if int(x) >= 0)
        tot += len(g & r) / len(r)
        denom += 1
    return tot / denom if denom else 1.0
