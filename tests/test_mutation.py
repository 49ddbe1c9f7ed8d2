"""Mutation-tier tests (ISSUE 7): upsert / delete / streaming ingest
with background compaction, single-chip and sharded.

Contracts under test (docs/mutation.md):

* an ACKNOWLEDGED upsert is visible to the very next search; a delete
  masks the row everywhere (main slab, delta, every replica copy);
* upsert into a non-full delta segment, tombstone flips, and
  health/failover flips all run with ZERO retraces of the compiled
  programs (cache-size audits, Pallas ADC engine engaged on the PQ
  path under interpret);
* compaction folds deltas+tombstones back into main slabs with results
  preserved, warm-started centroid refresh bounded by the
  probe-overlap drift guardrail, and recall stays bounded across
  ingest+refresh cycles;
* checkpoint v4: full round-trip, the lowest-version writer rule, a
  FUTURE version rejected with a CorruptIndexError naming it, and
  dirty-list delta checkpoints that survive duplication and fail
  loudly on partial writes (faults.inject_partial_write);
* chaos: a mid-ingest rank failure + recover_rank/resync_rank cycle
  loses no acknowledged write.
"""

import dataclasses
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from raft_tpu import errors
from raft_tpu.spatial.ann import (
    BackgroundCompactor,
    CompactionPolicy,
    IVFFlatParams,
    IVFPQParams,
    apply_delta_checkpoint,
    compact,
    compaction_stats,
    delete,
    ivf_flat_build,
    ivf_pq_build,
    load_index,
    mutable_search,
    mutable_warmup,
    probe_overlap,
    save_delta_checkpoint,
    save_index,
    upsert,
    wrap_mutable,
)
from raft_tpu.spatial.ann import mutation as mut_mod
from raft_tpu.testing import faults
from tests.oracles import np_knn_ids

K = 5
D = 16


@pytest.fixture(scope="module")
def dataset():
    rng = np.random.default_rng(11)
    x = rng.standard_normal((1200, D)).astype(np.float32)
    q = x[::113][:8] + 0.05 * rng.standard_normal((8, D)).astype(
        np.float32
    )
    return x, q


@pytest.fixture(scope="module")
def flat_index(dataset):
    x, _ = dataset
    return ivf_flat_build(
        x, IVFFlatParams(n_lists=12, kmeans_n_iters=4,
                         kmeans_init="random", seed=3),
        metric="sqeuclidean",
    )


@pytest.fixture(scope="module")
def pq_index(dataset):
    x, _ = dataset
    return ivf_pq_build(x, IVFPQParams(
        n_lists=12, pq_dim=4, kmeans_n_iters=4, kmeans_init="random",
        seed=3,
    ))


def _search_ids(mw, q, **kw):
    kw.setdefault("n_probes", 6)
    kw.setdefault("qcap", q.shape[0])
    return np.asarray(mutable_search(mw, q, K, **kw)[1])


# ------------------------------------------------------- single-chip core
class TestUpsertDelete:
    def test_upsert_acked_then_visible_top1(self, flat_index, dataset):
        _, q = dataset
        mw = wrap_mutable(flat_index, delta_cap=8)
        new_ids = np.arange(7000, 7000 + q.shape[0]).astype(np.int32)
        mw2, acc = upsert(mw, q, new_ids)
        assert acc.all()
        ids = _search_ids(mw2, q)
        assert (ids[:, 0] == new_ids).all()
        # the original state is untouched (functional updates)
        assert not np.isin(_search_ids(mw, q), new_ids).any()

    def test_reupsert_supersedes_old_copy(self, flat_index, dataset):
        x, q = dataset
        mw = wrap_mutable(flat_index, delta_cap=8)
        # move an EXISTING main-slab row onto the first query
        victim = int(_search_ids(mw, q)[1, 0])
        mw2, acc = upsert(mw, q[:1], np.array([victim], np.int32))
        assert acc.all()
        ids = _search_ids(mw2, q)
        assert ids[0, 0] == victim
        # and re-upsert the DELTA copy again: still exactly one live copy
        mw3, _ = upsert(mw2, q[:1] + 0.001, np.array([victim], np.int32))
        ids3 = _search_ids(mw3, q)
        assert (ids3[0] == victim).sum() == 1
        live = (np.asarray(mw3.delta.live) > 0) & (
            np.asarray(mw3.delta.ids) == victim
        )
        assert live.sum() == 1

    def test_delete_masks_main_and_delta(self, flat_index, dataset):
        _, q = dataset
        mw = wrap_mutable(flat_index, delta_cap=8)
        new_ids = np.arange(7100, 7104).astype(np.int32)
        mw2, _ = upsert(mw, q[:4], new_ids)
        main_victims = _search_ids(mw2, q)[:, 1][:3].astype(np.int32)
        both = np.concatenate([new_ids, main_victims])
        mw3, found = delete(mw2, both)
        assert found.all()
        ids = _search_ids(mw3, q)
        assert not np.isin(ids, both).any()
        # deleting again: nothing live to find
        _, found2 = delete(mw3, both)
        assert not found2.any()

    def test_capacity_rejection_is_explicit(self, flat_index):
        mw = wrap_mutable(flat_index, delta_cap=2)
        # identical vectors land in one list: only cap=2 fit
        v = np.tile(np.asarray(flat_index.centroids)[0], (5, 1))
        mw2, acc = upsert(mw, v, np.arange(8000, 8005).astype(np.int32))
        assert acc.sum() == 2
        assert int(np.asarray(mw2.delta.counts).max()) == 2
        # rejected rows are NOT in the delta
        assert not np.isin(
            np.asarray(mw2.delta.ids), np.arange(8002, 8005)
        ).any()

    def test_rejected_upsert_is_a_strict_noop(self, flat_index,
                                              dataset):
        """Review regression: a capacity-rejected upsert must NOT
        tombstone the id's previous copy — False means "compact, then
        retry", and the old version keeps serving (main slab AND delta
        copies)."""
        _, q = dataset
        mw = wrap_mutable(flat_index, delta_cap=1)
        c0 = np.asarray(flat_index.centroids)[0:1]
        # fill list 0's one-slot segment
        mw, acc = upsert(mw, c0, np.array([8100], np.int32))
        assert acc.all()
        before = _search_ids(mw, q)
        # a MAIN-slab id re-upserted into the full list: rejected, and
        # its previous main copy keeps serving
        victim = int(before[0, 0])
        mw2, acc2 = upsert(mw, c0, np.array([victim], np.int32))
        assert not acc2.any()
        assert np.array_equal(_search_ids(mw2, q), before)
        # a DELTA id re-upserted into the full list: rejected, and the
        # previous delta copy stays live
        mw3, acc3 = upsert(mw2, c0 + 1e-4, np.array([8100], np.int32))
        assert not acc3.any()
        live = (np.asarray(mw3.delta.live) > 0) & (
            np.asarray(mw3.delta.ids) == 8100
        )
        assert live.sum() == 1

    def test_superseded_delta_copy_dirties_its_list(self, flat_index,
                                                    dataset, tmp_path):
        """Review regression: re-upserting an id whose delta copy lives
        in ANOTHER list must dirty that list too — otherwise replaying
        incremental checkpoints resurrects the stale copy."""
        _, q = dataset
        cents = np.asarray(flat_index.centroids)
        base = wrap_mutable(flat_index, delta_cap=4)
        mw, acc = upsert(base, cents[0:1], np.array([8200], np.int32))
        assert acc.all()
        p1 = tmp_path / "d1.npz"
        save_delta_checkpoint(mw, p1)
        # move the id to a different list
        mw, acc = upsert(mw, cents[5:6], np.array([8200], np.int32))
        assert acc.all()
        assert len(mw.dirty_lists) >= 2      # new list AND the old one
        p2 = tmp_path / "d2.npz"
        save_delta_checkpoint(mw, p2)
        fresh = wrap_mutable(flat_index, delta_cap=4)
        r = apply_delta_checkpoint(
            apply_delta_checkpoint(fresh, p1), p2
        )
        live = (np.asarray(r.delta.live) > 0) & (
            np.asarray(r.delta.ids) == 8200
        )
        assert live.sum() == 1               # exactly ONE live copy

    def test_sparse_id_space_rejected_loudly(self, flat_index):
        """The id→pos map is dense over [0, max_id]: wildly sparse ids
        must fail with a clear contract error, not a silent multi-GB
        allocation."""
        import dataclasses as dc

        huge = dc.replace(
            flat_index,
            storage=dc.replace(
                flat_index.storage,
                sorted_ids=jnp.asarray(
                    np.asarray(flat_index.storage.sorted_ids)
                    + (1 << 30)
                ),
            ),
        )
        with pytest.raises(ValueError, match="dense"):
            wrap_mutable(huge, delta_cap=4)

    def test_pq_engine_with_pallas_kernel_interpret(self, pq_index,
                                                    dataset):
        """The kernel-path tombstone contract: with the Pallas ADC
        engine engaged (interpret mode on CPU), upserts surface and
        deleted rows never do — the row mask is applied at the exact
        refine tail."""
        _, q = dataset
        mw = wrap_mutable(pq_index, delta_cap=8)
        kw = dict(n_probes=6, qcap=q.shape[0], refine_ratio=2.0,
                  use_pallas=True)
        new_ids = np.arange(7200, 7200 + q.shape[0]).astype(np.int32)
        mw2, acc = upsert(mw, q, new_ids)
        assert acc.all()
        ids = _search_ids(mw2, q, **kw)
        assert (ids[:, 0] == new_ids).all()
        victims = _search_ids(mw2, q, **kw)[:, 1][:4].astype(np.int32)
        mw3, found = delete(mw2, victims)
        assert found.all()
        ids3 = _search_ids(mw3, q, **kw)
        assert not np.isin(ids3, victims).any()

    def test_zero_retrace_upsert_tombstone_search(self, flat_index,
                                                  dataset):
        """THE zero-retrace acceptance: upsert into a non-full segment,
        tombstone flips, and repeated serving all reuse ONE compiled
        program per op (cache-size audit on the three jitted impls)."""
        _, q = dataset
        mw = wrap_mutable(flat_index, delta_cap=8)
        kw = dict(n_probes=6, qcap=q.shape[0])
        mutable_search(mw, q, K, **kw)
        s0 = mut_mod._mut_search_impl._cache_size()
        u0 = d0 = None
        for i in range(3):
            mw, acc = upsert(
                mw, q + 0.01 * i,
                np.arange(9000 + 10 * i, 9000 + 10 * i + q.shape[0],
                          dtype=np.int32),
            )
            assert acc.all()
            if u0 is None:
                u0 = mut_mod._upsert_impl._cache_size()
            mw, _ = delete(mw, np.array([9000 + 10 * i], np.int32))
            if d0 is None:
                d0 = mut_mod._delete_impl._cache_size()
            mutable_search(mw, q, K, **kw)
        assert mut_mod._mut_search_impl._cache_size() == s0, \
            "mutations must not retrace the serving program"
        assert mut_mod._upsert_impl._cache_size() == u0
        assert mut_mod._delete_impl._cache_size() == d0

    def test_warmup_consumes_nothing(self, flat_index):
        mw = wrap_mutable(flat_index, delta_cap=4)
        qc = mutable_warmup(mw, 4, k=K, n_probes=6, ingest_batch=8)
        assert isinstance(qc, int)
        assert int(np.asarray(mw.delta.counts).sum()) == 0
        assert int(np.asarray(mw.row_mask).min()) == 1


# --------------------------------------------------------- compaction
class TestCompaction:
    def test_compact_preserves_results(self, flat_index, dataset):
        x, q = dataset
        mw = wrap_mutable(flat_index, delta_cap=8)
        new_ids = np.arange(7300, 7306).astype(np.int32)
        mw, _ = upsert(mw, q[:6] * 1.01, new_ids)
        victims = _search_ids(mw, q)[:, 2][:4].astype(np.int32)
        mw, _ = delete(mw, victims)
        # every list probed: the delta scan is exhaustive, so a partial
        # probe would lose any delta row that compaction files into a
        # list the query does not probe (IVF recall, not compaction)
        nl = flat_index.centroids.shape[0]
        before = _search_ids(mw, q, n_probes=nl)
        mw2, stats = compact(mw)
        assert stats["survivors"] == 1200 + 6 - 4
        after = _search_ids(mw2, q, n_probes=nl)
        assert np.array_equal(before, after)
        # delta drained, mask all-live
        assert int(np.asarray(mw2.delta.counts).sum()) == 0
        assert compaction_stats(mw2)["tombstone_frac"] == 0.0

    def test_compact_statics_stable_across_cycles(self, flat_index,
                                                  dataset):
        _, q = dataset
        mw = wrap_mutable(flat_index, delta_cap=8)
        mw1, s1 = compact(mw)
        mw1, _ = upsert(mw1, q[:2], np.array([7400, 7401], np.int32))
        mw2, s2 = compact(mw1)
        # bucketed statics: a 2-row delta must not shift the program keys
        assert s1["max_list"] == s2["max_list"]
        assert s1["n_slab"] == s2["n_slab"]

    def test_refresh_drift_guardrail(self, flat_index, dataset):
        _, q = dataset
        mw = wrap_mutable(flat_index, delta_cap=8)
        # warm-started refresh on unchanged data: tiny drift, passes
        mw2, stats = compact(mw, refresh_centroids=True,
                             drift_queries=q, min_probe_overlap=0.5,
                             n_probes=6)
        assert stats["refreshed"] and stats["probe_overlap"] >= 0.5
        # an impossible bound trips the guardrail loudly
        with pytest.raises(ValueError, match="drift"):
            compact(mw, refresh_centroids=True, drift_queries=q,
                    min_probe_overlap=1.01, n_probes=6)

    def test_recall_bounded_across_ingest_refresh_cycles(self):
        """The drift-guardrail acceptance: recall vs a fresh exact
        oracle stays within bound across ingest + centroid-refresh
        cycles (clustered data, the regime IVF exists for)."""
        from raft_tpu.random import make_blobs
        from raft_tpu.random.rng import RngState

        x, _ = make_blobs(3000, D, n_clusters=24, cluster_std=0.6,
                          state=RngState(5))
        x = np.asarray(x, np.float32)
        idx = ivf_flat_build(
            x[:2400], IVFFlatParams(n_lists=16, kmeans_n_iters=5,
                                    kmeans_init="random", seed=1),
            metric="sqeuclidean",
        )
        mw = wrap_mutable(idx, delta_cap=64)
        rng = np.random.default_rng(2)
        q = x[rng.integers(0, 2400, 16)] + 0.05 * rng.standard_normal(
            (16, D)
        ).astype(np.float32)
        live = {i: x[i] for i in range(2400)}
        nxt = 2400
        for cycle in range(3):
            batch = np.arange(nxt, nxt + 200)
            mw, acc = upsert(mw, x[nxt:nxt + 200], batch.astype(np.int32))
            for i in batch[acc]:
                live[int(i)] = x[int(i)]
            nxt += 200
            dead = rng.choice(sorted(live), size=50, replace=False)
            mw, _ = delete(mw, dead.astype(np.int32))
            for i in dead:
                live.pop(int(i), None)
            mw, stats = compact(
                mw, refresh_centroids=True, drift_queries=q,
                min_probe_overlap=0.3, n_probes=8,
            )
            ids_live = np.array(sorted(live), np.int64)
            xs = np.stack([live[int(i)] for i in ids_live])
            true = ids_live[np_knn_ids(xs, q, K)]
            got = _search_ids(mw, q, n_probes=8)
            rec = np.mean([
                len(set(g.tolist()) & set(t.tolist())) / K
                for g, t in zip(got, true)
            ])
            assert rec >= 0.85, (cycle, rec)

    def test_background_compactor_lifecycle(self, flat_index, dataset):
        _, q = dataset
        mw = wrap_mutable(flat_index, delta_cap=4)
        bc = BackgroundCompactor(CompactionPolicy(max_fill_frac=0.25,
                                                  refresh_every=0))
        assert not bc.maybe_submit(mw)       # empty: nothing to do
        v = np.tile(np.asarray(flat_index.centroids)[0], (3, 1))
        mw, acc = upsert(mw, v, np.arange(7500, 7503).astype(np.int32))
        assert acc.all()
        assert bc.maybe_submit(mw)
        assert not bc.submit(mw)             # one in flight at a time
        bc.join(30.0)
        out = bc.poll()
        assert out is not None
        mw2, stats = out
        assert stats["survivors"] == 1200 + 3
        assert bc.poll() is None
        assert np.isin(
            np.asarray(mw2.index.storage.sorted_ids),
            np.arange(7500, 7503),
        ).sum() == 3

    def test_probe_overlap_bounds(self, flat_index, dataset):
        _, q = dataset
        c = np.asarray(flat_index.centroids)
        assert probe_overlap(c, c, q, 6) == 1.0
        rng = np.random.default_rng(0)
        # unrelated centroids: overlap collapses toward the random
        # expectation (n_probes / n_lists = 2/12)
        assert probe_overlap(
            c, rng.standard_normal(c.shape).astype(np.float32) * 10, q, 2
        ) < 0.75


# ------------------------------------------------- checkpointing (v4)
class TestCheckpointV4:
    def test_full_v4_roundtrip(self, flat_index, dataset, tmp_path):
        _, q = dataset
        mw = wrap_mutable(flat_index, delta_cap=8)
        mw, _ = upsert(mw, q[:4], np.arange(7600, 7604).astype(np.int32))
        mw, _ = delete(mw, _search_ids(mw, q)[:, 1][:2].astype(np.int32))
        p = tmp_path / "mut.npz"
        save_index(mw, p)
        hdr = json.loads(bytes(np.load(p)["__header__"]).decode())
        assert hdr["version"] == 4 and hdr["type"] == "mutable_ivf"
        back = load_index(p)
        assert np.array_equal(_search_ids(back, q), _search_ids(mw, q))

    def test_frozen_payload_keeps_lowest_version(self, flat_index,
                                                 tmp_path):
        p = tmp_path / "flat.npz"
        save_index(flat_index, p)
        hdr = json.loads(bytes(np.load(p)["__header__"]).decode())
        assert hdr["version"] == 2     # no coarse, no mutation payload

    def test_future_version_rejected_naming_it(self, flat_index,
                                               tmp_path):
        """ISSUE 7 satellite: a v3-era reader meeting a future-format
        header must raise a structured CorruptIndexError NAMING the
        version — never fall through to missing-key defaults."""
        p = tmp_path / "f.npz"
        save_index(flat_index, p)
        with np.load(p) as npz:
            hdr = json.loads(bytes(npz["__header__"]).decode())
            arrays = {k: npz[k] for k in npz.files if k != "__header__"}
        hdr["version"] = 9
        with open(p, "wb") as f:
            np.savez(f, __header__=np.frombuffer(
                json.dumps(hdr).encode(), dtype=np.uint8
            ), **arrays)
        with pytest.raises(errors.CorruptIndexError, match="9"):
            load_index(p)

    def test_delta_checkpoint_dirty_lists_and_idempotence(
        self, flat_index, dataset, tmp_path
    ):
        _, q = dataset
        base = wrap_mutable(flat_index, delta_cap=8)
        mw, _ = upsert(base, q[:4], np.arange(7700, 7704).astype(np.int32))
        dirty = set(mw.dirty_lists)
        assert dirty          # something got dirty
        p = tmp_path / "delta.npz"
        written = save_delta_checkpoint(mw, p)
        assert set(written) == dirty and not mw.dirty_lists
        fresh = wrap_mutable(flat_index, delta_cap=8)
        r1 = apply_delta_checkpoint(fresh, p)
        assert np.array_equal(_search_ids(r1, q), _search_ids(mw, q))
        # a duplicated flush re-applies to the same state
        r2 = apply_delta_checkpoint(r1, p)
        assert np.array_equal(_search_ids(r2, q), _search_ids(mw, q))

    @pytest.mark.parametrize("mode", ["truncate", "duplicate"])
    def test_partial_write_detected(self, flat_index, dataset, tmp_path,
                                    mode):
        """ISSUE 7 satellite: a torn or duplicated delta-segment flush
        must fail loudly at apply time (CorruptIndexError), never
        half-apply."""
        _, q = dataset
        mw = wrap_mutable(flat_index, delta_cap=8)
        mw, _ = upsert(mw, q, np.arange(7800, 7808).astype(np.int32))
        p = tmp_path / "delta.npz"
        save_delta_checkpoint(mw, p)
        damaged = faults.inject_partial_write(str(p), mode=mode,
                                              boundary=2)
        assert damaged
        fresh = wrap_mutable(flat_index, delta_cap=8)
        with pytest.raises(errors.CorruptIndexError):
            apply_delta_checkpoint(fresh, p)

    def test_geometry_mismatch_rejected(self, flat_index, dataset,
                                        tmp_path):
        _, q = dataset
        mw = wrap_mutable(flat_index, delta_cap=8)
        mw, _ = upsert(mw, q[:2], np.array([7900, 7901], np.int32))
        p = tmp_path / "delta.npz"
        save_delta_checkpoint(mw, p)
        other = wrap_mutable(flat_index, delta_cap=4)   # different cap
        with pytest.raises(errors.CorruptIndexError, match="geometry"):
            apply_delta_checkpoint(other, p)


# ------------------------------------------------------- sharded (MNMG)
from raft_tpu.comms import (  # noqa: E402 — mesh-dependent imports
    build_comms,
    mnmg_delete,
    mnmg_ivf_flat_build,
    mnmg_ivf_flat_search,
    mnmg_ivf_pq_build,
    mnmg_mutable_search,
    mnmg_upsert,
    place_index,
    recover_rank,
    resync_rank,
    wrap_mnmg_mutable,
)
from raft_tpu.resilience import FailoverPlan, ReplicaPlacement  # noqa: E402
from raft_tpu.resilience.health import ShardHealth  # noqa: E402


@pytest.fixture(scope="module")
def comms8():
    return build_comms(jax.devices()[:8])


@pytest.fixture(scope="module")
def sharded_flat_r2(comms8, dataset):
    x, _ = dataset
    idx = mnmg_ivf_flat_build(
        comms8, x, IVFFlatParams(n_lists=16, kmeans_n_iters=3,
                                 kmeans_init="random", seed=2),
        metric="sqeuclidean",
    )
    return place_index(comms8, idx, replication=2)


class TestMnmgMutation:
    def test_empty_state_parity_and_upsert_visible(self, comms8,
                                                   sharded_flat_r2,
                                                   dataset):
        _, q = dataset
        idx = sharded_flat_r2
        mw = wrap_mnmg_mutable(comms8, idx, delta_cap=8)
        kw = dict(n_probes=8, qcap=q.shape[0])
        v0, i0 = mnmg_mutable_search(comms8, mw, q, K, **kw)
        vp, ip = mnmg_ivf_flat_search(comms8, idx, q, K, **kw)
        assert np.array_equal(np.asarray(i0), np.asarray(ip))
        new_ids = np.arange(8800, 8800 + q.shape[0]).astype(np.int32)
        mw2, acc = mnmg_upsert(comms8, mw, q, new_ids)
        assert acc.all()
        _, i1 = mnmg_mutable_search(comms8, mw2, q, K, **kw)
        assert (np.asarray(i1)[:, 0] == new_ids).all()
        # the pre-upsert state is untouched (functional)
        _, i0b = mnmg_mutable_search(comms8, mw, q, K, **kw)
        assert np.array_equal(np.asarray(i0b), np.asarray(i0))

    def test_tombstone_vs_replica_bit_identical(self, comms8,
                                                sharded_flat_r2,
                                                dataset):
        """ISSUE 7 satellite: with R=2 and one rank down, a delete
        routed through the FailoverPlan masks the row on the SERVING
        REPLICA too — results bit-identical to the healthy mesh
        post-delete, coverage 1.0."""
        _, q = dataset
        idx = sharded_flat_r2
        mw = wrap_mnmg_mutable(comms8, idx, delta_cap=8)
        new_ids = np.arange(8900, 8904).astype(np.int32)
        mw, acc = mnmg_upsert(comms8, mw, q[:4], new_ids)
        assert acc.all()
        kw = dict(n_probes=8, qcap=q.shape[0])
        ids_now = np.asarray(
            mnmg_mutable_search(comms8, mw, q, K, **kw)[1]
        )
        victims = np.concatenate(
            [new_ids[:2], ids_now[:, 1][:3].astype(np.int32)]
        )
        h = faults.fail_rank(ShardHealth(8), 3)
        plan = FailoverPlan.from_health(
            ReplicaPlacement.of_index(idx), h
        )
        assert plan.fully_covered
        mw2, found = mnmg_delete(comms8, mw, victims)
        assert found.all()
        res_h = mnmg_mutable_search(comms8, mw2, q, K, shard_mask=True,
                                    **kw)
        res_d = mnmg_mutable_search(comms8, mw2, q, K, shard_mask=h,
                                    failover=plan, **kw)
        assert np.array_equal(np.asarray(res_h.ids),
                              np.asarray(res_d.ids))
        assert np.array_equal(np.asarray(res_h.distances),
                              np.asarray(res_d.distances))
        assert not np.isin(np.asarray(res_d.ids), victims).any()
        assert float(np.asarray(res_d.coverage).min()) == 1.0

    def test_mid_ingest_rank_failure_loses_no_acked_write(
        self, comms8, sharded_flat_r2, dataset, tmp_path
    ):
        """ISSUE 7 chaos acceptance: acked upserts before AND during a
        rank failure survive the fail_rank → recover_rank (main slabs
        from the CRC-verified checkpoint) → resync_rank (mutation slabs
        from the live replica) cycle; a TORN delta-segment flush is
        rejected loudly on the way (faults.inject_partial_write), so
        recovery routes through the replica instead of half-applying."""
        x, q = dataset
        idx = sharded_flat_r2
        ckpt = tmp_path / "base.npz"
        save_index(idx, ckpt)
        mw = wrap_mnmg_mutable(comms8, idx, delta_cap=8)
        kw = dict(n_probes=8, qcap=q.shape[0])
        ids1 = np.arange(9500, 9504).astype(np.int32)
        mw, acc1 = mnmg_upsert(comms8, mw, q[:4], ids1)
        assert acc1.all()
        # mid-ingest failure
        dead = 2
        h = faults.fail_rank(ShardHealth(8), dead)
        plan = FailoverPlan.from_health(
            ReplicaPlacement.of_index(idx), h
        )
        ids2 = np.arange(9600, 9604).astype(np.int32)
        mw, acc2 = mnmg_upsert(comms8, mw, q[4:8], ids2,
                               alive=h.mask())
        assert acc2.all()      # acked: recorded on every LIVE holder
        # every acked write serves through the failover route
        res = mnmg_mutable_search(comms8, mw, q, K, shard_mask=h,
                                  failover=plan, **kw)
        got = np.asarray(res.ids)
        assert (got[:4, 0] == ids1).all() and (got[4:8, 0] == ids2).all()
        # a torn delta-segment flush is detected, not half-applied
        side = ivf_flat_build(
            x[:400], IVFFlatParams(n_lists=4, kmeans_n_iters=2,
                                   kmeans_init="random"),
            metric="sqeuclidean",
        )
        smw = wrap_mutable(side, delta_cap=4)
        smw, _ = upsert(smw, x[:6], np.arange(100, 106).astype(np.int32))
        flush = tmp_path / "flush.npz"
        save_delta_checkpoint(smw, flush)
        faults.inject_partial_write(str(flush), mode="truncate",
                                    boundary=1)
        with pytest.raises(errors.CorruptIndexError):
            apply_delta_checkpoint(wrap_mutable(side, delta_cap=4), flush)
        # recovery: main slabs from the checkpoint, mutation slabs from
        # the surviving replica — then the healthy mesh serves every
        # acked write with primaries restored
        rec = recover_rank(comms8, mw.index, ckpt, dead)
        mw_rec = dataclasses.replace(mw, index=rec)
        mw_rec._id_loc = None
        mw_rec = resync_rank(comms8, mw_rec, dead)
        res2 = mnmg_mutable_search(comms8, mw_rec, q, K,
                                   shard_mask=True, **kw)
        got2 = np.asarray(res2.ids)
        assert (got2[:4, 0] == ids1).all()
        assert (got2[4:8, 0] == ids2).all()
        assert float(np.asarray(res2.coverage).min()) == 1.0

    def test_mnmg_rejected_upsert_is_a_strict_noop(self, comms8,
                                                   sharded_flat_r2,
                                                   dataset):
        """Review regression (MNMG): a capacity-rejected upsert leaves
        every replica copy of the id's previous version serving."""
        _, q = dataset
        idx = sharded_flat_r2
        mw = wrap_mnmg_mutable(comms8, idx, delta_cap=1)
        kw = dict(n_probes=8, qcap=q.shape[0])
        c = np.asarray(idx.centroids)[2:3]
        mw, acc = mnmg_upsert(comms8, mw, c, np.array([8300], np.int32))
        assert acc.all()                 # fills that list's one slot
        before = np.asarray(mnmg_mutable_search(comms8, mw, q, K, **kw)[1])
        victim = int(before[0, 0])
        mw2, acc2 = mnmg_upsert(comms8, mw, c,
                                np.array([victim], np.int32))
        assert not acc2.any()
        after = np.asarray(mnmg_mutable_search(comms8, mw2, q, K, **kw)[1])
        assert np.array_equal(before, after)

    def test_mutation_and_failover_flips_zero_retrace(
        self, comms8, sharded_flat_r2, dataset, monkeypatch
    ):
        """Upserts, tombstone flips, and health/failover flips all ride
        ONE compiled mutation-tier program (cache-size audit)."""
        from raft_tpu.comms import mnmg_ivf_flat as mod

        _, q = dataset
        idx = sharded_flat_r2
        mw = wrap_mnmg_mutable(comms8, idx, delta_cap=8)
        created = []
        orig = mod._cached_search

        def recording(*a, **k):
            fn = orig(*a, **k)
            created.append(fn)
            return fn

        monkeypatch.setattr(mod, "_cached_search", recording)
        kw = dict(n_probes=8, qcap=q.shape[0])
        h_up = np.ones(8, np.int32)
        h_dn = h_up.copy()
        h_dn[5] = 0
        plan = FailoverPlan.from_health(
            ReplicaPlacement.of_index(idx), h_dn
        )
        mnmg_mutable_search(comms8, mw, q, K, shard_mask=h_up, **kw)
        fn = created[0]
        size0 = fn._cache_size()
        for i in range(2):
            mw, acc = mnmg_upsert(
                comms8, mw, q + 0.01 * i,
                np.arange(9700 + 10 * i, 9700 + 10 * i + q.shape[0],
                          dtype=np.int32),
            )
            assert acc.all()
            mw, _ = mnmg_delete(
                comms8, mw, np.array([9700 + 10 * i], np.int32)
            )
            mnmg_mutable_search(comms8, mw, q, K, shard_mask=h_up, **kw)
            mnmg_mutable_search(comms8, mw, q, K, shard_mask=h_dn,
                                failover=plan, **kw)
        assert all(f is fn for f in created), \
            "mutation/health flips must reuse the cached program object"
        assert fn._cache_size() == size0, \
            "mutation/health flips must not retrace the program"

    def test_pq_mutation_with_pallas_kernel_engaged(self, comms8,
                                                    dataset,
                                                    monkeypatch):
        """The ISSUE 7 zero-retrace acceptance WITH the Pallas ADC
        engine engaged (interpret mode on CPU): upsert→visible,
        delete→masked, and no retrace across upsert + tombstone flips
        inside the fused PQ program running the kernel."""
        from raft_tpu.comms import mnmg_ivf as mod

        x, q = dataset
        idx = mnmg_ivf_pq_build(comms8, x, IVFPQParams(
            n_lists=8, pq_dim=4, kmeans_n_iters=3,
            kmeans_init="random", seed=4, store_raw=True,
        ))
        mw = wrap_mnmg_mutable(comms8, idx, delta_cap=8)
        kw = dict(n_probes=6, qcap=q.shape[0], refine_ratio=2.0,
                  use_pallas=True)
        created = []
        orig = mod._cached_search

        def recording(*a, **k):
            fn = orig(*a, **k)
            created.append(fn)
            return fn

        monkeypatch.setattr(mod, "_cached_search", recording)
        mnmg_mutable_search(comms8, mw, q, K, **kw)
        fn = created[0]
        size0 = fn._cache_size()
        new_ids = np.arange(9900, 9900 + q.shape[0]).astype(np.int32)
        mw2, acc = mnmg_upsert(comms8, mw, q, new_ids)
        assert acc.all()
        _, i1 = mnmg_mutable_search(comms8, mw2, q, K, **kw)
        assert (np.asarray(i1)[:, 0] == new_ids).all()
        victims = np.asarray(i1)[:, 1][:3].astype(np.int32)
        mw3, found = mnmg_delete(comms8, mw2, victims)
        assert found.all()
        _, i2 = mnmg_mutable_search(comms8, mw3, q, K, **kw)
        assert not np.isin(np.asarray(i2), victims).any()
        assert all(f is fn for f in created)
        assert fn._cache_size() == size0, \
            "mutations must not retrace the kernel-engaged program"
