"""Chaos suite for the resilience layer (raft_tpu/resilience/ +
raft_tpu/testing/faults.py) — every serving failure mode proven
end-to-end on the 8-device virtual CPU mesh, in tier-1:

* deadline-exceeded dispatch raises RaftTimeoutError; a retry succeeds
  WITHOUT recompiling (trace/dispatch counts audited);
* a fail_rank-masked shard yields a partial=True result whose valid
  entries exactly match a healthy search restricted to the surviving
  shards (parametrized over replication ∈ {1, 2});
* with R=2 replication and a FailoverPlan, a down rank's lists serve
  from their replica: coverage stays 1.0 and results are BIT-IDENTICAL
  to the healthy mesh, with zero retraces across the health flip;
* recover_rank restores a downed rank's slabs from a checkpoint and
  routing flips back — no rebuild;
* hedged dispatch beats an injected straggler deterministically;
  admission control sheds with RaftOverloadError, never collapses;
* a corrupt_bytes-damaged checkpoint raises CorruptIndexError naming
  the field, while an intact v1 (pre-manifest) file still loads;
* a batch with injected NaN rows returns finite top-k for all valid
  rows (and the empty answer, not garbage, for the poisoned ones);
* an index checkpoint restores onto a DIFFERENT mesh size via the
  place_index re-shard path with identical search results.

The failure-model rationale lives in docs/robustness.md.
"""

import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu import errors
from raft_tpu.comms import (
    build_comms,
    mnmg_ivf_flat_build,
    mnmg_ivf_flat_search,
    mnmg_ivf_pq_build,
    mnmg_ivf_pq_search,
    place_index,
    recover_rank,
    replicate_index,
    reshard_index,
)
from raft_tpu.resilience import (
    AdmissionController,
    Deadline,
    FailoverPlan,
    HedgePolicy,
    PartialSearchResult,
    ReplicaPlacement,
    RetryPolicy,
    ShardHealth,
    dispatch_hedged,
    dispatch_with_deadline,
    health_check,
)
from raft_tpu.resilience.health import HealthProbe, HealthReport
from raft_tpu.spatial.ann import (
    IVFFlatParams,
    IVFPQParams,
    ivf_flat_build,
    load_index,
    save_index,
)
from raft_tpu.testing import faults
from tests.oracles import np_knn_ids


# ---------------------------------------------------------------------------
# Deadline / RetryPolicy primitives (no mesh)
# ---------------------------------------------------------------------------


class TestDeadline:
    def test_after_and_remaining(self):
        d = Deadline.after(30.0)
        assert d.bounded
        assert 0.0 < d.remaining() <= 30.0
        assert not d.expired()

    def test_unbounded(self):
        d = Deadline.unbounded()
        assert not d.bounded
        assert d.remaining() == float("inf")
        assert not d.expired()
        assert Deadline.after(None).remaining() == float("inf")

    def test_expired(self):
        d = Deadline.after(1e-6)
        import time

        time.sleep(0.01)
        assert d.expired() and d.remaining() == 0.0

    def test_nonpositive_rejected(self):
        with pytest.raises(ValueError):
            Deadline.after(0.0)


class TestRetryPolicy:
    def test_backoff_deterministic_and_bounded(self):
        p = RetryPolicy(
            base_delay_s=0.1, multiplier=2.0, max_delay_s=0.5,
            jitter_frac=0.25, seed=11,
        )
        q = RetryPolicy(
            base_delay_s=0.1, multiplier=2.0, max_delay_s=0.5,
            jitter_frac=0.25, seed=11,
        )
        for a in range(1, 8):
            assert p.backoff_s(a) == q.backoff_s(a)  # replayable
            # exponential base, clipped, +-25% jitter
            base = min(0.5, 0.1 * 2.0 ** (a - 1))
            assert 0.75 * base <= p.backoff_s(a) <= 1.25 * base

    def test_seed_decorrelates(self):
        a = RetryPolicy(seed=1).backoff_s(1)
        b = RetryPolicy(seed=2).backoff_s(1)
        assert a != b  # two replicas de-synchronize their retries

    def test_classification(self):
        p = RetryPolicy()
        assert p.is_retryable(errors.RaftTimeoutError("t"))
        assert p.is_retryable(TimeoutError())
        assert not p.is_retryable(errors.RaftLogicError("bad arg"))
        assert not p.is_retryable(RuntimeError("boom"))


# ---------------------------------------------------------------------------
# dispatch_with_deadline + inject_delay (the straggler scenario)
# ---------------------------------------------------------------------------


class TestDispatchWithDeadline:
    def test_timeout_raises(self):
        fn, audit = faults.inject_delay(5.0)
        with pytest.raises(errors.RaftTimeoutError):
            dispatch_with_deadline(fn, jnp.arange(4.0), timeout_s=0.1)
        assert audit.calls == 1  # no retry without a policy

    def test_timeout_not_a_valueerror(self):
        """The serving loop's `except ValueError` (bad request) handler
        must never swallow a deadline."""
        fn, _ = faults.inject_delay(5.0)
        with pytest.raises(errors.RaftTimeoutError):
            try:
                dispatch_with_deadline(fn, jnp.arange(4.0), timeout_s=0.1)
            except ValueError:  # pragma: no cover - the bug being tested
                pytest.fail("RaftTimeoutError was caught as ValueError")

    def test_retry_succeeds_without_recompile(self):
        """THE acceptance audit: attempt 1 times out, the retry
        re-dispatches the already-compiled program (one trace, two
        executions) and returns the right answer."""
        fn, audit = faults.inject_delay(5.0, first_n=1)
        x = jnp.arange(8.0)
        seen = []
        out = dispatch_with_deadline(
            fn, x, timeout_s=0.25,
            retry=RetryPolicy(max_attempts=3, base_delay_s=0.01),
            on_retry=lambda a, e, s: seen.append((a, type(e).__name__)),
        )
        np.testing.assert_allclose(np.asarray(out), np.arange(8.0))
        assert audit.traces == 1, "retry must reuse the compiled program"
        assert audit.dispatches == 2, "retry must actually re-execute"
        assert audit.calls == 2
        assert seen == [(1, "RaftTimeoutError")]

    def test_non_retryable_propagates_immediately(self):
        calls = []

        def bad(_x):
            calls.append(1)
            raise errors.RaftLogicError("malformed batch")

        with pytest.raises(ValueError, match="malformed batch"):
            dispatch_with_deadline(
                bad, jnp.arange(4.0), timeout_s=1.0,
                retry=RetryPolicy(max_attempts=5, base_delay_s=0.01),
            )
        assert len(calls) == 1  # classification stopped the retries

    def test_overall_deadline_caps_retries(self):
        fn, audit = faults.inject_delay(5.0)
        with pytest.raises(errors.RaftTimeoutError):
            dispatch_with_deadline(
                fn, jnp.arange(4.0), timeout_s=0.1,
                deadline=Deadline.after(0.3),
                retry=RetryPolicy(max_attempts=100, base_delay_s=0.01),
            )
        assert audit.calls < 100  # the budget, not max_attempts, stopped it


# ---------------------------------------------------------------------------
# ShardHealth + health_check
# ---------------------------------------------------------------------------


class TestShardHealth:
    def test_mark_and_mask(self):
        h = ShardHealth(4)
        assert h.all_up and h.n_up == 4
        h.mark_down(2)
        h.mark_down(2)  # idempotent
        assert not h.all_up and h.n_up == 3 and not h.is_up(2)
        np.testing.assert_array_equal(h.mask(), [1, 1, 0, 1])
        h.mark_up(2)
        assert h.all_up
        assert "down=none" in repr(h)

    def test_bad_rank_rejected(self):
        h = ShardHealth(2)
        with pytest.raises(ValueError):
            h.mark_down(2)
        with pytest.raises(ValueError):
            ShardHealth(0)

    def test_fail_rank_helper(self):
        h = faults.fail_rank(8, 1, 5)
        np.testing.assert_array_equal(h.mask(), [1, 0, 1, 1, 1, 0, 1, 1])
        h2 = faults.fail_rank(h, 0)
        assert h2 is h and not h.is_up(0)


def test_health_check_timed_sweep(comms8):
    report = health_check(comms8)
    assert report.ok and report.failed == []
    assert len(report.probes) == 10  # the full self-test registry
    assert all(p.seconds >= 0 for p in report.probes.values())
    assert report.total_seconds > 0


def test_health_check_failure_marks_all_down(comms8, monkeypatch):
    from raft_tpu.comms import self_test as st

    def torn_mesh(_comms):
        raise RuntimeError("simulated torn mesh")

    monkeypatch.setitem(st.SELF_TESTS, "allreduce", torn_mesh)
    h = ShardHealth(8)
    report = health_check(comms8, health=h)
    assert not report.ok and report.failed == ["allreduce"]
    assert h.n_up == 0  # a torn fabric serves no shard
    with pytest.raises(errors.RaftException, match="allreduce"):
        health_check(comms8, raise_on_failure=True)


# ---------------------------------------------------------------------------
# Degraded sharded search (both engines) on the 8-device mesh
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def comms8():
    return build_comms(jax.devices()[:8])


@pytest.fixture(scope="module")
def dataset():
    rng = np.random.default_rng(7)
    x = rng.standard_normal((512, 16)).astype(np.float32)
    q = x[::37][:12] + 0.05 * rng.standard_normal((12, 16)).astype(
        np.float32
    )
    return x, q


FLAT_PARAMS = IVFFlatParams(n_lists=8, kmeans_n_iters=4, seed=3)
K = 5


@pytest.fixture(scope="module")
def flat_index(comms8, dataset):
    x, _ = dataset
    return mnmg_ivf_flat_build(comms8, x, FLAT_PARAMS)


@pytest.fixture(scope="module", params=[
    ("flat_probe", 1), ("two_level_probe", 1),
    ("flat_probe", 2), ("two_level_probe", 2),
], ids=lambda p: f"{p[0]}-r{p[1]}")
def probed_index(request, comms8, flat_index):
    """The degraded-search suite runs under BOTH coarse probes (flat
    centroid scan vs two-level CoarseIndex) AND under replication ∈
    {1, 2}: the PartialSearchResult semantics (shard_mask with a down
    rank, owner=-1 probe-set extras, NaN query rows) must be identical
    in all four layouts — an unrouted replicated index serves primaries
    exactly like the unreplicated one."""
    probe, replication = request.param
    idx = flat_index
    if replication > 1:
        idx = place_index(comms8, idx, replication=replication)
    if probe == "two_level_probe":
        from raft_tpu.comms import attach_coarse_index

        idx = attach_coarse_index(idx, seed=0)
    return idx


@pytest.fixture(scope="module")
def replicated_flat(comms8, flat_index):
    """The R=2 striped replica layout of the flat suite's index."""
    return place_index(comms8, flat_index, replication=2)


def _rank_row_ids(index, rank):
    """GLOBAL row ids whose PRIMARY owner is ``rank`` (host-side, from
    the slab layout: the primary segment is the first nl_pad/R lists,
    so its rows are [0, list_offsets[rank, nl_pad/R]))."""
    offs = np.asarray(index.list_offsets)
    sids = np.asarray(index.sorted_ids)
    nlp_base = index.nl_pad // int(getattr(index, "replication", 1) or 1)
    return sids[rank, : offs[rank, nlp_base]]


def test_all_up_mask_matches_healthy_search(comms8, dataset, probed_index):
    x, q = dataset
    v0, i0 = mnmg_ivf_flat_search(
        comms8, probed_index, q, K, n_probes=8, qcap=q.shape[0]
    )
    res = mnmg_ivf_flat_search(
        comms8, probed_index, q, K, n_probes=8, qcap=q.shape[0],
        shard_mask=True,
    )
    assert isinstance(res, PartialSearchResult)
    assert res.partial is False
    np.testing.assert_array_equal(np.asarray(res.coverage), 1.0)
    assert np.asarray(res.row_valid).all()
    np.testing.assert_allclose(
        np.asarray(res.distances), np.asarray(v0), rtol=1e-6
    )
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(i0))


def test_fail_rank_matches_surviving_shard_search(
    comms8, dataset, probed_index
):
    """THE degraded-search acceptance: with rank r down and every list
    probed, the partial result's valid entries exactly equal the exact
    top-k over the rows the SURVIVING shards own."""
    x, q = dataset
    # pick a rank that owns rows (they all do under LPT balance)
    dead = 2
    dead_ids = set(_rank_row_ids(probed_index, dead).tolist())
    assert dead_ids, "test premise: the dead rank owns rows"
    health = faults.fail_rank(ShardHealth(8), dead)
    res = mnmg_ivf_flat_search(
        comms8, probed_index, q, K, n_probes=8, qcap=q.shape[0],
        shard_mask=health,
    )
    assert res.partial is True
    cov = np.asarray(res.coverage)
    assert (cov < 1.0).any() and (cov >= 0.0).all()
    # oracle: exact search restricted to surviving rows (probe-everything
    # IVF-Flat == brute force over the surviving shards' union)
    alive_ids = np.array(
        sorted(set(range(x.shape[0])) - dead_ids), np.int64
    )
    want = alive_ids[np_knn_ids(x[alive_ids], q, K)]
    got_d = np.asarray(res.distances)
    got_i = np.asarray(res.ids)
    assert np.isfinite(got_d).all()  # >= K survivors everywhere
    np.testing.assert_array_equal(got_i, want)
    assert not (set(got_i.ravel().tolist()) & dead_ids)


def test_all_ranks_down_degrades_not_raises(comms8, dataset, probed_index):
    _, q = dataset
    res = mnmg_ivf_flat_search(
        comms8, probed_index, q, K, n_probes=8, qcap=q.shape[0],
        shard_mask=np.zeros(8, np.int32),
    )
    assert res.partial is True and res.min_coverage == 0.0
    assert np.isinf(np.asarray(res.distances)).all()
    assert (np.asarray(res.ids) == -1).all()


def test_nan_rows_neutralized(comms8, dataset, probed_index):
    """THE bad-input acceptance: poisoned rows cannot contaminate their
    batchmates — valid rows return the finite healthy answer, poisoned
    rows return the empty answer."""
    _, q = dataset
    bad_rows = [1, 4]
    qbad = faults.inject_nonfinite(q, bad_rows, kind="nan")
    qbad = faults.inject_nonfinite(qbad, [7], kind="inf")
    res = mnmg_ivf_flat_search(
        comms8, probed_index, qbad, K, n_probes=8, qcap=q.shape[0],
        shard_mask=True,
    )
    rv = np.asarray(res.row_valid)
    want_valid = np.ones(q.shape[0], bool)
    want_valid[[1, 4, 7]] = False
    np.testing.assert_array_equal(rv, want_valid)
    assert res.partial is True
    d, i = np.asarray(res.distances), np.asarray(res.ids)
    assert np.isfinite(d[rv]).all()
    assert np.isinf(d[~rv]).all() and (i[~rv] == -1).all()
    np.testing.assert_array_equal(np.asarray(res.coverage)[~rv], 0.0)
    # valid rows exactly match the healthy search of the same rows
    v0, i0 = mnmg_ivf_flat_search(
        comms8, probed_index, q, K, n_probes=8, qcap=q.shape[0]
    )
    np.testing.assert_array_equal(i[rv], np.asarray(i0)[rv])


def test_degraded_pq_engine(comms8, dataset):
    """The PQ engine shares the degraded contract (mask, +inf, coverage,
    sanitize) — spot-check all-up parity and a down rank."""
    x, q = dataset
    idx = mnmg_ivf_pq_build(
        comms8, x,
        IVFPQParams(n_lists=8, pq_dim=4, kmeans_n_iters=3, seed=5),
    )
    v0, i0 = mnmg_ivf_pq_search(comms8, idx, q, K, n_probes=8,
                                qcap=q.shape[0])
    res = mnmg_ivf_pq_search(
        comms8, idx, q, K, n_probes=8, qcap=q.shape[0], shard_mask=True,
    )
    assert res.partial is False
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(i0))
    health = faults.fail_rank(ShardHealth(8), 0)
    res2 = mnmg_ivf_pq_search(
        comms8, idx, q, K, n_probes=8, qcap=q.shape[0], shard_mask=health,
    )
    assert res2.partial is True
    dead_ids = set(_rank_row_ids(idx, 0).tolist())
    live = np.asarray(res2.ids)[np.asarray(res2.ids) >= 0]
    assert not (set(live.ravel().tolist()) & dead_ids)


def test_warmup_resilient_variant(comms8, dataset, probed_index):
    _, q = dataset
    qc = probed_index.warmup(
        comms8, q.shape[0], k=K, n_probes=8, shard_mask=True
    )
    assert isinstance(qc, int) and qc >= 1


def test_probe_set_extras_identical_partial_semantics(
    comms8, dataset, probed_index
):
    """owner=-1 probe-set extras under a down rank: the degraded result
    (distances, ids, coverage, row_valid) must be IDENTICAL with the
    extras attached — unowned far-away centroids never enter any
    query's top probes — and identical under the two-level vs flat
    probe (expand_probe_set rebuilds an attached coarse index over the
    expanded set)."""
    from raft_tpu.comms import expand_probe_set

    _, q = dataset
    rng = np.random.default_rng(17)
    far = (1e4 + rng.standard_normal((64, 16))).astype(np.float32)
    eidx = expand_probe_set(probed_index, far)
    # the coarse index (when present) must cover the expanded set
    assert (eidx.coarse is not None) == (probed_index.coarse is not None)
    if eidx.coarse is not None:
        assert eidx.coarse.n_cents == int(eidx.owner.shape[0])
    health = faults.fail_rank(ShardHealth(8), 3)
    base = mnmg_ivf_flat_search(
        comms8, probed_index, q, K, n_probes=8, qcap=q.shape[0],
        shard_mask=health,
    )
    res = mnmg_ivf_flat_search(
        comms8, eidx, q, K, n_probes=8, qcap=q.shape[0],
        shard_mask=health,
    )
    assert isinstance(res, PartialSearchResult)
    np.testing.assert_array_equal(
        np.asarray(res.ids), np.asarray(base.ids)
    )
    np.testing.assert_allclose(
        np.asarray(res.distances), np.asarray(base.distances), rtol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(res.coverage), np.asarray(base.coverage)
    )
    np.testing.assert_array_equal(
        np.asarray(res.row_valid), np.asarray(base.row_valid)
    )


def test_two_level_probe_health_flip_zero_retrace(
    comms8, dataset, flat_index, monkeypatch
):
    """The recompile-hazard regression (trace/dispatch audit): with the
    two-level probe engaged, flipping ``shard_mask`` values at runtime
    triggers ZERO retraces of the compiled serving program, while
    flipping ``overprobe`` is a trace-time static (a DIFFERENT program,
    itself compiled once and reused across mask flips)."""
    from raft_tpu.comms import attach_coarse_index
    from raft_tpu.comms import mnmg_ivf_flat as mod

    _, q = dataset
    idx = attach_coarse_index(flat_index, seed=0)
    created = []
    orig = mod._cached_search

    def recording(*a, **k):
        fn = orig(*a, **k)
        created.append(fn)
        return fn

    monkeypatch.setattr(mod, "_cached_search", recording)
    kw = dict(n_probes=8, qcap=q.shape[0])
    m_up = np.ones(8, np.int32)
    m_one = m_up.copy()
    m_one[3] = 0
    m_two = m_up.copy()
    m_two[1] = m_two[6] = 0
    mod.mnmg_ivf_flat_search(comms8, idx, q, K, shard_mask=m_up, **kw)
    fn = created[0]
    size0 = fn._cache_size()
    for mask in (m_one, m_two, m_up):
        mod.mnmg_ivf_flat_search(comms8, idx, q, K, shard_mask=mask, **kw)
    assert all(f is fn for f in created), \
        "health flips must reuse the cached program object"
    assert fn._cache_size() == size0, \
        "health flips must not retrace the compiled program"
    # overprobe flips at TRACE time: a distinct program...
    mod.mnmg_ivf_flat_search(
        comms8, idx, q, K, shard_mask=m_up, overprobe=3.0, **kw
    )
    fn2 = created[-1]
    assert fn2 is not fn
    size2 = fn2._cache_size()
    # ...that mask flips then reuse without retracing
    mod.mnmg_ivf_flat_search(
        comms8, idx, q, K, shard_mask=m_one, overprobe=3.0, **kw
    )
    assert created[-1] is fn2 and fn2._cache_size() == size2


# ---------------------------------------------------------------------------
# R-way replication + failover (resilience/replica.py)
# ---------------------------------------------------------------------------


class TestReplicaPlacement:
    def test_striped_holders_and_segments(self):
        p = ReplicaPlacement.striped(8, 2)     # default offset P//R = 4
        assert p.offset == 4
        assert p.holders(1) == (1, 5)
        assert p.segments(5) == (5, 1)
        assert p.memory_factor == 2
        p3 = ReplicaPlacement.striped(8, 3, offset=1)
        assert p3.holders(6) == (6, 7, 0)

    def test_colliding_offset_rejected(self):
        with pytest.raises(ValueError, match="collides"):
            ReplicaPlacement.striped(8, 2, offset=8)
        with pytest.raises(ValueError, match="collides"):
            ReplicaPlacement.striped(8, 3, offset=4)  # 2*4 % 8 == 0

    def test_replication_bounds(self):
        with pytest.raises(ValueError, match="replication"):
            ReplicaPlacement.striped(4, 5)
        with pytest.raises(ValueError, match="replication"):
            ReplicaPlacement.striped(4, 0)


class TestFailoverPlan:
    def test_healthy_routes_primaries(self):
        p = ReplicaPlacement.striped(8, 2)
        plan = FailoverPlan.from_health(p, True)
        np.testing.assert_array_equal(plan.route, np.zeros(8))
        assert plan.fully_covered
        np.testing.assert_array_equal(plan.serving_load(), np.ones(8))

    def test_single_failure_routes_to_replica(self):
        p = ReplicaPlacement.striped(8, 2)
        plan = FailoverPlan.from_health(p, faults.fail_rank(8, 2))
        assert plan.fully_covered
        assert plan.route[2] == 1 and plan.serving_rank(2) == 6
        assert (plan.route[np.arange(8) != 2] == 0).all()
        load = plan.serving_load()
        assert load[2] == 0 and load[6] == 2  # rank 6 carries both

    def test_whole_group_dead_unserved(self):
        p = ReplicaPlacement.striped(8, 2)
        plan = FailoverPlan.from_health(p, faults.fail_rank(8, 3, 7))
        # shards 3 and 7 share holders {3, 7}: both groups are dead
        assert not plan.fully_covered
        assert plan.unserved_shards == [3, 7]
        assert plan.serving_rank(3) == -1


def test_replicated_layout_geometry(flat_index, replicated_flat):
    base, rep = flat_index, replicated_flat
    assert rep.replication == 2 and rep.replica_offset == 4
    assert rep.nl_pad == 2 * base.nl_pad
    # primary segment 0 is byte-identical to the base layout (healthy
    # serving reads it with unchanged local ids/offsets), segment 1 is
    # the replica partner's primary
    szs_b = np.asarray(base.list_sizes)
    szs_r = np.asarray(rep.list_sizes)
    for r in range(8):
        np.testing.assert_array_equal(szs_r[r, : base.nl_pad], szs_b[r])
        np.testing.assert_array_equal(
            szs_r[r, base.nl_pad:], szs_b[(r - 4) % 8]
        )
    # every rank's replica segment carries its partner's primary rows
    for r in range(8):
        partner = (r - 4) % 8
        prim = np.sort(_rank_row_ids(rep, r))
        np.testing.assert_array_equal(
            prim, np.sort(_rank_row_ids(flat_index, r))
        )
        offs = np.asarray(rep.list_offsets)
        sids = np.asarray(rep.sorted_ids)
        seg1 = sids[r, offs[r, base.nl_pad]: offs[r, -1]]
        np.testing.assert_array_equal(
            np.sort(seg1), np.sort(_rank_row_ids(flat_index, partner))
        )


def test_failover_full_coverage_bit_identical(
    comms8, dataset, replicated_flat
):
    """THE tentpole acceptance: R=2, any single rank down, failover
    routed — coverage 1.0 everywhere and results BIT-IDENTICAL to the
    healthy mesh."""
    _, q = dataset
    v0, i0 = mnmg_ivf_flat_search(
        comms8, replicated_flat, q, K, n_probes=8, qcap=q.shape[0]
    )
    placement = ReplicaPlacement.of_index(replicated_flat)
    for dead in range(8):
        health = faults.fail_rank(ShardHealth(8), dead)
        plan = FailoverPlan.from_health(placement, health)
        assert plan.fully_covered
        res = mnmg_ivf_flat_search(
            comms8, replicated_flat, q, K, n_probes=8, qcap=q.shape[0],
            shard_mask=health, failover=plan,
        )
        assert isinstance(res, PartialSearchResult)
        assert res.partial is False
        np.testing.assert_array_equal(np.asarray(res.coverage), 1.0)
        np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(i0))
        np.testing.assert_array_equal(
            np.asarray(res.distances), np.asarray(v0)
        )


def test_failover_pq_engine_bit_identical(comms8, dataset):
    x, q = dataset
    idx = mnmg_ivf_pq_build(
        comms8, x,
        IVFPQParams(n_lists=8, pq_dim=4, kmeans_n_iters=3, seed=5),
    )
    v0, i0 = mnmg_ivf_pq_search(comms8, idx, q, K, n_probes=8,
                                qcap=q.shape[0])
    ridx = place_index(comms8, idx, replication=2)
    health = faults.fail_rank(ShardHealth(8), 5)
    plan = FailoverPlan.from_health(
        ReplicaPlacement.of_index(ridx), health
    )
    res = mnmg_ivf_pq_search(
        comms8, ridx, q, K, n_probes=8, qcap=q.shape[0],
        shard_mask=health, failover=plan,
    )
    assert res.partial is False and res.min_coverage == 1.0
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(i0))
    np.testing.assert_array_equal(
        np.asarray(res.distances), np.asarray(v0)
    )


def test_whole_group_dead_degrades_partial(comms8, dataset,
                                           replicated_flat):
    """Both replicas of one group down: the plan routes -1 and the
    search degrades to the PR 3 partial path for exactly those lists."""
    _, q = dataset
    health = faults.fail_rank(ShardHealth(8), 1, 5)  # group {1, 5}
    plan = FailoverPlan.from_health(
        ReplicaPlacement.of_index(replicated_flat), health
    )
    assert plan.unserved_shards == [1, 5]
    res = mnmg_ivf_flat_search(
        comms8, replicated_flat, q, K, n_probes=8, qcap=q.shape[0],
        shard_mask=health, failover=plan,
    )
    assert res.partial is True
    cov = np.asarray(res.coverage)
    assert (cov < 1.0).any()
    dead_ids = set(_rank_row_ids(replicated_flat, 1).tolist()) | set(
        _rank_row_ids(replicated_flat, 5).tolist()
    )
    live = np.asarray(res.ids)[np.asarray(res.ids) >= 0]
    assert not (set(live.ravel().tolist()) & dead_ids)


def test_failover_flip_zero_retrace(comms8, dataset, replicated_flat,
                                    monkeypatch):
    """THE zero-retrace acceptance across failover flips: health down →
    replica serves → health up, all against ONE compiled program (route
    and mask are runtime inputs)."""
    from raft_tpu.comms import mnmg_ivf_flat as mod

    _, q = dataset
    created = []
    orig = mod._cached_search

    def recording(*a, **k):
        fn = orig(*a, **k)
        created.append(fn)
        return fn

    monkeypatch.setattr(mod, "_cached_search", recording)
    placement = ReplicaPlacement.of_index(replicated_flat)
    kw = dict(n_probes=8, qcap=q.shape[0])
    health = ShardHealth(8)
    plan_up = FailoverPlan.from_health(placement, health)
    r0 = mod.mnmg_ivf_flat_search(
        comms8, replicated_flat, q, K, shard_mask=health,
        failover=plan_up, **kw,
    )
    fn = created[0]
    size0 = fn._cache_size()
    # rank 3 dies; its shard serves from the replica on rank 7
    health.mark_down(3)
    plan_down = FailoverPlan.from_health(placement, health)
    r1 = mod.mnmg_ivf_flat_search(
        comms8, replicated_flat, q, K, shard_mask=health,
        failover=plan_down, **kw,
    )
    # rank 3 heals; route flips back to the primary
    health.mark_up(3)
    r2 = mod.mnmg_ivf_flat_search(
        comms8, replicated_flat, q, K, shard_mask=health,
        failover=FailoverPlan.from_health(placement, health), **kw,
    )
    assert all(f is fn for f in created), \
        "failover flips must reuse the cached program object"
    assert fn._cache_size() == size0, \
        "failover flips must not retrace the compiled program"
    for r in (r1, r2):
        assert r.partial is False
        np.testing.assert_array_equal(
            np.asarray(r.ids), np.asarray(r0.ids)
        )
        np.testing.assert_array_equal(
            np.asarray(r.distances), np.asarray(r0.distances)
        )


def test_open_loop_executor_failover_chaos(comms8, dataset,
                                           replicated_flat, monkeypatch,
                                           tmp_path):
    """ISSUE 8 chaos acceptance: ONE open-loop executor serves a
    request stream through a mid-stream rank failure with R=2 — the
    hedge covers the straggling batches, the FailoverPlan route flows
    in as a runtime input, every answer stays bit-identical to the
    healthy mesh at coverage 1.0, and the compiled program never
    retraces.

    ISSUE 13 extension: a FlightRecorder rides the same executor, and
    its dump must tell the postmortem story — the straggling batch the
    hedge covered, the backup winning the race, and the failover flip's
    route — while the live retrace census reads the same program count
    the trace audit pins."""
    from raft_tpu.comms import mnmg_ivf_flat as mod
    from raft_tpu.obs import FlightRecorder, program_census
    from raft_tpu.serving import ServingExecutor

    _, q = dataset                                   # (12, 16) queries
    qcap = q.shape[0]
    buckets = (4, 8)
    created = []
    orig = mod._cached_search

    def recording(*a, **k):
        fn = orig(*a, **k)
        created.append(fn)
        return fn

    monkeypatch.setattr(mod, "_cached_search", recording)
    placement = ReplicaPlacement.of_index(replicated_flat)
    health = ShardHealth(8)

    def run(qq, shard_mask=None, failover=None):
        return mod.mnmg_ivf_flat_search(
            comms8, replicated_flat, qq, K, n_probes=8, qcap=qcap,
            shard_mask=shard_mask if shard_mask is not None
            else np.ones(8, np.int32),
            failover=failover,
        )

    # healthy reference + warm both bucket shapes BEFORE the audit mark
    plan0 = FailoverPlan.from_health(placement, health)
    ref = run(jnp.asarray(q), shard_mask=health.mask(), failover=plan0)
    vref, iref = np.asarray(ref.distances), np.asarray(ref.ids)
    for b in buckets:
        jax.block_until_ready(run(
            jnp.zeros((b, q.shape[1]), jnp.float32),
            shard_mask=health.mask(), failover=plan0,
        ))
    fn = created[0]
    size0 = fn._cache_size()

    straggler_s = 1.0
    primary, audit = faults.inject_straggler(run, every=3,
                                             seconds=straggler_s)
    recorder = FlightRecorder(1024, dump_dir=str(tmp_path),
                              name="chaos")
    ex = ServingExecutor(
        primary, buckets, dim=q.shape[1], flush_age_s=0.0,
        max_in_flight=2, hedge=0.02, backup_dispatch=run,
        runtime_inputs={"shard_mask": health.mask(), "failover": plan0},
        flight=recorder,
    )
    lat_ms = []
    results = []

    def drain(futs):
        for rows, fut, t0 in futs:
            res = fut.result(timeout=60)
            lat_ms.append((time.monotonic() - t0) * 1e3)
            results.append((rows, res))

    def submit_wave():
        futs = []
        for start, m in ((0, 3), (3, 2), (5, 3), (8, 4), (0, 8), (8, 2)):
            futs.append((
                list(range(start, start + m)),
                ex.submit(q[start:start + m]),
                time.monotonic(),
            ))
        return futs

    drain(submit_wave())                              # healthy traffic
    # rank 3 dies MID-STREAM: route its shard to the replica via the
    # executor's runtime inputs — later dispatches pick it up, nothing
    # recompiles
    faults.fail_rank(health, 3)
    plan = FailoverPlan.from_health(placement, health)
    assert plan.fully_covered
    ex.set_runtime(shard_mask=health.mask(), failover=plan)
    drain(submit_wave())                              # degraded traffic
    # rank 3 heals; primary routing resumes
    health.mark_up(3)
    ex.set_runtime(shard_mask=health.mask(),
                   failover=FailoverPlan.from_health(placement, health))
    drain(submit_wave())
    st = ex.stats()
    ex.close()

    assert st.completed == len(results) and st.failed == 0
    # hedge engaged on the injected stragglers (every 3rd batch)
    assert st.hedged_batches >= 1 and st.backup_wins >= 1
    # bounded tail THROUGH the failure: the straggling batches resolve
    # via the backup at ~hedge_delay + service, well under the 1 s
    # straggle the unhedged path would eat
    assert max(lat_ms) < 0.9 * straggler_s * 1e3, max(lat_ms)
    # every answer bit-identical to the healthy mesh at coverage 1.0
    for rows, res in results:
        np.testing.assert_array_equal(np.asarray(res.coverage), 1.0)
        assert bool(np.asarray(res.row_valid).all())
        np.testing.assert_array_equal(res.ids, iref[rows])
        np.testing.assert_array_equal(res.distances, vref[rows])
    # zero retraces across warm → fail → failover → heal, incl. hedges
    assert all(f is fn for f in created), \
        "the open-loop stream must reuse the cached program object"
    assert fn._cache_size() == size0, \
        "health/failover flips through the executor must not retrace"
    # the LIVE retrace gauge reads the same program count the trace
    # audit just pinned — the zero-retrace contract as a runtime metric
    census = program_census({"mnmg_ivf_flat._cached_search": fn})
    assert census["mnmg_ivf_flat._cached_search"] == size0

    # -- the flight-recorder postmortem (ISSUE 13 acceptance) ---------
    # the dump must NAME (a) the straggling batch the hedge covered,
    # (b) the hedge winner, (c) the failover flip's route
    hedges = recorder.events(event="hedge")
    assert hedges, "the injected stragglers must appear as hedge events"
    straggler_batch = hedges[0]["batch_id"]
    assert hedges[0]["age_ms"] >= 0.02 * 1e3 * 0.5
    wins = [e for e in recorder.events(event="demux")
            if e["winner"] == "backup"]
    assert wins, "a backup win must be attributed in the recorder"
    flips = [e for e in recorder.events(event="runtime_update")
             if "failover_route" in e]
    # the mid-stream flip routes rank 3's shard to replica copy 1
    # (and the heal routes it back to 0)
    assert any(e["failover_route"][3] == 1 for e in flips)
    assert any(e["failover_route"][3] == 0 for e in flips)
    path = recorder.dump("chaos-postmortem")
    lines = [json.loads(ln) for ln in open(path)]
    assert lines[0]["reason"] == "chaos-postmortem"
    dumped = {ln.get("event") for ln in lines[1:]}
    assert {"hedge", "demux", "runtime_update"} <= dumped
    assert any(ln.get("event") == "dispatch"
               and ln.get("batch_id") == straggler_batch
               for ln in lines[1:]), \
        "the dump must show the straggling batch's dispatch"


def test_failover_requires_shard_mask(comms8, dataset, replicated_flat):
    _, q = dataset
    plan = FailoverPlan.from_health(
        ReplicaPlacement.of_index(replicated_flat), True
    )
    with pytest.raises(ValueError, match="shard_mask"):
        mnmg_ivf_flat_search(
            comms8, replicated_flat, q, K, n_probes=8, qcap=q.shape[0],
            failover=plan,
        )


def test_failover_plan_geometry_mismatch_rejected(
    comms8, dataset, replicated_flat
):
    _, q = dataset
    bad = FailoverPlan.from_health(ReplicaPlacement.striped(8, 2, 1), True)
    with pytest.raises(ValueError, match="does not match"):
        mnmg_ivf_flat_search(
            comms8, replicated_flat, q, K, n_probes=8, qcap=q.shape[0],
            shard_mask=True, failover=bad,
        )


def test_replicated_checkpoint_roundtrip_and_reshard(
    comms8, dataset, replicated_flat, tmp_path
):
    """A replicated checkpoint round-trips (layout statics preserved)
    and restores onto a smaller mesh with replication re-applied."""
    _, q = dataset
    v0, i0 = mnmg_ivf_flat_search(
        comms8, replicated_flat, q, K, n_probes=8, qcap=q.shape[0]
    )
    p = tmp_path / "replicated.npz"
    save_index(replicated_flat, p)
    back = load_index(p, comms=comms8)
    assert back.replication == 2 and back.nl_pad == replicated_flat.nl_pad
    v1, i1 = mnmg_ivf_flat_search(
        comms8, back, q, K, n_probes=8, qcap=q.shape[0]
    )
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    comms4 = build_comms(jax.devices()[:4])
    idx4 = place_index(comms4, back, replication=2)
    assert idx4.sorted_ids.shape[0] == 4 and idx4.replication == 2
    plan4 = FailoverPlan.from_health(
        ReplicaPlacement.of_index(idx4), faults.fail_rank(4, 0)
    )
    res4 = mnmg_ivf_flat_search(
        comms4, idx4, q, K, n_probes=8, qcap=q.shape[0],
        shard_mask=faults.fail_rank(4, 0), failover=plan4,
    )
    assert res4.partial is False
    np.testing.assert_array_equal(np.asarray(res4.ids), np.asarray(i0))


def test_recover_rank_full_cycle(comms8, dataset, replicated_flat,
                                 tmp_path):
    """The heal path end-to-end: rank dies → failover serves (identical
    results) → replacement rank restores its slabs from the checkpoint
    (recover_rank) → health up, route back → healthy serving, all
    results identical throughout."""
    import dataclasses as dc

    _, q = dataset
    v0, i0 = mnmg_ivf_flat_search(
        comms8, replicated_flat, q, K, n_probes=8, qcap=q.shape[0]
    )
    p = tmp_path / "ckpt.npz"
    save_index(replicated_flat, p)
    placement = ReplicaPlacement.of_index(replicated_flat)
    dead = 6
    health = faults.fail_rank(ShardHealth(8), dead)
    # the dead rank's slab content is LOST (zeroed) — only the replica
    # and the checkpoint still hold its lists
    wrecked = dc.replace(
        replicated_flat,
        vectors_sorted=jnp.zeros_like(
            jnp.asarray(replicated_flat.vectors_sorted)
        ).at[np.arange(8) != dead].set(
            jnp.asarray(replicated_flat.vectors_sorted)[
                np.arange(8) != dead
            ]
        ),
        sorted_ids=jnp.asarray(replicated_flat.sorted_ids)
        .at[dead].set(0),
    )
    plan = FailoverPlan.from_health(placement, health)
    res = mnmg_ivf_flat_search(
        comms8, wrecked, q, K, n_probes=8, qcap=q.shape[0],
        shard_mask=health, failover=plan,
    )
    assert res.partial is False
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(i0))
    # replacement chip joins: restore the slabs, flip health + route
    healed = recover_rank(comms8, wrecked, p, dead)
    np.testing.assert_array_equal(
        np.asarray(healed.sorted_ids)[dead],
        np.asarray(replicated_flat.sorted_ids)[dead],
    )
    health.mark_up(dead)
    plan_back = FailoverPlan.from_health(placement, health)
    np.testing.assert_array_equal(plan_back.route, np.zeros(8))
    res2 = mnmg_ivf_flat_search(
        comms8, healed, q, K, n_probes=8, qcap=q.shape[0],
        shard_mask=health, failover=plan_back,
    )
    assert res2.partial is False
    np.testing.assert_array_equal(np.asarray(res2.ids), np.asarray(i0))
    np.testing.assert_array_equal(
        np.asarray(res2.distances), np.asarray(v0)
    )


def test_recover_rank_layout_mismatch_rejected(
    comms8, flat_index, replicated_flat, tmp_path
):
    p = tmp_path / "base.npz"
    save_index(flat_index, p)      # unreplicated checkpoint
    with pytest.raises(ValueError, match="not a checkpoint of this build"):
        recover_rank(comms8, replicated_flat, p, 0)


def test_replicate_index_rejects_replicated_input(replicated_flat):
    with pytest.raises(ValueError, match="already"):
        replicate_index(replicated_flat, 2)


# ---------------------------------------------------------------------------
# Hedged dispatch (resilience/deadline.py) — the straggler tail
# ---------------------------------------------------------------------------


class TestDispatchHedged:
    def test_backup_wins_on_straggler_without_recompile(self):
        """Primary straggles past the hedge delay → the backup is
        dispatched from the SAME compiled program and wins,
        deterministically."""
        fn, audit = faults.inject_delay(5.0, first_n=1)
        pol = HedgePolicy(default_delay_s=0.05, min_samples=100)
        out = dispatch_hedged(fn, jnp.arange(8.0), hedge=pol)
        np.testing.assert_allclose(np.asarray(out), np.arange(8.0))
        assert audit.traces == 1, "hedge must reuse the compiled program"
        assert audit.calls == 2 and audit.dispatches == 2
        assert pol.hedges == 1 and pol.backup_wins == 1
        assert pol.primary_wins == 0 and pol.unhedged == 0

    def test_fast_primary_never_hedges(self):
        fn, audit = faults.inject_delay(0.0)
        pol = HedgePolicy(default_delay_s=0.25, min_samples=100)
        out = dispatch_hedged(fn, jnp.arange(4.0), hedge=pol)
        np.testing.assert_allclose(np.asarray(out), np.arange(4.0))
        assert audit.calls == 1 and pol.hedges == 0
        assert pol.unhedged == 1 and pol.n_samples == 1

    def test_backup_fn_used_for_the_hedge(self):
        slow, _ = faults.inject_delay(5.0)
        fast_calls = []

        def fast(x):
            fast_calls.append(1)
            return jnp.asarray(x) * 1.0

        out = dispatch_hedged(slow, jnp.arange(4.0), hedge=0.02,
                              backup_fn=fast)
        np.testing.assert_allclose(np.asarray(out), np.arange(4.0))
        assert fast_calls == [1]

    def test_deadline_bounds_both_dispatches(self):
        fn, audit = faults.inject_delay(5.0)   # every call straggles
        with pytest.raises(errors.RaftTimeoutError):
            dispatch_hedged(fn, jnp.arange(4.0), hedge=0.02,
                            timeout_s=0.15)
        assert audit.calls == 2                # it DID hedge, then gave up

    def test_policy_percentile_adapts(self):
        pol = HedgePolicy(percentile=50.0, min_samples=2,
                          min_delay_s=0.0, max_delay_s=9.0)
        assert pol.hedge_delay_s() == pol.default_delay_s  # cold
        for s in (0.1, 0.2, 0.3):
            pol.record(s)
        assert abs(pol.hedge_delay_s() - 0.2) < 1e-9
        clamped = HedgePolicy(percentile=50.0, min_samples=1,
                              min_delay_s=0.5, max_delay_s=1.0)
        clamped.record(0.01)
        assert clamped.hedge_delay_s() == 0.5

    def test_policy_validation(self):
        with pytest.raises(ValueError):
            HedgePolicy(percentile=0.0)
        with pytest.raises(ValueError):
            HedgePolicy(min_delay_s=2.0, max_delay_s=1.0)

    def test_inject_straggler_schedule(self):
        calls = []

        def f(x):
            calls.append(x)
            return x

        wrapped, audit = faults.inject_straggler(f, every=3, seconds=0.01)
        outs = [wrapped(i) for i in range(6)]
        assert audit.calls == 6 and audit.dispatches == 2
        assert isinstance(outs[2], faults.DelayedReady)
        assert isinstance(outs[5], faults.DelayedReady)
        assert not isinstance(outs[0], faults.DelayedReady)


# ---------------------------------------------------------------------------
# Admission control (resilience/admission.py) — shed, never collapse
# ---------------------------------------------------------------------------


class TestAdmissionControl:
    def test_sheds_when_queue_full(self):
        ctrl = AdmissionController(max_concurrent=1, max_queue=0,
                                   retry_after_s=0.5)
        with ctrl.admit():
            with pytest.raises(errors.RaftOverloadError) as ei:
                with ctrl.admit():
                    pass  # pragma: no cover
            assert ei.value.retry_after_s == 0.5
            assert not isinstance(ei.value, ValueError)  # loud, typed
        st = ctrl.stats()
        assert st.admitted == 1 and st.shed_queue == 1
        assert st.shed == 1 and st.offered == 2
        assert abs(st.shed_fraction - 0.5) < 1e-9

    def test_queued_request_admitted_when_slot_frees(self):
        ctrl = AdmissionController(max_concurrent=1, max_queue=2)
        release = threading.Event()
        admitted = threading.Event()

        def holder():
            with ctrl.admit():
                admitted.set()
                release.wait(5.0)

        th = threading.Thread(target=holder)
        th.start()
        admitted.wait(5.0)
        got = []

        def waiter():
            with ctrl.admit(timeout_s=5.0):
                got.append(1)

        tw = threading.Thread(target=waiter)
        tw.start()
        time.sleep(0.05)
        assert ctrl.queue_depth == 1 and not got
        release.set()
        tw.join(5.0)
        th.join(5.0)
        assert got == [1]
        st = ctrl.stats()
        assert st.admitted == 2 and st.shed == 0
        assert st.peak_queue_depth == 1 and st.queue_depth == 0

    def test_unbounded_deadline_waits_instead_of_overflowing(self):
        """Deadline.unbounded()/after(None) through admit() must mean
        'wait forever', not Condition.wait(inf) -> OverflowError."""
        ctrl = AdmissionController(max_concurrent=1, max_queue=2)
        release = threading.Event()
        admitted = threading.Event()

        def holder():
            with ctrl.admit():
                admitted.set()
                release.wait(5.0)

        th = threading.Thread(target=holder)
        th.start()
        admitted.wait(5.0)
        got = []

        def waiter():
            with ctrl.admit(deadline=Deadline.unbounded()):
                got.append(1)

        tw = threading.Thread(target=waiter)
        tw.start()
        time.sleep(0.05)
        assert not got and ctrl.queue_depth == 1  # queued, not crashed
        release.set()
        tw.join(5.0)
        th.join(5.0)
        assert got == [1]

    def test_timeout_while_queued_is_timeout_not_overload(self):
        ctrl = AdmissionController(max_concurrent=1, max_queue=2)
        release = threading.Event()

        def holder():
            with ctrl.admit():
                release.wait(5.0)

        th = threading.Thread(target=holder)
        th.start()
        time.sleep(0.05)
        with pytest.raises(errors.RaftTimeoutError):
            with ctrl.admit(timeout_s=0.05):
                pass  # pragma: no cover
        release.set()
        th.join(5.0)
        assert ctrl.stats().timed_out == 1

    def test_token_limiter_deterministic_clock(self):
        t = [0.0]
        ctrl = AdmissionController(max_concurrent=4, max_queue=4,
                                   rate=2.0, burst=2, clock=lambda: t[0])
        with ctrl.admit():
            pass
        with ctrl.admit():
            pass
        with pytest.raises(errors.RaftOverloadError) as ei:
            with ctrl.admit():
                pass  # pragma: no cover
        assert 0.0 < ei.value.retry_after_s <= 0.5  # next token at rate 2/s
        t[0] = 0.6                                  # refill > 1 token
        with ctrl.admit():
            pass
        st = ctrl.stats()
        assert st.shed_rate == 1 and st.admitted == 3

    def test_retry_after_priced_from_measured_service(self):
        ctrl = AdmissionController(max_concurrent=1, max_queue=0)
        with ctrl.admit():
            time.sleep(0.05)             # measurable service time
        with ctrl.admit():               # in flight again
            with pytest.raises(errors.RaftOverloadError) as ei:
                with ctrl.admit():
                    pass  # pragma: no cover
        assert ei.value.retry_after_s is not None
        assert ei.value.retry_after_s > 0.0

    def test_retry_after_occupancy_floors_stale_ewma(self):
        """ISSUE 8 satellite regression: the service-time EWMA only
        moves on COMPLETIONS, so a burst after an idle stretch used to
        price retry_after_s from stale history while the in-flight
        occupancy already showed service had slowed. The age of the
        oldest in-flight request must floor the estimate (injectable
        clock, fully deterministic)."""
        t = [0.0]
        ctrl = AdmissionController(max_concurrent=1, max_queue=1,
                                   clock=lambda: t[0])
        # one fast completion seeds a tiny (soon stale) EWMA
        with ctrl.admit():
            t[0] += 0.001
        # a request enters service... and runs for 10 s (the regression
        # scenario: service slowed, nothing has completed since)
        ctrl.enqueue()
        ticket = ctrl.begin_service()
        t[0] += 10.0
        ctrl.enqueue()                        # fills the queue (1/1)
        with pytest.raises(errors.RaftOverloadError) as ei:
            ctrl.enqueue()                    # burst arrival: shed
        # priced from the 10 s occupancy evidence, NOT the 1 ms EWMA:
        # (1 waiter + 1 in flight) * max(ewma, oldest in-flight age)
        assert ei.value.retry_after_s == pytest.approx(20.0)
        # completion folds the observed slow service into the EWMA
        ctrl.finish_service(ticket)
        assert ctrl._service_ewma_s == pytest.approx(
            0.8 * 0.001 + 0.2 * 10.0
        )
        st = ctrl.stats()
        assert st.in_flight == 0 and st.queue_depth == 1
        ctrl.cancel_queued()
        assert ctrl.stats().queue_depth == 0

    def test_async_triple_counters_and_shed(self):
        """The executor's non-blocking path: enqueue never waits,
        begin/finish move the gauges, sheds beyond the TOTAL capacity
        (queued + in service vs max_queue + max_concurrent)."""
        ctrl = AdmissionController(max_concurrent=2, max_queue=0)
        ctrl.enqueue()
        ctrl.enqueue()
        with pytest.raises(errors.RaftOverloadError):
            ctrl.enqueue()                    # 2 outstanding == capacity
        tk = ctrl.begin_service(2)            # one micro-batch of 2
        st = ctrl.stats()
        assert st.queue_depth == 0 and st.in_flight == 2
        assert st.admitted == 2 and st.shed_queue == 1
        with pytest.raises(errors.RaftOverloadError):
            ctrl.enqueue()                    # in-service still counts
        ctrl.finish_service(tk)
        ctrl.enqueue()                        # capacity freed
        ctrl.cancel_queued()
        st = ctrl.stats()
        assert st.in_flight == 0 and st.completed == 2
        with pytest.raises(ValueError):
            ctrl.begin_service(1)             # nothing queued

    def test_enqueue_idle_default_controller_admits(self):
        """A default controller (max_concurrent=1, max_queue=0) on an
        IDLE server must admit the async path's first request — the
        bound is total capacity, not raw queue depth (a free slot would
        have absorbed the request immediately in the blocking world)."""
        ctrl = AdmissionController()
        ctrl.enqueue()                        # no shed
        tk = ctrl.begin_service()
        with pytest.raises(errors.RaftOverloadError):
            ctrl.enqueue()                    # now genuinely full
        ctrl.finish_service(tk)
        ctrl.enqueue()                        # and free again
        ctrl.cancel_queued()

    def test_occupancy_floor_amortized_over_batch_ticket(self):
        """A service ticket covers a whole micro-batch: the occupancy
        floor must price PER REQUEST (batch age / n), not charge every
        queued request the full batch age (injectable clock)."""
        t = [0.0]
        ctrl = AdmissionController(max_concurrent=8, max_queue=8,
                                   clock=lambda: t[0])
        for _ in range(4):
            ctrl.enqueue()
        ctrl.begin_service(4)                 # one batch of 4
        t[0] += 0.08                          # in service 80 ms
        for _ in range(12):
            ctrl.enqueue()                    # fills capacity (16)
        with pytest.raises(errors.RaftOverloadError) as ei:
            ctrl.enqueue()
        # (12 waiters + 4 in flight) * (0.08 / 4) per request — NOT
        # * 0.08, which would price a ~0.16 s backlog at 1.28 s
        assert ei.value.retry_after_s == pytest.approx(16 * 0.02)

    def test_abort_service_frees_slot_without_ewma_or_completed(self):
        """A crashed dispatch releases its slot but is NOT service
        evidence: the near-zero held time must not drag the EWMA toward
        0 (underpricing every later shed) and its failed requests must
        not count as completed (injectable clock)."""
        t = [0.0]
        ctrl = AdmissionController(max_concurrent=2, max_queue=4,
                                   clock=lambda: t[0])
        # a real completion seeds the EWMA at 2 s
        ctrl.enqueue()
        tk = ctrl.begin_service()
        t[0] += 2.0
        ctrl.finish_service(tk)
        assert ctrl._service_ewma_s == pytest.approx(2.0)
        # a dispatch that fails immediately aborts its ticket
        ctrl.enqueue()
        tk2 = ctrl.begin_service()
        ctrl.abort_service(tk2)
        st = ctrl.stats()
        assert st.in_flight == 0 and st.completed == 1
        assert ctrl._service_ewma_s == pytest.approx(2.0)  # untouched

    def test_validation(self):
        with pytest.raises(ValueError):
            AdmissionController(max_concurrent=0)
        with pytest.raises(ValueError):
            AdmissionController(max_queue=-1)
        with pytest.raises(ValueError):
            AdmissionController(rate=0.0)


# ---------------------------------------------------------------------------
# HealthReport → ShardHealth pipeline (apply_report)
# ---------------------------------------------------------------------------


class TestApplyReport:
    def test_rank_attributed_failures_down_exactly_those(self):
        h = ShardHealth(8)
        report = HealthReport(probes={
            "heartbeat@2": HealthProbe(ok=False, seconds=0.1, ranks=(2,)),
            "heartbeat@5": HealthProbe(ok=False, seconds=0.1, ranks=(5,)),
            "allreduce": HealthProbe(ok=True, seconds=0.1),
        })
        out = h.apply_report(report)
        assert out is h                   # chainable: one-call pipeline
        np.testing.assert_array_equal(h.mask(), [1, 1, 0, 1, 1, 0, 1, 1])

    def test_unattributed_failure_downs_everything(self):
        h = ShardHealth(4)
        h.apply_report(HealthReport(probes={
            "allgather": HealthProbe(ok=False, seconds=0.1),
        }))
        assert h.n_up == 0

    def test_passing_report_marks_nothing(self):
        h = ShardHealth(4)
        h.mark_down(1)
        h.apply_report(HealthReport(probes={
            "allreduce": HealthProbe(ok=True, seconds=0.1),
        }))
        assert h.n_up == 3 and not h.is_up(1)  # no auto mark_up

    def test_resolve_shard_mask_accepts_report(self):
        from raft_tpu.resilience import resolve_shard_mask

        report = HealthReport(probes={
            "hb": HealthProbe(ok=False, seconds=0.0, ranks=(0, 3)),
        })
        np.testing.assert_array_equal(
            resolve_shard_mask(report, 4), [0, 1, 1, 0]
        )


# ---------------------------------------------------------------------------
# Checkpoint integrity (format v2) + mesh-size recovery
# ---------------------------------------------------------------------------


@pytest.fixture()
def small_index():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((256, 8)).astype(np.float32)
    return ivf_flat_build(
        x, IVFFlatParams(n_lists=4, kmeans_n_iters=3, seed=1)
    )


def test_roundtrip_carries_manifest(small_index, tmp_path):
    p = tmp_path / "idx.npz"
    save_index(small_index, p)
    with np.load(p) as npz:
        header = json.loads(bytes(npz["__header__"]).decode("utf-8"))
    # no coarse quantizer attached -> the writer stamps the LOWEST
    # version that represents the payload (older readers keep working)
    assert header["version"] == 2
    man = header["integrity"]
    assert "data_sorted" in man and "centroids" in man
    for entry in man.values():
        assert set(entry) == {"crc32", "shape", "dtype"}
    idx2 = load_index(p)
    np.testing.assert_allclose(
        np.asarray(idx2.centroids), np.asarray(small_index.centroids)
    )


def test_corrupt_bytes_names_the_field(small_index, tmp_path):
    """THE integrity acceptance: silent payload damage (container CRCs
    rewritten to match) is caught by the manifest and names the field."""
    p = tmp_path / "idx.npz"
    save_index(small_index, p)
    damaged = faults.corrupt_bytes(p, field="data_sorted", seed=3)
    assert damaged == "data_sorted"
    with pytest.raises(errors.CorruptIndexError, match="data_sorted") as ei:
        load_index(p)
    assert ei.value.field == "data_sorted"
    assert not isinstance(ei.value, ValueError)  # loud, not absorbable


def test_corrupt_bytes_random_field_deterministic(small_index, tmp_path):
    p = tmp_path / "idx.npz"
    save_index(small_index, p)
    damaged = faults.corrupt_bytes(p, seed=12)
    with pytest.raises(errors.CorruptIndexError) as ei:
        load_index(p)
    assert ei.value.field == damaged


def test_corrupt_header_caught(small_index, tmp_path):
    p = tmp_path / "idx.npz"
    save_index(small_index, p)
    raw = bytearray(p.read_bytes())
    raw[: len(raw) // 2] = os.urandom(len(raw) // 2)
    p.write_bytes(bytes(raw))
    with pytest.raises(errors.CorruptIndexError):
        load_index(p)


def test_v1_file_still_loads(small_index, tmp_path):
    """Read-compat: a pre-manifest (v1) checkpoint loads unverified."""
    from raft_tpu.spatial.ann import serialize

    arrays, static = {}, {}
    serialize._flatten(small_index, "", arrays, static)
    header = {"type": "ivf_flat", "version": 1, "static": static}
    p = tmp_path / "v1.npz"
    with open(p, "wb") as f:
        np.savez(
            f,
            __header__=np.frombuffer(
                json.dumps(header).encode("utf-8"), dtype=np.uint8
            ),
            **arrays,
        )
    idx = load_index(p)
    np.testing.assert_allclose(
        np.asarray(idx.centroids), np.asarray(small_index.centroids)
    )


def test_future_version_rejected(small_index, tmp_path):
    from raft_tpu.spatial.ann import serialize

    arrays, static = {}, {}
    serialize._flatten(small_index, "", arrays, static)
    header = {"type": "ivf_flat", "version": 99, "static": static}
    p = tmp_path / "v99.npz"
    with open(p, "wb") as f:
        np.savez(
            f,
            __header__=np.frombuffer(
                json.dumps(header).encode("utf-8"), dtype=np.uint8
            ),
            **arrays,
        )
    # ISSUE 7 satellite: a structured, version-NAMING rejection (a
    # CorruptIndexError, deliberately NOT a ValueError) — a rolled-back
    # reader must fail loudly instead of filling a newer checkpoint's
    # unknown fields from missing-key defaults
    with pytest.raises(errors.CorruptIndexError, match="99"):
        load_index(p)


def test_restore_onto_smaller_mesh(comms8, dataset, flat_index, tmp_path):
    """THE recovery acceptance: a sharded checkpoint built for 8 ranks
    restores onto a 4-rank mesh (a lost rank pair) through the
    place_index re-shard path with identical search results."""
    x, q = dataset
    v8, i8 = mnmg_ivf_flat_search(
        comms8, flat_index, q, K, n_probes=8, qcap=q.shape[0]
    )
    p = tmp_path / "sharded.npz"
    save_index(flat_index, p)
    comms4 = build_comms(jax.devices()[:4])
    idx4 = load_index(p, comms=comms4)  # mismatch -> host load + re-shard
    assert idx4.sorted_ids.shape[0] == 4
    v4, i4 = mnmg_ivf_flat_search(
        comms4, idx4, q, K, n_probes=8, qcap=q.shape[0]
    )
    np.testing.assert_allclose(np.asarray(v4), np.asarray(v8), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(i4), np.asarray(i8))
    # reshard preserves the content inventory exactly
    all8 = np.sort(
        np.concatenate([_rank_row_ids(flat_index, r) for r in range(8)])
    )
    all4 = np.sort(
        np.concatenate([_rank_row_ids(idx4, r) for r in range(4)])
    )
    np.testing.assert_array_equal(all8, all4)


def test_place_index_reshards_directly(comms8, dataset, flat_index):
    _, q = dataset
    comms2 = build_comms(jax.devices()[:2])
    idx2 = place_index(comms2, flat_index)
    assert idx2.sorted_ids.shape[0] == 2
    v8, i8 = mnmg_ivf_flat_search(
        comms8, flat_index, q, K, n_probes=8, qcap=q.shape[0]
    )
    v2, i2 = mnmg_ivf_flat_search(
        comms2, idx2, q, K, n_probes=8, qcap=q.shape[0]
    )
    np.testing.assert_array_equal(np.asarray(i2), np.asarray(i8))


def test_reshard_keeps_the_builds_slab_height(comms8):
    """Build and reshard size the slab by one rule: a reshard onto a mesh
    of the build's size gives the build's statics, so it compiles no
    new search program. One list a rank, so a rank's load (its list) and
    the placement's bound (the mean load plus the largest list) round to
    different slab heights."""
    x = np.random.default_rng(11).standard_normal((1600, 16)).astype(
        np.float32)
    built = mnmg_ivf_flat_build(comms8, x, FLAT_PARAMS)
    same = reshard_index(comms8, built)
    assert (same.n_pad, same.nl_pad, same.max_list) == (
        built.n_pad, built.nl_pad, built.max_list)


def test_reshard_rejects_ownerless_index(comms8, flat_index):
    import dataclasses as dc

    bad = dc.replace(
        flat_index,
        owner=jnp.full_like(jnp.asarray(flat_index.owner), -1),
    )
    comms2 = build_comms(jax.devices()[:2])
    with pytest.raises(ValueError, match="owns no lists"):
        reshard_index(comms2, bad)
