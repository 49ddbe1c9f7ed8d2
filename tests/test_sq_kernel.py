"""int8 IVF-SQ Pallas dequant+scan engine (spatial/ann/sq_kernel) and
the shared scan-kernel core (spatial/ann/scan_core) — tier-1 coverage
(ISSUE 11).

The kernel runs under ``interpret=True`` on the CPU test platform,
pinned bitwise against its op-for-op lax mirror; the grouped SQ search's
``use_pallas=True`` path is then pinned against the XLA dequant scan.
Bit-identity between engines is asserted on DYADIC-EXACT fixtures:
``vscale`` a power of two (here exactly 1) and integer ``vmin`` make
every dequantized value a bf16-exact integer, so with a SATURATED rerank
pool both engines exact-score the same candidate set in f32 and
``(dists, ids)`` must match to the bit — the contract the sq_kernel
module docstring pins. The shared-planner property tests cover the ONE
``scan_core.plan_l_tile`` all three engines hand their byte models to
(the ISSUE 11 acceptance: one planner, duplicated copies deleted), the
``pad_queries`` rounding authority, and the ``tile_profile`` latency
plan. MNMG parity and the zero-retrace health-flip audit run inside the
fused one-dispatch program with the SQ kernel engaged.
"""

import dataclasses
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from raft_tpu.spatial.ann import (
    IVFFlatParams, IVFSQParams, ivf_flat_build,
)
from raft_tpu.spatial.ann import flat_kernel, pq_kernel, scan_core, sq_kernel
from tests.oracles import assert_knn_equal_up_to_ties
from raft_tpu.spatial.ann.ivf_sq import (
    IVFSQIndex,
    _resolve_sq_engine,
    ivf_sq_search,
    ivf_sq_search_grouped,
)

K_NN = 5


# -- the shared planner: one authority, three byte models --------------------

def test_plan_l_tile_shrinks_monotone_under_budget():
    """The 512->128 halving ladder: as the query block grows, the ONE
    shared planner's tile only ever SHRINKS, through lane-aligned steps,
    to None once even a 128-row tile exceeds the VMEM budget — for every
    engine's byte model (the planner is shared; the byte models are what
    differ)."""
    plans = {
        "flat": lambda qp: flat_kernel.plan_l_tile(96, qp),
        "sq": lambda qp: sq_kernel.plan_l_tile(96, qp),
        "pq": lambda qp: pq_kernel.plan_l_tile(12 * 256, qp),
    }
    for name, plan in plans.items():
        prev = 513
        for q_pad in (16, 64, 256, 1024, 4096, 1 << 15, 1 << 18, 1 << 21):
            lt = plan(q_pad)
            if lt is None:
                # None is terminal: every larger block must also fail
                assert plan(2 * q_pad) is None, name
                break
            assert lt % scan_core.LANE == 0, name
            assert lt <= min(prev, 512), (name, q_pad, lt, prev)
            prev = lt
        else:
            pytest.fail(f"{name}: planner never exhausted its budget")
    # the int8 model is leaner than the bf16 flat model at equal
    # geometry (that IS the footprint win): its plan is never narrower
    for q_pad in (16, 256, 4096):
        f, s = plans["flat"](q_pad), plans["sq"](q_pad)
        if f is not None:
            assert s is not None and s >= f


def test_pad_queries_is_the_one_rounding_authority():
    """Every engine re-exports scan_core.pad_queries — the bf16-sublane
    rounding a resolver approves and a serving plan then replays."""
    assert flat_kernel.pad_queries is scan_core.pad_queries
    assert sq_kernel.pad_queries is scan_core.pad_queries
    for qcap, want in ((1, 16), (8, 16), (16, 16), (17, 32), (48, 48)):
        assert scan_core.pad_queries(qcap) == want
        assert scan_core.pad_queries(qcap) % scan_core.Q_GRANULE == 0


def test_supported_predicates_agree_with_their_plans():
    """Each engine's *_supported predicate must equal "the shared
    planner approves this geometry under the profile the grouped path
    would auto-select" — approval and plan can never round differently
    (they share pad_queries/tile_profile calls by construction)."""
    for d, qcap in ((8, 1), (96, 8), (96, 48), (768, 512), (1 << 20, 64)):
        prof = scan_core.tile_profile(qcap)
        qp = scan_core.pad_queries(qcap)
        assert flat_kernel.flat_scan_supported(d, qcap) == (
            flat_kernel.plan_l_tile(d, qp, profile=prof) is not None
        )
        assert sq_kernel.sq_scan_supported(d, qcap) == (
            sq_kernel.plan_l_tile(d, qp, profile=prof) is not None
        )
    for bits in (4, 8):
        for qcap in (8, 48, 512):
            mk = 12 * (1 << bits)
            prof = scan_core.tile_profile(qcap)
            assert pq_kernel.pq_adc_supported(12, bits, qcap) == (
                pq_kernel.plan_l_tile(
                    mk, scan_core.pad_queries(qcap), profile=prof
                ) is not None
            )
    assert not sq_kernel.sq_scan_supported(0, 8)


def test_latency_profile_widens_small_qcap_tiles():
    """tile_profile: qcap <= 8 (the open-loop serving buckets) selects
    the latency plan, whose start width is 1024 — a tiny query block
    leaves the budget nearly untouched, so the plan holds the doubled
    tile and the grid-step count halves exactly where p99 lives."""
    assert scan_core.tile_profile(1) == "latency"
    assert scan_core.tile_profile(8) == "latency"
    assert scan_core.tile_profile(9) == "throughput"
    lt_thr = sq_kernel.plan_l_tile(96, 16, profile="throughput")
    lt_lat = sq_kernel.plan_l_tile(96, 16, profile="latency")
    assert lt_thr == 512 and lt_lat == 1024
    assert flat_kernel.plan_l_tile(96, 16, profile="latency") == 1024
    # the latency plan still shrinks under pressure — profile changes
    # the START, never the budget
    wide = sq_kernel.plan_l_tile(1 << 14, 16, profile="latency")
    assert wide is None or wide <= 1024


# -- the SQ kernel vs its lax mirror -----------------------------------------

def _sq_case(rng, lb, q, d, l_pad, dyadic=True):
    qrows = jnp.asarray(
        rng.integers(-64, 64, (lb, q, d)), jnp.float32
    )
    codes_t = jnp.asarray(
        rng.integers(-128, 128, (lb, d, l_pad)), jnp.int8
    )
    if dyadic:
        vmin = jnp.asarray(rng.integers(-8, 8, (d,)), jnp.float32)
        vscale = jnp.full((d,), 0.5, jnp.float32)
    else:
        vmin = jnp.asarray(rng.standard_normal(d), jnp.float32)
        vscale = jnp.asarray(
            np.abs(rng.standard_normal(d)) / 255.0 + 1e-3, jnp.float32
        )
    return qrows, codes_t, vmin, vscale


@pytest.mark.parametrize(
    "lb,q,d,l_pad,l_tile,dyadic",
    [
        (3, 32, 16, 256, 128, True),    # two slab tiles per list
        (2, 16, 24, 128, 128, False),   # generic affine stats
        (1, 48, 8, 512, 256, True),     # wider tiles
    ],
)
def test_sq_kernel_matches_lax_mirror_bitwise(rng_np, lb, q, d, l_pad,
                                              l_tile, dyadic):
    """Interpret-mode kernel == lax mirror, bit for bit, masked rows
    included — generic (non-dyadic) affine stats too: the mirror shares
    the kernel's exact dequant spelling (_dequant_tile), so the pin
    holds regardless of rounding."""
    qrows, codes_t, vmin, vscale = _sq_case(rng_np, lb, q, d, l_pad,
                                            dyadic)
    bounds = jnp.asarray(
        [[i, max(i, l_pad - 7 * i)] for i in range(lb)], jnp.int32
    )
    got = sq_kernel.sq_scan_subchunk_min(
        qrows, codes_t, bounds, vmin, vscale,
        interpret=True, l_tile=l_tile,
    )
    ref = sq_kernel.sq_scan_subchunk_min_lax(
        qrows, codes_t, bounds, vmin, vscale
    )
    assert got.shape == (lb, q, l_pad // scan_core.SUBCHUNK)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_sq_kernel_oracle_and_masking(rng_np):
    """Dequantized-vector distances against a float oracle on a
    bf16-EXACT fixture (y = code/2 — every value fits bf16's 8
    significand bits, so the in-kernel rounding is the identity and
    the oracle comparison is exact); lo == hi (empty list) -> every
    sub-chunk min is BIG."""
    qrows, codes_t, _, _ = _sq_case(rng_np, 2, 16, 16, 256)
    vmin = jnp.full((16,), -64.0, jnp.float32)
    vscale = jnp.full((16,), 0.5, jnp.float32)
    bounds = jnp.asarray([[5, 5], [0, 256]], jnp.int32)
    got = np.asarray(sq_kernel.sq_scan_subchunk_min(
        qrows, codes_t, bounds, vmin, vscale, interpret=True, l_tile=128
    ))
    assert (got[0] == scan_core.BIG).all()
    assert (got[1] < scan_core.BIG).all()
    # oracle over list 1 (full range): dyadic dequant is exact in f32
    y = (np.asarray(codes_t[1], np.float32) + 128.0) \
        * np.asarray(vscale)[:, None] + np.asarray(vmin)[:, None]
    qv = np.asarray(qrows[1], np.float32)
    d2 = (qv ** 2).sum(1)[:, None] + (y ** 2).sum(0)[None, :] \
        - 2.0 * (qv @ y)
    ref = d2.reshape(16, -1, scan_core.SUBCHUNK).min(-1)
    np.testing.assert_allclose(got[1], ref, rtol=1e-5, atol=1e-3)


def test_sq_kernel_validates_shapes_and_dtype():
    with pytest.raises(ValueError, match="int8"):
        sq_kernel.sq_scan_subchunk_min(
            jnp.zeros((1, 16, 16), jnp.float32),
            jnp.zeros((1, 16, 128), jnp.uint8),     # wrong dtype
            jnp.zeros((1, 2), jnp.int32),
            jnp.zeros((16,)), jnp.ones((16,)), interpret=True,
        )
    with pytest.raises(ValueError, match="dim"):
        sq_kernel.sq_scan_subchunk_min(
            jnp.zeros((1, 16, 16), jnp.float32),
            jnp.zeros((1, 24, 128), jnp.int8),      # slab dim mismatch
            jnp.zeros((1, 2), jnp.int32),
            jnp.zeros((24,)), jnp.ones((24,)), interpret=True,
        )
    with pytest.raises(ValueError, match="multiple"):
        sq_kernel.sq_scan_subchunk_min(
            jnp.zeros((1, 8, 16), jnp.float32),     # Q=8 not 16-aligned
            jnp.zeros((1, 16, 128), jnp.int8),
            jnp.zeros((1, 2), jnp.int32),
            jnp.zeros((16,)), jnp.ones((16,)), interpret=True,
        )


# -- grouped search: engine equivalence --------------------------------------

def _int_sq_index(x_int, n_lists=48):
    """A dyadic-exact SQ index: codes ARE the integer rows (vmin=-128,
    vscale=1 -> y = code), so every dequantized value is a bf16-exact
    integer and saturated-pool engine comparisons are BIT-identical
    (the sq_kernel docstring contract)."""
    d = x_int.shape[1]
    base = ivf_flat_build(x_int, IVFFlatParams(
        n_lists=n_lists, kmeans_n_iters=4, kmeans_init="random",
    ), metric="sqeuclidean")
    return IVFSQIndex(
        centroids=base.centroids,
        codes_sorted=base.data_sorted.astype(jnp.int8),
        vmin=jnp.full((d,), -128.0, jnp.float32),
        vscale=jnp.ones((d,), jnp.float32),
        storage=base.storage,
    )


def _int_dataset(seed, n=3000, d=16, nq=64):
    rng = np.random.default_rng(seed)
    centers = rng.integers(-60, 60, (8, d))
    x = (
        centers[rng.integers(0, 8, n)]
        + rng.integers(-6, 7, (n, d))
    ).clip(-127, 127).astype(np.float32)
    q = (
        x[rng.integers(0, n, nq)] + rng.integers(-2, 3, (nq, d))
    ).astype(np.float32)
    return x, q


@pytest.fixture(scope="module")
def dataset():
    return _int_dataset(7)


@pytest.fixture(scope="module")
def sq_index(dataset):
    x, _ = dataset
    return _int_sq_index(x)


def _saturating_ratio(index, p, k):
    l_tile = sq_kernel.plan_l_tile(index.centroids.shape[1], 64)
    l_pad = -(-index.storage.max_list // l_tile) * l_tile
    return float(p * l_pad // scan_core.SUBCHUNK) / k + 1.0


@pytest.mark.parametrize("stream", [None, True])
def test_sq_saturated_pool_bit_identical_single_chip(dataset, sq_index,
                                                     stream):
    """With the rerank pool covering every probed row, BOTH engines
    exact-score the same f32-dequantized candidate set — on the dyadic
    fixture the returned (dists, ids) must match to the bit."""
    x, q = dataset
    p = 4
    kw = dict(n_probes=p, qcap=64, stream_partials=stream,
              rerank_ratio=_saturating_ratio(sq_index, p, K_NN))
    d0, i0 = ivf_sq_search_grouped(sq_index, q, K_NN,
                                   use_pallas=False, **kw)
    d1, i1 = ivf_sq_search_grouped(sq_index, q, K_NN,
                                   use_pallas=True, **kw)
    assert_knn_equal_up_to_ties(x, q, d0, i0, d1, i1)


def test_sq_grouped_matches_per_query_search(dataset, sq_index):
    """The grouped SQ search (XLA and kernel engines) agrees with the
    per-query SQ search at full probe width on the dyadic fixture —
    three spellings of one exact computation."""
    x, q = dataset
    nl = sq_index.centroids.shape[0]
    d0, i0 = ivf_sq_search(sq_index, q, K_NN, n_probes=nl)
    kw = dict(n_probes=nl, qcap=q.shape[0],
              rerank_ratio=_saturating_ratio(sq_index, nl, K_NN))
    for up in (False, True):
        d1, i1 = ivf_sq_search_grouped(sq_index, q, K_NN,
                                       use_pallas=up, **kw)
        assert_knn_equal_up_to_ties(x, q, d0, i0, d1, i1)


def test_sq_kernel_recall_non_inferior(dataset, sq_index):
    """At a modest rerank_ratio the top-c sub-chunks cover the top-c
    rows of the bf16 scan (the 8-row cover argument over dequantized
    values), so kernel-path recall must not fall below the XLA dequant
    engine's beyond bf16 boundary noise."""
    from tests.oracles import np_knn_ids

    x, q = dataset
    true = np_knn_ids(x, np.asarray(q), K_NN)

    def rec(ids):
        g = np.asarray(ids)
        return sum(
            len(set(a.tolist()) & set(b.tolist()))
            for a, b in zip(g, true)
        ) / true.size

    kw = dict(n_probes=4, qcap=64, rerank_ratio=4.0)
    r_pal = rec(ivf_sq_search_grouped(sq_index, q, K_NN,
                                      use_pallas=True, **kw)[1])
    r_xla = rec(ivf_sq_search_grouped(sq_index, q, K_NN,
                                      use_pallas=False, **kw)[1])
    assert r_pal >= r_xla - 0.01, (r_pal, r_xla)


def test_sq_use_pallas_true_names_planner_requirement(dataset, sq_index):
    """Explicit opt-in must not silently fall back — and the message
    now names the unmet PLANNER requirement (the ISSUE 11 satellite:
    the pre-r11 message claimed no kernel path exists at all)."""
    x, q = dataset
    with pytest.raises(Exception) as ei:
        _resolve_sq_engine(True, 1 << 20, 512)
    msg = str(ei.value)
    assert "sq_scan_supported" in msg and "plan_l_tile" in msg
    assert "VMEM" in msg
    # k > max_list routes to the per-query search (no kernel path)
    with pytest.raises(Exception, match="per-query"):
        ivf_sq_search_grouped(
            sq_index, q, sq_index.storage.max_list + 1,
            n_probes=4, use_pallas=True,
        )


def test_resolve_sq_engine_auto_off_tpu():
    assert jax.default_backend() != "tpu"
    assert _resolve_sq_engine(None, 96, 48) is False
    assert _resolve_sq_engine(True, 96, 48) is True
    assert _resolve_sq_engine(False, 96, 48) is False


def test_cpu_default_never_imports_sq_kernel_module():
    """A fresh JAX_PLATFORMS=cpu process running default grouped SQ
    searches (plus warmup) must not import (let alone compile) the
    Pallas kernel modules — scan_core included."""
    prog = (
        "import os, sys\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "import numpy as np\n"
        "from raft_tpu.spatial.ann import IVFSQParams, ivf_sq_build\n"
        "from raft_tpu.spatial.ann.ivf_sq import "
        "ivf_sq_search_grouped\n"
        "rng = np.random.default_rng(0)\n"
        "x = rng.standard_normal((400, 8)).astype(np.float32)\n"
        "idx = ivf_sq_build(x, IVFSQParams(n_lists=8,\n"
        "    kmeans_n_iters=2))\n"
        "idx.warmup(8, k=3, n_probes=2)\n"
        "ivf_sq_search_grouped(idx, x[:8], 3, n_probes=2, qcap=8)\n"
        "for mod in ('sq_kernel', 'flat_kernel', 'scan_core'):\n"
        "    full = 'raft_tpu.spatial.ann.' + mod\n"
        "    assert full not in sys.modules, full + ' imported'\n"
        "print('OK')\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True,
        timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


# -- mutation tier: tombstones at the rerank tail ----------------------------

def test_sq_mutable_search_engine_parity_with_tombstones(dataset):
    """The SQ kernel path folds the mutation tier's row_mask at its
    exact rerank tail: on a small-list dyadic index both engines must
    return identical ids after upserts AND deletes, and no deleted id
    may ever surface."""
    from raft_tpu.spatial.ann.mutation import (
        delete, mutable_search, upsert, wrap_mutable,
    )

    x, q = dataset
    idx = _int_sq_index(x, n_lists=64)
    p = 2
    assert 4 * 10 * scan_core.SUBCHUNK >= p * idx.storage.max_list, \
        "fixture must saturate the default rerank pool"
    m = wrap_mutable(idx, delta_cap=32)
    assert m.engine == "sq"
    rng = np.random.default_rng(3)
    up_ids = jnp.asarray(rng.integers(0, x.shape[0], 8), jnp.int32)
    m, acc = upsert(m, jnp.asarray(x[np.asarray(up_ids)] + 1.0), up_ids)
    assert acc.all()
    dead = jnp.asarray(rng.integers(0, x.shape[0], 40), jnp.int32)
    m, _ = delete(m, dead)
    kw = dict(n_probes=p, qcap=64)
    d0, i0 = mutable_search(m, q, 10, use_pallas=False, **kw)
    d1, i1 = mutable_search(m, q, 10, use_pallas=True, **kw)
    x_live = x.copy()
    x_live[np.asarray(up_ids)] += 1.0
    assert_knn_equal_up_to_ties(x_live, q, d0, i0, d1, i1)
    alive_dead = set(np.asarray(dead).tolist()) - \
        set(np.asarray(up_ids).tolist())
    for ids in (i0, i1):
        got = set(np.asarray(ids).ravel().tolist())
        assert not (got & alive_dead), "deleted rows surfaced"


def test_sq_compact_requantizes_against_kept_stats(dataset):
    """Compaction folds deltas + tombstones into fresh int8 slabs
    against the KEPT affine stats (the PQ-codebook rule): surviving
    main rows round-trip losslessly, and the compacted state keeps
    serving through the same engine."""
    from raft_tpu.spatial.ann.mutation import (
        compact, mutable_search, upsert, wrap_mutable,
    )

    x, q = dataset
    idx = _int_sq_index(x, n_lists=32)
    m = wrap_mutable(idx, delta_cap=16)
    ids = jnp.asarray([1, 2, 3], jnp.int32)
    m, acc = upsert(m, jnp.asarray(x[1:4] + 2.0), ids)
    assert acc.all()
    m2, stats = compact(m)
    assert m2.engine == "sq"
    assert isinstance(m2.index, IVFSQIndex)
    assert m2.index.codes_sorted.dtype == jnp.int8
    d0, i0 = mutable_search(m, q, K_NN, n_probes=8, qcap=64)
    d1, i1 = mutable_search(m2, q, K_NN, n_probes=8, qcap=64)
    # dyadic integers survive the re-quantization round trip exactly,
    # so pre- and post-compaction searches agree on the dyadic fixture
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))


# -- MNMG: the fused one-dispatch program ------------------------------------

@pytest.fixture(scope="module")
def comms8():
    from raft_tpu.comms import build_comms

    return build_comms(jax.devices()[:8])


@pytest.fixture(scope="module")
def sharded_sq_index(dataset, comms8):
    from raft_tpu.comms import mnmg_ivf_sq_build

    x, _ = dataset
    idx = mnmg_ivf_sq_build(comms8, x, IVFSQParams(
        n_lists=32, kmeans_n_iters=4,
    ))
    # pin the dyadic contract on the sharded fixture too: codes stay,
    # the affine map becomes the identity-on-integers one
    d = x.shape[1]
    return dataclasses.replace(
        idx,
        vmin=jnp.full((d,), -128.0, jnp.float32),
        vscale=jnp.ones((d,), jnp.float32),
    )


def test_mnmg_sq_fused_program_engine_parity(dataset, comms8,
                                             sharded_sq_index):
    """The SQ kernel ACTIVE inside the MNMG fused one-dispatch program:
    saturated-pool results bit-identical to the XLA dequant engine's."""
    from raft_tpu.comms import mnmg_ivf_sq_search

    x, q = dataset
    p = 4
    l_tile = sq_kernel.plan_l_tile(x.shape[1], 64)
    l_pad = -(-int(sharded_sq_index.max_list) // l_tile) * l_tile
    rr = float(p * l_pad // scan_core.SUBCHUNK) / K_NN + 1.0
    kw = dict(n_probes=p, qcap=q.shape[0], rerank_ratio=rr)
    d0, i0 = mnmg_ivf_sq_search(comms8, sharded_sq_index, q, K_NN,
                                use_pallas=False, **kw)
    d1, i1 = mnmg_ivf_sq_search(comms8, sharded_sq_index, q, K_NN,
                                use_pallas=True, **kw)
    np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))


def test_mnmg_sq_health_flip_zero_retrace(
    dataset, comms8, sharded_sq_index, monkeypatch
):
    """The ISSUE 11 acceptance trace-audit with the SQ kernel engaged:
    use_pallas is a trace-time static, health/failover stay runtime
    inputs — shard_mask flips must reuse the ONE compiled fused
    program (zero retraces)."""
    from raft_tpu.comms import mnmg_ivf_flat as mod

    _, q = dataset
    created = []
    orig = mod._cached_search

    def recording(*a, **k):
        fn = orig(*a, **k)
        created.append(fn)
        return fn

    monkeypatch.setattr(mod, "_cached_search", recording)
    kw = dict(n_probes=4, qcap=q.shape[0], use_pallas=True)
    m_up = np.ones(8, np.int32)
    m_one = m_up.copy()
    m_one[3] = 0
    mod.mnmg_ivf_sq_search(comms8, sharded_sq_index, q, K_NN,
                           shard_mask=m_up, **kw)
    fn = created[0]
    size0 = fn._cache_size()
    for mask in (m_one, m_up):
        res = mod.mnmg_ivf_sq_search(comms8, sharded_sq_index, q, K_NN,
                                     shard_mask=mask, **kw)
    assert all(f is fn for f in created), \
        "health flips must reuse the cached program object"
    assert fn._cache_size() == size0, \
        "health flips must not retrace the compiled kernel program"
    assert float(jnp.min(res.coverage)) == 1.0


def test_mnmg_sq_index_places_and_serializes(dataset, comms8,
                                             sharded_sq_index, tmp_path):
    """The SQ index rides the shared placement/serialization machinery:
    save -> load -> place round-trips with identical search results."""
    from raft_tpu.comms import mnmg_ivf_sq_search, place_index
    from raft_tpu.spatial.ann import load_index, save_index

    x, q = dataset
    path = tmp_path / "sq.idx"
    save_index(sharded_sq_index, path)
    loaded = place_index(comms8, load_index(path))
    d0, i0 = mnmg_ivf_sq_search(comms8, sharded_sq_index, q, K_NN,
                                n_probes=4, qcap=48)
    d1, i1 = mnmg_ivf_sq_search(comms8, loaded, q, K_NN,
                                n_probes=4, qcap=48)
    np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))


def test_sq_compact_survivor_codes_verbatim_extreme_stats(dataset):
    """Survivor codes must ride compaction VERBATIM — a decode->
    re-encode round trip drifts a code unit once |vmin| dwarfs the
    dimension's range (f32 add/subtract of the offset is inexact), so
    the fold is pinned on adversarial stats: huge offset, tiny scale."""
    from raft_tpu.spatial.ann.mutation import compact, wrap_mutable

    x, _ = dataset
    d = x.shape[1]
    base = _int_sq_index(x, n_lists=32)
    idx = dataclasses.replace(
        base,
        vmin=jnp.full((d,), 2.0 ** 20, jnp.float32),
        vscale=jnp.full((d,), 2.0 ** -10, jnp.float32),
    )
    m = wrap_mutable(idx, delta_cap=8)
    m2, _ = compact(m)

    def by_id(index):
        sid = np.asarray(index.storage.sorted_ids)
        codes = np.asarray(index.codes_sorted)
        return {
            int(i): codes[pos].tobytes()
            for pos, i in enumerate(sid.tolist()) if i >= 0
        }

    assert by_id(m2.index) == by_id(idx), \
        "compaction rewrote untouched survivor codes"


def test_mnmg_sq_mutable_search_routes_to_sq_engine(dataset, comms8,
                                                    sharded_sq_index):
    """mnmg_mutable_search must dispatch an MnmgIVFSQIndex to the SQ
    fused program (the flat route would feed its None vectors_sorted
    into shard_map): upsert -> delete -> search through the mutation-
    tier variant, fresh row visible, deleted row never surfaces."""
    from raft_tpu.comms.mnmg_mutation import (
        mnmg_delete, mnmg_mutable_search, mnmg_upsert, wrap_mnmg_mutable,
    )

    x, q = dataset
    m = wrap_mnmg_mutable(comms8, sharded_sq_index, delta_cap=8)
    fresh = jnp.asarray(q[:1] * 0 + 3.0)          # a distinctive row
    m, acc = mnmg_upsert(comms8, m, fresh, jnp.asarray([7], jnp.int32))
    assert acc.all()
    m, found = mnmg_delete(comms8, m, jnp.asarray([11], jnp.int32))
    assert found.all()
    dv, iv = mnmg_mutable_search(
        comms8, m, fresh, 5, n_probes=4, qcap=8, use_pallas=True,
    )
    ids0 = np.asarray(iv)[0]
    assert 7 in ids0.tolist(), "upserted row must be visible"
    dall, iall = mnmg_mutable_search(
        comms8, m, jnp.asarray(q), 10, n_probes=8, qcap=q.shape[0],
    )
    assert 11 not in set(np.asarray(iall).ravel().tolist()), \
        "deleted row surfaced"
