"""Pallas flat-scan engine (spatial/ann/flat_kernel) — tier-1 coverage.

The kernel body runs under ``interpret=True`` on the CPU test platform
(the tests/test_pq_kernel.py pattern), pinned bitwise against the
op-for-op lax mirror and a float oracle; the grouped flat searches'
``use_pallas=True`` path is then pinned against the legacy XLA scan.
Bit-identity between engines is asserted on INTEGER-EXACT inputs with a
SATURATED rerank pool: every f32 accumulation is then exact regardless
of order (the kernel's different rerank accumulation shape cannot
perturb values) and the pool covers every probed row (the bf16 scan
cannot perturb candidate selection), so ``(dists, ids)`` must match to
the bit — the contract flat_kernel's module docstring pins. Elsewhere
the sub-chunk cover argument guarantees recall non-inferiority only,
asserted separately. MNMG parity runs inside the fused one-dispatch
program with a zero-retrace health-flip audit, and the mutation tier's
tombstone ``row_mask`` is pinned at the kernel path's rerank tail.
"""

import dataclasses
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from raft_tpu.spatial.ann import (
    IVFFlatParams, IVFSQParams, ivf_flat_build, ivf_sq_build,
)
from raft_tpu.spatial.ann import flat_kernel
from tests.oracles import assert_knn_equal_up_to_ties
from raft_tpu.spatial.ann.ivf_flat import (
    _resolve_scan_engine,
    ivf_flat_search_grouped,
)

K_NN = 5


def _rand_case(rng, lb, q, d, l_pad):
    # values on the bf16-exact integer grid: the mirror pin is bitwise,
    # but the oracle cross-check below wants operands the bf16 cast
    # cannot round
    qrows = jnp.asarray(
        rng.integers(-64, 64, (lb, q, d)), jnp.float32
    )
    slabs_t = jnp.asarray(
        rng.integers(-64, 64, (lb, d, l_pad)), jnp.float32
    )
    return qrows, slabs_t


def _oracle_subchunk_min(qrows, slabs_t, bounds):
    qv = np.asarray(qrows, np.float32)
    yv = np.asarray(slabs_t, np.float32)
    lb, q, d = qv.shape
    l_pad = yv.shape[2]
    out = np.empty((lb, q, l_pad), np.float32)
    for b in range(lb):
        qn = (qv[b] ** 2).sum(1)[:, None]
        yn = (yv[b] ** 2).sum(0)[None, :]
        out[b] = qn + yn - 2.0 * (qv[b] @ yv[b])
        lo, hi = int(bounds[b, 0]), int(bounds[b, 1])
        mask = np.zeros(l_pad, bool)
        mask[lo:hi] = True
        out[b] = np.where(mask[None, :], out[b], flat_kernel.BIG)
    sub = flat_kernel.SUBCHUNK
    return out.reshape(lb, q, l_pad // sub, sub).min(-1)


@pytest.mark.parametrize(
    "lb,q,d,l_pad,l_tile",
    [
        (3, 32, 16, 256, 128),   # two slab tiles per list
        (2, 16, 24, 128, 128),   # single tile, ragged d
        (1, 48, 8, 512, 256),    # wider tiles
    ],
)
def test_kernel_matches_lax_mirror_bitwise(rng_np, lb, q, d, l_pad,
                                           l_tile):
    """Interpret-mode kernel == lax mirror, bit for bit, masked rows
    included — the 'lax mirror pinned bitwise' acceptance pin."""
    qrows, slabs_t = _rand_case(rng_np, lb, q, d, l_pad)
    bounds = jnp.asarray(
        [[i, max(i, l_pad - 7 * i)] for i in range(lb)], jnp.int32
    )
    got = flat_kernel.flat_scan_subchunk_min(
        qrows, slabs_t, bounds, interpret=True, l_tile=l_tile
    )
    ref = flat_kernel.flat_scan_subchunk_min_lax(qrows, slabs_t, bounds)
    assert got.shape == (lb, q, l_pad // flat_kernel.SUBCHUNK)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    np.testing.assert_allclose(
        np.asarray(got), _oracle_subchunk_min(qrows, slabs_t, bounds),
        rtol=1e-6, atol=1e-4,
    )


def test_kernel_empty_and_full_ranges(rng_np):
    """lo == hi (empty list) -> every sub-chunk min is BIG; full range
    touches every row."""
    qrows, slabs_t = _rand_case(rng_np, 2, 16, 16, 256)
    bounds = jnp.asarray([[5, 5], [0, 256]], jnp.int32)
    got = np.asarray(flat_kernel.flat_scan_subchunk_min(
        qrows, slabs_t, bounds, interpret=True, l_tile=128
    ))
    assert (got[0] == flat_kernel.BIG).all()
    assert (got[1] < flat_kernel.BIG).all()


def test_plan_and_supported_predicates():
    assert flat_kernel.plan_l_tile(96, 48) is not None
    assert flat_kernel.flat_scan_supported(96, 48)
    # every planned tile is lane-aligned, even from a non-128-multiple
    # start and through budget-forced halvings (the pq_kernel review
    # regression, re-pinned here)
    for d in (8, 96, 4096):
        for start in (128, 384, 512):
            lt = flat_kernel.plan_l_tile(d, 64, l_tile=start)
            if lt is not None:
                assert lt % 128 == 0 and lt <= 512
    # absurd (d x qcap): one query block alone exceeds the VMEM budget
    assert not flat_kernel.flat_scan_supported(1 << 20, 512)
    assert not flat_kernel.flat_scan_supported(0, 8)
    with pytest.raises(ValueError, match="multiple"):
        flat_kernel.flat_scan_subchunk_min(
            jnp.zeros((1, 8, 16), jnp.float32),      # Q=8 not 16-aligned
            jnp.zeros((1, 16, 128), jnp.float32),
            jnp.zeros((1, 2), jnp.int32), interpret=True,
        )
    with pytest.raises(ValueError, match="dim"):
        flat_kernel.flat_scan_subchunk_min(
            jnp.zeros((1, 16, 16), jnp.float32),
            jnp.zeros((1, 24, 128), jnp.float32),    # slab dim mismatch
            jnp.zeros((1, 2), jnp.int32), interpret=True,
        )


# -- grouped search: engine equivalence --------------------------------------

def _int_dataset(seed, n=3000, d=16, nq=64):
    """Integer-exact clustered rows/queries (values on the bf16-exact
    grid, squared distances exact in f32 for ANY accumulation order) —
    what makes saturated-pool engine comparisons BIT-identical instead
    of last-ulp-identical (flat_kernel docstring)."""
    rng = np.random.default_rng(seed)
    centers = rng.integers(-60, 60, (8, d))
    x = (
        centers[rng.integers(0, 8, n)]
        + rng.integers(-6, 7, (n, d))
    ).astype(np.float32)
    q = (
        x[rng.integers(0, n, nq)] + rng.integers(-2, 3, (nq, d))
    ).astype(np.float32)
    return x, q


@pytest.fixture(scope="module")
def dataset():
    return _int_dataset(7)


@pytest.fixture(scope="module")
def flat_index(dataset):
    x, _ = dataset
    # n_lists > populated clusters on this data -> some lists are EMPTY,
    # so probes hit empty lists and padded tails (the masking edge cases)
    return ivf_flat_build(x, IVFFlatParams(
        n_lists=48, kmeans_n_iters=4, kmeans_init="random",
    ), metric="sqeuclidean")


def _saturating_ratio(index, p, k):
    """rerank_ratio that makes the kernel path's top-c sub-chunks cover
    every probed row: c*8 >= p*l_pad >= every row the scan saw."""
    l_tile = flat_kernel.plan_l_tile(
        index.centroids.shape[1], 64
    )
    l_pad = -(-index.storage.max_list // l_tile) * l_tile
    return float(p * l_pad // flat_kernel.SUBCHUNK) / k + 1.0


@pytest.mark.parametrize("stream", [None, True])
def test_saturated_pool_bit_identical_single_chip(dataset, flat_index,
                                                  stream):
    """With the rerank pool covering every probed row, BOTH engines
    exact-score the same candidate set in f32 — on integer-exact inputs
    the returned (dists, ids) must match to the bit."""
    x, q = dataset
    p = 4
    kw = dict(n_probes=p, qcap=64, stream_partials=stream,
              rerank_ratio=_saturating_ratio(flat_index, p, K_NN))
    d0, i0 = ivf_flat_search_grouped(flat_index, q, K_NN,
                                     use_pallas=False, **kw)
    d1, i1 = ivf_flat_search_grouped(flat_index, q, K_NN,
                                     use_pallas=True, **kw)
    assert_knn_equal_up_to_ties(x, q, d0, i0, d1, i1)


def _with_emptied_lists(x, base, emptied):
    """Rebuild ``base``'s storage with the rows of ``emptied`` lists
    remapped into list 0 — those lists keep their centroids (so probes
    still select them) but hold ZERO rows: the empty-probe edge case,
    constructed deterministically (the PQ-kernel fixture, flat flavor)."""
    from raft_tpu.spatial.ann.common import build_list_storage

    n = base.storage.n
    n_lists = base.centroids.shape[0]
    sid = np.asarray(base.storage.sorted_ids)
    sizes = np.asarray(base.storage.list_sizes)
    labels = np.empty(n, np.int64)
    labels[sid] = np.repeat(np.arange(n_lists), sizes)
    labels = np.where(np.isin(labels, list(emptied)), 0, labels)
    storage = build_list_storage(labels, n_lists)
    sid2 = np.asarray(storage.sorted_ids)
    data_sorted = jnp.concatenate([
        jnp.asarray(x[sid2]), jnp.zeros((1, x.shape[1]), jnp.float32)
    ])
    return dataclasses.replace(base, data_sorted=data_sorted,
                               storage=storage)


def test_emptied_lists_padded_tails_no_alien_rows(dataset, flat_index):
    """Empty lists are forced into the index (rows remapped away,
    centroids kept) so probes hit genuinely empty lists and padded
    tails; the kernel path must (a) stay bit-identical to the XLA
    engine at a saturated pool, and (b) never return rows outside the
    probed lists — sub-chunk windows overhang a list's tail into the
    NEXT list's slab rows, and the per-row validity mask must drop
    them."""
    x, q = dataset
    idx = _with_emptied_lists(x, flat_index, {1, 5, 9, 17})
    storage = idx.storage
    sizes = np.asarray(storage.list_sizes)
    assert (sizes == 0).any(), "fixture must include empty lists"
    p = 16
    kw = dict(n_probes=p, qcap=64,
              rerank_ratio=_saturating_ratio(idx, p, K_NN))
    ds0, is0 = ivf_flat_search_grouped(idx, q, K_NN, use_pallas=False,
                                       **kw)
    ds1, is1 = ivf_flat_search_grouped(idx, q, K_NN, use_pallas=True,
                                       **kw)
    assert_knn_equal_up_to_ties(x, q, ds0, is0, ds1, is1)

    from raft_tpu.spatial.ann.common import coarse_probe

    probes, _ = coarse_probe(
        jnp.asarray(q, jnp.float32),
        jnp.asarray(idx.centroids, jnp.float32), p,
    )
    probes = np.asarray(probes)
    sid = np.asarray(storage.sorted_ids)
    offs = np.asarray(storage.list_offsets)
    ids = np.asarray(is1)
    for qi in range(ids.shape[0]):
        allowed = set()
        for l in probes[qi]:
            allowed.update(sid[offs[l]:offs[l] + sizes[l]].tolist())
        got = set(t for t in ids[qi].tolist() if t >= 0)
        assert got <= allowed, f"query {qi} returned unprobed rows"


def test_kernel_recall_non_inferior(dataset, flat_index):
    """At a modest rerank_ratio the top-c sub-chunks cover the top-c
    rows of the bf16 scan (the 8-row cover argument), so kernel-path
    recall must not fall below the XLA engine's beyond bf16 boundary
    noise."""
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
    r_pal = rec(ivf_flat_search_grouped(flat_index, q, K_NN,
                                        use_pallas=True, **kw)[1])
    r_xla = rec(ivf_flat_search_grouped(flat_index, q, K_NN,
                                        use_pallas=False, **kw)[1])
    assert r_pal >= r_xla - 0.01, (r_pal, r_xla)


def test_large_k_exceeding_subchunk_pool(dataset):
    """k > p * (l_pad/8) is legal whenever k <= max_list: the kernel
    path must clamp its sub-chunk selection to the pool width instead
    of asking top_k for more sub-chunks than exist — and the clamped
    pool (c*8 = p*l_pad rows) still covers k rows."""
    x, q = dataset
    # few lists -> max_list well above l_pad/8
    idx = ivf_flat_build(x, IVFFlatParams(
        n_lists=4, kmeans_n_iters=3, kmeans_init="random",
    ), metric="sqeuclidean")
    L = idx.storage.max_list
    p = 1
    l_tile = flat_kernel.plan_l_tile(x.shape[1], 64)
    l_pad = -(-L // l_tile) * l_tile
    width = l_pad // flat_kernel.SUBCHUNK
    k = min(L, p * width + 8)
    assert k > p * width, "fixture must exceed the sub-chunk pool"
    kw = dict(n_probes=p, qcap=64, rerank_ratio=1.0)
    d0, i0 = ivf_flat_search_grouped(idx, q, k, use_pallas=False, **kw)
    d1, i1 = ivf_flat_search_grouped(idx, q, k, use_pallas=True, **kw)
    assert d1.shape == d0.shape == (q.shape[0], k)
    # at c = full pool both engines exact-score every probed row;
    # a k this deep into dense integer clusters hits exact ties
    assert_knn_equal_up_to_ties(x, q, d0, i0, d1, i1)


def test_use_pallas_true_raises_naming_requirement(dataset, flat_index):
    """Explicit opt-in must not silently fall back: the resolver raises
    naming the unmet requirement (VMEM plan / per-query routing)."""
    x, q = dataset
    with pytest.raises(Exception, match="VMEM plan"):
        _resolve_scan_engine(True, 1 << 20, 512)
    # k > max_list routes to the per-query search (no kernel path)
    with pytest.raises(Exception, match="per-query"):
        ivf_flat_search_grouped(
            flat_index, q, flat_index.storage.max_list + 1,
            n_probes=4, use_pallas=True,
        )


def test_resolve_scan_engine_auto_off_tpu():
    """Auto (None) never selects the kernel off-TPU; explicit values
    resolve as given when supported."""
    assert jax.default_backend() != "tpu"
    assert _resolve_scan_engine(None, 96, 48) is False
    assert _resolve_scan_engine(True, 96, 48) is True
    assert _resolve_scan_engine(False, 96, 48) is False


def test_cpu_default_never_imports_kernel_module():
    """A fresh JAX_PLATFORMS=cpu process running default grouped flat
    searches (plus warmup) must not import (let alone compile) the
    Pallas kernel module."""
    prog = (
        "import os, sys\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "import numpy as np\n"
        "from raft_tpu.spatial.ann import IVFFlatParams, ivf_flat_build\n"
        "from raft_tpu.spatial.ann.ivf_flat import "
        "ivf_flat_search_grouped\n"
        "rng = np.random.default_rng(0)\n"
        "x = rng.standard_normal((400, 8)).astype(np.float32)\n"
        "idx = ivf_flat_build(x, IVFFlatParams(n_lists=8,\n"
        "    kmeans_n_iters=2, kmeans_init='random'))\n"
        "idx.warmup(8, k=3, n_probes=2)\n"
        "ivf_flat_search_grouped(idx, x[:8], 3, n_probes=2, qcap=8)\n"
        "assert 'raft_tpu.spatial.ann.flat_kernel' not in sys.modules, \\\n"
        "    'CPU default search imported the TPU kernel module'\n"
        "print('OK')\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True,
        timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


# -- IVF-SQ: kernel lives in the grouped search; loud-fail names it ----------

def test_ivf_sq_per_query_use_pallas_points_at_grouped(dataset):
    """Since ISSUE 11 the SQ engine HAS a kernel path — in the grouped
    search (tests/test_sq_kernel.py). The per-query search still has
    none (it never forms list slabs): ``use_pallas=True`` there must
    raise POINTING AT the grouped entry, and ``None``/``False`` must
    run the XLA path with identical results."""
    from raft_tpu.spatial.ann.ivf_sq import ivf_sq_search

    x, q = dataset
    idx = ivf_sq_build(x, IVFSQParams(n_lists=16, kmeans_n_iters=3))
    with pytest.raises(Exception, match="ivf_sq_search_grouped"):
        ivf_sq_search(idx, q, K_NN, n_probes=4, use_pallas=True)
    d_def, i_def = ivf_sq_search(idx, q, K_NN, n_probes=4)
    d_none, i_none = ivf_sq_search(idx, q, K_NN, n_probes=4,
                                   use_pallas=None)
    d_off, i_off = ivf_sq_search(idx, q, K_NN, n_probes=4,
                                 use_pallas=False)
    for dd, ii in ((d_none, i_none), (d_off, i_off)):
        np.testing.assert_array_equal(np.asarray(d_def), np.asarray(dd))
        np.testing.assert_array_equal(np.asarray(i_def), np.asarray(ii))


# -- mutation tier: tombstones at the rerank tail ----------------------------

def test_mutable_search_engine_parity_with_tombstones(dataset):
    """The kernel path folds the mutation tier's row_mask at its exact
    rerank tail: on a small-list index (the default rerank_ratio
    saturates the pool) both engines must return bit-identical
    (dists, ids) after upserts AND deletes, and no deleted id may ever
    surface."""
    from raft_tpu.spatial.ann.mutation import (
        delete, mutable_search, upsert, wrap_mutable,
    )

    x, q = dataset
    idx = ivf_flat_build(x, IVFFlatParams(
        n_lists=64, kmeans_n_iters=4, kmeans_init="random",
    ), metric="sqeuclidean")
    # default rerank_ratio=4.0, k=10 -> c*8 = 320 rows >= p*max_list
    p = 2
    assert 4 * 10 * flat_kernel.SUBCHUNK >= p * idx.storage.max_list, \
        "fixture must saturate the default rerank pool"
    m = wrap_mutable(idx, delta_cap=32)
    rng = np.random.default_rng(3)
    up_ids = jnp.asarray(rng.integers(0, x.shape[0], 8), jnp.int32)
    m, _ = upsert(m, jnp.asarray(x[np.asarray(up_ids)] + 1.0), up_ids)
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
    got = set(np.asarray(i1).ravel().tolist())
    assert not (got & alive_dead), "deleted rows surfaced"


# -- MNMG: the fused one-dispatch program ------------------------------------

@pytest.fixture(scope="module")
def comms8():
    from raft_tpu.comms import build_comms

    return build_comms(jax.devices()[:8])


@pytest.fixture(scope="module")
def sharded_index(dataset, comms8):
    from raft_tpu.comms import mnmg_ivf_flat_build

    x, _ = dataset
    return mnmg_ivf_flat_build(comms8, x, IVFFlatParams(
        n_lists=32, kmeans_n_iters=4, kmeans_init="random",
    ), metric="sqeuclidean")


def test_mnmg_fused_program_engine_parity(dataset, comms8,
                                          sharded_index):
    """The Pallas path ACTIVE inside the MNMG fused one-dispatch
    program: saturated-pool results bit-identical to the XLA engine's
    (each probed list is scored shard-locally by the same grouped
    kernel, and the merge sees identical shard payloads)."""
    from raft_tpu.comms import mnmg_ivf_flat_search

    x, q = dataset
    p = 4
    l_tile = flat_kernel.plan_l_tile(x.shape[1], 64)
    l_pad = -(-int(sharded_index.max_list) // l_tile) * l_tile
    rr = float(p * l_pad // flat_kernel.SUBCHUNK) / K_NN + 1.0
    kw = dict(n_probes=p, qcap=q.shape[0], rerank_ratio=rr)
    d0, i0 = mnmg_ivf_flat_search(comms8, sharded_index, q, K_NN,
                                  use_pallas=False, **kw)
    d1, i1 = mnmg_ivf_flat_search(comms8, sharded_index, q, K_NN,
                                  use_pallas=True, **kw)
    np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))


def test_mnmg_pallas_health_flip_zero_retrace(
    dataset, comms8, sharded_index, monkeypatch
):
    """The acceptance trace-audit with the kernel engaged: use_pallas
    is a trace-time static, health stays a runtime input — shard_mask
    flips must reuse the ONE compiled fused program (zero retraces)."""
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
    mod.mnmg_ivf_flat_search(comms8, sharded_index, q, K_NN,
                             shard_mask=m_up, **kw)
    fn = created[0]
    size0 = fn._cache_size()
    for mask in (m_one, m_up):
        res = mod.mnmg_ivf_flat_search(comms8, sharded_index, q, K_NN,
                                       shard_mask=mask, **kw)
    assert all(f is fn for f in created), \
        "health flips must reuse the cached program object"
    assert fn._cache_size() == size0, \
        "health flips must not retrace the compiled kernel program"
    assert float(jnp.min(res.coverage)) == 1.0
