"""The served list-sharded IVF-Flat path on 4 of the suite's 8 virtual CPU
devices, as a four-chip host serves it: distributed build ->
``MnmgIVFFlatIndex.warmup`` -> ``ServingExecutor`` staging each batch
replicated on the mesh (``Comms.replicate``) -> ``mnmg_ivf_flat_search``
with the batch donated, checked against the plain exact reference
(``tests.oracles.np_knn_ids`` and exact float32 distances)."""

import re

import jax
import ml_dtypes
import numpy as np
import pytest

from raft_tpu.comms import (
    build_comms, mnmg_ivf_flat_build_distributed, mnmg_ivf_flat_search,
    shard_rows,
)
from raft_tpu.comms import mnmg_ivf_flat as mnmg_flat
from raft_tpu.serving import ServingExecutor
from raft_tpu.spatial.ann import IVFFlatParams
from tests.oracles import np_knn_ids

K = 10
N_PROBES = 12
BUCKET = 32
PARAMS = IVFFlatParams(n_lists=32, kmeans_n_iters=6, kmeans_init="random",
                       max_list_cap=100, seed=3)


@pytest.fixture(scope="module")
def comms():
    return build_comms(jax.devices()[:4])


@pytest.fixture(scope="module")
def dataset():
    """8,000 rows of 16 blobs stored bfloat16, as the benchmark stores
    them, and 96 fresh float32 draws of the same blobs."""
    rng = np.random.default_rng(27)
    centers = rng.normal(0.0, 4.0, (16, 24))
    x = (centers[np.arange(8000) % 16]
         + rng.normal(0.0, 1.0, (8000, 24))).astype(ml_dtypes.bfloat16)
    q = (centers[rng.integers(0, 16, 96)]
         + rng.normal(0.0, 1.0, (96, 24))).astype(np.float32)
    return x, q


@pytest.fixture(scope="module")
def index(comms, dataset):
    xg, n_valid = shard_rows(comms, dataset[0])
    return mnmg_ivf_flat_build_distributed(
        comms, xg, PARAMS, n_valid=n_valid, metric="sqeuclidean")


@pytest.fixture(scope="module")
def served(comms, dataset, index):
    """Every query served through the executor in requests of 1-7 rows
    (so each bucket is padded), and the per-request answers. The per-list
    query capacity is the whole bucket, so no query is dropped and each
    row's answer is independent of the rows batched beside it."""
    _, q = dataset
    qcap = index.warmup(comms, BUCKET, k=K, n_probes=N_PROBES,
                        qcap=BUCKET, donate_queries=True)

    def dispatch(batch, **_runtime):
        return mnmg_ivf_flat_search(comms, index, batch, K,
                                    n_probes=N_PROBES, qcap=qcap,
                                    donate_queries=True)

    sizes = [1 + i % 7 for i in range(40)]
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]]) % q.shape[0]
    requests = [q[(s + np.arange(m)) % q.shape[0]]
                for s, m in zip(starts, sizes)]
    with ServingExecutor(dispatch, [BUCKET], dim=q.shape[1],
                         stage=comms.replicate) as ex:
        futures = [ex.submit(r) for r in requests]
        answers = [f.result(timeout=120) for f in futures]
        stats = ex.stats()
    return requests, answers, stats, qcap


def _exact_d2(x, q, ids):
    rows = np.asarray(x, np.float32)[ids]              # (m, k, d)
    return ((q[:, None, :] - rows) ** 2).sum(-1)


def test_served_answers_have_recall(dataset, served):
    x, _ = dataset
    requests, answers, stats, _ = served
    q = np.concatenate(requests)
    ids = np.concatenate([a[1] for a in answers])
    true = np_knn_ids(np.asarray(x, np.float32), q, K)
    hits = sum(len(set(a.tolist()) & set(b.tolist()))
               for a, b in zip(ids, true))
    assert hits / true.size > 0.95
    assert stats.padded_rows > 0          # the buckets were padded


def test_served_distances_are_exact(dataset, served):
    """Each served distance is the exact squared distance of the id
    beside it, to float32 rounding of sums over bfloat16 rows."""
    x, _ = dataset
    requests, answers, _, _ = served
    for r, (d, ids) in zip(requests, answers):
        assert d.shape == ids.shape == (r.shape[0], K)
        np.testing.assert_allclose(d, _exact_d2(x, r, ids), rtol=1e-5,
                                   atol=1e-3)


def test_ids_are_global(comms, dataset, served):
    """Ids are row numbers of the whole set, from every shard: shard r
    holds rows r x n_loc .. (r + 1) x n_loc - 1."""
    x, _ = dataset
    ids = np.concatenate([a[1] for a in served[1]]).ravel()
    assert ((ids >= 0) & (ids < x.shape[0])).all()
    n_loc = x.shape[0] // comms.size
    assert set(ids // n_loc) == set(range(comms.size))


def test_padded_rows_never_reach_an_answer(comms, index, served):
    """Each request's answer is its own rows' search alone, row for row:
    the zero rows that pad its bucket, and the other requests packed
    beside it, reach none of its answers."""
    requests, answers, _, qcap = served
    for r, (d, ids) in list(zip(requests, answers))[:8]:
        want_d, want_i = mnmg_ivf_flat_search(
            comms, index, np.pad(r, ((0, BUCKET - r.shape[0]), (0, 0)),
                                 mode="edge"),
            K, n_probes=N_PROBES, qcap=qcap)
        np.testing.assert_array_equal(ids, np.asarray(want_i)[:r.shape[0]])
        np.testing.assert_allclose(d, np.asarray(want_d)[:r.shape[0]],
                                   rtol=1e-6)


def test_full_probe_is_exact(comms, dataset, index):
    x, q = dataset
    n_lists = int(index.centroids.shape[0])
    d, ids = mnmg_ivf_flat_search(comms, index, q, K, n_probes=n_lists,
                                  qcap=q.shape[0])
    ids = np.asarray(ids)
    np.testing.assert_array_equal(
        np.sort(ids, axis=1),
        np.sort(np_knn_ids(np.asarray(x, np.float32), q, K), axis=1))
    np.testing.assert_allclose(np.asarray(d), _exact_d2(x, q, ids),
                               rtol=1e-5, atol=1e-3)


def test_replicated_stage_enters_the_program_as_is(comms, dataset, index,
                                                   served):
    """The sharded stage places a batch whole on every chip of the
    index's mesh, which is the sharding the program takes its queries
    in: the dispatch reshards nothing, and the donated buffer is
    consumed in place."""
    _, q = dataset
    qcap = served[3]
    staged = comms.replicate(q[:BUCKET])
    assert staged.sharding.is_fully_replicated
    assert set(staged.sharding.device_set) == set(comms.mesh.devices.flat)
    fn, args, _ = mnmg_flat._prepare_flat_family(
        comms, index, staged, K, sq=False, n_probes=N_PROBES, qcap=qcap,
        list_block=32, qcap_max_drop_frac=None, donate_queries=True,
        shard_mask=None, failover=None, overprobe=2.0, merge_ways=None,
        mutation=None, wire="bf16", use_pallas=None, rerank_ratio=4.0)
    assert args[8] is staged
    lowered = fn.lower(*args)
    compiled = lowered.compile()
    assert compiled.input_shardings[0][8].is_equivalent_to(
        staged.sharding, staged.ndim)
    # the queries, and they alone, are the program's donor (a backend
    # that can donate, as the TPU can and the CPU cannot, then consumes
    # the staged buffer)
    main = next(line for line in lowered.as_text().splitlines()
                if "func.func public @main" in line)
    donors = re.findall(r"tensor<([0-9x]+)xf32> \{jax\.buffer_donor = true",
                        main)
    assert donors == [f"{BUCKET}x{q.shape[1]}"], donors
    d, ids = fn(*args)
    assert ids.sharding.is_fully_replicated


def _blobs(seed):
    """8,000 bfloat16 rows of 16 blobs, as ``dataset`` makes them, from
    ``seed``."""
    rng = np.random.default_rng(seed)
    centers = rng.normal(0.0, 4.0, (16, 24))
    return (centers[np.arange(8000) % 16]
            + rng.normal(0.0, 1.0, (8000, 24))).astype(ml_dtypes.bfloat16)


def test_fresh_data_of_a_shape_compiles_no_build_program(comms):
    """A build on fresh data of the same shape (rows, lists, cap, chips)
    reuses every program the first build compiled: the exchange's slab
    height, round size and the route's list count follow the shape, not
    the data. The list count after the split, and with it the search
    program's centroid operand, still follows the data."""
    from raft_tpu.comms import mnmg_ivf

    compiles = []

    def on_compile(name, *_, **__):
        # compiled here, or loaded from the persistent cache
        if name in ("/jax/core/compile/backend_compile_duration",
                    "/jax/compilation_cache/cache_hits"):
            compiles.append(name)

    def build(seed):
        xg, n_valid = shard_rows(comms, _blobs(seed))
        return mnmg_ivf_flat_build_distributed(
            comms, xg, PARAMS, n_valid=n_valid, metric="sqeuclidean")

    first = build(101)
    programs = {k: fn._cache_size()
                for k, fn in mnmg_ivf._PROGRAM_CACHE.items()}
    kinds = {k[0] for k in programs}
    assert {"route", "send", "swap", "recv"} <= kinds, kinds
    jax.monitoring.register_event_duration_secs_listener(on_compile)
    jax.monitoring.register_event_listener(on_compile)
    try:
        second = build(202)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
        jax.monitoring.unregister_event_listener(on_compile)
    assert compiles == []
    assert {k: fn._cache_size()
            for k, fn in mnmg_ivf._PROGRAM_CACHE.items()} == programs
    # the lists split at the cap (100 rows), so each build's longest list
    # is the cap, and the slab height is the greedy bound of both
    assert first.max_list == second.max_list == PARAMS.max_list_cap
    assert first.n_pad == second.n_pad
    assert not np.array_equal(np.asarray(first.list_sizes),
                              np.asarray(second.list_sizes))
