"""Fused Pallas distance+select kNN vs naive oracle — the reference's
fused-kernel test niche (cpp/test/spatial/fused_l2_knn.cu pattern: optimized
kernel vs naive distance + sort). Runs the Pallas kernel in interpret mode
on the CPU test platform."""

import numpy as np
import pytest

import jax.numpy as jnp

from raft_tpu.distance.distance_type import DistanceType
from raft_tpu.spatial.fused_knn import fused_l2_knn, fused_knn_supported
from raft_tpu.spatial.knn import brute_force_knn


def _oracle(q, x, k):
    q64 = q.astype(np.float64)
    x64 = x.astype(np.float64)
    d2 = (
        (q64 * q64).sum(1)[:, None]
        + (x64 * x64).sum(1)[None, :]
        - 2.0 * q64 @ x64.T
    )
    full = np.sqrt(np.maximum(d2, 0))
    oi = np.argsort(full, axis=1)[:, :k]
    return full, np.take_along_axis(full, oi, axis=1)


@pytest.mark.parametrize(
    "m,n,d,k",
    [
        (37, 8192, 19, 7),       # ragged everything
        (128, 5000, 64, 10),     # n not a multiple of the chunk width
        (10, 4109, 96, 3),       # prime-ish n
        (200, 16384, 128, 32),   # larger k
    ],
)
def test_fused_l2_knn_exact(m, n, d, k, rng_np):
    q = rng_np.standard_normal((m, d)).astype(np.float32)
    x = rng_np.standard_normal((n, d)).astype(np.float32)
    dists, idxs = fused_l2_knn(q, x, k, metric=DistanceType.L2SqrtExpanded)
    full, ov = _oracle(q, x, k)
    dv = np.take_along_axis(full, np.asarray(idxs), axis=1)
    np.testing.assert_allclose(dv, ov, atol=1e-6)       # right neighbors
    np.testing.assert_allclose(np.asarray(dists), ov, atol=1e-2)


def test_fused_metric_variants(rng_np):
    q = rng_np.standard_normal((16, 32)).astype(np.float32)
    x = rng_np.standard_normal((6000, 32)).astype(np.float32)
    ds, _ = fused_l2_knn(q, x, 4, metric=DistanceType.L2SqrtExpanded)
    dsq, _ = fused_l2_knn(q, x, 4, metric=DistanceType.L2Expanded)
    dun, _ = fused_l2_knn(q, x, 4, metric=DistanceType.L2Unexpanded)
    np.testing.assert_allclose(np.asarray(ds) ** 2, np.asarray(dsq), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(dsq), np.asarray(dun), rtol=1e-6)


def test_fused_bf16_recall(rng_np):
    """bf16 phase-1 with a wide margin stays near-exact (rescore is f32)."""
    q = rng_np.standard_normal((64, 64)).astype(np.float32)
    x = rng_np.standard_normal((20000, 64)).astype(np.float32)
    k = 10
    _, idxs = fused_l2_knn(
        q, x, k, metric=DistanceType.L2SqrtExpanded,
        compute_dtype=jnp.bfloat16, extra_chunks=32,
    )
    full, ov = _oracle(q, x, k)
    oi = np.argsort(full, axis=1)[:, :k]
    recall = np.mean([
        len(set(np.asarray(idxs)[r]) & set(oi[r])) / k
        for r in range(q.shape[0])
    ])
    assert recall >= 0.99, recall


def test_supported_predicate():
    L2 = DistanceType.L2SqrtExpanded
    assert fused_knn_supported(L2, 10, 100_000, 128, 10)
    assert not fused_knn_supported(L2, 10, 1000, 128, 10)   # too few chunks
    assert not fused_knn_supported(DistanceType.L1, 10, 100_000, 128, 10)
    assert not fused_knn_supported(L2, 10, 100_000, 128, 200)  # k too big


def test_brute_force_knn_use_fused_matches(rng_np):
    q = rng_np.standard_normal((32, 48)).astype(np.float32)
    x = rng_np.standard_normal((8192, 48)).astype(np.float32)
    d1, i1 = brute_force_knn(x, q, 5)
    d2, i2 = brute_force_knn(x, q, 5, use_fused=True)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), atol=1e-4)


def test_brute_force_knn_use_fused_unsupported_raises(rng_np):
    q = rng_np.standard_normal((8, 16)).astype(np.float32)
    x = rng_np.standard_normal((256, 16)).astype(np.float32)
    with pytest.raises(ValueError):
        brute_force_knn(x, q, 3, use_fused=True)  # n too small for cover


def test_fused_knn_row_gather_matches_chunk_gather(rng_np):
    """The big-index phase-2 row-gather branch (taken automatically above
    2 GB, forced here) must agree exactly with the chunk-gather branch."""
    q = rng_np.standard_normal((37, 24)).astype(np.float32)
    y = rng_np.standard_normal((4096 + 57, 24)).astype(np.float32)
    d1, i1 = fused_l2_knn(q, y, 7, gather_rows=False)
    d2, i2 = fused_l2_knn(q, y, 7, gather_rows=True)
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


def test_fused_knn_aligned_index_no_pad(rng_np):
    """Exact-multiple index rows skip the pad copy (big-index regime);
    results must still match the brute-force oracle."""
    q = rng_np.standard_normal((16, 32)).astype(np.float32)
    y = rng_np.standard_normal((8192, 32)).astype(np.float32)
    d1, i1 = fused_l2_knn(q, y, 5, bn=2048)
    full = ((q[:, None, :] - y[None, :, :]) ** 2).sum(-1)
    want_i = np.argsort(full, 1, kind="stable")[:, :5]
    want_d = np.sqrt(np.take_along_axis(full, want_i, 1))
    np.testing.assert_allclose(np.asarray(d1), want_d, rtol=1e-4, atol=1e-4)


def test_fused_knn_index_norms_matches(rng_np):
    """Caller-precomputed index norms (the stored-norms search mode,
    reference knn_brute_force_faiss.cuh:318-330) must be bit-identical to
    the self-computed path, and wrong shapes must raise."""
    q = rng_np.standard_normal((19, 32)).astype(np.float32)
    y = rng_np.standard_normal((12000, 32)).astype(np.float32)
    norms = (y.astype(np.float32) ** 2).sum(1)
    d1, i1 = fused_l2_knn(q, y, 5)
    d2, i2 = fused_l2_knn(q, y, 5, index_norms=norms)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), atol=1e-6)
    with pytest.raises(ValueError):
        fused_l2_knn(q, y, 5, index_norms=norms[:-1])
    # threaded through the partitioned entry point
    d3, i3 = brute_force_knn(
        [y[:6000], y[6000:]], q, 5, use_fused=True,
        index_norms=[norms[:6000], norms[6000:]],
    )
    d4, i4 = brute_force_knn([y[:6000], y[6000:]], q, 5, use_fused=True)
    np.testing.assert_array_equal(np.asarray(i3), np.asarray(i4))


def test_fused_knn_warm_start(rng_np):
    """Warm-starting partition B's search with partition A's (translated)
    results equals one search over A + B (the reference's previous-top-k
    warm path, fused_l2_knn.cuh:947)."""
    q = rng_np.standard_normal((23, 16)).astype(np.float32)
    a = rng_np.standard_normal((4096, 16)).astype(np.float32)
    b = rng_np.standard_normal((4096, 16)).astype(np.float32)
    k = 6
    da, ia = fused_l2_knn(q, a, k)
    db, ib = fused_l2_knn(q, b, k, init=(da, ia + 0))  # a-ids are global
    dfull, ifull = fused_l2_knn(q, np.concatenate([b, a]), k)
    # translate: b ids 0..4095 stay, a ids offset by 4096 in the concat
    got = np.sort(np.asarray(db), axis=1)
    want = np.sort(np.asarray(dfull), axis=1)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_fused_knn_rescore_tiles_beyond_smem_rows(rng_np):
    """Query batches whose padded row count exceeds the per-call SMEM
    row bound must keep the DMA rescore path by tiling into
    <= rescore_rows kernel calls (not silently fall back to the XLA
    gather)."""
    from raft_tpu.spatial.fused_knn import _fused_l2_knn_impl

    q = rng_np.standard_normal((40, 128)).astype(np.float32)
    y = rng_np.standard_normal((4096, 128)).astype(np.float32)
    dt, it = _fused_l2_knn_impl(
        q, y, 5, DistanceType.L2SqrtExpanded, bm=1024, bn=2048, bq2=40,
        extra_chunks=8, compute_dtype=jnp.dtype(jnp.float32),
        interpret=True, rescore_rows=16,  # forces ceil(40/16)=3 tiles
    )
    dref, iref = fused_l2_knn(q, y, 5)
    np.testing.assert_array_equal(np.asarray(it), np.asarray(iref))
    np.testing.assert_allclose(
        np.asarray(dt), np.asarray(dref), rtol=1e-5, atol=1e-5
    )
