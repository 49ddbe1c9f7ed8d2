"""Shared numpy oracles for tests (plain module, not conftest: importing
conftest as `tests.conftest` would load it twice — once by pytest as
top-level `conftest`, once as a package module — duplicating any
module-level state)."""

import numpy as np


def np_knn_ids(x, q, k):
    """Exact numpy kNN oracle (squared-L2 ids) for small test shapes.

    Pure-oracle call sites (ids discarded into recall thresholds) use
    this instead of brute_force_knn so they don't each pay a CPU-mesh
    jit compile for their unique shape (CI wall time; brute_force_knn
    itself is covered by tests/test_knn.py).
    """
    x = np.asarray(x, np.float32)
    q = np.asarray(q, np.float32)
    d2 = (
        (q * q).sum(1)[:, None] + (x * x).sum(1)[None, :]
        - 2.0 * (q @ x.T)
    )
    idx = np.argpartition(d2, k - 1, axis=1)[:, :k]
    vals = np.take_along_axis(d2, idx, axis=1)
    o = np.argsort(vals, axis=1, kind="stable")
    return np.take_along_axis(idx, o, axis=1)


def assert_knn_equal_up_to_ties(x, q, d0, i0, d1, i1):
    """Two engines' exact kNN results agree up to the order of ids among
    exactly equal distances — the comparison for integer-exact fixtures,
    where every squared distance is exact in f32 and equal distances are
    common, so which of two equidistant rows a search keeps depends only
    on its candidate order (probe rank on the XLA scan, sub-chunk rank
    on the kernels). Distances must match bitwise. Inside each run of
    equal distances both hold the same id set, except the run the
    k-boundary cuts: there each id must sit at exactly the reported
    squared distance from its query (``x`` holds the rows as the index
    stores them, ``q`` the queries; float64 makes the check exact)."""
    d = np.asarray(d0)
    np.testing.assert_array_equal(d, np.asarray(d1))
    a, b = np.asarray(i0), np.asarray(i1)
    x = np.asarray(x, np.float64)
    q = np.asarray(q, np.float64)
    k = d.shape[1]
    for r in range(d.shape[0]):
        for ids in (a[r], b[r]):
            assert len(set(ids.tolist())) == k, f"query {r}: duplicate ids"
        start = 0
        for end in range(1, k + 1):
            if end < k and d[r, end] == d[r, start]:
                continue
            if end < k or start == 0:
                assert set(a[r, start:end].tolist()) == \
                    set(b[r, start:end].tolist()), f"query {r}"
            else:
                for ids in (a[r, start:end], b[r, start:end]):
                    exact = ((x[ids] - q[r]) ** 2).sum(axis=1)
                    assert (exact == d[r, start]).all(), f"query {r}"
            start = end
