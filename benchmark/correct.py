"""The comparison that decides ``correct``.

It compares the answers that the timed window served, on a seeded sample
of its requests, with :func:`benchmark.reference.exact_knn` at the timed
sizes. Three numbers, each held to a limit from the configuration file:

* ``unanswered``: requests due in the window that failed or never
  returned (limit 0);
* ``miss_at_10``: 1 - recall@k of the sampled answers;
* ``dist_err``: the widest gap between a served distance and the exact
  distance of the id served beside it, as a share of the query's exact
  k-th distance. An id outside the rows reads infinite; an id of -1 with
  an infinite distance (no neighbour found) is a miss, not a gap.
"""

from __future__ import annotations

import math

import numpy as np

from benchmark import reference

NUMBERS = ("unanswered", "miss_at_10", "dist_err")


def sample_requests(seed: int, sizes, answered, target_rows: int):
    """Indices of answered requests drawn from ``seed`` until they hold
    ``target_rows`` query rows, the largest answered request among them."""
    answered = np.flatnonzero(answered)
    if answered.size == 0:
        return answered
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 7])
    order = rng.permutation(answered)
    largest = order[np.argmax(np.asarray(sizes)[order])]
    picked, rows = [largest], int(sizes[largest])
    for i in order:
        if rows >= target_rows:
            break
        if i != largest:
            picked.append(int(i))
            rows += int(sizes[i])
    return np.sort(np.asarray(picked, np.int64))


def gaps(x, q, dists, ids, ref_d, ref_i):
    """``miss_at_10`` and ``dist_err`` of answers ``(dists, ids)`` to the
    queries ``q`` against the reference ``(ref_d, ref_i)``."""
    dists = np.asarray(dists, np.float64)
    ids = np.asarray(ids, np.int64)
    k = ref_i.shape[1]
    hits = sum(len(set(a.tolist()) & set(b.tolist()))
               for a, b in zip(ids, ref_i))
    miss = 1.0 - hits / ref_i.size
    n = x.shape[0]
    none = (ids == -1) & ~np.isfinite(dists)
    bad = ~none & ((ids < 0) | (ids >= n) | ~np.isfinite(dists))
    if bad.any():
        return miss, math.inf
    true = reference.true_dists(x, q, np.where(none, 0, ids))
    scale = np.maximum(np.asarray(ref_d, np.float64)[:, k - 1:k], 1e-12)
    err = np.where(none, 0.0, np.abs(dists - true) / scale)
    return miss, float(err.max()) if err.size else 0.0


def judge(numbers: dict, limits: dict) -> bool:
    return all(numbers[name] <= limits[name] for name in NUMBERS)


def lines(numbers: dict, limits: dict):
    """One short line per number compared, beside its limit."""
    return [f"check {name} {numbers[name]!r} limit {limits[name]!r}"
            for name in NUMBERS]
