"""Exact kNN through ``brute_force_knn`` (the fused chunk-min kernel on a
TPU), called straight with the host's queries, as offline ground-truth
and kNN-graph jobs call it."""

from __future__ import annotations

import time

import jax
import numpy as np

from benchmark import data, roofline, serving
from benchmark.spans import span


def setup(cfg, traffic, seed, devices):
    from raft_tpu.spatial.knn import brute_force_knn

    dev = devices[0]
    t0 = time.perf_counter()
    with jax.default_device(dev):
        rows = jax.block_until_ready(data.make_rows(seed, cfg, dev))
        pool = np.asarray(data.make_queries(seed, cfg, cfg["query_pool"],
                                            dev))
    t1 = time.perf_counter()
    k = cfg["k"]

    def send(q):
        with span("bench.dispatch"):
            return brute_force_knn(rows, q, k, metric="sqeuclidean")

    jax.block_until_ready(send(pool[:traffic["queries"]]))
    return {"rows": rows, "pool": pool, "cfg": cfg, "send": send,
            "phases": {"data_s": t1 - t0,
                       "warmup_s": time.perf_counter() - t1}}


def _wait(handle):
    d, i = handle
    return np.asarray(d), np.asarray(i)


def serve(state, traffic, seconds, seed, tracer=None):
    return serving.closed_loop(state["send"], _wait, state["pool"],
                               traffic, seconds, seed, tracer)


def work(state, rec):
    """(flops, bytes) of one call, the mean over the window's calls:
    each reads the rows once and its float32 queries."""
    rows = state["rows"]
    n, dim = rows.shape
    m = float(np.mean(rec["requests"]["sizes"]))
    return {"fused_knn_chunk_mins": (
        roofline.brute_force_flops(m, n, dim),
        n * dim * rows.dtype.itemsize + m * dim * 4)}


def release(state):
    state.pop("send")
    return state["rows"], state["pool"]
