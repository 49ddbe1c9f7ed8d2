"""IVF-Flat served through ``ServingExecutor``: the rows are made on the
device, ``ivf_flat_build`` builds the index, ``index.warmup`` compiles the
traffic's buckets, and each batch runs ``ivf_flat_search_grouped``."""

from __future__ import annotations

import time

import jax
import numpy as np

from benchmark import data, roofline, serving
from benchmark.spans import span


def setup(cfg, traffic, seed, devices):
    from raft_tpu.spatial.ann import IVFFlatParams, ivf_flat_build
    from raft_tpu.spatial.ann import ivf_flat as ivf_mod

    dev = devices[0]
    ix = cfg["index"]
    t_data = time.perf_counter()
    with jax.default_device(dev):
        rows = jax.block_until_ready(data.make_rows(seed, cfg, dev))
        pool = np.asarray(data.make_queries(seed, cfg, cfg["query_pool"],
                                            dev))
        t0 = time.perf_counter()
        index = ivf_flat_build(rows, IVFFlatParams(
            n_lists=ix["n_lists"], kmeans_n_iters=ix["kmeans_iters"],
            kmeans_init=ix["kmeans_init"], max_list_cap=ix["max_list_cap"],
            seed=int(seed) % (2 ** 31)), metric="sqeuclidean")
        jax.block_until_ready(index.data_sorted)
        build_s = time.perf_counter() - t0
        k, p = cfg["k"], ix["n_probes"]
        # "qcap" absent: the program's default per-list query capacity
        qcaps = {b: index.warmup(b, k=k, n_probes=p, qcap=ix.get("qcap"))
                 for b in traffic["buckets"]}

    search = ivf_mod.ivf_flat_search_grouped

    def dispatch(batch, **_runtime):
        with span("bench.dispatch"):
            return search(index, batch, k, n_probes=p,
                          qcap=qcaps[int(batch.shape[0])])

    # one pass of each bucket through the executor itself before the
    # window, so that its first batches pay no one-off host work
    ex, _ = serving.executor(dispatch, traffic["buckets"], cfg["dim"])
    with ex:
        for b in traffic["buckets"]:
            ex.submit(pool[:b]).result(timeout=600)
    phases = {"data_s": t0 - t_data, "build_s": build_s,
              "warmup_s": time.perf_counter() - t0 - build_s}
    return {"rows": rows, "pool": pool, "index": index, "cfg": cfg,
            "dispatch": dispatch, "phases": phases,
            "about": f"{index.centroids.shape[0]} lists of at most "
                     f"{index.storage.max_list} rows"}


def serve(state, traffic, seconds, seed, tracer=None):
    cfg, pool = state["cfg"], state["pool"]
    buckets = traffic["buckets"]
    ex, reg = serving.executor(state["dispatch"], buckets, cfg["dim"])
    host = {}

    def snapshot():
        host.update(serving.executor_stats(ex, reg, buckets))

    with ex:
        if traffic["loop"] == "open":
            rec = serving.open_loop(ex, pool, traffic, seconds, seed,
                                    tracer, (snapshot,))
        else:
            rec = serving.closed_loop(
                ex.submit, lambda f: f.result(timeout=serving.LATE_S),
                pool, traffic, seconds, seed, tracer, (snapshot,))
        rec["executor"] = serving.executor_stats(ex, reg, buckets)
    rec["executor_host"] = host or rec["executor"]
    rec["build_s"] = state["phases"]["build_s"]
    return rec


def work(state, rec):
    """The scan's (flops, bytes) per batch, the mean over the window's
    batches, where each batch is one request (a closed loop at the
    bucket's size); nothing where batches mix requests."""
    ex = rec["executor"]
    req = rec["requests"]
    if ex["padded_rows"] or ex["batches"] != len(req["sizes"]):
        return {}
    index, cfg = state["index"], state["cfg"]
    sizes = np.asarray(index.storage.list_sizes)
    row_bytes = np.dtype(index.data_sorted.dtype).itemsize
    flops = nbytes = 0
    for s, m in zip(req["start"], req["sizes"]):
        probes = roofline.probed_lists(
            index.centroids, serving.pool_rows(state["pool"], s, m),
            cfg["index"]["n_probes"])
        f, b = roofline.ivf_scan_work(probes, sizes, cfg["dim"], row_bytes)
        flops, nbytes = flops + f, nbytes + b
    n = len(req["sizes"])
    return {"flat_scan_subchunk_min": (flops / n, nbytes / n)}


def release(state):
    """Drop the index; return the rows and the query pool."""
    state.pop("index")
    state.pop("dispatch")
    return state["rows"], state["pool"]
