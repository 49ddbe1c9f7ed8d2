"""One pass of raft_tpu's served main path on TPU chips, checked against a
plain exact reference written here.

    python chip_smoke.py             # one chip: IVF-Flat served, then brute force
    python chip_smoke.py --chips 4   # four chips: the list-sharded IVF-Flat index

The deployment is one chip's share of DEEP-100M on a v5e-8: 12.5M rows x 96
dims, L2, stored bf16 (bench.py's 12.5M flat shard row, docs/ivf_scale.md).
The rows are synthetic clustered data made on the device from ``--seed``.

One chip:

1. ``ivf_flat_build`` (the flat shard row's list geometry: 4096 lists,
   lists capped at 2048 rows), ``index.warmup`` for each bucket, then at
   least 200 requests of 1-64 queries through ``ServingExecutor`` with
   buckets {8, 64}. Recall@10 of the served answers on 1,024 queries must
   reach 0.90 against the exact reference.
2. ``brute_force_knn`` on the same rows: it must take the fused Pallas
   kernel; distances within 1e-3 relative of the reference, recall@10 at
   least 0.999.

Four chips (``--chips 4``): ``mnmg_ivf_flat_build_distributed`` over
4 x 12.5M rows, each shard made on its own chip, then
``mnmg_ivf_flat_search`` (16 probes per chip) on 1,024 queries, against
the reference computed shard by shard and merged on the host. Every chip
must hold its shard, and recall@10 must reach 0.90.

The exact reference is f32 HIGHEST-precision dot products and ``lax.top_k``
over row blocks, merged across blocks; it calls nothing of raft_tpu.

Each phase prints one JSON line. The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Without a TPU, or on any failed check, the script exits non-zero and does
not print that line.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys
import time


@dataclasses.dataclass(frozen=True)
class Shape:
    """The smoke's sizes; the defaults are the full per-chip shard."""

    rows: int = 12_500_000      # per chip: DEEP-100M over a v5e-8
    dim: int = 96
    blobs: int = 1000
    block: int = 2_500_000      # rows made per device program
    n_lists: int = 4096
    kmeans_iters: int = 8
    max_list_cap: int = 2048
    n_probes: int = 16
    k: int = 10
    eval_queries: int = 1024
    requests: int = 200
    max_request: int = 64
    buckets: tuple = (8, 64)
    ref_block: int = 250_000


IVF_RECALL_MIN = 0.90
BF_RECALL_MIN = 0.999
BF_DIST_RTOL = 1e-3


class SmokeFailure(Exception):
    """A check of the smoke failed; the message says which."""


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def emit(**fields):
    print(json.dumps(fields), flush=True)


def progress(msg, t0):
    """A progress line on stderr, so a run cut short shows how far it got."""
    print(f"chip_smoke: {msg} ({time.perf_counter() - t0:.1f} s)",
          file=sys.stderr, flush=True)


# -- data, made on the device from the seed -----------------------------------

def _row_block(key, centers, rank, b, block):
    """Rows [b*block, (b+1)*block) of shard ``rank``: a blob center plus
    unit Gaussian noise, cast to bf16 (the stored dtype)."""
    import jax
    import jax.numpy as jnp

    n_blobs, dim = centers.shape
    rows = b * block + jnp.arange(block)
    noise = jax.random.normal(
        jax.random.fold_in(jax.random.fold_in(key, rank), b), (block, dim)
    )
    return (centers[(rows + rank) % n_blobs] + noise).astype(jnp.bfloat16)


def make_centers(seed, shape):
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(seed)
    centers = jax.random.normal(key, (shape.blobs, shape.dim), jnp.float32)
    return key, centers * 6.0


def make_rows(seed, shape, device=None):
    """(rows, dim) bf16 on one device, made there block by block."""
    import jax
    import jax.numpy as jnp

    key, centers = make_centers(seed, shape)
    if device is not None:
        centers = jax.device_put(centers, device)
    block = min(shape.block, shape.rows)
    check(shape.rows % block == 0, "rows must be a multiple of block")
    gen = jax.jit(_row_block, static_argnames=("rank", "block"))
    parts = [gen(key, centers, rank=0, b=jnp.int32(b), block=block)
             for b in range(shape.rows // block)]
    return jnp.concatenate(parts) if len(parts) > 1 else parts[0]


def make_queries(seed, base_rows):
    """Queries near data rows: each picked row plus N(0, 0.3^2) noise."""
    import jax
    import jax.numpy as jnp

    key = jax.random.fold_in(jax.random.PRNGKey(seed), 99)
    return (base_rows.astype(jnp.float32)
            + 0.3 * jax.random.normal(key, base_rows.shape, jnp.float32))


def pick_rows(seed, x, count):
    import jax

    key = jax.random.fold_in(jax.random.PRNGKey(seed), 98)
    idx = jax.random.randint(key, (count,), 0, x.shape[0])
    return x[idx]


# -- the plain exact reference -------------------------------------------------

def exact_reference(x, q, k, block):
    """Exact squared-L2 top-k of ``q`` over the rows of ``x``: f32 HIGHEST
    dot products and ``lax.top_k`` per row block, merged across blocks.
    Returns device (dists (m, k), ids (m, k)), ids relative to ``x``, on
    ``x``'s device (not waited on)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    @functools.partial(jax.jit, static_argnames=("size",))
    def one_block(x, q, start, size):
        xb = lax.dynamic_slice_in_dim(x, start, size).astype(jnp.float32)
        dots = lax.dot_general(q, xb, (((1,), (1,)), ((), ())),
                               precision=lax.Precision.HIGHEST)
        d2 = (jnp.sum(q * q, axis=1)[:, None]
              + jnp.sum(xb * xb, axis=1)[None, :] - 2.0 * dots)
        neg, pos = lax.top_k(-d2, k)
        return -neg, pos + start

    @jax.jit
    def merge(d0, i0, d1, i1):
        d = jnp.concatenate([d0, d1], axis=1)
        i = jnp.concatenate([i0, i1], axis=1)
        neg, pos = lax.top_k(-d, k)
        return -neg, jnp.take_along_axis(i, pos, axis=1)

    q = jnp.asarray(q, jnp.float32)
    n = x.shape[0]
    best = None
    for start in range(0, n, block):
        part = one_block(x, q, jnp.int32(start), size=min(block, n - start))
        best = part if best is None else merge(*best, *part)
    return best


def recall_at_k(ids, ref_ids):
    import numpy as np

    ids, ref_ids = np.asarray(ids), np.asarray(ref_ids)
    hit = sum(len(set(a.tolist()) & set(b.tolist()))
              for a, b in zip(ids, ref_ids))
    return hit / ref_ids.size


def kernels_in(fn, *args):
    """Names of the Pallas kernels in ``fn``'s lowered program: what
    actually runs, as the compiler sees it (an interpret-mode or XLA path
    lowers with none)."""
    import jax

    text = jax.jit(fn).lower(*args).as_text()
    names = ("flat_scan_subchunk_min", "fused_knn_chunk_mins")
    if "tpu_custom_call" not in text:
        return []
    return [n for n in names if n in text]


def peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# -- phase 1: the served IVF-Flat index ---------------------------------------

def phase_ivf_flat(x, q, ref_ids, shape, *, use_pallas=None, seed=0):
    """Build, warm, serve; return the phase record (every request must
    resolve: a failed one raises here). ``use_pallas=None`` is
    the served default (the kernel on a TPU); tests pass True to run the
    kernel in interpret mode on the CPU."""
    import jax
    import numpy as np

    from raft_tpu.serving import ServingExecutor
    from raft_tpu.spatial.ann import IVFFlatParams, ivf_flat_build
    from raft_tpu.spatial.ann.ivf_flat import ivf_flat_search_grouped

    params = IVFFlatParams(
        n_lists=shape.n_lists, kmeans_n_iters=shape.kmeans_iters,
        kmeans_init="random", max_list_cap=shape.max_list_cap, seed=seed,
    )
    t0 = time.perf_counter()
    index = ivf_flat_build(x, params, metric="sqeuclidean")
    jax.block_until_ready(index.data_sorted)
    build_s = time.perf_counter() - t0
    progress("index built", t0)

    k, p = shape.k, shape.n_probes
    runs, warm_s, engines = {}, {}, {}
    for b in shape.buckets:
        t0 = time.perf_counter()
        qcap = index.warmup(b, k=k, n_probes=p, use_pallas=use_pallas)
        warm_s[b] = time.perf_counter() - t0

        def run(qq, qcap=qcap):
            return ivf_flat_search_grouped(
                index, qq, k, n_probes=p, qcap=qcap, use_pallas=use_pallas,
            )

        runs[b] = run
        engines[b] = kernels_in(
            lambda idx, qq, qcap=qcap: ivf_flat_search_grouped(
                idx, qq, k, n_probes=p, qcap=qcap, use_pallas=use_pallas),
            index, q[:b],
        )

    def dispatch(batch, **_runtime):
        return runs[int(batch.shape[0])](batch)

    q_host = np.asarray(q, np.float32)
    nq = q_host.shape[0]
    rng = np.random.default_rng(seed)
    sizes = rng.integers(1, shape.max_request + 1, shape.requests)
    futures, spans, at = [], [], 0
    t0 = time.perf_counter()
    with ServingExecutor(dispatch, shape.buckets, dim=q_host.shape[1]) as ex:
        for m in sizes:
            rows = (at + np.arange(m)) % nq
            at += int(m)
            spans.append(rows)
            futures.append(ex.submit(q_host[rows]))
        results = [f.result(timeout=600) for f in futures]
        stats = ex.stats()
    serve_s = time.perf_counter() - t0
    served_ids = np.concatenate([r[1] for r in results])
    rows = np.concatenate(spans)
    check(served_ids.shape == (rows.size, k),
          f"served ids shape {served_ids.shape} != {(rows.size, k)}")
    rec = recall_at_k(served_ids, np.asarray(ref_ids)[rows])
    return {
        "phase": "ivf_flat_served",
        "rows": int(x.shape[0]), "dim": int(x.shape[1]),
        "n_lists": int(index.centroids.shape[0]),
        "max_list": int(index.storage.max_list), "n_probes": p, "k": k,
        "engine": {str(b): engines[b] for b in shape.buckets},
        "build_s": build_s,
        "warmup_compile_s": {str(b): warm_s[b] for b in shape.buckets},
        "requests": len(futures), "queries_served": int(rows.size),
        "batches": int(stats.batches), "serve_s": serve_s,
        "recall_at_10": rec,
    }


# -- phase 2: exact brute-force kNN -------------------------------------------

def phase_brute_force(x, q, ref_d, ref_ids, shape, *, use_fused=None):
    import jax
    import numpy as np

    from raft_tpu.spatial.knn import brute_force_knn

    k = shape.k

    def search(xx, qq):
        return brute_force_knn(xx, qq, k, metric="sqeuclidean",
                               use_fused=use_fused)

    engine = kernels_in(search, x, q)
    t0 = time.perf_counter()
    d, i = jax.block_until_ready(search(x, q))
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    d, i = jax.block_until_ready(search(x, q + 0.0))
    steady_s = time.perf_counter() - t0
    d, i = np.asarray(d), np.asarray(i)
    ref_d = np.asarray(ref_d)
    rel = float(np.max(np.abs(d - ref_d) / np.maximum(np.abs(ref_d), 1e-6)))
    return {
        "phase": "brute_force_knn",
        "engine": engine, "first_call_s": first_s, "steady_call_s": steady_s,
        "recall_at_10": recall_at_k(i, ref_ids), "max_rel_dist_err": rel,
    }


# -- the four-chip phase: the list-sharded IVF-Flat index ---------------------

def make_sharded_rows(seed, shape, comms):
    """(P, rows, dim) bf16 sharded over the mesh; every shard is made on
    its own device (rank r's rows are ``_row_block`` with ``rank=r``)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from raft_tpu.compat import shard_map

    key, centers = make_centers(seed, shape)
    block = min(shape.block, shape.rows)
    check(shape.rows % block == 0, "rows must be a multiple of block")
    ranks = jax.device_put(
        jnp.arange(comms.size, dtype=jnp.int32),
        NamedSharding(comms.mesh, P(comms.axis)),
    )

    def gen(b):
        def body(r):
            return _row_block(key, centers, r[0], b, block)[None]

        return jax.jit(shard_map(
            body, mesh=comms.mesh, in_specs=P(comms.axis),
            out_specs=P(comms.axis, None, None),
        ))(ranks)

    parts = [gen(b) for b in range(shape.rows // block)]
    return (jnp.concatenate(parts, axis=1) if len(parts) > 1
            else parts[0])


def phase_mnmg(seed, shape, devices):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from raft_tpu.comms import build_comms
    from raft_tpu.comms.mnmg_ivf_flat import (
        mnmg_ivf_flat_build_distributed, mnmg_ivf_flat_search,
    )
    from raft_tpu.spatial.ann import IVFFlatParams

    comms = build_comms(devices)
    P_ = comms.size
    t0 = time.perf_counter()
    xg = jax.block_until_ready(make_sharded_rows(seed, shape, comms))
    gen_s = time.perf_counter() - t0
    progress("sharded rows made", t0)
    shards = sorted(xg.addressable_shards, key=lambda s: s.index[0].start)
    shard_devs = {s.device for s in shards}
    check(len(shard_devs) == P_,
          f"row shards sit on {len(shard_devs)} devices, not {P_}")

    # queries: eval_queries/P rows picked from each shard, on device 0
    per = shape.eval_queries // P_
    base = [jax.device_put(pick_rows(seed + r, s.data[0], per), devices[0])
            for r, s in enumerate(shards)]
    q = np.asarray(make_queries(seed, jnp.concatenate(base)))

    t0 = time.perf_counter()
    index = mnmg_ivf_flat_build_distributed(comms, xg, IVFFlatParams(
        n_lists=shape.n_lists, kmeans_n_iters=shape.kmeans_iters,
        kmeans_init="random", max_list_cap=shape.max_list_cap, seed=seed,
    ), metric="sqeuclidean")
    jax.block_until_ready(index.vectors_sorted)
    build_s = time.perf_counter() - t0
    progress("sharded index built", t0)
    index_devs = {s.device for s in index.vectors_sorted.addressable_shards}
    check(len(index_devs) == P_,
          f"index rows sit on {len(index_devs)} devices, not {P_}")

    # the global list count is the flat shard row's 4096, so each list
    # holds P x the rows and splits P x as many ways at the 2048 cap: a
    # query needs P x the probes to cover the same neighbourhood (16
    # probes read recall@10 0.765 on four chips, my chip run, PR 21)
    k, p = shape.k, shape.n_probes * P_
    t0 = time.perf_counter()
    d, ids = jax.block_until_ready(
        mnmg_ivf_flat_search(comms, index, q, k, n_probes=p))
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    d, ids = jax.block_until_ready(
        mnmg_ivf_flat_search(comms, index, q + 1e-6, k, n_probes=p))
    steady_s = time.perf_counter() - t0

    # the reference, shard by shard on each shard's own device (all four
    # dispatched before any is read back), merged here
    t0 = time.perf_counter()
    parts = [exact_reference(s.data[0], jax.device_put(q, s.device), k,
                             shape.ref_block) for s in shards]
    all_d = np.concatenate([np.asarray(pd) for pd, _ in parts], axis=1)
    all_i = np.concatenate([np.asarray(pi) + r * shape.rows
                            for r, (_, pi) in enumerate(parts)], axis=1)
    order = np.argsort(all_d, axis=1, kind="stable")[:, :k]
    ref_ids = np.take_along_axis(all_i, order, axis=1)
    ref_s = time.perf_counter() - t0

    return {
        "phase": "mnmg_ivf_flat", "chips": P_,
        "rows_per_chip": shape.rows, "dim": shape.dim,
        "shard_devices": sorted(str(dv) for dv in shard_devs),
        "index_devices": sorted(str(dv) for dv in index_devs),
        "n_lists_global": int(index.owner.shape[0]),
        "max_list": int(index.max_list), "n_probes": p, "k": k,
        "data_s": gen_s, "build_s": build_s, "first_search_s": first_s,
        "steady_search_s": steady_s, "reference_s": ref_s,
        "recall_at_10": recall_at_k(np.asarray(ids), ref_ids),
        "peak_bytes_in_use": [peak_bytes(dv) for dv in devices],
    }


# -- driver -------------------------------------------------------------------

def run_one_chip(seed, shape, device, *, use_pallas=None, use_fused=None):
    """Phases 1 and 2 on one device; returns their records (raises
    SmokeFailure on a failed check)."""
    import jax
    import numpy as np

    t0 = time.perf_counter()
    with jax.default_device(device):
        x = jax.block_until_ready(make_rows(seed, shape, device))
        q = make_queries(seed, pick_rows(seed, x, shape.eval_queries))
        data_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        ref_d, ref_i = map(np.asarray, exact_reference(
            x, q, shape.k, shape.ref_block))
        ref_s = time.perf_counter() - t0

        # the index dies with the phase: brute force runs without it
        ivf = phase_ivf_flat(x, q, ref_i, shape, use_pallas=use_pallas,
                             seed=seed)
        ivf["data_s"], ivf["reference_s"] = data_s, ref_s
        ivf["peak_bytes_in_use"] = peak_bytes(device)
        bf = phase_brute_force(x, q, ref_d, ref_i, shape,
                               use_fused=use_fused)
        bf["peak_bytes_in_use"] = peak_bytes(device)
    return ivf, bf


def check_one_chip(ivf, bf, *, on_tpu):
    check(ivf["recall_at_10"] >= IVF_RECALL_MIN,
          f"IVF-Flat recall@10 {ivf['recall_at_10']} < {IVF_RECALL_MIN} "
          f"at n_probes={ivf['n_probes']}")
    check(bf["recall_at_10"] >= BF_RECALL_MIN,
          f"brute-force recall@10 {bf['recall_at_10']} < {BF_RECALL_MIN}")
    check(bf["max_rel_dist_err"] <= BF_DIST_RTOL,
          f"brute-force distances off by {bf['max_rel_dist_err']} relative")
    if on_tpu:
        for b, names in ivf["engine"].items():
            check(names == ["flat_scan_subchunk_min"],
                  f"bucket {b}: IVF-Flat took {names or 'the XLA scan'}, "
                  "not the flat scan kernel")
        check(bf["engine"] == ["fused_knn_chunk_mins"],
              f"brute force took {bf['engine'] or 'the scan path'}, not "
              "the fused kernel")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        sys.exit(f"chip_smoke: no TPU present (JAX found {platform}); "
                 "this smoke runs only on the chip")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
                 f"devices, JAX found {len(devices)}")
    emit(device_kind=devices[0].device_kind, devices=len(devices),
         jax=jax.__version__)

    shape = Shape()
    try:
        if args.chips == 1:
            ivf, bf = run_one_chip(args.seed, shape, devices[0])
            emit(**ivf)
            emit(**bf)
            check_one_chip(ivf, bf, on_tpu=True)
        else:
            rec = phase_mnmg(args.seed, shape, devices[:args.chips])
            emit(**rec)
            check(all(rec["peak_bytes_in_use"]),
                  f"a chip reports no memory in use: "
                  f"{rec['peak_bytes_in_use']}")
            check(rec["recall_at_10"] >= IVF_RECALL_MIN,
                  f"sharded IVF-Flat recall@10 {rec['recall_at_10']} < "
                  f"{IVF_RECALL_MIN} at n_probes={rec['n_probes']}")
    except SmokeFailure as e:
        sys.exit(f"chip_smoke: FAILED: {e}")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices),
    }}), flush=True)


if __name__ == "__main__":
    main()
