"""ANN benchmarks — IVF-Flat/IVF-PQ build + search with recall@k
(the reference's IVF suites run through FAISS, ann_quantized_faiss.cuh;
BASELINE.md names IVF build+search as a target config).

Every search QPS line carries recall@10 against an exact oracle so the
numbers are falsifiable (VERDICT r1 weak #4).

Regime note (measured on v5e-1, n=500k d=96 batch=4096, this file):

* round-1 finding: per-query list gathers lose to dense MXU brute force
  at batch >= 512 (random gathers cost more than dense flops).
* round-2: query-grouped (list-major) search amortizes each list's load
  across all its probing queries — 8.4x the per-query IVF path and 2.5x
  the scan brute force in the same regime (145k vs 17k vs 59k QPS).
* the fused Pallas brute force (spatial/fused_knn.py) raised the dense
  bar to ~150k QPS *exact* at this scale, matching grouped IVF; IVF's
  grouped win over dense grows with n (dense compute scales with n,
  grouped IVF with probed volume only).
"""

import json
import time

import numpy as np
import jax
import jax.numpy as jnp

from bench.common import bench_fn, chained_dispatch_ms, chained_dispatch_stats
from raft_tpu.spatial.ann import (
    IVFFlatParams, ivf_flat_build, ivf_flat_search, ivf_flat_search_grouped,
    IVFPQParams, ivf_pq_build, ivf_pq_search, ivf_pq_search_grouped,
)
from raft_tpu.distance.distance_type import DistanceType
from raft_tpu.spatial.fused_knn import fused_l2_knn
from raft_tpu.spatial.knn import _knn_single_part


def recall_at_k(got_ids, true_ids):
    k = true_ids.shape[1]
    hits = sum(
        len(set(g.tolist()) & set(t.tolist()))
        for g, t in zip(np.asarray(got_ids), np.asarray(true_ids))
    )
    return hits / true_ids.size


def main():
    rng = np.random.default_rng(0)
    n, d, k = 500_000, 96, 10
    x = rng.standard_normal((n, d)).astype(np.float32)
    xd = jax.device_put(x)
    q_small = jax.device_put(rng.standard_normal((32, d)).astype(np.float32))
    nq = 4096
    q_big = jax.device_put(rng.standard_normal((nq, d)).astype(np.float32))

    # ground truth for recall (exact fused kNN)
    _, true_big = fused_l2_knn(q_big, xd, k, metric=DistanceType.L2Expanded)
    _, true_small = fused_l2_knn(q_small, xd, k, metric=DistanceType.L2Expanded)
    jax.block_until_ready((true_big, true_small))

    # throughput mode: dense exact baselines
    for name, fn in [
        ("bf_scan", lambda a, b: _knn_single_part(
            a, b, k, DistanceType.L2SqrtExpanded, 2.0, 65536, None)[0]),
        ("bf_fused", lambda a, b: fused_l2_knn(
            a, b, k, metric=DistanceType.L2SqrtExpanded)[0]),
    ]:
        ms = bench_fn(fn, q_big, xd, iters=4,
                      name=f"ann/{name}_throughput/{n}x{d}q{nq}",
                      work=2.0 * n * d * nq)
        print(json.dumps({
            "name": f"ann/{name}_throughput/{n}x{d}",
            "qps": round(nq / (ms / 1e3)), "recall_at_10": 1.0,
        }))

    # IVF-Flat: build, latency mode (per-query), throughput mode (grouped)
    t0 = time.perf_counter()
    index = ivf_flat_build(x, IVFFlatParams(n_lists=1024, kmeans_n_iters=10, kmeans_init="random"))
    float(jnp.sum(index.centroids))  # scalar fetch: a full sync
    build_s = time.perf_counter() - t0
    print(json.dumps({"name": f"ann/ivf_flat_build/{n}x{d}",
                      "build_s": round(build_s, 2)}))

    ms = bench_fn(lambda a: ivf_flat_search(index, a, k, n_probes=8)[0],
                  q_small, iters=6, name=f"ann/ivf_flat_latency_q32/{n}x{d}")
    r = recall_at_k(ivf_flat_search(index, q_small, k, n_probes=8)[1],
                    true_small)
    print(json.dumps({
        "name": f"ann/ivf_flat_latency_q32/{n}x{d}",
        "search_ms": round(ms, 2), "qps": round(32 / (ms / 1e3)),
        "recall_at_10": round(r, 4),
    }))

    for nprobe in (8, 16):
        ms = bench_fn(
            lambda a: ivf_flat_search_grouped(index, a, k, n_probes=nprobe)[0],
            q_big, iters=4,
            name=f"ann/ivf_flat_grouped_p{nprobe}/{n}x{d}q{nq}")
        r = recall_at_k(
            ivf_flat_search_grouped(index, q_big, k, n_probes=nprobe)[1],
            true_big)
        print(json.dumps({
            "name": f"ann/ivf_flat_grouped_p{nprobe}/{n}x{d}",
            "qps": round(nq / (ms / 1e3)), "recall_at_10": round(r, 4),
        }))

    # IVF-PQ: build + refined search + recall/n_probes sweep (VERDICT r1 #7)
    t0 = time.perf_counter()
    pq = ivf_pq_build(x, IVFPQParams(n_lists=1024, pq_dim=12, kmeans_n_iters=10,
                                     kmeans_init="random"))
    float(jnp.sum(pq.centroids))     # scalar fetch: a full sync
    build_s = time.perf_counter() - t0
    print(json.dumps({"name": f"ann/ivf_pq_build/{n}x{d}",
                      "build_s": round(build_s, 2)}))

    sweep = []
    for nprobe in (4, 8, 16, 32):
        ms = bench_fn(
            lambda a: ivf_pq_search(index=pq, queries=a, k=k,
                                    n_probes=nprobe, refine_ratio=4.0)[0],
            q_small, iters=6,
            name=f"ann/ivf_pq_refined_p{nprobe}_q32/{n}x{d}")
        r = recall_at_k(
            ivf_pq_search(pq, q_small, k, n_probes=nprobe,
                          refine_ratio=4.0)[1],
            true_small)
        sweep.append({"n_probes": nprobe, "search_ms": round(ms, 2),
                      "qps": round(32 / (ms / 1e3)),
                      "recall_at_10": round(r, 4)})
    print(json.dumps({"name": f"ann/ivf_pq_sweep_q32/{n}x{d}",
                      "refine_ratio": 4.0, "sweep": sweep}))

    # grouped (list-major) PQ throughput mode: one-hot ADC matmul on the
    # MXU instead of per-candidate LUT gathers. Timed by chained
    # dispatches (the grouped program is too large for the loop-in-jit
    # harness — same rationale as the headline bench's big-kNN config)
    for nprobe in (8, 16):
        def gsearch(a, nprobe=nprobe):
            return ivf_pq_search_grouped(
                index=pq, queries=a, k=k, n_probes=nprobe,
                refine_ratio=4.0, qcap=256,
            )

        jax.block_until_ready(gsearch(q_big)[0])  # compile + warm
        ms = chained_dispatch_ms(
            lambda salt: q_big * (1.0 + 1e-8 * salt), gsearch,
        )
        r = recall_at_k(gsearch(q_big)[1], true_big)
        rec = {
            "name": f"ann/ivf_pq_grouped_p{nprobe}/{n}x{d}",
            "recall_at_10": round(r, 4),
        }
        if ms is not None:
            rec["qps"] = round(nq / (ms / 1e3))
        else:
            rec["note"] = "quotient jitter-dominated at this scale"
        print(json.dumps(rec))

    bench_pq_adc_kernel()
    bench_flat_scan_kernel()
    bench_sq_scan_kernel()


def bench_flat_scan_kernel():
    """The flat scan-block microbench (ISSUE 10): the legacy XLA
    grouped-flat block — a materialized ``(LB, qcap, L)`` einsum
    distance tile fed to ``lax.top_k`` — vs the Pallas sub-chunk-min
    kernel, at FIXED shapes (the per-(list-block) scan work, isolated
    from probe/regroup/rerank) so the kernel speedup is tracked
    independently of the end-to-end flat QPS rows in bench.py.
    Spread-escalated via the shared chained-dispatch harness; on a
    non-TPU backend the kernel runs in interpret mode and the
    comparison is semantics-only."""
    import functools

    from raft_tpu.spatial.ann import flat_kernel

    LB, L, d, Q, kk = 8, 2048, 96, 48, 10
    interpret = jax.default_backend() != "tpu"
    rng = np.random.default_rng(11)
    qv = jax.device_put(rng.standard_normal((LB, Q, d)).astype(np.float32))
    slabs = jax.device_put(
        rng.standard_normal((LB, L, d)).astype(np.float32)
    )
    slabs_t = jnp.transpose(slabs, (0, 2, 1))
    bounds = jnp.tile(jnp.asarray([[0, L]], jnp.int32), (LB, 1))

    @jax.jit
    def xla_block(q_in):
        # the legacy per-block scan IS the anti-pattern the
        # wide-distance-materialize lint names: full distance tile
        # through HBM, selection re-reads it
        mn = jnp.einsum("bld,bld->bl", slabs, slabs,
                        preferred_element_type=jnp.float32)
        qn = jnp.einsum("bqd,bqd->bq", q_in, q_in,
                        preferred_element_type=jnp.float32)
        dots = jnp.einsum("bqd,bld->bql", q_in, slabs,
                          preferred_element_type=jnp.float32)
        d2 = qn[:, :, None] + mn[:, None, :] - 2.0 * dots
        vals, _ = jax.lax.top_k(-d2, kk)  # jaxlint: disable=wide-distance-materialize
        return -vals

    l_tile = flat_kernel.plan_l_tile(d, Q)     # the tile the impl plans

    @functools.partial(jax.jit, static_argnames=("interp",))
    def kernel_block(q_in, interp=interpret):
        return flat_kernel.flat_scan_subchunk_min(
            q_in, slabs_t, bounds, interpret=interp, l_tile=l_tile,
        )

    rec = {"name": f"ann/flat_scan_kernel/LB{LB}xL{L}xd{d}q{Q}"}
    for label, fn in (("xla", xla_block), ("pallas", kernel_block)):
        jax.block_until_ready(fn(qv))
        st = chained_dispatch_stats(
            lambda salt: qv * (1.0 + 1e-6 * salt), fn, escalate=1,
        )
        if st is None:
            rec[f"{label}_note"] = "jitter-dominated"
            continue
        rec[f"{label}_ms"] = round(st["ms"], 3)
        rec[f"{label}_spread"] = st["spread"]
        rec[f"{label}_escalations"] = st.get("escalations", 0)
    if "xla_ms" in rec and "pallas_ms" in rec:
        rec["speedup"] = round(rec["xla_ms"] / rec["pallas_ms"], 2)
    print(json.dumps(rec))


def bench_sq_scan_kernel():
    """The int8 SQ scan-block microbench (ISSUE 11): the XLA dequant
    scan — a full-width f32 dequant expansion of every slab block
    through HBM feeding a materialized distance tile — vs the Pallas
    in-kernel dequant+scan (spatial/ann/sq_kernel, on the shared
    scan-kernel core), at FIXED shapes so the kernel speedup is tracked
    independently of the e2e SQ QPS row in bench.py. The lax baseline
    here is the kernel's own op-for-op mirror: same bf16 rounding of
    the dequantized tile, so the comparison isolates the memory-path
    win (int8 crosses HBM at one byte/element and expands only in
    VMEM). Spread-escalated via the shared chained-dispatch harness;
    on a non-TPU backend the kernel runs in interpret mode and the
    comparison is semantics-only."""
    import functools

    from raft_tpu.spatial.ann import sq_kernel

    LB, L, d, Q = 8, 2048, 96, 48
    interpret = jax.default_backend() != "tpu"
    rng = np.random.default_rng(11)
    qv = jax.device_put(rng.standard_normal((LB, Q, d)).astype(np.float32))
    codes_t = jax.device_put(
        rng.integers(-128, 128, (LB, d, L)).astype(np.int8)
    )
    bounds = jnp.tile(jnp.asarray([[0, L]], jnp.int32), (LB, 1))
    vmin = jnp.asarray(rng.standard_normal(d).astype(np.float32))
    vscale = jnp.full((d,), 1.0 / 64.0, jnp.float32)

    @jax.jit
    def lax_block(q_in):
        return sq_kernel.sq_scan_subchunk_min_lax(
            q_in, codes_t, bounds, vmin, vscale
        )

    l_tile = sq_kernel.plan_l_tile(d, Q)       # the tile the impl plans

    @functools.partial(jax.jit, static_argnames=("interp",))
    def kernel_block(q_in, interp=interpret):
        return sq_kernel.sq_scan_subchunk_min(
            q_in, codes_t, bounds, vmin, vscale,
            interpret=interp, l_tile=l_tile,
        )

    rec = {"name": f"ann/sq_scan_kernel/LB{LB}xL{L}xd{d}q{Q}"}
    for label, fn in (("lax", lax_block), ("pallas", kernel_block)):
        jax.block_until_ready(fn(qv))
        st = chained_dispatch_stats(
            lambda salt: qv * (1.0 + 1e-6 * salt), fn, escalate=1,
        )
        if st is None:
            rec[f"{label}_note"] = "jitter-dominated"
            continue
        rec[f"{label}_ms"] = round(st["ms"], 3)
        rec[f"{label}_spread"] = st["spread"]
        rec[f"{label}_escalations"] = st.get("escalations", 0)
    if "lax_ms" in rec and "pallas_ms" in rec:
        rec["speedup"] = round(rec["lax_ms"] / rec["pallas_ms"], 2)
    print(json.dumps(rec))


def bench_pq_adc_kernel():
    """The ADC scan-block microbench: XLA one-hot matmul + per-block
    selection vs the Pallas sub-chunk-min kernel, at FIXED shapes (the
    two engines' per-(list-block) scan work, isolated from probe/LUT
    build/refine) — so the kernel speedup is tracked independently of
    the end-to-end index QPS rows in bench.py. Spread-escalated via the
    shared chained-dispatch harness; on a non-TPU backend the kernel
    runs in interpret mode and the comparison is semantics-only."""
    import functools

    from raft_tpu.spatial.ann import pq_kernel

    LB, L, M, K, Q, kk = 8, 2048, 12, 256, 48, 40
    interpret = jax.default_backend() != "tpu"
    rng = np.random.default_rng(11)
    luts = jax.device_put(
        rng.standard_normal((LB, Q, M * K)).astype(np.float32)
    )
    codes = jax.device_put(
        rng.integers(0, K, (LB, L, M)).astype(np.uint8)
    )
    codes_t = jnp.transpose(codes, (0, 2, 1))
    bounds = jnp.tile(jnp.asarray([[0, L]], jnp.int32), (LB, 1))

    @jax.jit
    def onehot_block(lut_in):
        # the legacy per-block scan: materialized one-hot, bf16
        # contraction, per-(list, slot) approx selection — the work the
        # kernel replaces (raft_tpu/spatial/ann/ivf_pq.py block_fn)
        onehot = (
            codes[..., None] == jnp.arange(K, dtype=jnp.uint8)
        ).astype(jnp.bfloat16)
        # the measured baseline IS the anti-pattern:
        d2 = jax.lax.dot_general(  # jaxlint: disable=adc-gather
            lut_in.astype(jnp.bfloat16),
            onehot.reshape(LB, L, M * K),
            (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        vals, _ = jax.lax.approx_min_k(d2, kk, recall_target=0.95)
        return vals

    l_tile = pq_kernel.plan_l_tile(M * K, Q)   # the tile the impl plans

    @functools.partial(jax.jit, static_argnames=("interp",))
    def kernel_block(lut_in, interp=interpret):
        return pq_kernel.pq_adc_subchunk_min(
            lut_in.astype(jnp.bfloat16), codes_t, bounds,
            interpret=interp, l_tile=l_tile,
        )

    rec = {"name": f"ann/pq_adc_kernel/LB{LB}xL{L}xM{M}xK{K}q{Q}"}
    for label, fn in (("onehot", onehot_block), ("pallas", kernel_block)):
        jax.block_until_ready(fn(luts))
        st = chained_dispatch_stats(
            lambda salt: luts * (1.0 + 1e-6 * salt), fn, escalate=1,
        )
        if st is None:
            rec[f"{label}_note"] = "jitter-dominated"
            continue
        rec[f"{label}_ms"] = round(st["ms"], 3)
        rec[f"{label}_spread"] = st["spread"]
        rec[f"{label}_escalations"] = st.get("escalations", 0)
    if "onehot_ms" in rec and "pallas_ms" in rec:
        rec["speedup"] = round(rec["onehot_ms"] / rec["pallas_ms"], 2)
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
