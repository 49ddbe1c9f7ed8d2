"""k-means benchmark — the BASELINE.md config (make_blobs 1M x 128,
k=1024; reference cpp/include/raft/cluster/detail/kmeans.cuh:780 loop)."""

import json
import time

import numpy as np
import jax

from raft_tpu.cluster import KMeansParams, kmeans_fit


def main():
    rng = np.random.default_rng(0)
    n, d, k = 1_000_000, 128, 1024
    x = jax.device_put(rng.standard_normal((n, d)).astype(np.float32))

    # methodology: two programs (max_iter=5 vs 20, tol=0 so the bound binds)
    # timed on FRESH input values, so no run reuses an earlier result; the
    # iteration cost is the difference quotient, cancelling k-means++ init
    # (present in both runs).
    p5 = KMeansParams(n_clusters=k, max_iter=5, tol=0.0, seed=0)
    p20 = KMeansParams(n_clusters=k, max_iter=20, tol=0.0, seed=0)
    # compile p5 (scalar fetch: a full sync)
    float(kmeans_fit(x, p5).inertia)
    float(kmeans_fit(x, p20).inertia)  # compile p20

    import jax.numpy as jnp

    x2 = jax.block_until_ready(x * jnp.float32(1.0001))  # fresh values
    t0 = time.perf_counter()
    out5 = kmeans_fit(x2, p5)
    float(out5.inertia)
    t5 = time.perf_counter() - t0
    t0 = time.perf_counter()
    out20 = kmeans_fit(x2, p20)
    float(out20.inertia)
    t20 = time.perf_counter() - t0
    per_iter = (t20 - t5) / (int(out20.n_iter) - int(out5.n_iter))
    print(json.dumps({
        "name": f"kmeans/{n}x{d}k{k}",
        "s_per_iter": round(per_iter, 4),
        "iters_per_s": round(1.0 / per_iter, 3),
        "init_plus_fixed_s": round(t5 - 5 * per_iter, 3),
    }))


if __name__ == "__main__":
    main()
