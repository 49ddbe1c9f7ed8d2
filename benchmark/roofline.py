"""Peaks of the chips, and the work the algorithms need.

Peaks, one TPU v5e chip (Google Cloud documentation, "TPU v5e"):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s. A device
kind not in the table is an error, never a default.

The work counted is what the algorithm needs, whatever implements it, so
that a share reads the same work across implementations:

* IVF-Flat scan, per batch: the distinct lists that the exact
  ``n_probes`` nearest centroids of its queries select, each list's rows
  read once at ``dim`` x the stored bytes, plus the float32 queries; and
  2 * dim FLOP for each (query, row of a probed list) pair.
* Brute-force kNN, per call: 2 * nq * n * dim FLOP (compute-bound).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12, "ops_int8": 393e12, "hbm_bytes_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; raises ``KeyError`` for
    a device that is not in it."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks known for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def least_time(flops: float, nbytes: float, peak: dict):
    """(seconds, bound): the larger of ``flops`` over peak FLOP/s and
    ``nbytes`` over peak HBM bytes/s, and which of the two it is."""
    t_c = flops / peak["flops_bf16"]
    t_m = nbytes / peak["hbm_bytes_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


@functools.partial(jax.jit, static_argnames=("n_probes",))
def _probe(centroids, q, *, n_probes):
    c = centroids.astype(jnp.float32)
    dots = lax.dot_general(q, c, (((1,), (1,)), ((), ())),
                           precision=lax.Precision.HIGHEST)
    d2 = jnp.sum(c * c, axis=1)[None, :] - 2.0 * dots
    return lax.top_k(-d2, n_probes)[1]


def probed_lists(centroids, queries, n_probes: int) -> np.ndarray:
    """(nq, n_probes) ids of each query's exact nearest centroids."""
    q = jax.device_put(jnp.asarray(queries, jnp.float32),
                       centroids.device)
    return np.asarray(_probe(centroids, q, n_probes=n_probes))


def ivf_scan_work(probes, list_sizes, dim: int, row_bytes: int,
                  query_bytes: int = 4):
    """(flops, bytes) one batch's scan needs: ``probes`` is its
    (nq, n_probes) list ids, ``list_sizes`` the rows in each list."""
    probes = np.asarray(probes)
    sizes = np.asarray(list_sizes, np.int64)
    distinct = np.unique(probes)
    nbytes = (int(sizes[distinct].sum()) * dim * row_bytes
              + probes.shape[0] * dim * query_bytes)
    flops = 2 * dim * int(sizes[probes].sum())
    return flops, nbytes


def brute_force_flops(nq: int, n: int, dim: int) -> int:
    """FLOP of one exact kNN call: a multiply-add per (query, row,
    dimension)."""
    return 2 * nq * n * dim
