"""Pallas ADC engine for IVF-PQ — the PQ port of the ``fused_knn``
two-phase recipe (ROADMAP item 4; reference: the interleaved-scan ADC
kernels under cpp/include/raft/neighbors/detail/ivf_pq_compute_similarity,
SURVEY §12/§17). Since ISSUE 11 the engine is a thin instantiation of
the shared scan-kernel core (:mod:`raft_tpu.spatial.ann.scan_core`): the
tile planner, the [lo, hi) slab masking, the 8-row sub-chunk-min select,
and the lax-mirror discipline live there once; this module contributes
only the ADC distance computation (VMEM one-hot expansion + bf16 LUT
contraction).

Why a kernel: the XLA grouped-ADC path materializes a one-hot expansion of
every scanned code block in HBM — (L, M·2^bits) bf16 per list, ~hundreds
of GB per 16k-query batch at the 10M×96 bench geometry — then writes the
full (qcap, L) distance tile back to HBM for ``top_k`` to read again. The
scan is memory-bound on traffic that never needed to exist.

Here the whole ADC scan for one (list, code-tile) step lives in VMEM:

* the per-(list, query-slot) **bf16 LUT** — ``lut[q, m·K + k]``, the full
  ADC table including the residual-norm constant — is loaded once per
  list block and stays VMEM-resident across its code tiles;
* the uint8 **code tile** is expanded to a one-hot operand *in VMEM* (a
  u8 compare against a constant (K, 1) index column — this is how a
  byte-index gather is expressed to the MXU on a toolchain whose Mosaic
  has no dynamic-gather lowering; the expansion never touches HBM);
* one MXU contraction ``lut (Q, M·K) @ onehot (M·K, Lt)`` yields the
  distance tile, rows outside the list's ``[lo, hi)`` slab range are
  masked to a finite BIG, and the tile is **min-reduced over 8-row
  sub-chunks in the same kernel** — only the (Q, Lpad/8) sub-chunk minima
  ever reach HBM, an 8× output-traffic cut on top of removing the one-hot
  round trip entirely.

Exactness contract (the ``fused_knn`` chunk-min argument at sub-chunk
granularity): every ADC-rank-``c`` candidate row lives in a sub-chunk
whose minimum is <= the c-th best ADC value, so the top-``c`` sub-chunks
by minimum contain the top-``c`` ADC rows — the refine pool built from
them is a superset of the row-granular path's pool, and the downstream
refine rescores in exact f32 (``refine_ratio`` semantics unchanged; the
bf16 LUT only perturbs *candidate ranking*, same as the one-hot path's
bf16 contraction). Tie ORDER within equal minima may differ from the
row-granular path — the same value-exact / tie-order-may-differ contract
``fused_knn`` documents.

CPU/tier-1: the kernel runs under ``interpret=True`` (pure XLA semantics,
no Mosaic), and :func:`pq_adc_subchunk_min_lax` is the op-for-op XLA
mirror used to pin the kernel's values bitwise in tests. Importing this
module never builds a TPU program; ``JAX_PLATFORMS=cpu`` callers reach it
only when they explicitly opt in with ``use_pallas=True``.
"""

from __future__ import annotations

import functools
import typing

import jax
import jax.numpy as jnp

from raft_tpu.spatial.ann import scan_core
from raft_tpu.spatial.ann.scan_core import (
    BIG as BIG,  # re-export: callers read the masked-row constant here
    SUBCHUNK,
    pad_queries,
)

__all__ = [
    "SUBCHUNK", "plan_l_tile", "pq_adc_subchunk_min",
    "pq_adc_subchunk_min_lax", "pq_adc_supported",
]


def _step_bytes(mk: int, q_pad: int, l_tile: int) -> int:
    # onehot (MK, Lt) bf16 + lut (Qp, MK) bf16 (x2: pipelined block) +
    # d2 (Qp, Lt) f32 + codes (M, Lt) u8 (< 1%, ignored)
    return 2 * mk * l_tile + 2 * 2 * q_pad * mk + 4 * q_pad * l_tile


def plan_l_tile(mk: int, q_pad: int,
                l_tile: typing.Optional[int] = None,
                profile: str = "throughput"):
    """The ADC engine's byte model handed to the ONE shared planner
    (:func:`raft_tpu.spatial.ann.scan_core.plan_l_tile`): largest
    lane-aligned code-tile width whose per-step working set fits the
    VMEM budget, from the profile's start width (512 throughput / 1024
    latency); None when even a 128-row tile does not fit (very wide
    M·K — the caller falls back to the XLA one-hot path)."""
    return scan_core.plan_l_tile(
        functools.partial(_step_bytes, mk), q_pad, l_tile, profile
    )


def pq_adc_supported(pq_dim: int, pq_bits: int, qcap: int) -> bool:
    """Whether the Pallas ADC engine applies at this config: codes are
    uint8 (pq_bits <= 8 — the index invariant) and one (LUT block,
    one-hot tile) step fits VMEM under the profile the grouped path
    would auto-select for this qcap (``scan_core.tile_profile``; the
    plan only shrinks from the profile start, so supportedness is
    profile-independent in truth value)."""
    if not (1 <= pq_bits <= 8):
        return False
    mk = pq_dim * (1 << pq_bits)
    return plan_l_tile(
        mk, pad_queries(qcap), profile=scan_core.tile_profile(qcap)
    ) is not None


def pq_adc_subchunk_min(luts, codes_t, bounds, *, interpret: bool,
                        l_tile: int = 256):
    """(LB, Q, M·K) bf16 LUTs x (LB, M, Lpad) uint8 codes -> (LB, Q,
    Lpad/8) f32 sub-chunk ADC minima.

    ``bounds`` (LB, 2) int32: per-list valid row range ``[lo, hi)``
    relative to the code slab (rows outside score BIG). Q must be a
    multiple of 16 (bf16 sublane tile) and Lpad a multiple of ``l_tile``
    (itself a multiple of 128) — the caller pads; padded query rows
    produce garbage-but-finite minima the caller drops."""
    lb, q_pad, mk = luts.shape
    m_dim = codes_t.shape[1]
    if mk % m_dim:
        raise ValueError(
            f"pq_adc_subchunk_min: LUT width {mk} is not a multiple of "
            f"pq_dim {m_dim}"
        )
    k_dim = mk // m_dim
    kidx = jnp.arange(k_dim, dtype=jnp.int32)[:, None]         # (K, 1)

    def tile_fn(res, til, bc):
        lut = res[0]                          # (Qp, MK) bf16
        codes = til[0]                        # (M, Lt)  u8
        kcol = bc[0]                          # (K, 1)   i32
        m = codes.shape[0]
        kd = kcol.shape[0]
        lt = codes.shape[1]
        # one-hot[m*K + k, l] = (codes[m, l] == k): a compare against
        # the constant (K, 1) index column — the byte-index gather,
        # spelled as an MXU operand (Mosaic on this toolchain has no
        # dynamic-gather lowering; the expansion is VMEM-only, which is
        # the point). The compare runs in int32: the v5e VPU has no
        # 8-bit compare.
        oh = (codes.astype(jnp.int32)[:, None, :]
              == kcol[None, :, :])                             # (M, K, Lt)
        ohf = oh.reshape(m * kd, lt).astype(jnp.bfloat16)
        return jax.lax.dot_general(
            lut, ohf, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                      # (Q, Lt) f32

    return scan_core.subchunk_scan(
        tile_fn, bounds,
        [luts.astype(jnp.bfloat16)], [codes_t], [kidx],
        l_tile=l_tile, interpret=interpret,
        name="pq_adc_subchunk_min",
    )


def pq_adc_subchunk_min_lax(luts, codes_t, bounds):
    """Op-for-op XLA mirror of :func:`pq_adc_subchunk_min` (same one-hot
    expansion, same bf16 contraction with f32 accumulation, same masking
    and sub-chunk reduce via ``scan_core.mask_subchunk_min_lax``) — the
    bit-compat reference the tier-1 tests pin the interpret-mode kernel
    against, and the engine's fallback wherever ``pallas_call`` is
    unavailable."""
    lb, q_pad, mk = luts.shape
    m_dim, l_pad = codes_t.shape[1], codes_t.shape[2]
    k_dim = mk // m_dim
    kidx = jnp.arange(k_dim, dtype=jnp.int32)
    oh = (codes_t.astype(jnp.int32)[:, :, None, :]
          == kidx[None, None, :, None])                        # (LB,M,K,Lp)
    ohf = oh.reshape(lb, mk, l_pad).astype(jnp.bfloat16)
    d2 = jax.lax.dot_general(
        luts.astype(jnp.bfloat16), ohf, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )                                                          # (LB, Q, Lp)
    return scan_core.mask_subchunk_min_lax(d2, bounds)
