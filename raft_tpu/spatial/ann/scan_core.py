"""Shared scan-kernel core — the one implementation of the fused
distance+selection recipe every Pallas scan engine in this codebase is
built from (ISSUE 11; ROADMAP item 2 "one scan-kernel framework").

PRs 6 and 10 grew two sibling engines (``pq_kernel``, ``flat_kernel``)
that copy-pasted the same five pieces; this module extracts them as the
single authority, and ``sq_kernel`` (the int8 IVF-SQ engine) plus the
kernelized two-level coarse probe (``common.two_level_probe``) are built
directly on it:

* **The VMEM step-budget tile planner** (:func:`plan_l_tile`): the
  largest lane-aligned slab/code-tile width whose per-grid-step working
  set fits the VMEM budget, halving from the profile's start width.
  Each engine supplies only its *byte model* (a ``step_bytes(q_pad,
  l_tile)`` callable) — the shrink loop, the lane re-alignment on halve
  (the pq-kernel review regression), and the None-when-nothing-fits
  contract live here once.
* **Tile profiles** (:func:`tile_profile`): ``"throughput"`` starts the
  plan at 512 rows (the PR 6/10 behavior, bit-for-bit); ``"latency"``
  starts at 1024 for the qcap-1/8 serving shapes — a tiny query block
  leaves the VMEM budget almost untouched, so a wider tile halves the
  grid-step count (and its per-step overhead) exactly where the
  open-loop p99 regime lives. The grouped engines auto-select the
  profile from the static qcap, so the latency regime stops paying
  throughput-shape tiles (docs/ivf_scale.md "One scan-kernel core").
* **Query padding** (:func:`pad_queries`): THE bf16-sublane rounding of
  a query-slot count. Every engine's ``*_supported`` predicate and its
  serving plan call this one function, so a resolver's approval and the
  plan it approved can never round differently.
* **The [lo, hi) slab-range masking idiom** (:func:`mask_slab_range` in
  kernel bodies, :func:`mask_subchunk_min_lax` in the op-for-op lax
  mirrors): rows outside a list's valid range score a finite BIG —
  never +inf (inf - inf NaNs on the VPU) — so masked sub-chunks order
  last in every pooled selection.
* **The 8-row sub-chunk-min select** (:func:`subchunk_min` +
  :func:`subchunk_scan`): the tile is min-reduced over
  :data:`SUBCHUNK`-row granules in the same kernel, so only the
  (Q, Lpad/8) minima ever reach HBM — the fused_knn cover argument at
  8-row granularity makes the downstream rerank pool a superset of the
  row-granular top-c (each engine's module docstring carries its own
  exactness contract).
* **The pinned-bitwise lax-mirror discipline**: every engine ships an
  op-for-op XLA mirror built from the same masking+reduce pieces
  (:func:`mask_subchunk_min_lax`), and the tier-1 suite pins the
  interpret-mode kernel against it bitwise — the mirror is also the
  fallback wherever ``pallas_call`` is unavailable.

:func:`subchunk_scan` is the shared ``pallas_call`` driver: an engine
provides its distance computation for ONE (list, tile) step — the MXU
contraction plus whatever VPU preprocessing its storage format needs
(one-hot expansion for PQ codes, affine int8 dequant for SQ) — and the
driver owns the grid, the block specs, the scalar-prefetched bounds, the
masking, and the sub-chunk reduce.

Importing this module never builds a TPU program; ``JAX_PLATFORMS=cpu``
callers reach it only through an engine's explicit ``use_pallas`` opt-in
(the engines' CPU-subprocess never-imports tests pin this transitively).
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "BIG", "LANE", "Q_GRANULE", "SUBCHUNK", "VMEM_BUDGET",
    "auto_kernel", "l2_gram_tile", "mask_slab_range", "mask_subchunk_min_lax",
    "pad_queries", "plan_l_tile", "round_up", "subchunk_min",
    "subchunk_scan", "tile_profile",
]

SUBCHUNK = 8      # rows per selection granule (f32 sublane width)
LANE = 128        # slab/code-tile rows must be lane-aligned
Q_GRANULE = 16    # bf16 sublane tile: the query axis pads to this

# Masked rows score a finite BIG (never +inf: inf - inf NaNs on the VPU,
# and pooled selection must still order masked sub-chunks last).
BIG = 1e30

# VMEM working-set budget for one grid step, double-buffering headroom
# included. ~16 MB/core total.
VMEM_BUDGET = 10 * 2**20

# Tile-plan start widths per profile: "throughput" is the PR 6/10
# default; "latency" doubles it for tiny (qcap-1/8) query blocks, whose
# step working set is planner-dominated by the tile itself — fewer,
# wider grid steps at the same VMEM budget.
_PROFILE_START = {"throughput": 512, "latency": 1024}

# qcap at or below which the grouped engines auto-select the latency
# profile (the open-loop serving buckets: qcap 1..8)
_LATENCY_QCAP = 8


def round_up(a: int, b: int) -> int:
    return -(-a // b) * b


def pad_queries(qcap: int) -> int:
    """Round a query-slot count up to the kernels' bf16 sublane granule
    — THE q_pad. Every engine's ``*_supported`` predicate and its
    grouped serving path call this, so a resolver's approval and the
    serving plan can never round differently."""
    return round_up(max(qcap, 1), Q_GRANULE)


def tile_profile(qcap: int) -> str:
    """The tile-plan profile a grouped engine should use at a static
    qcap: ``"latency"`` for the qcap-1/8 open-loop serving shapes (the
    planner starts at a 1024-row tile — half the grid steps at a VMEM
    cost the tiny query block easily affords), ``"throughput"``
    otherwise (the PR 6/10 plan, unchanged). Derived from the SAME
    static qcap the warm-up resolves (``common.static_qcap``), so the
    profile is a trace-time constant and can never flip at serve
    time."""
    return "latency" if qcap <= _LATENCY_QCAP else "throughput"


def auto_kernel(supported: bool, engine: str, config: str) -> bool:
    """The auto (``use_pallas=None``) engine choice of a grouped search
    on a TPU backend: the kernel when ``supported``, else the XLA scan —
    said once per (engine, config) as a warning, never silently."""
    if not supported:
        _warn_xla_fallback(engine, config)
    return supported


@functools.lru_cache(maxsize=None)
def _warn_xla_fallback(engine: str, config: str) -> None:
    from raft_tpu.core import logger

    logger.warn(
        "%s: the Pallas kernel does not fit its VMEM plan at %s; this "
        "search runs the XLA scan on the TPU", engine, config,
    )


def plan_l_tile(step_bytes: Callable[[int, int], int], q_pad: int,
                l_tile: Optional[int] = None,
                profile: str = "throughput") -> Optional[int]:
    """Largest tile width (a multiple of :data:`LANE`, at most the
    profile's start width / the explicit ``l_tile`` cap) whose per-step
    working set — ``step_bytes(q_pad, lt)``, the engine's byte model —
    fits :data:`VMEM_BUDGET`; None when even a 128-row tile does not
    fit (the caller falls back to its XLA scan).

    The ONE shared planner (ISSUE 11 acceptance): engines keep their
    byte models, this keeps the shrink loop — halving re-aligned down
    to the lane width, so a non-128-multiple start like 384 can never
    yield an unusable 192-row tile (the pq_kernel review regression,
    owned here once)."""
    start = _PROFILE_START[profile]
    lt = max(LANE, round_up(min(start if l_tile is None else l_tile,
                                start), LANE))
    while lt > LANE and step_bytes(q_pad, lt) > VMEM_BUDGET:
        lt = max(LANE, (lt // 2) // LANE * LANE)
    if step_bytes(q_pad, lt) > VMEM_BUDGET:
        return None
    return lt


def l2_gram_tile(qv, y):
    """THE flat-family distance body: ``‖q‖² + ‖y‖² − 2 qᵀy`` for one
    (..., Q, d) × (..., d, Lt) step — bf16 operands on the MXU with f32
    accumulation, norm terms in f32 on the VPU. Shared by the flat and
    SQ engines' in-kernel ``tile_fn``s (2-d operands) AND their batched
    lax mirrors (3-d operands), so the two engines — and each engine's
    kernel/mirror pair — can never drift by an op."""
    nb = qv.ndim - 2
    batch = tuple(range(nb))
    dots = jax.lax.dot_general(
        qv, y, (((qv.ndim - 1,), (y.ndim - 2,)), (batch, batch)),
        preferred_element_type=jnp.float32,
    )
    qf = qv.astype(jnp.float32)
    qn = jnp.sum(qf * qf, axis=-1)[..., :, None]
    yf = y.astype(jnp.float32)
    yn = jnp.sum(yf * yf, axis=-2)[..., None, :]
    return qn + yn - 2.0 * dots


def mask_slab_range(d2, col0, lo, hi, big: float = BIG):
    """In-kernel [lo, hi) slab-range masking: ``d2`` is one (Q, Lt)
    distance tile whose column 0 sits at absolute slab column ``col0``
    (= tile index x l_tile); rows outside the list's valid range score
    the finite ``big``."""
    col = col0 + jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1)
    return jnp.where((col >= lo) & (col < hi), d2, jnp.float32(big))


def subchunk_min(d2, sub: int = SUBCHUNK):
    """Min-reduce one (Q, Lt) tile over ``sub``-row granules — the only
    thing a scan kernel writes out — as (Lt/sub, Q) minima: the tile is
    transposed first so the granule reduce runs over sublanes and the
    query axis rides the lanes (the fused_knn chunk-min layout; Mosaic
    refuses a lane-axis ``(Q, Lt) -> (Q, Lt/sub, sub)`` reshape)."""
    q_pad, lt = d2.shape
    return jnp.min(d2.T.reshape(lt // sub, sub, q_pad), axis=1)


def mask_subchunk_min_lax(d2, bounds, sub: int = SUBCHUNK,
                          big: float = BIG):
    """The lax-mirror half of the masking+reduce discipline: the same
    [lo, hi) masking and sub-chunk min as the kernel, over the full
    batched (LB, Q, Lpad) distance tile — every engine's op-for-op XLA
    mirror ends with this call, so the piece the tier-1 suite pins the
    interpret-mode kernels against bitwise is shared too."""
    lb, q_pad, l_pad = d2.shape
    col = jnp.arange(l_pad, dtype=jnp.int32)[None, None, :]
    lo = bounds[:, 0][:, None, None]
    hi = bounds[:, 1][:, None, None]
    d2 = jnp.where((col >= lo) & (col < hi), d2, jnp.float32(big))
    return jnp.min(d2.reshape(lb, q_pad, l_pad // sub, sub), axis=3)


def validate_scan_shapes(name: str, q_pad: int, l_pad: int, l_tile: int):
    """The shared shape preconditions of every sub-chunk scan entry
    point (Q on the bf16 sublane granule, Lpad on the tile, the tile on
    the lane) — callers pad; the message leads with the engine's entry
    name so a violation reads like the engine raised it."""
    if q_pad % Q_GRANULE or l_pad % l_tile or l_tile % LANE:
        raise ValueError(
            f"{name}: Q={q_pad} must be a multiple of "
            f"{Q_GRANULE} and Lpad={l_pad} a multiple of "
            f"l_tile={l_tile} (itself a multiple of {LANE})"
        )


def subchunk_scan(tile_fn, bounds, resident: Sequence, tiled: Sequence,
                  broadcast: Sequence = (), *, l_tile: int,
                  interpret: bool, sub: int = SUBCHUNK,
                  name: str = "subchunk_scan"):
    """The shared ``pallas_call`` driver of every scan engine: a
    (list b, tile t) grid where

    * ``bounds`` (LB, 2) int32 rides the scalar-prefetch slot (the
      per-list [lo, hi) valid range);
    * each ``resident`` array (LB, A, B) is loaded once per list and stays
      VMEM-resident across its tiles (query rows, ADC LUTs);
    * each ``tiled`` array (LB, A, Lpad) streams as (A, l_tile) blocks
      (slab rows, code columns);
    * each ``broadcast`` array is small, whole-array resident across the
      grid (codebook index columns, dequant scale/offset);
    * ``tile_fn(resident_blocks, tiled_blocks, broadcast_blocks)``
      returns the (Q, l_tile) f32 distance tile for one step — the ONLY
      thing an engine writes; the driver owns the slab-range masking and
      the sub-chunk min, and nothing but the (LB, Q, Lpad/sub) minima
      ever reaches HBM.

    The kernel writes each step's minima as an (Lpad/sub, Q) block
    (:func:`subchunk_min`): its last dim is the whole query axis, which
    Mosaic accepts at every ``l_tile`` the planner returns; the driver
    swaps the two axes back outside the kernel (an XLA pass over the
    minima only, 1/sub of the tile).

    The q_pad is taken from ``resident[0].shape[1]`` (every engine's
    first resident operand carries the query axis)."""
    lb = tiled[0].shape[0]
    l_pad = tiled[0].shape[2]
    q_pad = resident[0].shape[1]
    validate_scan_shapes(name, q_pad, l_pad, l_tile)
    n_res, n_til = len(resident), len(tiled)

    def kernel(bounds_ref, *refs):
        b = pl.program_id(0)
        t = pl.program_id(1)
        res = [refs[i][0] for i in range(n_res)]
        til = [refs[n_res + i][0] for i in range(n_til)]
        bc = [refs[n_res + n_til + i][...] for i in range(len(broadcast))]
        o_ref = refs[-1]
        d2 = tile_fn(res, til, bc)
        d2 = mask_slab_range(d2, t * l_tile, bounds_ref[b, 0],
                             bounds_ref[b, 1])
        o_ref[0] = subchunk_min(d2, sub)

    def _res_spec(a):
        nd = a.ndim
        return pl.BlockSpec(
            (1,) + a.shape[1:],
            lambda b, t, bnd, _nd=nd: (b,) + (0,) * (_nd - 1),
        )

    def _til_spec(a):
        return pl.BlockSpec(
            (1, a.shape[1], l_tile), lambda b, t, bnd: (b, 0, t)
        )

    def _bc_spec(a):
        nd = a.ndim
        return pl.BlockSpec(
            a.shape, lambda b, t, bnd, _nd=nd: (0,) * _nd
        )

    in_specs = (
        [_res_spec(a) for a in resident]
        + [_til_spec(a) for a in tiled]
        + [_bc_spec(a) for a in broadcast]
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(lb, l_pad // l_tile),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, l_tile // sub, q_pad),
                                   lambda b, t, bnd: (b, t, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct(
            (lb, l_pad // sub, q_pad), jnp.float32
        ),
        interpret=interpret,
        name=name,
    )(bounds.astype(jnp.int32), *resident, *tiled, *broadcast)
    return jnp.swapaxes(out, 1, 2)
