"""Compile-only checks for the TPU v5e: the scan kernels, the fused
chunk-min kernel and the DMA rescore kernel at the widths the served
paths use, compiled for a described chip with no chip attached (a second
or two each). Interpret mode — what the rest of the suite runs — cannot
see what Mosaic refuses: unaligned blocks, unsupported reshapes or
compares, VMEM overruns. Each test asserts the compiled program holds a
Pallas kernel.

The topology is described inside a module fixture, never at import: one
process at a time may load the TPU library, and every xdist worker
imports this file."""

import os

import jax
import jax.numpy as jnp
import pytest

from raft_tpu.spatial.ann import (
    flat_kernel, graph_kernel, pq_kernel, scan_core, sq_kernel,
)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from raft_tpu import compat

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out
    prior = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compat.compilation_cache_reset()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prior)
    compat.compilation_cache_reset()


def _compile_has_kernel(sharding, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


LB, D = 4, 96


# every q_pad multiple of 16 the latency/throughput plans reach at d=96,
# at each tile width plan_l_tile can return from a 1024 start
@pytest.mark.parametrize("q_pad,l_tile", [
    (16, 1024), (16, 384), (16, 128), (48, 512), (128, 256),
])
def test_flat_scan_kernel_compiles(one_chip, q_pad, l_tile):
    _compile_has_kernel(
        one_chip,
        lambda q, s, b: flat_kernel.flat_scan_subchunk_min(
            q, s, b, interpret=False, l_tile=l_tile),
        ((LB, q_pad, D), jnp.bfloat16), ((LB, D, 2 * l_tile), jnp.bfloat16),
        ((LB, 2), jnp.int32),
    )


@pytest.mark.parametrize("q_pad", [16, 128])
def test_sq_scan_kernel_compiles(one_chip, q_pad):
    lt = sq_kernel.plan_l_tile(D, q_pad)
    _compile_has_kernel(
        one_chip,
        lambda q, s, b, vm, vs: sq_kernel.sq_scan_subchunk_min(
            q, s, b, vm, vs, interpret=False, l_tile=lt),
        ((LB, q_pad, D), jnp.bfloat16), ((LB, D, 2 * lt), jnp.int8),
        ((LB, 2), jnp.int32), ((D,), jnp.float32), ((D,), jnp.float32),
    )


@pytest.mark.parametrize("pq_dim,pq_bits,q_pad", [
    (24, 8, 16), (48, 8, 128), (48, 4, 16),
])
def test_pq_adc_kernel_compiles(one_chip, pq_dim, pq_bits, q_pad):
    mk = pq_dim * (1 << pq_bits)
    lt = pq_kernel.plan_l_tile(mk, q_pad)
    _compile_has_kernel(
        one_chip,
        lambda lut, c, b: pq_kernel.pq_adc_subchunk_min(
            lut, c, b, interpret=False, l_tile=lt),
        ((LB, q_pad, mk), jnp.bfloat16), ((LB, pq_dim, 2 * lt), jnp.uint8),
        ((LB, 2), jnp.int32),
    )


def test_graph_beam_kernel_compiles(one_chip):
    lt = graph_kernel.plan_l_tile(D, scan_core.Q_GRANULE)
    _compile_has_kernel(
        one_chip,
        lambda q, c, b: graph_kernel.beam_scan_subchunk_min(
            q, c, b, interpret=False, l_tile=lt),
        ((LB, scan_core.Q_GRANULE, D), jnp.bfloat16),
        ((LB, D, 2 * lt), jnp.bfloat16), ((LB, 2), jnp.int32),
    )


def test_fused_chunk_min_kernel_compiles(one_chip):
    from raft_tpu.spatial import fused_knn

    bm, bn, n = 1024, 2048, 8 * 2048
    _compile_has_kernel(
        one_chip,
        lambda q, y: fused_knn._chunk_mins(
            q, y, n_valid=n - 5, bm=bm, bn=bn,
            compute_dtype=jnp.dtype(jnp.float32), interpret=False),
        ((bm, D), jnp.float32), ((n, D), jnp.bfloat16),
    )


def test_dma_rescore_kernel_compiles_at_d768(one_chip):
    from raft_tpu.spatial import fused_knn

    m, c, d = 64, 24, 768
    _compile_has_kernel(
        one_chip,
        lambda q, cids, y: fused_knn._rescore_scores(
            q, cids, y, c=c, interpret=False),
        ((m, d), jnp.float32), ((m, c), jnp.int32),
        ((64 * 128, d), jnp.bfloat16),
    )
