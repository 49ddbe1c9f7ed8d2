"""The device scopes of the search programs and the names a profile
reduction reads them by (docs/observability.md "Spans and scopes").

Every op the grouped IVF program (``ivf.*``; flat and SQ, XLA scan and
Pallas kernel, materialized and streamed partials) and the fused
brute-force program (``knn.*``; DMA rescore, gather rescore, tiled) are
traced from must carry one of its module's scopes in its compiled HLO
``op_name`` — so that a profile can sum device time per part after any
refactor — and the benchmark's reduction must spell the span and scope
names exactly as the program defines them.
"""

import collections
import re

import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.distance.distance_type import DistanceType
from raft_tpu.serving import executor
from raft_tpu.spatial import fused_knn
from raft_tpu.spatial.ann import (
    IVFFlatParams, IVFSQParams, ivf_flat, ivf_flat_build, ivf_sq,
    ivf_sq_build,
)

_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _scoped_ops(hlo_text: str, program: str, scopes):
    """(ops traced from ``program``, {scope: count}, [unscoped op_names])
    over a compiled module's HLO text."""
    seen, counts, bare = 0, collections.Counter(), []
    for line in hlo_text.splitlines():
        m = _OP_NAME.search(line)
        if not m or not m.group(1).startswith(f"jit({program})/"):
            continue
        seen += 1
        parts = [p for p in m.group(1).split("/") if p in scopes]
        if parts:
            counts[parts[-1]] += 1
        else:
            bare.append(m.group(1))
    return seen, counts, bare


@pytest.fixture(scope="module")
def dataset():
    rng = np.random.default_rng(3)
    x = rng.standard_normal((3000, 16)).astype(np.float32)
    q = rng.standard_normal((16, 16)).astype(np.float32)
    return x, q


@pytest.fixture(scope="module")
def flat_index(dataset):
    return ivf_flat_build(dataset[0], IVFFlatParams(
        n_lists=16, kmeans_n_iters=2, kmeans_init="random",
    ), metric="sqeuclidean")


@pytest.fixture(scope="module")
def sq_index(dataset):
    return ivf_sq_build(dataset[0], IVFSQParams(n_lists=16,
                                                kmeans_n_iters=2))


GROUPED_VARIANTS = {
    "xla": dict(use_pallas=False),
    "xla_streamed": dict(use_pallas=False, stream_partials=True),
    "kernel": dict(use_pallas=True, pallas_interpret=True),
    "kernel_streamed": dict(use_pallas=True, pallas_interpret=True,
                            stream_partials=True),
}


@pytest.mark.parametrize("variant", sorted(GROUPED_VARIANTS))
@pytest.mark.parametrize("mode", ["flat", "sq"])
def test_grouped_program_ops_carry_a_scope(dataset, flat_index, sq_index,
                                           variant, mode):
    _, q = dataset
    kw = dict(GROUPED_VARIANTS[variant])
    if mode == "flat":
        index = flat_index
    else:
        index = ivf_sq._flat_view(sq_index)
        kw["dequant"] = (jnp.asarray(sq_index.vmin, jnp.float32),
                         jnp.asarray(sq_index.vscale, jnp.float32))
    text = ivf_flat._grouped_impl.lower(
        index, jnp.asarray(q), 4, 4, 8, 4, **kw).compile().as_text()
    seen, counts, bare = _scoped_ops(text, "_grouped_impl",
                                     ivf_flat.GROUPED_SCOPES)
    assert seen > 50, seen
    assert not bare, bare[:10]
    assert set(counts) == set(ivf_flat.GROUPED_SCOPES), counts


KNN_VARIANTS = {
    "dma": (128, {}),
    "dma_tiled": (128, dict(rescore_rows=8)),
    "gather_chunks": (128, dict(gather_rows=False)),
    "gather_rows": (96, dict(gather_rows=True)),
}


@pytest.mark.parametrize("variant", sorted(KNN_VARIANTS))
def test_brute_force_program_ops_carry_a_scope(variant):
    d, kw = KNN_VARIANTS[variant]
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((5000, d)), jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((20, d)), jnp.float32)
    text = fused_knn._fused_l2_knn_impl.lower(
        q, x, 4, DistanceType.L2Expanded, bm=128, bn=256, bq2=8,
        extra_chunks=8, compute_dtype=jnp.dtype(jnp.float32),
        interpret=True, **kw).compile().as_text()
    seen, counts, bare = _scoped_ops(text, "_fused_l2_knn_impl",
                                     fused_knn.KNN_SCOPES)
    assert seen > 20, seen
    assert not bare, bare[:10]
    assert set(counts) == set(fused_knn.KNN_SCOPES), counts


def test_benchmark_reads_the_program_names():
    """The benchmark's reduction writes the names out (it reads a trace
    without the program): they must be the program's own."""
    from benchmark import program_trace

    assert program_trace.SPANS == executor.SPANS
    assert (program_trace.SCOPES[program_trace.GROUPED]
            == ivf_flat.GROUPED_SCOPES)
    assert (program_trace.SCOPES[program_trace.BRUTE_FORCE]
            == fused_knn.KNN_SCOPES)
    assert program_trace.GROUPED == f"jit_{ivf_flat._grouped_impl.__name__}"
    assert (program_trace.BRUTE_FORCE
            == f"jit_{fused_knn._fused_l2_knn_impl.__name__}")
    assert all(s.startswith(program_trace.SERVING_PREFIX)
               for s in executor.SPANS)
