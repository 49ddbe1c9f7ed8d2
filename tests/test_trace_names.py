"""The device scopes of the search programs and the names a profile
reduction reads them by (docs/observability.md "Spans and scopes").

Every op the grouped IVF program (``ivf.*``; flat and SQ, XLA scan and
Pallas kernel, materialized and streamed partials), the list-sharded
mesh program around it (``mnmg.*``) and the fused brute-force program
(``knn.*``; DMA rescore, gather rescore, tiled) are traced from must
carry one of its module's scopes in its compiled HLO ``op_name`` — so
that a profile can sum device time per part after any refactor — and the
benchmark's reduction must spell the span, scope and program names
exactly as the program defines them.
"""

import collections
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.comms import (
    build_comms, mnmg_ivf_flat, mnmg_ivf_flat_build_distributed, shard_rows,
)
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


@pytest.fixture(scope="module")
def mnmg_index(dataset):
    comms = build_comms(jax.devices()[:4])
    xg, n_valid = shard_rows(comms, dataset[0])
    return comms, mnmg_ivf_flat_build_distributed(
        comms, xg, IVFFlatParams(n_lists=16, kmeans_n_iters=2,
                                 kmeans_init="random"),
        n_valid=n_valid, metric="sqeuclidean")


# ops the compiler makes in the mesh program's frames (the shard_map's
# and the inlined grouped program's: loop plumbing, constants split into
# broadcasts), which carry a frame's or an instruction's name and stand
# behind no traced op
_PLUMBING = ("constant", "parameter", "get-tuple-element", "tuple", "copy",
             "broadcast")


@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["xla", "kernel"])
def test_mesh_program_ops_carry_a_scope(dataset, mnmg_index, use_pallas):
    """The sharded search program has its own fixed name, and each op
    traced from its body carries an ``mnmg.*`` scope (its global probe
    and cross-shard merge) or an ``ivf.*`` one (the grouped body it runs
    on each shard)."""
    comms, index = mnmg_index
    fn, args, _ = mnmg_ivf_flat._prepare_flat_family(
        comms, index, comms.replicate(dataset[1]), 4, sq=False,
        n_probes=4, qcap=8, list_block=4, qcap_max_drop_frac=None,
        donate_queries=False, shard_mask=None, failover=None,
        overprobe=2.0, merge_ways=None, mutation=None, wire="bf16",
        use_pallas=use_pallas, rerank_ratio=4.0)
    text = fn.lower(*args).compile().as_text()
    program = mnmg_ivf_flat.PROGRAM_NAMES[False]
    assert text.startswith(f"HloModule jit_{program},"), text[:80]
    scopes = mnmg_ivf_flat.MNMG_SCOPES + ivf_flat.GROUPED_SCOPES
    seen, counts, bare = _scoped_ops(text, program, scopes)
    assert seen > 100, seen
    assert set(counts) == set(scopes), counts
    frame = re.compile(rf"jit\({program}\)/shard_map"
                       r"(/jit\(_grouped_impl\))?(/[a-z-]+\.\d+)?$")
    assert all(frame.match(b) for b in bare), sorted(set(bare))[:10]
    made = [line for line in text.splitlines()
            if any(f'op_name="{b}"' in line for b in set(bare))]
    kinds = {re.search(r" ([a-z-]+)\(", line.split(" = ", 1)[1]).group(1)
             for line in made}
    assert kinds <= set(_PLUMBING) | {"fusion"}, kinds
    assert all("broadcast" in line for line in made if " fusion(" in line)


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


def test_benchmark_reads_the_mesh_program_by_its_name():
    """The four-chip cell's device readers name the sharded program as
    the program names itself."""
    import importlib.util
    from pathlib import Path

    name = f"jit_{mnmg_ivf_flat.PROGRAM_NAMES[False]}"
    metrics = Path(__file__).resolve().parents[1] / "benchmark" / "metrics"
    readers = sorted(metrics.glob("mnmg.*.py"))
    programs = set()
    for path in readers:
        spec = importlib.util.spec_from_file_location(path.stem, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        if hasattr(mod, "PROGRAM"):
            programs.add(mod.PROGRAM)
    assert len(readers) >= 3 and programs == {name}, programs
