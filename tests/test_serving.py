"""Serving-path surface (ISSUE r6): warmup pre-compilation, the fused
deployment-view probe set (``expand_probe_set``), the persistent
compilation cache wiring on ``Resources``, the weakref-keyed
throughput-qcap audit registry, chunk-min tie semantics, and the bench
artifact compaction helpers."""

import gc
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.spatial.ann import (
    IVFFlatParams,
    IVFPQParams,
    ivf_flat_build,
    ivf_pq_build,
)
from raft_tpu.spatial.ann import common as ann_common
from raft_tpu.spatial.ann.ivf_flat import (
    _grouped_impl,
    ivf_flat_search_grouped,
)
from raft_tpu.spatial.ann.ivf_pq import (
    _pq_grouped_impl,
    ivf_pq_search_grouped,
)

FLAT_PARAMS = IVFFlatParams(n_lists=16, kmeans_n_iters=4, seed=1)


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(3)
    x = rng.standard_normal((4000, 16)).astype(np.float32)
    q = rng.standard_normal((32, 16)).astype(np.float32)
    return x, q


@pytest.fixture(scope="module")
def flat_index(data):
    return ivf_flat_build(data[0], FLAT_PARAMS)


@pytest.fixture(scope="module")
def comms():
    from raft_tpu.comms import build_comms

    return build_comms(jax.devices()[:8])


@pytest.fixture(scope="module")
def sharded_flat(data, comms):
    from raft_tpu.comms import mnmg_ivf_flat_build

    return mnmg_ivf_flat_build(
        comms, data[0], FLAT_PARAMS, metric="sqeuclidean"
    )


# ---------------------------------------------------------------- warmup
class TestWarmup:
    def test_static_qcap_is_shape_only(self):
        assert ann_common.static_qcap(None, 64, 8, 16) == \
            ann_common.default_qcap(64, 8, 16)
        assert ann_common.static_qcap("throughput", 64, 8, 16) == \
            ann_common.throughput_qcap(64, 8, 16)
        assert ann_common.static_qcap(12, 64, 8, 16) == 12
        with pytest.raises(Exception):
            ann_common.static_qcap(1.5, 64, 8, 16)
        with pytest.raises(Exception):
            ann_common.static_qcap(True, 64, 8, 16)

    def test_flat_warmup_precompiles_serving_program(self, flat_index,
                                                     data):
        qc = flat_index.warmup(32, k=5, n_probes=4)
        assert qc == ann_common.static_qcap(None, 32, 4, 16)
        warmed = _grouped_impl._cache_size()
        v, i = ivf_flat_search_grouped(
            flat_index, data[1], 5, n_probes=4, qcap=qc
        )
        # the warmed program IS the serving program: the real batch must
        # not trace or compile anything new
        assert _grouped_impl._cache_size() == warmed
        assert v.shape == (32, 5) and i.shape == (32, 5)

    def test_pq_warmup_precompiles_serving_program(self, data):
        pq = ivf_pq_build(data[0], IVFPQParams(
            n_lists=16, pq_dim=4, kmeans_n_iters=4, seed=1,
        ))
        qc = pq.warmup(32, k=5, n_probes=4, refine_ratio=2.0)
        warmed = _pq_grouped_impl._cache_size()
        v, i = ivf_pq_search_grouped(
            pq, data[1], 5, n_probes=4, qcap=qc, refine_ratio=2.0,
        )
        assert _pq_grouped_impl._cache_size() == warmed
        assert v.shape == (32, 5)

    def test_mnmg_flat_warmup_then_serve(self, comms, sharded_flat, data):
        from raft_tpu.comms import mnmg_ivf_flat_search

        qc = sharded_flat.warmup(comms, 32, k=5, n_probes=4)
        v, i = mnmg_ivf_flat_search(
            comms, sharded_flat, data[1], 5, n_probes=4, qcap=qc
        )
        assert v.shape == (32, 5)
        assert bool(jnp.all(i >= 0))


# ------------------------------------------- fused deployment-view probe
class TestExpandProbeSet:
    def test_far_extra_centroids_do_not_change_results(self, comms,
                                                       sharded_flat,
                                                       data):
        from raft_tpu.comms import expand_probe_set, mnmg_ivf_flat_search

        _, q = data
        rng = np.random.default_rng(11)
        far = (1e4 + rng.standard_normal((64, 16))).astype(np.float32)
        eidx = expand_probe_set(sharded_flat, far)
        assert eidx.centroids.shape[0] == \
            sharded_flat.centroids.shape[0] + 64
        assert int(eidx.owner[-1]) == -1
        v0, i0 = mnmg_ivf_flat_search(
            comms, sharded_flat, q, 5, n_probes=4, qcap=8
        )
        # the fused program probes the deployment-scale set; far-away
        # unowned centroids are never in any query's top probes, so the
        # shard's answers are unchanged
        v1, i1 = mnmg_ivf_flat_search(comms, eidx, q, 5, n_probes=4,
                                      qcap=8)
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
        np.testing.assert_allclose(np.asarray(v0), np.asarray(v1),
                                   rtol=1e-6)

    def test_donated_queries_dispatch(self, comms, sharded_flat, data):
        from raft_tpu.comms import mnmg_ivf_flat_search

        _, q = data
        v0, i0 = mnmg_ivf_flat_search(
            comms, sharded_flat, q, 5, n_probes=4, qcap=8
        )
        # serving mode: fresh buffer per dispatch, donated to the runtime
        v1, i1 = mnmg_ivf_flat_search(
            comms, sharded_flat, jnp.asarray(q), 5, n_probes=4, qcap=8,
            donate_queries=True,
        )
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))

    def test_dimension_mismatch_rejected(self, sharded_flat):
        from raft_tpu.comms import expand_probe_set

        with pytest.raises(Exception):
            expand_probe_set(sharded_flat, np.zeros((4, 7), np.float32))


# ------------------------------------------- persistent compilation cache
class TestCompilationCache:
    def test_resources_arg_enables_and_populates(self, tmp_path,
                                                 monkeypatch):
        from raft_tpu import compat
        from raft_tpu.core import (
            Resources,
            compilation_cache_dir,
            enable_compilation_cache,
        )
        from raft_tpu.core import resources as resources_mod

        cache = str(tmp_path / "xla_cache")
        # the cache is process-global config: capture the pre-test state
        # (CI runs the suite with its own cache dir exported) so teardown
        # RESTORES it — hardcoding None here would silently disable the
        # persistent cache for every later test in this process
        prior = {
            "jax_compilation_cache_dir":
                jax.config.jax_compilation_cache_dir,
            "jax_persistent_cache_min_compile_time_secs":
                jax.config.jax_persistent_cache_min_compile_time_secs,
            "jax_persistent_cache_min_entry_size_bytes":
                jax.config.jax_persistent_cache_min_entry_size_bytes,
        }
        prior_enabled = resources_mod._cache_dir_enabled
        try:
            # the environment places the cache when it names one
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cache + "_env")
            assert enable_compilation_cache(cache) == cache + "_env"
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
            Resources(compilation_cache_dir=cache)
            assert compilation_cache_dir() == cache

            @jax.jit
            def f(x):
                return x * 2.0 + 1.0

            f(jnp.arange(128.0)).block_until_ready()
            n_files = sum(len(fs) for _, _, fs in os.walk(cache))
            assert n_files > 0
            # idempotent re-enable (the serving bootstrap path calls it
            # once per Resources construction)
            enable_compilation_cache(cache)
            assert compilation_cache_dir() == cache
        finally:
            for name, val in prior.items():
                jax.config.update(name, val)
            compat.compilation_cache_reset()
            resources_mod._cache_dir_enabled = prior_enabled


# -------------------------------------- weakref-keyed throughput audit
class TestThroughputAuditRegistry:
    def test_registry_weakref_evicts_dead_entries(self):
        reg = ann_common._AuditRegistry()
        a = jnp.arange(8.0)
        sig = (16, 4, 8, 64)
        reg.add(a, sig)
        assert reg.seen(a, sig)
        assert not reg.seen(a, (1, 1, 1, 1))
        del a
        gc.collect()
        assert not reg._by_id

    def test_rebuilt_same_shape_index_is_reaudited(self, data,
                                                   monkeypatch):
        x, q = data
        calls = []
        orig = ann_common.probe_drop_stats

        def counting(*a, **k):
            calls.append(1)
            return orig(*a, **k)

        monkeypatch.setattr(ann_common, "probe_drop_stats", counting)

        def build_and_search():
            idx = ivf_flat_build(x, FLAT_PARAMS)
            ivf_flat_search_grouped(idx, q, 5, n_probes=4,
                                    qcap="throughput")
            return idx

        idx = build_and_search()
        n_first = len(calls)
        assert n_first >= 1
        # second search on the SAME index: audited once per process
        ivf_flat_search_grouped(idx, q, 5, n_probes=4, qcap="throughput")
        assert len(calls) == n_first
        # free the index, rebuild at the identical shape: the audit must
        # fire again — an id()-keyed registry can silently skip it when
        # the new centroids array lands on the recycled id
        del idx
        gc.collect()
        build_and_search()
        assert len(calls) == 2 * n_first


# --------------------------------------------- chunk-min tie semantics
class TestChunkMinTies:
    def test_duplicated_centroid_rows_value_multiset_matches_topk(self):
        # duplicated centroid rows (what max_list_cap splitting creates)
        # make exact distance ties; chunk-min may order ties differently
        # than lax.top_k's lowest-index tiebreak, but the selected VALUE
        # multiset must match exactly (docs/ivf_scale.md)
        rng = np.random.default_rng(5)
        base = rng.standard_normal((256, 8)).astype(np.float32)
        cents = np.repeat(base, 8, axis=0)                 # (2048, 8)
        q = rng.standard_normal((6, 8)).astype(np.float32)
        d2 = (
            (q ** 2).sum(1)[:, None] + (cents ** 2).sum(1)[None, :]
            - 2.0 * q @ cents.T
        ).astype(np.float32)
        k = 10
        from raft_tpu.spatial.selection import chunk_min_select_k

        # the chunk path must actually engage (not the top_k fallback)
        assert d2.shape[1] % 128 == 0 and d2.shape[1] // 128 >= k
        v, i = chunk_min_select_k(jnp.asarray(d2), k)
        tv, _ = jax.lax.top_k(-jnp.asarray(d2), k)
        v, i, tv = np.asarray(v), np.asarray(i), -np.asarray(tv)
        np.testing.assert_array_equal(np.sort(v, axis=1),
                                      np.sort(tv, axis=1))
        # returned indices address the returned values
        np.testing.assert_array_equal(
            np.take_along_axis(d2, i, axis=1), v
        )


# --------------------------------------------- bench artifact compaction
class TestBenchArtifact:
    @pytest.fixture(scope="class")
    def benchtop(self):
        import importlib.util

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        spec = importlib.util.spec_from_file_location(
            "benchtop", os.path.join(root, "bench.py")
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_compact_drops_prose_and_rounds(self, benchtop):
        row = {
            "metric": "m", "value": 123.456, "unit": "QPS",
            "spread": 0.2, "note": "prose", "qcap": "throughput (=24)",
            "vs_prev_qcap8_qps": 1.01,
            "extras": [{
                "metric": "e", "value": 10.12345, "bf16_note": "x",
                "rows": [{"engine": "fused_knn", "nq": 1,
                          "p50_ms": 0.123456, "qcap": 8}],
            }],
        }
        c = benchtop._compact(row)
        assert "note" not in c and "qcap" not in c
        assert c["value"] == 123.5
        assert c["vs_prev_qcap8_qps"] == 1.01
        sub = c["extras"][0]
        assert "bf16_note" not in sub
        assert sub["rows"][0] == {"engine": "fused_knn", "nq": 1,
                                  "p50_ms": 0.1235, "qcap": 8}
        # the whole compact line stays printable well under the driver cap
        import json

        assert len(json.dumps(c)) < 1800

    def test_fit_line_roundtrips_and_fits_cap(self, benchtop):
        """The r5 parsed=null regression: an over-long doc must be
        trimmed key-by-key until the printed line json.loads-round-trips
        under the driver cap, preserving every row's primary value."""
        import json

        doc = {
            "metric": "pairwise", "value": 101.5, "unit": "GFLOPS",
            "spread": 0.01, "repeats": 3,
            "extras": [
                {
                    "metric": f"extra_{i}", "value": 1000.0 + i,
                    "unit": "QPS", "spread": 0.02, "repeats": 7,
                    "recall_at_10": 0.95, "build_s": 100.0,
                    "build_warm_s": 2.0, "qcap8_qps": 9e4,
                    "measured_chip_qps": 1.2e4, "sharded_e2e_qps": 1.1e4,
                    "brute_force_same_shape_qps": 1.5e5,
                    "vs_prev": 1.01, "vs_prev_qcap8_qps": 0.99,
                    "vs_prev_build_warm_s": 1.0,
                }
                for i in range(14)
            ],
        }
        line = benchtop._fit_line(doc)
        parsed = json.loads(line)                 # round-trips
        assert len(line) <= 1800
        assert parsed["value"] == 101.5
        vals = [e["value"] for e in parsed["extras"]]
        assert vals == [1000.0 + i for i in range(14)]
        # trimming never touches the primary regression fields
        assert all("vs_prev" in e for e in parsed["extras"])

    def test_fit_line_small_doc_untrimmed(self, benchtop):
        import json

        doc = {"metric": "m", "value": 1.0, "unit": "QPS",
               "spread": 0.1, "repeats": 3}
        line = benchtop._fit_line(doc)
        assert json.loads(line) == benchtop._compact(doc)

    def test_vs_prev_significance_stamp(self, benchtop):
        prev = {"m": {"value": 112.0}}
        noisy = benchtop._stamp_vs_prev(
            {"metric": "m", "value": 118.0, "spread": 0.2}, prev
        )
        assert noisy["vs_prev_significant"] is False
        clear = benchtop._stamp_vs_prev(
            {"metric": "m", "value": 150.0, "spread": 0.05}, prev
        )
        assert "vs_prev_significant" not in clear


# ------------------------------------------------- latency sweep surface
def test_serving_latency_rows_tiny_config():
    from bench.bench_serving import serving_latency_rows

    out = serving_latency_rows(
        n=8192, d=8, k=4, n_probes=4, n_lists=8, nqs=(1, 4),
        engines=("ivf_flat",), chain=(1, 3), escalate=0,
        hedged=False, overload=False, mixed=False, open_loop=False,
        zipf=False,       # the zipf_hot_traffic row has its own smoke
        cold_tier=False,  # (tests/test_result_cache.py); the cold_tier
        self_heal=False,  # row's smoke lives in tests/test_tier.py, the
        graph=False,      # self_heal row's in tests/test_chaos.py, the
        durable=False,    # graph_ann + durable_ingest rows' below
    )
    assert out["unit"] == "ms"
    assert [r["nq"] for r in out["rows"]] == [1, 4]
    for r in out["rows"]:
        assert r["engine"] == "ivf_flat"
        assert ("p50_ms" in r) or ("error" in r)
        assert "qcap" in r


def test_graph_ann_row_tiny_config():
    """The graph-ANN row on a tiny CPU config (docs/graph_ann.md
    "Bench"): both arms must produce p50 + recall stamps, the served
    beam/degree/iters must be stamped, and the beam sweep must land
    recall within the 0.01 acceptance band of the in-row IVF baseline
    (p50 ordering itself is hardware territory — the CPU drive proves
    the measurement, not the win)."""
    from bench.bench_serving import graph_ann_row

    rng = np.random.default_rng(9)
    x = rng.standard_normal((4096, 8)).astype(np.float32)
    q = x[::17][:64] + 0.05 * rng.standard_normal((64, 8)).astype(
        np.float32
    )
    idx = ivf_flat_build(x, IVFFlatParams(n_lists=8, kmeans_n_iters=3,
                                          seed=2))
    row = graph_ann_row(x, q, idx, k=4, n_probes=4, degree=8,
                        beams=(8, 16, 32), n_recall_q=32,
                        chain=(1, 3), escalate=0)
    assert row["scenario"] == "graph_ann" and row["engine"] == "graph"
    assert row["nq"] == 1
    assert row["degree"] == 8 and row["beam"] in (8, 16, 32)
    assert isinstance(row["iters"], int) and row["iters"] >= 4
    assert ("p50_ms" in row) or ("error" in row)
    assert "ivf_recall_at_10" in row and "recall_at_10" in row
    assert row["recall_at_10"] >= row["ivf_recall_at_10"] - 0.01


def test_durable_ingest_row_tiny_config():
    """The durable-WAL ingest row on a tiny CPU config
    (docs/robustness.md "Durability"): both arms must stamp acked QPS,
    the ratio must be a positive quotient of them, the fsync sweep must
    carry one point per swept interval with real fsyncs counted, and
    the WAL throughput stamp must be positive (the ratio's 0.8
    acceptance is hardware territory — the CPU drive proves the
    measurement, not the win)."""
    from bench.bench_serving import durable_ingest_row

    rng = np.random.default_rng(13)
    x = rng.standard_normal((4096, 8)).astype(np.float32)
    q = x[::31][:32] + 0.05 * rng.standard_normal((32, 8)).astype(
        np.float32
    )
    idx = ivf_flat_build(x, IVFFlatParams(n_lists=8, kmeans_n_iters=3,
                                          seed=4))
    row = durable_ingest_row(idx, q, ingest_batch=16, n_batches=6,
                             delta_cap=32,
                             fsync_intervals_ms=(0.0, 1.0))
    assert row["scenario"] == "durable_ingest"
    assert row["engine"] == "ivf_flat"
    assert row["durable_qps"] > 0 and row["nondurable_qps"] > 0
    assert row["durability_ratio"] == pytest.approx(
        row["durable_qps"] / row["nondurable_qps"], rel=1e-2
    )
    assert row["fsync_interval_ms"] in (0.0, 1.0)
    assert row["fsync_p50_ms"] >= 0.0 and row["wal_mb_per_s"] > 0
    assert len(row["fsync_sweep"]) == 2
    for pt in row["fsync_sweep"]:
        assert pt["n_fsyncs"] >= 1          # every ack rode an fsync


def test_serving_resilience_rows_tiny_config():
    """The hedged-straggler and 2x-overload rows on a tiny CPU config:
    the hedge must cut the injected straggler's p99 (acceptance), and
    overload must SHED (RaftOverloadError accounting) with the queue
    bounded rather than collapsing."""
    import jax as _jax

    from bench.bench_serving import hedged_straggler_row, overload_row
    from raft_tpu.spatial.ann.ivf_flat import ivf_flat_search_grouped

    rng = np.random.default_rng(5)
    x = rng.standard_normal((4096, 8)).astype(np.float32)
    idx = ivf_flat_build(x, IVFFlatParams(n_lists=8, kmeans_n_iters=3,
                                          seed=2))
    nq = 8
    qcap = idx.warmup(nq, k=4, n_probes=4)
    qb = jnp.asarray(
        rng.standard_normal((nq, 8)).astype(np.float32)
    )

    def run(qq):
        return ivf_flat_search_grouped(idx, qq, 4, n_probes=4, qcap=qcap)

    _jax.block_until_ready(run(qb))
    hrow = hedged_straggler_row(run, qb, straggler_every=4,
                                n_requests=24)
    assert hrow["scenario"] == "hedged_straggler"
    assert hrow["p99_ms"] > 0 and hrow["hedged_p99_ms"] > 0
    # the injected straggler dominates the unhedged tail; the hedge
    # must cut it (generous margin — CI hosts are noisy)
    assert hrow["hedged_p99_ms"] < hrow["p99_ms"]

    orow = overload_row(run, qb, over_factor=2.0, n_requests=48,
                        max_queue=2)
    assert orow["scenario"] == "overload_2x"
    assert orow["shed_rate"] > 0.0          # it shed rather than queued
    assert orow["queue_peak"] <= 2 + 1      # bounded, never collapsed
    assert orow["timed_out"] == 0


def test_round6_bench_line_parses(benchtop_module=None):
    """ISSUE 5 satellite (extended for the r6 PQ-kernel round): the
    current artifact shape — the r5 extras, the serving resilience
    rows, plus this round's ``escalations``/``adc_engine`` stamps —
    must print as a line that json.loads-round-trips under the
    1800-char driver cap (r5 shipped parsed=null; the _fit_line
    self-check is asserted HERE, not left for the driver to
    discover)."""
    import importlib.util
    import json

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "benchtop_r6", os.path.join(root, "bench.py")
    )
    benchtop = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(benchtop)

    serving_rows = [
        {"engine": e, "nq": nq, "p50_ms": 1.2345, "spread": 0.08,
         "repeats": 5, "qcap": 24}
        for e in ("fused_knn", "ivf_flat", "ivf_pq")
        for nq in (1, 128, 1024)
    ] + [
        {"engine": "ivf_flat", "scenario": "hedged_straggler", "nq": 128,
         "p50_ms": 1.9, "p99_ms": 31.4, "hedged_p99_ms": 6.2,
         "hedge_delay_ms": 3.1, "straggler_every": 8,
         "straggler_ms": 25.0, "n_requests": 64},
        {"engine": "ivf_flat", "scenario": "overload_2x", "nq": 128,
         "p50_ms": 2.0, "offered_x": 2.0, "shed_rate": 0.47,
         "max_queue": 4, "n_requests": 96, "queue_peak": 5,
         "timed_out": 0, "p99_ms": 22.7},
    ]
    extras = [
        {"metric": f"extra_{i}", "value": 10000.0 + i, "unit": "QPS",
         "spread": 0.05, "repeats": 7, "escalations": 1,
         "adc_engine": "pallas", "recall_at_10": 0.95,
         "build_s": 150.0, "build_warm_s": 2.0, "qcap8_qps": 1.2e5,
         "measured_chip_qps": 1.1e4, "sharded_e2e_qps": 1.05e4,
         "probe_recall_vs_flat": 0.997, "probe_flop_ratio": 5.2,
         "brute_force_same_shape_qps": 1.5e5, "vs_prev": 1.01,
         "vs_prev_qcap8_qps": 0.99, "vs_prev_build_warm_s": 1.0,
         "note": "prose that must be dropped from the printed line"}
        for i in range(8)
    ] + [
        {"metric": "serving_p50_500000x96_k10_p16", "unit": "ms",
         "rows": serving_rows},
        {"metric": "warm_start_build_500000x96", "unit": "s",
         "value": 3.1, "cold_cache_build_s": 140.0, "build_warm_s": 1.9,
         "within_2x_warm": True},
    ]
    doc = {
        "metric": "pairwise_l2_expanded_8192x8192x512_f32",
        "value": 101000.5, "unit": "GFLOPS", "spread": 0.01,
        "repeats": 3, "f32_highest_gflops": 55000.2,
        "vs_baseline": 10.1, "vs_prev": 1.0,
        "extras": extras,
    }
    line = benchtop._fit_line(doc)
    parsed = json.loads(line)               # round-trips
    assert len(line) <= 1800
    assert isinstance(parsed, dict)
    assert parsed["value"] == 101000.5
    # every extra's primary value survives the trim
    vals = [e.get("value") for e in parsed["extras"]
            if "value" in e]
    assert vals[:8] == [10000.0 + i for i in range(8)]


def test_retired_shard_keys_never_print(benchtop_module=None):
    """ISSUE 8 satellite: the modeled-projection keys retired in PR 4
    (``probe_global_ms`` / ``projected_100m_qps`` / ``merge8_ms``) were
    still showing in BENCH_r05's shard rows. They must be stripped from
    every printed row — and from prior-round rows before vs_prev
    stamping — so a stale artifact can never resurrect them."""
    import importlib.util

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "benchtop_retired", os.path.join(root, "bench.py")
    )
    benchtop = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(benchtop)

    row = {
        "metric": "mnmg_ivf_flat_shard_12500000x96_q16384_k10_p16",
        "value": 50620.9, "unit": "QPS", "spread": 0.014,
        "merge8_ms": 0.45, "probe_global_ms": 50.45,
        "projected_100m_qps": 93002.5, "qcap8_qps": 130789.3,
        "vs_prev_projected_100m_qps": 1.01,
        "extras": [{"metric": "e", "value": 1.0,
                    "probe_global_ms": 50.19}],
    }
    c = benchtop._compact(row)
    for key in ("probe_global_ms", "projected_100m_qps", "merge8_ms",
                "vs_prev_projected_100m_qps"):
        assert key not in c, key
    assert "probe_global_ms" not in c["extras"][0]
    assert c["qcap8_qps"] == 130789.3          # measured keys survive
    # the retired keys are not in the print whitelist either
    for key in benchtop._RETIRED_KEYS:
        assert key not in benchtop._PRINT_KEYS


def test_round8_bench_line_parses_with_open_loop():
    """ISSUE 8 satellite (the _fit_line parse/cap test extended): the
    round-8 artifact shape — every prior row PLUS the open-loop
    executor row — must print as a line that json.loads-round-trips
    under the 1800-char driver cap, with the open-loop acceptance keys
    (saturation vs program ratio, p99 at 80/95% of saturation)
    surviving every trim stage."""
    import importlib.util
    import json

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "benchtop_r8", os.path.join(root, "bench.py")
    )
    benchtop = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(benchtop)

    serving_rows = [
        {"engine": e, "nq": nq, "p50_ms": 1.2345, "spread": 0.08,
         "repeats": 5, "qcap": 24}
        for e in ("fused_knn", "ivf_flat", "ivf_pq")
        for nq in (1, 128, 1024)
    ] + [
        {"engine": "ivf_flat", "scenario": "hedged_straggler", "nq": 128,
         "p50_ms": 1.9, "p99_ms": 31.4, "hedged_p99_ms": 6.2,
         "n_requests": 64},
        {"engine": "ivf_flat", "scenario": "overload_2x", "nq": 128,
         "p50_ms": 2.0, "shed_rate": 0.47, "p99_ms": 22.7},
        {"engine": "ivf_flat", "scenario": "mixed_ingest", "nq": 128,
         "ingest_batch": 256, "qcap": 24, "frozen_qps": 52000.0,
         "ingest_qps": 310000.0, "mixed_search_qps": 45000.0,
         "spread": 0.06, "repeats": 5, "escalations": 1,
         "qps_ratio_vs_frozen": 0.865, "upsert_visible_ms": 4.2,
         "delete_masked_ms": 2.9},
        {"engine": "ivf_flat", "scenario": "open_loop", "nq": 1024,
         "program_qps": 610000.0, "saturation_qps": 512000.0,
         "qps_ratio_vs_program": 0.839, "spread": 0.04, "repeats": 5,
         "p50_ms_50": 2.4, "p99_ms_50": 5.1, "p50_ms_80": 3.0,
         "p99_ms_80": 7.9, "p50_ms_95": 4.2, "p99_ms_95": 14.6,
         "shed_rate_95": 0.012, "max_in_flight": 4,
         "request_size": 16, "n_requests": 256},
    ]
    extras = [
        {"metric": f"extra_{i}", "value": 10000.0 + i, "unit": "QPS",
         "spread": 0.05, "repeats": 7, "escalations": 1,
         "adc_engine": "pallas", "recall_at_10": 0.95,
         "build_s": 150.0, "build_warm_s": 2.0, "qcap8_qps": 1.2e5,
         "measured_chip_qps": 1.1e4, "sharded_e2e_qps": 1.05e4,
         "probe_recall_vs_flat": 0.997, "probe_flop_ratio": 5.2,
         "brute_force_same_shape_qps": 1.5e5, "vs_prev": 1.01,
         "vs_prev_qcap8_qps": 0.99, "vs_prev_build_warm_s": 1.0}
        for i in range(8)
    ] + [
        {"metric": "serving_p50_500000x96_k10_p16", "unit": "ms",
         "rows": serving_rows},
        {"metric": "warm_start_build_500000x96", "unit": "s",
         "value": 3.1, "build_warm_s": 1.9, "within_2x_warm": True},
    ]
    doc = {
        "metric": "pairwise_l2_expanded_8192x8192x512_f32",
        "value": 101000.5, "unit": "GFLOPS", "spread": 0.01,
        "repeats": 3, "f32_highest_gflops": 55000.2,
        "vs_baseline": 10.1, "vs_prev": 1.0,
        "extras": extras,
    }
    line = benchtop._fit_line(doc)
    parsed = json.loads(line)               # round-trips
    assert len(line) <= 1800
    assert isinstance(parsed, dict)
    # the open-loop acceptance keys survive whatever trimming was
    # needed — they are not in _TRIM_ORDER, and only fall with "rows"
    if any("rows" in e for e in parsed.get("extras", [])):
        srv = next(e for e in parsed["extras"] if "rows" in e)
        orow = next(r for r in srv["rows"]
                    if r.get("scenario") == "open_loop")
        assert orow["qps_ratio_vs_program"] == 0.839
        assert orow["p99_ms_95"] == 14.6 and orow["p99_ms_80"] == 7.9
        assert "saturation_qps" in orow and "program_qps" in orow


def test_round9_bench_line_parses_with_cross_host():
    """ISSUE 9 satellite (the _fit_line parse/cap test extended,
    following the r05-r08 pattern): the round-9 artifact shape — every
    prior row PLUS the cross-host serving row — must print as a line
    that json.loads-round-trips under the 1800-char driver cap, with
    the cross-host acceptance keys (e2e QPS, dcn_bytes_ratio, the
    zero-retrace host-flip audit) surviving every trim stage."""
    import importlib.util
    import json

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "benchtop_r9", os.path.join(root, "bench.py")
    )
    benchtop = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(benchtop)

    serving_rows = [
        {"engine": e, "nq": nq, "p50_ms": 1.2345, "spread": 0.08,
         "repeats": 5, "qcap": 24}
        for e in ("fused_knn", "ivf_flat", "ivf_pq")
        for nq in (1, 128, 1024)
    ] + [
        {"engine": "ivf_flat", "scenario": "hedged_straggler", "nq": 128,
         "p50_ms": 1.9, "p99_ms": 31.4, "hedged_p99_ms": 6.2,
         "n_requests": 64},
        {"engine": "ivf_flat", "scenario": "overload_2x", "nq": 128,
         "p50_ms": 2.0, "shed_rate": 0.47, "p99_ms": 22.7},
        {"engine": "ivf_flat", "scenario": "mixed_ingest", "nq": 128,
         "frozen_qps": 52000.0, "ingest_qps": 310000.0,
         "mixed_search_qps": 45000.0, "spread": 0.06, "repeats": 5,
         "qps_ratio_vs_frozen": 0.865, "upsert_visible_ms": 4.2,
         "delete_masked_ms": 2.9},
        {"engine": "ivf_flat", "scenario": "open_loop", "nq": 1024,
         "program_qps": 610000.0, "saturation_qps": 512000.0,
         "qps_ratio_vs_program": 0.839, "spread": 0.04, "repeats": 5,
         "p50_ms_50": 2.4, "p99_ms_50": 5.1, "p50_ms_80": 3.0,
         "p99_ms_80": 7.9, "p50_ms_95": 4.2, "p99_ms_95": 14.6,
         "shed_rate_95": 0.012},
    ]
    extras = [
        {"metric": f"extra_{i}", "value": 10000.0 + i, "unit": "QPS",
         "spread": 0.05, "repeats": 7, "escalations": 1,
         "adc_engine": "pallas", "recall_at_10": 0.95,
         "build_s": 150.0, "build_warm_s": 2.0, "qcap8_qps": 1.2e5,
         "measured_chip_qps": 1.1e4, "sharded_e2e_qps": 1.05e4,
         "probe_recall_vs_flat": 0.997, "probe_flop_ratio": 5.2,
         "brute_force_same_shape_qps": 1.5e5, "vs_prev": 1.01,
         "vs_prev_qcap8_qps": 0.99, "vs_prev_build_warm_s": 1.0}
        for i in range(8)
    ] + [
        # the round-9 cross-host row, every key cross_host_row emits
        {"metric": "mnmg_cross_host_131072x64_q512_k10_hostsim_2x4",
         "value": 48123.4, "unit": "QPS", "spread": 0.07, "repeats": 5,
         "escalations": 1, "flat_e2e_qps": 50620.9,
         "qps_ratio_vs_flat": 0.951, "wire": "bf16",
         "dcn_bytes_per_query": 100.0,
         "flat_dcn_bytes_per_query": 320.0, "dcn_bytes_ratio": 3.2,
         "merge_ms_hier": 0.42, "merge_ms_flat": 0.31,
         "health_flip_retraces": 0, "coverage_host_down": 1.0,
         "host_down_bitident": True, "vs_prev": 1.0,
         "vs_prev_flat_e2e_qps": 1.0},
        {"metric": "serving_p50_500000x96_k10_p16", "unit": "ms",
         "rows": serving_rows},
        {"metric": "warm_start_build_500000x96", "unit": "s",
         "value": 3.1, "build_warm_s": 1.9, "within_2x_warm": True},
    ]
    doc = {
        "metric": "pairwise_l2_expanded_8192x8192x512_f32",
        "value": 101000.5, "unit": "GFLOPS", "spread": 0.01,
        "repeats": 3, "f32_highest_gflops": 55000.2,
        "vs_baseline": 10.1, "vs_prev": 1.0,
        "extras": extras,
    }
    line = benchtop._fit_line(doc)
    parsed = json.loads(line)               # round-trips
    assert len(line) <= 1800
    assert isinstance(parsed, dict)
    xrow = next((e for e in parsed["extras"]
                 if str(e.get("metric", "")).startswith(
                     "mnmg_cross_host")), None)
    assert xrow is not None
    assert xrow["value"] == 48123.4         # primary survives any trim
    # the acceptance keys are not in _TRIM_ORDER and print whitelisted,
    # so they only fall at the last-resort _core_projection
    if "dcn_bytes_ratio" in xrow:           # not core-projected
        assert xrow["dcn_bytes_ratio"] == 3.2
        assert xrow["qps_ratio_vs_flat"] == 0.951
        assert xrow["health_flip_retraces"] == 0
        assert xrow["coverage_host_down"] == 1.0
        assert xrow["host_down_bitident"] is True
    for key in ("dcn_bytes_ratio", "qps_ratio_vs_flat",
                "health_flip_retraces", "coverage_host_down",
                "host_down_bitident"):
        assert key not in benchtop._TRIM_ORDER
        assert key in benchtop._PRINT_KEYS
    # ... and the row's _compact projection always carries them (the
    # full-row pre-trim shape, the retired-keys test's sibling check)
    c = benchtop._compact(extras[8])
    for key in ("value", "dcn_bytes_ratio", "qps_ratio_vs_flat",
                "health_flip_retraces", "coverage_host_down",
                "host_down_bitident", "wire"):
        assert key in c, key


def test_mixed_ingest_row_tiny_config():
    """ISSUE 7: the mixed read/write row on a tiny CPU config — frozen
    vs under-ingest search QPS (ratio stamped), sustained ingest QPS,
    and the upsert→visible / delete→masked latencies, all through
    chained_dispatch_stats (escalations stamped)."""
    from bench.bench_serving import mixed_ingest_row

    rng = np.random.default_rng(9)
    x = rng.standard_normal((4096, 8)).astype(np.float32)
    idx = ivf_flat_build(
        x, IVFFlatParams(n_lists=8, kmeans_n_iters=3, seed=2),
        metric="sqeuclidean",
    )
    qb = jnp.asarray(x[:8] + 0.01)
    row = mixed_ingest_row(idx, qb, k=4, n_probes=4, ingest_batch=16,
                           chain=(1, 3), escalate=0)
    assert row["scenario"] == "mixed_ingest"
    assert row["ingest_batch"] == 16
    assert "error" not in row
    for key in ("frozen_qps", "mixed_search_qps", "qps_ratio_vs_frozen",
                "ingest_qps", "escalations", "spread",
                "upsert_visible_ms", "delete_masked_ms"):
        assert key in row, key
    assert row["mixed_search_qps"] > 0 and row["frozen_qps"] > 0
    assert row["upsert_visible_ms"] > 0
    assert row["delete_masked_ms"] > 0


def test_round7_bench_line_parses_with_mixed_ingest():
    """ISSUE 7 satellite (the _fit_line parse/cap test extended): the
    round-7 artifact shape — every prior row PLUS the mixed_ingest
    serving row — must print as a line that json.loads-round-trips
    under the 1800-char driver cap, with the mutation row's headline
    ratio surviving every trim stage."""
    import importlib.util
    import json

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "benchtop_r7", os.path.join(root, "bench.py")
    )
    benchtop = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(benchtop)

    serving_rows = [
        {"engine": e, "nq": nq, "p50_ms": 1.2345, "spread": 0.08,
         "repeats": 5, "qcap": 24}
        for e in ("fused_knn", "ivf_flat", "ivf_pq")
        for nq in (1, 128, 1024)
    ] + [
        {"engine": "ivf_flat", "scenario": "hedged_straggler", "nq": 128,
         "p50_ms": 1.9, "p99_ms": 31.4, "hedged_p99_ms": 6.2,
         "n_requests": 64},
        {"engine": "ivf_flat", "scenario": "overload_2x", "nq": 128,
         "p50_ms": 2.0, "shed_rate": 0.47, "p99_ms": 22.7},
        {"engine": "ivf_flat", "scenario": "mixed_ingest", "nq": 128,
         "ingest_batch": 256, "qcap": 24, "frozen_qps": 52000.0,
         "ingest_qps": 310000.0, "mixed_search_qps": 45000.0,
         "spread": 0.06, "repeats": 5, "escalations": 1,
         "qps_ratio_vs_frozen": 0.865, "upsert_visible_ms": 4.2,
         "delete_masked_ms": 2.9},
    ]
    extras = [
        {"metric": f"extra_{i}", "value": 10000.0 + i, "unit": "QPS",
         "spread": 0.05, "repeats": 7, "escalations": 1,
         "adc_engine": "pallas", "recall_at_10": 0.95,
         "build_s": 150.0, "build_warm_s": 2.0, "qcap8_qps": 1.2e5,
         "measured_chip_qps": 1.1e4, "sharded_e2e_qps": 1.05e4,
         "probe_recall_vs_flat": 0.997, "probe_flop_ratio": 5.2,
         "brute_force_same_shape_qps": 1.5e5, "vs_prev": 1.01,
         "vs_prev_qcap8_qps": 0.99, "vs_prev_build_warm_s": 1.0}
        for i in range(8)
    ] + [
        {"metric": "serving_p50_500000x96_k10_p16", "unit": "ms",
         "rows": serving_rows},
        {"metric": "warm_start_build_500000x96", "unit": "s",
         "value": 3.1, "build_warm_s": 1.9, "within_2x_warm": True},
    ]
    doc = {
        "metric": "pairwise_l2_expanded_8192x8192x512_f32",
        "value": 101000.5, "unit": "GFLOPS", "spread": 0.01,
        "repeats": 3, "f32_highest_gflops": 55000.2,
        "vs_baseline": 10.1, "vs_prev": 1.0,
        "extras": extras,
    }
    line = benchtop._fit_line(doc)
    parsed = json.loads(line)               # round-trips
    assert len(line) <= 1800
    assert isinstance(parsed, dict)
    # the headline ratio survives whatever trimming was needed — it is
    # not in _TRIM_ORDER, and mixed_search_qps only falls with "rows"
    if any("rows" in e for e in parsed.get("extras", [])):
        srv = next(e for e in parsed["extras"] if "rows" in e)
        mrow = next(r for r in srv["rows"]
                    if r.get("scenario") == "mixed_ingest")
        assert mrow["qps_ratio_vs_frozen"] == 0.865
        assert "mixed_search_qps" in mrow


def test_round10_bench_line_parses_with_flat_scan_kernel():
    """ISSUE 10 satellite (the _fit_line parse/cap test extended,
    following the r05-r09 pattern): the round-10 artifact shape — every
    prior row PLUS the flat_scan_kernel acceptance row and the
    ``scan_engine`` stamp on the flat shard row — must print as a line
    that json.loads-round-trips under the 1800-char driver cap, with
    the acceptance keys (kernel-vs-XLA speedup, the engine stamp,
    recall at both engines' operating point) surviving every trim
    stage short of the last-resort core projection."""
    import importlib.util
    import json

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "benchtop_r10", os.path.join(root, "bench.py")
    )
    benchtop = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(benchtop)

    serving_rows = [
        {"engine": e, "nq": nq, "p50_ms": 1.2345, "spread": 0.08,
         "repeats": 5, "qcap": 24}
        for e in ("fused_knn", "ivf_flat", "ivf_pq")
        for nq in (1, 128, 1024)
    ] + [
        {"engine": "ivf_flat", "scenario": "mixed_ingest", "nq": 128,
         "frozen_qps": 52000.0, "ingest_qps": 310000.0,
         "mixed_search_qps": 45000.0, "spread": 0.06, "repeats": 5,
         "qps_ratio_vs_frozen": 0.865, "upsert_visible_ms": 4.2,
         "delete_masked_ms": 2.9},
        {"engine": "ivf_flat", "scenario": "open_loop", "nq": 1024,
         "program_qps": 610000.0, "saturation_qps": 512000.0,
         "qps_ratio_vs_program": 0.839, "spread": 0.04, "repeats": 5,
         "p50_ms_95": 4.2, "p99_ms_95": 14.6, "shed_rate_95": 0.012},
    ]
    extras = [
        {"metric": f"extra_{i}", "value": 10000.0 + i, "unit": "QPS",
         "spread": 0.05, "repeats": 7, "escalations": 1,
         "adc_engine": "pallas", "recall_at_10": 0.95,
         "build_s": 150.0, "build_warm_s": 2.0, "qcap8_qps": 1.2e5,
         "measured_chip_qps": 1.1e4, "sharded_e2e_qps": 1.05e4,
         "probe_recall_vs_flat": 0.997, "probe_flop_ratio": 5.2,
         "brute_force_same_shape_qps": 1.5e5, "vs_prev": 1.01,
         "vs_prev_qcap8_qps": 0.99, "vs_prev_build_warm_s": 1.0}
        for i in range(7)
    ] + [
        # the round-10 acceptance row, every key extra_flat_scan_kernel
        # emits
        {"metric": "flat_scan_kernel_500000x96_q4096_k10_p16",
         "value": 104321.5, "unit": "QPS", "spread": 0.04, "repeats": 7,
         "escalations": 1, "scan_engine": "pallas",
         "recall_at_10": 0.9994, "xla_qps": 50620.9,
         "xla_recall_at_10": 0.9994, "xla_spread": 0.05,
         "speedup": 2.06, "vs_prev": 1.0, "vs_prev_xla_qps": 1.0},
        # the flat 100M-shard row now stamps its scan engine
        {"metric": "mnmg_ivf_flat_shard_12500000x96_q16384_k10_p16",
         "value": 50620.9, "unit": "QPS", "spread": 0.014, "repeats": 7,
         "escalations": 1, "scan_engine": "pallas",
         "recall_at_10_vs_shard": 0.9994, "build_s": 180.0,
         "qcap8_qps": 130789.3, "measured_chip_qps": 1.2e5,
         "sharded_e2e_qps": 1.1e5, "probe_recall_vs_flat": 0.997,
         "probe_flop_ratio": 5.2, "vs_prev": 1.05},
        {"metric": "mnmg_cross_host_131072x64_q512_k10_hostsim_2x4",
         "value": 48123.4, "unit": "QPS", "spread": 0.07,
         "flat_e2e_qps": 50620.9, "qps_ratio_vs_flat": 0.951,
         "wire": "bf16", "dcn_bytes_ratio": 3.2,
         "health_flip_retraces": 0, "coverage_host_down": 1.0,
         "host_down_bitident": True},
        {"metric": "serving_p50_500000x96_k10_p16", "unit": "ms",
         "rows": serving_rows},
        {"metric": "warm_start_build_500000x96", "unit": "s",
         "value": 3.1, "build_warm_s": 1.9, "within_2x_warm": True},
    ]
    doc = {
        "metric": "pairwise_l2_expanded_8192x8192x512_f32",
        "value": 101000.5, "unit": "GFLOPS", "spread": 0.01,
        "repeats": 3, "f32_highest_gflops": 55000.2,
        "vs_baseline": 10.1, "vs_prev": 1.0,
        "extras": extras,
    }
    line = benchtop._fit_line(doc)
    parsed = json.loads(line)               # round-trips
    assert len(line) <= 1800
    assert isinstance(parsed, dict)
    krow = next((e for e in parsed["extras"]
                 if str(e.get("metric", "")).startswith(
                     "flat_scan_kernel")), None)
    assert krow is not None
    assert krow["value"] == 104321.5        # primary survives any trim
    # the acceptance keys are not in _TRIM_ORDER and print whitelisted,
    # so they only fall at the last-resort _core_projection
    if "speedup" in krow:                   # not core-projected
        assert krow["speedup"] == 2.06
        assert krow["scan_engine"] == "pallas"
        assert krow["recall_at_10"] == 0.9994
    for key in ("speedup", "scan_engine", "recall_at_10"):
        assert key not in benchtop._TRIM_ORDER
        assert key in benchtop._PRINT_KEYS
    # xla_qps IS trimmable (speedup carries the acceptance signal), and
    # it is companion-tracked round-over-round
    assert "xla_qps" in benchtop._TRIM_ORDER
    assert "xla_qps" in benchtop._COMPANIONS
    # the rows' _compact projections always carry the stamps pre-trim
    c = benchtop._compact(extras[7])
    for key in ("value", "scan_engine", "speedup", "xla_qps",
                "xla_recall_at_10"):
        assert key in c, key
    assert benchtop._compact(extras[8])["scan_engine"] == "pallas"


def test_round11_bench_line_parses_with_sq_scan_kernel():
    """ISSUE 11 satellite (the _fit_line parse/cap test extended,
    following the r05-r10 pattern): the round-11 artifact shape — every
    prior row PLUS the sq_scan_kernel acceptance row (the int8
    dequant+scan engine vs its XLA dequant path) and the
    ``probe_kernel`` stamp on both shard rows — must print as a line
    that json.loads-round-trips under the 1800-char driver cap, with
    the acceptance keys (kernel-vs-XLA speedup, the scan_engine stamp,
    recall at both engines' operating point) surviving every trim
    stage short of the last-resort core projection. ``probe_kernel``
    is deliberately TRIMMABLE (a secondary stamp — the speedup rows
    carry the acceptance signal) but prints whitelisted."""
    import importlib.util
    import json

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "benchtop_r11", os.path.join(root, "bench.py")
    )
    benchtop = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(benchtop)

    serving_rows = [
        {"engine": e, "nq": nq, "p50_ms": 1.2345, "spread": 0.08,
         "repeats": 5, "qcap": 24}
        for e in ("fused_knn", "ivf_flat", "ivf_pq")
        for nq in (1, 128, 1024)
    ] + [
        {"engine": "ivf_flat", "scenario": "open_loop", "nq": 1024,
         "program_qps": 610000.0, "saturation_qps": 512000.0,
         "qps_ratio_vs_program": 0.839, "spread": 0.04, "repeats": 5,
         "p50_ms_95": 4.2, "p99_ms_95": 14.6, "shed_rate_95": 0.012},
    ]
    extras = [
        {"metric": f"extra_{i}", "value": 10000.0 + i, "unit": "QPS",
         "spread": 0.05, "repeats": 7, "escalations": 1,
         "adc_engine": "pallas", "recall_at_10": 0.95,
         "build_s": 150.0, "build_warm_s": 2.0, "qcap8_qps": 1.2e5,
         "measured_chip_qps": 1.1e4, "sharded_e2e_qps": 1.05e4,
         "probe_recall_vs_flat": 0.997, "probe_flop_ratio": 5.2,
         "brute_force_same_shape_qps": 1.5e5, "vs_prev": 1.01,
         "vs_prev_qcap8_qps": 0.99, "vs_prev_build_warm_s": 1.0}
        for i in range(6)
    ] + [
        # the round-10 flat acceptance row, unchanged
        {"metric": "flat_scan_kernel_500000x96_q4096_k10_p16",
         "value": 104321.5, "unit": "QPS", "spread": 0.04, "repeats": 7,
         "escalations": 1, "scan_engine": "pallas",
         "recall_at_10": 0.9994, "xla_qps": 50620.9,
         "xla_recall_at_10": 0.9994, "speedup": 2.06, "vs_prev": 1.0},
        # the round-11 acceptance row, every key extra_sq_scan_kernel
        # emits
        {"metric": "sq_scan_kernel_500000x96_q4096_k10_p16",
         "value": 98765.4, "unit": "QPS", "spread": 0.04, "repeats": 7,
         "escalations": 1, "scan_engine": "pallas",
         "recall_at_10": 0.9987, "xla_qps": 31234.5,
         "xla_recall_at_10": 0.9988, "xla_spread": 0.05,
         "speedup": 3.16, "index_gb": 0.05},
        # both shard rows now stamp the probe engine too
        {"metric": "mnmg_ivf_flat_shard_12500000x96_q16384_k10_p16",
         "value": 50620.9, "unit": "QPS", "spread": 0.014, "repeats": 7,
         "escalations": 1, "scan_engine": "pallas",
         "probe_kernel": "pallas",
         "recall_at_10_vs_shard": 0.9994, "build_s": 180.0,
         "qcap8_qps": 130789.3, "measured_chip_qps": 1.2e5,
         "sharded_e2e_qps": 1.1e5, "probe_recall_vs_flat": 0.997,
         "probe_flop_ratio": 5.2, "vs_prev": 1.05},
        {"metric": "mnmg_ivf_pq_shard_12500000x96_q16384_k10_p16",
         "value": 11900.0, "unit": "QPS", "spread": 0.02, "repeats": 7,
         "adc_engine": "pallas", "probe_kernel": "pallas",
         "recall_at_10_vs_shard": 0.9575, "qcap8_qps": 15500.0,
         "measured_chip_qps": 1.0e4, "sharded_e2e_qps": 0.95e4,
         "probe_recall_vs_flat": 0.997, "vs_prev": 1.0},
        {"metric": "serving_p50_500000x96_k10_p16", "unit": "ms",
         "rows": serving_rows},
        {"metric": "warm_start_build_500000x96", "unit": "s",
         "value": 3.1, "build_warm_s": 1.9, "within_2x_warm": True},
    ]
    doc = {
        "metric": "pairwise_l2_expanded_8192x8192x512_f32",
        "value": 101000.5, "unit": "GFLOPS", "spread": 0.01,
        "repeats": 3, "f32_highest_gflops": 55000.2,
        "vs_baseline": 10.1, "vs_prev": 1.0,
        "extras": extras,
    }
    line = benchtop._fit_line(doc)
    parsed = json.loads(line)               # round-trips
    assert len(line) <= 1800
    assert isinstance(parsed, dict)
    krow = next((e for e in parsed["extras"]
                 if str(e.get("metric", "")).startswith(
                     "sq_scan_kernel")), None)
    assert krow is not None
    assert krow["value"] == 98765.4         # primary survives any trim
    if "speedup" in krow:                   # not core-projected
        assert krow["speedup"] == 3.16
        assert krow["scan_engine"] == "pallas"
        assert krow["recall_at_10"] == 0.9987
    for key in ("speedup", "scan_engine", "recall_at_10"):
        assert key not in benchtop._TRIM_ORDER
        assert key in benchtop._PRINT_KEYS
    # probe_kernel prints whitelisted but IS trimmable under cap
    # pressure (the acceptance signal lives in the speedup rows)
    assert "probe_kernel" in benchtop._PRINT_KEYS
    assert "probe_kernel" in benchtop._TRIM_ORDER

def test_round12_bench_line_parses_with_program_audit_stamp():
    """ISSUE 12 satellite (the _fit_line parse/cap test extended,
    following the r05-r11 pattern): the round-12 artifact shape — every
    prior row PLUS the ``program_audit_ms`` stamp on the headline doc
    (the jaxpr-level contract gate's wall time, docs/static_analysis.md
    "Two tiers") — must print as a line that json.loads-round-trips
    under the 1800-char driver cap. ``program_audit_ms`` is
    deliberately TRIMMABLE (a secondary stamp: the gate's pass/fail
    lives in ci/run.sh programs, not the bench line) but prints
    whitelisted, and a red audit's ``program_audit_error`` string
    survives the _compact string filter so failures are visible on the
    driver line."""
    import importlib.util
    import json

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "benchtop_r12", os.path.join(root, "bench.py")
    )
    benchtop = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(benchtop)

    extras = [
        {"metric": f"extra_{i}", "value": 10000.0 + i, "unit": "QPS",
         "spread": 0.05, "repeats": 7, "escalations": 1,
         "adc_engine": "pallas", "recall_at_10": 0.95,
         "build_s": 150.0, "build_warm_s": 2.0, "qcap8_qps": 1.2e5,
         "measured_chip_qps": 1.1e4, "sharded_e2e_qps": 1.05e4,
         "probe_recall_vs_flat": 0.997, "probe_flop_ratio": 5.2,
         "brute_force_same_shape_qps": 1.5e5, "vs_prev": 1.01}
        for i in range(8)
    ] + [
        {"metric": "sq_scan_kernel_500000x96_q4096_k10_p16",
         "value": 98765.4, "unit": "QPS", "spread": 0.04, "repeats": 7,
         "escalations": 1, "scan_engine": "pallas",
         "recall_at_10": 0.9987, "xla_qps": 31234.5,
         "xla_recall_at_10": 0.9988, "speedup": 3.16},
        {"metric": "mnmg_ivf_flat_shard_12500000x96_q16384_k10_p16",
         "value": 50620.9, "unit": "QPS", "spread": 0.014, "repeats": 7,
         "scan_engine": "pallas", "probe_kernel": "pallas",
         "recall_at_10_vs_shard": 0.9994, "qcap8_qps": 130789.3,
         "measured_chip_qps": 1.2e5, "sharded_e2e_qps": 1.1e5,
         "vs_prev": 1.05},
    ]
    doc = {
        "metric": "pairwise_l2_expanded_8192x8192x512_f32",
        "value": 101000.5, "unit": "GFLOPS", "spread": 0.01,
        "repeats": 3, "f32_highest_gflops": 55000.2,
        # the round-12 stamp under test
        "program_audit_ms": 34193.2,
        "vs_baseline": 10.1, "vs_prev": 1.0,
        "extras": extras,
    }
    line = benchtop._fit_line(doc)
    parsed = json.loads(line)               # round-trips
    assert len(line) <= 1800
    assert isinstance(parsed, dict)
    # the stamp prints when the line has room...
    small = benchtop._fit_line({
        "metric": "pairwise_l2_expanded_8192x8192x512_f32",
        "value": 101000.5, "unit": "GFLOPS",
        "program_audit_ms": 34193.2, "extras": [],
    })
    assert json.loads(small)["program_audit_ms"] == 34193.2
    # ...is whitelisted-but-trimmable (the r11 acceptance keys are not)
    assert "program_audit_ms" in benchtop._PRINT_KEYS
    assert "program_audit_ms" in benchtop._TRIM_ORDER
    for key in ("speedup", "scan_engine", "recall_at_10"):
        assert key not in benchtop._TRIM_ORDER
        assert key in benchtop._PRINT_KEYS
    # a red audit's error string survives the _compact string filter
    err = benchtop._compact({
        "metric": "m", "program_audit_error": "exit 1: drift",
    })
    assert err["program_audit_error"] == "exit 1: drift"
    # and the stamp helper exists with the subprocess contract
    assert callable(benchtop._program_audit_stamp)


def test_round13_bench_line_parses_with_obs_overhead():
    """ISSUE 13 satellite (the _fit_line parse/cap test extended,
    following the r05-r12 pattern): the round-13 artifact shape — every
    prior row PLUS the open-loop row's ``obs_overhead_pct`` stamp
    (saturation QPS with the metric registry enabled vs
    ``RAFT_TPU_OBS=off``, docs/observability.md; acceptance <= ~2%) —
    must print as a line that json.loads-round-trips under the
    1800-char driver cap. The stamp is whitelisted-but-trimmable: the
    open-loop row's saturation/ratio acceptance keys outrank it when
    the line is tight."""
    import importlib.util
    import json

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "benchtop_r13", os.path.join(root, "bench.py")
    )
    benchtop = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(benchtop)

    extras = [
        {"metric": f"extra_{i}", "value": 10000.0 + i, "unit": "QPS",
         "spread": 0.05, "repeats": 7, "escalations": 1,
         "adc_engine": "pallas", "recall_at_10": 0.95,
         "build_s": 150.0, "build_warm_s": 2.0, "qcap8_qps": 1.2e5,
         "measured_chip_qps": 1.1e4, "sharded_e2e_qps": 1.05e4,
         "probe_recall_vs_flat": 0.997, "probe_flop_ratio": 5.2,
         "brute_force_same_shape_qps": 1.5e5, "vs_prev": 1.01}
        for i in range(8)
    ] + [
        # the round-13 open-loop row shape under test
        {"metric": "open_loop_ivf_flat_500000x96", "unit": "QPS",
         "scenario": "open_loop", "engine": "ivf_flat", "nq": 1024,
         "program_qps": 1.8e5, "saturation_qps": 1.5e5,
         "qps_ratio_vs_program": 0.83, "obs_overhead_pct": 1.4,
         "spread": 0.03, "repeats": 5,
         "p50_ms_50": 3.1, "p99_ms_50": 8.5, "p50_ms_80": 4.2,
         "p99_ms_80": 14.9, "p50_ms_95": 6.8, "p99_ms_95": 31.0,
         "shed_rate_95": 0.02, "vs_prev": 1.0},
    ]
    doc = {
        "metric": "pairwise_l2_expanded_8192x8192x512_f32",
        "value": 101000.5, "unit": "GFLOPS", "spread": 0.01,
        "repeats": 3, "f32_highest_gflops": 55000.2,
        "program_audit_ms": 34193.2,
        "vs_baseline": 10.1, "vs_prev": 1.0,
        "extras": extras,
    }
    line = benchtop._fit_line(doc)
    parsed = json.loads(line)               # round-trips
    assert len(line) <= 1800
    assert isinstance(parsed, dict)
    # the stamp prints when the line has room...
    small = benchtop._fit_line({
        "metric": "open_loop_ivf_flat_500000x96", "unit": "QPS",
        "saturation_qps": 1.5e5, "obs_overhead_pct": 1.4,
        "extras": [],
    })
    assert json.loads(small)["obs_overhead_pct"] == 1.4
    # ...is whitelisted-but-trimmable; the open-loop acceptance keys
    # it annotates are not trimmable
    assert "obs_overhead_pct" in benchtop._PRINT_KEYS
    assert "obs_overhead_pct" in benchtop._TRIM_ORDER
    for key in ("saturation_qps", "qps_ratio_vs_program"):
        assert key in benchtop._PRINT_KEYS
        assert key not in benchtop._TRIM_ORDER


def test_round15_bench_line_parses_with_zipf_hot_traffic():
    """ISSUE 15 satellite (the _fit_line parse/cap test extended,
    following the r05-r13 pattern): the round-15 artifact shape — every
    prior row PLUS the ``zipf_hot_traffic`` row (cache+coalescing
    saturation vs the uncached path under a Zipf(s≈1.1) mix,
    docs/serving.md "Hot traffic") — must print as a line that
    json.loads-round-trips under the 1800-char driver cap, with the
    acceptance keys (``qps_uplift``, ``cache_hit_rate``,
    ``cached_qps``, ``p99_ms_cached``) untrimmable."""
    import importlib.util
    import json

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "benchtop_r15", os.path.join(root, "bench.py")
    )
    benchtop = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(benchtop)

    extras = [
        {"metric": f"extra_{i}", "value": 10000.0 + i, "unit": "QPS",
         "spread": 0.05, "repeats": 7, "escalations": 1,
         "adc_engine": "pallas", "recall_at_10": 0.95,
         "build_s": 150.0, "build_warm_s": 2.0, "qcap8_qps": 1.2e5,
         "measured_chip_qps": 1.1e4, "sharded_e2e_qps": 1.05e4,
         "probe_recall_vs_flat": 0.997, "probe_flop_ratio": 5.2,
         "brute_force_same_shape_qps": 1.5e5, "vs_prev": 1.01}
        for i in range(8)
    ] + [
        # the round-13 open-loop row, unchanged
        {"metric": "open_loop_ivf_flat_500000x96", "unit": "QPS",
         "scenario": "open_loop", "engine": "ivf_flat", "nq": 1024,
         "program_qps": 1.8e5, "saturation_qps": 1.5e5,
         "qps_ratio_vs_program": 0.83, "obs_overhead_pct": 1.4,
         "spread": 0.03, "repeats": 5,
         "p50_ms_80": 4.2, "p99_ms_80": 14.9, "vs_prev": 1.0},
        # the round-15 hot-traffic row under test
        {"metric": "zipf_hot_traffic_ivf_flat_500000x96",
         "unit": "QPS", "scenario": "zipf_hot_traffic",
         "engine": "ivf_flat", "nq": 1024, "zipf_s": 1.1,
         "n_templates": 64, "program_qps": 1.8e5,
         "uncached_qps": 1.5e5, "cached_qps": 3.4e5,
         "qps_uplift": 2.27, "cache_hit_rate": 0.61,
         "coalesce_rate": 0.07, "p99_ms_uncached": 14.9,
         "p99_ms_cached": 9.1, "cached_identical": True,
         "spread": 0.03, "repeats": 5, "vs_prev": 1.0},
    ]
    doc = {
        "metric": "pairwise_l2_expanded_8192x8192x512_f32",
        "value": 101000.5, "unit": "GFLOPS", "spread": 0.01,
        "repeats": 3, "f32_highest_gflops": 55000.2,
        "program_audit_ms": 34193.2,
        "vs_baseline": 10.1, "vs_prev": 1.0,
        "extras": extras,
    }
    line = benchtop._fit_line(doc)
    parsed = json.loads(line)               # round-trips
    assert len(line) <= 1800
    assert isinstance(parsed, dict)
    # on a roomy line the row prints whole, acceptance keys included
    small = benchtop._fit_line({
        "metric": "zipf_hot_traffic_ivf_flat_500000x96", "unit": "QPS",
        "cached_qps": 3.4e5, "uncached_qps": 1.5e5,
        "qps_uplift": 2.27, "cache_hit_rate": 0.61,
        "coalesce_rate": 0.07, "cached_identical": True,
        "extras": [],
    })
    small_parsed = json.loads(small)
    assert small_parsed["qps_uplift"] == 2.27
    assert small_parsed["cache_hit_rate"] == 0.61
    assert small_parsed["cached_identical"] is True
    # the acceptance evidence is untrimmable; the secondaries trim
    for key in ("cached_qps", "qps_uplift", "cache_hit_rate",
                "p99_ms_cached"):
        assert key in benchtop._PRINT_KEYS
        assert key not in benchtop._TRIM_ORDER
    for key in ("zipf_s", "n_templates", "cached_identical",
                "coalesce_rate", "p99_ms_uncached", "uncached_qps"):
        assert key in benchtop._PRINT_KEYS
        assert key in benchtop._TRIM_ORDER


def test_round17_bench_line_parses_with_cold_tier():
    """ISSUE 17 satellite (the _fit_line parse/cap test extended,
    following the r05-r15 pattern): the round-17 artifact shape — every
    prior row PLUS the ``cold_tier`` row (same index served at
    1/capacity_x the HBM budget through the popularity tier,
    docs/tiering.md "Reading the bench row") — must print as a line
    that json.loads-round-trips under the 1800-char driver cap, with
    the acceptance keys (``capacity_x``, ``recall_vs_hot``,
    ``tier_hit_rate``, ``tiered_qps``, ``qps_ratio_vs_hot``,
    ``fetch_overlap_pct``, ``tier_hit_rate_95``) untrimmable."""
    import importlib.util
    import json

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "benchtop_r17", os.path.join(root, "bench.py")
    )
    benchtop = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(benchtop)

    extras = [
        {"metric": f"extra_{i}", "value": 10000.0 + i, "unit": "QPS",
         "spread": 0.05, "repeats": 7, "escalations": 1,
         "adc_engine": "pallas", "recall_at_10": 0.95,
         "build_s": 150.0, "build_warm_s": 2.0, "qcap8_qps": 1.2e5,
         "measured_chip_qps": 1.1e4, "sharded_e2e_qps": 1.05e4,
         "probe_recall_vs_flat": 0.997, "probe_flop_ratio": 5.2,
         "brute_force_same_shape_qps": 1.5e5, "vs_prev": 1.01}
        for i in range(8)
    ] + [
        # the round-15 hot-traffic row, unchanged
        {"metric": "zipf_hot_traffic_ivf_flat_500000x96",
         "unit": "QPS", "scenario": "zipf_hot_traffic",
         "engine": "ivf_flat", "nq": 1024, "zipf_s": 1.1,
         "n_templates": 64, "program_qps": 1.8e5,
         "uncached_qps": 1.5e5, "cached_qps": 3.4e5,
         "qps_uplift": 2.27, "cache_hit_rate": 0.61,
         "coalesce_rate": 0.07, "p99_ms_uncached": 14.9,
         "p99_ms_cached": 9.1, "cached_identical": True,
         "spread": 0.03, "repeats": 5, "vs_prev": 1.0},
        # the round-17 cold-tier row under test
        {"metric": "cold_tier_ivf_flat_500000x96", "unit": "QPS",
         "scenario": "cold_tier", "engine": "ivf_flat", "nq": 1024,
         "zipf_s": 1.1, "n_templates": 64, "n_slots": 512,
         "capacity_x": 4.0, "program_qps": 1.8e5,
         "hot_qps": 1.6e5, "tiered_qps": 1.4e5,
         "qps_ratio_vs_hot": 0.875, "tier_hit_rate": 0.93,
         "tier_hit_rate_50": 0.96, "tier_hit_rate_80": 0.94,
         "tier_hit_rate_95": 0.91, "p99_ms_50": 6.1,
         "p99_ms_80": 9.8, "p99_ms_95": 15.2,
         "fetch_overlap_pct": 71.4, "tier_fetches": 812,
         "recall_vs_hot": 0.982, "tier_degraded": False,
         "spread": 0.03, "repeats": 5, "vs_prev": 1.0},
    ]
    doc = {
        "metric": "pairwise_l2_expanded_8192x8192x512_f32",
        "value": 101000.5, "unit": "GFLOPS", "spread": 0.01,
        "repeats": 3, "f32_highest_gflops": 55000.2,
        "program_audit_ms": 34193.2,
        "vs_baseline": 10.1, "vs_prev": 1.0,
        "extras": extras,
    }
    line = benchtop._fit_line(doc)
    parsed = json.loads(line)               # round-trips
    assert len(line) <= 1800
    assert isinstance(parsed, dict)
    # on a roomy line the row prints whole, acceptance keys included
    small = benchtop._fit_line({
        "metric": "cold_tier_ivf_flat_500000x96", "unit": "QPS",
        "capacity_x": 4.0, "tiered_qps": 1.4e5, "hot_qps": 1.6e5,
        "qps_ratio_vs_hot": 0.875, "tier_hit_rate": 0.93,
        "fetch_overlap_pct": 71.4, "recall_vs_hot": 0.982,
        "tier_degraded": False,
        "extras": [],
    })
    small_parsed = json.loads(small)
    assert small_parsed["capacity_x"] == 4.0
    assert small_parsed["recall_vs_hot"] == 0.982
    assert small_parsed["tier_hit_rate"] == 0.93
    assert small_parsed["tier_degraded"] is False
    # the acceptance evidence is untrimmable; the secondaries trim
    for key in ("capacity_x", "recall_vs_hot", "tier_hit_rate",
                "tiered_qps", "qps_ratio_vs_hot", "fetch_overlap_pct",
                "tier_hit_rate_95"):
        assert key in benchtop._PRINT_KEYS
        assert key not in benchtop._TRIM_ORDER
    for key in ("n_slots", "tier_fetches", "tier_degraded",
                "tier_hit_rate_50", "tier_hit_rate_80", "hot_qps"):
        assert key in benchtop._PRINT_KEYS
        assert key in benchtop._TRIM_ORDER


def test_round18_bench_line_parses_with_self_heal():
    """ISSUE 18 satellite (the _fit_line parse/cap test extended,
    following the r05-r17 pattern): the round-18 artifact shape — every
    prior row PLUS the ``self_heal`` row (scripted kill→reroute→heal→
    reintegrate under open-loop Zipf, docs/robustness.md
    "Self-healing") — must print as a line that json.loads-round-trips
    under the 1800-char driver cap, with the acceptance stamps
    (``detection_ms``, ``route_convergence_ms``, ``reintegration_ms``,
    ``healed_p99_x``, ``p99_ms_degraded``) untrimmable."""
    import importlib.util
    import json

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "benchtop_r18", os.path.join(root, "bench.py")
    )
    benchtop = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(benchtop)

    extras = [
        {"metric": f"extra_{i}", "value": 10000.0 + i, "unit": "QPS",
         "spread": 0.05, "repeats": 7, "escalations": 1,
         "adc_engine": "pallas", "recall_at_10": 0.95,
         "build_s": 150.0, "build_warm_s": 2.0, "qcap8_qps": 1.2e5,
         "measured_chip_qps": 1.1e4, "sharded_e2e_qps": 1.05e4,
         "probe_recall_vs_flat": 0.997, "probe_flop_ratio": 5.2,
         "brute_force_same_shape_qps": 1.5e5, "vs_prev": 1.01}
        for i in range(8)
    ] + [
        # the round-17 cold-tier row, unchanged
        {"metric": "cold_tier_ivf_flat_500000x96", "unit": "QPS",
         "scenario": "cold_tier", "engine": "ivf_flat", "nq": 1024,
         "zipf_s": 1.1, "n_templates": 64, "n_slots": 512,
         "capacity_x": 4.0, "program_qps": 1.8e5,
         "hot_qps": 1.6e5, "tiered_qps": 1.4e5,
         "qps_ratio_vs_hot": 0.875, "tier_hit_rate": 0.93,
         "tier_hit_rate_50": 0.96, "tier_hit_rate_80": 0.94,
         "tier_hit_rate_95": 0.91, "p99_ms_50": 6.1,
         "p99_ms_80": 9.8, "p99_ms_95": 15.2,
         "fetch_overlap_pct": 71.4, "tier_fetches": 812,
         "recall_vs_hot": 0.982, "tier_degraded": False,
         "spread": 0.03, "repeats": 5, "vs_prev": 1.0},
        # the round-18 self-heal row under test
        {"metric": "self_heal_ivf_flat_500000x96", "unit": "ms",
         "scenario": "self_heal", "engine": "ivf_flat", "nq": 8,
         "request_size": 8, "zipf_s": 1.1, "n_templates": 32,
         "replication": 2, "n_ranks": 8, "rate_rps": 210.0,
         "detection_ms": 112.4, "route_convergence_ms": 113.0,
         "reintegration_ms": 41.7, "p99_ms_healthy": 9.8,
         "p99_ms_degraded": 14.2, "p99_ms_healed": 10.1,
         "healed_p99_x": 1.03, "route_pushes": 3, "heals_ok": 1,
         "transitions": 2, "all_serving": True, "gen_lag_ms": 4.4,
         "spread": 0.03, "repeats": 5, "vs_prev": 1.0},
    ]
    doc = {
        "metric": "pairwise_l2_expanded_8192x8192x512_f32",
        "value": 101000.5, "unit": "GFLOPS", "spread": 0.01,
        "repeats": 3, "f32_highest_gflops": 55000.2,
        "program_audit_ms": 34193.2,
        "vs_baseline": 10.1, "vs_prev": 1.0,
        "extras": extras,
    }
    line = benchtop._fit_line(doc)
    parsed = json.loads(line)               # round-trips
    assert len(line) <= 1800
    assert isinstance(parsed, dict)
    # on a roomy line the row prints whole, acceptance stamps included
    small = benchtop._fit_line({
        "metric": "self_heal_ivf_flat_500000x96", "unit": "ms",
        "detection_ms": 112.4, "route_convergence_ms": 113.0,
        "reintegration_ms": 41.7, "healed_p99_x": 1.03,
        "p99_ms_degraded": 14.2, "all_serving": True,
        "extras": [],
    })
    small_parsed = json.loads(small)
    assert small_parsed["detection_ms"] == 112.4
    assert small_parsed["route_convergence_ms"] == 113.0
    assert small_parsed["reintegration_ms"] == 41.7
    assert small_parsed["healed_p99_x"] == 1.03
    # the acceptance evidence is untrimmable; the secondaries trim
    for key in ("detection_ms", "route_convergence_ms",
                "reintegration_ms", "healed_p99_x", "p99_ms_degraded"):
        assert key in benchtop._PRINT_KEYS
        assert key not in benchtop._TRIM_ORDER
    for key in ("route_pushes", "heals_ok", "transitions",
                "all_serving", "rate_rps", "gen_lag_ms",
                "p99_ms_healthy", "p99_ms_healed"):
        assert key in benchtop._PRINT_KEYS
        assert key in benchtop._TRIM_ORDER


def test_round19_bench_line_parses_with_graph_ann():
    """ISSUE 19 satellite (the _fit_line parse/cap test extended,
    following the r05-r18 pattern): the round-19 artifact shape — every
    prior row PLUS the ``graph_ann`` row (one-dispatch beam search vs
    the in-row IVF-Flat qcap-1 baseline, docs/graph_ann.md) — must
    print as a line that json.loads-round-trips under the 1800-char
    driver cap, with the acceptance stamps (``p50_ms``,
    ``recall_at_10``, ``ivf_p50_ms``, ``ivf_recall_at_10``, ``beam``,
    ``degree``, ``iters``) untrimmable."""
    import importlib.util
    import json

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "benchtop_r19", os.path.join(root, "bench.py")
    )
    benchtop = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(benchtop)

    extras = [
        {"metric": f"extra_{i}", "value": 10000.0 + i, "unit": "QPS",
         "spread": 0.05, "repeats": 7, "escalations": 1,
         "adc_engine": "pallas", "recall_at_10": 0.95,
         "build_s": 150.0, "build_warm_s": 2.0, "qcap8_qps": 1.2e5,
         "measured_chip_qps": 1.1e4, "sharded_e2e_qps": 1.05e4,
         "probe_recall_vs_flat": 0.997, "probe_flop_ratio": 5.2,
         "brute_force_same_shape_qps": 1.5e5, "vs_prev": 1.01}
        for i in range(8)
    ] + [
        # the round-18 self-heal row, unchanged
        {"metric": "self_heal_ivf_flat_500000x96", "unit": "ms",
         "scenario": "self_heal", "engine": "ivf_flat", "nq": 8,
         "rate_rps": 210.0, "detection_ms": 112.4,
         "route_convergence_ms": 113.0, "reintegration_ms": 41.7,
         "p99_ms_healthy": 9.8, "p99_ms_degraded": 14.2,
         "p99_ms_healed": 10.1, "healed_p99_x": 1.03,
         "route_pushes": 3, "heals_ok": 1, "transitions": 2,
         "all_serving": True, "gen_lag_ms": 4.4,
         "spread": 0.03, "repeats": 5, "vs_prev": 1.0},
        # the round-19 graph-ANN row under test
        {"metric": "graph_ann_500000x96", "unit": "ms",
         "scenario": "graph_ann", "engine": "graph", "nq": 1,
         "degree": 16, "beam": 32, "iters": 23,
         "p50_ms": 0.41, "recall_at_10": 0.961, "spread": 0.04,
         "repeats": 5, "ivf_p50_ms": 1.38, "ivf_recall_at_10": 0.958,
         "ivf_qcap": 8, "ivf_spread": 0.05, "vs_prev": 1.0},
    ]
    doc = {
        "metric": "pairwise_l2_expanded_8192x8192x512_f32",
        "value": 101000.5, "unit": "GFLOPS", "spread": 0.01,
        "repeats": 3, "f32_highest_gflops": 55000.2,
        "program_audit_ms": 34193.2,
        "vs_baseline": 10.1, "vs_prev": 1.0,
        "extras": extras,
    }
    line = benchtop._fit_line(doc)
    parsed = json.loads(line)               # round-trips
    assert len(line) <= 1800
    assert isinstance(parsed, dict)
    # on a roomy line the row prints whole, acceptance stamps included
    small = benchtop._fit_line({
        "metric": "graph_ann_500000x96", "unit": "ms",
        "p50_ms": 0.41, "recall_at_10": 0.961, "ivf_p50_ms": 1.38,
        "ivf_recall_at_10": 0.958, "beam": 32, "degree": 16,
        "iters": 23, "extras": [],
    })
    small_parsed = json.loads(small)
    assert small_parsed["p50_ms"] == 0.41
    assert small_parsed["ivf_p50_ms"] == 1.38
    assert small_parsed["beam"] == 32
    assert small_parsed["iters"] == 23
    # the acceptance evidence is untrimmable; the secondaries trim
    for key in ("p50_ms", "recall_at_10", "ivf_p50_ms",
                "ivf_recall_at_10", "beam", "degree", "iters"):
        assert key in benchtop._PRINT_KEYS
        assert key not in benchtop._TRIM_ORDER
    for key in ("ivf_qcap", "ivf_spread"):
        assert key in benchtop._PRINT_KEYS
        assert key in benchtop._TRIM_ORDER


def test_round20_bench_line_parses_with_durable_ingest():
    """ISSUE 20 satellite (the _fit_line parse/cap test extended,
    following the r05-r19 pattern): the round-20 artifact shape — every
    prior row PLUS the ``durable_ingest`` row (fsync-durable acked QPS
    vs the non-durable apply, docs/robustness.md "Durability") — must
    print as a line that json.loads-round-trips under the 1800-char
    driver cap, with the acceptance stamps (``durable_qps``,
    ``nondurable_qps``, ``durability_ratio``) untrimmable."""
    import importlib.util
    import json

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "benchtop_r20", os.path.join(root, "bench.py")
    )
    benchtop = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(benchtop)

    extras = [
        {"metric": f"extra_{i}", "value": 10000.0 + i, "unit": "QPS",
         "spread": 0.05, "repeats": 7, "escalations": 1,
         "adc_engine": "pallas", "recall_at_10": 0.95,
         "build_s": 150.0, "build_warm_s": 2.0, "qcap8_qps": 1.2e5,
         "measured_chip_qps": 1.1e4, "sharded_e2e_qps": 1.05e4,
         "probe_recall_vs_flat": 0.997, "probe_flop_ratio": 5.2,
         "brute_force_same_shape_qps": 1.5e5, "vs_prev": 1.01}
        for i in range(8)
    ] + [
        # the round-19 graph-ANN row, unchanged
        {"metric": "graph_ann_500000x96", "unit": "ms",
         "scenario": "graph_ann", "engine": "graph", "nq": 1,
         "degree": 16, "beam": 32, "iters": 23,
         "p50_ms": 0.41, "recall_at_10": 0.961, "spread": 0.04,
         "repeats": 5, "ivf_p50_ms": 1.38, "ivf_recall_at_10": 0.958,
         "ivf_qcap": 8, "ivf_spread": 0.05, "vs_prev": 1.0},
        # the round-20 durable-ingest row under test
        {"metric": "durable_ingest_500000x96", "unit": "QPS",
         "scenario": "durable_ingest", "engine": "ivf_flat",
         "durable_qps": 38500.0, "nondurable_qps": 41200.0,
         "durability_ratio": 0.934, "fsync_interval_ms": 0.0,
         "fsync_p50_ms": 0.071, "wal_mb_per_s": 18.4,
         "spread": 0.03, "repeats": 5, "vs_prev": 1.0},
    ]
    doc = {
        "metric": "pairwise_l2_expanded_8192x8192x512_f32",
        "value": 101000.5, "unit": "GFLOPS", "spread": 0.01,
        "repeats": 3, "f32_highest_gflops": 55000.2,
        "program_audit_ms": 34193.2,
        "vs_baseline": 10.1, "vs_prev": 1.0,
        "extras": extras,
    }
    line = benchtop._fit_line(doc)
    parsed = json.loads(line)               # round-trips
    assert len(line) <= 1800
    assert isinstance(parsed, dict)
    # on a roomy line the row prints whole, acceptance stamps included
    small = benchtop._fit_line({
        "metric": "durable_ingest_500000x96", "unit": "QPS",
        "durable_qps": 38500.0, "nondurable_qps": 41200.0,
        "durability_ratio": 0.934, "fsync_interval_ms": 0.0,
        "fsync_p50_ms": 0.071, "wal_mb_per_s": 18.4, "extras": [],
    })
    small_parsed = json.loads(small)
    assert small_parsed["durable_qps"] == 38500.0
    assert small_parsed["nondurable_qps"] == 41200.0
    assert small_parsed["durability_ratio"] == 0.934
    # the acceptance evidence is untrimmable; the secondaries trim
    for key in ("durable_qps", "nondurable_qps", "durability_ratio"):
        assert key in benchtop._PRINT_KEYS
        assert key not in benchtop._TRIM_ORDER
    for key in ("fsync_interval_ms", "fsync_p50_ms", "wal_mb_per_s"):
        assert key in benchtop._PRINT_KEYS
        assert key in benchtop._TRIM_ORDER
