"""The reduction from a trace to device metrics: on hand-made traces
whose answers are counted by hand, and on a small trace recorded on a
TPU v5 lite (``data/v5e_trace.json.gz``: two runs of the grouped
IVF-Flat program on 64 queries and one brute-force call on 1,024, over
200k x 96 bf16 rows, each inside the harness's spans)."""

from pathlib import Path

import pytest

from benchmark import trace

MS = 1_000_000  # ns


def _trace(ops, modules=(), spans=()):
    return {"devices": [{"name": "/device:TPU:0", "ops": list(ops),
                         "modules": list(modules)}],
            "spans": [["bench.window", 0, 100 * MS], *spans]}


def test_names():
    assert trace.op_name("%copy.65 = bf16[2,96]{1,0} copy(...)") == \
        "copy.65"
    assert trace.base_name("flat_scan_subchunk_min.6") == \
        "flat_scan_subchunk_min"
    assert trace.base_name("jit__grouped_impl(7684464383155046416)") == \
        "jit__grouped_impl"


def test_busy_is_the_union_and_nesting_counts_once():
    ops = [["%while.1 = (...) while(...)", 10 * MS, 40 * MS],
           ["%fusion.2 = f32[] fusion()", 15 * MS, 10 * MS],   # nested
           ["%k.3 = f32[] custom-call()", 30 * MS, 5 * MS],     # nested
           ["%copy.4 = f32[] copy()", 45 * MS, 10 * MS],        # overlaps
           ["%all-reduce.5 = f32[] all-reduce()", 70 * MS, 5 * MS],
           ["%copy.6 = f32[] copy()", 95 * MS, 20 * MS]]        # clipped
    s = trace.reduce(_trace(ops))
    # [10, 55] + [70, 75] + [95, 100] = 45 + 5 + 5 ms
    assert s.window_s == pytest.approx(0.1)
    assert s.busy_s == pytest.approx(0.055)
    assert s.idle_share == pytest.approx(0.45)
    # self time: the while less its two children and the copy's overlap
    assert s.op_self_s["while.1"] == pytest.approx(0.020)
    assert sum(s.op_self_s.values()) == pytest.approx(s.busy_s)
    assert s.op_self_s["fusion.2"] == pytest.approx(0.010)
    assert s.kernel_s["k"] == pytest.approx(0.005)
    assert s.kernel_s["copy"] == pytest.approx(0.015)
    assert s.collective_s == pytest.approx(0.005)


def test_modules_and_gaps_named_by_host_span():
    ops = [["%a.1 = f32[] fusion()", 0, 20 * MS],
           ["%k.2 = f32[] custom-call()", 5 * MS, 10 * MS],
           ["%a.1 = f32[] fusion()", 60 * MS, 50 * MS],
           ["%k.2 = f32[] custom-call()", 65 * MS, 10 * MS],
           ["%k.2 = f32[] custom-call()", 85 * MS, 30 * MS]]
    # the last run reaches past the window: it is not counted whole
    modules = [["jit__grouped_impl(1)", 0, 20 * MS],
               ["jit__grouped_impl(1)", 60 * MS, 20 * MS],
               ["jit__grouped_impl(1)", 80 * MS, 50 * MS]]
    spans = [["bench.schedule.sleep", 20 * MS, 35 * MS],
             ["bench.dispatch", 55 * MS, 5 * MS]]
    s = trace.reduce(_trace(ops, modules, spans))
    assert s.module_runs["jit__grouped_impl"] == 2
    assert s.module_s["jit__grouped_impl"] == pytest.approx(0.040)
    # k.2 inside the two whole runs: [5, 15] and [65, 75] ms; in the
    # window also [85, 100]
    assert s.module_op_s["jit__grouped_impl"]["k"] == pytest.approx(0.020)
    assert s.kernel_s["k"] == pytest.approx(0.035)
    # one gap, [20, 60] ms: the generator slept through most of it
    assert s.gaps == [("bench.schedule.sleep", pytest.approx(0.040))]
    bd = s.breakdown()
    assert bd["device_ops"] == [["k.2", pytest.approx(0.035)],
                                ["a.1", pytest.approx(0.025)]]
    assert bd["idle_gaps"][0][0] == "bench.schedule.sleep"


def test_gap_without_a_harness_span():
    s = trace.reduce(_trace([["%a.1 = f32[] fusion()", 0, 50 * MS]]))
    assert s.gaps == [("no_bench_span", pytest.approx(0.050))]


def test_no_window_or_no_device_is_an_error():
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce({"devices": [], "spans": []})
    with pytest.raises(ValueError, match="device operation"):
        trace.reduce(_trace([]))


RECORDED = Path(__file__).parent / "data" / "v5e_trace.json.gz"


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_recorded_v5e_trace():
    t = trace.read(str(RECORDED))
    s = trace.reduce(t)
    assert [d["name"] for d in t["devices"]] == ["/device:TPU:0"]
    assert 0 < s.busy_s < s.window_s
    assert s.module_runs["jit__grouped_impl"] == 2
    assert s.module_runs["jit__fused_l2_knn_impl"] == 1
    assert s.kernel_s["flat_scan_subchunk_min"] > 0
    assert s.kernel_s["fused_knn_chunk_mins"] > 0
    # a kernel runs inside its program
    inside = s.module_op_s["jit__grouped_impl"]["flat_scan_subchunk_min"]
    assert inside == pytest.approx(s.kernel_s["flat_scan_subchunk_min"])
    assert inside < s.module_s["jit__grouped_impl"]
    assert s.module_op_s["jit__fused_l2_knn_impl"][
        "fused_knn_chunk_mins"] > 0
    # busy by hand: the union of every op interval inside the window
    lo, hi = trace._window(t["spans"])
    iv = sorted((max(a, lo), min(a + d, hi)) for _, a, d in
                t["devices"][0]["ops"] if a + d > lo and a < hi)
    busy, end = 0, None
    for a, b in iv:
        if end is None or a > end:
            busy, end = busy + (b - a), b
        elif b > end:
            busy, end = busy + (b - end), b
    assert s.busy_s == pytest.approx(busy * 1e-9)
    assert sum(s.op_self_s.values()) == pytest.approx(s.busy_s, rel=1e-6)
    causes = {c for c, _ in s.gaps}
    assert causes <= set(trace.GAP_CAUSES) | {"no_bench_span"}


def test_tail_trace_covers_the_window_end_and_stops_after_it(
        monkeypatch):
    import threading
    import time

    import benchmark.spans  # noqa: F401  (jax imported before the clock)

    events = []
    monkeypatch.setattr(trace, "start", lambda d: events.append(
        ("start", time.perf_counter(), threading.current_thread())))
    monkeypatch.setattr(trace, "stop", lambda: events.append(
        ("stop", time.perf_counter(), threading.current_thread())))
    tail = trace.TailTrace("unused", seconds=0.4, length_s=0.1)
    t0 = time.perf_counter()
    tail.arm(lambda: events.append(("hook", time.perf_counter(), None)))
    time.sleep(0.6)            # the served window, and its answers
    assert [e[0] for e in events] == ["hook", "start"]
    tail.stop()
    names = [e[0] for e in events]
    assert names == ["hook", "start", "stop"]
    assert tail.t_start == pytest.approx(t0 + 0.3, abs=0.05)
    assert events[1][1] >= tail.t_start
    # the profiler stops on the caller's thread, after the window
    assert events[2][2] is threading.current_thread()
    assert events[2][1] >= t0 + 0.6
    assert tail.stop_s is not None


def test_host_readers_stop_where_the_trace_starts():
    import importlib.util

    import numpy as np

    def reader(name):
        path = Path(__file__).parents[1] / "metrics" / f"{name}.py"
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read

    rec = {"requests": {"due": np.array([0.0, 1.0, 2.0, 3.0])},
           "loadgen": {"lags_s": np.array([0.001, 0.002, 0.5, 0.9])},
           "host_until": 2.0,
           "executor": {"batches": 9, "valid_rows": 10, "padded_rows": 90,
                        "queue_wait_sum_ms": 900.0, "queue_wait_count": 9},
           "executor_host": {"batches": 4, "valid_rows": 30,
                             "padded_rows": 10, "queue_wait_sum_ms": 8.0,
                             "queue_wait_count": 4}}
    assert reader("loadgen.lag_ms.interactive")(rec, None, {}) == \
        pytest.approx(2.0)
    assert reader("frontend.pad_fraction.interactive")(rec, None, {}) == \
        pytest.approx(0.25)
    assert reader("frontend.queue_wait_ms.interactive")(rec, None, {}) == \
        pytest.approx(2.0)
    rec["host_until"] = None
    assert reader("loadgen.lag_ms.interactive")(rec, None, {}) == \
        pytest.approx(900.0)
