"""The reduction of the program's own spans and scopes
(``benchmark.program_trace``): on synthetic traces, on the recorded v5e
trace that predates them (which must read exactly as ``benchmark.trace``
reads it), on a real capture of this machine's CPU, and on HLO text."""

from pathlib import Path

import pytest

from benchmark import program_trace as pt
from benchmark import trace

DATA = Path(__file__).parent / "data"

G = pt.GROUPED
LIST, SCAN, MERGE = "ivf.list_slabs", "ivf.scan", "ivf.merge"


def _run_ops(a):
    """One 200 ns run of the grouped program from ``a``: a while loop
    enclosing a slab op and a scan op, a merge op after it, and a copy
    the compiler made."""
    return [
        ("%copy.1 = bf16[8] copy(%p)", a, 10, LIST),
        ("%while.2 = (s32[]) while(%t)", a + 10, 180, SCAN),
        ("%fusion.3 = f32[8] fusion(%x)", a + 20, 60, LIST),
        ("%custom-call.4 = f32[8] custom-call(%y)", a + 80, 100, SCAN),
        ("%sort.5 = f32[8] sort(%z)", a + 190, 10, MERGE),
    ]


def _synthetic(runs=(100, 400, 700), head=-150, tail=900, spans=None,
               mapped=True):
    """A head run clipped by the window, three whole runs, a tail run
    clipped; the HLO map names every op (or all but the copy)."""
    ops, modules = [], []
    for a in (head,) + tuple(runs) + (tail,):
        ops.extend(_run_ops(a))
        modules.append([f"{G}(123)", a, 200])
    names = {pt.signature(t): s for t, _, _, s in _run_ops(0)}
    if not mapped:
        del names[pt.signature("%copy.1 = bf16[8] copy(%p)")]
    return {"devices": [{"name": "/device:TPU:0",
                         "ops": [[t, a, d] for t, a, d, _ in ops],
                         "modules": modules}],
            "spans": [[trace.WINDOW_SPAN, 0, 1000]],
            "program_spans": spans or [],
            "hlo_scopes": {G: names}}


def _batch(bid, disp_end, run_start, run_end, demux_lag=5, ww=20,
           dispatch=True):
    out = [[pt.PACK, disp_end - 60, 4, {"batch_id": bid, "bucket": 8,
                                        "n_requests": 2}],
           [pt.WINDOW_WAIT, disp_end - 50, ww, {"batch_id": bid}],
           [pt.STAGE, disp_end - 20, 10, {"batch_id": bid}],
           [pt.DEMUX, run_end + demux_lag, 3, {"batch_id": bid}]]
    if dispatch:
        out.append([pt.DISPATCH, disp_end - 10, 10, {"batch_id": bid}])
    return out


def _spans():
    """Batch 4's run began before the window (clipped head), batch 5
    was dispatched before the capture (no dispatch span), 6 and 7 are
    whole, 8's run ends past the window (clipped tail)."""
    return (_batch(4, -200, -150, 50)
            + _batch(5, -100, 100, 300, dispatch=False)
            + _batch(6, 150, 400, 600, ww=40)
            + _batch(7, 350, 700, 900, demux_lag=15)
            + _batch(8, 600, 900, 1100))


def test_scope_ms_per_run():
    ps = pt.reduce(_synthetic())
    # own time a run: list 60 + copy 10, scan 100 + the loop's own 20,
    # merge 10; three runs wholly in the window
    assert ps.scope_ms[G] == pytest.approx(
        {LIST: 70e-6, SCAN: 120e-6, MERGE: 10e-6})
    assert ps.scoped_share[G] == 1.0
    bare = pt.reduce(_synthetic(mapped=False))
    assert bare.scope_ms[G][""] == pytest.approx(10e-6)
    assert bare.scoped_share[G] == pytest.approx(190 / 200)


def test_pairing_with_clipped_head_and_tail():
    ps = pt.reduce(_synthetic(spans=_spans()))
    assert [b.batch_id for b in ps.batches] == [5, 6, 7]
    assert [b.run for b in ps.batches] == [(100, 300), (400, 600),
                                           (700, 900)]
    assert ps.batches[0].dispatch is None
    assert ps.batches[1].pack == {"batch_id": 6, "bucket": 8,
                                  "n_requests": 2}
    # window wait of all three; device queue of 6 and 7 only (5 has no
    # dispatch span); demux lag of all three
    assert ps.window_wait_ms() == pytest.approx((20 + 40 + 20) / 3 * 1e-6)
    assert ps.device_queue_ms() == pytest.approx((250 + 350) / 2 * 1e-6)
    assert ps.demux_lag_ms() == pytest.approx((5 + 5 + 15) / 3 * 1e-6)


def test_void_pairing_reads_none():
    # batch 6's dispatch ends after its run begins: void
    late = _spans()
    for s in late:
        if s[0] == pt.DISPATCH and s[3]["batch_id"] == 6:
            s[1] = 395
    ps = pt.reduce(_synthetic(spans=late))
    assert ps.batches is None
    assert ps.window_wait_ms() is None and ps.device_queue_ms() is None
    assert ps.demux_lag_ms() is None
    # batch 6's demux missing: its run would take batch 7's demux
    missing = [s for s in _spans()
               if not (s[0] == pt.DEMUX and s[3]["batch_id"] == 6)]
    assert pt.reduce(_synthetic(spans=missing)).batches is None
    # no serving spans at all
    assert pt.reduce(_synthetic()).batches is None


def test_gap_causes_prefer_the_programs_spans():
    t = _synthetic(spans=[[pt.DISPATCH, 310, 80, {"batch_id": 1}]])
    t["spans"] += [["bench.dispatch", 300, 100], ["bench.wait", 600, 90]]
    ps = pt.reduce(t)
    # the gaps 300-400, 600-700 and 50-100, longest first
    assert [c for c, _ in ps.gaps] == [pt.DISPATCH, "bench.wait",
                                       "no_bench_span"]
    assert [c for c, _ in ps.base.gaps] == ["bench.dispatch", "bench.wait",
                                            "no_bench_span"]
    assert [s for _, s in ps.gaps] == pytest.approx([1e-7, 1e-7, 5e-8])


def test_recorded_trace_reads_as_before():
    """The plain form of the earlier recording carries none of the new
    keys: every Summary field reads exactly as benchmark.trace reads
    it, and nothing is scoped or paired."""
    t = trace.read(str(DATA / "v5e_trace.json.gz"))
    ps = pt.reduce(t)
    assert ps.base == trace.reduce(t)
    assert ps.gaps == ps.base.gaps
    assert ps.batches is None
    assert all(s == {"": pytest.approx(v)} for s, v in zip(
        ps.scope_ms.values(), (1.160629, 7.694122)))


HLO = """HloModule jit__grouped_impl, is_scheduled=true

%body (p: (s32[], bf16[8,4])) -> (s32[], bf16[8,4]) {
  %p = (s32[], bf16[8,4]{1,0}) parameter(0)
  %gte = bf16[8,4]{1,0} get-tuple-element(%p), index=1
  %fusion.3 = f32[8]{0} fusion(bf16[8,4]{1,0} %gte), kind=kLoop, calls=%f, metadata={op_name="jit(_grouped_impl)/ivf.scan/while/body/ivf.list_slabs/dynamic_slice"}
  ROOT %t = (s32[], bf16[8,4]{1,0}) tuple(%x, %gte)
}

ENTRY %main (a: bf16[8,4]) -> f32[8] {
  %a = bf16[8,4]{1,0} parameter(0)
  %copy.65 = bf16[8,4]{0,1:T(8,128)(2,1)} copy(bf16[8,4]{1,0} %a)
  %sort = (f32[8]{0}, s32[8]{0}) sort(f32[8]{0} %fusion.3, s32[8]{0} %iota), dimensions={0}, to_apply=%cmp, metadata={op_name="jit(_grouped_impl)/ivf.merge/sort"}
  ROOT %fusion.4 = f32[8]{0} fusion(bf16[8,4]{0,1:T(8,128)(2,1)} %copy.65), kind=kLoop, calls=%g, metadata={op_name="jit(_grouped_impl)/ivf.list_slabs/transpose"}
}
"""


def _by_name(scopes):
    return {sig.split(" = ")[0]: s for sig, s in scopes.items()}


def test_hlo_scopes_from_dumped_text(tmp_path):
    (tmp_path / f"module_0001.{G}.after_optimizations.txt").write_text(HLO)
    got = pt.hlo_scopes(str(tmp_path))[G]
    assert got["fusion.3 = f32[8]{0} fusion"] == LIST
    # the signature an op event's text gives finds it
    assert got[pt.signature(
        "%sort = (f32[8]{0}, s32[8]{0}) sort(f32[8]{0} %fusion.3, "
        "s32[8]{0} %iota), dimensions={0}")] == MERGE
    got = _by_name(got)
    # no metadata: the scope of the op that reads it
    assert got["copy.65"] == LIST
    # inside a loop with none: the scope of what reads it, then the
    # loop's
    assert got["gte"] == LIST
    # a second program of the same name that disagrees drops the op
    (tmp_path / f"module_0002.{G}.after_optimizations.txt").write_text(
        HLO.replace("ivf.merge/sort", "ivf.scan/sort"))
    got = _by_name(pt.hlo_scopes(str(tmp_path))[G])
    assert "sort" not in got and got["fusion.3"] == LIST


# modelled on the v5e dump of the grouped program: the whole-index
# relayout copies carry no scope, and the compiler expanded the per-list
# slice (a vmapped gather) into a loop whose ops carry none either
LOOPS = """HloModule jit__grouped_impl, is_scheduled=true

%fused_ds (param_0: bf16[16,4]) -> bf16[1,8,4] {
  %param_0 = bf16[16,4]{1,0} parameter(0)
  ROOT %bitcast.1 = bf16[1,8,4]{2,1,0} bitcast(%param_0)
}

%gather_body (p.1: (s32[], bf16[16,4], bf16[1,8,4])) -> (s32[], bf16[16,4], bf16[1,8,4]) {
  %p.1 = (s32[], bf16[16,4]{1,0}, bf16[1,8,4]{2,1,0}) parameter(0)
  %gte.10 = bf16[16,4]{1,0} get-tuple-element(%p.1), index=1
  %ds_fusion = bf16[1,8,4]{2,1,0} fusion(%gte.10), kind=kLoop, calls=%fused_ds
  %copy.95 = bf16[1,8,4]{1,2,0} copy(%ds_fusion)
  ROOT %t.2 = (s32[], bf16[16,4]{1,0}, bf16[1,8,4]{1,2,0}) tuple(%c, %gte.10, %copy.95)
}

%list_body (p.2: (s32[], bf16[16,4])) -> (s32[], bf16[16,4]) {
  %p.2 = (s32[], bf16[16,4]{1,0}) parameter(0)
  %gte.20 = bf16[16,4]{1,0} get-tuple-element(%p.2), index=1
  %t.3 = (s32[], bf16[16,4]{1,0}, bf16[1,8,4]{2,1,0}) tuple(%c, %gte.20, %z)
  %while.20 = (s32[], bf16[16,4]{1,0}, bf16[1,8,4]{1,2,0}) while(%t.3), condition=%cond, body=%gather_body, metadata={op_name="jit(_grouped_impl)/ivf.scan/while/body/ivf.list_slabs/vmap()/gather"}
  %kernel = f32[8]{0} custom-call(%y), custom_call_target="tpu_custom_call", metadata={op_name="jit(_grouped_impl)/ivf.scan/while/body/ivf.scan/pallas_call"}
  ROOT %t.4 = (s32[], bf16[16,4]{1,0}) tuple(%c, %gte.20)
}

ENTRY %main (a: bf16[16,4], idx: s32[2]) -> bf16[2,4] {
  %a = bf16[16,4]{0,1} parameter(0)
  %copy.65 = bf16[16,4]{1,0} copy(%a)
  %t.5 = (s32[], bf16[16,4]{1,0}) tuple(%zero, %copy.65)
  %while.18 = (s32[], bf16[16,4]{1,0}) while(%t.5), condition=%cond2, body=%list_body, metadata={op_name="jit(_grouped_impl)/ivf.scan/while"}
  %copy.76 = bf16[16,4]{1,0} copy(%a), metadata={op_name="index.data_sorted"}
  ROOT %fusion.15 = bf16[2,4]{1,0} fusion(%copy.76, %idx), kind=kCustom, calls=%g, metadata={op_name="jit(_grouped_impl)/ivf.merge/gather"}
}
"""


def test_hlo_scopes_follow_compiler_loops_and_copies(tmp_path):
    (tmp_path / f"module_0001.{G}.x.after_optimizations.txt").write_text(
        LOOPS)
    got = _by_name(pt.hlo_scopes(str(tmp_path))[G])
    # the expanded gather's ops: the scope of the loop that runs them
    assert got["ds_fusion"] == got["copy.95"] == LIST
    # the relayout for the list loop: what the loop's body does with
    # the tuple element it rides in (the gather), not the loop itself
    assert got["copy.65"] == LIST
    # the relayout for the rerank: the gather that reads it
    assert got["copy.76"] == MERGE
    assert got["kernel"] == SCAN and got["while.18"] == SCAN


def test_load_keeps_program_spans_with_stats(tmp_path):
    """A real capture on this machine's CPU (which has no device plane):
    the program's ranges, on for the capture, land with their stats."""
    import jax

    from raft_tpu.core.annotate import annotate, profiling_enabled

    pt.start(str(tmp_path))
    try:
        assert profiling_enabled()
        with annotate(pt.STAGE, batch_id=7):
            pass
        with annotate(pt.PACK, batch_id=7) as sp:
            sp.set_metadata(bucket=8, n_requests=2)
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            pass
    finally:
        pt.stop()
    assert not profiling_enabled()
    plain = pt.load(str(tmp_path))
    got = {name: stats for name, _s, _d, stats in plain["program_spans"]}
    assert got == {pt.STAGE: {"batch_id": 7},
                   pt.PACK: {"batch_id": 7, "bucket": 8, "n_requests": 2}}
    assert [s[0] for s in plain["spans"]] == [trace.WINDOW_SPAN]


def test_closure_sums_the_parts_of_the_traced_requests():
    """benchmark/breakdown.py's closure: the traced requests' mean
    latency beside lag + queue wait + each batch's parts, the batch
    parts weighted by the batch's requests."""
    import types

    import numpy as np

    from benchmark import breakdown

    ms = 1e6  # ns a millisecond: batch parts are on the profiler's clock
    batches = [
        pt.Batch(batch_id=1, run=(100 * ms, 150 * ms),
                 demux=(160 * ms, 162 * ms), dispatch=(0, 20 * ms),
                 window_wait_ns=10 * ms, stage_ns=1 * ms,
                 pack={"n_requests": 1}),
        pt.Batch(batch_id=2, run=(150 * ms, 200 * ms),
                 demux=(204 * ms, 206 * ms), dispatch=(50 * ms, 70 * ms),
                 window_wait_ns=40 * ms, stage_ns=1 * ms,
                 pack={"n_requests": 3}),
    ]
    ps = types.SimpleNamespace(batches=batches)
    tail = types.SimpleNamespace(t_start=10.0, length_s=1.0)
    rec = {"requests": {"due": np.array([9.0, 9.5, 9.6, 9.7]),
                        "done": np.array([9.9, 10.2, 10.4, np.nan])},
           "loadgen": {"lags_s": np.array([0.0, 0.002, 0.004, 0.0])},
           "executor_host": {"queue_wait_sum_ms": 100.0,
                             "queue_wait_count": 10},
           "executor": {"queue_wait_sum_ms": 160.0,
                        "queue_wait_count": 13}}
    got = breakdown.closure(rec, tail, ps)
    assert got["requests"] == 2
    assert got["latency_mean_ms"] == pytest.approx(750.0)
    parts = got["parts"]
    assert parts["submit_lag_ms"] == pytest.approx(3.0)
    assert parts["queue_wait_ms"] == pytest.approx(20.0)
    assert parts["window_wait_ms"] == pytest.approx((10 + 3 * 40) / 4)
    assert parts["device_queue_ms"] == pytest.approx((80 + 3 * 80) / 4)
    assert parts["run_ms"] == pytest.approx(50.0)
    assert parts["demux_lag_ms"] == pytest.approx((10 + 3 * 4) / 4)
    assert parts["demux_ms"] == pytest.approx(2.0)
    assert got["sum_ms"] == pytest.approx(sum(parts.values()))
    # a closed loop has no schedule to lag behind
    del rec["loadgen"]
    assert breakdown.closure(rec, tail, ps)["parts"]["submit_lag_ms"] is None


def test_recorded_v5e_capture_reduces_as_reported():
    """A capture recorded on a TPU v5 lite (``benchmark/breakdown.py``
    on the bulk cell cut to 200k rows and 64 lists, 50 ms traced; op
    texts cut to their signatures): its two whole runs' ops all scoped
    from the dumped HLO, the pairing void, and the long idle gap put
    down to the program's demux span. PERF.md reports these values."""
    t = trace.read(str(DATA / "v5e_program_trace.json.gz"))
    assert {name for name, *_ in t["program_spans"]} == set(pt.SPANS)
    ps = pt.reduce(t)
    assert ps.base.module_runs == {G: 2.0}
    assert ps.base.module_s[G] / 2 * 1e3 == pytest.approx(5.886, abs=1e-3)
    assert ps.scoped_share == {G: 1.0}
    assert ps.scope_ms[G] == pytest.approx(
        {"ivf.merge": 3.969, "ivf.scan": 0.862, "ivf.probe": 0.798,
         LIST: 0.256}, abs=1e-3)
    # the run that batch 1311's demux follows began 1.0 ms before that
    # batch's dispatch span did: void
    assert ps.batches is None
    first = min(a for _, a, _ in t["devices"][0]["modules"])
    disp = {s[3]["batch_id"]: s[1] for s in t["program_spans"]
            if s[0] == pt.DISPATCH}
    assert (disp[1311] - first) * 1e-6 == pytest.approx(1.012, abs=1e-3)
    assert ps.gaps[0] == (pt.DEMUX, pytest.approx(0.037948, abs=1e-6))
    assert ps.base.gaps[0][0] == "bench.wait"
