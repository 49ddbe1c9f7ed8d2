"""Runtime telemetry (ISSUE 13): the metric registry (log2 histogram
quantiles, labels, thread-safety, the enable gate, exposition/JSONL),
per-stage executor timings driven by a `testing.load` virtual-clock
replay, the flight recorder's ring + automatic dump triggers, the
SLO-triggered profile capture, the annotate enable flag, and the live
retrace census. Everything host-side — the one jitted program here is
a 3-element add for the census — so the whole file stays cheap in
tier-1."""

import contextlib
import json
import threading
import time

import numpy as np
import pytest

import importlib

from raft_tpu import errors

# raft_tpu.core re-exports the `annotate` FUNCTION under the module's
# own name; fetch the module itself for the gate/state tests
annotate_mod = importlib.import_module("raft_tpu.core.annotate")
from raft_tpu.obs import FlightRecorder, MetricRegistry, program_census
from raft_tpu.obs import metrics as obsm
from raft_tpu.obs.capture import ProfileTrigger
from raft_tpu.serving import ServingExecutor
from raft_tpu.serving.executor import SPANS, STAGES, ExecutorStats
from raft_tpu.testing import load

D = 4


@pytest.fixture
def reg():
    return MetricRegistry()


@pytest.fixture(autouse=True)
def _obs_on():
    """Every test in this file assumes recording is ON (the repo
    default); restore whatever state the suite had."""
    prev = obsm.set_enabled(True)
    yield
    obsm.set_enabled(prev)


# ------------------------------------------------------------ histograms
class TestHistogram:
    def test_bucket_geometry(self):
        # octave buckets tile [2^LO, 2^HI); edges round-trip
        assert obsm.bucket_index(0.0) == 0
        assert obsm.bucket_index(2.0 ** obsm.LOG2_LO) == 1
        assert obsm.bucket_index(2.0 ** obsm.LOG2_HI) == obsm.N_BUCKETS - 1
        for i in range(1, obsm.N_BUCKETS - 1):
            lo, hi = obsm.bucket_edges(i)
            assert obsm.bucket_index(lo) == i
            assert obsm.bucket_index(hi * (1 - 1e-9)) == i

    def test_quantiles_exact_for_constant_stream(self, reg):
        h = reg.histogram("lat_ms")
        for _ in range(100):
            h.observe(3.25)
        # min/max clamping collapses the bucket to the observed value
        assert h.p50 == pytest.approx(3.25)
        assert h.p99 == pytest.approx(3.25)
        assert h.count == 100 and h.sum == pytest.approx(325.0)
        assert h.mean == pytest.approx(3.25)

    def test_quantiles_within_log2_bucket_error(self, reg):
        h = reg.histogram("lat_ms", stage="x")
        vals = np.random.default_rng(0).lognormal(1.0, 1.0, 5000)
        for v in vals:
            h.observe(float(v))
        for q in (50.0, 95.0, 99.0):
            est = h.quantile(q)
            ref = float(np.percentile(vals, q))
            # a log2 bucket's worst-case relative error is 2x; linear
            # interpolation lands far closer in practice
            assert ref / 2.0 <= est <= ref * 2.0, (q, est, ref)

    def test_empty_histogram_returns_none(self, reg):
        h = reg.histogram("lat_ms", stage="empty")
        assert h.quantile(50.0) is None and h.p99 is None
        assert h.mean is None and h.count == 0

    def test_quantile_range_validated(self, reg):
        with pytest.raises(ValueError):
            obsm.quantile_from_counts([1], 101.0)

    def test_merged_quantile_pools_buckets(self, reg):
        a = reg.histogram("m", bucket=4)
        b = reg.histogram("m", bucket=8)
        for _ in range(100):
            a.observe(1.0)
        for _ in range(100):
            b.observe(64.0)
        pooled = obsm.merged_quantile([a, b], 50.0)
        # half the pooled mass sits at 1.0 — the p50 must stay at the
        # low mode, not the high series' value
        assert pooled is not None and pooled <= 2.0
        assert obsm.merged_quantile([a, b], 99.0) >= 32.0
        assert obsm.merged_quantile([], 50.0) is None


# -------------------------------------------------------------- registry
class TestRegistry:
    def test_labels_key_distinct_series(self, reg):
        a = reg.counter("reqs", bucket=4)
        b = reg.counter("reqs", bucket=8)
        assert a is not b
        a.inc(3)
        assert a.value == 3 and b.value == 0
        # same (name, labels) -> the SAME handle
        assert reg.counter("reqs", bucket=4) is a

    def test_kind_conflict_raises(self, reg):
        reg.counter("x")
        with pytest.raises(ValueError, match="counter"):
            reg.gauge("x")
        # the rule is per NAME, not per (name, labels): exposition
        # emits one `# TYPE` per name, so a labels-differing series
        # must not smuggle a second kind in (review-caught r13)
        reg.counter("y", a=1)
        with pytest.raises(ValueError, match="counter"):
            reg.histogram("y", b=2)

    def test_gauge_set_add(self, reg):
        g = reg.gauge("depth")
        g.set(7)
        g.add(-2.5)
        assert g.value == 4.5

    def test_enable_gate_no_ops_everything(self, reg):
        c = reg.counter("c")
        g = reg.gauge("g")
        h = reg.histogram("h")
        fr = FlightRecorder(8)
        prev = obsm.set_enabled(False)
        try:
            c.inc(100)
            g.set(5)
            h.observe(1.0)
            fr.record("submit", request_id=1)
        finally:
            obsm.set_enabled(prev)
        assert c.value == 0 and g.value == 0.0 and h.count == 0
        assert fr.events() == []

    def test_thread_safety_smoke(self, reg):
        c = reg.counter("hits")
        h = reg.histogram("lat")
        n_threads, n_each = 8, 500

        def work():
            for i in range(n_each):
                c.inc()
                h.observe(float(i % 7) + 0.5)

        ts = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert c.value == n_threads * n_each
        assert h.count == n_threads * n_each

    def test_snapshot_text_and_exposition(self, reg):
        reg.counter("reqs", outcome="ok").inc(2)
        h = reg.histogram("lat_ms", stage="e2e")
        h.observe(1.0)
        snap = reg.snapshot()
        assert snap["reqs"][0]["value"] == 2
        assert snap["lat_ms"][0]["count"] == 1
        assert "p50" in snap["lat_ms"][0]
        txt = reg.text_snapshot()
        assert 'reqs{outcome="ok"} 2' in txt
        expo = reg.exposition()
        assert "# TYPE reqs counter" in expo
        assert "# TYPE lat_ms histogram" in expo
        assert 'lat_ms_bucket{le="+Inf",stage="e2e"}' in expo
        assert 'lat_ms_count{stage="e2e"} 1' in expo

    def test_jsonl_emitter(self, tmp_path):
        reg = MetricRegistry(clock=lambda: 123.5)   # injectable stamp
        reg.counter("n").inc(4)
        path = tmp_path / "metrics.jsonl"
        em = reg.start_emitter(str(path), interval_s=0.01)
        time.sleep(0.05)
        em.stop()
        lines = [json.loads(x) for x in
                 path.read_text().strip().splitlines()]
        assert len(lines) >= 2            # periodic + final flush
        assert lines[0]["t"] == 123.5
        assert lines[0]["metrics"]["n"][0]["value"] == 4
        reg.stop_emitters()               # idempotent


# -------------------------------------------- executor per-stage timing
def _host_dispatch(batch, **_rt):
    """A pure-host dispatch: results are immediately 'ready' (numpy has
    no is_ready), so the executor pipeline runs at full speed with no
    device in the loop."""
    return (batch * 2.0, np.argsort(batch, axis=1).astype(np.int32))


def _wait_for(cond, timeout_s=10.0):
    deadline = time.monotonic() + timeout_s
    while not cond():
        assert time.monotonic() < deadline, "condition never held"
        time.sleep(0.005)


class TestExecutorSpans:
    def test_spans_once_per_batch_in_order_inside_a_capture(self,
                                                            tmp_path):
        """Inside ``annotate.start_trace`` (a real CPU capture) each
        batch emits the five serving spans once, in hop order, tied by
        their ``batch_id`` stat; pack also carries bucket and
        n_requests."""
        from jax.profiler import ProfileData

        prev = annotate_mod.set_profiling(False)
        try:
            annotate_mod.start_trace(str(tmp_path))
            try:
                with ServingExecutor(_host_dispatch, (4, 8), dim=D,
                                     flush_age_s=0.0,
                                     registry=MetricRegistry()) as ex:
                    for rows in (1, 3, 6):
                        ex.submit(np.ones((rows, D), np.float32)).result(
                            timeout=30)
            finally:
                annotate_mod.stop_trace()
            assert not annotate_mod.profiling_enabled()
        finally:
            annotate_mod.set_profiling(prev)
        (path,) = tmp_path.rglob("*.xplane.pb")
        spans = {}
        for plane in ProfileData.from_file(str(path)).planes:
            for line in plane.lines:
                for e in line.events:
                    if e.name in SPANS:
                        stats = dict(e.stats)
                        spans.setdefault(stats["batch_id"], []).append(
                            (e.start_ns, e.name, stats))
        assert sorted(spans) == [0, 1, 2]
        for bid, events in spans.items():
            events.sort(key=lambda ev: ev[0])
            assert [name for _, name, _ in events] == list(SPANS), bid
        packs = [next(st for _, n, st in spans[b] if n == SPANS[0])
                 for b in (0, 1, 2)]
        assert [(p["bucket"], p["n_requests"]) for p in packs] == [
            (4, 1), (4, 1), (8, 1)]


class TestExecutorStageTiming:
    def test_stage_histograms_under_virtual_clock_replay(self):
        """The per-stage pin (ISSUE 13): drive the executor from a
        `testing.load` virtual-clock replay (all submits fire
        instantly) and assert every STAGE histogram filled with
        consistent counts — queue_wait/e2e once per request,
        batch_build/staging/dispatch_ready/demux once per batch."""
        reg = MetricRegistry()
        ex = ServingExecutor(_host_dispatch, (4, 8), dim=D,
                             flush_age_s=0.0, registry=reg,
                             name="stagetest")
        sched = load.poisson_arrivals(1000.0, 24, seed=5, sizes=2)
        futs, _, _ = load.replay(
            sched,
            lambda i, size: ex.submit(
                np.full((size, D), i, np.float32)),
            clock=lambda: 0.0, sleep=lambda s: None,
        )
        for f in futs:
            f.result(timeout=30)
        st = ex.stats()
        ex.close()
        assert st.completed == 24 and st.failed == 0
        for stage_name in STAGES:
            assert stage_name in st.stage_p50_ms, stage_name
            assert st.stage_p50_ms[stage_name] >= 0.0
            assert (st.stage_p99_ms[stage_name]
                    >= st.stage_p50_ms[stage_name])
        # count consistency: per-request vs per-batch stages
        def total(stage_name):
            return sum(
                h.count for (s, _b), h in ex._stage_hist.items()
                if s == stage_name
            )
        assert total("queue_wait") == 24 and total("e2e") == 24
        assert total("dispatch_ready") == st.batches
        assert total("batch_build") == st.batches
        assert total("window_wait") == st.batches
        assert total("staging") == st.batches
        assert total("demux") == st.batches
        # e2e contains dispatch_ready by construction
        assert (st.stage_p50_ms["e2e"]
                >= st.stage_p50_ms["dispatch_ready"])

    def test_window_wait_under_virtual_clock(self):
        """window_wait is packed -> staging start, read off the virtual
        clock: with one in-flight slot, the second batch waits out the
        first, and the clock the test advances meanwhile is its wait."""
        now = [0.0]
        release = threading.Event()

        class Gated:
            """A dispatch output the test holds unready."""

            def __init__(self, value):
                self.value = value

            def is_ready(self):
                return release.is_set()

        reg = MetricRegistry()
        ex = ServingExecutor(lambda b, **_: Gated(_host_dispatch(b)), (4,),
                             dim=D, flush_age_s=0.0, max_in_flight=1,
                             registry=reg, clock=lambda: now[0],
                             name="windowtest")
        try:
            f0 = ex.submit(np.ones((2, D), np.float32))
            _wait_for(lambda: ex.stats().in_flight == 1)
            f1 = ex.submit(np.ones((3, D), np.float32))
            _wait_for(lambda: ex.stats().pending == 0)
            time.sleep(0.1)          # the batcher is in the window wait
            now[0] = 2.5
            release.set()
            f0.result(timeout=30)
            f1.result(timeout=30)
        finally:
            ex.close()
        h = reg.histogram("serving_stage_ms", executor="windowtest",
                          stage="window_wait", bucket=4)
        assert h.count == 2
        assert h.sum == pytest.approx(2500.0)
        assert ex.stats().stage_p99_ms["window_wait"] >= 1000.0

    def test_executor_stats_positional_compat(self):
        """The pre-r13 12-field positional construction still works and
        the new stage fields default empty — byte-compatibility, the
        ISSUE 13 satellite contract."""
        st = ExecutorStats(1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12)
        assert st.submitted == 1 and st.in_flight == 12
        assert st.stage_p50_ms == {} and st.stage_p99_ms == {}
        assert st.pad_fraction == pytest.approx(8 / 15)

    def test_coverage_gauge_read_at_demux(self):
        """An mnmg-shaped result (PartialSearchResult pytree) feeds the
        coverage gauge from the ALREADY-converted host copy."""
        from raft_tpu.resilience.degraded import PartialSearchResult

        reg = MetricRegistry()

        def dispatch(batch, **_rt):
            b = batch.shape[0]
            return PartialSearchResult(
                distances=np.zeros((b, 2), np.float32),
                ids=np.zeros((b, 2), np.int32),
                coverage=np.full((b,), 0.75, np.float32),
                row_valid=np.ones((b,), bool),
            )

        ex = ServingExecutor(dispatch, (4,), dim=D, flush_age_s=0.0,
                             registry=reg, name="covtest")
        ex.submit(np.ones((2, D), np.float32)).result(timeout=30)
        ex.close()
        g = reg.gauge("serving_coverage_min", executor="covtest")
        assert g.value == pytest.approx(0.75)


# -------------------------------------------------------- flight recorder
class TestFlightRecorder:
    def test_span_events_join_by_request_id(self):
        fr = FlightRecorder(64, clock=lambda: 1.0)
        ex = ServingExecutor(_host_dispatch, (4,), dim=D,
                             flush_age_s=0.0, registry=MetricRegistry(),
                             flight=fr, name="fr")
        fut = ex.submit(np.ones((2, D), np.float32))
        fut.result(timeout=30)
        ex.close()
        evs = [e["event"] for e in fr.events(request_id=0)]
        assert evs[:2] == ["submit", "pack"]
        batch = fr.events(event="dispatch")
        assert batch and 0 in batch[0]["requests"]
        demux = fr.events(event="demux")
        assert demux and demux[0]["winner"] == "unhedged"
        assert demux[0]["delivered"] == 1

    def test_ring_bound_and_dropped(self):
        fr = FlightRecorder(4, clock=lambda: 0.0)
        for i in range(10):
            fr.record("submit", request_id=i)
        assert len(fr.events()) == 4
        assert fr.dropped == 6
        assert [e["request_id"] for e in fr.events()] == [6, 7, 8, 9]

    def test_dumps_header_and_schema(self):
        fr = FlightRecorder(8, name="s", clock=lambda: 2.5)
        fr.record("submit", request_id=3, rows=2)
        lines = [json.loads(x) for x in
                 fr.dumps("unit").strip().splitlines()]
        assert lines[0] == {"flight": "s", "reason": "unit", "t": 2.5,
                            "n_events": 1, "dropped": 0}
        assert lines[1]["event"] == "submit"
        assert lines[1]["request_id"] == 3 and lines[1]["rows"] == 2

    def test_dump_without_sink_is_noop(self):
        fr = FlightRecorder(8)
        fr.record("submit", request_id=0)
        assert fr.dump("no-sink") is None
        assert fr.events()               # ring untouched

    def test_auto_dump_on_batch_failure(self, tmp_path):
        """Trigger 1: a failing dispatch dumps the ring BEFORE failing
        the futures; trigger 3: close() with failures outstanding dumps
        again."""
        fr = FlightRecorder(64, dump_dir=str(tmp_path), name="boom")

        def bad_dispatch(batch, **_rt):
            raise errors.RaftTimeoutError("deadline tripped")

        ex = ServingExecutor(bad_dispatch, (4,), dim=D, flush_age_s=0.0,
                             registry=MetricRegistry(), flight=fr,
                             name="boom")
        fut = ex.submit(np.ones((1, D), np.float32))
        with pytest.raises(errors.RaftTimeoutError):
            fut.result(timeout=30)
        ex.close()
        assert len(fr.dumps_written) == 2
        first = [json.loads(x) for x in open(fr.dumps_written[0])]
        assert first[0]["reason"] == "batch-fail"
        fails = [e for e in first if e.get("event") == "batch_fail"]
        assert fails and fails[0]["error"] == "RaftTimeoutError"
        assert "deadline tripped" in fails[0]["message"]
        last = [json.loads(x) for x in open(fr.dumps_written[1])]
        assert last[0]["reason"] == "close-with-failures"
        assert any(e.get("event") == "close" and e.get("failed") == 1
                   for e in last)

    def test_broken_dump_sink_never_hangs_clients(self, tmp_path):
        """Review-caught r13: an OSError from the automatic dump (bad
        dir, disk full) must not escape _fail_batch — the futures
        still owe their callers the REAL dispatch exception, and an
        escape would kill the worker thread and hang every waiter."""
        fr = FlightRecorder(
            64, dump_dir=str(tmp_path / "missing" / "dir"), name="io",
        )

        def bad_dispatch(batch, **_rt):
            raise errors.RaftTimeoutError("the real failure")

        ex = ServingExecutor(bad_dispatch, (4,), dim=D, flush_age_s=0.0,
                             registry=MetricRegistry(), flight=fr,
                             name="io")
        fut = ex.submit(np.ones((1, D), np.float32))
        with pytest.raises(errors.RaftTimeoutError, match="real"):
            fut.result(timeout=30)       # resolved, not hung
        ex.close(timeout_s=10.0)         # completes despite the sink
        assert fr.dumps_written == []

    def test_shed_recorded(self):
        from raft_tpu.resilience import AdmissionController

        fr = FlightRecorder(16)
        ex = ServingExecutor(
            _host_dispatch, (4,), dim=D, flush_age_s=10.0,
            registry=MetricRegistry(), flight=fr, name="shed",
            admission=AdmissionController(max_concurrent=1, max_queue=0),
        )
        ex.submit(np.ones((1, D), np.float32))
        with pytest.raises(errors.RaftOverloadError):
            for _ in range(8):
                ex.submit(np.ones((1, D), np.float32))
        ex.close()
        assert fr.events(event="shed")


# ------------------------------------------------------- profile trigger
class _FakeTrace:
    def __init__(self):
        self.started = []
        self.stopped = 0

    def start(self, log_dir):
        self.started.append(log_dir)

    def stop(self):
        self.stopped += 1


class TestProfileTrigger:
    def _trigger(self, reg, fr=None, **kw):
        h = reg.histogram("e2e_ms")
        tr = _FakeTrace()
        slept = []
        trig = ProfileTrigger(
            h, threshold_ms=10.0, log_dir="/tmp/prof", consecutive=2,
            capture_s=0.25, max_captures=1, cooldown_s=60.0,
            registry=reg, recorder=fr, start=tr.start, stop=tr.stop,
            sleep=slept.append, clock=lambda: 100.0, **kw,
        )
        return h, tr, slept, trig

    def test_fires_after_consecutive_breaches_only(self, reg):
        fr = FlightRecorder(16)
        h, tr, slept, trig = self._trigger(reg, fr)
        # window 1: over threshold -> breach 1, no capture
        for _ in range(10):
            h.observe(50.0)
        assert trig.check() is None and tr.started == []
        # window 2: still over -> capture fires, bounded, path recorded
        for _ in range(10):
            h.observe(50.0)
        assert trig.check() == "/tmp/prof"
        assert tr.started == ["/tmp/prof"] and tr.stopped == 1
        assert slept == [0.25]
        assert trig.captures == 1
        c = reg.counter("profile_captures_total", trigger="e2e_ms")
        assert c.value == 1
        ev = fr.events(event="profile_capture")
        assert ev and ev[0]["path"] == "/tmp/prof"
        assert ev[0]["breached_ms"] > 10.0

    def test_windowed_not_lifetime_quantile(self, reg):
        h, tr, _, trig = self._trigger(reg)
        # a bad HISTORY must not trip the trigger once the current
        # window is healthy: lifetime p99 stays >10, window p99 is 1
        for _ in range(100):
            h.observe(50.0)
        assert trig.check() is None          # breach 1
        for _ in range(100):
            h.observe(1.0)
        assert trig.check() is None and tr.started == []
        # the healthy window also RESET the breach count
        for _ in range(10):
            h.observe(50.0)
        assert trig.check() is None          # breach 1 again, not 2

    def test_no_traffic_carries_no_evidence(self, reg):
        h, tr, _, trig = self._trigger(reg)
        for _ in range(10):
            h.observe(50.0)
        assert trig.check() is None          # breach 1
        assert trig.check() is None          # empty window: no advance
        for _ in range(10):
            h.observe(50.0)
        assert trig.check() == "/tmp/prof"   # breach 2 -> fires

    def test_failed_capture_rolls_back_the_budget(self, reg):
        """Review-caught r13: a refused start_trace (another capture
        already running) must not burn the one-capture budget — the
        trigger retries after the next full debounce instead of going
        dark for the process lifetime."""
        h = reg.histogram("e2e_ms", t="rollback")

        calls = []

        def refusing_start(_d):
            calls.append("start")
            raise RuntimeError("profiler already started")

        tr = _FakeTrace()
        trig = ProfileTrigger(
            h, threshold_ms=10.0, log_dir="/tmp/prof", consecutive=1,
            capture_s=0.1, max_captures=1, cooldown_s=60.0,
            registry=reg, start=refusing_start, stop=tr.stop,
            sleep=lambda s: None, clock=lambda: 100.0,
        )
        for _ in range(10):
            h.observe(50.0)
        with pytest.raises(RuntimeError):
            trig.check()
        assert trig.captures == 0            # budget intact
        # the profiler frees up; the next breach captures normally
        trig._start = tr.start
        for _ in range(10):
            h.observe(50.0)
        assert trig.check() == "/tmp/prof"
        assert trig.captures == 1

    def test_max_captures_bounds_the_storm(self, reg):
        h, tr, _, trig = self._trigger(reg)
        for round_ in range(4):
            for _ in range(10):
                h.observe(50.0)
            trig.check()
        assert len(tr.started) == 1          # max_captures=1

    def test_watch_thread_runs_and_stops(self, reg):
        h, tr, _, trig = self._trigger(reg)
        trig.watch(interval_s=0.01)
        for _ in range(10):
            h.observe(50.0)
        time.sleep(0.05)
        for _ in range(10):
            h.observe(50.0)
        deadline = time.monotonic() + 2.0
        while not tr.started and time.monotonic() < deadline:
            time.sleep(0.01)
        trig.stop()
        assert tr.started == ["/tmp/prof"]


# ------------------------------------------------- annotate enable flag
class TestAnnotateGate:
    def test_disabled_push_allocates_nothing(self, monkeypatch):
        """The 'near-zero cost' claim, pinned: with profiling off,
        push_range constructs NO profiler object and stacks NO
        ExitStack; annotate yields without touching jax.profiler."""
        constructed = []

        class Spy:
            def __init__(self, label):
                constructed.append(label)

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False

        monkeypatch.setattr(annotate_mod.jax.profiler,
                            "TraceAnnotation", Spy)
        prev = annotate_mod.set_profiling(False)
        try:
            annotate_mod.push_range("hot %d", 1)
            assert annotate_mod._stack == []
            assert constructed == []
            with annotate_mod.annotate("hot", batch_id=1):
                pass
            assert constructed == []
            # pop on the empty stack: loud no-op, never an exception
            annotate_mod.pop_range()
            # the serving hot path: a served batch constructs no span
            with ServingExecutor(_host_dispatch, (4,), dim=D,
                                 flush_age_s=0.0,
                                 registry=MetricRegistry()) as ex:
                ex.submit(np.ones((2, D), np.float32)).result(timeout=30)
            assert constructed == []
        finally:
            annotate_mod.set_profiling(prev)

    def test_enabled_push_pop_balanced(self, monkeypatch):
        constructed = []

        class Spy:
            def __init__(self, label):
                constructed.append(label)

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False

        monkeypatch.setattr(annotate_mod.jax.profiler,
                            "TraceAnnotation", Spy)
        prev = annotate_mod.set_profiling(True)
        try:
            annotate_mod.push_range("range %s", "a")
            assert len(annotate_mod._stack) == 1
            assert constructed == ["range a"]
            annotate_mod.pop_range()
            assert annotate_mod._stack == []
        finally:
            annotate_mod.set_profiling(prev)

    def test_enabled_annotate_carries_stats(self, monkeypatch):
        """A range's keyword stats ride on its TraceAnnotation; the
        range yields it, so stats known only inside can be added."""
        constructed = []

        class Spy:
            def __init__(self, label, **stats):
                constructed.append((label, stats))

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False

        monkeypatch.setattr(annotate_mod.jax.profiler,
                            "TraceAnnotation", Spy)
        prev = annotate_mod.set_profiling(True)
        try:
            with annotate_mod.annotate("batch %d", 3, batch_id=3) as sp:
                assert isinstance(sp, Spy)
            assert constructed == [("batch 3", {"batch_id": 3})]
        finally:
            annotate_mod.set_profiling(prev)

    def test_start_trace_passes_profiler_options(self, monkeypatch):
        seen = []
        monkeypatch.setattr(annotate_mod.jax.profiler, "start_trace",
                            lambda d, **kw: seen.append((d, kw)))
        monkeypatch.setattr(annotate_mod.jax.profiler, "stop_trace",
                            lambda: None)
        opts = object()
        prev = annotate_mod.set_profiling(False)
        try:
            annotate_mod.start_trace("/tmp/t", opts)
            annotate_mod.stop_trace()
            annotate_mod.start_trace("/tmp/u")
            annotate_mod.stop_trace()
        finally:
            annotate_mod.set_profiling(prev)
        assert seen == [("/tmp/t", {"profiler_options": opts}),
                        ("/tmp/u", {})]

    def test_host_ranges_leave_programs_untouched(self, monkeypatch):
        """A range open while a program is traced must not stamp its
        label into the program's op metadata (a compile inside a
        capture would differ from one outside it)."""
        import jax

        monkeypatch.setattr(annotate_mod.jax.profiler,
                            "TraceAnnotation",
                            lambda label, **kw: contextlib.nullcontext())
        prev = annotate_mod.set_profiling(True)
        try:
            with annotate_mod.annotate("host.range"):
                text = jax.jit(lambda x: x * 2).lower(
                    np.ones(3, np.float32)).as_text(debug_info=True)
        finally:
            annotate_mod.set_profiling(prev)
        assert "host.range" not in text

    def test_trace_capture_flips_the_gate(self, monkeypatch):
        monkeypatch.setattr(annotate_mod.jax.profiler, "start_trace",
                            lambda d: None)
        monkeypatch.setattr(annotate_mod.jax.profiler, "stop_trace",
                            lambda: None)
        prev = annotate_mod.set_profiling(False)
        try:
            annotate_mod.start_trace("/tmp/t")
            assert annotate_mod.profiling_enabled()
            annotate_mod.stop_trace()
            assert not annotate_mod.profiling_enabled()
        finally:
            annotate_mod.set_profiling(prev)

    def test_failed_start_trace_leaks_nothing(self, monkeypatch):
        """Review-caught r13: a refused profiler start (capture already
        running) must leave the range gate AND its restore stack
        untouched — the old order enabled ranges forever."""
        def refuse(_d):
            raise RuntimeError("profiler already started")

        monkeypatch.setattr(annotate_mod.jax.profiler, "start_trace",
                            refuse)
        prev = annotate_mod.set_profiling(False)
        depth = len(annotate_mod._pre_trace)
        try:
            with pytest.raises(RuntimeError):
                annotate_mod.start_trace("/tmp/t")
            assert not annotate_mod.profiling_enabled()
            assert len(annotate_mod._pre_trace) == depth
        finally:
            annotate_mod.set_profiling(prev)

    def test_unbalanced_stop_restores_env_default(self, monkeypatch):
        """Review-caught r13: a stop_trace with no matching
        start_trace falls back to the env-derived default, not a hard
        False — a RAFT_TPU_PROFILE=1 process must not be silently
        disabled by one stray stop."""
        monkeypatch.setattr(annotate_mod.jax.profiler, "stop_trace",
                            lambda: None)
        monkeypatch.setattr(annotate_mod, "_ENV_DEFAULT", True)
        prev = annotate_mod.set_profiling(True)
        try:
            assert annotate_mod._pre_trace == []
            annotate_mod.stop_trace()            # unbalanced
            assert annotate_mod.profiling_enabled()
        finally:
            annotate_mod.set_profiling(prev)


# --------------------------------------------------- live retrace census
class TestProgramCensus:
    def test_census_reads_cache_sizes(self, reg):
        import jax

        @jax.jit
        def f(x):
            return x + 1

        f(np.ones(3, np.float32))
        out = program_census({"f": f, "not_jitted": len}, registry=reg)
        assert out == {"f": 1}              # non-jitted entries skipped
        assert reg.gauge("compiled_programs", entry="f").value == 1
        # steady state: same shape, same census — a retrace would move
        # the gauge, which is exactly what an alert watches
        f(np.ones(3, np.float32) * 2)
        assert program_census({"f": f}, registry=reg)["f"] == 1
        f(np.ones(5, np.float32))           # a NEW shape retraces
        assert program_census({"f": f}, registry=reg)["f"] == 2


# ------------------------------------------------- health gauge seeding
class TestHealthGauge:
    def test_fresh_tracker_seeds_ranks_up(self):
        """Review-caught r13: a scrape before the first flip must read
        the constructed tracker's all-up count, not the gauge's 0.0
        initial value (which an alert would read as total outage)."""
        from raft_tpu.resilience import ShardHealth

        ShardHealth(6)
        g = obsm.default_registry().gauge("health_ranks_up")
        assert g.value == 6.0

    def test_throwaway_trackers_do_not_pollute(self):
        """Review-caught r13: the per-call HealthReport normalization
        (resolve_shard_mask) builds a transient tracker — it must
        neither reset the gauge nor count fake flip transitions on
        every degraded search."""
        from raft_tpu.resilience import ShardHealth
        from raft_tpu.resilience.degraded import resolve_shard_mask
        from raft_tpu.resilience.health import HealthProbe, HealthReport

        reg = obsm.default_registry()
        ShardHealth(8).mark_down(2)      # the real tracker: 7 up
        flips = reg.counter("health_transitions_total",
                            direction="down").value
        report = HealthReport(probes={
            "allreduce": HealthProbe(ok=False, seconds=0.1, ranks=(3,)),
        })
        for _ in range(5):               # steady degraded traffic
            mask = resolve_shard_mask(report, 8)
        assert mask.tolist() == [1, 1, 1, 0, 1, 1, 1, 1]
        g = reg.gauge("health_ranks_up")
        assert g.value == 7.0            # the REAL tracker's count
        assert reg.counter("health_transitions_total",
                           direction="down").value == flips


# -------------------------------------------------- admission metrics
class TestAdmissionMetrics:
    def test_shed_and_occupancy_series(self):
        from raft_tpu.resilience import AdmissionController

        reg = MetricRegistry()
        ctrl = AdmissionController(max_concurrent=1, max_queue=1,
                                   registry=reg, name="t")
        ctrl.enqueue()
        ctrl.enqueue()
        with pytest.raises(errors.RaftOverloadError):
            ctrl.enqueue()
        assert reg.counter("admission_shed_total", controller="t",
                           reason="queue").value == 1
        assert reg.gauge("admission_queue_depth",
                         controller="t").value == 2.0
        ticket = ctrl.begin_service(2)
        assert reg.gauge("admission_in_flight",
                         controller="t").value == 2.0
        ctrl.finish_service(ticket)
        assert reg.gauge("admission_in_flight",
                         controller="t").value == 0.0
        assert reg.gauge("admission_service_ewma_ms",
                         controller="t").value >= 0.0


# ------------------------------------------------- per-list load feed
class TestListLoadMetrics:
    def test_round_trip_and_shard_filter(self):
        from raft_tpu.resilience import (
            measured_list_load, record_list_load,
        )

        reg = MetricRegistry()
        record_list_load([3, 0, 2, 0], shard=0, registry=reg)
        record_list_load([1, 1, 0, 0], shard=0, registry=reg)
        record_list_load([0, 7, 0, 0], shard=1, registry=reg)
        np.testing.assert_array_equal(
            measured_list_load(4, shard=0, registry=reg), [4, 1, 2, 0])
        np.testing.assert_array_equal(
            measured_list_load(4, registry=reg), [4, 8, 2, 0])

    def test_bounded_cardinality_folds_into_other(self):
        """The cardinality rule: a shard mints at most ``max_series``
        per-list series; the remainder folds into ``list="other"`` so
        traffic totals are conserved and the catalog stays bounded."""
        from raft_tpu.resilience import (
            measured_list_load, record_list_load,
        )

        reg = MetricRegistry()
        rows = np.arange(1, 9)          # 8 lists, loads 1..8
        record_list_load(rows, shard=0, registry=reg, max_series=3)
        per_list = [
            inst for inst in reg.series("serving_list_rows_total")
            if inst.labels.get("list") != "other"
        ]
        assert len(per_list) == 3
        other = [
            inst for inst in reg.series("serving_list_rows_total")
            if inst.labels.get("list") == "other"
        ]
        assert len(other) == 1
        total = sum(float(i.value)
                    for i in reg.series("serving_list_rows_total"))
        assert total == float(rows.sum())       # conserved
        # minted series keep recording; measured_ excludes "other"
        record_list_load(rows, shard=0, registry=reg, max_series=3)
        assert measured_list_load(8, registry=reg).sum() > 0

    def test_default_registry_emission(self):
        # record once into the process registry so the live-registry
        # side of the catalog-parity scan sees the dynamic name
        from raft_tpu.resilience import record_list_load

        record_list_load([1, 0], shard=7)
        names = obsm.default_registry().snapshot()
        assert "serving_list_rows_total" in names


# ------------------------------------- mutation journal telemetry
class TestMutationJournalTelemetry:
    def test_journal_overflow_counts_and_flight_marks(self):
        """ISSUE 20 satellite: an epoch-journal overflow silently
        downgrades stale readers to "refresh everything" — it must be
        attributable: mutation_journal_compacted_total counts the
        dropped entries and each overflow flight-marks the new floor.
        Driven through _journal_note directly (the real write path
        calls it per mutation) so the file stays host-side cheap."""
        import types

        from raft_tpu.spatial.ann import mutation as mut_mod

        fl = FlightRecorder()
        m = types.SimpleNamespace(
            _epoch_journal=[], _journal_floor=0, epoch=0,
            name="journal-tel", flight=fl,
        )
        counter = mut_mod._mseries("journal-tel")["journal_compacted"]
        before = counter.value
        overflow = 6
        for e in range(mut_mod._EPOCH_JOURNAL_CAP + overflow):
            m.epoch = e + 1
            mut_mod._journal_note(m, [e % 4])
        assert counter.value == before + overflow
        assert len(m._epoch_journal) == mut_mod._EPOCH_JOURNAL_CAP
        evs = fl.events(event="mutation_journal_compacted")
        assert len(evs) == overflow
        floors = [e["floor"] for e in evs]
        assert floors == sorted(floors) and floors[-1] == overflow
        assert all(e["index"] == "journal-tel" and e["dropped"] == 1
                   for e in evs)
        # below the floor the journal answers None = full refresh
        assert mut_mod.lists_changed_since(m, 0) is None

    def test_no_flight_recorder_is_fine(self):
        import types

        from raft_tpu.spatial.ann import mutation as mut_mod

        m = types.SimpleNamespace(
            _epoch_journal=[], _journal_floor=0, epoch=0,
            name="journal-tel2", flight=None,
        )
        for e in range(mut_mod._EPOCH_JOURNAL_CAP + 2):
            m.epoch = e + 1
            mut_mod._journal_note(m, None)


# -------------------------------------------- metric-catalog parity
class TestMetricCatalogParity:
    def test_every_emitted_series_has_a_catalog_row(self):
        """ISSUE 16 satellite: docs/observability.md's metric catalog
        cannot drift behind the code. Every literal series name passed
        to a ``.counter/.gauge/.histogram`` factory anywhere in
        raft_tpu/ — plus whatever the process registry actually holds
        by the time this file has run — must appear in a catalog row
        (same one-heading-per-rule bar as the static_analysis.md
        parity test)."""
        import ast
        from pathlib import Path

        repo = Path(__file__).resolve().parents[1]
        emitted: dict = {}
        for f in sorted((repo / "raft_tpu").rglob("*.py")):
            for node in ast.walk(ast.parse(f.read_text())):
                if (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr in ("counter", "gauge",
                                               "histogram")
                        and node.args
                        and isinstance(node.args[0], ast.Constant)
                        and isinstance(node.args[0].value, str)):
                    emitted.setdefault(node.args[0].value,
                                       f.relative_to(repo).as_posix())
        assert len(emitted) >= 20   # the scan itself must not go blind
        # series created dynamically (names built at runtime) surface
        # through the live registry this suite already exercised
        for name in obsm.default_registry().snapshot():
            emitted.setdefault(name, "<default_registry>")
        catalog = (repo / "docs" / "observability.md").read_text()
        start = catalog.index("## Metric catalog")
        end = catalog.find("\n## ", start + 1)
        section = catalog[start:end if end != -1 else None]
        missing = [f"{n} (from {src})" for n, src in sorted(emitted.items())
                   if f"`{n}`" not in section]
        assert not missing, (
            "series emitted but not in the docs/observability.md "
            "catalog:\n" + "\n".join(missing)
        )
