"""Capture of a profiler trace, and its reduction to device metrics.

A trace is kept in a plain form, so that a small recorded one can be
committed and the reduction tested on it::

    {"devices": [{"name": "/device:TPU:0",
                  "ops": [[hlo_text, start_ns, dur_ns], ...],
                  "modules": [[program_name, start_ns, dur_ns], ...]}],
     "spans": [[name, start_ns, dur_ns], ...]}

``ops`` are the events of a device plane's "XLA Ops" line (a while loop's
event encloses those of its body), ``modules`` those of its "XLA Modules"
line (one per program run), and ``spans`` the harness's own host spans
(names starting ``bench.``), all on the profiler's one clock.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import gzip
import json
import os
import re
import threading
import time

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
# what the host was doing in a device gap, most telling first
GAP_CAUSES = ("bench.dispatch", "bench.stage", "bench.wait",
              "bench.schedule.submit", "bench.schedule.sleep")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def start(log_dir: str) -> None:
    """Start the profiler with the Python tracer off (host spans from
    ``TraceAnnotation`` are kept)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


class TailTrace:
    """Trace the last ``length_s`` seconds of a served window of
    ``seconds``, inside a ``bench.window`` span.

    The driver calls :meth:`arm` as its window opens; a thread of its own
    then waits out the window's head, calls the hooks given to ``arm``
    (the driver's snapshot of its host counters), notes ``t_start`` and
    starts the profiler. The profiler is stopped by :meth:`stop` on the
    caller's thread once the window and its answers are in, so that its
    collection (tens of seconds in the IVF-Flat cells) never runs beside
    the served window. Host readings are taken over the requests due
    before ``t_start``, where the tracer has not yet started. A short
    traced part keeps the trace inside the profiler's event buffer (a
    10 s window of the IVF-Flat cells overflowed it) and its collection
    short."""

    def __init__(self, log_dir: str, seconds: float, length_s: float):
        self.log_dir = log_dir
        self.seconds = seconds
        self.length_s = min(length_s, seconds / 2)
        self.t_start = None
        self.stop_s = None
        self._thread = None

    def arm(self, *hooks) -> None:
        from benchmark.spans import span

        delay = self.seconds - self.length_s
        t0 = time.perf_counter()

        def body():
            time.sleep(max(0.0, t0 + delay - time.perf_counter()))
            for hook in hooks:
                hook()
            self.t_start = time.perf_counter()
            start(self.log_dir)
            with span(WINDOW_SPAN):
                time.sleep(self.length_s)

        self._thread = threading.Thread(target=body, name="bench-tracer")
        self._thread.start()

    def stop(self) -> None:
        """Wait for the traced part to close, then stop the profiler."""
        if self._thread is None:
            raise RuntimeError("the driver never armed the tracer")
        self._thread.join()
        t0 = time.perf_counter()
        stop()
        self.stop_s = time.perf_counter() - t0


def load(log_dir: str) -> dict:
    """The plain form of the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(files[-1])
    devices, spans = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            dev = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key:
                    dev[key] = [[e.name, e.start_ns, e.duration_ns]
                                for e in line.events]
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend([e.name, e.start_ns, e.duration_ns]
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    devices.sort(key=lambda d: d["name"])
    return {"devices": devices, "spans": spans}


def save(trace: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace, f)


def read(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


_SUFFIX = re.compile(r"\.\d+$")


def op_name(text: str) -> str:
    """``%copy.65 = bf16[...] copy(...)`` -> ``copy.65``."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def base_name(name: str) -> str:
    """An op or program name without its instance suffix:
    ``flat_scan_subchunk_min.6`` -> ``flat_scan_subchunk_min``,
    ``jit__grouped_impl(7684)`` -> ``jit__grouped_impl``."""
    return _SUFFIX.sub("", name.split("(", 1)[0])


def _clip(events, lo, hi):
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b))
    return out


def _union(intervals):
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


@dataclasses.dataclass
class Summary:
    """What a traced window says, averaged over the devices used."""

    window_s: float
    busy_s: float
    op_self_s: dict        # op name -> seconds (per device, mean)
    kernel_s: dict         # op base name -> seconds (per device, mean)
    # runs of each program (base name) that lie wholly in the window,
    # their seconds, and the seconds of each op base name inside them
    module_runs: dict
    module_s: dict
    module_op_s: dict      # program -> {op base name: seconds}
    collective_s: float
    gaps: list             # [(cause, seconds)], longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_self_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[c, s] for c, s in self.gaps[:top]]}


def _window(spans):
    wins = [(s, s + d) for n, s, d in spans if n == WINDOW_SPAN]
    if not wins:
        raise ValueError(f"trace holds no {WINDOW_SPAN} span")
    return min(a for a, _ in wins), max(b for _, b in wins)


def _cause(a, b, spans):
    """The harness span that covers most of the gap [a, b], the most
    telling first on ties; ``no_bench_span`` where none does."""
    cover = collections.Counter()
    for name, s, d in spans:
        if name in GAP_CAUSES:
            cover[name] += max(0, min(b, s + d) - max(a, s))
    best = max(GAP_CAUSES, key=lambda n: (cover[n], -GAP_CAUSES.index(n)))
    return best if cover[best] > 0 else "no_bench_span"


def reduce(trace: dict) -> Summary:
    """Reduce a plain trace over its ``bench.window`` span."""
    lo, hi = _window(trace["spans"])
    window = (hi - lo) * 1e-9
    devs = [d for d in trace["devices"] if d["ops"]]
    if not devs:
        raise ValueError("trace holds no device operation")
    n = len(devs)
    busy = 0.0
    op_self, kernel = collections.Counter(), collections.Counter()
    module_s, module_runs = collections.Counter(), collections.Counter()
    module_op = collections.defaultdict(collections.Counter)
    collective = 0.0
    gaps = []
    for dev in devs:
        ops = [(op_name(t), a, b) for t, a, b in _clip(dev["ops"], lo, hi)]
        merged = _union([(a, b) for _, a, b in ops])
        busy += sum(b - a for a, b in merged) * 1e-9
        for name, t in _exclusive(ops).items():
            op_self[name] += t * 1e-9 / n
        runs = sorted((a, a + d, base_name(name))
                      for name, a, d in dev["modules"]
                      if a >= lo and a + d <= hi)
        starts = [a for a, _, _ in runs]
        for a, b, prog in runs:
            module_s[prog] += (b - a) * 1e-9 / n
            module_runs[prog] += 1 / n
        for name, a, b in ops:
            base = base_name(name)
            kernel[base] += (b - a) * 1e-9 / n
            if base.startswith(COLLECTIVES):
                collective += (b - a) * 1e-9 / n
            j = bisect.bisect_right(starts, a) - 1
            if j >= 0 and b <= runs[j][1]:
                module_op[runs[j][2]][base] += (b - a) * 1e-9 / n
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((a, b))
    gaps.sort(key=lambda ab: ab[0] - ab[1])
    named = [(_cause(a, b, trace["spans"]), (b - a) * 1e-9)
             for a, b in gaps[:10]]
    return Summary(window_s=window, busy_s=busy / n,
                   op_self_s=dict(op_self), kernel_s=dict(kernel),
                   module_runs=dict(module_runs), module_s=dict(module_s),
                   module_op_s={p: dict(c) for p, c in module_op.items()},
                   collective_s=collective, gaps=named)


def _exclusive(ops):
    """Self time per op name: its duration less that of the ops nested
    in it (on one line, a while loop's event encloses its body's)."""
    out = collections.Counter()
    stack = []  # [name, end]
    for name, a, b in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= a:
            stack.pop()
        out[name] += b - a
        if stack:
            out[stack[-1][0]] -= min(b, stack[-1][1]) - a
        stack.append((name, b))
    return out
