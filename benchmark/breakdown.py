"""Break one cell's window into the program's own parts, on the chip.

    python3 benchmark/breakdown.py --workload <cell> --seed <n> \
        --seconds <s> [--out DIR] [--cost-seeds a,b,...] [--save-trace] \
        [--trace-s 1.0] [--set index.n_lists=64 ...]

Sets the cell up as ``benchmark/run.py`` does, then serves:

* with ``--cost-seeds``, two untraced windows per seed, one with the
  program's ranges off and one with them on (``core.annotate``'s gate,
  the one ``RAFT_TPU_PROFILE=1`` sets), in alternating order, and prints
  each window's end-to-end values: what the ranges cost while on;
* one window whose last second is traced through the program's own
  ``start_trace`` (:mod:`benchmark.program_trace`), with the compiled
  search programs' optimized HLO dumped for the op-to-scope map, and
  prints the reduction: device ms per scope per run, the scoped share,
  the serving spans paired with their runs, the gaps, and the request
  latency of the traced second beside the sum of its parts.

A program loaded from the persistent compile cache is not compiled, so
not dumped, and its ops read unscoped: give the run a seed whose index
shapes are new, or no cache.

``--set key.path=value`` (a JSON value) changes the configuration for
this run only, as in ``benchmark/calibrate.py``: a small index makes a
trace small enough to keep.

The last line of standard output is one JSON object; ``--out`` also
gets it and, with ``--save-trace``, the plain trace and the programs'
optimized HLO. Needs a TPU, as ``run.py`` does.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import shutil
import sys
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if sys.path and Path(sys.path[0] or ".").resolve() == ROOT / "benchmark":
    sys.path.pop(0)
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

PROGRAMS_RE = "jit__(grouped_impl|fused_l2_knn_impl)"


class ProgramTail:
    """``trace.TailTrace`` with the capture started and stopped through
    :mod:`benchmark.program_trace`, so that the program's ranges are on
    for exactly the traced part."""

    def __init__(self, log_dir: str, seconds: float, length_s: float):
        self.log_dir = log_dir
        self.seconds = seconds
        self.length_s = min(length_s, seconds / 2)
        self.t_start = None
        self.stop_s = None
        self._thread = None

    def arm(self, *hooks) -> None:
        from benchmark import program_trace, trace
        from benchmark.spans import span

        t0 = time.perf_counter()

        def body():
            time.sleep(max(0.0, t0 + self.seconds - self.length_s
                           - time.perf_counter()))
            for hook in hooks:
                hook()
            self.t_start = time.perf_counter()
            program_trace.start(self.log_dir)
            with span(trace.WINDOW_SPAN):
                time.sleep(self.length_s)

        self._thread = threading.Thread(target=body, name="bench-tracer")
        self._thread.start()

    def stop(self) -> None:
        from benchmark import program_trace

        self._thread.join()
        t0 = time.perf_counter()
        program_trace.stop()
        self.stop_s = time.perf_counter() - t0


class _Spec:
    """``run.Spec`` with configuration keys set anew."""

    def __init__(self, spec, sets):
        self._spec, self._sets = spec, sets
        self.root = spec.root

    def __getattr__(self, name):
        return getattr(self._spec, name)

    def config(self, name):
        cfg = self._spec.config(name)
        for path, value in self._sets:
            *head, last = path.split(".")
            node = cfg
            for key in head:
                node = node[key]
            node[last] = value
        return cfg


def closure(rec: dict, tail: ProgramTail, ps) -> dict:
    """The mean latency (due -> answer, host clock) of the requests
    answered in the traced part, beside the sum of its parts: the open
    loop's lag behind the schedule, the queue wait, then each batch's
    parts, weighted by the batch's requests. Queue wait is the
    executor's histogram over the requests packed after the capture
    began (its counters then and at the end)."""
    import numpy as np

    req = rec["requests"]
    lo, hi = tail.t_start, tail.t_start + tail.length_s
    done = np.where(np.isfinite(req["done"]), req["done"], -1.0)
    pick = (done >= lo) & (done < hi)
    if not pick.any() or not ps.batches:
        return {}
    lat = float(np.mean((req["done"] - req["due"])[pick])) * 1e3
    lag = (float(np.mean(np.asarray(rec["loadgen"]["lags_s"])[pick])) * 1e3
           if "loadgen" in rec else None)
    a, b = rec["executor_host"], rec["executor"]
    n = b["queue_wait_count"] - a["queue_wait_count"]
    qw = (b["queue_wait_sum_ms"] - a["queue_wait_sum_ms"]) / n if n else None
    w = [(bt.pack or {}).get("n_requests", 1) for bt in ps.batches]

    def mean(values):
        pairs = [(v, x) for v, x in zip(values, w) if v is not None]
        if not pairs:
            return None
        return sum(v * x for v, x in pairs) / sum(x for _, x in pairs) * 1e-6

    bt = ps.batches
    parts = {
        "submit_lag_ms": lag,
        "queue_wait_ms": qw,
        "window_wait_ms": mean([x.window_wait_ns for x in bt]),
        "staging_ms": mean([x.stage_ns for x in bt]),
        "device_queue_ms": mean([x.device_queue_ns for x in bt]),
        "run_ms": mean([x.run[1] - x.run[0] for x in bt]),
        "demux_lag_ms": mean([x.demux_lag_ns for x in bt]),
        "demux_ms": mean([x.demux[1] - x.demux[0] for x in bt]),
    }
    total = sum(v for v in parts.values() if v is not None)
    return {"requests": int(pick.sum()), "latency_mean_ms": lat,
            "parts": parts, "sum_ms": total,
            "gap_share": 1.0 - total / lat}


def served_window(drv, state, traffic, seconds, seed, tracer=None):
    """One served window; its record and end-to-end values."""
    from benchmark import run

    rec = drv.serve(state, traffic, seconds, seed, tracer)
    values = run.end_to_end_values(rec, 0.0, float("nan"))
    return rec, {k: values[k] for k in ("p95_ms", "qps") if k in values}


def breakdown(spec, name: str, seed: int, seconds: float, trace_s: float,
              cost_seeds, out: Path, hlo: Path, save_trace: bool = False,
              log=print) -> dict:
    """One cell's cost windows and traced window; returns the result
    (``hlo``: where the process dumps its programs' HLO)."""
    import jax

    from benchmark import program_trace
    from raft_tpu.core.annotate import set_profiling

    wl = spec.workload(name)
    cfg = spec.config(wl["config"])
    traffic = spec.traffic(wl["traffic"])
    drv = spec.driver(cfg["driver"])
    devices = jax.devices()[:wl["chips"]]
    t0 = time.perf_counter()
    state = drv.setup(cfg, traffic, seed, devices)
    result = {"workload": name, "seed": seed,
              "setup_s": time.perf_counter() - t0,
              "device": {"platform": devices[0].platform,
                         "kind": devices[0].device_kind}}
    log(f"{name} seed {seed}: set-up {result['setup_s']:.1f} s")

    cost = []
    for k, s in enumerate(cost_seeds):
        for on in ((False, True), (True, False))[k % 2]:
            prev = set_profiling(on)
            try:
                _, values = served_window(drv, state, traffic, seconds, s)
            finally:
                set_profiling(prev)
            cost.append({"seed": s, "ranges_on": on, **values})
            log(f"cost window: {cost[-1]}")
    if cost:
        result["cost"] = cost

    tdir = spec.root / ".bench_cache" / "breakdown" / f"{name}.s{seed}"
    shutil.rmtree(tdir, ignore_errors=True)
    tail = ProgramTail(str(tdir), seconds, trace_s)
    rec, values = served_window(drv, state, traffic, seconds, seed, tail)
    tail.stop()
    drv.release(state)
    result["end_to_end"] = values
    t1 = time.perf_counter()
    plain = program_trace.load(str(tdir), str(hlo))
    shutil.rmtree(tdir, ignore_errors=True)
    if save_trace:
        keep = out / "hlo" / f"{name}.s{seed}"
        keep.mkdir(parents=True, exist_ok=True)
        program_trace.trace.save(plain, str(out / f"{name}.s{seed}"
                                            ".trace.json.gz"))
        for path in hlo.glob("*after_optimizations.txt"):
            with open(path, "rb") as f, \
                    gzip.open(keep / (path.name + ".gz"), "wb") as g:
                shutil.copyfileobj(f, g)
    ps = program_trace.reduce(plain)
    log(f"trace: stop {tail.stop_s:.1f} s, load and reduce "
        f"{time.perf_counter() - t1:.1f} s")
    base = ps.base
    result.update({
        "programs": {p: {"runs": base.module_runs[p],
                         "ms_per_run": base.module_s[p]
                         / base.module_runs[p] * 1e3}
                     for p in base.module_runs},
        "scope_ms": ps.scope_ms, "scoped_share": ps.scoped_share,
        "window_wait_ms": ps.window_wait_ms(),
        "device_queue_ms": ps.device_queue_ms(),
        "demux_lag_ms": ps.demux_lag_ms(),
        "batches_paired": None if ps.batches is None else len(ps.batches),
        "idle_share": base.idle_share, "gaps": ps.gaps,
        "breakdown": base.breakdown(),
        "hlo_programs": sorted(plain.get("hlo_scopes", {})),
    })
    if ps.batches:
        result["closure"] = closure(rec, tail, ps)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--trace-s", type=float, default=1.0)
    ap.add_argument("--cost-seeds", default="")
    ap.add_argument("--out", default=str(ROOT / "chiprun_out" / "breakdown"))
    ap.add_argument("--save-trace", action="store_true")
    ap.add_argument("--set", action="append", default=[])
    args = ap.parse_args(argv)
    sets = [(k, json.loads(v)) for k, v in
            (s.split("=", 1) for s in args.set)]

    out = Path(args.out)
    tag = f"{args.workload}.s{args.seed}"
    hlo = ROOT / ".bench_cache" / "breakdown" / "hlo" / tag
    shutil.rmtree(hlo, ignore_errors=True)
    # the search programs' optimized HLO, for the op-to-scope map (set
    # before JAX starts its backend)
    os.environ["XLA_FLAGS"] = " ".join(filter(None, [
        os.environ.get("XLA_FLAGS"), f"--xla_dump_to={hlo}",
        "--xla_dump_hlo_as_text",
        f"--xla_dump_hlo_module_re={PROGRAMS_RE}"]))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    from benchmark import run

    if jax.devices()[0].platform != "tpu":
        print(f"breakdown: no TPU: JAX found {jax.devices()[0].platform}",
              file=sys.stderr)
        return 2
    result = breakdown(
        _Spec(run.Spec(ROOT), sets), args.workload, args.seed, args.seconds,
        args.trace_s, [int(x) for x in args.cost_seeds.split(",") if x],
        out, hlo, args.save_trace,
        log=lambda s: print(s, file=sys.stderr, flush=True))
    result["set"] = args.set
    line = json.dumps(result, default=float)
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{tag}.json").write_text(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
