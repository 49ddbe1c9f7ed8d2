"""Run one cell of raft_tpu's benchmark on the chips of this machine.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>
    python3 benchmark/run.py --dry       # list what BENCHMARK.json names

A cell is one entry of ``workloads`` in ``BENCHMARK.json``: a
configuration under a traffic mix. Everything that belongs to one of them
is a file of its own, found by name:

* ``benchmark/configs/<config>.json``: sizes, index parameters, the limits
  of the comparison, and ``driver``, a module
  ``benchmark/drivers/<driver>.py`` with ``setup``, ``serve``, ``work``
  and ``release`` (``serve`` arms the tracer it is given as its window
  opens: see ``benchmark/serving.py``);
* ``benchmark/traffic/<traffic>.json``: the loop, sizes, rate or
  concurrency, and buckets;
* ``benchmark/metrics/<metric>.py``: a ``read(rec, tr, peak)`` that takes
  one per-layer metric from the run record and the reduced trace, or
  returns None where it finds nothing to read.

With ``--trace 0`` the result holds the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics from a traced window. The last line
of standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, then ``checks``: each number
compared beside its limit). A machine without a TPU, or with fewer chips
than the cell asks for, gets no result and a non-zero exit.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

# the TPU runtime logs under /tmp/tpu_logs unless told otherwise; a run
# writes only inside its checkout and the directories it is given
os.environ.setdefault("TPU_LOG_DIR", "disabled")

ROOT = Path(__file__).resolve().parent.parent
# import the benchmark as a package of the checkout, never its modules
# by bare name (benchmark/trace.py would shadow the standard library's)
if sys.path and Path(sys.path[0] or ".").resolve() == ROOT / "benchmark":
    sys.path.pop(0)
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from benchmark import correct, reference, roofline, trace  # noqa: E402

# the reference needs some hundreds of sampled queries to read recall to
# 1e-3; 1,024 keeps its run under a few seconds at 12.5M rows
CHECK_QUERIES = 1024
# the traced part of a --trace 1 window, at its end
TRACE_S = 1.0


class BenchError(Exception):
    """The run cannot give a result; the message says why."""


def _load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise BenchError(f"no module at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: Path) -> dict:
    if not path.is_file():
        raise BenchError(f"no file {path}")
    with open(path) as f:
        return json.load(f)


class Spec:
    """``BENCHMARK.json`` and the files it names, under ``root``."""

    def __init__(self, root: Path):
        self.root = Path(root)
        self.bench = _read_json(self.root / "BENCHMARK.json")
        self.dir = self.root / "benchmark"

    def workload(self, name: str) -> dict:
        for wl in self.bench["workloads"]:
            if wl["name"] == name:
                return wl
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        return _read_json(self.dir / "configs" / f"{name}.json")

    def traffic(self, name: str) -> dict:
        return _read_json(self.dir / "traffic" / f"{name}.json")

    def driver(self, name: str):
        return _load_module(self.dir / "drivers" / f"{name}.py",
                            f"bench_driver_{name}")

    def reader(self, name: str):
        return _load_module(self.dir / "metrics" / f"{name}.py",
                            f"bench_metric_{name.replace('.', '_')}")

    @staticmethod
    def _applies(metric: dict, wl: str) -> bool:
        return "workloads" not in metric or wl in metric["workloads"]

    def end_to_end(self, wl: str):
        return [m for m in self.bench["end_to_end"] if self._applies(m, wl)]

    def per_layer(self, wl: str):
        reported = {m["name"] for m in self.end_to_end(wl)}
        return [m for m in self.bench["per_layer"]
                if self._applies(m, wl) and m["moves"] in reported]


def dry(spec: Spec) -> list:
    """What the benchmark would run: each cell with its configuration,
    traffic, driver and metrics, each file loaded."""
    out = []
    for wl in spec.bench["workloads"]:
        cfg = spec.config(wl["config"])
        spec.traffic(wl["traffic"])
        spec.driver(cfg["driver"])
        layer = [m["name"] for m in spec.per_layer(wl["name"])]
        for name in layer:
            spec.reader(name)
        out.append({"workload": wl["name"], "config": wl["config"],
                    "traffic": wl["traffic"], "driver": cfg["driver"],
                    "chips": wl["chips"],
                    "end_to_end": [m["name"]
                                   for m in spec.end_to_end(wl["name"])],
                    "per_layer": layer})
    return out


def _enable_compile_cache(root: Path) -> None:
    import jax

    path = root / ".bench_cache" / "jax"
    path.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


_COMPILES = []


def _compiles() -> int:
    """Programs compiled or loaded from the compile cache so far in this
    process (the listeners are registered on the first call)."""
    if not _COMPILES:
        import jax.monitoring as mon

        _COMPILES.append(0)

        def event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                _COMPILES[0] += 1

        def duration(name, _secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                _COMPILES[0] += 1

        mon.register_event_listener(event)
        mon.register_event_duration_secs_listener(duration)
    return _COMPILES[0]


def _device_info(devices) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(max(peaks))}


def end_to_end_values(rec: dict, setup_s: float, recall: float) -> dict:
    """Every end-to-end quantity a run can give; a cell reports those
    that ``BENCHMARK.json`` gives it."""
    req = rec["requests"]
    lat = req["done"] - req["due"]
    lat = np.where(np.isfinite(lat), lat, math.inf)
    out = {"setup_s": setup_s, "recall_at_10": recall}
    if lat.size:
        out["p95_ms"] = float(np.percentile(lat, 95,
                                            method="inverted_cdf")) * 1e3
    ok = np.isfinite(req["done"])
    if ok.any():
        span = float(np.max(req["done"][ok]) - rec["t0"])
        out["qps"] = float(req["sizes"][ok].sum()) / span
        # the same rate under a name of its own, so that exact search,
        # which spreads far less than IVF, is held to a bound of its own
        out["qps.exact"] = out["qps"]
    return out


def run_workload(spec: Spec, name: str, seed: int, seconds: float,
                 traced: bool, *, require_tpu: bool = True,
                 control: bool = False, t_start: float = T_START,
                 log=print) -> dict:
    """One run of cell ``name``; returns the result object. ``control``
    also reads the int8 control on the same sampled queries (under
    ``"control"``); the benchmark's own runs never do."""
    import jax

    wl = spec.workload(name)
    cfg = spec.config(wl["config"])
    traffic = spec.traffic(wl["traffic"])
    drv = spec.driver(cfg["driver"])
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found {devices[0].platform}")
    if len(devices) < wl["chips"]:
        raise BenchError(f"{name} needs {wl['chips']} chips, JAX found "
                         f"{len(devices)}")
    devices = devices[:wl["chips"]]
    peak = roofline.peaks(devices[0].device_kind) if require_tpu else {}

    t_setup = time.perf_counter()
    state = drv.setup(cfg, traffic, seed, devices)
    # what set-up made lives through the window: keep it out of the
    # collector's full passes, which otherwise stall the load generator
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    log("set-up: " + ", ".join(
        f"{k} {v:.2f} s" for k, v in
        [("to the driver", t_setup - t_start)]
        + sorted(state.get("phases", {}).items()))
        + (f"; {state['about']}" if "about" in state else ""))
    before = _compiles()
    tdir = spec.root / ".bench_cache" / "trace" / name
    tracer = None
    if traced:
        shutil.rmtree(tdir, ignore_errors=True)
        tracer = trace.TailTrace(str(tdir), seconds, TRACE_S)
    rec = drv.serve(state, traffic, seconds, seed, tracer)
    if traced:
        tracer.stop()
    compiled = _compiles() - before
    device = _device_info(devices)
    if traced:
        rec["work"] = drv.work(state, rec)
    rows, pool = drv.release(state)

    req = rec["requests"]
    answered = np.isfinite(req["done"])
    unanswered = int((~answered).sum())
    pick = correct.sample_requests(seed, req["sizes"], answered,
                                   CHECK_QUERIES)
    qidx = np.concatenate([req["start"][i] + np.arange(req["sizes"][i])
                           for i in pick]) % pool.shape[0]
    q = pool[qidx]
    served_d = np.concatenate([rec["answers"][i][0] for i in pick])
    served_i = np.concatenate([rec["answers"][i][1] for i in pick])
    k = served_i.shape[1]
    ref_d, ref_i = reference.exact_knn(rows, q, k, cfg["reference_block"])
    miss, err = correct.gaps(rows, q, served_d, served_i, ref_d, ref_i)
    numbers = {"unanswered": unanswered, "miss_at_10": miss,
               "dist_err": err}
    limits = cfg["limits"]
    ok = correct.judge(numbers, limits)

    if traced:
        t0 = time.perf_counter()
        raw = trace.load(str(tdir))
        t1 = time.perf_counter()
        tr = trace.reduce(raw)
        log(f"trace: stop {tracer.stop_s:.1f} s, load {t1 - t0:.1f} s, "
            f"reduce {time.perf_counter() - t1:.1f} s, "
            f"{sum(len(d['ops']) for d in raw['devices'])} device ops")
        del raw
        shutil.rmtree(tdir, ignore_errors=True)
        metrics = {}
        for m in spec.per_layer(name):
            v = spec.reader(m["name"]).read(rec, tr, peak)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = tr.busy_s
        device["window_s"] = tr.window_s
    else:
        values = end_to_end_values(rec, setup_s, 1.0 - miss)
        metrics = {m["name"]: {"value": values[m["name"]],
                               "unit": m["unit"]}
                   for m in spec.end_to_end(name)}
    log(f"{name} seed {seed}: {len(req['sizes'])} requests, "
        f"{int(req['sizes'].sum())} queries, {compiled} programs compiled "
        f"or loaded in the window, sample {len(pick)} requests / "
        f"{q.shape[0]} queries")
    # answers' spacing: a slower device moves the median, a host that
    # falls behind the device moves the tail
    gaps = np.diff(np.sort(req["done"][answered])) * 1e3
    if gaps.size:
        log(f"answers {gaps.size + 1}, ms between them: median "
            f"{np.median(gaps):.2f}, p99 {np.percentile(gaps, 99):.2f}, "
            f"max {gaps.max():.2f}, over 2x median "
            f"{gaps[gaps > 2 * np.median(gaps)].sum():.0f} in all")
    result = {"correct": bool(ok), "attempted": int(len(req["sizes"])),
              "failed": unanswered, "metrics": metrics, "device": device}
    if traced:
        result["breakdown"] = tr.breakdown()
    if control:
        cd, ci = reference.int8_knn(rows, q, k, cfg["reference_block"])
        c_numbers = dict(zip(("miss_at_10", "dist_err"),
                             correct.gaps(rows, q, cd, ci, ref_d, ref_i)),
                         unanswered=0)
        result["control"] = dict(c_numbers,
                                 correct=correct.judge(c_numbers, limits))
    result["checks"] = {n: {"value": numbers[n], "limit": limits[n]}
                        for n in correct.NUMBERS}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dry", action="store_true")
    args = ap.parse_args(argv)
    try:
        spec = Spec(ROOT)
        if args.dry:
            for row in dry(spec):
                print(json.dumps(row))
            return 0
        if not args.workload:
            raise BenchError("--workload is required")
        import raft_tpu

        if Path(raft_tpu.__file__).resolve().parent.parent != ROOT:
            raise BenchError(f"raft_tpu is not in the checkout {ROOT}")
        _enable_compile_cache(ROOT)
        result = run_workload(spec, args.workload, args.seed, args.seconds,
                              bool(args.trace),
                              log=lambda s: print(s, file=sys.stderr))
    except (BenchError, ImportError, KeyError) as e:
        print(f"benchmark: {type(e).__name__}: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
