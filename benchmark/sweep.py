"""Sweep the offered rate of an open-loop cell, to find its knee: the
highest rate the system sustains.

    python3 benchmark/sweep.py --workload deep12m_ivf_flat.interactive \
        --rates 100,200,300 --seconds 10 --seed 11

One process sets the cell up once, then serves one window per rate,
traces its last ``--trace-s`` seconds and stops the profiler after the
window, as a ``--trace 1`` run does. It prints a JSON line for each rate:
the cell's per-layer metrics, read by their own readers, beside the
sweep's own figures over the requests due before the tracer started:
p50 and p95 latency from the due time, and how much longer the last
quarter's requests waited than the first quarter's (above 1, a backlog
that grows). The cell's traffic file keeps the rate chosen from it as a
number; the benchmark's own runs never sweep.
"""

from __future__ import annotations

import argparse
import gc
import json
import shutil
import sys

import numpy as np

import run  # noqa: F401  (puts the checkout on sys.path)
from benchmark import roofline, trace


def own_figures(rec) -> dict:
    req = rec["requests"]
    keep = np.ones(len(req["due"]), bool)
    if rec.get("host_until") is not None:
        keep = req["due"] < rec["host_until"]
    lat = (req["done"] - req["due"])[keep] * 1e3
    lat = np.where(np.isfinite(lat), lat, np.inf)
    q = max(1, lat.size // 4)
    return {"requests": int(lat.size),
            "p50_ms": float(np.percentile(lat, 50, method="inverted_cdf")),
            "p95_ms": float(np.percentile(lat, 95, method="inverted_cdf")),
            "late_over_early": float(np.median(lat[-q:])
                                     / np.median(lat[:q]))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace-s", type=float, default=0.5)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    spec = run.Spec(run.ROOT)
    run._enable_compile_cache(run.ROOT)
    wl = spec.workload(args.workload)
    cfg = spec.config(wl["config"])
    traffic = spec.traffic(wl["traffic"])
    drv = spec.driver(cfg["driver"])
    devices = jax.devices()[:wl["chips"]]
    if devices[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 2
    peak = roofline.peaks(devices[0].device_kind)
    state = drv.setup(cfg, traffic, args.seed, devices)
    gc.collect()
    gc.freeze()
    tdir = run.ROOT / ".bench_cache" / "trace" / "sweep"
    for rate in [float(r) for r in args.rates.split(",")]:
        shutil.rmtree(tdir, ignore_errors=True)
        tracer = trace.TailTrace(str(tdir), args.seconds, args.trace_s)
        rec = drv.serve(state, dict(traffic, rate_rps=rate), args.seconds,
                        args.seed, tracer)
        tracer.stop()
        tr = trace.reduce(trace.load(str(tdir)))
        row = {"rate_rps": rate,
               "offered_qps": float(rec["requests"]["sizes"].sum())
               / args.seconds}
        row.update(own_figures(rec))
        for m in spec.per_layer(args.workload):
            row[m["name"]] = spec.reader(m["name"]).read(rec, tr, peak)
        print(json.dumps(row), flush=True)
    shutil.rmtree(tdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
