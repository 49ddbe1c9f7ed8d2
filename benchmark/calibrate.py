"""Read the numbers that decide ``correct`` over many seeds in one
process: the program's, and the int8 control's on the same sampled
queries. The limits in ``configs/<config>.json`` are set from these
readings (PERF.md gives them).

    python3 benchmark/calibrate.py --workload deep12m_ivf_flat.bulk \
        --seeds 101,102,103 --seconds 4 [--control] [--set index.n_probes=8]

Prints one JSON line per seed. ``--set key.path=value`` (a JSON value)
changes the configuration for these runs only: a planted fault, such as
half the probes, read by the same comparison. The benchmark's own runs
never run the control.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import run


class _Spec(run.Spec):
    """The benchmark's files, with configuration keys set anew."""

    def __init__(self, root, sets):
        super().__init__(root)
        self.sets = sets

    def config(self, name):
        cfg = super().config(name)
        for path, value in self.sets:
            *head, last = path.split(".")
            node = cfg
            for key in head:
                node = node[key]
            node[last] = value
        return cfg


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--set", action="append", default=[])
    args = ap.parse_args(argv)
    sets = [(k, json.loads(v)) for k, v in
            (s.split("=", 1) for s in args.set)]
    spec = _Spec(run.ROOT, sets)
    run._enable_compile_cache(run.ROOT)
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        res = run.run_workload(
            spec, args.workload, seed, args.seconds, False,
            control=args.control, t_start=t0,
            log=lambda s: print(s, file=sys.stderr))
        print(json.dumps({"seed": seed, "set": args.set,
                          "correct": res["correct"],
                          "checks": res["checks"],
                          "control": res.get("control"),
                          "metrics": res["metrics"],
                          "memory_peak_bytes":
                              res["device"]["memory_peak_bytes"],
                          "wall_s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
