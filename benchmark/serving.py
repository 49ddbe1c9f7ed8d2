"""The window's loops, shared by the drivers: an open loop of requests
through ``raft_tpu.serving.ServingExecutor``, and closed loops through
the executor or straight through a search call.

Each returns the run record that the harness reduces::

    {"t0": start on the host clock,
     "requests": {"due", "done", "sizes", "start"},   # arrays, one per
     "answers": [(dists, ids) host arrays, or None],  # request
     "loadgen": {"lags_s": ...}, "executor": {...},
     "host_until": t or None, "executor_host": {...}}

``done`` is NaN for a request that failed or never came; ``start`` is
the request's first row in the query pool (rows wrap round the pool).
In a traced run the loops arm the tracer (:class:`benchmark.trace.
TailTrace`) as the window opens; ``host_until`` is then when it started
the profiler, and host readings cover the requests due before it
(``executor_host`` is the executor's counters at that moment).
"""

from __future__ import annotations

import math
import time

import numpy as np

from benchmark import loadgen
from benchmark.spans import span

# an answer may come this long after the window closes and still count
LATE_S = 60.0


def pool_rows(pool, start, size):
    """``size`` rows of the query pool from ``start``, wrapping round."""
    return pool[(start + np.arange(size)) % pool.shape[0]]


def _starts(sizes, n_pool, seed):
    """Each request's first pool row: consecutive runs of the pool from a
    seeded offset."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 3])
    return (rng.integers(0, n_pool) + np.concatenate(
        [[0], np.cumsum(sizes)[:-1]])) % n_pool


def executor(dispatch, buckets, dim):
    """A ``ServingExecutor`` with its own metric registry, whose staging
    is a harness span, and the registry."""
    import jax

    from raft_tpu.obs.metrics import MetricRegistry
    from raft_tpu.serving import ServingExecutor

    def stage(batch):
        with span("bench.stage"):
            return jax.device_put(batch)

    reg = MetricRegistry()
    return ServingExecutor(dispatch, buckets, dim=dim, registry=reg,
                           stage=stage), reg


def executor_stats(ex, reg, buckets) -> dict:
    st = ex.stats()
    qw = [reg.histogram("serving_stage_ms", executor=ex.name,
                        stage="queue_wait", bucket=b) for b in buckets]
    return {"batches": st.batches, "valid_rows": st.valid_rows,
            "padded_rows": st.padded_rows,
            "queue_wait_sum_ms": sum(h.sum for h in qw),
            "queue_wait_count": sum(h.count for h in qw)}


def _collect(handles, deadline):
    answers, ok = [], []
    for h in handles:
        try:
            if isinstance(h, BaseException):
                raise h
            d, i = h.result(timeout=max(0.0, deadline - time.perf_counter()))
            answers.append((np.asarray(d), np.asarray(i)))
            ok.append(True)
        except Exception:   # noqa: BLE001 — a failed request is data
            answers.append(None)
            ok.append(False)
    return answers, np.asarray(ok, bool)


def arm(tracer, *hooks):
    """Arm ``tracer`` (or nothing where the run is not traced)."""
    if tracer is not None:
        tracer.arm(*hooks)


def host_until(tracer):
    return None if tracer is None else tracer.t_start


def open_loop(ex, pool, traffic, seconds, seed, tracer=None,
              hooks=()) -> dict:
    """Poisson arrivals at the traffic's rate through ``ex``; each request
    timed from its due time to its answer. ``tracer`` is armed, with
    ``hooks``, as the window opens."""
    lo, hi = traffic["sizes"]
    times, sizes = loadgen.open_schedule(traffic["rate_rps"], seconds, lo,
                                         hi, seed)
    starts = _starts(sizes, pool.shape[0], seed)
    done = np.full(len(sizes), math.nan)

    def submit(i):
        fut = ex.submit(pool_rows(pool, starts[i], sizes[i]))
        fut.add_done_callback(
            lambda f, i=i: done.__setitem__(i, time.perf_counter()))
        return fut

    arm(tracer, *hooks)
    t0, handles, _stamps, lags = loadgen.replay_open(times, submit)
    with span("bench.wait"):
        answers, ok = _collect(handles, t0 + seconds + LATE_S)
    done[~ok] = math.nan
    return {"t0": t0,
            "requests": {"due": t0 + times, "done": done, "sizes": sizes,
                         "start": starts},
            "answers": answers, "loadgen": {"lags_s": lags},
            "host_until": host_until(tracer)}


def closed_loop(send, wait, pool, traffic, seconds, seed, tracer=None,
                hooks=()) -> dict:
    """``traffic["outstanding"]`` requests of ``traffic["queries"]`` rows
    in flight: ``send(rows)`` starts one, ``wait(handle)`` returns its
    (dists, ids). ``tracer`` is armed, with ``hooks``, as the window
    opens."""
    size = traffic["queries"]
    base = _starts(np.array([1]), pool.shape[0], seed)[0]

    def start_of(i):
        return (base + i * size) % pool.shape[0]

    arm(tracer, *hooks)
    t0, answers, sent, done = loadgen.closed_loop(
        lambda i: send(pool_rows(pool, start_of(i), size)), wait,
        traffic["outstanding"], seconds)
    n = len(answers)
    return {"t0": t0,
            "requests": {"due": sent, "done": done,
                         "sizes": np.full(n, size, np.int64),
                         "start": np.array([start_of(i) for i in range(n)],
                                           np.int64)},
            "answers": [(np.asarray(d), np.asarray(i)) for d, i in answers],
            "host_until": host_until(tracer)}
