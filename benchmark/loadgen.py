"""Load generation: an open loop on a seeded Poisson schedule, and a
closed loop with a fixed number of requests outstanding.

The open loop follows ``raft_tpu.testing.load`` (``poisson_arrivals``,
``replay``) with two departures the benchmark needs:

* every seed gets the same multiset of inter-arrival gaps (the quantiles
  of the exponential law at the offered rate) and of request sizes, in a
  seeded order, so that two seeds offer the same work;
* latency is timed from each request's *due* time on the schedule, not
  from when the generator got round to submitting it: a stall that makes
  the generator late shows in every request it delays. The generator's
  lag is recorded beside it.
"""

from __future__ import annotations

import math
import time
from typing import Callable

import numpy as np

from benchmark.spans import span


def _rng(seed: int, stream: int):
    return np.random.default_rng(
        [int(seed) & 0xFFFFFFFF, int(seed) >> 32, stream])


def open_schedule(rate_rps: float, seconds: float, size_lo: int,
                  size_hi: int, seed: int):
    """(due offsets in s, sizes) of ``ceil(rate_rps * seconds)`` requests
    arriving at ``rate_rps`` requests per second from offset 0; sizes
    uniform on ``[size_lo, size_hi]``. The inter-arrival gaps are the
    exponential law's quantiles and the sizes a balanced cycle: both
    fixed by the rate and the window, ordered by ``seed``. The last
    request is due before ``seconds``."""
    n = max(1, int(math.ceil(rate_rps * seconds)))
    u = (np.arange(n - 1) + 0.5) / max(1, n - 1)
    gaps = -np.log1p(-u) / rate_rps
    sizes = size_lo + np.arange(n) % (size_hi - size_lo + 1)
    rng = _rng(seed, 1)
    gaps = rng.permutation(gaps)
    sizes = rng.permutation(sizes).astype(np.int64)
    return np.concatenate([[0.0], np.cumsum(gaps)]), sizes


def replay_open(times_s, submit: Callable[[int], object], *,
                clock: Callable[[], float] = time.perf_counter,
                sleep: Callable[[float], None] = time.sleep):
    """Call ``submit(i)`` at each due offset in ``times_s`` from now,
    never waiting on what it returned; when a submit ran long, the next
    fires at once. Returns ``(t0, handles, stamps, lags)``: the start on
    ``clock``, each submit's return value (or the exception it raised),
    the actual submit stamps, and each request's lag behind its due
    time."""
    n = len(times_s)
    handles, stamps, lags = [None] * n, np.zeros(n), np.zeros(n)
    t0 = clock()
    for i in range(n):
        due = t0 + float(times_s[i])
        now = clock()
        if now < due:
            with span("bench.schedule.sleep"):
                sleep(due - now)
            now = clock()
        stamps[i], lags[i] = now, now - due
        with span("bench.schedule.submit"):
            try:
                handles[i] = submit(i)
            except Exception as exc:   # noqa: BLE001 — a shed is data
                handles[i] = exc
    return t0, handles, stamps, lags


def closed_loop(send: Callable[[int], object],
                wait: Callable[[object], object], outstanding: int,
                seconds: float, *,
                clock: Callable[[], float] = time.perf_counter):
    """Keep ``outstanding`` requests in flight: ``send(i)`` starts
    request ``i``; ``wait(handle)`` blocks for its answer. A new request
    is sent when one completes, until ``seconds`` have passed; then the
    rest are drained. Returns ``(t0, answers, sent, done)`` with the
    start, each answer, and the send and completion stamps."""
    answers, sent, done = [], [], []
    inflight = []
    t0 = clock()

    def start():
        i = len(answers)
        answers.append(None)
        sent.append(clock())
        done.append(math.nan)
        inflight.append((i, send(i)))

    for _ in range(outstanding):
        start()
    while inflight:
        i, handle = inflight.pop(0)
        with span("bench.wait"):
            answers[i] = wait(handle)
        done[i] = clock()
        if done[i] - t0 < seconds:
            start()
    return t0, answers, np.asarray(sent), np.asarray(done)
