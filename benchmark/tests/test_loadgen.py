"""The open loop times each request from its due time; the closed loop
keeps its requests outstanding."""

import threading
import time

import numpy as np

from benchmark import loadgen


def test_every_seed_offers_the_same_work():
    t1, s1 = loadgen.open_schedule(200.0, 10.0, 1, 8, seed=1)
    t2, s2 = loadgen.open_schedule(200.0, 10.0, 1, 8, seed=2 ** 40 + 3)
    g1, g2 = np.diff(t1), np.diff(t2)
    assert not np.array_equal(t1, t2)
    # the same multiset of gaps and of sizes, in another order
    assert np.allclose(np.sort(g1), np.sort(g2), rtol=0, atol=1e-12)
    assert np.array_equal(np.sort(s1), np.sort(s2))
    assert len(t1) == 2000 and set(s1.tolist()) == set(range(1, 9))
    assert s1.mean() == 4.5
    # Poisson at the rate: mean gap ~1/rate, all due inside the window
    assert abs(g1.mean() - 1 / 200.0) < 0.05 / 200.0
    assert t1[0] == 0.0 and t1[-1] < 10.0 and (g1 > 0).all()
    again, _ = loadgen.open_schedule(200.0, 10.0, 1, 8, seed=1)
    assert np.array_equal(t1, again)


class _StalledServer:
    """Answers at once, except that submit ``stall_at`` blocks the
    caller for ``stall_s``: a stalled submit path."""

    def __init__(self, stall_at, stall_s):
        self.stall_at, self.stall_s = stall_at, stall_s
        self.done = {}

    def submit(self, i):
        if i == self.stall_at:
            time.sleep(self.stall_s)
        self.done[i] = time.perf_counter()
        return i


def test_latency_from_due_time_shows_a_stall():
    times = np.arange(10) * 0.01          # one request every 10 ms
    server = _StalledServer(stall_at=3, stall_s=0.2)
    t0, handles, stamps, lags = loadgen.replay_open(times, server.submit)
    due = t0 + times
    done = np.array([server.done[i] for i in range(10)])
    from_due = done - due
    from_submit = done - stamps
    # requests 4.. were due while request 3 stalled the submit path
    assert (from_due[4:] > 0.1).all()
    assert (lags[4:] > 0.1).all()
    # the submit stamps hide it: each of them was answered at once
    assert (from_submit[4:] < 0.05).all()
    assert from_due[:3].max() < 0.05
    assert handles == list(range(10))


def test_replay_records_a_shed_as_data():
    def submit(i):
        if i == 1:
            raise RuntimeError("shed")
        return i

    _, handles, _, _ = loadgen.replay_open(np.zeros(3), submit)
    assert handles[0] == 0 and isinstance(handles[1], RuntimeError)


def test_closed_loop_keeps_requests_outstanding():
    lock = threading.Lock()
    state = {"now": 0, "peak": 0}

    def send(i):
        with lock:
            state["now"] += 1
            state["peak"] = max(state["peak"], state["now"])
        return i

    def wait(h):
        time.sleep(0.01)
        with lock:
            state["now"] -= 1
        return h

    t0, answers, sent, done = loadgen.closed_loop(send, wait, 2, 0.1)
    assert state["peak"] == 2 and state["now"] == 0
    assert answers == list(range(len(answers)))
    assert (done >= sent).all() and np.isfinite(done).all()
    # sending stops at the window's end; the rest drain
    assert (sent - t0 < 0.1 + 0.02).all()
