"""Serving-latency surface — p50 dispatch latency at small batch for the
three serving engines (fused exact kNN, grouped IVF-Flat, grouped
IVF-PQ), swept over nq ∈ {1, 128, 1024} at the shared 500k x 96 bench
config (docs/serving.md; the reference treats n_queries as a first-class
sweep axis, cpp/bench/spatial/knn.cu:34-60).

Methodology: each point is a chained-dispatch quotient
(bench/common.py) — the chain is device-serialized by a data
dependence, so with no pipelining the per-dispatch quotient IS the
program's dispatch-to-done latency, and the two-point difference
cancels the fixed host round trip that a naive
time-one-dispatch-and-block measurement would report as "latency". The
median over the (spread-escalated 3-7) repeats is the reported p50.

The serving recipe under measurement is the docs/serving.md one:
explicit integer qcap resolved by ``index.warmup(nq)`` (no per-call
host sync, no data-dependent re-trace), program caches warmed before
the clock starts, one jitted program per (engine, nq).

Two resilience rows ride on the IVF-Flat engine (docs/serving.md
"Overload and shedding", docs/robustness.md "hedge-delay tuning"):

* ``hedged_straggler`` — per-request latency with a deterministic
  injected straggler (every N-th dispatch polls not-ready for ~8x p50,
  ``faults.inject_straggler``), measured unhedged (``p99_ms``) and
  through ``resilience.dispatch_hedged`` (``hedged_p99_ms``): the hedge
  collapses the straggler tail toward hedge_delay + p50.
* ``overload_2x`` — a timed open-loop arrival schedule at 2x the
  measured sustainable rate driven through an
  ``AdmissionController`` (bounded queue): ``p99_ms`` of ADMITTED
  requests stays bounded at ~(max_queue+1) service times and the
  excess load is shed with ``RaftOverloadError`` (``shed_rate``)
  instead of collapsing the queue.

A third ``mixed_ingest`` row measures the mutation tier
(docs/mutation.md): search QPS under concurrent streaming ingest next
to the frozen-index QPS (``qps_ratio_vs_frozen`` — acceptance >= ~0.8
at equal recall), sustained ``ingest_qps``, and the upsert->visible /
delete->masked latencies (:func:`mixed_ingest_row`).

The ``open_loop`` row (ISSUE 8, docs/serving.md "Open-loop serving")
measures the serving EXECUTOR, not the program: a deterministic seeded
Poisson arrival stream (``raft_tpu.testing.load``) is driven through
``raft_tpu.serving.ServingExecutor`` (shape-bucketed micro-batching +
async pipelined dispatch), and the row reports

* ``program_qps`` — the raw compiled-program QPS at the largest
  bucket (closed-loop chained quotient, the denominator of the
  acceptance ratio);
* ``saturation_qps`` — measured open-loop completion rate with the
  arrival stream offered ABOVE capacity (admission sheds the excess);
* ``qps_ratio_vs_program`` — saturation over program QPS: the
  executor's dispatch-gap overhead (acceptance >= ~0.8);
* ``p50_ms_50/p99_ms_50`` (and ``_80``, ``_95``) — per-request
  latency percentiles at 50%/80%/95% of the measured saturation —
  the offered-load sweep that shows WHERE the latency knee sits.
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

NQS = (1, 128, 1024)


def _p99(ms_list) -> float:
    return float(np.percentile(np.asarray(ms_list), 99.0))


def _dispatch_lat_s(run, qb, reps: int = 16):
    lat = []
    for i in range(reps):
        qi = qb * (1.0 + 1e-6 * (i + 1))
        jax.block_until_ready(qi)
        t0 = time.perf_counter()
        jax.block_until_ready(run(qi))
        lat.append(time.perf_counter() - t0)
    lat.sort()
    return lat


def _dispatch_p50_s(run, qb, reps: int = 16) -> float:
    lat = _dispatch_lat_s(run, qb, reps)
    return lat[len(lat) // 2]


def hedged_straggler_row(run, qb, *, straggler_every: int = 8,
                         n_requests: int = 64,
                         straggler_s=None) -> dict:
    """p99 with a periodic injected straggler, unhedged vs hedged.

    ``run(q)`` is the warmed serving dispatch. Every ``straggler_every``-th
    call is wrapped in a ``DelayedReady`` that polls not-ready for
    ``straggler_s`` — the deterministic slow-chip schedule, identical
    in both arms (the injector's call counter is reset between them).
    The hedge delay is percentile-derived from measured base latencies
    (~2x the observed p94, the docs/robustness.md tuning rule: well
    above the NORMAL tail so jitter cannot fire spurious hedges that
    double the load, well below the straggler so the hedge still cuts
    it); the straggler defaults to the larger of 8x p50 and 5x the
    hedge delay. The hedged arm backs up through the UNwrapped ``run``
    (the real other-replica dispatch)."""
    from raft_tpu.core.interruptible import Interruptible
    from raft_tpu.resilience.deadline import dispatch_hedged
    from raft_tpu.testing import faults

    base = _dispatch_lat_s(run, qb)
    p50 = base[len(base) // 2]
    hedge_delay_s = max(0.002, 2.0 * base[-2])   # ~2x observed p94
    straggler_s = (
        max(0.02, 8.0 * p50, 5.0 * hedge_delay_s)
        if straggler_s is None else straggler_s
    )
    wrapped, audit = faults.inject_straggler(
        run, every=straggler_every, seconds=straggler_s
    )
    # warm the hedge machinery outside the measured window: one forced
    # hedge exercises the timeout raise + wait-any path so first-call
    # costs never land in a measured tail
    warm, _ = faults.inject_straggler(run, every=1, seconds=0.01)
    Interruptible.synchronize(
        dispatch_hedged(warm, qb * (1.0 + 1e-7), hedge=0.001,
                        backup_fn=run)
    )

    def measure(dispatch):
        lat_ms = []
        for i in range(n_requests):
            qi = qb * (1.0 + 1e-6 * (i + 1))
            jax.block_until_ready(qi)
            t0 = time.perf_counter()
            out = dispatch(qi)
            Interruptible.synchronize(out)
            lat_ms.append((time.perf_counter() - t0) * 1e3)
        return lat_ms

    unhedged = measure(wrapped)
    audit.calls = 0            # identical straggle schedule in both arms
    hedged = measure(
        lambda qi: dispatch_hedged(
            wrapped, qi, hedge=hedge_delay_s, backup_fn=run,
        )
    )
    return {
        "engine": "ivf_flat",
        "scenario": "hedged_straggler",
        "nq": int(qb.shape[0]),
        "p50_ms": round(p50 * 1e3, 3),
        "p99_ms": round(_p99(unhedged), 3),
        "hedged_p99_ms": round(_p99(hedged), 3),
        "hedge_delay_ms": round(hedge_delay_s * 1e3, 3),
        "straggler_every": straggler_every,
        "straggler_ms": round(straggler_s * 1e3, 1),
        "n_requests": n_requests,
    }


def overload_row(run, qb, *, over_factor: float = 2.0,
                 n_requests: int = 96, max_queue: int = 4) -> dict:
    """Open-loop arrivals at ``over_factor``x the sustainable rate
    through a bounded-queue ``AdmissionController``: admitted p99 stays
    bounded (~``(max_queue+1)`` service times) and the excess is shed
    with ``RaftOverloadError`` — the no-queue-collapse acceptance."""
    from raft_tpu import errors
    from raft_tpu.resilience import AdmissionController

    p50 = _dispatch_p50_s(run, qb)
    interval = p50 / over_factor
    ctrl = AdmissionController(max_concurrent=1, max_queue=max_queue)
    inputs = [qb * (1.0 + 1e-6 * (i + 1)) for i in range(n_requests)]
    jax.block_until_ready(inputs)
    lock = threading.Lock()
    ok_ms, n_shed, n_timeout = [], [0], [0]

    def handle(qi):
        t0 = time.perf_counter()
        try:
            # generous in-queue wait: the queue bound, not this timeout,
            # is what sheds load
            with ctrl.admit(timeout_s=60.0):
                jax.block_until_ready(run(qi))
            with lock:
                ok_ms.append((time.perf_counter() - t0) * 1e3)
        except errors.RaftOverloadError:
            with lock:
                n_shed[0] += 1
        except errors.RaftTimeoutError:
            with lock:
                n_timeout[0] += 1

    threads = []
    t0 = time.perf_counter()
    for i, qi in enumerate(inputs):
        lag = t0 + i * interval - time.perf_counter()
        if lag > 0:
            time.sleep(lag)
        th = threading.Thread(target=handle, args=(qi,))
        th.start()
        threads.append(th)
    for th in threads:
        th.join()
    st = ctrl.stats()
    row = {
        "engine": "ivf_flat",
        "scenario": "overload_2x",
        "nq": int(qb.shape[0]),
        "p50_ms": round(p50 * 1e3, 3),
        "offered_x": over_factor,
        "shed_rate": round(n_shed[0] / n_requests, 3),
        "max_queue": max_queue,
        "n_requests": n_requests,
        "queue_peak": st.peak_queue_depth,
        "timed_out": n_timeout[0],
    }
    if ok_ms:
        row["p99_ms"] = round(_p99(ok_ms), 3)
    return row


def mixed_ingest_row(idx, qb, *, k: int = 10, n_probes: int = 16,
                     ingest_batch: int = 256, delta_cap: int = 64,
                     chain=(2, 8), escalate: int = 1) -> dict:
    """The sustained mixed read/write row (ISSUE 7 acceptance): search
    QPS while EVERY dispatch also ingests an ``ingest_batch``-row upsert
    into the mutable tier, next to the frozen-index QPS of the same
    engine/config, plus the two mutation latencies a production caller
    cares about — upsert→visible and delete→masked (each measured
    through the real ack + serve path).

    Methodology: the three throughput numbers are chained-dispatch
    quotients (bench/common.py — ``escalations`` stamped like every QPS
    row). The mixed chain drives the ASYNC ingest path (the jitted
    upsert program, state threaded functionally, no per-batch ack sync)
    interleaved with the mutable serving search; ``frozen_qps`` is the
    plain frozen engine on the identical config, so
    ``qps_ratio_vs_frozen`` prices the whole mutation tier (tombstone
    fold + delta scan + concurrent ingest). Delta capacity may saturate
    over a long measured chain — rejected upserts run the identical
    program, so the quotient is unaffected (the visibility metrics use
    their own fresh ids)."""
    import dataclasses

    from bench.common import chained_dispatch_stats
    from raft_tpu.spatial.ann.ivf_flat import ivf_flat_search_grouped
    from raft_tpu.spatial.ann.mutation import (
        _upsert_impl, delete as mut_delete, mutable_search,
        mutable_warmup, upsert as mut_upsert, wrap_mutable,
    )

    nq, d = qb.shape
    mw = wrap_mutable(idx, delta_cap=delta_cap)
    qcap = mutable_warmup(mw, nq, k=k, n_probes=n_probes,
                          ingest_batch=ingest_batch)
    row = {
        "engine": "ivf_flat", "scenario": "mixed_ingest", "nq": int(nq),
        "ingest_batch": int(ingest_batch), "qcap": int(qcap),
    }

    # frozen-index reference: the plain engine at the identical config
    idx.warmup(nq, k=k, n_probes=n_probes, qcap=qcap)

    def run_frozen(qq):
        return ivf_flat_search_grouped(idx, qq, k, n_probes=n_probes,
                                       qcap=qcap)

    jax.block_until_ready(run_frozen(qb))
    st_f = chained_dispatch_stats(
        lambda s: qb * (1.0 + 1e-6 * s), run_frozen,
        n1=chain[0], n2=chain[1], escalate=escalate,
    )

    # ingest-only: the jitted upsert program, state threaded through a
    # cell (functional updates, no ack sync — the async serving path)
    ing_ids = jnp.arange(10_000_000, 10_000_000 + ingest_batch,
                         dtype=jnp.int32)
    cell = {"delta": mw.delta, "rm": mw.row_mask}

    def run_ingest(vb):
        nd, nrm, acc, _, _ = _upsert_impl(
            idx.centroids, cell["delta"], cell["rm"], mw.id_to_pos,
            vb, ing_ids,
        )
        cell["delta"], cell["rm"] = nd, nrm
        return acc.astype(jnp.float32)

    vb0 = jnp.tile(qb, (-(-ingest_batch // nq), 1))[:ingest_batch]
    jax.block_until_ready(run_ingest(vb0))
    st_i = chained_dispatch_stats(
        lambda s: vb0 * (1.0 + 1e-6 * s), run_ingest,
        n1=chain[0], n2=chain[1], escalate=escalate,
    )

    # mixed: every dispatch ingests one batch AND serves one search
    cell["delta"], cell["rm"] = mw.delta, mw.row_mask

    def run_mixed(qq):
        vb = jnp.tile(qq, (-(-ingest_batch // nq), 1))[:ingest_batch]
        nd, nrm, _, _, _ = _upsert_impl(
            idx.centroids, cell["delta"], cell["rm"], mw.id_to_pos,
            vb, ing_ids,
        )
        cell["delta"], cell["rm"] = nd, nrm
        cur = dataclasses.replace(mw, delta=nd, row_mask=nrm)
        return mutable_search(cur, qq, k, n_probes=n_probes, qcap=qcap)

    jax.block_until_ready(run_mixed(qb))
    st_m = chained_dispatch_stats(
        lambda s: qb * (1.0 + 1e-6 * s), run_mixed,
        n1=chain[0], n2=chain[1], escalate=escalate,
    )

    if st_f is not None:
        row["frozen_qps"] = round(nq / (st_f["ms"] / 1e3), 1)
    if st_i is not None:
        row["ingest_qps"] = round(ingest_batch / (st_i["ms"] / 1e3), 1)
    if st_m is not None:
        row["mixed_search_qps"] = round(nq / (st_m["ms"] / 1e3), 1)
        row["spread"] = st_m["spread"]
        row["repeats"] = st_m["repeats"]
        row["escalations"] = st_m.get("escalations", 0)
        if st_f is not None:
            row["qps_ratio_vs_frozen"] = round(
                row["mixed_search_qps"] / row["frozen_qps"], 3
            )
    if st_f is None and st_m is None:
        row["error"] = "jitter-dominated"
        return row

    # upsert→visible: ack one fresh-id batch whose row 0 equals the
    # probe query, then serve it back — measured on WARMED programs (the
    # qcap resolved above; the 1-row probe shape pre-compiled below), so
    # the number is the serving-path ack+serve latency, not a compile
    mw2 = wrap_mutable(idx, delta_cap=delta_cap)
    qc1 = mutable_warmup(mw2, 1, k=k, n_probes=n_probes)
    mut_delete(mw2, np.array([-1], np.int32))   # warm the B=1 delete
    probe = qb[:1] * 1.001
    vis_batch = jnp.concatenate([probe, vb0[1:]])
    vis_ids = np.arange(20_000_000, 20_000_000 + ingest_batch,
                        dtype=np.int32)
    t0 = time.perf_counter()
    mw3, acc = mut_upsert(mw2, vis_batch, vis_ids)
    iv = mutable_search(mw3, probe, k, n_probes=n_probes, qcap=qc1)[1]
    jax.block_until_ready(iv)
    vis_ms = (time.perf_counter() - t0) * 1e3
    if bool(acc[0]) and int(np.asarray(iv)[0, 0]) == int(vis_ids[0]):
        row["upsert_visible_ms"] = round(vis_ms, 3)
    # delete→masked: tombstone it and serve — the row must be gone
    t0 = time.perf_counter()
    mw4, found = mut_delete(mw3, vis_ids[:1])
    iv2 = mutable_search(mw4, probe, k, n_probes=n_probes, qcap=qc1)[1]
    jax.block_until_ready(iv2)
    del_ms = (time.perf_counter() - t0) * 1e3
    if bool(found[0]) and int(vis_ids[0]) not in np.asarray(iv2)[0].tolist():
        row["delete_masked_ms"] = round(del_ms, 3)
    return row


def durable_ingest_row(idx, qb, *, ingest_batch: int = 128,
                       n_batches: int = 24, delta_cap: int = 64,
                       fsync_intervals_ms=(0.0, 2.0)) -> dict:
    """The durable-WAL ingest row (ISSUE 20, docs/robustness.md
    "Durability"): acked-ingest QPS through
    :class:`raft_tpu.durability.wal.DurableIngest` (journal + apply +
    fsync-durable ack) next to the non-durable arm (the same jitted
    apply with a host sync per batch, no journal) — so
    ``durability_ratio`` prices exactly the WAL tax: encode + group
    commit + fsync wait. Acceptance >= ~0.8.

    ``fsync_intervals_ms`` sweeps the group-commit flush interval (0 =
    byte/immediate-triggered); the stamped primary
    ``durable_qps``/``fsync_interval_ms``/``fsync_p50_ms``/
    ``wal_mb_per_s`` come from the best interval, the full sweep rides
    in ``fsync_sweep`` (bench_full.json only). The WAL lives in a temp
    dir torn down with the row; every batch uses fresh ids, and a
    saturated delta rejects through the identical program in BOTH arms,
    so the quotient stays fair."""
    import tempfile

    from raft_tpu.durability import wal as wal_mod
    from raft_tpu.spatial.ann.mutation import (
        upsert as mut_upsert, wrap_mutable,
    )

    nq, d = qb.shape
    vb0 = np.asarray(
        jnp.tile(qb, (-(-ingest_batch // nq), 1))[:ingest_batch],
        np.float32,
    )
    row = {
        "engine": "ivf_flat", "scenario": "durable_ingest",
        "ingest_batch": int(ingest_batch), "n_batches": int(n_batches),
    }

    def batches(base):
        for b in range(n_batches):
            ids = np.arange(base + b * ingest_batch,
                            base + (b + 1) * ingest_batch, dtype=np.int32)
            yield vb0 * (1.0 + 1e-6 * (b + 1)), ids

    # non-durable arm: the same apply program, host-synced per batch
    # (the ack semantics minus durability — acc realized = batch landed)
    mw = wrap_mutable(idx, delta_cap=delta_cap)
    _, warm_acc = mut_upsert(mw, vb0, np.arange(ingest_batch,
                                                dtype=np.int32))
    np.asarray(warm_acc)                         # compile + warm
    mw = wrap_mutable(idx, delta_cap=delta_cap)
    t0 = time.perf_counter()
    for vb, ids in batches(30_000_000):
        mw, acc = mut_upsert(mw, vb, ids)
        np.asarray(acc)
    nd_s = time.perf_counter() - t0
    row["nondurable_qps"] = round(n_batches * ingest_batch / nd_s, 1)

    # durable arm, one run per swept fsync interval: WAL-first apply
    # with the ack resolved only after the group commit's fsync
    sweep = []
    for iv_ms in fsync_intervals_ms:
        fsync_ms = []

        def timed_fsync(fd, _lat=fsync_ms):
            t = time.perf_counter()
            os.fsync(fd)
            _lat.append((time.perf_counter() - t) * 1e3)

        with tempfile.TemporaryDirectory() as td:
            w = wal_mod.WalWriter(
                td, flush_interval_s=iv_ms / 1e3, name="bench-wal",
                fsync=timed_fsync,
            )
            ing = wal_mod.DurableIngest(
                wrap_mutable(idx, delta_cap=delta_cap), w)
            ing.upsert(vb0, np.arange(ingest_batch, dtype=np.int32))
            fsync_ms.clear()
            t0 = time.perf_counter()
            for vb, ids in batches(40_000_000):
                ing.upsert(vb, ids)
            du_s = time.perf_counter() - t0
            wal_bytes = sum(
                os.path.getsize(s)
                for s in wal_mod.segment_paths(td))
            ing.close()
        sweep.append({
            "fsync_interval_ms": float(iv_ms),
            "durable_qps": round(n_batches * ingest_batch / du_s, 1),
            "fsync_p50_ms": round(
                float(np.median(fsync_ms)), 4) if fsync_ms else 0.0,
            "n_fsyncs": len(fsync_ms),
            "wal_mb_per_s": round(wal_bytes / du_s / 1e6, 2),
        })

    best = max(sweep, key=lambda s: s["durable_qps"])
    row.update({k: best[k] for k in (
        "durable_qps", "fsync_interval_ms", "fsync_p50_ms",
        "wal_mb_per_s",
    )})
    row["durability_ratio"] = round(
        row["durable_qps"] / row["nondurable_qps"], 3)
    row["fsync_sweep"] = sweep
    return row


def _drive_open_loop(executor, schedule, qall, *, seed: int = 0,
                     rows_fn=None):
    """Replay one open-loop schedule through the executor; returns
    ``(latencies_ms, n_shed, achieved_qps, max_lag_s)``. Latency is
    submit→future-resolution wall time per COMPLETED request; achieved
    QPS counts completed query rows over the span from first submit to
    last completion (the open-loop throughput, sheds excluded).

    ``rows_fn(i, size)`` overrides the default random-unique row draw
    with the request's EXACT rows — the ``zipf_hot_traffic`` row maps
    each request's template id to a fixed block so hot templates
    re-arrive bitwise identical (no uniqueness perturbation: the
    result cache keys on the bytes)."""
    from raft_tpu import errors
    from raft_tpu.testing import load

    done = {}
    lock = threading.Lock()
    rng = np.random.default_rng(seed)
    q_pool = np.asarray(qall, np.float32)

    def submit(i, size):
        if rows_fn is not None:
            rows = rows_fn(i, size)
        else:
            rows = q_pool[rng.integers(0, q_pool.shape[0], size=size)]
            rows = rows * (1.0 + 1e-6 * (i + 1))
        fut = executor.submit(rows)

        def _stamp(_f, i=i):
            with lock:
                done[i] = time.perf_counter()

        fut.add_done_callback(_stamp)
        return fut

    results, stamps, max_lag = load.replay(
        schedule, submit, clock=time.perf_counter
    )
    lat_ms, n_shed, rows_done = [], 0, 0
    t_last = 0.0
    for i, r in enumerate(results):
        if isinstance(r, errors.RaftOverloadError):
            n_shed += 1
            continue
        if isinstance(r, BaseException):
            raise r
        r.result(timeout=120)            # surface dispatch failures
        # result() can return before add_done_callback has stamped
        # (set_result wakes waiters first, runs callbacks after) —
        # spin the tiny gap out instead of KeyError-ing the row
        while True:
            with lock:
                t_done = done.get(i)
            if t_done is not None:
                break
            time.sleep(0.0002)
        lat_ms.append((t_done - stamps[i]) * 1e3)
        rows_done += int(schedule.sizes[i])
        t_last = max(t_last, t_done)
    span = max(t_last - float(stamps[0]), 1e-9) if lat_ms else None
    qps = rows_done / span if span else 0.0
    return lat_ms, n_shed, qps, max_lag


def open_loop_row(make_run, qall, *, buckets=(128, 1024),
                  request_size: int = 16, n_requests: int = 256,
                  fracs=(0.5, 0.8, 0.95), flush_age_s: float = 0.002,
                  max_in_flight: int = 4, chain=(4, 32),
                  escalate: int = 2, seed: int = 11,
                  min_duration_s: float = 0.5,
                  max_requests: int = 20_000) -> dict:
    """The open-loop executor row (module docstring): saturation vs the
    raw program, then the offered-load sweep at ``fracs`` of measured
    saturation with p50/p99 per point.

    ``make_run(bucket)`` returns the WARMED serving closure for one
    bucket size (the bench warms ``index.warmup(bucket)`` per bucket);
    the executor routes each micro-batch to its bucket's closure.

    ``n_requests`` is a FLOOR: each measured point is stretched to at
    least ``min_duration_s`` of offered traffic at its own rate
    (capped at ``max_requests``) — at TPU rates a fixed request count
    would finish in milliseconds and measure noise, not serving."""
    from bench.common import chained_dispatch_stats
    from raft_tpu.resilience import AdmissionController
    from raft_tpu.serving import BucketSet, ServingExecutor
    from raft_tpu.testing.load import poisson_arrivals

    bset = BucketSet.of(buckets)
    runs = {b: make_run(b) for b in bset.sizes}
    d = int(np.asarray(qall).shape[1])

    def dispatch(batch, **_rt):
        return runs[int(batch.shape[0])](batch)

    # warm every bucket program before the clock starts
    for b in bset.sizes:
        jax.block_until_ready(runs[b](jnp.zeros((b, d), jnp.float32)))

    # the denominator: raw program QPS at the largest bucket,
    # closed-loop chained quotient (no executor in the path)
    big = bset.largest
    qb = jnp.asarray(np.asarray(qall, np.float32)[:big])
    st = chained_dispatch_stats(
        lambda s: qb * (1.0 + 1e-6 * s), runs[big],
        n1=chain[0], n2=chain[1], escalate=escalate,
    )
    row = {
        "engine": "ivf_flat", "scenario": "open_loop",
        "nq": big, "buckets": list(bset.sizes),
        "request_size": int(request_size),
        "n_requests": int(n_requests),
        "max_in_flight": int(max_in_flight),
    }
    if st is None:
        row["error"] = "jitter-dominated"
        return row
    program_qps = big / (st["ms"] / 1e3)
    row["program_qps"] = round(program_qps, 1)
    row["spread"] = st["spread"]
    row["repeats"] = st["repeats"]

    def fresh_executor():
        return ServingExecutor(
            dispatch, bset, dim=d, flush_age_s=flush_age_s,
            max_in_flight=max_in_flight,
            admission=AdmissionController(
                max_concurrent=max(1, 4 * big // request_size),
                max_queue=max(8, 4 * big // request_size),
            ),
        )

    def n_for(rate_rps):
        return int(min(max_requests,
                       max(n_requests, min_duration_s * rate_rps)))

    # saturation: offer ~1.5x the program rate; the completion rate IS
    # the executor's deliverable throughput (sheds excluded). Measured
    # TWICE — registry enabled (the production posture; this is the
    # reported saturation_qps) and RAFT_TPU_OBS=off — so the row stamps
    # the telemetry tax directly (`obs_overhead_pct`, ISSUE 13
    # acceptance: <= ~2%; the executor records its per-stage
    # histograms into the default registry either way, the gate just
    # turns every observe into an attribute load)
    from raft_tpu.obs import metrics as obsm

    rate_rps = 1.5 * program_qps / request_size
    prev_obs = obsm.set_enabled(True)
    try:
        with fresh_executor() as ex:
            _, _, sat_qps, sat_lag = _drive_open_loop(
                ex, poisson_arrivals(rate_rps, n_for(rate_rps),
                                     seed=seed, sizes=request_size),
                qall, seed=seed,
            )
        obsm.set_enabled(False)
        with fresh_executor() as ex:
            _, _, sat_qps_off, _ = _drive_open_loop(
                ex, poisson_arrivals(rate_rps, n_for(rate_rps),
                                     seed=seed, sizes=request_size),
                qall, seed=seed,
            )
    finally:
        obsm.set_enabled(prev_obs)
    row["saturation_qps"] = round(sat_qps, 1)
    row["qps_ratio_vs_program"] = round(sat_qps / program_qps, 3)
    if sat_qps_off > 0:
        row["obs_overhead_pct"] = round(
            100.0 * (1.0 - sat_qps / sat_qps_off), 2)
    # generator self-check (bench_full only): a lag comparable to the
    # mean inter-arrival gap means the measured rate was submit-bound
    row["gen_lag_ms_sat"] = round(sat_lag * 1e3, 3)

    # the offered-load sweep: p50/p99 at each fraction of saturation
    for frac in fracs:
        tag = f"{int(round(frac * 100))}"
        offered = frac * sat_qps / request_size
        if offered <= 0:
            continue
        n_point = n_for(offered)
        with fresh_executor() as ex:
            lat_ms, n_shed, qps, lag = _drive_open_loop(
                ex, poisson_arrivals(offered, n_point,
                                     seed=seed + int(frac * 100),
                                     sizes=request_size),
                qall, seed=seed + 1,
            )
        row[f"gen_lag_ms_{tag}"] = round(lag * 1e3, 3)
        if lat_ms:
            lat = np.asarray(lat_ms)
            row[f"p50_ms_{tag}"] = round(float(np.percentile(lat, 50)), 3)
            row[f"p99_ms_{tag}"] = round(float(np.percentile(lat, 99)), 3)
            row[f"achieved_qps_{tag}"] = round(qps, 1)
        if n_shed:
            row[f"shed_rate_{tag}"] = round(n_shed / n_point, 3)
    return row


def zipf_hot_traffic_row(make_run, qall, *, k: int,
                         buckets=(128, 1024), request_size: int = 16,
                         n_templates: int = 64, zipf_s: float = 1.1,
                         n_requests: int = 256,
                         flush_age_s: float = 0.002,
                         max_in_flight: int = 4, chain=(4, 32),
                         escalate: int = 2, seed: int = 23,
                         min_duration_s: float = 0.5,
                         max_requests: int = 20_000,
                         offered_x_cached: float = 4.0) -> dict:
    """The hot-traffic shaping row (ISSUE 15, docs/serving.md "Hot
    traffic"): saturation QPS and p99 under a Zipf(``zipf_s``)
    repeated-query mix, measured TWICE at fixed hardware — the plain
    executor (``uncached_qps``/``p99_ms_uncached``) vs the same
    executor with the result cache + request coalescing enabled
    (``cached_qps``/``p99_ms_cached``), plus ``qps_uplift`` (the >= 1.5x
    acceptance), ``cache_hit_rate`` and ``coalesce_rate`` from the
    executor's own counters, and ``cached_identical`` (a cached answer
    re-served for a hot template is bitwise the uncached program's —
    the exact tier serves at EQUAL recall by construction; the
    semantic tier stays off here, its guardrail is a per-deployment
    calibration).

    Traffic: ``n_templates`` fixed query blocks of ``request_size``
    rows; each request draws its template from
    :func:`raft_tpu.testing.load.zipf_template_weights` — hot
    templates re-arrive bitwise identical, exactly the traffic shape
    the cache keys on. The cached arm is offered
    ``offered_x_cached``x the raw program rate (the cache can clear
    MORE than program QPS, so saturating it needs more offered load
    than the uncached arm's 1.5x)."""
    from bench.common import chained_dispatch_stats
    from raft_tpu.resilience import AdmissionController
    from raft_tpu.serving import BucketSet, ResultCache, ServingExecutor
    from raft_tpu.testing.load import poisson_arrivals

    bset = BucketSet.of(buckets)
    runs = {b: make_run(b) for b in bset.sizes}
    d = int(np.asarray(qall).shape[1])

    def dispatch(batch, **_rt):
        return runs[int(batch.shape[0])](batch)

    for b in bset.sizes:
        jax.block_until_ready(runs[b](jnp.zeros((b, d), jnp.float32)))

    # the fixed template pool: template t IS a (request_size, d) block,
    # re-submitted verbatim on every arrival of t
    rng = np.random.default_rng(seed)
    q_pool = np.asarray(qall, np.float32)
    pool = np.stack([
        q_pool[rng.integers(0, q_pool.shape[0], size=request_size)]
        * (1.0 + 1e-6 * (t + 1))
        for t in range(n_templates)
    ])

    big = bset.largest
    qb = jnp.asarray(q_pool[:big])
    st = chained_dispatch_stats(
        lambda s: qb * (1.0 + 1e-6 * s), runs[big],
        n1=chain[0], n2=chain[1], escalate=escalate,
    )
    row = {
        "engine": "ivf_flat", "scenario": "zipf_hot_traffic",
        "nq": big, "request_size": int(request_size),
        "zipf_s": float(zipf_s), "n_templates": int(n_templates),
    }
    if st is None:
        row["error"] = "jitter-dominated"
        return row
    program_qps = big / (st["ms"] / 1e3)
    row["program_qps"] = round(program_qps, 1)
    row["spread"] = st["spread"]
    row["repeats"] = st["repeats"]

    def fresh_executor(cache: bool):
        rcache = None
        if cache:
            rcache = ResultCache(
                k, n_sets=max(64, 2 * n_templates), associativity=8,
                name="zipf_bench",
            )
        return ServingExecutor(
            dispatch, bset, dim=d, flush_age_s=flush_age_s,
            max_in_flight=max_in_flight,
            admission=AdmissionController(
                max_concurrent=max(1, 4 * big // request_size),
                max_queue=max(8, 4 * big // request_size),
            ),
            result_cache=rcache,
        )

    def n_for(rate_rps):
        return int(min(max_requests,
                       max(n_requests, min_duration_s * rate_rps)))

    def drive(ex, rate_rps, seed_pt):
        sched = poisson_arrivals(
            rate_rps, n_for(rate_rps), seed=seed_pt,
            sizes=request_size, zipf_s=zipf_s, n_templates=n_templates,
        )
        return _drive_open_loop(
            ex, sched, q_pool, seed=seed_pt,
            rows_fn=lambda i, _size, s=sched: pool[
                int(s.template_ids[i])],
        )

    results = {}
    for arm, offered_x in (("uncached", 1.5),
                           ("cached", offered_x_cached)):
        rate = offered_x * program_qps / request_size
        with fresh_executor(arm == "cached") as ex:
            _, _, sat_qps, _ = drive(ex, rate, seed)
            sat_stats = ex.stats()
        # p99 at 80% of the arm's OWN measured saturation
        p99_rate = 0.8 * sat_qps / request_size
        if p99_rate > 0:
            with fresh_executor(arm == "cached") as ex:
                lat_ms, _, _, _ = drive(ex, p99_rate, seed + 7)
            if lat_ms:
                row[f"p99_ms_{arm}"] = round(
                    float(np.percentile(np.asarray(lat_ms), 99)), 3)
        results[arm] = (sat_qps, sat_stats)

    row["uncached_qps"] = round(results["uncached"][0], 1)
    row["cached_qps"] = round(results["cached"][0], 1)
    if results["uncached"][0] > 0:
        row["qps_uplift"] = round(
            results["cached"][0] / results["uncached"][0], 3)
    st_c = results["cached"][1]
    if st_c.submitted:
        row["cache_hit_rate"] = round(
            st_c.cache_hits / st_c.submitted, 3)
        row["coalesce_rate"] = round(
            st_c.coalesced_requests / st_c.submitted, 3)

    # equal-recall spot check: the cached answer for a hot template is
    # bitwise the warmed program's own answer for that template block
    b0 = bset.select(request_size)
    padded = np.zeros((b0, d), np.float32)
    padded[:request_size] = pool[0]
    ref_ids = np.asarray(runs[b0](jnp.asarray(padded))[1])[:request_size]
    rc_spot = ResultCache(k, n_sets=max(64, 2 * n_templates),
                          associativity=8, name="zipf_spot")
    with ServingExecutor(dispatch, bset, dim=d,
                         flush_age_s=flush_age_s,
                         result_cache=rc_spot) as ex:
        ex.submit(pool[0]).result(timeout=60)
        # the cache fill is asynchronous (the demux thread writes it
        # AFTER resolving the caller) — wait for the insert so the
        # re-submit exercises the hit path, not a fill race
        t0 = time.monotonic()
        while rc_spot.stats().inserts < request_size \
                and time.monotonic() - t0 < 10.0:
            time.sleep(0.002)
        cached = ex.submit(pool[0]).result(timeout=60)
        hit = ex.stats().cache_hits >= 1
    row["cached_identical"] = bool(
        hit and np.array_equal(np.asarray(cached[1]), ref_ids))
    return row


def cold_tier_row(index, qall, *, k: int, n_probes: int,
                  capacity_x: float = 4.0, buckets=(128, 1024),
                  request_size: int = 16, n_templates: int = 64,
                  zipf_s: float = 1.1, n_requests: int = 256,
                  flush_age_s: float = 0.002, max_in_flight: int = 4,
                  chain=(4, 32), escalate: int = 2, seed: int = 29,
                  min_duration_s: float = 0.5,
                  max_requests: int = 20_000,
                  fracs=(0.5, 0.8, 0.95)) -> dict:
    """The popularity-tiered cold-tier row (ISSUE 17, docs/tiering.md
    "Reading the bench row"): the SAME index served two ways at fixed
    hardware — fully resident (``hot_qps``, the baseline every tier
    claim is priced against) vs through a
    :class:`~raft_tpu.tier.TieredListStore` whose hot "HBM" budget is
    ``1/capacity_x`` of the cold slab's bytes (``tiered_qps``), under
    the Zipf(``zipf_s``) template mix the tier exists for. Stamps:

    * ``capacity_x`` — measured cold/hot byte ratio (the >= 4x
      acceptance: the tier SERVES an index 4x its hot budget);
    * ``qps_ratio_vs_hot`` + the ``p99_ms_{50,80,95}`` sweep at
      fractions of the TIERED arm's own saturation (bounded p99);
    * ``tier_hit_rate`` (+ per-sweep-point ``tier_hit_rate_{tag}``) —
      the hit-rate-vs-QPS curve, post-convergence;
    * ``recall_vs_hot`` — measured id-overlap recall of the tiered
      answer vs the full-resident program ON the template traffic
      (the >= 0.95 acceptance);
    * ``fetch_overlap_pct`` — fetch spans stamped compute-overlapped
      (the executor was mid-flight), the async double-buffer evidence.

    The hot working set is converged ONCE (a gentle warm pass + fetcher
    drain) before any measured arm: the row prices the steady state,
    not the cold start — cold-start behavior is the degraded-probe
    guardrail's territory (tests/test_tier.py)."""
    from bench.common import chained_dispatch_stats
    from raft_tpu.resilience import AdmissionController
    from raft_tpu.serving import BucketSet, ServingExecutor
    from raft_tpu.spatial.ann.ivf_flat import ivf_flat_search_grouped
    from raft_tpu.testing.load import poisson_arrivals
    from raft_tpu.tier import (
        PromotionPolicy, SlabFetcher, TieredListStore,
    )

    bset = BucketSet.of(buckets)
    q_pool = np.asarray(qall, np.float32)
    d = int(q_pool.shape[1])
    qcaps = {b: index.warmup(b, k=k, n_probes=n_probes)
             for b in bset.sizes}

    def make_hot(b):
        def run(qq, qcap=qcaps[b]):
            return ivf_flat_search_grouped(
                index, qq, k, n_probes=n_probes, qcap=qcap,
            )
        return run

    runs = {b: make_hot(b) for b in bset.sizes}

    def hot_dispatch(batch, **_rt):
        return runs[int(batch.shape[0])](batch)

    for b in bset.sizes:
        jax.block_until_ready(runs[b](jnp.zeros((b, d), jnp.float32)))

    # the tier under test: hot budget = cold bytes / capacity_x
    storage = index.storage
    itemsize = np.asarray(index.data_sorted).dtype.itemsize
    cold_bytes = int(storage.n) * d * itemsize
    store = TieredListStore(
        index, hbm_budget_bytes=max(1, int(cold_bytes // capacity_x)),
        name="cold_tier", min_recall=0.95, touch_decay=0.95,
    )
    L = int(storage.max_list)
    big = bset.largest
    row = {
        "engine": "ivf_flat", "scenario": "cold_tier", "nq": big,
        "request_size": int(request_size), "zipf_s": float(zipf_s),
        "n_templates": int(n_templates), "n_slots": store.n_slots,
        "capacity_x": round(
            cold_bytes / (store.n_slots * L * d * itemsize), 2),
    }

    qb = jnp.asarray(q_pool[:big])
    st = chained_dispatch_stats(
        lambda s: qb * (1.0 + 1e-6 * s), runs[big],
        n1=chain[0], n2=chain[1], escalate=escalate,
    )
    if st is not None:
        program_qps = big / (st["ms"] / 1e3)
        row["spread"] = st["spread"]
        row["repeats"] = st["repeats"]
    else:
        # jitter-dominated host: a crude timed denominator beats
        # shipping no tier evidence at all (stamped by the missing
        # spread/repeats)
        t0 = time.perf_counter()
        for s in range(3):
            jax.block_until_ready(runs[big](qb * (1.0 + 1e-6 * s)))
        program_qps = 3 * big / max(time.perf_counter() - t0, 1e-9)
    row["program_qps"] = round(program_qps, 1)

    # the fixed Zipf template pool (the zipf_hot_traffic discipline:
    # hot templates re-arrive bitwise identical)
    rng = np.random.default_rng(seed)
    pool = np.stack([
        q_pool[rng.integers(0, q_pool.shape[0], size=request_size)]
        * (1.0 + 1e-6 * (t + 1))
        for t in range(n_templates)
    ])

    ex_box = {}

    def busy() -> bool:
        ex = ex_box.get("ex")
        return bool(ex is not None and ex.stats().in_flight > 0)

    def tier_dispatch(batch, tier=None, **_rt):
        return store.search(
            batch, k, n_probes=n_probes,
            qcap=qcaps[int(batch.shape[0])], runtime=tier,
        )

    def fresh_executor(tiered: bool):
        ex = ServingExecutor(
            tier_dispatch if tiered else hot_dispatch, bset, dim=d,
            flush_age_s=flush_age_s, max_in_flight=max_in_flight,
            admission=AdmissionController(
                max_concurrent=max(1, 4 * big // request_size),
                max_queue=max(8, 4 * big // request_size),
            ),
            runtime_provider=store.runtime if tiered else None,
        )
        ex_box["ex"] = ex
        return ex

    def n_for(rate_rps):
        return int(min(max_requests,
                       max(n_requests, min_duration_s * rate_rps)))

    def drive(ex, rate_rps, seed_pt):
        sched = poisson_arrivals(
            rate_rps, n_for(rate_rps), seed=seed_pt,
            sizes=request_size, zipf_s=zipf_s,
            n_templates=n_templates,
        )
        return _drive_open_loop(
            ex, sched, q_pool, seed=seed_pt,
            rows_fn=lambda i, _size, s=sched: pool[
                int(s.template_ids[i])],
        )

    policy = PromotionPolicy(demote_margin=1.25, min_touches=2.0,
                             max_moves=8)
    fetcher = SlabFetcher(store, window=4, policy=policy,
                          busy_fn=busy,
                          max_pending=4 * store.n_slots)
    try:
        # converge the hot set off the clock (misses -> async fills)
        with fresh_executor(True) as ex:
            drive(ex, max(1.0, 0.25 * program_qps / request_size),
                  seed + 3)
        fetcher.drain(60.0)
        s0 = store.stats()

        rate = 1.5 * program_qps / request_size
        with fresh_executor(False) as ex:
            _, _, hot_qps, _ = drive(ex, rate, seed)
        with fresh_executor(True) as ex:
            _, _, tiered_qps, _ = drive(ex, rate, seed)
        row["hot_qps"] = round(hot_qps, 1)
        row["tiered_qps"] = round(tiered_qps, 1)
        if hot_qps > 0:
            row["qps_ratio_vs_hot"] = round(tiered_qps / hot_qps, 3)

        # the hit-rate-vs-QPS sweep at fractions of the TIERED arm's
        # own measured saturation
        for frac in fracs:
            tag = f"{int(round(frac * 100))}"
            offered = frac * tiered_qps / request_size
            if offered <= 0:
                continue
            pre = store.stats()
            with fresh_executor(True) as ex:
                lat_ms, _, _, _ = drive(ex, offered,
                                        seed + int(frac * 100))
            post = store.stats()
            hits = post.probe_hits - pre.probe_hits
            misses = post.probe_misses - pre.probe_misses
            if hits + misses:
                row[f"tier_hit_rate_{tag}"] = round(
                    hits / (hits + misses), 3)
            if lat_ms:
                row[f"p99_ms_{tag}"] = round(
                    float(np.percentile(np.asarray(lat_ms), 99)), 3)

        send = store.stats()
        dh = send.probe_hits - s0.probe_hits
        dm = send.probe_misses - s0.probe_misses
        if dh + dm:
            row["tier_hit_rate"] = round(dh / (dh + dm), 3)
        row["fetch_overlap_pct"] = round(send.fetch_overlap_pct, 1)
        row["tier_fetches"] = send.fetches
    finally:
        fetcher.close()

    # measured recall of the tiered answer vs the full-resident
    # program ON the template traffic, post-convergence (the >= 0.95
    # acceptance; measure_recall also feeds the tier_recall gauge)
    recalls = [
        store.measure_recall(pool[t], k, n_probes=n_probes)
        for t in range(min(8, n_templates))
    ]
    row["recall_vs_hot"] = round(float(np.mean(recalls)), 4)
    row["tier_degraded"] = bool(store.degraded)
    return row


def self_heal_row(x, qall, *, k: int = 10, n_probes: int = 16,
                  replication: int = 2, n_lists: int = 32,
                  request_size: int = 8, n_templates: int = 32,
                  zipf_s: float = 1.1, kill_at_s: float = 0.6,
                  heal_at_s: float = 2.0, duration_s: float = 4.0,
                  max_rows: int = 65_536, consecutive: int = 2,
                  cooldown_s: float = 0.1, seed: int = 43) -> dict:
    """The self-healing supervisor row (ISSUE 18, docs/robustness.md
    "Self-healing"): one scripted kill→reroute→heal→reintegrate cycle
    against a live open-loop Zipf stream, with the SUPERVISOR doing all
    recovery — the schedule only flips the scripted health truth (and
    wrecks the dead rank's slabs, so the reroute is load-bearing, not
    cosmetic). Builds its own R-way replicated MNMG index over every
    visible device (needs >= 2; error-stamped row otherwise). Stamps:

    * ``detection_ms`` — kill instant → the monitor's confirmed down
      (the debounce cost: ``consecutive`` probes + tick cadence);
    * ``route_convergence_ms`` — kill instant → the supervisor's route
      push landing in the executor (acceptance: bounded, no manual
      call in the path);
    * ``reintegration_ms`` — heal signal → heal_done (checkpoint
      re-splice via ``recover_rank``; the recover program is warmed
      off the clock, so this prices the steady-state heal, not a
      first-compile);
    * ``p99_ms_healthy`` / ``p99_ms_degraded`` / ``p99_ms_healed`` —
      per-request p99 split by submit stamp into the three phases, and
      ``healed_p99_x`` (healed/healthy — the did-it-actually-recover
      ratio).

    Requests keep flowing through the whole cycle; admission is
    unbounded here because the row prices the failover path, not
    shedding (that is ``overload_2x``)."""
    import os
    import shutil
    import tempfile

    from raft_tpu.comms import (
        build_comms, mnmg_ivf_flat_build, mnmg_ivf_flat_search,
        place_index, recover_rank,
    )
    from raft_tpu.resilience import (
        FailoverPlan, HealActions, HealthMonitor, ReplicaPlacement,
        ServingSupervisor, ShardHealth,
    )
    from raft_tpu.serving import ServingExecutor
    from raft_tpu.spatial.ann import IVFFlatParams, save_index
    from raft_tpu.testing import chaos, load

    row = {
        "engine": "ivf_flat", "scenario": "self_heal",
        "nq": int(request_size), "request_size": int(request_size),
        "zipf_s": float(zipf_s), "n_templates": int(n_templates),
        "replication": int(replication),
    }
    devices = jax.devices()
    if len(devices) < 2:
        row["error"] = "self_heal needs >= 2 devices"
        return row
    n_ranks = len(devices)
    row["n_ranks"] = n_ranks
    comms = build_comms(devices)
    xs = np.asarray(x, np.float32)[:max_rows]
    idx0 = mnmg_ivf_flat_build(
        comms, xs,
        IVFFlatParams(n_lists=n_lists, kmeans_n_iters=4,
                      kmeans_init="random", seed=seed),
        metric="sqeuclidean",
    )
    rep = place_index(comms, idx0, replication=replication)
    tmp = tempfile.mkdtemp(prefix="raft_tpu_self_heal_")
    ckpt = os.path.join(tmp, "base.npz")
    try:
        save_index(rep, ckpt)
        cell = {"idx": rep}
        cell_lock = threading.Lock()
        qcap = int(request_size)
        d = int(np.asarray(qall).shape[1])

        def dispatch(batch, shard_mask=None, failover=None, **_rt):
            with cell_lock:
                idx = cell["idx"]
            return mnmg_ivf_flat_search(
                comms, idx, batch, k, n_probes=n_probes, qcap=qcap,
                shard_mask=(shard_mask if shard_mask is not None
                            else np.ones(n_ranks, np.int32)),
                failover=failover,
            )

        health = ShardHealth(n_ranks)
        placement = ReplicaPlacement.of_index(rep)
        monitor = HealthMonitor(n_ranks, consecutive=consecutive,
                                cooldown_s=cooldown_s,
                                clock=time.perf_counter)
        scripted = chaos.ScriptedHealth(n_ranks)
        dead = n_ranks // 2

        def recover(rank):
            with cell_lock:
                cell["idx"] = recover_rank(comms, cell["idx"], ckpt,
                                           rank)

        sup = ServingSupervisor(
            health, placement, scripted.probe,
            heal=HealActions(recover=recover), monitor=monitor,
            interval_s=0.01, step_deadline_s=120.0,
            clock=time.perf_counter, name="bench-self-heal",
        )

        # warm the serving AND recover programs off the clock, so the
        # stamps price the steady state, not first compiles
        plan0 = FailoverPlan.load_balanced(placement, health)
        q_pool = np.asarray(qall, np.float32)
        rng = np.random.default_rng(seed)
        pool = np.stack([
            q_pool[rng.integers(0, q_pool.shape[0], size=request_size)]
            * (1.0 + 1e-6 * (t + 1))
            for t in range(n_templates)
        ])
        jax.block_until_ready(dispatch(
            jnp.asarray(pool[0]), shard_mask=health.mask(),
            failover=plan0,
        ))
        recover_rank(comms, rep, ckpt, dead)      # discarded warm splice

        service_s = _dispatch_p50_s(
            lambda qq: dispatch(qq), jnp.asarray(pool[0]), reps=8,
        )
        rate_rps = max(4.0, 0.5 / max(service_s, 1e-4))
        n_requests = int(duration_s * rate_rps) + 1
        row["rate_rps"] = round(rate_rps, 1)
        row["n_requests"] = n_requests

        ex = ServingExecutor(
            dispatch, (qcap,), dim=d, flush_age_s=0.0,
            max_in_flight=2,
            runtime_inputs={"shard_mask": health.mask(),
                            "failover": plan0},
        )
        sup.register(ex)

        marks = {}

        def kill_fire():
            marks["kill"] = time.perf_counter()
            with cell_lock:
                idx = cell["idx"]
                cell["idx"] = dataclasses.replace(
                    idx,
                    vectors_sorted=jnp.asarray(idx.vectors_sorted)
                    .at[dead].set(0),
                    sorted_ids=jnp.asarray(idx.sorted_ids)
                    .at[dead].set(0),
                )
            scripted.set(dead, False)

        def heal_fire():
            marks["heal"] = time.perf_counter()
            scripted.set(dead, True)

        csched = chaos.ChaosSchedule(scripted=scripted, seed=seed)
        csched.at(kill_at_s, f"kill_rank_{dead}", kill_fire)
        csched.at(heal_at_s, f"heal_rank_{dead}", heal_fire)

        sched_load = load.poisson_arrivals(
            rate_rps, n_requests, seed=seed, sizes=request_size,
            zipf_s=zipf_s, n_templates=n_templates,
        )
        done = {}
        dlock = threading.Lock()

        def submit(i, size):
            fut = ex.submit(pool[int(sched_load.template_ids[i])])

            def _stamp(_f, i=i):
                with dlock:
                    done[i] = time.perf_counter()

            fut.add_done_callback(_stamp)
            return fut

        out = {}

        def drive():
            out["res"], out["stamps"], out["lag"] = load.replay(
                sched_load, submit, clock=time.perf_counter,
            )

        drv = threading.Thread(target=drive, daemon=True,
                               name="self-heal-load")
        drv.start()
        try:
            chaos.run_schedule(csched, duration_s=duration_s,
                               tick=lambda t: sup.step())
            # settle: a slow host may cross duration mid-reintegration
            t_end = time.perf_counter() + 60.0
            while (sup.stats().heals_ok < 1
                   and time.perf_counter() < t_end):
                sup.step()
                time.sleep(0.005)
            drv.join(timeout=120.0)
        finally:
            ex.close()
            sup.close()

        tl = sup.timeline()
        t_det = next((t for t, e, r in tl
                      if e == "confirmed_down" and r == dead), None)
        t_conv = None
        t_heal_done = next((t for t, e, r in tl
                            if e == "heal_done" and r == dead), None)
        if "kill" in marks:
            t_conv = next((t for t, e, _ in tl
                           if e == "route_pushed"
                           and t >= marks["kill"]), None)
            if t_det is not None:
                row["detection_ms"] = round(
                    (t_det - marks["kill"]) * 1e3, 1)
            if t_conv is not None:
                row["route_convergence_ms"] = round(
                    (t_conv - marks["kill"]) * 1e3, 1)
        if t_heal_done is not None and "heal" in marks:
            row["reintegration_ms"] = round(
                (t_heal_done - marks["heal"]) * 1e3, 1)

        lat = {"healthy": [], "degraded": [], "healed": []}
        stamps = out.get("stamps")
        for i, r in enumerate(out.get("res", ())):
            if isinstance(r, BaseException):
                continue
            r.result(timeout=120)
            # result() can return before the done-callback stamped —
            # same tiny race _drive_open_loop spins out
            while True:
                with dlock:
                    t_done = done.get(i)
                if t_done is not None:
                    break
                time.sleep(0.0002)
            t_sub = float(stamps[i])
            if "kill" not in marks or t_sub < marks["kill"]:
                phase = "healthy"
            elif t_heal_done is None or t_sub < t_heal_done:
                phase = "degraded"
            else:
                phase = "healed"
            lat[phase].append((t_done - t_sub) * 1e3)
        for phase, ms in lat.items():
            if len(ms) >= 5:
                row[f"p99_ms_{phase}"] = round(_p99(ms), 3)
        if len(lat["healthy"]) >= 5 and len(lat["healed"]) >= 5:
            h = _p99(lat["healthy"])
            if h > 0:
                row["healed_p99_x"] = round(_p99(lat["healed"]) / h, 3)
        st = sup.stats()
        row["route_pushes"] = st.route_pushes
        row["heals_ok"] = st.heals_ok
        row["transitions"] = monitor.transition_count
        row["all_serving"] = bool(all(
            s == "serving" for s in st.states.values()))
        row["gen_lag_ms"] = round(out.get("lag", 0.0) * 1e3, 3)
        return row
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def graph_ann_row(x, qall, ivf_index, *, k: int = 10,
                  n_probes: int = 16, degree: int = 16,
                  beams=(16, 32, 64), n_recall_q: int = 64,
                  chain=(4, 32), escalate: int = 2) -> dict:
    """The graph-ANN latency row (ISSUE 19, docs/graph_ann.md): the
    low-latency acceptance priced IN-ROW — the one-dispatch beam search
    at nq=1 vs the SAME corpus served by IVF-Flat at its
    latency-profile qcap-1 point, recall measured against an exact
    numpy oracle on ``n_recall_q`` queries. Stamps the graph arm's
    ``p50_ms``/``recall_at_10``, the baseline's
    ``ivf_p50_ms``/``ivf_recall_at_10``, and the ``beam``/``degree``/
    ``iters`` actually served: the smallest beam in ``beams`` whose
    recall lands within 0.01 of the baseline's (the acceptance bar —
    equal-or-better recall first, then the latency comparison means
    something)."""
    from bench.common import chained_dispatch_stats, recall_at_k
    from raft_tpu.spatial.ann import GraphParams, graph_build
    from raft_tpu.spatial.ann.graph import graph_search
    from raft_tpu.spatial.ann.ivf_flat import ivf_flat_search_grouped

    xn = np.asarray(x, np.float32)
    qn = np.asarray(qall, np.float32)
    n, k_eff = xn.shape[0], min(k, xn.shape[0])
    qr = qn[: min(n_recall_q, qn.shape[0])]
    # exact oracle in numpy: no jit compile for the odd recall shape
    d2 = ((qr * qr).sum(1)[:, None] + (xn * xn).sum(1)[None, :]
          - 2.0 * (qr @ xn.T))
    part = np.argpartition(d2, k_eff - 1, axis=1)[:, :k_eff]
    true = np.take_along_axis(
        part,
        np.argsort(np.take_along_axis(d2, part, axis=1), axis=1),
        axis=1,
    )
    row = {"engine": "graph", "scenario": "graph_ann", "nq": 1,
           "degree": min(degree, n - 1)}

    def p50_of(run, q1):
        jax.block_until_ready(run(q1))
        st = chained_dispatch_stats(
            lambda s, q1=q1: q1 * (1.0 + 1e-6 * s), run,
            n1=chain[0], n2=chain[1], escalate=escalate,
        )
        return st

    # baseline arm: IVF-Flat at ITS latency point (qcap-1, the serving
    # profile the graph index exists to beat)
    qcap1 = ivf_index.warmup(1, k=k_eff, n_probes=n_probes)
    row["ivf_qcap"] = qcap1

    def run_ivf(qq):
        return ivf_flat_search_grouped(
            ivf_index, qq, k_eff, n_probes=n_probes, qcap=qcap1,
        )

    qcap_r = ivf_index.warmup(qr.shape[0], k=k_eff, n_probes=n_probes)
    _, iv = ivf_flat_search_grouped(
        ivf_index, jnp.asarray(qr), k_eff, n_probes=n_probes,
        qcap=qcap_r,
    )
    ivf_rec = recall_at_k(iv, true)
    row["ivf_recall_at_10"] = round(ivf_rec, 4)
    st = p50_of(run_ivf, jnp.asarray(qn[:1]))
    if st is not None:
        row["ivf_p50_ms"] = round(st["ms"], 3)
        row["ivf_spread"] = st["spread"]

    # graph arm: smallest beam meeting the recall bar, then its p50
    gidx = graph_build(xn, GraphParams(degree=row["degree"], seed=0),
                       metric="sqeuclidean")
    beam, rec = None, 0.0
    for b in sorted({max(bm, k_eff) for bm in beams}):
        _, gi = graph_search(gidx, jnp.asarray(qr), k_eff, beam=b)
        beam, rec = b, recall_at_k(np.asarray(gi), true)
        if rec >= ivf_rec - 0.01:
            break
    row["beam"] = beam
    row["recall_at_10"] = round(rec, 4)
    it = gidx.warmup(1, k=k_eff, beam=beam)
    row["iters"] = it

    def run_graph(qq):
        return graph_search(gidx, qq, k_eff, beam=beam, iters=it)

    st = p50_of(run_graph, jnp.asarray(qn[:1]))
    if st is None:
        row["error"] = "jitter-dominated"
    else:
        row["p50_ms"] = round(st["ms"], 3)
        row["spread"] = st["spread"]
        row["repeats"] = st["repeats"]
    return row


def serving_latency_rows(
    n: int = 500_000, d: int = 96, k: int = 10, n_probes: int = 16,
    n_lists: int = 2048, nqs=NQS, engines=("fused_knn", "ivf_flat",
                                           "ivf_pq"),
    chain=(4, 32), escalate: int = 2,
    hedged: bool = True, overload: bool = True, mixed: bool = True,
    open_loop: bool = True, zipf: bool = True, cold_tier: bool = True,
    self_heal: bool = True, graph: bool = True, durable: bool = True,
):
    """One latency row per (engine, nq): ``{"engine", "nq", "p50_ms",
    "spread", "repeats", "qcap"?}`` (``"error"`` on a failed point so one
    engine cannot sink the sweep), plus — when ``ivf_flat`` is swept —
    the ``hedged_straggler`` and ``overload_2x`` resilience rows
    (:func:`hedged_straggler_row`, :func:`overload_row`). Parameterized
    so tests can run a tiny config on CPU; the bench defaults are the
    shared 500k x 96 shape."""
    from bench.common import chained_dispatch_stats
    from raft_tpu.distance.distance_type import DistanceType
    from raft_tpu.random import make_blobs
    from raft_tpu.random.rng import RngState
    from raft_tpu.spatial.ann import (
        IVFFlatParams, IVFPQParams, ivf_flat_build, ivf_pq_build,
    )
    from raft_tpu.spatial.ann.ivf_flat import ivf_flat_search_grouped
    from raft_tpu.spatial.ann.ivf_pq import ivf_pq_search_grouped
    from raft_tpu.spatial.fused_knn import fused_l2_knn

    # same synthesis as bench.common.ann_bench_dataset (clustered blobs,
    # perturbed dataset-point queries) minus the exact oracle — latency
    # rows carry no recall claim, and the oracle would double the setup
    key = jax.random.PRNGKey(2)
    x, _ = make_blobs(n, d, n_clusters=min(1000, max(2, n // 100)),
                      cluster_std=1.0, state=RngState(7))
    base = jax.random.choice(key, x, shape=(max(nqs),), axis=0)
    qall = base + 0.3 * jax.random.normal(
        jax.random.fold_in(key, 1), (max(nqs), d), jnp.float32
    )
    jax.block_until_ready(qall)
    cap = max(64, 2 * -(-n // n_lists) // 8 * 8) if n >= 100_000 else 0

    built = {}

    def get_index(engine):
        if engine not in built:
            if engine == "ivf_flat":
                built[engine] = ivf_flat_build(x, IVFFlatParams(
                    n_lists=n_lists, kmeans_n_iters=10,
                    kmeans_init="random",
                    max_list_cap=cap or None,
                ), metric="sqeuclidean")
            elif engine == "ivf_pq":
                # the 500k QPS row's pq_dim=24; smaller d falls back to
                # the largest divisor <= 24 (tiny test configs)
                pq_dim = max(
                    m for m in range(1, d + 1) if d % m == 0 and m <= 24
                )
                built[engine] = ivf_pq_build(x, IVFPQParams(
                    n_lists=n_lists, pq_dim=pq_dim, kmeans_n_iters=10,
                    kmeans_init="random", max_list_cap=cap or None,
                ))
            elif engine == "fused_knn":
                norms = jnp.einsum(
                    "nd,nd->n", x, x, preferred_element_type=jnp.float32
                )
                built[engine] = norms
        return built[engine]

    rows = []
    for engine in engines:
        for nq in nqs:
            row = {"engine": engine, "nq": nq}
            try:
                qb = qall[:nq]
                if engine == "fused_knn":
                    norms = get_index(engine)

                    def run(qq):
                        return fused_l2_knn(
                            qq, x, k, metric=DistanceType.L2Expanded,
                            index_norms=norms,
                        )
                elif engine == "ivf_flat":
                    idx = get_index(engine)
                    qcap = idx.warmup(nq, k=k, n_probes=n_probes)
                    row["qcap"] = qcap

                    def run(qq, idx=idx, qcap=qcap):
                        return ivf_flat_search_grouped(
                            idx, qq, k, n_probes=n_probes, qcap=qcap,
                        )
                else:
                    idx = get_index(engine)
                    qcap = idx.warmup(
                        nq, k=k, n_probes=n_probes, refine_ratio=4.0,
                    )
                    row["qcap"] = qcap

                    def run(qq, idx=idx, qcap=qcap):
                        return ivf_pq_search_grouped(
                            idx, qq, k, n_probes=n_probes, qcap=qcap,
                            refine_ratio=4.0,
                        )

                warm = run(qb)[0]                    # compile + warm
                float(jnp.sum(jnp.where(jnp.isfinite(warm), warm, 0.0)))
                st = chained_dispatch_stats(
                    lambda s, qb=qb: qb * (1.0 + 1e-6 * s), run,
                    n1=chain[0], n2=chain[1], escalate=escalate,
                )
                if st is None:
                    row["error"] = "jitter-dominated"
                else:
                    row["p50_ms"] = round(st["ms"], 3)
                    row["spread"] = st["spread"]
                    row["repeats"] = st["repeats"]
            except Exception as e:                   # noqa: BLE001 — one
                # failed point must not sink the other 8 rows
                row["error"] = f"{type(e).__name__}: {e}"[:160]
            rows.append(row)

    # resilience rows on the warmed IVF-Flat serving program: the hedged
    # straggler tail and the 2x-overload shed behavior (module docstring)
    if (hedged or overload) and "ivf_flat" in engines:
        try:
            idx = get_index("ivf_flat")
            nq_r = min(128, max(nqs))
            qb = qall[:nq_r]
            qcap_r = idx.warmup(nq_r, k=k, n_probes=n_probes)

            def run_r(qq, idx=idx, qcap=qcap_r):
                return ivf_flat_search_grouped(
                    idx, qq, k, n_probes=n_probes, qcap=qcap,
                )

            jax.block_until_ready(run_r(qb))
            if hedged:
                rows.append(hedged_straggler_row(run_r, qb))
            if overload:
                rows.append(overload_row(run_r, qb))
        except Exception as e:                       # noqa: BLE001
            rows.append({
                "engine": "ivf_flat", "scenario": "resilience",
                "error": f"{type(e).__name__}: {e}"[:160],
            })

    # the open-loop executor row (ISSUE 8): saturation vs the raw
    # program + the offered-load sweep with p50/p99 per point
    if open_loop and "ivf_flat" in engines:
        try:
            idx = get_index("ivf_flat")
            ol_buckets = tuple(sorted({nq for nq in nqs if nq > 1})
                               or {max(nqs)})

            def make_run(bucket, idx=idx):
                qcap = idx.warmup(bucket, k=k, n_probes=n_probes)

                def run(qq, qcap=qcap):
                    return ivf_flat_search_grouped(
                        idx, qq, k, n_probes=n_probes, qcap=qcap,
                    )
                return run

            rows.append(open_loop_row(
                make_run, np.asarray(qall),
                buckets=ol_buckets,
                request_size=max(1, min(16, max(ol_buckets) // 8)),
                n_requests=min(256, 32 * len(ol_buckets) * 4),
                chain=chain, escalate=escalate,
            ))
        except Exception as e:                       # noqa: BLE001
            rows.append({
                "engine": "ivf_flat", "scenario": "open_loop",
                "error": f"{type(e).__name__}: {e}"[:160],
            })

    # the hot-traffic shaping row (ISSUE 15): Zipf repeated-query mix,
    # cache+coalescing saturation vs the uncached path at fixed hardware
    if zipf and "ivf_flat" in engines:
        try:
            idx = get_index("ivf_flat")
            z_buckets = tuple(sorted({nq for nq in nqs if nq > 1})
                              or {max(nqs)})

            def make_run_z(bucket, idx=idx):
                qcap = idx.warmup(bucket, k=k, n_probes=n_probes)

                def run(qq, qcap=qcap):
                    return ivf_flat_search_grouped(
                        idx, qq, k, n_probes=n_probes, qcap=qcap,
                    )
                return run

            rows.append(zipf_hot_traffic_row(
                make_run_z, np.asarray(qall), k=k,
                buckets=z_buckets,
                request_size=max(1, min(16, max(z_buckets) // 8)),
                n_templates=min(64, max(8, 4 * len(z_buckets) * 8)),
                n_requests=min(256, 32 * len(z_buckets) * 4),
                chain=chain, escalate=escalate,
            ))
        except Exception as e:                       # noqa: BLE001
            rows.append({
                "engine": "ivf_flat", "scenario": "zipf_hot_traffic",
                "error": f"{type(e).__name__}: {e}"[:160],
            })

    # the popularity-tiered cold-tier row (ISSUE 17): same index at
    # 1/4 the "HBM" budget, hit-rate-vs-QPS sweep + recall-vs-hot
    if cold_tier and "ivf_flat" in engines:
        try:
            t_buckets = tuple(sorted({nq for nq in nqs if nq > 1})
                              or {max(nqs)})
            rows.append(cold_tier_row(
                get_index("ivf_flat"), np.asarray(qall), k=k,
                n_probes=n_probes, buckets=t_buckets,
                request_size=max(1, min(16, max(t_buckets) // 8)),
                n_templates=min(64, max(8, 4 * len(t_buckets) * 8)),
                n_requests=min(256, 32 * len(t_buckets) * 4),
                chain=chain, escalate=escalate,
            ))
        except Exception as e:                       # noqa: BLE001
            rows.append({
                "engine": "ivf_flat", "scenario": "cold_tier",
                "error": f"{type(e).__name__}: {e}"[:160],
            })

    # the self-healing supervisor row (ISSUE 18): scripted
    # kill→reroute→heal→reintegrate under open-loop Zipf —
    # detection/convergence/reintegration stamps + per-phase p99
    if self_heal and "ivf_flat" in engines:
        try:
            rows.append(self_heal_row(
                np.asarray(x), np.asarray(qall), k=k,
                n_probes=n_probes,
                n_lists=max(4, min(32, n_lists)),
                request_size=max(1, min(8, max(nqs))),
            ))
        except Exception as e:                       # noqa: BLE001
            rows.append({
                "engine": "ivf_flat", "scenario": "self_heal",
                "error": f"{type(e).__name__}: {e}"[:160],
            })

    # the graph-ANN low-latency row (ISSUE 19): one-dispatch beam
    # search vs the IVF-Flat qcap-1 baseline at matched recall
    if graph and "ivf_flat" in engines:
        try:
            rows.append(graph_ann_row(
                np.asarray(x), np.asarray(qall),
                get_index("ivf_flat"), k=k, n_probes=n_probes,
                chain=chain, escalate=escalate,
            ))
        except Exception as e:                       # noqa: BLE001
            rows.append({
                "engine": "graph", "scenario": "graph_ann",
                "error": f"{type(e).__name__}: {e}"[:160],
            })

    # the mutation tier's mixed read/write row (ISSUE 7): sustained
    # ingest QPS alongside search QPS, upsert→visible / delete→masked
    if mixed and "ivf_flat" in engines:
        try:
            nq_m = min(128, max(nqs))
            rows.append(mixed_ingest_row(
                get_index("ivf_flat"), qall[:nq_m], k=k,
                n_probes=n_probes,
                ingest_batch=min(256, max(8, nq_m * 2)),
                chain=(chain[0], max(chain[0] + 1, chain[1] // 4)),
                escalate=escalate,
            ))
        except Exception as e:                       # noqa: BLE001
            rows.append({
                "engine": "ivf_flat", "scenario": "mixed_ingest",
                "error": f"{type(e).__name__}: {e}"[:160],
            })

    # the durable-WAL ingest row (ISSUE 20, docs/robustness.md
    # "Durability"): acked-ingest QPS vs fsync interval, WAL tax
    # priced against the non-durable apply (durability_ratio >= ~0.8)
    if durable and "ivf_flat" in engines:
        try:
            nq_m = min(128, max(nqs))
            rows.append(durable_ingest_row(
                get_index("ivf_flat"), qall[:nq_m],
                ingest_batch=min(128, max(8, nq_m)),
            ))
        except Exception as e:                       # noqa: BLE001
            rows.append({
                "engine": "ivf_flat", "scenario": "durable_ingest",
                "error": f"{type(e).__name__}: {e}"[:160],
            })
    return {
        "metric": f"serving_p50_{n}x{d}_k{k}_p{n_probes}",
        "unit": "ms",
        "rows": rows,
    }


if __name__ == "__main__":
    print(json.dumps(serving_latency_rows()))
