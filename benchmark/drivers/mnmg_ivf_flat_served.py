"""IVF-Flat list-sharded over the cell's chips, served through
``ServingExecutor``: each chip makes its own rows,
``mnmg_ivf_flat_build_distributed`` builds the index, ``index.warmup``
compiles the traffic's buckets, the executor stages each batch replicated
on the mesh (``Comms.replicate``), and each batch runs
``mnmg_ivf_flat_search`` with its buffer donated."""

from __future__ import annotations

import functools
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import data, roofline, serving
from benchmark.spans import span

KERNEL = "flat_scan_subchunk_min"


@functools.partial(jax.jit, static_argnames=data.KEYS + ("blocks",))
def _make_shard(key, first, *, blocks, rows, dim, blobs, center_scale,
                noise_scale, noise_power, make_block, dtype):
    """Blocks ``first .. first + blocks - 1`` of the configuration's rows,
    made as ``data.make_rows`` makes each block, as a (1, n, dim) shard."""
    centers, scales = data._law(key, blobs, dim, center_scale, noise_scale,
                                noise_power)
    parts = []
    for j in range(blocks):
        b = first + j
        ids = b * make_block + jnp.arange(make_block)
        noise = jax.random.normal(jax.random.fold_in(key, 1000 + b),
                                  (make_block, dim))
        parts.append((centers[ids % blobs] + scales * noise).astype(dtype))
    return jnp.concatenate(parts)[None] if len(parts) > 1 else parts[0][None]


@functools.lru_cache(maxsize=4)
def _shard_maker(mesh, axis: str, per: int, params: tuple):
    """One program for every chip of ``mesh``: chip r makes blocks
    ``r x per .. r x per + per - 1`` on itself."""
    from jax.sharding import PartitionSpec as P

    from raft_tpu.comms import Comms

    comms = Comms(mesh=mesh, axis=axis)

    def body(key):
        first = jax.lax.axis_index(axis) * per
        return _make_shard(key, first, blocks=per, **dict(params))

    return jax.jit(comms.shard_map(body, in_specs=P(),
                                   out_specs=P(axis, None, None)))


def shard_rows(seed: int, cfg: dict, comms):
    """The configuration's rows as (chips, rows / chips, dim), sharded over
    the mesh: chip r makes the r-th run of blocks on itself, so no chip
    or host holds more than its shard, and global row id = r x
    (rows / chips) + local row, as ``data.make_rows`` orders them."""
    n_chips = comms.size
    n_blocks = cfg["rows"] // cfg["make_block"]
    if cfg["rows"] % cfg["make_block"] or n_blocks % n_chips:
        raise ValueError(f"rows={cfg['rows']} is not a whole number of "
                         f"make_block={cfg['make_block']} blocks a chip")
    params = tuple(sorted(data._params(cfg).items()))
    return _shard_maker(comms.mesh, comms.axis, n_blocks // n_chips,
                        params)(data.base_key(seed))


def _executor(dispatch, buckets, dim, replicate):
    """A ``ServingExecutor`` whose stage is ``replicate`` (the index
    mesh's ``Comms.replicate``) inside a harness span, and its metric
    registry."""
    from raft_tpu.obs.metrics import MetricRegistry
    from raft_tpu.serving import ServingExecutor

    def stage(batch):
        with span("bench.stage"):
            return replicate(batch)

    reg = MetricRegistry()
    return ServingExecutor(dispatch, buckets, dim=dim, registry=reg,
                           stage=stage), reg


def setup(cfg, traffic, seed, devices):
    from raft_tpu.comms import (
        build_comms, mnmg_ivf_flat_build_distributed, mnmg_ivf_flat_search,
    )
    from raft_tpu.spatial.ann import IVFFlatParams

    comms = build_comms(devices)
    # the executor's stage; taken first, so that a program without it
    # fails before any data is made
    replicate = comms.replicate
    ix = cfg["index"]
    t_data = time.perf_counter()
    rows = jax.block_until_ready(shard_rows(seed, cfg, comms))
    pool = np.asarray(data.make_queries(seed, cfg, cfg["query_pool"],
                                        devices[0]))
    t0 = time.perf_counter()
    index = mnmg_ivf_flat_build_distributed(comms, rows, IVFFlatParams(
        n_lists=ix["n_lists"], kmeans_n_iters=ix["kmeans_iters"],
        kmeans_init=ix["kmeans_init"], max_list_cap=ix["max_list_cap"],
        seed=int(seed) % (2 ** 31)), metric="sqeuclidean")
    jax.block_until_ready(index.vectors_sorted)
    build_s = time.perf_counter() - t0
    k, p = cfg["k"], ix["n_probes"]
    # "qcap" absent: the program's default per-list query capacity
    qcaps = {b: index.warmup(comms, b, k=k, n_probes=p,
                             qcap=ix.get("qcap"), donate_queries=True)
             for b in traffic["buckets"]}

    def dispatch(batch, **_runtime):
        with span("bench.dispatch"):
            return mnmg_ivf_flat_search(
                comms, index, batch, k, n_probes=p,
                qcap=qcaps[int(batch.shape[0])], donate_queries=True)

    # one pass of each bucket through the executor itself before the
    # window, so that its first batches pay no one-off host work
    ex, _ = _executor(dispatch, traffic["buckets"], cfg["dim"], replicate)
    with ex:
        for b in traffic["buckets"]:
            ex.submit(pool[:b]).result(timeout=600)
    phases = {"data_s": t0 - t_data, "build_s": build_s,
              "warmup_s": time.perf_counter() - t0 - build_s}
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return {"rows": rows, "pool": pool, "index": index, "cfg": cfg,
            "replicate": replicate, "devices": devices,
            "dispatch": dispatch, "phases": phases,
            "about": f"{index.owner.shape[0]} lists over "
                     f"{len(devices)} chips, at most {index.nl_pad - 1} a "
                     f"chip, of at most {index.max_list} rows; memory "
                     f"peak by chip {peaks}"}


def serve(state, traffic, seconds, seed, tracer=None):
    cfg, pool = state["cfg"], state["pool"]
    buckets = traffic["buckets"]
    ex, reg = _executor(state["dispatch"], buckets, cfg["dim"],
                        state["replicate"])
    host = {}

    def snapshot():
        host.update(serving.executor_stats(ex, reg, buckets))

    with ex:
        rec = serving.closed_loop(
            ex.submit, lambda f: f.result(timeout=serving.LATE_S), pool,
            traffic, seconds, seed, tracer, (snapshot,))
        rec["executor"] = serving.executor_stats(ex, reg, buckets)
    rec["executor_host"] = host or rec["executor"]
    rec["build_s"] = state["phases"]["build_s"]
    return rec


def work(state, rec):
    """Per batch, each chip's scan work (``roofline.ivf_scan_work`` over
    the lists it owns among the batch's exact global probes, the others
    sent to its empty sentinel list, as the program sends them): the
    (flops, bytes) of the mean chip, the mean over the window's batches,
    and ``probe_skew``, the mean over batches of the busiest chip's rows
    probed (query, row pairs) over the mean chip's. Nothing where batches
    mix requests."""
    ex = rec["executor"]
    req = rec["requests"]
    if ex["padded_rows"] or ex["batches"] != len(req["sizes"]):
        return {}
    index, cfg = state["index"], state["cfg"]
    cents = jax.device_put(np.asarray(index.centroids), state["devices"][0])
    owner = np.asarray(index.owner)
    local = np.asarray(index.local_id)
    sizes = np.asarray(index.list_sizes)            # (chips, nl_pad)
    sentinel = sizes.shape[1] - 1
    row_bytes = np.dtype(index.vectors_sorted.dtype).itemsize
    total = np.zeros((sizes.shape[0], 2))
    skew = []
    for s, m in zip(req["start"], req["sizes"]):
        probes = roofline.probed_lists(
            cents, serving.pool_rows(state["pool"], s, m),
            cfg["index"]["n_probes"])
        mine = owner[probes]
        chips = np.array([roofline.ivf_scan_work(
            np.where(mine == r, local[probes], sentinel), sizes[r],
            cfg["dim"], row_bytes) for r in range(sizes.shape[0])], float)
        total += chips
        # flops are 2 x dim per (query, row) pair
        skew.append(chips[:, 0].max() / chips[:, 0].mean())
    flops, nbytes = total.mean(axis=0) / len(req["sizes"])
    return {KERNEL: (flops, nbytes), "probe_skew": float(np.mean(skew))}


def _row_major_on(device, parts, piece: int):
    """The host arrays ``parts``, in order, as one row-major array on
    ``device``, copied ``piece`` rows at a time into a buffer placed
    row-major from the start.

    The v5e keeps a ``(n, 96)`` bf16 array column-major by default, and
    a gather of its rows (the reference's ``true_dists``) first copies it
    whole to row-major: at 50M rows the two layouts (8.9 + 11.9 GiB) do
    not fit one chip, where the row-major one alone does. The buffer's
    programs write no compile-cache entry: JAX loads a cached program
    without its output layout (``place_row_store``)."""
    from jax.experimental.layout import Format, Layout

    from raft_tpu import compat

    n = sum(p.shape[0] for p in parts)
    one = jax.sharding.SingleDeviceSharding(device)
    row_major = Format(Layout(major_to_minor=(0, 1)), one)
    with compat.persistent_cache_min_compile_time(float("inf")):
        out = jax.jit(lambda: jnp.zeros((n, parts[0].shape[1]),
                                        parts[0].dtype),
                      out_shardings=row_major)()
        put = jax.jit(
            lambda buf, part, at: jax.lax.dynamic_update_slice_in_dim(
                buf, part, at, 0),
            out_shardings=row_major, donate_argnums=(0,))
        at = 0
        for part in parts:
            for s in range(0, part.shape[0], piece):
                out = put(out, jax.device_put(part[s:s + piece], device),
                          np.int32(at + s))
            at += part.shape[0]
    return out


def release(state):
    """Drop the index; return the rows as one row-major array on the
    first chip, in global-id order, and the query pool. The shards go
    through the host, so no chip holds the rows twice."""
    index = state.pop("index")
    state.pop("dispatch")
    # an executor's thread cycle may still hold the dispatch, and with it
    # the index: free the index's buffers before the rows gather on one
    # chip
    for leaf in jax.tree.leaves(index):
        leaf.delete()
    del index
    gc.collect()
    rows = state.pop("rows")
    parts = [np.asarray(shard.data)[0] for shard in
             sorted(rows.addressable_shards, key=lambda s: s.index[0].start)]
    rows.delete()
    return (_row_major_on(state["devices"][0], parts,
                          state["cfg"]["make_block"]),
            state["pool"])
