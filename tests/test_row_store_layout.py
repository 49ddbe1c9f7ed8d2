"""The IVF row store's device layout (spatial/ann/common.place_row_store).

The TPU stores a tall array whose minor dimension is under 128 lanes,
e.g. ``bf16[n, 96]``, column-major (HLO ``{0,1}``). The grouped search
program reads rows, so a column-major store made XLA copy the whole
index to row-major on every run. These tests commit arrays column-major
on the CPU, as the TPU's default would, and pin that every build hands
the warmed program a row-major store (``{1,0}``) that it copies nowhere.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.layout import Format, Layout

from raft_tpu import compat
from raft_tpu.obs import metrics as obs_metrics
from raft_tpu.spatial.ann import ivf_flat
from raft_tpu.spatial.ann.common import is_row_major, place_row_store
from raft_tpu.spatial.ann.ivf_flat import (
    IVFFlatParams, ivf_flat_build, ivf_flat_search_grouped,
)
from raft_tpu.spatial.ann.ivf_sq import (
    IVFSQParams, ivf_sq_build, ivf_sq_search_grouped,
)
from raft_tpu.spatial.ann.mutation import (
    compact, delete, upsert, wrap_mutable,
)

N, D, N_LISTS, NQ, K, N_PROBES = 2000, 96, 16, 8, 10, 4
ROW_MAJOR, COL_MAJOR = (0, 1), (1, 0)


def commit(x, major_to_minor):
    # out of the persistent compile cache, as place_row_store keeps its
    # relayout: a cached one would come back labelled row-major
    with compat.persistent_cache_min_compile_time(float("inf")):
        return jax.device_put(
            x, Format(Layout(major_to_minor=major_to_minor), x.sharding)
        )


@pytest.fixture(scope="module")
def rows():
    # small integers: every distance is exact in f32, so two builds that
    # agree on the data agree bit for bit on the answers
    rng = np.random.default_rng(26)
    return rng.integers(-8, 8, (N, D)).astype(np.float32)


@pytest.fixture(scope="module")
def queries():
    rng = np.random.default_rng(27)
    return jnp.asarray(rng.integers(-8, 8, (NQ, D)).astype(np.float32))


def build_flat(x):
    return ivf_flat_build(
        x, IVFFlatParams(n_lists=N_LISTS, kmeans_n_iters=3, seed=1)
    )


def build_sq(x):
    return ivf_sq_build(
        x, IVFSQParams(n_lists=N_LISTS, kmeans_n_iters=3, seed=1)
    )


def rebuild_flat(x):
    """The mutation tier's compaction rebuild of a flat index."""
    mw = wrap_mutable(build_flat(x), delta_cap=8)
    mw, _ = upsert(mw, np.full((2, D), 3.0, np.float32),
                   np.array([N, N + 1], np.int32))
    mw, _ = delete(mw, np.array([0, 5, 9], np.int32))
    return compact(mw)[0].index


def rebuild_sq(x):
    """The mutation tier's compaction rebuild of an SQ index."""
    mw = wrap_mutable(build_sq(x), delta_cap=8)
    mw, _ = delete(mw, np.array([1, 7], np.int32))
    return compact(mw)[0].index


BUILDS = {
    "flat": (build_flat, jnp.bfloat16, "data_sorted", "flat"),
    "sq": (build_sq, jnp.float32, "codes_sorted", "sq"),
    "mutation_flat": (rebuild_flat, jnp.bfloat16, "data_sorted", "flat"),
    "mutation_sq": (rebuild_sq, jnp.float32, "codes_sorted", "sq"),
}


def search(index, q):
    fn = (ivf_sq_search_grouped if hasattr(index, "codes_sorted")
          else ivf_flat_search_grouped)
    return fn(index, q, K, n_probes=N_PROBES, qcap=NQ)


def warmed_program(index, monkeypatch):
    """The grouped program exactly as ``index.warmup`` compiled it: the
    arguments of its ``_grouped_impl`` call, lowered and compiled again
    (the same program, so the same entry layouts and ops)."""
    real = ivf_flat._grouped_impl
    calls = []

    def spy(*args, **kw):
        calls.append((args, kw))
        return real(*args, **kw)

    monkeypatch.setattr(ivf_flat, "_grouped_impl", spy)
    index.warmup(NQ, k=K, n_probes=N_PROBES)
    monkeypatch.setattr(ivf_flat, "_grouped_impl", real)
    assert len(calls) == 1
    args, kw = calls[0]
    return real.lower(*args, **kw).compile()


def store_layout_and_copies(compiled, store):
    """(major_to_minor of the program's row-store parameter, the number
    of ops in the program that copy an array of the store's shape)."""
    entry = compiled.input_formats[0][0]      # the index argument
    layout = entry.data_sorted.layout.major_to_minor
    shape = re.escape("[%d,%d]" % store.shape)
    copies = re.findall(
        r"= \w+%s\{[0-9,]+\} copy\(" % shape, compiled.as_text()
    )
    return layout, len(copies)


def gauge(engine):
    return obs_metrics.default_registry().gauge(
        "ivf_row_store_row_major", engine=engine
    )


@pytest.mark.parametrize("case", sorted(BUILDS))
def test_build_from_column_major_rows_stores_row_major(
        case, rows, queries, monkeypatch):
    build, dtype, field, engine = BUILDS[case]
    x = jnp.asarray(rows).astype(dtype)
    x_col = commit(x, COL_MAJOR)
    assert x_col.format.layout.major_to_minor == COL_MAJOR

    index = build(x_col)
    store = getattr(index, field)
    assert is_row_major(store)

    gauge(engine).set(0.0)
    layout, copies = store_layout_and_copies(
        warmed_program(index, monkeypatch), store
    )
    assert layout == ROW_MAJOR
    assert copies == 0
    assert gauge(engine).value == 1.0

    want_d, want_i = search(build(commit(x, ROW_MAJOR)), queries)
    got_d, got_i = search(index, queries)
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    np.testing.assert_array_equal(np.asarray(got_d), np.asarray(want_d))


@pytest.mark.parametrize("case", ["flat", "sq"])
def test_place_row_store_repins_a_column_major_store(
        case, rows, queries, monkeypatch):
    """A store left column-major (the TPU's default for d = 96) costs the
    warmed program a whole-store copy; the helper re-places it row-major
    once, keeping its sharding and values, and the copy is gone."""
    build, dtype, field, engine = BUILDS[case]
    index = build(jnp.asarray(rows).astype(dtype))
    want_d, want_i = search(index, queries)

    store = getattr(index, field)
    col = commit(store, COL_MAJOR)
    unpinned = dataclasses.replace(index, **{field: col})
    layout, copies = store_layout_and_copies(
        warmed_program(unpinned, monkeypatch), col
    )
    assert (layout, gauge(engine).value) == (COL_MAJOR, 0.0)
    assert copies >= 1

    pinned_store = place_row_store(col)
    assert is_row_major(pinned_store)
    assert pinned_store.sharding == col.sharding
    np.testing.assert_array_equal(np.asarray(pinned_store),
                                  np.asarray(store))
    assert place_row_store(pinned_store) is pinned_store
    pinned = dataclasses.replace(index, **{field: pinned_store})
    layout, copies = store_layout_and_copies(
        warmed_program(pinned, monkeypatch), pinned_store
    )
    assert (layout, copies, gauge(engine).value) == (ROW_MAJOR, 0, 1.0)
    got_d, got_i = search(pinned, queries)
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    np.testing.assert_array_equal(np.asarray(got_d), np.asarray(want_d))


def test_place_index_pins_the_sharded_flat_row_store(rows):
    """``place_index`` (the sharded builds, loads and re-shards) keeps
    the MNMG flat row store row-major on every shard, in its sharding."""
    from raft_tpu.comms import build_comms, mnmg_ivf_flat_build
    from raft_tpu.comms.mnmg_ivf import place_index

    comms = build_comms(jax.devices()[:8])
    index = mnmg_ivf_flat_build(
        comms, rows[:, :24],
        IVFFlatParams(n_lists=N_LISTS, kmeans_n_iters=3, seed=1),
    )
    assert is_row_major(index.vectors_sorted)
    col = commit(index.vectors_sorted, (1, 2, 0))
    placed = place_index(
        comms, dataclasses.replace(index, vectors_sorted=col)
    )
    assert is_row_major(placed.vectors_sorted)
    assert placed.vectors_sorted.sharding == index.vectors_sorted.sharding
    np.testing.assert_array_equal(np.asarray(placed.vectors_sorted),
                                  np.asarray(index.vectors_sorted))


_CACHED_PLACEMENT = """
import os, sys
import jax, jax.numpy as jnp, numpy as np
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
from raft_tpu.spatial.ann.common import is_row_major, place_row_store
from tests.test_row_store_layout import COL_MAJOR, commit
cache = sys.argv[1]
base = np.arange(300 * 96, dtype=np.float32).reshape(300, 96)
col = jax.block_until_ready(commit(jnp.asarray(base), COL_MAJOR))
rows = jax.jit(lambda a, i: a[i])(col, jnp.arange(0, 300, 7))
before = set(os.listdir(cache))
assert before                       # the cache is in use
store = jax.block_until_ready(place_row_store(col))
assert set(os.listdir(cache)) == before, "the relayout was cached"
assert is_row_major(store)
rows = jax.jit(lambda a, i: a[i])(store, jnp.arange(0, 300, 7))
np.testing.assert_array_equal(np.asarray(rows), base[::7])
print("ok")
"""


def test_place_row_store_keeps_its_relayout_out_of_the_compile_cache(
        tmp_path):
    """JAX loads a cached program without its output layouts, so a cached
    relayout would hand a later process row-major bytes labelled with the
    default layout (on the v5e the store's next program then refused
    them). The relayout must leave no entry in the persistent cache."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    out = subprocess.run(
        [sys.executable, "-c", _CACHED_PLACEMENT, str(tmp_path)],
        cwd=Path(__file__).resolve().parents[1], env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split()[-1] == "ok"
