"""The four-chip cell at a small size, on four virtual CPU devices in a
child process (the suite's own process has one CPU device): the chips'
shards are ``data.make_rows`` row for row, a sound run reads ``correct``,
and a run with one shard's answers left out of the cross-shard merge
does not."""

import json
import os
import subprocess
import sys

import pytest

from conftest import ROOT, SMALL, SMALL_INDEX, copy_tree

CELL = "deep50m_mnmg_ivf_flat.bulk"
CONFIG = "deep50m_mnmg_ivf_flat"
SEED = 2 ** 33 + 5
# SMALL, in blocks that split evenly over the four chips, with its lists
# split about six ways, as the full size splits ~12k-row lists at 2,048,
# so that 64 probes reach about as many k-means lists as there; and the
# whole batch as the per-list query capacity, as SMALL_INDEX raises it
MNMG = dict(SMALL, make_block=5000)
MNMG_INDEX = dict(SMALL_INDEX, max_list_cap=50, qcap=1024)

CHILD = r"""
import json, sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import data, run
from raft_tpu.comms import build_comms, mnmg_ivf_flat

root, config, cell, seed = Path(sys.argv[1]), sys.argv[2], sys.argv[3], \
    int(sys.argv[4])
spec = run.Spec(root)
cfg = spec.config(config)
drv = spec.driver(cfg["driver"])
out = {"devices": len(jax.devices())}

shards = drv.shard_rows(seed, cfg, build_comms(jax.devices()[:4]))
whole = np.asarray(data.make_rows(seed, cfg))
got = np.asarray(shards).reshape(whole.shape)
out["shards_equal"] = bool((got.view(np.uint16)
                            == whole.view(np.uint16)).all())
out["shard_devices"] = len({s.device for s in shards.addressable_shards})


def one_run():
    res = run.run_workload(spec, cell, seed, 1.0, False, require_tpu=False,
                           log=lambda s: None)
    return {k: res[k] for k in ("correct", "failed", "attempted",
                                "checks")}


out["sound"] = one_run()

real = mnmg_ivf_flat._merge_across_shards


def first_shard_left_out(ax, hier, vals, gids, *rest):
    rank = jax.lax.axis_index(ax.axis)
    return real(ax, hier, jnp.where(rank == 0, jnp.inf, vals), gids, *rest)


mnmg_ivf_flat._merge_across_shards = first_shard_left_out
mnmg_ivf_flat._cached_search.cache_clear()
out["shard_left_out"] = one_run()
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def child(tmp_path_factory):
    root = copy_tree(tmp_path_factory.mktemp("mnmg"))
    path = root / "benchmark" / "configs" / f"{CONFIG}.json"
    cfg = json.loads(path.read_text())
    cfg.update(MNMG)
    cfg["index"].update(MNMG_INDEX)
    path.write_text(json.dumps(cfg))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-c", CHILD, str(root), CONFIG, CELL, str(SEED)],
        capture_output=True, text=True, timeout=600, cwd=root, env=env)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_shards_are_the_configuration_rows(child):
    assert child["devices"] == 4 and child["shard_devices"] == 4
    assert child["shards_equal"]


def test_sound_run_is_correct(child):
    res = child["sound"]
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0


def test_shard_left_out_of_the_merge_is_not_correct(child):
    res = child["shard_left_out"]
    assert res["failed"] == 0
    assert not res["correct"], res["checks"]
    assert res["checks"]["miss_at_10"]["value"] > \
        res["checks"]["miss_at_10"]["limit"]
