"""The chip smoke's phases at a tiny size on the CPU (kernels in
interpret mode), and its refusal to run without a TPU. The script's
``main()`` runs only on the chip; these call its phase functions."""

import os
import subprocess
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TINY = chip_smoke.Shape(
    rows=4096, dim=32, blobs=16, block=2048, n_lists=16, kmeans_iters=2,
    max_list_cap=512, n_probes=4, eval_queries=64, requests=12,
    max_request=16, buckets=(8, 16), ref_block=1000,
)


def test_exact_reference_matches_numpy():
    x = chip_smoke.make_rows(1, TINY)
    q = chip_smoke.make_queries(1, chip_smoke.pick_rows(1, x, 16))
    d, i = chip_smoke.exact_reference(x, q, 5, TINY.ref_block)
    xf = np.asarray(x, np.float64)
    qf = np.asarray(q, np.float64)
    d2 = ((qf[:, None, :] - xf[None, :, :]) ** 2).sum(-1)
    want = np.argsort(d2, axis=1)[:, :5]
    assert chip_smoke.recall_at_k(i, want) == 1.0
    np.testing.assert_allclose(d, np.take_along_axis(d2, want, 1),
                               rtol=1e-3)


def test_one_chip_phases_tiny_interpret():
    ivf, bf = chip_smoke.run_one_chip(
        0, TINY, jax.devices()[0], use_pallas=True, use_fused=True,
    )
    assert ivf["requests"] == TINY.requests
    assert ivf["queries_served"] >= TINY.requests
    chip_smoke.check_one_chip(ivf, bf, on_tpu=False)


def test_four_chip_phase_on_virtual_devices():
    rec = chip_smoke.phase_mnmg(0, TINY, jax.devices()[:4])
    assert rec["chips"] == 4
    assert len(rec["shard_devices"]) == 4
    assert len(rec["index_devices"]) == 4
    assert rec["recall_at_10"] >= chip_smoke.IVF_RECALL_MIN


def test_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=120,
    )
    assert proc.returncode != 0
    assert "no TPU present" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_rejects_unknown_chip_count():
    with pytest.raises(SystemExit) as e:
        chip_smoke.main(["--chips", "2"])
    assert e.value.code != 0
