"""Work counts against hand counts, and the peak table."""

import numpy as np
import pytest

from benchmark import roofline


def test_unknown_device_raises():
    with pytest.raises(KeyError, match="TPU v9"):
        roofline.peaks("TPU v9")
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_s"] == 819e9


def test_ivf_scan_work_by_hand():
    # 3 queries, 2 probes each, over lists of 10, 20, 30, 40 rows
    probes = np.array([[0, 1], [1, 2], [1, 0]])
    sizes = np.array([10, 20, 30, 40])
    flops, nbytes = roofline.ivf_scan_work(probes, sizes, dim=4,
                                           row_bytes=2, query_bytes=4)
    # distinct lists 0, 1, 2: 60 rows x 4 dims x 2 B; queries 3 x 4 x 4 B
    assert nbytes == 60 * 4 * 2 + 3 * 4 * 4
    # pairs: (10+20) + (20+30) + (20+10) = 110 rows, 2 x 4 FLOP each
    assert flops == 110 * 2 * 4


def test_brute_force_flops_by_hand():
    assert roofline.brute_force_flops(3, 5, 4) == 2 * 3 * 5 * 4


def test_least_time_names_its_bound():
    peak = roofline.peaks("TPU v5 lite")
    t, bound = roofline.least_time(197e12, 0, peak)
    assert bound == "compute" and t == pytest.approx(1.0)
    t, bound = roofline.least_time(0, 819e9 * 2, peak)
    assert bound == "memory" and t == pytest.approx(2.0)


def test_probed_lists_are_the_nearest_centroids():
    import jax.numpy as jnp

    cents = jnp.array([[0.0, 0.0], [10.0, 0.0], [0.0, 10.0], [9.0, 9.0]])
    q = np.array([[9.5, 0.5], [1.0, 8.0]], np.float32)
    got = roofline.probed_lists(cents, q, 2)
    assert got[0].tolist() == [1, 3] and got[1].tolist() == [2, 0]
