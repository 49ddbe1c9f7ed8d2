"""The control: the exact reference computed in int8, the precision
below the configurations' bfloat16, put in the program's place. At a
size a test run holds, it must come out not correct under each cell's
limits, while the float32 reference agrees with a plain numpy search."""

import json

import numpy as np
import pytest

from benchmark import correct, data, reference
from conftest import ROOT, SMALL

K = 10


@pytest.fixture(scope="module")
def small():
    cfg = json.loads((ROOT / "benchmark" / "configs" /
                      "deep12m_ivf_flat.json").read_text())
    cfg.update(SMALL)
    x = data.make_rows(2 ** 35 + 11, cfg)
    q = np.asarray(data.make_queries(7, cfg, 512))
    ref_d, ref_i = reference.exact_knn(x, q, K, 5000)
    return x, q, ref_d, ref_i


def test_reference_matches_numpy(small):
    x, q, ref_d, ref_i = small
    xs = np.asarray(x, np.float64)[:4000]
    qs = q[:64].astype(np.float64)
    d2 = ((qs[:, None, :] - xs[None]) ** 2).sum(-1)
    want = np.argsort(d2, axis=1)[:, :K]
    got_d, got_i = reference.exact_knn(x[:4000], q[:64], K, 1000)
    assert (got_i == want).mean() > 0.999
    # |q|^2 + |x|^2 - 2 q.x in float32 at norms ~3,500: ~1e-3 absolute
    np.testing.assert_allclose(got_d, np.take_along_axis(d2, want, 1),
                               rtol=1e-4, atol=2e-3)


@pytest.mark.parametrize("config", ["deep12m_ivf_flat",
                                    "deep12m_brute_force"])
def test_int8_control_is_not_correct(small, config):
    x, q, ref_d, ref_i = small
    limits = json.loads((ROOT / "benchmark" / "configs" /
                         f"{config}.json").read_text())["limits"]
    cd, ci = reference.int8_knn(x, q, K, 5000)
    miss, err = correct.gaps(x, q, cd, ci, ref_d, ref_i)
    numbers = {"unanswered": 0, "miss_at_10": miss, "dist_err": err}
    assert not correct.judge(numbers, limits), numbers
    # and the reference in the program's place passes
    miss, err = correct.gaps(x, q, ref_d, ref_i, ref_d, ref_i)
    assert correct.judge({"unanswered": 0, "miss_at_10": miss,
                          "dist_err": err}, limits)
