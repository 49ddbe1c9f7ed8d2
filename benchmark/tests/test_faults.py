"""The comparison that decides ``correct``, driven through a whole run at
a small size on the CPU (the look for a chip skipped): sound runs pass,
and a run with the timed path broken underneath comes out not correct,
once for each fault a cell of one chip can have, and for an IVF search
cut to half its probes."""

import jax.numpy as jnp
import pytest

from benchmark import run


def _half_left_out(out, n_rows):
    """Half of the batch left out: its second half gets the first
    half's answers."""
    d, i = out
    h = d.shape[0] // 2
    if h == 0:
        return out
    return (d.at[h:].set(d[:d.shape[0] - h]),
            i.at[h:].set(i[:d.shape[0] - h]))


def _answer_altered(out, n_rows):
    """An answer altered where it is produced: the first row's best id
    becomes another row's, its distance left as it was."""
    d, i = out
    return d, i.at[0, 0].set((i[0, 0] + 1) % n_rows)


FAULTS = {"half_left_out": _half_left_out,
          "answer_altered": _answer_altered}
PATHS = {
    "deep12m_ivf_flat": ("raft_tpu.spatial.ann.ivf_flat",
                         "ivf_flat_search_grouped"),
    "deep12m_brute_force": ("raft_tpu.spatial.knn", "brute_force_knn"),
}
CELLS = ["deep12m_ivf_flat.interactive", "deep12m_ivf_flat.bulk",
         "deep12m_brute_force.offline"]


def _run(root, cell, seed=2 ** 33 + 5):
    spec = run.Spec(root)
    return run.run_workload(spec, cell, seed, 1.0, False,
                            require_tpu=False, log=lambda s: None)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(small_root, cell):
    res = _run(small_root, cell)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res["checks"]) == ["unanswered", "miss_at_10", "dist_err"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_broken_path_is_not_correct(small_root, monkeypatch, cell, fault):
    import importlib

    mod_name, fn_name = PATHS[cell.split(".")[0]]
    mod = importlib.import_module(mod_name)
    real = getattr(mod, fn_name)
    n_rows = 20000

    def broken(*args, **kwargs):
        return FAULTS[fault](real(*args, **kwargs), n_rows)

    monkeypatch.setattr(mod, fn_name, broken)
    res = _run(small_root, cell)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("cell", [c for c in CELLS if "ivf_flat" in c])
def test_half_the_probes_is_not_correct(small_root, monkeypatch, cell):
    from raft_tpu.spatial.ann import ivf_flat

    real = ivf_flat.ivf_flat_search_grouped

    def narrow(index, q, k, *, n_probes, **kwargs):
        return real(index, q, k, n_probes=n_probes // 2, **kwargs)

    monkeypatch.setattr(ivf_flat, "ivf_flat_search_grouped", narrow)
    res = _run(small_root, cell)
    assert res["failed"] == 0
    assert not res["correct"], res["checks"]
    assert res["checks"]["miss_at_10"]["value"] > \
        res["checks"]["miss_at_10"]["limit"]


def test_unanswered_request_is_not_correct(small_root, monkeypatch):
    from raft_tpu.spatial.ann import ivf_flat

    real = ivf_flat.ivf_flat_search_grouped
    calls = {"n": 0}

    def failing(index, q, *args, **kwargs):
        calls["n"] += 1
        if calls["n"] == 6:      # a batch inside the window fails
            raise RuntimeError("device lost")
        return real(index, q, *args, **kwargs)

    monkeypatch.setattr(ivf_flat, "ivf_flat_search_grouped", failing)
    res = _run(small_root, "deep12m_ivf_flat.interactive")
    assert res["failed"] > 0 and not res["correct"]
    assert jnp.isinf(res["metrics"]["p95_ms"]["value"]) or \
        res["metrics"]["p95_ms"]["value"] > 0
