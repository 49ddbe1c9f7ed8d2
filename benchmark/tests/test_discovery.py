"""A configuration, a traffic mix and a per-layer metric are found by
name: adding one takes new files and new BENCHMARK.json entries, and no
edit of a file that is there."""

import json
import subprocess
import sys

from conftest import ROOT, copy_tree


def _dry(root):
    out = subprocess.run(
        [sys.executable, str(root / "benchmark" / "run.py"), "--dry"],
        capture_output=True, text=True, timeout=120, cwd=root,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert out.returncode == 0, out.stderr
    return [json.loads(line) for line in out.stdout.splitlines()]


def test_dry_lists_every_cell():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    rows = _dry(ROOT)
    assert [r["workload"] for r in rows] == [
        w["name"] for w in bench["workloads"]]
    for r in rows:
        assert "setup_s" in r["end_to_end"] and r["per_layer"]


def test_new_config_traffic_and_metric_are_picked_up(tmp_path):
    root = copy_tree(tmp_path)
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*")
              if p.is_file()}
    bdir = root / "benchmark"
    cfg = json.loads((bdir / "configs" / "deep12m_ivf_flat.json")
                     .read_text())
    cfg["index"]["n_probes"] = 32
    (bdir / "configs" / "deep12m_ivf_flat_p32.json").write_text(
        json.dumps(cfg))
    (bdir / "traffic" / "zipf_hot.json").write_text(json.dumps(
        {"loop": "open", "rate_rps": 100, "sizes": [1, 1],
         "buckets": [8, 64]}))
    (bdir / "metrics" / "frontend.cache_hit_share.zipf_hot.py").write_text(
        "def read(rec, tr, peak):\n    return None\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(bench["configs"][0],
                                 name="deep12m_ivf_flat_p32",
                                 file="benchmark/configs/"
                                      "deep12m_ivf_flat_p32.json"))
    bench["workloads"].append({
        "name": "deep12m_ivf_flat_p32.zipf_hot",
        "config": "deep12m_ivf_flat_p32", "traffic": "zipf_hot",
        "chips": 1, "why": "a throwaway cell"})
    bench["end_to_end"][0]["workloads"].append(
        "deep12m_ivf_flat_p32.zipf_hot")
    bench["per_layer"].append({
        "name": "frontend.cache_hit_share.zipf_hot", "unit": "%",
        "better": "higher", "source": "program_counter",
        "layer": "serving front end", "moves": "p95_ms",
        "workloads": ["deep12m_ivf_flat_p32.zipf_hot"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    rows = {r["workload"]: r for r in _dry(root)}
    new = rows["deep12m_ivf_flat_p32.zipf_hot"]
    assert new["config"] == "deep12m_ivf_flat_p32"
    assert new["traffic"] == "zipf_hot"
    assert new["driver"] == "ivf_flat_served"
    assert "frontend.cache_hit_share.zipf_hot" in new["per_layer"]
    assert "p95_ms" in new["end_to_end"]
    for path, content in before.items():
        assert path.read_bytes() == content, path


def test_missing_file_is_an_error(tmp_path):
    root = copy_tree(tmp_path)
    (root / "benchmark" / "traffic" / "bulk.json").unlink()
    out = subprocess.run(
        [sys.executable, str(root / "benchmark" / "run.py"), "--dry"],
        capture_output=True, text=True, timeout=120, cwd=root,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert out.returncode != 0 and not out.stdout.strip()


def test_run_without_the_program_fails(tmp_path):
    root = copy_tree(tmp_path)
    out = subprocess.run(
        [sys.executable, str(root / "benchmark" / "run.py"),
         "--workload", "deep12m_brute_force.offline", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120, cwd=root,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert out.returncode != 0 and not out.stdout.strip()


def test_run_without_a_tpu_fails():
    out = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"),
         "--workload", "deep12m_brute_force.offline", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, cwd=ROOT,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert out.returncode != 0 and not out.stdout.strip()
    assert "no TPU" in out.stderr
