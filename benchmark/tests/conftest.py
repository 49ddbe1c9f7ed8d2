"""The benchmark's own tests run on the CPU, at small sizes."""

import json
import os
import shutil
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

# the cells' configurations cut to a size a test run holds: every width
# kept, and the list geometry's shape: ~16 k-means lists a blob, each
# split in two, so that 16 probes reach about 8 of a query's blob's
# lists, as at the full size; the rows, blobs and lists cut. With 4
# blobs a batch's queries crowd into few lists, so the per-list query
# capacity is raised to keep the program's overflow as rare as it is
# over 250 blobs
SMALL = {"rows": 20000, "blobs": 4, "make_block": 10000,
         "query_pool": 4096, "reference_block": 5000}
SMALL_INDEX = {"n_lists": 64, "max_list_cap": 200, "qcap": 256}


def copy_tree(dst: Path) -> Path:
    """A checkout of the benchmark alone (BENCHMARK.json and
    benchmark/) under ``dst``."""
    shutil.copytree(ROOT / "benchmark", dst / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    return dst


@pytest.fixture
def small_root(tmp_path):
    """A copy of the benchmark whose configurations are cut to a size
    the CPU runs in seconds."""
    root = copy_tree(tmp_path)
    for path in (root / "benchmark" / "configs").glob("*.json"):
        cfg = json.loads(path.read_text())
        cfg.update(SMALL)
        if "index" in cfg:
            cfg["index"].update(SMALL_INDEX)
        path.write_text(json.dumps(cfg))
    return root
