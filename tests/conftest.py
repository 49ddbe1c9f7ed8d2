"""Test config: run all tests on CPU with 8 virtual devices.

Mirrors the reference's test strategy (SURVEY.md §4): multi-device tests run
against a virtual mesh the way pyraft's Dask tests use a multi-process
single-node cluster (python/raft/raft/test/conftest.py in the reference).
Env vars must be set before jax initializes.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO_ROOT)

# The suite's wall time is dominated by jit compiles that are identical run
# to run; share ci/run.sh's workspace compile cache so bare pytest
# invocations (the tier-1 verify command) stay inside their time budget.
# Same knobs and disable convention as ci/run.sh (set the dir empty to
# disable); must be set before jax initializes.
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR", os.path.join(_REPO_ROOT, ".jax_cache")
)
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")

import jax  # noqa: E402

# The config knob is authoritative where a platform plugin ignores
# JAX_PLATFORMS from the environment.
jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def _map_count() -> int:
    try:
        with open("/proc/self/maps", "rb") as f:
            return sum(chunk.count(b"\n")
                       for chunk in iter(lambda: f.read(1 << 20), b""))
    except OSError:          # non-Linux: no /proc, no map-count ceiling
        return 0


def pytest_runtest_teardown(item):
    # Every loaded XLA executable mmaps its code pages (~3 regions
    # each) and the kernel caps a process at vm.max_map_count (65530
    # by default). The full suite compiles/loads ~5k programs in one
    # process, crosses the ceiling around 92% in, and the next
    # compile or cache-deserialize segfaults inside XLA when mmap
    # fails — any subset passes, only the whole run dies. Dropping
    # the executable caches under pressure stays below the ceiling;
    # the persistent compile cache keeps the re-compiles cheap.
    if _map_count() > 45_000:
        import gc

        jax.clear_caches()
        gc.collect()


@pytest.fixture(scope="session")
def devices():
    return jax.devices()


@pytest.fixture(scope="session")
def mesh8():
    import jax.sharding

    devs = np.array(jax.devices()[:8])
    return jax.sharding.Mesh(devs, ("x",))


@pytest.fixture()
def rng_np():
    return np.random.default_rng(42)
