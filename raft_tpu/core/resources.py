"""Resource handle — TPU-native analog of ``raft::handle_t``.

The reference handle (cpp/include/raft/core/handle.hpp:54-335) carries CUDA
streams, a stream pool, lazily-created cuBLAS/cuSOLVER/cuSPARSE handles, device
properties, and an injected communicator. On TPU, XLA owns scheduling and
kernel libraries, so the handle reduces to:

* the target device(s) and an optional ``jax.sharding.Mesh`` (the comms slot:
  reference handle.hpp:239-264 ``set_comms``/``get_comms``);
* compile/runtime policy: default float dtype, matmul precision, whether to
  donate buffers;
* a stream-pool analog: independent *dispatch lanes* are expressed simply as
  separate ``jax.jit`` dispatches (async by default) — we keep an integer
  ``n_lanes`` for API parity with ``get_stream_pool_size``.

Everything is cheap, immutable-ish, and safe to share across algorithms, like
the reference object.
"""

from __future__ import annotations

import dataclasses
import os
import threading
from typing import Any, Optional

import jax
import numpy as np


@dataclasses.dataclass
class Resources:
    """Per-algorithm-invocation resource context.

    Parameters mirror the semantics (not the fields) of ``raft::handle_t``.

    Attributes
    ----------
    device : the primary jax device computations land on.
    mesh : optional device mesh used by multi-chip algorithms; the analog of
        the injected ``comms_t`` (reference core/handle.hpp:239).
    sub_meshes : named sub-communicators, analog of
        ``set_subcomm/get_subcomm`` (reference core/handle.hpp:252-262).
    dtype : default floating dtype for algorithm internals.
    matmul_precision : passed to ``jax.lax`` dot ops ("default" | "float32" |
        "bfloat16_3x" ...). f32 accumulate on MXU is always used via
        ``preferred_element_type``.
    n_lanes : stream-pool-size analog (reference handle.hpp:158-237); used by
        batched algorithms to decide how many independent dispatches to keep
        in flight.
    compilation_cache_dir : opt-in path for JAX's persistent compilation
        cache. When set, :func:`enable_compilation_cache` runs with this
        path (``JAX_COMPILATION_CACHE_DIR`` takes precedence when set) —
        the cache is process-global, so EVERY builder/search entry
        (all of them jit-compiled programs) transparently reads and writes
        it from then on: a fresh process rebuilding a same-shape index pays
        executable deserialization instead of XLA compilation (the serving
        cold-start path, docs/serving.md "Warm start").
    """

    device: Any = None
    mesh: Optional[jax.sharding.Mesh] = None
    sub_meshes: dict = dataclasses.field(default_factory=dict)
    dtype: Any = np.float32
    matmul_precision: str = "highest"
    n_lanes: int = 1
    compilation_cache_dir: Optional[str] = None

    def __post_init__(self):
        if self.device is None:
            self.device = jax.devices()[0]
        if self.compilation_cache_dir is not None:
            enable_compilation_cache(self.compilation_cache_dir)

    # -- comms slot ---------------------------------------------------------
    def set_mesh(self, mesh: jax.sharding.Mesh) -> None:
        """Inject the communicator (analog of handle.set_comms)."""
        self.mesh = mesh

    def get_mesh(self) -> jax.sharding.Mesh:
        if self.mesh is None:
            raise RuntimeError(
                "No mesh set on Resources (analog of 'ERROR: communicator was not initialized')"
            )
        return self.mesh

    @property
    def has_mesh(self) -> bool:
        return self.mesh is not None

    def set_sub_mesh(self, key: str, mesh: jax.sharding.Mesh) -> None:
        self.sub_meshes[key] = mesh

    def get_sub_mesh(self, key: str) -> jax.sharding.Mesh:
        return self.sub_meshes[key]

    # -- stream-pool parity --------------------------------------------------
    def get_n_lanes(self) -> int:
        return max(1, int(self.n_lanes))

    # -- device properties ---------------------------------------------------
    def device_kind(self) -> str:
        return self.device.device_kind

    def is_tpu(self) -> bool:
        return self.device.platform == "tpu"

    def sync(self, *arrays) -> None:
        """Block until the given arrays (or, with no args, all dispatched
        side-effecting computations) are done.

        Analog of ``handle.sync_stream()``. JAX gives no global barrier over
        *pure* in-flight computations that you hold no reference to — pass
        the outputs you need ordered: ``res.sync(out)``.
        """
        if arrays:
            jax.block_until_ready(arrays)
        else:
            jax.effects_barrier()


# Backwards-compatible alias mirroring raft 22.08's rename handle_t -> device_resources
DeviceResources = Resources

_cache_lock = threading.Lock()
_cache_dir_enabled: Optional[str] = None


def _resolve_cache_dir(path: Optional[str] = None) -> str:
    """The cache directory :func:`enable_compilation_cache` uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if path:
        return path
    return os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))),
        ".jax_cache",
    )


def enable_compilation_cache(
    path: Optional[str] = None,
    *,
    min_compile_time_secs: float = 0.0,
    min_entry_size_bytes: int = -1,
) -> str:
    """Enable JAX's persistent compilation cache (idempotent) at
    ``JAX_COMPILATION_CACHE_DIR`` when it is set, else ``path`` when
    given, else ``<repo>/.jax_cache`` (a fixed path: the path is part of
    the cache key, so a directory that moves never hits) — and return
    the directory.

    Every jitted program compiled after this call — index builds, search
    programs, the shard_map mesh programs — is serialized there and
    deserialized by later processes instead of recompiled. The r5
    bench showed compile, not compute, dominating builds (cold 125-250 s
    vs 1.6-15 s warm); this turns that cold start into a disk read.

    Two defaults differ deliberately from JAX's:

    * ``min_compile_time_secs=0``: JAX skips caching programs that
      compiled in under 1 s, but this library dispatches many small
      helper programs per build whose compiles add up;
    * ``min_entry_size_bytes=-1``: no size floor.

    Thread-safe; re-enabling with the same directory is a no-op, a
    different one switches the cache over (after other JAX work too: the
    cache JAX already opened is reset).
    """
    global _cache_dir_enabled
    path = _resolve_cache_dir(path)
    with _cache_lock:
        if _cache_dir_enabled == path:
            return path
        jax.config.update("jax_compilation_cache_dir", path)
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs",
            float(min_compile_time_secs),
        )
        jax.config.update(
            "jax_persistent_cache_min_entry_size_bytes",
            int(min_entry_size_bytes),
        )
        # JAX opens the cache once per process at its first compile and
        # keeps that directory: drop it so the next compile opens `path`
        from raft_tpu import compat

        compat.compilation_cache_reset()
        _cache_dir_enabled = path
        return path


def compilation_cache_dir() -> Optional[str]:
    """The persistent-cache path enabled through this module, or None."""
    with _cache_lock:
        return _cache_dir_enabled


_default_lock = threading.Lock()
_default_resources: Optional[Resources] = None


def get_default_resources() -> Resources:
    """Process-wide default handle (lazily created), for API convenience.

    The reference requires an explicit handle everywhere; we accept ``None``
    in public APIs and fall back to this.
    """
    global _default_resources
    with _default_lock:
        if _default_resources is None:
            _default_resources = Resources()
        return _default_resources


def ensure_resources(res: Optional[Resources]) -> Resources:
    return res if res is not None else get_default_resources()
