"""JAX version-compatibility shim — the single sanctioned access point for
version-sensitive JAX APIs.

JAX moves symbols between releases (``jax.experimental.shard_map.shard_map``
graduated to ``jax.shard_map``; ``jax.tree_map`` was removed in favour of
``jax.tree.map``). Direct use of any spelling pins the codebase to one JAX
release. This module resolves each symbol against the installed JAX (0.9,
``pyproject.toml``) at import time, from a declarative :data:`COMPAT_TABLE`
that the static analyzer (``raft_tpu.analysis``, rule ``api-compat``)
consumes to flag direct spellings at lint time. The analog in the reference
RAFT is the pinned-RAPIDS-version dependency wall; here the wall is one
table.

Policy (enforced by ``python -m raft_tpu.analysis``):

* library code imports version-sensitive symbols from ``raft_tpu.compat``,
  never from their ``jax...`` home directly;
* adding a new version-sensitive symbol means adding a ``CompatEntry`` (the
  linter picks it up automatically from the table's ``banned`` spellings).

Resolution is by dotted-path string (``importlib`` + ``getattr``), so this
module itself never spells a banned attribute access in AST form.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Callable, Optional, Tuple

import jax

__all__ = [
    "COMPAT_TABLE",
    "CompatEntry",
    "resolve",
    "shard_map",
    "axis_size",
    "tree_map",
    "register_dataclass",
    "pure_callback",
    "io_callback",
    "compilation_cache_reset",
    "persistent_cache_min_compile_time",
]


@dataclasses.dataclass(frozen=True)
class CompatEntry:
    """One version-sensitive symbol: how to find it, and how not to spell it.

    ``candidates`` are dotted paths tried in order against the installed JAX
    (first hit wins). ``banned`` are the dotted spellings the ``api-compat``
    lint rule flags in library code — every candidate plus removed aliases.
    """

    name: str                      # attribute exposed on raft_tpu.compat
    candidates: Tuple[str, ...]    # dotted paths, newest spelling first
    banned: Tuple[str, ...]        # spellings jaxlint flags at call sites
    reason: str                    # one-line rationale shown in lint output


COMPAT_TABLE: Tuple[CompatEntry, ...] = (
    CompatEntry(
        name="shard_map",
        candidates=("jax.shard_map",),
        banned=(
            "jax.shard_map",
            "jax.experimental.shard_map.shard_map",
            "jax.experimental.shard_map",
        ),
        reason="graduated from jax.experimental.shard_map in JAX 0.6 (its "
               "replication-check kwarg is check_vma); route through "
               "compat so the next move is a one-line table edit",
    ),
    CompatEntry(
        name="axis_size",
        candidates=("jax.lax.axis_size",),
        banned=(
            "jax.lax.axis_size",
        ),
        reason="static mesh-axis size inside a traced region; its home "
               "has moved before (jax.core.axis_frame on 0.4)",
    ),
    CompatEntry(
        name="tree_map",
        candidates=("jax.tree.map",),
        banned=(
            "jax.tree_map",
            "jax.tree_multimap",
        ),
        reason="jax.tree_map was deprecated in 0.4.25 and removed in 0.6",
    ),
    CompatEntry(
        name="register_dataclass",
        candidates=("jax.tree_util.register_dataclass",),
        banned=(
            "jax.tree_util.register_dataclass",
        ),
        reason="its signature is still evolving (drop_fields, auto field "
               "inference); route through compat so a shim has one place "
               "to land",
    ),
    CompatEntry(
        name="pure_callback",
        candidates=("jax.pure_callback",),
        banned=(
            "jax.experimental.pure_callback",
        ),
        reason="graduated from jax.experimental in 0.4.27; the experimental "
               "alias is removed in newer releases",
    ),
    CompatEntry(
        name="compilation_cache_reset",
        candidates=(
            "jax.experimental.compilation_cache.compilation_cache.reset_cache",
        ),
        banned=(
            "jax.experimental.compilation_cache.compilation_cache",
            "jax._src.compilation_cache",
        ),
        reason="JAX opens the persistent cache once per process and keeps "
               "its directory; switching directories (core/resources.py "
               "enable_compilation_cache) needs reset_cache(), which lives "
               "under experimental — route through compat so the spelling "
               "has one home",
    ),
    CompatEntry(
        name="persistent_cache_min_compile_time",
        candidates=("jax._src.config.persistent_cache_min_compile_time_secs",),
        banned=(
            "jax._src.config.persistent_cache_min_compile_time_secs",
        ),
        reason="a thread-local context for the persistent cache's write "
               "threshold has no public spelling; keep one program out of "
               "the cache without touching the process-wide setting",
    ),
    CompatEntry(
        name="io_callback",
        candidates=("jax.experimental.io_callback",),
        banned=(
            "jax.experimental.io_callback",
        ),
        reason="still experimental — isolate the spelling here so its "
               "eventual graduation is a one-line table edit",
    ),
)


def _lookup(dotted: str) -> Any:
    """Resolve a dotted path against installed modules, or raise
    AttributeError/ImportError. Tries the longest importable module prefix,
    then getattrs down the remainder."""
    parts = dotted.split(".")
    obj: Any = None
    err: Optional[Exception] = None
    for split in range(len(parts), 0, -1):
        mod_name = ".".join(parts[:split])
        try:
            obj = importlib.import_module(mod_name)
        except ImportError as e:
            err = e
            continue
        for attr in parts[split:]:
            obj = getattr(obj, attr)  # AttributeError propagates to caller
        return obj
    raise AttributeError(f"cannot resolve {dotted!r}: {err}")


def resolve(name: str) -> Any:
    """Resolve a :data:`COMPAT_TABLE` entry by name against installed JAX.

    Returns the first available candidate; raises AttributeError naming
    every candidate tried when none resolves (a genuinely incompatible JAX).
    """
    for entry in COMPAT_TABLE:
        if entry.name == name:
            break
    else:
        raise KeyError(f"no compat entry named {name!r}")
    tried = []
    for dotted in entry.candidates:
        try:
            return _lookup(dotted)
        except (AttributeError, ImportError) as e:
            tried.append(f"{dotted} ({e.__class__.__name__})")
    raise AttributeError(
        f"compat: none of the candidate spellings for {name!r} exist on "
        f"jax=={jax.__version__}: {', '.join(tried)}"
    )


_shard_map_impl: Callable = resolve("shard_map")


def shard_map(f, *, mesh, in_specs, out_specs,
              check_vma: Optional[bool] = None, **kwargs):
    """``shard_map`` through its one sanctioned spelling; extra kwargs pass
    through untouched."""
    if check_vma is not None:
        kwargs["check_vma"] = check_vma
    return _shard_map_impl(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, **kwargs
    )


_axis_size_impl: Callable = resolve("axis_size")


def axis_size(axis) -> int:
    """Static size of a named mesh axis (or product over an axis tuple),
    callable from inside a traced region."""
    return int(_axis_size_impl(axis))


tree_map: Callable = resolve("tree_map")
register_dataclass: Callable = resolve("register_dataclass")
pure_callback: Callable = resolve("pure_callback")
io_callback: Callable = resolve("io_callback")
compilation_cache_reset: Callable = resolve("compilation_cache_reset")
persistent_cache_min_compile_time: Callable = resolve(
    "persistent_cache_min_compile_time"
)
