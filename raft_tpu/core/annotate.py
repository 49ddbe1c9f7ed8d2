"""Profiler range annotations — analog of the reference NVTX layer.

Reference: cpp/include/raft/core/nvtx.hpp:48-91 and
common/detail/nvtx.hpp:23-206 (RAII ``nvtx::range``, push_range/pop_range,
per-domain colored ranges, compiled out when NVTX disabled). The TPU analog
uses ``jax.profiler``: a range is a ``TraceAnnotation`` on the profiler's
host timeline, the clock the device planes of the same capture share, so a
range can be set against the device's operations with no clock mapping.
Keyword stats (``annotate("serving.stage", batch_id=7)``) ride on the
event, which is how the ranges of one batch are tied together.

Host ranges do NOT enter ``jax.named_scope``: a scope entered on a host
thread would stamp its label into the metadata of any program compiled
inside it, so a compile during a capture would differ from one outside it
(and miss the persistent compile cache). Device code names its parts with
``jax.named_scope`` directly, at trace time (the grouped IVF program's
``ivf.*`` and the brute-force program's ``knn.*`` scopes;
docs/observability.md "Spans and scopes").

Like the reference's ``NVTX_ENABLED`` compile-out, ranges honor a GLOBAL
enable flag: when profiling is off (the default — set ``RAFT_TPU_PROFILE=1``
to force it on), :func:`annotate` returns one shared no-op context and
:func:`push_range` returns at once — no ``TraceAnnotation``, no
``ExitStack``, no stack append — so the hot serving path pays one
list load per range (tests/test_obs.py pins the no-allocation claim).
:func:`start_trace` flips the flag on for the duration of a capture (and
:func:`stop_trace` restores it), so an SLO-triggered capture
(:class:`raft_tpu.obs.ProfileTrigger`) sees every range without anyone
paying for them between captures.
"""

from __future__ import annotations

import contextlib
import os
from typing import Any, ContextManager, List

import jax

from raft_tpu.core import logger

# the global range-enable gate (the NVTX_ENABLED analog): a list cell so
# every reader shares it by reference. Default off — ranges cost nothing
# until a trace capture (or RAFT_TPU_PROFILE=1) wants them.
_ENV_DEFAULT: bool = (
    os.environ.get("RAFT_TPU_PROFILE", "").strip().lower()
    in ("1", "on", "true", "yes")
)
_ENABLED: List[bool] = [_ENV_DEFAULT]
_stack: List[contextlib.ExitStack] = []
# profiling state before start_trace flipped it, restored by stop_trace
_pre_trace: List[bool] = []
# what annotate() returns while profiling is off: one shared no-op
_OFF: ContextManager[None] = contextlib.nullcontext()


def profiling_enabled() -> bool:
    """Are ranges currently being emitted?"""
    return _ENABLED[0]


def set_profiling(on: bool) -> bool:
    """Flip the global range gate; returns the PREVIOUS state. Ranges
    pushed while disabled are not tracked — a ``pop_range`` crossing an
    enable flip logs instead of popping someone else's range."""
    prev = _ENABLED[0]
    _ENABLED[0] = bool(on)
    return prev


def annotate(name: str, *args: Any, **stats: Any) -> ContextManager[Any]:
    """RAII-style range: a context manager.

    ``args`` are %-formatted into ``name`` like the reference's printf-style
    range names (nvtx.hpp:54 ``range(const char* format, Args... args)``);
    ``stats`` are attached to the event as trace stats (numbers or
    strings). Entering the range yields the ``TraceAnnotation``, whose
    ``set_metadata(**stats)`` adds stats known only inside the range, or
    None while profiling is off — then nothing is constructed.
    """
    if not _ENABLED[0]:
        return _OFF
    label = name % args if args else name
    return jax.profiler.TraceAnnotation(label, **stats)


def push_range(name: str, *args) -> None:
    """Imperative begin (reference nvtx.hpp push_range). A true no-op —
    nothing allocated, nothing stacked — while profiling is off."""
    if not _ENABLED[0]:
        return
    label = name % args if args else name
    es = contextlib.ExitStack()
    es.enter_context(jax.profiler.TraceAnnotation(label))
    _stack.append(es)


def pop_range() -> None:
    """Imperative end (reference nvtx.hpp pop_range). Popping an empty
    stack — an unbalanced pop, or ranges pushed while profiling was
    disabled — is a LOUD no-op (debug log), never an exception: range
    bookkeeping must not take down the path it annotates."""
    if _stack:
        _stack.pop().close()
    else:
        logger.debug(
            "pop_range: range stack empty (unbalanced pop, or the "
            "matching push_range ran while profiling was disabled)"
        )


def start_trace(log_dir: str, options: Any = None) -> None:
    """Start an XLA profiler trace capture (output viewable in
    TensorBoard) and enable range emission for its duration.
    ``options`` is a ``jax.profiler.ProfileOptions`` (the profiler's
    defaults when None). The profiler starts FIRST: if it refuses (a
    capture is already running), the range gate and its restore stack
    are untouched — a failed start must not leave every later range
    permanently paid for."""
    if options is None:
        jax.profiler.start_trace(log_dir)
    else:
        jax.profiler.start_trace(log_dir, profiler_options=options)
    _pre_trace.append(set_profiling(True))


def stop_trace() -> None:
    """Stop the capture and restore the range gate to its pre-capture
    state (an explicitly-enabled process stays enabled). An UNBALANCED
    stop — a capture someone started through ``jax.profiler`` directly
    — falls back to the env-derived default, never a hard False: a
    ``RAFT_TPU_PROFILE=1`` process must not be silently disabled by
    one stray stop."""
    try:
        jax.profiler.stop_trace()
    finally:
        set_profiling(_pre_trace.pop() if _pre_trace else _ENV_DEFAULT)
