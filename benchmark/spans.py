"""The harness's host spans: named ``jax.profiler.TraceAnnotation``s, so
that a traced run can say what the host was doing in each idle gap of the
device. Outside a trace they cost a few microseconds each."""

from __future__ import annotations

import jax


def span(name: str):
    """A host span named ``name`` on the profiler's timeline."""
    return jax.profiler.TraceAnnotation(name)
