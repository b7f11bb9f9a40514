"""Host spans on the profiler's clock (core layer: imports no jax).

``span(name, **meta)`` is ``jax.profiler.TraceAnnotation(name, **meta)``
when the process has imported jax, so the span lands in a profiler trace on
the same clock as the device's operations; with no profiler running it
costs one TraceMe check.  In a process that never imported jax (the
spawn-pool analysis children) it is a no-op, and the core layer stays
importable without jax.  docs/performance.md lists the spans and what each
covers.
"""
from __future__ import annotations

import sys


class _NoSpan:
    """Stands in for a TraceAnnotation where jax is not loaded."""

    def __enter__(self) -> "_NoSpan":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def set_metadata(self, **meta) -> None:
        return None


_NO_SPAN = _NoSpan()


def span(name: str, **meta):
    """A context manager that records ``name`` (with ``meta`` as its
    arguments) as a host span of the calling thread.  The returned object's
    ``set_metadata(**meta)`` adds arguments before the span closes."""
    profiler = sys.modules.get("jax.profiler")
    if profiler is None:
        return _NO_SPAN
    return profiler.TraceAnnotation(name, **meta)
