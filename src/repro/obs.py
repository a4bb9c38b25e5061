"""The program's host spans in the profiler's trace.

``span(name)`` opens a ``jax.profiler.TraceAnnotation`` named
``repro:<name>``. While a profiler trace is being taken it lands on the
host plane, beside the device operations; otherwise it costs about a
microsecond. Device time is named by ``jax.named_scope`` in the model code
instead, which only adds metadata to the compiled operations.
"""
from __future__ import annotations

import jax

PREFIX = "repro:"


def span(name: str):
    """A host span of the program, ``repro:<name>`` in the trace."""
    return jax.profiler.TraceAnnotation(PREFIX + name)
