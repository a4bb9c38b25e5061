"""Collective operations in a reduced trace (``chipbench.trace.Summary``).

An operation is a collective when its name holds one of ``KINDS``: the
synchronous form (``all-reduce.7``), the two halves of an asynchronous one
(``all-reduce-start.2``, ``all-reduce-done.2``), and a fusion named after the
collective it holds (``all-gather-fusion.1``).
"""
from __future__ import annotations

from chipbench import trace

KINDS = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute", "all-to-all")


def is_collective(name: str) -> bool:
    """``name`` as the trace reduction gives it, ``<program>/<operation>``."""
    op = name.rsplit("/", 1)[-1]
    return any(k in op for k in KINDS)


def busy_in(summary, span: str) -> float:
    """Device seconds of collective operations inside every benchmark span of
    that name, averaged over the devices."""
    spans = [(s, e) for n, s, e in summary.spans if n == trace.SPAN_PREFIX + span]
    total = 0.0
    for dev in summary.ops:
        merged = trace.union([(s, e) for name, s, e in dev if is_collective(name)])
        total += sum(trace.clipped_length(merged, s, e) for s, e in spans)
    return total / len(summary.ops)
