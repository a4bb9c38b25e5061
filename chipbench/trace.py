"""Profiler trace capture and its reduction to device busy time.

A run traces a few of its units (``Tracer``). The reduction reads the
``.xplane.pb`` the profiler writes: device operations from each TPU plane's
``XLA Ops`` line, moved onto the host's clock (``clock_offset_ns``), and the
benchmark's own host spans (``TraceAnnotation`` names starting with ``cb:``)
from the host plane. The
traced window runs from the first ``cb:unit`` span's start to the last one's
end. Busy time is the union of operation intervals inside the window,
averaged over the chips; the busy time inside a span is that union clipped to
the span.
"""
from __future__ import annotations

import bisect
import glob
import os
import tempfile
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

SPAN_PREFIX = "cb:"
UNIT = "cb:unit"
OPS_LINE = "XLA Ops"

Interval = Tuple[float, float]  # seconds, [start, end)


def span(name: str):
    """A benchmark span in the profiler's trace (nearly free when no trace is
    being taken)."""
    import jax

    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clipped_length(merged: List[Interval], lo: float, hi: float) -> float:
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


@dataclass
class Summary:
    """A reduced trace: per-device operation intervals and host spans."""

    ops: List[List[Tuple[str, float, float]]]  # per device: (name, start, end)
    spans: List[Tuple[str, float, float]]  # (name, start, end), cb: spans only
    _merged: List[List[Interval]] = field(default_factory=list, repr=False)

    def __post_init__(self):
        self._merged = [union([(s, e) for _, s, e in dev]) for dev in self.ops]
        units = [(s, e) for n, s, e in self.spans if n == UNIT]
        if not units or not self.ops:
            raise ValueError("trace holds no benchmark units or no device plane")
        self.lo = min(s for s, _ in units)
        self.hi = max(e for _, e in units)

    @property
    def window_s(self) -> float:
        return self.hi - self.lo

    def _busy(self, lo: float, hi: float) -> float:
        """Busy seconds in [lo, hi), averaged over the devices."""
        return sum(clipped_length(m, lo, hi) for m in self._merged) / len(self._merged)

    @property
    def busy_s(self) -> float:
        return self._busy(self.lo, self.hi)

    def count(self, name: str) -> int:
        return sum(1 for n, _, _ in self.spans if n == SPAN_PREFIX + name)

    def busy_in(self, name: str) -> float:
        """Device busy seconds inside every span of that name."""
        return sum(self._busy(s, e) for n, s, e in self.spans if n == SPAN_PREFIX + name)

    def host_in(self, name: str) -> float:
        """Host seconds inside every span of that name."""
        return sum(e - s for n, s, e in self.spans if n == SPAN_PREFIX + name)

    def _activity(self, t: float) -> str:
        """The innermost benchmark span open at ``t``."""
        best = None
        for n, s, e in self.spans:
            if s <= t < e and (best is None or e - s < best[1] - best[0]):
                best = (s, e, n)
        return best[2][len(SPAN_PREFIX):] if best else "outside spans"

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        """The device operations that took most time, and idle time in the
        window summed by what the host was doing (its innermost span)."""
        per_op: Dict[str, float] = defaultdict(float)
        for name, s, e in self.ops[0]:
            per_op[name] += max(0.0, min(e, self.hi) - max(s, self.lo))
        idle: Dict[str, float] = defaultdict(float)
        t = self.lo
        for s, e in self._merged[0] + [(self.hi, self.hi)]:
            s, e = max(s, self.lo), min(e, self.hi)
            if s > t:
                idle[self._activity((t + s) / 2)] += s - t
            t = max(t, e)
        order = lambda d: sorted(([k, v] for k, v in d.items()), key=lambda kv: -kv[1])[:top]
        return {"device_ops": order(per_op), "idle_gaps": order(idle)}


MODULES_LINE = "XLA Modules"
LAUNCH = "tpu::System::Execute"
COMPLETE = "CompleteCallbacks"


def _stat(ev, name):
    return dict(ev.stats).get(name)


def clock_offset_ns(device_plane, host_plane) -> float:
    """What to add to the device plane's times to put them on the host's
    clock. The device's clock is skewed against the host's (about a
    millisecond on a v5e), so each program execution bounds the skew: it
    cannot start before the host launched it, nor end after the host
    completed it (matched by run id). The middle of the tightest bounds is
    taken; with launches that cannot be paired one to one, the upper bound."""
    modules = sorted((ev.start_ns, ev.end_ns, _stat(ev, "run_id"))
                     for line in device_plane.lines if line.name == MODULES_LINE
                     for ev in line.events)
    done, launches = {}, []
    for line in host_plane.lines:
        for ev in line.events:
            if ev.name == COMPLETE:
                done[_stat(ev, "run_id")] = ev.start_ns
            elif ev.name == LAUNCH:
                launches.append(ev.start_ns)
    uppers = [done[r] - e for _, e, r in modules if r in done]
    if not uppers:
        return 0.0
    upper = min(uppers)
    if len(launches) == len(modules):
        lower = max(l - s for l, (s, _, _) in zip(sorted(launches), modules))
        if lower <= upper:
            return (lower + upper) / 2
    return upper


def _short(name: str) -> str:
    return name.split(" = ", 1)[0]


def read_xspace(profile) -> Summary:
    """Reduce a ``jax.profiler.ProfileData``: device operations, named by
    their program and instruction, shifted onto the host's clock."""
    planes = list(profile.planes)
    host = [p for p in planes if p.name == "/host:CPU"]
    ops, spans = [], []
    for plane in planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        shift = clock_offset_ns(plane, host[0]) if host else 0.0
        modules = sorted((ev.start_ns, ev.end_ns, ev.name.split("(", 1)[0])
                         for line in plane.lines if line.name == MODULES_LINE
                         for ev in line.events)
        starts = [m[0] for m in modules]
        dev = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                i = bisect.bisect_right(starts, ev.start_ns) - 1
                prog = modules[i][2] + "/" if i >= 0 and ev.start_ns < modules[i][1] else ""
                dev.append((prog + _short(ev.name), (ev.start_ns + shift) * 1e-9,
                            (ev.end_ns + shift) * 1e-9))
        ops.append(dev)
    for plane in host:
        for line in plane.lines:
            spans += [(ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                      for ev in line.events if ev.name.startswith(SPAN_PREFIX)]
    return Summary(ops=ops, spans=spans)


def read_file(path: str) -> Summary:
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        import gzip

        with gzip.open(path, "rb") as f:
            return read_xspace(ProfileData.from_serialized_xspace(f.read()))
    return read_xspace(ProfileData.from_file(path))


class Tracer:
    """Traces the device between ``start()`` and ``stop()`` into a temporary
    directory, which ``stop()`` reduces and deletes."""

    def __init__(self):
        self._dir = None

    def start(self):
        import jax

        self._dir = tempfile.TemporaryDirectory(prefix="chipbench-trace-")
        jax.profiler.start_trace(self._dir.name)

    def stop(self) -> Summary:
        import jax

        jax.profiler.stop_trace()
        try:
            files = glob.glob(os.path.join(self._dir.name, "plugins", "profile",
                                           "*", "*.xplane.pb"))
            if len(files) != 1:
                raise RuntimeError(f"expected one .xplane.pb, found {files}")
            return read_file(files[0])
        finally:
            self._dir.cleanup()
