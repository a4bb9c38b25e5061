#!/usr/bin/env python3
"""What the program marks in a profiler trace, read beside what
``chipbench/trace.py`` reads.

``trace.py`` reduces a ``.xplane.pb`` to device operations and the
benchmark's own ``cb:`` spans. This module reads two more things from the
same file and gives them in a ``ScopedSummary``, a ``trace.Summary`` whose
existing fields and numbers are unchanged:

- each device operation's scope path: the ``tf_op`` stat of its event
  metadata (``<path>:<op type>``), which carries the ``jax.named_scope``
  names around the operation, as in
  ``jit(train_step)/transpose(jvp(encoder))/while/body/attn/sdpa/dot_general``;
- the program's host spans (``TraceAnnotation`` names starting with
  ``repro:``, from ``repro.obs.span``), with the host thread each ran on.

The benchmark's ``Tracer`` does not call this reading yet (``PERF.md`` §7
names the edit). Until then it runs a cell as ``run.py --trace 1`` does,
with this reading in place of ``trace.read_file``:

    python3 chipbench/scopes.py --workload <cell> --seed <n> --seconds <s> --trace 1

and prints, after the result line (whose ``breakdown`` then also holds
``device_scopes`` and ``idle_gaps_inner``), one JSON line with the readers
of ``PROGRAM_METRICS`` under ``chipbench/metrics/``.
"""
from __future__ import annotations

import json
import sys
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Tuple

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from chipbench import trace  # noqa: E402

PROGRAM_PREFIX = "repro:"  # the program's spans (``repro.obs.span``)
# the program's named scopes at model layer boundaries, as PERF.md lists them
MODEL_SCOPES = ("embed", "encoder", "logits", "loss", "attn", "xattn", "qkv", "kv_cache",
                "sdpa", "out", "mlp", "moe", "mamba", "rwkv", "optimizer")
UNSCOPED = "unscoped"
# per-layer readers under chipbench/metrics/ that read the program's marks
PROGRAM_METRICS = ("sdpa_ms.decode", "sdpa_ms.prefill", "sdpa_ms.train",
                   "queue_wait_ms.train", "to_device_ms.train", "make_batch_ms.train")


def scope_components(scope: str) -> Tuple[str, ...]:
    """The components of a scope path, each unwrapped from the transforms
    around it: ``jit(train_step)/transpose(jvp(encoder))/while/body/attn``
    gives ``train_step, encoder, while, body, attn`` (a scope opened directly
    under ``jax.grad`` is named ``jvp(<scope>)``, its backward
    ``transpose(jvp(<scope>))``)."""
    out = []
    for c in scope.split("/"):
        while c.endswith(")") and "(" in c and len(c) > c.index("(") + 2:
            c = c[c.index("(") + 1:-1]
        out.append(c)
    return tuple(out)


def own_time(ops: List[trace.Interval]) -> List[float]:
    """Each operation's time less the part of it that operations starting
    inside it cover: a device's ``XLA Ops`` line holds a loop's event and,
    nested in it, the events of its body."""
    own = [max(0.0, e - s) for s, e in ops]
    stack: List[int] = []
    for i in sorted(range(len(ops)), key=lambda i: (ops[i][0], -ops[i][1])):
        s, e = ops[i]
        while stack and ops[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= max(0.0, min(e, ops[stack[-1]][1]) - s)
        stack.append(i)
    return own


@dataclass
class ScopedSummary(trace.Summary):
    """A reduced trace with the program's marks."""

    # per device, parallel to ``ops``: each operation's scope path, "" if none
    scopes: List[List[str]] = field(default_factory=list)
    # (name, start, end, host thread), repro: spans, "#..." arguments stripped
    program_spans: List[Tuple[str, float, float, int]] = field(default_factory=list)
    span_threads: Tuple[int, ...] = ()  # host threads that hold cb: spans

    def __post_init__(self):
        super().__post_init__()
        if not self.scopes:
            self.scopes = [[""] * len(dev) for dev in self.ops]
        self._components = {sc: scope_components(sc)
                            for dev in self.scopes for sc in set(dev)}

    def busy_in_scope(self, component: str, name: str) -> float:
        """Device busy seconds of the operations whose scope path has
        ``component`` as a whole component, inside every ``cb:`` span of that
        name, averaged over the devices."""
        total = 0.0
        for dev, scopes in zip(self.ops, self.scopes):
            merged = trace.union([(s, e) for (_, s, e), sc in zip(dev, scopes)
                                  if component in self._components[sc]])
            total += sum(trace.clipped_length(merged, s, e)
                         for n, s, e in self.spans if n == trace.SPAN_PREFIX + name)
        return total / len(self.ops)

    def _program_in_window(self, name: str):
        return [(s, e) for n, s, e, _ in self.program_spans
                if n == PROGRAM_PREFIX + name and self.lo <= s and e <= self.hi]

    def program_host_in(self, name: str) -> float:
        """Host seconds inside the program's spans of that name that lie in
        the traced window."""
        return sum(e - s for s, e in self._program_in_window(name))

    def program_count(self, name: str) -> int:
        return len(self._program_in_window(name))

    def _inner_activity(self, t: float) -> str:
        """The innermost span of either kind open at ``t`` on a thread that
        holds the benchmark's spans (the program's spans on other threads,
        such as the input pipeline's producer, are not what it waited in)."""
        best = None
        for n, s, e in self.spans + [sp[:3] for sp in self.program_spans
                                     if sp[3] in self.span_threads]:
            if s <= t < e and (best is None or e - s < best[1] - best[0]):
                best = (s, e, n)
        return best[2].split(":", 1)[1] if best else "outside spans"

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        """``trace.Summary.breakdown``, and: ``device_scopes``, device seconds
        in the window by the first model scope on each operation's path, each
        operation counting its own time; ``idle_gaps_inner``, idle time named
        by the innermost span of either kind."""
        per_scope: Dict[str, float] = defaultdict(float)
        clipped = [(max(s, self.lo), min(e, self.hi)) for _, s, e in self.ops[0]]
        for t, sc in zip(own_time(clipped), self.scopes[0]):
            per_scope[next((c for c in self._components[sc] if c in MODEL_SCOPES), UNSCOPED)] += t
        idle: Dict[str, float] = defaultdict(float)
        t = self.lo
        for s, e in self._merged[0] + [(self.hi, self.hi)]:
            s, e = max(s, self.lo), min(e, self.hi)
            if s > t:
                idle[self._inner_activity((t + s) / 2)] += s - t
            t = max(t, e)
        order = lambda d: sorted(([k, v] for k, v in d.items()), key=lambda kv: -kv[1])
        return dict(super().breakdown(top), device_scopes=order(per_scope),
                    idle_gaps_inner=order(idle)[:top])


def _varint(b: bytes, i: int) -> Tuple[int, int]:
    r = s = 0
    while True:
        x = b[i]
        i += 1
        r |= (x & 0x7F) << s
        if x < 0x80:
            return r, i
        s += 7


def _fields(b: bytes, lo: int, hi: int):
    """(field number, value) of the protobuf message in ``b[lo:hi]``; a
    length-delimited value is given as its (start, end), fixed-width ones as
    None."""
    i = lo
    while i < hi:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"unexpected protobuf wire type {wire}")
        yield key >> 3, v


def xla_op_scopes(raw: bytes) -> Dict[str, List[str]]:
    """For each TPU plane of a serialized XSpace, the ``tf_op`` stat of the
    event metadata of each ``XLA Ops`` event, in the events' order ("" where
    there is none). ``ProfileData`` does not expose event-metadata stats, so
    this reads the wire format of the profiler's ``xplane.proto``: XSpace
    planes 1; XPlane name 2, lines 3, event_metadata 4 and stat_metadata 5
    (map entries: key 1, value 2); XLine name 2, events 4; XEvent
    metadata_id 1; XEventMetadata stats 5; XStatMetadata name 2; XStat
    metadata_id 1, str_value 5, ref_value 7."""
    text = lambda v: raw[v[0]:v[1]].decode("utf-8", "replace")
    out: Dict[str, List[str]] = {}
    for f, plane in _fields(raw, 0, len(raw)):
        if f != 1:
            continue
        name, lines, emeta, smeta = "", [], {}, {}
        for g, v in _fields(raw, *plane):
            if g == 2:
                name = text(v)
            elif g == 3:
                lines.append(v)
            elif g in (4, 5):
                entry = dict(_fields(raw, *v))
                (emeta if g == 4 else smeta)[entry.get(1, 0)] = entry.get(2)
        if not name.startswith("/device:TPU:"):
            continue
        stat_names = {k: text(dict(_fields(raw, *v)).get(2, (0, 0)))
                      for k, v in smeta.items() if v}
        tf_op = next((k for k, n in stat_names.items() if n == "tf_op"), None)
        op_of = {}
        for k, v in emeta.items():
            for g, st in _fields(raw, *v) if v else ():
                if g != 5:
                    continue
                stat = dict(_fields(raw, *st))
                if stat.get(1, 0) == tf_op:
                    op_of[k] = text(stat[5]) if 5 in stat else stat_names.get(stat.get(7), "")
        ops = out.setdefault(name, [])
        for line in lines:
            items = list(_fields(raw, *line))
            if any(g == 2 and text(v) == trace.OPS_LINE for g, v in items):
                ops += [op_of.get(dict(_fields(raw, *ev)).get(1, 0), "")
                        for g, ev in items if g == 4]
    return out


def read_xspace(profile, tf_ops: Dict[str, List[str]]) -> ScopedSummary:
    """``trace.read_xspace`` of a ``jax.profiler.ProfileData``, with each
    operation's scope path from ``tf_ops`` (``xla_op_scopes`` of the same
    trace; a plane whose count of operations differs is left unscoped) and
    the program's host spans."""
    base = trace.read_xspace(profile)
    planes = [p.name for p in profile.planes if p.name.startswith("/device:TPU:")]
    scopes = []
    for name, dev in zip(planes, base.ops):
        found = tf_ops.get(name, [])
        # the scope path of a tf_op stat "<path>:<op type>"
        scopes.append([t.rsplit(":", 1)[0] for t in found] if len(found) == len(dev)
                      else [""] * len(dev))
    program_spans, span_threads = [], set()
    for plane in profile.planes:
        if plane.name != "/host:CPU":
            continue
        for thread, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(trace.SPAN_PREFIX):
                    span_threads.add(thread)
                elif ev.name.startswith(PROGRAM_PREFIX):
                    program_spans.append((ev.name.split("#", 1)[0], ev.start_ns * 1e-9,
                                          ev.end_ns * 1e-9, thread))
    return ScopedSummary(ops=base.ops, spans=base.spans, scopes=scopes,
                         program_spans=program_spans, span_threads=tuple(sorted(span_threads)))


def read_file(path: str) -> ScopedSummary:
    """``trace.read_file`` with the program's marks."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        import gzip

        with gzip.open(path, "rb") as f:
            raw = f.read()
    else:
        with open(path, "rb") as f:
            raw = f.read()
    return read_xspace(ProfileData.from_serialized_xspace(raw), xla_op_scopes(raw))


def main(argv=None) -> int:
    from chipbench import run

    read = {}

    def read_scoped(path):
        read["summary"] = read_file(path)
        return read["summary"]

    trace.read_file = read_scoped  # what the benchmark's Tracer reads with, in this process
    rc = run.main(argv)
    if "summary" in read:
        reading = run.Reading(read["summary"], {}, {}, 0.0)
        values = {m: run.load_module(run.BENCH / "metrics" / f"{m}.py").read(reading)
                  for m in PROGRAM_METRICS}
        print(json.dumps({"program_metrics": {k: v for k, v in values.items()
                                              if v is not None}}), flush=True)
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
