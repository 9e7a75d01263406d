"""Reduce a profiler trace to the numbers the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into
plain events: the device's operations (line ``XLA Ops`` of each
``/device:...`` plane), its programs (line ``XLA Modules``) and the
harness's host spans (``jax.profiler.TraceAnnotation`` names that start
with ``bench.``). The rest is arithmetic on intervals, kept here so that
every later change computes the same number the same way.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Sequence, Tuple

HOST_PREFIX = "bench."
FRAME = "bench.window"  # marks the traced window; never blamed for a gap
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float  # seconds, on the trace's clock
    end: float
    device: int = 0


@dataclasses.dataclass
class Trace:
    ops: List[Event]
    modules: List[Event]
    host: List[Event]
    n_devices: int

    def host_span(self, name: str) -> Tuple[float, float]:
        """First and last moment of the host spans called ``name``."""
        spans = [e for e in self.host if e.name == name]
        if not spans:
            raise KeyError(f"no host span {name!r} in the trace")
        return min(e.start for e in spans), max(e.end for e in spans)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: List[Event] = []
    modules: List[Event] = []
    host: List[Event] = []
    devices = 0
    for plane in data.planes:
        lines = {line.name: line for line in plane.lines}
        if plane.name.startswith("/device:") and OPS_LINE in lines:
            # a chip's plane; the device has planes without operations too
            dev = devices
            devices += 1
            ops.extend(_events(lines[OPS_LINE], dev))
            if MODULES_LINE in lines:
                modules.extend(_events(lines[MODULES_LINE], dev))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(
                    Event(e.name, e.start_ns * 1e-9,
                          (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events
                    if e.name.startswith(HOST_PREFIX)
                )
    return Trace(ops, modules, host, devices)


def _events(line, dev: int) -> Iterable[Event]:
    for e in line.events:
        start = e.start_ns * 1e-9
        yield Event(op_name(e.name), start, start + e.duration_ns * 1e-9, dev)


def op_name(text: str) -> str:
    """An operation's name: a device event may carry the whole HLO
    instruction (``%fusion.3 = bf16[...] fusion(...)``); keep ``%fusion.3``."""
    return text.split(" = ", 1)[0]


def clip(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    """Events cut to [lo, hi]; those wholly outside are dropped."""
    out = []
    for e in events:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            out.append(dataclasses.replace(e, start=s, end=t))
    return out


def merge(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """The union of intervals, as sorted disjoint intervals."""
    out: List[List[float]] = []
    for s, t in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [(s, t) for s, t in out]


def busy_s(trace: Trace, lo: float, hi: float) -> float:
    """Seconds in [lo, hi] in which an operation ran, averaged over devices."""
    per_dev: Dict[int, List[Tuple[float, float]]] = collections.defaultdict(list)
    for e in clip(trace.ops, lo, hi):
        per_dev[e.device].append((e.start, e.end))
    if trace.n_devices == 0:
        return 0.0
    total = sum(t - s for iv in per_dev.values() for s, t in merge(iv))
    return total / trace.n_devices


def idle_gaps(trace: Trace, lo: float, hi: float, device: int = 0
              ) -> List[Tuple[float, float]]:
    """Intervals of [lo, hi] in which ``device`` ran no operation."""
    busy = merge((e.start, e.end) for e in clip(trace.ops, lo, hi)
                 if e.device == device)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def gaps_by_host(trace: Trace, lo: float, hi: float, top: int = 10
                 ) -> List[List]:
    """Idle seconds on device 0 by what the host was doing: each part of a
    gap goes to the host span over it (the harness's spans do not nest,
    apart from the frame), the rest to "no host span"; largest first."""
    spans = [h for h in clip(trace.host, lo, hi) if h.name != FRAME]
    totals: Dict[str, float] = collections.defaultdict(float)
    for s, t in idle_gaps(trace, lo, hi):
        covered = 0.0
        for h in spans:
            overlap = min(t, h.end) - max(s, h.start)
            if overlap > 0:
                totals[h.name] += overlap
                covered += overlap
        if t - s - covered > 1e-12:
            totals["no host span"] += t - s - covered
    return [[k, v] for k, v in sorted(totals.items(), key=lambda kv: -kv[1])[:top]]


def matching(events: Iterable[Event], names: Sequence[str]) -> List[Event]:
    """Events whose name contains any of ``names``."""
    return [e for e in events if any(n in e.name for n in names)]


def seconds(events: Iterable[Event]) -> float:
    return sum(e.end - e.start for e in events)


def leaves(events: Iterable[Event]) -> List[Event]:
    """Operations that hold no other: a loop or call spans its body's
    operations, which the trace lists too."""
    ordered = sorted(events, key=lambda e: (e.start, -e.end))
    return [
        e for e, nxt in zip(ordered, ordered[1:] + [None])
        if nxt is None or nxt.start >= e.end
    ]


def top_ops(trace: Trace, lo: float, hi: float, top: int = 10) -> List[List]:
    """Device seconds by operation name (device 0, innermost operations),
    largest first."""
    totals: Dict[str, float] = collections.defaultdict(float)
    for e in leaves(e for e in clip(trace.ops, lo, hi) if e.device == 0):
        totals[e.name] += e.end - e.start
    return [[k, v] for k, v in sorted(totals.items(), key=lambda kv: -kv[1])[:top]]
