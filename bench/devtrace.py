"""Reduction of a profiler trace (``.xplane.pb``) to device busy time,
per-op device time, idle gaps and a device's exposed collective time.

The reading (``load``) and the arithmetic (``union``, ``reduce_events``,
...) are apart, so the arithmetic is tested on plain intervals. Times are
nanoseconds on the profiler's clock; the host spans the harness opens
(``jax.profiler.TraceAnnotation``) are on the same clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # (start_ns, end_ns)

# Device-plane lines that hold one event per executed op. Lines that only
# group ops (steps, modules, scopes) would count the same time twice.
OP_LINES = ("XLA Ops",)
# ops that only hold other ops (a loop over layers): their events span
# their bodies' events, so they count toward busy time but not per op
CONTAINERS = ("while", "conditional", "call")
# collective ops, by the start of their HLO op (``collective-permute-start``
# and ``-done`` are both the permute's)
COLLECTIVES = ("collective-permute", "all-reduce", "all-gather",
               "reduce-scatter", "all-to-all")
HARNESS_SPANS = ("window", "setup", "feed", "step", "serve.call")


@dataclasses.dataclass
class Event:
    name: str
    start: float
    dur: float
    text: str = ""                      # name plus every string stat

    @property
    def end(self) -> float:
        return self.start + self.dur

    @property
    def kind(self) -> str:
        """The HLO op of an ``%x = type op(...)`` event (else "")."""
        head = self.text.split(" = ", 1)
        if len(head) < 2:
            return ""
        m = re.search(r" ([a-z][a-z0-9\-]*)\(", " " + head[1])
        return m.group(1) if m else ""

    @property
    def label(self) -> str:
        """Name, result type without layouts, and op: a short stable key."""
        head = self.text.split(" = ", 1)
        if len(head) < 2:
            return self.name[:160]
        rtype = head[1].split(f" {self.kind}(", 1)[0] if self.kind else ""
        rtype = re.sub(r"\{[^{}]*\}|/\*index=\d+\*/", "", rtype)
        return f"{head[0].lstrip('%')} = {rtype} {self.kind}"[:160]


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Event]]     # device plane -> op events
    spans: List[Event]                  # harness spans on host threads
    line_names: Dict[str, List[str]]    # plane -> its line names


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    """Read device op events and harness spans from one xplane file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    lines: Dict[str, List[str]] = {}
    for plane in pd.planes:
        names = [ln.name for ln in plane.lines]
        lines[plane.name] = names
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            evs = []
            for ln in plane.lines:
                if ln.name not in OP_LINES:
                    continue
                for ev in ln.events:
                    stats = " ".join(str(v) for _, v in ev.stats
                                     if isinstance(v, str))
                    evs.append(Event(ev.name, float(ev.start_ns),
                                     float(ev.duration_ns),
                                     f"{ev.name} {stats}"))
            if evs:
                devices[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name in HARNESS_SPANS:
                        spans.append(Event(ev.name, float(ev.start_ns),
                                           float(ev.duration_ns)))
    return Trace(devices, spans, lines)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge overlapping intervals; sorted, disjoint."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of [lo, hi] not covered by ``busy`` (disjoint)."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def collective_kind(ev: Event) -> Optional[str]:
    """The collective an op event runs (one of ``COLLECTIVES``), by its HLO
    op or, where the event holds none, its name; else None."""
    op = ev.kind or ev.name
    return next((c for c in COLLECTIVES if op.startswith(c)), None)


def exposed(collectives: Iterable[Interval], compute: Sequence[Interval]
            ) -> float:
    """Time inside the collectives during which no compute op runs."""
    comp = union(compute)
    t = 0.0
    for s, e in union(collectives):
        t += total(gaps(clip(comp, s, e), s, e))
    return t


def exposed_collectives(events: Iterable[Event], lo: float, hi: float
                        ) -> Tuple[float, Dict[str, float]]:
    """Of one device's op events, the time in [lo, hi] when a collective op
    runs and no other op does (the union of the collective intervals less
    the union of the others; a container's event spans its body and is
    left out), and the time of each collective kind's events."""
    coll, comp, by = [], [], {}
    for ev in events:
        kind = collective_kind(ev)
        if kind is not None:
            coll.append((ev.start, ev.end))
            by[kind] = by.get(kind, 0.0) + total(
                clip([(ev.start, ev.end)], lo, hi))
        elif ev.kind not in CONTAINERS:
            comp.append((ev.start, ev.end))
    return exposed(clip(coll, lo, hi), clip(comp, lo, hi)), by


def first_chip(planes: Iterable[str]) -> str:
    """The plane of device 0: the lowest number ending a plane's name."""
    def number(name):
        m = re.search(r"(\d+)$", name)
        return int(m.group(1)) if m else 0
    return min(planes, key=number)


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                       # mean over device planes
    op_s: Dict[str, float]              # op name -> device seconds (summed)
    idle_gaps: List[Tuple[str, float]]  # (span open in the gap, seconds)
    events: List[Event]                 # every op event in the window
    planes: Dict[str, List[Event]] = dataclasses.field(
        default_factory=dict)           # the same, by device plane
    span: Interval = (0.0, 0.0)         # the window [lo, hi] in ns


def _span_at(spans: Sequence[Event], t: float) -> str:
    best = None
    for sp in spans:
        if sp.start <= t <= sp.end and (best is None or sp.dur < best.dur):
            best = sp
    return best.name if best is not None else "none"


def reduce_events(devices: Dict[str, List[Event]], spans: List[Event],
                  lo: float, hi: float, top: int = 10) -> Reduced:
    """Busy share, per-op time and idle gaps over the window [lo, hi]
    (ns). Several device planes are averaged."""
    busy, ops, all_gaps, evs, planes = [], {}, [], [], {}
    for plane, evs_d in devices.items():
        inside = [e for e in evs_d if e.end > lo and e.start < hi]
        evs += inside
        planes[plane] = inside
        iv = union(clip([(e.start, e.end) for e in inside], lo, hi))
        busy.append(total(iv))
        for e in inside:
            if e.kind not in CONTAINERS:
                ops[e.label] = ops.get(e.label, 0.0) + e.dur / 1e9
        for s, e in gaps(iv, lo, hi):
            all_gaps.append((_span_at(spans, (s + e) / 2), (e - s) / 1e9))
    n = max(len(devices), 1)
    all_gaps.sort(key=lambda g: -g[1])
    return Reduced(window_s=(hi - lo) / 1e9, busy_s=sum(busy) / n / 1e9,
                   op_s=ops, idle_gaps=all_gaps[:top], events=evs,
                   planes=planes, span=(lo, hi))


def window_of(spans: Sequence[Event], name: str) -> Optional[Interval]:
    """The [start, end] that the harness spans called ``name`` cover."""
    hits = [s for s in spans if s.name == name]
    if not hits:
        return None
    return min(s.start for s in hits), max(s.end for s in hits)


def top_ops(op_s: Dict[str, float], top: int = 10) -> List[List]:
    return [[k, v] for k, v in sorted(op_s.items(),
                                      key=lambda kv: -kv[1])[:top]]


def kernel_events(events: Iterable[Event], marks: Dict[str, Sequence[str]]
                  ) -> Dict[str, List[Event]]:
    """Device events of each kernel kind, by the kernel's name in the
    event's text (its name or a stat); ``marks`` maps kind -> names, and
    the first kind whose name appears claims the event."""
    out: Dict[str, List[Event]] = {k: [] for k in marks}
    for ev in events:
        for kind, names in marks.items():
            if any(n in ev.text for n in names):
                out[kind].append(ev)
                break
    return out
