"""The program's own spans and the device's program executions in a traced
run, and the arithmetic the readers of them share.

The program opens host spans on the profiler's clock (the names in
``repro.engine.spans``). ``load`` reads, from the run's ``.xplane.pb``,
the host events named like a program span or ``window`` and the device's
``XLA Modules`` line (one event per program execution); ``of`` keeps what
it read in the readers' shared context, so a run reads the file once.
The device's op events come from ``ctx["trace"].events``, which
bench/devtrace.py has already read. On a TPU those events carry the HLO
instruction and no ``op_name`` metadata, so the program's
``jax.named_scope`` names cannot be read from them. The arithmetic
(``innermost``, ``cut``, ``idle``, ...) is apart from the reading, so it
is tested on plain intervals. Times are nanoseconds.

A run of a program that opens no spans (an older one) gives readers
nothing to read: they return None.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import devtrace
from devtrace import Event, Interval

MODULE_LINES = ("XLA Modules",)


def span_names() -> Tuple[str, ...]:
    """The program's span names; none from a program that opens none."""
    try:
        from repro.engine import spans
    except ImportError:
        return ()
    return spans.SERVE_SPANS + spans.TRAIN_SPANS


@dataclasses.dataclass
class ProgTrace:
    spans: List[Event]                  # program spans, on host threads
    window: Optional[Interval]          # the harness's window span
    modules: List[Event]                # program executions on the device

    def named(self, name: str, lo: float = float("-inf"),
              hi: float = float("inf")) -> List[Event]:
        """Spans called ``name`` that start inside [lo, hi]."""
        return [s for s in self.spans
                if s.name == name and lo <= s.start <= hi]


def load(path: str) -> ProgTrace:
    """Program spans, the window and the device's program executions of
    one xplane file."""
    from jax.profiler import ProfileData
    keep = set(span_names())
    spans, windows, modules = [], [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name in keep:
                        spans.append(Event(ev.name, float(ev.start_ns),
                                           float(ev.duration_ns)))
                    elif ev.name == "window":
                        windows.append(Event(ev.name, float(ev.start_ns),
                                             float(ev.duration_ns)))
        elif plane.name.startswith("/device:") and "CPU" not in plane.name:
            for ln in plane.lines:
                if ln.name in MODULE_LINES:
                    modules += [Event(ev.name, float(ev.start_ns),
                                      float(ev.duration_ns), ev.name)
                                for ev in ln.events]
    return ProgTrace(spans, devtrace.window_of(windows, "window"), modules)


def of(ctx) -> Optional[ProgTrace]:
    """The traced run's program trace (None without a trace), read on the
    first call and kept in the context the run's readers share."""
    if "progtrace" not in ctx:
        tracer = ctx.get("tracer")
        ctx["progtrace"] = None if tracer is None else \
            load(devtrace.find_xplane(tracer.path))
    return ctx["progtrace"]


# -- arithmetic -------------------------------------------------------------

def innermost(spans: Sequence[Event], lo: float, hi: float
              ) -> List[Tuple[float, float, str]]:
    """[lo, hi] cut into (start, end, name) pieces, each named after the
    innermost span open over it ("none" where none is). Spans of one
    thread nest, so the innermost is the one opened last."""
    out: List[Tuple[float, float, str]] = []
    stack: List[Event] = []
    cur = lo

    def upto(t):
        nonlocal cur
        t = min(t, hi)
        if t > cur:
            out.append((cur, t, stack[-1].name if stack else "none"))
            cur = t

    for sp in sorted(spans, key=lambda s: (s.start, -s.dur)):
        while stack and stack[-1].end <= sp.start:
            upto(stack[-1].end)
            stack.pop()
        upto(sp.start)
        stack.append(sp)
    while stack:
        upto(stack[-1].end)
        stack.pop()
    upto(hi)
    return out


def overlap(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Length of the intersection of two sorted, disjoint interval lists."""
    i = j = 0
    t = 0.0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            t += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return t


def cut(idle: Sequence[Interval],
        pieces: Sequence[Tuple[float, float, str]]
        ) -> List[Tuple[float, float, str]]:
    """``idle`` (sorted, disjoint) cut at the pieces' bounds, each part
    named after the piece it lies in."""
    out: List[Tuple[float, float, str]] = []
    i = 0
    for s, e, name in pieces:
        while i < len(idle) and idle[i][1] <= s:
            i += 1
        k = i
        while k < len(idle) and idle[k][0] < e:
            a, b = max(s, idle[k][0]), min(e, idle[k][1])
            if b > a:
                out.append((a, b, name))
            k += 1
    return out


def totals(parts: Iterable[Tuple[float, float, str]]) -> Dict[str, float]:
    """Summed length of the parts under each name."""
    out: Dict[str, float] = {}
    for a, b, name in parts:
        out[name] = out.get(name, 0.0) + (b - a)
    return out


def busy(events: Iterable[Event], lo: float, hi: float) -> List[Interval]:
    """The union of the events' intervals inside [lo, hi]."""
    return devtrace.union(devtrace.clip(
        [(e.start, e.end) for e in events], lo, hi))


def idle(events: Iterable[Event], lo: float, hi: float) -> List[Interval]:
    """The intervals of [lo, hi] in which no event runs (one chip)."""
    return devtrace.gaps(busy(events, lo, hi), lo, hi)


def executions(modules: Iterable[Event], programs: Sequence[str]
               ) -> List[Event]:
    """The executions of the named jitted programs on the ``XLA Modules``
    line (``jit_<name>(<id>)``)."""
    pat = re.compile(r"^jit_(?:%s)\b" % "|".join(map(re.escape, programs)))
    return [m for m in modules if pat.search(m.name)]


def in_programs(events: Iterable[Event], modules: Sequence[Event],
                programs: Sequence[str]) -> List[Event]:
    """Op events of the named jitted programs: those that an execution of
    one of them holds."""
    runs = sorted((m.start, m.end) for m in executions(modules, programs))
    out, j = [], 0
    for e in sorted(events, key=lambda e: e.start):
        mid = e.start + e.dur / 2
        while j < len(runs) and runs[j][1] < mid:
            j += 1
        if j < len(runs) and runs[j][0] <= mid:
            out.append(e)
    return out


def device_s(events: Iterable[Event], lo: float, hi: float) -> float:
    """Seconds of [lo, hi] in which at least one of the events runs."""
    return devtrace.total(busy(events, lo, hi)) / 1e9

