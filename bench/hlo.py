"""Collective ops and their bytes in a compiled program's HLO text.

A copy of ``parse_collectives`` from the program's launch/roofline.py, kept
with the benchmark so that a change to the program cannot change how the
benchmark counts: the result-shape bytes of every all-reduce, all-gather,
reduce-scatter, all-to-all and collective-permute, ops inside ``while``
bodies counted once per trip (trip counts from the loop condition).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1,
                "f8e5m2": 1, "s64": 8, "u64": 8, "s32": 4, "u32": 4,
                "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
                "c64": 8, "c128": 16}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """Bytes of an HLO result type, incl. tuples '(f32[2,3], bf16[4])'."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class CollectiveStats:
    total_bytes: int
    by_type: Dict[str, int]
    max_single_op_bytes: int          # largest burst (the CDP balance metric)
    op_counts: Dict[str, int]
    # largest single op per collective type; lets callers look at the
    # gradient-merge burst (all-reduce / collective-permute / reduce-scatter)
    # in isolation from e.g. param all-gathers
    max_by_type: Dict[str, int] = dataclasses.field(default_factory=dict)


def _split_computations(hlo: str) -> Dict[str, List[str]]:
    comps: Dict[str, List[str]] = {}
    cur = None
    for line in hlo.splitlines():
        s = line.strip()
        # computation headers can have nested tuple parens and /*index=N*/
        # comments in the signature; exclude op-assignment lines instead
        m = re.match(r"(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(.*->\s*.*\{\s*$", s)
        is_op = re.match(r"(?:ROOT\s+)?%[\w\.\-]+\s*=", s)
        if m and not is_op:
            cur = m.group(1)
            comps[cur] = []
            continue
        if s == "}":
            cur = None
            continue
        if cur is not None:
            comps[cur].append(s)
    return comps


_TRIP_RE = re.compile(r'known_trip_count[^0-9]*(\d+)')


def _while_trip(line: str, comps, cond_name: Optional[str]) -> int:
    m = _TRIP_RE.search(line)
    if m:
        return int(m.group(1))
    return _cond_trip_count(comps.get(cond_name, [])) if cond_name else 1


def _cond_trip_count(cond_lines: List[str]) -> int:
    best = 1
    for ln in cond_lines:
        for m in re.finditer(r"constant\((\d+)\)", ln):
            best = max(best, int(m.group(1)))
    return best


def parse_collectives(hlo: str) -> CollectiveStats:
    comps = _split_computations(hlo)
    entry = None
    for line in hlo.splitlines():
        m = re.match(r"ENTRY\s+%?([\w\.\-]+)", line.strip())
        if m:
            entry = m.group(1)
            break
    if entry is None:       # fall back: treat whole text as one computation
        comps = {"main": hlo.splitlines()}
        entry = "main"

    by_type: Dict[str, int] = {c: 0 for c in _COLLECTIVES}
    op_counts: Dict[str, int] = {c: 0 for c in _COLLECTIVES}
    max_by_type: Dict[str, int] = {c: 0 for c in _COLLECTIVES}
    max_single = 0

    def comp_bytes(name: str, mult: int, seen) -> int:
        nonlocal max_single
        if name not in comps or name in seen:
            return 0
        seen = seen | {name}
        total = 0
        for ln in comps[name]:
            mm = re.match(r"(?:ROOT\s+)?%[\w\.\-]+\s*=\s*(.+?)\s+([\w\-]+)\(",
                          ln)  # lazy: tuple types contain /*index=N*/
            if not mm:
                continue
            shape_str, op = mm.group(1), mm.group(2)
            if op in ("all-reduce-start", "all-gather-start",
                      "collective-permute-start", "reduce-scatter-start",
                      "all-to-all-start"):
                op = op[:-6]
            if op in _COLLECTIVES:
                b = _shape_bytes(shape_str)
                by_type[op] += b * mult
                op_counts[op] += mult
                total += b * mult
                max_single = max(max_single, b)
                max_by_type[op] = max(max_by_type[op], b)
            elif op == "while":
                mb = re.search(r"body=%?([\w\.\-]+)", ln)
                mc = re.search(r"condition=%?([\w\.\-]+)", ln)
                if mb:
                    trip = _while_trip(ln, comps, mc.group(1) if mc else None)
                    total += comp_bytes(mb.group(1), mult * trip, seen)
            elif op in ("call", "conditional", "custom-call", "fusion"):
                for mc in re.finditer(r"(?:to_apply=|calls=)%?([\w\.\-]+)", ln):
                    total += comp_bytes(mc.group(1), mult, seen)
        return total

    total = comp_bytes(entry, 1, frozenset())
    return CollectiveStats(total_bytes=total, by_type=by_type,
                           max_single_op_bytes=max_single,
                           op_counts=op_counts, max_by_type=max_by_type)
