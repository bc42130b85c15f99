"""The comparisons that decide ``correct``: the numbers each cell reads
from the program and from the plain reference, and their limits.

Training, by the worst leaf of the program's layout:
  loss_gap        max over the checked steps of |loss_prog - loss_ref|
  grad_norm_gap   |‖g_prog‖ - ‖g_ref‖| / max(‖g_ref‖, median leaf ‖g_ref‖)
                  for the first gradient
  change_norm_gap the same for the parameters' change over the checked
                  steps, leaving out leaves whose reference gradient is
                  under GRAD_FLOOR of the median leaf's (round-off moves
                  them, as it moves a key bias under softmax)
Serving:
  served_gap_mean   the mean, over the served tokens of the sampled
                    requests, of the gap by which the reference's logit of
                    a served token lies below the reference's best at that
                    position (the reference in the configuration's own
                    precision)
A limit is set from readings (PERF.md lists them); a limit of None fails.
"""
from __future__ import annotations

import statistics
from typing import Dict, List

GRAD_FLOOR = 1e-3


def _rel_gap(prog: Dict[str, float], ref: Dict[str, float], keep=None
             ) -> float:
    names = [k for k in ref if keep is None or k in keep]
    med = statistics.median(ref[k] for k in names)
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in names)


def kept_leaves(ref_grad: Dict[str, float]) -> List[str]:
    med = statistics.median(ref_grad.values())
    return [k for k, v in ref_grad.items() if v >= GRAD_FLOOR * med]


def train_readings(prog: dict, ref: dict) -> Dict[str, float]:
    missing = set(ref["grad_norms"]) ^ set(prog["grad_norms"])
    if missing:
        raise ValueError(f"program and reference leaves differ: {missing}")
    keep = kept_leaves(ref["grad_norms"])
    return {
        "loss_gap": max(abs(a - b) for a, b in
                        zip(prog["losses"], ref["losses"], strict=True)),
        "grad_norm_gap": _rel_gap(prog["grad_norms"], ref["grad_norms"]),
        "change_norm_gap": _rel_gap(prog["change_norms"],
                                    ref["change_norms"], keep),
    }


def checks(readings: Dict[str, float], limits: Dict[str, float]
           ) -> List[dict]:
    """One entry per compared number, with its limit and verdict."""
    out = []
    for name, value in readings.items():
        limit = limits.get(name)
        ok = limit is not None and value == value and value <= limit
        out.append({"name": name, "value": value, "limit": limit,
                    "ok": ok})
    return out
