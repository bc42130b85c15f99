#!/usr/bin/env python3
"""Readings that set a cell's limits, on the chip at the cell's own size.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 [--control]

For each seed, in one process: the program's readings (the cell's own
path, with a short window: ``--seconds``), and with ``--control`` the
readings of the plain reference put in the program's place in the
precision below the configuration's (float8 e4m3 for bfloat16), and, for a
training cell, of the reference with half of each batch left out and, on
a stage-sharded plan, of the program with the exchange between its chips
left out (``no_exchange``). Each
side's readings go through the cell's own comparison (compare.checks,
with the limits of its configuration file) as a run's do: one JSON line
per seed on stdout gives each side's readings and ``correct``. A sound
run reads ``correct`` true, the control and the fault false; a limit lies
above every sound reading and below every control's (PERF.md).
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import compare  # noqa: E402
import harness  # noqa: E402


def judged(side, readings, limits):
    """A side's readings with the verdict the cell's comparison gives."""
    checks = compare.checks(readings, limits)
    for c in checks:
        harness.log(f"{side} check {c['name']}: {c['value']!r} "
                    f"(limit {c['limit']!r})")
    return {"readings": readings, "correct": all(c["ok"] for c in checks)}


@contextlib.contextmanager
def no_exchange():
    """The fault of a stage-sharded step whose exchange between chips is
    left out: the stages still stream forward, but no gradient crosses the
    ring back, so each chip updates its own stage from its own micro-batch
    alone (its gradient taken n times, so that the step's mean over the n
    chips leaves it whole)."""
    import jax
    from repro.parallel import zero_cdp
    stream = zero_cdp.stream_stages

    def cut(my_chunk, axis, n):
        others = jax.lax.stop_gradient(stream(my_chunk, axis, n))
        mine = my_chunk + (n - 1) * (my_chunk -
                                     jax.lax.stop_gradient(my_chunk))
        return jax.lax.dynamic_update_slice(
            others, mine[None], (jax.lax.axis_index(axis), 0))

    zero_cdp.stream_stages = cut
    try:
        yield
    finally:
        zero_cdp.stream_stages = stream


def train_seed(m, mix, seed, devs, seconds, control, limits):
    import train_driver as td
    prog = td.program_side(m, mix, seed, devs, seconds)
    ref = td.reference_side(m, mix, seed, prog["layout"], devices=devs)
    out = {"program": judged("program", compare.train_readings(prog, ref),
                             limits)}
    if control:
        fp8 = td.reference_side(m, mix, seed, prog["layout"], prec="fp8",
                                devices=devs)
        out["control_fp8"] = judged(
            "control_fp8", compare.train_readings(fp8, ref), limits)
        rows = td.step_mix(mix)["batch"]
        half = td.reference_side(m, mix, seed, prog["layout"],
                                 rows=slice(0, rows // 2), devices=devs)
        out["fault_half_batch"] = judged(
            "fault_half_batch", compare.train_readings(half, ref), limits)
        if mix["placement"] == "stage_sharded":
            with no_exchange():
                cut = td.program_side(m, mix, seed, devs, 0.0)
            out["fault_no_exchange"] = judged(
                "fault_no_exchange", compare.train_readings(cut, ref),
                limits)
    return out


def serve_seed(m, mix, seed, devs, seconds, control, limits):
    import serve_driver as sd
    prog = sd.program_side(m, mix, seed, devs, seconds)
    sample = sd.check_sample(prog, mix, seed)
    out = {"program": judged("program",
                             sd.readings(m, prog["params"], sample), limits),
           "served_tokens": int(sample["valid"].sum()),
           "failed": sum(r.status != "ok" for r in prog["requests"])}
    if control:
        out["control_fp8"] = judged(
            "control_fp8", sd.readings(m, prog["params"], sample,
                                       control="fp8"), limits)
    del prog
    gc.collect()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    manifest = harness.load_manifest()
    w = harness.cell(manifest, args.workload)
    conf = harness.load_config(manifest, w["config"])
    m = conf["model"]
    harness.program_path()
    import traffic
    mix = traffic.load_mix(w["traffic"])
    limits = harness.limits(conf, mix)
    harness.enable_cache()
    devs = harness.device_check(w["chips"])
    one = {"train": train_seed, "serve": serve_seed}.get(mix["driver"])
    if one is None:
        raise harness.Refused(f"no control readings for driver "
                              f"{mix['driver']!r}")
    for seed in (int(s) for s in args.seeds.split(",")):
        rec = one(m, mix, seed, devs, args.seconds, args.control, limits)
        print(json.dumps(dict(rec, seed=seed)), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
