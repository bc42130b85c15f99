#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (BENCHMARK.json ``workloads``) names a configuration
(bench/configs/<config>.json, whose family answers for the architecture:
bench/families/<family>.py) and a mix (bench/traffic/<mix>.json, whose
``driver`` names bench/<driver>_driver.py). The run builds the program on
weights and inputs made from ``--seed``, warms up every shape the window
uses (set-up), measures for ``--seconds``, checks what the window produced
against the plain reference, and prints one JSON line last on stdout:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and with
``--trace 1`` the per-layer metrics, the device's busy and window seconds,
and ``breakdown``), then ``checks``: each compared number with its limit.
With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, read from the profiler trace of the
window, the engine's counters and the host clock.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402
from harness import log  # noqa: E402


class Tracer:
    """The JAX profiler over the measured window, into a directory of the
    checkout (made anew for each traced run)."""

    def __init__(self, path: str):
        self.path = path

    def start(self):
        import jax
        shutil.rmtree(self.path, ignore_errors=True)
        jax.profiler.start_trace(self.path)

    def stop(self):
        import jax
        jax.profiler.stop_trace()


def per_layer(manifest, workload, ctx, out, tracer):
    """Reduce the window's trace and read each per-layer metric."""
    import devtrace as tr
    t0 = time.monotonic()
    tdata = tr.load(tr.find_xplane(tracer.path))
    win = tr.window_of(tdata.spans, "window")
    if win is None or not tdata.devices:
        raise harness.Refused(f"the trace holds no window span or no device "
                              f"ops; planes {tdata.line_names}")
    red = tr.reduce_events(tdata.devices, tdata.spans, *win)
    plane = next(iter(tdata.devices))
    log(f"trace: {plane} lines {tdata.line_names[plane]}")
    for name, sec in tr.top_ops(red.op_s, 12):
        log(f"trace op {sec:.4f}s {name}")
    log(f"trace: {len(red.events)} device ops, busy {red.busy_s:.4f}s of "
        f"{red.window_s:.4f}s, read in {time.monotonic() - t0:.1f}s")
    rctx = dict(ctx, trace=red, result=out)
    metrics = {}
    for spec in harness.metrics_for(manifest, workload, "per_layer"):
        value = harness.metric_reader(spec["name"])(rctx)
        if value is not None:
            metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
    breakdown = {"device_ops": tr.top_ops(red.op_s),
                 "idle_gaps": [[k, v] for k, v in red.idle_gaps]}
    return metrics, red, breakdown


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    manifest = harness.load_manifest()
    w = harness.cell(manifest, args.workload)
    conf = harness.load_config(manifest, w["config"])
    harness.program_path()
    import traffic
    mix = traffic.load_mix(w["traffic"])
    driver = harness.driver(mix["driver"])
    harness.family(conf["model"])        # refused here, before the chip
    import jax
    harness.enable_cache()
    devs = harness.device_check(w["chips"])

    tracer = None
    if args.trace:
        tracer = Tracer(os.path.join(harness.ROOT, ".bench_trace",
                                     args.workload))
    ctx = {"model": conf["model"], "mix": mix, "seed": args.seed,
           "seconds": args.seconds, "devices": devs, "t_start": T_START,
           "tracer": tracer, "config": conf}
    out = driver.run(ctx)
    log(f"compilations inside the measured window: "
        f"{out['compiles_in_window']}")
    limits = harness.limits(conf, mix)
    import compare
    checks = compare.checks(out["readings"], limits)
    result = {"correct": all(c["ok"] for c in checks),
              "attempted": out["attempted"], "failed": out["failed"],
              "device": out["device"]}
    if args.trace:
        import peaks
        ctx["peaks"] = peaks.peaks_for(out["device"]["kind"])
        metrics, red, breakdown = per_layer(manifest, args.workload, ctx,
                                            out, tracer)
        result["metrics"] = metrics
        result["device"] = dict(out["device"], busy_s=red.busy_s,
                                window_s=red.window_s)
        result["breakdown"] = breakdown
    else:
        units = {m["name"]: m["unit"] for m in manifest["end_to_end"]}
        e2e = dict(out["metrics"], setup_s=out["setup_s"])
        result["metrics"] = {
            m["name"]: {"value": e2e[m["name"]], "unit": units[m["name"]]}
            for m in harness.metrics_for(manifest, args.workload,
                                         "end_to_end")}
    harness.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
