"""Crash-isolated dry-run grid driver: one subprocess per (arch, shape,
mesh) so an XLA hard-abort cannot take down the whole grid; results are
merged incrementally into the output JSON.

    PYTHONPATH=src python -m repro.launch.run_grid \
        --out benchmarks/artifacts/dryrun_grid.json [--multi-pod] [--resume]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--resume", action="store_true",
                    help="skip pairs already present in --out")
    ap.add_argument("--rule", default="cdp_v2")
    ap.add_argument("--timeout", type=int, default=1800)
    ap.add_argument("--archs", default=None)
    ap.add_argument("--shapes", default=None)
    args = ap.parse_args(argv)

    from repro.configs import ARCHS, INPUT_SHAPES   # no jax init needed

    archs = args.archs.split(",") if args.archs else list(ARCHS)
    shapes = args.shapes.split(",") if args.shapes else list(INPUT_SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    records = []
    done = set()
    if args.resume and os.path.exists(args.out):
        with open(args.out) as f:
            records = json.load(f)
        done = {(r["arch"], r["shape"], r["mesh"]) for r in records
                if r.get("ok")}

    env = dict(os.environ)
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"          # dryrun compiles on host devices

    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                mesh_name = "2x16x16" if mp else "16x16"
                if (arch, shape, mesh_name) in done:
                    continue
                tmp = args.out + f".{arch}.{shape}.{mesh_name}.tmp"
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape, "--rule", args.rule,
                       "--out", tmp]
                if mp:
                    cmd.append("--multi-pod")
                t0 = time.time()
                try:
                    res = subprocess.run(cmd, capture_output=True, text=True,
                                         timeout=args.timeout, env=env)
                    if os.path.exists(tmp):
                        with open(tmp) as f:
                            recs = json.load(f)
                        os.remove(tmp)
                    else:
                        tail = (res.stderr or res.stdout or "")[-400:]
                        recs = [{"arch": arch, "shape": shape,
                                 "mesh": mesh_name, "ok": False,
                                 "error": f"subprocess rc={res.returncode}: "
                                          f"{tail}"}]
                except subprocess.TimeoutExpired:
                    recs = [{"arch": arch, "shape": shape, "mesh": mesh_name,
                             "ok": False, "error": "timeout"}]
                # replace any stale record for this triple
                records = [r for r in records
                           if (r["arch"], r["shape"], r["mesh"]) !=
                              (arch, shape, mesh_name)] + recs
                with open(args.out, "w") as f:
                    json.dump(records, f, indent=1)
                r = recs[0]
                status = "OK  " if r.get("ok") else "FAIL"
                extra = ""
                if r.get("ok"):
                    rl = r["roofline"]
                    extra = (f"bottleneck={rl['bottleneck']} "
                             f"peak={r['bytes_per_device']['peak_est']/2**30:.1f}GiB")
                else:
                    extra = r.get("error", "")[:120].replace("\n", " ")
                print(f"[{status}] {arch} x {shape} x {mesh_name} "
                      f"({time.time()-t0:.0f}s) {extra}", flush=True)

    n_ok = sum(1 for r in records if r.get("ok"))
    print(f"grid: {n_ok}/{len(records)} ok -> {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
