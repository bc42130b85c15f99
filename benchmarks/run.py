"""Benchmark runner — one module per paper table/figure + roofline summary.

Prints ``name,us_per_call,derived`` CSV (one line per metric).

    PYTHONPATH=src python -m benchmarks.run [--only table2,fig4] [--steps N]

After the modules run, the kernel-vs-jnp speedup ratios measured by the
attn/ssm/decode benches are aggregated into the repo-root
``BENCH_kernels.json`` trajectory (one record per run, keyed by git
commit) so the kernel-perf trend is auditable across PRs. Interpret-mode
(off-TPU) records are tagged — their ratios measure the Pallas
*interpreter*, not the kernels.
"""
from __future__ import annotations

import argparse
import datetime
import json
import math
import os
import subprocess
import sys
import traceback

_ART = os.path.join(os.path.dirname(os.path.abspath(__file__)), "artifacts")
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRAJECTORY = os.path.join(_ROOT, "BENCH_kernels.json")


def _load_artifact(name):
    path = os.path.join(_ART, name)
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return json.load(f)


def _geomean(xs):
    xs = [x for x in xs if x and x > 0]
    if not xs:
        return None
    return round(math.exp(sum(math.log(x) for x in xs) / len(xs)), 3)


def _pair_ratios(records, us_key, match_keys=("shape",)):
    """jnp_us / pallas_us per matching config (>1 means the kernel wins)."""
    by = {}
    for r in records:
        if us_key in r:
            by[(tuple(r.get(k) for k in match_keys), r["backend"])] = r
    ratios, interpret = [], False
    for (cfg, backend), r in by.items():
        if backend != "pallas":
            continue
        j = by.get((cfg, "jnp"))
        if j and r.get(us_key):
            ratios.append(j[us_key] / r[us_key])
            interpret |= bool(r.get("interpret"))
    return ratios, interpret


def update_trajectory(ran):
    """Append this run's kernel-vs-jnp speedups to BENCH_kernels.json.

    ``ran``: the bench modules that completed THIS invocation — only their
    artifacts are aggregated, so a stale file from an older commit (or from
    a module that just failed) is never recorded under the current one."""
    attn = _load_artifact("attn_bench.json") if "attn" in ran else []
    ssm = _load_artifact("ssm_bench.json") if "ssm" in ran else []
    decode = [r for r in _load_artifact("decode_bench.json")
              if r.get("level") == "kernel"] if "decode" in ran else []
    speedup, interpret = {}, False
    for key, recs, us_key in (
            ("train_attn_fwd", attn, "fwd_us"),
            ("train_attn_fwdbwd", attn, "fwdbwd_us"),
            ("ssm_scan_fwd", ssm, "fwd_us"),
            ("ssm_scan_fwdbwd", ssm, "fwdbwd_us"),
            ("decode_attn", decode, "us_per_call")):
        ratios, interp = _pair_ratios(recs, us_key)
        gm = _geomean(ratios)
        if gm is not None:
            speedup[key] = gm
            interpret |= interp
    if not speedup:
        return None
    blocks = {r["shape"]: f"{r['blocks_visited']}/{r['blocks_total']}"
              for r in attn if "blocks_visited" in r}
    try:
        commit = subprocess.check_output(
            ["git", "rev-parse", "--short", "HEAD"], cwd=_ROOT,
            stderr=subprocess.DEVNULL).decode().strip()
        status = subprocess.check_output(
            ["git", "status", "--porcelain"], cwd=_ROOT,
            stderr=subprocess.DEVNULL).decode()
        # the benches rewrite their own tracked artifacts every run — only
        # OTHER modifications mean the measured code differs from HEAD
        dirty = [ln for ln in status.splitlines()
                 if not ln[3:].startswith(("benchmarks/artifacts/",
                                           "BENCH_kernels.json"))]
        if dirty:
            commit += "+"        # measured on an uncommitted working tree
    except Exception:
        commit = "unknown"
    record = {
        "commit": commit,
        "when": datetime.datetime.now().isoformat(timespec="seconds"),
        "interpret": interpret,
        "pallas_speedup_vs_jnp": speedup,
        "blocks_visited_over_total": blocks,
    }
    trajectory = []
    if os.path.exists(TRAJECTORY):
        with open(TRAJECTORY) as f:
            trajectory = json.load(f)
    trajectory.append(record)
    with open(TRAJECTORY, "w") as f:
        json.dump(trajectory, f, indent=1)
    return record


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: table1,table2,fig3,fig4,roofline,attn,"
                         "decode,ssm,rollout,elastic")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (attn_bench, decode_bench, elastic_bench,
                            fig3_loss, fig4_memory, roofline_bench,
                            rollout_bench, ssm_bench, table1_comm,
                            table2_convergence)
    mods = {"table1": table1_comm, "table2": table2_convergence,
            "fig3": fig3_loss, "fig4": fig4_memory,
            "roofline": roofline_bench, "attn": attn_bench,
            "decode": decode_bench, "ssm": ssm_bench,
            "rollout": rollout_bench, "elastic": elastic_bench}
    only = args.only.split(",") if args.only else list(mods)

    print("name,us_per_call,derived")
    failed = []
    for name in only:
        try:
            for row in mods[name].run():
                print(",".join(str(x) for x in row), flush=True)
        except Exception as e:
            failed.append(name)
            traceback.print_exc()
            print(f"{name}.ERROR,0,{type(e).__name__}")
    ran = {"attn", "ssm", "decode"} & (set(only) - set(failed))
    if ran:
        try:
            rec = update_trajectory(ran)
            if rec:
                print(f"trajectory.BENCH_kernels,0.0,{TRAJECTORY}")
        except Exception:
            traceback.print_exc()
            print("trajectory.ERROR,0,")
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
