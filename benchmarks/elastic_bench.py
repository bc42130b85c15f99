"""Elastic recovery benchmark: what a rank death actually costs.

One run per plan (``dp`` on a 2-rank mesh, ``zero_cdp`` on a 3-rank
ring), each in a forced-host-device subprocess (like ``table1_comm``'s
plan measurement, so the runner keeps its single device): inject
``rank_down@k``, let the engine re-form the ring on the survivors from
the buddy snapshot, and record the price —

  * ``recovery_s``      — wall-clock of the shrink (restore point + mesh
    rebuild + state re-cut + re-jit + stream fast-forward);
  * ``steps_lost``      — work discarded (failed step - snapshot step),
    bounded by ``snapshot_every``;
  * ``snapshot_s_mean`` / ``snapshot_bytes`` — the steady-state overhead
    paid per snapshot interval for that recovery to exist;
  * ``source``          — where the restore point came from (``snapshot``
    unless the store was unusable and disk served).

Writes ``benchmarks/artifacts/elastic_bench.json`` and yields rows in
the ``name,us_per_call,derived`` CSV convention of ``benchmarks/run.py``.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

from benchmarks._util import ARTIFACTS, SMOKE

ARCH = "stablelm-1.6b"
STEPS = 6 if SMOKE else 10
FAIL_STEP = 3 if SMOKE else 5
SNAPSHOT_EVERY = 2

# (plan, n_ranks, dead_rank, global_batch) — batch divides both N and N-1
SCENARIOS = (("dp", 2, 1, 4), ("zero_cdp", 3, 1, 6))

_MEASURE_SNIPPET = """
import json
from repro.engine import RunSpec, TrainEngine

plan, n, dead, batch, steps, every, spec_str = {scenario!r}
spec = RunSpec(arch={arch!r}, reduced=True, plan=plan, mesh_data=n,
               mesh_model=1)
eng = TrainEngine(spec, steps=steps, batch=batch, seq=16, log_every=1,
                  elastic=True, snapshot_every=every,
                  resilience=spec_str, verbose=False)
eng.run()
rec = eng.recoveries[0]
snaps = eng.events.of("snapshot")
out = {{
    "plan": plan,
    "n_ranks": n,
    "dead_rank": dead,
    "fail_step": rec["failed_at"],
    "recover_step": rec["step"],
    "steps_lost": rec["steps_lost"],
    "recovery_s": round(rec["duration_s"], 4),
    "snapshot_s_mean": round(sum(s["dur_s"] for s in snaps)
                             / max(len(snaps), 1), 4),
    "snapshot_bytes": max(s["bytes"] for s in snaps),
    "snapshot_every": every,
    "source": rec["source"],
    "final_loss": round(eng.history[-1]["loss"], 6),
}}
print("ELASTIC " + json.dumps(out))
"""


def _one_scenario(plan, n, dead, batch, timeout=1200):
    env = dict(os.environ)
    flag = f"--xla_force_host_platform_device_count={n}"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " " + flag).strip()
    # forced host devices are CPU devices: pin the child there so it never
    # contends for an accelerator the parent holds
    env["JAX_PLATFORMS"] = "cpu"
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    snippet = _MEASURE_SNIPPET.format(
        scenario=(plan, n, dead, batch, STEPS, SNAPSHOT_EVERY,
                  f"rank_down@{FAIL_STEP}:{dead}"),
        arch=ARCH)
    res = subprocess.run([sys.executable, "-c", snippet],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    if res.returncode != 0:
        raise RuntimeError(f"elastic scenario {plan}@{n} failed:\n"
                           f"{res.stdout}\n{res.stderr}")
    for line in res.stdout.splitlines():
        if line.startswith("ELASTIC "):
            rec = json.loads(line[len("ELASTIC "):])
            rec["arch"] = ARCH
            rec["reduced"] = True
            return rec
    raise RuntimeError(f"no ELASTIC line in output:\n{res.stdout}")


def run():
    records, rows = [], []
    for plan, n, dead, batch in SCENARIOS:
        t0 = time.time()
        rec = _one_scenario(plan, n, dead, batch)
        us = (time.time() - t0) * 1e6
        records.append(rec)
        rows.append((f"elastic.{plan}.recovery_s", us, rec["recovery_s"]))
        rows.append((f"elastic.{plan}.steps_lost", 0.0, rec["steps_lost"]))
        rows.append((f"elastic.{plan}.snapshot_s", 0.0,
                     rec["snapshot_s_mean"]))
        rows.append((f"elastic.{plan}.snapshot_MB", 0.0,
                     round(rec["snapshot_bytes"] / 2**20, 2)))

    os.makedirs(ARTIFACTS, exist_ok=True)
    path = os.path.join(ARTIFACTS, "elastic_bench.json")
    with open(path, "w") as f:
        json.dump(records, f, indent=1)
    rows.append(("elastic.artifact", 0.0, path))
    return rows


if __name__ == "__main__":
    for r in run():
        print(",".join(str(x) for x in r))
