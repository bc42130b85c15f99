"""What every cell shares: the manifest, the device check, the weights and
data the benchmark hands the program, compile counting, and the result.

Cells, configurations, architectures, mixes and per-layer metrics are
found by name: ``BENCHMARK.json`` names them, ``bench/configs/<config>.json``
holds a configuration, whose ``model`` section names its ``family``,
``bench/families/<family>.py`` answers for that architecture (the program's
config and its check, the plain reference, the counts of FLOPs and bytes),
``bench/traffic/<mix>.json`` holds a mix, whose ``driver`` names
``bench/<driver>_driver.py``, and ``bench/metrics/<metric>.py`` is the
reader of one per-layer metric.

So a new architecture is new files and entries only: a family module, a
configuration file, a mix where it needs one, and its entries in
``BENCHMARK.json``; no file of the harness changes
(bench/tests/test_bench_families.py builds one).
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import os
import re
import sys
import time
from typing import List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
SRC = os.path.join(ROOT, "src")


class Refused(SystemExit):
    """A run that cannot be made here: exits non-zero with no result."""

    def __init__(self, why: str):
        super().__init__(f"bench: {why}")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# -- manifest ---------------------------------------------------------------

def load_manifest() -> dict:
    path = os.path.join(ROOT, "BENCHMARK.json")
    if not os.path.exists(path):
        raise Refused(f"no BENCHMARK.json at {ROOT}")
    with open(path) as f:
        return json.load(f)


def cell(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise Refused(f"no workload {name!r} in BENCHMARK.json")


def load_config(manifest: dict, name: str) -> dict:
    for c in manifest["configs"]:
        if c["name"] == name:
            with open(os.path.join(ROOT, c["file"])) as f:
                return json.load(f)
    raise Refused(f"no config {name!r} in BENCHMARK.json")


def metrics_for(manifest: dict, workload: str, kind: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    out = []
    for m in manifest[kind]:
        if "workloads" not in m or workload in m["workloads"]:
            out.append(m)
    return out


NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")


def _module(kind: str, name: str, path: str):
    """The module at ``path``, loaded once, or refuse."""
    if not NAME.fullmatch(str(name)) or not os.path.exists(path):
        raise Refused(f"no {kind} {name!r}: "
                      f"{os.path.relpath(path, ROOT)} is not there")
    key = f"bench_{kind}_" + re.sub(r"[^A-Za-z0-9_]", "_", name)
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[key] = mod
    return sys.modules[key]


def metric_reader(name: str):
    """``read(ctx)`` of bench/metrics/<name>.py."""
    return _module("metric", name,
                   os.path.join(HERE, "metrics", f"{name}.py")).read


def family(m: dict):
    """The module of bench/families/<family>.py that answers for the
    architecture of a configuration's ``model`` section."""
    name = m.get("family")
    return _module("family", name,
                   os.path.join(HERE, "families", f"{name}.py"))


def driver(name: str):
    """The module bench/<name>_driver.py that runs a mix whose ``driver``
    is ``name``, or refuse."""
    if not NAME.fullmatch(str(name)) or not os.path.exists(
            os.path.join(HERE, f"{name}_driver.py")):
        raise Refused(f"no driver {name!r}: bench/{name}_driver.py is "
                      f"not there")
    return importlib.import_module(f"{name}_driver")


def limits(conf: dict, mix: dict) -> dict:
    """The limits a cell's numbers are held to: those under the mix's
    driver and plan (``train.zero_cdp``) where the configuration has them,
    else those under its driver."""
    lim = conf["limits"]
    return lim.get(f"{mix['driver']}.{mix.get('plan')}", lim[mix["driver"]])


def program_path() -> None:
    """Put the system under test on the import path, or refuse."""
    if not os.path.isdir(os.path.join(SRC, "repro")):
        raise Refused(f"the program (src/repro) is not in {ROOT}")
    if SRC not in sys.path:
        sys.path.insert(0, SRC)
    if HERE not in sys.path:
        sys.path.insert(0, HERE)


# -- device -----------------------------------------------------------------

def device_check(chips: int):
    """The chips this cell asks for, or refuse: JAX falls back to the CPU
    when no accelerator initialises, and a CPU number is no chip number."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise Refused(f"no TPU: JAX found {d.platform}; nothing measured")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def device_info(devs) -> dict:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def enable_cache() -> str:
    """JAX's persistent cache at the program's fixed path (or
    JAX_COMPILATION_CACHE_DIR), every program kept, so the second run of a
    cell compiles nothing."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Times of XLA compilations (fresh or from the persistent cache) while
    ``armed``: the measured window should hold none."""

    def __init__(self):
        import jax
        self.armed = False
        self.times: List[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.armed and "backend_compile" in event:
            self.times.append(time.monotonic())

    def before(self, t: float) -> int:
        return sum(x <= t for x in self.times)


# -- what the benchmark hands the program -------------------------------------

@contextlib.contextmanager
def supplied(params=None, batches=None):
    """While an engine builds, its ``init_params`` returns the benchmark's
    weights and its data stream is the benchmark's batches. The engines
    take neither as an argument; this is the one seam the harness uses."""
    import numpy as np
    import repro.data
    import repro.models
    saved = (repro.models.init_params, repro.data.make_lm_data,
             repro.data.lm_batch_iterator)
    if params is not None:
        repro.models.init_params = lambda cfg, key: params
    if batches is not None:
        repro.data.make_lm_data = \
            lambda vocab, n, seed=0, order=2: np.zeros(n, np.int32)
        repro.data.lm_batch_iterator = \
            lambda tokens, batch, seq, seed=0: batches
    try:
        yield
    finally:
        (repro.models.init_params, repro.data.make_lm_data,
         repro.data.lm_batch_iterator) = saved


def program_config(m: dict):
    """The program's ModelConfig for a configuration's ``model`` section,
    as its family sets it and checks it against the file (the file is the
    yardstick; a program config that drifted from it is refused)."""
    return family(m).program_config(m)


def nearest_rank(values, q: float) -> float:
    """The q-th percentile by nearest rank (a value that was observed)."""
    v = sorted(values)
    if not v:
        return float("nan")
    return v[max(0, math.ceil(q / 100.0 * len(v)) - 1)]


def finite(x: float, cap: float = 1e12) -> float:
    """JSON has no infinity: a tail made of failed requests reads ``cap``."""
    return x if math.isfinite(x) else cap


def emit(result: dict, checks: List[dict]) -> None:
    """The compared numbers as the last lines on stderr, then the result
    as the last line on stdout, with ``checks`` as its last key."""
    for c in checks:
        log(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r})")
    out = dict(result)
    out["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                     for c in checks}
    print(json.dumps(out), flush=True)


def now() -> float:
    return time.monotonic()
