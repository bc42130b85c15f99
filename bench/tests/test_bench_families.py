"""Architectures and drivers found by name: the harness takes a family of
its own (bench/families/<family>.py) for each configuration and a driver
(bench/<driver>_driver.py) for each mix, and refuses one that is not
there; a new architecture is new files and manifest entries alone.

On a 4-device CPU mesh, at a tiny stablelm-shaped size: the norms read
back from ``zero_cdp``'s stage-sharded state equal those of the program's
own unchunked trees, the ``cdp_v1`` reference follows the program within
the configuration's limits, and the exchange left out fails them. The
exposed collective share is read from a synthetic trace."""
import json
import os
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

import devtrace as tr  # noqa: E402
import harness  # noqa: E402

CONFIGS = ("stablelm-1.6b", "chatglm3-6b")


def model(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)["model"]


def _checkout(tmp_path):
    """A copy of what a check runs: BENCHMARK.json and bench/."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path


def _python(cwd, code, devices=1, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count="
                         f"{devices}")
    return subprocess.run([sys.executable, "-c", code], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_run_refuses_an_unknown_driver(tmp_path):
    root = _checkout(tmp_path)
    with open(root / "bench" / "traffic" / "odd.json", "w") as f:
        json.dump({"driver": "nope"}, f)
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["workloads"].append({"name": "stablelm-1.6b.odd",
                             "config": "stablelm-1.6b", "traffic": "odd",
                             "chips": 1, "why": "a mix of no driver"})
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    shutil.copytree(os.path.join(ROOT, "src"), root / "src",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "stablelm-1.6b.odd",
         "--seed", "3", "--seconds", "1"], cwd=root, capture_output=True,
        text=True, timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no driver 'nope'" in p.stderr and "no TPU" not in p.stderr


@pytest.mark.parametrize("name", CONFIGS)
def test_family_loads_dense_for_each_config(name):
    fam = harness.family(model(name))
    assert fam.__name__ == "bench_family_dense"
    assert harness.family(model(name)) is fam
    for entry in ("check", "program_config", "train_steps", "change_norms",
                  "forward_served", "served_gaps",
                  "train_flops_per_token", "weight_bytes",
                  "kv_bytes_per_token", "matmul_params"):
        assert callable(getattr(fam, entry))


@pytest.mark.parametrize("family", ["moe_mla", "../configs/x", None])
def test_family_refuses_a_missing_one(family):
    with pytest.raises(harness.Refused, match="no family"):
        harness.family({"family": family} if family else {})


@pytest.mark.parametrize("name", CONFIGS)
def test_configs_pass_their_family_check(name):
    m = model(name)
    cfg = harness.program_config(m)
    assert cfg.num_layers == m["layers"] and cfg.qkv_bias == m["qkv_bias"]
    harness.family(m).check(cfg, m)


@pytest.mark.parametrize("key,value", [("d_ff", 5632 * 2), ("heads", 16),
                                       ("rotary_dims", 32)])
def test_planted_width_mismatch_is_refused(key, value):
    m = dict(model("stablelm-1.6b"), **{key: value})
    with pytest.raises(harness.Refused, match=key):
        harness.program_config(m)


TOY = '''"""A stand-in architecture: only what the harness asks of a family."""


def check(cfg, m):
    pass


def program_config(m):
    return ("toy config", m["layers"])


def train_flops_per_token(m, seq):
    return 6.0 * m["params"]
'''


def test_a_new_architecture_is_files_only(tmp_path):
    """A family module, a configuration file and manifest entries: the
    harness finds them, and the manifest still keeps every rule, with no
    file of the harness edited."""
    root = _checkout(tmp_path)
    before = {p: (root / "bench" / p).read_bytes() for p in
              os.listdir(root / "bench") if p.endswith(".py")}
    (root / "bench" / "families" / "toy.py").write_text(TOY)
    conf = {"name": "toy-1b", "source": "https://example.org/toy-1b",
            "reduced": {}, "model": {"family": "toy", "layers": 2,
                                     "params": 1e9},
            "limits": {"train": {"loss_gap": 1e-3}}}
    (root / "bench" / "configs" / "toy-1b.json").write_text(json.dumps(conf))
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["configs"].append({"name": "toy-1b", "source": conf["source"],
                           "file": "bench/configs/toy-1b.json",
                           "reduced": [], "why": "a new architecture"})
    man["workloads"].append({"name": "toy-1b.train.cdp_v2",
                             "config": "toy-1b", "traffic": "train.cdp_v2",
                             "chips": 1, "why": "the toy trained"})
    for m in man["end_to_end"] + man["per_layer"]:
        if "stablelm-1.6b.train.cdp_v2" in m.get("workloads", []):
            m["workloads"].append("toy-1b.train.cdp_v2")
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    code = (
        "import sys; sys.path.insert(0, 'bench')\n"
        "import harness, traffic\n"
        "man = harness.load_manifest()\n"
        "w = harness.cell(man, 'toy-1b.train.cdp_v2')\n"
        "conf = harness.load_config(man, w['config'])\n"
        "mix = traffic.load_mix(w['traffic'])\n"
        "print(harness.program_config(conf['model']))\n"
        "print(harness.family(conf['model']).train_flops_per_token("
        "conf['model'], 2048))\n"
        "print(harness.limits(conf, mix), mix['driver'])\n")
    p = _python(root, code)
    assert p.returncode == 0, p.stderr
    assert p.stdout.split("\n")[:3] == [
        "('toy config', 2)", "6000000000.0", "{'loss_gap': 0.001} train"]
    p = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "bench/tests/test_bench_manifest.py", "-k",
         "names or every_cell or per_layer or files_found"],
        cwd=root, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stdout + p.stderr
    assert "4 passed" in p.stdout
    assert before == {p: (root / "bench" / p).read_bytes() for p in before}


# -- zero_cdp on a 4-device CPU mesh ---------------------------------------

ZERO = r'''
import json, sys
sys.path.insert(0, "bench"); sys.path.insert(0, "src")
import jax
import compare, control, harness, traffic
import train_driver as td
from repro.configs import get_reduced
from repro.parallel import zero_cdp

harness.program_config = lambda m: get_reduced(m["program_arch"]).with_(
    qkv_bias=m["qkv_bias"], dtype=m["dtype"])
m = {"family": "dense", "program_arch": "stablelm-1.6b", "layers": 2,
     "d_model": 256, "heads": 4, "kv_heads": 4, "head_dim": 64, "d_ff": 512,
     "vocab": 512, "norm": "layernorm", "norm_eps": 1e-5, "qkv_bias": True,
     "rotary_dims": 16, "rope_interleaved": False, "rope_theta": 10000.0,
     "dtype": "float32"}
mix = traffic.load_mix("train.zero_cdp.x4")
mix.update(batch=1, seq=64, chunk_steps=2)
seed, devs = 2 ** 31 + 77, jax.devices()[:4]


def unchunked(eng, name):
    """The state entry as the program's own unchunk_params gives it."""
    lay = zero_cdp.build_stage_layout(eng.cfg, eng.mesh.shape["data"])
    lay = type(lay)(**dict(vars(lay), dtypes=("float32",) * len(lay.dtypes)))
    st = eng.state
    x = st["opt"]["mom"] if name == "mom" else st[name]
    return zero_cdp.unchunk_params(lay, jax.device_get(x["stages"]))


def by_trees(f, a, b):
    import jax.numpy as jnp
    return td._named_norms(jax.tree.map(
        lambda x, y: jnp.sqrt(jnp.sum(f(x, y) ** 2)), a, b))


pairs = {}
grad, change = td.grad_norms, td.change_norms


def grad_seen(eng, wd):
    got = grad(eng, wd)
    pairs["grad"] = (got, by_trees(lambda mo, p: mo - wd * p,
                                   unchunked(eng, "mom"),
                                   unchunked(eng, "params_prev")))
    return got


def change_seen(eng, p0):
    got = change(eng, p0)
    pairs["change"] = (got, by_trees(lambda x, y: x - y,
                                     unchunked(eng, "params"),
                                     jax.device_get(p0)))
    return got


td.grad_norms, td.change_norms = grad_seen, change_seen
prog = td.program_side(m, mix, seed, devs, 0.5)
td.grad_norms, td.change_norms = grad, change
ref = td.reference_side(m, mix, seed, prog["layout"], devices=devs)
with control.no_exchange():
    cut = td.program_side(m, mix, seed, devs, 0.0)
print(json.dumps({
    "pairs": pairs, "steps": prog["steps"],
    "compiles_in_window": prog["compiles_in_window"],
    "sound": compare.train_readings(prog, ref),
    "no_exchange": compare.train_readings(cut, ref)}))
'''


@pytest.fixture(scope="module")
def zero():
    p = _python(ROOT, ZERO, devices=4, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def _verdict(readings, key="train.zero_cdp"):
    with open(os.path.join(BENCH, "configs", "stablelm-1.6b.json")) as f:
        lim = json.load(f)["limits"]
    import compare
    return all(c["ok"] for c in compare.checks(readings,
                                               lim.get(key, lim["train"])))


@pytest.mark.parametrize("which", ["grad", "change"])
def test_stage_sharded_norms_match_the_unchunked_tree(zero, which):
    got, want = zero["pairs"][which]
    assert set(got) == set(want) and len(got) >= 10
    for k in want:
        assert got[k] == pytest.approx(want[k], rel=1e-5, abs=1e-7), k
    assert max(want.values()) > 0


def test_cdp_v1_reference_follows_zero_cdp(zero):
    assert zero["steps"] >= 2 and zero["compiles_in_window"] == 0
    assert _verdict(zero["sound"]), zero["sound"]


def test_exchange_left_out_is_not_correct(zero):
    assert not _verdict(zero["no_exchange"]), zero["no_exchange"]


# -- exposed collective time -------------------------------------------------

E = tr.Event


def _op(name, start, dur, op=None):
    text = f"%{name} = f32[8] {op}(%p)" if op else name
    return E(name, start, dur, text)


def _exposed_ctx(planes, result=None):
    evs = [e for p in planes.values() for e in p]
    red = tr.Reduced(window_s=1e-6, busy_s=0.0, op_s={}, idle_gaps=[],
                     events=evs, planes=planes, span=(0.0, 1000.0))
    res = result or {"metrics": {"train_tok_s": 1.0}}
    return harness.metric_reader("exposed_collective.train"), \
        {"trace": red, "result": res}


def test_exposed_collective_reads_chip_zero_bare_collective_time():
    chip0 = [
        _op("while.1", 0, 400, "while"),                 # container: out
        _op("fusion.1", 0, 100),
        _op("collective-permute-start.1", 90, 20,
            "collective-permute-start"),                  # bare 100..110
        _op("collective-permute-done.1", 200, 30,
            "collective-permute-done"),                   # bare 200..230
        _op("fusion.2", 250, 10),
        _op("all-reduce.3", 300, 5, "all-reduce"),        # bare 300..305
        _op("all-gather.4", 990, 40, "all-gather")]       # bare 990..1000
    chip1 = [_op("all-gather.9", 0, 1000, "all-gather")]
    read, ctx = _exposed_ctx({"/device:TPU:1": chip1,
                              "/device:TPU:0": chip0})
    # (10 + 30 + 5 + 10) ns of a 1000 ns window
    assert read(ctx) == pytest.approx(5.5)
    bare, by = tr.exposed_collectives(chip0, 0.0, 1000.0)
    assert by == {"collective-permute": 50, "all-reduce": 5,
                  "all-gather": 10}
    read, ctx = _exposed_ctx({"/device:TPU:0": [_op("fusion.1", 0, 10)]})
    assert read(ctx) is None
    read, ctx = _exposed_ctx({"/device:TPU:0": chip0},
                             {"metrics": {"itl_p95_ms": 1.0}})
    assert read(ctx) is None
