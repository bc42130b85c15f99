"""BENCHMARK.json against the benchmark's rules: names and units, every
cell's metrics, each per-layer metric's ``moves`` and ``workloads``, a
reader, a configuration and a mix found by name, and the run's refusal
where there is no TPU or no program."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def manifest():
    return harness.load_manifest()


def test_names_units_and_keys(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    names = [c["name"] for c in manifest["configs"]] + \
        [w["name"] for w in manifest["workloads"]] + \
        [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in manifest["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert 1 <= manifest["run_seconds"] <= 51
    for w in manifest["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])


def test_every_cell_reports_setup_another_metric_and_a_layer(manifest):
    for w in manifest["workloads"]:
        e2e = {m["name"] for m in
               harness.metrics_for(manifest, w["name"], "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert harness.metrics_for(manifest, w["name"], "per_layer")


def test_per_layer_moves_and_workloads(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    cells = {w["name"] for w in manifest["workloads"]}
    layers = {}
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        assert m["workloads"] and set(m["workloads"]) <= cells
        for w in m["workloads"]:
            reported = {x["name"] for x in
                        harness.metrics_for(manifest, w, "end_to_end")}
            assert m["moves"] in reported
        layers.setdefault(m["layer"], []).append(m["name"])
        # a share of a roofline or of a peak is named so
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_files_found_by_name(manifest):
    for c in manifest["configs"]:
        conf = harness.load_config(manifest, c["name"])
        assert c["file"].startswith("bench/configs/")
        assert conf["source"] == c["source"]
        assert set(c["reduced"]) == set(conf["reduced"])
    for w in manifest["workloads"]:
        path = os.path.join(BENCH, "traffic", f"{w['traffic']}.json")
        with open(path) as f:
            driver = json.load(f)["driver"]
        assert os.path.exists(os.path.join(BENCH, f"{driver}_driver.py"))
        assert harness.family(conf_of(manifest, w)["model"])
    for m in manifest["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))


def conf_of(manifest, w):
    return harness.load_config(manifest, w["config"])


def test_config_model_sections_match_the_published_keys(manifest):
    s = harness.load_config(manifest, "stablelm-1.6b")
    m = s["model"]
    assert (m["layers"], m["d_model"], m["heads"], m["kv_heads"],
            m["d_ff"], m["vocab"]) == (
        s["num_hidden_layers"], s["hidden_size"], s["num_attention_heads"],
        s["num_key_value_heads"], s["intermediate_size"], s["vocab_size"])
    assert m["rotary_dims"] == m["head_dim"] * s["partial_rotary_factor"]
    assert m["qkv_bias"] == s["use_qkv_bias"]
    g = harness.load_config(manifest, "chatglm3-6b")
    m = g["model"]
    assert (m["layers"], m["d_model"], m["heads"], m["kv_heads"],
            m["head_dim"], m["d_ff"], m["vocab"]) == (
        g["num_layers"], g["hidden_size"], g["num_attention_heads"],
        g["multi_query_group_num"], g["kv_channels"], g["ffn_hidden_size"],
        g["padded_vocab_size"])
    assert m["qkv_bias"] == g["add_qkv_bias"] and m["norm"] == "rmsnorm"


def _run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "stablelm-1.6b.train.cdp_v2", "--seed", "3", "--seconds", "1",
         "--trace", "0", *extra], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_refuses_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_refuses_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
