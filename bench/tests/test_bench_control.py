"""The comparison that decides ``correct`` refuses what it must, at a size
a CPU test run holds (reduced configurations, float32, Pallas kernels in
the interpreter): the control (the plain reference in float8 put in the
program's place) and each fault the cells can have, planted in the
program under a run that skips only the harness's look for a chip. A
sound run passes. The limits are the cells' own (bench/configs)."""
import json
import os
import sys
import time

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import compare  # noqa: E402
import harness  # noqa: E402
import traffic  # noqa: E402

SEED = 2 ** 31 + 99

TINY = {
    "stablelm-1.6b": {
        "family": "dense", "program_arch": "stablelm-1.6b",
        "layers": 2, "d_model": 256,
        "heads": 4, "kv_heads": 4, "head_dim": 64, "d_ff": 512,
        "vocab": 512, "norm": "layernorm", "norm_eps": 1e-5,
        "qkv_bias": True, "rotary_dims": 16, "rope_interleaved": False,
        "rope_theta": 10000.0, "dtype": "float32"},
    "chatglm3-6b": {
        "family": "dense", "program_arch": "chatglm3-6b",
        "layers": 2, "d_model": 256,
        "heads": 4, "kv_heads": 1, "head_dim": 64, "d_ff": 512,
        "vocab": 512, "norm": "rmsnorm", "norm_eps": 1e-5,
        "qkv_bias": True, "rotary_dims": 32, "rope_interleaved": True,
        "rope_theta": 10000.0, "dtype": "float32"},
}


def limits(config, kind):
    with open(os.path.join(BENCH, "configs", f"{config}.json")) as f:
        return json.load(f)["limits"][kind]


@pytest.fixture
def tiny(monkeypatch):
    """The program's reduced configurations stand in for the published
    ones (the harness would refuse a model section that differs), with
    the QKV bias and the dtype the section states."""
    from repro.configs import get_reduced
    monkeypatch.setattr(harness, "program_config", lambda m: get_reduced(
        m["program_arch"]).with_(qkv_bias=m["qkv_bias"], dtype=m["dtype"]))


def train_ctx(seconds=1.0):
    import jax
    mix = traffic.load_mix("train.cdp_v2")
    mix.update(batch=4, seq=64, chunk_steps=2)
    return {"model": TINY["stablelm-1.6b"], "mix": mix, "seed": SEED,
            "seconds": seconds, "devices": jax.devices()[:1],
            "t_start": time.monotonic()}


def serve_ctx(seconds=4.0, dtype="float32"):
    import jax
    mix = traffic.load_mix("serve.sessions")
    mix["sessions"].update(
        system_len=32, user_len={"median": 8, "sigma": 0.5, "cap": 16},
        answer_len={"median": 24, "sigma": 0.3, "cap": 32})
    mix.update(prompt_cap=64, prompt_len=64, gen=32, max_slots=4,
               prefill_chunk=16, kv_block_size=16, kv_pool_blocks=24,
               rate_rps=8.0,
               check_requests=4)
    return {"model": dict(TINY["chatglm3-6b"], dtype=dtype), "mix": mix,
            "seed": SEED, "seconds": seconds, "devices": jax.devices()[:1],
            "t_start": time.monotonic()}


def verdict(readings, config, kind):
    return all(c["ok"] for c in compare.checks(readings,
                                               limits(config, kind)))


def test_train_sound_run_is_correct(tiny):
    import train_driver
    out = train_driver.run(train_ctx())
    assert out["compiles_in_window"] == 0
    assert verdict(out["readings"], "stablelm-1.6b", "train")


def test_train_control_is_not_correct(tiny):
    import jax
    import train_driver
    from repro.models import init_params
    ctx = train_ctx()
    cfg = harness.program_config(ctx["model"])
    layout = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    m, mix = ctx["model"], ctx["mix"]
    ref = train_driver.reference_side(m, mix, SEED, layout)
    fp8 = train_driver.reference_side(m, mix, SEED, layout, prec="fp8")
    assert not verdict(compare.train_readings(fp8, ref), "stablelm-1.6b",
                       "train")


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_train_fault_is_not_correct(tiny, monkeypatch, fault):
    import jax.numpy as jnp
    from repro.core import trainer
    import train_driver
    make = trainer.make_train_step

    def broken(*a, **k):
        step, ssh, bsh = make(*a, **k)

        def bad(state, batch):
            if fault == "half_batch":
                h = batch["tokens"].shape[0] // 2
                batch = {key: jnp.concatenate([v[:h], v[:h]])
                         for key, v in batch.items()}
            new, metrics = step(state, batch)
            return (state if fault == "state_unchanged" else new), metrics
        return bad, ssh, bsh

    monkeypatch.setattr(trainer, "make_train_step", broken)
    out = train_driver.run(train_ctx())
    assert not verdict(out["readings"], "stablelm-1.6b", "train")


def test_serve_sound_run_is_correct(tiny):
    import serve_driver
    out = serve_driver.run(serve_ctx())
    assert out["failed"] == 0 and out["compiles_in_window"] == 0
    assert verdict(out["readings"], "chatglm3-6b", "serve")


def test_serve_control_and_altered_token_are_not_correct(tiny, monkeypatch):
    import serve_driver
    from repro.engine import serve as serve_mod
    ctx = serve_ctx()
    prog = serve_driver.program_side(ctx["model"], ctx["mix"], SEED,
                                     ctx["devices"], ctx["seconds"])
    sample = serve_driver.check_sample(prog, ctx["mix"], SEED)
    control = serve_driver.readings(ctx["model"], prog["params"], sample,
                                    control="fp8")
    assert not verdict(control, "chatglm3-6b", "serve")

    make = serve_mod._sampler

    def altering():
        sample_fn = make()

        def sample(logits, keys, temps, topks):
            toks, keys = sample_fn(logits, keys, temps, topks)
            return (toks + 1) % logits.shape[-1], keys
        return sample

    monkeypatch.setattr(serve_mod, "_sampler", altering)
    out = serve_driver.run(serve_ctx())
    assert not verdict(out["readings"], "chatglm3-6b", "serve")


def test_serve_bfloat16_sound_run_is_correct_and_control_is_not(tiny):
    """Served in bfloat16, as the cell is, the reference runs in bfloat16
    too: the sound run passes and the float8 control does not."""
    import serve_driver
    ctx = serve_ctx(dtype="bfloat16")
    out = serve_driver.run(ctx)
    assert out["failed"] == 0 and out["compiles_in_window"] == 0
    assert verdict(out["readings"], "chatglm3-6b", "serve")
    prog = serve_driver.program_side(ctx["model"], ctx["mix"], SEED,
                                     ctx["devices"], ctx["seconds"])
    sample = serve_driver.check_sample(prog, ctx["mix"], SEED)
    control = serve_driver.readings(ctx["model"], prog["params"], sample,
                                    control="fp8")
    assert not verdict(control, "chatglm3-6b", "serve")
