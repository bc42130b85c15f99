"""Engine API: RunSpec resolution, TrainEngine checkpoint/resume equality,
ServeEngine fused prefill vs the old launcher's teacher-forcing decode path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.engine import RunSpec, ServeEngine, TrainEngine
from repro.kernels.registry import KernelSpec
from repro.models import decode_step, init_cache, init_params

SPEC = RunSpec(arch="stablelm-1.6b", reduced=True, mesh_data=1, mesh_model=1)


# ---------------------------------------------------------------------------
# RunSpec / kernel registry resolution
# ---------------------------------------------------------------------------

def test_runspec_resolves_arch_and_kernels():
    cfg = SPEC.resolve_config()
    assert cfg.name == "stablelm-1.6b-reduced"
    cfg = SPEC.with_(kernels="decode_attn=pallas").resolve_config()
    assert cfg.kernels == KernelSpec(decode_attn="pallas")
    cfg = SPEC.with_(kernels="pallas").resolve_config()
    assert cfg.kernels == KernelSpec.all("pallas")


def test_runspec_attn_backend_alias_populates_registry():
    from repro.kernels import registry
    with pytest.warns(DeprecationWarning):
        cfg = SPEC.with_(attn_backend="pallas").resolve_config()
    spec = registry.resolve(cfg)
    assert spec.train_attn == "pallas" and spec.prefill_attn == "pallas"
    assert spec.decode_attn == "jnp" and spec.ssm_scan == "jnp"
    # an explicitly named op wins over the alias; ops the --kernels value
    # did not name are still filled from the alias (never silently dropped)
    with pytest.warns(DeprecationWarning):
        cfg = SPEC.with_(attn_backend="pallas",
                         kernels="train_attn=jnp").resolve_config()
    spec = registry.resolve(cfg)
    assert spec.train_attn == "jnp"
    assert spec.prefill_attn == "pallas"


def test_runspec_rejects_bad_backend():
    with pytest.raises(ValueError):
        SPEC.with_(kernels="decode_attn=cuda").resolve_config()
    with pytest.raises(ValueError):
        SPEC.with_(kernels="not_an_op=pallas").resolve_config()
    with pytest.warns(DeprecationWarning):
        with pytest.raises(ValueError):
            SPEC.with_(attn_backend="typo").resolve_config()


def test_trainer_validates_registry_not_alias_string():
    """make_train_step fails fast on a bad backend through the registry."""
    from repro.core.trainer import TrainerConfig, make_train_step
    from repro.launch.mesh import make_mesh
    from repro.optim import sgd_momentum
    cfg = get_reduced("stablelm-1.6b").with_(attn_backend="bogus")
    mesh = make_mesh((1, 1), ("data", "model"))
    with pytest.raises(ValueError):
        make_train_step(cfg, TrainerConfig(rule="dp"), mesh, sgd_momentum())


# ---------------------------------------------------------------------------
# TrainEngine: interrupted + resumed == uninterrupted
# ---------------------------------------------------------------------------

def test_train_engine_resume_matches_uninterrupted(tmp_path):
    kw = dict(rule="cdp_v2", steps=4, batch=2, seq=16, log_every=2,
              verbose=False)
    full = TrainEngine(SPEC, **kw)
    s_full = full.run()

    ckpt = str(tmp_path / "ck")
    part = TrainEngine(SPEC, ckpt_dir=ckpt, ckpt_every=2, **kw)
    part.run(steps=2)                       # interrupted after 2 steps
    resumed = TrainEngine(SPEC, ckpt_dir=ckpt, ckpt_every=2, **kw)
    resumed.build()
    assert resumed.start_step == 2
    s_res = resumed.run()

    for a, b in zip(jax.tree.leaves(s_full["params"]),
                    jax.tree.leaves(s_res["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(s_res["step"]) == 4


def test_train_engine_in_process_continuation_matches():
    """run(steps=2); run() on ONE engine == an uninterrupted run: the
    persistent loader hands prefetched batches to the next call instead of
    dropping them."""
    kw = dict(rule="cdp_v2", steps=4, batch=2, seq=16, log_every=2,
              verbose=False)
    s_full = TrainEngine(SPEC, **kw).run()
    parts = TrainEngine(SPEC, **kw)
    parts.run(steps=2)
    s_parts = parts.run()
    for a, b in zip(jax.tree.leaves(s_full["params"]),
                    jax.tree.leaves(s_parts["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# ServeEngine: fused prefill == old launcher teacher-forcing path
# ---------------------------------------------------------------------------

def _teacher_forced_reference(cfg, params, prompts, cache_len, gen,
                              memory=None):
    """The pre-engine launch/serve.py path: prefill by teacher-forcing the
    prompt through decode_step, then greedy decode."""
    B, S = prompts.shape
    cache = init_cache(cfg, B, cache_len)
    if memory is not None:
        cache["memory"] = memory            # EXACT memory (no zeros splice)
    step = jax.jit(lambda p, b, c: decode_step(cfg, p, b, c))
    logits = None
    for i in range(S):
        logits, cache = step(params, {"token": prompts[:, i]}, cache)
    toks = []
    tok = jnp.argmax(logits, -1)
    for _ in range(gen):
        toks.append(np.asarray(tok))
        logits, cache = step(params, {"token": tok}, cache)
        tok = jnp.argmax(logits, -1)
    return np.stack(toks, 1)


@pytest.mark.parametrize("arch", ["stablelm-1.6b", "zamba2-7b",
                                  "xlstm-350m"])
def test_serve_engine_matches_launcher_decode_path(arch):
    spec = SPEC.with_(arch=arch)
    B, S, gen = 2, 8, 4
    engine = ServeEngine(spec, batch=B, prompt_len=S, gen=gen, verbose=False)
    engine.build()
    prompts = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                 engine.cfg.vocab_size)
    result = engine.generate(prompts)
    ref = _teacher_forced_reference(engine.cfg, engine.params, prompts,
                                    engine.cache_len, gen)
    np.testing.assert_array_equal(result["tokens"], ref)
    assert result["prefill_tok_s"] > 0 and result["decode_tok_s"] > 0


def test_serve_engine_encdec_public_encode():
    """Enc-dec serving goes through the public encode() and keeps the EXACT
    encoder memory (the zeros-padded splice of the old launcher attended
    zero rows in cross-attention)."""
    spec = SPEC.with_(arch="seamless-m4t-large-v2")
    B, S, gen = 2, 8, 3
    engine = ServeEngine(spec, batch=B, prompt_len=S, gen=gen, verbose=False)
    engine.build()
    cfg = engine.cfg
    prompts = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0,
                                 cfg.vocab_size)
    frames = 0.01 * jnp.ones(
        (B, max(1, S // cfg.encdec.frame_rate_divisor), cfg.encdec.frontend_dim),
        jnp.dtype(cfg.dtype))
    memory = engine.encode(frames)
    assert memory.shape == (B, frames.shape[1], cfg.d_model)
    result = engine.generate(prompts, extras={"frames": frames})
    # after prefill the cached memory is the exact encoder output — no pad
    assert engine.cache["memory"].shape[1] == frames.shape[1]
    ref = _teacher_forced_reference(cfg, engine.params, prompts,
                                    engine.cache_len, gen, memory=memory)
    np.testing.assert_array_equal(result["tokens"], ref)


# ---------------------------------------------------------------------------
# Optimizer slot sharding is derived, not hardcoded
# ---------------------------------------------------------------------------

def test_optimizer_slot_keys_derived_from_structure():
    from repro.core.trainer import optimizer_slot_keys
    from repro.optim import adamw, sgd_momentum
    cfg = get_reduced("stablelm-1.6b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    assert optimizer_slot_keys(sgd_momentum().init(params), params) == {"mom"}
    assert optimizer_slot_keys(adamw().init(params), params) == {"m", "v"}

    # a custom optimizer with an unusual slot name is detected structurally
    custom = {"exp_avg": jax.tree.map(jnp.zeros_like, params),
              "count": jnp.zeros((), jnp.int32)}
    assert optimizer_slot_keys(custom, params) == {"exp_avg"}


def test_state_shardings_shard_custom_slots():
    from repro.launch.mesh import make_mesh
    from repro.sharding import specs as sh
    cfg = get_reduced("stablelm-1.6b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    mesh = make_mesh((1, 1), ("data", "model"))
    psh = sh.param_shardings(params, mesh)
    state = {"exp_avg": jax.tree.map(jnp.zeros_like, params),
             "count": jnp.zeros((), jnp.int32)}
    out = sh.state_shardings(state, psh)
    assert out["exp_avg"] is psh                # mirrors params
    assert out["count"].spec == jax.sharding.PartitionSpec()
