"""Reduced-scale dry-run: every assigned arch lowers + compiles a train and a
decode step on an 8-device (2 data x 2 model x 2 pod) host mesh — the same
code path as the 512-chip production dry-run, so sharding bugs surface in CI.
Run in a subprocess (forced host device count)."""
import pytest

from repro.configs import ARCHS


@pytest.mark.parametrize("arch", ARCHS)
def test_reduced_dryrun_train_and_decode(subproc, arch):
    subproc(f"""
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.configs import get_reduced
from repro.configs.base import InputShape
from repro.core.trainer import TrainerConfig, init_state, make_train_step
from repro.models import model as model_mod
from repro.optim import sgd_momentum
from repro.sharding import specs as sh
from repro.launch.roofline import parse_collectives

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
cfg = get_reduced({arch!r})

# --- train step (CDP-v2, multi-pod axes) ---
opt = sgd_momentum(0.9)
tr = TrainerConfig(rule="cdp_v2", pod_axis="pod", lr_schedule=lambda s: 1e-2)
step_fn, ssh_fn, bsh_fn = make_train_step(cfg, tr, mesh, opt)
state = jax.eval_shape(lambda: init_state(
    cfg, tr, model_mod.init_params(cfg, jax.random.PRNGKey(0)), opt))
B, S = 8, 32
batch = {{"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
          "targets": jax.ShapeDtypeStruct((B, S), jnp.int32)}}
if cfg.family == "vlm":
    batch["patches"] = jax.ShapeDtypeStruct(
        (B, cfg.vlm.num_patches, cfg.vlm.vision_dim), jnp.float32)
if cfg.family == "encdec":
    batch["frames"] = jax.ShapeDtypeStruct(
        (B, S // cfg.encdec.frame_rate_divisor, cfg.encdec.frontend_dim),
        jnp.float32)
ssh = ssh_fn(state, mesh)
jt = jax.jit(step_fn, in_shardings=(ssh, bsh_fn(batch)),
             out_shardings=(ssh, None), donate_argnums=(0,))
comp = jt.lower(state, batch).compile()
stats = parse_collectives(comp.as_text())
assert stats.op_counts["collective-permute"] > 0, "CDP ring missing"
print("train OK", stats.op_counts)

# --- decode step ---
cache = jax.eval_shape(lambda: model_mod.init_cache(cfg, B, 128))
dbatch = {{"token": jax.ShapeDtypeStruct((B,), jnp.int32)}}
params = jax.eval_shape(lambda: model_mod.init_params(cfg, jax.random.PRNGKey(0)))
psh = sh.param_shardings(params, mesh, "model", None)
bsh = sh.batch_sharding(dbatch, mesh, ("pod", "data"))
csh = sh.cache_pspecs(cache, mesh, ("pod", "data"), "model", batch=B)
jd = jax.jit(lambda p, b, c: model_mod.decode_step(cfg, p, b, c),
             in_shardings=(psh, bsh, csh), out_shardings=(None, csh))
comp2 = jd.lower(params, dbatch, cache).compile()
print("decode OK")
""", timeout=1200)
