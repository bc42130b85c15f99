import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()
os.environ["JAX_PLATFORMS"] = "cpu"     # the 512 devices are host devices

"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh)
combination against ShapeDtypeStruct inputs — proving the sharding config is
coherent — and record memory/cost/roofline terms.

MUST be run as a module entry point (the XLA_FLAGS line above executes before
any jax import):

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2.5-14b \
        --shape train_4k [--multi-pod] [--rule cdp_v2] [--out out.json]
    PYTHONPATH=src python -m repro.launch.dryrun --all --out dryrun.json
"""
import argparse
import json
import sys
import time
import traceback
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS, INPUT_SHAPES, get_config
from repro.core.schedule import RULE_CDP_V2, RULE_DP
from repro.core.trainer import (TrainerConfig, init_state, make_train_step)
from repro.launch import roofline as rl
from repro.launch.inputs import (adapt_config_for_shape, batch_specs,
                                 decode_specs, input_specs, params_specs)
from repro.launch.mesh import make_production_mesh
from repro.models import model as model_mod
from repro.models.model import analytic_param_count
from repro.optim import sgd_momentum
from repro.sharding import specs as sh


def _per_device_bytes(tree, mesh, bf16_only: bool = False) -> int:
    """Analytic per-device bytes of a (ShapeDtypeStruct) tree under the
    standard param shardings."""
    psh = sh.param_pspecs(tree, mesh, "model", None)
    total = 0
    for leaf, spec in zip(jax.tree.leaves(tree),
                          jax.tree.leaves(sh.param_pspecs(tree, mesh, "model", None),
                                          is_leaf=lambda x: isinstance(x, type(jax.sharding.PartitionSpec())))):
        if bf16_only and leaf.dtype != jnp.bfloat16:
            continue
        div = 1
        for ax in spec:
            if ax is None:
                continue
            for a in (ax if isinstance(ax, tuple) else (ax,)):
                div *= mesh.shape[a]
        total += int(np.prod(leaf.shape)) * leaf.dtype.itemsize // div
    return total


def _cache_model_shard(cache, csh, mesh):
    """Add model-axis sharding on the trailing head dim of cache leaves
    where divisible (kv caches: [..., KV, hd] or MLA latent [..., r])."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    msz = mesh.shape["model"]

    def one(leaf, nsh):
        spec = list(nsh.spec) + [None] * (leaf.ndim - len(nsh.spec))
        if leaf.ndim >= 3 and leaf.shape[-1] % msz == 0 and spec[-1] is None:
            spec[-1] = "model"
        return NamedSharding(mesh, P(*spec))
    return jax.tree.map(one, cache, csh)


def _eval_shape_state(cfg, trainer, opt):
    def build():
        params = model_mod.init_params(cfg, jax.random.PRNGKey(0))
        return init_state(cfg, trainer, params, opt)
    return jax.eval_shape(build)


def lower_pair(arch: str, shape_name: str, *, multi_pod: bool = False,
               rule: str = RULE_CDP_V2, remat: bool = True,
               extra: Dict[str, Any] | None = None,
               # ---- §Perf variant knobs (baseline = all defaults) ----
               zero1_ring: bool = False, seq_parallel: bool = False,
               grad_comm_dtype: str = "float32",
               donate_cache: bool = False,
               cache_model_shard: bool = False,
               force_dtype: str = None) -> Dict[str, Any]:
    """Lower + compile one (arch, shape, mesh). Returns the record dict.

    ``force_dtype='float32'`` compiles the model in f32: XLA:CPU then does no
    bf16->f32 operand promotion, giving structurally clean memory/collective
    numbers for the TPU target (report byte quantities / 2 for bf16)."""
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    shape = INPUT_SHAPES[shape_name]
    cfg = adapt_config_for_shape(get_config(arch), shape)
    if force_dtype:
        cfg = cfg.with_(dtype=force_dtype)
    daxes = ("pod", "data") if multi_pod else ("data",)

    # Serving paths (no CDP manual axis): if tensor parallelism alone leaves
    # more than ~10 GiB of weights per chip, additionally shard weights over
    # the data axes (weight-gathered inference) so the model fits HBM.
    def _serve_zero_axis(params):
        per_dev = _per_device_bytes(params, mesh)
        if per_dev > 10 * 2**30:
            return daxes if len(daxes) > 1 else daxes[0]
        return None

    if shape.is_decode:
        batch, cache = decode_specs(cfg, shape)
        params = params_specs(cfg)
        psh = sh.param_shardings(params, mesh, "model", _serve_zero_axis(params))
        bsh = sh.batch_sharding(batch, mesh, daxes)
        csh = sh.cache_pspecs(cache, mesh, daxes, "model",
                              batch=shape.global_batch)
        if cache_model_shard:
            # also shard the head/state dim of KV caches over the model axis
            csh = _cache_model_shard(cache, csh, mesh)

        def serve_step(params, batch, cache):
            return model_mod.decode_step(cfg, params, batch, cache)

        jitted = jax.jit(serve_step, in_shardings=(psh, bsh, csh),
                         out_shardings=(None, csh),
                         donate_argnums=(2,) if donate_cache else ())
        lowered = jitted.lower(params, batch, cache)
    elif shape.kind == "prefill":
        batch = batch_specs(cfg, shape, with_targets=False)
        params = params_specs(cfg)
        psh = sh.param_shardings(params, mesh, "model", _serve_zero_axis(params))
        bsh = sh.batch_sharding(batch, mesh, daxes)

        def prefill_step(params, batch):
            return model_mod.prefill_logits(cfg, params, batch)

        jitted = jax.jit(prefill_step, in_shardings=(psh, bsh))
        lowered = jitted.lower(params, batch)
    else:
        opt = sgd_momentum(0.9, state_dtype=jnp.bfloat16
                           if analytic_param_count(cfg) > 5e10 else jnp.float32)
        from repro.parallel import plan_from_legacy_flags
        trainer = TrainerConfig(
            plan=plan_from_legacy_flags(rule=rule, zero1_ring=zero1_ring),
            pod_axis="pod" if multi_pod else None,
            lr_schedule=lambda s: 1e-2,
            seq_parallel=seq_parallel,
            grad_comm_dtype=grad_comm_dtype)
        step_fn, state_sh_fn, batch_sh_fn = make_train_step(
            cfg, trainer, mesh, opt)
        state = _eval_shape_state(cfg, trainer, opt)
        batch = batch_specs(cfg, shape, with_targets=True)
        ssh = state_sh_fn(state, mesh)
        bsh = batch_sh_fn(batch)
        jitted = jax.jit(step_fn, in_shardings=(ssh, bsh),
                         out_shardings=(ssh, None), donate_argnums=(0,))
        lowered = jitted.lower(state, batch)

    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    mf = rl.model_flops_for(cfg, shape,
                            analytic_param_count(cfg, active_only=True))
    roof = rl.analyze(compiled, chips=chips, model_flops_global=mf)

    # XLA:CPU promotes bf16 matmul operands to f32 (native bf16 on the TPU
    # target): estimate that inflation so the recorded peak can be corrected
    bf16_param_dev = _per_device_bytes(params_specs(cfg), mesh, bf16_only=True)

    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16", "chips": chips,
        "rule": rule if shape.kind == "train" else "-",
        "ok": True,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "bytes_per_device": {
            "arguments": mem.argument_size_in_bytes,
            "output": mem.output_size_in_bytes,
            "temp": mem.temp_size_in_bytes,
            "alias": mem.alias_size_in_bytes,
            "peak_est": mem.argument_size_in_bytes + mem.output_size_in_bytes
                        + mem.temp_size_in_bytes - mem.alias_size_in_bytes,
            "bf16_params": bf16_param_dev,
            # TPU-corrected: remove the f32 copies of bf16 weights that the
            # CPU backend materialises (2x the bf16 bytes per copy)
            "peak_tpu_corrected": max(
                0, mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes - mem.alias_size_in_bytes
                - 2 * bf16_param_dev),
        },
        "roofline": rl.as_dict(roof),
    }
    if extra:
        rec.update(extra)
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCHS))
    ap.add_argument("--shape", choices=list(INPUT_SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--rule", default=RULE_CDP_V2)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    pairs = []
    if args.all:
        for a in ARCHS:
            for s in INPUT_SHAPES:
                pairs.append((a, s))
    else:
        pairs = [(args.arch, args.shape)]

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    records = []
    for arch, shape in pairs:
        for mp in meshes:
            tag = f"{arch} x {shape} x {'2x16x16' if mp else '16x16'}"
            try:
                rec = lower_pair(arch, shape, multi_pod=mp, rule=args.rule)
                r = rec["roofline"]
                print(f"[OK]   {tag}: compute={r['compute_s']*1e3:.2f}ms "
                      f"memory={r['memory_s']*1e3:.2f}ms "
                      f"collective={r['collective_s']*1e3:.2f}ms "
                      f"bottleneck={r['bottleneck']} "
                      f"peak={rec['bytes_per_device']['peak_est']/2**30:.2f}GiB "
                      f"(compile {rec['compile_s']}s)", flush=True)
            except Exception as e:
                rec = {"arch": arch, "shape": shape,
                       "mesh": "2x16x16" if mp else "16x16", "ok": False,
                       "error": f"{type(e).__name__}: {e}"}
                print(f"[FAIL] {tag}: {rec['error']}", flush=True)
                traceback.print_exc()
            records.append(rec)

    if args.out:
        with open(args.out, "w") as f:
            json.dump(records, f, indent=1)
        print(f"wrote {len(records)} records to {args.out}")
    return 0 if all(r.get("ok") for r in records) else 1


if __name__ == "__main__":
    sys.exit(main())
