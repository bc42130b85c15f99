"""The CDP trainer: Eq. (CDP) as one SPMD program.

``make_train_step`` builds a jitted training step for any registered
architecture, parametrised by a :class:`repro.parallel.ParallelPlan` — the
strategy object that owns the update rule, the gradient-sync implementation,
and the parameter/optimizer placement:

  * ``dp``         — every rank differentiates at theta_t; gradients merge
                     with a single collective (all-reduce HLO burst).
  * ``cdp_v1``     — all ranks differentiate at theta_{t-1}; gradients merge
                     on the point-to-point ring (collective-permute chain).
  * ``cdp_v2``     — rank i (micro-batch = ``lax.axis_index('data')``)
                     differentiates at theta_hat_i = stage-wise mix of
                     theta_t / theta_{t-1} per the paper's u_{i,j}; ring.
  * ``cdp_random`` — beyond-paper randomized freshness threshold; ring.
  * ``zero1_ring`` — ring reduce-scatter + data-sharded optimizer state +
                     parameter all-gather.
  * ``zero_cdp``   — stage-sharded parameters streamed point-to-point
                     (paper Sec. 4.4; ``repro.parallel.zero_cdp``).

The legacy ``TrainerConfig`` flags (``rule=``, ``ring_grads=``,
``zero1_ring=``, ``zero_axis=``) are DEPRECATED aliases that resolve to a
plan — exactly how ``attn_backend`` maps onto the kernel registry.

The step runs under ``jax.shard_map`` manual over the data axis (and the pod
axis when multi-pod), auto (GSPMD) over the model axis — so tensor
parallelism composes freely with the cyclic schedule.

State layout (tree placements):
    {"params": theta_t, "params_prev": theta_{t-1} (CDP only),
     "opt": optimizer state, "step": int32}
ZeRO-CDP replaces each params tree with {"stages": [N, chunk]} stage chunks
sharded over the data axis.
"""
from __future__ import annotations

import dataclasses
import warnings
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import grad_sync
from repro.core.schedule import RULE_CDP_V1, RULE_CDP_V2, RULE_DP
from repro.core.update_rules import (fresh_threshold_traced, needs_prev_params,
                                     select_params, validate_rule)
from repro.models import model as model_mod
from repro.optim import Optimizer
from repro.parallel import plan as plan_mod
from repro.sharding import specs as sh

# (repro.parallel.plan reads rule constants from repro.core.schedule; the
# core package __init__ re-exports this module lazily, so that import chain
# does not cycle back here. repro.parallel.zero_cdp is still imported
# lazily below — it is only needed for stage-sharded plans.)

PyTree = Any

_LEGACY_PLAN_FLAGS = ("rule", "ring_grads", "zero1_ring", "zero_axis")


@dataclasses.dataclass(frozen=True)
class TrainerConfig:
    # The parallelism strategy: a registered plan name ("dp", "cdp_v1",
    # "cdp_v2", "cdp_random", "zero1_ring", "zero_cdp") or a ParallelPlan.
    # None -> the legacy flags below (deprecated), else the cdp_v2 default.
    plan: Any = None                      # ParallelPlan | plan name | None
    # ---- DEPRECATED aliases (resolve to a plan; see resolved_plan) -------
    rule: Optional[str] = None            # DEPRECATED -> plan
    ring_grads: Optional[bool] = None     # DEPRECATED: False -> psum merge
    zero1_ring: Optional[bool] = None     # DEPRECATED -> plan "zero1_ring"
    zero_axis: Optional[str] = None       # DEPRECATED -> plan.zero_axis
    # ---- axes / loop knobs (not plan-owned) ------------------------------
    data_axis: str = "data"
    pod_axis: Optional[str] = None        # set for the multi-pod mesh
    model_axis: str = "model"
    donate: bool = True
    lr_schedule: Optional[Callable] = None
    grad_clip: float = 0.0                # global-norm clip (0 = off)
    grad_comm_dtype: str = "float32"      # ring communication dtype
    seq_parallel: bool = False            # sequence-sharded residual stream

    def __post_init__(self):
        # resolve once at construction: legacy-flag warnings fire here (not
        # on every make_train_step/state_shardings call) and a bad plan or
        # plan+legacy mix fails fast.
        object.__setattr__(self, "_plan", _resolve_trainer_plan(self))

    def resolved_plan(self):
        return self._plan


def _resolve_trainer_plan(tc: TrainerConfig):
    legacy = {k: getattr(tc, k) for k in _LEGACY_PLAN_FLAGS
              if getattr(tc, k) is not None}
    if tc.plan is not None:
        if legacy:
            raise ValueError(
                f"TrainerConfig: pass either plan= or the deprecated flags "
                f"({', '.join(sorted(legacy))}), not both")
        return plan_mod.resolve_plan(tc.plan)
    if legacy:
        warnings.warn(
            f"TrainerConfig({', '.join(f'{k}=' for k in sorted(legacy))}...) "
            f"is deprecated; pass plan= (a ParallelPlan or one of "
            f"{plan_mod.available_plans()})", DeprecationWarning, stacklevel=4)
        return plan_mod.plan_from_legacy_flags(
            rule=tc.rule, ring_grads=tc.ring_grads,
            zero1_ring=tc.zero1_ring, zero_axis=tc.zero_axis)
    return plan_mod.resolve_plan(None)


def init_state(cfg, trainer: TrainerConfig, params: PyTree, opt: Optimizer,
               mesh=None):
    """Initial train state for the trainer's plan. ``mesh`` is required for
    stage-sharded placement (the stage count is the data-axis size)."""
    plan = trainer.resolved_plan()
    if plan.placement == plan_mod.PLACE_STAGE_SHARDED:
        from repro.parallel import zero_cdp as zcdp
        if mesh is None:
            raise ValueError(
                f"plan {plan.name!r} needs the mesh at init_state (stage "
                "count = data-axis size)")
        return zcdp.init_stage_state(cfg, plan, params, opt,
                                     mesh.shape[trainer.data_axis])
    state = {"params": params, "opt": opt.init(params),
             "step": jnp.zeros((), jnp.int32)}
    if needs_prev_params(plan.rule):
        state["params_prev"] = jax.tree.map(jnp.copy, params)
    return state


def optimizer_slot_keys(opt_state: PyTree, params: PyTree) -> set:
    """Params-shaped optimizer slots (see ``sharding.specs.param_slot_keys``
    — one structural detector shared by the ZeRO-1 and mirrored paths)."""
    return sh.param_slot_keys(opt_state, params)


def state_shardings(cfg, trainer: TrainerConfig, state: PyTree, mesh):
    plan = trainer.resolved_plan()
    if plan.placement == plan_mod.PLACE_STAGE_SHARDED:
        psh = sh.stage_chunk_shardings(state["params"], mesh,
                                       trainer.data_axis)
    else:
        psh = sh.param_shardings(state["params"], mesh, trainer.model_axis,
                                 plan.zero_axis)
    if plan.placement == plan_mod.PLACE_ZERO1:
        slots = optimizer_slot_keys(state["opt"], state["params"])
        z1 = jax.tree.map(lambda s: NamedSharding(mesh, s),
                          sh.zero1_param_pspecs(
                              state["params"], mesh, trainer.data_axis,
                              trainer.model_axis, plan.zero_axis))
        opt_sh = {k: (z1 if k in slots else NamedSharding(mesh, P()))
                  for k in state["opt"]}
    else:
        opt_sh = sh.state_shardings(state["opt"], psh)
    out = {"params": psh,
           "opt": opt_sh,
           "step": NamedSharding(mesh, P())}
    if "params_prev" in state:
        out["params_prev"] = psh
    return out


def _data_axes(trainer: TrainerConfig):
    return ((trainer.pod_axis,) if trainer.pod_axis else ()) + (trainer.data_axis,)


def make_train_step(cfg, trainer: TrainerConfig, mesh, opt: Optimizer,
                    loss_fn: Callable = None):
    """Returns (train_step, state_sharding_fn, batch_sharding_fn).

    train_step(state, batch) -> (state, metrics); jit-ready with shardings.
    The strategy comes from ``trainer.resolved_plan()``; stage-sharded plans
    (``zero_cdp``) delegate to ``repro.parallel.zero_cdp``.
    """
    plan = trainer.resolved_plan()
    rule = validate_rule(plan.rule)
    # fail fast on a bad kernel backend: the registry is threaded
    # configs/base.py -> kernels/registry.py -> models/* -> here, and a typo
    # would otherwise only surface mid-trace inside the first jitted step
    from repro.kernels import registry as kernel_registry
    kernel_registry.resolve(cfg)
    plan.validate_mesh(mesh, data_axis=trainer.data_axis,
                       pod_axis=trainer.pod_axis)
    if plan.placement == plan_mod.PLACE_STAGE_SHARDED:
        from repro.parallel import zero_cdp as zcdp
        step_fn = zcdp.make_train_step(cfg, trainer, plan, mesh, opt, loss_fn)
        return (step_fn, partial(state_shardings, cfg, trainer),
                lambda batch: sh.batch_sharding(batch, mesh,
                                                _data_axes(trainer)))
    loss_fn = loss_fn or (lambda p, b: model_mod.loss_fn(cfg, p, b))
    n_data = mesh.shape[trainer.data_axis]
    lr_fn = trainer.lr_schedule or (lambda s: 1e-3)
    daxes = _data_axes(trainer)
    zero1 = plan.sync == plan_mod.SYNC_ZERO1_RING
    grad_pspecs_cache = {}

    def grad_pspecs(params):
        # tensor-parallel specs of the grads (mirror the params) so the ring
        # slices along unsharded dims only. Keyed on the treedef itself
        # (hashable): id() of a temporary treedef can be recycled by the
        # allocator after GC and alias a different params structure.
        key = jax.tree.structure(params)
        if key not in grad_pspecs_cache:
            grad_pspecs_cache[key] = sh.param_pspecs(
                params, mesh, trainer.model_axis, plan.zero_axis)
        return grad_pspecs_cache[key]

    # ---- the per-rank gradient computation, manual over data (+ pod) ------
    def grad_shard(params, params_prev, batch, step):
        i = jax.lax.axis_index(trainer.data_axis)
        if rule == RULE_DP or params_prev is None:
            theta_hat = params
        else:
            ids = model_mod.param_stage_ids(cfg, params, n_data)
            thr = fresh_threshold_traced(rule, i, n_data, step)
            theta_hat = select_params(params, params_prev, ids, thr)
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            theta_hat, batch)
        grads = grad_sync.sync_gradients(
            plan.sync, grads, trainer.data_axis, n_data, grad_pspecs(params),
            comm_dtype=jnp.dtype(trainer.grad_comm_dtype))
        if trainer.pod_axis:
            grads = grad_sync.psum_all_reduce(grads, trainer.pod_axis)
        loss = jax.lax.pmean(loss, daxes)
        metrics = jax.lax.pmean(metrics, daxes)
        return grads, loss, metrics

    use_prev = needs_prev_params(rule)

    def grad_out_specs(params):
        if not zero1:
            return jax.tree.map(lambda _: P(), params)
        # reduce-scattered grads come out data-sharded along the slice axis
        layout = grad_sync.zero1_layout(
            params, n_data, grad_pspecs(params))

        def one(leaf, ax):
            entries = [None] * leaf.ndim
            if ax >= 0:
                entries[ax] = trainer.data_axis
            return P(*entries)
        return jax.tree.map(one, params, layout)

    manual = sh.manual_axes(mesh, daxes)
    # activation sharding constrains the model axis, legal only while auto
    seq_parallel = trainer.seq_parallel and trainer.model_axis not in manual

    def train_step(state, batch):
        params = state["params"]
        params_prev = state["params_prev"] if use_prev else params
        if seq_parallel:
            from repro.models import blocks as blocks_mod
            blocks_mod.set_activation_sharding(mesh, trainer.model_axis)
        rep = lambda t: jax.tree.map(lambda _: P(), t)
        in_specs = (rep(params), rep(params_prev),
                    sh.batch_manual_pspecs(batch, daxes), P())
        out_specs = (grad_out_specs(params), P(), P())
        grads, loss, metrics = jax.shard_map(
            grad_shard, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            axis_names=manual, check_vma=False)(
                params, params_prev, batch, state["step"])
        if seq_parallel:
            from repro.models import blocks as blocks_mod
            blocks_mod.set_activation_sharding(None, None)

        if trainer.grad_clip:
            gnorm = jnp.sqrt(sum(
                jnp.sum(g.astype(jnp.float32) ** 2)
                for g in jax.tree.leaves(grads)))
            scale = jnp.minimum(1.0, trainer.grad_clip / (gnorm + 1e-9))
            grads = jax.tree.map(lambda g: (g * scale).astype(g.dtype), grads)
        lr = lr_fn(state["step"])
        new_params, new_opt = opt.update(grads, state["opt"], params, lr)
        new_state = {"params": new_params, "opt": new_opt,
                     "step": state["step"] + 1}
        if use_prev:
            new_state["params_prev"] = params            # theta_t -> theta_{t-1}
        metrics = dict(metrics)
        metrics["lr"] = lr
        return new_state, metrics

    def batch_shardings(batch):
        return sh.batch_sharding(batch, mesh, daxes)

    return train_step, partial(state_shardings, cfg, trainer), batch_shardings


def jit_train_step(cfg, trainer: TrainerConfig, mesh, opt: Optimizer,
                   state: PyTree, batch_example: PyTree, loss_fn=None):
    """Convenience: build + jit with explicit in/out shardings."""
    step_fn, state_sh_fn, batch_sh_fn = make_train_step(
        cfg, trainer, mesh, opt, loss_fn)
    ssh = state_sh_fn(state, mesh)
    bsh = batch_sh_fn(batch_example)
    jitted = jax.jit(step_fn,
                     in_shardings=(ssh, bsh),
                     out_shardings=(ssh, None),
                     donate_argnums=(0,) if trainer.donate else ())
    return jitted, ssh, bsh


# ---------------------------------------------------------------------------
# Serving steps (no CDP — decode/prefill are inference paths)
# ---------------------------------------------------------------------------

def make_prefill_step(cfg, mesh, data_axes=("data",)):
    def prefill(params, batch):
        return model_mod.prefill_logits(cfg, params, batch)
    return prefill


def make_serve_step(cfg, mesh, data_axes=("data",)):
    def serve_step(params, batch, cache):
        return model_mod.decode_step(cfg, params, batch, cache)
    return serve_step
