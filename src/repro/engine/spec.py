"""RunSpec: the execution-plan half of a run, shared by both engines.

One object owns the things every launcher used to re-implement:

  * config resolution  — arch-id lookup (full or reduced) or an explicit
    ``ModelConfig``, plus the kernel-backend registry (``kernels=``) with the
    deprecated ``attn_backend`` alias mapped onto it;
  * plan resolution    — the parallelism strategy (``plan=``, a
    ``repro.parallel`` registry name or ParallelPlan), validated fail-fast;
  * host-device forcing — the CPU-container ``--xla_force_host_platform_
    device_count`` dance, applied to the environment BEFORE jax initialises
    its backend;
  * mesh construction  — (data, model[, pod]) over whatever devices exist.

This module deliberately imports no jax at module scope so a launcher can
build a RunSpec and call :meth:`ensure_host_devices` before anything touches
device state.
"""
from __future__ import annotations

import dataclasses
import os
import warnings
from dataclasses import dataclass
from typing import Any, Optional, Union

from repro.kernels.registry import KernelSpec, coerce_ops


@dataclass(frozen=True)
class RunSpec:
    """What to run and where — but not the train/serve loop parameters
    (those belong to :class:`TrainEngine` / :class:`ServeEngine`)."""
    arch: str = ""
    reduced: bool = False
    config: Optional[Any] = None          # explicit ModelConfig overrides arch
    # kernel backend registry: KernelSpec | dict | CLI string ("pallas" or
    # "decode_attn=pallas,ssm_scan=jnp") | None (keep the config's choice)
    kernels: Union[KernelSpec, dict, str, None] = None
    attn_backend: Optional[str] = None    # DEPRECATED alias (train+prefill)
    # parallelism strategy: a registered plan name ("dp", "cdp_v1", "cdp_v2",
    # "cdp_random", "zero1_ring", "zero_cdp") or a repro.parallel.ParallelPlan
    # object; None -> the engine default (cdp_v2). Resolved fail-fast by
    # resolve_plan() exactly like kernels resolve through the kernel registry.
    plan: Optional[Any] = None
    mesh_data: int = 2
    mesh_model: int = 2
    mesh_pod: int = 0
    host_devices: int = 0                 # force N host CPU devices (0 = off)
    seed: int = 0

    def with_(self, **kw) -> "RunSpec":
        return dataclasses.replace(self, **kw)

    # -- config ------------------------------------------------------------

    def resolve_config(self):
        """The effective ModelConfig: explicit > arch lookup, with the
        kernel registry and the deprecated attn_backend alias applied and
        validated (fail fast, not mid-trace)."""
        from repro.configs import get_config, get_reduced
        from repro.kernels import registry

        if self.config is not None:
            cfg = self.config
        elif self.arch:
            cfg = get_reduced(self.arch) if self.reduced else get_config(self.arch)
        else:
            raise ValueError("RunSpec needs an arch id or an explicit config")
        ops = coerce_ops(self.kernels)
        if self.attn_backend is not None:
            warnings.warn(
                "RunSpec.attn_backend / --attn-backend is deprecated; use "
                "kernels=\"train_attn=...,prefill_attn=...\" (or a single "
                "backend for all ops)", DeprecationWarning, stacklevel=2)
            cfg = cfg.with_(attn_backend=self.attn_backend)
            if ops is not None:
                # the alias fills attention ops the explicit --kernels value
                # did not name (never silently dropped, never overriding an
                # explicitly named op)
                for op in ("train_attn", "prefill_attn"):
                    ops.setdefault(op, self.attn_backend)
        if ops is not None:
            cfg = cfg.with_(kernels=KernelSpec(**ops).validate())
        registry.resolve(cfg)             # validates, incl. the alias path
        return cfg

    # -- parallelism plan --------------------------------------------------

    def resolve_plan(self, default: str = "cdp_v2"):
        """The effective ParallelPlan (validated fail-fast: an unknown plan
        name raises here, not mid-build). Jax-free, like the rest of
        RunSpec resolution."""
        from repro.parallel import resolve_plan
        return resolve_plan(self.plan, default=default)

    # -- devices / mesh ----------------------------------------------------

    def auto_host_devices(self) -> "RunSpec":
        """``host_devices`` defaulted to the mesh size when unset and >1.
        The XLA flag only multiplies CPU devices, so this is inert on an
        accelerator machine while making any multi-rank mesh work out of
        the box on the CPU container. Launch shims call this; explicit
        ``host_devices`` always wins."""
        if self.host_devices:
            return self
        need = self.mesh_data * self.mesh_model * max(self.mesh_pod, 1)
        return self.with_(host_devices=need) if need > 1 else self

    def ensure_host_devices(self) -> None:
        """Force ``host_devices`` CPU devices via XLA_FLAGS. Must run before
        jax initialises its backend — call it first thing in a launcher."""
        if not self.host_devices:
            return
        flag = f"--xla_force_host_platform_device_count={self.host_devices}"
        flags = os.environ.get("XLA_FLAGS", "")
        if flag not in flags:
            os.environ["XLA_FLAGS"] = (flags + " " + flag).strip()

    def build_mesh(self):
        from repro.launch.mesh import make_host_mesh
        return make_host_mesh(self.mesh_data, self.mesh_model, self.mesh_pod)


def shrink_mesh(mesh, dead_rank: int, data_axis: str = "data"):
    """The survivor mesh after data-rank ``dead_rank`` dies: its row of
    model devices is deleted from the device grid, every surviving rank
    keeps its devices (their resident shards stay valid), and ranks above
    the dead one renumber down by one — exactly how the stage ring re-forms
    at N-1. A pod axis does not compose with elastic membership yet (the
    stage ring spans exactly the data axis)."""
    import jax
    import numpy as np

    names = tuple(mesh.axis_names)
    if "pod" in names:
        raise ValueError(
            "elastic shrink does not compose with a pod axis yet")
    if data_axis not in names:
        raise ValueError(f"mesh has no {data_axis!r} axis (axes: {names})")
    ax = names.index(data_axis)
    n = mesh.devices.shape[ax]
    if n <= 1:
        raise ValueError("cannot shrink a mesh with a single data rank")
    if not 0 <= dead_rank < n:
        raise ValueError(
            f"dead rank {dead_rank} outside the {data_axis!r} axis "
            f"(size {n})")
    survivors = np.delete(np.asarray(mesh.devices), dead_rank, axis=ax)
    return jax.sharding.Mesh(survivors, names)      # Auto axes by default
