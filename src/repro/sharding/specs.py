"""Logical-axis sharding rules: param/batch/cache PartitionSpecs per arch.

Tensor parallelism over the ``model`` mesh axis by naming convention on the
parameter tree paths; data parallelism over ``data`` (+ ``pod``). Optional
ZeRO-style parameter sharding (``zero_axis``) additionally shards the
*other* matrix dim of large 2D weights over a data axis — GSPMD then inserts
the per-layer all-gathers of ZeRO-3/FSDP automatically (the baseline the
paper's ZeRO-CDP variant improves on; see repro.core.zero for the cyclic
point-to-point version).

All rules degrade to replication when a dim is not divisible by the axis
size, so every (arch x mesh) combination lowers.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

PyTree = Any


def _axis_size(mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        n = 1
        for a in axis:
            n *= mesh.shape[a]
        return n
    return mesh.shape[axis]


def _div(dim: int, n: int) -> bool:
    return n > 0 and dim % n == 0


def _spec_for_leaf(path_names, leaf, mesh, model_axis, zero_axis) -> P:
    """Choose a PartitionSpec for one parameter leaf."""
    msz = _axis_size(mesh, model_axis)
    zsz = _axis_size(mesh, zero_axis)
    name = path_names[-1] if path_names else ""
    shape = leaf.shape

    def ok(i, n=msz):
        return i < len(shape) and _div(shape[i], n)

    last = len(shape) - 1

    # --- embeddings / heads -------------------------------------------------
    if name == "embed":
        spec = [None, None]
        if _div(shape[0], msz):
            spec[0] = model_axis
        if zero_axis and _div(shape[1], zsz):
            spec[1] = zero_axis
        return P(*spec)
    if name in ("lm_head", "frontend_proj"):
        spec = [None, None]
        if _div(shape[1], msz):
            spec[1] = model_axis
        if zero_axis and _div(shape[0], zsz):
            spec[0] = zero_axis
        return P(*spec)

    # --- norms / small vectors ---------------------------------------------
    if leaf.ndim <= 1 or name in ("scale", "bias", "A_log", "D", "dt_bias",
                                  "gate_bias", "norm", "b", "conv_b",
                                  "q_norm", "kv_norm"):
        return P(*([None] * leaf.ndim))

    # --- MoE expert banks [L, E, din, dout] ---------------------------------
    if name in ("w1", "w3", "w2") and leaf.ndim == 4:
        L, E, di, do = shape
        if _div(E, msz):
            spec = [None, model_axis, None, None]
            if zero_axis and _div(do if name != "w2" else di, zsz):
                if name != "w2":
                    spec[3] = zero_axis
                else:
                    spec[2] = zero_axis
            return P(*spec)
        if name != "w2" and _div(do, msz):
            return P(None, None, None, model_axis)
        if name == "w2" and _div(di, msz):
            return P(None, None, model_axis, None)
        return P(None, None, None, None)
    if name == "router":
        return P(*([None] * leaf.ndim))

    # --- generic stacked / unstacked matrices -------------------------------
    # Convention: "column-parallel" (out-dim sharded) for input projections,
    # "row-parallel" (in-dim sharded) for output projections.
    row_parallel = name in ("wo", "w2", "down", "out_proj")
    mat_dims = (last - 1, last)

    spec = [None] * leaf.ndim
    if row_parallel:
        if _div(shape[mat_dims[0]], msz):
            spec[mat_dims[0]] = model_axis
        if zero_axis and _div(shape[mat_dims[1]], zsz):
            spec[mat_dims[1]] = zero_axis
    else:
        if _div(shape[mat_dims[1]], msz):
            spec[mat_dims[1]] = model_axis
        if zero_axis and _div(shape[mat_dims[0]], zsz):
            spec[mat_dims[0]] = zero_axis
    return P(*spec)


def _path_names(path) -> Tuple[str, ...]:
    names = []
    for k in path:
        n = getattr(k, "key", None)
        if isinstance(n, str):
            names.append(n)
    return tuple(names)


def param_pspecs(params: PyTree, mesh, model_axis="model",
                 zero_axis=None) -> PyTree:
    return jax.tree_util.tree_map_with_path(
        lambda p, l: _spec_for_leaf(_path_names(p), l, mesh, model_axis,
                                    zero_axis), params)


def param_shardings(params: PyTree, mesh, model_axis="model",
                    zero_axis=None) -> PyTree:
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_pspecs(params, mesh, model_axis, zero_axis))


# ---------------------------------------------------------------------------
# Batch / cache
# ---------------------------------------------------------------------------

def batch_pspec(mesh, data_axes=("data",)) -> P:
    """Leading (batch) dim sharded over the data axes (incl. pod if present)."""
    axes = tuple(a for a in data_axes if a in mesh.shape)
    return P(axes if len(axes) > 1 else axes[0])


def batch_sharding(batch: PyTree, mesh, data_axes=("data",)) -> PyTree:
    spec = batch_pspec(mesh, data_axes)

    def shard_one(x):
        if x.ndim == 0:
            return NamedSharding(mesh, P())
        n = _axis_size(mesh, spec[0]) if spec else 1
        if x.shape[0] % max(n, 1) == 0:
            return NamedSharding(mesh, P(*(spec + (None,) * (x.ndim - 1))))
        return NamedSharding(mesh, P())
    return jax.tree.map(shard_one, batch)


def manual_axes(mesh, data_axes=("data",)) -> set:
    """The axes a step's shard_map is manual over: the data axes, plus every
    axis of size 1. A Pallas (Mosaic) kernel lowers only where no mesh axis
    is left to automatic partitioning, and a size-1 axis partitions
    nothing — so on a data-only or one-chip mesh the kernels run inside the
    step, while a model axis of size > 1 stays automatic (GSPMD)."""
    return set(data_axes) | {a for a in mesh.axis_names if mesh.shape[a] == 1}


def batch_manual_pspecs(batch: PyTree, data_axes=("data",)) -> PyTree:
    """Per-leaf specs for a batch entering a shard_map manual over the data
    axes: leading dim sharded, scalars replicated (shared by the tree-layout
    trainer and the ZeRO-CDP stage-streaming step)."""
    ax = tuple(data_axes) if len(data_axes) > 1 else data_axes[0]
    return jax.tree.map(
        lambda x: P(ax) if getattr(x, "ndim", 0) else P(), batch)


def cache_pspecs(cache: PyTree, mesh, data_axes=("data",),
                 model_axis="model", batch: Optional[int] = None) -> PyTree:
    """KV/state caches: shard the batch dim over data. Caches may be stacked
    once ([L, B, ...]) or twice ([P, per, B, ...] for the periodic SSM /
    hybrid stacks). When ``batch`` is given, only a dim equal to it is
    eligible (a stacked layer dim that happens to divide the axis must NOT be
    data-sharded — every device needs every layer's cache)."""
    daxes = tuple(a for a in data_axes if a in mesh.shape)
    dsz = _axis_size(mesh, daxes)

    def spec_one(x):
        spec = [None] * x.ndim
        ax = tuple(daxes) if len(daxes) > 1 else daxes[0]
        for i in range(min(3, x.ndim)):
            if batch is not None and x.shape[i] != batch:
                continue
            if _div(x.shape[i], dsz):
                spec[i] = ax
                break
        return NamedSharding(mesh, P(*spec))
    return jax.tree.map(spec_one, cache)


# ---------------------------------------------------------------------------
# Plan placements (repro.parallel): ZeRO-1 slots and ZeRO-CDP stage chunks
# ---------------------------------------------------------------------------

def zero1_param_pspecs(params: PyTree, mesh, data_axis: str = "data",
                       model_axis: str = "model",
                       zero_axis=None) -> PyTree:
    """Param pspecs with the data axis inserted at each leaf's ring slice
    axis — the layout of reduce-scattered grads and ZeRO-1 optimizer state
    (``placement='zero1'``)."""
    from repro.core import grad_sync
    gps = param_pspecs(params, mesh, model_axis, zero_axis)
    n = mesh.shape[data_axis]
    layout = grad_sync.zero1_layout(params, n, gps)

    def one(leaf, spec, ax):
        entries = list(spec) + [None] * (leaf.ndim - len(spec))
        if ax >= 0:
            entries[ax] = data_axis
        return P(*entries)
    return jax.tree.map(one, params, gps, layout)


def stage_chunk_shardings(tree: PyTree, mesh,
                          data_axis: str = "data") -> PyTree:
    """ZeRO-CDP placement (``placement='stage_sharded'``): every leaf is a
    [n_stages, chunk] stack of per-stage parameter chunks, stage j resident
    on data-rank j."""
    return jax.tree.map(
        lambda _: NamedSharding(mesh, P(data_axis, None)), tree)


def param_slot_keys(state: PyTree, params_like: PyTree) -> set:
    """Optimizer-state entries that are params-shaped trees (momenta,
    first/second moments, ...) — detected structurally against a
    params-structured template, NOT a hardcoded key list, so a new
    optimizer's slots shard correctly instead of silently replicating."""
    pdef = jax.tree.structure(params_like)
    return {k for k, v in state.items()
            if jax.tree.structure(v) == pdef}


def state_shardings(state: PyTree, params_sh: PyTree) -> PyTree:
    """Optimizer state mirrors the parameter shardings; everything that is
    not a params-shaped slot (step counters, scalars) is replicated."""
    mesh = jax.tree.leaves(params_sh)[0].mesh
    slots = param_slot_keys(state, params_sh)
    return {k: (params_sh if k in slots else NamedSharding(mesh, P()))
            for k in state}
