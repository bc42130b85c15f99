"""ZeRO-CDP (paper Sec. 4.4) on real registered architectures.

``core/zero.py`` demonstrates the schedule on a homogeneous toy stack; this
module is the production path behind ``--plan zero_cdp``: it works for ANY
architecture the model registry knows, by partitioning the *flattened*
parameter vector into N layer-group stages using
``models.model.param_stage_ids`` (embedding -> stage 0, stacked layer axes
-> even split, head/final norm -> stage N-1).

Layout
    The flattened parameters, ordered by layer-group stage id, form one
    stream that is cut into N equal contiguous chunks; data-parallel rank
    r persistently owns chunk r as an f32 master. The global state is a
    ``[N, chunk]`` array sharded over the data axis — parameters AND
    optimizer state live at Pp/N per rank (the ZeRO placement; boundaries
    are balanced by element count, so no rank idles on a short stage).

Streaming (forward)
    The chunks travel the ring point-to-point: N-1 ``lax.ppermute`` hops,
    one per tick; at tick t rank r holds stage (r - t) mod N and scatters
    it into its local reconstruction buffer. No collective broadcast — the
    HLO contains ``collective-permute`` ops where ZeRO-DP emits a
    per-stage ``all-gather`` (asserted in tests/test_parallel_plan.py).

Gradient merge (backward)
    ``jax.grad`` through the permute chain transposes it automatically:
    each rank's loss cotangent flows back along the reversed ring, and the
    contributions of ALL micro-batches to stage j accumulate at stage j's
    owner — the paper's "model states are communicated to a single GPU at
    the next time step", with no gradient collective at all.

Update rule
    The cyclic rotation makes parameters one step stale by the time the
    gradient lands (``cdp_v1``): the step streams theta_{t-1} while the
    owner updates theta_t. ``rule='dp'`` streams theta_t instead (exact DP
    numerics with ZeRO placement + point-to-point movement) — that variant
    anchors the parity test against plain DP.
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.grad_sync import _ring_perm
from repro.core.schedule import RULE_CDP_V1
from repro.core.update_rules import needs_prev_params
from repro.models import model as model_mod
from repro.parallel.plan import ParallelPlan
from repro.sharding import specs as sh

PyTree = Any


# ---------------------------------------------------------------------------
# Static stage layout: flattened params -> N layer-group stage chunks
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class StageSegment:
    """One contiguous run of a leaf's flat elements in a single stage."""
    leaf: int       # index in tree-flatten order
    start: int      # flat element range within the leaf
    stop: int
    stage: int
    offset: int     # element offset inside the stage's chunk


@dataclasses.dataclass(frozen=True)
class StageLayout:
    n: int
    chunk: int                       # elements per balanced stage chunk
    stage_sizes: tuple               # real (unpadded) elements per chunk
    segments: tuple                  # StageSegments, leaf-major order
    treedef: Any
    shapes: tuple
    dtypes: tuple

    @property
    def total(self) -> int:
        return sum(self.stage_sizes)


def _leading_stage_rows(sid: np.ndarray, shape: tuple):
    """Collapse a broadcastable stage-id array to (k, per-row stages) where
    the row index space is ``shape[:k]`` (k = last non-singleton id dim +1,
    covering both [L,1,..] and the double-stacked [P,per,1,..] layouts)."""
    k = max(i + 1 for i in range(sid.ndim) if sid.shape[i] > 1)
    rows = np.broadcast_to(sid.reshape(sid.shape[:k]), shape[:k]).ravel()
    return k, rows


@lru_cache(maxsize=8)
def build_stage_layout(cfg, n: int) -> StageLayout:
    """Partition ``cfg``'s parameter tree into ``n`` layer-group stages.

    Pure shape computation: parameters come from ``jax.eval_shape`` over
    ``init_params`` and stage assignments from ``param_stage_ids`` — stacked
    layer axes are split row-wise, so one leaf may span several stages.
    """
    shapes = jax.eval_shape(
        lambda: model_mod.init_params(cfg, jax.random.PRNGKey(0)))
    ids = model_mod.param_stage_ids(cfg, shapes, n)
    leaves, treedef = jax.tree.flatten(shapes)
    id_leaves = jax.tree.leaves(ids)

    raw = []                                     # (leaf, start, stop, stage)
    for li, (leaf, sid) in enumerate(zip(leaves, id_leaves)):
        sid_np = np.asarray(sid)
        size = int(np.prod(leaf.shape)) if leaf.shape else 1
        if sid_np.size == 1:
            raw.append((li, 0, size, int(sid_np.reshape(()))))
            continue
        k, rows = _leading_stage_rows(sid_np, leaf.shape)
        rest = size // int(np.prod(leaf.shape[:k]))
        run0 = 0
        for r in range(1, len(rows) + 1):
            if r == len(rows) or rows[r] != rows[run0]:
                raw.append((li, run0 * rest, r * rest, int(rows[run0])))
                run0 = r

    # Stage-id-major stream, BALANCED cut: concatenating the runs in
    # layer-group (stage-id) order preserves the paper's cyclic pipeline
    # order, but the raw groups are badly imbalanced (the embedding pins
    # most bytes to stage 0, short stacks leave stages empty). The stream
    # is therefore re-cut into N equal contiguous chunks — legal because
    # the supported update rules (dp / cdp_v1) apply uniform staleness, so
    # chunk boundaries carry no numerics, only ring-hop bytes. Per-leaf
    # run order stays monotonic in flat offset (stage ids increase with
    # the row index inside a leaf), which chunk/unchunk rely on.
    stage_major = [seg for st in range(n) for seg in raw if seg[3] == st]
    total = sum(b - a for _, a, b, _ in stage_major)
    chunk = max(-(-total // n), 1)
    segs = []
    g = 0                                        # offset in the stream
    for li, a, b, _ in stage_major:
        while a < b:
            c = g // chunk
            take = min(b - a, (c + 1) * chunk - g)
            segs.append(StageSegment(li, a, a + take, c, g - c * chunk))
            a += take
            g += take
    sizes = [max(0, min(chunk, total - j * chunk)) for j in range(n)]
    return StageLayout(
        n=n, chunk=chunk, stage_sizes=tuple(sizes),
        segments=tuple(segs), treedef=treedef,
        shapes=tuple(l.shape for l in leaves),
        dtypes=tuple(l.dtype for l in leaves))


def chunk_params(layout: StageLayout, params: PyTree) -> jnp.ndarray:
    """Params pytree -> [n, chunk] f32 master chunks (balanced cut of the
    stage-ordered stream; chunk j at row j)."""
    leaves = jax.tree.leaves(params)
    flats = [l.astype(jnp.float32).reshape(-1) for l in leaves]
    parts = [[] for _ in range(layout.n)]
    for s in layout.segments:                    # offsets follow this order
        parts[s.stage].append(flats[s.leaf][s.start:s.stop])
    rows = []
    for ps in parts:
        v = jnp.concatenate(ps) if ps else jnp.zeros((0,), jnp.float32)
        rows.append(jnp.pad(v, (0, layout.chunk - v.shape[0])))
    return jnp.stack(rows)


def unchunk_params(layout: StageLayout, stages: jnp.ndarray) -> PyTree:
    """[n, chunk] stage chunks -> params pytree (cast to each leaf dtype)."""
    pieces = [[] for _ in layout.shapes]
    for s in layout.segments:                    # leaf-major order
        pieces[s.leaf].append(stages[s.stage, s.offset:s.offset + s.stop - s.start])
    out = []
    for shape, dtype, ps in zip(layout.shapes, layout.dtypes, pieces):
        v = jnp.concatenate(ps) if len(ps) > 1 else ps[0]
        out.append(v.reshape(shape).astype(dtype))
    return jax.tree.unflatten(layout.treedef, out)


def params_from_state(cfg, state: PyTree, n: int) -> PyTree:
    """Materialise the full parameter pytree from a ZeRO-CDP train state
    (host-side: eval / export / comparison against a tree-layout run)."""
    layout = build_stage_layout(cfg, n)
    return unchunk_params(layout, state["params"]["stages"])


# ---------------------------------------------------------------------------
# Elastic re-cut: [n_old, chunk_old] -> [n_new, chunk_new]
# ---------------------------------------------------------------------------

def recut_chunks(layout_old: StageLayout, layout_new: StageLayout,
                 stages: np.ndarray) -> np.ndarray:
    """Re-cut one ``[n_old, chunk_old]`` chunk stack to the new layout
    WITHOUT a round-trip through the parameter pytree. The per-leaf flat
    buffers are reassembled from the old segments and re-sliced by the new
    ones, which handles the two traps a naive stream split would hit:
    stage assignment depends on n (``param_stage_ids(cfg, shapes, n)``
    reorders the stream), and ``unchunk_params`` casts to each leaf's
    dtype — fatal for f32 optimizer slots of a bf16 parameter. Here the
    arrays never leave the chunk dtype. Host-side numpy: recovery and
    rejoin run between steps, not inside jit."""
    if layout_old.treedef != layout_new.treedef:
        raise ValueError("recut_chunks: layouts describe different "
                         "parameter trees")
    stages = np.asarray(stages)
    if stages.shape != (layout_old.n, layout_old.chunk):
        raise ValueError(
            f"recut_chunks: expected [{layout_old.n}, {layout_old.chunk}] "
            f"chunks, got {stages.shape}")
    pieces = [[] for _ in layout_old.shapes]
    for s in layout_old.segments:                # leaf-major order
        pieces[s.leaf].append(
            stages[s.stage, s.offset:s.offset + s.stop - s.start])
    flats = [np.concatenate(ps) if len(ps) > 1 else ps[0] for ps in pieces]
    parts = [[] for _ in range(layout_new.n)]
    for s in layout_new.segments:                # offsets follow this order
        parts[s.stage].append(flats[s.leaf][s.start:s.stop])
    rows = []
    for ps in parts:
        v = np.concatenate(ps) if ps else np.zeros((0,), stages.dtype)
        rows.append(np.pad(v, (0, layout_new.chunk - v.shape[0])))
    return np.stack(rows)


def recut_stage_state(cfg, state: PyTree, n_old: int, n_new: int) -> PyTree:
    """Re-cut a whole ZeRO-CDP train state across a ring resize: every
    ``[n_old, chunk_old]`` leaf (master chunks, ``params_prev``, optimizer
    slots — matched by shape, not by key, so new optimizers' slots recut
    too) moves to the ``n_new`` layout; scalars (``step``, adamw's ``t``)
    pass through untouched. Input and output are host trees."""
    lo = build_stage_layout(cfg, n_old)
    ln = build_stage_layout(cfg, n_new)

    def one(x):
        arr = np.asarray(x)
        if arr.shape == (lo.n, lo.chunk):
            return recut_chunks(lo, ln, arr)
        return arr

    return jax.tree.map(one, state)


# ---------------------------------------------------------------------------
# The point-to-point stage ring
# ---------------------------------------------------------------------------

def stream_stages(my_chunk: jnp.ndarray, axis: str, n: int) -> jnp.ndarray:
    """Cyclic parameter streaming inside a shard_map manual over ``axis``.

    ``my_chunk`` is this rank's stage (stage index == rank index). N-1
    unrolled ``ppermute`` hops move every chunk one neighbour per tick — at
    tick t rank r holds stage (r - t) mod N and scatters it into its local
    [n, chunk] reconstruction. Each hop is a distinct ``collective-permute``
    HLO op; the transpose (gradient path) is the reversed ring, which
    accumulates every micro-batch's stage-j gradient at stage j's owner.
    """
    r = jax.lax.axis_index(axis)
    perm = _ring_perm(n)
    out = jnp.zeros((n,) + my_chunk.shape, my_chunk.dtype)
    buf = my_chunk
    for t in range(n):
        j = jax.lax.rem(r - t + n, n)
        out = jax.lax.dynamic_update_slice(out, buf[None], (j, 0))
        if t < n - 1:
            buf = jax.lax.ppermute(buf, axis, perm)
    return out


# ---------------------------------------------------------------------------
# Train-state plumbing (called by core.trainer under placement=stage_sharded)
# ---------------------------------------------------------------------------

def init_stage_state(cfg, plan: ParallelPlan, params: PyTree, opt,
                     n: int) -> PyTree:
    layout = build_stage_layout(cfg, n)
    chunks = {"stages": chunk_params(layout, params)}
    state = {"params": chunks, "opt": opt.init(chunks),
             "step": jnp.zeros((), jnp.int32)}
    if needs_prev_params(plan.rule):
        state["params_prev"] = jax.tree.map(jnp.copy, chunks)
    return state


def make_train_step(cfg, trainer, plan: ParallelPlan, mesh, opt,
                    loss_fn: Optional[Callable] = None):
    """Builds the ZeRO-CDP train_step(state, batch) -> (state, metrics).

    Returns the bare step function; ``core.trainer.make_train_step`` wraps
    it into the public (step, state_sharding_fn, batch_sharding_fn) triple
    (trainer owns the placement specs for every plan). ``trainer`` is the
    TrainerConfig (axes / lr / clip knobs)."""
    axis = trainer.data_axis
    n = mesh.shape[axis]
    # plan/mesh validation is core.trainer.make_train_step's job (the one
    # authoritative call, with the trainer's axis names)
    if trainer.seq_parallel:
        raise ValueError(
            "seq_parallel is not supported with stage-streamed plans "
            "(the reconstruction runs outside the activation-sharding "
            "scope); drop it or pick a tree-layout plan")
    layout = build_stage_layout(cfg, n)
    loss_fn = loss_fn or (lambda p, b: model_mod.loss_fn(cfg, p, b))
    lr_fn = trainer.lr_schedule or (lambda s: 1e-3)
    comm_dtype = jnp.dtype(trainer.grad_comm_dtype)
    use_prev = needs_prev_params(plan.rule)
    assert plan.rule in ("dp", RULE_CDP_V1)

    def grad_shard(src_chunk, batch):
        # src_chunk: [1, chunk] — this rank's stage of theta_{t-1} (cdp_v1)
        # or theta_t (dp). Differentiating through the streaming chain makes
        # the transposed ring deliver sum_r dL_r/d(stage) to the owner.
        # Chunks travel the ring in grad_comm_dtype (both directions: the
        # transpose of the cast is a cast); the f32 master stays local.
        def local_loss(my, b):
            streamed = stream_stages(my[0].astype(comm_dtype), axis, n)
            params = unchunk_params(layout, streamed)
            return loss_fn(params, b)

        (loss, metrics), g = jax.value_and_grad(local_loss, has_aux=True)(
            src_chunk, batch)
        g = g / n                              # transpose sums; want the mean
        return g, jax.lax.pmean(loss, axis), jax.lax.pmean(metrics, axis)

    def train_step(state, batch):
        chunks = state["params"]["stages"]
        src = state["params_prev"]["stages"] if use_prev else chunks
        grads, loss, metrics = jax.shard_map(
            grad_shard, mesh=mesh,
            in_specs=(P(axis, None), sh.batch_manual_pspecs(batch, (axis,))),
            out_specs=(P(axis, None), P(), P()),
            axis_names=sh.manual_axes(mesh, (axis,)),
            check_vma=False)(src, batch)
        if trainer.grad_clip:
            gnorm = jnp.sqrt(jnp.sum(grads.astype(jnp.float32) ** 2))
            scale = jnp.minimum(1.0, trainer.grad_clip / (gnorm + 1e-9))
            grads = grads * scale
        lr = lr_fn(state["step"])
        new_chunks, new_opt = opt.update({"stages": grads}, state["opt"],
                                         {"stages": chunks}, lr)
        new_state = {"params": new_chunks, "opt": new_opt,
                     "step": state["step"] + 1}
        if use_prev:
            new_state["params_prev"] = {"stages": chunks}
        metrics = dict(metrics)
        metrics["lr"] = lr
        return new_state, metrics

    return train_step
