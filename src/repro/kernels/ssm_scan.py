"""Pallas TPU chunked gated-linear-attention scan (Mamba2 SSD / mLSTM core)
— forward AND fused one-pass backward.

Layout: q,k [BH, S, dk]; v [BH, S, dv]; g [BH, S] (log-decay <= 0). Inside
the pallas_call g (and dg) travel as [BH, S, 1] columns: a (chunk,) block of
a [BH, S] array breaks the TPU's (8, 128) tiling rule for chunk < 128.
Per-row quantities (g, its cumsum, the decay weights) are [chunk, 1] columns
in-kernel; prefix/suffix sums are masked [chunk, chunk] reductions, and
row <-> column moves use the exact diagonal trick of ``kernels.tiles``.

Forward — grid (BH, nchunks) with the chunk axis sequential: the [dk, dv]
recurrent state lives in VMEM scratch and is carried across chunk
iterations; within a chunk the recurrence becomes two MXU contractions plus
a masked [Q, Q] contraction — the state-space-duality form, tiled so the
working set (3 chunk tiles + state + [Q,Q] mask) fits VMEM. Rows at or past
``s_valid`` (the block-padding tail) are masked out of the state update, so
the final state — emitted as a second output — is exact for any padding.
In training the forward also checkpoints the state ENTERING each chunk
(``collect_states=True``), the residual the backward consumes.

Backward — one reverse chunk-scan kernel (grid (BH, nchunks), iterated
newest chunk first via index-map remapping): the [dk, dv] adjoint state
``D_c = dL/dState_c`` lives in VMEM scratch and is carried backwards across
chunks, the per-chunk checkpointed forward states replay the inter-chunk
term, and all four gradients come out in a single pass:

    dq_i = (dSc @ k)_i + e_i * (dy_i @ P^T)        dSc = (dy v^T) . dmat
    dk_j = (dSc^T q)_j + w_j * (v_j @ D^T)
    dv_j = (A^T dy)_j  + w_j * (k_j @ D)           A = (q k^T) . dmat
    dg   = reverse-cumsum of  q.dq - k.dk          (suffix carried across
                                                    chunks in scratch)

where e = exp(cumsum g), w = exp(cum[-1] - cum), P is the chunk's entering
state and the dg identity dL/dG_t = q_t.dq_t - k_t.dk_t (G = global cumsum
of g) turns the decay gradient into two row-sums — no second forward, no
recompute through the jnp scan.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiles import col_to_row


def _ij(chunk: int):
    return (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0),
            jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))


def _chunk_decays(g_raw, rows_valid):
    """(cum, cum_row, e, a, w) of one chunk — [Q, 1] columns except the
    [1, Q] ``cum_row`` and the [1, 1] ``a`` — with padded rows masked out of
    the state path: g forced to 0 (decay 1) and w forced to 0 (no kv
    contribution)."""
    chunk = g_raw.shape[0]
    ii, jj = _ij(chunk)
    g = jnp.where(rows_valid, g_raw.astype(jnp.float32), 0.0)
    cum = jnp.sum(jnp.where(jj <= ii, col_to_row(g), 0.0), axis=1,
                  keepdims=True)              # inclusive prefix sum
    last = cum[chunk - 1:chunk]               # [1, 1]
    e = jnp.exp(cum)
    a = jnp.exp(last)
    w = jnp.where(rows_valid, jnp.exp(last - cum), 0.0)
    return cum, col_to_row(cum), e, a, w


def _rows_valid(chunk_id, chunk: int, s_valid: int):
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
    return chunk_id * chunk + rows < s_valid


def _intra_decay(cum, cum_row):
    """[Q, Q] lower-triangular decay matrix exp(cum_i - cum_j), j <= i."""
    ii, jj = _ij(cum.shape[0])
    return jnp.exp(jnp.where(jj <= ii, cum - cum_row, -jnp.inf))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _gla_fwd_kernel(q_ref, k_ref, v_ref, g_ref, o_ref, fin_ref, *rest,
                    chunk: int, nc: int, s_valid: int, collect: bool):
    if collect:
        states_ref, state_ref = rest
    else:
        (state_ref,) = rest
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    q = q_ref[0].astype(jnp.float32)          # [Q, dk]
    k = k_ref[0].astype(jnp.float32)          # [Q, dk]
    v = v_ref[0].astype(jnp.float32)          # [Q, dv]
    cum, cum_row, e, a, w = _chunk_decays(g_ref[0],
                                          _rows_valid(c, chunk, s_valid))

    # intra-chunk: A_ij = (q_i . k_j) * exp(cum_i - cum_j), j <= i
    scores = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    y = jax.lax.dot(scores * _intra_decay(cum, cum_row), v,
                    preferred_element_type=jnp.float32)

    # carried-state contribution and state update
    s0 = state_ref[...]                       # [dk, dv]
    if collect:
        states_ref[0, 0] = s0                 # checkpoint: state entering c
    y = y + jax.lax.dot(q * e, s0, preferred_element_type=jnp.float32)
    s_local = jax.lax.dot_general(k * w, v,
                                  (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    state_ref[...] = a * s0 + s_local
    o_ref[0] = y.astype(o_ref.dtype)

    @pl.when(c == nc - 1)
    def _emit_final():
        fin_ref[0] = state_ref[...]


def gla_scan_kernel(q, k, v, g, *, chunk: int = 64, s_valid: int = 0,
                    collect_states: bool = False, interpret: bool = False):
    """Forward chunk scan. S must be a multiple of chunk (ops.py pads);
    ``s_valid`` is the true length — padded rows never touch the state.

    g: [BH, S]. Returns (y [BH, S, dv], final_state [BH, dk, dv] f32),
    plus the per-chunk entering states [BH, nc, dk, dv] f32 in the middle
    when ``collect_states`` (the backward's residual):
    (y, states, final_state)."""
    BH, S, dk = q.shape
    dv = v.shape[-1]
    nc = S // chunk

    kernel = functools.partial(_gla_fwd_kernel, chunk=chunk, nc=nc,
                               s_valid=s_valid or S, collect=collect_states)
    out_specs = [
        pl.BlockSpec((1, chunk, dv), lambda b, c: (b, c, 0)),
        pl.BlockSpec((1, dk, dv), lambda b, c: (b, 0, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((BH, S, dv), q.dtype),
        jax.ShapeDtypeStruct((BH, dk, dv), jnp.float32),
    ]
    if collect_states:
        out_specs.append(
            pl.BlockSpec((1, 1, dk, dv), lambda b, c: (b, c, 0, 0)))
        out_shape.append(
            jax.ShapeDtypeStruct((BH, nc, dk, dv), jnp.float32))

    outs = pl.pallas_call(
        kernel,
        name="gla_scan_fwd",
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, dk), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, dk), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, dv), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, c: (b, c, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, g[..., None])
    y, fin = outs[0], outs[1]
    return (y, outs[2], fin) if collect_states else (y, fin)


# ---------------------------------------------------------------------------
# Backward: one reverse chunk scan, adjoint state in VMEM scratch
# ---------------------------------------------------------------------------

def _gla_bwd_kernel(q_ref, k_ref, v_ref, g_ref, st_ref, dy_ref,
                    dq_ref, dk_ref, dv_ref, dg_ref, dstate_ref, carry_ref, *,
                    chunk: int, nc: int, s_valid: int):
    r = pl.program_id(1)                      # 0 = NEWEST chunk (index maps
    c = nc - 1 - r                            # walk the chunks reversed)

    @pl.when(r == 0)
    def _init():
        dstate_ref[...] = jnp.zeros_like(dstate_ref)
        carry_ref[...] = jnp.zeros_like(carry_ref)

    q = q_ref[0].astype(jnp.float32)          # [Q, dk]
    k = k_ref[0].astype(jnp.float32)          # [Q, dk]
    v = v_ref[0].astype(jnp.float32)          # [Q, dv]
    dy = dy_ref[0].astype(jnp.float32)        # [Q, dv]
    rows_valid = _rows_valid(c, chunk, s_valid)
    cum, cum_row, e, a, w = _chunk_decays(g_ref[0], rows_valid)
    dmat = _intra_decay(cum, cum_row)

    P = st_ref[0, 0]                          # state entering this chunk
    D = dstate_ref[...]                       # adjoint of the LEAVING state

    scores = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    dsc = jax.lax.dot_general(dy, v, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32) * dmat

    dq = jax.lax.dot(dsc, k, preferred_element_type=jnp.float32) + \
        e * jax.lax.dot_general(dy, P, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
    dk = jax.lax.dot_general(dsc, q, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32) + \
        w * jax.lax.dot_general(v, D, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
    dv = jax.lax.dot_general(scores * dmat, dy, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32) + \
        w * jax.lax.dot(k, D, preferred_element_type=jnp.float32)

    # decay gradient: dL/dG_t = q_t.dq_t - k_t.dk_t, dg = suffix-sum of dG
    # (within-chunk masked suffix sum + the cross-chunk suffix carried in a
    # [1, 1] scratch)
    dG = jnp.where(rows_valid,
                   jnp.sum(q * dq, axis=-1, keepdims=True) -
                   jnp.sum(k * dk, axis=-1, keepdims=True), 0.0)   # [Q, 1]
    ii, jj = _ij(chunk)
    suffix = jnp.sum(jnp.where(jj >= ii, col_to_row(dG), 0.0), axis=1,
                     keepdims=True)
    carry = carry_ref[...]
    dg = carry + suffix
    carry_ref[...] = carry + jnp.sum(dG, axis=0, keepdims=True)

    # adjoint state entering this chunk, for the next (earlier) iteration
    dstate_ref[...] = a * D + jax.lax.dot_general(
        q * e, dy, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    dq_ref[0] = dq.astype(dq_ref.dtype)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)
    dg_ref[0] = dg.astype(dg_ref.dtype)


def gla_scan_bwd_kernel(q, k, v, g, states, dy, *, chunk: int = 64,
                        s_valid: int = 0, interpret: bool = False):
    """Fused VJP of :func:`gla_scan_kernel` (zero initial state, y output).
    ``states``: the per-chunk entering states checkpointed by the forward.
    Returns (dq, dk, dv, dg) in the input dtypes — one reverse pass."""
    BH, S, dk = q.shape
    dv = v.shape[-1]
    nc = S // chunk

    kernel = functools.partial(_gla_bwd_kernel, chunk=chunk, nc=nc,
                               s_valid=s_valid or S)
    rev = lambda b, r: (b, nc - 1 - r, 0)

    dq, dk, dv, dg = pl.pallas_call(
        kernel,
        name="gla_scan_bwd",
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, dk), rev),
            pl.BlockSpec((1, chunk, dk), rev),
            pl.BlockSpec((1, chunk, dv), rev),
            pl.BlockSpec((1, chunk, 1), rev),
            pl.BlockSpec((1, 1, dk, dv), lambda b, r: (b, nc - 1 - r, 0, 0)),
            pl.BlockSpec((1, chunk, dv), rev),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, dk), rev),
            pl.BlockSpec((1, chunk, dk), rev),
            pl.BlockSpec((1, chunk, dv), rev),
            pl.BlockSpec((1, chunk, 1), rev),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct(g.shape + (1,), g.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32),
                        pltpu.VMEM((1, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, g[..., None], states, dy)
    return dq, dk, dv, dg[..., 0]
