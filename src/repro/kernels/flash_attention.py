"""Pallas TPU flash-attention (training forward AND backward), causal +
sliding window, GQA-aware via the wrapper in ops.py — with BLOCK-SPARSE
grids: fully-masked (query-block, kv-block) tiles are never visited.

Layout: q [BH, Sq, d], k/v [BKV, Sk, d] with BH = batch*heads,
BKV = batch*kv_heads.

Grid structure — every kernel iterates a host-built tile plan
(:func:`flash_grid_plan`) instead of the dense (nq, nk) rectangle: the plan
enumerates exactly the (iq, jk) pairs with any unmasked element (causal ->
the lower block triangle jk <= iq; sliding window -> a constant-width band
of ~ceil(window/bk)+1 kv blocks per q block; non-causal -> the full
rectangle), and the kernels walk it as a 1D ragged axis whose block indices
come from scalar-prefetched arrays (``pltpu.PrefetchScalarGridSpec``).
Per-tile metadata flags mark the first/last tile of each accumulator group
and whether the tile is FULL — ``_tile_mask`` is only evaluated on the
diagonal/boundary tiles; interior tiles skip masking entirely.

Forward — grid (BH, T): the online-softmax accumulators (m, l, acc) live in
VMEM scratch and persist across the kv tiles of one (bh, iq) group; the
per-row logsumexp is written out as a second output so the backward pass can
recompute the probabilities blockwise (FlashAttention-2 residual).

Backward — two kernels, both recomputing scores from (q, k, lse) in VMEM:

  * dq: grid (BH, T) over the same plan; a [bq, d] accumulator persists
    across the kv tiles of one query block. ds = p * (dp - delta) * scale,
    dq += ds @ k.
  * dk/dv: grid (BKV, T2, G) where T2 is the plan transposed (tiles ordered
    by kv block, then q block) and G is the GQA query-group axis innermost:
    the [bk, d] accumulators sum across every query head of the kv head's
    group AND every visited query block — kv blocks no q block attends to
    get one masked sentinel tile so their dk/dv are written as exact zeros.

``delta = sum(dO * O, axis=-1)`` is precomputed by the caller (ops.py) — the
standard separate-pass trick that keeps both backward kernels matmul-only.

Block shapes are multiples of 128 on the lane dim for MXU alignment (ops.py
pads); padded kv positions are masked via ``sk_valid`` (tiles touching the
padded tail are never marked FULL) and padded q rows are harmless because
their output rows are sliced off (forward) and their dO rows are zero
(backward).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiles import col_to_row, row_to_col

NEG_INF = -1e30

# tile metadata bits (host-packed into the plan's int32 meta arrays)
_FIRST = 1   # first tile of this accumulator group (init scratch)
_LAST = 2    # last tile of this group (write outputs)
_FULL = 4    # no masked element in the tile (skip _tile_mask)


def _tile_mask(iq, jk, *, bq, bk, causal, window, q_offset, sk):
    """[bq, bk] validity mask of one (query-block, kv-block) tile.
    ``iq``/``jk`` may be traced scalars (read from the prefetched plan)."""
    q_pos = q_offset + iq * bq + jax.lax.broadcasted_iota(
        jnp.int32, (bq, bk), 0)
    k_pos = jk * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    valid = k_pos < sk
    if causal:
        valid = valid & (k_pos <= q_pos)
    if window:
        valid = valid & (k_pos > q_pos - window)
    return valid


# ---------------------------------------------------------------------------
# Host-side tile plan
# ---------------------------------------------------------------------------

def _group_meta(keys, full):
    """Pack FIRST/LAST/FULL flags for a pair list grouped by ``keys`` (the
    accumulator-owning block index, already contiguous)."""
    n = len(keys)
    meta = np.where(full, _FULL, 0).astype(np.int32)
    if n:
        first = np.ones(n, bool)
        first[1:] = keys[1:] != keys[:-1]
        last = np.ones(n, bool)
        last[:-1] = keys[1:] != keys[:-1]
        meta |= np.where(first, _FIRST, 0).astype(np.int32)
        meta |= np.where(last, _LAST, 0).astype(np.int32)
    return meta


@functools.lru_cache(maxsize=256)
def flash_grid_plan(Sq: int, Sk: int, bq: int, bk: int, causal: bool,
                    window: int, q_offset: int, sk_valid: int):
    """Block-sparse tile plan shared by the forward, dq and dk/dv kernels.

    Enumerates the (iq, jk) tiles with at least one unmasked (q_pos, k_pos)
    pair under causal/window/sk_valid masking, in two orders:

      * ``qblk``/``kblk``/``meta`` — row-major (by q block), for the forward
        and dq kernels whose accumulators are per q block;
      * ``kblk2``/``qblk2``/``meta2`` — column-major (by kv block), for the
        dk/dv kernel whose accumulators are per kv block.

    Tiles fully inside the mask are flagged ``_FULL`` (the kernels skip
    ``_tile_mask`` there). Every output block is guaranteed at least one
    tile in the enumeration order that writes it — and ONLY there: a q
    block with no valid kv tile (only possible for padded q rows) gets a
    masked sentinel in the row-major list, a kv block no q attends to (its
    dk/dv are exact zeros) gets one in the column-major list, so neither
    sentinel class inflates the other kernels' grids.

    ``visited``/``visited_dkv``/``total`` are the pruning ledger the
    benchmarks audit: (iq, jk) tiles walked per order vs the dense nq*nk
    rectangle.
    """
    nq, nk = Sq // bq, Sk // bk
    sk = sk_valid or Sk
    iq = np.arange(nq)[:, None]
    jk = np.arange(nk)[None, :]
    q_lo = q_offset + iq * bq
    q_hi = q_lo + bq - 1
    k_lo = jk * bk
    k_hi = k_lo + bk - 1

    visit = np.broadcast_to(k_lo < sk, (nq, nk)).copy()
    if causal:
        visit &= k_lo <= q_hi
    if window:
        visit &= k_hi > q_lo - window

    full = np.broadcast_to(k_hi < sk, (nq, nk)).copy()
    if causal:
        full &= k_hi <= q_lo
    if window:
        full &= k_lo > q_hi - window
    full &= visit

    # each sentinel class goes ONLY to the enumeration order that needs it
    # (a dkv sentinel walked by fwd/dq would erase the pruning win there)
    visit_fwd = visit.copy()
    empty_q = ~visit.any(axis=1)
    if empty_q.any():                       # padded q rows: force one tile
        visit_fwd[empty_q, 0] = True
    visit_dkv = visit.copy()
    empty_k = ~visit.any(axis=0)
    if empty_k.any():                       # unattended kv: zeros sentinel
        visit_dkv[nq - 1, empty_k] = True

    rows = np.argwhere(visit_fwd)           # row-major: sorted by (iq, jk)
    qblk, kblk = rows[:, 0].astype(np.int32), rows[:, 1].astype(np.int32)
    meta = _group_meta(qblk, full[rows[:, 0], rows[:, 1]])

    cols = np.argwhere(visit_dkv.T)         # column-major: sorted by (jk, iq)
    kblk2, qblk2 = cols[:, 0].astype(np.int32), cols[:, 1].astype(np.int32)
    meta2 = _group_meta(kblk2, full[cols[:, 1], cols[:, 0]])

    return {"qblk": qblk, "kblk": kblk, "meta": meta,
            "kblk2": kblk2, "qblk2": qblk2, "meta2": meta2,
            "visited": int(len(rows)), "visited_dkv": int(len(cols)),
            "total": int(nq * nk)}


def _plan_args(plan, transposed: bool):
    keys = ("kblk2", "qblk2", "meta2") if transposed else \
        ("qblk", "kblk", "meta")
    return tuple(jnp.asarray(plan[k]) for k in keys)


def _tile_dispatch(meta, s, accumulate, iq, jk, *, bq, bk, causal, window,
                   q_offset, sk):
    """Feed one tile's scores to ``accumulate``: FULL tiles skip the mask
    entirely; boundary tiles get ``_tile_mask`` applied first."""
    @pl.when((meta & _FULL) != 0)
    def _interior():
        accumulate(s)

    @pl.when((meta & _FULL) == 0)
    def _boundary():
        valid = _tile_mask(iq, jk, bq=bq, bk=bk, causal=causal,
                           window=window, q_offset=q_offset, sk=sk)
        accumulate(jnp.where(valid, s, NEG_INF))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _flash_kernel(qblk_ref, kblk_ref, meta_ref, q_ref, k_ref, v_ref,
                  o_ref, lse_ref, m_ref, l_ref, acc_ref, *, scale: float,
                  causal: bool, window: int, q_offset: int, bq: int, bk: int,
                  sk: int):
    t = pl.program_id(1)
    iq, jk, meta = qblk_ref[t], kblk_ref[t], meta_ref[t]

    @pl.when((meta & _FIRST) != 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                  # [bq, d]
    k = k_ref[0].astype(jnp.float32)                  # [bk, d]
    v = v_ref[0].astype(jnp.float32)                  # [bk, dv]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    def _accumulate(s):
        m_prev = m_ref[...]                           # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    _tile_dispatch(meta, s, _accumulate, iq, jk, bq=bq, bk=bk, causal=causal,
                   window=window, q_offset=q_offset, sk=sk)

    @pl.when((meta & _LAST) != 0)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0] = col_to_row(m_ref[...] + jnp.log(l))


def flash_attention_kernel(q, k, v, *, causal: bool = True, window: int = 0,
                           q_offset: int = 0, bq: int = 128, bk: int = 128,
                           group: int = 1, sk_valid: int = 0,
                           interpret: bool = False):
    """q: [BH, Sq, d]; k, v: [BKV, Sk, d]; group = heads per kv head.
    ``sk_valid``: true kv length (padded tail positions are masked).
    ``q_offset``: absolute position of q row 0 (for masking parity with
    ``models.attention.blockwise_attention``).

    Returns (out [BH, Sq, dv], lse [BH, 1, Sq] float32) — lse is the
    per-row logsumexp residual the backward kernels consume, kept lane-major
    so its (1, bq) blocks satisfy the TPU tiling rule. The grid walks only the
    tiles in :func:`flash_grid_plan` (block-sparse under causal/window).
    """
    BH, Sq, d = q.shape
    BKV, Sk, dv = v.shape
    scale = 1.0 / math.sqrt(d)
    plan = flash_grid_plan(Sq, Sk, bq, bk, causal, window, q_offset,
                           sk_valid or Sk)
    qblk, kblk, meta = _plan_args(plan, transposed=False)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        q_offset=q_offset, bq=bq, bk=bk, sk=sk_valid or Sk)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(BH, plan["visited"]),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, t, qb, kb, mt: (b, qb[t], 0)),
            pl.BlockSpec((1, bk, d),
                         lambda b, t, qb, kb, mt, g=group: (b // g, kb[t], 0)),
            pl.BlockSpec((1, bk, dv),
                         lambda b, t, qb, kb, mt, g=group: (b // g, kb[t], 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, dv), lambda b, t, qb, kb, mt: (b, qb[t], 0)),
            pl.BlockSpec((1, 1, bq), lambda b, t, qb, kb, mt: (b, 0, qb[t])),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, dv), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        name="flash_attention_fwd",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sq, dv), q.dtype),
            jax.ShapeDtypeStruct((BH, 1, Sq), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(qblk, kblk, meta, q, k, v)


# ---------------------------------------------------------------------------
# Backward: dq
# ---------------------------------------------------------------------------

def _flash_bwd_dq_kernel(qblk_ref, kblk_ref, meta_ref, q_ref, k_ref, v_ref,
                         do_ref, lse_ref, delta_ref, dq_ref, dq_acc_ref, *,
                         scale: float, causal: bool, window: int,
                         q_offset: int, bq: int, bk: int, sk: int):
    t = pl.program_id(1)
    iq, jk, meta = qblk_ref[t], kblk_ref[t], meta_ref[t]

    @pl.when((meta & _FIRST) != 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    q = q_ref[0].astype(jnp.float32)                  # [bq, d]
    k = k_ref[0].astype(jnp.float32)                  # [bk, d]
    v = v_ref[0].astype(jnp.float32)                  # [bk, dv]
    do = do_ref[0].astype(jnp.float32)                # [bq, dv]
    lse = row_to_col(lse_ref[0])                     # [bq, 1]
    delta = row_to_col(delta_ref[0])                 # [bq, 1]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    def _accumulate(s):
        p = jnp.exp(s - lse)                          # [bq, bk]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale                 # [bq, bk]
        dq_acc_ref[...] += jax.lax.dot(ds, k,
                                       preferred_element_type=jnp.float32)

    _tile_dispatch(meta, s, _accumulate, iq, jk, bq=bq, bk=bk, causal=causal,
                   window=window, q_offset=q_offset, sk=sk)

    @pl.when((meta & _LAST) != 0)
    def _finish():
        dq_ref[0] = dq_acc_ref[...].astype(dq_ref.dtype)


def flash_attention_bwd_dq(q, k, v, do, lse, delta, *, causal: bool = True,
                           window: int = 0, q_offset: int = 0, bq: int = 128,
                           bk: int = 128, group: int = 1, sk_valid: int = 0,
                           interpret: bool = False):
    """dq of flash attention. Shapes as the forward; lse/delta: [BH, 1, Sq]
    f32.
    Returns dq [BH, Sq, d] in q.dtype. Walks the same pruned tile plan as
    the forward."""
    BH, Sq, d = q.shape
    BKV, Sk, dv = v.shape
    scale = 1.0 / math.sqrt(d)
    plan = flash_grid_plan(Sq, Sk, bq, bk, causal, window, q_offset,
                           sk_valid or Sk)
    qblk, kblk, meta = _plan_args(plan, transposed=False)

    kernel = functools.partial(
        _flash_bwd_dq_kernel, scale=scale, causal=causal, window=window,
        q_offset=q_offset, bq=bq, bk=bk, sk=sk_valid or Sk)

    qmap = lambda b, t, qb, kb, mt: (b, qb[t], 0)
    qmap2 = lambda b, t, qb, kb, mt: (b, 0, qb[t])
    kmap = lambda b, t, qb, kb, mt, g=group: (b // g, kb[t], 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(BH, plan["visited"]),
        in_specs=[
            pl.BlockSpec((1, bq, d), qmap),
            pl.BlockSpec((1, bk, d), kmap),
            pl.BlockSpec((1, bk, dv), kmap),
            pl.BlockSpec((1, bq, dv), qmap),
            pl.BlockSpec((1, 1, bq), qmap2),
            pl.BlockSpec((1, 1, bq), qmap2),
        ],
        out_specs=pl.BlockSpec((1, bq, d), qmap),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        name="flash_attention_dq",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((BH, Sq, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(qblk, kblk, meta, q, k, v, do, lse, delta)


# ---------------------------------------------------------------------------
# Backward: dk / dv (GQA reduction over the query-group axis in-kernel)
# ---------------------------------------------------------------------------

def _flash_bwd_dkv_kernel(kblk_ref, qblk_ref, meta_ref, q_ref, k_ref, v_ref,
                          do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                          dk_acc_ref, dv_acc_ref, *, scale: float,
                          causal: bool, window: int, q_offset: int, bq: int,
                          bk: int, ng: int, sk: int):
    t = pl.program_id(1)
    g = pl.program_id(2)
    jk, iq, meta = kblk_ref[t], qblk_ref[t], meta_ref[t]

    @pl.when(((meta & _FIRST) != 0) & (g == 0))
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    q = q_ref[0].astype(jnp.float32)                  # [bq, d]
    k = k_ref[0].astype(jnp.float32)                  # [bk, d]
    v = v_ref[0].astype(jnp.float32)                  # [bk, dv]
    do = do_ref[0].astype(jnp.float32)                # [bq, dv]
    lse = row_to_col(lse_ref[0])                     # [bq, 1]
    delta = row_to_col(delta_ref[0])                 # [bq, 1]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    def _accumulate(s):
        p = jnp.exp(s - lse)                          # [bq, bk]
        # dv += p^T @ dO
        dv_acc_ref[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale                 # [bq, bk]
        # dk += ds^T @ q
        dk_acc_ref[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _tile_dispatch(meta, s, _accumulate, iq, jk, bq=bq, bk=bk, causal=causal,
                   window=window, q_offset=q_offset, sk=sk)

    @pl.when(((meta & _LAST) != 0) & (g == ng - 1))
    def _finish():
        dk_ref[0] = dk_acc_ref[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[...].astype(dv_ref.dtype)


def flash_attention_bwd_dkv(q, k, v, do, lse, delta, *, causal: bool = True,
                            window: int = 0, q_offset: int = 0, bq: int = 128,
                            bk: int = 128, group: int = 1, sk_valid: int = 0,
                            interpret: bool = False):
    """dk, dv of flash attention, accumulated across all ``group`` query
    heads of each kv head (GQA) and every visited query block inside the
    kernel. Walks the plan transposed (tiles grouped by kv block); kv blocks
    outside every q block's mask get a single sentinel tile so their dk/dv
    are written as exact zeros. Returns (dk [BKV, Sk, d], dv [BKV, Sk, dv])
    in k/v dtype."""
    BH, Sq, d = q.shape
    BKV, Sk, dv = v.shape
    scale = 1.0 / math.sqrt(d)
    plan = flash_grid_plan(Sq, Sk, bq, bk, causal, window, q_offset,
                           sk_valid or Sk)
    kblk2, qblk2, meta2 = _plan_args(plan, transposed=True)

    kernel = functools.partial(
        _flash_bwd_dkv_kernel, scale=scale, causal=causal, window=window,
        q_offset=q_offset, bq=bq, bk=bk, ng=group, sk=sk_valid or Sk)

    qmap = lambda b, t, g, kb, qb, mt, G=group: (b * G + g, qb[t], 0)
    qmap2 = lambda b, t, g, kb, qb, mt, G=group: (b * G + g, 0, qb[t])
    kmap = lambda b, t, g, kb, qb, mt: (b, kb[t], 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(BKV, plan["visited_dkv"], group),
        in_specs=[
            pl.BlockSpec((1, bq, d), qmap),
            pl.BlockSpec((1, bk, d), kmap),
            pl.BlockSpec((1, bk, dv), kmap),
            pl.BlockSpec((1, bq, dv), qmap),
            pl.BlockSpec((1, 1, bq), qmap2),
            pl.BlockSpec((1, 1, bq), qmap2),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), kmap),
            pl.BlockSpec((1, bk, dv), kmap),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, dv), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        name="flash_attention_dkv",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((BKV, Sk, d), k.dtype),
            jax.ShapeDtypeStruct((BKV, Sk, dv), v.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(kblk2, qblk2, meta2, q, k, v, do, lse, delta)
