"""Pallas TPU attention over a paged KV cache: block-table-indexed reads.

The serving engine's paged cache stores KV in a fixed pool of ``bs``-token
blocks, ``k/v: [NB+1, KV, bs, dh]`` (the last block is a write-off "trash"
block that absorbs masked writes and backs unallocated table entries; kv
heads sit ahead of the block's tokens so one head's block is a contiguous
``(bs, dh)`` tile, the layout the TPU's block tiling accepts), with
a per-row block table ``table: [B, nb]`` mapping logical block j of row b to
a physical pool slot.

This is the page-table extension of the block-sparse ``flash_grid_plan``
machinery: a page table IS a ragged grid plan, except the visited block
index comes from a scalar-prefetched table instead of the causal/window
enumerator.  Both kernels below keep grid position ``j`` as the *logical*
block (masking is positional: ``pos = j*bs + iota``), and only the BlockSpec
index map goes through the table — ``k_pool[tbl[row*nb + j]]`` — so the
online-softmax math is identical to the dense kernels visiting the same
logical blocks.

Because pool blocks hold whatever a freed/poisoned row left behind, both
kernels zero the V tile outside validity (0 * NaN would otherwise poison the
accumulator through the exactly-zero masked probabilities) and mask S after
the dot, which keeps the valid lanes bit-identical to the dense path.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# decode: one query token per row, KV gathered through the block table
# ---------------------------------------------------------------------------

def _paged_decode_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                         m_ref, l_ref, acc_ref, *, scale: float, bs: int,
                         nb: int, heads: int):
    b = pl.program_id(0)
    jk = pl.program_id(1)

    @pl.when(jk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                     # [1, d]
    k = k_ref[0, 0].astype(jnp.float32)                  # [bs, d]
    v = v_ref[0, 0].astype(jnp.float32)                  # [bs, dv]
    valid_len = len_ref[b // heads]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    pos = jk * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    valid = pos < valid_len
    s = jnp.where(valid, s, NEG_INF)
    # zero V outside validity: pool blocks can hold garbage (even NaN, from
    # quarantined rows) and 0 * NaN = NaN would leak through masked lanes
    col_valid = (jk * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, 1), 0)
                 ) < valid_len
    v = jnp.where(col_valid, v, 0.0)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(jk == nb - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def paged_decode_attention_kernel(q, k_pool, v_pool, table, cache_len, *,
                                  heads: int, interpret: bool = False):
    """q: [B*H, d]; k/v_pool: [NB+1, KV, bs, dh]; table: [B*nb] int32
    (flattened [B, nb], unallocated entries point at the trash block NB);
    cache_len: [B] int32 -> [B*H, dv]."""
    BH, d = q.shape
    _, KV, bs, dv = v_pool.shape
    B = cache_len.shape[0]
    nb = table.shape[0] // B
    g = (BH // B) // KV if KV else 1          # query heads per kv head
    H = heads
    scale = 1.0 / math.sqrt(d)

    kernel = functools.partial(_paged_decode_kernel, scale=scale, bs=bs,
                               nb=nb, heads=H)
    q3 = q[:, None, :]                                   # [BH, 1, d]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(BH, nb),
        in_specs=[
            pl.BlockSpec((1, 1, d), lambda b, j, tbl, ln: (b, 0, 0)),
            pl.BlockSpec((1, 1, bs, d),
                         lambda b, j, tbl, ln: (tbl[(b // H) * nb + j],
                                                (b % H) // g, 0, 0)),
            pl.BlockSpec((1, 1, bs, dv),
                         lambda b, j, tbl, ln: (tbl[(b // H) * nb + j],
                                                (b % H) // g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, dv), lambda b, j, tbl, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        name="paged_decode_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((BH, 1, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(table, cache_len, q3, k_pool, v_pool)
    return out[:, 0, :]


# ---------------------------------------------------------------------------
# prefill: ragged tail of new tokens (per-row start offset) vs paged cache
# ---------------------------------------------------------------------------

def _paged_prefill_kernel(tbl_ref, qs_ref, kl_ref, q_ref, k_ref, v_ref,
                          o_ref, m_ref, l_ref, acc_ref, *, scale: float,
                          bq: int, bs: int, nb: int, heads: int):
    b = pl.program_id(0)
    iq = pl.program_id(1)
    jk = pl.program_id(2)

    @pl.when(jk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    row = b // heads
    q = q_ref[0].astype(jnp.float32)                     # [bq, d]
    k = k_ref[0, 0].astype(jnp.float32)                  # [bs, d]
    v = v_ref[0, 0].astype(jnp.float32)                  # [bs, dv]
    q_start = qs_ref[row]
    kv_len = kl_ref[row]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    q_pos = (q_start + iq * bq +
             jax.lax.broadcasted_iota(jnp.int32, (bq, bs), 0))
    kv_pos = (jk * bs +
              jax.lax.broadcasted_iota(jnp.int32, (bq, bs), 1))
    valid = (kv_pos <= q_pos) & (kv_pos < kv_len)
    s = jnp.where(valid, s, NEG_INF)
    col_valid = (jk * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, 1), 0)
                 ) < kv_len
    v = jnp.where(col_valid, v, 0.0)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(jk == nb - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def paged_prefill_attention_kernel(q, k_pool, v_pool, table, q_start, kv_len,
                                   *, heads: int, bq: int = 128,
                                   interpret: bool = False):
    """q: [B*H, Sq, d] (the ragged tail, row b's token i sits at absolute
    position ``q_start[b//H] + i``); pools/table as in the decode kernel;
    kv_len: [B] total valid cache length per row -> [B*H, Sq, dv]."""
    BH, Sq, d = q.shape
    _, KV, bs, dv = v_pool.shape
    B = q_start.shape[0]
    nb = table.shape[0] // B
    H = heads
    g = (BH // B) // KV if KV else 1
    bq = min(bq, Sq)
    assert Sq % bq == 0, (Sq, bq)
    nq = Sq // bq
    scale = 1.0 / math.sqrt(d)

    kernel = functools.partial(_paged_prefill_kernel, scale=scale, bq=bq,
                               bs=bs, nb=nb, heads=H)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(BH, nq, nb),
        in_specs=[
            pl.BlockSpec((1, bq, d),
                         lambda b, i, j, tbl, qs, kl: (b, i, 0)),
            pl.BlockSpec((1, 1, bs, d),
                         lambda b, i, j, tbl, qs, kl: (tbl[(b // H) * nb + j],
                                                       (b % H) // g, 0, 0)),
            pl.BlockSpec((1, 1, bs, dv),
                         lambda b, i, j, tbl, qs, kl: (tbl[(b // H) * nb + j],
                                                       (b % H) // g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dv),
                               lambda b, i, j, tbl, qs, kl: (b, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, dv), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        name="paged_prefill_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((BH, Sq, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(table, q_start, kv_len, q, k_pool, v_pool)
