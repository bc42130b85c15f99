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

Decode's grid is ``(B, KV // G, nb)``: one step carries G K/V groups of one
pool block with all of their query heads (q laid out ``[B, KV, g, dh]``),
G = KV unless that tile is wider than ``DECODE_TILE_BYTES`` (then G = 1).
Its K/V index maps clamp the logical block to the row's last live one,
``min(j, max(ceil(len/bs) - 1, 0))``: a step past the row's length repeats
the previous block index, so the pipeline fetches nothing for it, and its
body is skipped. A row of length 0 costs one fetch and no compute.
Prefill's grid is ``(B*H, Sq/bq, nb)`` and visits every block.
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
# decode: one query token per row, KV read through the block table
# ---------------------------------------------------------------------------

# Bytes of one K (or V) tile a decode grid step may bring in. The pipeline
# double-buffers K and V, so four such tiles sit in VMEM at once.
DECODE_TILE_BYTES = 256 * 1024


def _paged_decode_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                         m_ref, l_ref, acc_ref, *, scale: float, bs: int,
                         nb: int, groups: int):
    b = pl.program_id(0)
    jk = pl.program_id(2)
    valid_len = len_ref[b]

    @pl.when(jk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # a block wholly past the row's length adds exactly nothing to m, l and
    # acc, so it is skipped (its index map repeated the last live block, so
    # nothing was fetched for it either)
    @pl.when(jk * bs < valid_len)
    def _attend():
        valid = (jk * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
                 ) < valid_len
        # zero V outside validity: pool blocks can hold garbage (even NaN,
        # from quarantined rows) and 0 * NaN = NaN would leak through masked
        # lanes
        col_valid = (jk * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, 1),
                                                        0)) < valid_len

        def group(i, carry):
            q = q_ref[0, i]                                  # [g, d]
            k = k_ref[0, i]                                  # [bs, d]
            dt = jnp.promote_types(q.dtype, k.dtype)
            s = jax.lax.dot_general(q.astype(dt), k.astype(dt),
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(valid, s * scale, NEG_INF)         # [g, bs]
            v = jnp.where(col_valid, v_ref[0, i].astype(jnp.float32), 0.0)
            m_prev = m_ref[i]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[i] = l_ref[i] * corr + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[i] = acc_ref[i] * corr + jax.lax.dot(
                p, v, preferred_element_type=jnp.float32)
            m_ref[i] = m_new
            return carry

        jax.lax.fori_loop(0, groups, group, 0)

    @pl.when(jk == nb - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def paged_decode_attention_kernel(q, k_pool, v_pool, table, cache_len, *,
                                  interpret: bool = False):
    """q: [B, H, d]; k/v_pool: [NB+1, KV, bs, dh]; table: [B*nb] int32
    (flattened [B, nb], unallocated entries point at the trash block NB);
    cache_len: [B] int32 -> [B, H, dv]."""
    B, H, d = q.shape
    _, KV, bs, dv = v_pool.shape
    nb = table.shape[0] // B
    g = H // KV                               # query heads per kv group
    # K/V groups a grid step carries: all of a pool block's, unless that
    # tile is too wide (an MHA pool at long blocks), then one
    G = KV if KV * bs * d * k_pool.dtype.itemsize <= DECODE_TILE_BYTES else 1
    scale = 1.0 / math.sqrt(d)

    kernel = functools.partial(_paged_decode_kernel, scale=scale, bs=bs,
                               nb=nb, groups=G)

    def kv_index(b, h, j, tbl, ln):
        # past the row's last live block, repeat it: an unchanged block
        # index issues no DMA
        last = jnp.maximum((ln[b] + bs - 1) // bs - 1, 0)
        return (tbl[b * nb + jnp.minimum(j, last)], h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KV // G, nb),
        in_specs=[
            pl.BlockSpec((1, G, g, d), lambda b, h, j, tbl, ln: (b, h, 0, 0)),
            pl.BlockSpec((1, G, bs, d), kv_index),
            pl.BlockSpec((1, G, bs, dv), kv_index),
        ],
        out_specs=pl.BlockSpec((1, G, g, dv),
                               lambda b, h, j, tbl, ln: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, g, 1), jnp.float32),
            pltpu.VMEM((G, g, 1), jnp.float32),
            pltpu.VMEM((G, g, dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        name="paged_decode_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, g, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(table, cache_len, q.reshape(B, KV, g, d), k_pool, v_pool)
    return out.reshape(B, H, dv)


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
