"""Pallas TPU decode attention: one query token vs a long KV cache.

Layout: q [BH, d], k/v [BKV, T, d]. Grid (BH, nk): kv blocks stream through
VMEM while the online-softmax accumulator persists in scratch — the memory-
bound flash-decode pattern (arithmetic intensity ~= 1 FLOP/byte, so the block
size mainly amortises HBM->VMEM latency).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                   *, scale: float, bk: int, nk: int, window: int, group: int):
    b = pl.program_id(0)
    jk = pl.program_id(1)

    @pl.when(jk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                    # [1, d] row
    k = k_ref[0].astype(jnp.float32)                    # [bk, d]
    v = v_ref[0].astype(jnp.float32)                    # [bk, dv]
    valid_len = len_ref[b // group]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale  # [1,bk]
    pos = jk * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    valid = pos < valid_len
    if window:
        # sliding window over a linear cache: the query position is
        # valid_len - 1, so only pos > valid_len - 1 - window contributes
        valid &= pos > valid_len - 1 - window
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]                                 # [1, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(jk == nk - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def decode_attention_kernel(q, k, v, cache_len, *, bk: int = 512,
                            group: int = 1, window: int = 0,
                            interpret: bool = False):
    """q: [BH, d]; k: [BKV, T, d]; v: [BKV, T, dv]; cache_len: [BKV] int32
    -> [BH, dv]. ``window`` > 0 masks cache positions more than ``window``
    behind the query (linear caches; ring buffers pass window=0)."""
    BH, d = q.shape
    BKV, T, dv = v.shape
    nk = T // bk
    scale = 1.0 / math.sqrt(d)

    kernel = functools.partial(_decode_kernel, scale=scale, bk=bk, nk=nk,
                               window=window, group=group)
    q3 = q[:, None, :]                                   # [BH, 1, d]

    # cache_len rides in SMEM as a scalar-prefetch operand (a rank-1 SMEM
    # block of one element is not a legal TPU tiling)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(BH, nk),
        in_specs=[
            pl.BlockSpec((1, 1, d), lambda b, j, ln: (b, 0, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, ln, g=group: (b // g, j, 0)),
            pl.BlockSpec((1, bk, dv),
                         lambda b, j, ln, g=group: (b // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, dv), lambda b, j, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, dv), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((BH, 1, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(cache_len, q3, k, v)
    return out[:, 0, :]
