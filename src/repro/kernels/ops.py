"""Jitted public wrappers around the Pallas kernels: padding, GQA head
bookkeeping, block-size selection, and the interpret switch (CPU validation
vs TPU execution).

``flash_attention`` is differentiable: a ``jax.custom_vjp`` routes its
backward pass through the fused Pallas dq and dk/dv kernels in
``repro.kernels.flash_attention`` (FlashAttention-2 style — the forward
saves the per-row logsumexp, the backward recomputes probabilities blockwise
from it after a precomputed ``delta = sum(dO * O)`` pass). The pruned
block-sparse grids are picked automatically from the ``causal``/``window``
statics — every kernel call walks ``flash_grid_plan``'s tile list, so
causal training skips the upper block triangle and sliding-window training
visits a constant ~ceil(window/bk)+1 kv blocks per q block.

``gla_scan`` is differentiable the same way: its ``jax.custom_vjp`` pairs
the forward chunk-scan kernel (which checkpoints the per-chunk entering
states) with the fused reverse chunk-scan kernel in
``repro.kernels.ssm_scan`` — a single backward pass, no recompute through
the jnp scan. These are the kernels behind ``kernels="pallas"`` in
``ModelConfig``; with ``interpret=True`` the same VJPs run on CPU for
tier-1 validation.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention import decode_attention_kernel
from repro.kernels.flash_attention import (flash_attention_bwd_dkv,
                                           flash_attention_bwd_dq,
                                           flash_attention_kernel)
from repro.kernels.paged_attention import (paged_decode_attention_kernel,
                                           paged_prefill_attention_kernel)
from repro.kernels.ssm_scan import gla_scan_bwd_kernel, gla_scan_kernel


def default_interpret() -> bool:
    """True off-TPU: Pallas kernels run in the (slow, exact) interpreter so
    the kernel-backed paths stay testable on CPU hosts."""
    return jax.default_backend() != "tpu"


def _pad_to(x, axis, mult):
    n = x.shape[axis]
    pad = (-n) % mult
    if not pad:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), n


# ---------------------------------------------------------------------------
# Flash attention with a fused-kernel VJP. The custom_vjp core operates on
# the folded, block-padded layout (q [BH, Sq, d]; k/v [BKV, Sk, d]) so the
# residuals are exactly the kernel operands; head fold/unfold and padding
# live in the public wrapper, where plain jax AD transposes them.
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _flash_core(qh, kh, vh, causal, window, q_offset, bq, bk, group,
                sk_valid, interpret):
    out, _ = flash_attention_kernel(
        qh, kh, vh, causal=causal, window=window, q_offset=q_offset,
        bq=bq, bk=bk, group=group, sk_valid=sk_valid, interpret=interpret)
    return out


def _flash_core_fwd(qh, kh, vh, causal, window, q_offset, bq, bk, group,
                    sk_valid, interpret):
    out, lse = flash_attention_kernel(
        qh, kh, vh, causal=causal, window=window, q_offset=q_offset,
        bq=bq, bk=bk, group=group, sk_valid=sk_valid, interpret=interpret)
    return out, (qh, kh, vh, out, lse)


def _flash_core_bwd(causal, window, q_offset, bq, bk, group, sk_valid,
                    interpret, res, do):
    qh, kh, vh, out, lse = res
    # delta pass: D_i = sum_d dO_id * O_id, one fused elementwise-reduce
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[:, None, :]                  # [BH, 1, Sq]
    kw = dict(causal=causal, window=window, q_offset=q_offset, bq=bq, bk=bk,
              group=group, sk_valid=sk_valid, interpret=interpret)
    dq = flash_attention_bwd_dq(qh, kh, vh, do, lse, delta, **kw)
    dk, dv = flash_attention_bwd_dkv(qh, kh, vh, do, lse, delta, **kw)
    return dq, dk, dv


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


@partial(jax.jit, static_argnames=("causal", "window", "q_offset", "bq",
                                   "bk", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    q_offset: int = 0, bq: int = 128, bk: int = 128,
                    interpret: bool = False):
    """q: [B,Sq,H,dh]; k,v: [B,Sk,KV,dv] -> [B,Sq,H,dv]. Heads fold into the
    grid's batch dim; GQA via the kv index map (group = H // KV).

    Differentiable — ``jax.grad`` through this runs the Pallas dq + dk/dv
    kernels. kv padding beyond ``Sk`` is masked inside every kernel
    (``sk_valid``); q padding is sliced off here (forward) and carries zero
    cotangents (backward)."""
    B, Sq, H, dh = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    group = H // KV
    bq = min(bq, Sq)
    bk = min(bk, Sk)

    qh = jnp.moveaxis(q, 2, 1).reshape(B * H, Sq, dh)
    kh = jnp.moveaxis(k, 2, 1).reshape(B * KV, Sk, dh)
    vh = jnp.moveaxis(v, 2, 1).reshape(B * KV, Sk, dv)
    qh, sq0 = _pad_to(qh, 1, bq)
    kh, sk0 = _pad_to(kh, 1, bk)
    vh, _ = _pad_to(vh, 1, bk)
    # padded kv positions are masked because kv_pos < sk is checked with the
    # ORIGINAL length baked into the kernel closure
    out = _flash_core(qh, kh, vh, causal, window, q_offset, bq, bk, group,
                      sk0, interpret)
    out = out[:, :sq0]
    return jnp.moveaxis(out.reshape(B, H, Sq, dv), 1, 2)


@partial(jax.jit, static_argnames=("window", "bk", "interpret"))
def decode_attention(q, k, v, cache_len, *, window: int = 0, bk: int = 512,
                     interpret: bool = False):
    """q: [B,1,H,dh]; k: [B,T,KV,dh]; v: [B,T,KV,dv]; cache_len: [B]
    -> [B,1,H,dv]. This is the ``decode_attn="pallas"`` registry op; dv may
    differ from dh (MLA latent decode). ``window`` > 0 applies sliding-window
    masking on a linear cache (ring-buffer callers pass window=0 — the
    wrapped ``cache_len`` semantics already cover the ring)."""
    B, _, H, dh = q.shape
    T, KV = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    group = H // KV
    bk = min(bk, T)
    qh = q[:, 0].reshape(B, H, dh).reshape(B * H, dh)
    kh = jnp.moveaxis(k, 2, 1).reshape(B * KV, T, dh)
    vh = jnp.moveaxis(v, 2, 1).reshape(B * KV, T, dv)
    kh, _ = _pad_to(kh, 1, bk)
    vh, _ = _pad_to(vh, 1, bk)
    ln = jnp.repeat(cache_len, KV, axis=0)
    out = decode_attention_kernel(qh, kh, vh, ln, bk=bk, group=group,
                                  window=window, interpret=interpret)
    return out.reshape(B, 1, H, dv)


@partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q, k_pool, v_pool, table, cache_len, *,
                           interpret: bool = False):
    """q: [B,1,H,dh]; k/v_pool: [NB+1,KV,bs,dh] (block pool, last block is
    the trash block); table: [B,nb] int32; cache_len: [B] -> [B,1,H,dv].
    The ``paged_attn="pallas"`` decode op: KV blocks are read through the
    scalar-prefetched block table, no gather materialises the row's cache."""
    out = paged_decode_attention_kernel(
        q[:, 0], k_pool, v_pool, table.reshape(-1).astype(jnp.int32),
        cache_len.astype(jnp.int32), interpret=interpret)
    return out[:, None]


@partial(jax.jit, static_argnames=("bq", "interpret"))
def paged_prefill_attention(q, k_pool, v_pool, table, q_start, kv_len, *,
                            bq: int = 128, interpret: bool = False):
    """q: [B,Sq,H,dh] ragged tail (row b's token i is at absolute position
    ``q_start[b] + i``; the tail's K/V must already be scattered into the
    pool); table: [B,nb]; kv_len: [B] total valid length -> [B,Sq,H,dv].
    Forward-only (serving admission); padding rows are masked by kv_len."""
    B, Sq, H, dh = q.shape
    dv = v_pool.shape[-1]
    bq = min(bq, Sq)
    qh = jnp.moveaxis(q, 2, 1).reshape(B * H, Sq, dh)
    qh, sq0 = _pad_to(qh, 1, bq)
    out = paged_prefill_attention_kernel(
        qh, k_pool, v_pool, table.reshape(-1).astype(jnp.int32),
        q_start.astype(jnp.int32), kv_len.astype(jnp.int32),
        heads=H, bq=bq, interpret=interpret)
    out = out[:, :sq0]
    return jnp.moveaxis(out.reshape(B, H, Sq, dv), 1, 2)


# ---------------------------------------------------------------------------
# GLA chunk scan with a fused-kernel VJP. Like flash attention, the
# custom_vjp core operates on the folded, chunk-padded layout (q,k [BH,S,dk];
# v [BH,S,dv]; g [BH,S]) so the residuals — inputs + the per-chunk entering
# states the forward checkpoints — are exactly the kernel operands; head
# fold/unfold and padding live in the public wrapper, where plain jax AD
# transposes them (padded rows therefore carry zero cotangents).
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _gla_core(qh, kh, vh, gh, chunk, s_valid, interpret):
    y, _ = gla_scan_kernel(qh, kh, vh, gh, chunk=chunk, s_valid=s_valid,
                           interpret=interpret)
    return y


def _gla_core_fwd(qh, kh, vh, gh, chunk, s_valid, interpret):
    y, states, _ = gla_scan_kernel(qh, kh, vh, gh, chunk=chunk,
                                   s_valid=s_valid, collect_states=True,
                                   interpret=interpret)
    return y, (qh, kh, vh, gh, states)


def _gla_core_bwd(chunk, s_valid, interpret, res, dy):
    qh, kh, vh, gh, states = res
    return gla_scan_bwd_kernel(qh, kh, vh, gh, states, dy, chunk=chunk,
                               s_valid=s_valid, interpret=interpret)


_gla_core.defvjp(_gla_core_fwd, _gla_core_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _gla_core_with_state(qh, kh, vh, gh, chunk, s_valid, interpret):
    return gla_scan_kernel(qh, kh, vh, gh, chunk=chunk, s_valid=s_valid,
                           interpret=interpret)


def _gla_core_with_state_fwd(qh, kh, vh, gh, chunk, s_valid, interpret):
    return _gla_core_with_state(qh, kh, vh, gh, chunk, s_valid,
                                interpret), None


def _gla_core_with_state_bwd(chunk, s_valid, interpret, res, dy):
    raise NotImplementedError(
        "ops.gla_scan(return_final_state=True) is a forward-only path "
        "(prefill/decode-cache fill); differentiate the default "
        "gla_scan(...) instead — its custom_vjp runs the fused reverse "
        "chunk-scan kernel.")


_gla_core_with_state.defvjp(_gla_core_with_state_fwd,
                            _gla_core_with_state_bwd)


@partial(jax.jit, static_argnames=("chunk", "interpret",
                                   "return_final_state"))
def gla_scan(q, k, v, g, *, chunk: int = 64, interpret: bool = False,
             return_final_state: bool = False):
    """Chunked gated-linear-attention. q,k: [B,S,H,dk]; v: [B,S,H,dv];
    g: [B,S,H] log-decay. Returns y: [B,S,H,dv].

    Differentiable — ``jax.grad`` through this runs the fused reverse
    chunk-scan kernel (single backward pass; the forward checkpoints its
    per-chunk states). With ``return_final_state=True`` also returns the
    [B,H,dk,dv] float32 state after the last VALID position — padded rows
    are masked out of the state update inside the kernel, so the state is
    exact for any S (this path is forward-only; training consumers use the
    default)."""
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    chunk = min(chunk, S)

    def fold(x):
        return jnp.moveaxis(x, 2, 1).reshape((B * H, S) + x.shape[3:])

    qh, kh, vh = fold(q), fold(k), fold(v)
    gh = jnp.moveaxis(g, 2, 1).reshape(B * H, S)
    qh, s0 = _pad_to(qh, 1, chunk)
    kh, _ = _pad_to(kh, 1, chunk)
    vh, _ = _pad_to(vh, 1, chunk)
    gh, _ = _pad_to(gh, 1, chunk)
    if return_final_state:
        # forward-only path: the custom_vjp exists solely to turn an AD
        # attempt into a clear error at the API (not deep inside pallas)
        y, fin = _gla_core_with_state(qh, kh, vh, gh, chunk, s0, interpret)
        y = jnp.moveaxis(y[:, :s0].reshape(B, H, S, dv), 1, 2)
        return y, fin.reshape(B, H, dk, dv)
    y = _gla_core(qh, kh, vh, gh, chunk, s0, interpret)
    y = y[:, :s0]
    return jnp.moveaxis(y.reshape(B, H, S, dv), 1, 2)
