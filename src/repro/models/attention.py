"""Attention variants: GQA/MHA (+ sliding window, partial/2D RoPE), MLA.

Prefill/train attention is computed **blockwise over the KV axis** with an
online softmax (flash-attention structure in pure jnp) so that no [S, S]
score tensor is ever materialised — required for the 32k prefill shapes.

``blockwise_attention`` dispatches on the per-op kernel backend registry
(``repro.kernels.registry``; ``cfg.kernels``, with ``cfg.attn_backend`` as
the deprecated alias): the jnp path here is the reference/default, and
``backend="pallas"`` routes both forward and backward through the fused
Pallas TPU kernels in ``repro.kernels`` (``ops.flash_attention``'s
custom_vjp — dq + dk/dv kernels), falling back to interpreter mode off-TPU.
Decode dispatches ``ops.decode_attention`` (flash-decode) the same way via
the ``decode_attn`` op. See the backend matrix in ROADMAP.md.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import registry
from repro.models.common import dense_init, split_dict
from repro.models.layers import apply_rope

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Blockwise (flash-style) attention — reference path for all archs
# ---------------------------------------------------------------------------

def _mask_for(block, Sk, q_pos, kv_pos, causal, window):
    valid = kv_pos < Sk
    if causal:
        valid = valid & (kv_pos <= q_pos)
    if window:
        valid = valid & (kv_pos > q_pos - window)
    return valid


def _flash_fwd_scan(q, k, v, *, causal, window, q_offset, block, sk_valid=None):
    """Returns (out [B,Sq,KV,G,dv], lse [B,Sq,G,KV]).

    ``q_offset`` / ``sk_valid`` may be [B] int32 arrays (per-row ragged
    offsets/lengths — the paged-prefill path, which calls this scan directly
    since custom_vjp nondiff args must be static); scalars broadcast as
    before and stay bit-identical to the original code path."""
    B, Sq, KV, G, dh = q.shape
    dv = v.shape[-1]
    scale = 1.0 / math.sqrt(dh)
    if getattr(q_offset, "ndim", 0):
        q_pos = q_offset[:, None, None] + jnp.arange(Sq)[None, :, None]
    else:
        q_pos = (jnp.arange(Sq) + q_offset)[None, :, None]       # [1,Sq,1]
    if sk_valid is None:
        Sk = k.shape[1]
    elif getattr(sk_valid, "ndim", 0):
        Sk = sk_valid[:, None, None]                             # [B,1,1]
    else:
        Sk = sk_valid

    nblk = k.shape[1] // block
    kb = jnp.moveaxis(k.reshape(B, nblk, block, KV, dh), 1, 0)
    vb = jnp.moveaxis(v.reshape(B, nblk, block, KV, dv), 1, 0)

    def step(carry, inp):
        m, l, acc, bi = carry
        kblk, vblk = inp
        kv_pos = bi * block + jnp.arange(block)[None, None, :]   # [1,1,blk]
        s = jnp.einsum("bsjgd,btjd->bsgjt", q, kblk,
                       preferred_element_type=jnp.float32) * scale
        valid = _mask_for(block, Sk, q_pos, kv_pos, causal, window)
        s = jnp.where(valid[:, :, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bsgjt,btjd->bsgjd", p, vblk.astype(jnp.float32))
        acc = acc * corr[..., None] + pv
        return (m_new, l, acc, bi + 1), None

    m0 = jnp.full((B, Sq, G, KV), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Sq, G, KV), jnp.float32)
    a0 = jnp.zeros((B, Sq, G, KV, dv), jnp.float32)
    (m, l, acc, _), _ = jax.lax.scan(step, (m0, l0, a0, jnp.int32(0)), (kb, vb))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = jnp.moveaxis(out, 2, 3)          # [B,Sq,G,KV,dv] -> [B,Sq,KV,G,dv]
    lse = m + jnp.log(jnp.maximum(l, 1e-30))   # [B,Sq,G,KV]
    return out, lse


def _flash_bwd_scan(res, do, *, causal, window, q_offset, block, sk_valid=None):
    """Flash backward: recompute scores blockwise from the saved logsumexp —
    memory O(S*block) instead of the O(S^2) an AD-of-scan would save."""
    q, k, v, out, lse = res          # q/out: [B,Sq,KV,G,*]; k/v: [B,Sk,KV,*]
    B, Sq, KV, G, dh = q.shape
    Sk = k.shape[1] if sk_valid is None else sk_valid
    dv = v.shape[-1]
    scale = 1.0 / math.sqrt(dh)
    q_pos = (jnp.arange(Sq) + q_offset)[None, :, None]

    nblk = k.shape[1] // block
    kb = jnp.moveaxis(k.reshape(B, nblk, block, KV, dh), 1, 0)
    vb = jnp.moveaxis(v.reshape(B, nblk, block, KV, dv), 1, 0)
    dof = do.astype(jnp.float32)
    delta = jnp.sum(dof * out, axis=-1)                    # [B,Sq,KV,G]

    def step(carry, inp):
        dq, bi = carry
        kblk, vblk = inp
        kv_pos = bi * block + jnp.arange(block)[None, None, :]
        s = jnp.einsum("bsjgd,btjd->bsgjt", q, kblk,
                       preferred_element_type=jnp.float32) * scale
        valid = _mask_for(block, Sk, q_pos, kv_pos, causal, window)
        s = jnp.where(valid[:, :, None, None, :], s, NEG_INF)
        p = jnp.exp(s - lse[..., None])                    # [B,Sq,G,KV,blk]
        dv_blk = jnp.einsum("bsgjt,bsjgd->btjd", p, dof)
        dp = jnp.einsum("bsjgd,btjd->bsgjt", dof, vblk.astype(jnp.float32))
        dlt = jnp.moveaxis(delta, 2, 3)                    # [B,Sq,G,KV]
        ds = p * (dp - dlt[..., None]) * scale
        dq = dq + jnp.einsum("bsgjt,btjd->bsjgd", ds, kblk.astype(jnp.float32))
        dk_blk = jnp.einsum("bsgjt,bsjgd->btjd", ds, q.astype(jnp.float32))
        return (dq, bi + 1), (dk_blk, dv_blk)

    dq0 = jnp.zeros(q.shape, jnp.float32)
    (dq, _), (dk_b, dv_b) = jax.lax.scan(step, (dq0, jnp.int32(0)), (kb, vb))
    sk_pad = k.shape[1]
    dk = jnp.moveaxis(dk_b, 0, 1).reshape(B, sk_pad, KV, dh)
    dvv = jnp.moveaxis(dv_b, 0, 1).reshape(B, sk_pad, KV, dv)
    return dq.astype(q.dtype), dk.astype(k.dtype), dvv.astype(v.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, window, q_offset, block, sk_valid):
    out, _ = _flash_fwd_scan(q, k, v, causal=causal, window=window,
                             q_offset=q_offset, block=block, sk_valid=sk_valid)
    return out


def _flash_f(q, k, v, causal, window, q_offset, block, sk_valid):
    out, lse = _flash_fwd_scan(q, k, v, causal=causal, window=window,
                               q_offset=q_offset, block=block, sk_valid=sk_valid)
    return out, (q, k, v, out, lse)


def _flash_b(causal, window, q_offset, block, sk_valid, res, do):
    return _flash_bwd_scan(res, do, causal=causal, window=window,
                           q_offset=q_offset, block=block, sk_valid=sk_valid)


_flash.defvjp(_flash_f, _flash_b)


ATTN_BACKENDS = ("jnp", "pallas")


def blockwise_attention(q, k, v, *, causal: bool, window: int = 0,
                        q_offset: int = 0, block: int = 512,
                        backend: str = "jnp"):
    """q: [B,Sq,H,dh], k: [B,Sk,KV,dh], v: [B,Sk,KV,dv] -> [B,Sq,H,dv].

    ``backend`` selects the contraction (the ``attn_backend`` config knob):

      * ``"jnp"``    — flash-structured blockwise online softmax in pure jnp
                       with a custom VJP that recomputes scores instead of
                       storing [Sq, Sk]; runs on any jax backend. This is
                       the reference twin of kernels/flash_attention.py.
      * ``"pallas"`` — fused Pallas TPU kernels for forward AND backward
                       (``repro.kernels.ops.flash_attention``'s custom_vjp);
                       interpreter mode is selected automatically off-TPU so
                       CPU training/tests still run. ``block`` applies to
                       the jnp path only — the kernels tile at their own
                       MXU-aligned bq/bk defaults.

    GQA: H must be a multiple of KV; query head g attends kv head g*KV//H.
    ``causal`` masks kv_pos > q_pos with q_pos = q_offset + arange(Sq).
    ``window``>0 additionally masks kv_pos <= q_pos - window (sliding window).
    """
    if backend not in ATTN_BACKENDS:
        raise ValueError(
            f"unknown attn backend {backend!r}; expected one of {ATTN_BACKENDS}")
    if backend == "pallas":
        from repro.kernels import ops
        return ops.flash_attention(
            q, k, v, causal=causal, window=window, q_offset=q_offset,
            interpret=ops.default_interpret())
    B, Sq, H, dh = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    G = H // KV
    block = min(block, Sk)
    nblk = -(-Sk // block)
    pad = nblk * block - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qr = q.reshape(B, Sq, KV, G, dh)
    out = _flash(qr, k, v, causal, window, q_offset, block, Sk)
    return out.reshape(B, Sq, H, dv).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, cache_len, *, window: int = 0,
                     backend: str = "jnp"):
    """Single-token attention. q: [B,1,H,dh]; caches: [B,T,KV,dh/dv].

    ``cache_len``: [B] int32 — number of valid cache entries (the new token's
    position is cache_len - 1 after insertion).  ``backend`` is the
    ``decode_attn`` registry op: ``"pallas"`` dispatches the flash-decode
    kernel (``ops.decode_attention``, interpreter mode off-TPU).
    """
    if backend == "pallas":
        from repro.kernels import ops
        return ops.decode_attention(q, k_cache, v_cache, cache_len,
                                    window=window,
                                    interpret=ops.default_interpret())
    B, _, H, dh = q.shape
    T, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(dh)
    qr = q.reshape(B, KV, G, dh)
    s = jnp.einsum("bjgd,btjd->bjgt", qr, k_cache,
                   preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(T)[None, None, None, :]
    cl = cache_len[:, None, None, None]
    valid = pos < cl
    if window:
        valid = valid & (pos > cl - 1 - window)
    s = jnp.where(valid, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bjgt,btjd->bjgd", p, v_cache.astype(jnp.float32))
    return out.reshape(B, 1, H, v_cache.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA projection layer (covers MHA, multi-query, SWA, partial/2D rope, bias)
# ---------------------------------------------------------------------------

def gqa_init(key, cfg, dtype):
    d, H, KV = cfg.d_model, cfg.num_heads, cfg.num_kv_heads
    hd = cfg.resolved_head_dim
    ks = split_dict(key, ["wq", "wk", "wv", "wo"])
    p = {"wq": dense_init(ks["wq"], d, H * hd, dtype),
         "wk": dense_init(ks["wk"], d, KV * hd, dtype),
         "wv": dense_init(ks["wv"], d, KV * hd, dtype),
         "wo": dense_init(ks["wo"], H * hd, d, dtype)}
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((KV * hd,), dtype)
        p["bv"] = jnp.zeros((KV * hd,), dtype)
    return p


def _project_qkv(p, cfg, x):
    B, S, _ = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    return (q.reshape(B, S, H, hd), k.reshape(B, S, KV, hd),
            v.reshape(B, S, KV, hd))


def _gqa_attend(p, cfg, x, positions, *, causal, window):
    """Shared project + rope + blockwise-attention body of apply/prefill.
    Returns (ctx [B,S,H*dv], roped k, v) so prefill can cache k/v without
    re-deriving them (one body — the numerics cannot diverge)."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(p, cfg, x)
    if positions.ndim == 1:
        positions = positions[None, :]
    q = apply_rope(q, positions, cfg.rope_theta, cfg.partial_rotary_factor,
                   interleaved=cfg.rope_2d)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.partial_rotary_factor,
                   interleaved=cfg.rope_2d)
    out = blockwise_attention(q, k, v, causal=causal, window=window,
                              backend=registry.active_attn_backend(cfg))
    return out.reshape(B, S, -1), k, v


def gqa_apply(p, cfg, x, positions, *, causal=True, window=None):
    """Self-attention over x: [B,S,d]. positions: [B,S] or [S]."""
    win = cfg.attn_window if window is None else window
    ctx, _, _ = _gqa_attend(p, cfg, x, positions, causal=causal, window=win)
    return ctx @ p["wo"]


def gqa_prefill(p, cfg, x, positions, cache, *, window=None, lengths=None):
    """Fused full-sequence prefill: ONE blockwise/flash attention pass over
    the prompt that also fills the decode cache (rope'd k/v at every prompt
    position) — replaces teacher-forcing the prompt through ``gqa_decode``
    token by token. Returns (out [B,S,d], new_cache).

    ``lengths`` ([B] int32, optional): ragged prompts packed left-aligned
    into the fixed [B,S] buffer. Every position is projected and written,
    but the cache ``len`` becomes per-row, so decode masking (and the next
    write slot) never sees a row's pad tail."""
    S = x.shape[1]
    win = cfg.attn_window if window is None else window
    ctx, k, v = _gqa_attend(p, cfg, x, positions, causal=True, window=win)
    T = cache["k"].shape[1]
    ring = bool(win) and win == T
    add = jnp.int32(S) if lengths is None else lengths.astype(jnp.int32)
    new_cache = {"k": _prefill_fill(cache["k"], k, ring),
                 "v": _prefill_fill(cache["v"], v, ring),
                 "len": cache["len"] + add}
    return ctx @ p["wo"], new_cache


def _prefill_fill(buf, new, ring: bool):
    """Write a [B,S,...] prefill projection into a [B,T,...] cache buffer,
    preserving the decode-slot invariant (position p lives at slot p % T on
    the ring, slot p otherwise)."""
    T, S = buf.shape[1], new.shape[1]
    new = new.astype(buf.dtype)
    if S <= T:
        return jax.lax.dynamic_update_slice_in_dim(buf, new, 0, axis=1)
    if not ring:
        raise ValueError(f"prompt length {S} exceeds cache length {T}")
    # keep the last T positions; position p = S-T+i -> slot p % T = (i + S) % T
    return jnp.roll(new[:, S - T:], S % T, axis=1)


def gqa_prefill_chunked(p, cfg, x, cache, lengths, hist):
    """Chunked dense prefill: ``x`` holds each row's NEXT prompt chunk
    (absolute positions ``hist[b]..lengths[b]``, packed left-aligned), which
    is scattered into the row's [T] cache at its absolute slots and attended
    over the row's full logical range — the dense-cache twin of
    ``gqa_prefill_paged``. Rows with ``hist == lengths`` are pure
    passengers: nothing is written, ``len`` is unchanged, and their (unused)
    output attends an empty range.

    A row's FIRST chunk (``hist == 0``) zeroes the whole cache row before
    scattering: the whole-prompt path gets fresh zero rows from the
    admission merge, and a quarantined previous tenant may have left NaN —
    which would leak through decode's exactly-zero masked probabilities
    (0 * NaN = NaN). Sliding windows are unsupported (the engine gates
    chunked prefill to non-windowed archs). Returns (out [B,S,d],
    new_cache)."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(p, cfg, x)
    pos = hist[:, None] + jnp.arange(S)[None, :]                 # [B,S]
    q = apply_rope(q, pos, cfg.rope_theta, cfg.partial_rotary_factor,
                   interleaved=cfg.rope_2d)
    k = apply_rope(k, pos, cfg.rope_theta, cfg.partial_rotary_factor,
                   interleaved=cfg.rope_2d)
    T = cache["k"].shape[1]
    reset = ((hist == 0) & (lengths > 0))[:, None, None, None]
    k_buf = jnp.where(reset, jnp.zeros_like(cache["k"]), cache["k"])
    v_buf = jnp.where(reset, jnp.zeros_like(cache["v"]), cache["v"])
    valid = jnp.arange(S)[None, :] < (lengths - hist)[:, None]   # [B,S]
    dst = jnp.where(valid, pos, T)          # invalid lanes: dropped OOB
    bidx = jnp.arange(B)[:, None]
    k_buf = k_buf.at[bidx, dst].set(k.astype(k_buf.dtype), mode="drop")
    v_buf = v_buf.at[bidx, dst].set(v.astype(v_buf.dtype), mode="drop")
    new_len = lengths.astype(jnp.int32)
    ctx = paged_prefill_attention_ref(q, k_buf, v_buf,
                                      hist.astype(jnp.int32), new_len)
    new_cache = {"k": k_buf, "v": v_buf, "len": new_len}
    return ctx.reshape(B, S, -1) @ p["wo"], new_cache


def gqa_decode(p, cfg, x, cache, *, window=None, ragged=False, active=None):
    """One-token decode. x: [B,1,d]; cache: {"k","v": [B,T,KV,hd], "len": [B]}.

    ``ragged=True`` is the continuous-batching path: every row sits at its
    own cache position (``len`` is genuinely per-row), so the write is a
    per-row scatter instead of one dynamic_update_slice.

    ``active`` ([B] bool, ragged-only) marks rows genuinely decoding this
    step: inactive rows (slots mid-chunked-prefill) drop their cache write
    and keep their ``len`` — a decode step must not clobber a half-filled
    prompt. ``active=None`` (or all-True) is value-identical to the
    historical path.
    """
    B = x.shape[0]
    q, k, v = _project_qkv(p, cfg, x)
    pos = cache["len"][:, None]                                   # [B,1]
    q = apply_rope(q, pos, cfg.rope_theta, cfg.partial_rotary_factor,
                   interleaved=cfg.rope_2d)
    k = apply_rope(k, pos, cfg.rope_theta, cfg.partial_rotary_factor,
                   interleaved=cfg.rope_2d)
    T = cache["k"].shape[1]
    win = cfg.attn_window if window is None else window
    ring = bool(win) and win == T      # cache sized exactly to the window
    if ragged:
        # Per-row slot: serving-only — the scatter would force GSPMD to
        # all-gather a batch-sharded cache, which is why the training-shaped
        # synchronized branch below stays the default.
        slot = cache["len"] % T if ring else jnp.minimum(cache["len"], T - 1)
        bidx = jnp.arange(B)
        if active is not None:
            slot = jnp.where(active, slot, T)        # inactive: dropped OOB
        k_cache = cache["k"].at[bidx, slot].set(k[:, 0], mode="drop")
        v_cache = cache["v"].at[bidx, slot].set(v[:, 0], mode="drop")
    else:
        if active is not None:
            raise ValueError("active mask requires ragged=True")
        # Synchronized batched decode: all rows advance together, so the
        # write is a dynamic_update_slice on the (unsharded) time axis. A
        # per-row scatter (`.at[arange(B), slot]`) forces GSPMD to
        # all-gather the whole batch-sharded cache — a 48 GiB burst at
        # decode_32k scale.
        if ring:
            slot0 = cache["len"][0] % T                           # ring buffer
        else:
            slot0 = jnp.minimum(cache["len"][0], T - 1)
        k_cache = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot0,
                                                      axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot0,
                                                      axis=1)
    new_len = cache["len"] + (jnp.int32(1) if active is None
                              else active.astype(jnp.int32))
    out = decode_attention(q, k_cache, v_cache, new_len,
                           window=0 if ring else win,
                           backend=registry.backend_for(cfg, "decode_attn"))
    new_cache = {"k": k_cache, "v": v_cache, "len": new_len}
    return out.reshape(B, 1, -1) @ p["wo"], new_cache


def gqa_cache_init(cfg, batch: int, cache_len: int, dtype):
    KV, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    T = min(cache_len, cfg.attn_window) if cfg.attn_window else cache_len
    return {"k": jnp.zeros((batch, T, KV, hd), dtype),
            "v": jnp.zeros((batch, T, KV, hd), dtype),
            "len": jnp.zeros((batch,), jnp.int32)}


# ---------------------------------------------------------------------------
# Paged GQA: the KV cache is a pool of fixed-size blocks shared by all rows,
# k/v [NB+1, KV, bs, hd] (block NB is the write-off "trash" block), plus a
# per-row block table [B, nb] owned by the engine. Logical cache position p of
# row b, kv head h lives at pool slot (table[b, p // bs], h, p % bs): each
# head's block is one contiguous (bs, hd) tile, as the Pallas kernels read
# it. Masked/out-of-range writes are redirected to the trash block, and
# every read path zeroes V outside validity (pool blocks may hold garbage,
# even NaN, from freed or quarantined rows — 0 * NaN would leak through the
# exactly-zero masked probabilities). Valid lanes are untouched, which is
# what keeps the paged path bitwise-identical to the dense cache.
# ---------------------------------------------------------------------------

def paged_gqa_cache_init(cfg, batch: int, num_blocks: int, block_size: int,
                         dtype):
    """One layer's slice of the paged pool (stacked per layer by the model)."""
    KV, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    # PAGED_POISON=1 initialises the pool (trash block included) with NaN
    # instead of zeros: any read of a never-written lane that escapes the
    # masks then surfaces as NaN logits instead of silently reading zeros —
    # the debug switch that turns "rare flaky token mismatch" into a
    # deterministic failure (tests/test_paged_cache.py uses it as a canary)
    import os
    fill = float("nan") if os.environ.get("PAGED_POISON") else 0.0
    return {"k": jnp.full((num_blocks + 1, KV, block_size, hd), fill, dtype),
            "v": jnp.full((num_blocks + 1, KV, block_size, hd), fill, dtype),
            "len": jnp.zeros((batch,), jnp.int32)}


def _paged_gather(pool, table):
    """[NB+1, KV, bs, hd] gathered to the row-major logical layout
    [B, nb*bs, KV, hd] through the [B, nb] block table."""
    B, nb = table.shape
    _, KV, bs, hd = pool.shape
    g = jnp.swapaxes(pool[table], 2, 3)              # [B, nb, bs, KV, hd]
    return g.reshape(B, nb * bs, KV, hd)


def paged_prefill_attention_ref(q, k_cache, v_cache, q_start, kv_len, *,
                                block: int = 512):
    """jnp reference for the ragged-tail paged prefill: q [B,Sq,H,dh] holds
    new tokens at per-row absolute offsets ``q_start``; k/v_cache [B,T,KV,*]
    is the gathered logical cache (garbage beyond ``kv_len``)."""
    B, Sq, H, dh = q.shape
    Sk, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    block = min(block, Sk)
    nblk = -(-Sk // block)
    pad = nblk * block - Sk
    vmask = (jnp.arange(Sk)[None, :] < kv_len[:, None])[:, :, None, None]
    v_cache = jnp.where(vmask, v_cache, 0)
    if pad:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qr = q.reshape(B, Sq, KV, G, dh)
    out, _ = _flash_fwd_scan(qr, k_cache, v_cache, causal=True, window=0,
                             q_offset=q_start.astype(jnp.int32), block=block,
                             sk_valid=kv_len.astype(jnp.int32))
    return out.reshape(B, Sq, H, v_cache.shape[-1]).astype(q.dtype)


def gqa_prefill_paged(p, cfg, x, cache, table, lengths, hist):
    """Paged ragged prefill: scatter the new tail (absolute positions
    ``hist[b]..lengths[b]`` of each row) into the block pool through the
    table, then attend the tail queries over the row's full logical range —
    positions below ``hist`` are served by already-filled (possibly shared)
    blocks, which is how a prefix-cache hit skips recomputing the prefix.
    Rows with ``hist == lengths`` write nothing (their tail is empty).
    Returns (out [B,S,d], new layer cache)."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(p, cfg, x)
    pos = hist[:, None] + jnp.arange(S)[None, :]                 # [B,S]
    q = apply_rope(q, pos, cfg.rope_theta, cfg.partial_rotary_factor,
                   interleaved=cfg.rope_2d)
    k = apply_rope(k, pos, cfg.rope_theta, cfg.partial_rotary_factor,
                   interleaved=cfg.rope_2d)
    nb = table.shape[1]
    bs = cache["k"].shape[2]
    trash = cache["k"].shape[0] - 1
    valid = jnp.arange(S)[None, :] < (lengths - hist)[:, None]
    lb = jnp.clip(pos // bs, 0, nb - 1)
    phys = jnp.take_along_axis(table, lb, axis=1)                # [B,S]
    # invalid lanes (and lanes whose table entry is unallocated) are DROPPED
    # via an out-of-bounds index — never scattered into the trash block,
    # which stays all-zero so nothing nondeterministic can ever be read back
    phys = jnp.where(valid & (phys != trash), phys, trash + 1)
    off = pos % bs
    k_pool = cache["k"].at[phys, :, off].set(
        k.astype(cache["k"].dtype), mode="drop")
    v_pool = cache["v"].at[phys, :, off].set(
        v.astype(cache["v"].dtype), mode="drop")
    new_len = lengths.astype(jnp.int32)
    if registry.backend_for(cfg, "paged_attn") == "pallas":
        from repro.kernels import ops
        ctx = ops.paged_prefill_attention(q, k_pool, v_pool, table,
                                          hist.astype(jnp.int32), new_len,
                                          interpret=ops.default_interpret())
    else:
        gk = _paged_gather(k_pool, table)
        gv = _paged_gather(v_pool, table)
        ctx = paged_prefill_attention_ref(q, gk, gv, hist, new_len)
    new_cache = {"k": k_pool, "v": v_pool, "len": new_len}
    return ctx.reshape(B, S, -1) @ p["wo"], new_cache


def gqa_decode_paged(p, cfg, x, cache, table, active=None):
    """One-token paged decode: scatter the new K/V at pool slot
    (table[b, len // bs], len % bs), attend over the row's logical range.
    Always ragged (per-row ``len``); sliding windows are unsupported — the
    engine gates paged mode to non-windowed GQA archs.

    ``active`` ([B] bool): rows mid-chunked-prefill drop their write into
    the out-of-bounds lane and keep their ``len`` (see ``gqa_decode``)."""
    B = x.shape[0]
    q, k, v = _project_qkv(p, cfg, x)
    pos = cache["len"][:, None]                                  # [B,1]
    q = apply_rope(q, pos, cfg.rope_theta, cfg.partial_rotary_factor,
                   interleaved=cfg.rope_2d)
    k = apply_rope(k, pos, cfg.rope_theta, cfg.partial_rotary_factor,
                   interleaved=cfg.rope_2d)
    nb = table.shape[1]
    bs = cache["k"].shape[2]
    trash = cache["k"].shape[0] - 1
    lb = jnp.clip(cache["len"] // bs, 0, nb - 1)
    phys = jnp.take_along_axis(table, lb[:, None], axis=1)[:, 0]  # [B]
    # rows without an allocated block here (freed slots that keep stepping)
    # drop their write out of bounds — the trash block stays all-zero
    phys = jnp.where(phys == trash, trash + 1, phys)
    if active is not None:
        phys = jnp.where(active, phys, trash + 1)
    off = cache["len"] % bs
    k_pool = cache["k"].at[phys, :, off].set(
        k[:, 0].astype(cache["k"].dtype), mode="drop")
    v_pool = cache["v"].at[phys, :, off].set(
        v[:, 0].astype(cache["v"].dtype), mode="drop")
    new_len = cache["len"] + (jnp.int32(1) if active is None
                              else active.astype(jnp.int32))
    if registry.backend_for(cfg, "paged_attn") == "pallas":
        from repro.kernels import ops
        out = ops.paged_decode_attention(q, k_pool, v_pool, table, new_len,
                                         interpret=ops.default_interpret())
    else:
        gk = _paged_gather(k_pool, table)
        gv = _paged_gather(v_pool, table)
        T = gv.shape[1]
        vmask = (jnp.arange(T)[None, :] < new_len[:, None])[:, :, None, None]
        gv = jnp.where(vmask, gv, 0)
        out = decode_attention(q, gk, gv, new_len, window=0, backend="jnp")
    new_cache = {"k": k_pool, "v": v_pool, "len": new_len}
    return out.reshape(B, 1, -1) @ p["wo"], new_cache


# ---------------------------------------------------------------------------
# Cross-attention (enc-dec): q from decoder, kv from encoder memory (no rope)
# ---------------------------------------------------------------------------

def cross_attn_init(key, cfg, dtype):
    return gqa_init(key, cfg.with_(qkv_bias=False), dtype)


def cross_attn_apply(p, cfg, x, memory, memory_len=None):
    B, S, _ = x.shape
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = (x @ p["wq"]).reshape(B, S, H, hd)
    k = (memory @ p["wk"]).reshape(B, memory.shape[1], KV, hd)
    v = (memory @ p["wv"]).reshape(B, memory.shape[1], KV, hd)
    out = blockwise_attention(q, k, v, causal=False,
                              backend=registry.active_attn_backend(cfg))
    return out.reshape(B, S, -1) @ p["wo"]


# ---------------------------------------------------------------------------
# MLA — DeepSeek-V3 multi-head latent attention
# ---------------------------------------------------------------------------

def mla_init(key, cfg, dtype):
    m = cfg.mla
    d, H = cfg.d_model, cfg.num_heads
    ks = split_dict(key, ["wq_a", "wq_b", "wkv_a", "wkv_b", "wo",
                          "q_norm", "kv_norm"])
    qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "wq_a": dense_init(ks["wq_a"], d, m.q_lora_rank, dtype),
        "q_norm": jnp.ones((m.q_lora_rank,), dtype),
        "wq_b": dense_init(ks["wq_b"], m.q_lora_rank, H * qk_dim, dtype),
        "wkv_a": dense_init(ks["wkv_a"], d, m.kv_lora_rank + m.qk_rope_head_dim, dtype),
        "kv_norm": jnp.ones((m.kv_lora_rank,), dtype),
        "wkv_b": dense_init(ks["wkv_b"], m.kv_lora_rank,
                            H * (m.qk_nope_head_dim + m.v_head_dim), dtype),
        "wo": dense_init(ks["wo"], H * m.v_head_dim, d, dtype),
    }


def _rms(x, scale):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + 1e-6)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _mla_q(p, cfg, x, positions):
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.num_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    q = _rms(x @ p["wq_a"], p["q_norm"]) @ p["wq_b"]
    q = q.reshape(B, S, H, qk)
    q_nope, q_rope = q[..., :m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_latent(p, cfg, x, positions):
    m = cfg.mla
    kv = x @ p["wkv_a"]
    c_kv, k_rope = kv[..., :m.kv_lora_rank], kv[..., m.kv_lora_rank:]
    c_kv = _rms(c_kv, p["kv_norm"])
    k_rope = apply_rope(k_rope[..., None, :], positions, cfg.rope_theta)[..., 0, :]
    return c_kv, k_rope


def _mla_attend(p, cfg, x, positions):
    """Shared materialised full-sequence MLA body of apply/prefill.
    Returns (ctx [B,S,H*vd], c_kv, k_rope) so prefill can cache the
    compressed latents without re-deriving them."""
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.num_heads
    if positions.ndim == 1:
        positions = positions[None, :]
    q_nope, q_rope = _mla_q(p, cfg, x, positions)
    c_kv, k_rope = _mla_latent(p, cfg, x, positions)
    kvb = (c_kv @ p["wkv_b"]).reshape(B, S, H, m.qk_nope_head_dim + m.v_head_dim)
    k_nope, v = kvb[..., :m.qk_nope_head_dim], kvb[..., m.qk_nope_head_dim:]
    q = jnp.concatenate([q_nope, q_rope], -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                                  q_rope.shape)], -1)
    out = blockwise_attention(q, k, v, causal=True, window=cfg.attn_window,
                              backend=registry.active_attn_backend(cfg))
    return out.reshape(B, S, -1), c_kv, k_rope


def mla_apply(p, cfg, x, positions):
    """Training/prefill MLA: materialise per-head K/V from the latent."""
    ctx, _, _ = _mla_attend(p, cfg, x, positions)
    return ctx @ p["wo"]


def mla_prefill(p, cfg, x, positions, cache, *, lengths=None):
    """Fused MLA prefill: the materialised full-sequence pass of
    ``mla_apply`` plus a fill of the compressed (c_kv, k_rope) decode cache.
    ``lengths`` ([B] int32) makes the cache ``len`` per-row for ragged
    prompts (see ``gqa_prefill``). Returns (out [B,S,d], new_cache)."""
    S = x.shape[1]
    ctx, c_kv, k_rope = _mla_attend(p, cfg, x, positions)
    T = cache["c_kv"].shape[1]
    ring = bool(cfg.attn_window) and cfg.attn_window == T
    add = jnp.int32(S) if lengths is None else lengths.astype(jnp.int32)
    new_cache = {"c_kv": _prefill_fill(cache["c_kv"], c_kv, ring),
                 "k_rope": _prefill_fill(cache["k_rope"], k_rope, ring),
                 "len": cache["len"] + add}
    return ctx @ p["wo"], new_cache


def mla_prefill_chunked(p, cfg, x, cache, lengths, hist):
    """Chunked MLA prefill: scatter each row's next chunk of compressed
    latents (c_kv rms'd, k_rope roped — exactly what ``mla_prefill``
    caches) at absolute positions ``hist[b]..lengths[b]``, then attend the
    chunk queries over the row's full logical range by re-materialising
    per-head K/V from the CACHED latents (the ``mla_apply`` math on the
    cache instead of the activations). First chunks zero the row first —
    see ``gqa_prefill_chunked`` for why (NaN from a quarantined previous
    tenant would leak through decode's masked-but-multiplied lanes).
    Returns (out [B,S,d], new_cache)."""
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.num_heads
    pos = hist[:, None] + jnp.arange(S)[None, :]                 # [B,S]
    q_nope, q_rope = _mla_q(p, cfg, x, pos)
    c_kv, k_rope = _mla_latent(p, cfg, x, pos)
    T = cache["c_kv"].shape[1]
    reset = ((hist == 0) & (lengths > 0))[:, None, None]
    c_buf = jnp.where(reset, jnp.zeros_like(cache["c_kv"]), cache["c_kv"])
    r_buf = jnp.where(reset, jnp.zeros_like(cache["k_rope"]),
                      cache["k_rope"])
    valid = jnp.arange(S)[None, :] < (lengths - hist)[:, None]   # [B,S]
    dst = jnp.where(valid, pos, T)          # invalid lanes: dropped OOB
    bidx = jnp.arange(B)[:, None]
    c_buf = c_buf.at[bidx, dst].set(c_kv.astype(c_buf.dtype), mode="drop")
    r_buf = r_buf.at[bidx, dst].set(k_rope.astype(r_buf.dtype), mode="drop")
    new_len = lengths.astype(jnp.int32)
    kvb = (c_buf @ p["wkv_b"]).reshape(B, T, H,
                                       m.qk_nope_head_dim + m.v_head_dim)
    k_nope, v = kvb[..., :m.qk_nope_head_dim], kvb[..., m.qk_nope_head_dim:]
    q = jnp.concatenate([q_nope, q_rope], -1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(r_buf[:, :, None, :],
                                  (B, T, H, m.qk_rope_head_dim))], -1)
    ctx = paged_prefill_attention_ref(q, k, v, hist.astype(jnp.int32),
                                      new_len)
    new_cache = {"c_kv": c_buf, "k_rope": r_buf, "len": new_len}
    return ctx.reshape(B, S, -1) @ p["wo"], new_cache


def mla_decode(p, cfg, x, cache, *, ragged=False, active=None):
    """Absorbed-matmul MLA decode: attention runs in the latent space, so the
    KV cache stores only (c_kv, k_rope) — the compressed cache that makes
    DeepSeek-V3 decode cheap. ``ragged=True`` scatters each row at its own
    slot (continuous batching; see ``gqa_decode``). ``active`` ([B] bool,
    ragged-only) drops the write and freezes ``len`` for rows that are
    mid-chunked-prefill (see ``gqa_decode``)."""
    m = cfg.mla
    B = x.shape[0]
    H = cfg.num_heads
    pos = cache["len"][:, None]
    q_nope, q_rope = _mla_q(p, cfg, x, pos)          # [B,1,H,*]
    c_kv, k_rope = _mla_latent(p, cfg, x, pos)       # [B,1,kvr], [B,1,rd]
    T = cache["c_kv"].shape[1]
    ring = bool(cfg.attn_window) and cfg.attn_window == T
    if ragged:
        slot = cache["len"] % T if ring else jnp.minimum(cache["len"], T - 1)
        bidx = jnp.arange(B)
        if active is not None:
            slot = jnp.where(active, slot, T)        # inactive: dropped OOB
        c_cache = cache["c_kv"].at[bidx, slot].set(c_kv[:, 0], mode="drop")
        r_cache = cache["k_rope"].at[bidx, slot].set(k_rope[:, 0],
                                                     mode="drop")
    else:
        if active is not None:
            raise ValueError("active mask requires ragged=True")
        # synchronized batched decode (see gqa_decode): time-axis DUS
        if ring:
            slot0 = cache["len"][0] % T              # ring buffer (windowed)
        else:
            slot0 = jnp.minimum(cache["len"][0], T - 1)
        c_cache = jax.lax.dynamic_update_slice_in_dim(cache["c_kv"], c_kv,
                                                      slot0, 1)
        r_cache = jax.lax.dynamic_update_slice_in_dim(cache["k_rope"], k_rope,
                                                      slot0, 1)
    new_len = cache["len"] + (jnp.int32(1) if active is None
                              else active.astype(jnp.int32))

    wkv_b = p["wkv_b"].reshape(m.kv_lora_rank, H, m.qk_nope_head_dim + m.v_head_dim)
    w_uk = wkv_b[..., :m.qk_nope_head_dim]           # [kvr,H,nope]
    w_uv = wkv_b[..., m.qk_nope_head_dim:]           # [kvr,H,vd]
    # absorb W_UK into the query
    q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, w_uk)   # [B,1,H,kvr]
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    if registry.backend_for(cfg, "decode_attn") == "pallas":
        # flash-decode in the latent space: every head attends the SAME
        # compressed cache, i.e. GQA with one kv "head" holding
        # [c_kv | k_rope]. The kernel scales by 1/sqrt(d_cat); pre-scale q
        # so the effective scale is the MLA 1/sqrt(nope+rope).
        from repro.kernels import ops
        d_cat = m.kv_lora_rank + m.qk_rope_head_dim
        q_cat = jnp.concatenate([q_lat, q_rope], -1) * (math.sqrt(d_cat) * scale)
        k_cat = jnp.concatenate([c_cache, r_cache], -1)[:, :, None, :]
        v_lat = c_cache[:, :, None, :]               # [B,T,1,kvr]
        ctx_lat = ops.decode_attention(q_cat.astype(x.dtype), k_cat, v_lat,
                                       new_len,
                                       interpret=ops.default_interpret())
    else:
        s = (jnp.einsum("bshr,btr->bhst", q_lat, c_cache, preferred_element_type=jnp.float32)
             + jnp.einsum("bshr,btr->bhst", q_rope, r_cache, preferred_element_type=jnp.float32)
             ) * scale                               # [B,H,1,T]
        valid = jnp.arange(T)[None, None, None, :] < new_len[:, None, None, None]
        s = jnp.where(valid, s, NEG_INF)
        pattn = jax.nn.softmax(s, axis=-1)
        ctx_lat = jnp.einsum("bhst,btr->bshr", pattn, c_cache.astype(jnp.float32))
    out = jnp.einsum("bshr,rhv->bshv", ctx_lat.astype(x.dtype), w_uv)
    out = out.reshape(B, 1, -1) @ p["wo"]
    return out, {"c_kv": c_cache, "k_rope": r_cache, "len": new_len}


def mla_cache_init(cfg, batch: int, cache_len: int, dtype):
    m = cfg.mla
    T = min(cache_len, cfg.attn_window) if cfg.attn_window else cache_len
    return {"c_kv": jnp.zeros((batch, T, m.kv_lora_rank), dtype),
            "k_rope": jnp.zeros((batch, T, m.qk_rope_head_dim), dtype),
            "len": jnp.zeros((batch,), jnp.int32)}
