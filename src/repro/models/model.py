"""Top-level models: init, train loss, prefill, one-token decode, per family.

Public API (all pure functions of (cfg, params, ...)):
    init_params(cfg, key)               -> params pytree
    loss_fn(cfg, params, batch)         -> (loss, metrics)
    prefill_logits(cfg, params, batch)  -> last-position logits (+ cache-free)
    prefill_with_cache(cfg, params, batch, cache)
                                        -> (last-position logits, filled
                                           decode cache) — ONE fused
                                           full-sequence pass, no per-token
                                           teacher forcing
    encode(cfg, params, frames)         -> encoder memory (encdec archs)
    init_cache(cfg, batch, cache_len)   -> decode cache pytree
    decode_step(cfg, params, batch, cache) -> (logits [B,V], new cache)
    param_stage_ids(cfg, params, n_stages) -> pytree of int32 stage ids
                                           (broadcastable to each leaf; used
                                           by the CDP update rules)

Full-sequence attention dispatches on the kernel-backend registry: the
train path uses the ``train_attn`` op, ``prefill_logits`` /
``prefill_with_cache`` enter ``registry.prefill_scope()`` so the same
layer code resolves ``prefill_attn``; decode and the SSM scan read their
own ops directly.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import (FAMILY_DENSE, FAMILY_ENCDEC, FAMILY_HYBRID,
                                FAMILY_MOE, FAMILY_SSM, FAMILY_VLM,
                                ModelConfig)
from repro.kernels import registry
from repro.models import blocks as B
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.common import (count_params, dense_init, dtype_of,
                                 embed_init, split_dict)
from repro.models.layers import apply_norm, mlp_param_count, norm_init

PyTree = Any


# ---------------------------------------------------------------------------
# Layout helpers
# ---------------------------------------------------------------------------

def _moe_split(cfg) -> tuple[int, int]:
    """(n_dense_layers, n_moe_layers) for the decoder stack."""
    if cfg.family == FAMILY_MOE and cfg.moe is not None:
        k = cfg.moe.first_k_dense
        return k, cfg.num_layers - k
    return cfg.num_layers, 0


def _xlstm_layout(cfg) -> tuple[int, int]:
    """(n_periods, period) — each period = (period-1) mLSTM + 1 sLSTM."""
    every = cfg.ssm.slstm_every
    if not every:
        return 0, 0
    assert cfg.num_layers % every == 0, "num_layers must divide slstm_every"
    return cfg.num_layers // every, every


def _hybrid_layout(cfg) -> tuple[int, int, int]:
    """(n_periods, period, tail) — shared attn block after each period."""
    every = cfg.hybrid.shared_attn_every
    n_periods = cfg.num_layers // every
    tail = cfg.num_layers - n_periods * every
    return n_periods, every, tail


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key) -> PyTree:
    dt = dtype_of(cfg)
    ks = split_dict(key, ["embed", "blocks", "blocks2", "head", "extra",
                          "enc", "mtp"])
    V = padded_vocab(cfg)
    p: Dict[str, Any] = {"embed": embed_init(ks["embed"], V, cfg.d_model, dt),
                         "final_norm": norm_init(cfg.norm, cfg.d_model, dt)}
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(ks["head"], cfg.d_model, V, dt, scale=0.02)

    fam = cfg.family
    if fam in (FAMILY_DENSE, FAMILY_MOE, FAMILY_VLM):
        n_dense, n_moe = _moe_split(cfg)
        blk = {}
        if n_dense:
            blk["dense"] = B._stack_init(
                lambda k: B.decoder_layer_init(k, cfg, dt, use_moe=False),
                ks["blocks"], n_dense)
        if n_moe:
            blk["moe"] = B._stack_init(
                lambda k: B.decoder_layer_init(k, cfg, dt, use_moe=True),
                ks["blocks2"], n_moe)
        p["blocks"] = blk
        if fam == FAMILY_VLM:
            v = cfg.vlm
            ke = split_dict(ks["extra"], ["p1", "p2"])
            p["projector"] = {
                "ln": norm_init("layernorm", v.vision_dim, dt),
                "w1": dense_init(ke["p1"], v.vision_dim, v.projector_hidden, dt),
                "w2": dense_init(ke["p2"], v.projector_hidden, cfg.d_model, dt)}
        if cfg.mtp:
            km = split_dict(ks["mtp"], ["l", "proj"])
            p["mtp"] = {"layer": B.decoder_layer_init(km["l"], cfg, dt, use_moe=False),
                        "norm": norm_init(cfg.norm, cfg.d_model, dt)}
    elif fam == FAMILY_ENCDEC:
        e = cfg.encdec
        ke = split_dict(ks["enc"], ["front", "layers"])
        p["frontend_proj"] = dense_init(ke["front"], e.frontend_dim,
                                        cfg.d_model, dt)
        p["encoder"] = {
            "blocks": B._stack_init(lambda k: B.encoder_layer_init(k, cfg, dt),
                                    ke["layers"], e.encoder_layers),
            "final_norm": norm_init(cfg.norm, cfg.d_model, dt)}
        p["blocks"] = {"xdec": B._stack_init(
            lambda k: B.xdec_layer_init(k, cfg, dt), ks["blocks"],
            cfg.num_layers)}
    elif fam == FAMILY_SSM:
        n_periods, period = _xlstm_layout(cfg)
        blk = {}
        if n_periods:
            def init_period(k):
                k1, k2 = jax.random.split(k)
                return {"mlstm": B._stack_init(
                            lambda kk: B.mlstm_layer_init(kk, cfg, dt),
                            k1, period - 1),
                        "slstm": B.slstm_layer_init(k2, cfg, dt)}
            blk["periods"] = B._stack_init(init_period, ks["blocks"], n_periods)
        else:
            blk["mlstm"] = B._stack_init(
                lambda k: B.mlstm_layer_init(k, cfg, dt), ks["blocks"],
                cfg.num_layers)
        p["blocks"] = blk
    elif fam == FAMILY_HYBRID:
        n_periods, period, tail = _hybrid_layout(cfg)
        blk = {"mamba_main": B._stack_init(
                   lambda k: jax.vmap(lambda kk: B.mamba_layer_init(kk, cfg, dt))(
                       jax.random.split(k, period)),
                   ks["blocks"], n_periods),
               "shared": B.shared_attn_block_init(ks["extra"], cfg, dt)}
        if tail:
            blk["mamba_tail"] = B._stack_init(
                lambda k: B.mamba_layer_init(k, cfg, dt), ks["blocks2"], tail)
        p["blocks"] = blk
    else:
        raise ValueError(f"unknown family {fam}")
    return p


# ---------------------------------------------------------------------------
# Forward (training / prefill)
# ---------------------------------------------------------------------------

def padded_vocab(cfg) -> int:
    """Vocab padded to a multiple of 256 so the vocab dim shards over any
    reasonable tensor-parallel axis (an unshardable vocab replicates the
    embedding AND the [tokens, V] logits — tens of GiB at 32k prefill)."""
    return -(-cfg.vocab_size // 256) * 256


def _embed(cfg, params, tokens):
    return params["embed"][tokens]


def _head(cfg, params, x):
    h = apply_norm(cfg.norm, params["final_norm"], x)
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = h @ w
    V = padded_vocab(cfg)
    if V != cfg.vocab_size:     # mask the padded columns
        pad = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                       logits.ndim - 1) >= cfg.vocab_size
        logits = jnp.where(pad, -1e30, logits)
    return logits


def _run_decoder_stack(cfg, params, x, positions, drop_tokens: bool = True):
    """dense/moe/vlm decoder trunk. Returns (hidden, aux_loss).
    ``drop_tokens=False`` -> dropless MoE routing (inference)."""
    aux = jnp.float32(0.0)
    blk = params["blocks"]
    if "dense" in blk:
        fn = lambda lp, h: B.decoder_layer_apply(lp, cfg, h, positions,
                                                 use_moe=False)
        x, a = B.scan_layers(fn, blk["dense"], x)
        aux += a
    if "moe" in blk:
        fn = lambda lp, h: B.decoder_layer_apply(lp, cfg, h, positions,
                                                 use_moe=True,
                                                 drop_tokens=drop_tokens)
        x, a = B.scan_layers(fn, blk["moe"], x)
        aux += a
    return x, aux


def _run_ssm_stack(cfg, params, x):
    aux = jnp.float32(0.0)
    blk = params["blocks"]
    if "periods" in blk:
        def period_fn(pp, h):
            fn = lambda lp, hh: B.mlstm_layer_apply(lp, cfg, hh)
            h, a = B.scan_layers(fn, pp["mlstm"], h)
            h2, _ = B.slstm_layer_apply(pp["slstm"], cfg, h)
            return h2, a
        x, aux = B.scan_layers(period_fn, blk["periods"], x)
    else:
        fn = lambda lp, h: B.mlstm_layer_apply(lp, cfg, h)
        x, aux = B.scan_layers(fn, blk["mlstm"], x)
    return x, aux


def _run_hybrid_stack(cfg, params, x, positions):
    blk = params["blocks"]
    shared = blk["shared"]

    def period_fn(pp, h):
        fn = lambda lp, hh: B.mamba_layer_apply(lp, cfg, hh)
        h, a = B.scan_layers(fn, pp, h)
        h = B.shared_attn_block_apply(shared, cfg, h, positions)
        return h, a

    x, aux = B.scan_layers(period_fn, blk["mamba_main"], x)
    if "mamba_tail" in blk:
        fn = lambda lp, h: B.mamba_layer_apply(lp, cfg, h)
        x, a = B.scan_layers(fn, blk["mamba_tail"], x)
        aux += a
    return x, aux


def _run_encoder(cfg, params, frames):
    x = frames @ params["frontend_proj"]
    pos = jnp.arange(x.shape[1])
    fn = lambda lp, h: B.encoder_layer_apply(lp, cfg, h, pos)
    x, _ = B.scan_layers(fn, params["encoder"]["blocks"], x)
    return apply_norm(cfg.norm, params["encoder"]["final_norm"], x)


def encode(cfg: ModelConfig, params: PyTree, frames) -> jnp.ndarray:
    """Public encoder forward for enc-dec archs: precomputed frame
    embeddings [B, T_frames, frontend_dim] -> memory [B, T_frames, d_model].
    Serving code uses this (under the prefill attention op) instead of
    reaching into the private ``_run_encoder``."""
    if cfg.family != FAMILY_ENCDEC:
        raise ValueError(f"encode() is for encdec archs, not {cfg.family!r}")
    with registry.prefill_scope():
        return _run_encoder(cfg, params, frames)


def forward(cfg: ModelConfig, params: PyTree, batch: Dict[str, Any]):
    """Full-sequence forward. Returns (logits [B,S,V], aux_loss, hidden)."""
    fam = cfg.family
    tokens = batch["tokens"]
    Bsz, S = tokens.shape
    x = _embed(cfg, params, tokens)
    positions = jnp.arange(S)

    if fam == FAMILY_VLM:
        v = cfg.vlm
        patches = batch["patches"]
        pr = params["projector"]
        pe = apply_norm("layernorm", pr["ln"], patches)
        pe = jax.nn.gelu(pe @ pr["w1"]) @ pr["w2"]
        x = jnp.concatenate([pe.astype(x.dtype), x], axis=1)
        positions = jnp.arange(x.shape[1])
        h, aux = _run_decoder_stack(cfg, params, x, positions)
        h = h[:, patches.shape[1]:]                 # text positions only
    elif fam in (FAMILY_DENSE, FAMILY_MOE):
        h, aux = _run_decoder_stack(cfg, params, x, positions)
    elif fam == FAMILY_ENCDEC:
        memory = _run_encoder(cfg, params, batch["frames"])
        fn = lambda lp, hh: B.xdec_layer_apply(lp, cfg, hh, positions, memory)
        h, aux = B.scan_layers(fn, params["blocks"]["xdec"], x)
    elif fam == FAMILY_SSM:
        h, aux = _run_ssm_stack(cfg, params, x)
    elif fam == FAMILY_HYBRID:
        h, aux = _run_hybrid_stack(cfg, params, x, positions)
    else:
        raise ValueError(fam)

    logits = _head(cfg, params, h)
    return logits, aux, h


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------

def _xent(logits, targets, mask=None):
    # one-hot contraction instead of take_along_axis: gathers along a
    # tensor-parallel (vocab-sharded) dim force GSPMD to replicate the
    # logits; the masked-sum partitions cleanly shard-local + all-reduce.
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    V = logits.shape[-1]
    onehot = (targets[..., None] == jax.lax.broadcasted_iota(
        jnp.int32, targets.shape + (V,), targets.ndim))
    ll = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
    nll = lse - ll
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def loss_fn(cfg: ModelConfig, params: PyTree, batch: Dict[str, Any]):
    logits, aux, h = forward(cfg, params, batch)
    loss = _xent(logits, batch["targets"])
    metrics = {"xent": loss, "aux": aux}
    if cfg.mtp and "mtp" in params:
        # DeepSeek-style multi-token prediction: one extra layer over the
        # trunk hidden state predicts token t+2.
        pos = jnp.arange(h.shape[1])
        h2 = apply_norm(cfg.norm, params["mtp"]["norm"], h)
        h2, _ = B.decoder_layer_apply(params["mtp"]["layer"], cfg, h2, pos,
                                      use_moe=False)
        logits2 = _head(cfg, params, h2)
        t2 = jnp.concatenate([batch["targets"][:, 1:],
                              batch["targets"][:, -1:]], axis=1)
        mtp_loss = _xent(logits2, t2)
        metrics["mtp"] = mtp_loss
        loss = loss + 0.3 * mtp_loss
    loss = loss + aux
    metrics["loss"] = loss
    return loss, metrics


def prefill_logits(cfg, params, batch):
    """Last-position logits only: the [B,S,V] logits tensor of a 32k prefill
    is tens of GiB, so the head matmul runs on the final hidden state."""
    fam = cfg.family
    with registry.prefill_scope():
        tokens = batch["tokens"]
        x = _embed(cfg, params, tokens)
        positions = jnp.arange(tokens.shape[1])
        if fam == FAMILY_VLM:
            v = cfg.vlm
            pr = params["projector"]
            pe = apply_norm("layernorm", pr["ln"], batch["patches"])
            pe = jax.nn.gelu(pe @ pr["w1"]) @ pr["w2"]
            x = jnp.concatenate([pe.astype(x.dtype), x], axis=1)
            positions = jnp.arange(x.shape[1])
            h, _ = _run_decoder_stack(cfg, params, x, positions,
                                      drop_tokens=False)
        elif fam in (FAMILY_DENSE, FAMILY_MOE):
            h, _ = _run_decoder_stack(cfg, params, x, positions,
                                      drop_tokens=False)
        elif fam == FAMILY_ENCDEC:
            memory = _run_encoder(cfg, params, batch["frames"])
            fn = lambda lp, hh: B.xdec_layer_apply(lp, cfg, hh, positions,
                                                   memory)
            h, _ = B.scan_layers(fn, params["blocks"]["xdec"], x)
        elif fam == FAMILY_SSM:
            h, _ = _run_ssm_stack(cfg, params, x)
        elif fam == FAMILY_HYBRID:
            h, _ = _run_hybrid_stack(cfg, params, x, positions)
        else:
            raise ValueError(fam)
        return _head(cfg, params, h[:, -1:])[:, 0]


# ---------------------------------------------------------------------------
# Decode (one token, cached)
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, cache_len: int) -> PyTree:
    dt = dtype_of(cfg)
    fam = cfg.family
    if fam in (FAMILY_DENSE, FAMILY_MOE, FAMILY_VLM):
        n_dense, n_moe = _moe_split(cfg)
        cache: Dict[str, Any] = {}
        one = lambda: B.decoder_layer_cache_init(cfg, batch, cache_len, dt)
        if n_dense:
            cache["dense"] = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (n_dense,) + x.shape).copy(), one())
        if n_moe:
            cache["moe"] = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (n_moe,) + x.shape).copy(), one())
        if cfg.mtp:
            cache["mtp"] = one()
        return cache
    if fam == FAMILY_ENCDEC:
        e = cfg.encdec
        n_frames = cache_len // e.frame_rate_divisor
        dec_len = min(cache_len, 2048)
        one = B.decoder_layer_cache_init(cfg.with_(attn_window=0), batch,
                                         dec_len, dt)
        return {"self": jax.tree.map(
                    lambda x: jnp.broadcast_to(x, (cfg.num_layers,) + x.shape).copy(), one),
                "memory": jnp.zeros((batch, n_frames, cfg.d_model), dt)}
    if fam == FAMILY_SSM:
        n_periods, period = _xlstm_layout(cfg)
        if n_periods:
            m = ssm_mod.mlstm_cache_init(cfg, batch)
            s = B.slstm_layer_apply  # unused; placeholder
            return {"periods": {
                "mlstm": jax.tree.map(
                    lambda x: jnp.broadcast_to(x, (n_periods, period - 1) + x.shape).copy(), m),
                "slstm": jax.tree.map(
                    lambda x: jnp.broadcast_to(x, (n_periods,) + x.shape).copy(),
                    ssm_mod.slstm_cache_init(cfg, batch))}}
        m = ssm_mod.mlstm_cache_init(cfg, batch)
        return {"mlstm": jax.tree.map(
            lambda x: jnp.broadcast_to(x, (cfg.num_layers,) + x.shape).copy(), m)}
    if fam == FAMILY_HYBRID:
        n_periods, period, tail = _hybrid_layout(cfg)
        mc = ssm_mod.mamba2_cache_init(cfg, batch, dt)
        att_len = min(cache_len, cfg.attn_window) if cfg.attn_window else cache_len
        ac = {"k": jnp.zeros((batch, att_len, cfg.num_kv_heads,
                              cfg.resolved_head_dim), dt),
              "v": jnp.zeros((batch, att_len, cfg.num_kv_heads,
                              cfg.resolved_head_dim), dt),
              "len": jnp.zeros((batch,), jnp.int32)}
        cache = {"mamba_main": jax.tree.map(
                     lambda x: jnp.broadcast_to(x, (n_periods, period) + x.shape).copy(), mc),
                 "shared": jax.tree.map(
                     lambda x: jnp.broadcast_to(x, (n_periods,) + x.shape).copy(), ac)}
        if tail:
            cache["mamba_tail"] = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (tail,) + x.shape).copy(), mc)
        return cache
    raise ValueError(fam)


def paged_unsupported_reason(cfg: ModelConfig) -> Optional[str]:
    """None if the paged KV cache supports this config, else why not (the
    engine falls back to — or fails fast toward — the dense merge_caches
    path with this reason)."""
    if cfg.family not in (FAMILY_DENSE, FAMILY_MOE):
        return (f"family {cfg.family!r} (paged cache supports dense/moe "
                f"decoder stacks)")
    if cfg.attn_kind == "mla":
        return "MLA latent caches (paged cache supports GQA attention only)"
    if cfg.attn_window:
        return (f"attn_window={cfg.attn_window} (paged cache is linear; "
                f"ring-buffer windows stay dense)")
    return None


def init_paged_cache(cfg: ModelConfig, batch: int, num_blocks: int,
                     block_size: int, cache_len: int) -> PyTree:
    """Paged decode cache: per layer-group block pools (k/v
    [L, num_blocks+1, KV, bs, hd] — one physical block id spans all layers;
    the extra last block is the write-off "trash" block) plus a top-level
    ``table`` [B, nb_max] int32 owned by the engine's allocator. Unallocated
    table entries point at the trash block. ``cache_len`` (a multiple of
    ``block_size``) bounds the logical range: nb_max = cache_len // bs."""
    reason = paged_unsupported_reason(cfg)
    if reason is not None:
        raise NotImplementedError(f"paged KV cache: unsupported — {reason}")
    if cache_len % block_size:
        raise ValueError(f"cache_len {cache_len} must be a multiple of "
                         f"kv block size {block_size}")
    dt = dtype_of(cfg)
    nb_max = cache_len // block_size
    n_dense, n_moe = _moe_split(cfg)
    one = lambda: B.decoder_layer_paged_cache_init(cfg, batch, num_blocks,
                                                   block_size, dt)
    cache: Dict[str, Any] = {
        "table": jnp.full((batch, nb_max), num_blocks, jnp.int32)}
    if n_dense:
        cache["dense"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_dense,) + x.shape).copy(), one())
    if n_moe:
        cache["moe"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_moe,) + x.shape).copy(), one())
    if cfg.mtp:
        cache["mtp"] = B.decoder_layer_cache_init(cfg, batch, cache_len, dt)
    return cache


def decode_step(cfg: ModelConfig, params: PyTree, batch: Dict[str, Any],
                cache: PyTree, *, ragged: bool = False):
    """batch: {"token": [B] int32}. Returns (logits [B,V], new_cache).

    ``ragged=True`` (static, serving-only) decodes with genuinely per-row
    cache lengths: attention caches scatter each row's k/v at its own slot
    instead of one synchronized dynamic_update_slice, so a continuous-
    batching engine can run rows at different positions in ONE jitted step.
    SSM/recurrent state layers are per-row already and ignore the flag.

    ``batch["active"]`` ([B] bool, ragged attention families only): rows
    marked inactive (slots mid-chunked-prefill) drop their cache write and
    keep their per-row ``len`` — absent (or all-True) is value-identical
    to the historical step."""
    fam = cfg.family
    x = _embed(cfg, params, batch["token"][:, None])     # [B,1,d]
    blk = params["blocks"]
    new_cache: Dict[str, Any] = {}
    # paged cache pytrees carry the engine-owned block table at the top level
    # (a host-side trace-time check — no new static argument)
    table = cache.get("table") if isinstance(cache, dict) else None
    active = batch.get("active")
    if active is not None and not (ragged or table is not None):
        raise NotImplementedError(
            "batch['active'] requires ragged decode (chunked prefill is a "
            "continuous-batching feature)")

    if fam in (FAMILY_DENSE, FAMILY_MOE, FAMILY_VLM):
        if "dense" in blk:
            fn = lambda lp, h, c: B.decoder_layer_decode(lp, cfg, h, c,
                                                         use_moe=False,
                                                         ragged=ragged,
                                                         paged_table=table,
                                                         active=active)
            x, nc = _decode_scan(fn, blk["dense"], cache["dense"], x)
            new_cache["dense"] = nc
        if "moe" in blk:
            fn = lambda lp, h, c: B.decoder_layer_decode(lp, cfg, h, c,
                                                         use_moe=True,
                                                         ragged=ragged,
                                                         paged_table=table,
                                                         active=active)
            x, nc = _decode_scan(fn, blk["moe"], cache["moe"], x)
            new_cache["moe"] = nc
        if cfg.mtp:
            new_cache["mtp"] = cache["mtp"]
        if table is not None:
            new_cache["table"] = table
    elif fam == FAMILY_ENCDEC:
        memory = cache["memory"]
        fn = lambda lp, h, c: B.xdec_layer_decode(lp, cfg, h, c, memory,
                                                  ragged=ragged)
        x, nc = _decode_scan(fn, blk["xdec"], cache["self"], x)
        new_cache = {"self": nc, "memory": memory}
    elif fam == FAMILY_SSM:
        if "periods" in blk:
            def period_fn(h, inp):
                pp, pc = inp
                fn = lambda lp, hh, c: B.mlstm_layer_decode(lp, cfg, hh, c)
                h, mlc = _decode_scan(fn, pp["mlstm"], pc["mlstm"], h)
                h, slc = B.slstm_layer_apply(pp["slstm"], cfg, h, pc["slstm"])
                return h, {"mlstm": mlc, "slstm": slc}
            x, nc = jax.lax.scan(period_fn, x,
                                 (blk["periods"], cache["periods"]))
            new_cache = {"periods": nc}
        else:
            fn = lambda lp, h, c: B.mlstm_layer_decode(lp, cfg, h, c)
            x, nc = _decode_scan(fn, blk["mlstm"], cache["mlstm"], x)
            new_cache = {"mlstm": nc}
    elif fam == FAMILY_HYBRID:
        shared = blk["shared"]

        def period_fn(h, inp):
            pp, pc_m, pc_a = inp
            fn = lambda lp, hh, c: B.mamba_layer_decode(lp, cfg, hh, c)
            h, mc = _decode_scan(fn, pp, pc_m, h)
            h, ac = B.shared_attn_block_decode(shared, cfg, h, pc_a,
                                               ragged=ragged)
            return h, (mc, ac)

        x, (mc, ac) = jax.lax.scan(
            period_fn, x, (blk["mamba_main"], cache["mamba_main"],
                           cache["shared"]))
        new_cache = {"mamba_main": mc, "shared": ac}
        if "mamba_tail" in blk:
            fn = lambda lp, h, c: B.mamba_layer_decode(lp, cfg, h, c)
            x, tc = _decode_scan(fn, blk["mamba_tail"], cache["mamba_tail"], x)
            new_cache["mamba_tail"] = tc
    else:
        raise ValueError(fam)

    logits = _head(cfg, params, x)[:, 0]
    return logits, new_cache


def _decode_scan(layer_fn, stacked, caches, x):
    def body(h, inp):
        lp, c = inp
        h, nc = layer_fn(lp, h, c)
        return h, nc
    return jax.lax.scan(body, x, (stacked, caches))


# ---------------------------------------------------------------------------
# Fused prefill: one full-sequence pass that fills the decode cache
# ---------------------------------------------------------------------------

def prefill_with_cache(cfg: ModelConfig, params: PyTree,
                       batch: Dict[str, Any], cache: PyTree):
    """Fused prefill from a FRESH ``init_cache`` pytree: one blockwise/flash
    full-sequence forward per layer that also writes every layer's decode
    state (KV / latent / recurrent), replacing the per-token teacher-forcing
    loop. Returns (last-position logits [B,V], filled cache).

    The attention contraction resolves the ``prefill_attn`` registry op; the
    enc-dec memory is the EXACT encoder output (no zeros-padded splice — the
    returned cache's memory shape follows the encoder, and decode re-traces
    on it).

    Ragged prompts (continuous batching): ``batch["lengths"]`` ([B] int32)
    declares per-row prompt lengths for prompts packed LEFT-ALIGNED into the
    fixed [B,S] buffer. The cache ``len`` becomes per-row, the returned
    logits are taken at each row's last VALID position, and pad-tail cache
    slots are dead (decode masks by per-row len and overwrites them).
    Causality keeps every valid position pad-free; only attention-cache
    families support it (SSM/recurrent state would absorb the pad tail)."""
    fam = cfg.family
    lengths = batch.get("lengths")
    if lengths is not None and fam not in (FAMILY_DENSE, FAMILY_MOE,
                                           FAMILY_VLM):
        raise NotImplementedError(
            f"ragged prefill (batch['lengths']) is only supported for "
            f"attention-cache families (dense/moe/vlm), not {fam!r}: a "
            f"recurrent prefill state would absorb the pad tail")
    with registry.prefill_scope():
        tokens = batch["tokens"]
        x = _embed(cfg, params, tokens)
        positions = jnp.arange(tokens.shape[1])
        blk = params["blocks"]
        new_cache: Dict[str, Any] = {}
        eff_lengths = lengths
        tail_lengths = None     # paged: x holds only the ragged tail

        if fam == FAMILY_VLM:
            pr = params["projector"]
            pe = apply_norm("layernorm", pr["ln"], batch["patches"])
            pe = jax.nn.gelu(pe @ pr["w1"]) @ pr["w2"]
            x = jnp.concatenate([pe.astype(x.dtype), x], axis=1)
            positions = jnp.arange(x.shape[1])
            if lengths is not None:          # patch prefix is always valid
                eff_lengths = lengths + pe.shape[1]

        if fam in (FAMILY_DENSE, FAMILY_MOE, FAMILY_VLM):
            # paged cache: ragged-tail prefill through the block table.
            # batch["hist"] [B] (default zeros) = tokens already in the
            # cache (a prefix-cache hit); only positions hist..lengths are
            # computed and written.
            table = cache.get("table") if isinstance(cache, dict) else None
            paged = None
            chunk_hist = None
            if table is not None:
                if lengths is None:
                    raise NotImplementedError(
                        "paged prefill requires batch['lengths'] (the paged "
                        "cache is always ragged)")
                hist = batch.get("hist")
                if hist is None:
                    hist = jnp.zeros_like(eff_lengths)
                paged = (table, hist.astype(jnp.int32))
                # row b's hidden states cover absolute positions
                # hist[b]..lengths[b]; its last valid logit sits at tail
                # index (lengths - hist) - 1 (the allocator caps hist at
                # lengths - 1, so admitted rows always have a tail)
                tail_lengths = eff_lengths - hist
            elif batch.get("hist") is not None:
                # dense-cache chunked prefill: x holds only each row's next
                # prompt chunk (absolute positions hist..lengths), scattered
                # into the dense [B,T] cache at its absolute slots
                if lengths is None:
                    raise NotImplementedError(
                        "chunked prefill requires batch['lengths'] (chunks "
                        "are always ragged)")
                if fam == FAMILY_VLM:
                    raise NotImplementedError(
                        "chunked prefill does not support VLM prompts (the "
                        "patch prefix is prefilled in one piece)")
                chunk_hist = batch["hist"].astype(jnp.int32)
                tail_lengths = eff_lengths - chunk_hist
            if "dense" in blk:
                fn = lambda lp, h, c: B.decoder_layer_prefill(
                    lp, cfg, h, positions, c, use_moe=False,
                    lengths=eff_lengths, paged=paged, chunk_hist=chunk_hist)
                x, nc = _decode_scan(fn, blk["dense"], cache["dense"], x)
                new_cache["dense"] = nc
            if "moe" in blk:
                fn = lambda lp, h, c: B.decoder_layer_prefill(
                    lp, cfg, h, positions, c, use_moe=True,
                    lengths=eff_lengths, paged=paged, chunk_hist=chunk_hist)
                x, nc = _decode_scan(fn, blk["moe"], cache["moe"], x)
                new_cache["moe"] = nc
            if cfg.mtp:
                new_cache["mtp"] = cache["mtp"]
            if table is not None:
                new_cache["table"] = table
        elif fam == FAMILY_ENCDEC:
            memory = _run_encoder(cfg, params, batch["frames"])
            fn = lambda lp, h, c: B.xdec_layer_prefill(lp, cfg, h, positions,
                                                       c, memory)
            x, nc = _decode_scan(fn, blk["xdec"], cache["self"], x)
            new_cache = {"self": nc, "memory": memory}
        elif fam == FAMILY_SSM:
            if "periods" in blk:
                def period_fn(h, inp):
                    pp, pc = inp
                    fn = lambda lp, hh, c: B.mlstm_layer_prefill(lp, cfg, hh, c)
                    h, mlc = _decode_scan(fn, pp["mlstm"], pc["mlstm"], h)
                    h, slc = B.slstm_layer_apply(pp["slstm"], cfg, h,
                                                 pc["slstm"])
                    return h, {"mlstm": mlc, "slstm": slc}
                x, nc = jax.lax.scan(period_fn, x,
                                     (blk["periods"], cache["periods"]))
                new_cache = {"periods": nc}
            else:
                fn = lambda lp, h, c: B.mlstm_layer_prefill(lp, cfg, h, c)
                x, nc = _decode_scan(fn, blk["mlstm"], cache["mlstm"], x)
                new_cache = {"mlstm": nc}
        elif fam == FAMILY_HYBRID:
            shared = blk["shared"]

            def period_fn(h, inp):
                pp, pc_m, pc_a = inp
                fn = lambda lp, hh, c: B.mamba_layer_prefill(lp, cfg, hh, c)
                h, mc = _decode_scan(fn, pp, pc_m, h)
                h, ac = B.shared_attn_block_prefill(shared, cfg, h,
                                                    positions, pc_a)
                return h, (mc, ac)

            x, (mc, ac) = jax.lax.scan(
                period_fn, x, (blk["mamba_main"], cache["mamba_main"],
                               cache["shared"]))
            new_cache = {"mamba_main": mc, "shared": ac}
            if "mamba_tail" in blk:
                fn = lambda lp, h, c: B.mamba_layer_prefill(lp, cfg, h, c)
                x, tc = _decode_scan(fn, blk["mamba_tail"],
                                     cache["mamba_tail"], x)
                new_cache["mamba_tail"] = tc
        else:
            raise ValueError(fam)

        if eff_lengths is not None:
            # per-row last VALID position (ragged prompts, left-aligned)
            gl = eff_lengths if tail_lengths is None else tail_lengths
            idx = jnp.clip(gl - 1, 0, x.shape[1] - 1)
            x_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)
            return _head(cfg, params, x_last)[:, 0], new_cache
        return _head(cfg, params, x[:, -1:])[:, 0], new_cache


# ---------------------------------------------------------------------------
# Stage ids for CDP update rules
# ---------------------------------------------------------------------------

def _stage_of(layer_idx, total_layers: int, n_stages: int):
    return (layer_idx * n_stages) // max(1, total_layers)


def param_stage_ids(cfg: ModelConfig, params: PyTree, n_stages: int) -> PyTree:
    """For every leaf, an int32 array broadcastable to the leaf giving the
    CDP stage of the parameters it holds. Stacked layer axes map layer ->
    stage with an even split; embedding -> stage 0; head/final -> N-1."""
    fam = cfg.family
    enc_layers = cfg.encdec.encoder_layers if cfg.encdec else 0
    total = cfg.num_layers + enc_layers

    def ids_for(path_names, leaf):
        def stacked_ids(offset, n, extra_stack=0):
            lids = _stage_of(np.arange(n) + offset, total, n_stages)
            arr = jnp.asarray(lids, jnp.int32)
            shape = (n,) + (1,) * (leaf.ndim - 1)
            if extra_stack:
                # leaf [P, per, ...] double-stacked
                per = leaf.shape[1]
                lids = _stage_of(
                    (np.arange(n)[:, None] * per + np.arange(per)[None, :]) + offset,
                    total, n_stages)
                return jnp.asarray(lids, jnp.int32).reshape(
                    (n, per) + (1,) * (leaf.ndim - 2))
            return arr.reshape(shape)

        top = path_names[0]
        if top in ("embed", "frontend_proj", "projector"):
            return jnp.int32(0)
        if top in ("lm_head", "final_norm", "mtp"):
            return jnp.int32(n_stages - 1)
        if top == "encoder":
            if "blocks" in path_names:
                return stacked_ids(0, enc_layers)
            return jnp.int32(_stage_of(enc_layers - 1, total, n_stages))
        if top == "blocks":
            sub = path_names[1]
            if sub == "dense":
                return stacked_ids(enc_layers, leaf.shape[0])
            if sub == "moe":
                n_dense, n_moe = _moe_split(cfg)
                return stacked_ids(enc_layers + n_dense, leaf.shape[0])
            if sub == "xdec":
                return stacked_ids(enc_layers, cfg.num_layers)
            if sub == "mlstm":
                return stacked_ids(0, leaf.shape[0])
            if sub == "periods":
                n_periods, period = _xlstm_layout(cfg)
                if "slstm" in path_names:
                    lids = _stage_of(np.arange(n_periods) * period + period - 1,
                                     total, n_stages)
                    return jnp.asarray(lids, jnp.int32).reshape(
                        (n_periods,) + (1,) * (leaf.ndim - 1))
                # mlstm: [P, per-1, ...]
                per = period - 1
                lids = _stage_of(np.arange(n_periods)[:, None] * period
                                 + np.arange(per)[None, :], total, n_stages)
                return jnp.asarray(lids, jnp.int32).reshape(
                    (n_periods, per) + (1,) * (leaf.ndim - 2))
            if sub == "mamba_main":
                n_periods, period, tail = _hybrid_layout(cfg)
                return stacked_ids(0, n_periods, extra_stack=1)
            if sub == "mamba_tail":
                n_periods, period, tail = _hybrid_layout(cfg)
                return stacked_ids(n_periods * period, leaf.shape[0])
            if sub == "shared":
                return jnp.int32(n_stages - 1)
        return jnp.int32(n_stages - 1)

    def walk(path, leaf):
        names = [getattr(k, "key", getattr(k, "idx", None)) for k in path]
        names = [n for n in names if isinstance(n, str)]
        return ids_for(names, leaf)

    return jax.tree_util.tree_map_with_path(walk, params)


# ---------------------------------------------------------------------------
# Analytic parameter counts
# ---------------------------------------------------------------------------

def analytic_param_count(cfg: ModelConfig, active_only: bool = False) -> int:
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    H, KV = cfg.num_heads, cfg.num_kv_heads
    V = padded_vocab(cfg)
    n = V * d                                                  # embed
    if not cfg.tie_embeddings:
        n += d * V                                             # head
    norm_p = 2 * d if cfg.norm == "layernorm" else d

    def attn_p():
        if cfg.attn_kind == "mla":
            m = cfg.mla
            qk = m.qk_nope_head_dim + m.qk_rope_head_dim
            a = d * m.q_lora_rank + m.q_lora_rank + m.q_lora_rank * H * qk
            a += d * (m.kv_lora_rank + m.qk_rope_head_dim) + m.kv_lora_rank
            a += m.kv_lora_rank * H * (m.qk_nope_head_dim + m.v_head_dim)
            a += H * m.v_head_dim * d
            return a
        a = d * H * hd + 2 * d * KV * hd + H * hd * d
        if cfg.qkv_bias:
            a += H * hd + 2 * KV * hd
        return a

    fam = cfg.family
    if fam in (FAMILY_DENSE, FAMILY_MOE, FAMILY_VLM):
        n_dense, n_moe = _moe_split(cfg)
        per_dense = attn_p() + mlp_param_count(d, cfg.d_ff, cfg.act) + 2 * norm_p
        n += n_dense * per_dense
        if n_moe:
            moe_p = (moe_mod.moe_active_param_count(cfg) if active_only
                     else moe_mod.moe_param_count(cfg))
            n += n_moe * (attn_p() + moe_p + 2 * norm_p)
        if fam == FAMILY_VLM:
            v = cfg.vlm
            n += v.vision_dim * v.projector_hidden + v.projector_hidden * d
            n += 2 * v.vision_dim
        if cfg.mtp:
            n += attn_p() + mlp_param_count(d, cfg.d_ff, cfg.act) + 3 * norm_p
    elif fam == FAMILY_ENCDEC:
        e = cfg.encdec
        n += e.frontend_dim * d
        per_enc = attn_p() + mlp_param_count(d, cfg.d_ff, cfg.act) + 2 * norm_p
        n += e.encoder_layers * per_enc + norm_p
        per_dec = 2 * attn_p() + mlp_param_count(d, cfg.d_ff, cfg.act) + 3 * norm_p
        n += cfg.num_layers * per_dec
    elif fam == FAMILY_SSM:
        from repro.models.ssm import mlstm_dims
        inner, Hh, dk, dv = mlstm_dims(cfg)
        per_m = (d * 2 * inner + inner * Hh * dk * 2 + inner * Hh * dv
                 + inner * 2 * Hh + 2 * Hh + inner + inner * d + d)
        n_periods, period = _xlstm_layout(cfg)
        dff = -(-4 * d // 3)
        per_s = d * 4 * d + 4 * d + 4 * (d // cfg.num_heads) * d + d + \
            2 * d * dff + dff * d + d
        if n_periods:
            n += n_periods * ((period - 1) * per_m + per_s)
        else:
            n += cfg.num_layers * per_m
    elif fam == FAMILY_HYBRID:
        s = cfg.ssm
        inner, Hh, conv_ch = ssm_mod.mamba2_dims(cfg)
        per_mamba = (d * (2 * inner + 2 * s.state_dim + Hh)
                     + s.conv_dim * conv_ch + conv_ch + 3 * Hh + inner
                     + inner * d + d)
        n += cfg.num_layers * per_mamba
        n += attn_p() + mlp_param_count(d, cfg.hybrid.shared_d_ff, cfg.act) + 2 * norm_p
    n += norm_p                                                # final norm
    return int(n)
