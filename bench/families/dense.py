"""The dense decoder family: GQA or MHA attention with rotary positions,
one SwiGLU MLP a layer, LayerNorm or RMSNorm (stablelm-1.6b, chatglm3-6b).

A family module answers for one architecture in the benchmark
(bench/harness.py ``family``); this one gives:

- ``program_config`` and ``check``: the program's config for a
  configuration's ``model`` section, every width compared with the file;
- the plain reference: ``train_steps``, ``change_norms``,
  ``forward_served``, ``served_gaps``;
- the counts the readers divide by: ``train_flops_per_token``,
  ``matmul_params``, ``weight_bytes``, ``kv_bytes_per_token``.

The reference runs in float32 with ``precision=HIGHEST`` matrix products,
layer by layer. It imports nothing of the program. It reads the widths
from a configuration's ``model`` section and the weights from a params
pytree in the program's layout (stacked layers under ``blocks/dense``),
which the benchmark drew itself (bench/weights.py). One layer's weights
are widened to float32 at a time, and attention runs over blocks of query
rows, so a 6B model fits beside its own bf16 weights.

``prec`` selects the arithmetic of every matrix product: ``"f32"`` is the
reference of a float32 configuration and of training; ``"bf16"`` that of
a bfloat16 configuration served: operands rounded to bfloat16, products
summed in float32, and the residual stream rounded to bfloat16 after each
layer, as a bfloat16 decoder keeps it; ``"fp8"`` rounds both operands to
float8 e4m3 (per-tensor scale) first. The last is the control, the
precision below the configuration's bfloat16, which a sound comparison
must refuse.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from harness import Refused

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 256                     # query rows per attention block


# -- the program's config, checked against the file --------------------------

def check(cfg, m: dict) -> None:
    """Refuse a program config whose widths differ from the file's (the
    file is the yardstick; a program config that drifted is refused)."""
    got = {"d_model": cfg.d_model, "heads": cfg.num_heads,
           "kv_heads": cfg.num_kv_heads, "head_dim": cfg.resolved_head_dim,
           "d_ff": cfg.d_ff, "vocab": cfg.vocab_size, "norm": cfg.norm,
           "dtype": cfg.dtype,
           "rope_interleaved": cfg.rope_2d,
           "rotary_dims": int(cfg.resolved_head_dim *
                              cfg.partial_rotary_factor)}
    bad = {k: (v, m[k]) for k, v in got.items() if v != m[k]}
    if bad:
        raise Refused(f"program config {m['program_arch']} differs from "
                      f"the benchmark's (program, file): {bad}")


def program_config(m: dict):
    """The program's ModelConfig for a ``model`` section. Depth and the QKV
    bias are options of the program and are set from the file; every width
    is checked against it."""
    from repro.configs import get_config
    cfg = get_config(m["program_arch"]).with_(num_layers=m["layers"],
                                              qkv_bias=m["qkv_bias"])
    check(cfg, m)
    return cfg


# -- counts: the operations and bytes the model needs -------------------------
# Only the work the model requires counts: recomputation (remat, the flash
# backward's second pass over the scores) does not count toward model
# FLOPs, and the embedding lookup is no matrix product.

def layer_matmul_params(m: dict) -> int:
    d, hd = m["d_model"], m["head_dim"]
    q, kv = m["heads"] * hd, m["kv_heads"] * hd
    attn = d * q + 2 * d * kv + q * d
    mlp = 3 * d * m["d_ff"]                       # SwiGLU: w1, w3, w2
    return attn + mlp


def matmul_params(m: dict) -> int:
    """Matrix-product parameters: every layer plus the LM head."""
    return m["layers"] * layer_matmul_params(m) + m["d_model"] * m["vocab"]


def param_count(m: dict) -> int:
    """All parameters: matrices, embedding, norms and biases."""
    d, hd = m["d_model"], m["head_dim"]
    norm = 2 * d if m["norm"] == "layernorm" else d
    bias = (m["heads"] + 2 * m["kv_heads"]) * hd if m["qkv_bias"] else 0
    per_layer = layer_matmul_params(m) + 2 * norm + bias
    return (m["layers"] * per_layer + norm + 2 * m["vocab"] * d)


def attn_flops_fwd(m: dict, q_len: int, kv_len: int, causal: bool) -> float:
    """QK^T and PV of one sequence over every layer and head. Causal
    attention of a block of queries at the end of ``kv_len`` keys counts
    only the keys each query sees."""
    hd, h = m["head_dim"], m["heads"]
    if causal:
        seen = q_len * kv_len - q_len * (q_len - 1) / 2
    else:
        seen = q_len * kv_len
    return 4.0 * seen * hd * h * m["layers"]


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward and backward (x3) of the matmuls and of causal attention."""
    return 6.0 * matmul_params(m) + 3.0 * attn_flops_fwd(
        m, seq, seq, True) / seq


def kv_bytes_per_token(m: dict, itemsize: int = 2) -> int:
    """K and V of one token over every layer."""
    return 2 * m["layers"] * m["kv_heads"] * m["head_dim"] * itemsize


def weight_bytes(m: dict, itemsize: int = 2) -> int:
    """Bytes of the weights a forward step reads (embedding rows aside)."""
    return (param_count(m) - m["vocab"] * m["d_model"]) * itemsize


def _quant8(x):
    """Round float32 ``x`` to float8 e4m3 under a per-tensor scale (its
    largest value maps to 448, the format's largest)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


@jax.custom_vjp
def _fp8(x):
    return _quant8(x)


def _fp8_fwd(x):
    return _quant8(x), None


def _fp8_bwd(_, g):
    # the gradient flowing back is rounded the same way, under its own
    # scale, as a float8 training recipe does
    return (_quant8(g),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


def _mm(a, b, prec, eq=None):
    if prec == "bf16":
        a, b = a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
        if eq is None:
            return jnp.matmul(a, b, preferred_element_type=jnp.float32)
        return jnp.einsum(eq, a, b, preferred_element_type=jnp.float32)
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if prec == "fp8":
        a, b = _fp8(a), _fp8(b)
    if eq is None:
        return jnp.matmul(a, b, precision=HI)
    return jnp.einsum(eq, a, b, precision=HI)


def _norm(m, p, x):
    x = x.astype(jnp.float32)
    if m["norm"] == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        y = (x - mu) / jnp.sqrt(var + m["norm_eps"])
        return y * p["scale"].astype(jnp.float32) + \
            p["bias"].astype(jnp.float32)
    ms = (x * x).mean(-1, keepdims=True)
    return x / jnp.sqrt(ms + m["norm_eps"]) * p["scale"].astype(jnp.float32)


def _rope(m, x, pos):
    """x [N, T, heads, hd]; rotates the first ``rotary_dims`` channels,
    pairing (i, i + r/2) or, interleaved, (2i, 2i + 1)."""
    r = m["rotary_dims"]
    inv = 1.0 / (m["rope_theta"] ** (np.arange(0, r, 2) / r))
    ang = pos[..., None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    xr, xp = x[..., :r], x[..., r:]
    if m["rope_interleaved"]:
        a, b = xr[..., 0::2], xr[..., 1::2]
        out = jnp.stack([a * cos - b * sin, b * cos + a * sin], -1)
        out = out.reshape(xr.shape)
    else:
        a, b = xr[..., :r // 2], xr[..., r // 2:]
        out = jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
    return jnp.concatenate([out, xp], -1)


def _attention(m, q, k, v, prec):
    """Causal softmax attention over blocks of query rows; q [N,T,H,hd],
    k/v [N,T,KV,hd]. Each block is recomputed in the backward pass."""
    N, T, H, hd = q.shape
    G = H // m["kv_heads"]
    k = jnp.repeat(k, G, axis=2)
    v = jnp.repeat(v, G, axis=2)
    qb = min(Q_BLOCK, T)
    nb = T // qb
    qs = jnp.moveaxis(q.reshape(N, nb, qb, H, hd), 1, 0)

    @jax.checkpoint
    def block(args):
        i, qblk = args
        s = _mm(qblk, k, prec, "nqhd,nkhd->nhqk") / math.sqrt(hd)
        qpos = i * qb + jnp.arange(qb)
        mask = jnp.arange(T)[None, :] <= qpos[:, None]
        s = jnp.where(mask[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _mm(p, v, prec, "nhqk,nkhd->nqhd")

    out = jax.lax.map(block, (jnp.arange(nb), qs))
    return jnp.moveaxis(out, 0, 1).reshape(N, T, H * hd)


def layer(m, lp, x, prec="f32"):
    """One decoder layer on x [N, T, d] (float32), positions 0..T-1."""
    N, T, _ = x.shape
    H, KV, hd = m["heads"], m["kv_heads"], m["head_dim"]
    a = lp["attn"]
    h = _norm(m, lp["ln1"], x)
    q, k, v = (_mm(h, a["wq"], prec), _mm(h, a["wk"], prec),
               _mm(h, a["wv"], prec))
    if m["qkv_bias"]:
        q = q + a["bq"].astype(jnp.float32)
        k = k + a["bk"].astype(jnp.float32)
        v = v + a["bv"].astype(jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(T), (N, T))
    q = _rope(m, q.reshape(N, T, H, hd), pos)
    k = _rope(m, k.reshape(N, T, KV, hd), pos)
    ctx = _attention(m, q, k, v.reshape(N, T, KV, hd), prec)
    x = x + _mm(ctx, a["wo"], prec)
    f = lp["ffn"]
    h = _norm(m, lp["ln2"], x)
    g = jax.nn.silu(_mm(h, f["w1"], prec)) * _mm(h, f["w3"], prec)
    x = x + _mm(g, f["w2"], prec)
    if prec == "bf16":
        x = x.astype(jnp.bfloat16).astype(jnp.float32)
    return x


def logits(m, hp, x, prec="f32"):
    return _mm(_norm(m, hp["final_norm"], x), hp["lm_head"], prec)


def layer_params(params, i):
    """Layer ``i`` of the program's stacked layout."""
    return jax.tree.map(lambda a: a[i], params["blocks"]["dense"])


def head_params(params):
    return {"final_norm": params["final_norm"], "lm_head": params["lm_head"]}


# -- serving: teacher-forced logits at the served positions ----------------

@functools.partial(jax.jit, static_argnums=(0, 3))
def _layer_jit(mkey, lp, x, prec):
    return layer(dict(mkey), lp, x, prec)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _served_logits(mkey, hp, x, pos, prec):
    h = jnp.take_along_axis(x, pos[:, :, None], axis=1)   # [N, G, d]
    return logits(dict(mkey), hp, h, prec)


def _key(m):
    return tuple(sorted(m.items()))


def forward_served(m, params, tokens, pos, prec="f32"):
    """Logits [N, G, V] at positions ``pos`` [N, G] of ``tokens`` [N, T]
    (left-aligned, causal, so what follows a row's end is never read).
    One row at a time, so a 6B model's activations fit beside it."""
    mk = _key(m)
    out = []
    for row in range(len(tokens)):
        x = params["embed"][jnp.asarray(tokens[row:row + 1])].astype(
            jnp.float32)
        for i in range(m["layers"]):
            x = _layer_jit(mk, layer_params(params, i), x, prec)
        out.append(_served_logits(mk, head_params(params), x,
                                  jnp.asarray(pos[row:row + 1]), prec))
    return jnp.concatenate(out)


def served_gaps(m, params, tokens, pos, served, prec="f32", picked=None):
    """Per position, how far the logit of the served token lies below the
    reference's best: ref_max - ref[served]. With ``picked`` (logits of the
    same positions from another computation) the served token is instead
    that computation's argmax: the control's reading."""
    ref = forward_served(m, params, tokens, pos, prec)
    tok = jnp.asarray(served) if picked is None else jnp.argmax(picked, -1)
    best = ref.max(-1)
    mine = jnp.take_along_axis(ref, tok[..., None], axis=-1)[..., 0]
    return np.asarray(best - mine)


# -- training: loss, gradient and the optimizer, layer by layer ------------

def _xent(m, hp, x, targets, prec):
    lg = logits(m, hp, x, prec)
    lse = jax.nn.logsumexp(lg, -1)
    ll = jnp.take_along_axis(lg, targets[..., None], -1)[..., 0]
    return (lse - ll).sum()


@functools.partial(jax.jit, static_argnums=(0, 4, 5))
def _head_loss_grad(mkey, hp, x, targets, n_tok, prec):
    """Mean cross-entropy, its gradient in the head params and in x. Rows
    of x are taken in chunks so that [tokens, vocab] logits never exist
    whole."""
    m = dict(mkey)
    N, T, d = x.shape
    rows = x.reshape(N * T, d)
    tg = targets.reshape(N * T)
    c = math.gcd(1024, N * T)
    xs, ts = rows.reshape(-1, c, d), tg.reshape(-1, c)

    def f(hp, xs):
        def body(acc, xt):
            xc, tc = xt
            return acc + jax.checkpoint(
                lambda h, a, b: _xent(m, h, a, b, prec))(hp, xc, tc), None
        s, _ = jax.lax.scan(body, jnp.float32(0.0), (xs, ts))
        return s / n_tok

    loss, (ghp, gx) = jax.value_and_grad(f, argnums=(0, 1))(hp, xs)
    return loss, ghp, gx.reshape(N, T, d)


def _bwd_update(m, at, lp, mom, x, dy, hyper, prec):
    """Gradient of one layer at ``at``, then SGD with momentum and
    decoupled weight decay (the configured optimizer) applied to ``lp`` at
    once. Returns the new layer params and momentum, dx, and the squared
    norm of each leaf's gradient."""
    lr, mu, wd = hyper
    _, vjp = jax.vjp(lambda p, xx: layer(m, p, xx, prec), at, x)
    g, dx = vjp(dy)
    new_p, new_m = _sgd(lp, mom, g, lr, mu, wd)
    sq = jax.tree.map(lambda a: jnp.sum(a.astype(jnp.float32) ** 2), g)
    return new_p, new_m, dx, sq


@functools.partial(jax.jit, static_argnums=(0, 6), donate_argnums=(1, 2))
def _layer_bwd_update(mkey, lp, mom, x, dy, hyper, prec):
    """The step at theta_t, params kept in their own dtype."""
    return _bwd_update(dict(mkey), lp, lp, mom, x, dy, hyper, prec)


@functools.partial(jax.jit, static_argnums=(0, 7, 8), donate_argnums=(2, 3))
def _layer_bwd_update_at(mkey, at, lp, mom, x, dy, hyper, prec, dtypes):
    """The step with the gradient taken at ``at``; also ``lp`` as the
    forward sees it (cast to ``dtypes``), the next step's ``at`` under a
    stale rule."""
    snap = _cast_leaves(lp, dtypes)
    return _bwd_update(dict(mkey), at, lp, mom, x, dy, hyper, prec) + (snap,)


def _sgd(p, mom, g, lr, mu, wd):
    def one(p, m, g):
        gf = g.astype(jnp.float32) + wd * p.astype(jnp.float32)
        m = mu * m + gf
        return (p.astype(jnp.float32) - lr * m).astype(p.dtype), m
    flat = jax.tree.map(one, p, mom, g)
    pick = lambda i: jax.tree.map(lambda t: t[i], flat,
                                  is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1)


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _head_update(hp, mom, g, hyper):
    lr, mu, wd = hyper
    new_p, new_m = _sgd(hp, mom, g, lr, mu, wd)
    sq = jax.tree.map(lambda a: jnp.sum(a.astype(jnp.float32) ** 2), g)
    return new_p, new_m, sq


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _embed_update(emb, mom, tokens, dx0, hyper):
    g = jnp.zeros(emb.shape, jnp.float32).at[tokens.reshape(-1)].add(
        dx0.reshape(-1, dx0.shape[-1]))
    lr, mu, wd = hyper
    new_p, new_m = _sgd(emb, mom, g, lr, mu, wd)
    return new_p, new_m, jnp.sum(g ** 2)


@functools.partial(jax.jit, static_argnums=(0,))
def _embed_rows(mkey, emb, tokens):
    return emb[tokens].astype(jnp.float32)


def _cast_leaves(tree, dtypes):
    leaves, tdef = jax.tree.flatten(tree)
    return jax.tree.unflatten(tdef, [jnp.copy(a.astype(d))
                                     for a, d in zip(leaves, dtypes)])


_cast = jax.jit(_cast_leaves, static_argnums=(1,))


def _dtypes(tree):
    return tuple(a.dtype.name for a in jax.tree.leaves(tree))


def _device(tree):
    """The one device that holds ``tree``'s first leaf."""
    (dev,) = jax.tree.leaves(tree)[0].devices()
    return dev


def _to(x, like):
    """``x`` on the device of ``like``."""
    return jax.device_put(x, _device(like))


def unstack(params, devices=None):
    """The reference's own state: a list of per-layer dicts, so each layer
    can be updated in place of its buffers. With several ``devices`` the
    embedding and the head live on the first and the layers are spread in
    order over the others, so that a state larger than one chip (float32
    masters over four chips) fits; the layers still run one after another.
    """
    n = jax.tree.leaves(params["blocks"]["dense"])[0].shape[0]
    devices = devices or [_device(params["embed"])]
    rest = devices[1:] or devices
    layers = [jax.device_put(layer_params(params, i),
                             rest[i * len(rest) // n]) for i in range(n)]
    first = lambda t: jax.device_put(t, devices[0])
    return {"embed": first(jnp.copy(params["embed"])),
            "head": first(jax.tree.map(jnp.copy, head_params(params))),
            "layers": layers}


def fresh_threshold(rule: str, i: int, n: int) -> int:
    """Micro-batch ``i`` of ``n`` differentiates stages j >= the threshold
    at theta_t and those below at theta_{t-1} (paper Sec. 3.2; the program
    states the same in core/schedule.py)."""
    if rule == "dp":
        return 0
    if rule == "cdp_v1":
        return n
    if rule == "cdp_v2":
        return n - 1 - i
    raise Refused(f"the reference knows no update rule {rule!r}")


def u_matrix(rule: str, n: int) -> np.ndarray:
    """[n, n]: u[i, j] = 1 where micro-batch i differentiates stage j at
    theta_t."""
    return np.array([[j >= fresh_threshold(rule, i, n) for j in range(n)]
                     for i in range(n)])


def train_steps(m, params, batches, hyper, rule, n, prec="f32", rows=None,
                master=None, devices=None):
    """Run len(batches) optimizer steps of the plain model from
    ``params``. Returns (losses, first-step gradient norm per leaf, the
    final state in the reference's per-layer form, for ``change_norms``).

    The update rule (paper Sec. 3.2, with u from ``u_matrix``): the batch is
    ``n`` micro-batches of equal rows (the program's data ranks); step t
    differentiates stage j of micro-batch i at theta^{ij} = theta_t where
    u[i, j] = 1 and at theta_{t-1} where u[i, j] = 0, with theta_{-1} =
    theta_0, and applies the mean gradient to theta_t:

        g_t          = (1/n) sum_i grad_theta L_i(theta^{i.})
        mom_{t+1}    = mu mom_t + g_t + wd theta_t
        theta_{t+1}  = theta_t - lr mom_{t+1}

    ``dp`` (u all 1), ``cdp_v1`` (u all 0: every stage one step stale, as
    ``zero_cdp`` runs it) and ``cdp_v2`` over one micro-batch (u = [[1]])
    give every micro-batch one version at every stage, so g_t is the
    gradient of the whole batch's mean loss at that version, taken in one
    pass. A rule that mixes the versions within a step (``cdp_v2`` over
    n > 1) is refused: no cell runs it.

    ``master`` keeps theta in that dtype (float32 for a stage-sharded plan,
    whose owner ranks hold float32 masters); the forward then sees theta
    cast to the layout's dtype, as the program's does. Without it theta
    stays in the layout's dtype. ``devices`` spreads the state over
    chips (``unstack``). ``rows`` keeps only those batch rows: the fault of
    half the batch left out, read as a broken program would show it."""
    u = u_matrix(rule, n)
    if (u != u[0, 0]).any():
        raise Refused(f"the reference takes one version a step; rule "
                      f"{rule} over {n} micro-batches mixes theta_t and "
                      f"theta_t-1")
    stale = not u[0, 0]
    mk = _key(m)
    hyper_a = tuple(jnp.float32(v) for v in hyper)
    st = unstack(params, devices)
    del params
    dt = {"embed": _dtypes(st["embed"]), "head": _dtypes(st["head"]),
          "layer": _dtypes(st["layers"][0])}
    at = None                       # the point a step differentiates at
    if master is not None:
        at = st
        st = jax.tree.map(lambda a: jnp.array(a, master, copy=True), st)
    elif stale:
        at = jax.tree.map(jnp.copy, st)
    zeros = lambda t: jax.tree.map(
        lambda a: jnp.zeros(a.shape, jnp.float32, device=_device(a)), t)
    mom = {"embed": zeros(st["embed"]), "head": zeros(st["head"]),
           "layers": [zeros(l) for l in st["layers"]]}
    losses, gsq = [], None
    for step, b in enumerate(batches):
        tok, tgt = jnp.asarray(b["tokens"]), jnp.asarray(b["targets"])
        if rows is not None:
            tok, tgt = tok[rows], tgt[rows]
        tok, tgt = _to(tok, st["embed"]), _to(tgt, st["head"])
        if at is None:
            pt = st
        elif stale or step == 0:
            pt = at
        else:
            pt = {"embed": _cast(st["embed"], dt["embed"]),
                  "head": _cast(st["head"], dt["head"]),
                  "layers": [_cast(l, dt["layer"]) for l in st["layers"]]}
        xs = [_embed_rows(mk, pt["embed"], tok)]
        for lp in pt["layers"]:
            xs[-1] = _to(xs[-1], lp)
            xs.append(_layer_jit(mk, lp, xs[-1], prec))
        loss, ghp, dx = _head_loss_grad(mk, pt["head"],
                                        _to(xs.pop(), pt["head"]),
                                        tgt, tok.size, prec)
        losses.append(float(loss))
        nxt = {"layers": [None] * len(st["layers"])}
        if stale:
            nxt["head"] = _cast(st["head"], dt["head"])
        sq = {}
        st["head"], mom["head"], hsq = _head_update(st["head"], mom["head"],
                                                    ghp, hyper_a)
        for k, v in jax.tree_util.tree_flatten_with_path(hsq)[0]:
            sq["/".join(str(getattr(q, "key", q)) for q in k)] = float(v)
        lsq = None
        for i in reversed(range(len(st["layers"]))):
            dx = _to(dx, st["layers"][i])
            if at is None:
                st["layers"][i], mom["layers"][i], dx, s = _layer_bwd_update(
                    mk, st["layers"][i], mom["layers"][i], xs.pop(), dx,
                    hyper_a, prec)
            else:
                (st["layers"][i], mom["layers"][i], dx, s,
                 nxt["layers"][i]) = _layer_bwd_update_at(
                    mk, pt["layers"][i], st["layers"][i], mom["layers"][i],
                    xs.pop(), dx, hyper_a, prec, dt["layer"])
            s = jax.tree.map(float, s)
            lsq = s if lsq is None else jax.tree.map(lambda a, b: a + b,
                                                     lsq, s)
        for k, v in jax.tree_util.tree_flatten_with_path(lsq)[0]:
            sq["blocks/dense/" + "/".join(str(getattr(q, "key", q))
                                          for q in k)] = v
        if stale:
            nxt["embed"] = _cast(st["embed"], dt["embed"])
        st["embed"], mom["embed"], esq = _embed_update(
            st["embed"], mom["embed"], tok, _to(dx, st["embed"]), hyper_a)
        sq["embed"] = float(esq)
        if stale:
            at = nxt
        if gsq is None:
            gsq = sq
        del xs, pt
    gnorm = {k: math.sqrt(v) for k, v in (gsq or {}).items()}
    return losses, gnorm, st


@jax.jit
def _diff_sq(a, b):
    return jax.tree.map(lambda x, y: jnp.sum(
        (x.astype(jnp.float32) - y.astype(jnp.float32)) ** 2), a, b)


def _named(tree, prefix=""):
    return {prefix + "/".join(str(getattr(q, "key", q)) for q in k): float(v)
            for k, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


def change_norms(p0, st):
    """Per leaf of the program's layout, the norm of (state - p0), with
    ``st`` in the reference's own per-layer form (``unstack``)."""
    sq = _named(_diff_sq({"embed": st["embed"], **st["head"]},
                         _to({"embed": p0["embed"], **head_params(p0)},
                             st["embed"])))
    acc = None
    for i, lp in enumerate(st["layers"]):
        d = jax.tree.map(float, _diff_sq(lp, _to(layer_params(p0, i), lp)))
        acc = d if acc is None else jax.tree.map(lambda a, b: a + b, acc, d)
    sq.update(_named(acc, "blocks/dense/"))
    return {k: math.sqrt(v) for k, v in sq.items()}
