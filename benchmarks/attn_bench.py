"""Attention micro-benchmark: fwd and fwd+bwd wall-clock + achieved FLOPs
for both attention backends ("jnp" blockwise reference and the Pallas
kernel suite behind ``train_attn="pallas"``), plus the block-sparse
pruning ledger.

Configs cover the two causal training shapes AND a sliding-window sweep
(window in {256, 1024, 4096} at S=8k) where grid pruning matters most.
Every Pallas record carries:

  * ``blocks_visited`` / ``blocks_total`` — tiles the pruned grid walks vs
    the dense (nq x nk) rectangle (from ``flash_grid_plan``), the auditable
    pruning win (causal ~ half, window ~ (window + bq)/S);
  * ``dq_us`` / ``dkv_us`` — the backward split, timed per kernel.

Writes a JSON artifact to ``benchmarks/artifacts/attn_bench.json`` (one
record per backend x config x pass) so the perf trajectory accumulates
attention datapoints across PRs, and yields the same rows in the
``name,us_per_call,derived`` CSV convention of ``benchmarks/run.py``.

Off-TPU the Pallas rows run in interpreter mode (``interpret=True``) —
correct but slow; they are tagged ``"interpret": true`` in the artifact so
trajectory tooling never mistakes them for kernel timings. Interpreter
wall-clock still scales with blocks_visited (each visited tile is one grid
step), so the pruning ratio shows up even in CPU-measured numbers.
"""
from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp

from benchmarks._util import ARTIFACTS, time_us

# B, S, H, KV, dh, window — causal self-attention training shapes
CONFIGS = [
    (2, 512, 8, 2, 64, 0),
    (1, 1024, 8, 4, 64, 0),
    # sliding-window sweep at long context: pruning visits ~(window/bk)+2
    # kv blocks per q block instead of the whole lower triangle
    (1, 8192, 1, 1, 64, 256),
    (1, 8192, 1, 1, 64, 1024),
    (1, 8192, 1, 1, 64, 4096),
]
BQ = BK = 128


def _unmasked_frac(S, window):
    """EXACT unmasked fraction of the causal (+ sliding-window) [S, S]
    score matrix: row i attends min(i+1, window) keys. Element-exact — not
    the coarser block-granular visited/total ratio, which counts boundary
    tiles as fully unmasked."""
    w = min(window, S) if window else S
    unmasked = w * (w + 1) // 2 + max(S - w, 0) * w
    return unmasked / (S * S)


def _attn_flops(B, S, H, dh, frac, *, bwd=False):
    """Matmul FLOPs of attention: QK^T and PV are 2*S*S*dh MACs per head;
    ``frac`` is the exact unmasked fraction of the score matrix; the flash
    backward re-does QK^T plus the four gradient matmuls -> 2.5x the
    forward."""
    f = 2 * 2 * B * H * S * S * dh * frac
    return int(f * 2.5) if bwd else int(f)


def _fold(x):
    B, S, H, d = x.shape
    return jnp.moveaxis(x, 2, 1).reshape(B * H, S, d)


def run():
    from repro.kernels import ops
    from repro.kernels.flash_attention import (flash_attention_bwd_dkv,
                                               flash_attention_bwd_dq,
                                               flash_attention_kernel,
                                               flash_grid_plan)
    from repro.models.attention import blockwise_attention

    interpret = ops.default_interpret()
    records = []
    rows = []
    for B, S, H, KV, dh, window in CONFIGS:
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        q = jax.random.normal(ks[0], (B, S, H, dh), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, KV, dh), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, KV, dh), jnp.float32)
        do = jax.random.normal(ks[3], (B, S, H, dh), jnp.float32)
        tag = f"b{B}s{S}h{H}kv{KV}d{dh}" + (f"w{window}" if window else "")

        bq, bk = min(BQ, S), min(BK, S)
        plan = flash_grid_plan(S, S, bq, bk, True, window, 0, S)
        frac = _unmasked_frac(S, window)

        backends = {
            "jnp": jax.jit(lambda q, k, v, w=window: blockwise_attention(
                q, k, v, causal=True, window=w, backend="jnp")),
            "pallas": jax.jit(lambda q, k, v, w=window: ops.flash_attention(
                q, k, v, causal=True, window=w, bq=bq, bk=bk,
                interpret=interpret)),
        }
        for name, fwd in backends.items():
            fwd_us = time_us(fwd, q, k, v)
            grad = jax.jit(jax.grad(
                lambda q, k, v: jnp.sum(fwd(q, k, v) * do),
                argnums=(0, 1, 2)))
            fwdbwd_us = time_us(grad, q, k, v)
            rec = {
                "backend": name, "shape": tag,
                "B": B, "S": S, "H": H, "KV": KV, "dh": dh,
                "causal": True, "window": window, "bq": bq, "bk": bk,
                "interpret": bool(name == "pallas" and interpret),
                "fwd_us": round(fwd_us, 1),
                "fwdbwd_us": round(fwdbwd_us, 1),
                "fwd_achieved_gflops": round(
                    _attn_flops(B, S, H, dh, frac) / fwd_us * 1e-3, 2),
                "fwdbwd_achieved_gflops": round(
                    _attn_flops(B, S, H, dh, frac, bwd=True)
                    / fwdbwd_us * 1e-3, 2),
            }
            if name == "pallas":
                # pruning ledger + per-kernel backward split
                rec["blocks_visited"] = plan["visited"]
                rec["blocks_visited_dkv"] = plan["visited_dkv"]
                rec["blocks_total"] = plan["total"]
                group = H // KV
                qh, kh, vh, doh = _fold(q), _fold(k), _fold(v), _fold(do)
                kw = dict(causal=True, window=window, bq=bq, bk=bk,
                          group=group, sk_valid=S, interpret=interpret)
                fwd_k = jax.jit(lambda qh, kh, vh: flash_attention_kernel(
                    qh, kh, vh, **kw))
                out, lse = fwd_k(qh, kh, vh)
                delta = jnp.sum(doh * out, axis=-1)[:, None, :]
                dq_us = time_us(jax.jit(
                    lambda *a: flash_attention_bwd_dq(*a, **kw)),
                    qh, kh, vh, doh, lse, delta)
                dkv_us = time_us(jax.jit(
                    lambda *a: flash_attention_bwd_dkv(*a, **kw)),
                    qh, kh, vh, doh, lse, delta)
                rec["dq_us"] = round(dq_us, 1)
                rec["dkv_us"] = round(dkv_us, 1)
                rows.append((f"attn.pallas.{tag}.bwd_dq", rec["dq_us"],
                             f"{plan['visited']}/{plan['total']}blocks"))
                rows.append((f"attn.pallas.{tag}.bwd_dkv", rec["dkv_us"],
                             f"{plan['visited_dkv']}/{plan['total']}blocks"))
            records.append(rec)
            rows.append((f"attn.{name}.{tag}.fwd", rec["fwd_us"],
                         f"{rec['fwd_achieved_gflops']}GFLOP/s"))
            rows.append((f"attn.{name}.{tag}.fwdbwd", rec["fwdbwd_us"],
                         f"{rec['fwdbwd_achieved_gflops']}GFLOP/s"))

    os.makedirs(ARTIFACTS, exist_ok=True)
    path = os.path.join(ARTIFACTS, "attn_bench.json")
    with open(path, "w") as f:
        json.dump(records, f, indent=1)
    rows.append(("attn.artifact", 0.0, path))
    return rows


if __name__ == "__main__":
    for r in run():
        print(",".join(str(x) for x in r))
