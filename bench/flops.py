"""Operations and bytes that the algorithm needs, computed from shapes.

``m`` is a configuration's ``model`` section (bench/configs/*.json). Only
the work the model requires counts: recomputation (remat, the flash
backward's second pass over the scores) does not count toward model
FLOPs, and the embedding lookup is no matrix product.
"""
from __future__ import annotations


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


# -- flash attention kernels (one call = one layer of one batch) -----------
# Matrix products of each kernel over the causal half of the S x S scores,
# per head: fwd QK^T + PV; dq recomputes QK^T, then dP = dO V^T, dQ = dS K;
# dkv recomputes QK^T, then dP, dV = P^T dO, dK = dS^T Q.
FLASH_MATMULS = {"fwd": 2, "dq": 3, "dkv": 4}
# bf16 tensors each kernel reads or writes, as (query-shaped, kv-shaped):
# fwd q o | k v; dq q do dq | k v; dkv q do | k v dk dv.
FLASH_TENSORS = {"fwd": (2, 2), "dq": (3, 2), "dkv": (2, 4)}


def flash_call_cost(kind: str, batch: int, seq: int, heads: int,
                    head_dim: int, kv_heads: int = None) -> tuple:
    """(flops, bytes) of one causal flash kernel call of ``kind``."""
    kv_heads = kv_heads or heads
    pairs = seq * (seq + 1) / 2
    flops = 2.0 * FLASH_MATMULS[kind] * pairs * head_dim * heads * batch
    nq, nkv = FLASH_TENSORS[kind]
    nbytes = (nq * batch * seq * heads * head_dim * 2 +
              nkv * batch * seq * kv_heads * head_dim * 2)
    # f32 row statistics per query row and head: lse (fwd writes it);
    # lse and delta (the backward kernels read both)
    nbytes += batch * heads * seq * 4 * (1 if kind == "fwd" else 2)
    return flops, nbytes


def least_time(flops: float, nbytes: float, peaks: dict) -> tuple:
    """(seconds, bound) of work at the chip's peaks."""
    tc = flops / peaks["bf16_flops"]
    tb = nbytes / peaks["hbm_bytes_s"]
    return (tc, "compute") if tc >= tb else (tb, "memory")


# -- serving ------------------------------------------------------------------

def kv_bytes_per_token(m: dict, itemsize: int = 2) -> int:
    """K and V of one token over every layer."""
    return 2 * m["layers"] * m["kv_heads"] * m["head_dim"] * itemsize


def weight_bytes(m: dict, itemsize: int = 2) -> int:
    """Bytes of the weights a forward step reads (embedding rows aside)."""
    return (param_count(m) - m["vocab"] * m["d_model"]) * itemsize


def paged_decode_bytes(m: dict, ctx_lens, block: int, itemsize: int = 2
                       ) -> float:
    """K and V bytes of the live blocks one paged decode reads over every
    layer, for rows at context lengths ``ctx_lens``."""
    blocks = sum(-(-int(n) // block) for n in ctx_lens)
    return blocks * block * kv_bytes_per_token(m, itemsize)


def emitted_kv_bytes(m: dict, rows, block: int, itemsize: int = 2
                     ) -> float:
    """K and V bytes the decode steps needed for the tokens the requests
    emitted. ``rows`` are (prompt length, tokens emitted): the first token
    comes from prefill; token i >= 1 is decoded at context prompt + i."""
    lens = [p + i for p, g in rows for i in range(1, g)]
    return paged_decode_bytes(m, lens, block, itemsize)
