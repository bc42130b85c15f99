"""Operations and bytes of the kernels, computed from their shapes, and the
least time of work at the chip's peaks.

The counts of a whole model (FLOPs per token, weight and K/V bytes) depend
on its architecture and come from its family (bench/families/, through
``harness.family``); what is here takes shapes or per-token bytes.
"""
from __future__ import annotations


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

def paged_decode_bytes(kv_per_token: int, ctx_lens, block: int) -> float:
    """K and V bytes of the live blocks one paged decode reads over every
    layer, for rows at context lengths ``ctx_lens``; ``kv_per_token`` is
    the family's ``kv_bytes_per_token``."""
    blocks = sum(-(-int(n) // block) for n in ctx_lens)
    return blocks * block * kv_per_token


def emitted_kv_bytes(kv_per_token: int, rows, block: int) -> float:
    """K and V bytes the decode steps needed for the tokens the requests
    emitted. ``rows`` are (prompt length, tokens emitted): the first token
    comes from prefill; token i >= 1 is decoded at context prompt + i."""
    lens = [p + i for p, g in rows for i in range(1, g)]
    return paged_decode_bytes(kv_per_token, lens, block)
