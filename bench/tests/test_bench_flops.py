"""The dense family's counts (bench/families/dense.py) and the kernel
counts of bench/flops.py against counts made by hand for both
configurations."""
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import flops  # noqa: E402
import harness  # noqa: E402

dense = harness.family({"family": "dense"})


def model(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)["model"]


def test_stablelm_counts():
    m = model("stablelm-1.6b")
    # per layer: q k v o 4 x 2048^2, SwiGLU 3 x 2048 x 5632
    layer = 4 * 2048 ** 2 + 3 * 2048 * 5632
    assert dense.layer_matmul_params(m) == layer == 51_380_224
    assert dense.matmul_params(m) == 16 * layer + 2048 * 100352
    # 16 layers: 6 x 1.0276e9 per token + causal attention x 3
    attn = 3 * 4 * (2048 * 2049 / 2) * 64 * 32 * 16 / 2048
    assert dense.train_flops_per_token(m, 2048) == pytest.approx(
        6 * 1_027_604_480 + attn)
    # the whole published depth with its QKV bias (3 x 2048 a layer): the
    # repo's own count without the bias, 1,644,367,872, plus 24 x 6,144
    assert dense.param_count(dict(m, layers=24)) == \
        1_644_367_872 + 24 * 3 * 2048 == 1_644_515_328


def test_chatglm_counts():
    m = model("chatglm3-6b")
    assert dense.param_count(m) == 6_243_584_000
    # K and V, 28 layers x 2 groups x 128 x bf16
    kv = dense.kv_bytes_per_token(m)
    assert kv == 2 * 28 * 2 * 128 * 2 == 28_672
    # weights a step reads: all but the embedding rows
    assert dense.weight_bytes(m) == 2 * (6_243_584_000 - 65024 * 4096)
    # three rows at 17, 32 and 33 tokens in 16-token blocks read 2+2+3
    assert flops.paged_decode_bytes(kv, [17, 32, 33], 16) == \
        7 * 16 * 28_672
    # a 16-token prompt that emitted 3 tokens decoded two of them, at
    # contexts 17 and 18 (2 blocks each); a row that emitted 1 decoded none
    assert flops.emitted_kv_bytes(kv, [(16, 3), (40, 1)], 16) == \
        4 * 16 * 28_672


def test_program_param_counts_agree():
    src = os.path.join(os.path.dirname(BENCH), "src")
    sys.path.insert(0, src)
    for name in ("stablelm-1.6b", "chatglm3-6b"):
        m = model(name)
        assert dense.param_count(m) == \
            harness.program_config(m).param_count()


def test_flash_call_cost_by_hand():
    # one layer of batch 4 x 2048, 32 heads of 64: causal pairs 2048*2049/2
    pairs = 2048 * 2049 / 2
    f, b = flops.flash_call_cost("fwd", 4, 2048, 32, 64)
    assert f == 2 * 2 * pairs * 64 * 32 * 4
    # q o k v in bf16 plus the f32 lse row
    assert b == 4 * 4 * 2048 * 32 * 64 * 2 + 4 * 32 * 2048 * 4
    fq, _ = flops.flash_call_cost("dq", 4, 2048, 32, 64)
    fk, bk = flops.flash_call_cost("dkv", 4, 2048, 32, 64)
    assert (fq / f, fk / f) == (1.5, 2.0)
    assert bk == 6 * 4 * 2048 * 32 * 64 * 2 + 2 * 4 * 32 * 2048 * 4
    peaks = {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}
    t, bound = flops.least_time(f, b, peaks)
    assert bound == "compute" and t == pytest.approx(f / 197e12)
