"""Model FLOP utilisation of training: the forward and backward FLOPs the
model needs per token (the family's count; dense: 6 x matmul parameters,
LM head included, plus causal attention x 3; no recomputation), times the
window's tokens per second, over chips x the chip's bf16 peak."""
import harness


def read(ctx):
    res = ctx["result"]
    if "train_tok_s" not in res["metrics"]:
        return None
    m = ctx["model"]
    fpt = harness.family(m).train_flops_per_token(m, ctx["mix"]["seq"])
    peak = ctx["peaks"]["bf16_flops"] * len(ctx["devices"])
    return 100.0 * fpt * res["metrics"]["train_tok_s"] / peak
