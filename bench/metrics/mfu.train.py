"""Model FLOP utilisation of training: the forward and backward FLOPs the
model needs per token (6 x matmul parameters, LM head included, plus
causal attention x 3; no recomputation), times the window's tokens per
second, over chips x the chip's bf16 peak."""
import flops


def read(ctx):
    res = ctx["result"]
    if "train_tok_s" not in res["metrics"]:
        return None
    fpt = flops.train_flops_per_token(ctx["model"], ctx["mix"]["seq"])
    peak = ctx["peaks"]["bf16_flops"] * len(ctx["devices"])
    return 100.0 * fpt * res["metrics"]["train_tok_s"] / peak
