"""The serving step's share of the chip's peaks: the least time of the
window's required work, over the device's busy time in the window.

Each decode step reads the weights once (embedding rows aside) and the
K/V of its live rows (counted from the tokens each request emitted: a
token decoded at context length L reads L positions, rounded up to whole
blocks); it is bound by bandwidth. Each chunked-prefill call reads the
weights once and computes 2 x matmul params x its prompt tokens (the
attention over the prefix is not counted); the chunks are counted
together, bound by whichever peak takes longer. Rows that the kernels read but that emit nothing
(mid-prefill rows, free slots) are not counted, so this is a lower bound
of the work."""
import flops
import harness


def kv_read_bytes(m, counters):
    """K/V bytes the window's decode steps needed for the tokens emitted."""
    rows = [(len(r.prompt), len(r.tokens)) for r in counters["requests"]]
    return flops.emitted_kv_bytes(harness.family(m).kv_bytes_per_token(m),
                                  rows, counters["paging"]["block_size"])


def read(ctx):
    res, m, pk = ctx["result"], ctx["model"], ctx["peaks"]
    c = res.get("counters", {})
    if "paging" not in c or ctx["trace"].busy_s <= 0:
        return None
    fam = harness.family(m)
    w = fam.weight_bytes(m)
    decode = (c["decode_steps"] * w + kv_read_bytes(m, c)) / \
        pk["hbm_bytes_s"]
    pre_tok = c["paging"]["marginal_prefill_tokens"]
    pre_flops = 2.0 * fam.matmul_params(m) * pre_tok
    prefill = max(pre_flops / pk["bf16_flops"],
                  c["prefill_calls"] * w / pk["hbm_bytes_s"])
    return 100.0 * (decode + prefill) / ctx["trace"].busy_s
