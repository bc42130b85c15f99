"""Share of their roofline that the flash attention kernels (fwd, dq,
dkv) reach in the traced window: for every kernel event, the least time
of one call's matmul FLOPs and bytes (bench/flops.py) at the chip's peaks,
summed, over the summed device time of those events. At batch 4 x 2048
every call is compute-bound.

The kernels carry no names of their own in the trace: each is a
``flash_attention.N`` custom call, told apart by its results: (bf16, f32)
is the forward (output, logsumexp), (bf16, bf16) the dk/dv kernel, and a
single bf16 the dq kernel."""
import flops


def kind_of(ev):
    if not ev.text.startswith("%flash_attention") or \
            ev.kind != "custom-call":
        return None
    rtype = ev.text.split(" = ", 1)[1].split(" custom-call(", 1)[0]
    if not rtype.startswith("("):
        return "dq"
    return "fwd" if "f32[" in rtype else "dkv"


def read(ctx):
    m, mix = ctx["model"], ctx["mix"]
    least = spent = 0.0
    for ev in ctx["trace"].events:
        kind = kind_of(ev)
        if kind is None:
            continue
        f, b = flops.flash_call_cost(kind, mix["batch"], mix["seq"],
                                     m["heads"], m["head_dim"],
                                     m["kv_heads"])
        least += flops.least_time(f, b, ctx["peaks"])[0]
        spent += ev.dur / 1e9
    if spent <= 0:
        return None
    return 100.0 * least / spent
