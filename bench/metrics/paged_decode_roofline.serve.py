"""Share of its roofline that the paged decode kernel reaches: the K/V
bytes of the live blocks it had to read (bench/flops.py, from the tokens
each request emitted) at the chip's HBM peak, over the summed device time
of its events in the window. Rows that emit nothing are not counted, so
this is a lower bound."""
import devtrace
import flops
import harness

MARKS = {"decode": ("paged_decode",)}


def read(ctx):
    c = ctx["result"].get("counters", {})
    if "paging" not in c:
        return None
    evs = devtrace.kernel_events(ctx["trace"].events, MARKS)["decode"]
    spent = sum(e.dur for e in evs) / 1e9
    if spent <= 0:
        return None
    rows = [(len(r.prompt), len(r.tokens)) for r in c["requests"]]
    m = ctx["model"]
    need = flops.emitted_kv_bytes(harness.family(m).kv_bytes_per_token(m),
                                  rows, c["paging"]["block_size"])
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_s"]) / spent
