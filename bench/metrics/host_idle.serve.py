"""Share of the traced serving window in which the device is idle while
the serving loop is not waiting for arrivals: the idle time (no op on the
device) that no ``serve.wait`` span covers, over the window. With the
idle under ``serve.wait`` it makes up ``device_idle.serve``.

The share counts every idle stretch, so one long host stall can double
it. Also logged, to judge the loop's host code by: the idle time under
each innermost program span, the share of the idle outside
``serve.wait`` that a named phase span holds, the longest idle stretches
outside ``serve.wait`` (the share without the longest), and the idle per
decode step under ``serve.sync`` and ``serve.decode``."""
import devtrace
import progtrace
from harness import log

BARE = ("serve.iter", "serve.wait", "none")


def read(ctx):
    pt = progtrace.of(ctx)
    if "itl_p95_ms" not in ctx["result"]["metrics"] or pt is None or \
            pt.window is None or not pt.named("serve.iter"):
        return None
    lo, hi = pt.window
    idle = progtrace.idle(ctx["trace"].events, lo, hi)
    waits = progtrace.busy(pt.named("serve.wait"), lo, hi)
    host = devtrace.total(idle) - progtrace.overlap(idle, waits)
    parts = progtrace.cut(idle, progtrace.innermost(pt.spans, lo, hi))
    by = progtrace.totals(parts)
    for name, ns in sorted(by.items(), key=lambda kv: -kv[1]):
        log(f"idle under {name}: {ns / 1e9!r} s, "
            f"{100.0 * ns / (hi - lo)!r}% of the window")
    named = sum(v for k, v in by.items() if k not in BARE)
    steps = ctx["result"]["counters"]["decode_steps"]
    log(f"idle outside serve.wait held by a phase span: "
        f"{100.0 * named / max(host, 1.0)!r}%; serve.decode spans "
        f"{len(pt.named('serve.decode', lo, hi))}, decode steps {steps}")
    longest = sorted((p for p in parts if p[2] != "serve.wait"),
                     key=lambda p: p[0] - p[1])[:5]
    log("longest idle outside serve.wait (s into the window, s, span): " +
        "; ".join(f"{(a - lo) / 1e9:.3f} {(b - a) / 1e9:.4f} {n}"
                  for a, b, n in longest))
    if longest:
        a, b, _ = longest[0]
        log(f"host idle without its longest stretch: "
            f"{100.0 * (host - (b - a)) / (hi - lo)!r}% of the window")
    if steps:
        log(f"idle per decode step: serve.sync "
            f"{by.get('serve.sync', 0.0) / 1e6 / steps!r} ms, serve.decode "
            f"{by.get('serve.decode', 0.0) / 1e6 / steps!r} ms")
    return 100.0 * host / (hi - lo)
