"""Share of the traced training window in which chip 0 runs a collective
op (``collective-permute*``, ``all-reduce*``, ``all-gather*``,
``reduce-scatter*``, ``all-to-all*``, by HLO op name) and no other op:
the collective time the step leaves exposed, over the window.

It is read from chip 0's ``XLA Ops`` line as the union of the collective
intervals less the union of the other ops' (a loop's event spans its body
and is left out), so it holds also where ops overlap on that line. Each
collective kind's seconds on chip 0 are logged beside it. A step with no
collective (one chip) gives nothing to read."""
import devtrace
from harness import log


def read(ctx):
    t = ctx["trace"]
    if "train_tok_s" not in ctx["result"]["metrics"] or not t.planes:
        return None
    plane = devtrace.first_chip(t.planes)
    lo, hi = t.span
    bare, by = devtrace.exposed_collectives(t.planes[plane], lo, hi)
    if not by:
        return None
    log(f"collectives on {plane}: " + ", ".join(
        f"{k} {v / 1e9!r} s" for k, v in sorted(by.items())) +
        f"; exposed {bare / 1e9!r} s of {(hi - lo) / 1e9!r} s; under "
        f"other ops {(sum(by.values()) - bare) / 1e9!r} s")
    return 100.0 * bare / (hi - lo)
