"""Share of the device's busy time in the traced serving window that the
admission programs take: the device time of the ops of ``admit_fresh``
and ``admit_shared`` (the paged path; chunked prefill runs through
``admit_shared``) or ``admit`` and ``admit_chunk`` (the dense path), over
the busy time.

Prefill takes device time from decode, so the last request ends later:
it moves ``serve_out_tok_s``. A share of busy time also grows when the
rest of the work shrinks, so the admission programs' device time is
logged in seconds and per chunk call beside it."""
import progtrace
from harness import log

ADMIT = ("admit", "admit_fresh", "admit_shared", "admit_chunk")


def read(ctx):
    t = ctx["trace"]
    pt = progtrace.of(ctx)
    if "serve_out_tok_s" not in ctx["result"]["metrics"] or pt is None or \
            pt.window is None or t.busy_s <= 0:
        return None
    evs = progtrace.in_programs(t.events, pt.modules, ADMIT)
    if not evs:
        return None
    lo, hi = pt.window
    sec = progtrace.device_s(evs, lo, hi)
    calls = [m for m in progtrace.executions(pt.modules, ADMIT)
             if lo <= m.start <= hi]
    log(f"admission programs: {sec!r} s of device time, {len(calls)} "
        f"calls, {1e3 * sec / max(len(calls), 1)!r} ms a call")
    return 100.0 * sec / t.busy_s
