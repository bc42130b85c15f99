"""Share of the traced serving window (arrivals through the last
completion) in which no op ran on the device."""


def read(ctx):
    t = ctx["trace"]
    if "itl_p95_ms" not in ctx["result"]["metrics"] or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
