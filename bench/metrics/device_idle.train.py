"""Share of the traced training window in which no op ran on the device
(1 - the union of device op intervals over the window), averaged over
the chips."""


def read(ctx):
    t = ctx["trace"]
    if "train_tok_s" not in ctx["result"]["metrics"] or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
