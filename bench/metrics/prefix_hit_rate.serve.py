"""Share of the window's prompt tokens served from cached prefix blocks:
the engine's own count (``metrics["paging"]["prefix_hit_rate"]`` of the
window's ``serve()`` call)."""


def read(ctx):
    pg = ctx["result"].get("counters", {}).get("paging")
    if pg is None:
        return None
    return 100.0 * pg["prefix_hit_rate"]
