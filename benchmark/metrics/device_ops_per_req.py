"""Planner + gate: device stages dispatched per request, the sum of
every `query_device_*_total` series and `query_fused_dispatch_total`
as deltas over the window, over the requests sent in it."""


def read(ctx):
    a, b = ctx["counters_after"], ctx["counters_before"]
    moved = sum(v - b.get(k, 0) for k, v in a.items()
                if k.startswith(("query_device_", "query_fused_dispatch_total")))
    return moved / len(ctx["replies"]) if ctx["replies"] else None
