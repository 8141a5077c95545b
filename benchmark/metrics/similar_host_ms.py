"""Executor: what a `similar_to` costs off the chip. The time inside
the program's `similar_to` span (counter `similar_ns_total`, written
at the span's exit) less its `device.call` child
(`device_call_ns_total{family="similar",...}`, all three phases; the
closing comma keeps family `similar_sharded` out), as
deltas over the window, over the calls dispatched in it
(`query_device_similar_total`): the vector view, the mask and its
`_member_of`, the merge, the value variable. A mean a call, not a
median over replies: the harness hands a reader counters and
`server_latency`, not spans. None where the counters are not served."""

SPAN = "similar_ns_total"
CALLS = "query_device_similar_total"
CHILD = 'device_call_ns_total{family="similar",'


def read(ctx):
    a, b = ctx["counters_after"], ctx["counters_before"]
    if SPAN not in a:
        return None
    calls = a.get(CALLS, 0) - b.get(CALLS, 0)
    if calls <= 0:
        return None
    inside = sum(v - b.get(k, 0) for k, v in a.items()
                 if k.startswith(CHILD))
    return (a[SPAN] - b.get(SPAN, 0) - inside) / calls / 1e6
