"""Executor: what a bound `@recurse` costs off the chip. The time
inside the program's `recurse` span (counter `recurse_ns_total`,
written at the span's exit) less its `device.call` child
(`device_call_ns_total{family="recurse",...}`, all three phases), as
deltas over the window, over the traversals dispatched in it
(`query_device_recurse_total`): the bound-shape check, the gate's
estimate, the roots' slots, and, where a later block reads the uids,
the bitmap turned into a uid array. A mean a call, not a median over
replies: the harness hands a reader counters and `server_latency`,
not spans. Host-tier traversals in the window count in the span's
time and not in the calls: `device_routed_share` says whether there
were any. None where the counters are not served."""

SPAN = "recurse_ns_total"
CALLS = "query_device_recurse_total"
CHILD = 'device_call_ns_total{family="recurse",'


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
