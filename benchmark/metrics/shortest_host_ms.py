"""Executor: what a one-path `shortest` block costs off the chip. The
time inside the program's `shortest` span (counter
`shortest_ns_total`, written at the span's exit) less its
`device.call` child (`device_call_ns_total{family="shortest",...}`,
all three phases), as deltas over the window, over the blocks that
dispatched in it (`query_device_shortest_total`): the gate's
reckoning, the pair's slots, the hand-over at the rendezvous, the
reply's path object. A mean a request, not a median over replies: the
harness hands a reader counters and `server_latency`, not spans.
Host-tier blocks in the window count in the span's time and not in
the calls: `device_routed_share` says whether there were any. None
where the counters are not served."""

SPAN = "shortest_ns_total"
CALLS = "query_device_shortest_total"
CHILD = 'device_call_ns_total{family="shortest",'


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
