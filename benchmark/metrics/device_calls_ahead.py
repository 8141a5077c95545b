"""Device: the queue a plain dispatch found at the chip, by count and
with no clock involved. The sum over families of
`device_call_ahead_total` (`query/devicecall.py` `device_call.wait`:
the device calls of the process dispatched and not yet ready when this
one began to wait) over the device calls of the window, counted as
`device_ops_per_req` counts them (every `query_device_*_total` series
and `query_fused_dispatch_total`), both as deltas over the window: a
mean a call. None where the counter is not served (a program older
than PR 39; a cell whose calls all ride a rendezvous) or no call was
made."""

AHEAD = "device_call_ahead_total"
CALLS = ("query_device_", "query_fused_dispatch_total")


def read(ctx):
    a, b = ctx["counters_after"], ctx["counters_before"]
    ahead = [k for k in a if k.startswith(AHEAD)]
    calls = sum(v - b.get(k, 0) for k, v in a.items()
                if k.startswith(CALLS))
    if not ahead or calls <= 0:
        return None
    return sum(a[k] - b.get(k, 0) for k in ahead) / calls
