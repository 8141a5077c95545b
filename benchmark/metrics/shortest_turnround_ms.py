"""Executor: the host's share of the gap between two calls of the
shortest-path rendezvous. `rendezvous_ns_total{family="shortest",
phase="turnround"}` (from `land`'s return to the next call's `launch`
returning, on the landing thread's clock: `query/devicecall.py`
`Rendezvous._fly`, the `device.flight` span's `turnround_us`) over
`rendezvous_chained_total{family="shortest"}` (the calls that had a
successor at their landing; one already on the device's queue adds
0 ns), as deltas over the window: a mean a call;
`flight_turnround_ms` of the k-hop cells, read for this family. It
bounds the host's share of the chip's idle gap a call from above.
None where the program serves one of the counters not at all or
chained no call in the window."""

NS = 'rendezvous_ns_total{family="shortest",phase="turnround"}'
CALLS = 'rendezvous_chained_total{family="shortest"}'


def read(ctx):
    a, b = ctx["counters_after"], ctx["counters_before"]
    if NS not in a or CALLS not in a:
        return None
    calls = a[CALLS] - b.get(CALLS, 0)
    if calls <= 0:
        return None
    return (a[NS] - b.get(NS, 0)) / calls / 1e6
