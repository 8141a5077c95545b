"""Executor: the host's share of the gap between two calls of the
k-hop rendezvous. `rendezvous_ns_total{family="recurse",
phase="turnround"}` (from `land`'s return to the next call's `launch`
returning, on the landing thread's clock: `query/devicecall.py`
`Rendezvous._fly`, the `device.flight` span's `turnround_us`) over
`rendezvous_chained_total{family="recurse"}` (the calls a landing
thread launched, the only ones that have a turn-round), as deltas over
the window: a mean a call. It bounds the host's share of the chip's
idle gap a call ((`window_s` - `busy_s`) / calls of a traced run) from
above: the device starts the next call before `launch` has returned.
What the gap has beyond that share lies before `land` returned: the
device's finish -> the runtime's notice of it -> the landing thread's
wake and fetch. None where the program serves one of the counters not
at all (a program older than PR 39) or chained no call in the
window."""

NS = 'rendezvous_ns_total{family="recurse",phase="turnround"}'
CALLS = 'rendezvous_chained_total{family="recurse"}'


def read(ctx):
    a, b = ctx["counters_after"], ctx["counters_before"]
    if NS not in a or CALLS not in a:
        return None
    calls = a[CALLS] - b.get(CALLS, 0)
    if calls <= 0:
        return None
    return (a[NS] - b.get(NS, 0)) / calls / 1e6
