"""Executor: the share of the k-hop rendezvous' calls that went onto
the device's queue BEHIND a call still in flight.
`rendezvous_ahead_total{family="recurse"}` (calls launched the moment
`ops/bitgraph.LANES` = 8 riders waited while a call was in flight and
none stood behind it, by the rider whose arrival filled the call:
`query/devicecall.py` `Rendezvous._board`, counted in `_launch`; the
`device.flight` span's `ahead`) over `recurse_batch_total` (every call
the rendezvous dispatched, counted in `executor._launch_traversals`),
as deltas over the window, in percent. The chip goes from such a call's
predecessor to it with no host thread in between, so
`device_idle_share` falls and `flight_turnround_ms` reads 0 for the
predecessor as this rises; what is left under 100 is the calls a
landing thread launched onto a free chip: a late eighth rider, the
pause of a full collection, fewer than 2 x LANES connections. 0 where
the program serves the counter and no call went ahead. None where the
program serves one of the counters not at all (a program older than
PR 40) or launched no call in the window."""

AHEAD = 'rendezvous_ahead_total{family="recurse"}'
CALLS = "recurse_batch_total"


def read(ctx):
    a, b = ctx["counters_after"], ctx["counters_before"]
    if AHEAD not in a or CALLS not in a:
        return None
    calls = a[CALLS] - b.get(CALLS, 0)
    if calls <= 0:
        return None
    return 100.0 * (a[AHEAD] - b.get(AHEAD, 0)) / calls
