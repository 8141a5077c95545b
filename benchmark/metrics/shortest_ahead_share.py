"""Executor: the share of the shortest-path rendezvous' calls that
went onto the device's queue BEHIND a call still in flight.
`rendezvous_ahead_total{family="shortest"}` (calls launched the moment
`ops/bitgraph.LANES` = 8 pairs waited while a call was in flight and
none stood behind it: `query/devicecall.py` `Rendezvous._board`,
counted in `_launch`) over `shortest_calls_total` (every call the
rendezvous dispatched, counted in `executor._launch_paths`), as deltas
over the window, in percent: `rendezvous_ahead_share` of the k-hop
cells, read for this family. With 16 clients on 8 lanes the chip goes
from one call to the next with no host thread in between as this
nears 100; what is left under it is the calls a landing thread
launched onto a free chip (a late eighth pair, a full collection's
pause). None where the program serves one of the counters not at all
or made no call in the window."""

AHEAD = 'rendezvous_ahead_total{family="shortest"}'
CALLS = "shortest_calls_total"


def read(ctx):
    a, b = ctx["counters_after"], ctx["counters_before"]
    if AHEAD not in a or CALLS not in a:
        return None
    calls = a[CALLS] - b.get(CALLS, 0)
    if calls <= 0:
        return None
    return 100.0 * (a[AHEAD] - b.get(AHEAD, 0)) / calls
