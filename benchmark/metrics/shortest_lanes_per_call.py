"""Executor: pairs a device call of the shortest-path rendezvous
carried. `shortest_riders_total` (pairs the calls carried) over
`shortest_calls_total` (calls `query/devicecall.Rendezvous`
dispatched at `executor._device_shortest`, counted in
`executor._launch_paths`), as deltas over the window: a mean a call,
between 1 (every request rode alone) and the rendezvous' capacity,
`ops/bitgraph.LANES` = 8 (every call full). It is the cell's own
proof that its calls ride full: a closed loop of 2 x 8 clients keeps
eight waiting whenever a call lands. None where the program serves
one of the counters not at all or made no call in the window."""

CALLS = "shortest_calls_total"
RIDERS = "shortest_riders_total"


def read(ctx):
    a, b = ctx["counters_after"], ctx["counters_before"]
    if CALLS not in a or RIDERS not in a:
        return None
    calls = a[CALLS] - b.get(CALLS, 0)
    if calls <= 0:
        return None
    return (a[RIDERS] - b.get(RIDERS, 0)) / calls
