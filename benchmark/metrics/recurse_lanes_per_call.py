"""Executor: traversals a device call of the k-hop rendezvous carried.
`recurse_batch_lanes_total` (traversals the calls carried) over
`recurse_batch_total` (calls `query/devicecall.Rendezvous` dispatched
at `executor._recurse_device`), as deltas over the window: a mean a
call, between 1 (every request rode alone) and the rendezvous'
capacity, `ops/bitgraph.LANES` = 8 (every call full). It is the
cell's own proof that its calls ride full: a closed loop of 2 x 8
clients keeps eight waiting whenever a call lands. None where the
program serves one of the counters not at all (a program older than
PR 34) or made no call in the window."""

CALLS = "recurse_batch_total"
LANES = "recurse_batch_lanes_total"


def read(ctx):
    a, b = ctx["counters_after"], ctx["counters_before"]
    if CALLS not in a or LANES not in a:
        return None
    calls = a[CALLS] - b.get(CALLS, 0)
    if calls <= 0:
        return None
    return (a[LANES] - b.get(LANES, 0)) / calls
