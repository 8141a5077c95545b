"""Executor: traversals a call of the SHARDED k-hop traversal
carried. `recurse_sharded_lanes_total` (traversals the calls carried)
over `recurse_sharded_total` (calls of `query/devicecall.Rendezvous`
that took `bfs_traverse_sharded`, the program whose adjacency is
split over a mesh's chips: counted in `executor._launch_traversals`
beside `recurse_batch_total`), as deltas over the window: a mean a
call, between 1 and the rendezvous' capacity, `ops/bitgraph.LANES` =
8. It is the cell's own proof of two things: that its calls take the
sharded program at all, and that they ride full (a closed loop of
2 x 8 clients keeps eight waiting whenever a call lands). None where
the program serves one of the counters not at all (a program without
`alpha --chips`) or made no such call in the window (an alpha without
a mesh)."""

CALLS = "recurse_sharded_total"
LANES = "recurse_sharded_lanes_total"


def read(ctx):
    a, b = ctx["counters_after"], ctx["counters_before"]
    if CALLS not in a or LANES not in a:
        return None
    calls = a[CALLS] - b.get(CALLS, 0)
    if calls <= 0:
        return None
    return (a[LANES] - b.get(LANES, 0)) / calls
