"""Kernels: levels a device call of the shortest-path search ran.
`shortest_levels_run_total` (the loop's levels, the call's own row of
`ops/bitgraph.bfs_paths`' result, added up in
`executor._land_paths`) over `shortest_calls_total`, as deltas over
the window: a mean a call. A call runs until its LAST lane has met
its source, found its frontier empty or spent its depth, so this is
the deepest of up to eight pairs, under the depth the queries ask
for (15 in this cell) wherever the pairs are nearer than that. Device time follows
it: a level costs about the same whatever the frontier holds. None
where the program serves one of the counters not at all or made no
call in the window."""

LEVELS = "shortest_levels_run_total"
CALLS = "shortest_calls_total"


def read(ctx):
    a, b = ctx["counters_after"], ctx["counters_before"]
    if LEVELS not in a or CALLS not in a:
        return None
    calls = a[CALLS] - b.get(CALLS, 0)
    if calls <= 0:
        return None
    return (a[LEVELS] - b.get(LEVELS, 0)) / calls
