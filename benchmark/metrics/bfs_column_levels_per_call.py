"""Kernels: levels of a served k-hop call that were answered from the
columns of its roots instead of gathered and streamed.
`recurse_column_levels_total` (a call's FIRST level where the program
read the out-neighbours of its few seed slots as their columns of the
reverse structures it holds, `ops/bitgraph._chip_columns`: a block of
128 word columns of the hub rows a seed and a compare an index of the
gathered classes; 0 or 1 a call, by a rule from the shapes alone,
`ops/bitgraph.columns_cheaper`; the call's third tally row, added up
in `executor._land_traversals`; the `recurse` and `device.call`
spans' `column_levels`) over `recurse_batch_total` (every call the
rendezvous dispatched, counted in `executor._launch_traversals`), as
deltas over the window: a mean a call, 0 to 1. Such a level streams
no tile of hub rows, so `bfs_rows_streamed_share` and the device time
of `bfs_hub_rows` and of the gathers' fusions fall by a level a call
as this rises. 0 where the program serves the counter and no call
took the column level (root sets past where the rule turns). None
where the program serves one of the counters not at all (a program
older than PR 43) or launched no call in the window."""

COLUMNS = "recurse_column_levels_total"
CALLS = "recurse_batch_total"


def read(ctx):
    a, b = ctx["counters_after"], ctx["counters_before"]
    if COLUMNS not in a or CALLS not in a:
        return None
    calls = a[CALLS] - b.get(CALLS, 0)
    if calls <= 0:
        return None
    return (a[COLUMNS] - b.get(COLUMNS, 0)) / calls
