"""Kernels: the share of the hub rows' stream that the shortest-path
search still reads. `shortest_rows_streamed_tiles_total` (tiles of hub
rows the calls' levels read: those in which some live lane had not
yet reached some row, `ops/bitgraph._tiles_needed`, and one a level
at least) over `shortest_rows_tiles_total` (levels run x tiles: what a
stream of every row at every level reads), as deltas over the window,
in percent; both added up in `executor._land_paths` from the call's
one small result. `bfs_rows_streamed_share` of the k-hop cells, read
for this program: `bfs_hub_rows`' device time in a traced run falls
in step with it. A lane that has met its source holds no frontier and
asks for no tile, so the share falls as lanes end early. None where
the program serves one of the counters not at all or no call with
hub rows landed in the window."""

STREAMED = "shortest_rows_streamed_tiles_total"
TOTAL = "shortest_rows_tiles_total"


def read(ctx):
    a, b = ctx["counters_after"], ctx["counters_before"]
    if STREAMED not in a or TOTAL not in a:
        return None
    total = a[TOTAL] - b.get(TOTAL, 0)
    if total <= 0:
        return None
    return 100.0 * (a[STREAMED] - b.get(STREAMED, 0)) / total
