"""Kernels: the share of the hub rows' stream that the served k-hop
traversal still reads. `recurse_hub_tiles_streamed_total` (tiles of
hub rows the calls' levels read: those in which some live lane had
not yet reached some row, `ops/bitgraph._tiles_needed`, and one a
level a chip at least, since a kernel's first block is fetched
whatever its flags say) over `recurse_hub_tiles_total` (levels run x
tiles, all chips: what a stream of every row at every level reads),
as deltas over the window, in percent. Both are added up in
`executor._land_traversals` from the call's one small result. 100
where every level reads every row (a call of one-hop lanes, or a
program that skips nothing); the device time of `bfs_hub_rows` in a
traced run falls in step with it. None where the program serves one
of the counters not at all (a program older than PR 38) or no call
with hub rows landed in the window."""

STREAMED = "recurse_hub_tiles_streamed_total"
TOTAL = "recurse_hub_tiles_total"


def read(ctx):
    a, b = ctx["counters_after"], ctx["counters_before"]
    if STREAMED not in a or TOTAL not in a:
        return None
    total = a[TOTAL] - b.get(TOTAL, 0)
    if total <= 0:
        return None
    return 100.0 * (a[STREAMED] - b.get(STREAMED, 0)) / total
