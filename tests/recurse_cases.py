"""Riders of the served k-hop traversal whose first level the tests of
`ops/bitgraph`'s column form hold to the streamed one, on one chip
(test_recurse_batch.py) and over a mesh (test_recurse_sharded.py)."""

from dgraph_tpu.ops import bitgraph

LANES = bitgraph.LANES


def traverse_as(badj, riders, columns, tile=bitgraph._HUB_TILE_ROWS):
    """bitgraph.traverse with the first level's form as `columns`
    says (the jitted programs' static argument: True from the roots'
    columns, False streamed, None by the rule, which is all that
    `traverse` itself ever asks for)."""
    packed = bitgraph._pack_riders(badj.n_slots, riders)
    if badj.mesh is None:
        return bitgraph.bfs_traverse(
            [b.in_nb for b in badj.gathered], badj.dense, packed,
            n_slots=badj.n_slots, n_covered=badj.n_covered, lanes=LANES,
            tile=tile, columns=columns)
    return bitgraph.bfs_traverse_sharded(
        badj.shard_nbs, badj.dense, packed, mesh=badj.mesh,
        part_rows=bitgraph.shard_parts(badj), n_slots=badj.n_slots,
        lanes=LANES, tile=tile, columns=columns)


def column_lanes(case: str, edges: dict, a_lane: int = 1) -> list:
    """[(root uids, depth)] a case, over `edges` (uid -> the uids its
    edges lead to). The hub: the vertex most edges lead to among
    those an edge leaves (a hub row wherever there are any); a sink:
    one no edge leaves."""
    heads = sorted(edges)
    indeg: dict = {}
    for d in edges.values():
        for v in d.tolist():
            indeg[v] = indeg.get(v, 0) + 1
    hub = max(heads, key=lambda u: (indeg.get(u, 0), -u))
    sink = min(v for v in indeg if v not in edges)
    return {
        # three roots of a call's eight seed slots
        "padding_seeds": [([heads[0]], 3), ([heads[5]], 6), ([heads[9]], 1)],
        "eight_seeds_no_padding": [([h], 2 + i % 5)
                                   for i, h in enumerate(heads[10:18])],
        "one_root_in_two_lanes": [([heads[3]], 2), ([heads[3]], 5),
                                  ([heads[3], heads[4]], 1)],
        "a_lane_of_depth_0": [([heads[0]], 0), ([heads[1]], 4), ([hub], 0)],
        "every_lane_of_depth_0": [([heads[0]], 0), ([hub], 0)],
        "a_root_with_no_out_edge": [([sink], 4), ([heads[2]], 3),
                                    ([sink, heads[7]], 2)],
        "a_root_that_is_a_hub": [([hub], 3), ([heads[6], hub], 2)],
        "several_roots_a_lane": [(heads[20:27], 2), (heads[24:30], 7)],
        # `a_lane` roots in each of the eight lanes
        "more_roots_than_the_rule_allows": [
            (heads[i:i + a_lane], 2 + i % 3) for i in range(LANES)],
    }[case]
