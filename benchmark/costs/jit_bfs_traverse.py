"""jit_bfs_traverse (ops/bitgraph.py), the whole of a bound @recurse
in one program, as `khop3` and `khop6` call it. The least a level of a
breadth-first search has to move is reckoned from the GRAPH and the
QUERY, not from the arrays the program happens to keep, so that
another layout of the adjacency cannot make the count stale: every
edge's source read once as a 4-byte index, the frontier read and the
next one written as bitmaps of one bit a vertex. A call moves that
once for each level the query ASKS for (`depth` - 1 edge hops).
Memory-bound by statement: a level is gathers and ORs, no matrix unit
work. A lower bound: no padding, no visited set, no count.

TEMPLATE is None: the bytes of a call follow the query's depth, which
two templates of the mix set differently, so `top_program_roofline`'s
one-template rule does not apply and it passes this program by;
`bfs_roofline` feeds least_bytes() the mean over the mix."""

TEMPLATE = None


def least_bytes(s: dict) -> float:
    """s: {"edges": the adjacency's edges, "vertices": its vertices,
    "levels": edge hops a call asks for (a mean over the mix)}."""
    return s["levels"] * (4 * s["edges"] + 2 * s["vertices"] / 8)
