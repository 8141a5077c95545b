"""jit_bfs_traverse_sharded (ops/bitgraph.py), the whole of a bound
@recurse in one program over a mesh's chips, as `khop3` and `khop6`
call it under `alpha --chips N`. The least ONE CHIP of them has to
move, reckoned from the GRAPH, the QUERY and the number of chips, not
from the arrays the program happens to keep, so that another layout
(hub rows or gathers, this split or another) cannot make the count
stale: a level has every edge's source read once as a 4-byte index,
and the edges are shared out over the chips, so a chip reads its share
of them; and every chip reads the frontier and writes the next one
WHOLE, as bitmaps of one bit a vertex, since each holds them whole
(whatever collective hands them round). A call moves that once for
each level the query ASKS for (`depth` - 1 edge hops). Memory-bound by
statement: a level is gathers, ANDs and ORs, no matrix unit work. A
lower bound: no padding, no visited set, no count, nothing a chip
receives from the others.

TEMPLATE is None: the bytes of a call follow the query's depth, which
two templates of the mix set differently, so `top_program_roofline`'s
one-template rule passes this program by; `bfs_shard_roofline` feeds
least_bytes() the mean over the mix."""

TEMPLATE = None


def least_bytes(s: dict) -> float:
    """Bytes ONE chip moves at least in a call. s: {"edges": the
    adjacency's edges (all chips'), "vertices": its vertices, "chips":
    the chips the edges are shared over, "levels": edge hops a call
    asks for (a mean over the mix)}."""
    return s["levels"] * (4 * s["edges"] / s["chips"]
                          + 2 * s["vertices"] / 8)
