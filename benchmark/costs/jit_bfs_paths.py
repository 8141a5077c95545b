"""jit_bfs_paths (ops/bitgraph.py), the one-path `shortest` block for
up to eight pairs in one program, as `shortest15` calls it. The least
a level of a pull breadth-first search has to move is reckoned from
the GRAPH, not from the arrays the program happens to keep, so that
another layout of the adjacency cannot make the count stale: every
edge's other end read once as a 4-byte index, the frontier read and
the next one written as bitmaps of one bit a vertex. A call moves
that once for each level it RAN (the search ends when its lanes have
met their sources, so the levels are counted by the program,
`shortest_levels_run_total`, and not read off the query's depth).
Memory-bound by statement: a level is gathers, ANDs and ORs, no
matrix unit work.

A lower bound, and it says what it leaves out: the hub rows the
program holds in place of most indices (a bitmap of every vertex a
row: far more bytes than the indices they stand for), the padding of
the gathered classes, the visited sets, the distances kept for the
walk (4 B a vertex and lane), the walk itself, and that a call
carries up to eight searches for one search's bytes.

TEMPLATE is None: the bytes of a call follow the levels it ran, which
no template parameter says, so `top_program_roofline`'s one-template
rule does not apply and it passes this program by;
`shortest_roofline` feeds least_bytes() the window's mean."""

TEMPLATE = None


def least_bytes(s: dict) -> float:
    """s: {"edges": the graph's edges, "vertices": its vertices,
    "levels": levels a call ran (a mean over the window)}."""
    return s["levels"] * (4 * s["edges"] + 2 * s["vertices"] / 8)
