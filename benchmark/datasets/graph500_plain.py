"""The plain reference of the Graph500 graph: k-hop neighbourhood
counts by a plain breadth-first search over the generator's own edge
arrays in numpy, importing nothing of the program and taking nothing
the program has made.

`ANSWERS[template name](dataset, scale, facts, query)` gives the value
of the reply's `data` member as Python objects (`dataset` is the
dataset module, which draws the SOUND graph of `facts["seed"]` again).
It answers both templates, every pool query.

Semantics, as DQL defines them for

    var(func: uid(R)) @recurse(depth: D, loop: false) { n as link }
    khop(func: uid(n)) { count(uid) }

`depth` counts levels INCLUDING the root's, so D levels are D - 1
edge hops: depth 4 is the source's k = 3, depth 7 its k = 6. Level 0's
frontier is {R}. A level follows every `link` edge out of its
frontier; what it reaches is added to `n`; `loop: false` then takes
every vertex seen before (R among them) out of what it reached, and
the rest is the next frontier. So `n` holds every vertex that an edge
of the walk leads to in 1..k hops: R itself is in `n` exactly where
some vertex within k - 1 hops of R has an edge back to R. The reply
is the one number `{"khop": [{"count": |n|}]}`.
"""

from __future__ import annotations

import re

import numpy as np

_KEPT: dict = {}


def _csr(dataset, scale: int, facts: dict):
    """(offsets, dst, vertices) of the sound graph, kept for the next
    query of the same run; the generator's edges are sorted by src."""
    key = (scale, int(facts["seed"]))
    if _KEPT.get("key") != key:
        src, dst, _, vertices = dataset.graph(scale, key[1])
        offsets = np.zeros(vertices + 1, np.int64)
        np.cumsum(np.bincount(src, minlength=vertices), out=offsets[1:])
        _KEPT["key"], _KEPT["csr"] = key, (offsets, dst, vertices)
    return _KEPT["csr"]


def reached(offsets: np.ndarray, dst: np.ndarray, vertices: int,
            root: int, hops: int) -> np.ndarray:
    """bool[vertices]: the vertices an edge of the walk leads to."""
    seen = np.zeros(vertices, bool)
    seen[root] = True
    n = np.zeros(vertices, bool)
    frontier = np.array([root], np.int64)
    for _ in range(hops):
        if not len(frontier):
            break
        starts = offsets[frontier]
        lens = offsets[frontier + 1] - starts
        at = np.repeat(starts - (np.cumsum(lens) - lens), lens) \
            + np.arange(int(lens.sum()))
        hit = np.zeros(vertices, bool)
        hit[dst[at]] = True
        n |= hit
        hit &= ~seen
        seen |= hit
        frontier = np.flatnonzero(hit)
    return n


def khop(dataset, scale, facts, query):
    m = re.search(r"uid\((0x[0-9a-fA-F]+)\)\)\s*@recurse\(depth:\s*(\d+)",
                  query)
    root = int(m.group(1), 16) - dataset.FIRST_UID
    offsets, dst, vertices = _csr(dataset, scale, facts)
    n = reached(offsets, dst, vertices, root, int(m.group(2)) - 1)
    return {"khop": [{"count": int(n.sum())}]}


ANSWERS = {"khop3": khop, "khop6": khop}
