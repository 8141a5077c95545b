"""The plain reference of the Pokec-shaped graph: the shortest path
between two profiles by plain breadth-first searches over the
generator's own edge arrays in numpy, importing nothing of the
program and taking nothing the program has made.

`ANSWERS[template name](dataset, scale, facts, query)` gives the value
of the reply's `data` member as Python objects (`dataset` is the
dataset module, which draws the SOUND graph of `facts["seed"]`
again). It answers the one template, every pool query.

Semantics, as the deployment states them (docs/deployment.md,
"shortest") for

    shortest(from: A, to: B, depth: D) { friend }

a path of the fewest hops from A to B along the edges' direction if
one of at most D hops exists, else no path; among the paths of that
length, the one whose uid sequence read from A is lexicographically
least. Here: a search from B against the edges, until A is met or D
levels are done, gives every vertex met its distance TO B (A's is the
hops, or there is no path within D); the path then takes, from A, at
every hop the smallest-uid out-neighbour whose distance to B is one
less, which is that least sequence. The reply is

    {"_path_": [{"uid": A, "_weight_": hops, "friend": {"uid": ...,
                 "friend": {... {"uid": B}}}}]}

the one vertex with weight 0 for A = B, and {"_path_": []} where
there is no path.
"""

from __future__ import annotations

import re

import numpy as np

_KEPT: dict = {}
_FAR = np.iinfo(np.int32).max


def _csr(starts: np.ndarray, ends: np.ndarray, vertices: int):
    """(offsets, ends) of the edges (start, end), grouped by start,
    a vertex's ends in rising order."""
    order = np.lexsort((ends, starts))
    offsets = np.zeros(vertices + 1, np.int64)
    np.cumsum(np.bincount(starts, minlength=vertices), out=offsets[1:])
    return offsets, ends[order]


def _graph(dataset, scale: int, facts: dict):
    """((offsets, dst), (offsets, src) against the edges, vertices)
    of the sound graph, kept for the next query of the same run."""
    key = (scale, int(facts["seed"]))
    if _KEPT.get("key") != key:
        src, dst, ranges = dataset.graph(scale, key[1])
        n = ranges["vertices"]
        _KEPT["key"] = key
        _KEPT["graph"] = (_csr(src, dst, n), _csr(dst, src, n), n)
    return _KEPT["graph"]


def distances(csr, vertices: int, root: int, stop: int,
              depth: int) -> np.ndarray:
    """int32[vertices]: hops from `root` along `csr`, searched level
    by level until `stop` is met or `depth` levels are done; _FAR for
    a vertex not met by then."""
    offsets, ends = csr
    dist = np.full(vertices, _FAR, np.int32)
    dist[root] = 0
    frontier = np.array([root], np.int64)
    for hop in range(1, depth + 1):
        if not len(frontier) or dist[stop] != _FAR:
            break
        starts = offsets[frontier]
        lens = offsets[frontier + 1] - starts
        at = np.repeat(starts - (np.cumsum(lens) - lens), lens) \
            + np.arange(int(lens.sum()))
        met = np.zeros(vertices, bool)
        met[ends[at]] = True
        frontier = np.flatnonzero(met & (dist == _FAR))
        dist[frontier] = hop
    return dist


def least_path(dataset, scale: int, facts: dict, a: int, b: int,
               depth: int) -> list[int]:
    """The vertices (uid - FIRST_UID) of the defined path from `a` to
    `b`, [] where none has at most `depth` hops."""
    out, back, n = _graph(dataset, scale, facts)
    to_b = distances(back, n, b, a, depth)
    if to_b[a] == _FAR:
        return []
    path = [a]
    for left in range(int(to_b[a]) - 1, -1, -1):
        nbs = out[1][out[0][path[-1]]:out[0][path[-1] + 1]]
        path.append(int(nbs[to_b[nbs] == left][0]))
    return path


def shortest15(dataset, scale, facts, query):
    m = re.search(r"shortest\(\s*from:\s*(0x[0-9a-fA-F]+),\s*to:\s*"
                  r"(0x[0-9a-fA-F]+),\s*depth:\s*(\d+)", query)
    a, b = (int(m.group(i), 16) - dataset.FIRST_UID for i in (1, 2))
    path = least_path(dataset, scale, facts, a, b, int(m.group(3)))
    if not path:
        return {"_path_": []}
    node = None
    for v in reversed(path):
        here = {"uid": hex(v + dataset.FIRST_UID)}
        if node is not None:
            here["friend"] = node
        node = here
    # the reply's key order: uid, _weight_, then the edge
    first = {"uid": node["uid"], "_weight_": float(len(path) - 1)}
    if "friend" in node:
        first["friend"] = node["friend"]
    return {"_path_": [first]}


ANSWERS = {"shortest15": shortest15}
