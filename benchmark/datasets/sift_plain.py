"""The plain reference of the SIFT-shaped corpus: exact nearest
neighbours worked out from the generator's own arrays in plain numpy,
importing nothing of the program and taking nothing the program has
made.

`ANSWERS[template name](dataset, scale, facts, query)` gives the value
of the reply's `data` member as Python objects (`dataset` is the
dataset module, which draws the SOUND corpus of `facts["seed"]` again;
`facts` are its counts). It answers all three templates, every pool
query.

Semantics. The distance is the squared L2 distance in whole numbers,
|row|^2 - 2 row.q + |q|^2 in int64. The one float step is the dot
product row.q, a BLAS call over the rows held as float32: the
components are whole numbers 0-255, so every product and every partial
sum is a whole number of at most 128 x 255^2 = 8,323,200 < 2^24, which
float32 holds exactly in whatever order they are added.
The k nearest are the first k rows by (distance, uid). A root
`similar_to` emits them in that order, nearest first, ties by uid,
and `val(similar_to_score)` is the NEGATED squared distance. As a
filter, `similar_to` keeps the k nearest AMONG the root's rows (here:
one category) and the block keeps the root's order, which is uid
order. Row i is uid FIRST_UID + i and has id i.
"""

from __future__ import annotations

import re

import numpy as np

# the corpus is widened to float32 this many rows at a time
BLOCK_ROWS = 1 << 16

_KEPT: dict = {}


def _args(query: str) -> tuple[int, np.ndarray]:
    m = re.search(r'similar_to\(embedding,\s*(\d+),\s*"\[([^\]]*)\]"', query)
    vec = np.array(m.group(2).replace(",", " ").split(), dtype=np.float64)
    return int(m.group(1)), vec


def widen(vecs: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(rows as float32, each row's squared norm as int64), block by
    block: done once a corpus, not once a query."""
    wide = np.empty(vecs.shape, np.float32)
    norms = np.empty(len(vecs), np.int64)
    for lo in range(0, len(vecs), BLOCK_ROWS):
        block = vecs[lo:lo + BLOCK_ROWS]
        wide[lo:lo + BLOCK_ROWS] = block
        norms[lo:lo + BLOCK_ROWS] = np.einsum(
            "ij,ij->i", block, block, dtype=np.int64)
    return wide, norms


def squared_distances(wide: np.ndarray, norms: np.ndarray,
                      q: np.ndarray) -> np.ndarray:
    """(n,) int64: |row - q|^2 = |row|^2 - 2 row.q + |q|^2."""
    dots = (wide @ q.astype(np.float32)).astype(np.int64)
    qi = q.astype(np.int64)
    return norms - 2 * dots + int(qi @ qi)


def nearest(wide: np.ndarray, norms: np.ndarray, q: np.ndarray, k: int,
            keep: np.ndarray | None = None
            ) -> tuple[np.ndarray, np.ndarray]:
    """(rows, distances) of the k nearest by (distance, row), among
    the rows `keep` marks (a boolean column) if it is given."""
    dist = squared_distances(wide, norms, q)
    rows = np.arange(len(wide)) if keep is None else np.flatnonzero(keep)
    if k < len(rows):
        # only rows at or under the k-th smallest distance can be
        # among the first k of the full order
        d = dist[rows]
        rows = rows[d <= np.partition(d, k - 1)[k - 1]]
    order = np.lexsort((rows, dist[rows]))[:k]
    return rows[order], dist[rows[order]]


def _corpus(dataset, scale, facts):
    """(wide, norms, categories) of the sound corpus, kept for the
    next query of the same run."""
    key = (scale, int(facts["seed"]))
    if _KEPT.get("key") != key:
        vecs, cats = dataset.corpus(scale, key[1])
        _KEPT["key"], _KEPT["corpus"] = key, (*widen(vecs), cats)
    return _KEPT["corpus"]


def knn_with_scores(dataset, scale, facts, query):
    """similar_to at the root {id val(similar_to_score)}."""
    k, q = _args(query)
    wide, norms, _ = _corpus(dataset, scale, facts)
    rows, dist = nearest(wide, norms, q, k)
    return {"q": [{"id": int(r), "val(similar_to_score)": -float(d)}
                  for r, d in zip(rows, dist)]}


def knn_ids(dataset, scale, facts, query):
    """similar_to at the root {id}."""
    k, q = _args(query)
    wide, norms, _ = _corpus(dataset, scale, facts)
    rows, _ = nearest(wide, norms, q, k)
    return {"q": [{"id": int(r)} for r in rows]}


def knn_in_category(dataset, scale, facts, query):
    """eq(category, c) @filter(similar_to(...)) {id}: the k nearest of
    the category, in the root's uid order."""
    k, q = _args(query)
    c = int(re.search(r"eq\(category,\s*(\d+)\)", query).group(1))
    wide, norms, cats = _corpus(dataset, scale, facts)
    rows, _ = nearest(wide, norms, q, k, keep=cats == c)
    return {"q": [{"id": int(r)} for r in np.sort(rows)]}


ANSWERS = {
    "knn10": knn_with_scores,
    "knn100": knn_ids,
    "knn10_in_category": knn_in_category,
}
