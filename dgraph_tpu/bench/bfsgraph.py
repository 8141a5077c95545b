"""The synthetic scale-free graph of the BFS digest kernel check, and
its single-core NumPy traversal baseline (bench/kernelcheck.py compiles
the kernel over it on a served chip and checks it against the baseline).
"""

from __future__ import annotations

import numpy as np


def make_graph(n_nodes: int, n_edges: int, seed: int = 0):
    """Scale-free-ish: Zipf-weighted destinations, uniform sources."""
    rng = np.random.default_rng(seed)
    src = rng.integers(1, n_nodes + 1, n_edges, dtype=np.uint64)
    # zipf over node ids truncated to range (heavy head like a movie graph)
    dst = (rng.zipf(1.3, n_edges) % n_nodes + 1).astype(np.uint64)
    mask = src != dst
    src, dst = src[mask], dst[mask]
    pairs = np.unique(np.stack([src, dst], axis=1), axis=0)
    src, dst = pairs[:, 0], pairs[:, 1]
    order = np.lexsort((dst, src))
    src, dst = src[order], dst[order]
    # CSR
    uniq_src, starts = np.unique(src, return_index=True)
    indptr = np.append(starts, len(src))
    return uniq_src, indptr, dst


def csr_to_dict(uniq_src, indptr, dst):
    return {int(u): dst[indptr[i]: indptr[i + 1]].astype(np.uint32)
            for i, u in enumerate(uniq_src)}


def numpy_bfs(uniq_src, indptr, dst, seeds, depth):
    """Single-core CPU baseline: vectorized CSR frontier expansion."""
    visited = seeds.copy()
    frontier = seeds
    for _ in range(depth):
        idx = np.searchsorted(uniq_src, frontier)
        idx = np.clip(idx, 0, len(uniq_src) - 1)
        hit = uniq_src[idx] == frontier
        rows = idx[hit]
        if not len(rows):
            frontier = np.empty(0, np.uint64)
            break
        parts = [dst[indptr[r]: indptr[r + 1]] for r in rows]
        nxt = np.unique(np.concatenate(parts))
        nxt = np.setdiff1d(nxt, visited, assume_unique=True)
        visited = np.union1d(visited, nxt)
        frontier = nxt
    return len(frontier)
