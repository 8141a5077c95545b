"""Mesh-sharded brute-force top-k (similar_to at multi-chip scale).

The vector analogue of parallel/dist_graph.py: a predicate's (n, d)
embedding block is row-sharded over the mesh's `uid` axis (the same
axis that shards one predicate's adjacency), one shard_map step does

    local:  scores = q @ local_rows.T  ->  lax.top_k(k) per shard
    ICI:    all_gather the per-shard (vals, global row idx) candidates
    local:  exact lax.top_k over the S*k candidates (replicated)

which is the TPU-KNN multi-chip layout (PAPERS.md 2206.14286 §4:
shard the database, per-shard partial top-k, tree-merge) mapped onto
the repo's mesh conventions. The final merge with MVCC overlay rows
happens on host via ops/knn.merge_topk.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map
from dgraph_tpu.ops import knn


def _axis_size(mesh: Mesh, axis: str) -> int:
    return mesh.shape[axis]


def shard_corpus(mesh: Mesh, corpus: np.ndarray, axis: str = "uid"):
    """Pad the row axis to the shard count and place the block over
    `axis`. Returns (device array, n_real)."""
    s = _axis_size(mesh, axis)
    n, d = corpus.shape
    per = max(knn.BUCKET_SIZE, -(-n // s))
    n_pad = per * s
    if n_pad != n:
        corpus = np.concatenate(
            [corpus, np.zeros((n_pad - n, d), np.float32)])
    arr = jnp.asarray(corpus, jnp.float32)
    spec = NamedSharding(mesh, P(axis, None))
    return jax.device_put(arr, spec), n

def sharded_topk(mesh: Mesh, corpus_dev, queries: np.ndarray, k: int,
                 metric: str = "cosine",
                 mask: np.ndarray | None = None,
                 n_real: int | None = None,
                 axis: str = "uid", sync=None
                 ) -> tuple[np.ndarray, np.ndarray]:
    """Per-shard top-k + on-device merge. corpus_dev is the padded,
    sharded block from shard_corpus; returns host (idx (q, k'), scores
    (q, k')) with idx into the UNPADDED row axis (entries whose score
    is -inf are padding and must be dropped by the caller). Exact,
    ordered by (-score, row): lax.top_k keeps the lower index first
    and the gather is in shard order. `sync` is applied to the
    dispatched result before it is fetched (`device_call.wait`)."""
    n_pad, d = corpus_dev.shape
    s = _axis_size(mesh, axis)
    per = n_pad // s
    if n_real is None:
        n_real = n_pad
    q = jnp.atleast_2d(jnp.asarray(queries, jnp.float32))
    m = np.zeros(n_pad, bool)
    m[:n_real] = True if mask is None else np.asarray(mask, bool)
    mask_dev = jax.device_put(jnp.asarray(m),
                              NamedSharding(mesh, P(axis)))
    k_eff = min(k, per)
    fn = _sharded_step(mesh, axis, per, k, k_eff, metric)
    out = fn(corpus_dev, q, mask_dev)
    vals, idx = out if sync is None else sync(out)
    return np.asarray(idx, np.int64), np.asarray(vals)


@functools.lru_cache(maxsize=64)
def _sharded_step(mesh: Mesh, axis: str, per: int, k: int, k_eff: int,
                  metric: str):
    """The jitted shard_map step, cached per (mesh, layout, k, metric)
    — rebuilding `jax.jit(shard_map(...))` inside sharded_topk gave
    every call a fresh, empty trace cache, so EVERY query paid a full
    retrace+recompile (dglint DG02). Distinct query-batch shapes still
    retrace, as jit always does; repeated shapes now hit the cache."""

    def step(rows, qm, keep):
        scores = knn._score_device(rows, qm, metric)
        scores = jnp.where(keep[None, :], scores, -jnp.inf)
        vals, idx = jax.lax.top_k(scores, k_eff)       # (q, k) local
        shard = jax.lax.axis_index(axis)
        gidx = idx + shard * per
        av = jax.lax.all_gather(vals, axis, axis=1, tiled=True)
        ai = jax.lax.all_gather(gidx, axis, axis=1, tiled=True)
        fvals, fpos = jax.lax.top_k(av, min(k, av.shape[1]))
        fidx = jnp.take_along_axis(ai, fpos, axis=1)
        return fvals, fidx

    smapped = shard_map(
        step, mesh=mesh,
        in_specs=(P(axis, None), P(None, None), P(axis)),
        out_specs=(P(None, None), P(None, None)),
        check_vma=False)
    return jax.jit(smapped)


# ---------------------------------------------------------------------------
# sharded quantized tier (ops/ivf.py index over a row-sharded corpus)
# ---------------------------------------------------------------------------


def sharded_ivf_topk(mesh: Mesh, ivf, vecs: np.ndarray,
                     queries: np.ndarray, k: int,
                     metric: str = "cosine",
                     keep: np.ndarray | None = None,
                     nprobe: int | None = None,
                     rerank: int | None = None,
                     axis: str = "uid") -> tuple[np.ndarray, np.ndarray]:
    """Quantized top-k over a sharded corpus: the clustered slot axis
    splits into one contiguous range per mesh shard (the same row
    partition shard_corpus uses for the dense block), each shard
    scores ONLY its slice of every probed list and keeps its local
    top-R approximate survivors, and the per-shard candidate lists
    k-way merge (ops/knn.merge_topk order: (-score, id)) into the
    global top-R before ONE exact re-rank — the TPU-KNN multi-chip
    recipe (per-shard partial top-k, tree merge) applied to the
    approximate stage.

    Parity by construction: the shard ranges PARTITION the clustered
    slots, each shard's top-R is a superset of its contribution to
    the global top-R, and the merge cuts by the same (-approx, slot)
    order the single-device path uses — so the re-ranked result is
    identical to ops/ivf.search on one device.

    EXECUTION NOTE: the mesh currently supplies the shard LAYOUT
    (ranges matching shard_corpus's row partition) while the
    candidate stage itself runs host-side per range — correct and
    merge-shaped for the multi-chip recipe, but not yet dispatched
    through shard_map like sharded_topk; device-dispatching the int8
    stage is ROADMAP depth (needs the codes block resident per
    device)."""
    from dgraph_tpu.ops import ivf as _ivf
    import jax.numpy as jnp

    q = np.atleast_2d(np.asarray(queries, np.float32))
    nq = len(q)
    p = min(ivf.nlist, int(nprobe or ivf.nprobe))
    r_depth = int(rerank or _ivf.rerank_depth(k))
    cs, lists = _ivf._probe_jit(jnp.asarray(q),
                                jnp.asarray(ivf.centroids), p,
                                str(metric))
    cs = np.asarray(cs)
    lists = np.asarray(lists, np.int64)
    keep_b = np.asarray(keep, bool) if keep is not None else None
    qn2 = (q.astype(np.float64) ** 2).sum(axis=1)
    s = mesh.shape[axis]
    n = ivf.n_rows
    per = -(-n // s)
    # per-shard approximate candidates within the shard's slot range
    shard_parts: list[tuple[list, list]] = []
    for si in range(s):
        lo, hi = si * per, min(n, (si + 1) * per)
        if lo >= hi:
            continue
        shard_parts.append(_shard_ivf_candidates(
            ivf, lists, cs, q, lo, hi, keep_b, qn2, metric, r_depth))
    out_i = np.full((nq, k), -1, np.int64)
    out_s = np.full((nq, k), -np.inf, np.float64)
    width = 0
    for qi in range(nq):
        # k-way merge of the per-shard survivor lists, cut to the
        # global top-R by the single-device (-approx, slot) order
        merged_slots, _ = _ivf_merge_candidates(
            [(sp[0][qi], sp[1][qi]) for sp in shard_parts], r_depth)
        if not len(merged_slots):
            continue
        rws, sc = _ivf._rerank_one(ivf, vecs, merged_slots, q[qi], k,
                                   metric)
        w = len(rws)
        out_i[qi, :w] = rws
        out_s[qi, :w] = sc
        width = max(width, w)
    return out_i[:, :width], out_s[:, :width]


def _shard_ivf_candidates(ivf, lists, cs, q, lo, hi, keep_b, qn2,
                          metric, r_depth):
    """One shard's local top-R approximate survivors: the SAME
    convert-once group-by-list engine as the single-device path,
    restricted to the shard's contiguous slot range [lo, hi) (lists
    are contiguous, so the intersection is arithmetic), then the
    SHARED per-query filter+transform+cut tail (ops/ivf._filter_cut
    — one implementation, so the parity claim can't rot)."""
    from dgraph_tpu.ops import ivf as _ivf

    slot_l, dot_l = _ivf._approx_scores_host(ivf, lists, cs, q,
                                             lo=lo, hi=hi)
    slot_out: list[np.ndarray] = []
    approx_out: list[np.ndarray] = []
    for qi in range(len(lists)):
        slots, approx = _ivf._filter_cut(
            ivf, slot_l[qi], dot_l[qi], keep_b, float(qn2[qi]),
            metric, r_depth)
        slot_out.append(slots)
        approx_out.append(np.asarray(approx, np.float64))
    return slot_out, approx_out


def _ivf_merge_candidates(parts, r_depth):
    """Merge per-shard (slots, approx) survivor lists and cut to the
    global top-R with the SAME deterministic (-approx, slot) rule as
    the single-device truncation (ops/ivf._cut_top_r) — including on
    boundary ties (duplicate vectors), so the candidate set entering
    the exact re-rank is identical by construction."""
    from dgraph_tpu.ops import ivf as _ivf

    slots = np.concatenate([p[0] for p in parts]) \
        if parts else np.empty(0, np.int64)
    approx = np.concatenate([p[1] for p in parts]) \
        if parts else np.empty(0, np.float64)
    return _ivf._cut_top_r(slots, approx, r_depth)
