"""Quantized IVF kernels: the approximate tier of similar_to().

The brute-force tiers (ops/knn.py) score every row; at the 10-100M
regime that is two orders of magnitude too much arithmetic even at
peak MXU FLOP/s. This module implements the coarse-then-rerank recipe
both retrieved papers point at (PAPERS.md):

  TPU-KNN (2206.14286) — keep the distance computation a dense matmul
    so it runs at peak throughput: centroid scoring is a (q, d) x
    (d, nc) dot, candidate scoring a gathered (R, d) int8
    dequant-and-dot, both MXU-shaped.

  A Faster Generalized Two-Stage Approximate Top-K (2506.04165) —
    budget the approximate stage from a recall target and finish with
    an EXACT reduction over the survivors: here stage one is the IVF
    probe (nprobe lists) + int8 approximate scores, stage two an exact
    float64 re-rank of the top `rerank` survivors, so the only recall
    loss is candidate-set truncation, never score noise.

Index layout (built once per clean base block, storage/vecstore.py):

  centroids  (nc, d) f32   k-means centers, trained on a seeded sample
  order      (n,)   i32    base-block row of clustered slot i — rows
                           sorted by (assigned centroid, row), so one
                           probed list is one CONTIGUOUS slice
  starts     (nc+1,) i64   list offsets into `order`
  codes      (n, d) i8     per-row scalar-quantized residual
                           (row - centroid), clustered order
  scales     (n,)   f32    per-row dequant scale (maxabs/127)
  norms2     (n,)   f32    exact squared L2 of the ORIGINAL rows,
                           clustered order — cosine/euclidean use the
                           true norm, only the dot is approximated

nprobe and the re-rank depth are not knobs the caller must guess:
build() measures recall@k_ref on a held-out sample of base rows
against a blocked exact scan and picks the smallest nprobe on a
doubling ladder that clears the target (conservative default 0.98,
twice the distance to 1.0 of the 0.95 acceptance floor).

Everything is deterministic: seeded rng, stable sorts, fixed-shape
jitted reductions — two builds over the same block byte-match, the
property the snapshot plane's determinism contract leans on.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import numpy as np

import jax

from dgraph_tpu.ops import knn
from dgraph_tpu.utils.metrics import inc_counter

# calibration reference k: nprobe is tuned for recall@K_REF; query-time
# k above k_max (below) falls back to the exact tiers
K_REF = 10
# recall target the build calibrates nprobe against (conservative:
# the acceptance floor is 0.95, the default budget aims past it)
TARGET_RECALL = 0.98
# re-rank depth: max(RERANK_MIN, RERANK_MULT * k) survivors get the
# exact float64 re-rank
RERANK_MULT = 4
RERANK_MIN = 64
# calibration sample size (held-out base rows scored exactly, blocked)
CALIB_QUERIES = 64
# nprobe doubling ladder the calibration walks
NPROBE_LADDER = (4, 8, 16, 32, 64, 128, 256)
# k-means: Lloyd iterations over a seeded sample
KMEANS_ITERS = 6
KMEANS_SAMPLE_PER_LIST = 128
# assignment matmul block (rows per jitted step — bounds peak memory
# at nlist * BLOCK f32 scores)
ASSIGN_BLOCK = 1 << 18


def default_nlist(n: int) -> int:
    """Power-of-two near sqrt(n), floored so the mean list still holds
    enough rows for the coarse quantizer to pay (>= ~32/list), min 8."""
    if n <= 0:
        return 8
    target = int(math.sqrt(n))
    nlist = 1 << max(3, target.bit_length() - 1)
    while nlist * 32 > n and nlist > 8:
        nlist //= 2
    return nlist


def rerank_depth(k: int) -> int:
    return max(RERANK_MIN, RERANK_MULT * int(k))


@dataclass
class IVFIndex:
    """The trained quantized index over one base block (immutable;
    versioned by the owning cache per (base_ts, schema))."""

    dim: int
    nlist: int
    centroids: np.ndarray   # (nc, d) f32
    order: np.ndarray       # (n,) i32
    starts: np.ndarray      # (nc+1,) i64
    codes: np.ndarray       # (n, d) i8
    scales: np.ndarray      # (n,) f32
    norms2: np.ndarray      # (n,) f32
    nprobe: int             # calibrated default
    sample_recall: float    # measured recall@K_REF at `nprobe`
    target_recall: float
    seed: int

    @property
    def n_rows(self) -> int:
        return len(self.order)

    @property
    def nbytes(self) -> int:
        return (self.centroids.nbytes + self.order.nbytes
                + self.starts.nbytes + self.codes.nbytes
                + self.scales.nbytes + self.norms2.nbytes)

    def scanned_rows(self, nprobe: int | None = None) -> int:
        """Expected rows the approximate stage scores per query — the
        planner's per-row cost driver for the quantized tier."""
        p = min(self.nlist, nprobe or self.nprobe)
        return int(round(self.n_rows * p / max(1, self.nlist)))

    def describe(self) -> dict:
        return {"rows": self.n_rows, "dim": self.dim,
                "nlist": self.nlist, "nprobe": self.nprobe,
                "bytes": int(self.nbytes),
                "codeBytes": int(self.codes.nbytes),
                "sampleRecall": round(float(self.sample_recall), 4),
                "targetRecall": float(self.target_recall)}


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------


@jax.jit
def _assign_jit(block, cents, cn2):
    import jax.numpy as jnp
    # nearest centroid by squared L2: argmin ||x||^2 - 2 x.c + ||c||^2
    # (the ||x||^2 term is constant per row — dropped)
    d = jnp.dot(block, cents.T, preferred_element_type=jnp.float32)
    return jnp.argmin(cn2[None, :] - 2.0 * d, axis=1)


def _assign(vecs: np.ndarray, cents: np.ndarray) -> np.ndarray:
    """Blocked nearest-centroid assignment (jitted matmul per block)."""
    import jax.numpy as jnp

    cn2 = jnp.asarray((cents.astype(np.float64) ** 2)
                      .sum(axis=1).astype(np.float32))
    cd = jnp.asarray(cents)
    out = np.empty(len(vecs), np.int32)
    for s in range(0, len(vecs), ASSIGN_BLOCK):
        blk = jnp.asarray(vecs[s:s + ASSIGN_BLOCK])
        out[s:s + ASSIGN_BLOCK] = np.asarray(
            _assign_jit(blk, cd, cn2), np.int32)
    return out


def _kmeans(vecs: np.ndarray, nlist: int, seed: int,
            iters: int = KMEANS_ITERS) -> np.ndarray:
    """Seeded Lloyd's over a deterministic sample; float64 mean
    accumulation (np.add.at) keeps the result order-independent."""
    n, d = vecs.shape
    rng = np.random.default_rng(seed)
    sample_n = min(n, KMEANS_SAMPLE_PER_LIST * nlist)
    sample = vecs if sample_n == n else \
        vecs[np.sort(rng.choice(n, sample_n, replace=False))]
    init = rng.choice(len(sample), nlist, replace=False)
    cents = sample[np.sort(init)].astype(np.float32).copy()
    for _ in range(iters):
        a = _assign(sample, cents)
        sums = np.zeros((nlist, d), np.float64)
        np.add.at(sums, a, sample.astype(np.float64))
        counts = np.bincount(a, minlength=nlist).astype(np.float64)
        nonempty = counts > 0
        cents[nonempty] = (sums[nonempty]
                           / counts[nonempty, None]).astype(np.float32)
        # empty clusters keep their previous center (deterministic)
    return cents


def exact_topk_blocked(vecs: np.ndarray, queries: np.ndarray, k: int,
                       metric: str = "dot",
                       block: int = 1 << 20) -> np.ndarray:
    """Exact top-k indices over an (n, d) block without materializing
    the full (q, n) score matrix — the calibration oracle at 10M+
    rows (f32 accumulate; ties break low-index like every tier).
    Supports dot and cosine (euclidean orders like dot for the
    calibration's near-duplicate queries only — not offered)."""
    if metric not in ("dot", "cosine"):
        raise ValueError(f"unsupported blocked metric {metric!r}")
    q = np.atleast_2d(np.asarray(queries, np.float32))
    nq, n = len(q), len(vecs)
    k = min(k, n)
    qn = np.linalg.norm(q, axis=1).astype(np.float32) \
        if metric == "cosine" else None
    best_s = np.full((nq, k), -np.inf, np.float32)
    best_i = np.zeros((nq, k), np.int64)
    for s in range(0, n, block):
        sc = q @ vecs[s:s + block].T
        if metric == "cosine":
            bn = np.linalg.norm(vecs[s:s + block], axis=1) \
                .astype(np.float32)
            denom = np.outer(qn, bn)
            sc = np.divide(sc, denom, out=np.zeros_like(sc),
                           where=denom > 0)
        cat_s = np.concatenate([best_s, sc], axis=1)
        cat_i = np.concatenate(
            [best_i, np.arange(s, s + sc.shape[1], dtype=np.int64)
             [None, :].repeat(nq, 0)], axis=1)
        part = np.argpartition(-cat_s, k - 1, axis=1)[:, :k]
        ps = np.take_along_axis(cat_s, part, axis=1)
        pi = np.take_along_axis(cat_i, part, axis=1)
        ordr = np.lexsort((pi, -ps), axis=1)
        best_s = np.take_along_axis(ps, ordr, axis=1)
        best_i = np.take_along_axis(pi, ordr, axis=1)
    return best_i


def build(vecs: np.ndarray, *, nlist: int | None = None, seed: int = 0,
          target_recall: float = TARGET_RECALL,
          calibrate: bool = True) -> IVFIndex:
    """Train the quantized index over one clean base block. The block
    is the float32 (n, d) array the exact tiers already score; the
    index adds ~d+9 bytes/row (int8 codes + scale/norm/order) and the
    (nc, d) codebook."""
    vecs = np.ascontiguousarray(vecs, np.float32)
    n, d = vecs.shape
    if n == 0 or d == 0:
        raise ValueError("cannot build an IVF index over an empty block")
    nlist = int(nlist) if nlist else default_nlist(n)
    nlist = max(1, min(nlist, n))
    cents = _kmeans(vecs, nlist, seed)
    assign = _assign(vecs, cents)
    # cluster-order rows: stable sort by (centroid, row) so every list
    # is one contiguous slice and the layout is deterministic
    order = np.argsort(assign, kind="stable").astype(np.int32)
    counts = np.bincount(assign, minlength=nlist)
    starts = np.zeros(nlist + 1, np.int64)
    np.cumsum(counts, out=starts[1:])
    # residual quantization runs BLOCKWISE: a full clustered copy +
    # float64 norm temp would cost ~5x the corpus bytes transient,
    # which OOMs exactly at the 10-100M regime this tier targets
    codes = np.empty((n, d), np.int8)
    scales = np.empty(n, np.float32)
    norms2 = np.empty(n, np.float32)
    for s in range(0, n, ASSIGN_BLOCK):
        e = min(n, s + ASSIGN_BLOCK)
        blk = vecs[order[s:e]]
        norms2[s:e] = np.einsum("ij,ij->i", blk, blk,
                                dtype=np.float64).astype(np.float32)
        resid = blk - cents[assign[order[s:e]]]
        sc = (np.abs(resid).max(axis=1) / 127.0).astype(np.float32)
        sc = np.where(sc > 0, sc, np.float32(1.0))
        scales[s:e] = sc
        codes[s:e] = np.rint(resid / sc[:, None]).astype(np.int8)
    ivf = IVFIndex(dim=d, nlist=nlist, centroids=cents, order=order,
                   starts=starts, codes=codes, scales=scales,
                   norms2=norms2, nprobe=min(nlist, NPROBE_LADDER[0]),
                   sample_recall=0.0, target_recall=float(target_recall),
                   seed=int(seed))
    if calibrate and n > K_REF:
        _calibrate(ivf, vecs, seed)
    inc_counter("vector_index_builds_total")
    return ivf


def _calibrate(ivf: IVFIndex, vecs: np.ndarray, seed: int) -> None:
    """Pick the smallest ladder nprobe whose measured recall@K_REF on
    a seeded sample of base rows clears the target; record what was
    achieved so EXPLAIN/tabstats can surface the real budget.
    Calibration runs the DEFAULT serving metric (cosine): on
    heterogeneous-norm data the dot ordering can diverge from the
    cosine one, and a dot-calibrated nprobe would overstate the
    served recall. The sample queries ARE base rows, so each query's
    own row — a guaranteed top-1 hit dead-center its probed list —
    is EXCLUDED from both the oracle and the probe sets: counting it
    would bias recall high and let the calibrated nprobe undershoot
    on real (out-of-corpus) queries."""
    n = len(vecs)
    rng = np.random.default_rng(seed + 1)
    nq = min(CALIB_QUERIES, n)
    rows = np.sort(rng.choice(n, nq, replace=False))
    queries = vecs[rows]
    want = exact_topk_blocked(vecs, queries, K_REF + 1,
                              metric="cosine")
    # rank-ordered true neighbors, self excluded, at most K_REF each
    want_sets = [set([g for g in want[i].tolist()
                      if g != int(rows[i])][:K_REF])
                 for i in range(nq)]
    total = sum(len(s) for s in want_sets)
    best = (ivf.nprobe, 0.0)
    for p in NPROBE_LADDER:
        p = min(p, ivf.nlist)
        idx, _ = search(ivf, vecs, queries, K_REF + 1, "cosine",
                        nprobe=p, count=False)
        hits = 0
        for i in range(nq):
            got = [g for g in idx[i].tolist()
                   if g >= 0 and g != int(rows[i])][:len(want_sets[i])]
            hits += len(set(got) & want_sets[i])
        rec = hits / float(total) if total else 1.0
        if rec > best[1]:
            best = (p, rec)
        if rec >= ivf.target_recall or p >= ivf.nlist:
            best = (p, rec)
            break
    ivf.nprobe, ivf.sample_recall = int(best[0]), float(best[1])


# ---------------------------------------------------------------------------
# search
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("nprobe", "metric"))
def _probe_jit(queries, cents, nprobe, metric):
    """Coarse stage: one (q, d) x (d, nc) MXU matmul -> top-nprobe
    list ids per query. The ranking is METRIC-SHAPED:

      euclidean/dot  negated squared distance 2 q.c - ||c||^2 (the
                     ||q||^2 term is per-query constant) — the
                     geometry the k-means partition was built in; a
                     raw dot ranking would favor large-norm centroids
                     over NEAR ones and collapse low-nprobe recall.
      cosine         angular, q.c / ||c|| — scale-INVARIANT in the
                     query, exactly like the metric itself: the
                     euclidean ranking depends on ||q||, so the same
                     direction at a different magnitude would probe
                     different lists and silently fall below the
                     calibrated recall budget.

    The raw dot scores still return: the approximate candidate score
    reconstructs q.x = q.centroid + q.residual from them."""
    import jax.numpy as jnp
    cs = jnp.dot(queries, cents.T, preferred_element_type=jnp.float32)
    cn2 = jnp.sum(cents * cents, axis=1)
    if metric == "cosine":
        rank = cs / jnp.sqrt(jnp.maximum(cn2, 1e-30))[None, :]
    else:
        rank = 2.0 * cs - cn2[None, :]
    _, lists = jax.lax.top_k(rank, nprobe)
    return cs, lists


def _approx_scores_host(ivf: IVFIndex, lists: np.ndarray,
                        cs: np.ndarray, q: np.ndarray,
                        lo: int = 0, hi: int | None = None
                        ) -> tuple[list, list]:
    """Approximate residual-dot scores of every probed candidate,
    grouped by LIST instead of by query: a batch's queries share
    probed lists, so each list's int8 block dequantizes ONCE and
    scores all m sharing queries in one (len, d) x (d, m) sgemm —
    convert bandwidth bounded by the probed fraction of `codes` per
    call, never per query. No row gather happens at all: a probed
    list is one contiguous slice of the clustered layout.

    [lo, hi) restricts scoring to a clustered-slot range (the
    sharded tier's per-shard partition, parallel/dist_knn) — the
    intersection with a list's slice is plain arithmetic.

    Returns per-query (slot-id arrays, approx-dot arrays) parallel
    lists, concat order = (list id, slot) — deterministic."""
    nq, p = lists.shape
    if hi is None:
        hi = ivf.n_rows
    by_list: dict[int, list[int]] = {}
    for qi in range(nq):
        for li in lists[qi]:
            by_list.setdefault(int(li), []).append(qi)
    slot_parts: list[list[np.ndarray]] = [[] for _ in range(nq)]
    dot_parts: list[list[np.ndarray]] = [[] for _ in range(nq)]
    for li in sorted(by_list):
        s = max(lo, int(ivf.starts[li]))
        e = min(hi, int(ivf.starts[li + 1]))
        if e <= s:
            continue
        qis = by_list[li]
        block = ivf.codes[s:e].astype(np.float32)       # dequant once
        dots = block @ q[qis].T                         # (len, m)
        dots *= ivf.scales[s:e, None]
        slots = np.arange(s, e, dtype=np.int64)
        for col, qi in enumerate(qis):
            slot_parts[qi].append(slots)
            # + q . centroid term: approx q.x = q.c + q.residual
            dot_parts[qi].append(dots[:, col] + cs[qi, li])
    return ([np.concatenate(sp) if sp else np.empty(0, np.int64)
             for sp in slot_parts],
            [np.concatenate(dp) if dp else np.empty(0, np.float32)
             for dp in dot_parts])


def _cut_top_r(slots: np.ndarray, approx: np.ndarray, r: int
               ) -> tuple[np.ndarray, np.ndarray]:
    """Deterministic top-r truncation by (-approx, slot): every slot
    strictly above the boundary value survives, boundary ties fill by
    LOWEST slot id. O(R) via argpartition — a plain argpartition cut
    would keep an arbitrary tied subset, and the sharded merge
    (parallel/dist_knn) must reproduce this set exactly for its
    parity-by-construction claim to hold on duplicate-vector data."""
    if len(slots) <= r:
        return slots, approx
    part = np.argpartition(-approx, r - 1)[:r]
    v = approx[part].min()
    above = approx > v
    need = r - int(above.sum())
    at_v = approx == v
    tie_keep = at_v & np.isin(slots, np.sort(slots[at_v])[:need])
    keep = above | tie_keep
    return slots[keep], approx[keep]


def _filter_cut(ivf: IVFIndex, slots: np.ndarray, adot: np.ndarray,
                keep_b: np.ndarray | None, qn2: float, metric: str,
                r_depth: int) -> tuple[np.ndarray, np.ndarray]:
    """Per-query tail of the approximate stage — keep-mask, metric
    transform, deterministic (-approx, slot) cut — shared by
    ops/ivf.search and the sharded path (parallel/dist_knn), whose
    parity-by-construction claim depends on this being ONE
    implementation. `keep_b` is the UNPERMUTED base-row mask; it is
    gathered at the probed slots only (O(scanned)) — permuting the
    full mask per query would put an O(n) floor under the sub-linear
    scan the tier exists for."""
    if not len(slots):
        return slots, adot.astype(np.float64)
    if keep_b is not None:
        m = keep_b[ivf.order[slots]]
        slots, adot = slots[m], adot[m]
        if not len(slots):
            return slots, adot.astype(np.float64)
    approx = _metric_transform(ivf, slots, adot, qn2, metric)
    return _cut_top_r(slots, approx, r_depth)


def _rerank_one(ivf: IVFIndex, vecs: np.ndarray, slots: np.ndarray,
                q1: np.ndarray, k: int, metric: str
                ) -> tuple[np.ndarray, np.ndarray]:
    """Exact float64 re-rank of one query's surviving slots ->
    (base rows, scores), shared by the single-device and sharded
    paths. The unique() sort makes subset order == base-row order,
    so topk_host's (-score, subset idx) tiebreak IS (-score, row)."""
    rows = np.unique(ivf.order[slots].astype(np.int64))
    idx, sc = knn.topk_host(vecs[rows], q1[None], k, metric)
    return rows[idx[0]], sc[0]


def _metric_transform(ivf: IVFIndex, slots: np.ndarray,
                      adot: np.ndarray, qn2: float,
                      metric: str) -> np.ndarray:
    """Approximate metric score from the approximate dot + the stored
    EXACT row norms (only the dot term carries quantization error)."""
    if metric == "dot":
        return adot
    n2 = ivf.norms2[slots]
    if metric == "cosine":
        denom = math.sqrt(qn2) * np.sqrt(n2)
        return np.where(denom > 0, adot / np.where(denom > 0, denom, 1),
                        0.0)
    return -(qn2 - 2.0 * adot + n2)  # euclidean, higher = closer


def search(ivf: IVFIndex, vecs: np.ndarray, queries: np.ndarray,
           k: int, metric: str = "cosine",
           keep: np.ndarray | None = None,
           nprobe: int | None = None, rerank: int | None = None,
           count: bool = True) -> tuple[np.ndarray, np.ndarray]:
    """Quantized top-k: IVF probe -> int8 approximate scores ->
    exact float64 re-rank of the top `rerank` survivors. Returns
    (idx (q, k'), scores (q, k')) with idx into the BASE block row
    axis; the re-rank runs knn.topk_host (float64, same formula as
    the host-exact tier) on the original vectors, so a surviving row
    carries the exact score up to BLAS summation order and the
    (-score, idx) tiebreak order matches every tier.

    `keep` masks base rows out (MVCC overlay-touched rows, candidate
    filters); masked rows never reach the re-rank."""
    import jax.numpy as jnp

    if metric not in knn.METRICS:
        raise ValueError(f"unknown metric {metric!r}")
    q = np.atleast_2d(np.asarray(queries, np.float32))
    nq = len(q)
    p = min(ivf.nlist, int(nprobe or ivf.nprobe))
    r_depth = int(rerank or rerank_depth(k))
    cs, lists = _probe_jit(jnp.asarray(q), jnp.asarray(ivf.centroids),
                           p, str(metric))
    cs = np.asarray(cs)
    lists = np.asarray(lists, np.int64)
    slot_l, dot_l = _approx_scores_host(ivf, lists, cs, q)
    keep_b = np.asarray(keep, bool) if keep is not None else None
    qn2 = (q.astype(np.float64) ** 2).sum(axis=1)
    out_i = np.full((nq, k), -1, np.int64)
    out_s = np.full((nq, k), -np.inf, np.float64)
    width = 0
    for qi in range(nq):
        slots, _ = _filter_cut(ivf, slot_l[qi], dot_l[qi], keep_b,
                               float(qn2[qi]), metric, r_depth)
        if not len(slots):
            continue
        rws, sc = _rerank_one(ivf, vecs, slots, q[qi], k, metric)
        w = len(rws)
        out_i[qi, :w] = rws
        out_s[qi, :w] = sc
        width = max(width, w)
    if count:
        # count=False keeps build-time calibration's ladder walks out
        # of the serving-rate series
        inc_counter("vector_quantized_searches_total")
    return out_i[:, :width], out_s[:, :width]
