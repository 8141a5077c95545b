"""Brute-force MIPS top-k kernels for similar_to().

Design follows the two retrieved papers (PAPERS.md):

  TPU-KNN: K Nearest Neighbor Search at Peak FLOP/s (2206.14286) —
    brute-force scoring IS a matmul, so a (q, d) x (d, n) dot runs at
    peak MXU throughput; the expensive part is not scoring but the
    top-k reduction over the n axis.

  A Faster Generalized Two-Stage Approximate Top-K (2506.04165) —
    replace the O(n log n)-ish exact top-k with: (1) partial reduce —
    split the n axis into `nb` buckets and take each bucket's top-L
    candidates with a cheap max/argmax (L small); (2) exact
    jax.lax.top_k over the nb*L surviving candidates. For a random
    corpus permutation the expected recall@k is
        E[recall] >= 1 - (k-1) / (2 * nb)          (L = 1)
    so the bucket count is chosen from the recall target and the
    kernel FALLS BACK to exact top-k whenever the corpus cannot
    sustain nb >= (k-1) / (2 * (1 - target)).

Three tiers, matching the repo's conventions:
  host    — numpy exact (float64 accumulate) for small/dirty data;
  device  — jitted scoring + two-stage/exact lax.top_k; scoring can
            route through a Pallas MXU tile kernel behind the existing
            `use_pallas` opt-in convention (ops/bitgraph.py: None
            resolves to False, callers own warmup+fallback);
  sharded — corpus rows sharded over a mesh axis via shard_map
            (parallel/dist_knn.py), per-shard top-k then a k-way merge.

Scores are "higher is better" for every metric: dot is the raw inner
product, cosine normalizes both sides, euclidean is the NEGATED
squared L2 distance (argmax order == nearest order).
"""

from __future__ import annotations

import math
from functools import lru_cache, partial

import numpy as np

import jax

METRICS = ("cosine", "dot", "euclidean")

# two-stage engages only above this corpus size — below it the exact
# top_k is already cheap and the bucket shuffle pure overhead
TWO_STAGE_MIN_ROWS = 4096
BUCKET_SIZE = 128          # n-axis bucket width (lane-aligned)
RECALL_TARGET = 0.99


def expected_loss(nb: int, k: int, l_per_bucket: int) -> float:
    """Expected fraction of the true top-k the two-stage reduce loses,
    for a random corpus order over nb buckets keeping L candidates per
    bucket (2506.04165 §3 collision analysis): item ranked i is lost
    iff its bucket already holds >= L higher-ranked items, so the
    per-item loss is ~ C(i, L)/nb^L and the mean over i < k is
    C(k, L+1) / (k * nb^L)."""
    if k <= l_per_bucket:
        return 0.0
    return math.comb(k, l_per_bucket + 1) / (k * float(nb) ** l_per_bucket)


def plan_two_stage(n: int, k: int,
                   recall: float = RECALL_TARGET) -> int:
    """Candidates-per-bucket L for the two-stage path, or 0 for exact
    fallback. Picks the smallest L in {1, 2} whose EXPECTED loss is
    under a quarter of the recall budget (4x margin so an empirical
    recall assert at `recall` holds with room to spare); corpora too
    small to bucket, or k too large for the budget, fall back to
    exact — the acceptance contract."""
    if n < TWO_STAGE_MIN_ROWS:
        return 0
    nb = n // BUCKET_SIZE
    budget = (1.0 - recall) / 4.0
    for l_per_bucket in (1, 2):
        if expected_loss(nb, k, l_per_bucket) <= budget:
            return l_per_bucket
    return 0


def can_two_stage(n: int, k: int, recall: float = RECALL_TARGET) -> bool:
    return plan_two_stage(n, k, recall) > 0


# ---------------------------------------------------------------------------
# host tier (exact, float64 accumulation)
# ---------------------------------------------------------------------------


def score_host(corpus: np.ndarray, queries: np.ndarray,
               metric: str) -> np.ndarray:
    """(n, d) x (q, d) -> (q, n) float64 scores, higher = closer."""
    c = np.asarray(corpus, np.float64)
    q = np.atleast_2d(np.asarray(queries, np.float64))
    if metric == "cosine":
        cn = np.linalg.norm(c, axis=1)
        qn = np.linalg.norm(q, axis=1)
        dots = q @ c.T
        denom = np.outer(qn, cn)
        with np.errstate(invalid="ignore", divide="ignore"):
            out = np.where(denom > 0, dots / np.where(denom > 0, denom, 1),
                           0.0)
        return out
    if metric == "dot":
        return q @ c.T
    if metric == "euclidean":
        c2 = np.sum(c * c, axis=1)
        q2 = np.sum(q * q, axis=1)
        return -(q2[:, None] - 2.0 * (q @ c.T) + c2[None, :])
    raise ValueError(f"unknown metric {metric!r}")


def _topk_rows(scores: np.ndarray, k: int
               ) -> tuple[np.ndarray, np.ndarray]:
    """Per-row exact top-k with (-score, idx) order over a (q, n)
    float matrix that may contain -inf for masked rows."""
    q, n = scores.shape
    k_eff = min(k, n)
    if k_eff == 0:
        return (np.empty((q, 0), np.int64), np.empty((q, 0), scores.dtype))
    if k_eff < n:
        part = np.argpartition(-scores, k_eff - 1, axis=1)[:, :k_eff]
    else:
        part = np.tile(np.arange(n), (q, 1))
    psc = np.take_along_axis(scores, part, axis=1)
    order = np.lexsort((part, -psc), axis=1)
    idx = np.take_along_axis(part, order, axis=1)
    sc = np.take_along_axis(psc, order, axis=1)
    return idx.astype(np.int64), sc


def topk_host(corpus: np.ndarray, queries: np.ndarray, k: int,
              metric: str = "cosine",
              mask: np.ndarray | None = None
              ) -> tuple[np.ndarray, np.ndarray]:
    """Exact top-k: (idx (q, k'), scores (q, k')) sorted by
    (-score, idx) — the deterministic tiebreak every tier shares."""
    scores = score_host(corpus, queries, metric)
    if mask is not None:
        scores = np.where(np.asarray(mask, bool)[None, :], scores, -np.inf)
    idx, sc = _topk_rows(scores, k)
    # rows are score-descending so -inf entries (masked/absent rows)
    # form a suffix per row; keep the widest per-query valid width and
    # let callers trim per query on -inf
    finite = np.isfinite(sc)
    if not finite.all():
        keep = int(finite.sum(axis=1).max(initial=0))
        idx, sc = idx[:, :keep], sc[:, :keep]
    return idx, sc


# ---------------------------------------------------------------------------
# device tier
# ---------------------------------------------------------------------------


def _score_device(corpus, queries, metric: str, use_pallas: bool,
                  pallas_interpret):
    import jax.numpy as jnp

    if use_pallas:
        from dgraph_tpu.ops.pallas_kernels import score_dot_pallas
        dots = score_dot_pallas(corpus, queries,
                                interpret=pallas_interpret)
    else:
        # HIGHEST: a TPU multiplies float32 operands in ONE bfloat16
        # pass by default, which reorders near-tied neighbours (v5e,
        # 1M x 128: 56/64 cosine and 61/64 euclidean top-10 sets
        # equal to the float64 host's). This tier is the EXACT one;
        # the approximate tiers (two-stage buckets, IVF) re-rank.
        dots = jnp.dot(queries, corpus.T,
                       preferred_element_type=jnp.float32,
                       precision=jax.lax.Precision.HIGHEST)
    if metric == "dot":
        return dots
    if metric == "cosine":
        cn = jnp.sqrt(jnp.sum(corpus * corpus, axis=1))
        qn = jnp.sqrt(jnp.sum(queries * queries, axis=1))
        denom = qn[:, None] * cn[None, :]
        return jnp.where(denom > 0, dots / jnp.where(denom > 0, denom, 1),
                         0.0)
    if metric == "euclidean":
        c2 = jnp.sum(corpus * corpus, axis=1)
        q2 = jnp.sum(queries * queries, axis=1)
        return -(q2[:, None] - 2.0 * dots + c2[None, :])
    raise ValueError(f"unknown metric {metric!r}")


@lru_cache(maxsize=64)
def _dispersal_perm(n_pad: int) -> np.ndarray:
    """Deterministic row-dispersal permutation for the two-stage
    bucketing. The recall bound assumes rows land in buckets at
    random, but the scored block is packed uid-ASCENDING — near-
    duplicate embeddings ingested under consecutive uids would share
    one bucket and break the bound. A multiplicative stride coprime
    with n_pad (golden-ratio start) sends any run of consecutive rows
    to positions `stride` apart, i.e. distinct buckets, restoring the
    TPU-KNN precondition without an RNG (stable across processes)."""
    stride = (int(0.6180339887 * n_pad) | 1) or 1
    while math.gcd(stride, n_pad) != 1:
        stride += 2
    # original row j lands at permuted slot (j * stride) % n_pad — the
    # golden stride's three-distance spreading is what disperses runs.
    # As a GATHER (slot i reads original perm[i]) that is the modular
    # inverse; perm doubles as the slot -> original index map.
    inv = pow(stride, -1, n_pad)
    return ((np.arange(n_pad, dtype=np.int64) * inv) % n_pad
            ).astype(np.int32)


def _two_stage_topk_dev(scores, k: int, l_per_bucket: int):
    """Bucketed approximate-then-exact top-k on device. scores is
    (q, n_pad) with -inf in padded/masked columns; returns (vals, idx)
    over the padded axis."""
    import jax.numpy as jnp

    qn, n_pad = scores.shape
    nb = n_pad // BUCKET_SIZE
    # disperse uid-contiguous rows across buckets (see _dispersal_perm)
    perm = jnp.asarray(_dispersal_perm(n_pad))
    scores = scores[:, perm]
    bucketed = scores.reshape(qn, nb, BUCKET_SIZE)
    # stage 1: partial reduce — top-L inside each bucket (L=1 is a
    # plain max+argmax, the TPU-KNN PartialReduce)
    if l_per_bucket == 1:
        bvals = jnp.max(bucketed, axis=2)                     # (q, nb)
        barg = jnp.argmax(bucketed, axis=2)                   # (q, nb)
        cand_vals = bvals
        cand_idx = barg + jnp.arange(nb, dtype=jnp.int32)[None, :] \
            * BUCKET_SIZE
    else:
        bvals, barg = jax.lax.top_k(bucketed, l_per_bucket)   # (q, nb, L)
        base = (jnp.arange(nb, dtype=jnp.int32) * BUCKET_SIZE)[None, :,
                                                               None]
        cand_vals = bvals.reshape(qn, nb * l_per_bucket)
        cand_idx = (barg + base).reshape(qn, nb * l_per_bucket)
    # stage 2: exact top-k over the nb*L candidates, mapped back to
    # the unpermuted row axis
    vals, pos = jax.lax.top_k(cand_vals, min(k, cand_vals.shape[1]))
    idx = jnp.take_along_axis(cand_idx, pos, axis=1)
    return vals, perm[idx]


@partial(jax.jit,
         static_argnames=("k", "metric", "two_stage", "l_per_bucket",
                          "use_pallas", "pallas_interpret", "n_real"))
def _topk_device_jit(corpus, queries, mask, k, metric, two_stage,
                     l_per_bucket, use_pallas, pallas_interpret, n_real):
    import jax.numpy as jnp

    scores = _score_device(corpus, queries, metric, use_pallas,
                           pallas_interpret)
    n_pad = scores.shape[1]
    col = jnp.arange(n_pad)
    invalid = col[None, :] >= n_real
    if mask is not None:
        invalid = invalid | ~mask[None, :]
    scores = jnp.where(invalid, -jnp.inf, scores)
    if two_stage:
        return _two_stage_topk_dev(scores, k, l_per_bucket)
    return jax.lax.top_k(scores, min(k, n_pad))


def pad_rows(corpus: np.ndarray, unit: int = BUCKET_SIZE) -> np.ndarray:
    """Zero-pad the row axis to a `unit` multiple (host-side, ONCE per
    block build) so topk_device never copies the corpus per query."""
    n, d = corpus.shape
    n_pad = max(unit, ((n + unit - 1) // unit) * unit)
    if n_pad == n:
        return corpus
    out = np.zeros((n_pad, d), np.float32)
    out[:n] = corpus
    return out


def topk_device(corpus_dev, queries: np.ndarray, k: int,
                metric: str = "cosine",
                mask: np.ndarray | None = None,
                two_stage: bool | None = None,
                l_per_bucket: int | None = None,
                use_pallas: bool | None = None,
                pallas_interpret: bool = False,
                n_real: int | None = None
                ) -> tuple[np.ndarray, np.ndarray]:
    """Device top-k over a (possibly already device-resident) corpus.
    Returns host (idx (q, k'), scores (q, k')) — idx into the corpus
    row axis; rows masked out / padded return -inf scores.

    `n_real` marks a corpus whose trailing rows are zero padding
    (pad_rows): only the first n_real rows are live. Hot-path callers
    should pre-pad their cached block so no per-query device copy
    happens here.

    two_stage=None auto-selects the bucketed approximate path when the
    corpus can hold the RECALL_TARGET bound and falls back to exact
    lax.top_k otherwise (the acceptance contract). use_pallas follows
    the repo convention: None resolves to False (ops/bitgraph.py)."""
    import jax.numpy as jnp

    corpus_dev = jnp.asarray(corpus_dev, jnp.float32)
    n_rows, d = corpus_dev.shape
    n = n_rows if n_real is None else int(n_real)
    q = jnp.atleast_2d(jnp.asarray(queries, jnp.float32))
    if use_pallas is None:
        use_pallas = False
    # pad the n axis so buckets tile exactly (and pallas tiles align —
    # SCORE_TILE_N is a multiple of BUCKET_SIZE); padding scores are
    # forced to -inf via n_real
    unit = BUCKET_SIZE
    if use_pallas:
        from dgraph_tpu.ops.pallas_kernels import SCORE_TILE_N
        unit = SCORE_TILE_N
    n_pad = max(unit, ((n_rows + unit - 1) // unit) * unit)
    if n_pad != n_rows:
        corpus_dev = jnp.concatenate(
            [corpus_dev, jnp.zeros((n_pad - n_rows, d), jnp.float32)])
    plan = plan_two_stage(n, k)
    if two_stage is None:
        two_stage = plan > 0
    elif two_stage and plan == 0:
        two_stage = False  # contract: fall back to exact when the
        #                    bucket count can't hold the recall target
    if l_per_bucket is None:
        l_per_bucket = max(plan, 1)
    mask_dev = None
    if mask is not None:
        m = np.zeros(n_pad, bool)
        m[:n] = np.asarray(mask, bool)
        mask_dev = jnp.asarray(m)
    vals, idx = _topk_device_jit(
        corpus_dev, q, mask_dev, int(k), str(metric), bool(two_stage),
        int(l_per_bucket), bool(use_pallas), bool(pallas_interpret),
        int(n))
    vals = np.asarray(vals)
    idx = np.asarray(idx, np.int64)
    # deterministic tiebreak to match the host tier: lax.top_k is
    # stable by index already (ties keep the lower index first)
    return idx, vals


# ---------------------------------------------------------------------------
# k-way merge (per-shard / base+overlay partial results)
# ---------------------------------------------------------------------------


def merge_topk(parts: list[tuple[np.ndarray, np.ndarray]], k: int
               ) -> tuple[np.ndarray, np.ndarray]:
    """Merge [(uids, scores), ...] partial top-k lists into the global
    top-k, ordered by (-score, uid) — the k-way merge after per-shard
    top-k (ref algo/uidlist.go MergeSorted role, score-ordered)."""
    parts = [(np.asarray(u, np.uint64), np.asarray(s, np.float64))
             for u, s in parts if len(np.atleast_1d(u))]
    if not parts:
        return np.empty(0, np.uint64), np.empty(0, np.float64)
    uids = np.concatenate([u for u, _ in parts])
    scores = np.concatenate([s for _, s in parts])
    ok = np.isfinite(scores)
    uids, scores = uids[ok], scores[ok]
    # a uid may appear in several parts (base block + overlay rows
    # must not — callers mask — but be safe): keep its best score
    order = np.lexsort((uids, -scores))
    uids, scores = uids[order], scores[order]
    seen = set()
    out_u, out_s = [], []
    for u, s in zip(uids.tolist(), scores.tolist()):
        if u in seen:
            continue
        seen.add(u)
        out_u.append(u)
        out_s.append(s)
        if len(out_u) == k:
            break
    return np.asarray(out_u, np.uint64), np.asarray(out_s, np.float64)
