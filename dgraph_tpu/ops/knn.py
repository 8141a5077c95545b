"""Brute-force MIPS top-k kernels for similar_to().

Design follows the two retrieved papers (PAPERS.md):

  TPU-KNN: K Nearest Neighbor Search at Peak FLOP/s (2206.14286) —
    brute-force scoring IS a matmul, so a (q, d) x (d, n) dot runs at
    peak MXU throughput; the expensive part is not scoring but the
    top-k reduction over the n axis.

  A Faster Generalized Two-Stage Approximate Top-K (2506.04165) —
    replace the O(n log n)-ish exact top-k with: (1) partial reduce —
    split the n axis into `nb` buckets and take each bucket's top-L
    candidates with a cheap max/argmax (L small); (2) an exact sort
    of the nb*L surviving candidates.

EVERY tier here is EXACT: the k rows of greatest score, ordered by
(-score, row index), and row index order is uid order. The two-stage
reduce is the device's fast path, not an approximation: its result is
PROVED on the device (one more pass over the score row counts the
rows that rank at or before the k-th result: exactly k means the
result is the top-k), and a query whose proof fails, because one
bucket held more than L of the true top-k, is answered by
`lax.top_k` over the full row in the same call. L is chosen so that
a random corpus order fails the proof less than once in a thousand
queries (`plan_two_stage`); what a failure costs is time, never an
answer.

Three tiers, matching the repo's conventions:
  host    — numpy exact (float64 accumulate) for small/dirty data;
  device  — jitted scoring + proved two-stage, or lax.top_k;
  sharded — corpus rows sharded over a mesh axis via shard_map
            (parallel/dist_knn.py), per-shard top-k then a k-way merge.

The device tier is ONE program of LANES query rows, a mask and a k
a lane (`launch_lanes` / `land_lanes`): the `similar_to` calls in
flight over one resident block ride one call of it
(query/devicecall.Rendezvous, family `similar`), and one small array
leaves the device for all of them. The static k is the call's
largest; a lane's answer is the first k_lane of it, proved at its
own k. 1..LANES riders run one compiled shape, so a lane's bits are
the same alone and in company.

Approximation is another index, asked for in the schema
(`@index(vector(ivf))`, ops/ivf.py); `@index(vector)` never
approximates, whatever the predicate's size.

Scores are "higher is better" for every metric: dot is the raw inner
product, cosine normalizes both sides, euclidean is the NEGATED
squared L2 distance (argmax order == nearest order). The device scores
in float32 (precision HIGHEST) and the host in float64: on
whole-number components up to 255 and 128 dimensions both are exact,
so the tiers agree on ties too; on other data they agree as far as
float32 tells two scores apart.
"""

from __future__ import annotations

import math
from functools import partial

import numpy as np

import jax

METRICS = ("cosine", "dot", "euclidean")

# two-stage engages only above this corpus size — below it the exact
# top_k is already cheap and the bucketing pure overhead
TWO_STAGE_MIN_ROWS = 4096
BUCKET_SIZE = 128          # rows a bucket
MAX_PER_BUCKET = 4         # each candidate kept costs a pass
# the share of queries that may fail the proof and pay for lax.top_k
# over the full row as well
FALLBACK_BUDGET = 1e-3
# score_host works through the corpus this many rows at a time
HOST_BLOCK_ROWS = 1 << 16


def fallback_probability(nb: int, k: int, l_per_bucket: int) -> float:
    """Upper bound on the share of queries whose true top-k does NOT
    fit into L candidates a bucket, for a random corpus order over nb
    buckets: some bucket must hold L+1 of the k, and each of the
    C(k, L+1) subsets shares a bucket with probability nb^-L (the
    collision analysis of 2506.04165 §3, as a union bound)."""
    if k <= l_per_bucket:
        return 0.0
    return math.comb(k, l_per_bucket + 1) / float(nb) ** l_per_bucket


def plan_two_stage(n: int, k: int,
                   budget: float = FALLBACK_BUDGET) -> int:
    """Candidates-per-bucket L for the two-stage path, or 0 for
    lax.top_k over the full row: the smallest L up to MAX_PER_BUCKET
    whose expected share of failed proofs is under `budget`. Corpora
    too small to bucket, or k too large for the bucket count, take the
    full-row path. The answer is exact either way."""
    if n < TWO_STAGE_MIN_ROWS:
        return 0
    nb = n // BUCKET_SIZE
    for l_per_bucket in range(1, MAX_PER_BUCKET + 1):
        if k <= nb * l_per_bucket and \
                fallback_probability(nb, k, l_per_bucket) <= budget:
            return l_per_bucket
    return 0


def can_two_stage(n: int, k: int) -> bool:
    return plan_two_stage(n, k) > 0


# ---------------------------------------------------------------------------
# host tier (exact, float64 accumulation)
# ---------------------------------------------------------------------------


def score_host(corpus: np.ndarray, queries: np.ndarray,
               metric: str) -> np.ndarray:
    """(n, d) x (q, d) -> (q, n) float64 scores, higher = closer.
    The corpus is widened to float64 HOST_BLOCK_ROWS rows at a time:
    the same arithmetic row for row as over the whole block, without
    a float64 copy of a million-row corpus (and another for its
    squares) on every query."""
    if metric not in METRICS:
        raise ValueError(f"unknown metric {metric!r}")
    q = np.atleast_2d(np.asarray(queries, np.float64))
    corpus = np.asarray(corpus)
    out = np.empty((len(q), len(corpus)), np.float64)
    qn = np.linalg.norm(q, axis=1)
    q2 = np.sum(q * q, axis=1)
    for lo in range(0, len(corpus), HOST_BLOCK_ROWS):
        c = np.asarray(corpus[lo:lo + HOST_BLOCK_ROWS], np.float64)
        dots = q @ c.T
        if metric == "cosine":
            denom = np.outer(qn, np.linalg.norm(c, axis=1))
            with np.errstate(invalid="ignore", divide="ignore"):
                dots = np.where(
                    denom > 0, dots / np.where(denom > 0, denom, 1), 0.0)
        elif metric == "euclidean":
            c2 = np.sum(c * c, axis=1)
            dots = -(q2[:, None] - 2.0 * dots + c2[None, :])
        out[:, lo:lo + HOST_BLOCK_ROWS] = dots
    return out


def _topk_rows(scores: np.ndarray, k: int
               ) -> tuple[np.ndarray, np.ndarray]:
    """Per-row exact top-k with (-score, idx) order over a (q, n)
    float matrix that may contain -inf for masked rows. Ties ACROSS
    the k-th place go to the lower idx too: every row that scores the
    k-th value is a candidate, not the ones a partition happened to
    leave in front."""
    q, n = scores.shape
    k_eff = min(k, n)
    idx = np.empty((q, k_eff), np.int64)
    sc = np.empty((q, k_eff), scores.dtype)
    if k_eff == 0:
        return idx, sc
    for r in range(q):
        row = scores[r]
        if k_eff < n:
            kth = np.partition(row, n - k_eff)[n - k_eff]
            cand = np.flatnonzero(row >= kth)
        else:
            cand = np.arange(n)
        cand = cand[np.lexsort((cand, -row[cand]))[:k_eff]]
        idx[r], sc[r] = cand, row[cand]
    return idx, sc


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


def _score_device(corpus, queries, metric: str):
    import jax.numpy as jnp

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


def _two_stage_topk_dev(scores, ks, k: int, l_per_bucket: int):
    """Bucketed top-k on device, proved exact a lane. scores is
    (q, n_pad) with -inf in padded/masked columns, ks int32[q] the
    lanes' own k (each at most the static `k`, the call's largest);
    -> (vals, idx, proved[q]): the k best of the bucket candidates by
    (-score, row), and for every lane whether the first ks[lane] of
    them ARE the top-ks[lane] of its whole row.

    Bucket j holds rows j, j + nb, j + 2 nb, ...: a reshape, no
    gather, and rows ingested under consecutive uids (near-duplicate
    embeddings) land in different buckets."""
    import jax.numpy as jnp

    qn, n_pad = scores.shape
    nb = n_pad // BUCKET_SIZE
    left = scores.reshape(qn, BUCKET_SIZE, nb)
    depth = jnp.arange(BUCKET_SIZE, dtype=jnp.int32)[None, :, None]
    lane = jnp.arange(nb, dtype=jnp.int32)[None, :]
    cand_vals, cand_idx = [], []
    # stage 1: partial reduce — the L best of each bucket, one max and
    # argmax a candidate (the TPU-KNN PartialReduce). argmax takes the
    # first of equal scores: the lowest row of the bucket
    for i in range(l_per_bucket):
        best = jnp.argmax(left, axis=1).astype(jnp.int32)     # (q, nb)
        cand_vals.append(jnp.max(left, axis=1))
        cand_idx.append(best * nb + lane)
        if i + 1 < l_per_bucket:
            left = jnp.where(depth == best[:, None, :], -jnp.inf, left)
    # stage 2: the candidates in (-score, row) order
    neg, idx = jax.lax.sort(
        (-jnp.concatenate(cand_vals, axis=1),
         jnp.concatenate(cand_idx, axis=1)), dimension=1, num_keys=2)
    vals, idx = -neg[:, :k], idx[:, :k]
    # the proof, a lane at its OWN k (a category of 50 rows asked for
    # 10 is not failed by a k of 100 riding along): the lane's k-th
    # result is a live row (finite, so all k are: k distinct rows,
    # each with its own score) and exactly k rows of the whole row
    # rank at or before it in (-score, row) order. With fewer than k
    # live rows the k-th value is -inf (an exhausted bucket emits
    # (-inf, lane), lane live or not) and nothing is proved: the full
    # row answers. A dead lane (k 0) asks nothing and is proved
    kth = (jnp.maximum(ks, 1) - 1)[:, None]
    v_k = jnp.take_along_axis(vals, kth, axis=1)
    i_k = jnp.take_along_axis(idx, kth, axis=1)
    col = jnp.arange(n_pad, dtype=jnp.int32)[None, :]
    ahead = jnp.sum((scores > v_k) | ((scores == v_k) & (col <= i_k)),
                    axis=1)
    return vals, idx, (ks == 0) | (
        (ahead == ks) & jnp.isfinite(v_k[:, 0]))


@partial(jax.jit,
         static_argnames=("k", "metric", "two_stage", "l_per_bucket",
                          "n_real"))
def _topk_device_jit(corpus, queries, masks, ks, k, metric, two_stage,
                     l_per_bucket, n_real):
    """The lanes program: a query row, a mask (bool over the padded
    rows, True = the row may answer) and a k (`ks`, dynamic, at most
    the static `k`) a lane -> int32[lanes, 2 k + 1], a lane's row its
    exact top-k by (-score, row) (lax.top_k keeps the lower index
    first among equal scores): k row indices, the k scores' bits,
    and whether a live lane's failed two-stage proof sent the call to
    the full row. A lane's answer is the first ks[lane] of its k; its
    bits depend on nothing but its own row, mask and k."""
    import jax.numpy as jnp

    scores = _score_device(corpus, queries, metric)
    n_pad = scores.shape[1]
    live = jnp.stack(masks) & (jnp.arange(n_pad) < n_real)[None, :]
    scores = jnp.where(live, scores, -jnp.inf)
    k = min(k, n_pad)
    if not two_stage:
        vals, idx = jax.lax.top_k(scores, k)
        fell_back = jnp.bool_(False)
    else:
        vals, idx, proved = _two_stage_topk_dev(scores, ks, k,
                                                l_per_bucket)
        fell_back = ~jnp.all(proved)
        vals, idx = jax.lax.cond(
            fell_back, lambda: tuple(jax.lax.top_k(scores, k)),
            lambda: (vals, idx))
    # ONE small array leaves the device for the call's riders
    return jnp.concatenate(
        [idx.astype(jnp.int32),
         jax.lax.bitcast_convert_type(vals, jnp.int32),
         jnp.broadcast_to(fell_back.astype(jnp.int32),
                          (len(queries), 1))], axis=1)


# what a device profile calls the one program this module dispatches
DEVICE_PROGRAM = "jit_" + _topk_device_jit.__name__
# query rows a call of it carries (a constant of the program, as
# bitgraph.LANES is of the traversal's: not a knob)
LANES = 8


def padded_rows(n: int) -> int:
    """Rows of a block of n once padded to a BUCKET_SIZE multiple."""
    return max(BUCKET_SIZE, -(-n // BUCKET_SIZE) * BUCKET_SIZE)


def pad_rows(corpus: np.ndarray) -> np.ndarray:
    """Zero-pad the row axis to a BUCKET_SIZE multiple (host-side, ONCE
    per block build) so topk_device never copies the corpus per query."""
    n, d = corpus.shape
    n_pad = padded_rows(n)
    if n_pad == n:
        return corpus
    out = np.zeros((n_pad, d), np.float32)
    out[:n] = corpus
    return out


def candidate_mask(row_uids: np.ndarray, candidates: np.ndarray,
                   n_pad: int | None = None) -> np.ndarray:
    """Bool mask over a block's rows (`row_uids`, sorted, one a row):
    which rows' uids are among `candidates`, False in the padding up
    to `n_pad`. The candidates are looked up in the row map, the
    smaller side into the larger (O(c log n), not the O(n log c) of
    testing every row), and scattered; duplicates and candidates the
    block does not hold change nothing."""
    n = len(row_uids)
    mask = np.zeros(n if n_pad is None else n_pad, bool)
    if n and len(candidates):
        pos = np.searchsorted(row_uids, candidates)
        pos[pos == n] = n - 1
        mask[pos[row_uids[pos] == candidates]] = True
    return mask


def _padded_mask(mask, n_pad: int):
    """A lane's mask as the program's operand: a device array as it
    is (a resident over the padded rows), a host array over the live
    or the padded rows as bool over the padded rows."""
    if isinstance(mask, jax.Array):
        return mask
    mask = np.asarray(mask, bool)
    if len(mask) == n_pad:
        return mask
    out = np.zeros(n_pad, bool)
    out[:len(mask)] = mask
    return out


def _plan(n: int, n_pad: int, k: int, two_stage: bool | None,
          l_per_bucket: int | None) -> tuple[bool, int]:
    """(two_stage, L) of a call whose largest k is `k`:
    two_stage=None takes the proved two-stage reduce where
    plan_two_stage finds an L for it and lax.top_k over the full row
    otherwise; two_stage=True with an explicit l_per_bucket forces the
    reduce at that L (a test's way to a failing proof)."""
    plan = plan_two_stage(n, k)
    forced = bool(two_stage) and l_per_bucket is not None \
        and k <= (n_pad // BUCKET_SIZE) * l_per_bucket
    if two_stage is None:
        two_stage = plan > 0
    elif two_stage and plan == 0 and not forced:
        two_stage = False  # too few buckets for this k: the full row
    if l_per_bucket is None:
        l_per_bucket = max(plan, 1)
    return bool(two_stage), int(l_per_bucket)


def launch_lanes(block, live, lanes: list, metric: str, n_real: int,
                 two_stage: bool | None = None,
                 l_per_bucket: int | None = None):
    """ONE call of the lanes program over `block` (float32, on the
    device, rows padded by pad_rows; the first `n_real` are live) for
    `lanes`, 1 to LANES of (query vector, k, mask), not waited for;
    -> the call's one result, its copy to the host begun. A lane's
    mask is None for every live row (it takes `live`, the block's
    all-True resident), a host array over the live or the padded rows
    (uploaded with the call), or a device array over the padded rows
    (a resident of engine/device_cache.store_similar_mask: nothing is
    uploaded): host or device, the same operand. Empty lanes are dead
    (k 0), so 1..LANES riders run ONE compiled shape a largest k, and
    a query is scored by the same program alone and in company."""
    if not 0 < len(lanes) <= LANES:
        raise ValueError(f"{len(lanes)} riders for {LANES} lanes")
    n_pad, d = block.shape
    q = np.zeros((LANES, d), np.float32)
    ks = np.zeros(LANES, np.int32)
    masks = [live] * LANES
    for i, (qvec, k, mask) in enumerate(lanes):
        q[i], ks[i] = qvec, min(int(k), n_pad)
        if mask is not None:
            masks[i] = _padded_mask(mask, n_pad)
    k = int(ks.max())
    two_stage, l_per_bucket = _plan(int(n_real), n_pad, k, two_stage,
                                    l_per_bucket)
    # host arrays ride the jitted call: no upload (and no program) of
    # their own, each one more turn in the interpreter
    out = _topk_device_jit(block, q, tuple(masks), ks, k, str(metric),
                           two_stage, l_per_bucket, int(n_real))
    # the one small result starts for the host as soon as the device
    # has it, not a round trip after somebody asks
    out.copy_to_host_async()
    return out


def land_lanes(handle) -> tuple[np.ndarray, np.ndarray, bool]:
    """launch_lanes' result, once the device has it: (idx int64
    [LANES, k], scores float32 [LANES, k], fell_back) for the call's
    largest k; lane i's answer is the first k_i of row i (rows masked
    out / padded carry -inf scores). ONE transfer."""
    out = np.asarray(handle)
    k = (out.shape[1] - 1) // 2
    return (out[:, :k].astype(np.int64),
            out[:, k:2 * k].view(np.float32), bool(out[0, -1]))


def topk_device(corpus_dev, queries: np.ndarray, k: int,
                metric: str = "cosine",
                mask=None,
                two_stage: bool | None = None,
                l_per_bucket: int | None = None,
                n_real: int | None = None,
                info: dict | None = None
                ) -> tuple[np.ndarray, np.ndarray]:
    """Exact device top-k over a (possibly already device-resident)
    corpus, the one-shot entry over the lanes program: the query
    matrix goes LANES rows a call. Returns host (idx (q, k'),
    scores (q, k')) ordered by (-score, row) like topk_host — idx
    into the corpus row axis; rows masked out / padded return -inf
    scores.

    `n_real` marks a corpus whose trailing rows are zero padding
    (pad_rows): only the first n_real rows are live. `mask` (bool,
    True = the row may answer; one for every query), `two_stage` and
    `l_per_bucket` as launch_lanes takes them; `info` receives
    `exact_fallback` (a two-stage proof failed and the full row was
    searched as well)."""
    import jax.numpy as jnp

    corpus_dev = jnp.asarray(corpus_dev, jnp.float32)
    n_rows, d = corpus_dev.shape
    n = n_rows if n_real is None else int(n_real)
    q = np.atleast_2d(np.asarray(queries, np.float32))
    # pad the n axis so buckets tile exactly; padding scores are
    # forced to -inf via n_real
    n_pad = padded_rows(n_rows)
    if n_pad != n_rows:
        corpus_dev = jnp.concatenate(
            [corpus_dev, jnp.zeros((n_pad - n_rows, d), jnp.float32)])
    # one upload for all the calls: every lane takes the one mask,
    # as a lane without one takes the block's all-live resident
    mask = jax.device_put(_padded_mask(
        np.ones(n_pad, bool) if mask is None else mask, n_pad))
    calls = [launch_lanes(corpus_dev, mask,
                          [(row, k, None) for row in q[lo:lo + LANES]],
                          metric, n, two_stage, l_per_bucket)
             for lo in range(0, len(q), LANES)]
    idx, vals, fell_back = zip(*(land_lanes(c) for c in calls))
    if info is not None:
        info["exact_fallback"] = any(fell_back)
    return np.concatenate(idx)[:len(q)], np.concatenate(vals)[:len(q)]


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
