"""Tiled merge-path intersect (ref algo/uidlist.go:137-287 — the
reference's hottest set-algebra loop; SURVEY §2a item 2).

The fused co-sort in ops/uidvec.py pays one O((n+m)·log²(n+m))
bitonic sort of the concatenated operands. The classic merge-path
decomposition cuts the log² factor: partition the MERGE DIAGONAL into
T equal slabs of K steps, binary-search the slab boundaries (T·log n
scalar work — tiny), then co-sort each slab independently at width
~2K (log²(2K) stages instead of log²(n+m)).

Design notes, measured on v5e (full numbers in BASELINE.md §round-5):

* Diagonal partitioning (not per-a-tile windows): each slab covers
  EXACTLY K merge steps, so the a-window and b-window are each ≤ K by
  construction — no data skew can overflow a window, and the spike's
  per-a-tile variant measured 100% window overflow on the uniform
  bench configs at 2x slack (not just adversarial skew).
* jnp.searchsorted is unusable for the boundaries (its scan lowering
  measured 0.09 GB/s-equivalent); the partition search here is a
  hand-unrolled vectorized binary search: ~21 rounds of two T-element
  gathers.
* Compaction (per-slab hits back to one sorted padded vector) pays a
  global single-operand sort; with hits ≤ K/hit_frac per slab the hit
  matrix is pre-sliced before that sort, with a per-slab count check
  raising the overflow flag (caller re-dispatches at hit_frac=1).

MEASURED VERDICT (v5e, bench_micro configs): correct on every config
(0 overflow, 0 wrong) but 0.10-0.18 GB/s vs the fused co-sort's
0.63-1.71 — 6-30x SLOWER — while the bare batched row-sort at slab
width runs 3.7-10.9 GB/s. The log²(n+m)→log²(2K) saving is real, but
merge-path's prerequisite is cheap data-dependent gather (partition
probes + window gathers touch n+m elements at arbitrary offsets),
and TPU has no per-lane gather hardware: XLA serializes those
gathers, the same wall the round-4 binary-probe experiment measured
at 0.09 GB/s. The engine therefore keeps uidvec.intersect (co-sort)
on the hot path; this module stays as the measured spike closing
SURVEY §2a item 2's "try a Pallas/tiled merge-path" question with
data rather than conjecture.

Output contract matches uidvec.intersect: ascending, SENTINEL-padded,
static length len(a).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from .uidvec import SENTINEL


def _partition(a: jax.Array, b: jax.Array, diag: jax.Array
               ) -> jax.Array:
    """Stable-merge split points: for each diagonal d in `diag`,
    the smallest x with a[x] > b[d-x-1] (a-before-equal-b order),
    clamped to [max(0, d-m), min(d, n)]. Vectorized binary search,
    statically unrolled to ceil(log2(n+1)) rounds."""
    n, m = a.shape[0], b.shape[0]
    lo = jnp.maximum(diag - m, 0)
    hi = jnp.minimum(diag, n)
    steps = max(1, int(np.ceil(np.log2(n + 1))) + 1)
    for _ in range(steps):
        mid = (lo + hi) >> 1
        av = a[jnp.clip(mid, 0, n - 1)]
        bi = diag - mid - 1
        bv = b[jnp.clip(bi, 0, m - 1)]
        # P(mid): a[mid] > b[d-mid-1], with out-of-range semantics
        # b[<0] = -inf (P true), a[>=n] = +inf handled by clamp range
        p = av > bv
        p = jnp.where(bi < 0, True, p)
        p = jnp.where(bi >= m, False, p)
        p = jnp.where(mid >= n, True, p)
        take_hi = p  # x* <= mid
        hi = jnp.where(take_hi, mid, hi)
        lo = jnp.where(take_hi, lo, mid + 1)
    return lo


def mergepath_hits(a: jax.Array, b: jax.Array, k: int = 1024
                   ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Per-slab sorted hit values.

    Returns (hitmat (T, K) of hit values left-compacted ascending per
    slab with SENTINEL padding, per-slab hit counts (T,), total real
    element count) — the building block mergepath_intersect compacts.
    """
    n, m = a.shape[0], b.shape[0]
    t = -(-(n + m) // k)  # ceil
    diag = jnp.minimum(jnp.arange(1, t + 1, dtype=jnp.int32) * k, n + m)
    xs = _partition(a, b, diag)  # (t,) split at each slab END
    a_end = xs
    a_beg = jnp.concatenate([jnp.zeros(1, jnp.int32), xs[:-1]])
    b_end = diag - a_end
    b_beg = jnp.concatenate([jnp.zeros(1, jnp.int32), b_end[:-1]])

    pos = jnp.arange(k, dtype=jnp.int32)[None, :]  # (1, K)
    ai = a_beg[:, None] + pos
    aw = jnp.where((pos < (a_end - a_beg)[:, None]) & (ai < n),
                   a[jnp.clip(ai, 0, n - 1)], SENTINEL)
    # +1 trailing b element per slab: a slab's LAST a value may equal
    # the FIRST b value of the next slab (stable split allows
    # a[x-1] == b[d-x]); b values are unique so the extra slot can't
    # double-count
    posb = jnp.arange(k + 1, dtype=jnp.int32)[None, :]
    bi = b_beg[:, None] + posb
    bw = jnp.where((posb < (b_end - b_beg)[:, None] + 1) & (bi < m),
                   b[jnp.clip(bi, 0, m - 1)], SENTINEL)

    c = jnp.concatenate([aw, bw], axis=1)          # (t, 2K+1)
    flag = jnp.concatenate(
        [jnp.ones(aw.shape, jnp.uint32), jnp.zeros(bw.shape, jnp.uint32)],
        axis=1)
    cs, fs = jax.lax.sort((c, flag), dimension=1, num_keys=1)
    pad = jnp.full((t, 1), SENTINEL, cs.dtype)
    one = jnp.ones((t, 1), jnp.uint32)
    nxt = jnp.concatenate([cs[:, 1:], pad], axis=1)
    fnx = jnp.concatenate([fs[:, 1:], one], axis=1)
    prv = jnp.concatenate([pad, cs[:, :-1]], axis=1)
    fpv = jnp.concatenate([one, fs[:, :-1]], axis=1)
    hit = (((nxt == cs) & (fnx == 0)) | ((prv == cs) & (fpv == 0))) \
        & (fs == 1) & (cs != SENTINEL)
    vals = jnp.where(hit, cs, SENTINEL)
    # left-compact each slab's hits (ascending; sentinels sort last)
    vals = jnp.sort(vals, axis=1)[:, :k]  # ≤ K hits per slab
    counts = jnp.sum(vals != SENTINEL, axis=1, dtype=jnp.int32)
    return vals, counts, jnp.int32(n)


def mergepath_intersect(a: jax.Array, b: jax.Array, k: int = 1024,
                        hit_frac: int = 4
                        ) -> tuple[jax.Array, jax.Array]:
    """Sorted-set intersection via diagonal merge-path.

    Returns (result padded to len(a), hit_overflow flag). The sparse
    compaction keeps K//hit_frac hit slots per slab before the global
    compaction sort — the dominant cost of the whole pipeline — so a
    slab with more hits than that OVERFLOWS: the flag turns True and
    the result DROPS the excess (invalid). Callers re-dispatch with
    hit_frac=1 (always exact: a slab holds ≤ K hits by construction)
    or fall back to uidvec.intersect — mirroring the static-window +
    fallback contract the round-4 verdict asked this spike to
    measure. With hit_frac=1 the flag is always False.
    """
    n = a.shape[0]
    hitmat, counts, _ = mergepath_hits(a, b, k=k)
    h = max(8, k // max(1, hit_frac))
    overflow = jnp.any(counts > h) if h < k \
        else jnp.zeros((), bool)
    flat = jnp.sort(hitmat[:, :h].reshape(-1))
    take = min(n, flat.shape[0])
    out = flat[:take]
    if take < n:
        out = jnp.concatenate(
            [out, jnp.full((n - take,), SENTINEL, a.dtype)])
    return out, overflow
