"""Sorted-UID vector kernels — the TPU equivalent of the reference's
``algo/uidlist.go`` (IntersectWith/IntersectSorted/MergeSorted/Difference,
ref algo/uidlist.go:137,287,354,322) and the decode side of
``codec/codec.go``.

Representation
--------------
A UID set lives on device as a 1-D ``uint32`` array of static length in
which valid UIDs are sorted ascending and all padding slots hold
``SENTINEL`` (0xFFFFFFFF).  Because the sentinel is the maximum value, the
*whole* array is sorted — every kernel below exploits that invariant:

  * membership is one vectorized binary search (``searchsorted``),
  * compaction after masking is one ``sort``,
  * k-way merge is concat + sort + adjacent-unique (no heap — the
    reference's uint64Heap at algo/heap.go:39 becomes a single XLA sort,
    which maps onto the TPU's sorting networks instead of branchy
    pointer-chasing).

UID width: the reference uses uint64 UIDs. On TPU, 64-bit integer ops are
emulated, so the device plane works in uint32 with a per-tablet 32-bit base
(the reference's own UidPack blocks guarantee a shared high word — the
"32 MSB block boundary" rule at codec/codec.go:43-109 — so this matches its
design, not just its behavior).  The host layer (storage/) owns full-width
UIDs and rebases before upload.  0xFFFFFFFF is reserved as padding and may
not be a live UID low-word.

All functions are pure and shape-polymorphic only in the Python sense: each
distinct input length compiles once.  Callers should bucket lengths to
powers of two (see pad_to) to bound recompiles.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

UID_DTYPE = jnp.uint32
SENTINEL = np.uint32(0xFFFFFFFF)


def _ceil_pow2(n: int) -> int:
    if n <= 1:
        return 1
    return 1 << (int(n - 1).bit_length())


def pad_to(n: int, minimum: int = 8) -> int:
    """Bucketed padded length for a set of n UIDs: next power of two,
    floored at `minimum`. Bounds the number of distinct compiled shapes."""
    return max(minimum, _ceil_pow2(n))


def from_numpy(uids: np.ndarray, size: int | None = None) -> jax.Array:
    """Host sorted uint32 UIDs -> padded device vector."""
    uids = np.asarray(uids, dtype=np.uint32)
    if size is None:
        size = pad_to(len(uids))
    if len(uids) > size:
        raise ValueError(f"{len(uids)} uids exceed padded size {size}")
    out = np.full(size, SENTINEL, dtype=np.uint32)
    out[: len(uids)] = uids
    return jnp.asarray(out)


def to_numpy(vec: jax.Array) -> np.ndarray:
    """Padded device vector -> compact host numpy array (drops padding)."""
    arr = np.asarray(vec)
    return arr[arr != SENTINEL]


def count(a: jax.Array) -> jax.Array:
    """Number of valid UIDs. Ref: codec.ExactLen (codec/codec.go:334)."""
    return jnp.sum(a != SENTINEL, dtype=jnp.int32)


def compact(a: jax.Array) -> jax.Array:
    """Re-establish the sorted/padded invariant after masking: one sort."""
    return jnp.sort(a)


def _sort_backend() -> bool:
    """True when comparator sorts are the fast membership lowering
    (TPU sorting networks); False on CPU, where XLA's generic
    single-thread comparator sort loses to searchsorted's binary-scan
    lowering by ~50x at every size that matters. Backend is fixed per
    process, so the verdict is a constant fold inside traces."""
    return jax.default_backend() != "cpu"


def member_mask(a: jax.Array, b: jax.Array) -> jax.Array:
    """Boolean mask over `a`: a[i] valid and present in `b`.

    Replaces the reference's per-pair lin/jump/bin strategy switch
    (algo/uidlist.go:151-159) with a co-sort: jnp.searchsorted's scan
    lowering is catastrophically slow on TPU (measured 1.8s where two
    stable lax.sorts finish in single-digit ms at 8x2^20), so
    membership is ONE two-operand key sort over concat(a, b) with an
    origin flag + original index as payloads, an adjacency check
    (valid because uid vectors are duplicate-free by invariant;
    sentinels are excluded explicitly), and a second key sort on the
    original index to restore a's order — sorts map onto the TPU's
    sorting networks, branch-free.

    On CPU the trade inverts (generic comparator sorts are the slow
    path there), so membership gathers through searchsorted instead.
    """
    if not _sort_backend():
        idx = jnp.clip(jnp.searchsorted(b, a), 0, b.shape[0] - 1)
        return (b[idx] == a) & (a != SENTINEL)
    n = a.shape[0]
    c = jnp.concatenate([a, b])
    flag = jnp.concatenate([
        jnp.ones(n, jnp.uint32),
        jnp.zeros(b.shape[0], jnp.uint32)])
    idx = jnp.concatenate([
        jnp.arange(n, dtype=jnp.uint32),
        jnp.full(b.shape[0], n, jnp.uint32)])
    cs, fs, ix = jax.lax.sort((c, flag, idx), dimension=0, num_keys=1)
    pad = jnp.full((1,), SENTINEL, dtype=cs.dtype)
    one = jnp.ones((1,), jnp.uint32)
    nxt = jnp.concatenate([cs[1:], pad])
    prv = jnp.concatenate([pad, cs[:-1]])
    fnx = jnp.concatenate([fs[1:], one])
    fpv = jnp.concatenate([one, fs[:-1]])
    hit = (((nxt == cs) & (fnx == 0)) | ((prv == cs) & (fpv == 0))) \
        & (fs == 1) & (cs != SENTINEL)
    # restore a's order: sort hits by original index (b rows key to n,
    # landing past every a row)
    _, hit_in_order = jax.lax.sort(
        (ix, hit.astype(jnp.uint32)), dimension=0, num_keys=1)
    return hit_in_order[:n].astype(bool)


def sorted_lookup(table: jax.Array, q: jax.Array) -> jax.Array:
    """Left-insertion indices of SORTED queries `q` in sorted `table`
    (what jnp.searchsorted returns), via the same co-sort trick as
    member_mask: in the stable key-sort of concat(q, table), a q-row's
    position minus its own q-rank equals the number of table elements
    strictly below it. Two lax.sorts replace the scan lowering that is
    pathologically slow on TPU for large query vectors."""
    n = q.shape[0]
    c = jnp.concatenate([q, table])
    ix = jnp.concatenate([
        jnp.arange(n, dtype=jnp.uint32),
        jnp.full(table.shape[0], n, jnp.uint32)])
    _, ixs = jax.lax.sort((c, ix), dimension=0, num_keys=1)
    pos = jnp.arange(c.shape[0], dtype=jnp.uint32)
    bidx = jnp.where(ixs < n, pos - ixs, 0)
    _, out = jax.lax.sort((ixs, bidx), dimension=0, num_keys=1)
    return out[:n].astype(jnp.int32)


# lookup_idx's rule, fitted on one v5e (PR 25: `bench_micro.py
# --lookup-crossover`, device time of one lookup in ms from a profiler
# trace, scan / co-sort; PERF.md has the finer grid):
#
#   n_t \ n_q     1,024          4,096          16,384        262,144
#   16,384     0.100 / 0.050  0.366 / 0.051  1.75 / 0.052  28.2 / 0.88
#   524,288    0.442 / 1.95   1.84  / 1.95   7.96 / 1.95   37.7 / 2.53
#   2,097,152  0.409 / 10.0   1.67  / 10.0   6.93 / 9.99   135  / 10.7
#
# The scan gathers n_q elements a round for log2(n_t)+1 rounds, 6-7 ns
# an element while XLA keeps the table in fast memory and ~21 ns when
# it does not (a parameter of 131,072 rows or more, alone in its
# program); the co-sort costs two sorts of n_q + n_t rows whatever the
# queries are. They cross where the table is 128 times the query
# (4,096 against 524,288: 1.84 / 1.95; 16,384 against 2,097,152). Under
# _LOOKUP_COSORT_MIN queries the scan stays: at most 0.14 ms is at
# stake there (1,024 against 16,384: 0.100 / 0.050), no served program
# has such a lookup, and a table in fast memory tilts it to the scan.
_LOOKUP_COSORT_MIN = 4096
_LOOKUP_TABLE_RATIO = 128


def lookup_cosorts(n_q: int, n_t: int) -> bool:
    """Whether lookup_idx co-sorts `n_q` queries with an `n_t`-row
    table on a sort backend: both sizes are static, so this is a
    constant inside a trace."""
    return n_q >= _LOOKUP_COSORT_MIN and n_q * _LOOKUP_TABLE_RATIO > n_t


def lookup_idx(table: jax.Array, q: jax.Array) -> jax.Array:
    """searchsorted(table, q), picking the implementation from the two
    static sizes (lookup_cosorts): the co-sort for a wide query
    against a table of comparable size, jnp.searchsorted's scan when
    the query is small or the table dwarfs it (a 4,096-row lookup
    never co-sorts a half-million-row table). On the CPU always the
    scan (_sort_backend).

    PRECONDITION (unlike jnp.searchsorted): `q` must be sorted
    ascending — the repo-wide padded-sorted-uid-vector invariant. The
    co-sort path computes each query's table rank as (position in the
    co-sorted concat) - (its own q-rank), which underflows to garbage
    for out-of-order queries. Callers passing value-ordered or
    otherwise unsorted vectors must sort first."""
    if _sort_backend() and lookup_cosorts(q.shape[0], table.shape[0]):
        return sorted_lookup(table, q)
    return jnp.searchsorted(table, q)


def _cosort_hits(a: jax.Array, b: jax.Array):
    """One stable key-sort of concat(a, b) with an origin flag, plus
    the adjacency hit mask for a-rows (a[i] present in b).  The
    building block of the FUSED set ops below: because the co-sorted
    values are already ascending, masking + one single-operand sort
    re-establishes the padded invariant — no order-restore sort and
    no separate compact() (the three-sort pipeline this replaces
    measured ~1.3 GB/s; two sorts with fewer payloads roughly halve
    the HBM traffic per element)."""
    n = a.shape[0]
    c = jnp.concatenate([a, b])
    flag = jnp.concatenate([
        jnp.ones(n, jnp.uint32),
        jnp.zeros(b.shape[0], jnp.uint32)])
    cs, fs = jax.lax.sort((c, flag), dimension=0, num_keys=1)
    pad = jnp.full((1,), SENTINEL, dtype=cs.dtype)
    one = jnp.ones((1,), jnp.uint32)
    nxt = jnp.concatenate([cs[1:], pad])
    prv = jnp.concatenate([pad, cs[:-1]])
    fnx = jnp.concatenate([fs[1:], one])
    fpv = jnp.concatenate([one, fs[:-1]])
    hit = (((nxt == cs) & (fnx == 0)) | ((prv == cs) & (fpv == 0))) \
        & (fs == 1) & (cs != SENTINEL)
    return cs, fs, hit


def intersect(a: jax.Array, b: jax.Array) -> jax.Array:
    """Sorted-set intersection. Ref algo.IntersectWith (algo/uidlist.go:137).

    Result has a's static length.  Always the fused co-sort — a
    binary-search probe of the larger side (the reference's bin pick,
    algo/uidlist.go:151) was measured 7x SLOWER here: XLA's
    searchsorted lowers to a sequential scan on TPU at these query
    sizes (0.09 GB/s vs 0.64 co-sort on the ratio=8 config).
    """
    cs, _fs, hit = _cosort_hits(a, b)
    vals = jnp.where(hit, cs, SENTINEL)
    return jnp.sort(vals)[: a.shape[0]]


def difference(a: jax.Array, b: jax.Array) -> jax.Array:
    """a \\ b. Ref algo.Difference (algo/uidlist.go:322)."""
    cs, fs, hit = _cosort_hits(a, b)
    keep = (fs == 1) & ~hit & (cs != SENTINEL)
    vals = jnp.where(keep, cs, SENTINEL)
    return jnp.sort(vals)[: a.shape[0]]


def union(a: jax.Array, b: jax.Array) -> jax.Array:
    """Sorted-set union with dedup. Ref algo.MergeSorted
    (algo/uidlist.go:354). Result length = |a|+|b| (static)."""
    return merge_many(jnp.concatenate([a, b]).reshape(1, -1))


def merge_many(mat: jax.Array) -> jax.Array:
    """K-way merge + dedup of k padded rows -> one padded vector of length
    k*n.  Ref algo.MergeSorted's uint64Heap loop (algo/uidlist.go:354,
    algo/heap.go:39) re-designed as sort + adjacent-unique."""
    flat = jnp.sort(mat.reshape(-1))
    prev = jnp.concatenate([jnp.full((1,), SENTINEL, dtype=flat.dtype), flat[:-1]])
    first_occurrence = flat != prev
    return compact(jnp.where(first_occurrence, flat, SENTINEL))


def intersect_many(mat: jax.Array) -> jax.Array:
    """Intersection of k padded rows (k static).  Ref algo.IntersectSorted
    (algo/uidlist.go:287), which intersects smallest-first; on device we
    fold pairwise — each fold is one searchsorted+sort, and XLA fuses the
    masking."""
    k = mat.shape[0]
    acc = mat[0]
    for i in range(1, k):
        acc = intersect(acc, mat[i])
    return acc


def first_k(a: jax.Array, k: int, offset: int = 0) -> jax.Array:
    """Pagination: the k-wide window after `offset` of a compact-sorted
    vector, SENTINEL-padded when the window runs off the end — never
    clamped backwards (lax.dynamic_slice clamps its start, which would
    duplicate the previous page's uids on the final page). Ref
    algo.IndexOf-based windowing in query pagination (query.go:2231)."""
    take = max(0, min(k, a.shape[0] - offset))
    pad = jnp.full((k - take,), SENTINEL, a.dtype)
    if not take:
        return pad
    sl = jax.lax.slice_in_dim(a, offset, offset + take)
    return jnp.concatenate([sl, pad]) if k > take else sl
