"""Device-resident adjacency + expansion kernels.

This is the TPU re-design of the reference's posting-list fan-out hot loop
(worker/task.go:581 handleUidPostings: per-UID goroutines doing Badger
reads + codec decode + per-list intersect). Here a whole predicate
("tablet") lives in HBM as degree-bucketed padded neighbor matrices, and
one jitted call expands an entire frontier level:

    rows    = searchsorted(bucket.src, frontier)        (vectorized lookup)
    cand    = bucket.neighbors[rows]                    (one batched gather)
    next    = sort+unique(concat over buckets)          (merge)

Degree bucketing bounds padding waste: a src uid lands in the bucket whose
width is the next power of two >= its degree, so padding is < 2x and each
bucket's gather is a dense [F, D] tile — MXU/VPU-friendly, no ragged
shapes inside jit.  The reference's analogue of "one list too big for a
node" (multi-part posting lists, posting/list.go:1149) maps to splitting a
bucket row across the mesh's uid axis — see parallel/.

Value postings (for order-by and inequality) live as two aligned sorted
views so both directions are one searchsorted: by-uid (gather a
candidate's sort key) and by-key (range select for le/ge/between).
Ref: worker/sort.go:177 sortWithIndex + worker/tokens.go:113
getInequalityTokens, re-designed as array kernels instead of index-bucket
walks.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from dgraph_tpu.ops.uidvec import (
    SENTINEL, compact, lookup_idx, member_mask, pad_to,
)

INT64_MAX = np.int64(2**63 - 1)


@dataclass
class AdjBucket:
    """One degree class of a predicate's adjacency."""

    src: jax.Array        # [M] uint32 sorted, SENTINEL padded
    neighbors: jax.Array  # [M, D] uint32, SENTINEL padded
    degree: int           # D


@dataclass
class DeviceAdjacency:
    """A predicate's full edge set on device.

    src_uids/degrees give O(log N) per-frontier-element count lookup
    (ref worker/task.go handleHasFunction + count index reads).
    """

    src_uids: jax.Array   # [N] uint32 sorted, SENTINEL padded
    degrees: jax.Array    # [N] int32 aligned to src_uids
    buckets: list[AdjBucket] = field(default_factory=list)
    n_edges: int = 0
    n_dst: int = 0        # distinct destination uids (bounds any union)
    n_src: int = 0        # real (unpadded) source count

    @property
    def shape_sig(self):
        return (self.src_uids.shape[0],
                tuple((b.src.shape[0], b.degree) for b in self.buckets))


def build_adjacency(edges: dict[int, np.ndarray],
                    min_degree_bucket: int = 8) -> DeviceAdjacency:
    """Host: {src_uid -> sorted dst uint32 array} -> DeviceAdjacency.

    Runs at rollup time (the analogue of posting.List.Rollup,
    posting/list.go:708): the committed state is re-packed into dense
    device tiles.
    """
    srcs = np.fromiter(edges.keys(), dtype=np.uint32, count=len(edges))
    order = np.argsort(srcs, kind="stable")
    srcs = srcs[order]
    degs = np.fromiter((len(edges[int(s)]) for s in srcs), dtype=np.int32,
                       count=len(srcs))

    n_pad = pad_to(len(srcs))
    src_pad = np.full(n_pad, SENTINEL, np.uint32)
    src_pad[: len(srcs)] = srcs
    deg_pad = np.zeros(n_pad, np.int32)
    deg_pad[: len(srcs)] = degs

    buckets: list[AdjBucket] = []
    n_edges = int(degs.sum())
    if len(srcs):
        caps = np.maximum(min_degree_bucket,
                          2 ** np.ceil(np.log2(np.maximum(degs, 1))).astype(np.int64))
        for cap in sorted(set(caps.tolist())):
            sel = srcs[caps == cap]
            m_pad = pad_to(len(sel))
            bsrc = np.full(m_pad, SENTINEL, np.uint32)
            bsrc[: len(sel)] = sel
            nb = np.full((m_pad, int(cap)), SENTINEL, np.uint32)
            for i, s in enumerate(sel):
                dst = edges[int(s)]
                nb[i, : len(dst)] = dst
            buckets.append(AdjBucket(jnp.asarray(bsrc), jnp.asarray(nb),
                                     int(cap)))
    n_dst = 0
    if edges:
        n_dst = len(np.unique(np.concatenate(
            [np.asarray(v) for v in edges.values()])))
    return DeviceAdjacency(jnp.asarray(src_pad), jnp.asarray(deg_pad),
                           buckets, n_edges, n_dst, len(srcs))


def _bucket_candidates(frontier: jax.Array, b: AdjBucket) -> jax.Array:
    """Flat (unsorted, SENTINEL-masked) neighbor candidates of `frontier`
    rows present in bucket `b`.

    Two duals of the same lookup, chosen at trace time by static shape:
      frontier smaller than bucket  -> gather rows for each frontier uid
                                       ([F, D] work)
      bucket smaller than frontier  -> mask bucket rows that appear in
                                       the frontier ([M, D] work)
    Work per hop is thus bounded by min(F, M) * D per bucket — a large
    frontier can never blow past the bucket's own edge count.
    """
    F = frontier.shape[0]
    M = b.src.shape[0]
    if F <= M:
        idx = jnp.clip(lookup_idx(b.src, frontier), 0, M - 1)
        hit = (b.src[idx] == frontier) & (frontier != SENTINEL)
        cand = b.neighbors[idx]                 # [F, D]
        cand = jnp.where(hit[:, None], cand, SENTINEL)
    else:
        hit = member_mask(b.src, frontier)      # [M]
        cand = jnp.where(hit[:, None], b.neighbors, SENTINEL)
    return cand.reshape(-1)


def expand(adj: DeviceAdjacency, frontier: jax.Array,
           out_size: int) -> jax.Array:
    """One BFS level: union of all neighbors of `frontier`.

    `frontier` MUST be sorted (SENTINEL-padded): the bucket membership
    test binary-searches into it when the frontier is larger than the
    bucket. Host entry points (device_cache.expand_np, bfs_reach) sort.

    Result is a padded sorted UID vector of static length `out_size`
    (truncates if the true union exceeds it — caller sizes via
    `max_expansion`). Replaces the reference's per-uid goroutine loop +
    MergeSorted heap (worker/task.go:581, algo/uidlist.go:354) with one
    gather + one sort.
    """
    parts = [_bucket_candidates(frontier, b) for b in adj.buckets]
    if not parts:
        return jnp.full((out_size,), SENTINEL, dtype=jnp.uint32)
    flat = jnp.sort(jnp.concatenate(parts))
    prev = jnp.concatenate(
        [jnp.full((1,), SENTINEL, dtype=flat.dtype), flat[:-1]])
    uniq = jnp.where(flat != prev, flat, SENTINEL)
    uniq = compact(uniq)
    if uniq.shape[0] >= out_size:
        return uniq[:out_size]
    return jnp.concatenate(
        [uniq, jnp.full((out_size - uniq.shape[0],), SENTINEL,
                        dtype=jnp.uint32)])


def max_expansion(adj: DeviceAdjacency, frontier_size: int) -> int:
    """Static bound on expand() output size for a frontier of F uids:
    the union can never exceed the distinct-destination count, nor the
    per-bucket work bound."""
    total = sum(min(b.src.shape[0], frontier_size) * b.degree
                for b in adj.buckets)
    cap = pad_to(adj.n_dst or adj.n_edges)
    return max(8, min(total, cap))


def count_gather(adj: DeviceAdjacency, uids: jax.Array) -> jax.Array:
    """Per-uid out-degree (0 for uids without the predicate); `uids`
    must be sorted (lookup_idx precondition).
    Ref: count-index reads (posting/index.go:284 updateCount)."""
    idx = jnp.clip(lookup_idx(adj.src_uids, uids), 0,
                   adj.src_uids.shape[0] - 1)
    hit = (adj.src_uids[idx] == uids) & (uids != SENTINEL)
    return jnp.where(hit, adj.degrees[idx], 0)


def has_uids(adj: DeviceAdjacency) -> jax.Array:
    """All uids carrying this predicate — the has() root function
    (ref worker/task.go:2075 handleHasFunction)."""
    return adj.src_uids


# -- value postings ----------------------------------------------------------


# int64 is unavailable on device without jax_enable_x64 (jnp silently
# downcasts to int32), so the device never sees raw sort keys: it holds
# order-preserving int32 RANKS into the host-side sorted unique-key
# table. Ordering and range selection are exact; raw-key bounds resolve
# to rank bounds with one host searchsorted.
RANK_MISSING = np.int32(2**31 - 1)


@dataclass
class DeviceValues:
    """Scalar predicate's sortable view: aligned (uid -> key rank) plus
    the rank-sorted permutation for range scans."""

    uids: jax.Array          # [N] uint32 sorted, SENTINEL padded
    ranks: jax.Array         # [N] int32 aligned (pad = RANK_MISSING)
    ranks_sorted: jax.Array  # [N] int32 sorted
    uids_by_key: jax.Array   # [N] uint32 aligned to ranks_sorted
    host_keys: np.ndarray    # [U] int64 sorted unique raw keys (host)
    n: int = 0               # real (unpadded) uid count
    # Dense uid -> rank table when the tablet's uid range is compact
    # (span <= max(2^20, 4n)): rank_lut[uid - lut_base] == rank, holes
    # hold RANK_MISSING. Turns the per-candidate rank gather into ONE
    # indexed load instead of a log2(N)-round binary search — the
    # difference between the fused page kernel winning and losing on
    # backends where searchsorted lowers to a sequential scan.
    rank_lut: jax.Array | None = None
    lut_base: jax.Array | None = None  # scalar uint32


# uid-span budget multiplier and floor for materializing rank_lut
_LUT_SPAN_FLOOR = 1 << 20
_LUT_SPAN_MULT = 4


def build_values(pairs: dict[int, int]) -> DeviceValues:
    """Host: {uid -> int64 sort key} -> DeviceValues."""
    n = len(pairs)
    n_pad = pad_to(n)
    uids = np.full(n_pad, SENTINEL, np.uint32)
    ranks = np.full(n_pad, RANK_MISSING, np.int32)
    host_keys = np.empty(0, np.int64)
    lut = base = None
    if n:
        u = np.fromiter(pairs.keys(), dtype=np.uint32, count=n)
        k = np.fromiter(pairs.values(), dtype=np.int64, count=n)
        order = np.argsort(u, kind="stable")
        host_keys, inv = np.unique(k, return_inverse=True)
        uids[:n] = u[order]
        ranks[:n] = inv[order].astype(np.int32)
        umin = int(u.min())
        span = int(u.max()) - umin + 1
        if span <= max(_LUT_SPAN_FLOOR, _LUT_SPAN_MULT * n):
            table = np.full(pad_to(span), RANK_MISSING, np.int32)
            table[u - np.uint32(umin)] = inv.astype(np.int32)
            lut = jnp.asarray(table)
            base = jnp.asarray(np.uint32(umin))
    by_key = np.lexsort((uids, ranks))
    return DeviceValues(jnp.asarray(uids), jnp.asarray(ranks),
                        jnp.asarray(ranks[by_key]),
                        jnp.asarray(uids[by_key]), host_keys, n,
                        lut, base)


def dv_view(dv: DeviceValues) -> tuple[tuple[jax.Array, jax.Array], bool]:
    """(payload, is_lut) pair for view_ranks: the dense-LUT form when the
    tablet carries one, else the binary-search form. The bool is a
    STATIC trace parameter — callers must thread it into their jit_stage
    statics so LUT and search executables never alias."""
    if dv.rank_lut is not None:
        return (dv.rank_lut, dv.lut_base), True
    return (dv.uids, dv.ranks), False


def view_ranks(cand: jax.Array, view: tuple[jax.Array, jax.Array],
               is_lut: bool, valid: jax.Array) -> jax.Array:
    """Ranks aligned to candidate uids from a dv_view payload; absent or
    invalid candidates get RANK_MISSING. LUT form is one gather; search
    form binary-searches the sorted uid plane (cand must be sorted)."""
    if is_lut:
        lut, lbase = view
        off = cand - lbase  # uint32: wraps huge for cand < base
        in_range = valid & (off < jnp.uint32(lut.shape[0]))
        idx = jnp.clip(off, 0, jnp.uint32(lut.shape[0] - 1)).astype(jnp.int32)
        return jnp.where(in_range, lut[idx], RANK_MISSING)
    du, dr = view
    idx = jnp.clip(lookup_idx(du, cand), 0, du.shape[0] - 1)
    hit = (du[idx] == cand) & valid
    return jnp.where(hit, dr[idx], RANK_MISSING)


def key_gather(dv: DeviceValues, uids: jax.Array,
               missing: int = int(RANK_MISSING)) -> jax.Array:
    """Sort-key ranks for candidate uids; `missing` for absent ones.
    `uids` must be sorted (lookup_idx precondition)."""
    idx = jnp.clip(lookup_idx(dv.uids, uids), 0, dv.uids.shape[0] - 1)
    hit = (dv.uids[idx] == uids) & (uids != SENTINEL)
    return jnp.where(hit, dv.ranks[idx], jnp.int32(missing))


def range_select(dv: DeviceValues, lo, hi,
                 lo_open: bool = False, hi_open: bool = False) -> jax.Array:
    """UIDs whose raw key is in [lo, hi] (open per flags) — le/lt/ge/gt/
    between root functions in one mask + compact. Raw int64 bounds
    become rank bounds on host.
    Ref: worker/tokens.go:113 getInequalityTokens bucket walk."""
    lo_rank = np.searchsorted(dv.host_keys, np.int64(lo),
                              side="right" if lo_open else "left")
    hi_rank = np.searchsorted(dv.host_keys, np.int64(hi),
                              side="left" if hi_open else "right")
    rs = dv.ranks_sorted
    in_range = (rs >= jnp.int32(lo_rank)) & (rs < jnp.int32(hi_rank))
    valid = dv.uids_by_key != SENTINEL
    return compact(jnp.where(in_range & valid, dv.uids_by_key, SENTINEL))


@partial(jax.jit, static_argnames=("descs",))
def multisort(cand: jax.Array, dv_uids: tuple, dv_ranks: tuple,
              descs: tuple) -> jax.Array:
    """Stable multi-key order-by fully on device: gather each order
    attr's rank column for the (sorted, SENTINEL-padded) candidates,
    then ONE lax.sort with the columns as leading keys and the uid
    vector as the final tiebreak — the reference's multiSort
    (worker/sort.go:300) without its per-attr re-sort passes. Missing
    values keep RANK_MISSING so they sink last under asc AND desc
    (the host path's missing-flag-dominates rule); SENTINEL padding
    sinks below real uids via the uid operand."""
    cols = _rank_cols(cand, dv_uids, dv_ranks, descs)
    out = jax.lax.sort(tuple(cols) + (cand,), num_keys=len(cols) + 1)
    return out[-1]


def _rank_cols(cand: jax.Array, dv_uids: tuple, dv_ranks: tuple,
               descs: tuple) -> list:
    """Per-order-attr rank columns aligned with `cand` (missing values
    keep RANK_MISSING so they sink last under asc AND desc — the host
    path's missing-flag-dominates rule)."""
    cols = []
    for du, dr, desc in zip(dv_uids, dv_ranks, descs):
        idx = jnp.clip(lookup_idx(du, cand), 0, du.shape[0] - 1)
        hit = (du[idx] == cand) & (cand != SENTINEL)
        ranks = jnp.where(hit, dr[idx], RANK_MISSING)
        if desc:
            ranks = jnp.where(hit, -ranks, RANK_MISSING)
        cols.append(ranks)
    return cols


def _page_slice(suids, after_uid, offset, window: int, limit=None):
    """Shared paging tail (traced inside the page kernels): after-
    cursor position -> start -> fixed `window` slice. `limit` treats
    cursor positions >= limit as absent. The SENTINEL tail keeps
    dynamic_slice exact for any start <= n_pad (an over-the-end start
    clamps onto pure padding = empty page); callers bound `offset`
    (host guard) so start stays far from int32 overflow."""
    hit_after = suids == after_uid.astype(suids.dtype)
    pos = jnp.argmax(hit_after)
    found = jnp.any(hit_after)
    if limit is not None:
        found = found & (pos < limit)
    start = jnp.where(found, pos + 1, 0) + offset.astype(jnp.int32)
    ext = jnp.concatenate(
        [suids, jnp.full((window,), SENTINEL, suids.dtype)])
    return jax.lax.dynamic_slice(ext, (start,), (window,)), start


@partial(jax.jit, static_argnames=("descs", "window"))
def multisort_page(cand: jax.Array, dv_uids: tuple, dv_ranks: tuple,
                   descs: tuple, window: int, after_uid: jax.Array,
                   offset: jax.Array):
    """multisort + after-cursor + offset + first in ONE dispatch,
    returning only the `window`-sized page instead of the whole sorted
    vector — at the 21M regime the full vector is ~4MB each way
    across the host link while the page is a few KB, and the whole
    chain costs one dispatch. Ref worker/sort.go:177 processSort applying
    offset+count inside the sort request.

    Returns one packed uint32 array [page..., start]: `start` is the
    UNCLAMPED index the page begins at in the sorted stream; the host
    derives the valid length as clip(n_real - start, 0, window). An
    absent after-cursor skips nothing (the host path's semantics)."""
    cols = _rank_cols(cand, dv_uids, dv_ranks, descs)
    suids = jax.lax.sort(tuple(cols) + (cand,),
                         num_keys=len(cols) + 1)[-1]
    page, start = _page_slice(suids, after_uid, offset, window)
    # one packed array = one device->host fetch: [page..., start]
    return jnp.concatenate(
        [page, start[None].astype(jnp.uint32)])


@partial(jax.jit, static_argnames=("descs", "window"))
def count_filter_sort_page(cand: jax.Array, degrees: jax.Array,
                           lo: jax.Array, hi: jax.Array,
                           dv_uids: tuple, dv_ranks: tuple,
                           descs: tuple, window: int,
                           after_uid: jax.Array, offset: jax.Array):
    """has(A) root + count(A)-threshold filter + order + paginate in
    ONE dispatch over the predicate's RESIDENT adjacency (cand =
    adj.src_uids, degrees aligned): nothing is uploaded and only the
    page comes back (q010's device path was two full-vector round
    trips). Filtered-out uids sink below even missing-value uids via
    a leading exclusion key. Ref worker/task.go:1111 handleCompare
    over the count index + sort.go:177.

    Returns one packed uint32 array [page..., start, n_kept]."""
    keep = (degrees >= lo) & (degrees <= hi) & (cand != SENTINEL)
    excl = jnp.where(keep, jnp.int32(0), jnp.int32(1))
    cols = _rank_cols(cand, dv_uids, dv_ranks, descs)
    suids = jax.lax.sort((excl,) + tuple(cols) + (cand,),
                         num_keys=len(cols) + 2)[-1]
    n_kept = jnp.sum(keep)
    # a cursor uid the filter excluded sank past n_kept: treat it as
    # ABSENT (skip nothing), exactly the host path's absent-uid rule —
    # matching it in the excluded region would return an empty page
    page, start = _page_slice(suids, after_uid, offset, window,
                              limit=n_kept)
    return jnp.concatenate(
        [page, start[None].astype(jnp.uint32),
         n_kept[None].astype(jnp.uint32)])


# Selection geometry of the fused whole-block kernel: candidates
# histogram into FUSED_SEL_BUCKETS primary-rank buckets and at most
# FUSED_SEL_CAP survivors reach the (small, cheap) exact multi-key
# sort. Both are STATIC — the cap bounds the sort operand so the
# executable's cost never scales with the candidate set, only the
# linear passes do. A page that cannot be proven inside the cap
# (boundary-bucket tie mass > cap) makes the kernel report
# sel_count > cap and the executor re-runs the staged chain.
FUSED_SEL_BUCKETS = 4096
FUSED_SEL_CAP = 4096


def fused_rank_page(cand: jax.Array,
                    rank_views: tuple, rank_luts: tuple,
                    rank_los: tuple, rank_his: tuple, rank_negs: tuple,
                    fparts: tuple, set_negs: tuple, set_aligned: bool,
                    fop: str,
                    ord_views: tuple, ord_luts: tuple, descs: tuple,
                    base0: jax.Array, shift: int, window: int,
                    offset: jax.Array):
    """Whole-block chain — filter algebra + multi-key order + offset/
    first page — as ONE traceable program: the fused tier's kernel
    (query/fusion.py jits it through the `jit_stage` seam, which also
    owns the mesh sharding constraints — this function stays pure and
    un-jitted so the seam is the only compile site, dglint DG02).

    Filter leaves come in two forms and fold under `fop` ("none" |
    "and" | "or") with per-leaf negation flags:

      rank leaves — dv_view payloads of the leaf predicate (dense
        rank LUT when the tablet's uid span is compact, else the
        sorted uid/rank planes; `rank_luts` carries the STATIC form
        flags) plus TRACED [lo, hi) rank bounds: eq/ineq on predicates
        whose sort key is injective (int/float/bool/datetime) evaluate
        as a gather + range test, no host index probe and no per-query
        upload; a threshold change re-binds two scalars, ZERO
        recompiles.
      set leaves — host-evaluated leaf sets (string eq, has,
        lang/list predicates), the general fallback form. When
        `set_aligned` (candidates host-known: the common eq-root
        shape) each fpart arrives as a bool mask ALIGNED to cand —
        the membership test already happened in one host searchsorted
        and the device sees a pure vector operand; otherwise (device-
        resident roots) fparts are sorted padded uid vectors and
        membership runs on device.

    Ordering avoids the full-width device sort (O(n log n) comparator
    sorts dwarf every linear pass at 500M-regime candidate counts)
    AND full-width scatters (XLA lowers scatter serially on sub-TPU
    backends; measured 12ms of a 23ms kernel at 2^17 candidates):
    kept candidates bucket by the desc-adjusted PRIMARY order rank
    (missing ranks bucket just past the real ones — the host path's
    missing-sinks-last rule), an unrolled binary search of masked
    REDUCTIONS finds the bucket threshold covering offset+window
    rows, and survivors compact through cumsum + searchsorted +
    gather — every full-width pass is a map or a reduce. Only the
    <= FUSED_SEL_CAP survivors take the exact multi-key lax.sort. Every
    order rank is looked up ONCE: the survivors inherit their primary
    key from the full-width column the bucketing computed (`c0[sidx]`,
    a gather), and only secondary order keys are looked up on the
    survivor vector, a FUSED_SEL_CAP-row query that lookup_idx answers
    by binary search whenever the table dwarfs it. Buckets
    are monotone in the primary rank, so the sorted survivors are a
    byte-exact prefix of the staged full ordering — the page slice is
    identical. `base0` recenters desc-negated ranks (traced: domain
    growth re-binds, only a `shift` change recompiles).

    Returns one packed uint32 array [page..., sel_count, n_kept]; a
    sel_count > FUSED_SEL_CAP means the boundary tie mass overflowed
    the cap and the caller must use the staged chain."""
    valid = cand != SENTINEL
    masks = []
    for view, is_lut, lo, hi in zip(rank_views, rank_luts, rank_los,
                                    rank_his):
        r = view_ranks(cand, view, is_lut, valid)
        masks.append((r != RANK_MISSING) & (r >= lo) & (r < hi))
    for fp in fparts:
        masks.append((fp & valid) if set_aligned
                     else member_mask(cand, fp))
    if fop == "and":
        keep = valid
        for m, neg in zip(masks, rank_negs + set_negs):
            keep = keep & (~m if neg else m)
    elif fop == "or":
        hit = jnp.zeros(cand.shape[0], bool)
        for m, neg in zip(masks, rank_negs + set_negs):
            hit = hit | (~m if neg else m)
        keep = valid & hit
    else:
        keep = valid
    keep = keep & valid  # a negated leaf must never resurrect padding
    n_kept = jnp.sum(keep)

    nb = jnp.int32(FUSED_SEL_BUCKETS)
    c0 = view_ranks(cand, ord_views[0], ord_luts[0], valid)
    if descs[0]:
        c0 = jnp.where(c0 == RANK_MISSING, c0, -c0)
    miss0 = c0 == RANK_MISSING
    # miss0 rows shift from base0 (not RANK_MISSING - base0, which
    # overflows int32 under a desc recenter) and rebucket to nb after
    b = jnp.clip((jnp.where(miss0, base0, c0) - base0) >> shift,
                 0, nb - 1)
    b = jnp.where(miss0, nb, b)
    b = jnp.where(keep, b, nb + 1)
    # smallest bucket threshold covering offset+window kept rows
    # (= searchsorted-left of the bucket cumulative), found by an
    # UNROLLED binary search of masked reductions — no histogram
    # scatter. Dropped rows sit in bucket nb+1, outside every probe.
    target = offset + jnp.int32(window)
    lo_t = jnp.int32(0)
    hi_t = nb
    for _ in range(FUSED_SEL_BUCKETS.bit_length()):
        open_ = lo_t < hi_t
        mid = (lo_t + hi_t) >> 1
        cnt = jnp.sum(b <= mid, dtype=jnp.int32)
        pred = cnt >= target
        hi_t = jnp.where(open_ & pred, mid, hi_t)
        lo_t = jnp.where(open_ & ~pred, mid + 1, lo_t)
    thresh = lo_t
    sel = keep & (b <= thresh)
    # scatter-free compaction: survivor o (1-based) lives at the first
    # index whose selection prefix sum reaches o; one sorted-query
    # searchsorted + gather replaces the serial scatter
    pos = jnp.cumsum(sel.astype(jnp.int32))
    sel_count = pos[-1]
    sidx = jnp.clip(
        jnp.searchsorted(pos, jnp.arange(1, FUSED_SEL_CAP + 1,
                                         dtype=jnp.int32),
                         side="left"),
        0, cand.shape[0] - 1)
    got = jnp.arange(1, FUSED_SEL_CAP + 1, dtype=jnp.int32) <= sel_count
    # compaction preserves cand's ascending order, so the survivor
    # vector satisfies the sorted-query precondition of the search-
    # form gathers below; unfilled slots carry RANK_MISSING keys +
    # SENTINEL uid and the uid operand sinks them last
    out_u = jnp.where(got, cand[sidx], SENTINEL)
    svalid = out_u != SENTINEL
    # survivors inherit the primary key from the full-width column the
    # bucketing computed (same uid, same view, already desc-adjusted):
    # a second lookup of it would co-sort the whole table again
    outs = [jnp.where(got, c0[sidx], RANK_MISSING)]
    for view, is_lut, desc in zip(ord_views[1:], ord_luts[1:], descs[1:]):
        r = view_ranks(out_u, view, is_lut, svalid)
        if desc:
            r = jnp.where(r == RANK_MISSING, r, -r)
        outs.append(r)
    suids = jax.lax.sort(tuple(outs) + (out_u,),
                         num_keys=len(outs) + 1)[-1]
    ext = jnp.concatenate(
        [suids, jnp.full((window,), SENTINEL, suids.dtype)])
    page = jax.lax.dynamic_slice(ext, (offset.astype(jnp.int32),),
                                 (window,))
    return jnp.concatenate(
        [page, sel_count[None].astype(jnp.uint32),
         n_kept[None].astype(jnp.uint32)])


@partial(jax.jit, static_argnames=("k", "desc"))
def order_topk(dv_uids, dv_ranks, cand: jax.Array, k: int,
               desc: bool = False):
    """First-k of `cand` ordered by value rank (uid tiebreak), returning
    (uids, valid_count). Ranks come from a DeviceValues view.

    Ref: worker/sort.go:412 processSort — the index-bucket walk +
    intersect per bucket becomes gather + one argsort; lax.sort's
    multi-operand form gives the stable uid tiebreak. `cand` must be
    a sorted padded uid vector (lookup_idx precondition).
    """
    idx = jnp.clip(lookup_idx(dv_uids, cand), 0, dv_uids.shape[0] - 1)
    hit = (dv_uids[idx] == cand) & (cand != SENTINEL)
    ranks = jnp.where(hit, dv_ranks[idx], RANK_MISSING)
    if desc:
        ranks = jnp.where(hit, -ranks, RANK_MISSING)
    # sort (rank, uid) pairs; absent uids (RANK_MISSING) sink to the end
    sranks, suids = jax.lax.sort((ranks, cand), num_keys=2)
    return suids[:k], jnp.minimum(jnp.sum(hit), k)
