"""Edge-centric bitmap traversal kernels — the fast BFS/SSSP data plane.

TPU re-design of the reference's multi-hop traversal hot path
(query/recurse.go:29 per-level goroutine fan-out, query/shortest.go:451
Dijkstra, worker/task.go:581 posting-list fan-out + algo/uidlist.go:354
MergeSorted heaps).

The sorted-UID-vector kernels in ops/graph.py pay one large sort per
level to rebuild a deduped frontier; for dense analytical traversals
that sort dominates. Here the frontier is a *bitmap over a permuted
node-slot space* and one BFS level is only gathers + reductions +
concats — no sort, no scatter:

  1. Node slots are assigned grouped by in-degree class (caps from the
     ~1.5x-step ladder {1,2,3} ∪ {4·2^k, 6·2^k}), rows sorted by uid
     inside a bucket, in-degree-0 nodes last. The reverse adjacency
     ("which slots point at me") is a dense padded [rows, cap] int32
     matrix per bucket.
  2. One level:  reach = concat_b( any(frontier_ext[b.in_nb], axis=1) )
     Because bucket rows occupy *contiguous* slot ranges in exactly
     concat order, the per-bucket hit vectors ARE the new bitmap — the
     scatter the textbook edge-centric BFS needs is compiled away by
     the slot permutation.
  3. dedup (`new = reach & ~visited`) is elementwise on bitmaps,
     replacing member_mask + compact (a search + a sort) per level.

Work per level is Θ(padded in-edges) row-gathers (padding waste < 1.33x
per row with the ladder caps). The gather unit is descriptor-rate bound
(~20-40M row-fetches/s on v5e, measured), so the batched kernels below
amortize each descriptor across thousands of bit-packed queries.

The SERVED traversal (bfs_traverse, bfs_traverse_sharded) holds the
high degree classes as bitmap rows instead (attach_dense) and streams
them. There a level's cost is an UPPER BOUND: the gathered classes
cost the same for every frontier, but of the hub rows a level reads
only the tiles in which some live lane has not yet reached some row
(_hub_pending: the bottom-up step's rule, look only at vertices not
yet found), so the deep levels of a traversal, by which a skewed
graph's hubs are all found, read few of them or none. And a call's
FIRST level, whose frontier is its handful of roots, reads neither:
the out-neighbours of a root are its COLUMN of these reverse
structures (_chip_columns), where that is cheaper (columns_cheaper).

The SERVED shortest path (bfs_paths) is that loop again, run from a
pair's TARGET over the transposed tile and ended when every lane has
met its source; the path is then walked out on the device.

SSSP follows the same layout with an int32 distance vector and a
min-reduction instead of any(): Bellman-Ford over dense tiles, with
optional per-edge weights aligned to the in-neighbor matrices.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, Optional

import numpy as np

import jax
import jax.numpy as jnp

INT32_INF = np.int32(2**31 - 1)


@dataclass
class RevBucket:
    """One in-degree class. Rows r of `in_nb` describe slots
    [offset, offset + rows): the slot's in-neighbor slots, padded with
    n_slots (a dummy always-unreachable slot)."""

    in_nb: jax.Array                 # [M, D] int32
    weights: Optional[jax.Array]     # [M, D] int32 or None
    degree: int
    offset: int
    # host copy of in_nb, kept so build_core_adjacency can re-derive
    # (dst, src) pairs without a device->host transfer; None for
    # buckets built before this field
    in_nb_host: Optional[np.ndarray] = None


@dataclass
class BitAdjacency:
    """A predicate's reverse adjacency in slot space.

    slot_uids[s] is the uid living in slot s. uids_sorted/slots_by_uid
    are the uid->slot lookup (host numpy; traversal entry points are
    host-driven like the reference's query planner).
    """

    slot_uids: np.ndarray            # [N] uint32, host
    uids_sorted: np.ndarray          # [N] uint32 sorted, host
    slots_by_uid: np.ndarray         # [N] int32 aligned to uids_sorted
    buckets: list[RevBucket]
    n_slots: int
    n_covered: int                   # slots with in-degree > 0 (prefix)
    n_edges: int
    # the served traversal's hub rows (attach_dense): buckets
    # [dense_from:] as one bitmap row a slot, uint32[rows, W] with
    # 32 W >= N: bit s // W of word s % W of row r set where slot s
    # points at the row's slot
    dense: Optional[jax.Array] = None
    dense_from: Optional[int] = None     # None: attach_dense not run
    # the served traversal over several chips (attach_dense with a
    # mesh): the mesh whose `uid` axis the DESTINATION rows are split
    # over, and the gathered classes' in-neighbour matrices as the
    # chips hold them (`dense` is then held that way too): a class's
    # rows padded to a multiple of the chips and cut into one run of
    # rows a chip, the padding pointing at the dummy slot
    mesh: Optional[jax.sharding.Mesh] = None
    shard_nbs: Optional[list] = None
    # the served shortest path (attach_uids): slot_uids on the device
    uids_dev: Optional[jax.Array] = None

    @property
    def gathered(self) -> list[RevBucket]:
        """The degree classes the served traversal gathers: those
        below the hub rows, all of them where there are none."""
        return self.buckets[:self.dense_from]

    @property
    def shards(self) -> int:
        """Chips the served traversal's rows are split over."""
        return 1 if self.mesh is None else self.mesh.shape[SHARD_AXIS]

    @property
    def dense_rows(self) -> int:
        """Hub rows, without the padding a split over chips adds."""
        return sum(int(b.in_nb.shape[0])
                   for b in self.buckets[self.dense_from:]) \
            if self.dense is not None else 0

    @property
    def shape_sig(self):
        return (self.n_slots,
                tuple((b.in_nb.shape[0], b.degree) for b in self.buckets))


def _bucket_ladder(max_cap: int = 2**31) -> np.ndarray:
    """Degree-class caps {1,2,3} ∪ {4·2^k, 6·2^k}: ~1.5x steps, so a
    row wastes <33% padding instead of <50% with pure pow-2 classes.
    The gather unit is descriptor-rate bound, so padded slots cost the
    same as real edges — tighter classes are a direct speedup."""
    caps = [1, 2, 3]
    k = 4
    while k < max_cap:
        caps.append(k)
        if k + k // 2 < max_cap:
            caps.append(k + k // 2)
        k *= 2
    return np.asarray(caps, np.int64)


_LADDER = _bucket_ladder()


def build_bitadjacency(edges: dict[int, np.ndarray],
                       weights: Optional[dict[int, np.ndarray]] = None,
                       min_degree_bucket: int = 1) -> BitAdjacency:
    """Host: {src_uid -> sorted dst uint32 array} -> BitAdjacency.

    Runs at rollup time like ops/graph.build_adjacency (the analogue of
    posting.List.Rollup, posting/list.go:708). `weights`, if given,
    must mirror `edges`' shapes (per-edge int costs for SSSP).
    """
    if not edges:
        return BitAdjacency(np.empty(0, np.uint32), np.empty(0, np.uint32),
                            np.empty(0, np.int32), [], 0, 0, 0)
    srcs = np.fromiter(edges.keys(), np.uint32, len(edges))
    degs = np.fromiter((len(edges[int(s)]) for s in srcs), np.int64,
                       len(srcs))
    src_rep = np.repeat(srcs, degs)
    dst_all = np.concatenate([np.asarray(edges[int(s)], dtype=np.uint32)
                              for s in srcs]) if len(srcs) else \
        np.empty(0, np.uint32)
    w_all = None
    if weights is not None:
        w_all = np.concatenate([np.asarray(weights[int(s)], dtype=np.int32)
                                for s in srcs])

    uids = np.unique(np.concatenate([srcs, dst_all]))
    n = len(uids)
    dst_idx = np.searchsorted(uids, dst_all)
    indeg = np.bincount(dst_idx, minlength=n)
    floor = np.maximum(indeg, min_degree_bucket)
    cap = np.where(
        indeg > 0,
        _LADDER[np.searchsorted(_LADDER, floor)],
        np.int64(1) << 62)
    perm = np.lexsort((uids, cap))            # slot -> uid index
    slot_of = np.empty(n, np.int32)
    slot_of[perm] = np.arange(n, dtype=np.int32)
    slot_uids = uids[perm]
    n_covered = int(np.sum(indeg > 0))

    src_slot = slot_of[np.searchsorted(uids, src_rep)]
    dst_slot = slot_of[dst_idx]
    eorder = np.argsort(dst_slot, kind="stable")
    src_slot = src_slot[eorder]
    dst_slot = dst_slot[eorder]
    if w_all is not None:
        w_all = w_all[eorder]
    counts = np.bincount(dst_slot, minlength=n)
    starts = np.zeros(n + 1, np.int64)
    np.cumsum(counts, out=starts[1:])
    pos = np.arange(len(dst_slot), dtype=np.int64) - starts[dst_slot]

    cap_by_slot = cap[perm][:n_covered]
    buckets: list[RevBucket] = []
    offset = 0
    for c in np.unique(cap_by_slot):
        c = int(c)
        m = int(np.sum(cap_by_slot == c))
        nb = np.full((m, c), n, np.int32)
        sel = (dst_slot >= offset) & (dst_slot < offset + m)
        nb[dst_slot[sel] - offset, pos[sel]] = src_slot[sel]
        wb = None
        if w_all is not None:
            warr = np.zeros((m, c), np.int32)
            warr[dst_slot[sel] - offset, pos[sel]] = w_all[sel]
            wb = jnp.asarray(warr)
        buckets.append(RevBucket(jnp.asarray(nb), wb, c, offset,
                                 in_nb_host=nb))
        offset += m

    order = np.argsort(slot_uids, kind="stable")
    return BitAdjacency(slot_uids, slot_uids[order],
                        order.astype(np.int32), buckets, n, n_covered,
                        int(len(dst_all)))


# -- host <-> bitmap ---------------------------------------------------------


def _uid_slots(badj: BitAdjacency,
               u: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """uid uint32 array -> (slot array, keep mask); unknown uids have
    keep=False. Shared by the single and batched packers."""
    idx = np.searchsorted(badj.uids_sorted, u)
    idx = np.clip(idx, 0, len(badj.uids_sorted) - 1)
    hit = badj.uids_sorted[idx] == u
    return badj.slots_by_uid[idx[hit]], hit


def uids_to_bits(badj: BitAdjacency, uids_np: np.ndarray) -> np.ndarray:
    """Seed uid array -> bool[N] bitmap (unknown uids dropped)."""
    bits = np.zeros(badj.n_slots, bool)
    if badj.n_slots == 0 or len(uids_np) == 0:
        return bits
    slots, _ = _uid_slots(badj, np.asarray(uids_np, np.uint32))
    bits[slots] = True
    return bits


def bits_to_uids(badj: BitAdjacency, bits: np.ndarray) -> np.ndarray:
    """bool[N] bitmap -> sorted uid uint32 array."""
    return np.sort(badj.slot_uids[np.asarray(bits, bool)])


# -- kernels -----------------------------------------------------------------


def _level(badj: BitAdjacency, f: jax.Array) -> jax.Array:
    """One frontier expansion: bool[N] -> bool[N] (reachable-in-1)."""
    return jnp.concatenate([
        _gathered_reach([b.in_nb for b in badj.buckets],
                        f.astype(jnp.uint32)) != 0,
        jnp.zeros((badj.n_slots - badj.n_covered,), jnp.bool_)])


def _gathered_reach(in_nbs, f: jax.Array) -> jax.Array:
    """Which rows of the in-neighbour matrices `in_nbs` a frontier
    over every slot reaches, in the matrices' order. `f` is uint32[N],
    bit b of a slot's word lane b's membership, and so is the answer:
    one gather an index serves every lane, because a gather costs the
    same whatever the width of what it fetches (bool, uint8 and int32
    within 12% on a v5e: PERF.md, PR 31). The matrices are ARGUMENTS,
    so that a jitted caller does not bake every edge into its program
    as a constant. Gathers every padded in-edge whatever the frontier
    holds: a level costs the same for every frontier."""
    fe = jnp.concatenate([f, jnp.zeros((1,), jnp.uint32)])
    parts = [jnp.bitwise_or.reduce(
        fe.at[nb].get(mode="promise_in_bounds"), axis=1)
        for nb in in_nbs]
    return jnp.concatenate(parts) if parts else jnp.zeros((0,), jnp.uint32)


def make_bfs_bits(badj: BitAdjacency, depth: int,
                  dedup: bool = True) -> Callable:
    """Compile BFS: seed bitmap bool[N] -> tuple of per-level frontier
    bitmaps (newly reached per level when dedup, raw reach otherwise).
    Matches @recurse semantics incl. loop:true via dedup=False
    (ref gql RecurseArgs.AllowLoop)."""

    def bfs(seed_bits: jax.Array):
        levels = []
        visited = seed_bits
        frontier = seed_bits
        for _ in range(depth):
            reach = _level(badj, frontier)
            if dedup:
                new = reach & ~visited
                visited = visited | new
            else:
                new = reach
            levels.append(new)
            frontier = new
        return tuple(levels)

    return jax.jit(bfs)


def bfs_bits_reach(badj: BitAdjacency, seeds_np: np.ndarray, depth: int,
                   dedup: bool = True) -> list[np.ndarray]:
    """Host wrapper: per-level sorted frontier uid arrays."""
    if badj.n_slots == 0:
        return [np.empty(0, np.uint32) for _ in range(depth)]
    fn = _bfs_cache(badj, depth, dedup)
    levels = fn(jnp.asarray(uids_to_bits(badj, seeds_np)))
    return [bits_to_uids(badj, np.asarray(lv)) for lv in levels]


def _bfs_cache(badj: BitAdjacency, depth: int, dedup: bool) -> Callable:
    cache = getattr(badj, "_bfs_cache", None)
    if cache is None:
        cache = badj._bfs_cache = {}
    fn = cache.get((depth, dedup))
    if fn is None:
        fn = cache[(depth, dedup)] = make_bfs_bits(badj, depth, dedup)
    return fn


# -- the served traversal: one program for the requests in flight -------------


# What a level costs on one v5e, measured (PERF.md, PR 31): a gathered
# in-edge (XLA's gather of one table element an index, padding
# included) and a streamed byte of the dense rows. A row of N bits
# costs N / 8 / DENSE_BYTES_PER_S however many in-edges it holds, so a
# slot with more than a handful of in-edges is cheaper as a row.
GATHER_SECONDS = 7.0e-9
DENSE_BYTES_PER_S = 6.5e11


# Traversals one call of bfs_traverse carries: the requests in flight
# over one adjacency ride it together (query/devicecall.py's
# Rendezvous), bit b of every word lane b's. A gathered class costs
# the same for 32 lanes as for one; the hub rows cost a lane's ANDs
# over every row word, which meet the time to stream the rows near
# eight lanes (PERF.md, PR 34).
LANES = 8

# the mesh axis a sharded traversal's destination rows are split over
# (parallel/mesh.py: uid-range shards of one predicate)
SHARD_AXIS = "uid"

# the hub rows' kernel: rows a grid step holds in VMEM, and the width
# (in words) a row is padded to so that a step's block is whole vregs
_HUB_TILE_ROWS = 256
_HUB_WORDS_UNIT = 128

# what a level without hub rows streams of them, and has
_NO_TILES = np.zeros(2, np.int32)


def hub_row_words(n_slots: int) -> int:
    """Words of one hub row over `n_slots` slots: a bit a slot, padded
    to whole vregs of 128 words, which is what the chip's tiled layout
    holds of a row anyway."""
    return -(-n_slots // (32 * _HUB_WORDS_UNIT)) * _HUB_WORDS_UNIT


def _chip_rows(rows: int, shards: int) -> int:
    """Rows of a block of `rows` one chip of `shards` holds: all of
    them on one chip; else an equal run a chip, whole groups of the
    eight rows _hub_kernel takes at a time."""
    return rows if shards == 1 else -(-rows // (8 * shards)) * 8


def _put_rows(block: np.ndarray, mesh) -> jax.Array:
    """`block` on the mesh, an equal run of its rows a chip."""
    return jax.device_put(block, jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(SHARD_AXIS)))


def attach_dense(badj: BitAdjacency, budget_bytes: int,
                 mesh=None) -> None:
    """Give the adjacency its hub rows: the degree classes whose rows
    are cheaper streamed than gathered, from the highest class down,
    as many whole classes as `budget_bytes` holds. On a skewed graph
    a small share of the slots holds most of the in-edges, so a
    bounded block of rows takes most of a level's gathers away. In a
    row of W words (hub_row_words) slot s is bit s // W of word s % W,
    so that a frontier over the slots folds into a row's layout
    without a transpose (_frontier_words).

    With a `mesh` the DESTINATION rows are split over its `uid` axis:
    `budget_bytes` is ONE chip's room and a chip holds its run of the
    hub rows and of every gathered class (bfs_traverse_sharded), so
    the chips together hold their number of budgets of rows."""
    shards = 1 if mesh is None else mesh.shape[SHARD_AXIS]
    n, words = badj.n_slots, hub_row_words(badj.n_slots)
    row_bytes = 4 * words
    first, rows = len(badj.buckets), 0
    for i in range(len(badj.buckets) - 1, -1, -1):
        b = badj.buckets[i]
        m = int(b.in_nb.shape[0])
        if b.degree * GATHER_SECONDS <= row_bytes / DENSE_BYTES_PER_S \
                or _chip_rows(rows + m, shards) * row_bytes > budget_bytes:
            break
        first, rows = i, rows + m
    badj.dense_from = first
    badj.dense = None
    if mesh is not None:
        badj.mesh = mesh
        # the one-chip copies go: a chip holds its run and no more
        # (a matrix then reads as its host copy, which the unserved
        # kernels above bake into their programs as they always did)
        for b in badj.buckets:
            b.in_nb = _host_nb(b)
        badj.shard_nbs = [_put_rows(np.pad(
            b.in_nb, ((0, -len(b.in_nb) % shards), (0, 0)),
            constant_values=n), mesh) for b in badj.gathered]
    if not rows:
        return
    start = badj.buckets[first].offset
    keys, bits = [], []
    for b in badj.buckets[first:]:
        nb = _host_nb(b)
        r, c = np.nonzero(nb < n)
        src = nb[r, c].astype(np.int64)
        keys.append((r + (b.offset - start)) * words + src % words)
        bits.append(np.uint32(1) << (src // words).astype(np.uint32))
    # OR the bits that share a word, then one write a word
    keys, bits = np.concatenate(keys), np.concatenate(bits)
    order = np.argsort(keys, kind="stable")
    keys, bits = keys[order], bits[order]
    at = np.flatnonzero(np.r_[True, keys[1:] != keys[:-1]])
    # (split over chips, the runs' padding lies behind the last row)
    block = np.zeros(shards * _chip_rows(rows, shards) * words, np.uint32)
    block[keys[at]] = np.bitwise_or.reduceat(bits, at)
    block = block.reshape(-1, words)
    badj.dense = jnp.asarray(block) if mesh is None \
        else _put_rows(block, mesh)


def resident_bytes(badj: BitAdjacency) -> int:
    """Bytes of the adjacency on the device, all chips together: the
    in-neighbour matrices and the hub rows as the served traversal
    holds them, and the slots' uids where the served shortest path
    has asked for them."""
    held = badj.shard_nbs if badj.mesh is not None \
        else [b.in_nb for b in badj.buckets]
    return sum(int(a.nbytes) for a in (
        *held, *(x for x in (badj.dense, badj.uids_dev) if x is not None)))


def _host_nb(b: RevBucket) -> np.ndarray:
    """A degree class's in-neighbour matrix on the host."""
    return b.in_nb_host if b.in_nb_host is not None \
        else np.asarray(b.in_nb)


def level_seconds(badj: BitAdjacency) -> float:
    """What one level of the served traversal costs on the device AT
    MOST, from the adjacency's layout alone: the gathered classes'
    padded in-edges and the dense rows' bytes. An upper bound: a
    level streams only the tiles of rows some live lane has not
    settled (_hub_pending): all of them at a call's second and third
    levels, fewer after, and its first level reads the roots' columns
    where those are cheaper still (columns_cheaper). It is what the
    executor's gate prices a level with."""
    gathered = sum(int(b.in_nb.size) for b in badj.gathered)
    dense = 0 if badj.dense is None else int(badj.dense.nbytes)
    # split over chips, a level costs what ONE chip gathers and
    # streams of it (the collective that follows is a few megabytes)
    return _streamed_seconds(gathered, dense) / badj.shards


def _streamed_seconds(gathered: int, dense_bytes: int) -> float:
    """A level that gathers `gathered` padded in-edges and streams
    `dense_bytes` of hub rows, on one chip."""
    return gathered * GATHER_SECONDS + dense_bytes / DENSE_BYTES_PER_S


def columns_cheaper(n_seeds: int, rows: int, words: int,
                    gathered: int) -> bool:
    """Whether a call's FIRST level costs less read from the columns
    of its `n_seeds` seed slots (_chip_columns) than streamed and
    gathered as any level (_chip_reach), from ONE chip's shapes
    alone: its `rows` hub rows of `words` words and the `gathered`
    padded in-edges of its other classes. A seed's column of the hub
    rows costs the (8, 128) tiles that hold it, a vreg's width of
    every row, and its column of a gathered class a compare an index,
    priced as the index's bytes; the level they replace costs what
    level_seconds says. A few roots read a sliver of the rows; past
    a row's count of column blocks (and what the gathers cost) the
    stream is the cheaper again. And never past the seed slots the
    column level was TIMED with: a larger root set takes the level
    every call took before there was another."""
    columns = n_seeds * 4 * (rows * _HUB_WORDS_UNIT + gathered) \
        / DENSE_BYTES_PER_S
    return n_seeds <= _COLUMN_SEEDS_TIMED \
        and columns < _streamed_seconds(gathered, 4 * rows * words)


# The most seed slots the column level was timed with on the chip
# (PERF.md, PR 43: 8 and 32, at both k-hop cells' shapes). The prices
# above put the turn further out (64 slots at the one-chip cell's
# shapes, 512 at a chip's of the four-chip cell), but the compare of
# every gathered index with every seed is priced as the index's bytes
# and was not timed there, and no benchmark cell sends such a call.
_COLUMN_SEEDS_TIMED = 32


def _lane_planes(words_by_slot: jax.Array, lanes: int) -> jax.Array:
    """uint32[N] lane words -> uint32[lanes, N] of 0 / 1: lane b's
    membership of every slot."""
    b = jnp.arange(lanes, dtype=jnp.uint32)[:, None]
    return (words_by_slot[None, :] >> b) & jnp.uint32(1)


def _hub_kernel(live_ref, plan_ref, fw_ref, rows_ref, out_ref):
    """A tile of hub rows against every LIVE lane's frontier, the
    tile read from HBM once, and not at all where no live lane needs
    it (_hub_plan). live_ref int32[1 + LANES] (SMEM): how many lanes
    are live, then their numbers; plan_ref int32[2 tiles] (SMEM):
    _hub_plan's, of which the kernel reads whether the step's tile is
    needed (the index maps read the rest); fw_ref uint32[LANES, 8,
    W]: a lane's frontier in the rows' own layout (_frontier_words),
    on eight sublanes; rows_ref uint32[T, W]: the step's tile where
    it is needed, else whatever block the pipeline already held;
    out_ref uint32[T, 128]: bit b of a row's words set where the row
    meets lane b's frontier in that column of vregs (the caller ORs
    the 128 together), all 0 for a tile not needed. All elementwise
    on whole vregs: no reduction across lanes of a vreg, no
    relayout."""
    from jax.experimental import pallas as pl

    n_live = live_ref[0]
    needed = (plan_ref[pl.num_programs(0) + pl.program_id(0)] & 1) != 0
    chunks = rows_ref.shape[1] // 128

    def group(g, carry):
        r0 = pl.multiple_of(g * 8, 8)
        rows = rows_ref[pl.ds(r0, 8), :]

        def lane(i, packed):
            b = live_ref[1 + i]
            met = rows & fw_ref[b]
            parts = [met[:, c * 128:(c + 1) * 128] for c in range(chunks)]
            while len(parts) > 1:       # a tree: no chain of 43 ORs
                parts = [parts[j] | parts[j + 1] if j + 1 < len(parts)
                         else parts[j] for j in range(0, len(parts), 2)]
            bit = jnp.left_shift(jnp.int32(1), b).astype(jnp.uint32)
            return packed | jnp.where(parts[0] != 0, bit, jnp.uint32(0))

        out_ref[pl.ds(r0, 8), :] = jax.lax.fori_loop(
            0, n_live, lane, jnp.zeros((8, 128), jnp.uint32))
        return carry

    @pl.when(needed)
    def _():
        jax.lax.fori_loop(0, rows_ref.shape[0] // 8, group, 0)

    @pl.when(jnp.logical_not(needed))
    def _():
        out_ref[...] = jnp.zeros(out_ref.shape, jnp.uint32)


def _frontier_words(frontier: jax.Array, words: int,
                    lanes: int) -> jax.Array:
    """uint32[N] lane words -> uint32[lanes, words]: a lane's frontier
    as the hub rows hold their in-neighbours, bit s // words of word
    s % words slot s: 32 runs of `words` slots, each shifted to its
    bit and summed, the slots on the minor axis throughout."""
    planes = _lane_planes(
        jnp.pad(frontier, (0, 32 * words - frontier.shape[0])), lanes)
    return jnp.sum(planes.reshape(lanes, 32, words)
                   << jnp.arange(32, dtype=jnp.uint32)[:, None],
                   axis=1, dtype=jnp.uint32)


def _hub_tile(rows: int, tile: int) -> int:
    """Rows a grid step of _hub_call holds of a block of `rows`:
    `tile`, or the whole of a smaller block, in groups of eight."""
    return min(tile, -(-rows // 8) * 8)


def _hub_pending(reached: jax.Array, active: jax.Array, start: int,
                 rows: int, chips: int = 1) -> jax.Array:
    """Which lanes still need which hub row: uint32[chips, rows a
    chip holds] lane words, a chip's run of the rows a line. The hub
    rows are the slots [start, start + rows); a row is a DESTINATION,
    and all a level learns from it is whether that slot is reached,
    so a lane that has the slot in `reached` already, or holds no
    frontier (`active`), needs it no more. The padding behind the
    last row (_chip_rows) is needed by nobody."""
    run = ~reached[start:start + rows] & active
    held = _chip_rows(rows, chips)
    return jnp.pad(run, (0, chips * held - rows)).reshape(chips, held)


def _tiles_needed(pending: jax.Array, tile: int) -> jax.Array:
    """_hub_pending's words -> bool[chips, tiles]: the tiles of
    `tile` rows in which some lane still needs some row."""
    chips, rows = pending.shape
    tiles = -(-rows // tile)
    return jnp.any(jnp.pad(pending, ((0, 0), (0, tiles * tile - rows)))
                   .reshape(chips, tiles, tile) != 0, axis=2)


def _hub_plan(needed: jax.Array) -> jax.Array:
    """bool[tiles] -> int32[2 tiles], what _hub_call's grid does,
    step by step: the needed tiles first, in their order, then the
    others. The pipeline issues a step's copy one step ahead, so a
    needed tile behind one that is not would wait for its rows with
    nothing to overlap them; in one run the copies overlap the ANDs
    as in a stream of every row. [:tiles]: the block of rows a
    step's index map names, its own tile's where that is needed,
    else the last needed one's, which the pipeline holds already: no
    copy is issued for it (where none is needed, block 0, once).
    [tiles:]: twice the tile whose words the step writes, plus 1
    where it is needed."""
    tiles = needed.shape[0]
    order = jnp.argsort(~needed, stable=True).astype(jnp.int32)
    n_needed = jnp.sum(needed, dtype=jnp.int32)
    is_needed = jnp.arange(tiles, dtype=jnp.int32) < n_needed
    rows = jnp.where(is_needed, order, order[jnp.maximum(n_needed - 1, 0)])
    return jnp.concatenate([rows, 2 * order + is_needed])


def _hub_reach(dense: jax.Array, frontier: jax.Array, active: jax.Array,
               pending: jax.Array, lanes: int, tile: int, chip=0):
    """Which hub rows a frontier reaches, lane by lane -> (uint32[rows]
    lane words, int32[2] tiles streamed and tiles there are, over all
    chips). `frontier` uint32[N] lane words over every slot, `active`
    the word of the lanes that hold any, `pending` _hub_pending's
    words for every chip and `chip` the one whose rows `dense` is.
    Only the tiles some live lane still needs are read (_tiles_needed),
    ONCE for all lanes; the words of the others are 0, which changes
    neither `new` nor `reached` in _traverse_lanes: every bit left
    out is in both `reached` and `visited` already. On the chip by
    _hub_kernel, a tile of rows in VMEM and a loop over the live
    lanes; elsewhere (the CPU the tests run on) by the same ANDs in
    plain jnp over every row, the same tiles' words then put to 0."""
    rows = dense.shape[0]
    tile = _hub_tile(rows, tile)
    needed = _tiles_needed(pending, tile)
    # a chip's pipeline fetches one block whatever its flags say
    tiles = jnp.stack([
        jnp.sum(jnp.maximum(jnp.sum(needed, axis=1, dtype=jnp.int32), 1)),
        jnp.int32(needed.size)])
    fw = _frontier_words(frontier, dense.shape[1], lanes)
    if jax.default_backend() != "tpu":
        bit = jnp.uint32(1) << jnp.arange(lanes, dtype=jnp.uint32)
        met = jnp.any((dense[None, :, :] & fw[:, None, :]) != 0, axis=2)
        reach = jnp.sum(jnp.where(met, bit[:, None], jnp.uint32(0)),
                        axis=0, dtype=jnp.uint32)
        return jnp.where(jnp.repeat(needed[chip], tile)[:rows], reach,
                         jnp.uint32(0)), tiles
    return jnp.bitwise_or.reduce(_hub_call(
        dense, fw, active, _hub_plan(needed[chip]), lanes, tile),
        axis=1), tiles


def _hub_call(dense, fw, active, plan, lanes: int, tile: int,
              interpret: bool = False):
    """_hub_kernel over the tiles of `dense` that `plan` (_hub_plan)
    says are needed -> uint32[rows, 128]. `tile` as _hub_tile gives
    it. Both index maps read the plan: a step writes the words of
    the tile the plan gives it, and one whose tile is not needed
    names the block of rows of the step before: the pipeline copies
    a block only when its index changes."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, words = dense.shape
    is_live = ((active >> jnp.arange(lanes, dtype=jnp.uint32)) & 1) != 0
    live = jnp.concatenate([
        jnp.sum(is_live, dtype=jnp.int32)[None],
        jnp.argsort(~is_live, stable=True).astype(jnp.int32)])
    tiles = pl.cdiv(rows, tile)
    return pl.pallas_call(
        _hub_kernel,
        out_shape=jax.ShapeDtypeStruct((rows, 128), jnp.uint32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(tiles,),
            in_specs=[
                pl.BlockSpec((lanes, 8, words),
                             lambda i, live, plan: (0, 0, 0)),
                pl.BlockSpec((tile, words),
                             lambda i, live, plan: (plan[i], 0))],
            out_specs=pl.BlockSpec(
                (tile, 128), lambda i, live, plan: (plan[tiles + i] >> 1, 0))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=2 * 4 * words * (tile + 8 * lanes)
            + (8 << 20)),
        interpret=interpret,
        name="bfs_hub_rows",
    )(live, plan, jnp.broadcast_to(fw[:, None, :], (lanes, 8, words)), dense)


@functools.partial(jax.jit, static_argnames=(
    "n_slots", "n_covered", "lanes", "tile", "columns"))
def bfs_traverse(in_nbs, dense, riders, *, n_slots: int, n_covered: int,
                 lanes: int, tile: int = _HUB_TILE_ROWS,
                 columns: Optional[bool] = None):
    """`@recurse(loop: false)` whole, for every LANE of the call: up
    to `lanes` traversals over one adjacency in one program, each
    with its own roots, depth, visited set and count. The frontier,
    the visited set and the reached set are uint32[N] LANE WORDS: bit
    b of a slot's word is lane b's membership, so a lane never sees
    another's bits and a level's gathers serve all of them.

    in_nbs      the gathered degree classes' in-neighbour matrices
    dense       the other classes' rows (attach_dense), or None
    riders      int32[2 S + lanes], ONE upload a call: S root slots of
                all lanes, padded with n_slots (the dummy slot,
                dropped); beside each its lane's bit (0 on padding; a
                (slot, lane) pair appears once); then a depth a lane,
                RUNTIME values: levels the lane expands (edge hops),
                0 for a lane nobody rides; a lane stops after its
                own, whatever the others do
    tile        hub rows a step of the rows' kernel holds
    columns     whether the first level is read from the S seeds'
                columns (_chip_columns) and not gathered and streamed;
                None: where the shapes say that is cheaper
                (columns_cheaper). The results are the same bit for
                bit but for the tiles streamed (the tests' to set)
    ->  (tally, reached)
    tally       int32[3, lanes], ONE fetch a call. Row 0: distinct
                slots a lane reached through an edge in 1..depth hops
                (a root counts where an edge leads back to it): what
                DQL's uid variable on the child holds. Row 1: levels
                a lane expanded; it ends early once a level finds it
                no new slot, and the loop ends when no lane is alive.
                Row 2, the call's, not a lane's: [0] tiles of hub
                rows the levels streamed (none for a level read from
                columns), [1] tiles they would have streamed had
                every level read every row (levels x tiles), [2]
                levels read from columns (0 or 1), the rest 0
    reached     uint32[N] lane words: the reached sets themselves.
                They stay on the device unless a lane's reader wants
                the uids (lane_uids)
    """
    return _traverse_lanes(
        *_one_chip(in_nbs, dense, riders, n_slots, n_covered, lanes, tile,
                   columns), riders, n_slots, lanes)


def _one_chip(in_nbs, dense, riders, n_slots: int, n_covered: int,
              lanes: int, tile: int, columns: Optional[bool]):
    """_traverse_lanes' (level, first, whole) for an adjacency held
    whole on one chip, as bfs_traverse's arguments describe it."""
    rows = 0 if dense is None else dense.shape[0]

    def level(frontier, active, reached):
        pending = None if dense is None else _hub_pending(
            reached, active, n_covered - rows, rows)
        return _chip_reach(in_nbs, dense, frontier, active, pending,
                           lanes, tile)

    def whole(share):
        return jnp.concatenate([
            share, jnp.zeros((n_slots - n_covered,), jnp.uint32)])

    return level, _first_level(in_nbs, dense, riders, lanes, tile,
                               columns), whole


def _first_level(in_nbs, dense, riders, lanes: int, tile: int,
                 columns: Optional[bool], chips: int = 1):
    """_traverse_lanes' `first` for ONE chip's `in_nbs` and `dense`
    of an adjacency split over `chips`: _chip_columns over them where
    `columns` (bfs_traverse's) says so, else None."""
    rows, words = (0, 0) if dense is None else dense.shape
    if columns is None:
        columns = columns_cheaper(
            (riders.shape[0] - lanes) // 2, rows, words,
            sum(int(nb.size) for nb in in_nbs))
    if not columns:
        return None
    # what the level streams of the hub rows, and what a stream of
    # every row would have: _hub_reach's count, over all chips
    tiles = jnp.array(
        [0, chips * -(-rows // _hub_tile(rows, tile)) if rows else 0],
        jnp.int32)
    return lambda slots, bits: (_chip_columns(in_nbs, dense, slots, bits),
                                tiles)


def _traverse_lanes(level, first, whole, riders, n_slots: int,
                    lanes: int, ends=None):
    """bfs_traverse's loop: the riders unpacked, every lane run to
    its own depth, -> (tally, reached). `level(frontier, active,
    reached) -> (share, tiles)`: the rows this chip holds that a
    frontier reaches (uint32 lane words, as `frontier` and `reached`
    are over every slot) and int32[2], the hub-row tiles the level
    streamed and had; `whole(share)` -> the level's reach over every
    slot, uint32[N]. `first(seed slots, their lane bits) -> (share,
    tiles)`, or None: a call's first level answered from its seeds
    and not from the frontier they make, which is the same set.

    `ends` (bfs_paths'): int32[lanes], the slot at which a lane's
    search ENDS (n_slots: none). A lane that has reached its end
    holds no frontier from then on, so the loop is over when every
    lane has met its end, emptied its frontier or spent its depth;
    and the level at which a lane reached a slot is kept, ->
    (tally, reached, levels int32[lanes, N], INT32_INF where it did
    not). Without it the loop is the k-hop program's, op for op."""
    n_seeds = (riders.shape[0] - lanes) // 2
    seed_slots = riders[:n_seeds]
    seed_bits = jax.lax.bitcast_convert_type(
        riders[n_seeds:2 * n_seeds], jnp.uint32)
    depths = riders[2 * n_seeds:]
    lane = jnp.arange(lanes, dtype=jnp.uint32)
    seed = jnp.zeros((n_slots + 1,), jnp.uint32).at[seed_slots].add(
        seed_bits, mode="promise_in_bounds")[:n_slots]

    def expanding(lvl):
        """The word of the lanes whose depth reaches past `lvl`."""
        return jnp.sum(jnp.where(depths > lvl, jnp.uint32(1) << lane,
                                 jnp.uint32(0)), dtype=jnp.uint32)

    def unmet(seen):
        """The word of the lanes whose end is not in `seen`."""
        hit = (seen.at[ends].get(mode="fill", fill_value=0) >> lane) & 1
        return jnp.sum(jnp.where(hit == 0, jnp.uint32(1) << lane,
                                 jnp.uint32(0)), dtype=jnp.uint32)

    def cond(state):
        return state[6] != 0

    def body(state):
        lvl, frontier, visited, reached, levels_run, tiles, active = \
            state[:7]
        if first is None:
            share, streamed = level(frontier, active, reached)
        else:
            # (a lane of depth 0 expands nothing: its seeds' bits go)
            share, streamed = jax.lax.cond(
                lvl == 0,
                lambda: first(seed_slots, seed_bits & expanding(0)),
                lambda: level(frontier, active, reached))
        reach = whole(share)
        new = reach & ~visited
        # a lane goes on only within its depth and from a new slot
        frontier = new & expanding(lvl + 1)
        kept = ()
        if ends is not None:
            # ... and only until it has met its end
            frontier = frontier & unmet(visited | new)
            kept = (jnp.where(_lane_planes(new, lanes) != 0, lvl + 1,
                              state[7]),)
        return (lvl + 1, frontier, visited | new, reached | reach,
                levels_run + ((active >> lane) & 1).astype(jnp.int32),
                tiles + streamed, jnp.bitwise_or.reduce(frontier)) + kept

    start = seed & expanding(jnp.int32(0))
    kept = ()
    if ends is not None:
        start = start & unmet(seed)
        kept = (jnp.where(_lane_planes(seed, lanes) != 0, jnp.int32(0),
                          INT32_INF),)
    active = jnp.bitwise_or.reduce(start)
    _, _, _, reached, levels_run, tiles, _, *kept = jax.lax.while_loop(
        cond, body, (jnp.int32(0), start, seed,
                     jnp.zeros((n_slots,), jnp.uint32),
                     jnp.zeros((lanes,), jnp.int32), _NO_TILES, active)
        + kept)
    counts = jnp.sum(_lane_planes(reached, lanes), axis=1, dtype=jnp.int32)
    # the first level ran where any lane held a frontier at all
    from_columns = jnp.int32(first is not None) * (active != 0)
    return (jnp.stack([counts, levels_run, jnp.pad(
        jnp.append(tiles, from_columns), (0, lanes - 3))]), reached, *kept)


def _chip_reach(in_nbs, dense, frontier, active, pending, lanes: int,
                tile: int, chip=0):
    """ONE chip's share of a level (all of it where there is one
    chip): the rows it holds of every gathered class, then of the hub
    rows (those some lane still needs: `pending`, `chip` as
    _hub_reach takes them), against the whole frontier -> (uint32
    lane words, a word a row it holds, padding rows 0; the level's
    hub-row tiles as _hub_reach counts them)."""
    parts, tiles = [_gathered_reach(in_nbs, frontier)], _NO_TILES
    if dense is not None:
        hub, tiles = _hub_reach(dense, frontier, active, pending, lanes,
                                tile, chip)
        parts.append(hub)
    return jnp.concatenate(parts), tiles


def _chip_columns(in_nbs, dense, slots, bits):
    """_chip_reach's lane words for a frontier of a FEW slots, read
    from the slots' COLUMNS: `slots` int32[S] (the dummy slot on
    padding) and `bits` uint32[S], the lanes each is in (0 on
    padding). The structures are the reverse adjacency, a row a
    destination, so the out-neighbours of slot s are the rows whose
    in-neighbours hold s: of a gathered class those with an entry
    EQUAL to s (a compare an index: no gather), of the hub rows
    those with bit s // W of word s % W set (_hub_columns). The
    dummy slot equals every row's padding, and carries no bit."""
    parts = [jnp.bitwise_or.reduce(
        jnp.where(nb[:, :, None] == slots, bits, jnp.uint32(0)),
        axis=(1, 2)) for nb in in_nbs]
    if dense is not None:
        parts.append(_hub_columns(dense, slots, bits))
    return jnp.concatenate(parts) if parts else jnp.zeros((0,), jnp.uint32)


# rows a grid step of the columns' kernel holds: blocks of a megabyte
_COLUMN_TILE_ROWS = 2048


def _hub_columns(dense, slots, bits):
    """The hub rows a few slots point at -> uint32[rows] lane words.
    Slot s is bit s // W of word s % W of every row (attach_dense),
    and a row's words lie in (8, 128) tiles, so a seed costs the
    block of 128 word columns that holds its word, rows x 512 B,
    where the stream of every row costs rows x 4 W. On the chip by
    _column_kernel, whose index map names a seed's block of columns
    (XLA's own slice of it cannot know the offset is a whole tile's
    and reads at half the stream's rate on a v5e: PERF.md, PR 43);
    elsewhere (the CPU the tests run on) the same block sliced out
    in plain jnp, a seed at a time."""
    rows, words = dense.shape
    word = slots % words
    block, column = word // _HUB_WORDS_UNIT, word % _HUB_WORDS_UNIT
    bit = (slots // words).astype(jnp.uint32)
    if jax.default_backend() == "tpu":
        return jnp.bitwise_or.reduce(
            _columns_call(dense, block, column, bit, bits), axis=1)
    columns = jnp.arange(_HUB_WORDS_UNIT, dtype=jnp.int32)

    def one(i, reach):
        held = jax.lax.dynamic_slice(
            dense, (0, block[i] * _HUB_WORDS_UNIT),
            (rows, _HUB_WORDS_UNIT))
        hit = ((held >> bit[i]) & 1 != 0) & (columns == column[i])
        return reach | jnp.where(jnp.any(hit, axis=1), bits[i],
                                 jnp.uint32(0))

    return jax.lax.fori_loop(0, slots.shape[0], one,
                             jnp.zeros((rows,), jnp.uint32))


def _column_kernel(seeds_ref, rows_ref, out_ref):
    """A tile of rows' block of 128 word columns against ONE seed:
    seeds_ref int32[4 S] (SMEM): every seed's block of columns (the
    index map's), its column in the block, its bit in the word and
    its lane bits; rows_ref uint32[T, 128]: the step's rows, the
    seed's block of them; out_ref uint32[T, 128]: the seeds' lane
    bits ORed where a row's word holds a seed's bit, in the seed's
    column (the caller ORs the 128 together). The seeds are the
    grid's inner axis: a tile's words stay in VMEM for all of them."""
    from jax.experimental import pallas as pl

    n, s = seeds_ref.shape[0] // 4, pl.program_id(1)

    @pl.when(s == 0)
    def _():
        out_ref[...] = jnp.zeros(out_ref.shape, jnp.uint32)

    hit = ((rows_ref[...] >> seeds_ref[2 * n + s].astype(jnp.uint32)) & 1
           != 0) & (jax.lax.broadcasted_iota(jnp.int32, rows_ref.shape, 1)
                    == seeds_ref[n + s])
    out_ref[...] |= jnp.where(
        hit, seeds_ref[3 * n + s].astype(jnp.uint32), jnp.uint32(0))


def _columns_call(dense, block, column, bit, bits,
                  interpret: bool = False):
    """_column_kernel over every tile of `dense`'s rows and every
    seed -> uint32[rows, 128]. A step copies rows x 128 words of the
    ONE block of columns its seed names: the pipeline reads the
    (8, 128) tiles that hold the seed's word and no other."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows = dense.shape[0]
    tile = _hub_tile(rows, _COLUMN_TILE_ROWS)
    seeds = jnp.concatenate([block, column, bit.astype(jnp.int32),
                             bits.astype(jnp.int32)])
    return pl.pallas_call(
        _column_kernel,
        out_shape=jax.ShapeDtypeStruct((rows, _HUB_WORDS_UNIT), jnp.uint32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(pl.cdiv(rows, tile), block.shape[0]),
            in_specs=[pl.BlockSpec((tile, _HUB_WORDS_UNIT),
                                   lambda i, s, seeds: (i, seeds[s]))],
            out_specs=pl.BlockSpec((tile, _HUB_WORDS_UNIT),
                                   lambda i, s, seeds: (i, 0))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="bfs_hub_columns",
    )(seeds, dense)


def _whole_reach(shares, part_rows, n_slots: int):
    """The chips' shares of a level, uint32[chips, L] as _chip_reach
    gives them, -> the level's reach in slot order, uint32[N]: a
    part's rows (a gathered class, or the hub rows) lie chip after
    chip, the padding behind the last of them. `part_rows`: (rows,
    rows a chip holds) a part, in slot order."""
    parts, at = [], 0
    for rows, held in part_rows:
        parts.append(shares[:, at:at + held].reshape(-1)[:rows])
        at += held
    covered = sum(rows for rows, _ in part_rows)
    parts.append(jnp.zeros((n_slots - covered,), jnp.uint32))
    return jnp.concatenate(parts)


@functools.partial(jax.jit, static_argnames=(
    "mesh", "part_rows", "n_slots", "lanes", "tile", "columns"))
def bfs_traverse_sharded(in_nbs, dense, riders, *, mesh, part_rows,
                         n_slots: int, lanes: int,
                         tile: int = _HUB_TILE_ROWS,
                         columns: Optional[bool] = None):
    """bfs_traverse with the adjacency split over the chips of
    `mesh`'s `uid` axis: same riders, same (tally, reached), bit for
    bit but for the tiles of hub rows, which a chip counts in its
    own run. The DESTINATION rows are split: a chip holds a run of
    every gathered class's rows (`in_nbs`) and of the hub rows
    (`dense`), as attach_dense laid them out, and the lane words of
    frontier, visited and reached sets whole. A level: every chip
    works out which of ITS rows the frontier reaches (_chip_reach: a
    share of the gathers and, of the rows some lane still needs, of
    the stream; at a call's first level the seeds' columns of ITS
    rows instead, as bfs_traverse's `columns` has it, by a chip's
    shapes), then ONE collective, an all-gather of the shares (a
    lane word a covered slot, 4 B a vertex over all chips), hands
    every chip the whole reach, from which each works out the next
    frontier for itself, and from the reached sets which tiles of
    rows EVERY chip streams next, so that the count of them needs no
    collective of its own. The loop runs inside the shard_map, so
    nothing else crosses chips; the results come out replicated and
    are read from one."""
    P = jax.sharding.PartitionSpec
    rows_spec = P(SHARD_AXIS)
    chips = mesh.shape[SHARD_AXIS]
    start = sum(rows for rows, _ in part_rows[:len(in_nbs)])

    def per_chip(in_nbs, dense, riders):
        def level(frontier, active, reached):
            pending = None if dense is None else _hub_pending(
                reached, active, start, part_rows[-1][0], chips)
            return _chip_reach(
                in_nbs, dense, frontier, active, pending, lanes, tile,
                jax.lax.axis_index(SHARD_AXIS))

        def whole(share):
            return _whole_reach(jax.lax.all_gather(share, SHARD_AXIS),
                                part_rows, n_slots)

        return _traverse_lanes(
            level, _first_level(in_nbs, dense, riders, lanes, tile,
                                columns, chips),
            whole, riders, n_slots, lanes)

    # every chip computes the same lane state from the gathered
    # shares: replicated by construction, which the checker cannot see
    return jax.shard_map(
        per_chip, mesh=mesh,
        in_specs=([rows_spec] * len(in_nbs),
                  None if dense is None else rows_spec, P()),
        out_specs=(P(), P()), check_vma=False)(in_nbs, dense, riders)


def _pack_riders(n_slots: int, riders: list) -> np.ndarray:
    """`riders` ([(root slots, depth)], a lane each) as the ONE int32
    upload of a call (bfs_traverse's `riders`)."""
    from dgraph_tpu.ops.uidvec import pad_to
    if not 0 < len(riders) <= LANES:
        raise ValueError(f"{len(riders)} riders for {LANES} lanes")
    n_seeds = pad_to(sum(len(slots) for slots, _ in riders))
    packed = np.zeros(2 * n_seeds + LANES, np.int32)
    packed[:n_seeds] = n_slots
    at = 0
    for b, (slots, depth) in enumerate(riders):
        packed[at:at + len(slots)] = slots
        packed[n_seeds + at:n_seeds + at + len(slots)] = 1 << b
        packed[2 * n_seeds + b] = min(depth, 2**31 - 1)
        at += len(slots)
    return packed


def traverse(badj: BitAdjacency, riders: list,
             tile: int = _HUB_TILE_ROWS):
    """The served traversal over an adjacency as attach_dense left
    it, one lane a rider: `riders` is [(root slots as seed_slots
    gives them, depth)], at most LANES of them; rider i is lane i of
    both results. bfs_traverse on one chip, bfs_traverse_sharded
    where the rows are split over a mesh. ONE compiled shape an
    adjacency while the riders' roots number eight or fewer together
    (then a power of two), whatever their count and depths. `tile`:
    hub rows a step of the rows' kernel holds (the tests' to set)."""
    packed = _pack_riders(badj.n_slots, riders)
    if badj.mesh is None:
        return bfs_traverse(
            [b.in_nb for b in badj.gathered], badj.dense, packed,
            n_slots=badj.n_slots, n_covered=badj.n_covered, lanes=LANES,
            tile=tile)
    return bfs_traverse_sharded(
        badj.shard_nbs, badj.dense, packed, mesh=badj.mesh,
        part_rows=shard_parts(badj), n_slots=badj.n_slots, lanes=LANES,
        tile=tile)


def shard_parts(badj: BitAdjacency) -> tuple:
    """(rows, rows a chip holds) of every part of a sharded
    adjacency, in slot order: the gathered classes, then the hub
    rows."""
    shards = badj.shards
    parts = [(int(b.in_nb.shape[0]), int(nb.shape[0]) // shards)
             for b, nb in zip(badj.gathered, badj.shard_nbs)]
    if badj.dense is not None:
        parts.append((badj.dense_rows, int(badj.dense.shape[0]) // shards))
    return tuple(parts)


def seed_slots(badj: BitAdjacency, uids32: np.ndarray
               ) -> Optional[np.ndarray]:
    """Root uids -> their int32 slots (each once) for traverse, or
    None where the adjacency does not know one of them (the caller
    answers on the host, as for any uid the tile cannot speak for)."""
    slots, hit = _uid_slots(badj, uids32)
    if not hit.all():
        return None
    return np.unique(slots).astype(np.int32)


def lane_uids(badj: BitAdjacency, reached: np.ndarray,
              lane: int) -> np.ndarray:
    """One lane of bfs_traverse's `reached` -> sorted uid uint32
    array."""
    return bits_to_uids(badj, (reached >> np.uint32(lane)) & np.uint32(1))


# -- the served shortest path: the lanes' searches, then the walk ---------------


# columns the path matrix has at least: a path of up to 15 hops, the
# depth of the social benchmarks' query, in ONE compiled shape
_PATH_WIDTH = 16


def path_width(depth: int, n_slots: int) -> int:
    """Columns of bfs_paths' path matrix for a call whose deepest
    rider asks `depth` hops: a slot a hop and the source's, a power of
    two (a compiled shape each), never more than the adjacency has
    slots for."""
    from dgraph_tpu.ops.uidvec import pad_to
    return pad_to(min(depth, max(n_slots - 1, 0)) + 1, _PATH_WIDTH)


@functools.partial(jax.jit, static_argnames=(
    "n_slots", "n_covered", "lanes", "width", "tile", "columns"))
def bfs_paths(in_nbs, dense, slot_uids, riders, *, n_slots: int,
              n_covered: int, lanes: int, width: int = _PATH_WIDTH,
              tile: int = _HUB_TILE_ROWS, columns: Optional[bool] = None):
    """`shortest(from:, to:, depth:)` over one predicate, unweighted,
    one path, for every LANE of the call: up to `lanes` pairs over one
    adjacency in one program. The adjacency is the TRANSPOSED tile of
    the direction the paths follow: a row a vertex that has an
    out-edge, holding its OUT-neighbours (`in_nbs`, `dense`: what
    bfs_traverse takes, of the transposed edges), so that a level of
    the k-hop loop, run from a lane's TARGET, reaches the vertices one
    hop further FROM which the target is reached: the level at which
    a lane reaches a slot is that slot's distance TO the target. The
    loop is bfs_traverse's (_traverse_lanes: the same gathers, hub
    rows, tiles skipped and first level from the targets' columns)
    and it ENDS when every lane has met its source, emptied its
    frontier or spent its depth: 3 to 6 levels between two profiles
    of a social graph, whatever depth the query allows.

    Then the walk, on the device: from a lane's source, at every hop
    the out-neighbour of the SMALLEST UID among those one level
    nearer the target, which read from the source is the
    lexicographically least of the shortest paths: the one path every
    tier gives (docs/deployment.md, "shortest"). A hop reads the row
    of the slot it stands on (a gathered class's indices, or a hub
    row's bits), a lane's levels at those slots and their uids.

    slot_uids   uint32[N]: the uid in every slot (the tie rule's)
    riders      int32[2 S + 2 lanes], ONE upload a call:
                bfs_traverse's riders with a lane's TARGET as its one
                root and the hops it allows as its depth, then a
                SOURCE slot a lane (n_slots for a lane nobody rides)
    width       columns of the path matrix (path_width)
    ->  int32[lanes + 1, 2 + width], ONE fetch a call. Row b, lane
        b's: [0] the path's hops, -1 where the target is not reached
        from the source within the lane's depth; [1] levels the lane
        expanded; [2:] the path's slots from the source to the
        target, n_slots behind them (and AT a hop where the walk
        found no neighbour one level nearer, which a sound tile never
        shows: the caller then answers as for a tile that cannot
        speak). Row `lanes`, the call's: [0] levels the loop ran,
        [1] tiles of hub rows they streamed, [2] tiles a stream of
        every row at every level reads, [3] levels read from columns.
    """
    ends = riders[-lanes:]
    riders = riders[:-lanes]
    tally, _, levels = _traverse_lanes(
        *_one_chip(in_nbs, dense, riders, n_slots, n_covered, lanes, tile,
                   columns), riders, n_slots, lanes, ends=ends)
    lane = jnp.arange(lanes)
    far = jnp.full((lanes, 1), INT32_INF)
    levels_ext = jnp.concatenate([levels, far], axis=1)
    hops = levels_ext[lane, ends]
    hops = jnp.where(hops == INT32_INF, -1, hops)
    uid_ext = jnp.concatenate([slot_uids, jnp.zeros((1,), jnp.uint32)])
    no_uid = jnp.uint32(0xFFFFFFFF)

    def least(ok, uids, slots):
        """Of the candidates `ok` ([lanes, C]) the slot of the least
        uid a lane, and whether it has any."""
        at = jnp.argmin(jnp.where(ok, uids, no_uid), axis=1)
        return jnp.broadcast_to(slots, ok.shape)[lane, at], \
            jnp.any(ok, axis=1)

    def hop(state):
        k, cur, path = state
        # the level of a lane's k-th slot, where its path has one
        want = jnp.where(k <= hops, hops - k, -1)[:, None]
        nxt = jnp.full((lanes,), n_slots, jnp.int32)
        at = 0
        for nb in in_nbs:
            m = nb.shape[0]
            out = nb[jnp.clip(cur - at, 0, m - 1)]
            ok = (jnp.take_along_axis(levels_ext, out, axis=1) == want) \
                & ((cur >= at) & (cur < at + m))[:, None]
            slot, found = least(ok, uid_ext[out], out)
            nxt = jnp.where(found, slot, nxt)
            at += m
        if dense is not None:
            rows, words = dense.shape
            row = dense[jnp.clip(cur - (n_covered - rows), 0, rows - 1)]
            # slot s is bit s // words of word s % words
            out = ((row[:, None, :] >> jnp.arange(
                32, dtype=jnp.uint32)[None, :, None]) & 1).reshape(
                    lanes, 32 * words)[:, :n_slots] != 0
            ok = out & (levels == want) \
                & ((cur >= n_covered - rows) & (cur < n_covered))[:, None]
            slot, found = least(ok, slot_uids[None, :],
                                jnp.arange(n_slots, dtype=jnp.int32)[None, :])
            nxt = jnp.where(found, slot, nxt)
        return k + 1, nxt, jax.lax.dynamic_update_slice(
            path, nxt[:, None], (0, k))

    start = jnp.where(hops >= 0, ends, n_slots)
    _, _, path = jax.lax.while_loop(
        lambda state: state[0] <= jnp.minimum(jnp.max(hops), width - 1),
        hop, (jnp.int32(1), start, jnp.full(
            (lanes, width), n_slots, jnp.int32).at[:, 0].set(start)))
    call = jnp.pad(jnp.concatenate([
        jnp.max(tally[1])[None], tally[2, :3]]), (0, width - 2))
    return jnp.concatenate([
        jnp.concatenate([hops[:, None], tally[1][:, None], path], axis=1),
        call[None, :]])


def paths(badj: BitAdjacency, pairs: list, tile: int = _HUB_TILE_ROWS,
          columns: Optional[bool] = None):
    """bfs_paths over a TRANSPOSED adjacency as attach_dense and
    attach_uids left it, one lane a pair: `pairs` is [(source slot,
    target slot, depth)], at most LANES of them; pair i is row i of
    the result. ONE compiled shape an adjacency while no pair asks
    more than 15 hops (then a power of two of them). `tile` and
    `columns` as bfs_traverse takes them (the tests' to set)."""
    packed = np.concatenate([
        _pack_riders(badj.n_slots, [
            (np.asarray([dst], np.int32), depth) for _, dst, depth in pairs]),
        np.asarray([src for src, _, _ in pairs]
                   + [badj.n_slots] * (LANES - len(pairs)), np.int32)])
    return bfs_paths(
        [b.in_nb for b in badj.gathered], badj.dense, badj.uids_dev, packed,
        n_slots=badj.n_slots, n_covered=badj.n_covered, lanes=LANES,
        width=path_width(max(d for _, _, d in pairs), badj.n_slots),
        tile=tile, columns=columns)


def attach_uids(badj: BitAdjacency) -> None:
    """Give the adjacency its slots' uids on the device, which the
    walk of bfs_paths breaks ties by."""
    if badj.uids_dev is None:
        badj.uids_dev = jnp.asarray(badj.slot_uids)


def path_uids(badj: BitAdjacency, row: np.ndarray) -> Optional[list]:
    """A lane's row of bfs_paths' result -> its path's uids from the
    source to the target, [] where there is none, None where the walk
    did not finish (bfs_paths says when)."""
    hops = int(row[0])
    if hops < 0:
        return []
    slots = row[2:3 + hops]
    if len(slots) != hops + 1 or (slots >= badj.n_slots).any():
        return None
    return badj.slot_uids[slots].tolist()


# -- batched (multi-query) kernels -------------------------------------------
#
# The TPU's gather unit is descriptor-rate bound (~20M row-fetches/s on
# v5e, measured): the cost of `f[in_nb]` is per *edge*, independent of
# row width up to HBM bandwidth. So the throughput design packs MANY
# queries into the lane dimension — frontier[n, w] is a uint32 whose
# bit b is query (w*32+b)'s membership — and one traversal pass answers
# 32*W queries for the price of one. This is the idiomatic TPU
# replacement for the reference's one-goroutine-per-request model
# (worker/task.go:581): batch across requests, not threads.


def uids_to_bits_batched(badj: BitAdjacency,
                         seed_lists: list[np.ndarray]) -> np.ndarray:
    """[B seed uid arrays] -> packed uint32[N+1, ceil(B/32)] frontier.

    Row N is the dummy always-empty slot targeted by adjacency padding,
    so kernels need no separate mask concat."""
    B = len(seed_lists)
    W = (B + 31) // 32
    out = np.zeros((badj.n_slots + 1, W), np.uint32)
    if badj.n_slots == 0 or B == 0:
        return out
    q, slots = _flat_query_slots(badj, seed_lists)
    np.bitwise_or.at(out, (slots, q // 32),
                     (np.uint32(1) << (q % 32).astype(np.uint32)))
    return out


def _flat_query_slots(badj: BitAdjacency, seed_lists: list[np.ndarray]
                      ) -> tuple[np.ndarray, np.ndarray]:
    """One vectorized pass over all (query, uid) pairs -> aligned
    (query index, slot) arrays with unknown uids dropped. Shared by the
    bitmap and seed-slot packers."""
    B = len(seed_lists)
    lens = np.fromiter((len(s) for s in seed_lists), np.int64, B)
    if lens.sum() == 0:
        return np.empty(0, np.int64), np.empty(0, np.int32)
    u = np.concatenate([np.asarray(s, np.uint32) for s in seed_lists])
    q = np.repeat(np.arange(B, dtype=np.int64), lens)
    slots, hit = _uid_slots(badj, u)
    return q[hit], slots


def bits_to_uids_batched(badj: BitAdjacency, packed: np.ndarray,
                         n_queries: int) -> list[np.ndarray]:
    """packed uint32[N+1, W] -> per-query sorted uid arrays."""
    packed = np.asarray(packed)[:badj.n_slots]
    out = []
    for q in range(n_queries):
        bits = (packed[:, q // 32] >> np.uint32(q % 32)) & np.uint32(1)
        out.append(np.sort(badj.slot_uids[bits.astype(bool)]))
    return out


def _gather_or(f: jax.Array, in_nb: jax.Array, degree: int) -> jax.Array:
    """OR of gathered frontier rows over the degree axis, in chunks of
    <=8 so no [M, D, W] intermediate is materialized and the unroll
    stays bounded for the huge-degree hub buckets."""
    Dc = next(c for c in (8, 6, 4, 3, 2, 1) if degree % c == 0)
    M = in_nb.shape[0]
    nb = in_nb.reshape(M * (degree // Dc), Dc)
    acc = f[nb[:, 0]]
    for d in range(1, Dc):
        acc = acc | f[nb[:, d]]
    if degree > Dc:
        acc = jnp.bitwise_or.reduce(acc.reshape(M, degree // Dc, -1), axis=1)
    return acc


def make_bfs_bits_batched(badj: BitAdjacency, depth: int,
                          dedup: bool = True) -> Callable:
    """Compile multi-query BFS: packed uint32[N+1, W] seed frontier ->
    tuple of per-level packed frontiers (same shape).

    One device call runs 32*W independent traversals. Per-edge work is
    one row-gather + OR: D separate [M, W] gathers (no [M, D, W]
    intermediate)."""
    ncov = badj.n_covered
    n = badj.n_slots

    def level(f):
        parts = [_gather_or(f, b.in_nb, b.degree) for b in badj.buckets]
        W = f.shape[1]
        tail = n - ncov
        if tail:
            parts.append(jnp.zeros((tail, W), jnp.uint32))
        if not parts:
            return jnp.zeros_like(f)
        reach = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
        # re-append the dummy slot row (always empty)
        return jnp.concatenate([reach, jnp.zeros((1, W), jnp.uint32)])

    def bfs(seed_packed: jax.Array):
        levels = []
        visited = seed_packed
        frontier = seed_packed
        for _ in range(depth):
            reach = level(frontier)
            if dedup:
                new = reach & ~visited
                visited = visited | new
            else:
                new = reach
            levels.append(new)
            frontier = new
        return tuple(levels)

    return jax.jit(bfs)


def make_frontier_counts_batched(n_queries: int) -> Callable:
    """Compile: packed uint32[N+1, W] -> int32[n_queries] per-query
    popcounts (set sizes), fully on device."""

    @jax.jit
    def counts(packed: jax.Array):
        # popcount per word, but per bit-position: extract each of the
        # 32 bit planes and reduce over rows.
        per_word_bit = []
        for b in range(32):
            plane = (packed >> np.uint32(b)) & np.uint32(1)
            per_word_bit.append(jnp.sum(plane, axis=0, dtype=jnp.int32))
        stacked = jnp.stack(per_word_bit, axis=1)  # [W, 32]
        return stacked.reshape(-1)[:n_queries]

    return counts


# -- core-space digest kernels -----------------------------------------------
#
# At reference scale (21M edges over 2M nodes) the bitmap memory
# [N+1, W] caps the query batch — and QPS is proportional to W because
# the gather unit is descriptor-bound (row width is nearly free). Two
# structural facts about any graph break that cap:
#   1. only slots with in-degree > 0 can appear in levels >= 1, and
#      those slots are a PREFIX of slot space by construction
#      (n_covered) — measured 27% of slots on the zipf bench graph;
#   2. only edges whose SOURCE is itself covered can contribute to
#      levels >= 2 — 27% of edges on the same graph.
# So level 1 runs once over the full adjacency into core space
# [n_covered+1, W], and deeper levels run entirely in core space with a
# re-bucketed core adjacency: ~3.7x less bitmap HBM and ~3.7x fewer
# gather descriptors per deep level, which buys back the batch width.


@dataclass
class CoreAdjacency:
    """Reverse adjacency restricted to covered->covered edges, in its
    own ROW space.

    Every covered slot owns exactly one row (slots with no covered
    in-neighbor sit in the cap-1 bucket gathering only the dummy), rows
    grouped by core-degree class — so the per-bucket concat order IS
    the core frontier layout and deep levels need no permutation.
    in_nb entries are ROW POSITIONS of source slots (dummy = n_core);
    `row_slots[r]` is the covered slot living in row r, used once at
    the level-1 boundary to permute slot-ordered bitmaps into row
    order."""

    buckets: list[RevBucket]
    row_slots: jax.Array             # [n_core] int32
    n_core: int


def build_core_adjacency(badj: BitAdjacency) -> CoreAdjacency:
    """Derive the covered->covered re-bucketed adjacency from the full
    buckets' host copies (no device transfer)."""
    ncov = badj.n_covered
    if ncov == 0 or not badj.buckets:
        return CoreAdjacency([], jnp.zeros((0,), jnp.int32), ncov)
    dsts, srcs = [], []
    for b in badj.buckets:
        nb = _host_nb(b)
        rr, cc = np.nonzero(nb < ncov)       # covered sources only
        dsts.append((rr + b.offset).astype(np.int64))
        srcs.append(nb[rr, cc])
    dst = np.concatenate(dsts)
    src = np.concatenate(srcs)
    indeg = np.bincount(dst, minlength=ncov)
    # every covered slot gets a row; 0-degree rows take cap 1 (one
    # dummy gather each — cheap, and it keeps row space == covered set)
    cap_all = _LADDER[np.searchsorted(_LADDER, np.maximum(indeg, 1))]
    order = np.lexsort((np.arange(ncov), cap_all))
    row_slots = order.astype(np.int32)       # row -> slot
    caps_o = cap_all[order]
    pos_of = np.empty(ncov, np.int64)        # slot -> row
    pos_of[order] = np.arange(ncov)
    rp = pos_of[dst]
    eorder = np.argsort(rp, kind="stable")
    rp, srco = rp[eorder], pos_of[src[eorder]]   # sources in ROW space
    starts = np.zeros(ncov + 1, np.int64)
    np.cumsum(np.bincount(rp, minlength=ncov), out=starts[1:])
    posin = np.arange(len(srco), dtype=np.int64) - starts[rp]
    buckets: list[RevBucket] = []
    offset = 0
    for c in np.unique(caps_o):
        c = int(c)
        m = int(np.sum(caps_o == c))
        nb = np.full((m, c), ncov, np.int32)
        sel = (rp >= offset) & (rp < offset + m)
        nb[rp[sel] - offset, posin[sel]] = srco[sel]
        # no in_nb_host: nothing re-derives edges from a CoreAdjacency,
        # so pinning the host copy would only hold memory
        buckets.append(RevBucket(jnp.asarray(nb), None, c, offset))
        offset += m
    return CoreAdjacency(buckets, jnp.asarray(row_slots), ncov)


def uid_lists_to_seed_slots(badj: BitAdjacency,
                            seed_lists: list[np.ndarray],
                            n_seeds: int | None = None) -> np.ndarray:
    """[B seed uid arrays] -> int32[B, S] slot matrix for the digest
    kernel; unknown uids and padding map to the dummy slot n_slots.
    Deduplicates (query, slot) pairs so the kernel's scatter-ADD packing
    is an exact OR. A query with more than S distinct known seeds is an
    error — silent truncation would answer a different query."""
    B = len(seed_lists)
    S = n_seeds if n_seeds is not None else \
        max((len(s) for s in seed_lists), default=1)
    out = np.full((B, max(S, 1)), badj.n_slots, np.int32)
    if badj.n_slots == 0 or B == 0:
        return out
    q, slots = _flat_query_slots(badj, seed_lists)
    if not len(q):
        return out
    pairs = np.unique((q << 32) | slots.astype(np.int64))
    q, slots = pairs >> 32, pairs & 0xFFFFFFFF
    starts = np.zeros(B + 1, np.int64)
    np.cumsum(np.bincount(q, minlength=B), out=starts[1:])
    pos = np.arange(len(q), dtype=np.int64) - starts[q]
    if pos.max(initial=-1) >= out.shape[1]:
        over = int(q[pos >= out.shape[1]][0])
        raise ValueError(
            f"query {over} has {int((q == over).sum())} distinct seeds "
            f"> n_seeds={out.shape[1]}")
    out[q, pos] = slots.astype(np.int32)
    return out


def make_bfs_digest_batched(badj: BitAdjacency, core: CoreAdjacency,
                            depth: int, n_queries: int,
                            n_seeds: int) -> Callable:
    """Compile the serving-shape BFS: int32[B, S] seed slots ->
    (uint32[depth] per-level popcount checksums,
     uint32[n_core+1, 1] final level's first word column).

    The packed frontier is built ON DEVICE (scatter-add of one bit per
    (query, seed)) so only the [B, S] slot matrix crosses the host link
    per batch — never an [N, W] bitmap. Level 1 gathers the full
    adjacency once; every deeper level runs in core slot space. Only
    frontier+visited (+ the level's reach) are live — no per-level
    bitmap pile-up, which is what held the batch at 8192 on a 16GB
    chip (ref regime: worker/task.go:581 fan-out at systest/21million
    scale). The first-word column ships ~n_core*4 bytes so the caller
    can parity-check queries 0..31 via make_frontier_counts_batched
    without pulling a full bitmap."""
    N, ncov = badj.n_slots, badj.n_covered
    W = (n_queries + 31) // 32

    def digest(seed_slots: jax.Array):
        q = jnp.arange(n_queries, dtype=jnp.uint32)
        bit = jnp.uint32(1) << (q % jnp.uint32(32))
        word = (q // jnp.uint32(32)).astype(jnp.int32)
        f = jnp.zeros((N + 1, W), jnp.uint32)
        f = f.at[seed_slots.reshape(-1),
                 jnp.repeat(word, n_seeds)].add(jnp.repeat(bit, n_seeds))
        f = f.at[N].set(jnp.uint32(0))   # dummy slot absorbs padding
        zrow = jnp.zeros((1, W), jnp.uint32)
        if badj.buckets:
            parts = [_gather_or(f, b.in_nb, b.degree)
                     for b in badj.buckets]
            reach1 = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
        else:
            reach1 = jnp.zeros((ncov, W), jnp.uint32)
        seeds_core = f[:ncov]
        new = reach1 & ~seeds_core
        sums = [jnp.sum(jax.lax.population_count(new), dtype=jnp.uint32)]
        # one boundary permutation into core ROW space; every deeper
        # level's bucket-concat then IS the next frontier layout
        vis_s = seeds_core | new
        frontier = jnp.concatenate([new[core.row_slots], zrow])
        visited = jnp.concatenate([vis_s[core.row_slots], zrow])
        for _ in range(depth - 1):
            parts = [_gather_or(frontier, b.in_nb, b.degree)
                     for b in core.buckets]
            reach = jnp.concatenate(parts + [zrow])
            frontier = reach & ~visited
            visited = visited | frontier
            sums.append(jnp.sum(jax.lax.population_count(frontier),
                                dtype=jnp.uint32))
        return jnp.stack(sums), frontier[:, :1]

    return jax.jit(digest)


def bfs_bits_reach_batched(badj: BitAdjacency,
                           seed_lists: list[np.ndarray], depth: int,
                           dedup: bool = True) -> list[list[np.ndarray]]:
    """Host wrapper: per-query, per-level sorted frontier uid arrays.
    Returns result[q][lvl]."""
    B = len(seed_lists)
    if badj.n_slots == 0 or B == 0:
        return [[np.empty(0, np.uint32) for _ in range(depth)]
                for _ in range(B)]
    cache = getattr(badj, "_bfsb_cache", None)
    if cache is None:
        cache = badj._bfsb_cache = {}
    W = (B + 31) // 32
    fn = cache.get((depth, dedup, W))
    if fn is None:
        fn = cache[(depth, dedup, W)] = make_bfs_bits_batched(
            badj, depth, dedup)
    packed = uids_to_bits_batched(badj, seed_lists)
    levels = fn(jnp.asarray(packed))
    per_level = [bits_to_uids_batched(badj, np.asarray(lv), B)
                 for lv in levels]
    return [[per_level[lvl][q] for lvl in range(depth)]
            for q in range(B)]


def make_sssp_bits(badj: BitAdjacency, max_iters: int,
                   weighted: bool = False) -> Callable:
    """Compile Bellman-Ford distances: seed bitmap -> int32[N] dist
    (INT32_INF = unreachable). With weighted=True uses the per-edge
    weights captured at build time (ref query/shortest.go:451 route()
    — the priority queue becomes dense relaxation rounds)."""
    ncov = badj.n_covered

    def sssp(seed_bits: jax.Array):
        dist = jnp.where(seed_bits, jnp.int32(0), INT32_INF)
        for _ in range(max_iters):
            de = jnp.concatenate([dist, jnp.full((1,), INT32_INF,
                                                 jnp.int32)])
            parts = []
            for b in badj.buckets:
                d = de[b.in_nb]                          # [M, D]
                w = b.weights if (weighted and b.weights is not None) \
                    else jnp.int32(1)
                # d + w can exceed int32 (long weighted paths) and must
                # saturate at INT32_INF, not wrap to a bogus negative
                # distance (advisor finding). int64 is unavailable
                # without jax_enable_x64, so test overflow before
                # adding: safe iff w <= INT32_INF - d (both sides
                # in-range int32 since 0 <= d < INT32_INF).
                w_arr = jnp.broadcast_to(jnp.asarray(w, jnp.int32),
                                         d.shape)
                safe = (d < INT32_INF) & (w_arr <= INT32_INF - d)
                cand = jnp.where(safe, d + w_arr, INT32_INF)
                parts.append(jnp.min(cand, axis=1))
            if parts:
                cand = jnp.concatenate(parts)
                dist = jnp.concatenate(
                    [jnp.minimum(dist[:ncov], cand), dist[ncov:]])
        return dist

    return jax.jit(sssp)


def sssp_dist(badj: BitAdjacency, seeds_np: np.ndarray, max_iters: int,
              weighted: bool = False, sync=None) -> dict[int, int]:
    """Host wrapper: {uid -> hop/weighted distance} for reachable uids.
    `sync` is applied to the dispatched result before it is fetched
    (query/devicecall.py's `wait`)."""
    if badj.n_slots == 0:
        return {}
    cache = getattr(badj, "_sssp_cache", None)
    if cache is None:
        cache = badj._sssp_cache = {}
    fn = cache.get((max_iters, weighted))
    if fn is None:
        fn = cache[(max_iters, weighted)] = make_sssp_bits(
            badj, max_iters, weighted)
    out = fn(jnp.asarray(uids_to_bits(badj, seeds_np)))
    dist = np.asarray(sync(out) if sync else out)
    ok = dist < INT32_INF
    return {int(u): int(d) for u, d in zip(badj.slot_uids[ok], dist[ok])}
