"""UID-range-sharded adjacency + distributed BFS.

This is the device-mesh version of ops/graph.py for one predicate whose
edge set exceeds a single chip — the reference's multi-part posting list
(posting/list.go:1149 splitUpList, navigated part-by-part at read time)
re-designed as SPMD: source uids are range-partitioned into `uid` shards,
every shard holds the same *shapes* (row counts padded to the max across
shards), and one `shard_map` step does

    local:   frontier (replicated) ∧ local rows -> local candidates
    ICI:     all_gather(candidates) over the uid axis
    local:   sort + unique -> next frontier (replicated)

which is exactly the reference's ReceivePredicate-style shard exchange
(worker/predicate_move.go streams) collapsed into one collective.

Two exchange strategies, mirroring the two long-context layouts:

  all_gather (make_sharded_bfs)  — frontier REPLICATED; each shard
      masks its local rows, one all_gather merges. Simple, but every
      device holds the full frontier (the "full attention matrix"
      analogue).
  ring (make_ring_bfs)           — frontier SHARDED by uid range;
      each step local candidates are routed to their dst-range home
      shard by rotating send blocks around the ICI ring (ppermute),
      accumulating with local dedup. Peak memory per device stays
      O(local block) — the ring-attention layout applied to frontier
      exchange.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from dgraph_tpu.ops.uidvec import (
    SENTINEL, compact, member_mask, pad_to, to_numpy,
)

MAX_U32 = SENTINEL - 1  # largest real uid a 32-bit tile can hold


@dataclass
class ShardedBucket:
    src: jax.Array        # [U, M] uint32 per-shard sorted, SENTINEL pad
    neighbors: jax.Array  # [U, M, D] uint32
    degree: int


@dataclass
class ShardedAdjacency:
    n_shards: int
    buckets: list[ShardedBucket] = field(default_factory=list)
    n_edges: int = 0
    n_dst: int = 0

    def put(self, mesh: Mesh, uid_axis: str = "uid") -> "ShardedAdjacency":
        """Place shards on the mesh: leading dim over the uid axis."""
        out = ShardedAdjacency(self.n_shards, [], self.n_edges, self.n_dst)
        for b in self.buckets:
            spec = NamedSharding(mesh, P(uid_axis))
            out.buckets.append(ShardedBucket(
                jax.device_put(b.src, spec),
                jax.device_put(b.neighbors, spec), b.degree))
        return out


def _degree_cap(n_edges: int, min_degree_bucket: int) -> int:
    return max(min_degree_bucket,
               1 << int(np.ceil(np.log2(max(n_edges, 1)))))


def _bucketize(edges: dict[int, np.ndarray], n_shards: int, shard_of,
               min_degree_bucket: int) -> list[ShardedBucket]:
    """Shared degree-cap bucketization for both sharding layouts: rows
    assigned to shards by `shard_of(src)`, shapes equalized across
    shards per cap."""
    caps = sorted({_degree_cap(len(d), min_degree_bucket)
                   for d in edges.values()}) if edges else []
    buckets = []
    for cap in caps:
        rows_per_shard: list[list[int]] = [[] for _ in range(n_shards)]
        for s, d in edges.items():
            if _degree_cap(len(d), min_degree_bucket) == cap:
                rows_per_shard[shard_of(int(s))].append(int(s))
        m = pad_to(max((len(r) for r in rows_per_shard), default=1))
        src_arr = np.full((n_shards, m), SENTINEL, np.uint32)
        nb_arr = np.full((n_shards, m, cap), SENTINEL, np.uint32)
        for si, sel in enumerate(rows_per_shard):
            for ri, s in enumerate(sorted(sel)):
                dst = edges[s]
                src_arr[si, ri] = s
                nb_arr[si, ri, : len(dst)] = dst.astype(np.uint32)
        buckets.append(ShardedBucket(jnp.asarray(src_arr),
                                     jnp.asarray(nb_arr), cap))
    return buckets


def build_sharded_adjacency(edges: dict[int, np.ndarray],
                            n_shards: int,
                            min_degree_bucket: int = 8) -> ShardedAdjacency:
    """Host: range-partition srcs into n_shards balanced by edge count,
    then bucket by degree with shapes equalized across shards."""
    srcs = np.sort(np.fromiter(edges.keys(), dtype=np.uint64,
                               count=len(edges)))
    degs = np.asarray([len(edges[int(s)]) for s in srcs], dtype=np.int64)
    cum = np.cumsum(degs)
    total = int(cum[-1]) if len(cum) else 0
    # contiguous ranges with ~equal edge mass (ref tablet move picks
    # heaviest->lightest, zero/tablet.go:180 — here we just balance)
    bounds = np.searchsorted(cum, np.linspace(0, total, n_shards + 1)[1:-1])
    shard_starts = [ss[0] if len(ss) else None
                    for ss in np.split(srcs, bounds)]

    def shard_of(s: int) -> int:
        si = 0
        for i, start in enumerate(shard_starts):
            if start is not None and s >= start:
                si = i
        return si

    buckets = _bucketize(edges, n_shards, shard_of, min_degree_bucket)
    n_dst = len(np.unique(np.concatenate(
        [np.asarray(v) for v in edges.values()]))) if edges else 0
    return ShardedAdjacency(n_shards, buckets, total, n_dst)


def _local_candidates(frontier, src_l, nb_l):
    """One shard's masked candidates for a replicated frontier."""
    hit = member_mask(src_l, frontier)
    cand = jnp.where(hit[:, None], nb_l, SENTINEL)
    return cand.reshape(-1)


def _expand_level_body(n_buckets: int, frontier, bucket_arrays,
                       uid_axis: str, out_size: int):
    """The shared SPMD body of one expansion level (used by both the
    single-level expander and the multi-level BFS): local candidates
    per shard -> all_gather over the uid axis -> sorted unique,
    padded/truncated to out_size (valid count is bounded by n_dst, so
    truncation at out_size >= pad_to(n_dst) never drops uids)."""
    parts = []
    for bi in range(n_buckets):
        src_l = bucket_arrays[2 * bi][0]      # [M] local shard
        nb_l = bucket_arrays[2 * bi + 1][0]   # [M, D]
        parts.append(_local_candidates(frontier, src_l, nb_l))
    local = compact(jnp.concatenate(parts)) if parts else \
        jnp.full((8,), SENTINEL, jnp.uint32)
    gathered = jax.lax.all_gather(local, uid_axis).reshape(-1)
    flat = jnp.sort(gathered)
    prev = jnp.concatenate(
        [jnp.full((1,), SENTINEL, flat.dtype), flat[:-1]])
    uniq = compact(jnp.where(flat != prev, flat, SENTINEL))
    if uniq.shape[0] >= out_size:
        return uniq[:out_size]
    return jnp.concatenate([uniq, jnp.full(
        (out_size - uniq.shape[0],), SENTINEL, jnp.uint32)])


def make_sharded_expand(mesh: Mesh, sadj: ShardedAdjacency,
                        out_size: int, uid_axis: str = "uid"):
    """Compile ONE expansion level over the uid-sharded adjacency —
    the executor's per-level device call when a predicate is too big
    for a single chip (multi-part posting list read,
    posting/list.go:1149, as one shard_map + all_gather).

    fn(frontier uint32 replicated) -> [out_size] uint32 (sorted unique
    destinations, SENTINEL padded). jit re-specializes per frontier
    shape; callers cache the returned fn per padded frontier size.
    """
    in_specs = [P()]
    for _ in sadj.buckets:
        in_specs.extend([P(uid_axis), P(uid_axis)])

    def step(frontier, *bucket_arrays):
        return _expand_level_body(len(sadj.buckets), frontier,
                                  bucket_arrays, uid_axis, out_size)

    smapped = shard_map(step, mesh=mesh, in_specs=tuple(in_specs),
                        out_specs=P(), check_vma=False)

    def sharded_expand(frontier):
        args = []
        for b in sadj.buckets:
            args.extend([b.src, b.neighbors])
        return smapped(frontier, *args)

    return jax.jit(sharded_expand)


def expand_sharded_np(mesh: Mesh, sadj: ShardedAdjacency,
                      src_u64: np.ndarray, sync=None) -> np.ndarray:
    """Host frontier -> sharded device expand -> host result; jitted
    expanders cached per frontier bucket size on the adjacency (the
    expand_np contract, `sync` included, device tier instead of single
    chip)."""
    src_u64 = np.sort(src_u64[src_u64 <= MAX_U32])
    f_pad = pad_to(len(src_u64))
    out_size = pad_to(max(sadj.n_dst, 1))
    cache = getattr(sadj, "_expander_cache", None)
    if cache is None:
        cache = sadj._expander_cache = {}
    fn = cache.get(f_pad)
    if fn is None:
        fn = make_sharded_expand(mesh, sadj, out_size)
        cache[f_pad] = fn
    fr = np.full(f_pad, SENTINEL, np.uint32)
    fr[: len(src_u64)] = src_u64.astype(np.uint32)
    out = fn(jnp.asarray(fr))
    return to_numpy(sync(out) if sync else out).astype(np.uint64)


@dataclass
class RingAdjacency:
    """Uniform-uid-range sharding for the ring exchange: device i holds
    the adjacency rows whose SRC uid falls in range i, and owns frontier
    uids in the same range — src and dst use ONE partition of the uid
    space so a candidate's home shard is computable on device
    (dst * n_shards // space)."""
    n_shards: int
    space: int                     # uid space size (ranges = space/n)
    buckets: list[ShardedBucket] = field(default_factory=list)
    n_edges: int = 0
    n_dst: int = 0

    def put(self, mesh: Mesh, uid_axis: str = "uid") -> "RingAdjacency":
        out = RingAdjacency(self.n_shards, self.space, [],
                            self.n_edges, self.n_dst)
        for b in self.buckets:
            spec = NamedSharding(mesh, P(uid_axis))
            out.buckets.append(ShardedBucket(
                jax.device_put(b.src, spec),
                jax.device_put(b.neighbors, spec), b.degree))
        return out


def build_ring_adjacency(edges: dict[int, np.ndarray],
                         n_shards: int,
                         min_degree_bucket: int = 8) -> RingAdjacency:
    """Host: partition srcs into UNIFORM uid ranges (value-based, not
    mass-balanced — the ring needs dst->shard computable on device)."""
    all_uids = list(edges.keys())
    for v in edges.values():
        all_uids.append(int(v.max()) if len(v) else 0)
    space = max(all_uids) + 1 if all_uids else 1
    per = -(-space // n_shards)  # ceil

    def shard_of(u: int) -> int:
        return min(int(u) // per, n_shards - 1)

    buckets = _bucketize(edges, n_shards, shard_of, min_degree_bucket)
    total = sum(len(v) for v in edges.values())
    n_dst = len(np.unique(np.concatenate(
        [np.asarray(v) for v in edges.values()]))) if edges else 0
    return RingAdjacency(n_shards, space, buckets, total, n_dst)


def make_ring_bfs(mesh: Mesh, radj: RingAdjacency, seed_size: int,
                  depth: int, block_size: int,
                  uid_axis: str = "uid", check_block: bool = True):
    """Compile a depth-`depth` ring-exchange BFS.

    fn(seeds [n_shards, seed_size] SHARDED by uid axis, each row the
    seeds falling in that shard's range) ->
      (levels tuple of [n_shards, block_size] sharded, total int32).

    Per level, per ring step k: every device masks its local
    candidates for target shard (self+k) mod n, compacts them into one
    send block, and `ppermute`s it one hop — after n steps every
    candidate reached its dst-range home, where it merged (sorted
    dedup) into the local next-frontier block. No device ever holds
    the whole frontier: memory is O(block) — the ring-attention
    schedule applied to frontier exchange (SURVEY §5.7's long-context
    mapping).

    `block_size` caps each shard's frontier/visited vectors; merges
    truncate at it, so it must bound the per-shard reachable set or
    uids would silently drop. n_dst (distinct destinations anywhere)
    + the seed block is always safe and is enforced here — callers
    with a tighter per-shard bound can pass check_block=False."""
    if check_block and block_size < pad_to(radj.n_dst + seed_size):
        raise ValueError(
            f"block_size {block_size} can overflow: a shard's "
            f"reachable set is only bounded by n_dst + seeds = "
            f"{radj.n_dst + seed_size} (pad to "
            f"{pad_to(radj.n_dst + seed_size)})")
    n = mesh.shape[uid_axis]
    per = -(-radj.space // n)

    in_specs = [P(uid_axis)]
    for _ in radj.buckets:
        in_specs.extend([P(uid_axis), P(uid_axis)])

    def merge_into(acc, blk):
        flat = jnp.sort(jnp.concatenate([acc, blk]))
        prev = jnp.concatenate(
            [jnp.full((1,), SENTINEL, flat.dtype), flat[:-1]])
        return compact(jnp.where(flat != prev, flat, SENTINEL))[
            : acc.shape[0]]

    def step(seeds, *bucket_arrays):
        me = jax.lax.axis_index(uid_axis)
        frontier = seeds[0]            # local block
        visited = jnp.concatenate([
            frontier,
            jnp.full((block_size - frontier.shape[0],), SENTINEL,
                     jnp.uint32)]) if frontier.shape[0] < block_size \
            else frontier[:block_size]
        levels = []
        for _ in range(depth):
            parts = []
            for bi in range(len(radj.buckets)):
                src_l = bucket_arrays[2 * bi][0]
                nb_l = bucket_arrays[2 * bi + 1][0]
                parts.append(_local_candidates(frontier, src_l, nb_l))
            cand = compact(jnp.concatenate(parts)) if parts else \
                jnp.full((8,), SENTINEL, jnp.uint32)
            home = jnp.minimum(cand // jnp.uint32(per),
                               jnp.uint32(n - 1))
            acc = jnp.full((block_size,), SENTINEL, jnp.uint32)
            for k in range(n):
                target = (me + k) % n
                blk = compact(jnp.where(
                    (home == target) & (cand != SENTINEL),
                    cand, SENTINEL))
                if k:
                    # rotate k hops so the block lands on its target
                    blk = jax.lax.ppermute(
                        blk, uid_axis,
                        [(j, (j + k) % n) for j in range(n)])
                acc = merge_into(acc, blk)
            new = compact(jnp.where(member_mask(acc, visited),
                                    SENTINEL, acc))
            visited = merge_into(visited, new)
            levels.append(new[None, :])
            frontier = new
        local_count = jnp.sum(frontier != SENTINEL, dtype=jnp.int32)
        total = jax.lax.psum(local_count, uid_axis)
        return tuple(levels), total

    smapped = shard_map(
        step, mesh=mesh, in_specs=tuple(in_specs),
        out_specs=(tuple(P(uid_axis) for _ in range(depth)), P()),
        check_vma=False)

    def ring_bfs(seeds):
        args = []
        for b in radj.buckets:
            args.extend([b.src, b.neighbors])
        return smapped(seeds, *args)

    return jax.jit(ring_bfs)


def make_sharded_bfs(mesh: Mesh, sadj: ShardedAdjacency, seed_size: int,
                     depth: int, level_size: int,
                     uid_axis: str = "uid"):
    """Compile a depth-`depth` distributed BFS step.

    Returns fn(seeds [seed_size] replicated) ->
      (levels tuple of [level_size], reached_count int32).
    Frontier stays replicated; per level each uid shard computes local
    candidates, all_gathers over the uid axis, and dedups. The count is
    a plain reduction of the final frontier (already replicated — the
    psum rides in the all_gather).
    """
    in_specs = [P()]
    for _ in sadj.buckets:
        in_specs.extend([P(uid_axis), P(uid_axis)])

    def step(seeds, *bucket_arrays):
        levels = []
        frontier = seeds
        visited = seeds
        for _ in range(depth):
            nxt = _expand_level_body(len(sadj.buckets), frontier,
                                     bucket_arrays, uid_axis, level_size)
            keep = ~member_mask(nxt, visited)
            nxt = compact(jnp.where(keep, nxt, SENTINEL))
            visited = compact(jnp.concatenate([visited, nxt]))
            levels.append(nxt)
            frontier = nxt
        count = jnp.sum(frontier != SENTINEL, dtype=jnp.int32)
        return tuple(levels), count

    smapped = shard_map(
        step, mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(tuple(P() for _ in range(depth)), P()),
        check_vma=False)

    def sharded_bfs(seeds):
        args = []
        for b in sadj.buckets:
            args.extend([b.src, b.neighbors])
        return smapped(seeds, *args)

    return jax.jit(sharded_bfs)
