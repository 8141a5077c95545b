"""Distributed query step over the full (data, tablet, uid) mesh.

One SPMD program = one level-batched query plan fragment, the mesh
version of query.ProcessGraph's scatter-gather (query/query.go:2017):

  data axis   : a batch of root frontiers (independent queries)
  tablet axis : predicates — each tablet shard expands through ITS
                predicates, then all_gathers so every shard holds every
                predicate's result (the reference routes per-attr RPCs
                to group leaders, worker/task.go:131; here the routing
                IS the sharding)
  uid axis    : uid-range shards within each predicate (multi-part
                posting lists, posting/list.go:1149)

The canonical step compiled here: 2-hop expansion through predicate 0
intersected with 1-hop expansion through predicate 1, per batched seed
set — the shape of "friends-of-friends who are also X" queries.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from dgraph_tpu.ops.uidvec import (
    SENTINEL, compact, first_k, member_mask, pad_to,
)
from dgraph_tpu.parallel.dist_graph import ShardedAdjacency, \
    build_sharded_adjacency


@dataclass
class TabletStack:
    """T predicates with identical bucket shapes, stacked on a leading
    tablet dim: srcs[i] [T, U, M], neighbors[i] [T, U, M, D]."""

    srcs: list[jax.Array]
    neighbors: list[jax.Array]
    degrees: list[int]
    n_tablets: int
    n_uid_shards: int
    level_cap: int


def stack_tablets(edge_maps: list[dict[int, np.ndarray]],
                  n_uid_shards: int) -> TabletStack:
    """Build per-predicate sharded adjacencies and pad them onto common
    bucket shapes so they stack on the tablet axis."""
    sadjs = [build_sharded_adjacency(e, n_uid_shards) for e in edge_maps]
    caps = sorted({b.degree for s in sadjs for b in s.buckets})
    srcs, neighbors, degrees = [], [], []
    for cap in caps:
        m = 8
        for s in sadjs:
            for b in s.buckets:
                if b.degree == cap:
                    m = max(m, b.src.shape[1])
        src_stack = np.full((len(sadjs), n_uid_shards, m), SENTINEL,
                            np.uint32)
        nb_stack = np.full((len(sadjs), n_uid_shards, m, cap), SENTINEL,
                           np.uint32)
        for ti, s in enumerate(sadjs):
            for b in s.buckets:
                if b.degree != cap:
                    continue
                sa = np.asarray(b.src)
                na = np.asarray(b.neighbors)
                src_stack[ti, :, : sa.shape[1]] = sa
                nb_stack[ti, :, : na.shape[1], :] = na
        srcs.append(jnp.asarray(src_stack))
        neighbors.append(jnp.asarray(nb_stack))
        degrees.append(cap)
    n_nodes = len({u for e in edge_maps for u in e} |
                  {int(d) for e in edge_maps for v in e.values() for d in v})
    return TabletStack(srcs, neighbors, degrees, len(sadjs), n_uid_shards,
                       pad_to(n_nodes + 8))


def _expand_local(frontier, srcs_l, nbs_l, level_cap):
    """Expand one frontier through the LOCAL tablet+uid shard's buckets,
    then all_gather over uid AND tablet axes so the union covers the
    whole predicate set of this expansion step."""
    parts = []
    for src_l, nb_l in zip(srcs_l, nbs_l):
        hit = member_mask(src_l, frontier)
        parts.append(jnp.where(hit[:, None], nb_l, SENTINEL).reshape(-1))
    local = compact(jnp.concatenate(parts))
    gathered = jax.lax.all_gather(local, ("tablet", "uid")).reshape(-1)
    flat = jnp.sort(gathered)
    prev = jnp.concatenate([jnp.full((1,), SENTINEL, flat.dtype), flat[:-1]])
    nxt = compact(jnp.where(flat != prev, flat, SENTINEL))
    if nxt.shape[0] >= level_cap:
        return nxt[:level_cap]
    return jnp.concatenate(
        [nxt, jnp.full((level_cap - nxt.shape[0],), SENTINEL, jnp.uint32)])


def make_dist_query_step(mesh: Mesh, stack: TabletStack, batch: int,
                         seed_size: int, page: tuple[int, int] | None = None):
    """Compile the canonical distributed query step.

    fn(seeds [batch, seed_size]) -> counts [batch] int32 where
    counts[b] = |2-hop reach of seeds[b] ∩ 1-hop reach| through the
    full predicate set ("friends-of-friends who are also direct
    friends").  With tablet axis size t, each shard expands through its
    local predicates and the all_gather unions them — any t divides
    the predicate work.

    With page=(offset, k) the step ALSO returns the paginated uid page
    [batch, k] of each query's result (uidvec.first_k on device — the
    reference's applyOrderAndPagination window, query/query.go:2231,
    applied before anything ships to the host), so a "first: k,
    offset: o" query transfers k uids per query instead of the whole
    compact result vector.
    """
    t_size = mesh.shape["tablet"]
    assert stack.n_tablets % t_size == 0 or stack.n_tablets <= t_size, \
        "tablet count must tile the tablet axis"

    in_specs = [P("data")]
    for _ in stack.srcs:
        in_specs.append(P("tablet", "uid"))
        in_specs.append(P("tablet", "uid"))

    level_cap = stack.level_cap

    def step(seeds, *arrays):
        srcs_l = [arrays[2 * i][:, 0] for i in range(len(stack.srcs))]
        nbs_l = [arrays[2 * i + 1][:, 0] for i in range(len(stack.srcs))]
        # local tablet shard may hold several predicates (leading dim)
        nt_local = srcs_l[0].shape[0]
        my_srcs = [s[j] for s in srcs_l for j in range(nt_local)]
        my_nbs = [nbq[j] for nbq in nbs_l for j in range(nt_local)]

        def one_query(seed_row):
            hop1 = _expand_local(seed_row, my_srcs, my_nbs, level_cap)
            hop2 = _expand_local(hop1, my_srcs, my_nbs, level_cap)
            direct = _expand_local(seed_row, my_srcs, my_nbs, level_cap)
            both = compact(jnp.where(member_mask(hop2, direct), hop2,
                                     SENTINEL))
            n = jnp.sum(both != SENTINEL, dtype=jnp.int32)
            if page is None:
                return n
            return n, first_k(both, page[1], page[0])

        return jax.vmap(one_query)(seeds)

    out_specs = P("data") if page is None else (P("data"), P("data"))
    smapped = shard_map(step, mesh=mesh, in_specs=tuple(in_specs),
                        out_specs=out_specs, check_vma=False)

    def dist_query_step(seeds):
        args = []
        for s, nb in zip(stack.srcs, stack.neighbors):
            args.extend([s, nb])
        return smapped(seeds, *args)

    return jax.jit(dist_query_step)
