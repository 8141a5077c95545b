"""Device snapshot management: host tablets -> resident HBM tiles.

The policy mirrors the reference's MVCC read path split (posting/list.go
immutable layer vs mutation layer): the *rolled-up* committed state lives
on device; while a tablet has live deltas (posting/mvcc.go mutation
layers) reads stay on the host overlay. Once rollup folds the overlay
(watermark = min active ts, ref worker/draft.go:1206), the tablet is
re-packed and uploaded lazily on first use.

Device tiles are uint32 (rebased): the engine checks the tablet's max
uid; >32-bit graphs fall back to host until uid-range partitioning
(parallel/) is wired in — the reference's own UidPack blocks make the
same 32-bit-low-word assumption per block (codec/codec.go:43).
"""

from __future__ import annotations

import contextlib
import time
import weakref
from typing import Optional

import numpy as np

import jax

from dgraph_tpu.ops.graph import (
    DeviceAdjacency, build_adjacency, build_values, expand, max_expansion,
)
from dgraph_tpu.engine.tile_cache import DeviceCacheLRU  # noqa: F401
from dgraph_tpu.ops.uidvec import SENTINEL, pad_to, to_numpy
from dgraph_tpu.utils.metrics import set_gauge
from dgraph_tpu.utils.tracing import span as _span

_MAX_U32 = 0xFFFFFFFE  # SENTINEL reserved

# seconds this process has spent building and uploading tiles: they
# are built on first use, so after a restart this is the part of
# getting warm that storage/snapshot.py's phases do not see
_TILE_SECONDS = 0.0


@contextlib.contextmanager
def _tile_load(**attrs):
    global _TILE_SECONDS
    t0 = time.perf_counter()
    try:
        with _span("device.tile_load", **attrs):
            yield
    finally:
        _TILE_SECONDS += time.perf_counter() - t0
        set_gauge("startup_phase_seconds", round(_TILE_SECONDS, 6),
                  labels={"phase": "tile_upload"})


def device_adjacency(db, tab, read_ts: int,
                     allow_dirty: bool = False
                     ) -> Optional[DeviceAdjacency]:
    """allow_dirty=True returns the tile built from the BASE arrays
    even while an overlay exists — callers doing overlay-on-device
    reads (executor._device_expand) answer overlay-touched rows on the
    host and use the tile only for untouched rows. Everyone else gets
    the strict clean-only contract."""
    if not _clean_resident(db, tab, read_ts, allow_dirty=allow_dirty):
        return None
    adj = getattr(tab, "_device_adj", None)
    if adj is not None and tab._device_adj_ts == tab.base_ts:
        db.device_cache.touch(tab, "_device_adj")
        return adj
    n_edges = sum(len(v) for v in tab.edges.values())
    if n_edges < db.device_min_edges:
        return None
    edges32 = _edges32(tab.edges)
    if edges32 is None:
        return None
    with _tile_load(pred=tab.pred, kind="adj",
               edges=n_edges):
        adj = build_adjacency(edges32)
    tab._device_adj = adj
    tab._device_adj_ts = tab.base_ts
    db.device_cache.put(tab, "_device_adj", adj)
    return adj


class VectorBlock:
    """The resident base block of a vector tablet: `rows` (float32,
    zero-padded to the bucket unit) and `live` (bool over the padded
    rows, all True: the mask operand of a lane of ops/knn's program
    that has none of its own) on the device, and `nbytes`, both
    together. An object and not the bare array, so that the requests
    whose read_ts resolved to THIS block can meet on it
    (query/devicecall.Rendezvous.at)."""

    def __init__(self, rows, live):
        self.rows = rows
        self.live = live
        self.nbytes = int(rows.nbytes) + int(live.nbytes)   # tile_cache


def device_vector_block(db, tab, base_vecs: np.ndarray) -> VectorBlock:
    """The tablet's base vector block (storage/vecstore.py) on the
    device: a tile like the adjacency tiles, cached per base_ts,
    counted in `device_cache_bytes` under the HBM budget and evictable
    (a tile larger than the budget is admitted alone, tile_cache.py).
    Rows are zero-padded to the bucket unit ONCE here, host-side, so
    ops/knn never copies the block per query. The gauge
    `device_vector_block_bytes{predicate}` is the rows resident (what
    a scan reads): dtype x padded shape, 0 after an eviction."""
    from dgraph_tpu.ops.knn import pad_rows

    block = getattr(tab, "_device_vecs", None)
    if block is not None and getattr(tab, "_device_vecs_ts", -1) \
            == tab.base_ts:
        db.device_cache.touch(tab, "_device_vecs")
        return block
    with _tile_load(pred=tab.pred, kind="vecs", rows=len(base_vecs)):
        rows = pad_rows(base_vecs)
        block = VectorBlock(*jax.block_until_ready(
            (jax.numpy.asarray(rows),
             jax.device_put(np.ones(len(rows), bool)))))
    tab._device_vecs = block
    tab._device_vecs_ts = tab.base_ts
    labels = {"predicate": tab.pred}
    db.device_cache.put(
        tab, "_device_vecs", block,
        on_evict=lambda: set_gauge("device_vector_block_bytes", 0.0,
                                   labels=labels))
    set_gauge("device_vector_block_bytes", float(block.rows.nbytes),
              labels=labels)
    return block


_MASK_ATTR = "_device_mask@"


class SimilarMask:
    """One resident candidate mask of a vector block: the padded bool
    rows on the device, how many are set, and what it is exact for
    (the row map it was laid over and the two tablets' base_ts)."""

    __slots__ = ("mask", "n_cand", "row_uids", "nbytes")

    def __init__(self, mask, n_cand: int, row_uids: np.ndarray):
        self.mask = mask
        self.n_cand = n_cand
        self.row_uids = row_uids
        self.nbytes = int(mask.nbytes)     # device bytes (tile_cache)


def _mask_attr(posting: tuple) -> str:
    pred, token, _ = posting
    return f"{_MASK_ATTR}{pred}@{token.hex()}"


def _mask_ts(tab, posting: tuple) -> tuple:
    return (tab.base_ts, posting[2])


def similar_mask_tile(db, tab, posting: tuple, row_uids: np.ndarray
                      ) -> Optional[SimilarMask]:
    """The resident mask of a vector tablet's block for ONE clean
    posting, `posting` = (filter predicate, token, that tablet's
    base_ts): the provenance of a candidate set, never its contents.
    None unless a tile was stored under both tablets' current base_ts
    and over this very row map; a hit is marked used."""
    attr = _mask_attr(posting)
    tile = getattr(tab, attr, None)
    if tile is None or tile.row_uids is not row_uids \
            or getattr(tab, attr + "_ts", -1) != _mask_ts(tab, posting):
        return None
    db.device_cache.touch(tab, attr)
    return tile


def store_similar_mask(db, tab, posting: tuple, row_uids: np.ndarray,
                       mask_pad: np.ndarray, n_cand: int) -> SimilarMask:
    """Make a call's host mask (padded to the block's rows) the
    resident tile of its posting: ONE `jax.device_put` (an upload, no
    program), counted in `device_cache_bytes` under the HBM budget and
    evictable like the block it masks. The gauge
    `device_similar_mask_bytes{predicate}` sums a vector predicate's
    resident masks."""
    attr = _mask_attr(posting)
    with _tile_load(pred=tab.pred, kind="similar_mask",
                    rows=len(row_uids)):
        tile = SimilarMask(jax.device_put(mask_pad), int(n_cand),
                           row_uids)
    setattr(tab, attr, tile)
    setattr(tab, attr + "_ts", _mask_ts(tab, posting))
    ref = weakref.ref(tab)

    def evicted():
        # the LRU has set both attributes to "absent": take them off,
        # so a filter over many values does not grow the tablet
        t = ref()
        if t is not None:
            vars(t).pop(attr, None)
            vars(t).pop(attr + "_ts", None)
            _set_mask_gauge(t)

    db.device_cache.put(tab, attr, tile, on_evict=evicted)
    _set_mask_gauge(tab)
    return tile


def _set_mask_gauge(tab) -> None:
    resident = sum(
        t.nbytes for a, t in list(vars(tab).items())
        if a.startswith(_MASK_ATTR) and isinstance(t, SimilarMask))
    set_gauge("device_similar_mask_bytes", float(resident),
              labels={"predicate": tab.pred})


def _clean_resident(db, tab, read_ts: int, want_uid: bool = True,
                    allow_dirty: bool = False) -> bool:
    """Shared residency policy: rolled-up committed state only.

    Rollup folds the delta overlay into the base arrays — a WRITE. In
    single-threaded embedded use it may run lazily right here, but a
    server running queries concurrently (read lock shared) must set
    db.rollup_in_read = False and fold from its write path instead
    (server/http.py janitor), or concurrent readers would see torn
    tablets."""
    if (tab.schema.value_type.name == "UID") != want_uid:
        return False
    if tab.dirty():
        if getattr(db, "rollup_in_read", True):
            wm = db.fold_watermark()
            if wm >= tab.max_commit_ts:
                tab.rollup(wm)
        if tab.dirty() and not allow_dirty:
            return False  # live overlay -> host path
    return read_ts >= tab.base_ts


def _edges32(edge_dict) -> Optional[dict]:
    edges32 = {}
    for src, dst in edge_dict.items():
        if src > _MAX_U32 or (len(dst) and int(dst[-1]) > _MAX_U32):
            return None
        edges32[int(src)] = dst.astype(np.uint32)
    return edges32


def _transposed_edges(tab) -> dict:
    """{dst -> sorted src} for a tablet, regardless of @reverse (the
    schema directive gates *queryable* reverse edges; SSSP path
    reconstruction needs the transpose either way)."""
    if tab.schema.reverse and tab.reverse:
        return tab.reverse
    srcs = []
    dsts = []
    for s, dl in tab.edges.items():
        srcs.append(np.full(len(dl), s, np.uint64))
        dsts.append(dl)
    if not srcs:
        return {}
    src_all = np.concatenate(srcs)
    dst_all = np.concatenate(dsts)
    order = np.argsort(dst_all, kind="stable")
    src_all, dst_all = src_all[order], dst_all[order]
    uniq, starts = np.unique(dst_all, return_index=True)
    bounds = np.append(starts, len(dst_all))
    return {int(d): np.sort(src_all[bounds[i]: bounds[i + 1]])
            for i, d in enumerate(uniq)}


def device_radjacency(db, tab, read_ts: int,
                      allow_dirty: bool = False
                      ) -> Optional[DeviceAdjacency]:
    """Reverse-direction expansion tiles (~pred traversal): a
    DeviceAdjacency over the tablet's reverse map. Requires @reverse
    (the executor rejects ~pred queries otherwise). allow_dirty as in
    device_adjacency."""
    if not tab.schema.reverse or not _clean_resident(
            db, tab, read_ts, allow_dirty=allow_dirty):
        return None
    adj = getattr(tab, "_device_radj", None)
    if adj is not None and getattr(tab, "_device_radj_ts", -1) == tab.base_ts:
        db.device_cache.touch(tab, "_device_radj")
        return adj
    n_edges = sum(len(v) for v in tab.reverse.values())
    if n_edges < db.device_min_edges:
        return None
    edges32 = _edges32(tab.reverse)
    if edges32 is None:
        return None
    with _tile_load(pred=tab.pred, kind="radj",
               edges=n_edges):
        adj = build_adjacency(edges32)
    tab._device_radj = adj
    tab._device_radj_ts = tab.base_ts
    db.device_cache.put(tab, "_device_radj", adj)
    return adj


def device_bitadjacency(db, tab, read_ts: int, transpose: bool = False,
                        dense: bool = False, walk: bool = False):
    """Bitmap adjacency (ops/bitgraph) for @recurse, BFS and SSSP.
    Same residency policy as device_adjacency: clean rolled-up tablets
    only; cached per base_ts. With transpose=True the expansion walks
    edges dst->src (`~pred`, and distance-to-target in shortest
    paths). With dense=True the tile also carries the hub rows the
    served traversal streams (bitgraph.attach_dense), as many as the
    HBM budget has room for when they are set, on first asking; they
    stay with the tile, and a tile evicted and built again takes the
    room there is then. Where the engine has a mesh whose `uid` axis
    splits a predicate (uid_mesh), the served traversal's rows are
    split over its chips: the room is then ONE chip's, every chip
    holds its run of the rows, and the tile counts a chip's bytes.
    With walk=True the tile also carries its slots' uids
    (bitgraph.attach_uids: 4 B a vertex), by which the served
    shortest path's walk breaks ties (bitgraph.bfs_paths, over the
    transposed tile WITH its hub rows).
    A tile like the others: counted in `device_cache_bytes` under the
    HBM budget and evictable. The gauges
    `device_bitadj_bytes{predicate}` (the in-neighbour matrices' and
    the hub rows' bytes on the device, all chips together),
    `device_bitadj_chip_bytes{predicate}` (the fullest chip's share
    of them), `device_bitadj_shards{predicate}` (the chips they are
    split over, 1 without a mesh) and
    `device_bitadj_edges{predicate}` and
    `device_bitadj_hub_rows{predicate}` (the vertices held as bitmap
    rows and not gathered) say what is resident, 0 after an
    eviction; the transposed tile is labelled `~pred`."""
    if not _clean_resident(db, tab, read_ts):
        return None
    attr = "_device_badj_t" if transpose else "_device_badj"
    badj = getattr(tab, attr, None)
    fresh = badj is None or getattr(tab, attr + "_ts", -1) != tab.base_ts
    if not fresh and (badj.dense_from is not None or not dense) \
            and (badj.uids_dev is not None or not walk):
        db.device_cache.touch(tab, attr)
        return badj
    from dgraph_tpu.ops.bitgraph import (
        attach_dense, attach_uids, build_bitadjacency, resident_bytes,
    )
    if fresh:
        n_edges = sum(len(v) for v in tab.edges.values())
        if n_edges < db.device_min_edges:
            return None
        edges32 = _edges32(_transposed_edges(tab) if transpose
                           else tab.edges)
        if edges32 is None:
            return None
        with _tile_load(pred=tab.pred, kind="bitadj", edges=n_edges):
            badj = build_bitadjacency(edges32)
    if dense and badj.dense_from is None:
        with _tile_load(pred=tab.pred, kind="bitadj_dense",
                        edges=badj.n_edges):
            attach_dense(badj, max(
                0, db.device_cache.budget - db.device_cache.bytes),
                mesh=uid_mesh(db))
    if walk:
        attach_uids(badj)
    setattr(tab, attr, badj)
    setattr(tab, attr + "_ts", tab.base_ts)
    labels = {"predicate": ("~" if transpose else "") + tab.pred}

    def gauges(nbytes: float, shards: float, edges: float,
               hub_rows: float = 0.0) -> None:
        set_gauge("device_bitadj_hub_rows", hub_rows, labels=labels)
        set_gauge("device_bitadj_bytes", nbytes, labels=labels)
        # equal runs of rows a chip: every chip is the fullest
        set_gauge("device_bitadj_chip_bytes", nbytes / max(shards, 1),
                  labels=labels)
        set_gauge("device_bitadj_shards", shards, labels=labels)
        set_gauge("device_bitadj_edges", edges, labels=labels)

    db.device_cache.put(tab, attr, badj,
                        on_evict=lambda: gauges(0.0, 0.0, 0.0))
    gauges(float(resident_bytes(badj)), float(badj.shards),
           float(badj.n_edges), float(badj.dense_rows))
    return badj


def uid_mesh(db):
    """The engine's mesh where its `uid` axis splits one predicate
    over two chips or more, else None (one chip)."""
    mesh = getattr(db, "mesh", None)
    if mesh is None or "uid" not in mesh.axis_names \
            or mesh.shape["uid"] < 2:
        return None
    return mesh


def device_sharded_adjacency(db, tab, read_ts: int,
                             reverse: bool = False):
    """UID-range-sharded adjacency over the engine's device mesh — the
    multi-part posting list tier (posting/list.go:1149 splitUpList):
    predicates above db.shard_min_edges get range-partitioned across
    the mesh's `uid` axis and expanded with one shard_map+all_gather
    per level (parallel/dist_graph).

    Residency rules match the single-chip tiles; requires db.mesh with
    a >1-sized `uid` axis."""
    mesh = uid_mesh(db)
    if mesh is None:
        return None
    if reverse and not tab.schema.reverse:
        return None
    if not _clean_resident(db, tab, read_ts):
        return None
    attr = "_device_sadj_r" if reverse else "_device_sadj"
    sadj = getattr(tab, attr, None)
    if sadj is not None and getattr(tab, attr + "_ts", -1) == tab.base_ts:
        db.device_cache.touch(tab, attr)
        return sadj
    # memoize the below-threshold verdict per base_ts: without it,
    # every expansion level on a mesh-enabled db would re-walk the
    # whole edge map just to fall through to the single-chip tier
    if getattr(tab, attr + "_small_ts", -1) == tab.base_ts:
        return None
    edge_map = tab.reverse if reverse else tab.edges
    n_edges = sum(len(v) for v in edge_map.values())
    if n_edges < db.shard_min_edges:
        setattr(tab, attr + "_small_ts", tab.base_ts)
        return None
    edges32 = _edges32(edge_map)
    if edges32 is None:
        return None
    from dgraph_tpu.parallel.dist_graph import build_sharded_adjacency
    with _tile_load(pred=tab.pred, kind="sharded",
               edges=n_edges):
        sadj = build_sharded_adjacency(
            edges32, n_shards=mesh.shape["uid"]).put(mesh)
    setattr(tab, attr, sadj)
    setattr(tab, attr + "_ts", tab.base_ts)
    db.device_cache.put(tab, attr, sadj)
    return sadj


def host_column_tile(db, tab, attr: str, obj) -> None:
    """Account a host-side columnar export (value-column view, token
    CSR) against the tile budget under the same LRU + eviction policy
    as the device tiles: the payload copies are NOT free host memory,
    and eviction clears the tablet attribute (`attr`/`attr`+"_ts") so
    the next consumer rebuilds. Put only on first sight — a put per
    query would re-scan the LRU under its lock for nothing."""
    cache = db.device_cache
    if not cache.touch(tab, attr):
        cache.put(tab, attr, obj)


def device_values(db, tab, read_ts: int, lang: str = ""):
    """Sortable value view for order-by / inequality offload (scalar
    tablets; same rollup-then-check policy as the adjacency tiles).
    `lang` selects language-tagged order keys (ref worker/sort.go
    multiSort with langs) — each language gets its own cached tile."""
    if not _clean_resident(db, tab, read_ts, want_uid=False):
        return None
    attr = "_device_values" if not lang else f"_device_values@{lang}"
    dv = getattr(tab, attr, None)
    if dv is not None and getattr(tab, attr + "_ts", -1) == tab.base_ts:
        db.device_cache.touch(tab, attr)
        return dv
    pairs = tab.sort_key_pairs(lang)
    if len(pairs) < db.device_min_edges:
        return None
    if pairs and max(pairs) > _MAX_U32:
        return None
    with _tile_load(pred=tab.pred, kind="values",
               rows=len(pairs)):
        dv = build_values(pairs)
    setattr(tab, attr, dv)
    setattr(tab, attr + "_ts", tab.base_ts)
    db.device_cache.put(tab, attr, dv)
    return dv


def expand_np(adj: DeviceAdjacency, src_u64: np.ndarray,
              sync=None) -> np.ndarray:
    """Host frontier -> device expand -> host result.

    The jitted expander is cached per (frontier bucket size) on the
    adjacency object, so repeated traversal levels reuse compiled code.
    The executor calls this inside a `device_call` block and hands its
    `wait` as `sync`: applied to the dispatched result before it is
    fetched, it is where the request's device time is taken.
    """
    # uids beyond uint32 cannot exist in a <=32-bit tablet: drop them
    # instead of letting astype(uint32) alias them onto real low uids.
    # Sort: the kernels' membership tests binary-search INTO the
    # frontier, and callers (e.g. order-by results) may pass any order.
    src_u64 = np.sort(src_u64[src_u64 <= _MAX_U32])
    f_pad = pad_to(len(src_u64))
    cache = getattr(adj, "_expander_cache", None)
    if cache is None:
        cache = adj._expander_cache = {}
    fn = cache.get(f_pad)
    if fn is None:
        out_size = max_expansion(adj, f_pad)

        def expand_frontier(fr):
            return expand(adj, fr, out_size)

        fn = jax.jit(expand_frontier)
        cache[f_pad] = fn
    fr = np.full(f_pad, SENTINEL, np.uint32)
    fr[: len(src_u64)] = src_u64.astype(np.uint32)
    res = fn(jax.numpy.asarray(fr))
    return to_numpy(sync(res) if sync else res).astype(np.uint64)
