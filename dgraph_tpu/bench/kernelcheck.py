"""Compile-and-compare sweep over every device kernel the engine can
reach, run INSIDE the process that owns the chip.

Tier-1 runs on the CPU backend, where `ops/uidvec` picks its CPU
lowerings — so "does this kernel compile on the accelerator, and does it still agree with its twin there" is a
question only the chip process can answer. `run()` answers it per
kernel, at the shape the kernel has in service, against its host or
XLA twin on the same inputs. chip_smoke.py calls it through
`POST /debug/kernelcheck`, a route only an alpha started with
`--kernelcheck` has: the sweep blocks for minutes and takes gigabytes
of device memory beside the resident tiles, so a serving alpha never
offers it.

Each check reports {"ok", "shape", "seconds", ...detail} or, when the
compiler or the runtime refuses, {"ok": false, "error": <its message>}.
Nothing here falls back: a kernel that does not compile is recorded
as exactly that. `tiny` shrinks every shape — it exists for the CPU
tests, which call `run()` directly; the HTTP route does not pass it.
"""

from __future__ import annotations

import time

import numpy as np


def _rng(seed: int = 0):
    return np.random.default_rng(seed)


def _pick_uid_tablet(db, pred: str | None):
    if pred:
        tab = db.tablets.get(pred)
        if tab is None:
            raise ValueError(f"no predicate {pred!r}")
        return tab
    best = None
    for tab in db.tablets.values():
        if tab.schema.value_type.name != "UID":
            continue
        n = tab.edge_count(False)
        if best is None or n > best[0]:
            best = (n, tab)
    if best is None:
        raise ValueError("store has no uid predicate")
    return best[1]


def _badj(db, pred):
    from dgraph_tpu.engine.device_cache import device_bitadjacency
    tab = _pick_uid_tablet(db, pred)
    badj = device_bitadjacency(db, tab, db.coordinator.max_assigned(),
                               transpose=True)
    if badj is None:
        raise RuntimeError(
            f"{tab.pred!r} has no device bitadjacency (dirty tablet, "
            f"> 32-bit uids or fewer than device_min_edges edges)")
    return tab, badj


def _vec_inputs(tiny):
    """SIFT1M's shape: 1M x 128 corpus, a 256-query batch."""
    rng = _rng(3)
    corpus = rng.standard_normal((4096 if tiny else 1_000_000, 128),
                                 dtype=np.float32)
    queries = rng.standard_normal((16 if tiny else 256, 128),
                                  dtype=np.float32)
    return corpus, queries


def check_knn_exact(tiny) -> dict:
    """The exact similar_to tier must return the float64 host's top-k
    SETS — the tier is documented exact, so nothing is loosened."""
    from dgraph_tpu.ops import knn

    corpus, queries = _vec_inputs(tiny)
    k, n_host = 10, (8 if tiny else 64)
    out = {}
    ok = True
    for metric in ("cosine", "euclidean"):
        idx, _sc = knn.topk_device(corpus, queries, k, metric,
                                   two_stage=False)
        want, _ = knn.topk_host(corpus, queries[:n_host], k, metric)
        same = [set(idx[i].tolist()) == set(want[i].tolist())
                for i in range(n_host)]
        out[metric] = {"queries_checked": n_host,
                       "topk_sets_equal": int(sum(same))}
        ok = ok and all(same)
    return {"ok": ok, "k": k, "metrics": out,
            "shape": {"corpus": list(corpus.shape),
                      "queries": list(queries.shape)}}


def check_bfs_digest(tiny) -> dict:
    """make_bfs_digest_batched (XLA gathers) at the serving batch
    shape; twin = the NumPy CSR BFS on the first 32 queries."""
    import jax.numpy as jnp

    from dgraph_tpu.bench.bfsgraph import (
        csr_to_dict, make_graph, numpy_bfs,
    )
    from dgraph_tpu.ops.bitgraph import (
        build_bitadjacency, build_core_adjacency,
        make_bfs_digest_batched, make_frontier_counts_batched,
        uid_lists_to_seed_slots,
    )

    nodes, edges, batch = (2000, 20_000, 64) if tiny \
        else (2_000_000, 21_000_000, 24576)
    seeds, depth = 8, 3
    uniq_src, indptr, dst = make_graph(nodes, edges)
    badj = build_bitadjacency(csr_to_dict(uniq_src, indptr, dst))
    core = build_core_adjacency(badj)
    seed_mat = np.sort(uniq_src[_rng(1).integers(
        0, len(uniq_src), (batch, seeds))], axis=1)
    slots = jnp.asarray(uid_lists_to_seed_slots(badj, list(seed_mat),
                                                seeds))
    digest = make_bfs_digest_batched(badj, core, depth, batch, seeds)
    sums, col0 = digest(slots)
    sums = np.asarray(sums)
    got = np.asarray(make_frontier_counts_batched(32)(col0))
    want = [numpy_bfs(uniq_src, indptr, dst, np.unique(seed_mat[i]),
                      depth) for i in range(32)]
    return {"ok": got.tolist() == want,
            "level_popcounts": sums.tolist(),
            "shape": {"nodes": nodes, "edges": int(len(dst)),
                      "batch": batch, "words": (batch + 31) // 32,
                      "depth": depth}}


def check_bfs_traverse(tiny) -> dict:
    """bitgraph.bfs_traverse, the served k-hop traversal, every lane
    ridden with its own root set and depth, over hub rows (on the
    chip, the Pallas kernel that reads them once for all lanes) and
    gathered classes both; twin = ops/traverse.bfs_reach with dedup
    off over the forward adjacency: a lane's reached set is every uid
    a walk of 1..depth edges from its roots ends at."""
    from dgraph_tpu.bench.bfsgraph import csr_to_dict, make_graph
    from dgraph_tpu.ops import bitgraph
    from dgraph_tpu.ops.graph import build_adjacency
    from dgraph_tpu.ops.traverse import bfs_reach

    nodes, n_edges = (2000, 20_000) if tiny else (200_000, 4_000_000)
    uniq_src, indptr, dst = make_graph(nodes, n_edges)
    edges = csr_to_dict(uniq_src, indptr, dst)
    badj = bitgraph.build_bitadjacency(edges)
    # half of the rows the budget would hold, so that both halves of
    # a level run
    rows = sum(int(b.in_nb.shape[0]) for b in badj.buckets) // 2
    bitgraph.attach_dense(
        badj, rows * 4 * bitgraph.hub_row_words(badj.n_slots))
    adj = build_adjacency(edges)
    rng = _rng(9)
    riders, want = [], []
    for lane in range(bitgraph.LANES):
        roots = np.unique(uniq_src[rng.integers(
            0, len(uniq_src), 1 + lane % 3)]).astype(np.uint32)
        depth = 1 + lane % 3
        riders.append((bitgraph.seed_slots(badj, roots), depth))
        want.append(np.unique(np.concatenate(
            [lv[lv != 0xFFFFFFFF] for lv in
             bfs_reach(adj, roots, depth, dedup=False)])))
    tally, reached = (np.asarray(x) for x in
                      bitgraph.traverse(badj, riders))
    counts, levels, tiles = tally
    got = [bitgraph.lane_uids(badj, reached, i)
           for i in range(len(riders))]
    same = [np.array_equal(g, w) and int(c) == len(w)
            for g, w, c in zip(got, want, counts)]
    return {"ok": all(same), "lanes_equal": int(sum(same)),
            "reached": counts.tolist(), "levels_run": levels.tolist(),
            "hub_tiles_streamed": int(tiles[0]), "hub_tiles": int(tiles[1]),
            "column_levels": int(tiles[2]),
            "shape": {"slots": badj.n_slots, "edges": badj.n_edges,
                      "hub_rows": 0 if badj.dense is None
                      else list(badj.dense.shape),
                      "gathered_classes": len(badj.gathered),
                      "lanes": bitgraph.LANES}}


def check_bfs_paths(tiny) -> dict:
    """bitgraph.bfs_paths, the served one-path `shortest`, every lane
    ridden with its own pair and depth, over the TRANSPOSED tile with
    hub rows and gathered classes both; twin = a plain search in
    NumPy: distances to the target against the edges, then from the
    source the smallest-uid out-neighbour one nearer."""
    from dgraph_tpu.bench.bfsgraph import csr_to_dict, make_graph
    from dgraph_tpu.ops import bitgraph

    nodes, n_edges = (2000, 20_000) if tiny else (200_000, 4_000_000)
    uniq_src, indptr, dst = make_graph(nodes, n_edges)
    edges = csr_to_dict(uniq_src, indptr, dst)
    # the edges against their direction: a vertex's in-neighbours at
    # src[at[v]:at[v + 1]], sorted
    order = np.argsort(dst, kind="stable")
    src = np.repeat(uniq_src, np.diff(indptr))[order].astype(np.int64)
    at = np.searchsorted(dst[order], np.arange(nodes + 2))
    badj = bitgraph.build_bitadjacency({
        v: src[at[v]:at[v + 1]].astype(np.uint32)
        for v in np.unique(dst).tolist()})
    rows = sum(int(b.in_nb.shape[0]) for b in badj.buckets) // 2
    bitgraph.attach_dense(
        badj, rows * 4 * bitgraph.hub_row_words(badj.n_slots))
    bitgraph.attach_uids(badj)
    rng = _rng(11)
    pairs, want = [], []
    for lane in range(bitgraph.LANES):
        a = int(uniq_src[rng.integers(0, len(uniq_src))])
        b = int(dst[rng.integers(0, len(dst))])
        depth = (2, 3, 15)[lane % 3]
        slots, _ = bitgraph._uid_slots(badj, np.asarray([a, b], np.uint32))
        pairs.append((int(slots[0]), int(slots[1]), depth))
        dist = np.full(nodes + 1, -1, np.int64)
        dist[b], frontier = 0, np.asarray([b])
        for hop in range(1, depth + 1):
            if dist[a] >= 0 or not len(frontier):
                break
            lens = at[frontier + 1] - at[frontier]
            met = np.unique(src[np.repeat(
                at[frontier] - (np.cumsum(lens) - lens), lens)
                + np.arange(int(lens.sum()))])
            frontier = met[dist[met] < 0]
            dist[frontier] = hop
        path = [a] if dist[a] >= 0 else []
        while path and path[-1] != b:
            nbs = edges[path[-1]].astype(np.int64)
            path.append(int(nbs[dist[nbs] == dist[path[-1]] - 1][0]))
        want.append(path)
    out = np.asarray(bitgraph.paths(badj, pairs))
    got = [bitgraph.path_uids(badj, out[i]) for i in range(len(pairs))]
    same = [g == w for g, w in zip(got, want)]
    return {"ok": all(same), "lanes_equal": int(sum(same)),
            "hops": out[:len(pairs), 0].tolist(),
            "levels_run": int(out[-1, 0]),
            "hub_tiles_streamed": int(out[-1, 1]),
            "hub_tiles": int(out[-1, 2]), "column_levels": int(out[-1, 3]),
            "shape": {"slots": badj.n_slots, "edges": badj.n_edges,
                      "hub_rows": 0 if badj.dense is None
                      else list(badj.dense.shape),
                      "gathered_classes": len(badj.gathered),
                      "lanes": bitgraph.LANES}}


def check_sssp_dist(db, pred) -> dict:
    """sssp_dist over the loaded graph's bitadjacency; twin = a NumPy
    level-synchronous BFS over the tablet's flat edge list, walked
    against the edge direction as the transposed bitadjacency is."""
    from dgraph_tpu.ops.bitgraph import sssp_dist

    tab, badj = _badj(db, pred)
    src = np.repeat(np.fromiter(tab.edges.keys(), np.uint64,
                                len(tab.edges)),
                    [len(dl) for dl in tab.edges.values()])
    dst = np.concatenate([np.asarray(dl, np.uint64)
                          for dl in tab.edges.values()])
    heads = np.unique(dst)
    seeds = np.sort(heads[_rng(6).choice(
        len(heads), min(len(heads), 64), replace=False)])
    iters = 4
    got = sssp_dist(badj, seeds.astype(np.uint32), max_iters=iters)
    want = {int(s): 0 for s in seeds}
    frontier = seeds
    for hop in range(1, iters + 1):
        nxt = np.unique(src[np.isin(dst, frontier)])
        frontier = nxt[~np.isin(nxt, np.fromiter(want, np.uint64,
                                                 len(want)))]
        if not len(frontier):
            break
        for v in frontier.tolist():
            want[int(v)] = hop
    return {"ok": got == want, "pred": tab.pred, "seeds": len(seeds),
            "reached": len(got),
            "shape": {"slots": badj.n_slots, "max_iters": iters}}


def check_range_select(db) -> dict:
    """The inequality range kernel over the loaded graph's largest
    numeric value view — the planner's cold priors keep ineq stages on
    the host's cached key arrays until a predicate holds several
    hundred thousand keys, so below that only this check reaches the
    kernel; twin = a NumPy mask over the tablet's sort-key arrays."""
    from dgraph_tpu.engine.device_cache import device_values
    from dgraph_tpu.ops.graph import range_select
    from dgraph_tpu.ops.uidvec import to_numpy

    ts = db.coordinator.max_assigned()
    numeric = [t for t in db.tablets.values()
               if t.schema.value_type.name in ("INT", "FLOAT",
                                               "DATETIME")]
    if not numeric:
        raise ValueError("store has no numeric predicate")
    tab = max(numeric, key=lambda t: len(t.values))
    dv = device_values(db, tab, ts)
    if dv is None:
        raise RuntimeError(f"{tab.pred!r} has no device value view")
    uids, keys = tab.sort_key_arrays("")
    lo, hi = (int(v) for v in np.quantile(keys, (0.25, 0.75)))
    got = to_numpy(range_select(dv, lo, hi)).astype(np.uint64)
    want = uids[(keys >= lo) & (keys <= hi)]
    return {"ok": bool(np.array_equal(got, want)), "pred": tab.pred,
            "selected": int(len(want)),
            "shape": {"rows": int(len(uids))}}


def check_setops_cosort(tiny) -> dict:
    """The k-way union and intersection co-sorts (ops/uidvec through
    ops/setops' device variants) — off the CPU these take the sort
    lowering tier-1 never runs, and the executor's gate only sends
    them multi-million-element operands; twin = the NumPy set algebra."""
    from dgraph_tpu.ops import setops

    n = 1 << (10 if tiny else 18)
    rng = _rng(8)
    parts = [np.unique(rng.integers(1, 4 * n, n, dtype=np.uint64))
             for _ in range(4)]
    union = setops.union_many_device(parts)
    isect = setops.intersect_many_device(parts)
    return {"ok": bool(
                union is not None and isect is not None
                and np.array_equal(union, setops.union_many(parts))
                and np.array_equal(isect, setops.intersect_many(parts))),
            "union": None if union is None else int(len(union)),
            "intersection": None if isect is None else int(len(isect)),
            "shape": {"parts": 4, "rows_each": n}}


def check_fused_rank_page(tiny) -> dict:
    """One whole-block fused executable (filter mask -> order -> page)
    through query/fusion's own jit seam at the 500M store's group
    width; twin = NumPy filter + lexsort + slice."""
    import jax.numpy as jnp

    from dgraph_tpu.ops.graph import FUSED_SEL_CAP
    from dgraph_tpu.ops.uidvec import SENTINEL
    from dgraph_tpu.query import fusion

    n = 4096 if tiny else 262_144
    rng = _rng(7)
    cand = np.sort(rng.choice(np.arange(1, 8 * n, dtype=np.uint32), n,
                              replace=False))
    ranks = rng.permutation(n).astype(np.int32)   # injective keys
    mask = rng.random(n) < 0.5
    window, offset = 16, 5
    shift = max(0, (n - 1).bit_length() - 12)
    run = fusion.fused_executable(
        None, None, "and", (), (False,), True, (False,), window,
        shift, (), (False,))
    out = np.asarray(run(
        jnp.asarray(cand), (), (), (), (jnp.asarray(mask),),
        ((jnp.asarray(cand), jnp.asarray(ranks)),),
        jnp.int32(0), jnp.int32(offset)))
    sel_count, n_kept = int(out[-2]), int(out[-1])
    kept, kr = cand[mask], ranks[mask]
    want = kept[np.lexsort((kept, kr))][offset:offset + window]
    page = out[:window]
    return {"ok": bool(n_kept == int(mask.sum())
                       and sel_count <= FUSED_SEL_CAP
                       and np.array_equal(page[page != SENTINEL], want)),
            "sel_count": sel_count, "n_kept": n_kept,
            "shape": {"rows": n, "window": window, "shift": shift}}


def run(db, pred: str | None = None, checks: tuple = (),
        tiny: bool = False) -> dict:
    """Run the named checks (all by default); never raises for a
    kernel's own failure. `pred` names the uid predicate whose
    bitadjacency sssp_dist uses, range_select picks the store's
    largest numeric predicate; the rest make their own inputs and
    need no data. The
    memory-hungriest check (the BFS digest at its benchmark batch)
    goes first, before the others have touched the allocator."""
    from dgraph_tpu.utils.backend import device_report

    table = [
        ("bfs_digest_xla", lambda: check_bfs_digest(tiny)),
        ("bfs_traverse", lambda: check_bfs_traverse(tiny)),
        ("bfs_paths", lambda: check_bfs_paths(tiny)),
        ("sssp_dist", lambda: check_sssp_dist(db, pred)),
        ("range_select", lambda: check_range_select(db)),
        ("fused_rank_page", lambda: check_fused_rank_page(tiny)),
        ("setops_cosort", lambda: check_setops_cosort(tiny)),
        ("knn_exact", lambda: check_knn_exact(tiny)),
    ]
    unknown = set(checks) - {name for name, _ in table}
    if unknown:
        raise ValueError(f"unknown kernel checks {sorted(unknown)}")
    kernels = {}
    for name, fn in table:
        if checks and name not in checks:
            continue
        t0 = time.monotonic()
        try:
            res = fn()
        except Exception as e:  # noqa: BLE001 — the compiler's or the
            # runtime's refusal IS this check's result; the sweep goes on
            res = {"ok": False,
                   "error": f"{type(e).__name__}: {e}"[:2000]}
        res["seconds"] = round(time.monotonic() - t0, 2)
        kernels[name] = res
    return {"device": device_report(), "tiny": bool(tiny),
            "kernels": kernels}
