"""The k-hop traversals in flight ride one device call (PR 34):
`ops/bitgraph.bfs_traverse`'s lanes, `query/devicecall.Rendezvous`,
and the executor's one dispatch site for them, `_recurse_device`.
Exactness and isolation are the deployment's guarantees: a lane's
answer is what the request gets alone, from the host tier and from the
plain reference's method (benchmark/datasets/graph500_plain.py)."""

import importlib.util
import json
import os
import threading
import time

import numpy as np
import pytest

from dgraph_tpu.engine.db import GraphDB
from dgraph_tpu.ingest.bulk import bulk_load
from dgraph_tpu.ops import bitgraph
from dgraph_tpu.query import executor as executor_mod
from dgraph_tpu.query.devicecall import Rendezvous
from dgraph_tpu.utils import metrics, tracing
from dgraph_tpu.utils.reqctx import (
    Cancelled, DeadlineExceeded, RequestContext,
)
from recurse_cases import column_lanes, traverse_as

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name):
    path = os.path.join(ROOT, "benchmark", "datasets", name + ".py")
    spec = importlib.util.spec_from_file_location("tb_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


graph500 = _load("graph500")
plain = _load("graph500_plain")
graph500._CACHE["program"] = graph500.PROGRAM_ROOT

GRAPHS = [(8, 2**31 + 5), (9, 31_000_017), (10, 7)]
KHOP = ("{ var(func: uid(%s)) @recurse(depth: %d, loop: false) "
        "{ n as link } khop(func: uid(n)) { count(uid) } }")
UIDS = ("{ var(func: uid(%s)) @recurse(depth: %d, loop: false) "
        "{ n as link } khop(func: uid(n)) { uid } }")


def _q(template, roots, depth):
    return template % (", ".join(hex(r) for r in roots), depth)


def _db(tmp, scale, seed, **kw):
    path = os.path.join(tmp, f"g{scale}-{seed}.rdf")
    if not os.path.exists(path):
        with open(path, "w") as f:
            graph500.write_rdf(f, scale, seed)
    return bulk_load([path], schema=graph500.SCHEMA, db=GraphDB(**kw))


@pytest.fixture(scope="module")
def worlds(tmp_path_factory):
    """(facts, device-tier db, host-tier db, csr) a seeded graph."""
    tmp = str(tmp_path_factory.mktemp("graph500"))
    out = {}
    for scale, seed in GRAPHS:
        src, dst, roots, vertices = graph500.graph(scale, seed)
        offsets = np.zeros(vertices + 1, np.int64)
        np.cumsum(np.bincount(src, minlength=vertices), out=offsets[1:])
        out[scale, seed] = (
            {"seed": seed, "roots": roots, "vertices": vertices},
            _db(tmp, scale, seed, prefer_device=True, device_min_edges=1),
            _db(tmp, scale, seed, prefer_device=False),
            (offsets, dst, vertices))
    return out


@pytest.fixture(autouse=True)
def every_bound_recurse_on_the_device(monkeypatch):
    """The gate is not what is tested here (tests/test_recurse_bound.py
    does): every bound @recurse of a device-tier db takes the chip."""
    monkeypatch.setattr(executor_mod.Executor, "_device_worth",
                        lambda self, *a, **kw: True)


def _counter(name):
    return sum(v for k, v in metrics.snapshot()["counters"].items()
               if k.startswith(name))


def _data(db, q, **kw):
    body = db.query_json(q, **kw)
    return body[len('{"data":'):body.rfind(',"extensions":')]


def _plain_reached(csr, roots, hops):
    """The plain reference's walk from a root SET: a vertex is within
    k hops of the set where it is of one of its members."""
    n = np.zeros(csr[2], bool)
    for r in roots:
        n |= plain.reached(*csr, r - graph500.FIRST_UID, hops)
    return n


def _requests(facts, n, seed):
    """n requests of mixed depths 1-7 and root sets of 1-5 roots."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        roots = sorted({graph500.FIRST_UID + int(r) for r in rng.integers(
            0, facts["roots"], int(rng.integers(1, 6)))})
        out.append((roots, 1 + (i + int(rng.integers(0, 7))) % 7))
    return out


def _traverse(badj, riders):
    """bitgraph.traverse on the host -> (counts, levels, reached)."""
    tally, reached = bitgraph.traverse(badj, riders)
    counts, levels, _ = np.asarray(tally)
    return counts, levels, np.asarray(reached)


def _tile(dev):
    """The forward adjacency tile of a device-tier db, built by a
    query if it is not there."""
    _data(dev, _q(KHOP, [graph500.FIRST_UID], 3))
    return dev.tablets["link"]._device_badj


# -- the program: lanes ------------------------------------------------


@pytest.mark.parametrize("riders", (1, 3, bitgraph.LANES))
@pytest.mark.parametrize("graph", GRAPHS, ids=lambda g: f"scale{g[0]}")
def test_a_lane_answers_what_it_answers_alone(worlds, graph, riders):
    facts, dev, host, csr = worlds[graph]
    badj = _tile(dev)
    reqs = _requests(facts, riders, graph[0] * 10 + riders)
    lanes = [(bitgraph.seed_slots(badj, np.array(r, np.uint32)), d - 1)
             for r, d in reqs]
    counts, levels, reached = _traverse(badj, lanes)
    for i, (roots, depth) in enumerate(reqs):
        alone = _traverse(badj, [lanes[i]])
        want = _plain_reached(csr, roots, depth - 1)
        assert counts[i] == alone[0][0] == int(want.sum()), (roots, depth)
        assert levels[i] == alone[1][0] <= depth - 1
        uids = bitgraph.lane_uids(badj, reached, i)
        assert np.array_equal(uids, bitgraph.lane_uids(badj, alone[2], 0))
        assert uids.tolist() == (np.flatnonzero(want)
                                 + graph500.FIRST_UID).tolist()
        assert json.loads(_data(host, _q(KHOP, roots, depth))) \
            == {"khop": [{"count": int(counts[i])}]}
    # a lane nobody rides reaches nothing and runs no level
    assert not counts[riders:].any() and not levels[riders:].any()
    assert not (reached >> np.uint32(riders)).any()


def test_a_lane_stops_at_its_own_depth_beside_a_deeper_one(worlds):
    facts, dev, _, csr = worlds[10, 7]
    badj = _tile(dev)
    root = graph500.FIRST_UID + 3
    slots = bitgraph.seed_slots(badj, np.array([root], np.uint32))
    counts, levels, _ = _traverse(
        badj, [(slots, k) for k in (1, 2, 3, 6, 0)])
    want = [int(_plain_reached(csr, [root], k).sum()) for k in (1, 2, 3, 6)]
    assert counts[:5].tolist() == want + [0]
    assert want[0] < want[1] < want[2] <= want[3]
    assert levels[:5].tolist()[:3] == [1, 2, 3] and levels[4] == 0
    # the same root twice is two lanes, each exact
    twice = _traverse(badj, [(slots, 3), (slots, 3)])[0]
    assert twice[0] == twice[1] == want[2]


def test_the_loop_ends_when_every_lane_is_dead(worlds):
    facts, dev, _, _ = worlds[8, 2**31 + 5]
    badj = _tile(dev)
    # a vertex that only has in-edges: its walk finds nothing
    sink = bitgraph.seed_slots(badj, np.array(
        [graph500.FIRST_UID + facts["vertices"] - 1], np.uint32))
    counts, levels, _ = _traverse(badj, [(sink, 50), (sink, 7)])
    assert counts[:2].tolist() == [0, 0] and levels[:2].tolist() == [1, 1]
    # and a live one runs only until no lane finds a new vertex
    src = bitgraph.seed_slots(badj, np.array([graph500.FIRST_UID], np.uint32))
    _, levels, _ = _traverse(badj, [(src, 2**31 - 1), (sink, 3)])
    assert 1 < levels[0] < 64 and levels[1] == 1


def test_one_compiled_traversal_an_adjacency_for_every_batch_size(worlds):
    facts, dev, _, _ = worlds[9, 31_000_017]
    badj = _tile(dev)
    slots = [bitgraph.seed_slots(badj, np.array(
        [graph500.FIRST_UID + i], np.uint32)) for i in range(bitgraph.LANES)]
    programs = bitgraph.bfs_traverse._cache_size()
    for n in range(1, bitgraph.LANES + 1):
        bitgraph.traverse(badj, [(slots[i], 2 + i) for i in range(n)])
    assert bitgraph.bfs_traverse._cache_size() == programs


def _frontier(badj, live, seed, share=0.05):
    """Lane words over every slot, the lanes of `live` alone, and
    the word of the lanes that hold any."""
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    words = (rng.integers(0, 1 << bitgraph.LANES, badj.n_slots)
             * (rng.random(badj.n_slots) < share)).astype(np.uint32)
    frontier = jnp.asarray(words & np.uint32(live))
    return frontier, jnp.bitwise_or.reduce(frontier)


def _hub_level(badj, frontier, active, reached, tile, dense=None):
    """One level's hub rows by both forms -> (the plain form's lane
    words, the kernel's in interpret mode, the tiles needed)."""
    import jax.numpy as jnp
    dense = badj.dense if dense is None else dense
    rows, width = dense.shape
    lanes = bitgraph.LANES
    pending = bitgraph._hub_pending(
        jnp.asarray(reached), active, badj.n_covered - rows, rows)
    want, tiles = bitgraph._hub_reach(
        dense, frontier, active, pending, lanes, tile)
    step = bitgraph._hub_tile(rows, tile)
    needed = bitgraph._tiles_needed(pending, step)[0]
    assert np.asarray(tiles).tolist() == [max(int(needed.sum()), 1),
                                          len(needed)]
    got = jnp.bitwise_or.reduce(bitgraph._hub_call(
        dense, bitgraph._frontier_words(frontier, width, lanes), active,
        bitgraph._hub_plan(needed), lanes, step, interpret=True), axis=1)
    return np.asarray(want), np.asarray(got), np.asarray(needed)


def test_the_hub_kernel_reads_what_the_plain_form_reads(worlds):
    """_hub_kernel (the chip's path, here in interpret mode) against
    the jnp form the CPU runs, on a frontier of mixed lanes, some of
    them dead, nothing reached yet: every tile is read."""
    facts, dev, _, _ = worlds[10, 7]
    badj = _tile(dev)
    assert badj.dense is not None and badj.dense.shape[1] % 128 == 0
    nothing = np.zeros(badj.n_slots, np.uint32)
    for live in (0b1, 0b10100101, (1 << bitgraph.LANES) - 1):
        frontier, active = _frontier(badj, live, 5)
        want, got, needed = _hub_level(
            badj, frontier, active, nothing, bitgraph._HUB_TILE_ROWS)
        assert np.array_equal(got, want) and want.any() and needed.all()
        assert not (want & ~np.uint32(live)).any()


# -- the program: the rows no live lane needs are not read ---------------

TILE = 8       # rows a tile in these tests: a graph here has few hundred


def _mid_traversal(badj, csr, facts, seed):
    """(frontier, active, visited, reached) as a call of mixed lanes
    holds them three or four levels in: lane b from its own root, plain BFS."""
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    offsets, dst, vertices = csr
    frontier = np.zeros(badj.n_slots, np.uint32)
    visited, reached = frontier.copy(), frontier.copy()

    def slots(mask):
        return bitgraph.seed_slots(badj, (
            np.flatnonzero(mask) + graph500.FIRST_UID).astype(np.uint32))

    for b in range(bitgraph.LANES):
        root = int(rng.integers(0, facts["roots"]))
        seen = np.zeros(vertices, bool)
        seen[root] = True
        front, hit = seen.copy(), np.zeros(vertices, bool)
        for _ in range(3 + b % 2):
            nxt = np.zeros(vertices, bool)
            for v in np.flatnonzero(front):
                nxt[dst[offsets[v]:offsets[v + 1]]] = True
            hit |= nxt
            front = nxt & ~seen
            seen |= nxt
        for words, mask in ((frontier, front), (visited, seen),
                            (reached, hit)):
            words[slots(mask)] |= np.uint32(1 << b)
    frontier = jnp.asarray(frontier)
    return frontier, jnp.bitwise_or.reduce(frontier), visited, reached


@pytest.mark.parametrize("graph", GRAPHS, ids=lambda g: f"scale{g[0]}")
def test_the_rows_of_a_settled_tile_are_never_read(worlds, graph):
    """The poison test: some levels into a call some tiles are settled
    for every live lane; with ALL-ONES written over their rows both
    forms answer what they answer over the true rows, 0 in those
    tiles, and the level's `new` and `reached` come out as they do
    from a stream of every row."""
    import jax.numpy as jnp
    facts, dev, _, csr = worlds[graph]
    badj = _tile(dev)
    rows = badj.dense.shape[0]
    start = badj.n_covered - rows
    frontier, active, visited, reached = _mid_traversal(
        badj, csr, facts, graph[0])
    want, got, needed = _hub_level(badj, frontier, active, reached, TILE)
    assert needed.any() and not needed.all()
    row_needed = np.repeat(needed, TILE)[:rows]
    poisoned = jnp.asarray(np.where(
        row_needed[:, None], np.asarray(badj.dense), np.uint32(0xFFFFFFFF)))
    want_p, got_p, _ = _hub_level(badj, frontier, active, reached, TILE,
                                  dense=poisoned)
    assert np.array_equal(want, got)
    assert np.array_equal(want_p, want) and np.array_equal(got_p, want)
    assert want.any() and not want[~row_needed].any()
    # against a stream of every row: nothing reached yet, so that
    # every tile is needed
    full, _, all_needed = _hub_level(
        badj, frontier, active, np.zeros_like(reached), TILE)
    assert all_needed.all() and full[~row_needed].any()
    hub = slice(start, start + rows)
    assert np.array_equal(full & ~visited[hub], want & ~visited[hub])
    assert np.array_equal(full | reached[hub], want | reached[hub])


def test_a_level_with_every_tile_settled_reads_nothing(worlds):
    """Every hub row reached by every live lane: the plan names one
    block for every step and marks none, and both forms answer 0
    whatever the rows hold."""
    import jax.numpy as jnp
    facts, dev, _, _ = worlds[9, 31_000_017]
    badj = _tile(dev)
    frontier, active = _frontier(badj, 0b00110101, 3, share=0.5)
    everything = np.full(badj.n_slots, (1 << bitgraph.LANES) - 1, np.uint32)
    ones = jnp.full(badj.dense.shape, 0xFFFFFFFF, jnp.uint32)
    want, got, needed = _hub_level(badj, frontier, active, everything,
                                   TILE, dense=ones)
    assert not needed.any() and not want.any() and not got.any()
    plan = np.asarray(bitgraph._hub_plan(jnp.asarray(needed)))
    assert not plan[:len(needed)].any() and not (plan & 1).any()
    # a lane that holds no frontier holds no tile back either: rows
    # that only IT has not reached are settled
    but_lane_1 = everything & ~np.uint32(0b10)
    assert not _hub_level(badj, frontier, active, but_lane_1, TILE)[2].any()
    assert _hub_level(badj, frontier, active,
                      everything & ~np.uint32(0b100), TILE)[2].all()


def test_the_plan_runs_the_needed_tiles_first_and_copies_only_them():
    import jax.numpy as jnp
    needed = np.array([0, 0, 1, 0, 1, 1, 0, 0, 1, 0], bool)
    plan = np.asarray(bitgraph._hub_plan(jnp.asarray(needed)))
    rows, writes = plan[:10], plan[10:]
    # the four needed tiles in one run, then the six others; every
    # tile's words are written by one step
    assert (writes >> 1).tolist() == [2, 4, 5, 8, 0, 1, 3, 6, 7, 9]
    assert (writes & 1).tolist() == [1] * 4 + [0] * 6
    assert rows.tolist() == [2, 4, 5, 8] + [8] * 6
    # a copy is issued when a step's block differs from the step
    # before's: once a needed tile, the first step's included
    for flags in (needed, ~needed, np.ones(7, bool), np.zeros(7, bool),
                  np.array([1], bool), np.array([0], bool)):
        tiles = len(flags)
        plan = np.asarray(bitgraph._hub_plan(jnp.asarray(flags)))
        rows, writes = plan[:tiles], plan[tiles:]
        step_needed = (writes & 1).astype(bool)
        assert sorted(writes >> 1) == list(range(tiles))
        assert flags[writes >> 1].tolist() == step_needed.tolist()
        assert (rows[step_needed] == (writes >> 1)[step_needed]).all()
        assert 1 + int((rows[1:] != rows[:-1]).sum()) \
            == max(int(flags.sum()), 1)
        assert ((0 <= rows) & (rows < tiles)).all()


def _fresh(fn, **kw):
    """`fn` (a jitted traversal) traced anew, so that what a test has
    put in bitgraph's place is what runs."""
    import functools
    import jax
    return jax.jit(functools.partial(fn.__wrapped__, **kw))


def _lanes_of(badj, facts, seed):
    """Eight riders, `khop3` and `khop6` as the cell's mix sends them
    and depths 1, 2 and 7 beside them."""
    rng = np.random.default_rng(seed)
    depths = [3, 6, 6, 3, 1, 7, 2, 6]
    return [(bitgraph.seed_slots(badj, np.array(
        [graph500.FIRST_UID + int(rng.integers(0, facts["roots"]))],
        np.uint32)), d) for d in depths]


@pytest.mark.parametrize("form", ("plain", "kernel"))
@pytest.mark.parametrize("graph", GRAPHS, ids=lambda g: f"scale{g[0]}")
def test_a_call_answers_the_same_with_and_without_the_settled_tiles(
        worlds, graph, form, monkeypatch):
    """(counts, levels, reached) of a full call, bit for bit: tiles
    of 8 rows with the settled ones left out, by the plain form and
    by the kernel (interpret mode) inside the whole traversal,
    against a stream of every row at every level."""
    import functools
    import jax
    import jax.numpy as jnp
    facts, dev, _, csr = worlds[graph]
    badj = _tile(dev)
    riders = _lanes_of(badj, facts, graph[0])
    args = ([b.in_nb for b in badj.gathered], badj.dense,
            bitgraph._pack_riders(badj.n_slots, riders))
    kw = dict(n_slots=badj.n_slots, n_covered=badj.n_covered,
              lanes=bitgraph.LANES, tile=TILE)
    traced = []
    if form == "kernel":
        kernel = functools.partial(bitgraph._hub_call, interpret=True)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(bitgraph, "_hub_call", lambda *a: (
            traced.append(a[-1]), kernel(*a))[1])
    tally, reached = (np.asarray(x) for x in
                      _fresh(bitgraph.bfs_traverse, **kw)(*args))
    assert traced == [TILE] * (form == "kernel")
    monkeypatch.undo()
    monkeypatch.setattr(
        bitgraph, "_tiles_needed", lambda pending, tile: jnp.ones(
            (pending.shape[0], -(-pending.shape[1] // tile)), bool))
    tally_all, reached_all = (np.asarray(x) for x in
                              _fresh(bitgraph.bfs_traverse, **kw)(*args))
    assert np.array_equal(tally[:2], tally_all[:2])
    assert np.array_equal(reached, reached_all)
    # the deep lanes ran past the level at which hubs are all found
    assert tally[1].max() >= 4
    assert tally_all[2, 0] == tally_all[2, 1] == tally[2, 1] \
        == tally[1].max() * -(-badj.dense.shape[0] // TILE)
    assert 0 < tally[2, 0] < tally[2, 1]
    for i, (slots, depth) in enumerate(riders):
        root = int(badj.slot_uids[slots[0]])
        assert tally[0, i] == int(_plain_reached(csr, [root], depth).sum())


# -- the first level from the roots' columns ---------------------------
#
# The out-neighbours of a root are its COLUMN of the reverse
# structures the device holds: a call's first level read that way
# (bitgraph._chip_columns) is held, bit for bit, to the level that
# gathers and streams as every other does.


LAYOUTS = {"all_hub_rows": None, "some_hub_rows": 40, "no_hub_rows": 0}
# the file's three Graph500 graphs, and one whose hub rows are three
# blocks of 128 word columns wide
COLUMN_GRAPHS = [f"scale{g[0]}" for g in GRAPHS] + ["wide"]


def _wide_edges(n=9_000, m=40_000, seed=11):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, m)
    dst = rng.zipf(1.4, m) % n
    keep = src != dst
    return src[keep], dst[keep]


@pytest.fixture(scope="module")
def column_worlds():
    """{(graph, layout): (edges uid -> out-neighbour uids, the plain
    walk's csr, adjacency)}: a graph's adjacency with as many hub rows
    as the layout's room holds (None: every class, 0: none)."""
    out = {}
    for name, graph in zip(COLUMN_GRAPHS, GRAPHS + [None]):
        src, dst = _wide_edges() if graph is None \
            else graph500.graph(*graph)[:2]
        order = np.lexsort((dst, src))
        src, dst = src[order], dst[order]
        vertices = int(max(src.max(), dst.max())) + 1
        offsets = np.zeros(vertices + 1, np.int64)
        np.cumsum(np.bincount(src, minlength=vertices), out=offsets[1:])
        heads, starts = np.unique(src, return_index=True)
        edges = {int(u) + graph500.FIRST_UID:
                 np.unique(d + graph500.FIRST_UID).astype(np.uint32)
                 for u, d in zip(heads, np.split(dst, starts[1:]))}
        for layout, rows in LAYOUTS.items():
            badj = bitgraph.build_bitadjacency(edges)
            bitgraph.attach_dense(
                badj, 1 << 40 if rows is None
                else rows * 4 * bitgraph.hub_row_words(badj.n_slots))
            assert (badj.dense is None) == (rows == 0)
            assert bool(badj.gathered) == (rows is not None)
            out[name, layout] = edges, (offsets, dst, vertices), badj
    assert out["wide", "all_hub_rows"][2].dense.shape[1] == 3 * 128
    return out


def _three_forms(badj, riders, **kw):
    """(tally, reached) of the riders with the first level streamed,
    read from columns, and as the rule has it."""
    return [tuple(np.asarray(x) for x in traverse_as(
        badj, riders, c, **kw)) for c in (False, True, None)]


@pytest.mark.parametrize("case", (
    "padding_seeds", "eight_seeds_no_padding", "one_root_in_two_lanes",
    "a_lane_of_depth_0", "every_lane_of_depth_0",
    "a_root_with_no_out_edge", "a_root_that_is_a_hub",
    "several_roots_a_lane"))
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("graph", COLUMN_GRAPHS)
def test_the_column_first_level_is_the_streamed_one_bit_for_bit(
        column_worlds, graph, layout, case):
    edges, csr, badj = column_worlds[graph, layout]
    lanes = column_lanes(case, edges)
    riders = [(bitgraph.seed_slots(badj, np.array(roots, np.uint32)), d)
              for roots, d in lanes]
    (streamed, reached), (columns, reached_c), (ruled, reached_r) = \
        _three_forms(badj, riders, tile=TILE)
    assert streamed.dtype == columns.dtype
    assert np.array_equal(streamed[:2], columns[:2])
    assert np.array_equal(reached, reached_c)
    assert np.array_equal(streamed[:2], ruled[:2])
    assert np.array_equal(reached, reached_r)
    # and both are the plain walk
    for i, (roots, depth) in enumerate(lanes):
        want = graph500.FIRST_UID + np.flatnonzero(
            _plain_reached(csr, roots, depth))
        assert columns[0, i] == len(want), (roots, depth)
        assert np.array_equal(bitgraph.lane_uids(badj, reached_c, i), want)
    # the tally's third row: a level from columns streams no tile and
    # still counts among those a stream of every row would have read
    ran = int(streamed[1].max())
    assert streamed[2, 2] == 0 and columns[2, 2] == (ran > 0)
    assert not streamed[2, 3:].any() and not columns[2, 3:].any()
    assert columns[2, 1] == streamed[2, 1]
    if badj.dense is not None and ran:
        tiles = -(-badj.dense.shape[0] // TILE)
        assert streamed[2, 1] == ran * tiles
        assert columns[2, 0] <= streamed[2, 0] - tiles
    else:
        assert not streamed[2, :2].any() and not columns[2, :2].any()
    assert ruled[2, 2] == (ran > 0) * bitgraph.columns_cheaper(
        8, *((0, 0) if badj.dense is None else badj.dense.shape),
        sum(int(b.in_nb.size) for b in badj.gathered))


@pytest.mark.parametrize("graph", COLUMN_GRAPHS)
def test_the_column_kernel_reads_what_the_plain_form_reads(
        column_worlds, graph, monkeypatch):
    """_column_kernel (interpret mode) against the plain slices over
    the same rows, seeds in every block of columns a row has, the
    dummy slot and a seed in two lanes among them; then inside the
    whole traversal, where the chip's branch takes it."""
    import functools
    import jax
    import jax.numpy as jnp
    edges, _, badj = column_worlds[graph, "all_hub_rows"]
    rng = np.random.default_rng(5)
    slots = np.r_[rng.integers(0, badj.n_slots, 13), badj.n_slots,
                  badj.n_slots - 1, 0].astype(np.int32)
    slots[1] = slots[0]
    bits = (np.uint32(1) << (np.arange(16) % 8).astype(np.uint32))
    bits[13] = 0
    args = (badj.dense, jnp.asarray(slots), jnp.asarray(bits))
    plain = np.asarray(bitgraph._hub_columns(*args))
    assert plain.any()
    traced = []
    kernel = functools.partial(bitgraph._columns_call, interpret=True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(bitgraph, "_columns_call", lambda *a: (
        traced.append(a[1].shape), kernel(*a))[1])
    monkeypatch.setattr(bitgraph, "_hub_call", functools.partial(
        bitgraph._hub_call, interpret=True))
    assert np.array_equal(np.asarray(bitgraph._hub_columns(*args)), plain)
    lanes = column_lanes("a_root_that_is_a_hub", edges) \
        + column_lanes("padding_seeds", edges)
    riders = [(bitgraph.seed_slots(badj, np.array(roots, np.uint32)), d)
              for roots, d in lanes]
    packed = bitgraph._pack_riders(badj.n_slots, riders)
    kw = dict(n_slots=badj.n_slots, n_covered=badj.n_covered,
              lanes=bitgraph.LANES, tile=TILE)
    tally, reached = (np.asarray(x) for x in _fresh(
        bitgraph.bfs_traverse, columns=True, **kw)([], badj.dense, packed))
    assert traced == [(16,), (8,)] and tally[2, 2] == 1
    monkeypatch.undo()
    want, want_reached = (np.asarray(x) for x in traverse_as(
        badj, riders, False, tile=TILE))
    assert np.array_equal(tally[:2], want[:2])
    assert np.array_equal(reached, want_reached)


@pytest.mark.parametrize("graph", COLUMN_GRAPHS)
def test_more_roots_than_the_rule_allows_take_the_streamed_level(
        column_worlds, graph):
    """From shapes alone: eight seed slots read their columns, the
    count of them at which the rule turns streams as before."""
    edges, _, badj = column_worlds[graph, "some_hub_rows"]
    shapes = (*badj.dense.shape,
              sum(int(b.in_nb.size) for b in badj.gathered))
    turn = next(s for s in (16, 32, 64, 128, 256, 512, 1024)
                if not bitgraph.columns_cheaper(s, *shapes))
    assert bitgraph.columns_cheaper(8, *shapes)
    heads = sorted(edges)
    for seeds, want in ((8, 1), (turn // 2, 1), (turn, 0)):
        # `seeds` (root, lane) pairs over the eight lanes
        a_lane = seeds // bitgraph.LANES
        assert a_lane <= len(heads)
        riders = [(bitgraph.seed_slots(badj, np.array(
            heads[i:i + a_lane], np.uint32)), 2 + i % 3)
            for i in range(bitgraph.LANES)]
        (streamed, reached), _, (ruled, reached_r) = _three_forms(
            badj, riders)
        assert ruled[2, 2] == want
        assert np.array_equal(streamed[:2], ruled[:2])
        assert np.array_equal(reached, reached_r)


# the two cells' own shapes, a chip's (PERF.md section 4): hub rows,
# their width in words, padded in-edges gathered, and the first count
# of seed slots (a power of two from eight up) the stream is PRICED
# cheaper at
CELL_SHAPES = {
    "graph500-khop": (69_700, 5_504, 151_000, 64),
    "graph500-khop-x4": (20_240, 20_224, 697_900, 512),
}


@pytest.mark.parametrize("cell", sorted(CELL_SHAPES))
def test_the_turn_rule_at_the_cells_shapes(cell, monkeypatch):
    """Eight riders of one root each read their columns in both
    cells; where the rule turns back to the stream moves with
    GATHER_SECONDS and DENSE_BYTES_PER_S, and this says to where."""
    rows, words, gathered, turn = CELL_SHAPES[cell]
    assert words == bitgraph.hub_row_words(32 * words)
    counts = (8, 16, 32, 64, 128, 256, 512, 1024)
    cheaper = [s for s in counts
               if bitgraph.columns_cheaper(s, rows, words, gathered)]
    # the rule takes the columns where they are priced cheaper AND
    # were timed on the chip: up to 32 seed slots in both cells
    timed = bitgraph._COLUMN_SEEDS_TIMED
    assert timed == 32
    assert cheaper == [s for s in counts if s < turn and s <= timed] \
        == [8, 16, 32]
    # by the prices alone it would turn at the cell's own count
    monkeypatch.setattr(bitgraph, "_COLUMN_SEEDS_TIMED", 1 << 20)
    assert [s for s in counts if bitgraph.columns_cheaper(
        s, rows, words, gathered)] == [s for s in counts if s < turn]
    # eight columns cost a sixth of the level they replace, or less
    eight = 8 * 4 * (rows * 128 + gathered) / bitgraph.DENSE_BYTES_PER_S
    level = gathered * bitgraph.GATHER_SECONDS \
        + 4 * rows * words / bitgraph.DENSE_BYTES_PER_S
    assert 6 * eight < level
    # no rows and nothing gathered: nothing to read either way
    assert not bitgraph.columns_cheaper(8, 0, 0, 0)


def test_level_seconds_is_still_the_streamed_levels_price(column_worlds):
    """The gate's price of a level is what it was: the column level
    is under it, and no term of it moved."""
    badj = column_worlds["wide", "some_hub_rows"][2]
    gathered = sum(int(b.in_nb.size) for b in badj.gathered)
    assert bitgraph.level_seconds(badj) == pytest.approx(
        gathered * bitgraph.GATHER_SECONDS
        + badj.dense.nbytes / bitgraph.DENSE_BYTES_PER_S)


# -- the rendezvous, alone ---------------------------------------------


class _Chip:
    """A device that runs its calls in the order they were launched,
    as slowly as told: `gate` holds every call, `hold(i)` call `i`
    until `release(i)`."""

    def __init__(self):
        self.calls = []
        self.launched_by = []   # thread ids, a call each
        self.landed = []        # handles, in the order `land` returned
        self.on_device = self.worst = 0
        self.lock = threading.Lock()
        self.gate = threading.Event()
        self.gate.set()
        self.held = {}
        self.fail_launch = self.fail_land = None

    def hold(self, i):
        self.held[i] = threading.Event()

    def release(self, i):
        self.held[i].set()

    def launch(self, items):
        if self.fail_launch and len(self.calls) == self.fail_launch[0]:
            self.calls.append(None)
            self.launched_by.append(threading.get_ident())
            raise self.fail_launch[1]
        with self.lock:
            self.calls.append(list(items))
            self.launched_by.append(threading.get_ident())
            self.on_device += 1
            self.worst = max(self.worst, self.on_device)
            return len(self.calls) - 1

    def land(self, handle, n):
        self.gate.wait(30)
        if handle in self.held:
            self.held[handle].wait(30)
        with self.lock:
            self.on_device -= 1
            self.landed.append(handle)
        if self.fail_land and handle == self.fail_land[0]:
            raise self.fail_land[1]
        return [("answer", x) for x in self.calls[handle]]


def _ride_all(meet, chip, items, ctxs=None, stagger=None, out=None):
    """Every item from a thread of its own -> {item: Ride or error}
    (`out`, where the caller keeps one for several sets)."""
    out = {} if out is None else out

    def one(x):
        try:
            out[x] = meet.ride(x, chip.launch, chip.land,
                               (ctxs or {}).get(x))
        except BaseException as e:
            out[x] = e

    threads = [threading.Thread(target=one, args=(x,)) for x in items]
    for t in threads:
        t.start()
        if stagger:
            stagger(t)
    return threads, out


def _until(what, seconds=10):
    """Poll until `what()` holds, or give up after `seconds`."""
    deadline = time.monotonic() + seconds
    while not what() and time.monotonic() < deadline:
        time.sleep(0.001)


def _standing(meet, n):
    _until(lambda: len(meet._waiting) == n)
    assert len(meet._waiting) == n


def _launched(chip, n):
    _until(lambda: len(chip.calls) >= n)
    assert len(chip.calls) == n


def _lead(meet, chip):
    """One caller that finds the chip free, its call held on it."""
    first, got = _ride_all(meet, chip, ["lead"])
    _launched(chip, 1)
    return first, got


def test_a_lone_caller_launches_at_once_and_never_waits():
    meet, chip = Rendezvous(4), _Chip()
    for x in "abc":
        ride = meet.ride(x, chip.launch, chip.land)
        assert ride.result == ("answer", x)
        assert (ride.lanes, ride.lane, ride.waited_ns) == (1, 0, 0)
    assert chip.calls == [["a"], ["b"], ["c"]]
    assert meet._flight is None and not meet._waiting


def test_waiters_ride_the_next_call_and_the_overflow_the_one_after():
    meet, chip = Rendezvous(4), _Chip()
    chip.gate.clear()
    first, got1 = _lead(meet, chip)
    rest = [f"w{i}" for i in range(6)]
    threads, got = _ride_all(meet, chip, rest)
    # capacity 4: four of the six are a full call, on the device behind
    # the lead's already; the two others stand until a call lands
    _launched(chip, 2)
    _standing(meet, 2)
    chip.gate.set()
    for t in first + threads:
        t.join(30)
    # two calls, oldest first, and nobody is lost or answered from
    # another's result
    assert [len(c) for c in chip.calls] == [1, 4, 2]
    assert sorted(chip.calls[1] + chip.calls[2]) == rest
    for x in rest:
        assert got[x].result == ("answer", x)
        assert got[x].lanes == (4 if x in chip.calls[1] else 2)
    assert sum(got[x].waited_ns > 0 for x in rest) >= 5
    assert got1["lead"].lanes == 1
    assert meet._flight is None and meet._behind is None
    assert not meet._waiting


# -- a full call does not wait for the chip (PR 40) -----------------------


def test_a_full_call_goes_behind_the_call_in_flight_at_once():
    """Launched by the rider whose arrival filled it, while the call
    ahead is still on the chip and before anything lands."""
    meet, chip = Rendezvous(4), _Chip()
    chip.gate.clear()
    first, got1 = _lead(meet, chip)
    early, got = _ride_all(meet, chip, ["a", "b", "c"])
    _standing(meet, 3)
    time.sleep(0.02)
    assert len(chip.calls) == 1         # three of four: they stand
    last, _ = _ride_all(meet, chip, ["d"], out=got)
    _launched(chip, 2)
    assert chip.landed == [] and first[0].is_alive()
    assert chip.launched_by[1] == last[0].ident
    assert meet._flight.riders[0].item == "lead"
    assert sorted(r.item for r in meet._behind.riders) == list("abcd")
    assert meet._behind.ahead and not meet._flight.ahead
    assert not meet._waiting
    chip.gate.set()
    for t in first + early + last:
        t.join(30)
        assert not t.is_alive()
    assert chip.calls[1][:3] == sorted(chip.calls[1][:3])   # lane order
    for x in "abcd":
        assert got[x].result == ("answer", x)
        assert got[x].lanes == 4
        assert got[x].lane == chip.calls[1].index(x)
    # the one that filled the call never stood; the others stood from
    # joining to ITS launch, not to the lead's landing
    assert got["d"].waited_ns == 0
    assert all(0.02e9 * 0.9 <= got[x].waited_ns for x in "abc")
    assert got1["lead"].result == ("answer", "lead")
    assert meet._flight is None and meet._behind is None


def test_at_most_two_calls_are_on_the_device_and_they_land_in_order():
    meet, chip = Rendezvous(2), _Chip()
    for i in range(4):
        chip.hold(i)
    first, got = _lead(meet, chip)
    threads = []
    for pair, stand in (("ab", 0), ("cd", 2), ("ef", 4)):
        threads += _ride_all(meet, chip, list(pair), out=got)[0]
        _standing(meet, stand)
    # one call behind the one in flight and no more: two full sets
    # stand, launched by nobody
    time.sleep(0.02)
    assert [len(c) for c in chip.calls] == [1, 2]
    for i in range(4):
        _launched(chip, min(i + 2, 4))
        assert chip.on_device <= 2
        chip.release(i)
        _until(lambda: len(chip.landed) > i)
    for t in first + threads:
        t.join(30)
        assert not t.is_alive()
    assert chip.landed == [0, 1, 2, 3] and chip.worst == 2
    assert sorted(map(sorted, chip.calls[1:])) \
        == [["a", "b"], ["c", "d"], ["e", "f"]]
    for x in "abcdef":
        assert got[x].result == ("answer", x) and got[x].lanes == 2
    assert meet._flight is None and meet._behind is None


def test_fewer_than_a_full_call_waits_for_the_landing():
    """Seven of eight behind a call in flight: the parent's path, call
    for call: launched by the thread that lands the call ahead."""
    meet, chip = Rendezvous(8, family="t"), _Chip()
    chip.gate.clear()
    metrics.reset()
    tracing.clear()
    first, _ = _lead(meet, chip)
    rest = [f"w{i}" for i in range(7)]
    threads, got = _ride_all(meet, chip, rest)
    _standing(meet, 7)
    time.sleep(0.05)
    assert len(chip.calls) == 1 and meet._behind is None
    chip.gate.set()
    for t in first + threads:
        t.join(30)
        assert not t.is_alive()
    assert [len(c) for c in chip.calls] == [1, 7]
    assert chip.launched_by[1] == first[0].ident
    assert all(got[x].result == ("answer", x) for x in rest)
    lead, second = (f["args"] for f in _flights())
    assert (lead["ahead"], second["ahead"]) == (False, False)
    assert lead["turnround_us"] >= lead["launch_us"] >= 0
    assert "turnround_us" not in second
    counters = metrics.snapshot()["counters"]
    assert counters['rendezvous_chained_total{family="t"}'] == 1
    # the series is served, at 0: nothing went ahead
    assert counters['rendezvous_ahead_total{family="t"}'] == 0


def test_the_third_set_goes_behind_the_second_at_the_first_landing():
    """More than 2 x capacity callers: when the queued call moves up,
    the landing thread puts a full set of waiters behind it."""
    meet, chip = Rendezvous(2, family="t"), _Chip()
    for i in range(3):
        chip.hold(i)
    metrics.reset()
    tracing.clear()
    first, got = _lead(meet, chip)
    second, _ = _ride_all(meet, chip, ["a", "b"], out=got)
    _launched(chip, 2)
    third, _ = _ride_all(meet, chip, ["c", "d"], out=got)
    _standing(meet, 2)
    chip.release(0)
    _launched(chip, 3)
    first[0].join(30)
    # launched by the lead's thread as it landed, behind ("a", "b"),
    # which is still on the chip
    assert chip.launched_by[2] == first[0].ident
    assert chip.landed == [0] and sorted(chip.calls[2]) == ["c", "d"]
    assert sorted(r.item for r in meet._flight.riders) == ["a", "b"]
    assert sorted(r.item for r in meet._behind.riders) == ["c", "d"]
    chip.release(1)
    # (two landers let go at once may reach the rendezvous out of
    # order, and the call that lands second then had no successor)
    _until(lambda: meet._behind is None)
    chip.release(2)
    for t in second + third:
        t.join(30)
        assert not t.is_alive()
    for x in "abcd":
        assert got[x].result == ("answer", x) and got[x].lanes == 2
    flights = {f["args"]["lanes"]: f["args"] for f in _flights()[:1]}
    # the lead's landing found its successor launched: a turn-round of
    # nothing, though it launched a call (not its successor) itself
    assert flights[1]["turnround_us"] == 0 and "launch_us" in flights[1]
    counters = metrics.snapshot()["counters"]
    assert counters['rendezvous_ahead_total{family="t"}'] == 2
    assert counters['rendezvous_chained_total{family="t"}'] == 2
    assert counters[
        'rendezvous_ns_total{family="t",phase="turnround"}'] == 0


def test_the_next_call_is_on_the_chip_before_results_are_handed_out():
    """The thread that took a call's result launches the waiters'
    call first: the chip does not wait for a thread to wake."""
    meet, chip = Rendezvous(8), _Chip()
    chip.gate.clear()
    order = []
    launch0 = chip.launch

    def launch(items):
        order.append(("launch", tuple(items)))
        return launch0(items)

    chip.launch = launch
    first, got1 = _ride_all(meet, chip, ["lead"])
    while not chip.calls:
        time.sleep(0.001)
    threads, got = _ride_all(meet, chip, ["a", "b"])
    _standing(meet, 2)
    lead_thread = first[0]
    chip.gate.set()
    lead_thread.join(30)
    order.append("lead returned")
    for t in threads:
        t.join(30)
    assert order[0] == ("launch", ("lead",))
    assert order[1][0] == "launch" and sorted(order[1][1]) == ["a", "b"]
    assert order[2] == "lead returned"
    assert {x: got[x].result for x in "ab"} \
        == {"a": ("answer", "a"), "b": ("answer", "b")}


@pytest.mark.parametrize("where", ("launch", "land"))
def test_a_call_that_raises_fails_its_riders_and_frees_the_chip(where):
    """Fewer than a full call behind the lead: the landing thread
    launches them, and the call that fails is theirs alone."""
    meet, chip = Rendezvous(4), _Chip()
    chip.gate.clear()
    boom = RuntimeError("the device said no")
    setattr(chip, "fail_" + where, (1, boom))
    first, got1 = _lead(meet, chip)
    threads, got = _ride_all(meet, chip, ["a", "b", "c"])
    _standing(meet, 3)
    chip.gate.set()
    for t in first + threads:
        t.join(30)
        assert not t.is_alive()
    assert got1["lead"].result == ("answer", "lead")
    assert all(got[x] is boom for x in "abc")
    assert meet._flight is None and not meet._waiting
    # and the rendezvous serves on
    assert meet.ride("z", chip.launch, chip.land).result == ("answer", "z")


@pytest.mark.parametrize("where", (
    "launch-behind", "land-behind", "land-ahead"))
def test_of_two_calls_on_the_device_the_one_that_raises_fails_alone(where):
    meet, chip = Rendezvous(2), _Chip()
    chip.gate.clear()
    boom = RuntimeError("the device said no")
    what, _, which = where.partition("-")
    setattr(chip, "fail_" + what, (1 if which == "behind" else 0, boom))
    first, got = _lead(meet, chip)
    pair, _ = _ride_all(meet, chip, ["a", "b"], out=got)
    _launched(chip, 2)
    if where == "launch-behind":
        # its riders have their error while the lead's call, untouched,
        # is still on the chip with its lander blocked for it
        for t in pair:
            t.join(30)
            assert not t.is_alive()
        assert first[0].is_alive() and chip.landed == []
        assert meet._flight.riders[0].item == "lead"
        assert meet._behind is None
    late, _ = _ride_all(meet, chip, ["c"], out=got)
    _standing(meet, 1)
    chip.gate.set()
    for t in first + pair + late:
        t.join(30)
        assert not t.is_alive()
    failed = ["lead"] if which == "ahead" else ["a", "b"]
    for x in ("lead", "a", "b", "c"):
        if x in failed:
            assert got[x] is boom
        else:
            assert got[x].result == ("answer", x)
    # the one that stood rode a call of its own after a landing
    assert chip.calls[2] == ["c"]
    assert meet._flight is None and meet._behind is None
    assert not meet._waiting
    assert meet.ride("z", chip.launch, chip.land).result == ("answer", "z")


def test_a_rider_past_its_deadline_leaves_alone():
    meet, chip = Rendezvous(8), _Chip()
    chip.gate.clear()
    first, _ = _lead(meet, chip)
    gone = RequestContext.background()
    threads, got = _ride_all(meet, chip, ["a", "gone", "b"], {"gone": gone})
    _standing(meet, 3)
    late, got_late = _ride_all(
        meet, chip, ["late"], {"late": RequestContext.with_timeout(0.05)})
    late[0].join(30)
    gone.cancel()
    _standing(meet, 2)          # both left while the chip was busy
    got.update(got_late)
    chip.gate.set()
    for t in first + threads:
        t.join(30)
        assert not t.is_alive()
    assert isinstance(got["late"], DeadlineExceeded)
    assert isinstance(got["gone"], Cancelled)
    assert got["a"].result == ("answer", "a") and got["a"].lanes == 2
    assert got["b"].result == ("answer", "b")
    assert sorted(chip.calls[1]) == ["a", "b"]


def test_a_rider_past_its_deadline_leaves_a_call_behind():
    """Its call is on the device's queue already: it leaves with its
    own error, its lane is computed and dropped."""
    meet, chip = Rendezvous(2), _Chip()
    chip.gate.clear()
    first, _ = _lead(meet, chip)
    gone = RequestContext.background()
    early, got = _ride_all(meet, chip, ["gone"], {"gone": gone})
    _standing(meet, 1)
    filler, _ = _ride_all(meet, chip, ["a"], out=got)
    _launched(chip, 2)          # "a" filled the call and lands it
    gone.cancel()
    early[0].join(30)
    assert not early[0].is_alive() and isinstance(got["gone"], Cancelled)
    assert chip.landed == [] and filler[0].is_alive()
    chip.gate.set()
    for t in first + filler:
        t.join(30)
        assert not t.is_alive()
    assert chip.calls[1] == ["gone", "a"]
    assert got["a"].result == ("answer", "a")
    assert (got["a"].lanes, got["a"].lane) == (2, 1)
    assert meet._flight is None and meet._behind is None


def test_many_threads_lose_no_rider_and_never_share_the_chip():
    """More threads than cores under a short switch interval: every
    ride gets its own answer out of its own call, the calls' lanes add
    up to the rides, never more than two calls are on the device (the
    one in flight and the one behind it), and full calls do go
    behind."""
    import sys
    meet = Rendezvous(8, family="stress")
    lock = threading.Lock()
    state = {"on_chip": 0, "worst": 0, "calls": 0, "lanes": 0}
    before = metrics.counters_snapshot()

    def launch(items):
        with lock:
            state["on_chip"] += 1
            state["worst"] = max(state["worst"], state["on_chip"])
            state["calls"] += 1
            state["lanes"] += len(items)
            return state["calls"], list(items)

    def land(handle, n):
        time.sleep(0.0002)
        with lock:
            state["on_chip"] -= 1
        call, items = handle
        return [(call, x * x) for x in items]

    threads_n, rides_n = 48, 40
    wrong = []
    seats: dict = {}

    def client(k):
        for j in range(rides_n):
            x = k * 1000 + j
            ride = meet.ride(x, launch, land)
            call, answer = ride.result
            if answer != x * x:
                wrong.append(x)
            with lock:
                seats.setdefault(call, []).append((ride.lane, ride.lanes))

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(threads_n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(was)
    assert not wrong
    assert state["lanes"] == threads_n * rides_n
    assert state["worst"] == 2 and state["on_chip"] == 0
    assert state["calls"] < state["lanes"]
    # every call handed each of its lanes to one rider, and to no other
    assert len(seats) == state["calls"]
    for riders in seats.values():
        assert sorted(lane for lane, _ in riders) \
            == list(range(riders[0][1]))
    moved = metrics.counters_delta(before)
    assert 0 < moved['rendezvous_ahead_total{family="stress"}'] \
        < state["calls"]
    assert meet._flight is None and meet._behind is None
    assert not meet._waiting


def test_a_tile_has_one_rendezvous_and_another_tile_another():
    class Tile:
        pass

    a, b = Tile(), Tile()
    made = []
    threads = [threading.Thread(
        target=lambda: made.append(Rendezvous.at(a, 8))) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len({id(m) for m in made}) == 1
    assert Rendezvous.at(b, 8) is not made[0]


# -- the flight, spanned by the thread that lands it (PR 39) ------------

PHASES = ("land", "board", "launch", "settle")


class _Annotation:
    """A stand-in for jax's TraceAnnotation that keeps a log."""

    log: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.log.append(("enter", self.name, threading.get_ident()))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name, threading.get_ident()))


@pytest.fixture
def annotations(monkeypatch):
    from dgraph_tpu.query import devicecall
    monkeypatch.setattr(_Annotation, "log", [])
    monkeypatch.setattr(devicecall, "trace_annotation", _Annotation)
    return _Annotation.log


def _by_lander(log):
    """The `flight.*` annotations of a log, a list a landing thread in
    the order the threads began to land."""
    threads: dict = {}
    for what, name, tid in log:
        if name.startswith("flight."):
            threads.setdefault(tid, []).append((what, name))
    return list(threads.values())


def _ride_in_blocks(meet, chip, items):
    """Every item from a thread and a `device_call` block of its own."""
    from dgraph_tpu.query.devicecall import device_call

    def one(x):
        with device_call("query_device_recurse_total") as dc:
            try:
                dc.wait_for(lambda: meet.ride(x, chip.launch, chip.land))
            except RuntimeError:
                pass

    threads = [threading.Thread(target=one, args=(x,)) for x in items]
    for t in threads:
        t.start()
    return threads


def _flights():
    return [s for s in tracing.recent_spans()
            if s["name"] == "device.flight"]


def test_two_chained_flights_are_a_span_each_with_their_phases(
        annotations):
    meet, chip = Rendezvous(4, family="t"), _Chip()
    chip.gate.clear()
    metrics.reset()
    tracing.clear()
    first = _ride_in_blocks(meet, chip, ["lead"])
    while not chip.calls:
        time.sleep(0.001)
    rest = _ride_in_blocks(meet, chip, ["a", "b"])
    _standing(meet, 2)
    chip.gate.set()
    for t in first + rest:
        t.join(30)
    flights = _flights()
    assert [f["args"]["lanes"] for f in flights] == [1, 2]
    assert all(f["args"]["family"] == "t" for f in flights)
    # the lead's flight launched the waiters' call: it has all four
    # phases and a turn-round, from land's return to launch's; the
    # second launched nothing
    lead, second = (f["args"] for f in flights)
    assert all(p + "_us" in lead for p in PHASES)
    assert lead["board_us"] + lead["launch_us"] - 2 \
        <= lead["turnround_us"] <= lead["board_us"] + lead["launch_us"] + 2
    assert "launch_us" not in second and "turnround_us" not in second
    assert (lead["left_waiting"], second["left_waiting"]) == (0, 0)
    counters = metrics.snapshot()["counters"]
    assert counters['rendezvous_chained_total{family="t"}'] == 1
    for phase, flights_with_it in (("land", 2), ("board", 2), ("settle", 2),
                                   ("launch", 1), ("turnround", 1)):
        key = f'rendezvous_ns_total{{family="t",phase="{phase}"}}'
        assert counters[key] >= 0
        assert abs(counters[key] // 1000
                   - sum(f["args"].get(phase + "_us", 0) for f in flights)
                   ) <= flights_with_it
    # a rider's span names the flight that brought its result; the
    # flight hangs under the span of the thread that landed it
    calls = [s for s in tracing.recent_spans() if s["name"] == "device.call"]
    by_lanes = {f["args"]["lanes"]: f for f in flights}
    assert sorted(c["args"]["flight"] for c in calls) == sorted(
        [by_lanes[1]["span_id"]] + 2 * [by_lanes[2]["span_id"]])
    ids = {c["span_id"]: c for c in calls}
    for f in flights:
        assert ids[f["parent_id"]]["args"]["flight"] == f["span_id"]
    # each phase is an annotation of its own, closed in order, board
    # and launch nested in the turn-round
    lead_thread, second_thread = _by_lander(annotations)
    assert lead_thread == [
        ("enter", "flight.land"), ("exit", "flight.land"),
        ("enter", "flight.turnround"),
        ("enter", "flight.board"), ("exit", "flight.board"),
        ("enter", "flight.launch"), ("exit", "flight.launch"),
        ("exit", "flight.turnround"),
        ("enter", "flight.settle"), ("exit", "flight.settle")]
    assert second_thread == [
        e for e in lead_thread if e[1] != "flight.launch"]


def test_riders_the_next_call_cannot_seat_are_left_waiting():
    meet, chip = Rendezvous(2, family="t"), _Chip()
    chip.gate.clear()
    chip.hold(1)
    tracing.clear()
    first, _ = _ride_all(meet, chip, ["lead"])
    while not chip.calls:
        time.sleep(0.001)
    threads, _ = _ride_all(meet, chip, ["a", "b", "c"])
    # two of the three are a full call, behind the lead's already
    _launched(chip, 2)
    _standing(meet, 1)
    chip.gate.set()
    # (the calls land in the device's order: the lead's first)
    first[0].join(30)
    chip.release(1)
    for t in first + threads:
        t.join(30)
    assert [(f["args"]["lanes"], f["args"]["left_waiting"])
            for f in _flights()] == [(1, 1), (2, 0), (1, 0)]


@pytest.mark.parametrize("where", ("launch", "land"))
def test_a_flight_that_raises_closes_its_span_and_annotations(
        where, annotations):
    from dgraph_tpu.query import devicecall

    meet, chip = Rendezvous(4, family="t"), _Chip()
    chip.gate.clear()
    setattr(chip, "fail_" + where, (1, RuntimeError("the device said no")))
    metrics.reset()
    tracing.clear()
    first = _ride_in_blocks(meet, chip, ["lead"])
    while not chip.calls:
        time.sleep(0.001)
    rest = _ride_in_blocks(meet, chip, ["a", "b"])
    _standing(meet, 2)
    chip.gate.set()
    for t in first + rest:
        t.join(30)
        assert not t.is_alive()
    # the lead's flight is whole whichever way the next call went; one
    # that failed at its launch never flew, one that failed at its
    # landing has a span with its phases all the same
    flights = _flights()
    assert len(flights) == (1 if where == "launch" else 2)
    assert all(p + "_us" in flights[0]["args"] for p in PHASES)
    assert all(p + "_us" in flights[-1]["args"]
               for p in ("land", "board", "settle"))
    for seen in _by_lander(annotations):
        assert sorted(n for what, n in seen if what == "enter") \
            == sorted(n for what, n in seen if what == "exit")
    assert ("exit", "flight.launch") in _by_lander(annotations)[0]
    assert metrics.snapshot()["counters"][
        'rendezvous_chained_total{family="t"}'] == 1
    assert devicecall._inflight == 0


# -- the executor's site -----------------------------------------------


def _hold_first_call(monkeypatch):
    """Keep the first traversal on the 'chip' until released, and a
    full call that went behind it meanwhile (the device runs them in
    order), so that what arrives meanwhile is known to wait."""
    gate, calls = threading.Event(), []
    land0 = executor_mod._land_traversals

    def land(handle, n):
        calls.append(n)
        gate.wait(30)
        return land0(handle, n)

    monkeypatch.setattr(executor_mod, "_land_traversals", land)
    return gate, calls


def _serve(db, queries, ctxs=None):
    """Each query from a thread of its own -> ({i: parsed reply or
    error}, threads)."""
    out = {}

    def one(i, q):
        try:
            out[i] = json.loads(db.query_json(
                q, **({"ctx": ctxs[i]} if ctxs and i in ctxs else {})))
        except BaseException as e:
            out[i] = e

    threads = [threading.Thread(target=one, args=(i, q))
               for i, q in enumerate(queries)]
    for t in threads:
        t.start()
    return out, threads


def test_a_lone_request_is_one_call_of_one_lane_and_never_waits(worlds):
    facts, dev, host, _ = worlds[10, 7]
    _tile(dev)
    q = _q(KHOP, [graph500.FIRST_UID + 9], 7)
    metrics.reset()
    tracing.clear()
    sl = json.loads(dev.query_json(q))["extensions"]["server_latency"]
    counters = metrics.snapshot()["counters"]
    assert sl["device_calls"] == 1
    assert counters["recurse_batch_total"] == 1
    assert counters["recurse_batch_lanes_total"] == 1
    assert counters["query_device_recurse_total"] == 1
    spans = {s["name"]: s["args"] for s in tracing.recent_spans()}
    for name in ("recurse", "device.call"):
        assert spans[name]["lanes"] == 1
        assert spans[name]["batch_wait_us"] == 0
    assert "recurse_batch_total 1" in metrics.render_prometheus()
    assert _data(dev, q) == _data(host, q)


def test_a_served_traversal_is_a_flight_of_the_recurse_family(worlds):
    facts, dev, host, _ = worlds[10, 7]
    _tile(dev)
    q = _q(KHOP, [graph500.FIRST_UID + 9], 7)
    metrics.reset()
    tracing.clear()
    sl = json.loads(dev.query_json(q))["extensions"]["server_latency"]
    flight, = _flights()
    call, = [s for s in tracing.recent_spans() if s["name"] == "device.call"]
    assert (flight["args"]["family"], flight["args"]["lanes"]) \
        == ("recurse", 1)
    assert call["args"]["flight"] == flight["span_id"]
    assert flight["parent_id"] == call["span_id"]
    # it found the chip free: no stand, and nobody to launch for
    assert sl["device_queue_ns"] == 0 < sl["device_wait_ns"]
    counters = metrics.snapshot()["counters"]
    assert counters['rendezvous_ns_total{family="recurse",phase="land"}'] > 0
    assert 'rendezvous_chained_total{family="recurse"}' not in counters
    assert counters['device_call_queue_ns_total{family="recurse"}'] == 0


STREAMED = "recurse_hub_tiles_streamed_total"
TOTAL = "recurse_hub_tiles_total"


def test_the_hub_tiles_are_counted_spanned_and_served(worlds):
    """The call's third tally row reaches two registered counters,
    the recurse span and the exposition the harness scrapes."""
    import urllib.request
    from dgraph_tpu.server.http import serve
    facts, dev, host, _ = worlds[10, 7]
    badj = _tile(dev)
    tiles = -(-badj.dense.shape[0] // bitgraph._HUB_TILE_ROWS)
    assert {STREAMED, TOTAL} <= set(metrics.REGISTERED)
    metrics.reset()
    tracing.clear()
    # depth 2 is ONE hop: nothing is reached before a call's first
    # level, which reads every tile
    _data(dev, _q(KHOP, [graph500.FIRST_UID + 9], 2))
    c = metrics.snapshot()["counters"]
    assert c[STREAMED] == c[TOTAL] == tiles
    span = [s["args"] for s in tracing.recent_spans()
            if s["name"] == "recurse"][-1]
    assert span["hub_tiles_streamed"] == span["hub_tiles"] == tiles
    q = _q(KHOP, [graph500.FIRST_UID + 9], 7)
    assert _data(dev, q) == _data(host, q)
    span = [s["args"] for s in tracing.recent_spans()
            if s["name"] == "recurse" and s["args"]["tier"] == "device"][-1]
    c = metrics.snapshot()["counters"]
    assert c[TOTAL] == tiles + span["levels_run"] * tiles
    assert span["hub_tiles"] == span["levels_run"] * tiles
    assert tiles + span["hub_tiles_streamed"] == c[STREAMED] <= c[TOTAL]
    httpd, _ = serve(dev, host="127.0.0.1", port=0, block=False)
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{httpd.server_address[1]}"
                "/debug/prometheus_metrics") as r:
            text = r.read().decode()
    finally:
        httpd.shutdown()
    assert f"{STREAMED} {c[STREAMED]:g}" in text
    assert f"{TOTAL} {c[TOTAL]:g}" in text


READER = os.path.join(ROOT, "benchmark", "metrics",
                      "bfs_rows_streamed_share.py")
# (counters before the window, after it, what the reader says)
SHARES = {
    # 800 calls of six levels over 273 tiles, 63.4% of them read
    "a-window": ({STREAMED: 1_000, TOTAL: 1_000},
                 {STREAMED: 1_000 + 830_842, TOTAL: 1_000 + 1_310_400},
                 100.0 * 830_842 / 1_310_400),
    "every-level-reads-every-row": ({}, {STREAMED: 546, TOTAL: 546}, 100.0),
    # what the parent serves (the reader is laid over its checkout
    # too): other counters, neither of these
    "a-program-without-the-counters": (
        {"recurse_batch_total": 1}, {"recurse_batch_total": 900,
                                     "recurse_batch_lanes_total": 7000},
        None),
    "one-counter-without-the-other": ({}, {STREAMED: 5}, None),
    "the-other-without-the-one": ({}, {TOTAL: 5}, None),
    "no-call-in-the-window": ({STREAMED: 9, TOTAL: 12},
                              {STREAMED: 9, TOTAL: 12}, None),
    "an-adjacency-without-hub-rows": ({}, {STREAMED: 0, TOTAL: 0}, None),
    "nothing-served": ({}, {}, None),
}


def _read(path, before, after):
    """What the benchmark's reader at `path` makes of two scrapes."""
    spec = importlib.util.spec_from_file_location("tb_reader", path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    return reader.read({"counters_before": before, "counters_after": after})


@pytest.mark.parametrize("case", sorted(SHARES))
def test_the_streamed_shares_reader(case):
    before, after, want = SHARES[case]
    got = _read(READER, before, after)
    assert got == want if want is None else got == pytest.approx(want)


def test_the_streamed_share_is_a_metric_of_both_khop_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = (m for m in bench["per_layer"]
              if m["name"] == "bfs_rows_streamed_share")
    assert entry == {
        "name": "bfs_rows_streamed_share", "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "kernels", "moves": "ok_qps",
        "workloads": ["graph500-khop.khop-deep-c16",
                      "graph500-khop-x4.khop-deep-c16"]}
    cells = {w["name"] for w in bench["workloads"]}
    assert set(entry["workloads"]) <= cells


AHEAD_READER = os.path.join(ROOT, "benchmark", "metrics",
                            "rendezvous_ahead_share.py")
AHEAD = 'rendezvous_ahead_total{family="recurse"}'
CALLS = "recurse_batch_total"
# (counters before the window, after it, what the reader says)
AHEAD_SHARES = {
    # 2,535 calls a window, 2,460 of them launched behind another
    "a-window": ({AHEAD: 40, CALLS: 50},
                 {AHEAD: 40 + 2_460, CALLS: 50 + 2_535},
                 100.0 * 2_460 / 2_535),
    "every-call-behind-another": ({}, {AHEAD: 800, CALLS: 800}, 100.0),
    # fewer than 2 x LANES connections: served, and nothing went ahead
    "no-call-ever-full": ({AHEAD: 0, CALLS: 10},
                          {AHEAD: 0, CALLS: 410}, 0.0),
    # what the parent serves (the reader is laid over its checkout
    # too): the calls, not this counter
    "a-program-without-the-counter": (
        {CALLS: 1}, {CALLS: 900, "recurse_batch_lanes_total": 7000,
                     'rendezvous_chained_total{family="recurse"}': 890},
        None),
    "the-counter-without-the-calls": ({}, {AHEAD: 5}, None),
    "another-familys-counter": (
        {}, {'rendezvous_ahead_total{family="similar"}': 5, CALLS: 9},
        None),
    "no-call-in-the-window": ({AHEAD: 9, CALLS: 12},
                              {AHEAD: 9, CALLS: 12}, None),
    "nothing-served": ({}, {}, None),
}


@pytest.mark.parametrize("case", sorted(AHEAD_SHARES))
def test_the_ahead_shares_reader(case):
    before, after, want = AHEAD_SHARES[case]
    got = _read(AHEAD_READER, before, after)
    assert got == want if want is None else got == pytest.approx(want)


def test_the_ahead_share_is_a_metric_of_both_khop_cells_and_no_other():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = (m for m in bench["per_layer"]
              if m["name"] == "rendezvous_ahead_share")
    assert entry == {
        "name": "rendezvous_ahead_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "executor", "moves": "ok_qps",
        "workloads": ["graph500-khop.khop-deep-c16",
                      "graph500-khop-x4.khop-deep-c16"]}
    # appended, nothing moved: behind it only what later PRs appended
    names = [m["name"] for m in bench["per_layer"]]
    assert names[names.index("rendezvous_ahead_share"):][:2] == [
        "rendezvous_ahead_share", "bfs_column_levels_per_call"]
    # (PR 45's eight, of the other family's rendezvous and cell)
    assert all(n.startswith("shortest_") for n in names[41:49])
    # (PR 46's one, of the vector scan's rendezvous and cell)
    assert names[49] == "similar_lanes_per_call"
    # the cells that send a bound @recurse to a device tier, all of them
    recursing = [w["name"] for w in bench["workloads"]
                 if w["config"].startswith("graph500-khop")]
    assert entry["workloads"] == recursing


COLUMN_READER = os.path.join(ROOT, "benchmark", "metrics",
                             "bfs_column_levels_per_call.py")
COLUMNS = "recurse_column_levels_total"
# (counters before the window, after it, what the reader says)
COLUMN_LEVELS = {
    # 2,800 calls a window, every one's first level from columns
    "a-window": ({COLUMNS: 60, CALLS: 60},
                 {COLUMNS: 60 + 2_800, CALLS: 60 + 2_800}, 1.0),
    # one call in four carried more roots than the rule allows
    "some-calls-streamed": ({COLUMNS: 3, CALLS: 5},
                            {COLUMNS: 3 + 600, CALLS: 5 + 800}, 0.75),
    # served, and the rule kept every first level streamed
    "no-call-took-columns": ({COLUMNS: 0, CALLS: 10},
                             {COLUMNS: 0, CALLS: 410}, 0.0),
    # what the parent serves (the reader is laid over its checkout
    # too): the calls and the tiles, not this counter
    "a-program-without-the-counter": (
        {CALLS: 1}, {CALLS: 900, STREAMED: 9_000, TOTAL: 14_000,
                     'rendezvous_ahead_total{family="recurse"}': 890},
        None),
    "the-counter-without-the-calls": ({}, {COLUMNS: 5}, None),
    "no-call-in-the-window": ({COLUMNS: 9, CALLS: 12},
                              {COLUMNS: 9, CALLS: 12}, None),
    "nothing-served": ({}, {}, None),
}


@pytest.mark.parametrize("case", sorted(COLUMN_LEVELS))
def test_the_column_levels_reader(case):
    before, after, want = COLUMN_LEVELS[case]
    got = _read(COLUMN_READER, before, after)
    assert got == want if want is None else got == pytest.approx(want)


def test_the_column_levels_are_a_metric_of_both_khop_cells_and_no_other():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = (m for m in bench["per_layer"]
              if m["name"] == "bfs_column_levels_per_call")
    assert entry == {
        "name": "bfs_column_levels_per_call", "unit": "levels",
        "better": "higher", "source": "program_counter",
        "layer": "kernels", "moves": "ok_qps",
        "workloads": ["graph500-khop.khop-deep-c16",
                      "graph500-khop-x4.khop-deep-c16"]}
    assert bench["per_layer"][40] is entry     # appended (PR 43), nothing moved
    recursing = [w["name"] for w in bench["workloads"]
                 if w["config"].startswith("graph500-khop")]
    assert entry["workloads"] == recursing
    # the one reader this PR adds, beside every other metric's
    assert os.path.exists(COLUMN_READER)


def test_the_column_levels_are_counted_spanned_and_served(
        worlds, monkeypatch):
    """The call's third tally row, [2], reaches a registered counter,
    the recurse span, its device.call child and the exposition the
    harness scrapes: 0 where the rule keeps the first level streamed
    (served all the same), 1 where it is read from columns; the
    answer is the host tier's both ways."""
    import functools
    import urllib.request
    from dgraph_tpu.server.http import serve
    facts, dev, host, _ = worlds[10, 7]
    badj = _tile(dev)
    tiles = -(-badj.dense.shape[0] // bitgraph._HUB_TILE_ROWS)
    # a row of this graph is ONE block of 128 word columns: a column
    # a seed costs a stream of every row, and the rule says stream
    assert badj.dense.shape[1] == 128 and not bitgraph.columns_cheaper(
        8, *badj.dense.shape, 0)
    assert COLUMNS in metrics.REGISTERED

    def spans(name):
        return [s["args"] for s in tracing.recent_spans()
                if s["name"] == name and s["args"].get("tier", "device")
                == "device" and "column_levels" in s["args"]]

    metrics.reset()
    tracing.clear()
    before = metrics.counters_snapshot()
    q = _q(KHOP, [graph500.FIRST_UID + 9], 7)
    assert _data(dev, q) == _data(host, q)
    c = metrics.snapshot()["counters"]
    assert c[COLUMNS] == 0 and c[CALLS] == 1         # served, and 0
    streamed = spans("recurse")[-1]
    assert streamed["column_levels"] == 0 \
        == spans("device.call")[-1]["column_levels"]
    assert _read(COLUMN_READER, before, metrics.counters_snapshot()) == 0.0
    # the same request with the first level read from columns
    monkeypatch.setattr(bitgraph, "traverse", functools.partial(
        traverse_as, columns=True))
    assert _data(dev, q) == _data(host, q)
    c = metrics.snapshot()["counters"]
    assert c[COLUMNS] == 1 and c[CALLS] == 2
    columns = spans("recurse")[-1]
    assert columns["column_levels"] == 1 \
        == spans("device.call")[-1]["column_levels"]
    assert columns["levels_run"] == streamed["levels_run"]
    assert columns["reached"] == streamed["reached"]
    # a level's tiles fewer streamed, as many counted
    assert columns["hub_tiles"] == streamed["hub_tiles"]
    assert columns["hub_tiles_streamed"] \
        == streamed["hub_tiles_streamed"] - tiles
    assert _read(COLUMN_READER, before, metrics.counters_snapshot()) \
        == pytest.approx(0.5)
    share = os.path.join(ROOT, "benchmark", "metrics",
                         "bfs_rows_streamed_share.py")
    assert _read(share, before, metrics.counters_snapshot()) \
        == pytest.approx(100.0 * (2 * streamed["hub_tiles_streamed"] - tiles)
                         / (2 * streamed["hub_tiles"]))
    httpd, _ = serve(dev, host="127.0.0.1", port=0, block=False)
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{httpd.server_address[1]}"
                "/debug/prometheus_metrics") as r:
            text = r.read().decode()
    finally:
        httpd.shutdown()
    assert f"{COLUMNS} 1" in text


def test_a_served_call_behind_another_is_what_the_reader_reads(
        worlds, monkeypatch):
    """The executor's site end to end: 1 + LANES requests are two
    calls, the second launched behind the first, and the reader makes
    of the served counters one call in two."""
    facts, dev, host, _ = worlds[9, 31_000_017]
    _tile(dev)
    gate, calls = _hold_first_call(monkeypatch)
    before = metrics.counters_snapshot()
    tracing.clear()
    q = [_q(KHOP, [graph500.FIRST_UID + i], 4)
         for i in range(1 + bitgraph.LANES)]
    out, threads = _serve(dev, q[:1])
    while not calls:
        time.sleep(0.001)
    more, threads2 = _serve(dev, q[1:])
    while len(calls) < 2:
        time.sleep(0.001)
    assert calls == [1, bitgraph.LANES] and threads[0].is_alive()
    gate.set()
    for t in threads + threads2:
        t.join(60)
        assert not t.is_alive()
    for i, rep in [(0, out[0])] + [(i + 1, more[i])
                                   for i in range(bitgraph.LANES)]:
        assert json.dumps(rep["data"], separators=(",", ":")) \
            == _data(host, q[i])
    assert sorted((f["args"]["lanes"], f["args"]["ahead"])
                  for f in _flights()) == [(1, False), (bitgraph.LANES, True)]
    assert _read(AHEAD_READER, before, metrics.counters_snapshot()) \
        == pytest.approx(50.0)


@pytest.mark.parametrize("graph", GRAPHS, ids=lambda g: f"scale{g[0]}")
def test_requests_in_flight_share_calls_and_keep_their_own_accounts(
        worlds, graph, monkeypatch):
    facts, dev, host, csr = worlds[graph]
    _tile(dev)
    n = 2 * bitgraph.LANES + 3
    reqs = _requests(facts, n, graph[0])
    # every third reader wants the uids themselves
    queries = [_q(UIDS if i % 3 == 0 else KHOP, r, d)
               for i, (r, d) in enumerate(reqs)]
    gate, calls = _hold_first_call(monkeypatch)
    metrics.reset()
    out, threads = _serve(dev, queries[:1])
    while not calls:
        time.sleep(0.001)
    more, threads2 = _serve(dev, queries[1:])
    meet = Rendezvous.at(dev.tablets["link"]._device_badj, bitgraph.LANES,
                         family="recurse")
    # the first LANES of them are a full call, on the device behind the
    # one in flight already; the others stand
    _standing(meet, n - 1 - bitgraph.LANES)
    while len(calls) < 2:
        time.sleep(0.001)
    assert calls == [1, bitgraph.LANES]
    held_ns = 150_000_000   # every one of them waits this long at least
    time.sleep(held_ns / 1e9)
    gate.set()
    for t in threads + threads2:
        t.join(60)
        assert not t.is_alive()
    replies = [out[0]] + [more[i] for i in range(n - 1)]
    counters = metrics.snapshot()["counters"]
    # fewer calls than requests, nobody lost: 1, then LANES, LANES, 2
    assert calls == [1, bitgraph.LANES, bitgraph.LANES, 2]
    assert counters["recurse_batch_total"] == 4
    assert counters["recurse_batch_lanes_total"] == n
    assert counters["query_device_recurse_total"] == n
    for i, ((roots, depth), rep) in enumerate(zip(reqs, replies)):
        assert not isinstance(rep, BaseException), rep
        want = _plain_reached(csr, roots, depth - 1)
        got = rep["data"]["khop"]
        if i % 3 == 0:
            assert [int(o["uid"], 16) for o in got] == (
                np.flatnonzero(want) + graph500.FIRST_UID).tolist()
        else:
            assert got == [{"count": int(want.sum())}]
        assert json.dumps(rep["data"], separators=(",", ":")) \
            == _data(host, queries[i])
        sl = rep["extensions"]["server_latency"]
        assert sl["device_calls"] == 1
        # its wait covers its queueing behind the call in flight
        if i:
            assert sl["device_wait_ns"] >= held_ns * 0.9
        assert sl["device_wait_ns"] <= sl["processing_ns"]


def test_the_wait_for_the_batch_is_not_host_time(worlds, monkeypatch):
    """`recurse_host_ms` is recurse_ns_total less the family's three
    phases: the wait at the rendezvous lands in `wait`, not in it."""
    facts, dev, _, _ = worlds[9, 31_000_017]
    _tile(dev)
    gate, calls = _hold_first_call(monkeypatch)
    metrics.reset()
    tracing.clear()
    q = [_q(KHOP, [graph500.FIRST_UID + i], 4) for i in range(3)]
    out, threads = _serve(dev, q[:1])
    while not calls:
        time.sleep(0.001)
    more, threads2 = _serve(dev, q[1:])
    meet = Rendezvous.at(dev.tablets["link"]._device_badj, bitgraph.LANES,
                         family="recurse")
    _standing(meet, 2)
    time.sleep(0.2)
    gate.set()
    for t in threads + threads2:
        t.join(60)
    c = metrics.snapshot()["counters"]
    phases = sum(v for k, v in c.items()
                 if k.startswith('device_call_ns_total{family="recurse"'))
    assert phases >= 3 * 0.2e9 * 0.9
    assert 0 <= c["recurse_ns_total"] - phases < 0.2e9
    waits = sorted(s["args"]["batch_wait_us"] for s in tracing.recent_spans()
                   if s["name"] == "recurse")
    assert waits[0] == 0 and waits[1] >= 0.2e6 * 0.9
    assert sorted(s["args"]["lanes"] for s in tracing.recent_spans()
                  if s["name"] == "device.call") == [1, 2, 2]


def test_a_request_past_its_deadline_leaves_and_the_others_answer(
        worlds, monkeypatch):
    facts, dev, host, _ = worlds[9, 31_000_017]
    _tile(dev)
    gate, calls = _hold_first_call(monkeypatch)
    q = [_q(KHOP, [graph500.FIRST_UID + i], 5) for i in range(4)]
    out, threads = _serve(dev, q[:1])
    while not calls:
        time.sleep(0.001)
    more, threads2 = _serve(dev, q[2:])
    meet = Rendezvous.at(dev.tablets["link"]._device_badj, bitgraph.LANES,
                         family="recurse")
    _standing(meet, 2)
    late, threads3 = _serve(
        dev, q[1:2], ctxs={0: RequestContext.with_timeout(0.05)})
    threads3[0].join(60)    # the one with the deadline has left
    _standing(meet, 2)
    gate.set()
    for t in threads + threads2:
        t.join(60)
        assert not t.is_alive()
    assert isinstance(late[0], DeadlineExceeded)
    for i, rep in ((0, out[0]), (2, more[0]), (3, more[1])):
        assert json.dumps(rep["data"], separators=(",", ":")) \
            == _data(host, q[i])
    assert calls == [1, 2]


def test_a_dispatch_that_raises_releases_every_member(worlds, monkeypatch):
    facts, dev, host, _ = worlds[8, 2**31 + 5]
    _tile(dev)
    gate, calls = _hold_first_call(monkeypatch)
    launch0 = executor_mod._launch_traversals
    launches = []

    def launch(badj, riders):
        launches.append(len(riders))
        if len(launches) == 2:
            raise RuntimeError("RESOURCE_EXHAUSTED")
        return launch0(badj, riders)

    monkeypatch.setattr(executor_mod, "_launch_traversals", launch)
    q = [_q(KHOP, [graph500.FIRST_UID + i], 4) for i in range(4)]
    out, threads = _serve(dev, q[:1])
    while not calls:
        time.sleep(0.001)
    more, threads2 = _serve(dev, q[1:])
    meet = Rendezvous.at(dev.tablets["link"]._device_badj, bitgraph.LANES,
                         family="recurse")
    _standing(meet, 3)
    before = _counter("query_device_recurse_total")
    gate.set()
    for t in threads + threads2:
        t.join(60)
        assert not t.is_alive()
    assert launches == [1, 3]
    assert all(isinstance(more[i], RuntimeError) for i in range(3))
    # a block that raised counts no dispatch; the first request did
    assert _counter("query_device_recurse_total") == before + 1
    assert json.dumps(out[0]["data"], separators=(",", ":")) \
        == _data(host, q[0])
    # and the site serves on
    assert _data(dev, q[1]) == _data(host, q[1])


def test_requests_meet_only_over_the_tile_their_read_ts_resolves_to(
        tmp_path):
    from dgraph_tpu.cluster.coordinator import StaleSnapshot
    db = _db(str(tmp_path), 8, 77, prefer_device=True, device_min_edges=1)
    db.rollup_in_read = False
    db.alter("link: [uid] @reverse .")
    db.rollup_all(window=0)
    root = graph500.FIRST_UID + 2
    fwd = _q(KHOP, [root], 4)
    rev = fwd.replace("n as link", "n as ~link")
    _data(db, fwd)
    _data(db, rev)
    tab = db.tablets["link"]
    tile, tile_t = tab._device_badj, tab._device_badj_t
    # the transposed tile is another tile: another rendezvous
    assert Rendezvous.at(tile, 8) is not Rendezvous.at(tile_t, 8)
    old_ts = db.coordinator.max_assigned()
    old = json.loads(_data(db, fwd))["khop"][0]["count"]
    db.mutate(set_nquads=f"<{root:#x}> <link> <0xfffff> .\n"
                         "<0xfffff> <link> <0xffffe> .")
    # a dirty tablet never gets to the rendezvous: the host tier
    # answers what the tile cannot speak for
    before = _counter("query_device_recurse_total")
    assert tab.dirty()
    assert json.loads(_data(db, fwd))["khop"][0]["count"] == old + 2
    assert _counter("query_device_recurse_total") == before
    # rolled up, the tablet has ANOTHER tile with a rendezvous of its
    # own; a reader pinned below its base_ts is refused before it
    # could ride it
    db.rollup_all(window=0)
    assert json.loads(_data(db, fwd))["khop"][0]["count"] == old + 2
    assert _counter("query_device_recurse_total") == before + 1
    new_tile = tab._device_badj
    assert new_tile is not tile and old_ts < tab.base_ts
    assert Rendezvous.at(new_tile, 8) is not Rendezvous.at(tile, 8)
    with pytest.raises(StaleSnapshot):
        _data(db, fwd, read_ts=old_ts)
    assert not Rendezvous.at(new_tile, 8)._waiting
