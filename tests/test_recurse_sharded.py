"""The k-hop traversal with its adjacency split over a mesh's chips
(`alpha --chips N`): `ops/bitgraph.bfs_traverse_sharded` against the
one-chip `bfs_traverse` and a plain numpy BFS, bit for bit; the shares
of a level add up to the level; the served path with a mesh answers
what the postings tier answers; the flag. The suite runs on 8 virtual
CPU devices (conftest.py), four of which make the mesh."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dgraph_tpu import cli
from dgraph_tpu.engine.db import GraphDB
from dgraph_tpu.ingest.bulk import bulk_load
from dgraph_tpu.ops import bitgraph
from dgraph_tpu.parallel.mesh import make_mesh
from dgraph_tpu.query import executor as executor_mod
from dgraph_tpu.utils import metrics, tracing
from recurse_cases import column_lanes, traverse_as

CHIPS = 4
LANES = bitgraph.LANES


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(CHIPS, axes=("uid",))


def _edges(n: int, m: int, seed: int, back_to: int = 1) -> dict:
    """A skewed directed graph over uids 1..n: m edges drawn with
    Zipf targets (a few hubs, many classes of one or two rows), no
    self-loops, and a cycle through `back_to` so that an edge leads
    back to that root."""
    rng = np.random.default_rng(seed)
    src = rng.integers(1, n + 1, m)
    dst = rng.zipf(1.5, m) % n + 1
    pairs = {(int(s), int(d)) for s, d in zip(src, dst) if s != d}
    pairs |= {(back_to, back_to + 1), (back_to + 1, back_to + 2),
              (back_to + 2, back_to)}
    out: dict = {}
    for s, d in sorted(pairs):
        out.setdefault(s, []).append(d)
    return {s: np.array(d, np.uint32) for s, d in out.items()}


def _plain(edges: dict, roots: list, hops: int) -> np.ndarray:
    """Sorted uids an edge of the walk leads to within `hops` hops of
    `roots`: numpy over the edge lists, nothing of the program's."""
    seen = set(roots)
    reached: set = set()
    frontier = set(roots)
    for _ in range(hops):
        nxt = set()
        for u in frontier:
            nxt.update(int(v) for v in edges.get(u, ()))
        reached |= nxt
        frontier = nxt - seen
        seen |= nxt
        if not frontier:
            break
    return np.array(sorted(reached), np.uint32)


def _pair(edges: dict, mesh, budget_rows):
    """(one-chip adjacency, the same split over `mesh`), both with as
    many hub rows as `budget_rows` rows of room allow (None: all the
    room there is, 0: none), a chip of the mesh a quarter of it."""
    one = bitgraph.build_bitadjacency(edges)
    four = bitgraph.build_bitadjacency(edges)
    row = 4 * bitgraph.hub_row_words(one.n_slots)
    room = 1 << 40 if budget_rows is None else row * budget_rows
    bitgraph.attach_dense(one, room)
    bitgraph.attach_dense(four, room if budget_rows is None
                          else room // CHIPS, mesh=mesh)
    return one, four


def _both(one, four, riders, **kw):
    """The same riders over both adjacencies -> the sharded (tally,
    reached), held to the one-chip program's bit for bit: counts,
    levels and reached sets. The hub-row tiles (tally row 2) are a
    chip's own runs', so they differ by layout; neither program
    streams more than there is. Both programs twice: the first level
    streamed (what is returned) and read from the roots' COLUMNS,
    which is the same to the bit and streams a level's tiles fewer."""
    t1, r1 = _traverse(one, riders, columns=False, **kw)
    t4, r4 = _traverse(four, riders, columns=False, **kw)
    assert t1.dtype == t4.dtype and r1.dtype == r4.dtype
    assert np.array_equal(t1[:2], t4[:2]) and np.array_equal(r1, r4)
    for t, adj in ((t1, one), (t4, four)):
        streamed, full = t[2, :2]
        assert 0 <= streamed <= full and not t[2, 2:].any()
        assert (full > 0) == (adj.dense is not None and t[1].any())
        c, rc = _traverse(adj, riders, columns=True, **kw)
        assert np.array_equal(c[:2], t[:2]) and np.array_equal(rc, r1)
        # (a chip whose run is all padding streams one tile a level,
        # not a level's: at most a level's tiles fewer)
        assert c[2, 1:].tolist() == [full, int(t[1].any())] \
            + [0] * (LANES - 3)
        assert streamed - (full // t[1].max() if full else 0) \
            <= c[2, 0] <= streamed - bool(full)
    return t4, r4


def _traverse(adj, riders, columns=None, **kw):
    """bitgraph.traverse -> (tally, reached) on the host, the first
    level's form forced where `columns` says which."""
    return tuple(np.asarray(x) for x in traverse_as(
        adj, riders, columns, **kw))


# (vertices, edges drawn, seed): no vertex count is a multiple of 4 or
# of a vreg's 4,096; the second has more than one vreg of them
GRAPHS = {"small": (1003, 6000, 3), "wide": (4099 + 130, 30000, 11)}


@pytest.fixture(scope="module")
def graphs():
    return {k: _edges(n, m, seed) for k, (n, m, seed) in GRAPHS.items()}


@pytest.mark.parametrize("rows", (None, 40, 0),
                         ids=("all_hub_rows", "some_hub_rows", "no_hub_rows"))
@pytest.mark.parametrize("riders", (1, 2, 3, 5, LANES))
def test_sharded_traversal_is_the_one_chip_traversal_bit_for_bit(
        graphs, mesh, rows, riders):
    edges = graphs["small"]
    one, four = _pair(edges, mesh, rows)
    assert four.mesh is mesh and four.shards == CHIPS
    assert (four.dense is None) == (one.dense is None)
    assert four.n_slots % CHIPS and four.n_slots % 4096
    if rows == 0:
        # degree classes with fewer rows than chips are among the parts
        assert sum(0 < r < CHIPS
                   for r, _ in bitgraph.shard_parts(four)) >= 3
    # mixed depths: a lane that ends early beside ones that do not;
    # lane 0's root is one an edge leads back to
    starts = [1] + sorted(edges)[5:5 + riders - 1]
    depths = [3, 1, 6, 2, 7, 4, 1, 5][:riders]
    lanes = [(bitgraph.seed_slots(four, np.array([u], np.uint32)), d)
             for u, d in zip(starts, depths)]
    tally, reached = _both(one, four, lanes)
    for i, (u, d) in enumerate(zip(starts, depths)):
        want = _plain(edges, [u], d)
        assert tally[0, i] == len(want), (u, d)
        assert np.array_equal(bitgraph.lane_uids(four, reached, i), want)
        assert 1 <= tally[1, i] <= d
    assert 1 in _plain(edges, [1], 3)          # the root, led back to
    # a lane nobody rides reaches nothing and runs no level
    assert not tally[:2, riders:].any()
    assert not (reached >> np.uint32(riders)).any()


def test_sharded_traversal_over_more_than_a_vreg_of_vertices(graphs, mesh):
    edges = graphs["wide"]
    one, four = _pair(edges, mesh, 300)
    assert four.n_slots > 4096 and four.dense is not None
    assert four.dense.shape[1] == 256 and four.dense.shape[0] % (8 * CHIPS) == 0
    roots = sorted(edges)[:3]
    # several roots a lane, and a lane of depth 0 among the riders
    lanes = [(bitgraph.seed_slots(four, np.array(roots, np.uint32)), 4),
             (bitgraph.seed_slots(four, np.array(roots[:1], np.uint32)), 0),
             (bitgraph.seed_slots(four, np.array(roots[1:], np.uint32)), 64)]
    tally, reached = _both(one, four, lanes)
    assert tally[0, 0] == len(_plain(edges, roots, 4))
    assert tally[:2, 1].tolist() == [0, 0]
    assert np.array_equal(bitgraph.lane_uids(four, reached, 2),
                          _plain(edges, roots[1:], 64))
    assert tally[1, 2] < 64                    # it ended early


@pytest.mark.parametrize("case", (
    "padding_seeds", "one_root_in_two_lanes", "a_lane_of_depth_0",
    "every_lane_of_depth_0", "a_root_with_no_out_edge",
    "a_root_that_is_a_hub", "eight_seeds_no_padding",
    "more_roots_than_the_rule_allows"))
@pytest.mark.parametrize("rows", (None, 40, 0),
                         ids=("all_hub_rows", "some_hub_rows", "no_hub_rows"))
@pytest.mark.parametrize("graph", ("small", "wide"))
def test_the_sharded_first_level_from_columns_is_the_streamed_one(
        graphs, mesh, graph, rows, case):
    """A chip reads the seeds' columns of ITS run of the hub rows and
    of every gathered class; the level's one all-gather and the rest
    of the call are as they were: (counts, levels, reached) bit for
    bit (_both), the plain walk's, and by the rule from a chip's own
    shapes where nobody forces a form."""
    edges = dict(graphs[graph])
    # (a vertex no edge leaves, which these graphs may lack)
    last = max(max(edges), max(int(d[-1]) for d in edges.values()))
    edges[min(edges)] = np.append(edges[min(edges)], np.uint32(last + 1))
    one, four = _pair(edges, mesh, rows)

    def ruled(adj, seeds):
        """The rule's word for `seeds` seed slots over ONE chip's
        shapes of `adj`."""
        chip_rows, words = (0, 0) if adj.dense is None else (
            adj.dense.shape[0] // adj.shards, adj.dense.shape[1])
        held = adj.shard_nbs if adj.mesh is not None \
            else [b.in_nb for b in adj.gathered]
        return bitgraph.columns_cheaper(
            seeds, chip_rows, words,
            sum(int(nb.size) for nb in held) // adj.shards)

    # the count of seed slots, a power of two, at which both layouts
    # turn back to the stream
    turn = next(s for s in (8, 16, 32, 64, 128, 256, 512, 1024, 2048)
                if not (ruled(one, s) or ruled(four, s)))
    lanes = column_lanes(case, edges, turn // LANES)
    riders = _riders(four, lanes)
    tally, reached = _both(one, four, riders, tile=TILE)
    for i, (roots, depth) in enumerate(lanes):
        want = _plain(edges, roots, depth)
        assert tally[0, i] == len(want), (roots, depth)
        assert np.array_equal(bitgraph.lane_uids(four, reached, i), want)
    seeds = max(8, sum(len(roots) for roots, _ in lanes))
    for adj in (one, four):
        t, r = _traverse(adj, riders, tile=TILE)
        assert np.array_equal(t[:2], tally[:2])
        assert np.array_equal(r, reached)
        assert t[2, 2] == (ruled(adj, seeds) and tally[1].any())
        if case == "more_roots_than_the_rule_allows":
            assert seeds == turn and t[2, 2] == 0
        elif rows is not None and tally[1].any():
            assert t[2, 2] == 1


@pytest.mark.parametrize("rows", (None, 40, 0),
                         ids=("all_hub_rows", "some_hub_rows", "no_hub_rows"))
def test_the_chips_shares_add_up_to_the_level(graphs, mesh, rows):
    """Each chip's reach segment, taken apart by part and put chip
    after chip, is the one-chip level's reach."""
    one, four = _pair(graphs["small"], mesh, rows)
    rng = np.random.default_rng(7)
    words = (rng.integers(0, 1 << LANES, one.n_slots)
             * (rng.random(one.n_slots) < 0.1)).astype(np.uint32)
    frontier = jnp.asarray(words)
    active = jnp.bitwise_or.reduce(frontier)
    # the one-chip level, as bfs_traverse's body works it out
    # nothing reached yet: every lane that holds a frontier needs
    # every row, as at a call's first level
    nothing = jnp.zeros(one.n_slots, jnp.uint32)
    tile = bitgraph._HUB_TILE_ROWS
    parts = [bitgraph._gathered_reach(
        [b.in_nb for b in one.gathered], frontier)]
    if one.dense is not None:
        rows = one.dense.shape[0]
        parts.append(bitgraph._hub_reach(
            one.dense, frontier, active, bitgraph._hub_pending(
                nothing, active, one.n_covered - rows, rows),
            LANES, tile)[0])
    want = np.concatenate([np.asarray(p) for p in parts] + [
        np.zeros(one.n_slots - one.n_covered, np.uint32)])
    P = jax.sharding.PartitionSpec
    part_rows = bitgraph.shard_parts(four)
    pending = None if four.dense is None else bitgraph._hub_pending(
        nothing, active, four.n_covered - four.dense_rows, four.dense_rows,
        CHIPS)
    shares = jax.jit(jax.shard_map(
        lambda nbs, dense, f, a: bitgraph._chip_reach(
            nbs, dense, f, a, pending, LANES, tile,
            jax.lax.axis_index("uid"))[0][None],
        mesh=mesh,
        in_specs=([P("uid")] * len(four.shard_nbs),
                  None if four.dense is None else P("uid"), P(), P()),
        out_specs=P("uid")))(four.shard_nbs, four.dense, frontier, active)
    assert shares.shape == (CHIPS, sum(held for _, held in part_rows))
    assert sum(r for r, _ in part_rows) == four.n_covered
    got = np.asarray(bitgraph._whole_reach(shares, part_rows, four.n_slots))
    assert want.any() and np.array_equal(got, want)
    # what lies behind a part's last row on the last chips is padding
    at = 0
    for r, held in part_rows:
        flat = np.asarray(shares)[:, at:at + held].reshape(-1)
        assert not flat[r:].any()
        at += held


def test_a_chip_holds_its_run_of_the_rows_and_prices_its_share(graphs, mesh):
    one = bitgraph.build_bitadjacency(graphs["small"])
    four = bitgraph.build_bitadjacency(graphs["small"])
    room = 40 * 4 * bitgraph.hub_row_words(one.n_slots)
    bitgraph.attach_dense(one, room)
    bitgraph.attach_dense(four, room, mesh=mesh)   # a chip's room each
    for nb in four.shard_nbs + [four.dense]:
        assert nb.sharding.shard_shape(nb.shape)[0] * CHIPS == nb.shape[0]
        assert len(nb.sharding.device_set) == CHIPS
    # four budgets of rows hold at least the classes one budget holds
    assert four.dense_from <= one.dense_from
    assert four.dense_rows >= one.dense_rows
    # the one-chip copies of the matrices are gone from the device
    assert all(isinstance(b.in_nb, np.ndarray) for b in four.buckets)
    assert bitgraph.resident_bytes(four) == sum(
        int(a.nbytes) for a in four.shard_nbs) + int(four.dense.nbytes)
    lone = bitgraph.build_bitadjacency(graphs["small"])
    bitgraph.attach_dense(lone, 1 << 40)
    split = bitgraph.build_bitadjacency(graphs["small"])
    bitgraph.attach_dense(split, 1 << 40, mesh=mesh)
    # the same rows on four chips cost a chip about a quarter
    assert bitgraph.level_seconds(split) < 0.3 * bitgraph.level_seconds(lone)


# -- the rows no live lane needs are not read ----------------------------
#
# A graph made to settle tile by tile, tiles of TILE rows. R and the
# fifteen HUBS hold 8 in-edges each (one degree class, R its first
# row): seven FEEDERS point at every one of them, R at every hub, and
# b back at R, the end of R -> a -> b -> R. So R is a hub ROOT that an
# edge leads back to only at hop 3: `visited` from the start, not
# `reached` until then. b also starts a chain b -> c -> d -> e, found
# through the one-in-edge class at levels 3-5, after every hub is.
# Forty leaves (an in-edge each, from a feeder) keep that class too
# large for the budget that holds the hubs' rows.

TILE = 8
R, HUBS, FEEDERS = 1, list(range(2, 17)), list(range(100, 107))
A, B, C, D, E = 50, 51, 52, 53, 54
LEAVES = list(range(200, 240))


def _settling_edges() -> dict:
    out = {R: HUBS + [A], A: [B], B: [R, C], C: [D], D: [E]}
    for i, f in enumerate(FEEDERS):
        out[f] = [R] + HUBS + LEAVES[i::len(FEEDERS)]
    return {s: np.array(sorted(d), np.uint32) for s, d in out.items()}


@pytest.fixture(scope="module", params=("every_class_as_rows",
                                        "the_hubs_as_rows"))
def settling(request, mesh):
    """(edges, one-chip adjacency, the same over the mesh): every
    degree class as hub rows (61 rows: 7 tiles and 5 rows; 16 rows a
    chip), or the hubs' class alone (16 rows: two tiles; 8 rows on
    each of two chips and two chips of padding) with the
    one-in-edge class gathered."""
    edges = _settling_edges()
    one = bitgraph.build_bitadjacency(edges)
    four = bitgraph.build_bitadjacency(edges)
    row = 4 * bitgraph.hub_row_words(one.n_slots)
    if request.param == "every_class_as_rows":
        bitgraph.attach_dense(one, 1 << 40)
        bitgraph.attach_dense(four, 1 << 40, mesh=mesh)
        assert one.dense_rows == four.dense_rows == 61 and 61 % TILE
        assert not one.gathered
    else:
        bitgraph.attach_dense(one, 16 * row)
        bitgraph.attach_dense(four, 8 * row, mesh=mesh)
        assert one.dense_rows == four.dense_rows == 16
        assert len(one.gathered) == len(four.gathered) == 1
        # R's row is the first, the hubs' follow by uid
        assert one.slot_uids[one.n_covered - 16:one.n_covered].tolist() \
            == [R] + HUBS
    return edges, one, four


def _riders(adj, lanes):
    return [(bitgraph.seed_slots(adj, np.array(roots, np.uint32)), depth)
            for roots, depth in lanes]


def _checked(settling, lanes):
    """The riders over both adjacencies in tiles of TILE rows, every
    lane held to the plain BFS -> the one-chip and the sharded
    tallies, every level of both streamed."""
    edges, one, four = settling
    riders = _riders(one, lanes)
    t4, reached = _both(one, four, riders, tile=TILE)
    t1, _ = _traverse(one, riders, tile=TILE, columns=False)
    for i, (roots, depth) in enumerate(lanes):
        want = _plain(edges, roots, depth)
        assert t4[0, i] == len(want), (roots, depth)
        assert np.array_equal(bitgraph.lane_uids(four, reached, i), want)
    return t1, t4


@pytest.mark.parametrize("depth", (1, 2, 3, 4, 6, 7))
def test_a_hub_root_keeps_its_row_until_an_edge_leads_back(settling, depth):
    edges, one, _ = settling
    t1, _ = _checked(settling, [([R], depth)])
    # R is its own third hop and nothing before
    assert (R in _plain(edges, [R], depth)) == (depth >= 3)
    assert t1[1, 0] == min(depth, 6)
    if one.gathered:
        # the hubs' two tiles: both at level 1; R's alone while R is
        # not reached (levels 2, 3); then none, and the level still
        # finds c, d, e through the gathered class (a step's first
        # block is fetched whatever the flags say: 1 a level)
        assert t1[2, :2].tolist() == [
            sum([2, 1, 1, 1, 1, 1][:depth]), 2 * min(depth, 6)]
    else:
        assert t1[2, 1] == 8 * min(depth, 6)
        assert (t1[2, 0] < t1[2, 1]) == (depth > 1)


def test_a_lane_that_settled_a_tile_rides_beside_one_that_did_not(settling):
    _, one, _ = settling
    # a feeder's lane reaches R and every hub at its first level; R's
    # own lane needs R's row for two levels more
    lanes = [([R], 4), ([FEEDERS[0]], 3), ([FEEDERS[1], A], 2), ([B], 6)]
    t1, t4 = _checked(settling, lanes)
    for i, lane in enumerate(lanes):
        alone1, alone4 = _checked(settling, [lane])
        assert t1[:2, i].tolist() == alone1[:2, 0].tolist() \
            == alone4[:2, 0].tolist()
    assert 0 < t1[2, 0] < t1[2, 1]


def test_a_dead_lane_and_an_unridden_one_hold_no_tile_back(settling):
    # a hub has no out-edge: its lane finds nothing and is dead after
    # one level; a rider of depth 0 never lives; lanes 4-7 are not
    # ridden. None of them keeps a tile streaming for R's lane
    alone1, alone4 = _checked(settling, [([R], 5)])
    t1, t4 = _checked(settling, [([R], 5), ([HUBS[4]], 5), ([R], 0),
                                  ([HUBS[0], HUBS[9]], 7)])
    assert t1[:2, 1:4].tolist() == [[0, 0, 0], [1, 0, 1]]
    assert t1[2].tolist() == alone1[2].tolist()
    assert t4[2].tolist() == alone4[2].tolist()


def test_lanes_of_one_hop_stream_every_tile(settling):
    """Nothing is reached at a call's first level: it reads every
    tile, and the count of them says so."""
    t1, t4 = _checked(settling, [([R], 1), ([FEEDERS[2]], 1), ([B], 1)])
    assert t1[2, 0] == t1[2, 1] > 0 and t4[2, 0] == t4[2, 1] >= t1[2, 1]


def test_a_chips_tiles_follow_its_own_run_of_the_rows(settling, mesh):
    """_hub_pending hands every chip every chip's needs (the count of
    tiles then needs no collective); the padding behind the last row
    is nobody's."""
    _, one, four = settling
    rows = four.dense_rows
    held = four.dense.shape[0] // CHIPS
    start = four.n_covered - rows
    reached = np.zeros(four.n_slots, np.uint32)
    reached[start + 3:start + rows] = 0b11          # all but three rows
    active = jnp.uint32(0b01)
    pending = np.asarray(bitgraph._hub_pending(
        jnp.asarray(reached), active, start, rows, CHIPS))
    assert pending.shape == (CHIPS, held)
    assert pending.reshape(-1)[:3].tolist() == [1, 1, 1]
    # lanes 2-7 have reached none of the other rows and are not live:
    # they need nothing
    assert not pending.reshape(-1)[3:].any()
    needed = np.asarray(bitgraph._tiles_needed(jnp.asarray(pending), TILE))
    assert needed.sum() == 1 and needed[0, 0]
    assert not np.asarray(bitgraph._hub_pending(
        jnp.asarray(reached), jnp.uint32(0), start, rows, CHIPS)).any()
    assert np.array_equal(
        np.asarray(bitgraph._hub_pending(
            jnp.asarray(reached), active, start, rows)).reshape(-1),
        pending.reshape(-1)[:rows])


# -- the served path ---------------------------------------------------


KHOP = ("{ var(func: uid(%s)) @recurse(depth: %d, loop: false) "
        "{ n as link } khop(func: uid(n)) { %s } }")


def _db(rdf: str, **kw) -> GraphDB:
    return bulk_load([rdf], schema="link: [uid] .", db=GraphDB(**kw))


def _data(db, q):
    body = db.query_json(q)
    return json.loads(body[len('{"data":'):body.rfind(',"extensions":')])


def _counter(name):
    return sum(v for k, v in metrics.snapshot()["counters"].items()
               if k.startswith(name))


def _gauge(name):
    return sum(v for k, v in metrics.snapshot()["gauges"].items()
               if k.startswith(name))


@pytest.fixture()
def every_bound_recurse_on_the_device(monkeypatch):
    monkeypatch.setattr(executor_mod.Executor, "_device_worth",
                        lambda self, *a, **kw: True)


@pytest.fixture(scope="module")
def rdf(graphs, tmp_path_factory):
    path = str(tmp_path_factory.mktemp("sharded") / "g.rdf")
    with open(path, "w") as f:
        f.writelines(f"<{s:#x}> <link> <{int(d):#x}> .\n"
                     for s, ds in graphs["small"].items() for d in ds)
    return path


@pytest.fixture(scope="module")
def engines(graphs, mesh, rdf):
    """(edges, an engine with the mesh, one without, the postings
    tier's) over one bulk-loaded graph."""
    edges = graphs["small"]
    return (edges,
            _db(rdf, prefer_device=True, device_min_edges=1, mesh=mesh),
            _db(rdf, prefer_device=True, device_min_edges=1),
            _db(rdf, prefer_device=False))


@pytest.mark.parametrize("depth", (4, 7), ids=("khop3", "khop6"))
@pytest.mark.parametrize("reader", ("count(uid)", "uid"))
def test_an_engine_with_a_mesh_answers_as_the_postings_tier_does(
        engines, every_bound_recurse_on_the_device, depth, reader):
    edges, sharded, _, host = engines
    before = {n: _counter(n) for n in (
        "recurse_sharded_total", "recurse_sharded_lanes_total",
        "recurse_batch_total", "query_device_recurse_total")}
    roots = [1] + sorted(edges)[10:13]
    for u in roots:
        q = KHOP % (hex(u), depth, reader)
        got = _data(sharded, q)
        assert got == _data(host, q)
        want = _plain(edges, [u], depth - 1)
        if reader == "uid":
            assert [int(x["uid"], 16) for x in got["khop"]] == want.tolist()
        else:
            assert got == {"khop": [{"count": len(want)}]}
    for n, was in before.items():
        assert _counter(n) == was + len(roots), n
    tile = sharded.tablets["link"]._device_badj
    assert tile.mesh is not None and tile.shards == CHIPS


def test_a_full_call_over_the_mesh_goes_behind_the_call_in_flight(
        engines, every_bound_recurse_on_the_device, monkeypatch):
    """PR 40 over the sharded program: with one call held "on the
    chips", LANES requests are a full call that one of them launches
    at once (another thread than the one blocked for the first), and
    each gets the postings tier's answer out of its own lane."""
    import threading
    import time

    edges, sharded, _, host = engines
    roots = sorted(edges)[:1 + LANES]
    queries = [KHOP % (hex(u), 4 + 3 * (i % 2), "count(uid)")
               for i, u in enumerate(roots)]
    _data(sharded, queries[0])      # the tile built, the program warm
    gate, calls = threading.Event(), []
    land0 = executor_mod._land_traversals

    def land(handle, n):
        calls.append(n)
        gate.wait(60)
        return land0(handle, n)

    monkeypatch.setattr(executor_mod, "_land_traversals", land)
    before = {n: _counter(n) for n in (
        "recurse_sharded_total", "recurse_sharded_lanes_total",
        'rendezvous_ahead_total{family="recurse"}')}
    got: dict = {}

    def one(i):
        try:
            got[i] = _data(sharded, queries[i])
        except BaseException as e:  # noqa: BLE001 -- asserted below
            got[i] = e

    threads = [threading.Thread(target=one, args=(i,))
               for i in range(len(queries))]
    threads[0].start()
    deadline = time.monotonic() + 60
    while not calls and time.monotonic() < deadline:
        time.sleep(0.001)
    for t in threads[1:]:
        t.start()
    while len(calls) < 2 and time.monotonic() < deadline:
        time.sleep(0.001)
    # launched while the first call's lander is still blocked for it
    assert calls == [1, LANES] and threads[0].is_alive()
    gate.set()
    for t in threads:
        t.join(60)
        assert not t.is_alive()
    for i, q in enumerate(queries):
        assert got[i] == _data(host, q), q
    assert _counter("recurse_sharded_total") \
        == before["recurse_sharded_total"] + 2
    assert _counter("recurse_sharded_lanes_total") \
        == before["recurse_sharded_lanes_total"] + 1 + LANES
    assert _counter('rendezvous_ahead_total{family="recurse"}') \
        == before['rendezvous_ahead_total{family="recurse"}'] + 1


def test_the_tile_is_counted_and_gauged_a_chip_at_a_time(
        rdf, mesh, every_bound_recurse_on_the_device):
    db = _db(rdf, prefer_device=True, device_min_edges=1, mesh=mesh)
    _data(db, KHOP % ("0x1", 4, "count(uid)"))
    tile = db.tablets["link"]._device_badj
    whole = bitgraph.resident_bytes(tile)
    assert _gauge('device_bitadj_shards{predicate="link"}') == CHIPS
    assert _gauge('device_bitadj_chip_bytes{predicate="link"}') \
        == whole / CHIPS
    assert _gauge('device_bitadj_bytes{predicate="link"}') == whole
    # the LRU counts what ONE chip holds, against a chip's budget
    assert db.device_cache.bytes == whole // CHIPS
    assert _gauge("device_cache_bytes") == whole // CHIPS


def test_the_span_the_call_and_explain_name_the_shards(
        engines, every_bound_recurse_on_the_device):
    _, sharded, _, _ = engines
    q = KHOP % ("0x1", 4, "count(uid)")
    body = json.loads(sharded.query_json(q, explain="analyze"))
    ex = body["extensions"]["explain"]
    assert ex["tiers"]["deviceShards"] == CHIPS
    stage = [s for s in ex["stages"] if s["stage"] == "recurse"]
    assert stage and stage[0]["tier"] == "device"
    assert stage[0]["shards"] == CHIPS
    assert stage[0]["program"] == "bfs_traverse_sharded"
    calls = [r for r in tracing.spans_for(ex["traceId"])
             if r["name"] == "device.call"]
    assert calls and calls[0]["args"]["program"] == "bfs_traverse_sharded"
    assert calls[0]["args"]["shards"] == CHIPS


def test_without_a_mesh_nothing_is_sharded(
        engines, every_bound_recurse_on_the_device):
    edges, _, lone, host = engines
    assert lone.mesh is None
    before = (_counter("recurse_sharded_total"),
              _counter("recurse_batch_total"),
              _counter("recurse_sharded_lanes_total"))
    programs = bitgraph.bfs_traverse_sharded._cache_size()
    q = KHOP % ("0x1", 7, "count(uid)")
    assert _data(lone, q) == _data(host, q)
    assert _counter("recurse_sharded_total") == before[0]
    assert _counter("recurse_batch_total") == before[1] + 1
    assert _counter("recurse_sharded_lanes_total") == before[2]
    assert bitgraph.bfs_traverse_sharded._cache_size() == programs
    tile = lone.tablets["link"]._device_badj
    assert tile.mesh is None and tile.shards == 1 and tile.shard_nbs is None
    # the program's name is what the one-chip cells' traces show
    lowered = bitgraph.bfs_traverse.lower(
        [b.in_nb for b in tile.gathered], tile.dense,
        np.zeros(2 * 8 + LANES, np.int32), n_slots=tile.n_slots,
        n_covered=tile.n_covered, lanes=LANES)
    assert "jit_bfs_traverse" in lowered.as_text()[:200] \
        and "sharded" not in lowered.as_text()[:200]
    body = json.loads(lone.query_json(q, explain="analyze"))
    assert "deviceShards" not in body["extensions"]["explain"]["tiers"]


def test_the_sharded_programs_name_is_its_own(graphs, mesh):
    _, four = _pair(graphs["small"], mesh, 40)
    lowered = bitgraph.bfs_traverse_sharded.lower(
        four.shard_nbs, four.dense, np.zeros(2 * 8 + LANES, np.int32),
        mesh=mesh, part_rows=bitgraph.shard_parts(four),
        n_slots=four.n_slots, lanes=LANES)
    text = lowered.as_text()
    assert "jit_bfs_traverse_sharded" in text[:200]
    # ONE collective a level: the all-gather of the chips' shares
    assert text.count("stablehlo.all_gather") == 1
    assert "all_reduce" not in text and "all_to_all" not in text \
        and "collective_permute" not in text


# -- the flag ------------------------------------------------------------


def test_alpha_chips_parses_and_defaults_to_one(monkeypatch):
    seen = []
    monkeypatch.setattr(cli, "cmd_alpha", lambda a: seen.append(a) or 0)
    assert cli.main(["alpha", "--chips", "4", "--port", "0"]) == 0
    assert cli.main(["alpha", "--port", "0"]) == 0
    assert [a.chips for a in seen] == [4, 1]


def test_alpha_chips_makes_one_uid_axis(mesh):
    got = cli._chips_mesh(CHIPS)
    assert got.axis_names == ("uid",) and got.shape["uid"] == CHIPS
    assert got == mesh


def test_alpha_with_more_chips_than_the_host_has_exits_at_once(monkeypatch):
    four = jax.devices()[:4]
    monkeypatch.setattr(jax, "devices", lambda *a, **kw: four)
    with pytest.raises(SystemExit) as e:
        cli.main(["alpha", "--chips", "8", "--port", "0"])
    assert e.value.code not in (0, None)
    assert "--chips 8" in str(e.value.code) \
        and "this host has 4" in str(e.value.code)
    with pytest.raises(SystemExit):
        cli.main(["alpha", "--chips", "0", "--port", "0"])
