"""The one-path `shortest` block served from the chip (PR 45):
`ops/bitgraph.bfs_paths` (the lanes' searches that end when every lane
has met its end, then the walk on the device), the host tier's form of
the same rule (`storage/tablet.least_path`), the executor's dispatch
site `_device_shortest` at a rendezvous of family `shortest`, the
gate's two sides (`planner.shortest_costs`) and the transposed tile
with hub rows. ONE defined path is the deployment's guarantee
(docs/deployment.md, "shortest"): every tier gives it, and the plain
reference of the benchmark's cell (benchmark/datasets/pokec_plain.py,
which imports nothing of the program) gives it too."""

import hashlib
import importlib.util
import json
import os
import threading
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dgraph_tpu.engine.db import GraphDB
from dgraph_tpu.ops import bitgraph
from dgraph_tpu.query.devicecall import Rendezvous
from dgraph_tpu.query.planner import shortest_costs
from dgraph_tpu.storage.tablet import least_path
from dgraph_tpu.utils import metrics, tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name):
    path = os.path.join(ROOT, "benchmark", "datasets", name + ".py")
    spec = importlib.util.spec_from_file_location("ts_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


pokec = _load("pokec")
plain = _load("pokec_plain")

SEEDS = (77, 2**31 + 9)
PROFILES = 1500
Q = "{ shortest(from: %#x, to: %#x, depth: %d) { friend } }"


class World:
    """A Pokec-shaped graph of 1,500 profiles (the generator's own
    draw, under its smallest scale): its edges as vertex indices
    (uid - 1), behind the dataset's interface for the plain
    reference, and its transposed tile as the served path holds it."""

    def __init__(self, seed):
        packed = pokec.drawn_edges(PROFILES, np.random.default_rng(seed))
        self.src, self.dst = packed >> 32, packed & 0xFFFFFFFF
        self.seed = seed
        self.stub = types.SimpleNamespace(
            FIRST_UID=1, graph=lambda scale, s: (
                self.src, self.dst, {"vertices": PROFILES}))
        self._tiles = {}

    def want(self, a, b, depth):
        """The plain reference's path, as uids."""
        if a == b:
            return [a]
        path = plain.least_path(self.stub, 0, {"seed": self.seed},
                                a - 1, b - 1, depth)
        return [v + 1 for v in path]

    def tile(self, hub):
        """The transposed tile: `hub` 0 no hub rows, 1 every class the
        rule allows, 0.5 half of those rows' bytes."""
        if hub not in self._tiles:
            order = np.lexsort((self.src, self.dst))
            ends, starts = np.unique(self.dst[order], return_index=True)
            badj = bitgraph.build_bitadjacency({
                int(v) + 1: (self.src[order][lo:hi] + 1).astype(np.uint32)
                for v, lo, hi in zip(ends, starts, np.append(
                    starts[1:], len(order)))})
            full = sum(int(b.in_nb.shape[0]) for b in badj.buckets) \
                * 4 * bitgraph.hub_row_words(badj.n_slots)
            bitgraph.attach_dense(badj, int(hub * full))
            bitgraph.attach_uids(badj)
            self._tiles[hub] = badj
        return self._tiles[hub]

    def quads(self):
        return "\n".join(f"<{s + 1:#x}> <friend> <{d + 1:#x}> ."
                         for s, d in zip(self.src.tolist(),
                                         self.dst.tolist()))


@pytest.fixture(scope="module")
def worlds():
    plain._KEPT.clear()
    return {seed: World(seed) for seed in SEEDS}


def _call(badj, pairs, **kw):
    """bfs_paths for [(from uid, to uid, depth)] -> (paths as uids,
    the call's own row)."""
    slots = []
    for a, b, d in pairs:
        s, hit = bitgraph._uid_slots(badj, np.asarray([a, b], np.uint32))
        assert hit.all()
        slots.append((int(s[0]), int(s[1]), d))
    out = np.asarray(bitgraph.paths(badj, slots, **kw))
    assert out.shape == (bitgraph.LANES + 1, 2 + bitgraph.path_width(
        max(d for _, _, d in pairs), badj.n_slots))
    return [bitgraph.path_uids(badj, out[i])
            for i in range(len(pairs))], out[-1]


# -- the program against the plain reference -----------------------------


@pytest.mark.parametrize("riders", (1, 3, 8))
@pytest.mark.parametrize("columns", (True, False),
                         ids=("first-by-columns", "first-by-rows"))
@pytest.mark.parametrize("hub", (0, 0.5, 1),
                         ids=("gathered", "half-hub", "hub"))
@pytest.mark.parametrize("seed", SEEDS)
def test_a_call_gives_every_rider_the_plain_references_path(
        worlds, seed, hub, columns, riders):
    w = worlds[seed]
    plain._KEPT.clear()
    badj = w.tile(hub)
    assert (badj.dense is None) == (hub == 0)
    assert bool(badj.gathered) == (hub != 1)
    rng = np.random.default_rng([seed % 1000, riders])
    found = 0
    for _ in range(3):
        pairs = [(int(rng.choice(w.src)) + 1, int(rng.choice(w.dst)) + 1,
                  int(rng.choice([2, 3, 4, 15]))) for _ in range(riders)]
        got, call = _call(badj, pairs, columns=columns)
        want = [w.want(*p) for p in pairs]
        assert got == want
        found += sum(map(bool, want))
        # the loop ran as many levels as its deepest lane needed, and
        # the first of them from columns where the caller says so
        assert call[0] <= max(d for _, _, d in pairs)
        assert call[3] == int(columns)
        hops = [len(p) - 1 for p in want if p]
        if hops and all(want):
            assert call[0] == max(hops)
    assert found


def _lattice():
    """A lattice with MANY tied paths: layer k holds uids 10 k + 1 ..
    10 k + 4, every vertex of a layer points at every vertex of the
    next; 0x1 .. 0x4 is layer 0. Then a tail 0x29 -> 0x33 -> 0x3d
    behind layer 4 (0x29 .. 0x2c), and a vertex nothing leads to."""
    edges = [(10 * k + i, 10 * (k + 1) + j) for k in range(4)
             for i in range(1, 5) for j in range(1, 5)]
    edges += [(41, 51), (51, 61), (70, 1)]
    return sorted(edges)


def _plain_path(edges, a, b, depth):
    src, dst = (np.asarray(x, np.int64) for x in zip(*edges))
    n = int(max(src.max(), dst.max())) + 2
    stub = types.SimpleNamespace(
        FIRST_UID=0, graph=lambda scale, s: (src, dst, {"vertices": n}))
    plain._KEPT.clear()
    if a == b:
        return [a]
    return plain.least_path(stub, 0, {"seed": 0}, a, b, depth)


# (from, to, depth, the path): ties take the least uid at every hop
SPECIAL = {
    "many-tied-paths": (1, 41, 15, [1, 11, 21, 31, 41]),
    "tied-and-not-the-least-source": (4, 44, 4, [4, 11, 21, 31, 44]),
    "from-is-to": (21, 21, 15, [21]),
    "exactly-depth": (1, 61, 6, [1, 11, 21, 31, 41, 51, 61]),
    "depth-plus-one": (1, 61, 5, []),
    "no-path-against-the-edges": (41, 1, 15, []),
    "nothing-leads-to-it": (1, 70, 15, []),
    "one-hop": (70, 1, 1, [70, 1]),
}


@pytest.mark.parametrize("case", SPECIAL)
def test_the_plain_reference_on_the_special_pairs(case):
    a, b, depth, want = SPECIAL[case]
    assert _plain_path(_lattice(), a, b, depth) == want


@pytest.fixture(scope="module")
def lattice_tiles():
    back = {}
    for u, v in _lattice():
        back.setdefault(v, []).append(u)
    tiles = {}
    for hub in (0, 1):
        badj = bitgraph.build_bitadjacency(
            {v: np.asarray(sorted(us), np.uint32) for v, us in back.items()})
        bitgraph.attach_dense(badj, hub << 30)
        bitgraph.attach_uids(badj)
        tiles[hub] = badj
    return tiles


@pytest.mark.parametrize("hub", (0, 1), ids=("gathered", "hub"))
@pytest.mark.parametrize("case", SPECIAL)
def test_the_program_on_the_special_pairs(lattice_tiles, case, hub):
    a, b, depth, want = SPECIAL[case]
    got, call = _call(lattice_tiles[hub], [(a, b, depth)])
    assert got == [want]
    # a lane that has met its source expands no further: the loop
    # ends at the path's length, not at the query's depth
    if want:
        assert call[0] == len(want) - 1
    assert call[0] <= depth


def test_all_special_pairs_ride_one_call_each_with_its_own_depth(
        lattice_tiles):
    cases = list(SPECIAL.values())
    got, call = _call(lattice_tiles[1], [c[:3] for c in cases])
    assert got == [c[3] for c in cases]
    assert call[0] == 6     # the deepest lane's, "exactly-depth"


def test_one_compiled_search_an_adjacency_for_every_batch_and_depth(
        worlds):
    w = worlds[SEEDS[0]]
    badj = w.tile(1)
    a, b = int(w.src[0]) + 1, int(w.dst[-1]) + 1
    _call(badj, [(a, b, 15)])
    size = bitgraph.bfs_paths._cache_size()
    for pairs in ([(a, b, 1)], [(a, b, 3), (b, a, 15)],
                  [(a, b, d) for d in range(1, 9)]):
        _call(badj, pairs)
    assert bitgraph.bfs_paths._cache_size() == size
    assert bitgraph.path_width(15, badj.n_slots) == 16
    assert bitgraph.path_width(16, badj.n_slots) == 32
    # never more columns than the adjacency has slots for
    assert bitgraph.path_width(10**9, 40) == 64


# -- the host tier's form ------------------------------------------------


def _engine(quads, schema="friend: [uid] .", rollup=True, **kw):
    db = GraphDB(**kw)
    db.alter(schema)
    db.mutate(set_nquads=quads, commit_now=True)
    if rollup:
        db.rollup_all()
    return db


@pytest.fixture(scope="module")
def engines(worlds):
    """(device tier forced, postings tier) over the first world."""
    quads = worlds[SEEDS[0]].quads()
    return (_engine(quads, prefer_device=True, device_min_edges=1),
            _engine(quads, prefer_device=False))


@pytest.mark.parametrize("depth", (2, 3, 4, 15))
@pytest.mark.parametrize("seed", SEEDS)
def test_least_path_gives_the_plain_references_path(worlds, seed, depth):
    w = worlds[seed]
    plain._KEPT.clear()
    tab = _engine(w.quads(), prefer_device=False).tablets["friend"]
    rng = np.random.default_rng(depth)
    found = 0
    for _ in range(40):
        a, b = (int(x) for x in rng.integers(1, PROFILES + 1, 2))
        want = w.want(a, b, depth)
        assert least_path(tab, a, b, depth, 1 << 60) == want
        found += bool(want)
    assert found


@pytest.mark.parametrize("schema", ("friend: [uid] .",
                                    "friend: [uid] @reverse ."),
                         ids=("plain", "reverse"))
@pytest.mark.parametrize("case", SPECIAL)
def test_least_path_on_the_special_pairs_clean_and_dirty(case, schema):
    a, b, depth, want = SPECIAL[case]
    edges = _lattice()
    quads = ["<%#x> <friend> <%#x> ." % e for e in edges]
    clean = _engine("\n".join(quads), schema, prefer_device=False)
    # the same graph with a third of it, and two edges that go again,
    # still in the overlay
    dirty = _engine("\n".join(quads[::3] + ["<0x1> <friend> <0x29> .",
                                            "<0x46> <friend> <0x3d> ."]),
                    schema, prefer_device=False)
    dirty.mutate(set_nquads="\n".join(quads), commit_now=True)
    dirty.mutate(del_nquads="<0x1> <friend> <0x29> .\n"
                 "<0x46> <friend> <0x3d> .", commit_now=True)
    assert dirty.tablets["friend"].dirty()
    for db in (clean, dirty):
        tab = db.tablets["friend"]
        assert least_path(tab, a, b, depth, 1 << 60) == want
    if "@reverse" in schema:
        # against the edges: the same rule over the reversed graph
        back = sorted((v, u) for u, v in edges)
        for db in (clean, dirty):
            assert least_path(db.tablets["friend"], b, a, depth, 1 << 60,
                              reverse=True) \
                == _plain_path(back, b, a, depth)


# -- the served query: every tier, byte for byte -------------------------


def _served(db, a, b, depth):
    return json.loads(db.query_json(Q % (a, b, depth)))


def _uids(data):
    out, node = [], (data.get("_path_") or [None])[0]
    while node is not None:
        out.append(int(node["uid"], 16))
        node = node.get("friend")
    return out


@pytest.mark.parametrize("depth", (2, 3, 15))
def test_device_tier_postings_tier_and_plain_reference_agree(
        worlds, engines, depth):
    w = worlds[SEEDS[0]]
    plain._KEPT.clear()
    dev, host = engines
    rng = np.random.default_rng(depth)
    before = metrics.counters_snapshot()
    found = 0
    for _ in range(16):
        a, b = (int(x) for x in rng.integers(1, PROFILES + 1, 2))
        want = plain.ANSWERS["shortest15"](
            w.stub, 0, {"seed": w.seed}, Q % (a, b, depth))
        got = [_served(db, a, b, depth)["data"] for db in (dev, host)]
        assert got[0] == got[1] == want
        assert json.dumps(got[0]) == json.dumps(got[1])
        assert _uids(got[0]) == w.want(a, b, depth)
        found += bool(want["_path_"])
    assert found
    moved = metrics.counters_delta(before)
    assert moved['shortest_tier_total{tier="device"}'] >= found
    assert moved['shortest_tier_total{tier="host"}'] >= 16


@pytest.mark.parametrize("case", SPECIAL)
def test_the_served_special_pairs_from_both_tiers(case):
    a, b, depth, want = SPECIAL[case]
    quads = "\n".join("<%#x> <friend> <%#x> ." % e for e in _lattice())
    replies = [
        _served(_engine(quads, prefer_device=dev, device_min_edges=1),
                a, b, depth)["data"] for dev in (True, False)]
    assert replies[0] == replies[1]
    assert _uids(replies[0]) == want
    if want:
        assert replies[0]["_path_"][0]["_weight_"] == float(len(want) - 1)
    else:
        assert replies[0] == {"_path_": []}


def test_the_reverse_predicate_is_the_same_rule_over_the_reversed_graph():
    edges = _lattice()
    quads = "\n".join("<%#x> <friend> <%#x> ." % e for e in edges)
    back = sorted((v, u) for u, v in edges)
    dbs = [_engine(quads, "friend: [uid] @reverse .", prefer_device=dev,
                   device_min_edges=1) for dev in (True, False)]
    before = metrics.counters_snapshot()
    for a, b, depth in ((41, 1, 15), (61, 4, 6), (61, 4, 5), (1, 41, 15)):
        q = "{ shortest(from: %#x, to: %#x, depth: %d) { ~friend } }" \
            % (a, b, depth)
        got = [json.loads(db.query_json(q))["data"] for db in dbs]
        assert got[0] == got[1]
        out, node = [], (got[0]["_path_"] or [None])[0]
        while node is not None:
            out.append(int(node["uid"], 16))
            node = node.get("~friend")
        assert out == _plain_path(back, a, b, depth)
    assert metrics.counters_delta(before)["query_device_shortest_total"] == 4


GENERAL = {
    # weighted: the lighter path of three hops beats the two-hop one
    "weighted": ("{ shortest(from: 0x1, to: 0x4) { road @facets(km) } }",
                 [[1, 2, 3, 4]], [3.0]),
    "numpaths-2": ("{ shortest(from: 0x1, to: 0x4, numpaths: 2) "
                   "{ road } }", [[1, 5, 4], [1, 2, 3, 4]], [2.0, 3.0]),
    "two-predicates": ("{ shortest(from: 0x1, to: 0x6) { road rail } }",
                       [[1, 5, 6]], [2.0]),
    "weight-window": ("{ shortest(from: 0x1, to: 0x4, minweight: 3) "
                      "{ road } }", [[1, 2, 3, 4]], [3.0]),
}


@pytest.mark.parametrize("case", GENERAL)
def test_the_general_searches_stay_the_hosts_and_keep_their_answers(case):
    """Weighted, k-shortest, several-predicate and weight-window
    searches are outside the defined path: the host's Dijkstra
    answers them as before, from an engine with the device tier
    forced too, and no `shortest` call reaches the device."""
    query, paths, weights = GENERAL[case]
    schema = "road: [uid] .\nrail: [uid] ."
    quads = """
      <0x1> <road> <0x2> (km=1) .
      <0x2> <road> <0x3> (km=1) .
      <0x3> <road> <0x4> (km=1) .
      <0x1> <road> <0x5> (km=9) .
      <0x5> <road> <0x4> (km=9) .
      <0x5> <rail> <0x6> .
    """
    before = metrics.counters_snapshot()
    for dev in (True, False):
        db = _engine(quads, schema, prefer_device=dev, device_min_edges=1)
        got = json.loads(db.query_json(query))["data"]["_path_"]
        assert len(got) == len(paths)
        for node, path, weight in zip(got, paths, weights):
            assert node["_weight_"] == weight
            seen = []
            while node is not None:
                seen.append(int(node["uid"], 16))
                node = node.get("road") or node.get("rail")
            assert seen == path
    moved = metrics.counters_delta(before)
    assert moved.get("query_device_shortest_total", 0) == 0
    assert moved.get('shortest_tier_total{tier="device"}', 0) == 0
    assert moved['shortest_tier_total{tier="host"}'] == 2


def test_a_path_variable_reads_the_path_in_its_order(engines, worlds):
    w = worlds[SEEDS[0]]
    plain._KEPT.clear()
    a, b = int(w.src[5]) + 1, int(w.dst[-7]) + 1
    q = ("{ p as shortest(from: %#x, to: %#x, depth: 15) { friend } "
         "on(func: uid(p)) { uid } }" % (a, b))
    got = [json.loads(db.query_json(q))["data"] for db in engines]
    assert got[0] == got[1]
    assert [int(n["uid"], 16) for n in got[0]["on"]] == w.want(a, b, 15)


# -- the dispatch site: span, counters, the request's roll-up --------------


def test_a_served_block_is_spanned_counted_and_rolled_up(engines, worlds):
    w = worlds[SEEDS[0]]
    plain._KEPT.clear()
    dev, _ = engines
    a, b = int(w.src[11]) + 1, int(w.dst[-3]) + 1
    want = w.want(a, b, 15)
    assert len(want) > 2
    _served(dev, a, b, 15)      # the tile, the program
    tracing.clear()
    before = metrics.counters_snapshot()
    out = _served(dev, a, b, 15)
    moved = metrics.counters_delta(before)
    assert _uids(out["data"]) == want
    (sp,) = [s for s in tracing.recent_spans() if s["name"] == "shortest"]
    assert sp["args"]["tier"] == "device" and sp["args"]["lanes"] == 1
    assert sp["args"]["program"] == "bfs_paths"
    assert sp["args"]["levels"] == len(want) - 1 == sp["args"]["hops"]
    assert sp["args"]["depth"] == 15
    (call,) = [s for s in tracing.recent_spans()
               if s["name"] == "device.call"]
    assert call["args"]["family"] == "shortest"
    assert call["parent_id"] == sp["span_id"]
    (flight,) = [s for s in tracing.recent_spans()
                 if s["name"] == "device.flight"]
    assert flight["args"]["family"] == "shortest"
    assert call["args"]["flight"] == flight["span_id"]
    width = bitgraph.path_width(15, dev.tablets["friend"]._device_badj_t
                                .n_slots)
    assert moved == pytest.approx({
        'shortest_tier_total{tier="device"}': 1,
        "query_device_shortest_total": 1, "shortest_calls_total": 1,
        "shortest_riders_total": 1,
        "shortest_levels_run_total": len(want) - 1,
        "shortest_fetch_bytes_total": 4 * (bitgraph.LANES + 1) * (2 + width),
    } | {k: v for k, v in moved.items() if k not in (
        'shortest_tier_total{tier="device"}', "query_device_shortest_total",
        "shortest_calls_total", "shortest_riders_total",
        "shortest_levels_run_total", "shortest_fetch_bytes_total")})
    for name in ("shortest_ns_total", "shortest_rows_tiles_total",
                 'device_call_ns_total{family="shortest",phase="wait"}',
                 'rendezvous_ns_total{family="shortest",phase="land"}'):
        assert moved[name] > 0
    assert moved.get('rendezvous_ahead_total{family="shortest"}', 0) == 0
    # the span's time holds the call's: what is left is the host's
    inside = sum(v for k, v in moved.items()
                 if k.startswith('device_call_ns_total{family="shortest"'))
    assert 0 < inside < moved["shortest_ns_total"]
    sl = out["extensions"]["server_latency"]
    assert sl["device_calls"] == 1 and sl["device_wait_ns"] > 0
    # none of it reads as the k-hop family's
    assert not any("recurse" in k for k in moved)


def test_explain_analyze_lists_the_shortest_stage(engines, worlds):
    w = worlds[SEEDS[0]]
    a, b = int(w.src[11]) + 1, int(w.dst[-3]) + 1
    for db, tier in zip(engines, ("device", "host")):
        out = json.loads(db.query_json(Q % (a, b, 15), explain="analyze"))
        (stage,) = [s for s in out["extensions"]["explain"]["stages"]
                    if s["stage"] == "shortest"]
        assert stage["tier"] == tier and stage["durUs"] > 0
        if tier == "device":
            assert stage["program"] == "bfs_paths"
            assert stage["lanes"] == 1 and stage["levels"] >= 1
        else:
            assert "program" not in stage and "lanes" not in stage


def test_the_transposed_tile_is_counted_evictable_and_gauged(worlds):
    w = worlds[SEEDS[1]]
    plain._KEPT.clear()
    db = _engine(w.quads(), prefer_device=True, device_min_edges=1)
    a, b = int(w.src[3]) + 1, int(w.dst[-9]) + 1
    assert _uids(_served(db, a, b, 15)["data"]) == w.want(a, b, 15)
    tab = db.tablets["friend"]
    badj = tab._device_badj_t
    assert badj.dense is not None and badj.uids_dev is not None
    # the k-hop family's tile of the same predicate is another one
    assert getattr(tab, "_device_badj", None) is None
    g = metrics.gauges_snapshot()
    label = '{predicate="~friend"}'
    assert g["device_bitadj_edges" + label] == len(w.src)
    assert g["device_bitadj_hub_rows" + label] == badj.dense_rows > 0
    assert g["device_bitadj_bytes" + label] \
        == bitgraph.resident_bytes(badj) \
        >= badj.dense.nbytes + 4 * badj.n_slots
    assert db.device_cache.bytes >= bitgraph.resident_bytes(badj)
    with db.device_cache._lock:
        db.device_cache._evict_lru()
    assert tab._device_badj_t is None
    g = metrics.gauges_snapshot()
    assert g["device_bitadj_bytes" + label] == 0
    assert g["device_bitadj_hub_rows" + label] == 0
    # built again on the next asking, the same answer
    assert _uids(_served(db, a, b, 15)["data"]) == w.want(a, b, 15)


def test_an_engine_whose_mesh_splits_the_predicate_answers_on_the_host(
        worlds):
    from dgraph_tpu.parallel.mesh import make_mesh
    w = worlds[SEEDS[0]]
    plain._KEPT.clear()
    db = _engine(w.quads(), prefer_device=True, device_min_edges=1,
                 mesh=make_mesh(4, axes=("uid",)))
    a, b = int(w.src[11]) + 1, int(w.dst[-3]) + 1
    before = metrics.counters_snapshot()
    assert _uids(_served(db, a, b, 15)["data"]) == w.want(a, b, 15)
    moved = metrics.counters_delta(before)
    assert moved.get("query_device_shortest_total", 0) == 0
    assert moved['shortest_tier_total{tier="host"}'] == 1


# -- the gate's two sides -------------------------------------------------


def test_the_gate_keeps_the_host_on_a_cpu_and_a_forced_engine_dispatches(
        worlds):
    w = worlds[SEEDS[0]]
    plain._KEPT.clear()
    a, b = int(w.src[11]) + 1, int(w.dst[-3]) + 1
    db = _engine(w.quads(), prefer_device=True)     # default gate
    before = metrics.counters_snapshot()
    assert _uids(_served(db, a, b, 15)["data"]) == w.want(a, b, 15)
    moved = metrics.counters_delta(before)
    assert moved.get("query_device_shortest_total", 0) == 0
    assert getattr(db.tablets["friend"], "_device_badj_t", None) is None


COSTS = {
    # (depth, rows, edges, sum_sq): the cell at scale 10, counted on
    # the CPU from seed 3700004501; the source's own size; one hop
    "the-cell": ((15, 152_787, 3_061_237, 322_862_000), (0.02, 0.08), 4),
    "the-source": ((15, 1_632_803, 30_622_564, 4_600_000_000),
                   (0.3, 2.0), 4),
    "one-hop-allowed": ((1, 152_787, 3_061_237, 322_862_000),
                        (0.0, 1e-5), 1),
    "no-edges": ((15, 0, 0, 0), (0.0, 0.0), 0),
}


@pytest.mark.parametrize("case", COSTS)
def test_the_gates_reckoning(case):
    args, (lo, hi), levels = COSTS[case]
    host, got = shortest_costs(*args)
    assert lo <= host <= hi and got == levels


def test_the_cell_clears_the_gate_by_the_reckoning_alone():
    """At the cell's shapes the host's search costs several device
    calls' worth: the margin over ONE dispatch round-trip (0.7 ms on
    the chip's host: PERF.md) is tens of milliseconds, so no seed's
    moments put the block on the fence."""
    host, levels = shortest_costs(15, 152_787, 3_061_237, 322_862_000)
    # the cell's tile: 111,421 padded out-edges gathered, 2.13 GB rows
    device = levels * bitgraph._streamed_seconds(111_421, 2_130_345_984)
    assert host - device > 10 * 1.25 * 0.0007


# -- two families at the rendezvous -----------------------------------------


def test_a_tile_has_a_rendezvous_a_family_and_they_never_share_a_call():
    class Tile:
        pass

    tile = Tile()
    meets = {f: Rendezvous.at(tile, 8, family=f)
             for f in ("recurse", "shortest")}
    assert meets["recurse"] is not meets["shortest"]
    assert Rendezvous.at(tile, 8, family="shortest") is meets["shortest"]
    launched, gate = [], threading.Event()

    def launch(family):
        def go(items):
            launched.append((family, list(items)))
            return items
        return go

    def land(handle, n):
        gate.wait(10)
        return list(handle)

    got = {}

    def rider(family, i):
        got[family, i] = meets[family].ride(
            (family, i), launch(family), land).result

    threads = [threading.Thread(target=rider, args=(f, i))
               for i in range(5) for f in ("recurse", "shortest")]
    for t in threads:
        t.start()
    # each family's first rider flies alone; the others of BOTH wait,
    # each behind its own family's call
    for _ in range(200):
        if sum(len(m._waiting) for m in meets.values()) == 8:
            break
        threading.Event().wait(0.01)
    assert sorted(f for f, _ in launched) == ["recurse", "shortest"]
    gate.set()
    for t in threads:
        t.join(20)
    assert got == {k: k for k in got} and len(got) == 10
    for family, items in launched:
        assert {f for f, _ in items} == {family}
    assert sorted(len(items) for _, items in launched) == [1, 1, 4, 4]


def test_eight_blocks_in_flight_ride_one_call(engines, worlds, monkeypatch):
    """Behind a call in flight the next eight pairs make ONE call."""
    from dgraph_tpu.query import executor as executor_mod
    w = worlds[SEEDS[0]]
    plain._KEPT.clear()
    dev, _ = engines
    rng = np.random.default_rng(8)
    pairs = [(int(rng.choice(w.src)) + 1, int(rng.choice(w.dst)) + 1)
             for _ in range(9)]
    _served(dev, *pairs[0], 15)
    hold, real = threading.Event(), executor_mod._land_paths

    def slow_land(handle, n):
        hold.wait(20)
        return real(handle, n)

    monkeypatch.setattr(executor_mod, "_land_paths", slow_land)
    before = metrics.counters_snapshot()
    out = {}

    def ask(i):
        out[i] = _uids(_served(dev, *pairs[i], 15)["data"])

    threads = [threading.Thread(target=ask, args=(i,)) for i in range(9)]
    threads[0].start()
    meet = Rendezvous.at(dev.tablets["friend"]._device_badj_t,
                         bitgraph.LANES, family="shortest")
    for _ in range(500):
        if meet._flight is not None and meet._flight.launched:
            break
        threading.Event().wait(0.01)
    for t in threads[1:]:
        t.start()
    for _ in range(500):
        if meet._behind is not None and meet._behind.launched:
            break
        threading.Event().wait(0.01)
    hold.set()
    for t in threads:
        t.join(60)
    assert out == {i: w.want(*pairs[i], 15) for i in range(9)}
    moved = metrics.counters_delta(before)
    assert moved["shortest_calls_total"] == 2
    assert moved["shortest_riders_total"] == 9
    # the full call went behind the one in flight
    assert moved['rendezvous_ahead_total{family="shortest"}'] == 1


# -- the k-hop programs are the programs they were -------------------------


def _lowered(fn, *args, **kw):
    return hashlib.sha256(
        fn.lower(*args, **kw).as_text().encode()).hexdigest()[:16]


def _spec(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


# sha256 of the lowered text at the parent commit (326bc86), taken
# with this very function at these shapes before this PR touched
# ops/bitgraph.py
LOWERED_AT_THE_PARENT = {
    "bfs_traverse-hub": "9579a62a0089acd6",
    "bfs_traverse-gathered": "2be0ecc108fffe65",
    "bfs_traverse_sharded": "f1ae178d54fc6a42",
}


@pytest.mark.parametrize("program", LOWERED_AT_THE_PARENT)
def test_the_khop_programs_trace_to_what_they_were(program):
    lanes, n, rows = bitgraph.LANES, 200, 48
    gathered = [(40, 1), (24, 2), (16, 3)]
    words = bitgraph.hub_row_words(n)
    covered = rows + sum(m for m, _ in gathered)
    riders = _spec((2 * 8 + lanes,), jnp.int32)
    if program == "bfs_traverse_sharded":
        from jax.sharding import Mesh
        mesh = Mesh(np.asarray(jax.devices()[:4]), (bitgraph.SHARD_AXIS,))
        held = [-(-m // 4) for m, _ in gathered]
        chip_rows = bitgraph._chip_rows(rows, 4)
        got = _lowered(
            bitgraph.bfs_traverse_sharded,
            [_spec((4 * h, d), jnp.int32)
             for h, (_, d) in zip(held, gathered)],
            _spec((4 * chip_rows, words), jnp.uint32), riders, mesh=mesh,
            part_rows=tuple((m, h) for (m, _), h in zip(gathered, held))
            + ((rows, chip_rows),), n_slots=n, lanes=lanes)
    else:
        dense = program.endswith("hub")
        got = _lowered(
            bitgraph.bfs_traverse,
            [_spec(g, jnp.int32) for g in gathered],
            _spec((rows, words), jnp.uint32) if dense else None, riders,
            n_slots=n, n_covered=covered if dense else covered - rows,
            lanes=lanes)
    assert got == LOWERED_AT_THE_PARENT[program]
