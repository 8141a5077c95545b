"""A bound `@recurse` (a `var` block whose children are bare uid
predicates, `loop: false`) on the normal path: level-at-a-time on the
host tier, ONE device traversal on the device tier, one answer in
both, and that answer the general path's and the plain reference's
(benchmark/datasets/graph500_plain.py, which imports nothing of the
program) on seeded Graph500 graphs.
"""

import importlib.util
import json
import os

import numpy as np
import pytest

from dgraph_tpu.engine.db import GraphDB
from dgraph_tpu.ingest.bulk import bulk_load
from dgraph_tpu.query import executor as executor_mod
from dgraph_tpu.query.planner import recurse_costs
from dgraph_tpu.utils import metrics, tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name):
    path = os.path.join(ROOT, "benchmark", "datasets", name + ".py")
    spec = importlib.util.spec_from_file_location("t_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


graph500 = _load("graph500")
plain = _load("graph500_plain")
# the program beside the dataset module is this one: no probe child
graph500._CACHE["program"] = graph500.PROGRAM_ROOT

GRAPHS = [(8, 2**31 + 5), (9, 31_000_017), (10, 7)]
KHOP = ("{ var(func: uid(%#x)) @recurse(depth: %d, loop: false) "
        "{ n as link } khop(func: uid(n)) { count(uid) } }")
UIDS = ("{ var(func: uid(%#x)) @recurse(depth: %d, loop: false) "
        "{ n as link } khop(func: uid(n)) { uid } }")


def _db(tmp, scale, seed, schema=graph500.SCHEMA, **kw):
    path = os.path.join(tmp, f"g{scale}-{seed}.rdf")
    if not os.path.exists(path):
        with open(path, "w") as f:
            graph500.write_rdf(f, scale, seed)
    return bulk_load([path], schema=schema, db=GraphDB(**kw))


@pytest.fixture(scope="module")
def worlds(tmp_path_factory):
    """(scale, facts, device-tier db, host-tier db) a seeded graph."""
    tmp = str(tmp_path_factory.mktemp("graph500"))
    out = {}
    for scale, seed in GRAPHS:
        _, _, roots, vertices = graph500.graph(scale, seed)
        out[scale, seed] = (
            {"seed": seed, "roots": roots, "vertices": vertices},
            _db(tmp, scale, seed, prefer_device=True, device_min_edges=1),
            _db(tmp, scale, seed, prefer_device=False))
    return out


def _counter(name):
    return sum(v for k, v in metrics.snapshot()["counters"].items()
               if k.startswith(name))


def _data(db, q):
    """The bytes of `data` as the server would send them."""
    body = db.query_json(q)
    return body[len('{"data":'):body.rfind(',"extensions":')]


def _roots(facts, scale):
    """Three roots of the usable range and one vertex that only has
    in-edges (a root with no out-edge)."""
    rng = np.random.default_rng(scale)
    first = graph500.FIRST_UID
    return [first + int(i) for i in rng.integers(0, facts["roots"], 3)] \
        + [first + facts["vertices"] - 1]


@pytest.mark.parametrize("depth", range(1, 8))
@pytest.mark.parametrize("graph", GRAPHS, ids=lambda g: f"scale{g[0]}")
def test_count_is_the_plain_references_in_both_tiers(worlds, graph, depth):
    facts, dev, host = worlds[graph]
    for root in _roots(facts, graph[0]):
        q = KHOP % (root, depth)
        want = plain.khop(graph500, graph[0], facts, q)
        before = _counter("query_device_recurse_total")
        got = _data(dev, q)
        assert json.loads(got) == want, (root, depth)
        assert _counter("query_device_recurse_total") == before + 1
        assert _data(host, q) == got
        # the uids themselves, where a later block reads them
        q = UIDS % (root, depth)
        assert _data(dev, q) == _data(host, q)
        assert len(json.loads(_data(dev, q))["khop"]) \
            == want["khop"][0]["count"]


def _general_path(monkeypatch):
    """Send every @recurse down the general (parent -> children) path,
    as the program before this change ran all of them."""
    monkeypatch.setattr(executor_mod.Executor, "_recurse_bound",
                        lambda self, gq: None)


SHAPES = {
    "bound": "{ var(func: uid(%(r)#x)) @recurse(depth: 4, loop: false) "
             "{ n as link } q(func: uid(n)) { count(uid) } }",
    "bound-default-loop": "{ var(func: uid(%(r)#x)) @recurse(depth: 5) "
                          "{ n as link } q(func: uid(n)) { uid } }",
    "bound-no-depth": "{ var(func: uid(%(r)#x)) @recurse "
                      "{ n as link } q(func: uid(n)) { count(uid) } }",
    "bound-two-roots": "{ var(func: uid(%(r)#x, %(r2)#x)) "
                       "@recurse(depth: 3, loop: false) { n as link } "
                       "q(func: uid(n)) { count(uid) } }",
    "bound-reverse": "{ var(func: uid(%(r)#x)) @recurse(depth: 4) "
                     "{ n as ~link } q(func: uid(n)) { count(uid) } }",
    "bound-two-children": "{ var(func: uid(%(r)#x)) @recurse(depth: 3) "
                          "{ a as link b as ~link } "
                          "x(func: uid(a)) { count(uid) } "
                          "y(func: uid(b)) { count(uid) } }",
    "bound-read-twice": "{ var(func: uid(%(r)#x)) @recurse(depth: 3) "
                        "{ n as link } a(func: uid(n)) { count(uid) } "
                        "b(func: uid(n), first: 3) { uid } }",
    "bound-filtered-reader": "{ var(func: uid(%(r)#x)) @recurse(depth: 3) "
                             "{ n as link } q(func: uid(n)) "
                             "@filter(uid(%(r2)#x, %(r)#x)) { count(uid) } }",
    "loop-true": "{ var(func: uid(%(r)#x)) @recurse(depth: 4, loop: true) "
                 "{ n as link } q(func: uid(n)) { count(uid) } }",
    "filtered-child": "{ var(func: uid(%(r)#x)) @recurse(depth: 4) "
                      "{ n as link @filter(uid(%(r2)#x, %(r)#x)) } "
                      "q(func: uid(n)) { count(uid) } }",
    "nested-output": "{ q(func: uid(%(r)#x)) @recurse(depth: 3) "
                     "{ uid link } }",
    "uid-child-var": "{ var(func: uid(%(r)#x)) @recurse(depth: 3) "
                     "{ v as uid link } q(func: uid(v)) { count(uid) } }",
}


REVERSE_SHAPES = ["bound-reverse", "bound-two-children"]   # need @reverse


@pytest.mark.parametrize("shape", sorted(set(SHAPES) - set(REVERSE_SHAPES)))
def test_every_shape_answers_as_the_general_path_does(worlds, shape,
                                                      monkeypatch):
    facts, dev, host = worlds[9, 31_000_017]
    r, r2 = _roots(facts, 9)[:2]
    q = SHAPES[shape] % {"r": r, "r2": r2}
    got_dev, got_host = _data(dev, q), _data(host, q)
    _general_path(monkeypatch)
    want = _data(host, q)
    assert got_dev == want and got_host == want
    assert want != "{}"


@pytest.mark.parametrize("shape", REVERSE_SHAPES)
def test_reverse_children_answer_as_the_general_path_does(
        tmp_path, shape, monkeypatch):
    scale, seed = 8, 99
    kw = dict(schema="link: [uid] @reverse .")
    dev = _db(str(tmp_path), scale, seed, prefer_device=True,
              device_min_edges=1, **kw)
    host = _db(str(tmp_path), scale, seed, prefer_device=False, **kw)
    q = SHAPES[shape] % {"r": 3, "r2": 9}
    before = _counter("query_device_recurse_total")
    got_dev, got_host = _data(dev, q), _data(host, q)
    # one child is the device's; two are the host tier's
    assert _counter("query_device_recurse_total") - before \
        == (1 if shape == "bound-reverse" else 0)
    _general_path(monkeypatch)
    assert got_dev == got_host == _data(host, q)


def _small(**kw):
    db = GraphDB(**kw)
    db.alter("link: [uid] .")
    rng = np.random.default_rng(3)
    db.mutate(set_nquads="\n".join(
        f"<{u}> <link> <{d}> ." for u in range(1, 80)
        for d in np.unique(rng.integers(1, 90, 3)) if d != u))
    return db


FALLBACKS = {
    # a live overlay: the tile cannot speak for the tablet
    "dirty-tablet": (lambda db: db.mutate(
        set_nquads="<5> <link> <200> .\n<200> <link> <201> ."), 5),
    # a uid no edge touches
    "unknown-root": (None, 5000),
    # a uid the 32-bit tiles cannot hold
    "root-over-32-bits": (None, (1 << 33) + 7),
}


@pytest.mark.parametrize("case", sorted(FALLBACKS))
def test_what_the_device_cannot_speak_for_falls_to_the_host_tier(
        case, monkeypatch):
    dev = _small(prefer_device=True, device_min_edges=1)
    host = _small(prefer_device=False)
    # the tile is there and a plain traversal takes it
    before = _counter("query_device_recurse_total")
    assert _data(dev, KHOP % (1, 4)) == _data(host, KHOP % (1, 4))
    assert _counter("query_device_recurse_total") == before + 1
    prepare, root = FALLBACKS[case]
    if prepare is not None:
        prepare(dev)
        prepare(host)
        dev.rollup_in_read = host.rollup_in_read = False
    before = _counter("query_device_recurse_total")
    tiers = _counter('recurse_tier_total{tier="host"}')
    got = [_data(dev, q % (root, 5)) for q in (KHOP, UIDS)]
    assert _counter("query_device_recurse_total") == before
    assert _counter('recurse_tier_total{tier="host"}') == tiers + 2
    assert got == [_data(host, q % (root, 5)) for q in (KHOP, UIDS)]
    _general_path(monkeypatch)
    assert got == [_data(host, q % (root, 5)) for q in (KHOP, UIDS)]
    if case == "dirty-tablet":
        assert json.loads(got[0])["khop"][0]["count"] > 2


def test_an_upserts_mutation_still_gets_the_uids():
    """The variable of a bound @recurse read by `count(uid)` alone
    keeps its uids on the device in a served query; an upsert's
    mutation reads the variable off the executor, so there the uids
    come back."""
    out = []
    for kw in (dict(prefer_device=True, device_min_edges=1),
               dict(prefer_device=False)):
        db = _small(**kw)
        db.alter("link: [uid] .\nseen: bool .")
        before = _counter("query_device_recurse_total")
        db.mutate(
            query="{ var(func: uid(1)) @recurse(depth: 3) { n as link } "
                  "c(func: uid(n)) { count(uid) } }",
            set_nquads='uid(n) <seen> "true" .')
        out.append((_counter("query_device_recurse_total") - before,
                    _data(db, "{ q(func: has(seen)) { uid } }"),
                    _data(db, KHOP % (1, 3))))
    assert out[0][0] == 1 and out[1][0] == 0
    assert out[0][1:] == out[1][1:]
    assert len(json.loads(out[0][1])["q"]) \
        == json.loads(out[0][2])["khop"][0]["count"] > 0


def test_an_empty_root_set_defines_the_variable(worlds):
    _, dev, host = worlds[8, 2**31 + 5]
    q = ("{ r as var(func: uid(0x7fffff)) @filter(has(link)) "
         "var(func: uid(r)) @recurse(depth: 4) { n as link } "
         "khop(func: uid(n)) { count(uid) } }")
    assert _data(dev, q) == _data(host, q) == '{"khop":[{"count":0}]}'


def test_a_bound_recurse_is_one_device_program_and_one_call(worlds):
    facts, dev, _ = worlds[10, 7]
    from dgraph_tpu.ops import bitgraph
    root = _roots(facts, 10)[0]
    _data(dev, KHOP % (root, 3))          # tile and program are there
    programs = bitgraph.bfs_traverse._cache_size()
    tracing.clear()
    metrics.reset()
    sl = json.loads(dev.query_json(KHOP % (root, 7)))[
        "extensions"]["server_latency"]
    assert sl["device_calls"] == 1
    counters = metrics.snapshot()["counters"]
    assert {k: v for k, v in counters.items()
            if k.startswith(("query_device_", "query_fused_"))} \
        == {"query_device_recurse_total": 1}
    assert counters['recurse_tier_total{tier="device"}'] == 1
    calls = [s for s in tracing.recent_spans() if s["name"] == "device.call"]
    assert [(s["args"]["family"], s["args"]["program"]) for s in calls] \
        == [("recurse", "bfs_traverse")]
    # two scalars left the device, not a bitmap: count(uid) reads the
    # variable's size alone
    assert calls[0]["args"]["out_bytes"] == 8
    (sp,) = [s for s in tracing.recent_spans() if s["name"] == "recurse"]
    assert sp["args"]["tier"] == "device" and sp["args"]["depth"] == 7
    assert sp["args"]["roots"] == 1 and 1 <= sp["args"]["levels_run"] <= 6
    assert sp["args"]["reached"] == json.loads(
        _data(dev, KHOP % (root, 7)))["khop"][0]["count"]
    assert calls[0]["parent_id"] == sp["span_id"]
    assert counters["recurse_ns_total"] >= sum(
        v for k, v in counters.items()
        if k.startswith('device_call_ns_total{family="recurse"'))
    # depth is a runtime value: another depth is the same program
    _data(dev, KHOP % (root, 5))
    assert bitgraph.bfs_traverse._cache_size() == programs
    # where a later block reads the uids, the lanes' reached words
    # come too: the SAME program (it keeps them on the device for
    # whoever asks), still one call
    tracing.clear()
    _data(dev, UIDS % (root, 7))
    (call,) = [s for s in tracing.recent_spans()
               if s["name"] == "device.call"]
    assert call["args"]["out_bytes"] == 8 + 4 * facts["vertices"]
    assert bitgraph.bfs_traverse._cache_size() == programs


def test_the_host_tier_has_its_span_and_counters(worlds):
    facts, _, host = worlds[10, 7]
    root = _roots(facts, 10)[0]
    tracing.clear()
    metrics.reset()
    want = json.loads(_data(host, KHOP % (root, 7)))["khop"][0]["count"]
    (sp,) = [s for s in tracing.recent_spans() if s["name"] == "recurse"]
    assert sp["args"]["tier"] == "host" and sp["args"]["reached"] == want
    assert 1 <= sp["args"]["levels_run"] <= 6
    counters = metrics.snapshot()["counters"]
    assert counters['recurse_tier_total{tier="host"}'] == 1
    assert counters["recurse_ns_total"] > 0
    assert "query_device_recurse_total" not in counters


def test_the_adjacency_is_a_counted_tile_with_gauges(worlds):
    facts, dev, _ = worlds[9, 31_000_017]

    def evict_all():
        with dev.device_cache._lock:
            while dev.device_cache._entries:
                dev.device_cache._evict_lru()

    evict_all()     # an earlier test's reset() wiped the gauges
    _data(dev, KHOP % (_roots(facts, 9)[0], 4))
    tab = dev.tablets["link"]
    badj = tab._device_badj
    gauges = metrics.snapshot()["gauges"]
    assert badj.dense is not None     # the traversal's hub rows too
    nbytes = sum(b.in_nb.nbytes for b in badj.buckets) + badj.dense.nbytes
    assert gauges['device_bitadj_bytes{predicate="link"}'] == nbytes
    assert gauges['device_bitadj_edges{predicate="link"}'] \
        == badj.n_edges == tab.edge_count()
    assert dev.device_cache.bytes >= nbytes
    # evicted: the tablet lets go of it and the gauges read 0
    evict_all()
    assert getattr(tab, "_device_badj", None) is None
    gauges = metrics.snapshot()["gauges"]
    assert gauges['device_bitadj_bytes{predicate="link"}'] == 0
    assert gauges['device_bitadj_edges{predicate="link"}'] == 0
    # and the next traversal builds it again
    _data(dev, KHOP % (_roots(facts, 9)[0], 4))
    assert metrics.snapshot()["gauges"][
        'device_bitadj_bytes{predicate="link"}'] == nbytes


# -- the gate: from what the plan knows, before it runs -----------------

DISPATCH_S = 0.65e-3      # one v5e's measured dispatch (PERF.md)


def _moments(scale, seed):
    src, _, roots, _ = graph500.graph(scale, seed)
    d = np.bincount(src)
    return roots, len(src), int((d.astype(np.int64) ** 2).sum())


def _adjacency(scale, seed, budget):
    from dgraph_tpu.ops import bitgraph
    src, dst, roots, vertices = graph500.graph(scale, seed)
    offs = np.zeros(vertices + 1, np.int64)
    np.cumsum(np.bincount(src, minlength=vertices), out=offs[1:])
    badj = bitgraph.build_bitadjacency(
        {int(u) + 1: (dst[offs[u]:offs[u + 1]] + 1).astype(np.uint32)
         for u in range(roots)})
    bitgraph.attach_dense(badj, budget)
    return badj


@pytest.fixture(scope="module", params=[16, 18])
def sized(request):
    """(degree moments, seconds a device level costs) of a Graph500
    graph at a served size, the hub rows under a default alpha's
    budget (2 GiB, nothing else resident)."""
    from dgraph_tpu.ops import bitgraph
    badj = _adjacency(request.param, 5, 2 << 30)
    return _moments(request.param, 5), bitgraph.level_seconds(badj)


@pytest.mark.parametrize("k,levels,device", [
    (1, 1, False), (2, 2, False), (3, 3, True), (6, 5, True), (63, 5, True)])
def test_the_gate_sends_deep_traversals_of_a_skewed_graph_to_the_device(
        sized, k, levels, device):
    """Both sides of the choice as the executor reckons them: one or
    two hops stay on the host, three hops on a power-law graph are
    most of its edges and go to the device. Never near the border,
    so the choice cannot turn on the seed."""
    moments, level_s = sized
    host, got_levels = recurse_costs(1, k, *moments)
    assert got_levels == levels
    margin = host - min(host, got_levels * level_s)
    border = 1.25 * DISPATCH_S
    assert (margin > border) is device
    assert abs(margin - border) > 0.5 * border


def test_the_gate_keeps_a_regular_sparse_graph_on_the_host():
    # a ring: every vertex one out-edge, no skew; 6 hops touch 6 edges
    host, levels = recurse_costs(1, 6, 1 << 20, 1 << 20, 1 << 20)
    assert host < 1e-5 and levels == 6
    assert recurse_costs(1, 6, 0, 0, 0) == (0.0, 0)


def test_the_executor_asks_the_gate_and_nothing_it_measured(
        tmp_path, monkeypatch):
    from dgraph_tpu.ops import bitgraph
    db = _db(str(tmp_path), 14, 11, prefer_device=True)
    assert db.device_min_edges > 1
    monkeypatch.setattr(GraphDB, "device_is_accelerator", lambda self: True)
    monkeypatch.setattr(GraphDB, "device_dispatch_seconds",
                        lambda self: DISPATCH_S)
    root = graph500.FIRST_UID + 17
    tab = db.tablets["link"]
    taken = {}
    for k in (1, 2, 3, 6):
        before = _counter("query_device_recurse_total")
        for _ in range(3):      # the same choice every time
            _data(db, KHOP % (root, k + 1))
        taken[k] = _counter("query_device_recurse_total") - before
        # one and two hops never build the tile they would not use
        assert (getattr(tab, "_device_badj", None) is not None) is (k >= 3)
    assert taken == {1: 0, 2: 0, 3: 3, 6: 3}
    # the device's side of the choice is the tile's own layout: here
    # every degree class is cheaper streamed than gathered
    badj = tab._device_badj
    # (a row is padded to whole vregs of 128 words)
    assert bitgraph.hub_row_words(4097) == 256
    assert badj.dense_from == 0 and badj.dense.shape == (
        badj.n_covered, bitgraph.hub_row_words(badj.n_slots))
    assert bitgraph.level_seconds(badj) == pytest.approx(
        badj.dense.nbytes / bitgraph.DENSE_BYTES_PER_S)


def test_hub_rows_take_the_classes_the_budget_holds():
    """attach_dense: whole degree classes from the highest down while
    they fit the budget and a row is cheaper streamed than gathered;
    the traversal answers the same with none, some and all of them."""
    from dgraph_tpu.ops import bitgraph
    src, dst, roots, vertices = graph500.graph(10, 7)
    offs = np.zeros(vertices + 1, np.int64)
    np.cumsum(np.bincount(src, minlength=vertices), out=offs[1:])
    edges = {int(u) + 1: (dst[offs[u]:offs[u + 1]] + 1).astype(np.uint32)
             for u in range(roots)}
    row_bytes = 4 * -(-vertices // 32)
    answers, shapes = [], []
    for budget in (0, 40 * row_bytes, 1 << 30):
        badj = bitgraph.build_bitadjacency(edges)
        bitgraph.attach_dense(badj, budget)
        n_dense = 0 if badj.dense is None else badj.dense.shape[0]
        assert n_dense * row_bytes <= budget
        assert n_dense == sum(b.in_nb.shape[0]
                              for b in badj.buckets[badj.dense_from:])
        shapes.append(n_dense)
        got = []
        for root in (1, 17, roots):
            slots = bitgraph.seed_slots(badj, np.array([root], np.uint32))
            tally, reached = bitgraph.traverse(badj, [(slots, 6)])
            counts, levels, _ = np.asarray(tally)
            uids = bitgraph.lane_uids(badj, np.asarray(reached), 0)
            assert len(uids) == int(counts[0])
            got.append((int(counts[0]), int(levels[0]), uids.tolist()))
        answers.append(got)
    assert shapes[0] == 0 < shapes[1] < shapes[2] == badj.n_covered
    assert answers[0] == answers[1] == answers[2]
    assert answers[0][0][0] == plain.khop(
        graph500, 10, {"seed": 7}, KHOP % (1, 7))["khop"][0]["count"]


# -- the host tier's level: one pass over a CSR -------------------------

def test_expand_frontier_reads_wide_frontiers_through_the_csr(worlds):
    _, _, host = worlds[10, 7]
    tab = host.tablets["link"]
    ts = host.coordinator.max_assigned()
    rng = np.random.default_rng(1)
    srcs = tab.src_uids(ts)
    for n in (1, 63, 64, 500, len(srcs)):
        fr = np.unique(np.concatenate([
            rng.choice(srcs, n), np.array([10**6], np.uint64)]))
        want = np.unique(np.concatenate(
            [tab.get_dst_uids(int(u), ts) for u in fr.tolist()]))
        assert np.array_equal(tab.expand_frontier(fr, ts), want)
    assert getattr(tab, "_csr_fwd")[0] == tab.base_ts


def test_expand_frontier_sends_overlay_rows_through_the_getters():
    db = _small(prefer_device=False)
    db.rollup_in_read = False
    tab = db.tablets["link"]
    fr = np.arange(1, 80, dtype=np.uint64)
    clean = tab.expand_frontier(fr, db.coordinator.max_assigned())
    db.mutate(set_nquads="<5> <link> <300> .", del_nquads="<6> * * .")
    assert tab.dirty()
    ts = db.coordinator.max_assigned()
    want = np.unique(np.concatenate(
        [tab.get_dst_uids(int(u), ts) for u in fr.tolist()]))
    got = tab.expand_frontier(fr, ts)
    assert np.array_equal(got, want) and 300 in got.tolist()
    assert not np.array_equal(got, clean)


def test_graphdb_bfs_and_the_served_host_tier_are_one_function(worlds):
    facts, dev, host = worlds[9, 31_000_017]
    root = _roots(facts, 9)[0]
    for dedup in (True, False):
        a = host.bfs("link", [root], 5, dedup=dedup)
        b = dev.bfs("link", [root], 5, dedup=dedup)
        assert len(a) == len(b) == 5
        assert all(np.array_equal(x, y) for x, y in zip(a, b))
    levels = host.bfs("link", [root], 6)
    n = np.unique(np.concatenate(levels))
    # bfs drops the seeds from its levels; the variable keeps a root
    # an edge leads back to
    got = json.loads(_data(host, UIDS % (root, 7)))["khop"]
    assert {int(o["uid"], 16) for o in got} - {root} == set(n.tolist())
