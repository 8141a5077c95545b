"""The Pokec-shaped dataset, its plain reference and the readers
`pokec-shortest.pairs-c16` adds: the generator's figures are the
source's at two scales (what `scale` cuts is the number of profiles
and nothing else), the seed decides the graph, the two classes of
literal are the two contiguous ranges, the plain reference gives the
defined path (checked against a search written here, ties included,
and against the program's own tiers on the same small graph), and
each reader gives the expected value on a synthetic `ctx` and None,
never an error, on what a program without the counters serves."""

import io
import json
import os
import types

import numpy as np
import pytest

from conftest import BENCH, load

POKEC = load("datasets/pokec.py")
PLAIN = load("datasets/pokec_plain.py")
PEAKS = {"hbm_bytes_per_s": 819e9}


def load_path(path):
    return load(os.path.relpath(path, BENCH))


# -- the generator ---------------------------------------------------------


@pytest.fixture(scope="module")
def graphs():
    return {scale: POKEC.graph(scale, 2**31 + 7 + scale)
            for scale in (1, 3)}


@pytest.mark.parametrize("scale", (1, 3))
def test_the_sources_figures_hold_at_every_scale(graphs, scale):
    src, dst, ranges = graphs[scale]
    profiles = round(POKEC.PROFILES * scale / 100)
    # edges a profile: the source's 30,622,564 / 1,632,803
    assert len(src) / profiles == pytest.approx(18.75, rel=1e-3)
    fig = POKEC.degree_figures(src, dst)
    assert fig["reciprocated"] == pytest.approx(0.54, abs=0.015)
    # the degree law: log-normal weights of sigma 1.44 (out) and 1.56
    # (in) about the same mean at every scale: a median far under the
    # mean, and a tail three orders above it
    out = np.bincount(src, minlength=profiles)
    assert 5 <= np.median(out) <= 9
    assert np.log(out[out > 0]).std() == pytest.approx(1.2, abs=0.12)
    assert fig["max_out_degree"] > 40 * 18.75
    assert fig["max_in_degree"] > 40 * 18.75
    # distinct, no self-loop, sorted by (src, dst)
    packed = (src << 32) | dst
    assert (np.diff(packed) > 0).all() and (src != dst).all()
    assert ranges["vertices"] == len(np.union1d(src, dst))


def test_the_law_does_not_follow_the_scale(graphs):
    """Same mean, same spread of the logarithm, same reciprocity at
    1% and 3% of the source's profiles: what grows is the graph."""
    a, b = ({"n": len(s), "sd": np.log(np.bincount(s)[np.bincount(s) > 0]
                                       ).std(),
             "rec": POKEC.degree_figures(s, d)["reciprocated"]}
            for s, d, _ in (graphs[1], graphs[3]))
    assert b["n"] / a["n"] == pytest.approx(3.0, rel=0.01)
    assert a["sd"] == pytest.approx(b["sd"], abs=0.05)
    assert a["rec"] == pytest.approx(b["rec"], abs=0.02)


def hops(src, dst, vertices, roots=256):
    """The share of (root, reachable vertex) pairs at each distance
    along the edges, from `roots` evenly spaced sources."""
    csr = PLAIN._csr(src, dst, vertices + 1)
    count = np.zeros(16)
    for root in np.unique(src)[::max(1, len(np.unique(src)) // roots)]:
        d = PLAIN.distances(csr, vertices + 1, int(root), vertices, 15)
        count += np.bincount(d[d < 16], minlength=16)
    count[0] = 0
    return count / count.sum()


def effective_diameter(share, q=0.9):
    """SNAP's figure: the distance within which `q` of the connected
    pairs lie, interpolated between whole hops."""
    c = np.cumsum(share)
    d = int(np.searchsorted(c, q))
    return d - 1 + (q - c[d - 1]) / (c[d] - c[d - 1])


# (mean hops, 90% effective diameter) the generator gives: the graph
# deepens with the profiles, and at the source's scale the fit reads
# 4.81 and 5.24 (the source: 5.2-5.3; 12: 4.12 and 4.67; 25: 4.39 and
# 4.86): too large to draw here
DEPTH = {1: (3.30, 3.77), 3: (3.62, 3.955)}


@pytest.mark.parametrize("scale", (1, 3))
def test_the_graph_is_as_deep_as_the_fit_says(graphs, scale):
    src, dst, ranges = graphs[scale]
    share = hops(src, dst, ranges["vertices"])
    mean, eff = DEPTH[scale]
    assert share @ np.arange(16) == pytest.approx(mean, abs=0.07)
    assert effective_diameter(share) == pytest.approx(eff, abs=0.07)


def test_friends_are_near(graphs, monkeypatch):
    """With the ends of the edges matched at random the same degrees
    give a shallower graph (at the source's scale 4.27 hops and 4.77
    against the fit's 4.81 and 5.24)."""
    src, dst, ranges = graphs[3]
    near = hops(src, dst, ranges["vertices"]) @ np.arange(16)
    monkeypatch.setattr(POKEC, "LOCAL", 0.0)
    monkeypatch.setitem(POKEC._CACHE, "graph_key", None)
    flat = POKEC.graph(3, 2**31 + 10)
    monkeypatch.setitem(POKEC._CACHE, "graph_key", None)
    assert len(flat[0]) == pytest.approx(len(src), rel=2e-3)
    assert near - hops(flat[0], flat[1], flat[2]["vertices"]) \
        @ np.arange(16) > 0.05


def test_every_seed_deals_the_same_weights():
    """The weights are the law's quantiles, so two seeds' graphs have
    the same largest degrees within the Poisson draw of their edges."""
    a, b = POKEC.graph(1, 5), POKEC.graph(1, 6)
    top = [np.sort(np.bincount(g[0]))[-20:].sum() for g in (a, b)]
    assert top[0] == pytest.approx(top[1], rel=0.03)


def test_the_two_classes_are_two_contiguous_ranges(graphs):
    src, dst, r = graphs[1]
    facts = r | {"seed": 0}
    first, n = POKEC.class_range("from", 1, facts)
    assert set(range(first - 1, first - 1 + n)) == set(np.unique(src))
    first, n = POKEC.class_range("to", 1, facts)
    assert set(range(first - 1, first - 1 + n)) == set(np.unique(dst))
    assert POKEC.class_of_literal(0x10001) == ("from", 1)
    assert POKEC.class_of_literal(0x20001) == ("to", 1)
    assert POKEC.class_of_literal(0x30001) is None
    with pytest.raises(ValueError):
        POKEC.class_range("root", 1, facts)


def test_the_seed_decides_the_graph_and_the_variant_drops_a_thousandth():
    a = POKEC.graph(1, 11)
    b = POKEC.graph(1, 12)
    again = POKEC.graph(1, 11)
    assert len(a[0]) != len(b[0]) or (a[1] != b[1]).any()
    assert (a[0] == again[0]).all() and (a[1] == again[1]).all()
    less = POKEC.graph(1, 11, "drop-edges")
    assert 1 - len(less[0]) / len(a[0]) == pytest.approx(0.001, abs=0.0004)
    # what is left is the sound graph's own edges
    assert np.isin((less[0] << 32) | less[1], (a[0] << 32) | a[1]).all()
    with pytest.raises(ValueError):
        POKEC.graph(1, 11, "no-such")


def test_write_rdf_writes_every_edge_and_the_facts(monkeypatch):
    monkeypatch.setattr(POKEC, "require_defined_path", lambda: None)
    out = io.StringIO()
    facts = POKEC.write_rdf(out, 1, 2**31 + 99)
    lines = out.getvalue().splitlines()
    src, dst, _ = POKEC.graph(1, 2**31 + 99)
    assert len(lines) == facts["rdf"] == facts["edges"]["friend"] == len(src)
    assert lines[0] == f"<0x{src[0] + 1:04x}> <friend> <0x{dst[0] + 1:04x}> ."
    assert facts["profiles"] == 16328 and facts["seed"] == 2**31 + 99
    assert facts["mean_out_degree"] == pytest.approx(18.75, rel=2e-3)
    assert facts["from_count"] + facts["to_count"] >= facts["vertices"]


def test_a_program_without_the_defined_path_is_refused(monkeypatch,
                                                       tmp_path):
    """The parent commit's tree: no storage/tablet.least_path."""
    (tmp_path / "dgraph_tpu" / "storage").mkdir(parents=True)
    (tmp_path / "dgraph_tpu" / "__init__.py").write_text("")
    (tmp_path / "dgraph_tpu" / "storage" / "__init__.py").write_text("")
    (tmp_path / "dgraph_tpu" / "storage" / "tablet.py").write_text(
        "def bfs_levels():\n    pass\n")
    monkeypatch.setattr(POKEC, "PROGRAM_ROOT", str(tmp_path))
    monkeypatch.setitem(POKEC._CACHE, "program", None)
    with pytest.raises(RuntimeError, match="cannot run the pokec"):
        POKEC.write_rdf(io.StringIO(), 1, 5)


@pytest.mark.parametrize("scale", (8, 9, 10, 11))
def test_no_scale_the_cell_may_take_lies_near_a_step_of_the_rows_width(
        scale):
    """A hub row is a bit a vertex padded to 4,096 (128 words): where
    the seeds' vertex counts straddle a multiple of it, the seed
    draws the tile's LAYOUT (a row 2-4% wider or not) and the rate
    with it. Of 8..11 every one is 500 vertices or more clear; and
    the mean out-degree is the source's within a thousandth."""
    src, dst, ranges = POKEC.graph(scale, 2**31 + 45 + scale)
    n = ranges["vertices"]
    assert min(n % 4096, 4096 - n % 4096) >= 500
    profiles = round(POKEC.PROFILES * scale / 100)
    assert len(src) / profiles == pytest.approx(18.75, rel=1e-3)
    assert ranges["from_count"] == len(np.unique(src))
    assert ranges["to_count"] == len(np.unique(dst))


# -- the plain reference -------------------------------------------------------


def _search(edges, a, b, depth):
    """The statement again, with dictionaries (as the tier-1 test of
    the program has it)."""
    out, back = {}, {}
    for u, v in edges:
        out.setdefault(u, []).append(v)
        back.setdefault(v, []).append(u)
    dist, frontier = {b: 0}, [b]
    while frontier:
        nxt = []
        for v in frontier:
            for u in back.get(v, ()):
                if u not in dist:
                    dist[u] = dist[v] + 1
                    nxt.append(u)
        frontier = nxt
    if dist.get(a, 1 << 30) > depth:
        return []
    path = [a]
    while path[-1] != b:
        path.append(min(w for w in out[path[-1]]
                        if dist.get(w) == dist[path[-1]] - 1))
    return path


@pytest.fixture(scope="module")
def small():
    """A Pokec-shaped graph of 1,500 profiles (the generator's own
    draw, under the smallest scale) behind the dataset's interface."""
    packed = POKEC.drawn_edges(1500, np.random.default_rng(77))
    src, dst = packed >> 32, packed & 0xFFFFFFFF
    stub = types.SimpleNamespace(
        FIRST_UID=1, graph=lambda scale, seed: (
            src, dst, {"vertices": 1500}))
    return stub, src, dst


def test_the_plain_reference_gives_the_defined_path(small):
    stub, src, dst = small
    edges = list(zip(src.tolist(), dst.tolist()))
    rng = np.random.default_rng(5)
    facts, hops = {"seed": 77}, []
    for _ in range(60):
        a, b = (int(x) for x in rng.integers(0, 1500, 2))
        depth = int(rng.choice([2, 3, 15]))
        want = _search(edges, a, b, depth) if a != b else [a]
        assert PLAIN.least_path(stub, 0, facts, a, b, depth) == want
        hops.append(len(want) - 1)
    assert max(hops) >= 3 and min(hops) == -1       # and some have none


def test_the_reply_is_the_programs_object(small):
    stub, src, dst = small
    facts = {"seed": 77}
    a = int(src[0])
    b = int(dst[src == dst[src == a][0]][0])       # two hops on
    q = "{ shortest(from: %#x, to: %#x, depth: 15) { friend } }"
    got = PLAIN.ANSWERS["shortest15"](stub, 0, facts, q % (a + 1, b + 1))
    path = PLAIN.least_path(stub, 0, facts, a, b, 15)
    node = got["_path_"][0]
    assert list(node)[:2] == ["uid", "_weight_"]
    assert node["_weight_"] == float(len(path) - 1)
    seen = []
    while node is not None:
        seen.append(int(node["uid"], 16) - 1)
        node = node.get("friend")
    assert seen == path
    same = PLAIN.ANSWERS["shortest15"](stub, 0, facts, q % (a + 1, a + 1))
    assert same == {"_path_": [{"uid": hex(a + 1), "_weight_": 0.0}]}
    # against the edges of a profile nothing leads to: no path
    lone = int(np.setdiff1d(np.arange(1500), dst)[0])
    assert PLAIN.ANSWERS["shortest15"](
        stub, 0, facts, q % (a + 1, lone + 1)) == {"_path_": []}


def test_device_tier_postings_tier_and_plain_reference_agree(small):
    """The program's two tiers and the plain reference on the same
    Pokec-shaped graph, answer for answer (the device tier forced:
    on the CPU its gate would keep the host)."""
    from dgraph_tpu.engine.db import GraphDB
    stub, src, dst = small
    quads = "\n".join(f"<{s + 1:#x}> <friend> <{d + 1:#x}> ."
                      for s, d in zip(src.tolist(), dst.tolist()))
    dbs = []
    for prefer in (True, False):
        db = GraphDB(prefer_device=prefer, device_min_edges=1)
        db.alter(POKEC.SCHEMA)
        db.mutate(set_nquads=quads, commit_now=True)
        db.rollup_all()
        dbs.append(db)
    rng = np.random.default_rng(6)
    found = 0
    for _ in range(24):
        a, b = (int(x) for x in rng.integers(1, 1501, 2))
        q = "{ shortest(from: %#x, to: %#x, depth: 15) { friend } }" % (a, b)
        want = PLAIN.ANSWERS["shortest15"](stub, 0, {"seed": 77}, q)
        for db in dbs:
            assert json.loads(db.query_json(q))["data"] == want
        found += bool(want)
    assert found >= 12


# -- the readers ----------------------------------------------------------------


CHILD = 'device_call_ns_total{family="shortest",phase="%s"}'
OTHER = 'device_call_ns_total{family="recurse",phase="wait"}'
AHEAD = 'rendezvous_ahead_total{family="shortest"}'
CHAINED = 'rendezvous_chained_total{family="shortest"}'
TURN = 'rendezvous_ns_total{family="shortest",phase="turnround"}'
BEFORE = {"shortest_calls_total": 100, "shortest_riders_total": 790,
          "shortest_levels_run_total": 520, "shortest_fetch_bytes_total":
          64_800, "shortest_ns_total": 5e9, "query_device_shortest_total":
          790, CHILD % "enqueue": 1e9, CHILD % "wait": 2e9,
          CHILD % "fetch": 0.5e9, OTHER: 7e9,
          "shortest_rows_streamed_tiles_total": 10_000,
          "shortest_rows_tiles_total": 20_000, AHEAD: 90, CHAINED: 99,
          TURN: 1e6, 'rendezvous_ahead_total{family="recurse"}': 5}
AFTER = {"shortest_calls_total": 1100, "shortest_riders_total": 8_750,
         "shortest_levels_run_total": 6_020,
         "shortest_fetch_bytes_total": 712_800, "shortest_ns_total": 505e9,
         "query_device_shortest_total": 8_750, CHILD % "enqueue": 2e9,
         CHILD % "wait": 490e9, CHILD % "fetch": 1.5e9, OTHER: 99e9,
         "shortest_rows_streamed_tiles_total": 70_000,
         "shortest_rows_tiles_total": 100_000, AHEAD: 1_040, CHAINED: 1_099,
         TURN: 26e6, 'rendezvous_ahead_total{family="recurse"}': 77}
FACTS = {"vertices": 187_899, "edges": {"friend": 3_673_758}}
PROGRAMS = [["jit_bfs_paths", 3.0, 60], ["jit_convert_element_type", 0.2,
                                         500], ["jit_bfs_paths.1", 1.5, 40]]
# 5.5 levels a call over the window; 100 calls in the trace
EACH = 5.5 * (4 * 3_673_758 + 2 * 187_899 / 8)


def ctx(before=None, after=None, programs=None, peaks=PEAKS):
    return {"replies": [], "window_s": 45.0, "counters_before": before or {},
            "counters_after": after or {}, "facts": FACTS, "notes": [],
            "trace": None if programs is None else {"programs": programs},
            "peaks": peaks, "bench_dir": BENCH, "load_module": load_path}


CASES = [
    ("shortest_roofline", ctx(BEFORE, AFTER, PROGRAMS),
     100.0 * (100 * EACH / 819e9) / 4.5),
    ("shortest_lanes_per_call", ctx(BEFORE, AFTER), 7.96),
    ("shortest_levels_per_call", ctx(BEFORE, AFTER), 5.5),
    # 648 B a call of eight lanes
    ("shortest_fetch_bytes_per_req", ctx(BEFORE, AFTER), 648_000 / 7_960),
    # 500 s in the span less 490 s in its device.call child, 7,960 blocks
    ("shortest_host_ms", ctx(BEFORE, AFTER), 10e9 / 7_960 / 1e6),
    # 60,000 of 80,000 tiles; 950 of 1,000 calls; 25 ms over 1,000 calls
    ("shortest_rows_streamed_share", ctx(BEFORE, AFTER), 75.0),
    ("shortest_ahead_share", ctx(BEFORE, AFTER), 95.0),
    ("shortest_turnround_ms", ctx(BEFORE, AFTER), 0.025),
]


@pytest.mark.parametrize("name,c,want", CASES, ids=[c[0] for c in CASES])
def test_a_reader_reads_what_it_says(name, c, want):
    assert load(f"metrics/{name}.py").read(c) == pytest.approx(want)


SILENT = [
    # the parent commit, the --no-device child: no such counter
    ("shortest_roofline", ctx({}, {"recurse_batch_total": 5.0}, PROGRAMS)),
    ("shortest_roofline", ctx(BEFORE, AFTER, None)),            # untraced
    ("shortest_roofline", ctx(BEFORE, AFTER, PROGRAMS[1:2])),   # no program
    ("shortest_roofline", ctx(BEFORE, AFTER, PROGRAMS, None)),  # no peaks
    ("shortest_roofline", ctx(AFTER, AFTER, PROGRAMS)),         # no call
    ("shortest_lanes_per_call", ctx({}, {})),
    ("shortest_lanes_per_call", ctx(AFTER, AFTER)),
    ("shortest_levels_per_call", ctx({}, {"shortest_calls_total": 3.0})),
    ("shortest_levels_per_call", ctx(AFTER, AFTER)),
    ("shortest_fetch_bytes_per_req", ctx({}, {})),
    ("shortest_fetch_bytes_per_req", ctx(AFTER, AFTER)),
    ("shortest_host_ms", ctx({}, {})),
    ("shortest_host_ms", ctx(AFTER, AFTER)),
    ("shortest_rows_streamed_share", ctx({}, {"recurse_hub_tiles_total": 9})),
    ("shortest_rows_streamed_share", ctx(AFTER, AFTER)),
    # the k-hop family's counters are not this family's
    ("shortest_ahead_share", ctx({}, {"shortest_calls_total": 3.0,
     'rendezvous_ahead_total{family="recurse"}': 2.0})),
    ("shortest_ahead_share", ctx(AFTER, AFTER)),
    ("shortest_turnround_ms", ctx({}, {TURN: 5.0})),
    ("shortest_turnround_ms", ctx(AFTER, AFTER)),
]


@pytest.mark.parametrize("name,c", SILENT,
                         ids=[f"{n}-{i}" for i, (n, _) in enumerate(SILENT)])
def test_a_reader_is_silent_where_there_is_nothing_to_read(name, c):
    assert load(f"metrics/{name}.py").read(c) is None


def test_the_cost_counts_the_graph_and_not_the_layout():
    cost = load("costs/jit_bfs_paths.py")
    assert cost.TEMPLATE is None
    one = cost.least_bytes({"edges": 1000, "vertices": 800, "levels": 1})
    assert one == 4 * 1000 + 2 * 100
    assert cost.least_bytes({"edges": 1000, "vertices": 800,
                             "levels": 5.5}) == 5.5 * one


def test_the_roofline_says_what_it_was_worked_out_from():
    c = ctx(BEFORE, AFTER, PROGRAMS)
    load("metrics/shortest_roofline.py").read(c)
    (note,) = c["notes"]
    assert "100 calls" in note and "5.50 levels of 3673758 edges" in note


# -- the manifest ----------------------------------------------------------------


def _bench():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        return json.load(f)


NEW_METRICS = ("shortest_roofline", "shortest_lanes_per_call",
               "shortest_levels_per_call", "shortest_rows_streamed_share",
               "shortest_fetch_bytes_per_req", "shortest_host_ms",
               "shortest_ahead_share", "shortest_turnround_ms")


def test_every_string_of_the_new_entries_is_inside_its_limit():
    """PR 41 was lost to one string: count them."""
    import re
    bench = _bench()
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    (cfg,) = [c for c in bench["configs"] if c["name"] == "pokec-shortest"]
    (cell,) = [w for w in bench["workloads"]
               if w["config"] == "pokec-shortest"]
    for text in (cfg["why"], cfg["source"], cell["why"]):
        assert 1 <= len(text) <= 200, (len(text), text)
        assert text.isascii() and text.isprintable()
    assert cfg == {"name": "pokec-shortest", "source": cfg["source"],
                   "file": "benchmark/configs/pokec-shortest.json",
                   "reduced": ["scale"], "why": cfg["why"]}
    assert cell == {"name": "pokec-shortest.pairs-c16",
                    "config": "pokec-shortest", "traffic": "pairs-c16",
                    "chips": 1, "why": cell["why"]}
    mine = [m for m in bench["per_layer"]
            if m.get("workloads") == [cell["name"]]]
    assert sorted(m["name"] for m in mine) == sorted(NEW_METRICS)
    # appended, in no accepted metric's list, and named by no other
    assert bench["per_layer"][-8:] == mine
    assert not any(cell["name"] in m.get("workloads", ())
                   for m in bench["per_layer"][:-8] + bench["end_to_end"])
    for m in mine:
        assert name.match(m["name"]) and len(m["layer"]) <= 200
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))
    assert all(name.match(x) for x in (
        cfg["name"], cell["name"], cell["traffic"], *cfg["reduced"]))


def test_the_configuration_states_what_the_manifest_says():
    bench = _bench()
    (cfg,) = [c for c in bench["configs"] if c["name"] == "pokec-shortest"]
    with open(os.path.join(os.path.dirname(BENCH), cfg["file"])) as f:
        config = json.load(f)
    assert config["source"] == cfg["source"] and config["scale"] == 10
    assert config["architecture"] is None and config["chips"] == 1
    assert config["source_scale"] == 100 and config["serve_flags"] == []
    assert config["dataset"] == "pokec"
    assert config["plain_reference"] == "pokec_plain"
    assert set(config["reduced_why"]) == set(cfg["reduced"])
    assert any("lexicographically least" in g for g in config["guarantees"])
    with open(os.path.join(BENCH, "traffic", "pairs-c16.json")) as f:
        mix = json.load(f)
    assert mix["loop"] == "closed" and mix["clients"] == 16
    assert mix["bindings"] == 4096 and mix["uid_literals"] == {"zipf": 0}
    assert [t["name"] for t in mix["templates"]] == ["shortest15"]
