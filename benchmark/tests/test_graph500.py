"""The Graph500 generator and its plain reference: the same seed gives
the same bytes, the degree skew that makes three hops most of the
graph is there, the roots' range is what the traffic generator draws
from, and the plain breadth-first search gives DQL's count on a graph
small enough to work out by hand."""

import io
import json
import os
import re

import numpy as np
import pytest

from conftest import BENCH, load

g = load("datasets/graph500.py")
plain = load("datasets/graph500_plain.py")
# these tests ask nothing of the program beside the module
g._CACHE["program"] = g.PROGRAM_ROOT


def rdf(scale, seed, variant=""):
    out = io.StringIO()
    facts = g.write_rdf(out, scale, seed, variant)
    return out.getvalue(), facts


def test_same_seed_same_bytes_another_seed_another_graph():
    a, fa = rdf(9, 2**31 + 77)
    g._CACHE.pop("graph_key")
    b, fb = rdf(9, 2**31 + 77)
    c, fc = rdf(9, 2**31 + 78)
    assert a == b and fa == fb
    assert a != c and fa["edges"] != fc["edges"]
    assert fa["rdf"] == a.count("\n") == fa["edges"]["link"]


def test_the_sources_shapes():
    assert (g.A, g.B, g.C, g.EDGE_FACTOR) == (0.57, 0.19, 0.19, 16)
    assert g.SCHEMA.split() == ["link:", "[uid]", "."]
    src, dst, roots, vertices = g.graph(12, 5)
    drawn = g.EDGE_FACTOR << 12
    # duplicates and self-loops dropped, nothing else
    assert 0.5 * drawn < len(src) < drawn
    assert not (src == dst).any()
    assert len(np.unique((src << 32) | dst)) == len(src)
    # every vertex touches an edge; those with an out-edge come first
    assert set(np.unique(src)) == set(range(roots))
    assert np.unique(np.concatenate([src, dst])).tolist() \
        == list(range(vertices))
    assert vertices <= 1 << 12


def test_degree_skew_is_present():
    src, dst, roots, _ = g.graph(14, 3)
    out = np.bincount(src)
    mean = len(src) / roots
    # a Kronecker graph's hubs: the largest out-degree is hundreds of
    # times the mean, and a vertex reached through an edge has tens of
    # times the mean degree (what makes k = 3 most of the graph)
    assert out.max() > 100 * mean
    assert (out.astype(float) ** 2).sum() / len(src) > 10 * mean
    assert np.median(out[out > 0]) < mean


def test_roots_are_one_contiguous_range_of_vertices_with_an_out_edge(
        traffic):
    _, facts = rdf(10, 11)
    assert g.class_of_literal(0x10001) == ("root", 1)
    assert g.class_of_literal(0x1) is None
    first, n = g.class_range("root", 10, facts)
    assert (first, n) == (g.FIRST_UID, facts["roots"])
    mix = traffic.load_mix(os.path.join(BENCH, "traffic", "khop-deep-c16.json"))
    assert mix["uid_literals"] == {"zipf": 0}
    # a closed loop over a rendezvous of capacity C sends at least
    # 2 x C clients: eight then wait whenever a call lands, every call
    # rides full, and no split of the connections can keep itself
    assert mix["loop"] == "closed" and mix["clients"] == 16
    pool = traffic.build_pool(mix, g, 10, facts, 11)
    assert len(pool) == 64
    assert [e["name"] for e in pool] == ["khop3"] * 32 + ["khop6"] * 32
    src = set(g.graph(10, 11)[0].tolist())
    for e in pool:
        depth = 4 if e["name"] == "khop3" else 7
        assert f"@recurse(depth: {depth}, loop: false)" in e["query"]
        (root,) = {int(u, 16) for u in
                   re.findall(r"0x[0-9a-f]+", e["query"])}
        assert root - g.FIRST_UID in src
    # uniform: the roots are spread over the range, not piled on hubs
    roots = sorted(int(re.search(
        r"0x[0-9a-f]+", e["query"]).group(0), 16) for e in pool)
    assert roots[-1] - roots[0] > facts["roots"] // 2


def test_the_control_graph_lacks_one_edge_in_a_thousand():
    sound = g.graph(12, 9)[0].size
    served = g.graph(12, 9, "drop-edges")[0].size
    assert served == sound - sound // 1000
    with pytest.raises(ValueError):
        g.graph(12, 9, "no-such-variant")


def test_the_plain_search_counts_as_dql_does():
    # 0 -> 1 -> 2 -> 0 and 2 -> 3: a cycle back to the root, and a tail
    offsets = np.array([0, 1, 2, 4, 4])
    dst = np.array([1, 2, 0, 3])
    n = [int(plain.reached(offsets, dst, 4, 0, k).sum()) for k in range(5)]
    # k = 3: the edge 2 -> 0 leads back, so the root counts from then on
    assert n == [0, 1, 2, 4, 4]
    # from the tail nothing is reached at any depth
    assert int(plain.reached(offsets, dst, 4, 3, 6).sum()) == 0


def test_the_plain_reference_answers_every_template_of_the_mix(traffic):
    _, facts = rdf(9, 21)
    mix = traffic.load_mix(os.path.join(BENCH, "traffic", "khop-deep-c16.json"))
    assert {t["name"] for t in mix["templates"]} == set(plain.ANSWERS)
    pool = traffic.build_pool(mix, g, 9, facts, 21)
    src, dst, _, vertices = g.graph(9, 21)
    adj = {}
    for s, d in zip(src.tolist(), dst.tolist()):
        adj.setdefault(s, set()).add(d)
    for e in pool[::8]:
        got = plain.ANSWERS[e["name"]](g, 9, facts, e["query"])
        root = int(re.search(
            r"0x[0-9a-f]+", e["query"]).group(0), 16) - g.FIRST_UID
        seen, n, frontier = {root}, set(), {root}
        for _ in range(3 if e["name"] == "khop3" else 6):
            reach = set().union(*(adj.get(u, set()) for u in frontier))
            n |= reach
            frontier = reach - seen
            seen |= frontier
        assert got == {"khop": [{"count": len(n)}]}


def test_the_configuration_keeps_the_sources_shapes():
    with open(os.path.join(BENCH, "configs", "graph500-khop.json")) as f:
        cfg = json.load(f)
    assert cfg["dataset"] == "graph500"
    assert cfg["plain_reference"] == "graph500_plain"
    assert cfg["architecture"] is None and cfg["serve_flags"] == []
    assert cfg["source_scale"] == 22 and 16 <= cfg["scale"] <= 22
    assert set(cfg["reduced_why"]) == {"scale"}
    assert cfg["device_counters"] == ["query_device_recurse_total"]
    assert {"direction", "count", "roots", "clients"} <= set(cfg["assumed"])
