"""The GraphQL± hot path must actually reach the device kernels.

Round-1 verdict: the flagship @recurse/@shortest/order-by query strings
ran per-uid host Python while the device kernels sat unused. These
tests issue real query strings against a device-preferring engine and
assert BOTH result parity with the host path AND (via the metrics
counters) that the device kernels were the ones doing the work.
"""

import numpy as np
import pytest

from dgraph_tpu.engine.db import GraphDB
from dgraph_tpu.utils import metrics


def _counter(name: str) -> float:
    snap = metrics.snapshot()["counters"]
    return sum(v for k, v in snap.items() if k.startswith(name))


def _build(prefer_device: bool) -> GraphDB:
    rng = np.random.default_rng(42)
    db = GraphDB(prefer_device=prefer_device, device_min_edges=1)
    db.alter("follows: [uid] @reverse .\n"
             "name: string @index(exact) .\n"
             "age: int @index(int) .")
    n = 120
    quads = []
    for u in range(1, n + 1):
        quads.append(f'<{u}> <name> "user{u:03d}" .')
        quads.append(f'<{u}> <age> "{(u * 37) % 90}" .')
        for d in np.unique(rng.integers(1, n + 1, 6)):
            if d != u:
                quads.append(f"<{u}> <follows> <{d}> .")
    db.mutate(set_nquads="\n".join(quads))
    return db


@pytest.fixture(scope="module")
def dbs():
    return _build(True), _build(False)


def test_recurse_hits_device_kernels_with_parity(dbs):
    dev, host = dbs
    q = """{
      r(func: uid(1)) @recurse(depth: 3) {
        name
        follows @filter(has(name))
      }
    }"""
    metrics.reset()
    got = dev.query(q)
    assert _counter("query_device_expand_total") > 0, \
        "3-hop recurse never reached the device expand kernel"
    want = host.query(q)
    assert got["data"] == want["data"]


def test_reverse_expansion_on_device(dbs):
    dev, host = dbs
    q = """{
      r(func: uid(5)) @recurse(depth: 2) {
        name
        ~follows @filter(has(name))
      }
    }"""
    metrics.reset()
    got = dev.query(q)
    snap = metrics.snapshot()["counters"]
    assert snap.get('query_device_expand_total{dir="rev"}', 0) > 0, \
        "reverse expansion stayed on host"
    want = host.query(q)
    assert got["data"] == want["data"]


def test_shortest_hits_the_device_search(dbs):
    dev, host = dbs
    q = """{
      path as shortest(from: 1, to: 97) {
        follows
      }
      path(func: uid(path)) { name }
    }"""
    metrics.reset()
    got = dev.query(q)
    assert _counter("query_device_shortest_total") > 0, \
        "shortest never reached the device's lane search"
    want = host.query(q)
    # ONE defined path: both tiers give it, byte for byte
    assert got["data"] == want["data"] and got["data"]["_path_"]


def test_orderby_uses_device_keys(dbs):
    dev, host = dbs
    q = """{
      q(func: has(age), orderasc: age, first: 20) { name age }
    }"""
    metrics.reset()
    got = dev.query(q)
    assert _counter("query_device_multisort_total") \
        + _counter("query_device_sort_page_total") \
        + _counter("query_device_orderkeys_total") > 0, \
        "order-by never reached the device sort path"
    want = host.query(q)
    assert got["data"] == want["data"]


def test_inequality_root_uses_device_range(dbs):
    dev, host = dbs
    q = '{ q(func: ge(age, 40)) { name age } }'
    metrics.reset()
    got = dev.query(q)
    assert _counter("query_device_range_total") > 0, \
        "inequality root scan never reached the device range kernel"
    want = host.query(q)
    assert sorted(x["name"] for x in got["data"]["q"]) == \
        sorted(x["name"] for x in want["data"]["q"])
