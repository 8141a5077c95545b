"""Async cold-store prefetch (engine/prefetch.PrefetchPool): decode
overlap, at-most-once handover, staleness discard, metric wiring and
shutdown — the pipeline that hides tablet decode behind query
compute."""

import time

import pytest

from dgraph_tpu.engine.db import GraphDB
from dgraph_tpu.engine.prefetch import PrefetchPool
from dgraph_tpu.utils import metrics

pytestmark = pytest.mark.racecheck

SCHEMA = """
score: int @index(int) .
tier: string @index(exact) .
link: [uid] .
"""


def _seeded_dir(tmp_path, n=300):
    d = str(tmp_path / "store")
    db = GraphDB(store_dir=d)
    db.alter(schema_text=SCHEMA)
    quads = []
    for i in range(1, n + 1):
        quads.append(f'<0x{i:x}> <score> "{i % 97}" .')
        quads.append(f'<0x{i:x}> <tier> "t{i % 3}" .')
        quads.append(f'<0x{i:x}> <link> <0x{(i % n) + 1:x}> .')
    db.mutate(set_nquads="\n".join(quads))
    db.rollup_all()
    db.close()
    return d


@pytest.fixture()
def store_dir(tmp_path):
    return _seeded_dir(tmp_path)


def test_prefetch_hit_serves_query(store_dir):
    """A scheduled decode is consumed by the first query touching the
    predicate: hits and bytes counters move, answers are correct."""
    db = GraphDB(store_dir=store_dir, prefetch_workers=2)
    try:
        before = metrics.counters_snapshot()
        got = db.query('{ q(func: eq(tier, "t1"), first: 5) { uid score } }')
        assert len(got["data"]["q"]) == 5
        st = db.prefetcher.stats()
        assert st["scheduled"] > 0
        assert st["hits"] + st["waits"] > 0 or st["misses"] > 0
        delta = metrics.counters_delta(before)
        assert delta.get("prefetch_hits_total", 0) == st["hits"]
        assert st["hits"] == 0 or delta.get("prefetch_bytes_total", 0) > 0
    finally:
        db.close()


def test_take_is_at_most_once(store_dir):
    db = GraphDB(store_dir=store_dir, prefetch_workers=1)
    try:
        pf = db.prefetcher
        assert pf.schedule(db, ["score"]) == 1
        tab = pf.take("score", None)
        assert tab is not None
        # the future was popped: a second take is a clean None
        assert pf.take("score", None) is None
    finally:
        db.close()


def test_stale_decode_discarded(store_dir):
    """A decode scheduled against a blob the engine re-saved since is
    stale: take() must discard it (saved_ts mismatch), the caller
    loads fresh."""
    db = GraphDB(store_dir=store_dir, prefetch_workers=1)
    try:
        pf = db.prefetcher
        assert pf.schedule(db, ["score"]) == 1
        # wait the decode out, then claim the engine re-saved at a
        # different base_ts than the decoded blob carries
        deadline = time.time() + 10
        while pf._inflight.get("score") is not None \
                and not pf._inflight["score"].done():
            if time.time() > deadline:
                pytest.fail("prefetch decode never finished")
            time.sleep(0.01)
        assert pf.take("score", saved_ts=-1) is None
        assert pf.hits == 0
    finally:
        db.close()


def test_schedule_filters_resident_and_unknown(store_dir):
    db = GraphDB(store_dir=store_dir, prefetch_workers=1)
    try:
        pf = db.prefetcher
        # force-load one predicate: now resident, never rescheduled
        assert db.tablets.get("tier") is not None
        assert pf.schedule(db, ["tier"]) == 0
        assert pf.schedule(db, ["never_heard_of_it"]) == 0
        # in-flight dedup: the second schedule is a no-op
        assert pf.schedule(db, ["score"]) == 1
        assert pf.schedule(db, ["score"]) == 0
    finally:
        db.close()


def test_inflight_bound(store_dir):
    db = GraphDB(store_dir=store_dir, prefetch_workers=1)
    try:
        pf = db.prefetcher
        pf.max_inflight = 2
        n = pf.schedule(db, ["score", "tier", "link"])
        assert n <= 2
        assert len(pf._inflight) <= 2
    finally:
        db.close()


def test_close_is_terminal(store_dir):
    db = GraphDB(store_dir=store_dir, prefetch_workers=1)
    pf = db.prefetcher
    db.close()
    assert pf.schedule(db, ["score"]) == 0
    assert pf.take("score", None) is None
    # and the engine no longer routes through the closed pool
    assert db.prefetcher is None


def test_misses_counted_without_pool_interference(store_dir):
    """With a pool attached but nothing scheduled for a predicate, the
    synchronous load path must count a miss and still serve."""
    db = GraphDB(store_dir=store_dir, prefetch_workers=1)
    try:
        pf = db.prefetcher
        before = pf.misses
        assert db.tablets.get("link") is not None  # sync load
        assert pf.misses >= before + 1
    finally:
        db.close()


def test_standalone_pool_decode_parity(store_dir):
    """A pool-decoded tablet is the same object restore would build:
    same base_ts and posting count as a synchronous store load."""
    db = GraphDB(store_dir=store_dir)
    try:
        pool = PrefetchPool(db.tablet_store, workers=1)
        assert pool.schedule(db, ["score"]) == 1
        tab = pool.take("score", None)
        sync = db.tablet_store.load("score", db.schema)
        assert tab is not None and sync is not None
        assert tab.base_ts == sync.base_ts
        pool.close()
    finally:
        db.close()
