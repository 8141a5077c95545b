"""similar_to gives ONE answer (PR 26): the device tier, the host
tier and the benchmark's plain reference agree in set AND order, ties
included; the dispatch runs inside `device.call`; the vector block is
a counted, evictable tile; approximation is the schema's to ask for;
and the SIFT-shaped dataset of the benchmark is reproducible."""

import importlib.util
import io
import json
import os

import numpy as np
import pytest

from dgraph_tpu.engine.db import GraphDB
from dgraph_tpu.models.schema import parse_schema
from dgraph_tpu.ops import knn
from dgraph_tpu.utils import metrics, tracing

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")


def _load(relpath):
    name = "knn_exact_" + os.path.basename(relpath).removesuffix(".py")
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, relpath))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def sift():
    return _load("datasets/sift.py")


@pytest.fixture(scope="module")
def plain():
    return _load("datasets/sift_plain.py")


@pytest.fixture(scope="module")
def traffic():
    return _load("traffic.py")


# ---------------------------------------------------------------------------
# (a) three tiers, one answer
# ---------------------------------------------------------------------------


def _tied_corpus(n, seed):
    """Whole-number rows built to tie: few distinct values, the
    query's nearest duplicated under adjacent and distant uids, and
    more rows at the k-th distance than the k-th place has room for."""
    rng = np.random.default_rng(seed)
    c = rng.integers(0, 6, (n, 16)).astype(np.uint8)
    q = rng.integers(0, 6, 16).astype(np.uint8)
    near = q.copy()
    near[0] += 1                      # distance 1
    for row in (40, 41, 42, n // 2, n - 7):
        c[row] = near
    c[n // 3] = q                     # distance 0, once
    far = q.copy()
    far[1] += 2                       # distance 4: a dozen of them, so
    for row in range(500, 512):       # k = 10 ends inside the tie
        c[row] = far
    return c, q


def _plain_rows(plain, c, q, k, mask):
    wide, norms = plain.widen(c)
    rows, dist = plain.nearest(wide, norms, q.astype(np.float64), k,
                               keep=mask)
    return rows, -dist.astype(np.float64)


@pytest.mark.parametrize("masked", [False, True], ids=["all", "masked"])
@pytest.mark.parametrize("k", [10, 100])
@pytest.mark.parametrize("n", [8192, 20000])
def test_tiers_agree_in_set_and_order_on_ties(plain, n, k, masked):
    c, q = _tied_corpus(n, seed=n + k)
    mask = None
    if masked:
        mask = np.random.default_rng(5).random(n) < 0.4
        mask[[40, 41, n // 2, 505]] = True
    cf, qf = c.astype(np.float32), q.astype(np.float32)[None]
    want_i, want_s = _plain_rows(plain, c, q, k, mask)
    hi, hs = knn.topk_host(cf, qf, k, "euclidean", mask=mask)
    assert np.array_equal(hi[0], want_i)
    assert np.array_equal(hs[0], want_s)
    # the default plan, the proved two-stage forced at L 2 and 3, and
    # lax.top_k over the full row
    for two_stage, l_per in ((None, None), (True, 2), (True, 3),
                             (False, None)):
        di, ds = knn.topk_device(cf, qf, k, "euclidean", mask=mask,
                                 two_stage=two_stage,
                                 l_per_bucket=l_per)
        assert np.array_equal(di[0], want_i), (two_stage, l_per)
        assert np.array_equal(ds[0], want_s), (two_stage, l_per)
    assert knn.plan_two_stage(n, 10) > 0   # two-stage is engaged


def test_a_bucket_with_too_many_neighbours_takes_the_exact_path(plain):
    """Rows j and j + nb share a bucket: with L = 1 the second of two
    nearest neighbours there cannot be a candidate, the proof fails,
    and the same call answers from the full row."""
    n, k = 8192, 4
    nb = n // knn.BUCKET_SIZE
    rng = np.random.default_rng(3)
    c = rng.integers(0, 200, (n, 8)).astype(np.uint8)
    q = rng.integers(50, 150, 8).astype(np.uint8)
    c[5], c[5 + nb], c[5 + 3 * nb] = q, q, q
    cf, qf = c.astype(np.float32), q.astype(np.float32)[None]
    info = {}
    di, ds = knn.topk_device(cf, qf, k, "euclidean", two_stage=True,
                             l_per_bucket=1, info=info)
    assert info["exact_fallback"] is True
    want_i, want_s = _plain_rows(plain, c, q, k, None)
    assert want_i[:3].tolist() == [5, 5 + nb, 5 + 3 * nb]
    assert np.array_equal(di[0], want_i)
    assert np.array_equal(ds[0], want_s)
    # enough candidates a bucket and the proof holds
    info = {}
    di, _ = knn.topk_device(cf, qf, k, "euclidean", two_stage=True,
                            l_per_bucket=3, info=info)
    assert info["exact_fallback"] is False
    assert np.array_equal(di[0], want_i)


@pytest.mark.parametrize("metric", list(knn.METRICS))
@pytest.mark.parametrize("k", [10, 100])
def test_tiers_agree_on_the_set_on_gaussian_floats(metric, k):
    rng = np.random.default_rng(11)
    c = rng.standard_normal((20000, 32), dtype=np.float32)
    q = c[[7, 9000]] + 0.05 * rng.standard_normal((2, 32),
                                                  dtype=np.float32)
    hi, _ = knn.topk_host(c, q, k, metric)
    for two_stage in (None, False):
        di, _ = knn.topk_device(c, q, k, metric, two_stage=two_stage)
        for b in range(len(q)):
            assert set(di[b].tolist()) == set(hi[b].tolist())


_NB = 8192 // knn.BUCKET_SIZE      # bucket j holds rows j, j + _NB, ...


@pytest.mark.parametrize("live,k", [
    # every bucket holds at most L live rows
    ([3, 4000, 8000], 10),
    # bucket 5 holds four of nine live rows where L is 3, and row 0 is
    # live: an exhausted bucket 0 emits (-inf, 0), row 0 a second time
    ([0, _NB, 5, 5 + _NB, 5 + 2 * _NB, 5 + 3 * _NB,
      10 + _NB, 11 + _NB, 12 + _NB], 10),
    # all live rows in ONE bucket, more than L of them
    ([7 + i * _NB for i in range(6)], 10),
    # exactly k live rows, five of them in one bucket
    ([9 + i * _NB for i in range(5)] + [1, 2, 3, 4, 6], 10),
    # one live row; none
    ([_NB], 10),
    ([], 10),
])
def test_fewer_live_rows_than_k_come_back_whole(live, k):
    c = np.random.default_rng(1).integers(0, 9, (8192, 8)).astype(
        np.float32)
    assert knn.plan_two_stage(len(c), k) == 3
    mask = np.zeros(len(c), bool)
    mask[live] = True
    q = c[[0, 5 + _NB]]                # a batch: one proof for both
    hi, hs = knn.topk_host(c, q, k, "euclidean", mask=mask)
    di, ds = knn.topk_device(c, q, k, "euclidean", mask=mask)
    for r in range(len(q)):
        got = np.isfinite(ds[r])
        assert sorted(hi[r].tolist()) == sorted(live)[:k]
        assert di[r][got].tolist() == hi[r].tolist()
        assert np.array_equal(ds[r][got], hs[r])


def test_the_plan_keeps_failed_proofs_rare_or_takes_the_full_row():
    for n, k in ((8192, 10), (20000, 10), (100_000, 100),
                 (1_000_000, 10), (1_000_000, 100)):
        l_per = knn.plan_two_stage(n, k)
        assert 1 <= l_per <= knn.MAX_PER_BUCKET
        assert knn.fallback_probability(
            n // knn.BUCKET_SIZE, k, l_per) <= knn.FALLBACK_BUDGET
    assert knn.plan_two_stage(1000, 5) == 0        # too small to bucket
    assert knn.plan_two_stage(8192, 100) == 0      # too few buckets


# ---------------------------------------------------------------------------
# schema: approximation is asked for, never a matter of size
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("text,arg,approx", [
    ("e: float32vector @index(vector) .", "", False),
    ("e: float32vector @index(vector(ivf)) .", "ivf", True),
])
def test_vector_tokenizer_argument_round_trips(text, arg, approx):
    ps = parse_schema(text)[0][0]
    assert ps.tokenizers == ["vector"]
    assert (ps.vector_arg, ps.vector_approx) == (arg, approx)
    assert ps.describe() == text
    assert parse_schema(ps.describe())[0][0] == ps


@pytest.mark.parametrize("text", [
    "e: float32vector @index(vector(hnsw)) .",
    "e: float32vector @index(vector(exact)) .",   # exact has one spelling
    "e: float32vector @index(vector(ivf) .",
    "n: string @index(term(ivf)) .",
])
def test_other_tokenizer_arguments_are_refused(text):
    with pytest.raises(ValueError):
        parse_schema(text)


def _clustered_rdf(n, d=4, seed=40):
    rng = np.random.default_rng(seed)
    centres = rng.standard_normal((16, d)).astype(np.float32)
    vecs = centres[rng.integers(0, 16, n)] + np.float32(0.3) \
        * rng.standard_normal((n, d)).astype(np.float32)
    return "\n".join(
        f'<0x{i + 1:x}> <embedding> "{list(map(float, vecs[i]))}" .'
        for i in range(n))


@pytest.mark.parametrize("index,trained", [
    ("vector", False), ("vector(ivf)", True)])
def test_size_alone_never_trains_or_serves_the_quantized_tier(
        index, trained):
    db = GraphDB(prefer_device=False, vec_index_min_rows=100)
    db.alter(f"embedding: float32vector @index({index}) .")
    db.mutate(set_nquads=_clustered_rdf(500), commit_now=True)
    db.rollup_all()
    assert (db.tablets["embedding"].vector_ivf() is not None) == trained
    q = ('{ q(func: similar_to(embedding, 3, "[1.0, 0.0, -1.0, 0.5]"))'
         ' { uid } }')
    tiers = db.query(q, explain="analyze")["extensions"]["explain"][
        "tiers"]["vector"]
    assert (tiers[0]["tier"] == "quantized") == trained
    # an index that came with the tablet (an older snapshot's) is not
    # consulted either, unless the schema asks
    if not trained:
        db.build_vector_index("embedding")
        tiers = db.query(q, explain="analyze")["extensions"][
            "explain"]["tiers"]["vector"]
        assert tiers[0]["tier"] == "exact"


# ---------------------------------------------------------------------------
# (b) the served path: device.call, counters, the block as a tile
# ---------------------------------------------------------------------------


RARE = 99                         # a category of three rows


def _category(i):
    return RARE if i in (10, 3000, 5005) else i % 5


def _int_db(n=6000, d=8, seed=2, **kw):
    rng = np.random.default_rng(seed)
    vecs = rng.integers(0, 256, (n, d))
    rdf = "\n".join(
        f'<0x{i + 1:x}> <embedding> "{vecs[i].tolist()}" .\n'
        f'<0x{i + 1:x}> <id> "{i}" .\n'
        f'<0x{i + 1:x}> <category> "{_category(i)}" .'
        for i in range(n))
    db = GraphDB(**kw)
    db.alter("embedding: float32vector @index(vector) .\n"
             "id: int @index(int) .\n"
             "category: int @index(int) .")
    db.mutate(set_nquads=rdf, commit_now=True)
    db.rollup_all()
    return db, vecs


def _counter(name):
    return sum(v for k, v in metrics.snapshot()["counters"].items()
               if k.split("{")[0] == name)


def _gauge(name):
    return sum(v for k, v in metrics.snapshot()["gauges"].items()
               if k.split("{")[0] == name)


MASK_SOURCES = ("none", "tile_hit", "tile_miss", "call")


def _mask_sources():
    counters = metrics.snapshot()["counters"]
    return {src: counters.get(
        'similar_mask_total{source="%s"}' % src, 0)
        for src in MASK_SOURCES}


class _Served:
    """One query against the device tier: its `data` as JSON, what
    the mask counters and the `similar_to` span say it did."""

    def __init__(self, db, q, **kw):
        before, masked = _mask_sources(), _counter("similar_masked_total")
        tracing.clear()
        self.data = json.dumps(db.query(q, **kw)["data"])
        after = _mask_sources()
        self.sources = {s: after[s] - before[s] for s in MASK_SOURCES
                        if after[s] != before[s]}
        self.uploads = _counter("similar_masked_total") - masked
        self.span = [sp for sp in tracing.recent_spans()
                     if sp["name"] == "similar_to"][-1]["args"]


ROOT_Q = ('{ q(func: similar_to(embedding, 10, "%s", "euclidean")) '
          '{ uid val(similar_to_score) } }')
FILTER_Q = ('{ q(func: eq(category, 3)) @filter(similar_to(embedding, '
            '10, "%s", "euclidean")) { uid } }')


@pytest.mark.parametrize("template", [ROOT_Q, FILTER_Q],
                         ids=["root", "filter"])
def test_a_served_similar_to_runs_inside_device_call(template):
    db, vecs = _int_db()
    host, _ = _int_db(prefer_device=False)
    q = template % vecs[17].tolist()
    calls = _counter("query_device_similar_total")
    masked = _counter("similar_masked_total")
    sources = _mask_sources()
    spent = _counter("similar_ns_total")
    tracing.clear()
    res = db.query(q)
    lat = res["extensions"]["server_latency"]
    assert lat["device_calls"] >= 1
    assert lat["device_wait_ns"] > 0 and lat["device_enqueue_ns"] > 0
    assert _counter("query_device_similar_total") == calls + 1
    # the root ships no mask; a category's goes up once, as its tile
    uploads, source = (0, "none") if template is ROOT_Q \
        else (1, "tile_miss")
    assert _counter("similar_masked_total") == masked + uploads
    sources[source] += 1
    assert _mask_sources() == sources
    assert _counter("similar_ns_total") > spent
    spans = {s["name"]: s for s in tracing.recent_spans()}
    call, sim = spans["device.call"], spans["similar_to"]
    assert call["args"]["family"] == "similar"
    assert call["args"]["program"] == "jit__topk_device_jit"
    assert call["parent_id"] == sim["span_id"]
    assert sim["args"]["k"] == 10 and sim["args"]["rows"] == 6000
    assert sim["args"]["exact_fallback"] == 0
    want_candidates = 6000 if template is ROOT_Q else 1200
    assert sim["args"]["candidates"] == want_candidates
    assert sim["args"]["mask"] == source
    # and the answer is the postings tier's, byte for byte
    assert json.dumps(res["data"]) == json.dumps(
        host.query(q)["data"])


def test_the_vector_block_is_a_counted_and_evictable_tile():
    db, vecs = _int_db(device_hbm_budget=1 << 20)
    q = ROOT_Q % vecs[3].tolist()
    db.query(q)
    tab = db.tablets["embedding"]
    block = 6016 * 8 * 4        # rows padded to the bucket unit
    # the tile: the rows, and the all-live mask of a lane without one
    assert tab._device_vecs.rows.nbytes == block
    assert tab._device_vecs.nbytes == block + 6016
    assert db.device_cache.stats()["bytes"] >= block + 6016
    assert _gauge("device_cache_bytes") >= block
    gauges = metrics.snapshot()["gauges"]
    assert gauges['device_vector_block_bytes{predicate="embedding"}'] \
        == block
    # a second query finds the tile; nothing is uploaded again
    first = tab._device_vecs
    db.query(q)
    assert tab._device_vecs is first
    # a tile that does not fit beside it evicts the block (LRU)
    evictions = _counter("device_cache_evictions")
    other = db.tablets["category"]
    db.device_cache.put(other, "_device_values", _DeviceBytes())
    assert tab._device_vecs is None
    assert _counter("device_cache_evictions") == evictions + 1
    gauges = metrics.snapshot()["gauges"]
    assert gauges['device_vector_block_bytes{predicate="embedding"}'] \
        == 0
    # and the next query builds it again
    db.query(q)
    assert tab._device_vecs is not None


class _DeviceBytes:
    """Counts as a megabyte of DEVICE bytes in the tile cache (which
    tells device from host by duck type, engine/tile_cache.py)."""
    nbytes = 1 << 20


# ---------------------------------------------------------------------------
# (b2) the candidate mask lives on the device (PR 27)
# ---------------------------------------------------------------------------

MASK_BYTES = 6016                 # one bool a padded row
MASK_GAUGE = 'device_similar_mask_bytes{predicate="embedding"}'


@pytest.fixture(scope="module")
def host_db():
    return _int_db(prefer_device=False)[0]


def _in_category(c, vec, k=10, filt="similar_to(embedding, %d, \"%s\", "
                 "\"euclidean\")"):
    return ('{ q(func: eq(category, %d)) @filter(%s) { uid id } }'
            % (c, filt % (k, vec.tolist())))


def test_a_categorys_mask_is_uploaded_once_and_found_again(host_db):
    db, vecs = _int_db()
    first = _Served(db, _in_category(3, vecs[17]))
    assert first.sources == {"tile_miss": 1} and first.uploads == 1
    assert first.span["mask"] == "tile_miss"
    assert metrics.snapshot()["gauges"][MASK_GAUGE] == MASK_BYTES
    tile = [v for a, v in vars(db.tablets["embedding"]).items()
            if a.startswith("_device_mask@") and not a.endswith("_ts")]
    assert len(tile) == 1 and tile[0].n_cand == 1200
    assert db.device_cache.stats()["bytes"] \
        == db.tablets["embedding"]._device_vecs.nbytes + MASK_BYTES
    # another vector, the same category: nothing is built or uploaded
    q = _in_category(3, vecs[18])
    again = _Served(db, q)
    assert again.sources == {"tile_hit": 1} and again.uploads == 0
    assert again.span["mask"] == "tile_hit"
    assert again.span["candidates"] == 1200
    assert again.data == json.dumps(host_db.query(q)["data"])
    # another category: another tile
    other = _Served(db, _in_category(4, vecs[18]))
    assert other.sources == {"tile_miss": 1}
    assert metrics.snapshot()["gauges"][MASK_GAUGE] == 2 * MASK_BYTES


SIM = 'similar_to(embedding, 10, "%s", "euclidean")'


@pytest.mark.parametrize("root, filt, source", [
    # the set was narrowed before similar_to saw it: a mask for the call
    ("eq(category, 3)", "lt(id, 3000) AND " + SIM, "call"),
    ("eq(category, 3)", "(lt(id, 900) OR gt(id, 4000)) AND " + SIM,
     "call"),
    ("eq(category, 3)", "NOT lt(id, 3000) AND " + SIM, "call"),
    ("eq(category, [3, 4])", SIM, "call"),
    ("has(category)", "eq(category, 3) AND " + SIM, "call"),
    ("lt(id, 3000)", SIM, "call"),
    # similar_to is handed the root's posting itself, whatever the
    # tree does with its answer afterwards: the category's tile
    ("eq(category, 3)", SIM + " AND lt(id, 3000)", "tile_miss"),
    ("eq(category, 3)", SIM + " OR lt(id, 40)", "tile_miss"),
    ("eq(category, 3)", "NOT " + SIM, "tile_miss"),
], ids=["second-conjunct", "after-or", "after-not", "two-values",
        "eq-as-filter", "lt-root", "first-conjunct", "under-or",
        "under-not"])
def test_the_masks_source_follows_where_the_candidates_came_from(
        host_db, root, filt, source):
    db, vecs = _int_db()
    q = '{ q(func: %s) @filter(%s) { uid id } }' % (
        root, filt % vecs[17].tolist())
    got = _Served(db, q)
    assert got.sources == {source: 1}
    assert got.span["mask"] == source
    assert got.uploads == 1
    assert got.data == json.dumps(host_db.query(q)["data"])
    # a second time: only a posting's tile is found again
    again = _Served(db, q)
    assert again.sources == {
        "tile_hit" if source == "tile_miss" else "call": 1}
    assert again.data == got.data


def test_a_posting_bound_to_a_variable_is_still_that_posting(host_db):
    db, vecs = _int_db()
    q = ('{ A as var(func: eq(category, 3)) '
         'q(func: uid(A)) @filter(%s) { uid id } }'
         % (SIM % vecs[17].tolist()))
    got = _Served(db, q)
    assert got.sources == {"tile_miss": 1}
    assert got.data == json.dumps(host_db.query(q)["data"])


NEAR = [7, 7, 7, 7, 7, 7, 7, 7]


def _add_row(db):
    return db.mutate(set_nquads=(
        f'<0x2000> <embedding> "{NEAR}" .\n<0x2000> <id> "8191" .\n'
        '<0x2000> <category> "3" .'), commit_now=True)


def _leave_category(db):
    # row 3 (uid 4) keeps its vector and leaves the category: the
    # vector tablet stays clean, the filter's does not
    return db.mutate(del_nquads='<0x4> <category> * .', commit_now=True)


def _move_row(db):
    # uid 4's vector moves away: the filter's tablet stays clean
    return db.mutate(set_nquads='<0x4> <embedding> "[255, 255, 255, '
                     '255, 255, 255, 255, 255]" .', commit_now=True)


@pytest.mark.parametrize("change, found", [
    (_add_row, {"0x2000": True}), (_leave_category, {"0x4": False}),
    (_move_row, {"0x4": False})], ids=["add", "leave", "move"])
def test_a_tile_never_serves_a_snapshot_it_was_not_built_for(
        change, found):
    db, vecs = _int_db()
    host, _ = _int_db(prefer_device=False)
    # the query sits on row 3 (uid 0x4, category 3) or on the new row
    at = NEAR if change is _add_row else vecs[3].tolist()
    q = '{ q(func: eq(category, 3)) @filter(%s) { uid } }' % (SIM % at)

    def uids(data):
        return {r["uid"] for r in json.loads(data)["q"]}

    old_ts = db.coordinator.max_assigned()
    before = _Served(db, q)
    assert before.sources == {"tile_miss": 1}
    assert before.data == json.dumps(host.query(q)["data"])
    change(db)
    change(host)
    # after the commit, before a rollup: a tablet is dirty, the tile
    # is not consulted
    after = _Served(db, q)
    assert after.sources == {"call": 1}
    assert after.data == json.dumps(host.query(q)["data"])
    for uid, there in found.items():
        assert (uid in uids(after.data)) == there
        assert (uid in uids(before.data)) != there
    # a root call too: no mask while the overlay touches nothing
    # of the vector tablet, the view's own mask where it does
    root = _Served(db, ROOT_Q % at)
    assert root.sources == {
        "none" if change is _leave_category else "call": 1}
    assert root.data == json.dumps(host.query(ROOT_Q % at)["data"])
    # below the commit the old answer stands, from the old tile
    then = _Served(db, q, read_ts=old_ts)
    assert then.data == before.data
    assert then.sources == ({"tile_hit": 1} if change is _move_row
                            else {"call": 1})
    # after a rollup the tablets are clean under a new base_ts: the
    # old tile is not found, a new one is built and then served
    db.rollup_all()
    host.rollup_all()
    rolled = _Served(db, q)
    assert rolled.sources == {"tile_miss": 1}
    assert rolled.data == after.data
    assert rolled.data == json.dumps(host.query(q)["data"])
    hit = _Served(db, q)
    assert hit.sources == {"tile_hit": 1} and hit.data == after.data
    assert metrics.snapshot()["gauges"][MASK_GAUGE] == MASK_BYTES


def test_a_mask_tile_is_evicted_under_the_budget_and_rebuilt(host_db):
    db, vecs = _int_db(device_hbm_budget=1 << 20)
    q = _in_category(3, vecs[17])
    first = _Served(db, q)
    tab = db.tablets["embedding"]
    assert metrics.snapshot()["gauges"][MASK_GAUGE] == MASK_BYTES
    evictions = _counter("device_cache_evictions")
    db.device_cache.put(db.tablets["category"], "_device_values",
                        _DeviceBytes())
    # block and mask both went, and the tablet keeps no trace of it
    assert _counter("device_cache_evictions") >= evictions + 2
    assert tab._device_vecs is None
    assert metrics.snapshot()["gauges"][MASK_GAUGE] == 0
    assert not [a for a in vars(tab) if a.startswith("_device_mask@")]
    again = _Served(db, q)
    assert again.sources == {"tile_miss": 1} and again.uploads == 1
    assert again.data == first.data
    assert again.data == json.dumps(host_db.query(q)["data"])
    assert metrics.snapshot()["gauges"][MASK_GAUGE] == MASK_BYTES


def test_a_category_smaller_than_k_answers_from_the_full_row(host_db):
    db, vecs = _int_db()
    for vec, source in ((vecs[17], "tile_miss"), (vecs[18], "tile_hit")):
        q = _in_category(RARE, vec)
        got = _Served(db, q)
        assert got.sources == {source: 1}
        assert got.span["candidates"] == 3
        # 6,000 rows plan the two-stage reduce; three live rows prove
        # nothing, and the full row answers
        assert got.span["exact_fallback"] == 1
        assert len(json.loads(got.data)["q"]) == 3
        assert got.data == json.dumps(host_db.query(q)["data"])


def _sets(name):
    rng = np.random.default_rng(5)
    rows = np.unique(rng.integers(1000, 50000, 4000).astype(np.uint64))
    some = rng.choice(rows, 300, replace=False)
    return rows, {
        "empty": np.empty(0, np.uint64),
        "no-overlap": rows[:50] + np.uint64(100000),
        "all-rows": rows,
        "below-and-above": np.asarray([1, 2, 999, 50001, 1 << 40],
                                      np.uint64),
        "some": np.sort(some),
        "some-and-strangers": np.sort(np.concatenate(
            [some, np.asarray([3, 70000], np.uint64)])),
        "duplicates": np.sort(np.concatenate([some, some[:40]])),
        "unsorted": some,
        "one-row": rows[-1:],
    }[name]


@pytest.mark.parametrize("name", [
    "empty", "no-overlap", "all-rows", "below-and-above", "some",
    "some-and-strangers", "duplicates", "unsorted", "one-row"])
def test_candidate_mask_is_member_of_probed_the_other_way(name):
    from dgraph_tpu.query.executor import _member_of
    rows, cand = _sets(name)
    # _member_of asks for a sorted unique set; candidate_mask does not
    want = _member_of(rows, np.unique(cand))
    got = knn.candidate_mask(rows, cand)
    assert got.dtype == bool and got.tolist() == want.tolist()
    padded = knn.candidate_mask(rows, cand, knn.padded_rows(len(rows)))
    assert len(padded) == knn.padded_rows(len(rows))
    assert padded[:len(rows)].tolist() == want.tolist()
    assert not padded[len(rows):].any()
    assert not knn.candidate_mask(rows[:0], cand).any()


@pytest.mark.parametrize("form", ["live", "padded", "device"])
def test_topk_device_takes_a_mask_from_the_host_or_the_device(
        form, caplog):
    import logging

    import jax
    c, q = _tied_corpus(8200, 3)
    n_pad = knn.padded_rows(len(c))
    block = jax.numpy.asarray(knn.pad_rows(c.astype(np.float32)))
    mask = np.arange(len(c)) % 3 != 0
    want = knn.topk_host(c.astype(np.float32), q, 10, "euclidean",
                         mask=mask)
    padded = np.zeros(n_pad, bool)
    padded[:len(c)] = mask
    forms = {"live": mask, "padded": padded,
             "device": jax.device_put(padded)}
    got = knn.topk_device(block, q, 10, "euclidean", mask=forms[form],
                          n_real=len(c))
    assert got[0].tolist() == want[0].tolist()
    assert got[1].tolist() == want[1].tolist()
    # host or device, one operand: the other forms compile nothing
    with caplog.at_level(logging.DEBUG,
                         logger="jax._src.interpreters.pxla"):
        for other in forms.values():
            again = knn.topk_device(block, q, 10, "euclidean",
                                    mask=other, n_real=len(c))
            assert again[0].tolist() == want[0].tolist()
    assert not [r for r in caplog.records
                if "Compiling" in r.getMessage()]



# ---------------------------------------------------------------------------
# (c) the benchmark's dataset
# ---------------------------------------------------------------------------


def _rdf(sift, scale, seed, variant=""):
    out = io.StringIO()
    facts = sift.write_rdf(out, scale, seed, variant)
    return out.getvalue(), facts


def test_sift_same_seed_same_bytes_other_seed_other_rows(sift):
    a, fa = _rdf(sift, 2, 7)
    b, fb = _rdf(sift, 2, 7)
    c, _ = _rdf(sift, 2, 8)
    assert a == b and fa == fb
    assert a != c
    assert fa["rdf"] == a.count("\n") == 6000
    assert fa["seed"] == 7 and sum(fa["category_sizes"]) == 2000
    vecs, cats = sift.corpus(2, 7)
    assert vecs.shape == (2000, sift.DIM) and vecs.dtype == np.uint8
    other, _ = sift.corpus(2, 8)
    assert (vecs != other).any(axis=1).mean() > 0.99
    # the text is the rows: line 0 is row 0's literal
    first = a.split("\n", 1)[0]
    assert first.startswith('<0x1> <embedding> "[')
    got = np.array(first.split('"')[1].strip("[]").split(), np.uint8)
    assert np.array_equal(got, vecs[0])
    assert "0x" not in open(os.path.join(
        BENCH, "traffic", "queries", "knn10.gql")).read()


def test_sift_value_range_and_category_skew(sift):
    vecs, cats = sift.corpus(20, 1)
    assert vecs.min() == 0 and 200 < vecs.max() <= 255
    share = np.bincount(cats, minlength=sift.N_CATEGORIES) / len(cats)
    assert 0.17 < share[0] < 0.25 and 0.001 < share[-1] < 0.006
    w = sift.category_weights()
    assert w[0] == pytest.approx(0.207, abs=0.002)


def test_sift_traffic_file_regenerates_byte_for_byte(sift):
    with open(os.path.join(BENCH, "traffic", "knn-mix.json")) as f:
        text = f.read()
    assert text == json.dumps(sift.traffic_mix(), indent=1) + "\n"
    mix = json.loads(text)
    assert (mix["loop"], mix["clients"], mix["bindings"]) \
        == ("closed", 8, 32)
    lits = mix["templates"][0]["params"]["vec"]["choice"]
    assert lits == sift.query_literals() and len(set(lits)) == 256
    assert mix["templates"][2]["params"]["c"] == {"int": [0, 63]}


def test_sift_control_variant_moves_one_component_of_a_hundredth(sift):
    sound = sift.corpus(3, 5)[0].astype(np.int16).copy()
    bent = sift.corpus(3, 5, "off-by-one")[0].astype(np.int16)
    delta = bent - sound
    rows = np.flatnonzero((delta != 0).any(axis=1))
    assert len(rows) == 30                       # 1% of 3,000
    assert ((delta[rows] != 0).sum(axis=1) == 1).all()
    assert set(np.unique(np.abs(delta[rows]).max(axis=1))) == {1}
    with pytest.raises(ValueError):
        sift.corpus(3, 5, "rounded")


def test_sift_refuses_a_program_whose_schema_cannot_ask_for_ivf(
        sift, tmp_path, monkeypatch):
    """A program in which size, not the schema, chooses the quantized
    tier cannot give this deployment's guarantee: the dataset writes
    nothing for it (and the harness's run ends there, not in a
    measured window of answers that differ by design)."""
    sift.require_exact_program()                 # this program can
    old = tmp_path / "dgraph_tpu" / "models"
    old.mkdir(parents=True)
    (tmp_path / "dgraph_tpu" / "__init__.py").write_text("")
    (old / "__init__.py").write_text("")
    (old / "schema.py").write_text(
        "def parse_schema(text):\n"
        "    raise ValueError(\"schema: bad index arg '('\")\n")
    monkeypatch.setattr(sift, "PROGRAM_ROOT", str(tmp_path))
    out = io.StringIO()
    with pytest.raises(RuntimeError, match="bad index arg"):
        sift.write_rdf(out, 1, 7)
    assert out.getvalue() == ""
    monkeypatch.undo()
    assert _rdf(sift, 1, 7)[1]["rows"] == 1000


# ---------------------------------------------------------------------------
# (d) the plain reference against the program's postings tier
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [11, 2400000011, 3100000019])
def test_plain_reference_equals_the_postings_tier(sift, plain, traffic,
                                                  seed, tmp_path):
    from dgraph_tpu.ingest.bulk import bulk_load

    scale = 3
    rdf = tmp_path / "g.rdf"
    with open(rdf, "w") as f:
        facts = sift.write_rdf(f, scale, seed)
    db = bulk_load([str(rdf)], schema=sift.SCHEMA,
                   db=GraphDB(prefer_device=False))
    mix = traffic.load_mix(os.path.join(BENCH, "traffic",
                                        "knn-mix.json"))
    mix["bindings"] = 5
    pool = traffic.build_pool(mix, sift, scale, facts, seed)
    assert {e["name"] for e in pool} == set(plain.ANSWERS)
    assert len(pool) == 15
    for e in pool:
        got = json.loads(json.dumps(db.query(e["query"])["data"]))
        want = plain.ANSWERS[e["name"]](sift, scale, facts, e["query"])
        assert got == want, e["name"]
        if e["name"] == "knn10_in_category":   # the smallest hold 9
            assert 1 <= len(want["q"]) <= 10
        else:
            assert len(want["q"]) == (100 if e["name"] == "knn100"
                                      else 10)
