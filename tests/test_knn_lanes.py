"""The `similar_to` calls in flight ride ONE device call (PR 46):
ops/knn's lanes program (a query row, a mask and a k a lane; a lane's
bits the same alone and in company; the proof a lane at its own k;
one compiled shape a largest k), and the executor's site (a
rendezvous of family `similar` on the resident block: riders share
calls and keep their own accounts, a failed call fails its own
riders, another base_ts is another block and another rendezvous)."""

import json
import logging
import threading
import time

import jax
import numpy as np
import pytest

from dgraph_tpu.ops import knn
from dgraph_tpu.query import executor as executor_mod
from dgraph_tpu.query.devicecall import Rendezvous
from dgraph_tpu.utils import metrics, tracing
from dgraph_tpu.utils.reqctx import DeadlineExceeded, RequestContext

from test_knn_exact import FILTER_Q, ROOT_Q, _counter, _int_db

# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------

N = 70_000          # rows enough for the two-stage reduce at k 100
# (k, where the lane's mask lives) of a full call; "none" takes the
# block's all-live resident
LANE_MIX = [(10, "none"), (100, "none"), (10, "tile"), (10, "host"),
            (100, "tile"), (10, "none"), (100, "host"), (10, "tile")]


class _World:
    """A block on the device as engine/device_cache keeps it, eight
    lanes over it, and what they answer in company."""

    def __init__(self, kind):
        rng = np.random.default_rng(46)
        if kind == "whole":     # few distinct values: ties everywhere
            self.metric = "euclidean"
            self.c = rng.integers(0, 6, (N, 16)).astype(np.float32)
            self.q = rng.integers(0, 6, (knn.LANES, 16)).astype(np.float32)
        else:
            self.metric = "cosine"
            self.c = rng.standard_normal((N, 32), dtype=np.float32)
            self.q = self.c[rng.integers(0, N, knn.LANES)] + 0.05 \
                * rng.standard_normal((knn.LANES, 32), dtype=np.float32)
        rows = knn.pad_rows(self.c)
        self.rows = jax.numpy.asarray(rows)
        self.live = jax.device_put(np.ones(len(rows), bool))
        self.host_masks, self.lanes = [], []
        for i, (k, where) in enumerate(LANE_MIX):
            mask = None if where == "none" else rng.random(N) < 0.3
            self.host_masks.append(mask)
            if where == "tile":         # over the padded rows, resident
                padded = np.zeros(len(rows), bool)
                padded[:N] = mask
                mask = jax.device_put(padded)
            self.lanes.append((self.q[i], k, mask))
        assert knn.plan_two_stage(N, 100) > 0      # the reduce engages
        self.company = self.call(self.lanes)

    def call(self, lanes, **kw):
        return knn.land_lanes(knn.launch_lanes(
            self.rows, self.live, lanes, self.metric, N, **kw))


@pytest.fixture(scope="module")
def worlds():
    made = {}

    def world(kind):
        if kind not in made:
            made[kind] = _World(kind)
        return made[kind]
    return world


@pytest.mark.parametrize("lane", range(knn.LANES))
@pytest.mark.parametrize("kind", ["whole", "gauss"])
def test_a_lanes_bits_are_the_same_alone_and_in_company(worlds, kind,
                                                        lane):
    w = worlds(kind)
    k = LANE_MIX[lane][0]
    idx, sc, fell_back = w.company
    assert not fell_back and idx.shape == (knn.LANES, 100)
    # alone: lane 0 of a call of its own, whose largest k is its own
    # (another program where k is 10), seven dead lanes beside it
    a_idx, a_sc, a_fell = w.call([w.lanes[lane]])
    assert not a_fell and a_idx.shape == (knn.LANES, k)
    assert idx[lane, :k].tolist() == a_idx[0].tolist()
    assert sc[lane, :k].view(np.int32).tolist() \
        == a_sc[0].view(np.int32).tolist()
    # and at another seat, among other neighbours
    shuffled = [w.lanes[(lane + 3) % knn.LANES], w.lanes[lane]]
    s_idx, s_sc, _ = w.call(shuffled)
    assert s_idx[1, :k].tolist() == a_idx[0].tolist()
    assert s_sc[1, :k].view(np.int32).tolist() \
        == a_sc[0].view(np.int32).tolist()
    # the host tier's answer, in set and order
    h_idx, h_sc = knn.topk_host(w.c, w.q[lane], k, w.metric,
                                mask=w.host_masks[lane])
    assert idx[lane, :k].tolist() == h_idx[0].tolist()
    if kind == "whole":     # float32 is exact there: the scores too
        assert sc[lane, :k].tolist() == h_sc[0].tolist()


def _spread_rows(n_live, offset=0):
    """`n_live` rows no two of which share a bucket (bucket j holds
    rows j, j + nb, ...)."""
    return np.arange(n_live) * 7 + offset


@pytest.fixture(scope="module")
def small_category(worlds):
    """A k-100 lane with no mask beside a k-10 lane whose category has
    50 live rows: fewer than the call's largest k, more than its own."""
    w = worlds("whole")
    mask = np.zeros(N, bool)
    mask[_spread_rows(50)] = True
    return w, mask, [(w.q[0], 100, None), (w.q[1], 10, mask)]


def test_a_lane_is_proved_at_its_own_k_not_the_calls(small_category):
    w, mask, lanes = small_category
    idx, sc, fell_back = w.call(lanes)
    assert not fell_back
    for lane, (q, k, m) in enumerate(lanes):
        h_idx, h_sc = knn.topk_host(w.c, q, k, w.metric, mask=m)
        assert idx[lane, :k].tolist() == h_idx[0].tolist()
        assert sc[lane, :k].tolist() == h_sc[0].tolist()
    # beyond its own k the masked lane's row is not an answer: the
    # category has no 51st row
    assert not np.isfinite(sc[1, 50:]).any()


@pytest.mark.parametrize("live", [5, 0], ids=["five-live", "none-live"])
def test_a_lane_short_of_its_own_k_sends_the_call_to_the_full_row(
        small_category, live):
    w, mask, lanes = small_category
    few = np.zeros(N, bool)
    few[_spread_rows(live, offset=3)] = True
    lanes = lanes + [(w.q[2], 10, few)]
    idx, sc, fell_back = w.call(lanes)
    assert fell_back
    # every lane of that call is still right
    for lane, (q, k, m) in enumerate(lanes):
        h_idx, h_sc = knn.topk_host(w.c, q, k, w.metric, mask=m)
        got = np.isfinite(sc[lane, :k])
        assert got.sum() == min(k, int(m.sum()) if m is not None else k)
        assert idx[lane, :k][got].tolist() == h_idx[0].tolist()
        assert sc[lane, :k][got].tolist() == h_sc[0].tolist()


def test_a_failed_proof_of_one_lane_is_the_calls_fallback(worlds):
    """Forced to one candidate a bucket, a lane whose two nearest share
    a bucket fails its proof; the lane beside it is answered from the
    full row with it, and both are exact."""
    w = worlds("whole")
    nb = len(w.rows) // knn.BUCKET_SIZE
    q = w.c[[5, 5 + nb]].mean(axis=0)
    mask = np.zeros(N, bool)
    mask[[5, 5 + nb, 9, 11]] = True        # 5 and 5 + nb: one bucket
    lanes = [(w.q[0], 4, None), (q, 2, mask)]
    idx, sc, fell_back = w.call(lanes, two_stage=True, l_per_bucket=1)
    assert fell_back
    for lane, (qv, k, m) in enumerate(lanes):
        h_idx, _ = knn.topk_host(w.c, qv, k, w.metric, mask=m)
        assert idx[lane, :k].tolist() == h_idx[0].tolist()
    # alone and unforced the same lane is proved
    assert not w.call(lanes[1:])[2]


@pytest.mark.parametrize("riders", range(1, knn.LANES + 1))
def test_every_rider_count_runs_one_compiled_shape(worlds, riders, caplog):
    w = worlds("whole")
    tens = [lane for lane in w.lanes if lane[1] == 10]
    tens = (tens * 2)[:riders]
    w.call(tens[:1])                    # the program of largest k 10
    cached = knn._topk_device_jit._cache_size()
    with caplog.at_level(logging.DEBUG,
                         logger="jax._src.interpreters.pxla"):
        idx, sc, fell_back = w.call(tens)
    assert not [r for r in caplog.records
                if "Compiling" in r.getMessage()]
    # device operands all (the block, its all-live mask, mask tiles):
    # the jit cache itself does not grow with the rider count
    if all(isinstance(m, jax.Array) or m is None for _, _, m in tens):
        assert knn._topk_device_jit._cache_size() == cached
    # dead lanes answer nothing and fail nothing
    assert not fell_back and idx.shape == (knn.LANES, 10)


def test_a_call_takes_one_to_lanes_riders(worlds):
    w = worlds("whole")
    for lanes in ([], w.lanes + w.lanes[:1]):
        with pytest.raises(ValueError):
            knn.launch_lanes(w.rows, w.live, lanes, w.metric, N)


def test_the_one_shot_entry_is_the_lanes_program(worlds):
    """topk_device sends a query matrix LANES rows a call of the same
    program: eleven rows are two calls, and a row's answer is what a
    lane of a served call gives."""
    w = worlds("whole")
    q = np.concatenate([w.q, w.q[:3] + 1])
    info = {}
    idx, sc = knn.topk_device(w.rows, q, 10, w.metric, n_real=N,
                              info=info)
    assert idx.shape == (11, 10) and info == {"exact_fallback": False}
    for lane in (0, 5):                 # LANE_MIX: k 10, no mask
        assert idx[lane].tolist() == w.company[0][lane, :10].tolist()
        assert sc[lane].view(np.int32).tolist() \
            == w.company[1][lane, :10].view(np.int32).tolist()
    h_idx, _ = knn.topk_host(w.c, q, 10, w.metric)
    assert idx.tolist() == h_idx.tolist()


# ---------------------------------------------------------------------------
# the executor's site
# ---------------------------------------------------------------------------

CALLS = 'rendezvous_calls_total{family="similar"}'
RIDERS = 'rendezvous_riders_total{family="similar"}'
ROOT_100 = ROOT_Q.replace("embedding, 10,", "embedding, 100,")


def _meet(db):
    return Rendezvous.at(db.tablets["embedding"]._device_vecs, knn.LANES,
                         family="similar", key="euclidean")


def _hold_first_call(monkeypatch):
    """Keep the first call on the 'chip' until released, so that what
    arrives meanwhile is known to stand."""
    gate, calls = threading.Event(), []
    land0 = executor_mod._land_similar

    def land(handle, n):
        calls.append(n)
        if len(calls) == 1:
            gate.wait(30)
        return land0(handle, n)

    monkeypatch.setattr(executor_mod, "_land_similar", land)
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


def _until(what, seconds=20):
    deadline = time.monotonic() + seconds
    while not what() and time.monotonic() < deadline:
        time.sleep(0.001)
    assert what()


def _join(*thread_lists):
    for threads in thread_lists:
        for t in threads:
            t.join(60)
            assert not t.is_alive()


def _data(db, q, **kw):
    return json.dumps(db.query(q, **kw)["data"], separators=(",", ":"))


@pytest.fixture(scope="module")
def served():
    db, vecs = _int_db()
    host, _ = _int_db(prefer_device=False)
    # the mix of the benchmark's cell: root k 10, root k 100, k 10
    # inside a category
    queries = [(ROOT_Q, ROOT_100, FILTER_Q)[i % 3] % vecs[20 + i].tolist()
               for i in range(knn.LANES)]
    db.query(queries[0])                # the block is resident
    return db, host, queries


def test_requests_in_flight_ride_one_call_and_keep_their_own_accounts(
        served, monkeypatch):
    db, host, queries = served
    gate, calls = _hold_first_call(monkeypatch)
    metrics.reset()
    tracing.clear()
    out, first = _serve(db, queries[:1])
    _until(lambda: calls)
    more, rest = _serve(db, queries[1:])
    meet = _meet(db)
    _until(lambda: len(meet._waiting) == knn.LANES - 1)
    held_ns = 100_000_000
    time.sleep(held_ns / 1e9)
    gate.set()
    _join(first, rest)
    # two calls for eight requests: the lone one, then the seven that
    # stood behind it
    assert calls == [1, knn.LANES - 1]
    counters = metrics.snapshot()["counters"]
    assert counters[CALLS] == 2 and counters[RIDERS] == knn.LANES
    assert counters[RIDERS] > counters[CALLS]
    assert counters["query_device_similar_total"] == knn.LANES
    assert "similar_exact_fallback_total" not in counters
    replies = [out[0]] + [more[i] for i in range(knn.LANES - 1)]
    for i, rep in enumerate(replies):
        assert not isinstance(rep, BaseException), rep
        # byte for byte the postings tier's
        assert json.dumps(rep["data"], separators=(",", ":")) \
            == _data(host, queries[i])
        sl = rep["extensions"]["server_latency"]
        assert sl["device_calls"] == 1
        if i:       # its wait covers its stand behind the call in flight
            assert sl["device_wait_ns"] >= held_ns * 0.9
            assert sl["device_queue_ns"] >= held_ns * 0.9
        assert sl["device_wait_ns"] <= sl["processing_ns"]
    spans = tracing.recent_spans()
    flights = {s["span_id"]: s["args"] for s in spans
               if s["name"] == "device.flight"}
    assert sorted(a["lanes"] for a in flights.values()) \
        == [1, knn.LANES - 1]
    assert {a["family"] for a in flights.values()} == {"similar"}
    blocks = [s["args"] for s in spans if s["name"] == "device.call"]
    assert len(blocks) == knn.LANES
    for a in blocks:
        assert a["family"] == "similar"
        assert a["program"] == "jit__topk_device_jit"
        # every rider's block names the flight its result came from
        assert flights[a["flight"]]["lanes"] == a["lanes"]
    assert sorted(a["lanes"] for a in blocks) \
        == [1] + [knn.LANES - 1] * (knn.LANES - 1)
    assert sorted(a["batch_wait_us"] > 0 for a in blocks) \
        == [False] + [True] * (knn.LANES - 1)


def test_a_fallback_is_counted_once_a_call_and_said_by_every_rider(
        served, monkeypatch):
    """A category of three rows asked for ten: the lane is short of
    its own k, the call answers from the full row."""
    db, host, queries = served
    rare = queries[2].replace("eq(category, 3)", "eq(category, 99)")
    db.query(rare)                      # its mask tile is resident
    gate, calls = _hold_first_call(monkeypatch)
    metrics.reset()
    tracing.clear()
    out, first = _serve(db, queries[:1])
    _until(lambda: calls)
    # (both of k 10: at 6,000 rows a call of largest k 100 takes the
    # full row by plan, and that is no fallback)
    more, rest = _serve(db, [rare, queries[3]])
    _until(lambda: len(_meet(db)._waiting) == 2)
    gate.set()
    _join(first, rest)
    assert calls == [1, 2]
    assert _counter("similar_exact_fallback_total") == 1
    said = sorted(s["args"]["exact_fallback"]
                  for s in tracing.recent_spans()
                  if s["name"] == "similar_to")
    assert said == [0, 1, 1]
    for rep, q in ((more[0], rare), (more[1], queries[3])):
        assert json.dumps(rep["data"], separators=(",", ":")) \
            == _data(host, q)
    assert len(more[0]["data"]["q"]) == 3


def test_a_launch_that_raises_fails_its_own_riders_only(served,
                                                        monkeypatch):
    db, host, queries = served
    gate, calls = _hold_first_call(monkeypatch)
    launch0 = executor_mod._launch_similar
    launches = []

    def launch(block, metric, n_real, riders):
        launches.append(len(riders))
        if len(launches) == 2:
            raise RuntimeError("RESOURCE_EXHAUSTED")
        return launch0(block, metric, n_real, riders)

    monkeypatch.setattr(executor_mod, "_launch_similar", launch)
    out, first = _serve(db, queries[:1])
    _until(lambda: calls)
    more, rest = _serve(db, queries[1:4])
    _until(lambda: len(_meet(db)._waiting) == 3)
    before = _counter("query_device_similar_total")
    gate.set()
    _join(first, rest)
    assert launches == [1, 3]
    assert all(isinstance(more[i], RuntimeError) for i in range(3))
    # a block that raised counts no dispatch; the first request did
    assert _counter("query_device_similar_total") == before + 1
    assert json.dumps(out[0]["data"], separators=(",", ":")) \
        == _data(host, queries[0])
    # and the site serves on
    assert _data(db, queries[1]) == _data(host, queries[1])


def test_a_rider_past_its_deadline_leaves_and_the_call_lands_for_the_rest(
        served, monkeypatch):
    db, host, queries = served
    gate, calls = _hold_first_call(monkeypatch)
    out, first = _serve(db, queries[:1])
    _until(lambda: calls)
    more, rest = _serve(db, queries[2:4])
    meet = _meet(db)
    _until(lambda: len(meet._waiting) == 2)
    late, leaving = _serve(
        db, queries[1:2], ctxs={0: RequestContext.with_timeout(0.05)})
    _join(leaving)                      # the one with the deadline left
    assert isinstance(late[0], DeadlineExceeded)
    assert len(meet._waiting) == 2
    gate.set()
    _join(first, rest)
    assert calls == [1, 2]
    for i, rep in ((0, out[0]), (2, more[0]), (3, more[1])):
        assert json.dumps(rep["data"], separators=(",", ":")) \
            == _data(host, queries[i])


NEAR = [7, 7, 7, 7, 7, 7, 7, 7]


def _add_row(db):
    # a new row at the query's very place
    return db.mutate(set_nquads=f'<0x2000> <embedding> "{NEAR}" .\n'
                     '<0x2000> <category> "3" .', commit_now=True)


def _move_row(db):
    # uid 4's vector moves onto the query
    return db.mutate(set_nquads=f'<0x4> <embedding> "{NEAR}" .',
                     commit_now=True)


@pytest.mark.parametrize("change,uid", [(_add_row, "0x2000"),
                                        (_move_row, "0x4")],
                         ids=["add", "move"])
def test_riders_meet_only_over_the_block_their_read_ts_resolved_to(
        change, uid, monkeypatch):
    db, _ = _int_db()
    host, _ = _int_db(prefer_device=False)
    q = ROOT_Q % NEAR
    old = _data(db, q)
    assert old == _data(host, q) and uid not in old
    tab = db.tablets["embedding"]
    block, meet = tab._device_vecs, _meet(db)
    # two riders of the old snapshot, one on the 'chip', one standing
    gate, calls = _hold_first_call(monkeypatch)
    out, first = _serve(db, [q])
    _until(lambda: calls)
    more, rest = _serve(db, [q])
    _until(lambda: len(meet._waiting) == 1)
    # a commit: while readers below it are in flight nothing is folded,
    # the base block is every snapshot's, and the overlay is each
    # request's own (its mask, its host-side rows). A reader above the
    # commit stands with the one below it
    change(db)
    change(host)
    db.rollup_all()
    assert tab._device_vecs is block
    late, last = _serve(db, [q])
    _until(lambda: len(meet._waiting) == 2)
    gate.set()
    _join(first, rest, last)
    # ... and rides one call with it, each lane its own answer
    assert calls == [1, 2]
    for rep in (out[0], more[0]):
        assert json.dumps(rep["data"], separators=(",", ":")) == old
    new = json.dumps(late[0]["data"], separators=(",", ":"))
    assert new == _data(host, q) and uid in new
    # rolled up, the tablet has ANOTHER block under another base_ts,
    # with a rendezvous of its own
    db.rollup_all()
    host.rollup_all()
    assert _data(db, q) == new
    assert tab._device_vecs is not block and _meet(db) is not meet
    assert calls == [1, 2, 1]
    assert not meet._waiting and not _meet(db)._waiting


def test_another_metric_is_another_rendezvous_on_the_same_block(served):
    db, host, queries = served
    # (uids alone: a cosine's float32 and float64 differ in print)
    cosine = queries[2].replace('"euclidean"', '"cosine"')
    assert _data(db, cosine) == _data(host, cosine)
    block = db.tablets["embedding"]._device_vecs
    other = Rendezvous.at(block, knn.LANES, family="similar", key="cosine")
    assert other is not _meet(db) and other.family == "similar"
    assert Rendezvous.at(block, knn.LANES, family="similar",
                         key="cosine") is other


@pytest.mark.parametrize("family", ["recurse", "shortest", "similar"])
def test_every_family_counts_its_calls_and_riders(family):
    """`Rendezvous._launch` counts for every family alike; a launch
    that raises put no call on the device and counts none."""
    meet = Rendezvous(knn.LANES, family)
    calls = 'rendezvous_calls_total{family="%s"}' % family
    riders = 'rendezvous_riders_total{family="%s"}' % family
    before = metrics.snapshot()["counters"]
    gate = threading.Event()

    def land(handle, n):
        gate.wait(30)
        return list(handle)

    out, threads = {}, []
    for i in range(4):
        t = threading.Thread(target=lambda i=i: out.update(
            {i: meet.ride(i, list, land).result}))
        t.start()
        threads.append(t)
        # the first is on the 'chip' before the others come
        _until(lambda: meet._flight is not None
               and len(meet._waiting) == i)
    gate.set()
    _join(threads)
    assert out == {i: i for i in range(4)}

    def boom(items):
        raise RuntimeError("no room")

    with pytest.raises(RuntimeError):
        meet.ride(9, boom, land)
    after = metrics.snapshot()["counters"]
    assert after[calls] - before.get(calls, 0) == 2
    assert after[riders] - before.get(riders, 0) == 4
