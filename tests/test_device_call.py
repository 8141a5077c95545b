"""`device.call` (query/devicecall.py): every device dispatch counted
and timed at one place, rolled up into `extensions.server_latency`,
on the profiler's clock, under programs named for what they are; and
the front end's, the storage layer's and the collector's own clocks
(PERF.md section 3)."""

import ast
import glob
import json
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from dgraph_tpu.engine.db import GraphDB
from dgraph_tpu.utils import metrics, tracing

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# every counter that stands for a device dispatch: the benchmark's
# `device_ops_per_req` and `report_counters` read them by these names
DISPATCH_COUNTERS = (
    "query_device_count_page_total",
    "query_device_expand_total",
    "query_device_multisort_total",
    "query_device_orderkeys_total",
    "query_device_overlay_expand_total",
    "query_device_range_total",
    "query_device_recurse_total",
    "query_device_setops_total",
    "query_device_shortest_total",
    "query_device_similar_sharded_total",
    "query_device_similar_total",
    "query_device_sort_page_total",
    "query_fused_dispatch_total",
    "query_sharded_expand_total",
)
DEVICE_KEYS = ("device_calls", "device_enqueue_ns", "device_wait_ns",
               "device_fetch_ns")


def _graph(**kw) -> GraphDB:
    db = GraphDB(**kw)
    db.alter("name: string @index(exact) .\nage: int @index(int) .\n"
             "friend: [uid] @reverse @count .")
    nq = []
    for i in range(1, 60):
        nq += [f'<{i}> <name> "n{i}" .', f'<{i}> <age> "{i % 17}" .',
               f"<{i}> <friend> <{i * 7 % 59 + 1}> .",
               f"<{i}> <friend> <{i * 11 % 59 + 1}> ."]
    db.mutate(set_nquads="\n".join(nq))
    db.rollup_all(window=0)
    return db


PAGE = "{ q(func: has(age), orderasc: age, first: 5) { name friend { name } } }"


def test_device_forced_query_rolls_its_calls_up():
    db = _graph(device_min_edges=1)
    tracing.clear()
    sl = db.query(PAGE)["extensions"]["server_latency"]
    assert sl["device_calls"] >= 1
    phases = (sl["device_enqueue_ns"], sl["device_wait_ns"],
              sl["device_fetch_ns"])
    assert all(p > 0 for p in phases)
    assert sum(phases) <= sl["processing_ns"]
    calls = [s for s in tracing.recent_spans()
             if s["name"] == "device.call"]
    assert len(calls) == sl["device_calls"]
    # one place: the spans say what the roll-up says, to the rounding
    # of each phase to whole microseconds
    for key, attr in zip(DEVICE_KEYS[1:],
                         ("enqueue_us", "wait_us", "fetch_us")):
        total = sum(s["args"][attr] for s in calls)
        assert 0 <= sl[key] // 1000 - total <= len(calls)
    for s in calls:
        assert s["args"]["family"] and s["args"]["program"]
        assert s["args"]["out_bytes"] > 0
    # each call hangs under a stage's span of the same request
    by_id = {s["span_id"]: s for s in tracing.recent_spans()}
    assert all(by_id[s["parent_id"]]["name"] != "device.call"
               and s["trace_id"] == by_id[s["parent_id"]]["trace_id"]
               for s in calls)


def test_host_only_query_has_the_keys_at_zero():
    db = _graph(prefer_device=False)
    out = db.query(PAGE)
    sl = out["extensions"]["server_latency"]
    assert [sl[k] for k in DEVICE_KEYS] == [0, 0, 0, 0]
    # the reference's own message keeps the reference's fields
    assert set(out["extensions"]["latency"]) == {
        "parsing_ns", "processing_ns", "encoding_ns",
        "assign_timestamp_ns"}


def test_phase_counters_move_with_the_site_counter():
    db = _graph(device_min_edges=1)
    before = metrics.counters_snapshot()
    db.query(PAGE)
    moved = metrics.counters_delta(before)
    assert moved["query_device_sort_page_total"] == 1
    for phase in ("enqueue", "wait", "fetch"):
        key = ('device_call_ns_total{family="sort_page",'
               f'phase="{phase}"}}')
        assert moved[key] > 0


def test_a_block_that_never_dispatched_counts_nothing():
    from dgraph_tpu.query.devicecall import device_call
    from dgraph_tpu.engine.db import Latency

    lat = Latency()
    before = metrics.counters_snapshot()
    with device_call("query_device_setops_total", sink=lat):
        pass  # the callee declined before it reached the device
    with pytest.raises(RuntimeError):
        with device_call("query_device_setops_total", sink=lat) as dc:
            dc.wait(np.zeros(4))
            raise RuntimeError("after the dispatch")
    assert lat.device_calls == 0
    assert not metrics.counters_delta(before)


def _calls(tree):
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and node.args \
                and isinstance(node.args[0], ast.Constant):
            f = node.func
            yield (f.attr if isinstance(f, ast.Attribute)
                   else getattr(f, "id", "")), node.args[0].value


@pytest.fixture(scope="module")
def emission_sites():
    """{counter name -> [(file, emitting function), ...]} over the
    program's tree."""
    sites: dict = {}
    for path in glob.glob(os.path.join(_REPO, "dgraph_tpu", "**", "*.py"),
                          recursive=True):
        with open(path) as f:
            tree = ast.parse(f.read(), filename=path)
        for fn, name in _calls(tree):
            if fn in ("inc_counter", "device_call"):
                sites.setdefault(name, []).append(
                    (os.path.relpath(path, _REPO), fn))
    return sites


@pytest.mark.parametrize("counter", DISPATCH_COUNTERS)
def test_dispatch_counter_moves_only_in_the_helper(counter,
                                                   emission_sites):
    sites = emission_sites.get(counter, [])
    assert sites, f"{counter} has no dispatch site left"
    assert all(fn == "device_call" for _, fn in sites), sites


def test_no_dispatch_counter_is_missing_from_the_list(emission_sites):
    named = {n for n in emission_sites
             if n.startswith(("query_device_", "query_fused_dispatch",
                              "query_sharded_expand"))}
    # what times the calls must not read as one more dispatch: the
    # benchmark sums the `query_device_` prefix as a count
    assert named == set(DISPATCH_COUNTERS)


def test_profile_holds_the_programs_spans(tmp_path):
    from jax.profiler import ProfileData

    db = _graph(device_min_edges=1)
    db.query(PAGE)  # compile outside the trace
    with tracing.profile_device(str(tmp_path)):
        db.query(PAGE)
    (pb,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    host = {e.name for plane in ProfileData.from_file(pb).planes
            if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events}
    assert {"query", "execute", "device.call", "device.enqueue",
            "device.wait", "device.fetch", "encode"} <= host


def _post(url: str, body: str = ""):
    req = urllib.request.Request(url, data=body.encode(), method="POST")
    with urllib.request.urlopen(req) as resp:
        return json.loads(resp.read())


def test_http_request_phases_and_device_profile_route():
    from dgraph_tpu.server.http import serve

    httpd, alpha = serve(_graph(device_min_edges=1), block=False, port=0)
    try:
        base = f"http://127.0.0.1:{httpd.server_address[1]}"
        before = metrics.counters_snapshot()
        tracing.clear()
        out = _post(base + "/query", PAGE)
        assert out["extensions"]["server_latency"]["device_calls"] >= 1
        # `post` ends after the last byte is written: the client can
        # be back before the handler has counted
        deadline = time.monotonic() + 10.0
        while "http_requests_total" not in metrics.counters_delta(before) \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        moved = metrics.counters_delta(before)
        assert moved["http_requests_total"] == 1
        phases = {p: moved[f'http_request_ns_total{{phase="{p}"}}']
                  for p in ("pre", "engine", "post")}
        assert all(v > 0 for v in phases.values())
        sl = out["extensions"]["server_latency"]
        assert phases["engine"] >= sl["total_ns"]
        spans = {s["name"]: s for s in tracing.recent_spans()}
        assert spans["query"]["parent_id"] \
            == spans["http.request"]["span_id"]
        # a request that fails counts no phases
        before = metrics.counters_snapshot()
        with pytest.raises(urllib.error.HTTPError):
            _post(base + "/query", "{ q(func: nope")
        assert "http_requests_total" not in metrics.counters_delta(before)

        # the device profile: answers with the directory it wrote, and
        # refuses a second call while one runs
        got: list = []
        t = threading.Thread(target=lambda: got.append(_post(
            base + "/debug/device_profile?seconds=1.5")))
        t.start()
        while not alpha._device_profile_lock.locked() and t.is_alive():
            time.sleep(0.01)
        with pytest.raises(urllib.error.HTTPError) as refused:
            _post(base + "/debug/device_profile?seconds=0.1")
        assert refused.value.code == 429
        t.join(timeout=60)
        assert not t.is_alive()
        assert got and got[0]["seconds"] == 1.5
        assert glob.glob(os.path.join(got[0]["dir"], "plugins", "profile",
                                      "*", "*.xplane.pb"))
        import shutil
        shutil.rmtree(got[0]["dir"], ignore_errors=True)
    finally:
        httpd.shutdown()


def test_device_profile_is_guardian_only_under_acl():
    from dgraph_tpu.server.acl import AclError
    from dgraph_tpu.server.http import AlphaServer

    srv = AlphaServer(acl_secret=b"s3cret")
    with pytest.raises(AclError):
        srv.handle_device_profile({"seconds": "0.1"}, "")


def test_snapshot_load_says_where_its_time_went(tmp_path):
    from dgraph_tpu.storage.snapshot import load_snapshot, save_snapshot

    path = str(tmp_path / "p.snap")
    save_snapshot(_graph(prefer_device=False), path)
    tracing.clear()
    db = load_snapshot(path, GraphDB(device_min_edges=1))
    gauges = metrics.gauges_snapshot()
    took = {p: gauges[f'startup_phase_seconds{{phase="{p}"}}']
            for p in ("snapshot_read", "snapshot_decode", "index_build")}
    assert all(v >= 0 for v in took.values())
    (sp,) = [s for s in tracing.recent_spans()
             if s["name"] == "snapshot.load"]
    assert sum(took.values()) <= sp["dur_us"] / 1e6 + 1e-3
    # tiles are built on first use: that is when their phase appears
    tile = 'startup_phase_seconds{phase="tile_upload"}'
    was = gauges.get(tile, 0.0)
    db.query(PAGE)
    assert metrics.gauges_snapshot()[tile] > was


def test_collector_pauses_are_counted_once_watched():
    import gc

    metrics.watch_gc()
    metrics.watch_gc()  # once, however often it is asked
    assert gc.callbacks.count(metrics._on_gc) == 1
    try:
        metrics.collect_runtime_gauges()
        key = 'process_gc_pause_seconds_total{gen="2"}'
        was = metrics.counters_snapshot()[key]
        junk = [[i] for i in range(50_000)]
        gc.collect()
        del junk
        metrics.collect_runtime_gauges()
        assert metrics.counters_snapshot()[key] > was
        assert key in metrics.render_prometheus()
    finally:
        gc.callbacks.remove(metrics._on_gc)


def test_device_memory_peak_is_read_from_watched_devices():
    class Chip:
        id = 3

        def memory_stats(self):
            return {"peak_bytes_in_use": 1234}

    class Cpu:
        id = 0

        def memory_stats(self):
            return None  # the CPU backend reports none

    metrics.watch_devices([Chip(), Cpu()])
    try:
        metrics.collect_runtime_gauges()
        g = metrics.gauges_snapshot()
        assert g['device_memory_peak_bytes{device="3"}'] == 1234
        assert 'device_memory_peak_bytes{device="0"}' not in g
    finally:
        metrics.watch_devices([])


# -- where a request waits (PR 39): the stand at a rendezvous, the
# chip's queue by count, the interpreter by thread CPU time ------------


def _held_chip():
    """`launch`, `land` of a chip that keeps a call until `gate` is
    set and runs its calls in order (one behind another takes 10 ms
    more), and the list of the calls launched."""
    gate, calls, over = threading.Event(), [], []

    def launch(items):
        calls.append(list(items))
        over.append(threading.Event())
        return len(calls) - 1

    def land(handle, n):
        gate.wait(30)
        if handle and not over[handle - 1].is_set():
            over[handle - 1].wait(30)
            time.sleep(0.01)
        over[handle].set()
        return [("answer", x) for x in calls[handle]]

    return gate, calls, launch, land


def _ride_in_a_block(meet, launch, land, item, out):
    from dgraph_tpu.engine.db import Latency
    from dgraph_tpu.query.devicecall import device_call

    lat = Latency()
    with device_call("query_device_recurse_total", sink=lat,
                     program="t") as dc:
        ride = dc.wait_for(lambda: meet.ride(item, launch, land))
    out[item] = (lat, ride)


@pytest.fixture
def lead_and_rider():
    """One request that finds the chip free and one that joins while
    the first's call is on it -> ({item: (Latency, Ride)}, moved
    counters, the spans by name)."""
    from dgraph_tpu.query.devicecall import Rendezvous

    meet = Rendezvous(4, family="t")
    gate, calls, launch, land = _held_chip()
    out: dict = {}
    before = metrics.counters_snapshot()
    tracing.clear()
    threads = [threading.Thread(target=_ride_in_a_block,
                                args=(meet, launch, land, x, out))
               for x in ("lead", "rider")]
    threads[0].start()
    while not calls:
        time.sleep(0.001)
    threads[1].start()
    deadline = time.monotonic() + 10
    while len(meet._waiting) != 1 and time.monotonic() < deadline:
        time.sleep(0.001)
    time.sleep(0.02)
    gate.set()
    for t in threads:
        t.join(30)
        assert not t.is_alive()
    return out, metrics.counters_delta(before), tracing.recent_spans()


@pytest.mark.parametrize("who", ("lead", "rider"))
def test_the_stand_at_a_rendezvous_lies_inside_the_wait(lead_and_rider,
                                                        who):
    out, moved, _ = lead_and_rider
    lat, ride = out[who]
    assert lat.device_calls == 1
    assert lat.device_queue_ns == ride.waited_ns
    if who == "lead":       # it found the chip free
        assert lat.device_queue_ns == 0
    else:
        assert 0.02e9 * 0.9 <= lat.device_queue_ns <= lat.device_wait_ns
    assert lat.server_latency()["device_queue_ns"] == lat.device_queue_ns
    # a series of its own: the three phases' sum stays the block's time
    assert moved['device_call_queue_ns_total{family="recurse"}'] \
        == out["rider"][0].device_queue_ns
    assert not any(k.startswith("device_call_ns_total")
                   and 'phase="queue"' in k for k in moved)


def test_a_rider_counts_no_calls_ahead_and_a_flight_is_one(lead_and_rider):
    from dgraph_tpu.query import devicecall

    out, moved, spans = lead_and_rider
    assert devicecall._inflight == 0
    assert not any(k.startswith("device_call_ahead_total") for k in moved)
    assert all("ahead" not in s["args"] for s in spans
               if s["name"] == "device.call")
    metrics.collect_runtime_gauges()    # the gauge is read at a scrape
    assert metrics.gauges_snapshot()["device_calls_inflight"] == 0


@pytest.fixture
def lead_and_a_full_call():
    """One request that finds the chip free, then two that fill a call
    of two while the first's is still on it (PR 40) -> ({item:
    (Latency, Ride)}, moved counters, the spans by name)."""
    from dgraph_tpu.query.devicecall import Rendezvous

    meet = Rendezvous(2, family="t")
    gate, calls, launch, land = _held_chip()
    out: dict = {}
    before = metrics.counters_snapshot()
    tracing.clear()
    threads = [threading.Thread(target=_ride_in_a_block,
                                args=(meet, launch, land, x, out))
               for x in ("lead", "early", "filler")]
    threads[0].start()
    while not calls:
        time.sleep(0.001)
    threads[1].start()
    deadline = time.monotonic() + 10
    while len(meet._waiting) != 1 and time.monotonic() < deadline:
        time.sleep(0.001)
    time.sleep(0.02)
    threads[2].start()
    while len(calls) < 2 and time.monotonic() < deadline:
        time.sleep(0.001)
    # on the device's queue with the lead's call still on the chip
    assert calls == [["lead"], ["early", "filler"]]
    assert threads[0].is_alive()
    gate.set()
    for t in threads:
        t.join(30)
        assert not t.is_alive()
    return out, metrics.counters_delta(before), tracing.recent_spans()


def test_a_call_launched_behind_another_is_counted_and_spanned(
        lead_and_a_full_call):
    from dgraph_tpu.query import devicecall

    out, moved, spans = lead_and_a_full_call
    assert "rendezvous_ahead_total" in metrics.REGISTERED
    # two launches, one of them behind a call in flight
    assert moved['rendezvous_ahead_total{family="t"}'] == 1
    flights = {f["args"]["lanes"]: f for f in spans
               if f["name"] == "device.flight"}
    assert flights[1]["args"]["ahead"] is False
    assert flights[2]["args"]["ahead"] is True
    # the one whose arrival filled the call launched and landed it: the
    # flight hangs under ITS block; it never stood, the other stood
    # until that launch and no longer
    calls = {c["span_id"]: c for c in spans if c["name"] == "device.call"}
    assert calls[flights[2]["parent_id"]]["args"]["flight"] \
        == flights[2]["span_id"]
    assert out["filler"][1].waited_ns == 0 == out["filler"][0].device_queue_ns
    assert 0.02e9 * 0.9 <= out["early"][0].device_queue_ns \
        == out["early"][1].waited_ns
    assert out["early"][1].flight is out["filler"][1].flight
    assert (out["early"][1].lane, out["filler"][1].lane) == (0, 1)
    # a call is on the count of those on the device once, whoever
    # launched it
    assert devicecall._inflight == 0


def test_a_landing_that_finds_its_successor_launched_has_no_turnround(
        lead_and_a_full_call):
    _, moved, spans = lead_and_a_full_call
    lead, second = sorted(
        (f["args"] for f in spans if f["name"] == "device.flight"),
        key=lambda a: a["lanes"])
    # the lead's call had a successor, on the device already: one
    # chained call, 0 ns of the host between the two, nothing launched
    assert moved['rendezvous_chained_total{family="t"}'] == 1
    turn = 'rendezvous_ns_total{family="t",phase="turnround"}'
    assert turn in metrics.counters_snapshot() and turn not in moved
    assert lead["turnround_us"] == 0 and "launch_us" not in lead
    assert not any('phase="launch"' in k for k in moved
                   if k.startswith("rendezvous_ns_total"))
    # the call behind had none
    assert "turnround_us" not in second
    assert all(p + "_us" in f for f in (lead, second)
               for p in ("land", "board", "settle"))


def test_a_lone_dispatch_finds_nothing_ahead_of_it():
    import jax.numpy as jnp
    from dgraph_tpu.query import devicecall

    tracing.clear()
    before = metrics.counters_snapshot()
    with devicecall.device_call("query_device_setops_total") as dc:
        dc.wait(jnp.arange(8) + 1)
    call = [s for s in tracing.recent_spans()
            if s["name"] == "device.call"][-1]["args"]
    assert call["ahead"] == 0
    # the series is there at 0: a reader tells "none ahead" from "not
    # served"
    assert metrics.counters_snapshot()[
        'device_call_ahead_total{family="setops"}'] \
        == before.get('device_call_ahead_total{family="setops"}', 0)
    assert devicecall._inflight == 0


def test_a_dispatch_behind_a_flight_finds_one_call_ahead():
    import jax.numpy as jnp
    from dgraph_tpu.query import devicecall

    meet = devicecall.Rendezvous(4, family="t")
    gate, calls, launch, land = _held_chip()
    t = threading.Thread(target=meet.ride, args=("x", launch, land))
    t.start()
    while devicecall._inflight != 1:
        time.sleep(0.001)
    tracing.clear()
    with devicecall.device_call("query_device_setops_total") as dc:
        dc.wait(jnp.arange(8) + 1)
    gate.set()
    t.join(30)
    call = [s for s in tracing.recent_spans()
            if s["name"] == "device.call"][-1]["args"]
    assert call["ahead"] == 1
    assert devicecall._inflight == 0


def test_a_wait_that_raises_leaves_nothing_in_flight():
    from dgraph_tpu.query import devicecall

    class Broken:
        """What `jax.block_until_ready` gives up on."""

        def block_until_ready(self):
            raise RuntimeError("the device said no")

    before = metrics.counters_snapshot()
    with pytest.raises(RuntimeError):
        with devicecall.device_call("query_device_setops_total") as dc:
            dc.wait(Broken())
    assert devicecall._inflight == 0
    metrics.collect_runtime_gauges()    # the gauge is read at a scrape
    assert metrics.gauges_snapshot()["device_calls_inflight"] == 0
    assert "query_device_setops_total" not in metrics.counters_delta(before)


def test_a_request_that_never_stood_serves_a_stand_of_zero():
    sl = _graph(device_min_edges=1).query(PAGE)[
        "extensions"]["server_latency"]
    assert sl["device_calls"] >= 1 and sl["device_queue_ns"] == 0
    host = _graph(prefer_device=False).query(PAGE)
    assert host["extensions"]["server_latency"]["device_queue_ns"] == 0


# -- the request threads' CPU time, read at a scrape -------------------

CPU = "http_handler_cpu_ns_total"


def _spin(seconds: float) -> None:
    t = time.thread_time()
    while time.thread_time() - t < seconds:
        pass


def _scraped() -> float:
    metrics.collect_runtime_gauges()
    return metrics.counters_snapshot().get(CPU, 0)


def test_a_watched_threads_cpu_time_is_read_from_another_thread():
    spun, leave = threading.Event(), threading.Event()

    def serve():
        _spin(0.02)             # before it is watched: not counted
        metrics.watch_thread_cpu()
        _spin(0.05)
        spun.set()
        leave.wait(30)
        _spin(0.03)
        metrics.watch_thread_cpu(False)

    before = _scraped()
    t0 = time.perf_counter()
    t = threading.Thread(target=serve)
    t.start()
    assert spun.wait(30)
    # read while the thread lives and waits, by this thread
    live = _scraped() - before
    assert 0.05e9 * 0.9 <= live <= (time.perf_counter() - t0) * 1e9
    assert live < 0.07e9 * 1.5      # the 20 ms before the watch are not in
    leave.set()
    t.join(30)
    # what it used is kept when it has left, and counted once
    gone = _scraped() - before
    assert 0.08e9 * 0.9 <= gone <= (time.perf_counter() - t0) * 1e9
    assert _scraped() - before == gone


def test_a_thread_that_died_unannounced_is_dropped_at_the_next_scrape():
    t = threading.Thread(target=metrics.watch_thread_cpu)
    t.start()
    t.join(30)
    assert t.ident in metrics._THREAD_CLOCKS
    # a scrape raises nothing; the kernel may take a moment to reap it
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        before = _scraped()
        if t.ident not in metrics._THREAD_CLOCKS:
            break
        time.sleep(0.01)
    assert t.ident not in metrics._THREAD_CLOCKS
    assert _scraped() == before


def test_the_threads_that_serve_connections_are_watched():
    import http.client

    from dgraph_tpu.server.http import serve

    httpd, _ = serve(_graph(prefer_device=False), block=False, port=0)
    try:
        before = _scraped()
        watched = set(metrics._THREAD_CLOCKS)
        t0 = time.perf_counter()
        conn = http.client.HTTPConnection(*httpd.server_address[:2])
        for _ in range(5):
            conn.request("POST", "/query", PAGE,
                         {"Content-Type": "application/dql"})
            assert json.loads(conn.getresponse().read())["data"]["q"]
        # the connection is open: its thread is read where it stands
        assert len(set(metrics._THREAD_CLOCKS) - watched) == 1
        live = _scraped() - before
        assert 0 < live <= (time.perf_counter() - t0) * 1e9
        conn.close()
        deadline = time.monotonic() + 10.0
        while set(metrics._THREAD_CLOCKS) - watched \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        assert not set(metrics._THREAD_CLOCKS) - watched
        assert _scraped() - before >= live
    finally:
        httpd.shutdown()
