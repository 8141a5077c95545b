"""The readers of where a request waits (PR 39): the stand at the
rendezvous, the turn-round between calls, the chip's queue by count,
the interpreter by the request threads' CPU time. As `test_span_readers.py` holds
its readers: each gives the expected value on a synthetic `ctx`, and
None (never an error) on what a program without the key or counter
serves: the parent commit laid under this PR's benchmark files."""

import json
import os

import pytest

from conftest import ROOT, load

SPANS = load("tests/test_span_readers.py")
ctx, reply, OLD = SPANS.ctx, SPANS.reply, SPANS.OLD


def served(calls, wait, queue, processing):
    return reply(parsing_ns=10_000, processing_ns=processing,
                 encoding_ns=700_000,
                 total_ns=processing + 710_000, device_calls=calls,
                 device_enqueue_ns=90_000, device_wait_ns=wait,
                 device_queue_ns=queue, device_fetch_ns=10_000)


# three replies that rode a call (one found the chip free), one that
# stayed on the host, one that failed
NEW = [served(1, 28_000_000, 12_000_000, 30_000_000),
       served(1, 16_000_000, 0, 17_000_000),
       served(1, 30_000_000, 13_000_000, 33_000_000),
       served(0, 0, 0, 4_000_000),
       reply(False)]

TURN = 'rendezvous_ns_total{family="recurse",phase="turnround"}'
CHAINED = 'rendezvous_chained_total{family="recurse"}'
AHEAD = 'device_call_ahead_total{family="%s"}'
WALL = 'http_request_ns_total{phase="%s"}'
CPU = "http_handler_cpu_ns_total"
WAIT = 'device_call_ns_total{family="%s",phase="%s"}'

CASES = [
    ("device_queue_ms", ctx(NEW), 12.0),
    ("flight_turnround_ms",
     ctx(before={TURN: 1e9, CHAINED: 500},
         after={TURN: 1.45e9, CHAINED: 800}), 1.5),
    # the calls of every family, as device_ops_per_req counts them
    ("device_calls_ahead",
     ctx(before={AHEAD % "sort_page": 10, AHEAD % "fused_dispatch": 5,
                 "query_device_sort_page_total": 100,
                 "query_fused_dispatch_total": 40},
         after={AHEAD % "sort_page": 310, AHEAD % "fused_dispatch": 405,
                "query_device_sort_page_total": 200,
                "query_fused_dispatch_total": 140}), 3.5),
    ("device_calls_ahead",
     ctx(before={AHEAD % "similar": 0, "query_device_similar_total": 1},
         after={AHEAD % "similar": 0, "query_device_similar_total": 9}),
     0.0),
    # 1,000 requests: 18 s of handler wall time in three phases, 3 s
    # of the threads' CPU, 4 s of device waits in two families (their
    # other phases are the threads' own work and stay out)
    ("interpreter_wait_ms",
     ctx(before={WALL % "pre": 1e9, WALL % "engine": 5e9,
                 WALL % "post": 1e9, CPU: 2e9, "http_requests_total": 100,
                 WAIT % ("similar", "wait"): 1e9,
                 WAIT % ("similar", "fetch"): 1e9},
         after={WALL % "pre": 2e9, WALL % "engine": 20e9,
                WALL % "post": 3e9, CPU: 5e9, "http_requests_total": 1100,
                WAIT % ("similar", "wait"): 4e9,
                WAIT % ("sort_page", "wait"): 1e9,
                WAIT % ("similar", "fetch"): 9e9}), 11.0),
    # nothing queues and the lander computes inside its wait: under 0
    ("interpreter_wait_ms",
     ctx(before={WALL % "engine": 0.0, CPU: 0.0, "http_requests_total": 0,
                 WAIT % ("recurse", "wait"): 0.0},
         after={WALL % "engine": 30e9, CPU: 1e9, "http_requests_total": 1000,
                WAIT % ("recurse", "wait"): 29.5e9}), -0.5),
]


@pytest.mark.parametrize("name,context,want", CASES,
                         ids=[f"{c[0]}-{i}" for i, c in enumerate(CASES)])
def test_reader_reads_what_the_program_serves(name, context, want):
    got = load(f"metrics/{name}.py").read(context)
    assert got == pytest.approx(want, rel=1e-9, abs=1e-12)


@pytest.mark.parametrize("name", sorted({c[0] for c in CASES}))
@pytest.mark.parametrize("context", [
    # what the parent serves: four keys a reply, the counters it had
    ctx(OLD, before={"plan_cache_hits": 1, "http_requests_total": 1,
                     "query_device_similar_total": 1,
                     'http_request_ns_total{phase="pre"}': 1.0},
        after={"plan_cache_hits": 9, "http_requests_total": 9,
               "query_device_similar_total": 9,
               'http_request_ns_total{phase="pre"}': 9.0}),
    # the replies of PR 24 to PR 38: the device keys, none of PR 39's
    ctx(SPANS.NEW),
    ctx()], ids=["parent", "pr38-replies", "empty"])
def test_reader_is_silent_where_the_program_serves_nothing(name, context):
    assert load(f"metrics/{name}.py").read(context) is None


@pytest.mark.parametrize("name,before,after", [
    ("flight_turnround_ms", {TURN: 5.0, CHAINED: 7}, {TURN: 5.0, CHAINED: 7}),
    ("device_calls_ahead", {AHEAD % "similar": 0}, {AHEAD % "similar": 0}),
    ("interpreter_wait_ms",
     {WALL % "engine": 5.0, CPU: 5.0, "http_requests_total": 7},
     {WALL % "engine": 5.0, CPU: 5.0, "http_requests_total": 7}),
])
def test_nothing_counted_is_no_mean(name, before, after):
    assert load(f"metrics/{name}.py").read(
        ctx(before=before, after=after)) is None


def test_the_entries_are_the_issues_table():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    khop = ["graph500-khop.khop-deep-c16", "graph500-khop-x4.khop-deep-c16"]
    want = {
        "device_queue_ms": ("ms", "program_span", "device",
                            "read_p50_ms", khop),
        "flight_turnround_ms": ("ms", "program_span", "executor",
                                "ok_qps", khop),
        "device_calls_ahead": ("calls", "program_counter", "device",
                               "read_p50_ms",
                               ["movies21m.device-families",
                                "sift1m-exact.knn-mix"]),
        "interpreter_wait_ms": ("ms", "program_counter", "executor",
                                "read_p50_ms", None),
    }
    got = {m["name"]: (m["unit"], m["source"], m["layer"], m["moves"],
                       m.get("workloads"))
           for m in bench["per_layer"][-len(want):]}
    assert got == want
    assert all(m["better"] == "lower"
               for m in bench["per_layer"][-len(want):])
