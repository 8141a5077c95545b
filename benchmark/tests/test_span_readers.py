"""The readers of the program's own spans and counters (PR 24): each
gives the expected value on a synthetic `ctx`, and None (never an
error) on what a program without the span or counter serves: the
parent commit, the `--no-device` child."""

import pytest

from conftest import load

STATS = load("stats.py")


def reply(good=True, **server):
    return {"good": good, "latency_s": 0.04, "server": server}


def ctx(replies=(), before=None, after=None, window_s=45.0):
    return {"replies": list(replies), "stats": STATS,
            "counters_before": before or {}, "counters_after": after or {},
            "window_s": window_s}


def served(calls, enqueue, wait, fetch, processing):
    return reply(parsing_ns=10_000, processing_ns=processing,
                 encoding_ns=700_000, total_ns=processing + 710_000,
                 device_calls=calls, device_enqueue_ns=enqueue,
                 device_wait_ns=wait, device_fetch_ns=fetch)


# three replies that went to the device, one that stayed on the host,
# one that failed; and the parent's replies, which have four keys
NEW = [served(1, 1_000_000, 20_000_000, 500_000, 30_000_000),
       served(1, 2_000_000, 30_000_000, 700_000, 40_000_000),
       served(2, 3_000_000, 40_000_000, 900_000, 50_000_000),
       served(0, 0, 0, 0, 4_000_000),
       reply(False)]
OLD = [reply(parsing_ns=10_000, processing_ns=30_000_000,
             encoding_ns=700_000, total_ns=30_710_000), reply(False)]

GC = 'process_gc_pause_seconds_total{gen="2"}'
PRE = 'http_request_ns_total{phase="pre"}'
POST = 'http_request_ns_total{phase="post"}'
DECODE = 'startup_phase_seconds{phase="snapshot_decode"}'

CASES = [
    ("device_wait_ms", ctx(NEW), 30.0),
    ("device_enqueue_ms", ctx(NEW), 2.0),
    ("device_fetch_ms", ctx(NEW), 0.7),
    # self times 8.5, 7.3, 6.1 and the host-only reply's 4.0
    ("executor_host_ms", ctx(NEW), (6.1 + 7.3) / 2),
    ("device_routed_share", ctx(NEW), 75.0),
    ("frontend_handler_ms",
     ctx(before={PRE: 1e9, POST: 2e9, "http_requests_total": 100},
         after={PRE: 1.6e9, POST: 2.4e9, "http_requests_total": 1100}),
     1.0),
    ("gc_pause_share", ctx(before={GC: 9.0}, after={GC: 9.6}), 0.6 / 0.45),
    ("gc_pause_share", ctx(before={GC: 9.0}, after={GC: 9.0}), 0.0),
    ("snapshot_decode_s", ctx(after={DECODE: 44.5}), 44.5),
]


@pytest.mark.parametrize("name,context,want", CASES,
                         ids=[f"{c[0]}-{i}" for i, c in enumerate(CASES)])
def test_reader_reads_what_the_program_serves(name, context, want):
    got = load(f"metrics/{name}.py").read(context)
    assert got == pytest.approx(want, rel=1e-9, abs=1e-12)


@pytest.mark.parametrize("name", sorted({c[0] for c in CASES}))
@pytest.mark.parametrize("context", [
    ctx(OLD, before={"plan_cache_hits": 1}, after={"plan_cache_hits": 9}),
    ctx()], ids=["parent", "empty"])
def test_reader_is_silent_where_the_program_serves_nothing(name, context):
    assert load(f"metrics/{name}.py").read(context) is None


def test_no_request_counted_is_no_mean():
    got = load("metrics/frontend_handler_ms.py").read(ctx(
        before={PRE: 5.0, POST: 5.0, "http_requests_total": 7},
        after={PRE: 5.0, POST: 5.0, "http_requests_total": 7}))
    assert got is None
