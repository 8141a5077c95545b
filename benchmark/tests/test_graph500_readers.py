"""The readers PR 31 adds (`bfs_roofline`, `recurse_host_ms`,
`bitadj_bytes`), PR 36's `recurse_lanes_per_call` and the traversal's
cost function: each gives the
expected value on a synthetic `ctx` and on the recorded trace's
reduction, and None (never an error) on what a program without the
gauge, the counter or the device program serves: the parent commit,
the `--no-device` child, a cell that sends no traversal."""

import os

import pytest

from conftest import BENCH, load

STATS = load("stats.py")
PEAKS = {"hbm_bytes_per_s": 819e9}
EDGES = 'device_bitadj_edges{predicate="link"}'
BYTES = 'device_bitadj_bytes{predicate="link"}'
CHILD = 'device_call_ns_total{family="recurse",phase="%s"}'
OTHER = 'device_call_ns_total{family="sort_page",phase="wait"}'
KHOP = "{ var(func: uid(0x5)) @recurse(depth: %d, loop: false) { n as link } }"
POOL = [{"name": "khop3", "query": KHOP % 4},
        {"name": "khop3", "query": KHOP % 4},
        {"name": "khop6", "query": KHOP % 7}]


def load_path(path):
    return load(os.path.relpath(path, BENCH))


def ctx(before=None, after=None, programs=None, peaks=PEAKS, pool=POOL):
    return {"replies": [], "stats": STATS, "window_s": 45.0,
            "counters_before": before or {}, "counters_after": after or {},
            "trace": None if programs is None else {"programs": programs},
            "peaks": peaks, "notes": [], "pool": pool,
            "facts": {"vertices": 174_000}, "bench_dir": BENCH,
            "load_module": load_path}


# 100 calls in 4.5 s of device time; the templates ask for 3 and 6
# levels and are sent equally often, so 4.5 levels a call, each
# 3,939,574 edges x 4 B and two bitmaps of 174,000 bits
TRAVERSE = [["jit_bfs_traverse", 3.0, 60],
            ["jit_convert_element_type", 0.2, 500],
            ["jit_bfs_traverse.1", 1.5, 40]]
EACH = 4.5 * (4 * 3_939_574 + 2 * 174_000 / 8)
WINDOW_BEFORE = {"recurse_ns_total": 5e9, "query_device_recurse_total": 50,
                 CHILD % "enqueue": 1e9, CHILD % "wait": 2e9,
                 CHILD % "fetch": 0.5e9, OTHER: 7e9}
WINDOW_AFTER = {"recurse_ns_total": 55e9, "query_device_recurse_total": 1050,
                CHILD % "enqueue": 2e9, CHILD % "wait": 46e9,
                CHILD % "fetch": 1.5e9, OTHER: 99e9,
                EDGES: 3_939_574.0, BYTES: 18_977_108.0}

CASES = [
    ("bfs_roofline", ctx(after={EDGES: 3_939_574.0}, programs=TRAVERSE),
     100.0 * (100 * EACH / 819e9) / 4.5),
    # 50 s in the span less 46 s in its device.call child, 1,000 calls
    ("recurse_host_ms", ctx(WINDOW_BEFORE, WINDOW_AFTER), 4.0),
    ("bitadj_bytes", ctx(after={BYTES: 18_977_108.0,
                                'device_bitadj_bytes{predicate="~link"}':
                                1024.0}), 18_978_132.0),
    # an evicted adjacency reads 0, not nothing
    ("bitadj_bytes", ctx(after={BYTES: 0.0}), 0.0),
    # 879 calls carried 7,021 traversals in the window (PR 35's traced
    # run of this traffic); the first pass's 64 calls of one lane each
    # lie before it and do not count
    ("recurse_lanes_per_call", ctx(
        {"recurse_batch_total": 64, "recurse_batch_lanes_total": 64},
        {"recurse_batch_total": 943, "recurse_batch_lanes_total": 7085}),
     7021 / 879),
]


@pytest.mark.parametrize("name,context,want", CASES,
                         ids=[f"{c[0]}-{i}" for i, c in enumerate(CASES)])
def test_reader_reads_what_the_program_serves(name, context, want):
    got = load(f"metrics/{name}.py").read(context)
    assert got == pytest.approx(want, rel=1e-9, abs=1e-12)


def test_the_cost_is_reckoned_from_the_graph_and_the_query():
    cost = load("costs/jit_bfs_traverse.py")
    assert cost.least_bytes({"edges": 10, "vertices": 16, "levels": 3}) \
        == 3 * (40 + 4)
    # the one-template rule of top_program_roofline passes it by
    assert cost.TEMPLATE is None
    costs = load("kernel_costs.py")
    assert costs.find("jit_bfs_traverse", BENCH, load_path) is not None


def test_the_roofline_says_what_it_was_worked_out_from():
    c = ctx(after={EDGES: 3_939_574.0}, programs=TRAVERSE)
    share = load("metrics/bfs_roofline.py").read(c)
    (note,) = c["notes"]
    assert "100 calls" in note and "4.50 levels" in note
    assert "45.000 ms a call" in note
    assert 0 < share < 1      # gather-bound: far under the roofline


def test_the_roofline_on_the_recorded_trace():
    """The reduction of the recorded trace (small_trace.pbtxt's
    programs) holds no traversal: the reader is silent; with one
    added it reads that program alone."""
    reduce = load("trace_reduce.py")
    assert hasattr(reduce, "_program_name")
    assert reduce._program_name("jit_bfs_traverse(123456)") \
        == "jit_bfs_traverse"
    recorded = [["jit_fused_page_none_r0s0o1", 3.41, 848],
                ["jit_count_filter_sort_page", 0.67, 94]]
    reader = load("metrics/bfs_roofline.py")
    assert reader.read(ctx(after={EDGES: 1e6}, programs=recorded)) is None
    got = reader.read(ctx(after={EDGES: 1e6}, programs=recorded + [
        ["jit_bfs_traverse", 0.5, 10]]))
    want = 100.0 * (10 * 4.5 * (4e6 + 2 * 174_000 / 8) / 819e9) / 0.5
    assert got == pytest.approx(want)


PARENT = {"plan_cache_hits": 9, "device_cache_bytes": 37e6,
          OTHER: 99e9, "query_device_sort_page_total": 50}


@pytest.mark.parametrize("name", sorted({c[0] for c in CASES}))
@pytest.mark.parametrize("context", [
    ctx({"plan_cache_hits": 1}, PARENT,
        programs=[["jit_multisort_page", 2.0, 300]]),
    ctx({"plan_cache_hits": 1}, PARENT),
    ctx()], ids=["parent-traced", "parent", "empty"])
def test_reader_is_silent_where_the_program_serves_nothing(name, context):
    assert load(f"metrics/{name}.py").read(context) is None


def test_no_call_in_the_window_is_no_mean():
    same = dict(WINDOW_AFTER)
    assert load("metrics/recurse_host_ms.py").read(ctx(same, same)) is None
    lanes = load("metrics/recurse_lanes_per_call.py")
    stood = {"recurse_batch_total": 64, "recurse_batch_lanes_total": 64}
    assert lanes.read(ctx(stood, stood)) is None
    # one counter without the other is a program that serves neither
    # ratio: silent, not a division by what is not there
    assert lanes.read(ctx({}, {"recurse_batch_total": 9})) is None
    assert lanes.read(ctx({}, {"recurse_batch_lanes_total": 9})) is None


def test_the_roofline_needs_peaks_device_time_and_a_depth():
    reader = load("metrics/bfs_roofline.py")
    assert reader.read(ctx(after={EDGES: 1.0}, programs=TRAVERSE,
                           peaks=None)) is None
    assert reader.read(ctx(after={EDGES: 1.0}, programs=[
        ["jit_bfs_traverse", 0.0, 0]])) is None
    assert reader.read(ctx(after={EDGES: 1.0}, programs=TRAVERSE, pool=[
        {"name": "q", "query": "{ q(func: has(link)) { uid } }"}])) is None
