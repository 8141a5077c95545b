"""The readers PR 26 adds (`knn_scan_roofline`, `similar_host_ms`,
`vector_block_bytes`): each gives the expected value on a synthetic
`ctx`, and None (never an error) on what a program without the gauge,
the counter or the device program serves: the parent commit, the
`--no-device` child, a cell that sends no vector query."""

import pytest

from conftest import load

STATS = load("stats.py")
PEAKS = {"hbm_bytes_per_s": 819e9}
BLOCK = 'device_vector_block_bytes{predicate="embedding"}'
CHILD = 'device_call_ns_total{family="similar",phase="%s"}'
OTHER = 'device_call_ns_total{family="sort_page",phase="wait"}'
SHARDED = 'device_call_ns_total{family="similar_sharded",phase="wait"}'


def ctx(before=None, after=None, programs=None, peaks=PEAKS):
    return {"replies": [], "stats": STATS, "window_s": 45.0,
            "counters_before": before or {}, "counters_after": after or {},
            "trace": None if programs is None else {"programs": programs},
            "peaks": peaks, "notes": []}


# 1,000 calls of the scan in 1.5 s of device time over a 256,049,152 B
# block: 0.3126 s at 819 GB/s; another program's time does not count
SCAN = [["jit__topk_device_jit", 1.0, 700],
        ["jit_convert_element_type", 0.2, 5000],
        ["jit__topk_device_jit.1", 0.5, 300]]
WINDOW_BEFORE = {"similar_ns_total": 4e9, "query_device_similar_total": 100,
                 CHILD % "enqueue": 1e9, CHILD % "wait": 2e9,
                 CHILD % "fetch": 0.5e9, OTHER: 7e9}
WINDOW_AFTER = {"similar_ns_total": 24e9, "query_device_similar_total": 2100,
                CHILD % "enqueue": 3e9, CHILD % "wait": 8e9,
                CHILD % "fetch": 1.5e9, OTHER: 99e9, BLOCK: 256049152.0}

CASES = [
    ("knn_scan_roofline", ctx(after={BLOCK: 256049152.0}, programs=SCAN),
     100.0 * (1000 * 256049152.0 / 819e9) / 1.5),
    # 20 s in the span less 9 s in its device.call child, 2,000 calls
    ("similar_host_ms", ctx(WINDOW_BEFORE, WINDOW_AFTER), 5.5),
    # the mesh-sharded site's family is not this span's child
    ("similar_host_ms", ctx(WINDOW_BEFORE,
                            {**WINDOW_AFTER, SHARDED: 6e9}), 5.5),
    ("vector_block_bytes", ctx(after={BLOCK: 256049152.0,
                                      'device_vector_block_bytes{predicate="other"}': 1024.0}),
     256050176.0),
    # an evicted block reads 0, not nothing
    ("vector_block_bytes", ctx(after={BLOCK: 0.0}), 0.0),
]


@pytest.mark.parametrize("name,context,want", CASES,
                         ids=[f"{c[0]}-{i}" for i, c in enumerate(CASES)])
def test_reader_reads_what_the_program_serves(name, context, want):
    got = load(f"metrics/{name}.py").read(context)
    assert got == pytest.approx(want, rel=1e-9, abs=1e-12)


def test_the_roofline_says_what_it_was_worked_out_from():
    c = ctx(after={BLOCK: 256049152.0}, programs=SCAN)
    load("metrics/knn_scan_roofline.py").read(c)
    (note,) = c["notes"]
    assert "1000 calls" in note and "256049152 B" in note


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
    assert load("metrics/similar_host_ms.py").read(ctx(same, same)) is None


def test_the_roofline_needs_the_chips_peaks_and_some_device_time():
    reader = load("metrics/knn_scan_roofline.py")
    assert reader.read(ctx(after={BLOCK: 1.0}, programs=SCAN,
                           peaks=None)) is None
    assert reader.read(ctx(after={BLOCK: 1.0}, programs=[
        ["jit__topk_device_jit", 0.0, 0]])) is None
