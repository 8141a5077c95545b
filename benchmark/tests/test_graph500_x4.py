"""The configuration `graph500-khop-x4` and its cell
`graph500-khop-x4.khop-deep-c16` (PR 37): the four readers and the
cost function the cell brings give the expected value on a synthetic
`ctx` shaped as PR 35's traced run of this traffic was, and None
(never an error) on what a program without the counters, the gauges
or the sharded device program serves: the parent commit, a one-chip
alpha, the `--no-device` child; and the cell end to end on the CPU at
a tiny scale (`--rehearse`), the chip child an `alpha --chips 4` over
four virtual CPU devices.

On the CPU the gate keeps every traversal on the host tier (an
XLA-CPU "device" shares the host's silicon), so the rehearsal walks
the harness, the flag, the mesh's construction and the host tier; the
sharded program is tier-1's (tests/test_recurse_sharded.py, which
forces it) and the chip's."""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT, load

CELL = "graph500-khop-x4.khop-deep-c16"
STATS = load("stats.py")
PEAKS = {"hbm_bytes_per_s": 819e9}
EDGES = 'device_bitadj_edges{predicate="link"}'
SHARDS = 'device_bitadj_shards{predicate="link"}'
CHIP_BYTES = 'device_bitadj_chip_bytes{predicate="link"}'
KHOP = "{ var(func: uid(0x5)) @recurse(depth: %d, loop: false) { n as link } }"
POOL = [{"name": "khop3", "query": KHOP % 4},
        {"name": "khop6", "query": KHOP % 7}]


def load_path(path):
    return load(os.path.relpath(path, BENCH))


def ctx(before=None, after=None, trace=None, peaks=PEAKS, pool=POOL):
    return {"replies": [], "stats": STATS, "window_s": 45.0,
            "counters_before": before or {}, "counters_after": after or {},
            "trace": trace, "peaks": peaks, "notes": [], "pool": pool,
            "facts": {"vertices": 646_259}, "bench_dir": BENCH,
            "load_module": load_path}


# PR 35's traced run, as trace_reduce.py hands it on: 97 calls, an
# event a chip (388), 4.617947 s a chip; its ops averaged over chips
TRACE = {"chips": 4,
         "programs": [["jit_bfs_traverse_sharded", 4.617947, 388],
                      ["jit_convert_element_type", 0.0002, 8]],
         "device_ops": [
             ["jit_bfs_traverse_sharded/while.22", 4.566727],
             ["jit_bfs_traverse_sharded/bfs_hub_rows.4", 1.754242],
             ["jit_bfs_traverse_sharded/fusion.57", 1.082027],
             ["jit_bfs_traverse_sharded/all-gather-start.2", 0.003084],
             ["jit_bfs_traverse_sharded/all-gather-done.2", 0.020000],
             ["jit_other/all-reduce.1", 9.0]]}
GAUGES = {EDGES: 16_085_704.0, SHARDS: 4.0, CHIP_BYTES: 1_640_126_640.0}
# 4.5 levels a call; a chip reads a quarter of the edges' indices and
# two bitmaps of all the vertices
EACH = 4.5 * (4 * 16_085_704 / 4 + 2 * 646_259 / 8)

CASES = [
    ("bfs_shard_roofline", ctx(after=GAUGES, trace=TRACE),
     100.0 * (97 * EACH / 819e9) / 4.617947),
    ("bfs_collective_share", ctx(after=GAUGES, trace=TRACE),
     100.0 * 0.023084 / 4.617947),
    # 879 calls carried 7,021 traversals in the window; the first
    # pass's 64 calls of one lane each lie before it
    ("recurse_sharded_lanes_per_call", ctx(
        {"recurse_sharded_total": 64, "recurse_sharded_lanes_total": 64},
        {"recurse_sharded_total": 943, "recurse_sharded_lanes_total": 7085}),
     7021 / 879),
    ("bitadj_chip_bytes", ctx(after=GAUGES | {
        'device_bitadj_chip_bytes{predicate="~link"}': 1024.0}),
     1_640_127_664.0),
    # an evicted adjacency reads 0, not nothing
    ("bitadj_chip_bytes", ctx(after={CHIP_BYTES: 0.0}), 0.0),
]


@pytest.mark.parametrize("name,context,want", CASES,
                         ids=[f"{c[0]}-{i}" for i, c in enumerate(CASES)])
def test_reader_reads_what_the_program_serves(name, context, want):
    got = load(f"metrics/{name}.py").read(context)
    assert got == pytest.approx(want, rel=1e-9, abs=1e-12)


def test_the_cost_is_a_chips_share_of_the_edges_and_whole_bitmaps():
    cost = load("costs/jit_bfs_traverse_sharded.py")
    assert cost.least_bytes({"edges": 40, "vertices": 16, "chips": 4,
                             "levels": 3}) == 3 * (40 + 4)
    one = load("costs/jit_bfs_traverse.py")
    # on one chip it is the one-chip traversal's
    s = {"edges": 10, "vertices": 16, "levels": 3}
    assert cost.least_bytes(s | {"chips": 1}) == one.least_bytes(s)
    assert cost.TEMPLATE is None
    costs = load("kernel_costs.py")
    assert costs.find("jit_bfs_traverse_sharded", BENCH, load_path) \
        is not None


def test_the_shares_say_what_they_were_worked_out_from_and_stay_under_100():
    c = ctx(after=GAUGES, trace=TRACE)
    share = load("metrics/bfs_shard_roofline.py").read(c)
    between = load("metrics/bfs_collective_share.py").read(c)
    roofline, collective = c["notes"]
    assert "97 calls on 4 chips" in roofline and "4.50 levels" in roofline
    assert "47.608 ms a call" in roofline
    assert "all-gather-start.2" in collective \
        and "all-gather-done.2" in collective \
        and "all-reduce.1" not in collective
    assert 0 < share < 1 and 0 < between < 1


# what a program without this PR serves: other counters, a one-chip
# traversal in the trace; and a one-chip alpha of this PR (gauges
# there, one shard, no sharded call)
PARENT = {"plan_cache_hits": 9, "device_cache_bytes": 37e6,
          "recurse_batch_total": 900, "recurse_batch_lanes_total": 7000,
          EDGES: 3_939_574.0, "query_device_recurse_total": 50}
PARENT_TRACE = {"chips": 1,
                "programs": [["jit_bfs_traverse", 4.5, 229]],
                "device_ops": [["jit_bfs_traverse/bfs_hub_rows.7", 2.9],
                               ["jit_bfs_traverse/fusion.37", 0.39]]}
ONE_CHIP = PARENT | {SHARDS: 1.0, "recurse_sharded_total": 0,
                     "recurse_sharded_lanes_total": 0}
SILENT = [
    ("parent-traced", ctx({"plan_cache_hits": 1}, PARENT,
                          trace=PARENT_TRACE)),
    ("parent", ctx({"plan_cache_hits": 1}, PARENT)),
    ("no-device-ops", ctx(after={EDGES: 16_085_704.0, SHARDS: 4.0}, trace={
        "chips": 4, "programs": [], "device_ops": []})),
    ("empty", ctx()),
]


@pytest.mark.parametrize("name", sorted({c[0] for c in CASES}))
@pytest.mark.parametrize("context", [c for _, c in SILENT],
                         ids=[i for i, _ in SILENT])
def test_reader_is_silent_where_the_program_serves_nothing(name, context):
    assert load(f"metrics/{name}.py").read(context) is None


@pytest.mark.parametrize("name", (
    "bfs_shard_roofline", "bfs_collective_share",
    "recurse_sharded_lanes_per_call"))
def test_reader_is_silent_on_a_one_chip_alpha(name):
    c = ctx(ONE_CHIP, ONE_CHIP, trace=PARENT_TRACE)
    assert load(f"metrics/{name}.py").read(c) is None


def test_a_collective_too_short_to_be_listed_is_nothing_not_zero():
    trace = dict(TRACE, device_ops=TRACE["device_ops"][:3])
    assert load("metrics/bfs_collective_share.py").read(
        ctx(after=GAUGES, trace=trace)) is None
    # one counter without the other is a program that serves neither
    lanes = load("metrics/recurse_sharded_lanes_per_call.py")
    assert lanes.read(ctx({}, {"recurse_sharded_total": 9})) is None
    assert lanes.read(ctx({}, {"recurse_sharded_lanes_total": 9})) is None


def test_the_roofline_needs_peaks_chips_device_time_and_a_depth():
    reader = load("metrics/bfs_shard_roofline.py")
    assert reader.read(ctx(after=GAUGES, trace=TRACE, peaks=None)) is None
    assert reader.read(ctx(after=GAUGES, trace=dict(TRACE, chips=0))) is None
    assert reader.read(ctx(after=GAUGES, trace=dict(TRACE, programs=[
        ["jit_bfs_traverse_sharded", 0.0, 0]]))) is None
    assert reader.read(ctx(after=GAUGES, trace=TRACE, pool=[
        {"name": "q", "query": "{ q(func: has(link)) { uid } }"}])) is None


# -- the configuration and the cell -----------------------------------


def test_the_configuration_is_the_one_chip_ones_shapes_at_its_own_scale():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell == {"name": CELL, "config": "graph500-khop-x4",
                    "traffic": "khop-deep-c16", "chips": 4,
                    "why": cell["why"]}
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    (entry,) = [c for c in bench["configs"]
                if c["name"] == "graph500-khop-x4"]
    assert entry["reduced"] == ["scale"]
    with open(os.path.join(ROOT, entry["file"])) as f:
        x4 = json.load(f)
    with open(os.path.join(BENCH, "configs", "graph500-khop.json")) as f:
        one = json.load(f)
    assert set(x4) == set(one) and x4["source"] == entry["source"]
    assert x4["source"] != one["source"]
    for same in ("dataset", "plain_reference", "source_scale",
                 "guarantees", "schema", "device_counters",
                 "architecture"):
        assert x4[same] == one[same], same
    assert (x4["scale"], x4["chips"], x4["serve_flags"]) \
        == (20, 4, ["--chips", "4"])
    assert 19 <= x4["scale"] < x4["source_scale"]
    assert set(x4["reduced_why"]) == {"scale"}
    assert "sharding" in x4["assumed"]
    scoped = {m["name"] for m in bench["per_layer"]
              if m.get("workloads") == [CELL]}
    assert scoped == {c[0] for c in CASES}
    for name in scoped:
        assert os.path.isfile(os.path.join(BENCH, "metrics", name + ".py"))


def rehearse(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=os.path.join(ROOT, ".jax_cache"))
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, *args], env=env, capture_output=True,
        text=True, timeout=900)


def test_the_cell_rehearsed_on_four_cpu_devices_is_correct():
    p = rehearse("--seed", str(2**31 + 37), "--seconds", "3",
                 "--trace", "1", "--rehearse", "10")
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert res["device"]["platform"] == "cpu"
    assert res["device"]["count"] == 4
    assert res["compared"]["plain_answers_differing"] \
        == {"value": 0, "limit": 0, "of": 64}
    m = res["metrics"]
    assert m["compiles_in_window"]["value"] == 0
    # on the CPU the host tier answers: no sharded call was made, and
    # the readers of what it would serve stay silent
    assert "recurse_sharded_total +0" in p.stdout
    assert not {"bfs_shard_roofline", "bfs_collective_share",
                "recurse_sharded_lanes_per_call"} & set(m)
    # the one-chip cell's scoped readers are not this cell's
    assert not {"bfs_roofline", "recurse_lanes_per_call",
                "bitadj_bytes"} & set(m)


def test_the_cell_needs_its_four_chips():
    """One CPU device where the cell asks for four: the run ends
    non-zero with nothing on stdout, saying why."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "5", "--seconds", "2",
         "--rehearse", "10"], env=env, capture_output=True, text=True,
        timeout=600)
    assert p.returncode != 0 and not p.stdout.strip()
    assert "needs 4 chips" in p.stderr
