"""The cell `sift1m-exact.knn-mix` end to end on the CPU at a tiny
scale (`--rehearse`), beside the existing cells and through the same
harness: the result line's form, `correct: true` with every pool
query held to the plain reference, and the control corpus (one
component of 1% of the rows moved by 1) coming out `correct: false`.

Each rehearsal starts three children and takes about half a minute.
"""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT

CELL = "sift1m-exact.knn-mix"
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
               "compared"}
NEW_COUNTED = {"vector_block_bytes", "similar_host_ms"}


def rehearse(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=os.path.join(ROOT, ".jax_cache"))
    return subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         *args], env=env, capture_output=True, text=True, timeout=900)


def last_line(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def sound():
    # 8,000 rows: past the device gate and the two-stage floor
    return rehearse("--seed", str(2**31 + 29), "--seconds", "3",
                    "--trace", "1", "--rehearse", "8")


def test_the_last_line_has_the_contracts_keys_and_is_correct(sound):
    res = last_line(sound)
    assert set(res) == RESULT_KEYS
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert res["device"]["platform"] == "cpu"
    assert sound.stdout.count("(limit 0)") == 4
    # the plain reference answers every template, every pool query
    assert "plain reference 0 of the 96 pool queries (of 96)" \
        in sound.stdout


def test_the_templates_reach_the_device_inside_device_call(sound):
    """On the CPU at 8,000 rows the adaptive planner may send a stage
    to the postings tier when the machine is busy (the tiers give one
    answer, so `correct` does not care): the device path has to be
    driven, by the renamed counter, not by every request."""
    res = last_line(sound)
    first = [ln for ln in sound.stdout.splitlines()
             if ln.startswith("first pass, device stages by template: ")]
    assert len(first) == 1 and "query_device_similar_total" in first[0]
    assert all(t in first[0] for t in ("knn10: ", "knn100: ",
                                       "knn10_in_category: "))
    m = res["metrics"]
    assert m["device_routed_share"]["value"] > 50.0
    assert m["device_ops_per_req"]["value"] > 0.5
    assert m["compiles_in_window"]["value"] == 0


def test_the_block_is_in_the_devices_books(sound):
    m = last_line(sound)["metrics"]
    block = 8064 * 128 * 4      # 8,000 rows padded to the bucket unit
    assert m["vector_block_bytes"] == {"value": block, "unit": "bytes"}
    assert m["tile_bytes"]["value"] >= block
    # a CPU run reports what counters give: the span's accumulated
    # time over the calls; the device trace's roofline is silent
    assert m["similar_host_ms"]["value"] > 0
    assert "knn_scan_roofline" not in m
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        by_name = {e["name"]: e for e in json.load(f)["per_layer"]}
    assert all(by_name[k]["source"] == "program_counter" for k in m)
    assert NEW_COUNTED <= set(m)


def test_the_control_corpus_comes_out_not_correct():
    p = rehearse("--seed", "11", "--seconds", "2", "--trace", "0",
                 "--rehearse", "8", "--control", "off-by-one")
    res = last_line(p)
    assert set(res) == RESULT_KEYS
    assert res["correct"] is False
