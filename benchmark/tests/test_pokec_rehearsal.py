"""The cell `pokec-shortest.pairs-c16` end to end on the CPU at the
smallest scale (`--rehearse 1`: 16,328 profiles), beside the existing
cells and through the same harness: the result line's form, `correct:
true` with every pool query held to the plain reference, the control
graph (one edge in a thousand left out) and a reply altered where it
is produced both coming out `correct: false`.

On the CPU the gate keeps every `shortest` block on the host tier (an
XLA-CPU "device" shares the host's silicon: `executor._device_worth`),
so this walks the harness, the generator, the reference and the host
tier; the device tier is tier-1's (tests/test_shortest_device.py and
test_pokec.py here, which force it) and the chip's.

Each rehearsal starts three children and takes a minute or two.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import ROOT, alter_answers, copy_checkout

CELL = "pokec-shortest.pairs-c16"
with open(os.path.join(ROOT, "benchmark", "traffic", "pairs-c16.json")) as _f:
    POOL = json.load(_f)["bindings"]
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
               "compared"}


def rehearse(*args, root=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=os.path.join(ROOT, ".jax_cache"))
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", CELL, *args], env=env, capture_output=True,
        text=True, timeout=900)


def last_line(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def sound():
    return rehearse("--seed", str(2**31 + 44), "--seconds", "3",
                    "--trace", "1", "--rehearse", "1")


def test_the_last_line_has_the_contracts_keys_and_is_correct(sound):
    res = last_line(sound)
    assert set(res) == RESULT_KEYS
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert res["device"]["platform"] == "cpu"
    assert sound.stdout.count("(limit 0)") == 4
    assert list(res)[-1] == "compared"
    # the plain reference answers the one template, every pool query
    assert res["compared"]["plain_answers_differing"] \
        == {"value": 0, "limit": 0, "of": POOL}
    assert f"pool {POOL} queries of 1 templates" in sound.stdout


def test_every_request_is_a_shortest_block_and_the_counters_say_which_tier(
        sound):
    res = last_line(sound)
    (gate,) = [ln for ln in sound.stdout.splitlines()
               if ln.startswith("gate: ")]
    took = dict(part.rsplit(" +", 1) for part in
                gate.split("in the window: ")[1].split(", "))
    assert int(took["shortest_tier_total"]) == res["attempted"]
    # on the CPU the host tier answers; the window compiles nothing
    assert int(took["query_device_shortest_total"]) == 0
    m = res["metrics"]
    assert m["compiles_in_window"]["value"] == 0
    assert m["plan_cache_hit_share"]["value"] > 90.0
    # readers of what no host-tier run serves stay silent
    for name in ("shortest_roofline", "shortest_lanes_per_call",
                 "shortest_levels_per_call", "shortest_host_ms",
                 "shortest_fetch_bytes_per_req"):
        assert name not in m


def test_the_control_graph_comes_out_not_correct():
    p = rehearse("--seed", "13", "--seconds", "2", "--trace", "0",
                 "--rehearse", "1", "--control", "drop-edges")
    res = last_line(p)
    assert set(res) == RESULT_KEYS
    assert res["correct"] is False
    # a few dozen of the pool's answers lose their path's edge; the
    # others are the sound graph's
    differing = res["compared"]["plain_answers_differing"]
    assert 0 < differing["value"] < POOL // 20 and differing["of"] == POOL
    assert res["failed"] > 0


def test_a_reply_altered_where_it_is_produced_is_not_correct(tmp_path):
    """The other side of the comparison broken: a checkout whose chip
    child answers through a launcher that puts a digit before every
    path's weight; the harness is the tree's own."""
    root = str(tmp_path / "checkout")
    alter_answers(os.path.join(copy_checkout(root), "serve_chip.py"),
                  "_weight_")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    res = last_line(rehearse("--seed", "17", "--seconds", "2", "--trace",
                             "0", "--rehearse", "1", root=root))
    assert res["correct"] is False
    differing = res["compared"]["plain_answers_differing"]
    # every pair that has a path (all but a handful of the pool)
    assert POOL - 40 <= differing["value"] <= POOL
    assert res["failed"] > 0
