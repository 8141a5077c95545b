"""The cell `graph500-khop.khop-deep-c16` end to end on the CPU at a
tiny scale (`--rehearse`), beside the existing cells and through the
same harness: the result line's form, `correct: true` with every pool
query held to the plain reference, the control graph (one edge in a
thousand left out) coming out `correct: false`, and so a count
altered where it is produced.

On the CPU the gate keeps every traversal on the host tier (an
XLA-CPU "device" shares the host's silicon: `executor._device_worth`),
so this walks the harness, the generator, the reference and the host
tier; the device tier is tier-1's (tests/test_recurse_bound.py, which
forces it) and the chip's.

Each rehearsal starts three children and takes about a quarter of a
minute.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import ROOT, alter_answers, copy_checkout

CELL = "graph500-khop.khop-deep-c16"
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
    return rehearse("--seed", str(2**31 + 31), "--seconds", "3",
                    "--trace", "1", "--rehearse", "10")


def test_the_last_line_has_the_contracts_keys_and_is_correct(sound):
    res = last_line(sound)
    assert set(res) == RESULT_KEYS
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert res["device"]["platform"] == "cpu"
    assert sound.stdout.count("(limit 0)") == 4
    assert list(res)[-1] == "compared"
    assert res["compared"]["plain_answers_differing"] \
        == {"value": 0, "limit": 0, "of": 64}
    # the plain reference answers both templates, every pool query
    assert "plain reference 0 of the 64 pool queries (of 64)" \
        in sound.stdout
    assert "pool 64 queries of 2 templates" in sound.stdout


def test_every_request_is_a_recurse_and_the_counters_say_which_tier(sound):
    res = last_line(sound)
    (gate,) = [ln for ln in sound.stdout.splitlines()
               if ln.startswith("gate: ")]
    took = dict(part.rsplit(" +", 1) for part in
                gate.split("in the window: ")[1].split(", "))
    assert int(took["recurse_tier_total"]) == res["attempted"]
    # on the CPU the host tier answers; the window compiles nothing
    assert int(took["query_device_recurse_total"]) == 0
    m = res["metrics"]
    assert m["compiles_in_window"]["value"] == 0
    assert m["plan_cache_hit_share"]["value"] > 90.0
    # readers of what no host-tier run serves stay silent, they do
    # not raise
    assert "bfs_roofline" not in m and "recurse_host_ms" not in m
    # no call of the rendezvous was made: no lanes a call
    assert "recurse_lanes_per_call" not in m


def test_the_control_graph_comes_out_not_correct():
    p = rehearse("--seed", "13", "--seconds", "2", "--trace", "0",
                 "--rehearse", "10", "--control", "drop-edges")
    res = last_line(p)
    assert set(res) == RESULT_KEYS
    assert res["correct"] is False
    assert res["failed"] > 0


def test_a_count_altered_where_it_is_produced_comes_out_not_correct(
        tmp_path):
    """The rest of a run with the timed path broken underneath: the
    chip child answers through a launcher that puts a digit before
    the count of every reply; the harness is the tree's own."""
    root = str(tmp_path / "checkout")
    alter_answers(os.path.join(copy_checkout(root), "serve_chip.py"),
                  "count")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    res = last_line(rehearse("--seed", "17", "--seconds", "2", "--trace",
                             "0", "--rehearse", "10", root=root))
    assert res["correct"] is False
    assert res["failed"] == res["attempted"] > 0
    assert res["compared"]["plain_answers_differing"]["value"] == 64
