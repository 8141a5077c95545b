"""The harness end to end on the CPU at a tiny scale (`--rehearse`):
the result line's form, that a cell, a configuration, a traffic mix
and a per-layer metric are added by files and entries alone, that a
degraded graph or a broken timed path comes out `correct: false`, and
that no chip is a failure with nothing on stdout.

Each rehearsal starts three children and takes about half a minute.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT, alter_answers, copy_checkout

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
               "compared"}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A checkout of its own: the benchmark's files copied, the
    program linked in, and a throwaway configuration, traffic mix,
    cell and per-layer metric ADDED as new files and new entries."""
    root = str(tmp_path_factory.mktemp("checkout"))
    bdir = copy_checkout(root)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(ROOT, bench["configs"][0]["file"])) as f:
        cfg = json.load(f)
    cfg["name"] = "throwaway-cfg"
    cfg["settle"] = {"counters": [], "quiet_s": 2, "max_s": 30}
    # a deployment's own alpha flags come from its file: one plan at a
    # time in the cache, where the default holds every skeleton
    cfg["serve_flags"] = ["--plan-cache-size", "1"]
    with open(os.path.join(bdir, "configs", "throwaway-cfg.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bdir, "traffic", "queries",
                           "throwaway_q.gql"), "w") as f:
        f.write("{ q(func: uid(0x20003)) { name rating } }\n")
    with open(os.path.join(bdir, "traffic", "throwaway-mix.json"), "w") as f:
        json.dump({"loop": "closed", "clients": 2, "bindings": 3,
                   "templates": [
                       {"name": "throwaway_q",
                        "file": "queries/throwaway_q.gql"},
                       {"name": "q058_after_with_sort",
                        "file": "queries/df_q058_after_with_sort.gql",
                        "params": {"first": {"int": [3, 5]}}}]}, f)
    with open(os.path.join(bdir, "metrics", "throwaway_count.py"), "w") as f:
        f.write("def read(ctx):\n    return len(ctx['pool'])\n")
    bench["configs"].append({
        "name": "throwaway-cfg", "source": "none",
        "file": "benchmark/configs/throwaway-cfg.json",
        "reduced": ["scale"], "why": "test"})
    bench["workloads"].append({
        "name": "throwaway.cell", "config": "throwaway-cfg",
        "traffic": "throwaway-mix", "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "throwaway_count", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "test", "moves": "ok_qps",
        "workloads": ["throwaway.cell"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def rehearse(root, *args, env=None):
    base = dict(os.environ, JAX_PLATFORMS="cpu",
                # the program's own rule: this variable places the
                # compile cache; the sandbox's warm one saves minutes
                JAX_COMPILATION_CACHE_DIR=os.path.join(ROOT, ".jax_cache"))
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), *args],
        env=base if env is None else env, capture_output=True, text=True,
        timeout=900)


def last_line(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_added_cell_runs_and_the_last_line_has_the_contracts_keys(checkout):
    p = rehearse(checkout, "--workload", "throwaway.cell", "--seed",
                 str(2**31 + 17), "--seconds", "3", "--trace", "1",
                 "--rehearse", "2")
    res = last_line(p)
    assert set(res) == RESULT_KEYS
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert res["device"]["platform"] == "cpu"
    # the throwaway metric was read; a CPU run reports counts only
    assert res["metrics"]["throwaway_count"] == {"value": 6,
                                                 "unit": "count"}
    with open(os.path.join(checkout, "BENCHMARK.json")) as f:
        by_name = {m["name"]: m for m in json.load(f)["per_layer"]}
    assert res["metrics"]["compiles_in_window"]["value"] == 0
    # the configuration's own alpha flag arrived: two skeletons take
    # turns in a plan cache of one
    assert res["metrics"]["plan_cache_hit_share"]["value"] < 100
    assert all(by_name[m]["source"] == "program_counter"
               for m in res["metrics"])
    # each number compared stands beside its limit, on an earlier
    # line; the plain reference answered the sort-page template
    assert p.stdout.count("(limit 0)") == 4
    assert "plain reference 0 of the 3 pool queries (of 6)" in p.stdout
    # and in the result's line, under its last key, and as stderr's
    # last lines: what the driver's record keeps of a run at fault
    assert list(res)[-1] == "compared" and len(res["compared"]) == 4
    assert all(c["value"] == 0 == c["limit"]
               for c in res["compared"].values())
    assert res["compared"]["plain_answers_differing"]["of"] == 3
    tail = p.stderr.strip().splitlines()[-4:]
    assert [ln.split(":")[0] for ln in tail] \
        == ["compared " + k for k in res["compared"]]
    # which templates' stages went to the device, and the server's
    # full collections, are said in every run
    assert "first pass, device stages by template: throwaway_q: " in p.stdout
    assert "full collections since it started" in p.stdout


def test_end_to_end_line_of_a_cpu_run_carries_no_timing(checkout):
    p = rehearse(checkout, "--workload", "throwaway.cell", "--seed", "7",
                 "--seconds", "2", "--trace", "0", "--rehearse", "2")
    res = last_line(p)
    assert set(res) == RESULT_KEYS and res["metrics"] == {}


def test_degraded_graph_comes_out_not_correct(checkout):
    """The control: the served graph's ratings are rounded to one
    decimal while the reference serves the full ones; the template
    that orders by rating and prints it must mismatch."""
    p = rehearse(checkout, "--workload", "throwaway.cell", "--seed", "11",
                 "--seconds", "2", "--trace", "0", "--rehearse", "2",
                 "--control", "rating-1dp")
    res = last_line(p)
    assert res["correct"] is False and res["failed"] > 0
    assert res["compared"]["window_replies_mismatching"]["value"] \
        == res["failed"]


def test_broken_timed_path_comes_out_not_correct(checkout, tmp_path):
    """The chip child answers through a launcher that alters one
    answer where it is produced (the first rating of the engine's
    serialized reply gets a digit put before it): the rest of the run is the harness's own,
    and `correct` must come out false."""
    broken = os.path.join(checkout, "benchmark", "serve_chip.py")
    sound = broken + ".sound"
    shutil.copy(broken, sound)
    try:
        alter_answers(broken, "rating")
        p = rehearse(checkout, "--workload", "throwaway.cell", "--seed",
                     "7", "--seconds", "2", "--trace", "0",
                     "--rehearse", "2")
    finally:
        shutil.move(sound, broken)
    res = last_line(p)
    assert res["correct"] is False and res["failed"] > 0


def test_no_chip_is_a_failure_with_nothing_on_stdout(checkout):
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    p = rehearse(checkout, "--workload", "throwaway.cell", "--seed", "7",
                 "--seconds", "2", "--trace", "0", env=env)
    assert p.returncode != 0 and p.stdout == ""
    # --rehearse alone, without JAX_PLATFORMS=cpu, is refused too
    p = rehearse(checkout, "--workload", "throwaway.cell", "--seed", "7",
                 "--seconds", "2", "--trace", "0", "--rehearse", "2",
                 env=env)
    assert p.returncode != 0 and p.stdout == ""


def test_without_the_program_around_it_the_harness_fails(tmp_path):
    root = str(tmp_path)
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = json.load(f)["workloads"][0]["name"]
    p = rehearse(root, "--workload", cell, "--seed", "7", "--seconds", "2",
                 "--trace", "0")
    assert p.returncode != 0 and p.stdout == ""
