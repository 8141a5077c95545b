"""BENCHMARK.json against the contract's rules of form, and against
the files it names."""

import json
import os
import re

import pytest

from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])


def test_every_name_and_unit_is_well_formed(bench):
    names = []
    for c in bench["configs"]:
        names.append(c["name"])
        assert all(NAME.match(k) for k in c["reduced"])
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in bench["workloads"]:
        names += [w["name"], w["config"], w["traffic"]]
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in bench["end_to_end"] + bench["per_layer"]:
        names.append(m["name"])
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    assert all(NAME.match(n) for n in names), names
    metric_names = [m["name"] for m in bench["end_to_end"]
                    + bench["per_layer"]]
    assert len(metric_names) == len(set(metric_names))


def test_everything_named_is_a_file_of_its_own(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    for c in bench["configs"]:
        assert c["file"].startswith(bench["paths"][0] + "/")
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert os.path.exists(os.path.join(
            BENCH, "datasets", cfg["dataset"] + ".py"))
        assert any(w["config"] == c["name"] for w in bench["workloads"])
    for w in bench["workloads"]:
        assert os.path.exists(os.path.join(
            BENCH, "traffic", w["traffic"] + ".json"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert os.path.exists(os.path.join(
            BENCH, "metrics", m["name"] + ".py")), m["name"]
        assert set(m.get("workloads", cells)) <= cells
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
    with open(os.path.join(BENCH, "peaks.json")) as f:
        assert "TPU v5 lite" in json.load(f)


def test_the_harness_names_no_cell_query_or_metric(bench):
    with open(os.path.join(BENCH, "run.py")) as f:
        src = f.read()
    words = [w["name"] for w in bench["workloads"]] \
        + [w["traffic"] for w in bench["workloads"]] \
        + [c["name"] for c in bench["configs"]] \
        + [m["name"] for m in bench["end_to_end"] + bench["per_layer"]
           if m["name"] not in ("setup_s", "snapshot_load_s", "warmup_s",
                                "compiles_in_window")]
    for w in bench["workloads"]:
        with open(os.path.join(BENCH, "traffic",
                               w["traffic"] + ".json")) as f:
            words += [t["name"] for t in json.load(f)["templates"]]
    # setup_s & co. are phases of a run the harness itself times and
    # hands to the readers under those keys
    assert [w for w in words if w in src] == []
