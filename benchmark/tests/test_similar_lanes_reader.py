"""The reader PR 46 adds (`similar_lanes_per_call`): the expected
value on a synthetic `ctx`, and None (never an error) on what a
program without the two series serves: the parent commit (the rule
PR 32 was refused over), the `--no-device` child, a cell that sends no
vector query. Its cases live here and not in `test_sift_readers.py`
because a PR that claims in an existing cell edits no file the
benchmark already has."""

import json
import os

import pytest

from conftest import ROOT, load

CALLS = 'rendezvous_calls_total{family="similar"}'
RIDERS = 'rendezvous_riders_total{family="similar"}'
# another family's calls over the same window are not this reader's
OTHER = {'rendezvous_calls_total{family="recurse"}': 900.0,
         'rendezvous_riders_total{family="recurse"}': 7100.0}
NAME = "similar_lanes_per_call"


def ctx(before=None, after=None):
    return {"replies": [], "window_s": 45.0, "trace": None, "peaks": None,
            "counters_before": before or {}, "counters_after": after or {},
            "notes": []}


def read(context):
    return load(f"metrics/{NAME}.py").read(context)


@pytest.mark.parametrize("before,after,want", [
    # 4,000 calls carried 17,200 queries in the window
    ({CALLS: 1000.0, RIDERS: 1000.0}, {CALLS: 5000.0, RIDERS: 18200.0}, 4.3),
    # every request rode alone
    ({CALLS: 10.0, RIDERS: 10.0}, {CALLS: 510.0, RIDERS: 510.0}, 1.0),
    # the series appeared inside the window: counted from 0
    ({}, {CALLS: 200.0, RIDERS: 1600.0}, 8.0),
    ({CALLS: 1.0, RIDERS: 1.0, **OTHER},
     {CALLS: 3.0, RIDERS: 8.0, **{k: 2 * v for k, v in OTHER.items()}}, 3.5),
], ids=["mean", "alone", "from-zero", "own-family"])
def test_it_reads_riders_over_calls_of_its_own_family(before, after, want):
    assert read(ctx(before, after)) == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("before,after", [
    ({}, {}),                                   # the parent: no series
    ({}, OTHER),                                # another family's only
    ({}, {CALLS: 5.0}), ({}, {RIDERS: 5.0}),    # one of the two
    ({CALLS: 7.0, RIDERS: 9.0}, {CALLS: 7.0, RIDERS: 9.0}),   # no call
], ids=["parent", "other-family", "calls-only", "riders-only", "no-call"])
def test_it_is_silent_where_there_is_nothing_to_read(before, after):
    assert read(ctx(before, after)) is None


def test_its_entry_is_the_issues():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    assert entries[NAME] == {
        "name": NAME, "unit": "lanes", "better": "higher",
        "source": "program_counter", "layer": "executor",
        "moves": "ok_qps", "workloads": ["sift1m-exact.knn-mix"]}
