"""The reader of the front end's connection counter (PR 29): the
share on a window of counters, and None (never an error) on what a
program without the counter serves: the parent commit."""

import pytest

from conftest import load

CONNS, COUNT = "http_connections_total", "http_requests_total"


def ctx(before=None, after=None):
    return {"counters_before": before or {}, "counters_after": after or {},
            "replies": [], "window_s": 45.0}


def read(context):
    return load("metrics/http_conn_reuse_share.py").read(context)


@pytest.mark.parametrize("before, after, want", [
    # 8 clients and the harness's scrape, 13,000 requests
    ({CONNS: 120, COUNT: 700}, {CONNS: 129, COUNT: 13_700},
     100.0 * (1 - 9 / 13_000)),
    # a counter first seen inside the window counts from 0
    ({COUNT: 0}, {CONNS: 2, COUNT: 8}, 75.0),
    # a client that reconnects for every request, and the scrape
    ({CONNS: 5, COUNT: 5}, {CONNS: 106, COUNT: 105}, 0.0),
], ids=["kept", "from-zero", "reconnecting"])
def test_share_of_requests_on_an_open_connection(before, after, want):
    assert read(ctx(before, after)) == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("before, after", [
    ({COUNT: 100}, {COUNT: 1100}),            # the parent: no counter
    ({}, {}),                                  # nothing served
    ({CONNS: 3, COUNT: 7}, {CONNS: 4, COUNT: 7}),  # no request counted
], ids=["parent", "empty", "no-requests"])
def test_silent_where_there_is_nothing_to_read(before, after):
    assert read(ctx(before, after)) is None


def test_the_manifest_has_the_metric_in_every_cell():
    import json
    import os

    from conftest import ROOT
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = [m for m in json.load(f)["per_layer"]
                 if m["name"] == "http_conn_reuse_share"]
    assert entry == [{"name": "http_conn_reuse_share", "unit": "%",
                      "better": "higher", "source": "program_counter",
                      "layer": "front end", "moves": "read_p50_ms"}]
