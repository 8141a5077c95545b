"""bench/kernelcheck.py at toy shapes with the Pallas kernels
simulated: every check runs and agrees with its twin on the CPU
backend. The real shapes are chip_smoke.py's job. `tiny` and
`interpret` are arguments of `run()` only; the HTTP route passes
neither and exists only where `alpha --kernelcheck` asked for it."""

import json
import urllib.error
import urllib.request

import pytest

from dgraph_tpu.bench import kernelcheck
from dgraph_tpu.engine.db import GraphDB
from dgraph_tpu.server.http import serve
from tests.golden.dataset import generate


@pytest.fixture(scope="module")
def db():
    schema, quads = generate(1)
    db = GraphDB(device_min_edges=1)
    db.alter(schema)
    db.mutate(set_nquads="\n".join(quads))
    db.rollup_all()
    return db


def test_every_kernel_matches_its_twin(db):
    out = kernelcheck.run(db, pred="starring", tiny=True, interpret=True)
    assert out["device"]["platform"] == "cpu"
    assert out["tiny"] and out["interpret"]
    assert sorted(out["kernels"]) == [
        "bfs_digest_xla", "bitmap_and_pallas", "bucket_or_pallas",
        "fused_rank_page", "knn_exact", "range_select",
        "score_dot_pallas", "score_int8_pallas", "setops_cosort",
        "sssp_dist"]
    bad = {k: v for k, v in out["kernels"].items() if not v["ok"]}
    assert not bad, bad


def test_refusal_is_recorded_not_raised(db):
    """interpret off on the CPU backend: Pallas refuses, the sweep
    records the compiler's words and the other checks still run."""
    out = kernelcheck.run(
        db, checks=("bitmap_and_pallas", "fused_rank_page"), tiny=True)
    assert not out["interpret"]
    assert sorted(out["kernels"]) == ["bitmap_and_pallas",
                                      "fused_rank_page"]
    assert out["kernels"]["fused_rank_page"]["ok"]
    refused = out["kernels"]["bitmap_and_pallas"]
    assert not refused["ok"] and "interpret" in refused["error"]


def _post(url):
    req = urllib.request.Request(url, data=b"")
    try:
        with urllib.request.urlopen(req, timeout=300) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_route_exists_only_when_asked_for(db):
    httpd, alpha = serve(db, port=0, block=False)
    base = f"http://127.0.0.1:{httpd.server_address[1]}"
    try:
        url = base + "/debug/kernelcheck?checks=nope"
        assert _post(url)[0] == 404
        alpha.kernelcheck = True  # what `alpha --kernelcheck` sets
        status, out = _post(url)
        assert status == 400 and "nope" in json.dumps(out)
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(url, timeout=30)  # GET: no route
        assert e.value.code == 404
    finally:
        httpd.shutdown()
