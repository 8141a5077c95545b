"""bench/kernelcheck.py at toy shapes: every check runs and agrees
with its twin on the CPU backend. The real shapes are chip_smoke.py's
job. `tiny` is an argument of `run()` only; the HTTP route does not
pass it and exists only where `alpha --kernelcheck` asked for it."""

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


CHECKS = ["bfs_digest_xla", "bfs_paths", "bfs_traverse", "fused_rank_page",
          "knn_exact", "range_select", "setops_cosort", "sssp_dist"]


@pytest.mark.parametrize("name", CHECKS)
def test_every_kernel_matches_its_twin(db, name):
    out = kernelcheck.run(db, pred="starring", checks=(name,),
                          tiny=True)
    assert out["device"]["platform"] == "cpu" and out["tiny"]
    assert list(out["kernels"]) == [name]
    assert out["kernels"][name]["ok"], out["kernels"][name]


def test_refusal_is_recorded_not_raised(db, monkeypatch):
    """A check the compiler refuses: the sweep records the compiler's
    words and the other checks still run. Every check is a stand-in
    here, so the whole table runs and names itself."""
    def refuse(*_a):
        raise RuntimeError("Mosaic: not implemented")

    for fn in ("check_bfs_digest", "check_bfs_traverse", "check_bfs_paths",
               "check_sssp_dist",
               "check_range_select", "check_fused_rank_page",
               "check_knn_exact"):
        monkeypatch.setattr(kernelcheck, fn, lambda *_a: {"ok": True})
    monkeypatch.setattr(kernelcheck, "check_setops_cosort", refuse)
    out = kernelcheck.run(db, tiny=True)
    assert sorted(out["kernels"]) == CHECKS
    refused = out["kernels"].pop("setops_cosort")
    assert not refused["ok"] and refused["error"] == \
        "RuntimeError: Mosaic: not implemented"
    assert all(v["ok"] for v in out["kernels"].values())


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
