"""EXPLAIN / EXPLAIN ANALYZE: the plan-introspection surface.

Acceptance contract (query/explain.py docstring, docs/deployment.md):

  * `explain` NEVER changes execution — the `data` payload is
    byte-identical with and without it (differential tests below, at
    the engine and HTTP layers and over the full golden workload);
  * ANALYZE actuals are the execution's own counts (actualRows ==
    emitted rows, actualRootRows == the pre-filter root set);
  * estimated-vs-actual rows honor the documented per-basis error
    bound on EVERY golden workload query:
        exact    actual == est
        index    actual <= est <= estMax
        stats    actual <= estMax
        unknown  no claim
"""

import json
import urllib.error
import urllib.request

import pytest

from dgraph_tpu.engine.db import GraphDB
from dgraph_tpu.gql.lexer import GQLError
from dgraph_tpu.gql.parser import parse
from dgraph_tpu.query.plan import skeleton
from tests.golden import runner

SCHEMA = """
name: string @index(exact, term) .
age: int @index(int) .
friend: [uid] @reverse .
"""

NQUADS = """
_:a <name> "alice" .
_:a <age> "30" .
_:b <name> "bob" .
_:b <age> "25" .
_:c <name> "carol" .
_:c <age> "35" .
_:a <friend> _:b .
_:a <friend> _:c .
_:b <friend> _:c .
"""

Q_EQ = '{ q(func: eq(name, "alice")) { name age friend { name } } }'
Q_HAS = '{ q(func: has(age)) { age } }'


@pytest.fixture(scope="module")
def db():
    d = GraphDB(prefer_device=False)
    d.alter(schema_text=SCHEMA)
    d.mutate(set_nquads=NQUADS)
    return d


# ----------------------------------------------------- @explain parsing


def test_parser_explain_flag():
    res = parse("@explain { q(func: has(name)) { name } }")
    assert res.explain == "plan"
    assert len(res.queries) == 1


def test_parser_explain_analyze():
    res = parse("@explain(analyze: true) { q(func: has(name)) "
                "{ name } }")
    assert res.explain == "analyze"


def test_parser_explain_analyze_false_is_plan():
    res = parse("@explain(analyze: false) { q(func: has(name)) "
                "{ name } }")
    assert res.explain == "plan"


def test_parser_repeated_explain_keeps_stronger_mode():
    """A bare @explain after @explain(analyze: true) must not
    downgrade analyze to plan — repetition keeps the stronger mode,
    like the transport-flag/document-directive combiner."""
    res = parse("@explain(analyze: true) @explain "
                "{ q(func: has(name)) { name } }")
    assert res.explain == "analyze"
    res = parse("@explain @explain(analyze: true) "
                "{ q(func: has(name)) { name } }")
    assert res.explain == "analyze"


def test_parser_rejects_unknown_directive_and_options():
    with pytest.raises(GQLError, match="unknown document directive"):
        parse("@expain { q(func: has(name)) { name } }")
    with pytest.raises(GQLError, match="only 'analyze'"):
        parse("@explain(verbose: true) { q(func: has(name)) "
              "{ name } }")
    with pytest.raises(GQLError, match="true or false"):
        parse("@explain(analyze: maybe) { q(func: has(name)) "
              "{ name } }")


def test_explain_flag_does_not_change_skeleton():
    """An @explain'd request compiles to the SAME plan as the plain
    text: the flag is a response annotation, not a plan input."""
    plain = parse(Q_EQ)
    flagged = parse("@explain(analyze: true) " + Q_EQ)
    assert skeleton(plain)[0] == skeleton(flagged)[0]


# ------------------------------------------------------- engine surface


def test_no_explain_by_default(db):
    resp = db.query(Q_EQ)
    assert "explain" not in resp["extensions"]


def test_explain_plan_payload(db):
    resp = db.query(Q_EQ, explain="plan")
    e = resp["extensions"]["explain"]
    assert e["mode"] == "plan"
    p = e["planner"]
    assert p["cached"] is True
    assert len(p["skeleton"]) == 16
    int(p["skeleton"], 16)
    assert p["blocks"] and isinstance(p["blocks"][0], str)
    assert set(e["tiers"]) == {"planner", "columnar", "compressed",
                               "device", "deviceMinEdges", "quantized",
                               "vector", "fused", "fusedMinRows"}
    assert e["tiers"]["vector"] == []  # no similar_to in this request
    assert e["tiers"]["planner"] in ("adaptive", "static")
    # per-stage tier decisions ride every explain payload
    assert isinstance(e["tierDecisions"], list)
    blk = e["blocks"][0]
    for k in ("name", "attr", "estRows", "estRowsMax", "basis",
              "source"):
        assert k in blk
    assert blk["basis"] in ("exact", "index", "stats", "unknown")
    # plan mode annotates estimates only: no execution measurements
    assert "actualRows" not in blk
    assert "counters" not in e and "stages" not in e
    # the eq root estimated from the token index, capped by the tablet
    assert blk["basis"] == "stats"
    assert blk["estRowsMax"] >= len(resp["data"]["q"])
    # children annotated with expansion estimates
    kids = {c["attr"]: c for c in blk["children"]}
    assert "friend" in kids and kids["friend"]["basis"] == "stats"


def test_explain_vector_tier_decisions():
    """A similar_to request's explain carries tiers.vector: one entry
    per evaluation with the serving tier and, when quantized, its
    recall budget (nprobe / rerank / calibrated sample recall) —
    alongside the planner's generic tierDecisions entry."""
    import numpy as np

    rng = np.random.default_rng(50)
    C = rng.standard_normal((16, 4)).astype(np.float32)
    vecs = C[rng.integers(0, 16, 400)] + np.float32(0.3) \
        * rng.standard_normal((400, 4)).astype(np.float32)
    d = GraphDB(prefer_device=False, vec_index_min_rows=100)
    d.alter("embedding: float32vector @index(vector(ivf)) .")
    d.mutate(set_nquads="\n".join(
        f'<0x{i + 1:x}> <embedding> "{list(map(float, vecs[i]))}"'
        '^^<xs:float32vector> .' for i in range(len(vecs))),
        commit_now=True)
    d.rollup_all()
    q = ('{ q(func: similar_to(embedding, 3, "[1.0, 0.0, -1.0, '
         '0.5]")) { uid } }')
    e = d.query(q, explain="analyze")["extensions"]["explain"]
    vd = e["tiers"]["vector"]
    assert len(vd) == 1
    ent = vd[0]
    assert ent["pred"] == "embedding" and ent["tier"] == "quantized"
    for key in ("nprobe", "rerank", "nlist", "scannedRows",
                "sampleRecall", "k", "n", "metric"):
        assert key in ent, key
    assert ent["scannedRows"] <= ent["n"]
    sim = [x for x in e["tierDecisions"] if x["stage"] == "similar_to"]
    assert sim and sim[0]["tier"] == "quantized"
    assert "quantized" in sim[0]["costUs"]
    # tabstats surfaces the trained index for EXPLAIN's costing
    from dgraph_tpu.storage.tabstats import tablet_stats
    st = tablet_stats(d.tablets["embedding"])
    assert st["vectorIndex"]["nlist"] == ent["nlist"]
    assert st["residency"]["vecIndex"] > 0
    # the stage span carries the tier for the coststore's cells
    spans = [s for s in e["stages"] if s["stage"] == "similar_to"]
    assert spans and spans[0]["tier"] == "quantized"


def test_explain_directive_matches_kwarg(db):
    via_kwarg = db.query(Q_EQ, explain="plan")
    via_directive = db.query("@explain " + Q_EQ)
    assert via_directive["extensions"]["explain"]["blocks"] == \
        via_kwarg["extensions"]["explain"]["blocks"]
    assert via_directive["data"] == via_kwarg["data"]


def test_invalid_explain_mode_rejected(db):
    with pytest.raises(ValueError, match="explain must be"):
        db.query(Q_EQ, explain="bogus")


def test_plan_cache_outcome_surfaces(db):
    q = '{ cachehit_probe(func: eq(name, "alice")) { name } }'
    first = db.query(q, explain="plan")
    second = db.query(q, explain="plan")
    assert first["extensions"]["explain"]["planner"]["cacheHit"] \
        is False
    assert second["extensions"]["explain"]["planner"]["cacheHit"] \
        is True


def test_analyze_actuals_match_emitted_rows(db):
    resp = db.query(Q_HAS, explain="analyze")
    e = resp["extensions"]["explain"]
    assert e["mode"] == "analyze"
    blk = e["blocks"][0]
    assert blk["actualRows"] == len(resp["data"]["q"]) == 3
    # no filter/pagination: the root set IS the result set
    assert blk["actualRootRows"] == 3
    # has() over a clean-or-dirty tablet: the documented bound
    assert blk["basis"] in ("index", "stats")
    assert blk["actualRootRows"] <= blk["estRowsMax"]


def test_analyze_carries_trace_stages_and_counters(db):
    resp = db.query(Q_EQ, explain="analyze")
    e = resp["extensions"]["explain"]
    assert e["traceId"]
    assert isinstance(e["counters"], dict)
    stages = [s["stage"] for s in e["stages"]]
    assert "parse" in stages and "encode" in stages
    for s in e["stages"]:
        assert s["durUs"] >= 0.0


def test_explain_never_changes_data_bytes(db):
    """The differential acceptance test, engine layer: the serialized
    `data` payload with explain on (kwarg AND directive, both modes)
    is byte-identical to the plain request's."""
    def data_bytes(raw: str) -> str:
        head = '{"data":'
        assert raw.startswith(head)
        return raw.split(',"extensions":', 1)[0][len(head):]

    plain = data_bytes(db.query_json(Q_EQ))
    assert plain == data_bytes(db.query_json(Q_EQ, explain="plan"))
    assert plain == data_bytes(db.query_json(Q_EQ, explain="analyze"))
    assert plain == data_bytes(db.query_json("@explain " + Q_EQ))
    assert plain == data_bytes(
        db.query_json("@explain(analyze: true) " + Q_EQ))


def test_reqlog_entries_carry_plan_key(db):
    """/debug/requests joins against the plan cache: a planned query's
    record carries the SAME 16-hex skeleton EXPLAIN reports."""
    from dgraph_tpu.utils import reqlog

    reqlog.reset()
    resp = db.query(Q_EQ, explain="plan")
    skel = resp["extensions"]["explain"]["planner"]["skeleton"]
    recs = [r for r in reqlog.snapshot()["recent"]
            if r["op"] == "query"]
    assert recs and recs[-1]["plan_key"] == skel
    assert recs[-1]["batch_id"] == ""  # unbatched dispatch


# -------------------------------------- golden workload: est vs actual


def _check_bounds(blk: dict, depth: int, name: str) -> int:
    """Recursively enforce the documented per-basis error bound; returns
    the number of (node, bound) comparisons actually made."""
    basis = blk["basis"]
    assert basis in ("exact", "index", "stats", "unknown"), \
        f"{name}: unknown basis {basis!r}"
    est, cap = blk["estRows"], blk["estRowsMax"]
    actual = blk["actualRootRows"] if depth == 0 else blk["actualRows"]
    checked = 0
    if basis != "unknown" and actual >= 0:
        checked = 1
        ctx = (f"{name} depth={depth} attr={blk['attr']} "
               f"basis={basis} est={est} cap={cap} actual={actual} "
               f"({blk['source']})")
        if basis == "exact":
            assert actual == est, ctx
        elif basis == "index":
            assert actual <= est <= cap, ctx
        else:  # stats
            assert actual <= cap, ctx
    for ch in blk.get("children", []):
        checked += _check_bounds(ch, depth + 1, name)
    return checked


@pytest.mark.parametrize("name", runner.query_names())
def test_golden_workload_estimate_bounds(name):
    """EXPLAIN ANALYZE over every golden workload query: the data is
    byte-identical to the plain run, and every non-unknown estimate
    honors its basis' documented bound against the measured actuals."""
    import os

    with open(os.path.join(runner.QUERY_DIR, name + ".gql")) as f:
        q = f.read()
    gdb = runner.get_db()
    plain = gdb.query(q)
    resp = gdb.query(q, explain="analyze")
    assert json.dumps(resp["data"], sort_keys=False) == \
        json.dumps(plain["data"], sort_keys=False)
    e = resp["extensions"]["explain"]
    assert e["mode"] == "analyze"
    # every executed block is annotated (var blocks execute without
    # emitting, so blocks >= emitted result keys)
    assert len(e["blocks"]) >= len(plain["data"])
    for blk in e["blocks"]:
        _check_bounds(blk, 0, name)


def test_golden_workload_estimates_are_informative():
    """The estimator must actually commit to bounds: across the golden
    workload, most root estimates carry a checkable (non-unknown)
    basis — a regression that demotes everything to 'unknown' would
    pass the bound test vacuously."""
    import os

    gdb = runner.get_db()
    total = checked = 0
    for name in runner.query_names():
        with open(os.path.join(runner.QUERY_DIR, name + ".gql")) as f:
            q = f.read()
        e = gdb.query(q, explain="analyze")["extensions"]["explain"]
        for blk in e["blocks"]:
            total += 1
            checked += _check_bounds(blk, 0, name) and 1
    assert total >= 70
    assert checked / total > 0.6, (checked, total)


# --------------------------------------------------------- HTTP surface


@pytest.fixture(scope="module")
def server():
    from dgraph_tpu.server.http import serve

    d = GraphDB(prefer_device=False)
    d.alter(schema_text=SCHEMA)
    d.mutate(set_nquads=NQUADS)
    httpd, alpha = serve(d, host="127.0.0.1", port=0, block=False)
    port = httpd.server_address[1]
    yield f"http://127.0.0.1:{port}"
    httpd.shutdown()


def _post(base, path, body):
    req = urllib.request.Request(
        base + path, body.encode(),
        {"Content-Type": "application/dql"})
    with urllib.request.urlopen(req) as resp:
        return resp.read().decode()


def test_http_explain_param(server):
    plain = _post(server, "/query", Q_EQ)
    for param in ("explain=true", "explain=plan"):
        raw = _post(server, f"/query?{param}", Q_EQ)
        out = json.loads(raw)
        assert out["extensions"]["explain"]["mode"] == "plan"
        # the data payload is byte-identical to the plain request
        assert raw.split(',"extensions":', 1)[0] == \
            plain.split(',"extensions":', 1)[0]
    out = json.loads(_post(server, "/query?explain=analyze", Q_EQ))
    e = out["extensions"]["explain"]
    assert e["mode"] == "analyze"
    assert e["blocks"][0]["actualRows"] == len(out["data"]["q"])


def test_http_explain_directive(server):
    out = json.loads(_post(server, "/query",
                           "@explain(analyze: true) " + Q_HAS))
    assert out["extensions"]["explain"]["mode"] == "analyze"


def test_http_bad_explain_is_400(server):
    req = urllib.request.Request(
        server + "/query?explain=verbose", Q_EQ.encode(),
        {"Content-Type": "application/dql"})
    with pytest.raises(urllib.error.HTTPError) as err:
        urllib.request.urlopen(req)
    assert err.value.code == 400


def test_http_debug_stats_endpoint(server):
    _post(server, "/query", Q_EQ)  # guarantee observations exist
    with urllib.request.urlopen(server + "/debug/stats") as resp:
        out = json.loads(resp.read())
    for key in ("tablets", "cost", "costStore", "deviceCache",
                "planCache", "histograms", "counters", "schemaEpoch"):
        assert key in out, key
    tab = out["tablets"]["name"]
    for key in ("nSrc", "edges", "fanout", "tokenIndex", "valueTypes",
                "bytesAtRest", "bytesDecoded", "residency", "dirtyOps",
                "touches"):
        assert key in tab, key
    # base cardinality + un-folded overlay ops covers every write the
    # fixture made (nSrc counts BASE state; fresh writes sit in the
    # dirty overlay until a rollup folds them)
    assert tab["nSrc"] + tab["dirtyOps"] >= 3
    assert tab["touches"] > 0
    # the observed-cost store saw this process' stage spans
    assert out["costStore"]["observations"] > 0
    stages = {ent["stage"] for ent in out["cost"]}
    assert "query" in stages


def test_grpc_explain_directive():
    """The generic (wire-codec) gRPC surface needs no transport
    support: the in-query directive rides extensions like HTTP's."""
    grpc = pytest.importorskip("grpc")  # noqa: F841
    from dgraph_tpu.server.grpc_api import GrpcClient, serve_grpc
    from dgraph_tpu.server.http import AlphaServer

    alpha = AlphaServer(db=GraphDB(prefer_device=False))
    alpha.db.alter(schema_text=SCHEMA)
    alpha.db.mutate(set_nquads=NQUADS)
    grpc_server, port = serve_grpc(alpha, port=0)
    client = GrpcClient(f"127.0.0.1:{port}")
    try:
        out = client.query("@explain " + Q_EQ)
        assert out["extensions"]["explain"]["mode"] == "plan"
        assert out["data"]["q"] == \
            client.query(Q_EQ)["data"]["q"]
    finally:
        client.close()
        grpc_server.stop(0)
