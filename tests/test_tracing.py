"""Tracing: hierarchical spans on the query/commit paths, trace
context propagation (traceparent, RequestContext), Chrome-trace
export, /debug/traces + /debug/requests, extensions.server_latency,
the span-overhead budget, and the jax.profiler device-profile hook
(§5.1).
"""

import json

from dgraph_tpu.engine.db import GraphDB
from dgraph_tpu.utils import tracing


def test_spans_record_query_and_commit():
    tracing.clear()
    db = GraphDB(prefer_device=False)
    db.alter("name: string @index(exact) .")
    db.mutate(set_nquads='<1> <name> "t" .')
    db.query('{ q(func: eq(name, "t")) { name } }')
    names = [s["name"] for s in tracing.recent_spans()]
    assert "commit" in names and "query" in names and "block" in names
    q = next(s for s in reversed(tracing.recent_spans())
             if s["name"] == "query")
    assert q["args"]["blocks"] == 1 and q["dur_us"] > 0


def test_chrome_trace_export_shape():
    tracing.clear()
    with tracing.span("unit", k=1):
        pass
    events = tracing.export_chrome_trace()
    assert events and events[-1]["ph"] == "X"
    assert events[-1]["name"] == "unit"
    json.dumps(events)  # serializable as-is


def test_debug_traces_endpoint():
    import urllib.request
    from dgraph_tpu.server.http import serve
    tracing.clear()
    httpd, alpha = serve(block=False, port=0)
    try:
        port = httpd.server_address[1]
        alpha.handle_query("{ q(func: uid(0x1)) { uid } }", {})
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/debug/traces").read()
        events = json.loads(body)["traceEvents"]
        assert any(e["name"] == "query" for e in events)
    finally:
        httpd.shutdown()


def test_debug_traces_requires_acl_token():
    import pytest
    from dgraph_tpu.server.acl import AclError
    from dgraph_tpu.server.http import AlphaServer
    srv = AlphaServer(acl_secret=b"s3cret")
    with pytest.raises(AclError):
        srv.handle_traces("")  # anonymous: rejected like /state


def test_device_profile_smoke(tmp_path):
    import jax.numpy as jnp
    with tracing.profile_device(str(tmp_path)):
        jnp.arange(8).sum().block_until_ready()
    # a profile dump landed in the log dir
    assert any(tmp_path.rglob("*"))


def test_span_ring_bounded():
    tracing.clear()
    for i in range(5000):
        with tracing.span("x"):
            pass
    assert len(tracing.recent_spans(limit=10**6)) <= 4096


# ------------------------------------------------- hierarchical spans


def test_span_hierarchy_and_trace_ids():
    tracing.clear()
    with tracing.span("query"):
        with tracing.span("parse"):
            pass
        with tracing.span("execute"):
            with tracing.span("expand"):
                pass
    spans = {s["name"]: s for s in tracing.recent_spans()}
    q = spans["query"]
    assert q["parent_id"] == ""
    assert q["trace_id"] == q["span_id"]  # unbound spans self-root
    assert spans["parse"]["parent_id"] == q["span_id"]
    assert spans["execute"]["parent_id"] == q["span_id"]
    assert spans["expand"]["parent_id"] == spans["execute"]["span_id"]
    assert {s["trace_id"] for s in spans.values()} == {q["trace_id"]}


def test_bind_joins_existing_trace():
    tracing.clear()
    with tracing.bind("feedfacefeedface", "aaaaaaaaaaaaaaaa",
                      node="n1"):
        with tracing.span("query"):
            pass
    (s,) = tracing.spans_for("feedfacefeedface")
    assert s["parent_id"] == "aaaaaaaaaaaaaaaa"
    assert s["node"] == "n1"
    assert tracing.spans_for("feedfacefeedface")  # filter works
    assert not tracing.spans_for("no-such-trace")


def test_traceparent_roundtrip():
    hdr = tracing.format_traceparent("abc123", "00aa")
    got = tracing.parse_traceparent(hdr)
    assert got is not None
    tid, sid = got
    assert len(tid) == 32 and tid.endswith("abc123")
    assert len(sid) == 16 and sid.endswith("00aa")
    # non-hex trace ids still produce a well-formed header
    assert tracing.parse_traceparent(
        tracing.format_traceparent("not hex!", "")) is not None
    assert tracing.parse_traceparent("garbage") is None
    assert tracing.parse_traceparent(
        "00-" + "0" * 32 + "-" + "1" * 16 + "-01") is None


def test_disabled_records_nothing():
    tracing.clear()
    tracing.set_enabled(False)
    try:
        with tracing.span("x", k=1) as args:
            assert args == {"k": 1}  # attrs still usable
    finally:
        tracing.set_enabled(True)
    assert tracing.recent_spans() == []


def test_query_spans_join_request_trace():
    from dgraph_tpu.utils.reqctx import RequestContext

    db = GraphDB(prefer_device=False)
    db.alter("name: string @index(exact) .")
    db.mutate(set_nquads='<1> <name> "t" .')
    tracing.clear()
    ctx = RequestContext.background(trace_id="0123456789abcdef",
                                    parent_span="fedcba9876543210")
    db.query('{ q(func: eq(name, "t")) { name } }', ctx=ctx)
    spans = tracing.spans_for("0123456789abcdef")
    names = {s["name"] for s in spans}
    assert {"query", "parse", "execute", "block", "encode"} <= names
    q = next(s for s in spans if s["name"] == "query")
    assert q["parent_id"] == "fedcba9876543210"
    # children link under the query span, not the wire parent
    parse = next(s for s in spans if s["name"] == "parse")
    assert parse["parent_id"] == q["span_id"]


def test_mutate_records_span_and_server_latency():
    tracing.clear()
    db = GraphDB(prefer_device=False)
    out = db.mutate(set_nquads='<1> <name> "t" .')
    sl = out["extensions"]["server_latency"]
    assert sl["total_ns"] > 0
    assert sl["total_ns"] >= sl["processing_ns"]
    names = [s["name"] for s in tracing.recent_spans()]
    assert "mutate" in names and "commit" in names
    spans = {s["name"]: s for s in tracing.recent_spans()}
    assert spans["commit"]["trace_id"] == spans["mutate"]["trace_id"]


def test_chrome_export_has_node_lanes():
    tracing.clear()
    with tracing.bind("aa" * 8, node="nodeA"):
        with tracing.span("query"):
            pass
    with tracing.bind("aa" * 8, node="nodeB"):
        with tracing.span("rpc.recv"):
            pass
    events = tracing.export_chrome_trace(trace_id="aa" * 8)
    meta = {e["args"]["name"] for e in events if e["ph"] == "M"}
    assert meta == {"nodeA", "nodeB"}
    pids = {e["pid"] for e in events if e["ph"] == "X"}
    assert len(pids) == 2
    json.dumps(events)


def test_trace_merge_slices():
    from tools.trace_merge import merge_slices

    tracing.clear()
    with tracing.bind("bb" * 8, node="nodeA"):
        with tracing.span("query"):
            pass
    a = tracing.spans_for("bb" * 8)
    b = [dict(s, node="nodeB", name="rpc.recv") for s in a]
    events = merge_slices([("nodeA", a), ("nodeB", b)],
                          trace_id="bb" * 8)
    assert {e["args"]["name"] for e in events
            if e["ph"] == "M"} == {"nodeA", "nodeB"}
    assert len({e["pid"] for e in events if e["ph"] == "X"}) == 2
    json.dumps(events)


# ------------------------------------------- serving-edge integration


def _post(url, body, headers=None):
    import urllib.request
    req = urllib.request.Request(url, data=body.encode(),
                                 headers=headers or {})
    resp = urllib.request.urlopen(req)
    return resp, json.loads(resp.read())


def test_server_latency_and_trace_over_http():
    from dgraph_tpu.server.http import serve

    httpd, alpha = serve(block=False, port=0)
    try:
        base = f"http://127.0.0.1:{httpd.server_address[1]}"
        tid = "c0ffee" * 5 + "aa"  # 32 hex
        hdr = {"traceparent": f"00-{tid}-00000000000000aa-01"}
        resp, out = _post(base + "/mutate?commitNow=true",
                          '<0x1> <name> "n" .', hdr)
        assert out["extensions"]["server_latency"]["total_ns"] > 0
        resp, out = _post(base + "/query",
                          "{ q(func: uid(0x1)) { uid } }", hdr)
        sl = out["extensions"]["server_latency"]
        assert set(sl) == {"parsing_ns", "processing_ns",
                           "encoding_ns", "total_ns", "device_calls",
                           "device_enqueue_ns", "device_wait_ns",
                           "device_queue_ns", "device_fetch_ns"}
        assert all(v >= 0 for v in sl.values())
        assert sl["total_ns"] >= (sl["parsing_ns"]
                                  + sl["processing_ns"]
                                  + sl["encoding_ns"])
        # traceparent out: the response names the trace, and the
        # node-local slice is queryable by it
        assert resp.headers["X-Dgraph-Trace-Id"] == tid
        assert tracing.parse_traceparent(
            resp.headers["traceparent"])[0] == tid
        body = json.loads(__import__("urllib.request", fromlist=["x"])
                          .urlopen(base + f"/debug/traces?trace_id={tid}")
                          .read())
        names = {e["name"] for e in body["traceEvents"]
                 if e["ph"] == "X"}
        assert {"query", "parse", "execute", "mutate"} <= names
    finally:
        httpd.shutdown()


def test_debug_profile_and_requests_over_http():
    from dgraph_tpu.server.http import serve

    httpd, alpha = serve(block=False, port=0)
    try:
        base = f"http://127.0.0.1:{httpd.server_address[1]}"
        _post(base + "/mutate?commitNow=true", '<0x1> <name> "n" .')
        _, out = _post(base + "/query?debug=true",
                       "{ q(func: uid(0x1)) { uid } }",
                       {"X-Dgraph-Trace-Id": "prof1"})
        prof = out["extensions"]["profile"]["counters"]
        assert prof.get("dgraph_num_queries_total") == 1
        import urllib.request
        reqs = json.loads(urllib.request.urlopen(
            base + "/debug/requests").read())
        ops = {r["op"] for r in reqs["recent"]}
        assert {"query", "mutate"} <= ops
        assert any(r["trace_id"] == "prof1" and r["outcome"] == "ok"
                   and r["breakdown"]["total_ns"] > 0
                   for r in reqs["recent"])
        slow = reqs["slowest"]
        assert slow == sorted(slow, key=lambda r: -r["latency_ms"])
    finally:
        httpd.shutdown()


def test_request_log_records_shed_outcome():
    from dgraph_tpu.utils import reqlog
    from dgraph_tpu.server.http import AlphaServer
    import pytest
    from dgraph_tpu.utils.reqctx import Overloaded, RequestContext

    reqlog.reset()
    srv = AlphaServer(max_pending=1)
    ctx = RequestContext.background(trace_id="shed-trace")
    with srv._admit(None):  # occupy the only slot
        with pytest.raises(Overloaded):
            srv.handle_query("{ q(func: uid(0x1)) { uid } }", {},
                             ctx=ctx)
    snap = reqlog.snapshot()
    assert any(r["outcome"] == "shed" and r["trace_id"] == "shed-trace"
               for r in snap["recent"])


def test_request_log_carries_tenant_over_http():
    """The QoS accounting namespace rides X-Dgraph-Tenant ->
    RequestContext -> the reqlog `tenant` field at /debug/requests."""
    import urllib.request
    from dgraph_tpu.server.http import serve
    from dgraph_tpu.utils import reqlog

    reqlog.reset()
    httpd, _alpha = serve(block=False, port=0)
    try:
        base = f"http://127.0.0.1:{httpd.server_address[1]}"
        _post(base + "/query", "{ q(func: uid(0x1)) { uid } }",
              {"X-Dgraph-Tenant": "acme"})
        _post(base + "/query", "{ q(func: uid(0x1)) { uid } }")
        reqs = json.loads(urllib.request.urlopen(
            base + "/debug/requests").read())
        by_tenant = {r["tenant"] for r in reqs["recent"]
                     if r["op"] == "query"}
        assert "acme" in by_tenant, reqs["recent"]
        assert "" in by_tenant  # untagged stays untagged in the log
    finally:
        httpd.shutdown()


def test_tenant_qos_sheds_hot_tenant_only():
    """Per-tenant admission under the shared gate: the tenant over
    its bucket sheds (typed Overloaded -> the 429 class, labeled
    shed counter, reqlog tenant) while another tenant's request on
    the SAME server is admitted."""
    import pytest
    from dgraph_tpu.server.http import AlphaServer
    from dgraph_tpu.utils import metrics, reqlog
    from dgraph_tpu.utils.reqctx import Overloaded, RequestContext

    reqlog.reset()
    srv = AlphaServer(tenant_rate=1000.0, tenant_burst=2.0)
    q = "{ q(func: uid(0x1)) { uid } }"
    shed0 = metrics.get_counter("dgraph_tenant_shed_total",
                                labels={"tenant": "hog"})
    srv.qos._clock = lambda: 0.0  # freeze refill: burst only
    for _ in range(2):
        srv.handle_query(q, {}, ctx=RequestContext.background(
            tenant="hog"))
    with pytest.raises(Overloaded):
        srv.handle_query(q, {}, ctx=RequestContext.background(
            trace_id="hog-shed", tenant="hog"))
    # the quiet tenant is untouched by the hog's exhaustion
    srv.handle_query(q, {}, ctx=RequestContext.background(
        tenant="quiet"))
    assert metrics.get_counter("dgraph_tenant_shed_total",
                               labels={"tenant": "hog"}) == shed0 + 1
    assert any(r["outcome"] == "shed" and r["tenant"] == "hog"
               and r["trace_id"] == "hog-shed"
               for r in reqlog.snapshot()["recent"])


def test_server_latency_over_grpc():
    import pytest
    grpc = pytest.importorskip("grpc")  # noqa: F841
    from dgraph_tpu.server.grpc_api import GrpcClient, serve_grpc
    from dgraph_tpu.server.http import AlphaServer

    alpha = AlphaServer()
    server, port = serve_grpc(alpha, port=0)
    try:
        cl = GrpcClient(f"127.0.0.1:{port}")
        cl.mutate('<0x1> <name> "n" .')
        out = cl.query("{ q(func: uid(0x1)) { uid } }")
        sl = out["extensions"]["server_latency"]
        assert sl["total_ns"] >= (sl["parsing_ns"]
                                  + sl["processing_ns"]
                                  + sl["encoding_ns"]) > 0
        cl.close()
    finally:
        server.stop(None)


# ------------------------------------------- what a disabled span costs


def test_disabled_span_builds_no_record(monkeypatch):
    """With the ring off and nobody observing, a span block is its
    flag check: no span id is minted, no record is built, the trace
    context is left alone — counted, not timed."""
    minted = []
    monkeypatch.setattr(tracing, "_observers", [])
    monkeypatch.setattr(tracing, "new_span_id",
                        lambda: minted.append(1) or "0" * 16)
    tracing.clear()
    tracing.set_enabled(False)
    try:
        before = tracing._CUR.get()
        for _ in range(1000):
            with tracing.span("x", k=1) as args:
                assert tracing._CUR.get() is before
        assert args == {"k": 1}
    finally:
        tracing.set_enabled(True)
    assert minted == [] and tracing.recent_spans() == []
    with tracing.span("y"):
        pass
    assert len(minted) == 1 and len(tracing.recent_spans()) == 1


# ------------------------------- the collector on the profiler's clock


class _Annotation:
    log: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.log.append(("enter", self.name))

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))


def _collect_watched(monkeypatch, generation):
    import gc

    from dgraph_tpu.utils import metrics

    monkeypatch.setattr(_Annotation, "log", [])
    monkeypatch.setattr(tracing, "trace_annotation", _Annotation)
    was_enabled = gc.isenabled()
    gc.disable()            # no collection but the one asked for
    metrics.watch_gc()
    try:
        gc.collect(generation)
    finally:
        gc.callbacks.remove(metrics._on_gc)
        if was_enabled:
            gc.enable()
    return _Annotation.log


def test_a_full_collection_is_one_gc_pause_annotation(monkeypatch):
    assert _collect_watched(monkeypatch, 2) == [
        ("enter", "gc.pause"), ("exit", "gc.pause")]


def test_a_young_collection_opens_no_annotation(monkeypatch):
    assert _collect_watched(monkeypatch, 0) == []


def test_an_open_span_says_its_id():
    tracing.clear()
    sp = tracing.span("query")
    with sp:
        sid = sp.span_id
        assert tracing.current()[1] == sid
    assert tracing.recent_spans()[-1]["span_id"] == sid
