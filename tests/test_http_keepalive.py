"""The front end keeps its connections (server/http.py `_Handler`):
HTTP/1.1 persistent connections, one send a reply, a connection never
left out of step. Counts and bytes only; nothing here reads a clock.
"""

import http.client
import json
import socket
import threading
import time

import pytest

from dgraph_tpu.engine.db import GraphDB
from dgraph_tpu.server import http as server_http
from dgraph_tpu.utils import metrics

QUERY = b'{ q(func: eq(kname, "Kay")) { kname kage } }'
ANSWER = {"q": [{"kname": "Kay", "kage": 41}]}
DQL = {"Content-Type": "application/dql"}
CONNS, REQS = "http_connections_total", "http_requests_total"


@pytest.fixture(scope="module")
def served():
    db = GraphDB(prefer_device=False)
    db.alter(schema_text="kname: string @index(exact) .\nkage: int .")
    db.mutate(db.new_txn(), set_nquads='_:k <kname> "Kay" .\n'
              '_:k <kage> "41"^^<xs:int> .', commit_now=True)
    httpd, alpha = server_http.serve(db, port=0, block=False)
    yield httpd
    httpd.shutdown()
    httpd.server_close()


@pytest.fixture
def port(served):
    return served.server_address[1]


@pytest.fixture
def conn(port):
    c = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    yield c
    c.close()


def ask(c, body=QUERY, path="/query", headers=DQL, method="POST"):
    c.request(method, path, body=body, headers=headers)
    r = c.getresponse()
    return r, r.read()


def moved(before, name, want):
    """The counter's change since `before`, once it has reached
    `want`: a handler counts after its last byte has left, so the
    client can be back first."""
    deadline = time.monotonic() + 10.0
    while metrics.counters_delta(before).get(name, 0) < want \
            and time.monotonic() < deadline:
        time.sleep(0.005)
    return metrics.counters_delta(before).get(name, 0)


def raw_exchange(port, request: bytes) -> bytes:
    """Everything the server sends until IT closes the connection."""
    with socket.create_connection(("127.0.0.1", port), timeout=30) as s:
        s.sendall(request)
        got = b""
        while chunk := s.recv(65536):
            got += chunk
        return got


def test_requests_share_one_connection(conn):
    before = metrics.counters_snapshot()
    ports = set()
    for _ in range(20):
        r, raw = ask(conn)
        assert (r.status, r.version, r.will_close) == (200, 11, False)
        assert json.loads(raw)["data"] == ANSWER
        ports.add(conn.sock.getsockname()[1])
    assert len(ports) == 1
    assert moved(before, REQS, 20) == 20
    assert metrics.counters_delta(before)[CONNS] == 1


@pytest.mark.parametrize("request_line, extra", [
    ("POST /query HTTP/1.1", "Connection: close\r\n"),
    ("POST /query HTTP/1.0", ""),
], ids=["connection-close", "http-1.0"])
def test_closes_when_the_request_says_so(port, request_line, extra):
    got = raw_exchange(port, (
        f"{request_line}\r\nHost: x\r\nContent-Type: application/dql\r\n"
        f"{extra}Content-Length: {len(QUERY)}\r\n\r\n").encode() + QUERY)
    head, _, body = got.partition(b"\r\n\r\n")
    assert head.startswith(b"HTTP/1.1 200 ")
    assert b"\r\nConnection: close" in head
    assert f"\r\nContent-Length: {len(body)}\r\n".encode() in head + b"\r\n"
    assert json.loads(body)["data"] == ANSWER


@pytest.mark.parametrize("path, headers, status", [
    ("/query", {**DQL, "X-Dgraph-Deadline-Ms": "soon"}, 400),
    ("/mutate", {**DQL, "X-Dgraph-Deadline-Ms": "1.5"}, 400),
    ("/query", {**DQL, "Transfer-Encoding": "gzip"}, 400),
], ids=["malformed-deadline", "malformed-deadline-mutate",
        "transfer-encoding"])
def test_reply_before_the_body_was_read_closes(conn, path, headers,
                                               status):
    """The refusal leaves the request's body unread: the server says
    `Connection: close`, so those bytes are never parsed as the next
    request, and the SAME client object gets a correct answer next."""
    r, raw = ask(conn, path=path, headers=headers)
    assert r.status == status and r.will_close
    assert "errors" in json.loads(raw)
    for _ in range(2):
        r, raw = ask(conn)
        assert r.status == 200 and json.loads(raw)["data"] == ANSWER


def test_get_with_a_body_closes(conn):
    r, raw = ask(conn, method="GET", path="/health", headers={})
    assert r.status == 200 and r.will_close
    assert json.loads(raw)["status"] == "healthy"
    r, raw = ask(conn)
    assert r.status == 200 and json.loads(raw)["data"] == ANSWER


@pytest.mark.parametrize("length", ["-5", "many"])
def test_unusable_content_length_is_refused_and_closes(port, length):
    got = raw_exchange(port, (
        "POST /query HTTP/1.1\r\nHost: x\r\n"
        f"Content-Length: {length}\r\n\r\n").encode() + QUERY)
    head, _, body = got.partition(b"\r\n\r\n")
    assert head.startswith(b"HTTP/1.1 400 ")
    assert b"\r\nConnection: close" in head
    # ONE reply: the body's bytes were not taken for a second request
    assert got.count(b"HTTP/1.1 ") == 1 and "errors" in json.loads(body)


def test_error_after_the_body_was_read_keeps_the_connection(conn):
    before = metrics.counters_snapshot()
    r, _ = ask(conn)
    local = conn.sock.getsockname()
    for path, body, status in (("/query", b"{ q(func: nope", 400),
                               ("/no/such/route", QUERY, 404),
                               ("/query", QUERY, 200)):
        r, raw = ask(conn, path=path, body=body)
        assert (r.status, r.will_close) == (status, False)
        assert conn.sock.getsockname() == local
    assert json.loads(raw)["data"] == ANSWER
    assert metrics.counters_delta(before)[CONNS] == 1


def test_expect_100_continue(port):
    with socket.create_connection(("127.0.0.1", port), timeout=30) as s:
        s.sendall(b"POST /query HTTP/1.1\r\nHost: x\r\n"
                  b"Content-Type: application/dql\r\n"
                  b"Expect: 100-continue\r\nConnection: close\r\n"
                  + f"Content-Length: {len(QUERY)}\r\n\r\n".encode())
        assert s.recv(65536).startswith(b"HTTP/1.1 100 Continue\r\n")
        s.sendall(QUERY)
        got = b""
        while chunk := s.recv(65536):
            got += chunk
    assert got.startswith(b"HTTP/1.1 200 ")
    assert json.loads(got.partition(b"\r\n\r\n")[2])["data"] == ANSWER


def test_a_reply_is_one_send_on_a_nodelay_socket(conn, monkeypatch):
    sends, nodelay = [], []

    class Counted:
        def __init__(self, wfile):
            self._wfile = wfile

        def write(self, data):
            sends.append(bytes(data))
            return self._wfile.write(data)

        def __getattr__(self, name):
            return getattr(self._wfile, name)

    setup = server_http._Handler.setup

    def counting_setup(self):
        setup(self)
        nodelay.append(self.connection.getsockopt(
            socket.IPPROTO_TCP, socket.TCP_NODELAY))
        self.wfile = Counted(self.wfile)

    monkeypatch.setattr(server_http._Handler, "setup", counting_setup)
    for method, path, body, status in (
            ("POST", "/query", QUERY, 200),              # raw bytes
            ("POST", "/query?debug=true", QUERY, 200),   # an object
            ("GET", "/health", None, 200),
            ("GET", "/debug/prometheus_metrics", None, 200),
            ("POST", "/query", b"{ q(func: nope", 400),
            ("GET", "/no/such/route", None, 404)):
        del sends[:]
        r, raw = ask(conn, method=method, path=path, body=body)
        assert r.status == status and not r.will_close
        assert len(sends) == 1, (path, [s[:40] for s in sends])
        head, _, sent_body = sends[0].partition(b"\r\n\r\n")
        assert sent_body == raw
        assert f"\r\nContent-Length: {len(raw)}".encode() in head
    assert nodelay and all(nodelay)


def test_eight_clients_eight_connections(port):
    before = metrics.counters_snapshot()
    good = [0] * 8

    def client(i):
        c = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        try:
            for _ in range(200):
                r, raw = ask(c)
                good[i] += r.status == 200 \
                    and json.loads(raw)["data"] == ANSWER
        finally:
            c.close()

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    assert good == [200] * 8
    assert moved(before, REQS, 1600) == 1600
    assert metrics.counters_delta(before)[CONNS] == 8


def test_an_idle_connection_ends(port, monkeypatch):
    monkeypatch.setattr(server_http._Handler, "timeout", 0.2)
    with socket.create_connection(("127.0.0.1", port), timeout=30) as s:
        s.sendall(b"POST /query HTTP/1.1\r\nHost: x\r\n"
                  b"Content-Type: application/dql\r\n"
                  + f"Content-Length: {len(QUERY)}\r\n\r\n".encode()
                  + QUERY)
        got = b""
        while chunk := s.recv(65536):  # b"": the server hung up
            got += chunk
    assert got.count(b"HTTP/1.1 200 ") == 1
    # and one that never says a word does not keep its thread either
    with socket.create_connection(("127.0.0.1", port), timeout=30) as s:
        assert s.recv(65536) == b""


def test_shutdown_returns_with_an_idle_connection_open():
    httpd, _ = server_http.serve(GraphDB(prefer_device=False), port=0,
                                 block=False)
    assert httpd.request_queue_size == 128
    c = http.client.HTTPConnection(
        "127.0.0.1", httpd.server_address[1], timeout=30)
    try:
        r, _ = ask(c, method="GET", path="/health", body=None, headers={})
        assert r.status == 200 and not r.will_close

        def stop():
            httpd.shutdown()
            httpd.server_close()

        t = threading.Thread(target=stop)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
        assert c.sock is not None  # the idle connection was still ours
    finally:
        c.close()
