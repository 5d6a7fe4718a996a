"""Protocol-level tests for the native epoll HTTP front-end.

Covers the transport behaviors the /v1 routing tests (test_server_e2e.py
TestRest, which runs against both backends) can't see: keep-alive and
pipelining, chunked request bodies, header/body limits, idle timeouts,
concurrency, and handler-failure fallbacks — the territory of the
reference's net_http tests (util/net_http/server/internal/evhttp_server
tests).
"""

from __future__ import annotations

import gzip
import json
import socket
import threading
import time
import urllib.request

import pytest

from min_tfs_client_tpu.server.native_http import (
    NativeRestServer,
    native_http_available,
)

pytestmark = pytest.mark.skipif(
    not native_http_available(), reason="native HTTP library not buildable")


def echo_route(handlers, prom, method, path, body, trace_id=""):
    payload = json.dumps({
        "method": method, "path": path, "len": len(body),
        "body": body.decode("latin1"),
    }).encode()
    return 200, "application/json", payload


@pytest.fixture()
def server():
    srv = NativeRestServer(None, 0, route_fn=echo_route, timeout_ms=2000)
    yield srv
    srv.shutdown()


def _recv_n_responses(sock: socket.socket, n: int, timeout=10.0) -> bytes:
    """Read until `n` complete Content-Length-framed responses arrived."""
    sock.settimeout(timeout)
    data = b""
    while data.count(b"HTTP/1.1 ") < n or not _all_complete(data, n):
        chunk = sock.recv(65536)
        if not chunk:
            break
        data += chunk
    return data


def _all_complete(data: bytes, n: int) -> bool:
    seen = 0
    rest = data
    while rest:
        head_end = rest.find(b"\r\n\r\n")
        if head_end < 0:
            return False
        head = rest[:head_end].decode("latin1")
        clen = 0
        for line in head.split("\r\n"):
            if line.lower().startswith("content-length:"):
                clen = int(line.split(":")[1])
        total = head_end + 4 + clen
        if len(rest) < total:
            return False
        seen += 1
        rest = rest[total:]
    return seen >= n


def test_ephemeral_port_assigned(server):
    assert server.port > 0


def test_keep_alive_sequential_requests(server):
    s = socket.create_connection(("127.0.0.1", server.port))
    for i in range(3):
        s.sendall(f"GET /r{i} HTTP/1.1\r\nHost: x\r\n\r\n".encode())
        resp = _recv_n_responses(s, 1)
        assert f"/r{i}".encode() in resp
        assert b"Connection: keep-alive" in resp
    s.close()


def test_pipelined_requests_answered_in_order(server):
    s = socket.create_connection(("127.0.0.1", server.port))
    s.sendall(b"GET /first HTTP/1.1\r\nHost: x\r\n\r\n"
              b"GET /second HTTP/1.1\r\nHost: x\r\n\r\n"
              b"GET /third HTTP/1.1\r\nHost: x\r\nConnection: close\r\n\r\n")
    data = _recv_n_responses(s, 3)
    assert data.index(b"/first") < data.index(b"/second") < data.index(
        b"/third")
    s.close()


def test_chunked_request_body(server):
    s = socket.create_connection(("127.0.0.1", server.port))
    s.sendall(b"POST /c HTTP/1.1\r\nHost: x\r\n"
              b"Transfer-Encoding: chunked\r\n\r\n"
              b"5\r\nhello\r\n6\r\n world\r\n0\r\n\r\n")
    resp = _recv_n_responses(s, 1)
    assert b'"len": 11' in resp
    assert b"hello world" in resp
    s.close()


def test_chunked_size_near_uint64_max_rejected_413(server):
    # A hex chunk size near 2^64 must be rejected outright: summing it
    # into body.size() first would wrap past the 256MB cap and let the
    # client stream unbounded data (remote memory-exhaustion DoS).
    s = socket.create_connection(("127.0.0.1", server.port))
    s.sendall(b"POST /c HTTP/1.1\r\nHost: x\r\n"
              b"Transfer-Encoding: chunked\r\n\r\n"
              b"1\r\na\r\nFFFFFFFFFFFFFFF0\r\n")
    resp = _recv_n_responses(s, 1)
    assert b"413" in resp.split(b"\r\n", 1)[0]
    s.close()


def test_chunked_with_extensions_and_trailers(server):
    s = socket.create_connection(("127.0.0.1", server.port))
    s.sendall(b"POST /c HTTP/1.1\r\nHost: x\r\n"
              b"Transfer-Encoding: chunked\r\n\r\n"
              b"4;ext=1\r\nabcd\r\n0\r\nX-Trailer: t\r\n\r\n")
    resp = _recv_n_responses(s, 1)
    assert b'"len": 4' in resp
    s.close()


def test_gzip_request_inflated_before_handler(server):
    body = gzip.compress(b"payload-bytes")
    req = urllib.request.Request(
        f"http://127.0.0.1:{server.port}/z", data=body,
        headers={"Content-Encoding": "gzip"})
    with urllib.request.urlopen(req, timeout=10) as r:
        reply = json.load(r)
    assert reply["len"] == len(b"payload-bytes")
    assert reply["body"] == "payload-bytes"


def test_corrupt_gzip_request_is_400(server):
    req = urllib.request.Request(
        f"http://127.0.0.1:{server.port}/z", data=b"not gzip",
        headers={"Content-Encoding": "gzip"})
    with pytest.raises(urllib.error.HTTPError) as err:
        urllib.request.urlopen(req, timeout=10)
    assert err.value.code == 400


def test_large_response_gzipped_when_accepted():
    def big_route(handlers, prom, method, path, body, trace_id=""):
        return 200, "text/plain", b"A" * 50000

    srv = NativeRestServer(None, 0, route_fn=big_route)
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/big",
            headers={"Accept-Encoding": "gzip"})
        with urllib.request.urlopen(req, timeout=10) as r:
            assert r.headers.get("Content-Encoding") == "gzip"
            assert gzip.decompress(r.read()) == b"A" * 50000
        # Without Accept-Encoding the body must come back verbatim.
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/big", timeout=10) as r:
            assert r.headers.get("Content-Encoding") is None
            assert r.read() == b"A" * 50000
    finally:
        srv.shutdown()


def test_oversized_header_block_rejected(server):
    s = socket.create_connection(("127.0.0.1", server.port))
    s.sendall(b"GET / HTTP/1.1\r\nHost: x\r\n")
    s.sendall(b"X-Junk: " + b"j" * (70 * 1024) + b"\r\n\r\n")
    resp = _recv_n_responses(s, 1)
    assert b"431" in resp.split(b"\r\n", 1)[0]
    s.close()


def test_malformed_request_line_rejected(server):
    s = socket.create_connection(("127.0.0.1", server.port))
    s.sendall(b"NONSENSE\r\n\r\n")
    resp = _recv_n_responses(s, 1)
    assert b"400" in resp.split(b"\r\n", 1)[0]
    s.close()


def test_doomed_connection_force_closed_by_sweep():
    """A client that provokes a protocol error and never reads the reply
    must be force-closed by the idle sweep — one error response, then EOF
    (no repeated 408s, no fd leak)."""
    srv = NativeRestServer(None, 0, route_fn=echo_route, timeout_ms=300)
    try:
        s = socket.create_connection(("127.0.0.1", srv.port))
        s.sendall(b"NONSENSE\r\n\r\n")  # malformed -> 400 + close_after
        time.sleep(3.5)  # > several 1s sweep periods
        s.settimeout(10)
        data = b""
        while True:
            chunk = s.recv(65536)
            if not chunk:
                break
            data += chunk
        assert data.count(b"HTTP/1.1 ") == 1  # exactly one error response
        assert b"400" in data.split(b"\r\n", 1)[0]
        s.close()
    finally:
        srv.shutdown()


def test_idle_connection_swept():
    srv = NativeRestServer(None, 0, route_fn=echo_route, timeout_ms=300)
    try:
        s = socket.create_connection(("127.0.0.1", srv.port))
        s.settimeout(10)
        # No bytes sent: the sweeper should close the socket (EOF).
        assert s.recv(1) == b""
        s.close()
    finally:
        srv.shutdown()


def test_http_1_0_closes_by_default(server):
    s = socket.create_connection(("127.0.0.1", server.port))
    s.sendall(b"GET /old HTTP/1.0\r\nHost: x\r\n\r\n")
    data = _recv_n_responses(s, 1)
    assert b"Connection: close" in data
    # Server closes after responding.
    s.settimeout(10)
    assert s.recv(1) == b""
    s.close()


def test_handler_exception_becomes_500():
    def bad_route(handlers, prom, method, path, body, trace_id=""):
        raise RuntimeError("boom inside the router")

    srv = NativeRestServer(None, 0, route_fn=bad_route)
    try:
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/x", timeout=10)
        assert err.value.code == 500
        assert "boom" in json.load(err.value)["error"]
    finally:
        srv.shutdown()


def test_concurrent_requests_across_connections(server):
    results = []
    lock = threading.Lock()

    def one(i):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/t{i}", timeout=15) as r:
            body = json.load(r)
        with lock:
            results.append(body["path"])

    threads = [threading.Thread(target=one, args=(i,)) for i in range(32)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert sorted(results) == sorted(f"/t{i}" for i in range(32))


def test_shutdown_unbinds_port():
    srv = NativeRestServer(None, 0, route_fn=echo_route)
    port = srv.port
    srv.shutdown()
    # A fresh server can bind the same port immediately (SO_REUSEADDR and
    # the listener actually closed).
    srv2 = NativeRestServer(None, port, route_fn=echo_route)
    assert srv2.port == port
    srv2.shutdown()
