"""C100K wire plane — event-loop front end core (ISSUE 19).

The load-bearing gates:

- **Incremental parser**: ``frontend/http1.RequestParser`` driven
  byte-at-a-time — slow-loris request lines, split headers, and
  truncated bodies park the CONNECTION (``None``), never mis-frame the
  next keep-alive request, and malformed heads poison the parser with
  the right status (400/431/505).
- **Slow-loris robustness on the wire**: a byte-dribbled request on
  one socket must not block service for other clients — asserted
  against BOTH cores (``core="eventloop"`` and ``core="threaded"``),
  since the threaded core is the transition fallback.
- **Reaper + cap**: past ``frontend_max_connections`` new accepts are
  refused cheaply (counted), idle sockets are closed after
  ``frontend_idle_timeout_s`` (counted), and an idle flood below the
  cap never starves active requests.
- **SO_REUSEPORT sharding**: multi-loop (``shards=2``) and
  multi-server (``reuse_port=True`` on a shared port) fan-in both
  serve every request; gracefully skipped where the platform lacks
  ``SO_REUSEPORT``.

Everything here runs tiny models and sub-second timeouts.
"""

import http.client
import json
import socket
import threading
import time

import numpy as np
import pytest

from bigdl_tpu import nn
from bigdl_tpu.frontend import FrontendServer
from bigdl_tpu.frontend.http1 import (CHUNK_TRAILER, ProtocolError,
                                      RequestParser, encode_chunk,
                                      render_head)
from bigdl_tpu.serving import ModelRegistry


def make_model(din=16, dout=4):
    return nn.Sequential(nn.Linear(din, 32), nn.ReLU(),
                         nn.Linear(32, dout), nn.SoftMax()).initialize(0)


SPEC16 = ((16,), np.float32)


def post(port, path, body, headers=None, timeout=60):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", path, body=body,
                     headers={"Content-Type": "application/json",
                              **(headers or {})})
        resp = conn.getresponse()
        return resp.status, dict(resp.getheaders()), resp.read()
    finally:
        conn.close()


def wait_until(pred, timeout=10.0, what="condition"):
    deadline = time.monotonic() + timeout
    while not pred():
        if time.monotonic() >= deadline:
            raise AssertionError(f"timed out waiting for {what}")
        time.sleep(0.005)


def req_bytes(path, obj, extra=None, version="HTTP/1.1"):
    """Serialize one POST request for raw-socket tests."""
    body = json.dumps(obj).encode()
    head = (f"POST {path} {version}\r\n"
            "Host: t\r\n"
            "Content-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\n"
            + "".join(f"{k}: {v}\r\n" for k, v in (extra or {}).items())
            + "\r\n")
    return head.encode("latin-1") + body


def read_response(sock, timeout=30.0):
    """Read one Content-Length-framed response off a raw socket."""
    sock.settimeout(timeout)
    buf = b""
    while b"\r\n\r\n" not in buf:
        d = sock.recv(4096)
        if not d:
            raise AssertionError(f"closed mid-head: {buf!r}")
        buf += d
    head, _, rest = buf.partition(b"\r\n\r\n")
    lines = head.decode("latin-1").split("\r\n")
    status = int(lines[0].split()[1])
    hdrs = {}
    for ln in lines[1:]:
        k, _, v = ln.partition(":")
        hdrs[k.strip().lower()] = v.strip()
    n = int(hdrs.get("content-length", 0))
    while len(rest) < n:
        d = sock.recv(4096)
        if not d:
            break
        rest += d
    return status, hdrs, rest[:n], rest[n:]


# ===========================================================================
# incremental parser — pure unit tests, no sockets
# ===========================================================================
class TestHttp1Parser:
    REQ = req_bytes("/v1/models/clf/predict", {"inputs": [[1.0, 2.0]]},
                    extra={"X-Tenant": "acme"})

    def test_byte_dribble_completes_only_on_last_byte(self):
        p = RequestParser()
        for b in self.REQ[:-1]:
            p.feed(bytes([b]))
            assert p.poll() is None
        p.feed(self.REQ[-1:])
        req = p.poll()
        assert req is not None
        assert (req.method, req.target) == ("POST",
                                            "/v1/models/clf/predict")
        assert req.get("x-tenant") == "acme"
        assert json.loads(req.body)["inputs"] == [[1.0, 2.0]]
        assert req.keep_alive  # HTTP/1.1 default

    def test_head_ready_before_body_for_preflight_checks(self):
        body_start = self.REQ.index(b"\r\n\r\n") + 4
        p = RequestParser()
        p.feed(self.REQ[:body_start])
        head = p.head()
        assert head is not None and head.get("content-length")
        assert p.poll() is None  # body still outstanding
        p.feed(self.REQ[body_start:])
        assert p.poll() is not None

    def test_pipelined_requests_never_misframed(self):
        a = req_bytes("/a", {"inputs": [[1.0]]})
        b = req_bytes("/b", {"inputs": [[2.0, 3.0]]})
        p = RequestParser()
        p.feed(a + b)  # one TCP segment, two requests
        ra, rb = p.poll(), p.poll()
        assert ra.target == "/a" and rb.target == "/b"
        assert json.loads(rb.body)["inputs"] == [[2.0, 3.0]]
        assert p.poll() is None and p.buffered() == 0

    def test_stray_crlf_between_keepalive_requests_tolerated(self):
        p = RequestParser()
        p.feed(self.REQ + b"\r\n" + self.REQ)
        assert p.poll() is not None and p.poll() is not None

    def test_malformed_request_line_400_and_poisoned(self):
        p = RequestParser()
        p.feed(b"NOT A VALID LINE AT ALL\r\n\r\n")
        with pytest.raises(ProtocolError) as ei:
            p.poll()
        assert ei.value.status == 400
        with pytest.raises(ProtocolError):  # poisoned: no resync guess
            p.head()

    def test_whitespace_before_colon_refused(self):
        p = RequestParser()
        p.feed(b"GET / HTTP/1.1\r\nHost : t\r\n\r\n")
        with pytest.raises(ProtocolError) as ei:
            p.poll()
        assert ei.value.status == 400

    def test_unsupported_version_505(self):
        p = RequestParser()
        p.feed(b"GET / HTTP/2.0\r\n\r\n")
        with pytest.raises(ProtocolError) as ei:
            p.poll()
        assert ei.value.status == 505

    def test_oversized_head_431_even_without_terminator(self):
        p = RequestParser(max_head=128)
        p.feed(b"GET /" + b"a" * 200)  # no CRLFCRLF ever arrives
        with pytest.raises(ProtocolError) as ei:
            p.head()
        assert ei.value.status == 431

    def test_keep_alive_version_defaults(self):
        def ka(first_line, conn=None):
            p = RequestParser()
            h = f"Connection: {conn}\r\n" if conn else ""
            p.feed(f"{first_line}\r\n{h}\r\n".encode())
            return p.poll().keep_alive
        assert ka("GET / HTTP/1.1") is True
        assert ka("GET / HTTP/1.1", "close") is False
        assert ka("GET / HTTP/1.0") is False
        assert ka("GET / HTTP/1.0", "keep-alive") is True

    def test_obs_fold_continuation_joined(self):
        p = RequestParser()
        p.feed(b"GET / HTTP/1.1\r\nX-Long: part one\r\n  part two\r\n\r\n")
        assert p.poll().get("x-long") == "part one part two"

    def test_bogus_content_length_frames_zero_body(self):
        # framing survives; the 400 taxonomy is the exchange layer's job
        p = RequestParser()
        p.feed(b"POST / HTTP/1.1\r\nContent-Length: nope\r\n\r\n")
        req = p.poll()
        assert req is not None and req.body == b""

    def test_render_head_single_framing_mode(self):
        h = render_head(200, {"A": "b"}, content_length=3)
        assert b"Content-Length: 3\r\n" in h
        assert b"Transfer-Encoding" not in h
        h = render_head(200, chunked=True, close=True)
        assert b"Transfer-Encoding: chunked\r\n" in h
        assert b"Content-Length" not in h
        assert b"Connection: close\r\n" in h

    def test_chunk_encoding_roundtrip(self):
        assert encode_chunk(b"") == b""  # empty must not terminate
        assert encode_chunk(b"abc") == b"3\r\nabc\r\n"
        assert CHUNK_TRAILER == b"0\r\n\r\n"


# ===========================================================================
# slow-loris / partial-parse robustness — both cores
# ===========================================================================
@pytest.fixture(scope="module")
def stack():
    model = make_model()
    reg = ModelRegistry()
    svc = reg.deploy("clf", model, input_spec=SPEC16, max_batch_size=8,
                     batch_timeout_ms=2.0, queue_capacity=256)
    yield reg, svc, model
    reg.stop_all()


@pytest.fixture(scope="module", params=["eventloop", "threaded"])
def wire(request, stack):
    reg, svc, model = stack
    fe = FrontendServer(reg, port=0, core=request.param)
    fe.start()
    yield fe, svc, model
    fe.stop()


class TestSlowLorisBothCores:
    def _sock(self, fe):
        return socket.create_connection(("127.0.0.1", fe.port),
                                        timeout=30)

    def test_dribbled_request_line_does_not_block_other_clients(
            self, wire):
        fe, svc, model = wire
        raw = req_bytes("/v1/models/clf/predict",
                        {"inputs": rows(np.random.default_rng(1),
                                        1).tolist()})
        s = self._sock(fe)
        try:
            # park a half-open request line on the server ...
            for b in raw[:10]:
                s.sendall(bytes([b]))
            time.sleep(0.05)
            # ... other clients must be completely unaffected
            x = rows(np.random.default_rng(2), 2)
            t0 = time.monotonic()
            status, _, body = post(
                fe.port, "/v1/models/clf/predict",
                json.dumps({"inputs": x.tolist()}).encode())
            assert status == 200 and time.monotonic() - t0 < 10
            ref, _ = model.apply(svc.params, svc.state, x, training=False)
            np.testing.assert_array_equal(
                np.asarray(json.loads(body)["outputs"], np.float32),
                np.asarray(ref))
            # the parked client eventually finishes its dribble and is
            # served normally — parked, not punished
            s.sendall(raw[10:])
            status, _, out, _ = read_response(s)
            assert status == 200 and b"outputs" in out
        finally:
            s.close()

    def test_split_headers_across_segments(self, wire):
        fe, _svc, _model = wire
        raw = req_bytes("/v1/models/clf/predict",
                        {"inputs": rows(np.random.default_rng(3),
                                        1).tolist()})
        cut1 = raw.index(b"Content-Length") + 9  # mid-header-NAME
        cut2 = raw.index(b"\r\n\r\n") + 2  # mid-terminator
        s = self._sock(fe)
        try:
            for part in (raw[:cut1], raw[cut1:cut2], raw[cut2:]):
                s.sendall(part)
                time.sleep(0.05)
            status, _, out, _ = read_response(s)
            assert status == 200 and b"outputs" in out
        finally:
            s.close()

    def test_truncated_body_disconnect_leaves_server_healthy(
            self, wire):
        fe, _svc, _model = wire
        head = (b"POST /v1/models/clf/predict HTTP/1.1\r\n"
                b"Host: t\r\nContent-Type: application/json\r\n"
                b"Content-Length: 500\r\n\r\n")
        before = fe.metrics.counter("frontend/responses_5xx").value
        s = self._sock(fe)
        s.sendall(head + b'{"inputs": [[')  # 487 bytes never arrive
        time.sleep(0.05)
        s.close()
        x = rows(np.random.default_rng(4), 1)
        status, _, _body = post(fe.port, "/v1/models/clf/predict",
                                json.dumps({"inputs": x.tolist()}).encode())
        assert status == 200
        assert fe.metrics.counter("frontend/responses_5xx").value == before

    def test_keep_alive_pipelined_requests_both_served_in_order(
            self, wire):
        fe, svc, model = wire
        xa = rows(np.random.default_rng(5), 1)
        xb = rows(np.random.default_rng(6), 2)
        raw = (req_bytes("/v1/models/clf/predict",
                         {"inputs": xa.tolist()})
               + req_bytes("/v1/models/clf/predict",
                           {"inputs": xb.tolist()}))
        s = self._sock(fe)
        try:
            s.sendall(raw)  # both requests in one write
            sa, _, outa, extra = read_response(s)
            # hand any read-ahead bytes back for the second response
            sb, _, outb, _ = read_response(_Rewound(s, extra))
            assert sa == 200 and sb == 200
            # the back-to-back pair may coalesce into one dispatch —
            # allclose, not bitwise (GEMM shape differs from batch-1)
            ref_a, _ = model.apply(svc.params, svc.state, xa,
                                   training=False)
            ref_b, _ = model.apply(svc.params, svc.state, xb,
                                   training=False)
            np.testing.assert_allclose(
                np.asarray(json.loads(outa)["outputs"], np.float32),
                np.asarray(ref_a), rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(
                np.asarray(json.loads(outb)["outputs"], np.float32),
                np.asarray(ref_b), rtol=1e-5, atol=1e-6)
        finally:
            s.close()


class _Rewound:
    """Socket wrapper replaying read-ahead bytes before real recvs."""

    def __init__(self, sock, leftover):
        self._sock = sock
        self._pending = leftover

    def settimeout(self, t):
        self._sock.settimeout(t)

    def recv(self, n):
        if self._pending:
            out, self._pending = self._pending[:n], self._pending[n:]
            return out
        return self._sock.recv(n)


def rows(rng, n, din=16):
    return rng.normal(0, 1, (n, din)).astype(np.float32)


# ===========================================================================
# idle reaper + hard connection cap — both cores
# ===========================================================================
@pytest.fixture(scope="class", params=["eventloop", "threaded"])
def capped(request, stack):
    reg, svc, model = stack
    fe = FrontendServer(reg, port=0, core=request.param,
                        max_connections=4, idle_timeout_s=0.4)
    fe.start()
    yield fe, svc, model
    fe.stop()


class TestReaperAndCap:
    def test_cap_refuses_cheaply_then_recovers(self, capped):
        fe, _svc, _model = capped
        idles = [socket.create_connection(("127.0.0.1", fe.port),
                                          timeout=30) for _ in range(4)]
        try:
            wait_until(lambda: fe.open_connections == 4,
                       what="4 idle conns admitted")
            refused_before = fe.metrics.counter(
                "frontend/conns_refused").value
            over = socket.create_connection(("127.0.0.1", fe.port),
                                            timeout=30)
            over.settimeout(10)
            try:
                # past the cap: closed before any handler/exchange work
                assert over.recv(1) == b""
            except (ConnectionResetError, ConnectionAbortedError):
                pass
            finally:
                over.close()
            wait_until(lambda: fe.metrics.counter(
                "frontend/conns_refused").value > refused_before,
                what="refusal counted")
            # freeing one slot re-opens the door for active work
            idles.pop().close()
            wait_until(lambda: fe.open_connections <= 3,
                       what="slot released")
            x = rows(np.random.default_rng(7), 1)
            status, _, _b = post(
                fe.port, "/v1/models/clf/predict",
                json.dumps({"inputs": x.tolist()}).encode())
            assert status == 200
        finally:
            for s in idles:
                s.close()

    def test_idle_sockets_reaped_and_do_not_starve_active(self, capped):
        fe, svc, model = capped
        wait_until(lambda: fe.open_connections == 0,
                   what="previous test's conns drained")
        idles = [socket.create_connection(("127.0.0.1", fe.port),
                                          timeout=30) for _ in range(3)]
        try:
            wait_until(lambda: fe.open_connections == 3,
                       what="3 idle conns admitted")
            # active traffic flows with the idle flood parked
            x = rows(np.random.default_rng(8), 2)
            for _ in range(3):
                # the previous post's server-side conn releases
                # asynchronously after the client close — wait for the
                # free slot or the cap (3 idle + 1 draining) refuses us
                wait_until(lambda: fe.open_connections <= 3,
                           what="active slot free under the cap")
                status, _, body = post(
                    fe.port, "/v1/models/clf/predict",
                    json.dumps({"inputs": x.tolist()}).encode())
                assert status == 200
            ref, _ = model.apply(svc.params, svc.state, x, training=False)
            np.testing.assert_array_equal(
                np.asarray(json.loads(body)["outputs"], np.float32),
                np.asarray(ref))
            # past idle_timeout_s the parked sockets are closed on us
            wait_until(lambda: fe.open_connections == 0, timeout=15,
                       what="idle conns reaped")
            for s in idles:
                s.settimeout(10)
                try:
                    assert s.recv(1) == b""
                except (ConnectionResetError, ConnectionAbortedError,
                        socket.timeout):
                    pass
            if fe.core == "eventloop":  # threaded reaps via rfile timeout
                assert fe.metrics.counter(
                    "frontend/conns_reaped").value >= 3
        finally:
            for s in idles:
                s.close()


# ===========================================================================
# SO_REUSEPORT sharding
# ===========================================================================
_HAS_REUSEPORT = hasattr(socket, "SO_REUSEPORT")


class TestSharding:
    def _hammer(self, port, svc, model, n=8):
        x = rows(np.random.default_rng(9), 1)
        ref, _ = model.apply(svc.params, svc.state, x, training=False)
        errs = []

        def one():
            try:
                status, _, body = post(
                    port, "/v1/models/clf/predict",
                    json.dumps({"inputs": x.tolist()}).encode())
                assert status == 200
                # concurrent requests coalesce into shared batches, so
                # GEMM shapes (and rounding) differ from the batch-1
                # reference — fan-in correctness here, bitwise parity
                # is test_frontend.py's single-dispatch gate
                np.testing.assert_allclose(
                    np.asarray(json.loads(body)["outputs"], np.float32),
                    np.asarray(ref), rtol=1e-5, atol=1e-6)
            except Exception as e:  # pragma: no cover - surfaced below
                errs.append(e)

        ts = [threading.Thread(target=one) for _ in range(n)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(60)
        assert not errs, errs

    def test_multi_loop_shards_serve_all(self, stack):
        reg, svc, model = stack
        fe = FrontendServer(reg, port=0, core="eventloop", shards=2)
        fe.start()
        try:
            names = {t.name for t in threading.enumerate()}
            assert "bigdl-tpu-frontend-loop0" in names
            assert "bigdl-tpu-frontend-loop1" in names
            self._hammer(fe.port, svc, model)
        finally:
            fe.stop()
        # both loops joined on stop — no leaked threads
        names = {t.name for t in threading.enumerate()}
        assert "bigdl-tpu-frontend-loop0" not in names
        assert "bigdl-tpu-frontend-loop1" not in names

    @pytest.mark.skipif(not _HAS_REUSEPORT,
                        reason="platform lacks SO_REUSEPORT")
    def test_two_servers_share_one_port(self, stack):
        reg, svc, model = stack
        fe1 = FrontendServer(reg, port=0, core="eventloop",
                             reuse_port=True)
        fe1.start()
        fe2 = None
        try:
            fe2 = FrontendServer(reg, port=fe1.port, core="eventloop",
                                 reuse_port=True)
            fe2.start()
            assert fe2.port == fe1.port
            self._hammer(fe1.port, svc, model)
            # one shard going away must not brown out the port
            fe2.stop()
            fe2 = None
            self._hammer(fe1.port, svc, model, n=4)
        finally:
            if fe2 is not None:
                fe2.stop()
            fe1.stop()


if __name__ == "__main__":
    import sys
    sys.exit(pytest.main([__file__, "-q"]))
