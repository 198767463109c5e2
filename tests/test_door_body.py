"""The door's body read path: one copy per body, from the socket into a
buffer the request owns, and a decode that views that buffer.

Three layers:

  * the connection protocol driven by hand (no socket): every buffer it
    hands the transport during a body is a view of the body's own array,
    and only the bytes that came in the same read as the head are copied;
  * a live ``FrontDoor`` over raw sockets (``frontend`` marker, no jax
    compile): bodies in any piece size, pipelining, a client that leaves
    mid-body, the 413 before any allocation, JSON-base64, and the
    ``direct_bodies`` counter;
  * the router's forward of a door-read body (``ProcWorker.infer`` on a
    pooled connection), which takes the new buffer type untranscoded.
"""
import asyncio
import concurrent.futures
import json
import socket
import tracemalloc

import numpy as np
import pytest

from repro.frontend import (FrontDoor, LocalBackend, ProcWorker,
                            ServerThread, wire)
from repro.frontend.app import HEAD_READ, _Connection
from repro.serving.metrics import ServerMetrics


class _Server:
    """A ``HeteroServer`` stand-in that keeps every array it was given and
    answers its first four values at once."""

    def __init__(self):
        self.state = "running"
        self.metrics = ServerMetrics()
        self.seen = []

    def submit(self, name, x, *, priority=1, deadline_ms=None,
               request_id=None):
        if name != "tiny":
            raise KeyError(f"unknown network {name!r}")
        self.seen.append(x)
        fut = concurrent.futures.Future()
        fut.set_result(np.asarray(x, np.float32).reshape(-1)[:4].copy())
        return fut

    def shutdown(self, budget_s):
        self.state = "closed"


class _Backend(LocalBackend):
    """``LocalBackend`` that keeps each payload the door handed it."""

    def __init__(self, server):
        super().__init__(server)
        self.payloads = []

    async def infer(self, payload):
        self.payloads.append(payload)
        return await super().infer(payload)


@pytest.fixture
def door():
    server = _Server()
    h = ServerThread(FrontDoor(_Backend(server))).start()
    try:
        yield server, h
    finally:
        h.stop(drain=False)


def _image(shape=(4, 5, 3), seed=0):
    return np.random.default_rng(seed).standard_normal(shape).astype(
        np.float32)


def _binary(x) -> tuple[bytes, bytes]:
    """(head, body) of one binary keep-alive ``POST /v1/infer`` asking for
    a binary answer."""
    body, headers = wire.infer_request("tiny", x, binary=True,
                                       accept=wire.TENSOR_CONTENT_TYPE)
    lines = "".join(f"{k}: {v}\r\n" for k, v in headers.items())
    head = (f"POST /v1/infer HTTP/1.1\r\nHost: x\r\n"
            f"Content-Length: {len(body)}\r\n{lines}\r\n").encode()
    return head, body


def _responses(s: socket.socket, n: int) -> list[tuple[int, bytes]]:
    """(status, body) of the next ``n`` responses on a raw socket."""
    buf = b""
    out = []
    while len(out) < n:
        while b"\r\n\r\n" not in buf:
            chunk = s.recv(65536)
            assert chunk, f"socket closed after {len(out)} answers"
            buf += chunk
        head, buf = buf.split(b"\r\n\r\n", 1)
        status = int(head.split(b" ", 2)[1])
        length = 0
        for line in head.split(b"\r\n")[1:]:
            k, _, v = line.partition(b":")
            if k.strip().lower() == b"content-length":
                length = int(v)
        while len(buf) < length:
            chunk = s.recv(65536)
            assert chunk, "socket closed mid-answer"
            buf += chunk
        out.append((status, buf[:length]))
        buf = buf[length:]
    return out


def _connect(port) -> socket.socket:
    s = socket.create_connection(("127.0.0.1", port), timeout=30)
    s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    return s


def _assert_viewed(server, backend, xs):
    """Each submitted array is bit-identical to its image and to the
    decode of a ``bytes`` copy of the frame, is writable, and views the
    body buffer the door read."""
    assert len(server.seen) == len(backend.payloads) == len(xs)
    for x, got, payload in zip(xs, server.seen, backend.payloads):
        old = wire.decode_tensor(bytes(payload["_tensor"]))
        assert got.tobytes() == old.tobytes() == x.tobytes()
        assert got.dtype == old.dtype == x.dtype
        assert got.shape == old.shape == x.shape
        assert got.flags.writeable
        assert np.shares_memory(got, np.asarray(payload["_tensor"]))
        assert not np.shares_memory(old, np.asarray(payload["_tensor"]))


# --- the protocol by hand ---------------------------------------------------

class _Transport:
    def __init__(self):
        self.paused = False

    def pause_reading(self):
        self.paused = True

    def resume_reading(self):
        self.paused = False


def _deliver(conn, data: bytes, piece: int) -> list[memoryview]:
    """Hand ``data`` to ``conn`` as the selector transport does: ask for a
    buffer, receive into it, report the count.  The buffers handed out."""
    views = []
    while data:
        buf = conn.get_buffer(-1)
        n = min(len(buf), piece, len(data))
        buf[:n] = data[:n]
        views.append(buf)
        conn.buffer_updated(n)
        data = data[n:]
    return views


@pytest.mark.parametrize("piece", [1, 1000, 65536])
def test_socket_writes_the_body_into_its_own_buffer(piece):
    x = _image((32, 32, 3), seed=piece)
    head, body = _binary(x)
    early = 100                       # body bytes in the head's read

    async def go():
        conn = _Connection(door=None)
        conn.transport = _Transport()
        first = conn.get_buffer(-1)
        assert len(first) <= HEAD_READ   # a read between bodies is small
        _deliver(conn, head + body[:early], len(first))
        parsed = await wire.read_head(conn)
        assert parsed[0] == "POST" and parsed[1] == "/v1/infer"
        task = asyncio.ensure_future(conn.read_body(len(body)))
        await asyncio.sleep(0)        # the body's buffer is handed out
        views = _deliver(conn, body[early:], piece)
        got = await task
        return got, views

    got, views = asyncio.run(go())
    assert got.dtype == np.uint8 and got.tobytes() == body
    # after the head's read, the socket writes nowhere but the body
    assert views and all(np.shares_memory(np.asarray(v), got)
                         for v in views)
    assert sum(len(v) for v in views) >= len(body) - early
    x_back = wire.decode_tensor(memoryview(got))
    assert np.shares_memory(x_back, got) and x_back.flags.writeable
    assert x_back.tobytes() == x.tobytes()


def test_a_full_head_buffer_pauses_reading_until_consumed():
    async def go():
        conn = _Connection(door=None)
        conn.transport = _Transport()
        line = b"X-Pad: " + b"a" * 120 + b"\r\n"
        while not conn.transport.paused:
            buf = conn.get_buffer(-1)
            n = min(len(buf), len(line))
            buf[:n] = line[:n]
            conn.buffer_updated(n)
        assert await conn.readline() == line
        assert not conn.transport.paused
        assert len(conn.get_buffer(-1)) > 0

    asyncio.run(go())


# --- a live door ------------------------------------------------------------

@pytest.mark.frontend
@pytest.mark.parametrize("piece", [1, 1000, 65536])
def test_binary_body_in_pieces_is_served_and_viewed(door, piece):
    server, h = door
    x = _image((4, 5, 3) if piece == 1 else (64, 64, 16), seed=piece)
    head, body = _binary(x)
    with _connect(h.port) as s:
        s.sendall(head)
        for i in range(0, len(body), piece):
            s.sendall(body[i:i + piece])
        [(status, raw)] = _responses(s, 1)
    assert status == 200
    assert np.array_equal(wire.decode_tensor(raw), x.reshape(-1)[:4])
    _assert_viewed(server, h.door.backend, [x])
    assert h.door.direct_bodies == h.door.requests == 1


@pytest.mark.frontend
def test_pipelined_pair_in_one_send_and_head_with_body(door):
    """Two whole requests in one ``send`` (each head arrives with its
    body in one segment): both answered, in order, each viewed."""
    server, h = door
    xs = [_image((16, 16, 3), seed=s) for s in (1, 2)]
    wire_bytes = b"".join(b"".join(_binary(x)) for x in xs)
    with _connect(h.port) as s:
        s.sendall(wire_bytes)
        answers = _responses(s, 2)
    for x, (status, raw) in zip(xs, answers):
        assert status == 200
        assert np.array_equal(wire.decode_tensor(raw), x.reshape(-1)[:4])
    _assert_viewed(server, h.door.backend, xs)
    assert h.door.connections == 1


@pytest.mark.frontend
def test_client_leaving_mid_body_gets_no_answer_and_wedges_nothing(door):
    server, h = door
    x = _image((64, 64, 16))
    head, body = _binary(x)
    with _connect(h.port) as s:
        s.sendall(head + body[:len(body) // 2])
        s.shutdown(socket.SHUT_WR)
        s.settimeout(10.0)
        assert s.recv(65536) == b"", "a half-sent request was answered"
    assert server.seen == [] and h.door.direct_bodies == 0
    # the door still serves: a new connection gets its row
    with _connect(h.port) as s:
        s.sendall(b"".join(_binary(x)))
        [(status, raw)] = _responses(s, 1)
    assert status == 200
    assert np.array_equal(wire.decode_tensor(raw), x.reshape(-1)[:4])
    assert h.door.direct_bodies == 1


@pytest.mark.frontend
def test_oversize_content_length_is_413_before_any_allocation(door):
    server, h = door
    tracemalloc.start()
    try:
        tracemalloc.reset_peak()
        with _connect(h.port) as s:
            s.sendall((f"POST /v1/infer HTTP/1.1\r\nHost: x\r\n"
                       f"Content-Type: {wire.TENSOR_CONTENT_TYPE}\r\n"
                       f"X-Network: tiny\r\n"
                       f"Content-Length: {wire.MAX_BODY_BYTES + 1}\r\n"
                       f"\r\n").encode())
            [(status, raw)] = _responses(s, 1)
            s.settimeout(10.0)
            assert s.recv(65536) == b"", "413 must close the socket"
        _cur, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert status == 413 and json.loads(raw)["error"] == "payload_too_large"
    assert peak < wire.MAX_BODY_BYTES // 16, f"{peak} bytes allocated"
    assert h.door.direct_bodies == 0 and server.seen == []


@pytest.mark.frontend
def test_json_base64_body_still_round_trips(door):
    server, h = door
    x = _image((8, 8, 4), seed=3)
    body, headers = wire.infer_request("tiny", x)
    lines = "".join(f"{k}: {v}\r\n" for k, v in headers.items())
    with _connect(h.port) as s:
        s.sendall((f"POST /v1/infer HTTP/1.1\r\nHost: x\r\n"
                   f"Content-Length: {len(body)}\r\n{lines}\r\n").encode()
                  + body)
        [(status, raw)] = _responses(s, 1)
    assert status == 200
    row = wire.decode_array(json.loads(raw)["result"])
    assert np.array_equal(row, x.reshape(-1)[:4])
    assert server.seen[0].tobytes() == x.tobytes()
    assert h.door.direct_bodies == 1


@pytest.mark.frontend
def test_direct_bodies_counts_every_binary_request(door):
    server, h = door
    xs = [_image((32, 32, 3), seed=s) for s in range(5)]
    with _connect(h.port) as s:
        for x in xs:
            s.sendall(b"".join(_binary(x)))
            [(status, _raw)] = _responses(s, 1)
            assert status == 200
        s.sendall(b"GET /healthz HTTP/1.1\r\nHost: x\r\n\r\n")
        [(status, _raw)] = _responses(s, 1)
        assert status == 200
    assert h.door.requests == len(xs) + 1
    assert h.door.direct_bodies == len(xs)   # the GET has no body
    _assert_viewed(server, h.door.backend, xs)


# --- the router's forward ---------------------------------------------------

@pytest.mark.frontend
def test_router_forwards_a_door_read_body_untranscoded(door):
    """``ProcWorker.infer`` sends the door's body buffer (a ``memoryview``
    of the request's own array) as the frame it is."""
    server, h = door
    x = _image((8, 8, 3), seed=4)
    frame = wire.encode_tensor(x)
    body = np.frombuffer(frame, np.uint8).copy()
    w = ProcWorker("w", {})
    w.pool = wire.HttpPool("127.0.0.1", h.port)

    async def go():
        try:
            return await w.infer({"network": "tiny",
                                  "_tensor": memoryview(body),
                                  "_accept": wire.TENSOR_CONTENT_TYPE})
        finally:
            w.pool.close()

    status, raw, headers = asyncio.run(go())
    assert status == 200
    assert headers["content-type"].startswith(wire.TENSOR_CONTENT_TYPE)
    assert np.array_equal(wire.decode_tensor(raw), x.reshape(-1)[:4])
    assert server.seen[0].tobytes() == x.tobytes()
