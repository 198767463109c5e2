"""Wire-decode fuzzing: NO body a client can send makes the decoders
raise anything but ``WireDecodeError`` (a typed 400 on the wire) — and
at the door, a volley of malformed requests on ONE keep-alive socket
answers every request with a typed 4xx and leaves the connection sane
(the next well-formed request still gets its row).

Three layers:

  * deterministic corpus tests (tier-1, no server): every malformed
    JSON-base64 body and binary tensor frame in the corpus raises
    ``WireDecodeError``, never ``TypeError``/``struct.error``/
    ``OverflowError``/raw ``ValueError`` from numpy;
  * framing parity (tier-1): binary and base64 framings of the same
    array decode bit-identical, for every allowlisted dtype, including
    big-endian inputs (normalized to little-endian on the wire);
  * door fuzz (``frontend`` marker): the malformed corpus thrown at a
    live ``FrontDoor`` over one persistent connection — zero 500s, all
    typed 4xx, socket survives (the PR-10 acceptance criterion).

A hypothesis suite extends the corpus with generated garbage when
hypothesis is installed (the CI frontend job); the deterministic corpus
keeps the guarantee tested in environments without it.
"""
import base64
import concurrent.futures
import http.client
import json
import socket
import struct

import numpy as np
import pytest

from repro.frontend import FrontDoor, LocalBackend, ServerThread, wire
from repro.serving.metrics import ServerMetrics

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


def _b64(n: int) -> str:
    return base64.b64encode(b"\x00" * n).decode()


def _good() -> dict:
    return {"shape": [2, 3], "dtype": "<f4", "data": _b64(24)}


# every entry must raise WireDecodeError — nothing else
BAD_ARRAY_BODIES = [
    [1, 2, 3],                                     # not an object
    "just a string",
    None,
    {},                                            # missing fields
    {"shape": [2], "dtype": "<f4"},                # no data
    {**_good(), "dtype": "float99"},               # unknown dtype name
    {**_good(), "dtype": "<f9"},
    {**_good(), "dtype": "object"},                # never executable dtypes
    {**_good(), "dtype": "O"},
    {**_good(), "dtype": "|S8"},
    {**_good(), "dtype": "complex64"},             # not in the allowlist
    {**_good(), "dtype": 123},
    {**_good(), "dtype": None},
    {**_good(), "shape": "nope"},                  # non-list shapes
    {**_good(), "shape": 6},
    {**_good(), "shape": {"n": 6}},
    {**_good(), "shape": [2, "3"]},                # non-int dims
    {**_good(), "shape": [2.5, 4]},
    {**_good(), "shape": [True, 6]},               # bool is not a dim
    {**_good(), "shape": [-1, 4]},                 # negative dims
    {**_good(), "shape": [2 ** 31, 2 ** 31]},      # shape overflow
    {**_good(), "shape": [1] * 17},                # ndim bomb
    {**_good(), "data": 123},                      # non-string data
    {**_good(), "data": "!!not-base64!!"},         # invalid base64
    {**_good(), "data": _b64(23)},                 # truncated payload
    {**_good(), "data": _b64(25)},                 # overlong payload
    {"shape": [2, 3], "dtype": "<f4", "data": ""},
]

_H = struct.Struct("<4sBBH")
BAD_TENSOR_FRAMES = [
    b"",                                           # empty
    b"XT0",                                        # truncated magic
    b"NOPE" + b"\x00" * 16,                        # wrong magic
    _H.pack(b"XT01", 200, 1, 0) + struct.pack("<I", 1) + b"\x00" * 4,
    _H.pack(b"XT01", 9, 20, 0) + b"\x00" * 80,     # ndim bomb
    _H.pack(b"XT01", 9, 2, 0) + struct.pack("<I", 2),   # truncated shape
    _H.pack(b"XT01", 9, 1, 0) + struct.pack("<I", 3) + b"\x00" * 8,
    _H.pack(b"XT01", 9, 1, 0) + struct.pack("<I", 3) + b"\x00" * 16,
    _H.pack(b"XT01", 9, 2, 0)                      # u32 dims that overflow
    + struct.pack("<2I", 0xFFFFFFFF, 0xFFFFFFFF),  # the byte-size bound
]


@pytest.mark.parametrize("body", BAD_ARRAY_BODIES,
                         ids=range(len(BAD_ARRAY_BODIES)))
def test_malformed_array_bodies_raise_typed(body):
    with pytest.raises(wire.WireDecodeError):
        wire.decode_array(body)
    status, reply, _h = wire.error_reply(wire.WireDecodeError("x"))
    assert status == 400 and reply["error"] == "bad_request"
    assert reply["retryable"] is False


@pytest.mark.parametrize("frame", BAD_TENSOR_FRAMES,
                         ids=range(len(BAD_TENSOR_FRAMES)))
def test_malformed_tensor_frames_raise_typed(frame):
    with pytest.raises(wire.WireDecodeError):
        wire.decode_tensor(frame)


def test_tensor_frames_reject_non_bytes():
    for bad in ("a string", 123, {"a": 1}, [1, 2], None):
        with pytest.raises(wire.WireDecodeError):
            wire.decode_tensor(bad)


# --- framing parity ---------------------------------------------------------

def test_binary_and_base64_framings_are_bit_identical():
    rng = np.random.RandomState(0)
    for name in wire.WIRE_DTYPES:
        x = (rng.randn(3, 4, 5) * 50).astype(name)
        via_json = wire.decode_array(wire.encode_array(x))
        via_bin = wire.decode_tensor(wire.encode_tensor(x))
        assert via_json.tobytes() == via_bin.tobytes() == x.tobytes(), name
        assert via_json.shape == via_bin.shape == x.shape
        assert via_json.dtype == via_bin.dtype == x.dtype


def test_encode_pins_little_endian_and_decode_byteswaps():
    x = np.arange(6, dtype=np.float32).reshape(2, 3)
    be = x.astype(">f4")
    # a big-endian INPUT array is byteswapped on encode, not emitted raw
    for enc in (wire.encode_array(be), wire.encode_array(x)):
        assert enc["dtype"] == "<f4"
        assert base64.b64decode(enc["data"]) == x.astype("<f4").tobytes()
    # an explicit big-endian wire body decodes byteswapped-to-native
    d = {"shape": [2, 3], "dtype": ">f4", "data":
         base64.b64encode(be.tobytes()).decode()}
    y = wire.decode_array(d)
    assert np.array_equal(y, x) and y.dtype == np.dtype("float32")
    # both framings agree byte-for-byte on the big-endian input too
    assert wire.decode_tensor(wire.encode_tensor(be)).tobytes() \
        == x.astype("<f4").tobytes()


def _tensor_buffers(frame: bytes):
    """The buffer types ``decode_tensor`` takes: ``bytes``, ``bytearray``,
    and a writable and a read-only ``memoryview``."""
    return (frame, bytearray(frame), memoryview(bytearray(frame)),
            memoryview(frame))


def test_decode_tensor_views_writable_buffers_and_copies_bytes():
    """A writable buffer decodes as a view of it; ``bytes`` (and a
    read-only view) to a writable copy; every input to the same bits."""
    rng = np.random.RandomState(1)
    for name in wire.WIRE_DTYPES:
        for shape in ((), (3,), (2, 3, 4)):
            x = np.asarray(rng.randn(*shape) * 50).astype(name)
            frame = wire.encode_tensor(x)
            for buf in _tensor_buffers(frame):
                y = wire.decode_tensor(buf)
                assert y.tobytes() == x.tobytes(), (name, shape, type(buf))
                assert y.dtype == x.dtype and y.shape == x.shape
                assert y.flags.writeable
                # a view where the buffer is writable and the payload
                # aligned for its dtype; a copy otherwise
                payload = np.frombuffer(buf, x.dtype, offset=8 + 4 * x.ndim)
                viewed = not memoryview(buf).readonly and \
                    payload.flags.aligned
                assert np.shares_memory(y, np.frombuffer(buf, np.uint8)) \
                    == viewed, (name, shape, type(buf))
            be = x.astype(x.dtype.newbyteorder(">"))
            z = wire.decode_tensor(bytearray(wire.encode_tensor(be)))
            assert z.dtype.isnative and z.tobytes() == x.tobytes()


def test_zero_size_arrays_cross_both_framings():
    for shape in ((0,), (0, 3), (2, 0, 4)):
        x = np.zeros(shape, dtype=np.float32)
        assert wire.decode_array(wire.encode_array(x)).shape == shape
        assert wire.decode_tensor(wire.encode_tensor(x)).shape == shape


def test_unsupported_dtype_is_rejected_at_encode():
    with pytest.raises(wire.WireDecodeError):
        wire.encode_array(np.zeros(2, dtype=np.complex64))
    with pytest.raises(wire.WireDecodeError):
        wire.encode_tensor(np.array(["a", "b"]))


# --- hypothesis extension (runs where hypothesis is installed) --------------

if HAVE_HYPOTHESIS:
    json_scalars = st.one_of(st.none(), st.booleans(),
                             st.integers(-2 ** 63, 2 ** 63),
                             st.floats(allow_nan=False), st.text(max_size=8))

    @settings(max_examples=200, deadline=None)
    @given(st.dictionaries(
        st.sampled_from(["shape", "dtype", "data", "x"]),
        st.one_of(json_scalars, st.lists(json_scalars, max_size=6))))
    def test_fuzzed_array_bodies_never_escape_typed(d):
        try:
            out = wire.decode_array(d)
        except wire.WireDecodeError:
            return
        assert isinstance(out, np.ndarray)   # only other legal outcome

    @settings(max_examples=200, deadline=None)
    @given(st.binary(max_size=256))
    def test_fuzzed_tensor_frames_never_escape_typed(buf):
        try:
            out = wire.decode_tensor(buf)
        except wire.WireDecodeError:
            return
        assert isinstance(out, np.ndarray)

    @settings(max_examples=100, deadline=None)
    @given(st.sampled_from(wire.WIRE_DTYPES),
           st.lists(st.integers(0, 5), min_size=0, max_size=4),
           st.integers(0, 2 ** 32))
    def test_roundtrip_parity_property(name, shape, seed):
        rng = np.random.RandomState(seed % (2 ** 32))
        # randn() with no dims is a Python float: asarray keeps 0-d arrays
        x = np.asarray(rng.randn(*shape) * 100).astype(name)
        a = wire.decode_array(wire.encode_array(x))
        frame = wire.encode_tensor(x)
        for buf in _tensor_buffers(frame):
            b = wire.decode_tensor(buf)
            assert a.tobytes() == b.tobytes() == x.tobytes()
            assert a.shape == b.shape == x.shape
            assert a.dtype == b.dtype == x.dtype
            assert b.flags.writeable
        big = wire.decode_tensor(wire.encode_tensor(
            x.astype(x.dtype.newbyteorder(">"))))
        assert big.dtype.isnative and big.tobytes() == x.tobytes()


# --- the door under fire (frontend marker: sockets, no jax compile) ---------

class _FakeServer:
    """A ``HeteroServer`` stand-in: real ``ServerMetrics``, instant rows
    — so the door fuzz exercises the REAL ``LocalBackend``/``FrontDoor``
    decode-and-answer path without paying a compile."""

    def __init__(self):
        self.state = "running"
        self.metrics = ServerMetrics()

    def submit(self, name, x, *, priority=1, deadline_ms=None,
               request_id=None):
        if name != "tiny":
            raise KeyError(f"unknown network {name!r}")
        fut = concurrent.futures.Future()
        fut.set_result(np.asarray(x, dtype=np.float32).reshape(-1)[:4]
                       .copy())
        return fut

    def shutdown(self, budget_s):
        self.state = "closed"


def _fuzz_door():
    return ServerThread(FrontDoor(LocalBackend(_FakeServer()))).start()


def _volley_bodies():
    """(body_bytes, headers) for every malformed request in the corpus,
    in both framings."""
    out = []
    for bad in BAD_ARRAY_BODIES:
        out.append((json.dumps({"network": "tiny",
                                **(bad if isinstance(bad, dict) else {}),
                                "_": bad if not isinstance(bad, dict)
                                else None}).encode(),
                    {"Content-Type": "application/json"}))
    out.append((b"this is not json {", {"Content-Type":
                                        "application/json"}))
    out.append((b"[1, 2, 3]", {"Content-Type": "application/json"}))
    for frame in BAD_TENSOR_FRAMES:
        out.append((frame, {"Content-Type": wire.TENSOR_CONTENT_TYPE,
                            "X-Network": "tiny"}))
    # binary frame with no X-Network, and with a junk priority header
    out.append((wire.encode_tensor(np.zeros(4, np.float32)),
                {"Content-Type": wire.TENSOR_CONTENT_TYPE}))
    out.append((wire.encode_tensor(np.zeros(4, np.float32)),
                {"Content-Type": wire.TENSOR_CONTENT_TYPE,
                 "X-Network": "tiny", "X-Deadline-Ms": "soon"}))
    return out


@pytest.mark.frontend
def test_malformed_volley_is_all_typed_4xx_and_socket_survives():
    h = _fuzz_door()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", h.port, timeout=30)
        statuses = []
        for body, headers in _volley_bodies():
            conn.request("POST", "/v1/infer", body=body, headers=headers)
            r = conn.getresponse()
            reply = json.loads(r.read())
            statuses.append(r.status)
            assert 400 <= r.status < 500, (r.status, reply)
            assert reply["retryable"] is False
            assert "Traceback" not in json.dumps(reply)
        assert statuses, "empty volley"
        # the same socket still serves a well-formed request
        x = np.arange(8, dtype=np.float32)
        body, headers = wire.infer_request("tiny", x)
        conn.request("POST", "/v1/infer", body=body, headers=headers)
        r = conn.getresponse()
        assert r.status == 200
        row = wire.decode_array(json.loads(r.read())["result"])
        assert np.array_equal(row, x[:4])
        assert h.door.connections == 1, "a 4xx must not burn the socket"
        conn.close()
    finally:
        h.stop(drain=False)


@pytest.mark.frontend
def test_wrong_content_length_stays_typed():
    """A Content-Length shorter than the body truncates the JSON parse:
    typed 400, and the response still arrives on the raw socket."""
    h = _fuzz_door()
    try:
        payload = json.dumps(wire.infer_payload(
            "tiny", np.zeros(4, np.float32))).encode()
        with socket.create_connection(("127.0.0.1", h.port),
                                      timeout=10) as s:
            head = (f"POST /v1/infer HTTP/1.1\r\nHost: x\r\n"
                    f"Content-Type: application/json\r\n"
                    f"Content-Length: {len(payload) // 2}\r\n"
                    f"Connection: close\r\n\r\n").encode()
            s.sendall(head + payload[:len(payload) // 2])
            reply = b""
            while b"\r\n\r\n" not in reply:
                chunk = s.recv(4096)
                if not chunk:
                    break
                reply += chunk
        assert b" 400 " in reply.split(b"\r\n", 1)[0]
        assert b"bad_request" in reply or b"Content-Length" in reply
    finally:
        h.stop(drain=False)


@pytest.mark.frontend
def test_oversize_content_length_is_413_and_closes():
    h = _fuzz_door()
    try:
        with socket.create_connection(("127.0.0.1", h.port),
                                      timeout=10) as s:
            s.sendall((f"POST /v1/infer HTTP/1.1\r\nHost: x\r\n"
                       f"Content-Length: {wire.MAX_BODY_BYTES + 1}\r\n"
                       f"\r\n").encode())
            reply = s.recv(65536)
            assert b" 413 " in reply.split(b"\r\n", 1)[0]
            assert b"Connection: close" in reply
    finally:
        h.stop(drain=False)


@pytest.mark.frontend
def test_bad_requests_counter_tracks_the_failure_class():
    h = _fuzz_door()
    try:
        bad = json.dumps({"network": "tiny", "shape": [4], "dtype": "<f4",
                          "data": _b64(9)}).encode()
        for _ in range(3):
            conn = http.client.HTTPConnection("127.0.0.1", h.port,
                                              timeout=10)
            conn.request("POST", "/v1/infer", body=bad,
                         headers={"Content-Type": "application/json"})
            assert conn.getresponse().status == 400
            conn.close()
        conn = http.client.HTTPConnection("127.0.0.1", h.port, timeout=10)
        conn.request("GET", "/metrics")
        snap = json.loads(conn.getresponse().read())
        conn.close()
        assert snap["bad_requests"] >= 3
    finally:
        h.stop(drain=False)
