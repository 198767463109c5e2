"""Wire protocol v2 for the process-level serving front door.

HTTP/1.1 over stdlib asyncio, two request framings, one request = one
inference:

**JSON-base64 (compatibility default)** —

    POST /v1/infer
    Content-Type: application/json
    {"network": "mbv2", "shape": [96, 96, 3], "dtype": "<f4",
     "data": "<base64 little-endian bytes>",
     "priority": 1, "deadline_ms": 50.0}

    200 {"network": "mbv2", "result": {"shape": ..., "dtype": ...,
                                       "data": ...}}

**Binary tensor (negotiated)** — ``Content-Type: application/x-tensor``
carries the array as a fixed little-endian frame (magic, dtype code,
ndim, u32 shape, raw bytes — see ``encode_tensor``); request metadata
rides in headers (``X-Network``, ``X-Priority``, ``X-Deadline-Ms``).  A
client that sends ``Accept: application/x-tensor`` gets its 200 row back
as the same frame; error replies are ALWAYS JSON:

    4xx/5xx {"error": "<stable code>", "retryable": bool,
             "message": "..."}

Both framings decode to bit-identical arrays (parity-tested in
``tests/test_wire_fuzz.py``); the binary frame skips base64's ~33%
size tax on the hot path.

**Byte order is pinned.**  ``encode_array``/``encode_tensor`` emit
explicit little-endian dtypes (``<f4``-style strings, byteswapping
big-endian inputs), and the decoders validate against the
``WIRE_DTYPES`` allowlist, check ``len(raw) == prod(shape) * itemsize``
BEFORE ``frombuffer``, and byteswap any explicit big-endian input — a
malformed body raises ``WireDecodeError`` (a typed 400 on the wire),
never an uncaught 500.

The error body's ``error``/``retryable`` fields come straight from the
typed serving errors (``repro.serving.errors``): ``overloaded`` -> 429 +
``Retry-After``, ``deadline_exceeded`` -> 504, ``server_closed`` /
``shutdown`` -> 503.  A router decides whether to re-issue a request from
``retryable`` alone — no isinstance ladder crosses the process boundary.

The HTTP layer is split so the front door can ADMIT OR SHED AFTER THE
HEADERS, BEFORE the body (``read_head``, then the body into a buffer of
its own, which ``decode_tensor`` views without a copy), and since
protocol v2 it speaks **keep-alive**: responses carry
``Connection: keep-alive`` when the client asked for (or HTTP/1.1
implies) it, and ``HttpPool`` is the client half — persistent
per-worker connections with a single safe retry on a stale pooled
socket (our server always answers before closing, so EOF-before-status
on a REUSED connection means the request was never processed).
"""
from __future__ import annotations

import asyncio
import base64
import binascii
import json
import math
import struct

import numpy as np

from repro.serving.errors import ServingError

MAX_BODY_BYTES = 64 << 20          # refuse absurd bodies before reading
MAX_NDIM = 16                      # decode bound: no 255-d reshape bombs
REASONS = {200: "OK", 400: "Bad Request", 404: "Not Found",
           405: "Method Not Allowed", 413: "Payload Too Large",
           429: "Too Many Requests", 500: "Internal Server Error",
           503: "Service Unavailable", 504: "Gateway Timeout"}

TENSOR_CONTENT_TYPE = "application/x-tensor"
TENSOR_MAGIC = b"XT01"
_TENSOR_HEAD = struct.Struct("<4sBBH")     # magic, dtype code, ndim, flags


class WireDecodeError(ValueError):
    """A malformed wire body.  Subclasses ``ValueError`` so every decode
    failure maps to a typed 400 ``bad_request`` (never a 500): the
    request is wrong, not the worker, and it is never retried."""

    code = "bad_request"


# -- dtype allowlist ---------------------------------------------------------
# The only dtypes that may cross the wire, pinned little-endian.  Keyed by
# BOTH the numpy name ("float32") and the explicit-order string ("<f4");
# big-endian strings (">f4") are accepted on decode and byteswapped.

WIRE_DTYPES = ("bool", "int8", "uint8", "int16", "uint16", "int32",
               "uint32", "int64", "uint64", "float16", "float32",
               "float64")
_DECODE_DTYPES: dict[str, np.dtype] = {}
_DTYPE_CODES: dict[str, int] = {}          # LE str -> binary frame code
_CODE_DTYPES: dict[int, np.dtype] = {}     # binary frame code -> LE dtype
for _i, _name in enumerate(WIRE_DTYPES):
    _le = np.dtype(_name).newbyteorder("<")
    _DECODE_DTYPES[_name] = _le
    _DECODE_DTYPES[_le.str] = _le
    _DTYPE_CODES[_le.str] = _i
    _CODE_DTYPES[_i] = _le
    if _le.itemsize > 1:                   # ">f4": decode + byteswap
        _DECODE_DTYPES[_le.newbyteorder(">").str] = _le.newbyteorder(">")


def _wire_dtype(dt: np.dtype) -> np.dtype:
    """The pinned little-endian dtype an array goes out as."""
    le = np.dtype(dt).newbyteorder("<")
    if le.str not in _DTYPE_CODES:
        raise WireDecodeError(f"unsupported wire dtype {dt!s}")
    return le


def _decode_dtype(name) -> np.dtype:
    if not isinstance(name, str) or name not in _DECODE_DTYPES:
        raise WireDecodeError(f"dtype {name!r} not in wire allowlist")
    return _DECODE_DTYPES[name]


def _decode_shape(shape, itemsize: int) -> tuple[int, ...]:
    """Validate a wire shape: a list/tuple of non-negative ints whose
    total byte size stays under ``MAX_BODY_BYTES``."""
    if not isinstance(shape, (list, tuple)):
        raise WireDecodeError(f"shape must be a list, got "
                              f"{type(shape).__name__}")
    if len(shape) > MAX_NDIM:
        raise WireDecodeError(f"shape has {len(shape)} dims (max "
                              f"{MAX_NDIM})")
    dims = []
    for v in shape:
        if isinstance(v, bool) or not isinstance(v, int):
            raise WireDecodeError(f"shape dim {v!r} is not an int")
        if v < 0:
            raise WireDecodeError(f"negative shape dim {v}")
        dims.append(v)
    if math.prod(dims) * itemsize > MAX_BODY_BYTES:
        raise WireDecodeError("shape overflows the body-size bound")
    return tuple(dims)


def _as_native(a: np.ndarray, dt: np.dtype) -> np.ndarray:
    """A writable native-order array from a frombuffer view: the view
    itself where its buffer is writable and it is aligned, else a copy."""
    if dt.byteorder == ">":
        return a.astype(dt.newbyteorder("<"))
    if a.flags.writeable and a.flags.aligned:
        return a
    return a.copy()


# -- array <-> JSON ----------------------------------------------------------

def encode_array(a) -> dict:
    """JSON-base64 framing.  The ``dtype`` field is an explicit
    ``<``-prefixed little-endian string and the bytes match it — a
    big-endian input array is byteswapped, never emitted native."""
    a = np.asarray(a, order="C")         # keeps 0-d (ascontiguousarray not)
    le = _wire_dtype(a.dtype)
    a = a.astype(le, copy=False)
    return {"shape": list(a.shape), "dtype": le.str,
            "data": base64.b64encode(a.tobytes()).decode("ascii")}


def decode_array(d) -> np.ndarray:
    """Decode a JSON-base64 array body.  Every malformed input —
    unknown dtype, bad shape, truncated/overlong payload, invalid
    base64 — raises ``WireDecodeError`` (typed 400), never escapes as a
    500."""
    if not isinstance(d, dict):
        raise WireDecodeError(f"array body must be an object, got "
                              f"{type(d).__name__}")
    for k in ("shape", "dtype", "data"):
        if k not in d:
            raise WireDecodeError(f"array body missing field {k!r}")
    dt = _decode_dtype(d["dtype"])
    shape = _decode_shape(d["shape"], dt.itemsize)
    data = d["data"]
    if not isinstance(data, str):
        raise WireDecodeError("array data must be a base64 string")
    try:
        raw = base64.b64decode(data, validate=True)
    except (binascii.Error, ValueError) as e:
        raise WireDecodeError(f"invalid base64 data: {e}") from e
    want = math.prod(shape) * dt.itemsize
    if len(raw) != want:
        raise WireDecodeError(f"payload is {len(raw)} bytes, shape "
                              f"{list(shape)} x {dt.itemsize}B needs "
                              f"{want}")
    return _as_native(np.frombuffer(raw, dtype=dt).reshape(shape), dt)


# -- array <-> binary frame --------------------------------------------------

def encode_tensor(a) -> bytes:
    """``application/x-tensor`` framing: a fixed little-endian header —
    magic ``XT01``, u8 dtype code (index into ``WIRE_DTYPES``), u8 ndim,
    u16 reserved, then ndim u32 dims — followed by the raw little-endian
    element bytes.  No base64: the frame is ``8 + 4*ndim + nbytes``
    long, ~25% smaller on the wire than the JSON path's base64."""
    a = np.asarray(a, order="C")         # keeps 0-d (ascontiguousarray not)
    le = _wire_dtype(a.dtype)
    a = a.astype(le, copy=False)
    if a.ndim > MAX_NDIM:
        raise WireDecodeError(f"{a.ndim} dims exceed the wire bound")
    head = _TENSOR_HEAD.pack(TENSOR_MAGIC, _DTYPE_CODES[le.str],
                             a.ndim, 0)
    head += struct.pack(f"<{a.ndim}I", *a.shape)
    return head + a.tobytes()


def decode_tensor(buf) -> np.ndarray:
    """Decode one binary tensor frame; every malformed frame raises
    ``WireDecodeError`` — bad magic, unknown dtype code, truncated
    header, or a byte count that disagrees with the declared shape.

    A writable buffer (a ``bytearray``, or the door's ``memoryview`` of
    a body the request owns) decodes as a view of it, with no copy; a
    read-only one (``bytes``) decodes to a writable copy."""
    if not isinstance(buf, (bytes, bytearray, memoryview)):
        raise WireDecodeError("tensor body must be bytes")
    buf = memoryview(buf)
    if not (buf.c_contiguous and buf.ndim == 1 and buf.itemsize == 1):
        buf = memoryview(buf.tobytes())
    if len(buf) < _TENSOR_HEAD.size:
        raise WireDecodeError(f"tensor frame truncated at {len(buf)} "
                              f"bytes")
    magic, code, ndim, _flags = _TENSOR_HEAD.unpack_from(buf, 0)
    if magic != TENSOR_MAGIC:
        raise WireDecodeError(f"bad tensor magic {magic!r}")
    if code not in _CODE_DTYPES:
        raise WireDecodeError(f"unknown dtype code {code}")
    if ndim > MAX_NDIM:
        raise WireDecodeError(f"{ndim} dims exceed the wire bound")
    dt = _CODE_DTYPES[code]
    off = _TENSOR_HEAD.size + 4 * ndim
    if len(buf) < off:
        raise WireDecodeError("tensor frame truncated inside shape")
    shape = struct.unpack_from(f"<{ndim}I", buf, _TENSOR_HEAD.size)
    shape = _decode_shape(list(shape), dt.itemsize)
    want = math.prod(shape) * dt.itemsize
    if len(buf) - off != want:
        raise WireDecodeError(f"tensor payload is {len(buf) - off} "
                              f"bytes, shape {list(shape)} needs {want}")
    return _as_native(np.frombuffer(buf, dtype=dt, offset=off)
                      .reshape(shape), dt)


# -- client request builders -------------------------------------------------

def infer_payload(network: str, x, *, priority: int | None = None,
                  deadline_ms: float | None = None) -> dict:
    """Client-side JSON body for ``POST /v1/infer``."""
    out = {"network": network, **encode_array(x)}
    if priority is not None:
        out["priority"] = int(priority)
    if deadline_ms is not None:
        out["deadline_ms"] = float(deadline_ms)
    return out


def infer_request(network: str, x, *, priority: int | None = None,
                  deadline_ms: float | None = None, binary: bool = False,
                  accept: str | None = None):
    """(body_bytes, headers) for ``POST /v1/infer`` in either framing.
    ``X-Priority`` always rides in the headers so the door's weighted
    admission can classify the request BEFORE reading its body."""
    headers = {}
    if priority is not None:
        headers["X-Priority"] = str(int(priority))
    if accept is not None:
        headers["Accept"] = accept
    if binary:
        headers["Content-Type"] = TENSOR_CONTENT_TYPE
        headers["X-Network"] = str(network)
        if deadline_ms is not None:
            headers["X-Deadline-Ms"] = f"{float(deadline_ms):g}"
        return encode_tensor(x), headers
    headers["Content-Type"] = "application/json"
    body = infer_payload(network, x, priority=priority,
                         deadline_ms=deadline_ms)
    return json.dumps(body).encode(), headers


def infer_meta_from_headers(headers: dict) -> dict:
    """network/priority/deadline_ms for a binary-framed request, from
    its ``X-``* headers (the body is the bare tensor)."""
    net = headers.get("x-network")
    if not net:
        raise WireDecodeError("binary infer request missing X-Network")
    meta: dict = {"network": net}
    if "x-priority" in headers:
        try:
            meta["priority"] = int(headers["x-priority"])
        except ValueError as e:
            raise WireDecodeError(f"bad X-Priority: "
                                  f"{headers['x-priority']!r}") from e
    if "x-deadline-ms" in headers:
        try:
            meta["deadline_ms"] = float(headers["x-deadline-ms"])
        except ValueError as e:
            raise WireDecodeError(f"bad X-Deadline-Ms: "
                                  f"{headers['x-deadline-ms']!r}") from e
    return meta


def accepts_tensor(accept: str | None) -> bool:
    return bool(accept) and TENSOR_CONTENT_TYPE in accept


def priority_from_headers(headers: dict, default: int = 1) -> int:
    """The admission class of a request, read pre-body (``X-Priority``
    header; malformed values fall back to the default class — admission
    must never throw before the typed decode path can answer)."""
    try:
        return int(headers.get("x-priority", default))
    except (TypeError, ValueError):
        return default


# -- typed error <-> wire ----------------------------------------------------

def error_reply(exc: BaseException, *, retry_after_s: float = 0.05):
    """(status, body, headers) for any failure.  Typed serving errors map
    through their stable ``code``/``retryable``/``wire_status``; malformed
    wire bodies map to a typed 400; anything else is an opaque 500 marked
    retryable (the process may be sick, a different worker can serve) —
    tracebacks never cross the wire."""
    if isinstance(exc, ServingError):
        status = exc.wire_status
        body = {"error": exc.code, "retryable": bool(exc.retryable),
                "message": str(exc)}
        lane = getattr(exc, "lane_label", None)
        if lane is not None:
            body["lane"] = lane
    elif isinstance(exc, WireDecodeError):
        status = 400
        body = {"error": exc.code, "retryable": False,
                "message": str(exc)}
    elif isinstance(exc, (KeyError, ValueError)):
        # unregistered network / malformed image: the request is wrong,
        # not the worker — never retried
        status = 400
        body = {"error": "bad_request", "retryable": False,
                "message": str(exc)}
    else:
        status = 500
        body = {"error": "internal", "retryable": True,
                "message": type(exc).__name__}
    headers = {}
    if status == 429:
        headers["Retry-After"] = f"{retry_after_s:.3f}"
    return status, body, headers


def shed_reply(reason: str, *, retry_after_s: float = 0.05):
    """A 429 minted at an admission gate ABOVE ``submit`` (token bucket,
    pending bound) — same shape as a server-side ``Overloaded``."""
    return 429, {"error": "overloaded", "retryable": True,
                 "message": reason, "gate": reason}, \
        {"Retry-After": f"{retry_after_s:.3f}"}


def is_retryable(status: int, body: dict | None) -> bool:
    """Router-side retry decision from a wire response alone."""
    if isinstance(body, dict) and "retryable" in body:
        return bool(body["retryable"])
    return status in (429, 503)


# -- minimal HTTP/1.1 --------------------------------------------------------

async def read_head(reader: asyncio.StreamReader):
    """(method, path, headers, version) — or None on EOF/garbage.  Stops
    at the blank line so the caller can shed before touching the body."""
    line = await reader.readline()
    if not line:
        return None
    try:
        method, path, version = line.decode("latin-1").split(None, 2)
    except ValueError:
        return None
    headers: dict[str, str] = {}
    while True:
        h = await reader.readline()
        if h in (b"\r\n", b"\n", b""):
            break
        if b":" in h:
            k, v = h.decode("latin-1").split(":", 1)
            headers[k.strip().lower()] = v.strip()
    return method.upper(), path, headers, version.strip()


def wants_keepalive(version: str, headers: dict) -> bool:
    """HTTP/1.1 defaults to keep-alive; any other version must opt in
    explicitly.  ``Connection: close`` always wins."""
    conn = headers.get("connection", "").lower()
    if "close" in conn:
        return False
    if "keep-alive" in conn:
        return True
    return version.upper().endswith("/1.1")


async def read_body(reader: asyncio.StreamReader, headers: dict) -> bytes:
    """A response body on the client half (``HttpPool``); the door reads
    request bodies through its own connection protocol."""
    n = int(headers.get("content-length", 0) or 0)
    if n <= 0:
        return b""
    return await reader.readexactly(n)


def response_bytes(status: int, body, headers: dict | None = None, *,
                   keepalive: bool = False,
                   content_type: str | None = None) -> bytes:
    """Serialize one response; dict bodies go out as JSON, bytes bodies
    as-is (``content_type`` names their framing).  ``keepalive`` decides
    the ``Connection`` header — the v2 door answers many requests per
    socket."""
    if isinstance(body, (dict, list)):
        payload = json.dumps(body).encode()
        ctype = "application/json"
    else:
        payload = bytes(body or b"")
        ctype = content_type or "text/plain"
    lines = [f"HTTP/1.1 {status} {REASONS.get(status, 'Unknown')}",
             f"Content-Type: {ctype}",
             f"Content-Length: {len(payload)}",
             f"Connection: {'keep-alive' if keepalive else 'close'}"]
    for k, v in (headers or {}).items():
        if k.lower() in ("content-type", "content-length", "connection"):
            continue
        lines.append(f"{k}: {v}")
    return ("\r\n".join(lines) + "\r\n\r\n").encode() + payload


def encode_result(body: dict, accept: str | None):
    """(payload, content_type, extra_headers) for a 200 body carrying a
    served row (``_row``), in the client's negotiated framing.  Bit-match
    parity between the two encodings is a protocol invariant — both are
    lossless byte codecs of the same row."""
    row = body["_row"]
    if accepts_tensor(accept):
        return (encode_tensor(row), TENSOR_CONTENT_TYPE,
                {"X-Network": str(body.get("network", ""))})
    out = {k: v for k, v in body.items() if not k.startswith("_")}
    out["result"] = encode_array(row)
    return out, "application/json", {}


def parse_client_body(headers: dict, raw: bytes):
    """Client-side response body parse: tensor frame -> ndarray, JSON ->
    dict, anything else -> raw bytes."""
    ctype = headers.get("content-type", "")
    if ctype.startswith(TENSOR_CONTENT_TYPE):
        return decode_tensor(raw)
    if not raw:
        return None
    if "json" in ctype or ctype.startswith("text/"):
        try:
            return json.loads(raw)
        except ValueError:
            return raw
    return raw


async def _read_response(reader: asyncio.StreamReader):
    """(status, headers, raw_body, keepalive_ok) for one response on a
    (possibly reused) client connection."""
    status_line = await reader.readline()
    if not status_line:
        raise ConnectionError("empty response")
    try:
        status = int(status_line.split()[1])
    except (IndexError, ValueError) as e:
        raise ConnectionError(f"bad status line {status_line!r}") from e
    headers: dict[str, str] = {}
    while True:
        h = await reader.readline()
        if h in (b"\r\n", b"\n", b""):
            break
        if b":" in h:
            k, v = h.decode("latin-1").split(":", 1)
            headers[k.strip().lower()] = v.strip()
    raw = await read_body(reader, headers)
    if not raw and "content-length" not in headers:
        raw = await reader.read()
    keep = "close" not in headers.get("connection", "").lower()
    return status, headers, raw, keep


def _request_head(method: str, path: str, host: str, port: int,
                  headers: dict, n_body: int, *, keepalive: bool) -> bytes:
    lines = [f"{method} {path} HTTP/1.1", f"Host: {host}:{port}",
             f"Content-Length: {n_body}",
             f"Connection: {'keep-alive' if keepalive else 'close'}"]
    seen = {"host", "content-length", "connection"}
    for k, v in (headers or {}).items():
        if k.lower() in seen:
            continue
        lines.append(f"{k}: {v}")
    return ("\r\n".join(lines) + "\r\n\r\n").encode()


class HttpPool:
    """Persistent keep-alive connections to one (host, port) — the
    client half of the v2 data plane.  The router keeps one pool per
    worker instead of dialing per request; connection setup stops being
    a per-request tax exactly where the paper's communication-overhead
    budget is spent.

    A request on a REUSED socket that dies before the status line
    arrives is retried ONCE on a fresh connection: our server always
    writes the response before closing, so an EOF there means the
    request was never processed (the server had already closed the
    idle socket) — the retry cannot double-serve.  A failure on a
    FRESH connection propagates: that is the router's ejection signal.
    """

    def __init__(self, host: str, port: int, *, size: int = 8):
        self.host = host
        self.port = int(port)
        self.size = max(1, int(size))
        self._idle: list[tuple] = []
        self.dials = 0                  # fresh connections opened
        self.reuses = 0                 # requests served on a pooled conn

    async def _checkout(self):
        """(reader, writer, reused)."""
        while self._idle:
            reader, writer = self._idle.pop()
            if writer.is_closing():
                self._close((reader, writer))
                continue
            return reader, writer, True
        reader, writer = await asyncio.open_connection(self.host,
                                                       self.port)
        self.dials += 1
        return reader, writer, False

    def _checkin(self, rw) -> None:
        if len(self._idle) < self.size and not rw[1].is_closing():
            self._idle.append(rw)
        else:
            self._close(rw)

    @staticmethod
    def _close(rw) -> None:
        try:
            rw[1].close()
        except Exception:
            pass

    def close(self) -> None:
        """Close every idle connection (synchronous: transport close
        only, safe from lifecycle code off the pool's loop)."""
        while self._idle:
            self._close(self._idle.pop())

    async def _once(self, rw, method, path, headers, body):
        reader, writer = rw
        writer.write(_request_head(method, path, self.host, self.port,
                                   headers, len(body), keepalive=True)
                     + body)
        await writer.drain()
        return await _read_response(reader)

    async def request(self, method: str, path: str, *, body: bytes = b"",
                      headers: dict | None = None, timeout: float = 30.0):
        """(status, headers, raw_body).  Raises ``ConnectionError`` /
        ``OSError`` on transport failure and ``asyncio.TimeoutError``
        past ``timeout`` — the router's retry and ejection signals."""
        held: list = []

        async def _go():
            rw3 = await self._checkout()
            rw, reused = rw3[:2], rw3[2]
            held.append(rw)
            try:
                out = await self._once(rw, method, path, headers or {},
                                       body)
            except (ConnectionError, OSError,
                    asyncio.IncompleteReadError) as e:
                held.remove(rw)
                self._close(rw)
                if not reused:
                    raise
                # stale pooled socket: one retry on a fresh connection
                reader, writer = await asyncio.open_connection(
                    self.host, self.port)
                self.dials += 1
                rw = (reader, writer)
                held.append(rw)
                try:
                    out = await self._once(rw, method, path,
                                           headers or {}, body)
                except asyncio.IncompleteReadError as e2:
                    raise ConnectionError(str(e2)) from e
            if reused:
                self.reuses += 1
            status, rheaders, raw, keep = out
            held.remove(rw)
            if keep:
                self._checkin(rw)
            else:
                self._close(rw)
            return status, rheaders, raw

        try:
            return await asyncio.wait_for(_go(), timeout)
        except BaseException:
            # timeout/cancel mid-flight: never pool a half-read socket
            for rw in held:
                self._close(rw)
            raise


async def http_json(host: str, port: int, method: str, path: str,
                    body: dict | None = None, timeout: float = 30.0):
    """Tiny one-shot asyncio HTTP client: (status, headers, parsed-JSON
    body) over a fresh ``Connection: close`` socket.  Kept for
    compatibility and for callers that deliberately measure the
    reconnect-per-request path; persistent clients use ``HttpPool``."""

    async def _go():
        reader, writer = await asyncio.open_connection(host, port)
        try:
            payload = b"" if body is None else json.dumps(body).encode()
            writer.write(_request_head(
                method, path, host, port,
                {"Content-Type": "application/json"}, len(payload),
                keepalive=False) + payload)
            await writer.drain()
            status, headers, raw, _keep = await _read_response(reader)
            out = json.loads(raw) if raw else None
            return status, headers, out
        finally:
            writer.close()
            try:
                await writer.wait_closed()
            except Exception:
                pass

    return await asyncio.wait_for(_go(), timeout)
