"""Asyncio HTTP front door over an in-process ``HeteroServer``.

The last layer between the compiled heterogeneous engine and real
multiplexed traffic: requests arrive over HTTP/1.1 (stdlib asyncio only
— no new dependencies), are admission-checked BEFORE their body is read,
decoded, submitted to the server's batching lanes with their
``deadline_ms``/``priority`` propagated, and answered from the request
future.  The PR-6 typed errors cross the process boundary as stable wire
codes instead of tracebacks (``repro.frontend.wire``): ``Overloaded`` ->
429 + Retry-After, ``DeadlineExceeded`` -> 504, ``ServerClosed``/
``Shutdown`` -> 503.

**Protocol v2 (keep-alive).**  The door honors ``Connection:
keep-alive`` (the HTTP/1.1 default): one socket carries many
request/response rounds.  A reader task parses heads and bodies in
order; each admitted request runs as its own task while the NEXT
request is already being read, and a per-connection writer task sends
the responses back in request order — so a slow inference never
deadlocks the socket and a burst of pipelined requests overlaps with
batching.  Two bounds keep a connection honest: ``idle_timeout_s``
closes a socket with no request in flight and nothing arriving, and
``conn_inflight`` caps unanswered requests per connection (the reader
stops parsing until responses drain — backpressure, not a 429, because
the client self-inflicted the queue).  Both framings of
``repro.frontend.wire`` are served: JSON-base64 (default) and
``application/x-tensor`` request bodies, with the response framing
negotiated via ``Accept``.

**Body read.**  Each connection is a ``_Connection`` (an
``asyncio.BufferedProtocol``).  After the head is admitted and passes the
size check, the body gets a buffer of its own (``np.empty`` of its
``Content-Length``) and the socket writes into it directly: one copy,
the kernel's, apart from the bytes that arrived in the same read as the
head.  A binary body then decodes as a view of that buffer
(``wire.decode_tensor``); a JSON body parses from a ``bytes`` copy.
``direct_bodies`` counts the bodies read this way.

**Admission path** (cheapest check first, all before deserialization):

  1. drain fence / server state      -> 503 ``shutdown``/``server_closed``
  2. weighted per-priority token buckets (``rate``/``burst``/
     ``weights``)                    -> 429 ``overloaded`` (gate=rate)
  3. pending-futures bound (``max_pending``, read from the server's
     metrics gauges)                 -> 429 ``overloaded`` (gate=pending)
  4. body size sanity                -> 413 (connection closed)
  5. ``HeteroServer.submit`` itself  -> per-lane queue bound, typed 429

The admission class is read pre-body from the ``X-Priority`` header
(class 1 if absent): ``WeightedTokenBuckets`` splits the refill rate by
per-class weights (default ``{0: 3, 1: 1}``), so when the door
saturates, deadline-critical class-0 traffic sheds LAST instead of
competing in one global bucket.

**Endpoints.**  ``POST /v1/infer`` (inference), ``GET /healthz`` (cheap
liveness: ok flag + the gauges, served from one
``ServerMetrics.snapshot()``), ``GET /metrics`` (the full snapshot),
``POST /drain`` (fence + graceful drain, also wired to SIGTERM).

**Drain.**  ``drain()`` fences new admissions (every later request gets
a typed 503), then runs ``HeteroServer.shutdown`` off-loop under a hard
budget — every already-admitted future resolves (row or typed error; the
PR-6 contract), and the door answers each of them before the sockets
close.  A drain never hangs: the shutdown call itself is bounded and the
fence guarantees the in-flight set only shrinks.

**Spans.**  Where the backend has a span log (``LocalBackend.spans``, the
server's ``metrics.spans``) and it is on, each ``/v1/infer`` request gets
an id at the door and five spans under it: ``door.read`` (the body, after
the head), ``door.decode`` and ``door.submit`` (in ``LocalBackend.infer``),
``door.encode`` and ``door.write`` (the answer).  The server's own spans
for the request carry the same id.  A backend without a log (the
``Router``) records nothing.

``faults.trip("conn")`` fires per parsed request head (the
connection-loop trigger point: the error is answered typed and the
socket survives) and ``faults.trip("http")`` fires in the handler
between decode and submit, so front-door failures are injectable in CI
exactly like device faults (``repro.runtime.faults``).
"""
from __future__ import annotations

import asyncio
import json
import threading
import time

import numpy as np

from repro.frontend import wire
from repro.runtime import faults
from repro.serving.errors import DeadlineExceeded, ServerClosed, Shutdown

DRAIN_BUDGET_S = 10.0
DEFAULT_LANE_WEIGHTS = {0: 3.0, 1: 1.0}
HEAD_LIMIT = 1 << 16    # unread bytes a connection holds between bodies
HEAD_READ = 1 << 12     # the most one socket read takes between bodies


class TokenBucket:
    """Classic token bucket: ``rate`` tokens/s, ``burst`` capacity.
    ``rate=None`` disables the gate.  Not thread-safe — it lives on the
    event loop (one caller) by construction."""

    def __init__(self, rate: float | None, burst: int = 32):
        self.rate = rate
        self.burst = max(1, int(burst))
        self._tokens = float(self.burst)
        self._t = time.monotonic()

    def _refill(self) -> None:
        now = time.monotonic()
        self._tokens = min(self.burst,
                           self._tokens + (now - self._t) * self.rate)
        self._t = now

    def admit(self) -> bool:
        if self.rate is None:
            return True
        self._refill()
        if self._tokens >= 1.0:
            self._tokens -= 1.0
            return True
        return False

    def retry_after_s(self) -> float:
        """Seconds until one token exists — recomputed from
        ``time.monotonic()`` NOW, not from the last ``admit()`` call's
        time base, so a bucket probed without traffic reports the true
        remaining wait instead of a stale (or zero) one."""
        if self.rate is None or self.rate <= 0:
            return 0.05
        self._refill()
        return max(0.001, (1.0 - self._tokens) / self.rate)


class WeightedTokenBuckets:
    """Per-priority-class admission: one ``TokenBucket`` per class, the
    total refill ``rate`` split by ``weights`` (class -> share).  Under
    saturation each class degrades to its own weighted rate instead of
    racing for one global bucket — the deadline-critical class-0 lane
    (default weight 3) sheds LAST.  Unknown classes ride the
    lowest-weight bucket; ``rate=None`` disables every gate."""

    def __init__(self, rate: float | None, burst: int = 64,
                 weights: dict | None = None):
        self.rate = rate
        ws = {int(k): float(v)
              for k, v in (weights or DEFAULT_LANE_WEIGHTS).items()}
        if not ws or any(v <= 0 for v in ws.values()):
            raise ValueError(f"lane weights must be positive: {ws}")
        total = sum(ws.values())
        self.weights = ws
        self.buckets = {
            p: TokenBucket(None if rate is None else rate * w / total,
                           max(1, round(burst * w / total)))
            for p, w in ws.items()}
        self._fallback = min(ws, key=ws.get)

    def bucket_for(self, priority: int) -> TokenBucket:
        return self.buckets.get(int(priority), self.buckets[self._fallback])

    def admit(self, priority: int = 1) -> bool:
        return self.bucket_for(priority).admit()

    def retry_after_s(self, priority: int = 1) -> float:
        return self.bucket_for(priority).retry_after_s()


class LocalBackend:
    """One in-process ``HeteroServer`` behind the door — the single-worker
    backend, and the request semantics every worker process serves.

    The same object backs the router's in-process workers
    (``repro.frontend.router.LocalWorker``), so wire semantics are ONE
    code path whether a request crossed a socket or not.
    """

    def __init__(self, server, *, rate: float | None = None,
                 burst: int = 64, weights: dict | None = None,
                 max_pending: int | None = None,
                 request_timeout_s: float = 60.0,
                 drain_budget_s: float = DRAIN_BUDGET_S):
        self.server = server
        self.buckets = WeightedTokenBuckets(rate, burst, weights)
        self.max_pending = max_pending
        self.request_timeout_s = request_timeout_s
        self.drain_budget_s = drain_budget_s
        self.draining = False
        self.sheds = 0                     # admission-gate rejections
        self.sheds_by_class: dict[int, int] = {}
        self._drain_result: dict | None = None

    @property
    def spans(self):
        """The server's span log, which the door records its spans in."""
        return self.server.metrics.spans

    # -- admission (pre-body: nothing here touches the payload) ------------

    def admit(self, priority: int = 1):
        """None to admit, else a (status, body, headers) shed reply.
        Called after the request HEAD is parsed and before the body is
        read — an overloaded door never pays deserialization for a
        request it rejects.  ``priority`` is the admission class from
        the ``X-Priority`` header (weighted buckets)."""
        if self.draining:
            return wire.error_reply(Shutdown("draining: admission fenced"))
        if self.server.state != "running":
            return wire.error_reply(ServerClosed(
                f"server is {self.server.state}, not running"))
        if not self.buckets.admit(priority):
            self.sheds += 1
            key = int(priority)
            self.sheds_by_class[key] = self.sheds_by_class.get(key, 0) + 1
            return wire.shed_reply(
                "rate", retry_after_s=self.buckets.retry_after_s(priority))
        if self.max_pending is not None:
            gauges = self.server.metrics.snapshot()["gauges"]
            if gauges.get("pending_requests", 0) >= self.max_pending:
                self.sheds += 1
                return wire.shed_reply("pending")
        return None

    # -- request path ------------------------------------------------------

    async def infer(self, payload: dict):
        """(status, body, headers) for one /v1/infer payload.  The array
        arrives as JSON-base64 fields, a raw binary frame under
        ``_tensor``, or pre-decoded under ``_x``; a 200 body carries the
        served row un-encoded under ``_row`` (the door encodes it at the
        edge, in the client's negotiated framing).  ``_rid`` is the
        request's id in the span log, set by the door while it is on."""
        spans = self.server.metrics.spans
        rid = payload.get("_rid", 0) if spans.on else 0
        try:
            faults.trip("http")
            t0 = time.monotonic_ns() if rid else 0
            if "_tensor" in payload:
                x = wire.decode_tensor(payload["_tensor"])
            elif "_x" in payload:
                x = payload["_x"]
            else:
                x = wire.decode_array(payload)
            t1 = time.monotonic_ns() if rid else 0
            fut = self.server.submit(
                payload["network"], x,
                priority=int(payload.get("priority", 1)),
                deadline_ms=payload.get("deadline_ms"),
                request_id=rid or None)
            if rid:
                spans.add("door.decode", t0, t1, rid)
                spans.add("door.submit", t1, time.monotonic_ns(), rid)
        except Exception as e:
            reply = wire.error_reply(e)
            if reply[0] == 400:
                # malformed wire bodies are a tracked failure class, not
                # an anonymous error
                self.server.metrics.count("bad_requests")
            return reply
        try:
            row = await asyncio.wait_for(asyncio.wrap_future(fut),
                                         self.request_timeout_s)
        except asyncio.TimeoutError:
            # the future may still resolve — answer 504 NOT retryable so
            # no router re-issues a possibly-still-running request
            return wire.error_reply(DeadlineExceeded(
                f"no result within {self.request_timeout_s}s",
                waited_s=self.request_timeout_s))
        except Exception as e:
            return wire.error_reply(e)
        return 200, {"network": payload["network"], "_row": row}, {}

    async def health(self):
        snap = self.server.metrics.snapshot()
        gauges = snap.get("gauges", {})
        ok = (not self.draining
              and gauges.get("state", self.server.state) == "running")
        body = {"ok": ok, "state": gauges.get("state", self.server.state),
                "draining": self.draining,
                "uptime_s": snap.get("uptime_s", 0.0),
                "pending_requests": gauges.get("pending_requests", 0),
                "inflight_batches": gauges.get("inflight_batches", 0),
                "queue_total": gauges.get("queue_total", 0),
                "queue_depth": gauges.get("queue_depth", {}),
                "completed": snap.get("completed", 0),
                "bad_requests": snap.get("bad_requests", 0),
                "shed": snap.get("shed", 0) + self.sheds,
                "sheds_by_class": dict(self.sheds_by_class)}
        return (200 if ok else 503), body, {}

    async def metrics(self):
        return 200, self.server.metrics.snapshot(), {}

    async def drain(self, budget_s: float | None = None):
        """Fence admissions, then gracefully shut the server down off-loop
        under a hard budget.  Idempotent; never hangs."""
        if self._drain_result is not None:
            return 200, self._drain_result, {}
        self.draining = True
        budget = budget_s if budget_s is not None else self.drain_budget_s
        t0 = time.monotonic()
        loop = asyncio.get_running_loop()
        try:
            await asyncio.wait_for(
                loop.run_in_executor(None, self.server.shutdown, budget),
                budget + 1.0)
            timed_out = False
        except asyncio.TimeoutError:    # wedged drain thread: report, the
            timed_out = True            # sweep already fenced admissions
        snap = self.server.metrics.snapshot()
        self._drain_result = {
            "drained": not timed_out,
            "elapsed_s": time.monotonic() - t0,
            "pending_requests": snap["gauges"].get("pending_requests", 0),
            "drain_aborted": snap.get("drain_aborted", 0),
            "drain_flushed": snap.get("drain_flushed", 0)}
        return 200, self._drain_result, {}


class _Connection(asyncio.BufferedProtocol):
    """One door socket, read without intermediate copies.

    Between bodies the socket reads at most ``HEAD_READ`` bytes at a time
    into a small head buffer, which ``readline`` parses.  Once a head is
    admitted, ``read_body`` gives the body a buffer of its own and hands
    the socket a view of its unfilled part, so the kernel writes the body
    bytes once, into the array the request then decodes as a view; only
    the bytes that came in the same read as the head are copied.  Writes
    go straight to the transport, and ``drain`` waits out
    ``pause_writing``."""

    def __init__(self, door: "FrontDoor"):
        self._door = door
        self.transport = None
        self._head = bytearray(HEAD_LIMIT)
        self._hv = memoryview(self._head)
        self._lo = self._hi = 0         # unread head bytes: _head[_lo:_hi]
        self._body: memoryview | None = None  # pending body, unfilled part
        self._waiter: asyncio.Future | None = None
        self._eof = False
        self._exc: BaseException | None = None
        self._lost = False
        self._read_paused = False
        self._write_paused = False
        self._drain_waiter: asyncio.Future | None = None
        self._closed: asyncio.Future | None = None
        self._task: asyncio.Task | None = None

    # -- called by the transport -------------------------------------------

    def connection_made(self, transport) -> None:
        self.transport = transport
        loop = asyncio.get_running_loop()
        self._closed = loop.create_future()
        self._task = loop.create_task(self._door._handle(self))

    def get_buffer(self, sizehint: int) -> memoryview:
        if self._body is not None:
            return self._body
        if self._lo == self._hi:
            self._lo = self._hi = 0
        elif self._lo and HEAD_LIMIT - self._hi < HEAD_READ:
            n = self._hi - self._lo
            self._head[:n] = self._hv[self._lo:self._hi]
            self._lo, self._hi = 0, n
        return self._hv[self._hi:self._hi + HEAD_READ]

    def buffer_updated(self, nbytes: int) -> None:
        if self._body is not None:
            self._body = self._body[nbytes:]
            if len(self._body):
                return
            self._body = None
        else:
            self._hi += nbytes
            if self._hi - self._lo >= HEAD_LIMIT:
                self._read_paused = True    # full: nothing to hand out
                self.transport.pause_reading()
        self._wake(self._waiter)

    def eof_received(self) -> bool:
        self._eof = True
        self._wake(self._waiter)
        return True                     # half-closed: still answer

    def connection_lost(self, exc) -> None:
        self._lost = self._eof = True
        self._exc = exc
        self._wake(self._waiter)
        self._wake(self._drain_waiter)
        self._wake(self._closed)

    def pause_writing(self) -> None:
        self._write_paused = True

    def resume_writing(self) -> None:
        self._write_paused = False
        self._wake(self._drain_waiter)

    # -- called by the door ------------------------------------------------

    @staticmethod
    def _wake(fut) -> None:
        if fut is not None and not fut.done():
            fut.set_result(None)

    async def _wait(self) -> None:
        self._waiter = asyncio.get_running_loop().create_future()
        try:
            await self._waiter
        finally:
            self._waiter = None

    def _consume(self, n: int) -> None:
        self._lo += n
        if self._read_paused:
            self._read_paused = False
            self.transport.resume_reading()

    async def readline(self) -> bytes:
        """The next line with its ``\\n``; at EOF the unterminated rest,
        then ``b""`` (``StreamReader.readline``'s contract)."""
        while True:
            if self._exc is not None:
                raise self._exc
            i = self._head.find(b"\n", self._lo, self._hi)
            if i >= 0 or self._eof:
                end = i + 1 if i >= 0 else self._hi
                line = bytes(self._hv[self._lo:end])
                self._consume(end - self._lo)
                return line
            if self._hi - self._lo >= HEAD_LIMIT:
                raise ValueError(f"request head line over {HEAD_LIMIT} "
                                 f"bytes")
            await self._wait()

    async def read_body(self, n: int) -> np.ndarray:
        """The next ``n`` bytes, in a buffer of their own (uninitialised,
        so a stalled client commits no more memory than it sent).  Raises
        ``IncompleteReadError`` if the stream ends first."""
        body = np.empty(n, np.uint8)
        view = memoryview(body)
        k = min(n, self._hi - self._lo)
        view[:k] = self._hv[self._lo:self._lo + k]
        self._consume(k)
        if k == n:
            return body
        self._body = view[k:]
        try:
            while self._body is not None:
                if self._exc is not None:
                    raise self._exc
                if self._eof:
                    got = n - len(self._body)
                    raise asyncio.IncompleteReadError(bytes(view[:got]), n)
                await self._wait()
        finally:
            self._body = None
        return body

    def write(self, data) -> None:
        self.transport.write(data)

    async def drain(self) -> None:
        if self._write_paused and not self._lost:
            self._drain_waiter = asyncio.get_running_loop().create_future()
            try:
                await self._drain_waiter
            finally:
                self._drain_waiter = None
        if self._lost:
            raise ConnectionResetError("connection lost")

    def close(self) -> None:
        self.transport.close()

    async def wait_closed(self) -> None:
        await self._closed


class FrontDoor:
    """The HTTP surface: routes requests on one asyncio server to any
    backend exposing ``admit``/``infer``/``health``/``metrics``/``drain``
    (``LocalBackend`` for a worker process, ``repro.frontend.router.
    Router`` for the multi-worker door).

    Protocol v2: keep-alive sockets with pipelined in-order responses,
    bounded by ``idle_timeout_s`` (close a quiet connection) and
    ``conn_inflight`` (max unanswered requests per connection before the
    reader stops parsing — per-socket backpressure)."""

    def __init__(self, backend, *, host: str = "127.0.0.1", port: int = 0,
                 idle_timeout_s: float = 30.0, conn_inflight: int = 8):
        self.backend = backend
        self.spans = getattr(backend, "spans", None)
        self.host = host
        self.port = port
        self.idle_timeout_s = idle_timeout_s
        self.conn_inflight = max(1, int(conn_inflight))
        self._srv: asyncio.AbstractServer | None = None
        self.requests = 0
        self.connections = 0
        self.keepalive_reuses = 0       # requests beyond a socket's first
        self.direct_bodies = 0          # bodies read into their own buffer

    async def start(self) -> "FrontDoor":
        self._srv = await asyncio.get_running_loop().create_server(
            lambda: _Connection(self), self.host, self.port)
        self.port = self._srv.sockets[0].getsockname()[1]
        return self

    async def aclose(self) -> None:
        if self._srv is not None:
            self._srv.close()
            await self._srv.wait_closed()
            self._srv = None

    async def drain_and_close(self, budget_s: float | None = None) -> dict:
        """SIGTERM path: fence + drain the backend, then stop listening.
        In-flight handler tasks still hold their sockets and answer."""
        _status, body, _h = await self.backend.drain(budget_s)
        await self.aclose()
        return body

    # -- connection handler ------------------------------------------------

    async def _handle(self, conn: _Connection) -> None:
        """One keep-alive connection: this reader loop parses request
        heads and bodies IN ORDER, admission-checks between them, and
        enqueues each request's (future, keepalive, accept) for the
        writer task — which answers in the same order while the reader
        is already parsing the next request."""
        self.connections += 1
        queue: asyncio.Queue = asyncio.Queue(maxsize=self.conn_inflight)
        pending = [0]                   # enqueued, not yet answered
        wtask = asyncio.ensure_future(self._writer_loop(conn, queue,
                                                        pending))
        first = True
        try:
            while not wtask.done():
                try:
                    head = await asyncio.wait_for(wire.read_head(conn),
                                                  self.idle_timeout_s)
                except asyncio.TimeoutError:
                    if pending[0] > 0:
                        continue        # responses in flight: not idle
                    break               # idle: close the socket
                if head is None:
                    break               # EOF or unparseable head
                method, path, headers, version = head
                self.requests += 1
                if not first:
                    self.keepalive_reuses += 1
                first = False
                keep = wire.wants_keepalive(version, headers)
                item = await self._read_and_route(method, path, headers,
                                                  conn)
                if item is None:
                    break               # transport died mid-body
                result, force_close, rid = item
                keep = keep and not force_close
                pending[0] += 1
                await queue.put((result, keep, headers.get("accept"), rid))
                if not keep:
                    break
            await queue.put(None)
            await wtask
        except (ConnectionError, asyncio.IncompleteReadError):
            pass                        # client went away: nothing to answer
        except Exception as e:          # defensive: no traceback on the wire
            try:
                conn.write(wire.response_bytes(*wire.error_reply(e)))
                await conn.drain()
            except Exception:
                pass
        finally:
            if not wtask.done():
                wtask.cancel()
                try:
                    await wtask
                except (asyncio.CancelledError, Exception):
                    pass
            try:
                conn.close()
                await conn.wait_closed()
            except Exception:
                pass

    async def _writer_loop(self, conn, queue, pending) -> None:
        """Answer queued requests in order.  On a broken client socket,
        keep CONSUMING (awaiting each result, dropping the bytes) so the
        reader's bounded queue can never wedge a backend task."""
        broken = False
        spans = self.spans
        while True:
            item = await queue.get()
            if item is None:
                return
            result, keep, accept, rid = item
            try:
                if isinstance(result, tuple):
                    status, body, extra = result
                else:
                    status, body, extra = await result
            except Exception as e:
                status, body, extra = wire.error_reply(e)
            pending[0] -= 1
            if broken:
                continue
            # rid is nonzero only where the span log was on at the read
            on = rid and spans.on
            try:
                t0 = time.monotonic_ns() if on else 0
                data = self._encode(status, body, extra, keep, accept)
                t1 = time.monotonic_ns() if on else 0
                conn.write(data)
                await conn.drain()
                if on:
                    spans.add("door.encode", t0, t1, rid)
                    spans.add("door.write", t1, time.monotonic_ns(), rid)
            except Exception:
                broken = True
                continue
            if not keep:
                return

    @staticmethod
    def _encode(status, body, extra, keep, accept) -> bytes:
        """Serialize one response, encoding a served row (``_row``) at
        the edge in the client's negotiated framing."""
        if isinstance(body, dict) and "_row" in body:
            try:
                out, ctype, xh = wire.encode_result(body, accept)
            except Exception as e:
                return wire.response_bytes(*wire.error_reply(e),
                                           keepalive=keep)
            return wire.response_bytes(status, out, {**(extra or {}), **xh},
                                       keepalive=keep, content_type=ctype)
        if isinstance(body, (bytes, bytearray)):
            # router passthrough: an already-framed worker response
            ct = (extra or {}).get("content-type")
            return wire.response_bytes(status, body, extra, keepalive=keep,
                                       content_type=ct)
        return wire.response_bytes(status, body, extra, keepalive=keep)

    async def _read_and_route(self, method: str, path: str, headers: dict,
                              conn):
        """(result, force_close, rid) for one parsed request head — result
        is a (status, body, headers) tuple answered immediately, or an
        asyncio future for an in-flight inference; rid is the inference's
        id in the span log, 0 where the log is off or no inference runs.
        None means the transport died mid-body (close without
        answering)."""
        path = path.split("?", 1)[0]
        try:
            faults.trip("conn")
        except Exception as e:
            if not await self._discard_body(conn, headers):
                return None
            return wire.error_reply(e), False, 0
        if path == "/healthz" and method == "GET":
            return await self.backend.health(), False, 0
        if path == "/metrics" and method == "GET":
            return await self.backend.metrics(), False, 0
        if path == "/drain" and method == "POST":
            await self._discard_body(conn, headers)
            return await self.backend.drain(), False, 0
        if path != "/v1/infer":
            await self._discard_body(conn, headers)
            return (404, {"error": "not_found", "retryable": False,
                          "message": path}, {}), False, 0
        if method != "POST":
            await self._discard_body(conn, headers)
            return (405, {"error": "method_not_allowed", "retryable": False,
                          "message": method}, {}), False, 0
        # admission BEFORE the body: shed work, not just requests.  The
        # class rides in X-Priority so the weighted buckets can act here.
        shed = self.backend.admit(wire.priority_from_headers(headers))
        if shed is not None:
            if not await self._discard_body(conn, headers):
                return None
            return shed, False, 0
        if int(headers.get("content-length", 0) or 0) > wire.MAX_BODY_BYTES:
            # refusing to read the body leaves the socket mid-stream:
            # answer 413 and force the connection closed
            return (413, {"error": "payload_too_large",
                          "retryable": False, "message": ""}, {}), True, 0
        spans = self.spans
        on = spans is not None and spans.on
        t0 = time.monotonic_ns() if on else 0
        try:
            body = await self._read_body(conn, headers)
        except (ConnectionError, asyncio.IncompleteReadError, OSError):
            return None
        ctype = headers.get("content-type", "")
        if ctype.startswith(wire.TENSOR_CONTENT_TYPE):
            try:
                meta = wire.infer_meta_from_headers(headers)
            except Exception as e:
                return wire.error_reply(e), False, 0
            # the request owns the body: decode_tensor views it, no copy
            payload = {**meta, "_tensor": memoryview(body)}
        else:
            try:
                payload = json.loads(body.tobytes())
            except Exception as e:
                return (400, {"error": "bad_request", "retryable": False,
                              "message": f"invalid JSON: {e}"}, {}), False, 0
            if not isinstance(payload, dict):
                return (400, {"error": "bad_request", "retryable": False,
                              "message": "request body must be a JSON "
                                         "object"}, {}), False, 0
        if headers.get("accept"):
            # ride along so a router hop can forward the negotiation and
            # pass the worker's framed response through untranscoded
            payload["_accept"] = headers["accept"]
        rid = 0
        if on:
            rid = payload["_rid"] = spans.new_id()
            spans.add("door.read", t0, time.monotonic_ns(), rid)
        return asyncio.ensure_future(self.backend.infer(payload)), False, rid

    async def _read_body(self, conn: _Connection, headers) -> np.ndarray:
        """The request's body as a uint8 array it owns, the socket
        writing into it directly (``_Connection.read_body``); empty when
        the head declares no length."""
        n = int(headers.get("content-length", 0) or 0)
        if n <= 0:
            return np.empty(0, np.uint8)
        body = await conn.read_body(n)
        self.direct_bodies += 1
        return body

    async def _discard_body(self, conn, headers) -> bool:
        """Drain a rejected request's body so the client can read the
        reply AND the next pipelined request starts at a clean byte
        boundary (a closed pipe mid-upload reads as a transport error,
        and a transport error would be retried — a shed must stay
        typed).  False if the transport died under the read."""
        try:
            await self._read_body(conn, headers)
            return True
        except Exception:
            return False


class ServerThread:
    """Run a ``FrontDoor`` (and optionally extra startup coroutines, e.g.
    ``Router.start``) on a dedicated event loop in a daemon thread — the
    handle tests, benchmarks and examples drive blocking HTTP clients
    against.

        with ServerThread(FrontDoor(LocalBackend(server))) as h:
            requests -> 127.0.0.1:h.port
    """

    def __init__(self, door: FrontDoor, *, also_start=()):
        self.door = door
        self._also = list(also_start)   # extra "async def start()" objects
        self.loop = asyncio.new_event_loop()
        self._ready = threading.Event()
        self._thread = threading.Thread(target=self._run,
                                        name="frontdoor-loop", daemon=True)

    def _run(self) -> None:
        asyncio.set_event_loop(self.loop)

        async def boot():
            for obj in self._also:
                await obj.start()
            await self.door.start()
            self._ready.set()

        self.loop.run_until_complete(boot())
        self.loop.run_forever()
        # cancel stragglers so the loop closes clean
        for task in asyncio.all_tasks(self.loop):
            task.cancel()
        try:
            self.loop.run_until_complete(
                self.loop.shutdown_asyncgens())
        except Exception:
            pass
        self.loop.close()

    def start(self) -> "ServerThread":
        self._thread.start()
        if not self._ready.wait(30.0):
            raise RuntimeError("front door failed to start in 30s")
        return self

    @property
    def port(self) -> int:
        return self.door.port

    def call(self, coro, timeout: float = 60.0):
        """Run one coroutine on the door's loop from any thread."""
        fut = asyncio.run_coroutine_threadsafe(coro, self.loop)
        return fut.result(timeout)

    def stop(self, drain: bool = True, budget_s: float = DRAIN_BUDGET_S):
        out = None
        if self._thread.is_alive():
            if drain:
                try:
                    out = self.call(self.door.drain_and_close(budget_s),
                                    timeout=budget_s + 5.0)
                except Exception:
                    pass
            self.loop.call_soon_threadsafe(self.loop.stop)
            self._thread.join(10.0)
        return out

    def __enter__(self) -> "ServerThread":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()
