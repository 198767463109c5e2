"""Load generator: a child process of ``run.py`` that imports no JAX.

It speaks the front door's binary protocol with its own copy of the
``XT01`` tensor frame and a minimal keep-alive HTTP/1.1 client, so it
shares no interpreter, no import and no core with the server it loads.

Protocol with the parent, one JSON object per line:

1. argv[1] is the job: ``{"traffic": {...}, "seed": n, "network": name,
   "shape": [H, W, C], "cores": [...]}``.
2. The child pins itself to ``cores``, draws every image from the seed,
   encodes every request, and prints ``{"ready": true}``.
3. The parent writes ``{"port": p, "t_go": s, "t_close": s}``
   (``time.monotonic()`` seconds, a clock both processes share).  The load
   starts at ``t_go``; requests stop being issued at ``t_close``.
4. The child waits for every outstanding answer and prints
   ``{"records": [...], "rows": {...}, "mismatch": {...}}``, described in
   ``run_load``.

Open loop: requests are due on a Poisson schedule; latency is timed from
when each was due, and the child reports how late it sent them.  Closed
loop: exactly ``clients`` requests are outstanding at all times.
"""
from __future__ import annotations

import asyncio
import base64
import gc
import json
import os
import struct
import sys
import time
from collections import deque

import numpy as np

# -- the XT01 tensor frame (a copy of the program's wire format) ---------

MAGIC = b"XT01"
# dtype codes: index into the program's wire allowlist
DTYPE_CODES = {"float32": 10}
CODE_DTYPES = {10: np.dtype("<f4")}
CONTENT_TYPE = "application/x-tensor"
# The event loop's sleep ends up to a millisecond late; the open loop sleeps
# to this much before a request is due and spins the rest of the way.
SPIN_S = 0.002


def encode_tensor(a: np.ndarray) -> bytes:
    a = np.ascontiguousarray(a, dtype="<f4")
    head = struct.pack("<4sBBH", MAGIC, DTYPE_CODES["float32"], a.ndim, 0)
    return head + struct.pack(f"<{a.ndim}I", *a.shape) + a.tobytes()


def decode_tensor(buf: bytes) -> np.ndarray:
    magic, code, ndim, _ = struct.unpack_from("<4sBBH", buf, 0)
    if magic != MAGIC or code not in CODE_DTYPES:
        raise ValueError(f"not a float32 tensor frame: {buf[:8]!r}")
    shape = struct.unpack_from(f"<{ndim}I", buf, 8)
    return np.frombuffer(buf, CODE_DTYPES[code], offset=8 + 4 * ndim
                         ).reshape(shape)


def request_bytes(network: str, x: np.ndarray) -> bytes:
    """One whole binary ``POST /v1/infer``: head and frame."""
    frame = encode_tensor(x)
    head = ("POST /v1/infer HTTP/1.1\r\n"
            "Host: 127.0.0.1\r\n"
            f"Content-Length: {len(frame)}\r\n"
            "Connection: keep-alive\r\n"
            f"Content-Type: {CONTENT_TYPE}\r\n"
            f"Accept: {CONTENT_TYPE}\r\n"
            f"X-Network: {network}\r\n\r\n").encode()
    return head + frame


async def read_response(reader: asyncio.StreamReader):
    """(status, body) of one response on a keep-alive connection."""
    line = await reader.readline()
    if not line:
        raise ConnectionError("connection closed")
    status = int(line.split()[1])
    length = 0
    while True:
        h = await reader.readline()
        if h in (b"\r\n", b"\n", b""):
            break
        k, _, v = h.partition(b":")
        if k.strip().lower() == b"content-length":
            length = int(v)
    body = await reader.readexactly(length) if length else b""
    return status, body


# -- traffic ---------------------------------------------------------------

def make_images(seed: int, n: int, shape) -> np.ndarray:
    """The request images of a run: the same seed gives the same images."""
    rng = np.random.default_rng([seed, 1])
    return (0.5 * rng.standard_normal((n, *shape))).astype(np.float32)


def body_order(seed: int, n_images: int, n: int) -> np.ndarray:
    """Which image each of ``n`` requests sends, drawn from the seed."""
    rng = np.random.default_rng([seed, 2])
    return rng.integers(0, n_images, size=n)


def poisson_offsets(traffic: dict, seed: int, horizon_s: float
                    ) -> np.ndarray:
    """Due times (seconds after the start) of an open loop.

    Every seed gets the same multiset of gaps, drawn from the traffic's own
    ``schedule_seed``, in an order drawn from the run's seed: the work of a
    run does not depend on its seed, only the order does."""
    rate = float(traffic["rate"])
    n = int(rate * horizon_s * 1.2) + 64
    gaps = np.random.default_rng(int(traffic["schedule_seed"])
                                 ).exponential(1.0 / rate, size=n)
    gaps = np.random.default_rng([seed, 3]).permutation(gaps)
    due = np.cumsum(gaps)
    return due[due < horizon_s]


class Collector:
    """Per-request records, and the served row of each image."""

    def __init__(self):
        self.records = []       # [image, t_due, t_sent, t_done, status]
        self.rows: dict[int, bytes] = {}
        self.mismatch: dict[int, float] = {}

    def done(self, image, t_due, t_sent, status, body):
        t_done = time.monotonic()
        if status == 200:
            first = self.rows.setdefault(image, body)
            if body != first:
                diff = float(np.max(np.abs(decode_tensor(body)
                                           - decode_tensor(first))))
                self.mismatch[image] = max(self.mismatch.get(image, 0.0),
                                           diff)
        self.records.append([image, t_due, t_sent, t_done, status])


async def one_request(conn, payload: bytes):
    """(status, body); status 0 where the connection failed."""
    reader, writer = conn
    try:
        writer.write(payload)
        await writer.drain()
        return await read_response(reader)
    except (ConnectionError, OSError, asyncio.IncompleteReadError):
        return 0, b""


async def closed_loop(port, payloads, order, clients, t_go, t_close, col):
    nxt = iter(range(len(order)))

    async def client():
        conn = await asyncio.open_connection("127.0.0.1", port)
        try:
            while True:
                now = time.monotonic()
                if now >= t_close:
                    return
                i = next(nxt)
                img = int(order[i])
                status, body = await one_request(conn, payloads[img])
                col.done(img, now, now, status, body)
                if status == 0:
                    conn[1].close()
                    conn = await asyncio.open_connection("127.0.0.1", port)
        finally:
            conn[1].close()

    await asyncio.sleep(max(0.0, t_go - time.monotonic()))
    await asyncio.gather(*(client() for _ in range(clients)))


async def open_loop(port, payloads, order, offsets, t_go, col):
    idle: deque = deque()       # first in, first out: every socket stays warm
    tasks = []

    async def send(img, due):
        t_sent = time.monotonic()
        reused = bool(idle)
        conn = idle.popleft() if reused else await asyncio.open_connection(
            "127.0.0.1", port)
        status, body = await one_request(conn, payloads[img])
        if status == 0 and reused:
            # the door closes a connection that stayed idle too long; a
            # request that died on such a socket was never served, so it
            # goes once more on a fresh one (its latency counts the retry)
            conn[1].close()
            conn = await asyncio.open_connection("127.0.0.1", port)
            status, body = await one_request(conn, payloads[img])
        col.done(img, due, t_sent, status, body)
        if status:
            idle.append(conn)
        else:
            conn[1].close()

    for _ in range(8):
        idle.append(await asyncio.open_connection("127.0.0.1", port))
    for k, off in enumerate(offsets):
        due = t_go + float(off)
        wait = due - time.monotonic()
        if wait > SPIN_S:
            await asyncio.sleep(wait - SPIN_S)
        while time.monotonic() < due:
            await asyncio.sleep(0)      # poll the sockets, spin to the due time
        tasks.append(asyncio.ensure_future(send(int(order[k]), due)))
    await asyncio.gather(*tasks)
    for conn in idle:
        conn[1].close()


def run_load(job: dict, payloads, go: dict) -> dict:
    """Drive the load and return what the parent reduces.

    ``records`` holds one ``[image, t_due, t_sent, t_done, status]`` per
    request (``status`` 0 for a transport failure); ``rows`` maps each image
    that was served to its row, base64 of float32; ``mismatch`` maps an
    image to the largest difference between two of its served rows."""
    traffic, seed = job["traffic"], int(job["seed"])
    col = Collector()
    t_go, t_close = float(go["t_go"]), float(go["t_close"])
    horizon = t_close - t_go
    if traffic["loop"] == "open":
        offsets = poisson_offsets(traffic, seed, horizon)
        order = body_order(seed, len(payloads), offsets.size)
        coro = open_loop(go["port"], payloads, order, offsets, t_go, col)
    else:
        clients = int(traffic["clients"])
        # more than the window can hold at any rate the door sustains
        order = body_order(seed, len(payloads),
                           int(horizon * 20000) + clients)
        coro = closed_loop(go["port"], payloads, order, clients, t_go,
                           t_close, col)
    gc.disable()
    try:
        asyncio.run(asyncio.wait_for(coro, horizon + 60.0))
    finally:
        gc.enable()
    return {"records": col.records,
            "rows": {str(k): base64.b64encode(decode_tensor(v).tobytes()
                                              ).decode()
                     for k, v in col.rows.items()},
            "mismatch": {str(k): v for k, v in col.mismatch.items()}}


def main() -> int:
    job = json.loads(sys.argv[1])
    os.sched_setaffinity(0, job["cores"])
    images = make_images(int(job["seed"]), int(job["traffic"]["images"]),
                         job["shape"])
    payloads = [request_bytes(job["network"], x) for x in images]
    del images
    print(json.dumps({"ready": True}), flush=True)
    go = json.loads(sys.stdin.readline())
    out = run_load(job, payloads, go)
    sys.stdout.write(json.dumps(out) + "\n")
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
