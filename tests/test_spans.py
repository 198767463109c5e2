"""The serving span log (``ServerMetrics.spans``): what the served path
records while it is on, that it records nothing while it is off, and that
concurrent submitters and a bound smaller than the traffic lose nothing
uncounted."""
import http.client
import sys
import threading
import time

import jax
import numpy as np
import pytest

from repro.frontend import (FrontDoor, LocalBackend, ServerThread,
                            build_server, wire)
from repro.serving.metrics import SpanLog

HW = (8, 8)
C = 16
SPEC = {"networks": [{"kind": "fire", "name": "tiny", "hw": list(HW),
                      "c_in": C, "squeeze": 4, "expand": 8, "seed": 0}],
        "server": {"max_wait_ms": 1.0}}
DOOR = ("door.read", "door.decode", "door.submit", "door.encode",
        "door.write")
BATCH = ("server.pad", "server.dispatch", "server.device_wait",
         "server.debatch")


def _images(n, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), n)
    return [np.asarray(0.5 * jax.random.normal(k, (*HW, C)),
                       dtype=np.float32) for k in ks]


@pytest.fixture(scope="module")
def door():
    """One server behind ``FrontDoor(LocalBackend(...))``: (server, a
    function that serves one image over a socket in the binary framing,
    as the benchmark's clients send it)."""
    server = build_server(SPEC)
    handle = ServerThread(FrontDoor(LocalBackend(server))).start()

    def serve(x):
        conn = http.client.HTTPConnection("127.0.0.1", handle.port,
                                          timeout=60)
        try:
            body, headers = wire.infer_request(
                "tiny", x, binary=True, accept=wire.TENSOR_CONTENT_TYPE)
            conn.request("POST", "/v1/infer", body=body, headers=headers)
            r = conn.getresponse()
            assert r.status == 200, r.read()[:200]
            return wire.decode_tensor(r.read())
        finally:
            conn.close()
    try:
        yield server, serve
    finally:
        handle.stop()


def _drain_when(spans, done, timeout=10.0):
    """Stop the log once ``done(records so far)`` holds (the door records
    its write span just after the client has its answer), and return the
    records."""
    recs = []
    t_end = time.monotonic() + timeout
    while True:
        recs += spans.drain()
        if done(recs) or time.monotonic() > t_end:
            spans.stop()
            return recs + spans.drain()
        time.sleep(0.005)


@pytest.fixture(scope="module")
def traced(door):
    """The records of one request served with the log on."""
    server, serve = door
    spans = server.metrics.spans
    spans.drain()
    spans.start()
    # past the drain thread's 50 ms wait timeout: the wait that takes the
    # request began with the log on
    time.sleep(0.12)
    serve(_images(1, seed=1)[0])
    recs = _drain_when(spans, lambda rs: any(r[0] == "door.write"
                                             for r in rs))
    assert spans.dropped == 0
    return recs


def test_log_off_records_nothing(door):
    server, serve = door
    spans = server.metrics.spans
    assert not spans.on
    spans.drain()
    serve(_images(1, seed=2)[0])
    time.sleep(0.05)
    assert spans.drain() == [] and spans.dropped == 0


def test_request_spans_share_one_id(traced):
    """The door's five spans and the batcher's queue span of the request
    carry the id the door gave it; each ends after it starts."""
    names = [r[0] for r in traced]
    for name in (*DOOR, "batcher.queue"):
        assert names.count(name) == 1, (name, names)
    ids = {r[3] for r in traced if r[0] in (*DOOR, "batcher.queue")}
    assert len(ids) == 1 and ids != {0}
    assert all(r[1] <= r[2] for r in traced)
    by = {r[0]: r for r in traced}
    # in the order the request meets them
    starts = [by[n][1] for n in ("door.read", "door.decode", "door.submit")]
    assert starts == sorted(starts)
    assert by["door.encode"][1] >= by["batcher.queue"][2]


def test_batch_spans_nest_in_their_batch(traced):
    """pad, dispatch, device wait and de-batch lie inside their
    ``server.batch`` and carry its id; the request's queue span names that
    batch as its parent and ends before the batch starts its work."""
    (batch,) = [r for r in traced if r[0] == "server.batch"]
    bid, t0, t1 = batch[3], batch[1], batch[2]
    assert bid != 0
    for name in BATCH:
        (span,) = [r for r in traced if r[0] == name]
        assert (span[3], span[4]) == (bid, bid), name
        assert t0 <= span[1] <= span[2] <= t1, name
    (queue,) = [r for r in traced if r[0] == "batcher.queue"]
    assert queue[4] == bid and queue[2] <= t0
    waits = [r for r in traced if r[0] == "batcher.wait"]
    assert waits and all(r[3] == 0 for r in waits)


def test_concurrent_submitters_lose_no_span(door):
    """Eight threads submit at once, with a short switch interval: every
    request's queue span is there exactly once, under its own id, and
    every batch has its four inner spans."""
    server, _ = door
    spans = server.metrics.spans
    spans.drain()
    imgs = _images(4, seed=3)
    ids = [[] for _ in range(8)]
    errors = []

    def submitter(k):
        try:
            futs = []
            for i in range(24):
                rid = spans.new_id()
                ids[k].append(rid)
                futs.append(server.submit("tiny", imgs[i % 4],
                                          request_id=rid))
            for f in futs:
                f.result(timeout=60)
        except Exception as e:          # reported below, in the test
            errors.append(e)

    before = server.metrics.snapshot()["batches"]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        spans.start()
        threads = [threading.Thread(target=submitter, args=(k,))
                   for k in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not any(t.is_alive() for t in threads)
        recs = _drain_when(spans, lambda rs: sum(
            r[0] == "server.batch" for r in rs)
            == server.metrics.snapshot()["batches"] - before)
    finally:
        sys.setswitchinterval(old)
    assert not errors, errors
    assert spans.dropped == 0
    queued = sorted(r[3] for r in recs if r[0] == "batcher.queue")
    assert queued == sorted(i for per in ids for i in per)
    batches = {r[3] for r in recs if r[0] == "server.batch"}
    assert len(batches) == server.metrics.snapshot()["batches"] - before
    assert {r[4] for r in recs if r[0] == "batcher.queue"} == batches
    for name in BATCH:
        assert sorted(r[3] for r in recs if r[0] == name) == sorted(batches)


@pytest.mark.parametrize("capacity,offered", [(10, 25), (10, 10), (1, 4)])
def test_a_full_log_counts_its_drops(capacity, offered):
    """Past its bound the log keeps the newest records and counts the
    oldest it let fall; a later drain counts from where the last ended."""
    log = SpanLog(capacity)
    log.start()
    for i in range(offered):
        log.add("server.pad", i, i + 1, i)
    kept = log.drain()
    assert [r[3] for r in kept] == list(range(offered))[-capacity:]
    assert log.dropped == max(0, offered - capacity)
    log.add("server.pad", 0, 1, 99)
    assert [r[3] for r in log.drain()] == [99]
    assert log.dropped == max(0, offered - capacity)


def test_a_full_log_counts_the_served_paths_drops(door):
    """A log smaller than what one served request records keeps its bound
    and counts the rest as dropped."""
    server, serve = door
    big = server.metrics.spans
    small = SpanLog(capacity=3)
    server.metrics.spans = small        # the server's own sites follow it
    try:
        small.start()
        for x in _images(2, seed=4):
            server.submit("tiny", x).result(timeout=60)
        small.stop()
        recs = small.drain()
    finally:
        server.metrics.spans = big
    # one batch a request, each with its five spans
    assert len(recs) == 3 and small.dropped >= 2 * 5 - 3
