"""Dynamic request batching: multi-lane padded buckets + deadline flush.

Requests are single images; the batcher groups them into *lanes* — one
FIFO per ``(network, resolution, priority)`` — and releases a group when
either (a) enough requests are queued to fill the largest bucket, or
(b) the lane's oldest request has crossed its deadline.  The released
group is padded up to the smallest bucket that holds it, so every flush
hits one of a handful of pre-warmed jit traces instead of compiling a
fresh batch shape per group size.  Groups never mix lanes: a batch is
always one network, one input resolution, one priority class.

Flush policy (the QoS scheduler):

  * **Deadline flushes run earliest-deadline-first.**  Each lane's
    deadline is ``max_wait_s`` after its head request enqueued —
    scaled down by ``high_wait_frac`` for priority <= 0 lanes, so
    deadline-critical requests preempt bulk traffic at flush time.
    Ordering by deadline (not by priority) is the starvation guard:
    every lane's wait is bounded by its own deadline plus the flushes
    already due, no matter how saturated a higher lane is.
  * **Full buckets flush highest-priority-first**, oldest head breaking
    ties — but never ahead of an already-overdue lane.
  * **Deadline flushes are admission-gated on downstream depth.**  When
    ``can_dispatch`` reports the dispatch window full, a partial bucket
    would only queue behind in-flight batches, so the flush is deferred
    — requests keep accumulating into a fuller bucket — until the hard
    deadline (``hard_wait_mult`` x the lane deadline), which flushes
    regardless.  Full buckets are never deferred: they cannot get any
    fuller.  ``kick()`` wakes the scheduler when a downstream slot
    frees.

Bit-exactness contract: the compiled engine is batch-invariant (see
``repro.core.lowering``), so neither the bucket choice, the zero padding,
nor a request's batch-mates can change its logits.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from concurrent.futures import Future
from dataclasses import dataclass, field
from typing import NamedTuple

import numpy as np

DEFAULT_BUCKETS = (1, 4, 8, 32)
DEFAULT_PRIORITY = 1       # bulk; priority <= 0 is the deadline-critical lane
HIGH_WAIT_FRAC = 0.25      # priority <= 0 deadline, as a fraction of max_wait
HARD_WAIT_MULT = 4.0       # deferred deadline flushes fire at this multiple


class LaneKey(NamedTuple):
    """Identity of one batching queue.  ``res`` is the input (H, W) —
    ``None`` only for control requests that never reach an engine."""
    network: str
    res: tuple | None
    priority: int


@dataclass
class Request:
    network: str
    x: object                              # (H, W, C) array
    res: tuple | None = None               # input (H, W); lane component
    priority: int = DEFAULT_PRIORITY
    deadline_s: float | None = None        # per-request deadline (from
    #                                      # enqueue); late work is
    #                                      # rejected with DeadlineExceeded
    retries: int = 0                       # dispatch-failure retries spent
    rid: int = 0                           # request id in the span log
    #                                      # (0 while the log is off)
    future: Future = field(default_factory=Future)
    t_enqueue: float = field(default_factory=time.monotonic)

    @property
    def lane(self) -> LaneKey:
        return LaneKey(self.network, self.res, self.priority)


def pick_bucket(n: int, buckets) -> int:
    """Smallest bucket >= n (buckets must be sorted ascending; n is capped
    at the largest bucket by the flush logic)."""
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


def pad_batch(xs, bucket: int):
    """Stack (H,W,C) images into a (bucket,H,W,C) batch, zero-padding the
    tail slots.  Host-side numpy on purpose: a ``jnp.stack`` here would
    jit-compile one concatenate per (bucket, image-count) pair and bill the
    first live request for it.  Zero rows never affect real rows (batch
    invariance)."""
    xb = np.zeros((bucket, *np.shape(xs[0])), np.float32)
    for i, x in enumerate(xs):
        xb[i] = np.asarray(x)
    return xb


class DynamicBatcher:
    """Per-lane FIFO queues with a shared condition variable.

    ``put`` enqueues and wakes the drain loop; ``wait_ready`` blocks until
    some lane has a flushable group (full bucket, or deadline hit and the
    dispatch window open) and pops it.  Multi-plan and multi-resolution
    isolation is structural: groups never mix lanes, so each flush goes to
    exactly one compiled engine at exactly one input shape.
    """

    def __init__(self, max_wait_s: float = 0.002,
                 max_batch: int = DEFAULT_BUCKETS[-1],
                 high_wait_frac: float = HIGH_WAIT_FRAC,
                 hard_wait_mult: float = HARD_WAIT_MULT):
        self.max_wait_s = max_wait_s
        self.max_batch = max_batch
        self.high_wait_frac = high_wait_frac
        self.hard_wait_mult = hard_wait_mult
        self._queues: dict[LaneKey, deque] = {}
        self._cond = threading.Condition()

    def put(self, req: Request, bound: int | None = None) -> bool:
        """Enqueue one request.  ``bound`` is the lane's queue-depth limit:
        when the lane already holds ``bound`` requests the request is NOT
        enqueued and False is returned — the caller sheds it
        (reject-with-backpressure) instead of buffering without bound."""
        with self._cond:
            q = self._queues.setdefault(req.lane, deque())
            if bound is not None and len(q) >= bound:
                if not q:                   # never leave an empty stub lane
                    del self._queues[req.lane]
                return False
            q.append(req)
            self._cond.notify()
            return True

    def put_front(self, reqs) -> None:
        """Re-enqueue already-admitted requests at the HEAD of their lane,
        preserving their order (the dispatch-failure retry path: retried
        rows must not fall behind younger traffic in the same lane, or
        FIFO-within-lane breaks).  Bounds do not apply — these rows were
        admitted once already."""
        by_lane: dict[LaneKey, list] = {}
        for r in reqs:
            by_lane.setdefault(r.lane, []).append(r)
        with self._cond:
            for lane, rs in by_lane.items():
                self._queues.setdefault(lane, deque()).extendleft(
                    reversed(rs))
            self._cond.notify()

    def depth(self, lane: LaneKey) -> int:
        with self._cond:
            return len(self._queues.get(lane, ()))

    def kick(self) -> None:
        """Wake the scheduler without enqueueing — called when a downstream
        dispatch slot frees, so deferred deadline flushes re-evaluate."""
        with self._cond:
            self._cond.notify()

    def pending(self) -> int:
        with self._cond:
            return sum(len(q) for q in self._queues.values())

    def depths(self) -> dict[LaneKey, int]:
        """Point-in-time queue depth per non-empty lane (the /healthz
        gauge source — one pass under the batcher's own lock)."""
        with self._cond:
            return {lane: len(q) for lane, q in self._queues.items() if q}

    def _lane_wait(self, lane: LaneKey) -> float:
        """The lane's soft deadline: priority <= 0 lanes flush after a
        fraction of the bulk max-wait — preemption at flush time."""
        if lane.priority <= 0:
            return self.max_wait_s * self.high_wait_frac
        return self.max_wait_s

    def _next_deadline_in(self, now: float, free: bool) -> float | None:
        """Seconds until the soonest actionable lane deadline (the hard
        deadline when the dispatch window is full — nothing happens at the
        soft one until ``kick``)."""
        waits = []
        for lane, q in self._queues.items():
            if not q:
                continue
            due = self._lane_wait(lane)
            if not free:
                due *= self.hard_wait_mult
            waits.append(max(0.0, due - (now - q[0].t_enqueue)))
        return min(waits, default=None)

    @staticmethod
    def _deadline_take(n: int, ladder) -> int:
        """How many of n overdue requests to flush given a bucket ladder.
        Padding n up to its covering bucket is cheap when the waste is
        small; when more than half the covering bucket would be pad (e.g.
        10 requests into a 32-bucket), flush the largest full bucket
        instead and leave the remainder queued for the next group."""
        cover = pick_bucket(n, ladder)
        if cover - n <= cover // 2:
            return n
        full = [b for b in ladder if b <= n]
        return full[-1] if full else n

    def wait_ready(self, timeout: float | None = None,
                   buckets_by: dict | None = None,
                   can_dispatch=None):
        """Block until a group is flushable; returns (lane, requests,
        by_deadline) or None on timeout.  ``buckets_by`` maps network ->
        bucket ladder override (per-network bucket policy).
        ``can_dispatch`` is the downstream admission signal: a callable
        returning False while the dispatch window is full, which defers
        deadline flushes (see module docstring) — full buckets and
        hard-overdue lanes flush regardless."""
        t_end = None if timeout is None else time.monotonic() + timeout
        with self._cond:
            while True:
                now = time.monotonic()
                free = can_dispatch() if can_dispatch is not None else True
                full_lanes, overdue = [], []
                for lane, q in list(self._queues.items()):
                    if not q:
                        # prune dead lanes: callers may mint arbitrarily
                        # many (network, res, priority) keys over a long
                        # run, and scanning them forever would make every
                        # wakeup O(all lanes ever seen)
                        del self._queues[lane]
                        continue
                    ladder = ((buckets_by or {}).get(lane.network)
                              or (self.max_batch,))
                    limit = min(self.max_batch, ladder[-1])
                    if len(q) >= limit:
                        full_lanes.append((lane.priority, q[0].t_enqueue,
                                           lane, limit))
                        continue
                    age = now - q[0].t_enqueue
                    soft = self._lane_wait(lane)
                    if age >= soft and (free
                                        or age >= soft * self.hard_wait_mult):
                        deadline = q[0].t_enqueue + soft
                        overdue.append((deadline, lane, ladder, limit))
                if overdue:                    # earliest deadline first
                    _, lane, ladder, limit = min(overdue)
                    q = self._queues[lane]
                    take = self._deadline_take(min(len(q), limit), ladder)
                    reqs = [q.popleft() for _ in range(take)]
                    if not q:
                        del self._queues[lane]
                    return lane, reqs, True
                if full_lanes:                 # highest priority first
                    _, _, lane, limit = min(full_lanes)
                    q = self._queues[lane]
                    reqs = [q.popleft() for _ in range(limit)]
                    if not q:
                        del self._queues[lane]
                    return lane, reqs, False
                wait = self._next_deadline_in(now, free)
                if t_end is not None:
                    rem = t_end - now
                    if rem <= 0:
                        return None
                    wait = rem if wait is None else min(wait, rem)
                self._cond.wait(wait)

    def drain_all(self):
        """Pop every queued request (shutdown path), grouped per lane."""
        with self._cond:
            out = [(lane, list(q)) for lane, q in self._queues.items() if q]
            self._queues.clear()
            return out
