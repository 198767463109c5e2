"""Serving metrics: counters, latency percentiles and a span log.

One ``ServerMetrics`` per ``HeteroServer``; the drain loop records a sample
per completed request (end-to-end: enqueue -> result ready) and a sample
per flushed batch, tagged with the batch's lane (network @ resolution /
priority) so the snapshot reports per-lane p50/p99 next to the server-wide
numbers.  ``snapshot`` is safe to call from any thread.

``ServerMetrics.spans`` is the server's ``SpanLog``: off unless an operator
starts it, it then records where each request and batch spent its time on
the served path (door, batcher, engine), on ``time.monotonic_ns()``.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import deque

# The span log's default bound, in records: about three minutes of a
# server answering ~900 requests/s, at six spans a request.
SPAN_CAPACITY = 1 << 20


def percentile(values, q: float) -> float:
    """Linear-interpolation percentile (q in [0, 100]) of an iterable."""
    vs = sorted(values)
    if not vs:
        return float("nan")
    if len(vs) == 1:
        return float(vs[0])
    pos = (len(vs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(vs) - 1)
    frac = pos - lo
    return float(vs[lo] * (1.0 - frac) + vs[hi] * frac)


class SpanLog:
    """A bounded in-memory log of timed spans on the served path.

    A record is ``(name, t0_ns, t1_ns, id, parent, thread, replica)``:

    - ``t0_ns``/``t1_ns`` are ``time.monotonic_ns()`` readings;
    - ``id`` is the request's or the batch's id (``new_id``), shared by
      every span of that request or batch;
    - ``parent`` is the id of the span that caused this one (a request's
      ``batcher.queue`` span names the batch that took it), else 0;
    - ``thread`` is ``threading.get_ident()`` of the recording thread;
    - ``replica`` is the replica a ``server.dispatch`` or
      ``server.device_wait`` ran on, else -1.

    The spans, by the layer that records them:

    - door (the front door's event loop): ``door.read`` (the request body,
      after its head), ``door.decode``, ``door.submit`` (``submit``,
      including the batcher's ``put``), ``door.encode``, ``door.write``;
    - batcher (the drain thread): ``batcher.queue`` per request, from its
      enqueue to the pop that took it into a batch; ``batcher.wait``, the
      drain thread waiting for a flushable group;
    - engine (the drain or completion thread): ``server.batch`` from the
      flush to the last future fulfilled, around ``server.pad`` (bucket
      pick and padding), ``server.dispatch`` (the engine call, which
      enqueues the batch's copy to the device and the program),
      ``server.device_wait`` (until the result is ready: the copy where
      it is still running, then the program) and ``server.debatch``
      (copy back, futures, counters).

    Off by default.  While off, each site costs one attribute read of
    ``on`` and allocates nothing; while on, a site adds a tuple to a
    ``deque``, which needs no lock.  When more than ``capacity`` records
    wait to be drained the oldest fall off and ``dropped`` counts them.
    """

    def __init__(self, capacity: int = SPAN_CAPACITY):
        self.on = False
        self.dropped = 0
        self._recs: deque = deque(maxlen=capacity)
        self._ids = itertools.count(1)
        # one tick per record offered, and one per drain (see ``drain``)
        self._offered = itertools.count()
        self._base = 0

    def start(self) -> None:
        self.on = True

    def stop(self) -> None:
        self.on = False

    def new_id(self) -> int:
        """A fresh request or batch id (never 0, unique in this log)."""
        return next(self._ids)

    def add(self, name: str, t0_ns: int, t1_ns: int, id: int = 0,
            parent: int = 0, replica: int = -1) -> None:
        next(self._offered)
        self._recs.append((name, t0_ns, t1_ns, id, parent,
                           threading.get_ident(), replica))

    def drain(self) -> list:
        """Every record kept since the last drain, oldest first; adds what
        fell off the bound meanwhile to ``dropped``."""
        out = []
        while True:
            try:
                out.append(self._recs.popleft())
            except IndexError:
                break
        tick = next(self._offered)
        self.dropped += tick - self._base - len(out)
        self._base = tick + 1
        return out


class ServerMetrics:
    """Thread-safe counters and bounded latency reservoirs (one server-wide,
    one per lane)."""

    def __init__(self, reservoir: int = 8192, lane_reservoir: int = 2048):
        self._lock = threading.Lock()
        self.spans = SpanLog()
        self._t_start = time.monotonic()         # uptime_s in snapshot
        # live-state gauge provider: a callable returning a dict of point-
        # in-time gauges (queue depths, in-flight, pending futures, server
        # state).  The owning server registers it; ``snapshot`` calls it
        # OUTSIDE this metrics lock — the provider reads structures that
        # carry their own locks, so /healthz and /metrics serve counters
        # AND gauges from one snapshot without any new locking here.
        self._gauges = None
        self._lat = deque(maxlen=reservoir)      # seconds, per request
        self._lane_reservoir = lane_reservoir
        self._lanes: dict[str, dict] = {}        # label -> {lat, completed}
        # replica lanes: "net/r<idx>" -> same stats, one per data-axis
        # replica of a striped entry (repro.core.executor.ReplicaSet)
        self._replica_lanes: dict[str, dict] = {}
        self.submitted = 0
        self.completed = 0
        self.failed = 0
        self.batches = 0
        self.deadline_flushes = 0                # flushed by max-wait timer
        self.size_flushes = 0                    # flushed by a full bucket
        self.padded_slots = 0                    # bucket slots wasted on pad
        self.recompiles = 0                      # stale-engine recoveries
        self.swaps = 0                           # prepared-param hot-swaps
        self.shed = 0                            # Overloaded rejections
        self.bad_requests = 0                    # malformed wire bodies (400)
        self.retries = 0                         # dispatch-failure requeues
        self.deadline_exceeded = 0               # per-request deadline misses
        self.errors = 0                          # unexpected loop errors
        self.failovers = 0                       # hybrid -> GPU-only swaps
        self.recoveries = 0                      # GPU-only -> hybrid swaps
        self.probes_ok = 0                       # half-open probes that passed
        self.probes_failed = 0                   # half-open probes that failed
        self.straggler_events = 0                # watchdog budget overruns
        self.backup_dispatches = 0               # straggler backup launches
        self.cross_replica_backups = 0           # backups on another replica
        self.ema_updates = 0                     # online EMA scale refinements
        self.drain_flushed = 0                   # batches served during drain
        self.drain_aborted = 0                   # requests Shutdown-rejected
        self.measured_batches = 0                # timed replan sample batches
        self.replans = 0                         # plan hot-migrations served
        self.breaker_states: dict[str, str] = {}  # network -> breaker state
        self.fitted_scales: dict[str, dict] = {}  # network -> fitted coeffs

    def record_submit(self, n: int = 1):
        with self._lock:
            self.submitted += n

    def record_batch(self, n_real: int, bucket: int, latencies,
                     by_deadline: bool, lane: str | None = None,
                     replica: str | None = None):
        with self._lock:
            self.batches += 1
            self.completed += n_real
            self.padded_slots += bucket - n_real
            if by_deadline:
                self.deadline_flushes += 1
            else:
                self.size_flushes += 1
            self._lat.extend(latencies)
            for label, lanes in ((lane, self._lanes),
                                 (replica, self._replica_lanes)):
                if label is None:
                    continue
                st = lanes.setdefault(
                    label, {"lat": deque(maxlen=self._lane_reservoir),
                            "completed": 0, "batches": 0})
                st["lat"].extend(latencies)
                st["completed"] += n_real
                st["batches"] += 1

    def record_failure(self, n: int = 1):
        with self._lock:
            self.failed += n

    def record_recompile(self):
        with self._lock:
            self.recompiles += 1

    def record_swap(self):
        with self._lock:
            self.swaps += 1

    def count(self, name: str, n: int = 1):
        """Increment one of the failure-state counters by attribute name
        (``shed``, ``retries``, ``failovers``, ...)."""
        with self._lock:
            setattr(self, name, getattr(self, name) + n)

    def set_breaker(self, network: str, state: str):
        with self._lock:
            self.breaker_states[network] = state

    def set_fitted(self, network: str, scales: dict):
        """Record the replanner's latest fitted cost coefficients."""
        with self._lock:
            self.fitted_scales[network] = dict(scales)

    def set_gauge_provider(self, fn) -> None:
        """Register the live-state gauge callable (see ``__init__``)."""
        self._gauges = fn

    def snapshot(self) -> dict:
        with self._lock:
            lat = list(self._lat)
            lanes = {label: (list(st["lat"]), st["completed"], st["batches"])
                     for label, st in self._lanes.items()}
            replicas = {label: (list(st["lat"]), st["completed"],
                                st["batches"])
                        for label, st in self._replica_lanes.items()}
            out = {
                "submitted": self.submitted,
                "completed": self.completed,
                "failed": self.failed,
                "batches": self.batches,
                "deadline_flushes": self.deadline_flushes,
                "size_flushes": self.size_flushes,
                "padded_slots": self.padded_slots,
                "recompiles": self.recompiles,
                "swaps": self.swaps,
                "shed": self.shed,
                "bad_requests": self.bad_requests,
                "retries": self.retries,
                "deadline_exceeded": self.deadline_exceeded,
                "errors": self.errors,
                "failovers": self.failovers,
                "recoveries": self.recoveries,
                "probes_ok": self.probes_ok,
                "probes_failed": self.probes_failed,
                "straggler_events": self.straggler_events,
                "backup_dispatches": self.backup_dispatches,
                "cross_replica_backups": self.cross_replica_backups,
                "ema_updates": self.ema_updates,
                "drain_flushed": self.drain_flushed,
                "drain_aborted": self.drain_aborted,
                "measured_batches": self.measured_batches,
                "replans": self.replans,
                "breakers": dict(self.breaker_states),
                "fitted": {k: dict(v)
                           for k, v in self.fitted_scales.items()},
                "uptime_s": time.monotonic() - self._t_start,
            }
        # gauges are read outside the lock: the provider's structures
        # (batcher, pending registry) carry their own synchronization
        gauges = {}
        if self._gauges is not None:
            try:
                gauges = dict(self._gauges() or {})
            except Exception:       # a mid-shutdown provider never breaks
                gauges = {}         # a health probe
        out["gauges"] = gauges
        out["p50_ms"] = percentile(lat, 50) * 1e3 if lat else float("nan")
        out["p99_ms"] = percentile(lat, 99) * 1e3 if lat else float("nan")
        out["lanes"] = {
            label: {"completed": completed, "batches": batches,
                    "p50_ms": percentile(ls, 50) * 1e3,
                    "p99_ms": percentile(ls, 99) * 1e3}
            for label, (ls, completed, batches) in lanes.items()}
        out["replicas"] = {
            label: {"completed": completed, "batches": batches,
                    "p50_ms": percentile(ls, 50) * 1e3,
                    "p99_ms": percentile(ls, 99) * 1e3}
            for label, (ls, completed, batches) in replicas.items()}
        return out
