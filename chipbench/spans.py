"""The program's span log, read over the traced window and put on the
device trace's clock.

The served program records spans (``repro.serving.metrics.SpanLog``) as
``(name, t0_ns, t1_ns, id, parent, thread, replica)`` on
``time.monotonic_ns()``.  The harness reads that clock right before and
right after each of the two marker runs (``trace.MARKER``) that open and
close the window.  Each marker's run on a device lies inside its host
bracket, so each bracket bounds the offset from that device's clock to the
host's; ``offset`` takes the midpoint of what both brackets allow.

``Spans`` keeps the records of one window and reads from them:

- the per-layer metrics ``door_ms``, ``queue_ms`` and ``batch_host_ms``,
  over spans that start inside the window;
- ``label_gaps``: each of the trace's longest idle gaps named by what the
  host was doing in it.
"""
from __future__ import annotations

import statistics

from chipbench.trace import MARKER, MODULES_LINE

DOOR = ("door.read", "door.decode", "door.submit", "door.encode",
        "door.write")
# what the drain thread does, each span on its own (``server.batch`` is
# their parent and names nothing more)
DRAIN = ("batcher.wait", "server.pad", "server.dispatch",
         "server.device_wait", "server.debatch")
BATCH_HOST = ("server.pad", "server.dispatch", "server.debatch")
# a gap less than this share of which the drain thread's spans cover stays
# unattributed
COVER = 0.5


def marker_runs(plane: dict) -> list[tuple[float, float]]:
    """``(start, end)`` of each run of the marker program on a device, in
    order, on the device's clock."""
    return sorted((s, s + d) for n, s, d in plane.get(MODULES_LINE, [])
                  if n.startswith(f"jit_{MARKER}"))


def offset_bounds(plane: dict, brackets) -> list[tuple[float, float]]:
    """For the first and the last marker run on the device, the range of
    offsets (host clock minus device clock) under which that run lies
    inside its host bracket ``(before_ns, after_ns)``."""
    runs = marker_runs(plane)
    return [(a - s, b - e)
            for (a, b), (s, e) in zip(brackets, (runs[0], runs[-1]))]


def offset(plane: dict, brackets) -> tuple[float, float] | None:
    """(offset, half-width) in ns from the device's clock to the host's:
    the midpoint of the offsets both markers allow.  None where they allow
    none in common."""
    bounds = offset_bounds(plane, brackets)
    lo = max(b[0] for b in bounds)
    hi = min(b[1] for b in bounds)
    if lo > hi:
        return None
    return (lo + hi) / 2, (hi - lo) / 2


def _overlap(spans, a: float, b: float) -> float:
    """How much of ``[a, b]`` the union of ``spans`` covers."""
    cut = sorted((max(a, s[1]), min(b, s[2])) for s in spans
                 if s[1] < b and s[2] > a)
    total, edge = 0.0, a
    for s, e in cut:
        s = max(s, edge)
        if e > s:
            total += e - s
            edge = e
    return total


def _most(spans, a: float, b: float, key) -> tuple[str | None, float]:
    """The ``key`` of the spans that cover most of ``[a, b]``, with how
    much they cover."""
    groups: dict = {}
    for s in spans:
        if s[1] < b and s[2] > a:
            groups.setdefault(key(s), []).append(s)
    best = max(groups, default=None,
               key=lambda k: _overlap(groups[k], a, b))
    return best, (0.0 if best is None else _overlap(groups[best], a, b))


def _drain_label(span) -> str:
    """A drain-thread span's name, with its replica where it has one."""
    return span[0] if span[6] < 0 else f"{span[0]}@r{span[6]}"


class Spans:
    """The records of one window, with the host brackets of its markers."""

    def __init__(self, records, brackets):
        self.records = [tuple(r) for r in records]
        self.brackets = [tuple(b) for b in brackets]
        # the window on the host's clock: after the opening marker's run,
        # before the closing one's
        self.window = (self.brackets[0][1], self.brackets[1][0])

    def inside(self, names) -> list:
        """The records of ``names`` that start inside the window."""
        a, b = self.window
        return [r for r in self.records if r[0] in names and a <= r[1] < b]

    @staticmethod
    def _median_ms(values) -> float | None:
        return statistics.median(values) * 1e-6 if values else None

    def door_ms(self) -> float | None:
        """Median over requests of the sum of the request's five door
        spans, for requests with all five inside the window."""
        per: dict = {}
        for r in self.inside(DOOR):
            per.setdefault(r[3], {})[r[0]] = r[2] - r[1]
        return self._median_ms([sum(d.values()) for d in per.values()
                                if len(d) == len(DOOR)])

    def queue_ms(self) -> float | None:
        """Median time a request waited in the batcher's queue."""
        return self._median_ms([r[2] - r[1]
                                for r in self.inside(("batcher.queue",))])

    def batch_host_ms(self) -> float | None:
        """Median over batches of the drain thread's host work on the
        batch: padding, dispatch and de-batching."""
        per: dict = {}
        for r in self.inside(BATCH_HOST):
            per.setdefault(r[3], {})[r[0]] = r[2] - r[1]
        return self._median_ms([sum(d.values()) for d in per.values()
                                if len(d) == len(BATCH_HOST)])

    def offsets(self, trace) -> dict:
        """(offset, half-width) in ns for each plane of ``trace``, or None
        where its markers disagree."""
        return {p: offset(plane, self.brackets)
                for p, plane in trace.planes.items()}

    def label(self, a: float, b: float, replica: int | None = None) -> str:
        """What the host was doing over ``[a, b]`` (host clock): the drain
        thread's span covering most of it; where that is ``batcher.wait``
        and a door span covers at least ``COVER`` of it, that door span
        too, as ``batcher.wait<door.read``; ``unattributed`` where the
        drain thread's spans cover less than ``COVER`` of it.  ``replica``
        is the device's replica, which a multi-replica label begins
        with."""
        drain = [r for r in self.records if r[0] in DRAIN]
        if _overlap(drain, a, b) < COVER * (b - a):
            name = "unattributed"
        else:
            name, _ = _most(drain, a, b, _drain_label)
            if name == "batcher.wait":
                door, covered = _most([r for r in self.records
                                       if r[0] in DOOR], a, b,
                                      lambda r: r[0])
                if covered >= COVER * (b - a):
                    name = f"{name}<{door}"
        return name if replica is None else f"r{replica}: {name}"

    def label_gaps(self, trace, replica_of: dict | None = None,
                   top: int = 10) -> list[list]:
        """The ``top`` longest idle gaps over every plane of ``trace``, as
        ``[label, seconds]``, longest first.  ``replica_of`` maps a plane
        to the replica on its device, where there are several."""
        offsets = self.offsets(trace)
        gaps = []
        for p in trace.planes:
            if offsets[p] is None:
                continue
            off = offsets[p][0]
            edge, end = trace._window(p)
            for s, e in [*trace._busy(p), (end, end)]:
                if s > edge:
                    gaps.append((s - edge, p, edge + off, s + off))
                edge = max(edge, e)
        gaps.sort(key=lambda g: g[0], reverse=True)
        return [[self.label(a, b, (replica_of or {}).get(p)), g * 1e-9]
                for g, p, a, b in gaps[:top]]
