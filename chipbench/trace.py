"""Reduction of a profiler trace to what the per-layer metrics read.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps,
for each device plane, the events of its op and module lines as
``[name, start_ns, duration_ns]``.  ``Trace`` reduces those over the traced
window: busy time as the union of op intervals, idle gaps, time by op name,
a kernel's time, and the durations of one jitted program.  The tests run the
reduction on a small trace recorded on a TPU v5e.
"""
from __future__ import annotations

import glob
import os
import statistics
from collections import Counter

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# the tiny program the harness runs on each device as the window opens and
# as it closes: the window on a device's clock lies between its two runs
MARKER = "chipbench_window"


def short(name: str) -> str:
    """An op event's name as ``<instruction> <result type>``: the trace
    names an op by its whole HLO text (``%int8_gemm.8 = f32[6400,48]{..}
    custom-call(...)``)."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name
    return f"{head.lstrip('%')} {rest.split('{')[0].split(' ')[0]}"


def extract(trace_dir: str) -> dict:
    """``{plane: {"window": [start_ns, end_ns], line: [[name, start_ns,
    dur_ns], ...]}}`` for the device planes of the one trace under
    ``trace_dir``; the window runs from the end of the first marker run to
    the start of the last."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, found "
                           f"{paths}")
    data = ProfileData.from_file(paths[0])
    planes = {}
    for plane in data.planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {}
        for line in plane.lines:
            if line.name in (OPS_LINE, MODULES_LINE):
                lines[line.name] = [[short(ev.name), float(ev.start_ns),
                                     float(ev.duration_ns)]
                                    for ev in line.events]
        marks = sorted((s, d) for n, s, d in lines.get(MODULES_LINE, [])
                       if n.startswith(f"jit_{MARKER}"))
        if len(marks) >= 2:
            lines["window"] = [marks[0][0] + marks[0][1], marks[-1][0]]
            planes[plane.name] = lines
    return planes


def merge(intervals) -> list[tuple[float, float]]:
    """The union of ``(start, end)`` intervals, as disjoint sorted ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


class Trace:
    """The device planes of one traced window, each with the window on its
    own clock, from what ``extract`` returns.  A device without a window
    (no plane at all, as on a CPU) reads as empty."""

    def __init__(self, planes: dict):
        self.planes = planes

    def _window(self, plane: str) -> tuple[float, float]:
        a, b = self.planes[plane]["window"]
        return float(a), float(b)

    def _ops(self, plane: str):
        """The ops that start inside the plane's window."""
        a, b = self._window(plane)
        return [e for e in self.planes[plane].get(OPS_LINE, [])
                if a <= e[1] < b]

    def _busy(self, plane: str) -> list[tuple[float, float]]:
        a, b = self._window(plane)
        return merge((max(a, s), min(b, s + d))
                     for _, s, d in self.planes[plane].get(OPS_LINE, [])
                     if s < b and s + d > a)

    @property
    def window_s(self) -> float:
        """The window's length, averaged over devices."""
        if not self.planes:
            return 0.0
        return statistics.fmean(b - a for a, b in map(
            self._window, self.planes)) * 1e-9

    def busy_s(self) -> float:
        """Seconds in which an op ran on a device, averaged over devices."""
        if not self.planes:
            return 0.0
        return statistics.fmean(sum(e - s for s, e in self._busy(p))
                                for p in self.planes) * 1e-9

    def idle_share(self) -> float | None:
        if not self.planes:
            return None
        return 1.0 - self.busy_s() / self.window_s

    def idle_gaps(self, top: int = 10) -> list[float]:
        """The longest stretches, in seconds, with no op on a device."""
        gaps = []
        for p in self.planes:
            edge, end = self._window(p)
            for s, e in self._busy(p):
                gaps.append(s - edge)
                edge = e
            gaps.append(end - edge)
        return [g * 1e-9 for g in sorted(gaps, reverse=True)[:top] if g > 0]

    def op_seconds(self) -> Counter:
        """Device seconds by op name, summed over devices."""
        out: Counter = Counter()
        for p in self.planes:
            for name, _, d in self._ops(p):
                out[name] += d * 1e-9
        return out

    def kernel_s(self, kernel: str) -> float:
        """Device seconds of the ops named after a kernel (the instruction
        ``kernel`` or ``kernel.<n>``), summed over devices."""
        return sum(t for name, t in self.op_seconds().items()
                   if name.split(" ")[0].split(".")[0] == kernel)

    def module_s(self, prefix: str) -> list[float]:
        """Durations in seconds of each run of the jitted programs whose
        name starts with ``prefix``, on every device."""
        out = []
        for p in self.planes:
            a, b = self._window(p)
            out += [d * 1e-9 for name, s, d in
                    self.planes[p].get(MODULES_LINE, [])
                    if name.startswith(prefix) and a <= s < b]
        return out

    def breakdown(self, top: int = 10) -> dict:
        """The ops that took most device time and the longest idle gaps.
        The program has no spans yet, so a gap is not attributed to what
        the host was doing in it."""
        return {"device_ops": [[n, t] for n, t in
                               self.op_seconds().most_common(top)],
                "idle_gaps": [["unattributed", g]
                              for g in self.idle_gaps(top)]}
