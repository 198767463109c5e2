#!/usr/bin/env python3
"""One run of one benchmark cell, as ``run.py`` makes it, with the served
program's span log on over the measured window.

    python3 chipbench/spanrun.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1> [--dump <path.json.gz>]

The log (``server.metrics.spans``) starts just before the marker program
runs as the window opens, and stops after it runs as the window closes;
``time.monotonic_ns()`` is read right before and right after each of those
two marker runs.  Everything else is ``run.py``'s, unchanged.

- ``--trace 1``: the spans and the two host brackets reach the per-layer
  metrics as ``run.spans`` (a ``spans.Spans``), which the metrics of
  ``SPAN_METRICS`` read beside the cell's own; the breakdown's idle gaps
  are labelled by what the host was doing in them (``Spans.label_gaps``),
  and each device's clock offset and its half-width go to stderr.
- ``--trace 0``: the end-to-end metrics with the log on, to set against
  ``run.py``'s with it off: the log's cost.
- ``--dump``: writes the reduced trace (``trace.extract``'s planes, the
  ops line merged where ops lie within ``MERGE_NS`` of each other), the
  spans, the brackets and each plane's replica, gzipped JSON, as the
  tests' recorded window holds them.
"""
from __future__ import annotations

import argparse
import gzip
import json
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parent / "src")]

from chipbench import run, spans as spanlib, trace  # noqa: E402
from chipbench.trace import OPS_LINE, merge  # noqa: E402

# The per-layer metrics read from the span log, as BENCHMARK.json entries.
SPAN_METRICS = [
    {"name": "door_ms.stream", "unit": "ms", "better": "lower",
     "source": "program_counter", "layer": "door",
     "moves": "latency_p95_ms", "workloads": ["mbv2.stream"]},
    {"name": "door_ms.saturate", "unit": "ms", "better": "lower",
     "source": "program_counter", "layer": "door",
     "moves": "throughput_img_s",
     "workloads": ["shfl.saturate", "mbv2.saturate", "mbv2.x4.saturate"]},
    {"name": "queue_ms.stream", "unit": "ms", "better": "lower",
     "source": "program_counter", "layer": "batcher",
     "moves": "latency_p95_ms", "workloads": ["mbv2.stream"]},
    {"name": "batch_host_ms.saturate", "unit": "ms", "better": "lower",
     "source": "program_counter", "layer": "engine (host side)",
     "moves": "throughput_img_s",
     "workloads": ["shfl.saturate", "mbv2.saturate", "mbv2.x4.saturate"]},
]
# Ops closer than this on a device are one busy stretch in a dump: the
# breakdown's gaps are milliseconds long, the ops' seams nanoseconds.
MERGE_NS = 1000.0


def replica_of(server) -> dict:
    """Device plane -> the replica on that device, for a network served
    by replicas; empty otherwise."""
    out = {}
    for name in server.networks():
        engine, _ = server.active(name)
        for r, s in enumerate(getattr(engine, "shardings", ())):
            for d in s.device_set:
                out[f"/device:TPU:{d.id}"] = r
    return out


class Window:
    """What one run's window gives the span reading: the server until the
    window closes, the brackets of the opening and closing marker runs,
    the spans, and each device plane's replica."""

    def __init__(self):
        self.server = None
        self.marks = 0
        self.brackets: list[tuple[int, int]] = []
        self.spans: spanlib.Spans | None = None
        self.planes: dict | None = None
        self.replica_of: dict = {}


def reduced(planes: dict) -> dict:
    """``planes`` with each ops line merged into busy stretches (``ops``),
    gaps under ``MERGE_NS`` closed."""
    out = {}
    for p, plane in planes.items():
        stretches: list[list[float]] = []
        for s, e in merge((s, s + d) for _, s, d in plane.get(OPS_LINE,
                                                                 [])):
            if stretches and s - stretches[-1][1] < MERGE_NS:
                stretches[-1][1] = e
            else:
                stretches.append([s, e])
        out[p] = {**plane,
                  OPS_LINE: [["ops", s, e - s] for s, e in stretches]}
    return out


def main(argv=None, bench_path: Path = run.ROOT / "BENCHMARK.json",
         require=run.require_devices) -> int:
    ap = argparse.ArgumentParser(prog="chipbench/spanrun.py",
                                 description=__doc__.split("\n")[0])
    ap.add_argument("--dump", help="write the window's reduced trace and "
                                   "spans here (gzipped JSON)")
    args, rest = ap.parse_known_args(argv)
    win = Window()
    from repro.frontend import worker

    build_server = worker.build_server
    mark = run.Markers.mark
    extract = trace.extract
    breakdown = trace.Trace.breakdown
    run_cls = run.Run

    def build(spec):
        win.server = build_server(spec)
        return win.server

    def bracketed_mark(self):
        win.marks += 1
        if win.marks not in (2, 3):         # the set-up run: unbracketed
            return mark(self)
        log = win.server.metrics.spans
        if win.marks == 2:
            log.drain()
            log.start()
        t0 = time.monotonic_ns()
        mark(self)
        win.brackets.append((t0, time.monotonic_ns()))
        if win.marks == 3:
            log.stop()
            win.spans = spanlib.Spans(log.drain(), win.brackets)
            win.replica_of = replica_of(win.server)
            # the run frees the server's device state before the reference
            win.server = None
            print(f"spanrun: {len(win.spans.records)} spans, "
                  f"{log.dropped} dropped; replicas by plane "
                  f"{win.replica_of}", file=sys.stderr)

    def kept_extract(trace_dir):
        win.planes = extract(trace_dir)
        return win.planes

    def labelled_breakdown(self, top: int = 10):
        out = breakdown(self, top)
        if win.spans is not None and self.planes:
            for p, off in win.spans.offsets(self).items():
                print(f"spanrun: {p} clock offset "
                      + ("none: the markers disagree" if off is None else
                         f"{off[0]:.0f} ns, half-width {off[1]:.0f} ns"),
                      file=sys.stderr)
            out["idle_gaps"] = win.spans.label_gaps(
                self, win.replica_of or None, top)
        return out

    class SpanRun(run_cls):
        """``run.Run`` with the window's spans."""

        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.spans = win.spans

    bench = json.loads(Path(bench_path).read_text())
    bench["per_layer"] = bench["per_layer"] + SPAN_METRICS
    with tempfile.TemporaryDirectory(prefix="chipbench-spanrun-") as tmp:
        path = Path(tmp) / "BENCHMARK.json"
        path.write_text(json.dumps(bench))
        worker.build_server = build
        run.Markers.mark = bracketed_mark
        trace.extract = kept_extract
        trace.Trace.breakdown = labelled_breakdown
        run.Run = SpanRun
        try:
            rc = run.main(rest, bench_path=path, require=require)
        finally:
            worker.build_server = build_server
            run.Markers.mark = mark
            trace.extract = extract
            trace.Trace.breakdown = breakdown
            run.Run = run_cls
    if args.dump and win.spans is not None:
        with gzip.open(args.dump, "wt") as f:
            json.dump({"planes": reduced(win.planes or {}),
                       "brackets": win.brackets,
                       "spans": win.spans.records,
                       "replica_of": win.replica_of}, f)
    return rc


if __name__ == "__main__":
    sys.exit(main())
