#!/usr/bin/env python3
"""One run of one benchmark cell on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell, its configuration (``chipbench/configs/<config>.json``) and its
traffic mix (``chipbench/traffic/<traffic>.json``) are found by name from
``BENCHMARK.json``.  This process holds the chips: it builds the served
network through the program's worker path (``build_server``), serves it
through ``FrontDoor(LocalBackend(...))`` on a loopback port, and drives it
from a load generator in a child process (``loadgen.py``) that imports no
JAX and runs on cores this process does not use.

``setup_s`` is the time from this process's start to the first request of
the warm-up load.  The load then runs ``warm_s`` seconds before the window
opens and until it closes.  With ``--trace 0`` the run prints the cell's
end-to-end metrics; with ``--trace 1`` it traces the window with the JAX
profiler and prints the cell's per-layer metrics, each read by
``metrics/<name>.py``.

``correct`` compares what the window served with the plain reference
(``reference/``, at the numerics the configuration states), run after the
server is shut down: every copy of an image's answer must be the same
bits, each image's answer must lie within ``REF_REL_LIMIT`` of the
reference's logits, and the answers must be float32 values, not bfloat16
ones widened (``BF16_SHARE_LIMIT``).  Without a TPU, or
with fewer chips than the cell asks for, the run exits 2 and prints no
result.  The last line of standard output is the result as one JSON object.
"""
from __future__ import annotations

import argparse
import base64
import gc
import importlib
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time
import traceback
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import loadgen  # noqa: E402

# Largest per-image relative error of a served answer against the
# reference, and the largest share of the answers' values that bfloat16
# holds exactly (PERF.md gives the readings each was set from).
REF_REL_LIMIT = 0.07
BF16_SHARE_LIMIT = 0.05
# Server counters read as deltas over the window.
COUNTERS = ("completed", "batches", "padded_slots", "size_flushes",
            "deadline_flushes", "shed", "failed", "straggler_events",
            "backup_dispatches")
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")
# How long the generator may take to get its last answers after the close.
ANSWER_GRACE_S = 60.0


class NoDevice(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def process_start() -> float:
    """``time.monotonic()`` at which this process started."""
    with open("/proc/self/stat") as f:
        stat = f.read()
    ticks = int(stat[stat.rindex(")") + 2:].split()[19])
    age = (time.clock_gettime(time.CLOCK_BOOTTIME)
           - ticks / os.sysconf("SC_CLK_TCK"))
    return time.monotonic() - age


def split_cores(cores) -> tuple[list[int], list[int]]:
    """(server's cores, generator's cores): disjoint, the generator's the
    smaller set, a quarter of them and at least one."""
    cores = sorted(cores)
    if len(cores) < 2:
        return cores, cores
    n_gen = max(1, len(cores) // 4)
    return cores[:-n_gen], cores[-n_gen:]


def require_devices(chips: int):
    """The first ``chips`` TPU devices; ``NoDevice`` where there are none."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoDevice(f"no accelerator: {e}") from e
    if devices[0].platform != "tpu":
        raise NoDevice(f"needs a TPU, found {devices[0].platform}")
    if len(devices) < chips:
        raise NoDevice(f"the cell needs {chips} chips, found "
                       f"{len(devices)}")
    return devices[:chips]


def peak_of(kind: str) -> dict:
    """The published peaks of one chip of ``kind``, from ``peaks.json``."""
    peaks = json.loads((HERE / "peaks.json").read_text())
    if kind not in peaks:
        raise SystemExit(f"run.py: no peaks for device kind {kind!r} in "
                         f"peaks.json")
    return peaks[kind]


def load_cell(bench: dict, name: str):
    """(cell, configuration, traffic) of the cell ``name``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(ROOT / conf["file"]) as f:
        cfg = json.load(f)
    with open(HERE / "traffic" / f"{cell['traffic']}.json") as f:
        traffic = json.load(f)
    return cell, cfg, traffic


def metrics_of(entries, cell: str) -> list[dict]:
    """The metrics among ``entries`` that ``cell`` reports."""
    return [m for m in entries if cell in m.get("workloads", [cell])]


def load_reader(name: str):
    """``read`` of ``metrics/<name>.py``; for a metric split by the cells
    it is read in (``<quantity>.<cells>``) that has no file of its own,
    ``read`` of ``metrics/<quantity>.py``."""
    path = HERE / "metrics" / f"{name}.py"
    if not path.exists():
        path = HERE / "metrics" / f"{name.split('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Run:
    """What a per-layer metric reads: the server's counters over the
    window, the reduced trace, the configuration and the chips' peaks."""

    def __init__(self, counters, trace, cfg, chips, peak):
        self.counters, self.trace, self.cfg = counters, trace, cfg
        self.chips, self.peak = chips, peak

    @staticmethod
    def cost(name: str, fallback: str | None = None):
        """``costs/<name>.py``, or ``costs/<fallback>.py`` where there is
        none."""
        if fallback and importlib.util.find_spec(
                f"chipbench.costs.{name}") is None:
            name = fallback
        return importlib.import_module(f"chipbench.costs.{name}")

    @staticmethod
    def note(line: str) -> None:
        print(line, file=sys.stderr)


class CompileCount:
    """Traces and compilations JAX reports while ``counting`` is on, each
    with the thread and the frames outside JAX that asked for it."""

    def __init__(self):
        import jax
        self.counting, self.seen = False, []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _secs, **_kw):
        if self.counting and event in COMPILE_EVENTS:
            frames = [f"{Path(f.filename).name}:{f.lineno} {f.name}"
                      for f in traceback.extract_stack()[:-1]
                      if "/jax/" not in f.filename][-6:]
            self.seen.append(f"{event} on {threading.current_thread().name}"
                             f": {' <- '.join(reversed(frames))}")


def chipbench_window(x):
    """The marker program (``trace.MARKER``) run on each device as the
    window opens and closes."""
    return x + 1


class Markers:
    """Runs the marker program on each of ``devices`` and waits for it:
    compiled here, in set-up, and run as the window opens and closes."""

    def __init__(self, devices):
        import jax
        self._jax = jax
        self._fn = jax.jit(chipbench_window)
        self._xs = [jax.device_put(np.zeros(8, np.float32), d)
                    for d in devices]
        self.mark()

    def mark(self) -> None:
        self._jax.block_until_ready([self._fn(x) for x in self._xs])


def enable_compile_cache() -> str:
    """JAX's persistent cache at ``JAX_COMPILATION_CACHE_DIR``, else at a
    fixed path in the checkout; every program goes in, however small."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def warm_served_path(server, name: str, shape, replicas: int) -> None:
    """Serve one batch of each bucket on each replica through the live path
    (``submit``), with images as the door hands them over.  The program's
    own warm-up calls each bucket with device arrays; the first live call
    with a host array traces the program again, which must not fall in the
    window."""
    for bucket in server.stats()["engines"][name]["buckets"]:
        for _ in range(2 * replicas):
            for fut in server.submit_many(
                    name, np.zeros((bucket, *shape), np.float32)):
                fut.result(timeout=120)


def sleep_until(t: float) -> None:
    while (left := t - time.monotonic()) > 0:
        time.sleep(min(left, 0.05))


def spawn_generator(job: dict) -> subprocess.Popen:
    env = dict(os.environ, OMP_NUM_THREADS="1", OPENBLAS_NUM_THREADS="1",
               MKL_NUM_THREADS="1")
    return subprocess.Popen(
        [sys.executable, str(HERE / "loadgen.py"), json.dumps(job)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, env=env)


def read_line(gen: subprocess.Popen) -> dict:
    line = gen.stdout.readline()
    if not line:
        raise RuntimeError(f"load generator ended (code {gen.wait()})")
    return json.loads(line)


def quantile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def window_records(records, traffic, t_open, t_close):
    """The requests that belong to the window: due in it (open loop) or
    answered in it (closed loop)."""
    col = 1 if traffic["loop"] == "open" else 3
    return [r for r in records if t_open <= r[col] < t_close]


def end_to_end(names, records, traffic, t_open, t_close, setup_s) -> dict:
    inside = window_records(records, traffic, t_open, t_close)
    out = {"setup_s": setup_s}
    if "throughput_img_s" in names:
        ok = sum(1 for r in inside if r[4] == 200)
        out["throughput_img_s"] = ok / (t_close - t_open)
    if "latency_p95_ms" in names:
        # a failed request misses any limit: it counts as never answered
        never = t_close + ANSWER_GRACE_S
        lat = [(r[3] if r[4] == 200 else never) - r[1] for r in inside]
        out["latency_p95_ms"] = quantile(lat, 95) * 1e3 if lat else None
    return {k: v for k, v in out.items() if k in names}


def bf16_exact_share(rows: np.ndarray) -> float:
    """Share of the float32 values in ``rows`` that bfloat16 holds exactly
    (their low 16 bits zero): about 2**-16 for answers computed and kept in
    float32, 1 for answers kept in bfloat16 and widened."""
    bits = np.ascontiguousarray(rows, np.float32).view(np.uint32)
    return float(np.mean((bits & 0xFFFF) == 0))


def check_answers(cfg, seed, traffic, shape, result) -> dict:
    """The numbers ``correct`` is decided by, each with its limit."""
    from chipbench.reference import Reference, rel_errors
    rows = {int(k): np.frombuffer(base64.b64decode(v), np.float32)
            for k, v in result["rows"].items()}
    served = sorted(rows)
    images = loadgen.make_images(seed, int(traffic["images"]), shape)
    ref = Reference(cfg, seed)(images[served])
    answers = np.stack([rows[i] for i in served])
    rel = rel_errors(answers, ref)
    copies = max(result["mismatch"].values(), default=0.0)
    unanswered = sum(1 for r in result["records"] if r[4] == 0)
    return {"ref_rel_max": {"value": float(rel.max()),
                            "limit": REF_REL_LIMIT},
            "bf16_exact_share": {"value": bf16_exact_share(answers),
                                 "limit": BF16_SHARE_LIMIT},
            "copy_diff_max": {"value": copies, "limit": 0.0},
            "unanswered": {"value": unanswered, "limit": 0},
            "images_checked": {"value": len(served), "limit": 1}}


def passed(checks: dict) -> bool:
    lower_is_better = ("ref_rel_max", "bf16_exact_share", "copy_diff_max",
                       "unanswered", "window_compiles")
    return all((c["value"] <= c["limit"]) if k in lower_is_better
               else (c["value"] >= c["limit"]) for k, c in checks.items())


def measure(args, bench, cell, cfg, traffic, gen, t_start, require) -> int:
    import jax
    try:
        devices = require(int(cell["chips"]))
    except NoDevice as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    kind = devices[0].device_kind
    peak = peak_of(kind)
    print(f"compile cache: {enable_compile_cache()}", file=sys.stderr)
    compiles = CompileCount()

    from chipbench.reference import weight_seed
    from repro.frontend import FrontDoor, LocalBackend, ServerThread
    from repro.frontend.worker import build_server

    serve = cfg["serve"]
    name = serve.get("as") or serve["name"]
    server = build_server({"networks": [
        {**serve, "seed": weight_seed(args.seed)}]})
    register_s = server.stats()["engines"][name]["register_s"]
    replicas = int(serve.get("replicas", 1))
    warm_served_path(server, name, [*serve["res"], 3], replicas)
    door = ServerThread(FrontDoor(LocalBackend(server))).start()
    markers = Markers(devices)
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") \
        if args.trace else None
    try:
        if not read_line(gen).get("ready"):
            raise RuntimeError("load generator not ready")
        t_go = time.monotonic()
        setup_s = t_go - t_start
        t_open = t_go + float(traffic["warm_s"])
        t_close = t_open + args.seconds
        gen.stdin.write(json.dumps({"port": door.port, "t_go": t_go,
                                    "t_close": t_close}) + "\n")
        gen.stdin.flush()
        sleep_until(t_open)
        if args.trace:
            # device ops only: host spans per Python call or per runtime
            # task would slow the served path they measure several times
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        markers.mark()
        snap0 = server.metrics.snapshot()
        compiles.counting = True
        sleep_until(t_close)
        compiles.counting = False
        snap1 = server.metrics.snapshot()
        markers.mark()
        if args.trace:
            jax.profiler.stop_trace()
        result = read_line(gen)
        memory_peak = max(int((d.memory_stats() or {}).get(
            "peak_bytes_in_use", 0)) for d in devices)
    finally:
        door.stop()
        server.shutdown()
    counters = {k: snap1[k] - snap0[k] for k in COUNTERS}
    serving = sum(1 for k, v in snap1["replicas"].items()
                  if v["batches"] > snap0["replicas"].get(
                      k, {"batches": 0})["batches"])
    print(f"register_s {register_s} setup_s {setup_s} window "
          f"{t_close - t_open} s; counters over the window {counters}",
          file=sys.stderr)
    records = result["records"]
    inside = window_records(records, traffic, t_open, t_close)
    failed = sum(1 for r in inside if r[4] != 200)
    if traffic["loop"] == "open":
        late = [r[2] - r[1] for r in inside]
        print(f"generator lateness p50 {quantile(late, 50) * 1e3} ms, p95 "
              f"{quantile(late, 95) * 1e3} ms over {len(late)} requests",
              file=sys.stderr)

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    out = {"attempted": len(inside), "failed": failed}
    # the program's state goes before the reference runs on the chip
    del door, server, markers
    gc.collect()
    if args.trace:
        from chipbench.trace import Trace, extract
        trace = Trace(extract(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        run = Run(counters, trace, cfg, int(cell["chips"]), peak)
        values = {m["name"]: (m, load_reader(m["name"])(run))
                  for m in metrics_of(bench["per_layer"], cell["name"])}
        device.update(busy_s=trace.busy_s(), window_s=trace.window_s)
        out["breakdown"] = trace.breakdown()
    else:
        entries = metrics_of(bench["end_to_end"], cell["name"])
        e2e = end_to_end({m["name"] for m in entries}, records, traffic,
                         t_open, t_close, setup_s)
        values = {m["name"]: (m, e2e.get(m["name"])) for m in entries}
    out["metrics"] = {k: {"value": v, "unit": m["unit"]}
                      for k, (m, v) in values.items() if v is not None}
    out["device"] = device

    shape = [*serve["res"], 3]
    for seen in compiles.seen:
        print(f"compiled in the window: {seen}", file=sys.stderr)
    checks = {"window_compiles": {"value": len(compiles.seen), "limit": 0},
              **check_answers(cfg, args.seed, traffic, shape, result)}
    if replicas > 1:
        # every replica served in the window, so the answers compared
        # came from each of them
        checks["replicas_serving"] = {"value": serving, "limit": replicas}
    for k, c in checks.items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    line = {"correct": passed(checks), **out, "checks": checks}
    print(json.dumps({k: line[k] for k in (
        "correct", "attempted", "failed", "metrics", "device",
        *(("breakdown",) if "breakdown" in line else ()), "checks")}))
    return 0


def main(argv=None, bench_path: Path = ROOT / "BENCHMARK.json",
         require=require_devices) -> int:
    t_start = process_start()
    ap = argparse.ArgumentParser(prog="chipbench/run.py",
                                 description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = json.loads(Path(bench_path).read_text())
    cell, cfg, traffic = load_cell(bench, args.workload)
    server_cores, gen_cores = split_cores(os.sched_getaffinity(0))
    os.sched_setaffinity(0, server_cores)
    print(f"cores: server {server_cores}, generator {gen_cores}",
          file=sys.stderr)
    gen = spawn_generator({"traffic": traffic, "seed": args.seed,
                           "network": cfg["serve"].get("as")
                           or cfg["serve"]["name"],
                           "shape": [*cfg["serve"]["res"], 3],
                           "cores": gen_cores})
    try:
        return measure(args, bench, cell, cfg, traffic, gen, t_start,
                       require)
    finally:
        if gen.poll() is None:
            gen.kill()
        gen.wait()


if __name__ == "__main__":
    sys.exit(main())
