#!/usr/bin/env python3
"""Chip smoke: serve the three paper networks at 224x224 on a TPU through
the front door, and check every row that comes back.

    python chip_smoke.py              # one chip: squeezenet, mobilenetv2 and
                                      # shufflenetv2 (width 0.5), Pallas on
    python chip_smoke.py --chips 4    # four chips: mobilenetv2 striped over
                                      # 4 replicas against 1 replica

The path is the one a worker serves: ``repro.frontend.worker.build_server``
(graph -> partitioner -> passes -> compile_network -> HeteroServer) behind
``ServerThread(FrontDoor(LocalBackend(server)))``, with binary-framed
``POST /v1/infer`` requests.  Per network it checks that

  * the engine runs the Pallas kernels its plan implies (``int8_gemm``, and
    ``fused_chain`` for ShuffleNetV2) in its compiled program;
  * every served row is bit-identical to a batch-1 call of the same engine,
    and within rel < 8e-2, cos > 0.995 of both the interpreted hetero
    oracle and the plain float32 network, computed on the host CPU;
  * nothing hid the device: no failover, no error, no failed request, and
    every entry serves its primary plan behind a closed breaker.

Everything runs in this one process: a chip belongs to one process.
Without a TPU it exits 2 and prints no result.  The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import re
import sys
import time
from collections import Counter

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.graph import NETWORKS  # noqa: E402
from repro.core.hetero import init_network, run_network  # noqa: E402
from repro.core.partitioner import partition_network  # noqa: E402
from repro.frontend import FrontDoor, LocalBackend, ServerThread, wire  # noqa: E402
from repro.frontend.worker import build_server  # noqa: E402
from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402

NETS = ("squeezenet", "mobilenetv2", "shufflenetv2")
# Pallas kernels each network's paper-faithful plan puts on the chip
KERNELS = {"squeezenet": ("int8_gemm",), "mobilenetv2": ("int8_gemm",),
           "shufflenetv2": ("int8_gemm", "fused_chain")}
REL_BOUND, COS_BOUND = 8e-2, 0.995      # tests/test_executor.py bounds
BUCKETS = (1, 8)
RES = 224                               # the networks' published input
REQUESTS = 12                           # per network: some flush at batch 8
REQUESTS_4 = 32                         # four chips: a batch per replica
SEED = 0


class SmokeFailure(AssertionError):
    pass


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def make_spec(nets, res: int) -> dict:
    """Worker spec: each entry of ``nets`` is a network name or a dict of
    extra spec keys (``as``, ``replicas``) over {"name": ...}."""
    entries = []
    for net in nets:
        net = {"name": net} if isinstance(net, str) else dict(net)
        entries.append({"kind": "zoo", "res": [res, res], "seed": SEED,
                        "buckets": list(BUCKETS), **net})
    # a wait long enough for concurrent requests to share a batch-8 bucket
    return {"networks": entries, "server": {"max_wait_ms": 50.0}}


def images(n: int, res: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return (0.5 * rng.standard_normal((n, res, res, 3))).astype(np.float32)


def post_all(port: int, name: str, xs: np.ndarray) -> np.ndarray:
    """Send every image at once as binary ``POST /v1/infer``; the rows."""
    async def go():
        pool = wire.HttpPool("127.0.0.1", port, size=len(xs))

        async def one(x):
            body, headers = wire.infer_request(
                name, x, binary=True, accept=wire.TENSOR_CONTENT_TYPE)
            status, rh, raw = await pool.request(
                "POST", "/v1/infer", body=body, headers=headers,
                timeout=300.0)
            require(status == 200, f"{name}: HTTP {status}: {raw[:300]!r}")
            return wire.parse_client_body(rh, raw)
        try:
            return await asyncio.gather(*(one(x) for x in xs))
        finally:
            pool.close()
    return np.stack(asyncio.run(go()))


def pallas_calls(hlo_text: str) -> Counter:
    """Pallas kernels in a compiled TPU program, by ``pallas_call`` name."""
    return Counter(re.findall(
        r"%([A-Za-z_][A-Za-z0-9_]*?)(?:\.\d+)? = [^\n]*"
        r'custom_call_target="tpu_custom_call"', hlo_text))


def rel_cos(out: np.ndarray, ref: np.ndarray) -> tuple[float, float]:
    out = out.astype(np.float64)
    ref = ref.astype(np.float64)
    rel = np.linalg.norm(out - ref) / max(np.linalg.norm(ref), 1e-12)
    cos = np.sum(out * ref) / (np.linalg.norm(out) * np.linalg.norm(ref))
    return float(rel), float(cos)


def references(name: str, xs: np.ndarray):
    """(interpreted hetero oracle, plain float32 network) rows for ``xs``,
    computed on the host CPU from the seed: independent of the engine and
    of the chip."""
    with jax.default_device(jax.devices("cpu")[0]):
        mods = NETWORKS[name]()
        plans = partition_network(mods, paper_faithful=True)
        params = init_network(mods, jax.random.PRNGKey(SEED))
        oracle = run_network(mods, params, xs, plans)
        fp32 = run_network(mods, params, xs)
        return (np.asarray(oracle).reshape(len(xs), -1),
                np.asarray(fp32).reshape(len(xs), -1))


def serve_and_check(server, port: int, name: str, xs: np.ndarray, *,
                    net: str | None = None):
    """Serve ``xs`` to ``name`` over HTTP and check every row: bit-identical
    to a batch-1 call of the serving engine, and within the bounds of both
    CPU references (of network ``net``, default ``name``).  Returns
    (summary, rows)."""
    rows = post_all(port, name, xs)
    engine, prepared = server.active(name)
    batch1 = np.stack([np.asarray(engine(prepared, x[None].copy(),
                                         donate=True))[0] for x in xs])
    require(rows.shape == batch1.shape,
            f"{name}: rows {rows.shape} vs batch-1 {batch1.shape}")
    require(bool(np.all(np.isfinite(rows))), f"{name}: non-finite rows")
    diff = float(np.max(np.abs(rows - batch1)))
    require(rows.tobytes() == batch1.tobytes(),
            f"{name}: served rows differ from batch-1 calls "
            f"(max abs diff {diff})")
    lanes = server.metrics.snapshot()["lanes"]
    lane = [v for k, v in lanes.items() if k.startswith(f"{name}@")]
    require(bool(lane) and lane[0]["completed"] > lane[0]["batches"],
            f"{name}: no batch held more than one request ({lane})")
    oracle, fp32 = references(net or name, xs)
    out = {"network": name, "requests": len(xs),
           "batches": lane[0]["batches"],
           "max_abs_diff_vs_batch1": diff}
    for ref_name, ref in (("oracle", oracle), ("fp32", fp32)):
        rel, cos = rel_cos(rows, ref)
        require(rel < REL_BOUND and cos > COS_BOUND,
                f"{name}: vs {ref_name} rel={rel} cos={cos}")
        out[f"{ref_name}_rel"], out[f"{ref_name}_cos"] = rel, cos
    return out, rows


def check_nothing_hidden(server) -> None:
    """No failover, error or failed request; every entry primary, breaker
    closed."""
    snap = server.metrics.snapshot()
    for key in ("failovers", "errors", "failed", "retries", "shed",
                "bad_requests", "deadline_exceeded"):
        require(snap[key] == 0, f"metrics {key}={snap[key]}")
    for name, eng in server.stats()["engines"].items():
        require(eng["mode"] == "primary" and eng["breaker"] == "closed",
                f"{name}: mode={eng['mode']} breaker={eng['breaker']}")


def peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def one_chip() -> None:
    server = build_server(make_spec(NETS, RES))
    engines = server.stats()["engines"]
    with ServerThread(FrontDoor(LocalBackend(server))) as door:
        for i, name in enumerate(NETS):
            engine, prepared = server.active(name)
            require(engine.use_pallas is True, f"{name}: use_pallas off")
            calls = pallas_calls(engine.compiled_text(
                prepared, (BUCKETS[-1], RES, RES, 3)))
            for kernel in KERNELS[name]:
                require(calls[kernel] > 0,
                        f"{name}: no {kernel} kernel in HLO ({calls})")
            out, _rows = serve_and_check(
                server, door.port, name,
                images(REQUESTS, RES, SEED + 1 + i))
            print(json.dumps({**out,
                              "register_s": engines[name]["register_s"],
                              "pallas_calls": dict(calls),
                              "peak_bytes_in_use": peak_bytes()}),
                  flush=True)
    check_nothing_hidden(server)


def four_chips() -> None:
    """mobilenetv2 striped over 4 replicas against 1, in one server."""
    require(len(jax.devices()) >= 4, f"need 4 chips, "
            f"have {len(jax.devices())}")
    nets = [{"name": "mobilenetv2", "as": "r4", "replicas": 4},
            {"name": "mobilenetv2", "as": "r1"}]
    server = build_server(make_spec(nets, RES))
    xs = images(REQUESTS_4, RES, SEED + 1)
    with ServerThread(FrontDoor(LocalBackend(server))) as door:
        r1, rows1 = serve_and_check(server, door.port, "r1", xs,
                                    net="mobilenetv2")
        rows4 = post_all(door.port, "r4", xs)
    diff = float(np.max(np.abs(rows4 - rows1)))
    require(rows4.tobytes() == rows1.tobytes(),
            f"4-replica rows differ from 1-replica rows (max abs {diff})")
    _engine, prepared = server.active("r4")
    devices = [sorted({d.id for leaf in jax.tree.leaves(prepared[r].tree)
                       for d in leaf.devices()})
               for r in range(len(prepared))]
    require(len(devices) == 4 and all(len(d) == 1 for d in devices)
            and len({d[0] for d in devices}) == 4,
            f"replica trees not on 4 distinct devices: {devices}")
    served = {k: v["batches"]
              for k, v in server.metrics.snapshot()["replicas"].items()}
    require(sorted(served) == [f"r4/r{r}" for r in range(4)]
            and min(served.values()) >= 1,
            f"not every replica served a batch: {served}")
    check_nothing_hidden(server)
    print(json.dumps({"r1": r1, "r4_max_abs_diff_vs_r1": diff,
                      "replica_devices": devices,
                      "replica_batches": served,
                      "peak_bytes_in_use": peak_bytes()}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="chip_smoke.py",
                                 description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: no accelerator: {e}", file=sys.stderr)
        return 2
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {devices[0].platform}",
              file=sys.stderr)
        return 2
    enable_compile_cache()
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips()
    else:
        one_chip()
    print(f"# total {time.perf_counter() - t0:.1f}s", file=sys.stderr)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
