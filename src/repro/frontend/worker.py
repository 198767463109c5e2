"""Worker process entrypoint: one ``HeteroServer`` + front door per OS
process, shared-nothing.

Each worker owns its own compiled-plan residency: the spec (a plain JSON
dict) names the networks to register, and ``build_server`` compiles,
prepares and bucket-warms them inside THIS process — nothing is shared
with siblings, so a worker crash takes down exactly one plan residency
and a respawned worker re-registers from the same spec (crash-resume is
"re-run the registration", not state recovery).  Parameters are
deterministic per spec (``init_network`` under the spec's seed), so every
worker spawned from one spec serves bit-identical rows — the property
that lets the router retry a request on a DIFFERENT worker without
changing its answer.

Spec schema (everything optional but ``networks``):

    {"networks": [{"kind": "zoo",  "name": "mobilenetv2", "res": [32, 32],
                   "seed": 0, "buckets": [1, 4, 8], "pipelined": false,
                   "paper_faithful": true, "replicas": 1, "as": "mbv2"},
                  {"kind": "fire", "name": "tiny", "hw": [8, 8],
                   "c_in": 16, "squeeze": 4, "expand": 8, "seed": 0}],
     "server":  {"max_wait_ms": 2.0, "max_queue": 64, "in_flight": 1},
     "door":    {"rate": null, "burst": 64, "max_pending": null,
                 "weights": {"0": 3.0, "1": 1.0}},
     "http":    {"idle_timeout_s": 30.0, "conn_inflight": 8},
     "host": "127.0.0.1", "port": 0, "drain_budget_s": 10.0}

Run: ``python -m repro.frontend.worker --spec '<json>'``.  The process
prints ``READY host=<h> port=<p> pid=<pid>`` on stdout once the door is
listening (the supervisor's startup handshake), serves until SIGTERM (or
a ``POST /drain``), gracefully drains — fence, flush, resolve every
admitted future, PR-6 semantics — and exits 0.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import signal
import sys

from repro.frontend.app import DRAIN_BUDGET_S, FrontDoor, LocalBackend
from repro.runtime.compile_cache import enable_compile_cache


def _build_mods(net: dict):
    kind = net.get("kind", "zoo")
    if kind == "zoo":
        from repro.core.graph import NETWORKS
        return NETWORKS[net["name"]]()
    if kind == "fire":
        # the test-suite workload: one tiny fire module, compiles in
        # seconds — keeps multi-process tests CI-budgetable
        from repro.core.graph import fire
        hw = net.get("hw", [8, 8])
        return [fire(net.get("name", "tiny"), int(hw[0]),
                     int(net.get("c_in", 16)), int(net.get("squeeze", 4)),
                     int(net.get("expand", 8)))]
    raise ValueError(f"unknown network kind {kind!r}")


def _register_name(net: dict) -> str:
    return net.get("as") or net.get("name") or "net"


def build_server(spec: dict):
    """Compile/prepare/warm every network in ``spec`` into a started
    ``HeteroServer`` — the one code path both worker processes and the
    router's in-process workers build from, so a crash-resume respawn
    reconstructs exactly the residency the dead worker had."""
    import jax

    from repro.core.hetero import init_network
    from repro.core.partitioner import partition_network
    from repro.serving import HeteroServer

    server = HeteroServer(**spec.get("server", {}))
    for net in spec["networks"]:
        mods = _build_mods(net)
        plans = None
        if net.get("plans", "partitioned") == "partitioned":
            plans = partition_network(
                mods, paper_faithful=bool(net.get("paper_faithful", True)))
        params = init_network(mods, jax.random.PRNGKey(
            int(net.get("seed", 0))))
        hw = net.get("res") or net.get("hw") or [8, 8]
        kwargs = {}
        if net.get("buckets"):
            kwargs["buckets"] = tuple(net["buckets"])
        server.register(_register_name(net), mods, plans, params,
                        input_hw=tuple(int(v) for v in hw),
                        pipelined=bool(net.get("pipelined", False)),
                        replicas=int(net.get("replicas", 1)),
                        **kwargs)
    return server.start()


def make_door(spec: dict):
    """(FrontDoor, LocalBackend) for a spec — unstarted; the caller owns
    the event loop."""
    server = build_server(spec)
    backend = LocalBackend(
        server,
        drain_budget_s=float(spec.get("drain_budget_s", DRAIN_BUDGET_S)),
        **spec.get("door", {}))
    door = FrontDoor(backend, host=spec.get("host", "127.0.0.1"),
                     port=int(spec.get("port", 0)),
                     **spec.get("http", {}))
    return door, backend


async def _serve(spec: dict) -> int:
    door, backend = make_door(spec)
    await door.start()
    print(f"READY host={door.host} port={door.port} pid={os.getpid()}",
          flush=True)
    done = asyncio.Event()
    loop = asyncio.get_running_loop()

    def _term():
        if not backend.draining:
            asyncio.ensure_future(_drain())

    async def _drain():
        await door.drain_and_close()
        done.set()

    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            loop.add_signal_handler(sig, _term)
        except NotImplementedError:     # non-posix fallback
            signal.signal(sig, lambda *_: _term())
    # a POST /drain must also end the process: wake on the backend fence
    while not done.is_set():
        if backend.draining and backend._drain_result is not None:
            await door.aclose()
            break
        try:
            await asyncio.wait_for(done.wait(), 0.1)
        except asyncio.TimeoutError:
            pass
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="repro.frontend.worker")
    ap.add_argument("--spec", help="worker spec as a JSON string")
    ap.add_argument("--spec-file", help="worker spec as a JSON file path")
    args = ap.parse_args(argv)
    if not args.spec and not args.spec_file:
        ap.error("--spec or --spec-file is required")
    spec = (json.loads(args.spec) if args.spec
            else json.load(open(args.spec_file)))
    enable_compile_cache()
    return asyncio.run(_serve(spec))


if __name__ == "__main__":
    sys.exit(main())
