"""Multi-worker router: PR 6's circuit breaker lifted to the process level.

``Router`` fronts N shared-nothing workers (each its own ``HeteroServer``
residency — a separate OS process via ``ProcWorker``, or an in-process
``LocalWorker`` for CI-speed tests and benchmarks; both serve the same
``LocalBackend`` request semantics).  It implements the same backend
protocol as ``repro.frontend.app.LocalBackend``, so one ``FrontDoor``
serves either a single worker or a whole fleet.

**Dispatch.**  Least-outstanding among healthy workers (round-robin on
ties).  ``faults.trip("worker", device=<name>)`` fires per forward, so a
worker-path failure is injectable in CI like a device fault.  Since
protocol v2 each ``ProcWorker`` keeps a ``wire.HttpPool`` of persistent
keep-alive connections — forwards ride pooled sockets instead of paying
a dial per request — and a binary-framed request (``_tensor`` payload)
is forwarded as the SAME raw frame with its metadata in headers: the
router hop never transcodes an array, in either direction (a worker's
``application/x-tensor`` response passes through as opaque bytes).

**Retry.**  Exactly ONE re-issue, on a DIFFERENT worker, after a jittered
backoff — and only for failures where the first attempt definitely did
not answer: transport errors (connection refused/reset — the channel is
dead, at most the compute happened twice but the client is answered
once) and wire responses marked ``retryable`` (429/503 typed sheds — the
request was never admitted/served).  504s and other non-retryable codes
return as-is: re-issuing a possibly-still-running request could answer
it twice.

**Health.**  A probe loop GETs each worker's ``/healthz`` (backed by its
``ServerMetrics.snapshot()``): ``eject_after`` consecutive failures —
probe or live-dispatch transport failures alike — eject the worker from
rotation; while ejected, probes continue, and ``reinstate_after``
consecutive passes put it back (the breaker's closed/open/half-open
cycle, per process).  A dead process (``alive()`` False) is ejected
immediately and respawned from its spec — crash-resume re-REGISTERS the
networks (deterministic params per spec, so the respawn serves
bit-identical rows) and rejoins via the same probe-based reinstatement.

**Auto-scaling.**  Give the router a ``worker_factory`` (name -> new
worker) and ``scale_max``, and the probe loop sizes the fleet from the
queue-depth gauge each worker already reports on ``/healthz``
(``pending_requests + queue_total``, plus the router's own outstanding
count): mean depth per healthy worker >= ``scale_up_depth`` spawns a
worker (respecting ``scale_max``); mean depth <= ``scale_down_depth``
retires the least-loaded one down to ``scale_min`` (the starting fleet
size by default).  Retirement reuses the drain machinery — the worker
leaves rotation (state ``"retiring"``), its in-flight forwards settle,
it drains gracefully, THEN the process dies — and scale-ups reuse the
spec-respawn path, so a scaled-up worker serves bit-identical rows.
One scale operation runs at a time, off the probe loop, behind a
``scale_cooldown_s`` hysteresis.

**Admission.**  Weighted per-priority token buckets +
total-outstanding bound at the door, checked before the request body is
even read (``FrontDoor`` calls ``admit()`` between headers and body).

**Drain.**  ``drain()`` fences admission (typed 503 from then on), waits
for the router's own in-flight forwards to settle, then drains every
worker in parallel — each worker's ``HeteroServer.shutdown`` resolves
every admitted future (PR-6 contract) — all under one hard budget, so a
SIGTERM never hangs even with a wedged worker (it is killed at the
budget's edge).
"""
from __future__ import annotations

import asyncio
import json
import os
import random
import signal
import subprocess
import sys
import time

from repro.frontend import wire
from repro.frontend.app import (DRAIN_BUDGET_S, LocalBackend,
                                WeightedTokenBuckets)
from repro.runtime import faults
from repro.serving.errors import Shutdown

RETRYABLE_EXC = (ConnectionError, OSError, asyncio.TimeoutError,
                 asyncio.IncompleteReadError, faults.InjectedFault)


class LocalWorker:
    """An in-process worker: its own ``HeteroServer`` behind the same
    ``LocalBackend`` semantics a worker process serves, minus the socket.
    ``crash()`` emulates process death deterministically: dispatches
    raise ``ConnectionError``, and the orphaned server's admitted futures
    resolve typed via shutdown — exactly what a supervisor sees when a
    real worker dies mid-request."""

    def __init__(self, name: str, factory, *, door: dict | None = None):
        self.name = name
        self.factory = factory               # () -> started HeteroServer
        self._door_cfg = dict(door or {})
        self.server = factory()
        self.backend = LocalBackend(self.server, **self._door_cfg)
        self._dead = False
        self.outstanding = 0
        self.depth = 0                       # queue-depth gauge (probes)
        self.state = "healthy"       # router-managed: | ejected | retiring
        self.fails = 0
        self.oks = 0
        self.restarting = False
        self.restarts = 0

    def alive(self) -> bool:
        return not self._dead

    def crash(self) -> None:
        """Simulate the process dying NOW."""
        self._dead = True
        srv = self.server
        import threading
        threading.Thread(target=lambda: srv.shutdown(2.0),
                         daemon=True).start()

    async def restart(self) -> None:
        loop = asyncio.get_running_loop()
        self.server = await loop.run_in_executor(None, self.factory)
        self.backend = LocalBackend(self.server, **self._door_cfg)
        self._dead = False
        self.restarts += 1

    async def infer(self, payload: dict):
        if self._dead:
            raise ConnectionError(f"{self.name}: worker dead")
        shed = self.backend.admit(int(payload.get("priority", 1)))
        if shed is not None:
            return shed
        out = await self.backend.infer(payload)
        if self._dead:
            # died while serving: the socket would have reset before the
            # response left the process
            raise ConnectionError(f"{self.name}: worker died mid-request")
        return out

    async def healthz(self):
        if self._dead:
            raise ConnectionError(f"{self.name}: worker dead")
        return await self.backend.health()

    async def drain(self, budget_s: float) -> None:
        if not self._dead:
            await self.backend.drain(budget_s)

    def terminate(self) -> None:
        self.crash()


class ProcWorker:
    """A worker OS process (``python -m repro.frontend.worker``) plus the
    HTTP client half: spawn, READY handshake, pooled keep-alive requests
    (``wire.HttpPool`` — no dial per forward), SIGTERM drain, kill.
    ``restart()`` respawns from the same spec — the crash-resume path."""

    def __init__(self, name: str, spec: dict, *,
                 startup_timeout_s: float = 120.0,
                 request_timeout_s: float = 60.0,
                 probe_timeout_s: float = 5.0,
                 pool_size: int = 8):
        self.name = name
        self.spec = dict(spec)
        self.spec.setdefault("port", 0)
        self.startup_timeout_s = startup_timeout_s
        self.request_timeout_s = request_timeout_s
        self.probe_timeout_s = probe_timeout_s
        self.pool_size = pool_size
        self.proc: subprocess.Popen | None = None
        self.host = "127.0.0.1"
        self.port: int | None = None
        self.pool: wire.HttpPool | None = None
        self.outstanding = 0
        self.depth = 0
        self.state = "healthy"
        self.fails = 0
        self.oks = 0
        self.restarting = False
        self.restarts = 0

    # -- process lifecycle -------------------------------------------------

    def _spawn(self) -> None:
        env = dict(os.environ)
        src = os.path.join(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))))
        env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "repro.frontend.worker",
             "--spec", json.dumps(self.spec)],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            env=env, text=True)
        t_end = time.monotonic() + self.startup_timeout_s
        while time.monotonic() < t_end:
            line = self.proc.stdout.readline()
            if not line:
                break
            if line.startswith("READY"):
                fields = dict(kv.split("=", 1)
                              for kv in line.split()[1:] if "=" in kv)
                self.host = fields.get("host", "127.0.0.1")
                self.port = int(fields["port"])
                self.pool = wire.HttpPool(self.host, self.port,
                                          size=self.pool_size)
                return
        raise RuntimeError(f"{self.name}: worker never became READY")

    async def start(self) -> "ProcWorker":
        loop = asyncio.get_running_loop()
        await loop.run_in_executor(None, self._spawn)
        return self

    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None

    async def restart(self) -> None:
        if self.alive():
            self.terminate()
        if self.pool is not None:
            self.pool.close()           # stale sockets die with the corpse
        await self.start()
        self.restarts += 1

    def terminate(self) -> None:
        # the pool's sockets reset with the process; a later checkout
        # fails fast and feeds the ejection count — no cross-thread
        # transport close needed here
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(5.0)

    # -- request path ------------------------------------------------------

    def _pool(self) -> wire.HttpPool:
        if self.pool is None:
            raise ConnectionError(f"{self.name}: worker not started")
        return self.pool

    async def infer(self, payload: dict):
        """Forward one request on a pooled connection.  ``_tensor``
        payloads go out as the raw binary frame with metadata headers
        (no transcode); a worker's ``x-tensor`` response comes back as
        opaque bytes the door writes straight through."""
        if "_tensor" in payload:
            body = payload["_tensor"]
            headers = {"Content-Type": wire.TENSOR_CONTENT_TYPE,
                       "X-Network": str(payload.get("network", ""))}
            if "priority" in payload:
                headers["X-Priority"] = str(int(payload["priority"]))
            if payload.get("deadline_ms") is not None:
                headers["X-Deadline-Ms"] = \
                    f"{float(payload['deadline_ms']):g}"
        else:
            send = {k: v for k, v in payload.items()
                    if not k.startswith("_")}
            body = json.dumps(send).encode()
            headers = {"Content-Type": "application/json"}
        if payload.get("_accept"):
            headers["Accept"] = payload["_accept"]
        status, rheaders, raw = await self._pool().request(
            "POST", "/v1/infer", body=body, headers=headers,
            timeout=self.request_timeout_s)
        ctype = rheaders.get("content-type", "")
        if ctype.startswith(wire.TENSOR_CONTENT_TYPE):
            return status, raw, {"content-type": ctype,
                                 "x-network": rheaders.get("x-network", "")}
        return status, (json.loads(raw) if raw else None), dict(rheaders)

    async def healthz(self):
        status, _headers, raw = await self._pool().request(
            "GET", "/healthz", timeout=self.probe_timeout_s)
        return status, (json.loads(raw) if raw else None), {}

    async def drain(self, budget_s: float) -> None:
        """SIGTERM-initiated graceful drain; hard-kill at the budget."""
        if not self.alive():
            return
        self.proc.send_signal(signal.SIGTERM)
        loop = asyncio.get_running_loop()
        try:
            await asyncio.wait_for(
                loop.run_in_executor(None, self.proc.wait),
                budget_s)
        except asyncio.TimeoutError:
            self.terminate()
        if self.pool is not None:
            self.pool.close()


class Router:
    """Health-checked least-outstanding dispatch over a worker fleet.
    Implements the front-door backend protocol (``admit``/``infer``/
    ``health``/``metrics``/``drain``)."""

    def __init__(self, workers, *, rate: float | None = None,
                 burst: int = 64, weights: dict | None = None,
                 max_outstanding: int | None = None,
                 eject_after: int = 3, reinstate_after: int = 2,
                 probe_interval_s: float = 0.05,
                 probe_timeout_s: float = 2.0,
                 retry_backoff_s: float = 0.01,
                 auto_restart: bool = True,
                 worker_factory=None, scale_min: int | None = None,
                 scale_max: int | None = None,
                 scale_up_depth: float = 8.0,
                 scale_down_depth: float = 1.0,
                 scale_cooldown_s: float = 1.0,
                 drain_budget_s: float = DRAIN_BUDGET_S,
                 seed: int = 0):
        self.workers = list(workers)
        if not self.workers:
            raise ValueError("Router needs at least one worker")
        self.buckets = WeightedTokenBuckets(rate, burst, weights)
        self.max_outstanding = max_outstanding
        self.eject_after = max(1, int(eject_after))
        self.reinstate_after = max(1, int(reinstate_after))
        self.probe_interval_s = probe_interval_s
        self.probe_timeout_s = probe_timeout_s
        self.retry_backoff_s = retry_backoff_s
        self.auto_restart = auto_restart
        self.worker_factory = worker_factory
        self.scale_min = (len(self.workers) if scale_min is None
                          else max(1, int(scale_min)))
        self.scale_max = scale_max
        self.scale_up_depth = float(scale_up_depth)
        self.scale_down_depth = float(scale_down_depth)
        self.scale_cooldown_s = float(scale_cooldown_s)
        self.drain_budget_s = drain_budget_s
        self.draining = False
        self._rng = random.Random(seed)
        self._rr = 0                          # round-robin tiebreaker
        self._outstanding = 0
        self._probe_task: asyncio.Task | None = None
        self._scaling = False                 # one scale op at a time
        self._scale_task: asyncio.Task | None = None
        self._last_scale = time.monotonic()
        self._auto_seq = 0
        self.counters = {"dispatched": 0, "retries": 0, "sheds": 0,
                         "ejections": 0, "reinstatements": 0,
                         "restarts": 0, "no_worker": 0, "probes": 0,
                         "scale_ups": 0, "scale_downs": 0}

    # -- lifecycle ---------------------------------------------------------

    async def start(self) -> "Router":
        for w in self.workers:
            if isinstance(w, ProcWorker) and w.port is None:
                await w.start()
        self._probe_task = asyncio.ensure_future(self._probe_loop())
        return self

    async def aclose(self) -> None:
        for task in (self._probe_task, self._scale_task):
            if task is not None and not task.done():
                task.cancel()
                try:
                    await task
                except (asyncio.CancelledError, Exception):
                    pass
        self._probe_task = None
        self._scale_task = None

    # -- admission (pre-body) ----------------------------------------------

    def admit(self, priority: int = 1):
        if self.draining:
            return wire.error_reply(Shutdown("router draining: admission "
                                             "fenced"))
        if not self.buckets.admit(priority):
            self.counters["sheds"] += 1
            return wire.shed_reply(
                "rate", retry_after_s=self.buckets.retry_after_s(priority))
        if (self.max_outstanding is not None
                and self._outstanding >= self.max_outstanding):
            self.counters["sheds"] += 1
            return wire.shed_reply("outstanding")
        return None

    # -- dispatch ----------------------------------------------------------

    def _healthy(self, exclude=()):
        return [w for w in self.workers
                if w.state == "healthy" and w.alive() and w not in exclude]

    def _pick(self, exclude=()):
        pool = self._healthy(exclude)
        if not pool:
            return None
        lo = min(w.outstanding for w in pool)
        pool = [w for w in pool if w.outstanding == lo]
        self._rr += 1
        return pool[self._rr % len(pool)]

    async def _forward(self, w, payload: dict):
        """One attempt on one worker.  Transport failures come back as a
        typed retryable 503 (and feed the worker's ejection count) — the
        retry decision upstream only ever reads (status, body)."""
        w.outstanding += 1
        self._outstanding += 1
        try:
            faults.trip("worker", device=w.name)
            return await w.infer(payload)
        except RETRYABLE_EXC as e:
            self._record_failure(w)
            return 503, {"error": "worker_unreachable", "retryable": True,
                         "worker": w.name,
                         "message": f"{type(e).__name__}: {e}"}, {}
        finally:
            w.outstanding -= 1
            self._outstanding -= 1

    async def infer(self, payload: dict):
        self.counters["dispatched"] += 1
        w = self._pick()
        if w is None:
            self.counters["no_worker"] += 1
            return 503, {"error": "no_healthy_worker", "retryable": True,
                         "message": "every worker ejected or dead"}, {}
        status, body, headers = await self._forward(w, payload)
        if (status != 200 and wire.is_retryable(status, body)
                and not self.draining):
            w2 = self._pick(exclude=(w,))
            if w2 is not None:
                # ONE bounded retry, jittered so synchronized failures
                # don't re-converge on the same instant
                self.counters["retries"] += 1
                await asyncio.sleep(
                    self.retry_backoff_s * (0.5 + self._rng.random()))
                status, body, headers = await self._forward(w2, payload)
                if isinstance(body, dict):
                    body = dict(body)
                    body["retried"] = True
        return status, body, headers

    # -- health: probe loop, ejection, reinstatement, crash-resume ---------

    def _record_failure(self, w) -> None:
        w.oks = 0
        w.fails += 1
        if w.fails >= self.eject_after and w.state == "healthy":
            w.state = "ejected"
            self.counters["ejections"] += 1

    def _record_pass(self, w) -> None:
        w.fails = 0
        if w.state == "ejected":
            w.oks += 1
            if w.oks >= self.reinstate_after:
                w.state = "healthy"
                w.oks = 0
                self.counters["reinstatements"] += 1

    async def _probe_one(self, w) -> None:
        if w.state == "retiring":       # leaving anyway: don't respawn it
            return
        if not w.alive():
            self._record_failure(w)
            if w.state == "healthy":        # eject a corpse immediately
                w.state = "ejected"
                self.counters["ejections"] += 1
            if self.auto_restart and not w.restarting and not self.draining:
                w.restarting = True
                try:
                    await w.restart()
                    self.counters["restarts"] += 1
                except Exception:
                    pass                    # next probe tick tries again
                finally:
                    w.restarting = False
            return
        try:
            status, body, _h = await asyncio.wait_for(
                w.healthz(), self.probe_timeout_s)
            ok = status == 200 and bool((body or {}).get("ok", False))
            if isinstance(body, dict):
                # the autoscaler's signal: queued + admitted-not-served
                w.depth = (int(body.get("pending_requests", 0))
                           + int(body.get("queue_total", 0)))
        except Exception:
            ok = False
        self.counters["probes"] += 1
        if ok:
            self._record_pass(w)
        else:
            self._record_failure(w)

    async def _probe_loop(self) -> None:
        while not self.draining:
            await asyncio.gather(*(self._probe_one(w)
                                   for w in self.workers))
            self._autoscale_tick()
            await asyncio.sleep(self.probe_interval_s)

    # -- auto-scaling ------------------------------------------------------

    def autoscale_enabled(self) -> bool:
        return (self.worker_factory is not None
                and self.scale_max is not None)

    def _autoscale_tick(self) -> None:
        """Size the fleet from the queue-depth gauge.  Decisions are
        taken on the probe loop; the scale operation itself (spawn with
        its compile/warm time, or drain-and-retire) runs as its own task
        so probing — ejection detection — never stalls behind it."""
        if (not self.autoscale_enabled() or self._scaling
                or self.draining):
            return
        if time.monotonic() - self._last_scale < self.scale_cooldown_s:
            return
        healthy = self._healthy()
        if not healthy:
            return
        depth = (sum(w.depth + w.outstanding for w in healthy)
                 / len(healthy))
        n_live = len([w for w in self.workers if w.state != "retiring"])
        if depth >= self.scale_up_depth and n_live < self.scale_max:
            self._scaling = True
            self._scale_task = asyncio.ensure_future(self._scale_up())
        elif (depth <= self.scale_down_depth and n_live > self.scale_min
                and len(healthy) > 1):
            victim = min(healthy, key=lambda w: (w.outstanding, w.depth))
            self._scaling = True
            self._scale_task = asyncio.ensure_future(
                self._scale_down(victim))

    async def _scale_up(self) -> None:
        try:
            name = f"auto{self._auto_seq}"
            self._auto_seq += 1
            w = self.worker_factory(name)
            if isinstance(w, ProcWorker) and w.port is None:
                await w.start()         # spec-respawn path: bit-identical
            self.workers.append(w)      # join AFTER ready: never dispatch
            self.counters["scale_ups"] += 1     # to a half-started worker
        except Exception:
            pass                        # next tick may try again
        finally:
            self._last_scale = time.monotonic()
            self._scaling = False

    async def _scale_down(self, w) -> None:
        try:
            w.state = "retiring"        # out of rotation, probes skip it
            t_end = time.monotonic() + self.drain_budget_s
            while w.outstanding > 0 and time.monotonic() < t_end:
                await asyncio.sleep(0.01)
            try:                        # graceful: resolves admitted work
                await asyncio.wait_for(
                    w.drain(self.drain_budget_s), self.drain_budget_s + 1.0)
            except Exception:
                pass
            try:
                w.terminate()
            except Exception:
                pass
            if w in self.workers:
                self.workers.remove(w)
            self.counters["scale_downs"] += 1
        finally:
            self._last_scale = time.monotonic()
            self._scaling = False

    # -- observability -----------------------------------------------------

    def snapshot(self) -> dict:
        return {"counters": dict(self.counters),
                "draining": self.draining,
                "outstanding": self._outstanding,
                "n_workers": len(self.workers),
                "autoscale": {"enabled": self.autoscale_enabled(),
                              "min": self.scale_min, "max": self.scale_max},
                "workers": {w.name: {"state": w.state,
                                     "alive": w.alive(),
                                     "outstanding": w.outstanding,
                                     "depth": getattr(w, "depth", 0),
                                     "fails": w.fails, "oks": w.oks,
                                     "restarts": w.restarts}
                            for w in self.workers}}

    async def health(self):
        snap = self.snapshot()
        ok = not self.draining and bool(self._healthy())
        snap["ok"] = ok
        return (200 if ok else 503), snap, {}

    async def metrics(self):
        return 200, self.snapshot(), {}

    # -- drain -------------------------------------------------------------

    async def drain(self, budget_s: float | None = None):
        """Fence, settle, drain every worker in parallel, never hang."""
        budget = budget_s if budget_s is not None else self.drain_budget_s
        t0 = time.monotonic()
        self.draining = True                 # fence: admit() rejects now
        await self.aclose()                  # stop probing/respawn/scaling
        # settle the router's own in-flight forwards (they answer their
        # clients through the workers' own drains below)
        while self._outstanding > 0 and time.monotonic() - t0 < budget:
            await asyncio.sleep(0.005)
        remaining = max(0.5, budget - (time.monotonic() - t0))

        async def _drain_one(w):
            try:
                await asyncio.wait_for(w.drain(remaining), remaining + 1.0)
            except Exception:
                try:
                    w.terminate()           # budget's edge: hard stop
                except Exception:
                    pass

        await asyncio.gather(*(_drain_one(w) for w in self.workers))
        return 200, {"drained": True,
                     "elapsed_s": time.monotonic() - t0,
                     "outstanding": self._outstanding,
                     "counters": dict(self.counters)}, {}
