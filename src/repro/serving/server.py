"""HeteroServer: batched multi-plan, multi-resolution QoS serving.

The deployment half of the paper's argument: per-layer FPGA-GPU gains only
matter if the serving loop preserves them.  ``HeteroServer`` keeps one
compiled engine per registered (modules, plans) pair resident — SqueezeNet,
MobileNetV2 and ShuffleNetV2 plans simultaneously, keyed by the PR-1 plan
signature — admits single-image requests into a multi-lane dynamic batcher,
and dispatches padded bucket-sized batches from a background drain thread.

    server = HeteroServer(buckets=(1, 4, 8, 32), max_wait_ms=2.0,
                          in_flight=4)
    server.register("mbv2", mods, plans, params,
                    input_hw=[(96, 96), (64, 64)])    # one lane set per res
    with server:                        # starts the drain loop
        fut = server.submit("mbv2", image)            # returns immediately
        hot = server.submit("mbv2", image, priority=0)   # deadline-critical
        logits = fut.result()                         # de-batched row

**Multi-resolution lanes.**  ``register(..., input_hw=...)`` accepts one
(H, W) or a list of them; every (network, resolution, priority) triple is
its own batching lane, so batches never mix input shapes and each
(resolution, bucket) pair is a separately warmed resident jit trace —
compiled programs for all registered resolutions stay resident
side-by-side.  ``submit`` infers the lane from the image's shape.

**Priority lanes.**  ``submit(..., priority=0)`` routes to the
deadline-critical lane: its deadline is a fraction (default 1/4) of the
bulk max-wait, so urgent requests preempt bulk traffic at flush time,
while deadline flushes stay earliest-deadline-first overall — the
starvation guard that keeps every bulk lane's wait bounded even under a
saturated high-priority lane (``repro.serving.batcher``).

**In-flight-aware admission.**  Deadline flushes are gated on downstream
occupancy: while ``in_flight`` batches are still unfinished, a partial
bucket would only queue behind them, so the batcher keeps accumulating
(up to a hard deadline) and flushes a fuller batch when a slot frees.
Full buckets are never deferred.

**Replica-striped dispatch** (PR 8).  ``register(..., replicas=R)`` (or
an explicit ``mesh=``) stripes one network's traffic across R data-axis
replicas of a device mesh: the parameters are prepared ONCE and a copy
is committed to each replica's devices under one shared generation stamp
(``repro.core.executor.ReplicaSet``); each flushed batch routes whole to
the least-outstanding replica (round-robin on ties), ``in_flight``
becomes a per-replica depth, metrics grow per-replica lanes, and the
straggler watchdog's backup dispatch fires on a DIFFERENT replica than
the straggling one.  ``swap_params``/plan migrations swap all replicas
atomically — no batch ever mixes parameter generations across replicas —
and every served row still bit-matches the single-device batch-1 oracle
(same program, same prepared tree; placement only moves it).

**Prepared-parameter hot-swap.**  ``swap_params(net, params)`` prepares
the new weights on a shadow handle (the expensive half, outside the
server lock; serialized against stale-engine recompiles)
and then atomically redirects dispatch to it — the queue is never
drained.  Batches already dispatched finish on the old parameter
generation; every batch flushed after the swap returns uses the new one
(``repro.core.executor.PreparedParams`` stamps the generation, and
``stats()``/``metrics`` record the swap).  Bit-match contract across a
swap: every served row equals a batch-1 engine call under exactly ONE
parameter generation — generations never mix inside a batch, and requests
submitted after ``swap_params`` returns are guaranteed the new one.

**Failure semantics** (the PR-6 fault-tolerance contract):

  * **Every future issued by ``submit`` resolves exactly once** — with a
    logits row, or with a typed error (``repro.serving.errors``).  There
    is no path on which an admitted request hangs: dispatch failures
    de-batch into one bounded head-of-lane retry and then reject;
    ``shutdown`` flushes the backlog and sweeps whatever is left with
    ``Shutdown``.
  * **Admission failures raise synchronously.**  ``submit`` on a server
    that is not running raises ``ServerClosed``; a lane at its
    queue-depth bound (``max_queue``) raises ``Overloaded``
    (reject-with-backpressure, never unbounded buffering).
  * **Per-request deadlines.**  ``submit(..., deadline_ms=...)``: a
    request whose deadline passes before its batch dispatches resolves
    with ``DeadlineExceeded`` instead of being served late.
  * **Degraded-mode failover.**  Each entry carries a circuit breaker
    over device-attributed dispatch failures: after
    ``breaker_threshold`` consecutive FPGA-attributed failures the
    server shadow-prepares the GPU-only plan for the same modules (the
    paper's all-GPU baseline), bucket-warms it, and atomically redirects
    live traffic to it — the ``swap_params`` mechanism generalized from
    weight swaps to plan swaps.  While failed over, half-open probe
    batches run on the hybrid plan every ``probe_interval_s``;
    ``recover_after`` consecutive passes swap traffic back.  Served rows
    always bit-match the batch-1 oracle of the plan that served them.
  * **Straggler defense.**  The completion loop polls each dispatched
    batch against a rolling budget (``straggler_factor`` x the entry's
    median completion, via ``repro.runtime.resilience.StragglerMonitor``);
    a batch past its budget counts a watchdog event and, for pipelined
    entries, races a backup monolithic dispatch of the same batch.
  * All of it is deterministic under ``repro.runtime.faults`` injection —
    no hardware fault required to exercise any path in CI.

**Online re-partitioning** (PR 7, ``repro.core.replan``).  Constructed
with ``replanner=Replanner(...)``, the server samples every
``measure_every``-th primary-mode batch through the engine's timed
dispatch (per-stage walls on pipelined entries), attributes the measured
times to the cost model's device/transfer coefficients, and re-fits them
over a sliding window.  When re-partitioning under the fitted model
predicts a latency win that clears the replanner's hysteresis (>= 15%
for >= K consecutive windows by default), the entry hot-migrates:
``_Entry.migrate`` is the breaker-failover shadow-prepare/atomic-redirect
generalized to ANY candidate plan set.  ``stats()['replan']`` carries the
fitted coefficients and migration log; rows served before and after a
migration each bit-match their own plan generation's batch-1 oracle.

Guarantees:
  * results are bit-identical to ``compile_network`` called one request at
    a time — the engine is batch-invariant, padding rows are inert, and
    neither donation, in-flight depth, lane, nor priority changes any
    computed value;
  * every (bucket, resolution) shape is compile-warmed at register time,
    so no live request pays a jit trace;
  * a ``clear_cache()`` in ``repro.core.executor`` does not break a live
    server: the drain loop notices the stale engine and transparently
    recompiles (counted in ``stats()['recompiles']``).

``register(..., pipelined=True)`` serves a network through the
stage-pipelined engine (``compile_pipelined``) instead of the monolithic
one — same bits, device hand-offs exposed for overlap.
"""
from __future__ import annotations

import queue
import threading
import time

import jax
import numpy as np

from repro.core.executor import (ReplicaSet, compile_network,
                                 compile_pipelined)
from repro.core.hetero import init_network
from repro.launch.mesh import make_production_mesh
from repro.core.replan import Replanner, carry_calibration
from repro.core.schedule import network_stage_components
from repro.runtime import faults
from repro.runtime.resilience import StragglerMonitor
from repro.serving.batcher import (DEFAULT_BUCKETS, DEFAULT_PRIORITY,
                                   DynamicBatcher, LaneKey, Request,
                                   pad_batch, pick_bucket)
from repro.serving.errors import (DeadlineExceeded, Overloaded, ServerClosed,
                                  Shutdown)
from repro.serving.metrics import ServerMetrics


def _normalize_resolutions(input_hw) -> tuple:
    """Accept a single (H, W) pair or an iterable of pairs."""
    hw = tuple(input_hw)
    if hw and all(isinstance(v, int) for v in hw):
        hw = (hw,)
    res = tuple(tuple(int(v) for v in r) for r in hw)
    if not res or any(len(r) != 2 for r in res):
        raise ValueError(f"input_hw must be (H, W) or a list of (H, W) "
                         f"pairs, got {input_hw!r}")
    if len(set(res)) != len(res):
        raise ValueError(f"duplicate resolutions in input_hw: {input_hw!r}")
    return res


def lane_label(lane: LaneKey) -> str:
    """Human-readable lane name for the metrics snapshot."""
    res = "x".join(str(v) for v in lane.res) if lane.res else "?"
    return f"{lane.network}@{res}/p{lane.priority}"


class _Breaker:
    """Per-network circuit breaker over FPGA-attributed dispatch failures.

    closed -> open after ``threshold`` consecutive failures on the
    primary (hybrid) plan; while open, half-open probe batches run on
    the primary every ``probe_interval_s`` and ``recover_after``
    consecutive passes close it again.  Not thread-safe on its own —
    all transitions happen on the drain thread."""

    def __init__(self, threshold: int = 3, probe_interval_s: float = 0.25,
                 recover_after: int = 2):
        self.threshold = max(1, int(threshold))
        self.probe_interval_s = probe_interval_s
        self.recover_after = max(1, int(recover_after))
        self.state = "closed"
        self.fails = 0              # consecutive primary failures
        self.oks = 0                # consecutive half-open probe passes
        self.last_probe = 0.0

    @property
    def label(self) -> str:
        if self.state == "open" and self.oks > 0:
            return "half_open"      # probing, partway to recovery
        return self.state

    def record_failure(self) -> bool:
        """True when this failure trips (or finds) the breaker open."""
        self.fails += 1
        if self.fails >= self.threshold:
            self.state = "open"
            self.oks = 0
        return self.state == "open"

    def record_success(self) -> None:
        self.fails = 0

    def probe_due(self, now: float) -> bool:
        return (self.state == "open"
                and now - self.last_probe >= self.probe_interval_s)

    def record_probe(self, ok: bool, now: float) -> bool:
        """True when this probe completes recovery (breaker closes)."""
        self.last_probe = now
        if not ok:
            self.oks = 0
            return False
        self.oks += 1
        if self.oks >= self.recover_after:
            self.state = "closed"
            self.fails = self.oks = 0
            return True
        return False


class _Entry:
    """One registered network: engine + prepared params + bucket policy +
    the set of admitted input resolutions + the fault-tolerance state
    (circuit breaker, GPU-only fallback variant, straggler monitor)."""

    def __init__(self, name, mods, plans, params, input_hw, buckets,
                 use_pallas, calib_x=None, pipelined=False,
                 breaker: _Breaker | None = None,
                 straggler_factor: float = 4.0,
                 replicas: int = 1, mesh=None,
                 ema_batches: int = 16, ema_alpha: float = 0.25):
        self.name = name
        self.mods = mods
        self.plans = plans
        self.params = params
        self.resolutions = _normalize_resolutions(input_hw)
        self.buckets = tuple(sorted(buckets))
        self.use_pallas = use_pallas
        self.calib_x = calib_x
        self.pipelined = pipelined
        # replica striping: an explicit mesh wins; replicas > 1 builds a
        # data-only mesh over the first ``replicas`` devices.  mesh=None,
        # replicas=1 keeps the raw engine — the pre-replication path,
        # byte for byte.
        self.mesh = mesh
        if self.mesh is None and int(replicas) > 1:
            self.mesh = make_production_mesh(shape=(int(replicas),))
        self._compile = compile_pipelined if pipelined else compile_network
        self.engine = self._wrap(
            self._compile(mods, plans, use_pallas=use_pallas))
        self.replicas = (self.engine.n_replicas
                         if isinstance(self.engine, ReplicaSet) else 1)
        if self.engine.needs_calibration and calib_x is None:
            raise ValueError(
                f"{name}: plans request calibration (Plan.calibrate=True) "
                f"— register(..., calib_x=batch) is required")
        self.prepared = self.engine.prepare(params, calib_x)
        # online EMA scale refinement budget (Plan.calibrate("ema")):
        # the first ``ema_batches`` primary batches each blend their
        # captured amplitudes into the frozen scales
        self.ema_left = (int(ema_batches)
                         if getattr(self.engine, "ema_modules", None) else 0)
        self.ema_alpha = float(ema_alpha)
        self.c_in = mods[0].nodes[0].spec.c_in
        # model-side stage decomposition of the LIVE plan set — aligned
        # 1:1 with the pipelined engine's executable stages, this is what
        # measured stage times are attributed against (repro.core.replan)
        self.stage_comps = network_stage_components(mods, plans)
        self.plan_generation = 0            # bumped by each replan migration
        self.measure_seq = 0                # batches since registration
        # serializes swap_params against refresh: a stale-engine recompile
        # must never finish AFTER a swap it started BEFORE and silently
        # revert the served parameters to the pre-swap generation
        self.swap_lock = threading.Lock()
        # failover state: "primary" serves the registered (hybrid) plans,
        # "fallback" the GPU-only plan for the same modules
        self.mode = "primary"
        self.fb_engine = None               # lazily compiled GPU-only plan
        self.fb_prepared = None
        self.bk_engine = None               # lazy monolithic straggler backup
        self.bk_prepared = None
        self.breaker = breaker or _Breaker()
        self.monitor = StragglerMonitor(threshold=straggler_factor)
        self._seq = 0
        self.register_s = 0.0       # compile + prepare + bucket warm-up

    def _wrap(self, eng):
        """Stripe an engine across this entry's mesh when replicated;
        single-replica entries keep the raw engine (the pre-replication
        serving path, byte for byte)."""
        return ReplicaSet(eng, self.mesh) if self.mesh is not None else eng

    def input_shape(self, batch: int, res: tuple | None = None) -> tuple:
        return (batch, *(res or self.resolutions[0]), self.c_in)

    def match_res(self, shape: tuple) -> tuple | None:
        """The registered resolution an (H, W, C) image shape belongs to."""
        for r in self.resolutions:
            if tuple(shape) == (*r, self.c_in):
                return r
        return None

    def next_seq(self) -> int:
        self._seq += 1
        return self._seq

    def active(self):
        """(engine, prepared) snapshot of the live variant."""
        if self.mode == "fallback":
            return self.fb_engine, self.fb_prepared
        return self.engine, self.prepared

    def _warm_shapes(self) -> list:
        return [self.input_shape(b, r)
                for r in self.resolutions for b in self.buckets]

    def warmup(self) -> dict:
        # warm the donating variant: it is what the dispatch path calls
        return self.engine.warmup(self.prepared, self._warm_shapes(),
                                  donate=True)

    def ensure_fallback(self) -> None:
        """Shadow-prepare the GPU-only plan (the paper's all-GPU baseline):
        compiled, prepared and bucket-warmed BEFORE any live traffic is
        redirected to it — failover is an atomic pointer swap, not a
        compile on the request path."""
        if self.fb_engine is None or not self.fb_engine.is_current():
            # the fallback inherits the entry's replica striping, so a
            # failover keeps serving across the same mesh
            self.fb_engine = self._wrap(compile_network(
                self.mods, None, use_pallas=self.use_pallas))
            self.fb_prepared = self.fb_engine.prepare(self.params)
            self.fb_engine.warmup(self.fb_prepared, self._warm_shapes(),
                                  donate=True)

    def failover(self) -> None:
        with self.swap_lock:
            self.ensure_fallback()
            self.mode = "fallback"          # atomic redirect

    def recover(self) -> None:
        with self.swap_lock:
            self.mode = "primary"

    def probe(self, xb) -> bool:
        """Half-open probe: one batch on the primary (hybrid) engine,
        output discarded — live traffic keeps flowing on the fallback.
        Dispatches a COPY through the donating path (the only variant
        ``warmup`` traces — a non-donating call here would pay a fresh
        jit trace mid-failover), so the caller's buffer survives for the
        real dispatch."""
        try:
            out = self.engine(self.prepared, np.array(xb), donate=True)
            jax.block_until_ready(out)
            return True
        except Exception:
            return False

    def ensure_backup(self):
        """Monolithic engine over the SAME plans — the straggler backup
        for pipelined entries (bit-identical results, no stage hand-offs
        to stall on).  None for entries already monolithic."""
        if not self.pipelined:
            return None
        if self.bk_engine is None or not self.bk_engine.is_current():
            self.bk_engine = compile_network(self.mods, self.plans,
                                             use_pallas=self.use_pallas)
            self.bk_prepared = self.bk_engine.prepare(self.params,
                                                      self.calib_x)
        return self.bk_engine

    def refresh(self) -> None:
        """Re-acquire the engine after an executor cache clear (re-running
        calibration from the stored batch when the plans need it).  Keeps
        the CURRENT params, and holds ``swap_lock`` end to end so a
        concurrent ``swap_params`` either completes before the recompile
        reads ``self.params`` or lands after it — a hot-swap that raced
        the clear always survives.  The fallback variant (if built) is
        rebuilt too; the straggler backup rebuilds lazily."""
        faults.trip("refresh")
        with self.swap_lock:
            self.engine = self._wrap(self._compile(
                self.mods, self.plans, use_pallas=self.use_pallas))
            self.prepared = self.engine.prepare(self.params, self.calib_x)
            self.warmup()
            if self.fb_engine is not None:
                self.fb_engine = None
                self.ensure_fallback()
            self.bk_engine = None

    def migrate(self, plans) -> None:
        """Hot-migrate this entry to a replanner candidate plan set — the
        breaker-failover machinery generalized from "the GPU-only plan"
        to ANY plan: shadow-compile, prepare and bucket-warm the new
        plans' engine first (live traffic keeps flowing on the old one),
        then atomically redirect under ``swap_lock``.  Batches already
        dispatched finish on the old plan generation; every batch flushed
        after this returns serves the new one, and each still bit-matches
        its own plan's batch-1 oracle.  Candidate plans inherit the live
        plans' per-module calibration choice (a migration never changes
        quantization semantics)."""
        plans = carry_calibration(self.plans, plans)
        eng = self._wrap(self._compile(self.mods, plans,
                                       use_pallas=self.use_pallas))
        cal = self.calib_x if eng.needs_calibration else None
        prep = eng.prepare(self.params, cal)
        eng.warmup(prep, self._warm_shapes(), donate=True)
        with self.swap_lock:
            self.plans = plans
            self.engine = eng
            self.prepared = prep                # atomic redirect
            self.stage_comps = network_stage_components(self.mods, plans)
            self.bk_engine = None   # straggler backup follows the new plans
            self.plan_generation += 1


class HeteroServer:
    """Async dynamic-batching server over ``repro.core.executor``."""

    def __init__(self, *, buckets=DEFAULT_BUCKETS, max_wait_ms: float = 2.0,
                 use_pallas: bool | None = None, in_flight: int = 1,
                 max_queue: int = 1024, breaker_threshold: int = 3,
                 probe_interval_s: float = 0.25, recover_after: int = 2,
                 straggler_factor: float = 4.0,
                 straggler_min_ms: float = 50.0,
                 replanner: Replanner | None = None,
                 measure_every: int = 8,
                 ema_batches: int = 16, ema_alpha: float = 0.25):
        self.buckets = tuple(sorted(buckets))
        self.use_pallas = use_pallas
        self.in_flight = max(1, int(in_flight))
        self.max_queue = max(1, int(max_queue))
        # online re-partitioning: every ``measure_every``-th primary-mode
        # batch dispatches through the engine's timed path (serialized,
        # per-stage walls), feeds the replanner's fitter, and may trigger
        # a hot plan migration (repro.core.replan)
        self._replanner = replanner
        self.measure_every = max(1, int(measure_every))
        # online EMA scale refinement (Plan.calibrate("ema")): budget of
        # refined batches per entry, and the blend factor per batch
        self.ema_batches = max(0, int(ema_batches))
        self.ema_alpha = float(ema_alpha)
        # widest replica fan-out across entries: scales the dispatch
        # window the batcher's deadline deferral reads (1 = today's gate)
        self._max_replicas = 1
        self._breaker_cfg = (breaker_threshold, probe_interval_s,
                             recover_after)
        self.straggler_factor = straggler_factor
        self._straggler_min_s = straggler_min_ms * 1e-3
        self._batcher = DynamicBatcher(max_wait_s=max_wait_ms * 1e-3,
                                       max_batch=self.buckets[-1])
        self._entries: dict[str, _Entry] = {}
        self._caps: dict[str, tuple] = {}      # per-network bucket ladder
        self.metrics = ServerMetrics()
        self._thread: threading.Thread | None = None
        self._cthread: threading.Thread | None = None
        # dispatched-but-unresolved batches, FIFO to the completion thread
        self._completions: queue.Queue | None = (
            queue.Queue() if self.in_flight > 1 else None)
        # async results the dispatcher has not yet gated on (depth window)
        self._outstanding: list = []
        # dispatched-but-uncompleted batch count: the admission signal the
        # batcher's deadline deferral reads (downstream occupancy)
        self._inflight_batches = 0
        self._inflight_lock = threading.Lock()
        self._stop = threading.Event()
        self._lock = threading.Lock()
        # every admitted future, until resolved: the shutdown sweep's
        # ground truth that nothing ever hangs
        self._pending: set = set()
        self._pending_lock = threading.Lock()
        self._state = "new"                 # -> "running" -> "closed"
        # live-state gauges for /healthz and /metrics: served through
        # ``metrics.snapshot()`` so transports read counters AND gauges
        # from one call (the provider reads under the batcher's and the
        # pending registry's own locks — no new locking)
        self.metrics.set_gauge_provider(self._gauge_snapshot)

    def _gauge_snapshot(self) -> dict:
        with self._pending_lock:
            pending = len(self._pending)
        depths = self._batcher.depths()
        return {"state": self._state,
                "pending_requests": pending,
                "inflight_batches": self._inflight(),
                "queue_total": sum(depths.values()),
                "queue_depth": {lane_label(lane): d
                                for lane, d in depths.items()}}

    @property
    def state(self) -> str:
        """Lifecycle state: ``new`` -> ``running`` -> ``closed``."""
        return self._state

    # -- registration ------------------------------------------------------

    def register(self, name: str, mods, plans=None, params=None, *,
                 input_hw=(96, 96), buckets=None, warm: bool = True,
                 use_pallas: bool | None = None, calib_x=None,
                 pipelined: bool = False,
                 prewarm_fallback: bool = False,
                 replicas: int = 1, mesh=None) -> dict:
        """Compile, prepare and bucket-warm a network under ``name``.

        ``input_hw`` is one (H, W) pair or a list of them: every listed
        resolution gets its own batching lanes and its own warmed jit
        traces, resident side-by-side (``submit`` routes by image shape).
        ``buckets`` overrides the server-wide bucket ladder (per-network
        policy: e.g. cap a cache-thrashing workload at batch 8).
        ``calib_x`` is the calibration batch for plans that freeze
        activation scales at prepare time (``Plan.calibrate``) — required
        for such plans, ignored otherwise.  Calibrated and uncalibrated
        plans carry different plan signatures, so mixed registrations
        never share an engine.  ``pipelined=True`` serves through the
        stage-pipelined engine (bit-identical results; device hand-offs
        exposed for overlap).  ``prewarm_fallback=True`` compiles and
        bucket-warms the GPU-only failover plan NOW, bounding a later
        failover pause to the atomic redirect instead of a first-failure
        compile (by default the fallback builds lazily when the breaker
        trips).  ``replicas=R`` (or an explicit ``mesh=``) stripes this
        network's traffic across R data-axis replicas: the parameters are
        prepared once and committed per replica (one shared generation
        stamp), flushed batches route to the least-outstanding replica,
        and each replica gets its own in-flight slots and metrics lane —
        requires at least R devices (``make_production_mesh(shape=(R,))``).
        Returns the engine's exec stats after warm-up (one trace per
        bucket x resolution, per replica)."""
        t0 = time.perf_counter()
        if params is None:
            params = init_network(mods, jax.random.PRNGKey(0))
        if use_pallas is None:
            use_pallas = self.use_pallas    # server-wide default
        entry = _Entry(name, mods, plans, params,
                       input_hw, buckets or self.buckets, use_pallas,
                       calib_x=calib_x, pipelined=pipelined,
                       breaker=_Breaker(*self._breaker_cfg),
                       straggler_factor=self.straggler_factor,
                       replicas=replicas, mesh=mesh,
                       ema_batches=self.ema_batches,
                       ema_alpha=self.ema_alpha)
        if prewarm_fallback and plans is not None:
            entry.ensure_fallback()
        with self._lock:
            self._entries[name] = entry
            self._caps[name] = entry.buckets
            self._max_replicas = max(self._max_replicas, entry.replicas)
        self.metrics.set_breaker(name, entry.breaker.label)
        out = entry.warmup() if warm else entry.engine.exec_stats()
        entry.register_s = time.perf_counter() - t0
        return out

    def networks(self) -> list[str]:
        with self._lock:
            return list(self._entries)

    def active(self, name: str):
        """(engine, prepared) serving ``name`` right now: a batch-1
        ``engine(prepared, x)`` call reproduces any row served under it."""
        with self._lock:
            entry = self._entries.get(name)
        if entry is None:
            raise KeyError(f"unregistered network {name!r}; "
                           f"registered: {self.networks()}")
        return entry.active()

    def swap_params(self, name: str, params, *, calib_x=None) -> dict:
        """Hot-swap a registered network's weights without draining.

        The new parameters are prepared on a shadow handle first (weight
        quantization + optional re-calibration — the expensive half runs
        outside the server lock, so live traffic keeps flowing on the old
        generation), then dispatch is atomically redirected.  In-flight
        batches finish on the old generation; every batch flushed after
        this returns uses the new one.  The entry's ``swap_lock``
        serializes this against concurrent swaps and against stale-engine
        ``refresh`` recompiles, so a recompile that raced the swap can
        never revert it.  ``calib_x`` defaults to the batch stored at
        register time (calibrated plans re-freeze their scales against
        the new weights).  A built GPU-only fallback variant re-prepares
        under the same swap, so a later failover serves the new weights.
        Returns the new generation stamp."""
        with self._lock:
            entry = self._entries.get(name)
        if entry is None:
            raise KeyError(f"unregistered network {name!r}; "
                           f"registered: {self.networks()}")
        with entry.swap_lock:
            cal = calib_x if calib_x is not None else entry.calib_x
            prepared = entry.engine.prepare(params, cal)  # shadow prepare
            fb_prepared = (entry.fb_engine.prepare(params)
                           if entry.fb_engine is not None else None)
            with self._lock:
                entry.params = params
                if calib_x is not None:
                    entry.calib_x = calib_x
                old_gen = entry.prepared.generation
                entry.prepared = prepared                 # atomic redirect
                if fb_prepared is not None:
                    entry.fb_prepared = fb_prepared
                entry.bk_engine = None    # backup re-prepares on next use
        self.metrics.record_swap()
        return {"network": name, "generation": prepared.generation,
                "previous_generation": old_gen}

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> "HeteroServer":
        if self._state == "closed":
            raise ServerClosed("start() after shutdown(): a HeteroServer "
                               "is single-use")
        if self._thread is not None:
            return self
        self._state = "running"
        self._stop.clear()
        if self._completions is not None:
            self._cthread = threading.Thread(target=self._completion_loop,
                                             name="hetero-serve-complete",
                                             daemon=True)
            self._cthread.start()
        self._thread = threading.Thread(target=self._drain_loop,
                                        name="hetero-serve-drain",
                                        daemon=True)
        self._thread.start()
        return self

    def shutdown(self, timeout: float = 10.0) -> None:
        """Graceful drain: stop admission first, flush everything still
        queued (partial buckets included, in chunks when a backlog
        exceeds the largest bucket), let every dispatched batch complete
        (at in_flight > 1 via the completion thread), then resolve
        anything still pending with ``Shutdown`` — a shutdown never
        leaves a future hanging."""
        self._state = "closed"                         # stop admission
        if self._thread is not None:
            self._stop.set()
            self._batcher.put(Request("__wake__", None))  # unblock wait_ready
            self._thread.join(timeout)
            if self._thread.is_alive():
                # drain thread still mid-flush (e.g. a long recompile):
                # leave the completion thread running so its batches still
                # resolve; a later shutdown() retries the join
                return
            self._thread = None
            # bounded passes: a dispatch-failure retry during the drain
            # re-enqueues head-of-lane and must still be flushed
            for _ in range(3):
                drained = self._batcher.drain_all()
                if not drained:
                    break
                for lane, reqs in drained:
                    reqs = [r for r in reqs if r.network != "__wake__"]
                    if not reqs:
                        continue
                    # a backlog can exceed the largest bucket — chunk it
                    cap = self._caps.get(lane.network, self.buckets)[-1]
                    for i in range(0, len(reqs), cap):
                        self.metrics.count("drain_flushed")
                        self._flush(lane, reqs[i:i + cap], by_deadline=True)
            if self._cthread is not None:
                self._completions.put(None)            # completion sentinel
                self._cthread.join(timeout)
                self._cthread = None
        # registry sweep: whatever survived the flush resolves typed
        with self._pending_lock:
            leftovers = list(self._pending)
            self._pending.clear()
        for fut in leftovers:
            if fut.done():
                continue
            try:
                fut.set_exception(Shutdown("server shut down before this "
                                           "request could be served"))
                self.metrics.count("drain_aborted")
            except Exception:           # resolved in the race window: fine
                pass

    def __enter__(self) -> "HeteroServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.shutdown()

    # -- request path ------------------------------------------------------

    def _fulfil(self, fut, value) -> None:
        """Resolve a future with a result, exactly once (late duplicates —
        e.g. a shutdown sweep racing a completion — are dropped)."""
        with self._pending_lock:
            self._pending.discard(fut)
        try:
            fut.set_result(value)
        except Exception:
            pass

    def _reject(self, fut, exc) -> None:
        with self._pending_lock:
            self._pending.discard(fut)
        try:
            fut.set_exception(exc)
        except Exception:
            pass

    def submit(self, name: str, x, *, priority: int = DEFAULT_PRIORITY,
               deadline_ms: float | None = None,
               request_id: int | None = None):
        """Admit one image; returns a ``concurrent.futures.Future`` whose
        result is that request's logits row.  The image's (H, W) picks the
        resolution lane; ``priority <= 0`` routes to the deadline-critical
        lane (shorter flush deadline), larger values are bulk traffic.

        ``deadline_ms`` is a per-request deadline from now: if the batch
        holding the request has not dispatched by then, the future
        resolves with ``DeadlineExceeded``.  Raises ``ServerClosed`` when
        the server is not running, ``Overloaded`` when the request's lane
        is at the ``max_queue`` depth bound (load shed).

        ``request_id`` is the id this request's spans carry in
        ``metrics.spans`` (the front door passes the id of its own spans);
        while the log is on, a request without one gets a fresh id."""
        # validation precedes the state check: a malformed request is
        # malformed whether or not the server is running
        with self._lock:
            entry = self._entries.get(name)
        if entry is None:
            raise KeyError(f"unregistered network {name!r}; "
                           f"registered: {self.networks()}")
        x = np.asarray(x) if not hasattr(x, "shape") else x
        shape = tuple(x.shape)
        if len(shape) == 4 and shape[0] == 1:
            x, shape = x[0], shape[1:]
        res = entry.match_res(shape)
        if res is None:
            want = [entry.input_shape(1, r)[1:] for r in entry.resolutions]
            raise ValueError(f"{name}: expected an image of shape "
                             f"{' or '.join(map(str, want))} "
                             f"(or with a leading batch-1 axis), "
                             f"got {shape}")
        if self._state != "running":
            raise ServerClosed("submit() before start()"
                               if self._state == "new" else
                               "submit() after shutdown()")
        now = time.monotonic()
        deadline = None if deadline_ms is None else now + deadline_ms * 1e-3
        spans = self.metrics.spans
        rid = request_id or (spans.new_id() if spans.on else 0)
        req = Request(name, x, res=res, priority=int(priority),
                      deadline_s=deadline, rid=rid)
        with self._pending_lock:
            self._pending.add(req.future)
        if not self._batcher.put(req, bound=self.max_queue):
            with self._pending_lock:
                self._pending.discard(req.future)
            self.metrics.count("shed")
            raise Overloaded(f"lane {lane_label(req.lane)} at queue-depth "
                             f"bound {self.max_queue}",
                             lane=req.lane, bound=self.max_queue,
                             label=lane_label(req.lane))
        self.metrics.record_submit()
        return req.future

    def submit_many(self, name: str, images, *,
                    priority: int = DEFAULT_PRIORITY) -> list:
        return [self.submit(name, x, priority=priority) for x in images]

    # -- drain loop --------------------------------------------------------

    def _inflight(self) -> int:
        with self._inflight_lock:
            return self._inflight_batches

    def _inflight_add(self, d: int) -> None:
        with self._inflight_lock:
            self._inflight_batches += d

    def _can_dispatch(self) -> bool:
        """Downstream admission signal for the batcher: False while the
        dispatch window is fully occupied (a deadline flush would only
        queue behind in-flight batches — keep accumulating instead).
        Replica striping widens the window: ``in_flight`` is a per-replica
        depth, so R replicas absorb R x in_flight batches."""
        return self._inflight() < self.in_flight * self._max_replicas

    def _drain_loop(self) -> None:
        while not self._stop.is_set():
            reqs: list = []
            try:
                spans = self.metrics.spans
                t0 = time.monotonic_ns() if spans.on else 0
                got = self._batcher.wait_ready(
                    timeout=0.05, buckets_by=self._caps,
                    can_dispatch=self._can_dispatch)
                # read again: the log may have started during the wait
                on = spans.on
                if on:
                    t_pop = time.monotonic_ns()
                    if t0:
                        spans.add("batcher.wait", t0, t_pop)
                if got is None:
                    continue
                lane, popped, by_deadline = got
                reqs = [r for r in popped if r.network != "__wake__"]
                if reqs:
                    bid = 0
                    if on:
                        # each request's queue span ends at the pop and
                        # names the batch that took it
                        bid = spans.new_id()
                        for r in reqs:
                            spans.add("batcher.queue",
                                      int(r.t_enqueue * 1e9), t_pop,
                                      r.rid, bid)
                    self._flush(lane, reqs, by_deadline, bid)
            except Exception as e:      # defensive: the loop must survive
                self.metrics.count("errors")
                self.metrics.record_failure(len(reqs))
                for r in reqs:
                    self._reject(r.future, e)

    def _flush(self, lane: LaneKey, reqs, by_deadline: bool,
               bid: int = 0) -> None:
        """Dispatch one single-lane batch.  At in_flight == 1 this also
        completes it inline (the fully-serialized pre-pipelining loop);
        otherwise the async result is handed to the completion thread and
        this thread immediately returns to batching — padding of batch i+1
        overlaps device compute of batch i.  ``bid`` is the batch's id in
        the span log (a fresh one is drawn while the log is on)."""
        spans = self.metrics.spans
        on = spans.on
        t_batch = 0
        if on:
            t_batch = time.monotonic_ns()
            bid = bid or spans.new_id()
        with self._lock:
            entry = self._entries.get(lane.network)
        if entry is None:                     # unregistered mid-flight
            for r in reqs:
                self._reject(r.future, KeyError(lane.network))
            self.metrics.record_failure(len(reqs))
            return
        # per-request deadlines: late rows reject BEFORE dispatch — a
        # deadline that passed while queued is never served late
        now = time.monotonic()
        live = []
        for r in reqs:
            if r.deadline_s is not None and now > r.deadline_s:
                self.metrics.count("deadline_exceeded")
                self.metrics.record_failure(1)
                self._reject(r.future, DeadlineExceeded(
                    f"queued {now - r.t_enqueue:.4f}s, deadline "
                    f"{r.deadline_s - r.t_enqueue:.4f}s",
                    waited_s=now - r.t_enqueue,
                    deadline_s=r.deadline_s - r.t_enqueue))
                continue
            live.append(r)
        if not live:
            return
        reqs = live
        engine = replica = None
        try:
            engine, prepared = entry.active()
            if not engine.is_current():
                # executor cache was cleared under us: rebuild, stay live
                entry.refresh()
                self.metrics.record_recompile()
                engine, prepared = entry.active()
            t_pad = time.monotonic_ns() if on else 0
            bucket = pick_bucket(len(reqs), entry.buckets)
            xb = pad_batch([r.x for r in reqs], bucket)
            if on:
                spans.add("server.pad", t_pad, time.monotonic_ns(), bid, bid)
            if entry.mode == "fallback" and entry.breaker.probe_due(now):
                self._probe(entry, xb)
                # a completed recovery redirects THIS batch already
                engine, prepared = entry.active()
            striped = isinstance(engine, ReplicaSet)
            if self._completions is not None:
                # depth gate BEFORE dispatch: this batch is padded and
                # ready while at most (in_flight - 1) computations are
                # still unfinished — at in_flight=2 compute stays
                # serialized and only host work overlaps it.  Replica
                # striping scales the window: the gate is per replica.
                window = ((self.in_flight - 1)
                          * (engine.n_replicas if striped else 1))
                while len(self._outstanding) >= window:
                    jax.block_until_ready(self._outstanding.pop(0))
            # replica striping: claim the least-outstanding replica AFTER
            # the gate (freshest occupancy); released on completion
            replica = engine.pick() if striped else None
            rkw = {} if replica is None else {"replica": replica}
            # xb is drain-loop-owned and never read after dispatch: donate
            # its buffer (exec_stats counts the copies saved).  The host
            # array itself survives donation, so the completion path can
            # still re-dispatch it on the straggler backup engine.
            t_disp = time.monotonic_ns() if on else 0
            measured = None
            if self._replanner is not None and entry.mode == "primary":
                entry.measure_seq += 1
                if entry.measure_seq % self.measure_every == 0:
                    # sampled measurement batch: serialized timed dispatch
                    # with per-stage walls (pipelined) or one total
                    out, measured = engine.timed_call(prepared, xb,
                                                      donate=True, **rkw)
            if measured is None:
                out = engine(prepared, xb, donate=True, **rkw)
            if on:
                spans.add("server.dispatch", t_disp, time.monotonic_ns(),
                          bid, bid, -1 if replica is None else replica)
        except Exception as e:
            if replica is not None:
                engine.release(replica)
            self._dispatch_failure(entry, lane, reqs, e, by_deadline)
            return
        if entry.mode == "primary":
            entry.breaker.record_success()
            if entry.ema_left > 0:
                # online EMA scale refinement: this batch served under
                # ``prepared``'s generation; the refined tree redirects
                # the NEXT flush (atomic, one stamp across all replicas)
                self._ema_refine(entry, engine, prepared, xb)
        if measured is not None:
            self._maybe_replan(entry, lane, measured, bucket)
        self._inflight_add(1)
        item = (entry, lane, reqs, bucket, by_deadline, xb, out,
                engine, prepared, replica, bid, t_batch)
        if self._completions is not None:
            self._outstanding.append(out)
            self._completions.put(item)
        else:
            try:
                self._complete(*item)
            finally:
                self._inflight_add(-1)
                self._batcher.kick()

    def _dispatch_failure(self, entry: _Entry, lane: LaneKey, reqs,
                          exc: Exception, by_deadline: bool) -> None:
        """A dispatch raised before any result existed.  Policy:
        FPGA-attributed failures on the primary plan feed the circuit
        breaker — tripping it fails over to the GPU-only plan and
        re-dispatches the same rows WITHOUT spending their retry budget
        (the rows did nothing wrong).  Every other failure de-batches
        into one bounded retry per request, re-enqueued head-of-lane so
        FIFO-within-lane survives; rows out of budget reject with the
        original error."""
        dev = faults.fault_device(exc)
        if entry.mode == "primary" and dev == "fpga":
            if entry.breaker.record_failure():
                self.metrics.set_breaker(entry.name, entry.breaker.label)
                try:
                    entry.failover()
                except Exception:
                    # fallback build failed: counted, then the retry path
                    self.metrics.count("errors")
                else:
                    self.metrics.count("failovers")
                    self._flush(lane, reqs, by_deadline)  # budget-free retry
                    return
        retry, dead = [], []
        for r in reqs:
            if r.retries < 1:
                r.retries += 1
                retry.append(r)
            else:
                dead.append(r)
        if retry:
            self.metrics.count("retries", len(retry))
            self._batcher.put_front(retry)
        for r in dead:
            self._reject(r.future, exc)
        if dead:
            self.metrics.record_failure(len(dead))

    def _probe(self, entry: _Entry, xb) -> None:
        """Half-open probe batch on the primary engine (output discarded);
        ``recover_after`` consecutive passes swap live traffic back."""
        now = time.monotonic()
        ok = entry.probe(xb)
        self.metrics.count("probes_ok" if ok else "probes_failed")
        if entry.breaker.record_probe(ok, now):
            entry.recover()
            self.metrics.count("recoveries")
        self.metrics.set_breaker(entry.name, entry.breaker.label)

    # -- online EMA scale refinement ---------------------------------------

    def _ema_refine(self, entry: _Entry, engine, prepared, xb) -> None:
        """One step of the ``Plan.calibrate("ema")`` online calibrator:
        capture each EMA site's amplitude on the live batch (under the
        CURRENT frozen scales) and blend it into the frozen scale,
        ``s' = (1 - alpha) * s + alpha * s_batch``.  The refined tree is
        a fresh generation, redirected atomically under ``swap_lock`` —
        the batch that fed the capture keeps its own generation, and a
        refinement never overwrites a racing ``swap_params`` (it only
        lands while the handle it refined is still the live one).  On a
        replicated entry all replicas refine under ONE stamp."""
        try:
            # xb was donated to the dispatch above; the host array
            # survives, a copy keeps the capture's buffer independent
            scales = engine.capture_scales(prepared, np.array(xb))
            scales = {m: s for m, s in scales.items()
                      if m in engine.ema_modules}
            if not scales:
                entry.ema_left = 0
                return
            refined = engine.refine_scales(prepared, scales,
                                           alpha=entry.ema_alpha)
        except Exception:
            self.metrics.count("errors")
            return
        with entry.swap_lock:
            if entry.prepared is prepared:
                entry.prepared = refined
                entry.ema_left -= 1
                self.metrics.count("ema_updates")

    # -- online re-partitioning --------------------------------------------

    def _maybe_replan(self, entry: _Entry, lane: LaneKey, times,
                      batch: int) -> None:
        """Feed one measured batch to the replanner and execute its
        decision.  Runs on the drain thread, exactly like breaker
        failover: a migration's shadow compile+warm blocks batching
        briefly, but the redirect itself is atomic and the queue is never
        drained.  A failed migration leaves the live plan untouched."""
        rep = self._replanner
        rep.observe(entry.name, lane.res, entry.plans, entry.stage_comps,
                    times, batch)
        self.metrics.count("measured_batches")
        decision = rep.consider(entry.name, entry.mods, entry.plans)
        if decision.scales is not None:
            self.metrics.set_fitted(entry.name, decision.scales.as_dict())
        if not decision.migrate:
            return
        try:
            entry.migrate(decision.plans)
        except Exception:
            self.metrics.count("errors")
            return
        self.metrics.count("replans")

    # -- completion path ---------------------------------------------------

    def _watch(self, entry: _Entry, xb, out, engine=None, prepared=None,
               replica=None):
        """Straggler watchdog: poll the async result against the rolling
        budget (``straggler_factor`` x the entry's median completion,
        floored at ``straggler_min_ms``).  Past the budget: count the
        event and race a backup dispatch of the same batch — on a
        DIFFERENT replica for replicated entries, on the monolithic
        engine for pipelined ones.  Whichever result this returns, the
        bits match (same plans, same prepared generation contract)."""
        budget = entry.monitor.budget()
        if budget is None or not hasattr(out, "is_ready"):
            return out
        budget = max(budget, self._straggler_min_s)
        t0 = time.monotonic()
        while not out.is_ready():
            if time.monotonic() - t0 > budget:
                self.metrics.count("straggler_events")
                backup = self._backup_dispatch(entry, xb, engine, prepared,
                                               replica)
                return out if backup is None else backup
            time.sleep(0.0005)
        return out

    def _backup_dispatch(self, entry: _Entry, xb, engine=None,
                         prepared=None, replica=None):
        """Best-effort re-dispatch of a straggling batch; None (= keep
        waiting on the original) when no backup path exists or the backup
        itself fails.  A replicated entry re-dispatches on the
        least-outstanding OTHER replica — same prepared generation, same
        bits, but none of the straggler's device state; non-replicated
        pipelined entries keep the monolithic backup engine."""
        try:
            if (replica is not None and isinstance(engine, ReplicaSet)
                    and engine.n_replicas > 1):
                other = engine.peek(exclude=(replica,))
                self.metrics.count("backup_dispatches")
                self.metrics.count("cross_replica_backups")
                # a copy through the donating path: the only variant
                # warmup traced, and the original xb stays re-usable
                return engine(prepared, np.array(xb), donate=True,
                              replica=other)
            bk = entry.ensure_backup()
            if bk is None:
                return None
            self.metrics.count("backup_dispatches")
            return bk(entry.bk_prepared, xb)
        except Exception:
            return None

    def _complete(self, entry: _Entry, lane: LaneKey, reqs, bucket: int,
                  by_deadline: bool, xb, out, engine=None, prepared=None,
                  replica=None, bid: int = 0, t_batch: int = 0) -> None:
        """Resolve one dispatched batch: block until the device result
        lands (under the straggler watchdog), de-batch, fulfil futures.
        Callers release the admission slot (their ``finally``), so a
        crash in here can never double-release it; the replica slot the
        flush claimed is released HERE, in all paths.  ``t_batch`` is when
        the flush began, where the span log was on then (else 0)."""
        spans = self.metrics.spans
        on = bool(t_batch) and spans.on
        t0 = time.monotonic_ns()
        try:
            out = self._watch(entry, xb, out, engine, prepared, replica)
            jax.block_until_ready(out)
            t_ready = time.monotonic_ns()
            entry.monitor.record(entry.next_seq(), (t_ready - t0) * 1e-9)
            # one host copy, then de-batch as numpy views — per-row device
            # slices would pay 1 dispatch per request
            rows = np.asarray(out)
            now = time.monotonic()
            lats = [now - r.t_enqueue for r in reqs]
            for i, r in enumerate(reqs):
                self._fulfil(r.future, rows[i])
            self.metrics.record_batch(len(reqs), bucket, lats, by_deadline,
                                      lane=lane_label(lane),
                                      replica=(f"{entry.name}/r{replica}"
                                               if replica is not None
                                               else None))
            if on:
                t_end = time.monotonic_ns()
                spans.add("server.device_wait", t0, t_ready, bid, bid,
                          -1 if replica is None else replica)
                spans.add("server.debatch", t_ready, t_end, bid, bid)
                spans.add("server.batch", t_batch, t_end, bid)
        except Exception as e:
            # completion-time failure: the batch's rows get the error — no
            # retry from here (a requeue behind younger completed traffic
            # would break FIFO-within-lane at in_flight > 1)
            for r in reqs:
                self._reject(r.future, e)
            self.metrics.record_failure(len(reqs))
        finally:
            if replica is not None and isinstance(engine, ReplicaSet):
                engine.release(replica)

    def _completion_loop(self) -> None:
        """FIFO completion path (in_flight > 1): batches resolve in
        dispatch order, so per-request ordering survives pipelining.
        Wrapped so an unexpected error resolves the batch's futures and
        the loop keeps serving — one bad batch never wedges the server."""
        while True:
            item = self._completions.get()
            if item is None:                  # shutdown sentinel
                return
            reqs = item[2]
            try:
                self._complete(*item)
            except Exception as e:            # pragma: no cover - defensive
                self.metrics.count("errors")
                self.metrics.record_failure(len(reqs))
                for r in reqs:
                    self._reject(r.future, e)
            finally:
                self._inflight_add(-1)
                self._batcher.kick()  # a slot freed: deferred flushes re-run

    # -- observability -----------------------------------------------------

    def stats(self) -> dict:
        """Server metrics + per-engine exec/trace stats + executor cache."""
        from repro.core.executor import cache_stats
        with self._lock:
            engines = {name: {**e.engine.exec_stats(),
                              "current": e.engine.is_current(),
                              "pipelined": e.pipelined,
                              "buckets": e.buckets,
                              "resolutions": e.resolutions,
                              "param_generation": e.prepared.generation,
                              "plan_generation": e.plan_generation,
                              "replica_count": e.replicas,
                              "ema_left": e.ema_left,
                              "devices": e.engine.devices,
                              "register_s": e.register_s,
                              "mode": e.mode,
                              "breaker": e.breaker.label,
                              "fallback_ready": e.fb_engine is not None}
                       for name, e in self._entries.items()}
        out = {"server": self.metrics.snapshot(),
               "state": self._state,
               "in_flight": self.in_flight,
               "inflight_batches": self._inflight(),
               "engines": engines,
               "executor_cache": cache_stats()}
        if self._replanner is not None:
            out["replan"] = self._replanner.snapshot()
        return out
