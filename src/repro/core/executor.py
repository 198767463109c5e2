"""Compiled heterogeneous inference engine: jit-once plan execution.

The interpreter in ``repro.core.hetero`` walks a ``(modules, plans)`` pair
node by node in Python, re-quantizing FPGA weights on every call — correct,
readable, slow.  This module is the production path: it lowers the same pair
ONCE into a single end-to-end ``jax.jit``-compiled callable and caches the
result under a hashable *plan signature*, so repeated calls (and repeated
``compile_network`` invocations with an equivalent plan) never re-trace.

API::

    engine   = compile_network(mods, plans)      # cached by plan signature
    prepared = engine.prepare(params)            # one-time: quantize FPGA
                                                 # weights -> resident int8
    logits   = engine(prepared, x)               # single jitted call

    pipe = compile_pipelined(mods, plans)        # stage-pipelined variant:
    logits = pipe(prepared, x)                   #  same bits, cut at every
    outs = pipe.run_many(prepared, xs, depth=4)  #  FPGA<->GPU boundary so
                                                 #  micro-batches overlap

    rset = ReplicaSet(engine, mesh)              # data-parallel striping:
    prepared = rset.prepare(params)              #  one prepared copy per
    logits = rset(prepared, x, replica=1)        #  data-axis replica, ONE
                                                 #  shared generation stamp

Plans that opted into prepare-time calibration (``Plan.calibrate``) freeze
their activation scales from a calibration batch::

    prepared = engine.prepare(params, calib_x=calib_batch)

``prepare`` is the compile-time half of the paper's DHM story: FPGA-assigned
weights leave fp32 exactly once (int8 + per-channel scale for the GEMM path,
fake-quantized grids for the fused/conv paths) and stay resident across
calls, the analogue of weights living in FPGA logic.  ``engine(prepared, x)``
is a pure function of arrays — no Python dispatch, no per-call quantization.

Lowering goes through the ``repro.core.passes`` pipeline (annotate ->
fuse -> calibrate -> backend; full detail in the README):

  - fused FPGA chains ([pw1x1 ->] dw3x3/stride -> pw1x1, stride 1 or 2)
                                   -> ``fused_chain`` Pallas kernel
                                      (VMEM-resident intermediates)
  - FPGA pwconv / fc               -> ``int8_gemm`` with resident int8
                                      weights quantized at prepare time
  - gconv input-channel splits     -> one concatenated XLA conv
  - other FPGA convs               -> XLA conv, weights fake-quantized at
                                      prepare time
  - GPU nodes                      -> unchanged fp32 XLA path

``use_pallas`` defaults to auto: Pallas kernels on TPU/GPU backends, their
pure-XLA reference implementations on CPU (where Pallas only interprets).
The interpreted ``hetero.run_network`` remains the oracle the engine is
parity-tested against (``tests/test_executor.py``).
"""
from __future__ import annotations

import threading
import time
import warnings
from collections.abc import Mapping
from contextlib import contextmanager, nullcontext
from dataclasses import astuple

import jax
import jax.numpy as jnp

from repro.core.graph import ModuleGraph
from repro.core.lowering import lower_network
from repro.core.passes import chain_groups
from repro.core.schedule import Plan
from repro.runtime import faults


def _default_use_pallas() -> bool:
    return jax.default_backend() != "cpu"


def plan_devices(plans: list[Plan] | None) -> tuple:
    """The device set a (modules, plans) pair touches — ("gpu",) for the
    all-GPU baseline.  Reported to the fault-injection site so rules
    pinned to ``device="fpga"`` fire on hybrid engines but never on the
    GPU-only fallback plan."""
    devs = {"gpu"}
    for p in plans or []:
        devs.update(p.assign.values())
    return tuple(sorted(devs))


@contextmanager
def _quiet_donation():
    """Scope-limited filter for jax's trace-time "donated buffers were not
    usable" warning: donation is best-effort by design here — buffers whose
    shape matches no computation output simply are not reused, which is not
    actionable for callers.  Applied only around first-trace dispatches so
    steady-state calls pay no filter-manipulation cost."""
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        yield


def _variants(fn) -> int:
    """How many compiled variants a ``jax.jit`` function holds: one per
    argument signature it has dispatched, placements included."""
    return fn._cache_size()


def plan_signature(mods: list[ModuleGraph], plans: list[Plan] | None,
                   use_pallas: bool) -> tuple:
    """Hashable signature of everything lowering depends on: the graph
    topology/specs, each plan's routing decisions, the fused chains the
    fusion pass will actually form, and the calibration choice.  Two equal
    signatures lower to byte-identical programs, so the compile cache may
    share them — and calibrated plans NEVER alias uncalibrated ones (their
    numerics differ)."""
    plan_by = {p.module: p for p in plans} if plans else {}
    sig = []
    for m in mods:
        p = plan_by.get(m.name)
        if p:
            fused_sig = tuple(tuple(n.name for n in g)
                              for g in chain_groups(m, p) if len(g) > 1)
            psig = (p.scheme, tuple(sorted(p.assign.items())),
                    tuple(p.fused), tuple(sorted(p.gconv.items())),
                    fused_sig, p.calibrator)
        else:
            psig = None
        sig.append((m.name, m.kind, m.output, m.residual,
                    tuple((n.name, astuple(n.spec), n.inputs, n.act)
                          for n in m.nodes),
                    psig))
    return (use_pallas, tuple(sig))


_PREPARE_GEN = [0]                  # process-global monotonic prepare stamp
_PREPARE_GEN_LOCK = threading.Lock()


def _next_prepare_generation() -> int:
    with _PREPARE_GEN_LOCK:
        _PREPARE_GEN[0] += 1
        return _PREPARE_GEN[0]


class PreparedParams(Mapping):
    """Generation-stamped handle over one prepared parameter tree.

    Every ``engine.prepare`` draws from one process-global monotonic
    counter, so a serving layer hot-swapping weights can tell which
    parameter generation served a given batch: no two ``prepare`` calls
    ever share a stamp, and the numbering never rewinds — not even when
    ``clear_cache`` forces a recompile onto a fresh engine instance.

    ``placement`` makes the handle's device residency explicit: None (the
    default) leaves the tree wherever jax put it — byte-identical to the
    pre-placement behaviour — while a ``jax.sharding.NamedSharding``
    means every leaf was committed to it at prepare time, so jit runs the
    whole program on that placement's devices and uncommitted (host)
    batch inputs follow it there.

    The engine unwraps ``.tree`` before dispatch; the ``Mapping``
    interface is preserved so callers that index the raw tree
    (``prepared[mod][site]``) keep working unchanged."""

    __slots__ = ("tree", "generation", "placement")

    def __init__(self, tree: dict, generation: int, placement=None):
        self.tree = tree
        self.generation = generation
        self.placement = placement

    def __getitem__(self, key):
        return self.tree[key]

    def __iter__(self):
        return iter(self.tree)

    def __len__(self):
        return len(self.tree)

    def __repr__(self):  # pragma: no cover - debug aid
        place = "" if self.placement is None else f", placed={self.placement}"
        return (f"PreparedParams(generation={self.generation}, "
                f"modules={list(self.tree)}{place})")


def _unwrap(prepared):
    """Accept both the stamped handle and a raw prepared tree."""
    return getattr(prepared, "tree", prepared)


def place_tree(tree: dict, placement):
    """Commit every leaf of a prepared tree to ``placement`` via the
    elastic-resharding helper (``repro.runtime.resilience.reshard``) —
    the same device_put walk that re-admits a restored training state
    onto a new mesh places serving replicas."""
    from repro.runtime.resilience import reshard
    return reshard(tree, jax.tree.map(lambda _: placement, tree))


class CompiledNetwork:
    """A (modules, plans) pair lowered and jitted once.  Call ``prepare``
    once per parameter tree, then treat the instance as the forward fn.

    ``jax.jit`` still traces once per distinct input SHAPE — a serving
    layer that pads requests into bucket-sized batches should ``warmup``
    each bucket shape ahead of traffic so no live request ever pays a
    trace.  ``exec_stats()["traces"]`` counts what JAX really did: each
    run of the traced Python body, plus each new compiled variant that a
    call added without one (an input of a new placement, such as a host
    array where warm-up passed device arrays, compiles again on the same
    trace)."""

    def __init__(self, mods: list[ModuleGraph], plans: list[Plan] | None,
                 use_pallas: bool):
        self.signature = plan_signature(mods, plans, use_pallas)
        self.use_pallas = use_pallas
        self.devices = plan_devices(plans)
        self.generation = _GENERATION[0]
        lowered = lower_network(mods, plans, use_pallas)
        self._prepare_fn = lowered.prepare      # jits its own internals
        self._capture_fn = lowered.capture
        self._freeze_fn = lowered.freeze
        self.needs_calibration = lowered.needs_calibration
        self.ema_modules = lowered.ema_modules
        self._traced = 0                # runs of the traced body

        def run(tree, x):
            self._count_trace()         # runs only while JAX traces
            return lowered.run(tree, x)
        self._jitted = jax.jit(run)
        # donating variant of the same program: the caller hands over the
        # input-batch buffer and XLA reuses it instead of allocating (one
        # copy saved per call on the serving hot path, where the padded
        # batch is drain-loop-owned and never read again)
        self._jitted_donate = jax.jit(run, donate_argnums=(1,))
        # (shape, dtype, donate) keys already dispatched: scopes the
        # donation-warning filter and timed_call's pre-trace
        self._shapes_seen: set = set()
        self._exec = {"calls": 0, "traces": 0, "prepares": 0,
                      "donated_calls": 0, "donated_bytes": 0,
                      "timed_calls": 0}
        # cached engines are shared across threads (serving drain loop +
        # direct callers); keep the accounting race-free
        self._stats_lock = threading.Lock()

    def prepare(self, params, calib_x=None, *,
                placement=None) -> PreparedParams:
        """One-time parameter lowering: FPGA weights quantized here (int8
        resident for the GEMM path), GPU weights passed through.  When the
        plans opted into calibration (``needs_calibration``), a calibration
        batch is required and activation scales are frozen from it.
        ``placement`` (a ``NamedSharding``) additionally commits the
        prepared tree to specific devices — None keeps today's implicit
        default placement, bit for bit.  Returns a generation-stamped
        ``PreparedParams`` handle (the stamp is a process-global monotonic
        prepare counter — hot-swap bookkeeping that survives engine
        recompiles)."""
        faults.trip("prepare", device=self.devices)
        tree = self._prepare_fn(params, calib_x)
        if placement is not None:
            tree = place_tree(tree, placement)
        with self._stats_lock:
            self._exec["prepares"] += 1
        return PreparedParams(tree, _next_prepare_generation(), placement)

    def capture_scales(self, prepared, x) -> dict:
        """Capture each calibrated quant site's amplitude statistic on a
        live batch, run under the CURRENT frozen scales: ``{module:
        {site: scale}}``.  The online-EMA refinement input
        (``Plan.calibrate("ema")``); the serving layer filters the result
        to ``ema_modules`` so non-EMA calibrators stay frozen."""
        return self._capture_fn(_unwrap(prepared), x)

    def refine_scales(self, prepared, scales, *, alpha: float = 1.0,
                      _generation: int | None = None) -> PreparedParams:
        """A new ``PreparedParams`` with captured scales blended into the
        frozen ones (s' = (1-alpha)*s + alpha*s_batch), re-committed to
        the handle's placement.  Draws a fresh generation unless the
        caller supplies one — a ``ReplicaSet`` refines every replica
        under a single stamp so no batch can mix generations."""
        tree = self._freeze_fn(_unwrap(prepared), scales, alpha)
        placement = getattr(prepared, "placement", None)
        if placement is not None:
            tree = place_tree(tree, placement)
        gen = (_generation if _generation is not None
               else _next_prepare_generation())
        return PreparedParams(tree, gen, placement)

    def _count_trace(self) -> None:
        with self._stats_lock:
            self._traced += 1
            self._exec["traces"] += 1

    def _count_call(self, x, donate: bool) -> None:
        nbytes = int(getattr(x, "nbytes", 0))
        with self._stats_lock:
            self._exec["calls"] += 1
            if donate:
                self._exec["donated_calls"] += 1
                self._exec["donated_bytes"] += nbytes

    def __call__(self, prepared, x, *, donate: bool = False):
        """Run the jitted program.  ``donate=True`` donates ``x``'s buffer
        to the computation — the CALLER'S array becomes unusable after the
        call; only pass buffers you own and will not read again."""
        # fault-injection site, BEFORE any dispatch or donation: an
        # injected dispatch failure leaves the caller's buffer intact
        faults.trip("dispatch", device=self.devices)
        key = (tuple(x.shape), str(getattr(x, "dtype", "f32")), donate)
        first = key not in self._shapes_seen
        self._shapes_seen.add(key)
        self._count_call(x, donate)
        fn = self._jitted_donate if donate else self._jitted
        with _quiet_donation() if (first and donate) else nullcontext():
            return self._call_counted(fn, _unwrap(prepared), x)

    def _call_counted(self, fn, *args):
        """``fn(*args)``, adding to ``traces`` each compiled variant the
        call added with no run of the traced body: an old trace compiled
        again for a new input placement."""
        n0, traced0 = _variants(fn), self._traced
        out = fn(*args)
        silent = _variants(fn) - n0 - (self._traced - traced0)
        if silent > 0:
            with self._stats_lock:
                self._exec["traces"] += silent
        return out

    def timed_call(self, prepared, x, *, donate: bool = False):
        """Synchronous, measured forward: ``(out, [wall_seconds])``.  The
        monolithic engine has no internal stage boundaries, so the list
        holds ONE element — total dispatch-to-ready wall time.  The shape
        is pre-traced outside the timed region so a first-shape call never
        reports compile time as execution time."""
        key = (tuple(x.shape), str(getattr(x, "dtype", "f32")), donate)
        if key not in self._shapes_seen:
            jax.block_until_ready(
                self(prepared, jnp.zeros(x.shape, x.dtype), donate=donate))
        t0 = time.perf_counter()
        out = self(prepared, x, donate=donate)
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        with self._stats_lock:
            self._exec["timed_calls"] += 1
        return out, [dt]

    def warmup(self, prepared, shapes, *, donate: bool = False) -> dict:
        """Trace/compile each input shape once on zeros (per-bucket compile
        warm-up for the serving path; ``donate`` must match how the live
        path will call — the two variants trace separately).  Returns
        ``exec_stats()``."""
        for s in shapes:
            jax.block_until_ready(
                self(prepared, jnp.zeros(s, jnp.float32), donate=donate))
        return self.exec_stats()

    def compiled_text(self, prepared, shape) -> str:
        """Text of the program the serving path (the donating variant)
        compiles for one input shape; on a TPU each Pallas kernel shows
        as a ``tpu_custom_call`` named after its ``pallas_call``."""
        x = jax.ShapeDtypeStruct(tuple(shape), jnp.float32)
        return (self._jitted_donate.lower(_unwrap(prepared), x)
                .compile().as_text())

    def exec_stats(self) -> dict:
        with self._stats_lock:
            return dict(self._exec)

    def is_current(self) -> bool:
        """False once ``clear_cache`` ran after this engine was built —
        a serving layer holding the instance should re-``compile_network``
        (the engine itself keeps working; this only flags staleness)."""
        return self.generation == _GENERATION[0]


class PipelinedEngine:
    """The same (modules, plans) pair, compiled as a STAGE PIPELINE.

    ``repro.core.passes.stage`` cuts the lowered network at every FPGA<->GPU
    boundary into maximal same-device segments; each segment jits separately
    and the engine threads a dict of live inter-stage values through them.
    Running the stages back to back is bit-identical to the monolithic
    ``CompiledNetwork`` (the parity oracle — ``tests/test_pipeline.py``),
    but the cut exposes the paper's overlap: with JAX's async dispatch,
    stage s of micro-batch i runs while stage s+1 still works on
    micro-batch i-1 (``run_many``), the software analogue of the FPGA
    front-end computing input i+1 under the GPU back-end of input i.

    Inter-stage envs are engine-owned, so every stage after the first
    donates its env (``donate_argnums``) — device hand-offs reuse buffers
    instead of copying.  The network input rides a separate, never-donated
    argument, so caller arrays are never consumed.
    """

    def __init__(self, mods: list[ModuleGraph], plans: list[Plan] | None,
                 use_pallas: bool):
        self.signature = ("pipelined",) + plan_signature(mods, plans,
                                                         use_pallas)
        self.use_pallas = use_pallas
        self.devices = plan_devices(plans)
        self.generation = _GENERATION[0]
        lowered = lower_network(mods, plans, use_pallas)
        self._prepare_fn = lowered.prepare
        self._capture_fn = lowered.capture
        self._freeze_fn = lowered.freeze
        self.needs_calibration = lowered.needs_calibration
        self.ema_modules = lowered.ema_modules
        self.stages = lowered.stages
        self._traced = 0                # runs of a stage's traced body
        self._jitted = [
            jax.jit(self._counted(s.fn)) if i == 0
            else jax.jit(self._counted(s.fn), donate_argnums=(2,))
            for i, s in enumerate(self.stages)]
        self._shapes_seen: set = set()
        self._env_bytes: dict[tuple, int] = {}   # per input shape, at trace
        self._exec = {"calls": 0, "traces": 0, "prepares": 0,
                      "stages": len(self.stages),
                      "donated_calls": 0, "donated_bytes": 0,
                      "timed_calls": 0}
        self._stats_lock = threading.Lock()

    def prepare(self, params, calib_x=None, *,
                placement=None) -> PreparedParams:
        faults.trip("prepare", device=self.devices)
        tree = self._prepare_fn(params, calib_x)
        if placement is not None:
            tree = place_tree(tree, placement)
        with self._stats_lock:
            self._exec["prepares"] += 1
        return PreparedParams(tree, _next_prepare_generation(), placement)

    capture_scales = CompiledNetwork.capture_scales
    refine_scales = CompiledNetwork.refine_scales
    _count_trace = CompiledNetwork._count_trace
    _call_counted = CompiledNetwork._call_counted

    def _counted(self, fn):
        """``fn`` counting each run of its traced body in ``traces``: the
        count is per stage, so one new input shape adds one per stage."""
        def stage(*args):
            self._count_trace()         # runs only while JAX traces
            return fn(*args)
        return stage

    def _slices(self, prepared) -> list:
        """Per-stage prepared-parameter slices (tiny host-side dicts; each
        stage's jit signature only carries the weights it actually uses)."""
        tree = _unwrap(prepared)
        return [{f"{m}.{p}": tree[m][p] for m, p in s.params}
                for s in self.stages]

    def _dispatch(self, slices, x, env, s: int):
        stage = self.stages[s]
        # per-stage fault site: "fail stage k of batch n" is expressible,
        # and the injected fault carries the stage's device tag.  A real
        # failure stays untagged: it is an error, never a failover
        faults.trip("stage", device=stage.device, stage=s)
        xin = x if stage.needs_input else ()
        return self._call_counted(self._jitted[s], slices[s], xin, env)

    def _count_call(self, x, donated_env_bytes: int) -> None:
        with self._stats_lock:
            self._exec["calls"] += 1
            if len(self.stages) > 1:
                self._exec["donated_calls"] += 1
                self._exec["donated_bytes"] += donated_env_bytes

    def _env_nbytes(self, x, envs) -> int:
        """Bytes handed over by donation in one full stage sweep — computed
        once per input shape (the env shapes are a function of it)."""
        key = tuple(x.shape)
        if key not in self._env_bytes:
            self._env_bytes[key] = sum(
                int(v.nbytes) for env in envs for v in env.values())
        return self._env_bytes[key]

    def __call__(self, prepared, x, *, donate: bool = False):
        """Single-batch forward through the stage list.  Async dispatch:
        returns as soon as the last stage is enqueued.  ``donate`` is
        accepted for interface parity with ``CompiledNetwork`` — the
        caller's ``x`` is never consumed either way (inter-stage donation
        is always on)."""
        faults.trip("dispatch", device=self.devices)
        key = (tuple(x.shape), str(getattr(x, "dtype", "f32")))
        first = key not in self._shapes_seen
        slices = self._slices(prepared)
        env: dict = {}
        envs = []
        with _quiet_donation() if first else nullcontext():
            for s in range(len(self.stages)):
                env = self._dispatch(slices, x, env, s)
                if s + 1 < len(self.stages):
                    envs.append(env)
        self._shapes_seen.add(key)
        self._count_call(x, self._env_nbytes(x, envs))
        return env["__out"]

    def timed_call(self, prepared, x, *, donate: bool = False):
        """Measured forward with PER-STAGE wall times: ``(out, times)``
        where ``times[s]`` is the dispatch-to-ready wall of stage ``s`` —
        the list aligns 1:1 with ``self.stages`` and therefore with
        ``repro.core.schedule.network_stage_components`` of the same
        (modules, plans) pair.  Blocking at every stage boundary
        serializes the sweep (no cross-stage async overlap), so this is a
        sampling path: the serving layer measures every Nth batch and
        leaves the rest on the async ``__call__``.  Injected stage faults
        (``repro.runtime.faults``, ``op="stage"``) run inside the timed
        region — injected delays are *measured*, which is what lets CI
        drive the replanner without hardware."""
        if ((tuple(x.shape), str(getattr(x, "dtype", "f32")))
                not in self._shapes_seen):
            # trace every stage outside the timed region
            jax.block_until_ready(self(prepared, x))
        faults.trip("dispatch", device=self.devices)
        slices = self._slices(prepared)
        env: dict = {}
        times: list[float] = []
        for s in range(len(self.stages)):
            t0 = time.perf_counter()
            env = self._dispatch(slices, x, env, s)
            jax.block_until_ready(env)
            times.append(time.perf_counter() - t0)
        self._count_call(x, 0)
        with self._stats_lock:
            self._exec["timed_calls"] += 1
        return env["__out"], times

    def run_many(self, prepared, xs, *, depth: int = 2) -> list:
        """Micro-batch software pipeline with at most ``depth`` batches in
        flight: each round advances every active batch one stage (oldest
        first, so stage s of batch i dispatches right after stage s+1 of
        batch i-1 — the skewed schedule), starts a new batch only while
        fewer than ``depth`` are active, and otherwise host-blocks to
        retire the oldest.  The window bounds live inter-stage envs — the
        memory cap ``depth`` promises — during fill as well as steady
        state.  Results are ordered and bit-identical to per-batch
        ``__call__``."""
        depth = max(1, int(depth))
        n, n_stages = len(xs), len(self.stages)
        if n and ((tuple(xs[0].shape), str(getattr(xs[0], "dtype", "f32")))
                  not in self._shapes_seen):
            # trace every stage on the first micro-batch before pipelining
            # (keeps donation warnings scoped and the pipeline trace-free)
            jax.block_until_ready(self(prepared, xs[0]))
        slices = self._slices(prepared) if n else []
        envs: list = [None] * n
        outs: list = [None] * n
        stage_of = [0] * n             # next stage to dispatch per batch
        started = retired = 0
        while retired < n:
            for i in range(retired, started):
                s = stage_of[i]
                if s >= n_stages:
                    continue           # fully dispatched, awaiting retire
                env = self._dispatch(slices, xs[i], envs[i] or {}, s)
                stage_of[i] = s + 1
                if s == n_stages - 1:
                    outs[i] = env["__out"]
                    envs[i] = None
                    self._count_call(xs[i], 0)
                else:
                    envs[i] = env
            if started < n and started - retired < depth:
                started += 1           # admitted; advances next round
            elif outs[retired] is not None:
                jax.block_until_ready(outs[retired])
                retired += 1
        return outs

    def warmup(self, prepared, shapes, *, donate: bool = False) -> dict:
        for s in shapes:
            jax.block_until_ready(
                self(prepared, jnp.zeros(s, jnp.float32), donate=donate))
        return self.exec_stats()

    def exec_stats(self) -> dict:
        with self._stats_lock:
            return dict(self._exec)

    def is_current(self) -> bool:
        return self.generation == _GENERATION[0]


class ReplicaPrepared:
    """Replica-striped prepared state: one placed ``PreparedParams`` per
    data-axis replica, ALL sharing one generation stamp.  The shared
    stamp is the atomic-swap invariant — a swap replaces the whole handle
    at once, so whichever replica serves a batch, the batch carries
    exactly one parameter generation and generations never mix."""

    __slots__ = ("replicas",)

    def __init__(self, replicas):
        self.replicas = tuple(replicas)
        if not self.replicas:
            raise ValueError("ReplicaPrepared needs at least one replica")
        if len({p.generation for p in self.replicas}) != 1:
            raise ValueError("replica handles must share one generation")

    @property
    def generation(self) -> int:
        return self.replicas[0].generation

    def __len__(self):
        return len(self.replicas)

    def __getitem__(self, r: int) -> PreparedParams:
        return self.replicas[r]

    def __repr__(self):  # pragma: no cover - debug aid
        return (f"ReplicaPrepared(n={len(self.replicas)}, "
                f"generation={self.generation})")


class ReplicaSet:
    """Data-parallel replica striping over ONE compiled engine.

    Wraps a ``CompiledNetwork``/``PipelinedEngine`` with the ``data``
    axis of a ``repro.launch.mesh`` mesh: ``prepare`` lowers the
    parameters once (one generation stamp) and commits one copy per
    data-axis replica (``replica_shardings``), and each dispatched batch
    runs wholly on one replica's devices — jit follows the committed
    prepared tree, and the host-side batch input follows it there.  Same
    program, same bits: a row served by any replica equals the batch-1
    call on any other.

    The engine's call surface is preserved (``__call__``/``timed_call``/
    ``warmup``/``exec_stats``/``is_current``/``prepare``), so a serving
    layer treats a ReplicaSet exactly like an engine; the extra
    ``replica=`` keyword pins a dispatch to one replica.  Striping policy
    lives in ``pick``/``release``: ``pick`` claims the least-outstanding
    replica (round-robin tiebreak) and ``release`` returns the slot —
    callers that skip the accounting get plain round-robin."""

    def __init__(self, engine, mesh):
        from repro.launch.mesh import replica_shardings
        self.engine = engine
        self.mesh = mesh
        self.shardings = replica_shardings(mesh)
        self.n_replicas = len(self.shardings)
        self._rr = 0
        self._outstanding = [0] * self.n_replicas
        self._calls = [0] * self.n_replicas
        self._lock = threading.Lock()

    # -- engine surface ----------------------------------------------------

    @property
    def signature(self):
        return self.engine.signature

    @property
    def devices(self):
        return self.engine.devices

    @property
    def use_pallas(self):
        return self.engine.use_pallas

    @property
    def needs_calibration(self):
        return self.engine.needs_calibration

    @property
    def ema_modules(self):
        return self.engine.ema_modules

    def is_current(self) -> bool:
        return self.engine.is_current()

    def prepare(self, params, calib_x=None) -> ReplicaPrepared:
        """Lower the parameters ONCE (weight quantization + optional
        calibration — one prepare, one generation stamp), then commit a
        copy to every replica's placement."""
        base = self.engine.prepare(params, calib_x)
        return ReplicaPrepared([
            PreparedParams(place_tree(base.tree, s), base.generation, s)
            for s in self.shardings])

    # -- striping policy ---------------------------------------------------

    def _least(self, exclude=()) -> int:
        cand = [r for r in range(self.n_replicas) if r not in exclude]
        if not cand:
            cand = list(range(self.n_replicas))
        return min(cand, key=lambda r: (self._outstanding[r],
                                        (r - self._rr) % self.n_replicas))

    def pick(self, exclude=()) -> int:
        """Claim the least-outstanding replica (round-robin tiebreak on
        equal load), skipping ``exclude``.  Pairs with ``release``."""
        with self._lock:
            r = self._least(exclude)
            self._outstanding[r] += 1
            self._rr = (r + 1) % self.n_replicas
            return r

    def peek(self, exclude=()) -> int:
        """The replica ``pick`` would choose, WITHOUT claiming it — the
        cross-replica straggler backup targets this."""
        with self._lock:
            return self._least(exclude)

    def release(self, r: int) -> None:
        with self._lock:
            if self._outstanding[r] > 0:
                self._outstanding[r] -= 1

    def _route(self, prepared, replica):
        if replica is None:
            with self._lock:
                replica = self._rr
                self._rr = (replica + 1) % self.n_replicas
        handle = (prepared[replica] if isinstance(prepared, ReplicaPrepared)
                  else prepared)
        with self._lock:
            self._calls[replica] += 1
        return handle, replica

    # -- dispatch ----------------------------------------------------------

    def __call__(self, prepared, x, *, donate: bool = False, replica=None):
        handle, _ = self._route(prepared, replica)
        return self.engine(handle, x, donate=donate)

    def timed_call(self, prepared, x, *, donate: bool = False, replica=None):
        handle, _ = self._route(prepared, replica)
        return self.engine.timed_call(handle, x, donate=donate)

    def run_many(self, prepared, xs, *, depth: int = 2, replica=None):
        handle, _ = self._route(prepared, replica)
        return self.engine.run_many(handle, xs, depth=depth)

    def warmup(self, prepared, shapes, *, donate: bool = False) -> dict:
        """Warm every (shape, replica) pair: jit compiles per placement,
        so each replica's program must be built before live traffic."""
        for r in range(self.n_replicas):
            self.engine.warmup(prepared[r], shapes, donate=donate)
        return self.exec_stats()

    def capture_scales(self, prepared, x, *, replica: int = 0) -> dict:
        handle = (prepared[replica] if isinstance(prepared, ReplicaPrepared)
                  else prepared)
        return self.engine.capture_scales(handle, x)

    def refine_scales(self, prepared, scales, *,
                      alpha: float = 1.0) -> ReplicaPrepared:
        """EMA-refine every replica under ONE fresh generation stamp."""
        gen = _next_prepare_generation()
        return ReplicaPrepared([
            self.engine.refine_scales(prepared[r], scales, alpha=alpha,
                                      _generation=gen)
            for r in range(self.n_replicas)])

    def exec_stats(self) -> dict:
        with self._lock:
            per = {"replicas": self.n_replicas,
                   "replica_calls": list(self._calls),
                   "replica_outstanding": list(self._outstanding)}
        return {**self.engine.exec_stats(), **per}


_CACHE: dict[tuple, CompiledNetwork] = {}
_STATS = {"hits": 0, "misses": 0}
_GENERATION = [0]       # bumped by clear_cache; engines stamp it at build


def compile_network(mods: list[ModuleGraph], plans: list[Plan] | None = None,
                    *, use_pallas: bool | None = None,
                    cache: bool = True) -> CompiledNetwork:
    """Compile (or fetch from cache) the engine for this (modules, plans)
    pair.  ``plans=None`` compiles the all-GPU fp32 network."""
    if use_pallas is None:
        use_pallas = _default_use_pallas()
    sig = plan_signature(mods, plans, use_pallas)
    if cache and sig in _CACHE:
        _STATS["hits"] += 1
        return _CACHE[sig]
    _STATS["misses"] += 1
    eng = CompiledNetwork(mods, plans, use_pallas)
    if cache:
        _CACHE[sig] = eng
    return eng


def compile_pipelined(mods: list[ModuleGraph],
                      plans: list[Plan] | None = None, *,
                      use_pallas: bool | None = None,
                      cache: bool = True) -> PipelinedEngine:
    """Compile (or fetch from cache) the stage-pipelined engine for this
    (modules, plans) pair.  Pipelined and monolithic engines share the
    executor cache but never alias (distinct signature tags): they are
    different programs with identical numerics."""
    if use_pallas is None:
        use_pallas = _default_use_pallas()
    sig = ("pipelined",) + plan_signature(mods, plans, use_pallas)
    if cache and sig in _CACHE:
        _STATS["hits"] += 1
        return _CACHE[sig]
    _STATS["misses"] += 1
    eng = PipelinedEngine(mods, plans, use_pallas)
    if cache:
        _CACHE[sig] = eng
    return eng


def cache_stats() -> dict:
    return {"size": len(_CACHE), "generation": _GENERATION[0], **_STATS}


def clear_cache() -> None:
    """Drop all cached engines and invalidate live ones (their
    ``is_current`` flips false; holders decide when to recompile)."""
    _CACHE.clear()
    _STATS.update(hits=0, misses=0)
    _GENERATION[0] += 1
