"""Deterministic fault injection for the serving runtime.

A real embedded FPGA-GPU deployment sees transient device faults as the
norm, not the exception — but CI has neither device.  This module makes
every failure mode of the serving stack *testable* by injecting faults at
the host-side dispatch points the compiled engines and ``HeteroServer``
already go through:

  * ``op="dispatch"``  — an engine ``__call__`` (monolithic or pipelined);
                         the site reports the devices its plan touches, so
                         a rule pinned to ``device="fpga"`` fires on the
                         hybrid plan but never on the GPU-only fallback.
  * ``op="stage"``     — one ``PipelinedEngine`` stage dispatch; the site
                         reports the stage index and its device tag, so
                         "fail stage k of batch n" is expressible exactly.
  * ``op="prepare"``   — ``engine.prepare`` (weight quantization /
                         calibration).
  * ``op="refresh"``   — a server-side stale-engine recompile.

Process-level trigger points (``repro.frontend``) sit ABOVE the engines,
at the serving process boundary, so router/front-door failure handling is
just as CI-testable as the in-process paths:

  * ``op="http"``      — the front door's request handler, after decode
                         and before ``submit`` (a fired rule surfaces as
                         a typed 500 wire response, never a hung socket).
  * ``op="worker"``    — the router's per-worker forward; the site
                         reports the target worker's name as ``device``,
                         so "fail every dispatch to worker w1" is
                         expressible exactly (a fired rule looks like a
                         transport failure: the retry/ejection path runs).
  * ``op="conn"``      — the front door's keep-alive connection loop,
                         once per parsed request head; a fired rule is
                         answered as a typed 500 while the SOCKET
                         SURVIVES — the test hook for "one request on a
                         persistent connection failed, the rest keep
                         flowing".

Faults are **deterministic**: a rule fires on an explicit trigger window
(``after`` skips the first N matching events, ``times`` bounds how many
fire) or on a seeded Bernoulli draw (``p``), never on wall-clock state.
The same plan against the same call sequence always injects the same
faults — which is what lets the failover/retry/shed paths run in CI
without real hardware.

    plan = FaultPlan([FaultRule(op="dispatch", device="fpga", times=3)])
    with inject(plan):
        ...                      # first 3 hybrid dispatches raise
    plan.fired                   # -> list of FaultEvent records

``kind="delay"`` injects latency (``delay_s`` of host-side sleep at the
dispatch point) instead of raising — the straggler/overload knob.
Raised faults are ``InjectedFault`` instances carrying the attributed
``device``/``stage``/``op`` so the serving layer's circuit breaker can
tell an FPGA-path failure from a GPU one.
"""
from __future__ import annotations

import random
import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass
from typing import NamedTuple


class InjectedFault(RuntimeError):
    """A deliberately injected failure.  ``device`` is the attributed
    device path ("fpga"/"gpu"/None), ``stage`` the pipelined stage index
    (None outside stage dispatch), ``op`` the injection point."""

    def __init__(self, msg: str, *, op: str, device: str | None = None,
                 stage: int | None = None):
        super().__init__(msg)
        self.op = op
        self.device = device
        self.stage = stage


class FaultEvent(NamedTuple):
    """One injected fault (or delay), as recorded on the plan."""
    op: str
    device: str | None
    stage: int | None
    kind: str
    hit: int                   # 1-based index among the rule's matches


def fault_device(exc: BaseException) -> str | None:
    """The device a failure is attributed to, if any.  ``InjectedFault``
    carries it directly; a real exception carries none, so a kernel that
    fails on the device counts as an error and never as a failover."""
    dev = getattr(exc, "device", None)
    return dev if isinstance(dev, str) else None


@dataclass
class FaultRule:
    """One injection rule.  Matching is by site predicates (``op``, and —
    where the site reports them — ``stage`` and ``device``); firing is by
    a deterministic window over the rule's *matching* events (``after`` /
    ``times``) or a seeded Bernoulli draw (``p``).  For sites that report
    no device of their own (``prepare``/``refresh``), ``device`` is pure
    attribution: it labels the raised fault without restricting the match.
    """
    op: str = "dispatch"            # dispatch | stage | prepare | refresh
    kind: str = "fail"              # fail | delay
    device: str | None = None       # site matcher + attribution label
    stage: int | None = None        # pipelined stage index matcher
    after: int = 0                  # skip the first `after` matching events
    times: int | None = 1           # fire this many times (None = forever)
    p: float | None = None          # seeded Bernoulli instead of a window
    delay_s: float = 0.05           # kind="delay": injected latency
    hits: int = 0                   # matching events seen (runtime state)
    fired: int = 0                  # faults actually injected

    def matches(self, op: str, device, stage: int | None) -> bool:
        if op != self.op:
            return False
        if self.stage is not None and stage != self.stage:
            return False
        if self.device is not None and device is not None:
            site = device if isinstance(device, (tuple, list, set)) \
                else (device,)
            if self.device not in site:
                return False
        return True


class FaultPlan:
    """A set of rules plus the deterministic state that drives them.
    Thread-safe: serving dispatch runs across drain/completion threads.
    ``fired`` records every injected event for test assertions."""

    def __init__(self, rules: list[FaultRule], seed: int = 0):
        self.rules = list(rules)
        self.fired: list[FaultEvent] = []
        self._rng = random.Random(seed)
        self._lock = threading.Lock()

    def check(self, op: str, device=None, stage: int | None = None) -> None:
        """Evaluate every rule against one dispatch site.  Delay rules
        sleep; fail rules raise ``InjectedFault`` (first firing rule
        wins).  Called from the engines via ``trip``."""
        delay = 0.0
        boom: InjectedFault | None = None
        with self._lock:
            for r in self.rules:
                if not r.matches(op, device, stage):
                    continue
                r.hits += 1
                if r.p is not None:
                    fire = self._rng.random() < r.p
                else:
                    fire = (r.hits > r.after
                            and (r.times is None
                                 or r.fired < r.times))
                if not fire:
                    continue
                r.fired += 1
                dev = r.device if r.device is not None else (
                    device if isinstance(device, str) else None)
                self.fired.append(FaultEvent(op, dev, stage, r.kind,
                                             r.hits))
                if r.kind == "delay":
                    delay = max(delay, r.delay_s)
                elif boom is None:
                    boom = InjectedFault(
                        f"injected {op} fault "
                        f"(device={dev}, stage={stage}, hit={r.hits})",
                        op=op, device=dev, stage=stage)
        if delay > 0.0:
            time.sleep(delay)
        if boom is not None:
            raise boom


# -- global injection point ---------------------------------------------------
# One process-wide active plan: the compiled engines are cached and shared
# across servers/threads, so the injection point must be too.  ``trip`` is
# a single attribute read when no plan is installed — the production hot
# path pays one ``is None`` check per dispatch.

_ACTIVE: FaultPlan | None = None
_ACTIVE_LOCK = threading.Lock()


def install(plan: FaultPlan | None) -> None:
    global _ACTIVE
    with _ACTIVE_LOCK:
        _ACTIVE = plan


def active() -> FaultPlan | None:
    return _ACTIVE


@contextmanager
def inject(plan: FaultPlan):
    """Scope a fault plan: install on entry, uninstall on exit.  Keep
    oracle/reference engine calls OUTSIDE the scope — the injection point
    is process-global, exactly like the engine cache."""
    install(plan)
    try:
        yield plan
    finally:
        install(None)


def trip(op: str, device=None, stage: int | None = None) -> None:
    """Fault-injection hook: no-op unless a plan is installed."""
    plan = _ACTIVE
    if plan is not None:
        plan.check(op, device=device, stage=stage)
