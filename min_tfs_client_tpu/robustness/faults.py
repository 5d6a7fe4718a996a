"""Deterministic fault injection: named points, armed by a seeded plan.

Every capability this system grew since PR 9 (pressure eviction, drain,
replicated stickiness, pin recovery) was hardened by review rounds
finding races AFTER the fact. This module is the adversary built in:
the hot paths carry named injection sites —

    faults.point("router.forward.pre", backend=..., method=...)

— that cost ONE module-global read when disarmed (the default, always,
in production: nothing is armed unless an operator passes a plan), and
execute a matching rule's action when armed. Rules live in a seeded
JSON **fault plan**, so a storm that found a race replays bit-for-bit:

    {"seed": 1234,
     "rules": [
       {"point": "router.forward.pre", "match": {"probing": true},
        "action": "grpc_error", "code": "UNAVAILABLE",
        "every": 3, "max_fires": 10},
       {"point": "kv.alloc", "action": "page_pressure",
        "probability": 0.25},
       {"point": "backend.handle.pre", "match": {"model": "t5"},
        "action": "delay", "delay_ms": 50}]}

Rule matching: `point` is an fnmatch pattern over the point name;
`match` compares call-site context values (stringified — JSON true
matches Python True); `every` fires each Nth eligible hit, and/or
`probability` rolls a per-rule seeded RNG; `max_fires` bounds the
total. The FIRST rule that fires wins the hit.

Actions:

  delay            sleep `delay_ms` in the calling thread (on the aio
                   loop this IS a loop stall — deliberately so; the
                   lag ticker must see it)
  error            raise a typed ServingError with canonical `code` —
                   surfaces on the wire exactly like a real one
  grpc_error       raise an InjectedRpcError carrying grpc `code` —
                   for forward paths whose error handling is keyed on
                   grpc.RpcError (probe walks, unreachable accounting)
  connection_drop  raise ConnectionResetError — for socket-level paths
                   (http_pool's stale-reuse discipline)
  deadline_corrupt return an override the call site applies to its
                   forward deadline (`deadline_ms`)
  page_pressure    return a marker the KV PageAllocator reads as
                   "arena exhausted" — storms exercise swap/close/
                   refuse without actually filling HBM

Every fired fault is recorded in the flight recorder (kind="fault")
and annotated onto the active request trace, so a storm failure is
diagnosable from the same stitched timelines (PR 12) an operator
would pull for a real outage.
"""

from __future__ import annotations

import fnmatch
import json
import logging
import os
import random
import threading
import time
from dataclasses import dataclass, field
from typing import Optional

log = logging.getLogger(__name__)

ENV_PLAN = "TPU_SERVING_FAULT_PLAN"

_ACTIONS = frozenset({"delay", "error", "grpc_error", "connection_drop",
                      "deadline_corrupt", "page_pressure"})


class FaultPlanError(ValueError):
    """A malformed fault plan fails LOUDLY at arm time — a typo'd rule
    silently never firing would fake a green storm."""


class Fired:
    """What `point()` returns when a rule fired with a VALUE action the
    call site must apply itself (deadline_corrupt, page_pressure).
    Raising actions never construct one. Falsy context checks stay
    cheap: `if faults.point(...)` is True only when something fired."""

    __slots__ = ("point", "action", "deadline_ms", "page_pressure")

    def __init__(self, point: str, action: str,
                 deadline_ms: float = 0.0, page_pressure: bool = False):
        self.point = point
        self.action = action
        self.deadline_ms = deadline_ms
        self.page_pressure = page_pressure

    def __bool__(self) -> bool:
        return True


def _injected_rpc_error(code_name: str, details: str):
    """A grpc.RpcError the forward paths' `err.code()/err.details()`
    handling treats exactly like a wire error. Built lazily so this
    module imports grpc-free (the KV pool and batching sites must not
    drag grpc into jax-only processes)."""
    import grpc

    class InjectedRpcError(grpc.RpcError):
        def __init__(self, code, detail):
            super().__init__(detail)
            self._code = code
            self._details = detail

        def code(self):
            return self._code

        def details(self):
            return self._details

    return InjectedRpcError(getattr(grpc.StatusCode, code_name), details)


@dataclass
class FaultRule:
    point: str
    action: str
    match: dict = field(default_factory=dict)
    every: int = 0
    probability: float = 1.0
    max_fires: int = 0
    delay_ms: float = 0.0
    code: str = "UNAVAILABLE"
    message: str = ""
    deadline_ms: float = 0.0

    # runtime state, engine-lock guarded
    eligible: int = 0   # guarded_by: FaultEngine._lock
    fires: int = 0      # guarded_by: FaultEngine._lock

    def validate(self, index: int) -> None:
        if self.action not in _ACTIONS:
            raise FaultPlanError(
                f"rule[{index}]: unknown action {self.action!r} "
                f"(want one of {sorted(_ACTIONS)})")
        if not self.point:
            raise FaultPlanError(f"rule[{index}]: empty point pattern")
        if self.action == "delay" and self.delay_ms <= 0:
            raise FaultPlanError(
                f"rule[{index}]: delay needs delay_ms > 0")
        if self.action == "deadline_corrupt" and self.deadline_ms <= 0:
            raise FaultPlanError(
                f"rule[{index}]: deadline_corrupt needs deadline_ms > 0")
        if self.action in ("error", "grpc_error"):
            from min_tfs_client_tpu.utils.status import Code

            if not hasattr(Code, self.code):
                raise FaultPlanError(
                    f"rule[{index}]: unknown status code {self.code!r}")
        if not 0.0 <= self.probability <= 1.0:
            raise FaultPlanError(
                f"rule[{index}]: probability must be in [0, 1]")
        if self.every < 0 or self.max_fires < 0:
            raise FaultPlanError(
                f"rule[{index}]: every/max_fires must be >= 0")


_RULE_FIELDS = frozenset({
    "point", "action", "match", "every", "probability", "max_fires",
    "delay_ms", "code", "message", "deadline_ms"})


class FaultEngine:
    """One armed plan: rules + per-rule seeded RNGs and counters.

    Determinism contract: with a fixed plan (seed included) and a fixed
    SEQUENCE of eligible hits per rule, the set of hits that fire is a
    pure function of the plan — `every` counts eligible hits, and
    `probability` draws from a per-rule Random seeded from the plan
    seed, never from global randomness. (Across threads the interleaving
    of DIFFERENT points may vary; each rule's own decision stream does
    not.)"""

    def __init__(self, plan: dict):
        if not isinstance(plan, dict):
            raise FaultPlanError("fault plan must be a JSON object")
        unknown = set(plan) - {"seed", "rules"}
        if unknown:
            raise FaultPlanError(f"unknown plan keys: {sorted(unknown)}")
        self.seed = int(plan.get("seed", 0))
        self._lock = threading.Lock()
        self.rules: list[FaultRule] = []
        self._rngs: list[random.Random] = []
        self._fired_by_point: dict[str, int] = {}  # guarded_by: self._lock
        for index, raw in enumerate(plan.get("rules", ())):
            if not isinstance(raw, dict):
                raise FaultPlanError(f"rule[{index}] must be an object")
            unknown = set(raw) - _RULE_FIELDS
            if unknown:
                raise FaultPlanError(
                    f"rule[{index}]: unknown keys {sorted(unknown)}")
            rule = FaultRule(**raw)
            rule.validate(index)
            self.rules.append(rule)
            self._rngs.append(random.Random(self.seed * 1000003 + index))

    # -- the hot path --------------------------------------------------------

    def hit(self, name: str, ctx: dict) -> Optional[Fired]:
        for index, rule in enumerate(self.rules):
            if not fnmatch.fnmatchcase(name, rule.point):
                continue
            if any(str(ctx.get(key)) != str(want)
                   for key, want in rule.match.items()):
                continue
            with self._lock:
                rule.eligible += 1
                if rule.max_fires and rule.fires >= rule.max_fires:
                    continue
                if rule.every and rule.eligible % rule.every != 0:
                    continue
                if rule.probability < 1.0 and \
                        self._rngs[index].random() >= rule.probability:
                    continue
                rule.fires += 1
                self._fired_by_point[name] = \
                    self._fired_by_point.get(name, 0) + 1
            return self._fire(index, rule, name, ctx)
        return None

    def _fire(self, index: int, rule: FaultRule, name: str,
              ctx: dict) -> Optional[Fired]:
        self._record(index, rule, name, ctx)
        if rule.action == "delay":
            time.sleep(rule.delay_ms / 1e3)
            return Fired(name, "delay")
        if rule.action == "error":
            from min_tfs_client_tpu.utils.status import Code, ServingError

            raise ServingError(
                getattr(Code, rule.code),
                rule.message or f"fault injected at {name} "
                                f"(rule {index}, {rule.code})")
        if rule.action == "grpc_error":
            raise _injected_rpc_error(
                rule.code,
                rule.message or f"fault injected at {name} "
                                f"(rule {index}, {rule.code})")
        if rule.action == "connection_drop":
            raise ConnectionResetError(
                rule.message or f"fault injected at {name} "
                                f"(rule {index}, connection drop)")
        if rule.action == "deadline_corrupt":
            return Fired(name, "deadline_corrupt",
                         deadline_ms=rule.deadline_ms)
        return Fired(name, "page_pressure", page_pressure=True)

    def _record(self, index: int, rule: FaultRule, name: str,
                ctx: dict) -> None:
        """Every fire lands in the black box AND on the active request
        trace — a storm failure must be diagnosable from the same
        surfaces a real outage is. Best-effort: the recorder must never
        turn an injected fault into a second, unplanned one."""
        try:
            from min_tfs_client_tpu.observability import (
                flight_recorder,
                tracing,
            )

            flight_recorder.record(
                "fault", point=name, rule=index, action=rule.action,
                **{k: str(v)[:80] for k, v in sorted(ctx.items())})
            tracing.annotate(fault=f"{name}:{rule.action}")
        except Exception:  # pragma: no cover - recording is best-effort
            pass

    # -- introspection -------------------------------------------------------

    def stats(self) -> dict:
        with self._lock:
            return {
                "seed": self.seed,
                "fired_by_point": dict(self._fired_by_point),
                "rules": [
                    {"point": r.point, "action": r.action,
                     "eligible": r.eligible, "fires": r.fires}
                    for r in self.rules],
            }


# The one module global the disarmed fast path reads. Swapped by
# arm()/disarm() only; sites read it through point() below.
_engine: Optional[FaultEngine] = None


def point(name: str, **ctx) -> Optional[Fired]:
    """One named injection site. Disarmed (the default): a module-global
    read and a None return — the <1% routed-leg budget the bench
    asserts. Armed: the first matching rule's action executes here
    (sleeps and raises happen IN the caller's frame)."""
    engine = _engine
    if engine is None:
        return None
    return engine.hit(name, ctx)


def arm(plan) -> FaultEngine:
    """Arm a plan: a dict, a JSON string, or a path to a JSON file.
    Replaces any previously armed plan."""
    global _engine
    if isinstance(plan, (str, os.PathLike)):
        text = str(plan)
        if text.lstrip().startswith("{"):
            plan = json.loads(text)
        else:
            with open(text, "r", encoding="utf-8") as f:
                plan = json.load(f)
    engine = FaultEngine(plan)
    _engine = engine
    log.warning("fault injection ARMED: seed=%d, %d rule(s)",
                engine.seed, len(engine.rules))
    try:
        from min_tfs_client_tpu.observability import flight_recorder

        flight_recorder.record("faults_armed", seed=engine.seed,
                               rules=len(engine.rules))
    except Exception:  # pragma: no cover - recording is best-effort
        pass
    return engine


def disarm() -> None:
    global _engine
    _engine = None


def armed() -> bool:
    return _engine is not None


def covers(name: str) -> bool:
    """Whether an armed rule names point `name` (by its pattern alone:
    match, every and max_fires are not consulted), so that a caller that
    may not sleep can stay away from the point. Disarmed: a
    module-global read."""
    engine = _engine
    return engine is not None and any(
        fnmatch.fnmatchcase(name, rule.point) for rule in engine.rules)


def stats() -> Optional[dict]:
    engine = _engine
    return engine.stats() if engine is not None else None


def arm_from_env() -> bool:
    """Arm from TPU_SERVING_FAULT_PLAN (a path or inline JSON) when set —
    how subprocess fleets in the storm suites arm their backends without
    new flags threading through every harness. Called by the server and
    router mains; a malformed plan raises (fail the boot loudly, never
    serve with a half-armed adversary)."""
    raw = os.environ.get(ENV_PLAN, "")
    if not raw:
        return False
    arm(raw)
    return True
