"""Per-session device state for incremental autoregressive decode.

BASELINE.md config 5 calls for "tokens/s autoregressive decode via
repeated Predict()": each Predict("decode_step") advances one token and
the KV cache lives in HBM between requests. The reference is stateless
request/response (its Session holds no per-client state, SURVEY.md §7.9);
this store is the TPU-native extension that makes the repeated-Predict
surface possible without re-transferring or re-computing the cache.

States are jax pytrees whose buffers stay device-resident; the step
function donates them (jax.jit donate_argnums), so XLA updates caches in
place — a decode step moves one token in and one token out over the link,
nothing else.

Capacity: each session pins HBM (encoded activations + caches) until
closed, stepped to exhaustion, or idle past the TTL. Capacity pressure is
backpressure — decode_init fails RESOURCE_EXHAUSTED when full — never a
silent eviction of a live session mid-generation.
"""

from __future__ import annotations

import asyncio
import collections
import contextlib
import dataclasses
import functools
import math
import threading
import time
import weakref
from typing import Callable, Optional

from min_tfs_client_tpu.observability import tracing
from min_tfs_client_tpu.utils import aio_loop
from min_tfs_client_tpu.utils.status import ServingError

# -- which paging a pool gets ---------------------------------------------
#
# The builder of the decode-session signatures (decode_signatures.py) runs
# inside an exported servable.py whose saved signature_kwargs predate the
# paging knobs, so the server flags (--kv_block_size / --kv_num_blocks /
# --kv_evict_policy / --kv_prefill_chunk) reach it through a side channel:
# platforms.make_loader wraps the factory call in `paging_scope`, and
# `Paging.resolve` reads the scope for every knob the caller left None.
# block_size 0 = paging off (the max-length slot pool).

EVICT_POLICIES = ("swap", "close", "refuse")


@dataclasses.dataclass(frozen=True)
class Paging:
    """The paging a decode pool gets, as ONE value: page size in tokens
    (0 = the dense slot pool), pages in the arena (0 = the dense pool's
    byte budget), what happens when the arena runs dry, and how many
    forced-prefix tokens a chunked-prefill round streams (0 = one page).
    It validates itself where it is made; nothing downstream checks or
    defaults a knob again."""

    block_size: int = 0
    num_blocks: int = 0
    evict_policy: str = "swap"
    prefill_chunk: int = 0

    def __post_init__(self):
        if self.evict_policy not in EVICT_POLICIES:
            raise ServingError.invalid_argument(
                f"kv_evict_policy must be one of {EVICT_POLICIES}, "
                f"got {self.evict_policy!r}")

    @classmethod
    def resolve(cls, block_size: Optional[int] = None,
                num_blocks: Optional[int] = None,
                evict_policy: Optional[str] = None,
                prefill_chunk: Optional[int] = None) -> "Paging":
        """Explicit knobs win; a knob left None takes the value of this
        thread's `paging_scope` (the loader's server flags), and outside
        any scope the class defaults (paging off)."""
        scope = getattr(_paging_tls, "scope", None) or _NO_PAGING
        return cls(
            int(scope.block_size if block_size is None else block_size),
            int(scope.num_blocks if num_blocks is None else num_blocks),
            scope.evict_policy if evict_policy is None else evict_policy,
            int(scope.prefill_chunk if prefill_chunk is None
                else prefill_chunk))


_NO_PAGING = Paging()
_paging_tls = threading.local()


@contextlib.contextmanager
def paging_scope(block_size: int = 0, num_blocks: int = 0,
                 evict_policy: str = "swap", prefill_chunk: int = 0):
    """Scope paging knobs to ONE loader factory call via a THREAD-LOCAL
    override (the factory and the builders it invokes run synchronously on
    this thread). A process-global set/restore pair — even a locked one —
    either races concurrent loads into the wrong pool flavor (a dense-
    configured load observing a paged scope, or vice versa) or serializes
    every load on one lock; thread-locality removes both failure modes."""
    previous = getattr(_paging_tls, "scope", None)
    _paging_tls.scope = Paging(int(block_size), int(num_blocks),
                               evict_policy, int(prefill_chunk))
    try:
        yield
    finally:
        _paging_tls.scope = previous


# -- per-session decode timelines --------------------------------------------
#
# The slot pools are where a decode session's lifecycle actually happens
# (init, prefill-chunk rounds, per-tick progress, swap/restore under page
# pressure, eviction, close) — but until now that lifecycle was visible
# only as aggregate gauges. SessionTimelines is the bounded, lock-light
# event log behind `/monitoring/sessions`: every pool owns one, events
# are pre-built tuples appended under one short lock (never while a
# device call is in flight — tick events are pushed after the dispatch),
# and both the per-session event count and the closed-session archive
# are rings, so a long-lived server cannot grow without bound. What a
# round does to all its sessions (`tick`) is written ONCE a round, into
# a ring of rounds, and joined to a session when its timeline is read.
#
# Cross-linking: decode-step request traces annotate `session_id`
# (server/handlers.py), so a span timeline at /monitoring/traces and a
# session timeline here join on the id.


class _SessionTimeline:
    __slots__ = ("session_id", "slot", "started", "ended", "state",
                 "events")

    def __init__(self, slot: int, session_id: Optional[str],
                 events_per_session: int):
        self.session_id = session_id or f"slot-{slot}"
        self.slot = slot
        self.started = time.time()
        self.ended: Optional[float] = None  # set when it leaves its slot
        self.state = "live"
        self.events: collections.deque = collections.deque(
            maxlen=events_per_session)

    def leave(self, state: str) -> None:
        self.state, self.ended = state, time.time()

    def to_dict(self, max_events: Optional[int] = None,
                rounds=()) -> dict:
        """`rounds`: the pool's round events (`SessionTimelines.
        round_event`); those of this session's slot and lifetime are
        its own, in time order with the rest, under the same ring."""
        events = list(self.events)
        until = self.ended if self.ended is not None else math.inf
        mine = [(ts, kind, {**shared, **dict(zip(names, slots[self.slot]))})
                for ts, kind, shared, names, slots in rounds
                if self.slot in slots and self.started <= ts <= until]
        if mine:
            events = sorted(events + mine, key=lambda e: e[0])
            events = events[-self.events.maxlen:]
        dropped = 0
        if max_events is not None and len(events) > max_events:
            dropped = len(events) - max_events
            events = events[-max_events:]
        return {
            "session_id": self.session_id,
            "slot": self.slot,
            "state": self.state,
            "started": round(self.started, 6),
            "age_s": round(time.time() - self.started, 3),
            "events_dropped": dropped,
            "events": [
                {"t": round(ts, 6), "kind": kind, **(fields or {})}
                for ts, kind, fields in events
            ],
        }


# A pool's newest rounds, each one entry: at one token a session a
# round, more than the longest session's (`events_per_session`) lives.
ROUNDS_KEPT = 1024


class SessionTimelines:
    """Bounded per-session event logs for one slot pool.

    Keyed by slot while live (the pool's unit of identity); `begin`
    archives any previous occupant of the slot, so slot reuse never
    splices two sessions into one timeline. All methods build the event
    tuple first and hold `_lock` only for the append — callers may hold
    the pool lock (pool lock -> timeline lock, never reversed)."""

    def __init__(self, label: str = "default", *,
                 events_per_session: int = 256,
                 closed_capacity: int = 64):
        self.label = label
        self.events_per_session = int(events_per_session)
        self._lock = threading.Lock()
        self._live: dict[int, _SessionTimeline] = {}  # guarded_by: self._lock
        self._closed: collections.deque = collections.deque(
            maxlen=closed_capacity)                   # guarded_by: self._lock
        self._rounds: collections.deque = collections.deque(
            maxlen=ROUNDS_KEPT)                       # guarded_by: self._lock
        register_timelines(self)

    def begin(self, slot: int, session_id=None) -> None:
        if isinstance(session_id, bytes):
            session_id = session_id.decode("utf-8", "replace")
        timeline = _SessionTimeline(slot, session_id,
                                    self.events_per_session)
        timeline.events.append((time.time(), "init", None))
        with self._lock:
            previous = self._live.pop(slot, None)
            if previous is not None:
                # The pool reused the slot without an observed close
                # (store-level eviction raced): archive, never splice.
                previous.leave("superseded")
                self._closed.append(previous)
            self._live[slot] = timeline

    def event(self, slot: int, kind: str, **fields) -> None:
        entry = (time.time(), kind, fields or None)
        with self._lock:
            timeline = self._live.get(slot)
            if timeline is not None:
                timeline.events.append(entry)

    def events_many(self, entries) -> None:
        """[(slot, kind, fields|None)] under ONE lock acquisition (a
        chunked-prefill round's progress, per chunking session)."""
        now = time.time()
        with self._lock:
            for slot, kind, fields in entries:
                timeline = self._live.get(slot)
                if timeline is not None:
                    timeline.events.append((now, kind, fields))

    def round_event(self, kind: str, slots: dict, names: tuple = (),
                    **shared) -> None:
        """One event for every session of a round, written once: `slots`
        maps each slot of the round to its own values of the fields
        `names`, `shared` holds the fields that are the round's. The
        loop thread pays one append a round, whatever the round carries;
        a reader joins it to the sessions (`_SessionTimeline.to_dict`)."""
        entry = (time.time(), kind, shared, names, slots)
        with self._lock:
            self._rounds.append(entry)

    def close(self, slot: int, kind: str = "close") -> None:
        entry = (time.time(), kind, None)
        with self._lock:
            timeline = self._live.pop(slot, None)
            if timeline is None:
                return
            timeline.events.append(entry)
            timeline.leave("closed" if kind == "close" else kind)
            self._closed.append(timeline)

    def snapshot(self, max_events: Optional[int] = None) -> dict:
        with self._lock:
            live = list(self._live.values())
            closed = list(self._closed)
            rounds = list(self._rounds)
        return {
            "pool": self.label,
            "events_per_session": self.events_per_session,
            "live": [t.to_dict(max_events, rounds) for t in live],
            "closed": [t.to_dict(max_events, rounds) for t in closed],
        }

    def find(self, session_id: str,
             max_events: Optional[int] = None) -> list[dict]:
        with self._lock:
            matches = [t for t in self._live.values()
                       if t.session_id == session_id]
            matches += [t for t in self._closed
                        if t.session_id == session_id]
            rounds = list(self._rounds)
        return [dict(t.to_dict(max_events, rounds), pool=self.label)
                for t in matches]


_timelines_lock = threading.Lock()
_timelines: list = []  # weakrefs to live SessionTimelines  # guarded_by: _timelines_lock


def register_timelines(timelines: SessionTimelines) -> None:
    """Weakly register a pool's timeline log for /monitoring/sessions
    (telemetry must not extend a pool's lifetime)."""
    with _timelines_lock:
        _timelines[:] = [r for r in _timelines if r() is not None]
        _timelines.append(weakref.ref(timelines))


def _registered_timelines() -> list[SessionTimelines]:
    with _timelines_lock:
        refs = list(_timelines)
    return [t for t in (r() for r in refs) if t is not None]


def _stamp() -> tuple[float, float]:
    """Now on the spans' clock and on the calling thread's CPU clock:
    the two ends of a phase of the tick loop are read together, so that
    the phase carries the CPU its thread spent inside it (`cpu_us`). A
    phase whose CPU is a tenth of its length was waiting: for a lock,
    for the interpreter, for the device."""
    return time.perf_counter(), time.thread_time()


def _cpu_us(since: float, now: float) -> int:
    """Between two readings of a thread's CPU clock, in microseconds."""
    return int((now - since) * 1e6)


def _wake_and_fetch(of_round: "TickRound", launched: tuple, outputs):
    """The second half of a pool's tick, after `of_round.launched()` and
    the pool's bookkeeping: closes `decode/wake` (begun at `launched`,
    the `_stamp` of the enqueue's end: the wake-up of the round before's
    riders ran inside the pool's lock, then the bookkeeping), waits for
    the program's outputs under `decode/fetch`, and tells the round when
    that ended."""
    from min_tfs_client_tpu.servables.servable import fetch_outputs

    woke = _stamp()
    tracing.add_span(
        "decode/wake", launched[0], woke[0], round=of_round.ordinal,
        woken=of_round.woken, under_pool_lock=1,
        cpu_us=_cpu_us(launched[1], woke[1]))
    with tracing.span("decode/fetch", round=of_round.ordinal) as fetch:
        fetched = fetch_outputs(outputs)
        cpu = time.thread_time()
        fetch.args["cpu_us"] = _cpu_us(woke[1], cpu)
    of_round.fetched, of_round.fetched_cpu = time.perf_counter(), cpu
    return fetched


def _note_tick_cost(label: str, busy_s: float) -> None:
    """Report one tick-loop device round to the duty-cycle registry
    (observability/costs.py -> tpu_serving_tick_utilization). One call
    per device round — amortized over every session the tick advanced,
    never per token."""
    try:
        from min_tfs_client_tpu.observability import costs

        costs.note_tick(label, busy_s)
    except Exception:  # pragma: no cover - telemetry must not break ticks
        pass


# Default event cap for the LIST view: the summary must stay scrapeable
# with hundreds of live sessions; ?session= detail returns the full ring.
_LIST_VIEW_EVENTS = 8


def sessions_payload(session: Optional[str] = None,
                     max_events: Optional[int] = None) -> dict:
    """The /monitoring/sessions payload. Bare: one summary block per
    registered pool (live + recently-closed sessions, last few events
    each). With `session`: every timeline matching that session id
    (live or archived, any pool) with its full event list."""
    if session is not None:
        timelines: list[dict] = []
        for tl in _registered_timelines():
            timelines.extend(tl.find(session, max_events))
        return {"session": session, "found": bool(timelines),
                "timelines": timelines}
    cap = _LIST_VIEW_EVENTS if max_events is None else max_events
    return {"pools": [tl.snapshot(cap) for tl in _registered_timelines()]}


class DecodeSessionStore:
    """session id (bytes) -> opaque device-state pytree; TTL + capacity.

    on_evict(state) fires whenever the store drops an entry WITHOUT
    handing ownership to a caller — TTL sweep, close(), clear() — so a
    slot-pooled state (an int slot index) can return to the free list.
    take() transfers ownership and does not fire it.
    """

    def __init__(self, *, max_sessions: int = 64, ttl_s: float = 600.0,
                 metric_label: str = "default",
                 on_evict: Optional[Callable[[object], None]] = None):
        self._lock = threading.Lock()
        self._states: dict[bytes, tuple[object, float]] = {}
        self._max = max_sessions
        self._ttl = ttl_s
        self._metric_label = metric_label
        self._on_evict = on_evict

    def set_metric_label(self, label: str) -> None:
        """Re-label the gauge cell (the loader knows the model name and
        version; the family builder does not). Distinct stores must carry
        distinct labels or they overwrite each other's cell."""
        with self._lock:
            self._metric_label = label
            self._report()

    def _report(self) -> None:
        """Called under self._lock after every mutation."""
        try:
            from min_tfs_client_tpu.server import metrics
        except Exception:  # servelint: fallback-ok metrics unimportable
            return  # means there is no channel to record with
        metrics.safe_set(metrics.decode_session_count, len(self._states),
                         self._metric_label)

    def __len__(self) -> int:
        with self._lock:
            return len(self._states)

    def __contains__(self, session_id: bytes) -> bool:
        """Membership WITHOUT the TTL sweep (a liveness probe must not
        mutate) — the StepDeduper's is_live oracle."""
        with self._lock:
            return session_id in self._states

    def put(self, session_id: bytes, state: object) -> None:
        """Insert/refresh a session. A NEW session past capacity raises
        RESOURCE_EXHAUSTED after TTL sweeping (backpressure at init time;
        active sessions are never silently evicted mid-generation)."""
        now = time.monotonic()
        with self._lock:
            self._sweep_locked(now)
            if (session_id not in self._states
                    and len(self._states) >= self._max):
                raise ServingError.resource_exhausted(
                    f"decode session capacity ({self._max}) reached; close "
                    "idle sessions or raise max_sessions")
            displaced = self._states.get(session_id)
            # A re-init over a live session drops the old state without
            # handing it to anyone — fire on_evict (slot reclamation) the
            # same as sweep/close, unless it's the same state coming back
            # from a take()/put() step cycle.
            if (displaced is not None and self._on_evict is not None
                    and displaced[0] is not state):
                self._on_evict(displaced[0])
            self._states[session_id] = (state, now)
            self._report()

    def take(self, session_id: bytes) -> object:
        """Remove and return the state (the caller owns it until it puts
        an updated state back). Popping makes concurrent steps on one
        session fail loudly instead of racing on donated buffers."""
        with self._lock:
            self._sweep_locked(time.monotonic())
            entry = self._states.pop(session_id, None)
            self._report()
        if entry is None:
            raise ServingError.not_found(
                f"decode session {session_id!r} does not exist (never "
                "initialized, expired, closed, or a step is in flight)")
        return entry[0]

    def close(self, session_id: bytes) -> bool:
        with self._lock:
            entry = self._states.pop(session_id, None)
            self._report()
        # Outside the lock: a pooled state's on_evict waits for the tick
        # in flight to launch (TickBatcher.release) and for the pool's
        # lock, a launch's length together, and every `take` of every
        # other session would queue behind it: on the gRPC event loop,
        # where the steps run, that is every request of the process.
        if entry is not None and self._on_evict is not None:
            self._on_evict(entry[0])
        return entry is not None

    def clear(self) -> None:
        with self._lock:
            if self._on_evict is not None:
                for state, _ in self._states.values():
                    self._on_evict(state)
            self._states.clear()
            self._report()

    def _sweep_locked(self, now: float) -> None:
        """TTL sweep only: a session that stopped stepping frees its HBM
        after ttl_s; live sessions are never evicted."""
        expired = [sid for sid, (_, t) in self._states.items()
                   if now - t > self._ttl]
        for sid in expired:
            state, _ = self._states.pop(sid)
            if self._on_evict is not None:
                self._on_evict(state)
        if expired:
            self._report()


class StepDeduper:
    """At-most-once decode steps: the per-session (ordinal, response)
    cache that makes retry-on-UNAVAILABLE honest for sessioned traffic.

    A decode step that fails AMBIGUOUSLY (connection died after the
    request was fully sent) may or may not have ticked the session —
    resending it blind could advance the stream twice, which is why the
    router and client refuse to retry bare sessioned requests
    (docs/ROUTING.md, http_pool's idempotency discipline). The ordinal
    closes that hole from the SERVER side: a step request carrying a
    monotonic per-session `step_ordinal` is executed at most once —

     * a NEW ordinal (first seen, or last+1) ticks and caches the
       response under that ordinal;
     * the SAME ordinal again (a retry of an ambiguous failure) returns
       the cached response — bit-identical bytes, no tick;
     * anything else (gaps, rewinds) is a typed FAILED_PRECONDITION:
       the client's bookkeeping is broken and silently ticking would
       corrupt the stream it was trying to protect.

    Ordinal-less steps bypass this entirely (today's wire behavior,
    byte-for-byte); mixing guarded and bare steps on one session voids
    the guard for the bare steps only. Entries survive session
    exhaustion (the LAST step's retry must still answer from cache
    after the pool slot is gone) and are dropped on decode_close, on a
    re-init of the same id, or — past the size bound — by shedding
    DEAD sessions' entries oldest-first. With `is_live` wired (the
    session store's membership test), a LIVE session's entry is NEVER
    silently evicted: voiding a live guard would turn the advertised
    safe-retry into exactly the double-tick it exists to prevent, so
    the cache prefers growing to the live-session count (itself
    bounded by the store's capacity backpressure) over breaking the
    contract. Every shed entry is flight-recorded."""

    def __init__(self, max_entries: int = 256, is_live=None):
        self._lock = threading.Lock()
        self._max = max(8, int(max_entries))
        self._is_live = is_live
        # sid -> (ordinal, outputs); OrderedDict as LRU.
        self._cache: "collections.OrderedDict[bytes, tuple]" = \
            collections.OrderedDict()  # guarded_by: self._lock
        # sid -> ordinal currently EXECUTING (replay marked it, commit/
        # abandon clears it): a duplicate racing the original mid-tick
        # must answer typed-retryable, not fall through to the store's
        # NOT_FOUND ("a step is in flight") and kill a healthy stream.
        self._pending: dict[bytes, int] = {}  # guarded_by: self._lock

    def replay(self, session_id: bytes,
               ordinal: Optional[int]) -> Optional[dict]:
        """The cached response when `ordinal` is a duplicate resend;
        None when the step should execute — in which case the ordinal
        is marked IN FLIGHT until commit() or abandon(). A duplicate
        arriving while the original still executes raises a typed
        retryable UNAVAILABLE (the retry tiers back off and collect the
        cached response once the original commits). Out-of-order
        ordinals raise FAILED_PRECONDITION. `ordinal` None = unguarded
        step: always execute, never marked."""
        if ordinal is None:
            return None
        if ordinal < 1:
            raise ServingError.invalid_argument(
                f"step_ordinal must be >= 1, got {ordinal}")
        last = None
        with self._lock:
            if self._pending.get(session_id) == ordinal:
                raise ServingError.unavailable(
                    f"step_ordinal {ordinal} is already executing for "
                    "this session (the first attempt is in flight) — "
                    "retry to collect its response")
            entry = self._cache.get(session_id)
            if entry is not None:
                self._cache.move_to_end(session_id)
                last, outputs = entry
                if ordinal == last:
                    return outputs  # duplicate resend: cached, no tick
            if last is None or ordinal == last + 1:
                self._pending[session_id] = ordinal
                return None  # first guarded step / the next step
        raise ServingError.failed_precondition(
            f"step_ordinal {ordinal} is out of order for this session "
            f"(last executed: {last}; a retry must resend {last}, the "
            f"next step must send {last + 1})")

    def abandon(self, session_id: bytes,
                ordinal: Optional[int]) -> None:
        """The marked step FAILED before producing a response: clear
        the in-flight marker so a retry of the same ordinal executes
        (the failed attempt never ticked — errors propagate before the
        store re-parks state)."""
        if ordinal is None:
            return
        with self._lock:
            if self._pending.get(session_id) == ordinal:
                del self._pending[session_id]

    def commit(self, session_id: bytes, ordinal: Optional[int],
               outputs: dict) -> None:
        """Record an EXECUTED step's response before it leaves the
        server — a resend must replay even when the first reply never
        reached the client."""
        if ordinal is None:
            return
        shed = []
        with self._lock:
            if self._pending.get(session_id) == ordinal:
                del self._pending[session_id]
            self._cache[session_id] = (ordinal, outputs)
            self._cache.move_to_end(session_id)
            if len(self._cache) > self._max:
                for key in list(self._cache):
                    if len(self._cache) <= self._max:
                        break
                    if key == session_id:
                        continue
                    if self._is_live is not None:
                        if self._is_live(key):
                            # NEVER void a live session's guard — see
                            # the class docstring; the cache grows
                            # toward the (store-bounded) live count
                            # instead.
                            continue
                        del self._cache[key]
                        shed.append(key)
                    else:
                        # No liveness oracle (standalone use): plain
                        # LRU, still observable below.
                        del self._cache[key]
                        shed.append(key)
        for key in shed:
            try:
                from min_tfs_client_tpu.observability import (
                    flight_recorder,
                )

                flight_recorder.record(
                    "step_dedup_evict",
                    session=key.decode("utf-8", "replace")[:64])
            except Exception:  # pragma: no cover - evidence best-effort
                pass

    def forget(self, session_id: bytes) -> None:
        with self._lock:
            self._cache.pop(session_id, None)
            self._pending.pop(session_id, None)

    def __len__(self) -> int:
        with self._lock:
            return len(self._cache)


def read_step_ordinal(inputs) -> Optional[int]:
    """The optional `step_ordinal` wire input as a python int (scalar,
    any integer dtype), or None when the request doesn't carry it."""
    import numpy as np

    raw = inputs.get("step_ordinal")
    if raw is None:
        return None
    arr = np.asarray(raw).reshape(-1)
    if arr.size != 1:
        raise ServingError.invalid_argument(
            f"step_ordinal must hold exactly one value, got {arr.size}")
    try:
        return int(arr[0])
    except (TypeError, ValueError):
        raise ServingError.invalid_argument(
            f"step_ordinal must be an integer, got {arr.dtype}")


class SlotPool:
    """Continuous batching: S sessions stacked into ONE device state.

    The modern decode-serving design the reference has no analogue for
    (vLLM-style continuous batching), built the TPU way: session state
    lives in a statically-shaped slot pool (leaves `(S, 1, ...)` — S
    single-sequence sessions), one jitted `tick` advances every
    *requested* slot per device call (vmapped step + active-mask merge,
    pool buffers donated so caches update in place), and slots are
    recycled as sessions close or expire. K concurrent sessions cost one
    dispatch per token instead of K.

    step_fn(params, state) -> (new_state, outputs) must be pure over a
    single session's state (leaves `(1, ...)`). `params` rides as a jit
    ARGUMENT of the tick (a closed-over tree would be re-baked into the
    executable as constants — losing sharding constraints and int8
    residency for quantized weights); pass params=None and a
    single-argument step_fn for stateless tests.
    """

    def __init__(self, template_state, step_fn, *, max_slots: int,
                 params=None, metric_label: str = "dense"):
        import jax
        import jax.numpy as jnp

        self._jax = jax
        self.max_slots = max_slots
        self._params = params
        self.metric_label = metric_label
        self.timeline = SessionTimelines(label=metric_label)
        shapes = jax.eval_shape(lambda: template_state)
        self._pool = jax.tree_util.tree_map(
            lambda sd: jnp.zeros((max_slots,) + sd.shape, sd.dtype), shapes)
        self._lock = threading.Lock()
        self._free = list(range(max_slots))

        def write_fn(pool, state, slot):
            def upd(p, s):
                return jax.lax.dynamic_update_slice(
                    p, s[None].astype(p.dtype),
                    (slot,) + (0,) * s.ndim)
            return jax.tree_util.tree_map(upd, pool, state)

        def tick_fn(params, pool, active):
            if params is None:
                new_pool, outputs = jax.vmap(step_fn)(pool)
            else:
                new_pool, outputs = jax.vmap(
                    lambda s: step_fn(params, s))(pool)

            def merge(n, o):
                mask = active.reshape((-1,) + (1,) * (n.ndim - 1))
                return jnp.where(mask, n, o)

            merged = jax.tree_util.tree_map(merge, new_pool, pool)
            return merged, outputs

        self._write_jit = jax.jit(write_fn, donate_argnums=(0,))
        self._tick_jit = jax.jit(tick_fn, donate_argnums=(1,))

    def acquire_slot(self) -> int:
        with self._lock:
            if not self._free:
                raise ServingError.resource_exhausted(
                    f"decode slot pool ({self.max_slots}) exhausted; close "
                    "idle sessions or raise max_slots")
            return self._free.pop()

    def release_slot(self, slot: int) -> None:
        self.timeline.close(slot)
        with self._lock:
            if slot not in self._free:
                self._free.append(slot)

    def write(self, state, slot: int, *, session_key=None) -> None:
        """Park a freshly-prefilled session state into its slot.
        `session_key` labels the slot's timeline at
        /monitoring/sessions (the wire-visible session id)."""
        self.timeline.begin(slot, session_key)
        with self._lock:
            self._pool = self._write_jit(self._pool, state,
                                         self._jax.numpy.int32(slot))

    def tick(self, slots: list[int],
             of_round: Optional[TickRound] = None) -> dict[int, dict]:
        """Advance the given slots in ONE device call; other slots'
        state is untouched (masked merge). Returns per-slot host outputs
        after a single overlapped fetch. `of_round` is the TickBatcher's
        (None for a direct call): the phase spans carry its ordinal."""
        import numpy as np

        from min_tfs_client_tpu.robustness import faults

        of_round = of_round or TickRound(0, 0.0)
        ordinal, entered = of_round.ordinal, _stamp()
        # Pre-tick faultpoint: a delay stretches every tick-mate's step,
        # a typed error fails the whole tick loudly (the TickBatcher
        # gives it to every rider of the round).
        faults.point("backend.tick.pre", slots=len(slots))
        t0 = time.perf_counter()
        with self._lock:
            active = np.zeros((self.max_slots,), bool)
            active[list(slots)] = True
            prepared = _stamp()
            tracing.add_span(
                "decode/prepare", entered[0], prepared[0],
                round=ordinal, slots=len(slots), live=len(slots),
                cpu_us=_cpu_us(entered[1], prepared[1]))
            with tracing.span("decode/tick", slots=len(slots),
                              round=ordinal) as launch:
                self._pool, outputs = self._tick_jit(
                    self._params, self._pool,
                    self._jax.numpy.asarray(active))
                cpu = time.thread_time()
                launch.args["cpu_us"] = _cpu_us(prepared[1], cpu)
            launched = (time.perf_counter(), cpu)
            of_round.launched()
        fetched = _wake_and_fetch(of_round, launched, outputs)
        round_s = time.perf_counter() - t0
        self.timeline.round_event("tick", dict.fromkeys(slots, ()),
                                  tick_ms=round(round_s * 1e3, 3))
        _note_tick_cost(self.metric_label, round_s)
        return {s: {k: np.asarray(v)[s] for k, v in fetched.items()}
                for s in slots}

    def step_cost(self, slot: int):
        """Per-step cost attribution hook (TickBatcher cost_fn). The
        dense pool has no page accounting — every slot pins its full
        max-length state, which HBM telemetry already covers."""
        return None


class PageAllocator:
    """Free-list allocator over the shared KV page arena.

    Pages are plain int indices into the (num_blocks + 1)-page arenas the
    PagedSlotPool owns (the extra page is the pool's trash page and is
    never allocated). Exhaustion is a TYPED capacity error —
    RESOURCE_EXHAUSTED at the handlers, never a bare RuntimeError that
    would serve as INTERNAL and trip the flight-recorder latch."""

    def __init__(self, num_blocks: int, *, metric_label: str = "default"):
        self.num_blocks = int(num_blocks)
        self._lock = threading.Lock()
        self._free = list(range(num_blocks))  # guarded_by: self._lock
        self._label = metric_label            # guarded_by: self._lock

    def set_metric_label(self, label: str) -> None:
        with self._lock:
            self._label = label
            self._report_locked()

    def _report_locked(self) -> None:
        """Gauge export rides page-allocation events only (a page turns
        over once per block_size tokens), never the per-token tick."""
        try:
            from min_tfs_client_tpu.server import metrics
        except Exception:  # servelint: fallback-ok metrics unimportable
            return  # means there is no channel to record with
        metrics.safe_set(metrics.kv_blocks_used,
                         self.num_blocks - len(self._free), self._label)
        metrics.safe_set(metrics.kv_blocks_total, self.num_blocks,
                         self._label)

    def try_alloc(self, n: int = 1) -> Optional[list[int]]:
        """n pages or None — callers with an eviction policy retry."""
        from min_tfs_client_tpu.robustness import faults

        # page_pressure fault = "the arena is full" WITHOUT filling
        # HBM: the caller walks its real eviction policy (swap/close/
        # refuse), which is exactly the path KV-pressure storms exist
        # to exercise. Gated on armed() so the DISARMED allocation path
        # pays one module-global read, never a lock just for the label.
        if faults.armed():
            with self._lock:
                label = self._label
            fired = faults.point("kv.alloc", label=label, n=n)
            if fired is not None and fired.page_pressure:
                return None
        with self._lock:
            if len(self._free) < n:
                return None
            pages = [self._free.pop() for _ in range(n)]
            self._report_locked()
            return pages

    def alloc(self, n: int = 1) -> list[int]:
        pages = self.try_alloc(n)
        if pages is None:
            raise ServingError.resource_exhausted(
                f"decode KV page pool exhausted ({self.used()} of "
                f"{self.num_blocks} blocks in use, {n} requested); close "
                "idle sessions, raise --kv_num_blocks, or enable eviction "
                "(--kv_evict_policy=swap)")
        return pages  # servelint: transfers caller

    def free(self, pages: list[int]) -> None:
        with self._lock:
            self._free.extend(pages)
            self._report_locked()

    def used(self) -> int:
        with self._lock:
            return self.num_blocks - len(self._free)


def _plain_path(path) -> tuple:
    """jax KeyPath -> plain (str | int, ...) tuple for paged-leaf match."""
    out = []
    for k in path:
        if hasattr(k, "key"):
            out.append(k.key)
        elif hasattr(k, "idx"):
            out.append(k.idx)
        elif hasattr(k, "name"):
            out.append(k.name)
        else:  # pragma: no cover - future key kinds
            out.append(str(k))
    return tuple(out)


# Sentinel a paged tick returns for a slot still streaming its prefill
# chunks: the session consumed a chunk round but has no token yet — the
# tick batcher keeps the slot due (other sessions' decode steps ride the
# same rounds) until a real row arrives.
PREFILL_PENDING = object()


class _SwappedSession:
    """Host-side copy of an evicted session's pages (bit-identical bf16/f32
    round trip; restored by scatter on the session's next tick)."""

    __slots__ = ("pages_host", "tokens", "n_pages")

    def __init__(self, pages_host: list, tokens: int, n_pages: int):
        self.pages_host = pages_host
        self.tokens = tokens
        self.n_pages = n_pages


class PagedSlotPool:
    """Block-table-paged continuous batching (ROADMAP open item 1).

    Same tick surface as SlotPool — S single-sequence sessions advanced by
    ONE jitted call per token — but KV-cache leaves live in shared page
    arenas instead of per-slot max-length blocks:

      * per cache leaf, ONE HBM arena laid out by ops/attention's
        `PagedKV.arena`: `(num_blocks + 1, block_size, F)`, a page
        block_size token rows, a row everything the leaf holds of one
        token; the last page is the trash page absorbing masked writes;
      * per session, a block table of int32 page indices grown ON DEMAND —
        a session holds ceil(used_tokens / block_size) pages, so
        concurrent-session capacity scales with tokens actually written,
        not max_decode_len × max_slots;
      * a free-list PageAllocator guarded by its own declared lock.

    One decode program, driven through the model's paging-aware step
    contract (`paged_step`, required): the tick hands the model a PagedKV
    handle (ops/attention.PagedKV): arenas + block tables + per-session
    lengths, no dense materialization. The model appends exactly this
    step's new K/V rows (inactive slots and padded chunk rows route to
    the trash page) and attends via ops/attention.paged_attention() — the
    ragged Pallas kernel on TPU, the gather oracle elsewhere — so
    per-tick KV reads scale with the pages sessions actually own, not the
    table width. The same contract powers chunked prefill
    (`prefill_chunk` rounds streaming a forced decoder prefix through the
    Sq>1 kernel path) and is what paged speculative verify blocks ride.

    Recycled pages are NOT zeroed: rows at or beyond a session's written
    length are masked inside the model (exp(NEG_INF) underflows to exactly
    0.0), so garbage never reaches an output — the paged-decode suite
    asserts token-exactness against the dense pool.

    Phase separation: `write()` only QUEUES a prefilled state (prefill
    phase); the next tick integrates pending prefills through a separate
    jitted write program — bounded per round, ticking slots first — before
    running the decode program, so a burst of long prefills cannot stall
    in-flight decodes.

    Eviction under pressure (`evict_policy`): when the free list runs dry,
      swap    gather the oldest-idle session's pages to host memory and
              free them; the session restores transparently (bit-identical)
              on its next tick;
      close   drop the oldest-idle session; its next step raises the typed
              capacity error (RESOURCE_EXHAUSTED);
      refuse  no eviction — the REQUESTING session's step fails with the
              typed capacity error and stays live for retry.
    """

    def __init__(self, template_state, *, max_slots: int, paging: Paging,
                 paged_step,
                 paged_axis_fn: Callable[[tuple], Optional[int]],
                 params=None, max_prefills_per_tick: int = 8,
                 metric_label: str = "default"):
        """`paging` sizes the pages, the arena, the eviction policy and
        the prefill chunk. `paged_axis_fn(path)` names the KV-cache
        leaves of the state and their paged (seq) axis. `paged_step` is
        the paging-aware step contract: an object with
          decode(params, tree, kv) -> (new_tree, kv, outputs)
          prefill_chunk(params, tree, kv, tokens, chunk_lens, next_tokens)
              -> (new_tree, kv)
        where `tree` is the session-state template with dense leaves
        slot-batched `(max_slots, *leaf)` and paged leaves replaced by
        None, and `kv` is an ops/attention.PagedKV keyed by the paged
        leaves' pytree paths. Both are traced (called inside jit, state
        donated); decode's outputs and every returned dense leaf must be
        slot-batched, inactive rows merge away."""
        import jax
        import jax.numpy as jnp

        self._jax = jax
        self._jnp = jnp
        self.max_slots = int(max_slots)
        self.block_size = paging.block_size
        self._params = params
        self._policy = paging.evict_policy
        self._max_prefills = int(max_prefills_per_tick)
        self.prefill_chunk = paging.prefill_chunk or paging.block_size
        self.metric_label = metric_label

        shapes = jax.eval_shape(lambda: template_state)
        flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
        self._treedef = treedef
        self._leaves = [leaf for _, leaf in flat]
        self._paths = [_plain_path(p) for p, _ in flat]
        paged_axes: dict[int, int] = {}
        seq_len = None
        for i, (path, leaf) in enumerate(flat):
            axis = paged_axis_fn(_plain_path(path))
            if axis is None:
                continue
            if leaf.shape[0] != 1:
                raise ValueError(
                    "paged sessions are single-sequence: leaf "
                    f"{_plain_path(path)} has batch dim {leaf.shape[0]}")
            if seq_len is None:
                seq_len = int(leaf.shape[axis])
            elif int(leaf.shape[axis]) != seq_len:
                raise ValueError(
                    "paged leaves must share one seq length (pages "
                    f"allocate in lockstep); got {leaf.shape[axis]} vs "
                    f"{seq_len} at {_plain_path(path)}")
            paged_axes[i] = int(axis)
        if not paged_axes:
            raise ValueError("paged_axis_fn matched no leaves")
        self._paged_axes = paged_axes
        self.max_len = seq_len
        self.pages_per_session = -(-seq_len // self.block_size)
        # Default: the same KV byte budget as the dense slot pool —
        # identical worst case, strictly better short-sequence packing.
        self.num_blocks = (paging.num_blocks
                           or self.max_slots * self.pages_per_session)
        self._trash = self.num_blocks  # extra arena page absorbing masked writes
        self.allocator = PageAllocator(self.num_blocks,
                                       metric_label=metric_label)

        # What one token holds of each paged leaf: the leaf's dims less
        # the singleton session batch and the paged axis, in their order.
        # (1, H, S, D) axis 2 => (H, D). PagedKV.arena lays an arena out
        # from it; nothing here spells an arena's shape.
        from min_tfs_client_tpu.ops.attention import PagedKV

        self._token_shapes: dict[int, tuple] = {}
        arena_bytes = 0
        dense_equiv = 0
        page_bytes_total = 0  # bytes one page holds across ALL paged leaves
        for i, axis in paged_axes.items():
            shape = self._leaves[i].shape
            self._token_shapes[i] = tuple(shape[1:axis]) \
                + tuple(shape[axis + 1:])
            itemsize = jnp.dtype(self._leaves[i].dtype).itemsize
            pages, *page = PagedKV.arena_shape(
                self.num_blocks, self.block_size, self._token_shapes[i])
            per_page = itemsize * math.prod(page)
            arena_bytes += pages * per_page
            page_bytes_total += per_page
            dense_equiv += self.max_slots * itemsize * math.prod(shape)
        self.arena_bytes = arena_bytes
        self.dense_equivalent_bytes = dense_equiv
        self.page_bytes = page_bytes_total

        self._lock = threading.Lock()
        # Tuples, not lists: the pools are identity-swapped wholesale under
        # the lock (jit donation invalidates the old buffers), never
        # mutated in place.
        self._arenas = tuple(
            PagedKV.arena(self.num_blocks, self.block_size,
                          self._token_shapes[i], self._leaves[i].dtype)
            for i in sorted(paged_axes))          # guarded_by: self._lock
        self._arena_pos = {i: k for k, i in enumerate(sorted(paged_axes))}
        self._dense_pool = tuple(
            None if i in paged_axes
            else jnp.zeros((self.max_slots,) + leaf.shape, leaf.dtype)
            for i, leaf in enumerate(self._leaves))  # guarded_by: self._lock
        self._free_slots = list(range(max_slots))  # guarded_by: self._lock
        self._pages: dict[int, list[int]] = {}     # guarded_by: self._lock
        self._tokens: dict[int, int] = {}          # guarded_by: self._lock
        self._last_tick: dict[int, float] = {}     # guarded_by: self._lock
        self._swapped: dict[int, _SwappedSession] = {}  # guarded_by: self._lock
        self._dead: dict[int, ServingError] = {}   # guarded_by: self._lock
        self._pending: dict[int, object] = {}      # guarded_by: self._lock
        self._prefix: dict[int, dict] = {}         # guarded_by: self._lock
        self._width = 1                            # guarded_by: self._lock
        self._gather_bytes_last = 0                # guarded_by: self._lock
        self._counters = {"prefill_flushed": 0, "decode_ticks": 0,
                          "evicted_swap": 0, "evicted_close": 0,
                          "restored": 0,
                          "prefill_chunks": 0}     # guarded_by: self._lock
        self._stats_lock = threading.Lock()
        self._stats_cache: dict = {}               # guarded_by: self._stats_lock
        # The tick loop's own counters (TickBatcher.counters:
        # decode_steps_ahead, decode_tokens_dropped), shown beside the
        # pool's; whoever drives the pool through a batcher sets it.
        self.loop_counters: Callable[[], dict] = dict
        # Pages held per slot at its most recent device round — the
        # per-step cost tap (step_cost). Its OWN cheap lock: a stepping
        # caller reading its page count must never queue behind the
        # pool lock, which is held across whole device ticks.
        self._page_ticks_lock = threading.Lock()
        self._page_ticks: dict[int, int] = {}  # guarded_by: self._page_ticks_lock
        # Per-session lifecycle event log behind /monitoring/sessions:
        # appended off the device path (tick events push after the
        # fetch), rings bound both axes.
        self.timeline = SessionTimelines(label=metric_label)

        dense_idx = [i for i in range(len(self._leaves))
                     if i not in paged_axes]

        def write_fn(dense_list, state_leaves, slot):
            """Prefill-phase program: scatter ONE session's dense leaves
            into the dense pool. Paged leaves are ignored — sessions start
            with zero used tokens and recycled-page garbage is masked."""
            out = list(dense_list)
            for i in dense_idx:
                s = state_leaves[i]
                out[i] = jax.lax.dynamic_update_slice(
                    dense_list[i], s[None].astype(dense_list[i].dtype),
                    (slot,) + (0,) * s.ndim)
            return out

        def _contract_tree(dense_list):
            """Session-state tree for the step contract: dense leaves
            slot-batched, paged leaves None (they live in the arenas the
            PagedKV handle carries)."""
            leaves = [dense_list[i] if i not in paged_axes else None
                      for i in range(len(self._leaves))]
            return jax.tree_util.tree_unflatten(treedef, leaves)

        def _contract_kv(arenas, tables, lengths, active):
            return PagedKV(
                {self._paths[i]: arenas[self._arena_pos[i]]
                 for i in paged_axes},
                tables, lengths,
                block_size=self.block_size, trash=self._trash,
                active=active)

        def _merge_dense(dense_list, new_tree, active):
            """Masked merge of the contract's returned dense leaves,
            matched BY PATH (the model returns paged leaves as None, so
            positional zip would mis-align on structure drift)."""
            new_by_path = {
                _plain_path(p): leaf for p, leaf in
                jax.tree_util.tree_flatten_with_path(new_tree)[0]}
            out = list(dense_list)
            for i in dense_idx:
                n = new_by_path[self._paths[i]]
                mask = active.reshape((-1,) + (1,) * (n.ndim - 1))
                out[i] = jnp.where(mask, n, dense_list[i])
            return out

        def direct_tick_fn(params, dense_list, arenas, tables, active,
                           lengths):
            """Decode-phase program: no dense materialization — the
            model appends this step's K/V rows and attends through the
            block tables (ops/attention.paged_attention). Table width W
            is a trace-time shape: a pow2 bucket (1, 2, 4, ... capped at
            pages_per_session) that grows when a live session needs more
            pages and shrinks only when the widest one departs — at most
            log2(pages_per_session)+1 compiles, not one per length."""
            kv = _contract_kv(arenas, tables, lengths, active)
            new_tree, kv, outputs = paged_step.decode(
                params, _contract_tree(dense_list), kv)
            out_dense = _merge_dense(dense_list, new_tree, active)
            out_arenas = [kv.arenas[self._paths[i]]
                          for i in sorted(paged_axes)]
            return out_dense, out_arenas, outputs

        def chunk_fn(params, dense_list, arenas, tables, tokens,
                     chunk_lens, next_tokens, lengths):
            """Chunked-prefill program: stream `prefill_chunk` forced
            decoder-prefix positions per chunking slot through the Sq>1
            contract path. chunk_lens[slot] == 0 marks a slot not
            chunking this round; a short final chunk's padded rows route
            to the trash page inside the contract's append."""
            active = chunk_lens > 0
            kv = _contract_kv(arenas, tables, lengths, active)
            new_tree, kv = paged_step.prefill_chunk(
                params, _contract_tree(dense_list), kv, tokens,
                chunk_lens, next_tokens)
            out_dense = _merge_dense(dense_list, new_tree, active)
            out_arenas = [kv.arenas[self._paths[i]]
                          for i in sorted(paged_axes)]
            return out_dense, out_arenas

        def gather_fn(arenas, table_row):
            """Swap-out program: one session's pages, trash-padded up to a
            pow2 width bucket (_swap_width) — transfer and host RAM scale
            with what the victim actually holds, and eviction compiles are
            bounded at log2(pages_per_session)+1 buckets."""
            return [arena[table_row] for arena in arenas]

        def restore_fn(arenas, pages_list, table_row):
            out = []
            for arena, pages in zip(arenas, pages_list):
                out.append(arena.at[table_row].set(pages.astype(arena.dtype)))
            return out

        from min_tfs_client_tpu.observability import runtime as rt

        self._write_jit = rt.instrument_jit(
            f"paged:{metric_label}:prefill_write",
            jax.jit(write_fn, donate_argnums=(0,)))
        self._tick_jit = rt.instrument_jit(
            f"paged:{metric_label}:tick_direct",
            jax.jit(direct_tick_fn, donate_argnums=(1, 2)))
        self._chunk_jit = rt.instrument_jit(
            f"paged:{metric_label}:prefill_chunk",
            jax.jit(chunk_fn, donate_argnums=(1, 2)))
        self._gather_jit = jax.jit(gather_fn)
        self._restore_jit = jax.jit(restore_fn, donate_argnums=(0,))
        with self._lock:
            self._publish_stats_locked()
        rt.register_kv_pool(self)

    # -- labels / telemetry ---------------------------------------------------

    def set_metric_label(self, label: str) -> None:
        self.metric_label = label
        self.allocator.set_metric_label(label)
        self.timeline.label = label

    def stats(self) -> dict:
        """Last published snapshot. Reads ONLY the stats lock — the pool
        lock is held across whole device ticks and swap-out D2H, so a
        monitoring scrape must never queue behind it (the off-the-hot-path
        discipline the /monitoring/runtime payload promises). Mutators
        publish via _publish_stats_locked."""
        with self._stats_lock:
            snap = dict(self._stats_cache)
        snap.update(self.loop_counters())
        return snap

    def _publish_stats_locked(self) -> None:
        """Called under self._lock at the end of every state-changing
        public operation; the snapshot swap itself takes only the cheap
        stats lock (pool lock -> stats lock, never reversed)."""
        snap = {
            "block_size": self.block_size,
            "num_blocks": self.num_blocks,
            "blocks_used": self.allocator.used(),
            "max_slots": self.max_slots,
            "sessions": len(self._pages) + len(self._pending)
            + len(self._swapped),
            "swapped_sessions": len(self._swapped),
            "swapped_host_bytes": int(sum(
                h.nbytes for s in self._swapped.values()
                for h in s.pages_host)),
            "pending_prefills": len(self._pending),
            "table_width": self._width,
            "evict_policy": self._policy,
            "arena_bytes": self.arena_bytes,
            "dense_equivalent_bytes": self.dense_equivalent_bytes,
            # Wire surface of /monitoring/runtime and the fleet view: the
            # contract is the pool's only decode program.
            "step_contract": True,
            "prefill_chunk_size": self.prefill_chunk,
            "chunking_sessions": len(self._prefix),
            "kv_gather_bytes_per_tick": self._gather_bytes_last,
            **dict(self._counters),
        }
        with self._stats_lock:
            self._stats_cache = snap

    # -- slots ----------------------------------------------------------------

    def acquire_slot(self) -> int:
        with self._lock:
            if not self._free_slots:
                raise ServingError.resource_exhausted(
                    f"decode slot pool ({self.max_slots}) exhausted; close "
                    "idle sessions or raise max_slots")
            return self._free_slots.pop()

    def release_slot(self, slot: int) -> None:
        with self._lock:
            self._release_locked(slot)
            self._publish_stats_locked()

    def _release_locked(self, slot: int) -> None:
        self.timeline.close(slot)
        with self._page_ticks_lock:
            # A reused slot must not report the dead session's pages
            # before its own first tick (pool lock -> page-ticks lock,
            # never reversed).
            self._page_ticks.pop(slot, None)
        self._pending.pop(slot, None)
        self._prefix.pop(slot, None)
        self._dead.pop(slot, None)
        self._swapped.pop(slot, None)
        self._tokens.pop(slot, None)
        self._last_tick.pop(slot, None)
        pages = self._pages.pop(slot, None)
        if pages:
            self.allocator.free(pages)
        if slot not in self._free_slots:
            self._free_slots.append(slot)
        self._shrink_width_locked()

    def _shrink_width_locked(self) -> None:
        """Table-width shrink: when the high-water session departs, drop
        the pow2 width bucket back to what live sessions actually hold —
        one long-dead outlier must not pin wide (recompile-prone) tick
        shapes forever. Growth stays monotone within a session's life;
        shrink only fires on close/eviction, so compile count stays
        bounded by churn of the longest session, not by tokens."""
        held = max((len(p) for p in self._pages.values()), default=0)
        target = min(self.pages_per_session,
                     1 << max(0, held - 1).bit_length())
        if target < self._width:
            self._width = max(1, target)

    # -- prefill phase --------------------------------------------------------

    def write(self, state, slot: int, *, prefill_inputs=None,
              prefill_next: int = 0, session_key=None) -> None:
        """Queue a freshly-prefilled session (PREFILL phase). The state is
        integrated by the next tick's write program, so a long prefill
        burst never blocks in-flight decode rounds on the pool lock.

        `prefill_inputs` (1-D int array) queues a forced decoder prefix
        for CHUNKED prefill: the positions stream through the step
        contract's Sq>1 path `prefill_chunk` tokens per round, interleaved
        with in-flight decode ticks, instead of one monolithic prefill
        stalling the pool. `prefill_next` is the input token the first
        decode step after the prefix consumes."""
        import numpy as np

        self.timeline.begin(slot, session_key)
        with self._lock:
            self._pending[slot] = state
            if prefill_inputs is not None:
                inputs = np.asarray(prefill_inputs, np.int32).reshape(-1)
                if inputs.size > self.max_len:
                    raise ServingError.invalid_argument(
                        f"decoder prefix ({inputs.size} positions) exceeds "
                        f"max_decode_len {self.max_len}")
                if inputs.size:
                    self._prefix[slot] = {"inputs": inputs,
                                          "next": int(prefill_next),
                                          "done": 0}
                    self.timeline.event(
                        slot, "prefill_queued", prefix_len=int(inputs.size),
                        chunk_tokens=self.prefill_chunk)
            self._last_tick[slot] = time.monotonic()
            self._publish_stats_locked()

    def flush_prefills(self, limit: Optional[int] = None) -> int:
        with self._lock:
            flushed = self._flush_prefills_locked(limit=limit)
            self._publish_stats_locked()
            return flushed

    def _flush_prefills_locked(self, limit: Optional[int] = None,
                               urgent: tuple = ()) -> int:
        """Integrate pending prefills: slots about to tick FIRST (their
        step must see the state), then up to `limit` others — the
        phase-aware admission bound keeping decode latency flat under an
        init flood."""
        order = [s for s in urgent if s in self._pending]
        order += [s for s in list(self._pending) if s not in set(order)]
        flushed = 0
        for slot in order:
            if (limit is not None and flushed >= limit
                    and slot not in urgent):
                break
            state = self._pending.pop(slot)
            leaves = self._jax.tree_util.tree_leaves(state)
            self._dense_pool = tuple(self._write_jit(
                self._dense_pool, leaves, self._jnp.int32(slot)))
            self._pages[slot] = []
            self._tokens[slot] = 0
            self.timeline.event(slot, "prefill_flush")
            flushed += 1
        self._counters["prefill_flushed"] += flushed
        return flushed

    # -- page management ------------------------------------------------------

    def _alloc_page_locked(self, busy: tuple, evict: bool = True) -> int:
        """One page, evicting by the pool's policy when the arena is dry.
        `evict` False (a step that runs ahead of its client,
        `TickRound.asked`) refuses instead: no session loses its pages
        to a token nobody has asked for yet."""
        if self._policy == "refuse" or not evict:
            return self.allocator.alloc(1)[0]
        while True:
            pages = self.allocator.try_alloc(1)
            if pages is not None:
                return pages[0]  # servelint: transfers caller
            victim = self._pick_victim_locked(busy)
            if victim is None:
                raise ServingError.resource_exhausted(
                    f"decode KV page pool exhausted ({self.num_blocks} "
                    "blocks) and no evictable session (every page holder "
                    "is in the current tick); close sessions or raise "
                    "--kv_num_blocks")
            self._evict_locked(victim)

    def _swap_width(self, n_pages: int) -> int:
        """Pow2 row width for the swap gather/restore programs: scales
        transfer + parked host bytes with the victim's real page count
        while keeping the compile count bounded (same bucket discipline
        as the tick's table width)."""
        return min(self.pages_per_session,
                   1 << max(0, n_pages - 1).bit_length())

    def _pick_victim_locked(self, busy: tuple) -> Optional[int]:
        """Oldest-idle session holding pages, excluding the current tick's
        slots (evicting a session mid-round would corrupt its gather)."""
        best, best_t = None, None
        for slot, pages in self._pages.items():
            if slot in busy or not pages:
                continue
            t = self._last_tick.get(slot, 0.0)
            if best_t is None or t < best_t:
                best, best_t = slot, t
        return best

    def _evict_locked(self, victim: int) -> None:
        from min_tfs_client_tpu.servables.servable import fetch_outputs

        pages = self._pages.pop(victim)
        tokens = self._tokens.pop(victim, 0)
        self._last_tick.pop(victim, None)
        if self._policy == "swap":
            import numpy as np

            row = np.full((self._swap_width(len(pages)),), self._trash,
                          np.int32)
            row[:len(pages)] = pages
            gathered = self._gather_jit(self._arenas, self._jnp.asarray(row))
            # servelint: blocks swap-out must complete before the freed
            # pages can be reallocated under this same lock
            host = fetch_outputs(
                {str(k): g for k, g in enumerate(gathered)})
            swap = _SwappedSession(
                [host[str(k)] for k in range(len(gathered))],
                tokens, len(pages))
            self._swapped[victim] = swap
            self._counters["evicted_swap"] += 1
            self._report_eviction("swap")
            self.timeline.event(
                victim, "swap_out", pages=len(pages), tokens=tokens,
                host_bytes=int(sum(h.nbytes for h in swap.pages_host)))
        else:
            self._dead[victim] = ServingError.resource_exhausted(
                "decode session preempted: KV page pool exhausted and "
                "kv_evict_policy=close dropped this oldest-idle session; "
                "re-run decode_init to start over")
            self._counters["evicted_close"] += 1
            self._report_eviction("close")
            self.timeline.event(victim, "evict_close",
                                pages=len(pages), tokens=tokens)
        self.allocator.free(pages)
        self._shrink_width_locked()

    def _restore_locked(self, slot: int, busy: tuple,
                        evict: bool = True) -> None:
        from min_tfs_client_tpu.observability import runtime

        swap = self._swapped.pop(slot)
        pages: list[int] = []
        try:
            for _ in range(swap.n_pages):
                pages.append(self._alloc_page_locked(busy, evict))
        except ServingError:
            if pages:
                self.allocator.free(pages)
            self._swapped[slot] = swap  # still restorable later
            raise
        import numpy as np

        row = np.full((self._swap_width(swap.n_pages),), self._trash,
                      np.int32)
        row[:swap.n_pages] = pages
        dev = [self._jax.device_put(h) for h in swap.pages_host]
        runtime.count_transfer(
            "host_to_device",
            int(sum(h.nbytes for h in swap.pages_host)))
        self._arenas = tuple(self._restore_jit(self._arenas, dev,
                                               self._jnp.asarray(row)))
        self._pages[slot] = pages
        self._tokens[slot] = swap.tokens
        self._counters["restored"] += 1
        self._report_eviction("restore")
        self.timeline.event(slot, "restore", pages=swap.n_pages,
                            tokens=swap.tokens)

    def _report_eviction(self, kind: str) -> None:
        try:
            from min_tfs_client_tpu.server import metrics

            metrics.kv_evictions.increment(self.metric_label, kind)
        except Exception:  # pragma: no cover - metrics must not break serving
            pass

    # -- decode phase ---------------------------------------------------------

    def tick(self, slots: list[int],
             of_round: Optional[TickRound] = None) -> dict[int, object]:
        """Advance the given slots in ONE device call (plus at most one
        chunked-prefill round for sessions still streaming a forced
        prefix). Returns per-slot host outputs;
        slots that could not run carry their TYPED error as the value
        (per-slot failure isolation — a capacity refusal for one session
        must not poison its tick-mates), and slots still mid-prefix carry
        the PREFILL_PENDING sentinel (the batcher keeps such a slot in
        its rounds, so tick-mates' decodes interleave with the remaining
        chunks). `of_round` is the TickBatcher's (None for a direct
        call): the phase spans carry its ordinal, `asked` says which
        slots may take another session's pages, and `launched()` is
        called once the program is enqueued."""
        import numpy as np

        from min_tfs_client_tpu.robustness import faults

        of_round = of_round or TickRound(0, 0.0)
        ordinal, entered = of_round.ordinal, _stamp()
        slots = list(slots)
        # Pre-tick faultpoint, OUTSIDE the pool lock: a delay models a
        # slow device round; a typed error fails the whole tick (the
        # TickBatcher gives it to every rider of the round).
        faults.point("backend.tick.pre", slots=len(slots), paged=True)
        results: dict[int, object] = {}
        live: list[int] = []
        outputs = None
        ticked: dict[int, tuple] = {}  # slot -> (tokens, pages) after it
        t0 = time.perf_counter()
        with self._lock:
            self._flush_prefills_locked(
                limit=self._max_prefills, urgent=tuple(slots))
            chunk_errors: dict[int, ServingError] = {}
            if self._prefix:
                with tracing.span("decode/prefill_chunk"):
                    chunk_errors = self._run_chunk_round_locked(
                        requested=tuple(slots), asks=of_round.asks)
            # The steps that are asked for first: one of them may take
            # the pages of a session that only runs ahead in this round
            # (whose own step is then refused, and waits for its
            # request), never the other way round.
            asked = frozenset(s for s in slots if of_round.asks(s))
            for s in sorted(slots, key=lambda s: s not in asked):
                err = self._dead.get(s)
                if err is not None:
                    err.slot_fatal = True
                    results[s] = err
                    continue
                if s in chunk_errors:
                    # A capacity refusal mid-prefix must surface to the
                    # requester (session + progress intact, retryable) —
                    # swallowing it would spin the caller on
                    # PREFILL_PENDING with no possible progress.
                    results[s] = chunk_errors[s]
                    continue
                if s in self._prefix:
                    results[s] = PREFILL_PENDING
                    continue
                try:
                    self._prepare_slot_locked(s, busy=asked,
                                              evict=s in asked)
                except ServingError as exc:
                    if not hasattr(exc, "slot_fatal"):
                        # Capacity refusal: the session's pages/state are
                        # intact; the caller may retry after closing others.
                        exc.slot_fatal = False
                    results[s] = exc
                    continue
                live.append(s)
            if live:
                width = self._width
                tables = np.full((self.max_slots, width), self._trash,
                                 np.int32)
                for s, pages in self._pages.items():
                    tables[s, :len(pages)] = pages
                active = np.zeros((self.max_slots,), bool)
                active[live] = True
                lengths = np.zeros((self.max_slots,), np.int32)
                for s, t in self._tokens.items():
                    lengths[s] = t
                # What the ragged kernel actually reads: the pages
                # live sessions own — not slots × table width.
                gather_pages = sum(len(self._pages[s]) for s in live)
                gather_bytes = self.page_bytes * gather_pages
            prepared = _stamp()
            tracing.add_span(
                "decode/prepare", entered[0], prepared[0],
                round=ordinal, slots=len(slots), live=len(live),
                cpu_us=_cpu_us(entered[1], prepared[1]))
            if live:
                # Times the three sends and the ENQUEUE of the program:
                # its run on the device ends under `decode/fetch`.
                with tracing.span("decode/tick", slots=len(live),
                                  round=ordinal, width=width,
                                  pages=gather_pages) as launch:
                    dense, arenas, outputs = self._tick_jit(
                        self._params, self._dense_pool, self._arenas,
                        self._jnp.asarray(tables),
                        self._jnp.asarray(active),
                        self._jnp.asarray(lengths))
                    cpu = time.thread_time()
                    launch.args["cpu_us"] = _cpu_us(prepared[1], cpu)
                launched = (time.perf_counter(), cpu)
                # The program is enqueued: the riders of the round before
                # may go (`decode/wake`: their wake-up, here under the
                # pool's lock, and the bookkeeping below, the pool's own).
                of_round.launched()
                self._dense_pool = tuple(dense)
                self._arenas = tuple(arenas)
                now = time.monotonic()
                for s in live:
                    self._tokens[s] += 1
                    self._last_tick[s] = now
                    ticked[s] = (self._tokens[s], len(self._pages[s]))
                self._counters["decode_ticks"] += 1
                self._gather_bytes_last = gather_bytes
                self._report_gather_bytes(gather_bytes)
            self._publish_stats_locked()
        of_round.launched()  # a round that enqueued nothing
        if live:
            fetched = _wake_and_fetch(of_round, launched, outputs)
            self.timeline.round_event(
                "tick", ticked, ("tokens", "pages"),
                tick_ms=round((time.perf_counter() - t0) * 1e3, 3))
            # Publish each advanced session's page count for the
            # per-step cost tap (pages x ticks): one cheap lock, never
            # while a device call is in flight.
            with self._page_ticks_lock:
                self._page_ticks.update(
                    (s, pages) for s, (_, pages) in ticked.items())
            for s in live:
                results[s] = {k: np.asarray(v)[s] for k, v in fetched.items()}
        _note_tick_cost(self.metric_label, time.perf_counter() - t0)
        return results

    def step_cost(self, slot: int):
        """Per-step cost attribution (TickBatcher cost_fn): the KV
        pages this session held at its most recent device round — one
        step's pages x ticks contribution to its cost vector
        (observability/costs.py)."""
        with self._page_ticks_lock:
            pages = self._page_ticks.get(slot, 0)
        return {"kv_page_ticks": float(pages)} if pages else None

    def _report_gather_bytes(self, gather_bytes: int) -> None:
        try:
            from min_tfs_client_tpu.server import metrics

            metrics.safe_set(metrics.kv_gather_bytes_per_tick, gather_bytes,
                             self.metric_label)
        except Exception:  # pragma: no cover - metrics must not break serving
            pass

    def _run_chunk_round_locked(self, requested: tuple,
                                asks=lambda slot: True) -> dict:
        """ONE chunked-prefill round: stream the next `prefill_chunk`
        forced-prefix positions for up to max_prefills_per_tick chunking
        slots (requested slots always ride — their callers are parked on
        this very round) through the contract's Sq>1 program. Bounded per
        tick so an init flood of long prefixes cannot stall in-flight
        decodes; still-chunking slots get PREFILL_PENDING and stay in the
        rounds, so chunks interleave with tick-mates' decode steps.
        `asks(slot)`: whether the slot's step is asked for
        (TickRound.asks); the others take no page from another session.
        Returns {slot: ServingError} for REQUESTED slots whose chunk hit
        a capacity refusal (progress intact, caller retries)."""
        import numpy as np

        errors: dict[int, ServingError] = {}
        urgent = [s for s in requested if s in self._prefix]
        order = urgent + [s for s in self._prefix if s not in set(urgent)]
        order.sort(key=lambda s: not asks(s))  # as in tick: asked first
        # Only flushed sessions hold a block table; unflushed ones catch
        # the next round after their write-program flush.
        ready = [s for s in order
                 if s in self._pages or s in self._swapped]
        chosen = ready[:max(self._max_prefills, len(urgent))]
        if not chosen:
            return errors
        busy = tuple(s for s in set(chosen) | set(requested) if asks(s))
        chunk = self.prefill_chunk
        tokens = np.zeros((self.max_slots, chunk), np.int32)
        chunk_lens = np.zeros((self.max_slots,), np.int32)
        next_tokens = np.zeros((self.max_slots, 1), np.int32)
        lengths = np.zeros((self.max_slots,), np.int32)
        ran: list[tuple[int, int]] = []
        for s in chosen:
            pf = self._prefix[s]
            try:
                evict = asks(s)
                if s in self._swapped:
                    self._restore_locked(s, busy, evict)
                inputs, done = pf["inputs"], pf["done"]
                n = min(chunk, len(inputs) - done)
                needed = -(-(done + n) // self.block_size)
                while len(self._pages[s]) < needed:
                    self._pages[s].append(
                        self._alloc_page_locked(busy, evict))
                if needed > self._width:
                    grown = 1 << (needed - 1).bit_length()
                    self._width = min(self.pages_per_session, grown)
            except ServingError as exc:
                # Capacity refusal mid-prefix: the session keeps its
                # progress and retries; a REQUESTED slot's error surfaces
                # to its caller (else it would spin on PREFILL_PENDING
                # against a dry pool), others retry next round.
                if s in requested:
                    if not hasattr(exc, "slot_fatal"):
                        exc.slot_fatal = False
                    errors[s] = exc
                continue
            tokens[s, :n] = inputs[done:done + n]
            chunk_lens[s] = n
            next_tokens[s, 0] = (inputs[done + n]
                                 if done + n < len(inputs) else pf["next"])
            lengths[s] = done
            ran.append((s, n))
        if not ran:
            return errors
        width = self._width
        tables = np.full((self.max_slots, width), self._trash, np.int32)
        for s, pages in self._pages.items():
            tables[s, :len(pages)] = pages
        dense, arenas = self._chunk_jit(
            self._params, self._dense_pool, self._arenas,
            self._jnp.asarray(tables), self._jnp.asarray(tokens),
            self._jnp.asarray(chunk_lens), self._jnp.asarray(next_tokens),
            self._jnp.asarray(lengths))
        self._dense_pool = tuple(dense)
        self._arenas = tuple(arenas)
        now = time.monotonic()
        chunk_events: list[tuple] = []
        for s, n in ran:
            pf = self._prefix[s]
            pf["done"] += n
            self._tokens[s] = pf["done"]
            self._last_tick[s] = now
            self._counters["prefill_chunks"] += 1
            chunk_events.append(
                (s, "prefill_chunk",
                 {"done": pf["done"], "of": len(pf["inputs"]),
                  "chunk_tokens": n, "pages": len(self._pages[s])}))
            if pf["done"] >= len(pf["inputs"]):
                del self._prefix[s]
        self.timeline.events_many(chunk_events)
        self._report_prefill_chunks(len(ran))
        return errors

    def _report_prefill_chunks(self, n: int) -> None:
        try:
            from min_tfs_client_tpu.server import metrics

            metrics.kv_prefill_chunks.increment(self.metric_label,
                                                by=float(n))
        except Exception:  # pragma: no cover - metrics must not break serving
            pass

    def _prepare_slot_locked(self, slot: int, busy: tuple,
                             evict: bool = True) -> None:
        if slot in self._swapped:
            self._restore_locked(slot, busy, evict)
        if slot not in self._pages:
            raise _slot_fatal(
                f"slot {slot} holds no parked session state (released or "
                "never written)")
        needed = -(-(self._tokens[slot] + 1) // self.block_size)
        if needed > self.pages_per_session:
            raise _slot_fatal(
                f"slot {slot} stepped past max_len {self.max_len}")
        while len(self._pages[slot]) < needed:
            self._pages[slot].append(self._alloc_page_locked(busy, evict))
        if needed > self._width:
            grown = 1 << (needed - 1).bit_length()
            self._width = min(self.pages_per_session, grown)


class TickRound:
    """One round of the TickBatcher, as the pool's `tick` sees it.

    ordinal   the per-batcher count that every phase span of the round
              carries, and the `decode/wait` of each step whose token it
              computes (0 for a direct call of `tick`).
    taken     when the round snapshotted its slots.
    asked     the slots of it that a `decode_step` was waiting for at
              that snapshot. The others run AHEAD of their clients: the
              pool takes no page from another session for them (a
              refusal is tried again once the request is there). None,
              a direct call: every slot is asked for.
    fetched   set by the tick when its fetch ended: where
              `decode/deliver` starts, and the next round's
              `decode/handoff`. `fetched_cpu`: the loop thread's CPU
              clock at that instant (`_stamp`), if the tick reads it.
    launched  the tick calls it once its program is enqueued (it takes
              no lock of the batcher's; the pools call it while they
              hold their own). From then on a slot of the round may be
              released and handed on, and the batcher wakes the riders
              of the round before (their answers are serialised under
              this round's program, not beside its launch). The batcher
              calls it itself when a tick returns without having done so.
    woken     how many riders of the round before that call woke: an
              argument of the tick's `decode/wake`, which runs from the
              end of `decode/tick` to the start of `decode/fetch`.

    The round is also where the loop thread's spans go (a loop thread
    has no request trace): it is the trace-like target active around
    the tick, and a request that collects a token of the round moves
    what is in `spans` onto its own trace (the first to come takes the
    phases, and whoever comes after `decode/deliver` was written takes
    that: each span is recorded once, as a leader's were).
    """

    __slots__ = ("ordinal", "taken", "asked", "fetched", "fetched_cpu",
                 "woken", "spans", "_on_launched", "_is_launched",
                 "_handed")

    def __init__(self, ordinal: int, taken: float,
                 asked: Optional[frozenset] = None):
        self.ordinal = ordinal
        self.taken = taken
        self.asked = asked
        self.fetched: Optional[float] = None
        self.fetched_cpu: Optional[float] = None
        self.woken = 0
        self.spans: list[tuple] = []  # (name, t0, t1, args|None)
        # The batcher's: what `launched` runs, the event it sets (a
        # release of one of the round's slots waits for it), and the
        # waiters that hold a row of the round (the loop thread's alone).
        self._on_launched = None
        self._is_launched = threading.Event()
        self._handed: list = []

    def asks(self, slot: int) -> bool:
        return self.asked is None or slot in self.asked

    def launched(self) -> None:
        done, self._on_launched = self._on_launched, None
        if done is not None:
            done()

    # The trace-like surface (`tracing.activate`): spans only.
    def add_span(self, name: str, t0: float, t1: float,
                 args: Optional[dict] = None) -> None:
        self.spans.append((name, t0, t1, args))

    def annotate(self, **kv) -> None:
        pass


class _Waiter:
    """One `decode_step` waiting for its token."""

    __slots__ = ("ready", "outcome")

    def __init__(self, ready):
        # Set (a thread's Event) or resolved (a coroutine's future) when
        # the request may leave with `outcome`: each waiter has its own,
        # so that a round wakes its riders and nobody else.
        self.ready = ready
        self.outcome = None  # (row, round, raised)


class _SlotEntry:
    """One open session, as the loop sees it. A new session on the same
    slot number is a new entry: a round hands its rows to the entries it
    snapshotted, never to a slot number."""

    __slots__ = ("room", "due_at", "round", "parked", "waiter")

    def __init__(self, room: int, due_at: Optional[float]):
        self.room = room        # tokens the session's cache still takes
        self.due_at: Optional[float] = due_at  # due since; None: not due
        self.round: Optional[TickRound] = None  # the one in flight
        self.parked = None      # (row, round, raised): not collected yet
        self.waiter: Optional[_Waiter] = None


class TickBatcher:
    """Keeps every open session ONE token ahead of its client.

    `decode_step` carries no token from the client, so an open session's
    stream is fixed by its state: the batcher computes token k+1 as soon
    as token k has been collected, without waiting to be asked, and the
    client's round trip runs under the next device program.

    A slot is DUE for the next round when its session is open (`admit`),
    no token of it is parked uncollected, none is in flight, and its
    cache has room. `step(slot)` COLLECTS: it takes the parked token if
    there is one (no wait, no device work), else waits for the round
    that computes it; collecting makes the slot due again. The rounds
    run on a loop thread of the batcher's own, started when a slot
    becomes due and no loop runs, and ended when nothing is due (so
    nothing is in flight either): no thread outlives its work. One tick
    is in flight at a time. A round: fetch N; under the lock hand
    each row to the request that waits for it (that slot is due at once)
    or park it; snapshot N+1 = every due slot; prepare and enqueue N+1;
    only then wake N's riders (`TickRound.launched`).

    What a step that runs ahead may not do: a per-slot refusal
    (`slot_fatal` False) of a step nobody had asked for at the snapshot
    is not parked; the slot is simply not ahead, and is due again (asked
    for, so the pool may evict for it) once its request is there. A
    slot-fatal error, and an exception of the whole tick, are parked
    like a token and reach the slot's next `step`. `release(slot)` drops
    a parked token and the row of a round in flight.
    Same-slot serialization is the session store's job (take/put).

    Spans (docs/OBSERVABILITY.md "Decode loop phases"): every step
    records `decode/wait` on its own trace, entry to the snapshot of the
    round that computes its token (0 when the snapshot came first;
    `ahead=1` when the token was parked or its round snapshotted at
    entry). The loop records each round's `decode/handoff` (the previous
    round's fetch, or the round's first due slot if later, to the
    snapshot), the pool's prepare, tick, wake and fetch, and
    `decode/deliver` (end of the fetch to the riders' wake-up, which now
    comes after the next round's launch) on the round; the first request
    that collects a token of the round takes them onto its trace. The
    time in which nothing is due and no loop thread exists belongs to no
    round and no request: `decode/idle` on the tracing spine's host
    track, from the snapshot that found nothing to the next loop's
    first, so that the loop's spans tile its time.
    """

    def __init__(self, tick_fn, *, cost_fn=None):
        # (sorted list[slot], TickRound) -> {slot: result}
        self._tick_fn = tick_fn
        # Optional per-slot cost hook (pool.step_cost): charged onto the
        # trace of the request that collects the step.
        self._cost_fn = cost_fn
        self._lock = threading.Lock()
        self._slots: dict[int, _SlotEntry] = {}  # guarded_by: self._lock
        self._running = False     # guarded_by: self._lock
        self._rounds = 0          # guarded_by: self._lock
        # When the last loop thread found nothing due and ended (None:
        # a loop runs, or none has run yet).
        self._idle_since: Optional[float] = None  # guarded_by: self._lock
        # decode_steps_ahead: steps whose token was parked or under way
        # when they arrived; decode_tokens_dropped: tokens computed and
        # never collected (their session closed first).
        self._counters = {"decode_steps_ahead": 0,
                          "decode_tokens_dropped": 0}  # guarded_by: self._lock

    def counters(self) -> dict:
        with self._lock:
            return dict(self._counters)

    def _note_cost(self, slot: int) -> None:
        if self._cost_fn is None:
            return
        try:
            cost = self._cost_fn(slot)
        except Exception:  # servelint: fallback-ok cost attribution is
            return  # telemetry; a broken cost_fn must not break steps
        if cost:
            tracing.add_cost(**cost)

    # -- the sessions' side ---------------------------------------------------

    def admit(self, slot: int, room: int) -> None:
        """Open `slot` for a session whose cache takes `room` more
        tokens. The slot is due at once: its first token is under way
        before its first `decode_step` arrives."""
        with self._lock:
            self._slots[slot] = _SlotEntry(
                int(room), time.perf_counter() if room > 0 else None)
            self._start_loop_locked()

    def release(self, slot: int) -> None:
        """Forget `slot` (close, TTL eviction, exhaustion, a failed
        step). A parked token is dropped, and so is the row of a round in
        flight when it comes back. Returns once that round, if it has
        not enqueued its program yet, has: until then the pool's tick
        may still read the slot's state, and the slot number must not be
        handed to another session."""
        with self._lock:
            entry = self._slots.pop(slot, None)
            if entry is None:
                return
            if entry.parked is not None and _is_token(entry.parked):
                self._counters["decode_tokens_dropped"] += 1
            entry.parked = None
            waiter, entry.waiter = entry.waiter, None
            if waiter is not None:  # only a caller's own bug gets here
                waiter.outcome = (ServingError.not_found(
                    f"decode slot {slot} was released under its step"),
                    None, True)
                _set_ready([waiter])
            took = entry.round
        # Timed + loop-on-predicate (servelint DL003): the loop sets the
        # event in its `finally` too.
        while took is not None and not took._is_launched.wait(timeout=0.1):
            pass

    def step(self, slot: int):
        """Collect the slot's next token: its row, or the typed error the
        pool's tick gave for the slot (returned, not raised); an
        exception of the whole tick is raised. Blocks the calling thread
        until the round that computes the token has handed it out."""
        arrived = time.perf_counter()
        outcome, ahead, waiter = self._ask(slot, arrived, threading.Event)
        if waiter is not None:
            while not waiter.ready.wait(timeout=0.1):
                # Timed (servelint DL003): a loop lost to an
                # interpreter-level failure must not park its riders
                # forever; whoever times out starts the next.
                with self._lock:
                    self._start_loop_locked()
            outcome = waiter.outcome
        return self._answer(slot, outcome, arrived, ahead)

    async def astep(self, slot: int):
        """`step` for a caller on the gRPC event loop (utils/aio_loop):
        the same contract, but where `step` blocks its thread this one
        awaits, so the loop goes on answering other requests; the round
        that computes the token resolves the future from the tick
        loop's thread (`_wake`), every rider of a round in one call."""
        arrived = time.perf_counter()
        outcome, ahead, waiter = self._ask(
            slot, arrived, asyncio.get_running_loop().create_future)
        if waiter is not None:
            # No timeout, unlike `step`'s wait: no thread is parked here,
            # and a loop that dies settles its rounds on its way out
            # (`_run`'s finally), which resolves this.
            await waiter.ready
            outcome = waiter.outcome
        return self._answer(slot, outcome, arrived, ahead)

    def _ask(self, slot: int, arrived: float, make_ready):
        """The locked half of a step: (outcome, ahead, waiter). A parked
        token is collected here, and `waiter` is None; else `outcome` is
        None and the round that computes the token gives it to `waiter`
        and sets its `ready` (made by `make_ready`: an Event for a
        thread, a future for a coroutine)."""
        with self._lock:
            entry = self._slots.get(slot)
            if entry is None:
                return (_slot_fatal(f"decode slot {slot} is not open in "
                                    "the tick loop"), None, False), 0, None
            if entry.room <= 0 and entry.parked is None:
                return (_slot_fatal(f"decode slot {slot} stepped past its "
                                    "cache"), None, False), 0, None
            outcome, waiter = entry.parked, None
            ahead = outcome is not None or entry.round is not None
            if outcome is not None:
                entry.parked = None
                self._collected_locked(entry, outcome, arrived)
            else:
                waiter = entry.waiter = _Waiter(make_ready())
                if entry.round is None and entry.due_at is None:
                    # Not ahead (its step was refused while nobody asked
                    # for it): now that it is asked for, it is due.
                    entry.due_at = arrived
                self._start_loop_locked()
            self._counters["decode_steps_ahead"] += ahead
        return outcome, int(ahead), waiter

    def _answer(self, slot: int, outcome: tuple, arrived: float,
                ahead: int):
        """The rest of a step, under no lock: its span, the round's
        spans, its cost; the row, or the raise."""
        row, took, raised = outcome
        if took is not None:
            tracing.add_span("decode/wait", arrived,
                             max(arrived, took.taken),
                             round=took.ordinal, ahead=ahead,
                             inline=int(aio_loop.on_loop_thread()))
            trace = tracing.current_trace()
            try:
                # Each span of the round goes onto ONE trace: that of
                # the first request to collect a token of the round
                # after the loop wrote it (a pop is atomic; another
                # collector may empty the list under this one).
                while trace is not None:
                    trace.add_span(*took.spans.pop(0))
            except IndexError:
                pass
        if raised:
            raise row
        if not isinstance(row, Exception):
            self._note_cost(slot)
        return row

    def _collected_locked(self, entry: _SlotEntry, outcome: tuple,
                          now: float) -> None:
        """The entry's token went to its request: due again, if that
        was a token and the cache has room for another."""
        if _is_token(outcome) and entry.room > 0:
            entry.due_at = now
            self._start_loop_locked()

    # -- the loop's side ------------------------------------------------------

    def _start_loop_locked(self) -> None:
        if self._running or not any(
                e.due_at is not None for e in self._slots.values()):
            return
        self._running = True
        # Not a daemon: a tick in flight at interpreter exit is waited
        # for (the loop ends with its work, a round later at most).
        threading.Thread(target=self._run, name="decode-tick-loop",
                         daemon=False).start()

    def _snapshot_locked(self, now: float):
        """Every due slot, as the next round: (round, {slot: entry},
        when the first of them became due), or None."""
        batch = {s: e for s, e in self._slots.items()
                 if e.due_at is not None}
        if not batch:
            return None
        self._rounds += 1
        took = TickRound(
            self._rounds, now,
            frozenset(s for s, e in batch.items() if e.waiter is not None))
        first_due = min(e.due_at for e in batch.values())
        for e in batch.values():
            e.due_at, e.round = None, took
        return took, batch, first_due

    def _settle_locked(self, took: TickRound, batch: dict, results: dict,
                       err: Optional[BaseException], now: float) -> None:
        """Round `took` is back: each row to the request that waits for
        it, or parked; the rows of entries released meanwhile dropped."""
        for slot, entry in batch.items():
            entry.round = None
            if err is not None:
                outcome = (err, took, True)
            else:
                row = results.get(slot)
                if row is None:
                    row = ServingError.internal(
                        f"decode tick returned no row for slot {slot}")
                outcome = (row, took, False)
            if self._slots.get(slot) is not entry:
                if _is_token(outcome):
                    self._counters["decode_tokens_dropped"] += 1
                continue
            row = outcome[0]
            if row is PREFILL_PENDING:
                entry.due_at = now  # mid-prefix: due until a real token
                continue
            refused = (err is None and isinstance(row, Exception)
                       and not getattr(row, "slot_fatal", True))
            if refused and not took.asks(slot):
                # Nobody had asked for this step: the slot is not ahead.
                # Its request, if it has come since, makes it due.
                entry.due_at = now if entry.waiter is not None else None
                continue
            if _is_token(outcome):
                entry.room -= 1
            waiter, entry.waiter = entry.waiter, None
            if waiter is None:
                entry.parked = outcome
            else:
                # The request has its row; it leaves when the next round
                # is launched (`_wake`), not before.
                waiter.outcome = outcome
                took._handed.append(waiter)
                self._collected_locked(entry, outcome, now)

    def _run(self) -> None:
        back = None     # (round, batch, results, err): back, not settled
        flying = None   # (round, batch): in the tick
        # When the round before's fetch ended, on the spans' clock and
        # on this thread's CPU clock (a new thread's starts here).
        settled = (0.0, time.thread_time())
        ended = False   # left by the front door: `_running` is cleared
        try:
            while True:
                with self._lock:
                    now, cpu = _stamp()
                    if back is not None:
                        self._settle_locked(*back, now)
                    before = back[0] if back is not None else None
                    taken = self._snapshot_locked(now)
                    idle_since = None
                    if taken is not None:
                        idle_since, self._idle_since = self._idle_since, None
                    else:
                        self._running, ended = False, True
                        if self._idle_since is None:
                            self._idle_since = now
                back = None
                if taken is None:
                    self._wake(before, None)
                    return
                took, batch, first_due = taken
                if idle_since is not None:
                    tracing.process_span("decode/idle", idle_since,
                                         took.taken, restarted=1)
                took._on_launched = functools.partial(
                    self._wake, before, took)
                flying = (took, batch)
                # Time in which nothing was due is no hand-off (and the
                # thread's CPU since the fetch is the span's only as far
                # as the span is long).
                began = max(settled[0], first_due)
                took.add_span(
                    "decode/handoff", began, took.taken,
                    {"round": took.ordinal, "riders": len(batch),
                     "cpu_us": int(min(cpu - settled[1],
                                       took.taken - began) * 1e6)})
                results, err = {}, None
                try:
                    with tracing.activate(took):
                        results = self._tick_fn(sorted(batch), took)
                except Exception as exc:  # noqa: BLE001 - parked for the riders
                    err = exc
                settled = _stamp()
                if took.fetched is not None:
                    settled = (took.fetched, took.fetched_cpu or settled[1])
                took.launched()
                back, flying = (took, batch, results, err), None
        finally:
            # Only an interpreter-level failure leaves a round behind:
            # its riders get that as their tick's error, and the next
            # step that wakes starts a new loop.
            for lost in (flying, back):
                if lost is not None:
                    with self._lock:
                        self._settle_locked(
                            lost[0], lost[1], {},
                            RuntimeError("the decode tick loop died"),
                            time.perf_counter())
                    self._wake(lost[0], lost[0])
            if not ended:
                # A loop that ended by itself cleared the flag under the
                # lock of its last snapshot; a step may have started its
                # successor since, and clearing it again here would let a
                # third loop tick beside that one.
                with self._lock:
                    self._running = False

    def _wake(self, before: Optional[TickRound],
              took: Optional[TickRound]) -> None:
        """Round `took` is launched (None: there is no next round): wake
        the riders of the round `before` it, whose `decode/deliver` ends
        here, and whoever waits to release a slot of `took`. On the loop
        thread, under no lock of the batcher's; but a pool's tick calls
        `of_round.launched()` at its enqueue, INSIDE the pool's lock
        (`PagedSlotPool.tick`, `SlotPool.tick`), so the wake-up of
        `before`'s riders runs while that lock is held: the tick's
        `decode/wake` times it and says `under_pool_lock=1`."""
        now = time.perf_counter()
        if took is not None:
            took._is_launched.set()
        if before is not None:
            before.add_span("decode/deliver", before.fetched or now, now,
                            {"round": before.ordinal})
            handed, before._handed = before._handed, []
            if took is not None:
                took.woken = len(handed)
            _set_ready(handed)


def _set_ready(waiters: list) -> None:
    """Wake the requests that hold an outcome: a thread by its Event; the
    coroutines, which wait on the gRPC event loop, all in ONE call onto
    that loop, where a future may alone be resolved (one wake-up of the
    loop thread a round, not one a rider)."""
    by_loop: dict = {}
    for waiter in waiters:
        if isinstance(waiter.ready, threading.Event):
            waiter.ready.set()
        else:
            by_loop.setdefault(waiter.ready.get_loop(), []).append(
                waiter.ready)
    for loop, futures in by_loop.items():
        loop.call_soon_threadsafe(_resolve, futures)


def _resolve(futures: list) -> None:
    for future in futures:
        if not future.done():  # its request was cancelled meanwhile
            future.set_result(None)


def _slot_fatal(message: str) -> ServingError:
    """A typed per-slot error after which the slot's session is over."""
    exc = ServingError.failed_precondition(message)
    exc.slot_fatal = True
    return exc


def _is_token(outcome: tuple) -> bool:
    """A computed row, as against an error or a raised exception."""
    row, _, raised = outcome
    return not raised and not isinstance(row, Exception)
