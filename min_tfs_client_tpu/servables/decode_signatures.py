"""The decode-session wire surface, once, for any decode model.

    decode_init:         session_id + input_ids -> prefill; the session's
                         device state is parked under the session id
    decode_init_prefix:  decode_init plus prefix_ids — a FORCED decoder
                         prefix (continuation / forced decoding): the
                         session resumes as if it had already emitted
                         those tokens
    decode_step:         session_id -> one token per call (one token
                         crosses the wire each way); the optional
                         step_ordinal makes a resend at-most-once
    decode_close:        session_id -> free the session's device state

Host signatures: the bookkeeping is Python, the math is jitted.

What a model supplies is ONE object, `DecodeModel`: a model file builds
it and calls `build_session_signatures`; nothing under servables/ knows
which model it serves. Where a prefilled state is parked and who advances
it is the one seam (`_Backend`), with two implementations:

  per session   the state tree sits in a DecodeSessionStore and each step
                is its own device dispatch (buffers donated, the caches
                update in place);
  pooled        `continuous_batching=True`: the state sits in a slot of a
                pool (decode_sessions.SlotPool, or PagedSlotPool when
                `paging.block_size` > 0) and a TickBatcher's own loop
                advances every open session that is due in ONE device
                tick — K sessions cost one dispatch per token instead
                of K. decode_step carries no token from the client, so
                the loop computes a session's next token as soon as the
                last was collected, one ahead of the client and no
                more: decode_step collects it (parked, or from the
                round under way). Sessions are then single-sequence.

Beside the sessions, a decoder served as WHOLE generations: the loop over
the same `prefill` / `step` (`whole_generation`) and its one Signature,
with what the program counted as `CountTable`s (`generation_signature`).

Arrows: models/* -> this module -> decode_sessions -> ops/attention.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Mapping, NamedTuple, Optional

import numpy as np

from min_tfs_client_tpu.servables.decode_sessions import (
    DecodeSessionStore,
    PagedSlotPool,
    Paging,
    SlotPool,
    StepDeduper,
    TickBatcher,
    read_step_ordinal,
)
from min_tfs_client_tpu.servables.servable import (
    Signature,
    TensorSpec,
    fetch_outputs,
)
from min_tfs_client_tpu.utils.status import ServingError

# The per-example sampling inputs a session may be opened with: wire
# dtype, and the value the warm-up sends. A model names the ones its
# prefill takes (`DecodeModel.sampling_inputs`), in this order.
SAMPLING_INPUTS = {"temperature": (np.float32, 0.0),
                   "seed": (np.int32, 0),
                   "top_p": (np.float32, 1.0)}


@dataclasses.dataclass(frozen=True)
class DecodeModel:
    """What a decode model implements to be served as sessions — all of
    it. Every callable is pure and traced under jit; `params` rides as a
    jit ARGUMENT (a closed-over tree would be re-baked into each
    executable as constants).

    name              stem of the metric and compile-ledger labels:
                      "<name>" (per-session store), "<name>-pooled",
                      "<name>-paged".
    prefill           (params, input_ids (B, seq_len), *sampling
                      [, prefix_ids (B, max_decode_len)]) -> state: the
                      device state one session carries between steps, a
                      dict tree that holds at least "finished" (B,) bool.
                      `sampling` are the arrays named by
                      `sampling_inputs`, each (B,). With the trailing
                      `prefix_ids` (pad-suffixed, one shared length) the
                      prefill also runs the decoder over the forced
                      prefix, monolithically; each arity is its own trace.
    step              (params, state) -> (state', token (B,)): advance one
                      token. The per-session and dense-pool backends run
                      it (the dense pool under vmap over its slots).
    paged_step        the paging-aware step contract of
                      decode_sessions.PagedSlotPool: an object with
                      decode(params, tree, kv) -> (tree', kv', outputs)
                      and prefill_chunk(params, tree, kv, tokens,
                      chunk_lens, next_tokens) -> (tree', kv'); `tree` is
                      the prefill's state slot-batched with the paged
                      leaves None, `kv` an ops/attention.PagedKV keyed by
                      those leaves' pytree paths, `outputs` holds "token"
                      and "finished", both (slots, 1). Token for token it
                      must say what `step` says.
    paged_axis_fn     (pytree path of a state leaf) -> the axis along
                      which that leaf is a KV cache (one row a token,
                      append-only), or None for a leaf that stays dense.
    decoder_start_id  the decoder input at position 0.
    pad_id            pads prompts and prefixes; a finished row emits it.
    sampling_inputs   names from SAMPLING_INPUTS, () for greedy.
    """

    name: str
    prefill: Callable
    step: Callable
    paged_step: object
    paged_axis_fn: Callable[[tuple], Optional[int]]
    decoder_start_id: int
    pad_id: int
    sampling_inputs: tuple = ()


def whole_generation(prefill: Callable, step: Callable, params,
                     input_ids, *, max_decode_len: int, pad_id: int) -> dict:
    """A whole greedy generation in one traced program, written once over
    the decode contract: `prefill(params, input_ids) -> state`, then
    `max_decode_len` times `step(params, state) -> (state', token (B,))`
    (a `lax.scan` of all but the last, so that the state the last token
    was chosen from can be given too). A model's `step` decides what a
    finished example emits (pad_id by the contract); the lengths count
    what is not pad_id. -> {"output_ids" (B, max_decode_len),
    "output_lengths" (B,), "first": the prefill's state, "before_last":
    the state before the last step, "final": the state after it}."""
    import jax
    import jax.numpy as jnp

    first = prefill(params, jnp.asarray(input_ids, jnp.int32))

    def body(state, _):
        return step(params, state)

    before_last, head = jax.lax.scan(body, first, None,
                                     length=max_decode_len - 1)
    final, tail = step(params, before_last)
    output_ids = jnp.concatenate([head, tail[None]], axis=0).T
    return {"output_ids": output_ids,
            "output_lengths": jnp.sum(
                (output_ids != pad_id).astype(jnp.int32), axis=-1),
            "first": first, "before_last": before_last, "final": final}


@dataclasses.dataclass(frozen=True)
class CountTable:
    """What a generation's program counted, as data: an int32 output of
    the signature (`output`, a row an example, `columns` in order), the
    span a request's rows become on its trace, the section of
    `/monitoring/runtime` they add up in.

    derived    {column: (count, factor)}: the state's count of that name
               times the factor (no count: the factor on every row); any
               other column is the state's count of its own name.
    batch      columns that are the whole batch's, the same on every row:
               a request's figure is the max over its rows, not the sum.
    uncounted  columns left out of the section.
    shared     {batch column: (own, total)}: it enters the section times
               the request's share of the batch, its rows' `own` column
               over the batch's `total`."""

    output: str
    span: str
    section: str
    columns: tuple
    derived: Mapping = dataclasses.field(default_factory=dict)
    batch: tuple = ()
    uncounted: tuple = ()
    shared: Mapping = dataclasses.field(default_factory=dict)

    def rows(self, counts: Mapping):
        """A state's `counts` -> (B, len(columns)) int32, traced."""
        import jax.numpy as jnp

        def column(name):
            count, factor = self.derived.get(name, (name, None))
            if count is None:
                return jnp.full_like(counts["steps"], factor)
            if factor is not None:
                return counts[count] * factor
            if name in self.batch:
                return jnp.broadcast_to(counts[name], counts["steps"].shape)
            return counts[name]

        return jnp.stack([column(name).astype(jnp.int32)
                          for name in self.columns], axis=-1)

    def note(self, signature: Signature, outputs: Mapping) -> None:
        """A request's own rows of the output -> span and section."""
        rows = outputs.get(self.output)
        if rows is None:
            return
        rows = np.asarray(rows).reshape(-1, len(self.columns))
        args = {name: int(rows[:, i].max() if name in self.batch
                          else rows[:, i].sum())
                for i, name in enumerate(self.columns)}
        counted = {name: value for name, value in args.items()
                   if name not in self.uncounted}
        for name, (own, total) in self.shared.items():
            counted[name] = round(args[name] * (args[own]
                                                / max(args[total], 1)))
        note_generation(signature, self.section, {self.span: args}, counted)


def note_generation(signature: Signature, section: str, spans: Mapping,
                    counted: Mapping) -> None:
    """What a generation counted for ONE request: `spans` ({name:
    arguments}) as spans of no duration on its trace, and `counted` into
    the process's counters (`/monitoring/runtime`, `section`, under the
    signature's label)."""
    from min_tfs_client_tpu.observability import runtime, tracing

    now = time.perf_counter()
    for name, args in spans.items():
        tracing.add_span(name, now, now, **args)
    runtime.count_generation(
        section, signature.telemetry_label or "unlabeled", counted)


def generation_signature(prefill: Callable, step: Callable, params, *,
                         seq_len: int, max_decode_len: int, vocab_size: int,
                         pad_id: int, batch_buckets: tuple,
                         tables: tuple = ()) -> Signature:
    """The one signature of a decoder served as whole generations
    (`whole_generation` over its `prefill` and `step`): input_ids (B,
    seq_len) -> output_ids (B, max_decode_len), output_lengths, of the
    timed path itself the float32 logits the first and the last token
    were chosen from (of the loop's three states only logits and counts
    are kept), and an int32 output a count table, which `on_answer` turns
    into the table's span and section."""

    def generate_fn(tree, inputs):
        found = whole_generation(prefill, step, tree, inputs["input_ids"],
                                 max_decode_len=max_decode_len,
                                 pad_id=pad_id)
        return {"output_ids": found["output_ids"],
                "output_lengths": found["output_lengths"],
                "first_logits": found["first"]["logits"],
                "last_logits": found["before_last"]["logits"],
                **{table.output: table.rows(found["final"]["counts"])
                   for table in tables}}

    def note_answer(signature, outputs):
        for table in tables:
            table.note(signature, outputs)

    return Signature(
        fn=generate_fn, params=params,
        inputs={"input_ids": TensorSpec(np.int32, (None, seq_len))},
        outputs={
            "output_ids": TensorSpec(np.int32, (None, max_decode_len)),
            "output_lengths": TensorSpec(np.int32, (None,)),
            "first_logits": TensorSpec(np.float32, (None, vocab_size)),
            "last_logits": TensorSpec(np.float32, (None, vocab_size)),
            **{table.output: TensorSpec(np.int32,
                                        (None, len(table.columns)))
               for table in tables}},
        batch_buckets=tuple(batch_buckets),
        # a padding row is a prompt of length 0: `owned` by no request
        batch_pad_values={"input_ids": pad_id},
        on_answer=note_answer)


class _Step:
    """One decode_step under the at-most-once guard — the ONE place the
    StepDeduper dance lives. Built from the request: reads the session
    id and the optional step_ordinal and asks the guard BEFORE any store
    lookup (a duplicate resend of the final step must replay from cache
    even after exhaustion closed the session). `out` is then the cached
    answer, or None with the ordinal marked in flight; in the latter
    case the backend advances the session inside `with step:` and calls
    `step.answer(...)`. Leaving the block commits the answer, or, on an
    exception, abandons the mark: the failed attempt produced no
    response, so a retry of this ordinal executes instead of waiting on
    a commit that will never come.

    A context manager and not a wrapper function on purpose: the
    backend's step function calls the device round itself, so no frame
    of this module lies between the two (PERF.md §6, PR 29: how deep the
    Python stack is where a tick program is first traced moves its
    lowering time by a second a program)."""

    __slots__ = ("_dedup", "sid", "_ordinal", "out", "_replayed")

    def __init__(self, dedup: StepDeduper, inputs):
        self._dedup = dedup
        self.sid = _session_id(inputs)
        self._ordinal = read_step_ordinal(inputs)
        self.out = dedup.replay(self.sid, self._ordinal)
        self._replayed = self.out is not None

    def answer(self, token, finished, host_step: int) -> None:
        """The step's wire answer: `token` and `finished` (B,), int32."""
        self.out = {"token": token, "finished": finished,
                    "step": np.asarray(host_step, np.int32)}

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._replayed:
            return False
        if exc_type is None:
            self._dedup.commit(self.sid, self._ordinal, self.out)
        else:
            self._dedup.abandon(self.sid, self._ordinal)
        return False


class _Backend(NamedTuple):
    """Where a prefilled state is parked and who advances it.

    admit(sid, args, forced)  prefill `args` (params, input_ids,
        *sampling) and park the state under `sid`; `forced` is None or
        (prefix_ids, prefix_len).
    advance(inputs) -> outputs  decode_step itself: one token under a
        `_Step`; at max_decode_len the session ends and its state is
        freed.
    release(sid) -> bool  free the state; False when there was none.

    `store` and `kv_pool` (the paged pool, else None) are what the loader
    re-labels with the real model:version; `dedup` is the guard the
    inits and the close forget a session in; `loop_counters` reads the
    pooled backend's tick loop (TickBatcher.counters). `aadvance` is
    `advance` as a coroutine function, decode_step's `Signature.afn`,
    where the backend can await what `advance` blocks on: the tick
    loop's round. The per-session store has none (its step is a device
    dispatch of its own).
    """

    admit: Callable
    advance: Callable
    release: Callable
    store: DecodeSessionStore
    dedup: StepDeduper
    kv_pool: Optional[PagedSlotPool] = None
    loop_counters: Optional[Callable[[], dict]] = None
    aadvance: Optional[Callable] = None


def _deduper(store: DecodeSessionStore, max_sessions: int) -> StepDeduper:
    # is_live = the store's membership test: a LIVE session's guard is
    # never LRU-evicted (only closed/exhausted/expired entries shed).
    return StepDeduper(max_entries=max(2 * max_sessions, 64),
                       is_live=store.__contains__)


def _per_session_backend(params, model: DecodeModel, prefill_jit, *,
                         max_sessions: int, session_ttl_s: float,
                         max_decode_len: int) -> _Backend:
    import jax

    from min_tfs_client_tpu.observability import runtime as rt

    store = DecodeSessionStore(max_sessions=max_sessions,
                               ttl_s=session_ttl_s, metric_label=model.name)
    dedup = _deduper(store, max_sessions)
    step_jit = rt.instrument_jit(
        f"{model.name}:decode:step",
        jax.jit(model.step, donate_argnums=(1,)))

    def admit(sid, args, forced):
        if forced is None:
            store.put(sid, (prefill_jit(*args), 0))
            return
        # Monolithic prefill: prompt encode + the decoder run over the
        # whole forced prefix in one pass; the host-side step mirror
        # starts at its length, so the session decodes max_decode_len -
        # prefix_len further tokens.
        prefix, prefix_len = forced
        store.put(sid, (prefill_jit(*args, jax.device_put(prefix)),
                        prefix_len))

    def step_fn(inputs):
        with _Step(dedup, inputs) as step:
            if step.out is None:
                state, host_step = store.take(step.sid)
                state, token = step_jit(params, state)
                host_step += 1
                if host_step < max_decode_len:
                    store.put(step.sid, (state, host_step))
                else:
                    store.close(step.sid)  # cache exhausted: session ends
                # One overlapped fetch: the step's whole wire cost is one
                # token row (+ the finished flags) each way.
                fetched = fetch_outputs(
                    {"token": token, "finished": state["finished"]})
                step.answer(fetched["token"],
                            fetched["finished"].astype(np.int32), host_step)
        return step.out

    return _Backend(admit, step_fn, store.close, store, dedup)


def _pooled_backend(params, model: DecodeModel, prefill_jit, *,
                    seq_len: int, max_slots: int, session_ttl_s: float,
                    max_decode_len: int, paging: Paging) -> _Backend:
    import jax
    import jax.numpy as jnp

    from min_tfs_client_tpu.observability import tracing

    template = jax.eval_shape(
        model.prefill, params,
        jax.ShapeDtypeStruct((1, seq_len), jnp.int32),
        *(jax.ShapeDtypeStruct((1,), SAMPLING_INPUTS[name][0])
          for name in model.sampling_inputs))
    paged = bool(paging.block_size)
    if paged:
        pool = PagedSlotPool(
            template, max_slots=max_slots, params=params, paging=paging,
            paged_step=model.paged_step, paged_axis_fn=model.paged_axis_fn,
            metric_label=f"{model.name}-paged")
    else:
        def one_step(p, state):
            new_state, token = model.step(p, state)
            return new_state, {"token": token,
                               "finished": new_state["finished"]}

        pool = SlotPool(template, one_step, max_slots=max_slots,
                        params=params, metric_label=f"{model.name}-pooled")
    # cost_fn: each delivered step charges its session's pages-held onto
    # the CALLER's trace (pages x ticks, the paged pool's HBM-residency
    # cost unit; None on the dense pool).
    batcher = TickBatcher(pool.tick, cost_fn=pool.step_cost)
    if paged:
        pool.loop_counters = batcher.counters

    def release_slot(slot):
        # The loop first (a parked token, or the row of a round in
        # flight, is dropped), then the pool's slot and pages.
        batcher.release(slot)
        pool.release_slot(slot)

    store = DecodeSessionStore(
        max_sessions=max_slots, ttl_s=session_ttl_s,
        metric_label=f"{model.name}-pooled",
        on_evict=lambda entry: release_slot(entry[0]))
    dedup = _deduper(store, max_slots)

    def admit(sid, args, forced):
        chunked, start = {}, 0
        if forced is not None:
            prefix, start = forced
            if paged:
                # Encoder-only prefill; the forced prefix streams through
                # the ragged kernel in chunks, interleaved with other
                # sessions' ticks.
                tokens = prefix[0][:start]
                chunked = {
                    "prefill_inputs": np.concatenate(
                        [np.asarray([model.decoder_start_id], np.int32),
                         tokens[:-1]]),
                    "prefill_next": int(tokens[-1])}
            else:
                # Dense slot pool: one monolithic prefill.
                args += (jax.device_put(prefix),)
        # The prefill takes the device, and the pool write the pool's
        # lock, against the ticks of the sessions that are stepping.
        with tracing.span("decode/init", tokens=int(args[1].shape[1])):
            state = prefill_jit(*args)
            slot = pool.acquire_slot()
            try:
                pool.write(state, slot, session_key=sid, **chunked)
                store.put(sid, (slot, start))
            except Exception:
                pool.release_slot(slot)
                raise
        # Due at once: the session's first token is under way before its
        # first decode_step arrives.
        batcher.admit(slot, max_decode_len - start)

    def stepping(inputs):
        """decode_step, written once as a generator for its two callers:
        it yields where it may have to wait, `(collect, slot)` for the
        slot's row (the token is parked already, or the round that
        computes it is waited for; a slot mid-prefix stays in the rounds
        until its first real token) and `(release, slot)` to retire the
        slot (the pool's lock, which a tick holds through its launch),
        and returns the step's outputs. `step_fn` drives it on a thread
        that may block, `astep_fn` on the event loop, where it awaits."""
        with _Step(dedup, inputs) as step:
            if step.out is None:
                sid = step.sid
                slot, host_step = store.take(sid)
                try:
                    row = yield "collect", slot
                except Exception:
                    # The whole tick failed: the pool row may be in an
                    # undefined state; retire the slot rather than hand
                    # it to a future session mid-generation.
                    yield "release", slot
                    raise
                if isinstance(row, Exception):
                    # Per-slot failure from the paged pool's tick (typed
                    # capacity errors, eviction under kv_evict_policy=
                    # close). slot_fatal distinguishes a dead session from
                    # a capacity REFUSAL whose state is intact and may
                    # retry after others close.
                    if getattr(row, "slot_fatal", True):
                        yield "release", slot
                    else:
                        store.put(sid, (slot, host_step))
                    raise row
                host_step += 1
                if host_step < max_decode_len:
                    store.put(sid, (slot, host_step))
                else:
                    yield "release", slot  # cache exhausted: session ends
                step.answer(row["token"].reshape(-1),
                            row["finished"].reshape(-1).astype(np.int32),
                            host_step)
        return step.out

    def step_fn(inputs):
        return _drive(stepping(inputs), {"collect": batcher.step,
                                         "release": release_slot})

    async def astep_fn(inputs):
        import asyncio

        def release(slot):
            # Off the loop: it can wait a launch's length for two locks.
            return asyncio.get_running_loop().run_in_executor(
                None, release_slot, slot)

        return await _adrive(stepping(inputs), {"collect": batcher.astep,
                                                "release": release})

    # release: the store's on_evict hands the slot back to the pool.
    return _Backend(admit, step_fn, store.close, store, dedup,
                    pool if paged else None, batcher.counters, astep_fn)


def _drive(steps, do: dict):
    """Run a generator of `(name, argument)` requests to its end on this
    thread: each is answered by `do[name](argument)`, whose value, or
    exception, goes back into the generator; the generator's return
    value is the result."""
    try:
        name, arg = next(steps)
        while True:
            try:
                value = do[name](arg)
            except Exception as exc:  # noqa: BLE001 - the generator's to handle
                name, arg = steps.throw(exc)
            else:
                name, arg = steps.send(value)
    except StopIteration as done:
        return done.value


async def _adrive(steps, do: dict):
    """`_drive` on an event loop: `do[name](argument)` is awaited."""
    try:
        name, arg = next(steps)
        while True:
            try:
                value = await do[name](arg)
            except Exception as exc:  # noqa: BLE001 - the generator's to handle
                name, arg = steps.throw(exc)
            else:
                name, arg = steps.send(value)
    except StopIteration as done:
        return done.value


def build_session_signatures(params, model: DecodeModel, *, seq_len: int,
                             max_decode_len: int, max_sessions: int = 64,
                             session_ttl_s: float = 600.0,
                             continuous_batching: bool = False,
                             paging: Optional[Paging] = None) -> dict:
    """The four decode-session signatures of `model` (module docstring).
    `paging` decides the pooled backend's pool: None takes the loader's
    scope (decode_sessions.Paging.resolve); block_size 0 is the dense
    slot pool. Without `continuous_batching` it is not consulted."""
    import jax

    from min_tfs_client_tpu.observability import runtime as rt

    pooled = bool(continuous_batching)
    prefill_jit = rt.instrument_jit(
        f"{model.name}:{'pooled' if pooled else 'decode'}:prefill",
        jax.jit(model.prefill))
    if pooled:
        backend = _pooled_backend(
            params, model, prefill_jit, seq_len=seq_len,
            max_slots=max_sessions, session_ttl_s=session_ttl_s,
            max_decode_len=max_decode_len,
            paging=paging if paging is not None else Paging.resolve())
    else:
        backend = _per_session_backend(
            params, model, prefill_jit, max_sessions=max_sessions,
            session_ttl_s=session_ttl_s, max_decode_len=max_decode_len)
    store, dedup, step_fn = backend.store, backend.dedup, backend.advance
    sampling = tuple((name, SAMPLING_INPUTS[name][0])
                     for name in model.sampling_inputs)

    def read_sampling(inputs, batch):
        out = ()
        for name, dtype in sampling:
            arr = np.asarray(inputs[name], dtype).reshape(-1)
            if arr.shape != (batch,):
                raise ServingError.invalid_argument(
                    f"{name} must have {batch} elements (one per "
                    f"input_ids row); got {arr.shape[0]}")
            out += (jax.device_put(arr),)
        return out

    def init(inputs, with_prefix):
        sid = _session_id(inputs)
        # A re-init over a previously-used id is a NEW stream: drop any
        # surviving dedup entry (it deliberately outlives exhaustion, so
        # only close/init may clear it) or its first ordinal-guarded step
        # would be judged against, or replayed from, the dead stream's.
        dedup.forget(sid)
        ids = np.asarray(inputs["input_ids"]).astype(np.int32)
        batch = ids.shape[0]
        if batch != 1 and (pooled or with_prefix):
            kind = ("continuous-batching decode" if pooled
                    else "decode_init_prefix")
            raise ServingError.invalid_argument(
                f"{kind} sessions are single-sequence: "
                f"input_ids batch must be 1, got {batch}")
        forced = _read_prefix(inputs, model.pad_id) if with_prefix else None
        args = (params, jax.device_put(ids)) + read_sampling(inputs, batch)
        backend.admit(sid, args, forced)
        out = {"session_id": np.asarray(sid, object),
               "batch": np.asarray(batch, np.int32)}
        if with_prefix:
            out["prefix_len"] = np.asarray(forced[1], np.int32)
        return out

    def init_fn(inputs):
        return init(inputs, False)

    def init_prefix_fn(inputs):
        return init(inputs, True)

    def close_fn(inputs):
        sid = _session_id(inputs)
        dedup.forget(sid)
        return {"closed": np.asarray(int(backend.release(sid)), np.int32)}

    session_spec = TensorSpec("DT_STRING", ())
    init_inputs = {"session_id": session_spec,
                   "input_ids": TensorSpec(np.int32, (None, seq_len)),
                   **{name: TensorSpec(dtype, (None,))
                      for name, dtype in sampling}}
    init_outputs = {"session_id": TensorSpec("DT_STRING", ()),
                    "batch": TensorSpec(np.int32, ())}
    signatures = {
        "decode_init": Signature(
            fn=init_fn, inputs=init_inputs, outputs=init_outputs,
            on_host=True, batched=False),
        "decode_init_prefix": Signature(
            fn=init_prefix_fn,
            inputs={**init_inputs,
                    "prefix_ids": TensorSpec(np.int32,
                                             (None, max_decode_len))},
            outputs={**init_outputs,
                     "prefix_len": TensorSpec(np.int32, ())},
            on_host=True, batched=False),
        "decode_step": Signature(
            fn=step_fn,
            inputs={"session_id": session_spec},
            # step_ordinal is the OPTIONAL at-most-once guard: absent =
            # historical wire behavior byte-for-byte (docs/ROBUSTNESS.md
            # "Retry & idempotency").
            optional_inputs={"step_ordinal": TensorSpec(np.int64, ())},
            outputs={"token": TensorSpec(np.int32, (None,)),
                     "finished": TensorSpec(np.int32, (None,)),
                     "step": TensorSpec(np.int32, ())},
            on_host=True, batched=False, afn=backend.aadvance),
        "decode_close": Signature(
            fn=close_fn,
            inputs={"session_id": session_spec},
            outputs={"closed": TensorSpec(np.int32, ())},
            on_host=True, batched=False),
    }
    signatures["decode_init"].warmup_fn = _session_warmup_fn(
        init_fn, init_prefix_fn, step_fn, close_fn, seq_len=seq_len,
        max_decode_len=max_decode_len, model=model)
    # The loader re-labels the store's gauge (and a paged pool's) with the
    # real model:version (platforms.make_loader): the builder cannot know it.
    for sig in signatures.values():
        sig._decode_store = store
        if backend.kv_pool is not None:
            sig._kv_pool = backend.kv_pool
        if backend.loop_counters is not None:
            sig._loop_counters = backend.loop_counters
    return signatures


def _session_id(inputs) -> bytes:
    raw = np.asarray(inputs["session_id"]).reshape(-1)
    if raw.size != 1:
        raise ServingError.invalid_argument(
            f"session_id must hold exactly one id, got {raw.size}")
    value = raw[0]
    return value if isinstance(value, bytes) else str(value).encode()


def _read_prefix(inputs, pad_id: int):
    """decode_init_prefix's prefix_ids: (1, max_decode_len) int32, real
    tokens then pad — returns (array, true length). Single-sequence: the
    session state carries ONE step scalar, so a multi-row prefix init
    would need per-row lengths it cannot represent."""
    pre = np.asarray(inputs["prefix_ids"]).astype(np.int32)
    if pre.ndim != 2 or pre.shape[0] != 1:
        raise ServingError.invalid_argument(
            "prefix_ids must be a single-sequence (1, max_decode_len) "
            f"tensor; got shape {pre.shape}")
    row = pre[0]
    pads = np.flatnonzero(row == pad_id)
    plen = int(pads[0]) if pads.size else int(row.shape[0])
    if plen == 0:
        raise ServingError.invalid_argument(
            "prefix_ids holds no tokens (row starts with pad)")
    if plen >= row.shape[0]:
        # A full-width prefix leaves zero decode budget — and the first
        # step would write K/V at max_decode_len, which the cache write
        # CLAMPS to the last row, silently corrupting the prefix.
        raise ServingError.invalid_argument(
            f"prefix_ids fills the entire max_decode_len budget "
            f"({row.shape[0]}); at least one position must remain to "
            "decode")
    if pads.size and not (row[plen:] == pad_id).all():
        raise ServingError.invalid_argument(
            "prefix_ids must be real tokens followed only by pad "
            f"(pad_id {pad_id}); found tokens after position "
            f"{plen}")
    return pre, plen


def _session_warmup_fn(init_fn, init_prefix_fn, step_fn, close_fn, *,
                       seq_len: int, max_decode_len: int,
                       model: DecodeModel):
    """Prime the prefill and step/tick executables with two throwaway
    sessions, so that the first real decode_init, decode_init_prefix or
    decode_step never compiles (synthesize_warmup calls this through the
    warmup_fn hook). The second opens with a one-token forced prefix:
    the prefix-arity monolithic prefill on the per-session store and the
    dense pool, the chunked-prefill program on the paged pool."""
    prefix = np.full((1, max_decode_len), model.pad_id, np.int32)
    prefix[0, 0] = 1 if model.pad_id != 1 else 2

    def _warm():
        for sid, init, extra in (
                (b"__warmup__", init_fn, {}),
                (b"__warmup_prefix__", init_prefix_fn,
                 {"prefix_ids": prefix})):
            session = {"session_id": np.asarray(sid, object)}
            init({**session,
                  "input_ids": np.zeros((1, seq_len), np.int32),
                  **{name: np.full((1,), SAMPLING_INPUTS[name][1],
                                   SAMPLING_INPUTS[name][0])
                     for name in model.sampling_inputs},
                  **extra})
            step_fn(session)
            close_fn(session)
    return _warm
