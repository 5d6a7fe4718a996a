"""Continuous batching of decode sessions (SlotPool / TickBatcher).

Concurrent single-sequence decode sessions share ONE vmapped device tick
per token. Correctness bar: token streams are identical to the
whole-generation scan oracle regardless of interleaving, concurrency, or
which other sessions tick alongside.
"""

from __future__ import annotations

import threading
import time

import jax
import numpy as np
import pytest

from min_tfs_client_tpu.models import t5
from min_tfs_client_tpu.servables.decode_sessions import TickBatcher
from min_tfs_client_tpu.utils.status import ServingError
from tests.fixtures import tick_loop_threads, until

SEQ, MAXDEC = 12, 8


@pytest.fixture(autouse=True)
def _schedule_witness(schedule_witness):
    """Runtime schedule witness (docs/STATIC_ANALYSIS.md): the shared-tick
    machinery's lock order and guarded mutations are verified live."""
    yield


@pytest.fixture(scope="module")
def pooled():
    config = t5.T5Config.tiny()
    params = t5.init_params(jax.random.PRNGKey(0), config)
    sigs = t5.build_session_signatures(
        params, config, seq_len=SEQ, max_decode_len=MAXDEC,
        max_sessions=8, continuous_batching=True)
    return config, params, sigs


def _prompt(config, rng, n=1):
    ids = rng.integers(2, config.vocab_size, (n, SEQ)).astype(np.int32)
    ids[:, SEQ // 2:] = config.pad_id
    return ids


def _oracle(params, config, ids):
    lengths = np.sum((ids != config.pad_id).astype(np.int32), axis=-1)
    out_ids, _ = t5.greedy_decode(
        params, config, ids, lengths, max_decode_len=MAXDEC)
    return np.asarray(out_ids)


def _run_session(sigs, sid, ids):
    sigs["decode_init"].run({"session_id": sid, "input_ids": ids})
    tokens = []
    for _ in range(MAXDEC):
        out = sigs["decode_step"].run({"session_id": sid})
        tokens.append(int(out["token"][0]))
    return tokens


class TestPooledSessions:
    def test_single_session_matches_oracle(self, pooled):
        config, params, sigs = pooled
        ids = _prompt(config, np.random.default_rng(1))
        want = _oracle(params, config, ids)[0]
        got = _run_session(sigs, np.asarray(b"s-oracle", object), ids)
        np.testing.assert_array_equal(got, want)

    def test_interleaved_sessions_do_not_disturb_each_other(self, pooled):
        config, params, sigs = pooled
        rng = np.random.default_rng(2)
        ids_a, ids_b = _prompt(config, rng), _prompt(config, rng)
        want_a = _oracle(params, config, ids_a)[0]
        want_b = _oracle(params, config, ids_b)[0]

        sa = np.asarray(b"il-a", object)
        sb = np.asarray(b"il-b", object)
        sigs["decode_init"].run({"session_id": sa, "input_ids": ids_a})
        # A advances twice BEFORE B even initializes; B's stream must be
        # unaffected by A's ticks (masked merge leaves B's slot alone).
        toks_a = [int(sigs["decode_step"].run(
            {"session_id": sa})["token"][0]) for _ in range(2)]
        sigs["decode_init"].run({"session_id": sb, "input_ids": ids_b})
        toks_b = []
        for _ in range(MAXDEC):
            toks_b.append(int(sigs["decode_step"].run(
                {"session_id": sb})["token"][0]))
            if len(toks_a) < MAXDEC:
                toks_a.append(int(sigs["decode_step"].run(
                    {"session_id": sa})["token"][0]))
        np.testing.assert_array_equal(toks_a, want_a)
        np.testing.assert_array_equal(toks_b, want_b)
        sigs["decode_close"].run({"session_id": sa})
        sigs["decode_close"].run({"session_id": sb})

    def test_concurrent_sessions_token_exact(self, pooled):
        config, params, sigs = pooled
        rng = np.random.default_rng(3)
        n = 6
        prompts = [_prompt(config, rng) for _ in range(n)]
        # Reference = the SAME pooled program run one session at a time.
        # The scan oracle is a different XLA executable (batch-1 scan vs
        # the pool's vmapped batch-8 step); its float reassociation can
        # flip greedy argmax at near-ties (prompt 0 here has a 0.002
        # logit margin between tokens 0 and 54), which says nothing
        # about the property under test — that concurrency and tick
        # coalescing never change a session's tokens. Cross-program
        # oracle exactness is covered on tie-free prompts by
        # test_single_session_matches_oracle / test_interleaved above.
        wants = [_run_session(sigs, np.asarray(f"ref-{i}".encode(), object),
                              prompts[i]) for i in range(n)]
        results = [None] * n
        errors = []

        def worker(i):
            try:
                sid = np.asarray(f"cc-{i}".encode(), object)
                results[i] = _run_session(sigs, sid, prompts[i])
            except Exception as exc:  # noqa: BLE001
                errors.append(exc)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors
        for i in range(n):
            np.testing.assert_array_equal(results[i], wants[i])

    def test_capacity_backpressure_and_slot_reuse(self, pooled):
        config, params, sigs = pooled
        rng = np.random.default_rng(4)
        ids = _prompt(config, rng)
        sids = []
        for i in range(8):  # fill all 8 slots
            sid = np.asarray(f"cap-{i}".encode(), object)
            sigs["decode_init"].run({"session_id": sid, "input_ids": ids})
            sids.append(sid)
        with pytest.raises(ServingError) as err:
            sigs["decode_init"].run(
                {"session_id": np.asarray(b"cap-overflow", object),
                 "input_ids": ids})
        assert err.value.code == 8  # RESOURCE_EXHAUSTED
        # Closing one session frees its slot for a new one.
        sigs["decode_close"].run({"session_id": sids[0]})
        sigs["decode_init"].run(
            {"session_id": np.asarray(b"cap-new", object),
             "input_ids": ids})
        for sid in sids[1:]:
            sigs["decode_close"].run({"session_id": sid})
        sigs["decode_close"].run(
            {"session_id": np.asarray(b"cap-new", object)})

    def test_reinit_same_session_id_does_not_leak_slots(self, pooled):
        # A client retrying decode_init for the same id displaces the old
        # entry; the displaced slot must return to the pool (store
        # on_evict), or max_slots re-inits would exhaust it forever.
        config, params, sigs = pooled
        ids = _prompt(config, np.random.default_rng(7))
        sid = np.asarray(b"reinit", object)
        for _ in range(3 * 8):  # 3x the pool size
            sigs["decode_init"].run({"session_id": sid, "input_ids": ids})
        # Still room for a fresh session afterwards.
        other = np.asarray(b"reinit-other", object)
        sigs["decode_init"].run({"session_id": other, "input_ids": ids})
        sigs["decode_close"].run({"session_id": sid})
        sigs["decode_close"].run({"session_id": other})

    def test_exhausted_session_is_closed(self, pooled):
        config, params, sigs = pooled
        ids = _prompt(config, np.random.default_rng(5))
        sid = np.asarray(b"exh", object)
        _run_session(sigs, sid, ids)  # steps to max_decode_len
        with pytest.raises(ServingError) as err:
            sigs["decode_step"].run({"session_id": sid})
        assert err.value.code == 5  # NOT_FOUND

    def test_multi_sequence_init_rejected(self, pooled):
        config, params, sigs = pooled
        ids = _prompt(config, np.random.default_rng(6), n=2)
        with pytest.raises(ServingError) as err:
            sigs["decode_init"].run(
                {"session_id": np.asarray(b"multi", object),
                 "input_ids": ids})
        assert err.value.code == 3  # INVALID_ARGUMENT


def test_synthesize_warmup_primes_session_executables():
    """synthesize_warmup runs the warmup_fn hook: a throwaway session
    exercises prefill + tick, then closes — no session/slot leaks."""
    import types

    from min_tfs_client_tpu.servables.warmup import synthesize_warmup

    config = t5.T5Config.tiny()
    params = t5.init_params(jax.random.PRNGKey(0), config)
    for continuous in (False, True):
        sigs = t5.build_session_signatures(
            params, config, seq_len=SEQ, max_decode_len=MAXDEC,
            max_sessions=4, continuous_batching=continuous)
        servable = types.SimpleNamespace(signatures=sigs)
        runs = synthesize_warmup(servable)
        assert runs == 1
        store = sigs["decode_init"]._decode_store
        assert len(store) == 0  # warmup session closed behind itself
        # Every slot available again in the pooled case.
        sid = np.asarray(b"after-warm", object)
        ids = np.zeros((1, SEQ), np.int32)
        sigs["decode_init"].run({"session_id": sid, "input_ids": ids})
        sigs["decode_close"].run({"session_id": sid})


class TestDensePoolPhases:
    def test_dense_pool_records_the_paged_pool_s_phase_names(self, pooled):
        """One session: each step's trace has its wait and, copied from
        the loop's round that computed its token, the hand-off and the
        five phases in order, all of one round; consecutive steps are
        consecutive rounds; `decode/init` sits on the opening request."""
        from min_tfs_client_tpu.observability import tracing

        config, _, sigs = pooled
        sid = np.asarray(b"phases", object)
        with tracing.request_trace("decode_init") as opened:
            sigs["decode_init"].run(
                {"session_id": sid,
                 "input_ids": _prompt(config, np.random.default_rng(9))})
        assert [(name, args) for name, _, _, args in opened.spans
                if name.startswith("decode/")] \
            == [("decode/init", {"tokens": SEQ})]
        rounds = []
        for _ in range(3):
            with tracing.request_trace("decode_step") as trace:
                sigs["decode_step"].run({"session_id": sid})
            mine = [s for s in trace.spans if s[0].startswith("decode/")]
            spans = {s[0]: s for s in mine}
            phases = ["decode/handoff", "decode/prepare", "decode/tick",
                      "decode/wake", "decode/fetch", "decode/deliver"]
            assert sorted(s[0] for s in mine) \
                == sorted(["decode/wait"] + phases)
            assert len({s[3]["round"] for s in mine}) == 1
            tick = spans["decode/tick"][3]
            assert "width" not in tick  # no block table here
            assert tick["slots"] == 1
            for a, b in zip(phases, phases[1:]):
                assert spans[a][2] <= spans[b][1], (a, b)
            # What the loop thread is inside says how much of it was
            # the thread's own CPU; `deliver` overlaps the next round.
            for name in phases[:-1]:
                length_us = (spans[name][2] - spans[name][1]) * 1e6
                assert 0 <= spans[name][3]["cpu_us"] <= length_us + 1000, name
            assert "cpu_us" not in spans["decode/deliver"][3]
            assert spans["decode/wake"][3]["under_pool_lock"] == 1
            wait = spans["decode/wait"]
            assert wait[3]["ahead"] in (0, 1)
            assert trace.start <= wait[1] <= wait[2]
            # Entry to the round's snapshot, nothing where that came first.
            assert wait[2] == max(wait[1], spans["decode/handoff"][2])
            rounds.append(wait[3]["round"])
        assert rounds == [rounds[0], rounds[0] + 1, rounds[0] + 2]
        sigs["decode_close"].run({"session_id": sid})


class TestPooledAtMostOnce:
    """step_ordinal on the POOLED surface: a duplicate resend must not
    burn a shared tick (tick-mates' streams advance by real steps only)
    and must replay bit-identically even after exhaustion released the
    slot."""

    def _step(self, sigs, sid, ordinal=None):
        inputs = {"session_id": sid}
        if ordinal is not None:
            inputs["step_ordinal"] = np.asarray(ordinal, np.int64)
        return sigs["decode_step"].run(inputs)

    def test_guarded_stream_matches_oracle_and_replays(self, pooled):
        config, params, sigs = pooled
        rng = np.random.default_rng(17)
        ids = _prompt(config, rng)
        want = _oracle(params, config, ids)[0]
        sid = np.asarray(b"pooled-ord", object)
        sigs["decode_init"].run({"session_id": sid, "input_ids": ids})
        for i in range(MAXDEC):
            out = self._step(sigs, sid, ordinal=i + 1)
            dup = self._step(sigs, sid, ordinal=i + 1)
            for key in out:
                np.testing.assert_array_equal(out[key], dup[key])
            assert int(out["token"][0]) == int(want[i])
        # the final-step duplicate above already replayed after the
        # exhaustion path released the slot; a NEW ordinal now is an
        # honest NOT_FOUND, not a stale replay
        with pytest.raises(ServingError, match="does not exist"):
            self._step(sigs, sid, ordinal=MAXDEC + 1)

    def test_duplicate_resend_does_not_disturb_tick_mates(self, pooled):
        config, params, sigs = pooled
        rng = np.random.default_rng(23)
        ids_a, ids_b = _prompt(config, rng), _prompt(config, rng)
        want_b = _oracle(params, config, ids_b)[0]
        sid_a = np.asarray(b"pooled-ord-a", object)
        sid_b = np.asarray(b"pooled-ord-b", object)
        sigs["decode_init"].run({"session_id": sid_a, "input_ids": ids_a})
        sigs["decode_init"].run({"session_id": sid_b, "input_ids": ids_b})
        for i in range(MAXDEC):
            self._step(sigs, sid_a, ordinal=i + 1)
            self._step(sigs, sid_a, ordinal=i + 1)  # resend storm
            out_b = self._step(sigs, sid_b, ordinal=i + 1)
            assert int(out_b["token"][0]) == int(want_b[i]), \
                "a neighbor's duplicate resend advanced this stream"
        sigs["decode_close"].run({"session_id": sid_a})
        sigs["decode_close"].run({"session_id": sid_b})


class _Ticks:
    """A tick function with no device behind it. Records every round
    (ordinal, slots, the slots asked for), answers each slot with
    (slot, ordinal), stamps `fetched` as a pool does, and can hold a
    round open (`hold` = its ordinal) until `let_go` is set."""

    def __init__(self, hold=None, rows=None):
        self.rounds = []
        self.hold, self.holding = hold, threading.Event()
        self.let_go = threading.Event()
        self.rows = rows
        self.in_flight = self.most_in_flight = 0
        self._lock = threading.Lock()

    def __call__(self, slots, of_round):
        with self._lock:
            self.in_flight += 1
            self.most_in_flight = max(self.most_in_flight, self.in_flight)
            self.rounds.append((of_round.ordinal, list(slots),
                                sorted(of_round.asked)))
        try:
            of_round.launched()
            if of_round.ordinal == self.hold:
                self.holding.set()
                assert self.let_go.wait(10)
            if self.rows is not None:
                return self.rows(slots, of_round)
            return {s: (s, of_round.ordinal) for s in slots}
        finally:
            of_round.fetched = time.perf_counter()
            with self._lock:
                self.in_flight -= 1

    def slots_of(self, ordinal):
        return [slots for o, slots, _ in self.rounds if o == ordinal][0]


class TestTickBatcher:
    """The loop's contract (decode_sessions.TickBatcher): run-ahead of
    depth one, on a thread that belongs to the batcher."""

    @pytest.fixture(autouse=True)
    def _no_thread_outlives_its_work(self):
        until(lambda: not tick_loop_threads())  # an earlier test's last round
        yield
        until(lambda: not tick_loop_threads())

    def test_admission_makes_a_slot_due_and_its_token_is_parked(self):
        ticks = _Ticks()
        batcher = TickBatcher(ticks)
        batcher.admit(3, room=4)
        # Nobody has asked: the first token is computed all the same.
        until(lambda: ticks.rounds == [(1, [3], [])])
        until(lambda: not tick_loop_threads())
        # A parked token is returned at once: no tick for it...
        assert batcher.step(3) == (3, 1)
        assert ticks.rounds[0] == (1, [3], [])
        # ...and collecting it made the slot due: it rides the next
        # round with no request waiting.
        until(lambda: len(ticks.rounds) == 2)
        assert ticks.rounds[1] == (2, [3], [])
        assert batcher.step(3) == (3, 2)
        assert batcher.counters()["decode_steps_ahead"] == 2
        batcher.release(3)

    def test_a_slot_with_a_parked_token_is_not_ticked_again(self):
        """Depth one, whatever the wait: other slots' rounds go by and
        the parked slot is in none of them."""
        ticks = _Ticks()
        batcher = TickBatcher(ticks)
        batcher.admit(1, room=16)
        until(lambda: len(ticks.rounds) == 1 and not tick_loop_threads())
        batcher.admit(2, room=16)
        for k in range(1, 6):
            assert batcher.step(2) == (2, k + 1)
        until(lambda: len(ticks.rounds) == 7 and not tick_loop_threads())
        assert [slots for _, slots, _ in ticks.rounds] == [[1]] + [[2]] * 6
        assert batcher.step(1) == (1, 1)
        until(lambda: len(ticks.rounds) == 8 and not tick_loop_threads())
        batcher.release(1)
        batcher.release(2)
        assert batcher.counters()["decode_tokens_dropped"] == 2

    def test_a_spent_cache_is_not_due(self):
        """`room` tokens are computed and no more: a slot whose cache is
        full is not ticked, and a step past it is a typed error."""
        ticks = _Ticks()
        batcher = TickBatcher(ticks)
        batcher.admit(5, room=2)
        assert [batcher.step(5), batcher.step(5)] == [(5, 1), (5, 2)]
        until(lambda: not tick_loop_threads())
        assert len(ticks.rounds) == 2
        past = batcher.step(5)
        assert isinstance(past, ServingError) and past.slot_fatal
        batcher.release(5)
        assert batcher.counters()["decode_tokens_dropped"] == 0

    def test_arrivals_during_a_round_ride_the_next(self):
        ticks = _Ticks(hold=1)
        batcher = TickBatcher(ticks)
        batcher.admit(1, room=8)
        assert ticks.holding.wait(5)
        batcher.admit(2, room=8)      # a new session, mid-round
        out = {}
        rider = threading.Thread(
            target=lambda: out.update(two=batcher.step(2)))
        rider.start()                 # and its request, mid-round
        time.sleep(0.05)
        assert len(ticks.rounds) == 1
        ticks.let_go.set()
        rider.join(5)
        assert out == {"two": (2, 2)}
        # Round 2: slot 2, asked for. Slot 1's token was parked, so it
        # is not in it.
        assert ticks.rounds[1] == (2, [2], [2])
        assert batcher.step(1) == (1, 1)
        batcher.release(1)
        batcher.release(2)

    def test_one_tick_in_flight_at_a_time_and_concurrent_steps_share_it(
            self):
        ticks = _Ticks()
        batcher = TickBatcher(ticks)
        n, steps = 8, 20
        for slot in range(n):
            batcher.admit(slot, room=steps)
        got = {slot: [] for slot in range(n)}

        def client(slot):
            for _ in range(steps):
                got[slot].append(batcher.step(slot))

        threads = [threading.Thread(target=client, args=(slot,))
                   for slot in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(10)
        assert ticks.most_in_flight == 1
        for slot in range(n):
            assert [s for s, _ in got[slot]] == [slot] * steps
            ordinals = [o for _, o in got[slot]]
            assert ordinals == sorted(set(ordinals))  # one token a round
        # 160 tokens did not cost 160 ticks: the sessions share rounds.
        assert sum(len(slots) for _, slots, _ in ticks.rounds) == n * steps
        assert len(ticks.rounds) < n * steps
        for slot in range(n):
            batcher.release(slot)

    def test_a_tick_wide_exception_reaches_every_rider_at_its_next_step(
            self):
        def rows(slots, of_round):
            if of_round.ordinal == 2:
                raise RuntimeError("device fell over")
            return {s: "ok" for s in slots}

        ticks = _Ticks(hold=1, rows=rows)
        batcher = TickBatcher(ticks)
        batcher.admit(0, room=8)
        assert ticks.holding.wait(5)
        for slot in (1, 2, 3):
            batcher.admit(slot, room=8)
        out = {}

        def asked():
            try:
                batcher.step(3)
            except RuntimeError as exc:
                out[3] = str(exc)

        rider = threading.Thread(target=asked)
        rider.start()
        time.sleep(0.02)
        ticks.let_go.set()
        rider.join(5)
        until(lambda: not tick_loop_threads())
        # Round 2 took slots 1 to 3 (slot 0's token was parked) and
        # failed: each of its riders gets the exception from its next
        # step, the one that was asked for and those that ran ahead.
        assert ticks.rounds == [(1, [0], []), (2, [1, 2, 3], [3])]
        assert out == {3: "device fell over"}
        for slot in (1, 2):
            with pytest.raises(RuntimeError, match="device fell over"):
                batcher.step(slot)
        assert batcher.step(0) == "ok"
        # Parked like a token: no slot of the failed round is ticked
        # again before its session is retired.
        until(lambda: len(ticks.rounds) == 3 and not tick_loop_threads())
        assert ticks.rounds[2] == (3, [0], [])
        for slot in range(4):
            batcher.release(slot)

    def test_a_run_ahead_refusal_is_not_parked_and_runs_again_on_request(
            self):
        def rows(slots, of_round):
            out = {}
            for s in slots:
                if of_round.asks(s):
                    out[s] = ("row", of_round.ordinal)
                else:  # the pool evicts nobody for a step not asked for
                    out[s] = ServingError.resource_exhausted("no page")
                    out[s].slot_fatal = False
            return out

        ticks = _Ticks(rows=rows)
        batcher = TickBatcher(ticks)
        batcher.admit(7, room=8)
        until(lambda: len(ticks.rounds) == 1 and not tick_loop_threads())
        # Refused, not parked, and not tried over and over either.
        time.sleep(0.05)
        assert ticks.rounds == [(1, [7], [])]
        # The request makes it due again, asked for this time.
        assert batcher.step(7) == ("row", 2)
        assert ticks.rounds[1] == (2, [7], [7])
        assert batcher.counters()["decode_steps_ahead"] == 0
        batcher.release(7)

    def test_a_refusal_of_a_step_asked_for_goes_to_its_request(self):
        def rows(slots, of_round):
            exc = ServingError.resource_exhausted("no page, no victim")
            exc.slot_fatal = False
            return {s: exc for s in slots}

        ticks = _Ticks(rows=rows)
        batcher = TickBatcher(ticks)
        batcher.admit(2, room=8)
        until(lambda: len(ticks.rounds) == 1 and not tick_loop_threads())
        refused = batcher.step(2)
        assert isinstance(refused, ServingError) and not refused.slot_fatal
        # The session is intact and simply not ahead: asked again, it is
        # tried again.
        until(lambda: not tick_loop_threads())
        assert len(ticks.rounds) == 2
        assert isinstance(batcher.step(2), ServingError)
        assert len(ticks.rounds) == 3
        batcher.release(2)

    def test_a_slot_mid_prefix_stays_due_until_its_first_token(self):
        from min_tfs_client_tpu.servables.decode_sessions import (
            PREFILL_PENDING,
        )

        def rows(slots, of_round):
            return {s: (PREFILL_PENDING if of_round.ordinal < 3
                        else "first") for s in slots}

        ticks = _Ticks(rows=rows)
        batcher = TickBatcher(ticks)
        batcher.admit(4, room=1)
        assert batcher.step(4) == "first"
        assert [o for o, _, _ in ticks.rounds] == [1, 2, 3]
        batcher.release(4)

    def test_release_during_a_round_drops_its_row_for_good(self):
        """The row of a round in flight never reaches a new session on
        the same slot number, and neither does a parked one."""
        ticks = _Ticks(hold=1)
        batcher = TickBatcher(ticks)
        batcher.admit(6, room=8)
        assert ticks.holding.wait(5)
        batcher.release(6)            # close: round 1 holds slot 6
        batcher.admit(6, room=8)      # a new session, same slot number
        out = {}
        rider = threading.Thread(
            target=lambda: out.update(new=batcher.step(6)))
        rider.start()
        time.sleep(0.05)
        ticks.let_go.set()
        rider.join(5)
        assert out == {"new": (6, 2)}     # round 2's row, not round 1's
        assert batcher.counters()["decode_tokens_dropped"] == 1
        until(lambda: len(ticks.rounds) == 3 and not tick_loop_threads())
        batcher.release(6)            # a parked token: dropped too
        assert batcher.counters()["decode_tokens_dropped"] == 2
        batcher.admit(6, room=8)
        assert batcher.step(6) == (6, 4)
        batcher.release(6)

    def test_release_waits_for_a_round_that_has_not_launched(self):
        """Until a round's program is enqueued the pool's tick may still
        read the slot's state: `release` returns only then, so the slot
        number cannot be handed on under it."""
        launch, released = threading.Event(), threading.Event()

        def tick(slots, of_round):
            assert launch.wait(10)
            of_round.launched()
            return {s: "row" for s in slots}

        batcher = TickBatcher(tick)
        batcher.admit(1, room=8)
        until(lambda: bool(tick_loop_threads()))
        time.sleep(0.02)              # the round is snapshotted, held

        def close():
            batcher.release(1)
            released.set()

        closer = threading.Thread(target=close)
        closer.start()
        assert not released.wait(0.1)
        launch.set()
        assert released.wait(5)
        closer.join(5)

    def test_the_loop_ends_with_its_work_and_a_later_step_starts_another(
            self):
        before = set(threading.enumerate())
        ticks = _Ticks()
        batcher = TickBatcher(ticks)
        batcher.admit(1, room=8)
        until(lambda: len(ticks.rounds) == 1)
        until(lambda: not tick_loop_threads())
        assert set(threading.enumerate()) <= before
        assert batcher.step(1) == (1, 1)      # starts a new loop
        until(lambda: len(ticks.rounds) == 2 and not tick_loop_threads())
        batcher.release(1)
        assert set(threading.enumerate()) <= before

    def test_a_loop_that_has_ended_does_not_clear_its_successors_flag(self):
        """Between a loop's last snapshot (it clears `_running` under the
        lock) and its thread's exit a new slot may start the next loop;
        the old one must not clear the flag again, or a third loop could
        tick beside the second."""
        ticks = _Ticks(hold=2)
        batcher = TickBatcher(ticks)
        wake = batcher._wake

        def wake_then_admit(before, took):
            wake(before, took)
            if took is None and len(ticks.rounds) == 1:
                # The first loop is on its way out: its successor starts
                # here, and is held in its tick.
                batcher.admit(2, room=8)
                assert ticks.holding.wait(5)

        batcher._wake = wake_then_admit
        batcher.admit(1, room=1)
        until(lambda: len(tick_loop_threads()) == 1 and ticks.holding.is_set())
        batcher.admit(3, room=8)    # must not start a loop of its own
        time.sleep(0.05)
        assert len(tick_loop_threads()) == 1
        ticks.let_go.set()
        until(lambda: len(ticks.rounds) == 3 and not tick_loop_threads())
        assert ticks.most_in_flight == 1
        assert [slots for _, slots, _ in ticks.rounds] == [[1], [2], [3]]
        for slot in (1, 2, 3):
            batcher.release(slot)

    def test_astep_awaits_its_round_and_one_call_wakes_all_its_riders(self):
        """The coroutine form of `step` (what the gRPC event loop runs):
        a parked token is collected at once; riders that await a round
        under way are all resolved by ONE call onto their loop, and ride
        the next round like riders that block.

        Which slots a round takes is decided when the loop thread takes
        its snapshot, so the three slots are made due only while a round
        of slot 0's is held open (an event, not a clock): the round after
        it then takes all three."""
        import asyncio

        held = {n: (threading.Event(), threading.Event()) for n in (1, 3, 4)}

        def rows(slots, of_round):
            if of_round.ordinal in held:
                holding, let_go = held[of_round.ordinal]
                holding.set()
                assert let_go.wait(10)
            return {s: (s, of_round.ordinal) for s in slots}

        ticks = _Ticks(rows=rows)
        batcher = TickBatcher(ticks)
        batcher.admit(0, room=8)
        assert held[1][0].wait(5)                # round 1: slot 0 alone
        for slot in (1, 2, 3):
            batcher.admit(slot, room=8)
        held[1][1].set()
        until(lambda: len(ticks.rounds) == 2 and not tick_loop_threads())
        assert ticks.slots_of(2) == [1, 2, 3]

        async def main():
            loop = asyncio.get_running_loop()
            calls, threadsafe = [], loop.call_soon_threadsafe

            def counted(fn, *args):
                calls.append(getattr(fn, "__name__", ""))
                return threadsafe(fn, *args)

            async def reached(event):
                assert await loop.run_in_executor(None, event.wait, 5)

            loop.call_soon_threadsafe = counted
            assert await batcher.astep(0) == (0, 1)
            await reached(held[3][0])            # round 3: slot 0 alone
            first = [await batcher.astep(slot) for slot in (1, 2, 3)]
            assert first == [(1, 2), (2, 2), (3, 2)]     # parked: no wait
            held[3][1].set()
            await reached(held[4][0])            # round 4: all three, held
            assert ticks.slots_of(4) == [1, 2, 3]
            riders = [loop.create_task(batcher.astep(slot))
                      for slot in (1, 2, 3)]
            await asyncio.sleep(0)               # each runs up to its wait
            with batcher._lock:
                assert all(batcher._slots[slot].waiter is not None
                           for slot in (1, 2, 3))
            assert not any(r.done() for r in riders)     # round 4 is held
            held[4][1].set()
            second = await asyncio.wait_for(asyncio.gather(*riders),
                                            timeout=10)
            assert second == [(1, 4), (2, 4), (3, 4)]
            assert calls.count("_resolve") == 1
            return calls

        asyncio.run(main())
        until(lambda: len(ticks.rounds) == 5)    # handed out: due at once
        assert ticks.slots_of(5) == [1, 2, 3]
        for slot in (0, 1, 2, 3):
            batcher.release(slot)

    def test_astep_raises_a_tick_wide_exception_and_returns_a_slots_error(
            self):
        import asyncio

        def rows(slots, of_round):
            if of_round.ordinal == 2:
                raise RuntimeError("the device is gone")
            return {s: (s, of_round.ordinal) for s in slots}

        batcher = TickBatcher(_Ticks(rows=rows))
        batcher.admit(1, room=8)

        async def main():
            assert await batcher.astep(1) == (1, 1)
            with pytest.raises(RuntimeError, match="the device is gone"):
                await batcher.astep(1)
            row = await batcher.astep(9)     # never opened: typed, returned
            assert isinstance(row, ServingError) and row.slot_fatal

        asyncio.run(main())
        batcher.release(1)

    def test_every_wait_of_the_batcher_is_timed(self):
        """servelint DL003: no wait that can park a thread for ever."""
        import ast
        import inspect
        import textwrap

        tree = ast.parse(textwrap.dedent(inspect.getsource(TickBatcher)))
        waits = [node for node in ast.walk(tree)
                 if isinstance(node, ast.Call)
                 and isinstance(node.func, ast.Attribute)
                 and node.func.attr in ("wait", "wait_for", "join")]
        assert waits
        assert all(any(kw.arg == "timeout" for kw in call.keywords)
                   for call in waits)
