"""Continuous batching of decode sessions (SlotPool / TickBatcher).

Concurrent single-sequence decode sessions share ONE vmapped device tick
per token. Correctness bar: token streams are identical to the
whole-generation scan oracle regardless of interleaving, concurrency, or
which other sessions tick alongside.
"""

from __future__ import annotations

import threading

import jax
import numpy as np
import pytest

from min_tfs_client_tpu.models import t5
from min_tfs_client_tpu.servables.decode_sessions import TickBatcher
from min_tfs_client_tpu.utils.status import ServingError

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
        """One session, each step its own round: the leader's trace has
        the wait, the hand-off and the four phases in order, all of one
        round, and `decode/init` sits on the opening request."""
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
            spans = [s for s in trace.spans if s[0].startswith("decode/")]
            # Alone, a step's wait and its round's hand-off coincide.
            spans.sort(key=lambda s: (s[1], s[0] != "decode/wait"))
            assert [s[0] for s in spans] == [
                "decode/wait", "decode/handoff", "decode/prepare",
                "decode/tick", "decode/fetch", "decode/deliver"]
            assert len({s[3]["round"] for s in spans}) == 1
            assert "width" not in spans[3][3]  # no block table here
            assert spans[0][3]["led"] and spans[3][3]["slots"] == 1
            for a, b in zip(spans[1:], spans[2:]):
                assert a[2] <= b[1]
            rounds.append(spans[0][3]["round"])
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


class TestTickBatcher:
    def test_concurrent_steps_coalesce(self):
        batch_sizes = []
        release = threading.Event()

        def tick(slots, of_round):
            if not release.is_set():
                release.wait(5)
            batch_sizes.append(len(slots))
            return {s: s * 10 for s in slots}

        batcher = TickBatcher(tick, join_window_s=0.05)
        results = {}
        lock = threading.Lock()

        def worker(slot):
            r = batcher.step(slot)
            with lock:
                results[slot] = r

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        release.set()
        for t in threads:
            t.join()
        assert results == {i: i * 10 for i in range(8)}
        # 8 slots must NOT have cost 8 ticks: the join window coalesces.
        assert sum(batch_sizes) == 8
        assert len(batch_sizes) < 8
        assert max(batch_sizes) > 1

    def test_sequential_steps_each_get_a_tick(self):
        calls = []

        def tick(slots, of_round):
            calls.append(list(slots))
            return {s: "ok" for s in slots}

        batcher = TickBatcher(tick, join_window_s=0)
        assert batcher.step(3) == "ok"
        assert batcher.step(3) == "ok"
        assert calls == [[3], [3]]

    def test_tick_error_propagates_to_every_waiter(self):
        def tick(slots, of_round):
            raise RuntimeError("device fell over")

        batcher = TickBatcher(tick, join_window_s=0.02)
        errors = []

        def worker(slot):
            try:
                batcher.step(slot)
            except RuntimeError as exc:
                errors.append(str(exc))

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert errors == ["device fell over"] * 4

    def test_arrivals_during_tick_ride_next_round(self):
        rounds = []
        first_tick_started = threading.Event()
        let_first_finish = threading.Event()

        def tick(slots, of_round):
            rounds.append(list(slots))
            if len(rounds) == 1:
                first_tick_started.set()
                let_first_finish.wait(5)
            return {s: len(rounds) for s in slots}

        batcher = TickBatcher(tick, join_window_s=0)
        out = {}

        def first():
            out[1] = batcher.step(1)

        def second():
            first_tick_started.wait(5)
            out[2] = batcher.step(2)

        t1 = threading.Thread(target=first)
        t2 = threading.Thread(target=second)
        t1.start()
        t2.start()
        first_tick_started.wait(5)
        # Give the second thread a moment to enqueue mid-tick.
        import time as _time

        _time.sleep(0.1)
        let_first_finish.set()
        t1.join()
        t2.join()
        assert out[1] == 1 and out[2] == 2
        assert rounds == [[1], [2]]
