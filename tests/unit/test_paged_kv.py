"""Paged KV-cache decode pool (PagedSlotPool / PageAllocator).

Correctness bars:
 * token streams identical to the dense SlotPool at every block size
   (divisible and non-divisible tails), under interleaving and concurrency;
 * eviction swap/restore is bit-identical continuation;
 * every capacity path surfaces the TYPED error (RESOURCE_EXHAUSTED), at
   the pool AND through the serving handlers, without tripping the
   flight-recorder INTERNAL latch;
 * concurrent-session capacity scales with USED tokens: >= 4x the dense
   pool's sessions for a short-prompt mix under one fixed KV byte budget.
"""

from __future__ import annotations

import threading

import jax
import numpy as np
import pytest

from min_tfs_client_tpu.models import t5
from min_tfs_client_tpu.servables.decode_sessions import (
    PageAllocator,
    Paging,
    paging_scope,
)
from min_tfs_client_tpu.utils.status import ServingError
from tests.fixtures import until

SEQ, MAXDEC = 12, 8
RESOURCE_EXHAUSTED = 8


@pytest.fixture(autouse=True)
def _schedule_witness(schedule_witness):
    """Runtime schedule witness (docs/STATIC_ANALYSIS.md): the paged
    pool's allocator lock and block-table state are verified live."""
    yield


@pytest.fixture(autouse=True)
def _leak_witness(leak_witness):
    """Runtime leak witness: every PageAllocator page and slot-pool slot
    acquired in a test must be net-released by teardown."""
    yield


@pytest.fixture(scope="module")
def model():
    config = t5.T5Config.tiny()
    params = t5.init_params(jax.random.PRNGKey(0), config)
    return config, params


def _sigs(model, **kw):
    config, params = model
    kw.setdefault("seq_len", SEQ)
    kw.setdefault("max_decode_len", MAXDEC)
    kw.setdefault("max_sessions", 8)
    kw.setdefault("continuous_batching", True)
    return t5.build_session_signatures(params, config, **kw)


def _prompt(config, rng, n=1):
    ids = rng.integers(2, config.vocab_size, (n, SEQ)).astype(np.int32)
    ids[:, SEQ // 2:] = config.pad_id
    return ids


def _run(sigs, sid, ids, steps=MAXDEC):
    sigs["decode_init"].run({"session_id": sid, "input_ids": ids})
    tokens = []
    for _ in range(steps):
        out = sigs["decode_step"].run({"session_id": sid})
        tokens.append(int(out["token"][0]))
    return tokens


def _sid(name):
    return np.asarray(name.encode() if isinstance(name, str) else name,
                      object)


class TestPageAllocator:
    def test_alloc_free_reuse(self):
        alloc = PageAllocator(4)
        a = alloc.alloc(3)
        assert alloc.used() == 3
        alloc.free(a[:2])
        assert alloc.used() == 1
        b = alloc.alloc(3)
        assert alloc.used() == 4
        assert set(a[2:]) | set(b) == set(range(4))

    def test_exhaustion_is_typed_capacity_error(self):
        alloc = PageAllocator(2)
        alloc.alloc(2)
        assert alloc.try_alloc(1) is None
        with pytest.raises(ServingError) as err:
            alloc.alloc(1)
        assert err.value.code == RESOURCE_EXHAUSTED
        assert "RuntimeError" not in str(err.value)


class TestPagedTokenExactness:
    @pytest.mark.parametrize("block_size", [1, 3, 8])
    def test_streams_match_dense_pool(self, model, block_size):
        """Every block size — single-token pages, a non-divisible tail
        (8 tokens / 3-token pages), and one-page-per-session — serves the
        exact dense-pool stream."""
        config, _ = model
        ids = _prompt(config, np.random.default_rng(1))
        dense = _sigs(model)
        want = _run(dense, _sid("d"), ids)
        paged = _sigs(model, kv_block_size=block_size)
        got = _run(paged, _sid("p"), ids)
        assert got == want

    def test_streams_match_dense_pool_at_lane_dense_rows(self):
        """H * d_kv = 128, a whole lane tile a token row (T5's own head
        size, 64): the paged stream is the dense pool's token for
        token."""
        config = t5.T5Config.tiny(d_kv=64, num_heads=2, d_model=32)
        lane_model = (config, t5.init_params(jax.random.PRNGKey(3), config))
        ids = _prompt(config, np.random.default_rng(4))
        want = _run(_sigs(lane_model), _sid("ld"), ids)
        paged = _sigs(lane_model, kv_block_size=3)
        pool = paged["decode_init"]._kv_pool
        assert pool._arenas[0].shape == (pool.num_blocks + 1, 3, 128)
        assert _run(paged, _sid("lp"), ids) == want

    def test_interleaved_sessions_do_not_disturb_each_other(self, model):
        config, _ = model
        rng = np.random.default_rng(2)
        ids_a, ids_b = _prompt(config, rng), _prompt(config, rng)
        dense = _sigs(model)
        want_a = _run(dense, _sid("da"), ids_a)
        want_b = _run(dense, _sid("db"), ids_b)

        sigs = _sigs(model, kv_block_size=3)
        sa, sb = _sid("il-a"), _sid("il-b")
        sigs["decode_init"].run({"session_id": sa, "input_ids": ids_a})
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
        assert toks_a == want_a
        assert toks_b == want_b

    def test_concurrent_sessions_token_exact(self, model):
        """Concurrency/tick-coalescing invariance: reference = the SAME
        paged program run one session at a time (cross-program exactness
        vs the dense pool is covered on tie-free prompts above)."""
        config, _ = model
        rng = np.random.default_rng(3)
        n = 6
        sigs = _sigs(model, kv_block_size=3)
        prompts = [_prompt(config, rng) for _ in range(n)]
        wants = [_run(sigs, _sid(f"ref-{i}"), prompts[i]) for i in range(n)]
        results = [None] * n
        errors = []

        def worker(i):
            try:
                results[i] = _run(sigs, _sid(f"cc-{i}"), prompts[i])
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
            assert results[i] == wants[i]


class TestPhaseSeparation:
    def test_prefill_queues_and_flushes_at_next_tick(self, model):
        """decode_init parks the prefilled state in the PREFILL phase (no
        pages, no pool-lock device work) and makes its slot due; the
        loop's next tick integrates it through the separate write
        program and computes the first tokens, with no step asked for.
        The first round is held at its pre-tick faultpoint (outside the
        pool's lock) for as long as the pending state is looked at."""
        from min_tfs_client_tpu.robustness import faults

        config, _ = model
        sigs = _sigs(model, kv_block_size=2)
        pool = sigs["decode_init"]._kv_pool
        base = pool.stats()
        ids = _prompt(config, np.random.default_rng(5))
        faults.arm({"rules": [{"point": "backend.tick.pre", "max_fires": 1,
                               "action": "delay", "delay_ms": 1500}]})
        try:
            for i in range(3):
                sigs["decode_init"].run(
                    {"session_id": _sid(f"ph-{i}"), "input_ids": ids})
            stats = pool.stats()
            assert stats["pending_prefills"] == base["pending_prefills"] + 3
            assert stats["blocks_used"] == base["blocks_used"]
            # Explicit flush honors the admission bound...
            assert pool.flush_prefills(limit=1) == 1
            assert pool.stats()["pending_prefills"] == 2
        finally:
            faults.disarm()
        # ...and the rounds that admission started integrate the rest
        # and run every session one token ahead (a page each): two
        # rounds if the held one took the first session alone, then
        # nothing is due.
        until(lambda: pool.stats()["blocks_used"]
               == base["blocks_used"] + 3)
        stats = pool.stats()
        assert stats["pending_prefills"] == 0
        assert stats["prefill_flushed"] >= base["prefill_flushed"] + 3
        ticks = stats["decode_ticks"] - base["decode_ticks"]
        assert ticks in (1, 2)
        # The first step collects a parked token: no tick for it, one
        # after it (the slot is due again).
        sigs["decode_step"].run({"session_id": _sid("ph-0")})
        assert pool.stats()["decode_steps_ahead"] \
            == base["decode_steps_ahead"] + 1
        until(lambda: pool.stats()["decode_ticks"]
               == base["decode_ticks"] + ticks + 1)
        for i in range(3):
            sigs["decode_close"].run({"session_id": _sid(f"ph-{i}")})
        # Three tokens were computed and never collected (one of them
        # may still be in its round when its session closes).
        until(lambda: pool.stats()["decode_tokens_dropped"]
               == base["decode_tokens_dropped"] + 3)

    def test_close_of_pending_session_leaks_nothing(self, model):
        config, _ = model
        sigs = _sigs(model, kv_block_size=2)
        pool = sigs["decode_init"]._kv_pool
        ids = _prompt(config, np.random.default_rng(6))
        sigs["decode_init"].run({"session_id": _sid("pend"),
                                 "input_ids": ids})
        sigs["decode_close"].run({"session_id": _sid("pend")})
        stats = pool.stats()
        assert stats["pending_prefills"] == 0
        assert stats["blocks_used"] == 0
        assert stats["sessions"] == 0


class TestCapacityAndLeaks:
    def test_slot_exhaustion_typed_and_reusable(self, model):
        config, _ = model
        sigs = _sigs(model, kv_block_size=2, max_sessions=4)
        ids = _prompt(config, np.random.default_rng(4))
        for i in range(4):
            sigs["decode_init"].run({"session_id": _sid(f"cap-{i}"),
                                     "input_ids": ids})
        with pytest.raises(ServingError) as err:
            sigs["decode_init"].run({"session_id": _sid("cap-over"),
                                     "input_ids": ids})
        assert err.value.code == RESOURCE_EXHAUSTED
        sigs["decode_close"].run({"session_id": _sid("cap-0")})
        sigs["decode_init"].run({"session_id": _sid("cap-new"),
                                 "input_ids": ids})
        for name in ("cap-1", "cap-2", "cap-3", "cap-new"):
            sigs["decode_close"].run({"session_id": _sid(name)})

    def test_reinit_and_close_return_pages(self, model):
        config, _ = model
        sigs = _sigs(model, kv_block_size=2, max_sessions=4)
        pool = sigs["decode_init"]._kv_pool
        ids = _prompt(config, np.random.default_rng(7))
        for _ in range(3 * 4):  # 3x the slot count, same session id
            sigs["decode_init"].run({"session_id": _sid("re"),
                                     "input_ids": ids})
            sigs["decode_step"].run({"session_id": _sid("re")})
        sigs["decode_close"].run({"session_id": _sid("re")})
        stats = pool.stats()
        assert stats["blocks_used"] == 0
        assert stats["sessions"] == 0

    def test_capacity_scales_with_used_tokens_4x(self, model):
        """THE capacity demonstration: one fixed KV byte budget, short
        sessions (2 used tokens of max_decode_len=8: one collected, one
        the pool computed ahead of its client). The dense pool
        admits budget/max-length-bytes sessions; the paged pool admits
        4x+ because sessions only hold the pages they wrote."""
        config, _ = model
        rng = np.random.default_rng(8)
        prompts = [_prompt(config, rng) for _ in range(64)]

        # Budget: exactly 2 dense sessions' KV state.
        dense = _sigs(model, max_sessions=2)
        dense_admitted = 0
        try:
            for i in range(64):
                _run(dense, _sid(f"dn-{i}"), prompts[i], steps=1)
                dense_admitted += 1
        except ServingError as exc:
            assert exc.code == RESOURCE_EXHAUSTED
        assert dense_admitted == 2

        # Same budget in pages: block_size 2 -> 4 pages/session max-length,
        # so 2 dense sessions = 8 blocks. refuse policy: admission fails
        # typed instead of evicting, making "admitted" well-defined.
        paged = _sigs(model, max_sessions=64, kv_block_size=2,
                      kv_num_blocks=8, kv_evict_policy="refuse")
        pool = paged["decode_init"]._kv_pool
        assert pool.num_blocks == 8
        # The paged arena for this budget must not exceed the dense pool's
        # per-2-session KV bytes (+1 trash page of slack).
        per_page = pool.arena_bytes // (pool.num_blocks + 1)
        assert pool.arena_bytes <= 2 * 4 * per_page + per_page
        paged_admitted = 0
        streams = {}
        try:
            for i in range(64):
                streams[i] = _run(paged, _sid(f"pg-{i}"), prompts[i],
                                  steps=1)
                paged_admitted += 1
        except ServingError as exc:
            assert exc.code == RESOURCE_EXHAUSTED
        assert paged_admitted >= 4 * dense_admitted

        # Release the admitted sessions: the jit cache pins both pools
        # past this test (tick closures live in global PjitFunctions),
        # so abandoned sessions would be REAL leaks — and the armed
        # leak witness treats them as exactly that.
        for i in range(dense_admitted):
            dense["decode_close"].run({"session_id": _sid(f"dn-{i}")})
        # +1: the REFUSED admission keeps its slot by design (refuse
        # policy leaves state intact for retry); close is idempotent.
        for i in range(paged_admitted + 1):
            paged["decode_close"].run({"session_id": _sid(f"pg-{i}")})
        # ... and the admitted sessions are still token-exact.
        dense2 = _sigs(model, max_sessions=2)
        for i in range(2):
            want = _run(dense2, _sid(f"w-{i}"), prompts[i], steps=1)
            assert streams[i] == want
        for i in range(2):
            dense2["decode_close"].run({"session_id": _sid(f"w-{i}")})


class TestEviction:
    def test_swap_restore_bit_identical(self, model):
        """Two sessions alternating under a 5-block pool (each needs up
        to 4): every tick evicts the other's pages to host and restores
        them next tick — streams must equal the unpressured reference
        exactly, and the pressure counters must show it actually swapped."""
        config, _ = model
        rng = np.random.default_rng(9)
        pa, pb = _prompt(config, rng), _prompt(config, rng)
        ref = _sigs(model, kv_block_size=2)
        want_a = _run(ref, _sid("ra"), pa)
        want_b = _run(ref, _sid("rb"), pb)

        sigs = _sigs(model, kv_block_size=2, kv_num_blocks=5)
        pool = sigs["decode_init"]._kv_pool
        sa, sb = _sid("ev-a"), _sid("ev-b")
        sigs["decode_init"].run({"session_id": sa, "input_ids": pa})
        sigs["decode_init"].run({"session_id": sb, "input_ids": pb})
        ta, tb = [], []
        for _ in range(MAXDEC):
            ta.append(int(sigs["decode_step"].run(
                {"session_id": sa})["token"][0]))
            tb.append(int(sigs["decode_step"].run(
                {"session_id": sb})["token"][0]))
        assert ta == want_a
        assert tb == want_b
        stats = pool.stats()
        assert stats["evicted_swap"] > 0
        # Every restore undoes a swap-out; a session whose last token
        # was computed ahead may end swapped out, and is not restored.
        assert 0 < stats["restored"] <= stats["evicted_swap"]
        assert stats["step_contract"] is True

    def test_swap_out_and_restore_carry_the_pages_bitwise(self, model):
        """The arena's unit through `gather_fn` / `restore_fn` /
        `_SwappedSession`, whatever its shape: a victim's pages come back
        bit for bit, on whichever pages the allocator hands out."""
        config, _ = model
        rng = np.random.default_rng(11)
        sigs = _sigs(model, kv_block_size=2, kv_num_blocks=5)
        pool = sigs["decode_init"]._kv_pool
        sa, sb = _sid("rt-a"), _sid("rt-b")
        sigs["decode_init"].run(
            {"session_id": sa, "input_ids": _prompt(config, rng)})
        for _ in range(5):                         # three pages of rows
            sigs["decode_step"].run({"session_id": sa})
        slot = next(iter(pool._pages))
        held = list(pool._pages[slot])
        before = [np.asarray(a)[held] for a in pool._arenas]
        assert all(b.any() for b in before)
        assert before[0].shape == (3, 2, config.num_heads * config.d_kv)

        # A second session that needs the pool's other pages evicts it...
        sigs["decode_init"].run(
            {"session_id": sb, "input_ids": _prompt(config, rng)})
        for _ in range(5):
            sigs["decode_step"].run({"session_id": sb})
        assert slot in pool._swapped and slot not in pool._pages
        for host, want in zip(pool._swapped[slot].pages_host, before):
            np.testing.assert_array_equal(host[:len(held)], want)
        # ...and its own next step restores it, onto other pages or not.
        sigs["decode_close"].run({"session_id": sb})
        with pool._lock:
            pool._restore_locked(slot, ())
        after = [np.asarray(a)[pool._pages[slot]] for a in pool._arenas]
        for got, want in zip(after, before):
            np.testing.assert_array_equal(got, want)
        sigs["decode_close"].run({"session_id": sa})

    def test_close_policy_kills_oldest_idle_with_typed_error(self, model):
        config, _ = model
        rng = np.random.default_rng(10)
        pa, pb = _prompt(config, rng), _prompt(config, rng)
        ref = _sigs(model, kv_block_size=2)
        want_b = _run(ref, _sid("rb2"), pb)

        # 4 blocks: B alone can reach its 4-page worst case only after A
        # (oldest idle, 1 page) is dropped.
        sigs = _sigs(model, kv_block_size=2, kv_num_blocks=4,
                     kv_evict_policy="close")
        sa, sb = _sid("cl-a"), _sid("cl-b")
        want_a = _run(ref, _sid("ra2"), pa, steps=2)
        ref["decode_close"].run({"session_id": _sid("ra2")})
        pool = sigs["decode_init"]._kv_pool
        sigs["decode_init"].run({"session_id": sa, "input_ids": pa})
        ta = [int(sigs["decode_step"].run({"session_id": sa})["token"][0])]
        # A's second token is computed ahead of its client, and parked.
        until(lambda: pool.stats()["decode_ticks"] == 2)
        tb = _run(sigs, sb, pb)
        assert tb == want_b  # the aggressor's stream is undisturbed
        # The token that was computed before the eviction is A's to
        # collect; the step after it meets the preemption.
        ta.append(int(sigs["decode_step"].run(
            {"session_id": sa})["token"][0]))
        assert ta == want_a
        with pytest.raises(ServingError) as err:
            sigs["decode_step"].run({"session_id": sa})
        assert err.value.code == RESOURCE_EXHAUSTED
        assert "preempted" in str(err.value)
        # The victim's slot was retired; a fresh init works.
        sigs["decode_init"].run({"session_id": sa, "input_ids": pa})
        sigs["decode_close"].run({"session_id": sa})

    def test_refuse_policy_typed_error_session_survives(self, model):
        config, _ = model
        rng = np.random.default_rng(11)
        pa, pb = _prompt(config, rng), _prompt(config, rng)
        ref = _sigs(model, kv_block_size=4)
        want_a = _run(ref, _sid("ra3"), pa)

        # block_size 4 -> 2 pages/session; 2 blocks total. A takes page 1
        # at step 1; B takes page 2; A's step 5 needs its second page ->
        # typed refusal, session intact.
        sigs = _sigs(model, kv_block_size=4, kv_num_blocks=2,
                     kv_evict_policy="refuse")
        sa, sb = _sid("rf-a"), _sid("rf-b")
        sigs["decode_init"].run({"session_id": sa, "input_ids": pa})
        sigs["decode_init"].run({"session_id": sb, "input_ids": pb})
        toks = [int(sigs["decode_step"].run(
            {"session_id": sa})["token"][0]) for _ in range(4)]
        sigs["decode_step"].run({"session_id": sb})
        with pytest.raises(ServingError) as err:
            sigs["decode_step"].run({"session_id": sa})
        assert err.value.code == RESOURCE_EXHAUSTED
        # Close B -> A's retry continues its exact stream.
        sigs["decode_close"].run({"session_id": sb})
        while len(toks) < MAXDEC:
            toks.append(int(sigs["decode_step"].run(
                {"session_id": sa})["token"][0]))
        assert toks == want_a


class TestServerSurface:
    def test_module_paging_defaults_scope(self):
        with paging_scope(block_size=4, num_blocks=7,
                          evict_policy="close", prefill_chunk=6):
            assert Paging.resolve() == Paging(4, 7, "close", 6)
            # An explicit knob wins; the others still come from the scope.
            assert Paging.resolve(block_size=0) == Paging(0, 7, "close", 6)
        assert Paging.resolve() == Paging()
        assert Paging().block_size == 0

    def test_paging_scope_isolates_concurrent_loads(self):
        """Regression (review): a process-global set/restore pair races
        concurrent loads both ways — a scoped load's restore lands while
        another scoped factory is mid-flight, AND an UNCONFIGURED load's
        factory observes a configured load's scope and silently builds a
        paged pool. The thread-local paging_scope gives every factory
        exactly its own knobs."""
        seen = []
        errors = []
        start = threading.Barrier(5)

        def scoped_load(block_size):
            try:
                start.wait(5)
                with paging_scope(block_size=block_size, num_blocks=7):
                    # The "factory": reads the knobs a builder would.
                    for _ in range(50):
                        got = Paging.resolve()
                        assert got.block_size == block_size, got
                    seen.append(block_size)
            except Exception as exc:  # noqa: BLE001
                errors.append(exc)

        def unscoped_load():
            # A dense-configured model loading alongside paged ones must
            # keep seeing the process default (0), never a scope.
            try:
                start.wait(5)
                for _ in range(200):
                    got = Paging.resolve()
                    assert got.block_size == 0, got
                seen.append(0)
            except Exception as exc:  # noqa: BLE001
                errors.append(exc)

        threads = [threading.Thread(target=scoped_load, args=(bs,))
                   for bs in (2, 4, 8, 16)]
        threads.append(threading.Thread(target=unscoped_load))
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors
        assert sorted(seen) == [0, 2, 4, 8, 16]
        assert Paging.resolve().block_size == 0  # no scope leaked

    def test_bad_evict_policy_rejected(self):
        with pytest.raises(ServingError) as err:
            with paging_scope(block_size=2, evict_policy="lru"):
                pass
        assert err.value.code == 3  # INVALID_ARGUMENT
        with pytest.raises(ServingError) as err:
            Paging.resolve(block_size=2, evict_policy="lru")
        assert err.value.code == 3

    def test_builder_consults_module_defaults(self, model):
        with paging_scope(block_size=2, num_blocks=6):
            sigs = _sigs(model)
        pool = getattr(sigs["decode_init"], "_kv_pool", None)
        assert pool is not None
        assert pool.block_size == 2 and pool.num_blocks == 6

    def test_capacity_error_serves_resource_exhausted_not_internal(
            self, model, tmp_path):
        """Regression (ISSUE 9 satellite): pool exhaustion through the
        serving handlers must reach the wire as RESOURCE_EXHAUSTED — a
        capacity condition — and must NOT ring an INTERNAL into the
        flight recorder or trip its one-shot dump latch."""
        import dataclasses

        import grpc

        from min_tfs_client_tpu.client import TensorServingClient
        from min_tfs_client_tpu.models import export
        from min_tfs_client_tpu.observability import flight_recorder

        config, params = model
        base = tmp_path / "t5paged"
        export.export_servable(
            base, 1, "t5", dataclasses.asdict(config), params,
            signature_kwargs={"seq_len": SEQ, "max_decode_len": MAXDEC,
                              "continuous_batching": True,
                              "max_sessions": 2, "kv_block_size": 2})
        client = TensorServingClient(f"tpu://{base}")
        flight_recorder.recorder.reset()
        ids = _prompt(config, np.random.default_rng(12))
        for i in range(2):
            client.predict_request(
                "t5paged", {"session_id": _sid(f"h-{i}"), "input_ids": ids},
                signature_name="decode_init", timeout=600)
        with pytest.raises(grpc.RpcError) as err:
            client.predict_request(
                "t5paged", {"session_id": _sid("h-over"), "input_ids": ids},
                signature_name="decode_init", timeout=600)
        assert err.value.code() == grpc.StatusCode.RESOURCE_EXHAUSTED
        events = flight_recorder.recorder.snapshot()
        internals = [e for e in events
                     if e[2] == "error" and e[3].get("code") == 13]
        assert internals == []  # no INTERNAL => dump latch untouched
        for i in range(2):
            client.predict_request(
                "t5paged", {"session_id": _sid(f"h-{i}")},
                signature_name="decode_close", timeout=600)
        client.close()
        # The lazily-booted tpu:// server is registry-pinned with live
        # servable-load workers until someone owns its teardown.
        from min_tfs_client_tpu.server.local import shutdown_local_server

        assert shutdown_local_server(str(base))


class TestStepContract:
    """ISSUE 11 tentpole: the pooled tick drives the ragged paged path
    through the model's paging-aware step contract — no dense
    materialization. Since ISSUE 29 it is the paged pool's only decode
    program."""

    def test_contract_on_by_default_and_fallback_matches(self, model):
        """The contract's streams against the dense pool's (the
        dense-gather fallback it was once compared with is gone);
        `step_contract` stays in the monitoring payload, always True."""
        config, _ = model
        rng = np.random.default_rng(20)
        prompts = [_prompt(config, rng) for _ in range(3)]
        direct = _sigs(model, kv_block_size=3)
        assert direct["decode_init"]._kv_pool.stats()["step_contract"] \
            is True
        dense = _sigs(model)
        assert not hasattr(dense["decode_init"], "_kv_pool")
        for i, ids in enumerate(prompts):
            want = _run(dense, _sid(f"fb-{i}"), ids)
            got = _run(direct, _sid(f"dc-{i}"), ids)
            assert got == want

    def test_sampled_sessions_through_contract_match_dense(self, model):
        """The contract's sampling branch (per-slot PRNG keys riding the
        dense state, _sample_token after the paged logits): same
        temperature/seed must reproduce the dense pool's stream."""
        config, _ = model
        rng = np.random.default_rng(32)
        ids = _prompt(config, rng)

        def run_sampled(sigs, name):
            sigs["decode_init"].run(
                {"session_id": _sid(name), "input_ids": ids,
                 "temperature": np.asarray([0.8], np.float32),
                 "seed": np.asarray([7], np.int32)})
            return [int(sigs["decode_step"].run(
                {"session_id": _sid(name)})["token"][0])
                for _ in range(MAXDEC)]

        dense = _sigs(model, sampling=True)
        want = run_sampled(dense, "sm-d")
        paged = _sigs(model, sampling=True, kv_block_size=3)
        assert paged["decode_init"]._kv_pool.stats()["step_contract"]
        got = run_sampled(paged, "sm-p")
        assert got == want

    def test_gather_bytes_scale_with_used_tokens(self, model):
        """THE bandwidth bar, asserted: the tick's KV reads are the
        pages live sessions own, not slots x table-width (what a dense
        gather of the whole table would materialize)."""
        config, _ = model
        ids = _prompt(config, np.random.default_rng(21))
        sigs = _sigs(model, kv_block_size=2, max_sessions=8)
        pool = sigs["decode_init"]._kv_pool
        sigs["decode_init"].run({"session_id": _sid("gb"),
                                 "input_ids": ids})
        for step in range(4):
            sigs["decode_step"].run({"session_id": _sid("gb")})
            stats = pool.stats()
            # One session, one token a tick: the pool is one token ahead
            # of the client, two once the next round is under way.
            tokens = stats["decode_ticks"]
            assert step + 1 <= tokens <= step + 2
            pages_held = -(-tokens // pool.block_size)
            assert stats["kv_gather_bytes_per_tick"] == \
                pool.page_bytes * pages_held
        # The whole (slots, width) table on the same tick shape; the
        # tick read 1 session's 2 pages of it.
        table_bytes = pool.page_bytes * pool.max_slots * \
            pool.stats()["table_width"]
        assert stats["kv_gather_bytes_per_tick"] * 4 <= table_bytes
        from min_tfs_client_tpu.server import metrics

        assert metrics.kv_gather_bytes_per_tick.value("t5-paged") == \
            stats["kv_gather_bytes_per_tick"]
        sigs["decode_close"].run({"session_id": _sid("gb")})

    def test_table_width_shrinks_when_high_water_session_departs(
            self, model):
        """Satellite regression: one long-dead outlier must not pin wide
        tick shapes forever — and the shrunk-width program must keep the
        survivors' streams exact."""
        config, _ = model
        rng = np.random.default_rng(22)
        p_long, p_short = _prompt(config, rng), _prompt(config, rng)
        ref = _sigs(model, kv_block_size=2)
        want_short = _run(ref, _sid("ws-ref"), p_short)

        sigs = _sigs(model, kv_block_size=2)
        pool = sigs["decode_init"]._kv_pool
        # Long session: 7 tokens -> 4 pages -> width bucket 4.
        sigs["decode_init"].run({"session_id": _sid("ws-long"),
                                 "input_ids": p_long})
        for _ in range(7):
            sigs["decode_step"].run({"session_id": _sid("ws-long")})
        assert pool.stats()["table_width"] == 4
        # Short session: 1 token so far -> 1 page.
        sigs["decode_init"].run({"session_id": _sid("ws-short"),
                                 "input_ids": p_short})
        toks = [int(sigs["decode_step"].run(
            {"session_id": _sid("ws-short")})["token"][0])]
        # High-water session departs -> width drops to the survivor's.
        sigs["decode_close"].run({"session_id": _sid("ws-long")})
        assert pool.stats()["table_width"] == 1
        while len(toks) < MAXDEC - 1:
            toks.append(int(sigs["decode_step"].run(
                {"session_id": _sid("ws-short")})["token"][0]))
        # ...and it re-grew on demand as the survivor's pages grew (the
        # final step below releases the slot, shrinking width again).
        assert pool.stats()["table_width"] == 4
        toks.append(int(sigs["decode_step"].run(
            {"session_id": _sid("ws-short")})["token"][0]))
        assert toks == want_short
        assert pool.stats()["table_width"] == 1


class TestChunkedPrefill:
    """decode_init_prefix: forced decoder prefixes stream through the
    contract's Sq>1 kernel path in bounded chunks, interleaved with
    decode ticks; dense pools prefill monolithically. Streams identical."""

    def _prefix(self, config, rng, n):
        pre = np.full((1, MAXDEC), config.pad_id, np.int32)
        pre[0, :n] = rng.integers(2, config.vocab_size, n)
        return pre

    def _run_prefix(self, sigs, name, ids, pre, steps):
        out = sigs["decode_init_prefix"].run(
            {"session_id": _sid(name), "input_ids": ids,
             "prefix_ids": pre})
        toks = []
        for _ in range(steps):
            row = sigs["decode_step"].run({"session_id": _sid(name)})
            toks.append((int(row["token"][0]), int(row["step"])))
        return int(out["prefix_len"]), toks

    @pytest.mark.parametrize("block_size,chunk", [(2, 0), (3, 2)])
    def test_chunked_matches_dense_monolithic(self, model, block_size,
                                              chunk):
        """Tier-1 smoke: non-divisible chunks (5 positions in rounds of
        2) and page-aligned default chunks both reproduce the dense
        pool's monolithic-prefill continuation exactly."""
        config, _ = model
        rng = np.random.default_rng(23)
        ids, pre = _prompt(config, rng), self._prefix(config, rng, 5)
        dense = _sigs(model)
        want = self._run_prefix(dense, "cp-d", ids, pre, MAXDEC - 5)
        paged = _sigs(model, kv_block_size=block_size,
                      kv_prefill_chunk=chunk)
        got = self._run_prefix(paged, "cp-p", ids, pre, MAXDEC - 5)
        assert got == want
        stats = paged["decode_init"]._kv_pool.stats()
        expect_rounds = -(-5 // (chunk or block_size))
        assert stats["prefill_chunks"] == expect_rounds
        assert stats["chunking_sessions"] == 0

    @pytest.mark.slow
    @pytest.mark.parametrize("block_size,chunk,plen",
                             [(1, 1, 7), (2, 3, 6), (3, 1, 4), (4, 4, 7),
                              (8, 2, 5)])
    def test_chunked_matches_dense_sweep(self, model, block_size, chunk,
                                         plen):
        config, _ = model
        rng = np.random.default_rng(block_size * 100 + chunk * 10 + plen)
        ids, pre = _prompt(config, rng), self._prefix(config, rng, plen)
        dense = _sigs(model)
        want = self._run_prefix(dense, "cs-d", ids, pre, MAXDEC - plen)
        paged = _sigs(model, kv_block_size=block_size,
                      kv_prefill_chunk=chunk)
        got = self._run_prefix(paged, "cs-p", ids, pre, MAXDEC - plen)
        assert got == want

    def test_prefix_interleaves_with_decode_ticks(self, model):
        """A long prefix streaming chunk-by-chunk must not perturb a
        concurrently decoding session — and both finish exact."""
        config, _ = model
        rng = np.random.default_rng(24)
        ids_a, ids_b = _prompt(config, rng), _prompt(config, rng)
        pre = self._prefix(config, rng, 6)
        ref = _sigs(model, kv_block_size=2)
        want_a = _run(ref, _sid("il2-ra"), ids_a)
        want_b = self._run_prefix(ref, "il2-rb", ids_b, pre, MAXDEC - 6)

        sigs = _sigs(model, kv_block_size=2, kv_prefill_chunk=2)
        sigs["decode_init"].run({"session_id": _sid("il2-a"),
                                 "input_ids": ids_a})
        toks_a = [int(sigs["decode_step"].run(
            {"session_id": _sid("il2-a")})["token"][0]) for _ in range(3)]
        got_b = self._run_prefix(sigs, "il2-b", ids_b, pre, MAXDEC - 6)
        while len(toks_a) < MAXDEC:
            toks_a.append(int(sigs["decode_step"].run(
                {"session_id": _sid("il2-a")})["token"][0]))
        assert toks_a == want_a
        assert got_b == want_b

    def test_chunked_prefill_under_page_pressure_swaps_exact(self, model):
        """Chunking sessions hold pages and can be swap victims mid-
        prefix; the restore must continue the chunk stream bit-exact."""
        config, _ = model
        rng = np.random.default_rng(25)
        ids_a, ids_b = _prompt(config, rng), _prompt(config, rng)
        pre = self._prefix(config, rng, 6)
        ref = _sigs(model, kv_block_size=2)
        want_b = self._run_prefix(ref, "pp-rb", ids_b, pre, MAXDEC - 6)
        # 5 blocks for two sessions needing up to 4 each -> guaranteed
        # eviction traffic while B's prefix streams.
        sigs = _sigs(model, kv_block_size=2, kv_num_blocks=5,
                     kv_prefill_chunk=2)
        pool = sigs["decode_init"]._kv_pool
        sigs["decode_init"].run({"session_id": _sid("pp-a"),
                                 "input_ids": ids_a})
        for _ in range(6):
            sigs["decode_step"].run({"session_id": _sid("pp-a")})
        got_b = self._run_prefix(sigs, "pp-b", ids_b, pre, MAXDEC - 6)
        assert got_b == want_b
        assert pool.stats()["evicted_swap"] > 0
        for sid in ("pp-a", "pp-b"):
            sigs["decode_close"].run({"session_id": _sid(sid)})

    def test_refuse_policy_mid_prefix_surfaces_typed_error_then_resumes(
            self, model):
        """Liveness regression: with kv_evict_policy=refuse and a dry
        pool, a mid-prefix capacity refusal must surface to the
        requesting step as RESOURCE_EXHAUSTED (session + chunk progress
        intact) — NOT leave the caller spinning on the prefill sentinel.
        After pressure clears, the retry finishes the exact stream."""
        config, _ = model
        rng = np.random.default_rng(33)
        ids_a, ids_b = _prompt(config, rng), _prompt(config, rng)
        pre = self._prefix(config, rng, 6)
        ref = _sigs(model, kv_block_size=2)
        want_b = self._run_prefix(ref, "rfp-rb", ids_b, pre, MAXDEC - 6)

        # 4 blocks: A pins 2 (4 tokens); B's 6-position prefix needs 3.
        sigs = _sigs(model, kv_block_size=2, kv_num_blocks=4,
                     kv_evict_policy="refuse", kv_prefill_chunk=2)
        sigs["decode_init"].run({"session_id": _sid("rfp-a"),
                                 "input_ids": ids_a})
        for _ in range(4):
            sigs["decode_step"].run({"session_id": _sid("rfp-a")})
        sigs["decode_init_prefix"].run(
            {"session_id": _sid("rfp-b"), "input_ids": ids_b,
             "prefix_ids": pre})
        with pytest.raises(ServingError) as err:
            sigs["decode_step"].run({"session_id": _sid("rfp-b")})
        assert err.value.code == RESOURCE_EXHAUSTED
        sigs["decode_close"].run({"session_id": _sid("rfp-a")})
        toks = []
        for _ in range(MAXDEC - 6):
            row = sigs["decode_step"].run({"session_id": _sid("rfp-b")})
            toks.append((int(row["token"][0]), int(row["step"])))
        assert toks == want_b[1]

    def test_close_mid_prefix_leaks_nothing(self, model):
        config, _ = model
        rng = np.random.default_rng(26)
        ids, pre = _prompt(config, rng), self._prefix(config, rng, 6)
        sigs = _sigs(model, kv_block_size=2, kv_prefill_chunk=2)
        pool = sigs["decode_init"]._kv_pool
        sigs["decode_init_prefix"].run(
            {"session_id": _sid("cm"), "input_ids": ids,
             "prefix_ids": pre})
        sigs["decode_close"].run({"session_id": _sid("cm")})
        stats = pool.stats()
        assert stats["sessions"] == 0
        assert stats["blocks_used"] == 0
        assert stats["chunking_sessions"] == 0

    def test_unpooled_prefix_matches_pooled_dense(self, model):
        config, _ = model
        rng = np.random.default_rng(27)
        ids, pre = _prompt(config, rng), self._prefix(config, rng, 4)
        dense = _sigs(model)
        want = self._run_prefix(dense, "up-d", ids, pre, MAXDEC - 4)
        unpooled = _sigs(model, continuous_batching=False)
        got = self._run_prefix(unpooled, "up-u", ids, pre, MAXDEC - 4)
        assert got == want

    def test_bad_prefixes_rejected(self, model):
        config, _ = model
        ids = _prompt(config, np.random.default_rng(29))
        sigs = _sigs(model, kv_block_size=2)
        empty = np.full((1, MAXDEC), config.pad_id, np.int32)
        with pytest.raises(ServingError) as err:
            sigs["decode_init_prefix"].run(
                {"session_id": _sid("bp"), "input_ids": ids,
                 "prefix_ids": empty})
        assert err.value.code == 3  # INVALID_ARGUMENT
        holey = np.full((1, MAXDEC), config.pad_id, np.int32)
        holey[0, 0], holey[0, 2] = 5, 7  # pad in the middle
        with pytest.raises(ServingError) as err:
            sigs["decode_init_prefix"].run(
                {"session_id": _sid("bp"), "input_ids": ids,
                 "prefix_ids": holey})
        assert err.value.code == 3
        # Full-width prefix (review finding): zero decode budget remains,
        # and on dense pools the first step's clamped cache write would
        # silently corrupt the last prefix row — typed rejection instead.
        full = np.full((1, MAXDEC), 5, np.int32)
        for surface in (sigs,
                        _sigs(model),              # dense pool
                        _sigs(model, continuous_batching=False)):
            with pytest.raises(ServingError) as err:
                surface["decode_init_prefix"].run(
                    {"session_id": _sid("bp2"), "input_ids": ids,
                     "prefix_ids": full})
            assert err.value.code == 3


class TestPagedSpeculative:
    def test_verify_blocks_through_block_tables_token_exact(self, model):
        """Speculative decoding composes with paging: the target's Sq>1
        verify blocks run through block tables, streams bitwise equal to
        the dense-cache speculative path AND to plain greedy."""
        import jax.numpy as jnp

        config, params = model
        draft_cfg = t5.T5Config.tiny(num_decoder_layers=1,
                                     num_encoder_layers=1)
        draft = t5.init_params(jax.random.PRNGKey(5), draft_cfg)
        rng = np.random.default_rng(30)
        ids = jnp.asarray(rng.integers(2, config.vocab_size, (2, SEQ)),
                          jnp.int32)
        lens = jnp.sum((ids != config.pad_id).astype(jnp.int32), axis=-1)
        g_ids, _ = t5.greedy_decode(params, config, ids, lens,
                                    max_decode_len=MAXDEC)
        dense = t5.speculative_decode(params, config, draft, draft_cfg,
                                      ids, lens, max_decode_len=MAXDEC,
                                      k=3)
        for bs in (2, 3):
            paged = t5.speculative_decode(
                params, config, draft, draft_cfg, ids, lens,
                max_decode_len=MAXDEC, k=3, kv_block_size=bs)
            assert jnp.array_equal(paged[0], dense[0])
            assert jnp.array_equal(paged[1], dense[1])
            assert int(paged[2]) == int(dense[2])
        assert jnp.array_equal(dense[0], g_ids)

    def test_builder_routes_speculative_through_paging(self, model):
        """build_signatures with paging on serves decode_speculative
        through the paged verify path, same bytes on the wire."""
        config, params = model
        draft_cfg = t5.T5Config.tiny(num_decoder_layers=1,
                                     num_encoder_layers=1)
        draft = t5.init_params(jax.random.PRNGKey(5), draft_cfg)
        rng = np.random.default_rng(31)
        ids = rng.integers(2, config.vocab_size, (2, SEQ)).astype(np.int32)

        def build(**kw):
            return t5.build_signatures(
                params, config, seq_len=SEQ, max_decode_len=MAXDEC,
                draft_params=draft, draft_config=draft_cfg,
                speculative_k=3, **kw)["decode_speculative"]

        want = build().run({"input_ids": ids})
        got = build(kv_block_size=2).run({"input_ids": ids})
        np.testing.assert_array_equal(got["output_ids"],
                                      want["output_ids"])
        np.testing.assert_array_equal(got["output_lengths"],
                                      want["output_lengths"])


def test_synthesize_warmup_primes_paged_executables(model):
    """The warmup hook drives prefill + paged tick end to end and leaves
    no pages, pending prefills, or sessions behind."""
    import types

    from min_tfs_client_tpu.servables.warmup import synthesize_warmup

    config, params = model
    sigs = t5.build_session_signatures(
        params, config, seq_len=SEQ, max_decode_len=MAXDEC,
        max_sessions=4, continuous_batching=True, kv_block_size=2)
    servable = types.SimpleNamespace(signatures=sigs)
    assert synthesize_warmup(servable) == 1
    pool = sigs["decode_init"]._kv_pool
    stats = pool.stats()
    assert stats["blocks_used"] == 0
    assert stats["sessions"] == 0
    assert stats["decode_ticks"] >= 1
    # The warmup also primes the decode_init_prefix path (review
    # finding): the chunked-prefill program must have run and cleaned up.
    assert stats["prefill_chunks"] >= 1
    assert stats["chunking_sessions"] == 0


class TestSessionTimelinesThroughPool:
    """The fleet-observability acceptance bar at the pool level: a
    session's /monitoring/sessions timeline shows its prefill-chunk
    rounds, and swap/restore events when forced under page pressure —
    and the ragged telemetry satellites export as Prometheus series."""

    def _prefix(self, config, rng, n):
        pre = np.full((1, MAXDEC), config.pad_id, np.int32)
        pre[0, :n] = rng.integers(2, config.vocab_size, n)
        return pre

    def _timeline_kinds(self, session: str) -> list[str]:
        from min_tfs_client_tpu.servables import decode_sessions

        detail = decode_sessions.sessions_payload(session=session)
        assert detail["found"], f"no timeline for {session}"
        return [e["kind"] for t in detail["timelines"]
                for e in t["events"]]

    def test_timeline_shows_prefill_chunk_rounds(self, model):
        config, _ = model
        rng = np.random.default_rng(31)
        ids, pre = _prompt(config, rng), self._prefix(config, rng, 5)
        sigs = _sigs(model, kv_block_size=2, kv_prefill_chunk=2)
        sigs["decode_init_prefix"].run(
            {"session_id": _sid("tl-prefix"), "input_ids": ids,
             "prefix_ids": pre})
        for _ in range(2):
            sigs["decode_step"].run({"session_id": _sid("tl-prefix")})
        kinds = self._timeline_kinds("tl-prefix")
        assert kinds[0] == "init"
        assert "prefill_queued" in kinds
        # 5 prefix positions in rounds of 2 -> 3 chunk rounds, each an
        # event carrying progress + pages held.
        assert kinds.count("prefill_chunk") == 3
        assert "tick" in kinds
        from min_tfs_client_tpu.servables import decode_sessions

        detail = decode_sessions.sessions_payload(session="tl-prefix")
        chunks = [e for t in detail["timelines"] for e in t["events"]
                  if e["kind"] == "prefill_chunk"]
        assert [c["done"] for c in chunks] == [2, 4, 5]
        assert all(c["pages"] >= 1 for c in chunks)
        ticks = [e for t in detail["timelines"] for e in t["events"]
                 if e["kind"] == "tick"]
        assert all("tokens" in t and "pages" in t and "tick_ms" in t
                   for t in ticks)
        sigs["decode_close"].run({"session_id": _sid("tl-prefix")})
        assert self._timeline_kinds("tl-prefix")[-1] == "close"

    def test_timeline_shows_swap_and_restore_under_pressure(self, model):
        """Same 5-blocks-for-two-4-page-sessions squeeze as the
        exactness suite — here the claim is the EVENTS: the victim's
        timeline must show swap_out and the matching restore."""
        config, _ = model
        rng = np.random.default_rng(32)
        pa, pb = _prompt(config, rng), _prompt(config, rng)
        sigs = _sigs(model, kv_block_size=2, kv_num_blocks=5)
        sa, sb = _sid("tl-sw-a"), _sid("tl-sw-b")
        sigs["decode_init"].run({"session_id": sa, "input_ids": pa})
        sigs["decode_init"].run({"session_id": sb, "input_ids": pb})
        for _ in range(MAXDEC):
            sigs["decode_step"].run({"session_id": sa})
            sigs["decode_step"].run({"session_id": sb})
        pool = sigs["decode_init"]._kv_pool
        assert pool.stats()["evicted_swap"] > 0  # pressure actually hit
        kinds_a = self._timeline_kinds("tl-sw-a")
        kinds_b = self._timeline_kinds("tl-sw-b")
        swapped = kinds_a if "swap_out" in kinds_a else kinds_b
        assert "swap_out" in swapped
        assert "restore" in swapped
        # restore follows its swap_out on the same timeline
        assert swapped.index("restore") > swapped.index("swap_out")
        sigs["decode_close"].run({"session_id": sa})
        sigs["decode_close"].run({"session_id": sb})

    def test_kv_telemetry_exports_as_prometheus_series(self, model):
        """Satellite pin: kv_gather_bytes_per_tick (gauge) and
        kv_prefill_chunks (counter) must appear in the Prometheus text
        export with the pool's model label after real pool traffic —
        stats/payload-only telemetry cannot be dashboarded."""
        from min_tfs_client_tpu.server.metrics import prometheus_text

        config, _ = model
        rng = np.random.default_rng(33)
        ids, pre = _prompt(config, rng), self._prefix(config, rng, 4)
        sigs = _sigs(model, kv_block_size=2, kv_prefill_chunk=2)
        sigs["decode_init_prefix"].run(
            {"session_id": _sid("prom-kv"), "input_ids": ids,
             "prefix_ids": pre})
        sigs["decode_step"].run({"session_id": _sid("prom-kv")})
        text = prometheus_text()
        label = sigs["decode_init"]._kv_pool.metric_label
        gather = [line for line in text.splitlines()
                  if line.startswith("tpu_serving_kv_gather_bytes_per_tick")
                  and f'model="{label}"' in line]
        assert gather, "gauge missing from the Prometheus export"
        assert float(gather[0].rsplit(" ", 1)[1]) > 0
        chunks = [line for line in text.splitlines()
                  if line.startswith("tpu_serving_kv_prefill_chunks")
                  and f'model="{label}"' in line]
        assert chunks, "counter missing from the Prometheus export"
        assert float(chunks[0].rsplit(" ", 1)[1]) >= 2  # 4 positions / 2
        sigs["decode_close"].run({"session_id": _sid("prom-kv")})


class TestDecodeLoopPhases:
    """The spans that name the time between two ticks
    (docs/OBSERVABILITY.md "Decode loop phases"): the round leader's
    consecutive phases and every rider's wait, tied by `round`."""

    PHASES = ("decode/prepare", "decode/tick", "decode/wake",
              "decode/fetch", "decode/deliver")

    def _step_sessions(self, sigs, config, n, steps, seed=31):
        """n sessions stepping side by side, each step inside its own
        request trace as a handler would open it; returns the traces."""
        from min_tfs_client_tpu.observability import tracing

        rng = np.random.default_rng(seed)
        for i in range(n):
            sigs["decode_init"].run({"session_id": _sid(f"ph-{seed}-{i}"),
                                     "input_ids": _prompt(config, rng)})
        traces, errors = [], []
        start = threading.Barrier(n)

        def worker(i):
            try:
                start.wait(10)
                for _ in range(steps):
                    with tracing.request_trace("decode_step") as trace:
                        sigs["decode_step"].run(
                            {"session_id": _sid(f"ph-{seed}-{i}")})
                    traces.append(trace)
            except Exception as exc:  # noqa: BLE001
                errors.append(exc)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors
        return traces

    def _close(self, sigs, n, seed=31):
        for i in range(n):
            sigs["decode_close"].run({"session_id": _sid(f"ph-{seed}-{i}")})

    @staticmethod
    def _by_round(traces, names):
        rounds: dict = {}
        for tr in traces:
            if tr is None:
                continue
            for name, t0, t1, args in tr.spans:
                if name in names:
                    rounds.setdefault(args["round"], []).append(
                        (name, t0, t1, args))
        return rounds

    def test_each_round_has_its_phases_once_in_order(self, model):
        config, _ = model
        n, steps = 3, 5
        sigs = _sigs(model, kv_block_size=2)
        pool = sigs["decode_init"]._kv_pool
        traces = self._step_sessions(sigs, config, n, steps)
        until(lambda: pool.stats()["blocks_used"] == 3 * n)
        width_at_end = pool.stats()["table_width"]
        self._close(sigs, n)
        rounds = self._by_round(traces,
                                self.PHASES + ("decode/handoff",))
        assert rounds and 0 not in rounds
        stepped = 0
        launch_of = {}
        for r, spans in sorted(rounds.items()):
            by_name = {name: (t0, t1, args) for name, t0, t1, args in spans}
            # Once each, over all the traces; a round's `decode/deliver`
            # is written when the next round is launched, and is lost if
            # every rider has collected by then.
            assert sorted(name for name, *_ in spans) in (
                sorted(self.PHASES + ("decode/handoff",)),
                sorted(self.PHASES[:-1] + ("decode/handoff",))), (r, spans)
            order = [by_name[name] for name in
                     ("decode/handoff",) + self.PHASES if name in by_name]
            for (_, end, _), (begin, _, _) in zip(order, order[1:]):
                assert end <= begin, (r, spans)
            prepare, tick = by_name["decode/prepare"], by_name["decode/tick"]
            assert tick[2]["slots"] == prepare[2]["live"] \
                == by_name["decode/handoff"][2]["riders"]
            # The loop thread's CPU inside each phase it is inside: no
            # more than the phase is long (a clock tick of room).
            for name in ("decode/handoff",) + self.PHASES[:-1]:
                t0, t1, args = by_name[name]
                assert 0 <= args["cpu_us"] <= (t1 - t0) * 1e6 + 1000, name
            wake = by_name["decode/wake"][2]
            assert wake["under_pool_lock"] == 1
            assert 0 <= wake["woken"] <= n
            assert tick[2]["width"] in (1, 2, 4)
            # The pages the riders hold: at least one each, never more
            # than their table rows have entries.
            assert tick[2]["slots"] <= tick[2]["pages"] \
                <= tick[2]["slots"] * tick[2]["width"]
            stepped += tick[2]["slots"]
            launch_of[r] = (by_name["decode/handoff"][1], tick[1])
        # Every collected token is one rider of one of these rounds; the
        # tokens that ran ahead of the sessions' last steps are the rest.
        assert n * steps <= stepped <= n * (steps + 1)
        # `decode/deliver` spans the next round's launch, where the loop
        # went straight on to one (it ends with the loop otherwise).
        spanned = 0
        for r, spans in rounds.items():
            deliver = [s for s in spans if s[0] == "decode/deliver"]
            if deliver and r + 1 in launch_of:
                taken, launched = launch_of[r + 1]
                if taken <= deliver[0][2]:
                    assert deliver[0][2] >= launched
                    spanned += 1
        assert spanned
        last = max(rounds)
        assert {s[0]: s[3] for s in rounds[last]}["decode/tick"]["width"] \
            == width_at_end == 4  # 5 tokens, 2 a page: 3 pages, bucket 4

    def test_every_step_waited_for_a_round_the_loop_recorded(self, model):
        """Each step has one `decode/wait`, inside its own request, that
        names a round whose phases one of the requests took onto its
        trace, and says whether the loop was ahead of it."""
        config, _ = model
        n, steps = 3, 4
        sigs = _sigs(model, kv_block_size=2)
        pool = sigs["decode_init"]._kv_pool
        ahead_before = pool.stats()["decode_steps_ahead"]
        traces = self._step_sessions(sigs, config, n, steps, seed=32)
        taken = {a["round"]: t1 for tr in traces
                 for name, _, t1, a in tr.spans if name == "decode/handoff"}
        carriers = [a["round"] for tr in traces
                    for name, _, _, a in tr.spans if name == "decode/tick"]
        assert sorted(carriers) == sorted(taken)  # each round on one trace
        ahead = 0
        for tr in traces:
            waits = [(t0, t1, args) for name, t0, t1, args in tr.spans
                     if name == "decode/wait"]
            assert len(waits) == 1, tr.spans
            t0, t1, args = waits[0]
            assert tr.start <= t0 <= t1 <= tr.end
            assert set(args) == {"round", "ahead", "inline"}
            ahead += args["ahead"]
            # Entry to the snapshot; nothing when the snapshot came first.
            assert t1 == max(t0, taken[args["round"]])
        assert pool.stats()["decode_steps_ahead"] - ahead_before == ahead
        # Three clients in step with the loop: most steps find their
        # token computed or under way.
        assert ahead >= n * steps // 2
        self._close(sigs, n, seed=32)

    def test_phases_and_handoff_cover_a_busy_batcher(self, model):
        """Six sessions against a tick slowed to 5 ms (the pre-tick
        faultpoint, which lies in `decode/prepare`), so that steps
        arrive while a tick runs. The batcher is busy while a round is
        in progress or a step waits in it (`decode/wait`); while it is
        empty there is no work, and that is nobody's hand-off. From the
        first round to the last, the leader threads' phases name all of
        its busy time but the slivers between two phases."""
        from min_tfs_client_tpu.robustness import faults

        config, _ = model
        n, steps = 6, 6
        sigs = _sigs(model, kv_block_size=2)
        faults.arm({"rules": [{"point": "backend.tick.pre",
                               "action": "delay", "delay_ms": 5}]})
        try:
            traces = self._step_sessions(sigs, config, n, steps, seed=33)
        finally:
            faults.disarm()
        self._close(sigs, n, seed=33)
        rounds = self._by_round(traces, self.PHASES + ("decode/handoff",))
        first, last = min(rounds), max(rounds)
        assert last - first >= steps - 1
        # From the first round's snapshot to the last round's delivery.
        begin = max(t1 for name, _, t1, _ in rounds[first]
                    if name == "decode/handoff")
        end = max(t1 for _, _, t1, _ in rounds[last])

        def seconds(intervals):
            total, at = 0.0, begin
            for t0, t1 in sorted(intervals):
                t0, t1 = max(t0, at), min(t1, end)
                if t1 > t0:
                    total, at = total + t1 - t0, t1
            return total

        named = [(t0, t1) for spans in rounds.values()
                 for _, t0, t1, _ in spans]
        waits = [(t0, t1) for tr in traces for name, t0, t1, _ in tr.spans
                 if name == "decode/wait"]
        assert len(waits) == n * steps
        busy = seconds(named + waits)
        # Every round after the first is busy for its 5 ms delay at least.
        assert busy >= 0.005 * (last - first)
        assert seconds(named) >= 0.95 * busy, (seconds(named), busy)

    def test_a_step_that_arrives_during_a_tick_waits_out_its_rest(self):
        """A session that opens, and asks for its token, while round 1
        runs is taken by round 2's snapshot, which cannot come before
        round 1 is back; round 1's riders are woken after round 2's
        launch."""
        import time

        from min_tfs_client_tpu.observability import tracing
        from min_tfs_client_tpu.servables.decode_sessions import TickBatcher

        running, finish = threading.Event(), threading.Event()
        ticks = []

        def tick(slots, of_round):
            ticks.append((of_round.ordinal, list(slots)))
            of_round.launched()
            if of_round.ordinal == 1:
                running.set()
                finish.wait(5)
            of_round.fetched = time.perf_counter()
            return {s: of_round.ordinal for s in slots}

        batcher = TickBatcher(tick)
        traces = {}

        def rider(slot):
            with tracing.request_trace("decode_step") as trace:
                batcher.step(slot)
            traces[slot] = trace

        batcher.admit(1, room=1)
        assert running.wait(5)
        first = threading.Thread(target=rider, args=(1,))
        first.start()
        batcher.admit(2, room=1)
        late = threading.Thread(target=rider, args=(2,))
        late.start()
        time.sleep(0.05)  # the late rider waits out round 1
        released = time.perf_counter()
        finish.set()
        first.join()
        late.join()
        assert ticks == [(1, [1]), (2, [2])]
        (wait,) = [s for s in traces[2].spans if s[0] == "decode/wait"]
        assert wait[3] == {"round": 2, "ahead": 0, "inline": 0}
        assert wait[2] - wait[1] >= 0.05 and wait[2] >= released
        # The first rider's token was under way when it asked.
        (ahead,) = [s for s in traces[1].spans if s[0] == "decode/wait"]
        assert ahead[3] == {"round": 1, "ahead": 1, "inline": 0}
        deliver = [s for s in traces[1].spans if s[0] == "decode/deliver"]
        (handoff,) = [s for s in traces[2].spans if s[0] == "decode/handoff"]
        # Round 2's hand-off starts where round 1's fetch ended (not at
        # the late rider's arrival) and ends at its snapshot; round 1's
        # delivery starts at the same instant and outlasts the snapshot,
        # for it contains round 2's launch.
        assert len(deliver) == 1
        assert handoff[1] == deliver[0][1] >= released
        assert handoff[2] == wait[2] <= deliver[0][2]
        cpu_us = handoff[3].pop("cpu_us")
        assert handoff[3] == {"round": 2, "riders": 1}
        # The loop thread's CPU since round 1's fetch, and no more of it
        # than the span is long.
        assert 0 <= cpu_us <= (handoff[2] - handoff[1]) * 1e6
        batcher.release(1)
        batcher.release(2)

    def test_kill_switch_records_none_of_them(self, model):
        from min_tfs_client_tpu.observability import tracing

        config, _ = model
        sigs = _sigs(model, kv_block_size=2)
        recorded = len(tracing.ring_snapshot())
        tracing.enable(False)
        try:
            traces = self._step_sessions(sigs, config, 2, 2, seed=34)
        finally:
            tracing.enable(True)
        self._close(sigs, 2, seed=34)
        assert traces == [None] * 4
        assert len(tracing.ring_snapshot()) == recorded
