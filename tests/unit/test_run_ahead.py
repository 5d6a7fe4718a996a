"""Run-ahead of depth one leaves every served stream what it was.

The pooled backend computes a session's next token before it is asked
for (decode_sessions.TickBatcher). The bar: N interleaved pooled
sessions emit exactly the streams of the per-session backend, which
computes a token only when it is asked for; and what ran ahead and was
never collected costs nothing that stays: every page comes back, and
`decode_tokens_dropped` counts exactly the sessions closed early.
"""

from __future__ import annotations

import functools

import jax
import numpy as np
import pytest

from min_tfs_client_tpu.models import t5
from tests.fixtures import tick_loop_threads, until

SEQ, MAXDEC, N = 12, 8, 4


@pytest.fixture(autouse=True)
def _witnesses(schedule_witness, leak_witness):
    """The loop's lock order and guarded mutations are verified live, and
    every page and slot taken in a case is back by its end."""
    yield


@functools.lru_cache(maxsize=None)
def _model():
    config = t5.T5Config.tiny()
    return config, t5.init_params(jax.random.PRNGKey(0), config)


@functools.lru_cache(maxsize=None)
def _sigs(pool: str, sampling: bool):
    """One set of signatures per backend and sampling mode, shared by the
    cases (each set compiles its own programs)."""
    config, params = _model()
    kw = {"dense": {"continuous_batching": True, "kv_block_size": 0},
          "paged": {"continuous_batching": True, "kv_block_size": 2,
                    "kv_prefill_chunk": 2},
          "per-session": {}}[pool]
    return t5.build_session_signatures(
        params, config, seq_len=SEQ, max_decode_len=MAXDEC, max_sessions=8,
        sampling=sampling, **kw)


def _sid(name: str):
    return np.asarray(name.encode(), object)


def _loop_idle():
    return not tick_loop_threads()


def _sessions(config, sampling: bool, prefix_len: int, seed: int):
    """N sessions' init inputs (without the id)."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(N):
        ids = rng.integers(2, config.vocab_size, (1, SEQ)).astype(np.int32)
        ids[:, SEQ // 2 + i:] = config.pad_id
        inputs = {"input_ids": ids}
        if sampling:
            inputs["temperature"] = np.asarray([0.7], np.float32)
            inputs["seed"] = np.asarray([100 + i], np.int32)
        if prefix_len:
            pre = np.full((1, MAXDEC), config.pad_id, np.int32)
            pre[0, :prefix_len] = rng.integers(2, config.vocab_size,
                                               prefix_len)
            inputs["prefix_ids"] = pre
        out.append(inputs)
    return out


def _open(sigs, sid, inputs):
    name = "decode_init_prefix" if "prefix_ids" in inputs else "decode_init"
    sigs[name].run({"session_id": sid, **inputs})


def _step(sigs, sid, ordinal=None):
    inputs = {"session_id": sid}
    if ordinal is not None:
        inputs["step_ordinal"] = np.asarray(ordinal, np.int64)
    return sigs["decode_step"].run(inputs)


def _row(out):
    return int(out["token"][0]), int(out["finished"][0]), int(out["step"])


@pytest.mark.parametrize("prefix_len", [0, 3], ids=["no-prefix", "prefix"])
@pytest.mark.parametrize("sampling", [False, True],
                         ids=["greedy", "sampled"])
@pytest.mark.parametrize("pool", ["dense", "paged"])
def test_interleaved_pooled_sessions_emit_the_per_session_streams(
        pool, sampling, prefix_len):
    config, _ = _model()
    tag = f"{pool}-{int(sampling)}-{prefix_len}"
    opened = _sessions(config, sampling, prefix_len, seed=41 + prefix_len)
    budget = MAXDEC - prefix_len

    # What each session says when nothing runs ahead of it.
    alone = _sigs("per-session", sampling)
    want = []
    for i, inputs in enumerate(opened):
        sid = _sid(f"ref-{tag}-{i}")
        _open(alone, sid, inputs)
        want.append([_row(_step(alone, sid)) for _ in range(budget)])

    sigs = _sigs(pool, sampling)
    counters = sigs["decode_step"]._loop_counters
    dropped_before = counters()["decode_tokens_dropped"]
    sids = [_sid(f"run-{tag}-{i}") for i in range(N)]
    for sid, inputs in zip(sids, opened):
        _open(sigs, sid, inputs)
    # Session i is closed after `stop[i]` steps; the last runs its cache
    # full and is closed by that. Interleaved unevenly: in each sweep
    # session i steps i + 1 times. Session 2 sends every ordinal twice.
    stop = [1, 2, budget - 1, budget]
    got = [[] for _ in range(N)]
    while any(len(got[i]) < stop[i] for i in range(N)):
        for i in range(N):
            for _ in range(i + 1):
                if len(got[i]) == stop[i]:
                    break
                ordinal = len(got[i]) + 1 if i == 2 else None
                out = _step(sigs, sids[i], ordinal)
                got[i].append(_row(out))
                if i == 2:
                    again = _step(sigs, sids[i], ordinal)
                    assert sorted(again) == sorted(out)
                    for key in out:
                        np.testing.assert_array_equal(again[key], out[key])
    for i in range(N):
        assert got[i] == want[i][:stop[i]], (i, got[i], want[i])
    # The last session ran to max_decode_len: gone, nothing of it left.
    assert sids[N - 1].item() not in sigs["decode_init"]._decode_store
    # The others are one token ahead of their clients (once the loop has
    # nothing left to do): closing them drops exactly those tokens.
    until(_loop_idle)
    assert counters()["decode_tokens_dropped"] == dropped_before
    for sid in sids[:N - 1]:
        assert int(sigs["decode_close"].run({"session_id": sid})["closed"])
    assert counters()["decode_tokens_dropped"] == dropped_before + N - 1
    assert len(sigs["decode_init"]._decode_store) == 0
    if pool == "paged":
        stats = sigs["decode_init"]._kv_pool.stats()
        assert stats["blocks_used"] == 0 and stats["sessions"] == 0
        assert stats["decode_tokens_dropped"] \
            == counters()["decode_tokens_dropped"]
    # Every slot is free again: the pool takes its full count of sessions.
    fresh = [_sid(f"fresh-{tag}-{i}") for i in range(8)]
    for sid in fresh:
        _open(sigs, sid, opened[0])
    for sid in fresh:
        sigs["decode_close"].run({"session_id": sid})
    until(_loop_idle)
