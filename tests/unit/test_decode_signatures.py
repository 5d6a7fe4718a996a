"""The decode-session front (servables/decode_signatures.py) serving a
model that is NOT T5: a one-layer, one-head attention decoder over an
embedding, written here in a few lines of jax.numpy with its own dense
step and its own paged step contract. Every case runs on the three
backends — per-session store, dense slot pool, paged slot pool — and the
streams are held token for token to the toy's own full-sequence numpy
reference. That this file needs no edit under servables/ is the proof
that a new decode model is one `DecodeModel`."""

from __future__ import annotations

import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from min_tfs_client_tpu.servables.decode_sessions import Paging
from min_tfs_client_tpu.servables.decode_signatures import (
    DecodeModel,
    build_session_signatures,
)
from min_tfs_client_tpu.utils.status import ServingError

VOCAB, DIM, SEQ, MAXDEC = 23, 16, 6, 8
PAD, EOS, START = 0, 1, 2
INVALID_ARGUMENT, NOT_FOUND, FAILED_PRECONDITION = 3, 5, 9
K, V = ("cache", "k"), ("cache", "v")

BACKENDS = {
    "per_session": dict(continuous_batching=False),
    "dense_pool": dict(continuous_batching=True, paging=Paging()),
    "paged_pool": dict(continuous_batching=True,
                       paging=Paging(block_size=3, prefill_chunk=2)),
}


@pytest.fixture(autouse=True)
def _leak_witness(leak_witness):
    """Every pool slot and KV page a case takes is given back."""
    yield


# -- the toy model ------------------------------------------------------------


def _params():
    keys = jax.random.split(jax.random.PRNGKey(5), 6)
    return {"emb": jax.random.normal(keys[0], (VOCAB, DIM), jnp.float32),
            "pos": jax.random.normal(keys[1], (MAXDEC, DIM), jnp.float32),
            "out": jax.random.normal(keys[2], (DIM, VOCAB), jnp.float32),
            **{name: jax.random.normal(k, (DIM, DIM), jnp.float32) / 4.0
               for name, k in zip(("wq", "wk", "wv"), keys[3:])}}


def _context(params, input_ids):
    """What the prompt leaves behind: the mean embedding of its tokens."""
    real = (input_ids != PAD)[..., None]
    total = jnp.sum(jnp.where(real, params["emb"][input_ids], 0.0), axis=1)
    return total / jnp.maximum(jnp.sum(real, axis=1), 1)


def _next_token(params, x, attended, finished):
    logits = (x + attended) @ params["out"]
    token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    token = jnp.where(finished, PAD, token)
    return token, jnp.logical_or(finished, token == EOS)


def _prefill(params, input_ids, prefix_ids=None):
    b = input_ids.shape[0]
    state = {"ctx": _context(params, input_ids),
             "cache": {"k": jnp.zeros((b, 1, MAXDEC, DIM), jnp.float32),
                       "v": jnp.zeros((b, 1, MAXDEC, DIM), jnp.float32)},
             "token": jnp.full((b, 1), START, jnp.int32),
             "finished": jnp.zeros((b,), jnp.bool_),
             "step": jnp.int32(0)}
    if prefix_ids is not None:
        # One layer: a position's K and V need only its input token, so
        # the whole forced block fills the cache in one pass. Rows at or
        # past the prefix hold garbage that `step` masks and overwrites.
        plen = jnp.sum((prefix_ids[0] != PAD).astype(jnp.int32))
        block = jnp.concatenate([state["token"], prefix_ids[:, :-1]], axis=1)
        x = params["emb"][block] + state["ctx"][:, None] + params["pos"]
        state["cache"] = {"k": (x @ params["wk"])[:, None],
                          "v": (x @ params["wv"])[:, None]}
        state["step"] = plen
        state["token"] = jnp.take_along_axis(
            prefix_ids, jnp.full((b, 1), plen - 1, jnp.int32), axis=1)
    return state


def _step(params, state):
    x = (params["emb"][state["token"][:, 0]] + state["ctx"]
         + params["pos"][state["step"]])                           # (B, D)
    at = (0, 0, state["step"], 0)
    k = jax.lax.dynamic_update_slice(
        state["cache"]["k"], (x @ params["wk"])[:, None, None], at)
    v = jax.lax.dynamic_update_slice(
        state["cache"]["v"], (x @ params["wv"])[:, None, None], at)
    scores = jnp.einsum("bd,btd->bt", x @ params["wq"], k[:, 0])
    seen = jnp.arange(MAXDEC)[None, :] <= state["step"]
    weights = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
    attended = jnp.einsum("bt,btd->bd", weights, v[:, 0])
    token, finished = _next_token(params, x, attended, state["finished"])
    return {"ctx": state["ctx"], "cache": {"k": k, "v": v},
            "token": token[:, None], "finished": finished,
            "step": state["step"] + 1}, token


class _ToyPagedStep:
    """The toy's paging-aware step contract: leaves arrive slot-batched
    (slots, 1, ...), the caches live behind `kv`."""

    def decode(self, params, tree, kv):
        x = (params["emb"][tree["token"][:, 0, 0]] + tree["ctx"][:, 0]
             + params["pos"][kv.lengths])
        kv = kv.append({K: (x @ params["wk"])[:, None],
                        V: (x @ params["wv"])[:, None]})
        attended = kv.attend((x @ params["wq"])[:, None, None], K, V,
                             scale=1.0)[:, 0, 0]
        token, finished = _next_token(params, x, attended,
                                      tree["finished"][:, 0])
        new_tree = dict(tree, token=token[:, None, None],
                        finished=finished[:, None], step=tree["step"] + 1)
        return new_tree, kv, {"token": token[:, None],
                              "finished": finished[:, None]}

    def prefill_chunk(self, params, tree, kv, tokens, chunk_lens,
                      next_tokens):
        at = kv.lengths[:, None] + jnp.arange(tokens.shape[1])[None, :]
        x = (params["emb"][tokens] + tree["ctx"][:, 0][:, None]
             + params["pos"][jnp.minimum(at, MAXDEC - 1)])
        kv = kv.append({K: x @ params["wk"], V: x @ params["wv"]},
                       row_valid=chunk_lens)
        return dict(tree, token=next_tokens[:, :, None],
                    step=tree["step"] + chunk_lens), kv


TOY = DecodeModel(
    name="toy", prefill=_prefill, step=_step, paged_step=_ToyPagedStep(),
    paged_axis_fn=lambda path: 2 if path[0] == "cache" else None,
    decoder_start_id=START, pad_id=PAD)


def _reference(params, ids, steps, forced=()):
    """The stream by full recomputation: at every step attention over
    the whole sequence so far, no cache, numpy only."""
    p = {k: np.asarray(v) for k, v in params.items()}
    real = ids[0] != PAD
    ctx = p["emb"][ids[0][real]].sum(0) / max(real.sum(), 1)
    inputs, out, finished = [START], [], False
    for i in range(len(forced) + steps):
        x = p["emb"][inputs] + ctx + p["pos"][:len(inputs)]
        scores = (x[-1] @ p["wq"]) @ (x @ p["wk"]).T
        weights = np.exp(scores - scores.max())
        attended = (weights / weights.sum()) @ (x @ p["wv"])
        token = int(np.argmax((x[-1] + attended) @ p["out"]))
        if i < len(forced):
            token = int(forced[i])
        else:
            token = PAD if finished else token
            finished = finished or token == EOS
            out.append(token)
        inputs.append(token)
    return out


# -- harness ------------------------------------------------------------------


@pytest.fixture(scope="module")
def params():
    return _params()


def _sigs(params, backend, **kw):
    kw = {"max_sessions": 4, **BACKENDS[backend], **kw}
    return build_session_signatures(params, TOY, seq_len=SEQ,
                                    max_decode_len=MAXDEC, **kw)


def _sid(name):
    return np.asarray(name.encode(), object)


def _prompt(seed):
    ids = np.random.default_rng(seed).integers(
        3, VOCAB, (1, SEQ)).astype(np.int32)
    ids[:, SEQ - 2:] = PAD
    return ids


def _steps(sigs, sid, n, ordinals=None):
    out = []
    for i in range(n):
        inputs = {"session_id": sid}
        if ordinals is not None:
            inputs["step_ordinal"] = np.asarray(ordinals + i, np.int64)
        out.append(sigs["decode_step"].run(inputs))
    return out


def _code(fn, *args):
    with pytest.raises(ServingError) as err:
        fn(*args)
    return err.value.code


backends = pytest.mark.parametrize("backend", list(BACKENDS))


# -- the cases ----------------------------------------------------------------


@backends
def test_round_trip_is_the_references_stream(params, backend):
    sigs = _sigs(params, backend)
    streams = {}
    for seed in (1, 2):
        ids, sid = _prompt(seed), _sid(f"rt-{seed}")
        opened = sigs["decode_init"].run({"session_id": sid,
                                          "input_ids": ids})
        assert int(opened["batch"]) == 1
        outs = _steps(sigs, sid, MAXDEC - 2)
        assert [int(o["step"]) for o in outs] == list(range(1, MAXDEC - 1))
        assert all(o["token"].dtype == np.int32
                   and o["finished"].dtype == np.int32
                   and o["token"].shape == (1,) for o in outs)
        streams[seed] = [int(o["token"][0]) for o in outs]
        assert int(sigs["decode_close"].run({"session_id": sid})["closed"])
        assert not int(sigs["decode_close"].run(
            {"session_id": sid})["closed"])
        assert streams[seed] == _reference(params, ids, MAXDEC - 2)
    assert streams[1] != streams[2]  # the prompt reaches the stream


@backends
def test_forced_prefix_resumes_the_references_stream(params, backend):
    sigs = _sigs(params, backend)
    ids, sid = _prompt(5), _sid("fp")
    forced = [7, 11, 5]     # three tokens over two-token chunks: a short last chunk
    prefix = np.full((1, MAXDEC), PAD, np.int32)
    prefix[0, :len(forced)] = forced
    opened = sigs["decode_init_prefix"].run(
        {"session_id": sid, "input_ids": ids, "prefix_ids": prefix})
    assert int(opened["prefix_len"]) == len(forced)
    outs = _steps(sigs, sid, MAXDEC - len(forced) - 1)
    assert int(outs[0]["step"]) == len(forced) + 1
    assert [int(o["token"][0]) for o in outs] == _reference(
        params, ids, len(outs), forced)
    sigs["decode_close"].run({"session_id": sid})
    full = np.full((1, MAXDEC), 7, np.int32)
    assert _code(sigs["decode_init_prefix"].run,
                 {"session_id": sid, "input_ids": ids,
                  "prefix_ids": full}) == INVALID_ARGUMENT


@backends
def test_step_ordinal_replays_and_a_failed_attempt_is_abandoned(
        params, backend):
    sigs = _sigs(params, backend)
    ids, sid = _prompt(4), _sid("ord")
    step = {"session_id": sid, "step_ordinal": np.asarray(1, np.int64)}
    # No session yet: the attempt fails, and must not leave ordinal 1
    # marked in flight or answered.
    assert _code(sigs["decode_step"].run, step) == NOT_FOUND
    sigs["decode_init"].run({"session_id": sid, "input_ids": ids})
    first = sigs["decode_step"].run(step)
    again = sigs["decode_step"].run(step)         # a resend: replayed
    assert int(first["step"]) == int(again["step"]) == 1
    assert int(first["token"][0]) == int(again["token"][0])
    assert _code(sigs["decode_step"].run,
                 {"session_id": sid,
                  "step_ordinal": np.asarray(3, np.int64)}
                 ) == FAILED_PRECONDITION          # a gap
    rest = _steps(sigs, sid, 3, ordinals=2)
    assert [int(first["token"][0])] + [int(o["token"][0]) for o in rest] \
        == _reference(params, ids, 4)              # nothing advanced twice
    sigs["decode_close"].run({"session_id": sid})
    assert _code(sigs["decode_step"].run, step) == NOT_FOUND


@backends
def test_exhaustion_at_max_decode_len_frees_the_session(params, backend):
    sigs = _sigs(params, backend, max_sessions=2)
    store = sigs["decode_step"]._decode_store
    ids, sid = _prompt(5), _sid("ex")
    sigs["decode_init"].run({"session_id": sid, "input_ids": ids})
    outs = _steps(sigs, sid, MAXDEC, ordinals=1)
    assert int(outs[-1]["step"]) == MAXDEC
    assert [int(o["token"][0]) for o in outs] == _reference(
        params, ids, MAXDEC)
    assert sid.item() not in store and len(store) == 0
    last = {"session_id": sid,
            "step_ordinal": np.asarray(MAXDEC, np.int64)}
    assert int(sigs["decode_step"].run(last)["step"]) == MAXDEC  # replayed
    assert _code(sigs["decode_step"].run, {"session_id": sid}) == NOT_FOUND
    # Both places are free again: two more sessions fit in a pool of two.
    for name in ("ex-b", "ex-c"):
        sigs["decode_init"].run({"session_id": _sid(name),
                                 "input_ids": ids})
    for name in ("ex-b", "ex-c"):
        assert int(sigs["decode_close"].run(
            {"session_id": _sid(name)})["closed"])


@backends
def test_bad_session_id_and_batch_are_typed(params, backend):
    sigs = _sigs(params, backend)
    ids = _prompt(6)
    two_ids = np.asarray([b"a", b"b"], object)
    for name in ("decode_init", "decode_step", "decode_close"):
        assert _code(sigs[name].run, {"session_id": two_ids,
                                      "input_ids": ids}) == INVALID_ARGUMENT
    pair = np.concatenate([ids, _prompt(7)])
    prefix = np.full((1, MAXDEC), PAD, np.int32)
    prefix[0, 0] = 9
    assert _code(sigs["decode_init_prefix"].run,
                 {"session_id": _sid("b2"), "input_ids": pair,
                  "prefix_ids": prefix}) == INVALID_ARGUMENT
    if backend == "per_session":
        # The store parks whole batches: a step answers a row a sequence.
        opened = sigs["decode_init"].run({"session_id": _sid("b2"),
                                          "input_ids": pair})
        assert int(opened["batch"]) == 2
        out = sigs["decode_step"].run({"session_id": _sid("b2")})
        assert [int(t) for t in out["token"]] == [
            _reference(params, pair[i:i + 1], 1)[0] for i in range(2)]
        sigs["decode_close"].run({"session_id": _sid("b2")})
    else:
        assert _code(sigs["decode_init"].run,
                     {"session_id": _sid("b2"),
                      "input_ids": pair}) == INVALID_ARGUMENT
    assert len(sigs["decode_step"]._decode_store) == 0


@backends
def test_warm_up_leaves_no_live_session(params, backend):
    sigs = _sigs(params, backend, max_sessions=1)
    store = sigs["decode_init"]._decode_store
    sigs["decode_init"].warmup_fn()
    assert len(store) == 0
    # The one place is free, and the warmed programs serve the stream.
    ids, sid = _prompt(8), _sid("wu")
    sigs["decode_init"].run({"session_id": sid, "input_ids": ids})
    assert [int(o["token"][0]) for o in _steps(sigs, sid, 3)] \
        == _reference(params, ids, 3)
    sigs["decode_close"].run({"session_id": sid})


def test_labels_and_loader_handles_follow_the_models_name(params):
    labels = {}
    for backend in BACKENDS:
        sigs = _sigs(params, backend)
        stores = {id(s._decode_store) for s in sigs.values()}
        assert len(stores) == 1 and set(sigs) == {
            "decode_init", "decode_init_prefix", "decode_step",
            "decode_close"}
        pool = getattr(sigs["decode_init"], "_kv_pool", None)
        assert (pool is not None) == (backend == "paged_pool")
        labels[backend] = (sigs["decode_init"]._decode_store._metric_label,
                           pool.metric_label if pool else None)
    assert labels == {"per_session": ("toy", None),
                      "dense_pool": ("toy-pooled", None),
                      "paged_pool": ("toy-pooled", "toy-paged")}


def test_nothing_under_servables_knows_a_model():
    """The arrows: models/* -> decode_signatures -> decode_sessions. The
    front serves whatever DecodeModel it is handed."""
    servables = pathlib.Path(
        __import__("min_tfs_client_tpu.servables").__file__
    ).parent / "servables"
    for source in servables.glob("*.py"):
        text = source.read_text()
        assert "models.t5" not in text and "models import t5" not in text, \
            source.name
