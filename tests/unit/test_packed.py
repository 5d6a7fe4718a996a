"""The seam of a decoder served as whole generations (models/packed.py,
servables/decode_signatures.generation_signature): the packing's laws, a
third decoder written here and served through the seam with a count table
of its own, and what no model module may do to another."""

import ast
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import min_tfs_client_tpu.models
from min_tfs_client_tpu.models import export, packed
from min_tfs_client_tpu.models import layers as nn
from min_tfs_client_tpu.observability import runtime, tracing
from min_tfs_client_tpu.ops.attention import attention
from min_tfs_client_tpu.servables.decode_signatures import (
    CountTable,
    generation_signature,
    note_generation,
)

PAD, EOS = 0, 1

# -- the packing's laws -------------------------------------------------------

B, S, BLOCK = 4, 8, 8       # a buffer of 32 rows, four blocks
PATTERNS = {
    "all_full": (8, 8, 8, 8),
    "all_empty": (0, 0, 0, 0),
    "one_token": (0, 1, 0, 0),
    "an_empty_row_between_full_ones": (8, 0, 8, 3),
    "a_total_on_a_blocks_edge": (5, 3, 6, 2),
    "a_total_one_past_it": (5, 3, 6, 3),
}


@pytest.fixture(params=sorted(PATTERNS))
def chunk(request):
    """(ids (B, S), lengths, their Packing)."""
    lengths = np.asarray(PATTERNS[request.param])
    rng = np.random.default_rng(7)
    ids = rng.integers(2, 50, (B, S)).astype(np.int32)
    ids[np.arange(S)[None, :] >= lengths[:, None]] = PAD
    return ids, lengths, packed.pack(jnp.asarray(ids), PAD, BLOCK)


def test_the_real_tokens_come_first_in_example_then_position_order(chunk):
    ids, lengths, p = chunk
    total = int(lengths.sum())
    real = np.concatenate([ids[e, :n] for e, n in enumerate(lengths)])
    tokens = np.asarray(p.tokens)
    assert (p.b, p.s, p.block, p.t) == (B, S, BLOCK, B * S)
    assert int(p.total) == total
    assert tokens[:total].tolist() == real.tolist()
    assert (tokens[total:] == PAD).all()
    want = np.concatenate([np.arange(n) for n in lengths] or [[]])
    assert np.asarray(p.position)[:total].tolist() == want.tolist()
    assert np.asarray(p.lengths).tolist() == lengths.tolist()
    assert (np.asarray(p.ends) - np.asarray(p.starts)).tolist() == \
        lengths.tolist()


def test_cut_and_put_over_the_blocks_cover_the_real_rows_and_no_block_more(
        chunk):
    _, lengths, p = chunk
    total = int(lengths.sum())
    rows = jnp.arange(p.t, dtype=jnp.float32)
    marked = np.asarray(p.over_blocks(
        lambda lo, seen: p.put(seen, p.cut(rows, lo) + 1.0, lo),
        jnp.zeros((p.t,), jnp.float32)))
    covered = -(-total // BLOCK) * BLOCK
    assert int(p.blocks) == covered // BLOCK
    # each covered row was cut from its own place and put back there
    assert marked[:covered].tolist() == (np.arange(covered) + 1.0).tolist()
    assert (marked[covered:] == 0).all()
    assert covered - total < BLOCK


def test_grid_then_back_is_the_identity_on_real_rows_and_zero_past_them(
        chunk):
    _, lengths, p = chunk
    total = int(lengths.sum())
    x = jnp.asarray(np.random.default_rng(3).standard_normal((p.t, 5)),
                    jnp.float32)
    on_the_grid = p.grid(x)
    assert on_the_grid.shape == (B, S, 5)
    for e, n in enumerate(lengths):     # an example's rows, from its first
        start = int(p.starts[e])
        np.testing.assert_array_equal(on_the_grid[e, :n], x[start:start + n])
    flat = on_the_grid.reshape(B * S, -1)
    round_trip = np.asarray(p.over_blocks(
        lambda lo, out: p.put(out, p.back(flat, lo), lo),
        jnp.full((p.t, 5), 9.0)))
    covered = int(p.blocks) * BLOCK
    np.testing.assert_array_equal(round_trip[:total], np.asarray(x)[:total])
    assert (round_trip[total:covered] == 0).all()
    assert (round_trip[covered:] == 9.0).all()     # no block ran there


def test_held_by_example_sums_to_the_whole(chunk):
    _, lengths, p = chunk
    total = int(lengths.sum())
    a_row = np.random.default_rng(5).integers(0, 7, (p.t,)).astype(np.int32)
    a_row[total:] = 0            # a row past the last is routed nowhere
    by_example = np.asarray(p.held_by_example(
        jnp.asarray(a_row), jnp.zeros((B,), jnp.int32)))
    ends = np.cumsum(lengths)
    assert by_example.tolist() == [
        int(a_row[end - n:end].sum()) for n, end in zip(lengths, ends)]
    assert by_example.sum() == a_row.sum()


def test_last_rows_are_each_examples_last_real_row_or_zeros(chunk):
    _, lengths, p = chunk
    h = jnp.asarray(np.random.default_rng(4).standard_normal((p.t, 3)),
                    jnp.float32)
    last = np.asarray(p.last_rows(h))
    ends = np.cumsum(lengths)
    for e, n in enumerate(lengths):
        want = np.asarray(h)[ends[e] - 1] if n else np.zeros(3)
        np.testing.assert_array_equal(last[e], want)


# -- a third decoder, written here --------------------------------------------

VOCAB, D, HEADS, HD, LAYERS = 40, 16, 2, 8, 2
SEQ, STEPS = 8, 5
LENGTHS = (8, 0, 3, 1, 5, 8)
LABEL = "toy:1:serving_default"
TOY = CountTable(
    output="toy_counts", span="generate/toy", section="toy",
    columns=("prompt_tokens", "cache_rows", "twice_steps", "seven",
             "rows_fed"),
    derived={"twice_steps": ("steps", 2), "seven": (None, 7)},
    batch=("rows_fed",))


def toy_params():
    keys = iter(jax.random.split(jax.random.PRNGKey(11), 3 * LAYERS + 2))

    def normal(shape, std):
        return jax.random.normal(next(keys), shape, jnp.float32) * std

    return {"embed": normal((VOCAB, D), 1.0),
            "layers": [{"qkv": normal((D, 3 * HEADS * HD), D ** -0.5),
                        "out": normal((HEADS * HD, D), 0.5 * D ** -0.5),
                        "norm": nn.rms_norm_init(D)}
                       for _ in range(LAYERS)],
            "head": normal((D, VOCAB), D ** -0.5)}


def _qkv(layer, x):
    """x (..., D) -> q, k, v (..., HEADS, HD) each."""
    fused = nn.mm(nn.rms_norm(layer["norm"], x), layer["qkv"])
    return tuple(part.reshape(*x.shape[:-1], HEADS, HD)
                 for part in jnp.split(fused, 3, axis=-1))


def toy_chunk(params, ids, max_decode_len, row_block):
    """Two attention layers over the packed rows."""
    p = packed.pack(ids, PAD, row_block)
    h = params["embed"][p.tokens]
    caches = []
    for layer in params["layers"]:
        def project(lo, qkv, h=h, layer=layer):
            parts = _qkv(layer, p.cut(h, lo))
            return tuple(p.put(all_, part.reshape(p.block, -1), lo)
                         for all_, part in zip(qkv, parts))

        qkv = p.over_blocks(project, tuple(
            jnp.zeros((p.t, HEADS * HD), jnp.float32) for _ in range(3)))
        q, k, v = (p.grid(x).reshape(p.b, p.s, HEADS, HD)
                   .transpose(0, 2, 1, 3) for x in qkv)
        out = attention(q, k, v, causal=True, lengths=p.lengths,
                        causal_offset=0, queries_ragged=True)
        out = out.transpose(0, 2, 1, 3).reshape(p.b * p.s, -1)

        def mix(lo, h, layer=layer, out=out):
            return p.put(h, p.cut(h, lo) + nn.mm(p.back(out, lo),
                                                 layer["out"]), lo)

        h = p.over_blocks(mix, h)
        room = ((0, 0), (0, 0), (0, max_decode_len), (0, 0))
        caches.append({"k": jnp.pad(k, room), "v": jnp.pad(v, room)})
    no_experts = jnp.zeros((p.b,), jnp.int32), jnp.zeros((0, 1), jnp.int32)
    return (caches, nn.mm(p.last_rows(h), params["head"]), *no_experts,
            p.blocks * p.block, {"cache_rows": LAYERS * p.lengths})


def toy_prefill(params, ids, *, max_decode_len=STEPS, rows=2, row_block=8):
    return packed.prefill_by_chunks(
        lambda chunk: toy_chunk(params, chunk, max_decode_len, row_block),
        ids, rows=rows, pad_id=PAD, extra_counts=("rows_fed",))


def toy_step(params, state):
    token, finished, position, owned = packed.choose(state, PAD, EOS)
    each = jnp.arange(token.shape[0])
    h = params["embed"][token]
    caches = []
    for layer, cache in zip(params["layers"], state["caches"]):
        q, k, v = _qkv(layer, h)
        cache = {"k": cache["k"].at[each, :, position].set(k),
                 "v": cache["v"].at[each, :, position].set(v)}
        caches.append(cache)
        seen = jnp.arange(cache["k"].shape[2])[None, :] <= position[:, None]
        h = h + nn.mm(nn.attend_cache(q, cache, seen, None), layer["out"])
    return packed.advance(
        state, caches, nn.mm(h, params["head"]), token, finished,
        cache_rows=LAYERS * owned.astype(jnp.int32),
        rows_fed=jnp.sum(owned, dtype=jnp.int32)), token


def unpacked_generation(params, prompt, steps):
    """The same decoder with no packing, no cache and no batch: every
    token from a whole forward pass over all the tokens before it."""
    tokens, out, finished = [int(t) for t in prompt], [], False
    for _ in range(steps):
        h = params["embed"][jnp.asarray(tokens)]
        for layer in params["layers"]:
            q, k, v = _qkv(layer, h)
            scores = jnp.einsum("qhd,khd->hqk", q, k) * HD ** -0.5
            causal = jnp.tril(jnp.ones((len(tokens),) * 2, bool))
            weights = jax.nn.softmax(jnp.where(causal, scores, -1e30), -1)
            mixed = jnp.einsum("hqk,khd->qhd", weights, v)
            h = h + nn.mm(mixed.reshape(len(tokens), -1), layer["out"])
        token = PAD if finished else int(jnp.argmax(h[-1] @ params["head"]))
        finished = finished or token == EOS
        out.append(token)
        tokens.append(token)
    return out


@pytest.fixture(scope="module")
def toy():
    rng = np.random.default_rng(2)
    ids = rng.integers(2, VOCAB, (len(LENGTHS), SEQ)).astype(np.int32)
    ids[np.arange(SEQ)[None, :] >= np.asarray(LENGTHS)[:, None]] = PAD
    return {"params": toy_params(), "ids": ids}


@pytest.fixture
def own_counters(monkeypatch):
    """The process's counters are every test's of this worker: this
    test's sections live and die with it."""
    monkeypatch.setattr(runtime, "_generation_counts", {})


def toy_signature(params):
    signature = generation_signature(
        toy_prefill, toy_step, params, seq_len=SEQ, max_decode_len=STEPS,
        vocab_size=VOCAB, pad_id=PAD, batch_buckets=(8,), tables=(TOY,))
    signature.telemetry_label = LABEL
    return signature


def test_a_third_decoder_is_served_through_the_seam_token_for_token(toy):
    """`pack`, `prefill_by_chunks`, `choose` / `advance` and
    `generation_signature` carry a decoder that is neither MiMo nor
    Granite: its tokens are those of its own unpacked forward pass."""
    out = toy_signature(toy["params"]).run({"input_ids": toy["ids"]})
    assert out["output_ids"].shape == (len(LENGTHS), STEPS)
    assert out["first_logits"].shape == (len(LENGTHS), VOCAB)
    for row, n in enumerate(LENGTHS):
        if n:
            assert out["output_ids"][row].tolist() == unpacked_generation(
                toy["params"], toy["ids"][row, :n], STEPS), row
    lengths = (out["output_ids"] != PAD).sum(axis=-1)
    assert out["output_lengths"].tolist() == lengths.tolist()


def test_its_prefill_state_is_the_seams(toy):
    state = toy_prefill(toy["params"], jnp.asarray(toy["ids"]))
    assert set(state) == {"caches", "length", "logits", "token",
                          "finished", "counts"}
    assert state["length"].tolist() == list(LENGTHS)
    assert state["token"].shape == (len(LENGTHS), 1)
    assert not state["finished"].any()
    counts = state["counts"]
    assert counts["prompt_tokens"].tolist() == list(LENGTHS)
    assert counts["cache_rows"].tolist() == [LAYERS * n for n in LENGTHS]
    assert counts["rows_fed"].shape == () and int(counts["rows_fed"]) == 0
    # three chunks of two examples: 8, 4 and 13 real tokens in blocks of 8
    assert int(counts["prefill_rows"]) == 8 + 8 + 16
    assert (np.asarray(state["logits"])[1] == 0).all()   # a prompt of none


def test_its_counts_reach_its_span_and_its_section(toy, own_counters):
    """A count table of its own, with no edit to runtime.py, mimo.py or
    granite_hybrid.py: the output, the span on the request's trace, the
    section of /monitoring/runtime."""
    signature = toy_signature(toy["params"])
    assert signature.outputs["toy_counts"].shape == (None, 5)
    with tracing.request_trace("predict", model="toy",
                               signature="serving_default") as trace:
        out = signature.run({"input_ids": toy["ids"]})
        signature.on_answer(signature, out)      # what the handlers do
    real = sum(n > 0 for n in LENGTHS)
    rows = out["toy_counts"]
    assert rows[:, 0].tolist() == list(LENGTHS)
    assert rows[:, 1].tolist() == [LAYERS * (n + STEPS * (n > 0))
                                   for n in LENGTHS]
    assert set(rows[:, 2].tolist()) == {2 * STEPS}
    assert set(rows[:, 3].tolist()) == {7}
    assert set(rows[:, 4].tolist()) == {real * STEPS}    # the batch's
    spans = {name: args for name, _, _, args in trace.spans}
    want = {"prompt_tokens": sum(LENGTHS),
            "cache_rows": int(rows[:, 1].sum()),
            "twice_steps": 2 * STEPS * len(LENGTHS),
            "seven": 7 * len(LENGTHS), "rows_fed": real * STEPS}
    assert spans["generate/toy"] == want
    assert runtime.generation_totals("toy") == {
        LABEL: {"requests": 1, **want}}
    payload = runtime.snapshot()
    assert payload["toy"] == {LABEL: {"requests": 1, **want}}
    assert payload["route"] == {} and payload["state"] == {}


# -- the count table and the counter store ------------------------------------


class _Labelled:
    telemetry_label = "m:1:sig"


def test_a_request_with_no_such_output_notes_nothing(own_counters):
    with tracing.request_trace("predict") as trace:
        TOY.note(_Labelled(), {"output_ids": np.zeros((1, 2), np.int32)})
    assert trace.spans == [] and runtime.generation_totals("toy") == {}


@pytest.mark.parametrize("held, total, rows, want", [
    (30, 120, 512, 128),     # a quarter of the batch's held pairs
    (0, 120, 512, 0),        # a request of no held pair takes no share
    (120, 120, 512, 512),    # alone in its batch
    (0, 0, 512, 0),          # a batch that held nothing: no division by 0
])
def test_a_shared_column_enters_the_section_by_the_requests_share(
        own_counters, held, total, rows, want):
    table = packed.route_table(pairs_per_token=6)
    row = dict.fromkeys(packed.ROUTE_COLUMNS, 0)
    row.update(prompt_tokens=9, held_prefill=held, load_total=total,
               max_load=40, prefill_rows=rows, hit_decode=rows // 2)
    two_rows = np.asarray([[row[c] for c in packed.ROUTE_COLUMNS]] * 2)
    two_rows[1, :5] = 0      # the request's second row held nothing
    with tracing.request_trace("predict") as trace:
        table.note(_Labelled(), {"route_counts": two_rows})
    (name, _, _, args), = trace.spans
    assert name == "generate/route"
    # the batch's figures as they are on the span: the max, not the sum
    assert (args["load_total"], args["max_load"],
            args["prefill_rows"]) == (total, 40, rows)
    counted = runtime.generation_totals("route")["m:1:sig"]
    assert counted["prefill_rows"] == want
    assert counted["hit_decode"] == round(rows // 2 * held / max(total, 1))
    assert "max_load" not in counted and "load_total" not in counted
    assert counted["requests"] == 1 and counted["prompt_tokens"] == 9


def test_the_rows_of_a_table_follow_its_columns():
    table = packed.route_table(pairs_per_token=6)
    counts = {"prompt_tokens": jnp.asarray([3, 0]),
              "held_prefill": jnp.asarray([4, 0]),
              "held_decode": jnp.asarray([5, 0]),
              "steps": jnp.asarray([2, 2]), "max_load": jnp.asarray(7),
              "load_total": jnp.asarray(8), "prefill_rows": jnp.asarray(16),
              "hit_decode": jnp.asarray(9)}
    rows = table.rows(counts)
    assert rows.dtype == jnp.int32
    assert rows.tolist() == [[3, 18, 4, 12, 5, 7, 8, 16, 9],
                             [0, 0, 0, 12, 0, 7, 8, 16, 9]]


def test_spans_of_one_request_count_as_one_request(own_counters):
    """T5's two spans (`generate/cross`, `generate/self`) through the
    hook's second half: both on the trace, one request in the section."""
    with tracing.request_trace("predict") as trace:
        for blocks, rows in ((3, 40), (1, 2)):
            note_generation(_Labelled(), "route",
                            {"generate/cross": {"blocks_read": blocks},
                             "generate/self": {"rows_read": rows}},
                            {"blocks_read": blocks, "rows_read": rows})
    assert [name for name, *_ in trace.spans] == [
        "generate/cross", "generate/self"] * 2
    assert runtime.generation_totals("route") == {
        "m:1:sig": {"requests": 2, "blocks_read": 4, "rows_read": 42}}
    assert runtime.generation_totals("state") == {}


def test_a_signature_without_a_label_counts_as_unlabeled(own_counters):
    class Bare:
        telemetry_label = ""

    note_generation(Bare(), "state", {"generate/state": {"steps": 2}},
                    {"steps": 2})
    assert runtime.generation_totals("state") == {
        "unlabeled": {"requests": 1, "steps": 2}}


# -- what no model module may do to another -----------------------------------

MODELS = pathlib.Path(min_tfs_client_tpu.models.__file__).parent
MODULES = sorted(path for path in MODELS.glob("*.py")
                 if path.name != "__init__.py")


def _private(name: str) -> bool:
    return name.startswith("_") and not name.startswith("__")


@pytest.mark.parametrize("path", MODULES, ids=lambda path: path.stem)
def test_no_model_module_takes_an_underscore_name_from_another(path):
    """Neither `from models.x import _name` nor `x._name` on an imported
    model module: what two models share lives in models/layers.py or
    models/packed.py, under a public name."""
    tree = ast.parse(path.read_text())
    package = "min_tfs_client_tpu.models"
    siblings, taken = set(), []
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module and (
                node.module == package
                or node.module.startswith(package + ".")):
            for alias in node.names:
                if node.module == package:      # a sibling module itself
                    siblings.add(alias.asname or alias.name)
                elif _private(alias.name):
                    taken.append(f"{node.module}.{alias.name}")
    for node in ast.walk(tree):
        if (isinstance(node, ast.Attribute) and _private(node.attr)
                and isinstance(node.value, ast.Name)
                and node.value.id in siblings):
            taken.append(f"{node.value.id}.{node.attr}")
    assert not taken


def test_granite_hybrid_imports_nothing_from_mimo():
    tree = ast.parse((MODELS / "granite_hybrid.py").read_text())
    imported = [node.module for node in ast.walk(tree)
                if isinstance(node, ast.ImportFrom)]
    imported += [alias.name for node in ast.walk(tree)
                 if isinstance(node, (ast.Import, ast.ImportFrom))
                 for alias in node.names]
    assert not [name for name in imported if name and "mimo" in name]


def test_the_loaders_table_keeps_every_familys_name():
    assert export.FAMILIES == ("bert", "t5", "resnet", "use", "mimo",
                               "granite_hybrid", "ling_hybrid", "xing")
