"""models/mimo.py at a small size on the CPU, float32, against the plain
reference of its benchmark configuration
(perfbench/configs/mimo-v2.5.reference.py): logits, not sampled tokens.
Hidden 64, 4 query heads over 1 (full) and 2 (window) K/V heads, qk 24 /
v 16, window 8, 16 experts of which 4 held, top-2, one dense layer and
six more in the published pattern."""

import itertools
import json
import pathlib
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from min_tfs_client_tpu.models import export, mimo, packed
from min_tfs_client_tpu.ops.attention import (
    attention_reference,
    flash_attention,
)
from min_tfs_client_tpu.parallel import moe
from min_tfs_client_tpu.parallel.moe import HeldExperts, held_experts_ffn
from perfbench import children

ROOT = pathlib.Path(__file__).resolve().parents[2]
LABEL = "mimo:1:serving_default"    # of the served signature (`route`)
SEQ, STEPS = 40, 30                  # 30 steps: more than three windows
LENGTHS = (5, 8, 29, 0, 40, 9)       # below, at and above the window; none


def tiny_config() -> dict:
    config = json.loads(
        (ROOT / "perfbench/configs/mimo-v2.5.json").read_text())
    config.update(hidden_size=64, num_attention_heads=4, head_dim=24,
                  v_head_dim=16, swa_head_dim=24, swa_v_head_dim=16,
                  num_key_value_heads=1, swa_num_key_value_heads=2,
                  sliding_window=8, intermediate_size=128,
                  moe_intermediate_size=32, n_routed_experts=4,
                  num_experts_per_tok=2, vocab_size=96)
    config["published"]["n_routed_experts"] = 16
    config["serve"]["config_kwargs"].update(
        n_routed_experts=16, dtype="float32", prefill_rows=2)
    config["serve"]["signature_kwargs"].update(
        seq_len=SEQ, max_decode_len=STEPS, batch_buckets=[8])
    return config


@pytest.fixture(scope="module")
def tiny():
    config = tiny_config()
    program_config = mimo.MimoConfig(
        **children.program_config_kwargs(config))
    params = mimo.init_params(jax.random.PRNGKey(7), program_config)
    rng = np.random.default_rng(7)
    ids = np.zeros((len(LENGTHS), SEQ), np.int32)
    for row, n in enumerate(LENGTHS):
        ids[row, :n] = rng.integers(2, config["vocab_size"], n)
    return {"config": config, "program_config": program_config,
            "params": params, "ids": ids,
            "reference": children.load_reference(config)}


def test_prefill_logits_agree_with_the_references_full_forward(tiny):
    state = jax.jit(lambda p, ids: mimo.prefill(
        p, tiny["program_config"], ids, max_decode_len=STEPS))(
            tiny["params"], tiny["ids"])
    assert np.isfinite(np.asarray(state["logits"])).all()   # length 0 too
    rows = [r for r, n in enumerate(LENGTHS) if n]
    want = tiny["reference"].forward(
        tiny["params"], tiny["config"],
        [tiny["ids"][r, :LENGTHS[r]] for r in rows],
        [[LENGTHS[r] - 1] for r in rows])
    for r, logits in zip(rows, want):
        np.testing.assert_allclose(np.asarray(state["logits"][r]),
                                   logits[0], atol=2e-4)


def test_every_decode_step_through_ring_and_full_caches_agrees(tiny):
    config = tiny["program_config"]
    state = jax.jit(lambda p, ids: mimo.prefill(
        p, config, ids, max_decode_len=STEPS))(tiny["params"], tiny["ids"])
    step = jax.jit(lambda p, s: mimo.step(p, config, s))
    chosen_from, tokens = [], []
    for _ in range(STEPS):
        chosen_from.append(np.asarray(state["logits"]))
        state, token = step(tiny["params"], state)
        tokens.append(np.asarray(token))
    chosen_from, tokens = np.stack(chosen_from, 1), np.stack(tokens, 1)
    assert np.isfinite(chosen_from).all()
    rows = [r for r, n in enumerate(LENGTHS) if n]
    want = tiny["reference"].forward(
        tiny["params"], tiny["config"],
        [np.concatenate([tiny["ids"][r, :LENGTHS[r]], tokens[r, :-1]])
         for r in rows],
        [np.arange(LENGTHS[r] - 1, LENGTHS[r] - 1 + STEPS) for r in rows])
    for r, logits in zip(rows, want):
        np.testing.assert_allclose(chosen_from[r], logits, atol=5e-4)
    per_token = config.top_k * sum(config.moe_pattern)
    counts = np.asarray(packed.route_table(per_token).rows(state["counts"]))
    assert counts[:, 0].tolist() == list(LENGTHS)
    assert (counts[:, 1] == np.asarray(LENGTHS) * per_token).all()
    assert (counts[:, 3] == STEPS * per_token).all()
    assert (counts[:, 2] <= counts[:, 1]).all() and counts[3, 2] == 0


BLOCK = 16     # of the packed prefill in the cases below: 80 rows a chunk
PACKED_CASES = {
    # the chunks of 2 examples hold 13, 29 and 49 real tokens: every one
    # a partial last block, the last chunk four blocks
    "mixed_lengths": (5, 8, 29, 0, 40, 9),
    "a_padding_row_among_real_rows": (0, 21, 7, 0, 0, 12),
    "a_chunk_that_is_wholly_padding": (17, 30, 0, 0, 3, 40),
    "every_row_at_seq_len": (40, 40, 40, 40, 40, 40),
    # 32 = two whole blocks and no partial one; a chunk of one token
    "a_multiple_of_the_block_and_a_single_token": (16, 16, 1, 0, 32, 0),
}


@pytest.mark.parametrize("lengths", PACKED_CASES.values(),
                         ids=PACKED_CASES.keys())
def test_the_packed_prefill_agrees_with_the_references_full_forward(
        tiny, lengths):
    """The prefill keeps a chunk's real tokens packed and runs its
    per-token work in blocks of 16 rows. Against one full forward pass a
    sequence: the first logits, both kinds of cache (by decoding from
    them), the held pairs and the load (the real tokens' pairs, from the
    reference's own routing), and the rows the blocks ran."""
    config, steps = tiny["program_config"], 10
    rng = np.random.default_rng(sum(lengths))
    ids = np.zeros((len(lengths), SEQ), np.int32)
    for row, n in enumerate(lengths):
        ids[row, :n] = rng.integers(2, tiny["config"]["vocab_size"], n)
    state = jax.jit(lambda p, ids: mimo.prefill(
        p, config, ids, max_decode_len=STEPS, row_block=BLOCK))(
            tiny["params"], ids)
    prefilled = state
    step = jax.jit(lambda p, s: mimo.step(p, config, s))
    chosen_from, tokens = [], []
    for _ in range(steps):
        chosen_from.append(np.asarray(state["logits"]))
        state, token = step(tiny["params"], state)
        tokens.append(np.asarray(token))
    chosen_from, tokens = np.stack(chosen_from, 1), np.stack(tokens, 1)
    assert np.isfinite(chosen_from).all()            # a length of 0 too
    rows = [r for r, n in enumerate(lengths) if n]
    want = tiny["reference"].forward(
        tiny["params"], tiny["config"],
        [np.concatenate([ids[r, :lengths[r]], tokens[r, :-1]])
         for r in rows],
        [np.arange(lengths[r] - 1, lengths[r] - 1 + steps) for r in rows])
    for r, logits in zip(rows, want):
        np.testing.assert_allclose(chosen_from[r, 0], logits[0], atol=2e-4)
        np.testing.assert_allclose(chosen_from[r], logits, atol=5e-4)

    # what the expert layers counted: the pairs of the real tokens alone
    held, load = _held_pairs_by_the_reference(tiny, ids, lengths)
    table = packed.route_table(config.top_k * sum(config.moe_pattern))
    counts = dict(zip(packed.ROUTE_COLUMNS,
                      np.asarray(table.rows(prefilled["counts"])).T))
    assert counts["prompt_tokens"].tolist() == list(lengths)
    assert counts["held_prefill"].tolist() == held.tolist()
    assert (counts["max_load"] == load.max()).all()
    assert load.sum() == held.sum()
    assert (counts["load_total"] == load.sum()).all()
    per_chunk = np.asarray(lengths).reshape(-1, config.prefill_rows).sum(1)
    assert (counts["prefill_rows"]
            == sum(-(-int(n) // BLOCK) * BLOCK for n in per_chunk)).all()


def _held_pairs_by_the_reference(tiny, ids, lengths):
    """(held pairs of each example (B,), rows of each held expert in each
    expert layer (layers, held)): the router of each expert layer on the
    rows that enter it in the reference's own pass over each sequence."""
    ref, config, program = (tiny["reference"], tiny["config"],
                            tiny["program_config"])
    eps = config["layernorm_epsilon"]
    held = np.zeros((len(lengths),), np.int64)
    load = np.zeros((sum(program.moe_pattern), program.experts_held),
                    np.int64)
    with jax.default_matmul_precision("highest"):
        table = ref._f32(tiny["params"]["embed"]["embedding"])
        for row, n in enumerate(lengths):
            h, at = table[ids[row, :n]], 0
            for index in range(config["layers"] if n else 0):
                layer = ref._float32(tiny["params"]["layers"][index])
                if config["moe_layer_freq"][index]:
                    x = h + ref._attention(
                        config, index, layer["attn"],
                        ref._rms(layer["attn_norm"]["scale"], h, eps))
                    scores = jax.nn.sigmoid(
                        ref._rms(layer["ffn_norm"]["scale"], x, eps)
                        @ layer["moe"]["router"])
                    _, chosen = jax.lax.top_k(scores + layer["moe"]["bias"],
                                              program.top_k)
                    local = np.asarray(chosen) - program.expert_offset
                    mine = local[(local >= 0)
                                 & (local < program.experts_held)]
                    held[row] += mine.size
                    np.add.at(load[at], mine, 1)
                    at += 1
                h = ref._layer(config, index, layer, h)
    return held, load


def test_whole_generation_equals_prefill_then_steps(tiny):
    config = tiny["program_config"]
    sigs = mimo.build_signatures(tiny["params"], config, seq_len=SEQ,
                                 max_decode_len=STEPS, batch_buckets=(8,))
    out = sigs["serving_default"].run({"input_ids": tiny["ids"]})
    state = mimo.prefill(tiny["params"], config, tiny["ids"],
                         max_decode_len=STEPS)
    np.testing.assert_allclose(out["first_logits"],
                               np.asarray(state["logits"]), atol=1e-5)
    tokens = []
    for _ in range(STEPS):
        last = np.asarray(state["logits"])
        state, token = mimo.step(tiny["params"], config, state)
        tokens.append(np.asarray(token))
    np.testing.assert_array_equal(out["output_ids"], np.stack(tokens, 1))
    np.testing.assert_allclose(out["last_logits"], last, atol=1e-4)
    assert out["route_counts"].shape == (len(LENGTHS),
                                         len(packed.ROUTE_COLUMNS))


def test_the_rows_that_pad_a_batch_are_prompts_of_length_zero(tiny):
    """Three requests in a bucket of eight: the five padding rows are
    rows of pad_id, which no attention and no expert computes, so the
    batch's load is its real rows' and the answers are the direct ones."""
    config = tiny["program_config"]
    signature = mimo.build_signatures(
        tiny["params"], config, seq_len=SEQ, max_decode_len=STEPS,
        batch_buckets=(8,))["serving_default"]
    assert signature._padding_rows(
        "input_ids", tiny["ids"][:3], 5).tolist() == [[config.pad_id] * SEQ] * 5
    three = signature.run({"input_ids": tiny["ids"][:3]})
    whole = signature.run({"input_ids": tiny["ids"]})
    np.testing.assert_array_equal(three["output_ids"],
                                  whole["output_ids"][:3])
    counts = dict(zip(packed.ROUTE_COLUMNS, three["route_counts"].T))
    assert (counts["load_total"] == np.sum(counts["held_prefill"])).all()


@pytest.mark.parametrize("window, sink, kv_heads, d_v", list(
    itertools.product((None, 8, 128), (False, True), (1, 2, 4), (16, 24))))
def test_flash_kernel_window_sink_grouped_heads_unequal_sizes(
        window, sink, kv_heads, d_v):
    rng = np.random.default_rng(0)
    b, h, s, d = 3, 4, 300, 24
    q = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, kv_heads, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, kv_heads, s, d_v)), jnp.float32)
    kw = dict(causal=True, lengths=jnp.asarray([300, 0, 131], jnp.int32),
              causal_offset=0, window=window, queries_ragged=True,
              sink=(jnp.asarray(rng.standard_normal((h,)), jnp.float32)
                    if sink else None))
    got = flash_attention(q, k, v, interpret=True, **kw)
    want = attention_reference(q, k, v, **kw)
    assert got.shape == (b, h, s, d_v) and bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def _expert_layer(rng, tokens=50, d=32, f=16, experts=16):
    return {
        "x": jnp.asarray(rng.standard_normal((tokens, d)), jnp.float32),
        "router": jnp.asarray(rng.standard_normal((d, experts)) / d ** 0.5,
                              jnp.float32),
        "bias": jnp.asarray(rng.standard_normal((experts,)) * 0.1,
                            jnp.float32),
        "w_in": jnp.asarray(rng.standard_normal((experts, d, 2 * f))
                            / d ** 0.5, jnp.float32),
        "w_out": jnp.asarray(rng.standard_normal((experts, f, d))
                             / f ** 0.5, jnp.float32)}


def test_the_four_shares_add_up_to_the_uncut_reference(tiny):
    """The share is tied to the model: what each of the 4 chips (4
    experts each) gives for one layer adds up to what the reference
    gives when it is handed all 16."""
    case = _expert_layer(np.random.default_rng(1))
    uncut = dict(tiny["config"], n_routed_experts=16, num_experts_per_tok=2,
                 deployment={"expert_offset": 0})
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(tiny["reference"]._experts(
            uncut, {k: case[k] for k in ("router", "bias", "w_in", "w_out")},
            case["x"]))
    parts, loads = 0.0, []
    for share in range(4):
        held = slice(4 * share, 4 * share + 4)
        y, routed = jax.jit(
            lambda p, x, offset=4 * share: held_experts_ffn(
                p, x, top_k=2, experts_held=4, expert_offset=offset,
                row_block=16))(
            HeldExperts(case["router"], case["bias"], case["w_in"][held],
                        case["w_out"][held]), case["x"])
        parts = parts + np.asarray(y)
        loads.append(int(np.sum(routed.load)))
        assert int(np.sum(routed.held)) == loads[-1]
    assert sum(loads) == 50 * 2                  # every pair, once
    np.testing.assert_allclose(parts, whole, atol=1e-4)


def test_the_expert_layer_drops_nothing_when_every_token_picks_one():
    case = _expert_layer(np.random.default_rng(2), tokens=70)
    bias = jnp.zeros((16,), jnp.float32).at[jnp.asarray([2, 9])].set(10.0)
    y, routed = held_experts_ffn(
        HeldExperts(case["router"], bias, case["w_in"][:4],
                    case["w_out"][:4]), case["x"], top_k=2, experts_held=4,
        expert_offset=0, row_block=16)
    assert np.asarray(routed.load).tolist() == [0, 0, 70, 0]
    assert np.asarray(routed.held).tolist() == [1] * 70
    scores = jax.nn.sigmoid(case["x"] @ case["router"])
    weight = scores[:, 2] / (scores[:, 2] + scores[:, 9])
    hidden = case["x"] @ case["w_in"][2]
    want = ((jax.nn.silu(hidden[:, :16]) * hidden[:, 16:])
            @ case["w_out"][2]) * weight[:, None]
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-4)


def _on(*held):
    """A selection bias that puts every row's two choices on `held`."""
    return jnp.zeros((16,), jnp.float32).at[jnp.asarray(held)].set(10.0)


FORM_CASES = {
    "one_row": dict(tokens=1),
    "four_rows": dict(tokens=4),
    "the_decode_batch": dict(tokens=32),
    "the_most_rows_the_walk_takes": dict(tokens=moe.DECODE_ROWS),
    "no_pair_held_at_all": dict(tokens=32, bias=_on(0, 9), trips=0),
    "every_row_on_one_expert": dict(tokens=32, bias=_on(6, 9), trips=1),
    "some_rows_left_out": dict(tokens=32, valid=np.arange(32) % 3 > 0),
    "added_onto_a_residual": dict(tokens=32, onto=True),
}


@pytest.mark.parametrize("case", FORM_CASES.values(), ids=FORM_CASES.keys())
def test_the_walk_over_hit_experts_agrees_with_the_sorted_pairs(
        monkeypatch, case):
    """`held_experts_ffn` on the same inputs in both its forms (experts
    4..7 held of 16, top-2): the rows' results, each row's held pairs
    and each expert's load; the walk's trips are the experts hit."""
    layer = _expert_layer(np.random.default_rng(3), tokens=case["tokens"])
    x = layer["x"]
    params = HeldExperts(layer["router"], case.get("bias", layer["bias"]),
                         layer["w_in"][4:8], layer["w_out"][4:8])
    routing = {}
    if "valid" in case:
        routing["valid"] = jnp.asarray(case["valid"])
    if "onto" in case:
        routing["onto"] = 3.0 * x

    def run():
        return jax.jit(lambda p, x: held_experts_ffn(
            p, x, top_k=2, experts_held=4, expert_offset=4, **routing))(
                params, x)

    walked, counted = run()
    monkeypatch.setattr(moe, "DECODE_ROWS", 0)
    sorted_, want = run()
    np.testing.assert_allclose(np.asarray(walked), np.asarray(sorted_),
                               atol=1e-5)
    assert np.asarray(counted.held).tolist() == np.asarray(want.held).tolist()
    assert np.asarray(counted.load).tolist() == np.asarray(want.load).tolist()
    assert int(counted.hit) == int(np.sum(np.asarray(want.load) > 0))
    assert int(want.hit) == 0
    if "valid" in case:
        assert not np.asarray(counted.held)[~case["valid"]].any()
    if "trips" in case:
        assert int(counted.hit) == case["trips"]


def test_which_form_a_row_count_takes_shows_in_the_lowered_text():
    """At decode's rows the program holds no sort, no scatter and no
    grouped product; one row more, or the prefill's `rows=`, and the
    sorted form is there as it was."""
    layer = _expert_layer(np.random.default_rng(4), tokens=moe.DECODE_ROWS + 1)
    params = HeldExperts(layer["router"], layer["bias"], layer["w_in"][:4],
                         layer["w_out"][:4])

    def found(tokens, **routing):
        text = jax.jit(lambda p, x: held_experts_ffn(
            p, x, top_k=2, experts_held=4, expert_offset=0,
            **routing)).trace(params, layer["x"][:tokens]).lower(
                lowering_platforms=("tpu",)).as_text()
        return {name: name in text for name in (
            "stablehlo.sort", "stablehlo.scatter", "ragged_dot")}

    assert not any(found(32).values())
    assert not any(found(moe.DECODE_ROWS).values())
    assert all(found(moe.DECODE_ROWS + 1).values())
    assert all(found(32, rows=jnp.int32(20)).values())


def test_serving_default_through_a_real_server_with_batching(tiny, tmp_path):
    """An export made by export_servable, the gRPC front, the batcher and
    Signature.dispatch: single-example requests ride one batch and each
    gets what the direct call gives; each request's trace carries its own
    `generate/route`, and the process's counters add them up."""
    import dataclasses

    from min_tfs_client_tpu.client import TensorServingClient
    from min_tfs_client_tpu.observability import runtime, tracing
    from min_tfs_client_tpu.server.server import Server, ServerOptions
    from min_tfs_client_tpu.tensor.codec import tensor_proto_to_ndarray

    config = tiny["program_config"]
    export.export_servable(
        tmp_path / "mimo", 1, "mimo", dataclasses.asdict(config),
        tiny["params"],
        signature_kwargs={"seq_len": SEQ, "max_decode_len": STEPS,
                          "batch_buckets": [8]})
    direct = mimo.build_signatures(
        tiny["params"], config, seq_len=SEQ, max_decode_len=STEPS,
        batch_buckets=(8,))["serving_default"].run(
            {"input_ids": tiny["ids"]})
    batching = tmp_path / "batching.config"
    batching.write_text("max_batch_size { value: 8 }\n"
                        "batch_timeout_micros { value: 300000 }\n"
                        "allowed_batch_sizes: 8\n")
    before = {"prompt_tokens": 0, "prefill_rows": 0,
              **runtime.generation_totals("route").get(LABEL, {})}
    server = Server(ServerOptions(
        grpc_port=0, model_name="mimo", model_base_path=str(tmp_path / "mimo"),
        model_platform="jax", enable_batching=True,
        batching_parameters_file=str(batching),
        file_system_poll_wait_seconds=0)).build_and_start()
    try:
        answers = {}

        def call(row):
            with TensorServingClient("127.0.0.1", server.grpc_port) as c:
                resp = c.predict_request(
                    "mimo", {"input_ids": tiny["ids"][row:row + 1]},
                    timeout=300)
            answers[row] = {k: tensor_proto_to_ndarray(v)
                            for k, v in resp.outputs.items()}

        threads = [threading.Thread(target=call, args=(row,))
                   for row in range(len(LENGTHS))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
    finally:
        server.stop()
    for row in range(len(LENGTHS)):
        np.testing.assert_array_equal(answers[row]["output_ids"][0],
                                      direct["output_ids"][row])
        np.testing.assert_allclose(answers[row]["first_logits"][0],
                                   direct["first_logits"][row], atol=1e-4)
        np.testing.assert_allclose(answers[row]["last_logits"][0],
                                   direct["last_logits"][row], atol=1e-3)
    routes = [args for trace in tracing.ring_snapshot()
              for name, _, _, args in trace.spans
              if name == "generate/route"][-len(LENGTHS):]
    assert sorted(r["prompt_tokens"] for r in routes) == sorted(LENGTHS)
    assert all(r["pairs_decode"] == STEPS * 2 * 6 for r in routes)
    totals = runtime.generation_totals("route")[LABEL]
    assert totals["prompt_tokens"] - before["prompt_tokens"] == sum(LENGTHS)
    # the rows the prefill ran are a batch's figure, the same on each of
    # its riders; the counters give each request its share of them
    by_batch = {(r["load_total"], r["max_load"]): r["prefill_rows"]
                for r in routes}
    assert all(rows > 0 and rows % (2 * SEQ) == 0
               for rows in by_batch.values())       # chunks of 80 rows
    assert abs(totals["prefill_rows"] - before["prefill_rows"]
               - sum(by_batch.values())) <= len(LENGTHS)
    assert "route" in runtime.snapshot()


def test_an_on_answer_that_raises_loses_its_note_and_not_the_answer(caplog):
    import types

    from min_tfs_client_tpu.server.handlers import Handlers

    def raises(signature, outputs):
        raise ZeroDivisionError("a counter overflowed")

    noisy = types.SimpleNamespace(on_answer=raises,
                                  telemetry_label="m:1:serving_default")
    Handlers._answered(noisy, {})                    # returns: no raise
    assert "on_answer of m:1:serving_default raised" in caplog.text
    Handlers._answered(types.SimpleNamespace(on_answer=None), {})
