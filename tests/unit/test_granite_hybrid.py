"""models/granite_hybrid.py at a small size on the CPU, seeded weights:
prefill then decoding through state, window and cache against the plain
reference's ONE forward pass, at logits; the softmax-top-k routing rule
against its definition and `held_experts_ffn` under it in both forms; the
four shares of a layer adding up to the uncut layer; the spans and
counters of an answer; the export round trip."""

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from min_tfs_client_tpu.models import granite_hybrid as gh
from min_tfs_client_tpu.parallel import moe
from perfbench import children

ROOT = pathlib.Path(__file__).resolve().parents[2]
SEQ, STEPS, CHUNK = 48, 128, 16
# both sides of the convolution's width (4) and of a chunk's edge (16),
# the cap, and rows of length 0 that pad the batch
LENGTHS = (1, 3, 4, 5, 15, 16, 17, 48, 0, 0, 33, 0)


def published(**changes) -> dict:
    """The configuration's file at a small size, float32 stated."""
    config = json.loads(
        (ROOT / "perfbench/configs/granite-4.0-h-small.json").read_text())
    config.update(hidden_size=64, num_attention_heads=4,
                  num_key_value_heads=2, attention_multiplier=1 / 16,
                  mamba_n_heads=4, mamba_d_head=16, mamba_d_state=16,
                  mamba_chunk_size=CHUNK, intermediate_size=32,
                  shared_intermediate_size=64, num_local_experts=4,
                  num_experts_per_tok=3, vocab_size=96, layers=3,
                  layer_types=["mamba", "attention", "mamba"])
    config["serve"]["config_kwargs"].update(
        num_local_experts=16, dtype="float32", prefill_rows=4)
    config.update(changes)
    return config


@pytest.fixture(scope="module")
def tiny():
    config = published()
    program_config = gh.GraniteHybridConfig(
        **children.program_config_kwargs(config))
    params = gh.init_params(jax.random.PRNGKey(7), program_config)
    rng = np.random.default_rng(7)
    ids = np.zeros((len(LENGTHS), SEQ), np.int32)
    for row, n in enumerate(LENGTHS):
        ids[row, :n] = rng.integers(2, config["vocab_size"], (n,))
    return {"config": config, "program_config": program_config,
            "params": params, "ids": ids,
            "reference": children.load_reference(config)}


@pytest.fixture(scope="module")
def generated(tiny):
    """Prefill, then 127 steps through state, window and cache: the
    logits every token was chosen from, and the tokens."""
    pc, params = tiny["program_config"], tiny["params"]
    state = jax.jit(lambda p, ids: gh.prefill(
        p, pc, ids, max_decode_len=STEPS, row_block=32))(params, tiny["ids"])
    step = jax.jit(lambda p, s: gh.step(p, pc, s))
    logits, tokens = [np.asarray(state["logits"])], []
    for _ in range(STEPS - 1):
        state, token = step(params, state)
        tokens.append(np.asarray(token))
        logits.append(np.asarray(state["logits"]))
    return {"logits": np.stack(logits, 1), "tokens": np.stack(tokens, 1),
            "state": state}


@pytest.mark.parametrize("row", [r for r, n in enumerate(LENGTHS) if n])
def test_prefill_and_127_steps_are_one_forward_pass(tiny, generated, row):
    n = LENGTHS[row]
    sequence = np.concatenate([tiny["ids"][row, :n],
                               generated["tokens"][row]])
    want, = tiny["reference"].forward(
        tiny["params"], tiny["config"], [sequence],
        [np.arange(n - 1, n - 1 + STEPS)])
    np.testing.assert_allclose(generated["logits"][row], want, atol=2e-5)
    assert np.std(want) > 0.05             # logits, not zeros


def test_a_row_of_length_0_touches_nothing(tiny, generated):
    pc = tiny["program_config"]
    state = gh.prefill(tiny["params"], pc, tiny["ids"],
                       max_decode_len=4, row_block=32)
    empty = np.asarray(LENGTHS) == 0
    for kind, cache in zip(pc.layer_types, state["caches"]):
        if kind == "mamba":
            assert not np.any(np.asarray(cache["ssm"])[empty])
            assert not np.any(np.asarray(cache["conv"])[empty])
    assert not np.any(np.asarray(state["logits"])[empty])
    # ... and a window shorter than 3 rows is zeros in front
    short = LENGTHS.index(1)
    window = np.asarray(state["caches"][0]["conv"])[short]
    assert not np.any(window[:2]) and np.any(window[2])
    counts = generated["state"]["counts"]
    assert np.asarray(counts["held_decode"])[empty].tolist() == [0, 0, 0]


@pytest.mark.parametrize("form", ["jnp", "pallas"])
def test_padding_rows_change_nothing_for_the_real_rows(tiny, form,
                                                       monkeypatch):
    """A whole generation of the batch with its rows of length 0 against
    the same prompts in a batch without them: tokens, first and last
    logits; and the states the steps held and moved. `pallas`: the step
    through `_ssm_step_kernel` (interpret mode) as on the chip."""
    from min_tfs_client_tpu.servables.decode_signatures import (
        whole_generation,
    )

    if form == "pallas":
        monkeypatch.setattr(gh.ssm, "ssm_step", lambda *a, **kw:
                            gh.ssm.ssm_step_kernel(*a, **kw, interpret=True))
    pc, steps = tiny["program_config"], 12

    def generate(ids):
        found = jax.jit(lambda p, ids: whole_generation(
            lambda p, ids: gh.prefill(p, pc, ids, max_decode_len=steps,
                                      row_block=32),
            lambda p, state: gh.step(p, pc, state), p, ids,
            max_decode_len=steps, pad_id=pc.pad_id))(tiny["params"], ids)
        return jax.tree_util.tree_map(np.asarray, {
            "tokens": found["output_ids"],
            "first": found["first"]["logits"],
            "last": found["before_last"]["logits"],
            "counts": found["final"]["counts"],
            "states": [c["ssm"] for c in found["final"]["caches"]
                       if "ssm" in c]})

    real = np.nonzero(LENGTHS)[0]
    padded, packed = generate(tiny["ids"]), generate(tiny["ids"][real])
    assert np.array_equal(padded["tokens"][real], packed["tokens"])
    np.testing.assert_allclose(padded["first"][real], packed["first"],
                               atol=2e-5)
    np.testing.assert_allclose(padded["last"][real], packed["last"],
                               atol=2e-5)
    layers = pc.layer_types.count("mamba")
    assert int(padded["counts"]["state_rows_held"]) == (
        len(LENGTHS) * layers * steps)
    assert int(padded["counts"]["state_rows_moved"]) == (
        len(real) * layers * steps)
    assert (int(packed["counts"]["state_rows_held"])
            == int(packed["counts"]["state_rows_moved"])
            == len(real) * layers * steps)
    # a padding row's states are the zeros the prefill left, the real
    # rows' moved
    empty = np.asarray(LENGTHS) == 0
    for state in padded["states"]:
        assert not np.any(state[empty]) and np.all(
            np.any(state[real], axis=(1, 2)))


def test_the_prefill_does_not_pay_for_the_padding(tiny):
    """Per-token work runs in blocks of the real tokens, and the scan in
    whole chunks (on the CPU: every example of a group to the group's
    longest)."""
    state = gh.prefill(tiny["params"], tiny["program_config"], tiny["ids"],
                       max_decode_len=4, row_block=8)
    groups = [LENGTHS[i:i + 4] for i in range(0, len(LENGTHS), 4)]
    assert int(state["counts"]["prefill_rows"]) == sum(
        -(-sum(g) // 8) * 8 for g in groups)
    assert np.asarray(state["counts"]["scan_rows"]).tolist() == [
        -(-max(g) // CHUNK) * CHUNK for g in groups for _ in g]


# -- the routing rule ---------------------------------------------------------


def routed_case(tokens=40, d=32, experts=12, f=16, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    return {"x": jax.random.normal(k[0], (tokens, d)),
            "router": jax.random.normal(k[1], (d, experts)) * d ** -0.5 * 3,
            "w_in": jax.random.normal(k[2], (experts, d, 2 * f)) * d ** -0.5,
            "w_out": jax.random.normal(k[3], (experts, f, d)) * f ** -0.5}


def dense_layer(case, top_k, experts=None):
    """The definition, token by token in numpy: the top_k of the logits,
    the softmax of those alone, the sum over the chosen experts (those of
    `experts` where given)."""
    x = np.asarray(case["x"], np.float64)
    logits = x @ np.asarray(case["router"], np.float64)
    out = np.zeros_like(x)
    for t, row in enumerate(logits):
        chosen = np.argsort(-row)[:top_k]
        weights = np.exp(row[chosen] - row[chosen].max())
        weights /= weights.sum()
        for e, w in zip(chosen, weights):
            if experts is None or e in experts:
                hidden = x[t] @ np.asarray(case["w_in"][e], np.float64)
                f = hidden.shape[0] // 2
                gated = hidden[:f] / (1 + np.exp(-hidden[:f])) * hidden[f:]
                out[t] += w * (gated @ np.asarray(case["w_out"][e],
                                                  np.float64))
    return out


def test_softmax_top_k_is_its_definition():
    case = routed_case()
    experts, weights = moe.softmax_top_k(case["x"], case["router"], 4)
    logits = np.asarray(case["x"], np.float64) @ np.asarray(case["router"],
                                                            np.float64)
    assert np.array_equal(np.sort(experts, -1),
                          np.sort(np.argsort(-logits, -1)[:, :4], -1))
    chosen = np.take_along_axis(logits, np.asarray(experts), -1)
    want = np.exp(chosen) / np.exp(chosen).sum(-1, keepdims=True)
    np.testing.assert_allclose(weights, want, rtol=1e-5)
    np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-6)


@pytest.mark.parametrize("form", ["by_hit_expert", "by_sorted_pair"])
def test_held_experts_under_the_softmax_rule_in_both_forms(form):
    case = routed_case()
    held, offset = 5, 3
    params = moe.HeldExperts(case["router"], None,
                             case["w_in"][offset:offset + held],
                             case["w_out"][offset:offset + held])
    extra = {} if form == "by_hit_expert" else {
        "rows": jnp.asarray(case["x"].shape[0]), "row_block": 16}
    with jax.default_matmul_precision("highest"):
        y, routed = moe.held_experts_ffn(
            params, case["x"], top_k=4, experts_held=held,
            expert_offset=offset, routing="softmax_top_k", scale=0.22,
            **extra)
    want = 0.22 * dense_layer(case, 4, set(range(offset, offset + held)))
    np.testing.assert_allclose(y, want, atol=1e-5)
    assert (int(routed.hit) > 0) == (form == "by_hit_expert")


def test_the_default_rule_is_the_sigmoid_rule_to_the_letter():
    """MiMo's calls name no rule and no scale: they lower to the program
    they lowered to before either argument existed."""
    case = routed_case()
    params = moe.HeldExperts(case["router"], jnp.zeros((12,)), case["w_in"],
                             case["w_out"])
    common = dict(top_k=4, experts_held=12, expert_offset=0)
    plain = jax.jit(lambda p, x: moe.held_experts_ffn(p, x, **common))
    named = jax.jit(lambda p, x: moe.held_experts_ffn(
        p, x, routing="sigmoid", scale=None, **common))
    other = jax.jit(lambda p, x: moe.held_experts_ffn(
        p, x, routing="softmax_top_k", **common))
    text = plain.lower(params, case["x"]).as_text()
    assert named.lower(params, case["x"]).as_text() == text
    assert other.lower(params, case["x"]).as_text() != text


def test_an_unknown_rule_is_an_error():
    case = routed_case()
    params = moe.HeldExperts(case["router"], None, case["w_in"],
                             case["w_out"])
    with pytest.raises(ValueError, match="unknown routing"):
        moe.held_experts_ffn(params, case["x"], top_k=2, experts_held=12,
                             expert_offset=0, routing="softmax")


def test_the_four_shares_add_up_to_the_uncut_layer(tiny):
    """One layer's experts, cut four ways as the deployment cuts them:
    the shares' parts, with the shared expert counted ONCE, are the
    reference's whole layer with every expert held."""
    reference = tiny["reference"]
    whole = published(num_local_experts=16)
    whole["deployment"] = dict(whole["deployment"], expert_offset=0)
    layer = jax.tree_util.tree_map(
        lambda x: x, tiny["params"]["layers"][0])
    keys = jax.random.split(jax.random.PRNGKey(11), 3)
    layer["moe"] = {
        "router": layer["moe"]["router"],
        "w_in": jax.random.normal(keys[0], (16, 64, 64)) * 0.125,
        "w_out": jax.random.normal(keys[1], (16, 32, 64)) * 0.5}
    u = jax.random.normal(keys[2], (40, 64))
    with jax.default_matmul_precision("highest"):
        want = (reference._experts(whole, layer["moe"], u)
                + reference._swiglu(u, layer["shared"]["w_in"],
                                    layer["shared"]["w_out"]))
        parts = []
        for share in range(4):
            held = moe.HeldExperts(
                layer["moe"]["router"], None,
                layer["moe"]["w_in"][4 * share:4 * share + 4],
                layer["moe"]["w_out"][4 * share:4 * share + 4])
            parts.append(moe.held_experts_ffn(
                held, u, top_k=3, experts_held=4, expert_offset=4 * share,
                routing="softmax_top_k")[0])
        shared = gh._swiglu(layer["shared"], u)
    np.testing.assert_allclose(sum(parts) + shared, want, atol=2e-5)
    # every share gives something, and no share gives it all
    assert all(float(jnp.max(jnp.abs(p))) > 0 for p in parts)


# -- serving -----------------------------------------------------------------


def test_an_answer_carries_its_route_and_its_state(tiny):
    from min_tfs_client_tpu.models.packed import ROUTE_COLUMNS
    from min_tfs_client_tpu.observability import runtime, tracing

    signature = gh.build_signatures(
        tiny["params"], tiny["program_config"], seq_len=SEQ,
        max_decode_len=8, batch_buckets=(12,))["serving_default"]
    signature.telemetry_label = "granite:1:serving_default"
    with tracing.request_trace("predict", model="granite",
                               signature="serving_default") as trace:
        out = signature.run({"input_ids": tiny["ids"]})
        signature.on_answer(signature, out)      # what the handlers do
    assert out["output_ids"].shape == (12, 8)
    assert out["first_logits"].shape == out["last_logits"].shape == (12, 96)
    assert out["route_counts"].shape == (12, len(ROUTE_COLUMNS))
    rows = out["state_counts"]
    assert rows[:, 0].tolist() == list(LENGTHS)
    per_sequence = tiny["program_config"].state_bytes
    assert per_sequence == 2 * (4 * 16 * 64 + 4 * 3 * (64 + 32))
    assert set(rows[:, 2].tolist()) == {per_sequence}
    assert set(rows[:, 3].tolist()) == {8}
    # the batch's figures on every row: 12 rows, 9 of them real, through
    # 2 state-space layers and 8 steps
    assert set(rows[:, 4].tolist()) == {12 * 2 * 8}
    assert set(rows[:, 5].tolist()) == {9 * 2 * 8}
    spans = {name: args for name, _, _, args in trace.spans}
    assert spans["generate/state"] == {
        "prompt_tokens": sum(LENGTHS), "scan_rows": int(rows[:, 1].sum()),
        "state_bytes": 12 * per_sequence, "steps": 96,
        "state_rows_held": 12 * 2 * 8, "state_rows_moved": 9 * 2 * 8}
    assert spans["generate/route"]["prompt_tokens"] == sum(LENGTHS)
    assert spans["generate/route"]["pairs_decode"] == 12 * 8 * 3 * 3
    snapshot = runtime.snapshot()
    counted = snapshot["state"]["granite:1:serving_default"]
    assert counted["steps"] >= 96
    assert counted["state_rows_moved"] * 12 == counted["state_rows_held"] * 9
    # ... and a batch with no padding row moved every state it held
    full = np.nonzero(LENGTHS)[0][:4]
    signature.on_answer(signature, gh.build_signatures(
        tiny["params"], tiny["program_config"], seq_len=SEQ,
        max_decode_len=8, batch_buckets=(4,))["serving_default"].run(
            {"input_ids": tiny["ids"][full]}))
    after = runtime.snapshot()["state"]["granite:1:serving_default"]
    assert (after["state_rows_held"] - counted["state_rows_held"]
            == after["state_rows_moved"] - counted["state_rows_moved"]
            == 4 * 2 * 8)
    assert snapshot["route"]["granite:1:serving_default"]["requests"] >= 1


def test_the_family_exports_and_loads(tiny, tmp_path):
    import dataclasses

    from min_tfs_client_tpu.models import export

    version = export.export_servable(
        tmp_path / "granite", 1, "granite_hybrid",
        dataclasses.asdict(tiny["program_config"]), tiny["params"],
        signature_kwargs={"seq_len": SEQ, "max_decode_len": 4,
                          "batch_buckets": [4]})
    signature = export.load_signatures(version)["serving_default"]
    out = signature.run({"input_ids": tiny["ids"][:4]})
    direct = gh.build_signatures(
        tiny["params"], tiny["program_config"], seq_len=SEQ,
        max_decode_len=4, batch_buckets=(4,))["serving_default"].run(
            {"input_ids": tiny["ids"][:4]})
    assert np.array_equal(out["output_ids"], direct["output_ids"])
    np.testing.assert_allclose(out["first_logits"], direct["first_logits"],
                               atol=1e-6)


def test_a_config_says_what_it_cannot_run():
    with pytest.raises(ValueError, match="fewer entries"):
        gh.GraniteHybridConfig(num_layers=12)
    with pytest.raises(ValueError, match="unknown layer types"):
        gh.GraniteHybridConfig(num_layers=1, layer_types=("conv",))
    with pytest.raises(ValueError, match="one group"):
        gh.GraniteHybridConfig(num_layers=1, mamba_n_groups=2)
    with pytest.raises(ValueError, match="outside the router"):
        gh.GraniteHybridConfig(num_layers=1, experts_held=18,
                               expert_offset=60)
    config = gh.GraniteHybridConfig(num_layers=10)
    assert config.layer_types.count("attention") == 1
    assert (config.d_inner, config.conv_dim, config.head_dim) == (
        8192, 8448, 128)
    # 9 layers x (128 x 8192 float32 + 3 rows of 8,448 bfloat16)
    assert config.state_bytes == 9 * (4194304 + 50688)
