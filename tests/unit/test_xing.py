"""models/xing.py at a small size on the CPU, seeded weights (the
routers' selection bias given values): prefill then decoding through the
latent caches of every layer against the plain reference's ONE forward
pass, at logits; the three faults of the hand-over, made in the program,
each caught; a row of length 0 touches nothing; the eight shares of an
expert layer adding up to the uncut layer; the program's parameter count
at the published widths; the spans and counters of an answer, through
the jnp step and through the step's kernel (interpreted), whose
generation is the jnp one's; the export round trip; what a config
refuses."""

import dataclasses
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from min_tfs_client_tpu.models import xing
from min_tfs_client_tpu.parallel import moe
from perfbench import children
from tests.unit.test_latent import (
    answers_through_both_bodies,
    check_the_kernel_s_generation_is_the_jnp_one,
    check_the_rows_an_answer_brought_in,
)

ROOT = pathlib.Path(__file__).resolve().parents[2]
SEQ, STEPS = 80, 16
# one token, both sides of a row block's edge (32), the cap, and rows of
# length 0 that pad the batch
LENGTHS = (1, 2, 31, 32, 33, 80, 0, 0, 47, 0, 5, 64)
# float32 stated: the program and the reference part only by the order of
# float32 sums
ATOL = 3e-5
LAYERS = 4          # published layer 0 (dense, counted once) + 3 expert layers


def published(**changes) -> dict:
    """The configuration's file at a small size, float32 stated: hidden
    64, n = 4 streams, 8 experts of which 3 are held, top 2."""
    config = json.loads(
        (ROOT / "perfbench/configs/xing4.0-29b-a4b.json").read_text())
    config.update(hidden_size=64, num_attention_heads=4, q_lora_rank=24,
                  kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                  qk_head_dim=24, v_head_dim=16, intermediate_size=96,
                  moe_intermediate_size=32, n_routed_experts=3,
                  num_experts_per_tok=2, vocab_size=96, layers=LAYERS,
                  layer_types=["mla"] * LAYERS,
                  ffn_types=["dense"] + ["moe"] * (LAYERS - 1))
    config["rope_scaling"] = dict(config["rope_scaling"],
                                  original_max_position_embeddings=16)
    config["serve"]["config_kwargs"].update(
        num_experts=8, dtype="float32", prefill_rows=4,
        rope_original_positions=16)
    config.update(changes)
    return config


def with_a_selection_bias(params, seed=5, std=0.1):
    """The seeded bias is zero (`init_params` says why): here it has
    values, so that the choice on s + bias and the weights from s alone
    are both in what is compared."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 64))
    for layer in params["layers"]:
        if "moe" in layer:
            layer["moe"]["bias"] = std * jax.random.normal(
                next(keys), layer["moe"]["bias"].shape)
    return params


@pytest.fixture(scope="module")
def tiny():
    config = published()
    program_config = xing.XingConfig(
        **children.program_config_kwargs(config))
    params = with_a_selection_bias(
        xing.init_params(jax.random.PRNGKey(7), program_config))
    rng = np.random.default_rng(7)
    ids = np.zeros((len(LENGTHS), SEQ), np.int32)
    for row, n in enumerate(LENGTHS):
        ids[row, :n] = rng.integers(2, config["vocab_size"], (n,))
    return {"config": config, "program_config": program_config,
            "params": params, "ids": ids,
            "reference": children.load_reference(config),
            # traced once for every test that runs the sound program
            "prefill": jax.jit(lambda p, ids: xing.prefill(
                p, program_config, ids, max_decode_len=STEPS, row_block=32)),
            "step": jax.jit(lambda p, s: xing.step(p, program_config, s))}


def quick(pc):
    """Three of Sinkhorn's rounds for the tests that compare the program
    with itself: a third of the trace to compile."""
    return dataclasses.replace(pc, hc_sinkhorn_iters=3)


def generate(tiny, after_prefill=None, before_steps=None, after_step=None):
    """Prefill, then 15 steps through every layer's latent cache: the
    logits every token was chosen from, and the tokens. The hooks are
    where a test breaks the hand-over."""
    pc, params = tiny["program_config"], tiny["params"]
    state = tiny["prefill"](params, tiny["ids"])
    if after_prefill:
        state = after_prefill(state)
    step = tiny["step"]
    if before_steps:
        before_steps()        # what it swapped is traced anew
        step = jax.jit(lambda p, s: xing.step(p, pc, s))
    logits, tokens = [np.asarray(state["logits"])], []
    for _ in range(STEPS - 1):
        before = state
        state, token = step(params, state)
        if after_step:
            state = after_step(before, state)
        tokens.append(np.asarray(token))
        logits.append(np.asarray(state["logits"]))
    return {"logits": np.stack(logits, 1), "tokens": np.stack(tokens, 1),
            "state": state}


@pytest.fixture(scope="module")
def generated(tiny):
    return generate(tiny)


def reference_logits(tiny, generated, row):
    n = LENGTHS[row]
    sequence = np.concatenate([tiny["ids"][row, :n],
                               generated["tokens"][row]])
    want, = tiny["reference"].forward(
        tiny["params"], tiny["config"], [sequence],
        [np.arange(n - 1, n - 1 + STEPS)])
    return want


@pytest.mark.parametrize("row", [r for r, n in enumerate(LENGTHS) if n])
def test_prefill_and_15_steps_are_one_forward_pass(tiny, generated, row):
    want = reference_logits(tiny, generated, row)
    np.testing.assert_allclose(generated["logits"][row], want, atol=ATOL)
    assert np.std(want) > 0.05             # logits, not zeros


def test_a_row_of_length_0_touches_nothing(tiny, generated):
    pc = tiny["program_config"]
    state = xing.prefill(tiny["params"], pc, tiny["ids"],
                         max_decode_len=4, row_block=32)
    empty = np.asarray(LENGTHS) == 0
    assert len(state["caches"]) == LAYERS
    for cache in state["caches"]:
        # 32 + 8 values, then zeros to whole lane tiles
        assert cache["latent"].shape == (12, 1, SEQ + 4, 128)
        assert not np.any(np.asarray(cache["latent"])[..., 32 + 8:])
    assert not np.any(np.asarray(state["logits"])[empty])
    assert np.asarray(state["counts"]["stream_rows"]).tolist() \
        == [2 * LAYERS * n for n in LENGTHS]
    # ... and its decode steps count nothing: no expert, no cached row,
    # no stream
    counts = generated["state"]["counts"]
    for name in ("held_prefill", "held_decode", "latent_rows_read",
                 "latent_rows_held", "latent_rows_copied", "stream_rows"):
        assert np.asarray(counts[name])[empty].tolist() == [0, 0, 0], name
    # a real row's steps read the positions up to their own in every
    # layer: a prompt of n tokens and 15 steps read n + 1 .. n + 15
    real = np.asarray(LENGTHS)[~empty]
    assert np.asarray(counts["latent_rows_read"])[~empty].tolist() \
        == (LAYERS * (15 * real + 15 * 16 // 2)).tolist()
    assert set(np.asarray(counts["latent_rows_held"])[~empty].tolist()) \
        == {LAYERS * 15 * (SEQ + STEPS)}
    # the jnp step brings in every row it holds
    assert np.array_equal(counts["latent_rows_copied"],
                          counts["latent_rows_held"])
    assert np.asarray(counts["stream_rows"])[~empty].tolist() \
        == (2 * LAYERS * (real + 15)).tolist()


# -- the hand-over to decoding, broken in the program -------------------------


def last_row_dropped_in_one_layer(monkeypatch):
    """Layer 2's latent cache lacks each example's last prompt row."""
    def after_prefill(state):
        last = jnp.maximum(state["length"] - 1, 0)
        each = jnp.arange(last.shape[0])
        caches = list(state["caches"])
        caches[2] = {"latent": caches[2]["latent"].at[each, 0, last].set(0)}
        return dict(state, caches=caches)
    return {"after_prefill": after_prefill}


def rotary_position_off_by_one(monkeypatch):
    """A step rotates its query and its key one position late (the row
    is still written where it belongs)."""
    sound = xing._mla_inputs

    def before_steps():
        monkeypatch.setattr(
            xing, "_mla_inputs", lambda config, p, x, positions:
            sound(config, p, x, positions + 1))
    return {"before_steps": before_steps}


def padded_row_writes(monkeypatch):
    """A row that pads the batch writes its steps' latent rows into the
    NEXT example's caches (positions 0, 1, 2, ...: the padded row's own)
    in place of its own."""
    padded = [row for row, n in enumerate(LENGTHS) if not n
              and row + 1 < len(LENGTHS) and LENGTHS[row + 1]]

    def after_step(before, state):
        caches = []
        for cache in state["caches"]:
            latent = cache["latent"]
            for row in padded:
                at = before["length"][row]
                latent = latent.at[row + 1, 0, at].set(latent[row, 0, at])
            caches.append({"latent": latent})
        return dict(state, caches=caches)
    return {"after_step": after_step, "row": padded[0] + 1}


@pytest.mark.parametrize("fault", [last_row_dropped_in_one_layer,
                                   rotary_position_off_by_one,
                                   padded_row_writes],
                         ids=lambda f: f.__name__)
def test_a_fault_of_the_hand_over_fails_in_decoding(tiny, generated,
                                                     monkeypatch, fault):
    hooks = fault(monkeypatch)
    row = hooks.pop("row", LENGTHS.index(33))
    broken = generate(tiny, **hooks)
    # the prefill's own logits are sound: only decoding shows it
    np.testing.assert_allclose(broken["logits"][:, 0],
                               generated["logits"][:, 0], atol=ATOL)
    want = reference_logits(tiny, broken, row)
    assert np.max(np.abs(broken["logits"][row, 2:] - want[2:])) > 100 * ATOL


def test_padding_rows_change_nothing_for_the_real_rows(tiny):
    """A whole generation of the batch with its rows of length 0 against
    the same prompts in a batch without them: tokens, first and last
    logits."""
    from min_tfs_client_tpu.servables.decode_signatures import (
        whole_generation,
    )

    pc, steps = quick(tiny["program_config"]), 6

    def run(ids):
        out = jax.jit(lambda params, ids: whole_generation(
            lambda p, i: xing.prefill(p, pc, i, max_decode_len=steps,
                                      row_block=32),
            lambda p, s: xing.step(p, pc, s), params, ids,
            max_decode_len=steps, pad_id=pc.pad_id))(tiny["params"], ids)
        return (np.asarray(out["output_ids"]),
                np.asarray(out["first"]["logits"]),
                np.asarray(out["before_last"]["logits"]))

    real = np.nonzero(LENGTHS)[0]
    padded, alone = run(tiny["ids"]), run(tiny["ids"][real][:8])
    assert np.array_equal(padded[0][real[:8]], alone[0])
    np.testing.assert_allclose(padded[1][real[:8]], alone[1], atol=ATOL)
    np.testing.assert_allclose(padded[2][real[:8]], alone[2], atol=ATOL)


def test_the_prefill_pays_what_the_routed_experts_owe(tiny):
    """The routed experts' output reaches the streams one pass late (the
    next layer's, or the exit): a stack whose LAST layer is dense gives
    the reference's logits as the one that ends on an expert layer does
    (`tiny`)."""
    config = published(layers=2, layer_types=["mla"] * 2,
                       ffn_types=["moe", "dense"], hc_sinkhorn_iters=3)
    for ffns in (["moe", "dense"],):
        config["ffn_types"] = ffns
        pc = xing.XingConfig(**children.program_config_kwargs(config))
        params = with_a_selection_bias(
            xing.init_params(jax.random.PRNGKey(2), pc))
        state = jax.jit(lambda p, ids, pc=pc: xing.prefill(
            p, pc, ids, max_decode_len=2, row_block=32))(
                params, tiny["ids"][2:6])
        want = tiny["reference"].forward(
            params, config, [tiny["ids"][r, :LENGTHS[r]] for r in (2, 3, 4, 5)],
            [[LENGTHS[r] - 1] for r in (2, 3, 4, 5)])
        np.testing.assert_allclose(state["logits"], np.concatenate(want),
                                   atol=ATOL)


# -- the expert layer's shares --------------------------------------------------


def test_the_eight_shares_add_up_to_the_uncut_layer(tiny):
    """One layer's experts, cut eight ways as the deployment cuts them
    (every share runs the router over ALL 64 experts, top 4): the shares'
    routed parts, with the shared expert counted ONCE, are the
    reference's whole layer with every expert held."""
    reference = tiny["reference"]
    whole = published(n_routed_experts=64, num_experts_per_tok=4)
    whole["deployment"] = dict(whole["deployment"], expert_offset=0)
    keys = jax.random.split(jax.random.PRNGKey(11), 6)
    layer = {"moe": {
        "router": jax.random.normal(keys[0], (64, 64)) * 0.125,
        "bias": jax.random.normal(keys[1], (64,)) * 0.1,
        "w_in": jax.random.normal(keys[2], (64, 64, 64)) * 0.125,
        "w_out": jax.random.normal(keys[3], (64, 32, 64)) * 0.5},
        "shared": tiny["params"]["layers"][1]["shared"]}
    u = jax.random.normal(keys[4], (40, 64))
    with jax.default_matmul_precision("highest"):
        want = reference._feed_forward(whole, layer, u)
        parts = []
        for share in range(8):
            held = moe.HeldExperts(
                layer["moe"]["router"], layer["moe"]["bias"],
                layer["moe"]["w_in"][8 * share:8 * share + 8],
                layer["moe"]["w_out"][8 * share:8 * share + 8])
            parts.append(moe.held_experts_ffn(
                held, u, top_k=4, experts_held=8, expert_offset=8 * share,
                routing="sigmoid", scale=2.0)[0])
        shared = xing._feed_forward(layer, u)
    np.testing.assert_allclose(sum(parts) + shared, want, atol=2e-5)
    # every share gives something, and no share gives it all
    assert all(float(jnp.max(jnp.abs(p))) > 0 for p in parts)
    assert float(jnp.max(jnp.abs(want - shared - parts[0]))) > 0.01


def test_the_seeded_routers_read_channels_that_no_branch_writes(tiny):
    """`init_params`: every out-projection leaves the leading channels of
    the streams alone, the routers read those alone and the seeded bias
    is zero, so a router's order is the token's own in any precision: the
    bfloat16 program's held pairs are the float32 program's, example by
    example, prefill and decode."""
    pc = tiny["program_config"]
    params = xing.init_params(jax.random.PRNGKey(7), pc)
    quiet = min(xing.ROUTER_CHANNELS, pc.hidden_size // 4)
    for layer in params["layers"]:
        outs = [layer["mla"]["out"]["kernel"]]
        outs += [layer["mlp"]["wo"]["kernel"]] if "mlp" in layer else [
            layer["moe"]["w_out"], layer["shared"]["w_out"]]
        for kernel in outs:
            assert not np.any(np.asarray(kernel)[..., :quiet])
            assert np.any(np.asarray(kernel)[..., quiet:])
        if "moe" in layer:
            router = np.asarray(layer["moe"]["router"])
            assert not np.any(router[quiet:]) and np.all(router[:quiet])
            assert not np.any(np.asarray(layer["moe"]["bias"]))
    pc = quick(pc)
    half = dataclasses.replace(pc, dtype="bfloat16")
    small = ("router", "phi", "alpha", "bias", "scale")
    rounded = jax.tree_util.tree_map_with_path(
        lambda path, x: x if any(name in jax.tree_util.keystr(path)
                                 for name in small)
        else x.astype(jnp.bfloat16), params)
    same_weights = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32), rounded)
    counts = []
    for config, weights in ((pc, same_weights), (half, rounded)):
        state = jax.jit(lambda p, ids, config=config: xing.prefill(
            p, config, ids, max_decode_len=4, row_block=32))(
                weights, tiny["ids"])
        state = dict(state, logits=jnp.asarray(  # the same tokens in both
            np.eye(96, dtype=np.float32)[tiny["ids"][:, 0] % 96]))
        state, _ = jax.jit(lambda p, s, config=config: xing.step(
            p, config, s))(weights, state)
        counts.append((np.asarray(state["counts"]["held_prefill"]),
                       np.asarray(state["counts"]["held_decode"])))
    assert np.array_equal(counts[0][0], counts[1][0])
    assert np.array_equal(counts[0][1], counts[1][1])
    assert counts[0][0].sum() > 0 and counts[0][1].sum() > 0


# -- the file's count and the program's ----------------------------------------


def test_the_program_holds_the_parameters_the_file_counts():
    sizes = json.loads(
        (ROOT / "perfbench/configs/xing4.0-29b-a4b.json").read_text())
    config = xing.XingConfig(**children.program_config_kwargs(sizes))
    shapes = jax.eval_shape(
        lambda: xing.init_params(jax.random.PRNGKey(0), config))
    count = lambda tree: sum(  # noqa: E731
        int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(tree))
    assert count(shapes) == sizes["deployment"]["parameters_held"] \
        == 2685741816
    dense, expert = shapes["layers"][0], shapes["layers"][1]
    assert count(dense["mla"]) == 28411136
    assert count(dense["attn_hc"]) + count(dense["ffn_hc"]) == 688182
    assert count(dense["mlp"]) == 99090432
    assert count(dense) == 128196918 and count(expert) == 128426358
    assert count(expert["shared"]) * 8 == count(expert["moe"]["w_in"]) \
        + count(expert["moe"]["w_out"]) == 8 * 11010048
    # the latent caches at batch 32: 20 layers of 2,176 rows of 576 values
    assert (config.num_layers * 32 * (2048 + 128) * config.latent_width * 2
            == 1604321280)
    # the six rope_* arguments repeat the published rope_scaling group
    yarn = sizes["rope_scaling"]
    assert (config.rope_factor, config.rope_original_positions,
            config.rope_beta_fast, config.rope_beta_slow, config.rope_mscale,
            config.rope_mscale_all_dim) == (
        yarn["factor"], yarn["original_max_position_embeddings"],
        yarn["beta_fast"], yarn["beta_slow"], yarn["mscale"],
        yarn["mscale_all_dim"])
    assert yarn["type"] == "yarn"
    assert config.attention_scale == pytest.approx(
        192 ** -0.5 * (0.1 * np.log(64) + 1) ** 2,
        rel=1e-6)


# -- serving -----------------------------------------------------------------


def test_an_answer_carries_its_route_its_latent_rows_and_its_streams(tiny):
    from min_tfs_client_tpu.models.packed import ROUTE_COLUMNS
    from min_tfs_client_tpu.observability import runtime, tracing

    pc = quick(tiny["program_config"])
    signature = xing.build_signatures(
        tiny["params"], pc, seq_len=SEQ, max_decode_len=8,
        batch_buckets=(12,))["serving_default"]
    signature.telemetry_label = "xing:1:serving_default"
    with tracing.request_trace("predict", model="xing",
                               signature="serving_default") as trace:
        out = signature.run({"input_ids": tiny["ids"]})
        signature.on_answer(signature, out)      # what the handlers do
    assert out["output_ids"].shape == (12, 8)
    assert out["first_logits"].shape == out["last_logits"].shape == (12, 96)
    assert out["route_counts"].shape == (12, len(ROUTE_COLUMNS))
    real = np.asarray(LENGTHS) > 0
    latent = out["latent_counts"]
    assert latent.shape == (12, len(xing.LATENT_COLUMNS))
    assert latent[:, 3].tolist() == (real * LAYERS * 8 * (SEQ + 8)).tolist()
    assert latent[:, 2].tolist() == (
        real * LAYERS * (8 * np.asarray(LENGTHS) + 8 * 9 // 2)).tolist()
    streams = out["stream_counts"]
    assert streams.shape == (12, len(xing.STREAM_COLUMNS))
    assert streams[:, 0].tolist() == list(LENGTHS)
    assert streams[:, 2].tolist() == (
        real * 2 * LAYERS * (np.asarray(LENGTHS) + 8)).tolist()
    assert np.array_equal(streams[:, 3], 3 * streams[:, 2])   # `quick`
    spans = {name: args for name, _, _, args in trace.spans}
    assert spans["generate/streams"] == {
        "prompt_tokens": sum(LENGTHS), "steps": 96,
        "stream_rows": int(streams[:, 2].sum()),
        "sinkhorn_rounds": 3 * int(streams[:, 2].sum())}
    assert spans["generate/latent"]["latent_rows_held"] \
        == spans["generate/latent"]["latent_rows_copied"] \
        == 9 * LAYERS * 8 * (SEQ + 8)
    assert spans["generate/route"]["prompt_tokens"] == sum(LENGTHS)
    # three expert layers of the four, top 2
    assert spans["generate/route"]["pairs_decode"] == 12 * 8 * 3 * 2
    assert "generate/streams" in tracing.STAGES
    snapshot = runtime.snapshot()
    counted = snapshot["streams"]["xing:1:serving_default"]
    assert counted["requests"] >= 1
    assert counted["sinkhorn_rounds"] == 3 * counted["stream_rows"] > 0
    latent_counted = snapshot["latent"]["xing:1:serving_default"]
    assert latent_counted["latent_rows_read"] \
        < latent_counted["latent_rows_copied"] \
        == latent_counted["latent_rows_held"]
    assert snapshot["route"]["xing:1:serving_default"]["requests"] >= 1


# -- whole generations through the step's kernel -----------------------------


@pytest.fixture(scope="module")
def answers(tiny):
    """A whole generation of 16 steps as an answer, through the jnp step
    and through the step's kernel (the caches hold 96 positions)."""
    return answers_through_both_bodies(
        xing, tiny["params"], quick(tiny["program_config"]), tiny["ids"],
        seq_len=SEQ, steps=STEPS, model="xing")


@pytest.mark.parametrize("row", [r for r, n in enumerate(LENGTHS) if n])
def test_a_generation_through_the_kernel_is_the_jnp_generation(answers, row):
    check_the_kernel_s_generation_is_the_jnp_one(answers, row, ATOL)


@pytest.mark.parametrize("form", ["jnp", "kernel"])
def test_an_answer_counts_the_cache_rows_its_steps_brought_in(answers, form):
    check_the_rows_an_answer_brought_in(
        answers, form, xing.LATENT_COLUMNS, LENGTHS,
        layers=LAYERS, seq_len=SEQ, steps=STEPS)


def test_the_family_exports_and_loads(tiny, tmp_path):
    from min_tfs_client_tpu.models import export

    assert "xing" in export.FAMILIES
    pc = quick(tiny["program_config"])
    version = export.export_servable(
        tmp_path / "xing", 1, "xing", dataclasses.asdict(pc), tiny["params"],
        signature_kwargs={"seq_len": SEQ, "max_decode_len": 4,
                          "batch_buckets": [4]})
    signature = export.load_signatures(version)["serving_default"]
    out = signature.run({"input_ids": tiny["ids"][:4]})
    direct = xing.build_signatures(
        tiny["params"], pc, seq_len=SEQ,
        max_decode_len=4, batch_buckets=(4,))["serving_default"].run(
            {"input_ids": tiny["ids"][:4]})
    assert np.array_equal(out["output_ids"], direct["output_ids"])
    np.testing.assert_allclose(out["first_logits"], direct["first_logits"],
                               atol=1e-6)


def test_a_config_says_what_it_cannot_run():
    with pytest.raises(ValueError, match="fewer entries"):
        xing.XingConfig(num_layers=4, ffn_types=("dense", "moe"))
    with pytest.raises(ValueError, match="unknown ffn_types"):
        xing.XingConfig(num_layers=2, ffn_types=("dense", "conv"))
    with pytest.raises(ValueError, match="outside the router"):
        xing.XingConfig(experts_held=8, expert_offset=60)
    with pytest.raises(ValueError, match="at least one stream"):
        xing.XingConfig(hc_mult=0)
    with pytest.raises(ValueError, match="not implemented"):
        xing.XingConfig(rope_mscale=0.7)
    config = xing.XingConfig()
    # the published pattern: two dense layers, an expert layer after
    assert config.ffn_types[:3] == ("dense", "dense", "moe")
    assert config.expert_layers == 38
    assert config.latent_width == 576 and config.qk_head_dim == 192
    assert config.maps_width == 24
    # a factor of 1 leaves the rotary and the scale plain
    plain = xing.XingConfig(rope_factor=1.0)
    assert plain.attention_scale == 192 ** -0.5
    np.testing.assert_allclose(
        plain.frequencies()(32), 1e4 ** (-np.arange(32) / 32), rtol=1e-6)
