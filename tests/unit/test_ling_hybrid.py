"""models/ling_hybrid.py at a small size on the CPU, seeded weights:
prefill then decoding through the delta-rule state, the convolution's
window and the latent cache against the plain reference's ONE forward
pass, at logits (the absorbed decode form against the decompressed one:
tests/unit/test_latent.py, with the latent forms' one home); the two
faults of the hand-over, made in the program, each caught; the
four shares of an expert layer adding up to the uncut layer; the spans
and counters of an answer, through the jnp step and through the step's
kernel (interpreted), whose generation is the jnp one's; the export
round trip; what a config refuses."""

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from min_tfs_client_tpu.models import ling_hybrid as lh
from min_tfs_client_tpu.parallel import moe
from perfbench import children
from tests.unit.test_latent import (
    answers_through_both_bodies,
    check_the_kernel_s_generation_is_the_jnp_one,
    check_the_rows_an_answer_brought_in,
)

ROOT = pathlib.Path(__file__).resolve().parents[2]
SEQ, STEPS, CHUNK = 80, 16, 32
# both sides of the convolution's width (4) and of a chunk's edge (32),
# the cap, and rows of length 0 that pad the batch
LENGTHS = (1, 3, 4, 5, 31, 32, 33, 80, 0, 0, 47, 0)
# float32 stated: the chunked form's products run at "highest" precision,
# the step is float32 elementwise, so the program and the reference part
# only by the order of float32 sums
ATOL = 3e-5


def published(**changes) -> dict:
    """The configuration's file at a small size, float32 stated."""
    config = json.loads(
        (ROOT / "perfbench/configs/ling-3.0-flash.json").read_text())
    config.update(hidden_size=64, num_attention_heads=4, head_dim=16,
                  qk_nope_head_dim=16, qk_rope_head_dim=8, qk_head_dim=24,
                  v_head_dim=16, kv_lora_rank=32, intermediate_size=96,
                  moe_intermediate_size=32,
                  moe_shared_expert_intermediate_size=32, num_experts=8,
                  num_experts_per_tok=3, n_group=4, topk_group=2,
                  vocab_size=96, layers=4,
                  layer_types=["kda", "kda", "mla", "kda"],
                  ffn_types=["dense", "moe", "moe", "moe"])
    config["serve"]["config_kwargs"].update(
        num_experts=32, dtype="float32", prefill_rows=4, kda_chunk=CHUNK,
        expert_swiglu_limits=[0] * 4, shared_swiglu_limits=[0] * 4)
    config.update(changes)
    return config


@pytest.fixture(scope="module")
def tiny():
    config = published()
    program_config = lh.LingHybridConfig(
        **children.program_config_kwargs(config))
    params = lh.init_params(jax.random.PRNGKey(7), program_config)
    rng = np.random.default_rng(7)
    ids = np.zeros((len(LENGTHS), SEQ), np.int32)
    for row, n in enumerate(LENGTHS):
        ids[row, :n] = rng.integers(2, config["vocab_size"], (n,))
    return {"config": config, "program_config": program_config,
            "params": params, "ids": ids,
            "reference": children.load_reference(config)}


def generate(tiny, prefill=None, step=None):
    """Prefill, then 15 steps through state, window and latent cache: the
    logits every token was chosen from, and the tokens."""
    pc, params = tiny["program_config"], tiny["params"]
    prefill = prefill or (lambda p, ids: lh.prefill(
        p, pc, ids, max_decode_len=STEPS, row_block=32))
    step = step or (lambda p, s: lh.step(p, pc, s))
    state = jax.jit(prefill)(params, tiny["ids"])
    step = jax.jit(step)
    logits, tokens = [np.asarray(state["logits"])], []
    for _ in range(STEPS - 1):
        state, token = step(params, state)
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
    state = lh.prefill(tiny["params"], pc, tiny["ids"],
                       max_decode_len=4, row_block=32)
    empty = np.asarray(LENGTHS) == 0
    for kind, cache in zip(pc.layer_types, state["caches"]):
        if kind == "kda":
            assert not np.any(np.asarray(cache["kda"])[empty])
            assert not np.any(np.asarray(cache["conv"])[empty])
        else:
            # 32 + 8 values, then zeros to whole lane tiles
            assert cache["latent"].shape == (12, 1, SEQ + 4, 128)
            assert not np.any(np.asarray(cache["latent"])[..., 32 + 8:])
    assert not np.any(np.asarray(state["logits"])[empty])
    # ... and a window shorter than 3 rows is zeros in front
    short = LENGTHS.index(1)
    window = np.asarray(state["caches"][0]["conv"])[short]
    assert not np.any(window[:2]) and np.any(window[2])
    counts = generated["state"]["counts"]
    assert np.asarray(counts["held_decode"])[empty].tolist() == [0, 0, 0]
    assert np.asarray(counts["latent_rows_read"])[empty].tolist() == [0] * 3
    # a real row's steps read the positions up to their own: a prompt of
    # n tokens and 15 steps read n + 1 .. n + 15
    real = np.asarray(LENGTHS)[~empty]
    assert np.asarray(counts["latent_rows_read"])[~empty].tolist() \
        == (15 * real + 15 * 16 // 2).tolist()
    assert set(np.asarray(counts["latent_rows_held"])[~empty].tolist()) \
        == {15 * (SEQ + STEPS)}


# -- the hand-over to decoding, broken in the program -------------------------


def state_after_the_padding(tiny, monkeypatch):
    """The delta rule runs on through the padding: no lengths, so no g =
    0 and beta = 0 behind an example's last token."""
    sound = lh.kda.kda_prefill
    monkeypatch.setattr(
        lh.kda, "kda_prefill", lambda q, k, v, g, beta, lengths=None, **kw:
        sound(q, k, v, g, beta, None, **kw))


def dropped(name):
    def fault(tiny, monkeypatch):
        # the window, or the latent cache, lacks each example's last row
        sound = lh.prefill

        def prefill(params, config, ids, **kw):
            state = sound(params, config, ids, **kw)
            last = jnp.maximum(state["length"] - 1, 0)
            each = jnp.arange(ids.shape[0])
            state["caches"] = [
                dict(c, conv=c["conv"].at[:, -1].set(0))
                if name == "window" and "conv" in c else
                dict(c, latent=c["latent"].at[each, 0, last].set(0))
                if name == "latent" and "latent" in c else c
                for c in state["caches"]]
            return state

        monkeypatch.setattr(lh, "prefill", prefill)
    fault.__name__ = f"{name}_row_dropped"
    return fault


@pytest.mark.parametrize("fault", [state_after_the_padding,
                                   dropped("window"), dropped("latent")],
                         ids=lambda f: f.__name__)
def test_a_fault_of_the_hand_over_fails_in_decoding(tiny, generated,
                                                     monkeypatch, fault):
    fault(tiny, monkeypatch)
    pc = tiny["program_config"]
    broken = generate(tiny, prefill=lambda p, ids: lh.prefill(
        p, pc, ids, max_decode_len=STEPS, row_block=32))
    # the prefill's own logits are sound: only decoding shows it
    np.testing.assert_allclose(broken["logits"][:, 0],
                               generated["logits"][:, 0], atol=ATOL)
    row = LENGTHS.index(33)                 # real tokens AND padding
    want = reference_logits(tiny, broken, row)
    assert np.max(np.abs(broken["logits"][row, 1:] - want[1:])) > 100 * ATOL


@pytest.mark.parametrize("form", ["jnp", "pallas"])
def test_the_prefill_through_the_dispatcher_is_the_prefill(tiny, form,
                                                           monkeypatch):
    """`kda.kda_prefill` in `kda_chunked`'s place. Off the TPU it IS
    `kda_chunked` (`jnp`: logits, states, windows and the rows the chunks
    ran, to the bit); `pallas`: the prefill through `_kda_chunk_kernel`
    (interpret mode) as on the chip: the same logits and states, and each
    example's OWN chunks run, not the group's longest."""
    pc, params = tiny["program_config"], tiny["params"]

    def prefill(through):
        monkeypatch.setattr(lh.kda, "kda_prefill", through)
        return jax.jit(lambda p, ids: lh.prefill(
            p, pc, ids, max_decode_len=4, row_block=32))(params, tiny["ids"])

    got = prefill(lh.kda.kda_prefill if form == "jnp" else (
        lambda *a, **kw: lh.kda.kda_chunk_kernel(*a, **kw, interpret=True)))
    want = prefill(lh.kda.kda_chunked)
    same = np.array_equal if form == "jnp" else (
        lambda a, b: np.allclose(a, b, atol=ATOL))
    assert same(got["logits"], want["logits"])
    assert np.std(np.asarray(want["logits"])) > 0.05
    for a, b in zip(got["caches"], want["caches"]):
        assert all(same(a[name], b[name]) for name in a)
    lengths = np.asarray(LENGTHS)
    longest = lengths.reshape(-1, pc.prefill_rows).max(1).repeat(
        pc.prefill_rows)
    ran = {"jnp": longest, "pallas": lengths}[form]
    assert np.asarray(got["counts"]["scan_rows"]).tolist() \
        == (-(-ran // CHUNK) * CHUNK).tolist()


@pytest.mark.parametrize("form", ["jnp", "pallas"])
def test_padding_rows_change_nothing_for_the_real_rows(tiny, form,
                                                       monkeypatch):
    """A whole generation of the batch with its rows of length 0 against
    the same prompts in a batch without them: tokens, first and last
    logits; and the states the steps held and moved. `pallas`: the step
    through `_kda_step_kernel` (interpret mode) as on the chip."""
    from min_tfs_client_tpu.servables.decode_signatures import (
        whole_generation,
    )

    if form == "pallas":
        monkeypatch.setattr(lh.kda, "kda_step", lambda *a, **kw:
                            lh.kda.kda_step_kernel(*a, **kw, interpret=True))
    pc, steps = tiny["program_config"], 6

    def run(ids):
        out = jax.jit(lambda params, ids: whole_generation(
            lambda p, i: lh.prefill(p, pc, i, max_decode_len=steps,
                                    row_block=32),
            lambda p, s: lh.step(p, pc, s), params, ids,
            max_decode_len=steps, pad_id=pc.pad_id))(tiny["params"], ids)
        return (np.asarray(out["output_ids"]),
                np.asarray(out["first"]["logits"]),
                np.asarray(out["before_last"]["logits"]),
                {k: np.asarray(v) for k, v in out["final"]["counts"].items()})

    real = np.nonzero(LENGTHS)[0]
    padded, alone = run(tiny["ids"]), run(tiny["ids"][real][:8])
    assert np.array_equal(padded[0][real[:8]], alone[0])
    np.testing.assert_allclose(padded[1][real[:8]], alone[1], atol=ATOL)
    np.testing.assert_allclose(padded[2][real[:8]], alone[2], atol=ATOL)
    kda_layers = pc.layer_types.count("kda")
    assert padded[3]["state_rows_held"] == 12 * kda_layers * steps
    assert padded[3]["state_rows_moved"] == 9 * kda_layers * steps
    assert alone[3]["state_rows_held"] == alone[3]["state_rows_moved"]


# -- the expert layer's shares --------------------------------------------------


def test_the_four_shares_add_up_to_the_uncut_layer(tiny):
    """One layer's experts, cut four ways as the deployment cuts them (a
    share is one of the 4 groups here; every share runs the group choice
    over ALL 32 experts): the shares' routed parts, with the shared
    expert counted ONCE, are the reference's whole layer with every
    expert held."""
    reference, pc = tiny["reference"], tiny["program_config"]
    whole = published(num_experts=32)
    whole["deployment"] = dict(whole["deployment"], expert_offset=0)
    layer = dict(tiny["params"]["layers"][1])
    keys = jax.random.split(jax.random.PRNGKey(11), 3)
    layer["moe"] = dict(
        layer["moe"],
        w_in=jax.random.normal(keys[0], (32, 64, 64)) * 0.125,
        w_out=jax.random.normal(keys[1], (32, 32, 64)) * 0.5)
    u = jax.random.normal(keys[2], (40, 64))
    with jax.default_matmul_precision("highest"):
        want = (reference._experts(whole, layer["moe"], u)
                + reference._swiglu(u, layer["shared"]["w_in"],
                                    layer["shared"]["w_out"]))
        parts = []
        for share in range(4):
            held = moe.HeldExperts(
                layer["moe"]["router"], layer["moe"]["bias"],
                layer["moe"]["w_in"][8 * share:8 * share + 8],
                layer["moe"]["w_out"][8 * share:8 * share + 8])
            parts.append(moe.held_experts_ffn(
                held, u, top_k=3, experts_held=8, expert_offset=8 * share,
                routing="sigmoid_grouped", n_group=pc.n_group,
                topk_group=pc.topk_group,
                scale=pc.routed_scaling_factor)[0])
        shared = lh._swiglu(layer["shared"]["w_in"],
                            layer["shared"]["w_out"], u)
    np.testing.assert_allclose(sum(parts) + shared, want, atol=2e-5)
    # every share gives something, and no share gives it all
    assert all(float(jnp.max(jnp.abs(p))) > 0 for p in parts)
    assert float(jnp.max(jnp.abs(want - shared - parts[0]))) > 0.01


def test_the_seeded_routers_read_channels_that_no_branch_writes(tiny):
    """`init_params`: every out-projection leaves the leading channels of
    the stream alone and the routers read those alone, so a router sees
    the token's embedding and its choice is the same in any precision:
    the bfloat16 program's held pairs are the float32 program's, example
    by example, prefill and decode."""
    import dataclasses

    pc, params = tiny["program_config"], tiny["params"]
    quiet = min(lh.ROUTER_CHANNELS, pc.hidden_size // 4)
    for layer in params["layers"]:
        outs = [layer[kind]["out"]["kernel"] for kind in ("kda", "mla")
                if kind in layer]
        outs += [layer["mlp"]["wo"]["kernel"]] if "mlp" in layer else [
            layer["moe"]["w_out"], layer["shared"]["w_out"]]
        for kernel in outs:
            assert not np.any(np.asarray(kernel)[..., :quiet])
            assert np.any(np.asarray(kernel)[..., quiet:])
        if "moe" in layer:
            router = np.asarray(layer["moe"]["router"])
            assert not np.any(router[quiet:]) and np.all(router[:quiet])
    half = dataclasses.replace(pc, dtype="bfloat16")
    rounded = jax.tree_util.tree_map_with_path(
        lambda path, x: x if x.ndim < 2 or "router" in jax.tree_util.keystr(
            path) else x.astype(jnp.bfloat16), params)
    counts = []
    for config, weights in ((pc, params), (half, rounded)):
        state = lh.prefill(weights, config, tiny["ids"], max_decode_len=4,
                           row_block=32)
        state = dict(state, logits=jnp.asarray(  # the same tokens in both
            np.eye(96, dtype=np.float32)[tiny["ids"][:, 0] % 96]))
        state, _ = lh.step(weights, config, state)
        counts.append((np.asarray(state["counts"]["held_prefill"]),
                       np.asarray(state["counts"]["held_decode"])))
    assert np.array_equal(counts[0][0], counts[1][0])
    assert np.array_equal(counts[0][1], counts[1][1])
    assert counts[0][0].sum() > 0 and counts[0][1].sum() > 0


# -- serving -----------------------------------------------------------------


def test_an_answer_carries_its_route_its_state_and_its_latent_rows(tiny):
    from min_tfs_client_tpu.models.packed import ROUTE_COLUMNS
    from min_tfs_client_tpu.observability import runtime, tracing

    pc = tiny["program_config"]
    signature = lh.build_signatures(
        tiny["params"], pc, seq_len=SEQ, max_decode_len=8,
        batch_buckets=(12,))["serving_default"]
    signature.telemetry_label = "ling:1:serving_default"
    with tracing.request_trace("predict", model="ling",
                               signature="serving_default") as trace:
        out = signature.run({"input_ids": tiny["ids"]})
        signature.on_answer(signature, out)      # what the handlers do
    assert out["output_ids"].shape == (12, 8)
    assert out["first_logits"].shape == out["last_logits"].shape == (12, 96)
    assert out["route_counts"].shape == (12, len(ROUTE_COLUMNS))
    rows = out["state_counts"]
    assert rows[:, 0].tolist() == list(LENGTHS)
    per_sequence = pc.state_bytes
    assert per_sequence == 3 * (4 * 4 * 16 * 16 + 4 * 3 * 3 * 64)
    assert set(rows[:, 2].tolist()) == {per_sequence}
    assert set(rows[:, 3].tolist()) == {8}
    # the batch's figures on every row: 12 rows, 9 of them real, through
    # 3 KDA layers and 8 steps
    assert set(rows[:, 4].tolist()) == {12 * 3 * 8}
    assert set(rows[:, 5].tolist()) == {9 * 3 * 8}
    latent = out["latent_counts"]
    assert latent.shape == (12, len(lh.LATENT_COLUMNS))
    real = np.asarray(LENGTHS) > 0
    assert latent[:, 3].tolist() == (real * 8 * (SEQ + 8)).tolist()
    assert latent[:, 2].tolist() == (
        real * (8 * np.asarray(LENGTHS) + 8 * 9 // 2)).tolist()
    spans = {name: args for name, _, _, args in trace.spans}
    assert spans["generate/latent"] == {
        "prompt_tokens": sum(LENGTHS), "steps": 96,
        "latent_rows_read": int(latent[:, 2].sum()),
        "latent_rows_held": 9 * 8 * (SEQ + 8),
        # the jnp step brings in every row it holds
        "latent_rows_copied": 9 * 8 * (SEQ + 8)}
    assert spans["generate/state"]["state_rows_moved"] == 9 * 3 * 8
    assert spans["generate/state"]["scan_rows"] == int(rows[:, 1].sum())
    assert spans["generate/route"]["prompt_tokens"] == sum(LENGTHS)
    # three expert layers of the four, top 3
    assert spans["generate/route"]["pairs_decode"] == 12 * 8 * 3 * 3
    assert "generate/latent" in tracing.STAGES
    snapshot = runtime.snapshot()
    counted = snapshot["latent"]["ling:1:serving_default"]
    assert counted["requests"] >= 1
    assert counted["latent_rows_read"] < counted["latent_rows_copied"] \
        == counted["latent_rows_held"]
    assert snapshot["state"]["ling:1:serving_default"]["steps"] >= 96
    assert snapshot["route"]["ling:1:serving_default"]["requests"] >= 1


# -- whole generations through the step's kernel -----------------------------


@pytest.fixture(scope="module")
def answers(tiny):
    """A whole generation of 16 steps as an answer, through the jnp step
    and through the step's kernel (the caches hold 96 positions)."""
    return answers_through_both_bodies(
        lh, tiny["params"], tiny["program_config"], tiny["ids"],
        seq_len=SEQ, steps=STEPS, model="ling")


@pytest.mark.parametrize("row", [r for r, n in enumerate(LENGTHS) if n])
def test_a_generation_through_the_kernel_is_the_jnp_generation(answers, row):
    check_the_kernel_s_generation_is_the_jnp_one(answers, row, ATOL)


@pytest.mark.parametrize("form", ["jnp", "kernel"])
def test_an_answer_counts_the_cache_rows_its_steps_brought_in(answers, form):
    check_the_rows_an_answer_brought_in(
        answers, form, lh.LATENT_COLUMNS, LENGTHS,
        layers=1, seq_len=SEQ, steps=STEPS)


def test_the_family_exports_and_loads(tiny, tmp_path):
    import dataclasses

    from min_tfs_client_tpu.models import export

    assert "ling_hybrid" in export.FAMILIES
    version = export.export_servable(
        tmp_path / "ling", 1, "ling_hybrid",
        dataclasses.asdict(tiny["program_config"]), tiny["params"],
        signature_kwargs={"seq_len": SEQ, "max_decode_len": 4,
                          "batch_buckets": [4]})
    signature = export.load_signatures(version)["serving_default"]
    out = signature.run({"input_ids": tiny["ids"][:4]})
    direct = lh.build_signatures(
        tiny["params"], tiny["program_config"], seq_len=SEQ,
        max_decode_len=4, batch_buckets=(4,))["serving_default"].run(
            {"input_ids": tiny["ids"][:4]})
    assert np.array_equal(out["output_ids"], direct["output_ids"])
    np.testing.assert_allclose(out["first_logits"], direct["first_logits"],
                               atol=1e-6)


def test_a_config_says_what_it_cannot_run():
    with pytest.raises(ValueError, match="fewer entries"):
        lh.LingHybridConfig(num_layers=4, layer_types=("kda", "mla"))
    with pytest.raises(ValueError, match="unknown layer_types"):
        lh.LingHybridConfig(num_layers=2, layer_types=("kda", "window"))
    with pytest.raises(ValueError, match="outside the router"):
        lh.LingHybridConfig(experts_held=128, expert_offset=448)
    # the clamp of the late layers' SwiGLU is refused, not guessed
    with pytest.raises(ValueError, match="clamped SwiGLU"):
        lh.LingHybridConfig(num_layers=2, expert_swiglu_limits=(0, 4))
    with pytest.raises(ValueError, match="clamped SwiGLU"):
        lh.LingHybridConfig(num_layers=2, shared_swiglu_limits=(5, 0))
    config = lh.LingHybridConfig()
    # the published pattern: MLA where (i + 1) % 6 == 0, two dense layers
    assert config.layer_types.count("mla") == 7
    assert [i for i, kind in enumerate(config.layer_types)
            if kind == "mla"][:2] == [5, 11]
    assert config.ffn_types[:3] == ("dense", "dense", "moe")
    assert config.latent_width == 576 and config.qk_head_dim == 192
    assert config.state_bytes == 35 * (4 * 32 * 128 * 128 + 2 * 3 * 12288)


def test_the_file_s_limit_lists_are_the_published_lists_entries():
    config = json.loads(
        (ROOT / "perfbench/configs/ling-3.0-flash.json").read_text())
    kwargs = children.program_config_kwargs(config)
    kept = config["published_layers"]
    assert kwargs["expert_swiglu_limits"] \
        == [config["expert_swiglu_limit_list"][i] for i in kept]
    assert kwargs["shared_swiglu_limits"] \
        == [config["share_expert_swiglu_limit_list"][i] for i in kept]
    assert kwargs["layer_types"] == [
        "mla" if (i + 1) % config["layer_group_size"] == 0 else "kda"
        for i in kept]
    assert kwargs["ffn_types"] == [
        "dense" if i < config["first_k_dense_replace"] else "moe"
        for i in kept]
    # a late layer's nonzero limit would be refused
    late = dict(kwargs, expert_swiglu_limits=[
        config["expert_swiglu_limit_list"][i] for i in range(35, 42)])
    with pytest.raises(ValueError, match="clamped SwiGLU"):
        lh.LingHybridConfig(**late)


def test_no_name_comes_from_another_model_s_file():
    source = (ROOT / "min_tfs_client_tpu/models/ling_hybrid.py").read_text()
    assert "models.mimo" not in source and "import mimo" not in source
    assert "granite_hybrid import" not in source
    assert "import granite_hybrid" not in source
