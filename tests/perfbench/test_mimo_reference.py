"""The MiMo-V2.5 reference's `check` and `verify` at a small size on the
CPU (float32 stated, so the bars are tight): the program's own output
passes, and each fault fails at least one bar. Then the cell's new
readers on hand-written spans and a small trace."""

import json
import pathlib
import types

import numpy as np
import pytest

from perfbench import children, metrics

ROOT = pathlib.Path(__file__).resolve().parents[2]
SEQ, STEPS = 48, 24
BARS = {"logits_atol": 2e-3, "logits_rms_atol": 2e-4,
        "min_equal_generated_tokens": 0.75, "generated_logit_gap": 2e-3}


@pytest.fixture(scope="module")
def tiny():
    import jax

    from min_tfs_client_tpu.models import mimo

    config = json.loads(
        (ROOT / "perfbench/configs/mimo-v2.5.json").read_text())
    config.update(hidden_size=64, num_attention_heads=4, head_dim=24,
                  v_head_dim=16, num_key_value_heads=1,
                  swa_num_key_value_heads=2, sliding_window=8,
                  intermediate_size=128, moe_intermediate_size=32,
                  n_routed_experts=4, num_experts_per_tok=2, vocab_size=96,
                  correctness=dict(BARS))
    config["serve"]["config_kwargs"].update(
        n_routed_experts=16, dtype="float32", prefill_rows=4)
    config["serve"]["signature_kwargs"].update(
        seq_len=SEQ, max_decode_len=STEPS, batch_buckets=[8])
    reference = children.load_reference(config)
    reference.PROMPT_LENGTHS = (4, 7, 8, 9, 30, 17, 47, 48)
    program_config = mimo.MimoConfig(
        **children.program_config_kwargs(config))
    params = mimo.init_params(jax.random.PRNGKey(3), program_config)
    expected = reference.make_expected(params, config,
                                       np.random.default_rng(3))
    return {"config": config, "reference": reference, "params": params,
            "program_config": program_config, "expected": expected,
            "mimo": mimo}


def judge(tiny, params=None, **config_changes) -> dict:
    """`check` then `verify` on what a program with these parameters and
    this configuration serves; the reference keeps the sound ones."""
    import dataclasses

    mimo = tiny["mimo"]
    params = tiny["params"] if params is None else params
    program_config = dataclasses.replace(tiny["program_config"],
                                         **config_changes)
    signature = mimo.build_signatures(
        params, program_config, seq_len=SEQ, max_decode_len=STEPS,
        batch_buckets=(8,))["serving_default"]
    ctx = types.SimpleNamespace(
        config=tiny["config"], expected=tiny["expected"], deferred={},
        predict=lambda name, inputs: signature.run(inputs))
    found = tiny["reference"].check(ctx)
    later = tiny["reference"].verify(
        lambda prefix: tiny["params"][prefix], tiny["config"],
        tiny["expected"], ctx.deferred)
    found["ok"] = bool(found["ok"] and later.pop("ok"))
    found.update(later)
    json.dumps(found)
    return found


def test_the_programs_own_output_passes(tiny):
    found = judge(tiny)
    assert found["ok"], found
    assert found["first_logits_max_abs_diff"] < 5e-4
    assert found["last_logits_max_abs_diff"] < 5e-4
    assert found["first_logits_rms_diff"] < 5e-5
    assert found["last_logits_rms_diff"] < 5e-5
    assert found["generated_tokens_equal"] == 1.0
    assert found["generated_tokens_compared"] == 3 * STEPS
    assert len(found["last_logits_diff_by_row"]) == 3    # the cap's row too


def without_sinks(params):
    layers = [dict(layer, attn={k: v for k, v in layer["attn"].items()
                                if k != "sink"})
              for layer in params["layers"]]
    return dict(params, layers=layers)


def without_one_expert(params):
    import jax.numpy as jnp

    layers = []
    for layer in params["layers"]:
        if "moe" in layer:
            moe = dict(layer["moe"])
            moe["w_out"] = moe["w_out"].at[1].set(jnp.zeros_like(
                moe["w_out"][1]))
            layer = dict(layer, moe=moe)
        layers.append(layer)
    return dict(params, layers=layers)


def in_bfloat16(params):
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16) if x.ndim >= 2 else x, params)


@pytest.mark.parametrize("fault", [
    "dropped_sink", "value_scale", "window_off_by_one", "expert_left_out",
    "bfloat16_where_float32_is_stated"])
def test_each_fault_fails_at_least_one_bar(tiny, fault):
    found = {
        "dropped_sink": lambda: judge(tiny, without_sinks(tiny["params"])),
        "value_scale": lambda: judge(tiny, value_scale=1.0),
        "window_off_by_one": lambda: judge(tiny, window=9),
        "expert_left_out": lambda: judge(
            tiny, without_one_expert(tiny["params"])),
        "bfloat16_where_float32_is_stated": lambda: judge(
            tiny, in_bfloat16(tiny["params"])),
    }[fault]()
    assert not found["ok"], found


def test_the_control_in_bfloat16_throughout_comes_out_not_correct(tiny):
    """`below()`: the reference's own pass with the residual stream, the
    norms, the softmax and the router rounded to bfloat16, through
    `check`. No single logit need move far: with `logits_atol` out of
    the way it is the noise level that fails."""
    found = tiny["reference"].below(tiny["params"], tiny["config"],
                                    tiny["expected"])
    assert not found["ok"], found
    loose = dict(tiny["config"], correctness=dict(BARS, logits_atol=10.0))
    found = tiny["reference"].below(tiny["params"], loose, tiny["expected"])
    assert not found["ok"] and found["first_logits_max_abs_diff"] < 10.0
    assert found["first_logits_rms_diff"] > BARS["logits_rms_atol"]


def test_the_two_distances_on_hand_written_rows():
    """At the published configuration's own bars: the noise level is the
    MEDIAN over the rows (one flipped router choice lifts one row and
    passes), every row's largest difference is held."""
    config = json.loads(
        (ROOT / "perfbench/configs/mimo-v2.5.json").read_text())
    bar = config["correctness"]
    reference = children.load_reference(config)
    rng = np.random.default_rng(0)
    want = rng.normal(size=(8, 4096))
    noise = rng.normal(size=want.shape)
    sound = want + 0.6 * bar["logits_rms_atol"] * noise
    sound[2] = want[2] + 2.5 * bar["logits_rms_atol"] * noise[2]
    found, ok = reference._distances(sound, want, bar, "first_logits")
    assert ok, found
    assert found["first_logits_rms_diff"] == pytest.approx(
        0.6 * bar["logits_rms_atol"], rel=0.05)
    assert max(found["first_logits_rms_diff_by_row"]) \
        > bar["logits_rms_atol"]
    doubled = want + 1.4 * bar["logits_rms_atol"] * noise
    found, ok = reference._distances(doubled, want, bar, "first_logits")
    assert not ok and found["first_logits_max_abs_diff"] < bar["logits_atol"]
    moved = sound.copy()
    moved[5, 17] += 2 * bar["logits_atol"]
    found, ok = reference._distances(moved, want, bar, "first_logits")
    assert not ok and found["first_logits_rms_diff"] < bar["logits_rms_atol"]
    assert not reference._distances(np.full_like(want, np.nan), want, bar,
                                    "first_logits")[1]


# -- the cell's new readers ---------------------------------------------------


def route(prompt, held_prefill, held_decode, max_load=40, load_total=960):
    return {"prompt_tokens": prompt, "pairs_prefill": prompt * 48,
            "held_prefill": held_prefill, "pairs_decode": 128 * 48,
            "held_decode": held_decode, "max_load": max_load,
            "load_total": load_total}


def rider(batch_ts, args):
    return {"ts": 0.0, "dur": 1.0, "args": {}, "spans": [
        ("batching/execute", batch_ts, 600.0, {}),
        ("generate/route", batch_ts + 700.0, 0.0, args)]}


def run_of(requests, **kw):
    config = json.loads(
        (ROOT / "perfbench/configs/mimo-v2.5.json").read_text())
    peaks = json.loads((ROOT / "perfbench/peaks.json").read_text())
    base = dict(requests=requests, config=config, trace=None, capture=None,
                traffic={"signature": "serving_default"},
                peak=peaks["TPU v5 lite"],
                kernel=lambda name: metrics.load_file(
                    ROOT / "perfbench" / "kernels" / f"{name}.py"))
    base.update(kw)
    return types.SimpleNamespace(**base)


def test_expert_readers_on_hand_written_spans():
    requests = [rider(1000.0, route(100, 300, 384)),
                rider(1000.0, route(200, 600, 384)),
                rider(9000.0, route(64, 192, 384, max_load=30,
                                    load_total=1920))]
    run = run_of(requests)
    pairs = (100 + 200 + 64) * 48 + 3 * 128 * 48
    assert metrics.load("expert_held_share").read(run) == pytest.approx(
        100.0 * (300 + 600 + 192 + 3 * 384) / pairs)
    # 6 expert layers x 16 held experts = 96 cells: 40 / (960 / 96) = 4
    # in the first batch, 30 / (1920 / 96) = 1.5 in the second
    assert metrics.load("expert_load_max_over_mean").read(run) \
        == pytest.approx(2.75)


def test_flash_need_counts_unmasked_pairs_of_unpadded_tokens():
    kernel = metrics.load_file(ROOT / "perfbench/kernels/_flash_kernel.py")
    assert kernel.pairs(5) == 15 and kernel.pairs(5, 128) == 15
    assert kernel.pairs(130, 128) == 128 * 129 // 2 + 2 * 128
    flops, moved = kernel.ops_and_bytes(length=2048, heads=64, kv_heads=8,
                                        d_qk=192, d_v=128, window=128)
    assert flops == 2.0 * kernel.pairs(2048, 128) * 320 * 64
    assert moved == 2 * 2048 * (64 * 320 + 8 * 320)
    assert kernel.ops_and_bytes(length=0, heads=64, kv_heads=4, d_qk=192,
                                d_v=128) == (0.0, 0.0)


def test_trace_readers_on_a_small_trace():
    """Two whole programs of 2 s in the capture, 28 kernel calls each of
    5 ms: the share of the program, the roofline share of the calls and
    the whole step's share of the peak."""
    lengths = [640] * 32
    requests = [rider(1000.0, route(n, 0, 0)) for n in lengths]
    trace = {"modules": {"jit_generate_fn(123)": [2.0, 2.0]},
             "ops": {"_flash_kernel": [0.005] * 56}}
    run = run_of(requests, trace=trace)
    assert metrics.load("flash_share").read(run) == pytest.approx(
        100.0 * 56 * 0.005 / 4.0)
    flash = metrics.load("flash_roofline")
    least = flash.batch_least_s(run, lengths)
    assert flash.read(run) == pytest.approx(100.0 * 2 * least / 0.28)
    assert 0.0 < flash.read(run) < 100.0
    model = metrics.load_file(ROOT / "perfbench/kernels/generate.py")
    need = 32 * model.needed_flops(run.config, length=640, steps=128,
                                   held_pairs=0)
    assert metrics.load("generate_mfu").read(run) == pytest.approx(
        100.0 * need / (2.0 * 197e12))
    # by hand: a token's matrices are the attention projections of 2 full
    # and 5 window layers, layer 0's MLP and 6 routers
    d = 4096
    per_token = (2 * (2 * d * (64 * 192 + 4 * 320) + 2 * 64 * 128 * d)
                 + 5 * (2 * d * (64 * 192 + 8 * 320) + 2 * 64 * 128 * d)
                 + 2 * 3 * d * 16384 + 6 * 2 * d * 256)
    assert model.per_token_flops(run.config) == pytest.approx(per_token)


def test_a_padded_batch_needs_less_and_never_more_than_it_ran():
    model = metrics.load_file(ROOT / "perfbench/kernels/generate.py")
    config = json.loads(
        (ROOT / "perfbench/configs/mimo-v2.5.json").read_text())
    short = model.needed_flops(config, length=93, steps=128, held_pairs=10)
    full = model.needed_flops(config, length=2048, steps=128, held_pairs=10)
    assert 0 < short < full
    more = model.needed_flops(config, length=93, steps=128, held_pairs=11)
    assert more - short == pytest.approx(2.0 * 3 * 4096 * 2048)
