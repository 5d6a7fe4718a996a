"""The Ling-3.0-flash reference's `check` and `verify` at a small size on
the CPU (float32 stated, so the bars are tight): the program's own output
passes, and each fault of structure and the control in bfloat16 fail at
least one bar. Then the cell's new readers on hand-written spans and a
small trace, and the configuration's file against the catalog's row."""

import json
import pathlib
import types

import numpy as np
import pytest

from perfbench import children, metrics

ROOT = pathlib.Path(__file__).resolve().parents[2]
SEQ, STEPS = 80, 12
BARS = {"logits_atol": 2e-3, "logits_rms_atol": 2e-4,
        "min_equal_generated_tokens": 0.75, "generated_logit_gap": 2e-3}
CELL = "ling-3.0-flash.long-answers"
FAULTS = ("decay_left_out", "beta_one", "rope_score_dropped",
          "group_limit_dropped", "expert_left_out", "shared_expert_left_out",
          "head_gate_left_out")


def published() -> dict:
    return json.loads(
        (ROOT / "perfbench/configs/ling-3.0-flash.json").read_text())


@pytest.fixture(scope="module")
def tiny():
    import jax

    from min_tfs_client_tpu.models import ling_hybrid

    config = published()
    config.update(hidden_size=64, num_attention_heads=4, head_dim=16,
                  qk_nope_head_dim=16, qk_rope_head_dim=8, qk_head_dim=24,
                  v_head_dim=16, kv_lora_rank=32, intermediate_size=96,
                  moe_intermediate_size=32,
                  moe_shared_expert_intermediate_size=32, num_experts=8,
                  num_experts_per_tok=3, n_group=4, topk_group=2,
                  vocab_size=96, layers=4,
                  layer_types=["kda", "kda", "mla", "kda"],
                  ffn_types=["dense", "moe", "moe", "moe"],
                  correctness=dict(BARS))
    config["serve"]["config_kwargs"].update(
        num_experts=32, dtype="float32", prefill_rows=4, kda_chunk=32,
        expert_swiglu_limits=[0] * 4, shared_swiglu_limits=[0] * 4)
    config["serve"]["signature_kwargs"].update(
        seq_len=SEQ, max_decode_len=STEPS, batch_buckets=[12])
    reference = children.load_reference(config)
    # under, at and over the convolution's width and a chunk's edge
    reference.PROMPT_LENGTHS = (1, 3, 4, 31, 32, 33, 50, 79, 80)
    program_config = ling_hybrid.LingHybridConfig(
        **children.program_config_kwargs(config))
    params = ling_hybrid.init_params(jax.random.PRNGKey(3), program_config)
    expected = reference.make_expected(params, config,
                                       np.random.default_rng(3))
    return {"config": config, "reference": reference, "params": params,
            "program_config": program_config, "expected": expected,
            "program": ling_hybrid}


def test_the_programs_own_output_passes(tiny):
    signature = tiny["program"].build_signatures(
        tiny["params"], tiny["program_config"], seq_len=SEQ,
        max_decode_len=STEPS, batch_buckets=(12,))["serving_default"]
    ctx = types.SimpleNamespace(
        config=tiny["config"], expected=tiny["expected"], deferred={},
        predict=lambda name, inputs: signature.run(inputs))
    found = tiny["reference"].check(ctx)
    later = tiny["reference"].verify(
        lambda prefix: tiny["params"][prefix], tiny["config"],
        tiny["expected"], ctx.deferred)
    assert found["ok"] and later["ok"], (found, later)
    json.dumps({**found, **later})
    assert found["first_logits_max_abs_diff"] < 5e-5
    assert later["last_logits_max_abs_diff"] < 5e-5
    assert found["first_logits_rms_diff"] < 5e-6
    assert later["generated_tokens_equal"] == 1.0
    assert later["generated_tokens_compared"] == 3 * STEPS
    assert len(found["first_logits_diff_by_row"]) == 9
    assert len(later["last_logits_diff_by_row"]) == 3    # the cap's row too


# -- faults and the precision below, made in the reference's own pass ---------


def test_the_reference_names_its_faults(tiny):
    assert tiny["reference"].FAULTS == FAULTS
    assert tiny["reference"].PROMPT_LENGTHS[-1] == SEQ
    fresh = children.load_reference(published())
    assert fresh.PROMPT_LENGTHS == (1, 3, 4, 63, 64, 65, 512, 2047, 2048)
    assert fresh.GENERATED == (1, 6, 8)       # 3, 512 and 2,048 tokens


@pytest.mark.parametrize("fault", FAULTS)
def test_each_fault_of_structure_fails_the_largest_difference(tiny, fault):
    found = tiny["reference"].control(tiny["params"], tiny["config"],
                                      tiny["expected"], fault=fault)
    assert not found["ok"], found
    assert found["first_logits_max_abs_diff"] > BARS["logits_atol"]


def test_the_sound_pass_through_control_is_correct(tiny):
    found = tiny["reference"].control(tiny["params"], tiny["config"],
                                      tiny["expected"])
    assert found["ok"] and found["first_logits_max_abs_diff"] == 0.0


def test_the_control_in_bfloat16_throughout_comes_out_not_correct(tiny):
    """The reference's own pass with the residual stream, the norms, g,
    exp(g), beta, the state and the router rounded to bfloat16, through
    `check`. With `logits_atol` out of the way it is the noise level
    that fails."""
    control = tiny["reference"].control
    found = control(tiny["params"], tiny["config"], tiny["expected"],
                    "below")
    assert not found["ok"], found
    loose = dict(tiny["config"], correctness=dict(BARS, logits_atol=10.0))
    found = control(tiny["params"], loose, tiny["expected"], "below")
    assert not found["ok"] and found["first_logits_max_abs_diff"] < 10.0
    assert found["first_logits_rms_diff"] > BARS["logits_rms_atol"]


def test_the_group_limit_is_what_the_reference_routes_by(tiny):
    """The reference's own router: a token's choices lie in topk_group
    groups, weights sum to the scaling factor; with the limit dropped
    some token reaches into a third group."""
    import jax

    reference, config = tiny["reference"], tiny["config"]
    layer = tiny["params"]["layers"][1]["moe"]
    u = jax.random.normal(jax.random.PRNGKey(5), (64, 64))
    chosen, weights = reference.route(config, layer, u)
    assert chosen.shape == (64, 3)
    np.testing.assert_allclose(weights.sum(-1), 2.5, rtol=1e-5)
    assert max(len(set((row // 8).tolist())) for row in chosen) <= 2
    free, _ = reference.route(config, layer, u, fault="group_limit_dropped")
    assert max(len(set((row // 8).tolist())) for row in free) == 3


def test_the_published_files_bars_lie_between_their_readings():
    """Each limit of `correctness` above what the program read on the
    chip and below what its control read (the readings are in
    `correctness.why` and PERF.md section 2)."""
    bar = published()["correctness"]
    assert 0 < bar["logits_rms_atol"] < bar["logits_atol"] < 1
    assert bar["min_equal_generated_tokens"] == 0.75
    assert len(bar["why"]) > 500


def test_the_file_holds_the_catalogs_row_and_lists_its_cuts():
    """Every number of the published config under the same key, but the
    three of `reduced`, each with its published value beside it; every
    reading the config does not define under `assumed`."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == "ling-3.0-flash")
    assert entry["reduced"] == ["layers", "num_experts", "vocab_size"]
    sizes = published()
    assert entry["source"] == sizes["source"]
    widths = {"hidden_size": 2560, "intermediate_size": 6144,
              "moe_intermediate_size": 768,
              "moe_shared_expert_intermediate_size": 768,
              "num_attention_heads": 32, "head_dim": 128,
              "qk_head_dim": 192, "qk_nope_head_dim": 128,
              "qk_rope_head_dim": 64, "v_head_dim": 128,
              "kv_lora_rank": 512, "num_experts_per_tok": 8, "n_group": 8,
              "topk_group": 4, "short_conv_kernel_size": 4,
              "num_hidden_layers": 42, "routed_scaling_factor": 2.5,
              "kda_lower_bound": -5, "rope_theta": 6000000}
    assert {k: sizes[k] for k in widths} == widths
    assert (sizes["layers"], sizes["num_experts"],
            sizes["vocab_size"]) == (7, 128, 39296)
    assert {k: sizes["published"][k] for k in entry["reduced"]} \
        == {"layers": 42, "num_experts": 512, "vocab_size": 157184}
    assert sizes["vocab_size"] * 4 == sizes["published"]["vocab_size"]
    assert len(sizes["expert_swiglu_limit_list"]) == 42
    assert sizes["published_layers"] == [0, 2, 3, 4, 5, 6, 7]
    # layer 0 and one whole period, 5 KDA layers to 1 MLA layer
    assert sizes["layer_types"] == ["kda"] * 4 + ["mla"] + ["kda"] * 2
    assert sizes["ffn_types"] == ["dense"] + ["moe"] * 6
    assert "head_dim is NOT aliased" in sizes["assumed"]["reader_aliases"]
    for reading in ("layer_pattern", "kda", "mla", "routing",
                    "swiglu_limits", "mtp", "decoding", "weights",
                    "precision", "reader_aliases"):
        assert len(sizes["assumed"][reading]) > 100
    assert "LEFT OUT" in sizes["assumed"]["mtp"]
    assert sizes["deployment"]["parameters_held"] == 5169366976


def test_the_program_holds_the_parameters_the_file_counts():
    import jax

    from min_tfs_client_tpu.models import ling_hybrid

    sizes = published()
    config = ling_hybrid.LingHybridConfig(
        **children.program_config_kwargs(sizes))
    shapes = jax.eval_shape(
        lambda: ling_hybrid.init_params(jax.random.PRNGKey(0), config))
    held = sum(int(np.prod(leaf.shape))
               for leaf in jax.tree_util.tree_leaves(shapes))
    assert held == sizes["deployment"]["parameters_held"]
    assert config.state_bytes == 6 * (4 * 32 * 128 * 128 + 2 * 3 * 12288)


# -- the cell's new readers ---------------------------------------------------


def rider(batch_ts, prompt, read, held=(0, 0)):
    route = {"prompt_tokens": prompt, "pairs_prefill": prompt * 48,
             "held_prefill": held[0], "pairs_decode": 256 * 48,
             "held_decode": held[1], "max_load": 40, "load_total": 7680,
             "prefill_rows": 512, "hit_decode": 76800}
    state = {"prompt_tokens": prompt, "scan_rows": -(-prompt // 64) * 64,
             "state_bytes": 13025280, "steps": 256}
    latent = {"prompt_tokens": prompt, "steps": 256,
              "latent_rows_read": read, "latent_rows_held": 256 * 2304}
    return {"ts": 0.0, "dur": 1.0, "args": {}, "spans": [
        ("batching/execute", batch_ts, 600.0, {}),
        ("generate/route", batch_ts + 700.0, 0.0, route),
        ("generate/state", batch_ts + 700.0, 0.0, state),
        ("generate/latent", batch_ts + 700.0, 0.0, latent)]}


def run_of(requests, **kw):
    peaks = json.loads((ROOT / "perfbench/peaks.json").read_text())
    base = dict(requests=requests, config=published(), trace=None,
                capture=None, traffic={"signature": "serving_default"},
                peak=peaks["TPU v5 lite"],
                kernel=lambda name: metrics.load_file(
                    ROOT / "perfbench" / "kernels" / f"{name}.py"))
    base.update(kw)
    return types.SimpleNamespace(**base)


NEW = ("ling_generate_mfu", "kda_step_roofline", "kda_step_share",
       "mla_flash_roofline", "latent_read_share")


def test_latent_read_share_is_the_mean_over_batches():
    held = 256 * 2304
    requests = [rider(1000.0, 100, held // 4), rider(1000.0, 300, held // 2),
                rider(9000.0, 256, held)]
    assert metrics.load("latent_read_share").read(run_of(requests)) \
        == pytest.approx((100.0 * 0.375 + 100.0) / 2)
    # a program that does not say what its latent cache held (the parent,
    # another model) reads nothing, and nothing raises
    silent = [{"ts": 0.0, "dur": 1.0, "args": {}, "spans": [
        ("batching/execute", 1.0, 2.0, {})]}]
    for name in NEW:
        assert metrics.load(name).read(run_of(silent)) is None
    granite = json.loads(
        (ROOT / "perfbench/configs/granite-4.0-h-small.json").read_text())
    trace = {"modules": {"jit_generate_fn(1)": [2.0]},
             "ops": {"_flash_kernel": [0.001] * 8}}
    other = run_of(requests, config=granite, trace=trace)
    assert metrics.load("ling_generate_mfu").read(other) is None
    assert metrics.load("mla_flash_roofline").read(other) is None


def test_the_step_s_need_is_the_state_each_way_and_the_token_s_rows():
    step = metrics.load_file(ROOT / "perfbench/kernels/_kda_step_kernel.py")
    flops, moved = step.ops_and_bytes(heads=32, head_dim=128)
    assert moved == 8 * 32 * 128 * 128 + 4 * 32 * 128 * 6
    assert flops == 7.0 * 32 * 128 * 128
    # bound by the bytes: 4.3 MB at 819 GB/s is 5.2 us a row a layer
    assert moved / 819e9 > flops / 197e12


def test_trace_readers_on_a_small_trace():
    """Two whole programs of 3 s in the capture: 3,072 step calls of 0.2
    ms, 16 flash calls of 2 ms."""
    lengths = [512] * 20                       # 20 riders of 32 rows
    requests = [rider(1000.0, n, 256 * 640, held=(3000, 1500))
                for n in lengths]
    trace = {"modules": {"jit_generate_fn(123)": [3.0, 3.0]},
             "ops": {"_kda_step_kernel": [0.0002] * 3072,
                     "_flash_kernel": [0.002] * 16}}
    run = run_of(requests, trace=trace)
    assert metrics.load("kda_step_share").read(run) == pytest.approx(
        100.0 * 0.6144 / 6.0)
    model = metrics.load_file(ROOT / "perfbench/kernels/ling_generate.py")
    assert model.kda_shape(run.config) == {"heads": 32, "head_dim": 128}
    # the step: 20 real rows' states of 2.1 MB each way a call
    _, moved = run.kernel("_kda_step_kernel").ops_and_bytes(
        **model.kda_shape(run.config))
    got = metrics.load("kda_step_roofline").read(run)
    assert got == pytest.approx(100.0 * 20 * (moved / 819e9) / 0.0002)
    assert 0 < got < 100
    # the prefill's attention: 1 MLA layer of the batch's 20 x 512 real
    # tokens a program, 32 K/V heads of 192 | 128
    flash = metrics.load("mla_flash_roofline")
    least = flash.batch_least_s(run, lengths)
    flops, moved = run.kernel("_flash_kernel").ops_and_bytes(
        length=512, heads=32, kv_heads=32, d_qk=192, d_v=128)
    assert least == pytest.approx(20 * max(flops / 197e12, moved / 819e9))
    assert flash.read(run) == pytest.approx(100.0 * 2 * least / 0.032)
    assert 0 < flash.read(run) < 100
    need = 20 * model.needed_flops(run.config, length=512, steps=256,
                                   held_pairs=4500)
    assert metrics.load("ling_generate_mfu").read(run) == pytest.approx(
        100.0 * need / (3.0 * 197e12))
    assert 0 < metrics.load("ling_generate_mfu").read(run) < 100
    # the accepted readers read this cell's spans as they are
    assert metrics.load("flash_share").read(run) == pytest.approx(
        100.0 * 0.032 / 6.0)
    assert metrics.load("scan_real_share").read(run) == pytest.approx(100.0)
    assert metrics.load("expert_held_share").read(run) == pytest.approx(
        100.0 * 4500 / (768 * 48))
    assert metrics.load("expert_load_max_over_mean").read(run) \
        == pytest.approx(40 * 6 * 128 / 7680)
    # ... and one that the cell may not list (an accepted test pins its
    # cells) would read its spans all the same: trips a layer a step
    assert metrics.load("expert_decode_trips_mean").read(run) \
        == pytest.approx(76800 * 8 / (256 * 48))


def test_a_token_s_matrices_by_hand():
    model = metrics.load_file(ROOT / "perfbench/kernels/ling_generate.py")
    config = published()
    d = 2560
    kda = 2 * d * (4 * 4096 + 64) + 2 * 4 * 12288 + 2 * 4096 * d
    mla = (2 * d * (32 * 192 + 576 + 32) + 2 * 512 * 32 * 256
           + 2 * 32 * 128 * d)
    dense = 2 * 3 * d * 6144
    beside = 2 * d * 512 + 2 * 3 * d * 768
    assert model.per_token_flops(config) == pytest.approx(
        6 * kda + mla + dense + 6 * beside)
    short = model.needed_flops(config, length=74, steps=256, held_pairs=10)
    full = model.needed_flops(config, length=2048, steps=256, held_pairs=10)
    assert 0 < short < full
    more = model.needed_flops(config, length=74, steps=256, held_pairs=11)
    assert more - short == pytest.approx(2.0 * 3 * d * 768)
    # one more prompt token: its matrices, its recurrence in 6 layers, and
    # the pairs it adds in the one MLA layer
    longer = model.needed_flops(config, length=75, steps=256, held_pairs=10)
    assert longer - short == pytest.approx(
        model.per_token_flops(config) + 6 * 7.0 * 32 * 128 * 128
        + 2.0 * 320 * 32 * (75 + 256))


def test_the_cell_reports_what_the_issue_names():
    from perfbench import run

    spec = run.load_cell(CELL)
    assert spec["end_to_end"] == ["first_output_p50_ms", "setup_s"]
    for name in NEW + ("scan_real_share", "program_ms", "flash_share",
                       "expert_held_share", "expert_load_max_over_mean",
                       "device_idle", "batch_occupancy", "hbm_peak_gb",
                       "queue_wait_p50_ms", "idle_named"):
        assert name in spec["per_layer"]
    # the other models' own shares are not this model's; flash_roofline
    # reads `head_dim` as the query/key size, which is 128 here and not
    # MLA's 192; and four lists that accepted tests pin to their cells
    # stay as they are (CHANGES.md)
    for name in ("generate_mfu", "hybrid_generate_mfu", "flash_roofline",
                 "ssm_step_roofline", "ssd_roofline",
                 "prefill_packed_share", "expert_decode_trips_mean",
                 "gc_pause_share", "host_idle_named"):
        assert name not in spec["per_layer"]
    assert spec["cell"]["chips"] == 1
    mix = spec["traffic"]
    assert mix["kind"] == "open_loop" and mix.get("window_scale", 1) == 1
    grid = mix["input_length_grid"]
    assert len(grid) == 64 and min(grid) >= 32 and max(grid) <= 2048
    assert sorted(grid)[31:33] == [504, 520]            # median 512
    assert sum(n == 2048 for n in grid) == 3
    assert (mix["lead_in_s"], mix["timeout_s"],
            mix["generator"]["threads"]) == (4.0, 120, 160)
    # the rate's slots tile the lead-in and the window
    rate = mix["rate_per_s"]
    assert rate * 4.0 == round(rate * 4.0) and rate * 40 == round(rate * 40)
    sizes = spec["config"]
    assert sizes["serve"]["signature_kwargs"] == {
        "seq_len": 2048, "max_decode_len": 256, "batch_buckets": [32]}
    assert sizes["kernels"]["_kda_step_kernel"]["calls_per_program"] \
        == 6 * 256
    assert sizes["main_program"] == {"serving_default": "jit_generate_fn"}
