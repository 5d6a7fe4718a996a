"""The Granite-4.0-H-Small reference's `check` and `verify` at a small
size on the CPU (float32 stated, so the bars are tight): the program's
own output passes, and each fault of structure and the control in
bfloat16 fail at least one bar. Then the cell's new readers on
hand-written spans and a small trace."""

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
CELL = "granite-4.0-h-small.short-chat"


def published() -> dict:
    return json.loads(
        (ROOT / "perfbench/configs/granite-4.0-h-small.json").read_text())


@pytest.fixture(scope="module")
def tiny():
    import jax

    from min_tfs_client_tpu.models import granite_hybrid

    config = published()
    config.update(hidden_size=64, num_attention_heads=4,
                  num_key_value_heads=2, attention_multiplier=1 / 16,
                  mamba_n_heads=4, mamba_d_head=16, mamba_d_state=16,
                  mamba_chunk_size=16, intermediate_size=32,
                  shared_intermediate_size=64, num_local_experts=4,
                  num_experts_per_tok=2, vocab_size=96, layers=3,
                  layer_types=["mamba", "attention", "mamba"],
                  correctness=dict(BARS))
    config["serve"]["config_kwargs"].update(
        num_local_experts=16, dtype="float32", prefill_rows=4)
    config["serve"]["signature_kwargs"].update(
        seq_len=SEQ, max_decode_len=STEPS, batch_buckets=[12])
    reference = children.load_reference(config)
    # under, at and over the convolution's width and a chunk's edge
    reference.PROMPT_LENGTHS = (1, 3, 4, 15, 16, 17, 30, 47, 48)
    program_config = granite_hybrid.GraniteHybridConfig(
        **children.program_config_kwargs(config))
    params = granite_hybrid.init_params(jax.random.PRNGKey(3),
                                        program_config)
    expected = reference.make_expected(params, config,
                                       np.random.default_rng(3))
    return {"config": config, "reference": reference, "params": params,
            "program_config": program_config, "expected": expected,
            "program": granite_hybrid}


def judge(tiny) -> dict:
    """`check` then `verify` on what the program serves as it stands
    (a test may have patched a fault into it)."""
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
    found["ok"] = bool(found["ok"] and later.pop("ok"))
    found.update(later)
    json.dumps(found)
    return found


def test_the_programs_own_output_passes(tiny):
    found = judge(tiny)
    assert found["ok"], found
    assert found["first_logits_max_abs_diff"] < 5e-5
    assert found["last_logits_max_abs_diff"] < 5e-5
    assert found["first_logits_rms_diff"] < 5e-6
    assert found["generated_tokens_equal"] == 1.0
    assert found["generated_tokens_compared"] == 3 * STEPS
    assert len(found["first_logits_diff_by_row"]) == 9
    assert len(found["last_logits_diff_by_row"]) == 3    # the cap's row too


# -- the hand-over to decoding, broken in the program -------------------------


def state_after_the_padding(tiny, monkeypatch):
    """The scan runs on through the padding: no lengths, so no dt = 0."""
    ssm = tiny["program"].ssm
    sound = ssm.ssd
    monkeypatch.setattr(ssm, "ssd", lambda x, dt, a, b, c, d, lengths=None,
                        **kw: sound(x, dt, a, b, c, d, None, **kw))


def window_row_dropped(tiny, monkeypatch):
    """The window handed to decoding lacks its last row."""
    program = tiny["program"]
    sound = program.prefill

    def prefill(*args, **kw):
        state = sound(*args, **kw)
        state["caches"] = [
            dict(c, conv=c["conv"].at[:, -1].set(0)) if "conv" in c else c
            for c in state["caches"]]
        return state

    monkeypatch.setattr(program, "prefill", prefill)


@pytest.mark.parametrize("fault", [state_after_the_padding,
                                   window_row_dropped],
                         ids=lambda f: f.__name__)
def test_a_fault_of_the_hand_over_fails_in_decoding(tiny, monkeypatch,
                                                     fault):
    fault(tiny, monkeypatch)
    found = judge(tiny)
    assert not found["ok"], found
    # the prefill's own logits are sound: only decoding shows it
    assert found["first_logits_max_abs_diff"] < 5e-5
    assert found["last_logits_max_abs_diff"] > BARS["logits_atol"]


# -- faults and the precision below, made in the reference's own pass ---------


def test_the_reference_names_its_faults(tiny):
    assert tiny["reference"].FAULTS == (
        "dx_left_out", "residual_multiplier_one", "expert_left_out",
        "shared_expert_left_out")


@pytest.mark.parametrize("fault", ["dx_left_out", "residual_multiplier_one",
                                   "expert_left_out",
                                   "shared_expert_left_out"])
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
    """The reference's own pass with the residual stream, the norms, dt,
    the decays, the state and the router rounded to bfloat16, through
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


def test_the_published_files_bars_lie_between_their_readings():
    """Each limit of `correctness` above what the program read on the
    chip and below what its control read (the readings are in
    `correctness.why` and PERF.md section 2)."""
    bar = published()["correctness"]
    assert 0 < bar["logits_rms_atol"] < bar["logits_atol"] < 1
    assert bar["min_equal_generated_tokens"] == 0.75
    assert len(bar["why"]) > 500


# -- the cell's new readers ---------------------------------------------------


def rider(batch_ts, prompt, scan_rows, held=(0, 0)):
    route = {"prompt_tokens": prompt, "pairs_prefill": prompt * 100,
             "held_prefill": held[0], "pairs_decode": 128 * 100,
             "held_decode": held[1], "max_load": 40, "load_total": 960,
             "prefill_rows": 512, "hit_decode": 20000}
    state = {"prompt_tokens": prompt, "scan_rows": scan_rows,
             "state_bytes": 38204928, "steps": 128}
    return {"ts": 0.0, "dur": 1.0, "args": {}, "spans": [
        ("batching/execute", batch_ts, 600.0, {}),
        ("generate/route", batch_ts + 700.0, 0.0, route),
        ("generate/state", batch_ts + 700.0, 0.0, state)]}


def run_of(requests, **kw):
    peaks = json.loads((ROOT / "perfbench/peaks.json").read_text())
    base = dict(requests=requests, config=published(), trace=None,
                capture=None, traffic={"signature": "serving_default"},
                peak=peaks["TPU v5 lite"],
                kernel=lambda name: metrics.load_file(
                    ROOT / "perfbench" / "kernels" / f"{name}.py"))
    base.update(kw)
    return types.SimpleNamespace(**base)


def test_scan_real_share_is_the_mean_over_batches():
    requests = [rider(1000.0, 100, 256), rider(1000.0, 300, 512),
                rider(9000.0, 256, 256)]
    assert metrics.load("scan_real_share").read(run_of(requests)) \
        == pytest.approx((100.0 * 400 / 768 + 100.0) / 2)
    # a program that does not say what its scan ran reads nothing
    silent = [{"ts": 0.0, "dur": 1.0, "args": {}, "spans": [
        ("batching/execute", 1.0, 2.0, {})]}]
    for name in ("scan_real_share", "ssd_roofline", "ssm_step_roofline",
                 "hybrid_generate_mfu", "ssd_share", "ssm_step_share"):
        assert metrics.load(name).read(run_of(silent)) is None


def test_the_scan_s_need_counts_real_rows_chunk_by_chunk():
    kernel = metrics.load_file(ROOT / "perfbench/kernels/_ssd_kernel.py")
    shape = dict(heads=128, head_dim=64, state=128, chunk=256)
    assert kernel.chunk_rows(600, 256) == [256, 256, 88]
    assert kernel.ops_and_bytes(length=0, **shape) == (0.0, 0.0)
    flops, moved = kernel.ops_and_bytes(length=256, **shape)
    pairs = 256 * 257 // 2
    assert flops == 2.0 * pairs * (128 + 8192) + 2.0 * 256 * 128 * 8192
    assert moved == 256 * (4 * 8192 + 4 * 128 + 8 * 128) + 4 * 128 * 8192
    more, _ = kernel.ops_and_bytes(length=257, **shape)
    # the 257th token: one pair, into the state, and the state into it
    assert more - flops == 2.0 * (128 + 8192) + 4.0 * 128 * 8192
    step = metrics.load_file(ROOT / "perfbench/kernels/_ssm_step_kernel.py")
    flops, moved = step.ops_and_bytes(heads=128, head_dim=64, state=128)
    assert moved == 8 * 128 * 8192 + 8192 * 6 + 4 * 128 + 4 * 128
    assert flops == 5.0 * 128 * 8192


def test_trace_readers_on_a_small_trace():
    """Two whole programs of 2 s in the capture: 144 scan calls of 1 ms,
    2,304 step calls of 0.5 ms, 16 flash calls."""
    lengths = [352] * 24                       # 24 riders of 32 rows
    requests = [rider(1000.0, n, 512, held=(900, 320)) for n in lengths]
    trace = {"modules": {"jit_generate_fn(123)": [2.0, 2.0]},
             "ops": {"_ssd_kernel": [0.001] * 144,
                     "_ssm_step_kernel": [0.0005] * 2304,
                     "_flash_kernel": [0.0002] * 16}}
    run = run_of(requests, trace=trace)
    assert metrics.load("ssd_share").read(run) == pytest.approx(
        100.0 * 0.144 / 4.0)
    assert metrics.load("ssm_step_share").read(run) == pytest.approx(
        100.0 * 1.152 / 4.0)
    model = metrics.load_file(ROOT / "perfbench/kernels/hybrid_generate.py")
    shape = model.ssm_shape(run.config)
    # the step: 24 real states of 8.4 MB each way a call, by the bytes
    _, moved = run.kernel("_ssm_step_kernel").ops_and_bytes(**shape)
    assert metrics.load("ssm_step_roofline").read(run) == pytest.approx(
        100.0 * 24 * (moved / 819e9) / 0.0005)
    assert 0 < metrics.load("ssm_step_roofline").read(run) < 100
    # the scan: 9 layers of the batch's 24 x 352 real tokens a program
    ssd = metrics.load("ssd_roofline")
    least = ssd.batch_least_s(run, lengths)
    flops, moved = run.kernel("_ssd_kernel").ops_and_bytes(
        length=352, chunk=256, **shape)
    assert least == pytest.approx(9 * 24 * max(flops / 197e12,
                                               moved / 819e9))
    assert ssd.read(run) == pytest.approx(100.0 * 2 * least / 0.144)
    assert 0 < ssd.read(run) < 100
    need = 24 * model.needed_flops(run.config, length=352, steps=128,
                                   held_pairs=1220)
    assert metrics.load("hybrid_generate_mfu").read(run) == pytest.approx(
        100.0 * need / (2.0 * 197e12))
    # the accepted readers of the attention kernel read this file too
    assert metrics.load("flash_share").read(run) == pytest.approx(
        100.0 * 0.0032 / 4.0)
    assert 0 < metrics.load("flash_roofline").read(run) < 100
    # ... and two that the cell may not list (accepted tests pin their
    # cells) would read its spans all the same
    assert metrics.load("expert_decode_trips_mean").read(run) \
        == pytest.approx(20000 * 10 / 12800)
    assert metrics.load("prefill_packed_share").read(run) \
        == pytest.approx(100.0 * 24 * 352 / 512)


def test_a_token_s_matrices_by_hand():
    model = metrics.load_file(ROOT / "perfbench/kernels/hybrid_generate.py")
    config = published()
    d = 4096
    mamba = 2 * d * 16768 + 2 * 4 * 8448 + 2 * 8192 * d
    attention = 2 * d * (32 + 16) * 128 + 2 * 32 * 128 * d
    beside = 2 * d * 72 + 2 * 3 * d * 1536
    assert model.per_token_flops(config) == pytest.approx(
        9 * mamba + attention + 10 * beside)
    short = model.needed_flops(config, length=37, steps=128, held_pairs=10)
    full = model.needed_flops(config, length=2048, steps=128, held_pairs=10)
    assert 0 < short < full
    more = model.needed_flops(config, length=37, steps=128, held_pairs=11)
    assert more - short == pytest.approx(2.0 * 3 * d * 768)


def test_the_cell_reports_what_the_issue_names():
    from perfbench import run

    spec = run.load_cell(CELL)
    assert spec["end_to_end"] == ["first_output_p50_ms", "setup_s"]
    for name in ("hybrid_generate_mfu", "ssm_step_roofline",
                 "ssm_step_share", "ssd_roofline", "ssd_share",
                 "scan_real_share", "program_ms", "flash_roofline",
                 "flash_share", "expert_held_share",
                 "expert_load_max_over_mean", "device_idle"):
        assert name in spec["per_layer"]
    # MiMo's own share of the peak is not this model's; and four lists
    # that accepted tests pin to their cells stay as they are (CHANGES.md)
    for name in ("generate_mfu", "prefill_packed_share",
                 "expert_decode_trips_mean", "gc_pause_share",
                 "host_idle_named"):
        assert name not in spec["per_layer"]
    assert spec["cell"]["chips"] == 1
    mix = spec["traffic"]
    assert mix["kind"] == "open_loop" and mix.get("window_scale", 1) == 1
    grid = mix["input_length_grid"]
    assert len(grid) == 64 and min(grid) >= 32 and max(grid) <= 2048
    assert sorted(grid)[31:33] == [252, 260]            # median 256
    assert sum(n > 1024 for n in grid) == 3
    assert (mix["lead_in_s"], mix["timeout_s"],
            mix["generator"]["threads"]) == (4.0, 120, 160)
    sizes = spec["config"]
    assert (sizes["layers"], sizes["num_local_experts"],
            sizes["vocab_size"]) == (10, 18, 25088)
    assert sizes["published"]["num_local_experts"] == 72
    assert sizes["deployment"]["parameters_held"] == 2955758208
