"""`t5_generate_mfu` and the count behind it (kernels/t5_generate.py) on
hand-written request traces and a toy device trace."""

import json
import pathlib
import types

import pytest

from perfbench import metrics

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIG = json.loads((ROOT / "perfbench/configs/t5-large.json").read_text())
TRAFFIC = json.loads((ROOT / "perfbench/traffic/generate.json").read_text())
PEAK = json.loads((ROOT / "perfbench/peaks.json").read_text())["TPU v5 lite"]
COUNT = metrics.load_file(ROOT / "perfbench/kernels/t5_generate.py")

# a toy of the same kind, small enough to count by hand
TOY = {"d_model": 8, "d_kv": 2, "num_heads": 4, "d_ff": 16, "num_layers": 3,
       "vocab_size": 10, "assumed": {"num_decoder_layers": 2}}


def by_hand(n, steps):
    projection = 2 * 8 * (4 * 2)              # one d_model x (h * d_kv)
    dense = 2 * 2 * 8 * 16
    pair = 2 * 2 * 2 * 4                      # score + weighted sum, 4 heads
    encoder = 3 * (n * (4 * projection + dense) + pair * n * n)
    cross_kv = 2 * n * 2 * projection
    decoder = steps * (2 * (6 * projection + dense) + 2 * 8 * 10)
    pairs = 2 * pair * (sum(t + 1 for t in range(steps)) + steps * n)
    return encoder + cross_kv + decoder + pairs


@pytest.mark.parametrize("n, steps", [(0, 4), (1, 1), (5, 4), (12, 7)])
def test_the_count_is_the_sum_of_its_parts(n, steps):
    assert COUNT.needed_flops(TOY, input_tokens=n, steps=steps) \
        == pytest.approx(by_hand(n, steps))


def test_the_count_at_the_published_widths():
    """T5-large, 278 input tokens (the traffic's mean), 256 steps: 411
    GFLOP, of which the decoder's matrices and head are nearly half."""
    got = COUNT.needed_flops(CONFIG, input_tokens=278, steps=256)
    assert got == pytest.approx(410.95e9, rel=1e-4)
    # an input of nothing still decodes: the steps' matrices, the head
    # and the causal triangle
    assert COUNT.needed_flops(CONFIG, input_tokens=0, steps=256) \
        == pytest.approx(200.6e9, rel=1e-3)


def rider(batch_ts, tokens, noted=True):
    spans = [("batching/execute", batch_ts, 600.0, {})]
    if noted:
        spans.insert(0, ("generate/cross", batch_ts - 300.0, 0.0, {
            "input_tokens": tokens, "blocks_read": -(-tokens // 128),
            "blocks_held": 4}))
    return {"ts": 0.0, "dur": 1.0, "args": {}, "spans": spans}


def trace_of(*seconds):
    """A reduced device trace whose "XLA Modules" line holds these runs
    of the cell's main program (and a tick's, which is not it)."""
    return {"modules": {"jit_decode_fn(123)": list(seconds),
                        "jit_direct_tick_fn(7)": [0.026]}}


def run_of(requests, trace):
    run = types.SimpleNamespace(
        requests=requests, config=CONFIG, traffic=TRAFFIC, peak=PEAK,
        trace=trace)
    run.kernel = lambda name: metrics.load_file(
        ROOT / "perfbench/kernels" / f"{name}.py")
    return run


def need(*tokens):
    return sum(COUNT.needed_flops(CONFIG, input_tokens=n, steps=256)
               for n in tokens)


@pytest.mark.parametrize("requests, trace, want", [
    # one batch of three riders in a program of 1.4 s
    ([rider(1000.0, 76), rider(1000.0, 256), rider(1000.0, 512)],
     trace_of(1.4), 100.0 * need(76, 256, 512) / (1.4 * 197e12)),
    # two batches: the mean batch's need over the median program
    ([rider(1000.0, 129)] + [rider(9000.0, 300)] * 3,
     trace_of(1.3, 1.5, 1.4),
     100.0 * (need(129) + 3 * need(300)) / 2 / (1.4 * 197e12)),
    # no span (the parent), no capture, or no run of the program: nothing
    ([rider(1000.0, 300, noted=False)], trace_of(1.4), None),
    ([rider(1000.0, 300)], None, None),
    ([rider(1000.0, 300)], {"modules": {"jit_direct_tick_fn(7)": [0.026]}},
     None),
])
def test_needed_flops_over_the_programs_time_at_the_peak(requests, trace,
                                                        want):
    got = metrics.load("t5_generate_mfu").read(run_of(requests, trace))
    assert got == (None if want is None else pytest.approx(want))


def test_a_full_batch_at_the_traffics_mean_reads_a_few_percent():
    """32 riders of 278 tokens in 1.35 s: 4.9% of the peak; the cell's 21
    riders a batch 3.2%: inside the 1-10% the metric is held to."""
    run = run_of([rider(1000.0, 278)] * 32, trace_of(1.35))
    assert metrics.load("t5_generate_mfu").read(run) \
        == pytest.approx(4.94, abs=0.01)
    run = run_of([rider(1000.0, 278)] * 21, trace_of(1.35))
    assert metrics.load("t5_generate_mfu").read(run) \
        == pytest.approx(3.24, abs=0.01)


def test_the_benchmark_lists_it_for_the_generate_cell_only():
    (entry,) = [m for m in BENCH["per_layer"]
                if m["name"] == "t5_generate_mfu"]
    assert entry == {"name": "t5_generate_mfu", "unit": "%",
                     "better": "higher", "source": "device_trace",
                     "layer": "models", "moves": "first_output_p50_ms",
                     "workloads": ["t5-large.generate"]}
    names = [m["name"] for m in BENCH["per_layer"]]
    # appended behind what was there, nothing moved
    assert names.index("t5_generate_mfu") \
        == names.index("expert_decode_trips_mean") + 1


def test_a_line_leaves_it_out_where_there_is_nothing_to_read():
    run = run_of([rider(1000.0, 300, noted=False)], trace_of(1.4))
    assert metrics.read_all(["t5_generate_mfu"], run, BENCH) == {}
    run = run_of([rider(1000.0, 300)], trace_of(1.4))
    (found,) = metrics.read_all(["t5_generate_mfu"], run, BENCH).values()
    assert found["unit"] == "%" and 0.1 < found["value"] < 0.2
