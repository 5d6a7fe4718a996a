"""`expert_decode_trips_mean` on hand-written request traces."""

import json
import pathlib
import types

import pytest

from perfbench import metrics

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIG = {"num_experts_per_tok": 8}
STEPS, LAYERS = 128, 6


def rider(batch_ts, hit_decode=None, steps=STEPS):
    """One request that rode the batch executed at `batch_ts`."""
    args = {"prompt_tokens": 300, "pairs_prefill": 300 * 48,
            "held_prefill": 900, "pairs_decode": steps * 8 * LAYERS,
            "held_decode": 384, "max_load": 40, "load_total": 960,
            "prefill_rows": 512}
    if hit_decode is not None:
        args["hit_decode"] = hit_decode
    return {"ts": 0.0, "dur": 1.0, "args": {}, "spans": [
        ("batching/execute", batch_ts, 600.0, {}),
        ("generate/route", batch_ts + 700.0, 0.0, args)]}


def run_of(requests):
    return types.SimpleNamespace(requests=requests, config=CONFIG)


@pytest.mark.parametrize("requests, want", [
    # one batch of two riders: 5.25 experts a layer a step, the batch's
    # figure once and not once a rider
    ([rider(1000.0, 4032), rider(1000.0, 4032)],
     4032 / (STEPS * LAYERS)),
    # two batches: the mean of the batches' means
    ([rider(1000.0, 3072), rider(9000.0, 6144), rider(9000.0, 6144)],
     (4.0 + 8.0) / 2),
    # every held expert hit in every layer of every step
    ([rider(1000.0, 16 * STEPS * LAYERS)], 16.0),
    # a program that sorted its pairs ran no trip: nothing to read
    ([rider(1000.0, 0)], None),
    # a program that does not count them (the parent): nothing
    ([rider(1000.0), rider(1000.0)], None),
    # a request without the span is no rider
    ([{"ts": 0.0, "dur": 1.0, "args": {}, "spans": [
        ("batching/execute", 1000.0, 600.0, {})]},
      rider(1000.0, 768)], 1.0),
    ([], None),
])
def test_experts_a_decode_steps_expert_layer_walked(requests, want):
    got = metrics.load("expert_decode_trips_mean").read(run_of(requests))
    assert got == (None if want is None else pytest.approx(want))


def test_the_benchmark_lists_it_for_the_mixed_generate_cell_only():
    (entry,) = [m for m in BENCH["per_layer"]
                if m["name"] == "expert_decode_trips_mean"]
    assert entry == {"name": "expert_decode_trips_mean", "unit": "experts",
                     "better": "lower", "source": "program_counter",
                     "layer": "expert layer",
                     "moves": "first_output_p50_ms",
                     "workloads": ["mimo-v2.5.mixed-generate"]}
    names = [m["name"] for m in BENCH["per_layer"]]
    # appended behind what was there, nothing moved
    assert names.index("expert_decode_trips_mean") \
        == names.index("tput_host_idle_named") + 1


def test_a_line_leaves_it_out_where_there_is_nothing_to_read():
    run = run_of([rider(1000.0)])
    assert metrics.read_all(["expert_decode_trips_mean"], run, BENCH) == {}
    run = run_of([rider(1000.0, 3840)])
    assert metrics.read_all(["expert_decode_trips_mean"], run, BENCH) == {
        "expert_decode_trips_mean": {"value": 5.0, "unit": "experts"}}
