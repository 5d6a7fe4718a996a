"""`cross_read_share` on hand-written request traces."""

import json
import pathlib
import types

import pytest

from perfbench import metrics

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIG = json.loads((ROOT / "perfbench/configs/t5-large.json").read_text())
TRAFFIC = json.loads((ROOT / "perfbench/traffic/generate.json").read_text())


def rider(batch_ts, tokens, noted=True, block=128, seq_len=512):
    """One single-example request that rode the batch executed at
    `batch_ts`, its `generate/cross` written before its batch ran."""
    spans = [("batching/execute", batch_ts, 600.0, {})]
    if noted:
        spans.insert(0, ("generate/cross", batch_ts - 300.0, 0.0, {
            "input_tokens": tokens, "blocks_read": -(-tokens // block),
            "blocks_held": seq_len // block}))
    return {"ts": 0.0, "dur": 1.0, "args": {}, "spans": spans}


def run_of(requests):
    return types.SimpleNamespace(requests=requests, config=CONFIG,
                                 traffic=TRAFFIC)


@pytest.mark.parametrize("requests, want", [
    # one batch of three riders: 1 + 2 + 4 blocks of the 32 x 4 it holds
    ([rider(1000.0, 76), rider(1000.0, 256), rider(1000.0, 512)],
     100.0 * 7 / 128),
    # two batches, each its own share: the mean of the shares
    ([rider(1000.0, 129)] + [rider(9000.0, 512)] * 32,
     (100.0 * 2 / 128 + 100.0) / 2),
    # a block of 256 rows: the span says what the program holds
    ([rider(1000.0, 300, block=256)], 100.0 * 2 / 64),
    # a program that reads every block whatever the lengths (the
    # parent) does not say so: nothing
    ([rider(1000.0, 300, noted=False)], None),
    # a request that never rode a batch is no rider
    ([{"ts": 0.0, "dur": 1.0, "args": {}, "spans": [
        ("generate/cross", 10.0, 0.0, {"input_tokens": 9, "blocks_read": 1,
                                      "blocks_held": 4})]},
      rider(1000.0, 512)], 100.0 * 4 / 128),
    ([], None),
])
def test_share_of_the_held_blocks_that_a_step_reads(requests, want):
    got = metrics.load("cross_read_share").read(run_of(requests))
    assert got == (None if want is None else pytest.approx(want))


def test_the_benchmark_lists_it_for_the_generate_cell_only():
    (entry,) = [m for m in BENCH["per_layer"]
                if m["name"] == "cross_read_share"]
    assert entry == {"name": "cross_read_share", "unit": "%",
                     "better": "lower", "source": "program_counter",
                     "layer": "models", "moves": "first_output_p50_ms",
                     "workloads": ["t5-large.generate"]}
    names = [m["name"] for m in BENCH["per_layer"]]
    # appended behind what was there, nothing moved
    assert names.index("cross_read_share") \
        == names.index("t5_generate_mfu") + 1


def test_a_line_leaves_it_out_where_there_is_nothing_to_read():
    run = run_of([rider(1000.0, 300, noted=False)])
    assert metrics.read_all(["cross_read_share"], run, BENCH) == {}
    run = run_of([rider(1000.0, 512)] * 16)
    assert metrics.read_all(["cross_read_share"], run, BENCH) == {
        "cross_read_share": {"value": 50.0, "unit": "%"}}
