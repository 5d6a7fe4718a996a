"""`prefill_packed_share` on hand-written request traces."""

import json
import pathlib
import types

import pytest

from perfbench import metrics

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def rider(batch_ts, prompt, prefill_rows=None):
    """One request that rode the batch executed at `batch_ts`."""
    args = {"prompt_tokens": prompt, "pairs_prefill": prompt * 48,
            "held_prefill": prompt * 3, "pairs_decode": 128 * 48,
            "held_decode": 384, "max_load": 40, "load_total": 960}
    if prefill_rows is not None:
        args["prefill_rows"] = prefill_rows
    return {"ts": 0.0, "dur": 1.0, "args": {}, "spans": [
        ("batching/execute", batch_ts, 600.0, {}),
        ("generate/route", batch_ts + 700.0, 0.0, args)]}


def run_of(requests):
    return types.SimpleNamespace(requests=requests)


@pytest.mark.parametrize("requests, want", [
    # one batch: 900 real tokens in two blocks of 512 rows
    ([rider(1000.0, 300, 1024), rider(1000.0, 600, 1024)],
     100.0 * 900 / 1024),
    # two batches, each its own share: the mean of the shares, not the
    # share of the sums
    ([rider(1000.0, 512, 512), rider(9000.0, 100, 512),
      rider(9000.0, 156, 512)], (100.0 + 50.0) / 2),
    # a batch whose prefill ran every padded position
    ([rider(1000.0, 805, 32 * 2048)], 100.0 * 805 / 65536),
    # a program that does not say what it ran (the parent): nothing
    ([rider(1000.0, 300), rider(1000.0, 600)], None),
    # a request without the span is no rider
    ([{"ts": 0.0, "dur": 1.0, "args": {}, "spans": [
        ("batching/execute", 1000.0, 600.0, {})]},
      rider(1000.0, 256, 512)], 50.0),
    ([], None),
])
def test_share_of_the_prefills_rows_that_were_real_tokens(requests, want):
    got = metrics.load("prefill_packed_share").read(run_of(requests))
    assert got == (None if want is None else pytest.approx(want))


def test_the_benchmark_lists_it_for_the_mixed_generate_cell_only():
    (entry,) = [m for m in BENCH["per_layer"]
                if m["name"] == "prefill_packed_share"]
    assert entry == {"name": "prefill_packed_share", "unit": "%",
                     "better": "higher", "source": "program_counter",
                     "layer": "models", "moves": "first_output_p50_ms",
                     "workloads": ["mimo-v2.5.mixed-generate"]}
    names = [m["name"] for m in BENCH["per_layer"]]
    # appended behind what was there, nothing moved
    assert names.index("prefill_packed_share") \
        == names.index("generate_mfu") + 1


def test_a_line_leaves_it_out_where_there_is_nothing_to_read():
    run = run_of([rider(1000.0, 300)])
    assert metrics.read_all(["prefill_packed_share"], run, BENCH) == {}
    run = run_of([rider(1000.0, 256, 512)])
    assert metrics.read_all(["prefill_packed_share"], run, BENCH) == {
        "prefill_packed_share": {"value": 50.0, "unit": "%"}}
