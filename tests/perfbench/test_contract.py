"""BENCHMARK.json against the contract's letter, the result line's
shape, and the command refusing to run without a TPU."""

import json
import os
import pathlib
import re
import subprocess
import sys

import pytest

from perfbench import metrics, run

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_are_exactly_the_contracts():
    assert sorted(BENCH) == sorted(["command", "paths", "run_seconds",
                                    "configs", "workloads", "end_to_end",
                                    "per_layer"])
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


@pytest.mark.parametrize("name", sorted(
    {m["name"] for m in METRICS} | set(CELLS)
    | {c["name"] for c in BENCH["configs"]}
    | {w["traffic"] for w in BENCH["workloads"]}))
def test_every_name_is_made_of_the_allowed_characters(name):
    assert NAME.match(name)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_every_metric_has_a_unit_a_direction_a_source_and_a_reader(metric):
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    assert callable(metrics.load(metric["name"]).read)
    for cell in metric.get("workloads", []):
        assert cell in CELLS


def test_names_are_unique():
    for names in ([m["name"] for m in METRICS], CELLS,
                  [c["name"] for c in BENCH["configs"]]):
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("metric", BENCH["end_to_end"],
                         ids=lambda m: m["name"])
def test_end_to_end_metrics_have_bounds_and_host_or_device_sources(metric):
    assert sorted(set(metric) - {"workloads"}) == sorted(
        ["name", "unit", "better", "bound", "source"])
    assert 0.01 <= metric["bound"] <= 0.1
    assert metric["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_a_layer_metric_moves_a_metric_reported_in_all_its_cells(metric):
    assert sorted(set(metric) - {"workloads"}) == sorted(
        ["name", "unit", "better", "source", "layer", "moves"])
    moved = next(m for m in BENCH["end_to_end"]
                 if m["name"] == metric["moves"])
    for cell in metric.get("workloads", CELLS):
        assert cell in moved.get("workloads", CELLS)
    if metric["name"].endswith("_roofline"):
        assert metric["unit"] == "%"


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_setup_another_end_to_end_and_a_layer(cell):
    spec = run.load_cell(cell)
    assert "setup_s" in spec["end_to_end"]
    assert len(spec["end_to_end"]) >= 2 and spec["per_layer"]
    assert spec["cell"]["chips"] in (1, 4)
    assert 1 <= len(spec["cell"]["why"]) <= 200
    assert spec["traffic"]["kind"] in ("open_loop", "sessions")


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_a_configuration_is_a_file_of_sizes_with_its_reference(config):
    assert sorted(config) == sorted(["name", "source", "file", "reduced",
                                     "why"])
    assert any(config["file"].startswith(p + "/") for p in BENCH["paths"])
    sizes = json.loads((ROOT / config["file"]).read_text())
    assert sizes["source"] == config["source"]
    assert (ROOT / sizes["reference"]).is_file()
    assert any(w["config"] == config["name"] for w in BENCH["workloads"])
    widths = re.compile(r"(hidden|intermediate|latent|state|projection)"
                        r"|_dim$|_rank$|head_dim|d_kv|d_ff|d_model")
    assert not [k for k in config["reduced"] if widths.search(k)]


def test_the_command_names_only_files_under_paths():
    assert BENCH["command"][0] == "python3"
    script = BENCH["command"][1]
    assert any(script.startswith(p + "/") for p in BENCH["paths"])
    assert (ROOT / script).is_file()
    for word in BENCH["command"]:
        assert not word.startswith("/") and ".." not in word
    for path in BENCH["paths"]:
        assert (ROOT / path).is_dir()


def test_the_result_line_has_the_keys_the_driver_reads():
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 123}
    values = {"setup_s": {"value": 50.123456, "unit": "s"}}
    plain = run.result_line(correct=True, attempted=400, failed=0,
                            metric_values=values, device=device)
    assert sorted(plain) == ["attempted", "correct", "device", "failed",
                             "metrics"]
    assert plain["metrics"]["setup_s"] == {"value": 50.123456, "unit": "s"}
    reduced = {"busy_s": 1.5, "window_s": 3.0, "idle_gaps": [0.2, 0.1],
               "ops": {"_paged_kernel": [0.5, 0.5], "fusion": [0.25]}}
    traced = run.result_line(correct=True, attempted=400, failed=0,
                             metric_values=values, device=device,
                             reduced=reduced)
    assert traced["device"]["busy_s"] == 1.5
    assert traced["device"]["window_s"] == 3.0
    assert traced["breakdown"] == {
        "device_ops": [["_paged_kernel", 1.0], ["fusion", 0.25]],
        "idle_gaps": [["unattributed", 0.2], ["unattributed", 0.1]]}
    assert "busy_s" not in device          # the caller's dict is not touched
    json.dumps(traced)


def test_the_line_ends_with_each_number_compared_beside_its_limit():
    verdict = {"ok": True, "seconds": {"verify": 13.0},
               "encoding_max_abs_diff": 0.105, "tokens_equal": 1.0,
               "first_logits_diff_by_row": [0.02, 0.05]}
    config = {"correctness": {"encoding_atol": 0.25, "why": "words"}}
    compared = run.checked(verdict, config, compiled=0, rc=0)
    assert compared == {
        "read": {"encoding_max_abs_diff": 0.105, "tokens_equal": 1.0,
                 "compiles_in_window": 0, "server_exit_code": 0},
        "limits": {"encoding_atol": 0.25, "compiles_in_window": 0,
                   "server_exit_code": 0}}
    line = run.result_line(
        correct=True, attempted=4, failed=0, metric_values={},
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                "memory_peak_bytes": 1}, compared=compared)
    assert list(line)[-1] == "checked"
    json.dumps(line)


def test_the_command_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(ROOT / BENCH["command"][1]), "--workload",
         CELLS[0], "--seed", "4000000001", "--seconds", "1", "--trace", "0"],
        env=env, cwd=str(ROOT), capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""            # no result line at all
    assert "no chip to measure on" in proc.stderr


def test_the_parent_never_imports_jax():
    code = ("import sys; sys.path.insert(0, %r); "
            "from perfbench import run, loadgen, server, traffic, stats, "
            "spans, trace_reduce, metrics; "
            "assert 'jax' not in sys.modules" % str(ROOT))
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)
