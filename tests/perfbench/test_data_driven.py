"""A later PR may add files and entries and may not edit a file that is
there. In a temporary copy: one new configuration file, one new traffic
file, one new per-layer metric file and one new `workloads` entry, and
the harness finds all four with no existing file edited."""

import hashlib
import json
import pathlib
import shutil
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]


def digests(root: pathlib.Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "perfbench").rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def test_a_config_a_traffic_mix_a_metric_and_a_cell_are_added_as_files(
        tmp_path):
    shutil.copytree(ROOT / "perfbench", tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = digests(tmp_path)

    # 1. a configuration: its file of sizes (and its reference beside it)
    sizes = json.loads((ROOT / "perfbench/configs/t5-large.json").read_text())
    sizes.update(source="https://example.org/t5-3b/config.json",
                 d_model=1024, d_kv=128, num_heads=32, d_ff=16384,
                 reference="perfbench/configs/t5-3b.reference.py")
    (tmp_path / "perfbench/configs/t5-3b.json").write_text(json.dumps(sizes))
    shutil.copy(ROOT / "perfbench/configs/t5-large.reference.py",
                tmp_path / "perfbench/configs/t5-3b.reference.py")
    # 2. a traffic mix: parameters for the one general generator
    mix = json.loads((ROOT / "perfbench/traffic/generate.json").read_text())
    mix.update(rate_per_s=5, input_length_grid=[16, 32, 64, 400])
    (tmp_path / "perfbench/traffic/trickle.json").write_text(json.dumps(mix))
    # 3. a per-layer metric: a reader of its own
    (tmp_path / "perfbench/metrics/merge_p50_ms.py").write_text(
        "from perfbench import spans, stats\n\n\n"
        "def read(run):\n"
        "    return stats.percentile(spans.per_request_ms(\n"
        "        run.requests, ('batching/merge',)), 50)\n")
    # 4. the entries that name them
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "t5-3b", "source": sizes["source"],
        "file": "perfbench/configs/t5-3b.json", "reduced": [],
        "why": "wider heads and MLP through the same path"})
    bench["workloads"].append({
        "name": "t5-3b.trickle", "config": "t5-3b",
        "traffic": "trickle", "chips": 1, "why": "a lone caller"})
    for metric in bench["end_to_end"]:
        if metric["name"] == "first_output_p50_ms":
            metric["workloads"].append("t5-3b.trickle")
    bench["per_layer"].append({
        "name": "merge_p50_ms", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "batching",
        "moves": "first_output_p50_ms",
        "workloads": ["t5-3b.trickle"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    probe = (
        "import json, sys, types\n"
        f"sys.path.insert(0, {str(tmp_path)!r})\n"
        "from perfbench import run, metrics, traffic, children\n"
        "spec = run.load_cell('t5-3b.trickle')\n"
        "plan = traffic.build_plan(spec['traffic'], 4000000001, 10.0)\n"
        "fake = types.SimpleNamespace(requests=[{'spans': [\n"
        "    ('batching/merge', 0.0, 250.0, {})]}])\n"
        "ref = children.load_reference(spec['config'])\n"
        "print(json.dumps({\n"
        "    'd_ff': spec['config']['d_ff'],\n"
        "    'kwargs': children.program_config_kwargs(spec['config']),\n"
        "    'arrivals': sum(0 <= r['due'] < 10 for r in plan['requests']),\n"
        "    'lengths': sorted({r['length'] for r in plan['requests']}),\n"
        "    'per_layer': spec['per_layer'],\n"
        "    'end_to_end': spec['end_to_end'],\n"
        "    'merge': metrics.load('merge_p50_ms').read(fake),\n"
        "    'reference': [callable(getattr(ref, name)) for name in\n"
        "                  ('make_expected', 'check', 'verify')]}))\n")
    out = subprocess.run([sys.executable, "-c", probe], cwd=str(tmp_path),
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    found = json.loads(out.stdout.strip().splitlines()[-1])
    assert found["d_ff"] == 16384
    assert found["kwargs"]["d_kv"] == 128               # read from the file
    assert found["kwargs"]["num_decoder_layers"] == 24
    assert found["arrivals"] == 50                      # 5 a second, 10 s
    assert found["lengths"] == [16, 32, 64, 400]
    assert found["per_layer"] == ["merge_p50_ms"]
    assert found["end_to_end"] == ["first_output_p50_ms", "setup_s"]
    assert found["merge"] == 0.25
    assert found["reference"] == [True, True, True]

    after = digests(tmp_path)
    assert {k: after[k] for k in before} == before      # nothing was edited
    assert sorted(set(after) - set(before)) == [
        "perfbench/configs/t5-3b.json",
        "perfbench/configs/t5-3b.reference.py",
        "perfbench/metrics/merge_p50_ms.py",
        "perfbench/traffic/trickle.json"]
