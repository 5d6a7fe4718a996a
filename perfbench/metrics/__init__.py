"""One small reader per metric, found by the metric's name:
perfbench/metrics/<name>.py with `read(run) -> number or None`. A reader
that finds nothing to read returns None and the harness leaves the
metric out of the line. `run` is run.py's Run: the client-side records,
the server's request traces, the reduced device trace, the files of the
cell. A later PR adds a metric by adding its file and its entry in
BENCHMARK.json; no file that is there needs an edit."""

from __future__ import annotations

import importlib.util
import pathlib

HERE = pathlib.Path(__file__).parent


def load_file(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "perfbench_file_" + path.stem.replace("-", "_").replace(".", "_"),
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load(name: str):
    path = HERE / f"{name}.py"
    if not path.exists():
        raise FileNotFoundError(f"metric {name!r} has no reader at {path}")
    return load_file(path)


def read_all(names, run, bench: dict) -> dict:
    """{name: {"value", "unit"}} for the readers that found something."""
    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    out = {}
    for name in names:
        value = load(name).read(run)
        if value is not None:
            out[name] = {"value": float(value), "unit": units[name]}
    return out
