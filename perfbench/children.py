"""The three helper children that need jax. All are pinned to the CPU by
their parent (JAX_PLATFORMS=cpu), so the chip stays with the server.

    python perfbench/children.py export <config.json> <out_dir>
    python perfbench/children.py trace <profile_dir> <events.json>
    python perfbench/children.py verify <config.json> <export_dir> \
        <deferred.npz> <verified.json>
"""

from __future__ import annotations

import importlib
import json
import pathlib
import sys

REPO = pathlib.Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))


def load_reference(config: dict):
    """The configuration's plain reference, a file beside its sizes."""
    from perfbench.metrics import load_file

    return load_file(REPO / config["reference"])


def _named(path: str):
    module, _, attr = path.partition(":")
    return getattr(importlib.import_module(module), attr)


def program_config_kwargs(config: dict) -> dict:
    """The program's config arguments, each read from the published key
    the file maps it to: the sizes are stated once."""
    serve = config["serve"]
    kwargs = {ours: config[theirs]
              for ours, theirs in serve["config_kwargs_from"].items()}
    kwargs.update(serve.get("config_kwargs", {}))
    return kwargs


# The version directory's loader (the "jax" platform's contract is a
# servable.py with build(path)). The program's own stub returns a model
# family's signatures with aliases: `serving_default` and `predict` (BERT)
# or `decode` (T5) are ONE Signature object under two names. With
# --enable_batching, batching/session.py:maybe_wrap_servable wraps that
# object once per name, so the second runner's inner run is the first
# runner's queue; on a one-chip host the scheduler has one thread
# (scheduler.py:_default_thread_count) and the first batched request
# waits on itself for ever. Until the program is repaired (PERF.md, Open
# questions), the benchmark's export keeps one name per signature, and
# says in the server's log how many names that dropped: run.py reads the
# line and gives notice once the stub drops none.
_SERVABLE_STUB = '''\
# Written by perfbench/children.py: the program's loader, with one name
# kept for each Signature object (see the note there).
def build(path):
    from min_tfs_client_tpu.models.export import load_signatures

    given = load_signatures(path)
    kept, seen = {}, set()
    for name, signature in given.items():
        if id(signature) not in seen:
            seen.add(id(signature))
            kept[name] = signature
    print(f"perfbench-stub: kept {len(kept)} of {len(given)} "
          "signature names", flush=True)
    return kept
'''


def child_export(config_path: str, out_dir: str) -> None:
    """Seeded weights through the program's own export, and what the
    plain reference says about a fixed sample of inputs."""
    import dataclasses

    import jax
    import numpy as np

    from min_tfs_client_tpu.models import export

    config = json.loads(pathlib.Path(config_path).read_text())
    serve = config["serve"]
    out = pathlib.Path(out_dir)
    program_config = _named(serve["config_class"])(
        **program_config_kwargs(config))
    reference = load_reference(config)
    params = _named(serve["init_params"])(
        jax.random.PRNGKey(int(serve["weight_seed"])), program_config)
    if hasattr(reference, "adjust_params"):
        params = reference.adjust_params(params, config)
    version_dir = export.export_servable(
        out / serve["model_name"], 1, serve["family"],
        dataclasses.asdict(program_config), params,
        signature_kwargs=serve.get("signature_kwargs", {}))
    (version_dir / "servable.py").write_text(_SERVABLE_STUB)
    expected = reference.make_expected(
        params, config, np.random.default_rng(int(serve["weight_seed"])))
    np.savez(out / "expected.npz", **expected)
    (out / "DONE").write_text("ok\n")  # last: a cut export is made again


def child_verify(config_path: str, export_dir: str, deferred_path: str,
                 out_path: str) -> None:
    """The reference's `verify(weights, config, expected, deferred)` on
    what the served model answered during the check: the part of
    `correct` that needs the reference's arithmetic at run time.
    `weights(prefix)` gives the exported parameters under one top-level
    name, read from the export's own file."""
    import numpy as np

    from min_tfs_client_tpu.models import export

    config = json.loads(pathlib.Path(config_path).read_text())
    stored = np.load(pathlib.Path(export_dir) / config["serve"]["model_name"]
                     / "1" / "params.npz", allow_pickle=False)

    def weights(prefix: str):
        return export.unflatten_params(
            {k: stored[k] for k in stored.files
             if k.split(".")[0] == prefix})[prefix]

    found = load_reference(config).verify(
        weights, config, dict(np.load(pathlib.Path(export_dir)
                                      / "expected.npz")),
        dict(np.load(deferred_path)))
    pathlib.Path(out_path).write_text(json.dumps(found))


def child_trace(profile_dir: str, events_path: str) -> None:
    """The device planes of the capture as plain events, names interned:
    {"names": [...], "planes": [{"name", "lines": [{"name", "events":
    [[name index, start ns, duration ns], ...]}]}]}. An operation keeps
    its instruction's name, not the whole HLO text the profiler gives."""
    import jax

    from perfbench.trace_reduce import instruction

    files = sorted(pathlib.Path(profile_dir).rglob("*.xplane.pb"))
    if not files:
        raise SystemExit("trace: the capture wrote no .xplane.pb")
    names: dict[str, int] = {}
    planes = []
    for path in files:
        for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
            if "/device:" not in plane.name:
                continue
            lines = []
            for line in plane.lines:
                events = [[names.setdefault(instruction(e.name), len(names)),
                           int(e.start_ns), int(e.duration_ns)]
                          for e in line.events]
                lines.append({"name": line.name, "events": events})
            planes.append({"name": plane.name, "lines": lines})
    pathlib.Path(events_path).write_text(json.dumps(
        {"names": list(names), "planes": planes}))


if __name__ == "__main__":
    {"export": child_export, "trace": child_trace,
     "verify": child_verify}[sys.argv[1]](*sys.argv[2:])
