"""The compile-cache rule (utils/compile_cache.py): whoever runs the
program may place the cache with JAX_COMPILATION_CACHE_DIR, and then code
sets no directory; otherwise every process lands on one fixed path under
the checkout."""

import json
import os
import pathlib
import subprocess
import sys

import jax

from min_tfs_client_tpu.utils import compile_cache

REPO = pathlib.Path(__file__).resolve().parents[2]


def _recorded_updates(monkeypatch) -> dict:
    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.__setitem__(name, value))
    return updates


def test_variable_set_means_code_sets_no_directory(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    updates = _recorded_updates(monkeypatch)
    assert compile_cache.configure() == str(tmp_path)
    assert "jax_compilation_cache_dir" not in updates
    # ... and every program is cached, however quickly it compiled.
    assert updates["jax_persistent_cache_min_compile_time_secs"] == 0


def test_variable_unset_means_the_fixed_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    updates = _recorded_updates(monkeypatch)
    assert compile_cache.configure() == str(REPO / ".jax_cache")
    assert updates["jax_compilation_cache_dir"] == str(REPO / ".jax_cache")


def test_two_processes_agree_on_the_path_and_jax_takes_it(tmp_path):
    code = ("import json, jax; "
            "from min_tfs_client_tpu.utils import compile_cache; "
            "print(json.dumps([compile_cache.configure(), "
            "jax.config.jax_compilation_cache_dir]))")
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = str(REPO)
    seen = [json.loads(subprocess.run(
        [sys.executable, "-c", code], cwd=cwd, env=env, check=True,
        capture_output=True, text=True, timeout=120).stdout)
        for cwd in (str(REPO), str(tmp_path))]
    assert seen[0] == seen[1] == [str(REPO / ".jax_cache")] * 2
