"""Where JAX_PLATFORMS excludes the TPU (this test process's environment,
the tier-1 command), the chip entry point says so, exits non-zero, and
prints no result — it does not fall back to another backend."""

import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]


@pytest.mark.parametrize("script", ["chip_smoke.py"])
def test_entry_point_refuses_to_run_without_a_chip(script):
    res = subprocess.run(
        [sys.executable, str(REPO / script)], capture_output=True, text=True,
        timeout=60, cwd=str(REPO), env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert res.returncode != 0
    assert res.stdout == ""
    assert "JAX_PLATFORMS='cpu'" in res.stderr and "excludes" in res.stderr
