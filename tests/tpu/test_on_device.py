"""tests/tpu tier: real-accelerator checks. The pytest process is pinned
to a CPU mesh by tests/conftest.py, so the device work runs in ONE
subprocess against the real backend (tests/tpu/_device_driver.py). The
tier skips where the environment pins another backend (JAX_PLATFORMS=cpu,
the tier-1 command) or where JAX finds no TPU; on the chip, run it through
the chip tool: `python -m pytest tests/tpu -q`.

Checks driven on hardware:
  * Pallas flash attention (compiled) vs the jnp oracle — plain, causal,
    and ragged-lengths variants — and that the dispatcher picks it;
  * ragged paged attention with bias over a multi-page table (T5's path);
  * a decode step's latent attention, the kernel against the jnp form at
    Xing's and Ling's shapes (the row written in place, blocks by length);
  * a bucketed Predict through the full tpu:// serving stack;
  * mesh attach + predict on a 1-device device mesh;
  * int8 weight-only quantized Predict vs full precision;
  * a partitioned imported SavedModel's interior on the chip;
  * continuous-batching decode sessions vs the greedy oracle.
"""

import json
import os
import pathlib
import subprocess
import sys

import pytest

DRIVER = pathlib.Path(__file__).parent / "_device_driver.py"
REPO = pathlib.Path(__file__).resolve().parents[2]
DRIVER_TIMEOUT_S = float(os.environ.get("TPU_TIER_TIMEOUT", 600))


@pytest.fixture(scope="module")
def device_results() -> dict:
    # tests/conftest.py pins this process to the CPU and keeps what the
    # environment asked for under TESTS_INCOMING_JAX_PLATFORMS.
    incoming = os.environ.get("TESTS_INCOMING_JAX_PLATFORMS", "")
    if incoming and "tpu" not in incoming.split(","):
        pytest.skip(f"JAX_PLATFORMS={incoming} excludes the TPU")
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS",
                        "JAX_ENABLE_COMPILATION_CACHE")}
    if incoming:
        env["JAX_PLATFORMS"] = incoming
    res = subprocess.run(
        [sys.executable, str(DRIVER)], capture_output=True, text=True,
        timeout=DRIVER_TIMEOUT_S, env=env, cwd=str(REPO))
    results = {}
    for line in res.stdout.splitlines():
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if isinstance(rec, dict) and "check" in rec:
            results[rec["check"]] = rec
    backend = results.get("backend")
    if backend is not None and backend["platform"] == "cpu":
        pytest.skip("no accelerator (JAX fell back to the cpu backend)")
    if not results:
        pytest.fail(f"device driver rc={res.returncode}:\n"
                    f"{res.stderr[-2000:]}")
    results["exit_code"] = res.returncode
    return results


@pytest.mark.integration
def test_driver_exit_code_says_every_check_passed(device_results):
    failed = [name for name, rec in device_results.items()
              if isinstance(rec, dict) and not rec["ok"]]
    assert device_results["exit_code"] == 0 and not failed, failed


@pytest.mark.integration
@pytest.mark.parametrize("variant", ["plain", "causal", "lengths"])
def test_flash_attention_on_mxu(device_results, variant):
    rec = device_results.get(f"flash_attention/{variant}")
    assert rec is not None, f"driver never ran flash_attention/{variant}"
    assert rec["ok"], f"max_err={rec.get('max_err')}"


@pytest.mark.integration
def test_attention_dispatcher_picks_flash_on_device(device_results):
    rec = device_results.get("flash_dispatch")
    assert rec is not None and rec["ok"], rec


@pytest.mark.integration
@pytest.mark.parametrize("sq", [1, 5])
def test_paged_attention_with_bias_past_one_page(device_results, sq):
    rec = device_results.get(f"paged_bias_multipage/sq{sq}")
    assert rec is not None and rec["ok"], rec


@pytest.mark.integration
@pytest.mark.parametrize("cell", ["xing", "ling"])
def test_latent_step_kernel_is_the_jnp_step_on_device(device_results, cell):
    rec = device_results.get(f"latent_step/{cell}")
    assert rec is not None and rec["ok"], rec


@pytest.mark.integration
def test_bucketed_predict_on_device(device_results):
    rec = device_results.get("bucketed_predict")
    assert rec is not None and rec["ok"], rec


@pytest.mark.integration
def test_mesh_attach_predict_on_device(device_results):
    rec = device_results.get("mesh_attach_predict")
    assert rec is not None and rec["ok"], rec


@pytest.mark.integration
def test_int8_predict_on_device(device_results):
    rec = device_results.get("int8_predict")
    assert rec is not None and rec["ok"], rec


@pytest.mark.integration
def test_partitioned_import_classify_on_device(device_results):
    # An imported SavedModel's dense interior jitted on the chip while
    # Example decode + label lookup stay host.
    rec = device_results.get("partitioned_import_classify")
    assert rec is not None and rec["ok"], rec


@pytest.mark.integration
def test_continuous_batching_decode_on_device(device_results):
    rec = device_results.get("continuous_batching_decode")
    assert rec is not None and rec["ok"], rec
