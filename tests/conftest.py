"""Test bootstrap: force an 8-device virtual CPU mesh BEFORE any jax use.

This is the "multi-node without a cluster" analogue the survey prescribes
(SURVEY.md §4): every sharding/collective code path runs against 8 virtual
CPU devices, so TP/DP/SP tests execute real XLA collectives with no TPU pod.

The environment decides the backend: `JAX_PLATFORMS=cpu` set here, before
jax is imported, is all it takes (subprocesses the tests spawn inherit it).
The persistent compilation cache is switched off for the same processes —
tests must not depend on, or fill, a cache directory in the checkout
(utils/compile_cache.py).
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# What the environment asked for, kept for the tests/tpu tier: it runs its
# device work in a subprocess and skips when this excludes the TPU.
os.environ.setdefault("TESTS_INCOMING_JAX_PLATFORMS",
                      os.environ.get("JAX_PLATFORMS", ""))
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"


# ---------------------------------------------------------------------------
# Runtime schedule witness (docs/STATIC_ANALYSIS.md "Runtime witness"):
# concurrency suites opt in with an autouse fixture that requests
# `schedule_witness`; every test then runs with threading.Lock/RLock/
# Condition recording acquisition order and every `# guarded_by:`-declared
# mutation checked held-at-mutation, asserted clean at teardown.

import pytest  # noqa: E402


@pytest.fixture
def schedule_witness():
    from min_tfs_client_tpu.analysis import witness as witness_mod

    wit = witness_mod.ScheduleWitness.for_package()
    wit.install()
    try:
        yield wit
    finally:
        wit.uninstall()
    # After uninstall, so an assertion failure can't leak the patches.
    wit.assert_clean()


# Runtime leak witness (docs/STATIC_ANALYSIS.md "Leak witness"): the
# paged-KV, router-scaleout, and storm-smoke suites arm this autouse;
# every pool that outlives the test must then hold zero net
# pages/slots/pins/conns, and no non-daemon thread may outlive it.


@pytest.fixture
def leak_witness():
    from min_tfs_client_tpu.analysis import witness as witness_mod

    wit = witness_mod.LeakWitness()
    wit.install()
    try:
        yield wit
    finally:
        wit.uninstall()
    # After uninstall, so an assertion failure can't leak the patches.
    wit.assert_no_leaks()
