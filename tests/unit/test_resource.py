"""Per-device HBM accounting (resources/resource_util.cc bound/unbound
algebra collapsed to device/hbm kinds; resource_tracker.cc gate)."""

import numpy as np
import pytest

from min_tfs_client_tpu.core.resource import (
    ResourceTracker,
    estimate_for_mesh,
)
from min_tfs_client_tpu.core.states import ServableId
from min_tfs_client_tpu.utils.status import ServingError

GB = 1 << 30


def four_chip_tracker():
    return ResourceTracker({i: 16 * GB for i in range(4)})


class TestUnboundPlacement:
    def test_single_chip_model_binds_to_one_device(self):
        tracker = four_chip_tracker()
        assert tracker.try_reserve(ServableId("m", 1), 14 * GB)
        used = tracker.reserved_per_device()
        assert sorted(used.values()) == [0, 0, 0, 14 * GB]

    def test_pool_total_does_not_mask_per_chip_overflow(self):
        """The round-2 failure case: 4x16GB chips = 64GB 'total', but a
        20GB unbound model must NOT be approved."""
        tracker = four_chip_tracker()
        assert not tracker.try_reserve(ServableId("m", 1), 20 * GB)

    def test_overflow_binds_to_least_loaded(self):
        tracker = four_chip_tracker()
        tracker.try_reserve(ServableId("a", 1), 10 * GB)
        tracker.try_reserve(ServableId("b", 1), 8 * GB)
        used = tracker.reserved_per_device()
        # second model landed on a different chip
        assert sorted(v for v in used.values() if v) == [8 * GB, 10 * GB]

    def test_release_frees_the_chip(self):
        tracker = four_chip_tracker()
        for i in range(4):
            assert tracker.try_reserve(ServableId("m", i), 10 * GB)
        assert not tracker.try_reserve(ServableId("m", 9), 10 * GB)
        tracker.release(ServableId("m", 0))
        assert tracker.try_reserve(ServableId("m", 9), 10 * GB)


class TestBoundAllocations:
    def test_tp_slices_checked_per_chip(self):
        tracker = four_chip_tracker()
        tp_model = {i: 9 * GB for i in range(4)}  # 36GB over 4 chips
        assert tracker.try_reserve(ServableId("tp", 1), tp_model)
        # A second TP model of the same footprint exceeds every chip.
        assert not tracker.try_reserve(ServableId("tp2", 1), tp_model)
        # But a small single-chip model still fits beside the slices.
        assert tracker.try_reserve(ServableId("s", 1), 6 * GB)

    def test_two_tp_models_different_footprints(self):
        tracker = four_chip_tracker()
        assert tracker.try_reserve(ServableId("a", 1),
                                   {0: 10 * GB, 1: 10 * GB})
        assert tracker.try_reserve(ServableId("b", 1),
                                   {2: 10 * GB, 3: 10 * GB})
        assert not tracker.try_reserve(ServableId("c", 1),
                                       {0: 10 * GB, 2: 10 * GB})

    def test_unknown_device_rejected(self):
        tracker = four_chip_tracker()
        assert not tracker.try_reserve(ServableId("x", 1), {7: GB})

    def test_reserve_or_raise_reports_per_device(self):
        tracker = four_chip_tracker()
        with pytest.raises(ServingError, match="does not fit any chip"):
            tracker.reserve_or_raise(ServableId("big", 1), 100 * GB)


class TestCanFitAll:
    def test_simulation_does_not_reserve(self):
        tracker = four_chip_tracker()
        assert tracker.can_fit_all([14 * GB, 14 * GB, 14 * GB, 14 * GB])
        assert tracker.reserved_bytes() == 0
        assert not tracker.can_fit_all([14 * GB] * 5)

    def test_mixed_bound_and_unbound(self):
        tracker = four_chip_tracker()
        tracker.try_reserve(ServableId("a", 1), {i: 10 * GB for i in range(4)})
        # Placement is greedy in list order (unbound binds to the
        # least-loaded chip at its turn).
        assert tracker.can_fit_all([{0: 6 * GB}, 5 * GB]) is True
        assert tracker.can_fit_all([{0: 6 * GB}, 7 * GB]) is False
        assert tracker.can_fit_all([{0: 20 * GB}]) is False


class TestMeshEstimate:
    def test_tp_shards_divide_params(self):
        # 8-device CPU test mesh (conftest): data=4 x model=2 -> each chip
        # holds half the parameters.
        alloc = estimate_for_mesh(8 * GB, {"data": 4, "model": 2})
        assert isinstance(alloc, dict)
        assert len(alloc) == 8
        assert set(alloc.values()) == {4 * GB}

    def test_unresolvable_mesh_falls_back_to_unbound(self):
        alloc = estimate_for_mesh(8 * GB, {"data": 64, "model": 16})
        assert alloc == 8 * GB


class TestDetectPools:
    """The virtual pool is for CPU devices only; a TPU without a real
    bytes_limit must not be gated against an invented one."""

    class _Device:
        def __init__(self, id, platform, stats):
            self.id, self.platform, self._stats = id, platform, stats
            self.device_kind = f"fake {platform}"

        def memory_stats(self):
            return self._stats

    def _detect(self, monkeypatch, devices):
        import jax

        from min_tfs_client_tpu.core.resource import detect_hbm_pools

        monkeypatch.setattr(jax, "local_devices", lambda: devices)
        return detect_hbm_pools()

    def test_cpu_devices_get_the_virtual_pool(self, monkeypatch):
        pools = self._detect(monkeypatch, [self._Device(0, "cpu", None),
                                           self._Device(1, "cpu", None)])
        assert pools == {0: 1 << 40, 1: 1 << 40}

    def test_tpu_reports_its_real_limit(self, monkeypatch):
        pools = self._detect(monkeypatch, [self._Device(
            0, "tpu", {"bytes_limit": 16 * GB, "bytes_in_use": 0})])
        assert pools == {0: 16 * GB}

    def test_tpu_without_bytes_limit_is_an_error(self, monkeypatch):
        with pytest.raises(ServingError, match="bytes_limit"):
            self._detect(monkeypatch, [self._Device(0, "tpu", None)])
