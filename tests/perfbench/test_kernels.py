"""Operations and bytes of `_paged_kernel` against PERF.md section 5's
hand figures; the peaks' table refuses an unknown chip."""

import json
import pathlib

import pytest

from perfbench import metrics, trace_reduce

BENCH = pathlib.Path(__file__).resolve().parents[2] / "perfbench"


def kernel(name):
    return metrics.load_file(BENCH / "kernels" / f"{name}.py")


def test_paged_kernel_reads_262_kb_of_pages_at_t5_small_8_sessions():
    flops, moved = kernel("_paged_kernel").ops_and_bytes(
        pages=8, heads=8, page_tokens=16, d_head=64)
    assert moved == 262_144          # 8 pages x 32 KB: K and V, 8 heads, bf16
    assert flops == 4 * 8 * 8 * 16 * 64


def test_paged_kernel_need_grows_with_pages_not_with_table_width():
    few = kernel("_paged_kernel").ops_and_bytes(
        pages=48, heads=16, page_tokens=16, d_head=64)
    many = kernel("_paged_kernel").ops_and_bytes(
        pages=96, heads=16, page_tokens=16, d_head=64)
    assert many[0] == 2 * few[0] and many[1] == 2 * few[1]


def test_roofline_share_is_bound_by_the_slower_of_the_two():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert trace_reduce.roofline_share(2.0, 100.0, 5.0, peak) == 0.5   # flops
    assert trace_reduce.roofline_share(2.0, 10.0, 10.0, peak) == 0.5   # bytes


def test_peaks_table_has_the_v5e_and_its_source():
    peaks = json.loads((BENCH / "peaks.json").read_text())
    v5e = peaks["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in v5e["source"]


def test_an_unknown_device_kind_is_an_error(capsys):
    from perfbench import run

    with pytest.raises(SystemExit) as exc:
        run.peak_for("TPU v9 imaginary")
    assert exc.value.code != 0
    assert "not in perfbench/peaks.json" in capsys.readouterr().err
