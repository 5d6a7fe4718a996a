"""servetrend unit suite (observability/servetrend.py): record
extraction from bench emit lines and driver captures (provenance +
staleness as per-record stamps), the schema-versioned ledger, and the
provenance-refusing regression gate."""

import json

import pytest

from min_tfs_client_tpu.observability import servetrend
from min_tfs_client_tpu.observability.servetrend import (
    SCHEMA,
    gate,
    gather,
    load_ledger,
    records_from_bench_line,
    records_from_driver_file,
)

def _rec(metric, value, *, platform="cpu", device_kind=None, stale=False,
         unit="ms", seq=0, higher=None):
    return {"schema": SCHEMA, "t": 0.0, "metric": metric,
            "value": value, "unit": unit,
            "higher_is_better": (unit in ("qps", "tokens/s")
                                 if higher is None else higher),
            "platform": platform, "device_kind": device_kind,
            "probe_outcome": "ok", "stale": stale, "source": "test",
            "context": {}, "_seq": seq}


def _emit_line(metric="lat_p50", value=100.0, platform="cpu",
               stale=None, configs=None):
    extra = {"platform": platform, "device_kind": None,
             "probe_outcome": "ok", "model": "m", "batch": 8}
    if stale is not None:
        extra["stale"] = stale
    if configs is not None:
        extra["configs"] = configs
    return {"metric": metric, "value": value, "unit": "ms",
            "vs_baseline": 1.0, "extra": extra}


# ---------------------------------------------------------------------------
# Record extraction


def test_bench_line_primary_and_config_legs():
    configs = {
        "toy_p50": {"value": 5.0, "unit": "ms",
                    "measured_platform": "cpu", "batch": 4},
        "lat_p50": {"value": 100.0, "unit": "ms"},  # dup of primary
    }
    recs = records_from_bench_line(
        _emit_line(platform="tpu", configs=configs), source="s")
    assert [r["metric"] for r in recs] == ["lat_p50", "toy_p50"]
    primary, toy = recs
    assert primary["platform"] == "tpu"
    assert toy["platform"] == "cpu"  # leg's own measurement stamp wins
    assert toy["context"] == {"batch": 4}
    assert all(r["schema"] == SCHEMA for r in recs)


def test_leg_staleness_never_inherits_the_parent_marker():
    # A stale tpu replay primary riding next
    # to freshly-measured live cpu legs in one emit line.
    configs = {
        "replayed@cpu": {"value": 7.0, "unit": "ms", "stale": True,
                         "measured_platform": "tpu"},
        "live_cpu_leg": {"value": 3.0, "unit": "ms",
                         "measured_platform": "cpu"},
    }
    recs = records_from_bench_line(
        _emit_line(platform="tpu", stale=True, configs=configs))
    by_metric = {r["metric"]: r for r in recs}
    assert by_metric["lat_p50"]["stale"] is True
    assert by_metric["replayed"]["stale"] is True   # @cpu suffix dropped
    assert by_metric["live_cpu_leg"]["stale"] is False


def test_driver_file_parsed_tail_and_unusable(tmp_path):
    line = _emit_line()
    parsed = tmp_path / "a.json"
    parsed.write_text(json.dumps(
        {"cmd": "x", "rc": 0, "parsed": line, "tail": ""}))
    assert [r["metric"] for r in records_from_driver_file(
        str(parsed))] == ["lat_p50"]
    # No `parsed`: the tail is scanned backwards for the emit line.
    tail = tmp_path / "b.json"
    tail.write_text(json.dumps(
        {"cmd": "x", "rc": 0, "parsed": None,
         "tail": "noise\n" + json.dumps(line) + "\nmore noise"}))
    [rec] = records_from_driver_file(str(tail))
    assert rec["metric"] == "lat_p50" and rec["source"] == "b.json"
    # Unusable captures yield NO records, never an exception.
    broken = tmp_path / "c.json"
    broken.write_text(json.dumps(
        {"cmd": "x", "rc": 1, "parsed": None,
         "tail": 'runcated {"metric": "lat_p50", "va'}))
    assert records_from_driver_file(str(broken)) == []
    assert records_from_driver_file(str(tmp_path / "missing.json")) == []


# ---------------------------------------------------------------------------
# Ledger


def test_ledger_roundtrip_skips_torn_lines_refuses_foreign_schema(
        tmp_path):
    ledger = tmp_path / "trend.jsonl"
    n = servetrend.append_bench_run(_emit_line(), str(ledger))
    assert n == 1
    with open(ledger, "a", encoding="utf-8") as f:
        f.write('{"torn": ')  # a concurrent append died mid-line
    recs = load_ledger(str(ledger))
    assert len(recs) == 1 and "_seq" not in recs[0]
    with open(ledger, "a", encoding="utf-8") as f:
        f.write("\n" + json.dumps(
            {"schema": "servetrend/999", "metric": "m",
             "value": 1.0}) + "\n")
    with pytest.raises(ValueError, match="servetrend/999"):
        load_ledger(str(ledger))


def test_gather_orders_mixed_sources_and_stamps_seq(tmp_path):
    ledger = tmp_path / "trend.jsonl"
    servetrend.append_bench_run(_emit_line(value=90.0), str(ledger))
    capture = tmp_path / "BENCH_x.json"
    capture.write_text(json.dumps(
        {"cmd": "x", "rc": 0, "parsed": _emit_line(value=110.0)}))
    recs = gather([str(ledger), str(capture)])
    assert [r["_seq"] for r in recs] == [0, 1]
    assert [r["value"] for r in recs] == [90.0, 110.0]


# ---------------------------------------------------------------------------
# The gate


def test_gate_flags_regression_beyond_band_and_exits_nonzero(tmp_path):
    history = [_rec("lat", 100.0 + i, seq=i) for i in range(3)]
    ok_report = gate(history + [_rec("lat", 104.0, seq=3)])
    assert ok_report["ok"] and ok_report["gated"] == 1
    bad = history + [_rec("lat", 160.0, seq=3)]  # +60% > 35% cpu band
    report = gate(bad)
    assert not report["ok"] and report["regressions"] == 1
    [entry] = report["results"]
    assert entry["status"] == "regression" and entry["delta"] > 0.35
    # The CLI exit code is the contract CI wires on.
    ledger = tmp_path / "bad.jsonl"
    servetrend.append_records(bad, str(ledger))
    assert servetrend.main(["gate", str(ledger)]) == 2
    good = tmp_path / "good.jsonl"
    servetrend.append_records(
        history + [_rec("lat", 104.0, seq=3)], str(good))
    assert servetrend.main(["gate", str(good)]) == 0


def test_gate_direction_respects_higher_is_better():
    history = [_rec("thr", 100.0, unit="qps", seq=i) for i in range(3)]
    drop = gate(history + [_rec("thr", 50.0, unit="qps", seq=3)])
    assert not drop["ok"]
    rise = gate(history + [_rec("thr", 160.0, unit="qps", seq=3)])
    assert rise["ok"]
    assert rise["results"][0]["status"] == "improved"


def test_gate_refuses_cross_provenance_comparison():
    # cpu newest vs tpu-only history: refused, NOT compared.
    recs = [_rec("lat", 10.0, platform="tpu", device_kind="v4", seq=0),
            _rec("lat", 11.0, platform="tpu", device_kind="v4", seq=1),
            _rec("lat", 500.0, platform="cpu", seq=2)]
    report = gate(recs)
    [entry] = report["results"]
    assert report["ok"] and report["gated"] == 0
    assert entry["status"] == "no_comparable_history"
    assert entry["refused_provenance"] == ["tpu/v4"]
    # Same platform, different chip generation: still refused.
    recs = [_rec("lat", 10.0, platform="tpu", device_kind="v4", seq=0),
            _rec("lat", 30.0, platform="tpu", device_kind="v5e", seq=1)]
    assert gate(recs)["results"][0]["status"] == "no_comparable_history"


def test_gate_excludes_stale_replays_from_both_sides():
    recs = [_rec("lat", 100.0, seq=0),
            _rec("lat", 101.0, seq=1),
            _rec("lat", 500.0, stale=True, seq=2)]  # replay, not newest
    report = gate(recs)
    [entry] = report["results"]
    assert entry["status"] == "ok" and entry["newest"] == 101.0
    all_stale = [_rec("lat", 1.0, stale=True, seq=0)]
    assert gate(all_stale)["results"][0]["status"] == "all_stale"


def test_gate_band_override_and_spread_widening():
    # History spread wider than the floor widens the band honestly.
    history = [_rec("lat", v, seq=i)
               for i, v in enumerate((80.0, 100.0, 120.0))]
    wide = gate(history + [_rec("lat", 138.0, seq=3)])
    assert wide["ok"]  # spread (40/100) > cpu floor 0.35 covers +38%
    tight = gate(history + [_rec("lat", 138.0, seq=3)], band=0.10)
    assert not tight["ok"]


def test_gate_min_history_knob():
    recs = [_rec("lat", 100.0, seq=0), _rec("lat", 101.0, seq=1)]
    assert gate(recs)["gated"] == 1
    report = gate(recs, min_history=5)
    assert report["gated"] == 0
    assert report["results"][0]["status"] == "insufficient_history"


def test_cli_gate_with_no_usable_records_fails_loudly(tmp_path):
    empty = tmp_path / "empty.json"
    empty.write_text("{}")
    assert servetrend.main(["gate", str(empty)]) == 1


def test_cli_ingest_roundtrip(tmp_path, capsys):
    capture = tmp_path / "BENCH_x.json"
    capture.write_text(json.dumps(
        {"cmd": "x", "rc": 0, "parsed": _emit_line()}))
    ledger = tmp_path / "trend.jsonl"
    assert servetrend.main(
        ["ingest", str(capture), "--ledger", str(ledger)]) == 0
    assert len(load_ledger(str(ledger))) == 1
    out = capsys.readouterr().out
    assert "appended 1 record(s)" in out
