"""servetrend: the gated bench-regression sentry over the bench ledger.

A trajectory of bench captures that nothing READS hides regressions: a
chip-measured regression lands in a JSON file and stays invisible until
a human diffs numbers by hand — and a replayed or cpu number can
masquerade as a chip number. This tool makes the trajectory a gate:

 * every bench run appends schema-versioned trend records — one per
   measured leg, stamped with the knob context AND the measurement
   provenance `{platform, device_kind, probe_outcome}` captured at
   measurement time (bench.py stamps them; `ingest` backfills from
   driver capture files);
 * `servetrend gate` compares the newest non-stale record per
   (metric, platform, device_kind) group against the median of its
   own history inside a noise band, and EXITS NONZERO on a regression
   beyond the band — a recorded regression fails like a test;
 * cross-provenance comparisons are REFUSED, never silently made: a
   cpu record can never gate against a tpu record, a v4 record never
   against a v5e record. A metric whose only history lives on another
   platform reports `no_comparable_history` and gates nothing.

Noise bands are platform-honest: cpu numbers on shared CI hosts jitter
far more than dedicated-chip numbers, so the default band is 35% on
cpu and 15% elsewhere, widened by the observed spread of the history
itself; `--band` overrides. Stale replays (bench's lastgood marking)
are excluded from both sides of every comparison.

Stdlib-only (the bench driver and CI both run it with no serving deps).
Workflow: docs/OBSERVABILITY.md "Alerting & trend gating".
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

SCHEMA = "servetrend/1"
DEFAULT_LEDGER = "bench_trend.jsonl"

# Per-platform default noise-band floors (fractional). cpu legs run on
# whatever shared host CI landed on; chip legs are near-deterministic.
BAND_FLOORS = {"cpu": 0.35}
DEFAULT_BAND_FLOOR = 0.15

_HIGHER_UNITS = ("tokens/s", "qps", "examples/s", "items/s", "/s")

# Context keys worth carrying per record: the knobs the autotuner
# dataset joins on, not the whole emit blob.
_CONTEXT_KEYS = ("model", "batch", "seq_len", "iters", "transport",
                 "params_m", "partitioned", "pages", "block",
                 "chunked_prefill", "chunk", "mfu")


def _higher_is_better(unit: str) -> bool:
    unit = (unit or "").lower()
    return any(unit.endswith(h) or unit == h for h in _HIGHER_UNITS)


def _context_from_extra(extra: dict) -> dict:
    return {k: extra[k] for k in _CONTEXT_KEYS
            if k in extra and isinstance(
                extra[k], (str, int, float, bool))}


def _record(metric: str, value, unit: str, platform: str,
            device_kind, probe_outcome, stale: bool, source: str,
            context: dict) -> dict:
    return {
        "schema": SCHEMA,
        "t": round(time.time(), 3),
        "metric": str(metric),
        "value": float(value),
        "unit": str(unit or ""),
        "higher_is_better": _higher_is_better(unit),
        "platform": str(platform or "unknown"),
        "device_kind": (str(device_kind) if device_kind else None),
        "probe_outcome": str(probe_outcome or "unknown"),
        "stale": bool(stale),
        "source": source,
        "context": context,
    }


def records_from_bench_line(line: dict, source: str = "") -> list[dict]:
    """One bench emit line (`{metric, value, unit, vs_baseline, extra}`)
    -> trend records for the primary leg and every `extra.configs` leg.
    Leg provenance prefers the leg's own measurement-time stamps
    (`measured_platform`, `device_kind`) over the parent's; the `@cpu`
    display suffix marks a duplicate leg on another platform, not a
    distinct metric, so it is stripped after provenance is taken."""
    if not isinstance(line, dict) or "metric" not in line:
        return []
    extra = line.get("extra") or {}
    parent_platform = extra.get("platform", "unknown")
    parent_kind = extra.get("device_kind")
    probe_outcome = extra.get("probe_outcome", "unknown")
    parent_stale = bool(extra.get("stale"))
    records = [_record(
        line["metric"], line.get("value", 0.0), line.get("unit", ""),
        parent_platform, parent_kind, probe_outcome, parent_stale,
        source, _context_from_extra(extra))]
    configs = extra.get("configs") or {}
    if isinstance(configs, dict):
        for metric, leg in configs.items():
            if not isinstance(leg, dict) or "value" not in leg:
                continue
            if metric == line["metric"]:
                continue  # the primary, already recorded above
            platform = leg.get("measured_platform", parent_platform)
            # Staleness is a PER-RECORD stamp (bench's lastgood replay
            # marks each replayed record; live legs carry no marker):
            # a stale tpu replay primary rides next to freshly-measured
            # cpu legs in the same emit line, so the parent's marker
            # must not blanket the legs.
            records.append(_record(
                str(metric).removesuffix("@cpu"), leg["value"],
                leg.get("unit", ""), platform,
                leg.get("device_kind", parent_kind), probe_outcome,
                bool(leg.get("stale")), source,
                _context_from_extra(leg)))
    return records


def records_from_driver_file(path: str) -> list[dict]:
    """One checked-in BENCH_*.json driver capture (`{cmd, rc, parsed,
    tail, ...}`) -> trend records. `parsed` is the bench emit line when
    the driver could parse one; otherwise the tail is scanned backwards
    for the last parseable emit line. Unusable captures (rc-only, tail
    truncated mid-JSON) yield NO records — a broken capture must never
    break the gate, only shrink the history."""
    try:
        with open(path, "r", encoding="utf-8") as f:
            blob = json.load(f)
    except (OSError, ValueError):
        return []
    source = os.path.basename(path)
    if not isinstance(blob, dict):
        return []
    line = blob.get("parsed")
    if not isinstance(line, dict) or "metric" not in line:
        line = None
        for raw in reversed((blob.get("tail") or "").splitlines()):
            raw = raw.strip()
            if not (raw.startswith("{") and raw.endswith("}")):
                continue
            try:
                candidate = json.loads(raw)
            except ValueError:
                continue
            if isinstance(candidate, dict) and "metric" in candidate:
                line = candidate
                break
    if line is None:
        return []
    return records_from_bench_line(line, source=source)


def load_ledger(path: str) -> list[dict]:
    """Read a servetrend JSONL ledger. Unknown schema versions REFUSE
    (raise) — gating against records whose semantics this version does
    not understand would be a silent lie; malformed lines are skipped
    (a torn concurrent append must not break the gate)."""
    records = []
    with open(path, "r", encoding="utf-8") as f:
        for raw in f:
            raw = raw.strip()
            if not raw:
                continue
            try:
                rec = json.loads(raw)
            except ValueError:
                continue
            if not isinstance(rec, dict) or "metric" not in rec:
                continue
            schema = rec.get("schema")
            if schema != SCHEMA:
                raise ValueError(
                    f"{path}: record schema {schema!r} is not {SCHEMA!r}"
                    " — refusing to gate against records this version "
                    "does not understand")
            records.append(rec)
    return records


def gather(paths) -> list[dict]:
    """Records from a mixed list of sources, in the given order (the
    order IS the trend: earlier paths are history, the last path's
    records are newest). `.jsonl` = ledger; `.json` = driver capture or
    a bare bench emit line."""
    records: list[dict] = []
    for path in paths:
        if path.endswith(".jsonl"):
            records.extend(load_ledger(path))
            continue
        recs = records_from_driver_file(path)
        if not recs:
            try:
                with open(path, "r", encoding="utf-8") as f:
                    line = json.load(f)
                recs = records_from_bench_line(
                    line, source=os.path.basename(path))
            except (OSError, ValueError):
                recs = []
        records.extend(recs)
    for seq, rec in enumerate(records):
        rec["_seq"] = seq
    return records


def append_records(records, ledger_path: str) -> int:
    os.makedirs(os.path.dirname(os.path.abspath(ledger_path)),
                exist_ok=True)
    with open(ledger_path, "a", encoding="utf-8") as f:
        for rec in records:
            rec = {k: v for k, v in rec.items() if not k.startswith("_")}
            f.write(json.dumps(rec, sort_keys=True) + "\n")
    return len(records)


def append_bench_run(line: dict, ledger_path: str,
                     source: str = "bench") -> int:
    """bench.py's hook: one emit line -> appended ledger records."""
    return append_records(
        records_from_bench_line(line, source=source), ledger_path)


def _band_for(platform: str, history_values, override) -> float:
    if override is not None:
        return float(override)
    band = BAND_FLOORS.get(platform, DEFAULT_BAND_FLOOR)
    if len(history_values) >= 2:
        med = statistics.median(history_values)
        if med:
            spread = (max(history_values) - min(history_values)) / abs(med)
            band = max(band, spread)
    return band


def gate(records, band=None, min_history: int = 1) -> dict:
    """The regression verdict over a record stream. Groups by
    (metric, platform, device_kind) — provenance IS the group key, so a
    cpu record can never gate against a tpu record. Within each group:
    newest non-stale record vs the median of its earlier non-stale
    history, inside the noise band. Returns the full report; `ok` is
    False iff any group regressed."""
    by_metric: dict = {}
    for rec in records:
        by_metric.setdefault(rec["metric"], []).append(rec)
    results = []
    regressions = 0
    gated = 0
    for metric in sorted(by_metric):
        recs = sorted(by_metric[metric], key=lambda r: r.get("_seq", 0))
        fresh = [r for r in recs if not r.get("stale")]
        if not fresh:
            results.append({"metric": metric, "status": "all_stale",
                            "note": f"{len(recs)} record(s), every one a "
                                    "stale replay — nothing to gate"})
            continue
        newest = fresh[-1]
        prov = (newest["platform"], newest.get("device_kind"))
        history = [r for r in fresh[:-1]
                   if (r["platform"], r.get("device_kind")) == prov]
        refused = [r for r in fresh[:-1]
                   if (r["platform"], r.get("device_kind")) != prov]
        entry = {
            "metric": metric,
            "platform": newest["platform"],
            "device_kind": newest.get("device_kind"),
            "newest": newest["value"],
            "unit": newest["unit"],
            "history": len(history),
        }
        if refused:
            entry["refused_provenance"] = sorted(
                {f"{r['platform']}/{r.get('device_kind') or '?'}"
                 for r in refused})
        if len(history) < min_history:
            entry["status"] = ("no_comparable_history" if refused
                               else "insufficient_history")
            if refused:
                entry["note"] = (
                    "history exists only on mismatched provenance "
                    f"({', '.join(entry['refused_provenance'])}) — "
                    "refusing the cross-platform comparison")
            results.append(entry)
            continue
        values = [r["value"] for r in history]
        baseline = statistics.median(values)
        group_band = _band_for(newest["platform"], values, band)
        entry["baseline"] = round(baseline, 6)
        entry["band"] = round(group_band, 4)
        gated += 1
        if baseline <= 0:
            entry["status"] = "ok"
            results.append(entry)
            continue
        delta = newest["value"] / baseline - 1.0
        entry["delta"] = round(delta, 4)
        if newest.get("higher_is_better"):
            regressed = newest["value"] < baseline * (1.0 - group_band)
            improved = newest["value"] > baseline * (1.0 + group_band)
        else:
            regressed = newest["value"] > baseline * (1.0 + group_band)
            improved = newest["value"] < baseline * (1.0 - group_band)
        if regressed:
            regressions += 1
            entry["status"] = "regression"
        else:
            entry["status"] = "improved" if improved else "ok"
        results.append(entry)
    return {
        "schema": SCHEMA,
        "metrics": len(by_metric),
        "gated": gated,
        "regressions": regressions,
        "ok": regressions == 0,
        "results": results,
    }


# ---------------------------------------------------------------------------
# CLI


def _print_report(report: dict, as_json: bool) -> None:
    if as_json:
        print(json.dumps(report, indent=1))
        return
    for entry in report["results"]:
        status = entry["status"]
        prov = f"{entry.get('platform', '?')}/" \
               f"{entry.get('device_kind') or '?'}" \
            if "platform" in entry else ""
        detail = ""
        if "delta" in entry:
            detail = (f" {entry['newest']:.4g}{entry['unit']} vs median "
                      f"{entry['baseline']:.4g} ({entry['delta']:+.1%}, "
                      f"band ±{entry['band']:.0%}, "
                      f"n={entry['history']})")
        elif "note" in entry:
            detail = f" {entry['note']}"
        print(f"servetrend: [{status:>22}] {entry['metric']} "
              f"{prov}{detail}")
    print(f"servetrend: {report['gated']}/{report['metrics']} metric(s) "
          f"gated, {report['regressions']} regression(s)")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="servetrend",
        description="Gated bench-regression sentry over the BENCH "
                    "trend ledger (docs/OBSERVABILITY.md).")
    sub = parser.add_subparsers(dest="command", required=True)

    p_ingest = sub.add_parser(
        "ingest", help="append records from BENCH driver captures / "
                       "bench emit lines to a ledger")
    p_ingest.add_argument("paths", nargs="+")
    p_ingest.add_argument("--ledger", default=DEFAULT_LEDGER)

    p_show = sub.add_parser("show", help="print a ledger's records")
    p_show.add_argument("--ledger", default=DEFAULT_LEDGER)

    p_gate = sub.add_parser(
        "gate", help="exit nonzero when the newest record of any "
                     "metric regressed beyond its noise band")
    p_gate.add_argument("paths", nargs="*",
                        help="history sources in trend order (driver "
                             "captures, emit lines, .jsonl ledgers); "
                             "with --ledger, the ledger's records come "
                             "first")
    p_gate.add_argument("--ledger", default=None)
    p_gate.add_argument("--band", type=float, default=None,
                        help="override the fractional noise band "
                             "(default: 0.35 on cpu, 0.15 elsewhere, "
                             "widened by the history's own spread)")
    p_gate.add_argument("--min-history", type=int, default=1)
    p_gate.add_argument("--json", action="store_true")

    args = parser.parse_args(argv)
    try:
        if args.command == "ingest":
            records = gather(args.paths)
            n = append_records(records, args.ledger)
            print(f"servetrend: appended {n} record(s) to {args.ledger}")
            return 0 if n else 1
        if args.command == "show":
            for rec in load_ledger(args.ledger):
                print(json.dumps(rec, sort_keys=True))
            return 0
        # gate
        paths = ([args.ledger] if args.ledger else []) + list(args.paths)
        records = gather(paths)
        if not records:
            print("servetrend: no usable records in "
                  f"{len(paths)} source(s) — nothing to gate",
                  file=sys.stderr)
            return 1
        report = gate(records, band=args.band,
                      min_history=args.min_history)
        _print_report(report, args.json)
        return 0 if report["ok"] else 2
    except ValueError as exc:
        print(f"servetrend: {exc}", file=sys.stderr)
        return 2


if __name__ == "__main__":
    sys.exit(main())
