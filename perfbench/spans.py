"""The server's request traces (/monitoring/traces, Chrome-trace JSON)
as one record per request: its envelope, its annotations and its stage
spans. The per-layer readers in metrics/ take what they need from here.
Times are microseconds on the server's own clock."""

from __future__ import annotations


def requests_from_chrome(payload: dict) -> list[dict]:
    by_tid: dict[int, dict] = {}
    for event in payload.get("traceEvents", []):
        if event.get("ph") != "X":
            continue
        req = by_tid.setdefault(event["tid"], {"spans": []})
        if event.get("cat") == "request":
            req.update(api=event["name"], ts=event["ts"], dur=event["dur"],
                       args=event.get("args", {}))
        else:
            req["spans"].append((event["name"], event["ts"], event["dur"],
                                 event.get("args", {})))
    return [r for r in by_tid.values() if "ts" in r]


def of_signature(requests: list[dict], signature: str) -> list[dict]:
    """Requests of one signature that succeeded ('' is serving_default)."""
    wanted = {signature, ""} if signature == "serving_default" \
        else {signature}
    return [r for r in requests
            if r["args"].get("signature", "") in wanted
            and str(r["args"].get("status", "0")) == "0"]


def stage_ms(request: dict, names) -> float | None:
    """Summed duration of the named stages in one request; None when it
    has none of them (a stage may repeat, e.g. per chunk)."""
    durs = [dur for name, _, dur, _ in request["spans"] if name in names]
    return sum(durs) / 1e3 if durs else None


def per_request_ms(requests: list[dict], names) -> list[float]:
    values = (stage_ms(r, names) for r in requests)
    return [v for v in values if v is not None]


def distinct_spans(requests: list[dict], name: str) -> list[tuple]:
    """Spans of one name, once each: a batch's spans are written onto
    every rider's trace with the same start and duration."""
    seen = {(ts, dur): args for r in requests
            for n, ts, dur, args in r["spans"] if n == name}
    return sorted((ts, dur, args) for (ts, dur), args in seen.items())


def last_ts(requests: list[dict]) -> float:
    """The server's clock at its newest request (0 for none)."""
    return max((r["ts"] for r in requests), default=0.0)


def in_window(requests: list[dict], after_ts: float, first_sent: float,
              seconds: float) -> list[dict]:
    """The requests that began inside the measured window. The server's
    clock and the client's are joined at the run's first request: the
    earliest one the server traced after `after_ts` (its newest before
    the load generators started) is the one the generators sent first,
    `first_sent` seconds from the window's opening on the client's clock."""
    mine = [r for r in requests if r["ts"] > after_ts]
    if not mine:
        return []
    opens = min(r["ts"] for r in mine) - first_sent * 1e6
    return [r for r in mine if opens <= r["ts"] < opens + seconds * 1e6]
