"""One run of one cell of BENCHMARK.json, on the machine it is started on.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python3 perfbench/run.py --sweep --workload <open-loop cell> --rates 400,800,...

This parent never imports jax. It finds or makes the seeded export
(a child pinned to the CPU), boots `python -m
min_tfs_client_tpu.server.main` as the one process that holds the chip,
checks the answers and warms the cell's own shapes, drives gRPC traffic
from a load-generator worker, reads the server's monitoring endpoints,
stops the server, and prints the result as its last line. Without a TPU
it exits non-zero and prints no result. `--sweep` is not a cell and the
driver never runs it: it steps the rate of an open-loop cell inside one
server boot to find the knee that the traffic file then fixes.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import pathlib
import re
import shutil
import signal
import subprocess
import sys
import threading
import time

REPO = pathlib.Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from perfbench import (host_track, metrics, server as srv, spans,  # noqa: E402
                       stats, trace_reduce)
from perfbench.traffic import build_plan  # noqa: E402

PLATFORM = "tpu"
WORK = REPO / ".perfbench"    # fixed: exports, compile cache, run files
DEADLINE_S = 1150.0           # a first run may take 1200 s, compile included
TRACE_RING = 65536            # traced runs keep every request's spans,
                              # times the mix's `window_scale`


def fail(message: str, code: int = 2) -> "NoReturn":
    """No result line: the driver must not read a number from this run."""
    print(f"perfbench: {message}", file=sys.stderr)
    srv.stop_all()
    sys.exit(code)


# ---------------------------------------------------------------------------
# What to run: BENCHMARK.json names everything, files hold it


def load_cell(name: str) -> dict:
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        fail(f"no workload {name!r} in BENCHMARK.json; known: "
             f"{sorted(cells)}")
    cell = cells[name]
    config_entry = next(c for c in bench["configs"]
                        if c["name"] == cell["config"])
    config = json.loads((REPO / config_entry["file"]).read_text())
    traffic = json.loads((pathlib.Path(__file__).parent / "traffic"
                          / f"{cell['traffic']}.json").read_text())

    def of_cell(kind: str) -> list[str]:
        return [m["name"] for m in bench[kind]
                if name in m.get("workloads", [name])]

    return {"cell": cell, "config": config, "traffic": traffic,
            "bench": bench, "config_file": REPO / config_entry["file"],
            "end_to_end": of_cell("end_to_end"),
            "per_layer": of_cell("per_layer")}


def peak_for(kind: str) -> dict:
    peaks = json.loads((pathlib.Path(__file__).parent
                        / "peaks.json").read_text())
    if kind not in peaks:
        fail(f"device kind {kind!r} is not in perfbench/peaks.json: an "
             "unknown device is an error, not a default")
    return peaks[kind]


# ---------------------------------------------------------------------------
# Set-up: export, boot, check, warm


def ensure_export(config: dict, config_file: pathlib.Path) -> pathlib.Path:
    """The seeded export, made once per (configuration, weight seed) at a
    fixed path in the checkout; only a checkout's first run pays it."""
    serve = config["serve"]
    out = WORK / "models" / f"{config_file.stem}-w{serve['weight_seed']}"
    if not (out / "DONE").exists():
        shutil.rmtree(out, ignore_errors=True)
        out.mkdir(parents=True)
        child = srv.spawn(
            [sys.executable, str(REPO / "perfbench" / "children.py"),
             "export", str(config_file), str(out)],
            dict(os.environ, JAX_PLATFORMS="cpu"))
        if child.wait() != 0:
            fail(f"export child exited rc={child.returncode}")
    return out


def cache_dir() -> pathlib.Path:
    """Where the server keeps JAX's persistent compilation cache: where
    the environment says, else the benchmark's own fixed directory."""
    return pathlib.Path(os.environ.get("JAX_COMPILATION_CACHE_DIR",
                                       WORK / "jax_cache"))


def cached_programs() -> set[str]:
    """The cache's entries by name: one `*-cache` file a compiled
    program. Names and not a count: a cache held to a size drops an old
    entry for a new one."""
    return {p.name for p in cache_dir().glob("*-cache")}


def start_server(spec: dict, export_dir, trace_ring: int):
    """Boot the server on the export and refuse any device but the
    cell's; returns (server, device)."""
    server = srv.Server(WORK / "run", export_dir, spec["config"]["serve"],
                        platform=PLATFORM, cache_dir=cache_dir(),
                        trace_ring=trace_ring)
    device = server.device()
    if (device["platform"] != PLATFORM
            or device["count"] < spec["cell"]["chips"]):
        fail(f"the cell asks for {spec['cell']['chips']} {PLATFORM} chip(s); "
             f"JAX reports {device}")
    return server, device


def boot_check_and_warm(spec: dict, trace_ring: int):
    """Export (or find it), boot, check the answers and warm the cell's
    shapes; returns (server, export directory, device, verdict, seconds
    by phase).

    The server that meets the window has LOADED every program from the
    persistent cache and compiled none: a process that compiled its
    programs itself answers 10-13% fewer decode steps a second in the
    same window (PERF.md section 6, PR 45: 872.5 and 899.1 against
    989-1,025 in the same checkouts' next runs; only `setup_s` told such
    a run from the others). So where set-up added an entry to the cache,
    the server is stopped and booted once more, and the check and the
    warm-up run again on the one that stays. Only a checkout's first run
    pays that."""
    run_dir = WORK / "run"
    shutil.rmtree(run_dir, ignore_errors=True)
    (run_dir / "profile").mkdir(parents=True)
    clock = time.monotonic()
    export_dir = ensure_export(spec["config"], spec["config_file"])
    spent = {"export_s": time.monotonic() - clock, "boot_s": 0.0,
             "check_and_warm_s": 0.0, "programs_compiled": []}
    for last in (False, True):
        known, clock = cached_programs(), time.monotonic()
        server, device = start_server(spec, export_dir, trace_ring)
        booted = time.monotonic()
        verdict = check_and_warm(server, spec, export_dir, run_dir)
        spent["boot_s"] += booted - clock
        spent["check_and_warm_s"] += time.monotonic() - booted
        spent["programs_compiled"].append(len(cached_programs() - known))
        if last or not spent["programs_compiled"][-1]:
            return server, export_dir, device, verdict, spent
        server.terminate()


def alias_workaround(server) -> dict | None:
    """What the export's loader stub (children.py:_SERVABLE_STUB) said in
    the server's log: how many signature names it kept of those the
    program's loader gave. Once it drops none, the program no longer
    aliases signatures and the stub has nothing left to work around: say
    so loudly, and go on (a run that failed here would fail the very PR
    that repairs the program, which may not edit the yardstick)."""
    said = re.search(r"perfbench-stub: kept (\d+) of (\d+) signature names",
                     server.log.read_text(errors="replace"))
    if said is None:
        return None
    kept, given = int(said.group(1)), int(said.group(2))
    if kept == given:
        print("perfbench: NOTICE: the program's loader no longer gives one "
              "signature under two names; the next benchmark PR should "
              "remove _SERVABLE_STUB from perfbench/children.py (PERF.md, "
              "Open questions)", file=sys.stderr)
    return {"names_kept": kept, "names_given": given}


class CheckContext:
    """What a configuration's `check` sees: the live server, the
    reference's expectations, the cell's files. What `check` leaves in
    `deferred` (arrays by name) goes to the reference's `verify`, in a
    CPU-pinned child after the server has gone."""

    def __init__(self, client, config, traffic, expected):
        self.client, self.config = client, config
        self.traffic, self.expected = traffic, expected
        self.deferred: dict = {}

    def predict(self, signature: str, inputs: dict) -> dict:
        from min_tfs_client_tpu.tensor.codec import tensor_proto_to_ndarray

        resp = self.client.predict_request(
            self.config["serve"]["model_name"], inputs,
            timeout=900, signature_name=signature)
        return {k: tensor_proto_to_ndarray(v)
                for k, v in resp.outputs.items()}


def check_and_warm(server, spec, export_dir, run_dir) -> dict:
    """Correctness outside the timed window; the check's own requests
    compile or load every shape the cell uses."""
    import numpy as np

    from min_tfs_client_tpu.client import TensorServingClient
    from perfbench.children import load_reference

    config, traffic = spec["config"], spec["traffic"]
    expected = dict(np.load(export_dir / "expected.npz"))
    with TensorServingClient("127.0.0.1", server.grpc_port) as client:
        ctx = CheckContext(client, config, traffic, expected)
        verdict = load_reference(config).check(ctx)
    if ctx.deferred:
        np.savez(run_dir / "deferred.npz", **ctx.deferred)
    return verdict


def verify_deferred(spec, export_dir, run_dir, verdict: dict) -> None:
    """The reference's `verify` on what the check deferred: a CPU-pinned
    child, after the window and after the server has gone, so it costs
    neither the set-up nor the measured window. Its findings join the
    verdict; a child that fails makes the run incorrect."""
    if not (run_dir / "deferred.npz").exists():
        return
    clock = time.monotonic()
    found = run_dir / "verified.json"
    child = srv.spawn(
        [sys.executable, str(REPO / "perfbench" / "children.py"), "verify",
         str(spec["config_file"]), str(export_dir),
         str(run_dir / "deferred.npz"), str(found)],
        dict(os.environ, JAX_PLATFORMS="cpu"))
    later = (json.loads(found.read_text()) if child.wait() == 0
             else {"ok": False, "verify_child_rc": child.returncode})
    # Popped first, whatever the check said: the child's `ok` must never
    # reach `update` and stand in for a check that failed.
    later_ok = later.pop("ok")
    verdict["ok"] = bool(verdict["ok"] and later_ok)
    verdict.update(later)
    verdict.setdefault("seconds", {})["verify"] = time.monotonic() - clock


# ---------------------------------------------------------------------------
# The window: one load-generator worker, and what the host did meanwhile


def run_worker(server, spec, plan, seed: int, seconds: float, run_dir,
               lead_s: float, tag: str = "w"):
    """Start the worker, open the window when it is ready, and return
    (t0 on CLOCK_MONOTONIC, a function that waits for the records)."""
    config, traffic = spec["config"], spec["traffic"]
    plan = dict(
        plan, worker=0, seed=seed, seconds=seconds, host="127.0.0.1",
        port=server.grpc_port, model=config["serve"]["model_name"],
        signature=traffic["signature"], timeout_s=traffic["timeout_s"],
        threads=traffic.get("generator", {}).get("threads", 32),
        request_inputs=config["request_inputs"],
        vocab_size=config["vocab_size"])
    plan_file = run_dir / f"{tag}0.plan.json"
    plan_file.write_text(json.dumps(plan))
    out_file = run_dir / f"{tag}0.records.json"
    proc = srv.spawn(
        [sys.executable, str(REPO / "perfbench" / "loadgen.py"),
         str(plan_file), str(out_file)], dict(os.environ),
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    line = proc.stdout.readline()
    if line.strip() != "ready":
        fail(f"the load-generator worker did not get ready: {line!r}")
    t0 = time.monotonic() + lead_s + 0.25
    proc.stdin.write(f"{t0!r}\n")
    proc.stdin.flush()

    def collect() -> dict:
        if proc.wait(timeout=seconds + lead_s + 300) != 0:
            fail(f"the load-generator worker exited rc={proc.returncode}")
        return {"requests": [], "sessions": [], "generator": None,
                **json.loads(out_file.read_text())}

    return t0, collect


def process_cpu_s(pid: int) -> float | None:
    """User + system CPU seconds of a process and its threads so far
    (`/proc/<pid>/stat`); None where the host does not say."""
    try:
        fields = pathlib.Path(f"/proc/{pid}/stat").read_text() \
            .rsplit(")", 1)[1].split()
        return (int(fields[11]) + int(fields[12])) / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return None


def host_snapshot(server) -> dict:
    """One reading of what the window's whole length is judged by: the
    server's own counters (`/monitoring/runtime`), its CPU seconds as
    the host books them, the load average."""
    runtime = server.runtime()
    return {"at": time.monotonic(), "runtime": runtime,
            "server_cpu_s": process_cpu_s(server.proc.pid),
            "loadavg": list(os.getloadavg())}


def whole_window(opened: dict, closed: dict, records: dict,
                 seconds: float) -> dict:
    """The host over the WHOLE window, traced or not (a capture sees 4 s
    of a profiled server): the collector's pauses by generation, the
    event loop's CPU share (the server's own figure: its last minute at
    the close) and its stalls, the server's CPU in cores, the load
    generator's own account (loadgen.py's Probe), the load average, and
    the rate in each eighth of the window."""
    span = closed["at"] - opened["at"]
    gc0 = opened["runtime"].get("gc_pause_seconds", {})
    gc1 = closed["runtime"].get("gc_pause_seconds", {})
    grpc0 = opened["runtime"].get("grpc", {})
    grpc1 = closed["runtime"].get("grpc", {})
    cpu0, cpu1 = opened["server_cpu_s"], closed["server_cpu_s"]
    done = ([(t, 1) for s in records["sessions"] for t in s["steps"]]
            + [(r["done"], r["outputs"]) for r in records["requests"]
               if r["ok"]])
    eighth = seconds / 8
    return {
        "span_s": span,
        "gc_pause_s": {gen: gc1[gen] - gc0.get(gen, 0.0) for gen in gc1},
        "event_loop_cpu_share_last_minute": grpc1.get("event_loop_cpu_share"),
        "event_loop_stalls": (grpc1.get("lag_over_threshold", 0)
                              - grpc0.get("lag_over_threshold", 0)),
        # The server's longest stall since its boot, at the opening and
        # at the close: where the generator's probe ran seconds late and
        # this did not move, the stall was the generator's alone.
        "event_loop_lag_max_ms": [grpc0.get("event_loop_lag_max_ms"),
                                  grpc1.get("event_loop_lag_max_ms")],
        "server_cpu_cores": None if cpu0 is None or cpu1 is None
        else (cpu1 - cpu0) / span,
        "generator": records["generator"],
        "loadavg": closed["loadavg"],
        "cpu_count": len(os.sched_getaffinity(0)),
        "outputs_per_s_by_eighth": [
            stats.rate_per_s(done, k * eighth, (k + 1) * eighth)
            for k in range(8)]}


def capture_trace(server, t0: float, seconds: float, trace_seconds: float):
    """A device capture in the middle of the window; returns the capture's
    bounds in seconds from the window's opening."""
    start = t0 + max(0.0, (seconds - trace_seconds) / 2)
    time.sleep(max(0.0, start - time.monotonic()))
    begun = time.monotonic() - t0
    body = server.rest(
        f"/monitoring/profile?device=1&seconds={trace_seconds}")
    return {"start": begun, "end": time.monotonic() - t0,
            "seconds": body["seconds"], "files": body["files"]}


def read_device_trace(run_dir) -> dict | None:
    """After the server has gone: a CPU-pinned child turns the capture
    into plain events, and trace_reduce turns those into numbers."""
    events = run_dir / "events.json"
    child = srv.spawn(
        [sys.executable, str(REPO / "perfbench" / "children.py"), "trace",
         str(run_dir / "profile"), str(events)],
        dict(os.environ, JAX_PLATFORMS="cpu"))
    if child.wait() != 0:
        return None
    return json.loads(events.read_text())


# ---------------------------------------------------------------------------
# One run


class Run:
    """What the metric readers see (perfbench/metrics/<name>.py)."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def kernel(self, name: str):
        return metrics.load_file(
            pathlib.Path(__file__).parent / "kernels" / f"{name}.py")


def attempted_failed(records: dict, seconds: float) -> tuple[int, int]:
    """Operations asked of the server in the window, and those of them
    that failed or were refused."""
    rows = stats.due_in_window(records["requests"], 0.0, seconds)
    attempted = len(rows)
    failed = sum(not r["ok"] for r in rows)
    for s in records["sessions"]:
        steps = sum(stats.in_window(t, 0.0, seconds) for t in s["steps"])
        live = s["done"] > 0.0 and s["due"] < seconds
        attempted += steps + (1 if live else 0)
        failed += 1 if (live and not s["ok"]) else 0
    return attempted, failed


def steadiness(records: dict, seconds: float, page_tokens: int) -> dict:
    """For a cell of sessions: was the state steady? The live-session
    count's range over the window, and the share of the window at each
    block-table width implied by the longest live session (the pool's
    width is the power of two at or above that session's pages)."""
    widths: dict[int, int] = {}
    live_counts = []
    sessions = records["sessions"]
    for k in range(200):
        t = seconds * (k + 0.5) / 200
        live = [s for s in sessions
                if s.get("init_done", s["done"]) <= t < s["done"]]
        live_counts.append(len(live))
        if live:
            tokens = max(sum(x <= t for x in s["steps"]) + 1 for s in live)
            pages = -(-tokens // page_tokens)
            width = 1 << (pages - 1).bit_length()
            widths[width] = widths.get(width, 0) + 1
    return {"live_min": min(live_counts), "live_max": max(live_counts),
            "width_share": {str(w): n / 200 for w, n in sorted(widths.items())}}


def checked(verdict: dict, config: dict, compiled: int, rc: int) -> dict:
    """Each number `correct` was decided from, beside the limits the
    configuration's file sets for them (short plain names, no lists):
    what the driver keeps of a run that was not correct."""
    numbers = {k: v for k, v in verdict.items()
               if isinstance(v, (int, float, bool)) and k != "ok"}
    limits = {k: v for k, v in config.get("correctness", {}).items()
              if isinstance(v, (int, float))}
    return {"read": {**numbers, "compiles_in_window": compiled,
                     "server_exit_code": rc},
            "limits": {**limits, "compiles_in_window": 0,
                       "server_exit_code": 0}}


def result_line(*, correct: bool, attempted: int, failed: int,
                metric_values: dict, device: dict,
                reduced: dict | None = None, named_gaps=None,
                compared: dict | None = None) -> dict:
    """The one object the driver reads, printed as the last line. A
    traced run (`reduced` given) adds the device's busy and window
    seconds and the breakdown, its gaps named where the capture has a
    host track (`named_gaps`)."""
    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(failed), "metrics": metric_values,
              "device": dict(device)}
    if reduced is not None:
        result["device"].update(busy_s=reduced["busy_s"],
                                window_s=reduced["window_s"])
        result["breakdown"] = trace_reduce.breakdown(reduced, named_gaps)
    if compared is not None:
        result["checked"] = compared   # the line's last key
    return result


def run_cell(args) -> int:
    t_start = time.monotonic()
    spec = load_cell(args.workload)
    config, traffic = spec["config"], spec["traffic"]
    # A mix may ask for a window of `window_scale` times --seconds: a
    # closed loop judged on a rate wants the length (the collector's
    # pauses, a stall of the host), and pays for it in this cell alone.
    scale = float(traffic.get("window_scale", 1))
    seconds = float(args.seconds) * scale
    run_dir, bench = WORK / "run", spec["bench"]
    server, export_dir, device, verdict, spent = boot_check_and_warm(
        spec, math.ceil(TRACE_RING * scale) if args.trace else 0)
    t_warm = time.monotonic()
    peak = peak_for(device["kind"])
    aliases = alias_workaround(server)

    lead = float(traffic.get("ramp_s", traffic.get("lead_in_s", 0.0)))
    plan = build_plan(traffic, args.seed, seconds)
    compiles_before = server.runtime()["compile"]["total_compiles"]
    traced_before = spans.last_ts(spans.requests_from_chrome(
        server.rest("/monitoring/traces?limit=1"))) if args.trace else 0.0
    t0, collect = run_worker(server, spec, plan, args.seed, seconds,
                             run_dir, lead)
    setup_s = t0 - t_start
    time.sleep(max(0.0, t0 - time.monotonic()))
    opened = host_snapshot(server)
    capture = None
    if args.trace:
        capture = capture_trace(server, t0, seconds,
                                float(traffic["trace_seconds"]))
    time.sleep(max(0.0, t0 + seconds - time.monotonic()))
    closed = host_snapshot(server)
    records = collect()
    runtime_after = server.runtime()
    compiled = runtime_after["compile"]["total_compiles"] - compiles_before
    device = server.device(runtime_after)
    requests, reduced = [], None
    if args.trace:
        first_sent = min(r["sent"] for r in records["requests"]
                         + records["sessions"])
        requests = spans.in_window(
            spans.requests_from_chrome(server.rest("/monitoring/traces")),
            traced_before, first_sent, seconds)
    rc = server.terminate()
    if args.trace:
        events = read_device_trace(run_dir)
        reduced = events and trace_reduce.reduce(events, capture["seconds"])
    verify_deferred(spec, export_dir, run_dir, verdict)

    run = Run(records=records, seconds=seconds, setup_s=setup_s,
              config=config, traffic=traffic,
              requests=spans.of_signature(requests, traffic["signature"]),
              trace=reduced, capture=capture, peak=peak,
              memory_peak_bytes=device["memory_peak_bytes"])
    attempted, failed = attempted_failed(records, seconds)
    info = {"setup": {**spent, "workers_and_lead_in_s": t0 - t_warm},
            "check": verdict, "compiles_in_window": compiled,
            "server_exit_code": rc, "cache_dir": server.cache_dir,
            "alias_workaround": aliases,
            "whole_window": whole_window(opened, closed, records, seconds)}
    if records["sessions"]:
        info["steadiness"] = steadiness(
            records, seconds,
            next(iter(config["kernels"].values())).get("page_tokens", 16))
    if args.trace and reduced:
        info["programs"] = {
            name: {"runs": len(runs),
                   "median_ms": stats.percentile(runs, 50) * 1e3}
            for name, runs in reduced["modules"].items()}
    # Every metric of the cell, for the reader of the log; the result
    # line below carries only what this kind of run reports.
    info["all_metrics"] = metrics.read_all(
        spec["end_to_end"] + spec["per_layer"], run, bench)
    print(json.dumps({"info": info}), flush=True)

    if args.trace and not (reduced and reduced["busy_s"] > 0):
        fail("the traced run saw no operation on the device")
    result = result_line(
        correct=bool(verdict["ok"]) and compiled == 0 and rc == 0,
        attempted=attempted, failed=failed, device=device, reduced=reduced,
        named_gaps=(host_track.of_run(run) or {}).get("gaps"),
        compared=checked(verdict, config, compiled, rc),
        metric_values=metrics.read_all(
            spec["per_layer"] if args.trace else spec["end_to_end"],
            run, bench))
    srv.stop_all()
    print("perfbench: compared "
          + json.dumps(result["checked"]["read"]) + "\nperfbench: limits "
          + json.dumps(result["checked"]["limits"]), file=sys.stderr,
          flush=True)
    print(json.dumps(result), flush=True)
    return 0


# ---------------------------------------------------------------------------
# The sweep: find the knee of an open-loop cell, once


def run_sweep(args) -> int:
    spec = load_cell(args.workload)
    config, traffic = spec["config"], spec["traffic"]
    if traffic["kind"] != "open_loop":
        fail("only an open-loop cell has a knee to sweep for")
    step_s = float(args.seconds)
    run_dir = WORK / "run"
    server, _, device, verdict, _ = boot_check_and_warm(spec, TRACE_RING)
    table = []
    lead = float(traffic.get("lead_in_s", 0.0))
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        plan = build_plan(traffic, args.seed, step_s, rate=rate)
        t0, collect = run_worker(server, spec, plan, args.seed, step_s,
                                 run_dir, lead, tag=f"r{k}w")
        records = collect()
        rows = stats.due_in_window(records["requests"], 0.0, step_s)
        lat = [stats.first_output_ms(r) for r in rows if r["ok"]]
        half = len(rows) // 2
        by_due = sorted(rows, key=lambda r: r["due"])
        first = [stats.first_output_ms(r) for r in by_due[:half] if r["ok"]]
        second = [stats.first_output_ms(r) for r in by_due[half:] if r["ok"]]
        served = spans.of_signature(spans.requests_from_chrome(
            server.rest(f"/monitoring/traces?limit={len(rows)}")),
            traffic["signature"])
        run = Run(requests=served, config=config, traffic=traffic)
        row = {
            "rate_per_s": rate, "requests": len(rows),
            "failed": sum(not r["ok"] for r in rows),
            "p50_ms": stats.percentile(lat, 50),
            "p95_ms": stats.percentile(lat, 95),
            "p50_first_half_ms": stats.percentile(first, 50),
            "p50_second_half_ms": stats.percentile(second, 50),
            # Answered after the step's end: what was still queued then.
            "backlog_at_end": sum(r["done"] > step_s for r in rows),
            "completed_per_s": sum(
                r["outputs"] for r in rows
                if r["ok"] and r["done"] <= step_s) / step_s,
            "late_p99_ms": stats.percentile(
                [stats.lateness_ms(r) for r in rows], 99),
            "batch_occupancy": metrics.load("batch_occupancy").read(run),
            "queue_wait_p50_ms": metrics.load("queue_wait_p50_ms").read(run),
        }
        # The backlog grew if the second half waited markedly longer
        # than the first: past the knee the queue grows through the step.
        row["backlog_grew"] = bool(
            row["p50_second_half_ms"] > 1.5 * row["p50_first_half_ms"]
            and row["p50_second_half_ms"] - row["p50_first_half_ms"] > 5.0)
        table.append(row)
        print(json.dumps(row), flush=True)
        time.sleep(2.0)  # let the queue drain before the next rate
    server.terminate()
    srv.stop_all()
    out = REPO / "chiprun_out"
    out.mkdir(exist_ok=True)
    (out / f"sweep-{args.workload}.json").write_text(json.dumps(
        {"workload": args.workload, "step_seconds": step_s,
         "seed": args.seed, "device": device, "check": verdict,
         "table": table}, indent=1))
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--sweep", action="store_true")
    parser.add_argument("--rates", default="")
    args = parser.parse_args(argv)
    if args.seconds is None:
        args.seconds = 10.0 if args.sweep else float(json.loads(
            (REPO / "BENCHMARK.json").read_text())["run_seconds"])
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and PLATFORM not in platforms.split(","):
        fail(f"JAX_PLATFORMS={platforms!r} excludes {PLATFORM!r}: there is "
             "no chip to measure on, and the benchmark does not fall back "
             "to another backend")
    if not (REPO / "min_tfs_client_tpu").is_dir():
        fail("there is no system under test in this directory")
    watchdog = threading.Timer(DEADLINE_S, lambda: (
        print("perfbench: out of time", file=sys.stderr),
        srv.stop_all(), os._exit(3)))
    watchdog.daemon = True
    watchdog.start()
    # A run that is told to end (a time limit's SIGTERM) still stops the
    # server it booted: the exit unwinds through the `finally` below.
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(3))
    try:
        return run_sweep(args) if args.sweep else run_cell(args)
    except BaseException:
        log = WORK / "run" / "server.log"
        if log.exists():
            print("---- server.log (tail)\n"
                  + log.read_text(errors="replace")[-3000:], file=sys.stderr)
        raise
    finally:
        srv.stop_all()
        watchdog.cancel()


if __name__ == "__main__":
    sys.exit(main())
