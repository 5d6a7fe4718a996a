"""Benchmark: Predict latency/QPS through the full serving stack, on the chip.

Prints ONE JSON line: {"metric", "value", "unit", "extra"}. With no TPU it
exits non-zero and prints no line; so does a run in which any leg raised or
the deadline passed. A number from another backend is never a result here.

  parent (this process, never imports jax)
    refuses to start when JAX_PLATFORMS excludes the TPU, then runs every
    leg in ONE child (single backend init, shared compile cache) under a
    hard deadline (BENCH_BUDGET seconds, default 900);
  child
    requires jax.devices()[0].platform == "tpu", runs the legs in order,
    appends one JSON record per leg to a results file and stamps each with
    the device that measured it. A leg's exception ends the run.

Legs = the five BASELINE.md rows (half_plus_two→matmul toy, ResNet50,
BERT-base [primary metric], USE ragged strings, T5 decode tokens/s) and
the later additions, all measured through the in-process tpu:// transport:
export → version dir → ServerCore load → handlers → marshalling → jit on
the device. `routed` and `fleet_storm` boot their servers as subprocesses
pinned to the CPU (a chip belongs to one process): they run only when
named with --configs and their records say "cpu".
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys
import tempfile
import time
import traceback

REPO = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(REPO))

ACCEL_CONFIGS = ["bert", "resnet", "bert_int8", "matmul", "use", "t5",
                 "imported", "in_flight", "decode_paged"]
# Their servers are CPU-pinned subprocesses, so they are no part of the
# accelerator set and their records are never stamped with the chip.
CPU_SERVED_CONFIGS = ("routed", "fleet_storm")

BUDGET = float(os.environ.get("BENCH_BUDGET", 900))


def _load_results(out: pathlib.Path) -> list[dict]:
    return [json.loads(line) for line in out.read_text().splitlines()
            if line.strip()]


def _emit(records: list[dict]) -> dict:
    primary = next(
        (r for r in records if r["metric"].startswith("bert_base_p")),
        records[0])
    extra = dict(primary.get("extra", {}))
    extra["platform"] = extra.pop("measured_platform")
    extra.setdefault("transport", "tpu:// in-process")
    extra["configs"] = {
        rec["metric"]: dict(rec.get("extra", {}), value=rec["value"],
                            unit=rec["unit"])
        for rec in records if rec is not primary}
    line = {
        "metric": primary["metric"],
        "value": round(primary["value"], 4),
        "unit": primary["unit"],
        "extra": extra,
    }
    print(json.dumps(line))
    return line


def _append_trend(line: dict) -> None:
    """Append this run's emit line to the servetrend ledger — every
    bench run grows the gated trend history (ROADMAP item 7). Best
    effort: the ledger must never fail the bench."""
    try:
        from min_tfs_client_tpu.observability import servetrend

        n = servetrend.append_bench_run(
            line, str(REPO / "bench_trend.jsonl"), source="bench")
        print(f"bench: appended {n} trend record(s) to "
              "bench_trend.jsonl", file=sys.stderr)
    except Exception:
        traceback.print_exc(file=sys.stderr)


def main() -> int:
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        print(f"bench: JAX_PLATFORMS={platforms!r} excludes the TPU. This "
              "benchmark measures the chip and nothing else; there is no "
              "chip here, so there is no measurement.", file=sys.stderr)
        return 2
    fd, out_name = tempfile.mkstemp(prefix="bench_out_")
    os.close(fd)
    out = pathlib.Path(out_name)
    try:
        try:
            rc = subprocess.run(
                [sys.executable, str(REPO / "bench.py"), "--child",
                 "--out", str(out), "--configs", ",".join(ACCEL_CONFIGS)],
                timeout=BUDGET, cwd=str(REPO)).returncode
        except subprocess.TimeoutExpired:
            print(f"bench: child exceeded BENCH_BUDGET={BUDGET:.0f}s",
                  file=sys.stderr)
            return 124
        if rc != 0:
            print(f"bench: child failed rc={rc}", file=sys.stderr)
            return rc
        _append_trend(_emit(_load_results(out)))
        return 0
    finally:
        out.unlink(missing_ok=True)


# --------------------------------------------------------------------------
# Child: actual measurements (single process, one backend init)
# --------------------------------------------------------------------------

BATCH = 32
SEQ_LEN = 128

_CHILD_START = time.monotonic()
_CHILD_BUDGET = BUDGET * 0.85


def _child_time_left() -> float:
    return _CHILD_BUDGET - (time.monotonic() - _CHILD_START)


_RTT_MS: float | None = None


def _transport_rtt_ms() -> float:
    """p50 of a minimal dispatch+fetch round: the per-request latency floor
    the host-device link imposes regardless of model. Measured once per
    child."""
    global _RTT_MS
    if _RTT_MS is None:
        import jax
        import numpy as np

        f = jax.jit(lambda x: x + 1)
        x = np.zeros((8,), np.float32)
        np.asarray(f(x))
        ts = []
        for _ in range(7):
            t0 = time.perf_counter()
            np.asarray(f(x))
            ts.append((time.perf_counter() - t0) * 1e3)
        ts.sort()
        _RTT_MS = ts[len(ts) // 2]
    return _RTT_MS


def _concurrent_qps(call, *, batch: int, p50_ms: float,
                    threads: int = 8, total: int = 32) -> dict:
    """Throughput with `threads` requests in flight through the full stack
    (the gRPC-server pattern: one executor thread per active request). The
    transport RTT overlaps across in-flight requests, so per-request wall
    approaches the true device+host cost — this is the serving-relevant
    number on a high-latency link, and the implied per-call time bounds
    device time from above.

    Sized to the measured sync p50 so slow platforms (CPU BERT ≈ 7.6 s per
    call) stay inside the child budget; returns {} when even one wave of
    `threads` calls would not fit."""
    import concurrent.futures as cf

    wave_s = max(p50_ms, 1.0) / 1e3  # >= one call-time per wave of threads
    budget_s = min(20.0, max(0.0, _child_time_left() - 15.0) / 2)
    max_calls = int(budget_s / wave_s * threads / 2)  # /2: warm + measure
    if max_calls < threads:
        return {}
    total = max(threads, min(total, max_calls))
    with cf.ThreadPoolExecutor(threads) as pool:
        list(pool.map(lambda _: call(), range(threads)))  # warm the pool
        t0 = time.perf_counter()
        list(pool.map(lambda _: call(), range(total)))
        wall = time.perf_counter() - t0
    per_call_ms = wall / total * 1e3
    return {"qps_pipelined": round(batch * total / wall, 1),
            "pipelined_per_call_ms": round(per_call_ms, 3),
            "pipeline_depth": threads}


_TF_YARDSTICK_CODE = """\
import json, sys, time
import numpy as np
import tensorflow as tf
tf.config.threading.set_intra_op_parallelism_threads(0)
rng = np.random.default_rng(0)
xs = rng.standard_normal(({batch}, 8)).astype("float32")
w = tf.constant(rng.standard_normal((8, 4)).astype("float32"))
b = tf.constant(rng.standard_normal((4,)).astype("float32"))
@tf.function
def model(x):
    return tf.nn.softmax(tf.matmul(x, w) + b)
# Like-for-like with the serving path being measured: every request pays
# request marshal (ndarray->TensorProto), parse (TensorProto->tensor),
# execute, response marshal, response parse. TF's own C-accelerated
# make_tensor_proto/make_ndarray are the reference stack's equivalents.
def serve_once():
    req = tf.make_tensor_proto(xs)
    x = tf.constant(tf.make_ndarray(req))
    out = model(x).numpy()
    resp = tf.make_tensor_proto(out)
    return tf.make_ndarray(resp)
serve_once()
ts = []
for _ in range(300):
    t0 = time.perf_counter(); serve_once(); ts.append((time.perf_counter()-t0)*1e3)
ts.sort()
print(json.dumps({{"p50_ms": ts[len(ts)//2]}}))
"""


_TF_YARDSTICK_SERVER_CODE = _TF_YARDSTICK_CODE.replace(
    """serve_once()
ts = []
for _ in range(300):
    t0 = time.perf_counter(); serve_once(); ts.append((time.perf_counter()-t0)*1e3)
ts.sort()
print(json.dumps({{"p50_ms": ts[len(ts)//2]}}))
""",
    """serve_once()
print(json.dumps({{"ready": True}}), flush=True)
for line in sys.stdin:
    line = line.strip()
    if not line or line == "exit":
        break
    n = int(line)
    ts = []
    for _ in range(n):
        t0 = time.perf_counter(); serve_once(); ts.append((time.perf_counter()-t0)*1e3)
    ts.sort()
    print(json.dumps({{"p50_ms": ts[len(ts)//2]}}), flush=True)
""")
# str.replace silently no-ops when the template drifts, which would leave
# the 300-iter one-shot script running under the stdin protocol (parent
# blocks until the watchdog kills it, yardstick silently lost).
assert _TF_YARDSTICK_SERVER_CODE != _TF_YARDSTICK_CODE, \
    "yardstick server template drifted: replace() matched nothing"


def _chunk_p50(call, n: int) -> float:
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        call()
        ts.append((time.perf_counter() - t0) * 1e3)
    ts.sort()
    return ts[len(ts) // 2]


def _interleaved_yardstick(fw_call, batch: int, rounds: int = 3,
                           chunk: int = 100) -> tuple | None:
    """Framework and TF yardstick samples interleaved in time so both
    see the SAME ambient load (a shared box can swing a solo measurement
    1.5x): alternate fw-chunk / TF-chunk windows, take the median across
    rounds for each side, and report the per-side spread so the one
    head-to-head number the repo commits carries its own error bar. The
    TF side runs as a persistent subprocess (one import cost) answering
    chunk requests over stdin/stdout."""
    if _child_time_left() < 60:
        return None
    proc = None
    try:
        proc = subprocess.Popen(
            [sys.executable, "-c",
             _TF_YARDSTICK_SERVER_CODE.format(batch=batch)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL, text=True, bufsize=1,
            env={k: v for k, v in os.environ.items()
                 if not k.startswith(("JAX_", "PYTHONPATH"))})
        import threading

        watchdog = threading.Timer(90.0, proc.kill)
        watchdog.start()
        try:
            ready = json.loads(proc.stdout.readline())
            if not ready.get("ready"):
                return None
            fw_p50s, tf_p50s = [], []
            for _ in range(rounds):
                fw_p50s.append(_chunk_p50(fw_call, chunk))
                proc.stdin.write(f"{chunk}\n")
                proc.stdin.flush()
                tf_p50s.append(json.loads(proc.stdout.readline())["p50_ms"])
            proc.stdin.write("exit\n")
            proc.stdin.flush()
        finally:
            watchdog.cancel()
            proc.kill()
        fw_p50s.sort()
        tf_p50s.sort()
        fw_med = fw_p50s[len(fw_p50s) // 2]
        tf_med = tf_p50s[len(tf_p50s) // 2]

        def spread(xs):
            return round((xs[-1] - xs[0]) / max(xs[len(xs) // 2], 1e-9), 3)

        yardstick = {
            "value": tf_med, "unit": "ms",
            "interleaved": True, "rounds": rounds, "chunk": chunk,
            "spread": spread(tf_p50s), "fw_p50_ms": round(fw_med, 4),
            "fw_spread": spread(fw_p50s),
            "source": "measured: tensorflow-2.x CPU tf.function + "
                      "make_tensor_proto/make_ndarray marshalling both "
                      "directions (the per-request work the reference "
                      "stack pays), interleaved with the framework's "
                      "own samples on this host",
        }
        return fw_med, yardstick
    except Exception:
        traceback.print_exc(file=sys.stderr)
        if proc is not None:
            proc.kill()
        return None


def _tf_cpu_yardstick(batch: int) -> dict | None:
    """One-shot fallback when the interleaved measurement cannot run
    (TF unavailable / time short): the reference's own runtime executing
    the toy config's computation on this host's CPU, in a subprocess —
    TF and our generated protos must never share a process
    (descriptor-pool collisions)."""
    if _child_time_left() < 45:
        return None
    try:
        res = subprocess.run(
            [sys.executable, "-c", _TF_YARDSTICK_CODE.format(batch=batch)],
            capture_output=True, text=True, timeout=40,
            env={k: v for k, v in os.environ.items()
                 if not k.startswith(("JAX_", "PYTHONPATH"))})
        if res.returncode == 0:
            p50 = json.loads(res.stdout.strip().splitlines()[-1])["p50_ms"]
            return {"value": p50, "unit": "ms",
                    "source": "measured: tensorflow-2.x CPU tf.function + "
                              "make_tensor_proto/make_ndarray marshalling "
                              "both directions (the per-request work the "
                              "reference stack pays), this host"}
    except Exception:
        pass
    return None


def _measure(call, max_iters: int) -> dict:
    """Adaptive: one timed probe call sizes the loop so slow platforms
    (CPU BERT-base ≈ 7.6 s/call) still finish within the child budget."""
    call()  # warmup / compile
    t0 = time.perf_counter()
    call()
    probe_s = time.perf_counter() - t0
    iters = max(3, min(max_iters, int(12.0 / max(probe_s, 1e-4))))
    samples = [probe_s * 1e3]
    for _ in range(iters - 1):
        t0 = time.perf_counter()
        call()
        samples.append((time.perf_counter() - t0) * 1e3)
    samples.sort()
    import numpy as np

    return {"p50": float(np.percentile(samples, 50)),
            "p99": float(np.percentile(samples, 99)),
            "iters": iters}


def _param_count(params) -> int:
    import jax
    import numpy as np

    return sum(int(np.prod(p.shape))
               for p in jax.tree_util.tree_leaves(params))


def _add_mfu(extra: dict, flops: float, p50_ms: float) -> None:
    """mfu_sync from the synchronous p50; mfu from the pipelined per-call
    time when measured — the per-call wall under pipelining bounds device
    time from above, so this MFU is a lower bound on the chip's."""
    peak = _peak_flops_per_s()
    extra["mfu_sync"] = round(flops / (p50_ms / 1e3) / peak, 4)
    per_call = extra.get("pipelined_per_call_ms")
    if per_call:
        extra["mfu"] = round(flops / (per_call / 1e3) / peak, 4)


# Peak dense bf16 FLOP/s per chip, keyed by the device_kind JAX reports.
# Source: Google Cloud documentation, "TPU v5e" system architecture page
# (197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip).
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12}


def _peak_flops_per_s() -> float:
    import jax

    kind = jax.devices()[0].device_kind
    if kind not in PEAK_BF16_FLOPS:
        raise KeyError(
            f"no peak FLOP/s on record for device_kind {kind!r}; add it "
            "to PEAK_BF16_FLOPS with its source before reporting an MFU")
    return PEAK_BF16_FLOPS[kind]


def bench_bert(max_iters: int) -> dict:
    """BASELINE config 3: BERT-base, batch 32, seq 128, Predict p50."""
    import jax
    import numpy as np

    from min_tfs_client_tpu.client import TensorServingClient
    from min_tfs_client_tpu.models import bert, export
    from min_tfs_client_tpu.tensor.codec import tensor_proto_to_ndarray

    config = bert.BertConfig.base()
    params = bert.init_params(jax.random.PRNGKey(0), config)
    base = pathlib.Path(tempfile.mkdtemp(prefix="tpu_bench_")) / "bert_base"
    export.export_servable(base, 1, "bert", {}, params,
                           signature_kwargs={"seq_len": SEQ_LEN})

    client = TensorServingClient(f"tpu://{base}")
    rng = np.random.default_rng(0)
    ids = rng.integers(0, config.vocab_size, (BATCH, SEQ_LEN)).astype(np.int32)
    mask = np.ones((BATCH, SEQ_LEN), np.int32)

    def call():
        resp = client.predict_request(
            "bert_base", {"input_ids": ids, "attention_mask": mask},
            timeout=600)
        out = tensor_proto_to_ndarray(resp.outputs["probabilities"])
        assert out.shape == (BATCH, config.num_labels)

    stats = _measure(call, max_iters)
    n_params = _param_count(params)
    extra = {"model": "bert-base", "batch": BATCH, "seq_len": SEQ_LEN,
             "p99_ms": round(stats["p99"], 4),
             "qps": round(1000.0 / stats["p50"] * BATCH, 1),
             "iters": stats["iters"],
             "params_m": round(n_params / 1e6, 1),
             "transport_rtt_ms": round(_transport_rtt_ms(), 2)}
    if _child_time_left() > 30:
        extra.update(_concurrent_qps(call, batch=BATCH, p50_ms=stats["p50"]))
    # forward ≈ 2 * params * tokens FLOPs
    _add_mfu(extra, 2.0 * n_params * BATCH * SEQ_LEN, stats["p50"])
    return {"metric": f"bert_base_predict_p50_b{BATCH}_s{SEQ_LEN}",
            "value": stats["p50"], "unit": "ms", "extra": extra}


def bench_bert_int8(max_iters: int) -> dict:
    """BERT-base served weight-only int8 (quantize='int8'): int8-resident
    HBM halves weight reads vs bf16 — the small-batch serving win. Its own
    config entry so a mid-run kill never loses the bf16 record."""
    import dataclasses

    import jax
    import numpy as np

    from min_tfs_client_tpu.client import TensorServingClient
    from min_tfs_client_tpu.models import bert, export
    from min_tfs_client_tpu.tensor.codec import tensor_proto_to_ndarray

    config = bert.BertConfig.base()
    params = bert.init_params(jax.random.PRNGKey(0), config)
    base = pathlib.Path(tempfile.mkdtemp(prefix="tpu_bench_")) / "bert_q8"
    export.export_servable(base, 1, "bert", dataclasses.asdict(config),
                           params, signature_kwargs={"seq_len": SEQ_LEN},
                           quantize="int8")
    client = TensorServingClient(f"tpu://{base}")
    rng = np.random.default_rng(0)
    ids = rng.integers(0, config.vocab_size, (BATCH, SEQ_LEN)).astype(np.int32)
    mask = np.ones((BATCH, SEQ_LEN), np.int32)

    def call():
        resp = client.predict_request(
            "bert_q8", {"input_ids": ids, "attention_mask": mask},
            timeout=600)
        out = tensor_proto_to_ndarray(resp.outputs["probabilities"])
        assert np.isfinite(out).all()

    stats = _measure(call, max_iters)
    extra = {"model": "bert-base-int8", "batch": BATCH, "seq_len": SEQ_LEN,
             "p99_ms": round(stats["p99"], 4),
             "qps": round(1000.0 / stats["p50"] * BATCH, 1),
             "iters": stats["iters"],
             "transport_rtt_ms": round(_transport_rtt_ms(), 2)}
    return {"metric": f"bert_base_int8_predict_p50_b{BATCH}_s{SEQ_LEN}",
            "value": stats["p50"], "unit": "ms", "extra": extra}


def bench_matmul(max_iters: int) -> dict:
    """BASELINE config 1 analogue: toy model, single Predict p50."""
    import numpy as np

    from tests import fixtures
    from min_tfs_client_tpu.client import TensorServingClient
    from min_tfs_client_tpu.tensor.codec import tensor_proto_to_ndarray

    base = pathlib.Path(tempfile.mkdtemp(prefix="tpu_bench_")) / "matmul"
    fixtures.write_matmul_model(base)
    client = TensorServingClient(f"tpu://{base}")
    x = np.random.default_rng(0).standard_normal((BATCH, 8)).astype(np.float32)

    def call():
        resp = client.predict_request("matmul", {"x": x})
        out = tensor_proto_to_ndarray(resp.outputs["probs"])
        assert out.shape == (BATCH, 4)

    # Sub-ms calls need more samples than the default cap for a stable
    # p50 — this is the config the TF yardstick is compared against. An
    # explicit BENCH_ITERS cap still wins.
    if not os.environ.get("BENCH_ITERS"):
        max_iters = max(300, max_iters)
    stats = _measure(call, max_iters)
    extra = {"model": "matmul-toy", "batch": BATCH,
             "p99_ms": round(stats["p99"], 4),
             "qps": round(1000.0 / stats["p50"] * BATCH, 1),
             "iters": stats["iters"],
             "transport_rtt_ms": round(_transport_rtt_ms(), 2)}
    grpc_p50 = _grpc_loopback_p50(base, x)
    if grpc_p50 is not None:
        # The hop the reference client always pays (requests.py:49) and
        # tpu:// skips: same model over a real localhost gRPC socket.
        extra["grpc_loopback_p50_ms"] = round(grpc_p50, 3)
    rest_p50 = _rest_loopback_p50(base, x)
    if rest_p50 is not None:
        # Same model over the native epoll HTTP front-end + native JSON
        # tensor codec (net_http.cpp / json_tensor.cpp).
        extra["rest_loopback_p50_ms"] = round(rest_p50, 3)
    # Head-to-head number: interleave framework and TF samples so both
    # sides see the same ambient load; the metric value is then the
    # interleaved framework median (apples-to-apples with the yardstick),
    # with the solo full-run p50 kept in extra for continuity.
    value = stats["p50"]
    inter = _interleaved_yardstick(call, BATCH)
    if inter is not None:
        fw_med, yardstick = inter
        extra["solo_p50_ms"] = round(stats["p50"], 4)
        extra["yardstick_spread"] = yardstick["spread"]
        extra["fw_spread"] = yardstick["fw_spread"]
        value = fw_med
    else:
        yardstick = _tf_cpu_yardstick(BATCH)
    return {"metric": f"toy_predict_p50_b{BATCH}", "value": value,
            "unit": "ms", "extra": extra, "yardstick": yardstick}


def _grpc_loopback_p50(base: pathlib.Path, x) -> float | None:
    """Same toy model served over a real localhost gRPC socket."""
    if _child_time_left() < 30:
        return None
    try:
        from min_tfs_client_tpu.client import TensorServingClient
        from min_tfs_client_tpu.server.server import Server, ServerOptions
        from min_tfs_client_tpu.tensor.codec import tensor_proto_to_ndarray

        srv = Server(ServerOptions(
            grpc_port=0, model_name="matmul", model_base_path=str(base),
            file_system_poll_wait_seconds=0)).build_and_start()
        try:
            with TensorServingClient("127.0.0.1", srv.grpc_port) as client:
                ts = []
                for _ in range(20):
                    t0 = time.perf_counter()
                    resp = client.predict_request("matmul", {"x": x},
                                                  timeout=60)
                    tensor_proto_to_ndarray(resp.outputs["probs"])
                    ts.append((time.perf_counter() - t0) * 1e3)
            ts.sort()
            return ts[len(ts) // 2]
        finally:
            srv.stop()
    except Exception:
        traceback.print_exc(file=sys.stderr)
        return None


def _rest_loopback_p50(base: pathlib.Path, x) -> float | None:
    """Same toy model over the REST surface (native HTTP + JSON codec)."""
    if _child_time_left() < 30:
        return None
    try:
        import json as _json
        import urllib.request

        from min_tfs_client_tpu.server.server import Server, ServerOptions

        # rest_api_port=0 alone disables REST; an enabled monitoring
        # config turns it on at an ephemeral port (same as the e2e tests).
        mon = base.parent / "bench_monitoring.config"
        mon.write_text("prometheus_config { enable: true }\n")
        srv = Server(ServerOptions(
            grpc_port=0, rest_api_port=0, model_name="matmul",
            model_base_path=str(base),
            monitoring_config_file=str(mon),
            file_system_poll_wait_seconds=0)).build_and_start()
        try:
            body = _json.dumps({"inputs": {"x": x.tolist()}}).encode()
            url = (f"http://127.0.0.1:{srv.rest_port}"
                   "/v1/models/matmul:predict")
            ts = []
            for _ in range(20):
                t0 = time.perf_counter()
                with urllib.request.urlopen(
                        urllib.request.Request(url, data=body),
                        timeout=60) as r:
                    r.read()
                ts.append((time.perf_counter() - t0) * 1e3)
            ts.sort()
            return ts[len(ts) // 2]
        finally:
            srv.stop()
            mon.unlink(missing_ok=True)
    except Exception:
        traceback.print_exc(file=sys.stderr)
        return None


def bench_use(max_iters: int) -> dict:
    """BASELINE config 4: USE — string inputs, ragged host tokenize +
    bucketed device encode."""
    import jax
    import numpy as np

    from min_tfs_client_tpu.client import TensorServingClient
    from min_tfs_client_tpu.models import export, use
    from min_tfs_client_tpu.tensor.codec import tensor_proto_to_ndarray

    config = use.USEConfig.v4()
    params = use.init_params(jax.random.PRNGKey(0), config)
    base = pathlib.Path(tempfile.mkdtemp(prefix="tpu_bench_")) / "use_v4"
    export.export_servable(
        base, 1, "use",
        {"vocab_size": config.vocab_size, "hidden_size": config.hidden_size,
         "num_layers": config.num_layers, "num_heads": config.num_heads,
         "intermediate_size": config.intermediate_size,
         "embed_dim": config.embed_dim, "max_tokens": config.max_tokens,
         "seq_buckets": list(config.seq_buckets)},
        params, {})
    client = TensorServingClient(f"tpu://{base}")
    rng = np.random.default_rng(0)
    words = ["serving", "tpu", "latency", "ragged", "sentence", "encoder"]
    texts = np.array(
        [" ".join(rng.choice(words, size=rng.integers(2, 24)))
         .encode("utf-8") for _ in range(BATCH)], object)

    def call():
        resp = client.predict_request("use_v4", {"text": texts}, timeout=600)
        out = tensor_proto_to_ndarray(resp.outputs["embeddings"])
        assert out.shape == (BATCH, config.embed_dim)

    stats = _measure(call, max_iters)
    extra = {"model": "use-v4", "batch": BATCH, "ragged": True,
             "p99_ms": round(stats["p99"], 4),
             "qps": round(1000.0 / stats["p50"] * BATCH, 1),
             "iters": stats["iters"],
             "transport_rtt_ms": round(_transport_rtt_ms(), 2)}
    if _child_time_left() > 25:
        extra.update(_concurrent_qps(call, batch=BATCH, p50_ms=stats["p50"]))
    return {"metric": f"use_v4_predict_p50_b{BATCH}", "value": stats["p50"],
            "unit": "ms", "extra": extra}


def bench_t5(max_iters: int) -> dict:
    """BASELINE config 5: T5-small greedy decode, tokens/s (higher=better)."""
    import jax
    import numpy as np

    from min_tfs_client_tpu.client import TensorServingClient
    from min_tfs_client_tpu.models import export, t5
    from min_tfs_client_tpu.tensor.codec import tensor_proto_to_ndarray

    config = t5.T5Config.small()
    params = t5.init_params(jax.random.PRNGKey(0), config)
    batch, seq, decode_len = 8, 64, 32
    base = pathlib.Path(tempfile.mkdtemp(prefix="tpu_bench_")) / "t5_small"
    export.export_servable(
        base, 1, "t5", {}, params,
        signature_kwargs={"seq_len": seq, "max_decode_len": decode_len})
    client = TensorServingClient(f"tpu://{base}")
    rng = np.random.default_rng(0)
    ids = rng.integers(2, config.vocab_size, (batch, seq)).astype(np.int32)

    def call():
        resp = client.predict_request("t5_small", {"input_ids": ids},
                                      timeout=600)
        out = tensor_proto_to_ndarray(resp.outputs["output_ids"])
        assert out.shape == (batch, decode_len)

    stats = _measure(call, max_iters)
    tok_s = batch * decode_len / (stats["p50"] / 1e3)
    extra = {"model": "t5-small", "batch": batch, "seq_len": seq,
             "decode_len": decode_len,
             "p50_ms": round(stats["p50"], 4),
             "p99_ms": round(stats["p99"], 4),
             "iters": stats["iters"],
             "transport_rtt_ms": round(_transport_rtt_ms(), 2)}
    if _child_time_left() > 25:
        pipe = _concurrent_qps(call, batch=batch, p50_ms=stats["p50"])
        extra.update(pipe)
        if pipe:
            extra["tokens_per_s_pipelined"] = round(
                decode_len * 1e3 / pipe["pipelined_per_call_ms"] * batch, 1)
    if _child_time_left() > 30:
        # BASELINE-5's literal surface: repeated Predict("decode_step")
        # with the KV cache as per-session device state. Each step pays
        # one transport round trip, so this bounds per-token wire latency.
        sid = np.array(b"bench-sess", object)
        client.predict_request("t5_small",
                               {"session_id": sid, "input_ids": ids},
                               signature_name="decode_init", timeout=600)
        client.predict_request("t5_small", {"session_id": sid},
                               signature_name="decode_step", timeout=600)
        steps = min(decode_len - 1, 16)
        t0 = time.perf_counter()
        for _ in range(steps):
            client.predict_request("t5_small", {"session_id": sid},
                                   signature_name="decode_step", timeout=600)
        wall = time.perf_counter() - t0
        client.predict_request("t5_small", {"session_id": sid},
                               signature_name="decode_close", timeout=600)
        extra["tokens_per_s_stepwise"] = round(batch * steps / wall, 1)
        extra["stepwise_ms_per_token"] = round(wall / steps * 1e3, 2)
    if _child_time_left() > 40:
        pooled = _t5_pooled_tokens_per_s(config, params, seq, decode_len)
        if pooled:
            extra.update(pooled)
    return {"metric": f"t5_small_decode_tokens_per_s_b{batch}",
            "value": tok_s, "unit": "tokens/s", "higher_is_better": True,
            "extra": extra}


def _t5_pooled_run(config, params, seq: int, decode_len: int, *,
                   n_sessions: int = 8, prompts=None,
                   warm_full: bool = False, **session_kwargs) -> dict:
    """THE concurrent pooled-decode harness (shared by the t5 and
    decode_paged legs): init N single-sequence sessions, decode them
    concurrently through the shared tick, return
    {tokens_per_s, streams, pool_stats}. warm_full runs one throwaway
    full-length generation first — the paged pool recompiles per
    block-table width bucket, and steady state pays those once per
    deployment, not per session."""
    import threading

    import numpy as np

    from min_tfs_client_tpu.models import t5

    sigs = t5.build_session_signatures(
        params, config, seq_len=seq, max_decode_len=decode_len,
        max_sessions=n_sessions, continuous_batching=True,
        **session_kwargs)
    if prompts is None:
        rng = np.random.default_rng(1)
        prompts = [rng.integers(2, config.vocab_size, (1, seq)).astype(
            np.int32) for _ in range(n_sessions)]
    if warm_full:
        warm = np.asarray(b"warm", object)
        sigs["decode_init"].run({"session_id": warm,
                                 "input_ids": prompts[0]})
        for _ in range(decode_len - 1):
            sigs["decode_step"].run({"session_id": warm})
        sigs["decode_close"].run({"session_id": warm})
    for i, ids in enumerate(prompts):
        sigs["decode_init"].run({
            "session_id": np.asarray(f"b{i}".encode(), object),
            "input_ids": ids})
    streams = [[] for _ in range(n_sessions)]
    # Warm the tick executable before timing (session 0 steps once).
    out = sigs["decode_step"].run({"session_id": np.asarray(b"b0", object)})
    streams[0].append(int(out["token"][0]))

    steps = decode_len - 2
    barrier = threading.Barrier(n_sessions)

    # Each timed step runs under a request trace so the leg's
    # --breakdown table attributes pooled decode time per stage
    # (decode/tick, decode/fetch, host/execute) — the tick leader's
    # trace carries the shared device-round spans.
    from min_tfs_client_tpu.observability import tracing

    def worker(i):
        sid = np.asarray(f"b{i}".encode(), object)
        barrier.wait()
        start = 0 if i else 1  # session 0 already stepped once
        for _ in range(start, steps):
            with tracing.request_trace("decode_step", model="t5"):
                row = sigs["decode_step"].run({"session_id": sid})
            streams[i].append(int(row["token"][0]))

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n_sessions)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    pool = getattr(sigs["decode_init"], "_kv_pool", None)
    pool_stats = pool.stats() if pool is not None else None
    for i in range(n_sessions):
        sigs["decode_close"].run(
            {"session_id": np.asarray(f"b{i}".encode(), object)})
    total_tokens = steps * (n_sessions - 1) + (steps - 1)
    return {"tokens_per_s": round(total_tokens / wall, 1),
            "streams": streams, "pool_stats": pool_stats,
            "n_sessions": n_sessions}


def _t5_pooled_tokens_per_s(config, params, seq: int,
                            decode_len: int) -> dict:
    """Continuous batching: N concurrent single-sequence decode sessions
    share one vmapped device tick per token (SlotPool/TickBatcher) vs N
    independent per-session dispatches."""
    try:
        run = _t5_pooled_run(config, params, seq, decode_len)
        return {
            "tokens_per_s_continuous_batching": run["tokens_per_s"],
            "continuous_batching_sessions": run["n_sessions"],
        }
    except Exception:
        traceback.print_exc(file=sys.stderr)
        return {}


def bench_decode_paged(max_iters: int) -> dict:
    """Paged KV-cache decode (ROADMAP item 1): continuous-batching
    tokens/s with the block-table-paged pool vs the dense slot pool
    (same prompts, token identity recorded), plus the capacity
    demonstration — sessions admitted under ONE fixed KV byte budget for
    a short-prompt mix (paged admits pages-per-used-token, dense admits
    max-length slots)."""
    import jax
    import numpy as np

    from min_tfs_client_tpu.models import t5
    from min_tfs_client_tpu.utils.status import ServingError

    config = t5.T5Config.small()
    params = t5.init_params(jax.random.PRNGKey(0), config)
    seq, decode_len, n_sessions = 64, 32, 8
    rng = np.random.default_rng(1)
    prompts = [rng.integers(2, config.vocab_size, (1, seq)).astype(np.int32)
               for _ in range(n_sessions)]

    # Cost attribution armed for the WHOLE leg: every timed decode step
    # runs under a request trace, so the in-process ledger accumulates
    # per-request vectors (pages x ticks for the paged pool) and the
    # servecost JSONL becomes this leg's dataset artifact — the knob
    # context stamps WHICH configuration produced these costs.
    from min_tfs_client_tpu.observability import costs as costs_mod
    from min_tfs_client_tpu.observability import tracing as tracing_mod

    cost_dir = pathlib.Path(tempfile.mkdtemp(prefix="tpu_bench_costs_"))
    costs_mod.reset()
    costs_mod.reset_ticks()
    costs_mod.configure(
        log_dir=str(cost_dir), sample=1.0,
        context={"leg": "decode_paged", "model": "t5-small",
                 "kv_block_size": 8, "sessions": n_sessions,
                 "decode_len": decode_len})

    # The shared pooled-decode harness drives both pools over the SAME
    # prompts. warm_full primes every tick executable before timing: the
    # paged pool recompiles per block-table width bucket (W = 1, 2, 4
    # over a 32-token generation) — steady-state serving pays those once
    # per deployment, not per session.
    dense = _t5_pooled_run(config, params, seq, decode_len,
                           n_sessions=n_sessions, prompts=prompts,
                           warm_full=True)
    paged = _t5_pooled_run(config, params, seq, decode_len,
                           n_sessions=n_sessions, prompts=prompts,
                           warm_full=True, kv_block_size=8)
    dense_tps, dense_streams = dense["tokens_per_s"], dense["streams"]
    paged_tps, paged_streams = paged["tokens_per_s"], paged["streams"]
    paged_stats = paged["pool_stats"]
    extra = {
        "model": "t5-small", "sessions": n_sessions,
        "decode_len": decode_len, "kv_block_size": 8,
        "dense_tokens_per_s": dense_tps,
        "paged_over_dense": round(paged_tps / max(dense_tps, 1e-9), 3),
        # Cross-program argmax ties can flip a token between the dense
        # and paged executables (PERF.md round-5 note); record identity
        # rather than asserting it. The unit suite asserts exactness on
        # tie-free fixtures at every block size.
        "paged_token_exact": paged_streams == dense_streams,
        "paged_table_width": (paged_stats or {}).get("table_width"),
        "paged_arena_bytes": (paged_stats or {}).get("arena_bytes"),
        "paged_dense_equivalent_bytes":
            (paged_stats or {}).get("dense_equivalent_bytes"),
    }

    # -- per-tick KV read bytes, analytic AND measured (ISSUE 11): the
    # paged step contract reads the pages live sessions OWN; the dense
    # pool (and the dense-gather fallback) reads max-length state per
    # active slot. Asserted, not eyeballed — visible on this CPU-only
    # host because the numbers come from the tick's own accounting.
    tiny = t5.T5Config.tiny()
    tparams = t5.init_params(jax.random.PRNGKey(0), tiny)
    low_occ = t5.build_session_signatures(
        tparams, tiny, seq_len=12, max_decode_len=32, max_sessions=8,
        continuous_batching=True, kv_block_size=2)
    lrng = np.random.default_rng(3)
    pool = low_occ["decode_init"]._kv_pool
    for i in range(8):
        lids = lrng.integers(2, tiny.vocab_size, (1, 12)).astype(np.int32)
        low_occ["decode_init"].run(
            {"session_id": np.asarray(f"lo{i}".encode(), object),
             "input_ids": lids})
    for _ in range(2):  # 2 used tokens of 32 -> 1 page of 16 per session
        for i in range(8):
            low_occ["decode_step"].run(
                {"session_id": np.asarray(f"lo{i}".encode(), object)})
    lo_stats = pool.stats()
    paged_read = lo_stats["kv_gather_bytes_per_tick"]
    dense_read = pool.page_bytes * 8 * pool.pages_per_session
    assert lo_stats["step_contract"] is True
    # Low occupancy (2/32 tokens): the ragged path must read FAR less
    # than the dense per-tick traffic — the tentpole's bandwidth claim.
    assert paged_read * 2 <= dense_read, (paged_read, dense_read)
    for i in range(8):
        low_occ["decode_close"].run(
            {"session_id": np.asarray(f"lo{i}".encode(), object)})
    extra.update({
        "kv_read_bytes_per_tick_dense": dense_read,
        "kv_read_bytes_per_tick_paged_low_occupancy": paged_read,
        "kv_read_ratio_low_occupancy": round(
            paged_read / max(dense_read, 1), 4),
    })

    if _child_time_left() > 45:
        # -- chunked-prefill sub-leg: a 24-token forced prefix streams
        # through the ragged kernel in page chunks vs the dense pool's
        # monolithic prefill; streams asserted identical, walls recorded.
        def prefix_run(sigs, name):
            prng = np.random.default_rng(4)
            ids = prng.integers(2, tiny.vocab_size, (1, 12)).astype(
                np.int32)
            pre = np.zeros((1, 32), np.int32)
            pre[0, :24] = prng.integers(2, tiny.vocab_size, 24)
            sid = np.asarray(name.encode(), object)
            t0 = time.perf_counter()
            sigs["decode_init_prefix"].run(
                {"session_id": sid, "input_ids": ids, "prefix_ids": pre})
            first = sigs["decode_step"].run({"session_id": sid})
            ttft = time.perf_counter() - t0
            toks = [int(first["token"][0])]
            for _ in range(7):
                toks.append(int(sigs["decode_step"].run(
                    {"session_id": sid})["token"][0]))
            sigs["decode_close"].run({"session_id": sid})
            return toks, ttft

        dense_sigs = t5.build_session_signatures(
            tparams, tiny, seq_len=12, max_decode_len=32, max_sessions=8,
            continuous_batching=True)
        paged_sigs = t5.build_session_signatures(
            tparams, tiny, seq_len=12, max_decode_len=32, max_sessions=8,
            continuous_batching=True, kv_block_size=4)
        # Warm BOTH paths' prefill/chunk/tick executables, then measure —
        # steady state pays compiles once per deployment, not per prefix.
        prefix_run(dense_sigs, "pfdw")
        d_toks, d_ttft = prefix_run(dense_sigs, "pfd")
        prefix_run(paged_sigs, "pfw")
        # Snapshot the cumulative chunk counter so the reported number is
        # the MEASURED prefix's rounds, not warmup + measured doubled.
        chunks_before = paged_sigs["decode_init"]._kv_pool.stats()[
            "prefill_chunks"]
        p_toks, p_ttft = prefix_run(paged_sigs, "pfp")
        assert p_toks == d_toks, (p_toks, d_toks)
        extra.update({
            "prefill_prefix_tokens": 24,
            "prefill_chunks": paged_sigs["decode_init"]._kv_pool.stats()[
                "prefill_chunks"] - chunks_before,
            "prefill_ttft_ms_dense_monolithic": round(d_ttft * 1e3, 2),
            "prefill_ttft_ms_paged_chunked": round(p_ttft * 1e3, 2),
            "prefill_token_exact": True,
        })

    if _child_time_left() > 45:
        # -- speculative sub-leg: verify blocks (Sq=k+1) through the
        # block tables vs dense caches; bitwise identity asserted.
        import jax.numpy as jnp

        draft_cfg = t5.T5Config.tiny(num_decoder_layers=1,
                                     num_encoder_layers=1)
        draft = t5.init_params(jax.random.PRNGKey(1), draft_cfg)
        srng = np.random.default_rng(5)
        sids = jnp.asarray(srng.integers(2, tiny.vocab_size, (4, 12)),
                           jnp.int32)
        slens = jnp.sum((sids != tiny.pad_id).astype(jnp.int32), axis=-1)

        def spec_run(bs):
            t0 = time.perf_counter()
            out = t5.speculative_decode(
                tparams, tiny, draft, draft_cfg, sids, slens,
                max_decode_len=32, k=4, kv_block_size=bs)
            out = jax.tree_util.tree_map(np.asarray, out)
            compile_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            for _ in range(3):
                out = t5.speculative_decode(
                    tparams, tiny, draft, draft_cfg, sids, slens,
                    max_decode_len=32, k=4, kv_block_size=bs)
                out = jax.tree_util.tree_map(np.asarray, out)
            return out, (time.perf_counter() - t0) / 3, compile_s

        d_out, d_wall, _ = spec_run(0)
        p_out, p_wall, _ = spec_run(4)
        assert np.array_equal(p_out[0], d_out[0])
        assert np.array_equal(p_out[1], d_out[1])
        extra.update({
            "speculative_token_exact": True,
            "speculative_target_passes": int(d_out[2]),
            "speculative_wall_ms_dense": round(d_wall * 1e3, 1),
            "speculative_wall_ms_paged": round(p_wall * 1e3, 1),
        })

    if _child_time_left() > 30:
        # Capacity under a fixed budget (structural, so the tiny config's
        # fast compiles suffice): budget = 2 dense sessions' KV state;
        # short sessions write 4 of 32 tokens = 1 page at block_size 8.
        trng = np.random.default_rng(2)

        def admit(**kw):
            sigs = t5.build_session_signatures(
                tparams, tiny, seq_len=12, max_decode_len=32, **kw)
            admitted = 0
            try:
                for i in range(64):
                    ids = trng.integers(2, tiny.vocab_size,
                                        (1, 12)).astype(np.int32)
                    sid = np.asarray(f"c{i}".encode(), object)
                    sigs["decode_init"].run({"session_id": sid,
                                             "input_ids": ids})
                    for _ in range(4):  # short mix: 4 used tokens
                        sigs["decode_step"].run({"session_id": sid})
                    admitted += 1
            except ServingError:
                pass
            return admitted

        cap_dense = admit(max_sessions=2, continuous_batching=True)
        cap_paged = admit(max_sessions=64, continuous_batching=True,
                          kv_block_size=8, kv_num_blocks=8,
                          kv_evict_policy="refuse")
        extra.update({
            "capacity_budget_blocks": 8,
            "capacity_sessions_dense": cap_dense,
            "capacity_sessions_paged": cap_paged,
            "capacity_ratio": round(cap_paged / max(cap_dense, 1), 2),
        })

    # -- per-leg cost columns + the servecost dataset artifact: drain
    # the tracing ring synchronously, read the window aggregates, fold
    # the leg's JSONL into a dataset (the real producer path item 4's
    # autotuner consumes), then disarm the process-global log.
    tracing_mod.flush_metrics()
    cost_snap = costs_mod.snapshot()
    t5_entries = [e for e in cost_snap["entries"] if e["model"] == "t5"]
    if t5_entries:
        agg = t5_entries[0]
        mean = agg.get("mean", {})
        extra.update({
            "cost_requests": agg["count"],
            "cost_kv_page_ticks_mean": mean.get("kv_page_ticks"),
            "cost_decode_tick_us_mean": mean.get("decode_tick_us"),
            "cost_total_us_mean": mean.get("total_us"),
            "cost_tick_utilization": cost_snap["tick_utilization"],
        })
    costs_mod.tracker.log.close()
    costs_mod.configure(log_dir="", sample=1.0)
    from min_tfs_client_tpu.observability import servecost

    dataset = servecost.aggregate([str(cost_dir)])
    artifact = cost_dir / "servecost_dataset.json"
    artifact.write_text(json.dumps(dataset, indent=1,
                                   sort_keys=True) + "\n")
    # Asserted at leg level (NOT inside a swallowed try): an empty or
    # malformed dataset means the producer path broke, and the leg's
    # "real servecost artifact" claim must fail loudly with it.
    assert dataset["records"] > 0 and dataset["malformed"] == 0, dataset
    extra["servecost_dataset"] = {
        "path": str(artifact),
        "records": dataset["records"],
        "models": sorted(dataset["models"]),
        "contexts": len(dataset["contexts"]),
    }

    return {"metric": f"decode_paged_tokens_per_s_s{n_sessions}",
            "value": paged_tps, "unit": "tokens/s",
            "higher_is_better": True, "extra": extra}


def bench_resnet(max_iters: int) -> dict:
    """BASELINE config 2: ResNet50, batch 32 Predict p50 (conv path)."""
    import jax
    import numpy as np

    from min_tfs_client_tpu.client import TensorServingClient
    from min_tfs_client_tpu.models import export, resnet
    from min_tfs_client_tpu.tensor.codec import tensor_proto_to_ndarray

    config = resnet.ResNetConfig.resnet50()
    params = resnet.init_params(jax.random.PRNGKey(0), config)
    base = pathlib.Path(tempfile.mkdtemp(prefix="tpu_bench_")) / "resnet50"
    export.export_servable(base, 1, "resnet", {}, params, {})
    client = TensorServingClient(f"tpu://{base}")
    images = np.random.default_rng(0).standard_normal(
        (BATCH, config.image_size, config.image_size, 3)).astype(np.float32)

    def call():
        resp = client.predict_request("resnet50", {"images": images},
                                      timeout=600)
        out = tensor_proto_to_ndarray(resp.outputs["probabilities"])
        assert out.shape == (BATCH, config.num_classes)

    stats = _measure(call, max_iters)
    extra = {"model": "resnet50", "batch": BATCH,
             "p99_ms": round(stats["p99"], 4),
             "qps": round(1000.0 / stats["p50"] * BATCH, 1),
             "iters": stats["iters"],
             "transport_rtt_ms": round(_transport_rtt_ms(), 2),
             "input_mb_on_wire": round(
                 BATCH * config.image_size ** 2 * 3 * 2 / 2 ** 20, 1)}
    if _child_time_left() > 30:
        extra.update(_concurrent_qps(call, batch=BATCH, p50_ms=stats["p50"],
                                     threads=4, total=12))
    _add_mfu(extra, float(resnet.fwd_flops(config)) * BATCH, stats["p50"])
    return {"metric": f"resnet50_predict_p50_b{BATCH}", "value": stats["p50"],
            "unit": "ms", "extra": extra}


def bench_imported(max_iters: int) -> dict:
    """Beyond-BASELINE leg: an IMPORTED SavedModel — TF-Serving's bread
    and butter — served through the round-5 partitioned path (Example
    decode + string-label lookup on host, the transformer interior as
    ONE jitted device function). The fixture is built with this
    package's own protos (tests/fixtures.py), so the leg needs no TF at
    bench time and runs wherever the chip is."""
    import numpy as np

    from min_tfs_client_tpu.client import TensorServingClient
    from tests import fixtures

    seq, labels, batch = 64, 8, 16
    base = pathlib.Path(tempfile.mkdtemp(prefix="tpu_bench_")) / "imported"
    fixtures.write_imported_transformer_classify(
        base, seq=seq, labels=labels)

    client = TensorServingClient(f"tpu://{base}")
    # Placement evidence for the record, read from the servable the
    # channel just loaded (importing twice would burn child budget): the
    # signature must actually be partitioned — a silent all-host
    # fallback would make the number meaningless.
    from min_tfs_client_tpu.client.inprocess import _registry
    from min_tfs_client_tpu.protos import tfs_apis_pb2 as apis

    spec = apis.ModelSpec()
    spec.name = "imported"
    with _registry[str(base)].core.servable_handle(spec) as handle:
        part = handle.servable.signature("").partition
    partitioned = part is not None
    interior_ops = part.stats["interior_ops"] if partitioned else []
    rng = np.random.default_rng(0)
    feats = [{"ids": rng.integers(0, 2048, seq)} for _ in range(batch)]

    def call():
        resp = client.classification_request("imported", feats, timeout=120)
        assert len(resp.result.classifications) == batch

    stats = _measure(call, max_iters)
    extra = {"model": "imported-transformer-classify", "batch": batch,
             "seq_len": seq, "p99_ms": round(stats["p99"], 4),
             "qps": round(1000.0 / stats["p50"] * batch, 1),
             "iters": stats["iters"], "partitioned": partitioned,
             "interior_has_matmul": "BatchMatMulV2" in interior_ops}
    if _child_time_left() > 40:
        hb = _imported_host_batching_ratio(str(base))
        if hb:
            extra["host_batching"] = hb
    return {"metric": f"imported_classify_p50_b{batch}",
            "value": stats["p50"], "unit": "ms", "extra": extra}


def _imported_host_batching_ratio(base: str) -> dict:
    """The round-5 host-batching claim, measured (VERDICT r5 next #6):
    N concurrent single-example classify callers against the SAME
    partitioned import, served once through the batching front-end
    (merge -> decode/run once -> split) and once with the queue off.
    Reports per-call wall p50 both ways and the amortization ratio."""
    import concurrent.futures as cf

    import numpy as np

    from min_tfs_client_tpu.core.server_core import (
        ServerCore,
        single_model_config,
    )
    from min_tfs_client_tpu.protos import tfs_apis_pb2 as apis
    from min_tfs_client_tpu.protos import tfs_config_pb2
    from min_tfs_client_tpu.server.handlers import Handlers

    rng = np.random.default_rng(1)
    threads, rounds = 16, 4

    def one_request():
        req = apis.ClassificationRequest()
        req.model_spec.name = "hb"
        ex = req.input.example_list.examples.add()
        ex.features.feature["ids"].int64_list.value.extend(
            [int(v) for v in rng.integers(0, 2048, 64)])
        return req

    reqs = [one_request() for _ in range(threads)]

    def measure(batching: bool) -> "tuple[float, int]":
        params = tfs_config_pb2.BatchingParameters()
        if batching:
            params.max_batch_size.value = threads
            params.batch_timeout_micros.value = 2000
            # ONE compile bucket: merged totals vary per wave, and a
            # ladder of allowed sizes would keep compiling new buckets
            # mid-measurement.
            params.allowed_batch_sizes.append(threads)
        core = ServerCore(
            single_model_config("hb", base, platform="tensorflow"),
            file_system_poll_wait_seconds=0.05,
            platform_configs={"tensorflow": dict(
                {"batching_parameters": params} if batching else {},
                enable_model_warmup=False)})
        try:
            handlers = Handlers(core)
            # Count pipeline executions (host decode + interior dispatch)
            # under the hood: the amortization claim IS this count — N
            # callers collapsing to ~1 merged execution per wave.
            spec = apis.ModelSpec()
            spec.name = "hb"
            with core.servable_handle(spec) as handle:
                part = handle.servable.signature("").partition
            runs = [0]
            inner = part.run

            def counted(feeds, buckets):
                runs[0] += 1
                return inner(feeds, buckets)

            part.run = counted
            with cf.ThreadPoolExecutor(threads) as pool:
                for _ in range(2):  # warm: compile + prime the queue path
                    list(pool.map(handlers.classify, reqs))
                runs[0] = 0
                samples = []
                for _ in range(rounds):
                    t0 = time.perf_counter()
                    list(pool.map(handlers.classify, reqs))
                    samples.append(
                        (time.perf_counter() - t0) / threads * 1e3)
            samples.sort()
            return samples[len(samples) // 2], runs[0]
        finally:
            core.stop()

    try:
        unbatched_ms, unbatched_runs = measure(False)
        batched_ms, batched_runs = measure(True)
    except Exception:
        traceback.print_exc(file=sys.stderr)
        return {}
    return {"concurrent_callers": threads,
            "unbatched_per_call_ms": round(unbatched_ms, 3),
            "batched_per_call_ms": round(batched_ms, 3),
            "amortization_ratio": round(
                unbatched_ms / max(batched_ms, 1e-6), 3),
            "executions_unbatched": unbatched_runs,
            "executions_batched": batched_runs,
            "dispatch_amortization": round(
                unbatched_runs / max(batched_runs, 1), 2)}


def _pipeline_overlap_evidence(sig, x) -> dict:
    """One traced request through the microbatch pipeline, reduced to
    the two numbers that prove host/device overlap on a timeline no one
    has to eyeball: how much host-island wall ran while another chunk's
    device segment (dispatch->materialize) was in flight, and how many
    device dispatches were issued with at least one other chunk already
    in flight (the interleaving the GPipe schedule exists to produce)."""
    from min_tfs_client_tpu.observability import tracing

    tr = tracing.RequestTrace("bench", "in_flight", "predict")
    with tracing.activate(tr):
        sig.run({"x": x})
    spans = list(tr.spans)
    flights = {}  # (chunk, segment) -> [dispatched_at, materialized_at]
    for name, t0, t1, args in spans:
        if name == "pipeline/dispatch":
            flights.setdefault(
                (args["chunk"], args["segment"]), [t1, None])[0] = t1
        elif name == "pipeline/materialize":
            entry = flights.setdefault(
                (args["chunk"], args["segment"]), [None, t0])
            entry[1] = t0
    # Dispatch-only entries (a pipeline attempt that aborted before its
    # materialize span and fell back to serial) carry m=None — drop them
    # everywhere, not just from the window count.
    flights = {k: (d, m) for k, (d, m) in flights.items()
               if d is not None and m is not None}
    windows = [(d, m) for d, m in flights.values() if m > d]
    host_overlap = 0.0
    host_total = 0.0
    for name, t0, t1, args in spans:
        if name != "pipeline/host":
            continue
        host_total += t1 - t0
        for key, (d, m) in flights.items():
            if key[0] == args["chunk"]:
                continue  # own chunk: sequential by construction
            lo, hi = max(t0, d), min(t1, m)
            if hi > lo:
                host_overlap += hi - lo
                break  # count each host slice once
    interleaved = sum(
        1 for name, t0, t1, args in spans if name == "pipeline/dispatch"
        and any(d < t1 and m > t1 for (c, s), (d, m) in flights.items()
                if c != args["chunk"]))
    return {"host_ms_total": round(host_total * 1e3, 3),
            "host_ms_overlapped": round(host_overlap * 1e3, 3),
            "interleaved_dispatches": interleaved,
            "in_flight_windows": len(windows)}


def bench_in_flight(max_iters: int) -> dict:
    """In-flight execution window sweep (ISSUE 5): the same toy device
    signature served through BatchedSignatureRunner at window 1/4/8, and
    the imported two-tower fixture's multi-segment microbatch pipeline
    at depth 1/4/8 — both against a simulated-latency device (wall-clock
    injected between a dispatch and its result being ready). What it
    shows is that the window overlaps waiting, by how much for a given
    injected latency; it says nothing about the latency of a real
    host-device link, which has not been measured on the current code.
    Numerics must be bit-identical at every window size — that equality
    is asserted, not assumed."""
    import concurrent.futures as cf
    import tempfile as _tf

    import numpy as np

    from min_tfs_client_tpu.batching.scheduler import SharedBatchScheduler
    from min_tfs_client_tpu.batching.session import (
        BatchedSignatureRunner,
        pipeline_snapshot,
    )
    from min_tfs_client_tpu.servables.servable import Signature, TensorSpec
    from tests import fixtures

    # 10 ms per in-flight batch: above the 5 ms acceptance floor and
    # large enough that host scheduling noise can't drown the
    # serial-vs-overlapped contrast.
    latency_s = 0.010
    # 16 callers each sending 7 rows against max_batch_size 8: two such
    # requests never co-batch (7+7 > 8) and size >= max takes the
    # oversized direct path, so exactly one request = one queued batch =
    # one window slot — the window can hold 8 batches in flight while
    # the GIL churn of very wide caller pools stays out of the
    # measurement (cross-caller coalescing has its own leg; this one
    # measures the window).
    threads, per_thread, req_rows = 16, 4, 7

    def make_sig():
        import jax.numpy as jnp

        sig = Signature(
            fn=lambda inputs: {"y": jnp.tanh(inputs["x"]) * 2.0 + 1.0},
            inputs={"x": TensorSpec(np.float32, (None, 8))},
            outputs={"y": TensorSpec(np.float32, (None, 8))},
        )
        fixtures.simulate_device_latency(sig, latency_s)
        return sig

    def toy_point(window: int) -> dict:
        sched = SharedBatchScheduler(num_threads=1)
        sig = make_sig()
        dispatches = [0]
        inner = sig.dispatch

        def counting(inputs, output_filter=()):
            dispatches[0] += 1
            return inner(inputs, output_filter)

        sig.dispatch = counting
        runner = BatchedSignatureRunner(
            sig, sched, name=f"bench-inflight-w{window}",
            max_batch_size=8, batch_timeout_s=0.002,
            allowed_batch_sizes=[8], max_in_flight_batches=window)
        try:
            outs = {}

            def call(i):
                x = (np.arange(req_rows * 8, dtype=np.float32)
                     .reshape(req_rows, 8) * 0.01 + float(i % 32))
                # 7 rows: pads to the 8-bucket on dispatch, splits back
                # to exactly these rows on materialize.
                outs[i] = np.asarray(runner.run({"x": x})["y"])

            with cf.ThreadPoolExecutor(threads) as pool:
                list(pool.map(call, range(threads)))  # warm/compile
                dispatches[0] = 0
                # The window's counters are cumulative — snapshot after
                # warmup so the reported ratio covers only the measured
                # calls (warmup includes the ramp where in_flight is 0).
                warm = pipeline_snapshot().get(
                    f"bench-inflight-w{window}", {})
                total = threads * per_thread
                t0 = time.perf_counter()
                list(pool.map(call, range(total)))
                wall = time.perf_counter() - t0
            stats = pipeline_snapshot().get(
                f"bench-inflight-w{window}", {})
            d = stats.get("dispatched", 0) - warm.get("dispatched", 0)
            o = stats.get("overlapped", 0) - warm.get("overlapped", 0)
            return {"window": window,
                    "qps": round(total * req_rows / wall, 1),
                    "per_call_ms": round(wall / total * 1e3, 3),
                    "executions": dispatches[0],
                    "overlap_ratio": round(o / d, 4) if d else 0.0,
                    "outputs": {i: outs[i] for i in range(32)}}
        finally:
            runner.close()
            sched.stop()

    toy = [toy_point(w) for w in (1, 4, 8)]
    # Bit-identical across windows — the compat guarantee, enforced.
    for point in toy[1:]:
        for i, want in toy[0]["outputs"].items():
            assert np.array_equal(point["outputs"][i], want), (
                f"window {point['window']} diverged on caller {i}")
    for point in toy:
        del point["outputs"]
    speedup = round(toy[-1]["qps"] / max(toy[0]["qps"], 1e-6), 2)

    imported = []
    try:
        from min_tfs_client_tpu.servables.graphdef_import import (
            load_saved_model,
        )

        base = pathlib.Path(_tf.mkdtemp(prefix="tpu_bench_if_")) / "tt"
        fixtures.write_imported_two_tower(base)
        sv = load_saved_model(str(base / "1"), "tt", 1)
        sig = sv.signature("")
        part = sig.partition
        if part is not None and len(part.segments) > 1:
            fixtures.simulate_interior_latency(part, latency_s)
            # Host islands get a per-row cost too: the pipeline's win is
            # host work hidden under in-flight device segments, and the
            # two-tower fixture's lookup island is near-free on CPU
            # while production imports burn real host time on string
            # ops/Example parsing at these row counts.
            fixtures.simulate_host_latency(part, 0.0003)
            rng = np.random.default_rng(0)
            x = rng.standard_normal((32, 8)).astype(np.float32)
            want = None
            for depth in (1, 4, 8):
                part.pipeline_depth = depth
                sig.run({"x": x})  # warm/compile every chunk bucket
                samples = []
                for _ in range(5):
                    t0 = time.perf_counter()
                    got = sig.run({"x": x})
                    samples.append((time.perf_counter() - t0) * 1e3)
                if want is None:
                    want = got
                else:
                    for k in want:
                        assert np.array_equal(got[k], want[k]), (
                            f"pipeline depth {depth} diverged on {k}")
                samples.sort()
                point = {"depth": depth, "segments": len(part.segments),
                         "per_call_ms": round(samples[len(samples) // 2], 3)}
                if depth > 1:
                    point.update(_pipeline_overlap_evidence(sig, x))
                imported.append(point)
    except Exception:
        traceback.print_exc(file=sys.stderr)

    extra = {"injected_latency_ms": latency_s * 1e3,
             "concurrent_callers": threads,
             "toy": toy, "toy_speedup_w8_over_w1": speedup,
             "imported_pipeline": imported}
    if imported and len(imported) > 1:
        # Best depth, not last: each chunk pays the injected RTT, so
        # past the point where chunked latency outgrows the host work it
        # hides, deeper pipelines REGRESS (depth 8 on this fixture) —
        # report the sweet spot the way an operator would pick it.
        best = min(imported[1:], key=lambda p: p["per_call_ms"])
        extra["imported_speedup"] = round(
            imported[0]["per_call_ms"] / max(best["per_call_ms"], 1e-6), 2)
        extra["imported_best_depth"] = best["depth"]
    return {"metric": "in_flight_toy_qps_w8", "value": toy[-1]["qps"],
            "unit": "qps", "extra": extra}


def bench_routed(max_iters: int) -> dict:
    """Routed leg (ROADMAP item 3): 3 real server subprocesses behind a
    REAL `tpu-serving-router` subprocess on the asyncio data plane,
    driven with the UNMODIFIED client SDK. The router hop is a
    host-side byte proxy, so the servers are pinned to
    JAX_PLATFORMS=cpu (processes must not fight over one chip; the
    quantity under test is the extra hop, which is platform-invariant).

    What is ASSERTED in-bench, every round:

     * bit-identity of routed vs direct responses, gRPC AND REST — an
       overhead number for a proxy that rewrites bytes would be
       meaningless;
     * 8-caller routed qps >= 90% of direct (best-of-2) on hosts with
       >= 2 cores — the aio plane's reason to exist. On a ONE-core
       host the claim is physically unmeasurable (nothing overlaps
       anything; a zero-logic proxy measures the same ratio), so the
       in-bench assertion degrades to an aio-vs-threads A/B plus a
       regression floor, honestly labelled in the record;
     * trace-propagation overhead < 5% + 60us floor on the aio plane
       (in-process A/B — tracing.enable is process-local).

    Also measured: a 1/4/8/16 caller sweep (where does the proxy's
    ceiling actually sit), the sessioned sticky stream, and a
    `routed_scaleout` sub-leg — TWO router subprocesses sharing the
    fleet, 16 callers split across them, epoch agreement checked."""
    import numpy as np

    from min_tfs_client_tpu.client import TensorServingClient
    from min_tfs_client_tpu.router.main import RouterOptions, RouterServer
    from min_tfs_client_tpu.tensor.codec import tensor_proto_to_ndarray
    from tests import fixtures

    tmp = pathlib.Path(tempfile.mkdtemp(prefix="tpu_bench_routed_"))
    model_root = tmp / "model"
    fixtures.write_session_jax_servable(model_root)
    monitoring = tmp / "monitoring.config"
    monitoring.write_text("prometheus_config { enable: true }\n")

    servers = []
    routers = []
    inproc_router = None
    try:
        # Boot/parse/teardown choreography is the SHARED harness
        # (tests/fixtures.ModelServerProcess / RouterProcess) — same
        # code the router integration suites run, so a banner change
        # breaks one place, loudly.
        servers = [fixtures.ModelServerProcess(model_root, monitoring)
                   for _ in range(3)]
        backends = [s.wait_ready().backend_spec() for s in servers]
        backends_arg = ",".join(backends)

        # Register for teardown BEFORE wait_ready: a boot timeout must
        # not orphan a live router subprocess outside the finally.
        router = fixtures.RouterProcess(backends_arg)
        routers.append(router)
        router.wait_ready()

        def wait_live(r, n, timeout_s=30):
            t0 = time.monotonic()
            while len(r.snapshot()["view"]["live"]) < n:
                if time.monotonic() - t0 > timeout_s:
                    raise RuntimeError(
                        f"router never saw {n} LIVE backends")
                time.sleep(0.05)

        wait_live(router, 3)
        assert router.snapshot()["data_plane"]["mode"] == "aio"

        routed = TensorServingClient("127.0.0.1", router.grpc_port)
        direct = TensorServingClient(
            "127.0.0.1", int(backends[0].split(":")[1]))

        # -- bit identity (the proxy contract, asserted not assumed)
        for i in range(5):
            x = np.asarray([1.0 * i, -2.0 * i, 0.5], np.float32)
            via_router = routed.predict_request("sess", {"x": x})
            via_direct = direct.predict_request("sess", {"x": x})
            assert via_router.SerializeToString(deterministic=True) == \
                via_direct.SerializeToString(deterministic=True)
        # ...and on the REST plane (keep-alive pooled forwards must not
        # touch a byte either).
        import urllib.request as _urlreq

        rest_payload = json.dumps(
            {"instances": [{"x": 1.0}, {"x": 4.0}]}).encode()

        def rest_post(port):
            req = _urlreq.Request(
                f"http://127.0.0.1:{port}/v1/models/sess:predict",
                data=rest_payload,
                headers={"Content-Type": "application/json"})
            with _urlreq.urlopen(req, timeout=10) as resp:
                return resp.read()

        backend_rest = int(backends[0].rsplit(":", 1)[1])
        for _ in range(3):  # repeats exercise the keep-alive reuse path
            assert rest_post(router.rest_port) == rest_post(backend_rest)

        # -- stateless p50: direct vs routed (the router-hop overhead)
        x = np.zeros((32,), np.float32)

        def p50(client, n):
            ts = []
            for _ in range(n):
                start = time.perf_counter()
                client.predict_request("sess", {"x": x})
                ts.append((time.perf_counter() - start) * 1e3)
            ts.sort()
            return ts[len(ts) // 2]

        iters = max(10, min(max_iters, 50))
        p50(direct, 5), p50(routed, 5)  # warm both paths
        direct_ms = p50(direct, iters)
        routed_ms = p50(routed, iters)

        # -- caller sweep: where the proxy's concurrency ceiling sits
        def qps(client, threads, total=None):
            import concurrent.futures as cf

            total = total or max(32, threads * 8)

            def one(_):
                client.predict_request("sess", {"x": x})

            start = time.perf_counter()
            with cf.ThreadPoolExecutor(threads) as pool:
                list(pool.map(one, range(total)))
            return total / (time.perf_counter() - start)

        qps(routed, 8), qps(direct, 8)  # warm the concurrent path
        sweep = {}
        for callers in (1, 4, 8, 16):
            qd = qps(direct, callers)
            qr = qps(routed, callers)
            sweep[callers] = {
                "direct": round(qd, 1), "routed": round(qr, 1),
                "ratio": round(qr / max(qd, 1e-9), 3)}
        ratio_8 = max(
            sweep[8]["ratio"],
            round(qps(routed, 8) / max(qps(direct, 8), 1e-9), 3))
        # The acceptance bar is TOPOLOGY-AWARE, because the physics is.
        # On >= 2 cores the router's per-request CPU overlaps the
        # backend's and the aio plane must keep >= 90% of direct at 8
        # callers (ROADMAP target 95%). On ONE core nothing can
        # overlap anything: every proxy cycle is serial added CPU, and
        # a ZERO-logic python byte proxy measures the same ~0.55 ratio
        # this full router does (PERF.md round-12) — so the measurable
        # claims here are (a) the aio plane does not lose to the
        # threaded plane it replaces (interleaved best-of-2 A/B) and
        # (b) the ratio stays above a regression floor.
        cores = os.cpu_count() or 1
        plane_ab = None
        if cores >= 2:
            assert ratio_8 >= 0.90, (
                f"aio data plane kept only {ratio_8:.3f} of direct qps "
                f"at 8 callers on {cores} cores; the scale-out bar is "
                "0.90 (ROADMAP target 0.95)")
        else:
            threads_router = fixtures.RouterProcess(
                backends_arg, extra_args=("--data_plane=threads",))
            routers.append(threads_router)
            threads_router.wait_ready()
            wait_live(threads_router, 3)
            routed_t = TensorServingClient(
                "127.0.0.1", threads_router.grpc_port)
            qps(routed_t, 8)  # warm
            best_aio = best_threads = 0.0
            for _ in range(2):
                best_aio = max(best_aio, qps(routed, 8))
                best_threads = max(best_threads, qps(routed_t, 8))
            routed_t.close()
            threads_router.kill()
            routers.remove(threads_router)
            plane_ab = {
                "aio_qps_8": round(best_aio, 1),
                "threads_qps_8": round(best_threads, 1),
                "aio_over_threads": round(
                    best_aio / max(best_threads, 1e-9), 3),
            }
            assert best_aio >= 0.85 * best_threads, (
                f"aio plane lost to the threads plane it replaces: "
                f"{best_aio:.1f} vs {best_threads:.1f} qps at 8 callers")
            assert ratio_8 >= 0.40, (
                f"single-core routed ratio {ratio_8:.3f} fell below the "
                "0.40 regression floor (zero-logic-proxy band is ~0.55)")

        # -- sessioned path: sticky stream steps through the router
        sid = np.asarray(b"bench-routed-session", object)
        routed.predict_request(
            "sess", {"session_id": sid, "base": np.asarray(0, np.int32)},
            signature_name="decode_init")
        pids = set()
        step_ts = []
        for step in range(1, 21):
            start = time.perf_counter()
            resp = routed.predict_request(
                "sess", {"session_id": sid}, signature_name="decode_step")
            step_ts.append((time.perf_counter() - start) * 1e3)
            token = int(tensor_proto_to_ndarray(resp.outputs["token"])[0])
            assert token == step, "sticky stream broke"
            pids.add(int(tensor_proto_to_ndarray(resp.outputs["pid"])[0]))
        assert len(pids) == 1, "session hopped backends"
        routed.predict_request("sess", {"session_id": sid},
                               signature_name="decode_close")
        step_ts.sort()

        # -- routed_scaleout: a SECOND router replica joins the tier;
        # 16 callers split 8/8 across the two front doors. Replication
        # evidence rides along: both report the same membership epoch.
        router2 = fixtures.RouterProcess(backends_arg)
        routers.append(router2)
        router2.wait_ready()
        wait_live(router2, 3)
        assert router.snapshot()["view"]["epoch"] == \
            router2.snapshot()["view"]["epoch"], \
            "router replicas disagree on the membership epoch"
        routed2 = TensorServingClient("127.0.0.1", router2.grpc_port)
        qps(routed2, 4)  # warm replica 2's channels

        def qps_two_routers(total=128, threads=16):
            import concurrent.futures as cf

            clients = [routed, routed2]

            def one(i):
                clients[i % 2].predict_request("sess", {"x": x})

            start = time.perf_counter()
            with cf.ThreadPoolExecutor(threads) as pool:
                list(pool.map(one, range(total)))
            return total / (time.perf_counter() - start)

        qps_scaleout = qps_two_routers()
        qd16 = sweep[16]["direct"]
        routed2.close()
        router2.kill()
        routers.remove(router2)

        # -- trace-context propagation overhead (ASSERTED in-bench):
        # tracing.enable is process-local, so this A/B runs against an
        # IN-PROCESS router on the same aio plane — off disables the
        # router's span recording, trace-id minting, and header
        # injection, the whole fleet-tracing tax on a forward.
        # Adjacent best-of-2 pairs, <5% + 60us floor (CPU-noise on a
        # shared box must not fail an honest implementation).
        from min_tfs_client_tpu.observability import tracing

        inproc_router = RouterServer(RouterOptions(
            grpc_port=0, rest_api_port=0, backends=backends_arg,
            health_poll_interval_s=0.5)).build_and_start()
        t0 = time.monotonic()
        while len(inproc_router.core.membership.live_ids()) < 3:
            if time.monotonic() - t0 > 30:
                raise RuntimeError("in-process router never saw 3 LIVE")
            time.sleep(0.05)
        routed_in = TensorServingClient(
            "127.0.0.1", inproc_router.grpc_port)
        tracing.enable(False)
        try:
            p50(routed_in, 5)
            prop_off_ms = min(p50(routed_in, iters), p50(routed_in, iters))
        finally:
            tracing.enable(True)
        p50(routed_in, 5)
        prop_on_ms = min(p50(routed_in, iters), p50(routed_in, iters))
        propagation_overhead = prop_on_ms / max(prop_off_ms, 1e-9)
        assert prop_on_ms <= prop_off_ms * 1.05 + 0.06, (
            f"trace propagation costs {propagation_overhead:.3f}x on the "
            f"routed leg ({prop_on_ms:.3f} vs {prop_off_ms:.3f} ms p50); "
            "the <5% budget is the fleet-tracing contract")

        # -- disarmed-faultpoint overhead (ASSERTED in-bench): the
        # robustness fault layer is compiled into every hot path; its
        # DISARMED cost must be unmeasurable. A/B on the in-process
        # router: normal disarmed point() calls vs the same name
        # rebound to a no-op — best-of-2 adjacent pairs, <1% + a 60us
        # noise floor. (The subprocess backends' points stay disarmed-
        # normal in BOTH arms, so the delta isolates the per-request
        # point() calls on this request path; the call sites are the
        # same function everywhere.)
        from min_tfs_client_tpu.robustness import faults as faults_mod

        assert not faults_mod.armed(), \
            "bench must measure the DISARMED fault layer"
        real_point = faults_mod.point
        noop_point = lambda name, **ctx: None  # noqa: E731 - A/B arm
        p50(routed_in, 5)  # warm
        faults_off_ms = faults_on_ms = float("inf")
        # INTERLEAVED windows (3 adjacent pairs, best-of each arm):
        # sequential arms read box drift as signal on a one-core host —
        # a ~90us p50 wobble between two 50-request windows dwarfs the
        # nanoseconds actually under test.
        for _ in range(3):
            faults_mod.point = noop_point
            try:
                faults_off_ms = min(faults_off_ms, p50(routed_in, iters))
            finally:
                faults_mod.point = real_point
            faults_on_ms = min(faults_on_ms, p50(routed_in, iters))
        faultpoint_overhead = faults_on_ms / max(faults_off_ms, 1e-9)
        assert faults_on_ms <= faults_off_ms * 1.01 + 0.06, (
            f"DISARMED faultpoints cost {faultpoint_overhead:.3f}x on "
            f"the routed leg ({faults_on_ms:.3f} vs {faults_off_ms:.3f} "
            "ms p50); the <1% budget is the fault layer's "
            "zero-cost-when-disarmed contract (docs/ROBUSTNESS.md)")
        routed_in.close()

        # -- cost-attribution overhead (ASSERTED in-bench): two extra
        # backend subprocesses, identical except the servecost log —
        # one --cost_log_sample=1.0 (every request written), one
        # --cost_log_sample=0.0 (writes gated off) — A/B'd direct with
        # INTERLEAVED best-of-3 windows (sequential arms on this
        # one-core box read drift as signal; PR 12/14 convention).
        # The <5% + 60us budget is the off-the-hot-path design claim:
        # vectors fold and files write on the tracing DRAIN thread, so
        # arming the log must not tax the request path.
        cost_ab = None
        if _child_time_left() > 120:
            cost_dir = tmp / "costlogs"
            cost_on_srv = fixtures.ModelServerProcess(
                model_root, monitoring,
                extra_args=(f"--cost_log_dir={cost_dir}",
                            "--cost_log_sample=1.0"))
            servers.append(cost_on_srv)
            cost_off_srv = fixtures.ModelServerProcess(
                model_root, monitoring,
                extra_args=(f"--cost_log_dir={cost_dir}",
                            "--cost_log_sample=0.0"))
            servers.append(cost_off_srv)
            cost_on_srv.wait_ready()
            cost_off_srv.wait_ready()
            on_client = TensorServingClient(
                "127.0.0.1", cost_on_srv.grpc_port)
            off_client = TensorServingClient(
                "127.0.0.1", cost_off_srv.grpc_port)
            p50(on_client, 5), p50(off_client, 5)  # warm both
            cost_on_ms = cost_off_ms = float("inf")
            for _ in range(3):
                cost_off_ms = min(cost_off_ms, p50(off_client, iters))
                cost_on_ms = min(cost_on_ms, p50(on_client, iters))
            cost_overhead = cost_on_ms / max(cost_off_ms, 1e-9)
            assert cost_on_ms <= cost_off_ms * 1.05 + 0.06, (
                f"cost attribution (log armed) costs "
                f"{cost_overhead:.3f}x vs --cost_log_sample=0 "
                f"({cost_on_ms:.3f} vs {cost_off_ms:.3f} ms p50); the "
                "<5% budget is the off-the-hot-path contract "
                "(docs/OBSERVABILITY.md 'Cost attribution')")
            # The armed backend actually produced joinable records —
            # a zero-overhead no-op would pass the A/B vacuously. A GET
            # to its /monitoring/costs forces a synchronous
            # flush_metrics in THAT process (read-your-writes), then a
            # bounded poll rides out drain-thread lag on a GIL-starved
            # box instead of trusting one fixed sleep.
            from min_tfs_client_tpu.robustness.storm import (
                load_cost_records,
            )

            flush_deadline = time.monotonic() + 15.0
            while True:
                with _urlreq.urlopen(
                        f"http://127.0.0.1:{cost_on_srv.rest_port}"
                        "/monitoring/costs", timeout=10):
                    pass
                cost_records, cost_malformed = load_cost_records(
                    cost_dir)
                if len(cost_records) >= iters or \
                        time.monotonic() > flush_deadline:
                    break
                time.sleep(0.25)
            assert cost_malformed == 0, \
                f"{cost_malformed} malformed cost records"
            assert len(cost_records) >= iters, \
                f"armed backend wrote only {len(cost_records)} records"
            assert all(r.get("trace_id") for r in cost_records)
            on_client.close()
            off_client.close()
            for extra_srv in (cost_on_srv, cost_off_srv):
                extra_srv.kill()
                servers.remove(extra_srv)
            cost_ab = {
                "cost_p50_on_ms": round(cost_on_ms, 3),
                "cost_p50_off_ms": round(cost_off_ms, 3),
                "cost_overhead_ratio": round(cost_overhead, 3),
                "cost_records_written": len(cost_records),
                "mode": "direct_backend_ab_interleaved_best_of_3",
            }

        # Per-stage tables for the routed leg: the ROUTER's lanes come
        # from the in-process router's tracing ring (child_main attaches
        # them as extra.stage_breakdown under --breakdown); the
        # BACKEND's lanes are fetched from a backend's own trace ring
        # over its monitoring port, so the record shows both sides of
        # the hop.
        backend_stages = None
        backend_costs = None
        if os.environ.get("BENCH_BREAKDOWN", "") not in ("", "0"):
            with _urlreq.urlopen(
                    f"http://127.0.0.1:{backend_rest}"
                    "/monitoring/traces?summary=1", timeout=10) as resp:
                backend_stages = json.loads(resp.read()).get("stages")
            # Per-leg cost columns from the same backend's cost plane:
            # amortized device µs/request and padding-waste % straight
            # off the serving path (docs/OBSERVABILITY.md "Cost
            # attribution").
            with _urlreq.urlopen(
                    f"http://127.0.0.1:{backend_rest}"
                    "/monitoring/costs", timeout=10) as resp:
                cost_entries = json.loads(resp.read()).get("entries", [])
            backend_costs = []
            for entry in cost_entries:
                mean = entry.get("mean", {})
                device = mean.get("device_execute_us", 0.0)
                backend_costs.append({
                    "model": entry["model"],
                    "signature": entry["signature"],
                    "n": entry["count"],
                    "device_us_per_request": device,
                    "padding_waste_pct": round(
                        100.0 * mean.get("padding_waste_us", 0.0)
                        / device, 2) if device else 0.0,
                    "queue_wait_us": mean.get("queue_wait_us", 0.0),
                    "total_us": mean.get("total_us", 0.0),
                })

        # Event-loop health telemetry made it through the whole run
        # without a lag event (flight recorder stays silent on a sane
        # box; the gauge itself is the evidence the ticker ran).
        loop_health = router.snapshot()["data_plane"]

        routed.close()
        direct.close()
        extra_breakdown = (
            {"stage_breakdown_backend": backend_stages}
            if backend_stages else {})
        if backend_costs:
            extra_breakdown["cost_breakdown_backend"] = backend_costs
        return {
            "metric": "routed_predict_p50_ms", "value": routed_ms,
            "unit": "ms",
            "extra": {
                "data_plane": "aio",
                "direct_p50_ms": round(direct_ms, 3),
                "router_hop_overhead_ms": round(routed_ms - direct_ms, 3),
                "router_hop_overhead_ratio": round(
                    routed_ms / max(direct_ms, 1e-9), 3),
                "qps_sweep_by_callers": sweep,
                "qps_ratio_8_callers_best_of_2": ratio_8,
                "qps_assertion_mode": (
                    "direct_bar_0.90" if cores >= 2
                    else "single_core_plane_ab"),
                "cores": cores,
                **({"plane_ab": plane_ab} if plane_ab else {}),
                "routed_scaleout": {
                    "two_router_qps_16_callers": round(qps_scaleout, 1),
                    "direct_qps_16_callers": qd16,
                    "ratio": round(qps_scaleout / max(qd16, 1e-9), 3),
                },
                "session_step_p50_ms": round(
                    step_ts[len(step_ts) // 2], 3),
                "propagation_p50_on_ms": round(prop_on_ms, 3),
                "propagation_p50_off_ms": round(prop_off_ms, 3),
                "propagation_overhead_ratio": round(
                    propagation_overhead, 3),
                "faultpoints_p50_on_ms": round(faults_on_ms, 3),
                "faultpoints_p50_off_ms": round(faults_off_ms, 3),
                "faultpoints_overhead_ratio": round(
                    faultpoint_overhead, 3),
                **({"cost_ab": cost_ab} if cost_ab else {}),
                "event_loop_lag_ms": loop_health.get(
                    "event_loop_lag_ms"),
                "event_loop_lag_max_ms": loop_health.get(
                    "event_loop_lag_max_ms"),
                "backends": 3,
                "bit_identical": True,
                "rest_bit_identical": True,
                "sticky_session_verified": True,
                **extra_breakdown,
            },
        }
    finally:
        if inproc_router is not None:
            try:
                inproc_router.stop()
            except Exception:
                traceback.print_exc(file=sys.stderr)
        for router in routers:
            router.kill()
        for server in servers:
            server.kill()


def bench_fleet_storm(max_iters: int) -> dict:
    """fleet_storm leg (ROADMAP item 7; docs/ROBUSTNESS.md): a seeded
    open-loop storm — stateless + ordinal-guarded sessions, burst
    arrivals, a mid-run SIGKILL — against 3 backend subprocesses + a
    router subprocess, with every invariant from
    robustness/storm.py asserted DURING the run. The record is the
    storm's open-loop latency picture plus the invariant verdict; any
    violation fails the leg. Not in the default config list (the tier-1
    smoke in tests/integration/test_fleet_storm.py is the rot canary);
    run on demand: `python bench.py --child --configs fleet_storm`."""
    from min_tfs_client_tpu.robustness.storm import FleetStorm, StormConfig
    from tests import fixtures

    tmp = pathlib.Path(tempfile.mkdtemp(prefix="tpu_bench_storm_"))
    model_root = tmp / "model"
    fixtures.write_session_jax_servable(model_root)
    monitoring = tmp / "monitoring.config"
    monitoring.write_text("prometheus_config { enable: true }\n")
    cfg = StormConfig(
        seed=int(os.environ.get("STORM_SEED", "90210")),
        quiet_s=3.0,
        duration_s=min(20.0, max(8.0, max_iters / 3.0)),
        stateless_rate_hz=18.0,
        session_rate_hz=1.5,
        session_steps_choices=(4, 8, 12),
        burst_every_s=4.0, burst_size=16,
        chaos=((8.0, "kill:2"),),
        p99_budget_ratio=30.0, p99_floor_ms=1000.0)
    servers, routers = [], []
    try:
        servers = [fixtures.ModelServerProcess(model_root, monitoring)
                   for _ in range(3)]
        backends = ",".join(s.wait_ready().backend_spec()
                            for s in servers)
        router = fixtures.RouterProcess(backends)
        routers.append(router)
        router.wait_ready()
        t0 = time.monotonic()
        while len(router.snapshot()["view"]["live"]) < 3:
            if time.monotonic() - t0 > 30:
                raise RuntimeError("router never saw 3 LIVE backends")
            time.sleep(0.05)

        def kill_backend_2():
            pid = servers[2].pid
            servers[2].kill()
            return pid

        storm = FleetStorm(
            cfg,
            router_grpc_ports=[router.grpc_port],
            monitor_rest_ports=[router.rest_port,
                                *(s.rest_port for s in servers)],
            chaos_ops={"kill:2": kill_backend_2})
        report = storm.run()
        assert report.ok(), (
            "fleet_storm invariants violated:\n" + "\n".join(
                f"  [{v.at_s:7.2f}s] {v.kind}: {v.detail}"
                for v in report.violations))
        # This leg measures the CLEAN fleet: a leaked
        # TPU_SERVING_FAULT_PLAN in the environment would arm the
        # subprocesses and silently pollute the baseline.
        assert report.fault_events_seen == 0, (
            f"{report.fault_events_seen} fault event(s) fired during "
            "the clean storm — is TPU_SERVING_FAULT_PLAN leaked into "
            "the environment?")
        summary = report.to_dict()
        summary.pop("violations")
        return {
            "metric": "fleet_storm_open_loop_p99_ms",
            "value": report.storm_p99_ms, "unit": "ms",
            "extra": {
                "seed": cfg.seed,
                "duration_s": cfg.duration_s,
                "invariants_ok": True,
                **summary,
            },
        }
    finally:
        for router in routers:
            router.kill()
        for server in servers:
            server.kill()


_CONFIG_FNS = {"bert": bench_bert, "bert_int8": bench_bert_int8,
               "matmul": bench_matmul, "use": bench_use,
               "t5": bench_t5, "resnet": bench_resnet,
               "imported": bench_imported, "in_flight": bench_in_flight,
               "decode_paged": bench_decode_paged,
               "routed": bench_routed,
               "fleet_storm": bench_fleet_storm}


def _hot_frame_table(profiling) -> dict:
    """One leg's sampled CPU attribution, compacted for the JSONL
    record: overall top self frames, the subsystem sample mix, and the
    top self frames of each of the busiest threads (which for the
    routed leg includes the in-process router's aio event loop — the
    byte-path share ROADMAP item 4 cites)."""
    body = profiling.payload(limit=6)
    if not body["sampler"]["samples"]:
        return {}
    threads = sorted(body["threads"].items(),
                     key=lambda kv: -kv[1]["samples"])[:6]
    return {
        "samples": body["sampler"]["samples"],
        "attributed_pct": body["sampler"]["attributed_pct"],
        "top_self": profiling.top_hot_frames(10),
        "subsystems": body["subsystems"],
        "threads": {
            label: {
                "subsystem": info["subsystem"],
                "samples": info["samples"],
                "top_self": info["top_self"][:5],
            } for label, info in threads},
    }


def child_main(out: pathlib.Path, configs: list[str]) -> None:
    from min_tfs_client_tpu.utils import compile_cache

    compile_cache.configure()
    import jax

    device = jax.devices()[0]
    if (device.platform != "tpu"
            and not set(configs) <= set(CPU_SERVED_CONFIGS)):
        raise SystemExit(
            f"bench: the default device is {device.platform!r} "
            f"({device.device_kind}), not a TPU. No chip, no measurement.")
    max_iters = int(os.environ.get("BENCH_ITERS", 50))
    breakdown = os.environ.get("BENCH_BREAKDOWN", "") not in ("", "0")
    with out.open("a") as sink:
        for name in configs:
            if breakdown:
                # Per-leg per-stage table: every request in this leg
                # lands in the tracing ring; clear between legs so
                # each record aggregates only its own traffic.
                from min_tfs_client_tpu.observability import (
                    profiling,
                    tracing,
                )

                tracing.ring_clear()
                # Per-leg hot-frame table: a fresh sampler per leg
                # (configure resets the fold) at a rate high enough
                # to resolve a one-leg window. The imported leg's
                # samples are the host-island attribution; the
                # routed leg's router-event-loop rows are the
                # router's byte-path profile (ROADMAP items 5, 4).
                profiling.configure(hz=67.0)
                profiling.start()
            rec = _CONFIG_FNS[name](max_iters)
            extra = rec.setdefault("extra", {})
            if name in CPU_SERVED_CONFIGS:
                extra["measured_platform"] = "cpu"
            else:
                # Only knowable HERE, in the process that owns the
                # measurement: "TPU v4" and "TPU v5 lite" numbers must
                # never compare.
                extra["measured_platform"] = device.platform
                extra["device_kind"] = device.device_kind
                extra["device_count"] = len(jax.devices())
            if breakdown:
                table = tracing.stage_breakdown()
                if table:
                    extra["stage_breakdown"] = table
                frames = _hot_frame_table(profiling)
                profiling.stop()
                if frames:
                    extra["hot_frames"] = frames
            sink.write(json.dumps(rec) + "\n")
            sink.flush()
            print(f"bench child: {name} -> "
                  f"{rec['value']:.3f} {rec['unit']}", file=sys.stderr)


if __name__ == "__main__":
    parser = argparse.ArgumentParser()
    parser.add_argument("--child", action="store_true")
    parser.add_argument("--out", type=pathlib.Path)
    parser.add_argument("--configs", type=str, default="bert")
    parser.add_argument(
        "--breakdown", action="store_true",
        help="attach a per-stage p50/p99 latency table (from the request-"
             "tracing ring) to each leg's extra.stage_breakdown, plus a "
             "sampled hot-frame table (observability/profiling.py) to "
             "extra.hot_frames, so the emitted JSON line carries both "
             "the stage and the code-level attribution")
    ns = parser.parse_args()
    if ns.breakdown:
        os.environ["BENCH_BREAKDOWN"] = "1"  # children inherit via env
    if ns.child:
        child_main(ns.out, ns.configs.split(","))
    else:
        sys.exit(main())
