"""Chip smoke: the server's main path, once, on the accelerator.

    python chip_smoke.py

Boots `python -m min_tfs_client_tpu.server.main` on seeded BERT-base and
paged T5-small exports and drives it with the unmodified client SDK over
gRPC and REST; checks answers against a float32 reference, checks the
server's own evidence that the device did the work (runtime payload,
compile ledger, a device trace with both Pallas kernels in it), and boots
a second time to show the compile cache holds. Exits non-zero, and prints
no result line, unless every phase passed on a TPU.

One process per chip: this parent never imports jax; the children that
use the chip (the server boots) run strictly one after another, and the
two helper children (export, trace reader) are pinned to the CPU.
"""

from __future__ import annotations

import concurrent.futures as cf
import functools
import importlib.metadata
import json
import os
import pathlib
import re
import shutil
import signal
import subprocess
import sys
import threading
import time
import urllib.request

REPO = pathlib.Path(__file__).resolve().parent
WORK = REPO / ".chip_smoke"  # fixed: nothing here may key a compile
DEADLINE_S = 1150.0          # the contract allows 1200 s, compile included

# What is served and how it is judged. Children receive it as JSON, so
# the parent and its children can never disagree about sizes or seeds.
PLAN = {
    "platform": "tpu",
    "bert": {"config": {}, "seq_len": 128, "batch": 32, "n_batches": 5,
             "seed": 0,
             # bf16 serving vs the float32 reference: the same comparison
             # on the CPU backend reads max |dlogit| 0.012 at this size
             # (logits ~ +-1.2), so 0.05 is 4x the dtype's own noise.
             "logit_atol": 0.05},
    "t5": {"config": {}, "seq_len": 64, "max_decode_len": 32,
           "sessions": 8, "steps": 24, "seed": 1},
    "kv_block_size": 16,     # 24 steps cross a page: table width 1 -> 2
    # Greedy streams of two programs (paged session tick vs the dense
    # whole-generation scan) may part at an argmax near-tie, after which
    # that stream differs to its end. A paging fault parts EVERY stream
    # (at step 1, or at the page crossing). So: at least half the streams
    # identical over all steps, and at least 3/4 of all tokens equal.
    "t5_min_identical_streams": 0.5,
    "t5_min_equal_tokens": 0.75,
    "kernels": ["_flash_kernel", "_paged_kernel"],
    "capture_seconds": 6.0,
}


@functools.cache
def versions() -> dict:
    out = {}
    for dist in ("jax", "jaxlib", "libtpu"):
        try:
            out[dist] = importlib.metadata.version(dist)
        except importlib.metadata.PackageNotFoundError:
            out[dist] = None
    return out


def report(phase: str, device: dict, **fields) -> None:
    print(json.dumps({"phase": phase, **device, **versions(), **fields}),
          flush=True)


# ---------------------------------------------------------------------------
# Children pinned to the CPU


def child_export(plan: dict) -> None:
    """Seeded exports through models/export.export_servable, the smoke's
    fixed inputs, and the float32 reference logits for them."""
    import dataclasses

    import jax
    import numpy as np

    from min_tfs_client_tpu.models import bert, export, t5
    from min_tfs_client_tpu.utils import compile_cache

    compile_cache.configure()
    b = plan["bert"]
    config = bert.BertConfig(**b["config"])
    params = bert.init_params(jax.random.PRNGKey(b["seed"]), config)
    export.export_servable(
        WORK / "models" / "bert", 1, "bert", dataclasses.asdict(config),
        params, signature_kwargs={"seq_len": b["seq_len"]})
    rng = np.random.default_rng(b["seed"])
    arrays = {}
    # params ride as an argument: closed over, 438 MB of weights would be
    # baked into the executable (and into its compile-cache entry).
    reference = jax.jit(
        lambda p, ids, mask: bert.reference_logits(p, config, ids, mask))
    for i, batch in enumerate([b["batch"]] * b["n_batches"] + [1]):
        ids = rng.integers(0, config.vocab_size,
                           (batch, b["seq_len"])).astype(np.int32)
        lengths = rng.integers(1, b["seq_len"] + 1, (batch,))
        lengths[0] = b["seq_len"]  # one full row per batch
        mask = (np.arange(b["seq_len"])[None] < lengths[:, None]).astype(
            np.int32)
        arrays[f"bert_ids_{i}"] = ids
        arrays[f"bert_mask_{i}"] = mask
        arrays[f"bert_logits_{i}"] = np.asarray(reference(params, ids, mask))
    arrays["bert_param_bytes"] = np.asarray(sum(
        leaf.nbytes for leaf in jax.tree_util.tree_leaves(params)))

    t = plan["t5"]
    t5_config = t5.T5Config(**t["config"])
    t5_params = t5.init_params(jax.random.PRNGKey(t["seed"]), t5_config)
    export.export_servable(
        WORK / "models" / "t5", 1, "t5", dataclasses.asdict(t5_config),
        t5_params,
        signature_kwargs={"seq_len": t["seq_len"],
                          "max_decode_len": t["max_decode_len"],
                          "continuous_batching": True,
                          "max_sessions": t["sessions"]})
    arrays["t5_prompts"] = np.random.default_rng(t["seed"]).integers(
        2, t5_config.vocab_size,
        (t["sessions"], t["seq_len"])).astype(np.int32)
    np.savez(WORK / "expected.npz", **arrays)
    report("export", child_device(), models=["bert", "t5"],
           bert_params_m=round(int(arrays["bert_param_bytes"]) / 4e6, 1))


def child_export_sharded(plan: dict) -> None:
    """The same BERT weights, exported to load TP x DP over four chips."""
    import dataclasses

    from min_tfs_client_tpu.models import bert, export

    config = bert.BertConfig(**plan["bert"]["config"])
    params = export.load_params(WORK / "models" / "bert" / "1" / "params.npz")
    export.export_servable(
        WORK / "models4" / "bert", 1, "bert", dataclasses.asdict(config),
        params, signature_kwargs={"seq_len": plan["bert"]["seq_len"]},
        sharding={"axes": {"data": 2, "model": 2}})
    report("export_sharded", child_device(), axes={"data": 2, "model": 2})


def child_trace(plan: dict) -> None:
    """Read the device capture: the accelerator's planes must hold events
    of both Pallas kernels."""
    import jax

    files = sorted((WORK / "profile").rglob("*.xplane.pb"))
    if not files:
        raise SystemExit("trace: the capture wrote no .xplane.pb")
    found = {name: {"events": 0, "device_us": 0.0}
             for name in plan["kernels"]}
    planes = []
    for path in files:
        for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
            if "/device:" not in plane.name:
                continue
            n_events = 0
            for line in plane.lines:
                for event in line.events:
                    n_events += 1
                    for name, hit in found.items():
                        if name in event.name:
                            hit["events"] += 1
                            hit["device_us"] += event.duration_ns / 1e3
            planes.append({"plane": plane.name, "events": n_events})
    for hit in found.values():
        hit["device_us"] = round(hit["device_us"], 1)
    report("trace", child_device(), planes=planes, kernels=found,
           files=[str(p.relative_to(WORK)) for p in files])
    missing = [name for name, hit in found.items() if not hit["events"]]
    if missing:
        raise SystemExit(f"trace: no device event names {missing}")


def child_device() -> dict:
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind, "count": len(devices)}


CHILDREN = {"export": child_export, "export_sharded": child_export_sharded,
            "trace": child_trace}


# ---------------------------------------------------------------------------
# Parent: process control


_started: list[subprocess.Popen] = []


def spawn(cmd: list, env: dict, log: pathlib.Path | None = None):
    out = log.open("w") if log is not None else None
    try:
        proc = subprocess.Popen(
            cmd, env=env, cwd=str(REPO), start_new_session=True,
            stdout=out, stderr=subprocess.STDOUT if out else None)
    finally:
        if out is not None:
            out.close()
    _started.append(proc)
    return proc


def stop_everything() -> None:
    for proc in _started:
        if proc.poll() is None:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait()


def out_of_time() -> None:
    print(f"chip_smoke: exceeded {DEADLINE_S:.0f} s", file=sys.stderr)
    stop_everything()
    os._exit(3)


def show_server_logs() -> None:
    for log in sorted(WORK.glob("server_*.log")):
        print(f"---- {log.name} (tail)\n"
              + log.read_text(errors="replace")[-5000:], file=sys.stderr)


def run_cpu_child(name: str, timeout_s: float) -> None:
    """A helper child pinned to the CPU; its report lines pass through."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = spawn([sys.executable, str(REPO / "chip_smoke.py"), "--child",
                  name, json.dumps(PLAN)], env)
    if proc.wait(timeout=timeout_s) != 0:
        raise RuntimeError(f"child {name!r} exited rc={proc.returncode}")


class ServerBoot:
    """One `python -m min_tfs_client_tpu.server.main`, holding the chip
    from spawn until `terminate()` has seen it exit."""

    def __init__(self, tag: str, model_config: pathlib.Path):
        self.log = WORK / f"server_{tag}.log"
        monitoring = WORK / "monitoring.config"
        monitoring.write_text("prometheus_config { enable: true }\n")
        spawned_at = time.monotonic()
        self.proc = spawn(
            [sys.executable, "-u", "-m", "min_tfs_client_tpu.server.main",
             "--port=0", "--rest_api_port=0",
             f"--model_config_file={model_config}",
             f"--kv_block_size={PLAN['kv_block_size']}",
             "--max_num_load_retries=0",
             f"--monitoring_config_file={monitoring}",
             f"--profile_dir={WORK / 'profile'}"],
            dict(os.environ, JAX_PLATFORMS=PLAN["platform"]), self.log)
        self.grpc_port, self.rest_port, self.banner = self._await_banner()
        self.boot_s = round(time.monotonic() - spawned_at, 2)

    def _await_banner(self, timeout_s: float = 600.0):
        pattern = re.compile(r"serving: gRPC on (\d+), REST on (\d+)(.*)")
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            match = pattern.search(self.log.read_text(errors="replace"))
            if match:
                return (int(match.group(1)), int(match.group(2)),
                        match.group(3).strip("; \n"))
            if self.proc.poll() is not None:
                break
            time.sleep(0.2)
        raise RuntimeError(f"server did not serve (rc={self.proc.poll()}; "
                           f"its log follows on stderr): {self.log}")

    def rest(self, path: str, body: dict | None = None,
             timeout_s: float = 600.0) -> dict:
        """GET `path`, or POST `body` to it as JSON; the JSON answer."""
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.rest_port}{path}",
            data=None if body is None else json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=timeout_s) as resp:
            return json.loads(resp.read())

    def runtime(self) -> tuple[dict, dict]:
        """(device summary as JAX reports it, /monitoring/runtime)."""
        payload = self.rest("/monitoring/runtime")
        devices = payload["devices"]
        return ({"platform": devices[0]["platform"],
                 "device_kind": devices[0]["kind"],
                 "count": len(devices)}, payload)

    def terminate(self) -> int:
        """SIGTERM -> the server drains and exits; returns its code."""
        self.proc.send_signal(signal.SIGTERM)
        return self.proc.wait(timeout=180)


def write_model_config(path: pathlib.Path, models: dict) -> pathlib.Path:
    entries = "".join(
        f'  config {{ name: "{name}" base_path: "{base}" '
        f'model_platform: "jax" }}\n' for name, base in models.items())
    path.write_text(f"model_config_list {{\n{entries}}}\n")
    return path


# ---------------------------------------------------------------------------
# Parent: traffic and checks


def bert_predict(client, expected, i: int):
    """One gRPC Predict of fixed batch `i`; returns (logits, seconds)."""
    from min_tfs_client_tpu.tensor.codec import tensor_proto_to_ndarray

    t0 = time.monotonic()
    resp = client.predict_request(
        "bert", {"input_ids": expected[f"bert_ids_{i}"],
                 "attention_mask": expected[f"bert_mask_{i}"]},
        timeout=600)
    seconds = time.monotonic() - t0
    return tensor_proto_to_ndarray(resp.outputs["logits"]), seconds


def check_logits(got, want, what: str) -> float:
    import numpy as np

    if got.shape != want.shape:
        raise AssertionError(f"{what}: shape {got.shape} != {want.shape}")
    if not np.isfinite(got).all():
        raise AssertionError(f"{what}: non-finite logits")
    err = float(np.max(np.abs(got - want)))
    if err > PLAN["bert"]["logit_atol"]:
        raise AssertionError(
            f"{what}: max |dlogit| {err:.4f} vs the float32 reference "
            f"exceeds {PLAN['bert']['logit_atol']}")
    return err


def phase_bert(server: ServerBoot, client, expected) -> tuple[dict, object]:
    """Returns (report fields, the served logits of batch 0)."""
    import numpy as np

    b = PLAN["bert"]
    errs, times, served = [], [], []
    for i in range(b["n_batches"] + 1):  # the last one is batch 1
        logits, seconds = bert_predict(client, expected, i)
        errs.append(check_logits(logits, expected[f"bert_logits_{i}"],
                                 f"bert gRPC batch {i}"))
        times.append(round(seconds, 3))
        served.append(logits)
    last = b["n_batches"]
    rest = server.rest("/v1/models/bert:predict", {"inputs": {
        "input_ids": expected[f"bert_ids_{last}"].tolist(),
        "attention_mask": expected[f"bert_mask_{last}"].tolist()}})
    rest_err = check_logits(
        np.asarray(rest["outputs"]["logits"], np.float32),
        expected[f"bert_logits_{last}"], "bert REST batch 1")
    return {"grpc_predicts": len(errs), "rest_predicts": 1,
            "batch": b["batch"], "seq_len": b["seq_len"],
            "max_abs_dlogit": round(max(errs + [rest_err]), 5),
            "logit_atol": b["logit_atol"],
            "first_request_s": times[0], "request_s": times}, served[0]


def run_sessions(client, prompts, tag: str, steps: int) -> list[list[int]]:
    """decode_init, `steps` decode_steps, decode_close — one thread per
    session, all stepping concurrently (they share the pool's tick)."""
    import numpy as np

    from min_tfs_client_tpu.tensor.codec import tensor_proto_to_ndarray

    barrier = threading.Barrier(len(prompts))

    def one(i: int) -> list[int]:
        sid = np.asarray(f"{tag}-{i}".encode(), object)
        client.predict_request(
            "t5", {"session_id": sid, "input_ids": prompts[i:i + 1]},
            signature_name="decode_init", timeout=600)
        barrier.wait(timeout=600)
        tokens = []
        for _ in range(steps):
            resp = client.predict_request(
                "t5", {"session_id": sid}, signature_name="decode_step",
                timeout=600)
            tokens.append(int(tensor_proto_to_ndarray(
                resp.outputs["token"])[0]))
        client.predict_request("t5", {"session_id": sid},
                               signature_name="decode_close", timeout=600)
        return tokens

    with cf.ThreadPoolExecutor(len(prompts)) as pool:
        return [f.result() for f in
                [pool.submit(one, i) for i in range(len(prompts))]]


def phase_t5(client, expected) -> dict:
    from min_tfs_client_tpu.tensor.codec import tensor_proto_to_ndarray

    t = PLAN["t5"]
    prompts = expected["t5_prompts"]
    streams = run_sessions(client, prompts, "smoke", t["steps"])
    whole = tensor_proto_to_ndarray(client.predict_request(
        "t5", {"input_ids": prompts}, timeout=600).outputs["output_ids"])
    equal = identical = 0
    parted_at = {}
    for i, stream in enumerate(streams):
        want = [int(x) for x in whole[i, :t["steps"]]]
        same = [a == b for a, b in zip(stream, want)]
        equal += sum(same)
        if all(same):
            identical += 1
        else:
            parted_at[i] = same.index(False) + 1
    total = len(streams) * t["steps"]
    result = {"sessions": len(streams), "steps": t["steps"],
              "page_tokens": PLAN["kv_block_size"],
              "tokens_equal": equal, "tokens_total": total,
              "streams_identical": identical, "parted_at_step": parted_at,
              "bar": {"min_identical_streams": PLAN["t5_min_identical_streams"],
                      "min_equal_tokens": PLAN["t5_min_equal_tokens"]}}
    if (identical < PLAN["t5_min_identical_streams"] * len(streams)
            or equal < PLAN["t5_min_equal_tokens"] * total):
        raise AssertionError(f"t5 paged sessions disagree with the same "
                             f"server's whole generation: {result}")
    return result


def phase_capture(server: ServerBoot, client, expected) -> dict:
    """A device capture through /monitoring/profile?device=1 while BERT
    and paged-decode traffic runs; the trace child reads it later."""
    seconds = PLAN["capture_seconds"]
    with cf.ThreadPoolExecutor(1) as pool:
        capture = pool.submit(
            server.rest, f"/monitoring/profile?device=1&seconds={seconds}")
        rounds = 0
        while not capture.done():
            bert_predict(client, expected, 0)
            run_sessions(client, expected["t5_prompts"][:2],
                         f"capture{rounds}", PLAN["t5"]["steps"])
            rounds += 1
        body = capture.result()
    if not any(name.endswith(".xplane.pb") for name in body["files"]):
        raise AssertionError(f"device capture wrote no xplane: {body}")
    return {"seconds": body["seconds"], "traffic_rounds": rounds,
            "files": body["files"]}


def check_runtime(server: ServerBoot) -> dict:
    """The server's own evidence that the device did the work."""
    _, payload = server.runtime()
    for d in payload["devices"]:
        if (d["platform"] != PLAN["platform"] or d.get("source") != "pjrt"
                or not d.get("bytes_limit")):
            raise AssertionError(f"not a {PLAN['platform']}/pjrt device "
                                 f"with a real bytes_limit: {d}")
    programs = sorted(payload["compile"]["executables"])
    for needle in ("bert:", "t5:", ":tick_direct"):
        if not any(needle in label for label in programs):
            raise AssertionError(f"no {needle!r} program in the compile "
                                 f"ledger: {programs}")
    fallback = [p for p in programs if p.endswith(":tick")]
    if fallback:
        raise AssertionError(f"dense-gather tick compiled: {fallback}")
    return {"programs": programs,
            "bytes_limit": payload["devices"][0]["bytes_limit"]}


def cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def phase_four_chips(expected, one_chip_logits) -> dict:
    """BERT-base TP x DP over four chips: the same answer, and weights
    resident on every device rather than all on device 0."""
    import numpy as np

    from min_tfs_client_tpu.client import TensorServingClient

    run_cpu_child("export_sharded", 300)
    config = write_model_config(WORK / "models4.config",
                                {"bert": WORK / "models4" / "bert"})
    server = ServerBoot("four_chips", config)
    with TensorServingClient("127.0.0.1", server.grpc_port) as client:
        logits, seconds = bert_predict(client, expected, 0)
    err_ref = check_logits(logits, expected["bert_logits_0"],
                           "four-chip bert batch 0")
    err_one = float(np.max(np.abs(logits - one_chip_logits)))
    if err_one > PLAN["bert"]["logit_atol"]:
        raise AssertionError(f"four-chip answer is {err_one:.4f} from the "
                             "one-chip answer")
    devices = server.runtime()[1]["devices"]
    share = int(expected["bert_param_bytes"]) // len(devices)
    in_use = {d["id"]: d["bytes_in_use"] for d in devices}
    if len(devices) < 4 or min(in_use.values()) < share:
        raise AssertionError(f"weights not spread over four devices "
                             f"(share {share} bytes): {in_use}")
    rc = server.terminate()
    if rc != 0:
        raise AssertionError(f"four-chip server exited rc={rc} on SIGTERM")
    return {"max_abs_dlogit_vs_reference": round(err_ref, 5),
            "max_abs_dlogit_vs_one_chip": round(err_one, 5),
            "bytes_in_use": in_use, "weight_share_bytes": share,
            "first_request_s": round(seconds, 3), "boot_s": server.boot_s}


def main() -> int:
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and PLAN["platform"] not in platforms.split(","):
        print(f"chip_smoke: JAX_PLATFORMS={platforms!r} excludes "
              f"{PLAN['platform']!r}: there is no chip to smoke, and this "
              "script does not fall back to another backend",
              file=sys.stderr)
        return 2
    import numpy as np

    from min_tfs_client_tpu.client import TensorServingClient
    from min_tfs_client_tpu.utils import compile_cache  # imports no jax

    watchdog = threading.Timer(DEADLINE_S, out_of_time)
    watchdog.daemon = True
    watchdog.start()
    shutil.rmtree(WORK, ignore_errors=True)
    (WORK / "profile").mkdir(parents=True)
    try:
        run_cpu_child("export", 500)
        expected = dict(np.load(WORK / "expected.npz"))
        config = write_model_config(
            WORK / "models.config", {"bert": WORK / "models" / "bert",
                                     "t5": WORK / "models" / "t5"})

        server = ServerBoot("boot1", config)
        device, _ = server.runtime()
        report("boot", device, boot_s=server.boot_s, serving=server.banner)
        with TensorServingClient("127.0.0.1", server.grpc_port) as client:
            bert, one_chip_logits = phase_bert(server, client, expected)
            report("bert", device, **bert)
            report("t5_paged_sessions", device, **phase_t5(client, expected))
            report("capture", device,
                   **phase_capture(server, client, expected))
        report("runtime", device, **check_runtime(server))
        rc = server.terminate()
        if rc != 0:
            raise AssertionError(f"server exited rc={rc} on SIGTERM")
        report("sigterm", device, exit_code=rc)

        cache_dir = compile_cache.cache_dir()
        before = cache_entries(cache_dir)
        second = ServerBoot("boot2", config)
        with TensorServingClient("127.0.0.1", second.grpc_port) as client:
            logits, seconds = bert_predict(client, expected, 0)
        check_logits(logits, expected["bert_logits_0"], "second boot bert")
        rc = second.terminate()
        added = cache_entries(cache_dir) - before
        if rc != 0 or added:
            raise AssertionError(
                f"second boot: exit code {rc}, {added} new compile-cache "
                f"entries in {cache_dir} (expected 0 and 0)")
        report("second_boot", device, cache_dir=cache_dir,
               cache_entries=before, new_cache_entries=added,
               first_request_s={"first_boot": bert["first_request_s"],
                                "second_boot": round(seconds, 3)},
               boot_s={"first_boot": server.boot_s,
                       "second_boot": second.boot_s})

        if device["count"] >= 4:
            report("four_chips", device,
                   **phase_four_chips(expected, one_chip_logits))
        else:
            report("four_chips", device,
                   result=f"not run: {device['count']} device")

        run_cpu_child("trace", 300)
    except BaseException:
        show_server_logs()
        raise
    finally:
        stop_everything()
        watchdog.cancel()
    assert "jax" not in sys.modules, "the smoke's parent imported jax"
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["device_kind"],
        "count": device["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    if len(sys.argv) == 4 and sys.argv[1] == "--child":
        CHILDREN[sys.argv[2]](json.loads(sys.argv[3]))
        sys.exit(0)
    sys.exit(main())
