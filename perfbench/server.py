"""The system under test: one `python -m min_tfs_client_tpu.server.main`,
the only process of a run that holds the chip. Boot, the monitoring
endpoints, stop. (The sound parts of chip_smoke.py's ServerBoot, copied:
the yardstick may not import from a file a later PR may change.)
"""

from __future__ import annotations

import json
import os
import pathlib
import re
import signal
import subprocess
import sys
import time
import urllib.request

REPO = pathlib.Path(__file__).resolve().parents[1]

_started: list[subprocess.Popen] = []


def spawn(cmd: list, env: dict, log: pathlib.Path | None = None, **pipes):
    """Start a child in its own process group, remembered for stop_all.
    Its output goes to `log`, or where `pipes` (Popen's stdin, stdout,
    text) say, or to this process's own."""
    out = log.open("w") if log is not None else None
    try:
        if out is not None:
            pipes.update(stdout=out, stderr=subprocess.STDOUT)
        proc = subprocess.Popen(cmd, env=env, cwd=str(REPO),
                                start_new_session=True, **pipes)
    finally:
        if out is not None:
            out.close()
    _started.append(proc)
    return proc


def stop_all() -> None:
    """Kill whatever this run started and wait until each has ended."""
    for proc in _started:
        if proc.poll() is None:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
        proc.wait()


def batching_text(params: dict) -> str:
    """A BatchingParameters text proto from the configuration's dict."""
    lines = []
    for key, value in params.items():
        if isinstance(value, list):
            lines += [f"{key}: {v}" for v in value]
        else:
            lines.append(f"{key} {{ value: {value} }}")
    return "\n".join(lines) + "\n"


class Server:
    def __init__(self, work: pathlib.Path, export_dir: pathlib.Path,
                 serve: dict, *, platform: str, cache_dir: pathlib.Path,
                 trace_ring: int = 0):
        self.log = work / "server.log"
        monitoring = work / "monitoring.config"
        monitoring.write_text("prometheus_config { enable: true }\n")
        flags = list(serve.get("server_flags", []))
        models = work / "models.config"
        name = serve["model_name"]
        models.write_text(
            f'model_config_list {{\n  config {{ name: "{name}" base_path: '
            f'"{export_dir / name}" model_platform: "jax" }}\n}}\n')
        if serve.get("batching"):
            batching = work / "batching.config"
            batching.write_text(batching_text(serve["batching"]))
            flags += ["--enable_batching",
                      f"--batching_parameters_file={batching}"]
        if trace_ring:
            flags.append(f"--trace_ring_size={trace_ring}")
        env = dict(os.environ, JAX_PLATFORMS=platform)
        # The program keeps its compile cache where this variable says and
        # sets no other in code; unset, give it the benchmark's own fixed
        # directory inside the checkout.
        env.setdefault("JAX_COMPILATION_CACHE_DIR", str(cache_dir))
        self.cache_dir = env["JAX_COMPILATION_CACHE_DIR"]
        self.proc = spawn(
            [sys.executable, "-u", "-m", "min_tfs_client_tpu.server.main",
             "--port=0", "--rest_api_port=0",
             f"--model_config_file={models}", "--max_num_load_retries=0",
             f"--monitoring_config_file={monitoring}",
             f"--profile_dir={work / 'profile'}", *flags], env, self.log)
        self.grpc_port, self.rest_port = self._await_banner()

    def _await_banner(self, timeout_s: float = 900.0):
        pattern = re.compile(r"serving: gRPC on (\d+), REST on (\d+)")
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            match = pattern.search(self.log.read_text(errors="replace"))
            if match:
                return int(match.group(1)), int(match.group(2))
            if self.proc.poll() is not None:
                break
            time.sleep(0.1)
        raise RuntimeError(f"server did not serve (rc={self.proc.poll()}): "
                           + self.log_tail())

    def log_tail(self, n: int = 4000) -> str:
        return self.log.read_text(errors="replace")[-n:]

    def rest(self, path: str, timeout_s: float = 300.0) -> dict:
        url = f"http://127.0.0.1:{self.rest_port}{path}"
        with urllib.request.urlopen(url, timeout=timeout_s) as resp:
            return json.loads(resp.read())

    def runtime(self) -> dict:
        return self.rest("/monitoring/runtime")

    def device(self, runtime: dict | None = None) -> dict:
        """The device as JAX reports it, with the peak on the fullest chip."""
        devices = (runtime or self.runtime())["devices"]
        return {"platform": devices[0]["platform"],
                "kind": devices[0]["kind"], "count": len(devices),
                "memory_peak_bytes": max(
                    int(d.get("peak_bytes_in_use", 0)) for d in devices)}

    def terminate(self) -> int:
        """SIGTERM: the server drains and exits; returns its code."""
        self.proc.send_signal(signal.SIGTERM)
        try:
            return self.proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            os.killpg(self.proc.pid, signal.SIGKILL)
            return self.proc.wait()
