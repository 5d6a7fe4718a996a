"""Load generator worker: one process, few threads, driving the server
over gRPC with the unmodified client SDK.

    python perfbench/loadgen.py <plan.json> <records.json>

The parent (run.py) writes the plan: the run's work
(traffic.build_plan) and where the server listens. The worker
builds its inputs, prints `ready`, and reads from its standard input the
instant the window opens, on CLOCK_MONOTONIC, which every process of the
machine shares; then it runs its loop. All times in the records are
seconds from the window's opening. It never imports jax.
"""

from __future__ import annotations

import concurrent.futures as cf
import json
import os
import pathlib
import sys
import threading
import time

REPO = pathlib.Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))


class Sender:
    """Sends one request and records what the client saw."""

    def __init__(self, plan: dict):
        import numpy as np

        from min_tfs_client_tpu.client import TensorServingClient

        self.np = np
        self.plan = plan
        self.t0 = 0.0
        self.client = TensorServingClient(plan["host"], int(plan["port"]))
        self.rng = np.random.default_rng(
            [int(plan["seed"]) & (2**63 - 1), 100 + int(plan["worker"])])

    def inputs(self, item: dict) -> dict:
        from perfbench.traffic import request_inputs

        return request_inputs(self.plan["request_inputs"], item["length"],
                              item.get("examples", 1),
                              self.plan["vocab_size"], self.rng)

    def wait_go(self) -> None:
        """Inputs are built and the channel is connected (a lazy connect
        would make the first requests late): tell the parent, and take
        the window's opening instant from it."""
        import grpc

        grpc.channel_ready_future(self.client._channel).result(timeout=60)
        print("ready", flush=True)
        self.t0 = float(sys.stdin.readline())

    def now(self) -> float:
        return time.monotonic() - self.t0

    def predict(self, inputs: dict, signature: str | None = None):
        return self.client.predict_request(
            self.plan["model"], inputs,
            timeout=self.plan["timeout_s"],
            signature_name=signature or self.plan["signature"])

    def timed(self, item: dict, inputs: dict, due: float) -> dict:
        rec = {"id": item["id"], "due": due, "sent": self.now(),
               "outputs": item.get("examples", 1), "ok": True}
        try:
            self.predict(inputs)
        except Exception as exc:  # noqa: BLE001 - every failure is counted
            rec["ok"] = False
            rec["error"] = f"{type(exc).__name__}: {exc}"[:300]
        rec["done"] = self.now()
        return rec


class Probe(threading.Thread):
    """What the generator cost and how late its threads ran, over the
    window alone: the process's CPU (`os.times()` at the opening and at
    the close, in cores) and the overshoot of a thread that asks to sleep
    TICK_S again and again: a thread of this process that is ready to
    run waits that long for the interpreter, or for the host."""

    TICK_S = 0.01

    def __init__(self, sender: Sender, seconds: float):
        super().__init__(name="loadgen-probe", daemon=True)
        self.sender, self.seconds = sender, seconds
        self.found: dict = {}
        self.start()

    @staticmethod
    def _cpu_s() -> float:
        t = os.times()
        return t.user + t.system

    def run(self) -> None:
        from perfbench import stats

        time.sleep(max(0.0, -self.sender.now()))
        opened, cpu0, late = self.sender.now(), self._cpu_s(), []
        while (now := self.sender.now()) < self.seconds:
            time.sleep(self.TICK_S)
            late.append((self.sender.now() - now - self.TICK_S) * 1e3)
        span = self.sender.now() - opened
        self.found = {
            "span_s": span,
            "cpu_cores": (self._cpu_s() - cpu0) / span if span > 0 else None,
            "thread_late_ms": {
                "samples": len(late), "mean": sum(late) / len(late),
                "p50": stats.percentile(late, 50),
                "p99": stats.percentile(late, 99),
                "max": max(late)} if late else None}

    def result(self) -> dict:
        self.join(timeout=5.0)
        return self.found


def run_open_loop(sender: Sender, plan: dict) -> dict:
    """Arrivals on the schedule, whether or not earlier ones ended. A
    request waits for a pool thread only when more are in flight than
    `threads`; that wait shows as lateness (sent - due)."""
    requests = sorted(plan["requests"], key=lambda r: r["due"])
    prepared = [sender.inputs(r) for r in requests]
    sender.wait_go()
    probe = Probe(sender, float(plan["seconds"]))
    records = []
    with cf.ThreadPoolExecutor(int(plan["threads"])) as pool:
        futures = []
        for item, inputs in zip(requests, prepared):
            wait = item["due"] - sender.now()
            if wait > 0:
                time.sleep(wait)
            futures.append(pool.submit(sender.timed, item, inputs,
                                       item["due"]))
        records = [f.result() for f in futures]
    return {"requests": records, "generator": probe.result()}


def run_sessions(sender: Sender, plan: dict) -> dict:
    """Each client runs decode sessions back to back: decode_init, then
    decode_step up to the session's drawn output length (the traffic's
    length, not an EOS of random weights: a finished stream answers with
    the pad token and every answered step is one output), decode_close,
    and the next session at once. Clients start staggered over the ramp,
    before the window opens; at its close each ends its session."""
    np = sender.np
    end = float(plan["seconds"])
    sender.wait_go()
    probe = Probe(sender, end)
    lock = threading.Lock()   # one generator of inputs, several clients

    def one(start: float, sessions) -> list[dict]:
        out = []
        wait = start - sender.now()
        if wait > 0:
            time.sleep(wait)
        for item in sessions:
            if sender.now() >= end:
                break
            with lock:
                inputs = sender.inputs(item)
            sid = np.asarray(
                f"w{plan['worker']}-s{item['id']}".encode(), object)
            rec = {"id": item["id"], "length": item["length"],
                   "outputs": item["outputs"], "due": sender.now(),
                   "steps": [], "ok": True}
            rec["sent"] = rec["due"]
            try:
                sender.predict({"session_id": sid, **inputs}, "decode_init")
                rec["init_done"] = sender.now()
                for _ in range(item["outputs"]):
                    if sender.now() >= end:
                        break
                    sender.predict({"session_id": sid}, "decode_step")
                    rec["steps"].append(sender.now())
            except Exception as exc:  # noqa: BLE001 - counted as failed
                rec["ok"] = False
                rec["error"] = f"{type(exc).__name__}: {exc}"[:300]
            try:
                sender.predict({"session_id": sid}, "decode_close")
            except Exception as exc:  # noqa: BLE001
                if rec["ok"]:
                    rec["ok"] = False
                    rec["error"] = f"close: {type(exc).__name__}: {exc}"[:300]
            rec["done"] = sender.now()
            out.append(rec)
        else:
            raise RuntimeError("a session client ran out of sessions "
                               "before the window closed: raise "
                               "items_per_client in the traffic file")
        return out

    with cf.ThreadPoolExecutor(len(plan["clients"])) as pool:
        futures = [pool.submit(one, c["start"], c["sessions"])
                   for c in plan["clients"]]
        return {"sessions": [r for f in futures for r in f.result()],
                "generator": probe.result()}


LOOPS = {"open_loop": run_open_loop, "sessions": run_sessions}


def main(argv: list[str]) -> int:
    plan = json.loads(pathlib.Path(argv[1]).read_text())
    sender = Sender(plan)
    records = LOOPS[plan["kind"]](sender, plan)
    sender.client.close()
    pathlib.Path(argv[2]).write_text(json.dumps(records))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
