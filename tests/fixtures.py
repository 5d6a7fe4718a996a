"""Model fixtures built with this package's own protos (no TF import).

half_plus_two and the identity model mirror the reference's committed
fixtures (tests/integration/fixtures/, generated by
generate_tensorflow_model.py:12-57: TF1 placeholders -> identity, with both
a named and the default serving signature).
"""

from __future__ import annotations

import pathlib
import re

from min_tfs_client_tpu.protos import tf_graph_pb2, tf_tensor_pb2
from min_tfs_client_tpu.tensor.codec import ndarray_to_tensor_proto

import numpy as np

DT = tf_tensor_pb2


def _set_attrs(node, attrs):
    for key, value in attrs.items():
        if key in ("dtype", "T", "DstT", "SrcT"):
            node.attr[key].type = value
        elif key == "value":
            node.attr[key].tensor.CopyFrom(value)
        elif key == "func":
            node.attr["f"].func.name = value
        elif key == "shape":
            shp = node.attr[key].shape
            for d in value:
                shp.dim.add(size=d)
        elif isinstance(value, bool):
            node.attr[key].b = value
        elif isinstance(value, int):
            node.attr[key].i = value
        elif isinstance(value, float):
            node.attr[key].f = value
        elif isinstance(value, (str, bytes)):
            node.attr[key].s = (value.encode()
                                if isinstance(value, str) else value)
        elif isinstance(value, (list, tuple)) and \
                all(isinstance(v, int) for v in value):
            node.attr[key].list.i.extend(value)
        else:
            raise TypeError(f"unsupported attr {key}={value!r}")
    return node


def _node(graph, name, op, inputs=(), **attrs):
    node = graph.node.add()
    node.name = name
    node.op = op
    node.input.extend(inputs)
    return _set_attrs(node, attrs)


def _sig(meta_graph, key, method, inputs, outputs):
    sig = meta_graph.signature_def[key]
    sig.method_name = method
    for alias, (tensor_name, dtype, shape) in inputs.items():
        info = sig.inputs[alias]
        info.name = tensor_name
        info.dtype = dtype
        for d in shape:
            info.tensor_shape.dim.add(size=d)
    for alias, (tensor_name, dtype, shape) in outputs.items():
        info = sig.outputs[alias]
        info.name = tensor_name
        info.dtype = dtype
        for d in shape:
            info.tensor_shape.dim.add(size=d)


def _write(saved_model, base_path: pathlib.Path, version: int) -> pathlib.Path:
    vdir = base_path / str(version)
    vdir.mkdir(parents=True, exist_ok=True)
    (vdir / "saved_model.pb").write_bytes(saved_model.SerializeToString())
    return vdir


def write_half_plus_two(base_path, version: int = 1) -> pathlib.Path:
    """y = 0.5 * x + 2 over float32 [-1] — the classic TF-Serving toy."""
    sm = tf_graph_pb2.SavedModel(saved_model_schema_version=1)
    mg = sm.meta_graphs.add()
    mg.meta_info_def.tags.append("serve")
    g = mg.graph_def
    _node(g, "x", "Placeholder", dtype=DT.DT_FLOAT, shape=[-1])
    _node(g, "a", "Const", dtype=DT.DT_FLOAT,
          value=ndarray_to_tensor_proto(np.float32(0.5)))
    _node(g, "b", "Const", dtype=DT.DT_FLOAT,
          value=ndarray_to_tensor_proto(np.float32(2.0)))
    _node(g, "mul", "Mul", ["x", "a"], T=DT.DT_FLOAT)
    _node(g, "y", "Add", ["mul", "b"], T=DT.DT_FLOAT)
    _sig(mg, "serving_default", "tensorflow/serving/predict",
         {"x": ("x:0", DT.DT_FLOAT, [-1])},
         {"y": ("y:0", DT.DT_FLOAT, [-1])})
    return _write(sm, pathlib.Path(base_path), version)


def write_identity_model(base_path, version: int = 1) -> pathlib.Path:
    """string/float/int pass-through with a named and a default signature
    (mirrors reference generate_tensorflow_model.py:12-57)."""
    sm = tf_graph_pb2.SavedModel(saved_model_schema_version=1)
    mg = sm.meta_graphs.add()
    mg.meta_info_def.tags.append("serve")
    g = mg.graph_def
    for name, dtype in (("string", DT.DT_STRING), ("float", DT.DT_FLOAT),
                        ("int", DT.DT_INT32)):
        _node(g, f"{name}_input", "Placeholder", dtype=dtype, shape=[-1])
        _node(g, f"{name}_output", "Identity", [f"{name}_input"], T=dtype)
    io = {
        f"{n}_{d}": (f"{n}_{d}:0", t, [-1])
        for n, t in (("string", DT.DT_STRING), ("float", DT.DT_FLOAT),
                     ("int", DT.DT_INT32))
        for d in ("input", "output")
    }
    ins = {f"{n}_input": io[f"{n}_input"] for n in ("string", "float", "int")}
    outs = {f"{n}_output": io[f"{n}_output"] for n in ("string", "float", "int")}
    _sig(mg, "serving_default", "tensorflow/serving/predict", ins, outs)
    _sig(mg, "inputs", "tensorflow/serving/predict", ins, outs)
    return _write(sm, pathlib.Path(base_path), version)


def write_matmul_model(base_path, version: int = 1,
                       scale: float = 1.0) -> pathlib.Path:
    """Numeric-only model exercising MatMul/BiasAdd/Relu/Softmax on device.
    logits = relu(x @ W + b) * scale; probs = softmax(logits)."""
    rng = np.random.default_rng(42)
    w = rng.standard_normal((8, 4)).astype(np.float32)
    b = rng.standard_normal((4,)).astype(np.float32)
    sm = tf_graph_pb2.SavedModel(saved_model_schema_version=1)
    mg = sm.meta_graphs.add()
    mg.meta_info_def.tags.append("serve")
    g = mg.graph_def
    _node(g, "x", "Placeholder", dtype=DT.DT_FLOAT, shape=[-1, 8])
    _node(g, "w", "Const", dtype=DT.DT_FLOAT, value=ndarray_to_tensor_proto(w))
    _node(g, "b", "Const", dtype=DT.DT_FLOAT, value=ndarray_to_tensor_proto(b))
    _node(g, "mm", "MatMul", ["x", "w"], T=DT.DT_FLOAT)
    _node(g, "ba", "BiasAdd", ["mm", "b"], T=DT.DT_FLOAT)
    _node(g, "relu", "Relu", ["ba"], T=DT.DT_FLOAT)
    _node(g, "scale", "Const", dtype=DT.DT_FLOAT,
          value=ndarray_to_tensor_proto(np.float32(scale)))
    _node(g, "logits", "Mul", ["relu", "scale"], T=DT.DT_FLOAT)
    _node(g, "probs", "Softmax", ["logits"], T=DT.DT_FLOAT)
    _sig(mg, "serving_default", "tensorflow/serving/predict",
         {"x": ("x:0", DT.DT_FLOAT, [-1, 8])},
         {"logits": ("logits:0", DT.DT_FLOAT, [-1, 4]),
          "probs": ("probs:0", DT.DT_FLOAT, [-1, 4])})
    return _write(sm, pathlib.Path(base_path), version), (w, b)


JAX_SERVABLE_SRC = '''
"""Native jax servable fixture: predict + classify + regress signatures."""
import numpy as np

from min_tfs_client_tpu.servables.servable import (
    CLASSIFY_METHOD_NAME, REGRESS_METHOD_NAME, Servable, Signature, TensorSpec)
from min_tfs_client_tpu.tensor.example_codec import FeatureSpec


def build(path):
    def predict_fn(inputs):
        import jax.numpy as jnp
        x = inputs["x"]
        return {"y": x * 3.0 + 1.0}

    def classify_fn(inputs):
        import jax.numpy as jnp
        score = inputs["score"]
        pos = 1 / (1 + jnp.exp(-score))
        return {"scores": jnp.stack([1 - pos, pos], axis=-1)}

    def regress_fn(inputs):
        return {"outputs": inputs["x"] * 2.0}

    return {
        "serving_default": Signature(
            fn=predict_fn,
            inputs={"x": TensorSpec(np.float32, (None,))},
            outputs={"y": TensorSpec(np.float32, (None,))},
        ),
        "classify": Signature(
            fn=classify_fn,
            inputs={"score": TensorSpec(np.float32, (None,))},
            outputs={"scores": TensorSpec(np.float32, (None, 2))},
            method_name=CLASSIFY_METHOD_NAME,
            feature_specs={"score": FeatureSpec(np.float32)},
            class_labels=[b"neg", b"pos"],
        ),
        "regress": Signature(
            fn=regress_fn,
            inputs={"x": TensorSpec(np.float32, (None,))},
            outputs={"outputs": TensorSpec(np.float32, (None,))},
            method_name=REGRESS_METHOD_NAME,
            feature_specs={"x": FeatureSpec(np.float32)},
        ),
    }
'''


def write_jax_servable(base_path, version: int = 1) -> pathlib.Path:
    vdir = pathlib.Path(base_path) / str(version)
    vdir.mkdir(parents=True, exist_ok=True)
    (vdir / "servable.py").write_text(JAX_SERVABLE_SRC)
    return vdir


SESSION_JAX_SERVABLE_SRC = '''
"""Sessioned jax servable fixture: a minimal decode-session surface whose
per-session state lives in THIS process (stand-in for the HBM KV cache),
so routed-fleet tests can observe stickiness, drain, and loss.

 * serving_default  pure y = 3x + 1 — bit-identical on every backend;
 * whoami           returns the serving process pid — placement probe;
 * decode_init/step/close  host-side counter sessions keyed by
   session_id: step N returns base + N and the pid that owns the state.
   Live sessions feed the decode_session_count gauge so a server's
   --drain_grace_seconds waits for them like real decode sessions.
"""
import os
import threading

import numpy as np

from min_tfs_client_tpu.servables.decode_sessions import (
    StepDeduper, read_step_ordinal)
from min_tfs_client_tpu.servables.servable import Signature, TensorSpec
from min_tfs_client_tpu.utils.status import ServingError

MODEL_LABEL = "session_fixture"


def build(path):
    lock = threading.Lock()
    sessions = {}                            # guarded_by: lock
    # is_live via dict membership (GIL-atomic read): a live session's
    # at-most-once guard is never LRU-evicted, matching models/t5.py.
    dedup = StepDeduper(max_entries=512,
                        is_live=sessions.__contains__)
    pid = os.getpid()

    def _export_count(n):
        from min_tfs_client_tpu.server import metrics

        metrics.safe_set(metrics.decode_session_count, float(n),
                         MODEL_LABEL)

    def _sid(inputs):
        raw = np.asarray(inputs["session_id"]).reshape(-1)
        value = raw[0]
        return value if isinstance(value, bytes) else str(value).encode()

    def predict_fn(inputs):
        return {"y": inputs["x"] * 3.0 + 1.0}  # jitted: stay traceable

    def whoami_fn(inputs):
        return {"pid": np.asarray([pid], np.int64)}

    def init_fn(inputs):
        sid = _sid(inputs)
        dedup.forget(sid)  # re-init = new stream (same as models/t5.py)
        base = int(np.asarray(inputs["base"]).reshape(-1)[0])
        with lock:
            sessions[sid] = [base, 0]
            count = len(sessions)
        _export_count(count)
        return {"session_id": np.asarray(sid, object),
                "pid": np.asarray([pid], np.int64)}

    def step_fn(inputs):
        sid = _sid(inputs)
        # Optional at-most-once guard, same semantics as the real t5
        # decode surface: a duplicate step_ordinal resend replays the
        # cached response instead of re-advancing the counter.
        ordinal = read_step_ordinal(inputs)
        cached = dedup.replay(sid, ordinal)  # marks ordinal in flight
        if cached is not None:
            return cached
        try:
            with lock:
                if sid not in sessions:
                    raise ServingError.not_found(
                        "unknown session %r (state lives per-process)"
                        % sid)
                sessions[sid][1] += 1
                base, step = sessions[sid]
            out = {"token": np.asarray([base + step], np.int32),
                   "finished": np.asarray([0], np.int32),
                   "pid": np.asarray([pid], np.int64)}
        except BaseException:
            dedup.abandon(sid, ordinal)  # failed: retry re-executes
            raise
        dedup.commit(sid, ordinal, out)
        return out

    def close_fn(inputs):
        sid = _sid(inputs)
        dedup.forget(sid)
        with lock:
            closed = sessions.pop(sid, None) is not None
            count = len(sessions)
        _export_count(count)
        return {"closed": np.asarray(int(closed), np.int32)}

    session_spec = TensorSpec("DT_STRING", ())
    return {
        "serving_default": Signature(
            fn=predict_fn,
            inputs={"x": TensorSpec(np.float32, (None,))},
            outputs={"y": TensorSpec(np.float32, (None,))},
        ),
        "whoami": Signature(
            fn=whoami_fn,
            inputs={"x": TensorSpec(np.float32, (None,))},
            outputs={"pid": TensorSpec(np.int64, (1,))},
            on_host=True, batched=False,
        ),
        "decode_init": Signature(
            fn=init_fn,
            inputs={"session_id": session_spec,
                    "base": TensorSpec(np.int32, ())},
            outputs={"session_id": TensorSpec("DT_STRING", ()),
                     "pid": TensorSpec(np.int64, (1,))},
            on_host=True, batched=False,
        ),
        "decode_step": Signature(
            fn=step_fn,
            inputs={"session_id": session_spec},
            optional_inputs={"step_ordinal": TensorSpec(np.int64, ())},
            outputs={"token": TensorSpec(np.int32, (1,)),
                     "finished": TensorSpec(np.int32, (1,)),
                     "pid": TensorSpec(np.int64, (1,))},
            on_host=True, batched=False,
        ),
        "decode_close": Signature(
            fn=close_fn,
            inputs={"session_id": session_spec},
            outputs={"closed": TensorSpec(np.int32, ())},
            on_host=True, batched=False,
        ),
    }
'''


def write_session_jax_servable(base_path, version: int = 1) -> pathlib.Path:
    """The sessioned fixture servable used by the routing-tier tests
    (tests/integration/test_router.py) and the bench `routed` leg."""
    vdir = pathlib.Path(base_path) / str(version)
    vdir.mkdir(parents=True, exist_ok=True)
    (vdir / "servable.py").write_text(SESSION_JAX_SERVABLE_SRC)
    return vdir


# -- model-server subprocess harness -----------------------------------------
#
# ONE implementation of the boot/parse/teardown choreography for real
# `tpu_model_server` subprocesses, shared by the router integration suite
# and bench's `routed` leg — two drifting copies of the banner regex and
# reader-thread dance would break differently on any server boot change.

SERVER_BANNER_RE = re.compile(r"serving: gRPC on (\d+), REST on (\d+)")

_REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]


class ModelServerProcess:
    """One real server subprocess (CPU-pinned): spawn, parse the serving
    banner for its ephemeral ports, and guarantee teardown."""

    def __init__(self, model_root, monitoring_config, *,
                 model_name: str = "sess", model_platform: str = "jax",
                 drain_grace_s: float = 0.0, extra_args=()):
        import os
        import subprocess
        import sys
        import threading

        env = dict(os.environ, JAX_PLATFORMS="cpu")
        self.proc = subprocess.Popen(
            [sys.executable, "-u", "-m", "min_tfs_client_tpu.server.main",
             "--port=0",
             f"--model_name={model_name}",
             f"--model_base_path={model_root}",
             f"--model_platform={model_platform}",
             f"--monitoring_config_file={monitoring_config}",
             f"--drain_grace_seconds={drain_grace_s}",
             "--file_system_poll_wait_seconds=0", *extra_args],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            cwd=str(_REPO_ROOT), env=env)
        self.lines: list = []
        self._reader = threading.Thread(
            target=self._read, name=f"server-{self.proc.pid}-stdout",
            daemon=True)
        self._reader.start()
        self.grpc_port = None
        self.rest_port = None

    def _read(self) -> None:
        for line in self.proc.stdout:
            self.lines.append(line)

    @property
    def pid(self) -> int:
        return self.proc.pid

    def wait_ready(self, timeout_s: float = 120.0) -> "ModelServerProcess":
        import time

        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            match = next((SERVER_BANNER_RE.search(line)
                          for line in list(self.lines)
                          if SERVER_BANNER_RE.search(line)), None)
            if match:
                self.grpc_port = int(match.group(1))
                self.rest_port = int(match.group(2))
                return self
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"server exited rc={self.proc.returncode} before "
                    f"serving:\n{''.join(self.lines[-40:])}")
            time.sleep(0.05)
        raise AssertionError(
            f"server not serving after {timeout_s}s:\n"
            f"{''.join(self.lines[-40:])}")

    def sigterm(self) -> None:
        import signal

        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)

    def kill(self) -> None:
        import subprocess

        if self.proc.poll() is None:
            self.proc.kill()
        try:
            self.proc.wait(timeout=10)
        except subprocess.TimeoutExpired:  # pragma: no cover
            pass

    def backend_spec(self) -> str:
        return f"127.0.0.1:{self.grpc_port}:{self.rest_port}"


# One banner contract for router subprocesses too (router/main.py): the
# scale-out suite and bench's routed legs both boot real
# `tpu-serving-router` processes and parse this.
ROUTER_BANNER_RE = re.compile(r"routing: gRPC on (\d+), REST on (\d+)")


class RouterProcess:
    """One real `tpu-serving-router` subprocess: spawn with a backend
    list, parse the routing banner for its ephemeral ports, guarantee
    teardown. The N-replica scale-out contract is only provable with
    real separate processes — two in-process RouterServers would share
    metrics/tracing module globals and, more importantly, could share
    state by accident."""

    def __init__(self, backends: str, *, poll_interval_s: float = 0.25,
                 extra_args=()):
        import os
        import subprocess
        import sys
        import threading

        self.proc = subprocess.Popen(
            [sys.executable, "-u", "-m", "min_tfs_client_tpu.router.main",
             "--port=0", "--rest_api_port=0",
             f"--backends={backends}",
             f"--health_poll_interval_s={poll_interval_s}",
             "--probe_timeout_s=2.0", *extra_args],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            cwd=str(_REPO_ROOT), env=dict(os.environ))
        self.lines: list = []
        self._reader = threading.Thread(
            target=self._read, name=f"router-{self.proc.pid}-stdout",
            daemon=True)
        self._reader.start()
        self.grpc_port = None
        self.rest_port = None

    def _read(self) -> None:
        for line in self.proc.stdout:
            self.lines.append(line)

    def wait_ready(self, timeout_s: float = 60.0) -> "RouterProcess":
        import time

        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            match = next((ROUTER_BANNER_RE.search(line)
                          for line in list(self.lines)
                          if ROUTER_BANNER_RE.search(line)), None)
            if match:
                self.grpc_port = int(match.group(1))
                self.rest_port = int(match.group(2))
                return self
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"router exited rc={self.proc.returncode} before "
                    f"routing:\n{''.join(self.lines[-40:])}")
            time.sleep(0.05)
        raise AssertionError(
            f"router not routing after {timeout_s}s:\n"
            f"{''.join(self.lines[-40:])}")

    def snapshot(self) -> dict:
        import json
        import urllib.request

        url = f"http://127.0.0.1:{self.rest_port}/monitoring/router"
        with urllib.request.urlopen(url, timeout=10) as resp:
            return json.loads(resp.read())

    def kill(self) -> None:
        import subprocess

        if self.proc.poll() is None:
            self.proc.kill()
        try:
            self.proc.wait(timeout=10)
        except subprocess.TimeoutExpired:  # pragma: no cover
            pass


def reserve_ports(n: int) -> list:
    """n distinct OS-assigned TCP ports, released for a LATER bind —
    the join-mid-stream choreography needs a backend's ports known to
    the routers' --backends list before the process exists. Small
    inherent race (another process could grab one first); acceptable
    in CI, where the ephemeral range is large and the window short."""
    import socket

    sockets = []
    try:
        for _ in range(n):
            sock = socket.socket()
            sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            sock.bind(("127.0.0.1", 0))
            sockets.append(sock)
        return [s.getsockname()[1] for s in sockets]
    finally:
        for sock in sockets:
            sock.close()


# -- TF2 function-based (object-graph) SavedModels ---------------------------


def _fnode(fdef, name, op, inputs=(), **attrs):
    """Add a NodeDef to a FunctionDef body (same attr conventions as
    _node; function-style input refs 'node:out_name:idx' passed as-is)."""
    node = fdef.node_def.add()
    node.name = name
    node.op = op
    node.input.extend(inputs)
    return _set_attrs(node, attrs)


def _funcdef(library, name, input_args, output_args, ret):
    """FunctionDef skeleton: [(arg, dtype)...] ins/outs + ret map."""
    fdef = library.function.add()
    fdef.signature.name = name
    for arg, dtype in input_args:
        fdef.signature.input_arg.add(name=arg, type=dtype)
    for arg, dtype in output_args:
        fdef.signature.output_arg.add(name=arg, type=dtype)
    for key, value in ret.items():
        fdef.ret[key] = value
    return fdef


def write_tf2_function_model(base_path, version: int = 1):
    """TF2-style object-graph SavedModel, built without TF: VarHandleOp
    resources + nested StatefulPartitionedCall (signature wrapper calling
    the inference function, as real exports do) + ReadVariableOp inside the
    function + un-frozen variables/ checkpoint. Mirrors the export shape
    loader.cc:166-324 / tensorflow_model_server_test.py:570-670 serve.

    y = relu(x @ kernel + bias), x float32 [-1, 4], kernel (4, 3).
    """
    from min_tfs_client_tpu.servables import tensor_bundle

    rng = np.random.default_rng(7)
    kernel = rng.standard_normal((4, 3)).astype(np.float32)
    bias = rng.standard_normal((3,)).astype(np.float32)

    sm = tf_graph_pb2.SavedModel(saved_model_schema_version=1)
    mg = sm.meta_graphs.add()
    mg.meta_info_def.tags.append("serve")
    g = mg.graph_def

    # inner inference function (function-style 3-part tensor refs,
    # realistic output-arg names: value/product/z/activations)
    inner = _funcdef(
        g.library, "__inference_serve_fn",
        [("x", DT.DT_FLOAT), ("k_res", DT.DT_RESOURCE),
         ("b_res", DT.DT_RESOURCE)],
        [("y", DT.DT_FLOAT)],
        {"y": "act:activations:0"})
    _fnode(inner, "read_k", "ReadVariableOp", ["k_res"], dtype=DT.DT_FLOAT)
    _fnode(inner, "read_b", "ReadVariableOp", ["b_res"], dtype=DT.DT_FLOAT)
    _fnode(inner, "mm", "MatMul", ["x", "read_k:value:0"], T=DT.DT_FLOAT)
    _fnode(inner, "add", "AddV2", ["mm:product:0", "read_b:value:0"],
           T=DT.DT_FLOAT)
    _fnode(inner, "act", "Relu", ["add:z:0"], T=DT.DT_FLOAT)

    # signature wrapper calling the inner function (nested call)
    wrapper = _funcdef(
        g.library, "__inference_signature_wrapper",
        [("x", DT.DT_FLOAT), ("k_res", DT.DT_RESOURCE),
         ("b_res", DT.DT_RESOURCE)],
        [("y", DT.DT_FLOAT)],
        {"y": "call:output:0"})
    _fnode(wrapper, "call", "StatefulPartitionedCall",
           ["x", "k_res", "b_res"], func="__inference_serve_fn")

    # top-level graph: placeholder + resource vars + wrapper call
    _node(g, "serving_default_x", "Placeholder", dtype=DT.DT_FLOAT,
          shape=[-1, 4])
    _node(g, "dense_kernel", "VarHandleOp", dtype=DT.DT_FLOAT,
          shared_name="dense/kernel")
    _node(g, "dense_bias", "VarHandleOp", dtype=DT.DT_FLOAT,
          shared_name="dense/bias")
    call = _node(g, "StatefulPartitionedCall", "StatefulPartitionedCall",
                 ["serving_default_x", "dense_kernel", "dense_bias"])
    call.attr["f"].func.name = "__inference_signature_wrapper"
    _node(g, "Identity", "Identity", ["StatefulPartitionedCall:0"],
          T=DT.DT_FLOAT)

    _sig(mg, "serving_default", "tensorflow/serving/predict",
         {"x": ("serving_default_x:0", DT.DT_FLOAT, [-1, 4])},
         {"y": ("Identity:0", DT.DT_FLOAT, [-1, 3])})

    vdir = _write(sm, pathlib.Path(base_path), version)
    tensor_bundle.write_bundle(
        vdir / "variables" / "variables",
        {"dense/kernel/.ATTRIBUTES/VARIABLE_VALUE": kernel,
         "dense/bias/.ATTRIBUTES/VARIABLE_VALUE": bias})
    return vdir, (kernel, bias)


def write_tf2_while_model(base_path, version: int = 1) -> pathlib.Path:
    """StatelessWhile model: doubles x N times (N fed as int32 scalar).
    Exercises control flow -> lax.while_loop on the jit path."""
    sm = tf_graph_pb2.SavedModel(saved_model_schema_version=1)
    mg = sm.meta_graphs.add()
    mg.meta_info_def.tags.append("serve")
    g = mg.graph_def

    cond = _funcdef(
        g.library, "while_cond",
        [("i", DT.DT_INT32), ("n", DT.DT_INT32), ("x", DT.DT_FLOAT)],
        [("cond", DT.DT_BOOL)], {"cond": "less:z:0"})
    _fnode(cond, "less", "Less", ["i", "n"], T=DT.DT_INT32)

    body = _funcdef(
        g.library, "while_body",
        [("i", DT.DT_INT32), ("n", DT.DT_INT32), ("x", DT.DT_FLOAT)],
        [("i_out", DT.DT_INT32), ("n_out", DT.DT_INT32),
         ("x_out", DT.DT_FLOAT)],
        {"i_out": "inc:z:0", "n_out": "n", "x_out": "dbl:z:0"})
    _fnode(body, "one", "Const", dtype=DT.DT_INT32,
           value=ndarray_to_tensor_proto(np.int32(1)))
    _fnode(body, "two", "Const", dtype=DT.DT_FLOAT,
           value=ndarray_to_tensor_proto(np.float32(2.0)))
    _fnode(body, "inc", "AddV2", ["i", "one:output:0"], T=DT.DT_INT32)
    _fnode(body, "dbl", "Mul", ["x", "two:output:0"], T=DT.DT_FLOAT)

    _node(g, "x", "Placeholder", dtype=DT.DT_FLOAT, shape=[-1])
    _node(g, "n", "Placeholder", dtype=DT.DT_INT32, shape=[])
    _node(g, "zero", "Const", dtype=DT.DT_INT32,
          value=ndarray_to_tensor_proto(np.int32(0)))
    loop = _node(g, "loop", "StatelessWhile", ["zero", "n", "x"])
    loop.attr["cond"].func.name = "while_cond"
    loop.attr["body"].func.name = "while_body"
    _node(g, "y", "Identity", ["loop:2"], T=DT.DT_FLOAT)

    _sig(mg, "serving_default", "tensorflow/serving/predict",
         {"x": ("x:0", DT.DT_FLOAT, [-1]),
          "n": ("n:0", DT.DT_INT32, [])},
         {"y": ("y:0", DT.DT_FLOAT, [-1])})
    return _write(sm, pathlib.Path(base_path), version)


def write_tf2_if_model(base_path, version: int = 1) -> pathlib.Path:
    """StatelessIf model: pred ? x * 2 : x + 10."""
    sm = tf_graph_pb2.SavedModel(saved_model_schema_version=1)
    mg = sm.meta_graphs.add()
    mg.meta_info_def.tags.append("serve")
    g = mg.graph_def

    then_b = _funcdef(g.library, "then_branch", [("x", DT.DT_FLOAT)],
                      [("y", DT.DT_FLOAT)], {"y": "dbl:z:0"})
    _fnode(then_b, "two", "Const", dtype=DT.DT_FLOAT,
           value=ndarray_to_tensor_proto(np.float32(2.0)))
    _fnode(then_b, "dbl", "Mul", ["x", "two:output:0"], T=DT.DT_FLOAT)

    else_b = _funcdef(g.library, "else_branch", [("x", DT.DT_FLOAT)],
                      [("y", DT.DT_FLOAT)], {"y": "add:z:0"})
    _fnode(else_b, "ten", "Const", dtype=DT.DT_FLOAT,
           value=ndarray_to_tensor_proto(np.float32(10.0)))
    _fnode(else_b, "add", "AddV2", ["x", "ten:output:0"], T=DT.DT_FLOAT)

    _node(g, "x", "Placeholder", dtype=DT.DT_FLOAT, shape=[-1])
    _node(g, "pred", "Placeholder", dtype=DT.DT_BOOL, shape=[])
    branch = _node(g, "branch", "StatelessIf", ["pred", "x"])
    branch.attr["then_branch"].func.name = "then_branch"
    branch.attr["else_branch"].func.name = "else_branch"
    _node(g, "y", "Identity", ["branch:0"], T=DT.DT_FLOAT)

    _sig(mg, "serving_default", "tensorflow/serving/predict",
         {"x": ("x:0", DT.DT_FLOAT, [-1]),
          "pred": ("pred:0", DT.DT_BOOL, [])},
         {"y": ("y:0", DT.DT_FLOAT, [-1])})
    return _write(sm, pathlib.Path(base_path), version)


def write_imported_transformer_classify(
        base_path, version: int = 1, *, seq: int = 64, d_model: int = 128,
        layers: int = 2, vocab: int = 2048, labels: int = 8,
        seed: int = 7) -> pathlib.Path:
    """A transformer classify SavedModel built with this package's own
    protos (no TF): ParseExample(ids int64 [seq]) -> embedding ->
    `layers` self-attention blocks -> pooled logits -> softmax -> TopK ->
    string-label hash table. The exact shape the host/device partitioner
    serves split (interior jitted, parse + label lookup on host) — used
    by the bench's `imported` leg and the on-device tier, so the
    partitioned path is measurable on real hardware without a TF
    dependency at run time."""
    rng = np.random.default_rng(seed)
    sm = tf_graph_pb2.SavedModel(saved_model_schema_version=1)
    mg = sm.meta_graphs.add()
    mg.meta_info_def.tags.append("serve")
    g = mg.graph_def

    _node(g, "examples", "Placeholder", dtype=DT.DT_STRING, shape=[-1])
    _node(g, "names", "Const", dtype=DT.DT_STRING,
          value=ndarray_to_tensor_proto(np.array([], dtype=object)))
    _node(g, "key_ids", "Const", dtype=DT.DT_STRING,
          value=ndarray_to_tensor_proto(np.asarray(b"ids", dtype=object)))
    _node(g, "default_ids", "Const", dtype=DT.DT_INT64,
          value=ndarray_to_tensor_proto(np.array([], dtype=np.int64)))
    parse = _node(g, "parse", "ParseExample",
                  ["examples", "names", "key_ids", "default_ids"],
                  Nsparse=0, Ndense=1)
    parse.attr["Tdense"].list.type.append(DT.DT_INT64)
    parse.attr["sparse_types"].list.SetInParent()
    parse.attr["dense_shapes"].list.shape.add().dim.add(size=seq)

    def w(name, shape, scale):
        _node(g, name, "Const", dtype=DT.DT_FLOAT,
              value=ndarray_to_tensor_proto(
                  (rng.standard_normal(shape) * scale).astype(np.float32)))
        return name

    _node(g, "emb_tbl", "Const", dtype=DT.DT_FLOAT,
          value=ndarray_to_tensor_proto(
              (rng.standard_normal((vocab, d_model)) * 0.05
               ).astype(np.float32)))
    _node(g, "axis0", "Const", dtype=DT.DT_INT32,
          value=ndarray_to_tensor_proto(np.asarray(0, np.int32)))
    _node(g, "x0", "GatherV2", ["emb_tbl", "parse:0", "axis0"],
          Tparams=DT.DT_FLOAT)
    _node(g, "scale", "Const", dtype=DT.DT_FLOAT,
          value=ndarray_to_tensor_proto(
              np.float32(1.0 / np.sqrt(d_model))))
    x = "x0"
    for i in range(layers):
        _node(g, f"q{i}", "BatchMatMulV2",
              [x, w(f"wq{i}", (d_model, d_model), 0.05)], T=DT.DT_FLOAT)
        _node(g, f"k{i}", "BatchMatMulV2",
              [x, w(f"wk{i}", (d_model, d_model), 0.05)], T=DT.DT_FLOAT)
        _node(g, f"v{i}", "BatchMatMulV2",
              [x, w(f"wv{i}", (d_model, d_model), 0.05)], T=DT.DT_FLOAT)
        _node(g, f"att{i}", "BatchMatMulV2", [f"q{i}", f"k{i}"],
              T=DT.DT_FLOAT, adj_y=True)
        _node(g, f"atts{i}", "Mul", [f"att{i}", "scale"], T=DT.DT_FLOAT)
        _node(g, f"attp{i}", "Softmax", [f"atts{i}"], T=DT.DT_FLOAT)
        _node(g, f"ctx{i}", "BatchMatMulV2", [f"attp{i}", f"v{i}"],
              T=DT.DT_FLOAT)
        _node(g, f"res{i}", "AddV2", [x, f"ctx{i}"], T=DT.DT_FLOAT)
        _node(g, f"ff{i}", "BatchMatMulV2",
              [f"res{i}", w(f"wf{i}", (d_model, d_model), 0.05)],
              T=DT.DT_FLOAT)
        _node(g, f"relu{i}", "Relu", [f"ff{i}"], T=DT.DT_FLOAT)
        _node(g, f"out{i}", "AddV2", [f"res{i}", f"relu{i}"], T=DT.DT_FLOAT)
        x = f"out{i}"
    _node(g, "axis1", "Const", dtype=DT.DT_INT32,
          value=ndarray_to_tensor_proto(np.asarray(1, np.int32)))
    _node(g, "pool", "Mean", [x, "axis1"], T=DT.DT_FLOAT)
    _node(g, "logits", "MatMul",
          ["pool", w("wo", (d_model, labels), 0.1)], T=DT.DT_FLOAT)
    _node(g, "scores_all", "Softmax", ["logits"], T=DT.DT_FLOAT)
    _node(g, "topk", "Const", dtype=DT.DT_INT32,
          value=ndarray_to_tensor_proto(np.asarray(labels, np.int32)))
    _node(g, "ranked", "TopKV2", ["scores_all", "topk"], T=DT.DT_FLOAT)
    _node(g, "ranked64", "Cast", ["ranked:1"],
          SrcT=DT.DT_INT32, DstT=DT.DT_INT64)
    table = _node(g, "label_tbl", "HashTableV2", [])
    table.attr["key_dtype"].type = DT.DT_INT64
    table.attr["value_dtype"].type = DT.DT_STRING
    _node(g, "label_keys", "Const", dtype=DT.DT_INT64,
          value=ndarray_to_tensor_proto(
              np.arange(labels, dtype=np.int64)))
    _node(g, "label_vals", "Const", dtype=DT.DT_STRING,
          value=ndarray_to_tensor_proto(
              np.array([f"class_{i}".encode() for i in range(labels)],
                       dtype=object)))
    _node(g, "tbl_init", "LookupTableImportV2",
          ["label_tbl", "label_keys", "label_vals"])
    _node(g, "unk", "Const", dtype=DT.DT_STRING,
          value=ndarray_to_tensor_proto(np.asarray(b"UNK", dtype=object)))
    _node(g, "classes", "LookupTableFindV2",
          ["label_tbl", "ranked64", "unk"])
    _sig(mg, "serving_default", "tensorflow/serving/classify",
         {"inputs": ("examples:0", DT.DT_STRING, [-1])},
         {"classes": ("classes:0", DT.DT_STRING, [-1, labels]),
          "scores": ("ranked:0", DT.DT_FLOAT, [-1, labels])})
    return _write(sm, pathlib.Path(base_path), version)


def write_imported_two_tower(base_path, version: int = 1, *,
                             width: int = 8, seed: int = 11) -> pathlib.Path:
    """A two-tower SavedModel built with this package's own protos (no
    TF): dense tower A -> int vocab-id lookup (host island) -> dense
    tower B. The shape that exposed single-segment partitioning
    (VERDICT r5 Missing #3: one tower used to stay on numpy) — now both
    towers run as jitted device segments around the host lookup. Predict
    signature: x float [-1, width] -> scores [-1, width] (tower B) and
    tower_a [-1, width] (tower A's activation, fetched mid-graph)."""
    rng = np.random.default_rng(seed)
    sm = tf_graph_pb2.SavedModel(saved_model_schema_version=1)
    mg = sm.meta_graphs.add()
    mg.meta_info_def.tags.append("serve")
    g = mg.graph_def

    _node(g, "x", "Placeholder", dtype=DT.DT_FLOAT, shape=[-1, width])

    def w(name, scale):
        _node(g, name, "Const", dtype=DT.DT_FLOAT,
              value=ndarray_to_tensor_proto(
                  (rng.standard_normal((width, width)) * scale
                   ).astype(np.float32)))
        return name

    _node(g, "h1", "MatMul", ["x", w("wa", 0.4)], T=DT.DT_FLOAT)
    _node(g, "tower_a", "Relu", ["h1"], T=DT.DT_FLOAT)
    _node(g, "axis1", "Const", dtype=DT.DT_INT32,
          value=ndarray_to_tensor_proto(np.asarray(1, np.int32)))
    _node(g, "best", "ArgMax", ["tower_a", "axis1"], T=DT.DT_FLOAT)
    tbl = _node(g, "vocab_tbl", "HashTableV2", [])
    tbl.attr["key_dtype"].type = DT.DT_INT64
    tbl.attr["value_dtype"].type = DT.DT_INT64
    _node(g, "vocab_keys", "Const", dtype=DT.DT_INT64,
          value=ndarray_to_tensor_proto(np.arange(width, dtype=np.int64)))
    _node(g, "vocab_vals", "Const", dtype=DT.DT_INT64,
          value=ndarray_to_tensor_proto(
              ((np.arange(width, dtype=np.int64) * 3 + 1) % width)))
    _node(g, "vocab_init", "LookupTableImportV2",
          ["vocab_tbl", "vocab_keys", "vocab_vals"])
    _node(g, "vocab_dflt", "Const", dtype=DT.DT_INT64,
          value=ndarray_to_tensor_proto(np.asarray(0, np.int64)))
    _node(g, "mapped", "LookupTableFindV2",
          ["vocab_tbl", "best", "vocab_dflt"])
    _node(g, "mf", "Cast", ["mapped"], SrcT=DT.DT_INT64, DstT=DT.DT_FLOAT)
    _node(g, "col", "ExpandDims", ["mf", "axis1"], T=DT.DT_FLOAT)
    _node(g, "mix", "Mul", ["tower_a", "col"], T=DT.DT_FLOAT)
    _node(g, "h2", "MatMul", ["mix", w("wb", 0.2)], T=DT.DT_FLOAT)
    _node(g, "scores", "Softmax", ["h2"], T=DT.DT_FLOAT)
    _sig(mg, "serving_default", "tensorflow/serving/predict",
         {"x": ("x:0", DT.DT_FLOAT, [-1, width])},
         {"scores": ("scores:0", DT.DT_FLOAT, [-1, width]),
          "tower_a": ("tower_a:0", DT.DT_FLOAT, [-1, width])})
    return _write(sm, pathlib.Path(base_path), version)


class DelayedHandle:
    """An ExecutionHandle whose result becomes available a fixed
    wall-clock delay after dispatch — the simulated-latency device: an
    in-flight execution + D2H transfer takes `latency` seconds of REAL
    time regardless of what the host does meanwhile, so overlapped
    dispatches hide it while a serial dispatch->materialize loop pays it
    per batch. result() blocks only for the remaining time."""

    __slots__ = ("_handle", "_ready_at")

    def __init__(self, handle, ready_at: float):
        self._handle = handle
        self._ready_at = ready_at

    def result(self):
        _sleep_until(self._ready_at)
        return self._handle.result()


def _sleep_until(deadline: float) -> None:
    """Wait until perf_counter() >= deadline with sub-ms precision:
    time.sleep alone overshoots by ~1 ms per wakeup, which at m chunks x
    k segments of simulated waits would swamp the very overlap being
    measured. Sleep short, then spin the last millisecond."""
    import time

    remaining = deadline - time.perf_counter()
    if remaining > 0.002:
        time.sleep(remaining - 0.001)
    # Fine-grained polls, never a GIL-hogging spin: a tight Python loop
    # would hold the GIL for the full switch interval (~5 ms) and stall
    # every other serving thread, corrupting the very measurement.
    while time.perf_counter() < deadline:
        time.sleep(0.0002)


def simulate_device_latency(signature, latency_s: float):
    """Shim `signature.dispatch` so every launched execution completes
    `latency_s` of wall-clock after dispatch (a stand-in for a
    high-latency host-device link). Install BEFORE wrapping with
    BatchedSignatureRunner — the runner captures the dispatch seam at
    construction. sync `run` routes through the same seam, so the
    window=1 path pays the full latency serially."""
    import time

    inner = signature.dispatch

    def dispatch(inputs, output_filter=()):
        handle = inner(inputs, output_filter)
        return DelayedHandle(handle, time.perf_counter() + latency_s)

    signature.dispatch = dispatch
    return signature


def simulate_interior_latency(partition, latency_s: float):
    """Same idea for a GraphPartition: shim `_dispatch_interior` (the
    one seam both the serial and microbatch-pipelined paths launch
    jitted segments through) so each interior's D2H completes
    `latency_s` after its dispatch."""
    import time

    inner = partition._dispatch_interior

    def dispatch(fn, padded):
        handle = inner(fn, padded)
        return DelayedHandle(handle, time.perf_counter() + latency_s)

    partition._dispatch_interior = dispatch
    return partition


def simulate_host_latency(partition, per_row_s: float):
    """Give a partition's host islands (segment preludes + post) a
    wall-clock cost proportional to the rows they process — the
    CPU-CI stand-in for real imported-graph host stages (Example
    parsing, string ops, table lookups on production-sized batches),
    whose overlap against in-flight device segments is exactly what the
    microbatch pipeline converts into throughput."""
    import time

    def slow(fn):
        if fn is None:
            return None

        def wrapped(feeds, np_mod):
            rows = max((np.asarray(v).shape[0] for v in feeds
                        if np.ndim(v)), default=1)
            _sleep_until(time.perf_counter() + per_row_s * rows)
            return fn(feeds, np_mod)

        return wrapped

    for seg in partition.segments:
        seg.host_fn = slow(seg.host_fn)
    partition.post = slow(partition.post)
    return partition


def harness_threads() -> frozenset:
    """The sampling profiler's labels for the threads that are here
    already and were started without `threading` (pytest-xdist's execnet
    starts its own with `_thread.start_new_thread`): they are in
    `sys._current_frames()` and not in `threading.enumerate()`, so the
    sampler cannot name them. Call it before the process under test
    starts anything. They are the test runner's, not the serving
    process's: the profiler suites take their attribution bars over the
    threads the code under test answers for (`own_attribution`)."""
    import sys
    import threading

    registered = {t.ident for t in threading.enumerate()}
    return frozenset(f"unnamed-{ident}" for ident in sys._current_frames()
                     if ident not in registered)


def own_attribution(threads: dict, harness: frozenset) -> tuple:
    """(samples, of them on a named thread, of them in no known
    subsystem) over a profile payload's `threads` but the runner's own."""
    own = {label: info for label, info in threads.items()
           if label not in harness}
    samples = sum(info["samples"] for info in own.values())
    named = sum(info["samples"] for label, info in own.items()
                if not label.startswith("unnamed-"))
    other = sum(info["samples"] for info in own.values()
                if info["subsystem"] == "other")
    return samples, named, other


def until(predicate, timeout_s: float = 10.0) -> None:
    """Poll for what a background thread does by itself (the decode
    pool's tick loop runs ahead of the requests, on its own thread)."""
    import time

    deadline = time.monotonic() + timeout_s
    while not predicate():
        assert time.monotonic() < deadline, "timed out"
        time.sleep(0.002)


def tick_loop_threads() -> list:
    """The live loop threads of decode_sessions.TickBatcher: none once
    nothing is due."""
    import threading

    return [t for t in threading.enumerate() if t.name == "decode-tick-loop"]
