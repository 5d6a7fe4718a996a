"""In-process serving: the tpu:// transport endpoint.

A LocalServer wires Handlers directly to the InProcessChannel — a request
never serializes, never crosses a thread it didn't need, and executes on the
TPU in the caller's process. boot_local_server() is what
TensorServingClient("tpu://<base_path>") lazily invokes.
"""

from __future__ import annotations

import pathlib

from min_tfs_client_tpu.client.inprocess import (
    InProcessRpcError,
    LocalInvoker,
    register_server,
    unregister_server,
)
from min_tfs_client_tpu.core.server_core import ServerCore, single_model_config
from min_tfs_client_tpu.server.handlers import Handlers
from min_tfs_client_tpu.utils import compile_cache
from min_tfs_client_tpu.utils.status import error_from_exception, to_grpc_code


class LocalServer(LocalInvoker):
    """Dispatches gRPC method paths onto Handlers, in-process."""

    def __init__(self, core: ServerCore, *, response_tensors_as_content=True):
        self.core = core
        handlers = Handlers(
            core, response_tensors_as_content=response_tensors_as_content)
        self._routes = {
            "/tensorflow.serving.PredictionService/Predict": handlers.predict,
            "/tensorflow.serving.PredictionService/Classify": handlers.classify,
            "/tensorflow.serving.PredictionService/Regress": handlers.regress,
            "/tensorflow.serving.PredictionService/MultiInference":
                handlers.multi_inference,
            "/tensorflow.serving.PredictionService/GetModelMetadata":
                handlers.get_model_metadata,
            "/tensorflow.serving.SessionService/SessionRun":
                handlers.session_run,
            "/tensorflow.serving.ModelService/GetModelStatus":
                handlers.get_model_status,
            "/tensorflow.serving.ModelService/HandleReloadConfigRequest":
                handlers.handle_reload_config,
        }

    def invoke(self, method: str, request, timeout=None):
        import grpc

        handler = self._routes.get(method)
        if handler is None:
            raise InProcessRpcError(grpc.StatusCode.UNIMPLEMENTED, method)
        try:
            return handler(request)
        except InProcessRpcError:
            raise
        except Exception as exc:  # noqa: BLE001 - mapped onto the channel
            err = error_from_exception(exc)
            raise InProcessRpcError(to_grpc_code(err.code), err.message)

    def stop(self) -> None:
        self.core.stop()


def boot_local_server(base_path: str) -> LocalServer:
    """tpu://<model_base_path> -> serve the latest version of that model
    in-process. The model name is the directory basename; platform is "jax"
    when version dirs contain servable.py, else "tensorflow"."""
    path = pathlib.Path(base_path)
    name = path.name
    platform = "tensorflow"
    for child in sorted(path.iterdir()) if path.is_dir() else []:
        if child.is_dir() and child.name.isdigit():
            if (child / "servable.py").is_file():
                platform = "jax"
            break
    compile_cache.configure()
    core = ServerCore(
        single_model_config(name, str(path), platform=platform),
        file_system_poll_wait_seconds=0,  # poll once; in-process is static
    )
    server = LocalServer(core)
    register_server(base_path, server)
    return server


def shutdown_local_server(base_path: str) -> bool:
    """Stop and unregister the in-process server for ``base_path``.

    Lazily-booted tpu:// servers are otherwise process-lifetime: the
    registry pins the core, whose manager holds live servable-load/unload
    worker threads. Anything that boots one for a bounded scope (tests,
    one-shot tools) owns its teardown and must call this."""
    server = unregister_server(base_path)
    if server is None:
        return False
    server.stop()
    return True
