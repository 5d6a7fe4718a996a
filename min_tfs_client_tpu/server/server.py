"""Server assembly: options -> ServerCore -> gRPC services -> serving.

Parity with model_servers/server.{h,cc} (BuildAndStart): synthesizes a
single-model config from --model_name/--model_base_path (server.cc:83-96),
parses text-format proto config files (ParseProtoTextFile, server.cc:59-73),
builds ServerCore, registers Model/Prediction services on a grpc server with
optional SSL, and optionally re-polls the model config file
(PollFilesystemAndReloadConfig, server.cc:164-179).
"""

from __future__ import annotations

import asyncio
import logging
import os
import pathlib
import threading
import time
from concurrent import futures
from dataclasses import dataclass, field
from typing import Optional

import grpc
from google.protobuf import text_format

from min_tfs_client_tpu.core.server_core import (
    ServerCore,
    single_model_config,
)
from min_tfs_client_tpu.protos import grpc_service as gs
from min_tfs_client_tpu.protos import tfs_config_pb2
from min_tfs_client_tpu.server.grpc_services import (
    ModelServiceImpl,
    PredictionServiceImpl,
    SessionServiceImpl,
)
from min_tfs_client_tpu.server.handlers import Handlers
from min_tfs_client_tpu.utils import aio_loop
from min_tfs_client_tpu.utils.status import ServingError


@dataclass
class ServerOptions:
    """Mirrors the main.cc flag surface (main.cc:59-195) where applicable."""

    grpc_port: int = 8500
    rest_api_port: int = 0
    # Reference main.cc:70-75: worker-thread count and idle timeout of the
    # HTTP front-end. Consumed by the native epoll server; the Python
    # fallback backend is thread-per-connection and ignores them.
    rest_api_num_threads: int = 4
    rest_api_timeout_in_ms: int = 30000
    rest_api_impl: str = "auto"  # auto | native | python
    model_name: str = "default"
    model_base_path: str = ""
    model_platform: str = "tensorflow"
    model_config_file: str = ""
    model_config_file_poll_wait_seconds: float = 0
    file_system_poll_wait_seconds: float = 1.0
    enable_batching: bool = False
    batching_parameters_file: str = ""
    # In-flight execution window per batching queue: how many batches may
    # be dispatched (device work launched, D2H copies issued) with results
    # not yet materialized. 1 = the exact pre-window serial path; >1
    # overlaps batch k+1's dispatch with batch k's outstanding transfers
    # and sets the microbatch pipeline depth of multi-segment partitioned
    # imports (docs/MIGRATING.md "Pipelined in-flight execution").
    max_in_flight_batches: int = 1
    # Paged decode KV cache (docs/MIGRATING.md "Paged KV cache"):
    # block_size 0 = the pre-paging dense slot pool, byte-for-byte.
    kv_block_size: int = 0
    kv_num_blocks: int = 0
    kv_evict_policy: str = "swap"
    kv_prefill_chunk: int = 0
    monitoring_config_file: str = ""
    ssl_config_file: str = ""
    max_num_load_retries: int = 5
    load_retry_interval_micros: int = 60 * 1000 * 1000
    num_load_threads: int = 2
    num_unload_threads: int = 2
    grpc_max_threads: int = 16
    enable_model_warmup: bool = True
    # ModelWarmupOptions analogues (session_bundle_config.proto): replay
    # count per record, and whether to synthesize compile-priming requests
    # when a model ships no warmup file.
    warmup_iterations: int = 1
    synthesize_warmup: bool = False
    response_tensors_as_content: bool = False
    # Serving mesh: "data:-1" or "data:4,model:2" — batched device
    # signatures execute data-parallel (x tensor-parallel for exports with
    # a sharding config) over this device mesh. "" = single device. The
    # reference has no in-server parallelism at all (SURVEY.md §2.11).
    mesh_axes: str = ""
    # On-demand profiling (reference registers a profiler service on the
    # main server, server.cc:324,339); 0 disables.
    profiler_port: int = 0
    # Additional UNIX-domain listening socket (server.cc:330-336); "" off.
    grpc_socket_path: str = ""
    # "key=value,key=value" extra gRPC channel args (main.cc
    # grpc_channel_arguments flag).
    grpc_channel_arguments: str = ""
    # Comma-separated MetaGraphDef tags to select at SavedModel load
    # (main.cc saved_model_tags; default "serve").
    saved_model_tags: str = ""
    # Text-format PlatformConfigMap file (main.cc platform_config_file).
    # Mutually exclusive with enable_batching per the reference; entries
    # carrying a tpu.serving.TpuServableConfig Any override the per-platform
    # config assembled from the flags above.
    platform_config_file: str = ""
    # Labels may normally only point at AVAILABLE versions
    # (server_core.cc UpdateModelVersionLabelMap; main.cc flag).
    allow_version_labels_for_unavailable_models: bool = False
    # Serve <version>/model.tflite through the TFLite importer instead of
    # the SavedModel GraphDef (main.cc use_tflite_model).
    use_tflite_model: bool = False
    # Session threading knobs (main.cc:135-152). The reference sizes the
    # TF Session's Eigen pools with these; here within-op parallelism is
    # owned by XLA (SURVEY.md §2.11 "Within-op parallelism"), so
    # intra_op is accepted-and-inert, while inter_op (concurrently
    # executing sessions) maps to the real analogue — the gRPC executor
    # pool that runs signature executions — by capping grpc_max_threads.
    # session_parallelism fills in for whichever of the two is unset
    # (bundle_factory_util GetSessionOptions semantics). All three are
    # ignored when platform_config_file is set, like the reference.
    tensorflow_session_parallelism: int = 0
    tensorflow_intra_op_parallelism: int = 0
    tensorflow_inter_op_parallelism: int = 0
    # N/A on TPU: there is no GPU memory pool to fraction. Accepted for
    # CLI compatibility; a non-zero value logs a warning and does nothing
    # (main.cc per_process_gpu_memory_fraction).
    per_process_gpu_memory_fraction: float = 0.0
    # Drop the OS page cache for model files once the initial loads
    # finish (main.cc flush_filesystem_caches, default true there too):
    # params already live in HBM/host arrays, the file bytes are dead
    # weight.
    flush_filesystem_caches: bool = True
    # When true (the default — the reference checks unconditionally,
    # classifier.cc:296-312, regressor.cc:231), Classify/Regress verify
    # the signature's method_name matches the API called; false relaxes
    # it so any signature with Example feature specs serves either API.
    enable_signature_method_name_check: bool = True
    # -- health plane (observability/; docs/OBSERVABILITY.md) ------------
    # Default SLO objective: latency_objective at latency_quantile (e.g.
    # p99 <= 1000ms) and the allowed error fraction, computed over a
    # rolling window. Burn rate 1.0 = consuming exactly the budget.
    slo_latency_objective_ms: float = 1000.0
    slo_latency_quantile: float = 0.99
    slo_error_budget: float = 0.01
    slo_window_seconds: float = 60.0
    # Readiness sheds (readyz 503, grpc NOT_SERVING, ready gauge 0) when
    # the max burn rate reaches this; 0 disables shedding.
    slo_shed_burn_rate: float = 0.0
    # Relative routing capacity advertised in the readyz payload
    # (`"weight"`): a router's weighted rendezvous ring gives this
    # replica ~weight/sum(weights) of new placements. 1.0 = homogeneous.
    serving_weight: float = 1.0
    # Flight-recorder dump directory ("" = TPU_SERVING_FLIGHT_DIR env or
    # the system tempdir).
    flight_recorder_dir: str = ""
    # Capacity of the request-trace ring served at /monitoring/traces
    # (observability/tracing.py); 0 = keep the TPU_SERVING_TRACE_RING
    # env override or the 256 default.
    trace_ring_size: int = 0
    # Graceful drain (docs/ROUTING.md "Drain semantics"): on stop()/
    # SIGTERM the health plane flips NOT_SERVING immediately, then the
    # server keeps serving for up to this many seconds while live decode
    # sessions finish — their KV state is pinned to this process, so a
    # router cannot move them; it can only stop sending NEW sessions.
    # 0 = flip and stop without waiting for sessions (old behavior).
    drain_grace_seconds: float = 0.0
    # Seeded JSON fault plan (a path, or inline JSON) arming the
    # robustness/faults.py injection points in THIS process; "" = also
    # honor TPU_SERVING_FAULT_PLAN, else disarmed (docs/ROBUSTNESS.md).
    fault_plan: str = ""
    # Cost-attribution wide-event log (observability/costs.py;
    # docs/OBSERVABILITY.md "Cost attribution"): directory for the
    # schema-versioned servecost JSONL ("" = no file log — the
    # /monitoring/costs aggregates still run), and the deterministic
    # per-trace sampling fraction (0.0 writes nothing, 1.0 everything).
    cost_log_dir: str = ""
    cost_log_sample: float = 1.0
    # Watchdog (observability/watchdog.py; docs/OBSERVABILITY.md
    # "Alerting & trend gating"): streaming anomaly detectors over the
    # observability planes, on their own ticker thread. Default ON —
    # sampling is a handful of snapshot reads per interval, never on a
    # request thread (MIGRATING.md notes the new default-on flag).
    watchdog: bool = True
    watchdog_interval_s: float = 5.0
    watchdog_ring_size: int = 256
    # Sampling profiler (observability/profiling.py; docs/OBSERVABILITY.md
    # "Profiling plane"): continuous per-thread/per-stage CPU attribution
    # at /monitoring/profile. Default ON at a deliberately low rate —
    # one sys._current_frames() walk per tick on the sampler's own
    # thread, never on a request thread (MIGRATING.md notes the
    # default-on flag). 0 disables the ticker (on-demand ?seconds=
    # capture still works).
    profile_sampler_hz: float = 11.0
    # Destination for ?device=1 programmatic jax.profiler.trace captures
    # (XPlane dumps). Empty = device capture answers 400.
    profile_dir: str = ""

    def effective_inter_op_parallelism(self) -> int:
        """<= 0 = auto (leave grpc_max_threads alone; TF spells auto as
        0 and some tooling as -1)."""
        if self.platform_config_file:
            return 0
        value = (self.tensorflow_inter_op_parallelism
                 or self.tensorflow_session_parallelism)
        return max(0, value)


def _parse_channel_arguments(spec: str) -> list[tuple[str, object]]:
    """"grpc.max_send_message_length=4194304,..." -> grpc options list,
    ints coerced (the main.cc grpc_channel_arguments format).

    Serving tensors routinely exceed gRPC's 4 MB default, so the server
    is unlimited by default (reference parity: server.cc:340
    SetMaxMessageSize(kint32max)); explicit grpc_channel_arguments win.
    """
    out: list[tuple[str, object]] = []
    for part in (spec or "").split(","):
        part = part.strip()
        if not part:
            continue
        key, sep, value = part.partition("=")
        if not sep:
            raise ServingError.invalid_argument(
                f"malformed gRPC channel argument {part!r} (want key=value)")
        out.append((key, int(value) if value.lstrip("-").isdigit() else value))
    user_keys = {key for key, _ in out}
    defaults: list[tuple[str, object]] = [
        ("grpc.max_send_message_length", -1),
        ("grpc.max_receive_message_length", -1),
    ]
    return [d for d in defaults if d[0] not in user_keys] + out


def _flush_model_file_caches(config) -> None:
    """Advise the OS to drop page cache for the loaded model files
    (main.cc flush_filesystem_caches): the weights already live as device
    /host arrays, so the cached file bytes only crowd out memory.
    Best-effort — unsupported platforms and racing file removals are
    fine to ignore."""
    for mc in config.model_config_list.config:
        base = pathlib.Path(mc.base_path)
        try:
            files = [f for f in base.rglob("*") if f.is_file()]
        except OSError:
            continue
        for f in files:
            try:
                with open(f, "rb") as fh:
                    os.posix_fadvise(fh.fileno(), 0, 0,
                                     os.POSIX_FADV_DONTNEED)
            except AttributeError:
                return  # no fadvise on this platform: nothing to do
            except OSError:
                continue  # racing removal / unreadable file: skip it


def _parse_text_proto(path: str, proto_cls):
    msg = proto_cls()
    with open(path, "r") as f:
        text_format.Parse(f.read(), msg)
    return msg


class Server:
    def __init__(self, options: ServerOptions):
        self.options = options
        self.core: Optional[ServerCore] = None
        self._grpc_front: Optional[_GrpcFront] = None
        self._rest_server = None
        self._config_poll_stop = threading.Event()
        self._config_poll_thread: Optional[threading.Thread] = None

    # -- assembly ------------------------------------------------------------

    def build_and_start(self) -> "Server":
        opts = self.options
        if opts.model_config_file:
            config = _parse_text_proto(
                opts.model_config_file, tfs_config_pb2.ModelServerConfig)
        elif opts.model_base_path:
            config = single_model_config(
                opts.model_name, opts.model_base_path,
                platform=opts.model_platform)
        else:
            raise ServingError.invalid_argument(
                "Both server_model_config_file and model_base_path are empty!")

        batching = None
        if opts.enable_batching:
            if opts.batching_parameters_file:
                batching = _parse_text_proto(
                    opts.batching_parameters_file,
                    tfs_config_pb2.BatchingParameters)
            else:
                # Reference behavior: the flag alone enables batching with
                # default parameters (server.cc:208-273).
                batching = tfs_config_pb2.BatchingParameters()

        # Health-plane configuration BEFORE the core builds: load events
        # and any load-time compiles must already land in the recorder,
        # and the SLO objectives must be set before the first request.
        from min_tfs_client_tpu.observability import flight_recorder
        from min_tfs_client_tpu.observability.slo import SLOConfig, configure

        configure(default=SLOConfig(
            latency_objective_ms=opts.slo_latency_objective_ms,
            latency_quantile=opts.slo_latency_quantile,
            error_budget=opts.slo_error_budget,
            window_s=opts.slo_window_seconds,
            shed_burn_rate=opts.slo_shed_burn_rate,
        ))
        from min_tfs_client_tpu.observability import health

        health.set_serving_weight(opts.serving_weight)
        # Cost attribution: the SLO window also paces the cost windows,
        # and the knob context stamped into every servecost log header
        # is what item 4's autotuner trains against — the dataset must
        # say WHICH configuration produced these costs.
        from min_tfs_client_tpu.observability import costs

        batching_context = None
        if batching is not None:
            batching_context = {
                "max_batch_size": batching.max_batch_size.value or 32,
                "allowed_batch_sizes": list(batching.allowed_batch_sizes),
            }
        costs.configure(
            window_s=opts.slo_window_seconds,
            # "" must DISABLE (CostLog maps empty to no-dir), not "leave
            # unchanged": an earlier in-process server's armed log must
            # never keep collecting this server's requests under the old
            # header's knob context.
            log_dir=opts.cost_log_dir,
            sample=opts.cost_log_sample,
            context={
                "model_name": opts.model_name,
                "enable_batching": bool(opts.enable_batching),
                "batching": batching_context,
                "max_in_flight_batches": opts.max_in_flight_batches,
                "kv_block_size": opts.kv_block_size,
                "kv_num_blocks": opts.kv_num_blocks,
                "kv_evict_policy": opts.kv_evict_policy,
                "kv_prefill_chunk": opts.kv_prefill_chunk,
                "mesh_axes": opts.mesh_axes,
            })
        flight_recorder.configure(opts.flight_recorder_dir or None)
        flight_recorder.install_signal_handler()
        from min_tfs_client_tpu.observability import runtime

        runtime.watch_gc()
        # Watchdog detectors configure before the core builds (so the
        # compile-storm baseline starts at the warmup total, below) but
        # the ticker starts only after the initial loads finish.
        from min_tfs_client_tpu.observability import watchdog

        if opts.watchdog:
            watchdog.configure(interval_s=opts.watchdog_interval_s,
                               ring_size=opts.watchdog_ring_size)
        if opts.trace_ring_size:
            from min_tfs_client_tpu.observability import tracing

            tracing.configure_ring(opts.trace_ring_size)
        # The sampler starts BEFORE the core builds so the load/warmup
        # phase is profiled too (compile-heavy boots are exactly when
        # "which code" matters); stop() joins it.
        from min_tfs_client_tpu.observability import profiling

        profiling.configure(hz=opts.profile_sampler_hz,
                            profile_dir=opts.profile_dir)
        if opts.profile_sampler_hz > 0:
            profiling.start()
        # Fault injection arms BEFORE the core builds, so load-path
        # points fire too; a malformed plan fails the boot loudly.
        from min_tfs_client_tpu.robustness import faults

        if opts.fault_plan:
            faults.arm(opts.fault_plan)
        else:
            faults.arm_from_env()

        # servelint: thread-ok published exactly once, BEFORE the
        # config-poll thread spawns below; the poll loop only reads it
        self.core = ServerCore(
            config,
            file_system_poll_wait_seconds=opts.file_system_poll_wait_seconds,
            max_load_retries=opts.max_num_load_retries,
            load_retry_interval_s=opts.load_retry_interval_micros / 1e6,
            num_load_threads=opts.num_load_threads,
            num_unload_threads=opts.num_unload_threads,
            platform_configs=_platform_configs(opts, batching),
            allow_version_labels_for_unavailable_models=(
                opts.allow_version_labels_for_unavailable_models),
        )

        if opts.flush_filesystem_caches:
            # Initial loads finished inside the ServerCore constructor
            # (ConnectAdaptersToManagerAndAwaitModelLoads parity), so the
            # file bytes are now dead weight.
            _flush_model_file_caches(config)
        if opts.per_process_gpu_memory_fraction:
            logging.getLogger(__name__).warning(
                "per_process_gpu_memory_fraction=%s has no effect: TPU "
                "HBM is gated by the resource tracker, not a GPU pool",
                opts.per_process_gpu_memory_fraction)

        handlers = Handlers(
            self.core,
            response_tensors_as_content=opts.response_tensors_as_content,
            signature_method_name_check=(
                opts.enable_signature_method_name_check))
        inter_op = opts.effective_inter_op_parallelism()
        grpc_threads = (min(opts.grpc_max_threads, inter_op) if inter_op
                        else opts.grpc_max_threads)
        self._grpc_front = _GrpcFront(self, handlers, grpc_threads)
        self.grpc_port = self._grpc_front.start()

        if opts.rest_api_port or opts.monitoring_config_file:
            from min_tfs_client_tpu.server.native_http import (
                NativeRestServer,
                start_best_rest_server,
            )

            monitoring = None
            if opts.monitoring_config_file:
                monitoring = _parse_text_proto(
                    opts.monitoring_config_file, tfs_config_pb2.MonitoringConfig)
            self._rest_server, self.rest_port = start_best_rest_server(
                handlers, opts.rest_api_port, monitoring,
                num_threads=opts.rest_api_num_threads,
                timeout_ms=opts.rest_api_timeout_in_ms,
                impl=opts.rest_api_impl)
            self.rest_backend = ("native" if isinstance(
                self._rest_server, NativeRestServer) else "python")

        if opts.profiler_port:
            from min_tfs_client_tpu.server.profiler import (
                start_profiler_server,
            )

            if not start_profiler_server(opts.profiler_port):
                logging.getLogger("min_tfs_client_tpu").warning(
                    "profiler server failed to start on port %d; trace "
                    "capture will be unavailable", opts.profiler_port)

        if opts.model_config_file and opts.model_config_file_poll_wait_seconds > 0:
            # Seed poll dedup with the config ServerCore ACTUALLY applied —
            # re-reading the file here would silently swallow an edit made
            # during model load/warmup.
            self._applied_config_serialized = config.SerializeToString(
                deterministic=True)
            self._config_poll_thread = threading.Thread(
                target=self._poll_config_file, name="config-file-poll",
                daemon=True)
            self._config_poll_thread.start()
        if opts.watchdog:
            # After the initial loads: warmup compiles are in the
            # ledger, so the storm detector's first delta baseline
            # excludes them.
            from min_tfs_client_tpu.observability import watchdog

            watchdog.start()
        return self

    def _bind(self, server: "grpc.aio.Server", port: int) -> int:
        opts = self.options
        if opts.ssl_config_file:
            ssl = _parse_text_proto(opts.ssl_config_file,
                                    tfs_config_pb2.SSLConfig)
            creds = grpc.ssl_server_credentials(
                [(ssl.server_key.encode(), ssl.server_cert.encode())],
                root_certificates=ssl.custom_ca.encode() or None,
                require_client_auth=ssl.client_verify,
            )
            return server.add_secure_port(f"0.0.0.0:{port}", creds)
        return server.add_insecure_port(f"0.0.0.0:{port}")

    def _poll_config_file(self) -> None:
        interval = self.options.model_config_file_poll_wait_seconds
        last_applied = getattr(self, "_applied_config_serialized", None)
        while not self._config_poll_stop.wait(interval):
            try:
                config = _parse_text_proto(
                    self.options.model_config_file,
                    tfs_config_pb2.ModelServerConfig)
                serialized = config.SerializeToString(deterministic=True)
                if serialized == last_applied:
                    continue  # unchanged: no reload churn, no collector swap
                self.core.reload_config(config)
                last_applied = serialized
            except Exception:  # pragma: no cover - poll must survive bad files
                import traceback

                traceback.print_exc()

    # -- lifecycle -----------------------------------------------------------

    def wait_for_termination(self) -> None:
        # servelint: blocks the main thread parks here for the process
        # lifetime, as in grpc's own wait_for_termination; stop() ends it
        self._grpc_front.wait()

    def stop(self, grace: float = 5.0,
             drain_grace: Optional[float] = None) -> None:
        # Drain contract (docs/ROUTING.md): flip the health plane to
        # NOT_SERVING FIRST — before any in-flight work is waited out —
        # so routers polling readyz/grpc.health stop sending new traffic
        # during the grace window instead of discovering the corpse.
        from min_tfs_client_tpu.observability import health

        if self.core is not None:
            health.mark_draining(self.core)
        self._config_poll_stop.set()
        from min_tfs_client_tpu.observability import profiling, watchdog

        watchdog.stop()
        profiling.stop()
        dg = (self.options.drain_grace_seconds if drain_grace is None
              else drain_grace)
        if dg > 0:
            self._await_session_drain(dg)
        if self._grpc_front is not None:
            self._grpc_front.stop(grace)
        if self._rest_server is not None:
            self._rest_server.shutdown()
        if self.core is not None:
            self.core.stop()

    def _await_session_drain(self, drain_grace: float) -> None:
        """Keep the full serving surface up until every live decode
        session closes (their HBM state cannot move to another replica)
        or the drain grace expires. Routed fleets stop sending new
        sessions the moment the health plane flipped above; in-flight
        sessions keep stepping against this process until they finish.

        Reads the process-global decode_session_count gauge: with more
        than one Server in a process (tests) another server's sessions
        extend this wait — bounded by drain_grace either way."""
        from min_tfs_client_tpu.server import metrics

        deadline = time.monotonic() + drain_grace
        while time.monotonic() < deadline:
            if metrics.gauge_total(metrics.decode_session_count) <= 0:
                return
            time.sleep(0.05)
        logging.getLogger(__name__).warning(
            "drain grace %.1fs expired with %d decode session(s) still "
            "live; proceeding with shutdown", drain_grace,
            int(metrics.gauge_total(metrics.decode_session_count)))


class _GrpcFront:
    """The gRPC front end of one Server: a `grpc.aio` server on the
    process's one event loop (utils/aio_loop.py; any number of Servers,
    and a router's data plane, share it). `Predict` is a coroutine that
    answers on the loop thread where the request needs no waiting
    (server/grpc_services.py); everything else registered here is a
    synchronous servicer, which the aio server runs on `pool`: the
    `--grpc_max_threads` workers that blocking requests wait on."""

    def __init__(self, server: "Server", handlers: Handlers, threads: int):
        self._server = server
        self._handlers = handlers
        self._pool = futures.ThreadPoolExecutor(max_workers=threads)
        self._started = threading.Event()
        self._boot_error: Optional[BaseException] = None
        self._port = 0
        self._grace = 0.0      # the loop thread's, like _stopping
        self._stopping = None  # asyncio.Event, made on the loop
        self._served = None    # Future of _serve, from aio_loop.submit

    def start(self) -> int:
        """Bind and serve; returns the bound TCP port. A bind that fails
        raises here, in the caller."""
        self._served = aio_loop.submit(self._serve())
        # Timed + loop-on-predicate (servelint DL003): _serve sets the
        # event on every path.
        while not self._started.wait(timeout=1.0):
            pass
        if self._boot_error is not None:
            self._pool.shutdown(wait=False)
            raise self._boot_error
        return self._port

    def _build(self) -> "grpc.aio.Server":
        from min_tfs_client_tpu.server.grpc_services import (
            health_service_handler,
        )
        from min_tfs_client_tpu.server.profiler import ProfilerServiceImpl

        opts, handlers = self._server.options, self._handlers
        server = grpc.aio.server(
            migration_thread_pool=self._pool,
            options=_parse_channel_arguments(opts.grpc_channel_arguments))
        gs.add_PredictionServiceServicer_to_server(
            PredictionServiceImpl(handlers, self._pool), server)
        gs.add_ModelServiceServicer_to_server(
            ModelServiceImpl(handlers), server)
        gs.add_SessionServiceServicer_to_server(
            SessionServiceImpl(handlers), server)
        # tensorflow.ProfilerService on the MAIN port (server.cc:324,339).
        gs.add_ProfilerServiceServicer_to_server(
            ProfilerServiceImpl(), server)
        # grpc.health.v1.Health on the MAIN port — readiness for standard
        # probe tooling (observability/health.py).
        server.add_generic_rpc_handlers((health_service_handler(),))
        # servelint: thread-ok written before _started.set(); start()
        # reads only after wait() — Event handoff
        self._port = self._server._bind(server, opts.grpc_port)
        if opts.grpc_socket_path:
            if not server.add_insecure_port(
                    f"unix:{opts.grpc_socket_path}"):
                raise ServingError.unavailable(
                    f"could not bind UNIX socket {opts.grpc_socket_path}")
        return server

    async def _serve(self) -> None:
        self._stopping = asyncio.Event()
        try:
            server = self._build()
            await server.start()
        except BaseException as exc:  # noqa: BLE001 - raised by start()
            # servelint: thread-ok same Event handoff as _port
            self._boot_error = exc
            return
        finally:
            self._started.set()
        # servelint: blocks the serve coroutine parks here for the
        # server's lifetime; stop() sets the event
        await self._stopping.wait()
        await server.stop(self._grace)

    def _request_stop(self, grace: float) -> None:
        # On the loop (call_soon_threadsafe), so that the grace is there
        # before the serve coroutine wakes.
        self._grace = grace
        self._stopping.set()

    def stop(self, grace: float) -> None:
        """New RPCs are refused at once; those in flight get `grace`
        seconds, then are cancelled."""
        if self._served is None or self._served.done():
            return
        aio_loop.get().call_soon_threadsafe(self._request_stop, grace)
        try:
            # Bounded (servelint DL003): a handler wedged on a sick
            # device would otherwise hold process shutdown hostage
            # forever. Past grace + slack the teardown proceeds; the
            # pool's daemonized threads die with the process.
            self._served.result(timeout=grace + 5.0)
        except futures.TimeoutError:
            pass
        self._pool.shutdown(wait=False)

    def wait(self) -> None:
        # servelint: blocks the main thread parks here for the process
        # lifetime, as in grpc's own wait_for_termination; stop() ends it
        self._served.result()


def _parse_mesh_axes(spec: str) -> dict[str, int]:
    """"data:4,model:2" -> {"data": 4, "model": 2} (-1 = absorb rest)."""
    out: dict[str, int] = {}
    for part in (spec or "").split(","):
        part = part.strip()
        if not part:
            continue
        name, sep, size = part.partition(":")
        try:
            out[name] = int(size) if sep else int("")
        except ValueError:
            raise ServingError.invalid_argument(
                f"malformed mesh_axes entry {part!r} (want axis:size)")
    return out


def _platform_configs(opts: ServerOptions, batching) -> dict:
    shared: dict = {
        "enable_model_warmup": opts.enable_model_warmup,
        "warmup_iterations": opts.warmup_iterations,
        "synthesize_warmup": opts.synthesize_warmup,
    }
    if opts.max_in_flight_batches > 1:
        shared["max_in_flight_batches"] = opts.max_in_flight_batches
    if opts.kv_block_size > 0:
        shared["kv_block_size"] = opts.kv_block_size
        shared["kv_num_blocks"] = opts.kv_num_blocks
        shared["kv_evict_policy"] = opts.kv_evict_policy
        shared["kv_prefill_chunk"] = opts.kv_prefill_chunk
    elif (opts.kv_num_blocks or opts.kv_prefill_chunk
          or opts.kv_evict_policy != "swap"):
        logging.getLogger(__name__).warning(
            "--kv_num_blocks/--kv_evict_policy/--kv_prefill_chunk have no "
            "effect without --kv_block_size > 0; the decode stack keeps "
            "the dense max-length slot pool (docs/MIGRATING.md 'Paged KV "
            "cache')")
    if batching is not None:
        shared["batching_parameters"] = batching
    mesh_axes = _parse_mesh_axes(opts.mesh_axes)
    if mesh_axes:
        shared["mesh_axes"] = mesh_axes
    configs = {platform: dict(shared)
               for platform in ("tensorflow", "jax", "tpu")}
    if opts.saved_model_tags:
        configs["tensorflow"]["tags"] = [
            t.strip() for t in opts.saved_model_tags.split(",") if t.strip()]
    if opts.use_tflite_model:
        configs["tensorflow"]["use_tflite_model"] = True
    if opts.platform_config_file:
        if opts.enable_batching:
            raise ServingError.invalid_argument(
                "--enable_batching cannot be set with "
                "--platform_config_file (main.cc rule: the platform config "
                "carries its own batching parameters)")
        for platform, overrides in _parse_platform_config_file(
                opts.platform_config_file).items():
            configs.setdefault(platform, {}).update(overrides)
    return configs


def _parse_platform_config_file(path: str) -> dict[str, dict]:
    """Text-format PlatformConfigMap -> per-platform config dicts.

    Reference parity: main.cc reads the file into PlatformConfigMap and
    ServerCore builds one source adapter per entry from the Any-typed
    source_adapter_config (platform_config_util.cc). Here the Any is
    unpacked as tpu.serving.TpuServableConfig (our registered adapter
    config, protos/tpu_platform.proto) and lowered to the factory's
    config keys."""
    from min_tfs_client_tpu.protos import tpu_platform_pb2

    config_map = _parse_text_proto(path, tfs_config_pb2.PlatformConfigMap)
    out: dict[str, dict] = {}
    for platform, platform_config in config_map.platform_configs.items():
        overrides: dict = {}
        any_config = platform_config.source_adapter_config
        tpu_config = tpu_platform_pb2.TpuServableConfig()
        if any_config.Is(tpu_config.DESCRIPTOR):
            any_config.Unpack(tpu_config)
            if tpu_config.HasField("batching_parameters"):
                overrides["batching_parameters"] = \
                    tpu_config.batching_parameters
            if tpu_config.mesh.axes:
                overrides["mesh_axes"] = {
                    axis.name: axis.size for axis in tpu_config.mesh.axes}
            if tpu_config.warmup_iterations:
                overrides["warmup_iterations"] = tpu_config.warmup_iterations
            if tpu_config.HasField("sequence_bucketing"):
                overrides["seq_buckets"] = list(
                    tpu_config.sequence_bucketing.allowed_lengths)
                if tpu_config.sequence_bucketing.pad_value:
                    overrides["seq_pad_value"] = int(
                        tpu_config.sequence_bucketing.pad_value)
        elif any_config.type_url:
            raise ServingError.invalid_argument(
                f"platform {platform!r}: unsupported source_adapter_config "
                f"type {any_config.type_url!r} (expected "
                "tpu.serving.TpuServableConfig)")
        out[platform] = overrides
    return out
