"""CLI entry point — flag surface mirrors the reference model server
(model_servers/main.cc:59-195) where the flags are meaningful on TPU.

    python -m min_tfs_client_tpu.server.main --port=8500 \
        --model_name=resnet --model_base_path=/models/resnet
"""

from __future__ import annotations

import argparse
import sys

from min_tfs_client_tpu.server.server import Server, ServerOptions


def _flag_bool(v: str) -> bool:
    """TF-style bool flag values, case-insensitive: false/0/no disable
    (the reference's flag parser accepts e.g. =False; a value that only
    matched lowercase "false" would silently leave the flag ON)."""
    return str(v).strip().lower() not in ("false", "0", "no")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser("tpu_model_server")
    p.add_argument("--port", type=int, default=8500,
                   help="gRPC port to listen on")
    p.add_argument("--rest_api_port", type=int, default=0,
                   help="HTTP/REST port; 0 disables")
    p.add_argument("--rest_api_num_threads", type=int, default=4,
                   help="HTTP front-end worker threads (main.cc:70)")
    p.add_argument("--rest_api_timeout_in_ms", type=int, default=30000,
                   help="HTTP idle/request timeout (main.cc:73)")
    p.add_argument("--model_name", default="default")
    p.add_argument("--model_base_path", default="")
    p.add_argument("--model_platform", default="tensorflow",
                   help='"tensorflow" (SavedModel) or "jax" (native)')
    p.add_argument("--model_config_file", default="")
    p.add_argument("--model_config_file_poll_wait_seconds", type=float,
                   default=0)
    p.add_argument("--file_system_poll_wait_seconds", type=float, default=1.0)
    p.add_argument("--enable_batching", action="store_true")
    p.add_argument("--batching_parameters_file", default="")
    p.add_argument("--max_in_flight_batches", type=int, default=1,
                   help="batches a queue may have dispatched to the device "
                        "with results not yet materialized; >1 overlaps "
                        "batch k+1's dispatch with batch k's D2H copies "
                        "and microbatch-pipelines multi-segment imports "
                        "(1 = exact pre-window serial behavior)")
    p.add_argument("--kv_block_size", type=int, default=0,
                   help="page the decode KV cache into blocks of this many "
                        "tokens (decode_sessions.PagedSlotPool): session "
                        "capacity then scales with used tokens, not "
                        "max-length slots. 0 = the old dense slot pool, "
                        "byte-for-byte (docs/MIGRATING.md 'Paged KV cache')")
    p.add_argument("--kv_num_blocks", type=int, default=0,
                   help="KV page-pool capacity (the declared HBM budget); "
                        "0 sizes it to the dense pool's worst case "
                        "(max_sessions x ceil(max_decode_len/block_size))")
    p.add_argument("--kv_evict_policy", default="swap",
                   choices=["swap", "close", "refuse"],
                   help="when the KV page pool runs dry: swap the "
                        "oldest-idle session's pages to host memory "
                        "(restored bit-identical on its next step), close "
                        "it (typed RESOURCE_EXHAUSTED on its next step), "
                        "or refuse the requesting step (session stays "
                        "live for retry)")
    p.add_argument("--kv_prefill_chunk", type=int, default=0,
                   help="tokens per chunked-prefill round: forced decoder "
                        "prefixes (decode_init_prefix) stream through the "
                        "paged kernel this many positions per tick, "
                        "interleaved with in-flight decodes, instead of "
                        "one monolithic prefill. 0 = one page "
                        "(kv_block_size tokens) per round")
    p.add_argument("--monitoring_config_file", default="")
    p.add_argument("--ssl_config_file", default="")
    p.add_argument("--max_num_load_retries", type=int, default=5)
    p.add_argument("--load_retry_interval_micros", type=int,
                   default=60 * 1000 * 1000)
    p.add_argument("--num_load_threads", type=int, default=2)
    p.add_argument("--num_unload_threads", type=int, default=2)
    p.add_argument("--grpc_max_threads", type=int, default=16)
    p.add_argument("--enable_model_warmup", type=_flag_bool,
                   default=True)
    p.add_argument("--num_request_iterations_for_warmup", type=int, default=1,
                   help="replay count per warmup record (ModelWarmupOptions."
                        "num_request_iterations)")
    p.add_argument("--synthesize_warmup", action="store_true",
                   help="synthesize compile-priming requests for models "
                        "that ship no warmup file")
    p.add_argument("--mesh_axes", default="",
                   help='serving device mesh, e.g. "data:-1" or '
                        '"data:4,model:2"; batched signatures execute '
                        'data-parallel over it ("" = single device)')
    p.add_argument("--response_tensors_as_content", action="store_true",
                   help="serialize response tensors as tensor_content "
                        "instead of typed fields")
    p.add_argument("--profiler_port", type=int, default=0,
                   help="jax.profiler server port for on-demand trace "
                        "capture; 0 disables")
    p.add_argument("--grpc_socket_path", default="",
                   help="also listen on this UNIX-domain socket path")
    p.add_argument("--grpc_channel_arguments", default="",
                   help='extra gRPC server args, "key=value,key=value"')
    p.add_argument("--saved_model_tags", default="",
                   help="comma-separated MetaGraphDef tags to load "
                        '(default "serve")')
    p.add_argument("--platform_config_file", default="",
                   help="text-format PlatformConfigMap; mutually exclusive "
                        "with --enable_batching")
    p.add_argument("--allow_version_labels_for_unavailable_models",
                   action="store_true",
                   help="permit version labels pointing at versions that "
                        "are not yet AVAILABLE")
    p.add_argument("--use_tflite_model", action="store_true",
                   help="serve <version>/model.tflite via the TFLite "
                        "importer")
    p.add_argument("--tensorflow_session_parallelism", type=int, default=0,
                   help="threads for running a session; fills in for "
                        "whichever intra/inter flag is unset (main.cc:135)."
                        " Ignored if --platform_config_file is non-empty")
    p.add_argument("--tensorflow_intra_op_parallelism", type=int, default=0,
                   help="reference: threads per individual op. On TPU, "
                        "within-op parallelism is owned by XLA (SURVEY.md "
                        "§2.11), so this is accepted and inert")
    p.add_argument("--tensorflow_inter_op_parallelism", type=int, default=0,
                   help="concurrently executing operations; maps to the "
                        "executor pool that runs signature executions "
                        "(caps --grpc_max_threads). Ignored if "
                        "--platform_config_file is non-empty")
    p.add_argument("--per_process_gpu_memory_fraction", type=float,
                   default=0.0,
                   help="N/A on TPU — there is no GPU memory pool; HBM is "
                        "gated by the resource tracker. Accepted for CLI "
                        "compatibility, warns if non-zero")
    p.add_argument("--flush_filesystem_caches", type=_flag_bool,
                   default=True,
                   help="drop OS page cache for model files after the "
                        "initial loads (weights already live in device/"
                        "host arrays)")
    p.add_argument("--remove_unused_fields_from_bundle_metagraph",
                   type=_flag_bool, default=True,
                   help="reference trims unused MetaGraphDef fields after "
                        "load; the GraphDef import here retains only the "
                        "constants reachable from each signature by "
                        "design, so this is inherently satisfied and the "
                        "flag is accepted for CLI compatibility")
    p.add_argument("--enable_signature_method_name_check",
                   nargs="?", const=True, default=True,
                   type=_flag_bool,
                   help="require Classify/Regress signatures' method_name "
                        "to match the API called (default: true, matching "
                        "the reference's unconditional check; pass =false "
                        "to let any signature with Example feature specs "
                        "serve either API)")
    p.add_argument("--slo_latency_objective_ms", type=float, default=1000.0,
                   help="default per-model latency objective at "
                        "--slo_latency_quantile (health plane; "
                        "docs/OBSERVABILITY.md)")
    p.add_argument("--slo_latency_quantile", type=float, default=0.99,
                   help="quantile the latency objective applies to")
    p.add_argument("--slo_error_budget", type=float, default=0.01,
                   help="allowed error fraction over the SLO window")
    p.add_argument("--slo_window_seconds", type=float, default=60.0,
                   help="rolling window for SLO quantiles and burn rates")
    p.add_argument("--slo_shed_burn_rate", type=float, default=0.0,
                   help="readiness sheds when the max SLO burn rate "
                        "reaches this (0 disables shedding)")
    p.add_argument("--serving_weight", type=float, default=1.0,
                   help="relative routing capacity advertised in the "
                        "readyz payload; a router's weighted ring gives "
                        "this replica ~weight/sum(weights) of new "
                        "placements (docs/ROUTING.md)")
    p.add_argument("--flight_recorder_dir", default="",
                   help="directory for flight-recorder JSON dumps "
                        "(first INTERNAL error / SIGUSR2); empty = "
                        "TPU_SERVING_FLIGHT_DIR or the system tempdir")
    p.add_argument("--trace_ring_size", type=int, default=0,
                   help="capacity of the request-trace ring behind "
                        "/monitoring/traces (0 = TPU_SERVING_TRACE_RING "
                        "env or the 256 default)")
    p.add_argument("--fault_plan", default="",
                   help="seeded JSON fault plan (path or inline JSON) "
                        "arming the deterministic fault-injection "
                        "points in this process — TESTING/CHAOS ONLY "
                        "(docs/ROBUSTNESS.md). Empty = honor "
                        "TPU_SERVING_FAULT_PLAN, else disarmed "
                        "(zero-cost)")
    p.add_argument("--cost_log_dir", default="",
                   help="directory for the servecost JSONL wide-event "
                        "log: one schema-versioned cost record per "
                        "sampled request, every record carrying "
                        "trace_id so logs join stitched traces "
                        "(docs/OBSERVABILITY.md 'Cost attribution'). "
                        "Empty = no file log; /monitoring/costs "
                        "aggregates still serve")
    p.add_argument("--cost_log_sample", type=float, default=1.0,
                   help="fraction of requests written to the cost log, "
                        "deterministic per trace id (every process "
                        "that saw a trace keeps or drops it "
                        "identically); 0 disables writes")
    p.add_argument("--watchdog", type=_flag_bool, default=True,
                   help="streaming anomaly detectors over the "
                        "observability planes (SLO burn spike, KV leak "
                        "slope, tick collapse, compile storm, cost "
                        "conservation drift, ticker lag) on the "
                        "watchdog's own thread, served at "
                        "/monitoring/alerts (docs/OBSERVABILITY.md "
                        "'Alerting & trend gating')")
    p.add_argument("--watchdog_interval_s", type=float, default=5.0,
                   help="watchdog sampling/evaluation interval")
    p.add_argument("--watchdog_ring_size", type=int, default=256,
                   help="bounded alert-ring capacity served at "
                        "/monitoring/alerts")
    p.add_argument("--profile_sampler_hz", type=float, default=11.0,
                   help="continuous sampling-profiler rate: per-thread/"
                        "per-stage CPU attribution and flame graphs at "
                        "/monitoring/profile (docs/OBSERVABILITY.md "
                        "'Profiling plane'). Low and off-round by "
                        "design; 0 disables the ticker (on-demand "
                        "?seconds= capture still works)")
    p.add_argument("--profile_dir", default="",
                   help="directory for /monitoring/profile?device=1 "
                        "programmatic jax.profiler.trace captures "
                        "(XPlane dumps); empty disables device capture")
    p.add_argument("--drain_grace_seconds", type=float, default=0.0,
                   help="graceful-drain window on stop()/SIGTERM: the "
                        "health plane flips NOT_SERVING immediately, "
                        "then serving stays up this long while live "
                        "decode sessions finish (their KV state pins "
                        "them to this process; docs/ROUTING.md). 0 = "
                        "flip and stop without waiting for sessions")
    p.add_argument("--version", action="store_true",
                   help="print the server version and exit")
    return p


def options_from_args(args) -> ServerOptions:
    return ServerOptions(
        grpc_port=args.port,
        rest_api_port=args.rest_api_port,
        rest_api_num_threads=args.rest_api_num_threads,
        rest_api_timeout_in_ms=args.rest_api_timeout_in_ms,
        model_name=args.model_name,
        model_base_path=args.model_base_path,
        model_platform=args.model_platform,
        model_config_file=args.model_config_file,
        model_config_file_poll_wait_seconds=args.model_config_file_poll_wait_seconds,
        file_system_poll_wait_seconds=args.file_system_poll_wait_seconds,
        enable_batching=args.enable_batching,
        batching_parameters_file=args.batching_parameters_file,
        max_in_flight_batches=args.max_in_flight_batches,
        kv_block_size=args.kv_block_size,
        kv_num_blocks=args.kv_num_blocks,
        kv_evict_policy=args.kv_evict_policy,
        kv_prefill_chunk=args.kv_prefill_chunk,
        monitoring_config_file=args.monitoring_config_file,
        ssl_config_file=args.ssl_config_file,
        max_num_load_retries=args.max_num_load_retries,
        load_retry_interval_micros=args.load_retry_interval_micros,
        num_load_threads=args.num_load_threads,
        num_unload_threads=args.num_unload_threads,
        grpc_max_threads=args.grpc_max_threads,
        enable_model_warmup=args.enable_model_warmup,
        warmup_iterations=args.num_request_iterations_for_warmup,
        synthesize_warmup=args.synthesize_warmup,
        mesh_axes=args.mesh_axes,
        response_tensors_as_content=args.response_tensors_as_content,
        profiler_port=args.profiler_port,
        grpc_socket_path=args.grpc_socket_path,
        grpc_channel_arguments=args.grpc_channel_arguments,
        saved_model_tags=args.saved_model_tags,
        platform_config_file=args.platform_config_file,
        allow_version_labels_for_unavailable_models=(
            args.allow_version_labels_for_unavailable_models),
        use_tflite_model=args.use_tflite_model,
        tensorflow_session_parallelism=args.tensorflow_session_parallelism,
        tensorflow_intra_op_parallelism=args.tensorflow_intra_op_parallelism,
        tensorflow_inter_op_parallelism=args.tensorflow_inter_op_parallelism,
        per_process_gpu_memory_fraction=args.per_process_gpu_memory_fraction,
        flush_filesystem_caches=args.flush_filesystem_caches,
        enable_signature_method_name_check=(
            args.enable_signature_method_name_check),
        slo_latency_objective_ms=args.slo_latency_objective_ms,
        slo_latency_quantile=args.slo_latency_quantile,
        slo_error_budget=args.slo_error_budget,
        slo_window_seconds=args.slo_window_seconds,
        slo_shed_burn_rate=args.slo_shed_burn_rate,
        serving_weight=args.serving_weight,
        flight_recorder_dir=args.flight_recorder_dir,
        trace_ring_size=args.trace_ring_size,
        drain_grace_seconds=args.drain_grace_seconds,
        fault_plan=args.fault_plan,
        cost_log_dir=args.cost_log_dir,
        cost_log_sample=args.cost_log_sample,
        watchdog=args.watchdog,
        watchdog_interval_s=args.watchdog_interval_s,
        watchdog_ring_size=args.watchdog_ring_size,
        profile_sampler_hz=args.profile_sampler_hz,
        profile_dir=args.profile_dir,
    )


def install_sigterm_handler(server: Server) -> None:
    """SIGTERM = graceful drain (the k8s/pod-eviction contract): flip
    NOT_SERVING first, wait out live decode sessions up to
    --drain_grace_seconds, then stop. The actual stop runs on a worker
    thread — signal handlers must return promptly, and Server.stop can
    legitimately block for the whole drain window."""
    import signal
    import threading

    def _on_sigterm(signum, frame):
        # NON-daemon: wait_for_termination() returns the moment the gRPC
        # server stops, and main() returning must not let the
        # interpreter kill this thread before the REST shutdown and
        # core.stop() (model unload, manager teardown) finish — the
        # interpreter joins non-daemon threads on exit. Server.stop's
        # waits are internally bounded, so this cannot wedge shutdown.
        threading.Thread(target=server.stop, name="sigterm-drain",
                         daemon=False).start()

    signal.signal(signal.SIGTERM, _on_sigterm)


def _live_native_libs(rest_up: bool) -> list[str]:
    """Which native libraries this process runs on (each has a pure-Python
    stand-in when the toolchain cannot build it). The HTTP front-end and
    the JSON codec only matter, and are only built, when REST is up."""
    from min_tfs_client_tpu import native

    live = {"tpuserve": native.load() is not None}
    if rest_up:
        from min_tfs_client_tpu.server.json_fast import json_fast_available
        from min_tfs_client_tpu.server.native_http import (
            native_http_available,
        )

        live["tpunethttp"] = native_http_available()
        live["tpujson"] = json_fast_available()
    return [name for name, loaded in live.items() if loaded]


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if args.version:
        from min_tfs_client_tpu.server.version import version_string

        print(version_string())
        return 0

    # After the --version early-exit so flag-only invocations never pay a
    # jax import; before the server builds so load-time compiles are cached.
    from min_tfs_client_tpu.utils import compile_cache

    compile_cache.configure()
    server = Server(options_from_args(args)).build_and_start()
    install_sigterm_handler(server)
    ports = f"gRPC on {server.grpc_port}"
    rest_port = getattr(server, "rest_port", None)
    if rest_port:
        ports += f", REST on {rest_port}; rest_backend={server.rest_backend}"
    libs = ",".join(_live_native_libs(bool(rest_port))) or "none"
    print(f"[tpu_model_server] serving: {ports}; native_libs={libs}",
          flush=True)
    try:
        server.wait_for_termination()
    except KeyboardInterrupt:
        server.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
