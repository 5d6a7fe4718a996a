"""Sampled request/response logging to pluggable collectors.

Parity with core/request_logger.{h,cc} (uniform sampling from
SamplingConfig), core/server_request_logger.{h,cc} (per-model registry,
hot-swapped atomically on config reload — the FastReadDynamicPtr pattern
collapses to an atomic dict swap under the GIL), and core/log_collector
(type-registered sinks; "tfrecord" writes PredictionLog TFRecord files).
"""

from __future__ import annotations

import pathlib
import random
import threading
from typing import Callable, Mapping

from min_tfs_client_tpu.protos import tfs_apis_pb2 as apis
from min_tfs_client_tpu.protos import tfs_config_pb2
from min_tfs_client_tpu.utils import tfrecord
from min_tfs_client_tpu.utils.status import ServingError


class LogCollector:
    def collect(self, log: apis.PredictionLog) -> None:
        raise NotImplementedError

    def flush(self) -> None:
        pass

    def close(self) -> None:
        pass


class MemoryLogCollector(LogCollector):
    """Test/introspection sink."""

    def __init__(self, config=None):
        self.logs: list[apis.PredictionLog] = []

    def collect(self, log: apis.PredictionLog) -> None:
        self.logs.append(log)


class TFRecordLogCollector(LogCollector):
    """Appends PredictionLog records to <filename_prefix>.tfrecord."""

    def __init__(self, config: tfs_config_pb2.LogCollectorConfig):
        prefix = config.filename_prefix or "request_log"
        self._path = pathlib.Path(f"{prefix}.tfrecord")
        self._path.parent.mkdir(parents=True, exist_ok=True)
        self._lock = threading.Lock()
        self._file = open(self._path, "ab")

    def collect(self, log: apis.PredictionLog) -> None:
        framed = tfrecord.frame(log.SerializeToString())
        with self._lock:
            if self._file.closed:
                return  # config swap closed us mid-request: drop, don't raise
            self._file.write(framed)
            # Durable immediately: request logs must survive a server kill
            # (records are small; the OS page cache absorbs the cost).
            self._file.flush()

    def flush(self) -> None:
        with self._lock:
            if not self._file.closed:
                self._file.flush()

    def close(self) -> None:
        with self._lock:
            self._file.close()


_COLLECTOR_TYPES: dict[str, Callable] = {
    "tfrecord": TFRecordLogCollector,
    "memory": MemoryLogCollector,
}


def register_log_collector(type_name: str, factory: Callable) -> None:
    _COLLECTOR_TYPES[type_name] = factory


class RequestLogger:
    """Samples and forwards one model's request/response pairs."""

    def __init__(self, config: tfs_config_pb2.LoggingConfig,
                 collector: LogCollector, *,
                 rand: random.Random | None = None):
        self.config = config
        self.collector = collector
        self._rate = config.sampling_config.sampling_rate
        self._rand = rand or random.Random()

    def should_log(self) -> bool:
        return self._rate > 0 and self._rand.random() < self._rate

    def log(self, log: apis.PredictionLog, model_spec: apis.ModelSpec) -> None:
        log.log_metadata.model_spec.CopyFrom(model_spec)
        log.log_metadata.sampling_config.CopyFrom(self.config.sampling_config)
        self.collector.collect(log)


class ServerRequestLogger:
    """Per-model logger map, swapped wholesale on config updates."""

    def __init__(self):
        self._loggers: Mapping[str, RequestLogger] = {}

    def update(self, logging_configs: Mapping[str, tfs_config_pb2.LoggingConfig]):
        old = self._loggers
        new: dict[str, RequestLogger] = {}
        for model, config in logging_configs.items():
            if not config.HasField("log_collector_config"):
                continue
            existing = old.get(model)
            if existing is not None and existing.config == config:
                new[model] = existing  # unchanged: keep the open collector
                continue
            type_name = config.log_collector_config.type
            factory = _COLLECTOR_TYPES.get(type_name)
            if factory is None:
                raise ServingError.invalid_argument(
                    f"unknown log collector type {type_name!r}; registered: "
                    f"{sorted(_COLLECTOR_TYPES)}")
            new[model] = RequestLogger(config, factory(
                config.log_collector_config))
        self._loggers = new  # atomic swap (GIL): readers see old or new
        kept = {id(lg) for lg in new.values()}
        for logger in old.values():
            if id(logger) not in kept:
                logger.collector.flush()
                logger.collector.close()

    def logs(self, model_name: str) -> bool:
        """Whether requests of this model are logged at all (a log write
        may wait: such a request stays off the event loop)."""
        return model_name in self._loggers

    def maybe_log(self, model_name: str, build_log: Callable[[], apis.PredictionLog],
                  model_spec: apis.ModelSpec) -> None:
        logger = self._loggers.get(model_name)
        if logger is None:
            return
        try:
            if logger.should_log():
                logger.log(build_log(), model_spec)
                _count_outcome(model_name, "logged")
            else:
                _count_outcome(model_name, "sampled_out")
        except Exception:  # pragma: no cover - logging must never fail a
            import traceback  # healthy request (disk full, collector race)

            _count_outcome(model_name, "dropped")
            traceback.print_exc()


def _count_outcome(model_name: str, outcome: str) -> None:
    """Sampling outcomes per model — request-log sampling was previously
    invisible: a sampling_rate typo or a full disk produced no signal at
    all. Now `request_log_count{model,outcome}` makes logged vs
    sampled_out vs dropped scrapeable."""
    try:
        from min_tfs_client_tpu.server import metrics

        metrics.request_log_count.increment(model_name, outcome)
    except Exception:  # pragma: no cover - metrics must not break logging
        pass
