"""Transport-independent request handlers.

One implementation of the five PredictionService methods + two ModelService
methods, shared by the gRPC servicers, the tpu:// in-process channel, and
the REST front-end. Semantics follow the reference implementations:

  Predict        predict_util.cc:89-215 (signature lookup, alias resolution,
                 output_filter, effective model_spec in response)
  Classify       classifier.cc (scores/classes outputs, per-example assembly)
  Regress        regressor.cc
  MultiInference multi_inference.cc:31-77 (validation rules)
  GetModelMetadata get_model_metadata_impl.cc (signature_def only)
  GetModelStatus get_model_status_impl.cc:30-75
  ReloadConfig   model_service_impl.cc:41-69
"""

from __future__ import annotations

import functools
import logging
import time

import numpy as np

from min_tfs_client_tpu.core.server_core import ServerCore
from min_tfs_client_tpu.observability import tracing
from min_tfs_client_tpu.protos import tfs_apis_pb2 as apis
from min_tfs_client_tpu.servables.servable import (
    CLASSIFY_METHOD_NAME,
    CLASSIFY_OUTPUT_CLASSES,
    CLASSIFY_OUTPUT_SCORES,
    DEFAULT_SERVING_SIGNATURE_DEF_KEY,
    REGRESS_METHOD_NAME,
    REGRESS_OUTPUTS,
    Signature,
)
from min_tfs_client_tpu.tensor.codec import (
    ndarray_to_tensor_proto,
    tensor_proto_to_ndarray,
)
from min_tfs_client_tpu.tensor.example_codec import decode_input
from min_tfs_client_tpu.utils.status import ServingError

SIGNATURE_DEF_METADATA_FIELD = "signature_def"


def _effective_spec(target, model_spec, version: int, signature_name: str) -> None:
    target.name = model_spec.name
    target.version.value = version
    if signature_name:
        target.signature_name = signature_name


def _spec_of(request):
    spec = getattr(request, "model_spec", None)
    if spec is None:
        tasks = getattr(request, "tasks", None)
        spec = tasks[0].model_spec if tasks else None
    return spec


class _counted:
    """Request count/latency instrumentation (the serving-path metrics the
    reference records in servables/tensorflow/util.cc:36-71) and the error
    tap, around one handler invocation: entered OUTSIDE the request's
    trace, so that the trace has finished, with its status, when an
    error is counted (`opened` hands it the trace's id). A plain class with slots: this wraps every
    request."""

    __slots__ = ("api", "model", "signature", "trace_id", "_start")

    def __init__(self, api: str, request):
        spec = _spec_of(request)
        self.api = api
        self.model = spec.name if spec is not None else ""
        self.signature = spec.signature_name if spec is not None else ""
        self.trace_id = ""

    def __enter__(self):
        self._start = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        from min_tfs_client_tpu.server import metrics

        if exc is None:
            metrics.request_count.increment(self.api, "0")
            metrics.request_latency.observe(
                (time.perf_counter() - self._start) * 1e6, self.api)
        elif isinstance(exc, Exception):
            # Same mapping the transports apply to the wire status
            # (error_from_exception): an unexpected RuntimeError IS an
            # INTERNAL to the client, so it must count — and trigger the
            # flight-recorder dump — as one here too.
            from min_tfs_client_tpu.utils.status import error_from_exception

            code = error_from_exception(exc).code
            metrics.request_count.increment(self.api, str(code))
            # Black-box ring entry (and the one-shot dump when the code
            # is INTERNAL): every transport funnels through here, so
            # this is THE error tap.
            from min_tfs_client_tpu.observability import flight_recorder

            flight_recorder.record_error(
                self.api, self.model, self.signature, code, str(exc),
                trace_id=self.trace_id)
        return False

    def opened(self, trace) -> None:
        """The request's trace is open (None: tracing is off). Inside
        the trace + error funnel: an injected typed error counts,
        records, and surfaces on the wire exactly like a real handler
        failure; a delay lands in this request's stage timeline."""
        from min_tfs_client_tpu.robustness import faults

        if trace is not None:
            self.trace_id = trace.trace_id
        faults.point("backend.handle.pre", api=self.api, model=self.model,
                     signature=self.signature)


def _instrumented(api: str):
    """`_counted` + the request-trace envelope around a handler method:
    every transport (gRPC, REST, tpu://) funnels through these methods,
    so opening the RequestTrace here puts ALL entry points on the
    tracing spine."""

    def wrap(fn):
        @functools.wraps(fn)
        def inner(self, request):
            with _counted(api, request) as tap, tracing.request_trace(
                    api, model=tap.model,
                    signature=tap.signature) as trace:
                tap.opened(trace)
                return fn(self, request)
        return inner
    return wrap


class Handlers:
    def __init__(self, core: ServerCore, *,
                 response_tensors_as_content: bool = False,
                 signature_method_name_check: bool = True):
        self.core = core
        # False = typed fields (the reference server's default serialization,
        # server_core.h:186-188 kAsProtoField); True = tensor_content.
        self._as_content = response_tensors_as_content
        # Strict method_name match on Classify/Regress, ON by default: the
        # reference checks unconditionally (classifier.cc:296-312,
        # regressor.cc:231) — e.g. Regress against a classify signature is
        # InvalidArgument. --enable_signature_method_name_check=false
        # relaxes it so any signature carrying Example feature specs
        # serves either API (a this-framework extension).
        self._method_name_check = signature_method_name_check

    # -- PredictionService ---------------------------------------------------

    @_instrumented("predict")
    def predict(self, request: apis.PredictRequest) -> apis.PredictResponse:
        with self.core.servable_handle(request.model_spec) as handle:
            signature, inputs = self._predict_inputs(handle, request)
            outputs = signature.run(inputs, tuple(request.output_filter))
            self._answered(signature, outputs)
            return self._predict_response(handle, request, outputs)

    def can_await(self, request: apis.PredictRequest) -> bool:
        """Whether `apredict(request)` may run on the gRPC event-loop
        thread, where nothing may block: only where the request's own
        signature has a form that awaits instead (`Signature.afn`; None,
        the default, says no) and nothing around it can sleep either (an
        armed fault point, a request log that writes). A request that
        cannot even be asked about, for an unknown model or signature,
        answers no: the pool's run gives the error its trace and its
        wire form."""
        from min_tfs_client_tpu.robustness import faults

        spec = request.model_spec
        if faults.covers("backend.handle.pre") or \
                self.core.request_logger.logs(spec.name):
            return False
        try:
            with self.core.servable_handle(spec) as handle:
                return handle.servable.signature(
                    spec.signature_name).afn is not None
        except Exception:  # servelint: fallback-ok no outcome here: the
            return False   # request's own run, on the pool, reports it

    async def apredict(
            self, request: apis.PredictRequest) -> apis.PredictResponse:
        """`predict` on an event loop, for a request that `can_await`:
        the same envelope, the same stages, the signature's wait
        awaited."""
        with _counted("predict", request) as tap, tracing.request_trace(
                "predict", model=tap.model,
                signature=tap.signature) as trace:
            tap.opened(trace)
            with self.core.servable_handle(request.model_spec) as handle:
                signature, inputs = self._predict_inputs(handle, request)
                outputs = await signature.arun(
                    inputs, tuple(request.output_filter))
                self._answered(signature, outputs)
                return self._predict_response(handle, request, outputs)

    @staticmethod
    def _answered(signature, outputs) -> None:
        """The signature's `on_answer`, where it has one, on a Predict's
        own outputs."""
        Handlers._noted("on_answer", signature, outputs)

    @staticmethod
    def _noted(hook: str, signature, tensors) -> None:
        """A signature's `on_request` or `on_answer`, where it has one,
        on a Predict's own inputs or outputs. What it notes is telemetry:
        if it raises, the answer still goes out, and the log says what
        was lost."""
        note = getattr(signature, hook)
        if note is None:
            return
        try:
            note(signature, tensors)
        except Exception:  # servelint: fallback-ok the answer is sound
            logging.getLogger(__name__).exception(
                "%s of %s raised; its note is lost", hook,
                signature.telemetry_label or "a signature")

    def _predict_inputs(self, handle, request: apis.PredictRequest):
        """(signature, decoded inputs) of a Predict, annotated on its
        trace."""
        from min_tfs_client_tpu.tensor.codec import tensor_protos_to_dict

        tracing.annotate(version=handle.id.version)
        signature = handle.servable.signature(
            request.model_spec.signature_name)
        inputs = tensor_protos_to_dict(request.inputs, writable=False)
        self._noted("on_request", signature, inputs)
        sid = inputs.get("session_id")
        if sid is not None:
            # Sessioned decode surface: the session id on the trace
            # is what cross-links /monitoring/traces to the
            # per-session timeline at /monitoring/sessions.
            raw = np.asarray(sid).reshape(-1)
            if raw.size == 1:
                value = raw[0]
                tracing.annotate(session_id=(
                    value.decode("utf-8", "replace")
                    if isinstance(value, bytes) else str(value)))
        return signature, inputs

    def _predict_response(self, handle, request: apis.PredictRequest,
                          outputs) -> apis.PredictResponse:
        response = apis.PredictResponse()
        with tracing.span("serving/serialize"):
            _effective_spec(response.model_spec, request.model_spec,
                            handle.id.version,
                            request.model_spec.signature_name)
            for alias, arr in outputs.items():
                response.outputs[alias].CopyFrom(ndarray_to_tensor_proto(
                    arr, use_tensor_content=self._as_content))
        self.core.request_logger.maybe_log(
            request.model_spec.name,
            lambda: _predict_log(request, response),
            response.model_spec)
        return response

    def _example_signature(self, servable, model_spec, want_method: str) -> Signature:
        signature = servable.signature(model_spec.signature_name)
        if self._method_name_check and signature.method_name != want_method:
            raise ServingError.invalid_argument(
                f"Expected {want_method} signature method_name but got "
                f"{signature.method_name!r}")
        if signature.feature_specs is None:
            raise ServingError.failed_precondition(
                f"signature has no feature specs; cannot parse Examples")
        return signature

    def _run_examples(self, signature: Signature, request_input: apis.Input,
                      model_name: str = ""):
        from min_tfs_client_tpu.server import metrics

        with tracing.span("serving/parse_examples"):
            features, n = decode_input(request_input, signature.feature_specs)
        if n == 0:
            raise ServingError.invalid_argument("Input is empty")
        if model_name:
            metrics.request_example_counts.observe(n, model_name)
        return signature.run(features), n

    @_instrumented("classify")
    def classify(
        self, request: apis.ClassificationRequest
    ) -> apis.ClassificationResponse:
        with self.core.servable_handle(request.model_spec) as handle:
            signature = self._example_signature(
                handle.servable, request.model_spec, CLASSIFY_METHOD_NAME)
            outputs, n = self._run_examples(signature, request.input,
                                            request.model_spec.name)
            response = apis.ClassificationResponse()
            _effective_spec(response.model_spec, request.model_spec,
                            handle.id.version,
                            request.model_spec.signature_name)
            with tracing.span("serving/serialize"):
                _assemble_classifications(
                    response.result, outputs, n, signature.class_labels)
            self.core.request_logger.maybe_log(
                request.model_spec.name,
                lambda: _classify_log(request, response),
                response.model_spec)
            return response

    @_instrumented("regress")
    def regress(self, request: apis.RegressionRequest) -> apis.RegressionResponse:
        with self.core.servable_handle(request.model_spec) as handle:
            signature = self._example_signature(
                handle.servable, request.model_spec, REGRESS_METHOD_NAME)
            outputs, n = self._run_examples(signature, request.input,
                                            request.model_spec.name)
            response = apis.RegressionResponse()
            _effective_spec(response.model_spec, request.model_spec,
                            handle.id.version,
                            request.model_spec.signature_name)
            with tracing.span("serving/serialize"):
                _assemble_regressions(response.result, outputs, n)
            self.core.request_logger.maybe_log(
                request.model_spec.name,
                lambda: _regress_log(request, response),
                response.model_spec)
            return response

    @_instrumented("multi_inference")
    def multi_inference(
        self, request: apis.MultiInferenceRequest
    ) -> apis.MultiInferenceResponse:
        # Validation rules from multi_inference.cc:44-77.
        if not request.tasks:
            raise ServingError.invalid_argument("Inference request is empty")
        names = {t.model_spec.name for t in request.tasks}
        if len(names) != 1:
            raise ServingError.invalid_argument(
                "All ModelSpecs in a MultiInferenceRequest must access the "
                f"same model name; got {sorted(names)}")
        seen_signatures = set()
        for task in request.tasks:
            key = task.model_spec.signature_name or "serving_default"
            if key in seen_signatures:
                raise ServingError.invalid_argument(
                    f"Duplicate evaluation of signature: {key}")
            seen_signatures.add(key)
            if task.method_name not in (CLASSIFY_METHOD_NAME,
                                        REGRESS_METHOD_NAME):
                raise ServingError.unimplemented(
                    f"Unsupported signature method_name: {task.method_name}")

        response = apis.MultiInferenceResponse()
        spec0 = request.tasks[0].model_spec
        with self.core.servable_handle(spec0) as handle:
            servable = handle.servable
            sigs = [self._example_signature(
                        servable, task.model_spec, task.method_name)
                    for task in request.tasks]

            # Single-execution union (multi_inference.cc:31-77's one
            # Session::Run): eligible when every task's signature shares
            # inputs + feature specs, so the shared Input decodes once and
            # one fused executable evaluates all heads. Otherwise fall
            # back to one dispatch per task (still correct).
            first = sigs[0]
            keys = [t.model_spec.signature_name or
                    DEFAULT_SERVING_SIGNATURE_DEF_KEY for t in request.tasks]
            fuse = (len(sigs) > 1
                    and all(s.feature_specs is first.feature_specs
                            for s in sigs)
                    and servable.can_run_union(keys))
            union_outputs = None
            if fuse:
                features, n = decode_input(request.input, first.feature_specs)
                if n == 0:
                    raise ServingError.invalid_argument("Input is empty")
                union_outputs = servable.run_union(keys, features)

            for task, key, signature in zip(request.tasks, keys, sigs):
                if union_outputs is not None:
                    outputs = union_outputs[key]
                else:
                    outputs, n = self._run_examples(signature, request.input)
                result = response.results.add()
                _effective_spec(result.model_spec, task.model_spec,
                                handle.id.version,
                                task.model_spec.signature_name)
                if task.method_name == CLASSIFY_METHOD_NAME:
                    _assemble_classifications(
                        result.classification_result, outputs, n,
                        signature.class_labels)
                else:
                    _assemble_regressions(result.regression_result, outputs, n)
        return response

    def get_model_metadata(
        self, request: apis.GetModelMetadataRequest
    ) -> apis.GetModelMetadataResponse:
        if not request.metadata_field:
            raise ServingError.invalid_argument(
                "GetModelMetadataRequest must specify at least one metadata_field")
        for field in request.metadata_field:
            if field != SIGNATURE_DEF_METADATA_FIELD:
                raise ServingError.invalid_argument(
                    f"Metadata field {field} is not supported")
        with self.core.servable_handle(request.model_spec) as handle:
            response = apis.GetModelMetadataResponse()
            response.model_spec.name = request.model_spec.name
            response.model_spec.version.value = handle.id.version
            response.metadata[SIGNATURE_DEF_METADATA_FIELD].Pack(
                handle.servable.signature_def_map())
            return response

    @_instrumented("session_run")
    def session_run(self, request: apis.SessionRunRequest) -> apis.SessionRunResponse:
        """Raw feeds/fetches on the imported graph (session_service.proto:11-44;
        RunOptions are carried but ignored, matching the proto's own note)."""
        with self.core.servable_handle(request.model_spec) as handle:
            runner = getattr(handle.servable, "session_runner", None)
            if runner is None:
                raise ServingError.unimplemented(
                    f"model {request.model_spec.name!r} does not support raw "
                    "SessionRun (no imported graph)")
            feeds = {nt.name: tensor_proto_to_ndarray(nt.tensor, writable=False)
                     for nt in request.feed}
            outs = runner.run(feeds, list(request.fetch), list(request.target))
            response = apis.SessionRunResponse()
            _effective_spec(response.model_spec, request.model_spec,
                            handle.id.version, "")
            for name, value in zip(request.fetch, outs):
                nt = response.tensor.add()
                nt.name = name
                nt.tensor.CopyFrom(ndarray_to_tensor_proto(
                    value, use_tensor_content=self._as_content))
            return response

    # -- ModelService --------------------------------------------------------

    def get_model_status(
        self, request: apis.GetModelStatusRequest
    ) -> apis.GetModelStatusResponse:
        if not request.model_spec.name:
            raise ServingError.invalid_argument("Missing ModelSpec.name")
        version = self.core.resolve_version(request.model_spec)
        response = apis.GetModelStatusResponse()
        response.model_version_status.extend(
            self.core.model_version_states(request.model_spec.name, version))
        return response

    def handle_reload_config(
        self, request: apis.ReloadConfigRequest
    ) -> apis.ReloadConfigResponse:
        response = apis.ReloadConfigResponse()
        try:
            self.core.reload_config(request.config)
        except ServingError as err:
            response.status.CopyFrom(err.to_proto())
        return response


def _assemble_classifications(result, outputs, n: int, class_labels) -> None:
    """Per-example Classifications from 'scores'/'classes' outputs
    (classifier.cc semantics: at least one of the two must exist; both must
    be [batch, k])."""
    scores = outputs.get(CLASSIFY_OUTPUT_SCORES)
    classes = outputs.get(CLASSIFY_OUTPUT_CLASSES)
    if scores is None and classes is None:
        raise ServingError.failed_precondition(
            "Classification signature produced neither scores nor classes")
    k = None
    for arr in (scores, classes):
        if arr is None:
            continue
        if arr.ndim == 1:
            arr = arr.reshape(n, -1)
        if arr.shape[0] != n:
            raise ServingError.internal(
                f"classification output batch {arr.shape[0]} != examples {n}")
        k = arr.shape[1] if k is None else k
    scores2 = None if scores is None else np.asarray(scores).reshape(n, -1)
    classes2 = None if classes is None else np.asarray(classes).reshape(n, -1)
    for i in range(n):
        classifications = result.classifications.add()
        width = (scores2 if scores2 is not None else classes2).shape[1]
        for j in range(width):
            cls = classifications.classes.add()
            if classes2 is not None:
                label = classes2[i, j]
                cls.label = label.decode() if isinstance(label, bytes) else str(label)
            elif class_labels is not None and j < len(class_labels):
                raw = class_labels[j]
                cls.label = raw.decode() if isinstance(raw, bytes) else str(raw)
            else:
                cls.label = str(j)
            if scores2 is not None:
                cls.score = float(scores2[i, j])


def _assemble_regressions(result, outputs, n: int) -> None:
    values = outputs.get(REGRESS_OUTPUTS)
    if values is None:
        raise ServingError.failed_precondition(
            "Regression signature produced no 'outputs' tensor")
    values = np.asarray(values).reshape(-1)
    if values.shape[0] != n:
        raise ServingError.internal(
            f"regression output count {values.shape[0]} != examples {n}")
    for i in range(n):
        result.regressions.add().value = float(values[i])


def _predict_log(request, response) -> apis.PredictionLog:
    log = apis.PredictionLog()
    log.predict_log.request.CopyFrom(request)
    log.predict_log.response.CopyFrom(response)
    return log


def _classify_log(request, response) -> apis.PredictionLog:
    log = apis.PredictionLog()
    log.classify_log.request.CopyFrom(request)
    log.classify_log.response.CopyFrom(response)
    return log


def _regress_log(request, response) -> apis.PredictionLog:
    log = apis.PredictionLog()
    log.regress_log.request.CopyFrom(request)
    log.regress_log.response.CopyFrom(response)
    return log
