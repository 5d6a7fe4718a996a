"""Servable: a loaded model version exposing named signatures.

Execution parity with the reference's Predict path
(servables/tensorflow/predict_util.cc:89-215): signature lookup with
"serving_default" default, alias-keyed inputs, output_filter validation, and
alias-keyed outputs. The execution engine is TPU-first rather than a Session
port:

 * every signature is a pure, jittable function dict->dict;
 * XLA needs static shapes, so batched signatures pad the leading dim up to
   a bucket (powers of two by default, or BatchingParameters
   allowed_batch_sizes — the batching_session.h:66-99 round-up rule) and
   jax.jit's shape-keyed compile cache holds one executable per bucket;
 * string/host signatures (XLA has no string kernels) run eagerly on numpy,
   exactly where the reference runs string ops on CPU;
 * results slice back to the true batch before marshalling.
"""

from __future__ import annotations

import threading

from dataclasses import dataclass, field as dc_field
from typing import Awaitable, Callable, Mapping, Optional, Sequence

import numpy as np

from min_tfs_client_tpu.observability import runtime, tracing
from min_tfs_client_tpu.protos import tf_graph_pb2, tfs_apis_pb2
from min_tfs_client_tpu.tensor.dtypes import DataType
from min_tfs_client_tpu.tensor.example_codec import FeatureSpec
from min_tfs_client_tpu.utils.status import ServingError

DEFAULT_SERVING_SIGNATURE_DEF_KEY = "serving_default"

PREDICT_METHOD_NAME = "tensorflow/serving/predict"
CLASSIFY_METHOD_NAME = "tensorflow/serving/classify"
REGRESS_METHOD_NAME = "tensorflow/serving/regress"

# Classification signature contract (signature_constants; classifier.cc
# validation): inputs alias "inputs", outputs "classes" and/or "scores".
CLASSIFY_INPUTS = "inputs"
CLASSIFY_OUTPUT_CLASSES = "classes"
CLASSIFY_OUTPUT_SCORES = "scores"
REGRESS_INPUTS = "inputs"
REGRESS_OUTPUTS = "outputs"

DEFAULT_BATCH_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256)


@dataclass(frozen=True)
class TensorSpec:
    """Dtype + shape template; None dims are polymorphic (batch / sequence).

    `unknown_rank` mirrors TensorShapeProto.unknown_rank: shape () then
    means "rank unknown" (shape inference failed at export), NOT a
    scalar — no shape checks apply, and batching must not assume the
    tensor is non-batch-major."""

    dtype: object
    shape: tuple[Optional[int], ...] = ()
    unknown_rank: bool = False

    def __post_init__(self):
        object.__setattr__(self, "dtype", DataType(self.dtype))

    def validate(self, arr: np.ndarray, alias: str) -> None:
        if self.unknown_rank:
            return
        if len(arr.shape) != len(self.shape):
            raise ServingError.invalid_argument(
                f"input {alias!r}: expected rank {len(self.shape)}, "
                f"got shape {arr.shape}")
        for i, (want, got) in enumerate(zip(self.shape, arr.shape)):
            if want is not None and want != got:
                raise ServingError.invalid_argument(
                    f"input {alias!r}: dim {i} expected {want}, got {got}")


@dataclass(frozen=True)
class SequenceBucketing:
    """Sequence-length bucketing: the time-axis analogue of batch
    buckets (SURVEY.md hard part (b); tpu_platform.proto
    SequenceBucketing). XLA needs static shapes, so a request's sequence
    dim rounds UP to the smallest allowed length and the jit cache holds
    one executable per (batch bucket x seq bucket). Results stay exact
    because padded positions carry mask/pad values the model already
    ignores (attention lengths mask padded keys; CLS/pooling reads real
    positions only)."""

    buckets: tuple
    # input alias -> pad scalar for the padded positions (ids -> pad id,
    # attention masks -> 0). Inputs not listed don't have a seq axis.
    pad_values: dict
    # output alias -> axis holding the seq dim, sliced back after fetch.
    output_seq_axes: dict = dc_field(default_factory=dict)
    axis: int = 1
    # Model-imposed ceiling on any bucket (e.g. a position-embedding
    # table's size). Survives dataclasses.replace, so a platform-config
    # override cannot silently push buckets past what the model can
    # actually embed.
    hard_max: Optional[int] = None
    # Aliases holding CONTENT tokens (ids): the platform config's
    # SequenceBucketing.pad_value may override their pad scalar; mask-like
    # aliases keep their structural pad (0) regardless.
    content_aliases: tuple = ()

    def __post_init__(self):
        # round_up assumes ascending ints; normalize here so every
        # constructor (exports, platform config, third-party build()
        # modules) gets the same contract.
        object.__setattr__(self, "buckets",
                           tuple(sorted(int(b) for b in self.buckets)))
        if not self.buckets:
            raise ValueError("SequenceBucketing needs at least one bucket")
        if self.hard_max is not None and self.buckets[-1] > self.hard_max:
            raise ValueError(
                f"sequence bucket {self.buckets[-1]} exceeds the model's "
                f"maximum supported length {self.hard_max}")

    def round_up(self, length: int) -> int:
        for bucket in self.buckets:
            if bucket >= length:
                return int(bucket)
        # Over-max lengths are rejected, not compiled: each distinct
        # length would JIT a fresh executable at serve time and grow the
        # cache without bound.
        raise ServingError.invalid_argument(
            f"sequence length {length} exceeds the largest allowed "
            f"bucket {self.buckets[-1]}")


@dataclass
class Signature:
    """One named entry point of a servable.

    When `params` is set, `fn(params, inputs)` and the param pytree is
    passed as a jit ARGUMENT — mandatory for sharded serving: a pytree
    merely closed over is inlined into the jaxpr as compile-time
    constants, which GSPMD is then free to replicate per shard, silently
    discarding the tensor-parallel placement (and baking a full copy of
    the weights into the executable). As arguments, the leaves'
    NamedShardings constrain the partitioner and the ICI collectives are
    emitted. `params=None` keeps the plain `fn(inputs)` closure contract
    (GraphDef-imported consts, host signatures, toy fixtures).
    """

    fn: Callable[..., dict[str, object]]
    inputs: dict[str, TensorSpec]
    outputs: dict[str, TensorSpec]
    # OPTIONAL wire inputs: accepted and validated when the request
    # carries them, never required. `inputs` stays all-mandatory (the
    # reference's contract, and what the batching merge relies on), so
    # an optional field must not live there — this is how a signature
    # grows a wire-compatible extension (e.g. decode_step's
    # `step_ordinal` at-most-once guard) without forking its name.
    # Host-only: device signatures jit over a fixed input tree, and the
    # batching merge has no notion of per-request-optional aliases.
    optional_inputs: Optional[dict[str, TensorSpec]] = None
    params: Optional[object] = dc_field(default=None, repr=False,
                                        compare=False)
    method_name: str = PREDICT_METHOD_NAME
    # Example parsing spec for Classify/Regress/MultiInference surfaces.
    feature_specs: Optional[dict[str, FeatureSpec]] = None
    # When the import rewrote a serialized-Example string input into its
    # parsed feature aliases (the ParseExample bypass), the ORIGINAL
    # alias: Predict requests feeding that single string tensor (which
    # work on the reference — the graph parses it) decode host-side into
    # the feature aliases instead of failing with unknown-alias.
    serialized_alias: Optional[str] = None
    # Host signatures run eagerly on numpy (string ops). Device signatures
    # are jitted with bucketed static shapes.
    on_host: bool = False
    # Leading dim of every input is a shared batch dim, paddable.
    batched: bool = True
    batch_buckets: Sequence[int] = DEFAULT_BATCH_BUCKETS
    # Optional class-id -> label vocabulary for classification outputs.
    class_labels: Optional[Sequence[bytes]] = None
    # Optional alias -> pad value for inputs whose width legitimately
    # varies per request (VarLen Example features decoded to the
    # SparseToDense dense view): the batching merge bridges differing
    # widths with THIS value — pad_ragged's first-element rule would
    # inject fake valid data.
    ragged_pad_values: Optional[dict[str, object]] = None
    # Optional alias -> value: the rows that pad a batch up to its bucket
    # are filled with this value instead of repeating row 0. For a model
    # to which such a row is valid AND costs nothing (models/mimo.py: a
    # row of pad_id is a prompt of length 0, which attention and the
    # expert layer skip): a repeated real row would be computed in full,
    # once for every padding row, and a program's time would follow the
    # length of whichever request came first.
    batch_pad_values: Optional[dict[str, object]] = None
    # Optional alias -> dtype map: cast these inputs on the HOST before the
    # device transfer. For inputs the model immediately casts down anyway
    # (f32 images -> bf16 convs), this halves host->HBM DMA bytes without
    # changing results — the cast happens once either side of the link.
    transfer_casts: Optional[dict[str, object]] = None
    # Optional sequence-length bucketing (see SequenceBucketing).
    sequence_bucketing: Optional[SequenceBucketing] = None
    # Imported host/device-partitioned signatures carry their
    # GraphPartition here (servables/partition.py) — fn routes through
    # partition.run; exposed for introspection/tests (interior jaxpr,
    # stage op lists).
    partition: Optional[object] = dc_field(default=None, repr=False,
                                           compare=False)
    # Optional jax.sharding.Mesh: formed batches are device_put with the
    # batch dim sharded over the mesh's "data" axis before execution
    # (TP'd params carry their own shardings; GSPMD emits the ICI
    # collectives). This is the batching->mesh handoff the reference's
    # batching_session.h:178-215 hands to Session::Run — here it lands on
    # the mesh (SURVEY.md §7.6).
    mesh: Optional[object] = dc_field(default=None, repr=False,
                                      compare=False)

    # Optional coroutine function, `fn` for a caller on an event loop:
    # the same outputs by the same work, but it AWAITS wherever `fn`
    # blocks its thread (a device round, a batch-mate, a lock held
    # through either) and never sleeps or waits otherwise. None, the
    # default, says there is no such form. The gRPC front end runs a
    # request whose signature has one on its event-loop thread, with no
    # thread hand-off (`arun`; docs/MIGRATING.md "A signature that can
    # await instead of block"). Host, unpartitioned signatures only.
    afn: Optional[Callable[..., Awaitable[dict[str, object]]]] = \
        dc_field(default=None, repr=False, compare=False)

    # Optional `on_answer(signature, outputs)`: called by the Predict
    # handlers with a request's OWN outputs (its rows, after a batch was
    # split) before they are serialised: where a model puts what its
    # program counted onto the request's trace (models/mimo.py:
    # `generate/route`). It returns nothing; the handler logs what it
    # raises and answers all the same.
    on_answer: Optional[Callable[["Signature", Mapping[str, np.ndarray]],
                                 None]] = \
        dc_field(default=None, repr=False, compare=False)

    # Optional `on_request(signature, inputs)`: `on_answer`'s twin for
    # what a model can count from the request alone: called by the
    # Predict handlers with a request's OWN decoded inputs, before it
    # runs (models/t5.py: `generate/cross`, the blocks of K and V its
    # input's length will make every decode step read). Logged and
    # answered all the same where it raises.
    on_request: Optional[Callable[["Signature", Mapping[str, np.ndarray]],
                                  None]] = \
        dc_field(default=None, repr=False, compare=False)

    # "model:version:signature", stamped by Servable.__init__ — keys the
    # compile-event ledger (observability/runtime.py).
    telemetry_label: str = ""

    _jitted: Callable | None = dc_field(default=None, repr=False, compare=False)
    # jitted() + the compile-ledger probe, wrapped ONCE (the hit path
    # must not allocate thunks); cleared wherever _jitted is cleared.
    _exec_wrapped: Callable | None = dc_field(default=None, repr=False,
                                              compare=False)
    _resolved_fn: Callable | None = dc_field(default=None, repr=False,
                                             compare=False)

    def __post_init__(self):
        if self.afn is not None and (
                not self.on_host or self.partition is not None
                or self.params is not None):
            raise ValueError(
                "afn is supported on host signatures that call fn(inputs) "
                "directly (no device dispatch, partition or params)")
        if self.optional_inputs:
            if not self.on_host or self.batched:
                raise ValueError(
                    "optional_inputs is supported on host, non-batched "
                    "signatures only (device jit and the batching merge "
                    "both assume a fixed mandatory input tree)")
            overlap = set(self.optional_inputs) & set(self.inputs)
            if overlap:
                raise ValueError(
                    f"optional_inputs {sorted(overlap)} duplicate "
                    "mandatory inputs")
        if self.transfer_casts:
            import jax.numpy as jnp

            if self.on_host:
                raise ValueError(
                    "transfer_casts applies to device signatures only; "
                    "an on_host signature never crosses the link")
            unknown = set(self.transfer_casts) - set(self.inputs)
            if unknown:
                raise ValueError(
                    f"transfer_casts aliases {sorted(unknown)} are not "
                    f"signature inputs {sorted(self.inputs)}")
            # Resolve dtype strings eagerly: a typo fails at build, not at
            # the first request.
            self.transfer_casts = {
                alias: jnp.dtype(dt)
                for alias, dt in self.transfer_casts.items()}

    def jitted(self) -> Callable:
        if self._jitted is None:
            import jax

            self._jitted = jax.jit(self._device_fn())
        return self._jitted

    def _device_fn(self) -> Callable:
        """self.fn, with int8 weights dequantized INSIDE the traced
        computation (XLA fuses the dequant into the consuming matmuls;
        HBM keeps the int8 residency). Resolved once — the quantization
        walk must not run per request."""
        if self._resolved_fn is not None:
            return self._resolved_fn
        fn = self.fn
        if self.params is not None:
            from min_tfs_client_tpu.models.quantize import (
                dequantize_tree,
                is_quantized,
            )

            if is_quantized(self.params):
                inner = fn

                def fn(params, arrays):
                    return inner(dequantize_tree(params), arrays)

        self._resolved_fn = fn
        return fn

    def _execute(self, arrays: dict) -> dict:
        # Compile-event ledger: the instrument_jit wrapper (cached next
        # to _jitted) detects cache misses via _cache_size()
        # (~0.04us/read) and builds the shape-bucket string only when a
        # compile actually happened; the hit path is one attribute read
        # and a direct call — no per-request thunks.
        fn = self._exec_wrapped
        if fn is None:
            fn = self._exec_wrapped = runtime.instrument_jit(
                self.telemetry_label or "unlabeled", self.jitted(),
                # the arrays dict is always the LAST positional arg
                bucket_fn=lambda args: runtime.shape_bucket(args[-1]))
        args = (arrays,) if self.params is None else (self.params, arrays)
        if self.mesh is None:
            return fn(*args)
        import jax

        # The ambient mesh is how ops/attention learns, at trace time,
        # which axes to split its Pallas kernel over (XLA partitions the
        # rest of the program by itself, a Mosaic kernel it cannot).
        with jax.set_mesh(self.mesh):
            return fn(*args)

    def _data_axis_size(self) -> int:
        from min_tfs_client_tpu.parallel.mesh import data_axis_size

        return data_axis_size(self.mesh)

    # -- execution -----------------------------------------------------------

    def validate(
        self,
        inputs: Mapping[str, np.ndarray],
        output_filter: Sequence[str] = (),
    ) -> dict[str, np.ndarray]:
        """Per-request checks, shared by the direct and batched paths (the
        batched path must reject a bad request BEFORE it joins a batch, or
        one caller's mistake fails every co-batched caller)."""
        if (self.serialized_alias is not None
                and self.feature_specs is not None
                and self.serialized_alias not in self.inputs
                and set(inputs) == {self.serialized_alias}):
            from min_tfs_client_tpu.tensor.example_codec import (
                ExampleDecodeError,
                decode_serialized,
            )

            arr = np.asarray(inputs[self.serialized_alias])
            if arr.dtype.kind in "OSU":
                try:
                    inputs = decode_serialized(arr, self.feature_specs)
                except ExampleDecodeError as exc:
                    raise ServingError.invalid_argument(str(exc))
        missing = set(self.inputs) - set(inputs)
        if missing:
            raise ServingError.invalid_argument(
                "Request inputs do not match required inputs for the "
                f"signature. Missing: {sorted(missing)}")
        extra = set(inputs) - set(self.inputs) \
            - set(self.optional_inputs or ())
        if extra:
            raise ServingError.invalid_argument(
                f"inputs contain aliases not in the signature: {sorted(extra)}")
        for name in output_filter:
            if name not in self.outputs:
                raise ServingError.invalid_argument(
                    f"output_filter name {name!r} is not in the signature "
                    f"outputs {sorted(self.outputs)}")
        arrays = {}
        to_check = dict(self.inputs)
        for alias, spec in (self.optional_inputs or {}).items():
            if alias in inputs:  # present: validated like any input
                to_check[alias] = spec
        for alias, spec in to_check.items():
            arr = np.asarray(inputs[alias])
            if spec.dtype.is_string:
                if arr.dtype.kind not in ("O", "S", "U"):
                    raise ServingError.invalid_argument(
                        f"input {alias!r}: expected string tensor, got {arr.dtype}")
            else:
                try:
                    arr = arr.astype(spec.dtype.numpy_dtype, copy=False)
                except (ValueError, TypeError) as exc:
                    raise ServingError.invalid_argument(
                        f"input {alias!r}: {exc}")
            spec.validate(arr, alias)
            arrays[alias] = arr
        self._validate_sparse_triples(arrays)
        return arrays

    def _validate_sparse_triples(self, arrays: dict) -> None:
        """Internal consistency of sparse-triple features, enforced
        BEFORE a request can join a batch (a malformed triple must fail
        alone with INVALID_ARGUMENT, never its co-batched callers deep
        inside a host kernel)."""
        for name in self.sparse_feature_names():
            ia, va, sa = (f"{name}#indices", f"{name}#values",
                          f"{name}#shape")
            if ia not in arrays or va not in arrays or sa not in arrays:
                continue
            idx = np.asarray(arrays[ia]).reshape(-1, 2)
            vals = np.asarray(arrays[va]).reshape(-1)
            shp = np.asarray(arrays[sa]).reshape(-1)
            if idx.shape[0] != vals.shape[0]:
                raise ServingError.invalid_argument(
                    f"sparse feature {name!r}: {idx.shape[0]} index rows "
                    f"vs {vals.shape[0]} values")
            if shp.size != 2 or (shp < 0).any():
                raise ServingError.invalid_argument(
                    f"sparse feature {name!r}: dense_shape must be two "
                    f"non-negative dims, got {shp.tolist()}")
            if idx.size and (
                    (idx < 0).any()
                    or (idx[:, 0] >= shp[0]).any()
                    or (idx[:, 1] >= shp[1]).any()):
                raise ServingError.invalid_argument(
                    f"sparse feature {name!r}: indices out of bounds for "
                    f"dense_shape {shp.tolist()}")

    def sparse_feature_names(self) -> list[str]:
        """Features decoded as TF sparse triples ('<f>#indices/#values/
        #shape' aliases) — the batching merge treats them specially."""
        return [n for n, s in (self.feature_specs or {}).items()
                if getattr(s, "sparse_triple", False)]

    def request_batch(self, arrays: Mapping[str, np.ndarray]) -> int:
        """Example count of a validated request. Dense aliases carry it
        as dim 0; sparse-triple aliases carry it in '<f>#shape'[0]
        (indices/values lead with nnz, not batch). Raises on
        inconsistency so a bad request fails alone."""
        sparse_aliases: set[str] = set()
        batches: set[int] = set()
        for name in self.sparse_feature_names():
            sparse_aliases.update(
                (f"{name}#indices", f"{name}#values", f"{name}#shape"))
            shp = arrays.get(f"{name}#shape")
            if shp is not None:
                batches.add(int(np.asarray(shp).reshape(-1)[0]))
        for alias, arr in arrays.items():
            if alias not in sparse_aliases and np.ndim(arr):
                batches.add(int(np.shape(arr)[0]))
        if len(batches) != 1:
            raise ServingError.invalid_argument(
                f"inconsistent batch dims across inputs: {sorted(batches)}")
        return batches.pop()

    def run(
        self,
        inputs: Mapping[str, np.ndarray],
        output_filter: Sequence[str] = (),
    ) -> dict[str, np.ndarray]:
        """Validate, pad, execute, slice, return alias-keyed outputs.

        Window-1 view of the async seam: dispatch + immediate result().
        The batching layer's in-flight window calls the two halves from
        different threads to overlap batch k+1's dispatch with batch k's
        outstanding D2H copies."""
        return self.dispatch(inputs, output_filter).result()

    async def arun(
        self,
        inputs: Mapping[str, np.ndarray],
        output_filter: Sequence[str] = (),
    ) -> dict[str, np.ndarray]:
        """`run` through `afn`, on an event loop: what `run` does for a
        host signature, with the wait awaited."""
        with tracing.span("serving/validate"):
            arrays = self.validate(inputs, output_filter)
        keys = list(output_filter) if output_filter else list(self.outputs)
        with tracing.span("host/execute"):
            outputs = await self.afn(arrays)
        self._check_produced(outputs, keys)
        return {k: np.asarray(outputs[k]) for k in keys}

    def dispatch(
        self,
        inputs: Mapping[str, np.ndarray],
        output_filter: Sequence[str] = (),
    ) -> "ExecutionHandle":
        """Validate, pad, place, and LAUNCH the execution, returning a
        completion handle instead of materialized outputs.

        For device signatures the jit dispatch is async on real
        accelerators and every requested output's device->host copy is
        already issued (copy_to_host_async) when this returns — the
        caller can dispatch more work while the transfers run; the
        handle's result() blocks only for materialization. Host
        signatures (string graphs, partitioned imports) have no async
        device seam of their own, so they execute here and the handle is
        already complete. Validation errors raise HERE, synchronously —
        a malformed request must fail before any batch-mate could be
        affected. result() is idempotent and may be called from another
        thread; trace spans recorded during it land on whatever trace is
        active on THAT thread (the batching completion thread activates
        the riders' fanout before materializing)."""
        with tracing.span("serving/validate"):
            arrays = self.validate(inputs, output_filter)
        keys = list(output_filter) if output_filter else list(self.outputs)

        if self.on_host:
            if self.partition is not None:
                # The partitioned path emits its own stage spans
                # (partition/pre, device/execute, device/device_to_host,
                # partition/post) — an enveloping host/execute span would
                # double-count them in stage sums and misfile device time
                # under a host stage.
                outputs = (self._device_fn()(self.params, arrays)
                           if self.params is not None else self.fn(arrays))
            else:
                with tracing.span("host/execute"):
                    outputs = (self._device_fn()(self.params, arrays)
                               if self.params is not None
                               else self.fn(arrays))
            self._check_produced(outputs, keys)
            # servelint: sync-ok host-path outputs are already numpy (the
            # name is shared with the device branch below)
            return CompletedExecution({k: np.asarray(outputs[k])
                                       for k in keys})

        true_seq = self._true_seq_len(arrays)
        outputs, batch = self._run_device(arrays)
        self._check_produced(outputs, keys)
        # Fetch ONLY the requested outputs (the executable computes them
        # all, but unfetched ones never cross the device->host link), in a
        # single overlapped round: async-copy every output now, leave the
        # materialization to result(). N sequential blocking fetches
        # collapse to one overlapped round of DMAs.
        pending = {k: outputs[k] for k in keys}
        # Issuing the copies is the dispatch half of the D2H stage (the
        # handle's result() records the blocking half under the same
        # name; stage_durations sums them) — at MB-scale outputs the
        # issue loop is real wall time and must stay inside a span or
        # the trace-coverage acceptance (>=90%) loses it.
        with tracing.span("device/device_to_host"):
            start_fetch(pending)
        return _DeviceExecution(self, pending, batch, true_seq)

    def _true_seq_len(self, arrays: Mapping[str, np.ndarray]) -> Optional[int]:
        sb = self.sequence_bucketing
        if sb is None:
            return None
        for alias in sb.pad_values:
            arr = arrays.get(alias)
            if arr is not None and arr.ndim > sb.axis:
                return arr.shape[sb.axis]
        return None

    def _slice_seq_outputs(self, result: dict[str, np.ndarray],
                           true_seq: Optional[int]) -> dict[str, np.ndarray]:
        sb = self.sequence_bucketing
        if sb is None or true_seq is None:
            return result
        for alias, axis in sb.output_seq_axes.items():
            arr = result.get(alias)
            if arr is not None and arr.ndim > axis \
                    and arr.shape[axis] != true_seq:
                index = [slice(None)] * arr.ndim
                index[axis] = slice(0, true_seq)
                result[alias] = arr[tuple(index)]
        return result

    def _pad_seq(self, arrays: dict[str, np.ndarray]) -> dict:
        sb = self.sequence_bucketing
        if sb is None:
            return arrays
        true_seq = self._true_seq_len(arrays)
        if true_seq is None:
            return arrays
        # Cross-input consistency FIRST: a mismatch must be
        # INVALID_ARGUMENT whether or not padding happens.
        for alias in sb.pad_values:
            arr = arrays.get(alias)
            if arr is not None and arr.ndim > sb.axis \
                    and arr.shape[sb.axis] != true_seq:
                raise ServingError.invalid_argument(
                    f"input {alias!r}: inconsistent sequence dim "
                    f"{arr.shape[sb.axis]} != {true_seq}")
        padded_seq = sb.round_up(true_seq)
        if padded_seq == true_seq:
            return arrays
        out = dict(arrays)
        for alias, pad_value in sb.pad_values.items():
            arr = out.get(alias)
            if arr is None or arr.ndim <= sb.axis:
                continue
            widths = [(0, 0)] * arr.ndim
            widths[sb.axis] = (0, padded_seq - true_seq)
            out[alias] = np.pad(arr, widths, constant_values=pad_value)
        return out

    def _check_produced(self, outputs, keys) -> None:
        for key in keys:
            if key not in outputs:
                raise ServingError.internal(
                    f"signature fn did not produce declared output {key!r}")

    def _run_device(
        self, arrays: dict[str, np.ndarray]
    ) -> tuple[dict[str, object], Optional[int]]:
        """Execute on device; returns (device outputs, true batch or None)."""
        if not self.batched or not arrays:
            with tracing.span("serving/pad"):
                arrays = self._cast_transfers(self._pad_seq(arrays))
            with tracing.span("device/host_to_device"):
                arrays = self._place(arrays)
            with tracing.span("device/execute"):
                return self._execute(arrays), None
        with tracing.span("serving/pad"):
            arrays = self._pad_seq(arrays)
            batch = next(iter(arrays.values())).shape[0]
            for alias, arr in arrays.items():
                if arr.shape[0] != batch:
                    raise ServingError.invalid_argument(
                        f"input {alias!r}: inconsistent batch dim "
                        f"{arr.shape[0]} != {batch}")
            # Cast BEFORE padding: the pad concat then moves half the bytes
            # and no second full-bucket copy is made.
            arrays = self._cast_transfers(arrays)
            padded_batch = self.round_up_batch(batch)
            if padded_batch != batch:
                arrays = {
                    alias: np.concatenate(
                        [arr, self._padding_rows(alias, arr,
                                                 padded_batch - batch)])
                    for alias, arr in arrays.items()
                }
        tracing.annotate(batch_size=batch, padding_bucket=padded_batch,
                         padding_waste_fraction=round(
                             (padded_batch - batch) / max(1, padded_batch),
                             4))
        with tracing.span("device/host_to_device"):
            if self.mesh is not None:
                arrays = self._shard_inputs(arrays)
            else:
                arrays = self._place(arrays)
        # Dispatch is async on real accelerators: this span is submit time;
        # the device wait shows up in device/device_to_host (and on the
        # XProf timeline when profiling).
        with tracing.span("device/execute"):
            return self._execute(arrays), batch

    def _padding_rows(self, alias: str, arr: np.ndarray,
                      count: int) -> np.ndarray:
        """The rows that fill a batch up to its bucket: a repeat of row 0
        (valid data keeps XLA out of NaN paths — the
        batching_session.h:94-99 trick) unless the signature names a
        value for the alias (`batch_pad_values`)."""
        pad = self.batch_pad_values
        if pad and alias in pad:
            return np.full((count, *arr.shape[1:]), pad[alias], arr.dtype)
        return np.repeat(arr[:1], count, axis=0)

    # Below this the device_put plumbing (~0.2 ms of pure Python)
    # outweighs what an explicit transfer can save. The threshold predates
    # the locally attached chip and has not been re-measured on it.
    _PLACE_MIN_BYTES = 256 * 1024

    @classmethod
    def _place(cls, arrays: dict[str, np.ndarray]) -> dict:
        """Explicit batched host->device transfer before dispatch. Passing
        LARGE ndarrays straight as jit args leaves the transfer to
        per-argument conversion inside the call, serialized with
        dispatch; one batched device_put of the whole input dict overlaps
        the DMAs. Small inputs skip the explicit hop — for them
        device_put's own Python overhead exceeds the transfer. Lands on
        the default device: meshed signatures go through _shard_inputs
        instead."""
        import jax

        dense = {k: v for k, v in arrays.items()
                 if getattr(v, "dtype", None) is not None
                 and v.dtype.kind not in "OSU"}
        # All-or-none on TOTAL bytes: the ~0.2 ms plumbing is per call,
        # and a placed/unplaced split would exclude arrays from the one
        # overlapped DMA while still paying the call.
        total_bytes = sum(v.nbytes for v in dense.values())
        if not dense or total_bytes < cls._PLACE_MIN_BYTES:
            return dict(arrays)
        placed = jax.device_put(dense)
        runtime.count_transfer("host_to_device", total_bytes)
        return {k: placed.get(k, arrays[k]) for k in arrays}

    def _cast_transfers(self, arrays: dict[str, np.ndarray]) -> dict:
        if not self.transfer_casts:
            return arrays
        return {
            alias: (arr.astype(self.transfer_casts[alias])
                    if alias in self.transfer_casts else arr)
            for alias, arr in arrays.items()
        }

    def _shard_inputs(self, arrays: dict[str, np.ndarray]) -> dict:
        """Place the padded batch on the mesh, dim 0 over the data axis
        (parallel.mesh.shard_batch; its pad-to-multiple is a no-op here
        since round_up_batch already chose an ndata-divisible bucket).
        GSPMD then propagates through the jit: TP'd params keep their
        load-time shardings, activations follow the data."""
        from min_tfs_client_tpu.parallel.mesh import shard_batch

        runtime.count_transfer("host_to_device", sum(
            getattr(v, "nbytes", 0) for v in arrays.values()))
        return shard_batch(self.mesh, arrays)

    def round_up_batch(self, batch: int) -> int:
        """Smallest allowed bucket >= batch; with a mesh, the bucket must
        also split evenly over the data axis (static per-shard shapes)."""
        ndata = self._data_axis_size()
        for bucket in self.batch_buckets:
            if bucket >= batch and bucket % ndata == 0:
                return bucket
        return -(-batch // ndata) * ndata  # next multiple of ndata

    # -- metadata ------------------------------------------------------------

    def to_signature_def(self) -> tf_graph_pb2.SignatureDef:
        sig = tf_graph_pb2.SignatureDef(method_name=self.method_name)
        for alias, spec in self.inputs.items():
            info = sig.inputs[alias]
            info.name = f"{alias}:0"
            info.dtype = spec.dtype.enum
            if spec.unknown_rank:
                info.tensor_shape.unknown_rank = True
            for d in spec.shape:
                info.tensor_shape.dim.add(size=-1 if d is None else d)
        for alias, spec in self.outputs.items():
            info = sig.outputs[alias]
            info.name = f"{alias}:0"
            info.dtype = spec.dtype.enum
            if spec.unknown_rank:
                info.tensor_shape.unknown_rank = True
            for d in spec.shape:
                info.tensor_shape.dim.add(size=-1 if d is None else d)
        return sig


class ExecutionHandle:
    """Completion handle for one dispatched execution.

    result() returns the alias-keyed numpy outputs, raising the
    execution's error instead when it failed; it is idempotent (the
    first call materializes, later calls replay the outcome) and safe to
    call from a different thread than dispatch()."""

    __slots__ = ("_result", "_error", "_done", "_lock")

    def __init__(self):
        self._result: dict | None = None
        self._error: Exception | None = None
        self._done = False                   # guarded_by: self._lock
        self._lock = threading.Lock()

    def _materialize(self) -> dict:  # pragma: no cover - abstract
        raise NotImplementedError

    def result(self) -> dict:
        # Locked: "safe to call from a different thread" must include
        # two threads calling result() concurrently — an unlocked _done
        # check would let both run _materialize, and _DeviceExecution's
        # loser would fetch from the already-freed _pending.
        with self._lock:
            if not self._done:
                try:
                    self._result = self._materialize()
                except Exception as exc:  # delivered to every result() call
                    self._error = exc
                self._done = True
        if self._error is not None:
            raise self._error
        return self._result


class CompletedExecution(ExecutionHandle):
    """A handle whose work finished at dispatch time (host signatures,
    simulated executions in tests)."""

    __slots__ = ()

    def __init__(self, outputs: dict):
        super().__init__()
        self._result = outputs
        self._done = True


class _DeviceExecution(ExecutionHandle):
    """Pending device outputs: dispatch launched the executable and
    issued every D2H copy; materialization (np.asarray) happens in
    result() on whichever thread drives completion."""

    __slots__ = ("_signature", "_pending", "_batch", "_true_seq")

    def __init__(self, signature: "Signature", pending: dict,
                 batch: Optional[int], true_seq: Optional[int]):
        super().__init__()
        self._signature = signature
        self._pending = pending
        self._batch = batch
        self._true_seq = true_seq

    def _materialize(self) -> dict:
        with tracing.span("device/device_to_host"):
            result = fetch_outputs(self._pending, self._batch)
        self._pending = None  # free the device refs promptly
        return self._signature._slice_seq_outputs(result, self._true_seq)


def start_fetch(outputs: Mapping[str, object]) -> None:
    """Issue the device->host copy of every jax.Array output WITHOUT
    materializing: the transfers run while the caller does other work
    (the dispatch half of fetch_outputs' overlapped round)."""
    for value in outputs.values():
        start = getattr(value, "copy_to_host_async", None)
        if start is not None:
            try:
                start()
            except Exception:  # servelint: fallback-ok async start is an
                pass  # optimization; fetch_outputs does the sync copy


def fetch_outputs(outputs: Mapping[str, object],
                  batch: Optional[int] = None) -> dict[str, np.ndarray]:
    """Device->host for a dict of outputs as ONE overlapped round.

    Issues copy_to_host_async on every jax.Array first, then materializes;
    the transfers run concurrently, so the wall cost is max(transfer) plus
    one link round trip instead of a sequential sum. `batch` slices padded
    leading dims back to the true request size (host-side view, no copy).
    """
    start_fetch(outputs)
    result = {}
    fetched_bytes = 0
    for key, value in outputs.items():
        # servelint: sync-ok THE sanctioned device->host materialization:
        # every async copy above is already in flight, so this wall-clock
        # cost is max(transfer), not a serialized sum
        arr = np.asarray(value)
        fetched_bytes += arr.nbytes  # pre-slice: what crossed the link
        if batch is not None and arr.ndim:
            arr = arr[:batch]
        result[key] = arr
    runtime.count_transfer("device_to_host", fetched_bytes)
    return result


class Servable:
    """One loaded model version: named signatures + metadata."""

    def __init__(
        self,
        name: str,
        version: int,
        signatures: Mapping[str, Signature],
        *,
        hbm_estimate_bytes: int = 0,
        warmup_records: Sequence[object] = (),
    ):
        if not signatures:
            raise ValueError("servable must expose at least one signature")
        self.name = name
        self.version = version
        self.signatures = dict(signatures)
        for key, sig in self.signatures.items():
            if not sig.telemetry_label:
                sig.telemetry_label = f"{name}:{version}:{key}"
        self.hbm_estimate_bytes = hbm_estimate_bytes
        self.warmup_records = list(warmup_records)
        # Compiled union executables for MultiInference, keyed by the
        # sorted signature-key tuple.
        self._union_jits: dict[tuple, Callable] = {}

    def signature(self, name: str = "") -> Signature:
        key = name or DEFAULT_SERVING_SIGNATURE_DEF_KEY
        sig = self.signatures.get(key)
        if sig is None:
            raise ServingError.invalid_argument(
                f"Serving signature key \"{key}\" not found.")
        return sig

    def signature_def_map(self) -> tfs_apis_pb2.SignatureDefMap:
        out = tfs_apis_pb2.SignatureDefMap()
        for key, sig in self.signatures.items():
            out.signature_def[key].CopyFrom(sig.to_signature_def())
        return out

    def can_run_union(self, keys: Sequence[str]) -> bool:
        """True when the named signatures can evaluate in ONE device
        execution: all device-side, batched, and agreeing on inputs (the
        single-Session::Run precondition of multi_inference.cc:44-77 —
        there, one graph; here, one fused jit)."""
        try:
            sigs = [self.signature(k) for k in keys]
        except ServingError:  # servelint: status-ok capability probe —
            # "unknown signature" IS the False answer; the caller falls
            # back to per-task runs and the missing-signature error
            # surfaces there, typed.
            return False
        first = sigs[0]
        return all(
            not s.on_host and s.batched
            and s.inputs == first.inputs
            and s.mesh is first.mesh
            # run_union applies the FIRST signature's casts/buckets to the
            # shared inputs, so fusion is only sound when they agree —
            # otherwise fused vs per-task results could differ.
            and s.transfer_casts == first.transfer_casts
            and tuple(s.batch_buckets) == tuple(first.batch_buckets)
            for s in sigs)

    def run_union(self, keys: Sequence[str],
                  inputs: Mapping[str, np.ndarray]) -> dict[str, dict]:
        """Evaluate several signatures over shared inputs as ONE device
        dispatch + ONE overlapped fetch; returns {key: {alias: ndarray}}.

        The TPU-native equivalent of the reference's union Session::Run
        (multi_inference.cc:31-77): instead of fetching the union of
        tensor names from one graph, the signatures' pure functions fuse
        into one jitted callable (XLA dedupes the shared trunk — e.g.
        BERT classify+regress share every layer but the head)."""
        keys = list(keys)
        sigs = {k: self.signature(k) for k in keys}
        first = sigs[keys[0]]
        arrays = first.validate(inputs)
        batch = next(iter(arrays.values())).shape[0] if arrays else None

        union_key = tuple(sorted(keys))
        fused = self._union_jits.get(union_key)
        if fused is None:
            import jax

            fn_map = {k: s._device_fn() for k, s in sigs.items()}

            def union_fn(params_map, arrays):
                return {
                    k: (fn_map[k](params_map[k], arrays)
                        if params_map[k] is not None else fn_map[k](arrays))
                    for k in fn_map
                }

            fused = jax.jit(union_fn)
            self._union_jits[union_key] = fused

        arrays = first._cast_transfers(arrays)  # before pad: half the bytes
        if batch is not None:
            padded = first.round_up_batch(batch)
            if padded != batch:
                arrays = {
                    alias: np.concatenate(
                        [arr, np.repeat(arr[:1], padded - batch, axis=0)])
                    for alias, arr in arrays.items()
                }
        if first.mesh is not None:
            arrays = first._shard_inputs(arrays)
        else:
            arrays = Signature._place(arrays)
        params_map = {k: s.params for k, s in sigs.items()}
        nested = runtime.ledgered_call(
            f"{self.name}:{self.version}:union[{'+'.join(keys)}]",
            fused, lambda: fused(params_map, arrays), arrays)
        # Single overlapped fetch across every task's outputs.
        flat = {(k, alias): v for k, outs in nested.items()
                for alias, v in outs.items()}
        fetched = fetch_outputs(flat, batch)
        result: dict[str, dict] = {k: {} for k in keys}
        for (k, alias), arr in fetched.items():
            result[k][alias] = arr
        return result

    def unload(self) -> None:
        """Drop jit caches so XLA executables free their HBM."""
        self._union_jits.clear()
        for sig in self.signatures.values():
            sig._jitted = None
            sig._exec_wrapped = None
            if sig.partition is not None:
                sig.partition.unload()


def attach_mesh(signatures, mesh, *, only_if_absent: bool = False):
    """Attach a device mesh to every batched signature with device work
    so formed batches execute data-parallel over it. Pure host (string)
    signatures and unbatched signatures are untouched — but an on_host
    signature carrying a GraphPartition has a jitted dense interior, and
    THAT is meshed (partition.attach_mesh: batch-DP over "data", large
    interior weights TP over "model"), so imported SavedModels use the
    whole mesh like native families (VERDICT r5 Missing #2).

    `signatures` may be a Servable, a name->Signature mapping, or an
    iterable of Signatures (the single attach rule for platforms.py and
    models/export.py). only_if_absent keeps a mesh already chosen at
    export time (TP geometry) over a server-level default. Drops the jit
    cache on change; idempotent; returns its argument."""
    if mesh is None:
        return signatures
    if isinstance(signatures, Servable):
        sigs = list(signatures.signatures.values())
    elif isinstance(signatures, Mapping):
        sigs = list(signatures.values())
    else:
        sigs = list(signatures)
    for sig in sigs:
        if not sig.batched:
            continue
        part = sig.partition
        if sig.on_host and part is None:
            continue  # no device work anywhere: nothing to place
        if only_if_absent and (sig.mesh is not None
                               or (part is not None
                                   and part.mesh is not None)):
            continue
        if part is not None:
            part.attach_mesh(mesh)
            # The signature-level mesh makes round_up_batch (and with it
            # the batching front-end's bucket accounting) agree with the
            # partition's data-axis-divisible padding.
            sig.mesh = mesh
            continue
        if sig.mesh is not mesh:
            sig.mesh = mesh
            sig._jitted = None  # re-trace with the new placement
            sig._exec_wrapped = None
    return signatures
