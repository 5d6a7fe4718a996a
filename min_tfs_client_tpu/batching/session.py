"""Batching execution wrapper: merge -> pad -> execute once -> split.

Parity with BatchingSession (batching/batching_session.{h,cc}):

 * callers block on their task until the batch containing it completes;
 * tasks merge along dim 0; the merged batch rounds UP to the smallest
   allowed_batch_sizes entry >= total (batching_session.h:66-99) — on TPU
   this is also the compile-bucket rule, so the jit cache holds exactly one
   executable per allowed size;
 * padding rows repeat real data (first task's rows), not zeros (h:94-99);
 * optional variable-length padding: ragged non-batch dims pad to the
   per-batch max with the tensor's pad value (h:100-132 semantics);
 * oversized requests split into chunks (RunOptions-free equivalent of
   enable_large_batch_splitting).
"""

from __future__ import annotations

import collections
import threading
import time

import numpy as np

from min_tfs_client_tpu.observability import tracing
from min_tfs_client_tpu.batching.scheduler import (
    BatchQueue,
    BatchTask,
    QueueOptions,
    SharedBatchScheduler,
)
from min_tfs_client_tpu.protos import tfs_config_pb2
from min_tfs_client_tpu.servables.servable import Signature
from min_tfs_client_tpu.utils.status import ServingError

BatchingParameters = tfs_config_pb2.BatchingParameters


def params_from_proto(proto: BatchingParameters) -> dict:
    return {
        "max_batch_size": proto.max_batch_size.value or 32,
        "batch_timeout_s": (proto.batch_timeout_micros.value or 0) / 1e6,
        "max_enqueued_batches": proto.max_enqueued_batches.value or 64,
        "allowed_batch_sizes": list(proto.allowed_batch_sizes),
        "pad_variable_length_inputs": proto.pad_variable_length_inputs,
    }


def resolve_allowed_batch_sizes(
    signature: Signature, params: dict) -> tuple[int, ...]:
    """The allowed-sizes rule shared by the runner and pre-warmup bucket
    setup: explicit allowed_batch_sizes (last entry must equal
    max_batch_size, main.cc rule), else the signature's default buckets
    clipped to max_batch_size.

    With a data-parallel mesh attached (native signatures' `mesh`, or a
    partitioned import's interior mesh), padding buckets must split
    evenly over the data axis — every shard keeps a static shape — so
    indivisible entries are dropped (round_up_batch would skip them
    anyway; keeping them would make warmup prime executables that can
    never serve). When the survivors no longer cover max_batch_size
    (e.g. [8, 12] on an 8-way axis), the next axis multiple at/above it
    is appended — the scheduler still forms batches up to
    max_batch_size, and THAT bucket is where they pad, so warmup must
    prime it."""
    max_batch_size = params.get("max_batch_size", 32)
    allowed_batch_sizes = params.get("allowed_batch_sizes")
    if allowed_batch_sizes:
        allowed = sorted(int(v) for v in allowed_batch_sizes)
        if allowed[-1] != max_batch_size:
            raise ServingError.invalid_argument(
                f"allowed_batch_sizes last entry {allowed[-1]} must equal "
                f"max_batch_size {max_batch_size}")
    else:
        allowed = [s for s in signature.batch_buckets
                   if s <= max_batch_size] or [max_batch_size]
        if allowed[-1] != max_batch_size:
            allowed.append(max_batch_size)
    ndata = signature._data_axis_size()
    if ndata > 1:
        allowed = [b for b in allowed if b % ndata == 0]
        if not allowed or allowed[-1] < max_batch_size:
            allowed.append(-(-max_batch_size // ndata) * ndata)
    return tuple(allowed)


def apply_batch_buckets(servable, params: BatchingParameters | dict) -> dict:
    """Set every batched device signature's compile buckets from the
    batching config. Runs BEFORE warmup so warmup primes exactly the
    executables that will serve (not the default power-of-two ladder).
    Returns the normalized params dict for maybe_wrap_servable."""
    if isinstance(params, BatchingParameters):
        params = params_from_proto(params)
    for signature in servable.signatures.values():
        if signature.batched and (not signature.on_host
                                  or signature.partition is not None):
            # Host signatures with a partitioned device interior bucket
            # their interior jit cache on the allowed sizes too.
            signature.batch_buckets = resolve_allowed_batch_sizes(
                signature, params)
    return params


def pad_to_max(arrays: list[np.ndarray], axis: int,
               pad_value) -> list[np.ndarray]:
    """Pad one axis to the per-batch max with a FIXED pad value (the
    sequence-bucketing merge rule; contrast pad_ragged's first-element
    fill, which is wrong for attention masks)."""
    target = max(a.shape[axis] for a in arrays)
    out = []
    for a in arrays:
        if a.shape[axis] != target:
            widths = [(0, 0)] * a.ndim
            widths[axis] = (0, target - a.shape[axis])
            a = np.pad(a, widths, constant_values=pad_value)
        out.append(a)
    return out


def _slice_sparse_triple(arrays: dict, chunk: dict, name: str,
                         start: int, end: int) -> None:
    """Replace the naive row slices of a sparse triple in `chunk` with
    the correct example-range restriction: rows in [start, end) keep
    their values with re-based row ids; the chunk dense_shape is
    [end-start, chunk's own max width]."""
    ia, va, sa = f"{name}#indices", f"{name}#values", f"{name}#shape"
    if ia not in arrays:
        return
    idx = np.asarray(arrays[ia], dtype=np.int64).reshape(-1, 2)
    rows = idx[:, 0] if idx.size else np.zeros(0, np.int64)
    keep = (rows >= start) & (rows < end)
    sub = idx[keep].copy()
    if sub.size:
        sub[:, 0] -= start
    chunk[ia] = sub
    chunk[va] = np.asarray(arrays[va])[keep]
    # Carry the request's DECLARED width into every chunk — recomputing it
    # from the surviving indices shrinks width-dependent outputs
    # (SparseToDense views, indicator columns) when the declared width
    # exceeds max-index+1, and can differ per chunk, breaking the final
    # concatenate. The merge path preserves declared widths; chunking
    # must agree with it.
    width = int(np.asarray(arrays[sa]).reshape(-1)[1])
    chunk[sa] = np.asarray([end - start, width], np.int64)


def pad_ragged(arrays: list[np.ndarray]) -> list[np.ndarray]:
    """Pad non-batch dims to the per-batch max (batching_util.cc semantics:
    rank 1-6, pad value = tensor's first element)."""
    ranks = {a.ndim for a in arrays}
    if len(ranks) != 1:
        raise ServingError.invalid_argument(
            f"cannot merge tensors of different ranks {sorted(ranks)}")
    rank = ranks.pop()
    if rank < 1:
        raise ServingError.invalid_argument("cannot batch rank-0 tensors")
    max_dims = [max(a.shape[d] for a in arrays) for d in range(rank)]
    out = []
    for a in arrays:
        pad = [(0, 0)] + [(0, max_dims[d] - a.shape[d]) for d in range(1, rank)]
        if any(p[1] for p in pad):
            fill = a.reshape(-1)[0] if a.size else 0
            a = np.pad(a, pad, constant_values=fill)
        out.append(a)
    return out


class _InFlightWindow:
    """Bounded dispatch->materialize pipeline for one batching queue.

    A host-device link carries more with requests in flight than
    serialized (by how much on the local v5e is not measured yet); this
    window converts that capacity server-side: the batch worker
    acquire()s a slot, dispatches the batch (device work + D2H copies
    launched, nothing materialized), and submit()s the completion; a
    single completion thread materializes batches strictly in dispatch
    order, so per-caller response ordering is preserved and each batch's
    error stays its own. depth 1 is never constructed — window=1 keeps
    the synchronous path bit-for-bit.
    """

    CLOSE_DRAIN_TIMEOUT_S = 30.0

    def __init__(self, depth: int, name: str):
        self.depth = int(depth)
        self.name = name
        self._cv = threading.Condition()
        self._in_flight = 0          # guarded_by: self._cv
        self._pending: collections.deque = (
            collections.deque())     # guarded_by: self._cv
        self._closed = False         # guarded_by: self._cv
        self._thread: threading.Thread | None = None  # guarded_by: self._cv
        self._dispatched = 0         # guarded_by: self._cv
        self._overlapped = 0         # guarded_by: self._cv
        with _windows_lock:
            _windows[name] = self

    # -- scheduler-thread side ----------------------------------------------

    def acquire(self) -> bool:
        """Take an in-flight slot, blocking while the window is full —
        the backpressure that bounds device-queue depth and host memory
        pinned by outstanding batches. Returns False when the window
        closed instead: the worker already owns a popped batch at that
        point, and erroring it would break the shutdown contract (the
        pre-window code executed it synchronously — the caller must do
        the same, not fail its riders)."""
        with self._cv:
            while self._in_flight >= self.depth and not self._closed:
                # Timed + loop-on-predicate (servelint DL003): a
                # completion thread that died un-notified must not park
                # the batch worker forever with a popped batch in hand.
                self._cv.wait(timeout=0.1)
            if self._closed:
                return False
            self._in_flight += 1
            self._dispatched += 1
            if self._in_flight > 1:
                self._overlapped += 1
            self._publish_locked()
            return True

    def release(self) -> None:
        """Give a slot back without a completion (dispatch failed)."""
        with self._cv:
            self._in_flight -= 1
            self._publish_locked()
            self._cv.notify_all()

    def submit(self, complete) -> None:
        """Queue a completion callable; the completion thread runs them
        FIFO (dispatch order) and releases the slot after each."""
        with self._cv:
            self._pending.append(complete)
            try:
                if self._thread is None or not self._thread.is_alive():
                    self._thread = threading.Thread(
                        target=self._drain, name=f"inflight-{self.name}",
                        daemon=True)
                    self._thread.start()
            except BaseException:
                # Thread.start() can fail under thread exhaustion. The
                # completion MUST leave the queue before the caller's
                # unwind re-attaches the tasks and releases the slot —
                # a later drain popping it would double-complete the
                # batch and double-release, driving _in_flight negative
                # (close() would then spin forever). Still holding _cv,
                # so no drain thread can have popped it in between.
                self._pending.pop()
                raise
            self._cv.notify_all()

    def depth_now(self) -> int:
        with self._cv:
            return self._in_flight

    def stats(self) -> dict:
        with self._cv:
            return {
                "window": self.depth,
                "in_flight": self._in_flight,
                "dispatched": self._dispatched,
                "overlapped": self._overlapped,
                "overlap_ratio": round(
                    self._overlapped / self._dispatched, 4)
                if self._dispatched else 0.0,
            }

    # -- completion thread ---------------------------------------------------

    def _drain(self) -> None:
        while True:
            with self._cv:
                while not self._pending and not self._closed:
                    # servelint: blocks completion worker loop — parking
                    # on an empty window is its contract; close() wakes
                    # it with notify_all and it exits on the drained check
                    self._cv.wait()
                if not self._pending:
                    return  # closed and drained
                complete = self._pending.popleft()
            try:
                complete()
            except Exception:  # servelint: fallback-ok _complete_batch
                pass  # delivers its own errors to the riders; the drain
                # thread must survive
            finally:
                self.release()

    def close(self) -> None:
        """Stop accepting dispatches and DRAIN: every batch already in
        flight still materializes and its callers get real results —
        shutdown must never turn dispatched work into errors. The wait
        is BOUNDED (CLOSE_DRAIN_TIMEOUT_S): a wedged device must not
        hold unload hostage (the pre-window code never blocked unload
        on an executing batch). Past the deadline close() returns while
        the daemon completion thread keeps draining, so late answers
        still deliver to their callers."""
        deadline = time.monotonic() + self.CLOSE_DRAIN_TIMEOUT_S
        with self._cv:
            self._closed = True
            self._cv.notify_all()
            thread = self._thread
            while (self._pending or self._in_flight) \
                    and time.monotonic() < deadline:
                self._cv.wait(timeout=0.1)
            drained = not self._pending and not self._in_flight
        if drained and thread is not None:
            # Joining a known-wedged thread would just re-pay the
            # deadline; it is a daemon and keeps delivering on its own.
            thread.join(timeout=5.0)
        with _windows_lock:
            if _windows.get(self.name) is self:
                del _windows[self.name]

    def _publish_locked(self) -> None:
        """Gauges published under self._cv so depths cannot race out of
        order and stick stale (the BatchQueue depth-gauge rule)."""
        try:
            from min_tfs_client_tpu.server import metrics

            metrics.safe_set(metrics.in_flight_batches, self._in_flight,
                             self.name)
            metrics.safe_set(metrics.pipeline_overlap_occupancy,
                             self._in_flight / self.depth, self.name)
        except Exception:  # pragma: no cover - metrics must not break serving
            pass


_windows_lock = threading.Lock()
_windows: dict[str, _InFlightWindow] = {}      # guarded_by: _windows_lock


def pipeline_snapshot() -> dict:
    """Per-queue in-flight window stats for /monitoring/runtime."""
    with _windows_lock:
        windows = list(_windows.values())
    return {w.name: w.stats() for w in windows}


class BatchedSignatureRunner:
    """Drop-in .run() for a Signature, coalescing concurrent callers."""

    def __init__(
        self,
        signature: Signature,
        scheduler: SharedBatchScheduler,
        *,
        name: str = "signature",
        max_batch_size: int = 32,
        batch_timeout_s: float = 0.0,
        max_enqueued_batches: int = 64,
        allowed_batch_sizes: list[int] | None = None,
        pad_variable_length_inputs: bool = False,
        max_in_flight_batches: int = 1,
    ):
        allowed = list(resolve_allowed_batch_sizes(signature, {
            "max_batch_size": max_batch_size,
            "allowed_batch_sizes": allowed_batch_sizes,
        }))
        self.signature = signature
        # Captured BEFORE maybe_wrap_servable replaces signature.run with
        # runner.run — _process must execute the real signature, not re-enter
        # the queue.
        self._inner_run = signature.run
        # The async seam (dispatch is an instance attr when a test/bench
        # wrapper shimmed it, the class method otherwise): the windowed
        # path launches batch k+1 through this while batch k's D2H copies
        # are still outstanding.
        self._inner_dispatch = signature.dispatch
        window = max(1, int(max_in_flight_batches or 1))
        # window == 1 keeps the synchronous path — not a window of depth
        # 1 but literally the pre-window code, the default-compat
        # guarantee docs/MIGRATING.md documents.
        self._window = _InFlightWindow(window, name) if window > 1 else None
        # Outputs that can never split along dim 0: requests fetching one
        # of them bypass the queue (run() routes them direct), so callers
        # that filter them OUT still batch.
        self._non_batch_major = frozenset(
            declared_non_batch_major_outputs(signature))
        # Bucket the jit cache exactly on the allowed sizes.
        signature.batch_buckets = tuple(allowed)
        self._allowed = allowed
        self._pad_ragged = pad_variable_length_inputs
        self._scheduler = scheduler
        self._max_batch_size = max_batch_size
        self._queue: BatchQueue = scheduler.add_queue(
            name,
            QueueOptions(max_batch_size=max_batch_size,
                         batch_timeout_s=batch_timeout_s,
                         max_enqueued_batches=max_enqueued_batches),
            self._process,
        )

    # -- caller side ---------------------------------------------------------

    def run(self, inputs, output_filter=()) -> dict[str, np.ndarray]:
        if not self.signature.batched:
            return self._inner_run(inputs, output_filter)
        if self._non_batch_major and (
                not output_filter
                or any(k in self._non_batch_major for k in output_filter)):
            # The effective fetch set includes a declared non-batch-major
            # output (scalar / fixed-leading-dim): a merged batch could
            # never split it back per caller, so this request executes
            # direct. Requests whose output_filter excludes those outputs
            # keep the batched path — the filter union in _process_batch
            # then never fetches them.
            return self._inner_run(inputs, output_filter)
        # Reject bad requests BEFORE they join a batch: a malformed request
        # must fail alone with INVALID_ARGUMENT, never its batch-mates.
        arrays = self.signature.validate(inputs, output_filter)
        # Per-request sequence rounding happens CALLER-SIDE so every task
        # in a batch is already at an allowed length with the signature's
        # own pad values (mask padded with 0, not pad_ragged's
        # first-element rule); the merge then only bridges bucket gaps.
        true_seq = self.signature._true_seq_len(arrays)
        arrays = self.signature._pad_seq(arrays)
        # Example count, not dim 0 of everything: sparse-triple aliases
        # lead with nnz and carry the batch in '<f>#shape'[0].
        n = self.signature.request_batch(arrays)
        if n == 0:
            raise ServingError.invalid_argument("empty batch")
        if n >= self._max_batch_size:
            return self.signature._slice_seq_outputs(
                self._run_oversized(arrays, output_filter, n), true_seq)
        # Hand the request's trace across the thread boundary: the
        # scheduler thread accounts queue-wait / merge / execute back to
        # this caller (and annotates the queue it rode and the depth it
        # saw at enqueue).
        trace = tracing.current_trace()
        if trace is not None:
            # request_examples is THIS caller's real-example count — the
            # numerator of its amortized device-execute share (the
            # batch-level batch_size/padding_bucket annotations are
            # fanned out identically to every rider; without the
            # per-rider size, cost attribution could not split the
            # merged wall; observability/costs.py).
            trace.annotate(queue=self._queue.name,
                           queue_depth=self._queue.depth(),
                           request_examples=n)
        task = BatchTask(inputs=arrays, size=n,
                         output_filter=tuple(output_filter), trace=trace)
        # Pre-enqueue faultpoint: a delay here widens the batching
        # window artificially (merge storms), a typed error exercises
        # the fail-alone-before-joining-a-batch contract.
        from min_tfs_client_tpu.robustness import faults

        faults.point("batch.enqueue", queue=self._queue.name, size=n)
        self._scheduler.schedule(self._queue, task)
        # servelint: blocks delivery is the scheduler's hard contract —
        # the worker's finally and the window's bounded close() drain
        # both set done for every scheduled task, errors included; a
        # timeout here would have nothing sound to do on expiry
        task.done.wait()
        if task.error is not None:
            raise task.error
        keys = list(output_filter) if output_filter else list(self.signature.outputs)
        result = {k: task.outputs[k] for k in keys}
        # Slice seq-axis outputs back to THIS caller's true length (the
        # batch may have executed at a larger co-batched bucket).
        return self.signature._slice_seq_outputs(result, true_seq)

    def _run_oversized(self, arrays, output_filter, n):
        """Split a large request into max-size chunks run directly."""
        outs: list[dict] = []
        for start in range(0, n, self._max_batch_size):
            end = min(start + self._max_batch_size, n)
            chunk = {k: a[start:end] for k, a in arrays.items()}
            for name in self.signature.sparse_feature_names():
                _slice_sparse_triple(arrays, chunk, name, start, end)
            outs.append(self._inner_run(chunk, output_filter))
        return {k: np.concatenate([o[k] for o in outs]) for k in outs[0]}

    # -- scheduler side ------------------------------------------------------

    def _process(self, batch: list[BatchTask]) -> None:
        # Account the queue to every rider, then activate a fanout so the
        # merged execution's spans (merge, execute, and the inner
        # signature's pad/device stages) land on each rider's trace.
        now = time.perf_counter()
        traces = [t.trace for t in batch if t.trace is not None]
        for task in batch:
            if task.trace is not None:
                task.trace.add_span("batching/queue_wait",
                                    task.enqueue_pc, now)
        with tracing.activate(tracing.fanout(traces)):
            self._process_batch(batch)

    def _process_batch(self, batch: list[BatchTask]) -> None:
        sizes = [t.size for t in batch]
        total = sum(sizes)
        merged = {}
        sb = self.signature.sequence_bucketing
        # Sparse-triple features merge as SparseTensors: indices rows
        # offset by each task's example offset, values concatenate,
        # dense_shape becomes [total, max width] — exactly the triple a
        # single decode of the concatenated Examples would produce.
        sparse_handled: set[str] = set()
        for name in self.signature.sparse_feature_names():
            ia, va, sa = (f"{name}#indices", f"{name}#values",
                          f"{name}#shape")
            if ia not in batch[0].inputs:
                continue
            idx_cols, off = [], 0
            for t, size in zip(batch, sizes):
                idx = np.array(t.inputs[ia], dtype=np.int64, copy=True)
                if idx.size:
                    idx[:, 0] += off
                idx_cols.append(idx.reshape(-1, 2))
                off += size
            merged[ia] = np.concatenate(idx_cols, axis=0)
            merged[va] = np.concatenate(
                [t.inputs[va] for t in batch], axis=0)
            width = max((int(np.asarray(t.inputs[sa]).reshape(-1)[1])
                         for t in batch), default=0)
            merged[sa] = np.asarray([total, width], np.int64)
            sparse_handled.update((ia, va, sa))
        with tracing.span("batching/merge"):
            rpv = self.signature.ragged_pad_values
            for alias in batch[0].inputs:
                if alias in sparse_handled:
                    continue
                columns = [t.inputs[alias] for t in batch]
                if sb is not None and alias in sb.pad_values:
                    # Tasks arrive at (different) allowed bucket lengths;
                    # bridge to the batch max with the signature's OWN pad
                    # value — a mask padded by pad_ragged's first-element
                    # rule (1) would un-mask the padding.
                    columns = pad_to_max(columns, sb.axis,
                                         sb.pad_values[alias])
                elif rpv and alias in rpv:
                    # VarLen dense views: widths differ per request by
                    # construction; bridge with the feature's own pad
                    # (SparseToDense default), never first-element fill.
                    columns = pad_to_max(columns, 1, rpv[alias])
                elif self._pad_ragged:
                    columns = pad_ragged(columns)
                else:
                    shapes = {c.shape[1:] for c in columns}
                    if len(shapes) != 1:
                        raise ServingError.invalid_argument(
                            f"input {alias!r}: ragged non-batch dims "
                            f"{sorted(shapes)} need "
                            "pad_variable_length_inputs=true")
                merged[alias] = np.concatenate(columns, axis=0)

        # Execute once; the inner run rounds total up to the allowed bucket
        # and pads with repeated real rows. Fetch the union of the tasks'
        # output_filters: outputs no caller asked for never cross the
        # device->host link (any task without a filter wants everything).
        filters = [t.output_filter for t in batch]
        if any(not f for f in filters):
            union: tuple = ()
        else:
            union = tuple(sorted({name for f in filters for name in f}))
        if self._window is not None and self._dispatch_windowed(
                batch, sizes, total, merged, union):
            return
        # No window, or the window closed between this batch's pop and
        # its dispatch (unload racing the worker): execute synchronously
        # — the popped batch's riders get real results either way.
        with tracing.span("batching/execute"):
            outputs = self._inner_run(merged, union)

        self._record_batch_telemetry(total, len(batch))
        self._split_outputs(batch, sizes, total, outputs)

    def _record_batch_telemetry(self, total: int, n_tasks: int) -> None:
        try:
            from min_tfs_client_tpu.server import metrics

            bucket = self.signature.round_up_batch(total)
            metrics.batch_padding_ratio.observe(
                bucket / max(1, total), self._queue.name)
            # Occupancy + padding waste of THIS formed batch (the queue
            # telemetry Orca/Clipper-style policies key on).
            metrics.safe_set(metrics.batch_occupancy,
                             total / max(1, bucket), self._queue.name)
            if bucket > total:
                metrics.padding_wasted_examples.increment(
                    self._queue.name, by=bucket - total)
            tracing.annotate(batch_size=total, padding_bucket=bucket,
                             batch_tasks=n_tasks,
                             padding_waste_fraction=round(
                                 (bucket - total) / max(1, bucket), 4))
            # Flight-recorder ring: batch formations are exactly the
            # "what was happening" context a post-mortem needs around an
            # INTERNAL error. Scheduler thread, not the caller path.
            from min_tfs_client_tpu.observability import flight_recorder

            flight_recorder.record(
                "batch", queue=self._queue.name, tasks=n_tasks,
                examples=total, bucket=bucket)
        except Exception:  # pragma: no cover - metrics must not break serving
            pass

    def _split_outputs(self, batch: list[BatchTask], sizes: list[int],
                       total: int, outputs: dict) -> None:
        # Outputs must be batch-major to split back to callers — the
        # reference's batching_session errors on a mismatched 0th dim
        # rather than handing each caller an arbitrary slice (imported
        # host graphs can emit batch-free outputs, e.g. a vocab tensor).
        for k, v in outputs.items():
            if np.ndim(v) == 0 or np.shape(v)[0] != total:
                raise ServingError.internal(
                    f"batched output {k!r} has leading dim "
                    f"{np.shape(v)[0] if np.ndim(v) else 'scalar'}, "
                    f"expected the merged batch {total}; this signature "
                    "cannot be served through the batching front-end")
        offset = 0
        for task, size in zip(batch, sizes):
            task.outputs = {k: v[offset:offset + size]
                            for k, v in outputs.items()}
            offset += size

    # -- in-flight window (window > 1) ---------------------------------------

    def _dispatch_windowed(self, batch: list[BatchTask], sizes: list[int],
                           total: int, merged: dict, union: tuple) -> bool:
        """Scheduler-thread half of the pipelined path: take a window
        slot, LAUNCH the merged batch (device dispatch + D2H copies in
        flight), and hand materialization to the completion thread. The
        worker is then free to merge and dispatch the next batch while
        this one's transfers run. Returns False (batch untouched) when
        the window closed under the worker — the caller executes the
        batch synchronously instead of failing its riders."""
        window = self._window
        with tracing.span("batching/in_flight_wait"):
            if not window.acquire():
                return False
        try:
            with tracing.span("batching/dispatch"):
                handle = self._inner_dispatch(merged, union)
        except BaseException:
            # Dispatch failed on THIS batch: give the slot back and let
            # the worker's error path fail exactly these tasks.
            window.release()
            raise
        self._record_batch_telemetry(total, len(batch))
        tracing.annotate(in_flight_depth=window.depth_now(),
                         in_flight_window=window.depth)
        # Hand ownership to the completion thread. detached is flipped
        # before submit so the worker's finally can never complete a task
        # the window owns; until submit returns the window cannot have
        # run the completion, so the unwind below cannot race it.
        for task in batch:
            task.detached = True
        try:
            window.submit(lambda: self._complete_batch(
                batch, sizes, total, handle))
        except BaseException:
            for task in batch:
                task.detached = False
            window.release()
            raise
        return True

    def _complete_batch(self, batch: list[BatchTask], sizes: list[int],
                        total: int, handle) -> None:
        """Completion-thread half: materialize one batch's outputs and
        deliver them (or its error — isolated to THIS batch) to every
        rider. The riders' traces cross the thread boundary through the
        BatchTask mechanism, never ambient contextvars."""
        traces = [t.trace for t in batch if t.trace is not None]
        try:
            with tracing.activate(tracing.fanout(traces)):
                with tracing.span("batching/materialize"):
                    outputs = handle.result()
                self._split_outputs(batch, sizes, total, outputs)
        except Exception as exc:  # noqa: BLE001 - delivered to the riders
            for task in batch:
                task.error = exc
        finally:
            for task in batch:
                task.done.set()

    def close(self) -> None:
        self._scheduler.remove_queue(self._queue)
        if self._window is not None:
            # Drain AFTER the queue closed: no new dispatches can arrive,
            # and every batch already in flight still delivers.
            self._window.close()


def declared_non_batch_major_outputs(signature: Signature) -> list[str]:
    """Output aliases whose DECLARED spec can never split along dim 0:
    rank-0, or a concrete (non-None) leading dim. Requests fetching one
    of these execute direct rather than batched (ADVICE round-5:
    auto-fallback instead of unservable-under-batching). Unknown-rank
    specs (imported graphs whose shape inference failed) are NOT treated
    as non-batch-major — their () shape means "don't know", and the
    runtime split check still protects the batch."""
    return sorted(
        alias for alias, spec in signature.outputs.items()
        if not getattr(spec, "unknown_rank", False)
        and (not spec.shape or spec.shape[0] is not None))


def maybe_wrap_servable(servable, params: BatchingParameters | dict | None,
                        scheduler: SharedBatchScheduler | None = None):
    """Wrap every batched device signature of a servable with a batching
    runner (the WrapSessionForBatching step of bundle creation,
    saved_model_bundle_factory.cc:119-181). Returns the servable, mutated."""
    if params is None:
        return servable
    if isinstance(params, BatchingParameters):
        params = params_from_proto(params)
    scheduler = scheduler or _default_scheduler()
    # Batching is signature-level in the reference, not device-conditional
    # (batching_session.h:47-99): host signatures coalesce too — merge ->
    # run ONCE -> split amortizes the per-request Python, and a
    # partitioned import additionally amortizes its interior dispatch.
    for key, signature in servable.signatures.items():
        if not signature.batched:
            continue
        non_batch_major = declared_non_batch_major_outputs(signature)
        if non_batch_major and \
                len(non_batch_major) == len(signature.outputs):
            # EVERY declared output is non-batch-major (scalars, vocab
            # tensors, fixed-row tables): no request could ever split
            # from a merged batch, so skip the queue entirely — direct
            # (unbatched) execution instead of unservable-under-batching.
            # Mixed signatures ARE wrapped: the runner routes each
            # request by its effective fetch set (see run()), so callers
            # filtering the non-batch-major outputs away still batch.
            # Undeclared violations still surface per-batch in
            # _process_batch.
            continue
        runner = BatchedSignatureRunner(
            signature, scheduler,
            name=f"{servable.name}:{servable.version}:{key}",
            max_batch_size=params.get("max_batch_size", 32),
            batch_timeout_s=params.get("batch_timeout_s", 0.0),
            max_enqueued_batches=params.get("max_enqueued_batches", 64),
            allowed_batch_sizes=params.get("allowed_batch_sizes"),
            pad_variable_length_inputs=params.get(
                "pad_variable_length_inputs", False),
            max_in_flight_batches=params.get("max_in_flight_batches", 1),
        )
        # Replace the signature's run with the batched path, keep a handle
        # for unload-time queue removal.
        signature.run = runner.run  # type: ignore[method-assign]
        runners = getattr(servable, "_batch_runners", [])
        runners.append(runner)
        servable._batch_runners = runners
    _chain_unload(servable)
    return servable


def _default_scheduler() -> SharedBatchScheduler:
    from min_tfs_client_tpu.batching.scheduler import global_scheduler

    return global_scheduler()


def _chain_unload(servable) -> None:
    original_unload = servable.unload

    def unload():
        for runner in getattr(servable, "_batch_runners", []):
            runner.close()
        servable._batch_runners = []
        original_unload()

    servable.unload = unload  # type: ignore[method-assign]
