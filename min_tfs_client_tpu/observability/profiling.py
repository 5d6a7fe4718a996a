"""servespy: the continuous sampling-profiler plane.

Every other observability plane says WHICH STAGE is slow (trace stage
tables, cost vectors, SLO burn); this one says WHICH CODE. A
`StackSampler` ticker walks `sys._current_frames()` at a deliberately
low default rate (~11 Hz — prime-ish, so it cannot phase-lock with
10ms/100ms periodic work) and folds every sample into bounded per-thread
frame trees, with two attribution joins layered on top:

 * thread-name -> subsystem: TH002 forces `name=` on every thread spawn,
   so the sample's thread name maps to the owning subsystem (batch
   workers, the serial-device tick batcher, in-flight completion
   threads, the tracing drain, the router's aio event loop, the
   membership poller, ...);
 * sample -> active serving stage: while the sampler runs it arms the
   tracing layer's active-stage registry (tracing.track_stages), so each
   sample of a request-carrying thread lands in the stage
   (`serving/deserialize`, `device/execute`, ...) that thread was inside
   at that instant.

Served at `/monitoring/profile` on both REST backends and the router
(server/rest.py `_profile_reply`, shared by router/proxy.py):

 * bare GET        — JSON summary: top self/total frames per thread,
                     per stage, and the subsystem sample mix;
 * ?format=collapsed — folded stacks (`thread;frame;frame count`), the
                     Brendan Gregg format speedscope / flamegraph.pl
                     load directly;
 * ?seconds=N[&hz=H] — on-demand high-rate window capture sampled in the
                     calling HTTP worker thread (the continuous ticker
                     keeps running untouched);
 * ?diff=1&seconds=N — differential view: the capture window's per-frame
                     self shares against the rolling baseline ring, top
                     risers first (the "what changed just now" view);
 * ?device=1&seconds=N — programmatic `jax.profiler.trace` capture to
                     --profile_dir (the XPlane dump the chip-truth
                     campaign replays), with the stage spans mirrored
                     into it, `host_clock.json` and `host_track.json`
                     beside it (`traced_capture`). jax is imported inside that
                     function only — this module stays stdlib+tracing so
                     the jax-free router imports it.

Bias caveats (documented in docs/OBSERVABILITY.md): the sampler sees
only threads registered with the CPython interpreter, samples land on
GIL-holding code proportionally more than on C code that releases the
GIL, and an 11 Hz rate needs O(minutes) to resolve frames below ~1% of
a core. Treat the numbers as shares, not absolute CPU seconds.
"""

from __future__ import annotations

import collections
import contextlib
import json
import os
import sys
import threading
import time

from min_tfs_client_tpu.observability import tracing

# Default continuous rate: low enough to be always-on (<0.5% of a core
# with tens of threads), odd so it cannot phase-lock with round-number
# periodic work. `--profile_sampler_hz 0` disables.
DEFAULT_HZ = 11.0
# On-demand capture default: high enough to resolve a short window,
# again deliberately off any round number.
CAPTURE_HZ = 97.0
CAPTURE_MAX_SECONDS = 30.0
MAX_STACK_DEPTH = 80
MAX_TREE_NODES = 20000

# ---------------------------------------------------------------------------
# Thread-name -> subsystem attribution. TH002 (analysis/threads.py)
# forces name= on every package thread spawn, so these prefixes ARE the
# package's thread inventory; stdlib defaults (MainThread, Dummy-N for
# C-spawned threads entering Python, ThreadPoolExecutor-*) cover the
# rest.

_SUBSYSTEM_EXACT = {
    "MainThread": "main",
    "watchdog-ticker": "watchdog",
    "trace-metrics-export": "tracing-drain",
    "stream-batch-drive": "streaming",
    "sigterm-drain": "lifecycle",
    "rest-server": "rest-frontend",
    "router-rest-server": "rest-frontend",
    "router-aio-data-plane": "router-event-loop",
    "grpc-aio-loop": "grpc-event-loop",
    "router-membership-poll": "membership-poller",
    "router-fleet-scrape": "fleet-scraper",
    "fs-source-poll": "model-discovery",
    "config-file-poll": "config-poll",
    "flight-recorder-dump": "flight-recorder",
    "avmanager-tick": "model-lifecycle",
    "profile-sampler": "profiler",
}

_SUBSYSTEM_PREFIX = (
    ("batch-worker-", "batch-workers"),
    ("adaptive-batch-", "batch-workers"),
    ("serial-device-batch-", "tick-batcher"),
    ("inflight-", "completion"),
    ("router-grpc", "router-data-plane"),
    ("router-probe", "router-probes"),
    ("servable-load", "model-lifecycle"),
    ("servable-unload", "model-lifecycle"),
    ("storm-", "compile-storm"),
    ("ThreadPoolExecutor", "grpc-handlers"),
    ("Dummy-", "foreign"),
)


def subsystem_for(thread_name: str) -> str:
    """Owning subsystem for a thread name ("other" when unrecognized)."""
    sub = _SUBSYSTEM_EXACT.get(thread_name)
    if sub is not None:
        return sub
    for prefix, name in _SUBSYSTEM_PREFIX:
        if thread_name.startswith(prefix):
            return name
    # grpc names its poll threads for their target functions:
    # "Thread-1 (_serve)" (grpc.server(): the router's threaded plane)
    # and "Thread-1 (_poll_wrapper)" (grpc.aio's completion-queue
    # poller, one a process). Not ours to rename, but always present.
    if thread_name.startswith("Thread-") and thread_name.endswith(
            ("(_serve)", "(_poll_wrapper)")):
        return "grpc-server"
    return "other"


# ---------------------------------------------------------------------------
# Frame keys: "func (pkg/relative/path.py:firstlineno)". firstlineno,
# not the executing line — py-spy convention, so one function is ONE
# frame regardless of which line the sample landed on. Keyed by code
# object: formatting happens once per function, not once per sample.

# servelint: lock-ok per-code-object memo dict; single-key get/set are
# GIL-atomic and a racing double-format of the same code object writes
# the identical string
_KEY_CACHE: dict = {}
_KEY_CACHE_MAX = 8192


def _short_path(path: str) -> str:
    path = path.replace("\\", "/")
    parts = path.split("/")
    for anchor in ("min_tfs_client_tpu", "site-packages"):
        if anchor in parts:
            i = parts.index(anchor)
            if anchor == "site-packages":
                i += 1
            return "/".join(parts[i:])
    return "/".join(parts[-2:])


def _frame_key(code) -> str:
    key = _KEY_CACHE.get(code)
    if key is None:
        key = (f"{code.co_name} "
               f"({_short_path(code.co_filename)}:{code.co_firstlineno})")
        # The folded format splits frames on ';' — a pathological name
        # must not be able to fabricate stack levels.
        key = key.replace(";", ":").replace("\n", " ")
        if len(_KEY_CACHE) >= _KEY_CACHE_MAX:  # pragma: no cover - bound
            _KEY_CACHE.clear()
        _KEY_CACHE[code] = key
    return key


def _walk_stack(frame) -> list[str]:
    """Frame -> root-first key list, leaf last, depth-capped at the ROOT
    end (the leaf carries self attribution and must survive)."""
    keys: list[str] = []
    while frame is not None and len(keys) < MAX_STACK_DEPTH:
        keys.append(_frame_key(frame.f_code))
        frame = frame.f_back
    if frame is not None:
        keys.append("(stack-truncated)")
    keys.reverse()
    return keys


class _Node:
    __slots__ = ("self_n", "total_n", "children")

    def __init__(self):
        self.self_n = 0
        self.total_n = 0
        self.children: dict[str, _Node] = {}


class FrameTree:
    """Bounded trie of sampled stacks + exact per-frame counters.

    NOT internally locked: every instance is either private to one
    capture thread or guarded by its owning StackSampler's lock. The
    trie renders the folded/flame view; `key_self`/`key_total` are exact
    per-frame counters kept alongside (total counted once per sample via
    the stack's key SET, so recursion cannot double-bill a frame).
    """

    __slots__ = ("samples", "truncated", "key_self", "key_total",
                 "_root", "_nodes", "_max_nodes")

    def __init__(self, max_nodes: int = MAX_TREE_NODES):
        self.samples = 0
        self.truncated = 0  # samples that overflowed the node budget
        self.key_self: collections.Counter = collections.Counter()
        self.key_total: collections.Counter = collections.Counter()
        self._root = _Node()
        self._nodes = 0
        self._max_nodes = max_nodes

    def fold(self, stack: list[str]) -> None:
        if not stack:
            return
        self.samples += 1
        self.key_self[stack[-1]] += 1
        for key in set(stack):
            self.key_total[key] += 1
        node = self._root
        node.total_n += 1
        for key in stack:
            child = node.children.get(key)
            if child is None:
                if self._nodes >= self._max_nodes:
                    # Node budget exhausted: absorb the remainder into
                    # one overflow leaf so memory stays bounded while
                    # the counters above remain exact.
                    self.truncated += 1
                    sink = node.children.get("(tree-truncated)")
                    if sink is None:
                        sink = node.children["(tree-truncated)"] = _Node()
                    sink.total_n += 1
                    sink.self_n += 1
                    return
                child = node.children[key] = _Node()
                self._nodes += 1
            child.total_n += 1
            node = child
        node.self_n += 1

    def collapsed_into(self, out: dict, prefix: str) -> None:
        """Accumulate `prefix;frame;... -> self count` folded lines."""
        stack = [(self._root, prefix)]
        while stack:
            node, path = stack.pop()
            if node.self_n:
                out[path] = out.get(path, 0) + node.self_n
            for key, child in node.children.items():
                stack.append((child, f"{path};{key}"))

    def top(self, counter: collections.Counter, limit: int) -> list[dict]:
        n = self.samples or 1
        return [{"frame": k, "samples": c, "pct": round(100.0 * c / n, 1)}
                for k, c in counter.most_common(limit)]

    def summary(self, limit: int = 10) -> dict:
        return {
            "samples": self.samples,
            "top_self": self.top(self.key_self, limit),
            "top_total": self.top(self.key_total, limit),
        }


# ---------------------------------------------------------------------------
# The sampler


class _Fold:
    """One accumulation surface: per-thread trees, per-stage trees, the
    subsystem mix, and the attribution counters. Private to a capture
    thread or guarded by the owning sampler's lock (see FrameTree)."""

    __slots__ = ("threads", "stages", "subsystems", "samples",
                 "attributed", "ticks")

    def __init__(self):
        self.threads: dict[str, FrameTree] = {}
        self.stages: dict[str, FrameTree] = {}
        self.subsystems: collections.Counter = collections.Counter()
        self.samples = 0
        self.attributed = 0
        self.ticks = 0

    def sample_once(self, exclude_idents: frozenset) -> None:
        """Walk every interpreter thread once and fold. The three reads
        (frames, names, stages) are each GIL-atomic snapshots; a thread
        that exits between them costs one unattributed sample at most."""
        frames = sys._current_frames()
        names = {t.ident: t.name for t in threading.enumerate()}
        stages = tracing.active_stages()
        self.ticks += 1
        for ident, frame in frames.items():
            if ident in exclude_idents:
                continue
            name = names.get(ident)
            label = name if name is not None else f"unnamed-{ident}"
            stack = _walk_stack(frame)
            tree = self.threads.get(label)
            if tree is None:
                tree = self.threads[label] = FrameTree()
            tree.fold(stack)
            self.subsystems[subsystem_for(label)] += 1
            self.samples += 1
            if name is not None:
                self.attributed += 1
            stage = stages.get(ident)
            if stage is not None:
                stree = self.stages.get(stage)
                if stree is None:
                    stree = self.stages[stage] = FrameTree()
                stree.fold(stack)

    def merged_self(self) -> collections.Counter:
        merged: collections.Counter = collections.Counter()
        for tree in self.threads.values():
            merged.update(tree.key_self)
        return merged

    def collapsed(self) -> str:
        out: dict = {}
        for label, tree in sorted(self.threads.items()):
            tree.collapsed_into(out, label)
        return "".join(f"{path} {count}\n"
                       for path, count in sorted(out.items()))

    def summary(self, limit: int = 10) -> dict:
        attributed_pct = (100.0 * self.attributed / self.samples
                          if self.samples else 100.0)
        return {
            "samples": self.samples,
            "ticks": self.ticks,
            "attributed_samples": self.attributed,
            "attributed_pct": round(attributed_pct, 2),
            "threads": {
                label: dict(tree.summary(limit),
                            subsystem=subsystem_for(label))
                for label, tree in sorted(self.threads.items())},
            "subsystems": dict(self.subsystems),
            "stages": {stage: tree.summary(limit)
                       for stage, tree in sorted(self.stages.items())},
        }


class StackSampler:
    """The continuous ticker + baseline ring.

    Lifecycle: start() spawns the daemon ticker and arms the tracing
    layer's active-stage registry; stop() disarms it and JOINS the
    ticker (bounded), so the LeakWitness sees a clean start->stop pair.
    """

    def __init__(self, hz: float = DEFAULT_HZ,
                 baseline_bucket_s: float = 30.0,
                 baseline_buckets: int = 10):
        self.hz = float(hz)
        self._lock = threading.Lock()
        self._fold = _Fold()                     # guarded_by: self._lock
        self._thread = None                      # guarded_by: self._lock
        self._stop = threading.Event()
        self._started_wall = 0.0                 # guarded_by: self._lock
        # Rolling baseline ring for ?diff=1: every bucket_s the ticker
        # pushes the per-frame self-count DELTA since the previous push,
        # so the ring always holds the last ~bucket_s*buckets seconds.
        self._bucket_s = float(baseline_bucket_s)
        self._baseline: collections.deque = collections.deque(
            maxlen=max(1, int(baseline_buckets)))  # guarded_by: self._lock
        self._baseline_prev: collections.Counter = (
            collections.Counter())               # guarded_by: self._lock
        self._baseline_t = 0.0                   # guarded_by: self._lock

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> None:
        if self.hz <= 0:
            return
        with self._lock:
            if self._thread is not None and self._thread.is_alive():
                return
            self._stop.clear()
            tracing.track_stages(True)
            self._baseline_t = time.monotonic()
            self._started_wall = time.time()
            self._thread = threading.Thread(  # servelint: owns thread
                target=self._run, name="profile-sampler", daemon=True)
            self._thread.start()

    def stop(self) -> None:
        with self._lock:
            thread = self._thread
            self._thread = None
        self._stop.set()
        if thread is not None and thread.is_alive():
            # Bounded (servelint DL003): the ticker wakes at least every
            # 1/hz seconds; 2s covers the slowest configurable rate the
            # flag validation allows plus scheduler noise.
            thread.join(timeout=2.0)
        tracing.track_stages(False)

    def running(self) -> bool:
        with self._lock:
            return self._thread is not None and self._thread.is_alive()

    def _run(self) -> None:
        interval = 1.0 / self.hz
        exclude = frozenset((threading.get_ident(),))
        while not self._stop.wait(interval):
            with self._lock:
                self._fold.sample_once(exclude)
                now = time.monotonic()
                if now - self._baseline_t >= self._bucket_s:
                    self._push_baseline_locked(now)

    def _push_baseline_locked(self, now: float) -> None:
        merged = self._fold.merged_self()
        delta = merged - self._baseline_prev
        self._baseline.append({
            "self": dict(delta),
            "samples": sum(delta.values()),
        })
        self._baseline_prev = merged
        self._baseline_t = now

    # -- views ---------------------------------------------------------------

    def summary(self, limit: int = 10) -> dict:
        with self._lock:
            body = self._fold.summary(limit)
            running = self._thread is not None and self._thread.is_alive()
            baseline_buckets = len(self._baseline)
            started = self._started_wall
        body["sampler"] = {
            "running": running,
            "hz": self.hz,
            "started_at": started,
            "uptime_s": round(time.time() - started, 1) if started else 0.0,
            "baseline_buckets": baseline_buckets,
            "baseline_bucket_s": self._bucket_s,
        }
        return body

    def collapsed(self) -> str:
        with self._lock:
            return self._fold.collapsed()

    def top_hot_frames(self, limit: int = 3) -> list[dict]:
        """Process-wide hottest self frames — the watchdog's alert join.
        Excludes the profiler's own bookkeeping so an alert never blames
        the messenger."""
        with self._lock:
            merged: collections.Counter = collections.Counter()
            total = 0
            for label, tree in self._fold.threads.items():
                if subsystem_for(label) == "profiler":
                    continue
                merged.update(tree.key_self)
                total += tree.samples
        if not total:
            return []
        return [{"frame": k, "samples": c,
                 "pct": round(100.0 * c / total, 1)}
                for k, c in merged.most_common(limit)]

    def baseline_counts(self) -> tuple[collections.Counter, int]:
        """Merged rolling-ring per-frame self counts (falls back to the
        cumulative fold while the ring is still empty — early uptime)."""
        with self._lock:
            if self._baseline:
                merged: collections.Counter = collections.Counter()
                total = 0
                for bucket in self._baseline:
                    merged.update(bucket["self"])
                    total += bucket["samples"]
                return merged, total
            merged = self._fold.merged_self()
            return merged, sum(merged.values())

    # -- on-demand windows ---------------------------------------------------

    def capture(self, seconds: float, hz: float | None = None) -> _Fold:
        """High-rate window sampled in the CALLING thread (an HTTP
        worker): the continuous ticker keeps its own cadence. Arms the
        stage registry for the window when the ticker isn't running."""
        seconds = min(max(float(seconds), 0.05), CAPTURE_MAX_SECONDS)
        rate = min(max(float(hz or CAPTURE_HZ), 1.0), 999.0)
        armed_here = False
        if not tracing.stage_tracking():
            tracing.track_stages(True)
            armed_here = True
        fold = _Fold()
        exclude = {threading.get_ident()}
        with self._lock:
            if self._thread is not None and self._thread.ident:
                exclude.add(self._thread.ident)
        exclude_f = frozenset(exclude)
        interval = 1.0 / rate
        deadline = time.monotonic() + seconds
        try:
            while time.monotonic() < deadline:
                fold.sample_once(exclude_f)
                time.sleep(interval)
        finally:
            if armed_here and not self.running():
                tracing.track_stages(False)
        return fold

    def capture_summary(self, seconds: float, hz: float | None = None,
                        limit: int = 10) -> dict:
        fold = self.capture(seconds, hz)
        body = fold.summary(limit)
        body["capture"] = {"seconds": min(max(float(seconds), 0.05),
                                          CAPTURE_MAX_SECONDS),
                           "hz": min(max(float(hz or CAPTURE_HZ), 1.0),
                                     999.0)}
        return body

    def capture_collapsed(self, seconds: float,
                          hz: float | None = None) -> str:
        return self.capture(seconds, hz).collapsed()

    def diff(self, seconds: float, hz: float | None = None,
             limit: int = 20) -> dict:
        """Capture-window per-frame self SHARES minus the rolling
        baseline's — "what is hot right now that wasn't before". Shares,
        not raw counts: the window and the baseline ran for different
        durations at different rates."""
        base_counts, base_total = self.baseline_counts()
        fold = self.capture(seconds, hz)
        win_counts = fold.merged_self()
        win_total = sum(win_counts.values())
        deltas = []
        for key in set(win_counts) | set(base_counts):
            win_share = (win_counts.get(key, 0) / win_total
                         if win_total else 0.0)
            base_share = (base_counts.get(key, 0) / base_total
                          if base_total else 0.0)
            delta = win_share - base_share
            if abs(delta) < 1e-9:
                continue
            deltas.append({
                "frame": key,
                "window_pct": round(100.0 * win_share, 2),
                "baseline_pct": round(100.0 * base_share, 2),
                "delta_pct": round(100.0 * delta, 2),
            })
        deltas.sort(key=lambda d: -abs(d["delta_pct"]))
        return {
            "window_samples": win_total,
            "baseline_samples": base_total,
            "risers": [d for d in deltas if d["delta_pct"] > 0][:limit],
            "fallers": [d for d in deltas if d["delta_pct"] < 0][:limit],
        }


# ---------------------------------------------------------------------------
# Module singleton (configure/start/stop — the watchdog's pattern) +
# the endpoint-facing facade.

_singleton_lock = threading.Lock()
_sampler: StackSampler | None = None             # guarded_by: _singleton_lock
_profile_dir = ""                                # guarded_by: _singleton_lock


def configure(hz: float = DEFAULT_HZ, profile_dir: str = "",
              baseline_bucket_s: float = 30.0,
              baseline_buckets: int = 10) -> None:
    """(Re)build the process sampler. Stops a running one first —
    boot-time reconfiguration, not hot swap. hz <= 0 leaves the process
    without a continuous sampler (on-demand capture still works through
    the default instance get() lazily builds)."""
    global _sampler, _profile_dir
    with _singleton_lock:
        old, _sampler = _sampler, None
        _profile_dir = profile_dir or ""
    if old is not None:
        old.stop()
    sampler = StackSampler(hz=hz, baseline_bucket_s=baseline_bucket_s,
                           baseline_buckets=baseline_buckets)
    with _singleton_lock:
        _sampler = sampler


def get() -> StackSampler:
    """The process sampler (lazily built at the default rate, NOT
    started — serving binaries start it at boot)."""
    global _sampler
    with _singleton_lock:
        if _sampler is None:
            _sampler = StackSampler()
        return _sampler


def start() -> None:
    get().start()


def stop() -> None:
    with _singleton_lock:
        sampler = _sampler
    if sampler is not None:
        sampler.stop()


def running() -> bool:
    with _singleton_lock:
        sampler = _sampler
    return sampler is not None and sampler.running()


def profile_dir() -> str:
    with _singleton_lock:
        return _profile_dir


def payload(limit: int = 10) -> dict:
    """The bare GET /monitoring/profile JSON body. Top-level keys are
    pinned by tests/integration/test_monitoring_schema.py — extend, but
    never silently drop."""
    body = get().summary(limit)
    return {
        "sampler": body["sampler"] | {
            "samples": body["samples"],
            "ticks": body["ticks"],
            "attributed_samples": body["attributed_samples"],
            "attributed_pct": body["attributed_pct"],
        },
        "threads": body["threads"],
        "subsystems": body["subsystems"],
        "stages": body["stages"],
    }


def collapsed() -> str:
    return get().collapsed()


def top_hot_frames(limit: int = 3) -> list[dict]:
    """Hot-frame forensics for watchdog alerts: [] when no sampler has
    collected anything (alerts simply omit the join)."""
    with _singleton_lock:
        sampler = _sampler
    if sampler is None:
        return []
    try:
        return sampler.top_hot_frames(limit)
    except Exception:  # pragma: no cover - joins must not break alerts
        return []


def capture_payload(seconds: float, hz: float | None = None,
                    limit: int = 10) -> dict:
    return get().capture_summary(seconds, hz, limit)


def capture_collapsed(seconds: float, hz: float | None = None) -> str:
    return get().capture_collapsed(seconds, hz)


def diff_payload(seconds: float, hz: float | None = None) -> dict:
    return get().diff(seconds, hz)


HOST_CLOCK_FILE = "host_clock.json"
HOST_TRACK_FILE = "host_track.json"
# The host track is written out from this long BEFORE the capture. Inside
# a capture the profiler's hooks slow every Python thread (the sessions
# cell answered 305 steps a second inside one, 1,045 outside: PERF.md
# section 6, PR 40), so what the process's own work costs (the drain, the
# collector, the event loop's CPU) is read from the undisturbed stretch
# before it; the capture's own spans name the device's gaps.
TRACK_LEAD_S = 10.0


@contextlib.contextmanager
def traced_capture(log_dir: str):
    """One in-process `jax.profiler.trace` into `log_dir`, as both
    captures of this server take it (`device_capture` below and
    server/profiler.py `ProfilerServiceImpl.Profile`). For its length
    the tracing spine mirrors its spans into TraceAnnotations, so the
    capture shows the stage names on the host threads; at its end it
    writes `host_clock.json` beside the capture, which joins the spans'
    clock to the capture's, and `host_track.json`, what the host did
    meanwhile (`_write_host_track`).

    The profiler's events count nanoseconds from the start of its
    session (measured on a v5e, PERF.md section 7: not the Unix epoch),
    which begins inside `start_trace`. So `tracing.clock_pair()` is read
    just before `start_trace` (`zero`: the capture's zero on the spans'
    clock and on the wall clock, early by what `start_trace` does before
    it opens its session: 17-135 us measured), when it has returned
    (`start`) and just before `stop_trace` (`stop`). A span's `ts` lies
    at (ts - zero.span_us) * 1000 ns on the capture's host planes. The
    pairs' `epoch_unix_ns` differ by what the wall clock drifted against
    `perf_counter` meanwhile. jax is imported here only (the router
    imports this module and stays jax-free)."""
    import jax

    os.makedirs(log_dir, exist_ok=True)
    zero = tracing.clock_pair()
    with jax.profiler.trace(log_dir):
        start = tracing.clock_pair()
        try:
            with tracing.profiler_annotations():
                yield
        finally:
            stop = tracing.clock_pair()
            with open(os.path.join(log_dir, HOST_CLOCK_FILE), "w") as out:
                json.dump({
                    "schema": "host_clock/1",
                    "epoch_unix_ns": zero["epoch_unix_ns"],
                    "drift_ns": stop["epoch_unix_ns"]
                    - zero["epoch_unix_ns"],
                    "zero": zero, "start": start, "stop": stop},
                    out, indent=1)
    # After `stop_trace`: rendering a few thousand traces is a burst of
    # Python that the capture should not hold, and the requests that
    # ended while the profiler wrote its file are in the ring by now.
    _write_host_track(log_dir, zero, stop)


def _write_host_track(log_dir: str, zero: dict, stop: dict) -> None:
    """`host_track.json`: every process span and every request trace of
    the ring, of any signature, that overlaps the capture [zero, stop],
    in the one format `/monitoring/traces` has (`tracing.chrome_trace`),
    so that a capture is whole by itself: what an operator opens next to
    the device trace, and what a reader joins to it through
    `host_clock.json`. The process spans begin TRACK_LEAD_S before the
    capture. `otherData.capture` holds the capture's two ends as `ts`
    values, and `lead_us`, where the process spans begin. The ring
    bounds what it can hold (256 requests unless `--trace_ring_size`
    says more)."""
    t0, t1 = zero["perf_counter_s"], stop["perf_counter_s"]
    lead = t0 - TRACK_LEAD_S
    payload = tracing.chrome_trace(
        [tr for tr in tracing.ring_snapshot()
         if tr.start <= t1 and (tr.end is None or tr.end >= t0)],
        process_spans=tracing.process_snapshot(since=lead, until=t1))
    payload["otherData"].update(
        schema="host_track/1",
        capture={"zero_us": zero["span_us"], "stop_us": stop["span_us"],
                 "lead_us": tracing._us(lead)})
    with open(os.path.join(log_dir, HOST_TRACK_FILE), "w") as out:
        json.dump(payload, out, separators=(",", ":"))


def device_capture(seconds: float, log_dir: str = "") -> dict:
    """Programmatic device capture window -> --profile_dir (the
    endpoint maps the jax ImportError of a jax-free router to a 501)."""
    root = log_dir or profile_dir()
    if not root:
        raise ValueError(
            "device capture needs --profile_dir (no directory configured)")
    seconds = min(max(float(seconds), 0.1), CAPTURE_MAX_SECONDS)
    run_dir = os.path.join(root, f"servespy-{int(time.time() * 1000):x}")
    with traced_capture(run_dir):
        time.sleep(seconds)
    files = []
    for dirpath, _, filenames in os.walk(run_dir):
        for fn in filenames:
            files.append(os.path.relpath(os.path.join(dirpath, fn), run_dir))
    return {"profile_dir": run_dir, "seconds": seconds,
            "files": sorted(files)}
