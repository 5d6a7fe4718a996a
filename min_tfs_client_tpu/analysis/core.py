"""servelint core: findings, annotations, and the shared AST plumbing.

The reference stack gets its hot-path discipline from C++ machinery we
don't have in Python — `GUARDED_BY`/`EXCLUSIVE_LOCKS_REQUIRED` clang
thread-safety annotations on batching/manager state, and static typing
that makes an accidental device->host sync a visible type coercion. This
package is the Python analogue: a self-contained `ast`-based analyzer
(no new dependencies) with eight rule families (docs/STATIC_ANALYSIS.md):

  host-sync   (HS*)  device->host coercions in hot-path modules
  recompile   (RC*)  jit recompile hazards (per-call jit, tracer branches)
  locks       (LK*)  `# guarded_by:` lock-discipline (GUARDED_BY analogue)
  spans       (SP*)  trace spans opened outside `with` / leaked to threads
  lock-order  (DL*)  interprocedural lock-order cycles + untimed parks
  threads     (TH*)  thread-root inventory / undeclared shared state
  error-flow  (ER*)  raised-exception taxonomy at the handler boundary
  resource    (RL*)  acquire/release lifecycle + `# servelint: owns`

Annotations are ordinary comments, so the runtime never pays for them:

  self._batches = []        # guarded_by: self._lock
  _pending = deque()        # guarded_by: _pending_lock        (module level)
  def _seal(self, b):       # servelint: holds self._lock
  arr = np.asarray(v)       # servelint: sync-ok <reason>
  got = jax.jit(f)(x)       # servelint: jit-ok <reason>
  self._x += 1              # servelint: lock-ok <reason>
  s = tracing.span("x")     # servelint: span-ok <reason>
  self._cv.wait()           # servelint: blocks <reason>
  self.core = build()       # servelint: thread-ok <reason>
  raise RuntimeError(...)   # servelint: internal-ok <reason>
  except ServingError: ...  # servelint: status-ok <reason>
  while ... continue        # servelint: retry-ok <reason>
  except Exception: ...     # servelint: fallback-ok <reason>
  self._pages = {}          # servelint: owns pages
  return slot               # servelint: transfers <Receiver|caller>
  pool.release_slot(s)      # servelint: leak-ok <reason>
"""

from __future__ import annotations

import ast
import io
import re
import tokenize
from dataclasses import dataclass, field, replace
from typing import Iterable, Optional

# -- findings ----------------------------------------------------------------


@dataclass(frozen=True)
class Finding:
    """One rule violation: file:line + rule id + a fix hint, plus a
    line-number-independent key used by the baseline (line numbers shift
    on every edit; scope+detail survive reformatting)."""

    path: str       # posix path relative to the analysis root's parent
    line: int
    rule: str       # family: host-sync | recompile | locks | spans
    code: str       # stable id, e.g. HS001
    message: str
    hint: str = ""
    scope: str = "<module>"   # qualname of the enclosing def/class
    detail: str = ""          # stable token (attr/call name), for the key

    def key(self) -> str:
        return f"{self.path}::{self.code}::{self.scope}::{self.detail}"

    def render(self) -> str:
        hint = f"  [fix: {self.hint}]" if self.hint else ""
        return (f"{self.path}:{self.line}: {self.code} ({self.rule}) "
                f"{self.message}{hint}")


# -- configuration -----------------------------------------------------------

DEFAULT_HOT_PATHS = (
    "min_tfs_client_tpu/servables/",
    "min_tfs_client_tpu/batching/",
    "min_tfs_client_tpu/server/handlers.py",
    "min_tfs_client_tpu/tensor/codec.py",
)

# Modules that IMPLEMENT the tracing spine are exempt from the span rule
# (they necessarily construct spans outside `with`).
DEFAULT_SPAN_EXEMPT = (
    "min_tfs_client_tpu/observability/tracing.py",
)

# Handler boundary set for the ER (error-flow) family: functions whose
# raised exceptions reach a wire status. Servicer classes and
# `@_instrumented` handler methods are detected structurally; these are
# the boundary entries structure can't see (router forwards + the tick
# batcher's step, which raises what the loop thread's tick raised).
DEFAULT_BOUNDARY_FUNCTIONS = (
    "min_tfs_client_tpu/router/proxy.py::GrpcProxy._handle",
    "min_tfs_client_tpu/router/proxy.py::GrpcProxy._handle_routed",
    "min_tfs_client_tpu/router/proxy.py::GrpcProxy._forward",
    "min_tfs_client_tpu/router/proxy.py::rest_route_request",
    "min_tfs_client_tpu/router/aio_proxy.py::AioDataPlane._handle",
    "min_tfs_client_tpu/router/aio_proxy.py::AioDataPlane._forward",
    "min_tfs_client_tpu/servables/decode_sessions.py::TickBatcher.step",
)

# The one module allowed to make inline retry decisions (it IS the
# shared predicate home), and the predicate names everyone else must
# route through (ER003).
DEFAULT_RETRY_HOME = "min_tfs_client_tpu/robustness/retry.py"
DEFAULT_RETRY_PREDICATES = frozenset(
    {"next_forward_retry_delay_s", "retry_safe_predict"})


@dataclass(frozen=True)
class AnalysisConfig:
    """Knobs for a run. Tests override hot_paths to point at fixtures;
    the CLI uses the defaults, which mirror ISSUE/docs."""

    # host-sync applies only to modules whose relative path starts with
    # one of these prefixes (or equals the entry exactly).
    hot_paths: tuple = DEFAULT_HOT_PATHS
    span_exempt: tuple = DEFAULT_SPAN_EXEMPT
    # Method names whose call results are device values (jax Arrays still
    # on the accelerator) — the taint seeds of the host-sync rule.
    device_call_attrs: frozenset = frozenset(
        {"_execute", "_run_device", "jitted", "interior_jitted"})
    # Dotted callables returning device values.
    device_call_names: frozenset = frozenset(
        {"jax.device_put", "jax.pmap"})
    # Dotted callables producing a *device-executing callable*.
    jit_factories: frozenset = frozenset(
        {"jax.jit", "jax.pmap", "pjit", "jax.experimental.pjit.pjit"})
    # Calls that return HOST data (sinks clear taint; fetch_outputs is the
    # sanctioned overlapped device->host round).
    sanctioned_fetches: frozenset = frozenset({"fetch_outputs"})
    # ER boundary detection: explicit `path::qualname` entries plus the
    # structural signals (class-name suffix, method-name prefix,
    # decorator names, `# servelint: boundary` mark).
    boundary_functions: tuple = DEFAULT_BOUNDARY_FUNCTIONS
    boundary_class_suffixes: tuple = ("Servicer",)
    boundary_method_prefixes: tuple = ("do_",)
    boundary_decorators: frozenset = frozenset({"_instrumented"})
    # ER003: the shared retry predicates and their home module.
    retry_home: str = DEFAULT_RETRY_HOME
    retry_predicates: frozenset = DEFAULT_RETRY_PREDICATES

    def is_hot(self, relpath: str) -> bool:
        return any(relpath == p or relpath.startswith(p)
                   for p in self.hot_paths)

    def is_span_exempt(self, relpath: str) -> bool:
        return any(relpath == p or relpath.endswith(p)
                   for p in self.span_exempt)


# -- per-module context ------------------------------------------------------

_GUARDED_RE = re.compile(r"#\s*guarded_by:\s*([A-Za-z_][\w.]*)")
_SERVELINT_RE = re.compile(r"#\s*servelint:\s*([a-z-]+)(?:\s+(.*))?")


@dataclass
class ModuleInfo:
    """One parsed module plus its comment side-channel."""

    path: str                      # relative posix path (finding/baseline key)
    tree: ast.Module
    comments: dict[int, str] = field(default_factory=dict)  # line -> text
    # Lines whose ONLY content is the comment: the walk-up over "the
    # comment block above a statement" must stop at code lines, or an
    # inline annotation on the previous statement would leak onto this
    # one.
    comment_only: set = field(default_factory=set)

    # annotation lookups -----------------------------------------------------

    def guarded_decl(self, line: int) -> Optional[str]:
        """The `# guarded_by: <lock>` expression on `line`, if any."""
        m = _GUARDED_RE.search(self.comments.get(line, ""))
        return m.group(1) if m else None

    def servelint_marks(self, line: int) -> set[str]:
        """servelint markers on `line` (sync-ok, lock-ok, jit-ok, span-ok,
        holds)."""
        m = _SERVELINT_RE.search(self.comments.get(line, ""))
        return {m.group(1)} if m else set()

    def mark_arg(self, line: int, mark: str) -> Optional[str]:
        """The argument of `# servelint: <mark> <arg...>` on `line`
        (first whitespace-separated token; trailing prose is a reason)."""
        m = _SERVELINT_RE.search(self.comments.get(line, ""))
        if not m or m.group(1) != mark or not m.group(2):
            return None
        token = m.group(2).strip().split()[0]
        return token or None

    def stmt_mark_arg(self, stmt: ast.stmt, mark: str) -> Optional[str]:
        """mark_arg over a statement's whole line span (multi-line
        initializers carry the comment on any of their lines) or the
        contiguous comment block directly above it (where a line already
        carrying another annotation pushes the mark)."""
        for line in range(stmt.lineno, (stmt.end_lineno or stmt.lineno) + 1):
            arg = self.mark_arg(line, mark)
            if arg:
                return arg
        line = stmt.lineno - 1
        while line in self.comment_only:
            arg = self.mark_arg(line, mark)
            if arg:
                return arg
            line -= 1
        return None

    def holds_locks(self, line: int) -> set[str]:
        """Locks named by `# servelint: holds <lock>[, <lock>]` on line.
        Trailing prose after a lock name ("holds self._cv (callers...)")
        is ignored — a lock expression never contains whitespace."""
        m = _SERVELINT_RE.search(self.comments.get(line, ""))
        if not m or m.group(1) != "holds" or not m.group(2):
            return set()
        locks = set()
        for part in m.group(2).split(","):
            token = part.strip().split()[0] if part.strip() else ""
            if re.fullmatch(r"[A-Za-z_][\w.]*", token):
                locks.add(token)
        return locks

    def suppressed(self, node: ast.AST, mark: str,
                   stmt: ast.stmt | None = None) -> bool:
        """True when `# servelint: <mark>` sits on the node's line, on the
        first line of its enclosing statement, or on a comment line
        directly above the statement (where longer reasons live)."""
        lines = {getattr(node, "lineno", 0)}
        if stmt is not None:
            lines.add(stmt.lineno)
            line = stmt.lineno - 1
            # Walk up through a contiguous comment block above the stmt
            # (comment-ONLY lines: an inline comment on the previous
            # statement belongs to that statement, not this one).
            while line in self.comment_only:
                lines.add(line)
                line -= 1
        return any(mark in self.servelint_marks(ln) for ln in lines)


def parse_module(path: str, relpath: str, source: str | None = None
                 ) -> Optional[ModuleInfo]:
    """Parse one file into a ModuleInfo; None on syntax errors (a broken
    file is the test suite's problem, not the linter's)."""
    if source is None:
        with open(path, "r", encoding="utf-8", errors="replace") as f:
            source = f.read()
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError:
        return None
    comments: dict[int, str] = {}
    comment_only: set = set()
    try:
        for tok in tokenize.generate_tokens(io.StringIO(source).readline):
            if tok.type == tokenize.COMMENT:
                comments[tok.start[0]] = tok.string
                if tok.line.strip().startswith("#"):
                    comment_only.add(tok.start[0])
    except (tokenize.TokenizeError, IndentationError):  # pragma: no cover
        pass
    return ModuleInfo(path=relpath, tree=tree, comments=comments,
                      comment_only=comment_only)


# -- small AST helpers shared by every rule ----------------------------------


def dotted(node: ast.AST) -> Optional[str]:
    """'self._mu' / 'jax.jit' for Name/Attribute chains; None otherwise."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def call_name(call: ast.Call) -> Optional[str]:
    return dotted(call.func)


def walk_scopes(tree: ast.Module):
    """Yield (qualname, function_node) for every def/async def, with
    class nesting folded into the qualname (Cls.method, Cls.method.inner)."""

    def visit(node: ast.AST, prefix: str):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qual = f"{prefix}{child.name}"
                yield qual, child
                yield from visit(child, f"{qual}.")
            elif isinstance(child, ast.ClassDef):
                yield from visit(child, f"{prefix}{child.name}.")
            else:
                yield from visit(child, prefix)

    yield from visit(tree, "")


def walk_function_nodes(func: ast.AST) -> Iterable[ast.AST]:
    """ast.walk over one function's own body, NOT descending into nested
    def/class scopes (walk_scopes yields those separately). Lambdas stay:
    they share the enclosing scope's names."""
    stack = list(ast.iter_child_nodes(func))
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


def collect_jit_bindings(tree: ast.Module, jit_factories: frozenset
                         ) -> tuple[set, set]:
    """Names and `self.<attr>`s bound (anywhere in the module) to the
    result of a jit factory — calling them executes on device."""
    names: set[str] = set()
    attrs: set[str] = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign):
            continue
        value = node.value
        if not (isinstance(value, ast.Call)
                and (dotted(value.func) or "") in jit_factories):
            continue
        for target in node.targets:
            if isinstance(target, ast.Name):
                names.add(target.id)
            elif isinstance(target, ast.Attribute) and \
                    isinstance(target.value, ast.Name) and \
                    target.value.id == "self":
                attrs.add(target.attr)
    return names, attrs


def bound_names(target: ast.AST) -> Iterable[str]:
    """Plain names bound by an assignment/loop target."""
    if isinstance(target, ast.Name):
        yield target.id
    elif isinstance(target, (ast.Tuple, ast.List)):
        for elt in target.elts:
            yield from bound_names(elt)
    elif isinstance(target, ast.Starred):
        yield from bound_names(target.value)


__all__ = [
    "AnalysisConfig",
    "DEFAULT_HOT_PATHS",
    "Finding",
    "ModuleInfo",
    "bound_names",
    "call_name",
    "collect_jit_bindings",
    "dotted",
    "parse_module",
    "replace",
    "walk_function_nodes",
    "walk_scopes",
]
