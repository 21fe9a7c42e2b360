"""rlt-lint core: the per-file AST checker and the repo configuration.

Everything here is stdlib-only (``ast`` + ``re``) so ``format.sh`` can
gate on it in environments with no lint tooling installed.  The checker
is one recursive walker per file with explicit lexical context (class
stack, function stack, ``with``-lock stack, dict-key stack); rules are
small predicates over that context.  See the package docstring for the
rule catalog and ``docs/STATIC_ANALYSIS.md`` for the policy.
"""

from __future__ import annotations

import ast
import re
from dataclasses import dataclass, field
from typing import Any, Dict, FrozenSet, List, NamedTuple, Optional, Set, Tuple

__all__ = [
    "RULES",
    "Config",
    "Finding",
    "check_source",
    "load_env_registry",
    "load_schema_keys",
    "repo_config",
]

RULES = {
    "RLT000": "lint infrastructure (suppressions, registry, baseline)",
    "RLT001": "per-call jax.jit/pjit construction on a hot path",
    "RLT002": "host-sync call inside a registered hot-loop body",
    "RLT003": "guarded attribute accessed outside its lock",
    "RLT004": "clock discipline (wall vs perf_counter vs jit purity)",
    "RLT005": "RLT_* env read missing from parallel/env_bus.py",
    "RLT006": "telemetry dict key not in the schema validator key set",
    "RLT007": "thread hygiene (daemon=, swallowed thread errors)",
    "RLT008": "module/class-scope jit bypassing the program ledger",
}


class Finding(NamedTuple):
    path: str
    line: int
    rule: str
    message: str
    #: Stripped source text of the flagged line (the baseline match key).
    text: str

    def render(self) -> str:
        return f"{self.path}:{self.line}: {self.rule} {self.message}"


@dataclass(frozen=True)
class Config:
    """Which files/functions each rule applies to.  Paths are
    repo-relative with forward slashes; qualnames are ``Class.method``
    for methods and bare names for module-level functions."""

    #: RLT001: functions where constructing a jit object is banned.
    hot_jit: Dict[str, FrozenSet[str]] = field(default_factory=dict)
    #: RLT002: hot-loop bodies where host syncs are banned.
    hot_sync: Dict[str, FrozenSet[str]] = field(default_factory=dict)
    #: RLT004d: files whose SpanTracer() sites must pass clock=.
    wall_clock_tracer_files: FrozenSet[str] = frozenset()
    #: RLT004a: per-process timing modules where time.time() is banned
    #: (dict values under a wall-timestamp key are exempt).
    perf_timing_files: FrozenSet[str] = frozenset()
    #: RLT004b: cross-process envelope modules banning perf_counter().
    trace_envelope_files: FrozenSet[str] = frozenset()
    #: RLT006: path -> {function qualname -> schema key-set prefix}.
    schema_producers: Dict[str, Dict[str, str]] = field(default_factory=dict)
    #: RLT006: prefix -> (required keys, optional keys).
    schema_keys: Dict[str, Tuple[FrozenSet[str], FrozenSet[str]]] = field(
        default_factory=dict
    )
    #: RLT005: registered env knob names (parallel/env_bus.py).
    env_registry: FrozenSet[str] = frozenset()
    #: RLT005: files whose literal RLT_* strings are the registry itself.
    env_exempt_files: FrozenSet[str] = frozenset()
    #: RLT008: path prefixes where import-time jit construction must
    #: route through telemetry.program_ledger.ledgered_jit.
    ledger_paths: Tuple[str, ...] = ()


# Wall-timestamp dict keys exempt from the RLT004a time.time() ban:
# cross-process envelopes NEED a shared epoch there.
_TS_KEYS = frozenset({"ts", "t_wall", "wall_ts", "send_ts"})

_JIT_NAMES = frozenset({
    "jax.jit", "jit", "pjit", "jax.pjit", "pjit.pjit",
    "jax.experimental.pjit.pjit",
})
_SYNC_SIMPLE = frozenset({
    "np.asarray", "np.array", "numpy.asarray", "numpy.array",
    "jax.device_get", "jax.block_until_ready",
})
_ENV_GET = frozenset({
    "os.environ.get", "environ.get", "os.getenv", "getenv",
    "os.environ.setdefault", "environ.setdefault",
    "os.environ.pop", "environ.pop",
})
_ENV_MAPS = frozenset({"os.environ", "environ"})
# Banned namespaces inside jit-wrapped (trace-pure) functions: host
# clocks and host RNG burn into the compiled program at trace time.
_JIT_IMPURE_PREFIXES = ("time.", "random.", "np.random.", "numpy.random.")

_NOQA_RE = re.compile(
    r"#\s*rlt:\s*noqa\[([A-Za-z0-9_,\s]+)\]\s*(.*)$"
)
_GUARD_RE = re.compile(r"#\s*guarded by\s+(self\.\w+)")
_HOLDS_RE = re.compile(r"#\s*rlt:\s*holds\s+(self\.\w+)")


def _dotted(node: ast.AST) -> Optional[str]:
    """Render ``a.b.c`` attribute/name chains; None for anything else."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _decorator_name(deco: ast.AST) -> Optional[str]:
    """Dotted name of a decorator, unwrapping the
    ``@partial(jax.jit, ...)`` idiom (required whenever static/donated
    args are used) to the wrapped callable's name."""
    if isinstance(deco, ast.Call):
        dname = _dotted(deco.func)
        if (dname or "").rsplit(".", 1)[-1] == "partial" and deco.args:
            return _dotted(deco.args[0])
        return dname
    return _dotted(deco)


class _Frame:
    """Per-function lexical state.  A nested def/lambda gets a FRESH
    frame: its body does not execute under the enclosing ``with`` locks
    (deferred execution), but it inherits hot-path membership (a
    closure defined in a hot loop runs in the hot loop)."""

    def __init__(self, node: Optional[ast.AST], hot_jit: bool,
                 hot_sync: bool, producer: Optional[str],
                 holds: FrozenSet[str], jit_pure: bool):
        self.node = node
        self.hot_jit = hot_jit
        self.hot_sync = hot_sync
        self.producer = producer          # schema prefix, RLT006
        self.locks_held: List[str] = list(holds)
        self.checked_dict_vars: Set[str] = set()
        self.jit_pure = jit_pure


class _FileChecker:
    def __init__(self, path: str, src: str, config: Config):
        self.path = path
        self.src = src
        self.config = config
        self.lines = src.splitlines()
        self.findings: List[Finding] = []
        # line -> (set of codes, reason)
        self.noqa: Dict[int, Tuple[Set[str], str]] = {}
        # def-line -> lock name the method asserts its caller holds
        self.holds: Dict[int, str] = {}
        # line -> guard lock name (collection pass uses it)
        self.guard_comment: Dict[int, str] = {}
        # (class qualname, attr) -> lock dotted name
        self.guards: Dict[Tuple[str, str], str] = {}
        # lines spanned by the annotated declaration assignments —
        # the ONLY accesses a guard comment itself exempts (a guard
        # comment pasted on a use site must not become a reason-free
        # suppression channel; that is what noqa-with-reason is for)
        self.guard_decl_lines: Set[int] = set()
        # function names wrapped by jax.jit/pjit somewhere in this file
        self.jit_wrapped: Set[str] = set()
        # function names used as threading.Thread target= in this file
        self.thread_targets: Set[str] = set()
        # first line of the statement currently being visited
        self._stmt_line: Optional[int] = None
        # RLT008 applies to this file at all (prefix-scoped)
        self._ledger_scope = any(
            path.startswith(p) for p in config.ledger_paths
        )
        self._parse_comments()

    # -- comments ------------------------------------------------------------
    def _comment_lines(self) -> Dict[int, str]:
        """line -> comment text, via tokenize — NOT raw line scanning:
        a docstring or error message *mentioning* ``# rlt: noqa[...]``
        (this package's own help text does) must not parse as a
        directive."""
        import io
        import tokenize

        out: Dict[int, str] = {}
        try:
            for tok in tokenize.generate_tokens(
                    io.StringIO(self.src).readline):
                if tok.type == tokenize.COMMENT:
                    out[tok.start[0]] = tok.string
        except (tokenize.TokenError, IndentationError, SyntaxError):
            # Unparseable source: run() reports the syntax error; no
            # directives apply.
            return {}
        return out

    def _parse_comments(self) -> None:
        for i, line in self._comment_lines().items():
            m = _NOQA_RE.search(line)
            if m:
                codes = {c.strip().upper() for c in m.group(1).split(",")
                         if c.strip()}
                reason = m.group(2).strip()
                if reason.startswith("#"):
                    # a following comment is not a reason
                    reason = ""
                self.noqa[i] = (codes, reason)
                for code in codes:
                    if code not in RULES:
                        self._raw(i, "RLT000",
                                  f"noqa names unknown rule {code}")
                if not reason:
                    self._raw(
                        i, "RLT000",
                        "noqa without a reason — say why the rule does "
                        "not apply here",
                    )
            m = _GUARD_RE.search(line)
            if m:
                self.guard_comment[i] = m.group(1)
            m = _HOLDS_RE.search(line)
            if m:
                self.holds[i] = m.group(1)

    def _raw(self, line: int, rule: str, msg: str) -> None:
        text = (self.lines[line - 1].strip()
                if 0 < line <= len(self.lines) else "")
        self.findings.append(Finding(self.path, line, rule, msg, text))

    def _flag(self, node: ast.AST, rule: str, msg: str) -> None:
        """Record a finding unless a noqa for ``rule`` covers any line
        the node spans — or the first line of the enclosing statement
        (multi-line calls put the comment where the statement starts)."""
        lo = getattr(node, "lineno", 1)
        hi = getattr(node, "end_lineno", lo) or lo
        lines = set(range(lo, hi + 1))
        if self._stmt_line is not None:
            lines.add(self._stmt_line)
            # a standalone comment line directly above the statement
            above = self._stmt_line - 1
            if (0 < above <= len(self.lines)
                    and self.lines[above - 1].lstrip().startswith("#")):
                lines.add(above)
        for line in lines:
            entry = self.noqa.get(line)
            if entry and rule in entry[0] and entry[1]:
                return
        self._raw(lo, rule, msg)

    # -- entry ---------------------------------------------------------------
    def run(self) -> List[Finding]:
        try:
            tree = ast.parse(self.src)
        except SyntaxError as e:
            self._raw(e.lineno or 1, "RLT000", f"syntax error: {e.msg}")
            return self.findings
        self._collect(tree)
        self._check_registry_drift(tree)
        frame = _Frame(None, False, False, None, frozenset(), False)
        self._visit_body(tree.body, [], frame, dict_key_stack=[])
        return self.findings

    # -- collection pass -----------------------------------------------------
    def _collect(self, tree: ast.Module) -> None:
        class_stack: List[str] = []

        def walk(node: ast.AST) -> None:
            if isinstance(node, ast.ClassDef):
                class_stack.append(node.name)
                for child in node.body:
                    walk(child)
                class_stack.pop()
                return
            if isinstance(node, ast.Call):
                name = _dotted(node.func) or ""
                base = name.rsplit(".", 1)[-1]
                if name in _JIT_NAMES and node.args:
                    first = node.args[0]
                    if isinstance(first, ast.Name):
                        self.jit_wrapped.add(first.id)
                if base == "Thread":
                    for kw in node.keywords:
                        if kw.arg == "target":
                            tgt = _dotted(kw.value)
                            if tgt:
                                self.thread_targets.add(
                                    tgt.rsplit(".", 1)[-1]
                                )
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for deco in node.decorator_list:
                    if _decorator_name(deco) in _JIT_NAMES:
                        self.jit_wrapped.add(node.name)
            if isinstance(node, (ast.Assign, ast.AnnAssign)) and class_stack:
                targets = (node.targets
                           if isinstance(node, ast.Assign)
                           else [node.target])
                lo = node.lineno
                hi = getattr(node, "end_lineno", lo) or lo
                lock = None
                # inline on any spanned line, or a standalone comment
                # line directly above the assignment
                candidates = list(range(lo, hi + 1))
                if (lo > 1 and self.lines[lo - 2].lstrip()
                        .startswith("#")):
                    candidates.append(lo - 1)
                for line in candidates:
                    if line in self.guard_comment:
                        lock = self.guard_comment[line]
                        break
                if lock is not None:
                    for tgt in targets:
                        if (isinstance(tgt, ast.Attribute)
                                and isinstance(tgt.value, ast.Name)
                                and tgt.value.id == "self"):
                            cls = ".".join(class_stack)
                            self.guards[(cls, tgt.attr)] = lock
                            self.guard_decl_lines.update(
                                range(lo, hi + 1)
                            )
            for child in ast.iter_child_nodes(node):
                walk(child)

        for top in tree.body:
            walk(top)

    def _check_registry_drift(self, tree: ast.Module) -> None:
        """A registered hot-path/producer qualname that no longer
        resolves means the protection silently vanished — fail loudly
        so the registry moves with the refactor."""
        defined: Set[str] = set()

        def walk(node: ast.AST, cls: List[str]) -> None:
            for child in ast.iter_child_nodes(node):
                if isinstance(child, ast.ClassDef):
                    walk(child, cls + [child.name])
                elif isinstance(child,
                                (ast.FunctionDef, ast.AsyncFunctionDef)):
                    defined.add(".".join(cls + [child.name]))
                    # nested defs are not registry targets
                else:
                    walk(child, cls)

        walk(tree, [])
        registered: Set[str] = set()
        registered |= set(self.config.hot_jit.get(self.path, ()))
        registered |= set(self.config.hot_sync.get(self.path, ()))
        registered |= set(
            self.config.schema_producers.get(self.path, {})
        )
        for qn in sorted(registered - defined):
            self._raw(
                1, "RLT000",
                f"registered qualname {qn!r} not found in {self.path} — "
                f"update tools/rlt_lint config to follow the refactor",
            )

    # -- checking pass -------------------------------------------------------
    def _qualname(self, class_stack: List[str], name: str) -> str:
        return ".".join(class_stack + [name])

    def _visit_body(self, body: List[ast.stmt], class_stack: List[str],
                    frame: _Frame, dict_key_stack: List[Optional[str]]
                    ) -> None:
        for stmt in body:
            self._visit(stmt, class_stack, frame, dict_key_stack)

    def _enter_function(self, node, class_stack: List[str],
                        frame: _Frame) -> _Frame:
        cfg = self.config
        qn = self._qualname(class_stack, node.name) \
            if frame.node is None else None
        hot_jit = frame.hot_jit or (
            qn is not None and qn in cfg.hot_jit.get(self.path, ())
        )
        hot_sync = frame.hot_sync or (
            qn is not None and qn in cfg.hot_sync.get(self.path, ())
        )
        producer = frame.producer or (
            cfg.schema_producers.get(self.path, {}).get(qn)
            if qn is not None else None
        )
        holds: Set[str] = set()
        lo = node.lineno
        if node.decorator_list:
            lo = min(lo, node.decorator_list[0].lineno)
        hi = node.body[0].lineno if node.body else node.lineno
        candidates = list(range(lo, hi + 1))
        # a standalone comment line directly above the def
        if lo > 1 and self.lines[lo - 2].lstrip().startswith("#"):
            candidates.append(lo - 1)
        for line in candidates:
            if line in self.holds:
                holds.add(self.holds[line])
        jit_pure = frame.jit_pure or node.name in self.jit_wrapped
        lru = any(
            (_dotted(d) or "").rsplit(".", 1)[-1] in ("lru_cache", "cache")
            or (isinstance(d, ast.Call)
                and (_dotted(d.func) or "").rsplit(".", 1)[-1]
                in ("lru_cache", "cache"))
            for d in node.decorator_list
        )
        new = _Frame(node, hot_jit and not lru, hot_sync, producer,
                     frozenset(holds), jit_pure)
        return new

    def _visit(self, node: ast.AST, class_stack: List[str], frame: _Frame,
               dict_key_stack: List[Optional[str]]) -> None:
        if isinstance(node, ast.stmt):
            self._stmt_line = node.lineno
        if isinstance(node, ast.ClassDef):
            self._visit_body(node.body, class_stack + [node.name],
                             frame, dict_key_stack)
            return

        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if frame.hot_jit:
                # A @jax.jit-decorated def inside a hot function is a
                # fresh jit object per enclosing call, same as jit(f) —
                # and @partial(jax.jit, ...) constructs one just the
                # same (it is the required form for static/donated
                # args, so the most common evasion).
                for deco in node.decorator_list:
                    if _decorator_name(deco) in _JIT_NAMES:
                        self._flag(
                            deco, "RLT001",
                            "jit-decorated def inside a hot-path "
                            "function constructs a fresh jit object "
                            "per call — hoist it",
                        )
            # RLT008 — a @jax.jit def at module/class scope builds an
            # executable the program ledger never sees: no compile
            # timing, no cost/memory rows, and its recompiles are
            # invisible to the forensics ring.
            if frame.node is None and self._ledger_scope:
                for deco in node.decorator_list:
                    if _decorator_name(deco) in _JIT_NAMES:
                        self._flag(
                            deco, "RLT008",
                            "jit-decorated def at module/class scope "
                            "bypasses the program ledger — wrap with "
                            "telemetry.program_ledger.ledgered_jit("
                            "fn, site=...) so the executable is "
                            "inventoried and recompiles attributed",
                        )
            new = self._enter_function(node, class_stack, frame)
            # RLT007b: swallowed errors inside thread targets.
            if node.name in self.thread_targets:
                self._check_thread_body(node)
            self._visit_body(node.body, class_stack, new, [])
            return

        if isinstance(node, ast.Lambda):
            new = _Frame(node, frame.hot_jit, frame.hot_sync,
                         frame.producer, frozenset(), frame.jit_pure)
            self._visit(node.body, class_stack, new, [])
            return

        if isinstance(node, ast.With):
            added = []
            for item in node.items:
                name = _dotted(item.context_expr)
                if name is None and isinstance(item.context_expr, ast.Call):
                    name = _dotted(item.context_expr.func)
                if name:
                    frame.locks_held.append(name)
                    added.append(name)
                self._visit(item.context_expr, class_stack, frame,
                            dict_key_stack)
            self._visit_body(node.body, class_stack, frame, dict_key_stack)
            for _ in added:
                frame.locks_held.pop()
            return

        if isinstance(node, ast.Assign):
            self._check_dict_assign(node, frame)
            self._visit(node.value, class_stack, frame, dict_key_stack)
            for tgt in node.targets:
                self._visit(tgt, class_stack, frame, dict_key_stack)
            return

        if isinstance(node, ast.Dict):
            self._check_dict_literal(node, frame)
            for key, value in zip(node.keys, node.values):
                if key is not None:
                    self._visit(key, class_stack, frame, dict_key_stack)
                key_name = (key.value if isinstance(key, ast.Constant)
                            and isinstance(key.value, str) else None)
                dict_key_stack.append(key_name)
                self._visit(value, class_stack, frame, dict_key_stack)
                dict_key_stack.pop()
            return

        if isinstance(node, ast.Call):
            self._check_call(node, class_stack, frame, dict_key_stack)
            for child in ast.iter_child_nodes(node):
                self._visit(child, class_stack, frame, dict_key_stack)
            return

        if isinstance(node, ast.Subscript):
            self._check_subscript(node, frame)
            for child in ast.iter_child_nodes(node):
                self._visit(child, class_stack, frame, dict_key_stack)
            return

        if isinstance(node, ast.Attribute):
            self._check_guarded_attr(node, class_stack, frame)
            self._visit(node.value, class_stack, frame, dict_key_stack)
            return

        if isinstance(node, ast.ExceptHandler):
            # handled by _check_thread_body for thread targets; still
            # recurse for nested content.
            self._visit_body(node.body, class_stack, frame, dict_key_stack)
            return

        for child in ast.iter_child_nodes(node):
            self._visit(child, class_stack, frame, dict_key_stack)

    # -- rule bodies ---------------------------------------------------------
    def _check_call(self, node: ast.Call, class_stack: List[str],
                    frame: _Frame,
                    dict_key_stack: List[Optional[str]]) -> None:
        cfg = self.config
        name = _dotted(node.func) or ""
        base = name.rsplit(".", 1)[-1]
        kwargs = {kw.arg for kw in node.keywords}

        # RLT008 — jit construction at module/class scope (import
        # time).  These are exactly the steady-state executables the
        # program ledger exists to inventory; a bare jit here dispatches
        # outside the ledger forever.  ``partial(jax.jit, ...)`` alone
        # is a factory, not a program — only flag when a function is
        # actually wrapped (direct call or the partial applied).
        if frame.node is None and self._ledger_scope and node.args:
            wrapped = name if name in _JIT_NAMES else None
            if wrapped is None and isinstance(node.func, ast.Call):
                inner = _decorator_name(node.func)
                if inner in _JIT_NAMES:
                    wrapped = inner
            if wrapped is not None:
                self._flag(
                    node, "RLT008",
                    f"bare {wrapped}() at module/class scope bypasses "
                    f"the program ledger — route through "
                    f"telemetry.program_ledger.ledgered_jit(fn, "
                    f"site=...) so compile time, cost/memory and "
                    f"recompile forensics are captured",
                )

        # RLT001 — jit construction on a hot path.
        if frame.hot_jit and name in _JIT_NAMES:
            self._flag(
                node, "RLT001",
                "jit object constructed per call on a hot path — build "
                "it at module level, cache it on self at init, or "
                "functools.lru_cache the factory (a fresh jax.jit "
                "re-triggers backend_compile under cache pressure)",
            )

        # RLT002 — host syncs inside registered hot-loop bodies.
        if frame.hot_sync:
            sync = None
            if name in _SYNC_SIMPLE:
                sync = name
            elif base in ("item", "block_until_ready") and "." in name:
                sync = name
            elif name in ("float", "int") and node.args and not all(
                isinstance(a, ast.Constant) for a in node.args
            ):
                sync = name
            if sync is not None:
                self._flag(
                    node, "RLT002",
                    f"{sync}() forces a host/device sync inside a "
                    f"registered hot-loop body — keep the value on "
                    f"device, fetch asynchronously (_AsyncLogFetch "
                    f"pattern), or annotate the deliberate sync",
                )

        # RLT004a — wall clock in per-process timing modules.
        if (name == "time.time"
                and self.path in cfg.perf_timing_files
                and not (dict_key_stack and dict_key_stack[-1]
                         in _TS_KEYS)):
            self._flag(
                node, "RLT004",
                "time.time() in a perf-timing module — durations and "
                "phase timing use time.perf_counter(); wall clock is "
                "for cross-process envelope 'ts' fields only",
            )

        # RLT004b — perf_counter in cross-process envelope modules.
        if (name == "time.perf_counter"
                and self.path in cfg.trace_envelope_files):
            self._flag(
                node, "RLT004",
                "time.perf_counter() in a trace-envelope module — "
                "cross-process timestamps need the shared wall-clock "
                "epoch (time.time)",
            )

        # RLT004c — host clocks/RNG inside jit-wrapped functions.
        if frame.jit_pure and name.startswith(_JIT_IMPURE_PREFIXES):
            self._flag(
                node, "RLT004",
                f"{name}() inside a jit-wrapped function — the value "
                f"burns in at trace time (use traced operands or "
                f"jax.random with a threaded key)",
            )

        # RLT004d — distributed tracers must pass the wall clock.
        if (base == "SpanTracer"
                and self.path in cfg.wall_clock_tracer_files
                and "clock" not in kwargs):
            self._flag(
                node, "RLT004",
                "SpanTracer() without clock= in a distributed-tracer "
                "module — cross-process spans need clock=time.time or "
                "stitched traces land on process-private epochs",
            )

        # RLT005 — env reads must be registered.
        if (name in _ENV_GET and node.args
                and self.path not in cfg.env_exempt_files):
            first = node.args[0]
            if (isinstance(first, ast.Constant)
                    and isinstance(first.value, str)
                    and first.value.startswith("RLT_")
                    and first.value not in cfg.env_registry):
                self._flag(
                    node, "RLT005",
                    f"env knob {first.value} is not registered in "
                    f"parallel/env_bus.py — unregistered knobs are "
                    f"never forwarded to workers",
                )

        # RLT007a — explicit daemon= on every Thread.
        if base == "Thread" and "daemon" not in kwargs:
            self._flag(
                node, "RLT007",
                "threading.Thread without explicit daemon= — decide "
                "(and document) whether this thread may outlive its "
                "owner",
            )

        # RLT006 — subscript-store producers handled in _check_subscript;
        # nothing to do for calls.

    def _check_subscript(self, node: ast.Subscript, frame: _Frame) -> None:
        cfg = self.config
        name = _dotted(node.value)
        # RLT005 — os.environ["RLT_X"] forms.
        if (name in _ENV_MAPS
                and self.path not in cfg.env_exempt_files
                and isinstance(node.slice, ast.Constant)
                and isinstance(node.slice.value, str)
                and node.slice.value.startswith("RLT_")
                and node.slice.value not in cfg.env_registry):
            self._flag(
                node, "RLT005",
                f"env knob {node.slice.value} is not registered in "
                f"parallel/env_bus.py — unregistered knobs are never "
                f"forwarded to workers",
            )
        # RLT006 — var["key"] stores on a checked producer dict.
        if (frame.producer is not None
                and isinstance(node.value, ast.Name)
                and node.value.id in frame.checked_dict_vars
                and isinstance(node.slice, ast.Constant)
                and isinstance(node.slice.value, str)):
            self._check_schema_key(node, frame.producer, node.slice.value)

    def _check_dict_assign(self, node: ast.Assign, frame: _Frame) -> None:
        """Track names bound to checked producer dicts so later
        ``name["key"] = ...`` stores are validated too."""
        if frame.producer is None:
            return
        if isinstance(node.value, ast.Dict) and (
                self._anchored(node.value)
                or frame.producer.endswith("!any")):
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    frame.checked_dict_vars.add(tgt.id)

    def _anchored(self, node: ast.Dict) -> bool:
        """A producer dict literal is checked when it carries the wire
        anchor key (``type``/``schema``) or the producer covers every
        dict (single-document builders)."""
        for key in node.keys:
            if (isinstance(key, ast.Constant)
                    and key.value in ("type", "schema")):
                return True
        return False

    def _check_dict_literal(self, node: ast.Dict, frame: _Frame) -> None:
        if frame.producer is None:
            return
        prefix = frame.producer
        anchored = self._anchored(node) or prefix.endswith("!any")
        if not anchored:
            return
        for key in node.keys:
            if isinstance(key, ast.Constant) and isinstance(key.value, str):
                self._check_schema_key(key, prefix, key.value)

    def _check_schema_key(self, node: ast.AST, prefix: str,
                          key: str) -> None:
        prefix = prefix.split("!", 1)[0]
        sets = self.config.schema_keys.get(prefix)
        if sets is None:
            self._flag(
                node, "RLT000",
                f"producer registered against unknown schema prefix "
                f"{prefix!r} — no _{prefix}_REQUIRED/_OPTIONAL in "
                f"telemetry/schema.py",
            )
            return
        required, optional = sets
        if key not in required and key not in optional:
            self._flag(
                node, "RLT006",
                f"dict key {key!r} is not in telemetry/schema.py's "
                f"_{prefix}_REQUIRED/_OPTIONAL sets — producer and "
                f"validator drifted",
            )

    def _check_guarded_attr(self, node: ast.Attribute,
                            class_stack: List[str], frame: _Frame) -> None:
        if not (isinstance(node.value, ast.Name)
                and node.value.id == "self" and class_stack):
            return
        cls = ".".join(class_stack)
        lock = self.guards.get((cls, node.attr))
        if lock is None:
            return
        fn = frame.node
        fn_name = getattr(fn, "name", None)
        if fn_name in ("__init__", "__del__"):
            return
        # the annotated declaration assignment itself — and ONLY it; a
        # guard comment on a use site is not a suppression (use
        # `# rlt: noqa[RLT003] reason` for that)
        if node.lineno in self.guard_decl_lines:
            return
        if lock in frame.locks_held:
            return
        self._flag(
            node, "RLT003",
            f"self.{node.attr} is '# guarded by {lock}' but accessed "
            f"outside 'with {lock}' — wrap the access or annotate the "
            f"method '# rlt: holds {lock}'",
        )

    def _check_thread_body(self, node) -> None:
        for sub in ast.walk(node):
            if not isinstance(sub, ast.ExceptHandler):
                continue
            if sub.type is None:
                self._flag(
                    sub, "RLT007",
                    "bare except inside a thread target — name the "
                    "exception types; a typo-level bug would die "
                    "silently on this thread",
                )
                continue
            tname = _dotted(sub.type) or ""
            body_is_pass = all(
                isinstance(s, ast.Pass) for s in sub.body
            )
            if (tname.rsplit(".", 1)[-1] in ("Exception", "BaseException")
                    and body_is_pass):
                self._flag(
                    sub, "RLT007",
                    f"except {tname}: pass inside a thread target "
                    f"swallows every failure on this thread — log it, "
                    f"poison a mailbox, or narrow the type",
                )


def check_source(path: str, src: str, config: Config) -> List[Finding]:
    """Lint one file's source; returns findings (noqa already applied,
    baseline NOT applied — the CLI layers that)."""
    return _FileChecker(path, src, config).run()


# ---------------------------------------------------------------------------
# Repo configuration (registries + loaders)
# ---------------------------------------------------------------------------

def load_env_registry(env_bus_src: str) -> FrozenSet[str]:
    """Parse ``parallel/env_bus.py`` *statically* (no import): every
    ``EnvKnob("NAME", ...)`` call's literal first argument."""
    names: Set[str] = set()
    tree = ast.parse(env_bus_src)
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call)
                and (_dotted(node.func) or "").rsplit(".", 1)[-1]
                == "EnvKnob"
                and node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)):
            names.add(node.args[0].value)
    return frozenset(names)


def load_schema_keys(
    schema_src: str,
) -> Dict[str, Tuple[FrozenSet[str], FrozenSet[str]]]:
    """Parse ``telemetry/schema.py``'s module-level
    ``_<PREFIX>_REQUIRED`` / ``_<PREFIX>_OPTIONAL`` dict literals into
    per-prefix key sets."""
    req: Dict[str, Set[str]] = {}
    opt: Dict[str, Set[str]] = {}
    pat = re.compile(r"^_(\w+)_(REQUIRED|OPTIONAL)$")
    tree = ast.parse(schema_src)
    for node in tree.body:
        if not (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and isinstance(node.value, ast.Dict)):
            continue
        m = pat.match(node.targets[0].id)
        if not m:
            continue
        keys = {
            k.value for k in node.value.keys
            if isinstance(k, ast.Constant) and isinstance(k.value, str)
        }
        (req if m.group(2) == "REQUIRED" else opt).setdefault(
            m.group(1), set()
        ).update(keys)
    out: Dict[str, Tuple[FrozenSet[str], FrozenSet[str]]] = {}
    for prefix in set(req) | set(opt):
        out[prefix] = (
            frozenset(req.get(prefix, ())),
            frozenset(opt.get(prefix, ())),
        )
    return out


_PKG = "ray_lightning_tpu"

#: RLT001 — no jit construction inside these (request/step/tick paths).
_HOT_JIT = {
    f"{_PKG}/serve/engine.py": frozenset({
        "ServeEngine.step", "ServeEngine._step",
        "ServeEngine._decode_tick",
        "ServeEngine._spec_tick", "ServeEngine._tick_widths",
        "ServeEngine._tick_top_ks", "ServeEngine._complete",
        "ServeEngine._handle_queue_request",
        # Multi-LoRA hot paths: per-tick operand assembly and the
        # queue-plane hot-add (the round-17 fresh-jit-per-request
        # footgun must stay mechanically impossible here — the pool's
        # ONE scatter program is built at AdapterPool.__init__).
        "ServeEngine._lora_operands", "ServeEngine.add_adapter",
        "ServeEngine._load_adapter_item",
        # Prefix-cache / chunked-prefill hot paths: claims are pure
        # refcount bumps and chunk ticks replay ONE pre-built program
        # per step — a fresh jit on any of these would recompile per
        # admission.
        "ServeEngine._claim_prefix", "ServeEngine._suffix_prefill",
        "ServeEngine._start_chunk_job", "ServeEngine._chunk_tick",
        "ServeEngine._prefix_insert",
        # Live-migration admission: importing a mid-flight sequence
        # must reuse the SAME greedy-decomposed _import_fn executables
        # the handoff path warmed — a fresh jit here would turn every
        # drain into a recompile storm on the survivor.
        "ServeEngine._admit_migration",
    }),
    f"{_PKG}/serve/lora.py": frozenset({
        "AdapterPool.add", "AdapterPool.remove", "AdapterPool.slot_of",
    }),
    f"{_PKG}/serve/dist/prefill.py": frozenset({
        "PrefillRunner.step", "PrefillRunner._process",
    }),
    f"{_PKG}/serve/dist/router.py": frozenset({
        "Router.submit_request", "Router._route",
        "Router._ensure_adapter",
        # Headroom tie-break rides the placement hot path: the key
        # function must stay a pure dict read, never a jit probe.
        "Router._headroom",
        # Serving-plane resilience (ISSUE 19): migration retarget,
        # hedged placement and the brownout gate all ride the poll /
        # submit hot loops — pure dict work only.
        "Router._on_migration", "Router._hedge",
        "Router._update_brownout",
    }),
    f"{_PKG}/mpmd/stage.py": frozenset({
        "StageRunner._run_opt_step",
    }),
    f"{_PKG}/mpmd/transfer.py": frozenset({
        # The quantized-wire codec runs per micro-batch SEND on every
        # pipeline step: host-side numpy by design (np.asarray is its
        # job), but a jit constructed here would recompile per frame.
        "WireCodec.encode_payload", "LocalChannel.send",
        "QueueChannel.send", "StageInbox._file",
    }),
    f"{_PKG}/parallel/overlap.py": frozenset({
        # Grad taps are built per TRACE (amortized by the ledger's jit
        # cache), never per step — a jax.jit inside the tap machinery
        # would defeat exactly the overlap the taps exist to create.
        "TapPlane.tap", "TapPlane.apply_entry_taps",
    }),
    f"{_PKG}/core/loop.py": frozenset({
        "_AsyncLogFetch.schedule", "_RunningMeanLogs.update",
        "_RunningMeanLogs.update_stride", "_place_batch",
    }),
}

#: RLT002 — no host syncs inside these hot-loop bodies.  Narrower than
#: _HOT_JIT: prefill/router do host work by design (jax-free or
#: export-to-host), so only the decode/step/instruction loops gate.
_HOT_SYNC = {
    f"{_PKG}/serve/engine.py": frozenset({
        "ServeEngine.step", "ServeEngine._step",
        "ServeEngine._decode_tick",
        "ServeEngine._spec_tick", "ServeEngine._lora_operands",
        # Chunk ticks interleave with decode: a host sync per chunk
        # (beyond the final-chunk TTFT sync, which carries a noqa)
        # would serialize the stream the no-stall contract protects.
        "ServeEngine._claim_prefix", "ServeEngine._suffix_prefill",
        "ServeEngine._chunk_tick",
    }),
    f"{_PKG}/mpmd/stage.py": frozenset({
        "StageRunner._run_opt_step",
    }),
    f"{_PKG}/core/loop.py": frozenset({
        "_AsyncLogFetch.schedule", "_RunningMeanLogs.update",
        "_RunningMeanLogs.update_stride",
    }),
}

#: RLT006 — wire-document builders cross-checked against schema.py.
_SCHEMA_PRODUCERS = {
    f"{_PKG}/telemetry/heartbeat.py": {"make_beat": "HEARTBEAT"},
    f"{_PKG}/telemetry/monitor.py": {"make_event": "EVENT"},
    f"{_PKG}/telemetry/logs.py": {"make_log_item": "LOG"},
    f"{_PKG}/telemetry/spans.py": {"SpanTracer._span_dict": "SPAN!any"},
    f"{_PKG}/serve/dist/handoff.py": {
        "request_fields": "SERVE_REQUEST",
        "make_handoff_item": "SERVE_HANDOFF",
        "make_adapter_load_item": "SERVE_ADAPTER_LOAD",
        "make_migration_item": "SERVE_MIGRATION",
    },
    # SLO & capacity plane (ISSUE 18): store points, alert detail,
    # the oracle snapshot and the router's fleet fold.
    f"{_PKG}/telemetry/timeseries.py": {
        "TimeSeriesStore.points": "TIMESERIES_POINT",
    },
    f"{_PKG}/telemetry/slo.py": {
        "_alert_detail": "SLO_ALERT_DETAIL!any",
    },
    f"{_PKG}/serve/capacity.py": {
        "CapacityOracle.snapshot": "CAPACITY_SNAPSHOT",
        "aggregate_fleet": "FLEET_CAPACITY!any",
    },
}


def repo_config(repo_root: str) -> Config:
    """The tree's live configuration: registries above + key sets and
    the env registry parsed from their source-of-truth modules."""
    import os

    schema_path = os.path.join(repo_root, _PKG, "telemetry", "schema.py")
    env_bus_path = os.path.join(repo_root, _PKG, "parallel", "env_bus.py")
    with open(schema_path) as f:
        schema_keys = load_schema_keys(f.read())
    with open(env_bus_path) as f:
        env_registry = load_env_registry(f.read())
    return Config(
        hot_jit=_HOT_JIT,
        hot_sync=_HOT_SYNC,
        wall_clock_tracer_files=frozenset({
            f"{_PKG}/serve/engine.py",
            f"{_PKG}/serve/dist/router.py",
            f"{_PKG}/serve/dist/prefill.py",
            f"{_PKG}/mpmd/stage.py",
        }),
        perf_timing_files=frozenset({
            f"{_PKG}/telemetry/spans.py",
            f"{_PKG}/telemetry/step_stats.py",
            f"{_PKG}/telemetry/timeseries.py",
            f"{_PKG}/telemetry/slo.py",
            f"{_PKG}/serve/capacity.py",
            f"{_PKG}/serve/scheduler.py",
            f"{_PKG}/serve/metrics.py",
            # Brownout dwell/probe timers and client retry/hedge
            # latency samples are per-process intervals: monotonic
            # only, never wall clock.
            f"{_PKG}/serve/brownout.py",
            f"{_PKG}/serve/client.py",
            f"{_PKG}/mpmd/transfer.py",
            f"{_PKG}/parallel/grad_sync.py",
            f"{_PKG}/core/loop.py",
            f"{_PKG}/core/callbacks.py",
        }),
        trace_envelope_files=frozenset({
            f"{_PKG}/telemetry/propagate.py",
        }),
        schema_producers=_SCHEMA_PRODUCERS,
        schema_keys=schema_keys,
        env_registry=env_registry,
        env_exempt_files=frozenset({
            f"{_PKG}/parallel/env_bus.py",
        }),
        # RLT008 — the whole package: every import-time executable must
        # land in the program ledger (tools/bench drivers may build
        # throwaway jits; the package's are the steady-state programs).
        ledger_paths=(f"{_PKG}/",),
    )
