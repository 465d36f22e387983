"""The rule classes: one per invariant the codebase previously held by
convention (see tools/graftlint/__init__ for the inventory). Each rule is
a subscriber on the shared harness walk; findings carry a fix hint and a
line-drift-stable baseline key.
"""

from __future__ import annotations

import ast
import os
from typing import Dict, List, Optional, Set, Tuple

from tools.graftlint.harness import FileContext, RepoContext, Rule, Walker

__all__ = [
    "ClockDisciplineRule",
    "AtomicWriteRule",
    "LockGuardRule",
    "LockOrderRule",
    "ExceptSwallowRule",
    "VocabularyRule",
    "default_rules",
]


def _unparse(node: ast.AST) -> str:
    try:
        return ast.unparse(node)
    except Exception:  # pragma: no cover — unparse is total on parsed trees  # graftlint: swallow(unparse guard for exotic nodes; placeholder returned)
        return "<expr>"


# ---------------------------------------------------------------------------
# clock-discipline
# ---------------------------------------------------------------------------


class ClockDisciplineRule(Rule):
    """Policy/controller modules must read time and sleep through their
    injected seams (``policy.clock``/``policy.sleep``, ctor ``clock=``
    params): a bare ``time.time()``/``time.monotonic()``/``time.sleep()``
    in a decision path makes hysteresis/cooldown/lease logic untestable
    and non-deterministic. Referencing ``time.monotonic`` as a DEFAULT
    (``clock: Callable = time.monotonic``) is the seam itself and is not
    a call, so only calls are flagged."""

    id = "clock-discipline"
    hint = "route through the injected clock/sleep seam (ctor/policy argument)"

    #: The policy modules (decision logic gated on wall time). io/wire
    #: timing instrumentation (perf_counter spans) is out of scope.
    MODULES = {
        "autotune.py", "elastic.py", "retry.py", "stall.py", "fleet.py",
        "service.py", "serving.py",
    }
    CALLS = {"time", "monotonic", "sleep"}

    def visit(self, node: ast.AST, walker: Walker) -> None:
        if walker.ctx.name not in self.MODULES:
            return
        if not isinstance(node, ast.Call):
            return
        fn = node.func
        if (
            isinstance(fn, ast.Attribute)
            and isinstance(fn.value, ast.Name)
            and fn.value.id == "time"
            and fn.attr in self.CALLS
        ):
            self.emit(
                walker.ctx,
                node.lineno,
                f"bare time.{fn.attr}() in policy module "
                f"{walker.ctx.name} ({walker.qualname})",
                detail=f"time.{fn.attr}@{walker.qualname}",
            )


# ---------------------------------------------------------------------------
# atomic-write
# ---------------------------------------------------------------------------


class AtomicWriteRule(Rule):
    """Persisted artifacts (spools, cache entries, checkpoints, traces,
    journals) must land atomically: ``telemetry.atomic_write_bytes`` or
    stage-to-tmp + ``os.replace``. A bare write-mode ``open(p, "w")`` on
    a final path tears on crash and the reader (aggregator, Perfetto,
    resume) chokes on the stump. Compliant shapes recognized statically:
    the enclosing function also renames (stage-then-replace), or the path
    expression names a tmp/staging location (the stage file of such a
    pattern), or the enclosing function commits a MANIFEST afterwards via
    one of the shared durable-write helpers (the manifest-last sharded
    generation idiom: staged shard files are made visible-as-a-set by a
    later ``checkpoint.durable_write``/``atomic_write_bytes`` of the
    manifest, so readers only ever observe complete generations). The
    helper call must come AFTER the staged write — a manifest committed
    first covers nothing and stays flagged.

    Append-ONLY opens (``"a"``/``"ab"`` with no ``w``/``x``) get their
    own idiom: ``checkpoint.durable_append``'s fsync-before-return shape.
    An append never truncates — a crash tears at most the unfsynced
    tail, which a newest-consistent-prefix reader (the dispatcher
    journal replay) absorbs by design — so an append-only open whose
    enclosing scope also calls ``os.fsync`` is compliant. An append
    WITHOUT the fsync still tears silently across a host crash and
    stays flagged."""

    id = "atomic-write"
    hint = (
        "write via telemetry.atomic_write_bytes or checkpoint.durable_write, "
        "stage to a tmp path and os.replace into place, commit a "
        "manifest LAST via one of those helpers, or (append-only logs) "
        "go through checkpoint.durable_append's fsync-before-return shape"
    )

    _STAGED_PATH_MARKERS = ("tmp", "staging", "partial", "scratch")
    _RENAMES = {"replace", "rename", "renames"}
    _COMMIT_HELPERS = {"atomic_write_bytes", "durable_write"}

    def visit(self, node: ast.AST, walker: Walker) -> None:
        if not isinstance(node, ast.Call):
            return
        fn = node.func
        if not (isinstance(fn, ast.Name) and fn.id == "open"):
            return
        if len(node.args) < 2:
            return  # mode defaults to "r"
        mode = node.args[1]
        if not (isinstance(mode, ast.Constant) and isinstance(mode.value, str)):
            return
        # any truncating/creating mode counts — "w+" tears the destination
        # exactly like "w" ("r+" has no w/a/x and falls through)
        if not ({"w", "a", "x"} & set(mode.value)):
            return
        path_src = _unparse(node.args[0]).lower()
        if any(m in path_src for m in self._STAGED_PATH_MARKERS):
            return  # the stage file of a stage-then-replace pattern
        scope: ast.AST = (
            walker.func_stack[-1] if walker.func_stack else walker.ctx.tree
        )
        append_only = "a" in mode.value and not ({"w", "x"} & set(mode.value))
        if append_only and self._scope_fsyncs(scope):
            return  # the durable-append idiom (fsync before return)
        if self._scope_renames(scope):
            return
        if self._scope_commits_manifest_after(scope, node.lineno):
            return
        self.emit(
            walker.ctx,
            node.lineno,
            f"non-atomic write-mode open({_unparse(node.args[0])}, "
            f"{mode.value!r}) in {walker.qualname}",
            detail=f"open@{walker.qualname}:{_unparse(node.args[0])}",
        )

    @staticmethod
    def _scope_fsyncs(scope: ast.AST) -> bool:
        """An ``os.fsync(...)`` anywhere in the scope — paired with an
        append-only open this is the durable-append shape (the bytes are
        on the platter before the writer reports success)."""
        for sub in ast.walk(scope):
            if isinstance(sub, ast.Call):
                f = sub.func
                if (
                    isinstance(f, ast.Attribute)
                    and f.attr == "fsync"
                    and _unparse(f.value) == "os"
                ):
                    return True
        return False

    def _scope_renames(self, scope: ast.AST) -> bool:
        """A rename call that plausibly lands a staged file: ``os.replace``/
        ``os.rename`` or a filesystem object's ``.rename`` (``fs``,
        ``self.fs``, ``_fs.filesystem_for(...)``). A bare ``str.replace``
        on some unrelated variable must NOT exempt the write."""
        for sub in ast.walk(scope):
            if isinstance(sub, ast.Call):
                f = sub.func
                if isinstance(f, ast.Attribute) and f.attr in self._RENAMES:
                    recv = _unparse(f.value)
                    if recv == "os" or "fs" in recv.lower():
                        return True
        return False

    def _scope_commits_manifest_after(self, scope: ast.AST, lineno: int) -> bool:
        """The manifest-last idiom: the scope calls one of the shared
        durable-write commit helpers AFTER this write (by line), so the
        staged file only becomes load-bearing once the manifest lands
        atomically. A helper call BEFORE the write is manifest-first —
        it commits nothing about the bytes written later, so it must not
        exempt them."""
        for sub in ast.walk(scope):
            if not isinstance(sub, ast.Call):
                continue
            f = sub.func
            name = f.attr if isinstance(f, ast.Attribute) else (
                f.id if isinstance(f, ast.Name) else None
            )
            if name in self._COMMIT_HELPERS and sub.lineno > lineno:
                return True
        return False


# ---------------------------------------------------------------------------
# lock-guard
# ---------------------------------------------------------------------------

#: Method calls that mutate common containers in place.
_MUTATORS = {
    "append", "appendleft", "add", "pop", "popleft", "popitem", "update",
    "clear", "remove", "discard", "extend", "insert", "setdefault",
}


class LockGuardRule(Rule):
    """For classes declaring the ``_lock`` contract (``self._lock =
    threading.Lock()`` in ``__init__``), every attribute the class
    mutates under ``with self._lock`` is a GUARDED attribute — and any
    mutation of it outside the lock (outside ``__init__``, which is
    happens-before publication, and outside ``*_locked`` helpers, the
    repo's called-with-lock-held convention) is a race waiting for a
    second thread."""

    id = "lock-guard"
    hint = (
        "mutate under `with self._lock` (or move into a *_locked helper "
        "called with the lock held)"
    )

    def start_file(self, ctx: FileContext) -> None:
        # class qualname -> {attr: [(under_lock, in_init_or_locked, lineno, qual)]}
        self._mutations: Dict[str, List[Tuple[str, bool, bool, int, str]]] = {}
        self._declares_lock: Set[str] = set()

    def _class_key(self, walker: Walker) -> Optional[str]:
        if not walker.class_stack:
            return None
        return ".".join(c.name for c in walker.class_stack)

    @staticmethod
    def _exempt(walker: Walker) -> bool:
        """Mutations in __init__ (pre-publication) or *_locked helpers
        (called with the lock held by convention) are compliant."""
        for f in walker.func_stack:
            name = getattr(f, "name", "")
            if name == "__init__" or name.endswith("_locked"):
                return True
        return False

    def _record(self, walker: Walker, attr: str, lineno: int) -> None:
        key = self._class_key(walker)
        if key is None or not walker.func_stack:
            return
        self._mutations.setdefault(key, []).append(
            (
                attr,
                ("self", "_lock") in walker.lock_stack,
                self._exempt(walker),
                lineno,
                walker.qualname,
            )
        )

    @staticmethod
    def _self_attr(node: ast.AST) -> Optional[str]:
        if (
            isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name)
            and node.value.id == "self"
        ):
            return node.attr
        return None

    def visit(self, node: ast.AST, walker: Walker) -> None:
        if isinstance(node, (ast.Assign, ast.AugAssign, ast.Delete, ast.AnnAssign)):
            targets = (
                node.targets
                if isinstance(node, ast.Assign)
                else [node.target]
                if isinstance(node, (ast.AugAssign, ast.AnnAssign))
                else node.targets
            )
            for t in targets:
                attr = self._self_attr(t)
                if attr == "_lock" and isinstance(node, ast.Assign):
                    key = self._class_key(walker)
                    if key is not None:
                        self._declares_lock.add(key)
                    continue
                if attr is not None:
                    self._record(walker, attr, node.lineno)
                    continue
                # self.X[...] = v / del self.X[...]
                if isinstance(t, ast.Subscript):
                    attr = self._self_attr(t.value)
                    if attr is not None:
                        self._record(walker, attr, node.lineno)
        elif isinstance(node, ast.Call):
            f = node.func
            if isinstance(f, ast.Attribute) and f.attr in _MUTATORS:
                attr = self._self_attr(f.value)
                if attr is not None:
                    self._record(walker, attr, node.lineno)

    def finish_file(self, ctx: FileContext) -> None:
        for cls, muts in self._mutations.items():
            if cls not in self._declares_lock:
                continue
            guarded = {
                attr for attr, under, _ex, _ln, _q in muts if under
            }
            for attr, under, exempt, lineno, qual in muts:
                if attr in guarded and not under and not exempt:
                    self.emit(
                        ctx,
                        lineno,
                        f"{cls}.{attr} is mutated under self._lock "
                        f"elsewhere but written WITHOUT it in {qual}",
                        detail=f"{cls}.{attr}@{qual}",
                    )


# ---------------------------------------------------------------------------
# lock-order
# ---------------------------------------------------------------------------


class LockOrderRule(Rule):
    """Static lock-acquisition graph over every scanned module: a lexical
    ``with lockB`` inside ``with lockA`` adds edge A→B. Any CYCLE in the
    resulting digraph is a potential lock-order inversion — two threads
    entering the cycle from different nodes deadlock. Lock identity is
    ``module.Class.attr`` for ``self.*lock*`` attributes and
    ``module.name`` for module-level locks (instances of one class are
    conflated — conservative, the direction a deadlock checker must
    err)."""

    id = "lock-order"
    hint = "acquire these locks in one global order (or merge them)"

    def __init__(self) -> None:
        super().__init__()
        # edge -> first (path, line) observed
        self.edges: Dict[Tuple[str, str], Tuple[str, int]] = {}

    def _lock_id(self, walker: Walker, ident: Tuple[str, str]) -> str:
        mod = os.path.splitext(walker.ctx.name)[0]
        kind, name = ident
        if kind == "self" and walker.class_stack:
            return f"{mod}.{walker.class_stack[-1].name}.{name}"
        return f"{mod}.{name}"

    def visit(self, node: ast.AST, walker: Walker) -> None:
        if not isinstance(node, ast.With):
            return
        # visit() runs before the walker pushes this With's own locks, so
        # a multi-item `with a_lock, b_lock:` threads its items manually:
        # item N is acquired while items 0..N-1 (and every enclosing
        # lock) are held
        held = [self._lock_id(walker, h) for h in walker.lock_stack]
        for item in node.items:
            ident = Walker.lock_ident(item.context_expr)
            if ident is None:
                continue
            inner = self._lock_id(walker, ident)
            for outer in held:
                # outer == inner is KEPT: `with self.X: with self.X:` is
                # the same instance by construction (both spell `self`) —
                # a guaranteed self-deadlock on a non-reentrant Lock,
                # reported via the self-loop branch of the SCC scan
                self.edges.setdefault(
                    (outer, inner), (walker.ctx.rel, node.lineno)
                )
            held.append(inner)

    def finish(self, repo: RepoContext) -> None:
        graph: Dict[str, Set[str]] = {}
        for (a, b) in self.edges:
            graph.setdefault(a, set()).add(b)
            graph.setdefault(b, set())
        from tools.graftlint.harness import Finding

        for cycle in self._cycles(graph):
            a, b = cycle[0], cycle[1 % len(cycle)]
            path, line = self.edges.get((a, b), ("<multiple>", 0))
            ring = " -> ".join(cycle + [cycle[0]])
            self.findings.append(
                Finding(
                    rule=self.id,
                    path=path,
                    line=line,
                    message=f"lock-order cycle (potential deadlock): {ring}",
                    hint=self.hint,
                    detail="cycle:" + "|".join(sorted(cycle)),
                )
            )

    @staticmethod
    def _cycles(graph: Dict[str, Set[str]]) -> List[List[str]]:
        """Strongly-connected components of size > 1 (plus self-loops):
        each is reported once as a sorted node ring."""
        index: Dict[str, int] = {}
        low: Dict[str, int] = {}
        on_stack: Set[str] = set()
        stack: List[str] = []
        out: List[List[str]] = []
        counter = [0]

        def strongconnect(v: str) -> None:
            index[v] = low[v] = counter[0]
            counter[0] += 1
            stack.append(v)
            on_stack.add(v)
            for w in graph.get(v, ()):
                if w not in index:
                    strongconnect(w)
                    low[v] = min(low[v], low[w])
                elif w in on_stack:
                    low[v] = min(low[v], index[w])
            if low[v] == index[v]:
                comp = []
                while True:
                    w = stack.pop()
                    on_stack.discard(w)
                    comp.append(w)
                    if w == v:
                        break
                if len(comp) > 1 or v in graph.get(v, ()):
                    out.append(sorted(comp))

        for v in sorted(graph):
            if v not in index:
                strongconnect(v)
        return out


# ---------------------------------------------------------------------------
# except-swallow
# ---------------------------------------------------------------------------


class ExceptSwallowRule(Rule):
    """Every ``except Exception``/``except BaseException`` must do one of:
    re-raise, bump a counter (preferably an ``*.errors``/``*_errors``
    family — the swallow stays observable on the pulse/doctor), or carry
    an explicit ``# graftlint: swallow(<reason>)`` pragma documenting why
    silence is correct. A reasonless pragma is itself a finding."""

    id = "except-swallow"
    hint = (
        "re-raise, bump an *.errors counter, or annotate "
        "`# graftlint: swallow(<why silence is correct>)`"
    )

    _BROAD = {"Exception", "BaseException"}

    def start_file(self, ctx: FileContext) -> None:
        self._ordinals: Dict[str, int] = {}

    def _is_broad(self, type_node: Optional[ast.AST]) -> bool:
        if type_node is None:
            return True  # bare `except:` is the broadest spelling of all
        if isinstance(type_node, ast.Name):
            return type_node.id in self._BROAD
        if isinstance(type_node, ast.Tuple):
            return any(self._is_broad(e) for e in type_node.elts)
        return False

    @classmethod
    def _handler_complies(cls, handler: ast.ExceptHandler) -> bool:
        """A ``raise`` reachable on the except path, or a counter bump on a
        metrics registry. Nested function bodies do NOT count (a raise in a
        closure never fires on this path), and neither does ``list.count``/
        ``str.count`` — the receiver must look like a registry."""
        for sub in cls._walk_no_defs(handler.body):
            if isinstance(sub, ast.Raise):
                return True
            if isinstance(sub, ast.Call):
                f = sub.func
                if isinstance(f, ast.Attribute) and f.attr == "count":
                    recv = _unparse(f.value).rsplit(".", 1)[-1]
                    if recv in ("METRICS", "metrics"):
                        return True
        return False

    @staticmethod
    def _walk_no_defs(body):
        stack = list(body)
        while stack:
            node = stack.pop()
            yield node
            if isinstance(
                node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
            ):
                continue  # a nested def's body never runs on this path
            stack.extend(ast.iter_child_nodes(node))

    def visit(self, node: ast.AST, walker: Walker) -> None:
        if not isinstance(node, ast.ExceptHandler):
            return
        if not self._is_broad(node.type):
            return
        ctx = walker.ctx
        reason = ctx.pragma(node.lineno, "swallow")
        if reason:
            return
        if self._handler_complies(node):
            return
        qual = walker.qualname
        n = self._ordinals.get(qual, 0)
        self._ordinals[qual] = n + 1
        spelled = _unparse(node.type) if node.type is not None else "<bare>"
        if reason == "":
            msg = (
                f"except {spelled} carries a swallow pragma with no reason "
                f"in {qual}"
            )
        else:
            msg = (
                f"except {spelled} swallows without re-raise, counter, or "
                f"pragma in {qual}"
            )
        self.emit(ctx, node.lineno, msg, detail=f"except@{qual}#{n}")


# ---------------------------------------------------------------------------
# vocabulary
# ---------------------------------------------------------------------------


class VocabularyRule(Rule):
    """Call sites must use REGISTERED names (tpu_tfrecord/vocabulary.py),
    and the README's generated vocabulary block must match the registry —
    drift in either direction fails.

    Literal first arguments are checked against the right kind; f-strings
    are checked by their leading constant against the registered dynamic
    prefixes; everything else (variables, ``X + ".errors"``) is
    statically unknowable and skipped — the dynamic spellings in tree all
    ride registered prefixes/suffixes by construction."""

    id = "vocab-unregistered"
    DOCS_ID = "vocab-docs"
    hint = (
        "register the name in tpu_tfrecord/vocabulary.py and refresh the "
        "README block (python -m tools.graftlint --vocab-md)"
    )

    _METHOD_KINDS = {
        "count": "counter",
        "counter": "counter",
        "add": "stage",
        "observe": "stage",
        "stage": "stage",
        "timed": "stage",
        "gauge": "gauge",
        "gauge_value": "gauge",
    }
    _SPAN_FUNCS = {"span", "instant", "record_span"}
    _SPAN_RECEIVERS = {"telemetry", "RECORDER"}

    def __init__(self, vocab=None) -> None:
        super().__init__()
        if vocab is None:
            from tpu_tfrecord import vocabulary as vocab
        self.vocab = vocab

    def _call_kind(self, node: ast.Call) -> Optional[str]:
        fn = node.func
        if isinstance(fn, ast.Name):
            if fn.id in ("timed", "kernel_trace"):  # compile_cache.kernel_trace is a timed
                return "stage"
            if fn.id in self._SPAN_FUNCS:
                return "span"
            return None
        if not isinstance(fn, ast.Attribute):
            return None
        recv = _unparse(fn.value)
        if fn.attr in self._SPAN_FUNCS:
            tail = recv.rsplit(".", 1)[-1]
            return "span" if tail in self._SPAN_RECEIVERS else None
        kind = self._METHOD_KINDS.get(fn.attr)
        if kind is None:
            return None
        tail = recv.rsplit(".", 1)[-1]
        # only metrics registries: `METRICS.count`, `self.metrics.add`,
        # `metrics.gauge` — never `seen.add` / `conns.discard`
        return kind if tail in ("METRICS", "metrics") else None

    def visit(self, node: ast.AST, walker: Walker) -> None:
        if not isinstance(node, ast.Call) or not node.args:
            return
        kind = self._call_kind(node)
        if kind is None:
            return
        arg = node.args[0]
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            name = arg.value
            if not self.vocab.is_registered(name, kind):
                self.emit(
                    walker.ctx,
                    node.lineno,
                    f"unregistered {kind} name {name!r} at "
                    f"{walker.qualname}",
                    detail=f"{kind}:{name}",
                )
        elif isinstance(arg, ast.JoinedStr) and arg.values:
            head = arg.values[0]
            if isinstance(head, ast.Constant) and isinstance(head.value, str):
                prefix = head.value
                dyn = self.vocab.DYNAMIC_PREFIXES.get(kind, {})
                if not any(prefix.startswith(p) for p in dyn):
                    self.emit(
                        walker.ctx,
                        node.lineno,
                        f"dynamic {kind} name f-string {prefix!r}... has no "
                        f"registered dynamic prefix ({walker.qualname})",
                        detail=f"{kind}:f:{prefix}",
                    )

    def finish(self, repo: RepoContext) -> None:
        from tools.graftlint.harness import Finding

        v = self.vocab
        try:
            with open(repo.readme, "r", encoding="utf-8") as fh:
                readme = fh.read()
        except OSError as e:
            self.findings.append(
                Finding(
                    rule=self.DOCS_ID, path="README.md", line=1,
                    message=f"README unreadable: {e}", hint=self.hint,
                    detail="readme-unreadable",
                )
            )
            return
        begin, end = v.VOCABULARY_BEGIN, v.VOCABULARY_END
        i, j = readme.find(begin), readme.find(end)
        if i < 0 or j < 0 or j < i:
            self.findings.append(
                Finding(
                    rule=self.DOCS_ID, path="README.md", line=1,
                    message="README has no generated vocabulary block "
                    f"({begin.split(' ')[0]}...)",
                    hint=self.hint, detail="readme-block-missing",
                )
            )
            return
        block = readme[i : j + len(end)]
        want = v.vocabulary_markdown()
        if block.strip() != want.strip():
            line = readme.count("\n", 0, i) + 1
            # name the first drifted entry so the finding is actionable
            got_lines = set(block.splitlines())
            missing = [
                ln for ln in want.splitlines() if ln not in got_lines
            ]
            first = missing[0] if missing else "(entries removed)"
            self.findings.append(
                Finding(
                    rule=self.DOCS_ID, path="README.md", line=line,
                    message="README vocabulary block is stale vs "
                    f"tpu_tfrecord/vocabulary.py (first drift: {first!r})",
                    hint=self.hint, detail="readme-block-stale",
                )
            )


def default_rules() -> List[Rule]:
    return [
        ClockDisciplineRule(),
        AtomicWriteRule(),
        LockGuardRule(),
        LockOrderRule(),
        ExceptSwallowRule(),
        VocabularyRule(),
    ]
