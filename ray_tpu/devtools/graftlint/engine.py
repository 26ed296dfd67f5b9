"""graftlint analysis engine: one shared ``ast`` walk per module.

Everything the rule families consume is computed here, once:

- **import/alias resolution** — ``resolve()`` maps a dotted expression
  (``A.blockwise_attention`` after ``import ray_tpu.ops.attention as A``)
  to its fully qualified name, so rules match *symbols*, not spellings.
- **lock identification + with-block context** — attributes assigned from
  ``threading.Lock/RLock/Condition`` or ``util.contention.timed_lock/
  timed_rlock`` are lock attrs; ``Condition(self.x)`` remembers its base
  lock. Every statement is walked with the lexically-held lock set, so
  rules see "this write/call happened under ``self.lock``".
- **thread classification** — ``threading.Thread(target=self.m)`` marks
  ``m`` a thread entry; an intra-class ``self.m()`` call graph gives each
  method's reachability from thread entries vs the public API vs
  ``__init__``-only setup.
- **suppressions** — ``# graftlint: disable=rule1,rule2 -- reason`` on a
  line (or on its own line, applying to the next line) suppresses those
  rules there. A missing ``-- reason`` is itself reported (rule
  ``bare-suppression``): judged-intentional violations carry their
  justification in the tree, never a silent baseline entry.

The engine is stdlib-only (``ast`` + ``tokenize`` level machinery) and
must stay importable without jax — ``make lint`` runs it in every
environment.
"""

from __future__ import annotations

import ast
import hashlib
import os
import pickle
import re
import sys
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, FrozenSet, List, Optional, Set, Tuple

# attributes assigned from these callables are lock objects
LOCK_FACTORIES = {
    "threading.Lock": "lock",
    "threading.RLock": "rlock",
    "threading.Condition": "cond",
    "ray_tpu.util.contention.timed_lock": "lock",
    "ray_tpu.util.contention.timed_rlock": "rlock",
}

# fallback when the constructor is out of view: a `with self.<x>:` whose
# name *reads* like a lock is still treated as one
_LOCKISH_NAME = re.compile(r"(^|_)(lock|mutex|rlock|cv|cond)s?($|_)|_cv$")

_SUPPRESS_RE = re.compile(
    r"#\s*graftlint:\s*disable=([A-Za-z0-9_,\- ]+?)\s*(?:--\s*(.*))?$")
_PATH_OVERRIDE_RE = re.compile(r"#\s*graftlint:\s*path=(\S+)")

TIMER_CALLS = {"time.monotonic", "time.perf_counter", "time.time",
               "time.perf_counter_ns", "time.monotonic_ns"}


def is_lockish(name: str) -> bool:
    return bool(_LOCKISH_NAME.search(name))


def dotted_parts(node: ast.AST) -> Optional[List[str]]:
    """``a.b.c`` -> ["a","b","c"]; None for non-name expressions."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return parts[::-1]
    return None


@dataclass(frozen=True)
class Suppression:
    line: int
    rules: Tuple[str, ...]
    reason: str
    comment_line: int  # where the comment itself sits


@dataclass
class LockInfo:
    attr: str             # "lock", "_ref_lock", ... (no "self." prefix)
    kind: str             # "lock" | "rlock" | "cond"
    cond_base: Optional[str] = None  # Condition(self.X) -> "X"
    line: int = 0


@dataclass
class AttrWrite:
    attr: str
    line: int
    method: str           # method qualname within the class
    locks: FrozenSet[str]  # lock keys held ("self.lock", "_runtime_lock")
    kind: str             # "assign" | "aug" | "subscript"
    in_nested_func: bool  # inside a closure defined in the method


@dataclass
class CallSite:
    line: int
    func: str                     # enclosing function qualname ("" = module)
    fq: Optional[str]             # resolved fully-qualified target
    parts: Optional[Tuple[str, ...]]  # raw dotted parts of the callee
    locks: FrozenSet[str]
    loop_depth: int
    node: ast.Call


@dataclass
class FunctionInfo:
    name: str
    qualname: str
    node: ast.AST
    class_name: Optional[str]
    lineno: int
    self_calls: Set[str] = field(default_factory=set)
    calls_timer: bool = False
    # with-lock acquisitions made (lexically) anywhere in the body
    acquires: Set[str] = field(default_factory=set)


@dataclass
class ClassInfo:
    name: str
    node: ast.ClassDef
    lineno: int
    locks: Dict[str, LockInfo] = field(default_factory=dict)
    thread_targets: Set[str] = field(default_factory=set)
    methods: Dict[str, FunctionInfo] = field(default_factory=dict)
    writes: List[AttrWrite] = field(default_factory=list)
    # ordered (outer, inner, line, via) lock acquisitions; `via` names the
    # called method when the inner acquisition is one call level away
    lock_pairs: List[Tuple[str, str, int, str]] = field(default_factory=list)

    # -- reachability ---------------------------------------------------

    def _closure(self, roots: Set[str]) -> Set[str]:
        seen, work = set(), [r for r in roots if r in self.methods]
        while work:
            m = work.pop()
            if m in seen:
                continue
            seen.add(m)
            for callee in self.methods[m].self_calls:
                if callee in self.methods and callee not in seen:
                    work.append(callee)
        return seen

    def thread_reachable(self) -> Set[str]:
        return self._closure(set(self.thread_targets))

    def api_reachable(self) -> Set[str]:
        roots = {m for m in self.methods
                 if not m.startswith("_") or m in ("__call__", "__enter__",
                                                   "__exit__")}
        return self._closure(roots)

    def init_only(self) -> Set[str]:
        """Methods reachable from __init__ but from no API/thread root —
        single-threaded setup context."""
        init = self._closure({"__init__"})
        return init - self.api_reachable() - self.thread_reachable()


class ModuleIndex:
    """Per-file analysis product consumed by the rules."""

    def __init__(self, path: Path, display: str, scope_rel: str,
                 source: str):
        self.path = path
        self.display = display
        self.scope_rel = scope_rel  # "ray_tpu/..." posix path for scoping
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=str(path))
        self.parse_error: Optional[str] = None
        self.imports: Dict[str, str] = {}
        self.module_name = self._module_name()
        self.classes: Dict[str, ClassInfo] = {}
        self.functions: Dict[str, FunctionInfo] = {}  # by qualname
        self.module_locks: Set[str] = set()
        # (outer, inner, line, via) pairs acquired in module-level
        # functions — the class-free twin of ClassInfo.lock_pairs, so the
        # global lock-order graph sees edges outside any class
        self.lock_pairs: List[Tuple[str, str, int, str]] = []
        self.calls: List[CallSite] = []
        self.module_import_nodes: List[Tuple[int, str]] = []  # (line, fq)
        self.all_import_nodes: List[Tuple[int, str]] = []     # incl. nested
        self.suppressions: List[Suppression] = []
        self._suppress_map: Dict[int, Set[str]] = {}
        self._scan_comments()
        _Indexer(self).run()

    # -- identity -------------------------------------------------------

    def _module_name(self) -> str:
        rel = self.scope_rel
        if rel.endswith(".py"):
            rel = rel[:-3]
        return rel.replace("/", ".").removesuffix(".__init__")

    @property
    def package(self) -> str:
        # an __init__.py IS its package — relative imports resolve
        # against it, not its parent
        if self.scope_rel.endswith("/__init__.py"):
            return self.module_name
        return self.module_name.rpartition(".")[0]

    # -- comments: suppressions + path override -------------------------

    def _scan_comments(self) -> None:
        # real COMMENT tokens only — a disable= example inside a docstring
        # must not suppress anything (or demand a reason)
        import io
        import tokenize

        if "graftlint:" not in self.source:
            return
        try:
            tokens = list(tokenize.generate_tokens(
                io.StringIO(self.source).readline))
        except (tokenize.TokenError, IndentationError):
            return
        # statement spans: an own-line suppression covers the whole next
        # statement (incl. multi-line calls/comprehensions); a trailing
        # one covers the statement starting on its line
        spans = {}  # start line -> (start, end)
        _compound = (ast.If, ast.For, ast.AsyncFor, ast.While, ast.With,
                     ast.AsyncWith, ast.Try, ast.FunctionDef,
                     ast.AsyncFunctionDef, ast.ClassDef)
        for node in ast.walk(self.tree):
            if isinstance(node, ast.stmt) and hasattr(node, "end_lineno"):
                if isinstance(node, _compound):
                    # cover the HEADER only — a suppression before a
                    # def/with/if must not blanket the whole body
                    body = getattr(node, "body", None) or [node]
                    end = max(node.lineno, body[0].lineno - 1)
                else:
                    end = node.end_lineno or node.lineno
                cur = spans.get(node.lineno)
                if cur is None or end - node.lineno < cur[1] - cur[0]:
                    spans[node.lineno] = (node.lineno, end)

        def _cover(rules, start):
            span = spans.get(start, (start, start))
            for ln in range(span[0], span[1] + 1):
                self._suppress_map.setdefault(ln, set()).update(rules)
            return span[0]

        for tok in tokens:
            if tok.type != tokenize.COMMENT:
                continue
            m = _SUPPRESS_RE.search(tok.string)
            if not m:
                continue
            i = tok.start[0]
            rules = tuple(r.strip() for r in m.group(1).split(",")
                          if r.strip())
            reason = (m.group(2) or "").strip()
            own_line = self.lines[i - 1].lstrip().startswith("#")
            if own_line:
                # skip past continuation comment/blank lines to the code
                target = i + 1
                while (target <= len(self.lines)
                       and (not self.lines[target - 1].strip()
                            or self.lines[target - 1].lstrip()
                            .startswith("#"))):
                    target += 1
            else:
                target = i
            target = _cover(rules, target)
            self.suppressions.append(
                Suppression(target, rules, reason, i))

    def is_suppressed(self, line: int, rule: str) -> bool:
        rules = self._suppress_map.get(line)
        return bool(rules) and (rule in rules or "all" in rules)

    # -- resolution -----------------------------------------------------

    def resolve_parts(self, parts: List[str]) -> Optional[str]:
        """Fully-qualified name for a dotted reference, via the import
        table (falls back to local top-level defs)."""
        if not parts:
            return None
        head = parts[0]
        if head in self.imports:
            return ".".join([self.imports[head]] + parts[1:])
        if head == "self":
            return None
        if len(parts) == 1 and parts[0] in self.functions:
            return f"{self.module_name}.{parts[0]}"
        return None

    def resolve_node(self, node: ast.AST) -> Optional[str]:
        parts = dotted_parts(node)
        return self.resolve_parts(parts) if parts else None


class _Indexer:
    """Single recursive pass filling a ModuleIndex."""

    def __init__(self, mod: ModuleIndex):
        self.mod = mod

    def run(self) -> None:
        mod = self.mod
        # imports: one traversal; "module scope" = not enclosed in a
        # function (a try/if-guarded module-level import still runs at
        # import time, so it still counts)
        stack = [(mod.tree, False)]
        while stack:
            node, deferred = stack.pop()
            if isinstance(node, (ast.Import, ast.ImportFrom)):
                self._collect_import(node, top=not deferred)
                continue
            if isinstance(node, ast.If) and not deferred:
                # `if TYPE_CHECKING:` bodies never run — type-only
                # imports are not module-scope runtime imports
                parts = dotted_parts(node.test)
                fq = mod.resolve_parts(parts) if parts else None
                if fq == "typing.TYPE_CHECKING" or (
                        parts and parts[-1] == "TYPE_CHECKING"):
                    for child in node.body:
                        stack.append((child, True))
                    for child in node.orelse:
                        stack.append((child, False))
                    continue
            child_deferred = deferred or isinstance(
                node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda))
            for child in ast.iter_child_nodes(node):
                stack.append((child, child_deferred))
        # module-level locks: NAME = threading.Lock()
        for node in mod.tree.body:
            if (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)
                    and isinstance(node.value, ast.Call)):
                fq = mod.resolve_node(node.value.func)
                if fq in LOCK_FACTORIES:
                    mod.module_locks.add(node.targets[0].id)
        # classes: find lock attrs + thread targets first (any method may
        # assign them), then walk bodies with lock context
        for node in mod.tree.body:
            if isinstance(node, ast.ClassDef):
                self._index_class(node)
        # module-level functions
        for node in mod.tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                fi = FunctionInfo(node.name, node.name, node, None,
                                  node.lineno)
                mod.functions[fi.qualname] = fi
                _BodyWalker(mod, None, fi).walk_function(node)
        # bare module-level statements (scripts/benches): one shared
        # pseudo-function, registered so per-function rules (e.g. the
        # timing-barrier check) see module-level code too
        top = FunctionInfo("<module>", "<module>", mod.tree, None, 0)
        mod.functions[top.qualname] = top
        walker = _BodyWalker(mod, None, top)
        for node in mod.tree.body:
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                     ast.ClassDef)):
                walker.visit(node)

    def _collect_import(self, node: ast.AST, top: bool) -> None:
        mod = self.mod
        found: List[str] = []
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.asname:
                    mod.imports[a.asname] = a.name
                else:
                    head = a.name.split(".")[0]
                    mod.imports.setdefault(head, head)
                found.append(a.name)
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:  # relative: resolve against this package
                pkg_parts = mod.package.split(".") if mod.package else []
                keep = pkg_parts[:len(pkg_parts) - (node.level - 1)]
                base = ".".join(keep + ([node.module] if node.module else []))
            for a in node.names:
                if a.name == "*":
                    continue
                mod.imports[a.asname or a.name] = (
                    f"{base}.{a.name}" if base else a.name)
                found.append(f"{base}.{a.name}" if base else a.name)
        else:
            return
        target = mod.module_import_nodes if top else None
        for fq in found:
            mod.all_import_nodes.append((node.lineno, fq))
            if target is not None:
                target.append((node.lineno, fq))

    # -- class indexing -------------------------------------------------

    def _index_class(self, cnode: ast.ClassDef) -> None:
        mod = self.mod
        ci = ClassInfo(cnode.name, cnode, cnode.lineno)
        mod.classes[cnode.name] = ci
        methods = [n for n in cnode.body
                   if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
        # pass 1: lock attrs + thread targets + self-call graph
        for m in methods:
            fi = FunctionInfo(m.name, f"{cnode.name}.{m.name}", m,
                              cnode.name, m.lineno)
            ci.methods[m.name] = fi
            mod.functions[fi.qualname] = fi
            for node in ast.walk(m):
                if isinstance(node, ast.Assign) and isinstance(
                        node.value, ast.Call):
                    fq = mod.resolve_node(node.value.func)
                    kind = LOCK_FACTORIES.get(fq or "")
                    if kind:
                        for t in node.targets:
                            if (isinstance(t, ast.Attribute)
                                    and isinstance(t.value, ast.Name)
                                    and t.value.id == "self"):
                                base = None
                                if kind == "cond" and node.value.args:
                                    bp = dotted_parts(node.value.args[0])
                                    if bp and bp[0] == "self" and len(bp) == 2:
                                        base = bp[1]
                                ci.locks[t.attr] = LockInfo(
                                    t.attr, kind, base, node.lineno)
                if isinstance(node, ast.Call):
                    fq = mod.resolve_node(node.func)
                    if fq in ("threading.Thread", "threading.Timer"):
                        for kw in node.keywords:
                            if kw.arg == "target":
                                tp = dotted_parts(kw.value)
                                if tp and tp[0] == "self" and len(tp) == 2:
                                    ci.thread_targets.add(tp[1])
                    parts = dotted_parts(node.func)
                    if parts and parts[0] == "self" and len(parts) == 2:
                        fi.self_calls.add(parts[1])
                    if fq in TIMER_CALLS:
                        fi.calls_timer = True
        # pass 2: body walk with lock context
        for m in methods:
            _BodyWalker(mod, ci, ci.methods[m.name]).walk_function(m)


class _BodyWalker(ast.NodeVisitor):
    """Walks one function body tracking held locks + loop depth."""

    def __init__(self, mod: ModuleIndex, ci: Optional[ClassInfo],
                 fi: FunctionInfo):
        self.mod = mod
        self.ci = ci
        self.fi = fi
        self.locks: List[str] = []
        self.loop_depth = 0
        self.nested_depth = 0

    def walk_function(self, node) -> None:
        for stmt in node.body:
            self.visit(stmt)

    # -- lock recognition ----------------------------------------------

    def _lock_key(self, expr: ast.AST) -> Optional[str]:
        parts = dotted_parts(expr)
        if not parts:
            return None
        if parts[0] == "self" and len(parts) == 2:
            attr = parts[1]
            if self.ci and attr in self.ci.locks:
                return f"self.{attr}"
            if is_lockish(attr):
                return f"self.{attr}"
            return None
        if len(parts) == 1:
            name = parts[0]
            if name in self.mod.module_locks or is_lockish(name):
                return name
            return None
        # x.y.lock style: treat a lockish tail as a lock key
        if is_lockish(parts[-1]):
            return ".".join(parts)
        return None

    def visit_With(self, node: ast.With) -> None:
        for item in node.items:
            self.visit(item.context_expr)
        acquired = []
        for item in node.items:
            key = self._lock_key(item.context_expr)
            if key:
                acquired.append(key)
        for key in acquired:
            # pair against EVERY held lock, not just the innermost —
            # a->b->c vs c->a inverts on (a,c)
            sink = (self.ci.lock_pairs if self.ci is not None
                    else self.mod.lock_pairs)
            for held in self.locks:
                sink.append((held, key, node.lineno, ""))
            self.locks.append(key)
            if self.nested_depth == 0:
                # a closure's acquisition happens when the CALLBACK runs,
                # not when the defining method is called — attributing it
                # to the method fabricates call-through inversions
                self.fi.acquires.add(key)
        for stmt in node.body:
            self.visit(stmt)
        for _ in acquired:
            self.locks.pop()

    visit_AsyncWith = visit_With

    # -- loops ----------------------------------------------------------

    def visit_For(self, node) -> None:
        self.visit(node.iter)
        self.loop_depth += 1
        for stmt in node.body:
            self.visit(stmt)
        self.loop_depth -= 1
        for stmt in node.orelse:
            self.visit(stmt)

    visit_AsyncFor = visit_For

    def visit_While(self, node: ast.While) -> None:
        self.visit(node.test)
        self.loop_depth += 1
        for stmt in node.body:
            self.visit(stmt)
        self.loop_depth -= 1
        for stmt in node.orelse:
            self.visit(stmt)

    # -- nested functions: separate execution context --------------------

    def visit_FunctionDef(self, node) -> None:
        outer_locks, self.locks = self.locks, []
        outer_depth, self.loop_depth = self.loop_depth, 0
        self.nested_depth += 1
        for stmt in node.body:
            self.visit(stmt)
        self.nested_depth -= 1
        self.locks, self.loop_depth = outer_locks, outer_depth

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Lambda(self, node: ast.Lambda) -> None:
        outer_locks, self.locks = self.locks, []
        outer_depth, self.loop_depth = self.loop_depth, 0
        self.nested_depth += 1
        self.visit(node.body)
        self.nested_depth -= 1
        self.locks, self.loop_depth = outer_locks, outer_depth

    # -- events ----------------------------------------------------------

    def _record_write(self, target: ast.AST, kind: str, line: int) -> None:
        if self.ci is None:
            return
        if isinstance(target, (ast.Tuple, ast.List)):
            for elt in target.elts:
                self._record_write(elt, kind, line)
            return
        if isinstance(target, ast.Attribute) and isinstance(
                target.value, ast.Name) and target.value.id == "self":
            self.ci.writes.append(AttrWrite(
                target.attr, line, self.fi.name,
                frozenset(self.locks), kind, self.nested_depth > 0))
        elif isinstance(target, ast.Subscript):
            inner = target.value
            if isinstance(inner, ast.Attribute) and isinstance(
                    inner.value, ast.Name) and inner.value.id == "self":
                self.ci.writes.append(AttrWrite(
                    inner.attr, line, self.fi.name,
                    frozenset(self.locks), "subscript",
                    self.nested_depth > 0))

    def visit_Assign(self, node: ast.Assign) -> None:
        for t in node.targets:
            self._record_write(t, "assign", node.lineno)
        self.generic_visit(node)

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        self._record_write(node.target, "aug", node.lineno)
        self.generic_visit(node)

    def visit_AnnAssign(self, node: ast.AnnAssign) -> None:
        if node.value is not None:
            self._record_write(node.target, "assign", node.lineno)
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call) -> None:
        parts = dotted_parts(node.func)
        fq = self.mod.resolve_parts(parts) if parts else None
        self.mod.calls.append(CallSite(
            node.lineno, self.fi.qualname, fq,
            tuple(parts) if parts else None,
            frozenset(self.locks), self.loop_depth, node))
        if fq in TIMER_CALLS:
            self.fi.calls_timer = True
        # mutating container calls on self attrs count as writes
        if (self.ci is not None and parts and parts[0] == "self"
                and len(parts) == 3 and parts[2] in (
                    "append", "appendleft", "add", "pop", "popleft",
                    "update", "clear", "remove", "discard", "extend",
                    "setdefault")):
            self.ci.writes.append(AttrWrite(
                parts[1], node.lineno, self.fi.name,
                frozenset(self.locks), "mutcall", self.nested_depth > 0))
        self.generic_visit(node)


# ---------------------------------------------------------------------------
# project = a set of analyzed modules
# ---------------------------------------------------------------------------

class Project:
    def __init__(self, modules: List[ModuleIndex],
                 whole_package: bool = False):
        #: True when the lint scope covered the whole ray_tpu package —
        #: cross-file completeness checks (e.g. "documented failpoint has
        #: no call site") are only meaningful then
        self.whole_package = whole_package
        self.modules = modules
        self.by_scope: Dict[str, ModuleIndex] = {
            m.scope_rel: m for m in modules}

    def module(self, scope_rel: str) -> Optional[ModuleIndex]:
        return self.by_scope.get(scope_rel)

    def in_scope(self, prefix: str) -> List[ModuleIndex]:
        return [m for m in self.modules
                if m.scope_rel.startswith(prefix)]


def _scope_rel_for(path: Path) -> str:
    """Path used for rule scoping: the trailing ``ray_tpu/...`` segment
    when present (robust to cwd), else the basename. Fixture files
    override with ``# graftlint: path=ray_tpu/...``."""
    parts = list(path.parts)
    for i in range(len(parts) - 1, -1, -1):
        if parts[i] == "ray_tpu":
            return "/".join(parts[i:])
    return path.name


def load_module(path: Path, root: Optional[Path] = None) -> ModuleIndex:
    source = path.read_text()
    scope = _scope_rel_for(path)
    m = _PATH_OVERRIDE_RE.search("\n".join(source.splitlines()[:5]))
    if m:
        scope = m.group(1)
    if root is not None:
        try:
            display = path.resolve().relative_to(root.resolve()).as_posix()
        except ValueError:
            display = str(path)
    else:
        display = str(path)
    return ModuleIndex(path, display, scope, source)


# ---------------------------------------------------------------------------
# model cache (ISSUE 15): warm `make lint` re-analyzes only changed files
# ---------------------------------------------------------------------------

CACHE_DIR_NAME = ".graftlint_cache"
_CACHE_VERSION = 1
_engine_digest_memo: Optional[str] = None


def _engine_digest() -> str:
    """Invalidation key: a cached model is only valid for the engine
    source (and interpreter) that built it — ast node shapes and the
    analysis itself both change across versions."""
    global _engine_digest_memo
    if _engine_digest_memo is None:
        h = hashlib.sha256()
        h.update(Path(__file__).read_bytes())
        h.update(sys.version.encode())
        _engine_digest_memo = h.hexdigest()
    return _engine_digest_memo


def _set_display(mod: ModuleIndex, path: Path, root: Optional[Path]) -> None:
    # display is the only root-dependent field — recompute it after a
    # cache hit so findings render identically with any cwd/root
    if root is not None:
        try:
            mod.display = path.resolve().relative_to(
                root.resolve()).as_posix()
            return
        except ValueError:
            pass
    mod.display = str(path)


def _load_module_cached(f: Path, root: Optional[Path],
                        cache_dir: Path) -> ModuleIndex:
    """load_module through a (path, mtime_ns, size)-keyed pickle cache.
    Every failure mode (corrupt pickle, racing writer, read-only dir)
    falls back to a fresh parse — the cache can never change results,
    only skip work (parity-tested in test_graftlint.py)."""
    key = hashlib.sha256(
        str(f.resolve()).encode()).hexdigest()[:32]
    cpath = cache_dir / f"{key}.pkl"
    try:
        st = f.stat()
        with open(cpath, "rb") as fh:
            tag, mtime, size, digest, mod = pickle.load(fh)
        if (tag == _CACHE_VERSION and mtime == st.st_mtime_ns
                and size == st.st_size and digest == _engine_digest()
                and isinstance(mod, ModuleIndex)):
            _set_display(mod, f, root)
            return mod
    except Exception:
        pass
    mod = load_module(f, root)
    try:
        cache_dir.mkdir(exist_ok=True)
        tmp = cache_dir / f".{key}.{os.getpid()}.tmp"
        with open(tmp, "wb") as fh:
            pickle.dump((_CACHE_VERSION, f.stat().st_mtime_ns,
                         f.stat().st_size, _engine_digest(), mod),
                        fh, pickle.HIGHEST_PROTOCOL)
        tmp.replace(cpath)
    except Exception:
        pass
    return mod


def collect_files(paths: List[Path]) -> List[Path]:
    files: List[Path] = []
    seen = set()  # dedupe: a file named alongside its containing dir
    for p in paths:
        cands = sorted(p.rglob("*.py")) if p.is_dir() else (
            [p] if p.suffix == ".py" else [])
        for f in cands:
            key = f.resolve()
            if key in seen or "__pycache__" in f.parts:
                continue
            seen.add(key)
            files.append(f)
    return files


def build_project(paths: List[Path], root: Optional[Path] = None,
                  cache: bool = True):
    """Returns (Project, [Finding]) — the findings are parse errors.

    With ``cache=True`` and a ``root``, per-module models are pickled
    under ``<root>/.graftlint_cache/`` keyed (path, mtime_ns, size) +
    engine digest; rootless calls (single-fixture lints in tests) never
    touch the cache."""
    from ray_tpu.devtools.graftlint.model import Finding

    cache_dir = (root / CACHE_DIR_NAME) if (cache and root is not None) \
        else None
    modules, errors = [], []
    for f in collect_files(paths):
        try:
            if cache_dir is not None:
                modules.append(_load_module_cached(f, root, cache_dir))
            else:
                modules.append(load_module(f, root))
        except SyntaxError as e:
            errors.append(Finding(str(f), e.lineno or 0, "parse-error",
                                  f"syntax error: {e.msg}"))
    whole = any(p.is_dir() and (p.name == "ray_tpu"
                                or (p / "ray_tpu").is_dir())
                for p in paths)
    return Project(modules, whole_package=whole), errors


def run_rules(project: Project, rules) -> List:
    """Run rules, drop suppressed findings, return sorted findings."""
    by_display = {m.display: m for m in project.modules}
    findings = []
    for rule in rules:
        for f in rule.check(project):
            if getattr(rule, "suppressible", True):
                mod = by_display.get(f.path)
                if mod is not None and mod.is_suppressed(f.line, f.rule):
                    continue
            findings.append(f)
    return sorted(set(findings), key=lambda f: f.sort_key())
