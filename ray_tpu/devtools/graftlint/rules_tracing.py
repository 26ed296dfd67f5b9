"""Trace-plane discipline rules (family ``invariants``).

The trace plane (ISSUE 7) is only as analyzable as its span names: the
critical-path analyzer, the Perfetto export's categories, and operators
grepping ``/api/traces`` all key off the ``<layer>::<what>`` catalog in
``util/tracing.py``'s docstring. And the ``span()`` context is
THREAD-LOCAL — held open across a ``yield`` it leaks onto whatever the
worker thread runs next, silently mis-parenting every later span. Mirrors
the failpoint-sites literal+unique+doc-sync pattern.
"""

from __future__ import annotations

import ast
import re
from collections import defaultdict
from typing import Dict, Iterator, List, Optional, Set, Tuple

from ray_tpu.devtools.graftlint.engine import Project
from ray_tpu.devtools.graftlint.model import (
    FAMILY_INVARIANTS,
    Finding,
    Rule,
    register,
)

TRACING_MOD = "ray_tpu/util/tracing.py"
_SPAN_FNS = ("span", "manual_span", "record_span", "stamp")
_NAME_RE = re.compile(r"^[a-z0-9_.]+::[a-z0-9_.]+$")
_PREFIX_RE = re.compile(r"^[a-z0-9_.]+::$")
_CATALOG_LINE = re.compile(r"^\s{4}([a-z0-9_.]+::[a-z0-9_.<>]*)\s{2,}\S")


def documented_span_names(tracing_source: str
                          ) -> Tuple[Set[str], Set[str]]:
    """(exact names, dynamic prefixes) from the ``Span names`` block of
    util/tracing.py's docstring. An entry like ``lock::<name>`` documents
    the prefix ``lock::``; ``serve.handle::route`` documents itself."""
    tree = ast.parse(tracing_source)
    doc = ast.get_docstring(tree) or ""
    names: Set[str] = set()
    prefixes: Set[str] = set()
    in_block = False
    seen_entry = False
    for line in doc.splitlines():
        if line.startswith("Span names"):
            in_block = True
            continue
        if in_block:
            m = _CATALOG_LINE.match(line)
            if m:
                seen_entry = True
                entry = m.group(1)
                if "<" in entry:
                    prefixes.add(entry.split("<", 1)[0])
                else:
                    names.add(entry)
            elif seen_entry and line.strip() and not line.startswith(" "):
                break  # next top-level section (after the entries)
    return names, prefixes


def _is_span_call(cs) -> Optional[str]:
    """The span-API function name when ``cs`` records spans, else None."""
    if cs.fq and cs.fq.startswith("ray_tpu.util.tracing."):
        fn = cs.fq.rsplit(".", 1)[1]
        return fn if fn in _SPAN_FNS else None
    if (cs.parts and len(cs.parts) >= 2 and cs.parts[-2] == "tracing"
            and cs.parts[-1] in _SPAN_FNS):
        return cs.parts[-1]
    return None


def _span_name_arg(node: ast.Call):
    """(kind, value): ('literal', name) for a str constant,
    ('prefix', p) for an f-string with a literal ``<layer>::`` head,
    (None, None) otherwise."""
    if not node.args:
        return None, None
    arg = node.args[0]
    if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
        return "literal", arg.value
    if isinstance(arg, ast.JoinedStr) and arg.values:
        head = arg.values[0]
        if isinstance(head, ast.Constant) and isinstance(head.value, str) \
                and head.value.endswith("::"):
            return "prefix", head.value
    return None, None


@register
class TracingSpanNames(Rule):
    name = "tracing-span-names"
    family = FAMILY_INVARIANTS
    summary = ("tracing span/manual_span/record_span/stamp names are literal "
               "<layer>::<what> strings (or f-strings behind a literal "
               "<layer>:: prefix), unique per call site for exact names, "
               "and present in util/tracing.py's Span-names catalog")

    def check(self, project: Project) -> Iterator[Finding]:
        tr_mod = project.module(TRACING_MOD)
        documented = (documented_span_names(tr_mod.source)
                      if tr_mod is not None else None)
        literals: Dict[str, List[Tuple]] = defaultdict(list)
        used_prefixes: Set[str] = set()
        for mod in project.modules:
            if mod.scope_rel == TRACING_MOD:
                continue
            for cs in mod.calls:
                fn = _is_span_call(cs)
                if fn is None:
                    continue
                kind, value = _span_name_arg(cs.node)
                if kind is None:
                    yield self.finding(
                        mod, cs.line,
                        f"tracing.{fn}() with a non-literal name — span "
                        "names must be string literals (or f-strings "
                        "with a literal '<layer>::' prefix) so the "
                        "catalog, Perfetto categories, and critical-path "
                        "labels stay greppable")
                    continue
                if kind == "literal":
                    if not _NAME_RE.match(value):
                        yield self.finding(
                            mod, cs.line,
                            f"span name {value!r} does not follow the "
                            "'<layer>::<what>' convention "
                            "(lowercase dotted layer, '::', what)")
                        continue
                    literals[value].append((mod, cs.line))
                else:
                    if not _PREFIX_RE.match(value):
                        yield self.finding(
                            mod, cs.line,
                            f"span name prefix {value!r} does not follow "
                            "the '<layer>::' convention")
                        continue
                    used_prefixes.add(value)
                    if documented is not None and value not in documented[1]:
                        yield self.finding(
                            mod, cs.line,
                            f"span prefix '{value}<...>' is not in util/"
                            "tracing.py's Span-names catalog — add it "
                            "(the docstring is what operators and the "
                            "analyzers read)")
        for name, uses in sorted(literals.items()):
            if len(uses) > 1:
                locs = ", ".join(f"{m.display}:{ln}" for m, ln in uses)
                for m, ln in uses:
                    yield self.finding(
                        m, ln,
                        f"span name '{name}' is recorded from "
                        f"{len(uses)} call sites ({locs}) — exact names "
                        "are unique per call site so timeline segments "
                        "stay attributable; add a suffixed name")
            if documented is not None and name not in documented[0]:
                m, ln = uses[0]
                yield self.finding(
                    m, ln,
                    f"span name '{name}' is not in util/tracing.py's "
                    "Span-names catalog — add it there")
        if documented is not None and tr_mod is not None \
                and project.whole_package:
            stale = (documented[0] - set(literals)) | {
                p for p in documented[1] if p not in used_prefixes}
            for entry in sorted(stale):
                yield self.finding(
                    tr_mod, 1,
                    f"documented span name '{entry}' has no recording "
                    "call site left in the tree — remove it from the "
                    "Span-names catalog or restore the span")


def _yields_in_body(body: List[ast.stmt]) -> Optional[int]:
    """Line of the first yield lexically inside ``body``, not crossing
    into nested function/lambda scopes (their yields are other frames,
    executed after the with block exited)."""
    stack: List[ast.AST] = list(body)
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.Yield, ast.YieldFrom)):
            return node.lineno
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            continue  # prune nested scopes
        stack.extend(ast.iter_child_nodes(node))
    return None


@register
class TracingContextCapture(Rule):
    name = "tracing-context-capture"
    family = FAMILY_INVARIANTS
    summary = ("the thread-local span() context is never held open "
               "across a yield (generators must use manual_span/"
               "record_span), and tracing._ctx is never touched outside "
               "util/tracing.py")

    def check(self, project: Project) -> Iterator[Finding]:
        for mod in project.modules:
            if mod.scope_rel == TRACING_MOD:
                continue
            # gate: both findings need the tracing module or a span
            # callable in scope — skip the full-module walk elsewhere
            if not ("tracing" in mod.imports or "span" in mod.imports
                    or "span" in mod.functions):
                continue
            for node in ast.walk(mod.tree):
                if isinstance(node, (ast.With, ast.AsyncWith)):
                    if not any(
                            isinstance(item.context_expr, ast.Call)
                            and _is_span_call_node(mod, item.context_expr)
                            for item in node.items):
                        continue
                    line = _yields_in_body(node.body)
                    if line is not None:
                        yield self.finding(
                            mod, line,
                            "yield inside a `with tracing.span(...)` "
                            "body: the span context is thread-local and "
                            "leaks onto whatever this thread runs next "
                            "while the generator is suspended — record "
                            "the span with tracing.manual_span()/"
                            "record_span() instead")
                elif isinstance(node, ast.Attribute) \
                        and node.attr == "_ctx":
                    val = node.value
                    if isinstance(val, ast.Name) and val.id == "tracing":
                        yield self.finding(
                            mod, node.lineno,
                            "direct access to tracing._ctx outside util/"
                            "tracing.py — span context must re-enter "
                            "through the public tracing API "
                            "(current_traceparent()/span(parent=...))")


def _is_span_call_node(mod, call: ast.Call) -> bool:
    """Is this Call expression ``tracing.span(...)``? (context managers
    in With items are not in mod.calls' resolved index reliably, so
    match on the raw dotted parts.)"""
    from ray_tpu.devtools.graftlint.engine import dotted_parts

    parts = dotted_parts(call.func)
    if not parts:
        return False
    return (parts[-1] == "span"
            and (len(parts) == 1 or parts[-2] == "tracing"
                 or mod.resolve_parts(list(parts)) ==
                 "ray_tpu.util.tracing.span"))
