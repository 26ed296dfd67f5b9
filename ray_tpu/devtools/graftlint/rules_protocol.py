"""Wire-protocol drift rules (family ``protocol``, ISSUE 15).

The runtime speaks four multi-process vocabularies: the worker<->driver
pipe (casts / reqs / top-level frame kinds), GCS RPC methods, peer
(daemon<->daemon) RPC methods, and pubsub topics. Each one has three
surfaces that must agree: the *senders* (literal ops at call sites), the
*dispatch arms* (``if op == "...":`` chains in the designated handler
functions), and the checked-in catalog (``ray_tpu/core/protocol.py``).

These rules extract the first two from the AST and diff all three — the
failpoint-doc-sync pattern applied to the whole wire. A send without a
handler is a silently-dropped message; a handler without a sender is
dead protocol (r14's native migration left two: the driver's ``refpin``
cast arm and the worker's driver->worker ``batch`` arm, both kept as
regression fixtures); drift from the catalog means the review surface
lied.

Cross-surface checks only fire when both sides are in scope (whole-tree
lints); the catalog-membership direction works on a single file, which
is what the fixtures exercise.
"""

from __future__ import annotations

import ast
from pathlib import Path
from typing import Dict, Iterator, Optional, Tuple

from ray_tpu.devtools.graftlint.engine import ModuleIndex, Project
from ray_tpu.devtools.graftlint.model import (
    FAMILY_PROTOCOL,
    Finding,
    Rule,
    register,
)

CATALOG_SCOPE = "ray_tpu/core/protocol.py"
WORKER_SCOPE = "ray_tpu/core/worker.py"
RUNTIME_SCOPE = "ray_tpu/core/runtime.py"
GCS_SCOPE = "ray_tpu/cluster/gcs_server.py"
ADAPTER_SCOPE = "ray_tpu/cluster/adapter.py"


# ---------------------------------------------------------------------------
# catalog access: parse, never import (a lint run must not pull in the
# ray_tpu package)
# ---------------------------------------------------------------------------

def _parse_catalog(tree: ast.Module) -> Dict[str, Tuple[frozenset, int]]:
    out: Dict[str, Tuple[frozenset, int]] = {}
    for node in tree.body:
        if not (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)):
            continue
        val = node.value
        elts = None
        if (isinstance(val, ast.Call) and isinstance(val.func, ast.Name)
                and val.func.id == "frozenset" and val.args
                and isinstance(val.args[0], (ast.Set, ast.Tuple, ast.List))):
            elts = val.args[0].elts
        elif isinstance(val, (ast.Tuple, ast.Set, ast.List)):
            elts = val.elts
        if elts is None:
            continue
        lits = frozenset(e.value for e in elts
                         if isinstance(e, ast.Constant)
                         and isinstance(e.value, str))
        out[node.targets[0].id] = (lits, node.lineno)
    return out


def load_catalog(project: Project):
    """(catalog dict, catalog ModuleIndex or None). Prefers the catalog
    module inside the lint scope (so the drift test can substitute a
    modified one via the path override); falls back to the checked-in
    file on disk for single-file lints."""
    mod = project.module(CATALOG_SCOPE)
    if mod is not None:
        return _parse_catalog(mod.tree), mod
    p = Path(__file__).resolve().parents[2] / "core" / "protocol.py"
    try:
        return _parse_catalog(ast.parse(p.read_text())), None
    except Exception:
        return {}, None


# ---------------------------------------------------------------------------
# AST extraction helpers
# ---------------------------------------------------------------------------

def _const_str(node) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _literal_arg(call: ast.Call, idx: int) -> Optional[str]:
    if len(call.args) > idx:
        return _const_str(call.args[idx])
    return None


def dispatch_arms(mod: ModuleIndex, func_names,
                  var_names=("op", "kind", "method")) -> Dict[str, int]:
    """Literal arms of ``if <var> == "lit"`` / ``<var> in ("a", "b")`` /
    ``msg[0] == "lit"`` chains inside the named handler functions."""
    arms: Dict[str, int] = {}
    for fi in mod.functions.values():
        if fi.name not in func_names:
            continue
        for node in ast.walk(fi.node):
            if not (isinstance(node, ast.Compare) and len(node.ops) == 1
                    and isinstance(node.ops[0], (ast.Eq, ast.In))):
                continue
            left = node.left
            named = isinstance(left, ast.Name) and left.id in var_names
            # msg[0] == "batch" — restricted to the frame variable so a
            # payload compare (args[0] == "avail") is not a dispatch arm
            sub0 = (isinstance(left, ast.Subscript)
                    and isinstance(left.slice, ast.Constant)
                    and left.slice.value == 0
                    and isinstance(left.value, ast.Name)
                    and left.value.id == "msg")
            if not (named or sub0):
                continue
            comp = node.comparators[0]
            lits = []
            s = _const_str(comp)
            if s is not None:
                lits.append(s)
            elif isinstance(comp, (ast.Tuple, ast.List, ast.Set)):
                lits.extend(v for v in map(_const_str, comp.elts)
                            if v is not None)
            for lit in lits:
                arms.setdefault(lit, node.lineno)
    return arms


def _ifexp_branches(node):
    if isinstance(node, ast.IfExp):
        yield from _ifexp_branches(node.body)
        yield from _ifexp_branches(node.orelse)
    else:
        yield node


#: call tails that ship a ``(kind, ...)`` tuple down the pipe; _dropped
#: sees the same tuples (the chaos filter inspects the message it may
#: drop), so literal kinds reach the extractor even when the send itself
#: passes a variable
_SEND_TAILS = {"send", "_send", "_send_frame", "_dropped"}


def tuple_send_kinds(mod: ModuleIndex) -> Dict[str, int]:
    kinds: Dict[str, int] = {}
    for cs in mod.calls:
        if not cs.parts or cs.parts[-1] not in _SEND_TAILS:
            continue
        if not cs.node.args:
            continue
        for arg in _ifexp_branches(cs.node.args[0]):
            if isinstance(arg, ast.Tuple) and arg.elts:
                lit = _const_str(arg.elts[0])
                if lit is not None:
                    kinds.setdefault(lit, cs.line)
    return kinds


def _op_calls(mod: ModuleIndex, parts: Tuple[str, ...]) -> Dict[str, int]:
    """Literal first args of calls matching exactly ``parts``
    (e.g. ``self.cast("put", ...)``)."""
    out: Dict[str, int] = {}
    for cs in mod.calls:
        if cs.parts == parts:
            lit = _literal_arg(cs.node, 0)
            if lit is not None:
                out.setdefault(lit, cs.line)
    return out


def _fmt(names) -> str:
    return ", ".join(sorted(names))


# ---------------------------------------------------------------------------
# rule 1: the worker<->driver pipe
# ---------------------------------------------------------------------------

@register
class PipeProtocolSync(Rule):
    name = "pipe-protocol-sync"
    family = FAMILY_PROTOCOL
    summary = ("worker<->driver pipe vocabulary (casts, reqs, frame "
               "kinds) must agree three ways: every sender literal has a "
               "dispatch arm, every arm a sender, and both match the "
               "PIPE_* catalog in core/protocol.py")

    #: handler functions per direction (code facts, not protocol — the
    #: catalog holds the vocabulary, this holds where it is dispatched)
    RUNTIME_CAST_HANDLERS = ("_handle_cast",)
    RUNTIME_REQ_HANDLERS = ("_handle_req",)
    RUNTIME_KIND_HANDLERS = ("_handle_msg", "_serve_worker", "_reader_loop",
                             "_native_reader_loop")
    WORKER_KIND_HANDLERS = ("_dispatch_recv", "_recv_loop")

    def check(self, project: Project) -> Iterator[Finding]:
        catalog, cat_mod = load_catalog(project)
        casts = catalog.get("PIPE_CASTS", (frozenset(), 0))[0]
        reqs = catalog.get("PIPE_REQS", (frozenset(), 0))[0]
        wkinds = catalog.get("PIPE_WORKER_MSGS", (frozenset(), 0))[0]
        dkinds = catalog.get("PIPE_DRIVER_MSGS", (frozenset(), 0))[0]
        if not casts:
            return  # no catalog reachable: nothing to diff against

        worker = project.module(WORKER_SCOPE)
        runtime = project.module(RUNTIME_SCOPE)

        sent_casts = _op_calls(worker, ("self", "cast")) if worker else {}
        sent_reqs = _op_calls(worker, ("self", "request")) if worker else {}
        sent_wkinds = tuple_send_kinds(worker) if worker else {}
        sent_dkinds = tuple_send_kinds(runtime) if runtime else {}
        cast_arms = dispatch_arms(
            runtime, self.RUNTIME_CAST_HANDLERS) if runtime else {}
        req_arms = dispatch_arms(
            runtime, self.RUNTIME_REQ_HANDLERS) if runtime else {}
        wkind_arms = dispatch_arms(
            runtime, self.RUNTIME_KIND_HANDLERS) if runtime else {}
        dkind_arms = dispatch_arms(
            worker, self.WORKER_KIND_HANDLERS) if worker else {}

        surfaces = [
            # (vocab-name, catalog set, sender mod, sent, handler mod, arms)
            ("PIPE_CASTS", casts, worker, sent_casts, runtime, cast_arms),
            ("PIPE_REQS", reqs, worker, sent_reqs, runtime, req_arms),
            ("PIPE_WORKER_MSGS", wkinds, worker, sent_wkinds,
             runtime, wkind_arms),
            ("PIPE_DRIVER_MSGS", dkinds, runtime, sent_dkinds,
             worker, dkind_arms),
        ]
        for vocab, allowed, smod, sent, hmod, arms in surfaces:
            # catalog membership: works on a single file
            if smod is not None:
                for op, line in sorted(sent.items()):
                    if op not in allowed:
                        yield self.finding(
                            smod, line,
                            f"pipe op '{op}' is sent but absent from "
                            f"{vocab} in core/protocol.py — add it to the "
                            f"catalog (and a dispatch arm) or drop the "
                            f"send")
            if hmod is not None:
                for op, line in sorted(arms.items()):
                    if op not in allowed:
                        yield self.finding(
                            hmod, line,
                            f"dispatch arm for '{op}' is absent from "
                            f"{vocab} in core/protocol.py — dead protocol "
                            f"arm (r14-style leftover) or missing catalog "
                            f"entry")
            # sender<->handler sync: needs both modules in scope
            if smod is None or hmod is None:
                continue
            for op, line in sorted(sent.items()):
                if op in allowed and op not in arms:
                    yield self.finding(
                        smod, line,
                        f"pipe op '{op}' is sent but has no dispatch arm "
                        f"in {'/'.join(self._handlers_for(vocab))} — the "
                        f"message would be silently dropped")
            for op, line in sorted(arms.items()):
                if op in allowed and op not in sent:
                    yield self.finding(
                        hmod, line,
                        f"dispatch arm for '{op}' has no sender — dead "
                        f"protocol; remove the arm (keep the catalog "
                        f"honest) or wire up the sender")
            # catalog completeness: only when the catalog module itself
            # is in scope alongside both endpoints
            if cat_mod is not None:
                stale = allowed - set(sent) - set(arms)
                if stale:
                    line = catalog.get(vocab, (frozenset(), 1))[1]
                    yield self.finding(
                        cat_mod, line,
                        f"{vocab} lists {_fmt(stale)} but the tree "
                        f"neither sends nor handles them — stale catalog "
                        f"entries")

    def _handlers_for(self, vocab: str):
        return {
            "PIPE_CASTS": self.RUNTIME_CAST_HANDLERS,
            "PIPE_REQS": self.RUNTIME_REQ_HANDLERS,
            "PIPE_WORKER_MSGS": self.RUNTIME_KIND_HANDLERS,
            "PIPE_DRIVER_MSGS": self.WORKER_KIND_HANDLERS,
        }[vocab]


# ---------------------------------------------------------------------------
# rule 2: GCS + peer RPC
# ---------------------------------------------------------------------------

import re as _re

#: an RPC method literal: lowercase snake_case, >= 4 chars — excludes
#: ``memoryview.cast("B")`` and friends by construction
_METHOD_RE = _re.compile(r"^[a-z][a-z0-9_]{3,}$")

#: adapter helpers that take the method literal at arg index 1
_INDIRECT_SENDERS = {"_pg_call", "_call_with_attempt"}


def rpc_sender_literals(mod: ModuleIndex) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for cs in mod.calls:
        if not cs.parts:
            continue
        tail = cs.parts[-1]
        if tail in ("call", "cast"):
            # the worker's self.cast() is pipe vocabulary, not RPC
            if mod.scope_rel == WORKER_SCOPE and cs.parts == ("self",
                                                              "cast"):
                continue
            lit = _literal_arg(cs.node, 0)
        elif tail in _INDIRECT_SENDERS:
            lit = _literal_arg(cs.node, 1)
        else:
            continue
        if lit is not None and _METHOD_RE.match(lit):
            out.setdefault(lit, cs.line)
    return out


def _dict_key_literals(mod: ModuleIndex, func_names) -> Dict[str, int]:
    """String keys of dict literals inside the named functions — the
    adapter's local pg dispatch table names its peer methods this way."""
    out: Dict[str, int] = {}
    for fi in mod.functions.values():
        if fi.name not in func_names:
            continue
        for node in ast.walk(fi.node):
            if isinstance(node, ast.Dict):
                for k in node.keys:
                    lit = _const_str(k)
                    if lit is not None and _METHOD_RE.match(lit):
                        out.setdefault(lit, node.lineno)
    return out


@register
class RpcMethodSync(Rule):
    name = "rpc-method-sync"
    family = FAMILY_PROTOCOL
    summary = ("every RPC literal sent via .call()/.cast() must name a "
               "registered GCS rpc_* method or a peer _serve_peer arm, "
               "and every registered method must have a sender (dynamic "
               "'kv_'+op dispatch is cataloged as a prefix)")

    def check(self, project: Project) -> Iterator[Finding]:
        catalog, _cat_mod = load_catalog(project)
        gcs_rpc = catalog.get("GCS_RPC", (frozenset(), 0))[0]
        peer_rpc = catalog.get("PEER_RPC", (frozenset(), 0))[0]
        prefixes = tuple(catalog.get("GCS_RPC_DYNAMIC_PREFIXES",
                                     (frozenset(), 0))[0])
        if not gcs_rpc:
            return
        allowed = gcs_rpc | peer_rpc

        # senders: the whole scope
        sent: Dict[str, int] = {}
        for mod in project.modules:
            for lit, line in rpc_sender_literals(mod).items():
                if lit not in allowed:
                    yield self.finding(
                        mod, line,
                        f"RPC literal '{lit}' is not a cataloged GCS or "
                        f"peer method (core/protocol.py) — a typo here "
                        f"fails at runtime with method-not-found")
                sent.setdefault(lit, line)

        # handlers: GCS rpc_* methods
        gcs = project.module(GCS_SCOPE)
        if gcs is not None:
            for ci in gcs.classes.values():
                for mname, fi in ci.methods.items():
                    if not mname.startswith("rpc_"):
                        continue
                    op = mname[4:]
                    if op not in gcs_rpc:
                        yield self.finding(
                            gcs, fi.lineno,
                            f"rpc_{op} is registered but absent from "
                            f"GCS_RPC in core/protocol.py — update the "
                            f"catalog alongside the method")
                    elif (project.whole_package and op not in sent
                          and not any(op.startswith(p) for p in prefixes)):
                        yield self.finding(
                            gcs, fi.lineno,
                            f"rpc_{op} has no sender anywhere in the "
                            f"tree — dead RPC surface; remove it or add "
                            f"the caller (dynamic dispatch needs a "
                            f"GCS_RPC_DYNAMIC_PREFIXES entry)")

        # handlers: peer _serve_peer arms (+ the local pg dispatch table,
        # which names the same methods)
        adapter = project.module(ADAPTER_SCOPE)
        if adapter is not None:
            arms = dispatch_arms(adapter, ("_serve_peer",))
            for op, line in sorted(arms.items()):
                if op not in peer_rpc:
                    yield self.finding(
                        adapter, line,
                        f"_serve_peer arm '{op}' is absent from PEER_RPC "
                        f"in core/protocol.py — update the catalog "
                        f"alongside the arm")
                elif project.whole_package and op not in sent:
                    yield self.finding(
                        adapter, line,
                        f"_serve_peer arm '{op}' has no sender anywhere "
                        f"in the tree — dead peer protocol")
            if project.whole_package:
                for op in sorted(peer_rpc - set(arms)):
                    yield self.finding(
                        adapter, 1,
                        f"PEER_RPC lists '{op}' but _serve_peer has no "
                        f"arm for it — unhandled peer method")

        # catalog completeness for GCS methods
        if gcs is not None and project.whole_package:
            registered = {m[4:] for ci in gcs.classes.values()
                          for m in ci.methods if m.startswith("rpc_")}
            for op in sorted(gcs_rpc - registered):
                yield self.finding(
                    gcs, 1,
                    f"GCS_RPC lists '{op}' but no rpc_{op} method is "
                    f"registered — unhandled RPC")


# ---------------------------------------------------------------------------
# rule 3: pubsub topics
# ---------------------------------------------------------------------------

def _module_str_consts(mod: ModuleIndex) -> Dict[str, str]:
    out: Dict[str, str] = {}
    for node in mod.tree.body:
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)):
            lit = _const_str(node.value)
            if lit is not None:
                out[node.targets[0].id] = lit
    return out


def _channel_arg(mod: ModuleIndex, node: ast.Call, idx: int,
                 consts: Dict[str, str]) -> Optional[str]:
    if len(node.args) <= idx:
        return None
    arg = node.args[idx]
    lit = _const_str(arg)
    if lit is not None:
        return lit
    # CHANNEL module constants (util/tracing.py etc. publish this way)
    if isinstance(arg, ast.Name):
        return consts.get(arg.id)
    return None


@register
class PubsubTopicSync(Rule):
    name = "pubsub-topic-sync"
    family = FAMILY_PROTOCOL
    summary = ("every published pubsub channel must be in the "
               "PUBSUB_CHANNELS catalog, and every cataloged channel "
               "must be both published and subscribed somewhere — a "
               "topic nobody reads (or a subscription nobody feeds) is "
               "dead wire surface")

    def check(self, project: Project) -> Iterator[Finding]:
        catalog, cat_mod = load_catalog(project)
        channels = catalog.get("PUBSUB_CHANNELS", (frozenset(), 0))[0]
        if not channels:
            return
        published: Dict[str, Tuple[ModuleIndex, int]] = {}
        subscribed: Dict[str, Tuple[ModuleIndex, int]] = {}
        for mod in project.modules:
            consts = _module_str_consts(mod)
            for cs in mod.calls:
                if not cs.parts:
                    continue
                tail = cs.parts[-1]
                ch = None
                sink = None
                if tail == "_publish":
                    ch = _channel_arg(mod, cs.node, 0, consts)
                    sink = published
                elif tail in ("call", "cast"):
                    op = _literal_arg(cs.node, 0)
                    if op == "publish":
                        ch = _channel_arg(mod, cs.node, 1, consts)
                        sink = published
                    elif op == "subscribe":
                        ch = _channel_arg(mod, cs.node, 1, consts)
                        sink = subscribed
                if ch is None or sink is None:
                    continue
                if ch not in channels:
                    verb = ("published"
                            if sink is published else "subscribed")
                    yield self.finding(
                        mod, cs.line,
                        f"pubsub channel '{ch}' is {verb} but absent "
                        f"from PUBSUB_CHANNELS in core/protocol.py")
                sink.setdefault(ch, (mod, cs.line))
        if cat_mod is not None and project.whole_package:
            line = catalog.get("PUBSUB_CHANNELS", (frozenset(), 1))[1]
            for ch in sorted(channels - set(published)):
                yield self.finding(
                    cat_mod, line,
                    f"PUBSUB_CHANNELS lists '{ch}' but nothing publishes "
                    f"it — stale topic")
            for ch in sorted(channels - set(subscribed)):
                yield self.finding(
                    cat_mod, line,
                    f"PUBSUB_CHANNELS lists '{ch}' but nothing "
                    f"subscribes to it — topic published into the void")
