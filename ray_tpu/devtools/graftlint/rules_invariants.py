"""AST ports of the architecture invariants (family ``invariants``).

Each rule here supersedes a regex grep that used to live in
``tests/test_invariants.py``. The AST versions are alias-aware, survive
multi-line call sites, and — unlike the greps — know the difference
between ``collections.Counter`` and a metrics ``Counter``.
"""

from __future__ import annotations

import ast
from typing import Iterator

from ray_tpu.devtools.graftlint.engine import Project
from ray_tpu.devtools.graftlint.model import (
    FAMILY_INVARIANTS,
    Finding,
    Rule,
    register,
)


@register
class PipeReceiverDiscipline(Rule):
    name = "pipe-receiver-discipline"
    family = FAMILY_INVARIANTS
    summary = ("one receiver thread demuxes each worker pipe: .recv()/"
               ".recv_bytes() only in worker._recv_loop, runtime's "
               "_serve_worker handshake + _reader_loop, and rpc.py's "
               "reader machinery")

    #: scope_rel -> function names allowed to block on a pipe read
    ALLOWED = {
        "ray_tpu/core/worker.py": {"_recv_loop"},
        "ray_tpu/core/runtime.py": {"_serve_worker", "_reader_loop"},
    }
    #: in cluster/, only rpc.py's reader machinery may block on a socket
    CLUSTER_ALLOWED = {"_recv_framed", "_client_handshake"}

    def check(self, project: Project) -> Iterator[Finding]:
        for mod in project.modules:
            allowed = self.ALLOWED.get(mod.scope_rel)
            in_cluster = mod.scope_rel.startswith("ray_tpu/cluster/")
            if allowed is None and not in_cluster:
                continue
            if allowed is None:
                if mod.scope_rel == "ray_tpu/cluster/rpc.py":
                    allowed = self.CLUSTER_ALLOWED
                else:
                    allowed = set()
            for cs in mod.calls:
                if not cs.parts or cs.parts[-1] not in ("recv",
                                                        "recv_bytes"):
                    continue
                func_name = cs.func.rpartition(".")[2] or cs.func
                if func_name in allowed:
                    continue
                yield self.finding(
                    mod, cs.line,
                    f"{'.'.join(cs.parts)}() in {cs.func}() — a second "
                    f"pipe reader races the demux thread and corrupts "
                    f"reply routing (CLAUDE.md one-receiver-thread "
                    f"invariant); route new message kinds through the "
                    f"existing reader ({', '.join(sorted(allowed)) or 'rpc.py'})")


@register
class CloudpickleFirst(Rule):
    name = "cloudpickle-first"
    family = FAMILY_INVARIANTS
    summary = ("serialization.serialize tries cloudpickle FIRST — plain "
               "pickle serializes __main__ functions by reference and "
               "breaks workers")

    def check(self, project: Project) -> Iterator[Finding]:
        mod = project.module("ray_tpu/core/serialization.py")
        if mod is None:
            return
        dumps = []
        for cs in mod.calls:
            if cs.func.rpartition(".")[2] != "serialize":
                continue
            if cs.parts and cs.parts[-1] == "dumps":
                dumps.append(cs)
        if not dumps:
            yield self.finding(
                mod, 1,
                "serialize() no longer calls any .dumps — the "
                "cloudpickle-first invariant can't be verified")
            return
        first = min(dumps, key=lambda c: c.line)
        fq = first.fq or ".".join(first.parts)
        if not fq.startswith("cloudpickle."):
            yield self.finding(
                mod, first.line,
                f"serialize()'s first serializer is {fq} — cloudpickle "
                f"must come FIRST (plain pickle serializes __main__ "
                f"functions by reference and breaks workers)")


@register
class AdhocMetric(Rule):
    name = "adhoc-metric"
    family = FAMILY_INVARIANTS
    summary = ("core/ and cluster/ create metrics only via "
               "metric_defs.get — ad-hoc Counter/Gauge/Histogram "
               "instances skip the help/prefix/uniqueness invariants and "
               "the generated README table")

    _SCOPES = ("ray_tpu/core/", "ray_tpu/cluster/")
    _METRIC_FQS = {f"ray_tpu.util.metrics.{n}"
                   for n in ("Counter", "Gauge", "Histogram")}

    def check(self, project: Project) -> Iterator[Finding]:
        for mod in project.modules:
            if not mod.scope_rel.startswith(self._SCOPES):
                continue
            for cs in mod.calls:
                if cs.fq in self._METRIC_FQS:
                    yield self.finding(
                        mod, cs.line,
                        f"ad-hoc {cs.fq.rpartition('.')[2]}() in core/"
                        f"cluster — define it in ray_tpu/util/"
                        f"metric_defs.py and fetch with "
                        f"metric_defs.get(name) so it lands in the "
                        f"generated README reference")


@register
class UndeadlinedWait(Rule):
    name = "undeadlined-wait"
    family = FAMILY_INVARIANTS
    summary = ("cluster-plane blocking waits carry deadlines: no bare "
               "event/condition .wait() in cluster/ — a wedged peer must "
               "surface a timeout, never park a thread forever")

    def _event_like(self, mod, ci, parts) -> bool:
        """Known Event/Condition attr, or an event-ish name."""
        import re

        name = parts[-2] if len(parts) >= 2 else parts[0]
        if (ci is not None and parts[0] == "self" and len(parts) == 3
                and parts[1] in ci.locks):
            return True
        return bool(re.search(
            r"(^|_)(ev|event|stop|cv|cond|ready|done|flag)\w*$", name))

    def check(self, project: Project) -> Iterator[Finding]:
        for mod in project.modules:
            if not mod.scope_rel.startswith("ray_tpu/cluster/"):
                continue
            for cs in mod.calls:
                if not cs.parts or cs.parts[-1] != "wait":
                    continue
                # a real deadline: any arg/keyword that is not literal
                # None (wait(None) / wait(timeout=None) still block
                # forever)
                deadline = [a for a in cs.node.args
                            if not (isinstance(a, ast.Constant)
                                    and a.value is None)]
                deadline += [k for k in cs.node.keywords
                             if not (isinstance(k.value, ast.Constant)
                                     and k.value.value is None)]
                if deadline:
                    continue
                ci = mod.classes.get(cs.func.split(".")[0])
                if not self._event_like(mod, ci, list(cs.parts)):
                    continue
                yield self.finding(
                    mod, cs.line,
                    f"bare {'.'.join(cs.parts)}() in cluster/ — pass a "
                    f"timeout (and loop) so a wedged peer can't park "
                    f"this thread forever (chaos-plane invariant, "
                    f"ISSUE 5)")
