"""What runs inside the replica of a ``serve_state_family`` cell:
``FamilyLLM`` (``serve_family_replica.py``) with the program's scopes,
kernels and per-step counters taken from the FAMILY's file
(``families/<family>.py``: ``SCOPES``, ``KERNELS``, ``STEP_COUNTERS``)
instead of the lists ``serve_family_replica.py`` holds for its one family.
Everything else is imported: the weights, the engine's settings, the warm-up,
the probes, the tracer and the check. What had to be written again, because
the functions there read their module's own lists: the engine's step wrapper
(``StateFamilyEngine.step``), the mapping from instructions to scopes and
from operations to scope seconds (``scopes_of_instructions``,
``scope_seconds``, here with the lists as arguments), and the lowering of the
step program in ``_step_text`` (``FamilyLLM._step_scopes`` hands its text
straight to its own ``scopes_of_instructions``). Importing this module
imports no jax.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Sequence

from benchmark.kinds.serve_family_replica import FamilyLLM, load_family
from benchmark.kinds.serve_replica import BenchEngine


class StateFamilyEngine(BenchEngine):
    """``BenchEngine`` that also keeps, per step, how far the engine's
    counters named by the family grew in it."""

    def __init__(self, *args, step_counters: Sequence[str], **kw):
        super().__init__(*args, **kw)
        self.step_counters = tuple(step_counters)
        self.rec.step_counters = []

    def step(self):
        before = {k: self.stats.get(k, 0) for k in self.step_counters}
        n0 = len(self.rec.steps)
        busy = super().step()
        if len(self.rec.steps) > n0:
            self.rec.step_counters.append(
                {k: self.stats.get(k, 0) - v for k, v in before.items()})
        return busy


def scopes_of_instructions(hlo_text: str, scopes: Sequence[str],
                           kernels: Dict[str, str]) -> Dict[str, str]:
    """HLO instruction name -> the innermost of ``scopes`` in its
    ``op_name`` metadata (a fusion carries its root's), or the scope
    ``kernels`` gives its name's prefix."""
    out = {}
    pattern = re.compile(
        r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*op_name=\"([^\"]*)\"")
    for line in hlo_text.splitlines():
        m = pattern.match(line)
        if not m:
            continue
        hit = [p for p in m.group(2).split("/") if p in scopes] \
            or [s for k, s in kernels.items() if m.group(1).startswith(k)]
        if hit:
            out[m.group(1)] = hit[-1]
    return out


def scope_seconds(events: Dict[str, Any], by_instruction: Dict[str, str],
                  window_ns, scopes: Sequence[str]) -> Dict[str, float]:
    """Device seconds by scope inside the window: self time of every
    operation whose instruction lies in the scope, nested operations
    counted once (``trace_reduce.self_times``)."""
    from benchmark import trace_reduce

    lo, hi = window_ns
    out = {s: 0.0 for s in scopes}
    planes = [p for p in events["planes"]
              if p["name"].startswith(trace_reduce.DEVICE_PLANE)]
    for plane in planes:
        ops = trace_reduce._line(plane, trace_reduce.OPS_LINE)
        inside = [e for e in ops if e[1] + e[2] > lo and e[1] < hi]
        for name, sec in trace_reduce.self_times(inside).items():
            scope = by_instruction.get(
                name.split(" = ")[0].strip().lstrip("%"))
            if scope:
                out[scope] += sec / len(planes)
    return out


class StateFamilyLLM(FamilyLLM):
    """One replica of a ``serve_state_family`` cell."""

    def __init__(self, cell: Dict[str, Any], seed: int, overrides=None):
        self.family = load_family(cell["config_file"])
        super().__init__(cell, seed, overrides)

    def _engine_factory(self, *args, **kw):
        return StateFamilyEngine(
            *args, step_counters=self.family.STEP_COUNTERS, **kw)

    def _step_text(self) -> str:
        """The compiled step program's text: the program the engine runs,
        lowered again from its own shapes."""
        import jax
        import jax.numpy as jnp

        eng = self.engine
        spec = lambda tree: jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
        slots = eng.max_slots
        return jax.jit(eng._raw_step_paged, donate_argnums=(1,)).lower(
            spec(eng.params), spec(eng._cache),
            i32(slots, eng.prefill_chunk), i32(slots, eng._tbl_width),
            i32(slots), i32(slots),
            jax.ShapeDtypeStruct((slots,), jnp.bool_)).compile().as_text()

    def _step_scopes(self) -> Dict[str, str]:
        return scopes_of_instructions(
            self._step_text(), self.family.SCOPES, self.family.KERNELS)

    def bench_trace_stop(self) -> Dict[str, Any]:
        from benchmark import trace_reduce

        # BenchLLM's reduction; FamilyLLM's would add its own scopes'
        out = super(FamilyLLM, self).bench_trace_stop()
        if out.get("n_devices"):
            events = trace_reduce.load_xplane(
                trace_reduce.find_xplane(self._trace_dir))
            anchor = next(e for p in events["planes"] for l in p["lines"]
                          for e in l["events"]
                          if e[0] == trace_reduce.ANCHOR)
            offset = anchor[1] - self._anchor
            lo, hi = (t * 1e9 + offset for t in out["window_monotonic"])
            out["scope_s"] = scope_seconds(events, self._scopes, (lo, hi),
                                           self.family.SCOPES)
            out["scope_instructions"] = len(self._scopes)
        return out
