"""What runs inside the replica of a ``serve_family`` cell: ``BenchLLM`` and
``BenchEngine`` (``serve_replica.py``) with the weights made by the
configuration's family (``benchmark/families/<family>.py``), the engine's
own counters kept per step, and in a traced run the device time of each of
the program's ``jax.named_scope``s: an operation of the trace is named by
its HLO instruction, and the compiled step's text gives each instruction's
scope. The wrappers record; they decide nothing. Importing this module
imports no jax.
"""

from __future__ import annotations

import os
import re
from typing import Any, Dict

from benchmark import manifest
from benchmark.kinds.serve_replica import BenchEngine, BenchLLM, now

#: the program's scopes whose device time a traced run reports
SCOPES = ("moe_router", "moe_experts", "dsa_indexer",
          "paged_sparse_attention", "paged_attention")

#: operations that reach the compiled program without their scope: XLA
#: rewrites ``lax.ragged_dot`` into custom calls named ``ragged-dot-*`` whose
#: ``op_name`` is that name alone. By instruction-name prefix -> scope.
KERNELS = {"ragged-dot": "moe_experts"}

#: engine counters kept per step (their growth over the step)
STEP_COUNTERS = ("moe_expert_tokens_sum", "moe_expert_tokens_max",
                 "moe_experts_hit", "attn_keys_selected", "attn_keys_live")


def family_path(name: str) -> str:
    return os.path.join(manifest.HERE, "families", name + ".py")


def load_family(config_file: Dict[str, Any]):
    return manifest.load_module(family_path(config_file["reference"]))


class FamilyEngine(BenchEngine):
    """``BenchEngine`` that also keeps, per step, how far the engine's own
    counters grew in it."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.rec.step_counters = []

    def step(self):
        before = {k: self.stats.get(k, 0) for k in STEP_COUNTERS}
        n0 = len(self.rec.steps)
        busy = super().step()
        if len(self.rec.steps) > n0:
            self.rec.step_counters.append(
                {k: self.stats.get(k, 0) - v for k, v in before.items()})
        return busy


def scopes_of_instructions(hlo_text: str) -> Dict[str, str]:
    """HLO instruction name -> the innermost of ``SCOPES`` in its
    ``op_name`` metadata (a fusion carries its root's), or the scope
    ``KERNELS`` gives its name."""
    out = {}
    pattern = re.compile(
        r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*op_name=\"([^\"]*)\"")
    for line in hlo_text.splitlines():
        m = pattern.match(line)
        if not m:
            continue
        hit = [p for p in m.group(2).split("/") if p in SCOPES] \
            or [s for k, s in KERNELS.items() if m.group(1).startswith(k)]
        if hit:
            out[m.group(1)] = hit[-1]
    return out


def scope_seconds(events: Dict[str, Any], scopes: Dict[str, str],
                  window_ns) -> Dict[str, float]:
    """Device seconds by scope inside the window: self time of every
    operation whose instruction lies in the scope, nested operations
    counted once (``trace_reduce.self_times``)."""
    from benchmark import trace_reduce

    lo, hi = window_ns
    out = {s: 0.0 for s in SCOPES}
    planes = [p for p in events["planes"]
              if p["name"].startswith(trace_reduce.DEVICE_PLANE)]
    for plane in planes:
        ops = trace_reduce._line(plane, trace_reduce.OPS_LINE)
        inside = [e for e in ops if e[1] + e[2] > lo and e[1] < hi]
        for name, sec in trace_reduce.self_times(inside).items():
            scope = scopes.get(name.split(" = ")[0].strip().lstrip("%"))
            if scope:
                out[scope] += sec / len(planes)
    return out


class FamilyLLM(BenchLLM):
    """One replica of a family's serve cell: ``BenchLLM`` with the weights
    made by the family's own files."""

    def __init__(self, cell: Dict[str, Any], seed: int, overrides=None):
        import jax

        from benchmark import weights
        from ray_tpu.serve.llm import LLMDeployment
        from ray_tpu.util.tpu_info import ensure_compile_cache

        ensure_compile_cache()    # before this process's first compile
        t0 = now()
        self.cell, self.seed = cell, seed
        self.config_file = cell["config_file"]
        family = load_family(self.config_file)
        self.tconfig = family.transformer_config(
            self.config_file, **(overrides or {}))
        self.params = jax.jit(
            lambda key: family.build_params(self.tconfig, key))(
            weights.prng_key(seed))
        jax.block_until_ready(self.params)
        t1 = now()
        eng = self.config_file["engine"]
        LLMDeployment.__init__(
            self, self.tconfig, params=self.params, seed=seed,
            paged=eng["paged"], max_slots=eng["max_slots"],
            max_len=eng["max_len"], block_size=eng["block_size"],
            num_blocks=eng["num_blocks"], prefill_chunk=eng["prefill_chunk"],
            stream_batch=eng["stream_batch"])
        t2 = now()
        self._warm_up()
        self.setup = {"weights_s": t1 - t0, "engine_s": t2 - t1,
                      "warmup_s": now() - t2}
        self.marks: Dict[str, Any] = {}
        self._trace_dir = None
        self._scopes = None

    def _engine_factory(self, *args, **kw):
        return FamilyEngine(*args, **kw)

    def _step_scopes(self) -> Dict[str, str]:
        """The compiled step program's instructions by scope: the program
        the engine runs, lowered again from its own shapes (the compile
        comes out of the cache the step went into)."""
        import jax
        import jax.numpy as jnp

        eng = self.engine
        spec = lambda tree: jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
        slots = eng.max_slots
        text = jax.jit(eng._raw_step_paged, donate_argnums=(1,)).lower(
            spec(eng.params), spec(eng._cache),
            i32(slots, eng.prefill_chunk), i32(slots, eng._tbl_width),
            i32(slots), i32(slots),
            jax.ShapeDtypeStruct((slots,), jnp.bool_)).compile().as_text()
        return scopes_of_instructions(text)

    def bench_trace_start(self, trace_dir: str) -> float:
        if self._scopes is None:
            self._scopes = self._step_scopes()
        return super().bench_trace_start(trace_dir)

    def bench_trace_stop(self) -> Dict[str, Any]:
        from benchmark import trace_reduce

        out = super().bench_trace_stop()
        if out.get("n_devices"):
            events = trace_reduce.load_xplane(
                trace_reduce.find_xplane(self._trace_dir))
            anchor = next(e for p in events["planes"] for l in p["lines"]
                          for e in l["events"]
                          if e[0] == trace_reduce.ANCHOR)
            offset = anchor[1] - self._anchor
            lo, hi = (t * 1e9 + offset for t in out["window_monotonic"])
            out["scope_s"] = scope_seconds(events, self._scopes, (lo, hi))
            out["scope_instructions"] = len(self._scopes)
        return out

    def bench_forget_prefixes(self) -> int:
        """Between the rates of a sweep, the engine idle: drop the prefix
        trie, so that a rate starts with the pool the cell starts with.
        Every rate has tokens of its own, and where a rate's shared
        prefixes fill half the pool the last rate's are evicted under this
        one's requests. Returns the blocks freed."""
        eng = self.engine
        with eng._lock:
            return eng.prefix.clear() if eng.prefix is not None else 0

    def bench_collect(self) -> Dict[str, Any]:
        out = super().bench_collect()
        out["step_counters"] = self.engine.rec.step_counters
        return out


