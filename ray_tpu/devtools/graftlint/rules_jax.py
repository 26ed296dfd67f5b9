"""JAX/TPU discipline rules (family ``jax``).

SafeCheck-style ahead-of-time enforcement of the accelerator call
discipline (CLAUDE.md): the 50 GB-residual mistake, an enqueue timed as
if it were the work, the chip-fight hang, and a jax import in every
worker are all cheaper to catch at lint time than on the chip.
"""

from __future__ import annotations

import ast
from typing import Iterator, Set

from ray_tpu.devtools.graftlint.engine import Project, dotted_parts
from ray_tpu.devtools.graftlint.model import (
    FAMILY_JAX,
    Finding,
    Rule,
    register,
)

#: raw kernels without a memory-efficient VJP; the dispatch wrapper
#: ``ray_tpu.ops.flash_attention`` carries the custom VJP
_RAW_KERNELS = {"flash_attention_pallas", "blockwise_attention"}

#: jax transforms that differentiate their function argument
_DIFF_TRANSFORMS = {"jax.grad", "jax.value_and_grad", "jax.vjp",
                    "jax.jacfwd", "jax.jacrev", "jax.hessian"}


def _is_raw_kernel_call(mod, cs) -> bool:
    """Alias-aware: matches the symbol wherever it came from —
    ``from ...flash_pallas import flash_attention_pallas as fap`` or
    ``ops.attention.blockwise_attention(...)`` both resolve."""
    if cs.fq:
        tail = cs.fq.rpartition(".")[2]
        if tail in _RAW_KERNELS:
            return True
    if cs.parts and cs.parts[-1] in _RAW_KERNELS:
        return True
    return False


@register
class RawAttentionKernel(Rule):
    name = "raw-attention-call"
    family = FAMILY_JAX
    summary = ("outside ray_tpu/ops/, attention goes through "
               "ops.flash_attention (memory-efficient VJP) — raw "
               "flash_attention_pallas/blockwise_attention calls OOM real "
               "HBM when differentiated; also flags jax.grad over a local "
               "function that reaches a raw kernel")

    def check(self, project: Project) -> Iterator[Finding]:
        for mod in project.modules:
            in_ops = mod.scope_rel.startswith("ray_tpu/ops/")
            # functions (transitively, within the module) calling a raw kernel
            raw_callers: Set[str] = set()
            calls_by_func = {}
            for cs in mod.calls:
                calls_by_func.setdefault(cs.func, []).append(cs)
                if _is_raw_kernel_call(mod, cs):
                    raw_callers.add(cs.func)
                    if not in_ops:
                        yield self.finding(
                            mod, cs.line,
                            f"raw kernel {'.'.join(cs.parts or ('?',))}() "
                            f"called outside ray_tpu/ops — it has no "
                            f"memory-efficient VJP (saves every "
                            f"probability block: ~50 GB at llama-250M "
                            f"batch 16); call ray_tpu.ops.flash_attention "
                            f"instead")
            # close over intra-module plain-name calls
            changed = True
            while changed:
                changed = False
                for func, sites in calls_by_func.items():
                    if func in raw_callers:
                        continue
                    for cs in sites:
                        if (cs.parts and len(cs.parts) == 1
                                and any(rc.split(".")[-1] == cs.parts[0]
                                        for rc in raw_callers)):
                            raw_callers.add(func)
                            changed = True
                            break
            if not raw_callers or in_ops:
                # ops/ is the rule's documented home: its custom-VJP
                # machinery legitimately differentiates the raw kernels
                continue
            raw_tails = {rc.split(".")[-1] for rc in raw_callers}
            # jax.grad(f) where f reaches a raw kernel — differentiating
            # the raw path, even without a direct call at this site
            for cs in mod.calls:
                if cs.fq not in _DIFF_TRANSFORMS:
                    continue
                for arg in cs.node.args[:1]:
                    parts = dotted_parts(arg)
                    if parts and len(parts) == 1 and parts[0] in raw_tails:
                        yield self.finding(
                            mod, cs.line,
                            f"{cs.fq}({parts[0]}) differentiates a "
                            f"function that reaches a raw attention "
                            f"kernel — jax saves every probability block "
                            f"as a residual; route the attention through "
                            f"ray_tpu.ops.flash_attention")


@register
class UnreliableTimingBarrier(Rule):
    name = "unreliable-timing-barrier"
    family = FAMILY_JAX
    summary = ("one timing idiom: timed code ends in a device_get of a "
               "scalar data-dependent on all the work (the read spans a "
               "scanned n-step program), not a bare block_until_ready")

    def check(self, project: Project) -> Iterator[Finding]:
        for mod in project.modules:
            timer_funcs = {q for q, fi in mod.functions.items()
                           if fi.calls_timer}
            for cs in mod.calls:
                if not cs.parts or cs.parts[-1] != "block_until_ready":
                    continue
                if cs.func not in timer_funcs:
                    continue
                yield self.finding(
                    mod, cs.line,
                    f"block_until_ready in timing function {cs.func}() — "
                    f"the tree times with a jax.device_get of a scalar "
                    f"data-dependent on all the work "
                    f"(TrainLoopHelper.run_steps pattern)")


@register
class JaxPlatformsLeak(Rule):
    name = "jax-platforms-leak"
    family = FAMILY_JAX
    summary = ("never read the driver's JAX_PLATFORMS env into a worker "
               "env (outside util/) — propagating the accelerator value "
               "makes every worker fight for the chip and hang")

    _ALLOWED_PREFIXES = ("ray_tpu/util/",)

    def _env_read(self, mod, cs) -> bool:
        # os.environ.get("JAX_PLATFORMS") / environ.get(...) / os.getenv(...)
        if cs.fq in ("os.environ.get", "os.getenv") and cs.node.args:
            a = cs.node.args[0]
            return isinstance(a, ast.Constant) and a.value == "JAX_PLATFORMS"
        return False

    def check(self, project: Project) -> Iterator[Finding]:
        for mod in project.modules:
            if mod.scope_rel.startswith(self._ALLOWED_PREFIXES):
                continue
            if "JAX_PLATFORMS" not in mod.source:
                continue  # cheap gate before any tree walk
            for cs in mod.calls:
                if self._env_read(mod, cs):
                    yield self.finding(
                        mod, cs.line,
                        "reads the driver's JAX_PLATFORMS from "
                        "os.environ — workers hard-default to cpu "
                        "(DriverRuntime.worker_env); opt a designated "
                        "actor back in per-actor, don't forward the "
                        "driver's value")
            # os.environ["JAX_PLATFORMS"] *read* (a store is how the
            # allowed util/ helpers pin the value; elsewhere reads leak)
            for node in ast.walk(mod.tree):
                if (isinstance(node, ast.Subscript)
                        and isinstance(node.ctx, ast.Load)
                        and isinstance(node.slice, ast.Constant)
                        and node.slice.value == "JAX_PLATFORMS"):
                    parts = dotted_parts(node.value)
                    fq = mod.resolve_parts(parts) if parts else None
                    if fq == "os.environ":
                        yield self.finding(
                            mod, node.lineno,
                            "reads the driver's JAX_PLATFORMS from "
                            "os.environ — workers hard-default to cpu; "
                            "don't forward the driver's value")
            # {k: v for k, v in os.environ.items() if k in ("JAX_PLATFORMS",..)}
            for node in ast.walk(mod.tree):
                if not isinstance(node, (ast.DictComp, ast.SetComp,
                                         ast.ListComp, ast.GeneratorExp)):
                    continue
                over_environ = False
                for gen in node.generators:
                    it = gen.iter
                    if isinstance(it, ast.Call):
                        it = it.func
                    parts = dotted_parts(it)
                    fq = mod.resolve_parts(parts) if parts else None
                    if fq and fq.startswith("os.environ"):
                        over_environ = True
                if not over_environ:
                    continue
                for sub in ast.walk(node):
                    if (isinstance(sub, ast.Constant)
                            and sub.value == "JAX_PLATFORMS"):
                        yield self.finding(
                            mod, sub.lineno,
                            "filters JAX_PLATFORMS out of os.environ "
                            "into a forwarded env dict — the driver's "
                            "value would make every worker fight for "
                            "the chip; a TPU reservation gives a worker "
                            "its platform (core/runtime.py)")
                        break


#: ML-tier trees whose jit/pmap sites must go through the device-plane
#: registry (util/device_plane.registered_jit) so every compiled program
#: gets a name, a signature history, and cost analysis
_REGISTRY_SCOPES = ("ray_tpu/models/", "ray_tpu/train/", "ray_tpu/serve/",
                    "ray_tpu/rllib/")

#: introspection calls fenced to util/device_plane.py — each costs a
#: lowering/compile or a full live-array walk, and scattering them
#: defeats the single bounded registry
_FENCED_INTROSPECTION = {"cost_analysis", "memory_analysis", "live_arrays"}

_PLANE_FILE = "ray_tpu/util/device_plane.py"


@register
class JitRegistryDiscipline(Rule):
    name = "jit-registry-discipline"
    family = FAMILY_JAX
    summary = ("under models//train//serve//rllib, jax.jit/jax.pmap goes "
               "through util.device_plane.registered_jit (named program, "
               "retrace detection, cost analysis); cost_analysis/"
               "memory_analysis/live_arrays are fenced to "
               "util/device_plane.py")

    def check(self, project: Project) -> Iterator[Finding]:
        for mod in project.modules:
            is_plane = mod.scope_rel == _PLANE_FILE
            in_scope = mod.scope_rel.startswith(_REGISTRY_SCOPES)
            for cs in mod.calls:
                if in_scope and cs.fq in ("jax.jit", "jax.pmap"):
                    tail = cs.fq.rpartition(".")[2]
                    yield self.finding(
                        mod, cs.line,
                        f"raw jax.{tail}() in an ML-tier module — the "
                        f"compiled program is invisible to the device "
                        f"plane (no name, no retrace detection, no cost "
                        f"analysis); wrap it with "
                        f"ray_tpu.util.device_plane.registered_jit")
                if is_plane:
                    continue
                tail = None
                if cs.fq:
                    t = cs.fq.rpartition(".")[2]
                    if t in _FENCED_INTROSPECTION:
                        tail = t
                if tail is None and cs.parts \
                        and cs.parts[-1] in _FENCED_INTROSPECTION:
                    tail = cs.parts[-1]
                if tail is not None:
                    yield self.finding(
                        mod, cs.line,
                        f"{tail}() outside util/device_plane.py — XLA "
                        f"introspection costs a lowering (or a live-"
                        f"array walk) per call; the registry already "
                        f"holds it, read device_plane.registry() / "
                        f"state.device_report() instead")


@register
class JaxImportInCore(Rule):
    name = "jax-import-in-core"
    family = FAMILY_JAX
    summary = ("no module-scope jax import in core/ or cluster/ — zygote "
               "workers import these, and jax costs ~1.9 s per worker "
               "boot (defer to function scope)")

    _SCOPES = ("ray_tpu/core/", "ray_tpu/cluster/")

    def check(self, project: Project) -> Iterator[Finding]:
        for mod in project.modules:
            if not mod.scope_rel.startswith(self._SCOPES):
                continue
            for line, fq in mod.module_import_nodes:
                if fq == "jax" or fq.startswith("jax."):
                    yield self.finding(
                        mod, line,
                        f"module-scope import of {fq} in a zygote-"
                        f"imported module — every worker boot pays "
                        f"~1.9 s; import inside the function that needs "
                        f"it (workers spawn with python -S precisely to "
                        f"dodge this)")
