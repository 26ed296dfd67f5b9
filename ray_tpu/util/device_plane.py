"""Device plane: the compiled-program registry — observability plane #6.

Five planes (metrics, flight recorder, tracing, profiling, lifecycle
events) cover the HOST runtime; this plane covers the layer that makes
the framework TPU-native: jitted XLA programs. The reference pays for
this layer with per-component C++ stats (arXiv:1712.05889 §4); a TPU
stack needs the XLA-level equivalent — compile-time/HBM/FLOP accounting
per compiled program.

Three surfaces on one registry:

**Registry** — :func:`registered_jit` wraps ``jax.jit`` at the hot
entry points (``TrainLoopHelper``, the serve engine's paged
decode/copy/gather/scatter programs, the RL learner update, model
multiplexing's draft/verify programs). Every compiled program registers
its name, abstract input signature, compile wall time, donation map,
and the backend's static ``cost_analysis`` (flops, bytes accessed) —
plus ``memory_analysis`` when ``RTPU_DEVICE_PLANE_MEMORY=1`` opts into
the second XLA compile it costs. Registration never fails a step.
Disarmed cost is one dict get per call.

**Retrace detector** — compile detection is a ``_cache_size()`` probe
after each call. A
recompile past a program's first emits ONE ``jit_recompile`` lifecycle
event carrying the shape/dtype/static-arg DIFF against the prior
signature — the thing you need to fix it — and feeds
``rtpu_jit_compiles_total{program}`` / ``rtpu_jit_retraces_total`` and
the ``jit_compile_storm`` alert rule (util/alerts.py).

**HBM census + attribution** — :func:`snapshot` bundles the program
table with ``tpu_info.hbm_usage`` watermarks and a live-buffer census
(``jax.live_arrays`` grouped by shape/dtype). Snapshots federate like
metrics: workers cast them over the control pipe ("device" cast),
node daemons ride the GCS heartbeat as idempotent per-node payloads,
and ``state.device_report()`` merges the cluster view for
``/api/devices`` / ``rtpu devices``. ``train/telemetry.py`` and the RL
learner read :func:`program_flops_per_step` to
compute achieved FLOP/s and MFU from the cost model instead of
hand-maintained formulas (cost-analysis flops count every executed
flop, remat recompute included — callers that want MODEL flops keep
the analytic formula and report both).

Timing discipline: the plane never calls ``block_until_ready`` — the
wrapper measures call wall time (dispatch + first-execution on compile
calls, the existing ``record_compile`` convention); step-time
attribution stays with the callers' dependent ``device_get`` timing.

``RTPU_DEVICE_PLANE=0`` is the kill switch (plane is ON by default —
compiles are rare; per-call overhead is a dict get + two clock reads +
an int compare).
"""

from __future__ import annotations

import os
import sys
import threading
import time
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Tuple

#: bounds — the registry is a bounded table like every plane's ring
MAX_PROGRAMS = 256
MAX_SIGS = 8          # signature history kept per program
MAX_CENSUS_GROUPS = 32  # top-N live-buffer groups by bytes

_state: Dict[str, Any] = {"enabled": None}
_lock = threading.Lock()


def _resolve() -> bool:
    with _lock:
        if _state["enabled"] is None:
            _state["enabled"] = (
                os.environ.get("RTPU_DEVICE_PLANE", "1") != "0")
        return _state["enabled"]


def device_plane_enabled() -> bool:
    """Hot-path arming check: one dict get (the events/tracing idiom)."""
    e = _state["enabled"]
    if e is None:
        return _resolve()
    return e


def enable_device_plane() -> None:
    _state["enabled"] = True


def disable_device_plane() -> None:
    _state["enabled"] = False


def _reset_for_tests() -> None:
    global _registry
    with _lock:
        _state["enabled"] = None
    _registry = CompiledProgramRegistry()


# lazily-bound builtin metrics; never allowed to fail a call
_m: Dict[str, Any] = {}


def _metric(name: str):
    from ray_tpu.util import metric_defs, metrics

    inst = _m.get(name)
    if inst is None or metrics.registered(name) is not inst:
        inst = _m[name] = metric_defs.get(name)
    return inst


# ---------------------------------------------------------------------------
# abstract signatures + diffs
# ---------------------------------------------------------------------------


def _describe_leaf(x: Any) -> str:
    """One leaf of an abstract signature: ``f32[4,8]``-style for arrays
    (anything with shape+dtype: jax arrays — donated/deleted ones keep
    their metadata — numpy arrays, ShapeDtypeStructs), a bounded repr
    for python statics (THE static-arg half of a retrace diff)."""
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None:
        return "%s[%s]" % (dtype, ",".join(str(d) for d in shape))
    r = repr(x)
    return "py:%s:%s" % (type(x).__name__,
                         r if len(r) <= 40 else r[:37] + "...")


def abstract_signature(args: tuple, kwargs: dict) -> Dict[str, str]:
    """{tree path: leaf description} for a call's arguments — the unit
    the retrace detector stores and diffs. Paths come from
    ``tree_flatten_with_path`` so the diff names the actual argument
    (``[0]['params']['w']``), not a flat index."""
    import jax

    leaves, _ = jax.tree_util.tree_flatten_with_path((args, kwargs))
    sig: Dict[str, str] = {}
    for path, leaf in leaves:
        key = jax.tree_util.keystr(path)
        # (args, kwargs) wrapper: strip the outer [0]/[1] for readability
        key = key.replace("[0]", "args", 1) if key.startswith("[0]") \
            else key.replace("[1]", "kwargs", 1)
        sig[key] = _describe_leaf(leaf)
    return sig


def signature_diff(old: Dict[str, str],
                   new: Dict[str, str]) -> Dict[str, Any]:
    """The payload of a ``jit_recompile`` event: what changed between
    the prior signature and the one that just forced a recompile."""
    changed = {p: {"was": old[p], "now": new[p]}
               for p in new if p in old and old[p] != new[p]}
    added = {p: new[p] for p in new if p not in old}
    removed = {p: old[p] for p in old if p not in new}
    out: Dict[str, Any] = {}
    if changed:
        out["changed"] = changed
    if added:
        out["added"] = added
    if removed:
        out["removed"] = removed
    return out


def _to_spec(x: Any) -> Any:
    """Array leaf -> ShapeDtypeStruct (so ``.lower()`` for cost analysis
    never touches buffers — donated inputs are already invalid by the
    time the compile is detected); everything else passes through."""
    import jax

    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is not None and dtype is not None and not isinstance(
            x, jax.ShapeDtypeStruct):
        try:
            return jax.ShapeDtypeStruct(tuple(shape), dtype)
        except Exception:
            return x
    return x


def _normalize_cost(cost: Any) -> Optional[Dict[str, float]]:
    """``cost_analysis()`` returns a dict (Lowered) or a list of dicts
    (Compiled, one per partition) depending on the jax version — fold to
    one {metric: value} dict of the keys the plane reports."""
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else None
    if not isinstance(cost, dict):
        return None
    out: Dict[str, float] = {}
    for key in ("flops", "bytes accessed", "transcendentals"):
        v = cost.get(key)
        if isinstance(v, (int, float)):
            out[key.replace(" ", "_")] = float(v)
    return out or None


def _scan_aware_cost(closed_jaxpr: Any, lowered: Any = None
                     ) -> Optional[Tuple[Dict[str, float], Dict[str, float]]]:
    """Cost analysis of a traced program with every ``lax.scan`` counted
    at its full length. XLA's analysis counts the body of a while loop
    ONCE whatever the trip count, and a scan lowers to one: a model whose
    layers are scanned reported one layer's flops, and a ``steps=N``
    program one step's. Each scan's single counted iteration is replaced
    by ``length`` times its body's cost, the body analysed the same way
    (so nesting multiplies). A body that cannot be lowered alone
    (collectives under ``shard_map``) stays counted once. Returns the
    scan-aware cost and the cost as XLA counted it (what a caller that
    holds this program as a scan body has in its own count)."""
    import jax
    from jax.extend import core as jex

    if lowered is None:
        avals = [jax.ShapeDtypeStruct(a.shape, a.dtype)
                 for a in closed_jaxpr.in_avals]
        lowered = jax.jit(jex.jaxpr_as_fun(closed_jaxpr)).lower(*avals)
    once = _normalize_cost(lowered.cost_analysis())
    if not once:
        return None
    cost = dict(once)

    def visit(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "scan":
                length = int(eqn.params["length"])
                try:
                    body = (_scan_aware_cost(eqn.params["jaxpr"])
                            if length > 1 else None)
                except Exception:
                    body = None
                if body:
                    full, counted = body
                    for key, v in full.items():
                        cost[key] = cost.get(key, 0.0) + (
                            length * v - counted.get(key, 0.0))
                    continue  # the body's own scans are in `full` already
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                    if isinstance(sub, jex.ClosedJaxpr):
                        visit(sub.jaxpr)
                    elif isinstance(sub, jex.Jaxpr):
                        visit(sub)

    visit(closed_jaxpr.jaxpr)
    return cost, once


def _normalize_memory(mem: Any) -> Optional[Dict[str, int]]:
    out: Dict[str, int] = {}
    for attr in ("generated_code_size_in_bytes", "argument_size_in_bytes",
                 "output_size_in_bytes", "temp_size_in_bytes",
                 "alias_size_in_bytes"):
        v = getattr(mem, attr, None)
        if isinstance(v, int):
            out[attr.replace("_in_bytes", "")] = v
    return out or None


def _memory_analysis_wanted() -> bool:
    """memory_analysis costs a SECOND XLA compile of the program (the
    AOT ``lower().compile()`` path) — opt-in only."""
    return os.environ.get("RTPU_DEVICE_PLANE_MEMORY", "0") == "1"


# ---------------------------------------------------------------------------
# the registry
# ---------------------------------------------------------------------------


class CompiledProgramRegistry:
    """Per-process table of compiled programs (bounded, LRU on insert).

    One row per program NAME — a re-created wrapper (a second serve
    engine in the same process) folds into the same row: its fresh
    compile counts, but an already-seen signature is not a retrace."""

    def __init__(self):
        self._lock = threading.Lock()
        self._programs: "OrderedDict[str, Dict[str, Any]]" = OrderedDict()
        self._version = 0

    @property
    def version(self) -> int:
        return self._version

    def _row(self, name: str, component: str) -> Dict[str, Any]:
        rec = self._programs.get(name)
        if rec is None:
            while len(self._programs) >= MAX_PROGRAMS:
                self._programs.popitem(last=False)
            rec = {"program": name, "component": component, "steps": 1,
                   "donate": [], "sigs": [], "compiles": 0, "retraces": 0,
                   "compile_s_total": 0.0, "compile_s_last": 0.0,
                   "calls": 0, "cost": None, "memory": None,
                   "last_compile_ts": 0.0}
            self._programs[name] = rec
        return rec

    def record_compile(self, name: str, component: str, *,
                       sig: Optional[Dict[str, str]], seconds: float,
                       donate: Tuple[int, ...] = (), steps: int = 1,
                       cost: Optional[Dict[str, float]] = None,
                       memory: Optional[Dict[str, int]] = None,
                       ) -> Optional[Dict[str, Any]]:
        """Fold one compile event into the table. Returns the signature
        diff when this signature is NOVEL past the row's first (i.e. a
        retrace someone should look at), else None."""
        diff = None
        with self._lock:
            rec = self._row(name, component)
            rec["compiles"] += 1
            rec["calls"] += 1
            rec["compile_s_total"] += seconds
            rec["compile_s_last"] = seconds
            rec["last_compile_ts"] = time.time()
            # always refresh: cost and steps must stay a consistent pair
            # (a re-jitted scan with a different length updates both)
            rec["steps"] = max(1, int(steps))
            if donate:
                rec["donate"] = sorted(set(rec["donate"]) | set(donate))
            if cost:
                rec["cost"] = cost
            if memory:
                rec["memory"] = memory
            if sig is not None and sig not in rec["sigs"]:
                if rec["sigs"]:
                    rec["retraces"] += 1
                    diff = signature_diff(rec["sigs"][-1], sig)
                rec["sigs"].append(sig)
                del rec["sigs"][:-MAX_SIGS]
            self._version += 1
        return diff

    def note_call(self, name: str, component: str = "") -> None:
        # hot path (every armed registered-jit call): once the row
        # exists, the increment rides the GIL — a slightly racy counter
        # beats a lock acquisition per jit dispatch
        rec = self._programs.get(name)
        if rec is None:
            with self._lock:
                rec = self._row(name, component)
        rec["calls"] += 1

    def program(self, name: str) -> Optional[Dict[str, Any]]:
        with self._lock:
            rec = self._programs.get(name)
            return None if rec is None else _copy_row(rec)

    def rows(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [_copy_row(r) for r in self._programs.values()]

    def flops_per_step(self, name: str) -> Optional[float]:
        """Cost-analysis flops for ONE step of ``name`` (a scanned
        multi-step program's per-call flops divided by its scan length).
        None when the backend gave no cost model — callers fall back to
        their analytic formula."""
        with self._lock:
            rec = self._programs.get(name)
            if rec is None or not rec["cost"]:
                return None
            flops = rec["cost"].get("flops")
            if not flops or flops <= 0:
                return None
            return float(flops) / max(1, int(rec["steps"]))


def _copy_row(rec: Dict[str, Any]) -> Dict[str, Any]:
    out = dict(rec)
    out["sigs"] = [dict(s) for s in rec["sigs"]]
    out["donate"] = list(rec["donate"])
    if rec.get("cost"):
        out["cost"] = dict(rec["cost"])
    if rec.get("memory"):
        out["memory"] = dict(rec["memory"])
    return out


_registry = CompiledProgramRegistry()


def registry() -> CompiledProgramRegistry:
    return _registry


def program_flops_per_step(name: str) -> Optional[float]:
    return _registry.flops_per_step(name)


# ---------------------------------------------------------------------------
# the jit wrapper
# ---------------------------------------------------------------------------


class RegisteredFunction:
    """``jax.jit`` + registration. Calls forward to the jitted function;
    when the plane is armed, a ``_cache_size()`` probe after each call
    detects fresh compiles. Only compile calls pay the slow path (signature
    walk, ``lower().cost_analysis()``, event/metric emission)."""

    def __init__(self, fn: Callable, *, name: str, component: str = "",
                 steps: int = 1, **jit_kwargs: Any):
        import jax

        self._name = name
        self._component = component
        self._steps = int(steps)
        self._jit_kwargs = jit_kwargs
        self._jitted = jax.jit(fn, **jit_kwargs)
        donate = jit_kwargs.get("donate_argnums") or ()
        self._donate = (donate,) if isinstance(donate, int) else \
            tuple(donate)
        # NEVER store the bound ``_cache_size`` method: a bound method
        # of the C++ PjitFunction kept on this wrapper makes the
        # engine <-> jit reference cycle uncollectable (measured: the
        # serve engine — and every arena weight view it aliases — then
        # survives ``del`` + gc.collect() forever). Re-``getattr`` per
        # probe; the temporary method dies with the call frame.
        self._cache_size = 0

    @property
    def name(self) -> str:
        return self._name

    def __call__(self, *args: Any, **kwargs: Any) -> Any:
        if not device_plane_enabled():
            return self._jitted(*args, **kwargs)
        t0 = time.perf_counter()
        out = self._jitted(*args, **kwargs)
        dt = time.perf_counter() - t0
        n = self._jitted._cache_size()
        compiled = n != self._cache_size
        self._cache_size = n
        try:
            if compiled:
                self._on_compile(args, kwargs, dt)
            else:
                _registry.note_call(self._name, self._component)
        except Exception:
            pass  # the plane must never fail a step
        return out

    # AOT passthroughs so registered functions stay drop-in for jax.jit
    def lower(self, *args: Any, **kwargs: Any):
        return self._jitted.lower(*args, **kwargs)

    def eval_shape(self, *args: Any, **kwargs: Any):
        return self._jitted.eval_shape(*args, **kwargs)

    # -- slow path: one compile event ----------------------------------

    def _on_compile(self, args: tuple, kwargs: dict,
                    seconds: float) -> None:
        sig = None
        try:
            sig = abstract_signature(args, kwargs)
        except Exception:
            pass
        cost = memory = None
        try:
            import jax

            specs_a, specs_k = jax.tree_util.tree_map(
                _to_spec, (args, kwargs))
            traced = self._jitted.trace(*specs_a, **specs_k)
            low = traced.lower()
            aware = _scan_aware_cost(traced.jaxpr, low)
            cost = aware[0] if aware else None
            if _memory_analysis_wanted():
                memory = _normalize_memory(low.compile().memory_analysis())
        except Exception:
            pass  # a program without a cost model still registers
        _record_compile_event(
            self._name, self._component, sig=sig, seconds=seconds,
            donate=self._donate, steps=self._steps, cost=cost,
            memory=memory)


def _record_compile_event(name: str, component: str, *, sig, seconds,
                          donate=(), steps=1, cost=None,
                          memory=None) -> None:
    """THE compile-event sink (shared by the jit wrapper and the eager
    ``tracked_call`` hook): registry fold, retrace event, metrics,
    trace span."""
    diff = _registry.record_compile(
        name, component, sig=sig, seconds=seconds, donate=donate,
        steps=steps, cost=cost, memory=memory)
    try:
        _metric("rtpu_jit_compiles_total").inc(1, tags={"program": name})
        _metric("rtpu_jit_compile_seconds").observe(
            seconds, tags={"program": name})
        if diff:
            _metric("rtpu_jit_retraces_total").inc(
                1, tags={"program": name})
    except Exception:
        pass
    if diff:
        try:
            from ray_tpu.util import events

            events.emit("jit_recompile", program=name,
                        component=component,
                        seconds=round(seconds, 4), diff=diff)
        except Exception:
            pass
    try:
        from ray_tpu.util import tracing

        if tracing.tracing_enabled():
            end = time.time_ns()
            tracing.record_span(
                "device::compile", end - int(seconds * 1e9), end,
                {"program": name, "component": component,
                 "retrace": bool(diff),
                 **({"flops": cost["flops"]}
                    if cost and "flops" in cost else {})})
    except Exception:
        pass


def registered_jit(fn: Optional[Callable] = None, *, name: str,
                   component: str = "", steps: int = 1,
                   **jit_kwargs: Any):
    """``jax.jit`` with device-plane registration (decorator-friendly).

    ``name`` is the program's registry identity (``"serve::decode"``);
    ``steps`` declares a scanned multi-step program's scan length so
    ``program_flops_per_step`` can report per-step flops."""
    if fn is None:
        return lambda f: RegisteredFunction(
            f, name=name, component=component, steps=steps, **jit_kwargs)
    return RegisteredFunction(fn, name=name, component=component,
                              steps=steps, **jit_kwargs)


def tracked_call(name: str, component: str, fn: Callable[[], Any],
                 args: tuple, statics: Optional[dict] = None) -> Any:
    """Registry hook for EAGER dispatchers (``ops.flash_attention`` is
    deliberately unjitted so ``impl="auto"`` resolves per trace): a
    novel (arrays, statics) signature means the internals compiled —
    record it as a compile of ``name``; known signatures count a call."""
    if not device_plane_enabled():
        return fn()
    t0 = time.perf_counter()
    out = fn()
    dt = time.perf_counter() - t0
    try:
        sig = abstract_signature(args, {**(statics or {})})
        rec = _registry.program(name)
        if rec is None or sig not in rec["sigs"]:
            _record_compile_event(name, component, sig=sig, seconds=dt)
        else:
            _registry.note_call(name, component)
    except Exception:
        pass
    return out


# ---------------------------------------------------------------------------
# HBM census + snapshots (the federated unit)
# ---------------------------------------------------------------------------


def live_buffer_census() -> Optional[Dict[str, Any]]:
    """Live device arrays grouped by (dtype, shape) — top groups by
    bytes. None when jax was never imported in this process (zygote
    workers must not pay a jax import for a census)."""
    if "jax" not in sys.modules:
        return None
    try:
        import jax

        arrs = jax.live_arrays()
    except Exception:
        return None
    groups: Dict[Tuple[str, Tuple[int, ...]], List[int]] = {}
    total_bytes = 0
    n = 0
    for a in arrs:
        try:
            key = (str(a.dtype), tuple(a.shape))
            nbytes = int(a.nbytes)
        except Exception:
            continue
        ent = groups.setdefault(key, [0, 0])
        ent[0] += 1
        ent[1] += nbytes
        total_bytes += nbytes
        n += 1
    top = sorted(groups.items(), key=lambda kv: -kv[1][1])
    return {
        "buffers": n, "bytes": total_bytes,
        "groups": [{"dtype": k[0],
                    "shape": list(k[1]),
                    "count": c, "bytes": b}
                   for k, (c, b) in top[:MAX_CENSUS_GROUPS]]}


def _hbm() -> Optional[Dict[str, int]]:
    if "jax" not in sys.modules:
        return None
    try:
        from ray_tpu.util.tpu_info import hbm_usage

        return hbm_usage()
    except Exception:
        return None


def snapshot(min_version: Optional[int] = None,
             census: bool = True) -> Optional[Dict[str, Any]]:
    """This process's device-plane unit: program table + HBM watermarks
    + live-buffer census. ``min_version`` gates the push paths — None
    when nothing changed since (an empty registry never ships)."""
    reg = _registry
    with reg._lock:
        version = reg._version
        if min_version is not None and version <= min_version:
            return None
        programs = [_copy_row(r) for r in reg._programs.values()]
    snap: Dict[str, Any] = {"pid": os.getpid(), "version": version,
                            "programs": programs}
    hbm = _hbm()
    if hbm:
        snap["hbm"] = hbm
    if census:
        c = live_buffer_census()
        if c:
            snap["live_buffers"] = c
    try:
        _metric("rtpu_device_programs").set(len(programs))
        if census and snap.get("live_buffers"):
            _metric("rtpu_device_live_buffers").set(
                snap["live_buffers"]["buffers"])
            _metric("rtpu_device_live_buffer_bytes").set(
                snap["live_buffers"]["bytes"])
    except Exception:
        pass
    return snap


class DeviceStore:
    """Receiver side (driver/daemon): latest snapshot per origin with
    origin labels — snapshot-replace semantics like the metrics
    FederationStore (registry rows are mutable state, not a stream)."""

    MAX_ORIGINS = 256

    def __init__(self):
        self._lock = threading.Lock()
        self._origins: "OrderedDict[str, Dict[str, Any]]" = OrderedDict()

    def ingest(self, origin: str, labels: Dict[str, str],
               snap: Dict[str, Any]) -> None:
        with self._lock:
            self._origins.pop(origin, None)
            self._origins[origin] = {"labels": dict(labels),
                                     "snap": snap}
            while len(self._origins) > self.MAX_ORIGINS:
                self._origins.popitem(last=False)

    def export(self) -> List[Dict[str, Any]]:
        """[{**snap, **labels}] for every known origin."""
        with self._lock:
            return [{**e["snap"], **e["labels"]}
                    for e in self._origins.values()]

    def clear(self) -> None:
        with self._lock:
            self._origins.clear()


def node_processes(rt: Any = None,
                   component: Optional[str] = None) -> List[Dict[str, Any]]:
    """This NODE's process entries: the local process's snapshot plus
    every worker snapshot its DeviceStore ingested — the per-node unit
    the adapter ships on heartbeats."""
    out: List[Dict[str, Any]] = []
    snap = snapshot()
    if snap and (snap["programs"] or snap.get("hbm")
                 or snap.get("live_buffers")):
        ent = dict(snap)
        if component:
            ent["component"] = component
        out.append(ent)
    store = getattr(rt, "device_store", None) if rt is not None else None
    if store is not None:
        out.extend(store.export())
    return out


def merge_report(entries: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Fold process entries (each a labeled snapshot) into the
    ``state.device_report()`` shape: flat program rows with origin
    labels, per-process HBM/census, and cluster totals."""
    programs: List[Dict[str, Any]] = []
    processes: List[Dict[str, Any]] = []
    totals = {"processes": 0, "programs": 0, "compiles": 0,
              "retraces": 0, "live_buffer_bytes": 0}
    hbm_used = hbm_limit = 0
    for ent in entries:
        labels = {k: ent[k] for k in ("node_id", "worker_id", "component",
                                      "pid") if k in ent}
        proc: Dict[str, Any] = dict(labels)
        proc["programs"] = len(ent.get("programs") or ())
        if ent.get("hbm"):
            proc["hbm"] = ent["hbm"]
            hbm_used += int(ent["hbm"].get("bytes_in_use", 0))
            hbm_limit += int(ent["hbm"].get("bytes_limit", 0))
        if ent.get("live_buffers"):
            proc["live_buffers"] = ent["live_buffers"]
            totals["live_buffer_bytes"] += int(
                ent["live_buffers"].get("bytes", 0))
        processes.append(proc)
        totals["processes"] += 1
        for row in ent.get("programs") or ():
            r = dict(row)
            r.update(labels)
            programs.append(r)
            totals["programs"] += 1
            totals["compiles"] += int(row.get("compiles", 0))
            totals["retraces"] += int(row.get("retraces", 0))
    if hbm_limit:
        totals["hbm"] = {"bytes_in_use": hbm_used,
                         "bytes_limit": hbm_limit}
    programs.sort(key=lambda r: (-r.get("compile_s_total", 0.0),
                                 r.get("program", "")))
    return {"processes": processes, "programs": programs,
            "totals": totals}
