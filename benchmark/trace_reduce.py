"""From a profiler trace to numbers: device busy time, the operations that
took most of it, the program's time per execution, and the longest idle gaps
with what the host was doing in each.

Two steps, so that the arithmetic is testable without a chip:
``load_xplane`` turns jax's ``.xplane.pb`` into plain lists (needs jax);
``reduce`` works on those lists alone and is checked against the small
recorded trace in ``tests/data/``.

Trace timestamps are nanoseconds from the start of the trace. The
benchmark's own host spans are on CLOCK_MONOTONIC; one ``bench::anchor``
annotation, stamped on both clocks, ties the two together.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, List, Sequence, Tuple

from benchmark.stats import union_length

DEVICE_PLANE = "/device:TPU:"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
ANCHOR = "bench::anchor"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def start_trace(trace_dir: str) -> int:
    """Start jax's profiler (host annotations on, Python tracer off: it is
    the slow part) and stamp the anchor; returns the anchor's
    CLOCK_MONOTONIC nanoseconds for ``reduce``."""
    import time

    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    with jax.profiler.TraceAnnotation(ANCHOR):
        return time.monotonic_ns()


def load_xplane(path: str) -> Dict[str, Any]:
    """``{"planes": [{"name", "lines": [{"name", "events": [[name,
    start_ns, duration_ns], ...]}]}]}``. Host lines are kept only for the
    anchor; device lines whole."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        device = plane.name.startswith(DEVICE_PLANE)
        lines = []
        for line in plane.lines:
            events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                      for e in line.events
                      if device or e.name == ANCHOR]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


_OPCODE = re.compile(r" ([a-z][a-z0-9_\-]*)\(")


def op_label(raw: str) -> str:
    """A trace event's full HLO text cut to what names it: the result, the
    operation and the first result shape (``%fusion.177 fusion
    f32[16,32]``)."""
    head, sep, rest = raw.partition(" = ")
    if not sep:
        return raw[:120]
    op = _OPCODE.search(" " + rest)
    shape = re.search(r"[a-z0-9]+\[[0-9,]*\]", rest)
    return " ".join(x for x in (head, op.group(1) if op else "",
                                shape.group(0) if shape else "") if x)[:120]


def _line(plane: Dict[str, Any], name: str) -> List[list]:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def self_segments(events: List[list]) -> List[Tuple[str, float, float]]:
    """(name, start_ns, end_ns) pieces in which an event runs and none of
    the events nested inside it does: a ``while`` over the layers holds the
    layers' operations, and its own pieces are what is left between them."""
    out: List[Tuple[str, float, float]] = []
    stack: List[list] = []      # [name, end_ns, cursor_ns]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, end, cursor = stack.pop()
            if end > cursor:
                out.append((name, cursor, end))

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            parent = stack[-1]
            if start > parent[2]:
                out.append((parent[0], parent[2], start))
            parent[2] = max(parent[2], start + dur)
        stack.append([name, start + dur, start])
    close(float("inf"))
    return out


def self_times(events: List[list]) -> Dict[str, float]:
    """Seconds by operation name, nested operations counted once."""
    out: Dict[str, float] = {}
    for name, start, end in self_segments(events):
        out[name] = out.get(name, 0.0) + (end - start) / 1e9
    return out


def _clip(events: List[list], lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(s + d, hi)) for _, s, d in events
            if s + d > lo and s < hi]


def _gaps(busy: List[Tuple[float, float]], lo: float, hi: float):
    gaps, at = [], lo
    for s, e in sorted(busy):
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    return gaps


def _host_state(gap: Tuple[float, float], spans: List[tuple]) -> str:
    """The host span that covers more than half of an idle gap, else
    ``outside_any_span``, whatever touches the gap's ends: a second without
    a request begins in the last microseconds of the step before it."""
    best, best_cover = "outside_any_span", (gap[1] - gap[0]) / 2
    for name, s, e in spans:
        cover = min(e, gap[1]) - max(s, gap[0])
        if cover > best_cover:
            best, best_cover = name, cover
    return best


def reduce(trace: Dict[str, Any], *, host_spans: Sequence[tuple],
           anchor_ns: int, window: Tuple[float, float], program: str,
           top: int = 10, longest_gaps: int = 5) -> Dict[str, Any]:
    """``host_spans``: (name, t0, t1) in CLOCK_MONOTONIC seconds;
    ``window``: the traced window on the same clock; ``program``: the
    substring that names the step program among the trace's modules."""
    anchor = next((e for p in trace["planes"] for l in p["lines"]
                   for e in l["events"] if e[0] == ANCHOR), None)
    if anchor is None:
        raise ValueError("the trace holds no bench::anchor annotation")
    offset = anchor[1] - anchor_ns           # trace_ns = monotonic_ns + offset
    lo, hi = (window[0] * 1e9 + offset, window[1] * 1e9 + offset)
    spans = [(n, s * 1e9 + offset, e * 1e9 + offset) for n, s, e in host_spans]
    devices = [p for p in trace["planes"] if p["name"].startswith(DEVICE_PLANE)]
    out: Dict[str, Any] = {"window_s": (hi - lo) / 1e9,
                           "window_monotonic": list(window),
                           "n_devices": len(devices)}
    if not devices:
        return out
    busy, ops, runs = [], {}, []
    for plane in devices:
        op_events = _line(plane, OPS_LINE) or _line(plane, MODULES_LINE)
        inside = [e for e in op_events if e[1] + e[2] > lo and e[1] < hi]
        busy.append(_clip(inside, lo, hi))
        for name, sec in self_times(inside).items():
            ops[name] = ops.get(name, 0.0) + sec / len(devices)
        runs += [d / 1e6 for n, s, d in _line(plane, MODULES_LINE)
                 if program in n and s >= lo and s + d <= hi]
    out["busy_s"] = sum(union_length(b) for b in busy) / 1e9 / len(devices)
    out["busy_s_by_device"] = [union_length(b) / 1e9 for b in busy]
    out["program_runs_ms"] = runs
    out["device_ops"] = [[op_label(n), s] for n, s in sorted(
        ops.items(), key=lambda kv: -kv[1])[:top]]
    gaps = [[_host_state(g, spans), (g[1] - g[0]) / 1e9]
            for g in _gaps(busy[0], lo, hi)]
    by_state: Dict[str, float] = {}
    for state, sec in gaps:
        by_state[state] = by_state.get(state, 0.0) + sec
    out["idle_by_host_state_s"] = by_state
    out["idle_gaps"] = sorted(gaps, key=lambda g: -g[1])[:longest_gaps]
    return out
