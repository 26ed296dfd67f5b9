"""The longest device gaps of a profiler trace laid to the engine's OWN host
events: ``LLMEngine.step`` stamps ``serve::step`` and its phases
``serve.step::*`` as ``jax.profiler.TraceAnnotation``s (``util.tracing.stamp``),
so they lie in the xplane's host plane on the device trace's clock and a gap on
the device line is named by the phase that covers more than half of it, with no
anchor and no clock offset. Also counts the ``serve::step`` calls inside the
device's traced span and those of them that hold all five phases.

    JAX_PLATFORMS=cpu python experiments/step_trace_gaps.py <trace dir> <out.jsonl> <tag>

``<trace dir>`` is what ``jax.profiler.start_trace`` was given (a benchmark
run's ``.bench_out/<cell>/trace``). This is the reading PR 42 made by hand
beside ``breakdown.idle_gaps``; ``benchmark/trace_reduce.py`` keeps host lines
for its anchor alone until a ``benchmark`` PR lets it read these events
(PERF.md section 7)."""

import bisect
import glob
import json
import os
import sys

PHASES = {"admit", "build_inputs", "dispatch", "read", "route"}


def load(trace_dir):
    """(device operations [(start, end)], the stepping thread's host events
    [(name, start, end)], events a host line), nanoseconds of the trace."""
    import jax

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    device, lines = [], {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:0"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    device = [(e.start_ns, e.start_ns + e.duration_ns)
                              for e in line.events]
        elif plane.name.startswith("/host"):
            # a line a thread, and the names repeat
            for i, line in enumerate(plane.lines):
                events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events if e.name.startswith("serve")]
                if events:
                    lines[f"{line.name}#{i}"] = events
    loop = max(lines.values(), default=[], key=lambda ev: sum(
        e[0] == "serve::step" for e in ev))
    return device, loop, {k: len(v) for k, v in lines.items()}


def covering(gap, events, default):
    """The event that covers more than half of ``gap``."""
    best, cover = default, (gap[1] - gap[0]) / 2
    for name, s, e in events:
        c = min(e, gap[1]) - max(s, gap[0])
        if c > cover:
            best, cover = name, c
    return best


def reduce(device, loop, longest=5):
    calls = [e for e in loop if e[0] == "serve::step"]
    phases = sorted((e for e in loop if e[0].startswith("serve.step::")),
                    key=lambda e: e[1])
    out = {"n_serve_step": len(calls), "n_phase": {}}
    for e in phases:
        out["n_phase"][e[0]] = out["n_phase"].get(e[0], 0) + 1
    if not device or not calls:
        return out
    lo, hi = min(s for s, _ in device), max(e for _, e in device)
    starts = [e[1] for e in phases]
    whole = [w for w in calls if w[1] >= lo and w[2] <= hi]
    full = 0
    for w in whole:
        i, names = bisect.bisect_left(starts, w[1]), set()
        while i < len(phases) and phases[i][1] <= w[2]:
            if phases[i][2] <= w[2]:
                names.add(phases[i][0].partition("::")[2])
            i += 1
        full += names >= PHASES
    out["calls_inside_device_span"] = len(whole)
    out["calls_with_all_five"] = full
    gaps, at = [], min(e for _, e in device)
    for s, e in sorted(device):
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    out["idle_s_total"] = sum(b - a for a, b in gaps) / 1e9
    out["device_span_s"] = (hi - lo) / 1e9
    gaps.sort(key=lambda g: g[0] - g[1])
    out["xplane_gaps"] = [
        [covering(g, phases, covering(g, calls, "outside_any_call")),
         (g[1] - g[0]) / 1e9] for g in gaps[:longest]]
    median = lambda v: sorted(v)[len(v) // 2] if v else None
    out["phase_median_ms"] = {
        n: median([(e[2] - e[1]) / 1e6 for e in phases if e[0] == n])
        for n in out["n_phase"]}
    out["serve_step_median_ms"] = median(
        [(e[2] - e[1]) / 1e6 for e in calls])
    return out


def main(trace_dir, out_path, tag):
    device, loop, lines = load(trace_dir)
    out = {"tag": tag, "host_lines": lines, **reduce(device, loop)}
    with open(out_path, "a") as f:
        f.write(json.dumps(out) + "\n")
    print(json.dumps(out))


if __name__ == "__main__":
    main(*sys.argv[1:4])
