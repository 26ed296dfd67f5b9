"""Run a cell n times as the driver's check runs it and print, for every
run, what lies behind its end-to-end numbers:

    python3 -m benchmark.tools.spread --workload <cell> --seeds 1,2,3 \
        [--copies dirA,dirB] [--seconds 51] [--out chiprun_out/spread/x.jsonl]

Every run is a new process (``--one``, which is ``benchmark.run`` with a
second line of diagnostics printed before the result line). With
``--copies`` the runs take turns between checkouts of the repo (two ``git
archive`` copies of one tree stand in for the check's parent and change),
each with a ``HOME``, ``XDG_CACHE_HOME`` and ``TMPDIR`` of its own inside
it. Seed ``s`` is run in every copy, so a copy is one "set" of the same
seeds. The summary gives, per copy and metric, the median, the spread
(quartile distance over median, ``stats.iqr_share``) and the spread with the
run farthest from the median left out where that narrows it (how the check
reads tightness). ``kinds/`` is not edited for this: everything printed is
taken from what ``kind.run`` returns.

The diagnostics of one run (``[spread] {...}``): the percentiles of the gaps
between tokens around the 95th, the largest gaps with the instant each fell
in the window and the 95th percentile of each fifth of the window (a run
shifted as a whole against one with a tail of its own), the replica's spans
and steps (steps that carry a prefill chunk apart from decode-only ones, and
the idle time of the loop between two steps), how late the generator sent,
what the driver's collector cost inside the window, the parts of set-up and
the compile cache's hits and misses.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import manifest, reduce, stats  # noqa: E402

TAG = "[spread] "
QS = (50, 75, 90, 93, 95, 97, 99, 100)


def _pcts(values, qs=QS):
    return {f"p{q}": round(stats.percentile(values, q), 4) for q in qs} \
        if values else {}


def diagnose(outcome, collector):
    """What one run's ``kind.run`` outcome says beyond its result line."""
    run = outcome["run"]
    t0, t1 = run["window"]
    gaps = [((b - a) * 1e3, b - t0) for c in run["clients"]
            for a, b in zip(c.stamps, c.stamps[1:]) if b <= t1]
    only = [g for g, _ in gaps]
    fifth = (t1 - t0) / 5
    rep = run["replica"]
    spans = {}
    for name, s, e in rep["spans"]:
        if s >= t0 and e <= t1:
            spans.setdefault(name, []).append((e - s) * 1e3)
    steps = reduce.steps_in_window(run)
    wall = [(s[1] - s[0]) * 1e3 for s in steps]
    chunk = [(s[1] - s[0]) * 1e3 for s in steps
             if any(fed > 1 for _, fed, _ in s[2])]
    decode = [(s[1] - s[0]) * 1e3 for s in steps
              if all(fed == 1 for _, fed, _ in s[2])]
    between = [(b[0] - a[1]) * 1e3 for a, b in zip(steps, steps[1:])]
    inside = [(d, when - t0) for when, d in collector if t0 <= when <= t1]
    facts = outcome["facts"]
    return {
        "values": dict(outcome["values"]),
        "gaps": _pcts(only), "n_gaps": len(only),
        "gaps_p95_by_fifth": [
            round(stats.percentile(
                [g for g, at in gaps if i * fifth <= at < (i + 1) * fifth]
                or [0.0], 95), 3) for i in range(5)],
        "largest_gaps_ms_at_s": [[round(g, 2), round(at, 2)] for g, at in
                                 sorted(gaps, reverse=True)[:8]],
        "spans_ms": {n: {"n": len(v), **_pcts(v, (50, 95, 99, 100))}
                     for n, v in sorted(spans.items())},
        "steps": {"n": len(steps), "wall_ms": _pcts(wall, (50, 95, 99, 100)),
                  "with_chunk": {"n": len(chunk),
                                 **_pcts(chunk, (50, 95, 100))},
                  "decode_only": {"n": len(decode),
                                  **_pcts(decode, (50, 95, 100))},
                  "loop_between_steps_ms": _pcts(between, (50, 95, 99, 100)),
                  "rows_mean": round(sum(len(s[2]) for s in steps)
                                     / max(len(steps), 1), 3)},
        "driver_gc": {"n": len(inside),
                      "total_ms": round(sum(d for d, _ in inside) * 1e3, 3),
                      "max_ms": round(max((d for d, _ in inside),
                                          default=0.0) * 1e3, 3)},
        "setup_parts": facts["setup"], "compile_cache": facts["compile_cache"],
        "memory_peak_bytes": facts["memory_peak_bytes"],
        "cores": len(os.sched_getaffinity(0)), "loadavg": os.getloadavg(),
        "correct": bool(outcome["correct"]), "failed": outcome["failed"],
        "attempted": outcome["attempted"],
    }


def one(args) -> int:
    """``benchmark.run`` for one run, with the diagnostics line before the
    result line."""
    from benchmark import run as bench_run

    man = manifest.load_manifest()
    cell = manifest.load_cell(man, args.workload)
    kind = manifest.load_module(manifest.kind_path(cell["kind"]))
    out_dir = os.path.join(manifest.ROOT, ".bench_out", args.workload)
    os.makedirs(out_dir, exist_ok=True)
    collector, began = [], [0.0]

    def on_gc(phase, info):
        if phase == "start":
            began[0] = time.monotonic()
        else:
            collector.append((began[0], time.monotonic() - began[0]))

    gc.callbacks.append(on_gc)
    run_args = SimpleNamespace(workload=args.workload, seed=args.seed,
                               seconds=args.seconds, trace=0,
                               rehearse_cpu=args.rehearse_cpu, sweep="")
    ctx = SimpleNamespace(args=run_args, cell=cell, manifest=man,
                          t_start=T_START, out_dir=out_dir)
    outcome = kind.run(ctx)
    print(TAG + json.dumps(diagnose(outcome, collector)), flush=True)
    if outcome["facts"]["platform"] != "tpu":
        return 2
    print(json.dumps(bench_run.result_line(man, args.workload, 0, outcome)),
          flush=True)
    return 0


def trimmed_spread(values):
    """The spread with the run farthest from the median left out, where
    that narrows it."""
    med = stats.median(values)
    rest = sorted(values, key=lambda v: abs(v - med))[:-1]
    return min(stats.iqr_share(values), stats.iqr_share(rest)) \
        if len(rest) >= 2 else stats.iqr_share(values)


def summary(rows) -> None:
    by_copy = {}
    for r in rows:
        by_copy.setdefault(r["copy"], []).append(r)
    medians = {}
    for copy, rs in sorted(by_copy.items()):
        # a copy's first run compiles: its set-up is recorded apart
        for name in sorted(rs[0]["metrics"]):
            vals = [r["metrics"][name] for r in rs]
            if name == "setup_s":
                vals = vals[1:]
            if len(vals) < 2:
                continue
            medians.setdefault(name, []).append(stats.median(vals))
            print(f"{copy} {name}: median {stats.median(vals):.4f} spread "
                f"{100 * stats.iqr_share(vals):.3f} % trimmed "
                f"{100 * trimmed_spread(vals):.3f} % of "
                f"{[round(v, 3) for v in vals]}")
    for name, (a, *rest) in medians.items():
        for b in rest:
            print(f"second median over first, {name}: {100 * (b / a - 1):+.3f} %")


def run_in_copy(copy: str, workload: str, seed: int, seconds: float):
    """One run as a new process in one checkout, with a ``HOME``,
    ``XDG_CACHE_HOME`` and ``TMPDIR`` of that checkout's own."""
    home = os.path.join(copy, ".bench_tmp_home")
    os.makedirs(os.path.join(home, "tmp"), exist_ok=True)
    env = {**os.environ, "HOME": home, "TMPDIR": home + "/tmp",
           "XDG_CACHE_HOME": home + "/.cache"}
    t0 = time.monotonic()
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.tools.spread", "--one",
         "--workload", workload, "--seed", str(seed),
         "--seconds", str(seconds)],
        cwd=copy, env=env, capture_output=True, text=True)
    lines = p.stdout.strip().splitlines()
    row = {"copy": os.path.basename(copy), "seed": seed,
           "rc": p.returncode, "took_s": time.monotonic() - t0}
    diag = [l for l in lines if l.startswith(TAG)]
    if p.returncode == 0 and diag:
        line = json.loads(lines[-1])
        row["metrics"] = {k: v["value"] for k, v in line["metrics"].items()}
        row["correct"] = line["correct"]
        row["diag"] = json.loads(diag[-1][len(TAG):])
        row["checks"] = [l for l in lines if "[bench] check" in l]
    else:
        row["tail"] = (p.stdout[-3000:], p.stderr[-3000:])
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--copies", default=".")
    ap.add_argument("--out", default="")
    ap.add_argument("--one", action="store_true")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="with --one: toy sizes on the CPU, no result line")
    args = ap.parse_args(argv)
    if args.seconds is None:
        args.seconds = float(manifest.load_manifest()["run_seconds"])
    if args.one:
        return one(args)
    copies = [os.path.abspath(c) for c in args.copies.split(",")]
    out = args.out or os.path.join(
        "chiprun_out", "spread", args.workload + ".jsonl")
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        for copy in copies:
            row = run_in_copy(copy, args.workload, seed, args.seconds)
            rows.append(row)
            with open(out, "a", encoding="utf-8") as f:
                f.write(json.dumps({"workload": args.workload, **row}) + "\n")
            d = row.get("diag", {})
            print(f"{row['copy']} seed {seed} rc {row['rc']} "
                  f"{row['took_s']:.0f}s correct {row.get('correct')} "
                  f"{ {k: round(v, 3) for k, v in row.get('metrics', {}).items()} } "
                  f"gaps {d.get('gaps')} n {d.get('n_gaps')} steps "
                  f"{d.get('steps', {}).get('wall_ms')} cache "
                  f"{d.get('compile_cache')} "
                  f"{row.get('tail', '')}", flush=True)
    summary([r for r in rows if "metrics" in r])
    return 0 if all(r["rc"] == 0 and r.get("correct") for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
