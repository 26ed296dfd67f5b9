"""One run of one cell.

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result object of the contract in
README.md; every line before it is commentary. ``--rehearse-cpu`` walks the
same control flow at toy sizes on CPU devices and can never print a result
line (it exits 3). ``--sweep r1,r2,...`` offers several rates after one
set-up, to find a cell's knee, and prints no result line either.
"""

from __future__ import annotations

import time

T_START = time.monotonic()   # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:      # runnable as a file as well as with -m
    sys.path.insert(0, ROOT)

from benchmark import manifest  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--sweep", default="")
    return ap.parse_args(argv)


def result_line(man, workload, trace, outcome):
    """The metrics this run owes: the cell's end-to-end metrics, or with
    ``--trace 1`` its per-layer metrics, each read by its own file."""
    metrics = {}
    if trace:
        for m in manifest.metrics_of(man, "per_layer", workload):
            reader = manifest.load_module(manifest.layer_metric_path(m["name"]))
            value = reader.read(outcome["run"])
            if value is not None:    # nothing to read: left out of the line
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        for m in manifest.metrics_of(man, "end_to_end", workload):
            metrics[m["name"]] = {"value": float(outcome["values"][m["name"]]),
                                  "unit": m["unit"]}
    facts = outcome["facts"]
    device = {"platform": facts["platform"], "kind": facts["kind"],
              "count": facts["count"],
              "memory_peak_bytes": facts["memory_peak_bytes"]}
    line = {"correct": bool(outcome["correct"]),
            "attempted": outcome["attempted"], "failed": outcome["failed"],
            "metrics": metrics, "device": device}
    tr = outcome.get("trace")
    if trace and tr and tr.get("n_devices"):
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        line["breakdown"] = {"device_ops": tr["device_ops"],
                             "idle_gaps": tr["idle_gaps"]}
    return line


def main(argv=None) -> int:
    args = parse(argv)
    man = manifest.load_manifest()
    cell = manifest.load_cell(man, args.workload)
    kind = manifest.load_module(manifest.kind_path(cell["kind"]))
    out_dir = os.path.join(ROOT, ".bench_out", args.workload)
    # a traced run starts from an empty trace directory: the reduction
    # reads the newest trace, and old ones only fill the checkout
    shutil.rmtree(os.path.join(out_dir, "trace"), ignore_errors=True)
    os.makedirs(out_dir, exist_ok=True)
    ctx = SimpleNamespace(args=args, cell=cell, manifest=man, t_start=T_START,
                          out_dir=out_dir)
    outcome = kind.run(ctx)
    if args.rehearse_cpu or args.sweep:
        try:
            held = sorted(result_line(man, args.workload, args.trace,
                                      outcome)["metrics"])
        except KeyError as e:     # a reader that needs the chip's peaks
            held = f"(a reader needs the chip: {e})"
        print(f"[bench] no result line (rehearsal or sweep); it would have "
              f"held {held}, correct={outcome['correct']}", flush=True)
        return 3
    if outcome["facts"]["platform"] != "tpu":
        print("[bench] not on a TPU: no result", flush=True)
        return 2
    print(json.dumps(result_line(man, args.workload, args.trace, outcome)),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
