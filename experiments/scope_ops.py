"""A traced benchmark run's device time split BY OPERATION inside chosen
scopes: ``trace["scope_s"]`` says what ``moe_experts`` cost, this says which
sort, which gather, which grouped matmul. The profiler names a device
operation by its HLO instruction; the instruction's ``jax.named_scope`` is in
the COMPILED step's text (``op_name=``), which the replica lowers again for
its own ``scope_s`` and does not keep. So the text comes from a compile of
the same step for a described chip (no chip needed: the recipe of the cell's
``tests/test_tpu_compile.py::test_paged_step_*_compiles_at_published_widths``
at the cell's engine settings, ``compiled.as_text()`` written to a file): the
same program and compiler give the same instruction names, and the scopes'
totals printed here are to be held against the run's own ``scope_s``.

    python3 -m benchmark.run --workload trinity-large.mixed-queue --seed 1 \
        --seconds 51 --trace 1
    JAX_PLATFORMS=cpu python experiments/scope_ops.py \
        .bench_out/trinity-large.mixed-queue/trace step.hlo.txt out.json \
        moe_experts moe_router

Self times (an operation nested in a ``while`` or a ``conditional`` is
counted once, ``benchmark.trace_reduce.self_times``), summed over the whole
trace by (scope, operation label); ``ms_per_step`` divides by the step
program's executions in the trace. A last argument ``step=longest`` reads ONE
step instead: the longest execution of the step program in the trace (a
step that carries a whole prefill chunk deep in a context), its own
operations alone, and prints its length beside what the scopes hold of it."""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(trace, hlo_path, out_path, *scopes):
    import jax

    one_step = "step=longest" in scopes
    scopes = tuple(s for s in scopes if s != "step=longest")

    from benchmark import trace_reduce
    from benchmark.kinds.serve_state_family_replica import \
        scopes_of_instructions

    with open(hlo_path) as f:
        # XLA's grouped matmul is a custom call that carries no scope
        by_instruction = scopes_of_instructions(
            f.read(), scopes, {"ragged-dot": "moe_experts"})
    path = trace if trace.endswith(".pb") else trace_reduce.find_xplane(trace)
    events, steps = [], 0
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name == trace_reduce.MODULES_LINE:
                runs = [(float(e.start_ns), float(e.duration_ns))
                        for e in line.events if "_raw_step_paged" in e.name]
                steps = len(runs)
            if line.name == trace_reduce.OPS_LINE:
                events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                          for e in line.events]
    if one_step:
        median = sorted(d for _, d in runs)[len(runs) // 2]
        start, length = max(runs, key=lambda run: run[1])
        events = [e for e in events if start <= e[1] < start + length]
        steps = 1
        print(f"one step of {length / 1e6:.3f} ms (median step "
              f"{median / 1e6:.3f} ms, {len(runs)} in the trace)")
    by, placed = {}, 0
    for name, sec in trace_reduce.self_times(events).items():
        scope = by_instruction.get(name.split(" = ")[0].strip().lstrip("%"))
        if scope is None:
            continue
        placed += 1
        # the same operation of every layer run and width on one line
        label = " ".join(p.split(".")[0] if p.startswith("%") else p
                         for p in trace_reduce.op_label(name).split(" "))
        by[scope, label] = by.get((scope, label), 0.0) + sec
    rows = sorted(by.items(), key=lambda kv: -kv[1])
    total = {s: sum(sec for (scope, _), sec in rows if scope == s)
             for s in scopes}
    out = {"steps": steps, "operations_placed": placed, "scope_s": total,
           "ops": [{"scope": s, "op": label, "s": sec,
                    "ms_per_step": 1e3 * sec / max(steps, 1)}
                   for (s, label), sec in rows]}
    with open(out_path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"steps {steps}; {placed} operations placed; scope seconds {total}")
    for r in out["ops"][:40]:
        print(f"{r['scope']:12s} {r['ms_per_step']:8.3f} ms/step "
              f"{r['s']:7.3f} s  {r['op']}")


if __name__ == "__main__":
    main(*sys.argv[1:])
