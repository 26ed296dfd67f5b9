"""Model step (models/transformer.py, ops/moe.py): of a layer's experts, the
share a step of the window HIT, over layers and steps:
``engine.stats["moe_experts_hit"]`` over expert layers x experts x
``["steps"]`` (``rtpu_serve_moe_experts_hit_total``), in the pre-routed MoE
family's cell, where every expert is held: a decode step of ``r`` rows routes
``6 r`` pairs over 64 experts and hits about ``64 (1 - (63 / 64)^(6 r))`` of
them, and the step reads each expert hit ONCE, so this is what the step's
bytes, and with them its time, follow. Lower is less to read for the same
rows (neither is better by itself: it follows the rows alive). Nothing to
read in a configuration without ``moe_num_primary_experts``. Moves
tpot_p95_ms."""

from benchmark import reduce


def read(run):
    cf = run["config_file"]
    end = run.get("marks", {}).get("end", {}).get("stats", {})
    experts = cf.get("moe_num_primary_experts")
    if not experts or "moe_experts_hit" not in end:
        return None
    steps = reduce.window_delta(run, "steps")
    if not steps:
        return None
    return 100.0 * reduce.window_delta(run, "moe_experts_hit") \
        / (int(cf["num_hidden_layers"]) * experts * steps)
