"""Model step: how unevenly the window's steps loaded the experts this
program HOLDS: the tokens of each expert layer's busiest held expert, summed
over layers and steps, over the mean tokens a held expert of that layer got
(``engine.stats["moe_expert_tokens_max"] * held experts /
["moe_expert_tokens_sum"]``, both over the held experts' own ``[L, E_held]``
counts; the file's ``num_experts`` is the number held; 1.0 is an even load),
in the windowed MoE family's cell (``expert_load_max_over_mean`` reads the
same counters where every expert is held). The seeded selection bias sets it:
a skewed load makes the grouped matmuls' groups uneven and the step's time
follows (``PERF.md`` section 4). Nothing to read in an engine without the
counters. Moves tpot_p95_ms."""

from benchmark import reduce


def read(run):
    end = run.get("marks", {}).get("end", {}).get("stats", {})
    held = run.get("config_file", {}).get("num_experts")
    if not held or "moe_expert_tokens_sum" not in end:
        return None
    pairs = reduce.window_delta(run, "moe_expert_tokens_sum")
    return reduce.window_delta(run, "moe_expert_tokens_max") * held / pairs \
        if pairs else None
