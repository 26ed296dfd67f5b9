"""Model step (models/transformer.py, ops/moe.py): how unevenly the window's
steps loaded a layer's experts, where every expert is held: the tokens of
each layer's busiest expert, summed over layers and steps, over the mean
tokens an expert of that layer got (``engine.stats["moe_expert_tokens_max"]
* experts / ["moe_expert_tokens_sum"]``; 1.0 is an even load), in the
pre-routed MoE family's cell, whose published file names its experts
``moe_num_primary_experts`` (``expert_load_max_over_mean`` reads the same
counters under the key ``num_experts``). A step there routes a few hundred
pairs over 64 experts, so even a fair router reads well over 1; a skewed one
makes the kernel's groups uneven and the busiest expert's rows set the tile
that waits longest. Nothing to read in a configuration without that key or
an engine without the counters. Moves tpot_p95_ms."""

from benchmark import reduce


def read(run):
    end = run.get("marks", {}).get("end", {}).get("stats", {})
    experts = run.get("config_file", {}).get("moe_num_primary_experts")
    if not experts or "moe_expert_tokens_sum" not in end:
        return None
    pairs = reduce.window_delta(run, "moe_expert_tokens_sum")
    return reduce.window_delta(run, "moe_expert_tokens_max") * experts \
        / pairs if pairs else None
