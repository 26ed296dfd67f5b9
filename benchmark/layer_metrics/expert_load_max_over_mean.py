"""Model step (models/transformer.py, ops/moe.py): how unevenly the window's
steps loaded their experts: the tokens of each layer's busiest expert, summed
over layers and steps, over the mean tokens an expert of that layer got
(``engine.stats["moe_expert_tokens_max"] * experts /
["moe_expert_tokens_sum"]``; 1.0 is an even load). With eight single-token
rows most experts get nothing, so this reads high however fair the router
is: it says how many rows the busiest expert's matmul has against the mean.
Nothing to read in a dense model. Moves tpot_p95_ms."""


def read(run):
    start, end = (run["marks"][k]["stats"] for k in ("start", "end"))
    experts = run["config_file"].get("num_experts")
    if not experts or "moe_expert_tokens_sum" not in end:
        return None
    pairs = end["moe_expert_tokens_sum"] - start["moe_expert_tokens_sum"]
    if not pairs:
        return None
    return (end["moe_expert_tokens_max"] - start["moe_expert_tokens_max"]) \
        * experts / pairs
