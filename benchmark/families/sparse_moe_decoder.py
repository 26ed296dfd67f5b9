"""The sparse-attention MoE decoder family (Keye-VL-2.0-30B-A3B's language
model) for the ``serve_family`` kind: from a configuration file's published
keys to the program's ``TransformerConfig``, its seeded weights, the toy
widths of a rehearsal, and what a step NEEDS (the numerators of the
family's roofline shares). The reference is ``reference/sparse_moe_decoder
.py``; the family's name is the configuration's ``reference`` key.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, Tuple

from benchmark.opcount import BYTES

#: toy widths for ``--rehearse-cpu`` and the CPU tests: control flow only
TOY_WIDTHS = {"hidden_size": 64, "intermediate_size": 128,
              "moe_intermediate_size": 32, "num_attention_heads": 4,
              "num_key_value_heads": 2, "head_dim": 16,
              "num_hidden_layers": 2, "vocab_size": 512,
              "num_experts": 8, "num_local_experts": 8,
              "num_experts_per_tok": 2,
              "sa_config": {"indexer_head_dim": 16, "indexer_num_heads": 4,
                            "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                            "q_chunk_size": 512, "topk": 48}}

#: the embedding's scale, and how far ``wv`` is drawn above the usual scale
#: (see ``build_params``)
EMBED_STD = 1.0
WV_GAIN = 2.0


def transformer_config(cf: Dict[str, Any], **overrides):
    """``TransformerConfig`` from the published keys. Every layer is an
    expert layer of ``moe_intermediate_size`` (``decoder_sparse_step`` 1,
    no ``mlp_only_layers``), so the dense ``intermediate_size`` is unused."""
    from ray_tpu.models.config import TransformerConfig

    if cf["decoder_sparse_step"] != 1 or cf["mlp_only_layers"] \
            or cf["use_sliding_window"] or cf["attention_bias"] \
            or cf["sa_config"]["indexer_num_kv_heads"] != 1:
        raise NotImplementedError("a layer pattern this family file does "
                                  "not describe")
    prec, sa = cf["precision"], cf["sa_config"]
    kw = dict(
        vocab_size=cf["vocab_size"], d_model=cf["hidden_size"],
        n_layers=cf["num_hidden_layers"], n_heads=cf["num_attention_heads"],
        n_kv_heads=cf["num_key_value_heads"], head_dim=cf["head_dim"],
        d_ff=cf["moe_intermediate_size"],
        max_seq_len=cf["max_position_embeddings"],
        mlp="swiglu", norm="rms", positions="rope",
        rope_theta=float(cf["rope_theta"]), norm_eps=float(cf["rms_norm_eps"]),
        tie_embeddings=bool(cf["tie_word_embeddings"]), qk_norm=True,
        num_experts=cf["num_experts"], expert_top_k=cf["num_experts_per_tok"],
        expert_norm_topk=bool(cf["norm_topk_prob"]),
        index_heads=sa["indexer_num_heads"],
        index_head_dim=sa["indexer_head_dim"], index_topk=sa["topk"],
        dtype=prec["activations"], param_dtype=prec["weights"])
    kw.update(overrides)
    return TransformerConfig(**kw)


def build_params(config, key):
    """The parameter tree for ``config`` from a key (traceable), laid out as
    ``models.param_axes`` has it. Normal weights at the usual scales; norm
    gains (q/k-norm and the indexer's LayerNorm with its bias included) and
    the indexer's head weights away from their trivial values, so that
    leaving one out shows in the logits. The EMBEDDING is drawn at unit
    scale, not 0.02: the mean of ``v`` over a query's keys passes through
    attention unattenuated while everything token-specific shrinks with the
    square root of the keys averaged, so on a residual of norm 0.9 that
    common component takes the stream over by the third layer, every
    position's hidden state becomes one vector, and the relative logit error
    then reads how far a seed amplifies an early flipped key (0.022-0.046
    sound against 0.037-0.062 for the int8 control over seeds: PERF.md,
    PR 28). On a residual of norm 45 the stream stays token-specific.
    ``wv`` is drawn ``WV_GAIN`` times above the usual scale: an attention
    output is an average over the selected keys and shrinks with the square
    root of their effective number; with the gain it carries 1.7-3.4 a
    layer against the expert branch's 1.85 (read on the chip), so that the
    logits can tell which keys were selected (the reference with the
    indexer left out reads 0.16, ten times the limit)."""
    import jax
    import jax.numpy as jnp

    c = config
    dt = jnp.dtype(c.param_dtype)
    d, hd, f, L = c.d_model, c.hdim, c.ff, c.n_layers
    h, kv, v, e = c.n_heads, c.kv_heads, c.vocab_size, c.num_experts
    j, di = c.index_heads, c.index_head_dim
    proj = d ** -0.5
    out = proj / (2 * L) ** 0.5

    names = ["attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm", "wq_i",
             "wk_i", "w_i", "ki_norm", "ki_norm_b", "mlp_norm", "router",
             "w_gate", "w_up", "w_down", "embed", "final_norm", "lm_head"]
    ks = dict(zip(names, jax.random.split(key, len(names))))

    def draw(k, shape, std, mean):
        x = jax.random.normal(k, shape, jnp.float32) * std + mean
        return x.astype(dt)

    def stacked(name, shape, std, mean=0.0):
        # one layer at a time: the float32 draw of a whole stacked leaf
        # never exists
        return jax.lax.map(lambda k: draw(k, shape, std, mean),
                           jax.random.split(ks[name], L))

    def experts(name, shape, std):
        # and a layer's experts one at a time (a layer's stack is 805 MB
        # in float32)
        def layer(k):
            return jax.lax.map(lambda k1: draw(k1, shape, std, 0.0),
                               jax.random.split(k, e))
        return jax.lax.map(layer, jax.random.split(ks[name], L))

    layers = {
        "attn_norm": stacked("attn_norm", (d,), 0.1, 1.0),
        "wq": stacked("wq", (d, h, hd), proj),
        "wk": stacked("wk", (d, kv, hd), proj),
        "wv": stacked("wv", (d, kv, hd), proj * WV_GAIN),
        "wo": stacked("wo", (h, hd, d), out),
        "q_norm": stacked("q_norm", (hd,), 0.1, 1.0),
        "k_norm": stacked("k_norm", (hd,), 0.1, 1.0),
        "wq_i": stacked("wq_i", (d, j, di), proj),
        "wk_i": stacked("wk_i", (d, di), proj),
        "w_i": stacked("w_i", (d, j), proj),
        "ki_norm": stacked("ki_norm", (di,), 0.1, 1.0),
        "ki_norm_b": stacked("ki_norm_b", (di,), 0.1),
        "mlp_norm": stacked("mlp_norm", (d,), 0.1, 1.0),
        "router": stacked("router", (d, e), proj),
        "w_gate": experts("w_gate", (d, f), proj),
        "w_up": experts("w_up", (d, f), proj),
        "w_down": experts("w_down", (f, d), out),
    }
    params = {"embed": draw(ks["embed"], (v, d), EMBED_STD, 0.0),
              "layers": layers,
              "final_norm": draw(ks["final_norm"], (d,), 0.1, 1.0)}
    if not c.tie_embeddings:
        params["lm_head"] = draw(ks["lm_head"], (d, v), proj, 0.0)
    return params


# -- what a step needs -------------------------------------------------------

def layer_params(cf: Dict[str, Any]) -> Dict[str, int]:
    """Weights of one layer by part: attention, indexer, router, one
    expert."""
    d, sa = cf["hidden_size"], cf["sa_config"]
    q = cf["num_attention_heads"] * cf["head_dim"]
    kv = cf["num_key_value_heads"] * cf["head_dim"]
    j, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return {"attention": d * q + 2 * d * kv + q * d,
            "indexer": d * (j * di + di + j),
            "router": d * cf["num_experts"],
            "expert": 3 * d * cf["moe_intermediate_size"]}


def step_needs(cf: Dict[str, Any], rows: Iterable[Tuple[int, int, int]],
               counters: Dict[str, int]) -> Dict[str, Dict[str, int]]:
    """What one engine step needs, by scope and for the whole step.
    ``rows``: per active request (pos, n, samples), as
    ``opcount.decode_step_needs`` takes them. ``counters``: the step's own
    growth of the engine's ``moe_expert_tokens_sum`` (token-expert pairs
    over all layers) and ``moe_experts_hit`` (experts with a token, summed
    over layers): which experts a step hits is the router's choice and no
    shape gives it.

    - ``moe_experts``: each expert HIT read once, 6 FLOPs a weight for each
      pair routed to it, the pairs' activations in and out;
    - ``dsa_indexer``: the indexer's projections for every fed token and,
      for rows past ``topk`` keys, the ``kI`` of the row's live keys read
      ONCE and a product of every (query, head, causal key);
    - ``paged_sparse_attention``: rows past ``topk``: K and V of the
      selected keys read once (a single-token row selects ``topk``; a chunk
      row's queries select within the row's live keys, read once), score
      and value products over ``topk`` keys a query;
    - ``step``: those, the other weights once (attention, indexer, router,
      the head if a row samples), rows of at most ``topk`` keys attending to
      all of them, the new tokens' cache rows, embeddings and logits."""
    L, sa = cf["num_hidden_layers"], cf["sa_config"]
    wb = BYTES[cf["precision"]["weights"]]
    ab = BYTES[cf["precision"]["activations"]]
    d, topk = cf["hidden_size"], sa["topk"]
    heads, hd = cf["num_attention_heads"], cf["head_dim"]
    j, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    part = layer_params(cf)
    kv_token = 2 * cf["num_key_value_heads"] * hd * ab     # a layer's K+V
    pairs, hit = counters["moe_expert_tokens_sum"], counters["moe_experts_hit"]

    fed = sampled = 0
    idx_keys = idx_pairs = sel_keys = sel_pairs = dense_keys = dense_pairs = 0
    for pos, n, samples in rows:
        fed += n
        sampled += 1 if samples else 0
        seen = sum(p + 1 for p in range(pos, pos + n))   # causal pairs
        if pos + n > topk:
            idx_keys += pos + n
            idx_pairs += seen
            sel_keys += topk if n == 1 else pos + n
            sel_pairs += sum(min(p + 1, topk) for p in range(pos, pos + n))
        else:
            dense_keys += pos + n
            dense_pairs += seen
    experts = {"flops": 2 * part["expert"] * pairs,
               "bytes": wb * part["expert"] * hit + 2 * ab * d * pairs}
    indexer = {"flops": L * (2 * part["indexer"] * fed + 2 * j * di * idx_pairs),
               "bytes": L * (wb * part["indexer"] + ab * di * (idx_keys + fed))}
    sparse = {"flops": L * 4 * heads * hd * sel_pairs,
              "bytes": L * kv_token * sel_keys}
    head = cf["hidden_size"] * cf["vocab_size"]
    step = {
        "flops": (experts["flops"] + indexer["flops"] + sparse["flops"]
                  + L * (2 * (part["attention"] + part["router"]) * fed
                         + 4 * heads * hd * dense_pairs)
                  + 2 * head * sampled),
        "bytes": (experts["bytes"] + indexer["bytes"] + sparse["bytes"]
                  + L * (wb * (part["attention"] + part["router"])
                         + kv_token * (dense_keys + fed))
                  + (wb * head if sampled else 0)
                  + wb * d * fed + 4 * cf["vocab_size"] * sampled)}
    return {"moe_experts": experts, "dsa_indexer": indexer,
            "paged_sparse_attention": sparse, "step": step,
            "fed": fed, "sampled": sampled}
