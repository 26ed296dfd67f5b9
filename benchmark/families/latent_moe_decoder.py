"""The latent-attention MoE decoder family (Kimi-K2.5's language model,
``model_type`` kimi_k2: the DeepSeek-V3 layer under Moonshot's numbers) for
the ``serve_state_family`` kind: from a configuration file's published keys
to the program's ``TransformerConfig``, its seeded weights, the toy widths of
a rehearsal, the program's scopes, kernels and per-step counters that the kind
times and keeps, and what a step NEEDS (the numerators of the family's
roofline shares). The reference is ``reference/latent_moe_decoder.py``; the
family's name is the configuration's ``reference`` key.

What a reader of the family needs to know:

- **The layer.** Multi-head latent attention (queries through a
  ``q_lora_rank`` bottleneck; keys and values up-projected from ONE normed
  latent of ``kv_lora_rank`` a token; one YaRN-rotated key of
  ``qk_rope_head_dim`` shared by all heads), then a dense SwiGLU MLP in the
  first ``first_k_dense_replace`` layers and, after them, ``n_routed_experts``
  sigmoid-routed experts (top-k of score + selection bias, weights the
  renormalised scores times ``routed_scaling_factor``) beside
  ``n_shared_experts`` experts every token takes. The reference's docstring
  has the equations.
- **What is cached.** A token caches, a layer, its normed latent and its
  rotated key: ``kv_lora_rank + qk_rope_head_dim`` values in ONE pool
  (``cache["kv"]``). The program attends in the ABSORBED form (``W_uk``
  folded into the query, ``W_uv`` into the output: multi-query attention
  with one key of 576 whose first 512 values are its value too); the
  reference up-projects every head's keys and values.
- **The share.** A configuration file of this family may hold a chip's share
  of a deployment that divides each layer over several chips: its
  ``n_routed_experts`` is the number of experts HELD here and
  ``reduced.n_routed_experts`` gives the ``published`` count (the router's
  width) and the ``first`` held index. The router picks ``num_experts_per_tok``
  of ALL the published experts; the (token, expert) pairs whose expert is
  held are computed, the others left out, in the program and in the
  reference alike; what the absent chips would add is nobody's here. The
  engine counts ``moe_pairs_routed`` and ``moe_pairs_held``.
- **The kind** is ``serve_state_family``: the one kind that takes a family's
  scopes, kernels and per-step counters from the family's file (the lists
  below), so the cell has a ``pre_roll``. That kind draws its pre-roll with
  a seed of its own and warms the trie with the window's shared prefixes
  only, so a mix whose requests share documents names the generator
  ``document_sessions`` (both draws ask of the same documents), not
  ``sessions``.
- **The pool's width.** ``device_bytes`` gives the pool as the program shapes
  it (``pool_lanes``: rows of whole lanes, 640 for 576 values) and the
  content beside it.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, Tuple

from benchmark.opcount import BYTES

#: the program's scopes whose device time a traced run reports
#: (``jax.named_scope`` in ``ray_tpu/ops/latent_attention.py``,
#: ``models/latent.py``, ``ops/moe.py`` and ``models/transformer.py``)
SCOPES = ("mla_attention", "mla_q_proj", "mla_kv_proj", "mla_out_proj",
          "moe_router", "moe_experts", "shared_expert")

#: operations that reach the compiled program without their scope, by
#: instruction-name prefix -> scope (XLA rewrites ``lax.ragged_dot`` into
#: custom calls named ``ragged-dot-*``)
KERNELS = {"ragged-dot": "moe_experts"}

#: engine counters kept per step (their growth over the step)
STEP_COUNTERS = ("moe_expert_tokens_sum", "moe_expert_tokens_max",
                 "moe_experts_hit", "moe_pairs_routed", "moe_pairs_held",
                 "latent_tokens_read")

#: the three projection scopes ``mla_projections_roofline`` reads together
PROJECTIONS = ("mla_q_proj", "mla_kv_proj", "mla_out_proj")

#: toy widths for ``--rehearse-cpu`` and the CPU tests: control flow only
#: (3 layers: the dense one and two of the scanned expert layers; 4 of 16
#: experts held from index 4; YaRN over an original length of 32, so the toy
#: contexts pass it as the cell's pass 4096)
TOY_WIDTHS = {"hidden_size": 64, "intermediate_size": 128,
              "moe_intermediate_size": 32, "num_attention_heads": 4,
              "num_key_value_heads": 4, "num_hidden_layers": 3,
              "vocab_size": 512, "q_lora_rank": 32, "kv_lora_rank": 24,
              "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
              "v_head_dim": 16, "n_routed_experts": 4,
              "num_experts_per_tok": 4, "max_position_embeddings": 4096,
              "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 16,
                               "mscale": 1, "mscale_all_dim": 1,
                               "original_max_position_embeddings": 32,
                               "type": "yarn"},
              "reduced": {"n_routed_experts": {"published": 16, "here": 4,
                                               "first": 4}}}

#: the seeded weights' scales that are not the usual ones (``build_params``;
#: each is in the configuration's ``assumed.weights``)
EMBED_STD = 1.0
Q_GAIN = 1.25
UV_GAIN = 4.0
EXPERT_GAIN = 0.7


def share(cf: Dict[str, Any]) -> Dict[str, int]:
    """The router's width, the experts held here and the first held index."""
    cut = cf["reduced"].get("n_routed_experts", {})
    held = int(cf["n_routed_experts"])
    return {"published": int(cut.get("published", held)), "held": held,
            "first": int(cut.get("first", 0))}


def transformer_config(cf: Dict[str, Any], **overrides):
    """``TransformerConfig`` from the published keys."""
    from ray_tpu.models.config import TransformerConfig

    rs = cf["rope_scaling"]
    if rs["type"] != "yarn" or cf["n_group"] != 1 or cf["topk_group"] != 1 \
            or cf["moe_layer_freq"] != 1 or cf["attention_bias"] \
            or cf["hidden_act"] != "silu" or cf["topk_method"] != "noaux_tc":
        raise NotImplementedError("a layer pattern this family file does "
                                  "not describe")
    prec, sh = cf["precision"], share(cf)
    kw = dict(
        vocab_size=cf["vocab_size"], d_model=cf["hidden_size"],
        n_layers=cf["num_hidden_layers"], n_heads=cf["num_attention_heads"],
        d_ff=cf["intermediate_size"],
        max_seq_len=cf["max_position_embeddings"],
        mlp="swiglu", norm="rms", positions="rope",
        rope_theta=float(cf["rope_theta"]), norm_eps=float(cf["rms_norm_eps"]),
        tie_embeddings=bool(cf["tie_word_embeddings"]),
        q_lora_rank=cf["q_lora_rank"], kv_lora_rank=cf["kv_lora_rank"],
        qk_nope_head_dim=cf["qk_nope_head_dim"],
        qk_rope_head_dim=cf["qk_rope_head_dim"], v_head_dim=cf["v_head_dim"],
        rope_factor=float(rs["factor"]), rope_beta_fast=float(rs["beta_fast"]),
        rope_beta_slow=float(rs["beta_slow"]),
        rope_original_len=int(rs["original_max_position_embeddings"]),
        rope_mscale=float(rs["mscale"]),
        rope_mscale_all_dim=float(rs["mscale_all_dim"]),
        dense_layers=cf["first_k_dense_replace"],
        d_ff_expert=cf["moe_intermediate_size"],
        shared_experts=cf["n_shared_experts"], num_experts=sh["published"],
        expert_top_k=cf["num_experts_per_tok"],
        expert_norm_topk=bool(cf["norm_topk_prob"]),
        expert_scoring=cf["scoring_func"],
        expert_scale=float(cf["routed_scaling_factor"]),
        experts_held=sh["held"], experts_first=sh["first"], remat=False,
        dtype=prec["activations"], param_dtype=prec["weights"])
    kw.update(overrides)
    return TransformerConfig(**kw)


def build_params(config, key):
    """The parameter tree for ``config`` from a key (traceable), laid out as
    the program has it (``models.latent.block_shapes``: the layout is the
    program's interface, the values are drawn here). Normal weights at the
    usual scales (``fan_in^-0.5`` in, that over ``sqrt(2 L)`` out); every
    RMSNorm gain N(1, 0.1) and the router's selection bias N(0, 0.1), away
    from their trivial values so that leaving one out shows in the logits.
    Four scales are not the usual ones:

    - the EMBEDDING at unit scale, not 0.02, so that the residual stream
      stays token-specific through the layers
      (``families/sparse_moe_decoder.py`` says what happens otherwise);
    - ``wq_b`` ``Q_GAIN`` times the usual: over 33k-41k keys a random
      model's softmax is nearly flat, every query reads the mean of the
      values and the logits cannot tell which keys were read. With the gain
      the scores (times the published ``0.1447``) have a standard deviation
      of 2.5 and a query's weight lies on some tens of keys;
    - ``w_uv`` ``UV_GAIN`` times: an average over those keys still shrinks
      the values; with the gain the attention branch carries a few per cent
      of the residual's norm, as the expert branch does;
    - the ROUTED experts' ``w_down`` ``EXPERT_GAIN`` times, UNDER the usual
      scale: this chip holds 12 of 384 experts, so a token meets one held
      (token, expert) pair over its four expert layers, and the router's
      eighth choice flips on bf16 noise in one token-layer of several; where
      the flip falls on a held expert at a sampled position, that position's
      logits move by a whole pair. At 2.0 one such flip read 0.127 on its
      position and took a sound run's pooled error (0.0085 without one) to
      0.018-0.029, into the int8 control's 0.0195-0.039; at 0.7 a pair is
      0.04 of the logits, six flips in a check's 64 positions stay under the
      limit, and the held experts' sum still reads 2.5 times over it when
      it is dropped (PERF.md section 2 has every control's reading)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import latent

    c = config
    dt = jnp.dtype(c.param_dtype)
    f32 = jnp.float32
    gains = {"wq_b": Q_GAIN, "w_uv": UV_GAIN}

    def draw(k, shape, how, gain):
        x = jax.random.normal(k, shape, f32)
        if how == "gain":
            x = 1.0 + 0.1 * x
        elif how == "bias":
            x = 0.1 * x
        else:
            kind, fan_in = how
            x = x * (gain * fan_in ** -0.5
                     / ((2 * c.n_layers) ** 0.5 if kind == "out" else 1.0))
        return x.astype(dt)

    def leaf(k, n, shape, how, gain, per_expert):
        # one layer at a time, and a layer's experts one at a time: the
        # float32 draw of a stacked leaf never exists
        one = lambda k1: draw(k1, shape, how, gain)
        if per_expert:
            one = lambda k1: jax.lax.map(
                lambda k2: draw(k2, shape[1:], how, gain),
                jax.random.split(k1, shape[0]))
        return jax.lax.map(one, jax.random.split(k, n))

    if c.vocab_size % 8 or c.tie_embeddings:
        raise NotImplementedError("a tied head, or a vocabulary that does "
                                  "not divide by 8")
    shapes = latent.block_shapes(c)
    k_embed, k_norm, k_head, k_layers = jax.random.split(key, 4)
    layers = {}
    for s, (seg, n) in enumerate(latent.segments(c)):
        ks = jax.random.split(jax.random.fold_in(k_layers, s),
                              len(shapes[seg]))
        routed = lambda name: seg == "moe" and name in (
            "w_gate", "w_up", "w_down")
        layers[seg] = {
            name: leaf(k, n, shape, how, EXPERT_GAIN
                       if routed(name) and name == "w_down"
                       else gains.get(name, 1.0), routed(name))
            for k, (name, (shape, _, how)) in zip(ks, shapes[seg].items())}
    d, v = c.d_model, c.vocab_size
    rows = jax.lax.map(
        lambda k: (jax.random.normal(k, (v // 8, d), f32)
                   * EMBED_STD).astype(dt), jax.random.split(k_embed, 8))
    cols = jax.lax.map(
        lambda k: (jax.random.normal(k, (d, v // 8), f32)
                   * d ** -0.5).astype(dt), jax.random.split(k_head, 8))
    return {"embed": rows.reshape(v, d), "layers": layers,
            "final_norm": draw(k_norm, (d,), "gain", 1.0),
            "lm_head": jnp.moveaxis(cols, 0, 1).reshape(d, v)}


# -- what a step needs -------------------------------------------------------

def layer_params(cf: Dict[str, Any]) -> Dict[str, int]:
    """Weights of one layer by part (matrices and the gains beside them)."""
    d, h = cf["hidden_size"], cf["num_attention_heads"]
    qr, kr = cf["q_lora_rank"], cf["kv_lora_rank"]
    nope, rope, v = (cf["qk_nope_head_dim"], cf["qk_rope_head_dim"],
                     cf["v_head_dim"])
    fe = cf["moe_intermediate_size"]
    return {
        # W_qa, its norm, W_qb and W_uk (folded into the query)
        "mla_q_proj": d * qr + qr + qr * h * (nope + rope) + h * nope * kr,
        "mla_kv_proj": d * (kr + rope) + kr,
        # W_uv (folded into the output) and W_o
        "mla_out_proj": h * kr * v + h * v * d,
        "dense_mlp": 3 * d * cf["intermediate_size"],
        "expert": 3 * d * fe,
        "shared": 3 * d * fe * cf["n_shared_experts"],
        "router": d * share(cf)["published"] + share(cf)["published"],
        "norms": 2 * d,
    }


def pool_lanes(width: int) -> int:
    """The pool's last axis for ``width`` values a token: whole lanes of 128,
    as the program's ``ops.latent_attention.pool_width`` has it (576 -> 640,
    which is what a row of 576 takes in the chip's tiled memory anyway)."""
    return -(-width // 128) * 128


def device_bytes(cf: Dict[str, Any]) -> Dict[str, int]:
    """Bytes of the served weights and of the latent pool, from the shapes
    (the configuration's ``device_bytes`` are these)."""
    part = layer_params(cf)
    L, n_dense = cf["num_hidden_layers"], cf["first_k_dense_replace"]
    attn = sum(part[s] for s in PROJECTIONS) + part["norms"]
    params = (L * attn + n_dense * part["dense_mlp"]
              + (L - n_dense) * (cf["n_routed_experts"] * part["expert"]
                                 + part["shared"] + part["router"])
              + cf["vocab_size"] * cf["hidden_size"]
              * (1 if cf["tie_word_embeddings"] else 2) + cf["hidden_size"])
    ab = BYTES[cf["precision"]["activations"]]
    content = cf["kv_lora_rank"] + cf["qk_rope_head_dim"]
    eng = cf["engine"]
    tokens = eng["num_blocks"] * eng["block_size"]
    return {"parameters": params,
            "weights": params * BYTES[cf["precision"]["weights"]],
            # what a token caches, and what it takes in the pool: a row of
            # whole lanes (``ops/latent_attention.py::pool_width``)
            "kv_per_token_content": L * content * ab,
            "kv_per_token": L * pool_lanes(content) * ab,
            "kv_pool_content": L * content * ab * tokens,
            "kv_pool": L * pool_lanes(content) * ab * tokens}


def step_needs(cf: Dict[str, Any], rows: Iterable[Tuple[int, int, int]],
               counters: Dict[str, int]) -> Dict[str, Dict[str, int]]:
    """What one engine step needs, by scope and for the whole step.
    ``rows``: per active request (pos, n, samples), as
    ``opcount.decode_step_needs`` takes them. ``counters``: the step's own
    growth of the engine's ``moe_pairs_held`` (token-expert pairs whose
    expert is held here, over the expert layers) and ``moe_experts_hit``
    (held experts with a token, summed over layers): which experts a step
    hits is the router's choice and no shape gives it.

    - ``mla_attention`` (every layer): a row's live tokens' latent vectors
      (``kv_lora_rank + qk_rope_head_dim`` values) read ONCE a layer for all
      heads and the step's new ones written; per causal (query, key) pair and
      head a score over the vector and a sum over its latent part, the
      absorbed form (``2 (rank + rope) + 2 rank`` FLOPs: at the cell's chunk
      of 128 it is the cheaper of the two forms, see
      ``ray_tpu/ops/latent_attention.py``);
    - ``mla_q_proj``, ``mla_kv_proj``, ``mla_out_proj``: their weights once
      a layer (``W_uk`` with the query's, ``W_uv`` with the output's), 2
      FLOPs a weight a fed token, the tokens' activations in and out;
      ``mla_projections`` is the three together;
    - ``moe_experts``: each held expert HIT read once, 2 FLOPs a weight for
      each pair routed to it, the pairs' activations in and out;
    - ``shared_expert`` (every expert layer): its weights once, 2 FLOPs a
      weight a fed token, the tokens in and out;
    - ``step``: those, the dense layers' MLPs, the routers and the norms
      once, the embedding rows looked up, and if a row samples the head read
      once and its float32 logits written."""
    L, n_dense = cf["num_hidden_layers"], cf["first_k_dense_replace"]
    n_moe = L - n_dense
    wb = BYTES[cf["precision"]["weights"]]
    ab = BYTES[cf["precision"]["activations"]]
    part = layer_params(cf)
    d, h = cf["hidden_size"], cf["num_attention_heads"]
    kr, rope, v = cf["kv_lora_rank"], cf["qk_rope_head_dim"], cf["v_head_dim"]
    width = kr + rope
    pairs = counters.get("moe_pairs_held", 0)
    hit = counters.get("moe_experts_hit", 0)

    fed = sampled = read = causal = 0
    for pos, n, samples in rows:
        fed += n
        sampled += 1 if samples else 0
        read += pos + n
        causal += sum(p + 1 for p in range(pos, pos + n))
    attention = {"flops": L * h * (2 * width + 2 * kr) * causal,
                 "bytes": L * ab * width * (read + fed)}
    # activations a fed token carries into and out of each projection
    acts = {"mla_q_proj": d + h * width, "mla_kv_proj": d + width,
            "mla_out_proj": h * kr + d}
    proj = {s: {"flops": L * 2 * part[s] * fed,
                "bytes": L * (wb * part[s] + ab * acts[s] * fed)}
            for s in PROJECTIONS}
    together = {k: sum(p[k] for p in proj.values())
                for k in ("flops", "bytes")}
    experts = {"flops": 2 * part["expert"] * pairs,
               "bytes": wb * part["expert"] * hit + 2 * ab * d * pairs}
    shared = {"flops": n_moe * 2 * part["shared"] * fed,
              "bytes": n_moe * (wb * part["shared"] + 2 * ab * d * fed)}
    other = n_dense * part["dense_mlp"] + n_moe * part["router"] \
        + L * part["norms"] + d
    head = d * cf["vocab_size"]
    scopes = (attention, together, experts, shared)
    step = {"flops": sum(s["flops"] for s in scopes) + 2 * other * fed
            + 2 * head * sampled,
            "bytes": sum(s["bytes"] for s in scopes) + wb * other
            + wb * d * fed + (wb * head if sampled else 0)
            + 4 * cf["vocab_size"] * sampled}
    return {"mla_attention": attention, **proj, "mla_projections": together,
            "moe_experts": experts, "shared_expert": shared, "step": step,
            "fed": fed, "sampled": sampled}
