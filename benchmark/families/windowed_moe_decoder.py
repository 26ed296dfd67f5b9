"""The windowed MoE decoder family (Trinity-Large-Preview, ``model_type``
afmoe) for the ``serve_state_family`` kind: from a configuration file's
published keys to the program's ``TransformerConfig``, its seeded weights, the
toy widths of a rehearsal, the program's scopes, kernels and per-step counters
that the kind times and keeps, and what a step NEEDS (the numerators of the
family's roofline shares). The reference is
``reference/windowed_moe_decoder.py`` (its docstring has the equations); the
family's name is the configuration's ``reference`` key.

What a reader of the family needs to know:

- **The layer.** Gated GQA attention (a fourth projection of the layer's
  normed input whose sigmoid multiplies the attention's output before
  ``Wo``; RMSNorm on each query and key head), a norm on each branch's input
  AND output, then a dense SwiGLU MLP in the leading dense layers and, after
  them, ``num_experts`` sigmoid-routed experts (top-k of score + selection
  bias, weights the renormalised scores times ``route_scale``) beside one
  shared expert. ``layer_types`` says which layers are ``sliding_attention``
  (RoPE, a window of ``sliding_window`` keys) and which ``full_attention``
  (no positional encoding, every earlier key).
- **What is cached.** Two pools, each with block ids of its own: the full
  layers' K and V under a table as wide as the context, and the sliding
  layers' under a table that holds a row's LIVE window only (the engine
  releases the blocks behind it). ``engine.num_blocks`` sizes the first;
  the second is the engine's default, ``max_slots`` window tables (the
  harness hands the engine a fixed list of keys and ``window_blocks`` is not
  among them: ``PERF.md`` section 7).
- **The share.** As ``families/latent_moe_decoder.py``: the file's
  ``num_experts`` is the number HELD here, ``reduced.num_experts`` gives the
  ``published`` count (the router's width) and the ``first`` held index; the
  pairs whose expert is held are computed, the others left out, in program
  and reference alike. The file keeps ``num_dense_layers`` and
  ``layer_types`` as published; ``reduced.num_hidden_layers.dense_here`` says
  how many of the layers that run are the leading dense ones.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, Tuple

from benchmark.opcount import BYTES

#: the program's scopes whose device time a traced run reports
#: (``jax.named_scope`` in ``ray_tpu/models/transformer.py`` and
#: ``ops/moe.py``; the attention kernel's own ``paged_attention`` scope lies
#: inside ``swa_attention`` / ``global_attention``)
SCOPES = ("swa_attention", "global_attention", "gated_attn_proj",
          "moe_router", "moe_experts", "shared_expert")

#: operations that reach the compiled program without their scope, by
#: instruction-name prefix -> scope (XLA rewrites ``lax.ragged_dot`` into
#: custom calls named ``ragged-dot-*``)
KERNELS = {"ragged-dot": "moe_experts"}

#: engine counters kept per step (their growth over the step)
STEP_COUNTERS = ("moe_expert_tokens_sum", "moe_expert_tokens_max",
                 "moe_experts_hit", "moe_pairs_routed", "moe_pairs_held",
                 "window_keys_read", "shared_kv_keys_read",
                 "window_blocks_held", "window_blocks_full_table",
                 "window_blocks_released")

#: the three scopes ``ep8_experts_roofline`` reads together
EXPERT_SCOPES = ("moe_router", "moe_experts", "shared_expert")

#: toy widths for ``--rehearse-cpu`` and the CPU tests: control flow only
#: (5 layers: the dense one and four expert layers, sliding x 3, full,
#: sliding; a window of 8 tokens, two blocks of the rehearsal's four; 4 of 16
#: experts held from index 4)
TOY_WIDTHS = {"hidden_size": 64, "intermediate_size": 128,
              "moe_intermediate_size": 32, "num_attention_heads": 4,
              "num_key_value_heads": 2, "head_dim": 16,
              "num_hidden_layers": 5, "vocab_size": 512,
              "num_experts": 4, "num_experts_per_tok": 4,
              "sliding_window": 8, "max_position_embeddings": 4096,
              "reduced": {"num_hidden_layers": {"published": 60, "here": 5,
                                                "dense_here": 1},
                          "num_experts": {"published": 16, "here": 4,
                                          "first": 4}}}

#: the seeded weights' scales that are not the usual ones (``build_params``;
#: each is in the configuration's ``assumed.weights``)
EMBED_STD = 1.0
Q_GAIN = 2.0
EXPERT_GAIN = 0.35
BIAS_STD = 0.03


def post_gain(n_layers: int) -> float:
    """The post-norms' gains' mean: depth-scaled, ``(2 L)^-0.5``."""
    return (2 * n_layers) ** -0.5


def share(cf: Dict[str, Any]) -> Dict[str, int]:
    """The router's width, the experts held here and the first held index."""
    cut = cf.get("reduced", {}).get("num_experts", {})
    held = int(cf["num_experts"])
    return {"published": int(cut.get("published", held)), "held": held,
            "first": int(cut.get("first", 0))}


def layers(cf: Dict[str, Any]) -> Dict[str, Any]:
    """The layers that run: how many, how many of them the leading dense
    ones, each one's window (0: full), the counts by kind."""
    n = int(cf["num_hidden_layers"])
    cut = cf.get("reduced", {}).get("num_hidden_layers", {})
    dense = int(cut.get("dense_here", cf["num_dense_layers"]))
    kinds = cf["layer_types"][:n]
    if len(kinds) != n or dense >= n \
            or set(kinds) - {"sliding_attention", "full_attention"}:
        raise NotImplementedError("a layer pattern this family file does "
                                  "not describe")
    windows = tuple(int(cf["sliding_window"]) if k == "sliding_attention"
                    else 0 for k in kinds)
    n_win = sum(w > 0 for w in windows)
    return {"n": n, "dense": dense, "moe": n - dense, "windows": windows,
            "sliding": n_win, "full": n - n_win}


def transformer_config(cf: Dict[str, Any], **overrides):
    """``TransformerConfig`` from the published keys."""
    from ray_tpu.models.config import TransformerConfig

    if cf["n_group"] != 1 or cf["topk_group"] != 1 \
            or cf["num_expert_groups"] != 1 or cf["num_limited_groups"] != 1 \
            or cf["rope_scaling"] is not None or cf["hidden_act"] != "silu" \
            or cf["tie_word_embeddings"] or not cf["mup_enabled"] \
            or cf["global_attn_every_n_layers"] != 4:
        raise NotImplementedError("a layer pattern this family file does "
                                  "not describe")
    prec, sh, lay = cf["precision"], share(cf), layers(cf)
    kw = dict(
        vocab_size=cf["vocab_size"], d_model=cf["hidden_size"],
        n_layers=lay["n"], n_heads=cf["num_attention_heads"],
        n_kv_heads=cf["num_key_value_heads"], head_dim=cf["head_dim"],
        d_ff=cf["intermediate_size"],
        max_seq_len=cf["max_position_embeddings"],
        mlp="swiglu", norm="rms", positions="rope",
        rope_theta=float(cf["rope_theta"]), norm_eps=float(cf["rms_norm_eps"]),
        tie_embeddings=False, qk_norm=True, attn_gate=True, post_norms=True,
        rope_layers="window",
        sliding_window=int(cf["sliding_window"]),
        attn_windows=lay["windows"],
        embedding_multiplier=float(cf["hidden_size"]) ** 0.5,
        dense_layers=lay["dense"], d_ff_expert=cf["moe_intermediate_size"],
        shared_experts=cf["num_shared_experts"], num_experts=sh["published"],
        expert_top_k=cf["num_experts_per_tok"],
        expert_norm_topk=bool(cf["route_norm"]),
        expert_scoring=cf["score_func"],
        expert_scale=float(cf["route_scale"]),
        experts_held=sh["held"], experts_first=sh["first"], remat=False,
        dtype=prec["activations"], param_dtype=prec["weights"])
    kw.update(overrides)
    return TransformerConfig(**kw)


def build_params(config, key):
    """The parameter tree for ``config`` from a key (traceable), laid out as
    the program has it (``models.windowed_moe.block_shapes``: the layout is
    the program's interface, the values are drawn here). Normal weights at
    the usual scales (``fan_in^-0.5`` in, that over ``sqrt(2 L)`` out); every
    RMSNorm gain (the four norms a layer, the two head norms, the final one)
    N(1, 0.1) and the router's selection bias N(0, 0.1), away from their
    trivial values so that leaving one out shows in the logits; the gate's
    projection at the usual scale, so its sigmoid spreads over (0.1, 0.9)
    and a gate left out doubles the branch. Five scales are not the usual
    ones, each read against the check on a 512-wide cut on the CPU and then
    on the chip (PERF.md sections 2 and 4 have the readings):

    - the EMBEDDING times its fixed multiplier at unit scale, not 0.02 x
      ``sqrt(d)``;
    - the POST-NORMS' gains N(g, 0.1 g) with ``g = (2 L)^-0.5`` (0.316 at
      five layers), the "depth-scaled" start the model's description
      names: a branch then adds a third of the stream's size and not all
      of it. At gains of 1 every branch is as large as the embedding, and
      each attention branch hands its whole error on: a softmax of
      deviation ``s`` multiplies a relative error in q and k by about
      ``1.4 s``, five layers deep (the first chip readings: sound 0.041
      beside the int8 control's 0.065 at ``s`` 1.6, 0.079 beside 0.143 at
      2.5);
    - ``q_norm``'s GAIN N(``Q_GAIN``, 0.2): after q/k-norm a score is a dot
      of two unit-RMS heads over ``sqrt(hd)``, deviation 1 whatever the
      projections' scale, so the scores' spread is set where the model
      sets it. At 2.0 a query's weight over a 30k context lies on some
      hundreds of keys (``N / exp(4)``) and a short one's on a few;
    - the ROUTED experts' ``w_down`` ``EXPERT_GAIN`` times
      (``families/latent_moe_decoder.py`` says why: a router's choice that
      flips on bf16 noise moves a position by a whole pair; here a position
      meets a held pair in four layers of ten, and the positions with the
      largest errors were flips: at 0.7 the worst read 0.2-0.3, at 0.35
      0.05, and the held experts' sum left out still reads seven times the
      sound reading);
    - the router's SELECTION BIAS N(0, ``BIAS_STD``), not N(0, 0.1): a
      trained bias is what BALANCES the experts' load, and a seeded one
      unbalances it. At 0.1 the held experts' share of the routed pairs
      read 8.2, 11.3, 11.4 and 13.5 % on four seeds (12.5 under even
      routing) and the step's time followed it (a gap between tokens of
      39.3 ms at 8.2 %, 42.3 at 13.5), so two runs of the cell differed by
      their weights' seed more than by anything the program does; at 0.03
      the bias still moves a token's fourth choice often enough to read
      over the limit when it is left out."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import windowed_moe

    c = config
    dt = jnp.dtype(c.param_dtype)
    f32 = jnp.float32

    def draw(k, shape, how, gain, mean=1.0):
        x = jax.random.normal(k, shape, f32)
        if how == "gain":
            x = mean * (1.0 + 0.1 * x)
        elif how == "bias":
            x = BIAS_STD * x
        else:
            kind, fan_in = how
            x = x * (gain * fan_in ** -0.5
                     / ((2 * c.n_layers) ** 0.5 if kind == "out" else 1.0))
        return x.astype(dt)

    def leaf(k, n, shape, how, gain, per_expert, mean):
        # one layer at a time, and a layer's experts one at a time: the
        # float32 draw of a stacked leaf never exists
        one = lambda k1: draw(k1, shape, how, gain, mean)
        if per_expert:
            one = lambda k1: jax.lax.map(
                lambda k2: draw(k2, shape[1:], how, gain),
                jax.random.split(k1, shape[0]))
        return jax.lax.map(one, jax.random.split(k, n))

    if c.vocab_size % 8 or c.tie_embeddings:
        raise NotImplementedError("a tied head, or a vocabulary that does "
                                  "not divide by 8")
    shapes = windowed_moe.block_shapes(c)
    k_embed, k_norm, k_head, k_layers = jax.random.split(key, 4)
    tree = {}
    for s, (seg, n) in enumerate(windowed_moe.segments(c)):
        ks = jax.random.split(jax.random.fold_in(k_layers, s),
                              len(shapes[seg]))
        routed = lambda name: seg == "moe" and name in (
            "w_gate", "w_up", "w_down")
        tree[seg] = {
            name: leaf(k, n, shape, how,
                       EXPERT_GAIN if routed(name) and name == "w_down"
                       else 1.0, routed(name),
                       Q_GAIN if name == "q_norm"
                       else post_gain(c.n_layers) if name.startswith("post_")
                       else 1.0)
            for k, (name, (shape, _, how)) in zip(ks, shapes[seg].items())}
    d, v = c.d_model, c.vocab_size
    rows = jax.lax.map(
        lambda k: (jax.random.normal(k, (v // 8, d), f32)
                   * (EMBED_STD / c.embedding_multiplier)).astype(dt),
        jax.random.split(k_embed, 8))
    cols = jax.lax.map(
        lambda k: (jax.random.normal(k, (d, v // 8), f32)
                   * d ** -0.5).astype(dt), jax.random.split(k_head, 8))
    return {"embed": rows.reshape(v, d), "layers": tree,
            "final_norm": draw(k_norm, (d,), "gain", 1.0),
            "lm_head": jnp.moveaxis(cols, 0, 1).reshape(d, v)}


# -- what a step needs -------------------------------------------------------

def layer_params(cf: Dict[str, Any]) -> Dict[str, int]:
    """Weights of one layer by part (matrices and the gains beside them)."""
    d, hd = cf["hidden_size"], cf["head_dim"]
    q, kv = cf["num_attention_heads"] * hd, cf["num_key_value_heads"] * hd
    fe = cf["moe_intermediate_size"]
    return {
        # Wq, Wk, Wv, Wg, Wo, the two head norms and the norms before and
        # after the branch
        "gated_attn_proj": 3 * d * q + 2 * d * kv + 2 * hd + 2 * d,
        "dense_mlp": 3 * d * cf["intermediate_size"],
        "expert": 3 * d * fe,
        "shared": 3 * d * fe * cf["num_shared_experts"],
        "router": d * share(cf)["published"] + share(cf)["published"],
        "mlp_norms": 2 * d,
    }


def kv_token_bytes(cf: Dict[str, Any]) -> int:
    """A token's K and V in ONE layer's pool."""
    return 2 * cf["num_key_value_heads"] * cf["head_dim"] \
        * BYTES[cf["precision"]["activations"]]


def device_bytes(cf: Dict[str, Any]) -> Dict[str, int]:
    """Bytes of the served weights and of the two pools, from the shapes
    (the configuration's ``device_bytes`` are these)."""
    part, lay = layer_params(cf), layers(cf)
    params = (lay["n"] * (part["gated_attn_proj"] + part["mlp_norms"])
              + lay["dense"] * part["dense_mlp"]
              + lay["moe"] * (cf["num_experts"] * part["expert"]
                              + part["shared"] + part["router"])
              + 2 * cf["vocab_size"] * cf["hidden_size"] + cf["hidden_size"])
    eng = cf["engine"]
    bs, kv = eng["block_size"], kv_token_bytes(cf)
    # a row's window table: window + chunk - 1 tokens wherever they start
    table = (cf["sliding_window"] + eng["prefill_chunk"] + bs - 3) // bs + 1
    return {"parameters": params,
            "weights": params * BYTES[cf["precision"]["weights"]],
            "kv_per_token_full": lay["full"] * kv,
            "kv_per_token_window": lay["sliding"] * kv,
            "kv_pool_full": lay["full"] * kv * bs * eng["num_blocks"],
            "kv_pool_window": lay["sliding"] * kv * bs
            * eng["max_slots"] * table}


def step_needs(cf: Dict[str, Any], rows: Iterable[Tuple[int, int, int]],
               counters: Dict[str, int]) -> Dict[str, Dict[str, int]]:
    """What one engine step needs, by scope and for the whole step.
    ``rows``: per active request (pos, n, samples), as
    ``opcount.decode_step_needs`` takes them. ``counters``: the step's own
    growth of the engine's ``moe_pairs_held`` (token-expert pairs whose
    expert is held here, over the expert layers) and ``moe_experts_hit``
    (held experts with a token, summed over layers): which experts a step
    hits is the router's choice and no shape gives it.

    - ``swa_attention`` (the sliding layers): the keys a row's queries can
      see read once a layer (from ``max(pos - window + 1, 0)`` to the row's
      last: at most ``window + n - 1``), the queries in and the output out,
      and ``4 hd`` FLOPs a query head a visible (query, key) pair;
    - ``global_attention`` (the full layers): the same over every earlier
      key;
    - ``gated_attn_proj`` (every layer): the five projections, the head
      norms and the branch's two norms once, 2 FLOPs a weight a fed token,
      the tokens' activations in and out (``d`` in, ``2 q + 2 kv`` out of
      the first stage; ``2 q`` in, ``d`` out of the second);
    - ``ep8_experts`` (the program's ``moe_router``, ``moe_experts`` and
      ``shared_expert`` together, every expert layer): the router and the
      shared expert once, each held expert HIT once, 2 FLOPs a weight a fed
      token (router, shared) or a pair routed to a held expert, the tokens
      and pairs in and out;
    - ``step``: those, the dense layers' MLPs and the MLPs' two norms once,
      the step's K and V written, the embedding rows looked up, and if a
      row samples the head read once and its float32 logits written."""
    lay, part = layers(cf), layer_params(cf)
    wb = BYTES[cf["precision"]["weights"]]
    ab = BYTES[cf["precision"]["activations"]]
    d, hd, heads = (cf["hidden_size"], cf["head_dim"],
                    cf["num_attention_heads"])
    q, kv = heads * hd, cf["num_key_value_heads"] * hd
    window = int(cf["sliding_window"])
    kv_token = kv_token_bytes(cf)
    pairs = counters.get("moe_pairs_held", 0)
    hit = counters.get("moe_experts_hit", 0)

    fed = sampled = 0
    keys = {"full": 0, "swa": 0}
    seen = {"full": 0, "swa": 0}
    for pos, n, samples in rows:
        fed += n
        sampled += 1 if samples else 0
        keys["full"] += pos + n
        keys["swa"] += pos + n - max(pos - window + 1, 0)
        for p in range(pos, pos + n):
            seen["full"] += p + 1
            seen["swa"] += min(p + 1, window)
    attention = {
        name: {"flops": n_layers * 4 * hd * heads * seen[k],
               "bytes": n_layers * (kv_token * keys[k] + 2 * ab * q * fed)}
        for name, k, n_layers in (("swa_attention", "swa", lay["sliding"]),
                                  ("global_attention", "full", lay["full"]))}
    proj = {"flops": lay["n"] * 2 * part["gated_attn_proj"] * fed,
            "bytes": lay["n"] * (wb * part["gated_attn_proj"]
                                 + ab * fed * (2 * d + 4 * q + 2 * kv))}
    experts = {
        "flops": lay["moe"] * 2 * (part["router"] + part["shared"]) * fed
        + 2 * part["expert"] * pairs,
        "bytes": lay["moe"] * (wb * (part["router"] + part["shared"])
                               + 2 * ab * d * fed)
        + wb * part["expert"] * hit + 2 * ab * d * pairs}
    other = lay["dense"] * part["dense_mlp"] + lay["n"] * part["mlp_norms"] \
        + d
    head = d * cf["vocab_size"]
    scopes = (*attention.values(), proj, experts)
    step = {"flops": sum(s["flops"] for s in scopes) + 2 * other * fed
            + 2 * head * sampled,
            "bytes": sum(s["bytes"] for s in scopes) + wb * other
            + lay["n"] * kv_token * fed + wb * d * fed
            + (wb * head if sampled else 0)
            + 4 * cf["vocab_size"] * sampled}
    return {**attention, "gated_attn_proj": proj, "ep8_experts": experts,
            "step": step, "fed": fed, "sampled": sampled}
