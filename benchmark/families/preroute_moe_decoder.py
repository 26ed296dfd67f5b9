"""The pre-routed MoE decoder family (SmallThinker-21BA3B-Instruct) for the
``serve_state_family`` kind: from a configuration file's published keys to the
program's ``TransformerConfig``, its seeded weights, the toy widths of a
rehearsal, the program's scopes, kernels and per-step counters that the kind
times and keeps, and what a step NEEDS (the numerators of the family's
roofline shares). The reference is ``reference/preroute_moe_decoder.py`` (its
docstring has the equations); the family's name is the configuration's
``reference`` key.

What a reader of the family needs to know:

- **The layer.** Plain GQA attention (no bias, no gate, no head norm) between
  two pre-norms, then ``moe_num_primary_experts`` ReLU-gated experts held
  WHOLE, ``moe_num_active_primary_experts`` a token, no shared expert and no
  dense layer. The router reads the layer's normed INPUT, ahead of the
  attention (the program's ``router_input="attn_norm"``): softmax over all the
  experts, top-k, renormalised. ``sliding_window_layout`` says which layers
  have a window of ``sliding_window_size`` keys and ``rope_layout`` which
  rotate (the same layers: the full ones have no positional encoding); a
  period is full, sliding x 3.
- **What is cached.** Two pools, each with block ids of its own, as the
  windowed MoE family's: the full layers' K and V under a table as wide as the
  context, the sliding layers' under a table that holds a row's LIVE window
  only. ``engine.num_blocks`` sizes the first; the second is the engine's
  default, ``max_slots`` window tables.
- **Every pair is here.** No share of the experts, no exchange: the program's
  ``moe_pairs_held`` equals ``moe_pairs_routed``, and a step's bytes follow
  the experts it HITS (``moe_experts_hit``), which follow the rows alive.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, Tuple

from benchmark.opcount import BYTES

#: the program's scopes whose device time a traced run reports
#: (``jax.named_scope`` in ``ray_tpu/models/transformer.py`` and
#: ``ops/moe.py``; the attention kernel's own scope lies inside
#: ``swa_attention`` / ``global_attention``; ``moe_router`` runs in the
#: layer's first half here, beside the q, k and v projections)
SCOPES = ("swa_attention", "global_attention", "moe_router", "moe_experts")

#: operations that reach the compiled program without their scope, by
#: instruction-name prefix -> scope (XLA rewrites ``lax.ragged_dot`` into
#: custom calls named ``ragged-dot-*``)
KERNELS = {"ragged-dot": "moe_experts"}

#: engine counters kept per step (their growth over the step)
STEP_COUNTERS = ("moe_expert_tokens_sum", "moe_expert_tokens_max",
                 "moe_experts_hit", "moe_pairs_routed", "moe_pairs_held",
                 "moe_kernel_pairs",
                 "window_keys_read", "shared_kv_keys_read",
                 "window_blocks_held", "window_blocks_full_table",
                 "window_blocks_released")

#: the two scopes ``whole_experts_roofline`` reads together
EXPERT_SCOPES = ("moe_router", "moe_experts")

#: toy widths for ``--rehearse-cpu`` and the CPU tests: control flow only
#: (8 layers, full / sliding x 3 twice; a window of 8 tokens, two blocks of
#: the rehearsal's four; 8 experts top-3; a hidden size of whole lanes that
#: is not whole [8, 128] tiles, as the model's 2560)
TOY_WIDTHS = {"hidden_size": 384, "moe_ffn_hidden_size": 128,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 16, "num_hidden_layers": 8, "vocab_size": 512,
              "moe_num_primary_experts": 8,
              "moe_num_active_primary_experts": 3,
              "sliding_window_size": 8, "max_position_embeddings": 4096}

#: the seeded weights' scales that are not the usual ones (``build_params``;
#: each is in the configuration's ``assumed.weights``)
EMBED_STD = 0.1
EXPERT_GAIN = 0.2
ROUTER_GAIN = 4.0
Q_GAIN = 2.0


def layers(cf: Dict[str, Any]) -> Dict[str, Any]:
    """The layers that run: how many, each one's window (0: full), the
    counts by kind."""
    n = int(cf["num_hidden_layers"])
    sliding, rope = cf["sliding_window_layout"][:n], cf["rope_layout"][:n]
    if len(sliding) != n or list(sliding) != list(rope) \
            or set(sliding) - {0, 1}:
        raise NotImplementedError("a layer pattern this family file does "
                                  "not describe")
    windows = tuple(int(cf["sliding_window_size"]) if s else 0
                    for s in sliding)
    n_win = sum(w > 0 for w in windows)
    return {"n": n, "windows": windows, "sliding": n_win, "full": n - n_win}


def transformer_config(cf: Dict[str, Any], **overrides):
    """``TransformerConfig`` from the published keys."""
    from ray_tpu.models.config import TransformerConfig

    if cf["rope_scaling"] is not None or cf["tie_word_embeddings"] \
            or not cf["moe_primary_router_apply_softmax"]:
        raise NotImplementedError("a layer pattern this family file does "
                                  "not describe")
    prec, lay = cf["precision"], layers(cf)
    kw = dict(
        vocab_size=cf["vocab_size"], d_model=cf["hidden_size"],
        n_layers=lay["n"], n_heads=cf["num_attention_heads"],
        n_kv_heads=cf["num_key_value_heads"], head_dim=cf["head_dim"],
        d_ff=cf["moe_ffn_hidden_size"],
        max_seq_len=cf["max_position_embeddings"],
        mlp="swiglu", norm="rms", positions="rope",
        rope_theta=float(cf["rope_theta"]), norm_eps=float(cf["rms_norm_eps"]),
        tie_embeddings=False, rope_layers="window",
        sliding_window=int(cf["sliding_window_size"]),
        attn_windows=lay["windows"],
        d_ff_expert=cf["moe_ffn_hidden_size"],
        num_experts=cf["moe_num_primary_experts"],
        expert_top_k=cf["moe_num_active_primary_experts"],
        expert_norm_topk=bool(cf["norm_topk_prob"]),
        expert_act="relu", router_input="attn_norm", remat=False,
        dtype=prec["activations"], param_dtype=prec["weights"])
    kw.update(overrides)
    return TransformerConfig(**kw)


def build_params(config, key):
    """The parameter tree for ``config`` from a key (traceable), laid out as
    the program has it (``models.windowed_moe.block_shapes``: the layout is
    the program's interface, the values are drawn here). Normal weights at
    the usual scales (``fan_in^-0.5`` in, that over ``sqrt(2 L)`` out); every
    RMSNorm gain N(1, 0.1), away from its trivial value so that leaving one
    out shows in the logits. Four scales are not the usual ones
    (``families/windowed_moe_decoder.py`` has the findings they start from;
    the configuration's ``assumed.weights`` and ``PERF.md`` section 2 have
    the readings each was chosen by):

    - the EMBEDDING at std ``EMBED_STD`` (0.1), not 0.02 and not 1: the model
      has no multiplier on it. At unit scale the embedding, which is exact on
      both sides of the check, IS the stream, and the reference over int8
      weights reads what the sound bf16 engine reads; at 0.1 the branches
      are the larger part of the stream from the first layers on;
    - the experts' ``w_down`` ``EXPERT_GAIN`` (0.2) times: a router's sixth
      choice that flips on bf16 noise moves a position by a whole pair, and
      here every position meets six pairs in every one of eight layers, none
      of them another chip's: about one position in ten holds such a flip.
      At 0.35 a flipped position read 0.03-0.05 beside the others' 0.006 and
      a sample's pooled reading followed the COUNT of its flips (0.0073 to
      0.0148 over 36 seeds), which left no room under the fp8-pool control;
    - the ROUTER ``ROUTER_GAIN`` (4) times, its logits at deviation 4 and not
      1: a trained router is decisive, a seeded one at unit scale is nearly
      flat, so that its sixth choice weighs a tenth of the layer after
      renormalisation and its seventh as much; four times wider the sixth
      weighs an eightieth, and a flip costs an eighth of what it cost. WHICH
      six experts a token takes is the same at every gain: the load over the
      experts does not move;
    - ``wq`` ``Q_GAIN`` (2) times, the attention's scores at deviation 2
      and not 1: a trained head reads a handful of keys, a seeded one at unit
      scale reads the mean of its thousands, in which the rounding of a KV
      pool's values averages out and the attention branch itself is a
      small part of a value's norm; at 2 a query's weight lies on the
      fiftieth part of its keys, the branch carries what the pool holds, and
      an fp8 pool reads four times the sound engine where it read twice
      (``families/windowed_moe_decoder.py`` sets its scores at the same
      deviation through ``q_norm``'s gain; this model has no q/k-norm)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import windowed_moe

    c = config
    dt = jnp.dtype(c.param_dtype)
    f32 = jnp.float32

    def draw(k, shape, how, gain):
        x = jax.random.normal(k, shape, f32)
        if how == "gain":
            x = 1.0 + 0.1 * x
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

    if c.vocab_size % 8 or c.tie_embeddings or c.dense_layers \
            or c.shared_experts:
        raise NotImplementedError("a tied head, a vocabulary that does not "
                                  "divide by 8, a dense layer or a shared "
                                  "expert")
    shapes = windowed_moe.block_shapes(c)["moe"]
    k_embed, k_norm, k_head, k_layers = jax.random.split(key, 4)
    routed = ("w_gate", "w_up", "w_down")
    gains = {"w_down": EXPERT_GAIN, "router": ROUTER_GAIN, "wq": Q_GAIN}
    tree = {"moe": {
        name: leaf(k, c.n_layers, shape, how, gains.get(name, 1.0),
                   name in routed)
        for k, (name, (shape, _, how)) in zip(
            jax.random.split(k_layers, len(shapes)), shapes.items())}}
    d, v = c.d_model, c.vocab_size
    rows = jax.lax.map(
        lambda k: (jax.random.normal(k, (v // 8, d), f32)
                   * EMBED_STD).astype(dt), jax.random.split(k_embed, 8))
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
    return {
        # Wq, Wk, Wv, Wo
        "attn_proj": 2 * d * q + 2 * d * kv,
        "expert": 3 * d * cf["moe_ffn_hidden_size"],
        "router": d * cf["moe_num_primary_experts"],
        "norms": 2 * d,
    }


def kv_token_bytes(cf: Dict[str, Any]) -> int:
    """A token's K and V in ONE layer's pool."""
    return 2 * cf["num_key_value_heads"] * cf["head_dim"] \
        * BYTES[cf["precision"]["activations"]]


def parameters(cf: Dict[str, Any]) -> int:
    part, lay = layer_params(cf), layers(cf)
    return (lay["n"] * (part["attn_proj"] + part["norms"] + part["router"]
                        + cf["moe_num_primary_experts"] * part["expert"])
            + 2 * cf["vocab_size"] * cf["hidden_size"] + cf["hidden_size"])


def device_bytes(cf: Dict[str, Any]) -> Dict[str, int]:
    """Bytes of the served weights and of the two pools, from the shapes
    (the configuration's ``device_bytes`` are these)."""
    lay, params = layers(cf), parameters(cf)
    eng = cf["engine"]
    bs, kv = eng["block_size"], kv_token_bytes(cf)
    # a row's window table: window + chunk - 1 tokens wherever they start
    table = (cf["sliding_window_size"] + eng["prefill_chunk"] + bs - 3) \
        // bs + 1
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
    growth of the engine's ``moe_pairs_held`` (token-expert pairs, over the
    layers: all of the routed ones here) and ``moe_experts_hit`` (experts
    with a token, summed over layers): which experts a step hits is the
    router's choice and no shape gives it.

    - ``swa_attention`` (the sliding layers): the keys a row's queries can
      see read once a layer (from ``max(pos - window + 1, 0)`` to the row's
      last: at most ``window + n - 1``), the queries in and the output out,
      and ``4 hd`` FLOPs a query head a visible (query, key) pair;
    - ``global_attention`` (the full layers): the same over every earlier
      key;
    - ``whole_experts`` (the program's ``moe_router`` and ``moe_experts``
      together, every layer): the router once, each expert HIT once, 2 FLOPs
      a weight a fed token (router) or a routed pair, the tokens and pairs
      in and out;
    - ``step``: those, the four projections and the two norms of every layer
      once with 2 FLOPs a weight a fed token, the step's K and V written,
      the embedding rows looked up, and if a row samples the head read once
      and its float32 logits written."""
    lay, part = layers(cf), layer_params(cf)
    wb = BYTES[cf["precision"]["weights"]]
    ab = BYTES[cf["precision"]["activations"]]
    d, hd, heads = (cf["hidden_size"], cf["head_dim"],
                    cf["num_attention_heads"])
    q, kv = heads * hd, cf["num_key_value_heads"] * hd
    window = int(cf["sliding_window_size"])
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
    experts = {
        "flops": lay["n"] * 2 * part["router"] * fed
        + 2 * part["expert"] * pairs,
        "bytes": lay["n"] * (wb * part["router"] + 2 * ab * d * fed)
        + wb * part["expert"] * hit + 2 * ab * d * pairs}
    other = lay["n"] * (part["attn_proj"] + part["norms"]) + d
    head = d * cf["vocab_size"]
    scopes = (*attention.values(), experts)
    step = {"flops": sum(s["flops"] for s in scopes) + 2 * other * fed
            + 2 * head * sampled,
            "bytes": sum(s["bytes"] for s in scopes) + wb * other
            + lay["n"] * (kv_token * fed + ab * fed * (2 * d + 2 * q + 2 * kv))
            + wb * d * fed + (wb * head if sampled else 0)
            + 4 * cf["vocab_size"] * sampled}
    return {**attention, "whole_experts": experts, "step": step,
            "fed": fed, "sampled": sampled}
