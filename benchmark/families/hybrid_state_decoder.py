"""The hybrid state-space / attention decoder family (Phi-4-mini-flash-
reasoning: the SambaY layout) for the ``serve_state_family`` kind: from a
configuration file's published keys to the program's ``TransformerConfig``,
its seeded weights, the toy widths of a rehearsal, the program's scopes,
kernels and per-step counters that the kind times and keeps, and what a step
NEEDS (the numerators of the family's roofline shares). The reference is
``reference/hybrid_state_decoder.py``; the family's name is the
configuration's ``reference`` key.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Iterable, Tuple

from benchmark.opcount import BYTES

#: the program's scopes whose device time a traced run reports
#: (``jax.named_scope`` in ``ray_tpu/ops/ssm.py`` and ``models/hybrid.py``)
SCOPES = ("ssm_scan", "gmu", "window_attention", "shared_kv_attention")

#: operations that reach the compiled program without their scope, by
#: instruction-name prefix -> scope: none in this family (all ``jax.numpy``)
KERNELS: Dict[str, str] = {}

#: engine counters kept per step (their growth over the step)
STEP_COUNTERS = ("window_blocks_held", "window_blocks_full_table",
                 "window_blocks_released", "state_slots_live",
                 "shared_kv_keys_read", "window_keys_read")

#: toy widths for ``--rehearse-cpu`` and the CPU tests: control flow only
#: (10 layers: two periods of each scanned segment and the middle one)
TOY_WIDTHS = {"hidden_size": 64, "intermediate_size": 128,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "num_hidden_layers": 10, "vocab_size": 512,
              "sliding_window": 8}

#: the embedding's scale (see ``build_params``)
EMBED_STD = 0.02


def _assumed(cf: Dict[str, Any]) -> Dict[str, int]:
    """Mamba's sizes: the configuration's ``assumed.mamba`` (Mamba-1's
    defaults; the published config has no key for them)."""
    m = cf["assumed"]["mamba"]
    return {"ssm_state": int(m["d_state"]), "ssm_conv": int(m["d_conv"]),
            "ssm_expand": int(m["expand"])}


def layer_kinds(cf: Dict[str, Any]) -> Tuple[str, ...]:
    """The kind of every layer from ``num_hidden_layers`` and
    ``mb_per_layer`` 2 (even layers the state-space side, odd the attention
    side): the self-decoder is ``a = L // 4`` periods of (mamba, window) and
    one of (mamba, full), layers 0 .. 2 a + 1 (0 .. L/2 + 1 at the published
    32), the cross-decoder the rest."""
    n = cf["num_hidden_layers"]
    if cf["mb_per_layer"] != 2 or n % 2 or n < 6 \
            or cf["mlp_bias"] or cf["lm_head_bias"] \
            or cf["hidden_act"] != "silu":
        raise NotImplementedError("a layer pattern this family file does "
                                  "not describe")
    a = n // 4
    return ("mamba", "window") * a + ("mamba", "full") \
        + ("gmu", "cross") * ((n - 2 * a - 2) // 2)


def transformer_config(cf: Dict[str, Any], **overrides):
    from ray_tpu.models.config import TransformerConfig

    prec = cf["precision"]
    kw = dict(
        vocab_size=cf["vocab_size"], d_model=cf["hidden_size"],
        n_layers=cf["num_hidden_layers"], n_heads=cf["num_attention_heads"],
        n_kv_heads=cf["num_key_value_heads"],
        head_dim=cf["hidden_size"] // cf["num_attention_heads"],
        d_ff=cf["intermediate_size"],
        max_seq_len=cf["max_position_embeddings"],
        mlp="swiglu", norm="layer", positions="none",
        norm_eps=float(cf["layer_norm_eps"]),
        tie_embeddings=bool(cf["tie_word_embeddings"]),
        sliding_window=int(cf["sliding_window"]),
        layer_kinds=layer_kinds(cf), **_assumed(cf),
        dtype=prec["activations"], param_dtype=prec["weights"])
    kw.update(overrides)
    return TransformerConfig(**kw)


def build_params(config, key):
    """The parameter tree for ``config`` from a key (traceable), laid out as
    the program has it (``models.hybrid.block_shapes``: the layout is the
    program's interface, the values are drawn here). Normal weights at the
    usual scales (``d^-0.5`` in, ``/ sqrt(2 L)`` out); every LayerNorm gain
    N(1, 0.1) and bias N(0, 0.1), the projection biases and the conv's
    N(0, 0.1), ``D`` and the sub-norm's gain N(1, 0.1), the four lambda
    vectors N(0, 0.3) (so that ``lam - lam_init`` is of order one and of
    both signs over layers), ``A_log`` log(1..n) + N(0, 0.1) down the states
    (Mamba's S4D-real start), ``b_dt`` the inverse softplus of a step drawn
    log-uniformly in [1e-3, 1e-1] (Mamba's start): all away from their
    trivial values, so that leaving one out shows in the logits. The
    EMBEDDING (tied: it is the head too) is drawn at unit scale, not 0.02,
    so that the residual stream stays token-specific through 32 layers
    (``families/sparse_moe_decoder.py`` says what happens otherwise); the
    logits' scale follows (``sqrt(d)`` of the usual), and with it the scale
    of ``tie_gap_max``."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import hybrid

    c = config
    dt = jnp.dtype(c.param_dtype)
    d, di, L = c.d_model, c.d_inner, c.n_layers
    f32 = jnp.float32
    scale = {"proj": d ** -0.5, "out": d ** -0.5 / (2 * L) ** 0.5,
             "proj_inner": di ** -0.5,
             "out_inner": di ** -0.5 / (2 * L) ** 0.5,
             "dt": c.dt_rank ** -0.5, "conv": c.ssm_conv ** -0.5,
             "bias": 0.1, "lambda": 0.3}

    def draw(k, shape, how):
        if how == "dt_bias":
            lo, hi = math.log(1e-3), math.log(1e-1)
            step = jnp.exp(jax.random.uniform(k, shape, f32) * (hi - lo) + lo)
            x = step + jnp.log(-jnp.expm1(-step))
        else:
            x = jax.random.normal(k, shape, f32)
            if how == "A_log":
                x = 0.1 * x + jnp.log(
                    jnp.arange(1, shape[0] + 1, dtype=f32))[:, None]
            elif how == "gain":
                x = 1.0 + 0.1 * x
            else:
                x = x * scale[how]
        return x.astype(dt)

    if c.vocab_size % 8 or not c.tie_embeddings:
        raise NotImplementedError("an untied head, or a vocabulary that "
                                  "does not divide by 8")
    shapes = hybrid.block_shapes(c)
    k_embed, k_norm, k_layers = jax.random.split(key, 3)
    layers = {}
    for s, (seg, periods, blocks) in enumerate(hybrid.segments(c)):
        layers[seg] = {}
        for bi, (name, kind) in enumerate(blocks.items()):
            ks = jax.random.split(jax.random.fold_in(k_layers, 8 * s + bi),
                                  len(shapes[kind]))
            # one layer at a time: the float32 draw of a stacked leaf never
            # exists
            layers[seg][name] = {
                leaf: jax.lax.map(
                    lambda k, shape=shape, how=how: draw(k, shape, how),
                    jax.random.split(k0, periods))
                for k0, (leaf, (shape, _, how))
                in zip(ks, shapes[kind].items())}
    rows = jax.lax.map(
        lambda k: (jax.random.normal(k, (c.vocab_size // 8, d), f32)
                   * EMBED_STD).astype(dt), jax.random.split(k_embed, 8))
    params = {"embed": rows.reshape(-1, d)[:c.vocab_size],
              "layers": layers,
              "final_norm": draw(k_norm, (d,), "gain"),
              "final_norm_b": draw(jax.random.fold_in(k_norm, 1), (d,),
                                   "bias")}
    return params


# -- what a step needs -------------------------------------------------------

def layer_params(cf: Dict[str, Any]) -> Dict[str, int]:
    """Weights of one layer by part (matrices and the vectors beside them),
    and the sizes the counts below share."""
    d, f = cf["hidden_size"], cf["intermediate_size"]
    heads, kvh = cf["num_attention_heads"], cf["num_key_value_heads"]
    hd = d // heads
    m = _assumed(cf)
    di, n, k = m["ssm_expand"] * d, m["ssm_state"], m["ssm_conv"]
    r = -(-d // 16)
    diff = 6 * hd
    return {
        "mlp": 3 * d * f + 4 * d,
        "mamba_proj": d * 2 * di + di * d,          # w_in, w_out
        "ssm": k * di + di + di * (r + 2 * n) + r * di + di + n * di + di,
        "attn_own": d * heads * hd + heads * hd + 2 * (d * kvh * hd
                                                       + kvh * hd)
        + heads * hd * d + d + diff,
        "attn_cross": 2 * d * heads * hd + heads * hd + d + diff,
        "gmu": 2 * d * di,
        "di": di, "n": n, "k": k, "r": r, "hd": hd,
    }


def step_needs(cf: Dict[str, Any], rows: Iterable[Tuple[int, int, int]],
               counters: Dict[str, int]) -> Dict[str, Dict[str, int]]:
    """What one engine step needs, by scope and for the whole step.
    ``rows``: per active request (pos, n, samples), as
    ``opcount.decode_step_needs`` takes them; ``counters`` is not read (every
    count here follows from the rows' shapes).

    - ``ssm_scan`` (the ``a + 1`` state-space layers' conv, ``W_x``,
      ``W_dt``, scan and state): their small weights once; per fed token the
      conv (2 k), ``W_x`` and ``W_dt`` (2 a weight) and the scan (7 a state
      element: the decay's product, the input's outer product and sum, the
      read-out's product and sum; the exponential counts as one); the token's
      ``u`` in and ``y`` out; and a live row's state (float32) read and
      written ONCE a step;
    - ``gmu`` (``b`` layers): ``W_1`` and ``W_2`` once, 2 FLOPs a weight a
      fed token, the memory and the stream in and out;
    - ``window_attention`` (``a`` layers): per row the keys from its first
      query's window start to its last token read ONCE a layer (K and V),
      the step's tokens written, and per causal (query, key) pair inside
      the window the two softmax maps of every head pair (2 hd for a score,
      2 x 2 hd for a head's 2 hd-wide values: 6 hd a head);
    - ``shared_kv_attention`` (the full layer and the ``b`` cross layers):
      the row's whole context read once a LAYER (``b + 1`` times a step;
      that the program gathers it once is the program's business), the
      step's tokens written once, the same products over every causal pair;
    - ``step``: those, every other weight once (the MLPs, the mixers'
      projections, the LayerNorms), the embedding rows looked up, and if a
      row samples the head read once and its float32 logits written."""
    L = cf["num_hidden_layers"]
    kinds = layer_kinds(cf)
    a, b = kinds.count("window"), kinds.count("cross")
    wb = BYTES[cf["precision"]["weights"]]
    ab = BYTES[cf["precision"]["activations"]]
    part = layer_params(cf)
    d, heads = cf["hidden_size"], cf["num_attention_heads"]
    di, n, k, r, hd = (part[x] for x in ("di", "n", "k", "r", "hd"))
    window = cf["sliding_window"]
    kv_token = 2 * cf["num_key_value_heads"] * hd * ab      # a layer's K + V
    pair_flops = 6 * hd * heads                             # a (query, key)

    fed = sampled = live = 0
    win_keys = win_pairs = all_keys = all_pairs = 0
    for pos, m, samples in rows:
        fed += m
        live += 1
        sampled += 1 if samples else 0
        all_keys += pos + m
        all_pairs += sum(p + 1 for p in range(pos, pos + m))
        win_keys += pos + m - max(pos - window + 1, 0)
        win_pairs += sum(min(p + 1, window) for p in range(pos, pos + m))
    state = 4 * (n + k - 1) * di                  # a row's state, a layer
    ssm = {"flops": (a + 1) * fed * (2 * k * di + 2 * di * (r + 2 * n)
                                     + 2 * r * di + 7 * n * di + 2 * di),
           "bytes": (a + 1) * (wb * part["ssm"] + 2 * state * live
                               + 2 * ab * di * fed)}
    gmu = {"flops": b * 2 * part["gmu"] * fed,
           "bytes": b * (wb * part["gmu"] + ab * (di + 2 * d) * fed)}
    win = {"flops": a * pair_flops * win_pairs,
           "bytes": a * kv_token * (win_keys + fed)}
    shared = {"flops": (b + 1) * pair_flops * all_pairs,
              "bytes": kv_token * ((b + 1) * all_keys + fed)}
    other = (L * part["mlp"] + (a + 1) * part["mamba_proj"]
             + (a + 1) * part["attn_own"] + b * part["attn_cross"] + 2 * d)
    head = d * cf["vocab_size"]
    scopes = (ssm, gmu, win, shared)
    step = {"flops": sum(s["flops"] for s in scopes) + 2 * other * fed
            + 2 * head * sampled,
            "bytes": sum(s["bytes"] for s in scopes) + wb * other
            + wb * d * fed + (wb * head if sampled else 0)
            + 4 * cf["vocab_size"] * sampled}
    return {"ssm_scan": ssm, "gmu": gmu, "window_attention": win,
            "shared_kv_attention": shared, "step": step,
            "fed": fed, "sampled": sampled}
