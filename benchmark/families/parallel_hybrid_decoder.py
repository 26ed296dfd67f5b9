"""The parallel attention / Mamba-2 decoder family (Falcon-H1: every layer
runs its attention heads and its Mamba-2 heads side by side on one normed
input) for the ``serve_state_family`` kind: from a configuration file's
published keys to the program's ``TransformerConfig``, its seeded weights,
the toy widths of a rehearsal, the program's scopes, kernels and per-step
counters that the kind times and keeps, and what a step NEEDS (the numerators
of the family's roofline shares). The reference is
``reference/parallel_hybrid_decoder.py``; the family's name is the
configuration's ``reference`` key.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Iterable, Tuple

from benchmark.opcount import BYTES

#: the program's scopes whose device time a traced run reports
#: (``jax.named_scope`` in ``ray_tpu/ops/ssm.py``, ``ops/paged_attention.py``
#: and ``models/parallel_hybrid.py``)
SCOPES = ("ssd_conv", "ssd_scan", "ssd_gated_norm", "mamba2_in_proj",
          "mamba2_out_proj", "paged_attention")

#: operations that reach the compiled program without their scope, by
#: instruction-name prefix -> scope: none (the attention kernel's custom call
#: keeps its ``paged_attention`` scope)
KERNELS: Dict[str, str] = {}

#: engine counters kept per step (their growth over the step)
STEP_COUNTERS = ("ssd_positions_real", "ssd_positions_run",
                 "state_slots_live")

#: toy widths for ``--rehearse-cpu`` and the CPU tests: control flow only
TOY_WIDTHS = {"hidden_size": 64, "intermediate_size": 128,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 16, "num_hidden_layers": 3, "vocab_size": 512,
              "mamba_d_ssm": 32, "mamba_n_heads": 4, "mamba_d_head": 8,
              "mamba_d_state": 8, "mamba_n_groups": 2}

#: the seeded weights' scales that are not "the usual over the multiplier"
#: (see ``build_params``): the embedding's effective scale, how far ``wq``,
#: ``wv`` and the in-projection's ``B`` and ``C`` columns are drawn above the
#: usual
EMBED_STD = 1.0
Q_GAIN = 2.5
V_GAIN = 2.0
BC_GAIN = 3.0


def _described(cf: Dict[str, Any]) -> None:
    if (cf["attn_layer_indices"] is not None or cf["attention_bias"]
            or cf["mamba_proj_bias"] or cf["mlp_bias"]
            or cf["projectors_bias"] or not cf["mamba_conv_bias"]
            or not cf["mamba_rms_norm"] or cf["mamba_norm_before_gate"]
            or not cf["mamba_use_mlp"] or cf["rope_scaling"] is not None
            or cf["tie_word_embeddings"] or cf["hidden_act"] != "silu"
            or cf["mamba_d_ssm"] != cf["mamba_n_heads"] * cf["mamba_d_head"]):
        raise NotImplementedError("a layer pattern this family file does "
                                  "not describe")


def transformer_config(cf: Dict[str, Any], **overrides):
    from ray_tpu.models.config import TransformerConfig

    _described(cf)
    prec = cf["precision"]
    kw = dict(
        vocab_size=cf["vocab_size"], d_model=cf["hidden_size"],
        n_layers=cf["num_hidden_layers"], n_heads=cf["num_attention_heads"],
        n_kv_heads=cf["num_key_value_heads"], head_dim=cf["head_dim"],
        d_ff=cf["intermediate_size"],
        max_seq_len=cf["max_position_embeddings"],
        mlp="swiglu", norm="rms", positions="rope",
        rope_theta=float(cf["rope_theta"]),
        norm_eps=float(cf["rms_norm_eps"]), tie_embeddings=False,
        layer_kinds=("parallel",) * cf["num_hidden_layers"],
        ssm_width=cf["mamba_d_ssm"], ssm_heads=cf["mamba_n_heads"],
        ssm_head_dim=cf["mamba_d_head"], ssm_groups=cf["mamba_n_groups"],
        ssm_state=cf["mamba_d_state"], ssm_conv=cf["mamba_d_conv"],
        ssm_chunk=cf["mamba_chunk_size"],
        embedding_multiplier=cf["embedding_multiplier"],
        lm_head_multiplier=cf["lm_head_multiplier"],
        attention_in_multiplier=cf["attention_in_multiplier"],
        attention_out_multiplier=cf["attention_out_multiplier"],
        key_multiplier=cf["key_multiplier"],
        ssm_in_multiplier=cf["ssm_in_multiplier"],
        ssm_out_multiplier=cf["ssm_out_multiplier"],
        ssm_multipliers=tuple(cf["ssm_multipliers"]),
        mlp_multipliers=tuple(cf["mlp_multipliers"]),
        dtype=prec["activations"], param_dtype=prec["weights"])
    kw.update(overrides)
    return TransformerConfig(**kw)


def build_params(config, key):
    """The parameter tree for ``config`` from a key (traceable), laid out as
    the program has it (``models.parallel_hybrid.block_shapes``: the layout
    is the program's interface, the values are drawn here).

    The published multipliers are tuned to trained weights
    (``attention_out_multiplier`` 0.0375, ``key_multiplier`` 0.011,
    ``lm_head_multiplier`` 1/128): with matrices at the usual seeded scales
    the scores are flat, the attention branch is a thousandth of the
    residual and the logits are of order 0.01. So every matrix that a fixed
    multiplier scales is drawn at THE USUAL SCALE OVER ITS MULTIPLIER
    (``fan_in^-0.5``, output projections over ``sqrt(2 L)``): the product,
    which is what the layer computes, then has the usual scale, and a
    multiplier left at 1 moves its term by 1 / multiplier (3 to 128 times).
    Four scales are set beside that, each so that a branch carries its
    share (read on the chip, PERF.md section 4): the embedding times its
    multiplier at unit scale (``EMBED_STD``: the stream stays token-specific
    over the layers); ``wq`` at ``Q_GAIN`` times the usual (scores of
    standard deviation 2.5: an attention output is an average over the keys
    and shrinks with the square root of their effective number, about two
    at that spread and seven hundred on flat scores) and ``wv`` at
    ``V_GAIN`` (the first chip reading, without it: attention 5.3 a layer
    beside Mamba-2's 20.8 and the MLP's 12.5); the in-projection's
    ``B`` and ``C`` columns at ``BC_GAIN`` (the state's read-out ``S C``
    against the skip ``D x``: with ``D`` about 1 and steps of 1e-3 to 1e-1
    the carried state is otherwise a tenth of ``y``, and a state dropped
    between chunks would hardly show). Gains N(1, 0.1), the conv's bias N(0,
    0.1), ``D`` N(1, 0.1), ``A_log`` the log of a draw uniform in [1, 16]
    and ``dt_bias`` the inverse softplus of a step log-uniform in [1e-3,
    1e-1] (Mamba-2's own starts): all away from their trivial values."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import parallel_hybrid

    c = config
    dt = jnp.dtype(c.param_dtype)
    f32 = jnp.float32
    d, L, v = c.d_model, c.n_layers, c.vocab_size
    if c.tie_embeddings or v % 8:
        raise NotImplementedError("a tied head, or a vocabulary that does "
                                  "not divide by 8")
    gn = c.ssm_groups * c.ssm_state
    mup = c.ssm_mup
    # the in-projection's scale a column: the usual over the slice's
    # multipliers, B and C above it
    col = lambda n, m, gain=1.0: jnp.full(
        (n,), gain / (c.ssm_in_multiplier * m), f32)
    over = {"wq": Q_GAIN / c.attention_in_multiplier,
            "wk": 1.0 / (c.attention_in_multiplier * c.key_multiplier),
            "wv": V_GAIN / c.attention_in_multiplier,
            "wo": 1.0 / c.attention_out_multiplier,
            "w_ssm_z": col(c.d_inner, mup[0]),
            "w_ssm_xbc": jnp.concatenate([
                col(c.d_inner, mup[1]), col(gn, mup[2], BC_GAIN),
                col(gn, mup[3], BC_GAIN)]),
            "w_ssm_dt": col(c.ssm_heads, mup[4]),
            "w_ssm_out": 1.0 / c.ssm_out_multiplier,
            "w_gate": 1.0 / c.mlp_mup[0], "w_down": 1.0 / c.mlp_mup[1]}

    def draw(k, shape, how, leaf):
        if how == "dt_bias":
            lo, hi = math.log(1e-3), math.log(1e-1)
            step = jnp.exp(jax.random.uniform(k, shape, f32) * (hi - lo) + lo)
            x = step + jnp.log(-jnp.expm1(-step))
        elif how == "A_log":
            x = jnp.log(jax.random.uniform(k, shape, f32, 1.0, 16.0))
        else:
            x = jax.random.normal(k, shape, f32)
            if how == "gain":
                x = 1.0 + 0.1 * x
            elif how == "bias":
                x = 0.1 * x
            else:
                kind, fan_in = how
                x = x * (fan_in ** -0.5 / ((2 * L) ** 0.5
                                           if kind == "out" else 1.0))
                x = x * over.get(leaf, 1.0)
        return x.astype(dt)

    shapes = parallel_hybrid.block_shapes(c)
    k_embed, k_head, k_norm, k_layers = jax.random.split(key, 4)
    keys = jax.random.split(k_layers, len(shapes))
    # one layer at a time: the float32 draw of a stacked leaf never exists
    layers = {
        leaf: jax.lax.map(
            lambda k, shape=shape, how=how, leaf=leaf: draw(k, shape, how,
                                                            leaf),
            jax.random.split(k0, L))
        for k0, (leaf, (shape, _, how)) in zip(keys, shapes.items())}
    # and the two vocabulary-sized matrices an eighth at a time
    rows = jax.lax.map(
        lambda k: (jax.random.normal(k, (v // 8, d), f32)
                   * (EMBED_STD / c.embedding_multiplier)).astype(dt),
        jax.random.split(k_embed, 8))
    cols = jax.lax.map(
        lambda k: (jax.random.normal(k, (d, v // 8), f32)
                   * (d ** -0.5 / c.lm_head_multiplier)).astype(dt),
        jax.random.split(k_head, 8))
    return {"embed": rows.reshape(v, d),
            "layers": layers,
            "final_norm": draw(k_norm, (d,), "gain", "final_norm"),
            "lm_head": jnp.moveaxis(cols, 0, 1).reshape(d, v)}


# -- what a step needs -------------------------------------------------------

def layer_params(cf: Dict[str, Any]) -> Dict[str, int]:
    """Weights of one layer by part, and the sizes the counts below share."""
    d, f = cf["hidden_size"], cf["intermediate_size"]
    q = cf["num_attention_heads"] * cf["head_dim"]
    kv = cf["num_key_value_heads"] * cf["head_dim"]
    ds, h = cf["mamba_d_ssm"], cf["mamba_n_heads"]
    gn = cf["mamba_n_groups"] * cf["mamba_d_state"]
    cw, k = ds + 2 * gn, cf["mamba_d_conv"]
    return {
        "mlp": 3 * d * f + d,                       # and its RMSNorm
        "attn": d * q + 2 * d * kv + q * d,
        "input_norm": d,
        "mamba_proj": d * (2 * ds + 2 * gn + h) + ds * d,
        "ssm": k * cw + cw + 3 * h + ds,    # conv, dt_bias, A_log, D, gain
        "ds": ds, "h": h, "p": cf["mamba_d_head"], "g": cf["mamba_n_groups"],
        "n": cf["mamba_d_state"], "cw": cw, "k": k,
    }


def state_bytes(cf: Dict[str, Any]) -> int:
    """One request's float32 state in one layer: the scan's and the conv's."""
    part = layer_params(cf)
    return 4 * (part["h"] * part["p"] * part["n"]
                + (part["k"] - 1) * part["cw"])


def step_needs(cf: Dict[str, Any], rows: Iterable[Tuple[int, int, int]],
               counters: Dict[str, int]) -> Dict[str, Dict[str, int]]:
    """What one engine step needs, by scope and for the whole step.
    ``rows``: per active request (pos, n, samples), as
    ``opcount.decode_step_needs`` takes them; ``counters`` is not read (every
    count here follows from the rows' shapes). The needs are the WORK's,
    whatever implements it: a kernel that takes the scan's place reads
    against the same count.

    - ``ssd_scan`` (the program's scopes ``ssd_conv``, ``ssd_scan`` and
      ``ssd_gated_norm`` together): the conv's, the scan's and the gated
      norm's small weights once a layer; a live row's float32 state (scan
      and conv) read and written ONCE a layer; per fed position the conv (2
      k a channel), the step and the decay (softplus, product, exponential:
      6 a head), the skip (2 a channel) and the gated norm (8 a channel),
      its ``x | B | C``, ``dt`` and ``z`` in and ``y`` and the normed value
      out; for a row that feeds ONE position the turn of the recurrence (4 P
      N a head: decay and outer product, read-out); for a row that feeds a
      block of T positions the block products (``C B^T`` 2 T^2 N a group,
      its product with ``x`` 2 T^2 P a head, the state's read-out and
      update 2 x 2 T P N a head);
    - ``mamba2_projections`` (``mamba2_in_proj`` and ``mamba2_out_proj``):
      their weights once a layer, 2 FLOPs a weight a fed position, the
      positions' activations in and out;
    - ``paged_attention``: a row's live K and V read once a layer, the
      queries in and the output out, and 4 hd a query head a causal
      (query, key) pair;
    - ``step``: those, every other weight once (the attention's
      projections, the MLPs, the RMSNorms), the step's K and V written, the
      embedding rows looked up, and if a row samples the head read once and
      its float32 logits written."""
    L = cf["num_hidden_layers"]
    wb = BYTES[cf["precision"]["weights"]]
    ab = BYTES[cf["precision"]["activations"]]
    part = layer_params(cf)
    d, heads, hd = (cf["hidden_size"], cf["num_attention_heads"],
                    cf["head_dim"])
    ds, h, p, g, n, cw, k = (part[x] for x in
                             ("ds", "h", "p", "g", "n", "cw", "k"))
    kv_token = 2 * cf["num_key_value_heads"] * hd * ab      # a layer's K + V

    fed = sampled = live = 0
    keys = pairs = scan_flops = 0
    for pos, m, samples in rows:
        fed += m
        live += 1
        sampled += 1 if samples else 0
        keys += pos + m
        pairs += sum(q + 1 for q in range(pos, pos + m))
        if m == 1:
            scan_flops += 4 * p * n * h
        else:
            scan_flops += (2 * m * m * n * g + 2 * m * m * p * h
                           + 4 * m * p * n * h)
    ssd = {"flops": L * (scan_flops
                         + fed * (2 * k * cw + 6 * h + 2 * ds + 8 * ds)),
           "bytes": L * (wb * part["ssm"] + 2 * state_bytes(cf) * live
                         + ab * fed * (cw + h + 3 * ds))}
    proj = {"flops": L * 2 * part["mamba_proj"] * fed,
            "bytes": L * (wb * part["mamba_proj"]
                          + ab * fed * (2 * d + 2 * ds + cw + h))}
    attn = {"flops": L * 4 * hd * heads * pairs,
            "bytes": L * (kv_token * keys + 2 * ab * heads * hd * fed)}
    other = L * (part["attn"] + part["mlp"] + part["input_norm"]) + d
    head = d * cf["vocab_size"]
    scopes = (ssd, proj, attn)
    step = {"flops": sum(s["flops"] for s in scopes) + 2 * other * fed
            + 2 * head * sampled,
            "bytes": sum(s["bytes"] for s in scopes) + wb * other
            + L * kv_token * fed + wb * d * fed
            + (wb * head if sampled else 0)
            + 4 * cf["vocab_size"] * sampled}
    return {"ssd_scan": ssd, "mamba2_projections": proj,
            "paged_attention": attn, "step": step,
            "fed": fed, "sampled": sampled}
