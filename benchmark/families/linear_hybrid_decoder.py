"""The linear hybrid decoder family (Olmo-Hybrid: periods of gated delta-rule
layers closed by a full-attention layer) for the ``serve_snapshot_family``
kind: from a configuration file's published keys to the program's
``TransformerConfig``, its seeded weights, the toy widths of a rehearsal, the
program's scopes, kernels and per-step counters that the kind times and
keeps, and what a step NEEDS (the numerators of the family's roofline
shares). The reference is ``reference/linear_hybrid_decoder.py``; the
family's name is the configuration's ``reference`` key.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, Tuple

from benchmark.opcount import BYTES

#: the program's scopes whose device time a traced run reports
#: (``jax.named_scope`` in ``ray_tpu/ops/delta_rule.py``,
#: ``ops/paged_attention.py`` and ``models/linear_hybrid.py``)
SCOPES = ("delta_proj", "delta_rule", "delta_out", "paged_attention")

#: operations that reach the compiled program without their scope: none (the
#: attention kernel's custom call keeps its ``paged_attention`` scope)
KERNELS: Dict[str, str] = {}

#: engine counters kept per step (their growth over the step)
STEP_COUNTERS = ("delta_positions_real", "delta_positions_run",
                 "delta_rows_stepped", "delta_rows_blocked",
                 "state_slots_live")

#: toy widths for ``--rehearse-cpu`` and the CPU tests: control flow only
#: (six MHA heads: three 32-bit pairs, which the pool pads as it pads the
#: published 30; two delta heads fill a 128-lane row as the published do)
TOY_WIDTHS = {"hidden_size": 96, "intermediate_size": 128,
              "num_attention_heads": 6, "num_key_value_heads": 6,
              "num_hidden_layers": 8, "vocab_size": 512,
              "linear_num_key_heads": 4, "linear_num_value_heads": 4,
              "linear_key_head_dim": 8, "linear_value_head_dim": 64}

#: the seeded weights' scales that are not the usual (see ``build_params``)
EMBED_STD = 1.0
Q_NORM_GAIN = 2.5
DECAY_PROJ_GAIN = 0.05


def _described(cf: Dict[str, Any]) -> None:
    if (cf["attention_bias"] or cf["tie_word_embeddings"]
            or cf["hidden_act"] != "silu"
            or cf["linear_num_key_heads"] != cf["linear_num_value_heads"]
            or cf["rope_parameters"]["rope_theta"] is not None
            or cf["hidden_size"] % cf["num_attention_heads"]):
        raise NotImplementedError("a layer pattern this family file does "
                                  "not describe")


def layer_kinds(cf: Dict[str, Any]) -> Tuple[str, ...]:
    """The program's kinds of the layers held here: the first
    ``num_hidden_layers`` of the published ``layer_types``."""
    names = {"linear_attention": "delta", "full_attention": "full"}
    return tuple(names[t]
                 for t in cf["layer_types"][:cf["num_hidden_layers"]])


def transformer_config(cf: Dict[str, Any], **overrides):
    from ray_tpu.models.config import TransformerConfig

    _described(cf)
    prec = cf["precision"]
    kw = dict(
        vocab_size=cf["vocab_size"], d_model=cf["hidden_size"],
        n_layers=cf["num_hidden_layers"], n_heads=cf["num_attention_heads"],
        n_kv_heads=cf["num_key_value_heads"],
        head_dim=cf["hidden_size"] // cf["num_attention_heads"],
        d_ff=cf["intermediate_size"],
        max_seq_len=cf["max_position_embeddings"],
        mlp="swiglu", norm="rms", positions="none",
        norm_eps=float(cf["rms_norm_eps"]), tie_embeddings=False,
        layer_kinds=layer_kinds(cf),
        delta_key_heads=cf["linear_num_key_heads"],
        delta_key_dim=cf["linear_key_head_dim"],
        delta_value_dim=cf["linear_value_head_dim"],
        delta_conv=cf["linear_conv_kernel_dim"],
        delta_neg_eigval=bool(cf["linear_allow_neg_eigval"]),
        dtype=prec["activations"], param_dtype=prec["weights"])
    kw.update(overrides)
    return TransformerConfig(**kw)


def build_params(config, key):
    """The parameter tree for ``config`` from a key (traceable), laid out as
    the program has it (``models.linear_hybrid.block_shapes`` and
    ``segments``: the layout is the program's interface, the values are drawn
    here).

    Both kinds of layer norm a branch's OUTPUT (gain about 1), so every
    branch adds about one unit of RMS to the residual whatever its matrices'
    scales: no branch needs a scale of its own to be seen, and the stream's
    RMS grows like the square root of the branches behind it (1 to 5 over 12
    layers). What the scales decide is what happens INSIDE a mixer, and three
    are set away from the usual (matrices N(0, fan_in^-0.5), output
    projections over sqrt(2 L), gains N(1, 0.1), the conv's taps N(0,
    taps^-0.5)): the embedding at unit scale (``EMBED_STD``: the first layer's
    projections then read a stream of the size every later layer reads); the
    full layers' ``q_norm`` gain about ``Q_NORM_GAIN`` (q and k are normed
    over the whole projection, so a score is a sum of 128 products of
    unit-variance values over sqrt(128): standard deviation 1, nearly flat
    over 1,500 keys; at 2.5 a query reads a few dozen keys and a wrong key
    shows); the columns of ``w_ab`` behind the decay at ``DECAY_PROJ_GAIN``
    of the usual (the stream's RMS of 1-5 would otherwise swing ``softplus(x
    W_a + dt_bias)`` over several units and most heads would forget within
    two tokens: with it ``alpha`` stays near ``exp(-exp(A_log) dt)``, ``A``
    about 1 (``A_log`` N(0, 0.1)) and ``dt`` log-uniform in [1e-3, 0.7]:
    0.5-0.999, a state that carries from two to a thousand tokens). The
    columns behind ``beta`` keep the usual scale, so ``2 sigmoid(x W_b)``
    spreads over (0, 2) and passes 1 for about half the (token, head) pairs:
    negative eigenvalues occur. What was read on the chip: PERF.md section
    4."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import linear_hybrid

    c = config
    dt = jnp.dtype(c.param_dtype)
    f32 = jnp.float32
    d, L, v = c.d_model, c.n_layers, c.vocab_size
    if v % 8:
        raise NotImplementedError("a vocabulary that does not divide by 8")
    h = c.delta_key_heads
    over = {"w_ab": jnp.concatenate([jnp.full((h,), DECAY_PROJ_GAIN, f32),
                                     jnp.ones((h,), f32)])}
    means = {"q_norm": Q_NORM_GAIN}

    def draw(k, shape, how, leaf):
        if how in linear_hybrid.DRAWS:
            x = linear_hybrid.DRAWS[how](k, shape, c)
        else:
            x = jax.random.normal(k, shape, f32)
            if how == "gain":
                x = means.get(leaf, 1.0) * (1.0 + 0.1 * x)
            else:
                kind, fan_in = how
                x = x * (fan_in ** -0.5 / ((2 * L) ** 0.5
                                           if kind == "out" else 1.0))
                x = x * over.get(leaf, 1.0)
        return x.astype(dt)

    shapes = linear_hybrid.block_shapes(c)
    (segment, periods, blocks), = linear_hybrid.segments(c)
    k_embed, k_head, k_norm, k_layers = jax.random.split(key, 4)
    # one period at a time: the float32 draw of a stacked leaf never exists
    layers = {}
    for bi, (name, kind) in enumerate(blocks.items()):
        keys = jax.random.split(jax.random.fold_in(k_layers, bi),
                                len(shapes[kind]))
        layers[name] = {
            leaf: jax.lax.map(
                lambda k, shape=shape, how=how, leaf=leaf: draw(
                    k, shape, how, leaf), jax.random.split(k0, periods))
            for k0, (leaf, (shape, _, how)) in zip(keys,
                                                   shapes[kind].items())}
    # and the two vocabulary-sized matrices an eighth at a time
    rows = jax.lax.map(
        lambda k: (jax.random.normal(k, (v // 8, d), f32)
                   * EMBED_STD).astype(dt), jax.random.split(k_embed, 8))
    cols = jax.lax.map(
        lambda k: (jax.random.normal(k, (d, v // 8), f32)
                   * d ** -0.5).astype(dt), jax.random.split(k_head, 8))
    return {"embed": rows.reshape(v, d),
            "layers": {segment: layers},
            "final_norm": draw(k_norm, (d,), "gain", "final_norm"),
            "lm_head": jnp.moveaxis(cols, 0, 1).reshape(d, v)}


def slot_state(cache, slot: int, config):
    """What the engine's cache holds of the request in ``slot``, in the
    reference's layout (``reference/linear_hybrid_decoder.py::state_at``):
    ``(states [layers, H, dk, dv], conv inputs [layers, taps - 1, 2 H dk +
    H dv])``."""
    from ray_tpu.ops.delta_rule import heads_per_row, to_heads

    r = heads_per_row(config.delta_key_heads, config.delta_value_dim)
    conv = cache["conv"][:, slot]
    return (to_heads(cache["delta"][:, slot], r),
            conv.reshape(conv.shape[0], config.delta_conv - 1, -1))


# -- what a step needs -------------------------------------------------------

def layer_params(cf: Dict[str, Any]) -> Dict[str, int]:
    """Weights of one layer by part, and the sizes the counts below share."""
    d, f = cf["hidden_size"], cf["intermediate_size"]
    hd = d // cf["num_attention_heads"]
    q, kv = cf["num_attention_heads"] * hd, cf["num_key_value_heads"] * hd
    h, dk, dv = (cf["linear_num_value_heads"], cf["linear_key_head_dim"],
                 cf["linear_value_head_dim"])
    kw, vw, taps = h * dk, h * dv, cf["linear_conv_kernel_dim"]
    cw = 2 * kw + vw
    kinds = layer_kinds(cf)
    return {
        "mlp": 3 * d * f + d,                       # and its RMSNorm
        "mixer_norm": d,
        "attn": 2 * d * q + 2 * d * kv + q + kv,    # and its q/k norms
        # the six projections, the conv, the head norm
        "delta_proj": d * (cw + vw + 2 * h) + vw * d + taps * cw + dv,
        "delta_rule": 2 * h,                        # A_log, dt_bias
        "n_delta": kinds.count("delta"), "n_full": kinds.count("full"),
        "h": h, "dk": dk, "dv": dv, "cw": cw, "vw": vw, "taps": taps,
        "hd": hd, "heads": cf["num_attention_heads"], "kv": kv,
    }


def state_bytes(cf: Dict[str, Any]) -> Dict[str, int]:
    """One request's float32 state in one delta layer: the rule's matrices
    and the conv's last inputs."""
    p = layer_params(cf)
    return {"delta": 4 * p["h"] * p["dk"] * p["dv"],
            "conv": 4 * (p["taps"] - 1) * p["cw"]}


def step_needs(cf: Dict[str, Any], rows: Iterable[Tuple[int, int, int]],
               counters: Dict[str, int]) -> Dict[str, Dict[str, int]]:
    """What one engine step needs, by scope and for the whole step.
    ``rows``: per active request (pos, n, samples), as
    ``opcount.decode_step_needs`` takes them; ``counters`` is not read (every
    count here follows from the rows' shapes). The needs are the WORK's,
    whatever implements it: a kernel that takes the rule's place reads
    against the same count.

    - ``delta_rule`` (the program's scope of that name, both forms): a live
      row's float32 matrix state read and written ONCE a delta layer; per
      fed position ``q``, ``k``, ``v`` in (float32) and ``o`` out, the gates
      (softplus, exponential, sigmoid: 12 a head); for a row that feeds ONE
      position the turn (``S^T k``, ``S^T q``, the decay and the rank-one
      update: 8 dk dv a head); for a row that feeds a block of T positions
      the block form's products a head (``K K^T`` and ``Q K^T`` 2 x 2 T^2 dk,
      the carried state's two read-outs 2 x 2 T dk dv, the triangular solve
      T^2 dv, ``(Q K^T) U`` 2 T^2 dv, the state handed on 2 T dk dv + dk dv);
    - ``delta_projections`` (``delta_proj`` and ``delta_out``): the six
      projections', the conv's and the head norm's weights once a delta
      layer, 2 FLOPs a projection weight a fed position, the conv (2 taps a
      channel) and the head norm with its gate (10 a value channel) a
      position, a live row's conv inputs read and written once, the
      positions' activations in and out;
    - ``paged_attention``: a row's live K and V read once a full layer, the
      queries in and the output out, and 4 hd a query head a causal (query,
      key) pair;
    - ``step``: those, every other weight once (the full layers'
      projections, the MLPs, the norms), the step's K and V written, the
      embedding rows looked up, and if a row samples the head read once and
      its float32 logits written."""
    wb = BYTES[cf["precision"]["weights"]]
    ab = BYTES[cf["precision"]["activations"]]
    p = layer_params(cf)
    st = state_bytes(cf)
    d, heads, hd = cf["hidden_size"], p["heads"], p["hd"]
    h, dk, dv, cw, vw, taps = (p[x] for x in
                               ("h", "dk", "dv", "cw", "vw", "taps"))
    nd, nf = p["n_delta"], p["n_full"]
    kv_token = 2 * p["kv"] * ab                      # a layer's K + V

    fed = sampled = live = 0
    keys = pairs = rule_flops = 0
    for pos, m, samples in rows:
        fed += m
        live += 1
        sampled += 1 if samples else 0
        keys += pos + m
        pairs += sum(q + 1 for q in range(pos, pos + m))
        if m == 1:
            rule_flops += h * 8 * dk * dv
        else:
            rule_flops += h * (4 * m * m * dk + 4 * m * dk * dv
                               + 3 * m * m * dv + 2 * m * dk * dv + dk * dv)
    rule = {"flops": nd * (rule_flops + fed * 12 * h),
            "bytes": nd * (wb * p["delta_rule"] + 2 * st["delta"] * live
                           + 4 * fed * (cw + 2 * h) + ab * fed * vw)}
    proj_weights = p["delta_proj"] - taps * cw - dv
    proj = {"flops": nd * fed * (2 * proj_weights + 2 * taps * cw
                                 + 10 * vw),
            "bytes": nd * (wb * p["delta_proj"] + 2 * st["conv"] * live
                           + ab * fed * (2 * d + cw + 3 * vw) + 4 * fed * cw
                           + 4 * fed * 2 * h)}
    attn = {"flops": nf * 4 * hd * heads * pairs,
            "bytes": nf * (kv_token * keys + 2 * ab * heads * hd * fed)}
    other = (nf * p["attn"] + (nd + nf) * (p["mlp"] + p["mixer_norm"]) + d)
    head = d * cf["vocab_size"]
    scopes = (rule, proj, attn)
    step = {"flops": sum(s["flops"] for s in scopes) + 2 * other * fed
            + 2 * head * sampled,
            "bytes": sum(s["bytes"] for s in scopes) + wb * other
            + nf * kv_token * fed + wb * d * fed
            + (wb * head if sampled else 0)
            + 4 * cf["vocab_size"] * sampled}
    return {"delta_rule": rule, "delta_projections": proj,
            "paged_attention": attn, "step": step,
            "fed": fed, "sampled": sampled}
