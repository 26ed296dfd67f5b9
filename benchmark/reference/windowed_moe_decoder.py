"""Plain reference of the windowed MoE decoder family (Trinity-Large-Preview,
``model_type`` afmoe): the forward pass in straightforward ``jax.numpy``,
float32 at matmul precision "highest", written from the published
``config.json`` (huggingface.co/arcee-ai/Trinity-Large-Preview) and its
catalog row's ``described_as``, and independent of ``ray_tpu/models`` and
``ray_tpu/ops``: no kernel, no cache, no paged pool, no block table, no
grouped matmul. One sequence, every position at once.

    x0 = embed[token] * sqrt(hidden_size)                        (mup_enabled)
    layer i, kind = layer_types[i], dense if i < the leading dense layers:
      h  = RMSNorm(x; input_layernorm)
      q  = h Wq -> H x hd;  k = h Wk -> KV x hd;  v = h Wv;  g = h Wg -> H hd
      q, k = RMSNorm over each head's hd (q_norm, k_norm: one gain of hd)
      sliding: q, k = RoPE(q), RoPE(k)   (theta, pairs (j, j + hd / 2));
               query p sees keys j with p - sliding_window < j <= p
      full:    no positional encoding; sees every j <= p
      a  = softmax(q k^T / sqrt(hd)) v        (query head n reads KV head
                                               n // (H / KV))
      a  = a * sigmoid(g)                     (elementwise, before Wo)
      x  = x + RMSNorm(a Wo; post_attention_layernorm)
      h2 = RMSNorm(x; pre_mlp_layernorm)
      dense:  m = (silu(h2 Wgate) * (h2 Wup)) Wdown
      expert: s = sigmoid(h2 Wr) in float32, num_experts wide
              sel = top-k of (s + b)          (b: expert_bias, selection only)
              w = s[sel] / (sum s[sel] + 1e-20) * route_scale
              m = SwiGLU_shared(h2) + sum_{e in sel, e HELD} w_e SwiGLU_e(h2)
      x  = x + RMSNorm(m; post_mlp_layernorm)
    logits = RMSNorm(x; norm) W_head

No bias on any projection. **The share**: the router is as wide as the
published model and picks ``num_experts_per_tok`` of all its experts; the
weights hold ``E`` of them from index ``first`` (``reduced.num_experts`` of
the configuration). The pairs whose expert is held are summed, the others
left out, here as in the program: their part is another chip's.

What the config has a flag for and no formula is in the configuration's
``assumed``, each with the wording of ISSUE 46: the embedding's scale, RoPE
in sliding layers only, the gate from the layer's normed input applied before
``Wo``, q/k-norm before RoPE, the router in float32, the half-split pairing,
"SMEBU bias" read as the plain selection bias.

The weights arrive in the program's tree layout (``layers["dense"]`` and
``layers["moe"]``, each leaf stacked over its segment's layers, every matrix
two-dimensional as it is multiplied), which is how the benchmark hands the
same seeded weights to both sides. It runs beside the engine's 11.6 GB, so
little of ``[T, hidden]`` in float32 (0.4 GB at 32k tokens) exists at once:
attention goes one KV group (``H / KV`` query heads) and ``QUERY_BLOCK``
queries at a time, a sliding layer's block against the ``sliding_window +
QUERY_BLOCK`` keys it can see and not against all of them, the dense MLP in
columns of ``MLP_COLUMNS``, position-wise parts in blocks of ``TOKEN_BLOCK``
tokens, experts up-cast to float32 one at a time.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
QUERY_BLOCK = 128
TOKEN_BLOCK = 1024
MLP_COLUMNS = 2048

#: what ``logits_at(weights=...)`` takes beside "as_given": the int8
#: control, and mathematics left out or moved one piece at a time
CONTROLS = ("int8", "no_gate", "no_post_norms", "no_router_bias",
            "window_short_a_block", "rope_in_full", "no_rope",
            "no_embed_scale", "no_scale", "no_shared", "no_held",
            "no_qk_norm")

#: tokens a block holds where a control moves the window's edge by one
WINDOW_BLOCK = 16


def _rms_norm(x, gain, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * gain


def _rope(x, positions, theta):
    """x [T, n, hd] -> rotated; pairs (j, j + hd/2) turn by pos * theta^(-2j/hd)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(0, half, dtype=F32) * 2.0 / x.shape[-1])
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _int8(w, contract_axes):
    """Symmetric int8 with one scale per output channel, and back: the
    weights a weight-only int8 deployment would multiply by."""
    scale = jnp.max(jnp.abs(w), axis=contract_axes, keepdims=True) / 127.0
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


#: matrices, each contracted over its first axis (experts one at a time, so
#: their expert axis is gone). The router stays as given: an int8 deployment
#: keeps it.
_MATRICES = ("wq", "wk", "wv", "wg", "wo", "w_gate", "w_up", "w_down",
             "ws_gate", "ws_up", "ws_down")
_LATE = ("w_gate", "w_up", "w_down")    # up-cast where they are multiplied


def _blocks(x, size):
    return x.reshape(x.shape[0] // size, size, *x.shape[1:])


def _block_of(t, size):
    return size if t % size == 0 else t


def _attention(h, lp, hp, window, rotate):
    """The attention branch over a whole sequence ``h`` [T, d] (not normed),
    before its post-norm: ``(softmax(q k^T) v * sigmoid(g)) Wo``. ``window``
    0: every earlier key; ``rotate``: RoPE on q and k."""
    t, d = h.shape
    eps, hd = hp["rms_norm_eps"], hp["head_dim"]
    heads, kvh = hp["heads"], hp["kv_heads"]
    rep = heads // kvh
    control = hp["control"]
    pos = jnp.arange(t)
    tb = _block_of(t, TOKEN_BLOCK)
    n = lax.map(lambda hb: _rms_norm(hb, lp["attn_norm"], eps),
                _blocks(h, tb)).reshape(t, d)
    qb = _block_of(t, QUERY_BLOCK)
    # a sliding layer's query block sees at most window - 1 keys before its
    # first query: a span of keys, not all of them
    span = t if not window else min(
        t, -(-(window - 1 + qb) // qb) * qb)

    def head_norm(x, gain):
        return x if control == "no_qk_norm" else _rms_norm(x, gain, eps)

    def group(i, acc):
        cols = lambda w, width: lax.dynamic_slice_in_dim(
            w, i * width, width, 1)
        q = head_norm((n @ cols(lp["wq"], rep * hd)).reshape(t, rep, hd),
                      lp["q_norm"])
        k = head_norm((n @ cols(lp["wk"], hd)).reshape(t, 1, hd),
                      lp["k_norm"])
        v = n @ cols(lp["wv"], hd)                                # [T, hd]
        if rotate:
            q, k = _rope(q, pos, hp["rope_theta"]), \
                _rope(k, pos, hp["rope_theta"])
        k = k[:, 0]

        def one_block(args):
            qq, posb = args                       # [Q, rep, hd], [Q]
            at = jnp.clip(posb[-1] + 1 - span, 0, t - span)
            ks = lax.dynamic_slice_in_dim(k, at, span)
            vs = lax.dynamic_slice_in_dim(v, at, span)
            kpos = at + jnp.arange(span)
            s = jnp.einsum("qrd,sd->rqs", qq, ks) * hd ** -0.5
            seen = kpos[None, :] <= posb[:, None]                 # [Q, S]
            if window:
                seen &= kpos[None, :] > posb[:, None] - window
            p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), -1)
            return jnp.einsum("rqs,sd->qrd", p, vs)

        o = lax.map(one_block, (_blocks(q, qb), _blocks(pos, qb))) \
            .reshape(t, rep * hd)
        if control != "no_gate":
            o = o * jax.nn.sigmoid(n @ cols(lp["wg"], rep * hd))
        return acc + o @ lax.dynamic_slice_in_dim(
            lp["wo"], i * rep * hd, rep * hd)

    return lax.fori_loop(0, kvh, group, jnp.zeros((t, d), F32))


def _late(w, int8):
    w = w.astype(F32)
    return _int8(w, (0,)) if int8 else w


def _swiglu_columns(m, lp, names, int8):
    """SwiGLU of ``m`` [Q, d] by columns of the hidden width, the matrices
    up-cast a slab at a time."""
    wg, wu, wd = (lp[n] for n in names)
    f = wg.shape[1]
    cols = MLP_COLUMNS if f % MLP_COLUMNS == 0 else f
    # (a slab's int8 scales are its own output channels' for gate and up;
    # down's run over the whole contraction, so it is rounded whole)
    wd = _late(wd, int8)

    def slab(i, out):
        g = _late(lax.dynamic_slice_in_dim(wg, i * cols, cols, 1), int8)
        u = _late(lax.dynamic_slice_in_dim(wu, i * cols, cols, 1), int8)
        return out + (jax.nn.silu(m @ g) * (m @ u)) \
            @ lax.dynamic_slice_in_dim(wd, i * cols, cols, 0)

    return lax.fori_loop(0, f // cols, slab, jnp.zeros_like(m))


def _experts(m, lp, hp):
    """The expert branch over ``m`` [Q, d]: the router over ALL the published
    experts, a loop over the experts HELD, each over every token and
    weighted by what the router gave it (0 for the tokens that did not
    choose it), and the shared expert."""
    control, int8 = hp["control"], hp["control"] == "int8"
    logits = m @ lp["router"]                                     # float32
    k = hp["experts_per_tok"]
    if hp["score_func"] == "sigmoid":
        score = jax.nn.sigmoid(logits)
        biased = score if control == "no_router_bias" \
            else score + lp["router_bias"]
        _, top_e = lax.top_k(biased, k)
        top_p = jnp.take_along_axis(score, top_e, axis=-1)
        if hp["route_norm"]:
            top_p = top_p / (jnp.sum(top_p, axis=-1, keepdims=True) + 1e-20)
    else:
        top_p, top_e = lax.top_k(jax.nn.softmax(logits, axis=-1), k)
        if hp["route_norm"]:
            top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    if control != "no_scale":
        top_p = top_p * hp["route_scale"]

    def one(e, out):
        wg, wu, wd = (_late(lax.dynamic_index_in_dim(lp[n], e, 0, False),
                            int8) for n in _LATE)
        share = jnp.sum(jnp.where(top_e == hp["experts_first"] + e, top_p,
                                  0.0), axis=-1)
        return out + share[:, None] * (
            (jax.nn.silu(m @ wg) * (m @ wu)) @ wd)

    held = 0 if control == "no_held" else lp["w_gate"].shape[0]
    out = lax.fori_loop(0, held, one, jnp.zeros_like(m))
    if "ws_gate" in lp and control != "no_shared":
        out = out + _swiglu_columns(m, lp, ("ws_gate", "ws_up", "ws_down"),
                                    int8)
    return out


@functools.partial(jax.jit,
                   static_argnames=("hp", "dense", "window", "rotate"),
                   donate_argnums=(0,))
def _layer_at(h, layers, index, hp, dense, window, rotate):
    hp = dict(hp)
    control = hp["control"]
    int8 = control == "int8"
    # the MLP's and the experts' matrices stay in the type they are stored
    # in until their turn
    lp = {k: lax.dynamic_index_in_dim(w, index, 0, False)
          for k, w in layers.items()}
    late = lambda k: k in _LATE or k.startswith("ws_")
    lp = {k: w if late(k) else w.astype(F32) for k, w in lp.items()}
    if int8:
        lp = {k: _int8(w, (0,)) if k in _MATRICES and not late(k) else w
              for k, w in lp.items()}
    eps = hp["rms_norm_eps"]
    post = (lambda x, gain: x) if control == "no_post_norms" \
        else (lambda x, gain: _rms_norm(x, lp[gain], eps))
    with jax.default_matmul_precision("highest"):
        t = h.shape[0]
        tb = _block_of(t, TOKEN_BLOCK)
        a = _attention(h, lp, hp, window, rotate)
        a = h + lax.map(lambda ab: post(ab, "post_attn_norm"),
                        _blocks(a, tb)).reshape(h.shape)

        def mlp(ab):
            m = _rms_norm(ab, lp["mlp_norm"], eps)
            m = _swiglu_columns(m, lp, _LATE, int8) if dense \
                else _experts(m, lp, hp)
            return ab + post(m, "post_mlp_norm")

        return lax.map(mlp, _blocks(a, tb)).reshape(a.shape)


@functools.partial(jax.jit, static_argnames=("scale",))
def _embed(embed, tokens, scale):
    return embed[tokens].astype(F32) * scale


@functools.partial(jax.jit, static_argnames=("eps", "int8"))
def _head(h, rows, final_norm, head, eps, int8):
    head = head.astype(F32)
    if int8:
        head = _int8(head, (0,))
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(h[rows], final_norm.astype(F32), eps)
        return x @ head


def layer_plan(config_file: Dict[str, Any]):
    """``[(segment, index in the segment, window or 0, rotates)]`` of the
    layers that run: the first ``num_hidden_layers`` of ``layer_types``, the
    leading ``dense`` of them dense."""
    cf = config_file
    n = int(cf["num_hidden_layers"])
    cut = cf.get("reduced", {}).get("num_hidden_layers", {})
    dense = int(cut.get("dense_here", cf["num_dense_layers"]))
    kinds = cf["layer_types"][:n]
    if set(kinds) - {"sliding_attention", "full_attention"} or dense > n:
        raise NotImplementedError("a layer this reference does not describe")
    return [("dense" if i < dense else "moe", i if i < dense else i - dense,
             int(cf["sliding_window"]) if kind == "sliding_attention" else 0,
             kind == "sliding_attention") for i, kind in enumerate(kinds)]


def hyper(config_file: Dict[str, Any], control: str = "as_given"):
    """The published numbers the mathematics needs, hashable for jit."""
    cf = config_file
    if cf["n_group"] != 1 or cf["topk_group"] != 1 \
            or cf["num_expert_groups"] != 1 or cf["num_limited_groups"] != 1 \
            or cf["rope_scaling"] is not None or cf["hidden_act"] != "silu" \
            or cf["num_shared_experts"] != 1:
        raise NotImplementedError("a layer this reference does not describe")
    return (("rms_norm_eps", float(cf["rms_norm_eps"])),
            ("rope_theta", float(cf["rope_theta"])),
            ("heads", int(cf["num_attention_heads"])),
            ("kv_heads", int(cf["num_key_value_heads"])),
            ("head_dim", int(cf["head_dim"])),
            ("experts_per_tok", int(cf["num_experts_per_tok"])),
            ("route_norm", bool(cf["route_norm"])),
            ("score_func", str(cf["score_func"])),
            ("route_scale", float(cf["route_scale"])),
            ("experts_first", int(
                cf.get("reduced", {}).get("num_experts", {}).get("first", 0))),
            ("control", control))


def logits_at(params, tokens, rows, config_file: Dict[str, Any],
              weights: str = "as_given"):
    """Float32 logits [len(rows), V] of the sequence ``tokens`` [T] at the
    positions ``rows``. A control takes the honest pass's place
    (``CONTROLS``): ``weights="int8"``, the same mathematics over weights
    rounded to int8 per output channel (the nearest precision below the bf16
    the configuration states); and the weights as given with one piece of
    the mathematics left out or moved: ``no_gate``, ``no_post_norms``,
    ``no_router_bias``, ``window_short_a_block`` (a sliding layer sees
    ``WINDOW_BLOCK`` keys fewer), ``rope_in_full`` (the full layers rotate
    too), ``no_rope`` (no layer does), ``no_embed_scale``, ``no_scale``
    (``route_scale`` dropped), ``no_shared``, ``no_held`` (the held experts'
    sum dropped), ``no_qk_norm``."""
    if weights != "as_given" and weights not in CONTROLS:
        raise ValueError(f"unknown control {weights!r}")
    cf = config_file
    hp = hyper(cf, weights)
    scale = 1.0 if weights == "no_embed_scale" or not cf["mup_enabled"] \
        else float(cf["hidden_size"]) ** 0.5
    h = _embed(params["embed"], jnp.asarray(tokens, jnp.int32), scale)
    for seg, index, window, rotate in layer_plan(cf):
        if weights == "window_short_a_block" and window:
            window -= WINDOW_BLOCK
        rotate = {"rope_in_full": True, "no_rope": False}.get(weights, rotate)
        h = _layer_at(h, params["layers"][seg], index, hp, seg == "dense",
                      window, rotate)
    return _head(h, jnp.asarray(rows, jnp.int32), params["final_norm"],
                 params["lm_head"], float(cf["rms_norm_eps"]),
                 weights == "int8")
