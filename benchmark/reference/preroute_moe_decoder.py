"""Plain reference of the pre-routed MoE decoder family
(SmallThinker-21BA3B-Instruct): the forward pass in straightforward
``jax.numpy``, float32 at matmul precision "highest", written from the
published ``config.json``
(huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct) and its catalog
row's ``described_as``, and independent of ``ray_tpu/models``, of
``ray_tpu/ops`` and of every other reference here: no kernel, no cache, no
paged pool, no block table, no grouped matmul, no batching. One sequence,
every position at once.

    x = embed[token]
    layer l, window w_l = sliding_window_size if sliding_window_layout[l]
                          else 0 (full), r_l = rope_layout[l]:
      h   = RMSNorm(x; input_layernorm)
      z   = h W_r                      float32, moe_num_primary_experts wide:
                                       the router reads h, AHEAD of attention
      S   = top-k of softmax(z);  p = softmax(z)[S] / sum softmax(z)[S]
                                       (moe_primary_router_apply_softmax,
                                       norm_topk_prob: = softmax over z[S])
      q, k, v = h Wq -> H x hd, h Wk -> KV x hd, h Wv     (no bias, no head
                                       norm)
      q, k = RoPE(q), RoPE(k)  if r_l  (theta, pairs (j, j + hd / 2))
      a   = softmax(q k^T / sqrt(hd)) v   query p sees keys j <= p, and
                                       j > p - w_l where w_l (query head n
                                       reads KV head n // (H / KV))
      x   = x + a Wo
      u   = RMSNorm(x; post_attention_layernorm)
      y   = sum_{e in S} p_e (relu(u Wg_e) * (u Wu_e)) Wd_e
      x   = x + y
    logits = RMSNorm(x; norm) W_head

**Departures from the published description**, each in the configuration's
``assumed``: ``described_as``'s "secondary experts" have no key in ``config``
and are left out; the router's input is ``input_layernorm``'s output ("router
placed before attention"); the gate's activation is ReLU ("sparse ReGLU"); the
window counts the query's own position; rotate-half pairing.

The weights arrive in the program's tree layout (``layers["moe"]``: every leaf
stacked over the layers, every matrix two-dimensional as it is multiplied,
the experts ``[L, E, D, F]`` / ``[L, E, F, D]``), which is how the benchmark
hands the same seeded weights to both sides. It runs beside the engine (8 GB
of weights and 3 GB of pools on a 16 GB chip), so little of ``[T, hidden]``
in float32 (168 MB at 16,384 tokens) exists at once: attention goes one KV
head's query heads and ``QUERY_BLOCK`` queries at a time, a sliding layer's
block against the keys it can see and not all of them; the position-wise
parts go ``TOKEN_BLOCK`` tokens at a time; an expert is up-cast to float32
when its turn comes and multiplies EVERY token of the block, weighted by what
the router gave it there (zero where it was not chosen).
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

#: what ``logits_at(weights=...)`` takes beside "as_given": the int8 control,
#: and the mathematics moved one piece at a time (each a fault the program
#: could have: the check on the chip must refuse every one)
CONTROLS = ("int8", "route_post_attention", "silu", "rope_in_full", "no_rope",
            "window_short_a_block", "no_norm_topk")

#: tokens a block holds where a control moves the window's edge by one
WINDOW_BLOCK = 16

_ATTENTION = ("wq", "wk", "wv", "wo")
_EXPERTS = ("w_gate", "w_up", "w_down")


def _rms_norm(x, gain, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _rotate(x, positions, theta):
    """x [T, n, hd]: pairs (j, j + hd / 2) turned by pos * theta^(-2 j / hd)."""
    half = x.shape[-1] // 2
    freq = theta ** (-2.0 * jnp.arange(half, dtype=F32) / x.shape[-1])
    angle = positions.astype(F32)[:, None, None] * freq
    lo, hi = x[..., :half], x[..., half:]
    return jnp.concatenate([lo * jnp.cos(angle) - hi * jnp.sin(angle),
                            hi * jnp.cos(angle) + lo * jnp.sin(angle)], -1)


def _int8(w):
    """Symmetric int8 with one scale per output channel (the matrix is
    contracted over its first axis), and back."""
    scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def _in_blocks(fn, x, size):
    """``fn`` over ``x [T, ..]`` ``size`` rows at a time (all at once where
    ``size`` does not divide ``T``)."""
    t = x.shape[0]
    if t % size:
        return fn(x)
    out = lax.map(fn, x.reshape(t // size, size, *x.shape[1:]))
    return out.reshape(t, *out.shape[2:])


def _route(h, router, k, norm_topk):
    """(weights [T, k] float32, experts [T, k]) of the normed tensor ``h``."""
    probs = jax.nn.softmax(h @ router, axis=-1)
    top_p, top_e = lax.top_k(probs, k)
    if norm_topk:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return top_p, top_e


def _attention(h, lp, hp, window, rotate):
    """``(softmax(q k^T / sqrt(hd)) v) Wo`` over the normed sequence ``h``
    [T, d]; ``window`` 0: every earlier key."""
    t = h.shape[0]
    hd, heads, kvh = hp["head_dim"], hp["heads"], hp["kv_heads"]
    rep = heads // kvh
    pos = jnp.arange(t)
    qb = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    # the keys one block of queries can see: all, or a window before its
    # first query and the block itself
    span = t if not window else min(t, -(-(window - 1 + qb) // qb) * qb)

    def kv_head(g, out):
        q = (h @ lax.dynamic_slice_in_dim(lp["wq"], g * rep * hd, rep * hd, 1)
             ).reshape(t, rep, hd)
        k = (h @ lax.dynamic_slice_in_dim(lp["wk"], g * hd, hd, 1)
             ).reshape(t, 1, hd)
        v = h @ lax.dynamic_slice_in_dim(lp["wv"], g * hd, hd, 1)
        if rotate:
            q, k = (_rotate(a, pos, hp["rope_theta"]) for a in (q, k))
        k = k[:, 0]

        def block(args):
            qq, qpos = args                           # [Q, rep, hd], [Q]
            first = jnp.clip(qpos[-1] + 1 - span, 0, t - span)
            kk = lax.dynamic_slice_in_dim(k, first, span)
            vv = lax.dynamic_slice_in_dim(v, first, span)
            kpos = first + jnp.arange(span)
            seen = kpos[None, :] <= qpos[:, None]
            if window:
                seen &= kpos[None, :] > qpos[:, None] - window
            s = jnp.einsum("qrd,sd->rqs", qq, kk) * hd ** -0.5
            p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
            return jnp.einsum("rqs,sd->qrd", p, vv)

        o = lax.map(block, (q.reshape(t // qb, qb, rep, hd),
                            pos.reshape(t // qb, qb))).reshape(t, rep * hd)
        return out + o @ lax.dynamic_slice_in_dim(
            lp["wo"], g * rep * hd, rep * hd, 0)

    return lax.fori_loop(0, kvh, kv_head, jnp.zeros_like(h))


def _experts(u, top_p, top_e, lp, hp):
    """``sum_{e in S} p_e (act(u Wg_e) * (u Wu_e)) Wd_e`` over ``u`` [Q, d]:
    a loop over ALL the experts, each over every token of the block."""
    control = hp["control"]
    act = jax.nn.silu if control == "silu" else jax.nn.relu

    def one(e, out):
        wg, wu, wd = (lax.dynamic_index_in_dim(lp[n], e, 0, False).astype(F32)
                      for n in _EXPERTS)
        if control == "int8":
            wg, wu, wd = _int8(wg), _int8(wu), _int8(wd)
        share = jnp.sum(jnp.where(top_e == e, top_p, 0.0), axis=-1)
        return out + share[:, None] * ((act(u @ wg) * (u @ wu)) @ wd)

    return lax.fori_loop(0, lp["w_gate"].shape[0], one, jnp.zeros_like(u))


@functools.partial(jax.jit, static_argnames=("hp", "window", "rotate"),
                   donate_argnums=(0,))
def _layer_at(x, layers, index, hp, window, rotate):
    hp = dict(hp)
    control = hp["control"]
    lp = {name: lax.dynamic_index_in_dim(w, index, 0, False)
          for name, w in layers.items()}
    # the experts stay in the type they are stored in until their turn
    lp = {name: w if name in _EXPERTS else w.astype(F32)
          for name, w in lp.items()}
    if control == "int8":
        # (the router stays as given: an int8 deployment keeps it)
        lp = {name: _int8(w) if name in _ATTENTION else w
              for name, w in lp.items()}
    eps, k = hp["rms_norm_eps"], hp["experts_per_tok"]
    norm_topk = hp["norm_topk"] and control != "no_norm_topk"
    with jax.default_matmul_precision("highest"):
        h = _in_blocks(lambda a: _rms_norm(a, lp["attn_norm"], eps), x,
                       TOKEN_BLOCK)
        # the route, from the layer's normed INPUT
        top_p, top_e = _route(h, lp["router"], k, norm_topk)
        x = x + _attention(h, lp, hp, window, rotate)

        def mlp(args):
            xb, pb, eb = args
            u = _rms_norm(xb, lp["mlp_norm"], eps)
            if control == "route_post_attention":
                pb, eb = _route(u, lp["router"], k, norm_topk)
            return xb + _experts(u, pb, eb, lp, hp)

        t = x.shape[0]
        tb = TOKEN_BLOCK if t % TOKEN_BLOCK == 0 else t
        cut = lambda a: a.reshape(t // tb, tb, *a.shape[1:])
        return lax.map(mlp, (cut(x), cut(top_p), cut(top_e))
                       ).reshape(x.shape)


@jax.jit
def _embed(embed, tokens):
    return embed[tokens].astype(F32)


@functools.partial(jax.jit, static_argnames=("eps", "int8"))
def _head(x, rows, final_norm, head, eps, int8):
    head = head.astype(F32)
    if int8:
        head = _int8(head)
    with jax.default_matmul_precision("highest"):
        return _rms_norm(x[rows], final_norm.astype(F32), eps) @ head


def layer_plan(config_file: Dict[str, Any]):
    """``[(window or 0, rotates)]`` of the layers that run: the first
    ``num_hidden_layers`` of the two published layout lists."""
    cf = config_file
    n = int(cf["num_hidden_layers"])
    sliding, rope = cf["sliding_window_layout"][:n], cf["rope_layout"][:n]
    if len(sliding) != n or len(rope) != n:
        raise NotImplementedError("layout lists shorter than the layers")
    return [(int(cf["sliding_window_size"]) if s else 0, bool(r))
            for s, r in zip(sliding, rope)]


def hyper(config_file: Dict[str, Any], control: str = "as_given"):
    """The published numbers the mathematics needs, hashable for jit."""
    cf = config_file
    if cf["rope_scaling"] is not None or cf["tie_word_embeddings"] \
            or not cf["moe_primary_router_apply_softmax"]:
        raise NotImplementedError("a layer this reference does not describe")
    return (("rms_norm_eps", float(cf["rms_norm_eps"])),
            ("rope_theta", float(cf["rope_theta"])),
            ("heads", int(cf["num_attention_heads"])),
            ("kv_heads", int(cf["num_key_value_heads"])),
            ("head_dim", int(cf["head_dim"])),
            ("experts_per_tok", int(cf["moe_num_active_primary_experts"])),
            ("norm_topk", bool(cf["norm_topk_prob"])),
            ("control", control))


def logits_at(params, tokens, rows, config_file: Dict[str, Any],
              weights: str = "as_given"):
    """Float32 logits [len(rows), V] of the sequence ``tokens`` [T] at the
    positions ``rows``. A control takes the honest pass's place
    (``CONTROLS``): ``weights="int8"``, the same mathematics over weights
    rounded to int8 per output channel (the nearest precision below the bf16
    the configuration states); and the weights as given with one piece of the
    mathematics moved: ``route_post_attention`` (the router reads
    ``post_attention_layernorm``'s output, where every other expert model's
    does), ``silu`` (SiLU in ReLU's place), ``rope_in_full`` (the full
    layers rotate too), ``no_rope`` (no layer does), ``window_short_a_block``
    (a sliding layer sees ``WINDOW_BLOCK`` keys fewer), ``no_norm_topk``
    (the chosen weights not renormalised)."""
    if weights != "as_given" and weights not in CONTROLS:
        raise ValueError(f"unknown control {weights!r}")
    cf = config_file
    hp = hyper(cf, weights)
    x = _embed(params["embed"], jnp.asarray(tokens, jnp.int32))
    for index, (window, rotate) in enumerate(layer_plan(cf)):
        if weights == "window_short_a_block" and window:
            window -= WINDOW_BLOCK
        rotate = {"rope_in_full": True, "no_rope": False}.get(weights, rotate)
        x = _layer_at(x, params["layers"]["moe"], index, hp, window, rotate)
    return _head(x, jnp.asarray(rows, jnp.int32), params["final_norm"],
                 params["lm_head"], float(cf["rms_norm_eps"]),
                 weights == "int8")
