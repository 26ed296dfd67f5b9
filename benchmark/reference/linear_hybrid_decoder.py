"""Plain reference of the linear hybrid decoder family (Olmo-Hybrid,
``model_type`` olmo_hybrid): the forward pass in straightforward
``jax.numpy``, float32 at matmul precision "highest", written from the
published ``config.json`` (huggingface.co/allenai/Olmo-Hybrid-7B), the gated
delta rule of Gated DeltaNet (Yang, Kautz, Hatamizadeh, arXiv:2412.06464)
with ``beta`` in (0, 2) (Grazzi et al., arXiv:2411.12537) and the Olmo 2 /
Olmo 3 family's norm placement, independent of ``ray_tpu/models`` and
``ray_tpu/ops``: no cache, no paged pool, no batching, no block form. One
sequence, every position at once; the delta rule is written as the
token-by-token recurrence (a ``lax.scan`` over positions) and attention as a
masked softmax in blocks of queries.

    x0 = embed[token]
    a "linear_attention" layer, head h of H (keys dk wide, values dv wide):
      q~ | k~ | v~ = SiLU(conv(x Wq)), SiLU(conv(x Wk)), SiLU(conv(x Wv))    depthwise, causal, no bias
      q = l2norm(q~[h]) / sqrt(dk);   k = l2norm(k~[h]);   v = v~[h]
      beta  = sigmoid(x Wb)[h]   (x 2 with linear_allow_neg_eigval)
      alpha = exp(-exp(A_log[h]) softplus((x Wa)[h] + dt_bias[h]))
      S_t = alpha S_{t-1} + beta k (v - alpha S_{t-1}^T k)^T              S: dk x dv
      o   = S_t^T q
      y   = concat_h(RMSNorm_dv(o; head_norm) * SiLU((x Wg)[h])) Wo
    a "full_attention" layer:
      q = RMSNorm(x Wq; q_norm),  k = RMSNorm(x Wk; k_norm)     over the WHOLE projection
      y = (softmax(q k^T / sqrt(hd), causal) v) Wo              no rotary, no bias
    both:  x = x + RMSNorm(y; post-mixer norm);   x = x + RMSNorm(SwiGLU(x); post-MLP norm)
    logits = RMSNorm(x; final norm) W_head

What the published config has no key for is stated in the configuration's
``assumed``: the rule itself (the ``linear_*`` keys are sizes), where the
norms sit and what the q/k norm spans, ``rope_theta: null`` read as no rotary
at all, convolutions without bias and ``A_log`` / ``dt_bias`` a head.

The weights arrive in the program's tree layout
(``params["layers"]["periods"]["delta" | "attn"][leaf]``, stacked over the
periods, a delta leaf with the period's delta layers as its second axis;
matrices ``[in, out]``; ``Wq | Wk | Wv`` of a delta layer as the column
blocks of ``w_qkv`` and ``Wa | Wb`` as those of ``w_ab``), which is how the
benchmark hands the same seeded weights to both sides. It runs beside the
engine: a layer's mixer and its MLP are separate programs, each matrix
up-cast to float32 where it is multiplied, queries go in blocks of 512, and
the head in slices of the vocabulary.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
QUERY_BLOCK = 512
VOCAB_SLICES = 8

#: what ``weights`` may name beside "as_given": the int8 control, the matrix
#: state held in bf16 where the configuration states float32, and the
#: mathematics left out that the comparison has to see
VARIANTS = ("int8", "bf16_state", "state_reset", "no_delta", "no_attention",
            "no_decay", "beta_below_one", "no_conv", "head_qk_norm",
            "no_gate")
#: positions between resets of the "state_reset" control: the state is not
#: carried from one prefill chunk to the next
RESET_EVERY = 32

_MATRICES = ("w_qkv", "w_g", "w_ab", "wo", "wq", "wk", "wv", "w_gate",
             "w_up", "w_down")


def _rms_norm(x, gain, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * gain


def _l2norm(x):
    return x * lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                         + 1e-6)


def _int8(w):
    """Symmetric int8 with one scale per output channel (matrices are
    ``[in, out]``), and back."""
    scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def _bf16(x):
    """Rounded to bf16 and held in float32 (``reduce_precision``: the TPU's
    compiler drops a narrowing conversion that is widened again at once)."""
    return lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _leaf(tree, name, index, variant):
    w = tree[name]
    for i in index:
        w = lax.dynamic_index_in_dim(w, i, 0, False)
    w = w.astype(F32)
    return _int8(w) if variant == "int8" and name in _MATRICES else w


def _static(fn):
    return functools.partial(jax.jit, static_argnames=("hp",))(fn)


@_static
def _delta(x, tree, index, hp, upto=None):
    """A linear-attention layer's mixer output before its norm, [T, d], and
    what the layer carries past position ``upto`` (all of them where it is
    None): the matrix state ``[H, dk, dv]`` and the conv's last ``taps - 1``
    inputs ``[taps - 1, 2 H dk + H dv]``. Positions from ``upto`` on leave
    the state as it is, so their outputs mean nothing."""
    hp = dict(hp)
    variant = hp["variant"]
    leaf = lambda name: _leaf(tree, name, index, variant)
    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        h, dk, dv = hp["delta_heads"], hp["key_dim"], hp["value_dim"]
        kw = h * dk
        qkv = x @ leaf("w_qkv")
        conv_w, taps = leaf("conv_w"), hp["taps"]
        pad = jnp.concatenate([jnp.zeros((taps - 1, qkv.shape[1]), F32),
                               qkv])
        if variant == "state_reset":
            # the conv's inputs are state too: none cross a reset
            keep = (jnp.arange(t)[:, None] % RESET_EVERY
                    >= (taps - 1 - jnp.arange(taps))[None, :])
        else:
            keep = jnp.ones((t, taps), bool)
        conv = sum(jnp.where(keep[:, j:j + 1], pad[j:j + t], 0.0) * conv_w[j]
                   for j in range(taps))
        act = jax.nn.silu(qkv if variant == "no_conv" else conv)
        q = _l2norm(act[:, :kw].reshape(t, h, dk)) / jnp.sqrt(F32(dk))
        k = _l2norm(act[:, kw:2 * kw].reshape(t, h, dk))
        v = act[:, 2 * kw:].reshape(t, h, dv)
        ab = x @ leaf("w_ab")
        beta = jax.nn.sigmoid(ab[:, h:])
        if hp["neg_eigval"] and variant != "beta_below_one":
            beta = beta * 2.0
        alpha = jnp.exp(-jnp.exp(leaf("A_log"))
                        * jax.nn.softplus(ab[:, :h] + leaf("dt_bias")))
        if variant == "no_decay":
            alpha = jnp.ones_like(alpha)
        carried = jnp.ones((t,), F32) if variant != "state_reset" \
            else (jnp.arange(t) % RESET_EVERY != 0).astype(F32)
        if upto is not None:
            past = (jnp.arange(t) >= upto)[:, None]
            alpha, beta = jnp.where(past, 1.0, alpha), jnp.where(past, 0.0,
                                                                 beta)
        # "bf16_state": the state a request carries, rounded to bf16 at
        # every turn; the sums stay in float32
        kept = _bf16 if variant == "bf16_state" else (lambda s: s)

        def one(s, args):
            q_t, k_t, v_t, a_t, b_t, keep_t = args
            s = a_t[:, None, None] * s * keep_t
            u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t))
            s = kept(s + k_t[:, :, None] * u[:, None, :])
            return s, jnp.einsum("hkv,hk->hv", s, q_t)

        state, o = lax.scan(one, jnp.zeros((h, dk, dv), F32),
                            (q, k, v, alpha, beta, carried))
        last = lax.dynamic_slice_in_dim(pad, t if upto is None else upto,
                                        taps - 1)
        o = _rms_norm(o, leaf("head_norm"), hp["eps"])              # [T,H,dv]
        if variant != "no_gate":
            o = o * jax.nn.silu((x @ leaf("w_g")).reshape(t, h, dv))
        return o.reshape(t, h * dv) @ leaf("wo"), state, last


@_static
def _attention(x, tree, index, hp):
    """A full-attention layer's mixer output before its norm, [T, d]."""
    hp = dict(hp)
    variant = hp["variant"]
    leaf = lambda name: _leaf(tree, name, index, variant)
    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        heads, kv_heads, hd = hp["heads"], hp["kv_heads"], hp["head_dim"]
        q, k = x @ leaf("wq"), x @ leaf("wk")
        if variant == "head_qk_norm":
            # the norm a head (the mean square over hd), the gains as given
            norm = lambda a, g, n: (_rms_norm(
                a.reshape(t, n, hd), 1.0, hp["eps"]).reshape(t, -1) * g)
            q = norm(q, leaf("q_norm"), heads)
            k = norm(k, leaf("k_norm"), kv_heads)
        else:
            q = _rms_norm(q, leaf("q_norm"), hp["eps"])
            k = _rms_norm(k, leaf("k_norm"), hp["eps"])
        q = q.reshape(t, kv_heads, heads // kv_heads, hd)
        k = k.reshape(t, kv_heads, hd)
        v = (x @ leaf("wv")).reshape(t, kv_heads, hd)
        pos = jnp.arange(t)
        block = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t

        def one_block(args):
            qb, posb = args                                   # [Q, j, r, D]
            scores = jnp.einsum("qjrd,kjd->jrqk", qb, k) / jnp.sqrt(F32(hd))
            seen = pos[None, :] <= posb[:, None]
            probs = jax.nn.softmax(
                jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
            return jnp.einsum("jrqk,kjd->qjrd", probs, v)

        split = lambda a: a.reshape(t // block, block, *a.shape[1:])
        ctx = lax.map(one_block, (split(q), split(pos)))
        return ctx.reshape(t, heads * hd) @ leaf("wo")


@_static
def _close(x, y, tree, index, hp):
    """Both kinds' tail: the mixer's output ``y`` normed into the residual,
    then the MLP normed into it."""
    hp = dict(hp)
    leaf = lambda name: _leaf(tree, name, index, hp["variant"])
    with jax.default_matmul_precision("highest"):
        x = x + _rms_norm(y, leaf("attn_norm"), hp["eps"])
        m = (jax.nn.silu(x @ leaf("w_gate")) * (x @ leaf("w_up"))) \
            @ leaf("w_down")
        return x + _rms_norm(m, leaf("mlp_norm"), hp["eps"])


@jax.jit
def _embed(embed, tokens):
    return embed[tokens].astype(F32)


@functools.partial(jax.jit, static_argnames=("eps", "int8"))
def _head(h, rows, gain, head, eps, int8):
    """Logits of ``h[rows]`` against ``head [d, V]`` (untied), a slice of
    the vocabulary at a time."""
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(h[rows], gain.astype(F32), eps)
        v = head.shape[1]
        size = -(-v // VOCAB_SLICES)
        pad = jnp.pad(head, ((0, 0), (0, size * VOCAB_SLICES - v)))

        def one(w):
            w = w.astype(F32)
            return x @ (_int8(w) if int8 else w)

        out = lax.map(one, jnp.moveaxis(
            pad.reshape(-1, VOCAB_SLICES, size), 1, 0))
        return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], -1)[:, :v]


def hyper(cf: Dict[str, Any], variant: str):
    """The published numbers the mathematics needs, hashable for jit."""
    heads = int(cf["num_attention_heads"])
    return (("eps", float(cf["rms_norm_eps"])), ("heads", heads),
            ("kv_heads", int(cf["num_key_value_heads"])),
            ("head_dim", int(cf.get("head_dim")
                             or cf["hidden_size"] // heads)),
            ("delta_heads", int(cf["linear_num_value_heads"])),
            ("key_dim", int(cf["linear_key_head_dim"])),
            ("value_dim", int(cf["linear_value_head_dim"])),
            ("taps", int(cf["linear_conv_kernel_dim"])),
            ("neg_eigval", bool(cf["linear_allow_neg_eigval"])),
            ("variant", variant))


def _layers(params, tokens, cf: Dict[str, Any], weights: str, upto=None):
    """The residual stream ``[T, d]`` after the last layer, and every delta
    layer's ``(state, conv inputs)`` past position ``upto`` (``_delta``)."""
    if weights != "as_given" and weights not in VARIANTS:
        raise ValueError(f"unknown weights {weights!r}")
    blocks = params["layers"]["periods"]
    # the layers held are the first of the published pattern: as many
    # periods as the weights have
    periods, per = blocks["delta"]["w_qkv"].shape[:2]
    kinds = list(cf["layer_types"])[:periods * (per + 1)]
    if cf["tie_word_embeddings"] or cf["attention_bias"] \
            or cf["hidden_act"] != "silu" \
            or kinds != (["linear_attention"] * per
                         + ["full_attention"]) * periods \
            or cf["linear_num_key_heads"] != cf["linear_num_value_heads"] \
            or (cf.get("rope_parameters") or {}).get("rope_theta") \
            is not None:
        raise NotImplementedError("a layer this reference does not describe")
    hp = hyper(cf, weights)
    x = _embed(params["embed"], jnp.asarray(tokens, jnp.int32))
    idx = lambda *i: tuple(jnp.asarray(j, jnp.int32) for j in i)
    carried = []
    for p in range(periods):
        for j in range(per):
            y, *state = _delta(x, blocks["delta"], idx(p, j), hp, upto)
            carried.append(state)
            x = _close(x, jnp.zeros_like(x) if weights == "no_delta" else y,
                       blocks["delta"], idx(p, j), hp)
        y = jnp.zeros_like(x) if weights == "no_attention" \
            else _attention(x, blocks["attn"], idx(p), hp)
        x = _close(x, y, blocks["attn"], idx(p), hp)
    return x, carried


def logits_at(params, tokens, rows, config_file: Dict[str, Any],
              weights: str = "as_given"):
    """Float32 logits [len(rows), V] of the sequence ``tokens`` [T] at the
    positions ``rows``. ``weights`` names what takes the honest pass's
    place (``VARIANTS``): ``"int8"``, the same mathematics over weights
    rounded to int8 per output channel (the nearest precision below the
    bf16 the configuration states); ``"bf16_state"``, the delta rule's
    matrix state rounded to bf16 every turn (the configuration states
    float32); and the mathematics left out: ``"state_reset"`` (state and
    conv inputs dropped every ``RESET_EVERY`` positions), ``"no_delta"`` and
    ``"no_attention"`` (a kind of mixer dropped from the residual),
    ``"no_decay"`` (alpha 1), ``"beta_below_one"`` (beta not doubled),
    ``"no_conv"``, ``"head_qk_norm"`` (the q/k norm a head, not over the
    projection), ``"no_gate"`` (the output gate left out)."""
    x, _ = _layers(params, tokens, config_file, weights)
    return _head(x, jnp.asarray(rows, jnp.int32), params["final_norm"],
                 params["lm_head"], float(config_file["rms_norm_eps"]),
                 weights == "int8")


def state_at(params, tokens, upto: int, config_file: Dict[str, Any],
             weights: str = "as_given"):
    """What a request that has been fed ``tokens[:upto]`` carries, a delta
    layer at a time in the model's order: ``(states [layers, H, dk, dv],
    conv inputs [layers, taps - 1, 2 H dk + H dv])``, float32."""
    _, carried = _layers(params, tokens, config_file, weights,
                         jnp.asarray(upto, jnp.int32))
    return tuple(jnp.stack(leaf) for leaf in zip(*carried))
