"""Plain reference of the parallel attention / Mamba-2 decoder family
(Falcon-H1, ``model_type`` falcon_h1): the forward pass in straightforward
``jax.numpy``, float32 at matmul precision "highest", written from the
published ``config.json`` (huggingface.co/tiiuae/Falcon-H1-34B-Instruct) and
Mamba-2 (Dao and Gu, arXiv:2405.21060), independent of ``ray_tpu/models``
and ``ray_tpu/ops``: no cache, no paged pool, no batching, no block form.
One sequence, every position at once; the recurrence is written as the
recurrence (a ``lax.scan`` over positions) and attention as a masked softmax.

    x0 = embed[token] * embedding_multiplier
    every layer:
      h = RMSNorm(x; input_layernorm)
      # attention heads (query head i reads KV head i // (heads / kv_heads))
      q, k, v = (h a_in) Wq, ((h a_in) Wk) key_multiplier, (h a_in) Wv
      q, k = RoPE(q), RoPE(k)             rotate-half over the whole head
      att = (softmax(q k^T / sqrt(hd), causal) v) Wo * attention_out_multiplier
      # Mamba-2 heads, the same h
      z | xBC | dt = ((h ssm_in_multiplier) W_in) * mup     mup = ssm_multipliers over z, x, B, C, dt
      xBC = SiLU(conv1d(xBC) + b)          depthwise, causal, over x | B | C
      D_t = softplus(dt + dt_bias)  [H];   A = -exp(A_log)  [H]
      S_t[h] = exp(D_t[h] A[h]) S_{t-1}[h] + D_t[h] x_t[h] (x) B_t[g(h)]     [P, N] a head
      y_t[h] = S_t[h] C_t[g(h)] + D[h] x_t[h]
      y = RMSNorm_group(y * SiLU(z); gain)     the mean square a group of d_ssm / G
      ssm = (y W_out) * ssm_out_multiplier
      x = x + att + ssm
      m = RMSNorm(x; pre_ff_layernorm)
      x = x + (SiLU((m W_gate) mlp_multipliers[0]) * (m W_up)) W_down * mlp_multipliers[1]
    logits = (RMSNorm(x; final_layernorm) W_head) * lm_head_multiplier

Departures from the published model, each stated in the configuration's
``assumed``: the gated norm takes its mean square a group
(``mamba_rms_norm`` true, ``mamba_norm_before_gate`` false; the config has no
key for the grouping), and the conv runs over ``x | B | C`` together (Mamba-2's
convention).

The weights arrive in the program's tree layout (``params["layers"][leaf]``
stacked over layers, matrices ``[in, out]``, ``W_in`` as its three column
blocks ``w_ssm_z | w_ssm_xbc | w_ssm_dt``), which is how the benchmark
hands the same seeded weights to both sides. It runs beside the engine's
12.5 GB: a layer's three parts (attention, Mamba-2, MLP) are separate
programs, each matrix up-cast to float32 where it is multiplied (a layer is
1.72 GB in float32), queries go in blocks of 512, and the head in slices of
the vocabulary.
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

#: what ``weights`` may name beside "as_given": the int8 control, the
#: recurrence held in bf16 where the configuration states float32, and the
#: mathematics left out that the comparison has to see
VARIANTS = ("int8", "bf16_state", "bf16_scan", "state_reset", "no_attention",
            "no_mamba", "group0_for_all", "no_key_multiplier",
            "no_ssm_multipliers")
#: positions between resets of the "state_reset" control: the state is not
#: carried from one prefill chunk to the next
RESET_EVERY = 32

_MATRICES = ("wq", "wk", "wv", "wo", "w_ssm_z", "w_ssm_xbc", "w_ssm_dt",
             "w_ssm_out", "w_gate", "w_up", "w_down")


def _rms_norm(x, gain, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * gain


def _int8(w):
    """Symmetric int8 with one scale per output channel (matrices are
    ``[in, out]``), and back: the weights a weight-only int8 deployment
    would multiply by."""
    scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def _bf16(x):
    """Rounded to bf16 and held in float32. By ``reduce_precision``: on the
    TPU the compiler drops a narrowing conversion that is widened again at
    once (``tools/calibrate.py::_kv_rounder``)."""
    return lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _leaf(tree, name, index, variant):
    w = lax.dynamic_index_in_dim(tree[name], index, 0, False).astype(F32)
    return _int8(w) if variant == "int8" and name in _MATRICES else w


def _rope(x, positions, theta):
    """Rotate-half over the whole head: x [T, H, D]."""
    half = x.shape[-1] // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=F32) / half)
    ang = positions.astype(F32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _static(fn):
    return functools.partial(jax.jit, static_argnames=("hp",))(fn)


@_static
def _attention(x, tree, index, hp):
    """The attention branch's share of the residual, [T, d]."""
    hp = dict(hp)
    variant = hp["variant"]
    leaf = lambda name: _leaf(tree, name, index, variant)
    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        heads, kv_heads, hd = hp["heads"], hp["kv_heads"], hp["head_dim"]
        h = _rms_norm(x, leaf("attn_norm"), hp["eps"]) * hp["attn_in"]
        key_mult = 1.0 if variant == "no_key_multiplier" else hp["key"]
        pos = jnp.arange(t)
        q = _rope((h @ leaf("wq")).reshape(t, heads, hd), pos, hp["theta"])
        k = _rope(((h @ leaf("wk")) * key_mult).reshape(t, kv_heads, hd),
                  pos, hp["theta"])
        v = (h @ leaf("wv")).reshape(t, kv_heads, hd)
        q = q.reshape(t, kv_heads, heads // kv_heads, hd)
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
        return (ctx.reshape(t, heads * hd) @ leaf("wo")) * hp["attn_out"]


@_static
def _mamba2(x, tree, index, hp):
    """The Mamba-2 branch's share of the residual, [T, d]."""
    hp = dict(hp)
    variant = hp["variant"]
    leaf = lambda name: _leaf(tree, name, index, variant)
    with jax.default_matmul_precision("highest"):
        t = x.shape[0]
        nh, p, g, n = (hp["ssm_heads"], hp["ssm_head_dim"], hp["groups"],
                       hp["states"])
        ds, gn = nh * p, g * n
        mup = (1.0,) * 5 if variant == "no_ssm_multipliers" else hp["mup"]
        h = _rms_norm(x, leaf("attn_norm"), hp["eps"]) * hp["ssm_in"]
        # W_in arrives as its column blocks z | x B C | dt
        z = (h @ leaf("w_ssm_z")) * mup[0]
        proj = h @ leaf("w_ssm_xbc")
        xbc = jnp.concatenate(
            [proj[:, :ds] * mup[1], proj[:, ds:ds + gn] * mup[2],
             proj[:, ds + gn:] * mup[3]], axis=-1)
        dt = (h @ leaf("w_ssm_dt")) * mup[4]
        conv_w, kk = leaf("conv_w"), tree["conv_w"].shape[1]
        pad = jnp.concatenate([jnp.zeros((kk - 1, xbc.shape[1]), F32), xbc])
        if variant == "state_reset":
            # the conv's inputs are state too: none cross a reset
            keep = (jnp.arange(t)[:, None] % RESET_EVERY
                    >= (kk - 1 - jnp.arange(kk))[None, :])          # [T, k]
        else:
            keep = jnp.ones((t, kk), bool)
        conv = leaf("conv_b") + sum(
            jnp.where(keep[:, j:j + 1], pad[j:j + t], 0.0) * conv_w[j]
            for j in range(kk))
        act = jax.nn.silu(conv)
        xs = act[:, :ds].reshape(t, nh, p)
        bm = act[:, ds:ds + gn].reshape(t, g, n)
        cm = act[:, ds + gn:].reshape(t, g, n)
        if variant == "group0_for_all":
            bm, cm = (jnp.broadcast_to(a[:, :1], a.shape) for a in (bm, cm))
        group = jnp.arange(nh) // (nh // g)               # a head's group
        delta = jax.nn.softplus(dt + leaf("dt_bias"))               # [T, H]
        a = -jnp.exp(leaf("A_log"))                                 # [H]
        carried = jnp.ones((t,), F32) if variant != "state_reset" \
            else (jnp.arange(t) % RESET_EVERY != 0).astype(F32)

        # "bf16_state": the state a request carries, rounded to bf16 at
        # every turn; "bf16_scan": that, and what each turn reads (the
        # decay, the input, B and C of the position); sums stay in float32
        same = lambda v: v
        kept = _bf16 if variant in ("bf16_state", "bf16_scan") else same
        low = _bf16 if variant == "bf16_scan" else same

        def one(s, args):
            d_t, x_t, b_t, c_t, keep_t = args
            s = kept(low(jnp.exp(d_t * a))[:, None, None] * s * keep_t
                     + low(d_t[:, None] * x_t)[:, :, None]
                     * low(b_t)[group][:, None, :])
            return s, jnp.sum(s * low(c_t)[group][:, None, :], axis=-1)

        _, y = lax.scan(one, jnp.zeros((nh, p, n), F32),
                        (delta, xs, bm, cm, carried))
        y = y + leaf("D")[:, None] * xs                             # [T, H, P]
        gated = (y.reshape(t, ds) * jax.nn.silu(z)).reshape(t, g, ds // g)
        normed = gated * lax.rsqrt(
            jnp.mean(jnp.square(gated), axis=-1, keepdims=True) + hp["eps"])
        normed = normed.reshape(t, ds) * leaf("ssm_norm")
        return (normed @ leaf("w_ssm_out")) * hp["ssm_out"]


@_static
def _mlp(x, tree, index, hp):
    hp = dict(hp)
    leaf = lambda name: _leaf(tree, name, index, hp["variant"])
    with jax.default_matmul_precision("highest"):
        m = _rms_norm(x, leaf("mlp_norm"), hp["eps"])
        gate = jax.nn.silu((m @ leaf("w_gate")) * hp["mlp"][0])
        return x + ((gate * (m @ leaf("w_up"))) @ leaf("w_down")) \
            * hp["mlp"][1]


@functools.partial(jax.jit, static_argnames=("scale",))
def _embed(embed, tokens, scale):
    return embed[tokens].astype(F32) * scale


@functools.partial(jax.jit, static_argnames=("eps", "int8", "scale"))
def _head(h, rows, gain, head, eps, int8, scale):
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
        return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], -1)[:, :v] * scale


def hyper(cf: Dict[str, Any], variant: str):
    """The published numbers the mathematics needs, hashable for jit."""
    return (("eps", float(cf["rms_norm_eps"])),
            ("heads", int(cf["num_attention_heads"])),
            ("kv_heads", int(cf["num_key_value_heads"])),
            ("head_dim", int(cf["head_dim"])),
            ("theta", float(cf["rope_theta"])),
            ("ssm_heads", int(cf["mamba_n_heads"])),
            ("ssm_head_dim", int(cf["mamba_d_head"])),
            ("groups", int(cf["mamba_n_groups"])),
            ("states", int(cf["mamba_d_state"])),
            ("attn_in", float(cf["attention_in_multiplier"])),
            ("attn_out", float(cf["attention_out_multiplier"])),
            ("key", float(cf["key_multiplier"])),
            ("ssm_in", float(cf["ssm_in_multiplier"])),
            ("ssm_out", float(cf["ssm_out_multiplier"])),
            ("mup", tuple(float(m) for m in cf["ssm_multipliers"])),
            ("mlp", tuple(float(m) for m in cf["mlp_multipliers"])),
            ("variant", variant))


def logits_at(params, tokens, rows, config_file: Dict[str, Any],
              weights: str = "as_given"):
    """Float32 logits [len(rows), V] of the sequence ``tokens`` [T] at the
    positions ``rows``. ``weights`` names what takes the honest pass's
    place (``VARIANTS``): ``"int8"``, the same mathematics over weights
    rounded to int8 per output channel (the nearest precision below the
    bf16 the configuration states); and the mathematics left out:
    ``"state_reset"`` (the Mamba-2 state, scan and conv, dropped every
    ``RESET_EVERY`` positions: what a state not carried between chunks
    gives), ``"no_attention"`` and ``"no_mamba"`` (a branch dropped from the
    residual), ``"group0_for_all"`` (every head reads group 0's ``B`` and
    ``C``), ``"no_key_multiplier"`` and ``"no_ssm_multipliers"`` (those
    multipliers left at 1)."""
    if weights != "as_given" and weights not in VARIANTS:
        raise ValueError(f"unknown weights {weights!r}")
    cf = config_file
    if cf["tie_word_embeddings"] or cf["attention_bias"] \
            or cf["mamba_proj_bias"] or cf["mlp_bias"] \
            or not cf["mamba_conv_bias"] or not cf["mamba_rms_norm"] \
            or cf["mamba_norm_before_gate"] or not cf["mamba_use_mlp"] \
            or cf["attn_layer_indices"] is not None \
            or cf["rope_scaling"] is not None or cf["hidden_act"] != "silu":
        raise NotImplementedError("a layer this reference does not describe")
    hp = hyper(cf, weights)
    layers = params["layers"]
    x = _embed(params["embed"], jnp.asarray(tokens, jnp.int32),
               float(cf["embedding_multiplier"]))
    for i in range(layers["wq"].shape[0]):
        idx = jnp.asarray(i, jnp.int32)
        att = 0.0 if weights == "no_attention" \
            else _attention(x, layers, idx, hp)
        ssm = 0.0 if weights == "no_mamba" else _mamba2(x, layers, idx, hp)
        x = _mlp(x + att + ssm, layers, idx, hp)
    return _head(x, jnp.asarray(rows, jnp.int32), params["final_norm"],
                 params["lm_head"], float(cf["rms_norm_eps"]),
                 weights == "int8", float(cf["lm_head_multiplier"]))
