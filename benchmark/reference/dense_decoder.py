"""Plain reference of the dense decoder family (Mistral-7B, Qwen2-7B): the
forward pass in straightforward ``jax.numpy``, float32 at matmul precision
"highest", written from the published description (Jiang et al. 2023,
"Mistral 7B", arXiv:2310.06825; Qwen2 technical report, arXiv:2407.10671;
the models' ``modeling_*.py`` on huggingface.co) and independent of
``ray_tpu/models/transformer.py``: no kernel, no cache, no batching, no paged
pool. One sequence, every position at once.

    h_0   = E[tokens]
    a_l   = h_l + Wo · Attn(RoPE(Wq n + bq), RoPE(Wk n + bk), Wv n + bv),
            n = RMSNorm(h_l; g_attn, eps)
    h_l+1 = a_l + Wdown · (silu(Wgate m) * (Wup m)),  m = RMSNorm(a_l; g_mlp)
    logits = RMSNorm(h_L; g_final) · Whead

Attention is causal, grouped-query (query head i reads KV head i // (H/KV)),
and with ``sliding_window`` W position i sees keys j with i - W < j <= i.
RoPE is the half-split ("rotate_half") form with inv_freq_k = theta^(-2k/D).

Departures from the published files: none in the mathematics. The weights
arrive in the program's tree layout (``wq [L, d, H, D]`` ...), which is how
the benchmark hands the same seeded weights to both sides; each layer is
upcast to float32 when it is used, so the reference fits beside the engine.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms_norm(x, gain, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * gain


def _rope(x, positions, theta):
    """x [T, H, D] -> rotated; pairs (k, k + D/2) turn by pos * inv_freq_k."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=F32) * 2.0 / x.shape[-1])
    ang = positions.astype(F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(h, lp, hp):
    """One decoder layer over a whole sequence h [T, d], weights float32."""
    t = h.shape[0]
    heads, kv_heads = lp["wq"].shape[1], lp["wk"].shape[1]
    head_dim = lp["wq"].shape[2]
    pos = jnp.arange(t)
    n = _rms_norm(h, lp["attn_norm"], hp["rms_norm_eps"])
    q = jnp.einsum("td,dhk->thk", n, lp["wq"])
    k = jnp.einsum("td,dhk->thk", n, lp["wk"])
    v = jnp.einsum("td,dhk->thk", n, lp["wv"])
    if "bq" in lp:
        q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    q = _rope(q, pos, hp["rope_theta"])
    k = _rope(k, pos, hp["rope_theta"])
    group = heads // kv_heads
    q = q.reshape(t, kv_heads, group, head_dim)
    scores = jnp.einsum("igud,jgd->guij", q, k) / jnp.sqrt(F32(head_dim))
    i, j = pos[:, None], pos[None, :]
    seen = j <= i
    if hp["sliding_window"]:
        seen = seen & (j > i - hp["sliding_window"])
    scores = jnp.where(seen[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("guij,jgd->igud", probs, v).reshape(t, heads, head_dim)
    a = h + jnp.einsum("thk,hkd->td", ctx, lp["wo"])
    m = _rms_norm(a, lp["mlp_norm"], hp["rms_norm_eps"])
    gate = jax.nn.silu(m @ lp["w_gate"]) * (m @ lp["w_up"])
    return a + gate @ lp["w_down"]


def _int8(w, contract_axes):
    """Symmetric int8 with one scale per output channel, and back: the
    weights a weight-only int8 deployment would multiply by."""
    scale = jnp.max(jnp.abs(w), axis=contract_axes, keepdims=True) / 127.0
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


#: the axes each matrix is contracted over (the rest are output channels)
_CONTRACTS = {"wq": (0,), "wk": (0,), "wv": (0,), "wo": (0, 1),
              "w_gate": (0,), "w_up": (0,), "w_down": (0,)}


@functools.partial(jax.jit, static_argnames=("hp", "weights"))
def _layer_at(h, layers, index, hp, weights):
    lp = jax.tree.map(
        lambda w: jax.lax.dynamic_index_in_dim(w, index, 0, False).astype(F32),
        layers)
    if weights == "int8":
        lp = {k: _int8(w, _CONTRACTS[k]) if k in _CONTRACTS else w
              for k, w in lp.items()}
    with jax.default_matmul_precision("highest"):
        return _layer(h, lp, dict(hp))


@jax.jit
def _embed(embed, tokens):
    return embed[tokens].astype(F32)


@functools.partial(jax.jit, static_argnames=("eps", "weights"))
def _head(h, rows, final_norm, head, eps, weights):
    head = head.astype(F32)
    if weights == "int8":
        head = _int8(head, (0,))
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(h[rows], final_norm.astype(F32), eps)
        return x @ head


def hyper(config_file: Dict[str, Any]):
    """The published numbers the mathematics needs, hashable for jit."""
    return (("rms_norm_eps", float(config_file["rms_norm_eps"])),
            ("rope_theta", float(config_file["rope_theta"])),
            ("sliding_window", int(config_file["sliding_window"])
             if config_file["use_sliding_window"] else 0))


def logits_at(params, tokens, rows, config_file: Dict[str, Any],
              weights: str = "as_given"):
    """Float32 logits [len(rows), V] of the sequence ``tokens`` [T] at the
    positions ``rows``. ``weights="int8"`` is the control: the same
    mathematics over weights rounded to int8 per output channel, the nearest
    precision below the bf16 the configurations state."""
    hp = hyper(config_file)
    layers = params["layers"]
    n_layers = layers["wq"].shape[0]
    h = _embed(params["embed"], jnp.asarray(tokens, jnp.int32))
    for index in range(n_layers):
        h = _layer_at(h, layers, index, hp, weights)
    head = (params["embed"].T if config_file["tie_word_embeddings"]
            else params["lm_head"])
    return _head(h, jnp.asarray(rows, jnp.int32), params["final_norm"], head,
                 float(config_file["rms_norm_eps"]), weights)


@functools.partial(jax.jit, static_argnames=("axes", "stacked"),
                   donate_argnums=0)
def _rounded(w, axes, stacked):
    def one(x):
        return _int8(x.astype(F32), axes).astype(w.dtype)
    return jax.lax.map(one, w) if stacked else one(w)


def rounded_weights(params, config_file: Dict[str, Any]):
    """The tree a weight-only int8 deployment would serve: every matrix the
    int8 control rounds, rounded the same way and kept in the type it came
    in, so the program itself can be run over them (the control read
    through the engine, ``tools/calibrate.py``). The arrays of ``params``
    are given up (donated), one layer's float32 copy at a time."""
    if config_file["tie_word_embeddings"]:
        raise NotImplementedError("a tied head shares the embedding")
    layers = {k: _rounded(w, _CONTRACTS[k], True) if k in _CONTRACTS else w
              for k, w in params["layers"].items()}
    return {**params, "layers": layers,
            "lm_head": _rounded(params["lm_head"], (0,), False)}
