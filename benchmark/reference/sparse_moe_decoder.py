"""Plain reference of the sparse-attention MoE decoder family
(Keye-VL-2.0-30B-A3B's language model): the forward pass in straightforward
``jax.numpy``, float32 at matmul precision "highest", written from the
published ``config.json`` (``model_type`` KeyeVL2, huggingface.co/Kwai-Keye/
Keye-VL-2.0-30B-A3B), the Qwen3-MoE layer it extends and DeepSeek-V3.2's
"lightning indexer" (DeepSeek-AI 2025, "DeepSeek-V3.2-Exp"), and independent
of ``ray_tpu/models`` and ``ray_tpu/ops``: no kernel, no cache, no paged
pool, no grouped matmul. One sequence, every position at once.

    n      = RMSNorm(h_l; g_attn)
    q, k   = RoPE(RMSNorm_head(n Wq; g_q)), RoPE(RMSNorm_head(n Wk; g_k))
    v      = n Wv
    qI, kI = RoPE(n WqI), RoPE(LayerNorm(n WkI; g_I, b_I));  w = n Ww
    I[t,s] = sum_j w[t,j] relu(qI[t,j] . kI[s])                  (s <= t)
    S_t    = the min(topk, t + 1) keys with the largest I[t, .]
    a_l    = h_l + Wo softmax_{s in S_t}(q_t . k_s / sqrt(D)) v_s
    m      = RMSNorm(a_l; g_mlp);  p = softmax(m Wr) in float32
    h_l+1  = a_l + sum_{e in top8(p)} p_e / (sum of the eight)
                   Wdown_e (silu(Wgate_e m) * (Wup_e m))
    logits = RMSNorm(h_L; g_final) Whead

Attention is grouped-query (query head i reads KV head i // (H/KV)). RoPE
is the half-split form with inv_freq_k = theta^(-2k/D). Every layer is such
a layer (``decoder_sparse_step`` 1, ``mlp_only_layers`` []), so the dense
``intermediate_size`` is unused. No shared expert, no capacity: no token is
dropped.

Departures from the published model, each stated in the configuration's
``assumed``:

- no vision tower: the language model only, text positions only. With the
  three position components of M-RoPE equal (``mrope_section`` [16, 24, 24]
  sums to the 64 rotary pairs) M-RoPE IS the one-dimensional RoPE here;
- ASSUMED: per-head RMSNorm on q and k (Qwen3-MoE; the config has no key);
- ASSUMED: RoPE on ``qI`` and ``kI`` over all ``indexer_head_dim`` (no
  split is given), LayerNorm (gain and bias) on ``kI``;
- ASSUMED: ``q_chunk_size`` / ``kv_chunk_size`` tile the computation and
  leave the result unchanged: the selection is token-level, an exact
  ``lax.top_k`` over the whole causal score row.

The weights arrive in the program's tree layout (``wq [L, d, H, D]``,
``w_gate [L, E, d, f]``, ...), which is how the benchmark hands the same
seeded weights to both sides. It runs beside the engine's 11.7 GB: queries
go in blocks of 128 (128 x 32 heads x 29k keys x 4 B = 0.5 GB of scores) and
experts are up-cast to float32 one at a time.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
QUERY_BLOCK = 128


def _rms_norm(x, gain, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * gain


def _layer_norm(x, gain, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * gain + bias


def _rope(x, positions, theta):
    """x [T, H, D] -> rotated; pairs (k, k + D/2) turn by pos * inv_freq_k."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=F32) * 2.0 / x.shape[-1])
    ang = positions.astype(F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _int8(w, contract_axes):
    """Symmetric int8 with one scale per output channel, and back: the
    weights a weight-only int8 deployment would multiply by."""
    scale = jnp.max(jnp.abs(w), axis=contract_axes, keepdims=True) / 127.0
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


#: the axes each matrix is contracted over (the rest are output channels);
#: experts one at a time, so their expert axis is gone. The router and the
#: indexer's head weights stay as given: an int8 deployment keeps them.
_CONTRACTS = {"wq": (0,), "wk": (0,), "wv": (0,), "wo": (0, 1),
              "wq_i": (0,), "wk_i": (0,),
              "w_gate": (0,), "w_up": (0,), "w_down": (0,)}
_EXPERT = ("w_gate", "w_up", "w_down")


def _attention(n, lp, hp, selection):
    """The attention branch over a whole sequence n [T, d] (normed)."""
    t = n.shape[0]
    heads, kv_heads, head_dim = lp["wq"].shape[1], lp["wk"].shape[1], \
        lp["wq"].shape[2]
    eps, theta, topk = hp["rms_norm_eps"], hp["rope_theta"], hp["topk"]
    pos = jnp.arange(t)
    q = _rms_norm(jnp.einsum("td,dhk->thk", n, lp["wq"]), lp["q_norm"], eps)
    k = _rms_norm(jnp.einsum("td,dhk->thk", n, lp["wk"]), lp["k_norm"], eps)
    v = jnp.einsum("td,dhk->thk", n, lp["wv"])
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    qi = _rope(jnp.einsum("td,djk->tjk", n, lp["wq_i"]), pos, theta)
    ki = _layer_norm(n @ lp["wk_i"], lp["ki_norm"], lp["ki_norm_b"], eps)
    ki = _rope(ki[:, None, :], pos, theta)[:, 0]
    w = n @ lp["w_i"]                                             # [T, J]
    group = heads // kv_heads
    block = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t

    def one_block(args):
        qb, qib, wb, posb = args             # [Q, H, D], [Q, J, di], [Q, J]
        causal = pos[None, :] <= posb[:, None]                    # [Q, T]
        if selection == "recent_keys":
            # the control: the indexer left out, the latest topk keys
            chosen = causal & (pos[None, :] > posb[:, None] - topk)
        else:
            index = jnp.sum(jax.nn.relu(jnp.einsum("qjd,sd->qjs", qib, ki))
                            * wb[:, :, None], axis=1)             # [Q, T]
            vals, idx = lax.top_k(jnp.where(causal, index, -jnp.inf),
                                  min(topk, t))
            rows = jnp.arange(qb.shape[0])[:, None]
            chosen = jnp.zeros(causal.shape, bool).at[rows, idx].set(
                vals > -jnp.inf)          # fewer than topk causal keys: all
        qg = qb.reshape(-1, kv_heads, group, head_dim)
        scores = jnp.einsum("qgud,sgd->guqs", qg, k) / jnp.sqrt(F32(head_dim))
        scores = jnp.where(chosen[None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("guqs,sgd->qgud", probs, v).reshape(
            -1, heads, head_dim)

    split = lambda x: x.reshape(t // block, block, *x.shape[1:])
    ctx = lax.map(one_block, (split(q), split(qi), split(w), split(pos)))
    return jnp.einsum("thk,hkd->td", ctx.reshape(t, heads, head_dim),
                      lp["wo"])


def _experts(m, lp, hp, weights):
    """The expert branch: a loop over the experts, each over the whole
    sequence m [T, d] and weighted by what the router gave it (0 for the
    tokens that did not choose it)."""
    probs = jax.nn.softmax(m @ lp["router"], axis=-1)             # float32
    top_p, top_e = lax.top_k(probs, hp["experts_per_tok"])
    if hp["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    n_experts = lp["router"].shape[1]

    def one(e, out):
        wg, wu, wd = (lax.dynamic_index_in_dim(lp[k], e, 0, False)
                      .astype(F32) for k in _EXPERT)
        if weights == "int8":
            wg, wu, wd = (_int8(x, (0,)) for x in (wg, wu, wd))
        share = jnp.sum(jnp.where(top_e == e, top_p, 0.0), axis=-1)
        return out + share[:, None] * (
            (jax.nn.silu(m @ wg) * (m @ wu)) @ wd)

    return lax.fori_loop(0, n_experts, one, jnp.zeros_like(m))


@functools.partial(jax.jit, static_argnames=("hp", "weights"))
def _layer_at(h, layers, index, hp, weights):
    hp = dict(hp)
    # the experts stay in the type they are stored in until their turn
    lp = {k: lax.dynamic_index_in_dim(w, index, 0, False)
          for k, w in layers.items()}
    lp = {k: w if k in _EXPERT else w.astype(F32) for k, w in lp.items()}
    if weights == "int8":
        lp = {k: _int8(w, _CONTRACTS[k])
              if k in _CONTRACTS and k not in _EXPERT else w
              for k, w in lp.items()}
    selection = "recent_keys" if weights == "recent_keys" else "indexer"
    with jax.default_matmul_precision("highest"):
        eps = hp["rms_norm_eps"]
        a = h + _attention(_rms_norm(h, lp["attn_norm"], eps), lp, hp,
                           selection)
        return a + _experts(_rms_norm(a, lp["mlp_norm"], eps), lp, hp,
                            weights)


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
            ("topk", int(config_file["sa_config"]["topk"])),
            ("experts_per_tok", int(config_file["num_experts_per_tok"])),
            ("norm_topk_prob", bool(config_file["norm_topk_prob"])))


def logits_at(params, tokens, rows, config_file: Dict[str, Any],
              weights: str = "as_given"):
    """Float32 logits [len(rows), V] of the sequence ``tokens`` [T] at the
    positions ``rows``. Two controls take the honest pass's place:
    ``weights="int8"``, the same mathematics over weights rounded to int8
    per output channel (the nearest precision below the bf16 the
    configuration states), and ``weights="recent_keys"``, the weights as
    given with the indexer left out and every query reading its latest
    ``topk`` keys (what a selection that ignores the scores would give)."""
    hp = hyper(config_file)
    layers = params["layers"]
    h = _embed(params["embed"], jnp.asarray(tokens, jnp.int32))
    for index in range(layers["wq"].shape[0]):
        h = _layer_at(h, layers, index, hp, weights)
    head = (params["embed"].T if config_file["tie_word_embeddings"]
            else params["lm_head"])
    return _head(h, jnp.asarray(rows, jnp.int32), params["final_norm"], head,
                 float(config_file["rms_norm_eps"]),
                 "int8" if weights == "int8" else "as_given")
