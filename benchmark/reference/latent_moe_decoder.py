"""Plain reference of the latent-attention MoE decoder family (Kimi-K2.5's
language model, ``model_type`` kimi_k2: the DeepSeek-V3 layer under
Moonshot's numbers): the forward pass in straightforward ``jax.numpy``,
float32 at matmul precision "highest", written from the published
``config.json`` (huggingface.co/moonshotai/Kimi-K2.5), DeepSeek-V2's
multi-head latent attention (DeepSeek-AI 2024, arXiv:2405.04434), DeepSeek-V3's
auxiliary-loss-free routing (arXiv:2412.19437) and YaRN (Peng et al. 2023,
arXiv:2309.00071), and independent of ``ray_tpu/models`` and ``ray_tpu/ops``:
no kernel, no cache, no paged pool, no grouped matmul, attention UNABSORBED
(every head's keys and values up-projected from the latent). One sequence,
every position at once.

    n        = RMSNorm(h_l; g_attn)
    c_q      = RMSNorm(n W_qa; g_q);   [q_nope, q_rope]_h = (c_q W_qb)_h
    [c, k_r] = n W_kva;  c_kv = RMSNorm(c; g_kv);  k_r = RoPE(k_r)
    k_nope_h = c_kv W_uk_h^T;  v_h = c_kv W_uv_h          (W_kvb's halves)
    score_h[t, s] = (q_nope_h[t] . k_nope_h[s] + RoPE(q_rope_h)[t] . k_r[s]) * s
    a_l      = h_l + [softmax_{s <= t}(score_h) v_h]_h W_o
    m        = RMSNorm(a_l; g_mlp)
    layer < first_k_dense_replace:   h_l+1 = a_l + SwiGLU_18432(m)
    else:    sc = sigmoid(m W_r) in float32;  S = top8(sc + b)
             w_e = sc_e / (sum_{S} sc + 1e-20) * routed_scaling_factor
             h_l+1 = a_l + sum_{e in S, e HELD} w_e E_e(m) + Shared(m)
    logits   = RMSNorm(h_L; g_final) W_head

``s = (nope + rope)^-0.5 * mscale^2``, ``mscale = 0.1 * mscale_all_dim *
ln(factor) + 1``. RoPE is YaRN-scaled: inverse frequency ``k`` is
``theta^(-2k/rope)`` where it turns more than ``beta_fast`` times over the
original length, that over ``factor`` where it turns fewer than ``beta_slow``
times, a linear ramp over the index between; cos and sin are multiplied by
``mscale / mscale_all_dim``'s ratio of the same form (1 here). ONE rotated
key a token, shared by every head.

**The share.** The router is as wide as the published model and picks 8 of
all its experts; the weights hold ``E`` of them from index ``first``
(``reduced.n_routed_experts`` of the configuration). The pairs whose expert
is held are summed, the others left out, here as in the program: their part
is another chip's.

Departures from the published model, each in the configuration's
``assumed``: no vision tower (the language model only); RoPE pairs
``(k, k + rope / 2)`` (the half-split form; the config has no key for the
pairing); ``n_group`` 1 and ``topk_group`` 1 make the group-limited step
trivial.

The weights arrive in the program's tree layout (``layers["dense"]`` and
``layers["moe"]``, each leaf stacked over its segment's layers; ``w_uk [H,
nope, rank]`` and ``w_uv [H, rank, v]`` the two halves of ``kv_b_proj``),
which is how the benchmark hands the same seeded weights to both sides. It
runs beside the engine's 10 GB, so nothing of ``[T, hidden]`` in float32
(1.2 GB at 43k tokens) exists more than three times: position-wise parts go
in blocks of ``TOKEN_BLOCK`` tokens, attention ``HEAD_GROUP`` heads and
``QUERY_BLOCK`` queries at a time, the dense MLP in columns of
``MLP_COLUMNS``, experts up-cast to float32 one at a time.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
QUERY_BLOCK = 128
TOKEN_BLOCK = 1024
HEAD_GROUP = 8
MLP_COLUMNS = 2048

#: what ``logits_at(weights=...)`` takes beside "as_given": the int8
#: control, and mathematics left out one piece at a time
CONTROLS = ("int8", "no_rope_key", "plain_rope", "softmax_router",
            "no_router_bias", "no_scale", "no_shared", "no_held")


def _rms_norm(x, gain, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * gain


def yarn_inv_freq(dim, theta, factor, beta_fast, beta_slow, original):
    """[dim / 2] inverse frequencies, as DeepSeek-V3's
    ``DeepseekV3YarnRotaryEmbedding`` computes them."""
    plain = theta ** (-jnp.arange(0, dim, 2, dtype=F32) / dim)
    if factor <= 1.0:
        return plain

    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - low) / (high - low),
                    0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def _mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1.0 and m else 1.0


def _rope(x, positions, hp):
    """x [T, ..., R] -> rotated; pairs (k, k + R/2) turn by pos * inv_k."""
    half = x.shape[-1] // 2
    inv = yarn_inv_freq(x.shape[-1], hp["rope_theta"], hp["factor"],
                        hp["beta_fast"], hp["beta_slow"], hp["original"])
    amp = _mscale(hp["factor"], hp["mscale"]) \
        / _mscale(hp["factor"], hp["mscale_all_dim"])
    ang = positions.astype(F32)[:, None] * inv[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    cos, sin = (f(ang).reshape(shape) * amp for f in (jnp.cos, jnp.sin))
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _int8(w, contract_axes):
    """Symmetric int8 with one scale per output channel, and back: the
    weights a weight-only int8 deployment would multiply by."""
    scale = jnp.max(jnp.abs(w), axis=contract_axes, keepdims=True) / 127.0
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


#: the axes each matrix is contracted over (the rest are output channels);
#: experts one at a time, so their expert axis is gone. The router stays as
#: given: an int8 deployment keeps it.
_CONTRACTS = {"wq_a": (0,), "wq_b": (0,), "wkv_a": (0,), "w_uk": (2,),
              "w_uv": (1,), "wo": (0,), "w_gate": (0,), "w_up": (0,),
              "w_down": (0,), "ws_gate": (0,), "ws_up": (0,),
              "ws_down": (0,)}
_LATE = ("w_gate", "w_up", "w_down")    # up-cast where they are multiplied


def _blocks(x, size):
    return x.reshape(x.shape[0] // size, size, *x.shape[1:])


def _block_of(t, size):
    return size if t % size == 0 else t


def _attention(h, lp, hp):
    """The attention branch over a whole sequence h [T, d] (not normed)."""
    t, d = h.shape
    eps, rank = hp["rms_norm_eps"], hp["kv_lora_rank"]
    heads, nope, rope, vd = (hp["heads"], hp["qk_nope_head_dim"],
                             hp["qk_rope_head_dim"], hp["v_head_dim"])
    pos = jnp.arange(t)
    plain = hp["control"] == "plain_rope"
    if plain:
        hp = {**hp, "factor": 1.0}
    scale = (nope + rope) ** -0.5 * (
        1.0 if plain else _mscale(hp["factor"], hp["mscale_all_dim"]) ** 2)

    def latents(hb):
        n = _rms_norm(hb, lp["attn_norm"], eps)
        kv = n @ lp["wkv_a"]
        return (_rms_norm(n @ lp["wq_a"], lp["q_norm"], eps),
                _rms_norm(kv[:, :rank], lp["kv_norm"], eps), kv[:, rank:])

    tb = _block_of(t, TOKEN_BLOCK)
    c_q, c_kv, k_r = (x.reshape(t, -1)
                      for x in lax.map(latents, _blocks(h, tb)))
    k_r = _rope(k_r, pos, hp)                                     # [T, R]
    if hp["control"] == "no_rope_key":
        k_r = jnp.zeros_like(k_r)
    g = math.gcd(heads, HEAD_GROUP)
    qb = _block_of(t, QUERY_BLOCK)
    wq_b = lp["wq_b"].reshape(-1, heads, nope + rope)

    def group(i, acc):
        at = i * g
        q = jnp.einsum("tr,rhe->the", c_q,
                       lax.dynamic_slice_in_dim(wq_b, at, g, 1))
        q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], pos, hp)
        k_nope = jnp.einsum(
            "tr,hnr->thn", c_kv, lax.dynamic_slice_in_dim(lp["w_uk"], at, g))
        v = jnp.einsum(
            "tr,hrv->thv", c_kv, lax.dynamic_slice_in_dim(lp["w_uv"], at, g))

        def one_block(args):
            qn, qr, posb = args                  # [Q, g, nope], [Q, g, R]
            s = (jnp.einsum("qhn,shn->hqs", qn, k_nope)
                 + jnp.einsum("qhr,sr->hqs", qr, k_r)) * scale
            causal = pos[None, :] <= posb[:, None]                # [Q, T]
            p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), -1)
            return jnp.einsum("hqs,shv->qhv", p, v)

        o = lax.map(one_block, (_blocks(q_nope, qb), _blocks(q_rope, qb),
                                _blocks(pos, qb))).reshape(t, g * vd)
        return acc + o @ lax.dynamic_slice_in_dim(lp["wo"], at * vd, g * vd)

    return lax.fori_loop(0, heads // g, group, jnp.zeros((t, d), F32))


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
    """The expert branch over m [Q, d]: the router over ALL the published
    experts, a loop over the experts HELD, each over every token and
    weighted by what the router gave it (0 for the tokens that did not
    choose it), and the shared expert."""
    control, int8 = hp["control"], hp["control"] == "int8"
    logits = m @ lp["router"]                                     # float32
    k = hp["experts_per_tok"]
    if hp["scoring_func"] == "sigmoid" and control != "softmax_router":
        score = jax.nn.sigmoid(logits)
        biased = score if control == "no_router_bias" \
            else score + lp["router_bias"]
        _, top_e = lax.top_k(biased, k)
        top_p = jnp.take_along_axis(score, top_e, axis=-1)
        if hp["norm_topk_prob"]:
            top_p = top_p / (jnp.sum(top_p, axis=-1, keepdims=True) + 1e-20)
    else:
        top_p, top_e = lax.top_k(jax.nn.softmax(logits, axis=-1), k)
        if hp["norm_topk_prob"]:
            top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    if control != "no_scale":
        top_p = top_p * hp["routed_scaling_factor"]

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


@functools.partial(jax.jit, static_argnames=("hp", "dense"),
                   donate_argnums=(0,))
def _layer_at(h, layers, index, hp, dense):
    hp = dict(hp)
    int8 = hp["control"] == "int8"
    # the MLP's and the experts' matrices stay in the type they are stored
    # in until their turn
    lp = {k: lax.dynamic_index_in_dim(w, index, 0, False)
          for k, w in layers.items()}
    late = lambda k: k in _LATE or k.startswith("ws_")
    lp = {k: w if late(k) else w.astype(F32) for k, w in lp.items()}
    if int8:
        lp = {k: _int8(w, _CONTRACTS[k])
              if k in _CONTRACTS and not late(k) else w
              for k, w in lp.items()}
    eps = hp["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        a = h + _attention(h, lp, hp)

        def mlp(ab):
            m = _rms_norm(ab, lp["mlp_norm"], eps)
            if dense:
                return ab + _swiglu_columns(m, lp, _LATE, int8)
            return ab + _experts(m, lp, hp)

        t = a.shape[0]
        return lax.map(mlp, _blocks(a, _block_of(t, TOKEN_BLOCK))) \
            .reshape(a.shape)


@jax.jit
def _embed(embed, tokens):
    return embed[tokens].astype(F32)


@functools.partial(jax.jit, static_argnames=("eps", "int8"))
def _head(h, rows, final_norm, head, eps, int8):
    head = head.astype(F32)
    if int8:
        head = _int8(head, (0,))
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(h[rows], final_norm.astype(F32), eps)
        return x @ head


def hyper(config_file: Dict[str, Any], control: str = "as_given"):
    """The published numbers the mathematics needs, hashable for jit."""
    cf, rs = config_file, config_file["rope_scaling"]
    if rs["type"] != "yarn" or cf["n_group"] != 1 or cf["topk_group"] != 1 \
            or cf["moe_layer_freq"] != 1:
        raise NotImplementedError("a layer this reference does not describe")
    return (("rms_norm_eps", float(cf["rms_norm_eps"])),
            ("rope_theta", float(cf["rope_theta"])),
            ("factor", float(rs["factor"])),
            ("beta_fast", float(rs["beta_fast"])),
            ("beta_slow", float(rs["beta_slow"])),
            ("mscale", float(rs["mscale"])),
            ("mscale_all_dim", float(rs["mscale_all_dim"])),
            ("original", int(rs["original_max_position_embeddings"])),
            ("heads", int(cf["num_attention_heads"])),
            ("kv_lora_rank", int(cf["kv_lora_rank"])),
            ("qk_nope_head_dim", int(cf["qk_nope_head_dim"])),
            ("qk_rope_head_dim", int(cf["qk_rope_head_dim"])),
            ("v_head_dim", int(cf["v_head_dim"])),
            ("experts_per_tok", int(cf["num_experts_per_tok"])),
            ("norm_topk_prob", bool(cf["norm_topk_prob"])),
            ("scoring_func", str(cf["scoring_func"])),
            ("routed_scaling_factor", float(cf["routed_scaling_factor"])),
            ("experts_first", int(
                cf["reduced"].get("n_routed_experts", {}).get("first", 0))),
            ("control", control))


def logits_at(params, tokens, rows, config_file: Dict[str, Any],
              weights: str = "as_given"):
    """Float32 logits [len(rows), V] of the sequence ``tokens`` [T] at the
    positions ``rows``. A control takes the honest pass's place
    (``CONTROLS``): ``weights="int8"``, the same mathematics over weights
    rounded to int8 per output channel (the nearest precision below the bf16
    the configuration states); and the weights as given with one piece of
    the mathematics left out: ``no_rope_key`` (the rotated key dropped from
    the scores), ``plain_rope`` (RoPE unscaled and the softmax's ``mscale``
    1), ``softmax_router`` (softmax scores, no bias), ``no_router_bias``,
    ``no_scale`` (``routed_scaling_factor`` dropped), ``no_shared``,
    ``no_held`` (the held experts' sum dropped)."""
    if weights != "as_given" and weights not in CONTROLS:
        raise ValueError(f"unknown control {weights!r}")
    hp = hyper(config_file, weights)
    h = _embed(params["embed"], jnp.asarray(tokens, jnp.int32))
    for seg in ("dense", "moe"):
        layers = params["layers"][seg]
        for index in range(layers["attn_norm"].shape[0]):
            h = _layer_at(h, layers, index, hp, seg == "dense")
    head = (params["embed"].T if config_file["tie_word_embeddings"]
            else params["lm_head"])
    return _head(h, jnp.asarray(rows, jnp.int32), params["final_norm"], head,
                 float(config_file["rms_norm_eps"]), weights == "int8")
