"""Plain reference of the hybrid state-space / attention decoder family
(Phi-4-mini-flash-reasoning, ``model_type`` phi4flash: the SambaY layout, a
self-decoder and then a cross-decoder): the forward pass in straightforward
``jax.numpy``, float32 at matmul precision "highest", written from the
published ``config.json`` (huggingface.co/microsoft/Phi-4-mini-flash-
reasoning), Mamba-1 (Gu and Dao, arXiv:2312.00752) and differential attention
(Ye et al., arXiv:2410.05258), independent of ``ray_tpu/models`` and
``ray_tpu/ops``: no cache, no paged pool, no batching. One sequence, every
position at once; the recurrence is written as the recurrence (a
``lax.scan`` over positions) and attention as a masked softmax.

Every layer ``l`` of ``L`` is

    x = x + Mixer_l(LayerNorm(x; g, b));  x = x + W_down(SiLU(W_gate n) * (W_up n)),  n = LayerNorm(x; g', b')

with the mixer by layer index (``mb_per_layer`` 2: even layers the
state-space side, odd layers the attention side; the self-decoder is layers
``0 .. L/2 + 1``):

    l even, l <= L/2     Mamba-1:  [u, z] = n W_in;  u' = SiLU(conv1d(u) + b)  (depthwise, causal, kernel 4)
                         [r, B, C] = u' W_x;  D_t = softplus(r W_dt + b_dt);  A = -exp(A_log)
                         h_t = exp(D_t A) * h_{t-1} + (D_t u'_t) (x) B_t;  y_t = h_t C_t + D u'_t
                         out = (y * SiLU(z)) W_out.   Layer L/2's y is the memory m of every later layer
    l odd,  l <  L/2     attention over the last ``sliding_window`` keys (the query's own included)
    l = L/2 + 1          attention over every key; its k and v are what the cross layers read
    l odd,  l >  L/2 + 1 cross attention: q = n W_q + b_q only, causal over layer L/2 + 1's k and v
    l even, l >  L/2     gated memory unit: out = (m * SiLU(n W_1)) W_2

Attention is differential attention, in all three attention kinds: query
heads (2i, 2i + 1) are pair i, KV heads (2j, 2j + 1) pair j, query pair i
reads KV pair i // (query pairs a KV pair), V_j = [v_2j | v_2j+1]:

    out_i = RMSNorm(softmax(q_2i k_2j^T / sqrt(D)) V_j - lam softmax(q_2i+1 k_2j+1^T / sqrt(D)) V_j; g_sub) (1 - lam_init)
    lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init,   lam_init = 0.8 - 0.6 exp(-0.3 l)

then the output projection with its bias. No positional encoding. The
embedding is the head (tied).

Departures from the published model, each stated in the configuration's
``assumed``: the config has no key for Mamba's sizes (d_state 16, d_conv 4,
expand 2, dt_rank ceil(d / 16): Mamba-1's defaults), for the biases on q, k,
v and the output projection, or for differential attention's whole
parametrisation (the pairing, ``lam_init``'s schedule, the four lambda
vectors, the sub-norm as an RMSNorm with gain over 2 D); which tensor is the
memory (y before the z gate); the MLP's fused gate-and-up matrix is held as
its two halves, which is the same function.

The weights arrive in the program's tree layout (``params["layers"]
[segment][block][leaf]`` stacked over the segment's periods, matrices
``[in, out]``, ``A_log [n, d_inner]``), which is how the benchmark hands the
same seeded weights to both sides. It runs beside the engine's 9 GB: queries
go in blocks of 512 (40 heads x 512 x 4096 keys x 4 B = 0.3 GB of scores),
weights are up-cast to float32 a layer at a time and the head in slices of
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

#: what ``weights`` may name beside "as_given": the int8 control, and the
#: controls and broken layers that leave part of the mathematics out
VARIANTS = ("int8", "state_reset", "no_window", "window_plus_one",
            "cross_reads_window", "memory_after_gate", "no_lambda")
#: positions between resets of the "state_reset" control: the state is not
#: carried from one prefill chunk to the next
RESET_EVERY = 32


def _layer_norm(x, gain, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * gain + bias


def _int8(w):
    """Symmetric int8 with one scale per output channel (matrices are
    ``[in, out]``), and back: the weights a weight-only int8 deployment
    would multiply by."""
    scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


_MATRICES = ("w_in", "w_x", "w_dt", "w_out", "wq", "wk", "wv", "wo", "w1",
             "w2", "w_gate", "w_up", "w_down")


def _block(tree, index, variant):
    lp = {k: lax.dynamic_index_in_dim(w, index, 0, False).astype(F32)
          for k, w in tree.items()}
    if variant == "int8":
        lp = {k: _int8(w) if k in _MATRICES else w for k, w in lp.items()}
    return lp


def _mlp(x, lp, eps):
    n = _layer_norm(x, lp["mlp_norm"], lp["mlp_norm_b"], eps)
    return x + (jax.nn.silu(n @ lp["w_gate"]) * (n @ lp["w_up"])) \
        @ lp["w_down"]


def _mamba(n, lp, variant):
    """The Mamba-1 mixer over a whole sequence n [T, d] (normed).
    -> (out [T, d], y [T, d_inner])."""
    t = n.shape[0]
    di = lp["w_out"].shape[0]
    k, states = lp["conv_w"].shape[0], lp["A_log"].shape[0]
    r = lp["w_dt"].shape[0]
    uz = n @ lp["w_in"]
    u, z = uz[:, :di], uz[:, di:]
    pad = jnp.concatenate([jnp.zeros((k - 1, di), F32), u], axis=0)
    if variant == "state_reset":
        # the conv's inputs are state too: none cross a reset
        keep = (jnp.arange(t)[:, None] % RESET_EVERY
                >= (k - 1 - jnp.arange(k))[None, :])             # [T, k]
    else:
        keep = jnp.ones((t, k), bool)
    conv = lp["conv_b"] + sum(
        jnp.where(keep[:, j:j + 1], pad[j:j + t], 0.0) * lp["conv_w"][j]
        for j in range(k))
    up = jax.nn.silu(conv)
    xp = up @ lp["w_x"]
    rr, bm, cm = xp[:, :r], xp[:, r:r + states], xp[:, r + states:]
    delta = jax.nn.softplus(rr @ lp["w_dt"] + lp["b_dt"])         # [T, di]
    a = -jnp.exp(lp["A_log"])                                     # [n, di]
    carried = jnp.ones((t,), F32) if variant != "state_reset" \
        else (jnp.arange(t) % RESET_EVERY != 0).astype(F32)

    def one(h, args):
        d_t, u_t, b_t, c_t, keep_t = args
        h = jnp.exp(d_t[None, :] * a) * h * keep_t \
            + (d_t * u_t)[None, :] * b_t[:, None]
        return h, jnp.sum(h * c_t[:, None], axis=0)

    _, y = lax.scan(one, jnp.zeros((states, di), F32),
                    (delta, up, bm, cm, carried))
    y = y + lp["D"] * up
    return (y * jax.nn.silu(z)) @ lp["w_out"], y, z


def _diff_attention(q, k, v, lp, layer, hp, window):
    """Differential attention of queries q [T, H * D] over keys and values
    k, v [T, KV * D] of the same sequence, causal, ``window`` keys back
    (0: all)."""
    t = q.shape[0]
    heads, kv_heads = hp["heads"], hp["kv_heads"]
    hd = q.shape[1] // heads
    pairs, kv_pairs = heads // 2, kv_heads // 2
    q = q.reshape(t, kv_pairs, pairs // kv_pairs, 2, hd)     # [T, j, g, s, D]
    k = k.reshape(t, kv_pairs, 2, hd)                        # [T, j, s, D]
    v = v.reshape(t, kv_pairs, 2 * hd)                       # [T, j, 2 D]
    lam_init = 0.8 - 0.6 * jnp.exp(-0.3 * layer.astype(F32))
    lam = jnp.exp(jnp.sum(lp["lam_q1"] * lp["lam_k1"])) \
        - jnp.exp(jnp.sum(lp["lam_q2"] * lp["lam_k2"])) + lam_init
    if hp["variant"] == "no_lambda":
        lam = 0.0
    pos = jnp.arange(t)
    block = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t

    def one_block(args):
        qb, posb = args                                      # [Q, j, g, s, D]
        seen = pos[None, :] <= posb[:, None]
        if window:
            seen = seen & (pos[None, :] > posb[:, None] - window)
        scores = jnp.einsum("qjgsd,kjsd->jgsqk", qb, k) / jnp.sqrt(F32(hd))
        probs = jax.nn.softmax(
            jnp.where(seen[None, None, None], scores, -jnp.inf), axis=-1)
        out = jnp.einsum("jgsqk,kjd->qjgsd", probs, v)       # [Q,j,g,s,2D]
        diff = out[..., 0, :] - lam * out[..., 1, :]
        var = jnp.mean(jnp.square(diff), axis=-1, keepdims=True)
        return diff * lax.rsqrt(var + hp["eps"]) * lp["subln"] \
            * (1.0 - lam_init)

    split = lambda x: x.reshape(t // block, block, *x.shape[1:])
    ctx = lax.map(one_block, (split(q), split(pos)))
    return ctx.reshape(t, heads * hd) @ lp["wo"] + lp["bo"]


def _static(fn):
    return functools.partial(jax.jit, static_argnames=("hp",))(fn)


@_static
def _mamba_layer(x, tree, index, hp):
    hp = dict(hp)
    with jax.default_matmul_precision("highest"):
        lp = _block(tree, index, hp["variant"])
        out, y, z = _mamba(_layer_norm(x, lp["attn_norm"], lp["attn_norm_b"],
                                       hp["eps"]), lp, hp["variant"])
        if hp["variant"] == "memory_after_gate":
            y = y * jax.nn.silu(z)
        return _mlp(x + out, lp, hp["eps"]), y


@_static
def _attention_layer(x, tree, index, layer, kv, hp):
    """A window or full layer (``kv`` None: it projects its own keys and
    values) or a cross layer (``kv`` the full layer's). -> (x, (k, v))."""
    hp = dict(hp)
    with jax.default_matmul_precision("highest"):
        lp = _block(tree, index, hp["variant"])
        n = _layer_norm(x, lp["attn_norm"], lp["attn_norm_b"], hp["eps"])
        if kv is None:
            kv = (n @ lp["wk"] + lp["bk"], n @ lp["wv"] + lp["bv"])
        out = _diff_attention(n @ lp["wq"] + lp["bq"], *kv, lp, layer, hp,
                              hp["window"])
        return _mlp(x + out, lp, hp["eps"]), kv


@_static
def _gmu_layer(x, tree, index, memory, hp):
    hp = dict(hp)
    with jax.default_matmul_precision("highest"):
        lp = _block(tree, index, hp["variant"])
        n = _layer_norm(x, lp["attn_norm"], lp["attn_norm_b"], hp["eps"])
        out = (memory * jax.nn.silu(n @ lp["w1"])) @ lp["w2"]
        return _mlp(x + out, lp, hp["eps"])


@jax.jit
def _embed(embed, tokens):
    return embed[tokens].astype(F32)


@functools.partial(jax.jit, static_argnames=("eps", "int8"))
def _head(h, rows, gain, bias, head, eps, int8):
    """Logits of ``h[rows]`` against ``head [V, d]`` (the tied embedding),
    a slice of the vocabulary at a time."""
    with jax.default_matmul_precision("highest"):
        x = _layer_norm(h[rows], gain.astype(F32), bias.astype(F32), eps)
        v = head.shape[0]
        size = -(-v // VOCAB_SLICES)
        pad = jnp.pad(head, ((0, size * VOCAB_SLICES - v), (0, 0)))

        def one(w):
            w = w.astype(F32)
            if int8:
                w = _int8(w.T).T
            return x @ w.T

        out = lax.map(one, pad.reshape(VOCAB_SLICES, size, -1))
        return jnp.moveaxis(out, 0, 1).reshape(x.shape[0], -1)[:, :v]


def hyper(config_file: Dict[str, Any], variant: str, window: int):
    """The published numbers the mathematics needs, hashable for jit."""
    return (("eps", float(config_file["layer_norm_eps"])),
            ("heads", int(config_file["num_attention_heads"])),
            ("kv_heads", int(config_file["num_key_value_heads"])),
            ("variant", variant), ("window", int(window)))


def logits_at(params, tokens, rows, config_file: Dict[str, Any],
              weights: str = "as_given"):
    """Float32 logits [len(rows), V] of the sequence ``tokens`` [T] at the
    positions ``rows``. ``weights`` names what takes the honest pass's
    place (``VARIANTS``): ``"int8"``, the same mathematics over weights
    rounded to int8 per output channel (the nearest precision below the
    bf16 the configuration states); ``"state_reset"``, the state-space
    layers' state (scan and conv) dropped every ``RESET_EVERY`` positions,
    what a state not carried between chunks gives; ``"no_window"``, the
    window layers reading everything; and the broken layers the tests hold
    the comparison to: ``"window_plus_one"`` (one key more), ``"cross_reads_
    window"`` (the cross layers read the LAST WINDOW layer's keys and values,
    a pool of another layer), ``"memory_after_gate"`` (the memory taken
    after the z gate), ``"no_lambda"`` (differential attention's second
    map left out)."""
    if weights != "as_given" and weights not in VARIANTS:
        raise ValueError(f"unknown weights {weights!r}")
    if not config_file["tie_word_embeddings"]:
        raise NotImplementedError("an untied head")
    window = {"no_window": 0, "window_plus_one":
              config_file["sliding_window"] + 1}.get(
        weights, config_file["sliding_window"])
    hp_win = hyper(config_file, weights, window)
    hp_all = hyper(config_file, weights, 0)
    layers = params["layers"]
    a = layers["self"]["attn"]["wq"].shape[0]
    b = layers["cross"]["attn"]["wq"].shape[0]
    idx = lambda i: jnp.asarray(i, jnp.int32)
    x = _embed(params["embed"], jnp.asarray(tokens, jnp.int32))
    for i in range(a):
        x, _ = _mamba_layer(x, layers["self"]["mamba"], idx(i), hp_win)
        x, window_kv = _attention_layer(x, layers["self"]["attn"], idx(i),
                                        idx(2 * i + 1), None, hp_win)
    x, memory = _mamba_layer(x, layers["mid"]["mamba"], idx(0), hp_all)
    x, kv = _attention_layer(x, layers["mid"]["attn"], idx(0),
                             idx(2 * a + 1), None, hp_all)
    if weights == "cross_reads_window":
        kv = window_kv
    for i in range(b):
        x = _gmu_layer(x, layers["cross"]["gmu"], idx(i), memory, hp_all)
        x, _ = _attention_layer(x, layers["cross"]["attn"], idx(i),
                                idx(2 * a + 3 + 2 * i), kv, hp_all)
    return _head(x, jnp.asarray(rows, jnp.int32), params["final_norm"],
                 params["final_norm_b"], params["embed"],
                 float(config_file["layer_norm_eps"]), weights == "int8")
