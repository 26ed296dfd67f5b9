"""The linear hybrid decoder (Olmo-Hybrid layout:
``TransformerConfig.layer_kinds`` of ``"delta"`` and ``"full"``) on the
paged serve step: its parameter tree, its cache pools and the step's layer
loop.

Periods of ``a`` gated delta-rule layers closed by ONE full-attention layer,
``(("delta",) * a + ("full",)) * p``; both kinds norm a branch's OUTPUT and
nothing before it and carry a dense SwiGLU MLP::

    x = x + RMSNorm(mixer(x));   x = x + RMSNorm(SwiGLU(x))

A ``"delta"`` layer (:mod:`ray_tpu.ops.delta_rule`; ``H`` heads, keys
``dk`` and values ``dv`` wide)::

    q~ | k~ | v~ = SiLU(conv(x W_qkv))               depthwise causal, no bias
    q = l2norm(q~[h]) / sqrt(dk),  k = l2norm(k~[h]),  v = v~[h]
    beta = sigmoid(x W_b)[h]  (x 2 with delta_neg_eigval)
    alpha = exp(-exp(A_log[h]) softplus((x W_a)[h] + dt_bias[h]))
    S = alpha S + beta k (v - alpha S^T k)^T;   o = S^T q          float32
    y = concat_h(RMSNorm_dv(o) * SiLU((x W_g)[h])) W_o

A ``"full"`` layer: ``q = RMSNorm(x W_q)``, ``k = RMSNorm(x W_k)`` over the
WHOLE projection, heads of ``hd`` over ``kv_heads`` KV
heads, causal softmax at ``hd^-0.5``, NO positional encoding
(``positions="none"``: positions reach it through the delta layers' conv and
decay), no biases.

Parameters: ``params["layers"]["periods"]["delta" | "attn"][leaf]``, stacked
over the periods; a delta leaf has the period's ``a`` layers as its second
axis (ONE scanned delta layer inside ONE scanned period: the step program
holds each kind once). The three projections behind the conv are held as ONE
matrix ``w_qkv [d, 2 H dk + H dv]`` (11,520 columns at the published widths:
whole lanes, where a key projection alone, 2,880, is not) and the two
head-wide ones as ``w_ab [d, 2 H]``: the same function and the same count.

Cache pools (``init_cache``), by KIND of layer:

- ``"k"``, ``"v"`` ``[p, num_blocks, bs, pool_heads, hd]``: the full layers',
  read through the block table by
  :func:`ray_tpu.ops.paged_attention.paged_attention` as the uniform
  decoders read theirs. The head axis is as wide as the kernel tiles
  (:func:`ray_tpu.ops.paged_attention.pool_heads`: 30 KV heads lie in a pool
  of 32, two heads of zeros that no query reads);
- ``"conv"`` ``[a p, slots, (taps - 1) (2 H dk + H dv)]`` and ``"delta"``
  ``[a p, slots, H / r, dk, r dv]``, float32, indexed by the engine's SLOT:
  the last conv inputs (a slot's ``taps - 1`` rows laid end to end: three
  rows would tile as eight) and the rule's matrix state (``r`` heads side by
  side on the lanes: ``ops/delta_rule.py``), zeroed by the step for a row at
  position 0.
  ``Layout.snapshots``: the engine keeps copies of a slot's two leaves at
  block boundaries of a prompt, and a prefix hit restores one.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.config import TransformerConfig
from ray_tpu.ops.delta_rule import delta_rows, heads_per_row
from ray_tpu.ops.layers import rms_norm
from ray_tpu.ops.paged_attention import paged_attention, pool_heads

Params = Dict[str, Any]
F32 = jnp.float32

#: what refuses the layout anywhere but on the paged serve step
SERVE_ONLY = ("the linear hybrid layout (layer_kinds of 'delta' and "
              "'full': gated delta-rule layers closed by full attention)")

#: the longest block the delta rule's block form is asked for
DELTA_BLOCK = 64


# -- parameters ---------------------------------------------------------------

def block_shapes(c: TransformerConfig) -> Dict[str, Dict[str, tuple]]:
    """``{block: {leaf: (shape, logical axes, how it is drawn)}}`` of ONE
    period's blocks: ``"delta"`` with the period's ``a`` delta layers as its
    leading axis, ``"full"``."""
    d, f, hd = c.d_model, c.ff, c.hdim
    q, kv = c.n_heads * hd, c.kv_heads * hd
    a, _ = c.delta_periods
    h, vw, cw = c.delta_key_heads, c.delta_value_width, c.delta_conv_width
    tail = {
        "attn_norm": ((d,), ("norm",), "gain"),
        "mlp_norm": ((d,), ("norm",), "gain"),
        "w_gate": ((d, f), ("embed", "mlp"), ("proj", d)),
        "w_up": ((d, f), ("embed", "mlp"), ("proj", d)),
        "w_down": ((f, d), ("mlp", "embed"), ("out", f)),
    }
    delta = {
        "w_qkv": ((d, cw), ("embed", "mlp"), ("proj", d)),
        "w_g": ((d, vw), ("embed", "mlp"), ("proj", d)),
        "w_ab": ((d, 2 * h), ("embed", None), ("proj", d)),
        "conv_w": ((c.delta_conv, cw), (None, "mlp"),
                   ("proj", c.delta_conv)),
        "A_log": ((h,), (None,), "A_log"),
        "dt_bias": ((h,), (None,), "dt_bias"),
        "head_norm": ((c.delta_value_dim,), (None,), "gain"),
        "wo": ((vw, d), ("mlp", "embed"), ("out", vw)),
        **tail,
    }
    return {
        "delta": {leaf: ((a,) + shape, (None,) + axes, how)
                  for leaf, (shape, axes, how) in delta.items()},
        "full": {
            "wq": ((d, q), ("embed", "heads"), ("proj", d)),
            "wk": ((d, kv), ("embed", "kv_heads"), ("proj", d)),
            "wv": ((d, kv), ("embed", "kv_heads"), ("proj", d)),
            "q_norm": ((q,), ("heads",), "gain"),
            "k_norm": ((kv,), ("kv_heads",), "gain"),
            "wo": ((q, d), ("heads", "embed"), ("out", q)),
            **tail,
        },
    }


def segments(c: TransformerConfig):
    """``[(segment, periods, {block name in the tree: block kind})]``."""
    return [("periods", c.delta_periods[1],
             {"delta": "delta", "attn": "full"})]


def _decay_step(key, shape, c):
    """softplus^-1 of steps log-uniform in [1e-3, 0.7]: with ``A`` about 1 a
    head's decay ``alpha`` lies in 0.5-0.999, so a state carries from two to
    a thousand tokens."""
    step = jnp.exp(jax.random.uniform(key, shape, F32)
                   * (math.log(0.7) - math.log(1e-3)) + math.log(1e-3))
    return step + jnp.log(-jnp.expm1(-step))


#: the draws ``block_shapes`` names of its own (``layouts.draw``)
DRAWS = {
    "A_log": lambda key, shape, c: jax.random.normal(key, shape, F32) * 0.1,
    "dt_bias": _decay_step,
}


# -- cache ---------------------------------------------------------------------

def init_cache(c: TransformerConfig, num_blocks: int, block_size: int, *,
               state_slots: int, dtype=None) -> Params:
    dt = jnp.dtype(dtype or c.dtype)
    a, periods = c.delta_periods
    h, dk, dv = c.delta_key_heads, c.delta_key_dim, c.delta_value_dim
    r = heads_per_row(h, dv)
    kv = (periods, num_blocks, block_size, pool_heads(c.kv_heads), c.hdim)
    return {
        "k": jnp.zeros(kv, dt), "v": jnp.zeros(kv, dt),
        "conv": jnp.zeros((a * periods, state_slots,
                           (c.delta_conv - 1) * c.delta_conv_width), F32),
        "delta": jnp.zeros((a * periods, state_slots, h // r, dk, r * dv),
                           F32),
    }


def max_chunk(c: TransformerConfig):
    """The largest chunk a row may feed the step, and what it is: the block
    form runs a row's chunk as ONE block."""
    return DELTA_BLOCK, (f"the delta rule's block of {DELTA_BLOCK}: the "
                         "engine's chunk is the block of its block form")


# -- the step's layer loop ----------------------------------------------------

#: a delta layer's leaves by the stage that reads them: the projections
#: before the rule, the rule's own, the rest after it
_DELTA_BEFORE = ("w_qkv", "w_g", "w_ab")
_DELTA_RULE = ("conv_w", "A_log", "dt_bias")
_FULL_BEFORE = ("wq", "wk", "wv", "q_norm", "k_norm")
#: ... of which these come to the period as the scan's slices (indexed
#: inside the stage, the compiler relaid a whole [periods, d, d] stack in
#: every period: 88 MB copied where 29 MB are read)
_FULL_SCANNED = ("wq", "wk", "wv")


def run_layers(layers: Params, cache: Params, x, c: TransformerConfig, ctx):
    """The scanned periods over the residual stream ``x`` (``[B, C, D]``, or
    the ordered flat stream ``[1, B * C, D]`` under a budget). ``ctx`` as
    :func:`ray_tpu.models.parallel_hybrid.run_layers` takes it (``at``,
    ``stage``, ``to_rows`` / ``to_flat``, ``pos``, ``n_attend``,
    ``full_tables``, ``full_rows``). Returns ``(x, new cache, None)``: no
    expert counts."""
    from ray_tpu.models.transformer import _swiglu

    dt = jnp.dtype(c.dtype)
    eps = c.norm_eps or 1e-6
    a, periods = c.delta_periods
    h, kvh, hd = c.n_heads, c.kv_heads, c.hdim
    rep = h // kvh
    dh, dk, dv = c.delta_key_heads, c.delta_key_dim, c.delta_value_dim
    vw, cw = c.delta_value_width, c.delta_conv_width
    n_full, n_blocks, bs, kp = cache["k"].shape[:4]
    slots = cache["delta"].shape[1]
    # the pools travel as ONE pool of ``periods * n_blocks`` blocks (and
    # ``a * periods * slots`` states), carried through the scans and written
    # in place, as the uniform step's are
    flat = lambda p: p.reshape(-1, *p.shape[2:])
    dropped = n_full * n_blocks * bs
    fresh = ctx.pos == 0
    blocks = layers["periods"]
    like = lambda *tail, t=dt: jnp.zeros(x.shape[:2] + tail, t)

    def mlp(x, lp):
        with jax.named_scope("mlp"):
            m = _swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"], dt)
            return x + rms_norm(m, lp["mlp_norm"], eps=eps).astype(dt)

    def delta_layer(x, conv, pool, p, j):
        at = lambda names: {k: blocks["delta"][k][p, j] for k in names}
        li = p * a + j

        def before(_, ins):
            lp = at(_DELTA_BEFORE)
            with jax.named_scope("delta_proj"):
                proj = lambda w, t=None: jnp.einsum(
                    "bld,de->ble", ins["x"], lp[w].astype(dt),
                    preferred_element_type=t)
                return {"qkv": proj("w_qkv"), "g": proj("w_g"),
                        "ab": proj("w_ab", F32)}, None

        new, _ = ctx.stage(
            before, {"qkv": like(cw), "g": like(vw),
                     "ab": like(2 * dh, t=F32)}, {**ctx.at, "x": x})
        ab = ctx.to_rows(new["ab"])
        o, new_conv, pool = delta_rows(
            ctx.to_rows(new["qkv"]), ab[..., :dh], ab[..., dh:],
            conv[li].reshape(slots, c.delta_conv - 1, cw), pool, li * slots, at(_DELTA_RULE), ctx.n_attend, fresh,
            heads=dh, key_dim=dk, value_dim=dv,
            neg_eigval=c.delta_neg_eigval)
        conv = conv.at[li].set(new_conv.reshape(slots, -1))

        def after(x, ins):
            lp = {k: w[p, j] for k, w in blocks["delta"].items()
                  if k not in _DELTA_BEFORE + _DELTA_RULE}
            with jax.named_scope("delta_out"):
                heads = lambda v: v.astype(F32).reshape(
                    *v.shape[:2], dh, dv)
                o = heads(ins["o"])
                o = o * lax.rsqrt(jnp.mean(jnp.square(o), axis=-1,
                                           keepdims=True) + eps) \
                    * lp["head_norm"].astype(F32)
                gated = (o * jax.nn.silu(heads(ins["g"]))).reshape(
                    *o.shape[:2], vw).astype(dt)
                y = jnp.einsum("ble,ed->bld", gated, lp["wo"].astype(dt))
            x = x + rms_norm(y, lp["attn_norm"], eps=eps).astype(dt)
            return mlp(x, lp), None

        x, _ = ctx.stage(after, x, {**ctx.at, "g": new["g"],
                                    "o": ctx.to_flat(o.astype(dt))})
        return x, conv, pool

    def full_layer(x, k_pool, v_pool, p, qkv):
        def before(_, ins):
            lp = {**{k: blocks["attn"][k][p] for k in _FULL_BEFORE
                     if k not in _FULL_SCANNED}, **qkv}
            with jax.named_scope("qkv_proj"):
                proj = lambda w: jnp.einsum("bld,de->ble", ins["x"],
                                            lp[w].astype(dt))
                # the q/k norm runs over the WHOLE projection
                return {"q": rms_norm(proj("wq"), lp["q_norm"],
                                      eps=eps).astype(dt),
                        "k": rms_norm(proj("wk"), lp["k_norm"],
                                      eps=eps).astype(dt),
                        "v": proj("wv")}, None

        new, _ = ctx.stage(
            before, {"q": like(h * hd), "k": like(kvh * hd),
                     "v": like(kvh * hd)}, {**ctx.at, "x": x})
        # write BEFORE attending: a chunk's queries see its own keys. The
        # pool's head axis is ``kp`` wide (the heads past ``kvh`` stay zero)
        first = p * n_blocks
        rows = jnp.where(ctx.full_rows < 0, dropped,
                         ctx.full_rows + first * bs)
        with jax.named_scope("kv_write"):
            put = lambda pool, t: pool.at[rows // bs, rows % bs].set(
                jnp.pad(t.reshape(-1, kvh, hd),
                        ((0, 0), (0, kp - kvh), (0, 0))).astype(pool.dtype),
                mode="drop")
            k_pool, v_pool = put(k_pool, new["k"]), put(v_pool, new["v"])
        q = ctx.to_rows(new["q"])
        q = jnp.pad(q.reshape(*q.shape[:2], kvh, rep, hd),
                    ((0, 0), (0, 0), (0, kp - kvh), (0, 0), (0, 0)))
        o = paged_attention(q.reshape(*q.shape[:2], kp * rep, hd), k_pool,
                            v_pool, ctx.full_tables + first, ctx.pos,
                            ctx.n_attend, window=jnp.int32(1 << 30),
                            scale=hd ** -0.5)
        o = o.reshape(*o.shape[:2], kp, rep * hd)[:, :, :kvh].reshape(
            *o.shape[:2], h * hd)

        def after(x, ins):
            lp = {k: w[p] for k, w in blocks["attn"].items()
                  if k not in _FULL_BEFORE}
            with jax.named_scope("attn_out_proj"):
                y = jnp.einsum("ble,ed->bld", ins["o"], lp["wo"].astype(dt))
            x = x + rms_norm(y, lp["attn_norm"], eps=eps).astype(dt)
            return mlp(x, lp), None

        x, _ = ctx.stage(after, x, {**ctx.at, "o": ctx.to_flat(o)})
        return x, k_pool, v_pool

    def period(carry, inp):
        x, k_pool, v_pool, conv, pool = carry
        p, qkv = inp

        def one(inner, j):
            return delta_layer(*inner, p, j), None

        (x, conv, pool), _ = lax.scan(one, (x, conv, pool), jnp.arange(a))
        x, k_pool, v_pool = full_layer(x, k_pool, v_pool, p, qkv)
        return (x, k_pool, v_pool, conv, pool), None

    (x, k_pool, v_pool, conv, pool), _ = lax.scan(
        period, (x, flat(cache["k"]), flat(cache["v"]), cache["conv"],
                 flat(cache["delta"])),
        (jnp.arange(periods), {k: blocks["attn"][k] for k in _FULL_SCANNED}))
    return x, {"k": k_pool.reshape(cache["k"].shape),
               "v": v_pool.reshape(cache["v"].shape), "conv": conv,
               "delta": pool.reshape(cache["delta"].shape)}, None


#: what :func:`count` counts of a step (``layouts.StepRows``), by the rule the
#: program applies (``ops/delta_rule.py::delta_rows``): positions the rows
#: fed the rule and positions it computed for them (a row that feeds one
#: takes one turn; a row that feeds more takes the block form over the whole
#: chunk), the rows that took one turn and the rows that took a block
COUNTERS = ("delta_positions_real", "delta_positions_run",
            "delta_rows_stepped", "delta_rows_blocked")


def count(c: TransformerConfig, step) -> Dict[str, int]:
    single = int((step.nvalid == 1).sum())
    blocked = int((step.nvalid > 1).sum())
    return {"delta_positions_real": int(step.nvalid.sum()),
            "delta_positions_run": single + blocked * step.chunk,
            "delta_rows_stepped": single, "delta_rows_blocked": blocked}
