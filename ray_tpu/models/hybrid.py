"""The hybrid state-space / attention decoder (SambaY layout,
``TransformerConfig.layer_kinds``) on the paged serve step: its parameter
tree, its cache pools and the step's layer loop. Its state-space layers are
Mamba-1 (``ops/ssm.py::ssm_rows``: a decay a channel, a ``[16, d_inner]``
state walked position by position), and a layer is EITHER a state-space
layer OR an attention layer. The layout whose every layer runs attention and
a Mamba-2 mixer side by side (``ops/ssm.py::mamba2_rows``, the block form) is
:mod:`ray_tpu.models.parallel_hybrid`.

Five kinds of layer in three segments, each segment ONE scanned period so
that the step program does not unroll the stack::

    "self"   (mamba, window) x a     state + a windowed KV pool a layer
    "mid"    (mamba, full)           state + THE pool of the cross-decoder;
                                     the mamba layer's scan output is the
                                     memory ``m`` of every layer after it
    "cross"  (gmu, cross) x b        nothing of their own: ``m`` and the
                                     full layer's K and V

Parameters: ``params["layers"][segment][block]`` with ``block`` one of
``"mamba"``, ``"attn"``, ``"gmu"``, every leaf stacked over the segment's
periods. Every block carries its pre-norm (``attn_norm``), its mixer and its
MLP (``mlp_norm``, ``w_gate``, ``w_up``, ``w_down``: the one
``_decode_mlp``). Matrices are stored two-dimensional (``wq [d, h * hd]``,
pools ``[.., bs, kvh * hd]``): with 64-wide heads a ``[.., h, hd]`` array is
half padding in tiled memory.

Cache pools (``init_cache``), by KIND of layer, each with its own ids:

- ``"k"``, ``"v"`` ``[1, num_blocks, bs, kvh * hd]``: the full layer's, read
  by it and by the ``b`` cross layers; a request's table is as wide as its
  context;
- ``"wk"``, ``"wv"`` ``[a, window_blocks, bs, kvh * hd]``: the window
  layers'; a request's table holds only its live window
  (:func:`window_table_width` blocks at most), the same ids in every window
  layer;
- ``"conv"`` ``[a + 1, slots, k - 1, d_inner]`` and ``"ssm"`` ``[a + 1,
  slots, n, d_inner]``, float32: the state-space layers', indexed by the
  engine's SLOT, not by block.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.config import TransformerConfig
from ray_tpu.ops.diff_attention import paged_diff_attention
from ray_tpu.ops.ssm import gmu, ssm_rows

Params = Dict[str, Any]
F32 = jnp.float32

#: what refuses the layout anywhere but on the paged serve step
SERVE_ONLY = ("the SambaY hybrid state-space / attention layout (layer_kinds: "
              "mamba, window, full, gmu, cross)")


def window_table_width(window: int, chunk: int, block_size: int) -> int:
    """Blocks a window layer's table holds a row: the keys the first query
    of a chunk may see (``window`` back from it) to the chunk's last, a
    span of ``window + chunk - 1`` tokens wherever it starts."""
    return (window + chunk + block_size - 3) // block_size + 1


def lambda_init(layer):
    """Differential attention's ``lam_init`` of (absolute) layer ``layer``."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, F32))


# -- parameters ---------------------------------------------------------------

def block_shapes(c: TransformerConfig) -> Dict[str, Dict[str, tuple]]:
    """``{block: {leaf: (shape, logical axes, how it is drawn)}}`` of ONE
    layer of each block kind: the one place that knows the tree. Drawn as
    ``"proj"`` | ``"out"`` (normal at the usual scales), ``"gain"``
    (about 1), ``"bias"`` (about 0), or a name of its own."""
    d, f, hd, di = c.d_model, c.ff, c.hdim, c.d_inner
    q, kv = c.n_heads * hd, c.kv_heads * hd
    n, r, k = c.ssm_state, c.dt_rank, c.ssm_conv
    norm_mlp = {
        "attn_norm": ((d,), ("norm",), "gain"),
        "attn_norm_b": ((d,), ("norm",), "bias"),
        "mlp_norm": ((d,), ("norm",), "gain"),
        "mlp_norm_b": ((d,), ("norm",), "bias"),
        "w_gate": ((d, f), ("embed", "mlp"), "proj"),
        "w_up": ((d, f), ("embed", "mlp"), "proj"),
        "w_down": ((f, d), ("mlp", "embed"), "out"),
    }
    diff = {
        "lam_q1": ((hd,), (None,), "lambda"),
        "lam_k1": ((hd,), (None,), "lambda"),
        "lam_q2": ((hd,), (None,), "lambda"),
        "lam_k2": ((hd,), (None,), "lambda"),
        "subln": ((2 * hd,), (None,), "gain"),
    }
    query = {
        "wq": ((d, q), ("embed", "heads"), "proj"),
        "bq": ((q,), ("heads",), "bias"),
        "wo": ((q, d), ("heads", "embed"), "out"),
        "bo": ((d,), ("norm",), "bias"),
    }
    return {
        "mamba": {
            **norm_mlp,
            "w_in": ((d, 2 * di), ("embed", "mlp"), "proj"),
            "conv_w": ((k, di), (None, "mlp"), "conv"),
            "conv_b": ((di,), ("mlp",), "bias"),
            "w_x": ((di, r + 2 * n), ("mlp", None), "proj_inner"),
            "w_dt": ((r, di), (None, "mlp"), "dt"),
            "b_dt": ((di,), ("mlp",), "dt_bias"),
            "A_log": ((n, di), (None, "mlp"), "A_log"),
            "D": ((di,), ("mlp",), "gain"),
            "w_out": ((di, d), ("mlp", "embed"), "out_inner"),
        },
        "attn": {
            **norm_mlp, **query, **diff,
            "wk": ((d, kv), ("embed", "kv_heads"), "proj"),
            "bk": ((kv,), ("kv_heads",), "bias"),
            "wv": ((d, kv), ("embed", "kv_heads"), "proj"),
            "bv": ((kv,), ("kv_heads",), "bias"),
        },
        "cross": {**norm_mlp, **query, **diff},
        "gmu": {
            **norm_mlp,
            "w1": ((d, di), ("embed", "mlp"), "proj"),
            "w2": ((di, d), ("mlp", "embed"), "out_inner"),
        },
    }


def segments(c: TransformerConfig):
    """``[(segment, periods, {block name in the tree: block kind})]``."""
    a, b = c.hybrid_periods
    return [("self", a, {"mamba": "mamba", "attn": "attn"}),
            ("mid", 1, {"mamba": "mamba", "attn": "attn"}),
            ("cross", b, {"gmu": "gmu", "attn": "cross"})]


def _normal(std):
    """A normal draw at the width ``std(config)``."""
    return lambda key, shape, c: \
        jax.random.normal(key, shape, F32) * std(c) + 0.0


def _a_log(key, shape, c):
    # Mamba's S4D-real start, log(1..n) down the states, with a draw
    return jnp.log(jnp.arange(1, shape[0] + 1, dtype=F32))[:, None] \
        + _normal(lambda c: 0.1)(key, shape, c)


def dt_bias(key, shape, c):
    """softplus^-1 of steps spread log-uniformly over [1e-3, 1e-1]."""
    step = jnp.exp(jax.random.uniform(key, shape, F32)
                   * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
    return step + jnp.log(-jnp.expm1(-step))


#: the draws ``block_shapes`` names beside ``"gain"`` and ``"bias"``
#: (``layouts.draw``): ``name -> (key, shape, config) -> float32``. Every
#: gain, bias, ``A_log``, ``D``, ``b_dt`` and lambda away from its trivial
#: value, so that leaving one out shows in the logits.
DRAWS = {
    "proj": _normal(lambda c: c.d_model ** -0.5),
    "out": _normal(lambda c: c.d_model ** -0.5 / (2 * c.n_layers) ** 0.5),
    "proj_inner": _normal(lambda c: c.d_inner ** -0.5),
    "out_inner": _normal(
        lambda c: c.d_inner ** -0.5 / (2 * c.n_layers) ** 0.5),
    "dt": _normal(lambda c: c.dt_rank ** -0.5),
    "conv": _normal(lambda c: c.ssm_conv ** -0.5),
    "lambda": _normal(lambda c: 0.3),
    "A_log": _a_log,
    "dt_bias": dt_bias,
}


# -- cache ---------------------------------------------------------------------

def init_cache(c: TransformerConfig, num_blocks: int, block_size: int, *,
               window_blocks: int, state_slots: int, dtype=None) -> Params:
    dt = jnp.dtype(dtype or c.dtype)
    a, _ = c.hybrid_periods
    kv = c.kv_heads * c.hdim
    return {
        "k": jnp.zeros((1, num_blocks, block_size, kv), dt),
        "v": jnp.zeros((1, num_blocks, block_size, kv), dt),
        "wk": jnp.zeros((a, window_blocks, block_size, kv), dt),
        "wv": jnp.zeros((a, window_blocks, block_size, kv), dt),
        "conv": jnp.zeros((a + 1, state_slots, c.ssm_conv - 1, c.d_inner),
                          F32),
        "ssm": jnp.zeros((a + 1, state_slots, c.ssm_state, c.d_inner), F32),
    }


# -- the step's layer loop ----------------------------------------------------

def run_layers(layers: Params, cache: Params, x, c: TransformerConfig, ctx):
    """The three segments over the residual stream ``x`` (``[B, C, D]``, or
    the ordered flat stream ``[1, B * C, D]`` under a budget). ``ctx``
    (a namespace made by ``_step_paged_impl``): ``at`` (what a position-wise
    stage reads of each position), ``stage(fn, state, ins)`` (``fn`` over
    the stream's real positions), ``to_rows`` / ``to_flat`` (between the
    stream's order and ``[B, C]``), ``pos``, ``n_attend``, ``full_tables``,
    ``win_tables``, ``win_pos`` (a row's position in its window table's own
    numbering), ``full_rows`` / ``win_rows`` (each position's token row in
    ONE layer's pool; dropped positions negative), ``decode_mlp(x, lp,
    valid) -> (x, None)``. Returns ``(x, new cache, None)``: no expert
    counts."""
    from ray_tpu.models.transformer import _norm

    dt = jnp.dtype(c.dtype)
    a, b = c.hybrid_periods
    bs = cache["k"].shape[2]
    nb_win = cache["wk"].shape[1]
    eps = c.norm_eps or 1e-5
    di = c.d_inner
    geometry = dict(heads=c.n_heads, kv_heads=c.kv_heads, eps=eps)
    fresh = ctx.pos == 0

    def write(pool, new, rows):
        with jax.named_scope("kv_write"):
            return pool.at[rows // bs, rows % bs].set(
                new.reshape(-1, new.shape[-1]).astype(pool.dtype),
                mode="drop")

    def index(tree, i):
        """Period ``i`` of a segment's block, sliced where it is used: what
        a scan slices for a stage crosses the stage's branch as a copy."""
        return jax.tree.map(lambda w: w[i], tree)

    def lam_of(lp):
        f = lambda n: lp[n].astype(F32)
        return jnp.exp(jnp.sum(f("lam_q1") * f("lam_k1"))) \
            - jnp.exp(jnp.sum(f("lam_q2") * f("lam_k2")))

    def mlp(x, lp, at):
        return ctx.decode_mlp(x, lp, at["valid"])[0]

    def mamba_layer(x, block, i, state_i, conv, ssm):
        """-> (x, conv, ssm, y): ``y`` the scan's output in the stream's
        order (the memory, where the layer is the last of its kind)."""
        def before(_, ins):
            lp = index(block, i)
            h = _norm(ins["x"], lp["attn_norm"], lp["attn_norm_b"], c)
            uz = jnp.einsum("bld,de->ble", h, lp["w_in"].astype(dt))
            return {"u": uz[..., :di], "z": uz[..., di:]}, None

        like = {"u": jnp.zeros(x.shape[:2] + (di,), dt),
                "z": jnp.zeros(x.shape[:2] + (di,), dt)}
        uz, _ = ctx.stage(before, like, {**ctx.at, "x": x})
        lp = index({k: block[k] for k in (
            "conv_w", "conv_b", "w_x", "w_dt", "b_dt", "A_log", "D")}, i)
        y, new_conv, new_ssm = ssm_rows(
            ctx.to_rows(uz["u"]), conv[state_i], ssm[state_i], lp,
            ctx.n_attend, fresh)
        conv = conv.at[state_i].set(new_conv)
        ssm = ssm.at[state_i].set(new_ssm)
        y = ctx.to_flat(y.astype(dt))

        def after(x, ins):
            lp = index(block, i)
            gated = (ins["y"].astype(F32)
                     * jax.nn.silu(ins["z"].astype(F32))).astype(dt)
            x = x + jnp.einsum("ble,ed->bld", gated, lp["w_out"].astype(dt))
            return mlp(x, lp, ins), None

        x, _ = ctx.stage(after, x, {**ctx.at, "y": y, "z": uz["z"]})
        return x, conv, ssm, y

    def attention_layer(x, block, i, layer, kv, window):
        """A window, full or cross layer. ``kv``: the pools it reads as (k
        pool, v pool, token rows, tables); ``token rows`` ``None`` in a
        cross layer, which writes nothing of its own. -> (x, pools)."""
        k_pool, v_pool, rows, tables = kv
        own = rows is not None

        def before(_, ins):
            lp = index(block, i)
            with jax.named_scope("qkv_proj"):
                h = _norm(ins["x"], lp["attn_norm"], lp["attn_norm_b"], c)
                proj = lambda w, bias: jnp.einsum(
                    "bld,de->ble", h, lp[w].astype(dt)) + lp[bias].astype(dt)
                out = {"q": proj("wq", "bq")}
                if own:
                    out.update(k=proj("wk", "bk"), v=proj("wv", "bv"))
            return out, None

        width = lambda n: jnp.zeros(x.shape[:2] + (n * c.hdim,), dt)
        like = {"q": width(c.n_heads)}
        if own:
            like.update(k=width(c.kv_heads), v=width(c.kv_heads))
        new, _ = ctx.stage(before, like, {**ctx.at, "x": x})
        lp = index({k: block[k] for k in (
            "lam_q1", "lam_k1", "lam_q2", "lam_k2", "subln")}, i)
        init = lambda_init(layer)
        # the scope holds what the layer does with its pool: the write of
        # the step's keys, the attention through the table
        with jax.named_scope(
                "window_attention" if window else "shared_kv_attention"):
            if own:
                # write BEFORE attending: a chunk's queries see its own keys
                k_pool = write(k_pool, new["k"], rows)
                v_pool = write(v_pool, new["v"], rows)
            o = paged_diff_attention(
                ctx.to_rows(new["q"]), k_pool, v_pool, tables,
                ctx.win_pos if window else ctx.pos, ctx.n_attend,
                lam_of(lp) + init, init, lp["subln"], window=window,
                **geometry)

        def after(x, ins):
            lp = index(block, i)
            with jax.named_scope("attn_out_proj"):
                x = x + jnp.einsum("ble,ed->bld", ins["o"],
                                   lp["wo"].astype(dt)) + lp["bo"].astype(dt)
            return mlp(x, lp, ins), None

        x, _ = ctx.stage(after, x, {**ctx.at, "o": ctx.to_flat(o)})
        return x, (k_pool, v_pool)

    def gmu_layer(x, block, i, memory):
        def whole(x, ins):
            lp = index(block, i)
            h = _norm(x, lp["attn_norm"], lp["attn_norm_b"], c)
            x = x + gmu(h, ins["m"], lp["w1"], lp["w2"])
            return mlp(x, lp, ins), None

        return ctx.stage(whole, x, {**ctx.at, "m": memory})[0]

    # the window pools travel as ONE pool of ``a * window_blocks`` blocks
    # (window layer ``i`` owns ``[i * nb_win, (i + 1) * nb_win)``), carried
    # through the scan and written in place, as the uniform step's are
    flat = lambda p: p.reshape(-1, *p.shape[2:])
    wk, wv = flat(cache["wk"]), flat(cache["wv"])
    dropped_win = wk.shape[0] * bs
    dropped_full = cache["k"].shape[1] * bs
    conv, ssm = cache["conv"], cache["ssm"]

    def self_period(carry, i):
        x, wk, wv, conv, ssm = carry
        x, conv, ssm, _ = mamba_layer(x, layers["self"]["mamba"], i, i,
                                      conv, ssm)
        rows = jnp.where(ctx.win_rows < 0, dropped_win,
                         ctx.win_rows + i * nb_win * bs)
        x, (wk, wv) = attention_layer(
            x, layers["self"]["attn"], i, 2 * i + 1,
            (wk, wv, rows, ctx.win_tables + i * nb_win), c.sliding_window)
        return (x, wk, wv, conv, ssm), None

    (x, wk, wv, conv, ssm), _ = lax.scan(
        self_period, (x, wk, wv, conv, ssm), jnp.arange(a))

    x, conv, ssm, memory = mamba_layer(x, layers["mid"]["mamba"], 0, a,
                                       conv, ssm)
    rows = jnp.where(ctx.full_rows < 0, dropped_full, ctx.full_rows)
    x, (k_pool, v_pool) = attention_layer(
        x, layers["mid"]["attn"], 0, 2 * a + 1,
        (cache["k"][0], cache["v"][0], rows, ctx.full_tables), 0)

    def cross_period(x, i):
        x = gmu_layer(x, layers["cross"]["gmu"], i, memory)
        # the full layer's pools, as it left them, through its table
        x, _ = attention_layer(
            x, layers["cross"]["attn"], i, 2 * a + 3 + 2 * i,
            (k_pool, v_pool, None, ctx.full_tables), 0)
        return x, None

    x, _ = lax.scan(cross_period, x, jnp.arange(b))
    return x, {"k": k_pool[None], "v": v_pool[None],
               "wk": wk.reshape(cache["wk"].shape),
               "wv": wv.reshape(cache["wv"].shape),
               "conv": conv, "ssm": ssm}, None


def pool_layers(c: TransformerConfig):
    """``(layers that read the window pool, layers that read the pool a whole
    table names)``: the full layer and the cross layers after it."""
    a, b = c.hybrid_periods
    return a, b + 1
