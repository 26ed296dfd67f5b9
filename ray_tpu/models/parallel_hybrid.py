"""The parallel attention / Mamba-2 decoder (Falcon-H1 layout:
``TransformerConfig.layer_kinds`` all ``"parallel"``) on the paged serve
step: its parameter tree, its cache pools and the step's layer loop.

ONE kind of layer, ONE scanned period. A layer's attention heads (RoPE, GQA,
no biases) and its Mamba-2 heads read the same normed input side by side,
their scaled outputs are summed into the residual, and the MLP follows::

    h   = RMSNorm(x)
    att = (Attn((h a_in) Wq, ((h a_in) Wk) a_key, (h a_in) Wv) Wo) a_out
    z | xBC | dt = ((h s_in) W_in) * mup          mup: a factor a slice
    ssm = (GatedNorm(SSD(conv(xBC), dt), z) W_out) s_out
    x   = x + att + ssm
    x   = x + (SiLU((m W_gate) m_gate) * (m W_up)) W_down m_down,  m = RMSNorm(x)

with the fixed multipliers of the configuration (``a_in`` =
``attention_in_multiplier`` and so on). The recurrence is Mamba-2
(:func:`ray_tpu.ops.ssm.mamba2_rows`: the block form for a row that prefills,
one turn for a row that decodes); :mod:`ray_tpu.models.hybrid` is the SambaY
layout, whose state-space layers are Mamba-1.

Parameters: ``params["layers"][leaf]``, every leaf stacked over the layers,
matrices two-dimensional as they are multiplied (``wq [d, h * hd]``). The
Mamba-2 in-projection (published as ONE matrix of ``2 d_ssm + 2 G N + H``
columns, 9248 at Falcon-H1-34B's widths) is held as its three column blocks
``w_ssm_z | w_ssm_xbc | w_ssm_dt``: 9248 is not a whole number of the 128
lanes, and the compiler copied the whole stack into a padded layout every
step (568 MB); the blocks are whole lanes (the 32-wide ``dt`` block pads to
one tile), and the function is the same.

Cache pools (``init_cache``): a layer of this kind holds KV blocks AND a
state slot, and there is no window pool:

- ``"k"``, ``"v"`` ``[L, num_blocks, bs, kvh, hd]``: the uniform decoders'
  layout, read through the block table by
  :func:`ray_tpu.ops.paged_attention.paged_attention` exactly as they read
  it (the Pallas kernel on a TPU, the ``jax.numpy`` form elsewhere);
- ``"conv"`` ``[L, slots, k - 1, d_ssm + 2 G N]`` and ``"ssm"`` ``[L, slots,
  H, P, N]``, float32, indexed by the engine's SLOT: the last conv inputs
  and Mamba-2's state (``N`` on the lanes), zeroed by the step for a row at
  position 0.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.config import TransformerConfig
from ray_tpu.models.hybrid import dt_bias
from ray_tpu.ops.layers import apply_rotary, rms_norm
from ray_tpu.ops.paged_attention import paged_attention
from ray_tpu.ops.ssm import gated_rms_norm, mamba2_rows

Params = Dict[str, Any]
F32 = jnp.float32


#: what refuses the layout anywhere but on the paged serve step
SERVE_ONLY = ("the parallel attention / Mamba-2 layout (layer_kinds all "
              "'parallel')")


# -- parameters ---------------------------------------------------------------

def block_shapes(c: TransformerConfig) -> Dict[str, tuple]:
    """``{leaf: (shape, logical axes, how it is drawn)}`` of ONE layer: the
    one place that knows the tree. Drawn as ``("proj", fan_in)`` | ``("out",
    fan_in)`` (normal at ``fan_in^-0.5``, output projections over ``sqrt(2
    L)``), ``"gain"`` (about 1), ``"bias"`` (about 0), or a name of its
    own."""
    d, f, hd, ds = c.d_model, c.ff, c.hdim, c.d_inner
    q, kv = c.n_heads * hd, c.kv_heads * hd
    cw, hs = c.ssm_conv_width, c.ssm_heads
    return {
        "attn_norm": ((d,), ("norm",), "gain"),
        "wq": ((d, q), ("embed", "heads"), ("proj", d)),
        "wk": ((d, kv), ("embed", "kv_heads"), ("proj", d)),
        "wv": ((d, kv), ("embed", "kv_heads"), ("proj", d)),
        "wo": ((q, d), ("heads", "embed"), ("out", q)),
        "w_ssm_z": ((d, ds), ("embed", "mlp"), ("proj", d)),
        "w_ssm_xbc": ((d, cw), ("embed", "mlp"), ("proj", d)),
        "w_ssm_dt": ((d, hs), ("embed", None), ("proj", d)),
        "conv_w": ((c.ssm_conv, cw), (None, "mlp"), ("proj", c.ssm_conv)),
        "conv_b": ((cw,), ("mlp",), "bias"),
        "dt_bias": ((hs,), (None,), "dt_bias"),
        "A_log": ((hs,), (None,), "A_log"),
        "D": ((hs,), (None,), "gain"),
        "ssm_norm": ((ds,), ("mlp",), "gain"),
        "w_ssm_out": ((ds, d), ("mlp", "embed"), ("out", ds)),
        "mlp_norm": ((d,), ("norm",), "gain"),
        "w_gate": ((d, f), ("embed", "mlp"), ("proj", d)),
        "w_up": ((d, f), ("embed", "mlp"), ("proj", d)),
        "w_down": ((f, d), ("mlp", "embed"), ("out", f)),
    }


#: the draws ``block_shapes`` names of its own (``layouts.draw``): ``A_log``
#: the log of 1..16 spread over the heads and ``dt_bias`` the inverse softplus
#: of a step log-uniform in [1e-3, 1e-1] (Mamba-2's own starts: the state then
#: carries over hundreds of tokens)
DRAWS = {
    "A_log": lambda key, shape, c: jnp.log(
        jax.random.uniform(key, shape, F32, 1.0, 16.0)),
    "dt_bias": dt_bias,
}


# -- cache ---------------------------------------------------------------------

def init_cache(c: TransformerConfig, num_blocks: int, block_size: int, *,
               state_slots: int, dtype=None) -> Params:
    dt = jnp.dtype(dtype or c.dtype)
    kv = (c.n_layers, num_blocks, block_size, c.kv_heads, c.hdim)
    return {
        "k": jnp.zeros(kv, dt), "v": jnp.zeros(kv, dt),
        "conv": jnp.zeros((c.n_layers, state_slots, c.ssm_conv - 1,
                           c.ssm_conv_width), F32),
        "ssm": jnp.zeros((c.n_layers, state_slots, c.ssm_heads,
                          c.ssm_head_dim, c.ssm_state), F32),
    }


# -- the step's layer loop ----------------------------------------------------

#: a layer's leaves that the stage before its mixers multiplies by: those it
#: indexes out of their stacks itself, and ``wq``, ``wk``, ``wv``, which come
#: to it as the scan's slices (indexed inside the stage the compiler relaid
#: their whole stacks before the loop, 220 MB of temporaries for the same
#: bytes copied); those of the scan between the stages; the rest are the
#: stage's after
_BEFORE = ("attn_norm", "w_ssm_z", "w_ssm_xbc", "w_ssm_dt")
_QKV = ("wq", "wk", "wv")
_SCAN = ("conv_w", "conv_b", "dt_bias", "A_log", "D")


def max_chunk(c: TransformerConfig):
    """The largest chunk a row may feed the step, and what it is: the block
    form runs a row's chunk as ONE block."""
    return c.ssm_chunk, (f"the layout's ssm_chunk {c.ssm_chunk}: the engine's "
                         "chunk is the block of Mamba-2's block form")


def mup_vectors(c: TransformerConfig):
    """The in-projection's factor a column, by column block (z, x | B | C,
    dt): ``ssm_multipliers`` over the slices z, x, B, C, dt."""
    gn = c.ssm_groups * c.ssm_state
    m = c.ssm_mup
    full = lambda n, v: jnp.full((n,), v, F32)
    return (full(c.d_inner, m[0]),
            jnp.concatenate([full(c.d_inner, m[1]), full(gn, m[2]),
                             full(gn, m[3])]),
            full(c.ssm_heads, m[4]))


def run_layers(layers: Params, cache: Params, x, c: TransformerConfig, ctx):
    """The one scanned period over the residual stream ``x`` (``[B, C, D]``,
    or the ordered flat stream ``[1, B * C, D]`` under a budget). ``ctx`` (a
    namespace made by ``_step_paged_impl``, as
    :func:`ray_tpu.models.hybrid.run_layers` takes it): ``at``, ``stage``,
    ``to_rows`` / ``to_flat``, ``pos``, ``n_attend``, ``full_tables``,
    ``full_rows`` (each position's token row in ONE layer's pool; dropped
    positions negative), ``decode_mlp(x, lp, valid) -> (x, None)``. Returns
    ``(x, new cache, None)``: no expert counts."""
    dt = jnp.dtype(c.dtype)
    eps = c.norm_eps or 1e-6
    h, kvh, hd, ds = c.n_heads, c.kv_heads, c.hdim, c.d_inner
    cw = c.ssm_conv_width
    n_layers, n_blocks, bs = cache["k"].shape[:3]
    slots = cache["ssm"].shape[1]
    # the pools travel as ONE pool of ``n_layers * n_blocks`` blocks (and
    # ``n_layers * slots`` states), carried through the scan and written in
    # place, as the uniform step's are
    flat = lambda p: p.reshape(-1, *p.shape[2:])
    dropped = n_layers * n_blocks * bs
    fresh = ctx.pos == 0
    mup_z, mup_xbc, mup_dt = mup_vectors(c)

    def index(names, i):
        """Layer ``i`` of the named leaves, sliced where they are used: what
        a scan slices for a stage crosses the stage's branch as a copy."""
        return {k: layers[k][i] for k in names}

    def layer(carry, inp):
        x, k_pool, v_pool, conv, ssm = carry
        i, qkv = inp

        def before(_, ins):
            lp = {**index(_BEFORE, i), **qkv}
            hx = rms_norm(ins["x"], lp["attn_norm"], eps=eps)
            with jax.named_scope("qkv_proj"):
                ha = hx * c.attention_in_multiplier
                proj = lambda w: jnp.einsum("bld,de->ble", ha,
                                            lp[w].astype(dt))
                heads = lambda a, n: a.reshape(*a.shape[:2], n, hd)
                q, k, v = (heads(proj("wq"), h),
                           heads(proj("wk") * c.key_multiplier, kvh),
                           heads(proj("wv"), kvh))
            with jax.named_scope("rope"):
                q = apply_rotary(q, ins["cos"], ins["sin"])
                k = apply_rotary(k, ins["cos"], ins["sin"])
            with jax.named_scope("mamba2_in_proj"):
                hs = hx * c.ssm_in_multiplier
                block = lambda w, mup: (jnp.einsum(
                    "bld,de->ble", hs, lp[w].astype(dt),
                    preferred_element_type=F32) * mup).astype(dt)
                z, xbc, step = (block("w_ssm_z", mup_z),
                                block("w_ssm_xbc", mup_xbc),
                                block("w_ssm_dt", mup_dt))
            return {"q": q, "k": k, "v": v, "z": z, "xbc": xbc,
                    "dt": step}, None

        like = lambda *tail: jnp.zeros(x.shape[:2] + tail, dt)
        new, _ = ctx.stage(
            before,
            {"q": like(h, hd), "k": like(kvh, hd), "v": like(kvh, hd),
             "z": like(ds), "xbc": like(cw), "dt": like(c.ssm_heads)},
            {**ctx.at, "x": x})
        # write BEFORE attending: a chunk's queries see its own keys
        first = i * n_blocks
        rows = jnp.where(ctx.full_rows < 0, dropped,
                         ctx.full_rows + first * bs)
        with jax.named_scope("kv_write"):
            put = lambda pool, a: pool.at[rows // bs, rows % bs].set(
                a.reshape(-1, kvh, hd).astype(pool.dtype), mode="drop")
            k_pool, v_pool = put(k_pool, new["k"]), put(v_pool, new["v"])
        o = paged_attention(ctx.to_rows(new["q"]), k_pool, v_pool,
                            ctx.full_tables + first, ctx.pos, ctx.n_attend,
                            window=jnp.int32(1 << 30), scale=hd ** -0.5)
        y, new_conv, ssm = mamba2_rows(
            ctx.to_rows(new["xbc"]), ctx.to_rows(new["dt"]), conv[i], ssm,
            i * slots, index(_SCAN, i), ctx.n_attend, fresh,
            heads=c.ssm_heads, head_dim=c.ssm_head_dim, groups=c.ssm_groups,
            states=c.ssm_state)
        conv = conv.at[i].set(new_conv)

        def after(x, ins):
            lp = {k: w[i] for k, w in layers.items()
                  if k not in _BEFORE + _QKV + _SCAN}
            with jax.named_scope("attn_out_proj"):
                o = ins["o"].reshape(*ins["o"].shape[:2], h * hd)
                att = jnp.einsum("ble,ed->bld", o, lp["wo"].astype(dt)) \
                    * c.attention_out_multiplier
            normed = gated_rms_norm(ins["y"], ins["z"], lp["ssm_norm"],
                                    groups=c.ssm_groups, eps=eps).astype(dt)
            with jax.named_scope("mamba2_out_proj"):
                mix = jnp.einsum("ble,ed->bld", normed,
                                 lp["w_ssm_out"].astype(dt)) \
                    * c.ssm_out_multiplier
            return ctx.decode_mlp(x + att.astype(dt) + mix.astype(dt), lp,
                                  ins["valid"])

        x, _ = ctx.stage(after, x, {
            **ctx.at, "o": ctx.to_flat(o), "z": new["z"],
            "y": ctx.to_flat(y.astype(dt))})
        return (x, k_pool, v_pool, conv, ssm), None

    (x, k_pool, v_pool, conv, ssm), _ = lax.scan(
        layer, (x, flat(cache["k"]), flat(cache["v"]), cache["conv"],
                flat(cache["ssm"])),
        (jnp.arange(n_layers), {k: layers[k] for k in _QKV}))
    return x, {"k": k_pool.reshape(cache["k"].shape),
               "v": v_pool.reshape(cache["v"].shape), "conv": conv,
               "ssm": ssm.reshape(cache["ssm"].shape)}, None


#: what :func:`count` counts of a step (``layouts.StepRows``), by the rule the
#: program applies (``ops/ssm.py::mamba2_rows``): positions the rows fed the
#: mixer and positions it computed for them (a row that feeds one takes one
#: turn of the recurrence; a row that feeds more takes the block form over the
#: whole chunk, so a 17-token tail run as a 32 block is 15 positions for
#: nothing); the rows that fed the mixers one position and those of them whose
#: turn the kernel that walks the live rows' states took
#: (``kernels["ssd_impl"]``: all or none, the form the program was traced
#: with)
COUNTERS = ("ssd_positions_real", "ssd_positions_run", "ssd_rows_stepped",
            "ssd_kernel_rows")


def count(c: TransformerConfig, step) -> Dict[str, int]:
    single = int((step.nvalid == 1).sum())
    return {"ssd_positions_real": int(step.nvalid.sum()),
            "ssd_positions_run":
                single + (len(step.nvalid) - single) * step.chunk,
            "ssd_rows_stepped": single,
            "ssd_kernel_rows":
                single * (step.kernels["ssd_impl"] == "pallas")}
