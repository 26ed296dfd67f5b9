"""The latent-attention MoE decoder (the DeepSeek-V3 layer:
``TransformerConfig.kv_lora_rank``) on the paged serve step: its parameter
tree, its latent cache pool and the step's layer loop.

Two kinds of layer in two segments, each ONE scanned layer so that the step
program does not unroll the stack::

    "dense"  x dense_layers       MLA, then a SwiGLU MLP of width d_ff
    "moe"    x the rest           MLA, then the routed experts HELD here
                                  (``experts_held`` of ``num_experts``,
                                  ``ops/moe.py``) beside the shared expert

Every layer is ``x + Attn(RMSNorm(x))`` then ``x + FFN(RMSNorm(x))``.
Parameters: ``params["layers"][segment][leaf]``, every leaf stacked over the
segment's layers. The attention's matrices are stored as they are multiplied
(``block_shapes``): ``w_uk [H, nope, rank]`` and ``w_uv [H, rank, v]`` are
the two halves of the published ``kv_b_proj``, folded into the query and the
output (:mod:`ray_tpu.ops.latent_attention`).

The cache is ONE pool, ``"kv" [n_layers, num_blocks, bs, pool_width(rank +
rope)]``: a token's normed latent and its rotated key (576 values in whole
lanes, 640: :mod:`ray_tpu.ops.latent_attention` says why), dense layers
first. A block is a block: the prefix cache, copy-on-write, export and
adoption move it like any other pool of the dict.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.config import TransformerConfig
from ray_tpu.ops.latent_attention import (paged_latent_attention, pool_width,
                                          yarn_rotary)
from ray_tpu.ops.layers import apply_rotary, rms_norm

Params = Dict[str, Any]
F32 = jnp.float32


#: what refuses the layout anywhere but on the paged serve step
SERVE_ONLY = ("latent attention (kv_lora_rank) with its dense and expert "
              "layers")


def rope_tables(positions, c: TransformerConfig):
    """cos/sin of the rotated query and key at ``positions`` (YaRN)."""
    return yarn_rotary(
        positions, c.qk_rope_head_dim, theta=c.rope_theta,
        factor=c.rope_factor, beta_fast=c.rope_beta_fast,
        beta_slow=c.rope_beta_slow, original_len=c.rope_original_len,
        mscale=c.rope_mscale, mscale_all_dim=c.rope_mscale_all_dim)


def softmax_scale(c: TransformerConfig) -> float:
    return (c.qk_nope_head_dim + c.qk_rope_head_dim) ** -0.5 \
        * c.rope_softmax_mscale


# -- parameters ---------------------------------------------------------------

def block_shapes(c: TransformerConfig) -> Dict[str, Dict[str, tuple]]:
    """``{segment: {leaf: (shape, logical axes, how it is drawn)}}`` of ONE
    layer of each segment: the one place that knows the tree. Drawn as
    ``"proj"`` | ``"out"`` (normal at ``fan_in^-0.5``, output projections
    over ``sqrt(2 L)``), ``"gain"`` (about 1) or ``"bias"`` (about 0); the
    number beside a name is the fan-in."""
    d, h = c.d_model, c.n_heads
    qr, kr = c.q_lora_rank, c.kv_lora_rank
    nope, rope, v = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
    f, fe, fs = c.ff, c.ff_expert, c.ff_expert * c.shared_experts
    e = c.held_experts
    attn = {
        "attn_norm": ((d,), ("norm",), "gain"),
        "wq_a": ((d, qr), ("embed", None), ("proj", d)),
        "q_norm": ((qr,), (None,), "gain"),
        "wq_b": ((qr, h * (nope + rope)), (None, "heads"), ("proj", qr)),
        "wkv_a": ((d, kr + rope), ("embed", None), ("proj", d)),
        "kv_norm": ((kr,), (None,), "gain"),
        "w_uk": ((h, nope, kr), ("heads", None, None), ("proj", kr)),
        "w_uv": ((h, kr, v), ("heads", None, None), ("proj", kr)),
        "wo": ((h * v, d), ("heads", "embed"), ("out", h * v)),
        "mlp_norm": ((d,), ("norm",), "gain"),
    }
    return {
        "dense": {
            **attn,
            "w_gate": ((d, f), ("embed", "mlp"), ("proj", d)),
            "w_up": ((d, f), ("embed", "mlp"), ("proj", d)),
            "w_down": ((f, d), ("mlp", "embed"), ("out", f)),
        },
        "moe": {
            **attn,
            "router": ((d, c.num_experts), ("embed", None), ("proj", d)),
            "router_bias": ((c.num_experts,), (None,), "bias"),
            "w_gate": ((e, d, fe), ("expert", "embed", "mlp"), ("proj", d)),
            "w_up": ((e, d, fe), ("expert", "embed", "mlp"), ("proj", d)),
            "w_down": ((e, fe, d), ("expert", "mlp", "embed"), ("out", fe)),
            "ws_gate": ((d, fs), ("embed", "mlp"), ("proj", d)),
            "ws_up": ((d, fs), ("embed", "mlp"), ("proj", d)),
            "ws_down": ((fs, d), ("mlp", "embed"), ("out", fs)),
        },
    }


def segments(c: TransformerConfig):
    """``[(segment, layers)]``, in the order the layers run."""
    return [("dense", c.dense_layers), ("moe", c.n_layers - c.dense_layers)]


# -- cache ---------------------------------------------------------------------

def init_cache(c: TransformerConfig, num_blocks: int, block_size: int, *,
               dtype=None) -> Params:
    return {"kv": jnp.zeros(
        (c.n_layers, num_blocks, block_size, pool_width(c.latent_width)),
        jnp.dtype(dtype or c.dtype))}


# -- the step's layer loop ----------------------------------------------------

#: a layer's leaves that the stage before its attention multiplies by, and
#: those of the stage after it; the expert stacks stay whole
_BEFORE = ("attn_norm", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "w_uk")
_EXPERTS = ("w_gate", "w_up", "w_down")


def run_layers(layers: Params, cache: Params, x, c: TransformerConfig, ctx):
    """The two segments over the residual stream ``x`` (``[B, C, D]``, or
    the ordered flat stream ``[1, B * C, D]`` under a budget). ``ctx`` (a
    namespace made by ``_step_paged_impl``, as
    :func:`ray_tpu.models.hybrid.run_layers` takes it): ``at``, ``stage``,
    ``to_rows`` / ``to_flat``, ``pos``, ``n_attend``, ``full_tables``,
    ``full_rows`` (each position's token row in ONE layer's pool; dropped
    positions negative), ``decode_mlp(x, lp, valid, layer, dense)``.
    Returns ``(x, new cache, tokens per held expert [expert layers, E])``."""
    dt = jnp.dtype(c.dtype)
    eps = c.norm_eps or 1e-6
    h, rank, rope = c.n_heads, c.kv_lora_rank, c.qk_rope_head_dim
    nope = c.qk_nope_head_dim
    n_layers, n_blocks, bs, width = cache["kv"].shape
    pad = width - c.latent_width
    scale = softmax_scale(c)
    # the pool travels as ONE pool of ``n_layers * n_blocks`` blocks, carried
    # through both scans and written in place, as the uniform step's are
    pool = cache["kv"].reshape(n_layers * n_blocks, bs, width)
    dropped = n_layers * n_blocks * bs

    def index(tree, i):
        """Layer ``i`` of a segment, sliced where it is used: what a scan
        slices for a stage crosses the stage's branch as a copy."""
        return jax.tree.map(lambda w: w[i], tree)

    def layer(x, pool, block, i, first, dense):
        """Layer ``i`` of its segment; its pool starts at block ``first``."""
        def before(_, ins):
            lp = index({k: block[k] for k in _BEFORE}, i)
            with jax.named_scope("qkv_proj"):
                hx = rms_norm(ins["x"], lp["attn_norm"], eps=eps)
                with jax.named_scope("mla_q_proj"):
                    cq = rms_norm(jnp.einsum("bld,dr->blr", hx,
                                             lp["wq_a"].astype(dt)),
                                  lp["q_norm"], eps=eps)
                    q = jnp.einsum("blr,re->ble", cq, lp["wq_b"].astype(dt))
                    q = q.reshape(*q.shape[:2], h, nope + rope)
                    with jax.named_scope("rope"):
                        q_rope = apply_rotary(q[..., nope:], ins["cos"],
                                              ins["sin"])
                    # W_uk folded into the query: scores are taken in the
                    # latent space
                    q_abs = jnp.einsum("blhn,hnr->blhr", q[..., :nope],
                                       lp["w_uk"].astype(dt))
                    # at the pool's width, zero past the rotated query as the
                    # cached vectors are: the attention reads it as it is
                    q = jnp.concatenate(
                        [q_abs, q_rope,
                         jnp.zeros(q_rope.shape[:-1] + (pad,), dt)], axis=-1)
                with jax.named_scope("mla_kv_proj"):
                    kv = jnp.einsum("bld,dr->blr", hx, lp["wkv_a"].astype(dt))
                    c_kv = rms_norm(kv[..., :rank], lp["kv_norm"], eps=eps)
                    with jax.named_scope("rope"):
                        k_r = apply_rotary(kv[..., None, rank:], ins["cos"],
                                           ins["sin"])[..., 0, :]
                    kv = jnp.concatenate(
                        [c_kv, k_r, jnp.zeros(k_r.shape[:-1] + (pad,), dt)],
                        axis=-1)
            return {"q": q, "kv": kv}, None

        like = {"q": jnp.zeros(x.shape[:2] + (h, width), dt),
                "kv": jnp.zeros(x.shape[:2] + (width,), dt)}
        new, _ = ctx.stage(before, like, {**ctx.at, "x": x})
        # write BEFORE attending: a chunk's queries see its own tokens
        rows = jnp.where(ctx.full_rows < 0, dropped,
                         ctx.full_rows + first * bs)
        with jax.named_scope("kv_write"):
            pool = pool.at[rows // bs, rows % bs].set(
                new["kv"].reshape(-1, width).astype(pool.dtype), mode="drop")
        u = paged_latent_attention(
            ctx.to_rows(new["q"]), pool, ctx.full_tables + first, ctx.pos,
            ctx.n_attend, rank=rank, scale=scale)

        def after(x, ins):
            lp = {k: w if k in _EXPERTS and not dense else w[i]
                  for k, w in block.items() if k not in _BEFORE}
            with jax.named_scope("attn_out_proj"), \
                    jax.named_scope("mla_out_proj"):
                o = jnp.einsum("blhr,hrv->blhv", ins["u"],
                               lp["w_uv"].astype(dt))
                x = x + jnp.einsum("ble,ed->bld",
                                   o.reshape(*o.shape[:2], -1),
                                   lp["wo"].astype(dt))
            return ctx.decode_mlp(x, lp, ins["valid"],
                                  None if dense else i, dense)

        x, counts = ctx.stage(
            after, x, {**ctx.at, "u": ctx.to_flat(u)},
            None if dense else jnp.zeros((c.held_experts,), jnp.int32))
        return x, pool, counts

    expert_tokens, done = None, 0
    for seg, n in segments(c):
        def body(carry, i, seg=seg, done=done):
            x, pool, counts = layer(*carry, layers[seg], i,
                                    (done + i) * n_blocks, seg == "dense")
            return (x, pool), counts

        (x, pool), expert_tokens = lax.scan(body, (x, pool), jnp.arange(n))
        done += n
    return x, {"kv": pool.reshape(cache["kv"].shape)}, expert_tokens


#: what :func:`count` counts of a step (``layouts.StepRows``), by the rule
#: :func:`run_layers` applies: cached tokens the step's rows read (a row's
#: live context, layers left out), the rows that read them, and those of them
#: the block-walking kernel attended (``kernels["attn_impl"]``: all or none)
COUNTERS = ("latent_tokens_read", "latent_rows_attended",
            "latent_kernel_rows")


def count(c: TransformerConfig, step) -> Dict[str, int]:
    rows = len(step.pos)
    return {"latent_tokens_read": int((step.pos + step.nvalid).sum()),
            "latent_rows_attended": rows,
            "latent_kernel_rows":
                rows * (step.kernels["attn_impl"] == "pallas")}
