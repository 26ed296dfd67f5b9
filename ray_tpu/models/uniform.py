"""The uniform decoder (every layer one attention and one MLP or expert
layer over one pair of KV pools: Llama, GPT-2, Gemma-2, Mistral, Qwen2 and
their MoE and sparse-attention variants): its parameter tree and its paged
cache. Its training forward, its dense decode and the paged step's layer loop
are :mod:`ray_tpu.models.transformer`'s own; it is the one layout that trains.

Parameters: ``params["layers"][leaf]``, every leaf stacked over the layers
(``wq [L, d, h, hd]``). Cache pools: ``"k"``, ``"v"`` ``[n_layers,
num_blocks, block_size, kv_heads, head_dim]`` and, with a sparse-attention
indexer (``index_heads``), ``"ki"`` ``[n_layers, num_blocks,
*index_pool_shape(block_size, index_head_dim)]``: a block's keys in row-major
order, two 64-wide keys a 128-lane row where the widths divide
(:func:`ray_tpu.ops.sparse_attention.index_pool_shape`;
:func:`ray_tpu.models.transformer.init_cache_paged` says what a block is and
who carries it).
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from ray_tpu.models.config import TransformerConfig
from ray_tpu.ops.sparse_attention import index_pool_shape

Params = Dict[str, Any]


def init_params(rng: jax.Array, c: TransformerConfig) -> Params:
    pdt = jnp.dtype(c.param_dtype)
    d, hd, f, L = c.d_model, c.hdim, c.ff, c.n_layers
    h, kv, v = c.n_heads, c.kv_heads, c.vocab_size

    keys = iter(jax.random.split(rng, 16))

    def normal(key, shape, std):
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(pdt)

    proj_std = d ** -0.5
    out_std = proj_std / (2 * L) ** 0.5  # GPT-2-style depth scaling

    layers: Params = {
        "attn_norm": jnp.ones((L, d), pdt),
        "wq": normal(next(keys), (L, d, h, hd), proj_std),
        "wk": normal(next(keys), (L, d, kv, hd), proj_std),
        "wv": normal(next(keys), (L, d, kv, hd), proj_std),
        "wo": normal(next(keys), (L, h, hd, d), out_std),
        "mlp_norm": jnp.ones((L, d), pdt),
    }
    if c.attn_qkv_bias:
        layers["bq"] = jnp.zeros((L, h, hd), pdt)
        layers["bk"] = jnp.zeros((L, kv, hd), pdt)
        layers["bv"] = jnp.zeros((L, kv, hd), pdt)
    if c.norm == "layer":
        layers["attn_norm_b"] = jnp.zeros((L, d), pdt)
        layers["mlp_norm_b"] = jnp.zeros((L, d), pdt)
    if c.qk_norm:
        layers["q_norm"] = jnp.ones((L, hd), pdt)
        layers["k_norm"] = jnp.ones((L, hd), pdt)
    if c.index_heads:
        j, di = c.index_heads, c.index_head_dim
        layers["wq_i"] = normal(next(keys), (L, d, j, di), proj_std)
        layers["wk_i"] = normal(next(keys), (L, d, di), proj_std)
        layers["w_i"] = normal(next(keys), (L, d, j), proj_std)
        layers["ki_norm"] = jnp.ones((L, di), pdt)
        layers["ki_norm_b"] = jnp.zeros((L, di), pdt)

    if c.num_experts:
        e = c.num_experts
        layers["router"] = normal(next(keys), (L, d, e), proj_std)
        layers["w_gate"] = normal(next(keys), (L, e, d, f), proj_std)
        layers["w_up"] = normal(next(keys), (L, e, d, f), proj_std)
        layers["w_down"] = normal(next(keys), (L, e, f, d), out_std)
    elif c.mlp == "swiglu":
        layers["w_gate"] = normal(next(keys), (L, d, f), proj_std)
        layers["w_up"] = normal(next(keys), (L, d, f), proj_std)
        layers["w_down"] = normal(next(keys), (L, f, d), out_std)
    else:  # gelu
        layers["w_in"] = normal(next(keys), (L, d, f), proj_std)
        layers["b_in"] = jnp.zeros((L, f), pdt)
        layers["w_out"] = normal(next(keys), (L, f, d), out_std)
        layers["b_out"] = jnp.zeros((L, d), pdt)

    params: Params = {
        "embed": normal(next(keys), (v, d), 0.02),
        "layers": layers,
        "final_norm": jnp.ones((d,), pdt),
    }
    if c.norm == "layer":
        params["final_norm_b"] = jnp.zeros((d,), pdt)
    if c.positions == "learned":
        params["pos_embed"] = normal(next(keys), (c.max_seq_len, d), 0.02)
    if not c.tie_embeddings:
        params["lm_head"] = normal(next(keys), (d, v), proj_std)
    return params


def param_axes(c: TransformerConfig) -> Params:
    lay = {
        "attn_norm": ("layers", "norm"),
        "wq": ("layers", "embed", "heads", "head_dim"),
        "wk": ("layers", "embed", "kv_heads", "head_dim"),
        "wv": ("layers", "embed", "kv_heads", "head_dim"),
        "wo": ("layers", "heads", "head_dim", "embed"),
        "mlp_norm": ("layers", "norm"),
    }
    if c.attn_qkv_bias:
        lay["bq"] = ("layers", "heads", "head_dim")
        lay["bk"] = ("layers", "kv_heads", "head_dim")
        lay["bv"] = ("layers", "kv_heads", "head_dim")
    if c.norm == "layer":
        lay["attn_norm_b"] = ("layers", "norm")
        lay["mlp_norm_b"] = ("layers", "norm")
    if c.qk_norm:
        lay["q_norm"] = ("layers", "head_dim")
        lay["k_norm"] = ("layers", "head_dim")
    if c.index_heads:
        lay["wq_i"] = ("layers", "embed", None, None)
        lay["wk_i"] = ("layers", "embed", None)
        lay["w_i"] = ("layers", "embed", None)
        lay["ki_norm"] = ("layers", None)
        lay["ki_norm_b"] = ("layers", None)
    if c.num_experts:
        lay["router"] = ("layers", "embed", "expert")
        lay["w_gate"] = ("layers", "expert", "embed", "mlp")
        lay["w_up"] = ("layers", "expert", "embed", "mlp")
        lay["w_down"] = ("layers", "expert", "mlp", "embed")
    elif c.mlp == "swiglu":
        lay["w_gate"] = ("layers", "embed", "mlp")
        lay["w_up"] = ("layers", "embed", "mlp")
        lay["w_down"] = ("layers", "mlp", "embed")
    else:
        lay["w_in"] = ("layers", "embed", "mlp")
        lay["b_in"] = ("layers", "mlp")
        lay["w_out"] = ("layers", "mlp", "embed")
        lay["b_out"] = ("layers", "norm")
    axes: Params = {
        "embed": ("vocab", "embed"),
        "layers": lay,
        "final_norm": ("norm",),
    }
    if c.norm == "layer":
        axes["final_norm_b"] = ("norm",)
    if c.positions == "learned":
        axes["pos_embed"] = (None, "embed")
    if not c.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


def init_cache(c: TransformerConfig, num_blocks: int, block_size: int, *,
               dtype=None) -> Params:
    dt = jnp.dtype(dtype or c.dtype)
    shape = (c.n_layers, num_blocks, block_size, c.kv_heads, c.hdim)
    cache = {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
    if c.index_heads:
        cache["ki"] = jnp.zeros(
            (c.n_layers, num_blocks,
             *index_pool_shape(block_size, c.index_head_dim)), dt)
    return cache
