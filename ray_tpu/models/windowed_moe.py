"""The windowed MoE decoder (``TransformerConfig.windowed_moe``: the Trinity
layer, and by two keys more the SmallThinker layer) on the paged serve step:
its parameter tree and its cache pools. The step's layer loop is the uniform decoder's own
(``models/transformer.py::_step_paged_impl``), which runs this layout's
layers in runs (``transformer._layer_runs``).

Every layer is GQA attention then an MLP, each branch after a pre-norm; the
gate ``g`` (``attn_gate``), the head norms (``qk_norm``) and a norm on each
branch's output (``post_norms``) are there where the configuration has them::

    h = RMSNorm(x; attn_norm);  q, k, v, g = h Wq, h Wk, h Wv, h Wg
    q, k = RMSNorm over each head (q_norm, k_norm), then RoPE where the
           layer's kind has positions (``rope_layers``)
    a = softmax(q k^T / sqrt(hd)) v * sigmoid(g)
    x = x + RMSNorm(a Wo; post_attn_norm)
    x = x + RMSNorm(MLP(RMSNorm(x; mlp_norm)); post_mlp_norm)

The experts' gate activation is ``expert_act`` (SiLU, or ReLU), and the
router reads ``router_input``: the MLP's own normed input, or ``h`` above,
the layer's normed INPUT, in which case the route is made ahead of the
attention (``transformer._step_paged_impl::before_attention``) and the
experts take it after (``ops/moe.py::moe_route`` / ``moe_experts``).

Two kinds of layer in two segments, as :mod:`ray_tpu.models.latent` holds
them: ``"dense"`` x ``dense_layers`` (a SwiGLU MLP of width ``d_ff``) and
``"moe"`` x the rest (the routed experts HELD here beside the shared expert:
``ops/moe.py``, ``transformer._decode_mlp``). Parameters:
``params["layers"][segment][leaf]``, every leaf stacked over the segment's
layers and stored as it is multiplied (``wq [d, h * hd]``).

Cache pools by KIND of layer where ``attn_windows`` mix one window size with
full layers (``TransformerConfig.window_pool``), each with its own ids:
``"k"``, ``"v"`` ``[full layers, num_blocks, bs, kvh, hd]`` (a request's
table is as wide as its context) and ``"wk"``, ``"wv"`` ``[window layers,
window_blocks, bs, kvh, hd]`` (a request's table holds its live window only,
``hybrid.window_table_width`` blocks at most, the same ids in every window
layer). With any other pattern every layer is in ``"k"``, ``"v"`` and a
window is a mask.
"""

from __future__ import annotations

from typing import Any, Dict

import jax.numpy as jnp

from ray_tpu.models import latent
from ray_tpu.models.config import TransformerConfig

Params = Dict[str, Any]


#: what refuses the layout anywhere but on the paged serve step
SERVE_ONLY = ("the windowed MoE layout (GQA attention over dense and "
              "expert layers: attn_gate, post_norms, rope_layers, "
              "expert_act, router_input, dense_layers ... experts_first)")


def block_shapes(c: TransformerConfig) -> Dict[str, Dict[str, tuple]]:
    """``{segment: {leaf: (shape, logical axes, how it is drawn)}}`` of ONE
    layer of each segment, in ``latent.block_shapes``'s form."""
    d, hd = c.d_model, c.hdim
    q, kv = c.n_heads * hd, c.kv_heads * hd
    f, fe, fs = c.ff, c.ff_expert, c.ff_expert * c.shared_experts
    e = c.held_experts
    attn = {
        "attn_norm": ((d,), ("norm",), "gain"),
        "wq": ((d, q), ("embed", "heads"), ("proj", d)),
        "wk": ((d, kv), ("embed", "kv_heads"), ("proj", d)),
        "wv": ((d, kv), ("embed", "kv_heads"), ("proj", d)),
        "wo": ((q, d), ("heads", "embed"), ("out", q)),
        "mlp_norm": ((d,), ("norm",), "gain"),
    }
    if c.attn_gate:
        attn["wg"] = ((d, q), ("embed", "heads"), ("proj", d))
    if c.qk_norm:
        attn["q_norm"] = ((hd,), ("head_dim",), "gain")
        attn["k_norm"] = ((hd,), ("head_dim",), "gain")
    if c.post_norms:
        attn["post_attn_norm"] = ((d,), ("norm",), "gain")
        attn["post_mlp_norm"] = ((d,), ("norm",), "gain")
    moe = {
        "router": ((d, c.num_experts), ("embed", None), ("proj", d)),
        "w_gate": ((e, d, fe), ("expert", "embed", "mlp"), ("proj", d)),
        "w_up": ((e, d, fe), ("expert", "embed", "mlp"), ("proj", d)),
        "w_down": ((e, fe, d), ("expert", "mlp", "embed"), ("out", fe)),
    }
    if c.expert_scoring == "sigmoid":
        moe["router_bias"] = ((c.num_experts,), (None,), "bias")
    if c.shared_experts:
        moe.update(
            ws_gate=((d, fs), ("embed", "mlp"), ("proj", d)),
            ws_up=((d, fs), ("embed", "mlp"), ("proj", d)),
            ws_down=((fs, d), ("mlp", "embed"), ("out", fs)))
    return {
        "dense": {
            **attn,
            "w_gate": ((d, f), ("embed", "mlp"), ("proj", d)),
            "w_up": ((d, f), ("embed", "mlp"), ("proj", d)),
            "w_down": ((f, d), ("mlp", "embed"), ("out", f)),
        },
        "moe": {**attn, **moe},
    }


def segments(c: TransformerConfig):
    """``[(segment, layers)]``, in the order the layers run."""
    return [(seg, n) for seg, n in latent.segments(c) if n]


def pool_layers(c: TransformerConfig):
    """``(layers that read the window pool, layers that read the pool a whole
    table names)``; every layer is of the second kind where the windows are
    masks (no ``window_pool``)."""
    n_win = sum(w > 0 for w in c.layer_windows) if c.window_pool else 0
    return n_win, c.n_layers - n_win


def init_cache(c: TransformerConfig, num_blocks: int, block_size: int, *,
               window_blocks=None, dtype=None) -> Params:
    dt = jnp.dtype(dtype or c.dtype)
    shape = lambda layers, blocks: (layers, blocks, block_size, c.kv_heads,
                                    c.hdim)
    n_win, n_full = pool_layers(c)
    pools = {"k": (n_full, num_blocks), "v": (n_full, num_blocks)}
    if n_win:
        pools.update(wk=(n_win, window_blocks), wv=(n_win, window_blocks))
    return {name: jnp.zeros(shape(*size), dt)
            for name, size in pools.items()}
