"""TPU kernel library: attention, normalization, rotary, MoE dispatch.

This package is green-field relative to the reference — Ray has no kernel
layer (long-context/sequence-parallel is absent upstream, SURVEY §5) — but it
is the compute substrate every ML library here builds on. Three tiers:

- pure-XLA blockwise implementations (:mod:`ray_tpu.ops.attention`) that run
  anywhere (CPU tests, TPU) and are the numerical reference;
- Pallas TPU kernels (:mod:`ray_tpu.ops.flash_pallas`) for the hot path;
- sequence-parallel ring attention (:mod:`ray_tpu.ops.ring_attention`)
  running inside ``shard_map`` with ``lax.ppermute`` over ICI neighbors.
"""

from ray_tpu.ops.attention import (
    naive_attention,
    blockwise_attention,
    flash_attention,
    set_default_attention_impl,
    resolve_attention_impl,
)
from ray_tpu.ops.ring_attention import ring_attention
from ray_tpu.ops.layers import (
    layer_norm,
    rms_norm,
    rotary_embedding,
    apply_rotary,
    swiglu,
)
from ray_tpu.ops.moe import (
    top_k_router,
    moe_layer_dense,
    moe_layer_dropless,
)

__all__ = [
    "naive_attention",
    "blockwise_attention",
    "flash_attention",
    "set_default_attention_impl",
    "resolve_attention_impl",
    "ring_attention",
    "layer_norm",
    "rms_norm",
    "rotary_embedding",
    "apply_rotary",
    "swiglu",
    "top_k_router",
    "moe_layer_dense",
    "moe_layer_dropless",
]
