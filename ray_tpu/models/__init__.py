"""Built-in TPU-tuned model family.

The reference has no first-party model zoo (Train wraps user torch models;
RLlib builds small encoders via ``rllib/core/models/``). Here the flagship
LLM family is part of the framework because the headline benchmark is LLM
training on TPU (BASELINE.json north star): decoder-only transformers
covering Llama-3 shapes (RoPE/SwiGLU/RMSNorm/GQA), GPT-2 shapes
(learned-pos/GELU/LayerNorm), and MoE variants, all as pure functions over
param pytrees with logical-axis sharding annotations.
"""

from ray_tpu.models.config import (
    TransformerConfig,
    PRESETS,
    get_config,
    llama3_8b,
    llama3_70b,
    llama_1b,
    llama_250m,
    llama_debug,
    gemma2_9b,
    gemma_debug,
    mistral_7b,
    mistral_debug,
    qwen2_7b,
    qwen2_debug,
    gpt2_small,
    gpt2_debug,
    moe_debug,
    sparse_moe_debug,
)
from ray_tpu.models.layouts import layout_of
from ray_tpu.models.transformer import (
    init_params,
    param_axes,
    forward,
    loss_and_metrics,
    init_cache,
    decode_step,
    init_cache_paged,
    decode_step_paged,
    verify_step_paged,
    copy_kv_block,
    gather_kv_blocks,
    scatter_kv_blocks,
    generate,
)

from ray_tpu.models.delta import (
    apply_delta,
    delta_bytes,
    make_delta,
    params_bytes,
)

from ray_tpu.models.import_hf import (
    config_from_hf,
    import_hf_llama,
    load_hf_llama,
)

__all__ = [
    "config_from_hf",
    "import_hf_llama",
    "load_hf_llama",
    "TransformerConfig",
    "PRESETS",
    "get_config",
    "layout_of",
    "llama3_8b",
    "llama3_70b",
    "llama_1b",
    "llama_250m",
    "llama_debug",
    "gemma2_9b",
    "gemma_debug",
    "mistral_7b",
    "mistral_debug",
    "qwen2_7b",
    "qwen2_debug",
    "gpt2_small",
    "gpt2_debug",
    "moe_debug",
    "sparse_moe_debug",
    "init_params",
    "param_axes",
    "forward",
    "loss_and_metrics",
    "init_cache",
    "decode_step",
    "init_cache_paged",
    "decode_step_paged",
    "verify_step_paged",
    "copy_kv_block",
    "gather_kv_blocks",
    "scatter_kv_blocks",
    "generate",
    "apply_delta",
    "delta_bytes",
    "make_delta",
    "params_bytes",
]
