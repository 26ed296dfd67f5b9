"""HuggingFace checkpoint import: real weights into the ray_tpu model zoo.

Role analog: the reference ecosystem's checkpoint interop (RLlib/Train
users load pretrained torch checkpoints; a TPU framework must ingest the
same artifacts). Maps a ``transformers`` Llama-family state dict
(LlamaForCausalLM / MistralForCausalLM / Qwen2ForCausalLM — the
architectures our ``TransformerConfig`` reproduces exactly: RMSNorm,
RoPE, GQA, SwiGLU, optional Qwen2 q/k/v biases) onto the scanned-layer
param pytree of ``models/transformer.py``.

Conventions handled:

- torch ``nn.Linear`` stores ``W [out, in]`` computing ``x @ W.T`` — our
  einsum weights are ``[in, out]``-shaped, so every projection is
  transposed (then reshaped to split heads);
- per-layer tensors are STACKED on a leading layer axis (our layers run
  under ``lax.scan``);
- rotate-half RoPE matches HF's (first/second half split, same theta);
- tied embeddings reuse ``embed``; untied checkpoints fill ``lm_head``.

Verified by an exact logits-parity test against ``transformers`` on a
randomly initialized tiny Llama (tests/test_models.py).
"""

from __future__ import annotations

from typing import Any, Dict, Mapping

import numpy as np

from ray_tpu.models.config import TransformerConfig

Params = Dict[str, Any]


def config_from_hf(hf_config: Any) -> TransformerConfig:
    """TransformerConfig from a ``transformers`` LlamaConfig/MistralConfig
    (duck-typed: any object with the HF attribute names)."""
    if getattr(hf_config, "model_type", "") == "phi4flash":
        # a hybrid state-space / attention decoder: its keys look like a
        # uniform decoder's, and importing it as one would run 32 plain
        # attention layers under its name
        raise ValueError(
            "model_type 'phi4flash' (Phi-4-mini-flash-reasoning: Mamba, "
            "window, full, cross and gated-memory layers) cannot be "
            "imported yet: TransformerConfig.layer_kinds describes the "
            "layout and the paged serve step runs it, but there is no name "
            "map from the checkpoint's tensors to the five layer kinds' "
            "parameter blocks (models/hybrid.py::block_shapes)")
    if getattr(hf_config, "model_type", "") == "falcon_h1":
        # attention and a Mamba-2 mixer side by side in every layer: its keys
        # look like a uniform decoder's, and importing it as one would drop
        # the state-space half and every fixed multiplier
        raise ValueError(
            "model_type 'falcon_h1' (attention heads and Mamba-2 heads side "
            "by side in every layer, fixed multipliers) cannot be imported "
            "yet: TransformerConfig.layer_kinds all 'parallel' describes "
            "the layer and the paged serve step runs it, but there is no "
            "name map from the checkpoint's tensors to the layer's "
            "parameter block (models/parallel_hybrid.py::block_shapes)")
    if getattr(hf_config, "model_type", "") in ("kimi_k2", "deepseek_v3") \
            or getattr(hf_config, "kv_lora_rank", None):
        # latent attention: its keys (heads, hidden size, experts) look like
        # a uniform MoE decoder's, and importing it as one would run
        # full-head attention and softmax routing under its name
        raise ValueError(
            "model_type 'kimi_k2' / 'deepseek_v3' (latent attention, "
            "sigmoid-routed experts beside a shared expert, leading dense "
            "layers) cannot be imported yet: TransformerConfig.kv_lora_rank "
            "describes the layer and the paged serve step runs it, but "
            "there is no name map from the checkpoint's tensors to "
            "models/latent.py::block_shapes (kv_b_proj split into w_uk and "
            "w_uv per head, the experts' matrices stacked, "
            "e_score_correction_bias as router_bias), no rule for the "
            "pairing of the rotated dimensions (the checkpoint interleaves "
            "them), and no training forward for the layer")
    if getattr(hf_config, "model_type", "") == "afmoe":
        # gated GQA attention over window and full layers, sigmoid-routed
        # experts beside a shared one: its keys look like a uniform MoE
        # decoder's, and importing it as one would drop the gate, two of the
        # four norms, the selection bias and the layers' kinds
        raise ValueError(
            "model_type 'afmoe' (Trinity: gated GQA attention, window and "
            "full layers mixed, sigmoid-routed experts beside a shared one, "
            "four norms a layer) cannot be imported yet: the windowed MoE "
            "layout of TransformerConfig (attn_gate, post_norms, "
            "rope_layers, dense_layers ... experts_first) "
            "describes the layer and the paged serve step runs it, but "
            "there is no name map from the checkpoint's tensors to "
            "models/windowed_moe.py::block_shapes: the experts' matrices "
            "stacked, expert_bias as router_bias, the attention's gate_proj "
            "as wg, the four norms (input_layernorm, "
            "post_attention_layernorm, pre_mlp_layernorm, "
            "post_mlp_layernorm) and the head norms, and no checkpoint in "
            "this repository to test one against")
    scaling = getattr(hf_config, "rope_scaling", None)
    if scaling:
        raise ValueError(
            f"rope_scaling={scaling!r} is not supported: ray_tpu's "
            "rotary tables are unscaled, so importing (e.g.) a "
            "Llama-3.1+ checkpoint would produce silently wrong "
            "frequencies")
    if getattr(hf_config, "attention_bias", False):
        # HF Llama's attention_bias biases o_proj too, which the forward
        # does not model — refuse rather than import silently wrong
        raise ValueError(
            "attention_bias=True (q/k/v AND o_proj biases) is not "
            "supported; only Qwen2-style q/k/v-only biases are")
    qwen2 = getattr(hf_config, "model_type", "") == "qwen2"
    window = getattr(hf_config, "sliding_window", None) or 0
    attn_windows = None
    if qwen2:
        if window and getattr(hf_config, "use_sliding_window", False):
            # Per-layer windows. transformers reads layer_types per
            # layer when present; older configs use the prefix rule
            # (full attention below max_window_layers, SWA above).
            layer_types = getattr(hf_config, "layer_types", None)
            if layer_types:
                known = {"sliding_attention", "full_attention"}
                bad = set(layer_types) - known
                if bad or len(layer_types) != hf_config.num_hidden_layers:
                    # refuse-loudly policy: an unknown attention kind
                    # (chunked/linear/...) or a mis-sized list must not
                    # import as silently-wrong full attention
                    raise ValueError(
                        f"unsupported layer_types (unknown kinds {sorted(bad)}"
                        f", len {len(layer_types)} vs "
                        f"{hf_config.num_hidden_layers} layers)")
                per_layer = tuple(
                    int(window) if t == "sliding_attention" else 0
                    for t in layer_types)
            else:
                full = int(getattr(hf_config, "max_window_layers", 0))
                per_layer = tuple(
                    0 if i < full else int(window)
                    for i in range(hf_config.num_hidden_layers))
            # minimal repeating period keeps the grouped layer scan
            # small (a prefix rule has no short period and pays a
            # one-group trace; the common alternating/uniform cases
            # reduce to 1-2 entries)
            attn_windows = _min_period(per_layer)
            if set(attn_windows) == {0}:
                attn_windows = None
        window = 0  # HF ignores sliding_window unless use_sliding_window
    return TransformerConfig(
        vocab_size=hf_config.vocab_size,
        d_model=hf_config.hidden_size,
        n_layers=hf_config.num_hidden_layers,
        n_heads=hf_config.num_attention_heads,
        n_kv_heads=getattr(hf_config, "num_key_value_heads",
                           hf_config.num_attention_heads),
        head_dim=getattr(hf_config, "head_dim", None),
        d_ff=hf_config.intermediate_size,
        max_seq_len=hf_config.max_position_embeddings,
        rope_theta=float(getattr(hf_config, "rope_theta", 10000.0)),
        sliding_window=int(window),
        attn_windows=attn_windows,
        tie_embeddings=bool(getattr(hf_config, "tie_word_embeddings",
                                    False)),
        norm_eps=float(getattr(hf_config, "rms_norm_eps", 1e-6)),
        attn_qkv_bias=qwen2,  # Qwen2 biases q/k/v only (o stays clean)
        mlp="swiglu", norm="rms", positions="rope",
        dtype="float32", param_dtype="float32",
    )


def _min_period(pat: tuple) -> tuple:
    """Smallest repeating prefix generating ``pat`` (itself if aperiodic)."""
    n = len(pat)
    for p in range(1, n):
        if n % p == 0 and pat[:p] * (n // p) == pat:
            return pat[:p]
    return pat


def _np(w, dtype) -> np.ndarray:
    """torch tensor (or array) -> numpy in the TARGET param dtype (no
    transient f32 blow-up: an 8B bf16 checkpoint stays bf16-sized)."""
    if hasattr(w, "detach"):
        import torch

        w = w.detach().cpu()
        if w.dtype == torch.bfloat16:  # numpy has no native bf16 bridge
            w = w.float()
        w = w.numpy()
    import jax.numpy as jnp

    return np.asarray(w).astype(jnp.dtype(dtype))


def import_hf_llama(state_dict: Mapping[str, Any],
                    config: TransformerConfig) -> Params:
    """Build the ray_tpu param pytree from a Llama-family HF state dict.

    ``state_dict``: ``model.state_dict()`` of a ``LlamaForCausalLM`` /
    ``MistralForCausalLM`` (torch tensors or numpy arrays).
    """
    c = config
    if c.mlp != "swiglu" or c.norm != "rms" or c.positions != "rope":
        raise ValueError(
            "import_hf_llama maps Llama-family architectures only "
            f"(swiglu/rms/rope); config has {c.mlp}/{c.norm}/{c.positions}")
    sd = dict(state_dict)
    pre = "model." if "model.embed_tokens.weight" in sd else ""
    d, hd, h, kv, L = c.d_model, c.hdim, c.n_heads, c.kv_heads, c.n_layers
    pdt = c.param_dtype
    consumed = set()

    def take(key):
        consumed.add(key)
        return sd[key]

    def raw(i: int, name: str):
        return _np(take(f"{pre}layers.{i}.{name}.weight"), pdt)

    def lin(i: int, name: str):
        return raw(i, name).T  # Linear [out, in] -> einsum [in, out]

    stack = lambda mats: np.stack(mats, axis=0)
    layers: Params = {
        "attn_norm": stack([raw(i, "input_layernorm")
                            for i in range(L)]),
        "wq": stack([lin(i, "self_attn.q_proj").reshape(d, h, hd)
                     for i in range(L)]),
        "wk": stack([lin(i, "self_attn.k_proj").reshape(d, kv, hd)
                     for i in range(L)]),
        "wv": stack([lin(i, "self_attn.v_proj").reshape(d, kv, hd)
                     for i in range(L)]),
        "wo": stack([lin(i, "self_attn.o_proj").reshape(h, hd, d)
                     for i in range(L)]),
        "mlp_norm": stack([raw(i, "post_attention_layernorm")
                           for i in range(L)]),
        "w_gate": stack([lin(i, "mlp.gate_proj") for i in range(L)]),
        "w_up": stack([lin(i, "mlp.up_proj") for i in range(L)]),
        "w_down": stack([lin(i, "mlp.down_proj") for i in range(L)]),
    }
    if c.attn_qkv_bias:  # Qwen2-style q/k/v biases, head-split
        def bias(i, name, heads):
            return _np(take(f"{pre}layers.{i}.self_attn.{name}.bias"),
                       pdt).reshape(heads, hd)

        layers["bq"] = stack([bias(i, "q_proj", h) for i in range(L)])
        layers["bk"] = stack([bias(i, "k_proj", kv) for i in range(L)])
        layers["bv"] = stack([bias(i, "v_proj", kv) for i in range(L)])
    params: Params = {
        "embed": _np(take(f"{pre}embed_tokens.weight"), pdt),
        "layers": layers,
        "final_norm": _np(take(f"{pre}norm.weight"), pdt),
    }
    if not c.tie_embeddings:
        if "lm_head.weight" in sd:
            params["lm_head"] = _np(take("lm_head.weight"), pdt).T
        else:  # tied checkpoint imported into an untied config
            params["lm_head"] = params["embed"].T.copy()
    else:
        consumed.add("lm_head.weight")  # alias of embed when present

    # Strict-consumption check (torch load_state_dict strict=True role):
    # an architecture this mapping does NOT model (Qwen3 q/k norms,
    # MoE routers, ...) must fail loudly, never silently drop
    # tensors. Non-parameter buffers (rotary inv_freq caches) are
    # the only tolerated leftovers.
    leftovers = [k for k in sd
                 if k not in consumed and not k.endswith("inv_freq")]
    if leftovers:
        raise ValueError(
            "state dict has tensors this Llama-family mapping does not "
            f"consume (unsupported architecture?): {sorted(leftovers)[:8]}"
            f"{' ...' if len(leftovers) > 8 else ''}")

    import jax.numpy as jnp

    jdt = jnp.dtype(pdt)
    return {k: (jnp.asarray(v, jdt) if not isinstance(v, dict)
                else {kk: jnp.asarray(vv, jdt) for kk, vv in v.items()})
            for k, v in params.items()}


def load_hf_llama(model_name_or_path: str):
    """Convenience: load with ``transformers`` and import. Returns
    (config, params). Requires the checkpoint locally (zero-egress
    environments must pre-download)."""
    from transformers import AutoConfig, AutoModelForCausalLM

    hf_cfg = AutoConfig.from_pretrained(model_name_or_path)
    config = config_from_hf(hf_cfg)
    model = AutoModelForCausalLM.from_pretrained(model_name_or_path)
    params = import_hf_llama(model.state_dict(), config)
    return config, params
