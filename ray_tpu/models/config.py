"""Model configs + presets for the built-in transformer family.

The reference ships no model zoo of its own (RLlib's catalogs build
encoders per-framework, ``rllib/core/models/``; Train wraps user torch
models). Here the model family is first-class because the flagship
benchmark is LLM training (BASELINE.json north star: Llama-3-8B ≥45% MFU),
so the framework owns a TPU-tuned transformer the way the reference's
release benchmarks own ``torch_benchmark.py`` workloads
(``release/air_tests/air_benchmarks/workloads/``).

Everything is static at trace time: a config is hashable and is passed as a
static argument to jitted functions.
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Optional, Tuple


@dataclass(frozen=True)
class TransformerConfig:
    """Hashable, trace-static description of a decoder-only transformer."""

    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: Optional[int] = None   # None => MHA (= n_heads); < n_heads => GQA
    head_dim: Optional[int] = None     # None => d_model // n_heads
    d_ff: Optional[int] = None         # None => 4*d_model (gelu) / ~8/3*d_model (swiglu)
    max_seq_len: int = 2048

    # architecture family knobs
    mlp: str = "swiglu"                # "swiglu" (llama) | "gelu" (gpt2)
    norm: str = "rms"                  # "rms" (llama) | "layer" (gpt2)
    positions: str = "rope"            # "rope" (llama) | "learned" (gpt2) | "none"
    rope_theta: float = 500000.0
    tie_embeddings: bool = False
    # norm epsilon; None = family default (rms 1e-6, layer 1e-5). Real
    # checkpoints vary (llama-2/3 and mistral use 1e-5) — HF import sets
    # this from rms_norm_eps so parity is exact.
    norm_eps: Optional[float] = None
    # q/k/v projection biases (Qwen2; o_proj stays bias-free)
    attn_qkv_bias: bool = False

    # per-head RMSNorm on q and on k over the head size, before RoPE
    # (Qwen3-MoE convention)
    qk_norm: bool = False

    # mixture of experts (0 => dense). Training dispatches with a capacity
    # (``expert_capacity_factor``); the decode paths are dropless.
    num_experts: int = 0
    expert_top_k: int = 2
    expert_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    # renormalise the chosen experts' router weights to sum to one
    expert_norm_topk: bool = False

    # learned sparse attention (DeepSeek-V3.2 "lightning indexer"; 0 heads =
    # off): ``index_heads`` small query heads and ONE key head of
    # ``index_head_dim`` score every causal key, and attention reads the
    # ``index_topk`` best. Contexts of at most ``index_topk`` keys attend
    # to everything. Serve path only.
    index_heads: int = 0
    index_head_dim: int = 64
    index_topk: int = 2048

    # latent attention (MLA, DeepSeek-V2/V3; ``kv_lora_rank`` > 0 turns it
    # on): queries through a ``q_lora_rank`` bottleneck with an RMSNorm,
    # keys and values up-projected from ONE normed latent of
    # ``kv_lora_rank`` a token, and one rotated key of ``qk_rope_head_dim``
    # shared by all heads beside each head's ``qk_nope_head_dim``. What a
    # token caches is the latent and the rotated key, ``kv_lora_rank +
    # qk_rope_head_dim`` values a layer. Serve path only.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN-scaled RoPE (``rope_factor`` > 1 turns it on): per frequency a
    # linear ramp between the unscaled inverse frequency and that over
    # ``rope_factor``, from ``rope_beta_fast`` to ``rope_beta_slow``
    # rotations over ``rope_original_len`` positions; the softmax scale is
    # multiplied by ``(0.1 * rope_mscale_all_dim * ln(factor) + 1) ** 2``
    # and cos/sin by the ratio of that form over ``rope_mscale`` and over
    # ``rope_mscale_all_dim``. Latent attention only.
    rope_factor: float = 1.0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_original_len: int = 4096
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0

    # the expert layers of such a model (DeepSeek-V3 layout): the first
    # ``dense_layers`` layers are dense SwiGLU of width ``d_ff``, the rest
    # expert layers of width ``d_ff_expert`` beside ``shared_experts``
    # experts every token takes (one SwiGLU of ``shared_experts *
    # d_ff_expert``). ``expert_scoring``: "softmax", or "sigmoid" with a
    # selection bias (the top-k is taken of score + bias, the weights are the
    # scores); ``expert_scale`` multiplies the chosen experts' weights.
    # THE SHARE: the router is ``num_experts`` wide and picks
    # ``expert_top_k`` of them; this program holds ``experts_held`` of them
    # from index ``experts_first`` (``None``: all) and computes the (token,
    # expert) pairs whose expert is here, leaving the rest out.
    dense_layers: int = 0
    d_ff_expert: Optional[int] = None
    shared_experts: int = 0
    expert_scoring: str = "softmax"
    expert_scale: float = 1.0
    experts_held: Optional[int] = None
    experts_first: int = 0
    # The same expert layers are described under plain GQA attention too (the
    # windowed MoE layout, :mod:`ray_tpu.models.windowed_moe`, serve path
    # only), with these keys of its own: ``attn_gate`` (a projection of the
    # layer's normed input as wide as the heads' output, whose sigmoid
    # multiplies the attention's output elementwise before ``wo``);
    # ``post_norms`` (four norms a layer: an RMSNorm on each branch's OUTPUT
    # before the residual add, beside the two pre-norms); ``rope_layers``
    # (positions by layer kind: ``"all"``, or ``"window"`` = RoPE in the
    # layers that have a window and no positional encoding in the full
    # ones). Where this layout's ``attn_windows`` mix ONE window size with
    # full layers (0), the window layers keep their LIVE window only, in a
    # pool of their own whose blocks the engine releases behind it
    # (``window_pool``); any other pattern is a mask over a table as wide
    # as the context. ``embedding_multiplier`` is the embedding's fixed
    # scale there. ``expert_act`` is the routed experts' gate
    # activation by name (``"silu"``: SwiGLU; ``"relu"``: ReGLU, a sparse
    # gate). ``router_input`` says which tensor the router reads:
    # ``"mlp_norm"``, the experts' own normed input after the attention, or
    # ``"attn_norm"``, the layer's normed INPUT: the route is then made
    # ahead of the attention, beside q, k and v (SmallThinker).
    attn_gate: bool = False
    post_norms: bool = False
    rope_layers: str = "all"
    expert_act: str = "silu"
    router_input: str = "mlp_norm"

    # sliding-window (local) attention: each token attends to its last N
    # keys only (0 = full causal). Mistral-style; applies to every layer.
    sliding_window: int = 0
    # per-layer window PATTERN (Gemma-2 alternation): a repeating tuple of
    # windows, one per layer, 0 = global. E.g. (4096, 0) = sliding on even
    # layers, global on odd. Overrides ``sliding_window`` when set;
    # n_layers must divide by the pattern length. The training stack scans
    # layer GROUPS of the pattern length so each sub-layer's window stays
    # static (the banded kernels need static block liveness).
    attn_windows: Optional[Tuple[int, ...]] = None
    # attention-logit tanh soft-capping (Gemma-2: 50.0; 0 = off), applied
    # inside every attention impl before masking — incl. the Pallas
    # kernels' fwd and bwd, so training matches real checkpoints exactly
    attn_softcap: float = 0.0

    # hybrid state-space / attention decoder (SambaY: a self-decoder, then a
    # cross-decoder): the KIND of every layer's mixer, by layer index. Every
    # layer is ``x + Mixer(norm(x))`` then ``x + MLP(norm(x))``; the kinds
    # are ``"mamba"`` (Mamba-1: conv + selective scan, a fixed float32 state
    # a request and no keys), ``"window"`` (attention over the last
    # ``sliding_window`` keys, a cache of its own), ``"full"`` (attention
    # over everything; its K and V are THE cache of the cross-decoder),
    # ``"cross"`` (a query projection only, reads the ``"full"`` layer's K
    # and V) and ``"gmu"`` (gated memory unit: gates the LAST mamba layer's
    # scan output of the same token, holds nothing). The FIRST layout
    # described (SambaY): (mamba, window) x a, then (mamba, full), then
    # (gmu, cross) x b. Attention in such a model is differential attention
    # with biases on its projections. Serve path only; ``None`` = a uniform
    # decoder.
    #
    # The second layout described (Falcon-H1): EVERY layer is ``"parallel"``:
    # its attention heads (RoPE, GQA, no biases) and its Mamba-2 heads read
    # ONE normed input side by side and their scaled outputs are summed into
    # the residual, then the MLP. Such a layer holds KV blocks AND a state
    # slot; there is no window pool. Mamba-2's sizes are ``ssm_width`` (its
    # ``d_ssm``, a width of its own: not ``ssm_expand * d_model``) =
    # ``ssm_heads`` x ``ssm_head_dim``, ``ssm_groups`` groups of heads that
    # share ``B`` and ``C``, ``ssm_state`` states, the conv over ``x | B |
    # C``, and ``ssm_chunk`` (the published block of the scan: the longest
    # block the block form is asked for; the engine's prefill chunk IS the
    # block). RMSNorm, RoPE, a dense SwiGLU MLP, serve path only.
    layer_kinds: Optional[Tuple[str, ...]] = None
    ssm_state: int = 16                # Mamba d_state
    ssm_conv: int = 4                  # depthwise causal conv kernel
    ssm_expand: int = 2                # Mamba-1: d_inner = ssm_expand * d_model
    ssm_dt_rank: Optional[int] = None  # Mamba-1; None => ceil(d_model / 16)
    ssm_width: Optional[int] = None    # Mamba-2 d_ssm (the parallel layout)
    ssm_heads: int = 0                 # Mamba-2 heads
    ssm_head_dim: int = 0              # Mamba-2 channels a head
    ssm_groups: int = 1                # Mamba-2 groups (B and C a group)
    ssm_chunk: int = 0                 # Mamba-2 published scan block
    # fixed multipliers of the parallel layout (muP-style, part of the
    # published configuration, all 1 elsewhere): on the embedding's output,
    # the logits, the attention branch's input, output and keys, the Mamba-2
    # branch's input and output, the five slices of its in-projection (z, x,
    # B, C, dt) and the MLP's gate and output
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: Optional[Tuple[float, ...]] = None    # None => five 1s
    mlp_multipliers: Optional[Tuple[float, ...]] = None    # None => two 1s

    # The third layout described (the linear hybrid: Olmo-Hybrid): periods
    # of ``"delta"`` layers closed by one ``"full"`` layer, ``("delta",) * a
    # + ("full",)`` repeated. A ``"delta"`` layer is a gated delta rule
    # (Gated DeltaNet, arXiv:2412.06464): ``delta_key_heads`` heads (as
    # many value heads: no other count is described) whose state is a
    # ``delta_key_dim`` x ``delta_value_dim`` float32 MATRIX a head,
    # corrected by a rank-one
    # term a token (``S = alpha S + beta k (v - alpha S^T k)^T``), behind a
    # depthwise causal conv of ``delta_conv`` taps over q | k | v;
    # ``delta_neg_eigval`` lets ``beta`` reach (0, 2) (negative eigenvalues,
    # arXiv:2411.12537). It holds a state slot and no keys. Its ``"full"``
    # layers are MHA/GQA without positional encoding (``positions="none"``)
    # whose q/k norm runs over the WHOLE projection (one gain as wide as
    # the projection, not ``qk_norm``'s gain a head), and both kinds norm a
    # branch's OUTPUT and nothing before it (``x + RMSNorm(mixer(x))``, two
    # norms a layer; ``post_norms`` is the form with four): the layout's
    # own, with no key of their own. Serve path only.
    delta_key_heads: int = 0
    delta_key_dim: int = 0
    delta_value_dim: int = 0
    delta_conv: int = 0
    delta_neg_eigval: bool = False

    # pipeline parallelism: microbatch count for the GPipe schedule when
    # the ambient mesh has pp > 1 (0 => 2 * pp, the usual bubble/memory
    # compromise); batch size must divide by it
    pp_microbatches: int = 0

    # numerics / memory
    dtype: str = "bfloat16"            # activation/param compute dtype
    param_dtype: str = "float32"       # master param dtype
    remat: bool = True                 # jax.checkpoint each layer (HBM <-> FLOPs)
    # "full" recomputes the whole layer in backward; "save_attn" saves the
    # attention block's output (named checkpoint) so backward recomputes
    # only norms + QKV/FFN matmuls — attention (the expensive recompute:
    # its custom VJP already re-tiles the O(L^2) blocks) runs once
    remat_policy: str = "full"
    logits_softcap: float = 0.0        # tanh soft-capping (0 = off)
    z_loss: float = 0.0                # output z-loss weight
    # blockwise LM-head + cross entropy over C-token chunks (0 = off):
    # avoids materializing the [B, L, V] f32 logits (the largest single
    # train-step buffer); backward recomputes each chunk under remat
    loss_chunk: int = 0

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def hdim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def ff(self) -> int:
        if self.d_ff is not None:
            return self.d_ff
        if self.mlp == "swiglu":
            # llama-style: 2/3 * 4d rounded up to a multiple of 256 (MXU tiles)
            raw = int(8 * self.d_model / 3)
            return (raw + 255) // 256 * 256
        return 4 * self.d_model

    @property
    def latent(self) -> bool:
        """Latent attention (MLA) with the DeepSeek-V3 layer stack."""
        return self.kv_lora_rank > 0

    @property
    def latent_width(self) -> int:
        """Values a token caches a layer: the latent and the rotated key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def held_experts(self) -> int:
        return self.num_experts if self.experts_held is None \
            else self.experts_held

    @property
    def ff_expert(self) -> int:
        return self.d_ff_expert or self.ff

    @property
    def rope_softmax_mscale(self) -> float:
        """YaRN's factor on the softmax scale (1 without YaRN)."""
        if self.rope_factor <= 1.0 or not self.rope_mscale_all_dim:
            return 1.0
        return (0.1 * self.rope_mscale_all_dim
                * math.log(self.rope_factor) + 1.0) ** 2

    @property
    def d_inner(self) -> int:
        """Channels of a state-space mixer: Mamba-2's own width where the
        layout gives one, else Mamba-1's ``ssm_expand * d_model``."""
        return self.ssm_width or self.ssm_expand * self.d_model

    @property
    def parallel_hybrid(self) -> bool:
        """Every layer runs attention and Mamba-2 side by side."""
        return self.layer_kinds is not None \
            and set(self.layer_kinds) == {"parallel"}

    @property
    def linear_hybrid(self) -> bool:
        """Periods of gated delta-rule layers closed by a full-attention
        layer (:mod:`ray_tpu.models.linear_hybrid`)."""
        return self.layer_kinds is not None and "delta" in self.layer_kinds

    @property
    def delta_periods(self) -> Tuple[int, int]:
        """(a, p) of the linear hybrid: ``(("delta",) * a + ("full",)) *
        p``."""
        a = self.layer_kinds.index("full")
        return a, len(self.layer_kinds) // (a + 1)

    @property
    def delta_key_width(self) -> int:
        return self.delta_key_heads * self.delta_key_dim

    @property
    def delta_value_width(self) -> int:
        return self.delta_key_heads * self.delta_value_dim

    @property
    def delta_conv_width(self) -> int:
        """Channels of a delta layer's conv: ``q | k | v``."""
        return 2 * self.delta_key_width + self.delta_value_width

    @property
    def window_pool(self) -> bool:
        """The layout has window layers with a pool of their own (SambaY's
        ``"window"`` layers; the windowed MoE layout's, where its
        ``attn_windows`` mix one window size with full layers)."""
        return (self.windowed_moe and self.mixed_windows) or (
            self.layer_kinds is not None and "window" in self.layer_kinds)

    @property
    def mixed_windows(self) -> bool:
        """``attn_windows`` mix ONE window size with full layers (0)."""
        windows = self.attn_windows or ()
        return len(set(windows) - {0}) == 1 and 0 in windows

    @property
    def expert_share(self) -> bool:
        """The config says how its expert layers route and what of them is
        held here (``dense_layers`` ... ``experts_first``): the latent
        layout always, a GQA decoder where one of those keys is set."""
        return self.latent or bool(
            self.dense_layers or self.shared_experts
            or self.experts_held is not None or self.d_ff_expert
            or self.expert_scoring != "softmax" or self.expert_scale != 1.0)

    @property
    def windowed_moe(self) -> bool:
        """GQA attention over the expert layers of ``expert_share``, with an
        output gate, post-norms or positions by layer kind: two stacks of
        layers (:mod:`ray_tpu.models.windowed_moe`)."""
        return not self.latent and self.layer_kinds is None and bool(
            self.expert_share or self.attn_gate or self.post_norms
            or self.rope_layers != "all" or self.expert_act != "silu"
            or self.router_input != "mlp_norm")

    @property
    def ssm_conv_width(self) -> int:
        """Channels of Mamba-2's conv: ``x | B | C``."""
        return self.d_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def ssm_proj_width(self) -> int:
        """Outputs of Mamba-2's in-projection: ``z | x | B | C | dt``."""
        return self.d_inner + self.ssm_conv_width + self.ssm_heads

    @property
    def ssm_mup(self) -> Tuple[float, ...]:
        return tuple(self.ssm_multipliers or (1.0,) * 5)

    @property
    def mlp_mup(self) -> Tuple[float, float]:
        return tuple(self.mlp_multipliers or (1.0, 1.0))

    @property
    def dt_rank(self) -> int:
        return self.ssm_dt_rank or -(-self.d_model // 16)

    @property
    def hybrid_periods(self) -> Tuple[int, int]:
        """(a, b) of the SambaY layout: (mamba, window) x a, (mamba, full),
        (gmu, cross) x b."""
        kinds = self.layer_kinds
        a = next(i for i, k in enumerate(kinds) if k == "full") // 2
        return a, (len(kinds) - 2 * a - 2) // 2

    def __post_init__(self):
        for name in ("ssm_multipliers", "mlp_multipliers"):
            if getattr(self, name) is not None:
                object.__setattr__(self, name, tuple(
                    float(m) for m in getattr(self, name)))
        if (self.attn_gate or self.post_norms or self.rope_layers != "all"
                or self.expert_act != "silu"
                or self.router_input != "mlp_norm"
                ) and (self.latent or self.layer_kinds is not None):
            raise ValueError(
                "an attention output gate, post-norms, positions by layer "
                "kind, an expert activation by name and a router ahead of "
                "the attention (attn_gate, post_norms, rope_layers, "
                "expert_act, router_input) are described for a uniform GQA "
                "decoder only, not with kv_lora_rank or layer_kinds")
        mamba2 = (self.ssm_width is not None or self.ssm_heads
                  or self.ssm_head_dim or self.ssm_groups != 1
                  or self.ssm_chunk)
        # (the windowed MoE layout has the embedding's scale and no other)
        scaled = any(m != 1.0 for m in (
            1.0 if self.windowed_moe else self.embedding_multiplier,
            self.lm_head_multiplier,
            self.attention_in_multiplier, self.attention_out_multiplier,
            self.key_multiplier, self.ssm_in_multiplier,
            self.ssm_out_multiplier)) or self.ssm_multipliers is not None \
            or self.mlp_multipliers is not None
        if self.layer_kinds is not None and "parallel" in self.layer_kinds:
            kinds = tuple(self.layer_kinds)
            object.__setattr__(self, "layer_kinds", kinds)
            if (set(kinds) != {"parallel"} or len(kinds) != self.n_layers
                    or not self.ssm_heads or not self.ssm_head_dim
                    or self.ssm_width != self.ssm_heads * self.ssm_head_dim
                    or self.ssm_groups < 1
                    or self.ssm_heads % self.ssm_groups
                    or self.ssm_state < 1 or self.ssm_conv < 2
                    or self.ssm_chunk < 1
                    or self.ssm_dt_rank is not None or self.ssm_expand != 2
                    or len(self.ssm_mup) != 5 or len(self.mlp_mup) != 2
                    or self.norm != "rms" or self.positions != "rope"
                    or self.mlp != "swiglu" or self.n_heads % self.kv_heads
                    or self.sliding_window or self.attn_windows
                    or self.attn_qkv_bias or self.qk_norm
                    or self.attn_softcap or self.num_experts
                    or self.index_heads or self.kv_lora_rank):
                raise ValueError(
                    "the parallel layout described is (parallel,) x "
                    "n_layers: RoPE GQA attention without biases beside a "
                    "Mamba-2 mixer of ssm_width = ssm_heads x ssm_head_dim "
                    "in ssm_groups groups with an ssm_chunk, five "
                    "ssm_multipliers and two mlp_multipliers, RMSNorm and a "
                    "dense SwiGLU MLP; no window, softcap, q/k-norm, "
                    "experts, indexer or latent attention, and none of "
                    "Mamba-1's keys (ssm_dt_rank, ssm_expand); got "
                    f"{kinds!r}")
        elif mamba2 or scaled:
            raise ValueError(
                "Mamba-2's sizes (ssm_width, ssm_heads, ssm_head_dim, "
                "ssm_groups, ssm_chunk) and the fixed multipliers are "
                "described for the parallel layout (layer_kinds all "
                "'parallel') only")
        elif self.layer_kinds is not None and "delta" in self.layer_kinds:
            self._check_linear_hybrid()
        elif self.layer_kinds is not None:
            kinds = tuple(self.layer_kinds)
            object.__setattr__(self, "layer_kinds", kinds)
            if "full" not in kinds:
                raise ValueError(f"layer_kinds {kinds!r} has no full layer")
            a, b = self.hybrid_periods
            want = ("mamba", "window") * a + ("mamba", "full") \
                + ("gmu", "cross") * b
            if (kinds != want or a < 1 or b < 1
                    or len(kinds) != self.n_layers
                    or self.sliding_window < 1 or self.n_heads % 4
                    or self.kv_heads * 2 != self.n_heads
                    or self.num_experts or self.index_heads):
                raise ValueError(
                    "the hybrid layout described is (mamba, window) x a, "
                    "(mamba, full), (gmu, cross) x b over n_layers layers "
                    "with a sliding_window, query heads in pairs over KV "
                    f"heads in pairs and a dense MLP; got {kinds!r}")
        if not self.linear_hybrid and (
                self.delta_key_heads or self.delta_key_dim
                or self.delta_value_dim or self.delta_conv
                or self.delta_neg_eigval):
            raise ValueError(
                "the delta rule's sizes (delta_key_heads, delta_key_dim, "
                "delta_value_dim, delta_conv, delta_neg_eigval) are "
                "described for the linear hybrid layout (layer_kinds of "
                "'delta' and 'full') only")
        if self.latent:
            held = self.held_experts
            if (not self.q_lora_rank or not self.qk_nope_head_dim
                    or self.qk_rope_head_dim < 2 or self.qk_rope_head_dim % 2
                    or not self.v_head_dim
                    or self.layer_kinds is not None or self.index_heads
                    or self.sliding_window or self.attn_windows
                    or self.norm != "rms" or self.positions != "rope"
                    or not 0 <= self.dense_layers <= self.n_layers
                    or (self.dense_layers < self.n_layers
                        and not self.num_experts)
                    or self.expert_scoring not in ("softmax", "sigmoid")
                    or not 0 < held
                    or self.experts_first + held > max(self.num_experts, 1)):
                raise ValueError(
                    "a latent-attention model needs q_lora_rank, "
                    "qk_nope_head_dim, an even qk_rope_head_dim and "
                    "v_head_dim, RMSNorm and RoPE, no window, indexer or "
                    "layer_kinds, dense_layers within n_layers with experts "
                    "after them, and the held experts within num_experts")
        elif self.rope_factor != 1.0:
            raise ValueError(
                "YaRN is described for the latent-attention layout "
                "(kv_lora_rank) only")
        elif self.windowed_moe:
            self._check_windowed_moe()
        elif self.expert_share:
            raise ValueError(
                "leading dense layers, shared experts, a share of the "
                "experts and sigmoid routing are described for the "
                "latent-attention layout (kv_lora_rank) and for a uniform "
                "GQA decoder (layer_kinds None) only")
        if self.remat_policy not in ("full", "save_attn"):
            raise ValueError(
                f"unknown remat_policy {self.remat_policy!r}; "
                "expected 'full' or 'save_attn'")
        if self.attn_windows is not None:
            if not self.attn_windows or any(
                    not isinstance(w, int) or w < 0
                    for w in self.attn_windows):
                raise ValueError(
                    f"attn_windows must be a non-empty tuple of ints >= 0 "
                    f"(0 = global), got {self.attn_windows!r}")
            if self.n_layers % len(self.attn_windows):
                raise ValueError(
                    f"n_layers {self.n_layers} not divisible by the "
                    f"attn_windows pattern length {len(self.attn_windows)}")

    def _check_linear_hybrid(self) -> None:
        """The linear hybrid layout as described."""
        kinds = tuple(self.layer_kinds)
        object.__setattr__(self, "layer_kinds", kinds)
        a = kinds.index("full") if "full" in kinds else 0
        if (a < 1 or len(kinds) != self.n_layers or len(kinds) % (a + 1)
                or kinds != (("delta",) * a + ("full",))
                * (len(kinds) // (a + 1))
                or self.delta_key_heads < 1
                or self.delta_key_dim < 1 or self.delta_value_dim < 1
                or self.delta_conv < 2
                or self.norm != "rms" or self.positions != "none"
                or self.mlp != "swiglu" or self.n_heads % self.kv_heads
                or self.sliding_window or self.attn_windows
                or self.attn_qkv_bias or self.qk_norm or self.attn_softcap
                or self.num_experts or self.index_heads
                or self.kv_lora_rank or self.tie_embeddings):
            raise ValueError(
                "the linear hybrid layout described is (('delta',) x a, "
                "'full') x p over n_layers layers: a gated delta rule of "
                "delta_key_heads heads (delta_key_dim, delta_value_dim, a "
                "conv of delta_conv taps), full layers with "
                "positions='none', RMSNorm, a dense SwiGLU MLP and an "
                "untied head; no window, softcap, biases, per-head q/k-norm, "
                f"experts, indexer or latent attention; got {kinds!r}")

    def _check_windowed_moe(self) -> None:
        """The windowed MoE layout as described, each refusal by name."""
        held = self.held_experts
        if (self.norm != "rms" or self.mlp != "swiglu"
                or self.positions != "rope" or self.attn_qkv_bias
                or self.attn_softcap or self.index_heads
                or self.n_heads % self.kv_heads):
            raise ValueError(
                "the windowed MoE layout is RMSNorm, SwiGLU and RoPE GQA "
                "attention without biases, softcap or indexer")
        if (not self.num_experts
                or not 0 <= self.dense_layers < self.n_layers
                or self.expert_scoring not in ("softmax", "sigmoid")
                or not 0 < held
                or self.experts_first + held > self.num_experts):
            raise ValueError(
                "the windowed MoE layout needs experts after dense_layers "
                "leading dense layers (fewer than n_layers), softmax or "
                "sigmoid routing, and the held experts within num_experts")
        if self.rope_layers not in ("all", "window"):
            raise ValueError(
                f"rope_layers {self.rope_layers!r}: 'all' or 'window'")
        if self.expert_act not in ("silu", "relu"):
            raise ValueError(
                f"expert_act {self.expert_act!r}: 'silu' or 'relu'")
        if self.expert_act != "silu" and self.shared_experts:
            raise ValueError(
                f"expert_act {self.expert_act!r} beside a shared expert: "
                "the shared expert is described as SwiGLU only")
        if self.router_input not in ("mlp_norm", "attn_norm"):
            raise ValueError(
                f"router_input {self.router_input!r}: 'mlp_norm' (the "
                "experts' own input) or 'attn_norm' (the layer's normed "
                "input, ahead of the attention)")
        if self.rope_layers == "window" and not self.mixed_windows:
            raise ValueError(
                "rope_layers='window' needs attn_windows that mix one "
                "window size with full layers (0)")
        if self.mixed_windows \
                and self.sliding_window != max(self.attn_windows):
            raise ValueError(
                "the windowed MoE layout's window layers release their "
                "blocks where attn_windows mix one window size with full "
                "layers (0): it needs sliding_window equal to that size, "
                "the window pool's table has one width")

    @property
    def window_pattern(self) -> Tuple[int, ...]:
        """The repeating per-layer window pattern (0 = global)."""
        if self.attn_windows is not None:
            return tuple(self.attn_windows)
        return (self.sliding_window,)

    @property
    def layer_windows(self) -> Tuple[int, ...]:
        """Window per layer, expanded to all n_layers."""
        pat = self.window_pattern
        return pat * (self.n_layers // len(pat))

    @property
    def uniform_window(self) -> int:
        """The single window shared by ALL layers, or 0 when layers mix
        (or no window). Ring KV caches require a uniform window."""
        if self.layer_kinds is not None:
            return 0        # window, full and state-space layers mixed
        pat = set(self.window_pattern)
        return self.window_pattern[0] if len(pat) == 1 else 0

    def replace(self, **kw) -> "TransformerConfig":
        return dataclasses.replace(self, **kw)

    def num_params(self) -> int:
        """Parameter count (embeddings included once if tied)."""
        d, f, hd = self.d_model, self.ff, self.hdim
        if self.parallel_hybrid:
            return self._parallel_params()
        if self.linear_hybrid:
            return self._linear_hybrid_params()
        if self.layer_kinds is not None:
            return self._hybrid_params()
        if self.latent:
            return self._latent_params()
        if self.windowed_moe:
            return self._windowed_moe_params()
        attn = d * hd * self.n_heads + 2 * d * hd * self.kv_heads + hd * self.n_heads * d
        if self.attn_qkv_bias:
            attn += hd * (self.n_heads + 2 * self.kv_heads)
        if self.mlp == "swiglu":
            mlp = 3 * d * f
        else:
            mlp = 2 * d * f + f + d  # + b_in/b_out biases
        if self.num_experts:
            mlp = mlp * self.num_experts + d * self.num_experts  # + router
        attn += self._extra_attn_params()
        norms = 2 * d
        final_norm = d
        if self.norm == "layer":  # per-norm bias vectors
            norms += 2 * d
            final_norm += d
        per_layer = attn + mlp + norms
        emb = self.vocab_size * d
        head = 0 if self.tie_embeddings else self.vocab_size * d
        pos = self.max_seq_len * d if self.positions == "learned" else 0
        return self.n_layers * per_layer + emb + head + pos + final_norm

    def _hybrid_params(self) -> int:
        """Parameters of a hybrid layout (tied or untied head, LayerNorm
        with bias, projection biases, differential attention's four lambda
        vectors and sub-norm gain)."""
        d, f, hd, di = self.d_model, self.ff, self.hdim, self.d_inner
        q, kv = self.n_heads * hd, self.kv_heads * hd
        n, r, k = self.ssm_state, self.dt_rank, self.ssm_conv
        diff = 4 * hd + 2 * hd
        mixer = {
            "mamba": (d * 2 * di + k * di + di + di * (r + 2 * n) + r * di
                      + di + n * di + di + di * d),
            "window": d * q + q + 2 * (d * kv + kv) + q * d + d + diff,
            "cross": d * q + q + q * d + d + diff,
            "gmu": 2 * d * di,
        }
        mixer["full"] = mixer["window"]
        per_layer = 3 * d * f + 4 * d       # MLP, two LayerNorms with bias
        emb = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        return (sum(mixer[kind] + per_layer for kind in self.layer_kinds)
                + emb + 2 * d)

    def _parallel_parts(self) -> dict:
        """Parameters of ONE layer of the parallel layout by part: Mamba-2
        (in-projection, conv with bias, ``dt_bias``, ``A_log``, ``D``, the
        gated norm's gain, out-projection), attention, the MLP, the two
        RMSNorms."""
        d, f, hd, ds = self.d_model, self.ff, self.hdim, self.d_inner
        q, kv = self.n_heads * hd, self.kv_heads * hd
        cw = self.ssm_conv_width
        return {
            "mamba": (d * self.ssm_proj_width + self.ssm_conv * cw + cw
                      + 3 * self.ssm_heads + ds + ds * d),
            "attn": d * q + 2 * d * kv + q * d,
            "mlp": 3 * d * f,
            "norms": 2 * d,
        }

    def _parallel_params(self) -> int:
        emb = self.vocab_size * self.d_model \
            * (1 if self.tie_embeddings else 2)
        return (self.n_layers * sum(self._parallel_parts().values())
                + emb + self.d_model)

    def _linear_hybrid_parts(self) -> dict:
        """Parameters by part of the linear hybrid layout: ``delta`` (a
        delta layer's mixer: q, k, v and the output gate's projections, the
        two head-wide projections behind ``alpha`` and ``beta``, three
        depthwise convs without bias, ``A_log``, ``dt_bias``, the head
        norm's gain, the out-projection), ``attn`` (a full layer's four
        projections and its two projection-wide q/k norms), ``mlp``,
        ``norms`` (a layer's two)."""
        d, hd = self.d_model, self.hdim
        q, kv = self.n_heads * hd, self.kv_heads * hd
        kw, vw, h = (self.delta_key_width, self.delta_value_width,
                     self.delta_key_heads)
        return {
            "delta": (d * (2 * kw + 2 * vw) + vw * d + 2 * d * h
                      + self.delta_conv * self.delta_conv_width + 2 * h
                      + self.delta_value_dim),
            "attn": 2 * d * q + 2 * d * kv + q + kv,
            "mlp": 3 * d * self.ff,
            "norms": 2 * d,
        }

    def _linear_hybrid_params(self) -> int:
        p = self._linear_hybrid_parts()
        a, periods = self.delta_periods
        return (periods * (a * p["delta"] + p["attn"])
                + self.n_layers * (p["mlp"] + p["norms"])
                + 2 * self.vocab_size * self.d_model + self.d_model)

    def _latent_parts(self) -> dict:
        """Parameters by part of a latent-attention model: ``attn`` (a
        layer's MLA with its three norms' gains), ``dense`` (a dense
        layer's MLP), ``expert`` (ONE routed expert), ``shared``, ``router``
        (weights and selection bias), ``norms`` (a layer's two)."""
        d, h = self.d_model, self.n_heads
        qr, kr = self.q_lora_rank, self.kv_lora_rank
        nope, rope, v = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                         self.v_head_dim)
        fe = self.ff_expert
        return {
            "attn": (d * qr + qr + qr * h * (nope + rope) + d * (kr + rope)
                     + kr + kr * h * (nope + v) + h * v * d),
            "dense": 3 * d * self.ff,
            "expert": 3 * d * fe,
            "shared": 3 * d * fe * self.shared_experts,
            "router": d * self.num_experts
            + (self.num_experts if self.expert_scoring == "sigmoid" else 0),
            "norms": 2 * d,
        }

    def _latent_params(self, experts: Optional[int] = None) -> int:
        """Parameters HELD by this program (``experts_held`` routed experts
        a layer, the rows of the vocabulary it is given), or with
        ``experts`` that many routed experts a layer."""
        p = self._latent_parts()
        n_dense = self.dense_layers
        n_moe = self.n_layers - n_dense
        e = self.held_experts if experts is None else experts
        emb = self.vocab_size * self.d_model \
            * (1 if self.tie_embeddings else 2)
        return (self.n_layers * (p["attn"] + p["norms"])
                + n_dense * p["dense"]
                + n_moe * (e * p["expert"] + p["shared"] + p["router"])
                + emb + self.d_model)

    def _windowed_moe_parts(self) -> dict:
        """Parameters by part of the windowed MoE layout, as
        ``_latent_parts`` gives a latent model's: ``attn`` is a layer's
        projections with the gate's and the head norms' gains."""
        d, hd = self.d_model, self.hdim
        q, kv = self.n_heads * hd, self.kv_heads * hd
        fe = self.ff_expert
        return {
            "attn": (d * q * (3 if self.attn_gate else 2) + 2 * d * kv
                     + (2 * hd if self.qk_norm else 0)),
            "dense": 3 * d * self.ff,
            "expert": 3 * d * fe,
            "shared": 3 * d * fe * self.shared_experts,
            "router": d * self.num_experts
            + (self.num_experts if self.expert_scoring == "sigmoid" else 0),
            "norms": (4 if self.post_norms else 2) * d,
        }

    def _windowed_moe_params(self, experts: Optional[int] = None) -> int:
        """Parameters HELD by this program, or with ``experts`` routed
        experts a layer (``_latent_params``'s rule)."""
        p = self._windowed_moe_parts()
        n_moe = self.n_layers - self.dense_layers
        e = self.held_experts if experts is None else experts
        emb = self.vocab_size * self.d_model \
            * (1 if self.tie_embeddings else 2)
        return (self.n_layers * (p["attn"] + p["norms"])
                + self.dense_layers * p["dense"]
                + n_moe * (e * p["expert"] + p["shared"] + p["router"])
                + emb + self.d_model)

    def _extra_attn_params(self) -> int:
        """q/k-norm gains and the indexer's projections (wq_i, wk_i, the
        head weights, LayerNorm gain and bias on its key)."""
        n = 2 * self.hdim if self.qk_norm else 0
        if self.index_heads:
            j, di = self.index_heads, self.index_head_dim
            n += self.d_model * (j * di + di + j) + 2 * di
        return n

    def active_params(self) -> int:
        """Parameters one token is multiplied by: of a layer's experts only
        the ``expert_top_k`` it is routed to (all of ``num_params`` in a
        dense model)."""
        if not self.num_experts:
            return self.num_params()
        if self.latent:
            # of the published router's choice, whatever share is held here
            return self._latent_params(experts=self.expert_top_k)
        if self.windowed_moe:
            return self._windowed_moe_params(experts=self.expert_top_k)
        d, f = self.d_model, self.ff
        expert = 3 * d * f if self.mlp == "swiglu" else 2 * d * f + f + d
        idle = (self.num_experts - self.expert_top_k) * expert
        return self.num_params() - self.n_layers * idle

    def flops_per_token(self) -> int:
        """Approx training FLOPs/token (fwd+bwd ≈ 6N + attention quadratic),
        N the parameters a token USES: the experts it is routed to, not all
        of them, and the indexer's projections."""
        n = self.active_params()
        emb = self.vocab_size * self.d_model * (1 if self.tie_embeddings else 2)
        return 6 * (n - emb)


# ---------------------------------------------------------------------------
# Presets. llama3_* mirror public Llama-3 shapes; *_debug are CI-sized.
# ---------------------------------------------------------------------------

def llama3_8b() -> TransformerConfig:
    return TransformerConfig(
        vocab_size=128256, d_model=4096, n_layers=32, n_heads=32, n_kv_heads=8,
        d_ff=14336, max_seq_len=8192, mlp="swiglu", norm="rms",
        positions="rope", rope_theta=500000.0,
    )


def llama3_70b() -> TransformerConfig:
    return TransformerConfig(
        vocab_size=128256, d_model=8192, n_layers=80, n_heads=64, n_kv_heads=8,
        d_ff=28672, max_seq_len=8192, mlp="swiglu", norm="rms",
        positions="rope", rope_theta=500000.0,
    )


def llama_1b() -> TransformerConfig:
    """~1.2B params — fits one v5e chip in bf16 with optimizer state sharded."""
    return TransformerConfig(
        vocab_size=32000, d_model=2048, n_layers=16, n_heads=16, n_kv_heads=8,
        d_ff=5632, max_seq_len=4096,
    )


def llama_250m() -> TransformerConfig:
    """~250M-param model: large enough that the MXU dominates, small
    enough to init fast on one chip."""
    return TransformerConfig(
        vocab_size=32000, d_model=1024, n_layers=12, n_heads=16, n_kv_heads=8,
        d_ff=2816, max_seq_len=2048,
    )


def llama_debug() -> TransformerConfig:
    """Tiny config for tests and the multichip dryrun."""
    return TransformerConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=128, remat=False,
    )


def gpt2_small() -> TransformerConfig:
    return TransformerConfig(
        vocab_size=50257, d_model=768, n_layers=12, n_heads=12,
        d_ff=3072, max_seq_len=1024, mlp="gelu", norm="layer",
        positions="learned", tie_embeddings=True,
    )


def gpt2_debug() -> TransformerConfig:
    return TransformerConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4,
        d_ff=256, max_seq_len=128, mlp="gelu", norm="layer",
        positions="learned", tie_embeddings=True, remat=False,
    )


def gemma2_9b() -> TransformerConfig:
    """Gemma-2-9B-family shape: GQA, tied embeddings, tanh softcaps on
    both attention logits (50.0) and output logits (30.0), and the EXACT
    per-layer alternating windows — sliding 4096 on even layers, global on
    odd (HF gemma-2 ``layer_types`` order: layer 0 is sliding). Remaining
    known delta vs the real checkpoint: Gemma-2's pre+post sandwich norms
    are modeled as pre-norms only."""
    return TransformerConfig(
        vocab_size=256128, d_model=3584, n_layers=42, n_heads=16,
        n_kv_heads=8, head_dim=256, d_ff=14336, max_seq_len=8192,
        tie_embeddings=True, logits_softcap=30.0, attn_softcap=50.0,
        attn_windows=(4096, 0),
    )


def gemma_debug() -> TransformerConfig:
    """Tiny gemma-2-style config for tests: alternating windows (local
    layer 0, global layer 1), attention + logits softcaps, GQA, tied
    embeddings."""
    return TransformerConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=128, tie_embeddings=True, logits_softcap=30.0,
        attn_softcap=50.0, attn_windows=(24, 0),
        remat=False,
    )


def mistral_7b() -> TransformerConfig:
    """Mistral-7B-family shape: GQA + 4096-token sliding-window attention."""
    return TransformerConfig(
        vocab_size=32000, d_model=4096, n_layers=32, n_heads=32, n_kv_heads=8,
        d_ff=14336, max_seq_len=8192, sliding_window=4096,
    )


def mistral_debug() -> TransformerConfig:
    """Tiny sliding-window config for tests."""
    return TransformerConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=128, sliding_window=24, remat=False,
    )


def qwen2_7b() -> TransformerConfig:
    """Qwen2-7B-family shape: GQA + q/k/v biases, large vocab, theta 1M.
    Weight-portable via ``models.import_hf`` (exact parity incl. the
    bias path)."""
    return TransformerConfig(
        vocab_size=152064, d_model=3584, n_layers=28, n_heads=28,
        n_kv_heads=4, d_ff=18944, max_seq_len=32768,
        rope_theta=1_000_000.0, norm_eps=1e-6, attn_qkv_bias=True,
    )


def qwen2_debug() -> TransformerConfig:
    """Tiny qwen2-style config for tests: GQA + qkv biases."""
    return TransformerConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=128, attn_qkv_bias=True, remat=False,
    )


def moe_debug() -> TransformerConfig:
    return TransformerConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=4,
        d_ff=128, max_seq_len=128, num_experts=4, expert_top_k=2, remat=False,
    )


def hybrid_state_debug() -> TransformerConfig:
    """Tiny config of the hybrid state-space / attention decoder family
    (SambaY) for tests: all five layer kinds in three segments of two
    periods each but the middle one, window 8, no positional encoding
    (serve path only)."""
    return TransformerConfig(
        vocab_size=256, d_model=64, n_layers=10, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=128, max_seq_len=256, mlp="swiglu", norm="layer",
        positions="none", norm_eps=1e-5, tie_embeddings=True,
        sliding_window=8, ssm_state=4, ssm_dt_rank=8,
        layer_kinds=("mamba", "window") * 2 + ("mamba", "full")
        + ("gmu", "cross") * 2, remat=False,
    )


def parallel_hybrid_debug() -> TransformerConfig:
    """Tiny config of the parallel attention / Mamba-2 decoder family
    (Falcon-H1) for tests: three layers, each with 4 query heads over 2 KV
    heads beside 4 Mamba-2 heads of 8 in 2 groups of 8 states, a conv over
    ``x | B | C`` (64 channels), the twelve fixed multipliers all away from
    1, an untied head (serve path only)."""
    return TransformerConfig(
        vocab_size=256, d_model=64, n_layers=3, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=128, max_seq_len=512, norm_eps=1e-5,
        rope_theta=1e11, layer_kinds=("parallel",) * 3,
        ssm_width=32, ssm_heads=4, ssm_head_dim=8, ssm_groups=2,
        ssm_state=8, ssm_conv=4, ssm_chunk=32,
        embedding_multiplier=2.0, lm_head_multiplier=0.5,
        attention_in_multiplier=0.75, attention_out_multiplier=0.6,
        key_multiplier=0.4, ssm_in_multiplier=0.8, ssm_out_multiplier=0.7,
        ssm_multipliers=(0.9, 1.5, 0.6, 2.5, 1.3),
        mlp_multipliers=(1.6, 0.55), remat=False,
    )


def linear_hybrid_debug() -> TransformerConfig:
    """Tiny config of the linear hybrid decoder family (the Olmo-Hybrid
    layer) for tests: two periods of three gated delta-rule layers (4 heads
    whose state is 8 x 64, so two heads fill the 128 lanes of a state's
    row as the published 192-wide heads do; a conv of 4 taps; ``beta`` in
    (0, 2)) closed by an MHA layer of 6 heads (three 32-bit pairs: the pool
    pads them as it pads the published 30) with a q/k norm over the whole
    projection and no positional encoding, norms on the branches' outputs,
    an untied head (serve path only)."""
    return TransformerConfig(
        vocab_size=256, d_model=64, n_layers=8, n_heads=6, head_dim=16,
        d_ff=128, max_seq_len=512, norm_eps=1e-6, positions="none",
        layer_kinds=(("delta",) * 3 + ("full",)) * 2,
        delta_key_heads=4, delta_key_dim=8, delta_value_dim=64,
        delta_conv=4, delta_neg_eigval=True, remat=False,
    )


def latent_moe_debug() -> TransformerConfig:
    """Tiny config of the latent-attention MoE decoder family (the
    DeepSeek-V3 layer) for tests: MLA with a 24-value latent and an 8-value
    rotated key, YaRN over an original length of 32, one leading dense
    layer, then expert layers that HOLD 4 of 16 sigmoid-routed experts
    (top-4, a selection bias, a scaling factor) beside a shared expert
    (serve path only)."""
    return TransformerConfig(
        vocab_size=256, d_model=64, n_layers=3, n_heads=4, d_ff=128,
        max_seq_len=512, norm_eps=1e-5, rope_theta=50000.0,
        q_lora_rank=32, kv_lora_rank=24, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16,
        rope_factor=16.0, rope_beta_fast=32.0, rope_beta_slow=1.0,
        rope_original_len=32, rope_mscale=1.0, rope_mscale_all_dim=1.0,
        dense_layers=1, d_ff_expert=32, shared_experts=1, num_experts=16,
        expert_top_k=4, expert_norm_topk=True, expert_scoring="sigmoid",
        expert_scale=2.5, experts_held=4, experts_first=4, remat=False,
    )


def windowed_moe_debug() -> TransformerConfig:
    """Tiny config of the windowed MoE decoder family (the Trinity layer)
    for tests: gated GQA attention with q/k-norm and four norms a layer,
    five layers whose windows are (8, 8, 8, 0, 8) with RoPE in the window
    layers only and the window layers' blocks released, one leading dense
    layer, then expert layers that HOLD 4 of 16 sigmoid-routed experts
    (top-4, a selection bias, a scaling factor) beside a shared expert, the
    embedding scaled by ``sqrt(d_model)`` (serve path only)."""
    return TransformerConfig(
        vocab_size=256, d_model=64, n_layers=5, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=128, max_seq_len=512, norm_eps=1e-5,
        rope_theta=10000.0, qk_norm=True, attn_gate=True, post_norms=True,
        rope_layers="window", sliding_window=8,
        attn_windows=(8, 8, 8, 0, 8), embedding_multiplier=8.0,
        dense_layers=1, d_ff_expert=32, shared_experts=1, num_experts=16,
        expert_top_k=4, expert_norm_topk=True, expert_scoring="sigmoid",
        expert_scale=2.5, experts_held=4, experts_first=4, remat=False,
    )


def smallthinker_debug() -> TransformerConfig:
    """Tiny config of the windowed MoE layout as SmallThinker has it, for
    tests: plain GQA attention (no gate, q/k-norm or post-norms), eight
    layers in two periods that START with the full layer (windows (0, 8, 8,
    8), RoPE in the window layers only, their blocks released), no dense
    layer and no shared expert, 8 ReLU-gated experts top-3 held WHOLE and
    routed by a softmax router that reads the layer's normed INPUT, ahead of
    the attention; ``d_model`` 384 is whole lanes and not whole ``[8, 128]``
    tiles, as the model's 2560 (serve path only)."""
    return TransformerConfig(
        vocab_size=256, d_model=384, n_layers=8, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=128, max_seq_len=512, norm_eps=1e-6,
        rope_theta=1_500_000.0, tie_embeddings=False,
        rope_layers="window", sliding_window=8, attn_windows=(0, 8, 8, 8),
        d_ff_expert=128, num_experts=8, expert_top_k=3,
        expert_norm_topk=True, expert_act="relu", router_input="attn_norm",
        remat=False,
    )


def sparse_moe_debug() -> TransformerConfig:
    """Tiny config of the sparse-attention MoE decoder family for tests:
    q/k-norm, 8 dropless experts top-2 with renormalised weights, and an
    indexer that keeps 16 keys (serve path only)."""
    return TransformerConfig(
        vocab_size=256, d_model=64, n_layers=4, n_heads=4, n_kv_heads=2,
        d_ff=32, max_seq_len=128, norm_eps=1e-6, qk_norm=True,
        num_experts=8, expert_top_k=2, expert_norm_topk=True,
        index_heads=4, index_head_dim=16, index_topk=16, remat=False,
    )


PRESETS = {
    "llama3-8b": llama3_8b,
    "llama3-70b": llama3_70b,
    "llama-1b": llama_1b,
    "llama-250m": llama_250m,
    "llama-debug": llama_debug,
    "gpt2-small": gpt2_small,
    "gpt2-debug": gpt2_debug,
    "gemma2-9b": gemma2_9b,
    "gemma-debug": gemma_debug,
    "mistral-7b": mistral_7b,
    "mistral-debug": mistral_debug,
    "qwen2-7b": qwen2_7b,
    "qwen2-debug": qwen2_debug,
    "moe-debug": moe_debug,
    "sparse-moe-debug": sparse_moe_debug,
    "hybrid-state-debug": hybrid_state_debug,
    "latent-moe-debug": latent_moe_debug,
    "windowed-moe-debug": windowed_moe_debug,
    "smallthinker-debug": smallthinker_debug,
    "parallel-hybrid-debug": parallel_hybrid_debug,
    "linear-hybrid-debug": linear_hybrid_debug,
}


def get_config(name: str) -> TransformerConfig:
    try:
        return PRESETS[name]()
    except KeyError:
        raise ValueError(f"unknown preset {name!r}; have {sorted(PRESETS)}")
