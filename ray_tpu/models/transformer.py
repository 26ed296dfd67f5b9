"""Decoder-only transformer: pure-function forward over a param pytree.

TPU-native design notes:

- Parameters are plain pytrees (nested dicts of arrays) with a parallel
  *logical-axes* pytree (:func:`param_axes`); sharding is applied by mapping
  logical names through :mod:`ray_tpu.parallel.sharding` rules — no module
  wrappers (contrast the reference's DDP/FSDP wrapping at
  ``python/ray/train/torch/train_loop_utils.py:158``).
- Layers are **stacked** on a leading dim and the forward runs ``lax.scan``
  over them: one layer gets traced/compiled once regardless of depth, and
  XLA pipelines the weight prefetch of layer i+1 against layer i's compute.
- ``jax.checkpoint`` around the scanned body trades FLOPs for HBM (standard
  remat policy for LLM training).
- Attention dispatches to the Pallas flash kernel on TPU, the blockwise XLA
  kernel elsewhere, and ring attention (``lax.ppermute`` over the ``sp``
  mesh axis) when the ambient mesh has a nontrivial sequence-parallel axis.
- All matmuls run in ``config.dtype`` (bf16 by default) on the MXU; norms,
  softmax, and the loss accumulate in f32.
"""

from __future__ import annotations

import contextlib
import functools
from types import SimpleNamespace
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.config import TransformerConfig
from ray_tpu.models.layouts import layout_of, serve_only
from ray_tpu.ops.attention import naive_attention
from ray_tpu.ops.layers import (apply_rotary, layer_norm, rms_norm,
                                rotary_embedding)
from ray_tpu.ops.moe import (Route, moe_experts, moe_layer_dense,
                             moe_layer_dropless, moe_route)
from ray_tpu.ops.paged_attention import LANES, paged_attention
from ray_tpu.ops.sparse_attention import (paged_sparse_attention,
                                          write_index_keys)
from ray_tpu.parallel.sharding import constrain

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_params(rng: jax.Array, config: TransformerConfig) -> Params:
    """Initialize a parameter pytree (layers stacked on a leading dim), by
    the configuration's layout (:mod:`ray_tpu.models.layouts`)."""
    return layout_of(config).init_params(rng, config)


def param_axes(config: TransformerConfig) -> Params:
    """Logical-axes pytree matching :func:`init_params` leaf-for-leaf."""
    return layout_of(config).param_axes(config)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _qkv_proj(h, lp, dt, eps: float = 1e-6, hd: Optional[int] = None):
    """q/k/v projections (+ optional Qwen2-style qkv biases, + optional
    per-head RMSNorm on q and k over the head size, before RoPE). Matrices
    stored as they are multiplied (``[d, heads * hd]``: the windowed MoE
    layout) are split into heads of ``hd`` after the product."""
    if lp["wq"].ndim == 2:
        # the barrier keeps the split into heads out of the product: folded
        # into it, the compiler turns the MATRIX round to ``[heads, hd, d]``
        # (a copy of the whole stack it is indexed out of, 151 MB a step at
        # 4 x 3072 x 6144) where turning the product round is 3 MB
        q, k, v = (lax.optimization_barrier(
            jnp.einsum("bld,de->ble", h, lp[n].astype(dt)))
            .reshape(*h.shape[:2], -1, hd) for n in ("wq", "wk", "wv"))
    else:
        q = jnp.einsum("bld,dhk->blhk", h, lp["wq"].astype(dt))
        k = jnp.einsum("bld,dhk->blhk", h, lp["wk"].astype(dt))
        v = jnp.einsum("bld,dhk->blhk", h, lp["wv"].astype(dt))
    if "bq" in lp:
        q = q + lp["bq"].astype(dt)
        k = k + lp["bk"].astype(dt)
        v = v + lp["bv"].astype(dt)
    if "q_norm" in lp:
        q = rms_norm(q, lp["q_norm"], eps=eps)
        k = rms_norm(k, lp["k_norm"], eps=eps)
    return q, k, v


def _indexer_proj(h, lp, positions, c, dt):
    """The sparse-attention indexer's query heads ``qi [B, C, J, di]``, its
    one key ``ki [B, C, di]`` (LayerNorm, then RoPE like the queries) and
    the head weights ``w [B, C, J]`` of each position, all float32: what
    they decide is discrete (which keys a query reads), so nothing here is
    rounded between the projection and the score but the key itself, once,
    when it is written to its pool."""
    f32 = jnp.float32
    with jax.named_scope("dsa_indexer"):
        qi = jnp.einsum("bld,djk->bljk", h, lp["wq_i"].astype(dt),
                        preferred_element_type=f32)
        ki = jnp.einsum("bld,dk->blk", h, lp["wk_i"].astype(dt),
                        preferred_element_type=f32)
        ki = layer_norm(ki, lp["ki_norm"], lp["ki_norm_b"],
                        eps=c.norm_eps or 1e-6)
        cos, sin = rotary_embedding(positions, c.index_head_dim,
                                    theta=c.rope_theta)
        qi = apply_rotary(qi, cos, sin)
        ki = apply_rotary(ki[:, :, None], cos, sin)[:, :, 0]
        w = jnp.einsum("bld,dj->blj", h, lp["w_i"].astype(dt),
                       preferred_element_type=f32)
    return qi, ki, w


def _no_indexer(c: TransformerConfig, where: str) -> None:
    if c.index_heads:
        raise NotImplementedError(
            f"learned sparse attention (index_heads={c.index_heads}) runs on "
            f"the paged serve step only, not in {where}")


def _norm(x, w, b, c):
    # Both kinds carry bf16-residual custom VJPs (ops/layers.py) — plain
    # autodiff of the f32 upcast keeps f32 [B, L, D] residuals per site.
    if c.norm == "rms":
        return rms_norm(x, w, eps=c.norm_eps or 1e-6)
    return layer_norm(x, w, b, eps=c.norm_eps or 1e-5)


def _sp_axis_size() -> int:
    """Size of the ambient mesh's sequence-parallel axis (1 if absent)."""
    from jax.sharding import get_abstract_mesh

    mesh = get_abstract_mesh()
    if mesh is None or mesh.empty or "sp" not in mesh.axis_names:
        return 1
    return mesh.shape["sp"]


def _pp_axis_size() -> int:
    """Size of the ambient mesh's pipeline axis (1 if absent)."""
    from jax.sharding import get_abstract_mesh

    mesh = get_abstract_mesh()
    if mesh is None or mesh.empty or "pp" not in mesh.axis_names:
        return 1
    return mesh.shape["pp"]


def _attention(q, k, v, config: TransformerConfig, window: Optional[int] = None):
    """Training attention: ring over sp when sequence-parallel, else flash.

    ``window``: this LAYER's sliding window (per-layer alternation passes
    it explicitly; 0 = global). ``None`` falls back to the config-uniform
    window. Always STATIC — the banded kernels' block liveness is
    compile-time structure.
    """
    if window is None:
        window = config.uniform_window
    sp = _sp_axis_size()
    if sp > 1 and q.shape[1] % sp == 0 and k.shape[1] % sp == 0:
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from jax.sharding import get_abstract_mesh

        from ray_tpu.ops.ring_attention import (ring_attention,
                                                sliding_window_attention_sp)

        mesh = get_abstract_mesh()
        batch = tuple(a for a in ("dcn", "dp", "fsdp")
                      if a in mesh.axis_names)
        qspec = P(batch or None, "sp", "tp" if "tp" in mesh.axis_names else None, None)
        if window:
            # windowed + sequence-parallel: halo exchange — ceil(window/
            # Lloc) chained ppermutes, O(window/Lloc) comm independent
            # of sp. Multi-hop handles window > Lloc; any window is
            # exact (hops clamp at sp-1 = all-gather shape).
            inner = functools.partial(sliding_window_attention_sp,
                                      axis="sp",
                                      window=window,
                                      softcap=config.attn_softcap)
        else:
            inner = functools.partial(ring_attention, axis="sp",
                                      causal=True,
                                      softcap=config.attn_softcap)
        fn = shard_map(
            inner,
            mesh=mesh, in_specs=(qspec, qspec, qspec), out_specs=qspec,
            check_vma=False,
        )
        return fn(q, k, v)
    from ray_tpu import config as _knobs
    from ray_tpu.ops.attention import flash_attention, resolve_attention_impl

    # flash_attention carries the memory-efficient custom VJP: O(L)
    # residuals (out + lse) instead of O(L^2) probability blocks — without
    # it the backward of a scanned-layer model OOMs HBM at long context.
    # Tile sizes are config knobs (RTPU_ATTN_BLOCK_Q/K) so on-chip sweeps
    # can tune them without code edits.
    impl = resolve_attention_impl()
    attend = functools.partial(flash_attention, causal=True, impl=impl,
                               q_block=int(_knobs.get("attn_block_q")),
                               kv_block=int(_knobs.get("attn_block_k")),
                               window=window or None,
                               softcap=config.attn_softcap)
    from jax.sharding import get_abstract_mesh

    mesh = get_abstract_mesh()
    if impl == "pallas" and mesh is not None and not mesh.empty \
            and mesh.size > 1:
        # GSPMD cannot partition a Mosaic kernel ("wrap the call in a
        # shard_map"): run it per shard, manual over the batch axes and tp
        # (heads) — attention needs nothing from another batch row or
        # head, so the local result is the global one. A batch or head
        # count the mesh does not divide raises here rather than taking
        # another path.
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        batch = tuple(a for a in ("dcn", "dp", "fsdp")
                      if a in mesh.axis_names)
        spec = P(batch or None, None,
                 "tp" if "tp" in mesh.axis_names else None, None)
        attend = shard_map(attend, mesh=mesh, in_specs=(spec, spec, spec),
                           out_specs=spec, check_vma=False)
    return attend(q, k, v)


def _layers_pipelined(layer_params, x, layer_fn, c, pp, cos, sin):
    """Run the layer stack as a GPipe pipeline over the ``pp`` mesh axis.

    The stacked layer dim is sharded over pp (``"layers": "pp"`` rule), so
    each stage holds L/pp layers; activations rotate stage-to-stage inside
    :func:`ray_tpu.train.pipeline.pipeline_apply` (``lax.ppermute`` over
    ICI). ``shard_map`` is manual ONLY over pp (``axis_names={"pp"}``) —
    fsdp/tp shardings inside each block stay GSPMD-auto, so pp composes
    with the other axes. MoE layers are excluded (their aux-loss carry
    doesn't thread through the pipeline state; use ep for MoE scale-out).
    Pipeline parallel is absent from the reference (SURVEY §2.4).
    """
    from jax.sharding import PartitionSpec as P, get_abstract_mesh

    from ray_tpu.train.pipeline import (merge_microbatches, pipeline_apply,
                                        split_microbatches)

    if c.num_experts:
        raise NotImplementedError(
            "pipeline parallelism excludes MoE layers (aux loss does not "
            "thread through the pipeline carry); shard experts over ep")
    num_micro = c.pp_microbatches or 2 * pp
    b = x.shape[0]
    if b % num_micro:
        raise ValueError(
            f"batch {b} not divisible by pp microbatches {num_micro}")
    micro = split_microbatches(x, num_micro)  # [M, mb, L, D]
    lspecs = jax.tree.map(lambda _: P("pp"), layer_params)
    # rope tables ride as explicit replicated args (shard_map must not
    # close over traced arrays)
    extras = () if cos is None else (cos, sin)
    especs = () if cos is None else (P(), P())

    def run(lps, m, *extra):
        cs, sn = (extra + (None, None))[:2]

        def block(lp, h):
            h2, _aux = layer_fn(h, lp, cs, sn)
            return h2

        blk = _remat_wrap(block, c)
        return pipeline_apply(blk, lps, m, axis="pp")

    out = jax.shard_map(
        run,
        mesh=get_abstract_mesh(),
        in_specs=(lspecs, P()) + especs,
        out_specs=P(),
        axis_names={"pp"},
        # VMA checking off: scans INSIDE the stage compute (blockwise
        # attention) init fresh zeros (unvarying) and combine them with
        # pp-varying activations, which the checker rejects at every such
        # site; replication of the final output holds by construction
        # (pipeline_apply broadcasts the last stage's result)
        check_vma=False,
    )(layer_params, micro, *extras)
    return merge_microbatches(out), jnp.zeros((), jnp.float32)


def _remat_wrap(layer_fn, c: "TransformerConfig"):
    """Apply the config's rematerialization choice to the layer body.

    ``remat_policy="save_attn"`` keeps the named ``attn_out`` residual
    (bf16 [B,L,H,K] per layer) so the backward pass recomputes norms and
    matmuls but NOT attention — attention recompute is the costly part
    (the flash custom VJP re-tiles O(L^2) blocks a second time under full
    remat)."""
    if not c.remat:
        return layer_fn
    if c.remat_policy == "save_attn":
        policy = jax.checkpoint_policies.save_only_these_names("attn_out")
        return jax.checkpoint(layer_fn, policy=policy)
    return jax.checkpoint(layer_fn)


def forward_features(
    params: Params,
    tokens: jax.Array,
    config: TransformerConfig,
    *,
    positions: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Transformer stack up to (and including) the final norm:
    tokens [B, L] int32 → (features [B, L, D], moe_aux). The LM head is
    applied by :func:`forward` — split out so the chunked-loss path can
    run head+softmax blockwise without materializing [B, L, V] logits."""
    c = config
    _no_indexer(c, "the training forward")
    serve_only(c, "forward_features (the training forward)")
    dt = jnp.dtype(c.dtype)
    b, l = tokens.shape
    if positions is None:
        positions = jnp.arange(l)[None, :]

    # Embedding lookup. STORAGE is (vocab:tp, embed:fsdp) — ZeRO-3 — but
    # the lookup runs against a (vocab:tp, replicated-D) view: a D:fsdp
    # gather output cannot be resharded to (batch, seq) activation layout
    # without the SPMD partitioner's involuntary full rematerialization
    # (the MULTICHIP warnings); all-gathering the table's D axis first is
    # one clean collective and the standard TPU embedding layout.
    tbl = constrain(params["embed"].astype(dt), ("vocab", None))
    x = tbl[tokens]
    if c.positions == "learned":
        x = x + params["pos_embed"].astype(dt)[positions[0]][None]
    x = constrain(x, ("batch", "seq", None))

    if c.positions == "rope":
        cos, sin = rotary_embedding(positions[0], c.hdim, theta=c.rope_theta)
    else:
        cos = sin = None

    def layer(x, lp, cos=cos, sin=sin, window=None):
        h = _norm(x, lp["attn_norm"], lp.get("attn_norm_b"), c)
        q, k, v = _qkv_proj(h, lp, dt, c.norm_eps or 1e-6)
        if cos is not None:
            q = apply_rotary(q, cos, sin)
            k = apply_rotary(k, cos, sin)
        q = constrain(q, ("batch", "seq", "heads", None))
        k = constrain(k, ("batch", "seq", "kv_heads", None))
        o = _attention(q, k, v, c, window=window)
        from jax.ad_checkpoint import checkpoint_name

        o = checkpoint_name(o, "attn_out")  # no-op unless a policy saves it
        o = jnp.einsum("blhk,hkd->bld", o, lp["wo"].astype(dt))
        x = constrain(x + o, ("batch", "seq", None))

        h = _norm(x, lp["mlp_norm"], lp.get("mlp_norm_b"), c)
        aux = jnp.zeros((), jnp.float32)
        if c.num_experts:
            m, aux = moe_layer_dense(
                h, lp["router"].astype(dt), lp["w_gate"].astype(dt),
                lp["w_up"].astype(dt), lp["w_down"].astype(dt),
                k=c.expert_top_k, capacity_factor=c.expert_capacity_factor,
            )
        elif c.mlp == "swiglu":
            g = jax.nn.silu(jnp.einsum("bld,df->blf", h, lp["w_gate"].astype(dt)))
            u = jnp.einsum("bld,df->blf", h, lp["w_up"].astype(dt))
            gu = constrain(g * u, ("batch", "seq", "mlp"))
            m = jnp.einsum("blf,fd->bld", gu, lp["w_down"].astype(dt))
        else:
            hmid = jnp.einsum("bld,df->blf", h, lp["w_in"].astype(dt))
            hmid = jax.nn.gelu(hmid + lp["b_in"].astype(dt))
            hmid = constrain(hmid, ("batch", "seq", "mlp"))
            m = jnp.einsum("blf,fd->bld", hmid, lp["w_out"].astype(dt))
            m = m + lp["b_out"].astype(dt)
        x = constrain(x + m, ("batch", "seq", None))
        return x, aux

    pattern = c.window_pattern
    uniform = len(set(pattern)) == 1

    pp = _pp_axis_size()
    if pp > 1:
        if not uniform:
            raise NotImplementedError(
                "per-layer alternating windows (attn_windows) are not "
                "supported with pipeline parallelism yet; use a uniform "
                "window or pp=1")
        x, moe_aux = _layers_pipelined(params["layers"], x, layer, c, pp,
                                       cos, sin)
    elif uniform:
        body = _remat_wrap(layer, c)

        def scan_step(carry, lp):
            x, aux_sum = carry
            x, aux = body(x, lp)
            return (x, aux_sum + aux), None

        (x, moe_aux), _ = lax.scan(scan_step,
                                   (x, jnp.zeros((), jnp.float32)),
                                   params["layers"])
    else:
        # Per-layer alternating windows (Gemma-2): scan layer GROUPS of
        # the pattern length, each sub-layer compiled with its own STATIC
        # window — the banded kernels' block liveness is compile-time
        # structure, so a traced per-layer window is not an option. Same
        # one-compilation scan economy: the group body traces P layers
        # once, not n_layers times.
        P_ = len(pattern)
        n_groups = c.n_layers // P_
        grouped = jax.tree.map(
            lambda a: a.reshape((n_groups, P_) + a.shape[1:]),
            params["layers"])
        bodies = [_remat_wrap(functools.partial(layer, window=w), c)
                  for w in pattern]

        def scan_group(carry, glp):
            x, aux_sum = carry
            for i in range(P_):
                lp_i = jax.tree.map(lambda a: a[i], glp)
                x, aux = bodies[i](x, lp_i)
                aux_sum = aux_sum + aux
            return (x, aux_sum), None

        (x, moe_aux), _ = lax.scan(scan_group,
                                   (x, jnp.zeros((), jnp.float32)),
                                   grouped)

    x = _norm(x, params["final_norm"], params.get("final_norm_b"), c)
    return x, moe_aux


def forward(
    params: Params,
    tokens: jax.Array,
    config: TransformerConfig,
    *,
    positions: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence forward. tokens: [B, L] int32 → (logits [B,L,V] f32, moe_aux)."""
    c = config
    x, moe_aux = forward_features(params, tokens, c, positions=positions)
    logits = jnp.einsum("bld,dv->blv", x, _lm_head(params, c)).astype(
        jnp.float32)
    if c.logits_softcap:
        logits = jnp.tanh(logits / c.logits_softcap) * c.logits_softcap
    return logits, moe_aux


def _lm_head(params: Params, c: TransformerConfig) -> jax.Array:
    dt = jnp.dtype(c.dtype)
    return (params["embed"].T if c.tie_embeddings
            else params["lm_head"]).astype(dt)


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------

def loss_and_metrics(
    params: Params,
    batch: Dict[str, jax.Array],
    config: TransformerConfig,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Next-token cross entropy. batch: {"tokens": [B,L]} or explicit
    {"inputs", "targets", "mask"}."""
    if "inputs" in batch:
        inputs, targets = batch["inputs"], batch["targets"]
        mask = batch.get("mask")
    else:
        toks = batch["tokens"]
        inputs, targets = toks[:, :-1], toks[:, 1:]
        mask = batch.get("mask")
        if mask is not None:
            mask = mask[:, 1:]
    if mask is None:
        mask = jnp.ones(targets.shape, jnp.float32)
    mask = mask.astype(jnp.float32)

    C = config.loss_chunk
    if C and targets.shape[1] > C:
        nll_sum, z_sum, moe_aux = _chunked_xent(params, inputs, targets,
                                                mask, config)
    else:
        logits, moe_aux = forward(params, inputs, config)
        logz = jax.nn.logsumexp(logits, axis=-1)
        tgt_logit = jnp.take_along_axis(
            logits, targets[..., None], axis=-1)[..., 0]
        nll_sum = ((logz - tgt_logit) * mask).sum()
        z_sum = ((logz ** 2) * mask).sum() if config.z_loss else None
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = nll_sum / denom
    metrics = {"loss": loss, "ntokens": mask.sum()}
    if config.z_loss:
        zl = config.z_loss * z_sum / denom
        loss = loss + zl
        metrics["z_loss"] = zl
    if config.num_experts:
        loss = loss + config.moe_aux_weight * moe_aux
        metrics["moe_aux"] = moe_aux
    metrics["perplexity"] = jnp.exp(jnp.minimum(metrics["loss"], 20.0))
    return loss, metrics


def _chunked_xent(params, inputs, targets, mask, c: TransformerConfig):
    """Blockwise LM-head + cross entropy over sequence chunks.

    The full [B, L, V] f32 logits tensor is the largest single buffer in
    a train step (batch 16 x 2048 x 32000 = 4.2 GB, doubled by its
    cotangent). Applying head+softmax per C-token chunk under
    ``jax.checkpoint`` keeps only [B, C, V] live at a time — backward
    recomputes each chunk's logits from the (cheap-to-keep) features.
    Classic memory-efficient CE; no reference counterpart (torch keeps
    full logits). Sequences that don't divide by the chunk are padded with
    mask-0 positions (never a silent dense fallback — that would
    reintroduce the multi-GB buffer exactly when the user asked to avoid
    it)."""
    x, moe_aux = forward_features(params, inputs, c)
    head = _lm_head(params, c)
    pad = (-targets.shape[1]) % c.loss_chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    b, l, d = x.shape
    n = l // c.loss_chunk
    want_z = bool(c.z_loss)

    def chunk(args):
        xc, tc, mc = args  # [B, C, D], [B, C], [B, C]
        logits = jnp.einsum("bcd,dv->bcv", xc, head).astype(jnp.float32)
        if c.logits_softcap:
            logits = jnp.tanh(logits / c.logits_softcap) * c.logits_softcap
        logz = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
        nll = ((logz - tgt) * mc).sum()
        return (nll, ((logz ** 2) * mc).sum()) if want_z else nll

    xs = x.reshape(b, n, c.loss_chunk, d).swapaxes(0, 1)
    ts = targets.reshape(b, n, c.loss_chunk).swapaxes(0, 1)
    ms = mask.reshape(b, n, c.loss_chunk).swapaxes(0, 1)
    out = jax.lax.map(jax.checkpoint(chunk), (xs, ts, ms))
    if want_z:
        return out[0].sum(), out[1].sum(), moe_aux
    return out.sum(), None, moe_aux


# ---------------------------------------------------------------------------
# KV-cache decode (serve / RL inference path)
# ---------------------------------------------------------------------------

def init_cache(config: TransformerConfig, batch: int, max_len: int,
               dtype=None, rolling: Optional[bool] = None) -> Params:
    """KV cache. With ``sliding_window`` set and smaller than ``max_len``,
    the cache is a RING of ``sliding_window`` slots (Mistral-style): HBM
    stays O(window) no matter how long generation runs — the serving
    memory win SWA exists for. ``rolling=False`` forces the full-length
    layout (needed when a single prefill chunk exceeds the window)."""
    c = config
    serve_only(c, "init_cache (the dense decode cache)")
    dt = jnp.dtype(dtype or c.dtype)
    # ring layout requires ONE window shared by all layers (the cache is a
    # single [n_layers, ...] stack); per-layer alternating windows with a
    # global layer anywhere force the full-length layout
    uniform = c.uniform_window
    if rolling and not uniform:
        raise ValueError(
            "ring KV layout requires ONE window shared by all layers; "
            f"this config's pattern is {c.window_pattern} (0 = global / "
            "mixed) — use rolling=False (full-length cache)")
    use_ring = (bool(uniform) and uniform < max_len
                if rolling is None else rolling)
    length = uniform if use_ring else max_len
    shape = (c.n_layers, batch, length, c.kv_heads, c.hdim)
    return {
        "k": jnp.zeros(shape, dt),
        "v": jnp.zeros(shape, dt),
        "pos": jnp.zeros((), jnp.int32),
    }


def decode_step(
    params: Params,
    cache: Params,
    tokens: jax.Array,
    config: TransformerConfig,
) -> Tuple[jax.Array, Params]:
    """Append ``tokens`` [B, T] (prompt chunk or single step) to the cache and
    return (logits [B, T, V], new cache). Static T → one compiled program per
    chunk length (prefill vs decode=1)."""
    c = config
    _no_indexer(c, "decode_step")
    serve_only(c, "decode_step")
    dt = jnp.dtype(c.dtype)
    b, t = tokens.shape
    pos0 = cache["pos"]
    positions = pos0 + jnp.arange(t)
    cache_len = cache["k"].shape[2]
    # ring layout iff the cache was allocated at exactly the window size
    # (init_cache's rolling mode); slots are kept oldest->newest by
    # rolling, so slot j holds absolute position pos_new - cache_len + j
    uniform = c.uniform_window
    is_ring = bool(uniform) and cache_len == uniform
    # per-layer effective windows for the masked full-cache path (traced
    # through the layer scan; 2^30 = "global" — far beyond any position)
    win_arr = jnp.array([w if w > 0 else (1 << 30)
                         for w in c.layer_windows], jnp.int32)
    if is_ring and t > cache_len:
        raise ValueError(
            f"prefill chunk {t} exceeds the ring cache ({cache_len}); "
            "feed the prompt in <=window chunks or init_cache(..., "
            "rolling=False)")

    x = params["embed"].astype(dt)[tokens]
    if c.positions == "learned":
        x = x + jnp.take(params["pos_embed"].astype(dt), positions, axis=0)[None]
    if c.positions == "rope":
        cos, sin = rotary_embedding(positions, c.hdim, theta=c.rope_theta)
    else:
        cos = sin = None

    def layer(carry, inp):
        x = carry
        lp, kc, vc, wl = inp
        h = _norm(x, lp["attn_norm"], lp.get("attn_norm_b"), c)
        q, k, v = _qkv_proj(h, lp, dt, c.norm_eps or 1e-6)
        if cos is not None:
            q = apply_rotary(q, cos, sin)
            k = apply_rotary(k, cos, sin)
        if is_ring:
            # MODULAR ring layout everywhere: position p lives in slot
            # p % W; slot s holds the largest p ≡ s (mod W) written so
            # far (negative = unfilled). Keys are stored already-rotated
            # at absolute positions, and softmax is permutation-invariant
            # over keys, so only the MASK needs positions — which
            # naive_attention takes per-slot via ``k_positions``.
            if t == 1:
                # hot decode loop: ONE slot write, no roll/concat copies.
                # The overwritten slot held pos0 - W — out-of-window for
                # this query — so writing before attending is safe.
                slot = pos0 % cache_len
                kc = lax.dynamic_update_slice(kc, k.astype(kc.dtype),
                                              (0, slot, 0, 0))
                vc = lax.dynamic_update_slice(vc, v.astype(vc.dtype),
                                              (0, slot, 0, 0))
                slot_pos = pos0 - (
                    (slot - jnp.arange(cache_len)) % cache_len)
                o = naive_attention(q, kc, vc, causal=True, q_offset=pos0,
                                    window=uniform,
                                    k_positions=slot_pos,
                                    softcap=c.attn_softcap)
            else:
                # chunked prefill: attend over old ring ++ new keys
                # BEFORE evicting — a key evicted by the END of this
                # chunk can still be in-window for its EARLY queries
                prev = pos0 - 1
                slot_pos_old = prev - (
                    ((prev % cache_len) - jnp.arange(cache_len))
                    % cache_len)
                k_all = jnp.concatenate([kc, k.astype(kc.dtype)], axis=1)
                v_all = jnp.concatenate([vc, v.astype(vc.dtype)], axis=1)
                pos_all = jnp.concatenate([slot_pos_old, positions])
                o = naive_attention(q, k_all, v_all, causal=True,
                                    q_offset=pos0,
                                    window=uniform,
                                    k_positions=pos_all,
                                    softcap=c.attn_softcap)
                idx = positions % cache_len
                kc = kc.at[:, idx].set(k.astype(kc.dtype))
                vc = vc.at[:, idx].set(v.astype(vc.dtype))
        else:
            kc = lax.dynamic_update_slice(kc, k.astype(kc.dtype),
                                          (0, pos0, 0, 0))
            vc = lax.dynamic_update_slice(vc, v.astype(vc.dtype),
                                          (0, pos0, 0, 0))
            # wl is this layer's window riding the scan (2^30 = global),
            # so alternating-window models decode exactly
            o = naive_attention(q, kc, vc, causal=True, q_offset=pos0,
                                window=wl, softcap=c.attn_softcap)
        o = jnp.einsum("blhk,hkd->bld", o, lp["wo"].astype(dt))
        x = x + o
        return _decode_mlp(x, lp, c, dt)[0], (kc, vc)

    x, (new_k, new_v) = lax.scan(
        layer, x, (params["layers"], cache["k"], cache["v"], win_arr)
    )
    x = _norm(x, params["final_norm"], params.get("final_norm_b"), c)
    head = (params["embed"].T if c.tie_embeddings else params["lm_head"]).astype(dt)
    logits = jnp.einsum("bld,dv->blv", x, head).astype(jnp.float32)
    new_cache = {"k": new_k, "v": new_v, "pos": pos0 + t}
    return logits, new_cache



_EXPERTS = ("w_gate", "w_up", "w_down")
#: what the paged step under a budget slices out of the layer stacks late,
#: inside the stage that multiplies by it
_SLICED_LATE = ("wo", "w_gate", "w_up", "w_down", "w_in", "w_out")
#: a layer's leaves that the stage BEFORE its attention multiplies by, where
#: a layout indexes all of a layer out of its stacks inside the stages
_BEFORE_ATTENTION = ("attn_norm", "wq", "wk", "wv", "wg", "q_norm", "k_norm")


def _swiglu(h, w_gate, w_up, w_down, dt, mup=(1.0, 1.0)):
    """``mup``: fixed multipliers on the gate's pre-activation and on the
    output (a layout that has them; 1 and absent from the program
    elsewhere)."""
    g = jnp.einsum("bld,df->blf", h, w_gate.astype(dt))
    if mup[0] != 1.0:
        g = g * mup[0]
    out = jnp.einsum("blf,fd->bld", jax.nn.silu(g) * jnp.einsum(
        "bld,df->blf", h, w_up.astype(dt)), w_down.astype(dt))
    return out if mup[1] == 1.0 else out * mup[1]


#: ... and, where the router reads the layer's normed input
#: (``router_input="attn_norm"``), the router's own leaves
_ROUTER = ("router", "router_bias")
#: a route made ahead of the attention, as it crosses it in the stream's
#: order (``[.., k]`` a position; the counts ``[E]`` are no stream)
_ROUTE_STREAMS = ("route_weights", "route_experts", "route_order")


def _route_args(c, lp) -> dict:
    """What ``ops.moe.moe_route`` takes beside the tensor, the router and
    ``valid``: top-k and, with a share of the experts described
    (``expert_share``), its scoring, bias, scale and first held expert."""
    share = {} if not c.expert_share else dict(
        scoring=c.expert_scoring, bias=lp.get("router_bias"),
        scale=c.expert_scale, first=c.experts_first)
    return dict(k=c.expert_top_k, norm_topk=c.expert_norm_topk, **share)


def _decode_mlp(x, lp, c, dt, valid=None, layer=None, dense=False,
                route=None):
    """Post-attention norm + MLP tail shared by the two decode paths (the
    ONE definition: :func:`decode_step`, the offline reference, and the
    paged serving step must never diverge).
    Experts are DROPLESS here: a capacity would make what a request gets
    back depend on the rows that share its step. ``valid`` [B, L] marks
    the real positions (padding is routed nowhere). With ``layer`` the
    expert weights in ``lp`` are the whole stacks and ``layer`` picks this
    layer's (``moe_layer_dropless``). ``dense`` marks a leading dense layer
    of a model that has experts after it. A model with a share of its
    experts (``experts_held``) computes the pairs whose expert it holds; a
    shared expert (``ws_*``) is added to the routed sum; a norm on the
    branch's output (``post_mlp_norm``) comes before the residual add.
    ``route``: the layer's ``ops.moe.Route`` where it was made ahead of the
    attention, from the layer's normed input (``router_input``); the experts
    then take it and the router is not read here. The experts' activation is
    the configuration's ``expert_act``.
    Returns (x + mlp(x), the layer's tokens per (held) expert [E], or None
    in a dense layer)."""
    h = _norm(x, lp["mlp_norm"], lp.get("mlp_norm_b"), c)
    counts = None
    if c.num_experts and not dense:
        b, l, d = h.shape
        if route is None:
            m, counts = moe_layer_dropless(
                h.reshape(b * l, d), lp["router"], lp["w_gate"].astype(dt),
                lp["w_up"].astype(dt), lp["w_down"].astype(dt),
                valid=None if valid is None else valid.reshape(b * l),
                layer=layer, act=c.expert_act, **_route_args(c, lp))
        else:
            m, counts = moe_experts(
                h.reshape(b * l, d), route, lp["w_gate"].astype(dt),
                lp["w_up"].astype(dt), lp["w_down"].astype(dt),
                layer=layer, act=c.expert_act), route.counts
        m = m.reshape(b, l, d)
        if c.shared_experts:
            with jax.named_scope("shared_expert"):
                m = m + _swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"],
                                dt)
    elif c.mlp == "swiglu":
        m = _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"], dt, c.mlp_mup)
    else:
        hmid = jax.nn.gelu(jnp.einsum(
            "bld,df->blf", h, lp["w_in"].astype(dt)) + lp["b_in"].astype(dt))
        m = jnp.einsum("blf,fd->bld", hmid, lp["w_out"].astype(dt))
        m = m + lp["b_out"].astype(dt)
    if "post_mlp_norm" in lp:
        m = _norm(m, lp["post_mlp_norm"], None, c)
    return x + m, counts


def init_cache_paged(config: TransformerConfig, num_blocks: int,
                     block_size: int, dtype=None, *,
                     window_blocks: Optional[int] = None,
                     state_slots: Optional[int] = None) -> Params:
    """Block-paged KV cache for :func:`decode_step_paged` (the serving
    tier's vLLM-style layout): physical storage is a pool of fixed-size
    token blocks shared by EVERY request; each request maps its logical
    positions onto physical blocks through a per-slot block table. No
    per-slot ``pos`` lives here — positions and block ownership are
    host-side scheduler state (``ray_tpu.serve.kv_cache``), which is what
    makes prefix sharing possible: two requests whose tables name the
    same immutable block read the same HBM.

    Layout ``[n_layers, num_blocks, block_size, kv_heads, head_dim]``: a
    token's KV heads lie together, so the step writes a token with one
    row and :func:`ray_tpu.ops.paged_attention.paged_attention` copies a
    whole block (every head of ``block_size`` tokens) as one contiguous
    page; ``copy_kv_block``, ``gather_kv_blocks`` and ``scatter_kv_blocks``
    index axis 1 only and ship whole blocks.

    A model with a sparse-attention indexer (``index_heads``) has a THIRD
    pool, ``"ki"`` ``[n_layers, num_blocks, *index_pool_shape(block_size,
    index_head_dim)]``: the indexer's key of every cached token, written
    with its K and V, a block's keys in row-major order and stored in the
    shape the step carries and its readers read
    (:func:`ray_tpu.ops.sparse_attention.index_pool_shape`: two 64-wide
    keys a 128-lane row, ``[.., 8, 128]`` for 16-token blocks; a key a row
    where the widths do not divide). It is part of a block's state:
    whatever copies, ships or adopts a block (those three functions, which
    walk every pool of the dict; the serve engine's export and adoption;
    ``serve/kv_transfer.py``) carries it, or the token is later scored on
    garbage and silently never selected.

    The step carries these stacked pools through its layer loop as ONE pool
    of ``n_layers * num_blocks`` blocks and writes a step's rows in place
    in the donated buffers; a write to be dropped goes past the whole stack
    (``n_layers * num_blocks * block_size``), not past one layer's pool.

    A hybrid layout (``layer_kinds``) has pools by KIND of layer
    (:mod:`ray_tpu.models.hybrid`): ``num_blocks`` sizes the one full
    layer's, ``window_blocks`` the window layers' (their own ids) and
    ``state_slots`` the state-space layers' float32 state, one a slot.

    The windowed MoE layout with a window pool has pools by kind too
    (:mod:`ray_tpu.models.windowed_moe`): ``"k"``/``"v"`` over its full
    layers, ``"wk"``/``"wv"`` over its window layers (``window_blocks``).

    A latent-attention model (``kv_lora_rank``) has ONE pool, ``"kv"``
    ``[n_layers, num_blocks, block_size, kv_lora_rank + qk_rope_head_dim]``:
    a token's normed latent and its one rotated key
    (:mod:`ray_tpu.models.latent`).

    Each layout takes the arguments it has a pool for and refuses the
    others (:meth:`ray_tpu.models.layouts.Layout.init_cache`)."""
    return layout_of(config).init_cache(
        config, num_blocks, block_size, window_blocks=window_blocks,
        state_slots=state_slots, dtype=dtype)


def _one_id_space(cache: Params, what: str) -> None:
    """``what`` names blocks by ONE id; pools by kind of layer (``"wk"``,
    ``"wv"``: window layers with ids of their own) cannot be walked so."""
    if "wk" in cache:
        raise NotImplementedError(
            f"{what} walks every pool under one block id; this cache has "
            "pools by kind of layer (a window pool with ids of its own: "
            "TransformerConfig.window_pool) and is refused")


def copy_kv_block(cache: Params, src, dst) -> Params:
    """Copy one physical block (all layers, every pool) — the device half
    of copy-on-write: when a request must write into a block whose
    refcount is > 1 (shared prefix tail), the pool duplicates it first so
    the sharers keep reading the original."""
    _one_id_space(cache, "copy_kv_block")
    return {name: pool.at[:, dst].set(pool[:, src])
            for name, pool in cache.items()}


def gather_kv_blocks(cache: Params, block_ids) -> Params:
    """Gather a request's physical blocks out of the paged pool — the
    device half of KV-block EXPORT for disaggregated prefill/decode:
    the prefill engine pulls exactly the blocks named by one request's
    table ([L, n, bs, kvh, hd] per tensor) without ever materializing
    the whole pool on the host. The result is contiguous, so the
    transfer plane ships it as one raw tensor body."""
    _one_id_space(cache, "gather_kv_blocks")
    ids = jnp.asarray(block_ids, jnp.int32)
    return {name: pool[:, ids] for name, pool in cache.items()}


def scatter_kv_blocks(cache: Params, block_ids, kv: Params) -> Params:
    """Scatter a shipped block batch into this pool's physical blocks —
    the device half of KV-block ADOPTION on a decode engine: the blocks
    claimed for the arriving request (and ONLY those rows) are
    overwritten with the prefill engine's exported KV. ``kv`` layout
    matches :func:`gather_kv_blocks` ([L, n, bs, kvh, hd]). Out-of-range
    ids are DROPPED (mode="drop") — the engine pads batches to bucketed
    shapes with the out-of-range id so one compile serves a bucket of
    block counts instead of retracing per count."""
    _one_id_space(cache, "scatter_kv_blocks")
    ids = jnp.asarray(block_ids, jnp.int32)
    return {name: pool.at[:, ids].set(kv[name].astype(pool.dtype),
                                      mode="drop")
            for name, pool in cache.items()}


def decode_step_paged(
    params: Params,
    cache: Params,
    tokens: jax.Array,
    block_tables: jax.Array,
    pos: jax.Array,
    nvalid: jax.Array,
    config: TransformerConfig,
    active: Optional[jax.Array] = None,
    step_stats: bool = False,
    budget: Optional[int] = None,
) -> Tuple[jax.Array, Params]:
    """Advance B independent requests by up to C tokens each against the
    block-paged cache — ONE compiled program serves both chunked prefill
    (rows feeding C prompt tokens) and decode (rows feeding 1 token with
    C-1 padding), so a long prompt never stalls the in-flight decodes
    sharing its batch.

    ``budget`` (static; ``None`` or at least ``B * C``: every position-wise
    operation runs over all ``B * C`` positions, padding included) makes the
    program multiply its weights by the positions that are REAL: the step's
    real positions are gathered, row-major, to the front of one flat stream
    ``[1, B * C, D]`` that stays in that order from the embedding to the
    last layer, and the position-wise half of every layer (norms,
    projections, RoPE, ``wo``, the MLP or the experts, the indexer's
    projections) runs on its first ``budget`` positions when they hold
    every real one, on its first ``2 * budget`` when those do (a width the
    program has only where the grid is wider still: :func:`step_widths`),
    and on all of it when not (the arithmetic of the step without a
    budget), chosen on the device from ``nvalid`` and ``active``.
    Only the row-structured part keeps the ``[B, C]`` layout: queries are
    gathered into it for the attention call and its output gathered back;
    K and V rows go from the flat order straight to the pool. What a real
    position computes, and what each row gets back, is what
    ``budget=None`` gives.

    tokens: [B, C] int32; block_tables: [B, M] int32 physical block ids
    (row-major: logical position p of request b lives in physical block
    ``block_tables[b, p // bs]`` at offset ``p % bs``; unused entries must
    hold a valid id — they are masked, never written). pos: [B] tokens
    already cached; nvalid: [B] how many of this step's C tokens are real.
    Writes land via an out-of-bounds-dropped scatter, so invalid rows and
    padding touch nothing (a shared prefix block is immutable because no
    live request's write positions ever map into it). The stacked pools
    are the layer loop's carry, addressed flattened over layers (layer
    ``l`` owns blocks ``l * n_blocks + id``): with the cache donated the
    rows are written in place and nothing pool-sized is sliced, rebuilt or
    copied; dropped writes go past the WHOLE stack. Attention then
    reads the pool THROUGH the table
    (:func:`ray_tpu.ops.paged_attention.paged_attention`): KV heads stay
    grouped, keys and values stay in the pool's type with float32
    accumulation and a float32 softmax, and on a TPU (bf16 pool, 128-wide
    heads) a Pallas kernel copies only each row's live blocks, window
    start to ``ceil((pos + nvalid) / bs)``; elsewhere the same mathematics
    runs over the gathered table. A model with an indexer
    (``index_heads``) also writes its third pool and rows past
    ``index_topk`` keys attend to the selected keys only
    (:func:`ray_tpu.ops.sparse_attention.paged_sparse_attention`). Returns
    (logits [B, V] of each row's LAST VALID token, new cache), and with
    ``step_stats`` a third value: what only the device can count, today
    ``{"expert_tokens": [L, E]}`` for an MoE model and ``{}`` otherwise."""
    return _step_paged_impl(params, cache, tokens, block_tables, pos,
                            nvalid, config, active, all_logits=False,
                            step_stats=step_stats, budget=budget)


def verify_step_paged(
    params: Params,
    cache: Params,
    tokens: jax.Array,
    block_tables: jax.Array,
    pos: jax.Array,
    nvalid: jax.Array,
    config: TransformerConfig,
    active: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Params]:
    """The speculative-decoding verify twin of :func:`decode_step_paged`:
    identical cache semantics and masking, but logits come back for EVERY
    fed position ([B, C, V]) instead of only each row's last valid one.
    Feeding ``[last, d1..dk]`` verifies a k-token draft in one call —
    logits[:, i] is the target's distribution after consuming input i, so
    the greedy accept check is a per-position argmax compare. Invalid
    positions still write nothing; their logits are garbage and must be
    masked host-side via ``nvalid``. The extra lm-head cost (B*C rows vs
    B) is the price of batched verification and is exactly what the
    draft's accepted tokens amortize."""
    return _step_paged_impl(params, cache, tokens, block_tables, pos,
                            nvalid, config, active, all_logits=True)


class _Run(NamedTuple):
    """Consecutive layers of one segment and one pool kind: one scan of the
    paged step's layer body."""
    segment: str        # the stacks' key: "layers", or "dense" | "moe"
    start: int          # the run's first layer within its segment's stacks
    layers: int
    first_layer: int    # ... and within the model
    windowed: bool      # reads the window pool through the window table
    pool_first: int     # the run's first layer within its pool's layers
    rope: bool          # the layers rotate q and k


def _layer_runs(c: TransformerConfig) -> List[_Run]:
    """The layers as maximal runs that one scanned body serves. A uniform
    decoder is one run over ``params["layers"]``; the windowed MoE layout
    has a run for each stretch of a segment's consecutive layers that read
    the same pool kind and agree on RoPE."""
    layout = layout_of(c)
    if layout.segments is None:
        return [_Run("layers", 0, c.n_layers, 0, False, 0, True)]
    windows = c.layer_windows
    out: List[_Run] = []
    seen = {True: 0, False: 0}      # layers of each pool kind so far
    layer = 0
    for seg, n in layout.segments(c):
        for i in range(n):
            windowed = layout.window_pool and windows[layer] > 0
            rope = c.rope_layers == "all" or windows[layer] > 0
            last = out[-1] if out else None
            if (last and last.segment == seg and last.windowed == windowed
                    and last.rope == rope):
                out[-1] = last._replace(layers=last.layers + 1)
            else:
                out.append(_Run(seg, i, 1, layer, windowed, seen[windowed],
                                rope))
            seen[windowed] += 1
            layer += 1
    return out


def step_widths(budget: int, grid: int) -> List[int]:
    """The widths the paged step has for its position-wise work under
    ``budget`` on a grid of ``grid`` positions, narrowest first: the budget,
    twice the budget where the grid is wider still, the grid. A step runs
    the first that holds its real positions (:func:`decode_step_paged`; the
    serve engine counts by the same list). ONE middle width, not a ladder:
    each is one more traced and compiled copy of every position-wise stage,
    and a step of a chunk row or a few beside the decoding rows, just over
    the budget, is the one that paid most for the whole grid."""
    return [w for w in (budget, 2 * budget) if w < grid] + [grid]


def _step_paged_impl(
    params: Params,
    cache: Params,
    tokens: jax.Array,
    block_tables: jax.Array,
    pos: jax.Array,
    nvalid: jax.Array,
    config: TransformerConfig,
    active: Optional[jax.Array] = None,
    *,
    all_logits: bool = False,
    step_stats: bool = False,
    budget: Optional[int] = None,
):
    c = config
    layout = layout_of(c)
    dt = jnp.dtype(c.dtype)
    b, t = tokens.shape
    n_layers, n_blocks, bs = cache[layout.pool_leaf].shape[:3]
    m = block_tables.shape[1]
    if active is None:
        active = jnp.ones((b,), bool)
    win_arr = jnp.array([w if w > 0 else (1 << 30)
                         for w in c.layer_windows], jnp.int32)
    if c.index_heads and c.uniform_window:
        raise NotImplementedError(
            "a sliding window together with learned sparse attention")
    # under a budget narrower than the step the position-wise work runs on
    # the real positions, gathered to the front of one flat stream
    compact = budget is not None and budget < b * t
    if compact and (all_logits or budget < 1):
        raise ValueError(f"budget={budget} with all_logits={all_logits}")

    positions = pos[:, None] + jnp.arange(t)[None, :]           # [B, C]
    valid = (jnp.arange(t)[None, :] < nvalid[:, None]) \
        & active[:, None]                                       # [B, C]
    # physical destination of each new token inside one layer's pool
    blk = jnp.take_along_axis(block_tables,
                              jnp.clip(positions // bs, 0, m - 1), axis=1)
    dest = (blk * bs + positions % bs).reshape(-1)              # [B*C]
    # invalid tokens go PAST THE WHOLE STACK and are dropped: the pools are
    # addressed flattened over layers, where ``n_blocks * bs``, out of
    # bounds for one layer, is the first row of the next
    dropped = n_layers * n_blocks * bs
    # rows the attention may skip outright: parked slots feed nothing
    n_attend = jnp.where(active, nvalid, 0)
    if layout.window_pool:
        # the table's last columns are the window layers': a row's live
        # window only, entry 0 the block that holds the first key the row's
        # first query may see
        m_full = m - layout.table_width(c.sliding_window, t, bs)
        win_first = jnp.maximum(pos - c.sliding_window + 1, 0) // bs * bs
        rel = positions - win_first[:, None]
        win_blk = jnp.take_along_axis(
            block_tables[:, m_full:],
            jnp.clip(rel // bs, 0, m - m_full - 1), axis=1)
        win_dest = (win_blk * bs + rel % bs).reshape(-1)        # [B*C]

    if compact:
        # ONE permutation, made from the step's small integer inputs before
        # the embedding lookup: the real positions first, row-major, in a
        # flat stream ``[1, B * C]``. The residual stream keeps that order
        # through every layer, so no layer moves it.
        n = b * t
        widths = step_widths(budget, n)
        seen = jnp.cumsum(valid.reshape(-1))    # real positions up to each
        n_real = seen[-1]
        # the flat index of the j-th real position (past the last: any)
        src = jnp.minimum(jnp.searchsorted(
            seen, jnp.arange(1, n + 1), method="compare_all"), n - 1)
        # and where a real position sits in the order (padding: anywhere)
        slot_of = jnp.maximum(seen - 1, 0)
        with jax.named_scope("stream_gather"):
            tokens, positions = (a.reshape(-1)[src][None]
                                 for a in (tokens, positions))  # [1, B * C]
            dest = dest[src]
            if layout.window_pool:
                win_dest = win_dest[src]
        valid = (jnp.arange(n) < n_real)[None]

    # the step's stages by name (metadata alone: the device trace then
    # names an operation by its stage and not by its fusion number)
    with jax.named_scope("embed"):
        x = params["embed"].astype(dt)[tokens]      # [B, C, D] | [1, B * C, D]
        if c.embedding_multiplier != 1.0:
            x = x * c.embedding_multiplier
        if c.positions == "learned":
            # clamp ONLY the table lookup (padding rows can sit past the
            # table); rope below uses the true positions — the dense decode
            # paths do, and clamping would skew angles past max_seq_len
            x = x + jnp.take(params["pos_embed"].astype(dt),
                             jnp.clip(positions, 0, c.max_seq_len - 1),
                             axis=0)
    # what a position-wise stage reads of each position, beside the stream
    at = {"positions": positions, "valid": valid}
    with jax.named_scope("rope"):
        if layout.rope_tables is not None:
            at["cos"], at["sin"] = layout.rope_tables(positions, c)
        elif c.positions == "rope":
            at["cos"], at["sin"] = rotary_embedding(
                positions, c.hdim, theta=c.rope_theta)      # [.., .., D/2]

    def on_real(stage, state, ins, total=None):
        """``stage(state, ins) -> (state, counts)`` over the ordered stream:
        on the narrowest of :func:`step_widths` whose first positions hold
        every real one (the last is all of it: what the step without a
        budget computes), chosen ON THE DEVICE; the rest of ``state`` stays
        as it was and ``counts`` adds to ``total``. Not a loop over
        ``budget``-wide tiles: its compiler lifts a layer's weight slices
        out of such a loop as copies, and a step of several tiles would read
        the weights (with experts, nearly every expert's) once a tile."""
        def over(width):
            def run(state, total):
                cut = lambda a: a[:, :width]
                with jax.named_scope("stream_gather"):
                    state_in, ins_in = (jax.tree.map(cut, t)
                                        for t in (state, ins))
                new, counts = stage(state_in, ins_in)
                with jax.named_scope("stream_gather"):
                    state = jax.tree.map(
                        lambda a, u: a.at[:, :width].set(u), state, new)
                return state, None if total is None else total + counts
            return run
        if len(widths) == 2:
            # (no second width: the conditional as it was, to the letter)
            return lax.cond(n_real <= budget, over(budget), over(n), state,
                            total)
        return lax.switch(sum(n_real > w for w in widths[:-1]),
                          [over(w) for w in widths], state, total)

    def finish(x, make_cache, expert_tokens):
        """The step's tail: final norm, head, then the cache handed back
        (``make_cache()``) and what only the device counts. The cache is
        built AFTER the head, as it always was: built before it (the slice
        that takes the lane padding off ``ki`` ahead of the head's matmul)
        the sparse-attention MoE cell's gap between tokens read 3 % longer
        on the chip (PR 35)."""
        if compact:
            # each row's last real position, where the order has it
            with jax.named_scope("stream_gather"):
                last = jnp.maximum(
                    jnp.cumsum(jnp.clip(n_attend, 0, t)) - 1, 0)
                x = x[:, last]                                  # [1, B, D]
        with jax.named_scope("final_norm"):
            x = _norm(x, params["final_norm"], params.get("final_norm_b"), c)
        with jax.named_scope("lm_head"):
            head = (params["embed"].T if c.tie_embeddings
                    else params["lm_head"]).astype(dt)
            if all_logits:
                # verify path: the accept check needs a distribution at
                # every fed position, so project all B*C rows
                logits = jnp.einsum("bcd,dv->bcv", x, head).astype(
                    jnp.float32)
            else:
                # only each row's LAST VALID position needs logits —
                # project D->V for B rows, not B*C (the lm-head matmul
                # dominates small-model steps)
                if not compact:
                    last = jnp.clip(nvalid - 1, 0, t - 1)
                    x = jnp.take_along_axis(x, last[:, None, None], axis=1)
                x_last = x.reshape(b, -1)
                logits = jnp.einsum("bd,dv->bv", x_last, head).astype(
                    jnp.float32)
            if c.lm_head_multiplier != 1.0:
                logits = logits * c.lm_head_multiplier
            if c.logits_softcap:
                logits = jnp.tanh(
                    logits / c.logits_softcap) * c.logits_softcap
        new_cache = make_cache()
        if not step_stats:
            return logits, new_cache
        # what the step can count that the host cannot: tokens per expert
        # of every layer [L, E] (an empty dict in a dense model)
        stats = {"expert_tokens": expert_tokens} if c.num_experts else {}
        return logits, new_cache, stats

    if layout.run_layers is not None:
        # the layout's own loop (layers of several kinds in scanned
        # segments) between the shared prologue and the shared tail
        flat_valid = valid.reshape(-1)

        def moved(fn):
            """``fn`` under the scope of the stream's gathers."""
            def scoped(a):
                with jax.named_scope("stream_gather"):
                    return fn(a)
            return scoped

        ctx = SimpleNamespace(
            at=at, pos=pos, n_attend=n_attend,
            stage=on_real if compact
            else (lambda fn, state, ins, total=None: fn(state, ins)),
            to_rows=moved(
                lambda a: a[0, slot_of].reshape(b, t, *a.shape[2:]))
            if compact else (lambda a: a),
            to_flat=moved(lambda a: a.reshape(1, n, *a.shape[2:])[:, src])
            if compact else (lambda a: a))

        def decode_mlp(x, lp, valid, layer=None, dense=False):
            with jax.named_scope("mlp"):
                return _decode_mlp(x, lp, c, dt, valid=valid, layer=layer,
                                   dense=dense)

        ctx.decode_mlp = decode_mlp
        # each row's table and each position's token row in ONE layer's pool
        # (dropped positions negative), by pool kind: the fields the layout
        # names, made in the order it names them
        fields = {
            "full_tables": lambda: block_tables[:, :m_full]
            if layout.window_pool else block_tables,
            "win_tables": lambda: block_tables[:, m_full:],
            "win_pos": lambda: pos - win_first,
            "full_rows": lambda: jnp.where(flat_valid, dest, -1),
            "win_rows": lambda: jnp.where(flat_valid, win_dest, -1)}
        for name in layout.ctx:
            setattr(ctx, name, fields[name]())
        x, new_cache, expert_tokens = layout.run_layers(
            params["layers"], cache, x, c, ctx)
        return finish(x, lambda: new_cache, expert_tokens)

    def write(pool, new, rows):
        """The step's new tokens into a flattened stack of pools
        ``[n_layers * n_blocks, bs, ...]``, at its token rows ``rows`` (the
        indexer's keys, stored several a lane row, by their own rule)."""
        with jax.named_scope("kv_write"):
            if pool.shape[2:] != new.shape[2:]:
                return write_index_keys(pool, new.reshape(
                    -1, new.shape[-1]).astype(pool.dtype), rows)
            return pool.at[rows // bs, rows % bs].set(
                new.reshape(-1, *new.shape[2:]).astype(pool.dtype),
                mode="drop")

    # The pools travel through the layer loop as its CARRY, viewed as one
    # pool of ``n_layers * n_blocks`` blocks: layer ``l`` owns blocks
    # ``[l * n_blocks, (l + 1) * n_blocks)``, writes its rows there and
    # attends through the table shifted by ``l * n_blocks``. Scanned inputs
    # and outputs would have XLA slice every layer's pool out of the stack,
    # rewrite it whole and copy the new stack over the donated argument;
    # carried, the donated buffers take the step's rows in place.
    pools = {name: p.reshape(-1, *p.shape[2:]) for name, p in cache.items()}
    # The indexer's keys are stored several a lane row where the widths
    # allow (``ops.sparse_attention.index_pool_shape``) and travel as they
    # are stored. A pool whose widths do not divide keeps a key a row,
    # narrower than the TPU's 128 lanes, and for a scatter into so narrow a
    # stack of more than 2**20 rows its compiler turns the WHOLE stack
    # around and back, every layer: such a pool travels padded to whole
    # lanes, and the indexer reads the unpadded view.
    lane_pad = 0
    if c.index_heads and cache["ki"].shape[-1] == c.index_head_dim:
        lane_pad = -c.index_head_dim % LANES
        pools["ki"] = jnp.pad(pools["ki"], ((0, 0), (0, 0), (0, lane_pad)))

    # The layers in RUNS, each one scan of the one body below. A uniform
    # decoder is one run over ``params["layers"]``. The windowed MoE layout
    # (``models/windowed_moe.py``) has a run for each stretch of a segment's
    # layers that read one kind of pool: what differs between its runs is
    # static (the stacks, the pool and the table a layer reads, whether it
    # rotates), so each run's scan traces the body once with its own.
    runs = _layer_runs(c)
    trees = params["layers"] if layout.segments is not None \
        else {"layers": params["layers"]}
    # a pool kind's own view of the step: the pools it names, its blocks a
    # layer, each row's table and position in that table's numbering, each
    # position's token row in ONE layer's pool, and where a dropped write
    # goes (past the kind's WHOLE stack)
    by_kind = "wk" in cache
    full_kind = SimpleNamespace(
        names={name: name for name in cache if name not in ("wk", "wv")},
        n_blocks=n_blocks, first_block=None, dest=dest, dropped=dropped,
        tables=block_tables[:, :m_full] if by_kind else block_tables,
        scope=("global_attention",) if by_kind else ())
    win_kind = None
    if by_kind:
        nb_win = cache["wk"].shape[1]
        win_kind = SimpleNamespace(
            names={"k": "wk", "v": "wv"}, n_blocks=nb_win,
            tables=block_tables[:, m_full:], first_block=win_first // bs,
            dest=win_dest, dropped=cache["wk"].shape[0] * nb_win * bs,
            scope=("swa_attention",))
    gated = ("gated_attn_proj",) if c.attn_gate else ()

    def scopes(*names):
        """``jax.named_scope`` of each name, outermost first."""
        stack = contextlib.ExitStack()
        for name in names:
            stack.enter_context(jax.named_scope(name))
        return stack

    def run_layers(carry, run):
        tree = trees[run.segment]
        dense = run.segment == "dense"
        kind = win_kind if run.windowed else full_kind
        # the experts stay whole: the scan would copy each layer's slice of
        # them out of the stack, and the grouped matmul takes the stack
        stacks = {leaf: tree[leaf] for leaf in _EXPERTS} \
            if c.num_experts and not dense else {}
        # under a budget a stage sits in a branch, and what the scan slices
        # for it crosses the branch's boundary as a copy (117 MB a matrix of
        # a 7B MLP): ``wo`` and a dense MLP's matrices are indexed out of
        # their stacks inside the stage, where the slice fuses into its
        # matmul. ``wq``, ``wk`` and ``wv`` stay with the scan, which copies
        # them either way (``[D, H, hd]`` is not ``[D, H * hd]`` in tiled
        # memory). The windowed MoE layout stores every matrix as it is
        # multiplied, so all of a layer is indexed where it is used.
        indexed = {leaf for leaf in tree if leaf not in stacks and (
            c.windowed_moe or (compact and leaf in _SLICED_LATE))}
        scanned = {leaf: w for leaf, w in tree.items()
                   if leaf not in stacks and leaf not in indexed}
        # the router reads the layer's normed input: its route is made
        # ahead of the attention, beside q, k and v
        prerouted = bool(stacks) and c.router_input == "attn_norm"
        first_half = _BEFORE_ATTENTION + (_ROUTER if prerouted else ())
        early = {leaf: tree[leaf] for leaf in tree
                 if leaf in indexed and leaf in first_half}
        late = {leaf: tree[leaf] for leaf in tree
                if leaf in indexed and leaf not in first_half}

        def before_attention(x, lp, at, li):
            """The position-wise half of a layer before its attention: the
            rotated q, k and v of every position, with an output gate its
            pre-activation, with an indexer its queries, key (padded to
            the lanes where its pool travels padded) and weights, and where
            the router reads this half's normed input the layer's route
            (``_ROUTE_STREAMS`` and ``route_counts``)."""
            lp = {**lp, **{leaf: w[li] for leaf, w in early.items()}}
            with scopes("qkv_proj", *gated):
                h = _norm(x, lp["attn_norm"], lp.get("attn_norm_b"), c)
                q, k, v = _qkv_proj(h, lp, dt, c.norm_eps or 1e-6, c.hdim)
                gate = {"g": jnp.einsum("bld,de->ble", h,
                                        lp["wg"].astype(dt))} \
                    if c.attn_gate else {}
            if "cos" in at and run.rope:
                with jax.named_scope("rope"):
                    q = apply_rotary(q, at["cos"], at["sin"])
                    k = apply_rotary(k, at["cos"], at["sin"])
            out = {"q": q, "k": k, "v": v, **gate}
            if prerouted:
                rows, width = h.shape[0] * h.shape[1], h.shape[:2]
                route = moe_route(
                    h.reshape(rows, -1), lp["router"],
                    valid=at["valid"].reshape(rows), held=c.held_experts,
                    **_route_args(c, lp))
                out.update(
                    route_counts=route.counts,
                    **{name: a.reshape(*width, c.expert_top_k)
                       for name, a in zip(_ROUTE_STREAMS, route)})
            if c.index_heads:
                qi, ki, w = _indexer_proj(h, lp, at["positions"], c, dt)
                out.update(qi=qi, w=w,
                           ki=jnp.pad(ki, ((0, 0), (0, 0), (0, lane_pad))))
            return out

        def after_attention(x, o, lp, at, li, route_counts=None):
            """The position-wise half after it: the gate, ``wo``, the
            residual add, then the MLP or the experts (along the route
            ``at`` carries with ``route_counts``, where it was made ahead).
            Returns (x, tokens per expert or None)."""
            lp = {**lp, **stacks,
                  **{leaf: w[li] for leaf, w in late.items()}}
            with scopes("attn_out_proj", *gated):
                if c.windowed_moe:
                    o = o.reshape(*o.shape[:2], -1)
                    if c.attn_gate:
                        o = (o * jax.nn.sigmoid(
                            at["g"].astype(jnp.float32))).astype(dt)
                    a = jnp.einsum("ble,ed->bld", o, lp["wo"].astype(dt))
                    if "post_attn_norm" in lp:
                        a = _norm(a, lp["post_attn_norm"], None, c)
                    x = x + a
                else:
                    x = x + jnp.einsum("blhk,hkd->bld", o,
                                       lp["wo"].astype(dt))
            route = None
            if prerouted:
                k = c.expert_top_k
                route = Route(
                    *(at[name].reshape(-1, k) for name in _ROUTE_STREAMS[:2]),
                    at["route_order"].reshape(-1), route_counts)
            with jax.named_scope("mlp"):
                return _decode_mlp(x, lp, c, dt, valid=at["valid"],
                                   layer=li if stacks else None, dense=dense,
                                   route=route)

        # from a layer's place in its segment to its place in its pool kind
        to_pool = run.pool_first - run.start

        def layer(carry, inp):
            x, old = carry
            lp, wl, li = inp
            if run.start:
                li = li + run.start             # within the segment's stacks
            # the layer's first block within its pool kind
            first = (li + to_pool if to_pool else li) * kind.n_blocks
            tables = kind.tables + first
            rows = jnp.where(valid.reshape(-1), kind.dest + first * bs,
                             kind.dropped)
            zeros = lambda tree: jax.tree.map(
                lambda a: jnp.zeros(a.shape, a.dtype), tree)
            # (a route's counts are no stream: they leave a budgeted stage
            # as its counts do)
            apart = lambda new: (new, new.pop("route_counts", None))
            if not compact:
                new, route_counts = apart(before_attention(x, lp, at, li))
            else:
                like, counts_like = apart(
                    jax.eval_shape(before_attention, x, lp, at, li))
                new, route_counts = on_real(
                    lambda _, a: apart(before_attention(a["x"], lp, a, li)),
                    zeros(like), {**at, "x": x},
                    None if counts_like is None else zeros(counts_like))
            # write BEFORE attending: queries at chunk offset c must see the
            # chunk's own earlier keys (in-chunk causal self-attention); the
            # indexer's key travels with the token's K and V
            pools = {**old, **{name: write(old[name], new[leaf], rows)
                               for leaf, name in kind.names.items()}}
            k_pool, v_pool = pools[kind.names["k"]], pools[kind.names["v"]]
            # the attention keeps its rows: queries into ``[B, C]`` by where
            # each position sits in the order, its output back by the order
            with jax.named_scope("stream_gather"):
                q, qi, w = (
                    a if a is None or not compact
                    else a[0, slot_of].reshape(b, t, *a.shape[2:])
                    for a in (new["q"], new.get("qi"), new.get("w")))
            if c.index_heads:
                # rows past ``index_topk`` keys attend to the keys it selects
                o = paged_sparse_attention(
                    q, qi, w, k_pool, v_pool,
                    pools["ki"][..., :cache["ki"].shape[-1]], tables, pos,
                    n_attend, topk=c.index_topk, scale=c.hdim ** -0.5)
            else:
                # the pool is read through the block table: KV heads grouped,
                # in the pool's own type, each row only as far as its live
                # context (a window table: from the row's first live block)
                with scopes(*kind.scope):
                    o = paged_attention(
                        q, k_pool, v_pool, tables, pos, n_attend, window=wl,
                        softcap=c.attn_softcap, scale=c.hdim ** -0.5,
                        first_block=kind.first_block)
            # (the gate, and a route made ahead, stay in the stream's order
            # from stage to stage)
            carried = {name: new[name] for name in ("g",) + _ROUTE_STREAMS
                       if name in new}
            if not compact:
                x, expert_tokens = after_attention(
                    x, o, lp, {**at, **carried}, li, route_counts)
            else:
                with jax.named_scope("stream_gather"):
                    o = o.reshape(1, n, *o.shape[2:])[:, src]
                x, expert_tokens = on_real(
                    lambda x, a: after_attention(x, a["o"], lp, a, li,
                                                 route_counts),
                    x, {**at, **carried, "o": o},
                    None if not c.num_experts or dense
                    else jnp.zeros((c.held_experts,), jnp.int32))
            return (x, pools), expert_tokens

        at_layers = slice(run.first_layer, run.first_layer + run.layers)
        return lax.scan(layer, carry, (
            scanned, win_arr if run.layers == c.n_layers
            else win_arr[at_layers], jnp.arange(run.layers)))

    carry, counts = (x, pools), []
    for run in runs:
        carry, expert_tokens = run_layers(carry, run)
        if expert_tokens is not None:
            counts.append(expert_tokens)
    (x, pools), expert_tokens = carry, None
    if counts:
        expert_tokens = counts[0] if len(counts) == 1 \
            else jnp.concatenate(counts)
    return finish(x, lambda: {
        name: p[..., :cache[name].shape[-1]].reshape(cache[name].shape)
        for name, p in pools.items()}, expert_tokens)


def generate(
    params: Params,
    prompt: jax.Array,
    config: TransformerConfig,
    *,
    max_new_tokens: int,
    temperature: float = 0.0,
    rng: Optional[jax.Array] = None,
    max_len: Optional[int] = None,
) -> jax.Array:
    """Greedy/temperature sampling. prompt: [B, P] → [B, P+max_new_tokens].
    The offline reference the tests hold the serve engine to, over
    :func:`decode_step`: not a serving path."""
    serve_only(config, "generate()")
    b, p = prompt.shape
    total = max_len or min(config.max_seq_len, p + max_new_tokens)
    cache = init_cache(config, b, total)
    w = config.uniform_window
    if w and cache["k"].shape[2] == w and p > w:
        # ring cache + long prompt: prefill in window-sized chunks so HBM
        # stays O(window) even for prompts far beyond it (the long-context
        # serving case SWA exists for); the tail chunk keeps its own
        # compiled shape
        logits = None
        for i in range(0, p, w):
            logits, cache = decode_step(params, cache, prompt[:, i:i + w],
                                        config)
    else:
        logits, cache = decode_step(params, cache, prompt, config)
    last = logits[:, -1]

    def sample(logits, key):
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(key, logits / temperature).astype(jnp.int32)

    if rng is None:
        rng = jax.random.PRNGKey(0)

    def step(carry, key):
        cache, last_logits = carry
        tok = sample(last_logits, key)
        logits, cache = decode_step(params, cache, tok[:, None], config)
        return (cache, logits[:, -1]), tok

    keys = jax.random.split(rng, max_new_tokens)
    (_, _), toks = lax.scan(step, (cache, last), keys)
    return jnp.concatenate([prompt, toks.T], axis=1)
