"""Attention: naive reference + blockwise (flash-style) XLA implementation.

Shapes follow the JAX convention ``[batch, seq, heads, head_dim]``. Grouped
query attention (GQA) is supported: ``k``/``v`` may have fewer heads than
``q`` as long as ``q_heads % kv_heads == 0``.

The blockwise implementation is the online-softmax algorithm (running max /
running denominator) expressed with ``lax.scan`` so XLA keeps static shapes
and can pipeline HBM→VMEM streaming; the Pallas kernel in
:mod:`ray_tpu.ops.flash_pallas` is the hand-tiled version of the same loop.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30

# Process-wide attention implementation override. "auto" dispatches Pallas on
# TPU / blockwise XLA elsewhere; bench/serving preflights may pin "xla" when
# the Pallas kernel fails to compile on the attached chip (Mosaic tiling or
# VMEM rejections surface only at real-TPU compile time). Seeded from the
# RTPU_ATTN_IMPL env var so subprocesses inherit the choice.
_ATTN_IMPL = None  # None -> consult env / auto


def set_default_attention_impl(impl: Optional[str]) -> None:
    """Pin the attention implementation: "auto" | "pallas" | "xla" | "naive".

    ``None`` resets to the default (env ``RTPU_ATTN_IMPL`` or "auto").
    Takes effect at trace time, so call before compiling the model.
    """
    global _ATTN_IMPL
    if impl is not None and impl not in ("auto", "pallas", "xla", "naive"):
        raise ValueError(f"unknown attention impl: {impl!r}")
    _ATTN_IMPL = impl


def resolve_attention_impl() -> str:
    """Concrete impl for this process/backend: "pallas" | "xla" | "naive"."""
    import os

    from ray_tpu import config

    impl = _ATTN_IMPL or config.get("attn_impl") or "auto"
    if impl == "auto":
        from ray_tpu.util.tpu_info import is_tpu_backend

        impl = "pallas" if is_tpu_backend() else "xla"
    return impl


def _pallas_interpret() -> bool:
    """The ``attn_pallas_interpret`` knob: interpret-mode kernels for CPU
    rehearsals of the kernel path. Read at trace time."""
    from ray_tpu import config

    return bool(config.get("attn_pallas_interpret"))


def _band_mask(qpos, kpos, causal, window):
    """[qb, kb] visibility mask for the causal/sliding-window band, or None.

    The ONE definition shared by naive/blockwise/backward paths — forward
    and backward must never disagree on masking. ``window`` may be a
    TRACED int scalar (per-layer alternating windows ride a scanned layer
    stack in decode); ``None`` (not 0) means no window, so truthiness is
    never taken on a tracer.
    """
    if not (causal or window is not None):
        return None
    mask = qpos >= kpos
    if window is not None:
        mask &= (qpos - kpos) < window
    return mask


def _softcap_scores(s, softcap):
    """Attention-logit soft-capping (Gemma-2): ``cap * tanh(s / cap)``.
    Apply BEFORE masking — tanh(NEG_INF) would erase the mask value."""
    if not softcap:
        return s
    return softcap * jnp.tanh(s / softcap)


def _softcap_dfactor(s_hat, softcap):
    """d(capped)/d(raw) = 1 - tanh^2 = 1 - (s_hat/cap)^2, from the CAPPED
    (unmasked) score — shared by every backward recompute."""
    return 1.0 - jnp.square(s_hat / softcap)


def _repeat_kv(k: jax.Array, num_q_heads: int) -> jax.Array:
    """Expand kv heads to match q heads for GQA."""
    kv_heads = k.shape[2]
    if kv_heads == num_q_heads:
        return k
    if num_q_heads % kv_heads:
        raise ValueError(f"q heads {num_q_heads} not divisible by kv heads {kv_heads}")
    return jnp.repeat(k, num_q_heads // kv_heads, axis=2)


def naive_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    q_offset: int = 0,
    window: Optional[int] = None,
    k_offset=0,
    k_positions: Optional[jax.Array] = None,
    softcap: float = 0.0,
) -> jax.Array:
    """Materialized-scores attention; numerical reference for tests.

    ``q_offset`` shifts q's global positions (used for decode where q is a
    suffix of the kv sequence). ``window`` limits each query to the last
    ``window`` keys (sliding-window / Mistral-style local attention); it
    may be a traced int scalar (per-layer windows riding a decode scan).
    Ring KV caches position their keys explicitly: ``k_offset`` maps slot
    j to global position k_offset + j, or ``k_positions`` gives each slot
    an arbitrary global position; either way negative positions mean
    "slot not filled yet" and are masked. All three features require
    ``causal`` (they are defined in terms of the causal band).
    ``softcap`` applies Gemma-2-style tanh capping to the logits.
    """
    if isinstance(window, int) and window <= 0:
        window = None  # legacy "0 = off" callers; traced windows stay
    has_koff = (k_positions is not None
                or not (isinstance(k_offset, int) and k_offset == 0))
    if (window is not None or has_koff) and not causal:
        raise ValueError(
            "window / ring key positions require causal attention")
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    k = _repeat_kv(k, q.shape[2])
    v = _repeat_kv(v, q.shape[2])
    # [B, H, Lq, Lk]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
    scores = scores * scale
    scores = _softcap_scores(scores, softcap)
    if causal or window is not None or has_koff:
        lq, lk = q.shape[1], k.shape[1]
        if k_positions is not None:
            k_pos = k_positions
        else:
            k_pos = jnp.arange(lk) + k_offset
        mask = _band_mask(jnp.arange(lq)[:, None] + q_offset,
                          k_pos[None, :], causal, window)
        if has_koff:
            mask &= (k_pos >= 0)[None, :]
        scores = jnp.where(mask[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def _attend_block(q, k, v, m, l, o, mask, scale, softcap=0.0):
    """One online-softmax update: q block vs one kv block.

    q: [B, qb, H, D]; k/v: [B, kb, H, D]; m,l: [B, H, qb]; o: [B, qb, H, D];
    mask: [qb, kb] bool or None.
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale  # fp32
    s = _softcap_scores(s, softcap)
    if mask is not None:
        s = jnp.where(mask[None, None], s, NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    l_new = l * alpha + p.sum(axis=-1)
    o_new = o * alpha.transpose(0, 2, 1)[..., None] + jnp.einsum(
        "bhqk,bkhd->bqhd", p, v
    )
    return m_new, l_new, o_new


def blockwise_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    q_block: int = 512,
    kv_block: int = 512,
    q_offset: int = 0,
    window: Optional[int] = None,
    softcap: float = 0.0,
) -> jax.Array:
    """Flash-style attention with online softmax, pure XLA.

    Memory is O(q_block * kv_block) per head rather than O(Lq * Lk). Blocks
    are static so XLA tiles cleanly onto the MXU. ``window`` masks each
    query to its last ``window`` keys (sliding-window attention).
    """
    if isinstance(window, int) and window <= 0:
        window = None
    if window is not None and not causal:
        raise ValueError("window requires causal attention")
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    b, lq, h, d = q.shape
    lk = k.shape[1]
    k = _repeat_kv(k, h)
    v = _repeat_kv(v, h)
    q_block = min(q_block, lq)
    kv_block = min(kv_block, lk)
    if lq % q_block or lk % kv_block:
        # Fall back for ragged lengths; decode paths use naive anyway.
        return naive_attention(q, k, v, causal=causal, scale=scale,
                               q_offset=q_offset, window=window,
                               softcap=softcap)
    nq, nk = lq // q_block, lk // kv_block

    qf = q.astype(jnp.float32).reshape(b, nq, q_block, h, d)
    kf = k.astype(jnp.float32).reshape(b, nk, kv_block, h, d)
    vf = v.astype(jnp.float32).reshape(b, nk, kv_block, h, d)

    q_ids = jnp.arange(q_block)
    k_ids = jnp.arange(kv_block)

    def per_q_block(qi, qb):
        # qb: [B, qb, H, D]
        m0 = jnp.full((b, h, q_block), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, q_block), jnp.float32)
        o0 = jnp.zeros((b, q_block, h, d), jnp.float32)

        def kv_step(carry, inp):
            m, l, o = carry
            ki, kb, vb = inp
            mask = _band_mask(qi * q_block + q_ids[:, None] + q_offset,
                              ki * kv_block + k_ids[None, :], causal, window)
            m, l, o = _attend_block(qb, kb, vb, m, l, o, mask, scale, softcap)
            return (m, l, o), None

        (m, l, o), _ = lax.scan(
            kv_step, (m0, l0, o0), (jnp.arange(nk), kf.swapaxes(0, 1), vf.swapaxes(0, 1))
        )
        return o / l.transpose(0, 2, 1)[..., None]

    out = lax.map(lambda args: per_q_block(*args), (jnp.arange(nq), qf.swapaxes(0, 1)))
    # out: [nq, B, qb, H, D] -> [B, Lq, H, D]
    out = out.swapaxes(0, 1).reshape(b, lq, h, d)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Memory-efficient attention with a custom VJP (FlashAttention-2 style)
# ---------------------------------------------------------------------------
#
# Differentiating the blockwise/Pallas forward directly makes jax save every
# probability block as a residual — O(Lq*Lk) per layer, which stacks across
# a scanned-layer model into tens of GB (the round-1 bench OOMed a 16 GB
# v5e HBM on exactly this). The standard fix is a custom VJP: the forward
# saves only (q, k, v, out, lse) — O(L) per token — and the backward
# recomputes the probability blocks on the fly.
#
# Backward math (s = scale * q k^T, p = softmax rows = exp(s - lse)):
#   D  = rowsum(dout * out)            [B, H, Lq]
#   dp = dout v^T                      per block
#   ds = p * (dp - D)
#   dq = scale * ds k ; dk = scale * ds^T q ; dv = p^T dout
#
# GQA is handled OUTSIDE the custom-vjp core: kv heads are expanded with
# jnp.repeat first, whose autodiff sums gradients back over the group.


def _n_live_kv_blocks(nk: int, q_block: int, kv_block: int,
                      window) -> int:
    """Static count of kv blocks a q block can see under the window band.

    The visible columns for q block qi span ``q_block + window - 1``
    positions, which cross at most that many // kv_block + 2 block
    boundaries. Without a window every block is live.
    """
    if not window:
        return nk
    return min(nk, (q_block + window - 2) // kv_block + 2)


def _live_kv_start(qi, nk: int, n_live: int, q_block: int, kv_block: int,
                   window, pos_delta: int = 0):
    """First live kv block for q block ``qi`` (traced), clamped so the
    static-length slice stays in range. Clamping only ever EXTENDS
    coverage (earlier blocks get window-masked; later ones causal-masked),
    never drops a live block. ``pos_delta`` = (global q position of local
    q index 0) - (global k position of local k index 0) for the affine
    positional path (halo SP: delta = Lloc)."""
    if not window:
        return jnp.int32(0)
    start = (qi * q_block + pos_delta - (window - 1)) // kv_block
    return jnp.clip(start, 0, nk - n_live).astype(jnp.int32)


def _mha_fwd_blockwise(q, k, v, causal, scale, q_block, kv_block,
                       window=None, qpos=None, kpos=None, pos_delta=None,
                       softcap=0.0):
    """Blockwise forward returning (out, lse). Heads already expanded.

    Causal rows always see at least the diagonal key, so lse is finite.
    With ``window``, only the O(window/kv_block) live kv blocks per q block
    are scanned (static count, dynamic start) — the SWA FLOP win. A scanned
    block can still be fully masked for SOME rows: those rows accumulate
    exp(NEG_INF - NEG_INF) = 1 fake mass per key, which the online-softmax
    rescale alpha = exp(NEG_INF - m_finite) annihilates to exactly 0 at the
    first in-band block (every row's diagonal block IS in range). This
    relies on NEG_INF being a large FINITE negative — -inf would make the
    rescale exp(-inf - (-inf)) = NaN.
    """
    b, lq, h, d = q.shape
    lk = k.shape[1]
    nq, nk = lq // q_block, lk // kv_block
    qf = q.astype(jnp.float32).reshape(b, nq, q_block, h, d)
    kf = k.astype(jnp.float32).reshape(b, nk, kv_block, h, d)
    vf = v.astype(jnp.float32).reshape(b, nk, kv_block, h, d)
    kf_s, vf_s = kf.swapaxes(0, 1), vf.swapaxes(0, 1)  # [nk, B, kb, H, D]
    q_ids = jnp.arange(q_block)
    k_ids = jnp.arange(kv_block)
    # explicit position arrays keep the windowed live-block slicing as
    # long as the caller declares their affine delta (halo SP passes
    # Lloc); arbitrary non-affine positions fall back to the full scan
    n_live = (nk if (kpos is not None and pos_delta is None)
              else _n_live_kv_blocks(nk, q_block, kv_block, window))

    def per_q_block(qi, qb):
        m0 = jnp.full((b, h, q_block), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, q_block), jnp.float32)
        o0 = jnp.zeros((b, q_block, h, d), jnp.float32)

        def kv_step(carry, inp):
            m, l, o = carry
            ki, kb, vb = inp
            qp = (qi * q_block + q_ids if qpos is None
                  else lax.dynamic_slice_in_dim(qpos, qi * q_block, q_block))
            kp = (ki * kv_block + k_ids if kpos is None
                  else lax.dynamic_slice_in_dim(kpos, ki * kv_block,
                                                kv_block))
            mask = _band_mask(qp[:, None], kp[None, :], causal, window)
            if kpos is not None and mask is not None:
                mask &= (kp >= 0)[None, :]
            m, l, o = _attend_block(qb, kb, vb, m, l, o, mask, scale, softcap)
            return (m, l, o), None

        if kpos is not None and pos_delta is None:
            start = jnp.int32(0)
        else:
            start = _live_kv_start(qi, nk, n_live, q_block, kv_block,
                                   window, pos_delta or 0)
        idx = start + jnp.arange(n_live)
        ks = lax.dynamic_slice_in_dim(kf_s, start, n_live, axis=0)
        vs = lax.dynamic_slice_in_dim(vf_s, start, n_live, axis=0)
        (m, l, o), _ = lax.scan(kv_step, (m0, l0, o0), (idx, ks, vs))
        lse = m + jnp.log(jnp.maximum(l, 1e-30))        # [B, H, qb]
        return o / l.transpose(0, 2, 1)[..., None], lse

    out, lse = lax.map(lambda args: per_q_block(*args),
                       (jnp.arange(nq), qf.swapaxes(0, 1)))
    # out: [nq, B, qb, H, D] -> [B, Lq, H, D]; lse: [nq, B, H, qb] -> [B, H, Lq]
    out = out.swapaxes(0, 1).reshape(b, lq, h, d).astype(q.dtype)
    lse = lse.transpose(1, 2, 0, 3).reshape(b, h, lq)
    return out, lse


def _mha_bwd_blockwise(causal, scale, q_block, kv_block,
                       q, k, v, out, lse, dout, window=None,
                       qpos=None, kpos=None, pos_delta=None, softcap=0.0):
    """Blocked backward; recomputes p per (q-block, kv-block) pair."""
    b, lq, h, d = q.shape
    lk = k.shape[1]
    nq, nk = lq // q_block, lk // kv_block
    qf = q.astype(jnp.float32).reshape(b, nq, q_block, h, d).swapaxes(0, 1)
    kf = k.astype(jnp.float32).reshape(b, nk, kv_block, h, d).swapaxes(0, 1)
    vf = v.astype(jnp.float32).reshape(b, nk, kv_block, h, d).swapaxes(0, 1)
    dof = dout.astype(jnp.float32).reshape(b, nq, q_block, h, d).swapaxes(0, 1)
    outf = out.astype(jnp.float32).reshape(b, nq, q_block, h, d).swapaxes(0, 1)
    lsef = lse.reshape(b, h, nq, q_block).transpose(2, 0, 1, 3)  # [nq,B,H,qb]
    q_ids = jnp.arange(q_block)
    k_ids = jnp.arange(kv_block)

    n_live = (nk if (kpos is not None and pos_delta is None)
              else _n_live_kv_blocks(nk, q_block, kv_block, window))

    def q_step(carry, inp):
        dk_acc, dv_acc = carry                     # [nk, B, kb, H, D]
        qi, qb, dob, ob, lseb = inp
        dvec = (dob * ob).sum(-1).transpose(0, 2, 1)  # D: [B, H, qb]

        def kv_step(_, kin):
            ki, kb, vb = kin
            s = jnp.einsum("bqhd,bkhd->bhqk", qb, kb) * scale
            s_hat = _softcap_scores(s, softcap)  # pre-mask: dfactor source
            qp = (qi * q_block + q_ids if qpos is None
                  else lax.dynamic_slice_in_dim(qpos, qi * q_block, q_block))
            kp = (ki * kv_block + k_ids if kpos is None
                  else lax.dynamic_slice_in_dim(kpos, ki * kv_block,
                                                kv_block))
            mask = _band_mask(qp[:, None], kp[None, :], causal, window)
            if kpos is not None and mask is not None:
                mask &= (kp >= 0)[None, :]
            s = s_hat
            if mask is not None:
                s = jnp.where(mask[None, None], s, NEG_INF)
            # out-of-band keys: s = NEG_INF, lse finite -> p underflows to
            # exactly 0 (NEG_INF must stay a finite float for this)
            p = jnp.exp(s - lseb[..., None])       # [B, H, qb, kb]
            dp = jnp.einsum("bqhd,bkhd->bhqk", dob, vb)
            ds = p * (dp - dvec[..., None])
            if softcap:
                # chain through the cap: d(raw)/d(capped); masked entries
                # already have p = 0, so the (finite) factor is harmless
                ds = ds * _softcap_dfactor(s_hat, softcap)
            dq_c = scale * jnp.einsum("bhqk,bkhd->bqhd", ds, kb)
            dk_c = scale * jnp.einsum("bhqk,bqhd->bkhd", ds, qb)
            dv_c = jnp.einsum("bhqk,bqhd->bkhd", p, dob)
            return None, (dq_c, dk_c, dv_c)

        if kpos is not None and pos_delta is None:
            start = jnp.int32(0)
        else:
            start = _live_kv_start(qi, nk, n_live, q_block, kv_block,
                                   window, pos_delta or 0)
        idx = start + jnp.arange(n_live)
        ks = lax.dynamic_slice_in_dim(kf, start, n_live, axis=0)
        vs = lax.dynamic_slice_in_dim(vf, start, n_live, axis=0)
        _, (dq_cs, dk_cs, dv_cs) = lax.scan(kv_step, None, (idx, ks, vs))
        if n_live == nk:
            dk_acc = dk_acc + dk_cs
            dv_acc = dv_acc + dv_cs
        else:
            dk_acc = lax.dynamic_update_slice_in_dim(
                dk_acc,
                lax.dynamic_slice_in_dim(dk_acc, start, n_live, 0) + dk_cs,
                start, 0)
            dv_acc = lax.dynamic_update_slice_in_dim(
                dv_acc,
                lax.dynamic_slice_in_dim(dv_acc, start, n_live, 0) + dv_cs,
                start, 0)
        return (dk_acc, dv_acc), dq_cs.sum(0)

    zeros_kv = jnp.zeros((nk, b, kv_block, h, d), jnp.float32)
    (dk, dv), dq_blocks = lax.scan(
        q_step, (zeros_kv, zeros_kv),
        (jnp.arange(nq), qf, dof, outf, lsef))
    dq = dq_blocks.swapaxes(0, 1).reshape(b, lq, h, d).astype(q.dtype)
    dk = dk.swapaxes(0, 1).reshape(b, lk, h, d).astype(k.dtype)
    dv = dv.swapaxes(0, 1).reshape(b, lk, h, d).astype(v.dtype)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _mha(q, k, v, causal, scale, q_block, kv_block, use_pallas, window=None,
         softcap=0.0):
    out, _ = _mha_fwd(q, k, v, causal, scale, q_block, kv_block, use_pallas,
                      window, softcap)
    return out


def _mha_fwd(q, k, v, causal, scale, q_block, kv_block, use_pallas,
             window=None, softcap=0.0):
    """k/v stay at their native (possibly fewer, GQA) head count in the
    residuals — expanding before the VJP would multiply residual HBM by the
    group factor, eroding the O(L) memory win this VJP exists for."""
    if use_pallas:
        from ray_tpu.ops.flash_pallas import flash_attention_pallas_fwd

        # the Pallas kernel handles GQA natively (kv block reuse per group)
        out, lse = flash_attention_pallas_fwd(
            q, k, v, causal=causal, scale=scale,
            block_q=q_block, block_k=kv_block, window=window,
            softcap=softcap, interpret=_pallas_interpret())
    else:
        h = q.shape[2]
        out, lse = _mha_fwd_blockwise(q, _repeat_kv(k, h), _repeat_kv(v, h),
                                      causal, scale, q_block, kv_block,
                                      window, softcap=softcap)
    return out, (q, k, v, out, lse)


def _mha_fwd_rule(q, k, v, causal, scale, q_block, kv_block, use_pallas,
                  window=None, softcap=0.0):
    out, res = _mha_fwd(q, k, v, causal, scale, q_block, kv_block, use_pallas,
                        window, softcap)
    return out, res


def _mha_bwd_rule(causal, scale, q_block, kv_block, use_pallas, window,
                  softcap, res, dout):
    q, k, v, out, lse = res
    b, lk, hk, d = k.shape
    lq, h = q.shape[1], q.shape[2]
    # Backward impl follows the forward: hand-tiled Pallas kernels (FA2
    # dKV/dQ sweeps) on TPU, blockwise XLA elsewhere — O(L) residuals
    # either way. The Pallas kernels are GQA-NATIVE (per-group index maps
    # + in-kernel group accumulation, ADVICE r2 #5); only the XLA fallback
    # expands kv transiently and group-sums the grads back.
    if (use_pallas and lq % min(q_block, lq) == 0
            and lk % min(kv_block, lk) == 0):
        from ray_tpu.ops.flash_pallas import flash_attention_pallas_bwd

        dq, dk, dv = flash_attention_pallas_bwd(
            q, k, v, out, lse, dout, causal=causal, scale=scale,
            block_q=q_block, block_k=kv_block, window=window,
            softcap=softcap, interpret=_pallas_interpret())
    else:
        kx, vx = _repeat_kv(k, h), _repeat_kv(v, h)
        dq, dk, dv = _mha_bwd_blockwise(causal, scale, q_block, kv_block,
                                        q, kx, vx, out, lse, dout, window,
                                        softcap=softcap)
        if hk != h:
            group = h // hk
            dk = dk.reshape(b, lk, hk, group, d).sum(axis=3)
            dv = dv.reshape(b, lk, hk, group, d).sum(axis=3)
    return dq, dk, dv


_mha.defvjp(_mha_fwd_rule, _mha_bwd_rule)


# --- positional variant: explicit global positions per query/key ----------
# Used by the halo-exchange sequence-parallel sliding-window path, where
# each shard's queries/keys carry global positions (float32 so the
# custom-vjp cotangents are well-typed zeros; negative key positions mean
# "halo wrap garbage" and are masked). Same O(L) residuals as _mha.

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _mha_pos(q, k, v, qpos, kpos, scale, q_block, kv_block, window,
             pos_delta=None, softcap=0.0):
    out, _ = _mha_pos_fwd(q, k, v, qpos, kpos, scale, q_block, kv_block,
                          window, pos_delta, softcap)
    return out


def _mha_pos_fwd(q, k, v, qpos, kpos, scale, q_block, kv_block, window,
                 pos_delta=None, softcap=0.0):
    h = q.shape[2]
    out, lse = _mha_fwd_blockwise(q, _repeat_kv(k, h), _repeat_kv(v, h),
                                  True, scale, q_block, kv_block, window,
                                  qpos=qpos, kpos=kpos, pos_delta=pos_delta,
                                  softcap=softcap)
    return out, (q, k, v, out, lse, qpos, kpos)


def _mha_pos_bwd(scale, q_block, kv_block, window, pos_delta, softcap,
                 res, dout):
    q, k, v, out, lse, qpos, kpos = res
    b, lk, hk, d = k.shape
    h = q.shape[2]
    kx, vx = _repeat_kv(k, h), _repeat_kv(v, h)
    dq, dk, dv = _mha_bwd_blockwise(True, scale, q_block, kv_block,
                                    q, kx, vx, out, lse, dout, window,
                                    qpos=qpos, kpos=kpos,
                                    pos_delta=pos_delta, softcap=softcap)
    if hk != h:
        group = h // hk
        dk = dk.reshape(b, lk, hk, group, d).sum(axis=3)
        dv = dv.reshape(b, lk, hk, group, d).sum(axis=3)
    return dq, dk, dv, jnp.zeros_like(qpos), jnp.zeros_like(kpos)


_mha_pos.defvjp(lambda *a: _mha_pos_fwd(*a), _mha_pos_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    impl: str = "auto",
    q_block: int = 512,
    kv_block: int = 512,
    window: Optional[int] = None,
    softcap: float = 0.0,
) -> jax.Array:
    """Dispatching entry point: Pallas kernel on TPU, blockwise XLA elsewhere.

    ``impl``: ``auto`` | ``pallas`` | ``xla`` | ``naive``. Both pallas and
    xla run through the memory-efficient custom VJP above, so this is safe
    to differentiate at long context (no O(L^2) residuals).

    ``window`` enables sliding-window (Mistral-style local) attention:
    each query sees only its last ``window`` keys. Requires ``causal``.
    Both the Pallas kernels (banded block-liveness predicates) and the
    blockwise-XLA path (live kv-block slicing) skip out-of-band blocks,
    so SWA costs O(L * window), not O(L^2).

    Deliberately NOT jitted here: "auto" must resolve at every trace so a
    later ``set_default_attention_impl`` (e.g. a preflight pinning "xla"
    after Mosaic rejects the kernel) is honored — a jit cache keyed on the
    literal "auto" would replay the stale choice. Callers jit the enclosing
    computation; eager use still compiles the Pallas/blockwise internals.
    """
    if window is not None:
        if not causal:
            raise ValueError("window requires causal attention")
        if window <= 0:
            raise ValueError(f"window must be positive, got {window}")
    if impl == "auto":
        impl = resolve_attention_impl()
    if impl == "naive":
        return naive_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap)
    b, lq, h, d = q.shape
    lk, hk = k.shape[1], k.shape[2]
    q_block = min(q_block, lq)
    kv_block = min(kv_block, lk)
    if lq % q_block or lk % kv_block:
        # Non-divisible tile knob (e.g. RTPU_ATTN_BLOCK_Q=768 with seq
        # 2048): shrink to the largest divisor >=128 rather than silently
        # dispatching a differentiated TRAINING path to naive — naive
        # materializes O(L^2) scores and reintroduces the exact OOM the
        # custom VJP exists to prevent (ADVICE r4 #5). Genuinely ragged
        # short decode shapes (no >=128 divisor) still use naive.
        import warnings

        # blocks must stay sublane-aligned (x % 8) or Mosaic rejects the
        # Pallas BlockSpec on real silicon
        qb = next((x for x in range(q_block, 127, -1)
                   if lq % x == 0 and x % 8 == 0), 0)
        kb = next((x for x in range(kv_block, 127, -1)
                   if lk % x == 0 and x % 8 == 0), 0)
        if qb and kb:
            warnings.warn(
                f"attention tile sizes (q={q_block}, kv={kv_block}) do not "
                f"divide seq (lq={lq}, lk={lk}); using largest divisors "
                f"(q={qb}, kv={kb}) instead")
            q_block, kv_block = qb, kb
        else:
            return naive_attention(q, k, v, causal=causal, window=window,
                                   softcap=softcap)
    scale = d ** -0.5
    from ray_tpu.util import device_plane as _dp

    if _dp.device_plane_enabled() and not isinstance(q, jax.core.Tracer):
        # EAGER entry point (bench numerics, tests, preflights): the
        # blockwise/Pallas internals compile implicitly here — register
        # novel signatures as compiles of "ops::flash_attention" so the
        # device plane sees them too. Inside an enclosing jit (tracers)
        # the CALLER's registered program owns the compile.
        return _dp.tracked_call(
            "ops::flash_attention", "ops",
            lambda: _mha(q, k, v, causal, scale, q_block, kv_block,
                         impl == "pallas", window, softcap),
            (q, k, v),
            statics={"impl": impl, "causal": causal, "q_block": q_block,
                     "kv_block": kv_block, "window": window,
                     "softcap": softcap})
    return _mha(q, k, v, causal, scale, q_block, kv_block,
                impl == "pallas", window, softcap)
