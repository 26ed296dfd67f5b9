"""Pallas TPU flash-attention kernel (causal + full), with GQA support.

Hand-tiled version of :func:`ray_tpu.ops.attention.blockwise_attention`:
grid ``(batch, heads, q_blocks, kv_blocks)`` where the kv dimension is
sequential ("arbitrary") and carries the online-softmax state in VMEM
scratch; batch/head/q dims are parallel. Causal skips fully-masked kv
blocks via predication, so the kernel does ~half the FLOPs of full
attention at long context.

Layout: the wrapper transposes to ``[B, H, L, D]`` so the last two dims of
every block are (seq_block, head_dim) — MXU/VPU tile friendly.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import (_softcap_dfactor as _softcap_dfac,
                                   _softcap_scores as _softcap_fwd)

NEG_INF = -1e30
LANES = 128  # running max / denom stored broadcast over one lane tile


def _block_band(qi, ki, block_q: int, block_k: int, causal: bool, window):
    """(live, band) for one (q block, kv block) pair — the ONE in-kernel
    definition of the causal/sliding-window band, shared by the forward
    and both backward kernels so their masking can never diverge.

    ``live``: the block intersects the band at all (predication skips the
    whole tile otherwise). ``band``: [bq, bk] bool, or None when unmasked.
    """
    live = True
    if causal:
        live = ki * block_k <= (qi + 1) * block_q - 1
    if window:
        live &= qi * block_q - ((ki + 1) * block_k - 1) < window
    if not (causal or window):
        return live, None
    rows = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    cols = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    band = rows >= cols
    if window:
        band &= rows - cols < window
    return live, band


def _require_causal_window(causal: bool, window) -> None:
    if window and not causal:
        raise ValueError("window requires causal attention")
    if window is not None and window <= 0:
        raise ValueError(f"window must be positive, got {window}")


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                  *, causal: bool, scale: float, block_q: int, block_k: int,
                  window=None, softcap: float = 0.0):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    live, band = _block_band(qi, ki, block_q, block_k, causal, window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)          # [bk, d]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                    # [bq, bk]
        s = _softcap_fwd(s, softcap)
        if band is not None:
            s = jnp.where(band, s, NEG_INF)
        m_prev = m_ref[:, 0:1]                       # [bq, 1]
        l_prev = l_ref[:, 0:1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)   # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)              # [bq, 1]
        p = jnp.exp(s - m_new)                       # [bq, bk]
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_ref[:, 0:1]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> 0 output
        o_ref[0, 0] = (acc_ref[:] / l).astype(o_ref.dtype)
        # log-sum-exp per query row — the only residual (besides o) the
        # memory-efficient backward needs. Broadcast across the lane dim:
        # Mosaic requires output block last-two-dims (8,128)-tileable, so
        # the block is [block_q, LANES] and the wrapper slices lane 0.
        lse_ref[0, 0] = m_ref[:] + jnp.log(jnp.where(l_ref[:] == 0.0, 1.0,
                                                     l_ref[:]))


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "block_q", "block_k", "window",
                     "softcap", "interpret"),
)
def flash_attention_pallas_fwd(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    window: Optional[int] = None,
    softcap: float = 0.0,
    interpret: bool = False,
):
    """Flash attention forward returning ``(out, lse)``.

    ``q``: [B, Lq, H, D]; ``k``/``v``: [B, Lk, Hk, D]; ``lse``: [B, H, Lq]
    float32 log-sum-exp per query row, consumed by the memory-efficient
    backward in :mod:`ray_tpu.ops.attention`.
    """
    _require_causal_window(causal, window)
    b, lq, h, d = q.shape
    lk, hk = k.shape[1], k.shape[2]
    if h % hk:
        raise ValueError(f"q heads {h} not divisible by kv heads {hk}")
    group = h // hk
    scale = scale if scale is not None else d ** -0.5
    block_q = min(block_q, lq)
    block_k = min(block_k, lk)
    if lq % block_q or lk % block_k:
        from ray_tpu.ops.attention import _mha_fwd_blockwise, _repeat_kv

        return _mha_fwd_blockwise(q, _repeat_kv(k, h), _repeat_kv(v, h),
                                  causal, scale, lq, lk, window,
                                  softcap=softcap)
    nq, nk = lq // block_q, lk // block_k

    qt = q.transpose(0, 2, 1, 3)  # [B, H, Lq, D]
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _flash_kernel, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, window=window, softcap=softcap,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, qi, ki: (b_, h_ // group, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, qi, ki: (b_, h_ // group, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            pl.BlockSpec((1, 1, block_q, LANES),
                         lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, lq, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, lq, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention_fwd",
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3), lse[..., 0]


def flash_attention_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    window: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Forward-only flash attention (inference paths). For training, go
    through :func:`ray_tpu.ops.attention.flash_attention` which attaches
    the memory-efficient custom VJP."""
    out, _ = flash_attention_pallas_fwd(
        q, k, v, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, window=window,
        interpret=interpret)
    return out


# ---------------------------------------------------------------------------
# Backward kernels (FlashAttention-2 split: dKV sweep + dQ sweep)
# ---------------------------------------------------------------------------
#
# Residuals are (q, k, v, out, lse) — O(L). The backward recomputes p
# blockwise:  D = rowsum(dO * O);  p = exp(s - lse);  dp = dO V^T;
# ds = p * (dp - D);  dV += p^T dO;  dK += scale * ds^T Q;
# dQ += scale * ds K.  Two kernels so each output has one sequential axis:
# the dKV kernel owns a kv block and sweeps q blocks; the dQ kernel owns a
# q block and sweeps kv blocks.


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc,
                          *, causal: bool, scale: float,
                          block_q: int, block_k: int, nq: int,
                          window=None, softcap: float = 0.0):
    """dK/dV sweep at NATIVE kv-head count: the sequential grid dim walks
    (group, q_block) pairs — ``t = g * nq + qi`` — so each kv head's
    gradients accumulate over every q head of its group without ever
    materializing group-expanded K/V or dK/dV (ADVICE r2 #5)."""
    ki = pl.program_id(2)
    t = pl.program_id(3)
    nt = pl.num_programs(3)
    qi = t % nq

    @pl.when(t == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    live, band = _block_band(qi, ki, block_q, block_k, causal, window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)           # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)           # [bk, d]
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)         # [bq, d]
        lse = lse_ref[0, 0][:, 0:1]                   # [bq, 1]
        delta = delta_ref[0, 0][:, 0:1]               # [bq, 1]
        s_hat = _softcap_fwd(
            jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale,
            softcap)
        s = s_hat
        if band is not None:
            s = jnp.where(band, s, NEG_INF)
        p = jnp.exp(s - lse)                          # [bq, bk]
        dv_acc[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # p^T dO: [bk, d]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)                         # [bq, bk]
        if softcap:
            # masked entries have p = 0 already, so the factor is harmless
            ds = ds * _softcap_dfac(s_hat, softcap)
        dk_acc[:] += scale * jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # ds^T q: [bk, d]

    @pl.when(t == nt - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dq_acc,
                         *, causal: bool, scale: float,
                         block_q: int, block_k: int, window=None,
                         softcap: float = 0.0):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    live, band = _block_band(qi, ki, block_q, block_k, causal, window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, 0:1]
        delta = delta_ref[0, 0][:, 0:1]
        s_hat = _softcap_fwd(
            jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale,
            softcap)
        s = s_hat
        if band is not None:
            s = jnp.where(band, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        if softcap:
            ds = ds * _softcap_dfac(s_hat, softcap)
        dq_acc[:] += scale * jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # ds k: [bq, d]

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "block_q", "block_k", "window",
                     "softcap", "interpret"),
)
def flash_attention_pallas_bwd(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    out: jax.Array,
    lse: jax.Array,
    dout: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    window: Optional[int] = None,
    softcap: float = 0.0,
    interpret: bool = False,
):
    """Backward pass. ``q``/``out``/``dout``: [B, Lq, H, D]; ``k``/``v``
    may stay at their NATIVE (possibly fewer, GQA) head count [B, Lk, Hk,
    D] — dk/dv come back at that count with the per-group accumulation
    done in-kernel, so GQA pays no group-factor HBM for transients
    (ADVICE r2 #5). ``lse``: [B, H, Lq]. Returns (dq, dk, dv)."""
    _require_causal_window(causal, window)
    b, lq, h, d = q.shape
    lk, hk = k.shape[1], k.shape[2]
    if h % hk:
        raise ValueError(f"q heads {h} not divisible by kv heads {hk}")
    group = h // hk
    scale = scale if scale is not None else d ** -0.5
    block_q = min(block_q, lq)
    block_k = min(block_k, lk)
    nq, nk = lq // block_q, lk // block_k

    qt = q.transpose(0, 2, 1, 3)      # [B, H, L, D]
    kt = k.transpose(0, 2, 1, 3)      # [B, Hk, L, D]
    vt = v.transpose(0, 2, 1, 3)
    dot = dout.transpose(0, 2, 1, 3)
    outt = out.transpose(0, 2, 1, 3)
    # D = rowsum(dO * O), lane-broadcast like lse for tileable blocks
    delta = (dot.astype(jnp.float32) * outt.astype(jnp.float32)).sum(-1)
    lse_b = jnp.broadcast_to(lse[..., None], (*lse.shape, LANES))
    delta_b = jnp.broadcast_to(delta[..., None], (*delta.shape, LANES))

    # dK/dV at native kv heads: grid dim 1 walks kv heads, the sequential
    # dim walks (group, q_block) pairs t = g*nq + qi; q-side tensors index
    # the q head h_*group + t//nq
    def _qside(b_, h_, ki, t):
        return (b_, h_ * group + t // nq, t % nq, 0)

    dkv_kernel = functools.partial(
        _flash_bwd_dkv_kernel, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, nq=nq, window=window,
        softcap=softcap)
    dk_t, dv_t = pl.pallas_call(
        dkv_kernel,
        grid=(b, hk, nk, nq * group),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), _qside),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, ki, t: (b_, h_, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, ki, t: (b_, h_, ki, 0)),
            pl.BlockSpec((1, 1, block_q, d), _qside),
            pl.BlockSpec((1, 1, block_q, LANES), _qside),
            pl.BlockSpec((1, 1, block_q, LANES), _qside),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, ki, t: (b_, h_, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, ki, t: (b_, h_, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hk, lk, d), k.dtype),
            jax.ShapeDtypeStruct((b, hk, lk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(qt, kt, vt, dot, lse_b, delta_b)

    dq_kernel = functools.partial(
        _flash_bwd_dq_kernel, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, window=window, softcap=softcap)
    dq_t = pl.pallas_call(
        dq_kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            # GQA: q head h_ reads kv head h_//group (forward's index-map trick)
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, qi, ki: (b_, h_ // group, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, qi, ki: (b_, h_ // group, ki, 0)),
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            pl.BlockSpec((1, 1, block_q, LANES), lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            pl.BlockSpec((1, 1, block_q, LANES), lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, lq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(qt, kt, vt, dot, lse_b, delta_b)

    return (dq_t.transpose(0, 2, 1, 3), dk_t.transpose(0, 2, 1, 3),
            dv_t.transpose(0, 2, 1, 3))
