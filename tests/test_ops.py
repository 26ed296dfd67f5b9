"""Kernel correctness: blockwise/pallas/ring attention vs naive reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import (
    blockwise_attention,
    naive_attention,
    ring_attention,
    rms_norm,
    rotary_embedding,
    apply_rotary,
    moe_layer_dense,
)
from ray_tpu.ops.flash_pallas import flash_attention_pallas


def _rand_qkv(key, b=2, lq=128, lk=128, h=4, hk=None, d=32, dtype=jnp.float32):
    hk = h if hk is None else hk
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (b, lq, h, d), dtype)
    k = jax.random.normal(k2, (b, lk, hk, d), dtype)
    v = jax.random.normal(k3, (b, lk, hk, d), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_blockwise_matches_naive(causal):
    q, k, v = _rand_qkv(jax.random.PRNGKey(0))
    ref = naive_attention(q, k, v, causal=causal)
    out = blockwise_attention(q, k, v, causal=causal, q_block=32, kv_block=64)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_blockwise_gqa():
    q, k, v = _rand_qkv(jax.random.PRNGKey(1), h=8, hk=2)
    ref = naive_attention(q, k, v, causal=True)
    out = blockwise_attention(q, k, v, causal=True, q_block=32, kv_block=32)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_pallas_interpret_matches_naive(causal):
    q, k, v = _rand_qkv(jax.random.PRNGKey(2), b=1, lq=256, lk=256, h=2, d=64)
    ref = naive_attention(q, k, v, causal=causal)
    out = flash_attention_pallas(
        q, k, v, causal=causal, block_q=128, block_k=128, interpret=True
    )
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_pallas_interpret_gqa():
    q, k, v = _rand_qkv(jax.random.PRNGKey(3), b=1, lq=128, lk=128, h=4, hk=2, d=64)
    ref = naive_attention(q, k, v, causal=True)
    out = flash_attention_pallas(
        q, k, v, causal=True, block_q=64, block_k=64, interpret=True
    )
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_full(causal):
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map

    devs = np.array(jax.devices()[:4])
    mesh = Mesh(devs, ("sp",))
    q, k, v = _rand_qkv(jax.random.PRNGKey(4), b=2, lq=64, lk=64, h=2, d=16)
    ref = naive_attention(q, k, v, causal=causal)

    fn = shard_map(
        lambda q_, k_, v_: ring_attention(q_, k_, v_, axis="sp", causal=causal),
        mesh=mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"),
    )
    out = fn(q, k, v)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=2e-5)


def test_rms_norm():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 8))
    w = jnp.ones((8,)) * 2.0
    out = rms_norm(x, w)
    expect = x / np.sqrt(np.mean(np.square(np.asarray(x)), -1, keepdims=True) + 1e-6) * 2.0
    np.testing.assert_allclose(out, expect, atol=1e-5)


@pytest.mark.parametrize("kind", ["rms", "layer"])
def test_norm_custom_vjp_matches_autodiff(kind):
    """The bf16-residual custom VJPs must match plain autodiff exactly.

    Reference grads come from differentiating the raw f32 math (no custom
    VJP) — the analytic backward in ops/layers.py must agree for both dx
    and dw, in f32 (tight tol) and bf16 inputs (cast tol).
    """
    from ray_tpu.ops import layer_norm
    from ray_tpu.ops.layers import _layer_norm_fwd_math, _rms_norm_fwd_math

    if kind == "rms":
        fn = lambda x, w: rms_norm(x, w)
        raw = lambda x, w: _rms_norm_fwd_math(x, w, 1e-6)
    else:
        bias = jnp.full((32,), 0.25)
        fn = lambda x, w: layer_norm(x, w, bias.astype(x.dtype))
        raw = lambda x, w: _layer_norm_fwd_math(x, w, bias.astype(x.dtype),
                                                1e-5)

    for dtype, tol in ((jnp.float32, 1e-5), (jnp.bfloat16, 2e-2)):
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 16, 32), dtype)
        w = (1.0 + 0.1 * jax.random.normal(
            jax.random.PRNGKey(1), (32,))).astype(dtype)
        g = jax.random.normal(jax.random.PRNGKey(2), (4, 16, 32))

        def loss(f):
            return lambda x_, w_: (f(x_, w_).astype(jnp.float32) * g).sum()

        val, grads = jax.value_and_grad(loss(fn), argnums=(0, 1))(x, w)
        val_r, grads_r = jax.value_and_grad(loss(raw), argnums=(0, 1))(x, w)
        np.testing.assert_allclose(val, val_r, rtol=tol)
        for a, b in zip(grads, grads_r):
            np.testing.assert_allclose(
                np.asarray(a, dtype="float32"),
                np.asarray(b, dtype="float32"), atol=tol, rtol=tol)


@pytest.mark.parametrize("op", ["rms", "layer", "rotary"])
def test_vjp_residuals_are_input_dtype(op):
    """The custom VJPs must not stash f32 intermediates: residuals of a
    bf16 op stay bf16 (plus tiny tables). This is the property that lets
    no-remat training fit HBM — a regression here only surfaces as an
    on-chip OOM."""
    from ray_tpu.ops import layer_norm

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 4, 32), jnp.bfloat16)
    w = jnp.ones((32,), jnp.bfloat16)
    if op == "rms":
        _, vjp_fn = jax.vjp(rms_norm, x, w)
    elif op == "layer":
        _, vjp_fn = jax.vjp(lambda x_, w_: layer_norm(x_, w_, w), x, w)
    else:
        cos, sin = rotary_embedding(jnp.arange(8), 32)
        _, vjp_fn = jax.vjp(lambda x_: apply_rotary(x_, cos, sin), x)
    leaves = jax.tree_util.tree_leaves(vjp_fn)
    f32_big = [l for l in leaves
               if hasattr(l, "dtype") and l.dtype == jnp.float32
               and getattr(l, "size", 0) >= x.size]
    assert not f32_big, f"f32 residuals leaked: {[l.shape for l in f32_big]}"


def test_rotary_custom_vjp_matches_autodiff():
    """apply_rotary's rotate-the-cotangent backward vs plain autodiff."""
    from ray_tpu.ops.layers import _rotate

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 4, 32))
    cos, sin = rotary_embedding(jnp.arange(16), 32)
    g = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 4, 32))

    def loss(f):
        return lambda x_: (f(x_, cos, sin).astype(jnp.float32) * g).sum()

    dx = jax.grad(loss(apply_rotary))(x)
    dx_ref = jax.grad(loss(lambda x_, c, s: _rotate(x_, c, s, +1.0)))(x)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(dx_ref),
                               atol=1e-5, rtol=1e-5)


def test_rotary_norm_preserving():
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 4, 32))
    cos, sin = rotary_embedding(jnp.arange(16), 32)
    y = apply_rotary(x, cos, sin)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(y), axis=-1),
        np.linalg.norm(np.asarray(x), axis=-1),
        atol=1e-4, rtol=1e-4,
    )
    # position 0 is identity
    np.testing.assert_allclose(np.asarray(y[:, 0]), np.asarray(x[:, 0]), atol=1e-5)


def test_moe_shapes_and_gradient():
    key = jax.random.PRNGKey(5)
    b, l, d, e, f = 2, 8, 16, 4, 32
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (b, l, d))
    router_w = jax.random.normal(ks[1], (d, e)) * 0.1
    w_gate = jax.random.normal(ks[2], (e, d, f)) * 0.1
    w_up = jax.random.normal(ks[3], (e, d, f)) * 0.1
    w_down = jax.random.normal(ks[4], (e, f, d)) * 0.1

    def loss(params):
        out, aux = moe_layer_dense(x, *params, k=2, capacity_factor=2.0)
        return jnp.sum(out ** 2) + 0.01 * aux

    val, grads = jax.value_and_grad(loss)((router_w, w_gate, w_up, w_down))
    assert np.isfinite(float(val))
    for g in grads:
        assert np.all(np.isfinite(np.asarray(g)))


def test_moe_full_capacity_matches_dense_topk():
    # With capacity >= tokens, no drops: output = sum of top-k expert outputs
    key = jax.random.PRNGKey(6)
    b, l, d, e, f = 1, 4, 8, 2, 16
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (b, l, d))
    router_w = jax.random.normal(ks[1], (d, e))
    w_gate = jax.random.normal(ks[2], (e, d, f)) * 0.2
    w_up = jax.random.normal(ks[3], (e, d, f)) * 0.2
    w_down = jax.random.normal(ks[4], (e, f, d)) * 0.2

    out, _ = moe_layer_dense(x, router_w, w_gate, w_up, w_down, k=e,
                             capacity_factor=float(e * b * l))
    # dense reference: softmax-weighted sum over ALL experts (k=e)
    xt = np.asarray(x).reshape(-1, d)
    probs = jax.nn.softmax(xt @ np.asarray(router_w), axis=-1)
    expect = np.zeros_like(xt)
    for ei in range(e):
        gate = np.asarray(jax.nn.silu(xt @ np.asarray(w_gate[ei])))
        h = gate * (xt @ np.asarray(w_up[ei]))
        expect += probs[:, ei:ei + 1] * (h @ np.asarray(w_down[ei]))
    np.testing.assert_allclose(np.asarray(out).reshape(-1, d), expect, atol=1e-4)


# ---------------------------------------------------------------------------
# Memory-efficient custom VJP (flash_attention): grads vs naive autodiff
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_vjp_matches_naive_grads(causal):
    from ray_tpu.ops import flash_attention

    q, k, v = _rand_qkv(jax.random.PRNGKey(4), b=2, lq=128, lk=128, h=4, d=32)
    tang = jax.random.normal(jax.random.PRNGKey(5), q.shape, q.dtype)

    def loss_ref(q, k, v):
        return (naive_attention(q, k, v, causal=causal) * tang).sum()

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=causal, impl="xla",
                                q_block=32, kv_block=64) * tang).sum()

    ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for r, g in zip(ref, got):
        np.testing.assert_allclose(g, r, atol=5e-5, rtol=5e-5)


def test_flash_attention_vjp_gqa_grads():
    """GQA: kv grads must sum over the head group (handled by repeat's AD)."""
    from ray_tpu.ops import flash_attention

    q, k, v = _rand_qkv(jax.random.PRNGKey(6), b=1, lq=64, lk=64, h=8, hk=2,
                        d=16)
    tang = jax.random.normal(jax.random.PRNGKey(7), q.shape, q.dtype)

    def loss_ref(q, k, v):
        return (naive_attention(q, k, v, causal=True) * tang).sum()

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=True, impl="xla",
                                q_block=32, kv_block=32) * tang).sum()

    ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for r, g in zip(ref, got):
        assert r.shape == g.shape
        np.testing.assert_allclose(g, r, atol=5e-5, rtol=5e-5)


def test_pallas_fwd_lse_interpret_and_hybrid_grad():
    """Pallas forward's lse must agree with the blockwise forward's, and the
    pallas-fwd/xla-bwd hybrid VJP must match naive grads (interpret mode)."""
    from ray_tpu.ops.attention import _mha_fwd_blockwise
    from ray_tpu.ops.flash_pallas import flash_attention_pallas_fwd

    q, k, v = _rand_qkv(jax.random.PRNGKey(8), b=1, lq=256, lk=256, h=2, d=64)
    out_p, lse_p = flash_attention_pallas_fwd(
        q, k, v, causal=True, block_q=128, block_k=128, interpret=True)
    out_b, lse_b = _mha_fwd_blockwise(q, k, v, True, 64 ** -0.5, 128, 128)
    np.testing.assert_allclose(out_p, out_b, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(lse_p, lse_b, atol=2e-5, rtol=2e-5)


def test_flash_attention_vjp_memory_shape():
    """The residuals of the custom VJP are O(L): differentiate a long-ish
    sequence that would need a huge p-residual under plain autodiff."""
    from ray_tpu.ops import flash_attention

    # 2048^2 * 4 heads * f32 p-residual would be 64 MiB *per layer*; with
    # the VJP residuals are q,k,v,out,lse ~= 4 MiB. Just proving it runs
    # and produces finite grads at this length on CPU is the regression.
    q, k, v = _rand_qkv(jax.random.PRNGKey(9), b=1, lq=2048, lk=2048, h=2,
                        d=32)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, impl="xla").sum()

    g = jax.grad(loss)(q, k, v)
    assert np.isfinite(np.asarray(g)).all()


@pytest.mark.parametrize("causal", [True, False])
def test_pallas_bwd_kernels_match_naive_grads(causal):
    """FA2-style dKV/dQ pallas kernels (interpret mode) vs naive autodiff."""
    from ray_tpu.ops.attention import _mha_fwd_blockwise
    from ray_tpu.ops.flash_pallas import flash_attention_pallas_bwd

    q, k, v = _rand_qkv(jax.random.PRNGKey(10), b=1, lq=256, lk=256, h=2,
                        d=64)
    tang = jax.random.normal(jax.random.PRNGKey(11), q.shape, q.dtype)

    def loss_ref(q, k, v):
        return (naive_attention(q, k, v, causal=causal) * tang).sum()

    ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    out, lse = _mha_fwd_blockwise(q, k, v, causal, 64 ** -0.5, 128, 128)
    got = flash_attention_pallas_bwd(
        q, k, v, out, lse, tang, causal=causal,
        block_q=128, block_k=128, interpret=True)
    for r, g in zip(ref, got):
        np.testing.assert_allclose(g, r, atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_pallas_bwd_gqa_native_heads(causal):
    """GQA backward at NATIVE kv-head count (no group expand, ADVICE r2
    #5): dk/dv come back [B, Lk, Hk, D] and match naive autodiff."""
    from ray_tpu.ops.attention import _mha_fwd_blockwise, _repeat_kv
    from ray_tpu.ops.flash_pallas import flash_attention_pallas_bwd

    h, hk = 4, 2
    q, _, _ = _rand_qkv(jax.random.PRNGKey(12), b=1, lq=256, lk=256, h=h,
                        d=64)
    _, k, v = _rand_qkv(jax.random.PRNGKey(13), b=1, lq=256, lk=256, h=hk,
                        d=64)
    tang = jax.random.normal(jax.random.PRNGKey(14), q.shape, q.dtype)

    def loss_ref(q, k, v):
        return (naive_attention(q, k, v, causal=causal) * tang).sum()

    ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    out, lse = _mha_fwd_blockwise(q, _repeat_kv(k, h), _repeat_kv(v, h),
                                  causal, 64 ** -0.5, 128, 128)
    got = flash_attention_pallas_bwd(
        q, k, v, out, lse, tang, causal=causal,
        block_q=128, block_k=128, interpret=True)
    assert got[1].shape == k.shape and got[2].shape == v.shape
    for r, g in zip(ref, got):
        np.testing.assert_allclose(g, r, atol=5e-5, rtol=5e-5)


# ---------------------------------------------------------------------------
# Sliding-window (local) attention
# ---------------------------------------------------------------------------

def _dense_window_reference(q, k, v, window):
    """Materialized softmax with an explicit band mask — independent of the
    naive_attention implementation under test."""
    import numpy as np

    qf = np.asarray(q, np.float32)
    kf = np.asarray(k, np.float32)
    vf = np.asarray(v, np.float32)
    h, hk = qf.shape[2], kf.shape[2]
    if hk != h:
        kf = np.repeat(kf, h // hk, axis=2)
        vf = np.repeat(vf, h // hk, axis=2)
    scores = np.einsum("bqhd,bkhd->bhqk", qf, kf) * qf.shape[-1] ** -0.5
    lq, lk = qf.shape[1], kf.shape[1]
    qpos, kpos = np.arange(lq)[:, None], np.arange(lk)[None, :]
    mask = (qpos >= kpos) & (qpos - kpos < window)
    scores = np.where(mask[None, None], scores, -1e30)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, vf)


def test_sliding_window_fwd_all_impls():
    import numpy as np

    from ray_tpu.ops.attention import flash_attention

    rng = np.random.default_rng(0)
    q = rng.standard_normal((2, 64, 4, 16)).astype(np.float32)
    k = rng.standard_normal((2, 64, 2, 16)).astype(np.float32)
    v = rng.standard_normal((2, 64, 2, 16)).astype(np.float32)
    ref = _dense_window_reference(q, k, v, window=24)
    for impl in ("naive", "xla"):
        out = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                              causal=True, impl=impl, q_block=16,
                              kv_block=16, window=24)
        np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5,
                                   rtol=2e-4, err_msg=impl)


def test_sliding_window_grads_match_naive():
    """The custom-VJP blockwise backward must match autodiff through the
    naive masked softmax."""
    import numpy as np

    from ray_tpu.ops.attention import flash_attention

    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((1, 48, 2, 8)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 48, 2, 8)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 48, 2, 8)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((1, 48, 2, 8)), jnp.float32)

    def loss(impl):
        def f(q, k, v):
            o = flash_attention(q, k, v, causal=True, impl=impl,
                                q_block=16, kv_block=16, window=20)
            return (o * w).sum()

        return jax.value_and_grad(f, argnums=(0, 1, 2))(q, k, v)

    ln, gn = loss("naive")
    lx, gx = loss("xla")
    np.testing.assert_allclose(float(ln), float(lx), rtol=1e-5)
    for a, b in zip(gn, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-5, rtol=3e-4)


def test_sliding_window_requires_causal():
    from ray_tpu.ops.attention import flash_attention

    q = jnp.zeros((1, 16, 2, 8))
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, q, q, causal=False, window=8)


def test_sliding_window_kv_slicing_long_seq():
    """seq >> window: the live-kv-block slicing path (static count,
    dynamic start) must stay exact vs the dense reference, fwd AND bwd."""
    import numpy as np

    from ray_tpu.ops.attention import _n_live_kv_blocks, flash_attention

    # nk=8, n_live=4 -> the slice is active (not the full-scan fallback)
    assert _n_live_kv_blocks(8, 16, 16, 24) == 4

    rng = np.random.default_rng(2)
    q = rng.standard_normal((2, 128, 4, 16)).astype(np.float32)
    k = rng.standard_normal((2, 128, 2, 16)).astype(np.float32)
    v = rng.standard_normal((2, 128, 2, 16)).astype(np.float32)
    ref = _dense_window_reference(q, k, v, window=24)
    out = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          causal=True, impl="xla", q_block=16, kv_block=16,
                          window=24)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=2e-4)

    w = jnp.asarray(rng.standard_normal((2, 128, 4, 16)), jnp.float32)

    def loss(impl):
        def f(qq, kk, vv):
            o = flash_attention(qq, kk, vv, causal=True, impl=impl,
                                q_block=16, kv_block=16, window=24)
            return (o * w).sum()

        return jax.value_and_grad(f, argnums=(0, 1, 2))(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))

    ln, gn = loss("naive")
    lx, gx = loss("xla")
    np.testing.assert_allclose(float(ln), float(lx), rtol=1e-5)
    for a, b in zip(gn, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-4)


def test_sliding_window_pallas_interpret_fwd_bwd():
    """The Pallas kernels' banded liveness predicates + masks (interpret
    mode) must match the dense reference and the blockwise-XLA grads."""
    import numpy as np

    from ray_tpu.ops.attention import flash_attention
    from ray_tpu.ops.flash_pallas import (flash_attention_pallas_bwd,
                                          flash_attention_pallas_fwd)

    rng = np.random.default_rng(3)
    # GQA shapes; seq 256, window 48, blocks 64 -> interior blocks get
    # skipped by the window liveness predicate
    q = jnp.asarray(rng.standard_normal((1, 256, 4, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 256, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 256, 2, 16)), jnp.float32)
    ref = _dense_window_reference(q, k, v, window=48)
    out, lse = flash_attention_pallas_fwd(
        q, k, v, causal=True, block_q=64, block_k=64, window=48,
        interpret=True)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=2e-4)

    # backward: pallas dkv/dq kernels vs the naive-autodiff grads
    dout = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)
    dq, dk, dv = flash_attention_pallas_bwd(
        q, k, v, out, lse, dout, causal=True, block_q=64, block_k=64,
        window=48, interpret=True)

    def f(qq, kk, vv):
        o = flash_attention(qq, kk, vv, causal=True, impl="naive", window=48)
        return (o * dout).sum()

    gn = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    for got, want in zip((dq, dk, dv), gn):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=5e-5, rtol=5e-4)


@pytest.mark.parametrize("window", [24, 40, 64, 120])
def test_sliding_window_sp_halo_matches_single_device(window):
    """Halo-exchange SP sliding-window attention must match the
    single-device windowed reference, fwd AND grads, differentiated
    through shard_map. Lloc = 32, so the windows cover: one hop
    (24 <= Lloc), two hops (40, 64 > Lloc: multi-hop chained ppermutes),
    and the sp-1 clamp (120 spans >= all shards — all-gather shape,
    band mask still exact)."""
    import numpy as np
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from ray_tpu.ops.attention import flash_attention
    from ray_tpu.ops.ring_attention import sliding_window_attention_sp
    from ray_tpu.parallel import MeshConfig, make_mesh

    mesh = make_mesh(MeshConfig(dp=1, fsdp=2, tp=1, sp=4))
    rng = np.random.default_rng(4)
    # global seq 128 over sp=4 -> Lloc 32
    q = jnp.asarray(rng.standard_normal((2, 128, 4, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 128, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 128, 2, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((2, 128, 4, 16)), jnp.float32)

    def ref_loss(q, k, v):
        o = flash_attention(q, k, v, causal=True, impl="naive",
                            window=window)
        return (o * w).sum()

    ln, gn = jax.value_and_grad(ref_loss, argnums=(0, 1, 2))(q, k, v)

    spec = P(None, "sp", None, None)
    with jax.set_mesh(mesh):
        fn = shard_map(
            lambda q, k, v: sliding_window_attention_sp(
                q, k, v, axis="sp", window=window, q_block=16,
                kv_block=16),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)

        def sp_loss(q, k, v):
            return (fn(q, k, v) * w).sum()

        ls, gs = jax.jit(jax.value_and_grad(
            sp_loss, argnums=(0, 1, 2)))(q, k, v)
    np.testing.assert_allclose(float(ln), float(ls), rtol=1e-4)
    for a, b in zip(gn, gs):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-3)


# ---------------------------------------------------------------------------
# Attention-logit soft-capping (Gemma-2)
# ---------------------------------------------------------------------------

def test_softcap_fwd_bwd_all_impls_match_naive():
    """cap*tanh(s/cap) logits: value AND grads must agree across naive,
    blockwise-XLA custom VJP, and the Pallas kernels (interpret mode),
    with and without a sliding window."""
    import numpy as np

    from ray_tpu.ops.attention import _mha, naive_attention
    from ray_tpu.ops.flash_pallas import (flash_attention_pallas_bwd,
                                          flash_attention_pallas_fwd)

    rng = np.random.default_rng(2)
    B, S, HQ, HKV, D = 1, 64, 4, 2, 16
    q = jnp.asarray(rng.standard_normal((B, S, HQ, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, HKV, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, HKV, D)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((B, S, HQ, D)), jnp.float32)
    cap = 5.0  # small: scores genuinely bend

    for window in (None, 24):
        def loss_naive(q, k, v):
            o = naive_attention(q, k, v, causal=True, window=window,
                                softcap=cap)
            return (o * w).sum()

        def loss_xla(q, k, v):
            o = _mha(q, k, v, True, D ** -0.5, 16, 16, False, window, cap)
            return (o * w).sum()

        vn, gn = jax.value_and_grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
        vx, gx = jax.value_and_grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(vx, vn, rtol=1e-4)
        for a, b in zip(gx, gn):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-3)

        o_p, lse = flash_attention_pallas_fwd(
            q, k, v, causal=True, block_q=16, block_k=16, window=window,
            softcap=cap, interpret=True)
        o_n = naive_attention(q, k, v, causal=True, window=window,
                              softcap=cap)
        np.testing.assert_allclose(np.asarray(o_p), np.asarray(o_n),
                                   atol=1e-4, rtol=1e-3)
        dq, dk, dv = flash_attention_pallas_bwd(
            q, k, v, o_p, lse, w, causal=True, block_q=16, block_k=16,
            window=window, softcap=cap, interpret=True)
        for a, b in zip((dq, dk, dv), gn):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-3)


def test_softcap_changes_output():
    import numpy as np

    from ray_tpu.ops.attention import naive_attention

    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((1, 32, 2, 8)), jnp.float32)
    o1 = naive_attention(q, q, q, causal=True, softcap=5.0)
    o0 = naive_attention(q, q, q, causal=True)
    assert float(jnp.abs(o1 - o0).max()) > 1e-4
