"""Model family: shapes, loss, decode==forward consistency, sharded training."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu import models
from ray_tpu.models import (
    TransformerConfig, init_params, param_axes, forward, loss_and_metrics,
    init_cache, decode_step, generate,
)
from ray_tpu.parallel import MeshConfig, make_mesh, shard_params


CONFIGS = {
    "llama": models.llama_debug(),
    "gpt2": models.gpt2_debug(),
    "gemma": models.gemma_debug(),
    "qwen2": models.qwen2_debug(),
    "moe": models.moe_debug(),
}


@pytest.mark.parametrize("name", list(CONFIGS))
def test_forward_shapes(name):
    c = CONFIGS[name]
    params = init_params(jax.random.PRNGKey(0), c)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, c.vocab_size)
    logits, aux = forward(params, toks, c)
    assert logits.shape == (2, 16, c.vocab_size)
    assert logits.dtype == jnp.float32
    assert np.isfinite(np.asarray(logits)).all()


@pytest.mark.parametrize("name", list(CONFIGS))
def test_param_axes_match_params(name):
    c = CONFIGS[name]
    params = init_params(jax.random.PRNGKey(0), c)
    axes = param_axes(c)
    flat_p = jax.tree.leaves(params)
    flat_a = jax.tree.leaves(axes, is_leaf=lambda x: isinstance(x, tuple))
    assert len(flat_p) == len(flat_a)
    # Every axes tuple must have one entry per array dim.
    def check(p, a):
        assert len(a) == p.ndim, f"{a} vs {p.shape}"
    jax.tree.map(check, params, axes,
                 is_leaf=lambda x: isinstance(x, tuple) and all(
                     e is None or isinstance(e, str) for e in x))


@pytest.mark.parametrize("name", list(CONFIGS))
def test_num_params_formula_matches(name):
    c = CONFIGS[name]
    params = init_params(jax.random.PRNGKey(0), c)
    actual = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    assert actual == c.num_params()


def test_loss_decreases_under_sgd():
    c = models.llama_debug()
    params = init_params(jax.random.PRNGKey(0), c)
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, c.vocab_size)
    batch = {"tokens": toks}

    @jax.jit
    def step(params):
        (loss, m), grads = jax.value_and_grad(
            lambda p: loss_and_metrics(p, batch, c), has_aux=True)(params)
        params = jax.tree.map(lambda p, g: p - 0.1 * g.astype(p.dtype),
                              params, grads)
        return params, loss

    params, l0 = step(params)
    for _ in range(5):
        params, loss = step(params)
    assert float(loss) < float(l0)


def test_decode_matches_forward():
    c = models.llama_debug()
    params = init_params(jax.random.PRNGKey(0), c)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, c.vocab_size)
    full, _ = forward(params, toks, c)

    # prefill 8, then decode 4 one at a time
    cache = init_cache(c, 2, 16)
    lp, cache = decode_step(params, cache, toks[:, :8], c)
    np.testing.assert_allclose(np.asarray(lp), np.asarray(full[:, :8]),
                               atol=2e-2, rtol=2e-2)
    outs = [lp[:, -1:]]
    for i in range(8, 12):
        li, cache = decode_step(params, cache, toks[:, i:i + 1], c)
        outs.append(li)
    dec = jnp.concatenate(outs[1:], axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full[:, 8:12]),
                               atol=2e-2, rtol=2e-2)


def test_generate_greedy_deterministic():
    c = models.gpt2_debug()
    params = init_params(jax.random.PRNGKey(0), c)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 4), 0, c.vocab_size)
    out1 = generate(params, prompt, c, max_new_tokens=6)
    out2 = generate(params, prompt, c, max_new_tokens=6)
    assert out1.shape == (1, 10)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    np.testing.assert_array_equal(np.asarray(out1[:, :4]), np.asarray(prompt))


def test_sharded_train_step_tp_fsdp():
    """Full train step jitted over a dp×fsdp×tp mesh with sharded params."""
    c = models.llama_debug()
    mesh = make_mesh(MeshConfig(dp=2, fsdp=2, tp=2, sp=1))
    params = init_params(jax.random.PRNGKey(0), c)
    axes = param_axes(c)
    params = shard_params(params, axes, mesh)
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, c.vocab_size)
    batch = {"tokens": toks}

    with jax.set_mesh(mesh):
        @jax.jit
        def step(params):
            (loss, m), grads = jax.value_and_grad(
                lambda p: loss_and_metrics(p, batch, c), has_aux=True)(params)
            return jax.tree.map(
                lambda p, g: p - 0.1 * g.astype(p.dtype), params, grads), loss

        new_params, loss = step(params)
    assert np.isfinite(float(loss))
    # Param shardings preserved through the step (trailing-None spec forms
    # compare unequal, so check equivalence).
    wq_new, wq_old = new_params["layers"]["wq"], params["layers"]["wq"]
    assert wq_new.sharding.is_equivalent_to(wq_old.sharding, wq_old.ndim)


def test_sharded_train_step_ring_attention_sp():
    """sp>1 routes attention through ring attention inside the jitted step."""
    c = models.llama_debug()
    mesh = make_mesh(MeshConfig(dp=1, fsdp=2, tp=1, sp=4))
    params = init_params(jax.random.PRNGKey(0), c)
    params_sharded = shard_params(params, param_axes(c), mesh)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 65), 0, c.vocab_size)
    # Explicit inputs/targets keep the model seq len at 64 (divisible by sp).
    batch = {"inputs": toks[:, :-1], "targets": toks[:, 1:]}

    ref_loss, _ = loss_and_metrics(params, batch, c)  # no mesh: flash path

    with jax.set_mesh(mesh):
        @jax.jit
        def step(params):
            loss, m = loss_and_metrics(params, batch, c)
            return loss

        sp_loss = step(params_sharded)
    np.testing.assert_allclose(float(sp_loss), float(ref_loss), atol=2e-2, rtol=2e-2)


def test_remat_policies_grad_equivalent():
    """save_attn remat must produce the same loss AND grads as full remat
    (it only changes what backward recomputes); unknown policies fail loudly."""
    base = models.llama_debug()
    toks = np.asarray(
        np.random.default_rng(0).integers(0, base.vocab_size, (2, 33)),
        dtype=np.int32)
    batch = {"tokens": toks}

    def grads_for(policy):
        c = base.replace(remat=True, remat_policy=policy)
        params = init_params(jax.random.PRNGKey(0), c)
        return jax.jit(jax.value_and_grad(
            lambda p: loss_and_metrics(p, batch, c)[0]))(params)

    loss_full, g_full = grads_for("full")
    loss_attn, g_attn = grads_for("save_attn")
    np.testing.assert_allclose(float(loss_full), float(loss_attn), rtol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4),
        g_full, g_attn)

    with pytest.raises(ValueError, match="remat_policy"):
        base.replace(remat_policy="save-attention")


def test_chunked_xent_matches_dense():
    """loss_chunk must not change the loss (exact) or grads (beyond bf16
    accumulation-order noise) — it only changes what backward keeps live."""
    base = models.llama_debug().replace(z_loss=1e-4, logits_softcap=30.0)
    toks = np.asarray(np.random.default_rng(0).integers(
        0, base.vocab_size, (2, 65)), dtype=np.int32)
    batch = {"inputs": toks[:, :-1], "targets": toks[:, 1:]}

    def loss_grads(cfg):
        params = init_params(jax.random.PRNGKey(0), cfg)
        return jax.jit(jax.value_and_grad(
            lambda p: loss_and_metrics(p, batch, cfg)[0]))(params)

    l_dense, g_dense = loss_grads(base)
    l_chunk, g_chunk = loss_grads(base.replace(loss_chunk=16))
    np.testing.assert_allclose(float(l_dense), float(l_chunk), rtol=1e-5)

    def close(a, b):
        a, b = np.asarray(a, "float32"), np.asarray(b, "float32")
        denom = max(1e-3, float(abs(b).max()))
        assert abs(a - b).max() / denom < 5e-3

    jax.tree.map(close, g_dense, g_chunk)


def test_chunked_xent_pads_non_divisible_seq():
    """L not divisible by loss_chunk pads with mask-0 — never a silent
    dense fallback."""
    base = models.llama_debug()
    toks = np.asarray(np.random.default_rng(1).integers(
        0, base.vocab_size, (2, 65)), dtype=np.int32)
    batch = {"inputs": toks[:, :-1], "targets": toks[:, 1:]}
    params = init_params(jax.random.PRNGKey(0), base)
    l_dense = float(loss_and_metrics(params, batch, base)[0])
    l_pad = float(loss_and_metrics(
        params, batch, base.replace(loss_chunk=24))[0])
    np.testing.assert_allclose(l_dense, l_pad, rtol=1e-5)


def test_mistral_sliding_window_trains_and_decodes():
    """sliding_window threads through train (blockwise VJP path) and the
    KV-cache decode: decode logits must match the full-sequence forward."""
    c = models.mistral_debug()
    assert c.sliding_window == 24
    params = init_params(jax.random.PRNGKey(0), c)
    toks = np.asarray(np.random.default_rng(0).integers(
        0, c.vocab_size, (2, 65)), dtype=np.int32)
    batch = {"inputs": toks[:, :-1], "targets": toks[:, 1:]}
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: loss_and_metrics(p, batch, c)[0]))(params)
    assert np.isfinite(float(loss))
    assert np.isfinite(float(optax_global_norm(grads)))

    # decode parity: windowed prefill+decode equals windowed full forward.
    # The cache is auto-RING (24 slots for window 24), so the 48-token
    # prompt prefills in two window-sized chunks.
    from ray_tpu.models.transformer import decode_step, forward, init_cache

    prompt = toks[:1, :48]
    logits_full, _ = forward(params, prompt, c)
    cache = init_cache(c, 1, 64)
    assert cache["k"].shape[2] == c.sliding_window
    logits_dec = None
    for i in range(0, 48, 24):
        logits_dec, cache = decode_step(params, cache, prompt[:, i:i + 24], c)
    np.testing.assert_allclose(
        np.asarray(logits_dec[:, -1], np.float32),
        np.asarray(logits_full[:, -1], np.float32), atol=2e-2, rtol=2e-2)


def optax_global_norm(tree):
    import optax

    return optax.global_norm(tree)


def test_rolling_kv_cache_matches_full_cache():
    """Sliding-window ring cache (O(window) HBM) must produce the same
    logits as the full-length cache at every decode step, including far
    past the window."""
    from ray_tpu.models.transformer import decode_step, init_cache

    c = models.mistral_debug()  # window 24
    params = init_params(jax.random.PRNGKey(0), c)
    prompt = jnp.asarray(np.random.default_rng(0).integers(
        0, c.vocab_size, (2, 16)), jnp.int32)

    full = init_cache(c, 2, 64, rolling=False)
    ring = init_cache(c, 2, 64)
    assert full["k"].shape[2] == 64 and ring["k"].shape[2] == 24

    lf, full = decode_step(params, full, prompt, c)
    lr, ring = decode_step(params, ring, prompt, c)
    np.testing.assert_allclose(np.asarray(lf, np.float32),
                               np.asarray(lr, np.float32),
                               atol=1e-3, rtol=1e-2)
    step_full = jax.jit(lambda cc, t: decode_step(params, cc, t, c))
    step_ring = jax.jit(lambda cc, t: decode_step(params, cc, t, c))
    tok = jnp.argmax(lf[:, -1], -1).astype(jnp.int32)[:, None]
    for i in range(40):
        lf, full = step_full(full, tok)
        lr, ring = step_ring(ring, tok)
        np.testing.assert_allclose(np.asarray(lf, np.float32),
                                   np.asarray(lr, np.float32),
                                   atol=1e-3, rtol=1e-2, err_msg=f"step {i}")
        tok = jnp.argmax(lf[:, -1], -1).astype(jnp.int32)[:, None]

    # a prefill chunk larger than the ring is rejected loudly
    import pytest as _pytest

    big = jnp.zeros((2, 30), jnp.int32)
    with _pytest.raises(ValueError, match="ring cache"):
        decode_step(params, init_cache(c, 2, 64), big, c)


def test_generate_ring_prefill_long_prompt():
    """generate() keeps the O(window) ring even for prompts beyond the
    window (chunked prefill) and matches full-cache greedy decoding."""
    from ray_tpu.models.transformer import decode_step, generate, init_cache

    c = models.mistral_debug()  # window 24
    params = init_params(jax.random.PRNGKey(0), c)
    prompt = jnp.asarray(np.random.default_rng(0).integers(
        0, c.vocab_size, (1, 40)), jnp.int32)
    out_ring = generate(params, prompt, c, max_new_tokens=6)

    cache = init_cache(c, 1, 64, rolling=False)
    logits, cache = decode_step(params, cache, prompt, c)
    toks = [int(jnp.argmax(logits[0, -1], -1))]
    for _ in range(5):
        nxt = jnp.asarray([[toks[-1]]], jnp.int32)
        logits, cache = decode_step(params, cache, nxt, c)
        toks.append(int(jnp.argmax(logits[0, -1], -1)))
    assert list(np.asarray(out_ring)[0, 40:]) == toks


def test_mistral_sp_halo_train_step():
    """Windowed model under an sp mesh routes through the halo-exchange
    path and matches the single-device loss."""
    c = models.mistral_debug()  # window 24
    mesh = make_mesh(MeshConfig(dp=1, fsdp=-1, tp=2, sp=2))
    params = init_params(jax.random.PRNGKey(0), c)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 65), 0,
                              c.vocab_size)
    batch = {"inputs": toks[:, :-1], "targets": toks[:, 1:]}  # seq 64, Lloc 32
    ref_loss, _ = loss_and_metrics(params, batch, c)

    params_sharded = shard_params(params, param_axes(c), mesh)
    with jax.set_mesh(mesh):
        sp_loss = jax.jit(
            lambda p: loss_and_metrics(p, batch, c)[0])(params_sharded)
    np.testing.assert_allclose(float(sp_loss), float(ref_loss), atol=2e-2,
                               rtol=2e-2)

    # window > Lloc: the multi-hop halo (r5) handles it exactly
    big = c.replace(sliding_window=48)  # Lloc 32 < 48 -> 2 hops
    ref_big, _ = loss_and_metrics(params, batch, big)
    with jax.set_mesh(mesh):
        sp_big = jax.jit(
            lambda p: loss_and_metrics(p, batch, big)[0])(params_sharded)
    np.testing.assert_allclose(float(sp_big), float(ref_big), atol=2e-2,
                               rtol=2e-2)


def test_gemma2_alternating_windows_exact():
    """Per-layer alternating windows (Gemma-2 layer_types): the grouped
    layer scan must equal a hand-rolled per-layer naive-attention forward
    with each layer's own window AND the attention softcap."""
    import numpy as np

    from ray_tpu import models
    from ray_tpu.models import transformer as T
    from ray_tpu.ops.attention import naive_attention

    cfg = models.gemma_debug()
    assert cfg.window_pattern == (24, 0)
    assert cfg.uniform_window == 0      # mixed -> no ring cache
    assert cfg.layer_windows == (24, 0)
    params = models.init_params(jax.random.PRNGKey(0), cfg)
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 64), np.int32))

    def ref_forward(params, tokens, c):
        dt = jnp.dtype(c.dtype)
        x = params["embed"].astype(dt)[tokens]
        cos, sin = T.rotary_embedding(jnp.arange(tokens.shape[1]), c.hdim,
                                      theta=c.rope_theta)
        for li in range(c.n_layers):
            lp = jax.tree.map(lambda a: a[li], params["layers"])
            h = T._norm(x, lp["attn_norm"], lp.get("attn_norm_b"), c)
            q = jnp.einsum("bld,dhk->blhk", h, lp["wq"].astype(dt))
            k = jnp.einsum("bld,dhk->blhk", h, lp["wk"].astype(dt))
            v = jnp.einsum("bld,dhk->blhk", h, lp["wv"].astype(dt))
            q = T.apply_rotary(q, cos, sin)
            k = T.apply_rotary(k, cos, sin)
            o = naive_attention(q, k, v, causal=True,
                                window=c.layer_windows[li] or None,
                                softcap=c.attn_softcap)
            x = x + jnp.einsum("blhk,hkd->bld", o, lp["wo"].astype(dt))
            h = T._norm(x, lp["mlp_norm"], lp.get("mlp_norm_b"), c)
            g = jax.nn.silu(jnp.einsum("bld,df->blf", h,
                                       lp["w_gate"].astype(dt)))
            u = jnp.einsum("bld,df->blf", h, lp["w_up"].astype(dt))
            x = x + jnp.einsum("blf,fd->bld", g * u,
                               lp["w_down"].astype(dt))
        x = T._norm(x, params["final_norm"], params.get("final_norm_b"),
                    c)
        logits = jnp.einsum("bld,dv->blv", x,
                            params["embed"].T.astype(dt)).astype(jnp.float32)
        return jnp.tanh(logits / c.logits_softcap) * c.logits_softcap

    got, _ = T.forward(params, toks, cfg)
    want = ref_forward(params, toks, cfg)
    assert float(jnp.abs(got - want).max()) < 2e-2  # bf16 activations

    # the alternation is load-bearing: a uniform-window twin differs
    uni, _ = T.forward(params, toks, cfg.replace(attn_windows=(24, 24)))
    assert float(jnp.abs(got - uni).max()) > 1e-3


def test_gemma2_decode_matches_forward():
    """Mixed-window decode (full cache + per-layer traced windows) must
    reproduce the training forward position by position."""
    import numpy as np

    from ray_tpu import models
    from ray_tpu.models import transformer as T

    cfg = models.gemma_debug()
    params = models.init_params(jax.random.PRNGKey(1), cfg)
    toks = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab_size, (2, 56), np.int32))
    full, _ = T.forward(params, toks, cfg)
    cache = T.init_cache(cfg, 2, 56)
    assert cache["k"].shape[2] == 56  # mixed windows force full layout
    logits, cache = T.decode_step(params, cache, toks[:, :40], cfg)
    assert float(jnp.abs(logits - full[:, :40]).max()) < 2e-2
    for i in range(40, 44):
        lg, cache = T.decode_step(params, cache, toks[:, i:i + 1], cfg)
        assert float(jnp.abs(lg[:, 0] - full[:, i]).max()) < 2e-2


def test_attn_windows_config_validation():
    import pytest

    from ray_tpu import models

    with pytest.raises(ValueError, match="not divisible"):
        models.gemma_debug().replace(attn_windows=(24, 0, 0))
    with pytest.raises(ValueError, match="ints >= 0"):
        models.gemma_debug().replace(attn_windows=(24, -1))
    with pytest.raises(NotImplementedError, match="pipeline"):
        # per-layer windows + pp>1 is an explicit design limit
        import numpy as np

        from ray_tpu.models import transformer as T
        from ray_tpu.parallel import MeshConfig, make_mesh

        cfg = models.gemma_debug()
        params = models.init_params(jax.random.PRNGKey(0), cfg)
        mesh = make_mesh(MeshConfig(dp=1, fsdp=1, tp=1, sp=1, pp=8))
        toks = jnp.zeros((2, 32), jnp.int32)
        with jax.set_mesh(mesh):
            T.forward(params, toks, cfg)


@pytest.mark.parametrize("name,budget", [
    ("llama-debug", None), ("gemma-debug", None),
    # under a budget of 6 of the step's 16 positions: the prefill steps
    # (4 + 4 + 4 real positions) take the full width, a decode step (3)
    # the budget, a step of 4 + 1 + 1 fills it to the last place
    ("llama-debug", 6), ("gemma-debug", 6), ("qwen2-debug", 6),
    ("gpt2-debug", 6), ("gpt2-debug", None)])
def test_paged_step_matches_scalar_decode(name, budget):
    """The paged serving step must be token-exact vs per-sequence scalar
    decode_step (the offline reference): staggered prompt lengths in one
    batch, prompts fed in chunks so a prefilling row and a decoding row
    share a step, parked rows that must touch nothing, and the gemma-2
    alternating-window + softcap config (its window cut to 8 so that it
    clips). With a ``budget`` the step computes its real positions,
    gathered to the front (biases, learned positions and the GELU MLP among
    them)."""
    import numpy as np

    from ray_tpu import models
    from ray_tpu.models import transformer as T

    # float32: bf16 debug weights give exact top-2 logit ties that a
    # 1-ULP difference between the two attention forms flips
    cfg = models.get_config(name).replace(dtype="float32")
    if cfg.attn_windows:
        cfg = cfg.replace(attn_windows=(8, 0))
    decode_step = jax.jit(T.decode_step, static_argnums=3)
    step_paged = jax.jit(functools.partial(T.decode_step_paged,
                                           budget=budget), static_argnums=6)
    params = models.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    n_new, cache_len = 6, 24
    prompts = [rng.integers(0, cfg.vocab_size, p).astype(np.int32)
               for p in (5, 9, 13)]
    refs = []
    for pr in prompts:
        c1 = T.init_cache(cfg, 1, cache_len, rolling=False)
        lg, c1 = decode_step(params, c1, jnp.asarray(pr[None]), cfg)
        toks = [int(jnp.argmax(lg[0, -1]))]
        for _ in range(n_new - 1):
            lg, c1 = decode_step(
                params, c1, jnp.asarray([[toks[-1]]], dtype=jnp.int32),
                cfg)
            toks.append(int(jnp.argmax(lg[0, -1])))
        refs.append(toks)

    # one table a row; row 3 is parked for the whole run over row 0's
    # blocks (a write of its own would corrupt row 0), and the pool's
    # last block belongs to no table
    bs, chunk, n_rows = 4, 4, 4
    width = cache_len // bs
    cache = T.init_cache_paged(cfg, 3 * width + 1, bs)
    tables = np.arange(3 * width, dtype=np.int32).reshape(3, width)
    tables = np.concatenate([tables, tables[:1]])
    pos = np.zeros(n_rows, np.int32)
    outs = [[] for _ in prompts]
    shared = False
    while any(len(o) < n_new for o in outs):
        tokens = np.zeros((n_rows, chunk), np.int32)
        nvalid = np.zeros(n_rows, np.int32)
        for i, pr in enumerate(prompts):
            if pos[i] < len(pr):
                feed = pr[pos[i]:pos[i] + chunk]
            elif len(outs[i]) < n_new:
                feed = outs[i][-1:]
            else:
                continue    # finished: parked like row 3
            tokens[i, :len(feed)] = feed
            nvalid[i] = len(feed)
        shared |= bool((nvalid > 1).any()) and any(
            nvalid[i] and pos[i] >= len(pr) for i, pr in enumerate(prompts))
        logits, cache = step_paged(
            params, cache, jnp.asarray(tokens), jnp.asarray(tables),
            jnp.asarray(pos), jnp.asarray(nvalid), cfg,
            jnp.asarray(nvalid > 0))
        pos = pos + nvalid  # a new array: the step may alias the old one
        for i, pr in enumerate(prompts):
            if nvalid[i] and pos[i] >= len(pr):
                outs[i].append(int(np.argmax(np.asarray(logits[i]))))
    assert shared, "no step held a prefilling row beside a decoding one"
    assert outs == refs, (name, outs, refs)
    for pool in cache.values():
        assert not np.asarray(pool[:, -1]).any(), "an unowned block changed"


def test_hf_llama_import_logits_parity():
    """import_hf_llama: logits must match transformers' LlamaForCausalLM
    exactly (same f32 math, same RoPE convention, same GQA mapping) on a
    randomly initialized tiny model."""
    import torch
    from transformers import LlamaConfig, LlamaForCausalLM

    from ray_tpu.models import forward
    from ray_tpu.models.import_hf import config_from_hf, import_hf_llama

    hf_cfg = LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, max_position_embeddings=64,
        rope_theta=10000.0, rms_norm_eps=1e-6, tie_word_embeddings=False,
        attention_bias=False, mlp_bias=False)
    torch.manual_seed(0)
    hf = LlamaForCausalLM(hf_cfg).eval()

    cfg = config_from_hf(hf_cfg)
    params = import_hf_llama(hf.state_dict(), cfg)

    tokens = np.asarray([[3, 17, 99, 5, 64, 2, 120, 7]], np.int32)
    with torch.no_grad():
        ref = hf(torch.from_numpy(tokens).long()).logits.numpy()
    ours, _ = forward(params, jnp.asarray(tokens), cfg)
    np.testing.assert_allclose(np.asarray(ours), ref, atol=2e-4,
                               rtol=2e-3)


def test_hf_llama_import_generate_parity():
    """Greedy decode with imported weights must produce the same token
    ids as transformers' generate — proves the KV-cache decode path on
    real(istic) weights, not just the teacher-forced forward."""
    import torch
    from transformers import LlamaConfig, LlamaForCausalLM

    from ray_tpu.models import generate
    from ray_tpu.models.import_hf import config_from_hf, import_hf_llama

    hf_cfg = LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, max_position_embeddings=64,
        rope_theta=10000.0, rms_norm_eps=1e-6, tie_word_embeddings=True)
    torch.manual_seed(1)
    hf = LlamaForCausalLM(hf_cfg).eval()
    cfg = config_from_hf(hf_cfg)
    params = import_hf_llama(hf.state_dict(), cfg)

    prompt = np.asarray([[5, 99, 23, 42]], np.int32)
    with torch.no_grad():
        ref = hf.generate(torch.from_numpy(prompt).long(),
                          max_new_tokens=8, do_sample=False,
                          eos_token_id=None).numpy()
    ours = np.asarray(generate(params, jnp.asarray(prompt), cfg,
                               max_new_tokens=8))
    np.testing.assert_array_equal(ours, ref)


def test_hf_import_rejects_unmapped_tensors_and_rope_scaling():
    """Strictness: unconsumed state-dict tensors (a bias the mapping
    does not model, standing in for Qwen3 q/k norms etc.) and
    rope_scaling configs must fail loudly, never import silently
    wrong."""
    import torch
    from transformers import LlamaConfig, LlamaForCausalLM

    from ray_tpu.models.import_hf import config_from_hf, import_hf_llama

    hf_cfg = LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=48,
        num_hidden_layers=1, num_attention_heads=2,
        num_key_value_heads=2, max_position_embeddings=32,
        rms_norm_eps=1e-5)
    hf = LlamaForCausalLM(hf_cfg)
    cfg = config_from_hf(hf_cfg)
    assert cfg.norm_eps == 1e-5

    sd = dict(hf.state_dict())
    sd["model.layers.0.self_attn.q_proj.bias"] = torch.zeros(32)
    with pytest.raises(ValueError, match="does not consume"):
        import_hf_llama(sd, cfg)

    hf_cfg.rope_scaling = {"rope_type": "llama3", "factor": 8.0}
    with pytest.raises(ValueError, match="rope_scaling"):
        config_from_hf(hf_cfg)


def test_hf_qwen2_import_logits_parity():
    """Qwen2 (q/k/v biases) imports with exact logits parity — the
    attn_qkv_bias path end to end."""
    import torch
    from transformers import Qwen2Config, Qwen2ForCausalLM

    from ray_tpu.models import forward
    from ray_tpu.models.import_hf import config_from_hf, import_hf_llama

    hf_cfg = Qwen2Config(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, max_position_embeddings=64,
        rope_theta=10000.0, rms_norm_eps=1e-6, tie_word_embeddings=False,
        use_sliding_window=False)
    torch.manual_seed(2)
    hf = Qwen2ForCausalLM(hf_cfg).eval()
    # random biases (zeros would not exercise the path)
    with torch.no_grad():
        for layer in hf.model.layers:
            for proj in (layer.self_attn.q_proj, layer.self_attn.k_proj,
                         layer.self_attn.v_proj):
                proj.bias.normal_(0, 0.5)

    cfg = config_from_hf(hf_cfg)
    assert cfg.attn_qkv_bias
    params = import_hf_llama(hf.state_dict(), cfg)

    tokens = np.asarray([[3, 17, 99, 5, 64, 2, 120, 7]], np.int32)
    with torch.no_grad():
        ref = hf(torch.from_numpy(tokens).long()).logits.numpy()
    ours, _ = forward(params, jnp.asarray(tokens), cfg)
    np.testing.assert_allclose(np.asarray(ours), ref, atol=2e-4,
                               rtol=2e-3)


def test_hf_qwen2_swa_layer_mapping():
    """Qwen2 use_sliding_window: HF runs FULL attention on the first
    max_window_layers layers and SWA after — config_from_hf must map
    that to an explicit per-layer attn_windows tuple, and ignore
    sliding_window entirely when use_sliding_window is off."""
    from transformers import Qwen2Config

    from ray_tpu.models.import_hf import config_from_hf

    cfg = config_from_hf(Qwen2Config(
        num_hidden_layers=4, sliding_window=1024,
        use_sliding_window=True, max_window_layers=2))
    assert cfg.attn_windows == (0, 0, 1024, 1024)
    assert cfg.sliding_window == 0

    cfg = config_from_hf(Qwen2Config(
        num_hidden_layers=4, sliding_window=1024,
        use_sliding_window=False))
    assert cfg.attn_windows is None and cfg.sliding_window == 0

    # explicit layer_types wins over the max_window_layers prefix rule,
    # and periodic patterns reduce to their minimal repeat
    hf = Qwen2Config(num_hidden_layers=4, sliding_window=1024,
                     use_sliding_window=True, max_window_layers=0)
    hf.layer_types = ["sliding_attention", "full_attention"] * 2
    cfg = config_from_hf(hf)
    assert cfg.attn_windows == (1024, 0)
    assert cfg.layer_windows == (1024, 0, 1024, 0)

    # all-sliding uniform pattern reduces to one entry
    hf.layer_types = ["sliding_attention"] * 4
    cfg = config_from_hf(hf)
    assert cfg.attn_windows == (1024,)
    assert cfg.uniform_window == 1024

    # unknown attention kinds and mis-sized lists refuse loudly
    hf.layer_types = ["chunked_attention"] * 4
    with pytest.raises(ValueError, match="layer_types"):
        config_from_hf(hf)
    hf.layer_types = ["sliding_attention", "full_attention"]  # 2 != 4
    with pytest.raises(ValueError, match="layer_types"):
        config_from_hf(hf)
