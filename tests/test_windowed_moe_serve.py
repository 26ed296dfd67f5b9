"""The windowed MoE decoder family (the Trinity layer:
``TransformerConfig.windowed_moe``) on the serve path, at a small size on the
CPU (five layers whose windows are 8, 8, 8, full, 8; gated GQA attention with
q/k-norm and four norms a layer; a leading dense layer, then 4 of 16
sigmoid-routed experts held beside a shared one): the paged step and the
engine against the benchmark's plain reference
(``benchmark/reference/windowed_moe_decoder.py``: one float32 pass over the
whole sequence, no cache, no table), the window pool whose blocks are
released behind a row's window, admission by that pool's reservation, the
eight shares of an expert layer against the uncut layer, and everything that
ships a request refusing this layout."""

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import assert_three_widths, watch_step_widths

from benchmark import manifest
from ray_tpu import models
from ray_tpu.models import transformer
from ray_tpu.models.config import TransformerConfig
from ray_tpu.models.import_hf import config_from_hf
from ray_tpu.ops.paged_attention import paged_attention
from ray_tpu.serve.llm import LLMEngine
from ray_tpu.util import tracing

REF_LEN = 128
WINDOW = 8
#: float32 on both sides: what is left is the order of the sums (a paged
#: gather through two tables against one pass); the toy reads 1e-6
TOL = 1e-4
#: bfloat16 weights, activations and KV pools against the float32
#: reference: the toy reads about 0.02; a piece left out reads 0.1 and more
TOL_BF16 = 0.06


@pytest.fixture(scope="module")
def reference():
    return manifest.load_module(
        manifest.reference_path("windowed_moe_decoder"))


@pytest.fixture(scope="module")
def config():
    return models.get_config("windowed-moe-debug").replace(
        dtype="float32", param_dtype="float32")


@pytest.fixture(scope="module")
def params(config):
    return models.init_params(jax.random.PRNGKey(0), config)


def _config_file(c, **over):
    """The published keys the reference reads, from a ``TransformerConfig``."""
    kinds = ["sliding_attention" if w else "full_attention"
             for w in c.layer_windows]
    return {"rms_norm_eps": c.norm_eps, "rope_theta": c.rope_theta,
            "num_attention_heads": c.n_heads,
            "num_key_value_heads": c.kv_heads, "head_dim": c.hdim,
            "hidden_size": c.d_model, "num_hidden_layers": c.n_layers,
            "num_dense_layers": c.dense_layers, "layer_types": kinds,
            "sliding_window": c.sliding_window,
            "num_experts_per_tok": c.expert_top_k,
            "route_norm": c.expert_norm_topk, "score_func": c.expert_scoring,
            "route_scale": c.expert_scale, "num_shared_experts": 1,
            "n_group": 1, "topk_group": 1, "num_expert_groups": 1,
            "num_limited_groups": 1, "rope_scaling": None,
            "hidden_act": "silu", "mup_enabled": True,
            "reduced": {"num_experts": {"first": c.experts_first}}, **over}


def _reference_logits(reference, params, config, seq, rows, **kw):
    padded = np.zeros(REF_LEN, np.int32)
    padded[:len(seq)] = seq
    return np.asarray(reference.logits_at(params, padded, rows,
                                          _config_file(config), **kw))


def _rel(got, want):
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def _engine(config, params, **kw):
    kw = {"max_slots": 4, "max_len": 128, "block_size": 4,
          "prefill_chunk": 8, **kw}
    return LLMEngine(config, params, **kw)


def _serve_all(eng, requests, on_step=None):
    """Serve (prompt, n) pairs together to their end; returns per request
    (tokens, logits per token)."""
    outs, sample = [], eng._sample
    order = []

    def capture(row):
        order.append(row.copy())
        return sample(row)

    eng._sample, eng.capture = capture, True
    try:
        for prompt, n in requests:
            toks, logits = [], []
            outs.append((toks, logits))

            def emit(item, toks=toks, logits=logits):
                if isinstance(item, int):
                    toks.append(item)
                    logits.append(order[-1])

            eng.submit(prompt, n, emit)
        while eng.step():
            if on_step:
                on_step(eng)
    finally:
        eng._sample, eng.capture = sample, False
    return [(t, np.stack(l)) for t, l in outs]


def _serve(eng, prompt, n, **kw):
    return _serve_all(eng, [(prompt, n)], **kw)[0]


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(0, 256, n).tolist()


def _against_reference(reference, params, config, prompt, toks, logits,
                       **kw):
    seq = prompt + toks[:-1]
    want = _reference_logits(reference, params, config, seq,
                             np.arange(len(prompt) - 1, len(seq)), **kw)
    return _rel(logits, want)


# -- the layout ---------------------------------------------------------------

def test_the_layers_run_in_runs_by_segment_and_pool_kind(config):
    runs = transformer._layer_runs(config)
    assert [(r.segment, r.start, r.layers, r.windowed, r.pool_first, r.rope)
            for r in runs] == [
        ("dense", 0, 1, True, 0, True), ("moe", 0, 2, True, 1, True),
        ("moe", 2, 1, False, 0, False), ("moe", 3, 1, True, 3, True)]
    cache = models.init_cache_paged(config, 12, 4, window_blocks=7)
    assert {k: v.shape[:2] for k, v in cache.items()} == {
        "k": (1, 12), "v": (1, 12), "wk": (4, 7), "wv": (4, 7)}
    p = models.init_params(jax.random.PRNGKey(1), config)
    assert sum(x.size for x in jax.tree.leaves(p)) == config.num_params()
    axes = models.param_axes(config)
    assert jax.tree.structure(jax.tree.map(lambda a: 0, p)) == \
        jax.tree.structure(jax.tree.map(
            lambda a: 0, axes, is_leaf=lambda a: isinstance(a, tuple)))


# -- the step and the engine against the reference ---------------------------

@pytest.mark.parametrize("chunk,budget", [
    (1, None), (3, None), (8, None), (16, None), (8, 5)],
    ids=["1", "3", "8", "16", "8-budget_5"])
def test_engine_prefill_then_decode_matches_the_reference(
        reference, config, params, chunk, budget, monkeypatch):
    """Short and long rows in one step, through BOTH pools: six requests
    through four slots, prompts from under one window (5 tokens) to nine
    windows (70), so that long rows release blocks on the way while short
    ones never leave the window; prefill through chunks of 1, 3, 8 and 16
    positions (a chunk wider than the window too), then decode. Under the
    256-position budget every step of these grids is the step as it was;
    with a budget of 5 of 4 x 8 positions the steps of several chunk rows
    take the whole grid, those of one chunk row or a short tail beside
    decoding rows the second width (10) and the decode steps the budget."""
    if budget:
        monkeypatch.setattr("ray_tpu.serve.llm.STEP_BUDGET", budget)
    eng = _engine(config, params, prefill_chunk=chunk)
    reals = watch_step_widths(eng)
    requests = [(_prompt(10 + i, n), m) for i, (n, m) in enumerate(
        [(5, 20), (23, 12), (70, 30), (9, 9), (41, 5), (17, 40)])]
    served = _serve_all(eng, requests)
    for (prompt, n), (toks, logits) in zip(requests, served):
        assert len(toks) == n
        assert _against_reference(reference, params, config, prompt, toks,
                                  logits) < TOL
    s = eng.stats
    assert s["step_positions_real"] == sum(
        len(p) + n - 1 for p, n in requests)
    if budget:
        assert_three_widths(eng, reals)
    else:
        assert s["steps_full_width"] == s["steps_second_width"] == 0
    assert s["prefix_hit_tokens"] == 0 and len(eng.prefix) == 0
    assert s["window_blocks_released"] > 0
    assert s["window_blocks_held"] < s["window_blocks_full_table"]
    assert 0 < s["moe_pairs_held"] < s["moe_pairs_routed"]
    kv = eng.kv_state()
    assert kv["kv_free"] == kv["kv_total"] \
        == eng.pool.num_blocks + eng.win_pool.num_blocks
    assert kv["kv_pools"]["window"] == {
        "total": eng.win_pool.num_blocks, "free": eng.win_pool.num_blocks,
        "reserved": 0}
    assert "state" not in kv["kv_pools"]


def test_engine_in_bfloat16_stays_inside_its_tolerance(reference, config):
    c16 = config.replace(dtype="bfloat16", param_dtype="bfloat16")
    p16 = models.init_params(jax.random.PRNGKey(0), c16)
    eng = _engine(c16, p16)
    prompt = _prompt(3, 37)
    toks, logits = _serve(eng, prompt, 24)
    err = _against_reference(reference, p16, c16, prompt, toks, logits)
    assert 1e-4 < err < TOL_BF16
    assert {v.dtype for v in eng._cache.values()} == {jnp.dtype("bfloat16")}


def test_a_grid_wider_than_the_budget_runs_the_ordered_stream(
        reference, config, params):
    """16 slots x 32 positions pass the 256-position budget: the step
    gathers the real positions to the front of one flat stream (the gate
    rides it from the stage before the attention to the stage after)."""
    eng = _engine(config, params, max_slots=16, prefill_chunk=32,
                  max_len=96)
    requests = [(_prompt(40 + i, 33 + i), 6) for i in range(10)]
    served = _serve_all(eng, requests)
    for (prompt, n), (toks, logits) in zip(requests, served):
        assert _against_reference(reference, params, config, prompt, toks,
                                  logits) < TOL
    assert eng.stats["steps_full_width"] >= 1
    assert eng.stats["steps_decode_only"] >= 1


@pytest.mark.parametrize("control", [
    "no_gate", "no_post_norms", "no_router_bias", "window_short_a_block",
    "rope_in_full", "no_rope", "no_embed_scale", "no_scale", "no_shared",
    "no_held", "no_qk_norm"])
def test_each_piece_left_out_of_the_reference_shows(
        reference, config, params, control, monkeypatch):
    """The engine against the reference with one piece of the mathematics
    left out or moved: the gate, the post-norms, the selection bias, the
    window's edge (a block short), RoPE in the wrong kind of layer, ... each
    reads far over the tolerance, so a program that left it out would."""
    monkeypatch.setattr(reference, "WINDOW_BLOCK", 4)
    eng = _engine(config, params)
    prompt = _prompt(7, 45)
    toks, logits = _serve(eng, prompt, 16)
    assert _against_reference(reference, params, config, prompt, toks,
                              logits) < TOL
    assert _against_reference(reference, params, config, prompt, toks,
                              logits, weights=control) > 100 * TOL


# -- the window pool ------------------------------------------------------------

def test_a_row_past_two_windows_holds_no_block_before_its_window(
        config, params):
    """Every step: a row's window table starts at the block of the first key
    its first query may see, holds no block that lies wholly before it, and
    never more than ``window_table_width``."""
    eng = _engine(config, params)
    bs, seen = eng.pool.block_size, []

    def on_step(eng):
        for req in eng._slots:
            if req is None or not req.win_table:
                continue
            # (``pos`` counts the step just dispatched: the table was moved
            # for a step that began at or before it)
            assert req.win_first * bs + bs > req.pos - WINDOW - eng.prefill_chunk
            assert len(req.win_table) <= eng._win_width
            assert req.win_first >= max(
                req.pos - eng.prefill_chunk - WINDOW + 1, 0) // bs
            seen.append((req.pos, req.win_first, len(req.win_table)))

    _serve(eng, _prompt(5, 60), 30, on_step=on_step)
    past = [s for s in seen if s[0] > 2 * WINDOW + eng.prefill_chunk]
    assert past and all(first > 0 for _, first, _ in past)
    # a full table would hold ceil(pos / bs) blocks; the window table holds
    # the window's span
    assert max(n for _, _, n in seen) <= eng._win_width
    assert eng.stats["window_blocks_released"] >= 60 // bs
    assert eng.win_pool.free_count == eng.win_pool.num_blocks


def test_admission_waits_for_the_window_pools_reservation(config, params):
    """A window pool sized for two rows: the third request waits for WINDOW
    blocks (never raises inside a step), is admitted when a row ends, and
    both pools come back whole."""
    tracing._reset_for_tests()
    eng = _engine(config, params,
                  num_blocks=96, window_blocks=2 * 5)
    assert eng._win_width == 5 and eng.win_pool.num_blocks == 10
    requests = [(_prompt(20 + i, 30), 8) for i in range(3)]
    waited = []

    def on_step(eng):
        assert eng._win_reserved <= eng.win_pool.num_blocks
        waited.extend(r.waited_for for r in eng._pending)

    served = _serve_all(eng, requests, on_step=on_step)
    assert all(len(t) == 8 for t, _ in served)
    assert "window_blocks" in waited
    s = eng.stats
    assert s["requests_waited_window_blocks"] == 1
    assert 0 < s["window_blocks_wait_s"] <= s["pending_wait_s"]
    kv = eng.kv_state()
    assert kv["kv_free"] == kv["kv_total"]
    assert kv["kv_pools"]["window"]["reserved"] == 0
    with pytest.raises(ValueError, match="window blocks at once"):
        _engine(config, params, num_blocks=96, window_blocks=3) \
            .submit(_prompt(1, 30), 4, lambda _: None)


def test_window_blocks_sizes_the_window_pool_of_a_layout_that_has_one(
        config, params):
    with pytest.raises(ValueError, match="has no window pool"):
        LLMEngine("llama-debug", max_slots=2, max_len=32, block_size=4,
                  num_blocks=16, window_blocks=8)
    eng = _engine(config, params, num_blocks=64)
    assert eng.pool.num_blocks == 64
    assert eng.win_pool.num_blocks == 4 * eng._win_width


# -- the share ------------------------------------------------------------------

def test_the_eight_shares_of_an_expert_layer_add_up_to_the_uncut_layer(
        config, params):
    """Held experts 0-1, 2-3, ..., 14-15 of one expert layer, the shared
    expert counted once, against the layer holding all sixteen."""
    from ray_tpu.models.transformer import _decode_mlp

    c_all = config.replace(experts_held=None, experts_first=0)
    full = models.init_params(jax.random.PRNGKey(2), c_all)
    lp_all = jax.tree.map(lambda w: w[1], full["layers"]["moe"])
    # (no post-norm here: a norm of a sum is not the sum of the norms; the
    # shares add up BEFORE it, where a deployment's exchange adds them)
    lp_all.pop("post_mlp_norm")
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 9, config.d_model))
    dt = jnp.float32
    whole, counts = _decode_mlp(x, lp_all, c_all, dt)
    assert int(counts.sum()) == 2 * 9 * config.expert_top_k
    total = jnp.zeros_like(x)
    held_pairs = 0
    for first in range(0, 16, 2):
        c = config.replace(experts_held=2, experts_first=first)
        lp = {**lp_all, **{n: lp_all[n][first:first + 2]
                           for n in ("w_gate", "w_up", "w_down")}}
        part, n = _decode_mlp(x, lp, c, dt)
        held_pairs += int(n.sum())
        total = total + (part - x)
    # each share added the shared expert: count it once
    no_routed = config.replace(experts_held=2, experts_first=0)
    lp0 = {**lp_all, **{n: jnp.zeros_like(lp_all[n][:2])
                        for n in ("w_gate", "w_up", "w_down")}}
    shared = _decode_mlp(x, lp0, no_routed, dt)[0] - x
    assert held_pairs == 2 * 9 * config.expert_top_k
    np.testing.assert_allclose(total - 7 * shared, whole - x, atol=2e-5)


# -- refusals -----------------------------------------------------------------

def test_nothing_enters_the_trie_and_nothing_ships(config, params):
    eng = _engine(config, params)
    prompt = _prompt(9, 40)
    first, _ = _serve(eng, prompt, 6)
    again, _ = _serve(eng, prompt, 6)
    assert first == again
    assert eng.stats["prefix_hit_tokens"] == 0 and len(eng.prefix) == 0
    with pytest.raises(NotImplementedError, match="window_pool.*"
                       "a prefill-only export"):
        eng.submit(prompt, 4, lambda _: None, prefill_only=True)
    with pytest.raises(NotImplementedError, match="window_pool.*adoption"):
        eng.adopt(prompt, {}, 1, 4, lambda _: None)
    with pytest.raises(NotImplementedError,
                       match="window_pool.*live-session migration"):
        eng.begin_migration()
    for fn, args in ((models.copy_kv_block, (eng._cache, 0, 1)),
                     (models.gather_kv_blocks, (eng._cache, [0])),
                     (models.scatter_kv_blocks, (eng._cache, [0], {}))):
        with pytest.raises(NotImplementedError, match="pools by kind"):
            fn(*args)


@pytest.mark.parametrize("where", ["forward", "init_cache", "generate"])
def test_the_layout_is_the_paged_serve_steps_alone(config, params, where):
    toks = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(NotImplementedError, match="windowed MoE layout"):
        if where == "forward":
            models.forward(params, toks, config)
        elif where == "init_cache":
            models.init_cache(config, 1, 16)
        else:
            models.generate(params, toks, config, max_new_tokens=2)


BASE = dict(vocab_size=64, d_model=32, n_layers=4, n_heads=2, n_kv_heads=1,
            head_dim=16, d_ff=64, num_experts=4, expert_top_k=2,
            d_ff_expert=16, remat=False)


@pytest.mark.parametrize("keys, message", [
    (dict(attn_gate=True, num_experts=0), "needs experts"),
    (dict(post_norms=True, dense_layers=4), "needs experts"),
    (dict(experts_held=3, experts_first=2), "held experts within"),
    (dict(expert_scoring="tanh"), "softmax or sigmoid"),
    (dict(attn_gate=True, norm="layer"), "RMSNorm, SwiGLU and RoPE"),
    (dict(attn_gate=True, attn_qkv_bias=True), "without biases"),
    (dict(shared_experts=1, index_heads=2), "without biases, softcap or "
     "indexer"),
    (dict(rope_layers="full", attn_gate=True), "'all' or 'window'"),
    (dict(rope_layers="window"), "mix one window size"),
    (dict(rope_layers="window", attn_windows=(8, 8)), "mix one window size"),
    (dict(attn_windows=(8, 0)), "sliding_window equal"),
    (dict(attn_windows=(8, 0), sliding_window=4), "sliding_window equal"),
    (dict(attn_gate=True, attn_windows=(8, 8, 0, 8), sliding_window=16),
     "sliding_window equal"),
    (dict(attn_gate=True, kv_lora_rank=8), "not with kv_lora_rank"),
    (dict(post_norms=True, layer_kinds=("parallel",) * 4),
     "not with kv_lora_rank or layer_kinds"),
    (dict(shared_experts=1, layer_kinds=("parallel",) * 4),
     "parallel layout described"),
    (dict(embedding_multiplier=2.0, d_ff_expert=None),
     "described for the parallel layout"),
    (dict(rope_factor=4.0), "YaRN is described"),
])
def test_undescribed_combinations_are_refused_by_name(keys, message):
    with pytest.raises(ValueError, match=message):
        TransformerConfig(**{**BASE, **keys})


def test_described_combinations_stand():
    TransformerConfig(**BASE, shared_experts=1)
    TransformerConfig(**BASE, attn_gate=True, embedding_multiplier=4.0)
    # windows that do not mix ONE size with full layers stay a mask
    for windows in ((8, 4, 0, 0), (8, 8)):
        c = TransformerConfig(**BASE, attn_windows=windows)
        assert c.windowed_moe and not c.window_pool
    c = TransformerConfig(**BASE, sliding_window=8)
    assert c.windowed_moe and not c.window_pool and c.uniform_window == 8
    # ... and the same pattern on a decoder that is not this layout
    c = TransformerConfig(vocab_size=64, d_model=32, n_layers=4, n_heads=2,
                          n_kv_heads=1, d_ff=64, attn_windows=(8, 0))
    assert not c.windowed_moe and not c.window_pool
    c = TransformerConfig(**BASE, rope_layers="window", sliding_window=8,
                          attn_windows=(8, 8, 0, 8))
    assert c.window_pool and c.uniform_window == 0


def test_windows_that_mix_with_no_full_layer_are_a_mask_over_one_pool(
        reference, config, params):
    """The same weights with a window in EVERY layer (nothing to release a
    pool for: one pool, a window is a mask over a full table) give the
    reference's logits too."""
    masked = config.replace(attn_windows=(8,) * 5, rope_layers="all")
    eng = _engine(masked, params)
    assert eng.win_pool is None and set(eng._cache) == {"k", "v"}
    prompt = _prompt(11, 50)
    toks, logits = _serve(eng, prompt, 10)
    assert _against_reference(reference, params, masked, prompt, toks,
                              logits) < TOL


def test_afmoe_checkpoints_are_refused_by_name():
    with pytest.raises(ValueError, match="afmoe.*expert_bias"):
        config_from_hf(SimpleNamespace(model_type="afmoe"))


# -- the attention's offset -----------------------------------------------------

@pytest.mark.parametrize("preset", ["llama-debug", "mistral-debug",
                                    "qwen2-debug", "gemma-debug"])
def test_a_zero_first_block_is_bit_equal_for_the_uniform_decoders(preset):
    c = models.get_config(preset)
    rng = np.random.default_rng(0)
    b, t, m, bs, nb = 3, 4, 6, 4, 32
    q = jnp.asarray(rng.normal(size=(b, t, c.n_heads, c.hdim)), c.dtype)
    k = jnp.asarray(rng.normal(size=(nb, bs, c.kv_heads, c.hdim)), c.dtype)
    v = jnp.asarray(rng.normal(size=(nb, bs, c.kv_heads, c.hdim)), c.dtype)
    tables = jnp.asarray(rng.permutation(nb)[:b * m].reshape(b, m), jnp.int32)
    pos = jnp.asarray([0, 7, 19], jnp.int32)
    nvalid = jnp.asarray([4, 1, 3], jnp.int32)
    kw = dict(window=jnp.int32(c.uniform_window or 1 << 30),
              softcap=c.attn_softcap, scale=c.hdim ** -0.5)
    plain = paged_attention(q, k, v, tables, pos, nvalid, **kw)
    zero = paged_attention(q, k, v, tables, pos, nvalid, **kw,
                           first_block=jnp.zeros((b,), jnp.int32))
    assert np.array_equal(np.asarray(plain), np.asarray(zero))


def test_a_window_table_reads_what_the_full_table_reads():
    """A table that holds the live window only, entry 0 the block of the
    first visible key, with ``first_block``, against the full table."""
    rng = np.random.default_rng(1)
    b, t, bs, nb, h, kvh, hd, window = 2, 3, 4, 40, 4, 2, 16, 9
    q = jnp.asarray(rng.normal(size=(b, t, h, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(nb, bs, kvh, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(nb, bs, kvh, hd)), jnp.float32)
    pos = jnp.asarray([5, 41], jnp.int32)
    nvalid = jnp.asarray([3, 3], jnp.int32)
    full = jnp.asarray(rng.permutation(nb)[:b * 12].reshape(b, 12), jnp.int32)
    first = jnp.maximum(pos - window + 1, 0) // bs
    width = 4
    win = jnp.stack([jnp.take(full[i], jnp.minimum(
        first[i] + jnp.arange(width), 11)) for i in range(b)])
    kw = dict(window=jnp.int32(window), scale=hd ** -0.5)
    want = paged_attention(q, k, v, full, pos, nvalid, **kw)
    got = paged_attention(q, k, v, win, pos, nvalid, **kw, first_block=first)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
