"""The latent-attention MoE decoder family (the DeepSeek-V3 layer:
``TransformerConfig.kv_lora_rank``) on the serve path, at a small size on the
CPU (3 layers: the leading dense one and two scanned expert layers that HOLD
4 of 16 sigmoid-routed experts beside a shared expert; YaRN over an original
length of 32, which every context here passes): the paged step and the engine
against the benchmark's plain reference
(``benchmark/reference/latent_moe_decoder.py``: one float32 pass over the
whole sequence, attention UNABSORBED, no cache, experts one at a time, given
the same share), the latent pool through everything that moves a block, the
share of the experts, and the paths that refuse this layer by name."""

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import assert_three_widths, watch_step_widths

from benchmark import manifest
from ray_tpu import models
from ray_tpu.models import latent
from ray_tpu.models.import_hf import config_from_hf
from ray_tpu.ops.latent_attention import (paged_latent_attention,
                                          pool_width, yarn_inv_freq)
from ray_tpu.ops.moe import moe_layer_dropless, route_top_k
from ray_tpu.serve.kv_transfer import pack_export, unpack_payload
from ray_tpu.serve.llm import LLMEngine

REF_LEN = 128
#: float32 on both sides: what is left is the order of the sums (absorbed
#: against unabsorbed, a paged gather against one pass, a grouped matmul
#: against a loop over experts)
TOL = 2e-4
#: bfloat16 weights, activations and pool against the float32 reference: the
#: toy reads 0.02-0.08 over seeds (at a width of 64 one flipped expert of a
#: token's four is a large part of its layer); a piece left out reads 0.35
TOL_BF16 = 0.15


@pytest.fixture(scope="module")
def reference():
    return manifest.load_module(
        manifest.reference_path("latent_moe_decoder"))


@pytest.fixture(scope="module")
def config():
    return models.get_config("latent-moe-debug").replace(
        dtype="float32", param_dtype="float32")


@pytest.fixture(scope="module")
def params(config):
    """``models.init_params`` draws every gain and the selection bias away
    from its trivial value."""
    return models.init_params(jax.random.PRNGKey(0), config)


def _config_file(c, **over):
    """The published keys the reference reads, from the program's config."""
    cf = {"rms_norm_eps": c.norm_eps, "rope_theta": c.rope_theta,
          "rope_scaling": {
              "type": "yarn", "factor": c.rope_factor,
              "beta_fast": c.rope_beta_fast, "beta_slow": c.rope_beta_slow,
              "mscale": c.rope_mscale,
              "mscale_all_dim": c.rope_mscale_all_dim,
              "original_max_position_embeddings": c.rope_original_len},
          "n_group": 1, "topk_group": 1, "moe_layer_freq": 1,
          "num_attention_heads": c.n_heads, "kv_lora_rank": c.kv_lora_rank,
          "qk_nope_head_dim": c.qk_nope_head_dim,
          "qk_rope_head_dim": c.qk_rope_head_dim,
          "v_head_dim": c.v_head_dim,
          "num_experts_per_tok": c.expert_top_k,
          "norm_topk_prob": c.expert_norm_topk,
          "scoring_func": c.expert_scoring,
          "routed_scaling_factor": c.expert_scale,
          "tie_word_embeddings": c.tie_embeddings,
          "reduced": {"n_routed_experts": {
              "published": c.num_experts, "here": c.held_experts,
              "first": c.experts_first}}}
    cf.update(over)
    return cf


def _reference_logits(reference, params, config, seq, rows, **kw):
    padded = np.zeros(REF_LEN, np.int32)
    padded[:len(seq)] = seq
    return np.asarray(reference.logits_at(params, padded, rows,
                                          _config_file(config), **kw))


def _rel(got, want):
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def _engine(config, params, **kw):
    kw = {"max_slots": 4, "max_len": 128, "block_size": 8,
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


# -- the step and the engine against the reference ---------------------------

@pytest.mark.parametrize("chunk", [1, 8], ids=["token_rows", "chunk_rows"])
def test_paged_step_matches_the_reference_at_every_position(
        reference, config, params, chunk):
    """Prefill through chunks (the chunk form of the absorbed attention) or
    token by token (the one-query form) against the reference's unabsorbed
    full pass: logits of EVERY position of a 50-token sequence, through a
    block table that is not in order."""
    n, bs = 50, 4
    seq = np.asarray(_prompt(1, n))
    cache = models.init_cache_paged(config, 20, bs)
    assert set(cache) == {"kv"}
    # 24 + 8 values a token, in whole lanes (ops/latent_attention.py)
    assert config.latent_width == 24 + 8 and pool_width(32) == 128 \
        and pool_width(576) == 640
    assert cache["kv"].shape == (3, 20, bs, 128)
    step = jax.jit(lambda c, t, p, m: models.verify_step_paged(
        params, c, t, tables, p, m, config))
    tables = jnp.asarray([[7, 3, 11, 5, 19, 2, 13, 17, 1, 9, 15, 4, 6]])
    got, pos = [], 0
    while pos < n:
        m = min(chunk, n - pos)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :m] = seq[pos:pos + m]
        logits, cache = step(cache, jnp.asarray(toks), jnp.array([pos]),
                             jnp.array([m]))
        got.append(np.asarray(logits[0, :m]))
        pos += m
    want = _reference_logits(reference, params, config, seq, np.arange(n))
    assert _rel(np.concatenate(got), want) < TOL
    # blocks no table names stay as they were
    assert not np.asarray(cache["kv"][:, [0, 8, 10, 12, 14, 16, 18]]).any()


@pytest.mark.parametrize("budget", [None, 5],
                         ids=["budget_256", "budget_5"])
def test_engine_prefill_then_decode_matches_the_reference(
        reference, config, params, budget, monkeypatch):
    """Rows of different ages in one step, chunk rows beside decode rows:
    six requests through four slots, prompts that end inside a chunk and a
    block. Under the 256-position budget every step of this 4 x 8 grid fits
    it; with a budget of 5 the steps of several chunk rows take the full
    width, those of one chunk row or a short tail beside decoding rows the
    second width (10) and the decode steps the budget, and nothing a row
    gets back changes."""
    if budget:
        monkeypatch.setattr("ray_tpu.serve.llm.STEP_BUDGET", budget)
    eng = _engine(config, params)
    reals = watch_step_widths(eng)
    requests = [(_prompt(10 + i, n), m) for i, (n, m) in enumerate(
        [(5, 20), (23, 12), (40, 30), (9, 9), (31, 5), (17, 40)])]
    mixed = []
    served = _serve_all(eng, requests, on_step=lambda e: mixed.append(
        sorted({0 if r is None else min(len(r.prompt) - r.consumed, 8) or 1
                for r in e._slots})))
    for (prompt, n), (toks, logits) in zip(requests, served):
        assert len(toks) == n
        assert _against_reference(reference, params, config, prompt, toks,
                                  logits) < TOL
    s = eng.stats
    fed = sum(len(p) + n - 1 for p, n in requests)
    assert s["step_positions_real"] == fed
    assert (s["steps_full_width"] > 0) == bool(budget)
    if budget:
        assert_three_widths(eng, reals)
    assert s["steps_dispatched_ahead"] >= s["steps"] - 2    # the lookahead
    kv = eng.kv_state()
    assert kv["kv_free"] + kv["prefix"]["nodes"] == kv["kv_total"]


def test_engine_in_bfloat16_stays_inside_its_tolerance(reference, config):
    c16 = config.replace(dtype="bfloat16", param_dtype="bfloat16")
    p16 = models.init_params(jax.random.PRNGKey(0), c16)
    eng = _engine(c16, p16)
    assert eng._cache["kv"].dtype == jnp.bfloat16
    prompt = _prompt(3, 37)
    toks, logits = _serve(eng, prompt, 24)
    err = _against_reference(reference, p16, c16, prompt, toks, logits)
    assert 1e-4 < err < TOL_BF16


def test_a_grid_wider_than_the_budget_runs_the_ordered_stream(
        reference, config, params):
    """16 slots x 32 positions pass the 256-position budget: the step
    gathers the real positions to the front of one flat stream. Ten prompts
    of 33 tokens arriving together make a step of 320 real positions (over
    the budget: the whole grid); later steps hold one-token chunk tails
    beside decode rows under the budget."""
    eng = _engine(config, params, max_slots=16, prefill_chunk=32,
                  max_len=96)
    requests = [(_prompt(40 + i, 33), 6) for i in range(10)]
    served = _serve_all(eng, requests)
    for (prompt, n), (toks, logits) in zip(requests, served):
        assert _against_reference(reference, params, config, prompt, toks,
                                  logits) < TOL
    s = eng.stats
    assert s["steps_full_width"] == 1
    assert s["step_positions_run"] == 512 + 256 * (s["steps"] - 1)


@pytest.mark.parametrize("left_out", [
    "no_rope_key", "plain_rope", "softmax_router", "no_router_bias",
    "no_scale", "no_shared", "no_held", "int8"])
def test_mathematics_left_out_exceeds_the_tolerance(reference, config,
                                                    params, left_out):
    """Each piece of the layer shows: the reference with the rotated key
    dropped from the scores, plain RoPE in YaRN's place (and ``mscale`` 1),
    softmax routing, the selection bias dropped, the scaling factor
    dropped, the shared expert dropped, the HELD experts' sum dropped, or
    int8 weights, stands far from the engine, which stands on the honest
    reference."""
    eng = _engine(config, params)
    prompt = _prompt(5, 70)               # twice YaRN's original length
    toks, logits = _serve(eng, prompt, 12)
    assert _against_reference(reference, params, config, prompt, toks,
                              logits) < TOL
    assert _against_reference(reference, params, config, prompt, toks,
                              logits, weights=left_out) > 20 * TOL


# -- the latent pool through everything that moves a block --------------------

def test_a_prefix_hit_reads_the_latents_the_cold_serve_wrote(
        reference, config, params):
    """Second request over a cached prefix that ends INSIDE a block: full
    blocks come from the trie, the tail block is copied before it is
    written. What it reads there is what the first request wrote: with the
    pool wiped between the two, the hit still happens and the logits are
    wrong."""
    shared = _prompt(3, 60)              # 7 full blocks + 4 tokens
    first, second = shared + _prompt(4, 12), shared + _prompt(5, 14)

    def serve_both(wipe):
        eng = _engine(config, params)
        _serve(eng, first, 4)
        if wipe:
            eng._cache = jax.tree.map(jnp.zeros_like, eng._cache)
        hits0 = eng.stats["prefix_hit_tokens"]
        toks, logits = _serve(eng, second, 8)
        assert eng.stats["prefix_hit_tokens"] - hits0 >= 56
        return _against_reference(reference, params, config, second, toks,
                                  logits)

    assert serve_both(False) < TOL
    assert serve_both(True) > 100 * TOL


def test_cold_and_warm_serves_agree_bit_for_bit(config, params):
    """A prompt that ends on a block boundary served cold (its last tokens
    a full chunk row) and again warm (all but one token a prefix hit, the
    last one a single-token row): the sampled position takes the one-query
    form both times, so the logits are equal to the bit."""
    eng = _engine(config, params)
    prompt = _prompt(8, 64)
    cold_tokens, cold = _serve(eng, prompt, 6)
    hits0 = eng.stats["prefix_hit_tokens"]
    warm_tokens, warm = _serve(eng, prompt, 6)
    assert eng.stats["prefix_hit_tokens"] - hits0 == 63
    assert cold_tokens == warm_tokens
    assert np.array_equal(cold, warm)


def test_copy_gather_and_scatter_carry_the_latent_pool(config):
    cache = models.init_cache_paged(config, 6, 8)
    cache = {"kv": jax.random.normal(jax.random.PRNGKey(0),
                                     cache["kv"].shape)}
    copied = models.copy_kv_block(cache, 1, 4)
    got = models.gather_kv_blocks(cache, [3, 1])
    put = models.scatter_kv_blocks(cache, jnp.array([5, 6]), got)  # 6: OOB
    pool = cache["kv"]
    assert np.array_equal(copied["kv"][:, 4], pool[:, 1])
    assert np.array_equal(got["kv"][:, 0], pool[:, 3])
    assert np.array_equal(put["kv"][:, 5], pool[:, 3])
    assert np.array_equal(put["kv"][:, :5], pool[:, :5])


def test_export_and_adoption_carry_the_latent_blocks_bit_for_bit(
        reference, config, params):
    """Disaggregated serving: prefill on one engine, its latent blocks
    shipped (``pack_export`` / ``unpack_payload``) and adopted by another
    whose pool then holds them to the bit; decode there against the
    reference. A payload of K and V heads is refused."""
    prompt = _prompt(6, 75)
    exports = []
    pre = _engine(config, params, role="prefill")
    pre.submit(prompt, 1, exports.append, prefill_only=True)
    while pre.step():
        pass
    export = exports[0]
    assert set(export.kv) == {"kv"}
    assert export.kv["kv"].shape == (3, 10, 8, pool_width(config.latent_width))
    meta, arr = pack_export(export)
    assert arr.flags["C_CONTIGUOUS"] and arr.shape[0] == meta["n_blocks"]
    kv = unpack_payload(meta, arr)
    assert np.array_equal(kv["kv"], export.kv["kv"])

    dec = _engine(config, params, role="decode")
    toks, logits, sample = [], [], dec._sample

    def capture(row):
        logits.append(row.copy())
        return sample(row)

    dec._sample, dec.capture = capture, True
    dec.adopt(prompt, kv, export.token, 10,
              lambda item: toks.append(item) if isinstance(item, int)
              else None)
    dec.step()
    table = next(r for r in dec._slots if r is not None).table
    held = np.asarray(dec._cache["kv"][:, np.asarray(table[:10])])
    # the last block's tail past the prompt is the decode engine's to write
    assert np.array_equal(held[:, :9], export.kv["kv"][:, :9])
    assert np.array_equal(held[:, 9, :3], export.kv["kv"][:, 9, :3])
    while dec.step():
        pass
    assert toks[0] == export.token and len(toks) == 10
    seq = prompt + toks[:-1]
    want = _reference_logits(reference, params, config, seq,
                             np.arange(len(prompt), len(seq)))
    assert _rel(np.stack(logits), want) < TOL
    heads = {n: np.zeros((3, 10, 8, 4, 16), np.float32) for n in "kv"}
    with pytest.raises(ValueError, match="kv"):
        dec.adopt(prompt, heads, export.token, 4, lambda item: None)


# -- counters ------------------------------------------------------------------

def test_the_counters_of_a_small_run_by_hand(config, params):
    """One request of 11 prompt tokens and 3 answers at chunk 8: steps of 8
    and 3 prompt tokens, then single tokens at positions 11 and 12."""
    eng = _engine(config, params)
    _serve(eng, _prompt(7, 11), 3)
    s = eng.stats
    # a row reads its live context, the step's own tokens included
    assert s["latent_tokens_read"] == 8 + 11 + 12 + 13
    # four steps of one row each, none on the kernel: the CPU's form
    assert s["attn_impl"] == "xla"
    assert (s["latent_rows_attended"], s["latent_kernel_rows"]) == (4, 0)
    fed = 8 + 3 + 1 + 1
    assert s["step_positions_real"] == fed
    # 2 expert layers, top-4 of 16
    assert s["moe_pairs_routed"] == fed * 4 * 2
    assert s["moe_pairs_held"] == s["moe_expert_tokens_sum"] \
        <= s["moe_pairs_routed"]
    assert 0 < s["moe_experts_hit"] <= 4 * 2 * s["steps"]

    # the held pairs are those of the router's choices that fall on experts
    # 4..7: counted again from the step's own router
    cache = models.init_cache_paged(config, 4, 8)
    toks = jnp.asarray([_prompt(7, 8)])
    _, _, stats = models.decode_step_paged(
        params, cache, toks, jnp.asarray([[0, 1, 2, 3]]), jnp.array([0]),
        jnp.array([8]), config, step_stats=True)
    counts = np.asarray(stats["expert_tokens"])
    assert counts.shape == (2, 4)               # expert layers x HELD
    assert 0 < counts.sum() < 8 * 4 * 2


def test_the_kernel_counts_the_rows_it_attends(config, params, monkeypatch):
    """The same run in bf16 over a pool the kernel takes (a latent of 128 in
    256 lanes, blocks of 16), the kernel interpreted: every attended row is
    the kernel's, and the engine says which form its step was traced with."""
    from ray_tpu.ops.attention import set_default_attention_impl

    c = config.replace(kv_lora_rank=128, dtype="bfloat16",
                       param_dtype="bfloat16")
    monkeypatch.setenv("RTPU_ATTN_PALLAS_INTERPRET", "1")
    set_default_attention_impl("pallas")
    try:
        eng = _engine(c, models.init_params(jax.random.PRNGKey(0), c),
                      block_size=16)
        _serve(eng, _prompt(7, 11), 3)
    finally:
        set_default_attention_impl(None)
    s = eng.stats
    assert s["attn_impl"] == "pallas"
    assert s["latent_tokens_read"] == 8 + 11 + 12 + 13
    assert (s["latent_rows_attended"], s["latent_kernel_rows"]) == (4, 4)


@pytest.mark.parametrize("start,end,want", [
    ({}, {"latent_tokens_read": 9}, None),                  # the parent
    ({"latent_rows_attended": 2, "latent_kernel_rows": 2},
     {"latent_rows_attended": 9, "latent_kernel_rows": 9}, 100.0),
    ({"latent_rows_attended": 2, "latent_kernel_rows": 0},
     {"latent_rows_attended": 9, "latent_kernel_rows": 0}, 0.0),
    ({"latent_rows_attended": 5, "latent_kernel_rows": 5},       # idle
     {"latent_rows_attended": 5, "latent_kernel_rows": 5}, None),
], ids=["no_counter", "all_rows", "no_row", "idle_window"])
def test_the_kernel_rows_reader(start, end, want):
    """``benchmark/layer_metrics/latent_kernel_rows_pct.py`` over a window's
    two marks: nothing where the engine has no such counter."""
    reader = manifest.load_module(
        manifest.layer_metric_path("latent_kernel_rows_pct"))
    run = {"marks": {"start": {"stats": start}, "end": {"stats": end}}}
    assert reader.read(run) == want
    assert reader.read({}) is None


# -- the expert layer: routing and the share ----------------------------------

def _route_by_hand(x, w, bias, k, scale):
    """Ten lines of numpy: sigmoid scores, top-k of score + bias, the
    chosen SCORES renormalised and scaled."""
    out_p, out_e = [], []
    for row in np.asarray(x, np.float64) @ np.asarray(w, np.float64):
        score = 1.0 / (1.0 + np.exp(-row))
        chosen = np.argsort(-(score + np.asarray(bias, np.float64)),
                            kind="stable")[:k]
        p = score[chosen]
        out_p.append(p / (p.sum() + 1e-20) * scale)
        out_e.append(chosen)
    return np.stack(out_p), np.stack(out_e)


def test_sigmoid_routing_with_bias_renormalisation_and_scale():
    key = jax.random.PRNGKey(2)
    x = jax.random.normal(key, (33, 16))
    w = jax.random.normal(jax.random.fold_in(key, 1), (16, 12))
    # a bias large enough to change the choice, which must not weigh
    bias = 0.3 * jax.random.normal(jax.random.fold_in(key, 2), (12,))
    p, e = route_top_k(x, w, k=3, norm_topk=True, scoring="sigmoid",
                       bias=bias, scale=2.5)
    want_p, want_e = _route_by_hand(x, w, bias, 3, 2.5)
    assert np.array_equal(np.asarray(e), want_e)
    np.testing.assert_allclose(np.asarray(p), want_p, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(p).sum(-1), 2.5, rtol=1e-5)
    _, plain = route_top_k(x, w, k=3, norm_topk=True, scoring="sigmoid")
    assert not np.array_equal(np.asarray(plain), want_e)
    # softmax routing is what it was: no bias, no scale
    ps, es = route_top_k(x, w, k=3, norm_topk=True)
    top = np.argsort(-np.asarray(x @ w), axis=-1, kind="stable")[:, :3]
    assert np.array_equal(np.asarray(es), top)
    np.testing.assert_allclose(np.asarray(ps).sum(-1), 1.0, rtol=1e-5)


def test_the_shares_add_up_to_the_uncut_layer(reference, config, params):
    """THE SHARE TEST: the four shares of the toy's 16 experts (4 held
    each, from 0, 4, 8, 12), each through the program's expert layer with
    the 16-wide router, give routed sums that add up, with the shared
    expert counted once, to the reference's uncut layer over all 16."""
    c = config
    key = jax.random.PRNGKey(3)
    d, fe, e = c.d_model, c.ff_expert, c.num_experts
    draw = lambda i, *shape: jax.random.normal(
        jax.random.fold_in(key, i), shape) * shape[-2] ** -0.5
    whole = {"router": draw(0, d, e), "router_bias": 0.1 * draw(1, 1, e)[0],
             "w_gate": draw(2, e, d, fe), "w_up": draw(3, e, d, fe),
             "w_down": draw(4, e, fe, d), "ws_gate": draw(5, d, fe),
             "ws_up": draw(6, d, fe), "ws_down": draw(7, fe, d)}
    m = jax.random.normal(jax.random.fold_in(key, 8), (24, d))
    hp = dict(reference.hyper(_config_file(c)))
    with jax.default_matmul_precision("highest"):
        uncut = reference._experts(m, whole, {**hp, "experts_first": 0})
        shared = reference._swiglu_columns(
            m, whole, ("ws_gate", "ws_up", "ws_down"), False)
        total, pairs = jnp.zeros_like(m), 0
        for first in range(0, e, c.held_experts):
            cut = slice(first, first + c.held_experts)
            routed, counts = moe_layer_dropless(
                m, whole["router"], whole["w_gate"][cut],
                whole["w_up"][cut], whole["w_down"][cut], k=c.expert_top_k,
                norm_topk=True, scoring="sigmoid",
                bias=whole["router_bias"], scale=c.expert_scale,
                first=first)
            assert counts.shape == (c.held_experts,)
            # the reference, given the same share, gives the same part
            part = reference._experts(
                m, {k: w[cut] if k in ("w_gate", "w_up", "w_down") else w
                    for k, w in whole.items() if not k.startswith("ws_")},
                {**hp, "experts_first": first})
            assert _rel(np.asarray(routed), np.asarray(part)) < 1e-5
            total, pairs = total + routed, pairs + int(counts.sum())
    assert pairs == 24 * c.expert_top_k       # every pair on one share
    assert _rel(np.asarray(total + shared), np.asarray(uncut)) < 1e-5
    # and one share alone is far from the layer
    assert _rel(np.asarray(routed + shared), np.asarray(uncut)) > 0.1


def test_softmax_routing_goes_through_the_same_layer_unmoved():
    """A model that holds every expert and routes by softmax (the sparse-MoE
    family) gives the new arguments their defaults: the same sums as with
    them spelled out, to the bit."""
    key = jax.random.PRNGKey(4)
    x = jax.random.normal(key, (10, 16))
    r = jax.random.normal(jax.random.fold_in(key, 1), (16, 8))
    wg, wu = (jax.random.normal(jax.random.fold_in(key, i), (8, 16, 12))
              for i in (2, 3))
    wd = jax.random.normal(jax.random.fold_in(key, 4), (8, 12, 16))
    a, ca = moe_layer_dropless(x, r, wg, wu, wd, k=2, norm_topk=True)
    b, cb = moe_layer_dropless(x, r, wg, wu, wd, k=2, norm_topk=True,
                               scoring="softmax", bias=None, scale=1.0,
                               first=0)
    assert np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(np.asarray(ca), np.asarray(cb))


# -- YaRN and the absorbed attention -------------------------------------------

def test_yarn_frequencies_are_the_formula_at_three_positions(reference):
    """Kimi-K2.5's numbers: 64 rotated dimensions, theta 50,000, factor 64,
    beta 32 and 1 over 4096 positions. Index 0 turns 652 times over 4096
    positions and stays; index 31 turns 0.02 times and is divided by 64;
    index 14 lies on the ramp between index 8 and index 20."""
    import math

    dim, theta, factor, orig = 64, 50000.0, 64.0, 4096
    inv = np.asarray(yarn_inv_freq(dim, theta, factor, 32.0, 1.0, orig))
    plain = theta ** (-np.arange(0, dim, 2) / dim)
    index = lambda turns: dim * math.log(orig / (turns * 2 * math.pi)) \
        / (2 * math.log(theta))
    low, high = math.floor(index(32.0)), math.ceil(index(1.0))
    assert (low, high) == (8, 20)
    want = {0: plain[0], 14: plain[14] * (0.5 + 0.5 / factor),
            31: plain[31] / factor}
    for k, w in want.items():
        assert inv[k] == pytest.approx(w, rel=1e-6)
    np.testing.assert_allclose(inv, np.asarray(reference.yarn_inv_freq(
        dim, theta, factor, 32.0, 1.0, orig)), rtol=1e-6)
    # the angles at three positions, ten times the original length among
    # them: position x inverse frequency
    c = models.get_config("latent-moe-debug").replace(
        qk_rope_head_dim=64, rope_theta=theta, rope_factor=factor,
        rope_original_len=orig)
    cos, sin = latent.rope_tables(jnp.asarray([[1, 4096, 40960]]), c)
    for i, p in enumerate((1, 4096, 40960)):
        np.testing.assert_allclose(np.asarray(cos[0, i]),
                                   np.cos(p * inv.astype(np.float64)),
                                   atol=2e-3)
    assert c.rope_softmax_mscale == pytest.approx(
        (0.1 * math.log(64.0) + 1.0) ** 2)
    assert latent.softmax_scale(c.replace(
        qk_nope_head_dim=128)) == pytest.approx(0.1447, abs=1e-4)


def test_a_row_attends_alike_as_a_chunk_row_and_as_token_rows():
    """The absorbed attention's two forms over one pool: a row of 8 queries
    against the same queries one a row; and padding queries return nothing
    that reaches a real one."""
    key = jax.random.PRNGKey(5)
    rank, rope, h, bs = 24, 8, 4, 4
    pool = jax.random.normal(key, (12, bs, rank + rope))
    q = jax.random.normal(jax.random.fold_in(key, 1), (1, 8, h, rank + rope))
    table = jnp.asarray([[5, 2, 9, 0, 7, 11]])
    pos = jnp.asarray([13])
    chunk = paged_latent_attention(q, pool, table, pos, jnp.asarray([8]),
                                   rank=rank, scale=0.3)
    for i in range(8):
        one = paged_latent_attention(q[:, i:i + 1], pool, table, pos + i,
                                     jnp.asarray([1]), rank=rank, scale=0.3)
        np.testing.assert_allclose(np.asarray(chunk[0, i]),
                                   np.asarray(one[0, 0]), rtol=2e-5,
                                   atol=2e-6)
    short = paged_latent_attention(q, pool, table, pos, jnp.asarray([5]),
                                   rank=rank, scale=0.3)
    np.testing.assert_allclose(np.asarray(short[0, :5]),
                               np.asarray(chunk[0, :5]), rtol=2e-5,
                               atol=2e-6)


# -- what refuses the layer by name --------------------------------------------

@pytest.mark.parametrize("path", ["forward_features", "decode_step",
                                  "generate", "init_cache"])
def test_the_dense_paths_raise_for_this_layer(config, params, path):
    toks = jnp.zeros((1, 4), jnp.int32)
    calls = {
        "forward_features": lambda: models.forward(params, toks, config),
        "decode_step": lambda: models.decode_step(
            params, {"pos": jnp.zeros((1,), jnp.int32)}, toks, config),
        "generate": lambda: models.generate(params, toks, config,
                                            max_new_tokens=2),
        "init_cache": lambda: models.init_cache(config, 1, 16),
    }
    with pytest.raises(NotImplementedError, match="latent attention"):
        calls[path]()


@pytest.mark.parametrize("hf", [
    {"model_type": "kimi_k2"}, {"model_type": "deepseek_v3"},
    {"model_type": "other", "kv_lora_rank": 512}],
    ids=["kimi_k2", "deepseek_v3", "kv_lora_rank"])
def test_import_hf_refuses_latent_attention_with_what_is_missing(hf):
    with pytest.raises(ValueError, match="block_shapes.*pairing"):
        config_from_hf(SimpleNamespace(**hf))


def test_the_published_layout_counts_its_parameters():
    """Kimi-K2.5's language model at the benchmark's cut (5 layers, 12 of
    384 experts held, an eighth of the vocabulary) and whole."""
    family = manifest.load_module(
        manifest.HERE + "/families/latent_moe_decoder.py")
    cf = manifest.load_json(
        manifest.HERE + "/configs/kimi-k2.5-ep32-l5-serve.json")
    c = family.transformer_config(cf)
    shapes = jax.eval_shape(lambda k: family.build_params(c, k),
                            jax.random.PRNGKey(0))
    held = sum(x.size for x in jax.tree.leaves(shapes))
    assert c.num_params() == held == 3_496_763_904
    assert jax.tree.structure(shapes) == jax.tree.structure(
        jax.eval_shape(lambda k: models.init_params(k, c),
                       jax.random.PRNGKey(0)))
    # a token is multiplied by 8 experts a layer wherever they live
    assert c.active_params() - c.num_params() == 4 * (8 - 12) * 44_040_192
    assert c.flops_per_token() == 6 * (
        c.active_params() - 2 * 20480 * 7168)
    whole = c.replace(n_layers=61, vocab_size=163840, experts_held=None)
    assert 1.02e12 < whole.num_params() < 1.05e12          # "1.04T"
    assert 32e9 < whole.active_params() < 33.5e9           # "A32B"


@pytest.mark.parametrize("bad", [
    {"experts_held": 8, "experts_first": 12}, {"expert_scoring": "tanh"},
    {"q_lora_rank": 0}, {"qk_rope_head_dim": 7}, {"dense_layers": 4},
    {"sliding_window": 8}],
    ids=["share_past_the_router", "scoring", "no_q_rank", "odd_rope",
         "dense_past_depth", "window"])
def test_a_layer_that_is_not_described_is_refused(bad):
    with pytest.raises(ValueError, match="latent-attention"):
        models.get_config("latent-moe-debug").replace(**bad)


def test_the_new_keys_are_refused_outside_the_layouts_that_describe_them():
    """The expert layer's keys stand under latent attention and, since the
    windowed MoE layout, under a uniform GQA decoder; nowhere else."""
    with pytest.raises(ValueError, match="latent-attention layout"):
        models.get_config("hybrid-state-debug").replace(shared_experts=1)
    assert models.get_config("moe-debug").replace(
        shared_experts=1).windowed_moe
