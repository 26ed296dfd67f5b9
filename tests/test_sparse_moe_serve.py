"""The sparse-attention MoE decoder family on the serve path, at a small size
on the CPU (4 layers, 8 experts top-2, an indexer that keeps 16 keys,
contexts to 96): the paged step and the engine against the benchmark's plain
reference (``benchmark/reference/sparse_moe_decoder.py``: one float32 pass
over the whole sequence, no cache), the indexer's third pool through every
path that moves a block, and the dropless expert layer."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import assert_three_widths, watch_step_widths

from benchmark import manifest
from ray_tpu import models
from ray_tpu.ops import moe
from ray_tpu.ops.sparse_attention import (paged_sparse_attention,
                                          select_top_k)
from ray_tpu.serve.kv_transfer import pack_export, unpack_payload
from ray_tpu.serve.llm import LLMEngine

TOPK = 16
REF_LEN = 96
#: float32 on both sides: what is left is the order of the sums (a paged
#: gather against one pass, experts grouped against looped). A selection or
#: an expert that differed would read 1e-2 and more (the controls below).
TOL = 2e-5


@pytest.fixture(scope="module")
def reference():
    return manifest.load_module(manifest.reference_path("sparse_moe_decoder"))


@pytest.fixture(scope="module")
def config():
    return models.get_config("sparse-moe-debug").replace(
        dtype="float32", param_dtype="float32")


@pytest.fixture(scope="module")
def params(config):
    """Seeded weights with every gain, the LayerNorm bias and the q/k-norm
    away from their trivial values, so that leaving one out shows."""
    p = models.init_params(jax.random.PRNGKey(0), config)
    key = jax.random.PRNGKey(5)
    for name in ("attn_norm", "mlp_norm", "q_norm", "k_norm", "ki_norm",
                 "ki_norm_b"):
        key, k1 = jax.random.split(key)
        p["layers"][name] = p["layers"][name] + 0.2 * jax.random.normal(
            k1, p["layers"][name].shape)
    return p


def _config_file(config):
    return {"rms_norm_eps": config.norm_eps, "rope_theta": config.rope_theta,
            "sa_config": {"topk": config.index_topk},
            "num_experts_per_tok": config.expert_top_k,
            "norm_topk_prob": config.expert_norm_topk,
            "tie_word_embeddings": config.tie_embeddings}


def _reference_logits(reference, params, config, seq, rows, **kw):
    padded = np.zeros(REF_LEN, np.int32)
    padded[:len(seq)] = seq
    return np.asarray(reference.logits_at(params, padded, rows,
                                          _config_file(config), **kw))


def _rel(got, want):
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def _engine(config, params, **kw):
    kw = {"max_slots": 4, "max_len": 128, "block_size": 8, "num_blocks": 96,
          "prefill_chunk": 8, **kw}
    return LLMEngine(config, params, **kw)


def _serve(eng, prompt, n, **kw):
    """Serve one request to its end; returns (tokens, logits per token)."""
    toks, logits, sample = [], [], eng._sample

    def capture(row):
        logits.append(row.copy())
        return sample(row)

    eng._sample, eng.capture = capture, True
    try:
        eng.submit(prompt, n, lambda item: toks.append(item)
                   if isinstance(item, int) else None, **kw)
        while eng.step():
            pass
    finally:
        eng._sample, eng.capture = sample, False
    return toks, np.stack(logits)


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(0, 256, n).tolist()


# -- the step and the engine against the reference ---------------------------

@pytest.mark.parametrize("chunk", [1, 8], ids=["token_rows", "chunk_rows"])
def test_paged_step_matches_the_reference_at_every_position(
        reference, config, params, chunk):
    """Prefill through chunks (or token by token: the gather path) against
    the reference's full pass, logits of EVERY position: contexts run from 1
    key (everything selected, dense attention) to 72 (16 of 72 selected)."""
    n = 72
    seq = np.asarray(_prompt(1, n))
    cache = models.init_cache_paged(config, 16, 8)
    tables = jnp.arange(12, dtype=jnp.int32)[None]
    got, pos = [], 0
    step = jax.jit(lambda c, t, p, m: models.verify_step_paged(
        params, c, t, tables, p, m, config))
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
    # and the comparison can see a wrong selection: the reference with the
    # indexer left out (latest 16 keys) is far from the reference
    recent = _reference_logits(reference, params, config, seq, np.arange(n),
                               weights="recent_keys")
    assert _rel(recent[TOPK + 8:], want[TOPK + 8:]) > 100 * TOL


@pytest.mark.parametrize("budget", [None, 3],
                         ids=["budget_256", "budget_3"])
def test_engine_prefill_then_decode_matches_the_reference(
        reference, config, params, budget, monkeypatch):
    """With a budget of 3 of the step's 32 positions a whole chunk takes the
    full width, the prompt's last chunk of six positions the second width
    and a decode step the budget: the request's life crosses all three."""
    if budget:
        monkeypatch.setattr("ray_tpu.serve.llm.STEP_BUDGET", budget)
    eng = _engine(config, params)
    reals = watch_step_widths(eng)
    prompt = _prompt(2, 70)
    toks, logits = _serve(eng, prompt, 20)
    seq = prompt + toks[:-1]
    want = _reference_logits(reference, params, config, seq,
                             np.arange(len(prompt) - 1, len(seq)))
    assert _rel(logits, want) < TOL
    s = eng.stats
    # 19 single-token rows past topk read 16 keys each of 71..89; the first
    # token came off the prompt's last chunk
    assert s["attn_keys_selected"] == 19 * TOPK
    assert s["attn_keys_live"] == sum(range(71, 90))
    # dropless: every fed token ran top-2 experts in each of 4 layers
    assert s["moe_expert_tokens_sum"] == 2 * 4 * (70 + 19)
    assert 0 < s["moe_experts_hit"] <= 8 * 4 * s["steps"]
    assert s["moe_expert_tokens_max"] * 8 >= s["moe_expert_tokens_sum"]
    assert s["step_positions_real"] == 70 + 19
    # nine chunk steps (eight full, one of six positions) and 19 of a token
    assert s["steps_full_width"] == (8 if budget else 0)
    assert s["steps_second_width"] == (1 if budget else 0)
    assert s["step_positions_run"] == (8 * 32 + 6 + 19 * 3 if budget
                                       else 32 * s["steps"])
    if budget:
        assert_three_widths(eng, reals)


def test_a_mixed_batch_through_the_lookahead_is_each_request_alone(
        config, params):
    """Three requests through two slots with nothing of the logits crossing
    to the host (``capture`` off: the path a serving window runs): each
    one's tokens are those it gets alone under the tap, one of them ends on
    its ``eos`` a step late, and the device's expert counts, read one call
    after the step that made them, are whole once the engine has drained."""
    jobs = [(_prompt(30, 37), 9), (_prompt(31, 70), 12), (_prompt(32, 5), 15)]
    alone = [_serve(_engine(config, params), p, n)[0] for p, n in jobs]
    k = next(i for i in range(2, 11) if alone[1][i] not in alone[1][:i])
    eng = _engine(config, params, max_slots=2)
    outs = [[] for _ in jobs]
    for (p, n), out, kw in zip(jobs, outs, ({}, {"eos": alone[1][k]}, {})):
        eng.submit(p, n, out.append, **kw)
    while eng.step():
        pass
    assert outs[0] == alone[0] + [None]
    assert outs[1] == alone[1][:k + 1] + [None]
    assert outs[2] == alone[2] + [None]
    s = eng.stats
    assert s["rows_run_past_end"] == 1
    assert s["steps_dispatched_ahead"] == s["steps"] - 1
    # dropless: every position fed ran top-2 experts in each of 4 layers,
    # the row run past its end among them
    assert s["step_positions_real"] == 37 + 8 + 70 + k + 1 + 5 + 14
    assert s["moe_expert_tokens_sum"] == 2 * 4 * s["step_positions_real"]
    kv = eng.kv_state()
    assert kv["inflight"] == 0 and eng._inflight is None
    assert kv["kv_free"] + kv["prefix"]["nodes"] == kv["kv_total"]
    assert kv["prefix"]["nodes"] == 37 // 8 + 70 // 8


def _wipe_ki_of(eng, blocks):
    """What a path that carried K and V and left ``ki`` behind would leave:
    the indexer keys of ``blocks`` as the pool was created (zeros)."""
    ids = jnp.asarray(list(blocks), jnp.int32)
    eng._cache = {**eng._cache,
                  "ki": eng._cache["ki"].at[:, ids].set(0.0)}


def test_a_prefix_hit_and_a_copy_on_write_tail_bring_the_indexer_keys(
        reference, config, params):
    """Second request over a cached prefix that ends INSIDE a block: full
    blocks come from the trie, the tail block is copied before it is
    written. Both must bring ``ki`` with K and V."""
    shared = _prompt(3, 60)              # 7 full blocks + 4 tokens
    first, second = shared + _prompt(4, 12), shared + _prompt(5, 14)

    def serve_both(break_ki):
        eng = _engine(config, params)
        _serve(eng, first, 4)
        if break_ki:
            _wipe_ki_of(eng, range(eng.pool.num_blocks))
        hits0 = eng.stats["prefix_hit_tokens"]
        toks, logits = _serve(eng, second, 8)
        assert eng.stats["prefix_hit_tokens"] - hits0 >= 56
        seq = second + toks[:-1]
        want = _reference_logits(reference, params, config, seq,
                                 np.arange(len(second) - 1, len(seq)))
        return _rel(logits, want)

    assert serve_both(False) < TOL
    assert serve_both(True) > 100 * TOL       # ki left behind: it shows


def test_cold_and_warm_serves_agree_bit_for_bit(config, params):
    """The engine agrees with itself: a prompt that ends on a block boundary
    served cold (its last tokens a full chunk row) and again warm (all but
    one token a prefix hit, the last one a single-token row). The sampled
    position takes the gather form both times, so the logits are equal bit
    for bit, not only the tokens."""
    eng = _engine(config, params)
    prompt = _prompt(8, 64)                   # 8 blocks of 8, 8 chunks of 8
    cold_tokens, cold = _serve(eng, prompt, 6)
    hits0 = eng.stats["prefix_hit_tokens"]
    warm_tokens, warm = _serve(eng, prompt, 6)
    assert eng.stats["prefix_hit_tokens"] - hits0 == 63
    assert cold_tokens == warm_tokens
    assert np.array_equal(cold, warm)


def _cut_to(config, params, cache, n):
    """The model and its cache cut to their first ``n`` layers."""
    cut = lambda a: a[:n]
    return (config.replace(n_layers=n),
            {**params, "layers": jax.tree.map(cut, params["layers"])},
            jax.tree.map(cut, cache))


@pytest.mark.parametrize("step", ["decode_step_paged", "verify_step_paged"])
@pytest.mark.parametrize("model", ["dense", "indexer"])
def test_a_step_writes_its_own_rows_of_its_own_layer_and_nothing_else(
        config, params, model, step):
    """The step holds every layer's pool in ONE buffer flattened over
    layers, so a row sent to ``n_blocks * bs`` (out of bounds for one
    layer's pool) would be the first row of the next layer's. One step with
    a chunk row, a token row with padding behind it, a parked slot and a
    row with no token: every row of every pool of every layer that is not a
    valid token's destination keeps its bits, and a destination of layer
    ``l`` holds what the same step writes as the LAST layer of the model cut
    to ``l + 1`` layers (cut to one layer the flattened pool IS the layer's
    pool), to float32's last digits, which differs from layer to layer."""
    if model == "dense":
        config = models.get_config("llama-debug").replace(
            n_layers=3, dtype="float32", param_dtype="float32")
        params = models.init_params(jax.random.PRNGKey(2), config)
    n_blocks, bs, chunk = 12, 8, 8
    key = jax.random.PRNGKey(7)
    cache = {n: jax.random.normal(jax.random.fold_in(key, i), p.shape)
             for i, (n, p) in enumerate(
                 models.init_cache_paged(config, n_blocks, bs).items())}
    # block 0 belongs to the parked slot: the row after a layer's last is
    # block 0, offset 0 of the next layer
    tables = jnp.array([[5, 2, 11, 7], [3, 9, 10, 6], [0, 1, 4, 8],
                        [8, 4, 1, 0]], jnp.int32)
    pos = jnp.array([20, 21, 9, 30], jnp.int32)
    nvalid = jnp.array([8, 1, 3, 0], jnp.int32)
    active = jnp.array([True, True, False, True])
    tokens = jnp.asarray(np.random.default_rng(3).integers(
        0, 256, (4, chunk)), jnp.int32)
    fn = getattr(models, step)

    def run(config, params, cache):
        return jax.jit(lambda c: fn(params, c, tokens, tables, pos, nvalid,
                                    config, active=active)[1])(cache)

    new = run(config, params, cache)
    assert set(new) == set(cache)
    written = np.zeros((n_blocks, bs), bool)
    for b in (0, 1):                          # the two rows that feed tokens
        for p in range(int(pos[b]), int(pos[b] + nvalid[b])):
            written[int(tables[b, p // bs]), p % bs] = True
    assert written.sum() == 9 and not written[0].any()
    for name in cache:
        assert new[name].shape == cache[name].shape
        # (token by token: the indexer's keys lie several a stored row)
        by_token = lambda p: np.asarray(p).reshape(
            p.shape[0], n_blocks, bs, -1)
        got, was = by_token(new[name]), by_token(cache[name])
        assert np.array_equal(got[:, ~written], was[:, ~written]), name
        for layer in range(config.n_layers):
            own = run(*_cut_to(config, params, cache, layer + 1))[name]
            # float32, the same sums: a scan of another length fuses
            # otherwise and moves the last digits
            assert np.allclose(got[layer][written],
                               by_token(own)[layer][written],
                               atol=1e-5, rtol=1e-5), (name, layer)
            assert not np.array_equal(got[layer][written], was[layer][written])
            if layer:
                assert not np.allclose(got[layer][written],
                                       got[layer - 1][written], atol=1e-3)
    # both public steps are one program over the pools
    other = getattr(models, "verify_step_paged" if step == "decode_step_paged"
                    else "decode_step_paged")
    twin = jax.jit(lambda c: other(params, c, tokens, tables, pos, nvalid,
                                   config, active=active)[1])(cache)
    for name in cache:
        assert np.array_equal(np.asarray(twin[name]), np.asarray(new[name]))


#: (tokens fed a row, rows active, budget) of one step of four rows of eight
#: positions: which rows hold what, and whether the real positions fit
_BUDGET_STEPS = {
    # a full chunk, a decode row, a parked slot, a row with no token: 9
    # real positions within a budget of 12, with room
    "mixed_within_the_budget": ([8, 1, 3, 0], [True, True, False, True], 12),
    # the same step over a budget of 4: the full width
    "mixed_over_the_budget": ([8, 1, 3, 0], [True, True, False, True], 4),
    # the real positions fill the budget to the last place
    "exactly_the_budget": ([8, 1, 3, 0], [True, True, False, True], 9),
    # a partial chunk, two decode rows and a full chunk: 15, one too many
    "one_over_the_budget": ([5, 1, 8, 1], [True] * 4, 14),
    # every row a full chunk (what ``budget=None`` computes)
    "every_position_real": ([8, 8, 8, 8], [True] * 4, 16),
    # nothing real: the budget's positions are padding and write nothing
    "no_real_position": ([0, 0, 4, 0], [True, True, False, True], 8),
}


@pytest.mark.parametrize("case", list(_BUDGET_STEPS))
@pytest.mark.parametrize("model", ["dense", "indexer"])
def test_a_budget_changes_nothing_a_row_gets_back(config, params, model,
                                                  case):
    """``decode_step_paged(budget=...)`` computes the step's real positions,
    gathered to the front, and must give what ``budget=None`` gives: the logits of every
    row that fed a token, every bit of the three pools, and the tokens each
    expert of each layer got (padding is routed nowhere in either). Float32
    on the CPU: a row's sums are the same sums, so the two agree to the bit
    but for the experts' matmuls, grouped over other rows."""
    if model == "dense":
        config = models.get_config("qwen2-debug").replace(
            n_layers=3, dtype="float32", param_dtype="float32")
        params = models.init_params(jax.random.PRNGKey(2), config)
    n_blocks, bs, chunk = 12, 8, 8
    nvalid, active, budget = _BUDGET_STEPS[case]
    key = jax.random.PRNGKey(7)
    cache = {n: jax.random.normal(jax.random.fold_in(key, i), p.shape)
             for i, (n, p) in enumerate(
                 models.init_cache_paged(config, n_blocks, bs).items())}
    tables = jnp.array([[5, 2, 11, 7], [3, 9, 10, 6], [0, 1, 4, 8],
                        [8, 4, 1, 0]], jnp.int32)
    # rows 0, 1 and 3 are past the indexer's 16 keys, row 2 is not
    pos = jnp.array([20, 21, 9, 17], jnp.int32)
    tokens = jnp.asarray(np.random.default_rng(3).integers(
        0, 256, (4, chunk)), jnp.int32)

    def run(budget):
        return jax.jit(lambda c: models.decode_step_paged(
            params, c, tokens, tables, pos, jnp.array(nvalid, jnp.int32),
            config, active=jnp.array(active), step_stats=True,
            budget=budget))(cache)

    want, got = run(None), run(budget)
    fed = [i for i in range(4) if active[i] and nvalid[i]]
    assert np.allclose(np.asarray(got[0])[fed], np.asarray(want[0])[fed],
                       atol=1e-5, rtol=1e-5)
    if model == "dense":
        assert np.array_equal(np.asarray(got[0])[fed],
                              np.asarray(want[0])[fed])
    for name in cache:
        assert np.allclose(got[1][name], want[1][name], atol=1e-6,
                           rtol=1e-6), name
    assert set(got[2]) == set(want[2])
    for name in want[2]:
        assert np.array_equal(got[2][name], want[2][name]), name
    if model == "indexer":
        real = sum(n for n, a in zip(nvalid, active) if a)
        assert int(got[2]["expert_tokens"].sum()) \
            == real * config.expert_top_k * config.n_layers


def test_a_budget_is_refused_where_every_position_is_read():
    """``verify_step_paged`` returns logits at every fed position and has no
    budget; the implementation refuses the two together."""
    from ray_tpu.models import transformer as T

    config = models.get_config("llama-debug")
    params = models.init_params(jax.random.PRNGKey(0), config)
    cache = models.init_cache_paged(config, 4, 4)
    z = jnp.zeros((2,), jnp.int32)
    with pytest.raises(ValueError, match="budget=3"):
        T._step_paged_impl(params, cache, jnp.zeros((2, 4), jnp.int32),
                           jnp.zeros((2, 2), jnp.int32), z, z, config,
                           all_logits=True, budget=3)


def test_copy_kv_block_gather_and_scatter_carry_every_pool(config):
    cache = models.init_cache_paged(config, 6, 8)
    assert set(cache) == {"k", "v", "ki"}
    # eight keys of 16 are one row of the 128 lanes
    assert cache["ki"].shape == (4, 6, 1, 8 * config.index_head_dim)
    key = jax.random.PRNGKey(0)
    cache = {n: jax.random.normal(jax.random.fold_in(key, i), p.shape)
             for i, (n, p) in enumerate(cache.items())}
    copied = models.copy_kv_block(cache, 1, 4)
    got = models.gather_kv_blocks(cache, [3, 1])
    put = models.scatter_kv_blocks(cache, jnp.array([5, 6]), got)  # 6: OOB
    for name, pool in cache.items():
        assert np.array_equal(copied[name][:, 4], pool[:, 1])
        assert np.array_equal(got[name][:, 0], pool[:, 3])
        assert np.array_equal(put[name][:, 5], pool[:, 3])
        assert np.array_equal(put[name][:, :5], pool[:, :5])


def test_export_and_adoption_round_trip_brings_the_indexer_keys(
        reference, config, params):
    """Disaggregated serving: prefill on one engine, its blocks shipped
    (``pack_export`` / ``unpack_payload``) and adopted by another, decode
    there against the reference; a payload without ``ki`` is refused."""
    prompt = _prompt(6, 75)
    exports = []
    pre = _engine(config, params, role="prefill")
    pre.submit(prompt, 1, exports.append, prefill_only=True)
    while pre.step():
        pass
    export = exports[0]
    assert set(export.kv) == {"k", "v", "ki"}
    meta, arr = pack_export(export)
    assert arr.flags["C_CONTIGUOUS"] and arr.shape[0] == meta["n_blocks"]
    kv = unpack_payload(meta, arr)
    for name in export.kv:
        assert np.array_equal(kv[name], export.kv[name])

    dec = _engine(config, params, role="decode")
    toks, logits, sample = [], [], dec._sample

    def capture(row):
        logits.append(row.copy())
        return sample(row)

    dec._sample, dec.capture = capture, True
    dec.adopt(prompt, kv, export.token, 10,
              lambda item: toks.append(item) if isinstance(item, int)
              else None)
    while dec.step():
        pass
    assert toks[0] == export.token and len(toks) == 10
    seq = prompt + toks[:-1]
    want = _reference_logits(reference, params, config, seq,
                             np.arange(len(prompt), len(seq)))
    assert _rel(np.stack(logits), want) < TOL
    with pytest.raises(ValueError, match="lacks the 'ki' pool"):
        dec.adopt(prompt, {"k": kv["k"], "v": kv["v"]}, export.token, 4,
                  lambda item: None)


@pytest.mark.parametrize("max_len", [128, TOPK],
                         ids=["table_past_topk", "table_within_topk"])
def test_contexts_of_at_most_topk_take_the_dense_attention_bit_for_bit(
        config, params, max_len):
    """Rows whose context is at most ``topk`` keys select everything, so
    they take ``paged_attention`` itself: the logits equal, bit for bit,
    those of the same weights in a model WITHOUT the indexer. An engine
    whose table cannot hold more than ``topk`` keys has no row that
    selects (and nothing to take a top ``topk`` of)."""
    dense = dataclasses.replace(config, index_heads=0)
    dense_params = {**params, "layers": {
        k: v for k, v in params["layers"].items()
        if k not in ("wq_i", "wk_i", "w_i", "ki_norm", "ki_norm_b")}}
    prompt = _prompt(7, 10)

    def logits_of(cfg, p):
        return _serve(_engine(cfg, p, prefill_chunk=4, max_len=max_len),
                      prompt, 6)[1]

    sparse, plain = logits_of(config, params), logits_of(dense, dense_params)
    assert prompt and len(prompt) + 6 <= TOPK
    assert np.array_equal(sparse, plain)


# -- the selection -----------------------------------------------------------

def test_select_top_k_is_lax_top_k_with_its_ties():
    rng = np.random.default_rng(0)
    for trial in range(12):
        k = int(rng.integers(1, 60))
        s = rng.normal(size=(5, 200)).astype(np.float32)
        s[rng.random(s.shape) < 0.3] = 0.0            # exact ties (relu)
        s[rng.random(s.shape) < 0.2] = -np.inf        # not causal
        s[0] = np.round(s[0])
        s[1] = -np.inf
        s[1, :max(1, k // 2)] = 1.0                   # fewer than k exist
        got = np.asarray(select_top_k(jnp.asarray(s), k))
        _, idx = jax.lax.top_k(jnp.asarray(s), k)
        want = np.zeros(s.shape, bool)
        want[np.arange(5)[:, None], np.asarray(idx)] = True
        finite = np.isfinite(s)
        assert np.array_equal(got & finite, want & finite), trial
        assert got.sum(axis=1).max() <= k


def test_a_row_attends_alike_as_a_chunk_row_and_as_token_rows():
    """One function, two forms: the masked form a chunk row takes against
    the gather form single-token rows take, same pools, same queries."""
    b, c, h, kvh, hd, j, di, bs, m = 1, 8, 4, 2, 16, 4, 8, 4, 12
    keys = jax.random.split(jax.random.PRNGKey(3), 6)
    pool = lambda k, *tail: jax.random.normal(k, (m + 2, bs) + tail)
    k_pool, v_pool, ki_pool = (pool(keys[0], kvh, hd), pool(keys[1], kvh, hd),
                               pool(keys[2], di))
    q = jax.random.normal(keys[3], (b, c, h, hd))
    qi = jax.random.normal(keys[4], (b, c, j, di))
    w = jax.random.normal(keys[5], (b, c, j))
    tables = jnp.arange(m, dtype=jnp.int32)[None] + 1
    pos, topk = 30, 9
    chunk = paged_sparse_attention(
        q, qi, w, k_pool, v_pool, ki_pool, tables, jnp.array([pos]),
        jnp.array([c]), topk=topk, scale=hd ** -0.5)
    for t in range(c):
        one = paged_sparse_attention(
            q[:, t:t + 1], qi[:, t:t + 1], w[:, t:t + 1], k_pool, v_pool,
            ki_pool, tables, jnp.array([pos + t]), jnp.array([1]),
            topk=topk, scale=hd ** -0.5)
        np.testing.assert_allclose(one[0, 0], chunk[0, t], atol=2e-6)


# -- the expert layer --------------------------------------------------------

def _expert_weights(e=8, d=32, f=16):
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    return (jax.random.normal(ks[0], (d, e)),
            jax.random.normal(ks[1], (e, d, f)) * d ** -0.5,
            jax.random.normal(ks[2], (e, d, f)) * d ** -0.5,
            jax.random.normal(ks[3], (e, f, d)) * f ** -0.5)


@pytest.mark.parametrize("others", [1, 15])
def test_a_tokens_experts_do_not_depend_on_who_shares_its_step(others):
    """The capacity bug: with ``moe_layer_dense`` a token in a full step
    loses experts to its neighbours. Dropless, it gets the same output
    alone, beside 1 other row and beside 15, and exactly k experts."""
    weights = _expert_weights()
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 32))
    alone, counts = moe.moe_layer_dropless(x[:1], *weights, k=2,
                                           norm_topk=True)
    assert int(counts.sum()) == 2
    shared, counts = moe.moe_layer_dropless(x[:1 + others], *weights, k=2,
                                            norm_topk=True)
    assert int(counts.sum()) == 2 * (1 + others)
    np.testing.assert_allclose(shared[0], alone[0], rtol=1e-6, atol=1e-6)


def test_dropless_layer_is_the_plain_sum_and_padding_is_routed_nowhere():
    router, w_gate, w_up, w_down = _expert_weights()
    x = jax.random.normal(jax.random.PRNGKey(2), (12, 32))
    valid = jnp.arange(12) % 3 != 2
    out, counts = moe.moe_layer_dropless(x, router, w_gate, w_up, w_down,
                                         k=2, norm_topk=True, valid=valid)
    probs = jax.nn.softmax(x @ router, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, 2)
    top_p = top_p / top_p.sum(-1, keepdims=True)
    want = np.zeros((12, 32), np.float32)
    for t in range(12):
        for p, e in zip(np.asarray(top_p[t]), np.asarray(top_e[t])):
            hmid = jax.nn.silu(x[t] @ w_gate[e]) * (x[t] @ w_up[e])
            want[t] += p * np.asarray(hmid @ w_down[e])
    want[~np.asarray(valid)] = 0.0
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-6)
    assert int(counts.sum()) == 2 * int(valid.sum())
    assert np.array_equal(
        counts, np.bincount(np.asarray(top_e)[np.asarray(valid)].ravel(),
                            minlength=8))


# -- the configuration's arithmetic ------------------------------------------

def test_flops_per_token_counts_the_experts_a_token_uses(config):
    dense_like = config.replace(num_experts=0, index_heads=0, qk_norm=False)
    d, f = config.d_model, config.ff
    per_layer_active = (dense_like.num_params()
                        - 2 * config.vocab_size * d - d) // config.n_layers
    idx = d * (4 * 16 + 16 + 4) + 2 * 16 + 2 * config.hdim
    want = config.n_layers * (per_layer_active - 3 * d * f       # one MLP ...
                              + 2 * 3 * d * f + d * 8 + idx) + d  # ... top-2
    assert config.flops_per_token() == 6 * want
    assert config.num_params() - config.active_params() \
        == config.n_layers * 6 * 3 * d * f
    with pytest.raises(NotImplementedError, match="paged serve step only"):
        models.forward(models.init_params(jax.random.PRNGKey(0), config),
                       jnp.zeros((1, 4), jnp.int32), config)
