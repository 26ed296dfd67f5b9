"""The windowed MoE layout as SmallThinker has it (``smallthinker-debug``:
``expert_act="relu"``, ``router_input="attn_norm"``) on the serve path, at a
small size on the CPU (eight layers, full / sliding x 3 twice, a window of 8
tokens; plain GQA attention; 8 ReLU-gated experts top-3 held whole, routed
from the layer's normed INPUT ahead of its attention; ``d_model`` 384, whole
lanes and not whole ``[8, 128]`` tiles): the paged step and the engine against
the benchmark's plain reference (``benchmark/reference/preroute_moe_decoder.py``:
one float32 pass over the whole sequence, no cache, no table), the route made
ahead against ``route_top_k`` on the same tensor, the split of
``moe_layer_dropless`` against the one function it was, each fault the chip's
check must catch, and the configuration's new refusals."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import assert_three_widths, watch_step_widths
from test_windowed_moe_serve import _engine, _prompt, _rel, _serve_all

from benchmark import manifest
from ray_tpu import models
from ray_tpu.models import transformer
from ray_tpu.models.config import TransformerConfig
from ray_tpu.ops import moe

REF_LEN = 128
WINDOW = 8
#: float32 on both sides: what is left is the order of the sums (a paged
#: gather through two tables, a grouped matmul over sorted pairs, against
#: one pass over every expert); the toy reads 1e-6 to 2e-6
TOL = 1e-4
#: bfloat16 weights, activations and KV pools against the float32
#: reference: the toy reads about 0.02; a fault reads 0.25 and more
TOL_BF16 = 0.06


@pytest.fixture(scope="module")
def reference():
    return manifest.load_module(
        manifest.reference_path("preroute_moe_decoder"))


@pytest.fixture(scope="module")
def config():
    return models.get_config("smallthinker-debug").replace(
        dtype="float32", param_dtype="float32")


@pytest.fixture(scope="module")
def params(config):
    return models.init_params(jax.random.PRNGKey(0), config)


def _config_file(c):
    """The published keys the reference reads, from a ``TransformerConfig``."""
    sliding = [int(w > 0) for w in c.layer_windows]
    return {"rms_norm_eps": c.norm_eps, "rope_theta": c.rope_theta,
            "num_attention_heads": c.n_heads,
            "num_key_value_heads": c.kv_heads, "head_dim": c.hdim,
            "num_hidden_layers": c.n_layers,
            "sliding_window_layout": sliding, "rope_layout": sliding,
            "sliding_window_size": c.sliding_window,
            "moe_num_active_primary_experts": c.expert_top_k,
            "norm_topk_prob": c.expert_norm_topk,
            "moe_primary_router_apply_softmax": True, "rope_scaling": None,
            "tie_word_embeddings": False}


def _against_reference(reference, params, config, prompt, toks, logits,
                       **kw):
    seq = prompt + toks[:-1]
    padded = np.zeros(REF_LEN, np.int32)
    padded[:len(seq)] = seq
    want = np.asarray(reference.logits_at(
        params, padded, np.arange(len(prompt) - 1, len(seq)),
        _config_file(config), **kw))
    return _rel(logits, want)


# -- the step and the engine against the reference ---------------------------

@pytest.mark.parametrize("chunk,budget", [
    (1, None), (3, None), (8, None), (16, None), (8, 5)],
    ids=["1", "3", "8", "16", "8-budget_5"])
def test_engine_prefill_then_decode_matches_the_reference(
        reference, config, params, chunk, budget, monkeypatch):
    """Rows at different depths in one step, through BOTH pools and all four
    runs of layers: six requests through four slots, prompts from under one
    window (5 tokens) to nine windows (70); prefill through chunks of 1, 3, 8
    and 16 positions (a chunk wider than the window too), then decode. With
    a budget of 5 of 4 x 8 positions the route is made at one of three widths
    and crosses the attention in the stream's order, as narrow as the stage
    that made it."""
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
    positions = sum(len(p) + n - 1 for p, n in requests)
    assert s["step_positions_real"] == positions
    if budget:
        assert_three_widths(eng, reals)
    else:
        assert s["steps_full_width"] == s["steps_second_width"] == 0
    # every pair is here
    pairs = positions * config.expert_top_k * config.n_layers
    assert s["moe_pairs_routed"] == s["moe_pairs_held"] \
        == s["moe_expert_tokens_sum"] == pairs
    # window blocks released and none leaked, in either pool
    assert s["prefix_hit_tokens"] == 0 and len(eng.prefix) == 0
    assert s["window_blocks_released"] > 0
    assert s["window_blocks_held"] < s["window_blocks_full_table"]
    kv = eng.kv_state()
    assert kv["kv_free"] == kv["kv_total"] \
        == eng.pool.num_blocks + eng.win_pool.num_blocks
    assert kv["kv_pools"]["window"] == {
        "total": eng.win_pool.num_blocks, "free": eng.win_pool.num_blocks,
        "reserved": 0}


def test_a_grid_wider_than_the_budget_runs_the_ordered_stream(
        reference, config, params):
    """16 slots x 32 positions pass the 256-position budget: the step gathers
    the real positions to the front of one flat stream, and the route (its
    weights, experts and sorted order a position, its counts beside them)
    rides it from the stage before the attention to the stage after."""
    eng = _engine(config, params, max_slots=16, prefill_chunk=32,
                  max_len=96)
    requests = [(_prompt(40 + i, 33 + i), 6) for i in range(10)]
    served = _serve_all(eng, requests)
    for (prompt, n), (toks, logits) in zip(requests, served):
        assert _against_reference(reference, params, config, prompt, toks,
                                  logits) < TOL
    assert eng.stats["steps_full_width"] >= 1
    assert eng.stats["steps_decode_only"] >= 1
    assert eng.stats["moe_pairs_held"] == eng.stats["moe_pairs_routed"]


def test_engine_in_bfloat16_stays_inside_its_tolerance(reference, config):
    c16 = config.replace(dtype="bfloat16", param_dtype="bfloat16")
    p16 = models.init_params(jax.random.PRNGKey(0), c16)
    eng = _engine(c16, p16)
    prompt = _prompt(3, 37)
    (toks, logits), = _serve_all(eng, [(prompt, 24)])
    err = _against_reference(reference, p16, c16, prompt, toks, logits)
    assert 1e-4 < err < TOL_BF16
    assert {v.dtype for v in eng._cache.values()} == {jnp.dtype("bfloat16")}


@pytest.mark.parametrize("control", [
    "route_post_attention", "silu", "rope_in_full", "no_rope",
    "window_short_a_block", "no_norm_topk"])
def test_each_fault_the_chips_check_must_catch_shows_here_too(
        reference, config, params, control, monkeypatch):
    """The engine against the reference with one piece of the mathematics
    moved: the route read from ``post_attention_layernorm``'s output, SiLU in
    ReLU's place, RoPE in a full layer (or in none), the window's edge a
    block short, the chosen weights not renormalised: each reads far over
    the tolerance, so a program that had the fault would."""
    monkeypatch.setattr(reference, "WINDOW_BLOCK", 4)
    eng = _engine(config, params)
    prompt = _prompt(7, 45)
    (toks, logits), = _serve_all(eng, [(prompt, 16)])
    assert _against_reference(reference, params, config, prompt, toks,
                              logits) < TOL
    assert _against_reference(reference, params, config, prompt, toks,
                              logits, weights=control) > 100 * TOL


def test_the_reference_refuses_what_it_does_not_describe(reference, config,
                                                         params):
    cf = _config_file(config)
    with pytest.raises(ValueError, match="unknown control"):
        reference.logits_at(params, np.zeros(8, np.int32), [0], cf,
                            weights="no_such")
    with pytest.raises(NotImplementedError):
        reference.hyper({**cf, "moe_primary_router_apply_softmax": False})


# -- the route ---------------------------------------------------------------

def _old_moe_layer_dropless(x, router_w, w_gate, w_up, w_down, *, k,
                            norm_topk=False, valid=None, layer=None,
                            scoring="softmax", bias=None, scale=1.0,
                            first=None):
    """``ops.moe.moe_layer_dropless`` as it was before the route and the
    experts' sum came apart (its ``jax.numpy`` form, which every CPU run
    takes), to the letter."""
    t, d = x.shape
    e = router_w.shape[-1]
    top_p, top_e = moe.route_top_k(x, router_w, k=k, norm_topk=norm_topk,
                                   scoring=scoring, bias=bias, scale=scale)
    if first is not None:
        e = w_gate.shape[-3]
        top_e = top_e - first
        top_e = jnp.where((top_e >= 0) & (top_e < e), top_e, e)
    if valid is not None:
        top_e = jnp.where(valid[:, None], top_e, e)
    flat_e = top_e.reshape(t * k)
    order = jnp.argsort(flat_e, stable=True)
    counts = jnp.zeros((e + 1,), jnp.int32).at[flat_e].add(1)[:e]
    if layer is not None:
        w_gate, w_up, w_down = (w.reshape(-1, *w.shape[2:])
                                for w in (w_gate, w_up, w_down))
    groups = counts
    if layer is not None:
        groups = jax.lax.dynamic_update_slice(
            jnp.zeros((w_gate.shape[0],), jnp.int32), counts, (layer * e,))
    xs = x[order // k]
    gate = jax.lax.ragged_dot(xs, w_gate, groups,
                              preferred_element_type=jnp.float32)
    up = jax.lax.ragged_dot(xs, w_up, groups,
                            preferred_element_type=jnp.float32)
    mid = (jax.nn.silu(gate) * up).astype(x.dtype)
    down = jax.lax.ragged_dot(mid, w_down, groups,
                              preferred_element_type=jnp.float32)
    routed = jnp.arange(t * k) < jnp.sum(counts)
    down = jnp.where(routed[:, None], down, 0.0)
    pairs = down[jnp.argsort(order)].reshape(t, k, d)
    out = jnp.sum(pairs * top_p[:, :, None], axis=1)
    return out.astype(x.dtype), counts


@pytest.mark.parametrize("preset", [
    "windowed-moe-debug", "latent-moe-debug", "sparse-moe-debug"])
def test_the_split_gives_what_the_one_function_gave_to_the_bit(preset):
    """``moe_layer_dropless`` is the composition of ``moe_route`` and
    ``moe_experts``: on a preset's own expert shapes and routing (a share of
    sigmoid-routed experts with a bias and a scale, whole stacks and a
    traced layer; or softmax, every expert, renormalised), with padding rows,
    it returns the bits the one function returned."""
    c = models.get_config(preset)
    d, f, e_all, e = c.d_model, c.ff_expert, c.num_experts, c.held_experts
    rng = np.random.default_rng(e_all + d)
    t, layers = 24, 3
    x = jnp.asarray(rng.normal(size=(t, d)), jnp.bfloat16)
    router = jnp.asarray(rng.normal(size=(d, e_all)) * d ** -0.5, jnp.float32)
    wg, wu = (jnp.asarray(rng.normal(size=(layers, e, d, f)) * d ** -0.5,
                          jnp.bfloat16) for _ in range(2))
    wd = jnp.asarray(rng.normal(size=(layers, e, f, d)) * f ** -0.5,
                     jnp.bfloat16)
    kw = dict(k=c.expert_top_k, norm_topk=c.expert_norm_topk,
              valid=jnp.arange(t) < 19, layer=jnp.int32(1))
    if c.expert_share:
        kw.update(scoring=c.expert_scoring, scale=c.expert_scale,
                  first=c.experts_first,
                  bias=jnp.asarray(rng.normal(size=e_all) * 0.1,
                                   jnp.bfloat16))
    want, want_counts = jax.jit(
        lambda *a: _old_moe_layer_dropless(*a, **kw))(x, router, wg, wu, wd)
    got, counts = jax.jit(
        lambda *a: moe.moe_layer_dropless(*a, **kw))(x, router, wg, wu, wd)
    assert np.asarray(counts).tolist() == np.asarray(want_counts).tolist()
    assert 0 < int(counts.sum()) <= 19 * c.expert_top_k
    assert np.array_equal(np.asarray(got, np.float32),
                          np.asarray(want, np.float32))
    assert not np.asarray(got, np.float32)[19:].any()


def test_the_route_made_ahead_is_route_top_k_on_the_same_tensor(config,
                                                                params):
    """``moe_route`` over the layer's normed input: its weights and experts
    are ``route_top_k``'s on that tensor, its order sorts the pairs by
    expert with padding last, its counts count them; and ``moe_experts``
    along it over ANOTHER tensor is the sum written out pair by pair."""
    lp = jax.tree.map(lambda w: w[2], params["layers"]["moe"])
    rng = np.random.default_rng(5)
    t, k, e = 14, config.expert_top_k, config.num_experts
    h = jnp.asarray(rng.normal(size=(t, config.d_model)), jnp.float32)
    valid = jnp.arange(t) < 11
    route = moe.moe_route(h, lp["router"], k=k, norm_topk=True, valid=valid,
                          first=0, held=e)
    top_p, top_e = moe.route_top_k(h, lp["router"], k=k, norm_topk=True)
    assert np.array_equal(route.weights, top_p)
    assert np.array_equal(np.asarray(route.experts)[:11],
                          np.asarray(top_e)[:11])
    assert (np.asarray(route.experts)[11:] == e).all()
    by_expert = np.asarray(route.experts).reshape(-1)[np.asarray(route.order)]
    assert (np.diff(by_expert) >= 0).all()
    assert np.asarray(route.counts).tolist() == np.bincount(
        np.asarray(top_e)[:11].reshape(-1), minlength=e).tolist()
    # the experts multiply u, a tensor the router never saw
    u = jnp.asarray(rng.normal(size=(t, config.d_model)), jnp.float32)
    got = moe.moe_experts(u, route, lp["w_gate"], lp["w_up"], lp["w_down"],
                          act="relu")
    want = np.zeros((t, config.d_model), np.float32)
    for i in range(11):
        for p, j in zip(np.asarray(top_p)[i], np.asarray(top_e)[i]):
            g = np.maximum(np.asarray(u[i] @ lp["w_gate"][j]), 0.0)
            want[i] += p * np.asarray(
                (g * np.asarray(u[i] @ lp["w_up"][j])) @ lp["w_down"][j])
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-5)


def test_the_steps_route_reads_the_layers_normed_input(reference, config,
                                                       params):
    """The same weights served with ``router_input="mlp_norm"`` (the route
    made where every other expert model makes it) are another model: its
    logits are the reference's ``route_post_attention`` control, not the
    reference."""
    base = config.replace(router_input="mlp_norm")
    assert models.layout_of(base) is models.layout_of(config)
    eng = _engine(base, params)
    prompt = _prompt(7, 45)
    (toks, logits), = _serve_all(eng, [(prompt, 8)])
    assert _against_reference(reference, params, config, prompt, toks,
                              logits) > 100 * TOL
    assert _against_reference(reference, params, config, prompt, toks,
                              logits, weights="route_post_attention") < TOL


# -- the configuration -------------------------------------------------------

def test_the_new_keys_refusals_by_name(config):
    small = dict(vocab_size=64, d_model=128, n_layers=4, n_heads=2,
                 n_kv_heads=1, head_dim=16, d_ff=32, num_experts=4,
                 expert_top_k=2, remat=False)
    with pytest.raises(ValueError, match="expert_act 'gelu'"):
        TransformerConfig(**small, expert_act="gelu")
    with pytest.raises(ValueError, match="router_input 'embed'"):
        TransformerConfig(**small, router_input="embed")
    with pytest.raises(ValueError, match="beside a shared expert"):
        TransformerConfig(**small, expert_act="relu", shared_experts=1,
                          d_ff_expert=32)
    with pytest.raises(ValueError, match="expert_act, router_input"):
        TransformerConfig(**small, expert_act="relu", kv_lora_rank=8)
    with pytest.raises(ValueError, match="expert_act, router_input"):
        models.get_config("hybrid-state-debug").replace(
            router_input="attn_norm")
    with pytest.raises(ValueError, match="needs experts"):
        TransformerConfig(**{**small, "num_experts": 0},
                          router_input="attn_norm")
    # either key alone makes a uniform GQA decoder with experts this layout
    for kw in ({"expert_act": "relu"}, {"router_input": "attn_norm"}):
        c = TransformerConfig(**small, **kw)
        assert c.windowed_moe and not c.window_pool
        assert models.layout_of(c).name == "windowed_moe"
    assert not TransformerConfig(**small).windowed_moe


def test_the_presets_parameters_are_counted_as_they_are_drawn(config, params):
    assert sum(x.size for x in jax.tree.leaves(params)) \
        == config.num_params() == 10_254_720
    assert config.active_params() == config.num_params() - 8 * 5 * 3 * 384 * 128
    assert set(params["layers"]) == {"moe"}
    assert set(params["layers"]["moe"]) == {
        "attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "router", "w_gate",
        "w_up", "w_down"}
    assert [r.layers for r in transformer._layer_runs(config)] == [1, 3, 1, 3]
