"""The parallel attention / Mamba-2 decoder family (Falcon-H1 layout:
``TransformerConfig.layer_kinds`` all ``"parallel"``) on the serve path, at
a small size on the CPU (three layers, 4 query heads over 2 KV heads beside
4 Mamba-2 heads of 8 in 2 groups of 8 states, every multiplier away from 1):
the paged step and the engine against the benchmark's plain reference
(``benchmark/reference/parallel_hybrid_decoder.py``: one float32 pass over
the whole sequence, the recurrence as a recurrence, no cache), Mamba-2's
block form against its recurrence, the state pool beside the KV blocks with
no window pool, and everything that ships a request refusing this layout."""

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import assert_three_widths, watch_step_widths

from benchmark import manifest
from ray_tpu import models
from ray_tpu.models.import_hf import config_from_hf
from ray_tpu.ops.ssd_step import ssd_step_impl
from ray_tpu.ops.ssm import mamba2_rows, ssd_block, ssd_step
from ray_tpu.serve.llm import LLMEngine
from ray_tpu.util import tracing

REF_LEN = 128
#: float32 on both sides: what is left is the order of the sums (a paged
#: gather against one pass, the block form's products against the scan a
#: position); the toy reads 3e-7 to 2e-6
TOL = 1e-4
#: bfloat16 weights, activations and KV pool (float32 state and scan)
#: against the float32 reference: the toy reads 0.006-0.012 over prompts; a
#: branch or a multiplier left out reads 0.3 and more
TOL_BF16 = 0.03

#: the fixed multipliers, one scalar each: (field, index in a tuple or None)
MULTIPLIERS = [("embedding_multiplier", None), ("lm_head_multiplier", None),
               ("attention_in_multiplier", None),
               ("attention_out_multiplier", None), ("key_multiplier", None),
               ("ssm_in_multiplier", None), ("ssm_out_multiplier", None)] \
    + [("ssm_multipliers", i) for i in range(5)] \
    + [("mlp_multipliers", i) for i in range(2)]


@pytest.fixture(scope="module")
def reference():
    return manifest.load_module(
        manifest.reference_path("parallel_hybrid_decoder"))


@pytest.fixture(scope="module")
def config():
    return models.get_config("parallel-hybrid-debug").replace(
        dtype="float32", param_dtype="float32")


@pytest.fixture(scope="module")
def params(config):
    return models.init_params(jax.random.PRNGKey(0), config)


def _config_file(c):
    """The published keys the reference reads, from a ``TransformerConfig``."""
    return {"rms_norm_eps": c.norm_eps, "num_attention_heads": c.n_heads,
            "num_key_value_heads": c.kv_heads, "head_dim": c.hdim,
            "rope_theta": c.rope_theta, "mamba_n_heads": c.ssm_heads,
            "mamba_d_head": c.ssm_head_dim, "mamba_n_groups": c.ssm_groups,
            "mamba_d_state": c.ssm_state,
            "attention_in_multiplier": c.attention_in_multiplier,
            "attention_out_multiplier": c.attention_out_multiplier,
            "key_multiplier": c.key_multiplier,
            "ssm_in_multiplier": c.ssm_in_multiplier,
            "ssm_out_multiplier": c.ssm_out_multiplier,
            "ssm_multipliers": list(c.ssm_mup),
            "mlp_multipliers": list(c.mlp_mup),
            "embedding_multiplier": c.embedding_multiplier,
            "lm_head_multiplier": c.lm_head_multiplier,
            "tie_word_embeddings": False, "attention_bias": False,
            "mamba_proj_bias": False, "mlp_bias": False,
            "mamba_conv_bias": True, "mamba_rms_norm": True,
            "mamba_norm_before_gate": False, "mamba_use_mlp": True,
            "attn_layer_indices": None, "rope_scaling": None,
            "hidden_act": "silu"}


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


# -- the step and the engine against the reference ---------------------------

@pytest.mark.parametrize("chunk,budget", [
    (1, None), (3, None), (8, None), (16, None), (8, 5)],
    ids=["1", "3", "8", "16", "8-budget_5"])
def test_engine_prefill_then_decode_matches_the_reference(
        reference, config, params, chunk, budget, monkeypatch):
    """Rows of different ages in one step: six requests through four slots
    (two wait, then take a slot another request held: its state starts from
    zero by the ``fresh`` rule), prompts that end inside a chunk and a
    block; prefill through chunks of 1, 3, 8 and 16 positions (the block
    form over blocks of several lengths, tails shorter than a block), then
    decode through the KV blocks and the carried state. Under the
    256-position budget every step of these grids is the step as it was;
    with a budget of 5 of 4 x 8 positions the steps of several chunk rows
    take the whole grid, those of one chunk row or a short tail beside
    decoding rows the second width (10) and the decode steps the budget."""
    if budget:
        monkeypatch.setattr("ray_tpu.serve.llm.STEP_BUDGET", budget)
    eng = _engine(config, params, prefill_chunk=chunk)
    reals = watch_step_widths(eng)
    requests = [(_prompt(10 + i, n), m) for i, (n, m) in enumerate(
        [(5, 20), (23, 12), (40, 30), (9, 9), (31, 5), (17, 40)])]
    served = _serve_all(eng, requests)
    for (prompt, n), (toks, logits) in zip(requests, served):
        assert len(toks) == n
        assert _against_reference(reference, params, config, prompt, toks,
                                  logits) < TOL
    s = eng.stats
    fed = sum(len(p) + n - 1 for p, n in requests)
    assert s["step_positions_real"] == s["ssd_positions_real"] == fed
    if budget:
        assert_three_widths(eng, reals)
    else:
        assert s["steps_full_width"] == s["steps_second_width"] == 0
    assert s["prefix_hit_tokens"] == 0 and len(eng.prefix) == 0
    assert s["attn_impl"] == "xla"
    kv = eng.kv_state()
    assert kv["kv_free"] == kv["kv_total"] == eng.pool.num_blocks
    assert kv["kv_pools"]["state"]["live"] == 0


def test_engine_in_bfloat16_stays_inside_its_tolerance(reference, config):
    c16 = config.replace(dtype="bfloat16", param_dtype="bfloat16")
    p16 = models.init_params(jax.random.PRNGKey(0), c16)
    eng = _engine(c16, p16)
    prompt = _prompt(3, 37)
    toks, logits = _serve(eng, prompt, 24)
    err = _against_reference(reference, p16, c16, prompt, toks, logits)
    assert 1e-4 < err < TOL_BF16
    # the state and the conv's inputs stay float32, the KV pool follows
    assert eng._cache["ssm"].dtype == eng._cache["conv"].dtype == jnp.float32
    assert eng._cache["k"].dtype == jnp.bfloat16


def test_a_grid_wider_than_the_budget_runs_the_ordered_stream(
        reference, config, params):
    """16 slots x 32 positions pass the 256-position budget: the step
    gathers the real positions to the front of one flat stream; ten prompts
    of 33 tokens arriving together make a step of 320 real positions (over
    the budget: the whole grid), and the decode steps run the budget."""
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
    # a prompt is one whole block of 32 and a tail of one token, then five
    # decode rows: one turn each, so nothing is computed for nothing
    assert s["ssd_positions_real"] == 10 * (33 + 5)
    assert s["ssd_positions_run"] == s["ssd_positions_real"]


# -- mathematics left out fails the comparison --------------------------------

@pytest.mark.parametrize("broken", [
    "state_reset", "no_attention", "no_mamba", "group0_for_all",
    "no_key_multiplier", "no_ssm_multipliers", "int8"])
def test_mathematics_left_out_exceeds_the_tolerance(reference, config,
                                                    params, broken):
    """The engine's logits against the reference with one piece of the
    mathematics wrong: the Mamba-2 state dropped between chunks, a branch
    dropped from the residual, every head reading group 0's ``B`` and ``C``,
    the key's or the in-projection's multipliers left at 1 (and weights
    rounded to int8, the benchmark's control). Each reads far over the
    tolerance that the sound comparison keeps."""
    eng = _engine(config, params)
    prompt = _prompt(5, 45)
    toks, logits = _serve(eng, prompt, 16)
    assert _against_reference(reference, params, config, prompt, toks,
                              logits) < TOL
    assert _against_reference(reference, params, config, prompt, toks,
                              logits, weights=broken) > 100 * TOL


@pytest.mark.parametrize("name,index", MULTIPLIERS,
                         ids=[n if i is None else f"{n}[{i}]"
                              for n, i in MULTIPLIERS])
def test_every_multiplier_matters(reference, config, name, index):
    """One layer, one step of eight positions: the program with ONE fixed
    multiplier left at 1 against the reference with all of them as the
    configuration gives them. The sound program agrees to the order of the
    sums; each of the fourteen scalars left out fails the comparison."""
    c = config.replace(n_layers=1, layer_kinds=("parallel",))
    p = models.init_params(jax.random.PRNGKey(2), c)
    seq = np.asarray(_prompt(7, 8))
    want = _reference_logits(reference, p, c, seq, np.arange(8))

    def logits_of(cfg):
        cache = models.init_cache_paged(cfg, 4, 4, state_slots=1)
        out, _ = models.verify_step_paged(
            p, cache, jnp.asarray(seq)[None], jnp.arange(4)[None],
            jnp.array([0]), jnp.array([8]), cfg)
        return np.asarray(out[0])

    if name == MULTIPLIERS[0][0]:
        assert _rel(logits_of(c), want) < TOL
    value = getattr(c, name)
    if index is not None:
        value = tuple(1.0 if i == index else m for i, m in enumerate(value))
    # (the smallest: the step's and C's factors, 0.007-0.03 on eight
    # positions from a zero state; the sound program reads 1e-6)
    assert _rel(logits_of(c.replace(**{name: 1.0 if index is None
                                       else value})), want) > 30 * TOL


@pytest.mark.parametrize("pool", ["ssm", "conv", "k"])
def test_a_cache_that_is_not_written_back_shows(reference, config, params,
                                                pool):
    """The engine itself broken: the scan's state, the conv's inputs or the
    keys zeroed after every step, as a step that did not write them back
    would leave them."""
    def wipe(eng):
        eng._cache = {**eng._cache,
                      pool: jnp.zeros_like(eng._cache[pool])}

    eng = _engine(config, params)
    prompt = _prompt(6, 30)
    toks, logits = _serve(eng, prompt, 12, on_step=wipe)
    assert _against_reference(reference, params, config, prompt, toks,
                              logits) > 100 * TOL


# -- the block form and the recurrence ---------------------------------------

def _mixer_inputs(config, rows, chunk, seed=0):
    c = config
    rng = np.random.default_rng(seed)
    f32 = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    lp = {"conv_w": f32(c.ssm_conv, c.ssm_conv_width) * 0.5,
          "conv_b": f32(c.ssm_conv_width) * 0.1,
          "dt_bias": f32(c.ssm_heads) - 2.0,
          "A_log": jnp.log(jnp.asarray(
              rng.uniform(1, 16, c.ssm_heads), jnp.float32)),
          "D": 1.0 + 0.1 * f32(c.ssm_heads)}
    xbc = f32(rows, chunk, c.ssm_conv_width)
    dt = f32(rows, chunk, c.ssm_heads)
    conv = f32(rows, c.ssm_conv - 1, c.ssm_conv_width)
    # a pool of two layers' states: this layer's rows start at ``rows``
    pool = f32(2 * rows, c.ssm_heads, c.ssm_head_dim, c.ssm_state)
    return xbc, dt, conv, pool, lp


def _mixer(config, xbc, dt, conv, pool, lp, nvalid, fresh):
    c = config
    return mamba2_rows(xbc, dt, conv, pool, xbc.shape[0], lp,
                       jnp.asarray(nvalid), jnp.asarray(fresh),
                       heads=c.ssm_heads, head_dim=c.ssm_head_dim,
                       groups=c.ssm_groups, states=c.ssm_state)


def _a_position_at_a_time(config, xbc, dt, conv, pool, lp, nvalid, fresh):
    """The same rows fed ONE position a call: every call takes the
    one-step form, so this is the recurrence."""
    b, t, _ = xbc.shape
    ys, fresh = [], np.asarray(fresh)
    for i in range(t):
        live = (np.asarray(nvalid) > i).astype(np.int32)
        y, conv, pool = _mixer(config, xbc[:, i:i + 1], dt[:, i:i + 1], conv,
                               pool, lp, live, fresh & (i == 0))
        ys.append(y[:, 0])
    return jnp.stack(ys, axis=1), conv, pool


def test_the_block_form_equals_the_recurrence(config):
    """``ssd_block`` over a block against ``ssd_step`` a position, on a
    state carried in: outputs and the state handed on, two groups whose
    ``B`` and ``C`` differ (a head that read the other group's would not
    agree)."""
    c = config
    g, k, p, n, t = c.ssm_groups, c.ssm_heads // c.ssm_groups, \
        c.ssm_head_dim, c.ssm_state, 12
    rng = np.random.default_rng(1)
    f32 = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    s0, x, bm, cm = f32(g, k, p, n), f32(t, g, k, p), f32(t, g, n), \
        f32(t, g, n)
    delta = jax.nn.softplus(f32(t, g, k) - 1.0)
    a, d_skip = -jnp.exp(f32(g, k) * 0.5), 1.0 + 0.1 * f32(g, k)
    y, s = ssd_block(s0, x, bm, cm, delta, a, d_skip)
    want_s, want_y = s0[None], []
    for i in range(t):
        yi, want_s = ssd_step(want_s, x[i][None], bm[i][None], cm[i][None],
                              delta[i][None], a, d_skip)
        want_y.append(yi[0])
    assert np.allclose(y, jnp.stack(want_y), rtol=1e-5, atol=1e-5)
    assert np.allclose(s, want_s[0], rtol=1e-5, atol=1e-5)
    # the groups differ: swapping them moves the output
    y_swapped, _ = ssd_block(s0, x, bm[:, ::-1], cm[:, ::-1], delta, a,
                             d_skip)
    assert not np.allclose(y, y_swapped, atol=1e-2)


@pytest.mark.parametrize("one_turn", ["jax.numpy", "kernel"])
def test_rows_on_both_forms_in_one_step_agree_with_the_recurrence(
        config, monkeypatch, one_turn):
    """One call of ``mamba2_rows`` over five rows: an idle row (keeps what
    it had), a decode row (one turn), a whole block, a block with a tail of
    padding (5 of 8: a chunk that is not a whole block), and a fresh row in
    a used slot (starts from zero whatever the slot held). Against the same
    rows fed a position at a time, and against other padding. Through
    whichever form of the one-turn update ``mamba2_rows`` chooses: the pass
    over the layer's slots at the toy's 8 states, the kernel that walks the
    live rows (``ops/ssd_step.py``, interpreted) at 128 under the kernel's
    backend."""
    if one_turn == "kernel":
        config = config.replace(ssm_state=128)
        monkeypatch.setenv("RTPU_ATTN_PALLAS_INTERPRET", "1")
        monkeypatch.setattr("ray_tpu.ops.attention._ATTN_IMPL", "pallas")
    assert ssd_step_impl(jnp.float32, config.ssm_head_dim,
                         config.ssm_state) == (
        "pallas" if one_turn == "kernel" else "xla")
    nvalid = [0, 1, 8, 5, 8]
    fresh = [False, False, False, False, True]
    xbc, dt, conv, pool, lp = _mixer_inputs(config, 5, 8)
    y, conv1, pool1 = _mixer(config, xbc, dt, conv, pool, lp, nvalid, fresh)
    want_y, want_conv, want_pool = _a_position_at_a_time(
        config, xbc, dt, conv, pool, lp, nvalid, fresh)
    for r, n in enumerate(nvalid):
        assert np.allclose(y[r, :n], want_y[r, :n], rtol=1e-5, atol=1e-5)
    assert np.allclose(pool1, want_pool, rtol=1e-5, atol=1e-5)
    assert np.array_equal(conv1, want_conv)
    # the other layer's rows of the pool, and the idle row, bit for bit
    assert np.array_equal(pool1[:5], pool[:5])
    assert np.array_equal(pool1[5], pool[5])
    assert np.array_equal(conv1[0], conv[0])
    # padding holds anything: the same state
    _, conv2, pool2 = _mixer(config, xbc.at[3, 5:].set(7.0),
                             dt.at[3, 5:].set(3.0), conv, pool, lp, nvalid,
                             fresh)
    assert np.array_equal(pool2, pool1) and np.array_equal(conv2, conv1)
    # the fresh row: what a zero state would have given
    _, conv3, pool3 = _mixer(config, xbc, dt, conv.at[4].set(0.0),
                             pool.at[9].set(0.0), lp, nvalid,
                             [False] * 5)
    assert np.array_equal(pool3[9], pool1[9])
    assert np.array_equal(conv3[4], conv1[4])
    # and a block split over two steps ends where one step ends
    _, conv_a, pool_a = _mixer(config, xbc[:, :3], dt[:, :3], conv, pool, lp,
                               [0, 0, 3, 0, 0], [False] * 5)
    _, conv_b, pool_b = _mixer(config, xbc[:, 3:], dt[:, 3:], conv_a, pool_a,
                               lp, [0, 0, 5, 0, 0], [False] * 5)
    assert np.allclose(pool_b[7], pool1[7], rtol=1e-5, atol=1e-5)
    assert np.array_equal(conv_b[2], conv1[2])


# -- the engine's books: slots, pools, counters --------------------------------

def test_a_stateful_layout_without_a_window_pool(config, params):
    """The engine of this layout: recurrent state by slot, no window pool,
    a table as wide as the context; ``kv_state()`` counts the state pool in
    slots and in bytes beside the KV blocks."""
    eng = _engine(config, params)
    assert eng._stateful and eng.win_pool is None and eng._win_width == 0
    assert eng._tbl_width == eng._full_width == 32
    assert set(eng._cache) == {"k", "v", "conv", "ssm"}
    c = config
    assert eng._cache["ssm"].shape == (3, 4, c.ssm_heads, c.ssm_head_dim,
                                       c.ssm_state)
    assert eng._cache["conv"].shape == (3, 4, 3, c.ssm_conv_width)
    state = eng._cache["ssm"].nbytes + eng._cache["conv"].nbytes
    assert eng._state_bytes * 4 == state == 4 * c.n_layers * 4 * (
        c.ssm_heads * c.ssm_head_dim * c.ssm_state + 3 * c.ssm_conv_width)
    seen = []
    _serve_all(eng, [(_prompt(1, 9), 6), (_prompt(2, 20), 3)],
               on_step=lambda e: seen.append(e.kv_state()["kv_pools"]))
    assert "window" not in seen[0]
    assert seen[0]["state"] == {"total": 4, "live": 2,
                                "slot_bytes": state // 4, "bytes": state}
    assert seen[0]["full"]["free"] < seen[0]["full"]["total"]
    assert eng.kv_state()["kv_pools"]["state"]["live"] == 0


def test_the_engines_chunk_is_the_scans_block(config, params):
    """The block form is asked for blocks up to the published
    ``mamba_chunk_size`` (``ssm_chunk``): a longer prefill chunk is refused."""
    with pytest.raises(ValueError, match="ssm_chunk"):
        _engine(config, params, prefill_chunk=config.ssm_chunk + 1)


def test_the_scan_counters_follow_the_programs_rule(config, params):
    """A 21-token prompt through chunks of 8, then 5 tokens: two whole
    blocks and a 5-token tail on the block form (24 positions computed for
    21), the first token sampled from the tail, four decode rows."""
    eng = _engine(config, params)
    _serve(eng, _prompt(4, 21), 5)
    s = eng.stats
    assert (s["ssd_positions_real"], s["ssd_positions_run"]) == (25, 28)
    # the four decode rows took one turn each, on the ``jax.numpy`` form
    # (the toy's 8 states are no whole lanes, and this is the CPU)
    assert (s["ssd_rows_stepped"], s["ssd_kernel_rows"]) == (4, 0)
    assert s["state_slots_live"] == 7
    # a uniform decoder counts none of it
    plain = LLMEngine("llama-debug", max_slots=2, max_len=32, block_size=4,
                      prefill_chunk=4)
    plain.submit([1, 2, 3], 2, lambda item: None)
    while plain.step():
        pass
    assert all(plain.stats[k] == 0 for k in (
        "ssd_positions_real", "ssd_positions_run", "ssd_rows_stepped",
        "ssd_kernel_rows", "state_slots_live"))


@pytest.mark.parametrize("how", ["hand_over", "eos", "cancel", "abort_all"])
def test_a_step_in_flight_when_a_slot_changes_hands(reference, config,
                                                    params, how):
    """One step is in flight when a request ends. ``hand_over``: one slot,
    two requests; the second takes the slot (and its state, zeroed by the
    step at position 0, never by a write) in the step dispatched right
    after the first's last one, and both stay on the reference. ``eos``,
    ``cancel`` and ``abort_all``: no block and no state slot stays held."""
    first, second = (_prompt(40, 21), 14), (_prompt(41, 30), 9)
    if how == "hand_over":
        eng = _engine(config, params, max_slots=1)
        for (prompt, n), (toks, logits) in zip(
                (first, second), _serve_all(eng, [first, second])):
            assert len(toks) == n
            assert _against_reference(reference, params, config, prompt,
                                      toks, logits) < TOL
        # no pool of the slot's own to wait for: the second request's first
        # step is dispatched before the first's last token is read
        assert eng.stats["steps_dispatched_ahead"] == eng.stats["steps"] - 1
    else:
        alone = _serve(_engine(config, params), *first)[0]
        k = next(i for i in range(2, 13) if alone[i] not in alone[:i])
        eng = _engine(config, params, max_slots=2)
        out, beside = [], []
        req = eng.submit(first[0], first[1], out.append,
                         eos=alone[k] if how == "eos" else None)
        eng.submit(second[0], second[1], beside.append)
        while len(out) < 3:
            assert eng.step()
        assert eng._inflight is not None
        if how == "cancel":
            eng.cancel(req)
        elif how == "abort_all":
            eng.abort_all(RuntimeError("loop died"))
        while eng.step():
            pass
        toks = [t for t in out if isinstance(t, int)]
        assert toks == alone[:len(toks)]
        if how == "eos":
            assert out == alone[:k + 1] + [None]
        if how != "abort_all":
            assert len(beside) == second[1] + 1 and beside[-1] is None
    kv = eng.kv_state()
    assert eng._inflight is None and kv["inflight"] == 0
    assert kv["kv_free"] == kv["kv_total"]
    assert kv["kv_pools"]["state"]["live"] == 0


def test_the_prefill_span_names_the_state_slot(config, params, monkeypatch):
    monkeypatch.setenv("RTPU_TRACING", "1")
    monkeypatch.delenv("RTPU_TRACE_FILE", raising=False)
    tracing._reset_for_tests()
    try:
        eng = _engine(config, params, max_slots=2)
        parent = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"
        eng.submit(_prompt(1, 30), 20, lambda item: None)
        eng.submit(_prompt(2, 9), 2, lambda item: None, trace=parent)
        while eng.step():
            pass
        spans = [s for s in tracing.drain_ring()
                 if s["name"] == "serve.llm::prefill"]
    finally:
        monkeypatch.undo()
        tracing._reset_for_tests()
    assert [s["attributes"]["state_slot"] for s in spans] == [1]


# -- no prefix reuse, and the paths this layout refuses -----------------------

def test_no_prefix_hit_and_a_request_served_twice_agrees_to_the_bit(
        config, params):
    """A block of keys is not a prefix's whole state: nothing enters the
    trie and the second serving of a prompt takes no hit (never a resume
    from a zero state); its logits equal the first serving's bit for bit,
    in another slot and beside another request."""
    eng = _engine(config, params)
    prompt = _prompt(8, 64)
    cold_tokens, cold = _serve(eng, prompt, 10)
    assert len(eng.prefix) == 0 and eng.prefix.stats()["misses"] == 0
    (_, _), (warm_tokens, warm) = _serve_all(
        eng, [(_prompt(9, 21), 30), (prompt, 10)])
    assert eng.stats["prefix_hit_tokens"] == 0
    assert cold_tokens == warm_tokens
    assert np.array_equal(cold, warm)


@pytest.mark.parametrize("path", ["decode_step", "generate",
                                  "forward_features", "init_cache"])
def test_the_dense_paths_name_the_layout_they_refuse(config, params, path):
    tokens = jnp.zeros((1, 4), jnp.int32)
    calls = {
        "decode_step": lambda: models.decode_step(
            params, {"pos": jnp.zeros((), jnp.int32)}, tokens, config),
        "generate": lambda: models.generate(params, tokens, config,
                                            max_new_tokens=2),
        "forward_features": lambda: models.forward(params, tokens, config),
        "init_cache": lambda: models.init_cache(config, 1, 16),
    }
    with pytest.raises(NotImplementedError,
                       match="parallel attention / Mamba-2 layout.*paged "
                             "serve step only"):
        calls[path]()


@pytest.mark.parametrize("what", ["prefill_export", "adoption",
                                  "migration"])
def test_what_ships_a_request_refuses_this_layout(config, params, what):
    """Export, adoption and migration (the preemption drain's path) carry
    KV blocks; a request of this layout is also its state. Each refuses by
    name: never a silent partial copy."""
    eng = _engine(config, params)
    kv = {"k": np.zeros((3, 1, 4, 2, 16), np.float32)}
    calls = {
        "prefill_export": lambda: eng.submit(
            _prompt(1, 9), 4, lambda item: None, prefill_only=True),
        "adoption": lambda: eng.adopt(_prompt(1, 4), kv, 1, 4,
                                      lambda item: None),
        "migration": eng.begin_migration,
    }
    with pytest.raises(NotImplementedError,
                       match="a layout with recurrent state"):
        calls[what]()
    assert eng.kv_state()["queued"] == 0


def test_import_hf_refuses_falcon_h1_with_what_is_missing():
    hf = SimpleNamespace(model_type="falcon_h1", num_hidden_layers=72,
                         hidden_size=5120, num_attention_heads=20)
    with pytest.raises(ValueError, match="name map"):
        config_from_hf(hf)


# -- the layout's description --------------------------------------------------

def _published(**kw):
    base = dict(
        vocab_size=261120, d_model=5120, n_layers=6, n_heads=20,
        n_kv_heads=4, head_dim=128, d_ff=21504, norm_eps=1e-5,
        rope_theta=1e11, layer_kinds=("parallel",) * 6, ssm_width=4096,
        ssm_heads=32, ssm_head_dim=128, ssm_groups=2, ssm_state=256,
        ssm_conv=4, ssm_chunk=128, key_multiplier=0.011048543456039804,
        ssm_multipliers=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                         0.3535533905932738),
        mlp_multipliers=(0.1767766952966369, 0.011160714285714284))
    base.update(kw)
    return models.TransformerConfig(**base)


def test_the_published_layout_counts_its_parameters():
    """Falcon-H1-34B's layer at its published widths, six of them, and the
    whole vocabulary: 5,254.6 M parameters, the tree's leaves counted one by
    one; a request's state is 4.26 MB a layer in float32."""
    c = _published()
    assert c.parallel_hybrid and not c.window_pool
    assert c.d_inner == 4096 and c.ssm_conv_width == 5120 \
        and c.ssm_proj_width == 9248
    tree = jax.eval_shape(lambda: models.init_params(jax.random.PRNGKey(0),
                                                     c))
    assert sum(x.size for x in jax.tree.leaves(tree)) == c.num_params() \
        == 5_254_594_112
    axes = models.param_axes(c)
    assert jax.tree.structure(tree) == jax.tree.structure(
        jax.tree.map(lambda a: 0, axes,
                     is_leaf=lambda a: isinstance(a, tuple)))
    cache = jax.eval_shape(lambda: models.init_cache_paged(
        c, 16, 16, state_slots=1))
    assert cache["ssm"].size * 4 + cache["conv"].size * 4 \
        == 6 * 4 * (32 * 128 * 256 + 3 * 5120)
    assert c.uniform_window == 0


@pytest.mark.parametrize("wrong", [
    dict(layer_kinds=("parallel",) * 5 + ("full",)),       # a mixed tuple
    dict(layer_kinds=("parallel",) * 5),                   # not n_layers
    dict(sliding_window=512),                              # SambaY's keys
    dict(ssm_dt_rank=160), dict(ssm_expand=4),
    dict(ssm_width=10240),                                 # expand x d_model
    dict(ssm_groups=3), dict(ssm_chunk=0),
    dict(ssm_multipliers=(1.0, 1.0)), dict(norm="layer"),
    dict(num_experts=4), dict(attn_qkv_bias=True)])
def test_a_parallel_layout_that_is_not_described_is_refused(wrong):
    with pytest.raises(ValueError, match="parallel layout"):
        _published(**wrong)


@pytest.mark.parametrize("keys", [
    dict(ssm_width=32), dict(ssm_heads=4, ssm_head_dim=8),
    dict(ssm_groups=2), dict(ssm_chunk=16), dict(key_multiplier=0.5),
    dict(ssm_multipliers=(1.0,) * 5), dict(mlp_multipliers=(0.5, 0.5))])
@pytest.mark.parametrize("layout", ["sambay", "uniform"])
def test_other_layouts_refuse_the_parallel_layouts_keys(layout, keys):
    """Mamba-2's sizes and the fixed multipliers are read by the parallel
    layout alone: on a SambaY tuple or a uniform decoder they would be
    silently ignored, so they are refused."""
    base = models.get_config(
        "hybrid-state-debug" if layout == "sambay" else "llama-debug")
    with pytest.raises(ValueError, match="parallel layout"):
        base.replace(**keys)
