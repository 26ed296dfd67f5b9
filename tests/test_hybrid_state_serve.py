"""The hybrid state-space / attention decoder family (SambaY layout:
``TransformerConfig.layer_kinds``) on the serve path, at a small size on the
CPU (10 layers: all five kinds in three segments of two periods, window 8):
the paged step and the engine against the benchmark's plain reference
(``benchmark/reference/hybrid_state_decoder.py``: one float32 pass over the
whole sequence, the recurrence as a recurrence, no cache), the pools by layer
kind, the state pool, and everything that ships a request refusing this
layout."""

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import assert_three_widths, watch_step_widths

from benchmark import manifest
from ray_tpu import models
from ray_tpu.models import hybrid
from ray_tpu.models.import_hf import config_from_hf
from ray_tpu.ops.ssm import ssm_rows
from ray_tpu.serve.kv_cache import BlockPool
from ray_tpu.serve.llm import LLMEngine

REF_LEN = 128
WINDOW = 8
#: float32 on both sides: what is left is the order of the sums (a paged
#: gather against one pass, a scan a row against a scan a sequence)
TOL = 1e-4
#: bfloat16 weights, activations and pools against the float32 reference:
#: the toy reads 0.007-0.012 over prompts; a broken layer reads 0.1 and more
TOL_BF16 = 0.03


@pytest.fixture(scope="module")
def reference():
    return manifest.load_module(
        manifest.reference_path("hybrid_state_decoder"))


@pytest.fixture(scope="module")
def config():
    return models.get_config("hybrid-state-debug").replace(
        dtype="float32", param_dtype="float32")


@pytest.fixture(scope="module")
def params(config):
    """``models.init_params`` draws every gain, bias, ``A_log``, ``D``,
    ``b_dt`` and lambda of this layout away from its trivial value."""
    return models.init_params(jax.random.PRNGKey(0), config)


def _config_file(config):
    return {"layer_norm_eps": config.norm_eps,
            "num_attention_heads": config.n_heads,
            "num_key_value_heads": config.kv_heads,
            "sliding_window": config.sliding_window,
            "tie_word_embeddings": config.tie_embeddings}


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

@pytest.mark.parametrize("chunk", [1, 4], ids=["token_rows", "chunk_rows"])
def test_paged_step_matches_the_reference_at_every_position(
        reference, config, params, chunk):
    """Prefill through chunks (or token by token) against the reference's
    full pass, logits of EVERY position of a 40-token sequence: the window
    (8) slides off the sequence's start, the window layers' table holds the
    live window only and its blocks are handed back as they leave it."""
    n, bs, m_full = 40, 4, 12
    seq = np.asarray(_prompt(1, n))
    m_win = hybrid.window_table_width(WINDOW, chunk, bs)
    cache = models.init_cache_paged(config, 16, bs, window_blocks=m_win + 3,
                                    state_slots=1)
    step = jax.jit(lambda c, t, tb, p, m: models.verify_step_paged(
        params, c, t, tb, p, m, config))
    held, free = {}, list(range(m_win + 3))
    got, pos = [], 0
    while pos < n:
        m = min(chunk, n - pos)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :m] = seq[pos:pos + m]
        first = max(pos - WINDOW + 1, 0) // bs
        for gone in [b for b in held if b < first]:
            free.append(held.pop(gone))
        for b in range(first, (pos + m - 1) // bs + 1):
            if b not in held:
                held[b] = free.pop()
        assert len(held) <= m_win
        tables = np.zeros((1, m_full + m_win), np.int32)
        tables[0, :m_full] = np.arange(m_full) + 2
        for b, block in held.items():
            tables[0, m_full + b - first] = block
        logits, cache = step(cache, jnp.asarray(toks), jnp.asarray(tables),
                             jnp.array([pos]), jnp.array([m]))
        got.append(np.asarray(logits[0, :m]))
        pos += m
    want = _reference_logits(reference, params, config, seq, np.arange(n))
    assert _rel(np.concatenate(got), want) < TOL


@pytest.mark.parametrize("budget", [None, 5],
                         ids=["budget_256", "budget_5"])
def test_engine_prefill_then_decode_matches_the_reference(
        reference, config, params, budget, monkeypatch):
    """Rows of different ages in one step: six requests through four slots
    (two wait, then take a slot another request held: its state starts from
    zero), prompts that end inside a chunk and a block, every one past the
    window. With a budget of 5 of the step's 32 positions the steps of
    several chunk rows take the full width, those of one chunk row or a
    short tail beside decoding rows the second width (10) and the decode
    steps the budget."""
    if budget:
        monkeypatch.setattr("ray_tpu.serve.llm.STEP_BUDGET", budget)
    eng = _engine(config, params)
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
    assert s["step_positions_real"] == fed
    assert (s["steps_full_width"] > 0) == bool(budget)
    if budget:
        assert_three_widths(eng, reals)
    assert s["prefix_hit_tokens"] == 0 and len(eng.prefix) == 0
    # every pool back to empty: blocks of both kinds, reservations, slots
    kv = eng.kv_state()
    assert kv["kv_free"] == kv["kv_total"] == (
        eng.pool.num_blocks + eng.win_pool.num_blocks)
    assert kv["kv_pools"]["window"] == {
        "total": eng.win_pool.num_blocks, "free": eng.win_pool.num_blocks,
        "reserved": 0}
    assert kv["kv_pools"]["state"]["live"] == 0
    # the keys the layers read, by the program's rule: 3 layers the whole
    # context (the full layer and 2 cross), 2 window layers the live window
    assert s["shared_kv_keys_read"] > s["window_keys_read"] > 0
    assert s["state_slots_live"] >= s["steps"]


def test_engine_in_bfloat16_stays_inside_its_tolerance(reference, config):
    c16 = config.replace(dtype="bfloat16", param_dtype="bfloat16")
    p16 = models.init_params(jax.random.PRNGKey(0), c16)
    eng = _engine(c16, p16)
    prompt = _prompt(3, 37)
    toks, logits = _serve(eng, prompt, 24)
    err = _against_reference(reference, p16, c16, prompt, toks, logits)
    assert 1e-4 < err < TOL_BF16
    assert eng._cache["ssm"].dtype == jnp.float32      # the state stays


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


# -- a broken layer fails the comparison --------------------------------------

@pytest.mark.parametrize("broken", [
    "state_reset", "no_window", "window_plus_one", "cross_reads_window",
    "memory_after_gate", "no_lambda", "int8"])
def test_a_broken_layer_exceeds_the_tolerance(reference, config, params,
                                              broken):
    """The engine's logits against the reference with one piece of the
    mathematics wrong: the state-space layers' state dropped between chunks,
    the window ignored or one key wide of the mark, the cross layers
    reading another layer's keys and values, the memory taken after the z
    gate, differential attention's lambda left out (and weights rounded to
    int8, the benchmark's control). Each reads far over the tolerance that
    the sound comparison keeps."""
    eng = _engine(config, params)
    prompt = _prompt(5, 45)
    toks, logits = _serve(eng, prompt, 16)
    assert _against_reference(reference, params, config, prompt, toks,
                              logits) < TOL
    assert _against_reference(reference, params, config, prompt, toks,
                              logits, weights=broken) > 100 * TOL


@pytest.mark.parametrize("pool", ["ssm", "conv"])
def test_a_state_that_is_not_written_back_shows(reference, config, params,
                                                pool):
    """The engine itself broken: the scan's state (or the conv's inputs)
    zeroed after every step, as a step that did not write it back would
    leave it."""
    def wipe(eng):
        eng._cache = {**eng._cache,
                      pool: jnp.zeros_like(eng._cache[pool])}

    eng = _engine(config, params)
    prompt = _prompt(6, 30)
    toks, logits = _serve(eng, prompt, 12, on_step=wipe)
    assert _against_reference(reference, params, config, prompt, toks,
                              logits) > 100 * TOL


def test_padding_and_idle_rows_leave_the_state_untouched(config, params):
    """``ssm_rows``: a row that feeds nothing gets back the state it had, a
    row that feeds 3 of 8 positions the state after the third, whatever the
    padding holds; a fresh row starts from zero whatever its slot held."""
    lp = jax.tree.map(lambda w: w[0], params["layers"]["self"]["mamba"])
    rng = np.random.default_rng(0)
    di, n, k = config.d_inner, config.ssm_state, config.ssm_conv
    u = jnp.asarray(rng.normal(size=(3, 8, di)), jnp.float32)
    conv = jnp.asarray(rng.normal(size=(3, k - 1, di)), jnp.float32)
    h = jnp.asarray(rng.normal(size=(3, n, di)), jnp.float32)
    nvalid = jnp.array([0, 3, 8])
    fresh = jnp.array([False, False, True])
    y, conv1, h1 = ssm_rows(u, conv, h, lp, nvalid, fresh)
    assert np.array_equal(conv1[0], conv[0]) and np.array_equal(h1[0], h[0])
    # other padding, the same three real positions: the same state
    u2 = u.at[1, 3:].set(7.0)
    _, conv2, h2 = ssm_rows(u2, conv, h, lp, nvalid, fresh)
    assert np.array_equal(conv2[1], conv1[1]) and np.array_equal(h2[1], h1[1])
    assert np.array_equal(conv1[1], u[1, :3])
    # the fresh row: what zeros would have given
    _, conv3, h3 = ssm_rows(u, jnp.zeros_like(conv), jnp.zeros_like(h), lp,
                            nvalid, jnp.array([False, False, False]))
    assert np.array_equal(h3[2], h1[2]) and np.array_equal(conv3[2], conv1[2])
    # and a row split in two steps ends where one step ends
    _, conv_a, h_a = ssm_rows(u[:, :4], conv, h, lp, jnp.array([0, 3, 4]),
                              fresh)
    _, conv_b, h_b = ssm_rows(u[:, 4:], conv_a, h_a, lp,
                              jnp.array([0, 0, 4]),
                              jnp.array([False, False, False]))
    assert np.allclose(h_b[2], h1[2], atol=1e-6)
    assert np.array_equal(conv_b[2], conv1[2])


# -- the allocator -------------------------------------------------------------

def test_window_layers_hold_at_most_window_plus_chunk_plus_a_block(
        config, params):
    """Through a long decode the blocks a slot holds in the window pool
    never pass (window + chunk) / block_size + 1, whatever its context,
    blocks leave as the window slides, and the table a row hands the step
    starts at the block of the first key its first query may see."""
    eng = _engine(config, params)
    bound = (WINDOW + eng.prefill_chunk) // eng.pool.block_size + 1
    assert eng._win_width <= bound
    seen = []

    def watch(eng):
        for req in eng._slots:
            if req is not None:
                seen.append(len(req.win_table))
                assert len(req.win_table) <= eng._win_width
                # nothing is held that lies wholly before the window of
                # the step just run
                assert (req.win_first + 1) * 4 + WINDOW > req.pos - 8
        # (a request whose last token is in flight has left its slot and
        # holds its blocks until that token is read)
        holders = {r for r in eng._slots if r is not None}
        if eng._inflight is not None:
            holders |= {r for _i, r, _s, last in eng._inflight.rows if last}
        assert eng.win_pool.used_count == sum(
            len(r.win_table) for r in holders)

    _serve_all(eng, [(_prompt(20, 50), 60), (_prompt(21, 7), 90)],
               on_step=watch)
    assert max(seen) <= bound
    s = eng.stats
    assert s["window_blocks_released"] > 2 * (110 // 4 - bound)
    # against a table as wide as the requests' contexts
    assert s["window_blocks_held"] < 0.3 * s["window_blocks_full_table"]
    assert eng.win_pool.free_count == eng.win_pool.num_blocks


@pytest.mark.parametrize("how", ["hand_over", "eos", "cancel", "abort_all"])
def test_a_step_in_flight_when_a_slot_changes_hands(reference, config,
                                                    params, how):
    """One step is in flight when a request ends. ``hand_over``: one slot,
    two requests; the second takes the slot (and its state, zeroed by the
    step at position 0) in the step dispatched right after the first's last
    one, before that one is read, and both stay on the reference. ``eos``
    (found a step late: the row ran once past its end), ``cancel`` and
    ``abort_all``: no block of either pool, no reservation and no state slot
    stays held."""
    first, second = (_prompt(40, 21), 14), (_prompt(41, 30), 9)
    if how == "hand_over":
        eng = _engine(config, params, max_slots=1)
        for (prompt, n), (toks, logits) in zip(
                (first, second), _serve_all(eng, [first, second])):
            assert len(toks) == n
            assert _against_reference(reference, params, config, prompt,
                                      toks, logits) < TOL
        # (a window pool of ONE slot's blocks: the second waits for the
        # first's reservation, which goes back when its last step is read,
        # so the engine runs dry once between the two)
        assert eng.stats["steps_dispatched_ahead"] == eng.stats["steps"] - 2
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
            assert eng.stats["rows_run_past_end"] == 1
        if how != "abort_all":
            assert len(beside) == second[1] + 1 and beside[-1] is None
    kv = eng.kv_state()
    assert eng._inflight is None and kv["inflight"] == 0
    assert kv["kv_free"] == kv["kv_total"]
    assert kv["kv_pools"]["window"]["reserved"] == 0
    assert kv["kv_pools"]["state"]["live"] == 0


@pytest.mark.parametrize("short", ["full", "window"])
def test_admission_claims_every_pool_or_nothing(config, params, short):
    """A request that one pool cannot hold stays queued and holds nothing of
    the other pools: no block, no reservation."""
    eng = _engine(config, params, num_blocks=40)
    if short == "window":
        eng.win_pool = BlockPool(eng._win_width + 1, eng.pool.block_size)
    first = eng.submit(_prompt(30, 20), 60, lambda item: None)    # 20 blocks
    second = eng.submit(_prompt(31, 30), 60, lambda item: None)   # 23 blocks
    eng.step()
    assert eng._slots[0] is first and second in eng._pending
    assert second.table == [] and second.win_reserved == 0
    assert eng.pool.used_count == 20
    assert eng._win_reserved == first.win_reserved == eng._win_width
    while eng.step():
        pass
    assert second.generated == 60          # admitted once the first ended
    assert eng.pool.free_count == 40 and eng._win_reserved == 0


# -- no prefix reuse, and the paths this layout refuses -----------------------

@pytest.mark.parametrize("form", ["jax_numpy", "kernel"])
def test_no_prefix_hit_and_a_request_served_twice_agrees_to_the_bit(
        config, params, form, monkeypatch):
    """A block of keys is not a prefix's whole state: nothing enters the
    trie and the second serving of a prompt takes no hit (never a resume
    from a zero state); its logits equal the first serving's bit for bit,
    in another slot and beside another request. On the ``jax.numpy`` form
    of the attention and on the kernel's (interpreted; heads of 64 in bf16
    and blocks of 16, which ``diff_attention_impl`` takes)."""
    kw = {}
    if form == "kernel":
        from ray_tpu.ops.attention import set_default_attention_impl

        monkeypatch.setenv("RTPU_ATTN_PALLAS_INTERPRET", "1")
        set_default_attention_impl("pallas")
        config = config.replace(head_dim=64, dtype="bfloat16",
                                param_dtype="bfloat16")
        params = models.init_params(jax.random.PRNGKey(0), config)
        kw = {"block_size": 16, "prefill_chunk": 16, "max_slots": 2}
    try:
        eng = _engine(config, params, **kw)
        assert eng.stats["attn_impl"] == ("pallas" if kw else "xla")
        prompt = _prompt(8, 64)
        cold_tokens, cold = _serve(eng, prompt, 10)
        assert len(eng.prefix) == 0 and eng.prefix.stats()["misses"] == 0
        (_, _), (warm_tokens, warm) = _serve_all(
            eng, [(_prompt(9, 21), 30), (prompt, 10)])
    finally:
        if kw:
            set_default_attention_impl(None)
    assert eng.stats["prefix_hit_tokens"] == 0
    assert cold_tokens == warm_tokens
    assert np.array_equal(cold, warm)


@pytest.mark.parametrize("path", ["decode_step", "generate",
                                  "forward_features", "init_cache"])
def test_the_dense_paths_raise_for_this_layout(config, params, path):
    tokens = jnp.zeros((1, 4), jnp.int32)
    calls = {
        "decode_step": lambda: models.decode_step(
            params, {"pos": jnp.zeros((), jnp.int32)}, tokens, config),
        "generate": lambda: models.generate(params, tokens, config,
                                            max_new_tokens=2),
        "forward_features": lambda: models.forward(params, tokens, config),
        "init_cache": lambda: models.init_cache(config, 1, 16),
    }
    with pytest.raises(NotImplementedError, match="paged serve step only"):
        calls[path]()


@pytest.mark.parametrize("what", ["prefill_export", "adoption",
                                  "migration"])
def test_what_ships_a_request_refuses_this_layout(config, params, what):
    """Export, adoption and migration carry KV blocks; a request of this
    layout is also its state and its window's blocks. Each refuses with a
    message: never a silent partial copy."""
    eng = _engine(config, params)
    kv = {"k": np.zeros((1, 1, 4, 32), np.float32)}
    calls = {
        "prefill_export": lambda: eng.submit(
            _prompt(1, 9), 4, lambda item: None, prefill_only=True),
        "adoption": lambda: eng.adopt(_prompt(1, 4), kv, 1, 4,
                                      lambda item: None),
        "migration": eng.begin_migration,
    }
    with pytest.raises(NotImplementedError, match="recurrent state"):
        calls[what]()
    assert eng.kv_state()["queued"] == 0


def test_import_hf_refuses_phi4flash_with_what_is_missing():
    hf = SimpleNamespace(model_type="phi4flash", num_hidden_layers=32,
                         hidden_size=2560, num_attention_heads=40)
    with pytest.raises(ValueError, match="name map"):
        config_from_hf(hf)


# -- the layout's description --------------------------------------------------

def test_the_published_layout_counts_its_parameters():
    """Phi-4-mini-flash-reasoning's layer kinds at its published widths:
    3,852.6 M parameters, the tree's leaves counted one by one."""
    c = models.TransformerConfig(
        vocab_size=200064, d_model=2560, n_layers=32, n_heads=40,
        n_kv_heads=20, head_dim=64, d_ff=10240, norm="layer",
        positions="none", tie_embeddings=True, sliding_window=512,
        layer_kinds=("mamba", "window") * 8 + ("mamba", "full")
        + ("gmu", "cross") * 7)
    assert c.hybrid_periods == (8, 7) and c.dt_rank == 160
    tree = jax.eval_shape(lambda: models.init_params(jax.random.PRNGKey(0),
                                                     c))
    assert sum(x.size for x in jax.tree.leaves(tree)) == c.num_params() \
        == 3_852_562_944
    axes = models.param_axes(c)
    assert jax.tree.structure(tree) == jax.tree.structure(
        jax.tree.map(lambda a: 0, axes,
                     is_leaf=lambda a: isinstance(a, tuple)))
    assert c.uniform_window == 0        # layers mix: no ring, no shared rule


@pytest.mark.parametrize("kinds", [
    ("mamba", "window", "mamba", "full", "gmu"),
    ("window", "mamba") * 2 + ("mamba", "full") + ("gmu", "cross"),
    ("mamba", "window", "mamba", "window", "gmu", "cross")])
def test_a_layout_that_is_not_described_is_refused(kinds):
    with pytest.raises(ValueError, match="layer_kinds|hybrid layout"):
        models.TransformerConfig(
            vocab_size=64, d_model=32, n_layers=len(kinds), n_heads=4,
            n_kv_heads=2, sliding_window=8, layer_kinds=kinds)
