"""The linear hybrid decoder family (Olmo-Hybrid layout:
``TransformerConfig.layer_kinds`` of ``"delta"`` and ``"full"``) on the serve
path, at a small size on the CPU (two periods of three gated delta-rule
layers of 4 heads closed by an MHA layer of 6 heads whose pool pads them to
16): the paged step and the engine against the benchmark's plain reference
(``benchmark/reference/linear_hybrid_decoder.py``: one float32 pass over the
whole sequence, the delta rule as the token-by-token recurrence, no cache),
a prefix hit that RESTORES A STATE SNAPSHOT against the same prompt served
cold, the trie's snapshots (a hit lands only where one is kept, eviction, no
leak), and everything that ships a request refusing this layout."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import assert_three_widths, watch_step_widths

from benchmark import manifest
from benchmark.kinds.serve_family_replica import load_family
from ray_tpu import models
from ray_tpu.models import layouts
from ray_tpu.serve.kv_cache import BlockPool, PrefixCache
from ray_tpu.serve.llm import LLMEngine
from ray_tpu.util import tracing

REF_LEN = 128
#: float32 on both sides: what is left is the order of the sums (a paged
#: gather against one pass, the block form's solve against the recurrence);
#: the toy reads 5e-6 to 2e-5
TOL = 2e-4
#: bfloat16 weights, activations and KV pool (float32 state and rule) against
#: the float32 reference. The toy is 64 wide and every branch is normed
#: into the residual, so a rounding is not averaged down as it is at 3840: it
#: reads 0.05-0.15 over prompts; a branch left out reads 0.7 and more
TOL_BF16 = 0.3


@pytest.fixture(scope="module")
def reference():
    return manifest.load_module(
        manifest.reference_path("linear_hybrid_decoder"))


@pytest.fixture(scope="module")
def config():
    return models.get_config("linear-hybrid-debug").replace(
        dtype="float32", param_dtype="float32")


@pytest.fixture(scope="module")
def params(config):
    return models.init_params(jax.random.PRNGKey(0), config)


def _config_file(c):
    """The published keys the reference reads, from a ``TransformerConfig``."""
    a, p = c.delta_periods
    return {"rms_norm_eps": c.norm_eps, "num_attention_heads": c.n_heads,
            "num_key_value_heads": c.kv_heads, "hidden_size": c.d_model,
            "head_dim": c.hdim, "linear_num_key_heads": c.delta_key_heads,
            "linear_num_value_heads": c.delta_key_heads,
            "linear_key_head_dim": c.delta_key_dim,
            "linear_value_head_dim": c.delta_value_dim,
            "linear_conv_kernel_dim": c.delta_conv,
            "linear_allow_neg_eigval": c.delta_neg_eigval,
            "tie_word_embeddings": False, "attention_bias": False,
            "hidden_act": "silu", "rope_parameters": {"rope_theta": None},
            "layer_types": (["linear_attention"] * a
                            + ["full_attention"]) * p}


def _rel(got, want):
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def _engine(config, params, **kw):
    kw = {"max_slots": 4, "max_len": 128, "block_size": 4,
          "prefill_chunk": 8, **kw}
    return LLMEngine(config, params, **kw)


def _serve_all(eng, requests):
    """Serve (prompt, n) pairs together to their end; returns per request
    (tokens, logits per token, the request)."""
    outs, sample, order = [], eng._sample, []

    def capture(row):
        order.append(row.copy())
        return sample(row)

    eng._sample, eng.capture = capture, True
    try:
        for prompt, n in requests:
            toks, logits = [], []

            def emit(item, toks=toks, logits=logits):
                if isinstance(item, int):
                    toks.append(item)
                    logits.append(order[-1])

            outs.append((toks, logits, eng.submit(prompt, n, emit)))
        while eng.step():
            pass
    finally:
        eng._sample, eng.capture = sample, False
    return [(t, np.stack(l), r) for t, l, r in outs]


def _serve(eng, prompt, n):
    return _serve_all(eng, [(prompt, n)])[0]


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(0, 256, n).tolist()


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
    """Rows of different ages in one step: six requests through four slots
    (two wait, then take a slot another request held: its state starts from
    zero by the ``fresh`` rule), prompts that end inside a chunk and a
    block; prefill through chunks of 1, 3, 8 and 16 positions (the block
    form over blocks of several lengths, a chunk cut short on the boundary
    where the state is copied), then decode through the KV blocks and the
    carried state; with a budget of 5 of 4 x 8 positions the ordered stream."""
    if budget:
        monkeypatch.setattr("ray_tpu.serve.llm.STEP_BUDGET", budget)
    eng = _engine(config, params, prefill_chunk=chunk)
    reals = watch_step_widths(eng)
    requests = [(_prompt(10 + i, n), m) for i, (n, m) in enumerate(
        [(5, 20), (23, 12), (40, 30), (9, 9), (31, 5), (17, 40)])]
    served = _serve_all(eng, requests)
    for (prompt, n), (toks, logits, _) in zip(requests, served):
        assert len(toks) == n
        assert _against_reference(reference, params, config, prompt, toks,
                                  logits) < TOL
    s = eng.stats
    fed = sum(len(p) + n - 1 for p, n in requests)
    assert s["step_positions_real"] == s["delta_positions_real"] == fed
    if budget:
        assert_three_widths(eng, reals)
    assert s["attn_impl"] == "xla"
    # every prompt of a block or more left its blocks and a snapshot behind
    assert s["state_snapshots_taken"] == 6 and s["prefix_hit_tokens"] == 0
    kv = eng.kv_state()
    assert kv["kv_free"] + kv["prefix"]["nodes"] == kv["kv_total"]
    assert kv["prefix"]["snapshots_held"] == 6
    assert kv["kv_pools"]["state"]["live"] == 0


def test_engine_in_bfloat16_stays_inside_its_tolerance(reference, config):
    c16 = config.replace(dtype="bfloat16", param_dtype="bfloat16")
    p16 = models.init_params(jax.random.PRNGKey(0), c16)
    eng = _engine(c16, p16)
    prompt = _prompt(3, 37)
    toks, logits, _ = _serve(eng, prompt, 24)
    err = _against_reference(reference, p16, c16, prompt, toks, logits)
    assert 1e-3 < err < TOL_BF16
    # the state, its snapshots and the conv's inputs stay float32
    assert {str(eng._cache[k].dtype) for k in ("conv", "delta")} \
        == {str(a.dtype) for a in eng._snaps.values()} == {"float32"}
    assert str(eng._cache["k"].dtype) == "bfloat16"


@pytest.mark.parametrize("broken", [
    "state_reset", "no_delta", "no_attention", "no_decay", "beta_below_one",
    "no_conv", "head_qk_norm", "no_gate", "bf16_state", "int8"])
def test_mathematics_left_out_exceeds_the_tolerance(reference, config,
                                                    params, broken):
    """The comparison sees each piece: the reference with it left out (or
    held in a lower precision than the configuration states) is outside the
    float32 tolerance by a wide margin."""
    eng = _engine(config, params)
    prompt = _prompt(4, 50)
    toks, logits, _ = _serve(eng, prompt, 12)
    assert _against_reference(reference, params, config, prompt, toks,
                              logits) < TOL
    assert _against_reference(reference, params, config, prompt, toks,
                              logits, weights=broken) > 50 * TOL


# -- a prefix hit restores a snapshot -----------------------------------------

@pytest.mark.parametrize("n_prompt", [45, 48, 13])
def test_a_hit_through_a_snapshot_equals_the_cold_serve(reference, config,
                                                       params, n_prompt):
    """One prompt served twice: the second serve lands on the snapshot at the
    prompt's last block boundary UNDER its last token (44 of 45, 44 of 48,
    12 of 13), shares the blocks up to there with the trie, starts at that
    position with the state copied into its slot, and gives the cold
    serve's logits to the float32 state's tolerance: both are the
    reference's."""
    eng = _engine(config, params)
    prompt = _prompt(n_prompt, n_prompt)
    cold, cold_logits, cold_req = _serve(eng, prompt, 8)
    depth = (n_prompt - 1) // 4 * 4
    taken = n_prompt // 4 * 4
    assert (cold_req.prefix_hit, eng.stats["state_snapshots_taken"]) == (0, 1)
    held = eng.prefix.stats()
    assert (held["nodes"], held["snapshots_held"]) == (taken // 4, 1)

    seen = {}
    step = eng._step_fn

    def watch(params_, cache, tokens, tables, pos, nvalid, active):
        seen.setdefault("pos", int(pos[0]))
        seen.setdefault("table", [int(b) for b in tables[0]])
        return step(params_, cache, tokens, tables, pos, nvalid, active)

    eng._step_fn = watch
    warm, warm_logits, warm_req = _serve(eng, prompt, 8)
    if taken == n_prompt:
        # the only snapshot lies AT the prompt's end: one token has to run,
        # and a hit never lands between snapshots: cold again
        assert (warm_req.prefix_hit, seen["pos"]) == (0, 0)
        assert eng.stats["state_snapshots_restored"] == 0
    else:
        assert (warm_req.prefix_hit, seen["pos"]) == (depth, depth)
        assert eng.stats["state_snapshots_restored"] == 1
        assert eng.stats["state_snapshot_bytes"] == 2 * eng._state_bytes
        # the prefix's blocks are the trie's own, shared and refcounted
        node, shared = eng.prefix._root, []
        for i in range(depth // 4):
            node = node.children[tuple(prompt[4 * i:4 * i + 4])]
            shared.append(node.block_id)
        assert seen["table"][:len(shared)] == shared
    assert warm == cold
    np.testing.assert_allclose(warm_logits, cold_logits, atol=2e-4)
    for toks, logits in ((cold, cold_logits), (warm, warm_logits)):
        assert _against_reference(reference, params, config, prompt, toks,
                                  logits) < TOL
    kv = eng.kv_state()
    assert kv["kv_free"] + kv["prefix"]["nodes"] == kv["kv_total"]
    assert all(eng.pool.refcount(b) in (0, 1)
               for b in range(eng.pool.num_blocks))


def _state_rel(reference, family, eng, params, config, prompt, toks, req):
    """By (delta layer, head): how far the matrix state in the request's slot
    lies from the reference's after the tokens the request was fed; and the
    largest distance of a layer's conv inputs."""
    seq = prompt + toks[:-1]
    padded = np.zeros(REF_LEN, np.int32)
    padded[:len(seq)] = seq
    want = [np.asarray(a) for a in reference.state_at(
        params, padded, len(seq), _config_file(config))]
    got = [np.asarray(a) for a in family.slot_state(eng._cache, req.slot,
                                                    config)]
    a, p = config.delta_periods
    assert want[0].shape == got[0].shape == (
        a * p, config.delta_key_heads, config.delta_key_dim,
        config.delta_value_dim)
    assert want[1].shape == got[1].shape == (
        a * p, config.delta_conv - 1, config.delta_conv_width)
    norm = lambda x: np.sqrt(np.square(x).sum((-2, -1)))
    return (norm(got[0] - want[0]) / norm(want[0]),
            float((norm(got[1] - want[1]) / norm(want[1])).max()))


@pytest.mark.parametrize("n_prompt,chunk", [(45, 8), (13, 8), (30, 3),
                                            (61, 16)])
def test_a_served_requests_state_is_the_references(reference, config, params,
                                                   n_prompt, chunk):
    """What the benchmark's kind holds to ``state_rel_err``: the matrix state
    and the conv inputs a slot holds once its request is served (cold, and
    again through its snapshot) are the plain reference's after the prompt
    and all but the last token of the answer, every layer and head."""
    family = load_family({"reference": "linear_hybrid_decoder"})
    eng = _engine(config, params, prefill_chunk=chunk)
    prompt = _prompt(n_prompt, n_prompt)
    for serve in ("cold", "warm"):
        toks, _, req = _serve(eng, prompt, 6)
        by_head, conv = _state_rel(reference, family, eng, params, config,
                                   prompt, toks, req)
        assert by_head.max() < TOL and conv < TOL, serve
    assert req.prefix_hit == (n_prompt - 1) // 4 * 4
    # one position fewer is another state, by far more than the tolerance
    off, _ = _state_rel(reference, family, eng, params, config, prompt,
                        toks[:-1], req)
    assert off.min() > 100 * TOL


def test_a_state_pool_held_in_bfloat16_shows_in_the_state(reference, config,
                                                          params):
    """The control the logits cannot see (``benchmark/tools/
    calibrate_linear_hybrid.py``'s ``pool_bf16``): the state pool rounded to
    bfloat16 after every step lies a rounding from the reference's in EVERY
    head, where the float32 pool lies at the order of the sums."""
    family = load_family({"reference": "linear_hybrid_decoder"})
    eng = _engine(config, params)
    step = eng._step_fn
    low = jax.jit(lambda a: jax.lax.reduce_precision(a, 8, 7))

    def rounded(*args):
        out = step(*args)
        return out[0], {**out[1], "delta": low(out[1]["delta"])}

    eng._step_fn = rounded
    prompt = _prompt(45, 45)
    toks, _, req = _serve(eng, prompt, 6)
    by_head, _ = _state_rel(reference, family, eng, params, config, prompt,
                            toks, req)
    # (the first layer at a rounding, 0.002-0.003; the layers behind it
    # read a residual that has moved, and lie further)
    assert by_head.min() > 5 * TOL and by_head[0].max() < 0.01


def test_the_next_turn_lands_where_the_last_prompt_ended(reference, config,
                                                         params):
    """A conversation that grows: each turn's prompt is the last prompt, an
    answer's worth of tokens and a new message. The turn lands on the
    snapshot taken at the END of the last prompt (which did not exist
    before that request ran), prefills what lies past it, and its logits
    are the reference's over the whole conversation."""
    eng = _engine(config, params, max_len=128)
    rng = np.random.default_rng(7)
    history, last_boundary = rng.integers(0, 256, 21).tolist(), 0
    for turn in range(4):
        prompt = history + rng.integers(0, 256, 6).tolist()
        toks, logits, req = _serve(eng, prompt, 5)
        assert req.prefix_hit == last_boundary
        assert _against_reference(reference, params, config, prompt, toks,
                                  logits) < TOL
        last_boundary = len(prompt) // 4 * 4
        history = prompt + rng.integers(0, 256, 5).tolist()
    assert eng.stats["state_snapshots_restored"] == 3
    assert eng.stats["state_snapshots_taken"] == 4


def test_a_chunk_ends_on_the_boundary_the_state_is_copied_at(config, params):
    """45 tokens through chunks of 8: five whole chunks, then 4 (to the last
    block boundary, 44, where the copy is taken) and the last token alone:
    one more, shorter chunk step a request."""
    eng = _engine(config, params)
    fed = []
    step = eng._step_fn

    def watch(params_, cache, tokens, tables, pos, nvalid, active):
        fed.append(int(nvalid[0]))
        return step(params_, cache, tokens, tables, pos, nvalid, active)

    eng._step_fn = watch
    _serve(eng, _prompt(1, 45), 3)
    assert fed == [8, 8, 8, 8, 8, 4, 1, 1, 1]
    assert eng.stats["delta_positions_run"] == 6 * 8 + 3


def _compiles():
    from ray_tpu.util import device_plane

    return {r["program"]: r.get("compiles", 0)
            for r in device_plane.registry().rows()
            if r["program"] in ("serve::snapshot_state",
                                "serve::restore_state")}


def test_a_snapshot_rides_a_device_program_of_its_own(config, params,
                                                      monkeypatch):
    """``serve::snapshot_state`` and ``serve::restore_state`` beside
    ``serve::copy_kv_block``, compiled when the engine is built (nothing
    compiles at a request's admission), and the restore stamped."""
    eng = _engine(config, params)
    before = _compiles()
    assert set(before) == {"serve::snapshot_state", "serve::restore_state"}
    spans = []
    real = tracing.stamp

    def stamp(name, into=None):
        spans.append(name)
        return real(name, into)

    monkeypatch.setattr(tracing, "stamp", stamp)
    prompt = _prompt(2, 30)
    _serve(eng, prompt, 2)
    _serve(eng, prompt, 2)
    assert spans.count("serve::restore_state") == 1
    assert eng.stats["state_restore_s"] > 0
    assert eng.stats["state_snapshots_taken"] == 1
    assert _compiles() == before


# -- the trie's snapshots -------------------------------------------------------

def _trie(blocks=64, snapshots=3):
    pool = BlockPool(blocks, 4)
    return pool, PrefixCache(pool, snapshots)


def _insert(pool, trie, tokens, snapshot=None):
    ids = pool.alloc(len(tokens) // 4)
    trie.insert(tokens, ids, snapshot=snapshot)
    pool.release_all(ids)
    return ids


def test_a_hit_lands_only_where_a_snapshot_is_kept():
    pool, trie = _trie()
    chain = list(range(40))
    _insert(pool, trie, chain[:20])                 # blocks, no snapshot
    assert trie.match_snapshot(chain) == ([], 0, None)
    assert trie.stats()["misses"] == 1
    snap = trie.alloc_snapshot()
    ids = _insert(pool, trie, chain[:12], snapshot=snap)   # node 3 of 5
    blocks, matched, got = trie.match_snapshot(chain)
    assert (matched, got, len(blocks)) == (12, snap, 3)
    assert all(pool.refcount(b) == 2 for b in blocks)      # trie + caller
    pool.release_all(blocks)
    # the deepest of two on the path; never past ``len - 1``
    deep = trie.alloc_snapshot()
    _insert(pool, trie, chain[:20], snapshot=deep)
    assert trie.match_snapshot(chain)[1:] == (20, deep)
    assert trie.match_snapshot(chain[:20])[1:] == (12, snap)
    assert trie.match_snapshot(chain[:21])[1:] == (20, deep)
    assert trie.match_snapshot(chain[:12])[1:] == (0, None)
    # a prompt that leaves the chain lands on what lies before the fork
    assert trie.match_snapshot(chain[:14] + [99] * 10)[1:] == (12, snap)
    assert trie.match_snapshot([99] + chain[1:])[1:] == (0, None)


def test_the_pool_takes_back_what_lies_between_two_first_then_the_oldest():
    pool, trie = _trie(snapshots=3)
    a, b = list(range(100, 140)), list(range(200, 240))
    ids = [trie.alloc_snapshot() for _ in range(3)]
    assert trie.alloc_snapshot() is None        # all three the callers'
    _insert(pool, trie, a[:8], snapshot=ids[0])
    _insert(pool, trie, b[:8], snapshot=ids[1])
    _insert(pool, trie, a[:16], snapshot=ids[2])
    # nothing lies between two: the least recently used goes (chain b's is
    # older than chain a's, which the last insert walked)
    assert trie.alloc_snapshot() == ids[1]
    assert trie.match_snapshot(b)[1:] == (0, None)
    _insert(pool, trie, a[:24], snapshot=ids[1])
    # now a's second lies between its first and its third: it goes first,
    # though the oldest is the first
    assert trie.alloc_snapshot() == ids[2]
    assert trie.match_snapshot(a[:20])[1:] == (8, ids[0])
    assert trie.match_snapshot(a)[1:] == (24, ids[1])
    # one went with nothing to stand in for it, one from between two
    assert trie.stats()["snapshot_evictions"] == 1
    assert trie.stats()["snapshots_superseded"] == 1
    # a node outlives its snapshot: chain b's blocks are still the trie's
    assert len(trie) == 6 + 2
    trie.free_snapshot(ids[2])
    assert trie.stats()["snapshots_free"] == 1


def test_a_leaf_evicted_for_its_block_takes_its_snapshot_with_it():
    pool, trie = _trie(blocks=12, snapshots=2)
    chain = list(range(32))
    _insert(pool, trie, chain[:16], snapshot=trie.alloc_snapshot())
    _insert(pool, trie, chain[:32], snapshot=trie.alloc_snapshot())
    assert (pool.free_count, trie.snapshots_free()) == (4, 0)
    assert trie.evict(2) == 2                   # the chain's last two leaves
    assert trie.snapshots_free() == 1           # the deeper snapshot left
    assert trie.match_snapshot(chain + [0])[1] == 16
    pool.release_all(trie.match_snapshot(chain + [0])[0] * 2)
    assert trie.clear() == 6
    assert (pool.free_count, trie.snapshots_free()) == (12, 2)


def test_nothing_leaks_over_random_submits_cancels_and_retirements(
        config, params):
    """200 requests over 12 conversations that grow and start over, through
    a pool of 40 blocks (the trie evicts all the way) and 5 snapshots (every
    take after the first few takes one back), a fifth of them cancelled at
    a random step: afterwards every block is free or a trie node's, every
    snapshot is free or a node's, and no slot is held."""
    eng = _engine(config, params, max_slots=2, max_len=64, num_blocks=40)
    rng = np.random.default_rng(11)
    chats = [rng.integers(0, 256, int(rng.integers(5, 20))).tolist()
             for _ in range(12)]
    live, done = [], [0]
    for i in range(200):
        k = int(rng.integers(0, len(chats)))
        if len(chats[k]) > 40:
            chats[k] = chats[k][:int(rng.integers(5, 12))]
        prompt = chats[k] + rng.integers(0, 256, int(rng.integers(1, 7))
                                         ).tolist()
        n = int(rng.integers(1, 8))

        def emit(item):
            done[0] += item is None

        live.append(eng.submit(prompt, n, emit))
        chats[k] = prompt + rng.integers(0, 256, n).tolist()
        for _ in range(int(rng.integers(0, 4))):
            eng.step()
        if rng.random() < 0.2:
            eng.cancel(live[int(rng.integers(0, len(live)))])
    while eng.step():
        pass
    kv = eng.kv_state()
    prefix = kv["prefix"]
    assert kv["inflight"] == kv["queued"] == 0
    assert kv["kv_free"] + prefix["nodes"] == kv["kv_total"]
    assert prefix["snapshots_held"] + prefix["snapshots_free"] \
        == prefix["snapshots"] == kv["kv_pools"]["state"]["snapshots"] == 5
    assert sum(eng.pool.refcount(b) for b in range(40)) == prefix["nodes"]
    s = eng.stats
    assert s["state_snapshots_restored"] > 20
    assert s["state_snapshots_taken"] > 100
    assert s["state_snapshots_evicted"] == prefix["snapshot_evictions"] > 20
    assert prefix["snapshots_superseded"] > 0
    assert done[0] > 100


def test_without_a_trie_there_are_no_snapshots(config, params):
    eng = _engine(config, params, prefix_cache=False)
    assert (eng._snapshots, eng._snaps) == (False, {})
    prompt = _prompt(5, 30)
    cold = _serve(eng, prompt, 4)
    warm = _serve(eng, prompt, 4)
    assert cold[0] == warm[0] and warm[2].prefix_hit == 0
    assert eng.stats["state_snapshots_taken"] == 0


# -- what the layout refuses, and how it is described -------------------------

@pytest.mark.parametrize("what", ["prefill_export", "adoption",
                                  "migration"])
def test_what_ships_a_request_refuses_this_layout(config, params, what):
    """Export, adoption and migration carry KV blocks; a request of this
    layout is also its state (a snapshot is the ENGINE's own copy and ships
    nothing). Each refuses by name: never a silent partial copy."""
    eng = _engine(config, params)
    kv = {"k": np.zeros((2, 1, 4, 16, 16), np.float32)}
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


def test_the_engines_chunk_is_the_rules_block(config, params):
    with pytest.raises(ValueError, match="the delta rule's block of 64"):
        _engine(config, params, max_len=256, prefill_chunk=65)
    assert _engine(config, params, max_len=256,
                   prefill_chunk=64).prefill_chunk == 64


def _published(**kw):
    base = dict(
        vocab_size=100352, d_model=3840, n_layers=32, n_heads=30,
        head_dim=128, d_ff=11008, norm_eps=1e-6, positions="none",
        layer_kinds=(("delta",) * 3 + ("full",)) * 8,
        delta_key_heads=30, delta_key_dim=96, delta_value_dim=192,
        delta_conv=4, delta_neg_eigval=True)
    base.update(kw)
    return models.TransformerConfig(**base)


def test_the_published_layout_counts_its_parameters():
    """Olmo-Hybrid-7B at its published widths: the count from the
    ``linear_*`` sizes gives the published 7B, and the cut of the cell (12
    layers, three periods) its 6.54 GB."""
    c = _published()
    parts = c._linear_hybrid_parts()
    assert parts == {"delta": 88_750_332, "attn": 58_990_080,
                     "mlp": 126_812_160, "norms": 7_680}
    assert c.num_params() == 7_430_870_688
    cut = _published(n_layers=12,
                     layer_kinds=(("delta",) * 3 + ("full",)) * 3)
    assert cut.num_params() == 3_268_268_508
    assert models.layout_of(cut) is layouts.LINEAR_HYBRID
    tree = jax.eval_shape(lambda: models.init_params(
        jax.random.PRNGKey(0), cut.replace(param_dtype="bfloat16")))
    assert sum(a.size for a in jax.tree.leaves(tree)) == cut.num_params()
    cache = jax.eval_shape(lambda: models.init_cache_paged(
        cut.replace(dtype="bfloat16"), 5120, 16, state_slots=32))
    assert {k: v.shape for k, v in cache.items()} == {
        "k": (3, 5120, 16, 32, 128), "v": (3, 5120, 16, 32, 128),
        "conv": (9, 32, 34560), "delta": (9, 32, 15, 96, 384)}
    state = sum(math.prod(cache[k].shape) * 4 for k in ("conv", "delta"))
    assert state // 32 == 9 * 2_350_080         # 21.15 MB a request


@pytest.mark.parametrize("wrong", [
    dict(layer_kinds=(("delta",) * 3 + ("full",)) * 7 + ("delta",) * 4),
    dict(layer_kinds=("full",) + ("delta",) * 31),
    dict(layer_kinds=("delta", "window") * 16),
    dict(n_layers=31), dict(delta_key_heads=0), dict(delta_key_dim=0),
    dict(delta_conv=1), dict(delta_value_dim=0), dict(norm="layer"),
    dict(positions="rope"), dict(qk_norm=True), dict(sliding_window=64),
    dict(num_experts=8), dict(attn_qkv_bias=True), dict(tie_embeddings=True),
    dict(post_norms=True), dict(ssm_heads=4)],
    ids=lambda kw: ",".join(kw))
def test_a_linear_hybrid_that_is_not_described_is_refused(wrong):
    with pytest.raises(ValueError):
        _published(**wrong)


@pytest.mark.parametrize("keys", [
    dict(delta_key_heads=4), dict(delta_value_dim=64), dict(delta_conv=4),
    dict(delta_neg_eigval=True), dict(delta_key_dim=8)], ids=lambda kw: ",".join(kw))
@pytest.mark.parametrize("preset", ["llama-debug", "hybrid-state-debug",
                                    "parallel-hybrid-debug",
                                    "windowed-moe-debug"])
def test_other_layouts_refuse_the_linear_hybrids_keys(preset, keys):
    with pytest.raises(ValueError, match="linear hybrid layout"):
        models.get_config(preset).replace(**keys)



