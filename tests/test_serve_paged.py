"""Serving tier (ISSUE 12): paged KV block pool, prefix trie, COW,
numerics parity with ``generate``, chunked prefill, SLO admission,
deadlines, KV-aware routing, and the replica-death chaos case under the
replay generator (no leaked blocks)."""

import dataclasses
import time

import numpy as np
import pytest

from ray_tpu.serve.admission import (AdmissionController,
                                     DeadlineExceededError,
                                     RequestShedError, SLOConfig)
from ray_tpu.serve.kv_cache import BlockPool, KVCacheError, PrefixCache


# ---------------------------------------------------------------------------
# block pool
# ---------------------------------------------------------------------------

def test_block_pool_alloc_free_refcount():
    pool = BlockPool(8, 4)
    assert pool.free_count == 8 and pool.used_count == 0
    a = pool.alloc(3)
    assert len(a) == 3 and pool.free_count == 5
    assert all(pool.refcount(b) == 1 for b in a)
    # all-or-nothing: a too-big claim takes NOTHING
    assert pool.alloc(6) is None
    assert pool.free_count == 5
    # sharing: retain bumps, release drops, last ref frees
    pool.retain(a[0])
    assert pool.need_cow(a[0]) and not pool.need_cow(a[1])
    assert not pool.release(a[0])          # one ref left
    assert pool.release(a[0])              # freed
    assert pool.free_count == 6
    with pytest.raises(KVCacheError):
        pool.release(a[0])                 # double free is a bug
    with pytest.raises(KVCacheError):
        pool.retain(a[0])                  # retain of a free block too
    assert pool.release_all(a[1:]) == 2
    assert pool.free_count == 8
    assert pool.blocks_for_tokens(0) == 0
    assert pool.blocks_for_tokens(1) == 1
    assert pool.blocks_for_tokens(4) == 1
    assert pool.blocks_for_tokens(5) == 2


# ---------------------------------------------------------------------------
# prefix trie
# ---------------------------------------------------------------------------

def test_prefix_trie_hit_miss_and_cap():
    pool = BlockPool(16, 4)
    trie = PrefixCache(pool)
    prompt = list(range(10))               # 2 full blocks + 2 tail tokens
    blocks = pool.alloc(3)
    assert trie.match(prompt) == ([], 0, None)       # cold: miss
    assert trie.insert(prompt, blocks) == 2          # only FULL blocks
    assert len(trie) == 2
    # the trie holds its own refs; the request releases its copies
    pool.release_all(blocks)
    assert pool.refcount(blocks[0]) == 1 and pool.refcount(blocks[2]) == 0

    # longer prompt with the same head: both full blocks reused
    got, matched, cow = trie.match(list(range(8)) + [99, 98, 97])
    assert got == blocks[:2] and matched == 8 and cow is None
    assert pool.refcount(blocks[0]) == 2             # caller now holds one
    pool.release_all(got)

    # EXACT full-block prompt: capped at len-1 -> tail becomes COW source
    got, matched, cow = trie.match(list(range(8)))
    assert got == blocks[:1] and matched == 7 and cow == blocks[1]
    assert pool.refcount(blocks[1]) == 2             # retained for the copy
    pool.release_all(got)
    pool.release(cow)

    # diverging second block: only the first matches
    got, matched, cow = trie.match(list(range(4)) + [77, 77, 77, 77, 5])
    assert got == blocks[:1] and matched == 4 and cow is None
    pool.release_all(got)
    s = trie.stats()
    assert s["hits"] == 3 and s["misses"] == 1


def test_prefix_trie_eviction_lru_and_pinning():
    pool = BlockPool(4, 2)
    trie = PrefixCache(pool)
    a = pool.alloc(1)
    trie.insert([1, 2], a)
    time.sleep(0.01)
    b = pool.alloc(1)
    trie.insert([3, 4], b)
    pool.release_all(a + b)
    assert pool.free_count == 2            # trie pins both
    # a live sharer pins its chain against eviction — and the claimable
    # signal agrees (only the unshared leaf is evictable right now)
    got, _, _ = trie.match([1, 2, 9])
    assert got == a
    assert trie.evictable_count() == 1
    assert trie.evict(2) == 1              # only the unshared LRU leaf goes
    assert pool.refcount(b[0]) == 0 and pool.refcount(a[0]) == 2
    pool.release_all(got)
    assert trie.evict(2) == 1              # now reclaimable
    assert pool.free_count == 4 and len(trie) == 0
    # chains evict leaf-first: parent becomes reclaimable next round
    c = pool.alloc(2)
    trie.insert([5, 6, 7, 8], c)
    pool.release_all(c)
    assert trie.evict(4) == 2
    assert pool.free_count == 4


# ---------------------------------------------------------------------------
# engine: parity, prefix COW, chunked prefill
# ---------------------------------------------------------------------------

def _f32_cfg():
    from ray_tpu import models

    # f32: greedy parity across kernels (bf16 logit ties flip on 1-ULP
    # cross-kernel rounding differences — see test_serve.py's LLM test)
    return dataclasses.replace(models.get_config("llama-debug"),
                               dtype="float32", param_dtype="float32")


def _drain(eng, max_steps=500):
    for _ in range(max_steps):
        if not eng.step():
            return
    raise AssertionError("engine did not drain")


def _run_prompts(eng, prompts, max_new):
    outs = []
    for p in prompts:
        sink = []
        outs.append(sink)
        eng.submit(p, max_new, sink.append)
    _drain(eng)
    return [[t for t in o if t is not None] for o in outs]


@pytest.mark.parametrize("form", ["xla", "pallas_interpret"])
def test_paged_numerics_parity(form, monkeypatch):
    """Same prompts, shared prefixes included: paged (with prefix reuse
    + chunked prefill) == sequential generate, token-exact.
    ``pallas_interpret`` runs the paged engine with the attention KERNEL in
    the step (interpret mode), over the bf16 pool and 128-wide heads it
    takes, against the same engine stepped with the ``jax.numpy`` form."""
    import jax

    from ray_tpu import models
    from ray_tpu.models import transformer as T
    from ray_tpu.ops.attention import set_default_attention_impl
    from ray_tpu.serve.llm import LLMEngine

    kernel = form == "pallas_interpret"
    cfg = _f32_cfg()
    if kernel:
        cfg = dataclasses.replace(cfg, head_dim=128, dtype="bfloat16")
    params = models.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(3)
    shared = rng.integers(0, 256, 12).tolist()
    prompts = [shared + rng.integers(0, 256, n).tolist()
               for n in (3, 9, 5, 17)]

    def paged_engine():
        return LLMEngine(cfg, params, max_slots=4, max_len=64,
                         block_size=4, prefill_chunk=4)

    if kernel:
        refs = _run_prompts(paged_engine(), prompts, 6)
        monkeypatch.setenv("RTPU_ATTN_PALLAS_INTERPRET", "1")
        set_default_attention_impl("pallas")
    else:
        refs = []
        for p in prompts:
            g = T.generate(params, jax.numpy.asarray(
                np.asarray(p, np.int32)[None]), cfg, max_new_tokens=6)
            refs.append([int(x) for x in np.asarray(g[0, len(p):])])
    try:
        paged = paged_engine()
        assert paged.stats["attn_impl"] == ("pallas" if kernel else "xla")
        assert _run_prompts(paged, prompts, 6) == refs
        # run the SAME prompts again: now the trie serves the shared prefix
        # (and the full-prompt repeats exercise the COW path) — still exact
        assert _run_prompts(paged, prompts, 6) == refs
    finally:
        set_default_attention_impl(None)
    assert paged.prefix.stats()["hits"] >= 4
    assert paged.stats["prefix_hit_tokens"] >= 4 * 12
    # every row read its live blocks and no more than its table is wide
    assert 0 < paged.stats["attn_blocks_live"] \
        < paged.stats["attn_blocks_table"]


@pytest.mark.parametrize("budget", [3, 5, 16])
def test_engine_under_a_budget_is_generate_token_for_token(budget,
                                                           monkeypatch):
    """The engine's step program multiplies its weights by ``STEP_BUDGET``
    positions when a step's real ones fit it, by twice as many when they fit
    that, and by the whole grid when not (256 or 512 of a cell's 1024 to
    2048; here 3 or 6 and 5 or 10 of 16, so that the requests' lives cross
    all three; 16 is a grid no wider than the budget, the step as it was):
    tokens equal to sequential ``generate``, and the counters say what the
    program did."""
    import jax
    from conftest import assert_three_widths, watch_step_widths

    from ray_tpu import models
    from ray_tpu.models import transformer as T
    from ray_tpu.serve import llm

    monkeypatch.setattr(llm, "STEP_BUDGET", budget)
    cfg = _f32_cfg()
    params = models.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 256, n).tolist() for n in (3, 9, 5, 17)]
    refs = []
    for p in prompts:
        g = T.generate(params, jax.numpy.asarray(
            np.asarray(p, np.int32)[None]), cfg, max_new_tokens=6)
        refs.append([int(x) for x in np.asarray(g[0, len(p):])])
    eng = llm.LLMEngine(cfg, params, max_slots=4, max_len=64, block_size=4,
                        prefill_chunk=4, prefix_cache=False)
    reals = watch_step_widths(eng)
    assert _run_prompts(eng, prompts, 6) == refs
    st = eng.stats
    # every prompt token and every generated token but each request's last
    assert st["step_positions_real"] == sum(map(len, prompts)) + 4 * 5
    if budget < 16:
        assert_three_widths(eng, reals)
    else:
        assert st["steps_full_width"] == st["steps_second_width"] == 0
        assert st["step_positions_run"] == 16 * st["steps"]


def test_engine_refuses_the_removed_dense_path():
    """``paged`` is still accepted (the benchmark's files pass it) as a
    value that must be true; it selects nothing."""
    from ray_tpu.serve.llm import LLMDeployment, LLMEngine

    cfg = _f32_cfg()
    with pytest.raises(ValueError, match="paged"):
        LLMEngine(cfg, paged=False)
    with pytest.raises(ValueError, match="paged"):
        LLMDeployment("llama-debug", paged=False)
    kw = dict(max_slots=2, max_len=32, block_size=4)
    plain, keyword = LLMEngine(cfg, **kw), LLMEngine(cfg, paged=True, **kw)
    assert not hasattr(plain, "paged")
    assert plain.kv_state() == keyword.kv_state()
    assert "paged" not in plain.kv_state()


def test_prefix_cow_exact_repeat():
    """A prompt repeated EXACTLY forces the capped match: the tail block
    is copy-on-write'd, the original stays immutable for other sharers,
    and generation stays token-exact."""
    import jax

    from ray_tpu import models
    from ray_tpu.models import transformer as T
    from ray_tpu.serve.llm import LLMEngine

    cfg = _f32_cfg()
    params = models.init_params(jax.random.PRNGKey(1), cfg)
    prompt = np.random.default_rng(5).integers(0, 256, 8).tolist()
    g = T.generate(params, jax.numpy.asarray(
        np.asarray(prompt, np.int32)[None]), cfg, max_new_tokens=5)
    ref = [int(x) for x in np.asarray(g[0, len(prompt):])]

    eng = LLMEngine(cfg, params, max_slots=2, max_len=32, block_size=4,
                    prefill_chunk=4)
    assert _run_prompts(eng, [prompt], 5) == [ref]
    before = eng.pool.free_count
    assert _run_prompts(eng, [prompt], 5) == [ref]   # exact repeat: COW
    s = eng.prefix.stats()
    assert s["hits"] == 1 and s["hit_tokens"] == len(prompt) - 1
    assert eng.pool.free_count == before             # no leak either way


def test_chunked_prefill_does_not_stall_decode():
    """A decoding request keeps emitting ~every step while a long prompt
    prefills in chunks beside it (the whole point of chunked prefill)."""
    from ray_tpu.serve.llm import LLMEngine

    eng = LLMEngine(_f32_cfg(), max_slots=2, max_len=256, block_size=16,
                    prefill_chunk=16)
    first = []
    eng.submit([1, 2, 3], 40, first.append)
    for _ in range(10):
        eng.step()                       # first request is decoding now
    tokens_before = len(first)
    long_prompt = list(np.random.default_rng(0).integers(0, 256, 160))
    second = []
    eng.submit(long_prompt, 2, second.append)
    steps = 0
    while second.count(None) == 0:
        eng.step()
        steps += 1
        assert steps < 60, "long prompt starved the engine"
    # the 160-token prompt consumed ~160/16 steps, not 160
    assert steps <= 20
    # and the decoding request kept producing alongside the prefill
    emitted_during = len([t for t in first if t is not None]) \
        - tokens_before
    assert emitted_during >= steps - 2


# ---------------------------------------------------------------------------
# one step of lookahead: step n+1 is dispatched before step n is read
# ---------------------------------------------------------------------------

def _generate(params, cfg, prompt, n):
    import jax

    from ray_tpu.models import transformer as T

    g = T.generate(params, jax.numpy.asarray(
        np.asarray(prompt, np.int32)[None]), cfg, max_new_tokens=n)
    return [int(x) for x in np.asarray(g[0, len(prompt):])]


@pytest.fixture(scope="module")
def f32_model():
    import jax

    from ray_tpu import models

    cfg = _f32_cfg()
    return cfg, models.init_params(jax.random.PRNGKey(0), cfg)


def _tokens(sink):
    return [t for t in sink if isinstance(t, int)]


@pytest.mark.parametrize("arrivals", ["together", "staggered"])
def test_lookahead_engine_is_generate_token_for_token(f32_model, arrivals):
    """A mixed batch (prompts of under a chunk and of several, answers of
    one token and of many, arriving together or while others decode) through
    the lookahead: every token equal to sequential ``generate``, and all but
    the steps that found the device idle were dispatched ahead."""
    from ray_tpu.serve.llm import LLMEngine

    cfg, params = f32_model
    rng = np.random.default_rng(11)
    jobs = [(rng.integers(0, 256, n).tolist(), new)
            for n, new in ((3, 1), (9, 7), (5, 2), (17, 5), (2, 9), (12, 3))]
    eng = LLMEngine(cfg, params, max_slots=3, max_len=64, block_size=4,
                    prefill_chunk=4, prefix_cache=False)
    sinks = []
    for k, (prompt, new) in enumerate(jobs):
        sinks.append([])
        eng.submit(prompt, new, sinks[-1].append)
        if arrivals == "staggered":
            for _ in range(k + 1):
                eng.step()
    _drain(eng)
    for (prompt, new), sink in zip(jobs, sinks):
        assert _tokens(sink) == _generate(params, cfg, prompt, new)
        assert sink[-1] is None and sink.count(None) == 1
    st = eng.stats
    assert eng._inflight is None and all(r is None for r in eng._slots)
    assert eng.pool.free_count == eng.pool.num_blocks
    assert st["tokens_generated"] == sum(new for _, new in jobs)
    # only a step that found nothing in flight is not ahead: the first, and
    # the first after each time the engine ran dry
    assert 0 < st["steps"] - st["steps_dispatched_ahead"] <= 2
    assert st["rows_run_past_end"] == 0


def test_a_request_that_ends_on_eos_emits_nothing_after_it(f32_model):
    """``eos`` is found when its step is READ, one step late: the request
    ran one row-step past its end, whose token is dropped; nothing follows
    the ``eos``, no block leaks, and the trie holds the prompt's full blocks
    only (the stale row's write lay at a generated position). A request
    decoding beside it is not disturbed."""
    from ray_tpu.serve.llm import LLMEngine

    cfg, params = f32_model
    rng = np.random.default_rng(12)
    prompt, other = (rng.integers(0, 256, n).tolist() for n in (10, 6))
    ref = _generate(params, cfg, prompt, 12)
    k = next(i for i in range(2, 11) if ref[i] not in ref[:i])
    eng = LLMEngine(cfg, params, max_slots=2, max_len=64, block_size=4,
                    prefill_chunk=4)
    out, beside = [], []
    eng.submit(prompt, 12, out.append, eos=ref[k])
    eng.submit(other, 12, beside.append)
    _drain(eng)
    assert out == ref[:k + 1] + [None]
    assert _tokens(beside) == _generate(params, cfg, other, 12)
    assert eng.stats["rows_run_past_end"] == 1
    assert eng.stats["tokens_generated"] == k + 1 + 12
    kv = eng.kv_state()
    assert kv["inflight"] == 0 and eng._inflight is None
    assert kv["prefix"]["nodes"] == len(prompt) // 4 + len(other) // 4
    assert kv["kv_free"] + kv["prefix"]["nodes"] == kv["kv_total"]
    # served again from the trie's blocks: still the same tokens
    again = []
    eng.submit(prompt, 12, again.append, eos=ref[k])
    _drain(eng)
    assert again == out and eng.stats["prefix_hit_tokens"] == 8


@pytest.mark.parametrize("how", ["cancel", "deadline", "abort_all"])
def test_ending_a_request_with_a_step_in_flight_holds_nothing(f32_model,
                                                              how):
    """``cancel``, a deadline and ``abort_all`` while the device runs a step
    nobody has read: the step's tokens for the ended requests are dropped,
    the others go on, and no slot or block stays held. One request's LAST
    token is in flight at that instant (it has left its slot and holds its
    blocks)."""
    from ray_tpu.serve.llm import LLMEngine

    cfg, params = f32_model
    rng = np.random.default_rng(13)
    eng = LLMEngine(cfg, params, max_slots=3, max_len=64, block_size=4,
                    prefill_chunk=4)
    short, victim, stays = [], [], []
    p_short, p_victim, p_stays = (rng.integers(0, 256, n).tolist()
                                  for n in (3, 9, 5))
    r_short = eng.submit(p_short, 3, short.append)
    r_victim = eng.submit(p_victim, 20, victim.append,
                          deadline_s=60.0 if how == "deadline" else None)
    eng.submit(p_stays, 8, stays.append)
    while not any(last for *_, last in
                  (eng._inflight.rows if eng._inflight else ())):
        assert eng.step()
    flight = eng._inflight
    assert r_short not in eng._slots and r_short.table   # slot gone, blocks held
    assert any(r is r_victim for _i, r, _s, _l in flight.rows)
    if how == "cancel":
        eng.cancel(r_victim)
    elif how == "deadline":
        r_victim.deadline = time.monotonic() - 1.0   # it has just passed
    else:
        eng.abort_all(RuntimeError("loop died"))
        assert eng._inflight is None
    _drain(eng)
    assert eng._inflight is None and all(r is None for r in eng._slots)
    assert eng.pool.free_count + len(eng.prefix) == eng.pool.num_blocks
    got = len(_tokens(victim))
    assert got < 20 and _tokens(victim) == _generate(
        params, cfg, p_victim, 20)[:got]
    if how == "abort_all":
        for sink in (short, victim, stays):
            assert isinstance(sink[-1], RuntimeError)
        return
    if how == "deadline":
        assert isinstance(victim[-1], DeadlineExceededError)
        assert eng.stats["deadline_drops"] == 1
    assert short == _generate(params, cfg, p_short, 3) + [None]
    assert stays == _generate(params, cfg, p_stays, 8) + [None]


def test_a_slot_given_away_with_its_last_token_in_flight_serves_both(
        f32_model):
    """One slot, two requests: the second takes the slot in the step
    dispatched right after the first's last one, before that one is read
    (the device runs in order); both are served as ``generate`` serves
    them."""
    from ray_tpu.serve.llm import LLMEngine

    cfg, params = f32_model
    rng = np.random.default_rng(14)
    a, b = (rng.integers(0, 256, n).tolist() for n in (6, 7))
    eng = LLMEngine(cfg, params, max_slots=1, max_len=64, block_size=4,
                    prefill_chunk=4, prefix_cache=False)
    out_a, out_b = [], []
    r_a = eng.submit(a, 4, out_a.append)
    r_b = eng.submit(b, 5, out_b.append)
    handed_over, inner = [], eng._step_fn

    def watching(*args):
        # at the dispatch of a step of B's: is A's last token still unread?
        if eng._slots[0] is r_b and len(out_a) < 5:
            handed_over.append((len(_tokens(out_a)), bool(r_a.table)))
        return inner(*args)

    eng._step_fn = watching
    _drain(eng)
    # B's first chunk went to the device with A's fourth token unread and
    # A's blocks still A's
    assert handed_over == [(3, True)]
    assert out_a == _generate(params, cfg, a, 4) + [None]
    assert out_b == _generate(params, cfg, b, 5) + [None]
    assert eng.pool.free_count == eng.pool.num_blocks


def test_the_seam_the_benchmark_holds(f32_model):
    """What ``benchmark/kinds/serve_replica.py::BenchEngine`` leans on
    (``ROADMAP.md`` D6): one ``_step_fn`` call of seven arguments in every
    ``_advance_paged``, ``stats["steps"]`` grown by one in exactly the
    ``step()`` calls that ran it, every slot's request at that call a row of
    the step with ``pos`` and ``consumed`` as the step finds them, and under
    ``capture`` each sampling row's logits through ``_sample`` just before
    its ``emit``; without it ``_sample`` is never called."""
    from ray_tpu.serve.llm import LLMEngine

    cfg, params = f32_model

    class Tapped(LLMEngine):
        def __init__(self, *args, **kw):
            super().__init__(*args, **kw)
            self.calls = {"step_fn": 0, "advance": 0, "sample": 0}
            self.grew, self.fed, self.last_logits = [], 0, None
            inner = self._step_fn

            def stamped(*a):
                assert len(a) == 7
                self.calls["step_fn"] += 1
                return inner(*a)

            self._step_fn = stamped

        def _advance_paged(self, jax, jnp):
            for req in self._slots:
                if req is not None:
                    left = len(req.prompt) - req.consumed
                    self.fed += min(self.prefill_chunk, left) \
                        if left > 0 else 1
                    assert req.pos == req.consumed + (
                        0 if left > 0 else req.generated - 1 + (
                            req in {r for _i, r, s, _l in
                                    self._inflight.rows if s}))
            self.calls["advance"] += 1
            before = self.calls["step_fn"]
            out = super()._advance_paged(jax, jnp)
            assert self.calls["step_fn"] == before + 1
            return out

        def _sample(self, logits):
            self.calls["sample"] += 1
            if self.capture:
                self.last_logits = logits.copy()
            return super()._sample(logits)

        def step(self):
            n0, a0 = self.stats["steps"], self.calls["advance"]
            busy = super().step()
            self.grew.append((self.stats["steps"] - n0,
                              self.calls["advance"] - a0))
            return busy

    rng = np.random.default_rng(15)
    prompts = [rng.integers(0, 256, n).tolist() for n in (3, 9, 6)]
    eng = Tapped(cfg, params, max_slots=2, max_len=64, block_size=4,
                 prefill_chunk=4, prefix_cache=False)
    assert _run_prompts(eng, prompts, 4) == [
        _generate(params, cfg, p, 4) for p in prompts]
    assert eng.calls["sample"] == 0
    assert eng.calls["step_fn"] == eng.calls["advance"] == eng.stats["steps"]
    assert all(a == b and a in (0, 1) for a, b in eng.grew)
    assert eng.grew[-1] == (0, 0)      # the last call only read
    assert eng.fed == eng.stats["step_positions_real"]

    eng.capture = True
    pairs = []

    def sink(item):
        if isinstance(item, int):
            pairs.append((item, eng.last_logits))

    for p in prompts:
        eng.submit(p, 4, sink)
    _drain(eng)
    assert eng.calls["sample"] == len(pairs) == 12
    assert all(int(np.argmax(row)) == tok for tok, row in pairs)
    # each emit saw the logits of ITS row: no two tokens share an array
    assert len({id(row) for _, row in pairs}) == 12


def test_sampling_on_the_device_is_seeded(f32_model):
    """``temperature`` > 0 draws on the device from the engine's key folded
    with the step's number: two engines of one seed agree token for token,
    another seed parts from them, and a draw is not the arg-max."""
    from ray_tpu.serve.llm import LLMEngine

    cfg, params = f32_model
    prompt = np.random.default_rng(16).integers(0, 256, 7).tolist()

    def serve(seed, temperature=1.5):
        eng = LLMEngine(cfg, params, max_slots=2, max_len=64, block_size=4,
                        prefill_chunk=4, temperature=temperature, seed=seed)
        return _run_prompts(eng, [prompt, prompt[:4]], 16)

    first = serve(3)
    assert first == serve(3)
    assert first != serve(4)
    assert first != serve(3, temperature=0.0)
    assert all(0 <= t < cfg.vocab_size for out in first for t in out)


@pytest.mark.parametrize("model", [
    "llama-debug", "mistral-debug", "sparse-moe-debug", "hybrid-state-debug",
    "parallel-hybrid-debug", "latent-moe-debug", "windowed-moe-debug"])
def test_the_step_program_is_the_models_step_and_nothing_else(model):
    """Sampling and feeding forward are programs of their own: the engine's
    step program lowers to the text of ``models.decode_step_paged`` itself
    under the seven arguments the benchmark lowers it with (locations
    stripped), for every layout of ``models.layouts``. And the yardstick
    (``ray_tpu.devtools.step_text``, which builds no engine) lowers that
    same text from the settings alone."""
    import jax
    import jax.numpy as jnp

    from ray_tpu import models
    from ray_tpu.devtools import step_text
    from ray_tpu.serve import llm

    cfg = models.get_config(model)
    settings = dict(max_slots=2, max_len=32, block_size=4, prefill_chunk=4)
    eng = llm.LLMEngine(cfg, **settings)
    spec = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    args = (spec(eng.params), spec(eng._cache), i32(2, 4),
            i32(2, eng._tbl_width), i32(2), i32(2),
            jax.ShapeDtypeStruct((2,), jnp.bool_))

    def plain(params, cache, tokens, tables, pos, nvalid, active):
        return models.decode_step_paged(
            params, cache, tokens, tables, pos, nvalid, cfg, active=active,
            step_stats=True, budget=llm.STEP_BUDGET)

    text = step_text.lowered_text(eng._raw_step_paged, args)
    assert text == step_text.lowered_text(plain, args)
    assert text == step_text.step_text(cfg, **settings)
    assert [n for n in ("serve::sample", "serve::feed_tokens")
            if n not in (eng._sample_fn.name, eng._feed_fn.name)] == []


# ---------------------------------------------------------------------------
# admission + deadlines
# ---------------------------------------------------------------------------

def test_admission_controller_gates():
    ac = AdmissionController(SLOConfig(ttft_s=1.0, max_queue_s=0.5,
                                       tpot_s=0.05))
    # cold controller (no step estimate): everything admits
    ac.check_admit(64, 10, 640, 8, 1, 0)
    ac.observe_step(0.2)
    # queue gate: 10 queued * 0.2s = 2s > 0.5s
    with pytest.raises(RequestShedError) as e:
        ac.check_admit(8, 10, 80, 8, 1, 0)
    assert e.value.reason == "queue"
    # ttft gate: own prefill alone projects over 1s
    with pytest.raises(RequestShedError) as e:
        ac.check_admit(80, 0, 0, 8, 1, 0)
    assert e.value.reason == "ttft"
    # tpot gate: decode already slower than target with live streams
    with pytest.raises(RequestShedError) as e:
        ac.check_admit(1, 0, 0, 8, 1, 4)
    assert e.value.reason == "tpot"
    # deadline gate: projection exceeds the request's own budget
    ac2 = AdmissionController(SLOConfig())
    ac2.observe_step(0.2)
    with pytest.raises(RequestShedError) as e:
        ac2.check_admit(80, 0, 0, 8, 1, 0, deadline_s=0.5)
    assert e.value.reason == "deadline"
    snap = ac.snapshot()
    assert snap["shed"] == 3 and snap["shed_by_reason"]["ttft"] == 1


def test_engine_sheds_and_enforces_queue_deadline():
    from ray_tpu.serve.llm import LLMEngine

    # ttft_s=0 arms an always-shed gate once a step time is measured
    eng = LLMEngine(_f32_cfg(), max_slots=1, max_len=64,
                    slo=SLOConfig(ttft_s=1e-9))
    out = []
    eng.submit([1, 2, 3], 2, out.append)   # cold: admitted
    _drain(eng)
    with pytest.raises(RequestShedError):
        eng.submit([1, 2, 3], 2, out.append)

    # deadline enforced ACROSS ADMISSION QUEUEING: with one slot busy on
    # a long generation, a queued request expires before ever running.
    # Both submits land before the first step (cold projection admits);
    # FIFO puts the long request in the slot and the deadlined one in
    # the queue, where it must expire — not run late.
    eng2 = LLMEngine(_f32_cfg(), max_slots=1, max_len=128)
    slow, fast = [], []
    eng2.submit([1, 2, 3], 60, slow.append)
    eng2.submit([4, 5, 6], 4, fast.append, deadline_s=0.05)
    deadline = time.monotonic() + 30
    while not fast and time.monotonic() < deadline:
        eng2.step()
    assert fast and isinstance(fast[0], DeadlineExceededError), fast[:1]
    assert eng2.stats["deadline_drops"] == 1
    _drain(eng2)
    # the expired request never claimed blocks; the finished one freed
    # everything back except what the trie adopted
    assert eng2.pool.free_count + len(eng2.prefix) == eng2.pool.num_blocks


def test_pool_pressure_rejects_impossible_and_keeps_stats_honest():
    """A request bigger than the WHOLE pool is rejected at submit (it
    could never be admitted — queueing it would pin the FIFO head and
    busy-spin the loop); a merely-queued request re-running its prefix
    match every step must not inflate the hit counters."""
    from ray_tpu.serve.llm import LLMEngine

    eng = LLMEngine(_f32_cfg(), max_slots=2, max_len=64, block_size=4,
                    num_blocks=8, prefill_chunk=4)
    with pytest.raises(ValueError, match="KV blocks"):
        eng.submit(list(range(30)), 8, lambda t: None)   # 10 > 8 blocks

    # fill the pool with one request, seed the trie, then queue a
    # prefix-hitting request that cannot claim yet
    done = []
    prompt = list(np.random.default_rng(0).integers(0, 256, 16))
    eng.submit(prompt, 8, done.append)                   # 6 of 8 blocks
    hog = []
    eng.submit(list(np.random.default_rng(1).integers(0, 256, 8)), 16,
               hog.append)                               # 6 blocks: waits
    waiter = []
    eng.submit(prompt[:12] + [9], 4, waiter.append)      # prefix of 1st
    for _ in range(6):
        eng.step()
    s = eng.prefix.stats()
    # the queued waiter's repeated failed claims count AT MOST once
    assert s["hits"] + s["misses"] <= 2, s
    _drain(eng)
    assert eng.pool.free_count + len(eng.prefix) == eng.pool.num_blocks


# ---------------------------------------------------------------------------
# block-leak audit under churn (cancel mid-stream)
# ---------------------------------------------------------------------------

def test_no_block_leak_under_cancel_churn():
    from ray_tpu.serve.llm import LLMEngine

    eng = LLMEngine(_f32_cfg(), max_slots=4, max_len=64, block_size=4,
                    prefill_chunk=4)
    rng = np.random.default_rng(7)
    shared = rng.integers(0, 256, 8).tolist()
    reqs = []
    for i in range(12):
        sink = []
        p = shared + rng.integers(0, 256, int(rng.integers(1, 20))).tolist()
        reqs.append((eng.submit(p, 8, sink.append), sink))
    for step in range(8):
        eng.step()
        if step in (2, 4):               # cancel a batch mid-flight
            for r, _ in reqs[step::3]:
                eng.cancel(r)
    _drain(eng)
    # every non-trie block is back on the free list
    assert eng.pool.free_count + len(eng.prefix) == eng.pool.num_blocks
    # and the trie's blocks are exactly single-referenced
    trie_blocks = eng.pool.num_blocks - eng.pool.free_count
    assert trie_blocks == len(eng.prefix)
    # the ROUTING/AUTOSCALE signal reads the warm idle replica as fully
    # claimable (prefix retention is cache value, not pressure)
    assert eng.kv_state()["kv_claimable"] == eng.pool.num_blocks


# ---------------------------------------------------------------------------
# routing + autoscaling (controller-level)
# ---------------------------------------------------------------------------

def test_controller_kv_loads_and_autoscale():
    from ray_tpu.serve.controller import ServeController

    class _FakeReplica:
        def __init__(self, aid):
            class _Id:
                def __init__(self, b):
                    self._b = b

                def binary(self):
                    return self._b

            self._actor_id = _Id(aid)

    ctrl = ServeController.__new__(ServeController)
    ctrl._deployments = {}
    ctrl._version = 0
    ctrl._metrics = {}
    ctrl._deployments["llm"] = {
        "replicas": [_FakeReplica(b"a"), _FakeReplica(b"b")],
        "target": 2,
        "spec": {"config": {
            "autoscaling_config": {
                "min_replicas": 1, "max_replicas": 4,
                "target_ongoing_requests": 100.0,
                "upscale_factor": 1.5, "downscale_factor": 0.0,
                "target_kv_utilization": 0.5},
            "ray_actor_options": {"num_cpus": 2}}},
    }
    ctrl.report_replica_load("llm", b"a",
                             {"inflight": 3, "kv_free": 2, "kv_total": 32})
    ctrl.report_replica_load("llm", b"b",
                             {"inflight": 1, "kv_free": 4, "kv_total": 32})
    loads = ctrl.get_replica_loads("llm")
    assert loads[b"a"]["kv_free"] == 2 and "ts" in loads[b"a"]
    # ~92% average KV occupancy vs target 0.5 -> desired ~2*1.84 -> 4
    assert ctrl._desired_replicas("llm") == 4
    # v2 bridge: 2 missing replicas -> 2 bundles of the actor's resources
    bundles = ctrl.v2_demand()
    assert bundles == [{"CPU": 2.0}, {"CPU": 2.0}]
    # explicit num_cpus=0 advertises NO phantom CPU demand
    ctrl._deployments["llm"]["spec"]["config"]["ray_actor_options"] = {
        "num_cpus": 0, "resources": {"tpu_slot": 1}}
    assert ctrl.v2_demand() == [{"tpu_slot": 1.0}, {"tpu_slot": 1.0}]
    # death report prunes the corpse's load record
    ctrl._deployments["llm"]["spec"]["config"]["num_replicas"] = 2
    ctrl._kill = lambda r: None
    ctrl._make_replica = lambda spec: _FakeReplica(b"c")
    ctrl.report_replica_death("llm", b"a")
    assert b"a" not in ctrl.get_replica_loads("llm")


def test_handle_scores_fold_in_kv_and_exclude(monkeypatch):
    from ray_tpu.serve.handle import DeploymentHandle

    class _Id:
        def __init__(self, b):
            self._b = b

        def binary(self):
            return self._b

    class _Rep:
        def __init__(self, b):
            self._actor_id = _Id(b)

    h = DeploymentHandle("d")
    h._replicas = [_Rep(b"a"), _Rep(b"b")]
    h._depths = [1, 1]
    h._depth_ts = time.monotonic() + 3600     # pin the depth view
    h._delta = {0: 0, 1: 0}
    h._has_loads = True                       # replicas have reported
    h._route_state["kv_next"] = time.monotonic() + 3600  # pin the view
    h._route_state["kv_loads"] = {
        b"a": {"kv_free": 0, "kv_total": 10, "ts": time.time()},
        b"b": {"kv_free": 10, "kv_total": 10, "ts": time.time()}}
    scores = h._scores()
    assert scores[0] > scores[1]              # full replica penalized
    picks = {h._pick_replica() for _ in range(20)}
    assert picks == {1}
    # stale report -> no KV penalty
    h._route_state["kv_loads"][b"a"]["ts"] = time.time() - 3600
    assert h._scores()[0] == pytest.approx(1.0)
    # exclude bars the named replica while an alternative exists
    for _ in range(10):
        assert h._pick_replica(exclude=b"b") == 0
    # round-robin mode ignores scores
    monkeypatch.setenv("RTPU_SERVE_ROUTING", "rr")
    assert {h._pick_replica() for _ in range(4)} == {0, 1}
    # method-style clones SHARE routing state by reference: a fresh
    # clone per call must advance the same rr cursor (and keep the KV
    # TTL), not restart from the parent's snapshot every time
    clone_picks = set()
    for _ in range(4):
        c = h.options(method_name="kv_state")
        assert c._route_state is h._route_state
        clone_picks.add(c._pick_replica())
    assert clone_picks == {0, 1}


# ---------------------------------------------------------------------------
# serve-stack fault injection + chaos replay (quick tier)
# ---------------------------------------------------------------------------

@pytest.fixture
def rt_serve():
    import ray_tpu
    from ray_tpu import serve

    ray_tpu.init(num_cpus=8, ignore_reinit_error=True)
    yield
    serve.shutdown()
    ray_tpu.shutdown()


def test_replica_death_retry_excludes_dead_pick(rt_serve, monkeypatch):
    """The r9 death-report path folded into the load-aware picker: with
    the controller's death report suppressed (unreachable-controller
    fault) the routing table still lists the corpse — the retry must
    re-consult routing state WITH the dead pick excluded, not re-roll
    the same pick."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.handle import DeploymentHandle

    @serve.deployment(num_replicas=2)
    class Echo:
        def __call__(self, x):
            return x + 100

    handle = serve.run(Echo.bind(), name="retry_app")
    assert handle.remote(1).result(timeout_s=60) == 101
    handle._refresh(force=True)
    victim = handle._replicas[0]
    ray_tpu.kill(victim)

    # fault injection: the death report and forced refresh are lost
    # (wedged controller), so the table keeps naming the dead replica
    monkeypatch.setattr(DeploymentHandle, "_replica_died",
                        lambda self, replica: None)
    # and the unlucky first pick lands ON the corpse — exactly the case
    # the exclude exists for
    orig = DeploymentHandle._pick_replica

    def biased(self, exclude=None):
        if exclude is None:
            return 0
        return orig(self, exclude=exclude)

    monkeypatch.setattr(DeploymentHandle, "_pick_replica", biased)
    assert handle.remote(7).result(timeout_s=60) == 107
    from ray_tpu import serve as _s

    _s.delete("Echo")


def test_replay_replica_death_no_block_leak(rt_serve):
    """Chaos case from ISSUE 12: kill a replica mid-replay. New requests
    re-route to the survivor (the replay keeps completing), the
    controller reconciles a replacement, and NO replica leaks KV blocks
    — every live engine's free count returns to total minus what its
    prefix trie legitimately pins."""
    import threading

    import ray_tpu
    from conftest import poll_until
    from _replay import TraceConfig, gen_trace, replay
    from ray_tpu import serve
    from ray_tpu.serve import LLMDeployment

    app = serve.deployment(
        LLMDeployment, num_replicas=2,
        ray_actor_options={"max_concurrency": 16, "num_cpus": 0},
    ).bind("llama-debug", max_slots=4, max_len=96, block_size=8,
           prefill_chunk=8, seed=0)
    handle = serve.run(app, name="llm_chaos")
    sh = handle.options(stream=True)
    for _ in range(4):  # warm both replicas' compiles out of the replay
        list(sh.remote([1, 2, 3], 2))
    handle._refresh(force=True)
    victim = handle._replicas[0]

    killer = threading.Timer(0.8, lambda: ray_tpu.kill(victim))
    killer.start()
    cfg = TraceConfig(n_requests=24, n_tenants=2,
                      shared_prefix_tokens=16, suffix_tokens_mean=6,
                      max_new_tokens=6, burst_rps=20.0, seed=1)
    stats = replay(lambda req: sh.remote(req.prompt, req.max_new),
                   gen_trace(cfg), time_scale=1.0)
    killer.cancel()
    # the tier keeps serving through the death: errors are bounded by
    # the streams that were IN FLIGHT on the victim (half-consumed
    # streams cannot be resumed); everything else completes
    assert stats.started == 24
    assert stats.completed >= 24 - 8, vars(stats)
    assert stats.completed + stats.errors + stats.shed \
        + stats.deadline == 24

    # controller reconciles back to 2 replicas
    ctrl = ray_tpu.get_actor("SERVE_CONTROLLER")
    poll_until(
        lambda: ray_tpu.get(ctrl.list_deployments.remote())[
            "LLMDeployment"]["num_replicas"] == 2,
        timeout=60, desc="replacement replica reconciled")

    # zero leaked blocks on every LIVE replica: drain, then the
    # free-block count (the rtpu_serve_kv_blocks_free gauge's source)
    # must equal total minus the prefix trie's legitimate pins
    handle._refresh(force=True)

    def no_leaks():
        states = [ray_tpu.get(r.handle_request.remote("kv_state", (), {}),
                              timeout=30)
                  for r in handle._replicas]
        return all(
            s["inflight"] == 0 and s["queued"] == 0
            and s["kv_free"] + s["prefix"]["nodes"] == s["kv_total"]
            for s in states) and states

    states = poll_until(no_leaks, timeout=60,
                        desc="all replicas drained with zero leaked blocks")
    # prefix reuse actually happened during the replay on the survivor
    assert any(s["prefix"]["hits"] > 0 for s in states), states
    serve.delete("LLMDeployment")
