"""Multi-model serving plane (ISSUE 16): model registry (arena-paged
weights, LRU under a byte budget, pinning), speculative decoding
(greedy token-exactness for both drafters, acceptance fallback),
multiplexed deployment (lazy engines, swap counters, close hygiene),
and the routing legs (model residency fold, prefix affinity)."""

import dataclasses
import threading
import time

import numpy as np
import pytest

from ray_tpu.serve.admission import RequestShedError


def _f32_cfg(name="llama-debug"):
    from ray_tpu import models

    # f32: greedy parity across kernels (bf16 logit ties flip on 1-ULP
    # cross-kernel rounding differences — see test_serve_paged.py)
    return dataclasses.replace(models.get_config(name),
                               dtype="float32", param_dtype="float32")


def _drain(eng, max_steps=800):
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


# ---------------------------------------------------------------------------
# model registry: budget, LRU, pinning, deltas
# ---------------------------------------------------------------------------

def test_registry_register_validation():
    from ray_tpu.serve.multiplex import ModelRegistry

    reg = ModelRegistry(budget_bytes=0)
    cfg = _f32_cfg()
    reg.register("m0", cfg)
    with pytest.raises(ValueError, match="already registered"):
        reg.register("m0", cfg)
    with pytest.raises(ValueError, match="needs a config"):
        reg.register("m1")
    with pytest.raises(ValueError, match="not registered"):
        reg.register("v0", base="nope", delta={"targets": {}})
    with pytest.raises(ValueError, match="no delta"):
        reg.register("v0", base="m0")
    assert "m0" in reg and reg.models() == ["m0"]


def test_registry_lru_never_evicts_pinned():
    """The acceptance-criterion invariant: eviction makes room from the
    LRU UNPINNED tail; when every resident model is pinned the request
    sheds with reason=model_budget instead of yanking weights out from
    under an in-flight decode."""
    from ray_tpu import models
    from ray_tpu.serve.multiplex import ModelRegistry

    cfg = _f32_cfg()
    one = models.params_bytes(models.init_params(
        __import__("jax").random.PRNGKey(0), cfg))
    # budget fits exactly one resident model
    reg = ModelRegistry(budget_bytes=one + 1)
    reg.register("m0", cfg, seed=0)
    reg.register("m1", cfg, seed=1)

    reg.ensure_resident("m0")
    reg.pin("m0")
    with pytest.raises(RequestShedError) as e:
        reg.ensure_resident("m1")
    assert e.value.reason == "model_budget"
    snap = reg.snapshot()
    assert snap["m0"]["resident"] and snap["m0"]["state"] == "hbm"
    assert not snap["m1"]["resident"]

    # unpin -> the LRU victim is evictable and m1 swaps in
    reg.unpin("m0")
    reg.ensure_resident("m1")
    snap = reg.snapshot()
    assert not snap["m0"]["resident"] and snap["m0"]["swaps_out"] == 1
    assert snap["m1"]["resident"] and snap["m1"]["swaps_in"] == 1
    # LRU order: touch m1, then re-admit m0 -> m1 was just used, but it
    # is the ONLY unpinned resident, so it goes
    reg.ensure_resident("m0")
    assert reg.snapshot()["m1"]["swaps_out"] == 1
    with pytest.raises(RuntimeError, match="unpin"):
        reg.unpin("m0")


def test_registry_evict_cb_and_reacquire():
    """Eviction fires the bound engine drop hook; ensure_resident hands
    back fresh params afterwards (the params_provider reacquire path)."""
    import jax

    from ray_tpu import models
    from ray_tpu.serve.multiplex import ModelRegistry

    cfg = _f32_cfg()
    one = models.params_bytes(models.init_params(jax.random.PRNGKey(0),
                                                 cfg))
    reg = ModelRegistry(budget_bytes=one + 1)
    reg.register("m0", cfg, seed=0)
    reg.register("m1", cfg, seed=1)
    dropped = []
    reg.bind("m0", lambda: dropped.append("m0"))
    p0 = reg.ensure_resident("m0")
    reg.ensure_resident("m1")
    assert dropped == ["m0"]
    p0b = reg.ensure_resident("m0")          # swap back in
    assert p0b is not p0
    np.testing.assert_array_equal(np.asarray(p0["embed"]),
                                  np.asarray(p0b["embed"]))


def test_registry_delta_variant_shares_base():
    """A base+delta variant materializes via apply_delta, charges only
    its unique bytes, and shares untouched leaves with the base."""
    import jax

    from ray_tpu import models
    from ray_tpu.serve.multiplex import ModelRegistry

    cfg = _f32_cfg()
    base_params = models.init_params(jax.random.PRNGKey(0), cfg)
    delta = models.make_delta(jax.random.PRNGKey(9), cfg, rank=2,
                              scale=0.1)
    reg = ModelRegistry(budget_bytes=0)
    reg.register("base", cfg, params=base_params)
    reg.register("tuned", base="base", delta=delta)
    snap = reg.snapshot()
    assert snap["tuned"]["base"] == "base"
    assert 0 < snap["tuned"]["bytes"] < snap["base"]["bytes"]

    got = reg.ensure_resident("tuned")
    want = models.apply_delta(reg.ensure_resident("base"), delta)
    for leaf in ("wq", "wv"):
        np.testing.assert_allclose(np.asarray(got["layers"][leaf]),
                                   np.asarray(want["layers"][leaf]),
                                   rtol=1e-6)
    # untouched leaves are the SAME arrays as the resident base
    bp = reg.ensure_resident("base")
    assert got["layers"]["wk"] is bp["layers"]["wk"]
    assert got["embed"] is bp["embed"]


# ---------------------------------------------------------------------------
# speculative decoding: exact greedy parity + fallback
# ---------------------------------------------------------------------------

def _spec_parity_case(drafter, repeat_bias=0.0, **spec_kw):
    import jax

    from ray_tpu import models
    from ray_tpu.serve.llm import LLMEngine
    from ray_tpu.serve.multiplex import SpeculativeLLMEngine

    cfg = _f32_cfg()
    params = models.init_params(jax.random.PRNGKey(0), cfg)
    if repeat_bias:
        # lean the head towards the token just read: greedy output then
        # revisits its own n-grams now and then, which is what prompt
        # lookup drafts from. The untrained model alone never repeats
        # one in 24 tokens, so every draft misses (0 of 59 accepted).
        params = dict(params, lm_head=params["lm_head"]
                      + repeat_bias * params["embed"].T)
    rng = np.random.default_rng(11)
    # a mix: repetitive prompts (drafts land) + random ones (they don't)
    prompts = [
        [1, 2, 3, 4, 1, 2, 3, 4, 1, 2],
        rng.integers(0, 256, 7).tolist(),
        [5, 6, 5, 6, 5, 6, 5],
        rng.integers(0, 256, 19).tolist(),
    ]
    plain = LLMEngine(cfg, params, max_slots=4, max_len=96,
                      block_size=4, prefill_chunk=8)
    refs = _run_prompts(plain, prompts, 24)

    spec = SpeculativeLLMEngine(cfg, params, drafter=drafter,
                                max_slots=4, max_len=96,
                                block_size=4, prefill_chunk=8, **spec_kw)
    outs = _run_prompts(spec, prompts, 24)
    assert outs == refs, "speculative output diverged from plain greedy"
    return spec


def test_spec_ngram_exact_parity():
    spec = _spec_parity_case("ngram", repeat_bias=1.0, spec_k=4,
                             spec_accept_floor=0.0)
    assert spec.stats["spec_rounds"] > 0
    assert spec.stats["spec_accepted"] > 0       # drafts actually landed
    assert spec.stats["spec_accepted"] < spec.stats["spec_proposed"]
    s = spec.kv_state()["spec"]
    assert s["spec_accepted"] <= s["spec_proposed"]


def test_spec_model_drafter_exact_parity():
    # draft model: SAME debug config, different seed — vocab matches,
    # proposals mostly miss; exactness must hold regardless
    spec = _spec_parity_case("model", spec_k=3, draft_seed=5,
                             spec_accept_floor=0.0)
    assert spec.stats["spec_rounds"] > 0


def test_spec_validation():
    from ray_tpu.serve.multiplex import SpeculativeLLMEngine

    cfg = _f32_cfg()
    with pytest.raises(ValueError, match="greedy"):
        SpeculativeLLMEngine(cfg, temperature=0.7)
    with pytest.raises(ValueError, match="paged"):
        SpeculativeLLMEngine(cfg, paged=False)
    with pytest.raises(ValueError, match="spec_k"):
        SpeculativeLLMEngine(cfg, spec_k=0)
    with pytest.raises(ValueError, match="drafter"):
        SpeculativeLLMEngine(cfg, drafter="oracle")
    # model drafter with a mismatched vocab fails at first propose
    small = dataclasses.replace(cfg, vocab_size=128)
    eng = SpeculativeLLMEngine(cfg, drafter="model", draft_model=small,
                               max_slots=2, max_len=64)
    eng.submit([1, 2, 3], 4, lambda t: None)
    with pytest.raises(ValueError, match="vocab"):
        _drain(eng)


def test_spec_fallback_on_collapsed_acceptance():
    """With an impossible acceptance floor every request falls back to
    plain decode after warmup — and stays token-exact doing it."""
    spec = _spec_parity_case("ngram", spec_k=4, spec_accept_floor=1.1)
    assert spec.stats["spec_fallbacks"] >= 1
    # fallback stops proposing: rounds stop growing once off
    assert all(st["off"] for st in spec._spec.values()) or not spec._spec


# ---------------------------------------------------------------------------
# multiplexed deployment
# ---------------------------------------------------------------------------

def _consume(gen):
    return [t for t in gen]


def test_multiplex_two_models_parity_and_lazy_paging():
    """Two models behind one replica: each model's stream matches its
    dedicated single-model deployment token-for-token, engines come up
    lazily, and the registry's swap counters record the paging."""
    from ray_tpu.serve.llm import LLMDeployment
    from ray_tpu.serve.multiplex import MultiplexedLLMDeployment

    cfg0, cfg1 = _f32_cfg(), _f32_cfg("gpt2-debug")
    dep = MultiplexedLLMDeployment(
        {"m0": {"config": cfg0, "seed": 0},
         "m1": {"config": cfg1, "seed": 1}},
        max_slots=2, max_len=64, block_size=4, prefill_chunk=8)
    try:
        assert dep._deps == {}                   # nothing materialized yet
        prompt = [1, 2, 3, 4, 5]
        out0 = _consume(dep(prompt, 8, model_id="m0"))
        assert list(dep._deps) == ["m0"]         # m1 still cold
        out1 = _consume(dep(prompt, 8, model_id="m1"))
        snap = dep.registry.snapshot()
        assert snap["m0"]["swaps_in"] == 1 and snap["m1"]["swaps_in"] == 1
        assert snap["m0"]["pins"] == 0 and snap["m1"]["pins"] == 0

        for mid, cfg, seed, want in (("m0", cfg0, 0, out0),
                                     ("m1", cfg1, 1, out1)):
            solo = LLMDeployment(cfg, max_slots=2, max_len=64,
                                 block_size=4, prefill_chunk=8, seed=seed)
            try:
                assert _consume(solo(prompt, 8)) == want, mid
            finally:
                solo.close()

        with pytest.raises(ValueError, match="unknown model_id"):
            dep(prompt, 4, model_id="m7")
        # default model is the first registered
        assert _consume(dep(prompt, 8)) == out0

        ls = dep.load_state()
        assert set(ls["models"]) == {"m0", "m1"}
        assert all(rec["state"] == "hbm" for rec in ls["models"].values())
        assert ls["inflight"] == 0 and ls["kv_total"] > 0
        st = dep.stats()
        assert st["models"]["m0"]["swaps_in"] == 1
        dep.check_health()
    finally:
        dep.close()
    snap = dep.registry.snapshot()
    assert all(not rec["resident"] for rec in snap.values())


def test_multiplex_pin_survives_stream_and_unpins_on_error():
    from ray_tpu.serve.multiplex import MultiplexedLLMDeployment

    dep = MultiplexedLLMDeployment({"m0": _f32_cfg()}, max_slots=2,
                                   max_len=64, block_size=4,
                                   prefill_chunk=8)
    try:
        gen = dep([1, 2, 3], 6, model_id="m0")
        first = next(gen)
        assert isinstance(first, int)
        # mid-stream the model is pinned: un-evictable
        assert dep.registry.snapshot()["m0"]["pins"] == 1
        _consume(gen)
        assert dep.registry.snapshot()["m0"]["pins"] == 0
        # abandoned generator: closing it must still unpin
        gen2 = dep([1, 2, 3], 6)
        next(gen2)
        gen2.close()
        assert dep.registry.snapshot()["m0"]["pins"] == 0
    finally:
        dep.close()


def test_multiplex_speculative_matches_plain():
    from ray_tpu.serve.multiplex import MultiplexedLLMDeployment

    cfg = _f32_cfg()
    prompt = [1, 2, 3, 4, 1, 2, 3, 4, 1, 2]
    plain = MultiplexedLLMDeployment({"m0": cfg}, max_slots=2,
                                     max_len=96, block_size=4,
                                     prefill_chunk=8)
    try:
        want = _consume(plain(prompt, 16))
    finally:
        plain.close()
    spec = MultiplexedLLMDeployment({"m0": cfg}, speculative=True,
                                    spec_k=4, spec_accept_floor=0.0,
                                    max_slots=2, max_len=96,
                                    block_size=4, prefill_chunk=8)
    try:
        assert _consume(spec(prompt, 16)) == want
        # speculation actually ran (acceptance itself is weight-luck on
        # a random debug model — exactness above is the guarantee)
        assert spec._deps["m0"].engine.stats["spec_proposed"] > 0
    finally:
        spec.close()


# ---------------------------------------------------------------------------
# chaos: close mid-stream / mid-swap-in — no leaked blocks, no stranded refs
# ---------------------------------------------------------------------------

@pytest.fixture
def rt():
    import ray_tpu

    ray_tpu.init(num_cpus=8, ignore_reinit_error=True)
    yield ray_tpu
    ray_tpu.shutdown()


def test_multiplex_chaos_close_mid_swap_frees_everything(rt):
    """Kill-the-replica chaos, in-process: weights live in the ARENA
    store (real refs), a budgeted registry is mid-swap-churn with one
    stream in flight, and close() lands mid-stream. Afterwards: every
    weight ref is out of the store (no stranded arena bytes), nothing
    stays resident, and the drained engine's pool accounts for every
    block."""
    import jax

    from ray_tpu import models
    from ray_tpu.serve.multiplex import MultiplexedLLMDeployment
    from ray_tpu.util.state import object_store_tier

    cfg = _f32_cfg()
    one = models.params_bytes(models.init_params(jax.random.PRNGKey(0),
                                                 cfg))
    dep = MultiplexedLLMDeployment(
        {"m0": {"config": cfg, "seed": 0},
         "m1": {"config": cfg, "seed": 1}},
        budget_bytes=one + 1, max_slots=2, max_len=64, block_size=4,
        prefill_chunk=8)
    refs = [e["ref"] for e in dep.registry._entries.values()]
    assert all(r is not None for r in refs)      # store-backed, not host
    assert all(object_store_tier(r) == "shm" for r in refs)

    # stream on m0 holds its pin while a CONCURRENT m1 request forces the
    # budget: the swap-in must shed (m0 is pinned), never evict mid-decode
    gen = dep([1, 2, 3, 4], 8, model_id="m0")
    assert isinstance(next(gen), int)
    shed = []

    def hit_m1():
        try:
            _consume(dep([5, 6, 7], 4, model_id="m1"))
        except RequestShedError as e:
            shed.append(e.reason)

    t = threading.Thread(target=hit_m1)
    t.start()
    t.join(timeout=60)
    assert not t.is_alive()
    assert shed == ["model_budget"]
    assert dep.registry.snapshot()["m0"]["resident"]

    # consume one more token, then close mid-stream (the "kill")
    next(gen)
    dep.close()
    assert dep.registry.snapshot()["m0"]["pins"] == 1  # stream never ended
    # no stranded refs: registry.free() deleted every weight object from
    # the arena (directory + segment). What MAY remain is this process's
    # own view-liveness pin from the get() — drop the views and release
    # it, exactly what the store does for any freed-after-get object
    import gc

    from ray_tpu.core.runtime import _get_runtime

    snap = dep.registry.snapshot()
    assert all(not rec["resident"] for rec in snap.values())
    store = _get_runtime().store
    if store._arena is not None:
        assert all(not store._arena.contains(r.id.binary()) for r in refs)
    # the abandoned stream's engine still aliases the weight views —
    # drop it (the real kill reclaims the whole process) and the pins
    # become releasable
    gen.close()
    del gen, dep
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        gc.collect()
        for r in refs:
            store.release(r.id)
        if all(object_store_tier(r) == "unknown" for r in refs):
            break
        time.sleep(0.1)
    assert all(object_store_tier(r) == "unknown" for r in refs)


def test_multiplex_clean_drain_no_block_leak():
    """The non-chaos control: after streams complete and the deployment
    closes, each engine's free count + trie pins == total blocks."""
    from ray_tpu.serve.multiplex import MultiplexedLLMDeployment

    dep = MultiplexedLLMDeployment({"m0": _f32_cfg()}, max_slots=2,
                                   max_len=64, block_size=4,
                                   prefill_chunk=8)
    try:
        for _ in range(3):
            _consume(dep([1, 2, 3, 4, 5, 6], 6))
        eng = dep._deps["m0"].engine
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if eng.pool.free_count + len(eng.prefix) == eng.pool.num_blocks:
                break
            time.sleep(0.05)
        assert eng.pool.free_count + len(eng.prefix) == eng.pool.num_blocks
        assert eng.prefix.stats()["hits"] >= 1   # trie served the repeats
    finally:
        dep.close()


# ---------------------------------------------------------------------------
# routing: model residency fold + prefix affinity
# ---------------------------------------------------------------------------

class _Id:
    def __init__(self, b):
        self._b = b

    def binary(self):
        return self._b


class _Rep:
    def __init__(self, b):
        self._actor_id = _Id(b)


def _handle_with_loads(loads, n=2):
    from ray_tpu.serve.handle import DeploymentHandle

    h = DeploymentHandle("d")
    h._replicas = [_Rep(bytes([97 + i])) for i in range(n)]
    h._depths = [0] * n
    h._depth_ts = time.monotonic() + 3600
    h._delta = {i: 0 for i in range(n)}
    h._has_loads = True
    h._route_state["kv_next"] = time.monotonic() + 3600
    h._route_state["kv_loads"] = loads
    return h


def test_handle_model_residency_steers_routing():
    now = time.time()
    base = {"kv_free": 10, "kv_total": 10, "ts": now}
    h = _handle_with_loads({
        b"a": dict(base, models={"mx": {"state": "host"}}),
        b"b": dict(base, models={"mx": {"state": "hbm"}}),
    })
    # without a model_id: no penalty, scores tie
    assert h._scores()[0] == h._scores()[1]
    h2 = h.options(model_id="mx")
    assert h2._model_id == "mx"
    scores = h2._scores()
    assert scores[0] > scores[1]             # non-resident pays the weight
    assert {h2._pick_replica() for _ in range(20)} == {1}
    # a replica with NO models digest (single-model deployment) is not
    # penalized — only a digest that lacks residency is
    h3 = _handle_with_loads({b"a": dict(base), b"b": dict(base)})
    h3 = h3.options(model_id="mx")
    assert h3._scores()[0] == h3._scores()[1]


def test_handle_model_id_injected_into_kwargs():
    """_issue stamps the handle's model_id as a request kwarg (the
    routing hint doubles as the model address) without clobbering an
    explicit caller choice."""
    sent = {}

    class _Call:
        def remote(self, method, args, kwargs):
            sent.clear()
            sent.update(kwargs)
            return "ref"

    class _RichRep:
        _actor_id = _Id(b"a")
        handle_request = _Call()

    h = _handle_with_loads({}, n=1)
    h = h.options(model_id="m1")
    h._replicas = [_RichRep()]
    h._refresh = lambda force=False: None
    h._issue(([1, 2, 3], 4), {})
    assert sent.get("model_id") == "m1"
    h._issue(([1, 2, 3], 4), {"model_id": "override"})
    assert sent.get("model_id") == "override"


def test_handle_prefix_affinity_direct_pick_and_margin():
    from ray_tpu.serve.kv_cache import prefix_key_digest

    now = time.time()
    prompt = list(range(16))
    key = prefix_key_digest(prompt[:4])      # block_size=4
    base = {"kv_free": 10, "kv_total": 10, "ts": now, "block_size": 4}
    h = _handle_with_loads({
        b"a": dict(base, prefix_digest=[]),
        b"b": dict(base, prefix_digest=[(key, 7)]),
    })
    h = h.options(prefix_hint=prompt)
    assert h._affinity_key() == key
    for _ in range(10):
        assert h._pick_replica() == 1        # digest holder wins outright
    # overload: push the affinity home's score past the margin — load wins
    h._route_state["kv_loads"][b"b"]["kv_free"] = 0
    h._delta[1] = 50
    picks = {h._pick_replica() for _ in range(20)}
    assert 0 in picks
    # cold prefix: no digest anywhere -> rendezvous-hash fallback: one
    # deterministic home per key (every handle agrees without
    # coordination), so the tenant's opening burst lands on one trie
    h2 = _handle_with_loads({b"a": dict(base), b"b": dict(base)})
    h2 = h2.options(prefix_hint=list(range(50, 66)))
    picks2 = {h2._pick_replica() for _ in range(10)}
    assert len(picks2) == 1
    # ...and a different key may pick a different home, but is equally
    # sticky
    h2b = _handle_with_loads({b"a": dict(base), b"b": dict(base)})
    h2b = h2b.options(prefix_hint=list(range(100, 116)))
    assert len({h2b._pick_replica() for _ in range(10)}) == 1
    # hint shorter than a block: affinity disarms
    h3 = _handle_with_loads({b"a": dict(base), b"b": dict(base)})
    h3 = h3.options(prefix_hint=[1, 2])
    assert h3._affinity_key() is None
    # precomputed digest string passes through
    h4 = _handle_with_loads({b"a": dict(base)}, n=1)
    h4 = h4.options(prefix_hint=key)
    assert h4._affinity_key() == key


def test_handle_affinity_knob_off(monkeypatch):
    from ray_tpu.serve.kv_cache import prefix_key_digest

    prompt = list(range(16))
    key = prefix_key_digest(prompt[:4])
    base = {"kv_free": 10, "kv_total": 10, "ts": time.time(),
            "block_size": 4}
    h = _handle_with_loads({
        b"a": dict(base), b"b": dict(base, prefix_digest=[(key, 9)])})
    h = h.options(prefix_hint=prompt)
    monkeypatch.setenv("RTPU_SERVE_PREFIX_AFFINITY", "0")
    picks = {h._pick_replica() for _ in range(30)}
    assert picks == {0, 1}                   # pure p2c again


# ---------------------------------------------------------------------------
# controller + deployment load-report plumbing
# ---------------------------------------------------------------------------

def test_controller_model_report():
    from ray_tpu.serve.controller import ServeController

    ctrl = ServeController.__new__(ServeController)
    ctrl._deployments = {}
    ctrl._version = 0
    ctrl._metrics = {}
    ctrl._deployments["mux"] = {"replicas": [], "target": 1}
    ctrl._deployments["plain"] = {"replicas": [], "target": 1}
    ctrl.report_replica_load("mux", b"a", {
        "inflight": 2,
        "models": {"m0": {"state": "hbm", "swaps_in": 3, "swaps_out": 1,
                          "inflight": 2}},
        "prefix_digest": [("k0", 5)]})
    ctrl.report_replica_load("plain", b"b", {"inflight": 0})
    rep = ctrl.model_report()
    assert list(rep) == ["mux"]              # model-less deployments skip
    rec = rep["mux"]["replicas"][b"a".hex()]
    assert rec["models"]["m0"]["swaps_in"] == 3
    assert rec["prefix_digest"] == [("k0", 5)]
    assert rec["inflight"] == 2 and rec["ts"] > 0


def test_multiplex_load_state_shape_for_routing():
    """What MultiplexedLLMDeployment publishes is exactly what the
    handle's residency fold and affinity pick read."""
    from ray_tpu.serve.multiplex import MultiplexedLLMDeployment

    dep = MultiplexedLLMDeployment(
        {"m0": _f32_cfg(), "m1": _f32_cfg("gpt2-debug")},
        max_slots=2, max_len=64, block_size=4, prefill_chunk=8)
    try:
        prompt = [7] * 12
        _consume(dep(prompt, 4, model_id="m0"))
        _consume(dep(prompt, 4, model_id="m0"))  # repeat seeds the trie
        ls = dep.load_state()
        assert ls["models"]["m0"]["state"] == "hbm"
        assert ls["models"]["m1"]["state"] in ("host", "spilled")
        assert ls["block_size"] == 4
        # the merged prefix digest carries the shared first block
        from ray_tpu.serve.kv_cache import prefix_key_digest

        keys = [k for k, _ in ls["prefix_digest"]]
        assert prefix_key_digest(prompt[:4]) in keys
    finally:
        dep.close()
