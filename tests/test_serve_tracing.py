"""The serve engine's own stamps (``util.tracing.stamp`` in
``serve/llm.py``): the step's phases on the profiler's host plane, one ring
record a ``step()`` call, a request's wait and prefill under the caller's
trace, and the same stamps as always-on counters whose sums stand beside
their counts. And the names the step program's stages carry in its text.
All on the CPU with the debug models."""

import glob
import os
import sys
import threading
import time

import numpy as np
import pytest

from ray_tpu.util import tracing

PHASES = ("admit", "build_inputs", "dispatch", "read", "route")
NEW_SPANS = {"serve.llm::pending", "serve.llm::prefill"} | {
    f"serve.step::{p}" for p in PHASES + ("settle",)}
#: (count, the sum of seconds bumped with it)
PAIRS = (("requests_admitted", "pending_wait_s"),
         ("first_tokens", "prefill_s"),
         ("steps_decode_only", "step_s_decode_only"),
         ("steps_chunk", "step_s_chunk"),
         ("steps_full_width", "step_s_full_width"),
         ("steps_second_width", "step_s_second_width"),
         ("steps", "step_host_s"))


@pytest.fixture
def trace_env(monkeypatch):
    """``arm(True | False)`` resolves tracing anew from the environment;
    the ring is empty before and after."""
    def arm(on: bool):
        monkeypatch.setenv("RTPU_TRACING", "1" if on else "0")
        monkeypatch.delenv("RTPU_TRACE_FILE", raising=False)
        tracing._reset_for_tests()

    yield arm
    monkeypatch.undo()
    tracing._reset_for_tests()


def _engine(**kw):
    from ray_tpu.serve.llm import LLMEngine

    kw = {"max_slots": 4, "max_len": 64, "block_size": 4,
          "prefill_chunk": 4, **kw}
    return LLMEngine("llama-debug", **kw)


def _drain(eng, limit=2000):
    for _ in range(limit):
        if not eng.step():
            return
    raise AssertionError("the engine did not come to rest")


def _warm(eng):
    """The compiles, outside what a test times."""
    eng.submit([5, 6, 7, 8, 9], 2, lambda _t: None)
    _drain(eng)


def _prompts(n, rng=None):
    rng = rng or np.random.default_rng(7)
    return [rng.integers(1, 250, int(k)).tolist()
            for k in rng.integers(2, 14, n)]


# -- (a) one request, one trace ----------------------------------------------

def test_request_spans_share_one_trace_and_nest(trace_env):
    from ray_tpu.serve.llm import LLMDeployment

    trace_env(True)
    dep = LLMDeployment("llama-debug", max_slots=2, max_len=64, block_size=4,
                        prefill_chunk=4)
    firsts = []
    eng = dep.engine
    observe = eng.admission.observe_ttft
    eng.admission.observe_ttft = lambda s: (
        firsts.append((time.monotonic(), s)), observe(s))[1]
    try:
        toks = list(dep([3, 1, 4, 1, 5, 9, 2, 6, 5, 3], 4))
    finally:
        dep.close()
    assert len(toks) == 4 and len(firsts) == 1
    spans = {s["name"]: s for s in tracing.drain_ring()
             if s["name"].startswith("serve.llm::")}
    assert set(spans) == {"serve.llm::stream", "serve.llm::queue",
                          "serve.llm::pending", "serve.llm::prefill"}
    stream, queue, pending, prefill = (
        spans["serve.llm::" + n]
        for n in ("stream", "queue", "pending", "prefill"))
    assert len({s["trace_id"] for s in spans.values()}) == 1
    assert queue["parent_span_id"] == stream["span_id"]
    assert pending["parent_span_id"] == queue["span_id"]
    assert prefill["parent_span_id"] == queue["span_id"]
    lo, hi = "start_time_unix_nano", "end_time_unix_nano"
    assert stream[lo] <= queue[lo] <= pending[lo] <= pending[hi]
    assert pending[hi] == prefill[lo]
    assert prefill[hi] <= queue[hi] <= stream[hi]
    # the two end where the first token was read, and span its whole time
    # in the engine (what ``observe_ttft`` was handed)
    read_at, ttft = firsts[0]
    assert 0 <= tracing.epoch_ns(read_at) - prefill[hi] < 5e6
    assert abs((prefill[hi] - pending[lo]) - ttft * 1e9) < 1e3
    assert prefill["attributes"]["prompt_tokens"] == 10
    assert prefill["attributes"]["steps"] == 3        # chunks of 4
    assert prefill["attributes"]["prefix_hit_tokens"] == 0
    assert pending["attributes"]["ahead_at_submit"] == 0


def test_a_request_that_waited_says_what_for(trace_env):
    """Two slots, three requests: the third lies pending for a slot, and
    its ``serve.llm::pending`` span says so."""
    trace_env(True)
    eng = _engine(max_slots=2)
    _warm(eng)
    tracing.drain_ring()
    tp = lambda i: f"00-{i:032x}-{i:016x}-01"
    for i, p in enumerate(_prompts(3), 1):
        eng.submit(p, 6, lambda _t: None, trace=tp(i))
    _drain(eng)
    pending = sorted((s for s in tracing.drain_ring()
                      if s["name"] == "serve.llm::pending"),
                     key=lambda s: s["trace_id"])
    assert [s["trace_id"] for s in pending] == [f"{i:032x}" for i in (1, 2, 3)]
    assert [s["attributes"]["ahead_at_submit"] for s in pending] == [0, 1, 2]
    assert [s["attributes"]["waited_for"] for s in pending] == ["", "", "slot"]
    wait = [s["end_time_unix_nano"] - s["start_time_unix_nano"]
            for s in pending]
    assert wait[2] > max(wait[:2])
    assert eng.stats["requests_admitted"] == 4 == eng.stats["first_tokens"]


# -- (b) a slow step's time goes to ITS rows' kind ------------------------------

class _Late:
    """A device counter that arrives late: what ``jax.device_get`` waits for
    when the step it belongs to runs long."""

    def __init__(self, seconds):
        self.seconds = seconds

    def copy_to_host_async(self):
        pass

    def __array__(self, *a, **kw):
        time.sleep(self.seconds)
        return np.zeros((), np.int32)


def test_slow_step_lands_in_the_kind_of_its_own_rows():
    """The one chunk step of a request runs long on the 'device'. The engine
    books the time with that step's rows (chunk); a stamp around ``step()``
    from outside, paired with the rows the call DISPATCHED, books it with
    the decode-only step after it: the off-by-one the lookahead made."""
    slow_s = 0.5
    eng = _engine(max_slots=2)
    _warm(eng)
    before = dict(eng.stats)
    inner, calls = eng._step_fn, []

    def step_fn(*a):
        out = inner(*a)
        calls.append(len(calls))
        if calls[-1] == 0:          # the chunk step: its read waits
            return out[0], out[1], {"late": _Late(slow_s)}
        return out

    eng._step_fn = step_fn
    eng.submit([9, 8, 7], 6, lambda _t: None)
    outside = {"chunk": 0.0, "decode_only": 0.0}
    for _ in range(100):
        t0 = time.monotonic()
        busy = eng.step()
        if eng._inflight is not None:       # what this call dispatched
            outside[eng._inflight.kind] += time.monotonic() - t0
        if not busy:
            break
    grown = {k: eng.stats[k] - before[k] for k in before
             if isinstance(before[k], (int, float))}
    assert grown["steps_chunk"] == 1 and grown["steps_decode_only"] == 5
    assert grown["step_s_chunk"] >= slow_s
    assert grown["step_s_decode_only"] < slow_s / 2
    assert outside["decode_only"] >= slow_s and outside["chunk"] < slow_s / 2
    # and the wait was the device's, not the host's
    assert grown["step_host_s"] < slow_s / 2


# -- (c) the sums add up ----------------------------------------------------------

def test_counters_add_up_over_a_run(trace_env):
    trace_env(True)
    eng = _engine(max_slots=3)
    ttfts = []
    observe = eng.admission.observe_ttft
    eng.admission.observe_ttft = lambda s: (ttfts.append(s), observe(s))[1]
    rng = np.random.default_rng(11)
    for p in _prompts(7, rng):
        eng.submit(p, int(rng.integers(1, 9)), lambda _t: None)
    _drain(eng)
    s = eng.stats
    calls = [r["attributes"] for r in tracing.drain_ring()
             if r["name"] == "serve::step"]
    reads = [a for a in calls if "read_index" in a]
    kinds = ("decode_only", "chunk", "full_width")
    assert len(reads) == s["steps"] == sum(s["steps_" + k] for k in kinds)
    assert sorted(a["read_index"] for a in reads) == list(range(s["steps"]))
    assert sum(s["step_s_" + k] for k in kinds) == pytest.approx(
        sum(a["read_step_ms"] for a in reads) / 1e3, rel=1e-9)
    for k in kinds:
        mine = [a for a in reads if a["read_kind"] == k]
        assert len(mine) == s["steps_" + k]
        assert s["step_s_" + k] == pytest.approx(
            sum(a["read_step_ms"] for a in mine) / 1e3, rel=1e-9, abs=1e-12)
    assert s["steps_chunk"] > 0 and s["steps_decode_only"] > 0
    assert s["requests_admitted"] == s["first_tokens"] == len(ttfts) == 7
    assert abs(s["pending_wait_s"] + s["prefill_s"] - sum(ttfts)) < 7e-6
    # every row that was fed prompt tokens is a step of its request's prefill
    assert s["prefill_steps"] == sum(
        a.get("dispatched_chunk_rows", 0) for a in calls)
    # the host's share of a call: its phases but the wait, and a little more
    host = sum(a.get(p + "_ms", 0.0) for a in calls if "dispatched_index" in a
               for p in ("admit", "build_inputs", "dispatch", "route")) / 1e3
    assert host <= s["step_host_s"] < host + 0.02 * s["steps"]


def test_full_width_steps_are_their_own_kind(monkeypatch, trace_env):
    """With a budget narrower than the grid, a step whose real positions
    pass the widths under the grid is booked as full width, count and
    seconds together; one that the second width held (5 < 4 + 4 + 1 <= 10
    of 16) keeps its kind and is booked as second width beside it."""
    from ray_tpu.serve import llm

    trace_env(False)
    monkeypatch.setattr(llm, "STEP_BUDGET", 5)
    eng = _engine(max_slots=4)
    for p in ([1, 2, 3, 4, 5, 6, 7, 8, 9], [9, 8, 7, 6, 5, 4, 3, 2],
              [4, 5, 6]):
        eng.submit(p, 3, lambda _t: None)
    _drain(eng)
    s = eng.stats
    assert s["steps_full_width"] >= 1 and s["step_s_full_width"] > 0
    assert s["steps_second_width"] >= 1 and s["step_s_second_width"] > 0
    assert s["steps"] == sum(
        s["steps_" + k] for k in ("decode_only", "chunk", "full_width"))


# -- (d) off means off; a sum never shows without its count -------------------

def test_tracing_off_records_nothing_and_sums_keep_their_counts(trace_env):
    trace_env(False)
    eng = _engine(max_slots=3)
    _warm(eng)
    snaps, stop = [], threading.Event()

    def snapshot():
        while not stop.is_set():
            snaps.append(dict(eng.stats))

    watcher = threading.Thread(target=snapshot, daemon=True)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        watcher.start()
        rng = np.random.default_rng(3)
        for _round in range(3):
            for p in _prompts(5, rng):
                eng.submit(p, 5, lambda _t: None,
                           trace="00-" + "1" * 32 + "-" + "2" * 16 + "-01")
            _drain(eng)
    finally:
        stop.set()
        sys.setswitchinterval(interval)
        watcher.join(timeout=30)
    assert not watcher.is_alive()
    assert tracing.ring_stats()["len"] == 0
    assert eng.stats["steps"] > 20 and len(snaps) > 20
    torn = [(count, total, a[count], b[count], a[total], b[total])
            for a, b in zip(snaps, snaps[1:]) for count, total in PAIRS
            if (a[count] != b[count]) != (a[total] != b[total])]
    assert torn == []


# -- (e) the phases on the profiler's host plane ----------------------------------

def test_profiler_host_plane_holds_the_step_and_its_phases(tmp_path):
    import jax

    eng = _engine(max_slots=2)
    _warm(eng)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    n0, calls = eng.stats["steps"], 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        eng.submit([1, 2, 3, 4, 5, 6], 4, lambda _t: None)
        while True:
            calls += 1
            if not eng.step():
                break
    finally:
        jax.profiler.stop_trace()
    steps = eng.stats["steps"] - n0
    assert steps >= 5
    found = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(sorted(found)[-1])
    lines = [[(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for e in line.events if e.name.startswith("serve")]
             for plane in data.planes if plane.name.startswith("/host")
             for line in plane.lines]
    events = max(lines, key=len)         # the thread that stepped the engine
    whole = [e for e in events if e[0] == "serve::step"]
    assert len(whole) == calls
    inside = lambda e, w: w[1] <= e[1] and e[2] <= w[2]
    children = [sorted(e[0].partition("::")[2] for e in events
                       if e[0].startswith("serve.step::") and inside(e, w))
                for w in whole]
    # every phase lies in exactly one call
    assert sum(map(len, children)) == sum(
        e[0].startswith("serve.step::") for e in events)
    # a call that found a step in flight holds all five phases, once each
    assert children.count(sorted(PHASES)) == steps - 1
    # the first had nothing to read yet; the last call settles
    assert children[0] == ["admit", "build_inputs", "dispatch"]
    assert children[-1] == ["admit", "read", "settle"]


# -- (f) the catalog ----------------------------------------------------------------

def test_span_catalog_lists_the_engines_names_and_the_lint_passes():
    from _graftlint_tree import ROOT, tree_findings

    from ray_tpu.devtools.graftlint.rules_tracing import documented_span_names

    names, _prefixes = documented_span_names(
        (ROOT / "ray_tpu" / "util" / "tracing.py").read_text())
    assert NEW_SPANS | {"serve::step"} <= names
    assert [f for f in tree_findings()
            if f.rule == "tracing-span-names"] == []


# -- the step program's stages by name ---------------------------------------------

STAGES = ("embed", "qkv_proj", "rope", "kv_write", "attn_out_proj", "mlp",
          "final_norm", "lm_head", "stream_gather")


@pytest.mark.parametrize("model", ["llama-debug", "sparse-moe-debug",
                                   "hybrid-state-debug", "latent-moe-debug"])
def test_step_program_names_its_stages(model, monkeypatch):
    """Every stage of the paged step carries a ``jax.named_scope`` that
    reaches the compiled program's ``op_name``s, by which a device trace
    places an operation (``benchmark/kinds/serve_family_replica.py``);
    lowered under a budget narrower than the grid, so the stream's gathers
    are there."""
    import re

    import jax
    import jax.numpy as jnp

    from ray_tpu import models
    from ray_tpu.serve import llm

    monkeypatch.setattr(llm, "STEP_BUDGET", 3)
    cfg = models.get_config(model)
    eng = llm.LLMEngine(cfg, max_slots=2, max_len=32, block_size=4,
                        prefill_chunk=4)
    spec = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    text = jax.jit(eng._raw_step_paged, donate_argnums=(1,)).lower(
        spec(eng.params), spec(eng._cache), i32(2, 4),
        i32(2, eng._tbl_width), i32(2), i32(2),
        jax.ShapeDtypeStruct((2,), jnp.bool_)).compile().as_text()
    scopes = {part for name in re.findall(r'op_name="([^"]*)"', text)
              for part in name.split("/")}
    want = [s for s in STAGES
            if not (s == "rope" and cfg.positions == "none")]
    assert [s for s in want if s not in scopes] == []


def test_flash_kernels_have_names():
    """The three Pallas calls of ``ops/flash_pallas.py`` are named, as the
    paged and latent kernels are: a device trace shows the name."""
    import inspect

    from ray_tpu.ops import flash_pallas

    src = inspect.getsource(flash_pallas)
    assert src.count("pl.pallas_call(") == 3
    for name in ("flash_attention_fwd", "flash_attention_bwd_dkv",
                 "flash_attention_bwd_dq"):
        assert f'name="{name}"' in src
