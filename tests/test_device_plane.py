"""Device plane (ISSUE 19): compiled-program registry, planted-retrace
detection with exact signature diffs, version-gated snapshots, federation
stores, compile-storm alerting, and cost-model-driven MFU attribution.

Runs on the conftest 8-device virtual CPU mesh; the real-model parity
test needs modern jax and skips on the old sandbox."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.util import device_plane, events

from conftest import poll_until  # noqa: F401  (cluster-side tests import it)


@pytest.fixture
def plane():
    """Fresh device-plane + events state; restores env arming after."""
    saved_dp = os.environ.pop("RTPU_DEVICE_PLANE", None)
    saved_ev = os.environ.pop("RTPU_EVENTS", None)
    device_plane._reset_for_tests()
    events._reset_for_tests()
    yield device_plane
    for key, val in (("RTPU_DEVICE_PLANE", saved_dp),
                     ("RTPU_EVENTS", saved_ev)):
        if val is None:
            os.environ.pop(key, None)
        else:
            os.environ[key] = val
    device_plane._reset_for_tests()
    events._reset_for_tests()


# ---------------------------------------------------------------------------
# registry: compiles, calls, cost analysis, arming
# ---------------------------------------------------------------------------

def test_plane_on_by_default_and_kill_switch(plane):
    assert device_plane.device_plane_enabled()  # no env -> ON

    os.environ["RTPU_DEVICE_PLANE"] = "0"
    device_plane._reset_for_tests()
    assert not device_plane.device_plane_enabled()
    f = device_plane.registered_jit(lambda x: x * 2.0, name="off::f")
    assert float(f(jnp.float32(3.0))) == 6.0  # pure passthrough
    assert device_plane.registry().rows() == []  # nothing registered
    assert device_plane.snapshot(min_version=0) is None


def test_registered_jit_records_compile_cost_and_donation(plane):
    f = device_plane.registered_jit(
        lambda a, b: a @ b, name="test::mm", component="test", steps=4,
        donate_argnums=(0,))
    out = f(jnp.ones((32, 32)), jnp.ones((32, 32)))
    jax.block_until_ready(out)
    f(jnp.ones((32, 32)), jnp.ones((32, 32)))  # warm call

    row = device_plane.registry().program("test::mm")
    assert row["compiles"] == 1 and row["retraces"] == 0
    assert row["calls"] == 2
    assert row["component"] == "test"
    assert row["donate"] == [0]
    assert row["compile_s_total"] > 0
    assert row["sigs"] == [{"args[0]": "float32[32,32]",
                            "args[1]": "float32[32,32]"}]
    # static cost analysis: a 32^3 matmul is 2*32^3 = 65536 flops
    assert row["cost"] and row["cost"]["flops"] >= 65536
    # steps=4 declares a scanned program: per-step flops divide by 4
    assert device_plane.program_flops_per_step("test::mm") == \
        pytest.approx(row["cost"]["flops"] / 4)

    # the compile landed in the builtin metrics, labeled by program
    from ray_tpu.util import metric_defs
    samples = dict(metric_defs.get("rtpu_jit_compiles_total")._samples())
    vals = [v for tags, v in samples.items()
            if dict(tags).get("program") == "test::mm"]
    assert vals and vals[0] >= 1


def test_planted_retrace_emits_one_event_with_exact_diff(plane):
    """THE acceptance check: a planted retrace yields exactly one
    jit_recompile event naming the differing shape."""
    f = device_plane.registered_jit(lambda x: (x * 2.0).sum(),
                                    name="test::double", component="test")
    f(jnp.zeros((4, 8), jnp.float32))
    assert [e["name"] for e in events.drain_ring()] == []  # first compile
    f(jnp.zeros((4, 8), jnp.float32))                      # warm call
    f(jnp.zeros((8, 8), jnp.float32))                      # planted retrace

    evs = [e for e in events.drain_ring() if e["name"] == "jit_recompile"]
    assert len(evs) == 1
    ev = evs[0]
    assert ev["program"] == "test::double"
    assert ev["severity"] == "warning"
    assert ev["diff"] == {"changed": {"args[0]": {"was": "float32[4,8]",
                                                  "now": "float32[8,8]"}}}
    row = device_plane.registry().program("test::double")
    assert row["compiles"] == 2 and row["retraces"] == 1
    assert row["calls"] == 3


def test_static_arg_retrace_diff_names_the_python_value(plane):
    f = device_plane.registered_jit(
        lambda x, flag: x + 1 if flag else x - 1, name="test::static",
        component="test", static_argnames=("flag",))
    f(jnp.zeros((4,)), flag=True)
    f(jnp.zeros((4,)), flag=False)  # static-arg half of a retrace diff
    evs = [e for e in events.drain_ring() if e["name"] == "jit_recompile"]
    assert len(evs) == 1
    assert evs[0]["diff"]["changed"] == {
        "kwargs['flag']": {"was": "py:bool:True", "now": "py:bool:False"}}


def test_sig_history_bounded_and_known_sig_not_a_retrace(plane):
    f = device_plane.registered_jit(lambda x: x.sum(), name="test::hist")
    for n in range(device_plane.MAX_SIGS + 4):
        f(jnp.zeros((n + 1,)))
    row = device_plane.registry().program("test::hist")
    assert row["compiles"] == device_plane.MAX_SIGS + 4
    assert row["retraces"] == device_plane.MAX_SIGS + 3
    assert len(row["sigs"]) == device_plane.MAX_SIGS  # bounded history
    events.drain_ring()
    # replaying an already-cached signature is a plain call, not a retrace
    f(jnp.zeros((2,)))
    assert device_plane.registry().program("test::hist")["retraces"] == \
        device_plane.MAX_SIGS + 3
    assert events.drain_ring() == []


def test_signature_diff_unit():
    old = {"a": "float32[4]", "b": "int32[2]", "gone": "float32[1]"}
    new = {"a": "float32[8]", "b": "int32[2]", "fresh": "bool[3]"}
    assert device_plane.signature_diff(old, new) == {
        "changed": {"a": {"was": "float32[4]", "now": "float32[8]"}},
        "added": {"fresh": "bool[3]"},
        "removed": {"gone": "float32[1]"},
    }
    assert device_plane.signature_diff({"a": "x"}, {"a": "x"}) == {}


# ---------------------------------------------------------------------------
# snapshots, census, federation stores
# ---------------------------------------------------------------------------

def test_snapshot_version_gating(plane):
    # an empty registry never ships (zygote workers without jax)
    assert device_plane.snapshot(min_version=0) is None

    f = device_plane.registered_jit(lambda x: x + 1, name="test::snap")
    f(jnp.zeros((4,)))
    snap = device_plane.snapshot(min_version=0)
    assert snap is not None and snap["version"] > 0
    assert snap["pid"] == os.getpid()
    assert [r["program"] for r in snap["programs"]] == ["test::snap"]

    # nothing changed since: gated off. Warm calls don't bump the
    # version either — a busy-but-stable registry stops re-shipping.
    assert device_plane.snapshot(min_version=snap["version"]) is None
    f(jnp.zeros((4,)))
    assert device_plane.snapshot(min_version=snap["version"]) is None
    # a fresh compile bumps it past the cursor again
    f(jnp.zeros((8,)))
    assert device_plane.snapshot(min_version=snap["version"]) is not None


def test_live_buffer_census_groups_by_shape_dtype(plane):
    held = [jnp.ones((1031, 257), jnp.float32) for _ in range(3)]
    jax.block_until_ready(held)
    census = device_plane.live_buffer_census()
    assert census is not None
    assert census["buffers"] >= 3
    mine = [g for g in census["groups"]
            if g["shape"] == [1031, 257] and g["dtype"] == "float32"]
    assert mine, "held buffers missing from the census groups"
    assert mine[0]["count"] >= 3
    assert mine[0]["bytes"] >= 3 * 1031 * 257 * 4
    assert census["bytes"] >= mine[0]["bytes"]
    del held


def test_device_store_replaces_by_origin_and_evicts(plane):
    ds = device_plane.DeviceStore()
    ds.ingest("w1", {"worker_id": "w1", "component": "worker"},
              {"pid": 1, "version": 1, "programs": []})
    ds.ingest("w1", {"worker_id": "w1", "component": "worker"},
              {"pid": 1, "version": 2, "programs": []})
    out = ds.export()
    assert len(out) == 1  # snapshot-replace, not append
    assert out[0]["version"] == 2 and out[0]["worker_id"] == "w1"

    ds.MAX_ORIGINS = 2
    ds.ingest("w2", {"worker_id": "w2"}, {"pid": 2, "version": 1,
                                          "programs": []})
    ds.ingest("w3", {"worker_id": "w3"}, {"pid": 3, "version": 1,
                                          "programs": []})
    assert {e["worker_id"] for e in ds.export()} == {"w2", "w3"}


def test_merge_report_labels_totals_and_ordering(plane):
    entries = [
        {"pid": 1, "node_id": "n1", "component": "driver",
         "programs": [{"program": "a", "compiles": 2, "retraces": 1,
                       "compile_s_total": 1.0}],
         "hbm": {"bytes_in_use": 10, "bytes_limit": 100}},
        {"pid": 2, "node_id": "n2", "worker_id": "w2",
         "component": "worker",
         "programs": [{"program": "b", "compiles": 1, "retraces": 0,
                       "compile_s_total": 2.0}],
         "live_buffers": {"buffers": 3, "bytes": 64, "groups": []}},
    ]
    rep = device_plane.merge_report(entries)
    assert rep["totals"] == {"processes": 2, "programs": 2, "compiles": 3,
                             "retraces": 1, "live_buffer_bytes": 64,
                             "hbm": {"bytes_in_use": 10,
                                     "bytes_limit": 100}}
    # flat program rows carry their origin labels, heaviest compiler first
    assert [r["program"] for r in rep["programs"]] == ["b", "a"]
    assert rep["programs"][0]["node_id"] == "n2"
    assert rep["programs"][0]["component"] == "worker"
    assert rep["programs"][1]["node_id"] == "n1"
    procs = {p.get("node_id"): p for p in rep["processes"]}
    assert procs["n1"]["hbm"]["bytes_in_use"] == 10
    assert procs["n2"]["live_buffers"]["buffers"] == 3


# ---------------------------------------------------------------------------
# compile-storm + HBM alerts (synthetic watchdog ticks)
# ---------------------------------------------------------------------------

def _shipped_rule(name):
    from ray_tpu.util import alerts

    return [r for r in alerts.DEFAULT_RULES if r["name"] == name]


def test_compile_storm_alert_raises_and_clears_with_hysteresis(plane):
    from ray_tpu.util import alerts

    wd = alerts.Watchdog(rules=_shipped_rule("jit_compile_storm"),
                         sample_fn=lambda: {})

    def view(total):  # cumulative retrace counter, summed over programs
        return {"rtpu_jit_retraces_total": [((), float(total))]}

    assert wd.evaluate_once(view(0)) == []   # first tick: no window yet
    assert wd.evaluate_once(view(3)) == []   # +3 retraces: breach tick 1
    active = wd.evaluate_once(view(6))       # +3 again: FOR_TICKS met
    assert [a["alert"] for a in active] == ["jit_compile_storm"]
    assert [e["name"] for e in events.drain_ring()] == ["alert_raised"]
    assert wd.evaluate_once(view(6)) != []   # quiet tick 1: still active
    assert wd.evaluate_once(view(6)) == []   # quiet tick 2: cleared
    assert [e["name"] for e in events.drain_ring()] == ["alert_cleared"]


def test_hbm_occupancy_alert_is_a_ratio_over_the_limit(plane):
    from ray_tpu.util import alerts

    wd = alerts.Watchdog(rules=_shipped_rule("hbm_occupancy"),
                         sample_fn=lambda: {})

    def view(used):
        return {"rtpu_tpu_hbm_used_bytes": [((), float(used))],
                "rtpu_tpu_hbm_limit_bytes": [((), 100.0)]}

    assert wd.evaluate_once(view(95)) == []  # breach tick 1
    active = wd.evaluate_once(view(95))      # tick 2: raises at >92%
    assert [a["alert"] for a in active] == ["hbm_occupancy"]
    wd.evaluate_once(view(50))
    assert wd.evaluate_once(view(50)) == []  # two healthy ticks clear


# ---------------------------------------------------------------------------
# cost-model-driven MFU attribution
# ---------------------------------------------------------------------------

def test_mfu_parity_cost_model_vs_hand_formula(plane):
    """Registry cost-analysis flops agree with the analytic 6N formula
    within 5% on a pure-matmul train step (fwd 2N + bwd 4N per token —
    exact for a matmul chain once dx is taken through the input)."""
    d, layers, tokens = 128, 8, 256
    key = jax.random.PRNGKey(0)
    params = [jax.random.normal(jax.random.fold_in(key, i), (d, d)) * 0.02
              for i in range(layers)]
    x = jax.random.normal(jax.random.fold_in(key, 99), (tokens, d))

    def loss_fn(ws, xs):
        h = xs
        for w in ws:
            h = h @ w
        return jnp.sum(h * h)

    step = device_plane.registered_jit(
        lambda ws, xs: jax.grad(loss_fn, argnums=(0, 1))(ws, xs),
        name="test::mlp_step", component="train")
    jax.block_until_ready(step(params, x))

    fps = device_plane.program_flops_per_step("test::mlp_step")
    assert fps is not None
    hand = 6 * layers * d * d * tokens
    assert fps == pytest.approx(hand, rel=0.05)

    # telemetry closes the loop: record_step(program=...) pulls flops
    # from the registry; with a spec-sheet peak override equal to the
    # hand formula's rate, the cost-model MFU must land within 5% of 1.
    from ray_tpu.train.telemetry import StepTelemetry

    st = StepTelemetry()
    dt = 0.01
    st.peak_flops = hand / dt
    st.record_step(dt, program="test::mlp_step")
    snap = st.snapshot()
    assert snap["mfu"] == pytest.approx(1.0, rel=0.05)
    assert snap["flops_per_s"] == pytest.approx(fps / dt, rel=1e-6)


def test_mfu_parity_debug_model(plane):
    """Cost-analysis flops vs the hand matmul count on the real debug
    model (remat=False, so XLA executes exactly the analytic flops)."""
    from ray_tpu import models

    c = models.llama_debug()
    params = models.init_params(jax.random.PRNGKey(0), c)
    B, T = 4, 33
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0,
                              c.vocab_size)
    batch = {"tokens": np.asarray(toks)}

    def loss(p):
        return models.loss_and_metrics(p, batch, c)[0]

    step = device_plane.registered_jit(lambda p: jax.grad(loss)(p),
                                       name="test::debug_step",
                                       component="train")
    jax.block_until_ready(step(params))
    fps = device_plane.program_flops_per_step("test::debug_step")
    assert fps is not None

    # exact matmul count/token: projections + attention quadratic +
    # swiglu mlp per layer, plus the lm head; bwd doubles every matmul
    L = T - 1  # loss_and_metrics trains on tokens[:, :-1]
    d, f, hd = c.d_model, c.ff, c.hdim
    attn_p = d * hd * c.n_heads + 2 * d * hd * c.kv_heads \
        + hd * c.n_heads * d
    per_layer_fwd = 2 * (attn_p + 3 * d * f) + 4 * L * d
    fwd_per_token = c.n_layers * per_layer_fwd + 2 * d * c.vocab_size
    hand = 3 * fwd_per_token * B * L
    # the cost model counts every executed flop: never fewer than the
    # matmuls (it counted ONE layer of the scan until scans were counted
    # at their length), and at d_model=64 the norms, rope, softmax and
    # swiglu elementwise work adds ~5% on top (measured 5.2%)
    assert hand <= fps <= hand * 1.08


@pytest.mark.parametrize("shape", ["scan", "nested", "steps"])
def test_scans_are_counted_at_their_length(plane, shape):
    """XLA's cost analysis counts a while body once; the registry adds
    the other ``length - 1`` iterations of every ``lax.scan`` (nested
    ones multiply), so ``steps=N`` divides a true N-step total."""
    d, tokens, n = 64, 32, 8
    w = jnp.ones((n, d, d)) * 0.01
    x = jnp.ones((tokens, d))
    matmul = 2 * tokens * d * d

    def layers(h, ws):
        return jax.lax.scan(lambda c, wl: (c @ wl, None), h, ws)[0]

    if shape == "nested":
        fn = lambda h, ws: jax.lax.scan(            # noqa: E731
            lambda c, _: (layers(c, ws), None), h, None, length=3)[0]
        want, steps = 3 * n * matmul, 1
    else:
        fn, want, steps = layers, n * matmul, (n if shape == "steps" else 1)
    prog = device_plane.registered_jit(fn, name=f"test::{shape}",
                                       component="train", steps=steps)
    jax.block_until_ready(prog(x, w))
    fps = device_plane.program_flops_per_step(f"test::{shape}")
    assert fps == pytest.approx(want / steps, rel=0.01)


# ---------------------------------------------------------------------------
# eager dispatcher hook (ops::flash_attention)
# ---------------------------------------------------------------------------

def test_tracked_call_registers_novel_signatures_only(plane):
    calls = {"n": 0}

    def run():
        calls["n"] += 1
        return calls["n"]

    args = (jnp.zeros((2, 4, 8, 16)),)
    assert device_plane.tracked_call("test::eager", "ops", run, args,
                                     statics={"impl": "xla"}) == 1
    assert device_plane.tracked_call("test::eager", "ops", run, args,
                                     statics={"impl": "xla"}) == 2
    row = device_plane.registry().program("test::eager")
    assert row["compiles"] == 1 and row["calls"] == 2
    # a novel STATIC counts as a fresh implicit compile (and a retrace)
    device_plane.tracked_call("test::eager", "ops", run, args,
                              statics={"impl": "pallas"})
    row = device_plane.registry().program("test::eager")
    assert row["compiles"] == 2 and row["retraces"] == 1


# ---------------------------------------------------------------------------
# lifetime: the wrapper must never root its owner
# ---------------------------------------------------------------------------

def test_registered_jit_of_bound_method_does_not_pin_owner(plane):
    """Regression: storing the C++ PjitFunction's bound ``_cache_size``
    method on the wrapper made the owner <-> jit reference cycle
    uncollectable — a closed serve engine (and every arena weight view
    it aliased) survived ``del`` + ``gc.collect()`` forever, stranding
    shm. The wrapper must stay fully gc-traversable."""
    import gc
    import weakref

    class Owner:
        def step(self, x):
            return x * 2.0

    o = Owner()
    o.fn = device_plane.registered_jit(o.step, name="test::owner_step",
                                       component="test")
    assert float(o.fn(jnp.ones((4,)))[0]) == 2.0
    ref = weakref.ref(o)
    del o
    gc.collect()
    gc.collect()
    assert ref() is None
