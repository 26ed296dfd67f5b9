"""The pre-routed MoE family's file and its cell, CPU only, no ray_tpu runtime:
the configuration against the catalog row, its bytes against the shapes,
``build_params`` against the published count, ``step_needs`` on hand-counted
rows, the scope map with the family's lists, the five new readers (and the
appended ones) over a recorded toy run, the mix's seed rule, rate, knee and
``why`` held together, and the reference against a second, unblocked writing
of the equations and against itself with mathematics moved."""

import os

import numpy as np
import pytest

from benchmark import family_rooflines, manifest
from benchmark.generators import mixed_lengths
from benchmark.kinds import serve_state_family
from benchmark.kinds import serve_state_family_replica as replica

CELL = "smallthinker-21b-a3b.chat-longtail"
CONFIG = "smallthinker-21b-a3b-l8-serve"
NEW_READERS = ("whole_experts_roofline", "preroute_moe_step_roofline",
               "experts_hit_pct", "route_device_ms",
               "whole_expert_load_max_over_mean")
APPENDED = ("swa_attention_roofline", "global_attention_roofline",
            "swa_kv_held_pct", "window_blocks_wait_ms",
            "expert_kernel_pairs_pct", "paged_token_tile_rows_pct",
            "decode_only_step_ms", "step_positions_real_pct")

#: the catalog row's ``config`` (architectures.jsonl,
#: SmallThinker-21BA3B-Instruct), its two 52-long layout lists as the rule
#: that gives them
LAYOUT = [0 if i % 4 == 0 else 1 for i in range(52)]
ROW = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_layout": LAYOUT,
    "rope_scaling": None, "rope_theta": 1500000,
    "sliding_window_layout": LAYOUT, "sliding_window_size": 4096,
    "tie_word_embeddings": False, "vocab_size": 151936}

TOTAL = 3_966_937_600
ATTN = 20_971_520       # q, k, v, o
EXPERT = 5_898_240
LAYER = 398_627_840     # ... 64 experts, the router and two norms


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(manifest.load_manifest(), CELL)


@pytest.fixture(scope="module")
def family(cell):
    return replica.load_family(cell["config_file"])


def test_the_configuration_keeps_the_catalog_row_but_the_depth(cell):
    cf = cell["config_file"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):
        import json
        row, = [r for r in map(json.loads, open(catalog))
                if r["name"] == "SmallThinker-21BA3B-Instruct"]
        assert row["config"] == ROW and row["source_url"] == cf["source"]
    for key, value in ROW.items():
        assert cf[key] == (8 if key == "num_hidden_layers" else value), key
    assert cf["reduced"]["num_hidden_layers"]["published"] == 52
    assert cf["reduced"]["num_hidden_layers"]["here"] == 8
    assert set(cf["reduced"]) == {"num_hidden_layers"}
    # two whole periods, the full layer first
    assert cf["sliding_window_layout"][:8] == [0, 1, 1, 1, 0, 1, 1, 1]
    entry = next(c for c in manifest.load_manifest()["configs"]
                 if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == cf["source"] and len(entry["why"]) <= 200
    for key in ("router_input", "expert_activation", "secondary_experts",
                "window_edge", "router_precision", "rope_pairing",
                "rope_layers", "weights", "engine"):
        assert cf["assumed"][key], key
    assert cf["precision"] == {"weights": "bfloat16",
                               "activations": "bfloat16",
                               "matmul": "default", "router": "float32"}
    assert "44 layers" in cf["stands_for"]


def test_transformer_config_reads_every_published_key(cell, family):
    c = family.transformer_config(cell["config_file"])
    assert (c.d_model, c.n_heads, c.kv_heads, c.hdim, c.ff_expert) \
        == (2560, 28, 4, 128, 768)
    assert (c.n_layers, c.dense_layers, c.num_experts, c.held_experts,
            c.expert_top_k, c.shared_experts) == (8, 0, 64, 64, 6, 0)
    assert c.layer_windows == (0, 4096, 4096, 4096) * 2
    assert c.window_pool and c.rope_layers == "window"
    assert not (c.qk_norm or c.attn_gate or c.post_norms or c.attn_qkv_bias
                or c.tie_embeddings)
    assert (c.expert_act, c.router_input) == ("relu", "attn_norm")
    assert (c.expert_scoring, c.expert_norm_topk) == ("softmax", True)
    assert (c.rope_theta, c.norm_eps, c.vocab_size, c.max_seq_len) \
        == (1.5e6, 1e-6, 151936, 16384)
    assert (c.dtype, c.param_dtype) == ("bfloat16", "bfloat16")
    assert c.num_params() == TOTAL
    assert c.active_params() == TOTAL - 8 * 58 * EXPERT
    for key, bad in (("rope_scaling", {"type": "yarn"}),
                     ("tie_word_embeddings", True),
                     ("moe_primary_router_apply_softmax", False),
                     ("rope_layout", [1] * 52)):
        with pytest.raises(NotImplementedError):
            family.transformer_config({**cell["config_file"], key: bad})


def test_build_params_shapes_are_the_published_count(cell, family):
    import jax

    c = family.transformer_config(cell["config_file"])
    tree = jax.eval_shape(lambda k: family.build_params(c, k),
                          jax.random.PRNGKey(0))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree)) == TOTAL
    moe = tree["layers"]["moe"]
    assert set(tree["layers"]) == {"moe"}
    assert moe["w_gate"].shape == moe["w_up"].shape == (8, 64, 2560, 768)
    assert moe["w_down"].shape == (8, 64, 768, 2560)
    assert moe["router"].shape == (8, 2560, 64)
    assert moe["wq"].shape == (8, 2560, 3584)
    assert moe["wk"].shape == (8, 2560, 512)
    assert not {"wg", "q_norm", "post_attn_norm", "router_bias", "ws_gate"} \
        & set(moe)
    assert {x.dtype.name for x in jax.tree.leaves(tree)} == {"bfloat16"}
    toy = family.transformer_config({**cell["config_file"],
                                     **family.TOY_WIDTHS})
    p = jax.jit(lambda k: family.build_params(toy, k))(jax.random.PRNGKey(1))
    f32 = lambda a: np.asarray(a, np.float32)
    assert abs(f32(p["embed"]).std() - family.EMBED_STD) \
        < 0.05 * family.EMBED_STD
    assert abs(f32(p["layers"]["moe"]["attn_norm"]).mean() - 1) < 0.05
    assert f32(p["layers"]["moe"]["attn_norm"]).std() > 0.05
    down, up, wq, wk, router = (
        f32(p["layers"]["moe"][n]).std()
        for n in ("w_down", "w_up", "wq", "wk", "router"))
    # wq and the router: fan_in^-0.5, Q_GAIN and ROUTER_GAIN times
    assert wq == pytest.approx(family.Q_GAIN * 384 ** -0.5, rel=0.05)
    assert wk == pytest.approx(384 ** -0.5, rel=0.05)
    assert router == pytest.approx(family.ROUTER_GAIN * 384 ** -0.5,
                                   rel=0.05)
    # w_down: fan_in^-0.5 over sqrt(2 L), EXPERT_GAIN times
    assert down == pytest.approx(
        family.EXPERT_GAIN * 128 ** -0.5 / 4, rel=0.05)
    assert up == pytest.approx(384 ** -0.5, rel=0.05)


def test_the_cell_resolves_and_names_its_share(cell):
    man = manifest.load_manifest()
    for what, path in manifest.cell_paths(man, CELL).items():
        assert os.path.isfile(path), (what, path)
    assert cell["kind"] == "serve_state_family" and cell["chips"] == 1
    name = cell["config_file"]["reference"]
    assert name == "preroute_moe_decoder"
    assert os.path.isfile(manifest.reference_path(name))
    names = {m["name"] for m in manifest.metrics_of(man, "per_layer", CELL)}
    assert names >= set(NEW_READERS) | set(APPENDED) | {
        "engine_step_ms", "slot_occupancy_pct", "decode_step_device_ms",
        "device_idle_pct.serve", "chunk_step_ms", "step_host_ms",
        "ttft_prefill_ms", "full_width_time_pct"}
    # the file has no ``num_experts`` key: that reader's list is left alone
    assert not names & {"expert_load_max_over_mean", "ep8_experts_roofline",
                        "gated_attn_proj_roofline", "shared_expert_roofline",
                        "windowed_moe_step_roofline", "ep8_held_pairs_pct"}
    for m in man["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [CELL] and m["moves"] == "tpot_p95_ms"
        if m["name"] in APPENDED:
            assert m["workloads"][-1] == CELL
    assert [m["name"] for m in man["per_layer"][-5:]] == list(NEW_READERS)
    assert man["workloads"][-1]["name"] == CELL
    assert man["configs"][-1]["name"] == CONFIG
    other = manifest.load_cell(man, "trinity-large.mixed-queue")
    assert set(cell["limits"]) == set(other["limits"])
    share = cell["rate_rps"] / cell["knee_rps"]
    assert 0.73 <= share <= 0.90
    assert f"{share:.2f} of its knee" in cell["why"]
    assert f"{cell['rate_rps']:g} req/s" in cell["why"]
    assert len(cell["why"]) <= 200
    # the traffic as the issue gives it, inside max_len and ref_len
    mix, eng = cell["traffic_file"], cell["config_file"]["engine"]
    assert mix["tenants"] == 0 and mix["generator"] == "mixed_lengths"
    assert mix["classes"] == [
        {"name": "short", "share": 0.95, "prompt": "turn_tokens"},
        {"name": "long", "share": 0.05, "prompt": "history_tokens"}]
    assert mix["turn_tokens"] == {"dist": "lognormal", "median": 400,
                                  "sigma": 0.8, "min": 64, "max": 2048}
    assert mix["history_tokens"] == {"dist": "uniform", "min": 4096,
                                     "max": 12288}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 256,
                                    "sigma": 0.7, "min": 32, "max": 1024}
    assert mix["arrivals"] == {"process": "poisson"}
    assert mix["history_tokens"]["max"] + mix["output_tokens"]["max"] \
        <= eng["max_len"] == cell["check"]["ref_len"] == 16384 \
        == cell["config_file"]["max_position_embeddings"]
    want = mixed_lengths.expected_tokens(mix)
    assert 850 < want["prompt"] < 950 and 300 < want["output"] < 340
    # the self-agreement prompt lies past the window
    assert cell["self_agreement"]["prompt_tokens"] == 6144 > 4096 + 128
    assert cell["check"] == {"requests": 4, "new_tokens": 64,
                             "ref_len": 16384}
    assert cell["pre_roll"]["seconds"] == 15
    assert (eng["max_slots"], eng["block_size"], eng["stream_batch"]) \
        == (32, 16, 1)
    assert 10240 <= eng["num_blocks"] <= 20480


def test_both_schedules_follow_the_mixes_rule(cell):
    mix, rate = cell["traffic_file"], cell["rate_rps"]
    assert mixed_lengths.seed_by_rule(mix, rate, 51) == mix["traffic_seed"]
    span = cell["pre_roll"]["seconds"]
    assert mixed_lengths.seed_by_rule(mix, rate, span) \
        == cell["pre_roll"]["traffic_seed"]
    s = mixed_lengths.schedule(mix, rate, 51)
    n = len(s["due_s"])
    assert abs(n - rate * 51) <= 2.5
    assert abs((s["class"] == 1).sum() - 0.05 * n) <= 1
    long = s["prompt_tokens"][s["class"] == 1]
    short = s["prompt_tokens"][s["class"] == 0]
    assert long.min() >= 4096 and long.max() <= 12288
    assert short.min() >= 64 and short.max() <= 2048
    before = serve_state_family.pre_roll_requests(
        cell, rate, 2**31 + 7, cell["config_file"]["vocab_size"])
    assert all(-span <= r.due_s < 0 for r in before)
    assert abs(len(before) - rate * span) <= 2.5


def test_device_bytes_are_the_shapes(cell, family):
    cf = cell["config_file"]
    part = family.layer_params(cf)
    assert part == {"attn_proj": ATTN, "expert": EXPERT,
                    "router": 2560 * 64, "norms": 2 * 2560}
    assert ATTN + 64 * EXPERT + 2560 * 64 + 2 * 2560 == LAYER
    assert 8 * LAYER + 2 * 151936 * 2560 + 2560 == TOTAL
    got = family.device_bytes(cf)
    assert got["parameters"] == TOTAL and got["weights"] == 2 * TOTAL
    assert (got["kv_per_token_full"], got["kv_per_token_window"]) \
        == (4096, 12288)
    assert got["kv_pool_full"] == 4096 * 16 * cf["engine"]["num_blocks"]
    table = {64: 261, 128: 265}[cf["engine"]["prefill_chunk"]]
    assert got["kv_pool_window"] == 12288 * 16 * 32 * table
    for key, value in got.items():
        assert cf["device_bytes"][key] == value, key


def test_step_needs_on_hand_counted_rows(cell, family):
    cf = cell["config_file"]
    # a decoding row past the window, a chunk row past it, a short row
    rows = [(10000, 1, 1), (9000, 64, 0), (300, 1, 1)]
    counters = {"moe_pairs_held": 8 * 6 * 66, "moe_experts_hit": 300}
    needs = family.step_needs(cf, rows, counters)
    assert (needs["fed"], needs["sampled"]) == (66, 2)
    swa_keys = 4096 + (4096 + 63) + 301
    full_keys = 10001 + 9064 + 301
    kv = 2 * 4 * 128 * 2
    q = 28 * 128
    assert needs["swa_attention"]["bytes"] == 6 * (
        kv * swa_keys + 2 * 2 * q * 66)
    assert needs["global_attention"]["bytes"] == 2 * (
        kv * full_keys + 2 * 2 * q * 66)
    seen_swa = 4096 + 64 * 4096 + 301
    seen_full = 10001 + sum(range(9001, 9065)) + 301
    assert needs["swa_attention"]["flops"] == 6 * 4 * 128 * 28 * seen_swa
    assert needs["global_attention"]["flops"] == 2 * 4 * 128 * 28 * seen_full
    assert needs["whole_experts"]["bytes"] == 8 * (
        2 * 2560 * 64 + 2 * 2 * 2560 * 66) + 2 * EXPERT * 300 \
        + 2 * 2 * 2560 * 8 * 6 * 66
    assert needs["whole_experts"]["flops"] == 8 * 2 * 2560 * 64 * 66 \
        + 2 * EXPERT * 8 * 6 * 66
    # the experts' bytes follow the experts HIT
    more = family.step_needs(cf, rows, {**counters, "moe_experts_hit": 512})
    assert more["whole_experts"]["bytes"] - needs["whole_experts"]["bytes"] \
        == 2 * EXPERT * 212
    # 24 decoding rows that hit 57 of 64 experts a layer: issue 53's "about
    # 5.8 of the 6.4 GB a decode step reads are expert weights", redone:
    # 5.4 of 6.9 (the head's 0.78 GB was left out of the issue's 6.4)
    step = family.step_needs(
        cf, [(1000, 1, 1)] * 24,
        {"moe_pairs_held": 8 * 6 * 24, "moe_experts_hit": 8 * 57})
    assert 5.3e9 < 2 * EXPERT * 8 * 57 < 5.5e9
    assert 6.8e9 < step["step"]["bytes"] < 7.0e9
    assert step["step"]["flops"] / 197e12 < step["step"]["bytes"] / 819e9
    # every weight once: a step that hits every expert and samples reads the
    # model less the embedding
    full = family.step_needs(
        cf, [(0, 64, 1)] * 4,
        {"moe_pairs_held": 8 * 6 * 256, "moe_experts_hit": 512})
    head = 2560 * 151936
    weights = 2 * (TOTAL - head)
    assert weights < full["step"]["bytes"] < weights + 0.3e9


def test_scopes_come_from_the_family_file(family):
    assert family.SCOPES == ("swa_attention", "global_attention",
                             "moe_router", "moe_experts")
    assert family.KERNELS == {"ragged-dot": "moe_experts"}
    assert {"moe_pairs_routed", "moe_kernel_pairs", "moe_experts_hit"} \
        <= set(family.STEP_COUNTERS)
    text = '''
  %paged_attention_fwd.14 = bf16[32,4,448,128]{3,2,1,0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(s)/while/body/closed_call/swa_attention/paged_attention/jit(_paged_attention_pallas)/paged_attention_fwd/pallas_call"}
  %paged_attention_fwd.17 = bf16[32,4,448,128]{3,2,1,0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(s)/global_attention/paged_attention/jit(_paged_attention_pallas)/paged_attention_fwd/pallas_call"}
  %fusion.3 = bf16[1,256,3584]{2,1,0} fusion(%h), kind=kOutput, calls=%f, metadata={op_name="jit(s)/cond/branch_0_fun/qkv_proj/dot_general"}
  %fusion.5 = f32[256,64]{1,0} fusion(%h), kind=kOutput, calls=%f, metadata={op_name="jit(s)/while/body/closed_call/cond/branch_0_fun/moe_router/dot_general"}
  %expert_mlp_fwd.2 = f32[1536,8,384]{2,1,0} custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(s)/cond/branch_0_fun/mlp/moe_experts/jit(expert_mlp_pairs)/expert_mlp_fwd/pallas_call"}
  %ragged-dot-none.3 = f32[1536,768]{1,0} custom-call(%a, %b), custom_call_target="x", metadata={op_name="ragged-dot-none.3"}
'''
    assert replica.scopes_of_instructions(
        text, family.SCOPES, family.KERNELS) == {
        "paged_attention_fwd.14": "swa_attention",
        "paged_attention_fwd.17": "global_attention",
        "fusion.5": "moe_router", "expert_mlp_fwd.2": "moe_experts",
        "ragged-dot-none.3": "moe_experts"}


def test_readers_over_a_recorded_toy_run(cell, family):
    cf = cell["config_file"]
    rows = [(10000, 1, 1)] * 2 + [(600, 1, 1)] * 20 + [(9000, 64, 0)]
    fed = 22 + 64
    counters = {"moe_pairs_held": 48 * fed, "moe_pairs_routed": 48 * fed,
                "moe_kernel_pairs": 48 * fed, "moe_experts_hit": 400,
                "window_blocks_held": 1200, "window_blocks_full_table": 4000,
                "window_blocks_released": 9,
                "moe_expert_tokens_sum": 48 * fed,
                "moe_expert_tokens_max": 160, "steps": 1,
                "step_positions_real": fed, "step_positions_run": 256,
                "attn_rows_attended": 23, "attn_token_tile_rows": 22,
                "steps_decode_only": 0, "step_s_decode_only": 0.0}
    steps = [(float(i), i + 0.9, rows) for i in range(4)]
    stats0 = {**{k: 0 for k in counters}, "requests_admitted": 0,
              "window_blocks_wait_s": 0.0}
    stats1 = {**{k: 4 * v for k, v in counters.items()},
              "requests_admitted": 8, "window_blocks_wait_s": 0.4,
              "steps_decode_only": 2, "step_s_decode_only": 0.024}
    needs = family.step_needs(cf, rows, counters)
    scope_s = {"swa_attention": 4e-3, "global_attention": 2e-3,
               "moe_router": 0.6e-3, "moe_experts": 14e-3}
    trace = {"n_devices": 1, "window_monotonic": [0.5, 3.5],
             "window_s": 3.0, "busy_s": 2.0, "program_runs_ms": [20.0, 20.0],
             "device_ops": [], "idle_gaps": [], "scope_s": scope_s}
    outcome = {"replica": {"steps": steps, "step_counters": [counters] * 4,
                           "max_slots": 32, "engine_ttft": {}},
               "trace": trace, "config_file": cf, "cell": cell,
               "facts": {"kind": "TPU v5 lite"}, "window": (0.0, 4.0),
               "clients": [],
               "marks": {"start": {"stats": stats0}, "end": {"stats": stats1}}}
    assert len(family_rooflines.traced_steps(outcome)) == 2
    read = lambda name, run=outcome: manifest.load_module(
        manifest.layer_metric_path(name)).read(run)
    least = lambda scope: max(needs[scope]["bytes"] / 819e9,
                              needs[scope]["flops"] / 197e12)
    for name, scope in (("swa_attention_roofline", "swa_attention"),
                        ("global_attention_roofline", "global_attention")):
        assert read(name) == pytest.approx(
            100 * 2 * least(scope) / scope_s[scope])
    assert read("whole_experts_roofline") == pytest.approx(
        100 * 2 * least("whole_experts") / 14.6e-3)
    assert read("preroute_moe_step_roofline") == pytest.approx(
        100 * least("step") / 20e-3)
    assert read("experts_hit_pct") == pytest.approx(100 * 400 / 512)
    assert read("route_device_ms") == pytest.approx(0.3)
    # the busiest expert of each of 8 layers took 20 of a mean 86 * 6 / 64
    assert read("whole_expert_load_max_over_mean") == pytest.approx(
        160 * 64 / (48 * fed))
    assert read("step_positions_real_pct") == pytest.approx(100 * fed / 256)
    assert read("expert_kernel_pairs_pct") == pytest.approx(100.0)
    assert read("swa_kv_held_pct") == pytest.approx(30.0)
    assert read("window_blocks_wait_ms") == pytest.approx(50.0)
    assert read("paged_token_tile_rows_pct") == pytest.approx(100 * 22 / 23)
    assert read("decode_only_step_ms") == pytest.approx(12.0)
    for name in NEW_READERS:
        assert 0 <= read(name) <= 100, name
    assert read("whole_expert_load_max_over_mean") >= 1.0
    # a program without the scopes or the counters (another program under
    # these files): nothing to read, nothing raised
    bare = {**outcome, "trace": {**trace, "scope_s": {}},
            "marks": {"start": {"stats": {}}, "end": {"stats": {}}}}
    for name in NEW_READERS:
        if name != "preroute_moe_step_roofline":
            assert read(name, bare) is None, name
    untraced = {**outcome, "trace": None}
    for name in ("whole_experts_roofline", "preroute_moe_step_roofline",
                 "route_device_ms"):
        assert read(name, untraced) is None, name


def test_rehearsal_cell_runs_the_toy_widths(cell, family):
    toy = serve_state_family.rehearsal_cell(cell)
    c = family.transformer_config(toy["config_file"])
    assert (c.d_model, c.n_layers, c.sliding_window) == (384, 8, 8)
    assert c.layer_windows == (0, 8, 8, 8, 0, 8, 8, 8)
    assert (c.num_experts, c.held_experts, c.expert_top_k) == (8, 8, 3)
    assert c.d_model % 128 == 0 and c.d_model % 1024
    assert toy["pre_roll"]["seconds"] == 2.0


def _plain(params, tokens, cf, control="as_given"):
    """The equations of the reference's docstring written a second time, a
    position and a head at a time in numpy float64, no blocks: logits at
    every position."""
    f = lambda a: np.asarray(a, np.float64)
    lp = {k: f(v) for k, v in params["layers"]["moe"].items()}
    heads, kvh, hd = (cf["num_attention_heads"], cf["num_key_value_heads"],
                      cf["head_dim"])
    k_top, eps = cf["moe_num_active_primary_experts"], cf["rms_norm_eps"]
    norm = lambda x, g: x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * g

    def rope(x, pos):
        half = hd // 2
        ang = pos * cf["rope_theta"] ** (-2.0 * np.arange(half) / hd)
        lo, hi = x[..., :half], x[..., half:]
        return np.concatenate([lo * np.cos(ang) - hi * np.sin(ang),
                               hi * np.cos(ang) + lo * np.sin(ang)], -1)

    def route(h, router):
        z = h @ router
        p = np.exp(z - z.max())
        p /= p.sum()
        sel = np.argsort(-p, kind="stable")[:k_top]
        return sel, p[sel] / p[sel].sum()

    x = f(params["embed"])[np.asarray(tokens)]
    t = len(tokens)
    for l in range(cf["num_hidden_layers"]):
        window = cf["sliding_window_size"] * cf["sliding_window_layout"][l]
        h = norm(x, lp["attn_norm"][l])
        routes = [route(h[p], lp["router"][l]) for p in range(t)]
        q = (h @ lp["wq"][l]).reshape(t, heads, hd)
        k = (h @ lp["wk"][l]).reshape(t, kvh, hd)
        v = (h @ lp["wv"][l]).reshape(t, kvh, hd)
        if cf["rope_layout"][l]:
            q = np.stack([rope(q[p], p) for p in range(t)])
            k = np.stack([rope(k[p], p) for p in range(t)])
        a = np.zeros((t, heads, hd))
        for p in range(t):
            lo = max(p - window + 1, 0) if window else 0
            for n in range(heads):
                g = n // (heads // kvh)
                s = k[lo:p + 1, g] @ q[p, n] / np.sqrt(hd)
                w = np.exp(s - s.max())
                a[p, n] = (w / w.sum()) @ v[lo:p + 1, g]
        x = x + a.reshape(t, -1) @ lp["wo"][l]
        u = norm(x, lp["mlp_norm"][l])
        for p in range(t):
            sel, w = route(u[p], lp["router"][l]) \
                if control == "route_post_attention" else routes[p]
            for e, share in zip(sel, w):
                g = u[p] @ lp["w_gate"][l, e]
                g = g / (1 + np.exp(-g)) if control == "silu" \
                    else np.maximum(g, 0)
                x[p] = x[p] + share * (
                    (g * (u[p] @ lp["w_up"][l, e])) @ lp["w_down"][l, e])
    return norm(x, f(params["final_norm"])) @ f(params["lm_head"])


@pytest.fixture(scope="module")
def toy(cell, family):
    import jax

    cf = {**cell["config_file"], **family.TOY_WIDTHS, "hidden_size": 128,
          "moe_ffn_hidden_size": 32}
    c = family.transformer_config(cf, dtype="float32", param_dtype="float32")
    params = jax.jit(lambda k: family.build_params(c, k))(
        jax.random.PRNGKey(4))
    ref = manifest.load_module(manifest.reference_path(cf["reference"]))
    tokens = np.random.default_rng(0).integers(0, 512, 40).astype(np.int32)
    return cf, params, ref, tokens


@pytest.mark.parametrize("control", ["as_given", "route_post_attention",
                                     "silu"])
def test_the_reference_is_its_equations_written_plainly(toy, control):
    """The blocked float32 reference against the unblocked float64 writing,
    at every position of a sequence five windows long: the honest pass, and
    the two faults of this model's own (so that each control moves what it
    says it moves, and nothing else)."""
    cf, params, ref, tokens = toy
    rows = np.arange(len(tokens))
    got = np.asarray(ref.logits_at(params, tokens, rows, cf,
                                   weights=control))
    want = _plain(params, tokens, cf, control)
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < 2e-5


@pytest.mark.parametrize("control", [
    "int8", "route_post_attention", "silu", "rope_in_full", "no_rope",
    "window_short_a_block", "no_norm_topk"])
def test_the_reference_sees_each_piece_moved(toy, control, monkeypatch):
    cf, params, ref, tokens = toy
    monkeypatch.setattr(ref, "WINDOW_BLOCK", 4)
    rows = np.arange(24, 40)
    sound = np.asarray(ref.logits_at(params, tokens, rows, cf))
    got = np.asarray(ref.logits_at(params, tokens, rows, cf, weights=control))
    err = np.linalg.norm(got - sound) / np.linalg.norm(sound)
    assert err > (0.002 if control == "int8" else 0.02), err
    with pytest.raises(ValueError, match="unknown control"):
        ref.logits_at(params, tokens, rows, cf, weights="fp4")
