"""The windowed MoE family's file and its cell, CPU only, no ray_tpu runtime:
the configuration against the catalog row (which ``test_manifest.py``'s
``PUBLISHED`` does not hold yet: PERF.md section 7, "Left by PR 46"), its
bytes against the shapes, ``build_params`` against the published count,
``step_needs`` on hand-counted rows, the scope map with the family's lists,
the eight new readers over a synthetic run, the mix's generator and its seed
rule, and the reference against itself with mathematics left out."""

import os

import numpy as np
import pytest

from benchmark import family_rooflines, manifest
from benchmark.generators import mixed_lengths
from benchmark.kinds import serve_state_family
from benchmark.kinds import serve_state_family_replica as replica

CELL = "trinity-large.mixed-queue"
CONFIG = "trinity-large-ep8-l5-serve"
READERS = ("swa_attention_roofline", "global_attention_roofline",
           "gated_attn_proj_roofline", "ep8_experts_roofline",
           "windowed_moe_step_roofline", "swa_kv_held_pct",
           "ep8_held_pairs_pct", "window_blocks_wait_ms",
           "mixed_queue_positions_real_pct", "ep8_expert_load_max_over_mean")

#: the catalog row's ``config`` (architectures.jsonl, Trinity-Large-Preview),
#: its 60 ``layer_types`` as the rule that gives them
ROW = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 3072, "intermediate_size": 12288,
    "layer_types": ["full_attention" if i % 4 == 3 else "sliding_attention"
                    for i in range(60)],
    "load_balance_coeff": 5e-05, "max_position_embeddings": 262144,
    "model_type": "afmoe", "moe_intermediate_size": 3072,
    "mup_enabled": True, "n_group": 1, "num_attention_heads": 48,
    "num_dense_layers": 6, "num_expert_groups": 1, "num_experts": 256,
    "num_experts_per_tok": 4, "num_hidden_layers": 60,
    "num_key_value_heads": 8, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "route_norm": True, "route_scale": 2.448,
    "score_func": "sigmoid", "sliding_window": 4096,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192}

REDUCED = {"num_hidden_layers": 5, "num_experts": 32, "vocab_size": 25024}
TOTAL = 4_321_903_872
ATTN = 62_914_816       # q, k, v, gate, o and the two head norms
EXPERT = 28_311_552


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(manifest.load_manifest(), CELL)


@pytest.fixture(scope="module")
def family(cell):
    return replica.load_family(cell["config_file"])


def test_the_configuration_keeps_the_catalog_row_but_the_cut(cell):
    cf = cell["config_file"]
    for key, value in ROW.items():
        assert cf[key] == REDUCED.get(key, value), key
    assert set(cf["reduced"]) == set(REDUCED)
    for key, here in REDUCED.items():
        assert cf["reduced"][key]["published"] == ROW[key]
        assert cf["reduced"][key]["here"] == here
    assert cf["reduced"]["num_hidden_layers"]["dense_here"] == 1
    assert cf["reduced"]["num_experts"]["first"] == 0
    entry = next(c for c in manifest.load_manifest()["configs"]
                 if c["name"] == CONFIG)
    assert entry["reduced"] == list(REDUCED)
    assert entry["source"] == cf["source"] and len(entry["why"]) <= 200
    for key in ("embedding_scale", "rope_layers", "attention_gate", "qk_norm",
                "norms", "router_precision", "rope_pairing", "expert_bias",
                "weights", "engine"):
        assert cf["assumed"][key]
    assert "one of 8 chips" in cf["stands_for"]


def test_transformer_config_reads_every_published_key(cell, family):
    c = family.transformer_config(cell["config_file"])
    assert (c.d_model, c.n_heads, c.kv_heads, c.hdim, c.ff, c.ff_expert) \
        == (3072, 48, 8, 128, 12288, 3072)
    assert (c.n_layers, c.dense_layers, c.num_experts, c.held_experts,
            c.experts_first, c.expert_top_k, c.shared_experts) \
        == (5, 1, 256, 32, 0, 4, 1)
    assert c.layer_windows == (4096, 4096, 4096, 0, 4096)
    assert c.window_pool and c.rope_layers == "window" and c.qk_norm
    assert c.attn_gate and c.post_norms and not c.tie_embeddings
    assert (c.expert_scoring, c.expert_scale, c.expert_norm_topk) \
        == ("sigmoid", 2.448, True)
    assert c.embedding_multiplier == pytest.approx(3072 ** 0.5)
    assert (c.rope_theta, c.norm_eps, c.vocab_size) == (1e4, 1e-5, 25024)
    assert (c.dtype, c.param_dtype) == ("bfloat16", "bfloat16")
    assert c.num_params() == TOTAL
    for key, bad in (("n_group", 2), ("rope_scaling", {"type": "yarn"}),
                     ("tie_word_embeddings", True), ("mup_enabled", False),
                     ("num_dense_layers", 0)):
        cf = {**cell["config_file"], key: bad}
        if key == "num_dense_layers":
            cf["reduced"] = {**cf["reduced"], "num_hidden_layers": {
                "published": 60, "here": 5, "dense_here": 5}}
        with pytest.raises(NotImplementedError):
            family.transformer_config(cf)


def test_build_params_shapes_are_the_published_count(cell, family):
    import jax

    c = family.transformer_config(cell["config_file"])
    tree = jax.eval_shape(lambda k: family.build_params(c, k),
                          jax.random.PRNGKey(0))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree)) == TOTAL
    moe, dense = tree["layers"]["moe"], tree["layers"]["dense"]
    assert moe["w_gate"].shape == (4, 32, 3072, 3072)
    assert moe["router"].shape == (4, 3072, 256)
    assert moe["router_bias"].shape == (4, 256)
    assert moe["wg"].shape == (4, 3072, 6144)
    assert dense["w_up"].shape == (1, 3072, 12288)
    assert {x.dtype.name for x in jax.tree.leaves(tree)} == {"bfloat16"}
    toy = family.transformer_config({**cell["config_file"],
                                     **family.TOY_WIDTHS})
    p = jax.jit(lambda k: family.build_params(toy, k))(jax.random.PRNGKey(1))
    f32 = lambda a: np.asarray(a, np.float32)
    # gains and the selection bias away from their trivial values
    assert abs(f32(p["layers"]["moe"]["q_norm"]).mean()
               - family.Q_GAIN) < 0.1
    depth = family.post_gain(5)
    assert depth == pytest.approx(0.316, abs=1e-3)
    assert abs(f32(p["layers"]["moe"]["post_attn_norm"]).mean() - depth) \
        < 0.03
    assert f32(p["layers"]["moe"]["post_attn_norm"]).std() > 0.02
    assert abs(f32(p["layers"]["moe"]["router_bias"]).std()
               - family.BIAS_STD) < 0.01
    # the embedding times its multiplier at unit scale
    assert abs(f32(p["embed"]).std() * toy.embedding_multiplier - 1) < 0.1


def test_the_cell_resolves_and_names_its_share(cell):
    man = manifest.load_manifest()
    for what, path in manifest.cell_paths(man, CELL).items():
        assert os.path.isfile(path), (what, path)
    assert cell["kind"] == "serve_state_family" and cell["chips"] == 1
    name = cell["config_file"]["reference"]
    assert name == "windowed_moe_decoder"
    assert os.path.isfile(manifest.reference_path(name))
    names = {m["name"] for m in manifest.metrics_of(man, "per_layer", CELL)}
    assert names >= set(READERS) | {"engine_step_ms", "slot_occupancy_pct",
                                    "decode_step_device_ms",
                                    "device_idle_pct.serve",
                                    "chunk_step_ms", "step_host_ms",
                                    "ttft_prefill_ms",
                                    "full_width_time_pct"}
    # a window of this cell can hold no step without a prompt row (four or
    # five long prompts are always prefilling), so the mean of such steps
    # has nothing to read here: the metric lists the six cells before this
    accepted = [w["name"] for w in man["workloads"] if w["name"] != CELL]
    decode_only, = [m for m in man["per_layer"]
                    if m["name"] == "decode_only_step_ms"]
    assert decode_only["workloads"] == accepted
    assert "decode_only_step_ms" not in names
    assert not names & {"decode_step_roofline", "prefix_hit_token_pct",
                        "window_kv_held_pct", "held_expert_pairs_pct",
                        "held_experts_roofline"}
    for m in man["per_layer"]:
        if m["name"] in READERS:
            assert m["workloads"] == [CELL]
    other = manifest.load_cell(man, "falcon-h1-34b.chat-concurrent")
    assert set(cell["limits"]) == set(other["limits"])
    share = cell["rate_rps"] / cell["knee_rps"]
    assert 0.73 <= share <= 0.90
    assert f"{share:.2f} of its knee" in cell["why"]
    assert "1/8" in cell["why"] and "host" in cell["why"]
    assert len(cell["why"]) <= 200
    # the traffic as the issue gives it, inside max_len and ref_len
    mix, eng = cell["traffic_file"], cell["config_file"]["engine"]
    assert mix["tenants"] == 0 and mix["generator"] == "mixed_lengths"
    assert mix["classes"] == [
        {"name": "short", "share": 0.8, "prompt": "turn_tokens"},
        {"name": "long", "share": 0.2, "prompt": "history_tokens"}]
    assert mix["turn_tokens"] == {"dist": "lognormal", "median": 400,
                                  "sigma": 0.8, "min": 64, "max": 2048}
    assert mix["history_tokens"] == {"dist": "uniform", "min": 8192,
                                     "max": 30720}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 160,
                                    "sigma": 0.6, "min": 32, "max": 512}
    assert mix["arrivals"] == {"process": "poisson"}
    assert mix["history_tokens"]["max"] + mix["output_tokens"]["max"] \
        <= eng["max_len"] == cell["check"]["ref_len"] == 32768
    assert mix["history_tokens"]["max"] + cell["check"]["new_tokens"] \
        <= cell["check"]["ref_len"]
    # the self-agreement prompt lies past the window
    assert cell["self_agreement"]["prompt_tokens"] > 4096 + 128
    assert cell["pre_roll"]["seconds"] == 15
    assert (eng["max_slots"], eng["block_size"], eng["prefill_chunk"]) \
        == (32, 16, 64)
    assert eng["num_blocks"] == 20480


def test_both_schedules_follow_the_mixes_rule(cell):
    mix, rate = cell["traffic_file"], cell["rate_rps"]
    assert mixed_lengths.seed_by_rule(mix, rate, 51) == mix["traffic_seed"]
    span = cell["pre_roll"]["seconds"]
    assert mixed_lengths.seed_by_rule(mix, rate, span) \
        == cell["pre_roll"]["traffic_seed"]
    s = mixed_lengths.schedule(mix, rate, 51)
    n = len(s["due_s"])
    assert abs(n - rate * 51) <= 2.5
    assert abs((s["class"] == 1).sum() - 0.2 * n) <= 1
    long = s["prompt_tokens"][s["class"] == 1]
    short = s["prompt_tokens"][s["class"] == 0]
    assert long.min() >= 8192 and long.max() <= 30720
    assert short.min() >= 64 and short.max() <= 2048
    before = serve_state_family.pre_roll_requests(
        cell, rate, 7, cell["config_file"]["vocab_size"])
    assert all(-span <= r.due_s < 0 for r in before)
    assert abs(len(before) - rate * span) <= 2.5


def test_a_request_keeps_its_class_and_sizes_at_every_rate(cell):
    mix = cell["traffic_file"]
    slow, fast = (mixed_lengths.schedule(mix, r, 40) for r in (1.0, 2.0))
    n = len(slow["due_s"])
    for key in ("class", "prompt_tokens", "output_tokens"):
        assert np.array_equal(slow[key], fast[key][:n]), key
    assert np.allclose(slow["due_s"], 2.0 * fast["due_s"][:n])
    a = mixed_lengths.generate(mix, 1.5, 20, 2**31 + 11, 25024)
    b = mixed_lengths.generate(mix, 1.5, 20, 2**31 + 11, 25024)
    assert [r.prompt for r in a] == [r.prompt for r in b]
    assert all(0 <= t < 25024 for r in a for t in r.prompt[:50])
    assert mixed_lengths.warm_prompts(mix, 3, 25024) == []
    with pytest.raises(ValueError, match="shares sum"):
        mixed_lengths.schedule({**mix, "classes": mix["classes"][:1]}, 1, 9)


def test_device_bytes_are_the_shapes(cell, family):
    cf = cell["config_file"]
    part = family.layer_params(cf)
    assert part["gated_attn_proj"] == ATTN + 2 * 3072
    assert part["expert"] == part["shared"] == EXPERT
    assert part["router"] == 3072 * 256 + 256
    got = family.device_bytes(cf)
    assert got["parameters"] == TOTAL and got["weights"] == 2 * TOTAL
    assert (got["kv_per_token_full"], got["kv_per_token_window"]) \
        == (4096, 16384)
    assert got["kv_pool_full"] == 4096 * 16 * cf["engine"]["num_blocks"]
    assert got["kv_pool_window"] == 16384 * 16 * 32 * 261
    for key, value in got.items():
        assert cf["device_bytes"][key] == value, key


def test_step_needs_on_hand_counted_rows(cell, family):
    cf = cell["config_file"]
    # a decoding row past the window, a chunk row past it, a short row
    rows = [(20000, 1, 1), (9000, 128, 0), (300, 1, 1)]
    counters = {"moe_pairs_held": 70, "moe_experts_hit": 40}
    needs = family.step_needs(cf, rows, counters)
    assert (needs["fed"], needs["sampled"]) == (130, 2)
    swa_keys = 4096 + (4096 + 127) + 301
    full_keys = 20001 + 9128 + 301
    kv = 2 * 8 * 128 * 2
    q = 48 * 128
    assert needs["swa_attention"]["bytes"] == 4 * (
        kv * swa_keys + 2 * 2 * q * 130)
    assert needs["global_attention"]["bytes"] == kv * full_keys \
        + 2 * 2 * q * 130
    seen_swa = 4096 + 128 * 4096 + 301
    seen_full = 20001 + sum(range(9001, 9129)) + 301
    assert needs["swa_attention"]["flops"] == 4 * 4 * 128 * 48 * seen_swa
    assert needs["global_attention"]["flops"] == 4 * 128 * 48 * seen_full
    part = family.layer_params(cf)
    assert needs["gated_attn_proj"]["flops"] == 5 * 2 * part[
        "gated_attn_proj"] * 130
    assert needs["ep8_experts"]["bytes"] == 4 * (
        2 * (part["router"] + part["shared"]) + 2 * 2 * 3072 * 130) \
        + 2 * EXPERT * 40 + 2 * 2 * 3072 * 70
    # the experts' bytes follow the experts HIT, not the experts held
    more = family.step_needs(cf, rows, {**counters, "moe_experts_hit": 128})
    assert more["ep8_experts"]["bytes"] - needs["ep8_experts"]["bytes"] \
        == 2 * EXPERT * 88
    # a step of 256 real positions that hits every held expert reads all
    # the weights but the embedding: issue 46's 8.6 GB
    full = family.step_needs(
        cf, [(0, 128, 0), (128, 128, 1)],
        {"moe_pairs_held": 128, "moe_experts_hit": 128})
    assert 8.4e9 < full["step"]["bytes"] < 8.8e9
    assert full["step"]["flops"] / 197e12 < full["step"]["bytes"] / 819e9
    # every weight once: the parts add up to the model less the embedding
    other = part["dense_mlp"] + 5 * part["mlp_norms"] + 3072
    head = 3072 * 25024
    assert other + 5 * part["gated_attn_proj"] + 4 * (
        part["router"] + part["shared"] + 32 * EXPERT) + head \
        == TOTAL - head


def test_scopes_come_from_the_family_file(family):
    assert family.SCOPES == ("swa_attention", "global_attention",
                             "gated_attn_proj", "moe_router", "moe_experts",
                             "shared_expert")
    assert family.KERNELS == {"ragged-dot": "moe_experts"}
    text = '''
  %paged_attention_fwd.14 = bf16[32,8,768,128]{3,2,1,0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(s)/while/body/closed_call/swa_attention/paged_attention/jit(_paged_attention_pallas)/paged_attention_fwd/pallas_call"}
  %paged_attention_fwd.17 = bf16[32,8,768,128]{3,2,1,0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(s)/global_attention/paged_attention/jit(_paged_attention_pallas)/paged_attention_fwd/pallas_call"}
  %fusion.3 = bf16[1,256,6144]{2,1,0} fusion(%h), kind=kOutput, calls=%f, metadata={op_name="jit(s)/cond/branch_0_fun/qkv_proj/gated_attn_proj/dot_general"}
  %fusion.4 = bf16[1,256,3072]{2,1,0} fusion(%h), kind=kOutput, calls=%f, metadata={op_name="jit(s)/cond/branch_0_fun/attn_out_proj/gated_attn_proj/dot_general"}
  %fusion.5 = f32[1024,256]{1,0} fusion(%h), kind=kOutput, calls=%f, metadata={op_name="jit(s)/cond/branch_0_fun/mlp/moe_router/dot_general"}
  %ragged-dot-none.3 = f32[1024,3072]{1,0} custom-call(%a, %b), custom_call_target="x", metadata={op_name="ragged-dot-none.3"}
  %fusion.6 = bf16[1,256,3072]{2,1,0} fusion(%h), kind=kOutput, calls=%f, metadata={op_name="jit(s)/cond/branch_0_fun/mlp/shared_expert/dot_general"}
  %fusion.7 = bf16[1,256,3072]{2,1,0} fusion(%h), kind=kLoop, calls=%f, metadata={op_name="jit(s)/cond/branch_0_fun/rope/mul"}
'''
    assert replica.scopes_of_instructions(
        text, family.SCOPES, family.KERNELS) == {
        "paged_attention_fwd.14": "swa_attention",
        "paged_attention_fwd.17": "global_attention",
        "fusion.3": "gated_attn_proj", "fusion.4": "gated_attn_proj",
        "fusion.5": "moe_router", "ragged-dot-none.3": "moe_experts",
        "fusion.6": "shared_expert"}


def test_readers_over_a_synthetic_run(cell, family):
    cf = cell["config_file"]
    rows = [(20000, 1, 1)] * 3 + [(600, 1, 1)] * 6 + [(9000, 128, 0)]
    counters = {"moe_pairs_held": 70, "moe_experts_hit": 40,
                "moe_pairs_routed": 560, "window_blocks_held": 1200,
                "window_blocks_full_table": 4000,
                "window_blocks_released": 9,
                "moe_expert_tokens_sum": 70, "moe_expert_tokens_max": 14,
                "step_positions_real": 137, "step_positions_run": 2048}
    steps = [(float(i), i + 0.9, rows) for i in range(4)]
    stats0 = {**{k: 0 for k in counters}, "requests_admitted": 0,
              "window_blocks_wait_s": 0.0, "pending_wait_s": 0.0}
    stats1 = {**{k: 4 * v for k, v in counters.items()},
              "requests_admitted": 8, "window_blocks_wait_s": 0.4,
              "pending_wait_s": 1.0}
    needs = family.step_needs(cf, rows, counters)
    scope_s = {"swa_attention": 4e-3, "global_attention": 2e-3,
               "gated_attn_proj": 3e-3, "moe_router": 0.2e-3,
               "moe_experts": 6e-3, "shared_expert": 0.8e-3}
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
    read = lambda name: manifest.load_module(
        manifest.layer_metric_path(name)).read(outcome)
    least = lambda scope: max(needs[scope]["bytes"] / 819e9,
                              needs[scope]["flops"] / 197e12)
    for name, scope in (("swa_attention_roofline", "swa_attention"),
                        ("global_attention_roofline", "global_attention"),
                        ("gated_attn_proj_roofline", "gated_attn_proj")):
        assert read(name) == pytest.approx(
            100 * 2 * least(scope) / scope_s[scope])
    assert read("ep8_experts_roofline") == pytest.approx(
        100 * 2 * least("ep8_experts") / 7e-3)
    assert read("windowed_moe_step_roofline") == pytest.approx(
        100 * least("step") / 20e-3)
    assert read("swa_kv_held_pct") == pytest.approx(30.0)
    assert read("ep8_held_pairs_pct") == pytest.approx(12.5)
    assert read("window_blocks_wait_ms") == pytest.approx(50.0)
    assert read("mixed_queue_positions_real_pct") == pytest.approx(
        100 * 137 / 2048)
    # 14 tokens on the layers' busiest held experts against 70 / 32 a piece
    assert read("ep8_expert_load_max_over_mean") == pytest.approx(6.4)
    for name in READERS:
        assert 0 <= read(name) <= 100, name
    # a program without the scopes or the counters (the parent under these
    # files): nothing to read, nothing raised
    bare = {**outcome, "trace": {**trace, "scope_s": {}},
            "marks": {"start": {"stats": {}}, "end": {"stats": {}}}}
    for name in READERS:
        if name != "windowed_moe_step_roofline":
            assert manifest.load_module(
                manifest.layer_metric_path(name)).read(bare) is None, name
    untraced = {**outcome, "trace": None}
    for name in READERS[:5]:
        assert manifest.load_module(
            manifest.layer_metric_path(name)).read(untraced) is None, name


def test_rehearsal_cell_runs_the_toy_widths(cell, family):
    toy = serve_state_family.rehearsal_cell(cell)
    c = family.transformer_config(toy["config_file"])
    assert (c.d_model, c.n_layers, c.sliding_window) == (64, 5, 8)
    assert c.layer_windows == (8, 8, 8, 0, 8)
    assert (c.num_experts, c.held_experts, c.experts_first) == (16, 4, 4)
    assert toy["traffic_file"]["history_tokens"]["max"] == 30720 // 128
    assert toy["pre_roll"]["seconds"] == 2.0
    assert isinstance(toy["config_file"]["engine"]["num_blocks"], int)


@pytest.mark.parametrize("control", [
    "int8", "no_gate", "no_post_norms", "no_router_bias",
    "window_short_a_block", "rope_in_full", "no_rope", "no_embed_scale",
    "no_scale", "no_shared", "no_held", "no_qk_norm"])
def test_the_reference_sees_each_piece_left_out(cell, family, control,
                                                monkeypatch):
    import jax

    ref = manifest.load_module(manifest.reference_path(
        cell["config_file"]["reference"]))
    monkeypatch.setattr(ref, "WINDOW_BLOCK", 4)
    cf = {**cell["config_file"], **family.TOY_WIDTHS}
    c = family.transformer_config(cf, dtype="float32", param_dtype="float32")
    params = jax.jit(lambda k: family.build_params(c, k))(
        jax.random.PRNGKey(4))
    tokens = np.random.default_rng(0).integers(0, 512, 64).astype(np.int32)
    rows = np.arange(40, 64)
    sound = np.asarray(ref.logits_at(params, tokens, rows, cf))
    got = np.asarray(ref.logits_at(params, tokens, rows, cf, weights=control))
    err = np.linalg.norm(got - sound) / np.linalg.norm(sound)
    assert err > (0.002 if control == "int8" else 0.02), err
    with pytest.raises(ValueError, match="unknown control"):
        ref.logits_at(params, tokens, rows, cf, weights="fp4")
