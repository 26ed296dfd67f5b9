"""The latent-attention MoE decoder family's file and its cell, CPU only, no
ray_tpu runtime: the configuration against the catalog row, its bytes
against the shapes, ``step_needs`` on hand-counted rows, the scope map with
the family's lists, the six new readers over a synthetic run, and the
rehearsal's toy cell."""

import json
import os

import pytest

from benchmark import family_rooflines, manifest, run
from benchmark.kinds import serve_family, serve_family_replica
from benchmark.kinds import serve_state_family
from benchmark.kinds import serve_state_family_replica as replica

CELL = "kimi-k2.5.longdoc-reask"
CONFIG = "kimi-k2.5-ep32-l5-serve"
ROOFLINES = {"mla_attention_roofline": "mla_attention",
             "mla_projections_roofline": "mla_projections",
             "held_experts_roofline": "moe_experts",
             "shared_expert_roofline": "shared_expert"}
READERS = tuple(ROOFLINES) + ("latent_moe_step_roofline",
                              "held_expert_pairs_pct")

#: the catalog row's ``config`` (model-configs/architectures.jsonl, Kimi-K2.5)
ROW = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 7168, "intermediate_size": 18432,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "kimi_k2", "moe_intermediate_size": 2048,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 384,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 64,
    "num_nextn_predict_layers": 0, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 50000, "routed_scaling_factor": 2.827,
    "scoring_func": "sigmoid", "seq_aux": True, "tf_legacy_loss": False,
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 163840}
CUT = {"num_hidden_layers": 5, "n_routed_experts": 12, "vocab_size": 20480}


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(manifest.load_manifest(), CELL)


@pytest.fixture(scope="module")
def family(cell):
    return replica.load_family(cell["config_file"])


def test_the_configuration_keeps_every_key_of_the_catalog_row(cell, family):
    cf = cell["config_file"]
    for key, value in ROW.items():
        assert cf[key] == CUT.get(key, value), key
    # what is cut says what was published, and nothing else is cut
    assert set(cf["reduced"]) == set(CUT)
    for key, here in CUT.items():
        assert cf["reduced"][key]["published"] == ROW[key]
        assert cf["reduced"][key]["here"] == here == cf[key]
    entry = next(c for c in manifest.load_manifest()["configs"]
                 if c["name"] == CONFIG)
    assert sorted(entry["reduced"]) == sorted(CUT)
    assert entry["source"] == cf["source"]
    assert set(cf["assumed"]) >= {"vision_tower", "rope_pairing", "yarn",
                                  "group_routing", "weights", "engine"}
    assert "32 chips" in cf["stands_for"]
    # the share: the router as wide as published, 12 held from index 0, and
    # the guide's floors (four expert layers, eight experts, an eighth)
    assert family.share(cf) == {"published": 384, "held": 12, "first": 0}
    assert cf["num_hidden_layers"] - cf["first_k_dense_replace"] >= 4
    assert cf["n_routed_experts"] >= 8
    assert cf["vocab_size"] * 8 >= ROW["vocab_size"]
    tc = family.transformer_config(cf)
    assert (tc.num_experts, tc.held_experts, tc.experts_first,
            tc.expert_top_k) == (384, 12, 0, 8)
    assert (tc.dense_layers, tc.ff, tc.ff_expert, tc.shared_experts) \
        == (1, 18432, 2048, 1)
    assert tc.expert_scoring == "sigmoid" and tc.expert_scale == 2.827
    assert tc.rope_softmax_mscale == pytest.approx(1.4159 ** 2, rel=1e-4)
    assert cf["engine"] == {
        "paged": True, "max_slots": 12, "max_len": 43008, "block_size": 16,
        "num_blocks": 32768, "prefill_chunk": 128, "stream_batch": 1}


def test_the_cell_resolves_and_names_its_share(cell):
    man = manifest.load_manifest()
    for what, path in manifest.cell_paths(man, CELL).items():
        assert os.path.isfile(path), (what, path)
    assert cell["kind"] == "serve_state_family" and cell["chips"] == 1
    name = cell["config_file"]["reference"]
    assert name == "latent_moe_decoder"
    assert os.path.isfile(manifest.reference_path(name))
    assert os.path.isfile(serve_family_replica.family_path(name))
    names = {m["name"] for m in manifest.metrics_of(man, "per_layer", CELL)}
    # its own six, and the six that list no cells
    assert names >= set(READERS) | {
        "handle_ttft_overhead_ms", "engine_step_ms", "slot_occupancy_pct",
        "decode_step_device_ms", "device_idle_pct.serve",
        "step_lookahead_pct"}
    assert not names & {"decode_step_roofline", "moe_experts_roofline",
                        "hybrid_step_roofline", "sparse_keys_read_pct"}
    e2e = {m["name"] for m in manifest.metrics_of(man, "end_to_end", CELL)}
    assert e2e >= {"setup_s", "ttft_p90_ms", "tpot_p95_ms"}
    other = manifest.load_cell(man, "keye-vl2-30b-a3b.longdoc-sessions")
    assert set(cell["limits"]) == set(other["limits"]) \
        - {"self_agreement_missed_prefix"}
    share = cell["rate_rps"] / cell["knee_rps"]
    assert 0.73 <= share <= 0.90
    assert f"{share:.2f} of its knee" in cell["why"]
    assert "1/32" in cell["why"] and len(cell["why"]) <= 200
    # the traffic as the issue gives it, inside max_len and ref_len
    mix, eng = cell["traffic_file"], cell["config_file"]["engine"]
    assert (mix["generator"], mix["tenants"], mix["shared_prefix_tokens"]) \
        == ("document_sessions", 6, 32768)
    longest = mix["shared_prefix_tokens"] + mix["history_tokens"]["max"] \
        + mix["turn_tokens"]["max"]
    assert longest + mix["output_tokens"]["max"] <= eng["max_len"]
    assert longest + cell["check"]["new_tokens"] <= cell["check"]["ref_len"]
    # every context passes YaRN's original length eight times over
    assert mix["shared_prefix_tokens"] >= 8 * cell["config_file"][
        "rope_scaling"]["original_max_position_embeddings"]
    assert cell["pre_roll"]["seconds"] == 15


def test_device_bytes_are_the_shapes(cell, family):
    cf = cell["config_file"]
    part = family.layer_params(cf)
    d = 7168
    assert part["mla_q_proj"] == d * 1536 + 1536 + 1536 * 64 * 192 \
        + 64 * 128 * 512
    assert part["mla_kv_proj"] == d * 576 + 512
    assert part["mla_out_proj"] == 64 * 512 * 128 + 64 * 128 * d
    mla = sum(part[s] for s in family.PROJECTIONS)
    assert mla == 101_124_096                   # the issue's 101.12 M
    assert part["expert"] == part["shared"] == 3 * d * 2048 == 44_040_192
    assert part["dense_mlp"] == 3 * d * 18432
    assert part["router"] == d * 384 + 384
    total = (5 * (mla + 2 * d) + part["dense_mlp"]
             + 4 * (12 * part["expert"] + part["shared"] + part["router"])
             + 2 * 20480 * d + d)
    got = family.device_bytes(cf)
    assert got["parameters"] == total == cf["device_bytes"]["parameters"]
    assert got["weights"] == 2 * total == cf["device_bytes"]["weights"]
    assert abs(got["weights"] / 6.99e9 - 1) < 0.01
    # the pool: 576 values a token a layer, held in rows of whole lanes
    tokens = 32768 * 16
    assert got["kv_per_token_content"] == 5 * 576 * 2 == 5760
    assert got["kv_pool_content"] == 5760 * tokens
    assert abs(got["kv_pool_content"] / 3.02e9 - 1) < 0.01
    assert got["kv_per_token"] == 5 * 640 * 2 and family.pool_lanes(576) == 640
    assert got["kv_pool"] == 6400 * tokens
    for key, value in got.items():
        assert cf["device_bytes"][key] == value, key
    # the program sizes its tree and its pool the same way
    from ray_tpu.ops.latent_attention import pool_width
    tc = family.transformer_config(cf)
    assert tc.num_params() == total
    assert pool_width(tc.latent_width) == 640
    # 25 % of the chip (the floor for a new cell) is well under it
    assert got["weights"] + got["kv_pool"] > 0.6 * 16e9


def test_step_needs_on_hand_counted_rows(cell, family):
    cf = cell["config_file"]
    # two decode rows at 33,000 and 40,000 cached tokens and a 128-token
    # chunk row from 34,000; the step's routers sent 9 pairs to 3 held experts
    rows = [(33000, 1, 1), (40000, 1, 1), (34000, 128, 0)]
    counters = {"moe_pairs_held": 9, "moe_experts_hit": 3}
    needs = family.step_needs(cf, rows, counters)
    assert (needs["fed"], needs["sampled"]) == (130, 2)
    part = family.layer_params(cf)
    d, fed = 7168, 130
    read = 33001 + 40001 + 34128
    causal = 33001 + 40001 + sum(range(34001, 34129))
    assert needs["mla_attention"] == {
        "flops": 5 * 64 * (2 * 576 + 2 * 512) * causal,
        "bytes": 5 * 2 * 576 * (read + fed)}
    assert needs["mla_q_proj"] == {
        "flops": 5 * 2 * part["mla_q_proj"] * fed,
        "bytes": 5 * (2 * part["mla_q_proj"] + 2 * (d + 64 * 576) * fed)}
    assert needs["mla_kv_proj"]["bytes"] == 5 * (
        2 * part["mla_kv_proj"] + 2 * (d + 576) * fed)
    assert needs["mla_out_proj"]["flops"] == 5 * 2 * part["mla_out_proj"] * fed
    assert needs["mla_projections"] == {
        k: sum(needs[s][k] for s in family.PROJECTIONS)
        for k in ("flops", "bytes")}
    assert needs["moe_experts"] == {
        "flops": 2 * part["expert"] * 9,
        "bytes": 2 * part["expert"] * 3 + 2 * 2 * d * 9}
    assert needs["shared_expert"] == {
        "flops": 4 * 2 * part["shared"] * fed,
        "bytes": 4 * (2 * part["shared"] + 2 * 2 * d * fed)}
    other = part["dense_mlp"] + 4 * part["router"] + 5 * part["norms"] + d
    head = d * 20480
    scopes = [needs[s] for s in ("mla_attention", "mla_projections",
                                 "moe_experts", "shared_expert")]
    assert needs["step"]["flops"] == sum(s["flops"] for s in scopes) \
        + 2 * other * fed + 2 * head * 2
    assert needs["step"]["bytes"] == sum(s["bytes"] for s in scopes) \
        + 2 * other + 2 * d * fed + 2 * head + 4 * 20480 * 2
    # every held weight once: with all 48 held experts hit, the parts add up
    # to the model less the embedding's rows
    whole = 5 * sum(part[s] for s in family.PROJECTIONS) + other \
        + 4 * part["shared"] + 48 * part["expert"] + head
    assert whole == cf["device_bytes"]["parameters"] - 20480 * d
    # the chunk row's attention is compute-bound, a decode row's
    # bandwidth-bound (the cell's why)
    chunk = family.step_needs(cf, [(34000, 128, 0)], {})["mla_attention"]
    decode = family.step_needs(cf, [(34000, 1, 1)], {})["mla_attention"]
    assert chunk["flops"] / 197e12 > 10 * chunk["bytes"] / 819e9
    assert decode["flops"] / 197e12 < decode["bytes"] / 819e9
    # twelve decoding rows at 37k: weights once, 12 x 213 MB of latents
    steady = family.step_needs(cf, [(37000, 1, 1)] * 12,
                               {"moe_pairs_held": 12, "moe_experts_hit": 10})
    assert steady["mla_attention"]["bytes"] == pytest.approx(2.56e9, rel=0.01)
    assert steady["step"]["flops"] / 197e12 < steady["step"]["bytes"] / 819e9


def test_scopes_come_from_the_family_file(family):
    assert family.SCOPES == ("mla_attention", "mla_q_proj", "mla_kv_proj",
                             "mla_out_proj", "moe_router", "moe_experts",
                             "shared_expert")
    assert set(family.STEP_COUNTERS) >= {
        "moe_pairs_routed", "moe_pairs_held", "moe_experts_hit",
        "latent_tokens_read"}
    text = '''
  %fusion.7 = f32[64,43008]{1,0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(_raw_step_paged)/jit(main)/while/body/mla_attention/while/body/cond/branch_1_fun/dot_general"}
  %fusion.8 = bf16[1,256,64,576]{3,2,1,0} fusion(%p), kind=kOutput, calls=%f, metadata={op_name="jit(s)/while/body/cond/branch_0_fun/mla_q_proj/dot_general"}
  %ragged-dot-custom.3 = bf16[2048,2048]{1,0} custom-call(%a, %b), custom_call_target="x"
  %ragged-dot.5 = bf16[2048,2048]{1,0} fusion(%a), kind=kCustom, calls=%r, metadata={op_name="jit(s)/while/body/cond/branch_0_fun/ragged_dot"}
  %fusion.9 = bf16[1,256,7168]{2,1,0} fusion(%x), kind=kOutput, calls=%g, metadata={op_name="jit(s)/while/body/cond/branch_0_fun/shared_expert/dot_general"}
  %fusion.10 = f32[256,384]{1,0} fusion(%x), kind=kOutput, calls=%g, metadata={op_name="jit(s)/while/body/cond/branch_0_fun/moe_router/dot_general"}
  %fusion.11 = bf16[32,4096,1280]{2,1,0} fusion(%x), kind=kLoop, calls=%g, metadata={op_name="jit(s)/shared_kv_attention/dot_general"}
'''
    by_name = replica.scopes_of_instructions(text, family.SCOPES,
                                             family.KERNELS)
    assert by_name == {"fusion.7": "mla_attention", "fusion.8": "mla_q_proj",
                       "ragged-dot.5": "moe_experts",
                       "fusion.9": "shared_expert", "fusion.10": "moe_router"}


def test_readers_over_a_synthetic_run(cell, family):
    cf = cell["config_file"]
    rows = [(37000, 1, 1)] * 8 + [(34000, 128, 0)]
    counters = {"moe_expert_tokens_sum": 16, "moe_expert_tokens_max": 6,
                "moe_experts_hit": 11, "moe_pairs_routed": 136 * 8 * 4,
                "moe_pairs_held": 16, "latent_tokens_read": 8 * 37001 + 34128}
    steps = [(float(i), i + 0.9, rows) for i in range(4)]
    stats0 = {k: 0 for k in counters}
    stats1 = {k: 4 * v for k, v in counters.items()}
    needs = family.step_needs(cf, rows, counters)
    flops, byts = 197e12, 819e9
    trace = {"n_devices": 1, "window_monotonic": [0.5, 3.5],
             "window_s": 3.0, "busy_s": 2.9, "program_runs_ms": [70.0, 70.0],
             "device_ops": [], "idle_gaps": [],
             "scope_s": {"mla_attention": 80e-3, "mla_q_proj": 6e-3,
                         "mla_kv_proj": 1e-3, "mla_out_proj": 5e-3,
                         "moe_router": 1e-3, "moe_experts": 4e-3,
                         "shared_expert": 3e-3}}
    outcome = {"replica": {"steps": steps, "step_counters": [counters] * 4,
                           "max_slots": 12, "engine_ttft": {}},
               "trace": trace, "config_file": cf, "cell": cell,
               "facts": {"kind": "TPU v5 lite"}, "window": (0.0, 4.0),
               "clients": [],
               "marks": {"start": {"stats": stats0}, "end": {"stats": stats1}}}
    assert len(family_rooflines.traced_steps(outcome)) == 2
    read = lambda name: manifest.load_module(
        manifest.layer_metric_path(name)).read(outcome)
    least = lambda need: max(need["flops"] / flops, need["bytes"] / byts)
    assert read("held_expert_pairs_pct") == pytest.approx(
        100 * 16 / (136 * 8 * 4))
    took = {"mla_attention": 80e-3, "mla_projections": 12e-3,
            "moe_experts": 4e-3, "shared_expert": 3e-3}
    for name, scope in ROOFLINES.items():
        assert read(name) == pytest.approx(
            100 * 2 * least(needs[scope]) / took[scope])
        assert 0 < read(name) < 100
    # the chunk row makes this step's attention compute-bound
    assert least(needs["mla_attention"]) \
        == needs["mla_attention"]["flops"] / flops
    assert read("latent_moe_step_roofline") == pytest.approx(
        100 * least(needs["step"]) / 70e-3)
    assert 0 < read("latent_moe_step_roofline") < 100
    # a program without the counters or the scopes (the parent commit's):
    # nothing to read, no raise
    bare = {**outcome, "trace": {k: v for k, v in trace.items()
                                 if k != "scope_s"},
            "replica": {k: v for k, v in outcome["replica"].items()
                        if k != "step_counters"},
            "marks": {"start": {"stats": {}}, "end": {"stats": {}}}}
    for name in READERS:
        assert manifest.load_module(
            manifest.layer_metric_path(name)).read(bare) is None
    line = run.result_line(manifest.load_manifest(), CELL, 1, {
        "correct": True, "attempted": 1, "failed": 0, "trace": trace,
        "facts": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                  "memory_peak_bytes": 1}, "run": outcome})
    assert set(line["metrics"]) >= set(READERS) | {
        "decode_step_device_ms", "device_idle_pct.serve"}


def test_rehearsal_cell_crosses_the_toy_window(cell, family):
    toy = serve_state_family.rehearsal_cell(cell)
    cf, mix = toy["config_file"], toy["traffic_file"]
    assert cf["hidden_size"] == 64 and cf["num_hidden_layers"] == 3
    assert family.share(cf) == {"published": 16, "held": 4, "first": 4}
    # the toy contexts pass the toy's original length as the cell's pass 4096
    assert mix["shared_prefix_tokens"] >= 8 * cf["rope_scaling"][
        "original_max_position_embeddings"]
    assert toy["pre_roll"]["seconds"] == serve_state_family.REHEARSE_PRE_ROLL_S
    tc = family.transformer_config(cf)
    assert (tc.num_experts, tc.held_experts, tc.experts_first) == (16, 4, 4)
    assert tc.dense_layers == 1 and tc.rope_factor == 16.0
    json.dumps(toy)      # plain data: it is sent to the replica


def test_the_schedules_are_chosen_by_the_mixes_rule(cell):
    """``traffic_seed`` of the window's schedule and of the pre-roll's: the
    first of 1, 2, 3, ... that offers rate x span requests to within 2.5 and
    the mix's expected prompt and output tokens to within 5 %."""
    import numpy as np

    from benchmark import traffic
    from benchmark.generators import sessions

    mix, rate = cell["traffic_file"], cell["rate_rps"]
    rng = np.random.default_rng(0)
    prompt = (traffic.draw_lengths(mix["history_tokens"], 100000, rng)
              + traffic.draw_lengths(mix["turn_tokens"], 100000, rng)).mean()
    out = traffic.draw_lengths(mix["output_tokens"], 100000, rng).mean()

    def offers(seed, span):
        s = sessions.schedule({**mix, "traffic_seed": seed}, rate, span)
        want = rate * span
        got = s["history_tokens"].sum() + s["turn_tokens"].sum()
        return (abs(len(s["due_s"]) - want) <= 2.5
                and abs(got / (want * prompt) - 1) <= 0.05
                and abs(s["output_tokens"].sum() / (want * out) - 1) <= 0.05)

    first = lambda span: next(s for s in range(1, 1000) if offers(s, span))
    assert first(51) == mix["traffic_seed"]
    assert first(cell["pre_roll"]["seconds"]) \
        == cell["pre_roll"]["traffic_seed"]
    vocab = cell["config_file"]["vocab_size"]
    before = serve_state_family.pre_roll_requests(cell, rate, 7, vocab)
    assert all(-15 <= r.due_s < 0 for r in before)
    window = traffic.generate(mix, rate, 51, 7, vocab)
    assert all(32768 + 128 + 32 <= len(r.prompt) <= 32768 + 8192 + 256
               for r in before + window)
    assert all(max(r.prompt) < vocab for r in window[:3])


def test_both_draws_of_a_run_ask_of_the_documents_set_up_served(cell):
    """``document_sessions``: the pre-roll's draw (the kind gives it ``seed +
    1_000_003``) and the window's ask of the SAME six documents, the ones
    ``warm_prompts(mix, seed)`` put into the trie; what a draw makes its own
    is the unshared part. Under ``sessions`` the pre-roll's requests were
    cold prefills of 33k-41k tokens (PERF.md section 6, PR 37). Schedule,
    lengths and unshared tokens are ``sessions``'s own."""
    from benchmark import traffic
    from benchmark.generators import document_sessions, sessions

    mix, vocab = cell["traffic_file"], cell["config_file"]["vocab_size"]
    for seed in (7, 3700000001):
        docs = {tuple(p[:-1]) for p in traffic.warm_prompts(mix, seed, vocab)}
        assert len(docs) == 6
        window = traffic.generate(mix, 0.75, 51, seed, vocab)
        before = serve_state_family.pre_roll_requests(cell, 0.75, seed, vocab)
        for r in window + before:
            assert r.shared_tokens == 32768
            assert tuple(r.prompt[:32768]) in docs
        assert {tuple(r.prompt[32768:32800]) for r in window}.isdisjoint(
            tuple(r.prompt[32768:32800]) for r in before)
        plain = sessions.generate(mix, 0.75, 51, seed, vocab)
        assert [(r.due_s, r.tenant, r.max_new, r.prompt[32768:])
                for r in plain] == [(r.due_s, r.tenant, r.max_new,
                                     r.prompt[32768:]) for r in window]
    # another --seed, and another rate of a sweep (seed + i): other documents
    assert traffic.warm_prompts(mix, 7, vocab)[0][:16] \
        != traffic.warm_prompts(mix, 8, vocab)[0][:16]
    assert document_sessions.schedule is sessions.schedule
