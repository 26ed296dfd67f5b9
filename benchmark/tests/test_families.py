"""The ``serve_family`` kind's own pieces, CPU only, no ray_tpu runtime: the
family file's ``step_needs`` on hand-counted rows, the configuration's
published widths and bytes, what ``test_manifest`` asks of a cell for the
new kind, the scope map of a compiled program, and the readers over a
synthetic run."""

import json
import os

import pytest

from benchmark import family_rooflines, manifest, run
from benchmark.kinds import serve_family, serve_family_replica

CELL = "keye-vl2-30b-a3b.longdoc-sessions"
CONFIG = "keye-vl2-30b-a3b-l6-serve"


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(manifest.load_manifest(), CELL)


@pytest.fixture(scope="module")
def family(cell):
    return serve_family_replica.load_family(cell["config_file"])


def test_the_configuration_keeps_the_catalog_row(cell):
    cf = cell["config_file"]
    published = dict(
        hidden_size=2048, intermediate_size=6144, moe_intermediate_size=768,
        num_attention_heads=32, num_key_value_heads=4, head_dim=128,
        num_experts=128, num_local_experts=128, num_experts_per_tok=8,
        norm_topk_prob=True, vocab_size=151936, rope_theta=10000000,
        rms_norm_eps=1e-6, max_position_embeddings=262144,
        decoder_sparse_step=1, mlp_only_layers=[], attention_bias=False,
        tie_word_embeddings=False, use_sliding_window=False,
        sliding_window=None, max_window_layers=48, hidden_act="silu",
        model_type="KeyeVL2",
        sa_config={"indexer_head_dim": 64, "indexer_num_heads": 16,
                   "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                   "q_chunk_size": 512, "topk": 2048},
        rope_scaling={"mrope_section": [16, 24, 24], "rope_type": "default",
                      "type": "default"})
    for key, value in published.items():
        assert cf[key] == value, key
    assert list(cf["reduced"]) == ["num_hidden_layers"]
    assert cf["reduced"]["num_hidden_layers"]["published"] == 48
    assert cf["num_hidden_layers"] == 6
    entry = next(c for c in manifest.load_manifest()["configs"]
                 if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == cf["source"]


def test_the_cell_resolves_like_every_other(cell):
    """What ``test_manifest.test_every_name_resolves_to_a_file`` asks of a
    cell, for the new kind and its family's files."""
    man = manifest.load_manifest()
    for what, path in manifest.cell_paths(man, CELL).items():
        assert os.path.isfile(path), (what, path)
    assert cell["kind"] == "serve_family" and cell["chips"] == 1
    assert os.path.isfile(manifest.kind_path(cell["kind"]))
    name = cell["config_file"]["reference"]
    assert os.path.isfile(manifest.reference_path(name))
    assert os.path.isfile(serve_family_replica.family_path(name))
    assert os.path.isfile(manifest.generator_path(
        cell["traffic_file"]["generator"]))
    assert set(cell["limits"]) >= {"logit_rel_err_pooled", "tie_gap_max",
                                   "failed_requests"}
    e2e = [m["name"] for m in manifest.metrics_of(man, "end_to_end", CELL)]
    assert "setup_s" in e2e and len(e2e) >= 2
    names = {m["name"] for m in manifest.metrics_of(man, "per_layer", CELL)}
    assert names >= {"sparse_keys_read_pct", "expert_load_max_over_mean",
                     "moe_experts_roofline", "indexer_roofline",
                     "sparse_attention_roofline", "sparse_moe_step_roofline",
                     "engine_step_ms", "decode_step_device_ms",
                     "device_idle_pct.serve"}
    # the dense count is wrong here and lists the dense cells now
    assert "decode_step_roofline" not in names
    assert "prefix_hit_token_pct" not in names
    for other in ("mistral-7b.chat-steady", "qwen2-7b.agent-prefix"):
        had = {m["name"] for m in manifest.metrics_of(man, "per_layer", other)}
        assert "decode_step_roofline" in had and not had & {
            "sparse_keys_read_pct", "moe_experts_roofline"}
    # the traffic as the issue gives it, and a window that fits ref_len
    mix = cell["traffic_file"]
    assert (mix["tenants"], mix["shared_prefix_tokens"]) == (6, 20480)
    longest = 20480 + mix["history_tokens"]["max"] + mix["turn_tokens"]["max"]
    assert longest + cell["check"]["new_tokens"] <= cell["check"]["ref_len"]
    assert longest + mix["output_tokens"]["max"] \
        <= cell["config_file"]["engine"]["max_len"]


def test_device_bytes_are_the_shapes(cell, family):
    cf = cell["config_file"]
    part = family.layer_params(cf)
    assert part == {"attention": 18874368, "indexer": 2260992,
                    "router": 262144, "expert": 4718592}
    d, L = cf["hidden_size"], cf["num_hidden_layers"]
    gains = 2 * d + 2 * 128 + 2 * 64      # two norms, q/k-norm, kI's norm
    layer = (part["attention"] + part["indexer"] + part["router"]
             + 128 * part["expert"] + gains)
    total = L * layer + 2 * cf["vocab_size"] * d + d
    assert cf["device_bytes"]["weights"] == 2 * total == 8749244928
    per_token = L * (2 * 4 * 128 * 2 + 64 * 2)
    assert cf["device_bytes"]["kv_per_token"] == per_token == 13056
    eng = cf["engine"]
    assert cf["device_bytes"]["kv_pool"] \
        == eng["num_blocks"] * eng["block_size"] * per_token
    assert cf["device_bytes"]["active_parameters_per_token_per_layer"] \
        == part["attention"] + part["indexer"] + part["router"] \
        + 8 * part["expert"]


def test_step_needs_on_hand_counted_rows(cell, family):
    cf = cell["config_file"]
    # a decode row at 25,000 cached tokens, a decode row at 1000 (under
    # topk: dense), and a 128-token chunk row from 20,480
    rows = [(25000, 1, 1), (1000, 1, 1), (20480, 128, 0)]
    counters = {"moe_expert_tokens_sum": 6 * 8 * 130,
                "moe_experts_hit": 6 * 120}
    needs = family.step_needs(cf, rows, counters)
    assert (needs["fed"], needs["sampled"]) == (130, 2)
    expert = 3 * 2048 * 768
    assert needs["moe_experts"] == {
        "flops": 2 * expert * 6 * 8 * 130,
        "bytes": 2 * expert * 6 * 120 + 2 * 2 * 2048 * 6 * 8 * 130}
    # indexer: rows past topk score every causal pair on 16 heads x 64
    pairs = 25001 + sum(range(20481, 20609))
    live = 25001 + 20608
    assert needs["dsa_indexer"] == {
        "flops": 6 * (2 * 2260992 * 130 + 2 * 16 * 64 * pairs),
        "bytes": 6 * (2 * 2260992 + 2 * 64 * (live + 130))}
    # sparse attention: the decode row reads 2048 keys, the chunk row its
    # live keys once; every query multiplies 2048 keys
    assert needs["paged_sparse_attention"] == {
        "flops": 6 * 4 * 32 * 128 * (2048 * 129),
        "bytes": 6 * 2048 * (2048 + 20608)}
    head = 2048 * 151936
    rest = 18874368 + 262144
    assert needs["step"]["flops"] == (
        needs["moe_experts"]["flops"] + needs["dsa_indexer"]["flops"]
        + needs["paged_sparse_attention"]["flops"]
        + 6 * (2 * rest * 130 + 4 * 32 * 128 * 1001) + 2 * head * 2)
    assert needs["step"]["bytes"] == (
        needs["moe_experts"]["bytes"] + needs["dsa_indexer"]["bytes"]
        + needs["paged_sparse_attention"]["bytes"]
        + 6 * (2 * rest + 2048 * (1001 + 130)) + 2 * head
        + 2 * 2048 * 130 + 4 * 151936 * 2)


def test_scopes_of_instructions_reads_the_compiled_text():
    text = '''
  %fusion.7 = bf16[8,128]{1,0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(_raw_step_paged)/jit(main)/while/body/moe_experts/mul" source_file="x.py"}
  ROOT %custom-call.3 = f32[8192,768]{1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(s)/while/body/moe_experts/ragged_dot"}
  %sort.1 = (f32[8,32768]{1,0}) sort(%x), dimensions={1}, metadata={op_name="jit(s)/while/body/cond/branch_1_fun/dsa_indexer/top_k"}
  %copy.9 = bf16[6,14336]{1,0} copy(%y), metadata={op_name="jit(s)/while/body/dynamic_update_slice"}
  %ragged-dot-none.2 = f32[8192,2048]{1,0} custom-call(%a, %w), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %no_metadata = s32[] add(%a, %b)
'''
    assert serve_family_replica.scopes_of_instructions(text) == {
        "fusion.7": "moe_experts", "custom-call.3": "moe_experts",
        "sort.1": "dsa_indexer", "ragged-dot-none.2": "moe_experts"}
    events = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [
            ["%while.1 = (s32[]) while(...)", 0.0, 1000.0],
            ["%fusion.7 = bf16[8,128]{1,0} fusion(%p)", 100.0, 300.0],
            ["%sort.1 = (f32[8,32768]{1,0}) sort(%x)", 500.0, 200.0],
            ["%copy.9 = bf16[6,14336]{1,0} copy(%y)", 800.0, 100.0]]}]}]}
    got = serve_family_replica.scope_seconds(
        events, {"fusion.7": "moe_experts", "sort.1": "dsa_indexer"},
        (0.0, 600.0))
    assert got["moe_experts"] == pytest.approx(300e-9)
    assert got["dsa_indexer"] == pytest.approx(200e-9)   # whole event kept
    assert got["paged_attention"] == 0.0


def test_readers_over_a_synthetic_run(cell, family):
    cf = cell["config_file"]
    rows = [(25000, 1, 1)] * 8
    counters = {"moe_expert_tokens_sum": 6 * 64, "moe_experts_hit": 6 * 50,
                "moe_expert_tokens_max": 6 * 3, "attn_keys_selected": 8 * 2048,
                "attn_keys_live": 8 * 25001}
    steps = [(float(i), i + 0.9, rows) for i in range(4)]
    stats0 = {k: 0 for k in counters}
    stats1 = {k: 4 * v for k, v in counters.items()}
    needs = family.step_needs(cf, rows, counters)
    peak = 819e9
    trace = {"n_devices": 1, "window_monotonic": [0.5, 3.5],
             "window_s": 3.0, "busy_s": 2.0, "program_runs_ms": [20.0, 20.0],
             "device_ops": [], "idle_gaps": [],
             "scope_s": {"moe_experts": 4e-3, "dsa_indexer": 2e-3,
                         "paged_sparse_attention": 1e-3}}
    outcome = {"replica": {"steps": steps, "step_counters": [counters] * 4,
                           "max_slots": 8, "engine_ttft": {}},
               "trace": trace, "config_file": cf, "cell": cell,
               "facts": {"kind": "TPU v5 lite"}, "window": (0.0, 4.0),
               "clients": [],
               "marks": {"start": {"stats": stats0}, "end": {"stats": stats1}}}
    assert len(family_rooflines.traced_steps(outcome)) == 2   # steps 1, 2
    read = lambda name: manifest.load_module(
        manifest.layer_metric_path(name)).read(outcome)
    assert read("sparse_keys_read_pct") == pytest.approx(
        100 * 2048 / 25001)
    assert read("expert_load_max_over_mean") == pytest.approx(3 * 128 / 64)
    assert read("moe_experts_roofline") == pytest.approx(
        100 * 2 * needs["moe_experts"]["bytes"] / peak / 4e-3)
    assert read("sparse_attention_roofline") == pytest.approx(
        100 * 2 * needs["paged_sparse_attention"]["bytes"] / peak / 1e-3)
    assert 0 < read("indexer_roofline") < 100
    assert read("sparse_moe_step_roofline") == pytest.approx(
        100 * needs["step"]["bytes"] / peak / 20e-3)
    # a program without the counters or the scopes: nothing to read, no raise
    bare = {**outcome, "trace": {k: v for k, v in trace.items()
                                 if k != "scope_s"},
            "replica": {k: v for k, v in outcome["replica"].items()
                        if k != "step_counters"},
            "marks": {"start": {"stats": {}}, "end": {"stats": {}}}}
    for name in ("sparse_keys_read_pct", "expert_load_max_over_mean",
                 "moe_experts_roofline", "indexer_roofline",
                 "sparse_attention_roofline", "sparse_moe_step_roofline"):
        assert manifest.load_module(
            manifest.layer_metric_path(name)).read(bare) is None
    line = run.result_line(manifest.load_manifest(), CELL, 1, {
        "correct": True, "attempted": 1, "failed": 0, "trace": trace,
        "facts": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                  "memory_peak_bytes": 1}, "run": outcome})
    assert set(line["metrics"]) >= {
        "sparse_keys_read_pct", "moe_experts_roofline", "indexer_roofline",
        "sparse_attention_roofline", "sparse_moe_step_roofline",
        "decode_step_device_ms", "device_idle_pct.serve"}


def test_rehearsal_cell_runs_past_the_toy_topk(cell):
    toy = serve_family.rehearsal_cell(cell)
    cf, mix = toy["config_file"], toy["traffic_file"]
    assert cf["hidden_size"] == 64 and cf["num_experts"] == 8
    assert mix["shared_prefix_tokens"] > cf["sa_config"]["topk"]
    assert mix["shared_prefix_tokens"] + 64 + 2 + 2 \
        <= cf["engine"]["max_len"]
    json.dumps(toy)      # plain data: it is sent to the replica
