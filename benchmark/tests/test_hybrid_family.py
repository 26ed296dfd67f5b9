"""The ``serve_state_family`` kind's own pieces and the hybrid state-space /
attention family's file, CPU only, no ray_tpu runtime: the configuration
against the catalog row, its bytes against the shapes, ``step_needs`` on
hand-counted rows, the scope map with the FAMILY's lists, and the five new
readers over a synthetic run."""

import json
import os

import pytest

from benchmark import family_rooflines, manifest, run
from benchmark.kinds import (serve_family, serve_family_replica,
                             serve_state_family)
from benchmark.kinds import serve_state_family_replica as replica

CELL = "phi4-mini-flash.reason-longgen"
CONFIG = "phi4-mini-flash-serve"
READERS = ("ssm_scan_roofline", "shared_kv_attention_roofline",
           "window_attention_roofline", "hybrid_step_roofline",
           "window_kv_held_pct")


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(manifest.load_manifest(), CELL)


@pytest.fixture(scope="module")
def family(cell):
    return replica.load_family(cell["config_file"])


def test_the_configuration_keeps_the_catalog_row_whole(cell):
    cf = cell["config_file"]
    row = {"embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
           "intermediate_size": 10240, "layer_norm_eps": 1e-05,
           "max_position_embeddings": 262144, "mb_per_layer": 2,
           "model_type": "phi4flash", "num_attention_heads": 40,
           "num_hidden_layers": 32, "num_key_value_heads": 20,
           "resid_pdrop": 0, "sliding_window": 512,
           "tie_word_embeddings": True, "mlp_bias": False,
           "lm_head_bias": False, "vocab_size": 200064}
    for key, value in row.items():
        assert cf[key] == value, key
    assert cf["reduced"] == {}
    entry = next(c for c in manifest.load_manifest()["configs"]
                 if c["name"] == CONFIG)
    assert entry["reduced"] == [] and entry["source"] == cf["source"]
    assert set(cf["assumed"]) >= {
        "mamba", "layer_layout", "memory", "positions", "projection_biases",
        "differential_attention", "weights", "engine"}
    assert cf["assumed"]["mamba"]["dt_rank"] == -(-2560 // 16)


def test_the_cell_resolves_and_names_its_share(cell):
    man = manifest.load_manifest()
    for what, path in manifest.cell_paths(man, CELL).items():
        assert os.path.isfile(path), (what, path)
    assert cell["kind"] == "serve_state_family" and cell["chips"] == 1
    assert os.path.isfile(manifest.kind_path(cell["kind"]))
    name = cell["config_file"]["reference"]
    assert os.path.isfile(manifest.reference_path(name))
    assert os.path.isfile(serve_family_replica.family_path(name))
    names = {m["name"] for m in manifest.metrics_of(man, "per_layer", CELL)}
    assert names >= set(READERS) | {"engine_step_ms", "slot_occupancy_pct",
                                    "decode_step_device_ms",
                                    "device_idle_pct.serve",
                                    "handle_ttft_overhead_ms"}
    assert not names & {"decode_step_roofline", "prefix_hit_token_pct",
                        "sparse_keys_read_pct"}
    # all of cell 3's limits but the one this layout cannot meet by design
    other = manifest.load_cell(man, "keye-vl2-30b-a3b.longdoc-sessions")
    assert set(cell["limits"]) == set(other["limits"]) \
        - {"self_agreement_missed_prefix"}
    share = cell["rate_rps"] / cell["knee_rps"]
    assert 0.73 <= share <= 0.90
    assert f"{share:.2f} of its knee" in cell["why"]
    # the traffic as the issue gives it, inside max_len and ref_len
    mix, eng = cell["traffic_file"], cell["config_file"]["engine"]
    assert (mix["tenants"], mix["shared_prefix_tokens"]) == (0, 0)
    assert mix["turn_tokens"]["max"] + mix["output_tokens"]["max"] \
        <= eng["max_len"]
    assert mix["turn_tokens"]["max"] + cell["check"]["new_tokens"] \
        <= cell["check"]["ref_len"]
    assert cell["check"]["new_tokens"] > \
        cell["config_file"]["sliding_window"]


def test_device_bytes_are_the_shapes(cell, family):
    cf = cell["config_file"]
    part = family.layer_params(cf)
    assert part["mlp"] == 3 * 2560 * 10240 + 4 * 2560
    assert part["mamba_proj"] + part["ssm"] == 41_241_600
    assert part["attn_own"] == 19_668_864 and part["attn_cross"] == 13_112_704
    assert part["gmu"] == 26_214_400
    total = (32 * part["mlp"] + 9 * (part["mamba_proj"] + part["ssm"])
             + 9 * part["attn_own"] + 7 * part["attn_cross"]
             + 7 * part["gmu"] + 200064 * 2560 + 2 * 2560)
    assert cf["device_bytes"]["parameters"] == total == 3_852_562_944
    assert cf["device_bytes"]["weights"] == 2 * total
    eng = cf["engine"]
    full = eng["num_blocks"] * eng["block_size"] * 5120
    window = 8 * 32 * 35 * eng["block_size"] * 5120
    state = 32 * 9 * (16 + 3) * 5120 * 4
    assert cf["device_bytes"]["kv_pool"] == full + window + state
    assert eng["num_blocks"] == eng["max_slots"] * eng["max_len"] // 16
    # the program sizes its pools the same way
    from ray_tpu.models.hybrid import window_table_width
    assert window_table_width(512, eng["prefill_chunk"],
                              eng["block_size"]) == 35
    tc = family.transformer_config(cf)
    assert tc.num_params() == total and tc.hybrid_periods == (8, 7)
    assert tc.layer_kinds.count("mamba") == 9


def test_step_needs_on_hand_counted_rows(cell, family):
    cf = cell["config_file"]
    # a decode row at 2000 cached tokens (window slid off), one at 100
    # (inside the window), and a 32-token chunk row from 300
    rows = [(2000, 1, 1), (100, 1, 1), (300, 32, 0)]
    needs = family.step_needs(cf, rows, {})
    assert (needs["fed"], needs["sampled"]) == (34, 2)
    di, n, k, r = 5120, 16, 4, 160
    ssm_w = k * di + di + di * (r + 2 * n) + r * di + di + n * di + di
    assert needs["ssm_scan"] == {
        "flops": 9 * 34 * (2 * k * di + 2 * di * (r + 2 * n) + 2 * r * di
                           + 7 * n * di + 2 * di),
        "bytes": 9 * (2 * ssm_w + 2 * 4 * (n + k - 1) * di * 3
                      + 2 * 2 * di * 34)}
    assert needs["gmu"] == {
        "flops": 7 * 2 * 2 * 2560 * 5120 * 34,
        "bytes": 7 * (2 * 2 * 2560 * 5120 + 2 * (5120 + 2 * 2560) * 34)}
    # window layers: the decode row past the window reads 512 keys, the one
    # inside it its 101, the chunk row every key from 0 (300 - 511 < 0)
    win_keys = 512 + 101 + 332
    win_pairs = 512 + 101 + sum(range(301, 333))
    pair = 6 * 64 * 40
    assert needs["window_attention"] == {
        "flops": 8 * pair * win_pairs,
        "bytes": 8 * 5120 * (win_keys + 34)}
    all_keys = 2001 + 101 + 332
    all_pairs = 2001 + 101 + sum(range(301, 333))
    assert needs["shared_kv_attention"] == {
        "flops": 8 * pair * all_pairs,
        "bytes": 5120 * (8 * all_keys + 34)}
    part = family.layer_params(cf)
    other = (32 * part["mlp"] + 9 * part["mamba_proj"]
             + 9 * part["attn_own"] + 7 * part["attn_cross"] + 2 * 2560)
    head = 2560 * 200064
    scopes = [needs[s] for s in family.SCOPES]
    assert needs["step"]["flops"] == sum(s["flops"] for s in scopes) \
        + 2 * other * 34 + 2 * head * 2
    assert needs["step"]["bytes"] == sum(s["bytes"] for s in scopes) \
        + 2 * other + 2 * 2560 * 34 + 2 * head + 4 * 200064 * 2
    # every weight is read once: the parts add up to the model
    assert other + 9 * ssm_w + 7 * part["gmu"] + head \
        == cf["device_bytes"]["parameters"]
    # a step of 32 decoding rows at 1200 keys: the weights' 7.7 GB, the
    # shared pool's 1.57 GB (eight layers), the windows' 0.67 GB, the
    # state's 0.2 GB: memory-bound
    steady = family.step_needs(cf, [(1200, 1, 1)] * 32, {})
    assert 10.0e9 < steady["step"]["bytes"] < 10.4e9
    assert steady["step"]["flops"] / 197e12 < steady["step"]["bytes"] / 819e9


def test_scopes_come_from_the_family_file(family):
    assert family.SCOPES == ("ssm_scan", "gmu", "window_attention",
                             "shared_kv_attention")
    text = '''
  %fusion.7 = f32[32,16,5120]{2,1,0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(_raw_step_paged)/jit(main)/while/body/ssm_scan/while/body/mul" source_file="x.py"}
  ROOT %gather.3 = bf16[32,560,1280]{2,1,0} gather(%a, %i), metadata={op_name="jit(s)/while/body/window_attention/gather"}
  %fusion.9 = bf16[32,4096,1280]{2,1,0} fusion(%x), kind=kLoop, calls=%g, metadata={op_name="jit(s)/shared_kv_attention/cond/branch_1_fun/while/body/dot_general"}
  %fusion.11 = bf16[1,256,2560]{2,1,0} fusion(%y), kind=kOutput, calls=%h, metadata={op_name="jit(s)/while/body/cond/branch_0_fun/gmu/dot_general"}
  %fusion.12 = bf16[1,256,10240]{2,1,0} fusion(%y), kind=kOutput, calls=%h, metadata={op_name="jit(s)/while/body/cond/branch_0_fun/moe_experts/dot_general"}
  %no_metadata = s32[] add(%a, %b)
'''
    by_name = replica.scopes_of_instructions(text, family.SCOPES,
                                             family.KERNELS)
    assert by_name == {"fusion.7": "ssm_scan", "gather.3": "window_attention",
                       "fusion.9": "shared_kv_attention", "fusion.11": "gmu"}
    events = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [
            ["%while.1 = (s32[]) while(...)", 0.0, 1000.0],
            ["%fusion.7 = f32[32,16,5120]{2,1,0} fusion(%p)", 100.0, 300.0],
            ["%gather.3 = bf16[32,560,1280]{2,1,0} gather(%a)", 500.0,
             200.0]]}]}]}
    got = replica.scope_seconds(events, by_name, (0.0, 600.0), family.SCOPES)
    assert got == {"ssm_scan": pytest.approx(300e-9), "gmu": 0.0,
                   "window_attention": pytest.approx(200e-9),
                   "shared_kv_attention": 0.0}
    # the kind deploys its own replica class through serve_family.run
    assert serve_state_family.StateFamilyLLM is replica.StateFamilyLLM
    assert issubclass(replica.StateFamilyLLM, serve_family.FamilyLLM)
    assert serve_family.FamilyLLM is not replica.StateFamilyLLM


def test_readers_over_a_synthetic_run(cell, family):
    cf = cell["config_file"]
    rows = [(1200, 1, 1)] * 32
    counters = {"window_blocks_held": 32 * 33,
                "window_blocks_full_table": 32 * 90,
                "window_blocks_released": 2, "state_slots_live": 32,
                "shared_kv_keys_read": 8 * 32 * 1201,
                "window_keys_read": 8 * 32 * 512}
    steps = [(float(i), i + 0.9, rows) for i in range(4)]
    stats0 = {k: 0 for k in counters}
    stats1 = {k: 4 * v for k, v in counters.items()}
    needs = family.step_needs(cf, rows, counters)
    peak = 819e9
    trace = {"n_devices": 1, "window_monotonic": [0.5, 3.5],
             "window_s": 3.0, "busy_s": 2.0, "program_runs_ms": [25.0, 25.0],
             "device_ops": [], "idle_gaps": [],
             "scope_s": {"ssm_scan": 1e-3, "gmu": 1e-3,
                         "window_attention": 4e-3,
                         "shared_kv_attention": 12e-3}}
    outcome = {"replica": {"steps": steps, "step_counters": [counters] * 4,
                           "max_slots": 32, "engine_ttft": {}},
               "trace": trace, "config_file": cf, "cell": cell,
               "facts": {"kind": "TPU v5 lite"}, "window": (0.0, 4.0),
               "clients": [],
               "marks": {"start": {"stats": stats0}, "end": {"stats": stats1}}}
    assert len(family_rooflines.traced_steps(outcome)) == 2
    read = lambda name: manifest.load_module(
        manifest.layer_metric_path(name)).read(outcome)
    assert read("window_kv_held_pct") == pytest.approx(100 * 33 / 90)
    for name, scope in (("ssm_scan_roofline", "ssm_scan"),
                        ("window_attention_roofline", "window_attention"),
                        ("shared_kv_attention_roofline",
                         "shared_kv_attention")):
        assert read(name) == pytest.approx(
            100 * 2 * needs[scope]["bytes"] / peak / trace["scope_s"][scope])
        assert 0 < read(name) < 100
    assert read("hybrid_step_roofline") == pytest.approx(
        100 * needs["step"]["bytes"] / peak / 25e-3)
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


def test_rehearsal_cell_crosses_the_toy_window(cell):
    toy = serve_family.rehearsal_cell(cell)
    cf, mix = toy["config_file"], toy["traffic_file"]
    assert cf["hidden_size"] == 64 and cf["num_hidden_layers"] == 10
    assert mix["output_tokens"]["max"] > cf["sliding_window"]
    assert cf["assumed"]["mamba"]["d_state"] == 16
    json.dumps(toy)      # plain data: it is sent to the replica


def test_the_pre_roll_is_a_draw_of_its_own_before_the_window(cell):
    """The requests before the window: the cell's mix at the cell's rate
    from ``pre_roll.traffic_seed``, offsets below zero, tokens that are not
    the window's; the window's schedule stays the one every cell's rule
    checks (``traffic.generate(mix, rate, 51, ...)``)."""
    from benchmark import traffic

    vocab = cell["config_file"]["vocab_size"]
    span = cell["pre_roll"]["seconds"]
    rate = cell["rate_rps"]
    before = serve_state_family.pre_roll_requests(cell, rate, 7, vocab)
    window = traffic.generate(cell["traffic_file"], rate, 51, 7, vocab)
    assert all(-span <= r.due_s < 0 for r in before)
    assert abs(len(before) - rate * span) <= 2.5      # the mix's own rule
    assert [len(r.prompt) for r in before][:5] != \
        [len(r.prompt) for r in window][:5]
    assert before[0].prompt[:8] != window[0].prompt[:8]
    again = serve_state_family.pre_roll_requests(cell, rate, 7, vocab)
    assert [(r.due_s, r.prompt) for r in again] == \
        [(r.due_s, r.prompt) for r in before]
    toy = serve_state_family.rehearsal_cell(cell)
    assert toy["pre_roll"]["seconds"] \
        == serve_state_family.REHEARSE_PRE_ROLL_S
    # its seed by the mix's own rule over its span
    # (test_traffic.py::test_the_committed_schedule_... holds the window's)
    import numpy as np

    from benchmark.generators import sessions

    mix = cell["traffic_file"]
    rng = np.random.default_rng(0)
    prompt = (traffic.draw_lengths(mix["history_tokens"], 100000, rng)
              + traffic.draw_lengths(mix["turn_tokens"], 100000, rng)).mean()
    out = traffic.draw_lengths(mix["output_tokens"], 100000, rng).mean()

    def offers(seed):
        s = sessions.schedule({**mix, "traffic_seed": seed}, rate, span)
        want = rate * span
        got = s["history_tokens"].sum() + s["turn_tokens"].sum()
        return (abs(len(s["due_s"]) - want) <= 2.5
                and abs(got / (want * prompt) - 1) <= 0.05
                and abs(s["output_tokens"].sum() / (want * out) - 1) <= 0.05)

    assert next(s for s in range(1, 1000) if offers(s)) \
        == cell["pre_roll"]["traffic_seed"]


def test_what_the_window_counts_of_a_request_carried_in(cell):
    """First tokens of the requests due in the window only; tokens inside
    the window and gaps that end inside it from every request."""
    from benchmark import traffic
    from benchmark.kinds.serve import Client

    def client(due, stamps, max_new=99):
        c = Client(traffic.Request(0, due, -1, [1, 2, 3], 0, max_new))
        c.sent, c.stamps = 100.0 + due, list(stamps)
        return c

    window = (100.0, 110.0)
    new = client(1.0, [101.5, 101.6, 101.7])
    carried = client(-20.0, [81.0, 99.95, 100.05, 100.15, 111.0])
    done = client(-30.0, [71.0, 71.1])           # ended before the window
    e2e = serve_state_family.end_to_end([new], [carried, done], window, 10.0)
    assert e2e["n_requests"] == 1 and e2e["n_carried_in"] == 1
    assert e2e["ttft_p90_ms"] == pytest.approx(500.0)     # the new one's
    assert e2e["serve_tok_s"] == pytest.approx((3 + 2) / 10.0)
    # gaps: two of the new request, and of the carried one the gap that
    # straddles the window's start and the one inside; none from before
    assert e2e["n_gaps"] == 4
    assert e2e["tpot_p95_ms"] <= 100.0 + 1e-6
    assert carried.stamps[0] == 81.0            # the client is not edited
