"""The parallel attention / Mamba-2 family's file and its cell, CPU only, no
ray_tpu runtime: the configuration against the catalog row, its bytes
against the shapes, ``build_params`` against the published count,
``step_needs`` on hand-counted rows, the scope map with the family's lists,
the five new readers over a synthetic run, and the reference against itself
with mathematics left out."""

import json
import os

import numpy as np
import pytest

from benchmark import family_rooflines, manifest, run, traffic
from benchmark.generators import sessions
from benchmark.kinds import serve_family, serve_state_family
from benchmark.kinds import serve_state_family_replica as replica

CELL = "falcon-h1-34b.chat-concurrent"
CONFIG = "falcon-h1-34b-l6-serve"
READERS = ("ssd_scan_roofline", "mamba2_projections_roofline",
           "parallel_attention_roofline", "parallel_hybrid_step_roofline",
           "ssd_positions_real_pct")

#: the catalog row's ``config`` (architectures.jsonl, Falcon-H1-34B-Instruct)
ROW = {
    "attention_bias": False, "attention_in_multiplier": 1,
    "attention_out_multiplier": 0.0375, "attn_layer_indices": None,
    "embedding_multiplier": 5.656854249492381, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 5120, "intermediate_size": 21504,
    "key_multiplier": 0.011048543456039804,
    "lm_head_multiplier": 0.0078125, "mamba_chunk_size": 128,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 128,
    "mamba_d_ssm": 4096, "mamba_d_state": 256, "mamba_expand": 2,
    "mamba_n_groups": 2, "mamba_n_heads": 32,
    "mamba_norm_before_gate": False, "mamba_proj_bias": False,
    "mamba_rms_norm": True, "mamba_use_mlp": True,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_expansion_factor": 8,
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
    "model_type": "falcon_h1", "num_attention_heads": 20,
    "num_hidden_layers": 72, "num_key_value_heads": 4,
    "num_logits_to_keep": 1, "projectors_bias": False,
    "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 100000000000, "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "ssm_out_multiplier": 0.08838834764831845,
    "tie_word_embeddings": False, "vocab_size": 261120}

LAYER = 430_120_032
TOTAL = 5_254_594_112


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(manifest.load_manifest(), CELL)


@pytest.fixture(scope="module")
def family(cell):
    return replica.load_family(cell["config_file"])


def test_the_configuration_keeps_the_catalog_row_but_the_depth(cell):
    cf = cell["config_file"]
    for key, value in ROW.items():
        if key != "num_hidden_layers":
            assert cf[key] == value, key
    assert cf["num_hidden_layers"] == 6
    assert list(cf["reduced"]) == ["num_hidden_layers"]
    assert cf["reduced"]["num_hidden_layers"]["published"] == 72
    entry = next(c for c in manifest.load_manifest()["configs"]
                 if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == cf["source"]
    assert set(cf["assumed"]) >= {"gated_norm", "conv", "engine", "weights"}
    assert cf["precision"]["state"] == cf["precision"]["scan"] == "float32"


def test_transformer_config_reads_every_published_key(cell, family):
    cf = cell["config_file"]
    c = family.transformer_config(cf)
    assert c.parallel_hybrid and c.layer_kinds == ("parallel",) * 6
    assert (c.d_model, c.ff, c.n_heads, c.kv_heads, c.hdim) == (
        5120, 21504, 20, 4, 128)
    assert (c.d_inner, c.ssm_heads, c.ssm_head_dim, c.ssm_groups,
            c.ssm_state, c.ssm_conv, c.ssm_chunk) == (
        4096, 32, 128, 2, 256, 4, 128)
    assert (c.ssm_conv_width, c.ssm_proj_width) == (5120, 9248)
    assert (c.vocab_size, c.tie_embeddings, c.rope_theta, c.norm_eps) == (
        261120, False, 1e11, 1e-5)
    for key in ("embedding_multiplier", "lm_head_multiplier",
                "attention_in_multiplier", "attention_out_multiplier",
                "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier"):
        assert getattr(c, key) == ROW[key], key
    assert list(c.ssm_mup) == ROW["ssm_multipliers"]
    assert list(c.mlp_mup) == ROW["mlp_multipliers"]
    assert (c.dtype, c.param_dtype) == ("bfloat16", "bfloat16")
    assert c.num_params() == TOTAL
    # a layer pattern the file does not describe is refused by name
    for key, value in (("attn_layer_indices", [0, 2]),
                       ("mamba_norm_before_gate", True),
                       ("tie_word_embeddings", True),
                       ("mamba_d_ssm", 10240)):
        with pytest.raises(NotImplementedError, match="does not describe"):
            family.transformer_config({**cf, key: value})


def test_build_params_shapes_are_the_published_count(cell, family):
    import jax

    c = family.transformer_config(cell["config_file"])
    tree = jax.eval_shape(lambda k: family.build_params(c, k),
                          jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(tree)) == TOTAL
    assert {str(x.dtype) for x in jax.tree.leaves(tree)} == {"bfloat16"}
    from ray_tpu import models

    program = jax.eval_shape(lambda k: models.init_params(k, c),
                             jax.random.PRNGKey(0))
    assert jax.tree.map(lambda a: a.shape, tree) \
        == jax.tree.map(lambda a: a.shape, program)
    layers = tree["layers"]
    assert layers["w_ssm_z"].shape == (6, 5120, 4096)
    assert layers["w_ssm_xbc"].shape == (6, 5120, 5120)
    assert layers["w_ssm_dt"].shape == (6, 5120, 32)
    assert layers["conv_w"].shape == (6, 4, 5120)
    assert tree["lm_head"].shape == (5120, 261120)


def test_seeded_scales_undo_the_multipliers(cell, family):
    """Toy widths, real draws: each matrix a multiplier scales is drawn at
    the usual scale over it, so the product has the usual scale."""
    import jax

    cf = {**cell["config_file"], **family.TOY_WIDTHS,
          "precision": {"weights": "float32", "activations": "float32"}}
    c = family.transformer_config(cf)
    p = family.build_params(c, jax.random.PRNGKey(3))
    std = lambda a: float(np.asarray(a, np.float64).std())
    d = c.d_model
    lay = p["layers"]
    assert std(lay["wk"]) * c.key_multiplier == pytest.approx(
        d ** -0.5, rel=0.05)
    assert std(lay["wq"]) == pytest.approx(family.Q_GAIN * d ** -0.5,
                                           rel=0.05)
    assert std(lay["wo"]) * c.attention_out_multiplier == pytest.approx(
        (c.n_heads * c.hdim) ** -0.5 / 6 ** 0.5, rel=0.05)
    assert std(p["lm_head"]) * c.lm_head_multiplier == pytest.approx(
        d ** -0.5, rel=0.05)
    assert std(p["embed"]) * c.embedding_multiplier == pytest.approx(
        family.EMBED_STD, rel=0.05)
    gn = c.ssm_groups * c.ssm_state
    xbc = np.asarray(lay["w_ssm_xbc"])
    m = c.ssm_mup
    assert xbc[..., :c.d_inner].std() * c.ssm_in_multiplier * m[1] \
        == pytest.approx(d ** -0.5, rel=0.05)
    assert xbc[..., c.d_inner:c.d_inner + gn].std() \
        * c.ssm_in_multiplier * m[2] == pytest.approx(
        family.BC_GAIN * d ** -0.5, rel=0.08)
    assert std(lay["w_down"]) * c.mlp_mup[1] == pytest.approx(
        c.ff ** -0.5 / 6 ** 0.5, rel=0.05)
    # Mamba-2's own starts
    a = np.exp(np.asarray(lay["A_log"]))
    assert 1.0 <= a.min() and a.max() <= 16.0
    step = np.log1p(np.exp(np.asarray(lay["dt_bias"], np.float64)))
    assert 1e-3 * 0.99 <= step.min() and step.max() <= 1e-1 * 1.01


def test_the_cell_resolves_and_names_its_share(cell):
    man = manifest.load_manifest()
    for what, path in manifest.cell_paths(man, CELL).items():
        assert os.path.isfile(path), (what, path)
    assert cell["kind"] == "serve_state_family" and cell["chips"] == 1
    name = cell["config_file"]["reference"]
    assert name == "parallel_hybrid_decoder"
    assert os.path.isfile(manifest.reference_path(name))
    names = {m["name"] for m in manifest.metrics_of(man, "per_layer", CELL)}
    assert names >= set(READERS) | {"engine_step_ms", "slot_occupancy_pct",
                                    "decode_step_device_ms",
                                    "device_idle_pct.serve",
                                    "decode_only_step_ms", "chunk_step_ms",
                                    "full_width_time_pct"}
    assert not names & {"decode_step_roofline", "prefix_hit_token_pct",
                        "ssm_scan_roofline", "hybrid_step_roofline",
                        "window_kv_held_pct"}
    for m in man["per_layer"]:
        if m["name"] in READERS:
            assert m["workloads"] == [CELL]
    other = manifest.load_cell(man, "phi4-mini-flash.reason-longgen")
    assert set(cell["limits"]) == set(other["limits"])
    share = cell["rate_rps"] / cell["knee_rps"]
    assert 0.73 <= share <= 0.90
    assert f"{share:.2f} of its knee" in cell["why"]
    assert "MLP stays the step's first term" in cell["why"]
    assert len(cell["why"]) <= 200
    # the traffic as the issue gives it, inside max_len and ref_len
    mix, eng = cell["traffic_file"], cell["config_file"]["engine"]
    assert (mix["tenants"], mix["shared_prefix_tokens"]) == (0, 0)
    assert mix["turn_tokens"] == {"dist": "lognormal", "median": 400,
                                  "sigma": 0.8, "min": 64, "max": 1536}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 160,
                                    "sigma": 0.6, "min": 32, "max": 512}
    assert mix["turn_tokens"]["max"] + mix["output_tokens"]["max"] \
        <= eng["max_len"]
    assert mix["turn_tokens"]["max"] + cell["check"]["new_tokens"] \
        <= cell["check"]["ref_len"]
    assert (eng["max_slots"], eng["prefill_chunk"], eng["block_size"]) \
        == (48, 32, 16)


def _first_seed(mix, rate, span):
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

    return next(s for s in range(1, 2000) if offers(s))


def test_both_schedules_follow_the_mixes_rule(cell):
    mix, rate = cell["traffic_file"], cell["rate_rps"]
    assert _first_seed(mix, rate, 51) == mix["traffic_seed"]
    span = cell["pre_roll"]["seconds"]
    assert span == 10
    assert _first_seed(mix, rate, span) == cell["pre_roll"]["traffic_seed"]
    before = serve_state_family.pre_roll_requests(
        cell, rate, 7, cell["config_file"]["vocab_size"])
    assert all(-span <= r.due_s < 0 for r in before)
    assert abs(len(before) - rate * span) <= 2.5


def test_device_bytes_are_the_shapes(cell, family):
    cf = cell["config_file"]
    part = family.layer_params(cf)
    assert part["mamba_proj"] + part["ssm"] == 68_351_072
    assert part["attn"] == 31_457_280 and part["mlp"] == 330_301_440 + 5120
    assert sum(part[k] for k in ("mamba_proj", "ssm", "attn", "mlp",
                                 "input_norm")) == LAYER
    total = 6 * LAYER + 2 * 261120 * 5120 + 5120
    assert cf["device_bytes"]["parameters"] == total == TOTAL
    assert cf["device_bytes"]["weights"] == 2 * total
    eng = cf["engine"]
    kv = eng["num_blocks"] * eng["block_size"] * 6 * 2 * 4 * 128 * 2
    assert family.state_bytes(cf) == 4 * (32 * 128 * 256 + 3 * 5120) \
        == 4_255_744
    state = eng["max_slots"] * 6 * family.state_bytes(cf)
    assert cf["device_bytes"]["kv_pool"] == kv + state
    assert cf["device_bytes"]["kv_per_token"] == 12288
    # the program sizes its pools the same way
    import jax

    from ray_tpu import models

    tc = family.transformer_config(cf)
    cache = jax.eval_shape(lambda: models.init_cache_paged(
        tc, eng["num_blocks"], eng["block_size"],
        state_slots=eng["max_slots"]))
    assert sum(a.size * a.dtype.itemsize for a in cache.values()) \
        == cf["device_bytes"]["kv_pool"]
    # over a quarter of the chip before a request arrives
    assert (cf["device_bytes"]["weights"] + cf["device_bytes"]["kv_pool"]
            ) / 16.9e9 > 0.7


def test_step_needs_on_hand_counted_rows(cell, family):
    cf = cell["config_file"]
    # a decode row at 700 cached tokens, one at 100, a 32-token block from
    # 300 and a 17-token tail from 64
    rows = [(700, 1, 1), (100, 1, 1), (300, 32, 0), (64, 17, 1)]
    needs = family.step_needs(cf, rows, {})
    assert (needs["fed"], needs["sampled"]) == (51, 3)
    h, p, g, n, ds, cw, k = 32, 128, 2, 256, 4096, 5120, 4
    turn = 4 * p * n * h
    block = lambda t: 2 * t * t * n * g + 2 * t * t * p * h \
        + 4 * t * p * n * h
    small = k * cw + cw + 3 * h + ds
    assert needs["ssd_scan"] == {
        "flops": 6 * (2 * turn + block(32) + block(17)
                      + 51 * (2 * k * cw + 6 * h + 2 * ds + 8 * ds)),
        "bytes": 6 * (2 * small + 2 * 4_255_744 * 4
                      + 2 * 51 * (cw + h + 3 * ds))}
    proj = 5120 * 9248 + 4096 * 5120
    assert needs["mamba2_projections"] == {
        "flops": 6 * 2 * proj * 51,
        "bytes": 6 * (2 * proj + 2 * 51 * (2 * 5120 + 2 * ds + cw + h))}
    keys = 701 + 101 + 332 + 81
    pairs = 701 + 101 + sum(range(301, 333)) + sum(range(65, 82))
    assert needs["paged_attention"] == {
        "flops": 6 * 4 * 128 * 20 * pairs,
        "bytes": 6 * (2048 * keys + 2 * 2 * 2560 * 51)}
    part = family.layer_params(cf)
    other = 6 * (part["attn"] + part["mlp"] + part["input_norm"]) + 5120
    head = 5120 * 261120
    scopes = [needs[s] for s in ("ssd_scan", "mamba2_projections",
                                 "paged_attention")]
    assert needs["step"]["flops"] == sum(s["flops"] for s in scopes) \
        + 2 * other * 51 + 2 * head * 3
    assert needs["step"]["bytes"] == sum(s["bytes"] for s in scopes) \
        + 2 * other + 6 * 2048 * 51 + 2 * 5120 * 51 + 2 * head \
        + 4 * 261120 * 3
    # every weight is read once: the parts add up to the model less the
    # embedding (whose rows are looked up)
    assert other + 6 * (proj + small) + head == TOTAL - head
    # a step of 40 decoding rows at 700 keys, issue 44's count: 7.84 GB of
    # weights, 2.04 GB of state, 0.34 GB of KV: memory-bound, the MLP first
    steady = family.step_needs(cf, [(700, 1, 1)] * 40, {})
    assert 10.1e9 < steady["step"]["bytes"] < 10.4e9
    assert steady["step"]["flops"] / 197e12 < steady["step"]["bytes"] / 819e9
    assert 2.0e9 < steady["ssd_scan"]["bytes"] < 2.1e9
    assert steady["ssd_scan"]["bytes"] < 2 * 6 * 330_301_440   # the MLPs
    assert 0.33e9 < steady["paged_attention"]["bytes"] < 0.36e9
    # a block of 32 positions costs 32 turns' read-out and update and its
    # T^2 products on top (7 % at 32)
    assert 32 * turn < block(32) < 1.1 * 32 * turn


def test_scopes_come_from_the_family_file(family):
    assert family.SCOPES == ("ssd_conv", "ssd_scan", "ssd_gated_norm",
                             "mamba2_in_proj", "mamba2_out_proj",
                             "paged_attention")
    assert family.KERNELS == {}
    assert family.STEP_COUNTERS == ("ssd_positions_real", "ssd_positions_run",
                                    "state_slots_live")
    text = '''
  %fusion.7 = f32[48,32,128,256]{3,2,1,0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(_raw_step_paged)/jit(main)/while/body/closed_call/ssd_scan/mul" source_file="x.py"}
  %fusion.8 = f32[32,128,256]{2,1,0} fusion(%p), kind=kOutput, calls=%f, metadata={op_name="jit(s)/while/body/closed_call/ssd_scan/while/body/dot_general"}
  ROOT %fusion.9 = f32[48,32,5120]{2,1,0} fusion(%a), kind=kLoop, calls=%g, metadata={op_name="jit(s)/while/body/closed_call/ssd_conv/add"}
  %custom-call.3 = bf16[48,32,20,128]{3,2,1,0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(s)/while/body/closed_call/paged_attention/pallas_call"}
  %fusion.11 = bf16[1,256,5120]{2,1,0} fusion(%y), kind=kOutput, calls=%h, metadata={op_name="jit(s)/while/body/closed_call/cond/branch_0_fun/mamba2_out_proj/dot_general"}
  %fusion.12 = bf16[1,256,4096]{2,1,0} fusion(%y), kind=kLoop, calls=%h, metadata={op_name="jit(s)/while/body/closed_call/cond/branch_0_fun/ssd_gated_norm/mul"}
  %fusion.13 = bf16[1,256,21504]{2,1,0} fusion(%y), kind=kOutput, calls=%h, metadata={op_name="jit(s)/while/body/closed_call/cond/branch_0_fun/mlp/dot_general"}
'''
    by_name = replica.scopes_of_instructions(text, family.SCOPES,
                                             family.KERNELS)
    assert by_name == {"fusion.7": "ssd_scan", "fusion.8": "ssd_scan",
                       "fusion.9": "ssd_conv",
                       "custom-call.3": "paged_attention",
                       "fusion.11": "mamba2_out_proj",
                       "fusion.12": "ssd_gated_norm"}


def test_readers_over_a_synthetic_run(cell, family):
    cf = cell["config_file"]
    rows = [(700, 1, 1)] * 38 + [(320, 32, 0), (480, 17, 1)]
    counters = {"ssd_positions_real": 38 + 49, "ssd_positions_run": 38 + 64,
                "state_slots_live": 40}
    steps = [(float(i), i + 0.9, rows) for i in range(4)]
    stats0 = {k: 0 for k in counters}
    stats1 = {k: 4 * v for k, v in counters.items()}
    needs = family.step_needs(cf, rows, counters)
    trace = {"n_devices": 1, "window_monotonic": [0.5, 3.5],
             "window_s": 3.0, "busy_s": 2.0, "program_runs_ms": [20.0, 20.0],
             "device_ops": [], "idle_gaps": [],
             "scope_s": {"ssd_conv": 2e-3, "ssd_scan": 8e-3,
                         "ssd_gated_norm": 1e-3, "mamba2_in_proj": 1.5e-3,
                         "mamba2_out_proj": 1e-3, "paged_attention": 4e-3}}
    outcome = {"replica": {"steps": steps, "step_counters": [counters] * 4,
                           "max_slots": 48, "engine_ttft": {}},
               "trace": trace, "config_file": cf, "cell": cell,
               "facts": {"kind": "TPU v5 lite"}, "window": (0.0, 4.0),
               "clients": [],
               "marks": {"start": {"stats": stats0}, "end": {"stats": stats1}}}
    assert len(family_rooflines.traced_steps(outcome)) == 2
    read = lambda name: manifest.load_module(
        manifest.layer_metric_path(name)).read(outcome)
    peak, flops = 819e9, 197e12
    least = lambda scope: max(needs[scope]["bytes"] / peak,
                              needs[scope]["flops"] / flops)
    assert read("ssd_positions_real_pct") == pytest.approx(100 * 87 / 102)
    assert read("ssd_scan_roofline") == pytest.approx(
        100 * 2 * least("ssd_scan") / 11e-3)
    assert read("mamba2_projections_roofline") == pytest.approx(
        100 * 2 * least("mamba2_projections") / 2.5e-3)
    assert read("parallel_attention_roofline") == pytest.approx(
        100 * 2 * least("paged_attention") / 4e-3)
    assert read("parallel_hybrid_step_roofline") == pytest.approx(
        100 * least("step") / 20e-3)
    for name in READERS:
        assert 0 < read(name) < 100, name
    # a program without the counters or the scopes: nothing to read, no
    # raise
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


def test_rehearsal_cell_runs_the_toy_widths(cell, family):
    toy = serve_state_family.rehearsal_cell(cell)
    cf = toy["config_file"]
    assert cf["hidden_size"] == 64 and cf["num_hidden_layers"] == 3
    assert cf["mamba_d_ssm"] == cf["mamba_n_heads"] * cf["mamba_d_head"]
    assert toy["pre_roll"]["seconds"] \
        == serve_state_family.REHEARSE_PRE_ROLL_S
    family.transformer_config(cf)
    json.dumps(toy)      # plain data: it is sent to the replica
    assert serve_family.rehearsal_cell(cell)["check"]["ref_len"] == 512


def test_the_reference_sees_each_piece_left_out(cell, family):
    """The plain reference at toy widths against itself with one piece of
    the mathematics left out (the controls of PERF.md section 2): every one
    moves the logits by tenths; the int8 control by a hundredth, the state
    held in bf16 by thousandths."""
    import jax

    ref = manifest.load_module(manifest.reference_path(
        cell["config_file"]["reference"]))
    cf = {**cell["config_file"], **family.TOY_WIDTHS,
          "precision": {"weights": "float32", "activations": "float32"}}
    c = family.transformer_config(cf)
    params = family.build_params(c, jax.random.PRNGKey(5))
    tokens = np.random.default_rng(1).integers(0, 512, 96)
    rows = np.arange(40, 96)
    honest = np.asarray(ref.logits_at(params, tokens, rows, cf))
    assert honest.shape == (56, 512) and np.isfinite(honest).all()
    rel = lambda a: float(np.linalg.norm(a - honest)
                          / np.linalg.norm(honest))
    got = {v: rel(np.asarray(ref.logits_at(params, tokens, rows, cf,
                                           weights=v)))
           for v in ref.VARIANTS}
    lower = ("int8", "bf16_state", "bf16_scan")
    assert 0.005 < got["int8"] < 0.05
    # a lower precision of the state moves them, and by less than int8
    assert 0 < got["bf16_state"] < got["int8"]
    assert 0 < got["bf16_scan"] < got["int8"]
    for v in ref.VARIANTS:
        assert v in lower or got[v] > 0.1, (v, got)
    with pytest.raises(ValueError, match="unknown weights"):
        ref.logits_at(params, tokens, rows, cf, weights="fp4")
