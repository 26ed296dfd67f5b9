"""The linear hybrid family's file and its cell, CPU only, no ray_tpu runtime:
the configuration against the catalog row, its bytes against the shapes,
``build_params`` against the published count, ``step_needs`` on hand-counted
rows, the scope map with the family's lists, the seven new readers over a
synthetic run, the generator of growing conversations, the kind's two checks
and the reference against itself with mathematics left out."""

import json
import os

import numpy as np
import pytest

from benchmark import family_rooflines, manifest, run, traffic
from benchmark.generators import conversation_turns as turns
from benchmark.kinds import serve_snapshot_family, serve_state_family
from benchmark.kinds import serve_state_family_replica as replica

CELL = "olmo-hybrid-7b.multiturn-sessions"
CONFIG = "olmo-hybrid-7b-l12-serve"
READERS = ("delta_rule_roofline", "delta_projections_roofline",
           "mha_attention_roofline", "linear_hybrid_step_roofline",
           "state_prefix_hit_token_pct", "state_restore_ms",
           "state_snapshots_evicted_pct")

#: the catalog row's ``config`` (architectures.jsonl, Olmo-Hybrid-7B)
ROW = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False,
    "layer_types": (["linear_attention"] * 3 + ["full_attention"]) * 8,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}}

DELTA_MIXER = 88_750_332
MLP = 126_812_160
TOTAL = 3_268_268_508


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
    assert cf["num_hidden_layers"] == 12
    assert list(cf["reduced"]) == ["num_hidden_layers"]
    assert cf["reduced"]["num_hidden_layers"]["published"] == 32
    entry = next(c for c in manifest.load_manifest()["configs"]
                 if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == cf["source"] \
        == "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json"
    assert list(cf["assumed"]) == ["rule", "norms", "rope", "conv_and_gates",
                                   "weights", "engine"]
    prec = cf["precision"]
    assert prec["state"] == prec["snapshots"] == prec["decay"] \
        == prec["rule"] == "float32"
    assert prec["weights"] == prec["activations"] == prec["kv_pool"] \
        == "bfloat16"


def test_transformer_config_reads_every_published_key(cell, family):
    cf = cell["config_file"]
    c = family.transformer_config(cf)
    assert c.linear_hybrid and c.layer_kinds \
        == (("delta",) * 3 + ("full",)) * 3
    assert c.delta_periods == (3, 3)
    assert (c.d_model, c.ff, c.n_heads, c.kv_heads, c.hdim) == (
        3840, 11008, 30, 30, 128)
    assert (c.delta_key_heads, c.delta_key_dim, c.delta_value_dim,
            c.delta_conv, c.delta_neg_eigval) == (30, 96, 192, 4, True)
    assert (c.delta_key_width, c.delta_value_width, c.delta_conv_width) \
        == (2880, 5760, 11520)
    assert (c.vocab_size, c.tie_embeddings, c.positions, c.norm_eps) == (
        100352, False, "none", 1e-6)
    assert not c.qk_norm and not c.post_norms
    assert (c.dtype, c.param_dtype) == ("bfloat16", "bfloat16")
    assert c.num_params() == TOTAL
    whole = family.transformer_config({**cf, "num_hidden_layers": 32})
    assert whole.num_params() == 7_430_870_688
    # a layer pattern the file does not describe is refused by name
    for key, value in (("attention_bias", True),
                       ("tie_word_embeddings", True),
                       ("linear_num_key_heads", 15),
                       ("rope_parameters", {"rope_theta": 500000.0})):
        with pytest.raises(NotImplementedError, match="does not describe"):
            family.transformer_config({**cf, key: value})
    with pytest.raises(ValueError, match="linear hybrid layout described"):
        family.transformer_config({**cf, "num_hidden_layers": 10})


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
    blocks = tree["layers"]["periods"]
    assert blocks["delta"]["w_qkv"].shape == (3, 3, 3840, 11520)
    assert blocks["delta"]["w_ab"].shape == (3, 3, 3840, 60)
    assert blocks["delta"]["conv_w"].shape == (3, 3, 4, 11520)
    assert blocks["attn"]["q_norm"].shape == (3, 3840)
    assert tree["lm_head"].shape == (3840, 100352)


def test_the_seeded_scales_are_what_the_configuration_says(cell, family):
    """Toy widths, real draws: the embedding at unit scale, the q norm's
    gain about 2.5, the decay's columns of ``w_ab`` a twentieth of beta's,
    and decays that spread over 0.5-0.999."""
    import jax

    cf = {**cell["config_file"], **family.TOY_WIDTHS,
          "precision": {"weights": "float32", "activations": "float32"}}
    c = family.transformer_config(cf)
    p = family.build_params(c, jax.random.PRNGKey(3))
    std = lambda a: float(np.asarray(a, np.float64).std())
    blocks = p["layers"]["periods"]
    assert std(p["embed"]) == pytest.approx(family.EMBED_STD, rel=0.05)
    assert float(np.mean(blocks["attn"]["q_norm"])) == pytest.approx(
        family.Q_NORM_GAIN, rel=0.05)
    assert float(np.mean(blocks["attn"]["k_norm"])) == pytest.approx(
        1.0, rel=0.05)
    h = c.delta_key_heads
    w_ab = np.asarray(blocks["delta"]["w_ab"])
    assert std(w_ab[..., :h]) / std(w_ab[..., h:]) == pytest.approx(
        family.DECAY_PROJ_GAIN, rel=0.2)
    alpha = np.exp(-np.exp(np.asarray(blocks["delta"]["A_log"]))
                   * np.logaddexp(0, np.asarray(blocks["delta"]["dt_bias"])))
    assert 0.4 < alpha.min() < 0.75 and 0.99 < alpha.max() < 1.0
    assert std(blocks["delta"]["w_up"]) == pytest.approx(
        c.d_model ** -0.5, rel=0.05)


def test_the_cell_resolves_and_names_its_share(cell):
    man = manifest.load_manifest()
    for what, path in manifest.cell_paths(man, CELL).items():
        assert os.path.isfile(path), (what, path)
    assert cell["kind"] == "serve_snapshot_family" and cell["chips"] == 1
    name = cell["config_file"]["reference"]
    assert name == "linear_hybrid_decoder"
    assert os.path.isfile(manifest.reference_path(name))
    names = {m["name"] for m in manifest.metrics_of(man, "per_layer", CELL)}
    assert names >= set(READERS) | {"engine_step_ms", "slot_occupancy_pct",
                                    "decode_step_device_ms",
                                    "device_idle_pct.serve", "chunk_step_ms",
                                    "ttft_prefill_ms",
                                    "prefill_steps_per_request"}
    assert not names & {"decode_step_roofline", "prefix_hit_token_pct",
                        "ssd_scan_roofline", "parallel_hybrid_step_roofline"}
    for m in man["per_layer"]:
        if m["name"] in READERS:
            assert m["workloads"] == [CELL]
    assert [m["name"] for m in man["per_layer"][-7:]] == list(READERS)
    assert man["workloads"][-1]["name"] == CELL
    assert man["configs"][-1]["name"] == CONFIG
    other = manifest.load_cell(man, "falcon-h1-34b.chat-concurrent")
    assert set(cell["limits"]) == set(other["limits"])
    assert cell["snapshot_limits"] == {"self_agreement_missed_prefix": 0,
                                       "state_snapshots_leaked": 0,
                                       "snapshot_logit_drift": 0.001,
                                       "state_rel_err_first_layer": 0.007,
                                       "snapshot_check_missed": 0}
    share = cell["rate_rps"] / cell["knee_rps"]
    assert 0.73 <= share <= 0.90
    assert f"{share:.2f} of its knee" in cell["why"]
    assert "restores a snapshot" in cell["why"]
    assert len(cell["why"]) <= 200
    # the traffic as the issue gives it, inside max_len and ref_len
    mix, eng = cell["traffic_file"], cell["config_file"]["engine"]
    assert mix["generator"] == "conversation_turns"
    assert mix["sessions"] % 8 == 0 and mix["sessions"] >= 40
    assert (mix["popularity"], mix["min_turn_gap_s"],
            mix["max_prompt_tokens"]) == ({"zipf": 0.6}, 5.0, 2048)
    assert mix["history_tokens"]["sigma"] == 0.6
    # (as the issue draws them, up to the cap: the generator cuts the turn
    # that follows a fresh start to what the cap leaves)
    assert (mix["history_tokens"]["median"], mix["history_tokens"]["min"],
            mix["history_tokens"]["max"]) == (768, 256, 2048)
    assert mix["turn_tokens"] == {"dist": "lognormal", "median": 64,
                                  "sigma": 0.8, "min": 16, "max": 512}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 128,
                                    "sigma": 0.6, "min": 32, "max": 512}
    assert mix["max_prompt_tokens"] + mix["output_tokens"]["max"] \
        <= eng["max_len"]
    assert mix["max_prompt_tokens"] + cell["check"]["new_tokens"] \
        <= cell["check"]["ref_len"] <= eng["max_len"]
    assert (eng["max_slots"], eng["prefill_chunk"], eng["block_size"],
            eng["num_blocks"]) == (32, 64, 16, 5120)
    # a second serve of the self-agreement prompt can land on a snapshot:
    # its last block boundary lies under its last token
    assert cell["self_agreement"]["prompt_tokens"] % eng["block_size"]
    # no pre-roll: a request lives a twentieth of the window
    assert cell["pre_roll"]["seconds"] == 0
    assert serve_state_family.pre_roll_requests(
        cell, cell["rate_rps"], 7, 100352) == []


def test_conversations_grow_and_start_over(cell):
    mix, rate = cell["traffic_file"], cell["rate_rps"]
    s = turns.schedule(mix, rate, 51)
    n = len(s["due_s"])
    assert abs(n - rate * 51) <= 2.5
    assert (s["prompt_tokens"] <= mix["max_prompt_tokens"]).all()
    assert (s["prompt_tokens"] - s["shared_tokens"] >= 16).all()
    # nobody sends a turn before min_turn_gap_s after its last, bar the
    # instants at which every conversation is still waiting
    last, early = {}, 0
    for due, k in zip(s["due_s"], s["session"]):
        early += due - last.get(k, -1e9) < mix["min_turn_gap_s"]
        last[k] = due
    assert early <= 0.02 * n
    assert len(set(s["session"])) == mix["sessions"]
    # a conversation's prompt is its last prompt, an answer and a new turn;
    # after a restart it is its set-up history and a new turn
    first = turns._histories(mix)
    prompt_of, out_of = {}, {}
    for i in range(n):
        k = int(s["session"][i])
        if s["restart"][i]:
            assert s["shared_tokens"][i] == first[k]
            assert s["prompt_tokens"][i] == first[k] + s["turn_tokens"][i]
            # it started over because the next prompt would pass the cap
            # (a conversation's first turn: its set-up history and the turn
            # as drawn would)
            assert prompt_of.get(k, first[k]) + out_of.get(k, 0) \
                + s["turn_tokens"][i] > mix["max_prompt_tokens"] \
                or k not in prompt_of
        else:
            was = prompt_of.get(k, first[k])
            assert s["shared_tokens"][i] == was
            assert s["prompt_tokens"][i] == was + out_of.get(k, 0) \
                + s["turn_tokens"][i]
        prompt_of[k], out_of[k] = s["prompt_tokens"][i], s["output_tokens"][i]
    assert 0.05 < s["restart"].mean() < 0.3
    share = s["shared_tokens"].sum() / s["prompt_tokens"].sum()
    assert 0.75 < share < 0.92         # what a prefix cache can serve at best


def test_a_history_near_the_cap_leaves_a_short_turn(cell):
    """ISSUE 50 draws histories up to the cap of 2,048, where a set-up
    history and a turn must still fit under it: one that would leave less
    than the shortest turn is drawn again, and a turn that follows a fresh
    start is cut to what the cap leaves."""
    mix, rate = cell["traffic_file"], cell["rate_rps"]
    cap, least = mix["max_prompt_tokens"], mix["turn_tokens"]["min"]
    long, cut = 0, 0
    for seed in range(1, 9):
        m = {**mix, "traffic_seed": seed}
        first = turns._histories(m)
        assert (first >= 256).all() and (first <= cap - least).all()
        long += int((first > cap - mix["turn_tokens"]["max"]).sum())
        s = turns.schedule(m, rate, 51)
        assert (s["prompt_tokens"] <= cap).all()
        assert (s["turn_tokens"] >= least).all()
        cut += int(((s["restart"] == 1) & (s["prompt_tokens"] == cap)).sum())
    assert long > 0 and cut > 0


def test_one_schedule_for_every_seed_and_tokens_that_chain(cell):
    mix, rate, vocab = cell["traffic_file"], cell["rate_rps"], 100352
    a = traffic.generate(mix, rate, 20, 7, vocab)
    b = traffic.generate(mix, rate, 20, 3_000_000_019, vocab)
    assert [(r.due_s, r.tenant, len(r.prompt), r.max_new, r.shared_tokens)
            for r in a] == [(r.due_s, r.tenant, len(r.prompt), r.max_new,
                             r.shared_tokens) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in b]
    assert a == traffic.generate(mix, rate, 20, 7, vocab)
    warm = traffic.warm_prompts(mix, 7, vocab)
    assert len(warm) == mix["sessions"]
    assert [len(w) for w in warm] == turns._histories(mix).tolist()
    s = turns.schedule(mix, rate, 20)
    last = {}
    for r, restart in zip(a, s["restart"]):
        # a turn's prompt starts with what the conversation was: its set-up
        # history, and unless it started over its whole last prompt
        assert r.prompt[:len(warm[r.tenant])] == warm[r.tenant]
        if r.tenant in last and not restart:
            assert r.prompt[:len(last[r.tenant])] == last[r.tenant]
        assert r.prompt[:r.shared_tokens] == (
            warm[r.tenant] if restart or r.tenant not in last
            else last[r.tenant])
        last[r.tenant] = r.prompt
        assert all(0 <= t < vocab for t in r.prompt[-4:])
    assert traffic.generate(mix, rate, 0.0, 7, vocab) == []


def test_the_schedule_follows_the_mixes_rule(cell):
    mix, rate = cell["traffic_file"], cell["rate_rps"]
    assert turns.seed_by_rule(mix, rate, 51) == mix["traffic_seed"]
    want = turns.expected_tokens(mix, rate, 51)
    s = turns.schedule(mix, rate, 51)
    n = len(s["due_s"])
    assert abs((s["prompt_tokens"] - s["shared_tokens"]).sum()
               / (n * want["unshared"]) - 1) <= 0.05
    assert abs(s["output_tokens"].sum() / (n * want["output"]) - 1) <= 0.05
    assert 150 < want["unshared"] < 300 and 120 < want["output"] < 180


def test_device_bytes_are_the_shapes(cell, family):
    cf = cell["config_file"]
    part = family.layer_params(cf)
    assert part["delta_proj"] + part["delta_rule"] == DELTA_MIXER
    assert part["attn"] == 4 * 3840 ** 2 + 2 * 3840
    assert part["mlp"] == MLP + 3840 and part["mixer_norm"] == 3840
    assert (part["n_delta"], part["n_full"]) == (9, 3)
    total = (9 * DELTA_MIXER + 3 * part["attn"]
             + 12 * (part["mlp"] + part["mixer_norm"])
             + 2 * 100352 * 3840 + 3840)
    assert cf["device_bytes"]["parameters"] == total == TOTAL
    assert cf["device_bytes"]["weights"] == 2 * total
    assert family.state_bytes(cf) == {"delta": 2_211_840, "conv": 138_240}
    eng = cf["engine"]
    kv = eng["num_blocks"] * eng["block_size"] * 3 * 2 * 32 * 128 * 2
    state = 9 * sum(family.state_bytes(cf).values())
    assert state == 21_150_720
    snapshots = 5 * eng["max_slots"] // 2
    assert snapshots == 80
    assert cf["device_bytes"]["kv_pool"] \
        == kv + (eng["max_slots"] + snapshots) * state
    assert cf["device_bytes"]["kv_per_token"] == 3 * 2 * 32 * 128 * 2
    # the program sizes its pools the same way
    import jax

    from ray_tpu import models

    tc = family.transformer_config(cf)
    cache = jax.eval_shape(lambda: models.init_cache_paged(
        tc, eng["num_blocks"], eng["block_size"],
        state_slots=eng["max_slots"]))
    assert sum(a.size * a.dtype.itemsize for a in cache.values()) \
        == kv + eng["max_slots"] * state
    # three quarters of the chip before a request arrives
    assert 0.7 < (cf["device_bytes"]["weights"]
                  + cf["device_bytes"]["kv_pool"]) / 16.9e9 < 0.8


def test_step_needs_on_hand_counted_rows(cell, family):
    cf = cell["config_file"]
    # a decode row at 1500 cached tokens, one at 100, a 64-token block from
    # 1280 and a 17-token tail from 64
    rows = [(1500, 1, 1), (100, 1, 1), (1280, 64, 0), (64, 17, 1)]
    needs = family.step_needs(cf, rows, {})
    assert (needs["fed"], needs["sampled"]) == (83, 3)
    h, dk, dv, cw, vw, taps = 30, 96, 192, 11520, 5760, 4
    turn = h * 8 * dk * dv
    block = lambda t: h * (4 * t * t * dk + 4 * t * dk * dv + 3 * t * t * dv
                           + 2 * t * dk * dv + dk * dv)
    assert needs["delta_rule"] == {
        "flops": 9 * (2 * turn + block(64) + block(17) + 83 * 12 * h),
        "bytes": 9 * (2 * 2 * h + 2 * 2_211_840 * 4
                      + 4 * 83 * (cw + 2 * h) + 2 * 83 * vw)}
    weights = 3840 * (cw + vw + 2 * h) + vw * 3840
    assert needs["delta_projections"] == {
        "flops": 9 * 83 * (2 * weights + 2 * taps * cw + 10 * vw),
        "bytes": 9 * (2 * (weights + taps * cw + dv) + 2 * 138_240 * 4
                      + 2 * 83 * (2 * 3840 + cw + 3 * vw) + 4 * 83 * cw
                      + 4 * 83 * 2 * h)}
    keys = 1501 + 101 + 1344 + 81
    pairs = 1501 + 101 + sum(range(1281, 1345)) + sum(range(65, 82))
    assert needs["paged_attention"] == {
        "flops": 3 * 4 * 128 * 30 * pairs,
        "bytes": 3 * (15360 * keys + 2 * 2 * 3840 * 83)}
    part = family.layer_params(cf)
    other = 3 * part["attn"] + 12 * (part["mlp"] + part["mixer_norm"]) + 3840
    head = 3840 * 100352
    scopes = [needs[s] for s in ("delta_rule", "delta_projections",
                                 "paged_attention")]
    assert needs["step"]["flops"] == sum(s["flops"] for s in scopes) \
        + 2 * other * 83 + 2 * head * 3
    assert needs["step"]["bytes"] == sum(s["bytes"] for s in scopes) \
        + 2 * other + 3 * 15360 * 83 + 2 * 3840 * 83 + 2 * head \
        + 4 * 100352 * 3
    # every weight is read once: the parts add up to the model less the
    # embedding (whose rows are looked up)
    assert other + 9 * DELTA_MIXER + head == TOTAL - head
    # a step of 25 decoding rows at 1,500 keys, issue 50's count: 6.2 GB of
    # weights, 1.0 GB of state and 1.7 GB of KV: memory-bound; the delta
    # layers (mixer weights and state) ahead of the full layers' KV and
    # behind the MLPs
    steady = family.step_needs(cf, [(1500, 1, 1)] * 25, {})
    assert 8.6e9 < steady["step"]["bytes"] < 9.0e9
    assert steady["step"]["flops"] / 197e12 < steady["step"]["bytes"] / 819e9
    assert 0.99e9 < steady["delta_rule"]["bytes"] < 1.01e9
    assert 1.70e9 < steady["paged_attention"]["bytes"] < 1.76e9
    assert steady["paged_attention"]["bytes"] \
        < steady["delta_rule"]["bytes"] + steady["delta_projections"]["bytes"] \
        < 2 * 12 * MLP
    # a block of 64 positions costs 64 turns' read-outs and update less the
    # decay pass, and its T^2 products and solve on top
    assert 0.7 * 64 * turn < block(64) < 1.3 * 64 * turn


def test_scopes_come_from_the_family_file(family):
    assert family.SCOPES == ("delta_proj", "delta_rule", "delta_out",
                             "paged_attention")
    assert family.KERNELS == {}
    assert family.STEP_COUNTERS == (
        "delta_positions_real", "delta_positions_run", "delta_rows_stepped",
        "delta_rows_blocked", "state_slots_live")
    text = '''
  %fusion.7 = f32[15,96,384]{2,1,0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(_raw_step_paged)/jit(main)/while/body/while/body/closed_call/delta_rule/while/body/mul" source_file="x.py"}
  %fusion.8 = f32[30,64,192]{2,1,0} fusion(%p), kind=kOutput, calls=%f, metadata={op_name="jit(s)/while/body/while/body/closed_call/delta_rule/while/body/dot_general"}
  ROOT %fusion.9 = f32[32,64,11520]{2,1,0} fusion(%a), kind=kLoop, calls=%g, metadata={op_name="jit(s)/while/body/while/body/closed_call/delta_proj/add"}
  %custom-call.3 = bf16[32,32,64,128]{3,2,1,0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(s)/while/body/closed_call/paged_attention/pallas_call"}
  %fusion.11 = bf16[1,256,3840]{2,1,0} fusion(%y), kind=kOutput, calls=%h, metadata={op_name="jit(s)/while/body/while/body/cond/branch_0_fun/delta_out/dot_general"}
  %fusion.12 = bf16[1,256,11520]{2,1,0} fusion(%y), kind=kOutput, calls=%h, metadata={op_name="jit(s)/while/body/while/body/cond/branch_0_fun/delta_proj/dot_general"}
  %fusion.13 = bf16[1,256,11008]{2,1,0} fusion(%y), kind=kOutput, calls=%h, metadata={op_name="jit(s)/while/body/closed_call/cond/branch_0_fun/mlp/dot_general"}
'''
    by_name = replica.scopes_of_instructions(text, family.SCOPES,
                                             family.KERNELS)
    assert by_name == {"fusion.7": "delta_rule", "fusion.8": "delta_rule",
                       "fusion.9": "delta_proj",
                       "custom-call.3": "paged_attention",
                       "fusion.11": "delta_out", "fusion.12": "delta_proj"}


def _synthetic_run(cell, family):
    cf = cell["config_file"]
    rows = [(1500, 1, 1)] * 22 + [(1280, 64, 0), (480, 17, 1)]
    counters = {"delta_positions_real": 22 + 81,
                "delta_positions_run": 22 + 128, "delta_rows_stepped": 22,
                "delta_rows_blocked": 2, "state_slots_live": 24}
    steps = [(float(i), i + 0.9, rows) for i in range(4)]
    engine = {"prefix_hit_tokens": 9000, "requests_admitted": 10,
              "state_snapshots_taken": 10, "state_snapshots_restored": 9,
              "state_snapshots_evicted": 8, "state_snapshot_bytes": 19,
              "state_restore_s": 0.018}
    stats0 = {k: 0 for k in {**counters, **engine}}
    stats1 = {**{k: 4 * v for k, v in counters.items()}, **engine}
    trace = {"n_devices": 1, "window_monotonic": [0.5, 3.5],
             "window_s": 3.0, "busy_s": 2.0, "program_runs_ms": [20.0, 20.0],
             "device_ops": [], "idle_gaps": [],
             "scope_s": {"delta_proj": 6e-3, "delta_rule": 9e-3,
                         "delta_out": 1e-3, "paged_attention": 6e-3}}

    class Sent:
        sent, stamps, key = 1.0, [], 0

        def __init__(self, n):
            self.req = type("R", (), {"prompt": [0] * n})

    outcome = {"replica": {"steps": steps, "step_counters": [counters] * 4,
                           "max_slots": 32, "engine_ttft": {}},
               "trace": trace, "config_file": cf, "cell": cell,
               "facts": {"kind": "TPU v5 lite"}, "window": (0.0, 4.0),
               "clients": [Sent(1000)] * 10,
               "marks": {"start": {"stats": stats0}, "end": {"stats": stats1}}}
    return outcome, rows, counters, trace


def test_readers_over_a_synthetic_run(cell, family):
    outcome, rows, counters, trace = _synthetic_run(cell, family)
    needs = family.step_needs(cell["config_file"], rows, counters)
    assert len(family_rooflines.traced_steps(outcome)) == 2
    read = lambda name: manifest.load_module(
        manifest.layer_metric_path(name)).read(outcome)
    peak, flops = 819e9, 197e12
    least = lambda scope: max(needs[scope]["bytes"] / peak,
                              needs[scope]["flops"] / flops)
    assert read("delta_rule_roofline") == pytest.approx(
        100 * 2 * least("delta_rule") / 9e-3)
    assert read("delta_projections_roofline") == pytest.approx(
        100 * 2 * least("delta_projections") / 7e-3)
    assert read("mha_attention_roofline") == pytest.approx(
        100 * 2 * least("paged_attention") / 6e-3)
    assert read("linear_hybrid_step_roofline") == pytest.approx(
        100 * least("step") / 20e-3)
    assert read("state_prefix_hit_token_pct") == pytest.approx(90.0)
    assert read("state_restore_ms") == pytest.approx(2.0)
    assert read("state_snapshots_evicted_pct") == pytest.approx(80.0)
    for name in READERS[:5]:
        assert 0 < read(name) < 100, name
    # a program without the counters or the scopes (the parent's): nothing
    # to read, no raise
    bare = {**outcome, "trace": {k: v for k, v in trace.items()
                                 if k != "scope_s"},
            "replica": {k: v for k, v in outcome["replica"].items()
                        if k != "step_counters"},
            "marks": {"start": {"stats": {"prefix_hit_tokens": 0}},
                      "end": {"stats": {"prefix_hit_tokens": 5}}}}
    for name in READERS:
        assert manifest.load_module(
            manifest.layer_metric_path(name)).read(bare) is None
    line = run.result_line(manifest.load_manifest(), CELL, 1, {
        "correct": True, "attempted": 1, "failed": 0, "trace": trace,
        "facts": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                  "memory_peak_bytes": 1}, "run": outcome})
    assert set(line["metrics"]) >= set(READERS) | {
        "decode_step_device_ms", "device_idle_pct.serve"}


@pytest.mark.parametrize("hit,restored,held,free,twice,want", [
    (656, 1, 70, 10, (0.0, 3), (0, 0, 0.0, 0)),
    (0, 0, 70, 10, (0.0, 3), (1, 0, 0.0, 0)),
    (656, 0, 70, 10, (0.0, 3), (1, 0, 0.0, 0)),
    (656, 1, 69, 10, (0.0, 3), (0, 1, 0.0, 0)),
    (656, 1, 80, 0, (0.03, 1), (0, 0, 0.03, 0)),
    (656, 1, 80, 0, (0.0, 0), (0, 0, 0.0, 1)),
    (656, 1, 80, 0, (float("inf"), 2), (0, 0, float("inf"), 0))])
def test_the_kinds_own_checks(cell, hit, restored, held, free, twice, want):
    mark = lambda h, r: {"stats": {"prefix_hit_tokens": h,
                                   "state_snapshots_restored": r}}
    got = serve_snapshot_family.snapshot_numbers({"replica": {
        "marks": {"agree_1": mark(100, 5),
                  "agree_2": mark(100 + hit, 5 + restored)},
        "kv_state": {"prefix": {"snapshots": 80, "snapshots_held": held,
                                "snapshots_free": free}},
        "snapshot_check": {"snapshot_logit_drift": twice[0],
                           "state_rel_err_first_layer": 0.0012,
                           "restored": twice[1], "samples": 4}}})
    assert got["state_rel_err_first_layer"] == 0.0012
    assert (got["self_agreement_missed_prefix"],
            got["state_snapshots_leaked"], got["snapshot_logit_drift"],
            got["snapshot_check_missed"]) == want
    assert set(got) == set(cell["snapshot_limits"])


def test_a_drift_is_the_largest_difference_or_a_token():
    from benchmark.kinds.serve_snapshot_family_replica import logit_drift

    a = [[(5, np.zeros(4)), (7, np.ones(4))]]
    b = [[(5, np.zeros(4)), (7, np.ones(4) + [0, 0.25, 0, -0.5])]]
    assert logit_drift(a, a) == 0.0 and logit_drift(a, b) == 0.5
    assert logit_drift(a, [[(5, np.zeros(4)), (8, np.ones(4))]]) \
        == float("inf")


def test_rehearsal_cell_runs_the_toy_widths(cell, family):
    toy = serve_state_family.rehearsal_cell(cell)
    cf = toy["config_file"]
    assert cf["hidden_size"] == 96 and cf["num_hidden_layers"] == 8
    assert family.layer_kinds(cf) == (("delta",) * 3 + ("full",)) * 2
    c = family.transformer_config(cf)
    assert (c.hdim, c.n_heads, c.delta_value_dim) == (16, 6, 64)
    json.dumps(toy)      # plain data: it is sent to the replica
    assert toy["traffic_file"]["history_tokens"]["max"] == 16


def test_the_reference_sees_each_piece_left_out(cell, family):
    """The plain reference at toy widths against itself with one piece of
    the mathematics left out (the controls of PERF.md section 2): every one
    moves the logits by tenths; the int8 control and the matrix state held
    in bf16 by hundredths."""
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
    lower = ("int8", "bf16_state")
    assert 0.02 < got["int8"] < 0.6
    assert 0.002 < got["bf16_state"] < got["int8"]
    for v in ref.VARIANTS:
        assert v in lower or got[v] > 0.3, (v, got)
    with pytest.raises(ValueError, match="unknown weights"):
        ref.logits_at(params, tokens, rows, cf, weights="fp4")
    # the layers held are the first of the published pattern: the list
    # stays whole in the file and the weights say how many periods there are
    assert len(cf["layer_types"]) == 32
    with pytest.raises(NotImplementedError, match="does not describe"):
        ref.logits_at(params, tokens, rows,
                      {**cf, "layer_types": ["full_attention"] * 32})
