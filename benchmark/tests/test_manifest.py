"""Every name in BENCHMARK.json resolves to a file, and a new cell, a new
configuration and a new per-layer metric need new files and new entries only
(shown with a dummy of each in a copy of the benchmark)."""

import json
import os
import re
import shutil

import pytest

from benchmark import manifest, run


def test_every_name_resolves_to_a_file():
    man = manifest.load_manifest()
    assert man["paths"] == ["benchmark"]
    for cell in man["workloads"]:
        for what, path in manifest.cell_paths(man, cell["name"]).items():
            assert os.path.isfile(path), (cell["name"], what, path)
        loaded = manifest.load_cell(man, cell["name"])
        assert os.path.isfile(manifest.kind_path(loaded["kind"]))
        assert os.path.isfile(manifest.reference_path(
            loaded["config_file"]["reference"]))
        assert os.path.isfile(manifest.generator_path(
            loaded["traffic_file"]["generator"]))
        assert cell["chips"] == 1
        assert set(loaded["limits"]) >= {
            "logit_rel_err_pooled", "tie_gap_max", "failed_requests"}
        e2e = [m["name"] for m in
               manifest.metrics_of(man, "end_to_end", cell["name"])]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert manifest.metrics_of(man, "per_layer", cell["name"])
    for m in man["per_layer"]:
        reader = manifest.load_module(manifest.layer_metric_path(m["name"]))
        assert callable(reader.read)
        assert m["moves"] in {e["name"] for e in man["end_to_end"]}
    used = {c["config"] for c in man["workloads"]}
    assert used == {c["name"] for c in man["configs"]}


#: a configuration's published widths, from the ``config.json`` its
#: ``source`` names: everything but the depth, which ``reduced`` lists
PUBLISHED = {
    "mistral-7b-l16-serve": dict(
        hidden_size=4096, intermediate_size=14336, num_attention_heads=32,
        num_key_value_heads=8, vocab_size=32000, sliding_window=4096,
        rope_theta=10000.0, rms_norm_eps=1e-5),
    "qwen2-7b-l12-serve": dict(
        hidden_size=3584, intermediate_size=18944, num_attention_heads=28,
        num_key_value_heads=4, vocab_size=152064, rope_theta=1e6,
        rms_norm_eps=1e-6, use_sliding_window=False),
    "keye-vl2-30b-a3b-l6-serve": dict(
        hidden_size=2048, intermediate_size=6144, moe_intermediate_size=768,
        head_dim=128, num_attention_heads=32, num_key_value_heads=4,
        num_experts=128, num_local_experts=128, num_experts_per_tok=8,
        norm_topk_prob=True, decoder_sparse_step=1, vocab_size=151936,
        max_position_embeddings=262144, rope_theta=10000000,
        rms_norm_eps=1e-6, use_sliding_window=False,
        sa_config=dict(indexer_head_dim=64, indexer_num_heads=16,
                       indexer_num_kv_heads=1, kv_chunk_size=512,
                       q_chunk_size=512, topk=2048)),
}


@pytest.mark.parametrize("c", manifest.load_manifest()["configs"],
                         ids=lambda c: c["name"])
def test_configs_keep_the_published_widths(c):
    assert c["name"] in PUBLISHED, (
        f"no published widths listed for configuration {c['name']!r}: add "
        f"them to PUBLISHED from the config.json that {c['source']} names")
    cf = manifest.load_json(os.path.join(manifest.ROOT, c["file"]))
    for key, value in PUBLISHED[c["name"]].items():
        assert cf[key] == value, (c["name"], key)
    assert c["reduced"] == ["num_hidden_layers"] == list(cf["reduced"])
    assert cf["source"] == c["source"]
    assert cf["device_bytes"]["weights"] > 0.25 * 16e9


def test_a_configuration_without_listed_widths_fails_with_a_message():
    stranger = {"name": "stranger-1b", "source": "https://example.org/x",
                "file": "benchmark/configs/stranger-1b.json", "reduced": []}
    with pytest.raises(AssertionError, match="no published widths listed"):
        test_configs_keep_the_published_widths(stranger)


def _share(why: str) -> float:
    """The share of the knee that a cell's ``why`` names: "0.87 of"."""
    found = re.search(r"\b(0\.\d+) of (?:the|its) knee", why)
    assert found, f"the why names no share of a knee: {why!r}"
    return float(found.group(1))


@pytest.mark.parametrize("name", [w["name"] for w in
                                  manifest.load_manifest()["workloads"]])
def test_cells_run_at_the_share_of_their_knee_that_their_why_names(name):
    man = manifest.load_manifest()
    cell = manifest.load_cell(man, name)
    share = cell["rate_rps"] / cell["knee_rps"]
    assert 0.73 <= share <= 0.90, (name, cell["rate_rps"], cell["knee_rps"])
    assert abs(_share(cell["why"]) - share) < 0.015, (name, cell["why"],
                                                      share)


@pytest.fixture
def copy_of_benchmark(tmp_path, monkeypatch):
    root = tmp_path / "repo"
    shutil.copytree(manifest.HERE, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), root)
    monkeypatch.setattr(manifest, "ROOT", str(root))
    monkeypatch.setattr(manifest, "HERE", str(root / "benchmark"))
    return root


def test_adding_one_of_each_needs_only_new_files(copy_of_benchmark):
    root = copy_of_benchmark
    bench = root / "benchmark"
    man = json.loads((root / "BENCHMARK.json").read_text())
    # a configuration: its file of sizes
    cf = json.loads((bench / "configs/mistral-7b-l16-serve.json").read_text())
    cf["num_hidden_layers"] = 8
    (bench / "configs/dummy-l8.json").write_text(json.dumps(cf))
    man["configs"].append({"name": "dummy-l8", "source": cf["source"],
                           "file": "benchmark/configs/dummy-l8.json",
                           "reduced": ["num_hidden_layers"], "why": "dummy"})
    # a traffic generator: a file of its own, named by the mixes that use it
    (bench / "generators/dummy_pairs.py").write_text(
        "from benchmark.traffic import Request\n"
        "def generate(mix, rate_rps, seconds, seed, vocab):\n"
        "    return [Request(i, i / rate_rps, -1, [seed % vocab] * mix['len'],"
        " 0, 2)\n            for i in range(int(rate_rps * seconds))]\n"
        "def warm_prompts(mix, seed, vocab):\n    return []\n")
    (bench / "traffic/dummy-pairs.json").write_text(
        json.dumps({"generator": "dummy_pairs", "len": 5}))
    from benchmark import traffic
    pairs = traffic.generate({"generator": "dummy_pairs", "len": 5}, 2.0, 3,
                             7, 100)
    assert [(r.due_s, r.prompt) for r in pairs[:2]] == [
        (0.0, [7] * 5), (0.5, [7] * 5)] and len(pairs) == 6
    # a traffic mix and a cell: data files
    mix = json.loads((bench / "traffic/chat-steady.json").read_text())
    mix["arrivals"] = {"process": "onoff", "burst_requests": 8, "gap_s": 2.0}
    (bench / "traffic/dummy-burst.json").write_text(json.dumps(mix))
    cell = json.loads((bench / "workloads/mistral-7b.chat-steady.json")
                      .read_text())
    cell["rate_rps"] = 9.0
    (bench / "workloads/dummy.burst.json").write_text(json.dumps(cell))
    man["workloads"].append({"name": "dummy.burst", "config": "dummy-l8",
                             "traffic": "dummy-burst", "chips": 1,
                             "why": "dummy"})
    # a per-layer metric: a reader of its own
    (bench / "layer_metrics/dummy_steps.py").write_text(
        "def read(run):\n    return len(run['replica']['steps']) or None\n")
    man["per_layer"].append({"name": "dummy_steps", "unit": "steps",
                             "better": "higher", "source": "program_counter",
                             "layer": "serve engine", "moves": "serve_tok_s",
                             "workloads": ["dummy.burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(man))

    man = manifest.load_manifest()
    loaded = manifest.load_cell(man, "dummy.burst")
    assert loaded["config_file"]["num_hidden_layers"] == 8
    assert loaded["traffic_file"]["arrivals"]["process"] == "onoff"
    assert loaded["rate_rps"] == 9.0
    names = [m["name"] for m in manifest.metrics_of(man, "per_layer",
                                                    "dummy.burst")]
    assert "dummy_steps" in names and "engine_step_ms" in names
    assert "prefix_hit_token_pct" not in names       # lists other cells
    assert "dummy_steps" not in [
        m["name"] for m in manifest.metrics_of(man, "per_layer",
                                               "mistral-7b.chat-steady")]
    # the harness reads the new metric with no edit to any file it had
    outcome = {"correct": True, "attempted": 3, "failed": 0, "trace": None,
               "facts": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                         "memory_peak_bytes": 1},
               "run": {"replica": {"steps": [(0, 1, [])] * 3, "max_slots": 4,
                                   "engine_ttft": {}},
                       "window": (0, 2), "clients": [], "trace": None,
                       "cell": loaded}}
    line = run.result_line(man, "dummy.burst", 1, outcome)
    assert line["metrics"]["dummy_steps"] == {"value": 3.0, "unit": "steps"}
    assert "decode_step_device_ms" not in line["metrics"]   # nothing to read
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
