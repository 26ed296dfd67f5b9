"""The table of serve layouts (``models/layouts.py``): what an engine built
on each layout keeps, what ``init_params`` draws for it and what a served
step counts are what they were before the table (PR 49: every value below was
read off the parent commit, where the step, the cache and the engine each
tested the configuration for themselves), and a layout that runs on the paged
serve step only is refused everywhere else by its own name."""

import functools
import hashlib
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu import models
from ray_tpu.devtools.step_text import SERVE_PRESETS
from ray_tpu.models import layouts
from ray_tpu.serve.llm import LLMEngine

KV, KV2 = (16, 4, 2, 16), (16, 4, 32)      # a toy pool: heads apart, 2-D
#: layout, table width, window-table width, bytes of a slot's state, cache
#: leaves, what ``kv_state()["kv_pools"]`` names, parameter leaves and the
#: digest of :func:`_listing`
PARENT = {
    "llama-debug": ("uniform", 8, 0, 0, {"k": (2, *KV), "v": (2, *KV)},
                    [], 12, "6de36a2191b202a9"),
    "mistral-debug": ("uniform", 8, 0, 0, {"k": (2, *KV), "v": (2, *KV)},
                      [], 12, "6de36a2191b202a9"),
    "sparse-moe-debug": ("uniform", 8, 0, 0,
                         {"k": (4, *KV), "v": (4, *KV), "ki": (4, 16, 4, 16)},
                         [], 20, "7bfdb326aff51631"),
    "hybrid-state-debug": (
        "hybrid", 12, 4, 10752,
        {"k": (1, *KV2), "v": (1, *KV2), "wk": (2, 8, 4, 32),
         "wv": (2, 8, 4, 32), "conv": (3, 2, 3, 128), "ssm": (3, 2, 4, 128)},
        ["full", "state", "window"], 100, "ffc4fb5f9dc58a20"),
    "parallel-hybrid-debug": (
        "parallel", 8, 0, 5376,
        {"k": (3, *KV), "v": (3, *KV), "conv": (3, 2, 3, 64),
         "ssm": (3, 2, 4, 8, 8)},
        ["full", "state"], 22, "1529ceee6c5f744a"),
    # (PR 50: the sixth layout; no parent, read off its own first run)
    "linear-hybrid-debug": (
        "linear_hybrid", 8, 0, 72192,
        {"k": (2, 16, 4, 16, 16), "v": (2, 16, 4, 16, 16),
         "conv": (6, 2, 960), "delta": (6, 2, 2, 8, 128)},
        ["full", "state"], 27, "8c925cf631f19d1f"),
    "latent-moe-debug": ("latent", 8, 0, 0, {"kv": (3, 16, 4, 128)},
                         [], 34, "3a9531cb99e6b8f0"),
    "windowed-moe-debug": (
        "windowed_moe", 12, 4, 0,
        {"k": (1, *KV), "v": (1, *KV), "wk": (4, 8, 4, 2, 16),
         "wv": (4, 8, 4, 2, 16)},
        ["full", "window"], 36, "1a9d6440d520665b"),
    # (PR 53: two keys on the windowed MoE layout, no parent: read off its
    # own first run. Eight layers, full / sliding x 3 twice: two full
    # layers' pool, six window layers')
    "smallthinker-debug": (
        "windowed_moe", 12, 4, 0,
        {"k": (2, *KV), "v": (2, *KV), "wk": (6, 8, 4, 2, 16),
         "wv": (6, 8, 4, 2, 16)},
        ["full", "window"], 13, "225d2b921db14b44"),
}
#: every key of a fresh engine's ``stats`` on the parent (the same for every
#: layout: a counter is zero where the layout has no such thing)
PARENT_STATS = """adopted attn_blocks_live attn_blocks_table attn_impl
attn_keys_live attn_keys_selected deadline_drops exported first_tokens
latent_kernel_rows latent_rows_attended latent_tokens_read max_concurrent
migrated_out moe_expert_tokens_max moe_expert_tokens_sum moe_experts_hit
moe_kernel_pairs moe_pairs_held moe_pairs_routed pending_wait_s prefill_s
prefill_steps prefix_hit_tokens requests requests_admitted
requests_waited_window_blocks rows_run_past_end shared_kv_kernel_rows
shared_kv_keys_read shared_kv_rows_attended ssd_kernel_rows
ssd_positions_real ssd_positions_run ssd_rows_stepped state_slots_live
step_host_s step_positions_real step_positions_run step_s_chunk
step_s_decode_only step_s_full_width step_s_second_width steps steps_chunk
steps_decode_only steps_dispatched_ahead steps_full_width steps_second_width
tokens_generated window_blocks_full_table window_blocks_held
window_blocks_released window_blocks_wait_s window_keys_read""".split()
#: the keys PR 50 added for every layout: the delta rule's counts of a step
#: and what an engine that keeps snapshots of recurrent state counts
SINCE = {"delta_positions_real", "delta_positions_run", "delta_rows_stepped",
         "delta_rows_blocked", "state_snapshots_taken",
         "state_snapshots_restored", "state_snapshots_evicted",
         "state_snapshot_bytes", "state_restore_s"}
#: ... and PR 51: the rows a step's attention attended, and those of them
#: that fed one token to a kernel that gives such a row a tile of its own
SINCE |= {"attn_rows_attended", "attn_token_tile_rows"}
#: ... and PR 52: the rows the sparse-attention indexer scored, and those of
#: them the kernel that reads the key pool through the table scored
SINCE |= {"indexer_rows_scored", "indexer_kernel_rows"}
KV_STATE = ["admission", "block_size", "inflight", "kv_claimable", "kv_free",
            "kv_total", "kv_used", "max_slots", "prefix", "prefix_digest",
            "queued", "role"]
#: what three prompts (9, 5 and 13 tokens, six new each) on the toy engine
#: count, beside what every layout counts of them (``EVERY``)
EVERY = {"attn_blocks_live": 70, "attn_keys_live": 207,
         "step_positions_real": 42, "tokens_generated": 18,
         # (a row a step; no token tile where the ``jax.numpy`` form runs)
         "attn_rows_attended": 24}
WINDOWS = {"window_blocks_held": 58, "window_blocks_full_table": 98,
           "window_blocks_released": 12, "shared_kv_rows_attended": 24,
           "attn_blocks_table": 288, "steps": 17}
PARENT_COUNTS = {
    "llama-debug": {},
    "mistral-debug": {},
    "sparse-moe-debug": {"attn_keys_selected": 204, "moe_pairs_routed": 336,
                         "moe_pairs_held": 336,
                         # (the 13-token prompt's last two steps pass the
                         # 16 keys of ``index_topk``)
                         "indexer_rows_scored": 2},
    "hybrid-state-debug": {**WINDOWS, "state_slots_live": 24,
                           "shared_kv_keys_read": 741,
                           "window_keys_read": 354},
    "parallel-hybrid-debug": {"state_slots_live": 24,
                              "ssd_positions_real": 42,
                              "ssd_positions_run": 42,
                              "ssd_rows_stepped": 18},
    "linear-hybrid-debug": {"state_slots_live": 24,
                            "delta_positions_real": 42,
                            "delta_positions_run": 42,
                            "delta_rows_stepped": 18,
                            "delta_rows_blocked": 6},
    "latent-moe-debug": {"moe_pairs_routed": 336, "moe_pairs_held": 88,
                         "latent_tokens_read": 247,
                         "latent_rows_attended": 24},
    "windowed-moe-debug": {**WINDOWS, "moe_pairs_routed": 672,
                           "moe_pairs_held": 150, "shared_kv_keys_read": 247,
                           "window_keys_read": 708},
    # (every expert held: 42 positions x 3 x 8)
    "smallthinker-debug": {**WINDOWS, "moe_pairs_routed": 1008,
                           "moe_pairs_held": 1008,
                           "shared_kv_keys_read": 494,
                           "window_keys_read": 1062},
}
SERVE_ONLY = {"hybrid-state-debug": "SambaY hybrid state-space / attention",
              "parallel-hybrid-debug": "parallel attention / Mamba-2 layout",
              "linear-hybrid-debug": "linear hybrid layout",
              "latent-moe-debug": r"latent attention \(kv_lora_rank\)",
              "windowed-moe-debug": "windowed MoE layout",
              "smallthinker-debug": "windowed MoE layout"}


@functools.lru_cache(maxsize=None)
def _engine(preset: str) -> LLMEngine:
    """ONE engine a preset, shared by the cases below."""
    return LLMEngine(models.get_config(preset), max_slots=2, max_len=32,
                     block_size=4, prefill_chunk=4)


def _listing(params) -> str:
    """A line a leaf: its path, type, shape and the CRC of its bytes."""
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    return "\n".join(
        f"{jax.tree_util.keystr(path)} {np.asarray(a).dtype} "
        f"{tuple(a.shape)} {zlib.crc32(np.asarray(a).tobytes()):08x}"
        for path, a in leaves)


@pytest.mark.parametrize("preset", SERVE_PRESETS)
def test_an_engine_of_each_layout_keeps_what_the_parents_kept(preset):
    name, width, win_width, state_bytes, cache, pools, _, _ = PARENT[preset]
    eng = _engine(preset)
    assert eng._layout is models.layout_of(eng.config)
    assert eng._layout.name == name
    assert (eng._tbl_width, eng._win_width, eng._state_bytes) \
        == (width, win_width, state_bytes)
    assert {k: tuple(v.shape) for k, v in eng._cache.items()} == cache
    assert {k: str(v.dtype) for k, v in eng._cache.items()} == {
        k: "float32" if k in ("conv", "ssm", "delta") else "bfloat16"
        for k in cache}
    assert eng.stats["attn_impl"] == "xla"
    state = eng.kv_state()
    assert sorted(state) == sorted(KV_STATE + ["kv_pools"] * bool(pools))
    assert sorted(state.get("kv_pools", {})) == pools
    # every key the parent's ``stats`` had; the new ones are the forms of the
    # layout's other kernels, beside ``attn_impl``
    assert sorted(set(eng.stats) - {"ssd_impl", "expert_impl", "indexer_impl"}
                  - SINCE) == PARENT_STATS
    assert SINCE <= set(eng.stats)
    assert ("ssd_impl" in eng.stats) == (name == "parallel")
    assert ("indexer_impl" in eng.stats) == bool(eng.config.index_heads)
    assert ("expert_impl" in eng.stats) == bool(eng.config.num_experts)
    assert (eng._stateful, bool(eng._by_kind)) \
        == ("state" in pools, bool(pools))
    # snapshots of recurrent state: the layout's to say, the engine's pool
    assert eng._snapshots == bool(eng._layout.snapshots) \
        == (name == "linear_hybrid")
    assert sorted(eng._snaps) == sorted(
        eng._layout.state_leaves if eng._snapshots else [])
    assert "snapshots" in state["prefix"] if eng._snapshots \
        else "snapshots" not in state["prefix"]


@pytest.mark.parametrize("preset", SERVE_PRESETS)
def test_init_params_draws_the_parents_leaves_to_the_bit(preset):
    *_, n_leaves, digest = PARENT[preset]
    config = models.get_config(preset)
    params = models.init_params(jax.random.PRNGKey(0), config)
    listing = _listing(params)
    assert len(jax.tree.leaves(params)) == n_leaves
    assert hashlib.sha256(listing.encode()).hexdigest()[:16] == digest, \
        listing
    is_axes = lambda x: isinstance(x, tuple)
    assert jax.tree.structure(models.param_axes(config), is_leaf=is_axes) \
        == jax.tree.structure(params)


@pytest.mark.parametrize("preset", SERVE_PRESETS)
def test_a_served_step_counts_what_the_parents_counted(preset):
    eng = _engine(preset)
    before = dict(eng.stats)
    rng = np.random.default_rng(0)
    for n in (9, 5, 13):
        eng.submit(rng.integers(1, eng.config.vocab_size, n).tolist(), 6,
                   lambda item: None)
    while eng.step():
        pass
    want = {"attn_blocks_table": 192, "attn_keys_selected": 207, "steps": 16,
            **EVERY, **PARENT_COUNTS[preset]}
    grew = {k: eng.stats[k] - before[k]
            for k in layouts.COUNTERS + tuple(want)}
    assert grew == {**dict.fromkeys(layouts.COUNTERS, 0), **want}
    # a layout's count returns the names it declares and no other
    assert set(k for k in layouts.COUNTERS if grew[k]) \
        <= set(eng._layout.counters)


@pytest.mark.parametrize("where", ["forward", "decode_step", "generate"])
@pytest.mark.parametrize("preset", sorted(SERVE_ONLY))
def test_a_serve_only_layout_is_refused_by_its_own_name(preset, where):
    config = models.get_config(preset)
    params = jax.eval_shape(
        lambda: models.init_params(jax.random.PRNGKey(0), config))
    tokens = jnp.zeros((1, 4), jnp.int32)
    calls = {
        "forward": lambda: models.forward(params, tokens, config),
        "decode_step": lambda: models.decode_step(
            params, {"pos": jnp.zeros((), jnp.int32)}, tokens, config),
        "generate": lambda: models.generate(params, tokens, config,
                                            max_new_tokens=2)}
    with pytest.raises(NotImplementedError,
                       match=SERVE_ONLY[preset] + ".*paged serve step only"):
        calls[where]()


def test_a_layout_refuses_a_pool_it_does_not_have():
    """One ``init_cache`` signature: an argument the layout has no pool for
    is refused in the engine's old words, and so is the lack of one."""
    llama, hybrid = (models.get_config(n)
                     for n in ("llama-debug", "hybrid-state-debug"))
    with pytest.raises(ValueError, match="has no window pool"):
        models.init_cache_paged(llama, 8, 4, window_blocks=4)
    with pytest.raises(ValueError, match="has no window pool"):
        LLMEngine(llama, max_slots=2, max_len=32, window_blocks=4)
    with pytest.raises(ValueError, match="has no recurrent state"):
        models.init_cache_paged(llama, 8, 4, state_slots=2)
    with pytest.raises(ValueError, match="needs window_blocks"):
        models.init_cache_paged(hybrid, 8, 4, state_slots=2)
    with pytest.raises(ValueError, match="needs state_slots"):
        models.init_cache_paged(hybrid, 8, 4, window_blocks=4)


def test_the_table_is_closed_and_its_names_are_the_engines():
    """Seven rows for six layouts (the windowed MoE layout with and without
    its window pool), immutable, and every counter a row declares is a key
    of an engine's ``stats`` with a metric of its name."""
    from ray_tpu.util import metric_defs

    assert [r.name for r in layouts.LAYOUTS] == [
        "uniform", "hybrid", "parallel", "linear_hybrid", "latent",
        "windowed_moe", "windowed_moe"]
    with pytest.raises(Exception):
        layouts.UNIFORM.pool_leaf = "kv"
    stats = _engine("llama-debug").stats
    for row in layouts.LAYOUTS:
        assert set(row.counters) <= set(layouts.COUNTERS) <= set(stats)
        assert row.shareable == (row.no_ship is None)
        assert row.window_pool == (row.table_width is not None)
        # a snapshot is a copy of state by slot, and ships nothing
        assert not row.snapshots or (row.stateful and not row.shareable)
    for name in layouts.COUNTERS:
        assert metric_defs.get(f"rtpu_serve_{name}_total") is not None
    c = models.get_config("windowed-moe-debug")
    masks = c.replace(attn_windows=None, rope_layers="all")
    assert models.layout_of(c) is layouts.WINDOWED_MOE_POOLS
    assert models.layout_of(masks) is layouts.WINDOWED_MOE
    # SmallThinker's layer is two keys on that row, not a row of its own
    assert models.layout_of(models.get_config("smallthinker-debug")) \
        is layouts.WINDOWED_MOE_POOLS


def test_the_layers_of_a_period_that_starts_full_run_in_four_runs():
    """Eight layers, full / sliding x 3 twice, one segment: a run for each
    stretch that reads one pool kind, each with its place in its pool."""
    from ray_tpu.models import transformer

    c = models.get_config("smallthinker-debug")
    assert [tuple(r) for r in transformer._layer_runs(c)] == [
        ("moe", 0, 1, 0, False, 0, False), ("moe", 1, 3, 1, True, 0, True),
        ("moe", 4, 1, 4, False, 1, False), ("moe", 5, 3, 5, True, 3, True)]
    assert models.windowed_moe.pool_layers(c) == (6, 2)
    assert models.windowed_moe.segments(c) == [("moe", 8)]


#: prefix-hit tokens of a 13-token prompt served a second time (blocks of 4:
#: three whole blocks under the cap of ``len - 1``): the layouts whose block
#: is a prefix's whole state hit as they did before snapshots existed, the
#: layout that keeps snapshots lands on the one at its prompt's last
#: boundary, and the others still take none
SECOND_SERVE_HITS = {
    "llama-debug": 12, "mistral-debug": 12, "sparse-moe-debug": 12,
    "latent-moe-debug": 12, "linear-hybrid-debug": 12,
    "hybrid-state-debug": 0, "parallel-hybrid-debug": 0,
    "windowed-moe-debug": 0, "smallthinker-debug": 0}


@pytest.mark.parametrize("preset", SERVE_PRESETS)
def test_a_second_serve_hits_what_its_layout_lets_it(preset):
    eng = LLMEngine(models.get_config(preset), max_slots=2, max_len=32,
                    block_size=4, prefill_chunk=4)
    prompt = np.random.default_rng(1).integers(
        1, eng.config.vocab_size, 13).tolist()
    serves = []
    for _ in range(2):
        before, toks = eng.stats["prefix_hit_tokens"], []
        eng.submit(prompt, 4, toks.append)
        while eng.step():
            pass
        serves.append((toks, eng.stats["prefix_hit_tokens"] - before))
    (cold, hit0), (warm, hit1) = serves
    assert (hit0, hit1) == (0, SECOND_SERVE_HITS[preset])
    assert cold == warm and cold[-1] is None and len(cold) == 5
    restored = eng.stats["state_snapshots_restored"]
    assert restored == (1 if eng._layout.snapshots else 0)
    state = eng.kv_state()
    assert state["kv_free"] + state["prefix"]["nodes"] == state["kv_total"] \
        or eng.win_pool is not None
