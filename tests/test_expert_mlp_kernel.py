"""``ops.expert_mlp``: the Pallas kernel that multiplies the serve step's routed
(token, expert) pairs by their experts, walking the experts HIT and their own
rows (interpret mode, through ``attn_pallas_interpret``), against the three
``lax.ragged_dot`` calls ``ops.moe.moe_layer_dropless`` makes elsewhere, on
the same inputs at small sizes on the CPU whose ``D`` is whole ``[8, 128]``
tiles so that the kernel's form is the one tested. Another layer's experts
hold NaN; so does a row the kernel's own call may not read (through the
layer a padding row's NaN would reach its router weights in either form, so
there padding is merely loud)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest
from ray_tpu import models
from ray_tpu.ops import expert_mlp as kern
from ray_tpu.ops.attention import set_default_attention_impl
from ray_tpu.ops.moe import moe_layer_dropless
from ray_tpu.serve.llm import LLMEngine

BF16 = jnp.bfloat16


@pytest.fixture
def kernel(monkeypatch):
    """The kernel form, interpreted: what a TPU backend selects."""
    monkeypatch.setenv("RTPU_ATTN_PALLAS_INTERPRET", "1")
    set_default_attention_impl("pallas")
    yield
    set_default_attention_impl(None)


def _weights(seed, e, d, f, layers=None):
    """Expert matrices ``[E, D, F]`` / ``[E, F, D]``, or stacks of ``layers``
    of them."""
    rng = np.random.default_rng(seed)
    lead = () if layers is None else (layers,)
    return [jnp.asarray(rng.normal(size=lead + shape) * fan ** -0.5, BF16)
            for shape, fan in (((e, d, f), d), ((e, d, f), d), ((e, f, d), f))]


def _towards(router, experts, seed, noise=0.25):
    """Rows the router sends to ``experts`` (its first choice): the
    expert's own column and a little noise."""
    rng = np.random.default_rng(seed)
    d = router.shape[0]
    x = np.asarray(router).T[np.asarray(experts)] * (4.0 / d ** 0.5) \
        + rng.normal(size=(len(experts), d)) * noise
    return jnp.asarray(x, BF16)


def _both(kernel_on, *args, **kw):
    """The layer traced afresh in the form ``kernel_on`` selects."""
    set_default_attention_impl("pallas" if kernel_on else "xla")
    out, counts = jax.jit(lambda *a: moe_layer_dropless(*a, **kw))(*args)
    return np.asarray(out, np.float32), np.asarray(counts)


def _agree(got, want):
    scale = np.abs(want).max()
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2 * scale)


#: rows sent to each of four experts: groups that are empty, of one row, of
#: one tile to the row, of more than a tile, of more than two
GROUPS = {
    "empty_groups": (0, 5, 0, 3),
    "a_group_of_one_row": (4, 1, 0, 2),
    "a_full_tile": (128, 0, 3, 0),
    "wider_than_a_tile": (0, 150, 2, 0),
    "three_tiles_then_one_row": (0, 0, 300, 1),
    "no_row_at_all": (0, 0, 0, 0),
}


@pytest.mark.parametrize("groups", GROUPS.values(), ids=GROUPS.keys())
def test_the_kernel_against_the_ragged_dot_form_by_group_sizes(kernel,
                                                                groups):
    d, f, e = 1024, 256, 4
    router = jnp.asarray(np.random.default_rng(1).normal(size=(d, e)),
                         jnp.float32)
    sent = np.repeat(np.arange(e), groups)
    np.random.default_rng(2).shuffle(sent)
    # two padding rows besides: routed nowhere
    x = jnp.concatenate([_towards(router, sent, 3),
                         jnp.full((2, d), 50.0, BF16)])
    valid = jnp.arange(x.shape[0]) < len(sent)
    ws = _weights(4, e, d, f)
    got, counts = _both(True, x, router, *ws, k=1, valid=valid)
    want, _ = _both(False, x, router, *ws, k=1, valid=valid)
    assert counts.tolist() == list(groups)
    _agree(got, want)
    assert not got[len(sent):].any()


#: rows sent to each of four experts of ``[2560, 768]``: uneven groups, an
#: empty expert, a group past 128 rows (a second tile of its own expert)
WIDE_ROW_GROUPS = (37, 0, 150, 1)


@pytest.mark.parametrize("act", ["silu", "relu"])
def test_a_row_of_whole_lanes_that_is_not_whole_tiles(kernel, act):
    """``D`` 2560 (SmallThinker's: two and a half ``[8, 128]`` tiles a slab)
    and ``F`` 768 against the ``ragged_dot`` form, both activations: the row
    travels padded to ``[8, 384]``, the operand is cut back at 2560, a
    ``down`` row's padding is never read. ``top-2`` so that a token's pairs
    land in two experts' tiles."""
    d, f, e = 2560, 768, 4
    assert kern.slab_width(d) == 384 and kern.slab_width(2048) == 256
    assert kern.f_tile(d, f) == 768
    router = jnp.asarray(np.random.default_rng(1).normal(size=(d, e)),
                         jnp.float32)
    sent = np.repeat(np.arange(e), WIDE_ROW_GROUPS)
    np.random.default_rng(2).shuffle(sent)
    x = jnp.concatenate([_towards(router, sent, 3),
                         jnp.full((2, d), 50.0, BF16)])
    valid = jnp.arange(x.shape[0]) < len(sent)
    ws = _weights(4, e, d, f)
    kw = dict(k=2, norm_topk=True, valid=valid, act=act)
    got, counts = _both(True, x, router, *ws, **kw)
    want, want_counts = _both(False, x, router, *ws, **kw)
    assert counts.tolist() == want_counts.tolist()
    assert counts.sum() == 2 * len(sent) and counts.max() > 128
    _agree(got, want)
    assert not got[len(sent):].any()


def test_the_activation_is_the_one_named(kernel):
    """ReLU and SiLU differ, in both forms alike, and an unknown name is
    refused where the program is traced."""
    d, f, e = 1024, 256, 4
    rng = np.random.default_rng(7)
    router = jnp.asarray(rng.normal(size=(d, e)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(12, d)), BF16)
    ws = _weights(8, e, d, f)
    out = {(form, act): _both(form, x, router, *ws, k=2, act=act)[0]
           for form in (True, False) for act in ("silu", "relu")}
    for act in ("silu", "relu"):
        _agree(out[True, act], out[False, act])
    assert np.abs(out[False, "silu"] - out[False, "relu"]).max() \
        > 0.05 * np.abs(out[False, "relu"]).max()
    with pytest.raises(KeyError):
        _both(False, x, router, *ws, k=2, act="gelu")


#: (D, F, bytes a weight tile may have): the three cells' classes at small
#: sizes. D = F in several tiles of F (trinity), D over F in several
#: (kimi), D over F in one tile (keye)
TILES = {"d_equals_f_in_tiles": (1024, 1024, 1024 * 256 * 2, 4),
         "d_over_f_in_tiles": (2048, 256, 2048 * 128 * 2, 2),
         "d_over_f_in_one_tile": (1024, 384, kern.WEIGHT_TILE_BYTES, 1)}


@pytest.mark.parametrize("d, f, tile_bytes, n_f", TILES.values(),
                         ids=TILES.keys())
def test_the_kernel_by_tile_class_under_layer_first_and_valid(
        kernel, monkeypatch, d, f, tile_bytes, n_f):
    """``layer=`` stacks whose other layers are NaN for the kernel (XLA's
    grouped matmul on the CPU multiplies an empty group's weights by zero,
    so its stacks are finite), ``first=`` with half the router's experts
    held elsewhere (those pairs sort last), ``valid`` padding: all at once,
    as the serve step calls it."""
    monkeypatch.setattr(kern, "WEIGHT_TILE_BYTES", tile_bytes)
    assert f // kern.f_tile(d, f) == n_f
    t, k, e, e_all, first, layers, layer = 40, 2, 4, 8, 2, 3, 1
    rng = np.random.default_rng(d + f)
    router = jnp.asarray(rng.normal(size=(d, e_all)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(t, d)), BF16).at[33:].set(50.0)
    valid = jnp.arange(t) < 33
    ws = _weights(5, e, d, f, layers)
    kw = dict(k=k, norm_topk=True, valid=valid, layer=jnp.int32(layer),
              scoring="sigmoid", first=first, scale=2.5)
    others = (jnp.arange(layers) != layer)[:, None, None, None]
    got, counts = _both(True, x, router,
                        *(jnp.where(others, jnp.nan, w) for w in ws), **kw)
    want, want_counts = _both(False, x, router, *ws, **kw)
    assert counts.tolist() == want_counts.tolist()
    assert 0 < counts.sum() < 33 * k          # some pairs held, some not
    _agree(got, want)


OTHERS = (0, 7, 500)
PAIRS = (1024, 2048, 8192)


@pytest.fixture(scope="module")
def alone():
    """Three rows' outputs with nobody beside them, a pair count each."""
    return {}


@pytest.mark.parametrize("pairs", PAIRS)
@pytest.mark.parametrize("others", OTHERS)
def test_a_rows_output_is_bit_equal_beside_other_rows_and_at_every_width(
        kernel, alone, others, pairs):
    """Rows 0-2 of the step get the same bits whatever else the step holds
    (0, 7 or 500 further real rows, which share their experts and push them
    into other tiles) and however many pairs the step is wide."""
    d, f, e, k = 1024, 128, 4, 4
    t = pairs // k
    rng = np.random.default_rng(7)
    router = jnp.asarray(rng.normal(size=(d, 2 * e)), jnp.float32)
    ws = _weights(8, e, d, f)
    mine = jnp.asarray(rng.normal(size=(3, d)), BF16)
    rest = jnp.asarray(np.random.default_rng(others).normal(size=(t - 3, d)),
                       BF16)
    x = jnp.concatenate([mine, rest])
    valid = jnp.arange(t) < 3 + others
    got, counts = _both(True, x, router, *ws, k=k, valid=valid, first=e)
    assert counts.sum() > 0
    assert np.isfinite(got).all() and got[:3].any()
    want = alone.setdefault("rows", got[:3])
    assert np.array_equal(got[:3], want)


def test_rows_past_the_last_group_are_left_unread_and_unwritten(kernel):
    """The kernel's own call: pairs past ``sum(counts)`` in the order are
    tokens of NaN; what it returns holds the routed pairs' rows at their
    pair index and they are finite."""
    t, d, f, e, k = 16, 1024, 128, 3, 2
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=(t, d)), BF16).at[8:].set(jnp.nan)
    ws = _weights(10, e, d, f)
    # tokens 0-7: their first pair to expert t % 3, the second held elsewhere
    flat_e = np.full((t, k), e)
    flat_e[:8, 0] = np.arange(8) % e
    flat_e = jnp.asarray(flat_e.reshape(-1))
    order = jnp.argsort(flat_e, stable=True)
    counts = jnp.zeros((e + 1,), jnp.int32).at[flat_e].add(1)[:e]
    out = np.asarray(kern.expert_mlp_pairs(x, order, counts, 0, *ws, k=k,
                                           interpret=True))
    routed = np.asarray(flat_e) < e
    assert np.isfinite(out[routed]).all() and out[routed].any()
    xs = np.asarray(x, np.float32)
    for pair in np.flatnonzero(routed):
        g, u, dn = (np.asarray(w[int(flat_e[pair])], np.float32) for w in ws)
        gate, up = xs[pair // k] @ g, xs[pair // k] @ u
        mid = np.asarray(jnp.asarray(gate / (1 + np.exp(-gate)) * up, BF16),
                         np.float32)
        np.testing.assert_allclose(out[pair], mid @ dn, rtol=2e-2, atol=2e-2)


TABLES = {
    # counts, tiles of (group, first row, rows)
    "an_empty_group_between": ((3, 0, 5), [(0, 0, 3), (2, 3, 4), (2, 7, 1)]),
    "a_group_of_two_tiles_and_a_row": ((0, 9, 1), [(1, 0, 4), (1, 4, 4),
                                                   (1, 8, 1), (2, 9, 1)]),
    "whole_tiles": ((4, 8, 0), [(0, 0, 4), (1, 4, 4), (1, 8, 4)]),
    "nothing": ((0, 0, 0), []),
}


@pytest.mark.parametrize("counts, want", TABLES.values(), ids=TABLES.keys())
def test_the_tile_tables(counts, want):
    group, start, rows, n = (np.asarray(a) for a in kern.tile_tables(
        jnp.asarray(counts, jnp.int32), 6, rows=4))
    assert n == len(want)
    assert list(zip(group[:n], start[:n], rows[:n])) == want
    # past the last tile: no rows, and the group the last tile left
    assert not rows[n:].any()
    assert (group[n:] == (want[-1][0] if want else 0)).all()


RULE = {
    "cpu": (None, "bfloat16", 3072, 3072, "jnp"),
    "tpu_trinity": ("pallas", "bfloat16", 3072, 3072, "pallas"),
    "tpu_kimi": ("pallas", "bfloat16", 7168, 2048, "pallas"),
    "tpu_keye": ("pallas", "bfloat16", 2048, 768, "pallas"),
    "float32_weights": ("pallas", "float32", 2048, 768, "jnp"),
    "tpu_smallthinker": ("pallas", "bfloat16", 2560, 768, "pallas"),
    "d_whole_lanes_not_whole_tiles": ("pallas", "bfloat16", 1536, 768,
                                      "pallas"),
    "d_not_whole_lanes": ("pallas", "bfloat16", 1600, 768, "jnp"),
    "f_not_whole_lanes": ("pallas", "bfloat16", 2048, 192, "jnp"),
    "xla_asked_for": ("xla", "bfloat16", 3072, 3072, "jnp"),
}


@pytest.mark.parametrize("impl, dtype, d, f, want", RULE.values(),
                         ids=RULE.keys())
def test_the_rule_that_engages_the_kernel(impl, dtype, d, f, want):
    set_default_attention_impl(impl)
    try:
        assert kern.expert_mlp_impl(dtype, d, f) == want
    finally:
        set_default_attention_impl(None)


@pytest.mark.parametrize("d, f, want", [(3072, 3072, 512), (7168, 2048, 256),
                                        (2048, 768, 768), (1024, 128, 128)])
def test_a_weight_tile_follows_the_static_shapes_alone(d, f, want):
    assert kern.f_tile(d, f) == want


# -- through the engine ---------------------------------------------------------

@pytest.fixture(scope="module")
def served():
    """form -> (the engine's counters, the first request's tokens): the toy
    windowed MoE layout (4 of 16 experts held, top-4) at a width the kernel
    takes, an 11-token prompt (chunks of 8 and 3) answered with 4 tokens
    beside a one-token prompt answered with 2."""
    config = models.get_config("windowed-moe-debug").replace(
        d_model=1024, d_ff_expert=128, dtype="bfloat16",
        param_dtype="bfloat16")
    params = models.init_params(jax.random.PRNGKey(0), config)
    prompt = np.random.default_rng(8).integers(0, 256, 11).tolist()
    out = {}
    for form in ("kernel", "jax.numpy"):
        with pytest.MonkeyPatch.context() as patch:
            if form == "kernel":
                patch.setenv("RTPU_ATTN_PALLAS_INTERPRET", "1")
                set_default_attention_impl("pallas")
            try:
                eng = LLMEngine(config, params, max_slots=2, max_len=64,
                                block_size=4, prefill_chunk=8)
                tokens = []
                eng.submit(prompt, 4, tokens.append)
                eng.submit(prompt[:1], 2, lambda item: None)
                while eng.step():
                    pass
            finally:
                set_default_attention_impl(None)
        out[form] = (dict(eng.stats), [t for t in tokens
                                       if isinstance(t, int)])
    return out


@pytest.mark.parametrize("form", ["kernel", "jax.numpy"])
def test_the_engine_counts_the_pairs_the_kernel_multiplied(served, form):
    """``moe_kernel_pairs`` over ``moe_pairs_held``: every held pair on the
    kernel's form, none on the ``jax.numpy`` form."""
    s, tokens = served[form]
    # 16 real positions x top-4 x 4 expert layers
    assert s["moe_pairs_routed"] == (11 + 3 + 2) * 4 * 4
    assert 0 < s["moe_pairs_held"] < s["moe_pairs_routed"]
    assert s["moe_kernel_pairs"] == (s["moe_pairs_held"]
                                     if form == "kernel" else 0)
    assert len(tokens) == 4


def test_both_forms_hold_the_same_pairs(served):
    """The router is the same code on both forms: the same pairs are held
    as long as the tokens agree (the first steps, before a rounding can
    turn an argmax)."""
    kernel, plain = (served[f][0] for f in ("kernel", "jax.numpy"))
    assert kernel["moe_pairs_routed"] == plain["moe_pairs_routed"]
    assert served["kernel"][1][0] == served["jax.numpy"][1][0]


@pytest.mark.parametrize("start, end, want", [
    ({"moe_pairs_held": 3}, {"moe_pairs_held": 40}, None),
    ({"moe_pairs_held": 10, "moe_kernel_pairs": 10},
     {"moe_pairs_held": 50, "moe_kernel_pairs": 50}, 100.0),
    ({"moe_pairs_held": 2, "moe_kernel_pairs": 0},
     {"moe_pairs_held": 9, "moe_kernel_pairs": 0}, 0.0),
    ({"moe_pairs_held": 5, "moe_kernel_pairs": 5},               # idle
     {"moe_pairs_held": 5, "moe_kernel_pairs": 5}, None),
    ({}, {"moe_pairs_held": 8, "moe_kernel_pairs": 8}, 100.0),
], ids=["no_counter", "all_pairs", "no_pair", "idle_window", "no_start_mark"])
def test_the_kernel_pairs_reader(start, end, want):
    """``benchmark/layer_metrics/expert_kernel_pairs_pct.py`` over a window's
    two marks: nothing where the engine has no such counter, nothing where
    no pair was held."""
    reader = manifest.load_module(
        manifest.layer_metric_path("expert_kernel_pairs_pct"))
    run = {"marks": {"start": {"stats": start}, "end": {"stats": end}}}
    assert reader.read(run) == want
    assert reader.read({}) is None
