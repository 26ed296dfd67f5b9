"""``ops.ssd_step``: the Pallas kernel that takes the one-position rows' turn
of Mamba-2's recurrence in the state pool as it lies (interpret mode, through
``attn_pallas_interpret``) against ``ops.ssm.ssd_step_slots``, at small sizes on
the CPU whose states are whole lanes (``N`` = 128 or 256), so that the
kernel's form is the one tested: a pool of three layers' states, tiles of 8
heads and of 2, heads of 16 and of 8 channels."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest
from ray_tpu import models
from ray_tpu.ops import ssd_step as kern
from ray_tpu.ops.attention import set_default_attention_impl
from ray_tpu.ops.ssm import mamba2_rows, ssd_step_slots
from ray_tpu.serve.llm import LLMEngine

LAYERS = 3
#: (heads, channels a head, groups, states): a tile of 8 heads (two tiles a
#: row, one a group) | a tile of 2 (a group's two heads), 256 states
WIDTHS = {"tiles_of_8": (16, 16, 2, 128), "tiles_of_2": (4, 8, 2, 256)}
#: the layer whose rows the call takes: the pool's first, middle, last
FIRST = {"first_layer": 0, "middle_layer": 1, "last_layer": 2}
#: an idle row, a fresh single row (a one-token prompt in a used slot), a
#: carried single row, a block row, another carried single row
NVALID = (0, 1, 1, 7, 1)
FRESH = (False, True, False, True, False)


@contextlib.contextmanager
def _kernel_form(monkeypatch):
    """The kernel form, interpreted: what a TPU backend selects."""
    monkeypatch.setenv("RTPU_ATTN_PALLAS_INTERPRET", "1")
    set_default_attention_impl("pallas")
    try:
        yield
    finally:
        set_default_attention_impl(None)


@pytest.fixture
def kernel(monkeypatch):
    with _kernel_form(monkeypatch):
        yield


def _operands(widths, rows=len(NVALID), seed=0):
    """(pool of ``LAYERS`` layers, x, B, C, delta, a) as ``ssd_step`` takes
    them, a row a slot."""
    h, p, g, n = widths
    k = h // g
    rng = np.random.default_rng(seed)
    f32 = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    return (f32(LAYERS * rows, h, p, n), f32(rows, g, k, p), f32(rows, g, n),
            f32(rows, g, n), jax.nn.softplus(f32(rows, g, k) - 1.0),
            -jnp.exp(f32(g, k) * 0.5))


def _kernel(pool, layer, nvalid, fresh, x, bm, cm, delta, a):
    rows = x.shape[0]
    return kern.ssd_step_live(
        pool, jnp.int32(layer * rows), jnp.asarray(nvalid) == 1,
        jnp.asarray(fresh), x, bm, cm, delta, a)


def _wanted(pool, layer, nvalid, fresh, x, bm, cm, delta, a):
    """The same call on the ``jax.numpy`` form: one pass of ``ssd_step`` over
    the layer's slots."""
    g, k = a.shape
    return ssd_step_slots(
        pool, layer * x.shape[0], jnp.asarray(nvalid) == 1,
        jnp.asarray(fresh), x, bm, cm, delta, a, jnp.zeros((g, k)))


@pytest.mark.parametrize("layer", FIRST.values(), ids=FIRST.keys())
@pytest.mark.parametrize("widths", WIDTHS.values(), ids=WIDTHS.keys())
def test_the_kernel_takes_the_single_rows_turn_and_no_other_rows(
        kernel, widths, layer):
    """One step that holds an idle row, a fresh single row, a carried
    single row and a block row: the single rows' states and read-outs are
    ``ssd_step``'s (the fresh one's from zero whatever its slot held); the
    idle row, the block row and every other layer's rows keep their bytes."""
    pool, *ops = _operands(widths)
    rows = len(NVALID)
    new, y = jax.jit(_kernel, static_argnums=(1, 2, 3))(
        pool, layer, NVALID, FRESH, *ops)
    want, want_y = _wanted(pool, layer, NVALID, FRESH, *ops)
    lo = layer * rows
    assert np.allclose(new, want, rtol=1e-5, atol=1e-5)
    for r, n in enumerate(NVALID):
        if n == 1:
            assert np.allclose(y[r], want_y[r], rtol=1e-5, atol=1e-4)
        else:
            assert np.array_equal(new[lo + r], pool[lo + r])
    assert np.array_equal(new[:lo], pool[:lo])
    assert np.array_equal(new[lo + rows:], pool[lo + rows:])
    # the fresh single row: what a zeroed slot would have given, to the bit
    new0, y0 = jax.jit(_kernel, static_argnums=(1, 2, 3))(
        pool.at[lo + 1].set(0.0), layer, NVALID, (False,) * rows, *ops)
    assert np.array_equal(new0[lo + 1], new[lo + 1])
    assert np.array_equal(y0[1], y[1])


@pytest.mark.parametrize("nvalid", [(0, 0, 0, 0, 0), (0, 7, 0, 3, 0),
                                    (5, 5, 5, 5, 5)],
                         ids=["idle", "blocks_and_idle", "all_blocks"])
def test_a_step_with_no_single_row_leaves_the_pool_untouched(kernel, nvalid):
    """No row feeds one position: the kernel starts no copy (an index
    clamped onto some row would rewrite it, and the aliasing would hide
    it)."""
    pool, *ops = _operands(WIDTHS["tiles_of_8"])
    new, _ = jax.jit(_kernel, static_argnums=(1, 2, 3))(
        pool, 1, nvalid, (True,) * 5, *ops)
    assert np.array_equal(new, pool)


@pytest.mark.parametrize("companions", ["other_operands", "all_idle",
                                        "all_single", "moved_up_a_slot"])
def test_a_rows_turn_does_not_depend_on_its_companions(kernel, companions):
    """Row 2's state and read-out, bit for bit, whatever the other rows of
    the step feed, hold or are."""
    widths = WIDTHS["tiles_of_8"]
    pool, *ops = _operands(widths)
    run = jax.jit(_kernel, static_argnums=(1, 2, 3))
    base, y_base = run(pool, 1, NVALID, FRESH, *ops)
    rows, at = len(NVALID), 2
    nvalid, fresh, other = NVALID, FRESH, ops
    if companions == "other_operands":
        other = list(_operands(widths, seed=1)[1:])
        other[:4] = [o.at[at].set(mine[at])
                     for o, mine in zip(other[:4], ops[:4])]
        other[4] = ops[4]
        pool2 = _operands(widths, seed=2)[0].at[rows + at].set(
            pool[rows + at])
    elif companions == "all_idle":
        nvalid, pool2 = (0, 0, 1, 0, 0), pool
    elif companions == "all_single":
        nvalid, fresh, pool2 = (1,) * rows, (True, True, False, True, True), \
            pool
    else:
        # the same request one slot further up
        move = lambda o: jnp.roll(o, 1, axis=0)
        other = [move(o) for o in ops[:4]] + [ops[4]]
        pool2 = pool.at[rows + at + 1].set(pool[rows + at])
        nvalid, fresh, at = (1, 0, 0, 1, 1), (False,) * rows, at + 1
    got, y = run(pool2, 1, nvalid, fresh, *other)
    assert np.array_equal(got[rows + at], base[rows + 2])
    assert np.array_equal(y[at], y_base[2])


# -- through ``mamba2_rows`` and the engine ------------------------------------

@pytest.fixture(scope="module")
def config():
    # the toy parallel layout with whole lanes of states: the kernel's form
    return models.get_config("parallel-hybrid-debug").replace(
        dtype="float32", param_dtype="float32", ssm_state=128)


def _mixer_inputs(c, rows, chunk, seed=0):
    rng = np.random.default_rng(seed)
    f32 = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    lp = {"conv_w": f32(c.ssm_conv, c.ssm_conv_width) * 0.5,
          "conv_b": f32(c.ssm_conv_width) * 0.1,
          "dt_bias": f32(c.ssm_heads) - 2.0,
          "A_log": jnp.log(jnp.asarray(
              rng.uniform(1, 16, c.ssm_heads), jnp.float32)),
          "D": 1.0 + 0.1 * f32(c.ssm_heads)}
    return (f32(rows, chunk, c.ssm_conv_width), f32(rows, chunk, c.ssm_heads),
            f32(rows, c.ssm_conv - 1, c.ssm_conv_width),
            f32(2 * rows, c.ssm_heads, c.ssm_head_dim, c.ssm_state), lp)


def _mixer(c, xbc, dt, conv, pool, lp, nvalid, fresh):
    return mamba2_rows(xbc, dt, conv, pool, xbc.shape[0], lp,
                       jnp.asarray(nvalid), jnp.asarray(fresh),
                       heads=c.ssm_heads, head_dim=c.ssm_head_dim,
                       groups=c.ssm_groups, states=c.ssm_state)


def test_the_mixer_chooses_the_kernel_and_agrees_with_the_other_form(
        config, monkeypatch):
    """``mamba2_rows`` over an idle row, two decode rows (one fresh), a
    block and a block with a tail: on the kernel's form (a float32 pool of
    whole lanes under the kernel's backend) against the ``jax.numpy`` form
    of the same call. The block rows run the same code on both forms, to
    the bit."""
    c = config
    nvalid, fresh = [0, 1, 8, 5, 1], [False, False, False, True, True]
    inputs = _mixer_inputs(c, 5, 8)
    assert kern.ssd_step_impl(jnp.float32, c.ssm_head_dim,
                              c.ssm_state) == "xla"
    want_y, want_conv, want_pool = _mixer(c, *inputs, nvalid, fresh)
    with _kernel_form(monkeypatch):
        assert kern.ssd_step_impl(jnp.float32, c.ssm_head_dim,
                                  c.ssm_state) == "pallas"
        traced = str(jax.make_jaxpr(
            lambda *a: _mixer(c, *a, nvalid, fresh))(*inputs))
        assert "pallas_call" in traced and "ssd_step_fwd" in traced
        y, conv, pool = _mixer(c, *inputs, nvalid, fresh)
    for r, n in enumerate(nvalid):
        assert np.allclose(y[r, :n], want_y[r, :n], rtol=1e-5, atol=1e-5)
    assert np.allclose(pool, want_pool, rtol=1e-5, atol=1e-5)
    assert np.array_equal(conv, want_conv)
    for r in (0, 2, 3):         # idle, block, block with a tail
        assert np.array_equal(pool[5 + r], want_pool[5 + r])
    assert np.array_equal(pool[:5], inputs[3][:5])


@pytest.mark.parametrize("dtype,head_dim,states,impl,want", [
    ("float32", 128, 256, "pallas", "pallas"),
    ("float32", 8, 128, "pallas", "pallas"),
    ("float32", 128, 256, None, "xla"),          # the CPU's own choice
    ("bfloat16", 128, 256, "pallas", "xla"),     # the state is float32
    ("float32", 8, 8, "pallas", "xla"),          # the tests' toy widths
    ("float32", 128, 192, "pallas", "xla"),      # not whole lanes
    ("float32", 12, 128, "pallas", "xla"),       # not whole sublanes
    ("float32", 256, 128, "pallas", "xla"),      # wider than a transpose
])
def test_the_form_is_chosen_from_what_the_code_can_observe(
        dtype, head_dim, states, impl, want):
    set_default_attention_impl(impl)
    try:
        assert kern.ssd_step_impl(jnp.dtype(dtype), head_dim,
                                  states) == want
    finally:
        set_default_attention_impl(None)


@pytest.fixture(scope="module")
def served(config):
    """form -> (the engine's counters, the first request's tokens): an
    11-token prompt (chunks of 8 and 3) answered with 4 tokens beside a
    one-token prompt (a fresh single row) answered with 2."""
    params = models.init_params(jax.random.PRNGKey(0), config)
    prompt = np.random.default_rng(8).integers(0, 256, 11).tolist()
    out = {}
    for form in ("kernel", "jax.numpy"):
        with contextlib.ExitStack() as stack:
            if form == "kernel":
                stack.enter_context(_kernel_form(
                    stack.enter_context(pytest.MonkeyPatch.context())))
            eng = LLMEngine(config, params, max_slots=2, max_len=64,
                            block_size=4, prefill_chunk=8)
            tokens = []
            eng.submit(prompt, 4, tokens.append)
            eng.submit(prompt[:1], 2, lambda item: None)
            while eng.step():
                pass
        out[form] = (dict(eng.stats), [t for t in tokens
                                       if isinstance(t, int)])
    return out


@pytest.mark.parametrize("form", ["kernel", "jax.numpy"])
def test_the_engine_counts_the_rows_the_kernel_stepped(served, form):
    """``ssd_kernel_rows`` over ``ssd_rows_stepped``: every row that fed
    one position on the kernel's form, none on the ``jax.numpy`` form."""
    s, tokens = served[form]
    # the first request: three tokens after the one its prompt's last chunk
    # samples; the second: its one-token prompt and one more token
    assert s["ssd_rows_stepped"] == 3 + 2
    assert s["ssd_kernel_rows"] == (5 if form == "kernel" else 0)
    assert s["ssd_positions_real"] == 11 + 3 + 2
    assert len(tokens) == 4


def test_the_engine_serves_the_same_tokens_on_both_forms(served):
    assert served["kernel"][1] == served["jax.numpy"][1]


@pytest.mark.parametrize("start,end,want", [
    ({"ssd_rows_stepped": 2, "ssd_kernel_rows": 2},
     {"ssd_rows_stepped": 9, "ssd_kernel_rows": 9}, 100.0),
    ({"ssd_rows_stepped": 2, "ssd_kernel_rows": 0},
     {"ssd_rows_stepped": 9, "ssd_kernel_rows": 0}, 0.0),
    ({"ssd_rows_stepped": 5, "ssd_kernel_rows": 5},       # idle
     {"ssd_rows_stepped": 5, "ssd_kernel_rows": 5}, None),
    ({"steps": 1}, {"steps": 4}, None),                   # no such counter
], ids=["kernel", "jax.numpy", "idle_window", "no_counter"])
def test_the_reader_takes_the_counters_growth_between_the_marks(start, end,
                                                                want):
    """``benchmark/layer_metrics/ssd_kernel_rows_pct.py`` over a window's
    marks; nothing to read (and nothing raised) in a program without the
    counters, as the parent is."""
    reader = manifest.load_module(
        manifest.layer_metric_path("ssd_kernel_rows_pct"))
    run = {"marks": {"start": {"stats": start}, "end": {"stats": end}}}
    assert reader.read(run) == want
    assert reader.read({}) is None
