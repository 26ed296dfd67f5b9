"""``ops.diff_attention``: the Pallas kernel that reads the K and V pools
through the block table (interpret mode, through ``attn_pallas_interpret``)
against the ``jax.numpy`` form, at a small size on the CPU: heads of 64 so
that a KV pair is whole lanes, 8 query heads over 4 KV heads (two KV pairs,
two query pairs each), blocks of 16, tables of 8 to 32 blocks, key steps of
128 so that a row takes several."""

import contextlib
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu import models
from ray_tpu.ops import diff_attention as da
from ray_tpu.ops.attention import set_default_attention_impl
from ray_tpu.serve.llm import LLMEngine

HD, HEADS, KV_HEADS, BS = 64, 8, 4, 16
KVW = KV_HEADS * HD


@dataclasses.dataclass(frozen=True)
class Case:
    chunk: int
    pos: tuple          # tokens already cached, a row
    nvalid: tuple       # real queries of this step, a row
    tbl: int = 32       # 512 positions: four key steps of 128
    keys: int = 128


CASES = {
    # pos 0, inside the first block, on a block edge, across a key step
    "token_rows": Case(1, (0, 15, 16, 300), (1, 1, 1, 1)),
    "chunk_rows": Case(32, (0, 64, 200, 448), (32, 32, 32, 32)),
    # the last chunk of a prompt: some queries, one query
    "narrow_last_chunk": Case(32, (96, 130, 5, 256), (7, 17, 31, 1)),
    "rows_of_mixed_kinds": Case(32, (5, 16, 470, 333), (0, 1, 32, 9)),
    "rows_that_feed_nothing": Case(32, (5, 100, 300, 77), (0, 32, 0, 0)),
    # a context that ends inside a page, on a page's edge, on a key step's
    "ends_inside_a_page": Case(32, (5, 333, 120, 250), (32, 1, 3, 1)),
    "ends_on_a_page_edge": Case(32, (0, 47, 96, 255), (32, 1, 32, 1)),
    # a chunk that is not whole sublane tiles
    "chunk_of_24": Case(24, (3, 150, 100, 17), (24, 1, 20, 0)),
    # a table narrower than a key step, and one that is not whole steps
    "table_under_a_step": Case(32, (3, 60, 90, 17), (32, 1, 20, 0), tbl=8,
                               keys=512),
    "table_of_one_and_a_half_steps": Case(32, (3, 150, 100, 17),
                                          (32, 32, 1, 0), tbl=12),
    # the published key step over a table it holds whole
    "one_step": Case(32, (480, 0, 250, 31), (32, 32, 3, 1), keys=1024),
}
WINDOWS = {"every_key": 0, "window_of_40": 40, "window_of_200": 200}


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


def _first_page(pos, window):
    return max(pos - window + 1, 0) // BS if window else 0


def _inputs(case: Case, seed: int, window: int = 0):
    """Pools in which every row owns ``tbl`` scattered blocks, and the
    layer's scalars away from their trivial values."""
    rng = np.random.default_rng(seed)
    b, m = len(case.pos), case.tbl
    n_blocks = b * m + 3
    k_pool, v_pool = rng.normal(0, 1.0, (2, n_blocks, BS, KVW))
    q = jnp.asarray(rng.normal(0, 1.0, (b, case.chunk, HEADS * HD)),
                    jnp.bfloat16)
    tables = rng.permutation(n_blocks)[:b * m].reshape(b, m).astype(np.int32)
    # what no row may read: every block but the rows' live ones
    dead = np.ones(n_blocks, bool)
    for r in range(b):
        if case.nvalid[r]:
            dead[tables[r, _first_page(case.pos[r], window):
                        -(-(case.pos[r] + case.nvalid[r]) // BS)]] = False
    poison = lambda pool: jnp.asarray(
        np.where(dead[:, None, None], np.nan, pool), jnp.bfloat16)
    scalars = (jnp.float32(0.37), jnp.float32(0.55),
               jnp.asarray(rng.normal(1.0, 0.1, 2 * HD), jnp.bfloat16))
    return (q, (jnp.asarray(k_pool, jnp.bfloat16),
                jnp.asarray(v_pool, jnp.bfloat16)),
            (poison(k_pool), poison(v_pool)), jnp.asarray(tables),
            jnp.asarray(case.pos, jnp.int32),
            jnp.asarray(case.nvalid, jnp.int32), scalars)


def _attend(case: Case, q, pools, tables, pos, nvalid, scalars, monkeypatch,
            window=0):
    monkeypatch.setattr(da, "KEYS_PER_STEP", case.keys)
    return np.asarray(da.paged_diff_attention(
        q, *pools, tables, pos, nvalid, *scalars, window=window,
        heads=HEADS, kv_heads=KV_HEADS).astype(jnp.float32))


@pytest.mark.parametrize("window", list(WINDOWS))
@pytest.mark.parametrize("name", list(CASES))
def test_the_kernel_matches_the_jnp_form(name, window, monkeypatch):
    """Every real query of every row, the kernel over pools whose dead
    blocks (a row's table past ``pos + nvalid`` and, under a window, before
    the page of its first query's window start; all of a row that feeds
    nothing; blocks no table names) hold NaN: it reads none of them, and
    agrees with the ``jax.numpy`` form over the clean pools."""
    case, window = CASES[name], WINDOWS[window]
    q, pools, poisoned, tables, pos, nvalid, scalars = _inputs(
        case, seed=len(name), window=window)
    assert da.diff_attention_impl(pools[0].dtype, 2 * HD, BS) == "xla"
    want = _attend(case, q, pools, tables, pos, nvalid, scalars, monkeypatch,
                   window)
    with _kernel_form(monkeypatch):
        assert da.diff_attention_impl(pools[0].dtype, 2 * HD, BS) == "pallas"
        got = _attend(case, q, poisoned, tables, pos, nvalid, scalars,
                      monkeypatch, window)
    assert got.shape == want.shape == (len(case.pos), case.chunk, HEADS * HD)
    assert np.isfinite(got).all()
    for r, n in enumerate(case.nvalid):     # past nvalid: nobody reads it
        np.testing.assert_allclose(got[r, :n], want[r, :n], atol=2e-2,
                                   rtol=2e-2)


@pytest.mark.parametrize("window", [0, 40])
@pytest.mark.parametrize("row", [1, 2, 3], ids=["token", "chunk", "narrow"])
def test_a_rows_output_is_bit_equal_whatever_rows_share_its_step(
        row, window, kernel, monkeypatch):
    """A row among three others (before and after it, of every kind) and
    alone in a step of one: the same bits."""
    case = Case(32, (5, 310, 470, 333), (32, 1, 32, 9))
    q, pools, _, tables, pos, nvalid, scalars = _inputs(case, 7, window)
    among = _attend(case, q, pools, tables, pos, nvalid, scalars,
                    monkeypatch, window)
    one = slice(row, row + 1)
    alone = _attend(case, q[one], pools, tables[one], pos[one], nvalid[one],
                    scalars, monkeypatch, window)
    n = case.nvalid[row]
    assert np.array_equal(among[row, :n], alone[0, :n])


def test_diff_attention_impl_falls_back_by_backend_dtype_and_shape():
    """The kernel is chosen from backend, dtype and shape alone: the CPU, a
    float32 pool, a KV pair that is not whole lanes (the toy widths' ``2
    hd`` = 32) and blocks that are not whole sublane tiles take the
    ``jax.numpy`` form."""
    impl = da.diff_attention_impl
    assert impl(jnp.bfloat16, 128, 16) == "xla"                 # CPU
    set_default_attention_impl("pallas")
    try:
        assert impl(jnp.bfloat16, 128, 16) == "pallas"
        assert impl(jnp.bfloat16, 256, 32) == "pallas"
        assert impl(jnp.float32, 128, 16) == "xla"
        assert impl(jnp.bfloat16, 32, 16) == "xla"
        assert impl(jnp.bfloat16, 192, 16) == "xla"
        assert impl(jnp.bfloat16, 128, 8) == "xla"
        assert impl(jnp.bfloat16, 128, 4) == "xla"
    finally:
        set_default_attention_impl(None)
    set_default_attention_impl("xla")
    try:
        assert impl(jnp.bfloat16, 128, 16) == "xla"
    finally:
        set_default_attention_impl(None)


# -- the kernel inside the step and the engine --------------------------------

@pytest.fixture(scope="module")
def config():
    """The debug preset widened to heads of 64, in bf16: pools the kernel
    takes (one KV pair of 128 lanes, two query pairs)."""
    return models.get_config("hybrid-state-debug").replace(
        head_dim=HD, dtype="bfloat16", param_dtype="bfloat16")


@pytest.fixture(scope="module")
def params(config):
    return models.init_params(jax.random.PRNGKey(0), config)


def _logits(config, params, budget=None):
    """One step of three rows (a chunk, a token, nothing) over a cache the
    step before wrote; the window (8) has slid off the first step's keys."""
    from ray_tpu.models.hybrid import window_table_width

    chunk = 24
    step = jax.jit(functools.partial(models.decode_step_paged, config=config,
                                     budget=budget))
    m_win = window_table_width(config.sliding_window, chunk, BS)
    cache = models.init_cache_paged(config, 12, BS, window_blocks=3 * m_win,
                                    state_slots=3)
    win = np.arange(3 * m_win).reshape(3, m_win)
    tables = jnp.asarray(np.concatenate(
        [[[3, 7, 1, 0], [2, 9, 5, 0], [4, 6, 8, 0]], win], axis=1), jnp.int32)
    rng = np.random.default_rng(2)
    first = jnp.asarray(rng.integers(0, 256, (3, chunk)), jnp.int32)
    _, cache = step(params, cache, first, tables, jnp.zeros(3, jnp.int32),
                    jnp.asarray([16, 16, 0]))
    then = jnp.asarray(rng.integers(0, 256, (3, chunk)), jnp.int32)
    logits, _ = step(params, cache, then, tables, jnp.asarray([16, 16, 0]),
                     jnp.asarray([24, 1, 0]))
    return np.asarray(logits[:2])


@pytest.mark.parametrize("budget", [None, 32], ids=["grid", "ordered_stream"])
def test_the_paged_step_reads_alike_on_both_forms(config, params, budget,
                                                  monkeypatch):
    """``decode_step_paged`` with the kernel in the debug preset's five
    attention layers (two window, the full one, two cross) against the same
    step on the ``jax.numpy`` form, over the grid and over the ordered
    stream of a budget."""
    want = _logits(config, params, budget)
    with _kernel_form(monkeypatch):
        got = _logits(config, params, budget)
    assert np.isfinite(got).all()
    rel = np.linalg.norm(got - want) / np.linalg.norm(want)
    assert rel < 0.03


def test_the_engine_counts_the_rows_the_kernel_attended(config, params,
                                                        kernel):
    """``shared_kv_kernel_rows`` over ``shared_kv_rows_attended``: every
    row on the kernel's form, none on the ``jax.numpy`` form."""
    eng = LLMEngine(config, params, max_slots=2, max_len=128, block_size=BS,
                    prefill_chunk=16)
    assert eng.stats["attn_impl"] == "pallas"
    prompt = np.random.default_rng(8).integers(0, 256, 40).tolist()
    eng.submit(prompt, 3, lambda item: None)
    while eng.step():
        pass
    s = eng.stats
    # three chunks of the prompt (the last samples), two more tokens
    assert s["shared_kv_kernel_rows"] == s["shared_kv_rows_attended"] == 5
    set_default_attention_impl(None)
    plain = LLMEngine(config, params, max_slots=2, max_len=128,
                      block_size=BS, prefill_chunk=16)
    assert plain.stats["attn_impl"] == "xla"
    plain.submit(prompt, 3, lambda item: None)
    while plain.step():
        pass
    s = plain.stats
    assert s["shared_kv_rows_attended"] == 5
    assert s["shared_kv_kernel_rows"] == 0
