"""``ops.sparse_attention``: the indexer's key pool as it is stored (several
keys a lane row where the widths divide) and the Pallas kernel of the
one-query scores (interpret mode, through ``attn_pallas_interpret``) against
the ``jax.numpy`` form on the same pool, tables and positions at small sizes
on the CPU: equal selections, and nothing selected or read of rows that are
not sparse."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from benchmark import manifest
from ray_tpu import models
from ray_tpu.ops import sparse_attention as sa
from ray_tpu.ops.attention import set_default_attention_impl
from ray_tpu.serve.llm import LLMEngine

BF16 = jnp.bfloat16
BS, DI, HEADS, TOPK = 16, 64, 8, 32


@pytest.fixture
def kernel(monkeypatch):
    """The kernel form, interpreted: what a TPU backend selects."""
    monkeypatch.setenv("RTPU_ATTN_PALLAS_INTERPRET", "1")
    set_default_attention_impl("pallas")
    yield
    set_default_attention_impl(None)


def _pool(seed, n_blocks, nan_blocks=()):
    """A layer's pool in its stored shape; ``nan_blocks`` hold NaN (blocks
    nothing may read)."""
    rng = np.random.default_rng(seed)
    pool = rng.normal(size=(n_blocks, BS, DI)).astype(np.float32)
    pool[list(nan_blocks)] = np.nan
    return jnp.asarray(pool, BF16).reshape(
        n_blocks, *sa.index_pool_shape(BS, DI))


def _queries(seed, b):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(b, HEADS, DI)), jnp.float32),
            jnp.asarray(rng.normal(size=(b, HEADS)), jnp.float32))


def _scores(kernel_on, *args):
    set_default_attention_impl("pallas" if kernel_on else "xla")
    return np.asarray(jax.jit(
        lambda *a: sa._last_query_scores(*a, BS))(*args))


#: name -> (tables [B, M], the queries' positions, sparse, blocks of NaN)
_CASES = {
    # a row one key past ``topk``, alone
    "topk_plus_one": ([[3, 5, 1, 0]], [TOPK], [True], (2, 4, 6, 7)),
    # contexts that end in the middle of a block, and at a block's last key
    "mid_block": ([[1, 2, 3, 4, 5, 0], [6, 7, 8, 9, 10, 11]], [70, 95],
                  [True, True], ()),
    # a dead row (its table names a block of NaN) and a row of at most
    # ``topk`` keys beside a sparse one: -inf everywhere, nothing read
    "dead_and_short_rows": ([[12, 12, 12, 12], [1, 2, 3, 12], [4, 5, 12, 12]],
                            [0, 40, TOPK - 1], [False, True, False], (12,)),
    # two rows that share their first blocks (copy-on-write: a tail each)
    "shared_blocks": ([[1, 2, 3, 4], [1, 2, 3, 5]], [60, 63], [True, True],
                      ()),
    # more pages than one key step of the byte rule holds would not fit a
    # test; two steps by a smaller rule: the copies run ahead across rows
    "two_steps_ahead": ([[1, 2, 3, 4, 5, 6], [7, 8, 9, 10, 11, 0],
                         [0, 0, 0, 0, 0, 0], [6, 5, 4, 3, 2, 1]],
                        [90, 47, 0, 80], [True, True, False, True], ()),
}


@pytest.mark.parametrize("case", list(_CASES))
def test_kernel_selects_what_the_jax_numpy_form_selects(kernel, case,
                                                        monkeypatch):
    tables, pos, sparse, nan_blocks = _CASES[case]
    if case == "two_steps_ahead":
        monkeypatch.setattr(sa, "STEP_BYTES", 4 * BS * DI * 2)   # 4 pages
    pool = _pool(1, 13, nan_blocks)
    qi, w = _queries(2, len(tables))
    args = (qi, w, pool, jnp.asarray(tables, jnp.int32),
            jnp.asarray(pos, jnp.int32), jnp.asarray(sparse))
    assert sa.impl_for(pool, HEADS, DI) == "pallas"
    got, want = _scores(True, *args), _scores(False, *args)
    for b, is_sparse in enumerate(sparse):
        if not is_sparse:
            assert np.all(np.isneginf(got[b])), (case, b)
            continue
        live = np.arange(got.shape[1]) <= pos[b]
        assert np.all(np.isneginf(got[b][~live]))
        assert np.all(np.isfinite(got[b][live]))
        assert np.allclose(got[b][live], want[b][live], rtol=1e-5, atol=1e-5)
        k = min(TOPK, pos[b] + 1)
        assert np.array_equal(lax.top_k(jnp.asarray(got[b]), k)[1],
                              lax.top_k(jnp.asarray(want[b]), k)[1]), (case, b)


def test_a_chunk_rows_last_query_takes_the_kernel(kernel):
    """``paged_sparse_attention`` over a chunk row past ``topk`` keys, a
    decode row past them and a short row: the same output from both forms
    (the chunk row's last query and the decode row are the kernel's)."""
    rng = np.random.default_rng(5)
    b, c, h, kvh, hd, n_blocks = 3, 8, 4, 2, 16, 14
    k_pool, v_pool = (jnp.asarray(rng.normal(size=(n_blocks, BS, kvh, hd)),
                                  BF16) for _ in range(2))
    ki_pool = _pool(6, n_blocks)
    q = jnp.asarray(rng.normal(size=(b, c, h, hd)), BF16)
    qi = jnp.asarray(rng.normal(size=(b, c, HEADS, DI)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(b, c, HEADS)), jnp.float32)
    tables = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8], [9, 10, 11, 12]],
                         jnp.int32)
    pos = jnp.asarray([40, 50, 3], jnp.int32)
    nvalid = jnp.asarray([8, 1, 5], jnp.int32)

    def run(kernel_on):
        set_default_attention_impl("pallas" if kernel_on else "xla")
        return np.asarray(jax.jit(lambda *a: sa.paged_sparse_attention(
            *a, topk=TOPK, scale=hd ** -0.5))(
                q, qi, w, k_pool, v_pool, ki_pool, tables, pos, nvalid),
            np.float32)

    got, want = run(True), run(False)
    for row, n in enumerate(np.asarray(nvalid)):
        assert np.allclose(got[row, :n], want[row, :n], rtol=2e-2, atol=2e-2)
        assert np.array_equal(got[row, n - 1], want[row, n - 1])


@pytest.mark.parametrize("bs,di,stored", [
    (16, 64, (8, 128)),     # two keys a lane row
    (8, 16, (1, 128)),      # a block is one lane row of eight keys
    (4, 16, (4, 16)),       # the tiny serve config: 64 a block, a key a row
    (16, 96, (16, 96)),     # 128 is no multiple of the key
    (16, 128, (16, 128)),
])
def test_a_key_is_written_and_read_back_at_every_offset(bs, di, stored):
    """The stored shape follows from ``bs`` and ``di`` alone and holds a
    block's keys in row-major order; the step's write puts a token's key
    at its offset and leaves every other key's bits, two tokens of one lane
    row and a dropped token among them."""
    assert sa.index_pool_shape(bs, di) == stored
    config = models.get_config("sparse-moe-debug").replace(index_head_dim=di)
    cache = models.init_cache_paged(config, 5, bs)
    assert cache["ki"].shape == (config.n_layers, 5, *stored)
    if stored[1] == di:
        return    # a key a row: the write of K and V
    n_blocks = 6
    rng = np.random.default_rng(0)
    pool = jnp.asarray(rng.normal(size=(n_blocks, *stored)), BF16)
    by_key = lambda p: np.asarray(p, np.float32).reshape(n_blocks * bs, di)
    for offset in range(bs):
        # a token alone, then a run of three from the same offset (two of
        # them share a lane row: a request's tokens lie next to one another
        # in the step), a dropped token between the requests
        for run in (1, 3):
            rows = [2 * bs + offset + i for i in range(run)]
            rows = rows + [n_blocks * bs, 5 * bs + offset]
            new = jnp.asarray(rng.normal(size=(len(rows), di)), BF16)
            got = by_key(jax.jit(sa.write_index_keys)(
                pool, new, jnp.asarray(rows, jnp.int32)))
            want = by_key(pool)
            for r, key in zip(rows, np.asarray(new, np.float32)):
                if r < n_blocks * bs:
                    want[r] = key
            assert np.array_equal(got, want), (offset, run)


def _sparse_config():
    """The tiny sparse-attention MoE config at widths the kernel takes."""
    return models.get_config("sparse-moe-debug").replace(
        index_heads=HEADS, index_head_dim=DI, index_topk=TOPK)


@pytest.mark.parametrize("kernel_on", [True, False])
def test_engine_counts_the_rows_the_kernel_scored(kernel, kernel_on):
    """``indexer_rows_scored`` grows by a row for every step a row feeds a
    query past ``topk`` keys; ``indexer_kernel_rows`` with it where the
    program holds the kernel, and not at all where it does not; the reader
    of ``indexer_kernel_rows_pct`` gives their share over a window."""
    set_default_attention_impl("pallas" if kernel_on else "xla")
    config = _sparse_config()
    params = models.init_params(jax.random.PRNGKey(0), config)
    eng = LLMEngine(config, params, max_slots=2, max_len=96, block_size=BS,
                    prefill_chunk=16)
    assert eng.stats["indexer_impl"] == ("pallas" if kernel_on else "xla")
    start = dict(eng.stats)
    toks = []
    eng.submit(list(range(1, 41)), 6, toks.append)
    while eng.step():
        pass
    assert len([t for t in toks if isinstance(t, int)]) == 6
    # the chunk of positions 32-39 passes ``topk`` keys, then five tokens
    assert eng.stats["indexer_rows_scored"] == 1 + 5
    assert eng.stats["indexer_kernel_rows"] == (6 if kernel_on else 0)
    read = manifest.load_module(
        manifest.layer_metric_path("indexer_kernel_rows_pct")).read
    run = {"marks": {"start": {"stats": start},
                     "end": {"stats": dict(eng.stats)}}}
    assert read(run) == (100.0 if kernel_on else 0.0)
    assert read({"marks": {"start": {"stats": {}},
                           "end": {"stats": {"steps": 3}}}}) is None
    assert read({"marks": {"start": {"stats": start},
                           "end": {"stats": start}}}) is None
