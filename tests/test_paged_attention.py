"""``ops.paged_attention``: the Pallas kernel (interpret mode) and the grouped
``jax.numpy`` form against ``naive_attention`` over each row's gathered
table. One parametrised test; every case runs both forms."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import paged_attention as pa
from ray_tpu.ops.attention import naive_attention, \
    set_default_attention_impl


@dataclasses.dataclass(frozen=True)
class Case:
    heads: int
    kv_heads: int
    chunk: int
    pos: tuple          # tokens already cached, a row
    nvalid: tuple       # real queries of this step, a row
    bs: int = 16
    tbl: int = 40       # 640 positions: two inner steps, a padded table
    window: int = 1 << 30
    softcap: float = 0.0
    first_block: tuple = ()   # the table's entry 0 is this block of the row


HD = 128
CASES = {
    # rows at nvalid 0, 1 and C in one batch; pos on a block edge (16, 320),
    # off it (5, 333) and across an inner-step edge (500 + 32 > 512)
    "gqa_32_8_chunk32": Case(32, 8, 32, (5, 16, 500, 333), (0, 1, 32, 32)),
    "gqa_28_4_chunk32": Case(28, 4, 32, (320, 37, 600, 0), (32, 1, 0, 32)),
    "gqa_8_2_chunk1": Case(8, 2, 1, (0, 511, 512, 639), (1, 1, 0, 1)),
    "mha_4_4_chunk1": Case(4, 4, 1, (17, 300, 63, 64), (1, 1, 1, 0)),
    "mha_2_2_chunk32": Case(2, 2, 32, (100, 3, 0, 577), (32, 7, 32, 32)),
    # the window ends inside the context: whole inner steps are skipped and
    # the first live step is cut by the band
    "window_24": Case(8, 2, 32, (300, 16, 560, 7), (32, 1, 32, 32),
                      window=24),
    "window_300_chunk1": Case(14, 2, 1, (599, 16, 301, 299), (1, 1, 1, 1),
                              window=300),
    "softcap_50": Case(8, 2, 32, (5, 16, 250, 333), (32, 1, 32, 0),
                       softcap=50.0),
    "block_size_8": Case(8, 2, 8, (5, 16, 250, 290), (8, 1, 0, 8), bs=8),
    # token rows only, dead rows between them and a dead row 0: each row's
    # last key step starts the first of the next row that feeds anything
    "token_rows_across_gaps": Case(32, 8, 32, (7, 100, 0, 555, 0, 9, 630),
                                   (0, 1, 0, 1, 0, 0, 1)),
    # token rows beside rows that feed part of a chunk and a whole one
    "token_partial_whole": Case(8, 2, 32, (5, 16, 500, 333, 40, 0),
                                (1, 7, 32, 1, 20, 2)),
    # the head shapes of the cells that run the kernel, at small contexts:
    # trinity-large (48 / 8, chunk 64), qwen2-7b (28 / 4, chunk 32),
    # olmo-hybrid-7b (a pool of 32 MHA heads, chunk 64)
    "heads_48_8_chunk64": Case(48, 8, 64, (70, 300, 0, 129), (1, 64, 0, 33)),
    "heads_28_4_chunk32": Case(28, 4, 32, (70, 300, 0, 129), (1, 32, 0, 9)),
    "heads_32_32_chunk64": Case(32, 32, 64, (70, 150, 0, 129),
                                (1, 64, 0, 33)),
    # a table that holds the live window only (``first_block``): the token
    # rows' first live page is not the table's page 0 (their windows start
    # 21 and 9 pages into it), the chunk row's is
    "window_table_first_block": Case(
        8, 2, 32, (1000, 700, 64, 150), (1, 1, 32, 20), window=24,
        first_block=(40, 34, 0, 1)),
}


def _inputs(case: Case, seed: int):
    """A pool in which every row owns ``tbl`` blocks, scattered, and the
    table entries past a row's live range name ANOTHER row's live blocks:
    they must be masked, never read into the result."""
    rng = np.random.default_rng(seed)
    b, m = len(case.pos), case.tbl
    n_blocks = b * m + 3
    shape = (n_blocks, case.bs, case.kv_heads, HD)
    # keys large enough that softcap 50 bends the scores (|s| ~ 30)
    k_pool = jnp.asarray(rng.normal(0, 1.7, shape), jnp.bfloat16)
    v_pool = jnp.asarray(rng.normal(0, 1.0, shape), jnp.bfloat16)
    q = jnp.asarray(rng.normal(0, 1.7, (b, case.chunk, case.heads, HD)),
                    jnp.bfloat16)
    tables = rng.permutation(n_blocks)[:b * m].reshape(b, m).astype(np.int32)
    for r in range(b):
        live = -(-(_table_pos(case)[r] + case.nvalid[r]) // case.bs)
        other = (r + 1) % b
        tables[r, live:] = tables[other, :m - live]
    return (q, k_pool, v_pool, jnp.asarray(tables),
            jnp.asarray(case.pos, jnp.int32),
            jnp.asarray(case.nvalid, jnp.int32))


def _table_pos(case: Case):
    """The rows' positions in their tables' own numbering."""
    first = case.first_block or (0,) * len(case.pos)
    return tuple(p - f * case.bs for p, f in zip(case.pos, first))


def _reference(case: Case, q, k_pool, v_pool, tables, pos):
    b, m = tables.shape
    pos = _table_pos(case)
    out = []
    for r in range(b):
        kctx = k_pool[tables[r]].reshape(1, m * case.bs, case.kv_heads, HD)
        vctx = v_pool[tables[r]].reshape(1, m * case.bs, case.kv_heads, HD)
        out.append(naive_attention(
            q[r:r + 1].astype(jnp.float32), kctx.astype(jnp.float32),
            vctx.astype(jnp.float32), causal=True, q_offset=int(pos[r]),
            window=case.window, softcap=case.softcap))
    return np.asarray(jnp.concatenate(out))


@pytest.mark.parametrize("form", ["pallas_interpret", "xla"])
@pytest.mark.parametrize("name", list(CASES))
def test_paged_attention_matches_naive(name, form, monkeypatch):
    case = CASES[name]
    q, k_pool, v_pool, tables, pos, nvalid = _inputs(case, seed=len(name))
    want = _reference(case, q, k_pool, v_pool, tables, pos)
    if form == "pallas_interpret":
        monkeypatch.setenv("RTPU_ATTN_PALLAS_INTERPRET", "1")
        set_default_attention_impl("pallas")
    try:
        expect = "pallas" if form == "pallas_interpret" else "xla"
        assert pa.paged_attention_impl(
            k_pool.dtype, HD, case.kv_heads) == expect
        first = jnp.asarray(case.first_block, jnp.int32) \
            if case.first_block else None
        got = jax.jit(lambda *a: pa.paged_attention(
            *a[:-1], window=a[-1], softcap=case.softcap, scale=HD ** -0.5,
            first_block=first))(
            q, k_pool, v_pool, tables, pos, nvalid,
            jnp.asarray(case.window, jnp.int32))
    finally:
        set_default_attention_impl(None)
    got = np.asarray(got.astype(jnp.float32))
    assert got.shape == want.shape
    checked = 0
    for r, n in enumerate(case.nvalid):     # past nvalid: nobody reads it
        np.testing.assert_allclose(got[r, :n], want[r, :n],
                                   atol=2e-2, rtol=2e-2)
        checked += n
    assert checked and np.isfinite(got).all()


@pytest.mark.parametrize("fed", [1, 20, 32])
def test_a_rows_output_does_not_depend_on_the_rows_beside_it(fed,
                                                            monkeypatch):
    """A row that feeds one token, part of a chunk or a whole one gives the
    same bits alone in a call (every other row dead) and among rows of
    every kind: its key steps count from its own first live page and what
    the buffers hold of other rows is weighed 0. (What keeps a cold serve
    and a warm one equal.)"""
    case = Case(8, 2, 32, (333, 500, 600, 5, 77), (1, 32, fed, 7, 1),
                window=400)
    q, k_pool, v_pool, tables, pos, nvalid = _inputs(case, seed=fed)
    alone = nvalid * (jnp.arange(len(case.pos)) == 2)
    monkeypatch.setenv("RTPU_ATTN_PALLAS_INTERPRET", "1")
    set_default_attention_impl("pallas")
    try:
        call = jax.jit(lambda nv: pa.paged_attention(
            q, k_pool, v_pool, tables, pos, nv, window=jnp.int32(case.window),
            scale=HD ** -0.5))
        among, alone = call(nvalid), call(alone)
    finally:
        set_default_attention_impl(None)
    np.testing.assert_array_equal(np.asarray(among[2, :fed], np.float32),
                                  np.asarray(alone[2, :fed], np.float32))
    assert np.abs(np.asarray(alone[2, :fed], np.float32)).max() > 0
    # a dead row's output is zeros, whatever lay in its blocks
    assert not np.asarray(alone[:2], np.float32).any()


def test_paged_attention_impl_falls_back_by_dtype_and_shape():
    """The kernel is chosen from backend, dtype and shape alone: a float32
    pool, a 16-wide head or an odd head count take the ``jax.numpy`` form
    even where the backend says pallas; the CPU takes it always."""
    assert pa.paged_attention_impl(jnp.bfloat16, 128, 8) == "xla"   # CPU
    set_default_attention_impl("pallas")
    try:
        assert pa.paged_attention_impl(jnp.bfloat16, 128, 8) == "pallas"
        assert pa.paged_attention_impl(jnp.bfloat16, 256, 4) == "pallas"
        assert pa.paged_attention_impl(jnp.float32, 128, 8) == "xla"
        assert pa.paged_attention_impl(jnp.bfloat16, 16, 2) == "xla"
        assert pa.paged_attention_impl(jnp.bfloat16, 128, 3) == "xla"
        assert pa.paged_attention_impl(jnp.bfloat16, 128, 12) == "xla"
    finally:
        set_default_attention_impl(None)


@pytest.mark.parametrize("form", ["pallas_interpret", "xla"])
@pytest.mark.parametrize("chunk", [1, 16])
def test_thirty_kv_heads_through_a_pool_of_thirty_two(form, chunk,
                                                      monkeypatch):
    """30 MHA heads are 15 32-bit pairs a token, which the kernel does not
    tile: the pool's head axis is ``pool_heads(30)`` = 32 wide (two heads of
    zeros), the queries are padded with heads nobody reads, and both forms
    over the padded pool give the 30 heads what the naive attention over the
    30-head pool gives them."""
    assert [pa.pool_heads(n) for n in (1, 2, 4, 8, 16, 6, 12, 30, 48)] \
        == [16, 2, 4, 8, 16, 16, 16, 32, 48]
    case = Case(30, 30, chunk, (5, 16, 500, 333),
                (0, 1, chunk, chunk) if chunk > 1 else (1, 1, 0, 1))
    q, k_pool, v_pool, tables, pos, nvalid = _inputs(case, seed=30)
    want = _reference(case, q, k_pool, v_pool, tables, pos)
    wide = pa.pool_heads(30)
    pad = lambda a: jnp.pad(a, ((0, 0), (0, 0), (0, wide - 30), (0, 0)))
    if form == "pallas_interpret":
        monkeypatch.setenv("RTPU_ATTN_PALLAS_INTERPRET", "1")
        set_default_attention_impl("pallas")
    try:
        expect = "pallas" if form == "pallas_interpret" else "xla"
        assert pa.impl_for(pad(k_pool)) == expect
        assert pa.paged_attention_impl(k_pool.dtype, HD, 30) == "xla"
        got = jax.jit(lambda *a: pa.paged_attention(
            *a, window=jnp.int32(1 << 30), scale=HD ** -0.5))(
            pad(q), pad(k_pool), pad(v_pool), tables, pos, nvalid)
    finally:
        set_default_attention_impl(None)
    got = np.asarray(got.astype(jnp.float32))[:, :, :30]
    for r, n in enumerate(case.nvalid):
        np.testing.assert_allclose(got[r, :n], want[r, :n],
                                   atol=2e-2, rtol=2e-2)
