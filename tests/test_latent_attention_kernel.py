"""``ops.latent_attention``: the Pallas kernel that walks the live blocks of
the latent pool (interpret mode, through ``attn_pallas_interpret``) against
the ``jax.numpy`` form, at a small size on the CPU: 4 heads, a latent of 128
and a rotated key of 64 in a 256-lane pool, blocks of 16, tables of 8 to 32
blocks, key steps of 128 and tiles of 256 rows so that a row takes several
of each."""

import contextlib
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu import models
from ray_tpu.ops import latent_attention as la
from ray_tpu.ops.attention import set_default_attention_impl
from ray_tpu.serve.llm import LLMEngine

RANK, ROPE, HEADS, BS = 128, 64, 4, 16
WIDTH = la.pool_width(RANK + ROPE)
SCALE = (RANK + ROPE) ** -0.5


@dataclasses.dataclass(frozen=True)
class Case:
    chunk: int
    pos: tuple          # tokens already cached, a row
    nvalid: tuple       # real queries of this step, a row
    tbl: int = 32       # 512 positions: four key steps of 128
    keys: int = 128
    tile_rows: int = 256    # 16 queries x 16 (4 heads in a sublane tile)


CASES = {
    # pos 0, inside the first block, on a block edge, across a key step
    "token_rows": Case(1, (0, 15, 16, 300), (1, 1, 1, 1)),
    "chunk_rows": Case(32, (0, 64, 200, 448), (32, 32, 32, 32)),
    # the last chunk of a prompt: one tile, a tile and a query, one query
    "narrow_last_chunk": Case(32, (96, 130, 5, 256), (7, 17, 31, 1)),
    "pos_off_a_block_edge": Case(32, (5, 333, 127, 129), (32, 32, 32, 32)),
    "rows_that_feed_nothing": Case(32, (5, 100, 300, 77), (0, 32, 0, 0)),
    "rows_of_mixed_kinds": Case(32, (5, 16, 470, 333), (0, 1, 32, 9)),
    # a table narrower than a key step, and one that is not whole steps
    "table_under_a_step": Case(32, (3, 60, 90, 17), (32, 1, 20, 0), tbl=8,
                               keys=512),
    "table_of_one_and_a_half_steps": Case(32, (3, 150, 100, 17),
                                          (32, 32, 1, 0), tbl=12),
    # one tile holds the whole chunk; the published key step
    "one_tile_one_step": Case(32, (480, 0, 250, 31), (32, 32, 3, 1),
                              keys=512, tile_rows=1024),
}


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


def _inputs(case: Case, seed: int):
    """A pool in which every row owns ``tbl`` scattered blocks; queries and
    vectors are zero past ``RANK + ROPE``, as the model writes them."""
    rng = np.random.default_rng(seed)
    b, m = len(case.pos), case.tbl
    n_blocks = b * m + 3
    real = RANK + ROPE

    def padded(shape):
        a = np.zeros(shape + (WIDTH,), np.float32)
        a[..., :real] = rng.normal(0, 1.0, shape + (real,))
        return a

    pool = padded((n_blocks, BS))
    q = jnp.asarray(padded((b, case.chunk, HEADS)), jnp.bfloat16)
    tables = rng.permutation(n_blocks)[:b * m].reshape(b, m).astype(np.int32)
    # what no row may read: every block but the rows' live ones
    dead = np.ones(n_blocks, bool)
    for r in range(b):
        if case.nvalid[r]:
            dead[tables[r, :-(-(case.pos[r] + case.nvalid[r]) // BS)]] = False
    poisoned = pool.copy()
    poisoned[dead] = np.nan
    return (q, jnp.asarray(pool, jnp.bfloat16),
            jnp.asarray(poisoned, jnp.bfloat16), jnp.asarray(tables),
            jnp.asarray(case.pos, jnp.int32),
            jnp.asarray(case.nvalid, jnp.int32))


def _attend(case: Case, q, pool, tables, pos, nvalid, monkeypatch):
    monkeypatch.setattr(la, "KEYS_PER_STEP", case.keys)
    monkeypatch.setattr(la, "ROWS_PER_TILE", case.tile_rows)
    return np.asarray(la.paged_latent_attention(
        q, pool, tables, pos, nvalid, rank=RANK, scale=SCALE
    ).astype(jnp.float32))


@pytest.mark.parametrize("name", list(CASES))
def test_the_kernel_matches_the_jnp_form(name, monkeypatch):
    """Every real query of every row, the kernel over a pool whose dead
    blocks (a row's table past ``pos + nvalid``, all of a row that feeds
    nothing, blocks no table names) hold NaN: it reads none of them, and
    agrees with the ``jax.numpy`` form over the clean pool."""
    case = CASES[name]
    q, pool, poisoned, tables, pos, nvalid = _inputs(case, seed=len(name))
    assert la.latent_attention_impl(pool.dtype, WIDTH, BS, RANK) == "xla"
    want = _attend(case, q, pool, tables, pos, nvalid, monkeypatch)
    with _kernel_form(monkeypatch):
        assert la.latent_attention_impl(
            pool.dtype, WIDTH, BS, RANK) == "pallas"
        got = _attend(case, q, poisoned, tables, pos, nvalid, monkeypatch)
    assert got.shape == want.shape == (len(case.pos), case.chunk, HEADS, RANK)
    for r, n in enumerate(case.nvalid):     # past nvalid: nobody reads it
        assert np.isfinite(got[r, :n]).all()
        np.testing.assert_allclose(got[r, :n], want[r, :n], atol=1e-2,
                                   rtol=2e-2)


@pytest.mark.parametrize("pos,n", [(0, 32), (37, 9), (250, 22), (368, 16)])
def test_the_last_real_query_is_bit_equal_as_a_chunk_rows_and_a_token_rows(
        pos, n, kernel, monkeypatch):
    """The sampled query, attended among its chunk's queries (other rows
    beside it) and alone as a token row at its own position: one pass, the
    same bits."""
    case = Case(32, (pos, 100, 17, 64), (n, 32, 1, 0))
    q, pool, _, tables, p, nvalid = _inputs(case, seed=pos)
    chunk = _attend(case, q, pool, tables, p, nvalid, monkeypatch)
    alone = _attend(case, q[:1, n - 1:n], pool, tables[:1], p[:1] + n - 1,
                    jnp.asarray([1], jnp.int32), monkeypatch)
    assert np.array_equal(chunk[0, n - 1], alone[0, 0])
    # and the pass is not the tile's: the queries beside it took the other
    if n > 1:
        beside = _attend(case, q[:1, n - 2:n - 1], pool, tables[:1],
                         p[:1] + n - 2, jnp.asarray([1], jnp.int32),
                         monkeypatch)
        np.testing.assert_allclose(chunk[0, n - 2], beside[0, 0],
                                   atol=1e-2, rtol=2e-2)


def test_latent_attention_impl_falls_back_by_backend_dtype_and_shape():
    """The kernel is chosen from backend, dtype and shape alone: the CPU, a
    float32 pool, a pool or a latent that is not whole lanes and blocks
    that are not whole sublane tiles take the ``jax.numpy`` form."""
    impl = la.latent_attention_impl
    assert impl(jnp.bfloat16, 640, 16, 512) == "xla"            # CPU
    set_default_attention_impl("pallas")
    try:
        assert impl(jnp.bfloat16, 640, 16, 512) == "pallas"
        assert impl(jnp.bfloat16, 256, 32, 128) == "pallas"
        assert impl(jnp.float32, 640, 16, 512) == "xla"
        assert impl(jnp.bfloat16, 576, 16, 512) == "xla"
        assert impl(jnp.bfloat16, 128, 16, 24) == "xla"
        assert impl(jnp.bfloat16, 640, 8, 512) == "xla"
    finally:
        set_default_attention_impl(None)
    set_default_attention_impl("xla")
    try:
        assert impl(jnp.bfloat16, 640, 16, 512) == "xla"
    finally:
        set_default_attention_impl(None)


# -- the kernel inside the step and the engine --------------------------------

@pytest.fixture(scope="module")
def config():
    """The debug preset with a latent of whole lanes, in bf16: a pool the
    kernel takes (136 values in 256 lanes)."""
    return models.get_config("latent-moe-debug").replace(
        kv_lora_rank=RANK, dtype="bfloat16", param_dtype="bfloat16")


@pytest.fixture(scope="module")
def params(config):
    return models.init_params(jax.random.PRNGKey(0), config)


def _logits(config, params, budget=None):
    """One step of three rows (a chunk, a token, nothing) over a cache the
    step before wrote."""
    step = jax.jit(functools.partial(models.decode_step_paged, config=config,
                                     budget=budget))
    cache = models.init_cache_paged(config, 12, BS)
    tables = jnp.asarray([[3, 7, 1, 0], [2, 9, 5, 0], [4, 6, 8, 0]])
    rng = np.random.default_rng(2)
    first = jnp.asarray(rng.integers(0, 256, (3, 24)), jnp.int32)
    _, cache = step(params, cache, first, tables, jnp.zeros(3, jnp.int32),
                    jnp.asarray([24, 20, 0]))
    then = jnp.asarray(rng.integers(0, 256, (3, 24)), jnp.int32)
    logits, _ = step(params, cache, then, tables, jnp.asarray([24, 20, 0]),
                     jnp.asarray([24, 1, 0]))
    return np.asarray(logits[:2])


@pytest.mark.parametrize("budget", [None, 32], ids=["grid", "ordered_stream"])
def test_the_paged_step_reads_alike_on_both_forms(config, params, budget,
                                                  monkeypatch):
    """``decode_step_paged`` with the kernel in its layers against the same
    step on the ``jax.numpy`` form, over the grid and over the ordered
    stream of a budget."""
    want = _logits(config, params, budget)
    with _kernel_form(monkeypatch):
        got = _logits(config, params, budget)
    assert np.isfinite(got).all()
    rel = np.linalg.norm(got - want) / np.linalg.norm(want)
    assert rel < 0.03


def test_cold_and_warm_serves_agree_bit_for_bit_on_the_kernel(
        config, params, kernel):
    """``test_latent_moe_serve``'s property on the kernel form: a prompt
    served cold (its last tokens a chunk row) and warm (a prefix hit, then a
    single-token row) samples from equal logits."""
    eng = LLMEngine(config, params, max_slots=2, max_len=128, block_size=BS,
                    prefill_chunk=16)
    assert eng.stats["attn_impl"] == "pallas"
    prompt = np.random.default_rng(8).integers(0, 256, 64).tolist()

    def serve():
        rows, sample = [], eng._sample

        def capture(row):
            rows.append(row.copy())
            return sample(row)

        eng._sample, eng.capture = capture, True
        toks = []
        try:
            eng.submit(prompt, 3, lambda item: toks.append(item)
                       if isinstance(item, int) else None)
            while eng.step():
                pass
        finally:
            eng._sample, eng.capture = sample, False
        return toks, np.stack(rows[-3:])

    cold_tokens, cold = serve()
    hits0 = eng.stats["prefix_hit_tokens"]
    warm_tokens, warm = serve()
    assert eng.stats["prefix_hit_tokens"] - hits0 == 63
    assert cold_tokens == warm_tokens
    assert np.array_equal(cold, warm)
    s = eng.stats
    assert s["latent_kernel_rows"] == s["latent_rows_attended"] > 0
