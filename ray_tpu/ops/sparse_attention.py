"""Learned sparse attention in the paged serve step: a small indexer scores
every causal key of a query, and attention reads the ``topk`` best through
the block table (DeepSeek-V3.2's "lightning indexer").

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])       (s <= t)
    S_t     = the min(topk, t + 1) keys with the largest I[t, .]
    o_t     = softmax_{s in S_t}(q_t . k_s * scale) v_s

The indexer's keys ``kI`` live in a third pool beside K and V (a block's
``bs`` keys of ``index_dim`` in row-major order, stored as
:func:`index_pool_shape` says; written with the token's K and V). One
function, :func:`paged_sparse_attention`, serves a step whose rows differ:

- rows whose context is at most ``topk`` keys select everything: they skip
  the indexer and take :func:`ray_tpu.ops.paged_attention.paged_attention`,
  the attention every dense model takes (bit for bit);
- single-token rows (decode) past ``topk`` score their row's keys, take an
  exact ``lax.top_k`` and GATHER the chosen tokens' K and V out of the pool:
  ``topk`` keys a row a layer are read, not the context. The LAST query of
  a chunk row takes this form too (it is the one whose logits are sampled);
- chunk rows (prefill past ``topk``) have one selection per query, and a
  gather per query would be ``chunk * topk`` keys: they read the row's keys
  ONCE and apply the selection as a mask (:func:`select_top_k`: exactly
  what ``lax.top_k`` selects, found by counting instead of sorting). They
  run one row at a time under ``lax.cond``, so a step pays for the chunk
  rows it holds.

The scores of the one-query form (a decode row's query, a chunk row's last)
have two forms of one algorithm, chosen from what the code can observe
(:func:`indexer_impl`: backend, the pool's dtype and stored shape), never
from a model's name or a setting: a Pallas TPU kernel (``indexer_scores_fwd``)
that walks each SPARSE row's block table as far as the row's position and
reads the keys from the pool in place, and the ``jax.numpy`` form, which
gathers every row's whole table: what a CPU runs and what the kernel is
compared with. Everything else here is ``jax.numpy``. K and V stay in the
pool's type with float32 accumulation and a float32 softmax. The indexer's
scores keep float32 queries: they and the head weights arrive in float32,
the keys are read from their pool (rounded once, when written), and a query
goes to the matrix unit as TWO terms of the pool's type
(:func:`_score_products`; the kernel's rows of ``hi`` and ``lo``), because a
score decides a DISCRETE thing and a query rounded to bf16 flips a per cent
of the selected keys (PERF.md, PR 28).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.attention import (NEG_INF, _pallas_interpret,
                                   resolve_attention_impl)
from ray_tpu.ops.paged_attention import (LANES, STEP_BYTES, _heads_tile,
                                         _split_head_pair, each_page,
                                         paged_attention)

GLOBAL = 1 << 30      # paged_attention's "no window"
INDEX_KEY_TILE = 4096  # keys one indexer tile scores for a chunk row


def index_pool_shape(block_size: int, index_dim: int) -> tuple:
    """What one block of the indexer's pool is stored as: its ``block_size``
    keys of ``index_dim`` in row-major order, ``128 / index_dim`` of them a
    lane row where both widths divide (``[bs * di / 128, 128]``: 16 keys of
    64 are 8 rows of two), else a key a row (``[bs, di]``). The same bytes
    in the same order either way; a row of whole lanes is what the step's
    layer loop can carry and scatter into as it is stored (a ``[.., 64]``
    stack the TPU lays out with the block axis innermost, and converts)."""
    if LANES % index_dim == 0 and block_size * index_dim % LANES == 0:
        return (block_size * index_dim // LANES, LANES)
    return (block_size, index_dim)


def write_index_keys(pool, new, rows):
    """The step's keys ``new [T, di]`` into the flattened stack ``pool [N, R,
    128]`` of a pool stored several keys a lane row, at token rows ``rows
    [T]`` (``block * bs + offset``; past the stack: dropped). A key is
    ``di`` of a row's lanes, and a scatter of part rows is a loop of one
    update after another on the TPU, so each token writes its WHOLE row:
    its own key, beside it the keys of the step's other tokens of that row
    (a row's tokens are consecutive positions of one request, which the
    step holds next to one another), and what the pool held elsewhere.
    Tokens of one row write the same row, whichever lands last."""
    t, di = new.shape
    per_row = LANES // di
    flat = pool.reshape(-1, LANES)
    lane_row, own = rows // per_row, rows % per_row
    held = flat.at[lane_row].get(mode="clip")
    at = jnp.arange(t)
    parts = []
    for g in range(per_row):
        src = jnp.clip(at + g - own, 0, t - 1)
        written = rows[src] == lane_row * per_row + g
        parts.append(jnp.where(written[:, None], new[src],
                               held[:, g * di:(g + 1) * di]))
    return flat.at[lane_row].set(jnp.concatenate(parts, axis=1),
                                 mode="drop").reshape(pool.shape)


def _score_products(spec: str, qi, ki):
    """``einsum(spec, qi, ki)`` in float32 with ``qi`` float32 and ``ki`` in
    its pool's type, nothing of ``qi`` lost: against a bf16 pool the query
    is split into its bf16 rounding and the bf16 rounding of what that left
    (16 bits of mantissa between them), two plain products of the pool's
    type with float32 accumulation. No float32 copy of the keys, and two
    passes where a float32 product at full precision takes six."""
    f32 = jnp.float32
    if ki.dtype == f32:
        return jnp.einsum(spec, qi, ki, precision=lax.Precision.HIGHEST)
    hi = qi.astype(ki.dtype)
    lo = (qi - hi.astype(f32)).astype(ki.dtype)
    return jnp.einsum(spec, hi, ki, preferred_element_type=f32) \
        + jnp.einsum(spec, lo, ki, preferred_element_type=f32)


def indexer_scores(qi, w, ki):
    """``I[c, s] = sum_j w[c, j] * relu(qi[c, j] . ki[s])`` for one row:
    qi [C, J, di] and w [C, J] float32, ki [K, di] in the pool's type ->
    [C, K] float32. Keys go
    in tiles so that the per-head products ([C, J, tile] float32) stay
    small beside a resident model."""
    k = ki.shape[0]
    tile = min(INDEX_KEY_TILE, k)
    pad = -k % tile
    ki = jnp.pad(ki, ((0, pad), (0, 0)))

    def one(kt):
        s = _score_products("cjd,kd->cjk", qi, kt)
        return jnp.sum(jax.nn.relu(s) * w[:, :, None], axis=1)

    out = lax.map(one, ki.reshape(-1, tile, ki.shape[-1]))     # [n, C, tile]
    return out.transpose(1, 0, 2).reshape(qi.shape[0], -1)[:, :k]


def select_top_k(scores, k: int):
    """The mask of each row's ``k`` largest scores [Q, K] -> bool [Q, K]:
    what ``lax.top_k`` selects, ties going to the lower index, without a
    sort. The k-th largest value is built bit by bit on the scores' ordered
    integer image (32 counting passes; a sort of the row costs six times as
    much on the chip at 128 x 32768), then everything above it is taken
    and, of the scores equal to it, the first that still fit."""
    bits = lax.bitcast_convert_type(scores.astype(jnp.float32), jnp.uint32)
    # float order -> unsigned order: flip all bits of negatives, the sign
    # bit of the others
    keys = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))

    def bit(i, kth):
        trial = kth | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        enough = jnp.sum(keys >= trial[:, None], axis=1) >= k
        return jnp.where(enough, trial, kth)

    kth = lax.fori_loop(0, 32, bit,
                        jnp.zeros((scores.shape[0],), jnp.uint32))[:, None]
    above, equal = keys > kth, keys == kth
    room = k - jnp.sum(above, axis=1, keepdims=True)
    return above | (equal & (jnp.cumsum(equal, axis=1) <= room))


def indexer_impl(pool_dtype, block_shape, heads: int, index_dim: int) -> str:
    """``"pallas"`` when the kernel scores this pool on this backend, else
    ``"xla"`` (the ``jax.numpy`` form). ``block_shape`` is a stored block's
    (:func:`index_pool_shape`). The kernel wants a bf16 pool of whole lane
    rows (``128 / index_dim`` keys a row), the rows of a block read as
    32-bit pairs filling whole sublane tiles (the rule of
    ``paged_attention``'s head axis) and the heads whole float32 tiles."""
    rows, lanes = block_shape
    if (resolve_attention_impl() == "pallas"
            and jnp.dtype(pool_dtype) == jnp.bfloat16
            and lanes == LANES and LANES % index_dim == 0
            and _heads_tile(rows) and heads % 8 == 0):
        return "pallas"
    return "xla"


def impl_for(ki_pool, heads: int, index_dim: int) -> str:
    """:func:`indexer_impl` of a pool ``[..., rows, lanes]``: one layer's, the
    step's flattened stack or the cache's ``[L, n_blocks, ...]``."""
    return indexer_impl(ki_pool.dtype, ki_pool.shape[-2:], heads, index_dim)


def _last_query_scores(qi0, w0, ki_pool, block_tables, pos, sparse, bs):
    """``scores[b, s] = sum_j w0[b, j] * relu(qi0[b, j] . ki[s])`` of one
    query a row (at position ``pos[b]``) over the row's causal keys, ``-inf``
    elsewhere: [B, M * bs] float32. The kernel scores the rows with
    ``sparse[b]`` alone (``-inf`` everywhere in the others, of which nothing
    is read); the ``jax.numpy`` form every row."""
    b, j, di = qi0.shape
    m = block_tables.shape[1]
    causal = jnp.arange(m * bs)[None, :] <= pos[:, None]
    if impl_for(ki_pool, j, di) == "pallas":
        scores = _indexer_scores_pallas(
            qi0, w0, ki_pool, block_tables, pos, sparse, block_size=bs,
            interpret=_pallas_interpret())
        causal &= sparse[:, None]
    else:
        ki = ki_pool[block_tables].reshape(b, m * bs, di)
        s = _score_products("bjd,bkd->bjk", qi0, ki)
        scores = jnp.sum(jax.nn.relu(s) * w0[:, :, None], axis=1)  # [B, K]
    return jnp.where(causal, scores, -jnp.inf)


def _decode_rows(q0, qi0, w0, k_pool, v_pool, ki_pool, block_tables, pos,
                 sparse, topk, scale):
    """One query a row (q0 [B, H, hd], at position ``pos[b]``): score the
    row's keys, take the top ``topk`` and gather those tokens' K and V.
    Only rows with more than ``topk`` causal keys (``sparse``) may read the
    result."""
    b, h, hd = q0.shape
    bs, kvh = k_pool.shape[1], k_pool.shape[2]
    with jax.named_scope("dsa_indexer"):
        _, idx = lax.top_k(_last_query_scores(
            qi0, w0, ki_pool, block_tables, pos, sparse, bs), topk)
    with jax.named_scope("paged_sparse_attention"):
        phys = jnp.take_along_axis(block_tables, idx // bs, axis=1) * bs \
            + idx % bs                                            # [B, topk]
        ks = k_pool.reshape(-1, kvh, hd)[phys]            # [B, topk, kvh, hd]
        vs = v_pool.reshape(-1, kvh, hd)[phys]
        qg = q0.reshape(b, kvh, h // kvh, hd).astype(ks.dtype)
        a = jnp.einsum("bgrd,bkgd->bgrk", qg, ks,
                       preferred_element_type=jnp.float32) * scale
        p = jax.nn.softmax(a, axis=-1)
        o = jnp.einsum("bgrk,bkgd->bgrd", p.astype(vs.dtype), vs,
                       preferred_element_type=jnp.float32)
        return o.reshape(b, h, hd).astype(q0.dtype)


def _chunk_row(q, qi, w, k_pool, v_pool, ki_pool, table, pos, topk, scale):
    """One chunk row: q [C, H, hd] at positions ``pos + c``. The row's keys
    are read once; each query's selection is a mask over them: its
    ``topk`` best causal keys (all of them while there are at most
    ``topk``)."""
    c, h, hd = q.shape
    bs, kvh = k_pool.shape[1], k_pool.shape[2]
    keys = table.shape[0] * bs
    with jax.named_scope("dsa_indexer"):
        scores = indexer_scores(
            qi, w, ki_pool[table].reshape(keys, qi.shape[-1]))
        causal = jnp.arange(keys)[None, :] <= (pos + jnp.arange(c))[:, None]
        chosen = causal & select_top_k(
            jnp.where(causal, scores, -jnp.inf), min(topk, keys))

    def group(args):        # one KV head at a time: [C, rep, K] float32
        qh, kh, vh = args
        a = jnp.einsum("crd,kd->crk", qh, kh,
                       preferred_element_type=jnp.float32) * scale
        p = jax.nn.softmax(jnp.where(chosen[:, None, :], a, NEG_INF), -1)
        return jnp.einsum("crk,kd->crd", p.astype(vh.dtype), vh,
                          preferred_element_type=jnp.float32)

    with jax.named_scope("paged_sparse_attention"):
        kctx = k_pool[table].reshape(keys, kvh, hd)
        vctx = v_pool[table].reshape(keys, kvh, hd)
        qg = q.reshape(c, kvh, h // kvh, hd).astype(kctx.dtype)
        o = lax.map(group, (qg.transpose(1, 0, 2, 3),
                            kctx.transpose(1, 0, 2),
                            vctx.transpose(1, 0, 2)))      # [kvh, C, rep, hd]
        return o.transpose(1, 0, 2, 3).reshape(c, h, hd).astype(q.dtype)


def paged_sparse_attention(q, qi, w, k_pool, v_pool, ki_pool, block_tables,
                           pos, nvalid, *, topk: int, scale: float):
    """Attention of ``q[B, C, H, hd]`` over the pools through
    ``block_tables[B, M]``, each query over the ``topk`` keys its indexer
    (``qi[B, C, J, di]`` and head weights ``w[B, C, J]``, float32; keys in
    ``ki_pool``) scores highest among its causal keys. Row ``b`` holds
    ``nvalid[b]`` real queries from position ``pos[b]``; the caller has
    written the chunk's own K, V and kI. Queries past ``nvalid`` return
    values nobody may read. Returns ``o[B, C, H, hd]`` in ``q``'s dtype."""
    b, c = q.shape[:2]
    if block_tables.shape[1] * k_pool.shape[1] <= topk:
        # a table that cannot hold more than ``topk`` keys: no row selects
        return paged_attention(q, k_pool, v_pool, block_tables, pos, nvalid,
                               window=GLOBAL, scale=scale)
    sparse = (pos + nvalid > topk) & (nvalid > 0)
    chunk = sparse & (nvalid > 1)
    # rows that select everything: the attention the dense models take
    o = paged_attention(q, k_pool, v_pool, block_tables, pos,
                        jnp.where(sparse, 0, nvalid), window=GLOBAL,
                        scale=scale)
    if c > 1:
        def row(args):
            qb, qib, wb, tb, pb, is_chunk = args
            return lax.cond(
                is_chunk,
                lambda: _chunk_row(qb, qib, wb, k_pool, v_pool, ki_pool, tb,
                                   pb, topk, scale),
                lambda: jnp.zeros_like(qb))

        oc = lax.map(row, (q, qi, w, block_tables, pos, chunk))
        o = jnp.where(chunk[:, None, None, None], oc, o)
    # every sparse row's LAST query, the one whose logits are sampled, takes
    # the gather form, in a chunk row too: a request's first token then
    # comes out of the same code whether its prompt arrived as a chunk or
    # all but one token of it as a prefix hit (served twice, cold and warm,
    # the engine must agree with itself token for token)
    last = jnp.clip(nvalid - 1, 0, c - 1)
    rows = jnp.arange(b)
    o_last = lax.cond(
        jnp.any(sparse),
        lambda: _decode_rows(q[rows, last], qi[rows, last], w[rows, last],
                             k_pool, v_pool, ki_pool, block_tables,
                             pos + last, sparse, topk, scale),
        lambda: jnp.zeros_like(q[:, 0]))
    return o.at[rows, last].set(
        jnp.where(sparse[:, None, None], o_last, o[rows, last]))


# ---------------------------------------------------------------------------
# the Pallas kernel of the one-query scores
# ---------------------------------------------------------------------------

def _lane_row_scores(q, keys, w, *, heads: int, per_row: int):
    """The scores of the keys in lane rows ``keys [n, 128]``, a plane
    ``[1, n]`` a lane group: ``q [2 per_row J, 128]`` (the query's ``hi``
    rows, then its ``lo`` rows, each once a lane group), ``w [J, 1]``."""
    s = lax.dot_general(q, keys, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
    s = s[:per_row * heads] + s[per_row * heads:]                 # hi + lo
    return [jnp.sum(jax.nn.relu(s[g * heads:(g + 1) * heads]) * w, axis=0,
                    keepdims=True) for g in range(per_row)]


def _indexer_kernel(tbl_ref, pos_ref, live_ref,                  # prefetch
                    q_ref, w_ref, ki_hbm, out_ref, kbuf, sems, plan_ref, *,
                    pages: int, tbl_width: int, block_size: int, heads: int,
                    per_row: int):
    """Grid step ``b``: row ``b``'s scores if ``live_ref[b]``, else nothing.

    The row's pages (blocks 0 to ``pos // bs`` of its table) come from the
    HBM pool into double-buffered VMEM, ``pages`` a key step, one key step
    AHEAD of the products and across rows, by the plan
    ``paged_attention_fwd`` has (``plan_ref[b]``: the buffer of the row's
    first step; ``plan_ref[B + b]``: the next row that is scored;
    ``plan_ref[2 B]``: the first such row, which starts its own copies).

    A page is ``[rows, 128]`` with ``per_row`` keys a lane row: key ``r *
    per_row + g`` of the page lies in row ``r``, lanes ``[g di, (g + 1)
    di)``. Two rows share each 32-bit sublane word, so a step's pages are
    read as words and split into the even and the odd rows (the head pairs
    of ``paged_attention``). ``q_ref [1, 2 per_row J, 128]`` holds the query's ``hi``
    and then its ``lo`` term, each once a lane group (head ``j`` at lanes of
    group ``g`` in row ``g J + j``, zero elsewhere: a product over the 128
    lanes is the product over the key's own ``di``). So a step is two
    matrix products ``[2 per_row J, 128] x [128, pages rows / 2]``, and its
    scores leave in ``2 per_row`` planes: ``out_ref[0, parity per_row + g,
    step, i]`` is key ``(step n + i) 2 per_row + parity per_row + g`` of the
    row (``n = pages rows / 2``). Keys past ``pos`` in the row's last page
    and step hold whatever the buffer held: the launcher masks them."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, n_grid = pl.program_id(0), pl.num_programs(0)
    rows = kbuf.shape[2]

    def live_pages(row):
        return pos_ref[row] // block_size + 1

    def steps(row):
        return (live_pages(row) + pages - 1) // pages

    @pl.when(b == 0)
    def _plan():
        def buffers(row, slot):
            plan_ref[row] = slot
            return jnp.where(live_ref[row] > 0, (slot + steps(row)) % 2, slot)
        lax.fori_loop(0, n_grid, buffers, 0)

        def scored_next(i, then):
            row = n_grid - 1 - i
            plan_ref[n_grid + row] = then
            return jnp.where(live_ref[row] > 0, row, then)
        plan_ref[2 * n_grid] = lax.fori_loop(0, n_grid, scored_next, n_grid)

    def copy(page, slot, p):
        return pltpu.make_async_copy(ki_hbm.at[page], kbuf.at[slot, p],
                                     sems.at[slot])

    def start_copies(row, step, slot):
        at = row * tbl_width + step * pages
        each_page(jnp.minimum(live_pages(row) - step * pages, pages),
                  lambda p: copy(tbl_ref[at + p], slot, p).start())

    @pl.when(live_ref[b] > 0)
    def _scores():
        slot0, then = plan_ref[b], plan_ref[n_grid + b]
        last = steps(b)

        @pl.when(b == plan_ref[2 * n_grid])
        def _first_copy():
            start_copies(b, 0, slot0)            # (once a call)

        q = q_ref[0]                             # [2 per_row J, 128]
        w = w_ref[0][:, 0:1]                     # [J, 1]

        def key_step(step, carry):
            slot = (slot0 + step) % 2
            own = step + 1 < last

            @pl.when(own | (then < n_grid))
            def _next_copy():
                start_copies(jnp.where(own, b, then),
                             jnp.where(own, step + 1, 0), 1 - slot)

            each_page(jnp.minimum(live_pages(b) - step * pages, pages),
                      lambda p: copy(0, slot, p).wait())  # (only the size)
            words = kbuf.at[slot].reshape(pages * rows, LANES).bitcast(
                jnp.uint32)[...]                 # [pages rows / 2, 128]
            for parity, keys in enumerate(_split_head_pair(words)):
                for g, plane in enumerate(_lane_row_scores(
                        q, keys, w, heads=heads, per_row=per_row)):
                    out_ref[0, parity * per_row + g, pl.ds(step, 1), :] = \
                        plane
            return carry

        lax.fori_loop(0, last, key_step, 0)


@functools.partial(jax.jit, static_argnames=("block_size", "interpret"))
def _indexer_scores_pallas(qi0, w0, ki_pool, block_tables, pos, sparse, *,
                           block_size: int, interpret: bool = False):
    """The kernel's scores [B, M * bs] float32 of the rows with ``sparse``:
    every key of a scored row up to the end of its last live page (the
    caller masks by position); the other rows hold nothing."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, j, di = qi0.shape
    m = block_tables.shape[1]
    rows = ki_pool.shape[1]
    per_row = LANES // di
    dtype = ki_pool.dtype
    # the one byte rule of the paged kernels' key step
    pages = min(max(1, STEP_BYTES // (rows * LANES * dtype.itemsize)), m)
    n_steps = -(-m // pages)
    n = pages * rows // 2
    # the query as the matrix unit takes it: two terms of the pool's type
    # (``_score_products``), each once a lane group
    f32 = jnp.float32
    hi = qi0.astype(dtype)
    lo = (qi0 - hi.astype(f32)).astype(dtype)
    q = jnp.concatenate([
        jnp.pad(term, ((0, 0), (0, 0), (g * di, LANES - (g + 1) * di)))
        for term in (hi, lo) for g in range(per_row)], axis=1)
    w = jnp.broadcast_to(w0.astype(f32)[:, :, None], (b, j, LANES))
    row_block = lambda b_, *_: (b_, 0, 0)
    kernel = functools.partial(
        _indexer_kernel, pages=pages, tbl_width=m, block_size=block_size,
        heads=j, per_row=per_row)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[pl.BlockSpec((1, 2 * per_row * j, LANES), row_block),
                      pl.BlockSpec((1, j, LANES), row_block),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, 2 * per_row, n_steps, n),
                                   lambda b_, *_: (b_, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, pages, rows, LANES), dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((2 * b + 1,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, 2 * per_row, n_steps, n), f32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="indexer_scores_fwd",
        interpret=interpret,
    )(block_tables.reshape(-1).astype(jnp.int32),
      jnp.maximum(pos, 0).astype(jnp.int32), sparse.astype(jnp.int32),
      q, w, ki_pool)
    # [B, plane, step, i] -> key (step n + i) 2 per_row + plane
    return out.transpose(0, 2, 3, 1).reshape(b, -1)[:, :m * block_size]
