"""Learned sparse attention in the paged serve step: a small indexer scores
every causal key of a query, and attention reads the ``topk`` best through
the block table (DeepSeek-V3.2's "lightning indexer").

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])       (s <= t)
    S_t     = the min(topk, t + 1) keys with the largest I[t, .]
    o_t     = softmax_{s in S_t}(q_t . k_s * scale) v_s

The indexer's keys ``kI`` live in a third pool beside K and V
(``[n_blocks, bs, index_dim]``, written with the token's K and V). One
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

Everything here is ``jax.numpy``: the forms the kernels of a later PR are
compared with. K and V stay in the pool's type with float32 accumulation and
a float32 softmax. The indexer's scores keep float32 queries: they and the
head weights arrive in float32, the keys are read from their pool (rounded
once, when written), and a query goes to the matrix unit as TWO terms of the
pool's type (:func:`_score_products`), because a score decides a DISCRETE
thing and a query rounded to bf16 flips a per cent of the selected keys
(PERF.md, PR 28).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.attention import NEG_INF
from ray_tpu.ops.paged_attention import paged_attention

GLOBAL = 1 << 30      # paged_attention's "no window"
INDEX_KEY_TILE = 4096  # keys one indexer tile scores for a chunk row


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


def _decode_rows(q0, qi0, w0, k_pool, v_pool, ki_pool, block_tables, pos,
                 topk, scale):
    """One query a row (q0 [B, H, hd], at position ``pos[b]``): score the
    row's keys, take the top ``topk`` and gather those tokens' K and V.
    Only rows with more than ``topk`` causal keys may read the result."""
    b, h, hd = q0.shape
    bs, kvh = k_pool.shape[1], k_pool.shape[2]
    m = block_tables.shape[1]
    with jax.named_scope("dsa_indexer"):
        ki = ki_pool[block_tables].reshape(b, m * bs, -1)
        s = _score_products("bjd,bkd->bjk", qi0, ki)
        scores = jnp.sum(jax.nn.relu(s) * w0[:, :, None], axis=1)  # [B, K]
        causal = jnp.arange(m * bs)[None, :] <= pos[:, None]
        _, idx = lax.top_k(jnp.where(causal, scores, -jnp.inf), topk)
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
        scores = indexer_scores(qi, w, ki_pool[table].reshape(keys, -1))
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
                             pos + last, topk, scale),
        lambda: jnp.zeros_like(q[:, 0]))
    return o.at[rows, last].set(
        jnp.where(sparse[:, None, None], o_last, o[rows, last]))
