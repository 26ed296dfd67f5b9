"""Latent attention (MLA, DeepSeek-V2/V3) over the paged LATENT pool, in its
absorbed form, and the YaRN-scaled RoPE tables its rotated key takes.

What a token caches a layer is ONE vector ``[c_kv, k_r]``: the normed latent
(``rank`` values) and the rotated key (``rope`` values), read by every query
head. Unabsorbed, head ``h`` scores ``q_nope_h . (c_kv W_uk_h) + q_rope_h .
k_r`` and sums ``softmax * (c_kv W_uv_h)``. ``W_uk`` folds into the query and
``W_uv`` into the output (equal in exact arithmetic)::

    q'_h    = [q_nope_h W_uk_h^T, q_rope_h]          rank + rope values
    score_h = q'_h . [c_kv, k_r]
    u_h     = sum softmax * c_kv                     rank values
    o_h     = u_h W_uv_h

so attention is multi-query attention with ONE key of ``rank + rope`` whose
first ``rank`` values are its value too: a decoding row reads each live
token's vector once for all heads. :func:`paged_latent_attention` takes the
absorbed queries and returns ``u``; the caller holds both folds.

**Which form the chunk rows take.** A chunk of ``C`` queries over ``N`` cached
tokens, 64 heads, rank 512, rope 64, heads of 128: absorbed it is ``C * 64 *
N * (576 + 512) * 2`` FLOPs (17.8 MFLOP x N at ``C`` = 128); up-projecting
the cache first is ``N * 512 * 64 * 256 * 2`` (16.8 MFLOP x N) plus ``C * 64
* N * (192 + 128) * 2`` of scores and values (5.2 MFLOP x N at 128): 22.0
against 17.8 at a chunk of 128, 27.3 against 35.6 at 256. The serve step's
chunks are 128 wide or narrower, so every row takes the absorbed form, and
a request's first token comes out of the same arithmetic whether its prompt
arrived as chunks or all but one block of it as a prefix hit.

**The pool is as wide as whole lanes.** A row of ``rank + rope`` = 576
bf16 values is four and a half of the TPU's 128 lanes; for a pool declared
576 wide its compiler keeps the BLOCK axis innermost in HBM and relays all of
it around every step (3 GB in and 3 GB out at the benchmark's size, read off
the compile for a described v5e). So the pool's last axis is
:func:`pool_width` (640 there), which is what a row-major row of 576 takes in
tiled HBM anyway; the lanes past ``rank + rope`` hold zeros and the queries
are zero there.

All ``jax.numpy``: the Pallas block walk of :mod:`ray_tpu.ops
.paged_attention` wants heads of 128 lanes in pairs of K and V pools, and
this pool has one head of 576 that is its own value. Each live row's table
is gathered at its full width, a row at a time; a row that feeds nothing
gathers nothing.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.attention import NEG_INF

#: query heads a chunk row scores at once: 8 x 128 queries x 43k keys of
#: float32 scores is 0.18 GB
HEADS_PER_STEP = 8
#: the TPU's lanes: the pool's last axis is a multiple of them
LANES = 128


def pool_width(width: int) -> int:
    """The last axis of a pool that holds ``width`` values a token."""
    return -(-width // LANES) * LANES


def yarn_inv_freq(dim: int, theta: float, factor: float, beta_fast: float,
                  beta_slow: float, original_len: int):
    """Inverse frequencies ``[dim / 2]`` of YaRN-scaled RoPE (Peng et al.
    2023, as DeepSeek-V3 applies it): frequency ``k`` keeps its unscaled
    value ``theta^(-2k/dim)`` where it turns more than ``beta_fast`` times
    over ``original_len`` positions, takes that over ``factor`` where it
    turns fewer than ``beta_slow`` times, and a linear ramp over the index
    in between. ``factor`` 1 is plain RoPE."""
    k = jnp.arange(0, dim, 2, dtype=jnp.float32)
    plain = theta ** (-k / dim)
    if factor <= 1.0:
        return plain

    def index_of(turns: float) -> float:
        """The (fractional) frequency index that turns ``turns`` times."""
        return dim * math.log(original_len / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(index_of(beta_fast)), 0)
    high = min(math.ceil(index_of(beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return plain * (1.0 - ramp) + plain / factor * ramp


def yarn_rotary(positions, dim: int, *, theta: float, factor: float,
                beta_fast: float, beta_slow: float, original_len: int,
                mscale: float = 1.0, mscale_all_dim: float = 0.0):
    """cos/sin tables ``[..., L, dim / 2]`` for
    :func:`ray_tpu.ops.layers.apply_rotary` (pairs ``(k, k + dim / 2)``)."""
    inv = yarn_inv_freq(dim, theta, factor, beta_fast, beta_slow,
                        original_len)
    angles = positions.astype(jnp.float32)[..., None] * inv
    form = lambda m: 0.1 * m * math.log(factor) + 1.0 \
        if factor > 1.0 and m else 1.0
    amp = form(mscale) / form(mscale_all_dim)
    return jnp.cos(angles) * amp, jnp.sin(angles) * amp


def _one_query(q0, ctx, pos, rank: int, scale: float):
    """One query (``q0 [H, W]`` at position ``pos``) over a row's gathered
    vectors ``ctx [K, W]``: they are read once for all heads."""
    s = jnp.einsum("hd,kd->hk", q0, ctx,
                   preferred_element_type=jnp.float32) * scale
    seen = jnp.arange(ctx.shape[0]) <= pos
    p = jax.nn.softmax(jnp.where(seen[None], s, NEG_INF), axis=-1)
    return jnp.einsum("hk,kr->hr", p.astype(ctx.dtype), ctx[:, :rank],
                      preferred_element_type=jnp.float32)


def _chunk(q, ctx, pos, rank: int, scale: float):
    """A chunk of queries (``q [C, H, W]`` at positions ``pos + c``) over a
    row's gathered vectors, ``HEADS_PER_STEP`` heads at a time."""
    c, h, w = q.shape
    seen = jnp.arange(ctx.shape[0])[None, :] <= (pos + jnp.arange(c))[:, None]
    g = math.gcd(h, HEADS_PER_STEP)

    def heads(qg):                                   # [C, g, W]
        s = jnp.einsum("cgd,kd->cgk", qg, ctx,
                       preferred_element_type=jnp.float32) * scale
        p = jax.nn.softmax(jnp.where(seen[:, None], s, NEG_INF), axis=-1)
        return jnp.einsum("cgk,kr->cgr", p.astype(ctx.dtype), ctx[:, :rank],
                          preferred_element_type=jnp.float32)

    qg = q.reshape(c, h // g, g, w).transpose(1, 0, 2, 3)
    return lax.map(heads, qg).transpose(1, 0, 2, 3).reshape(c, h, rank)


def paged_latent_attention(q, pool, block_tables, pos, nvalid, *, rank: int,
                           scale: float):
    """Absorbed latent attention of ``q[B, C, H, rank + rope]`` (``W_uk``
    folded into its first ``rank`` values, the rotated query its last) over
    the latent pool ``pool[n_blocks, bs, pool_width(rank + rope)]`` through
    ``block_tables[B, M]``: query ``c`` of row ``b`` sits at position
    ``pos[b] + c`` and sees the tokens at positions up to its own; the caller
    has written the chunk's own vectors. Row ``b`` holds ``nvalid[b]`` real
    queries; queries past them return values nobody may read, and a row
    that holds none reads nothing. Returns ``u[B, C, H, rank]`` in ``q``'s
    dtype: per head the softmax-weighted sum of the latents, to be
    multiplied by ``W_uv``.

    Every row's LAST real query, the one whose logits are sampled, goes
    through the one-query form, in a chunk row too (as
    :func:`ray_tpu.ops.sparse_attention.paged_sparse_attention` has it):
    served twice, cold and warm, the engine agrees with itself."""
    b, c, h, width = q.shape
    w = pool.shape[-1]
    with jax.named_scope("mla_attention"):
        q = jnp.pad(q, ((0, 0),) * 3 + ((0, w - width),)).astype(pool.dtype)

        def row(args):
            qb, table, pb, n = args
            last = jnp.clip(n - 1, 0, c - 1)
            gathered = lambda: pool[table].reshape(-1, w)
            nothing = jnp.zeros((c, h, rank), jnp.float32)

            def single():
                return nothing.at[last].set(_one_query(
                    qb[last], gathered(), pb + last, rank, scale))

            def chunk():
                ctx = gathered()
                return _chunk(qb, ctx, pb, rank, scale).at[last].set(
                    _one_query(qb[last], ctx, pb + last, rank, scale))

            forms = [lambda: nothing, single] + ([chunk] if c > 1 else [])
            return lax.switch(jnp.minimum(n, len(forms) - 1), forms)

        u = lax.map(row, (q, block_tables, pos, nvalid))
        return u.astype(q.dtype)
