"""The gated delta rule (Gated DeltaNet: Yang, Kautz, Hatamizadeh,
arXiv:2412.06464; ``beta`` in (0, 2), negative eigenvalues: Grazzi et al.,
arXiv:2411.12537) on the serve step: a head's state is a ``dk x dv`` float32
MATRIX, decayed by a scalar a token and corrected by a rank-one term::

    S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T
    o_t = S_t^T q_t

(Mamba-2's state, ``ops/ssm.py``, is decayed and FED; here the token first
reads what the state already holds of its key and writes the difference.)
Everything is float32, the small products at full precision: the state is
carried over hundreds of tokens and read by every later one.

Two forms of one rule, as a row of the step needs them
(:func:`delta_rows`):

- a row that feeds ONE position takes one turn of the recurrence
  (:func:`delta_turn`), its state read twice and written once;
- a row that feeds more takes the BLOCK form over its chunk
  (:func:`delta_block`, the chunkwise WY form of the paper's section 3):
  with ``G_t`` the summed log-decays and ``u_t = beta_t (v_t - alpha_t
  S_{t-1}^T k_t)`` the block's corrections, ``(I + A) U = R`` where ``A[t,
  s] = beta_t e^{G_t - G_s} (k_t . k_s)`` below the diagonal and ``R_t =
  beta_t (v_t - e^{G_t} S_0^T k_t)``: ONE unit-triangular solve a block in
  place of ``T`` dependent turns, then ``o`` and ``S_T`` as products. The
  solve is forward substitution inside 16-row tiles (all tiles at once) and
  across them: the stable order, which IS the recurrence (the product form
  ``(I - A)(I + A^2)(I + A^4)..`` takes powers of ``A`` that pass 1e9 where
  the keys of a block point one way, as they do behind a SiLU).

Both walk the LIVE rows only, one row a turn of a loop as long as there are
such rows, each state read from and written to the pool where it lies.

**The pool's layout.** ``[rows, H / r, dk, r * dv]`` with ``r`` heads side by
side on the lanes (:func:`heads_per_row`: the fewest that fill whole 128-lane
tiles, 2 at the published 192; 1 where no count does): a float32 array
``[.., 96, 192]`` is tiled ``[.., 96, 256]`` on the chip, a third of the
pool and of every pass over it padding. The one turn works on that layout as
it lies (two small matrix products a group of heads); the block form turns a
row's state to ``[H, dk, dv]`` and back, once a block.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
LANES = 128
_EXACT = lax.Precision.HIGHEST
#: rows of a tile of the block form's triangular solve
SOLVE_TILE = 16


def heads_per_row(heads: int, value_dim: int) -> int:
    """Heads that share a row of the state pool's last axis: the fewest
    whose values fill whole lane tiles, where the head count divides by
    it; else 1."""
    for r in (1, 2, 4, 8):
        if (r * value_dim) % LANES == 0 and heads % r == 0:
            return r
    return 1


def to_heads(s, r: int):
    """``[.., H / r, dk, r * dv]`` (the pool's layout) -> ``[.., H, dk,
    dv]``."""
    *lead, p, dk, lanes = s.shape
    s = s.reshape(*lead, p, dk, r, lanes // r)
    return jnp.moveaxis(s, -2, -3).reshape(*lead, p * r, dk, lanes // r)


def to_pool(s, r: int):
    """``[.., H, dk, dv]`` -> ``[.., H / r, dk, r * dv]``."""
    *lead, h, dk, dv = s.shape
    s = s.reshape(*lead, h // r, r, dk, dv)
    return jnp.moveaxis(s, -3, -2).reshape(*lead, h // r, dk, r * dv)


# -- the recurrence ---------------------------------------------------------------

def delta_step(s, q, k, v, alpha, beta):
    """ONE turn of the rule for every row, in the plain layout: ``s [B, H,
    dk, dv]``, ``q``, ``k [B, H, dk]``, ``v [B, H, dv]``, ``alpha``,
    ``beta [B, H]``. The definition the two forms below are held to.
    -> (o [B, H, dv], s)."""
    s = alpha[..., None, None] * s
    u = beta[..., None] * (v - jnp.einsum("bhkv,bhk->bhv", s, k,
                                          precision=_EXACT))
    s = s + k[..., :, None] * u[..., None, :]
    return jnp.einsum("bhkv,bhk->bhv", s, q, precision=_EXACT), s


def delta_turn(s, q, k, v, alpha, beta, r: int):
    """One turn for ONE row on the pool's layout: ``s [H / r, dk, r * dv]``,
    ``q``, ``k [H, dk]``, ``v [H, dv]``, ``alpha``, ``beta [H]``. The state
    is read for ``S^T k`` and ``S^T q`` together (``o = alpha S^T q + (k . q)
    u``: the new state is never read back) and once more for the update.
    -> (o [H, dv], s)."""
    h, dk = k.shape
    dv = v.shape[-1]
    p = h // r
    eye = jnp.eye(r, dtype=F32)
    kq = jnp.stack([k, q], axis=1).reshape(p, r * 2, dk)
    read = jnp.einsum("pnk,pkl->pnl", kq, s, precision=_EXACT)
    # a head's own lanes of its two reads: [p, r, 2, r, dv] -> [H, 2, dv]
    read = jnp.sum(read.reshape(p, r, 2, r, dv)
                   * eye[None, :, None, :, None], axis=3).reshape(h, 2, dv)
    u = beta[:, None] * (v - alpha[:, None] * read[:, 0])
    o = alpha[:, None] * read[:, 1] \
        + jnp.sum(k * q, axis=-1, keepdims=True) * u
    # each head's correction on its own lanes of the group's row
    wide = (u.reshape(p, r, 1, dv) * eye[None, :, :, None]).reshape(
        p, r, r * dv)
    decay = jnp.repeat(alpha.reshape(p, r), dv, axis=-1)[:, None, :]
    s = decay * s + jnp.einsum("pjk,pjl->pkl", k.reshape(p, r, dk), wide,
                               precision=_EXACT)
    return o, s


# -- the block form ---------------------------------------------------------------

def _invert_unit_lower(a):
    """``(I + a)^-1`` for ``a [.., n, n]`` strictly lower triangular, by
    forward substitution a row (``n`` static turns, every leading index at
    once)."""
    n = a.shape[-1]
    rows = [jnp.broadcast_to(jnp.eye(n, dtype=F32)[0], a.shape[:-2] + (n,))]
    for i in range(1, n):
        done = jnp.stack(rows, axis=-2)                   # [.., i, n]
        rows.append(jnp.eye(n, dtype=F32)[i] - jnp.einsum(
            "...s,...sc->...c", a[..., i, :i], done, precision=_EXACT))
    return jnp.stack(rows, axis=-2)


def solve_unit_lower(a, rhs):
    """``U`` of ``(I + a) U = rhs`` for ``a [H, T, T]`` strictly lower
    triangular, ``rhs [H, T, dv]``: the diagonal tiles of ``SOLVE_TILE``
    rows inverted together, then forward substitution a tile."""
    t = a.shape[-1]
    n = min(SOLVE_TILE, t)
    pad = -t % n
    if pad:
        a = jnp.pad(a, ((0, 0), (0, pad), (0, pad)))
        rhs = jnp.pad(rhs, ((0, 0), (0, pad), (0, 0)))
    tiles = (t + pad) // n
    diag = jnp.stack([a[:, i * n:(i + 1) * n, i * n:(i + 1) * n]
                      for i in range(tiles)], axis=1)     # [H, tiles, n, n]
    inv = _invert_unit_lower(diag)
    out = []
    for i in range(tiles):
        b = rhs[:, i * n:(i + 1) * n]
        if i:
            b = b - jnp.einsum("hts,hsv->htv", a[:, i * n:(i + 1) * n, :i * n],
                               jnp.concatenate(out, axis=1),
                               precision=_EXACT)
        out.append(jnp.einsum("hts,hsv->htv", inv[:, i], b,
                              precision=_EXACT))
    return jnp.concatenate(out, axis=1)[:, :t]


def delta_block(s0, q, k, v, g, beta):
    """The block form over ONE row's block of ``T`` positions with the state
    ``s0 [H, dk, dv]`` carried in: ``q``, ``k [T, H, dk]``, ``v [T, H, dv]``,
    ``g [T, H]`` the log of the decay and ``beta [T, H]`` (both 0 at a
    position that is padding: it then neither decays nor corrects the
    state). Equals ``T`` turns of :func:`delta_step`; the state is read once
    and written once. -> (o [T, H, dv], s_T)."""
    t = q.shape[0]
    ell = jnp.cumsum(g, axis=0)                                   # [T, H]
    at = jnp.arange(t)
    causal = at[:, None] >= at[None, :]
    # e^{G_t - G_s} for s <= t (never above 1), heads first: [H, T, S]
    decay = jnp.moveaxis(jnp.exp(jnp.where(
        causal[:, :, None], ell[:, None] - ell[None, :], -jnp.inf)), -1, 0)
    kk = jnp.einsum("thk,shk->hts", k, k, precision=_EXACT)
    a = jnp.where(at[:, None] > at[None, :],
                  beta.T[:, :, None] * kk * decay, 0.0)
    carried = jnp.exp(ell)[..., None]                             # [T, H, 1]
    rhs = beta[..., None] * (v - carried * jnp.einsum(
        "thk,hkv->thv", k, s0, precision=_EXACT))
    u = solve_unit_lower(a, jnp.moveaxis(rhs, 1, 0))              # [H, T, dv]
    qk = jnp.einsum("thk,shk->hts", q, k, precision=_EXACT) * decay
    o = carried * jnp.einsum("thk,hkv->thv", q, s0, precision=_EXACT) \
        + jnp.moveaxis(jnp.einsum("hts,hsv->htv", qk, u, precision=_EXACT),
                       0, 1)
    to_end = jnp.exp(ell[-1][None] - ell)                         # [T, H]
    s = jnp.exp(ell[-1])[:, None, None] * s0 + jnp.einsum(
        "thk,htv->hkv", k * to_end[..., None], u, precision=_EXACT)
    return o, s


# -- the step's rows ----------------------------------------------------------------

def gates(a, b, lp, neg_eigval: bool):
    """``(log alpha, beta)`` float32 from the two head-wide projections:
    ``alpha = exp(-exp(A_log) softplus(a + dt_bias))``, ``beta =
    sigmoid(b)``, doubled where negative eigenvalues are allowed."""
    g = -jnp.exp(lp["A_log"].astype(F32)) * jax.nn.softplus(
        a.astype(F32) + lp["dt_bias"].astype(F32))
    beta = jax.nn.sigmoid(b.astype(F32))
    return g, beta * 2.0 if neg_eigval else beta


def _l2norm(x):
    return x * lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                         + 1e-6)


def delta_rows(qkv, a, b, conv_state, pool, first, lp, nvalid, fresh, *,
               heads: int, key_dim: int, value_dim: int, neg_eigval: bool):
    """A delta layer's conv and rule over the step's rows. ``qkv [B, C, W]``
    (``W = 2 H dk + H dv``: the conv's channels, ``q | k | v``) and ``a``,
    ``b [B, C, H]`` from the projections; ``conv_state [B, taps - 1, W]``
    float32; ``pool [rows, H / r, dk, r * dv]`` float32: the state pool of
    every layer, this layer's ``B`` rows from row ``first`` (updated in
    place: a layer's share is never sliced out whole); ``lp``: ``conv_w
    [taps, W]``, ``A_log``, ``dt_bias [H]``; ``nvalid`` positions a row
    feeds, ``fresh`` rows that start from a zero state. The conv follows the
    rows too: a row that feeds one position convolves that position alone
    (``taps`` inputs), a row that feeds a block convolves its chunk inside
    its turn of the block loop, and a row that feeds nothing keeps its
    inputs; nothing runs over the ``B x C`` grid but the gates. Returns ``(o
    [B, C, H dv] float32, conv_state, pool)``."""
    bsz, c, _ = qkv.shape
    h, dk, dv = heads, key_dim, value_dim
    r = heads_per_row(h, dv)
    taps = lp["conv_w"].shape[0]
    conv_w = lp["conv_w"].astype(F32)
    conv_state = jnp.where(fresh[:, None, None], 0.0, conv_state)

    def split(act):
        """SiLU'd conv output ``[.., W]`` -> q, k (normed), v by head."""
        lead = act.shape[:-1]
        q = _l2norm(act[..., :h * dk].reshape(*lead, h, dk)) * dk ** -0.5
        k = _l2norm(act[..., h * dk:2 * h * dk].reshape(*lead, h, dk))
        return q, k, act[..., 2 * h * dk:].reshape(*lead, h, dv)

    with jax.named_scope("delta_rule"):
        g, beta = gates(a, b, lp, neg_eigval)
        real = (jnp.arange(c)[None, :] < nvalid[:, None])[..., None]
        g, beta = jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0)
    # rows that feed more than one position, first: the block form, one row
    # a turn, its state read from and written to the pool's row
    blocks = nvalid > 1
    order = jnp.argsort(~blocks, stable=True)

    def one_block(j, carry):
        pool, conv_state, o = carry
        row = order[j]
        at = lambda x: lax.dynamic_index_in_dim(x, row, 0, keepdims=False)
        with jax.named_scope("delta_proj"):
            seq = jnp.concatenate([at(conv_state), at(qkv).astype(F32)])
            act = jax.nn.silu(sum(seq[t:t + c] * conv_w[t]
                                  for t in range(taps)))
            q, k, v = split(act)
            kept = lax.dynamic_slice_in_dim(seq, at(nvalid), taps - 1, 0)
            conv_state = lax.dynamic_update_index_in_dim(conv_state, kept,
                                                         row, 0)
        with jax.named_scope("delta_rule"):
            s0 = jnp.where(at(fresh), 0.0, lax.dynamic_index_in_dim(
                pool, first + row, 0, keepdims=False))
            out, s = delta_block(to_heads(s0, r), q, k, v, at(g), at(beta))
            pool = lax.dynamic_update_index_in_dim(
                pool, to_pool(s, r), first + row, 0)
            o = lax.dynamic_update_index_in_dim(
                o, out.reshape(c, h * dv), row, 0)
        return pool, conv_state, o

    pool, blocked_conv, o = lax.fori_loop(
        0, jnp.sum(blocks), one_block,
        (pool, conv_state, jnp.zeros((bsz, c, h * dv), F32)))
    # rows that feed one position: the conv of that position for all of them
    # at once (``taps`` inputs a row), then one turn each, the live ones only
    single = nvalid == 1
    with jax.named_scope("delta_proj"):
        seq = jnp.concatenate([conv_state, qkv[:, :1].astype(F32)], axis=1)
        turn = split(jax.nn.silu(jnp.einsum("btw,tw->bw", seq, conv_w,
                                            precision=_EXACT)))
        conv_state = jnp.where(single[:, None, None], seq[:, 1:],
                               blocked_conv)
    order = jnp.argsort(~single, stable=True)
    turn += (g[:, 0], beta[:, 0])

    def one_turn(j, carry):
        pool, o1 = carry
        row = order[j]
        qr, kr, vr, gr, br = (lax.dynamic_index_in_dim(
            x, row, 0, keepdims=False) for x in turn)
        s0 = jnp.where(fresh[row], 0.0, lax.dynamic_index_in_dim(
            pool, first + row, 0, keepdims=False))
        out, s = delta_turn(s0, qr, kr, vr, jnp.exp(gr), br, r)
        pool = lax.dynamic_update_index_in_dim(pool, s, first + row, 0)
        return pool, lax.dynamic_update_index_in_dim(
            o1, out.reshape(h * dv), row, 0)

    with jax.named_scope("delta_rule"):
        pool, o1 = lax.fori_loop(
            0, jnp.sum(single), one_turn,
            (pool, jnp.zeros((bsz, h * dv), F32)))
        o = o.at[:, 0].set(jnp.where(single[:, None], o1, o[:, 0]))
    return o, conv_state, pool
