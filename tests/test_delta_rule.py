"""The gated delta rule (``ray_tpu/ops/delta_rule.py``) on the CPU at toy
widths: the block form (chunkwise WY) and the one turn on the pool's layout
against the token-by-token recurrence, the triangular solve, the pool's
layout, and ``delta_rows`` over a step's rows (ragged ``nvalid``, rows on
both forms in one step, a row resumed mid-prompt, a fresh row, ``beta``
past 1)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import delta_rule as dr

H, DK, DV = 4, 8, 64           # two heads fill a 128-lane row: r = 2
#: float32 against float32: what is left is the order of the sums
TOL = 2e-5


def _draws(key, t, beta_max=2.0, heads=H, dk=DK, dv=DV):
    """(q, k, v, log alpha, beta) of ``t`` positions; the keys share a
    direction, as they do behind a SiLU."""
    ks = jax.random.split(key, 6)
    q = dr._l2norm(jax.random.normal(ks[0], (t, heads, dk))) * dk ** -0.5
    k = dr._l2norm(jax.random.normal(ks[1], (t, heads, dk)) + 1.5)
    v = jax.random.normal(ks[2], (t, heads, dv))
    g = -jax.random.uniform(ks[3], (t, heads)) * 0.7
    beta = jax.random.uniform(ks[4], (t, heads)) * beta_max
    return q, k, v, g, beta


@jax.jit
def _recurrence(s0, q, k, v, g, beta):
    """``delta_step`` a position: (o [T, H, dv], the state after)."""
    def one(s, x):
        q_t, k_t, v_t, g_t, b_t = (a[None] for a in x)
        o, s = dr.delta_step(s, q_t, k_t, v_t, jnp.exp(g_t), b_t)
        return s, o[0]

    s, o = jax.lax.scan(one, s0[None], (q, k, v, g, beta))
    return o, s[0]


_block = jax.jit(dr.delta_block)


@pytest.mark.parametrize("t", [1, 2, 3, 7, 15, 16, 17, 31, 32, 33, 48, 63,
                               64])
def test_the_block_form_equals_the_recurrence(t):
    key = jax.random.PRNGKey(t)
    q, k, v, g, beta = _draws(key, t)
    s0 = jax.random.normal(jax.random.fold_in(key, 9), (H, DK, DV))
    want_o, want_s = _recurrence(s0, q, k, v, g, beta)
    o, s = _block(s0, q, k, v, g, beta)
    np.testing.assert_allclose(o, want_o, atol=TOL)
    np.testing.assert_allclose(s, want_s, atol=TOL)


@pytest.mark.parametrize("beta_max", [1.0, 2.0])
def test_the_block_form_holds_with_beta_past_one(beta_max):
    """``beta`` in (1, 2) makes ``I - beta k k^T`` reflect (a negative
    eigenvalue): the recurrence no longer contracts, and the solve has to
    follow it all the same."""
    key = jax.random.PRNGKey(5)
    q, k, v, g, _ = _draws(key, 64)
    beta = jnp.full((64, H), beta_max * 0.97)
    s0 = jnp.zeros((H, DK, DV))
    want_o, want_s = _recurrence(s0, q, k, v, g, beta)
    o, s = _block(s0, q, k, v, g, beta)
    np.testing.assert_allclose(o, want_o, atol=5 * TOL)
    np.testing.assert_allclose(s, want_s, atol=5 * TOL)


@pytest.mark.parametrize("nvalid", [1, 5, 16, 40])
def test_padding_neither_decays_nor_corrects_the_state(nvalid):
    """Positions past ``nvalid`` carry ``g = 0`` and ``beta = 0``: the state
    handed on is the state after ``nvalid`` positions."""
    key = jax.random.PRNGKey(nvalid)
    q, k, v, g, beta = _draws(key, 48)
    real = (jnp.arange(48) < nvalid)[:, None]
    s0 = jax.random.normal(jax.random.fold_in(key, 1), (H, DK, DV))
    want_o, want_s = _recurrence(s0, *(x[:nvalid] for x in
                                       (q, k, v, g, beta)))
    o, s = _block(s0, q, k, v, jnp.where(real, g, 0.0),
                          jnp.where(real, beta, 0.0))
    np.testing.assert_allclose(o[:nvalid], want_o, atol=TOL)
    np.testing.assert_allclose(s, want_s, atol=TOL)


@pytest.mark.parametrize("t", [1, 5, 16, 23, 64])
def test_the_triangular_solve(t):
    key = jax.random.PRNGKey(t)
    # (entries of the rule's own size: beta (k . k') e^{..}, under 2 and
    # mostly far under; a wilder matrix has no bounded solution to compare)
    a = jnp.tril(jax.random.normal(key, (H, t, t)) * 0.15, -1)
    rhs = jax.random.normal(jax.random.fold_in(key, 1), (H, t, DV))
    u = dr.solve_unit_lower(a, rhs)
    want = jax.scipy.linalg.solve_triangular(
        a + jnp.eye(t), rhs, lower=True, unit_diagonal=True)
    np.testing.assert_allclose(u, want, atol=1e-4 * float(
        jnp.abs(want).max()))


@pytest.mark.parametrize("heads,dv,r", [(4, 64, 2), (30, 192, 2), (4, 32, 4),
                                        (3, 64, 1), (4, 128, 1), (4, 24, 1)])
def test_the_pools_layout_fills_whole_lanes_where_it_can(heads, dv, r):
    assert dr.heads_per_row(heads, dv) == r
    s = jax.random.normal(jax.random.PRNGKey(0), (2, heads, DK, dv))
    pooled = dr.to_pool(s, r)
    assert pooled.shape == (2, heads // r, DK, r * dv)
    np.testing.assert_array_equal(dr.to_heads(pooled, r), s)
    # head ``j`` of a group lies on lanes ``[j dv, (j + 1) dv)`` of its row
    np.testing.assert_array_equal(pooled[:, 0, :, :dv], s[:, 0])
    if r > 1:
        np.testing.assert_array_equal(pooled[:, 0, :, dv:2 * dv], s[:, 1])


@pytest.mark.parametrize("r", [1, 2, 4])
def test_the_turn_on_the_pools_layout_equals_the_recurrence(r):
    key = jax.random.PRNGKey(r)
    q, k, v, g, beta = _draws(key, 1)
    s0 = jax.random.normal(jax.random.fold_in(key, 2), (H, DK, DV))
    want_o, want_s = _recurrence(s0, q, k, v, g, beta)
    o, s = dr.delta_turn(dr.to_pool(s0, r), q[0], k[0], v[0],
                         jnp.exp(g[0]), beta[0], r)
    np.testing.assert_allclose(o, want_o[0], atol=TOL)
    np.testing.assert_allclose(dr.to_heads(s, r), want_s, atol=TOL)


# -- the step's rows ----------------------------------------------------------

TAPS = 4
W = 2 * H * DK + H * DV


def _layer(key):
    ks = jax.random.split(key, 3)
    return {"conv_w": jax.random.normal(ks[0], (TAPS, W)) * 0.5,
            "A_log": jax.random.normal(ks[1], (H,)) * 0.1,
            "dt_bias": jax.random.normal(ks[2], (H,))}


def _rows_reference(qkv, a, b, lp, n):
    """One row's first ``n`` positions from a zero state, a position at a
    time: the conv as a sum over taps, the rule as ``delta_step``."""
    pad = jnp.concatenate([jnp.zeros((TAPS - 1, W)), qkv[:n]])
    act = jax.nn.silu(sum(pad[j:j + n] * lp["conv_w"][j]
                          for j in range(TAPS)))
    q = dr._l2norm(act[:, :H * DK].reshape(n, H, DK)) * DK ** -0.5
    k = dr._l2norm(act[:, H * DK:2 * H * DK].reshape(n, H, DK))
    v = act[:, 2 * H * DK:].reshape(n, H, DV)
    g, beta = dr.gates(a[:n], b[:n], lp, True)
    o, s = _recurrence(jnp.zeros((H, DK, DV)), q, k, v, g, beta)
    return o.reshape(n, H * DV), s, pad[n:n + TAPS - 1]


@jax.jit
def _rows(pool, conv, first, lp, qkv, a, b, nvalid, fresh):
    return dr.delta_rows(qkv, a, b, conv, pool, first, lp, nvalid, fresh,
                         heads=H, key_dim=DK, value_dim=DV, neg_eigval=True)


def _step(pool, conv, first, lp, qkv, a, b, nvalid, fresh):
    return _rows(pool, conv, first, lp, qkv, a, b, jnp.asarray(nvalid),
                 jnp.asarray(fresh))


@pytest.mark.parametrize("chunk", [1, 3, 8, 16, 64])
def test_rows_on_both_forms_in_one_step_agree_with_the_recurrence(chunk):
    """Four slots of a layer that is the SECOND of a pool of three: a row
    that feeds a whole chunk, one that feeds a ragged part of it, one that
    decodes (one turn) and one that feeds nothing. Every live row's output,
    state and conv inputs are the recurrence's; the idle row's and the other
    layers' states are untouched."""
    key = jax.random.PRNGKey(chunk)
    lp = _layer(key)
    r = dr.heads_per_row(H, DV)
    slots, total = 4, 2 * chunk + 3
    ks = jax.random.split(jax.random.fold_in(key, 7), 3)
    qkv = jax.random.normal(ks[0], (slots, total, W))
    a = jax.random.normal(ks[1], (slots, total, H))
    b = jax.random.normal(ks[2], (slots, total, H)) * 2
    marker = jax.random.normal(key, (3 * slots, H // r, DK, r * DV))
    pool, conv = marker, jnp.zeros((slots, TAPS - 1, W))
    first = slots                    # the middle layer's rows
    # what each row has behind it: (positions cached, positions fed now)
    plan = [(0, chunk), (chunk, max(chunk // 2, 1)), (chunk + 2, 1), (5, 0)]
    # bring the rows to where the step finds them, a position at a time
    for slot, (pos, _) in enumerate(plan):
        for t in range(pos):
            nv = [0] * slots
            nv[slot] = 1
            fresh = [False] * slots
            fresh[slot] = t == 0
            pad = lambda x: jnp.zeros((slots, chunk) + x.shape[2:]).at[
                :, 0].set(x[:, t])
            _, conv, pool = _step(pool, conv, first, lp, pad(qkv), pad(a),
                                  pad(b), nv, fresh)
    before = pool
    nvalid = [n for _, n in plan]
    cut = lambda x: jnp.stack([jnp.zeros((chunk,) + x.shape[2:]).at[:n].set(
        x[slot, pos:pos + n]) for slot, (pos, n) in enumerate(plan)])
    o, new_conv, pool = _step(pool, conv, first, lp, cut(qkv), cut(a),
                              cut(b), nvalid, [p == 0 for p, _ in plan])
    for slot, (pos, n) in enumerate(plan):
        if not n:
            continue
        want_o, want_s, want_conv = _rows_reference(
            qkv[slot], a[slot], b[slot], lp, pos + n)
        np.testing.assert_allclose(o[slot, :n], want_o[pos:], atol=5 * TOL)
        np.testing.assert_allclose(dr.to_heads(pool[first + slot], r),
                                   want_s, atol=5 * TOL)
        np.testing.assert_allclose(new_conv[slot], want_conv, atol=TOL)
    np.testing.assert_array_equal(pool[first + 3], before[first + 3])
    np.testing.assert_array_equal(pool[:first], marker[:first])
    np.testing.assert_array_equal(pool[2 * slots:], marker[2 * slots:])


def test_a_fresh_row_starts_from_zero_whatever_its_slot_held():
    key = jax.random.PRNGKey(3)
    lp = _layer(key)
    r = dr.heads_per_row(H, DV)
    qkv = jax.random.normal(key, (2, 8, W))
    a = b = jnp.zeros((2, 8, H))
    dirty = jnp.ones((2, H // r, DK, r * DV)) * 7.0
    conv = jnp.ones((2, TAPS - 1, W)) * 3.0
    for nvalid in ([8, 1], [1, 8]):
        o, _, pool = _step(dirty, conv, 0, lp, qkv, a, b, nvalid,
                           [True, True])
        for slot, n in enumerate(nvalid):
            want_o, want_s, _ = _rows_reference(qkv[slot], a[slot], b[slot],
                                                lp, n)
            np.testing.assert_allclose(o[slot, :n], want_o, atol=TOL)
            np.testing.assert_allclose(dr.to_heads(pool[slot], r), want_s,
                                       atol=TOL)
