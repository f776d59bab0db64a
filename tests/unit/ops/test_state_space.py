"""``ops/state_space.py``: the two kernels in interpret mode against the
recurrence, token by token, and against the chunk form in ``jax.numpy``;
the stacked leaf's rows that do not run; the layout; the convolution's
tail and the padding."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import state_space as ss

# float32, products at "highest": a block's sums agree with the recurrence
# to a few 1e-6 at values of size ~1-10 (measured 4e-6 at worst); 2e-4 is
# far under what a wrong decay, a dropped carried state or a token too many
# does (0.1-10)
TOL = dict(rtol=2e-4, atol=2e-4)


def operands(H, P, N, T, B=3, R=5, L=2, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(k[0], (B, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, T, H)) - 2)
    a = -jnp.exp(jax.random.uniform(k[2], (H,), minval=0, maxval=2.7))
    b = jax.random.normal(k[3], (B, T, N))
    c = jax.random.normal(k[4], (B, T, N))
    h0 = jax.random.normal(k[5], (R, H, P, N))
    s = jnp.zeros((L, R) + ss.state_shape(H, P, N)).at[1].set(
        ss.to_tiles(h0))
    return x, dt, a, b, c, h0, s


def test_the_leaf_is_the_transposed_state_two_heads_of_64_to_a_tile():
    assert ss.state_shape(64, 64, 128) == (32, 128, 128)
    assert ss.state_shape(8, 16, 16) == (1, 16, 128)
    assert ss.state_shape(3, 16, 16) == (1, 16, 48)    # (3 heads: one tile)
    assert ss.state_shape(4, 128, 64) == (4, 64, 128)
    h = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 16, 24))
    tiles = ss.to_tiles(h)
    assert tiles.shape == (2, 1, 24, 128)
    # tile 0, state column n, lane (head g, p)
    assert float(tiles[1, 0, 5, 3 * 16 + 7]) == float(h[1, 3, 7, 5])
    np.testing.assert_array_equal(ss.from_tiles(tiles, 16), h)


@pytest.mark.parametrize("block", [16, 40])
@pytest.mark.parametrize("H,P,N", [(8, 16, 16), (4, 64, 32)])
def test_the_chunk_kernel_is_the_recurrence(H, P, N, block):
    """40 tokens in blocks of 16 (three calls, the state carried in place,
    the last one padded) and in one block of 40: ``y`` and the final state
    of the running rows against the recurrence; the row out of range runs
    nothing; every other row and layer of the leaf comes back bit for bit."""
    T = 40
    x, dt, a, b, c, h0, s = operands(H, P, N, T)
    rows = jnp.array([3, -1, 0])
    fresh = jnp.array([False, False, True])
    y, s2 = jax.jit(lambda *v: ss.ssm_prefill(*v, block=block))(
        x, dt, a, b, c, s, 1, rows, fresh)
    for i in (0, 2):
        start = jnp.where(fresh[i], 0, h0[rows[i]])
        want_y, want_h = ss.ssm_recurrence(x[i], dt[i], a, b[i], c[i], start)
        np.testing.assert_allclose(y[i], want_y, **TOL)
        np.testing.assert_allclose(ss.from_tiles(s2[1, rows[i]], P), want_h,
                                   **TOL)
    idle = jnp.array([1, 2, 4])
    np.testing.assert_array_equal(s2[1, idle], s[1, idle])
    np.testing.assert_array_equal(s2[0], s[0])
    assert (y[1] == 0).all()
    # and the chunk form in jax.numpy, which the forward without a cache
    # runs: one block from the carried state, and whole sequences from none
    plain_y, plain_h = ss.ssm_chunk_plain(x[:1], dt[:1], a, b[:1], c[:1],
                                          h0[3][None])
    np.testing.assert_allclose(plain_y[0], y[0], **TOL)
    np.testing.assert_allclose(plain_h[0], ss.from_tiles(s2[1, 3], P), **TOL)
    np.testing.assert_allclose(
        ss.ssm_sequence(x, dt, a, b, c, block=block)[2], y[2], **TOL)


@pytest.mark.parametrize("H,P,N", [(8, 16, 16), (4, 64, 32), (32, 16, 8)])
def test_the_decode_kernel_is_one_step_of_the_recurrence(H, P, N):
    x, dt, a, b, c, h0, s = operands(H, P, N, 1)
    rows = jnp.array([3, 7, 0])         # (7: out of range)
    fresh = jnp.array([False, False, True])
    y, s2 = jax.jit(ss.ssm_decode)(x[:, 0], dt[:, 0], a, b[:, 0], c[:, 0],
                                   s, 1, rows, fresh)
    for i in (0, 2):
        start = jnp.where(fresh[i], 0, h0[rows[i]])
        want_y, want_h = ss.ssm_recurrence(x[i], dt[i], a, b[i], c[i], start)
        np.testing.assert_allclose(y[i], want_y[0], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(ss.from_tiles(s2[1, rows[i]], P), want_h,
                                   rtol=1e-5, atol=1e-5)
    idle = jnp.array([1, 2, 4])
    np.testing.assert_array_equal(s2[1, idle], s[1, idle])
    np.testing.assert_array_equal(s2[0], s[0])
    assert (y[1] == 0).all()


def test_padding_advances_neither_the_state_nor_the_tail():
    """Tokens at or past ``length`` leave the state where the last real
    token put it, and the convolution's tail is the last three REAL
    inputs, whatever stands in the padding."""
    H, P, N, T = 8, 16, 16, 24
    x, dt, a, b, c, h0, s = operands(H, P, N, T, B=1)
    rows, fresh = jnp.array([2]), jnp.array([False])
    _, full = ss.ssm_prefill(x[:, :10], dt[:, :10], a, b[:, :10], c[:, :10],
                             s, 1, rows, fresh, block=8)
    junk = x.at[:, 10:].set(1e3)
    _, padded = ss.ssm_prefill(junk, dt, a, b, c, s, 1, rows, fresh,
                               length=jnp.array([10]), block=8)
    np.testing.assert_allclose(padded[1, 2], full[1, 2], rtol=1e-6,
                               atol=1e-6)
    k = jax.random.split(jax.random.PRNGKey(3), 4)
    xbc = jax.random.normal(k[0], (2, 12, 6))
    tail = jax.random.normal(k[1], (2, 3, 6))
    w, bias = jax.random.normal(k[2], (4, 6)), jax.random.normal(k[3], (6,))
    out, new = ss.causal_conv(xbc, tail, w, bias, jnp.array([12, 5]))
    seq = np.concatenate([tail, xbc], axis=1)
    want = np.stack([sum(w[j] * seq[:, t + j] for j in range(4)) + bias
                     for t in range(12)], axis=1)
    np.testing.assert_allclose(out, jax.nn.silu(want), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(new[0], xbc[0, 9:12])
    np.testing.assert_array_equal(new[1], xbc[1, 2:5])
    # fewer real tokens than taps: the old tail shifts by what there is
    _, short = ss.causal_conv(xbc, tail, w, bias, jnp.array([1, 0]))
    np.testing.assert_array_equal(
        short[0], jnp.concatenate([tail[0, 1:], xbc[0, :1]]))
    np.testing.assert_array_equal(short[1], tail[1])
