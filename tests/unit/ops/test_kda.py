"""``ops/kda.py``: the two kernels in interpret mode against the delta
rule's recurrence, token by token, and against the chunk form in
``jax.numpy``: decays near 0 and near 1, a chunk's padded tail, the state
carried over chunks, the stacked leaf's rows that do not run, the pairs
and the inverse the chunk form is made with."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import kda

# float32, products at "highest": a block's sums agree with the recurrence
# to a few 1e-6 at outputs of size ~0.1-1 and states of size ~1 (measured
# 3e-6 at worst, PR 50); 1e-4 is far under what a wrong decay, a dropped
# carried state, beta left out of the solve or a token too many does
# (0.05-1)
TOL = dict(rtol=1e-4, atol=1e-4)
# per-token log decays: hardly any (a state that never forgets), the seeded
# model's, and one under which exp(-G) of a 40-token block overflows
# float32 (exp(30 x 40) = inf): a quotient of two exponentials would be nan
DECAYS = {"near_1": 1e-4, "seeded": 0.3, "near_0": 30.0}


def operands(H, K, V, T, decay, B=3, R=5, L=2, seed=0):
    key = jax.random.split(jax.random.PRNGKey(seed), 6)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(jax.random.normal(key[0], (B, T, H, K))) / K ** 0.5
    k = unit(jax.random.normal(key[1], (B, T, H, K)))
    v = jax.random.normal(key[2], (B, T, H, V))
    g = -decay * jnp.abs(jax.random.normal(key[3], (B, T, H, K)))
    beta = jax.nn.sigmoid(jax.random.normal(key[4], (B, T, H)))
    s0 = jax.random.normal(key[5], (R, H, K, V))
    leaf = jnp.zeros((L, R, H, K, V)).at[1].set(s0)
    return (q, k, v, g, beta), s0, leaf


def recurrence(ops, i, start, upto=None):
    return kda.kda_recurrence(*(x[i, :upto] for x in ops), start)


@pytest.mark.parametrize("decay", sorted(DECAYS))
@pytest.mark.parametrize("block", [16, 48])
@pytest.mark.parametrize("H,K,V", [(2, 16, 8), (4, 32, 32)])
def test_the_chunk_kernel_is_the_recurrence(H, K, V, block, decay):
    """40 tokens in blocks of 16 (three calls, the state carried in place,
    the last one padded) and in one block of 48: ``o`` and the final state
    of the running rows against the recurrence; the row out of range runs
    nothing; every other row and layer of the leaf comes back bit for bit."""
    T = 40
    ops, s0, leaf = operands(H, K, V, T, DECAYS[decay])
    rows = jnp.array([3, -1, 0])
    fresh = jnp.array([False, False, True])
    o, leaf2 = jax.jit(lambda *a: kda.kda_prefill(*a, block=block))(
        *ops, leaf, 1, rows, fresh)
    assert np.isfinite(np.asarray(o)).all()
    for i in (0, 2):
        start = jnp.where(fresh[i], 0, s0[rows[i]])
        want_o, want_s = recurrence(ops, i, start)
        np.testing.assert_allclose(o[i], want_o, **TOL)
        np.testing.assert_allclose(leaf2[1, rows[i]], want_s, **TOL)
    idle = jnp.array([1, 2, 4])
    np.testing.assert_array_equal(leaf2[1, idle], leaf[1, idle])
    np.testing.assert_array_equal(leaf2[0], leaf[0])
    assert (o[1] == 0).all()
    # and the chunk form in jax.numpy, which the forward without a cache
    # runs: one block from the carried state, and whole sequences from none
    head = [x[:1, :32] for x in ops]
    plain_o, plain_s = kda.kda_chunk_plain(*head, s0[3][None])
    want_o, want_s = recurrence(ops, 0, s0[3], 32)
    np.testing.assert_allclose(plain_o[0], want_o, **TOL)
    np.testing.assert_allclose(plain_s[0], want_s, **TOL)
    np.testing.assert_allclose(
        kda.kda_sequence(*ops, block=block)[2], o[2], **TOL)


@pytest.mark.parametrize("decay", sorted(DECAYS))
@pytest.mark.parametrize("H,K,V", [(2, 16, 8), (4, 32, 32), (32, 8, 16)])
def test_the_decode_kernel_is_one_step_of_the_recurrence(H, K, V, decay):
    ops, s0, leaf = operands(H, K, V, 1, DECAYS[decay])
    rows = jnp.array([3, 7, 0])         # (7: out of range)
    fresh = jnp.array([False, False, True])
    o, leaf2 = jax.jit(kda.kda_decode)(*(x[:, 0] for x in ops), leaf, 1,
                                       rows, fresh)
    for i in (0, 2):
        start = jnp.where(fresh[i], 0, s0[rows[i]])
        want_o, want_s = recurrence(ops, i, start)
        np.testing.assert_allclose(o[i], want_o[0], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(leaf2[1, rows[i]], want_s, rtol=1e-5,
                                   atol=1e-5)
    idle = jnp.array([1, 2, 4])
    np.testing.assert_array_equal(leaf2[1, idle], leaf[1, idle])
    np.testing.assert_array_equal(leaf2[0], leaf[0])
    assert (o[1] == 0).all()


def test_decode_after_chunks_is_the_recurrence_over_all_of_it():
    """A prompt in two chunks, then three tokens one at a time, in place:
    the state a chunk leaves is what a token reads."""
    H, K, V, T = 2, 16, 16, 35
    ops, s0, leaf = operands(H, K, V, T, DECAYS["seeded"], B=1)
    rows, no = jnp.array([2]), jnp.array([False])
    _, leaf = kda.kda_prefill(*(x[:, :32] for x in ops), leaf, 1, rows,
                              jnp.array([True]), block=16)
    got = []
    for t in range(32, T):
        o, leaf = kda.kda_decode(*(x[:, t] for x in ops), leaf, 1, rows, no)
        got.append(o[0])
    want_o, want_s = recurrence(ops, 0, jnp.zeros_like(s0[0]))
    np.testing.assert_allclose(jnp.stack(got), want_o[32:], **TOL)
    np.testing.assert_allclose(leaf[1, 2], want_s, **TOL)


def test_padding_leaves_the_state_where_the_last_real_token_put_it():
    H, K, V, T = 2, 16, 8, 24
    ops, s0, leaf = operands(H, K, V, T, DECAYS["seeded"], B=1)
    rows, fresh = jnp.array([2]), jnp.array([False])
    _, full = kda.kda_prefill(*(x[:, :10] for x in ops), leaf, 1, rows,
                              fresh, block=16)
    junk = [x.at[:, 10:].set(7.0) for x in ops[:3]] + list(ops[3:])
    o, padded = kda.kda_prefill(*junk, leaf, 1, rows, fresh,
                                length=jnp.array([10]), block=16)
    np.testing.assert_allclose(padded[1, 2], full[1, 2], rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_allclose(o[0, :10], recurrence(ops, 0, s0[2], 10)[0],
                               **TOL)


def test_the_pairs_and_the_inverse_are_what_they_say():
    """``_pairs``: the channel-wise sums as written, whatever the
    sub-block; ``_unit_lower_inverse``: the inverse, at 16 (the series
    alone), 48 (an uneven split) and 128 rows, with entries near 1 (equal
    keys, no decay: the case in which powers of ``a`` grow)."""
    key = jax.random.split(jax.random.PRNGKey(0), 4)
    T, K = 32, 8
    a, b = (jax.random.normal(k, (3, T, K)) for k in key[:2])
    G = jnp.cumsum(-5.0 * jnp.abs(jax.random.normal(key[2], (3, T, K))), 1)
    low = np.tril(np.ones((T, T), bool))
    want = np.where(low, np.einsum(
        "brc,bic,bric->bri", a, b, np.exp(np.where(
            low[None, ..., None], G[:, :, None] - G[:, None], -np.inf))), 0)
    for sub in (8, 16, 32):
        np.testing.assert_allclose(kda._pairs(a, b, G, sub), want,
                                   rtol=1e-5, atol=1e-5)
    for n in (16, 48, 128):
        strict = np.tril(np.ones((n, n), np.float32), -1)
        # (the second: a block's series passes through binomials of 15
        # before they cancel, ~5e3 x float32's 1e-7; a seeded model's
        # entries are ~0.05)
        for lower, atol in (
                (0.1 * jax.random.normal(key[3], (2, n, n)) * strict, 1e-5),
                (0.97 * strict[None], 5e-3)):
            inv = kda._unit_lower_inverse(jnp.asarray(lower), 16)
            np.testing.assert_allclose(
                inv @ (np.eye(n) + lower), np.broadcast_to(np.eye(n),
                                                           lower.shape),
                atol=atol)
            assert (np.triu(np.asarray(inv), 1) == 0).all()
