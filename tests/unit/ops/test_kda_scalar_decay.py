"""``ops/kda.py`` under ONE log decay a head (Gated DeltaNet, PR 60): the
scalar-decay preparation of the chunk form against the recurrence token by
token and against the channel-decay chunk form with the decay broadcast
(``kda_chunk_plain``), value heads sharing their key head's ``q`` and
``k``; the two kernels under a DeltaNet layer's names, in interpret mode,
on the stacked leaf: decays near 0 and near 1, a chunk's padded tail, the
state carried over blocks, the rows that do not run."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import kda

# (the tolerance and its reason, the decays and theirs)
from tests.unit.ops.test_kda import DECAYS, TOL, recurrence


def operands(Hk, rep, K, V, T, decay, B=3, R=5, L=2, seed=0):
    """``(q, k (B, T, Hk, K), v (B, T, H, V), g, beta (B, T, H))``, a state
    a row and the stacked leaf that holds it in layer 1."""
    H = Hk * rep
    key = jax.random.split(jax.random.PRNGKey(seed), 6)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(jax.random.normal(key[0], (B, T, Hk, K))) / K ** 0.5
    k = unit(jax.random.normal(key[1], (B, T, Hk, K)))
    v = jax.random.normal(key[2], (B, T, H, V))
    g = -decay * jnp.abs(jax.random.normal(key[3], (B, T, H)))
    beta = jax.nn.sigmoid(jax.random.normal(key[4], (B, T, H)))
    s0 = jax.random.normal(key[5], (R, H, K, V))
    leaf = jnp.zeros((L, R, H, K, V)).at[1].set(s0)
    return (q, k, v, g, beta), s0, leaf


def by_channel(ops):
    """The same operands as the channel-decay rule reads them: ``q``, ``k``
    repeated a value head, the decay broadcast over the channels."""
    q, k, v, g, beta = ops
    rep = v.shape[2] // q.shape[2]
    q, k = (jnp.repeat(x, rep, axis=2) for x in (q, k))
    return q, k, v, jnp.broadcast_to(g[..., None], k.shape), beta


@pytest.mark.parametrize("decay", sorted(DECAYS))
@pytest.mark.parametrize("Hk,rep,K,V", [(2, 2, 16, 16), (1, 4, 32, 8),
                                        (2, 1, 16, 8)])
def test_the_scalar_decay_form_is_the_recurrence_and_the_broadcast_rule(
        Hk, rep, K, V, decay):
    """One block of 32 tokens from a carried state, in ``jax.numpy``: the
    scalar-decay preparation (one product a key head and a decay mask)
    against the recurrence, and against the channel-decay preparation (a
    sum a channel at a time) handed the decay broadcast: the same ``o``
    and the same state. The operands the kernel is handed agree one by
    one."""
    ops, s0, _ = operands(Hk, rep, K, V, 32, DECAYS[decay])
    start = s0[:3]
    o, s = kda.kda_chunk_plain(*ops, start)
    wide = by_channel(ops)
    o_wide, s_wide = kda.kda_chunk_plain(*wide, start)
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(o, o_wide, **TOL)
    np.testing.assert_allclose(s, s_wide, **TOL)
    for i in range(3):
        want_o, want_s = recurrence(wide, i, start[i])
        np.testing.assert_allclose(o[i], want_o, **TOL)
        np.testing.assert_allclose(s[i], want_s, **TOL)
        # (the recurrence takes a decay a head and q, k a key head too)
        narrow_o, narrow_s = recurrence(ops, i, start[i])
        np.testing.assert_array_equal(narrow_o, want_o)
        np.testing.assert_array_equal(narrow_s, want_s)
    heads = [jnp.swapaxes(x, 1, 2) for x in ops]
    wide_heads = [jnp.swapaxes(x, 1, 2) for x in wide]
    for got, want in zip(kda._chunk_operands(*heads),
                         kda._chunk_operands(*wide_heads)):
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, **TOL)


@pytest.mark.parametrize("decay", sorted(DECAYS))
@pytest.mark.parametrize("block", [16, 48])
def test_the_chunk_kernel_under_a_decay_a_head_is_the_recurrence(block,
                                                                 decay):
    """40 tokens in blocks of 16 (three calls, the state carried in place,
    the last one padded) and in one block of 48, two value heads a key
    head, under the name a DeltaNet layer gives the call: ``o`` and the
    final state of the running rows against the recurrence; the row out of
    range runs nothing; every other row and layer comes back bit for bit;
    and whole sequences from an empty state (the forward without a
    cache)."""
    ops, s0, leaf = operands(2, 2, 16, 16, 40, DECAYS[decay])
    rows = jnp.array([3, -1, 0])
    fresh = jnp.array([False, False, True])
    o, leaf2 = jax.jit(lambda *a: kda.kda_prefill(
        *a, block=block, name="gdn_chunk"))(*ops, leaf, 1, rows, fresh)
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
    np.testing.assert_allclose(
        kda.kda_sequence(*ops, block=block)[2], o[2], **TOL)


@pytest.mark.parametrize("decay", sorted(DECAYS))
@pytest.mark.parametrize("Hk,rep,K,V", [(2, 2, 16, 8), (8, 2, 8, 8)])
def test_the_decode_kernel_under_a_decay_a_head_is_one_step(Hk, rep, K, V,
                                                            decay):
    ops, s0, leaf = operands(Hk, rep, K, V, 1, DECAYS[decay])
    rows = jnp.array([4, 7, 1])         # (7: out of range, does not run)
    fresh = jnp.array([False, False, True])
    o, leaf2 = jax.jit(lambda *a: kda.kda_decode(*a, name="gdn_decode"))(
        *(x[:, 0] for x in ops), leaf, 1, rows, fresh)
    for i in (0, 2):
        start = jnp.where(fresh[i], 0, s0[rows[i]])
        want_o, want_s = recurrence(ops, i, start)
        np.testing.assert_allclose(o[i], want_o[0], **TOL)
        np.testing.assert_allclose(leaf2[1, rows[i]], want_s, **TOL)
    assert (o[1] == 0).all()
    np.testing.assert_array_equal(leaf2[1, jnp.array([0, 2, 3])],
                                  leaf[1, jnp.array([0, 2, 3])])


def test_padding_leaves_the_state_where_the_last_real_token_put_it():
    """A row's tokens at or past its ``length`` are padding (their ``g``
    and ``beta`` are zeroed, one number a head): the state stands where
    the last real token put it, whatever the padding holds."""
    ops, s0, leaf = operands(2, 2, 16, 16, 32, DECAYS["seeded"], B=2)
    rows, fresh = jnp.array([0, 1]), jnp.zeros((2,), bool)
    length = jnp.array([32, 20])
    o, leaf2 = kda.kda_prefill(*ops, leaf, 1, rows, fresh, length=length,
                               name="gdn_chunk")
    want_o, want_s = recurrence(ops, 1, s0[1], 20)
    np.testing.assert_allclose(o[1, :20], want_o, **TOL)
    np.testing.assert_allclose(leaf2[1, 1], want_s, **TOL)
