"""``ops/attention/power_retention.py`` in interpret mode on the CPU: the
feature map, the three forms of the layer against each other, and what the
kernels leave alone."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention import power_retention as pr

# float32 at "highest" on every side, sums in another order: the state
# form reaches a row's summed weight as (d/2 + 1) d signed feature products
# where the attention form adds squares, so a sum carries ~1e-7 of
# |phi(q)| |z| whatever its own size. Outputs of size ~1 agree to a few
# 1e-6 in most rows and to ~1e-4 in a row whose summed weight is small (a
# first token whose one (q . k) ** 2 is near 0); one bf16 rounding is 4e-3
ATOL = 2e-4
RTOL = 1e-3


def _inputs(B, T, KV, rep, d, seed=0):
    rng = np.random.default_rng(seed)
    q = rng.normal(size=(B, T, KV * rep, d)).astype(np.float32)
    k = rng.normal(size=(B, T, KV, d)).astype(np.float32)
    v = rng.normal(size=(B, T, KV, d)).astype(np.float32)
    log_g = np.log(rng.uniform(0.8, 0.999, size=(B, T, KV))) \
        .astype(np.float32)
    return q, k, v, log_g


def _stale(L, R, KV, d, seed=1):
    """A pool's leaf holding what earlier requests left."""
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(L, R, KV) + pr.state_shape(d)),
                       jnp.float32)


def _parts(state):
    """``(s (n, d, d), z (n, d))`` of one KV head's ``(n + 1, d, d)``."""
    n = state.shape[0] - 1
    return state[:n], state[n, :n]


def _zero(d):
    n = d // 2 + 1
    return jnp.zeros((n, d, d)), jnp.zeros((n, d))


@pytest.mark.parametrize("d", [16, 128])
def test_feature_map_squares_the_dot_product(d):
    rng = np.random.default_rng(d)
    a, b = rng.normal(size=(2, 7, d)).astype(np.float32)
    assert pr.feature_map(a).shape == (7, d // 2 + 1, d)
    got = jnp.einsum("tri,tri->t", pr.feature_map(a), pr.feature_map(b),
                     precision="highest")
    # 1e-7 of |phi(a)| |phi(b)| = |a|^2 |b|^2 ~ d^2 is the sum's rounding
    np.testing.assert_allclose(got, (a * b).sum(-1) ** 2, rtol=1e-5,
                               atol=1e-7 * d * d)
    # D_phi: within 6 % of the symmetric square's d (d + 1) / 2
    assert (d // 2 + 1) * d <= 1.06 * d * (d + 1) / 2 or d < 64


@pytest.mark.parametrize("chunk", [1, 16, 128])
def test_chunk_form_is_attention_form_is_token_recurrence(chunk):
    """Two chunks after a carried state, GQA (two query heads a KV head),
    rows of the pool out of order: the kernels against the attention form
    over the whole sequence and the state against the recurrence's."""
    B, KV, rep, d, L, R = 2, 2, 2, 16, 2, 4
    lead = 24                         # tokens before, by chunks of 8
    T = lead + 2 * chunk
    q, k, v, log_g = _inputs(B, T, KV, rep, d)
    want = pr.retention_attention(q, k, v, log_g)
    s = _stale(L, R, KV, d)
    rows, layer = jnp.asarray([2, 0], jnp.int32), 1
    outs, at = [], 0
    for width in (8, 8, 8, chunk, chunk):
        cut = slice(at, at + width)
        fresh = jnp.full((B,), at == 0)
        if width == 1:
            o, s = pr.retention_decode(q[:, at], k[:, at], v[:, at],
                                          log_g[:, at], s, layer, rows,
                                          fresh)
            o = o[:, None]
        else:
            o, s = pr.retention_chunk(q[:, cut], k[:, cut], v[:, cut],
                                         log_g[:, cut], s, layer, rows,
                                         fresh)
        outs.append(o)
        at += width
    np.testing.assert_allclose(jnp.concatenate(outs, 1), want, atol=ATOL, rtol=RTOL)
    scale = d ** -0.25
    zero = _zero(d)
    for b, row in enumerate((2, 0)):
        for j in range(KV):
            o_r, s_r, z_r = pr.retention_recurrence(
                jnp.asarray(q[b, :, j * rep:(j + 1) * rep]) * scale,
                jnp.asarray(k[b, :, j]) * scale, jnp.asarray(v[b, :, j]),
                jnp.asarray(log_g[b, :, j]), *zero)
            np.testing.assert_allclose(
                o_r, want[b, :, j * rep:(j + 1) * rep], atol=ATOL, rtol=RTOL)
            s_k, z_k = _parts(s[layer, row, j])
            np.testing.assert_allclose(s_k, s_r, atol=ATOL, rtol=RTOL)
            np.testing.assert_allclose(z_k, z_r, atol=ATOL, rtol=RTOL)


def test_plain_chunk_form_carries_a_state():
    d, rep, T = 16, 2, 12
    q, k, v, log_g = _inputs(1, 2 * T, 1, rep, d, seed=3)
    scale = d ** -0.25
    args = (jnp.asarray(q[0]) * scale, jnp.asarray(k[0, :, 0]) * scale,
            jnp.asarray(v[0, :, 0]), jnp.asarray(log_g[0, :, 0]))
    zero = _zero(d)
    o1, s, z = pr.retention_chunk_plain(*(a[:T] for a in args), *zero)
    o2, s, z = pr.retention_chunk_plain(*(a[T:] for a in args), s, z)
    o_r, s_r, z_r = pr.retention_recurrence(*args, *zero)
    np.testing.assert_allclose(jnp.concatenate([o1, o2]), o_r, atol=ATOL, rtol=RTOL)
    np.testing.assert_allclose(s, s_r, atol=ATOL, rtol=RTOL)
    np.testing.assert_allclose(z, z_r, atol=ATOL, rtol=RTOL)


def test_served_head_width_in_interpret_mode():
    """Heads of 128 with five query heads a KV head, the served block
    shapes: a chunk of 8 from nothing, then two tokens."""
    B, KV, rep, d = 1, 1, 5, 128
    q, k, v, log_g = _inputs(B, 10, KV, rep, d, seed=5)
    want = pr.retention_attention(q, k, v, log_g)
    s = _stale(1, 1, KV, d)
    rows = jnp.zeros((1,), jnp.int32)
    o, s = pr.retention_chunk(q[:, :8], k[:, :8], v[:, :8], log_g[:, :8],
                                 s, 0, rows, jnp.ones((1,), bool))
    outs = [o]
    for t in (8, 9):
        o, s = pr.retention_decode(q[:, t], k[:, t], v[:, t], log_g[:, t],
                                      s, 0, rows, jnp.zeros((1,), bool))
        outs.append(o[:, None])
    np.testing.assert_allclose(jnp.concatenate(outs, 1), want, atol=ATOL, rtol=RTOL)


@pytest.mark.parametrize("tokens", [1, 8])
def test_rows_outside_the_work_list_are_bitwise_untouched(tokens):
    B, KV, rep, d, L, R = 3, 2, 2, 16, 2, 5
    q, k, v, log_g = _inputs(B, tokens, KV, rep, d, seed=7)
    s0 = _stale(L, R, KV, d)
    rows = jnp.asarray([3, -1, 1], jnp.int32)     # entry 1 does not run
    fresh = jnp.zeros((B,), bool)
    if tokens == 1:
        o, s = pr.retention_decode(q[:, 0], k[:, 0], v[:, 0],
                                      log_g[:, 0], s0 + 0, 1, rows,
                                      fresh)
    else:
        o, s = pr.retention_chunk(q, k, v, log_g, s0 + 0, 1, rows, fresh)
    s, s0 = np.asarray(s), np.asarray(s0)
    assert np.array_equal(s[0], s0[0])
    for row in (0, 2, 4):                          # in no entry's name
        assert np.array_equal(s[1, row], s0[1, row])
    for row in (3, 1):
        assert not np.array_equal(s[1, row], s0[1, row])
    assert not np.asarray(o[1]).any()              # what did not run: 0
    # nobody runs: a grid of no step, the leaves as they came
    nobody = jnp.full((B,), -1, jnp.int32)
    if tokens == 1:
        _, s = pr.retention_decode(q[:, 0], k[:, 0], v[:, 0], log_g[:, 0],
                                      s0 + 0, 1, nobody, fresh)
    else:
        _, s = pr.retention_chunk(q, k, v, log_g, s0 + 0, 1, nobody, fresh)
    assert np.array_equal(np.asarray(s), s0)


@pytest.mark.parametrize("tokens", [1, 8])
def test_a_row_at_position_zero_reads_no_state(tokens):
    """What the row held, NaN included, is replaced, not decayed."""
    B, KV, rep, d = 1, 2, 2, 16
    q, k, v, log_g = _inputs(B, tokens, KV, rep, d, seed=9)
    want = pr.retention_attention(q, k, v, log_g)
    s = _stale(1, 2, KV, d)
    s = s.at[0, 1, 0, 3].set(jnp.nan).at[0, 1, 1, -1].set(jnp.inf)
    rows, fresh = jnp.asarray([1], jnp.int32), jnp.ones((1,), bool)
    if tokens == 1:
        o, s = pr.retention_decode(q[:, 0], k[:, 0], v[:, 0],
                                      log_g[:, 0], s, 0, rows, fresh)
        o = o[:, None]
    else:
        o, s = pr.retention_chunk(q, k, v, log_g, s, 0, rows, fresh)
    np.testing.assert_allclose(o, want, atol=ATOL, rtol=RTOL)
    assert np.isfinite(np.asarray(s[0, 1])).all()


def test_padding_tokens_leave_the_state_alone():
    """A prompt right-padded to a bucket: the state after the call is the
    state at the prompt's true length, whatever the padding holds; a
    sequence longer than one chunk rides the state across chunks."""
    B, KV, rep, d, T = 2, 2, 2, 16, 20
    q, k, v, log_g = _inputs(B, T + 1, KV, rep, d, seed=11)
    length = jnp.asarray([T, 13], jnp.int32)
    s = _stale(1, 2, KV, d)
    rows = jnp.arange(B, dtype=jnp.int32)
    o, s = pr.retention_prefill(q[:, :T], k[:, :T], v[:, :T],
                                   log_g[:, :T], s, 0, rows,
                                   jnp.ones((B,), bool), length=length)
    want = pr.retention_attention(q, k, v, log_g)
    np.testing.assert_allclose(o[0], want[0, :T], atol=ATOL, rtol=RTOL)
    short = pr.retention_attention(*(x[1:, :14] for x in (q, k, v, log_g)))
    np.testing.assert_allclose(o[1, :13], short[0, :13], atol=ATOL, rtol=RTOL)
    # the next token of the short row follows position 12, not the padding
    o, s = pr.retention_decode(q[:, 13], k[:, 13], v[:, 13], log_g[:, 13],
                                  s, 0, rows, jnp.zeros((B,), bool))
    np.testing.assert_allclose(o[1], short[0, 13], atol=ATOL, rtol=RTOL)
    # more than one chunk: CHUNK-sized pieces inside one call
    monkey = pr.CHUNK
    try:
        pr.CHUNK = 8
        s2 = _stale(1, 2, KV, d)
        o2, s2 = pr.retention_prefill(
            q[:, :T], k[:, :T], v[:, :T], log_g[:, :T], s2, 0, rows,
            jnp.ones((B,), bool), length=jnp.asarray([T, T], jnp.int32))
    finally:
        pr.CHUNK = monkey
    np.testing.assert_allclose(o2, want[:, :T], atol=ATOL, rtol=RTOL)


def test_work_list_orders_the_running_entries_first():
    batch, row, total = pr.work_list(jnp.asarray([7, -1, 2, 9, 0]), 8)
    assert int(total) == 3
    assert batch[:3].tolist() == [0, 2, 4] and row[:3].tolist() == [7, 2, 0]
