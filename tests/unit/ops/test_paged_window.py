"""The paged kernels on a group of sliding-window layers: the read starts
at the first visible entry and masks what lies a window behind a row; a
call with ``active`` False runs no step. Interpret mode on the CPU."""

import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention.paged_attention import (
    live_pages, paged_decode_attention, paged_write_columns)

L, P, KV, D, PS, B, H, PER_SLOT, W = 3, 20, 2, 32, 8, 4, 4, 8, 16
STARTS = np.array([3, 17, 40, 60], np.int32)


def _pool(seed=0):
    rng = np.random.default_rng(seed)
    k = jnp.asarray(rng.normal(size=(L, P, KV, D, PS)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(L, P, KV, D, PS)), jnp.float32)
    table = np.full((B, PER_SLOT), P, np.int32)
    nxt = 0
    for b in range(B):      # only the window's entries are mapped
        for e in range(max(STARTS[b] - W + 1, 0) // PS, STARTS[b] // PS + 1):
            table[b, e] = nxt
            nxt += 1
    return k, v, table


def _dense(q, k, v, table, rows):
    """Row t of slot b over positions (start + t - W, start + t]."""
    out = np.zeros((B, rows, H, D))
    for b in range(B):
        for t in range(rows):
            p = STARTS[b] + t
            pos = np.arange(max(p - W + 1, 0), p + 1)
            K = np.stack([k[table[b, x // PS], :, :, x % PS] for x in pos])
            V = np.stack([v[table[b, x // PS], :, :, x % PS] for x in pos])
            for h in range(H):
                c = h // (H // KV)
                s = K[:, c] @ q[b, t, h] / np.sqrt(D)
                w = np.exp(s - s.max())
                out[b, t, h] = (w / w.sum()) @ V[:, c]
    return out


@pytest.mark.parametrize("rows", [1, 3])
def test_window_read_matches_a_dense_window(rows):
    k, v, table = _pool()
    # a verify step's later rows write (and see) further entries
    for b in range(B):
        for e in range(STARTS[b] // PS, (STARTS[b] + rows - 1) // PS + 1):
            if table[b, e] == P:
                table[b, e] = P - 1 - b
    q = jnp.asarray(np.random.default_rng(1).normal(size=(B, rows, H, D)),
                    jnp.float32)
    got = paged_decode_attention(q, k, v, jnp.asarray(table),
                                 jnp.asarray(STARTS), layer=jnp.asarray(1),
                                 page_size=PS, window=W)
    want = _dense(np.asarray(q), np.asarray(k[1]), np.asarray(v[1]), table,
                  rows)
    # float32 both ways, the softmax folded a page at a time
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-6)


def test_window_work_list():
    _, _, table = _pool()
    table[2] = P                  # a freed slot: all sentinel, start counts on
    slot_of, entry_of, page_of, live, total, first = (
        np.asarray(x) for x in live_pages(
            jnp.asarray(STARTS), jnp.asarray(table), 1, PS, P, W))
    assert first.tolist() == [0, 0, 3, 5]
    assert live.tolist() == [1, 3, 1, 3]        # the freed slot: one step
    assert int(total) == 8
    steps = list(zip(slot_of[:8].tolist(), entry_of[:8].tolist()))
    assert steps == [(0, 0), (1, 0), (1, 1), (1, 2), (2, 3), (3, 5), (3, 6),
                     (3, 7)]
    assert (page_of < P).all()
    # without a window the list is the one it always was: five values
    assert len(live_pages(jnp.asarray(STARTS), jnp.asarray(table), 1, PS,
                          P)) == 5


def test_inactive_calls_touch_nothing():
    k, v, table = _pool()
    cols = jnp.ones((B, KV, D, 1), jnp.float32)
    same = paged_write_columns(k, jnp.asarray(1), cols, jnp.asarray(table),
                               jnp.asarray(STARTS), page_size=PS,
                               active=jnp.asarray(False))
    assert bool((same == k).all())
    written = paged_write_columns(k, jnp.asarray(1), cols,
                                  jnp.asarray(table), jnp.asarray(STARTS),
                                  page_size=PS, active=jnp.asarray(True))
    assert int((written != k).sum()) == B * KV * D
    q = jnp.zeros((B, 1, H, D), jnp.float32)
    out = paged_decode_attention(q, k, v, jnp.asarray(table),
                                 jnp.asarray(STARTS), layer=jnp.asarray(1),
                                 page_size=PS, window=W,
                                 active=jnp.asarray(False))
    assert out.shape == (B, 1, H, D)        # not to be read: no step ran
