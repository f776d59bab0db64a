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
    assert live.tolist() == [1, 3, 0, 3]        # the freed slot: no step
    assert int(total) == 7
    steps = list(zip(slot_of[:7].tolist(), entry_of[:7].tolist()))
    assert steps == [(0, 0), (1, 0), (1, 1), (1, 2), (3, 5), (3, 6), (3, 7)]
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


def test_a_window_table_that_maps_nothing_is_a_grid_of_no_step():
    k, v, table = _pool()
    table[:] = P
    *_, live, total, _ = live_pages(jnp.asarray(STARTS), jnp.asarray(table),
                                    1, PS, P, W)
    assert live.tolist() == [0] * B and int(total) == 0
    q = jnp.ones((B, 1, H, D), jnp.float32)
    out = paged_decode_attention(q, k * jnp.nan, v * jnp.nan,
                                 jnp.asarray(table), jnp.asarray(STARTS),
                                 layer=jnp.asarray(1), page_size=PS,
                                 window=W)
    assert out.shape == q.shape and bool(jnp.isfinite(out).all())


@pytest.mark.parametrize("rep", [1, 8])
@pytest.mark.parametrize("rows", [1, 8])
def test_dead_slots_in_a_window_group_are_no_step_and_change_nothing(
        rows, rep):
    """Freed slots (rows all sentinel, starts that count on) around the
    live ones, every unmapped page NaN: the live slots' rows are a dense
    window's, bitwise those of the call without the dead slots, and the
    dead slots' rows are finite."""
    rng = np.random.default_rng(31 + rows + rep)
    heads = KV * rep
    starts = np.array([50, 3, 17, 9, 40, 63], np.int32)
    alive = np.array([1, 2, 4])
    table = np.full((len(starts), PER_SLOT), P, np.int32)
    nxt = 0
    for b in alive:         # the entries the call's rows see, and no others
        for e in range(max(starts[b] - W + 1, 0) // PS,
                       (starts[b] + rows - 1) // PS + 1):
            table[b, e] = nxt
            nxt += 1
    assert nxt < P          # page P - 1, where a sentinel clips to, is NaN
    k = rng.normal(size=(L, P, KV, D, PS)).astype(np.float32)
    v = rng.normal(size=(L, P, KV, D, PS)).astype(np.float32)
    k[:, nxt:] = v[:, nxt:] = np.nan
    q = rng.normal(size=(len(starts), rows, heads, D)).astype(np.float32)
    *_, live, total, first = live_pages(
        jnp.asarray(starts), jnp.asarray(table), rows, PS, P, W)
    want_live = [0] + [int((table[b] < P).sum()) for b in range(1, 5)] + [0]
    assert live.tolist() == want_live and int(total) == sum(want_live)

    def call(slots):
        return np.asarray(paged_decode_attention(
            jnp.asarray(q[slots]), jnp.asarray(k), jnp.asarray(v),
            jnp.asarray(table[slots]), jnp.asarray(starts[slots]),
            layer=jnp.asarray(2), page_size=PS, window=W))

    got = call(np.arange(len(starts)))
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got[alive], call(alive))
    for b in alive:
        for t in range(rows):
            p = starts[b] + t
            pos = np.arange(max(p - W + 1, 0), p + 1)
            K = np.stack([k[2, table[b, x // PS], :, :, x % PS] for x in pos])
            V = np.stack([v[2, table[b, x // PS], :, :, x % PS] for x in pos])
            for h in range(heads):
                s = K[:, h // rep] @ q[b, t, h] / np.sqrt(D)
                w = np.exp(s - s.max())
                np.testing.assert_allclose(
                    got[b, t, h], (w / w.sum()) @ V[:, h // rep], atol=2e-6)
