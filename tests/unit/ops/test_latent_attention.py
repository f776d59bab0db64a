"""The absorbed read of latent attention (``ops/attention/
latent_attention.py``, ``mla_decode`` / ``mla_chunk`` in interpret mode)
against the EXPANDED form written out in ``jax.numpy``, float32: K and V of
every cached token rebuilt from its latent with ``W_kvb`` and attended to a
head. One mathematics, two forms."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention.latent_attention import (MAX_ROWS,
                                                          latent_attention)

H, R, DN, DR, DV = 4, 32, 16, 8, 16
W = R + DR
PS, PER_SLOT, P = 16, 8, 24
ATOL = 2e-5


def _case(seed, B, T, lengths, per_slot=PER_SLOT, P=P):
    """Random ``c``, ``k_r`` rows in pages through a shuffled table, and
    queries ``q_n``, ``q_r``: ``lengths[b]`` cached positions before this
    step's ``T`` (whose rows are already in the pages)."""
    rng = np.random.default_rng(seed)
    rows = rng.standard_normal((B, per_slot * PS, W)).astype(np.float32)
    w_kvb = (rng.standard_normal((R, H, DN + DV)) / math.sqrt(R)
             ).astype(np.float32)
    q_n = rng.standard_normal((B, T, H, DN)).astype(np.float32)
    q_r = rng.standard_normal((B, T, H, DR)).astype(np.float32)
    table = np.full((B, per_slot), P, np.int32)
    pages = rng.standard_normal((2, P, W, PS)).astype(np.float32)  # garbage
    free = list(rng.permutation(P))
    for b, n in enumerate(lengths):
        if n < 0:
            continue                                  # a slot that maps nothing
        for e in range(-(-(n + T) // PS)):
            pid = free.pop()
            table[b, e] = pid
            pages[1, pid] = rows[b, e * PS:(e + 1) * PS].T
    return rows, w_kvb, q_n, q_r, table, pages


def _expanded(rows, w_kvb, q_n, q_r, lengths, T):
    """The layer's equations as written, a slot at a time."""
    out = []
    for b, n in enumerate(lengths):
        S = max(n, 0) + T
        c, k_r = rows[b, :S, :R], rows[b, :S, R:]
        kv = np.einsum("sr,rhd->shd", c, w_kvb)
        k_n, v = kv[..., :DN], kv[..., DN:]
        s = (np.einsum("thd,shd->hts", q_n[b], k_n)
             + np.einsum("thd,sd->hts", q_r[b], k_r)) / math.sqrt(DN + DR)
        seen = np.arange(S)[None, :] <= max(n, 0) + np.arange(T)[:, None]
        s = np.where(seen[None], s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out.append(np.einsum("hts,shd->thd", p, v))
    return np.stack(out)


def _absorbed(rows, w_kvb, q_n, q_r, table, pages, lengths):
    q = jnp.concatenate(
        [jnp.einsum("bthd,rhd->bthr", q_n, w_kvb[..., :DN]), q_r], -1)
    ctx = latent_attention(q, jnp.asarray(pages), jnp.asarray(table),
                           jnp.asarray(np.maximum(lengths, 0), jnp.int32),
                           layer=jnp.asarray(1, jnp.int32), rank=R,
                           scale=1.0 / math.sqrt(DN + DR), page_size=PS)
    return ctx, np.asarray(jnp.einsum("bthr,rhd->bthd", ctx,
                                      w_kvb[..., DN:]))


@pytest.mark.parametrize("T,lengths", [
    (1, [0, 5, 15, 16, 47, 100]),        # a decode step, page edges
    (1, [33, -1, 7, -1]),                # slots that map nothing
    (5, [0, 12, 30]),                    # a few rows, not a whole tile
    (16, [48]),                          # a chunk behind a prefix
    (16, [0]),                           # a first chunk
])
def test_absorbed_equals_expanded(T, lengths):
    case = _case(len(lengths) * 7 + T, len(lengths), T, lengths)
    rows, w_kvb, q_n, q_r, table, pages = case
    ctx, got = _absorbed(rows, w_kvb, q_n, q_r, table, pages,
                         np.asarray(lengths))
    want = _expanded(rows, w_kvb, q_n, q_r, lengths, T)
    for b, n in enumerate(lengths):
        if n < 0:
            # no step visits the slot: zeros, finite, not attention output
            assert not np.asarray(ctx[b]).any()
        else:
            np.testing.assert_allclose(got[b], want[b], atol=ATOL)


@pytest.mark.parametrize("T", [1, 3])
@pytest.mark.parametrize("G", [1, 2, 4, 8])
def test_a_step_of_several_pages_folds_them_as_one_page_a_step_does(
        monkeypatch, G, T):
    """Blocks of ``G`` pages a grid step against one page a step: the same
    pages in the same order through the same update, so the same BITS in
    interpret mode. A slot of exactly ``G`` pages (one whole block), of
    ``G + 1`` with a partial last page and with a whole one (a block of
    one page behind a whole block), of one partial page, and a slot that
    maps nothing between two that do."""
    from deepspeed_tpu.ops.attention import latent_attention as la

    lengths = [G * PS - T, -1, G * PS + 5 - T, 3, (G + 1) * PS - T]
    case = _case(G * 10 + T, len(lengths), T, lengths, per_slot=10, P=32)
    rows, w_kvb, q_n, q_r, table, pages = case
    before = pages.copy()
    got = {}
    for g in (G, 1):
        monkeypatch.setattr(la, "pages_a_step", lambda *shapes, g=g: g)
        got[g] = _absorbed(rows, w_kvb, q_n, q_r, table, pages,
                           np.asarray(lengths))
    np.testing.assert_array_equal(np.asarray(got[G][0]),
                                  np.asarray(got[1][0]))
    np.testing.assert_array_equal(pages, before)
    want = _expanded(rows, w_kvb, q_n, q_r, lengths, T)
    for b, n in enumerate(lengths):
        if n < 0:
            assert not np.asarray(got[G][0][b]).any()
        else:
            np.testing.assert_allclose(got[G][1][b], want[b], atol=ATOL)


@pytest.mark.parametrize("G", [1, 2, 4, 8])
def test_the_work_list_in_blocks_against_numpy(G):
    """``page_blocks``: ``ceil(live / G)`` steps a slot, the slot's pages
    in table order, none past ``live``, no step for a slot of none."""
    from deepspeed_tpu.ops.attention.latent_attention import page_blocks

    per_slot = 11
    for live in ([0, 0, 0], [G, 0, G + 1, 1, per_slot, 0],
                 list(np.random.default_rng(G).integers(0, per_slot + 1, 9))):
        live = np.asarray(live, np.int32)
        slot_of, entry_of, total = map(np.asarray, page_blocks(
            jnp.asarray(live), G, per_slot))
        assert len(slot_of) == len(entry_of) == len(live) * -(-per_slot // G)
        assert total == np.sum(-(-live // G))
        pages = [(b, e) for b, e0 in zip(slot_of[:total], entry_of[:total])
                 for e in range(e0, min(e0 + G, live[b]))]
        assert pages == [(b, e) for b, n in enumerate(live)
                         for e in range(n)]
        assert all(e % G == 0 for e in entry_of[:total])
        # what never runs is in range all the same
        assert slot_of.max(initial=0) < len(live)
        assert 0 <= entry_of.min(initial=0) and \
            entry_of.max(initial=0) < per_slot


def test_more_rows_than_a_call_holds_go_in_several(monkeypatch):
    """``MAX_ROWS`` query-head rows a call; the later calls' rows stand
    further on and see further."""
    from deepspeed_tpu.ops.attention import latent_attention as la

    assert MAX_ROWS == 2048          # a served chunk: 128 tokens x 16 heads
    monkeypatch.setattr(la, "MAX_ROWS", 8 * H)
    case = _case(3, 1, 24, [40])
    rows, w_kvb, q_n, q_r, table, pages = case
    _, got = _absorbed(rows, w_kvb, q_n, q_r, table, pages, np.asarray([40]))
    np.testing.assert_allclose(got, _expanded(rows, w_kvb, q_n, q_r, [40],
                                              24), atol=ATOL)


def test_the_read_leaves_the_leaf_alone_and_reads_its_own_layer():
    rows, w_kvb, q_n, q_r, table, pages = _case(5, 2, 1, [20, 9])
    before = pages.copy()
    _absorbed(rows, w_kvb, q_n, q_r, table, pages, np.asarray([20, 9]))
    np.testing.assert_array_equal(pages, before)
    # layer 0 holds garbage: reading it gives something else
    q = jnp.concatenate(
        [jnp.einsum("bthd,rhd->bthr", q_n, w_kvb[..., :DN]), q_r], -1)
    kw = dict(rank=R, scale=0.2, page_size=PS)
    a = latent_attention(q, jnp.asarray(pages), jnp.asarray(table),
                         jnp.asarray([20, 9]), layer=jnp.asarray(1), **kw)
    b = latent_attention(q, jnp.asarray(pages), jnp.asarray(table),
                         jnp.asarray([20, 9]), layer=None, **kw)
    assert float(jnp.abs(a - b).max()) > 1e-2


def test_latent_module_absorbed_equals_expanded_through_a_dense_cache():
    """``LatentAttention`` itself: the no-cache forward (expanded) against
    a prefill of 9 tokens (expanded, rows stored) and 7 decode steps
    (absorbed against the stored rows), float32."""
    from deepspeed_tpu.models.lm_config import transformer_config
    from deepspeed_tpu.models.transformer_lm import TransformerLM

    cfg = transformer_config(
        "moonlight", vocab_size=64, max_seq_len=32, n_embd=32, n_layer=2,
        n_head=H, kv_lora_rank=R, qk_nope_head_dim=DN, qk_rope_head_dim=DR,
        v_head_dim=DV, ffn_dim=16, n_experts=4, experts_per_token=2,
        n_shared_experts=1, first_k_dense=1, dense_ffn_dim=48,
        dtype=jnp.float32)
    model = TransformerLM(cfg)
    ids = jnp.asarray(np.random.default_rng(1).integers(1, 64, (2, 16)))
    # (each program traced and compiled once: op by op the two layers'
    # scans are hundreds of small compiles)
    params = jax.jit(lambda: model.init(
        jax.random.PRNGKey(0), ids, method=model.logits))()["params"]
    full = jax.jit(lambda p: model.apply({"params": p}, ids,
                                         method=model.logits))(params)
    out, vars_ = jax.jit(lambda p: model.apply(
        {"params": p}, ids[:, :9], method=model.prefill,
        mutable=["cache"]))(params)
    np.testing.assert_allclose(out, full[:, :9], atol=ATOL)
    cache = vars_["cache"]
    assert set(cache["cache_store"]) == {"c", "index"}
    decode = jax.jit(lambda p, cache, token, t: model.apply(
        {"params": p, "cache": cache}, token, t, method=model.decode,
        mutable=["cache"]))
    for t in range(9, 16):
        out, vars_ = decode(params, cache, ids[:, t:t + 1], jnp.asarray(t))
        cache = vars_["cache"]
        np.testing.assert_allclose(out[:, 0], full[:, t], atol=ATOL)
