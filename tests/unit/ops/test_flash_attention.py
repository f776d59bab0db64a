"""Flash-attention kernel numerics vs plain-jnp reference (analog of the
reference's kernel-vs-PyTorch tests in tests/unit/ops/transformer/)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention import flash_attention as fa
from deepspeed_tpu.ops.attention.flash_attention import (
    flash_attention,
    mha_reference,
)


def _rand_qkv(b=2, t=256, h=4, d=64, dtype=jnp.float32, seed=0):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(k1, (b, t, h, d), dtype)
    k = jax.random.normal(k2, (b, t, h, d), dtype)
    v = jax.random.normal(k3, (b, t, h, d), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_reference(causal):
    q, k, v = _rand_qkv()
    out = flash_attention(q, k, v, causal=causal)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)


def test_forward_multiple_q_blocks():
    q, k, v = _rand_qkv(t=512)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=64)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("causal", [True, False])
def test_backward_matches_reference(causal):
    q, k, v = _rand_qkv(b=1, t=128, h=2, d=32)

    def f_flash(q, k, v):
        return (flash_attention(q, k, v, causal=causal) ** 2).sum()

    def f_ref(q, k, v):
        return (mha_reference(q, k, v, causal=causal) ** 2).sum()

    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-3)


def test_bf16_forward():
    q, k, v = _rand_qkv(dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                               np.asarray(ref, dtype=np.float32), rtol=5e-2, atol=5e-2)


# (seq, block_q, block_k), None = the tuned table; named for where the
# diagonal meets the (rows x chunk) tiles a block is walked in
WALKS = [
    pytest.param(1024, None, None, id="1024-auto-one-block-tiles-wider-than-a-chunk"),
    pytest.param(2048, None, None, id="2048-auto-one-block"),
    pytest.param(640, 128, 128, id="640-128x128-diagonal-at-a-chunk-edge"),
    pytest.param(384, 128, 384, id="384-128x384-three-offsets-of-one-key-block"),
    pytest.param(512, 128, 64, id="512-128x64-block_q-larger-than-the-chunk"),
    pytest.param(256, 64, 128, id="256-64x128-diagonal-inside-a-chunk"),
]


def _blocks(seq, block_q, block_k):
    auto_q, auto_k = fa.auto_block_sizes(seq)
    return min(block_q or auto_q, seq), min(block_k or auto_k, seq)


@pytest.mark.parametrize("dtype,tol_fwd,tol_bwd", [
    pytest.param(jnp.float32, 2e-3, 5e-3, id="fp32"),
    pytest.param(jnp.bfloat16, 5e-2, 5e-2, id="bf16")])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("seq,block_q,block_k", WALKS)
def test_walk_matches_reference(seq, block_q, block_k, causal, dtype, tol_fwd,
                                tol_bwd):
    """Forward and all three gradients wherever the diagonal falls."""
    q, k, v = _rand_qkv(b=1, t=seq, h=1, d=64, dtype=dtype)

    def f_flash(q, k, v):
        out = flash_attention(q, k, v, causal=causal, block_q=block_q,
                              block_k=block_k)
        return (out.astype(jnp.float32) ** 2).sum(), out

    def f_ref(q, k, v):
        out = mha_reference(q, k, v, causal=causal)
        return (out.astype(jnp.float32) ** 2).sum(), out

    g1, out = jax.grad(f_flash, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    g2, ref = jax.grad(f_ref, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol_fwd, atol=tol_fwd)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=tol_bwd, atol=tol_bwd)


@pytest.mark.parametrize("seq,block_q,block_k", WALKS)
def test_first_row_has_a_finite_lse(seq, block_q, block_k):
    """Row 0 sees key 0 alone: every other chunk of its tile is masked or
    skipped, and its lse is its one score."""
    q, k, v = (x[0].transpose(1, 0, 2) for x in _rand_qkv(b=1, t=seq, h=1, d=64))
    bq, bk = _blocks(seq, block_q, block_k)
    _, lse = fa._flash_fwd(q, k, v, causal=True, scale=0.125, block_q=bq,
                           block_k=bk)
    assert lse.shape == (1, fa.SUBLANES, seq) and np.isfinite(lse).all()
    np.testing.assert_allclose(float(lse[0, 0, 0]),
                               0.125 * float(q[0, 0] @ k[0, 0]), rtol=1e-5)


def _brute_force_share(seq, bq, bk, rows, causal):
    """Tiles of (rows x chunk) inside (bq x bk) blocks that hold at least one
    unmasked element, counted on the mask itself."""
    rows = min(rows, bq)
    while bq % rows:
        rows //= 2
    chunk = min(fa.LANES, bk)
    mask = np.tril(np.ones((seq, seq), bool)) if causal \
        else np.ones((seq, seq), bool)
    tiles = mask.reshape(seq // rows, rows, seq // chunk, chunk)
    return tiles.any(axis=(1, 3)).sum() * rows * chunk / seq ** 2


@pytest.mark.parametrize("rows", [fa.ROWS, fa.ROWS_DKV])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("seq,block_q,block_k", WALKS)
def test_visited_share_is_the_brute_force_count(seq, block_q, block_k, causal,
                                                rows):
    share = fa.visited_share(seq, block_q, block_k, rows=rows, causal=causal)
    assert share == _brute_force_share(seq, *_blocks(seq, block_q, block_k),
                                       rows, causal)
    if not causal:
        assert share == 1.0
    elif (seq, block_q) == (1024, None):
        assert 0.5 < share <= 0.65


@pytest.mark.parametrize("rows", [fa.ROWS, fa.ROWS_DKV])
@pytest.mark.parametrize("seq,block_q,block_k", WALKS)
def test_only_crossed_chunks_are_masked(seq, block_q, block_k, rows):
    """Every block of the grid: a free piece holds no masked element (it
    takes no mask), a crossed piece's mask is the causal one, and what no
    tile visits is masked whole."""
    bq, bk = _blocks(seq, block_q, block_k)
    tril = np.tril(np.ones((seq, seq), bool))
    offsets = set(fa._block_offsets(True, seq, seq, bq, bk))
    for q0 in range(0, seq, bq):
        for k0 in range(0, seq, bk):
            block = tril[q0:q0 + bq, k0:k0 + bk]
            rel = q0 - k0
            if rel + bq <= 0:
                assert not block.any()
                continue
            rel = None if rel >= bk - 1 else rel
            assert rel in offsets
            seen = np.zeros_like(block)
            for r0, n, pieces in fa._block_tiles(rel, bq, bk, rows):
                for lo, hi, ahead in pieces:
                    tile = block[r0:r0 + n, lo:hi]
                    if ahead is None:
                        assert tile.all()
                    else:
                        r, c = np.ogrid[:n, :hi - lo]
                        assert not tile.all()
                        np.testing.assert_array_equal(tile, r + ahead >= c)
                    seen[r0:r0 + n, lo:hi] = True
            assert not block[~seen].any()


def test_gpt2_with_flash_attention_trains():
    import deepspeed_tpu as ds
    from tests.unit.simple_model import base_config, token_batch, tiny_gpt2

    model = tiny_gpt2(n_embd=64, n_head=2, n_positions=128, use_flash_attention=True)
    engine, _, _, _ = ds.initialize(model=model, config=base_config(micro=1))
    batch = token_batch(8, seq=128)
    l0 = float(engine.train_batch(batch=batch))
    for _ in range(3):
        loss = engine.train_batch(batch=batch)
    assert float(loss) < l0
