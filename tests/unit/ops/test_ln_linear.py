"""Fused LayerNorm->Linear kernel numerics (ops/transformer/ln_linear.py).

The kernel-vs-plain-composition parity tests follow the reference's
kernel-vs-PyTorch pattern (tests/unit/ops/transformer) — here the oracle
is the unfused jnp composition, and the model-level test asserts the
fused block is a drop-in (identical param tree, matching loss/grads).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.transformer.ln_linear import (
    ln_linear,
    supports_fused,
)


def _reference(x, gamma, beta, w, bias, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    xc = xf - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    xh = xc * jax.lax.rsqrt(var + eps)
    n = (xh * gamma.astype(jnp.float32) +
         beta.astype(jnp.float32)).astype(x.dtype)
    return n @ w.astype(x.dtype) + bias.astype(x.dtype)


def _make(m, c, n, dtype, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((m, c)), dtype)
    gamma = jnp.asarray(1.0 + 0.1 * rng.standard_normal(c), jnp.float32)
    beta = jnp.asarray(0.1 * rng.standard_normal(c), jnp.float32)
    w = jnp.asarray(rng.standard_normal((c, n)) / np.sqrt(c), dtype)
    bias = jnp.asarray(0.1 * rng.standard_normal(n), jnp.float32)
    return x, gamma, beta, w, bias


@pytest.mark.parametrize("m,c,n", [(64, 128, 256), (128, 256, 128)])
def test_forward_matches_reference(m, c, n):
    args = _make(m, c, n, jnp.bfloat16)
    assert supports_fused(m, c, n)
    got = ln_linear(*args)
    want = _reference(*args)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=3e-2, rtol=3e-2)


def test_gradients_match_reference():
    m, c, n = 64, 128, 128
    x, gamma, beta, w, bias = _make(m, c, n, jnp.bfloat16)

    def loss_fused(args):
        return ln_linear(*args).astype(jnp.float32).sum()

    def loss_ref(args):
        return _reference(*args).astype(jnp.float32).sum()

    gf = jax.grad(loss_fused)((x, gamma, beta, w, bias))
    gr = jax.grad(loss_ref)((x, gamma, beta, w, bias))
    for a, b, name in zip(gf, gr, ("dx", "dgamma", "dbeta", "dw", "dbias")):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=0.15, rtol=5e-2, err_msg=name)


def test_ragged_shapes_fall_back():
    # M=9 has no MXU-aligned tile; the public API must still be exact
    m, c, n = 9, 128, 128
    args = _make(m, c, n, jnp.float32)
    assert not supports_fused(m, c, n)
    got = ln_linear(*args)
    want = _reference(*args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_leading_dims_flattened():
    b, t, c, n = 2, 32, 128, 128
    x3 = jnp.asarray(np.random.default_rng(1).standard_normal((b, t, c)),
                     jnp.bfloat16)
    _, gamma, beta, w, bias = _make(b * t, c, n, jnp.bfloat16, seed=1)
    got = ln_linear(x3, gamma, beta, w, bias)
    want = _reference(x3, gamma, beta, w, bias)
    assert got.shape == (b, t, n)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=3e-2, rtol=3e-2)


def test_model_level_fused_block_is_drop_in():
    """Fused and unfused GPT-2 blocks: identical param trees, matching
    loss and grads (the A/B ``fused_ln_linear`` toggles)."""
    import jax.tree_util as jtu

    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel

    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, 64, (2, 64)).astype(np.int32)}

    def build(fused):
        cfg = GPT2Config(vocab_size=64, n_positions=64, n_embd=128,
                         n_layer=2, n_head=2, dtype=jnp.bfloat16,
                         use_flash_attention=False, fused_ln_linear=fused,
                         remat=True, remat_policy="dots")
        return GPT2LMHeadModel(cfg)

    m_f, m_u = build(True), build(False)
    p_f = m_f.init({"params": jax.random.PRNGKey(0)}, batch)
    p_u = m_u.init({"params": jax.random.PRNGKey(0)}, batch)
    kf = [jtu.keystr(k) for k, _ in jtu.tree_flatten_with_path(p_f)[0]]
    ku = [jtu.keystr(k) for k, _ in jtu.tree_flatten_with_path(p_u)[0]]
    assert kf == ku

    lf, gf = jax.value_and_grad(lambda p: m_f.apply(p, batch))(p_u)
    lu, gu = jax.value_and_grad(lambda p: m_u.apply(p, batch))(p_u)
    assert abs(float(lf) - float(lu)) < 2e-2
    errs = [float(jnp.max(jnp.abs(a.astype(jnp.float32) -
                                  b.astype(jnp.float32))))
            for a, b in zip(jtu.tree_leaves(gf), jtu.tree_leaves(gu))]
    assert max(errs) < 6e-2, max(errs)
