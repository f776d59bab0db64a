"""Int8 serving compute: Pallas dequant-GEMM, QuantDense, engine tier.

Parity model: the reference's int8 inference path
(``csrc/quantization/quantize.cu`` + the fused dequant in
``csrc/transformer/inference/csrc/dequantize.cu``) behind
``weight_quantizer.py``. On the CPU suite the kernel runs in interpret
mode; numerics are checked against the jnp dequant-then-dot oracle.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.quantization import (
    QuantDense,
    int8_matmul,
    int8_matmul_reference,
    pad_features,
    quantize_columns,
)


def _rand_case(rng, m, k, n):
    w = rng.integers(-127, 128, (k, n), dtype=np.int8)
    s = (rng.random((1, n)) * 0.01 + 1e-3).astype(np.float32)
    x = rng.standard_normal((m, k)).astype(np.float32)
    return jnp.asarray(x), jnp.asarray(w), jnp.asarray(s)


@pytest.mark.parametrize("m,k,n", [
    (8, 256, 384),     # tiled path
    (3, 256, 384),     # M padding
    (5, 100, 384),     # K not a lane multiple -> full-dim K block
    (4, 256, 100),     # N not a lane multiple -> full-dim N block
])
def test_kernel_matches_reference(m, k, n):
    x, w, s = _rand_case(np.random.default_rng(0), m, k, n)
    ref = int8_matmul_reference(x, w, s)
    out = int8_matmul(x, w, s, block_n=128, block_k=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=1e-2,
                               rtol=1e-2)


def test_batched_input_shape():
    x, w, s = _rand_case(np.random.default_rng(1), 6, 128, 256)
    x3 = x.reshape(2, 3, 128)
    out = int8_matmul(x3, w, s, interpret=True)
    assert out.shape == (2, 3, 256)
    flat = int8_matmul(x, w, s, interpret=True)
    np.testing.assert_array_equal(np.asarray(out).reshape(6, 256),
                                  np.asarray(flat))


def test_quantize_columns_roundtrip():
    rng = np.random.default_rng(2)
    w = rng.standard_normal((64, 48)).astype(np.float32)
    q, s = quantize_columns(w)
    assert q.dtype == np.int8 and s.shape == (1, 48)
    back = q.astype(np.float32) * s
    # max per-column error is bounded by half a quant step
    assert np.abs(back - w).max() <= 0.5 * s.max() + 1e-6
    # zero column keeps scale 1.0 (no div-by-zero)
    w[:, 0] = 0.0
    q, s = quantize_columns(w)
    assert s[0, 0] == 1.0 and (q[:, 0] == 0).all()


def test_quant_dense_matches_dense():
    """QuantDense(quantize(W)) tracks nn.Dense(W) within quantization
    error, including a padded feature count."""
    import flax.linen as nn

    rng = np.random.default_rng(3)
    for feats in (256, 200):  # 200 -> padded to 256
        w = (rng.standard_normal((128, feats)) * 0.05).astype(np.float32)
        b = (rng.standard_normal((feats,)) * 0.1).astype(np.float32)
        x = jnp.asarray(rng.standard_normal((4, 128)), jnp.float32)

        dense_out = nn.Dense(feats, dtype=jnp.bfloat16).apply(
            {"params": {"kernel": jnp.asarray(w),
                        "bias": jnp.asarray(b)}}, x)

        n_pad = pad_features(feats)
        wp = np.pad(w, ((0, 0), (0, n_pad - feats)))
        q, s = quantize_columns(wp)
        qd_out = QuantDense(feats, kernel_mode="on").apply(
            {"params": {"kernel": jnp.asarray(q), "scale": jnp.asarray(s),
                        "bias": jnp.asarray(b, jnp.bfloat16)}}, x)
        assert qd_out.shape == dense_out.shape
        err = np.abs(np.asarray(qd_out, np.float32) -
                     np.asarray(dense_out, np.float32))
        assert err.max() < 0.05, err.max()


def test_auto_mode_off_tpu_uses_reference(monkeypatch):
    """kernel_mode='auto' / interpret=None must route to the jnp
    reference on non-TPU backends — interpret-mode Pallas is orders of
    magnitude slower."""
    import importlib

    # the package re-exports the function under the same name; importlib
    # returns the actual submodule
    mod = importlib.import_module(
        "deepspeed_tpu.ops.quantization.int8_matmul")

    def boom(*a, **k):
        raise AssertionError("Pallas kernel invoked on a non-TPU backend")

    monkeypatch.setattr(mod, "_int8_matmul_2d", boom)
    x, w, s = _rand_case(np.random.default_rng(5), 4, 256, 256)
    out = mod.int8_matmul(x, w, s)  # interpret=None, CPU backend
    ref = int8_matmul_reference(x, w, s)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_kernel_plan_vmem_gate():
    """Tile planning: aligned shapes plan normally; a full-dimension
    fallback whose operand tiles exceed the VMEM budget returns None so
    serve-time shapes fall back instead of failing to compile."""
    from deepspeed_tpu.ops.quantization.int8_matmul import (
        VMEM_BUDGET_BYTES,
        _plan_vmem_bytes,
        kernel_plan,
    )

    # aligned: picks divisible 128-multiples, well under budget
    plan = kernel_plan(64, 2048, 2048)
    assert plan is not None
    bm, bk, bn = plan
    assert bk % 128 == 0 and bn % 128 == 0
    assert _plan_vmem_bytes(bm, bk, bn) <= VMEM_BUDGET_BYTES

    # small non-128-multiple N: full-dim block, still under budget
    assert kernel_plan(8, 256, 100) is not None

    # non-128-multiple K forces a full-dim K block of 4000; with a big N
    # block the operand tiles blow the budget -> reference path
    assert kernel_plan(128, 4000, 4096, block_n=512) is None

    # untileable: K too large for the full-dim fallback cap
    assert kernel_plan(8, 5000, 256) is None


def test_engine_int8_compute_tier():
    """dtype=int8 on a TransformerLM swaps Dense -> QuantDense: int8
    kernels in the engine param tree, logits tracking the bf16 engine."""
    import deepspeed_tpu
    from deepspeed_tpu.models.lm_config import transformer_config
    from deepspeed_tpu.models.transformer_lm import TransformerLM

    cfg = transformer_config("llama", vocab_size=256, n_embd=128, n_layer=2,
                             n_head=4, max_seq_len=64)
    model = TransformerLM(cfg)
    ids = jnp.asarray(np.random.default_rng(4).integers(0, 256, (2, 12)))
    params = model.init({"params": jax.random.PRNGKey(0)}, ids,
                        method=model.logits)["params"]

    fp = deepspeed_tpu.init_inference(model, model_parameters=params,
                                      dtype="bfloat16")
    q = deepspeed_tpu.init_inference(model, model_parameters=params,
                                     dtype="int8")
    out_fp = np.asarray(fp.forward(ids), np.float32)
    out_q = np.asarray(q.forward(ids), np.float32)

    n_int8 = sum(1 for leaf in jax.tree_util.tree_leaves(q.params)
                 if leaf.dtype == jnp.int8)
    assert n_int8 > 0, "no int8 kernels in the serving tree"
    # int8-at-rest params are materially smaller than the bf16 tree
    def tree_bytes(t):
        return sum(leaf.size * leaf.dtype.itemsize
                   for leaf in jax.tree_util.tree_leaves(t))
    assert tree_bytes(q.params) < 0.75 * tree_bytes(fp.params)
    agree = (out_fp.argmax(-1) == out_q.argmax(-1)).mean()
    assert agree > 0.9, agree

    toks = q.generate(ids, max_new_tokens=4)
    assert toks.shape == (2, 16)
