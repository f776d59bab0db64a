"""ZeRO-Inference weight streaming — analog of the reference's
ZeRO-inference checkpoint-streaming tests (test_checkpoint_sharding /
zero-inference paths): streamed logits must equal the all-on-device
forward."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.zero_inference import ZeroInferenceEngine
from deepspeed_tpu.models.lm_config import (TransformerConfig,
                                            transformer_config)
from deepspeed_tpu.models.transformer_lm import TransformerLM


def _model_and_params(family="gpt2", n_layer=3):
    cfg = transformer_config(family, vocab_size=64, n_layer=n_layer,
                             n_head=2, n_embd=32, max_seq_len=32,
                             dtype=jnp.float32)
    model = TransformerLM(cfg)
    ids = jnp.ones((1, 8), jnp.int32)
    params = model.init({"params": jax.random.PRNGKey(0)}, ids,
                        method=model.logits)["params"]
    return cfg, model, params


def test_streamed_matches_resident():
    cfg, model, params = _model_and_params()
    ids = jnp.asarray(np.random.default_rng(0)
                      .integers(0, 64, (2, 16)).astype(np.int32))
    ref = model.apply({"params": params}, ids, method=model.logits)

    host = jax.device_get(params)
    zi = ZeroInferenceEngine(cfg, host, dtype=jnp.float32)
    out = zi(ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_streamed_bloom_family():
    """bloom has an embedding layernorm — the streamed path must apply
    it (regression for a dropped embed_ln)."""
    cfg, model, params = _model_and_params(family="bloom")
    ids = jnp.asarray(np.random.default_rng(2)
                      .integers(0, 64, (2, 12)).astype(np.int32))
    ref = model.apply({"params": params}, ids, method=model.logits)
    zi = ZeroInferenceEngine(cfg, jax.device_get(params), dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(zi(ids)), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_streamed_llama_family():
    cfg, model, params = _model_and_params(family="llama")
    ids = jnp.asarray(np.random.default_rng(1)
                      .integers(0, 64, (2, 12)).astype(np.int32))
    ref = model.apply({"params": params}, ids, method=model.logits)
    zi = ZeroInferenceEngine(cfg, jax.device_get(params), dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(zi(ids)), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_prefetch_variants_agree():
    cfg, model, params = _model_and_params(n_layer=4)
    ids = jnp.ones((1, 8), jnp.int32)
    host = jax.device_get(params)
    outs = [np.asarray(ZeroInferenceEngine(cfg, host, dtype=jnp.float32,
                                           prefetch=p)(ids))
            for p in (0, 1, 3)]
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-6)
    np.testing.assert_allclose(outs[0], outs[2], rtol=1e-6)


def test_score_ranks_likely_sequences():
    cfg, model, params = _model_and_params()
    zi = ZeroInferenceEngine(cfg, jax.device_get(params), dtype=jnp.float32)
    ids = np.random.default_rng(0).integers(0, 64, (3, 16)).astype(np.int32)
    scores = zi.score(ids)
    assert scores.shape == (3,)
    assert np.isfinite(scores).all()


def test_memmap_host_weights(tmp_path):
    """Weights can live in a memory-mapped file (the NVMe tier)."""
    cfg, model, params = _model_and_params()
    host = jax.device_get(params)
    # dump the stacked block weights to disk, reload as memmaps
    import pickle

    flat, tree = jax.tree_util.tree_flatten(host)
    paths = []
    for i, leaf in enumerate(flat):
        p = tmp_path / f"w{i}.npy"
        np.save(p, np.asarray(leaf))
        paths.append(p)
    mapped = jax.tree_util.tree_unflatten(
        tree, [np.load(p, mmap_mode="r") for p in paths])
    ids = jnp.ones((1, 8), jnp.int32)
    ref = model.apply({"params": host}, ids, method=model.logits)
    out = ZeroInferenceEngine(cfg, mapped, dtype=jnp.float32)(ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_streamed_generate_matches_resident():
    """generate() under weight streaming (per-token layer restream, KV
    caches device-resident) must produce the same greedy tokens as the
    all-on-device engine's generate — the ZeRO-Inference serving mode
    (reference docs/_posts/2022-09-10-zero-inference.md)."""
    import deepspeed_tpu as ds

    cfg, model, params = _model_and_params(family="llama")
    ids = jnp.asarray(np.random.default_rng(5)
                      .integers(0, 64, (2, 6)).astype(np.int32))

    resident = ds.init_inference(model, model_parameters=params,
                                 dtype="float32")
    expect = resident.generate(ids, max_new_tokens=6)

    zi = ZeroInferenceEngine(cfg, jax.device_get(params), dtype=jnp.float32)
    got = zi.generate(ids, max_new_tokens=6)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(expect))


def test_streamed_generate_contracts():
    """Engine-dtype != config-dtype must still generate (cache dtype is
    the module's, not the engine's), and max_new_tokens=0 returns the
    prompt — both matching the resident engine's contracts."""
    cfg, model, params = _model_and_params()
    ids = jnp.asarray(np.random.default_rng(6)
                      .integers(0, 64, (2, 5)).astype(np.int32))
    zi = ZeroInferenceEngine(cfg, jax.device_get(params),
                             dtype=jnp.bfloat16)  # cfg is float32
    out = zi.generate(ids, max_new_tokens=3)
    assert out.shape == (2, 8) and (out[:, :5] == np.asarray(ids)).all()
    np.testing.assert_array_equal(zi.generate(ids, max_new_tokens=0),
                                  np.asarray(ids))


def test_int8_streaming_tier():
    """int8=True quantizes the streamed Dense kernels to the QuantDense
    layout: each layer ships ~half the bytes, logits track the bf16
    stream, and generation still works (int8 ZeRO-Inference — the
    streamed analog of the engine's dtype=int8 tier)."""
    cfg, model, params = _model_and_params(family="llama", n_layer=3)
    host = jax.device_get(params)
    ids = jnp.asarray(np.random.default_rng(7)
                      .integers(0, 64, (2, 10)).astype(np.int32))

    ref_eng = ZeroInferenceEngine(cfg, host, dtype=jnp.float32)
    q_eng = ZeroInferenceEngine(cfg, host, dtype=jnp.float32, int8=True)

    # per-layer wire bytes drop close to half (scales/norms keep f32)
    assert sum(q_eng._leaf_nbytes) < 0.7 * sum(ref_eng._leaf_nbytes)

    ref = np.asarray(ref_eng(ids), np.float32)
    got = np.asarray(q_eng(ids), np.float32)
    agree = (ref.argmax(-1) == got.argmax(-1)).mean()
    assert agree > 0.9, agree

    toks = q_eng.generate(ids, max_new_tokens=4)
    assert toks.shape == (2, 14)
