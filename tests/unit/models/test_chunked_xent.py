"""Streaming cross-entropy parity: loss_chunk must change memory, not
math — same loss and same gradients as the dense (B, T, V)-logits path,
on both model families and both head types."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest


def _loss_and_grads(model, params, batch):
    def f(p):
        return model.apply({"params": p}, batch)

    # (one program: op by op the layers' backward is hundreds of compiles)
    loss, grads = jax.jit(jax.value_and_grad(f))(params)
    return float(loss), grads


def _init(model, batch):
    return jax.jit(lambda: model.init({"params": jax.random.PRNGKey(0)},
                                      batch))()["params"]


def _assert_tree_close(a, b, rtol, atol):
    for (pa, la), (pb, lb) in zip(
            jax.tree_util.tree_leaves_with_path(a),
            jax.tree_util.tree_leaves_with_path(b)):
        np.testing.assert_allclose(
            np.asarray(la, np.float32), np.asarray(lb, np.float32),
            rtol=rtol, atol=atol, err_msg=str(pa))


@pytest.mark.parametrize("chunk", [5, 16, 64])
def test_gpt2_chunked_matches_dense(chunk):
    import dataclasses

    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel

    cfg = GPT2Config(vocab_size=97, n_positions=16, n_embd=32, n_layer=2,
                     n_head=2, dtype=jnp.float32)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 97, (3, 16)).astype(np.int32)
    labels = ids.copy()
    labels[0, -3:] = -100  # masked tail
    batch = {"input_ids": jnp.asarray(ids), "labels": jnp.asarray(labels)}

    dense = GPT2LMHeadModel(cfg)
    params = _init(dense, batch)
    l_dense, g_dense = _loss_and_grads(dense, params, batch)

    chunked = GPT2LMHeadModel(dataclasses.replace(cfg, loss_chunk=chunk))
    l_chunk, g_chunk = _loss_and_grads(chunked, params, batch)

    assert abs(l_dense - l_chunk) < 1e-5 * max(1.0, abs(l_dense))
    _assert_tree_close(g_dense, g_chunk, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("tied", [True, False])
def test_transformer_lm_chunked_matches_dense(tied):
    import dataclasses

    from deepspeed_tpu.models.lm_config import TransformerConfig
    from deepspeed_tpu.models.transformer_lm import TransformerLM

    cfg = TransformerConfig(vocab_size=97, max_seq_len=16, n_embd=32,
                            n_layer=2, n_head=2, dtype=jnp.float32,
                            tie_word_embeddings=tied)
    rng = np.random.default_rng(1)
    ids = rng.integers(0, 97, (2, 16)).astype(np.int32)
    batch = {"input_ids": jnp.asarray(ids)}

    dense = TransformerLM(cfg)
    params = _init(dense, batch)
    l_dense, g_dense = _loss_and_grads(dense, params, batch)

    chunked = TransformerLM(dataclasses.replace(cfg, loss_chunk=7))
    # from-scratch init of the CHUNKED model must create the full param
    # tree (incl. the untied lm_head the streaming path reads without
    # calling) — same structure as the dense init
    params_c = jax.eval_shape(lambda: _init(chunked, batch))
    assert (jax.tree_util.tree_structure(params_c)
            == jax.tree_util.tree_structure(params))
    l_chunk, g_chunk = _loss_and_grads(chunked, params, batch)

    assert abs(l_dense - l_chunk) < 1e-5 * max(1.0, abs(l_dense))
    _assert_tree_close(g_dense, g_chunk, rtol=2e-4, atol=2e-5)


def test_chunked_int8_guard_is_untied_only():
    """loss_chunk + int8-quantized head: the ValueError must fire ONLY
    for an UNTIED int8 lm_head (QuantDense kernel the streaming loss
    can't read); tied embeddings are never quantized and must pass."""
    import dataclasses

    from deepspeed_tpu.models.lm_config import TransformerConfig
    from deepspeed_tpu.models.transformer_lm import TransformerLM

    base = TransformerConfig(vocab_size=64, max_seq_len=16, n_embd=32,
                             n_layer=1, n_head=2, dtype=jnp.float32,
                             loss_chunk=8, int8_weights=True, int8_head=True)
    rng = np.random.default_rng(2)
    batch = {"input_ids": jnp.asarray(
        rng.integers(0, 64, (2, 16)).astype(np.int32))}

    tied = TransformerLM(dataclasses.replace(base, tie_word_embeddings=True))
    params = tied.init({"params": jax.random.PRNGKey(0)}, batch)["params"]
    loss = tied.apply({"params": params}, batch)
    assert np.isfinite(float(loss))

    untied = TransformerLM(dataclasses.replace(base,
                                               tie_word_embeddings=False))
    with pytest.raises(ValueError, match="untied"):
        untied.init({"params": jax.random.PRNGKey(0)}, batch)


def test_chunked_xent_engine_trains():
    """The streaming loss composes with the full engine step (compiled
    train_batch, ZeRO-2): loss decreases."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel

    cfg = GPT2Config(vocab_size=64, n_positions=32, n_embd=32, n_layer=2,
                     n_head=2, loss_chunk=8)
    eng, _, _, _ = ds.initialize(model=GPT2LMHeadModel(cfg), config={
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 1,
        "zero_optimization": {"stage": 2}, "bf16": {"enabled": True},
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}}})
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(
        0, 64, (eng.train_batch_size(), 32)).astype(np.int32)}
    losses = [float(eng.train_batch(batch=batch)) for _ in range(4)]
    assert losses[-1] < losses[0]
