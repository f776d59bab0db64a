"""Hybrid engine (RLHF mode switching) — analog of reference
``tests/hybrid_engine/``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds


def _make_hybrid_engine():
    from deepspeed_tpu.models.lm_config import TransformerConfig
    from deepspeed_tpu.models.transformer_lm import TransformerLM

    cfg = TransformerConfig(vocab_size=128, n_layer=2, n_head=2, n_embd=32,
                            max_seq_len=64)
    model = TransformerLM(cfg)
    config = {
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "hybrid_engine": {"enabled": True},
        "steps_per_print": 1000,
    }
    engine, _, _, _ = ds.initialize(model=model, config=config)
    return engine, cfg


def _batch(engine, cfg, seq=16, seed=0):
    rng = np.random.default_rng(seed)
    return {"input_ids": rng.integers(
        0, cfg.vocab_size, (engine.train_batch_size(), seq)).astype(np.int32)}


def test_dispatch_and_train_generate_cycle():
    from deepspeed_tpu.runtime.hybrid_engine import DeepSpeedHybridEngine

    engine, cfg = _make_hybrid_engine()
    assert isinstance(engine, DeepSpeedHybridEngine)
    b = _batch(engine, cfg)
    l0 = float(engine.train_batch(batch=b))

    prompt = np.asarray([[5, 6, 7, 8]], dtype=np.int32)
    out1 = np.asarray(engine.generate(prompt, max_new_tokens=4, greedy=True))
    assert out1.shape == (1, 8)

    # params advance → generation output may change, engine must refresh
    for _ in range(3):
        engine.train_batch(batch=b)
    v1 = engine._inference_param_version
    out2 = np.asarray(engine.generate(prompt, max_new_tokens=4, greedy=True))
    assert engine._inference_param_version > v1
    assert out2.shape == (1, 8)


def test_lora_fuse_unfuse_roundtrip():
    from deepspeed_tpu.runtime.hybrid_engine import DeepSpeedHybridEngine

    engine, cfg = _make_hybrid_engine()
    rng = np.random.default_rng(0)
    params = {
        "proj": {
            "kernel": jnp.asarray(rng.standard_normal((8, 8)).astype(np.float32)),
            "lora_a": jnp.asarray(rng.standard_normal((8, 2)).astype(np.float32)),
            "lora_b": jnp.asarray(rng.standard_normal((2, 8)).astype(np.float32)),
        },
        "plain": {"kernel": jnp.ones((4, 4))},
    }
    fused = engine.fuse_lora_weight(params)
    expect = np.asarray(params["proj"]["kernel"]) + \
        np.asarray(params["proj"]["lora_a"]) @ \
        np.asarray(params["proj"]["lora_b"])
    np.testing.assert_allclose(np.asarray(fused["proj"]["kernel"]), expect,
                               rtol=1e-5)
    # lora_a zeroed so a LoRA-aware forward doesn't double-count
    assert (np.asarray(fused["proj"]["lora_a"]) == 0).all()
    np.testing.assert_array_equal(np.asarray(fused["plain"]["kernel"]),
                                  np.asarray(params["plain"]["kernel"]))
    # training params untouched (functional fuse)
    assert not (np.asarray(params["proj"]["lora_a"]) == 0).all()
    # unfuse inverts an in-place-style fuse (lora factors intact)
    manual_fused = {"proj": dict(params["proj"],
                                 kernel=jnp.asarray(expect)),
                    "plain": params["plain"]}
    unfused = engine.unfuse_lora_weight(manual_fused)
    np.testing.assert_allclose(np.asarray(unfused["proj"]["kernel"]),
                               np.asarray(params["proj"]["kernel"]),
                               rtol=1e-4, atol=1e-5)


def test_eval_train_mode_flip():
    engine, cfg = _make_hybrid_engine()
    engine.eval()
    assert engine._in_eval
    engine.train()
    assert not engine._in_eval
