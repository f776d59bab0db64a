"""MoE-GPT family + DeepSpeedTransformerLayer — analogs of reference
megatron_gpt_moe container and ops/transformer kernel tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models.gpt_moe import GPTMoEConfig, GPTMoEModel
from deepspeed_tpu.parallel import initialize_mesh
from deepspeed_tpu.parallel import mesh as mesh_mod


def _tiny(**kw):
    base = dict(vocab_size=64, n_positions=32, n_embd=32,
                n_layer=4, n_head=2, num_experts=4,
                drop_tokens=False, capacity_factor=2.0)
    base.update(kw)
    return GPTMoEConfig(**base)


def test_moe_gpt_trains():
    model = GPTMoEModel(_tiny())
    config = {
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "Adam", "params": {"lr": 3e-3}},
        "steps_per_print": 1000,
    }
    engine, _, _, _ = ds.initialize(model=model, config=config)
    rng = np.random.default_rng(0)
    b = {"input_ids": rng.integers(
        0, 64, (engine.train_batch_size(), 16)).astype(np.int32)}
    losses = [float(engine.train_batch(batch=b)) for _ in range(8)]
    assert losses[-1] < losses[0], losses


def test_moe_blocks_alternate():
    model = GPTMoEModel(_tiny(moe_every=2))
    b = {"input_ids": jnp.ones((2, 8), jnp.int32)}
    # (the tree alone: shapes, nothing computed)
    params = jax.eval_shape(lambda: model.init(
        {"params": jax.random.PRNGKey(0),
         "gating": jax.random.PRNGKey(1)}, b))["params"]
    # blocks 1 and 3 are MoE, 0 and 2 dense
    assert "moe" in params["block_1"] and "moe" in params["block_3"]
    assert "mlp_fc" in params["block_0"] and "mlp_fc" in params["block_2"]


def test_pyramid_experts():
    model = GPTMoEModel(_tiny(num_experts=[2, 4]))
    b = {"input_ids": jnp.ones((2, 8), jnp.int32)}
    params = jax.eval_shape(lambda: model.init(
        {"params": jax.random.PRNGKey(0),
         "gating": jax.random.PRNGKey(1)}, b))["params"]
    g1 = params["block_1"]["moe"]["gate"]["kernel"]
    g3 = params["block_3"]["moe"]["gate"]["kernel"]
    assert g1.shape[-1] == 2 and g3.shape[-1] == 4


def test_moe_gpt_expert_parallel_mesh():
    mesh_mod.reset_mesh()
    mesh = initialize_mesh(data=2, expert=4)
    model = GPTMoEModel(_tiny())
    config = {
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "steps_per_print": 1000,
    }
    engine, _, _, _ = ds.initialize(model=model, config=config, mesh=mesh)
    rng = np.random.default_rng(0)
    b = {"input_ids": rng.integers(
        0, 64, (engine.train_batch_size(), 16)).astype(np.int32)}
    l0 = float(engine.train_batch(batch=b))
    l1 = float(engine.train_batch(batch=b))
    assert np.isfinite(l0) and np.isfinite(l1)


class TestDeepSpeedTransformerLayer:
    def test_forward_shapes_both_orderings(self):
        from deepspeed_tpu.ops.transformer import (
            DeepSpeedTransformerConfig,
            DeepSpeedTransformerLayer,
        )

        for pre_ln in (False, True):
            cfg = DeepSpeedTransformerConfig(
                hidden_size=32, intermediate_size=64, heads=2,
                attn_dropout_ratio=0.0, hidden_dropout_ratio=0.0,
                pre_layer_norm=pre_ln, training=False)
            layer = DeepSpeedTransformerLayer(cfg)
            x = jnp.ones((2, 8, 32))
            mask = jnp.ones((2, 8), jnp.int32)
            params = layer.init(jax.random.PRNGKey(0), x, mask)
            out = layer.apply(params, x, mask)
            assert out.shape == x.shape

    def test_matches_bert_layer_post_ln(self):
        """Post-LN DeepSpeedTransformerLayer ≡ BertLayer numerics (the
        reference's kernel-vs-HF-BERT equivalence test shape)."""
        from deepspeed_tpu.models.bert import BertConfig, BertLayer
        from deepspeed_tpu.ops.transformer import (
            DeepSpeedTransformerConfig,
            DeepSpeedTransformerLayer,
        )

        cfg = DeepSpeedTransformerConfig(
            hidden_size=32, intermediate_size=64, heads=2,
            attn_dropout_ratio=0.0, hidden_dropout_ratio=0.0,
            pre_layer_norm=False, training=False)
        layer = DeepSpeedTransformerLayer(cfg)
        x = jnp.asarray(np.random.default_rng(0)
                        .standard_normal((2, 8, 32)).astype(np.float32))
        params = layer.init(jax.random.PRNGKey(0), x)
        out = layer.apply(params, x)

        bcfg = BertConfig(hidden_size=32, num_attention_heads=2,
                          intermediate_size=64, hidden_dropout_prob=0.0,
                          attention_probs_dropout_prob=0.0)
        ref_layer = BertLayer(bcfg)
        ref_out = ref_layer.apply(
            {"params": params["params"]["layer"]}, x, None, True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                                   rtol=1e-5, atol=1e-6)
