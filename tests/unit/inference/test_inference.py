"""Inference-stack tests (≅ reference tests/unit/inference/test_inference.py
model × dtype sweep, scaled to the unit harness):

- KV-cache decode logits == full-context recompute, per model family
- greedy generate with cache == naive argmax loop without cache
- AutoTP rule inference classifies col/row/embedding correctly
- TP generate produces identical tokens to single-replica generate
- sampling knobs (temperature/top_k/top_p) produce valid tokens
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models.lm_config import FAMILY_PRESETS, transformer_config
from deepspeed_tpu.models.transformer_lm import TransformerLM
from deepspeed_tpu.parallel import initialize_mesh

TINY = dict(vocab_size=64, max_seq_len=48, n_embd=32, n_layer=2, n_head=4,
            dtype=jnp.float32)


def _model(family, **kw):
    cfg = transformer_config(family, **{**TINY, **kw})
    return TransformerLM(cfg), cfg


def _init(model, B=2, T=8, seed=0):
    ids = jax.random.randint(jax.random.PRNGKey(seed), (B, T), 0, 64)
    # (inside one jit: op by op a model's init is a hundred small compiles)
    params = jax.jit(lambda: model.init(
        {"params": jax.random.PRNGKey(1)}, ids,
        method=model.logits))()["params"]
    return params, ids


@pytest.mark.parametrize("family", sorted(FAMILY_PRESETS))
def test_kv_cache_decode_matches_recompute(family):
    kw = {"n_kv_head": 2} if family == "llama" else {}
    model, cfg = _model(family, **kw)
    params, ids = _init(model)

    # full-context logits (no cache); each program compiled once
    full = jax.jit(lambda p: model.apply({"params": p}, ids,
                                         method=model.logits))(params)

    # prefill on the first 5 tokens, then decode the rest one by one
    pre, vars_ = jax.jit(lambda p: model.apply(
        {"params": p}, ids[:, :5], method=model.prefill,
        mutable=["cache"]))(params)
    np.testing.assert_allclose(np.asarray(pre), np.asarray(full[:, :5]),
                               rtol=2e-4, atol=2e-4)
    cache = vars_["cache"]
    decode = jax.jit(lambda p, cache, token, t: model.apply(
        {"params": p, "cache": cache}, token, t, method=model.decode,
        mutable=["cache"]))
    for t in range(5, ids.shape[1]):
        step, vars_ = decode(params, cache, ids[:, t:t + 1],
                             jnp.asarray(t, jnp.int32))
        cache = vars_["cache"]
        np.testing.assert_allclose(np.asarray(step[:, 0]),
                                   np.asarray(full[:, t]),
                                   rtol=2e-4, atol=2e-4, err_msg=f"pos {t}")


def test_generate_greedy_matches_naive():
    model, cfg = _model("gpt2")
    params, ids = _init(model, B=2, T=6)
    engine = ds.init_inference(model=model, model_parameters=params,
                               config={"dtype": "float32"})
    out = engine.generate(ids, max_new_tokens=6)
    assert out.shape == (2, 12)

    # naive: recompute full logits each step, take argmax
    cur = np.asarray(ids)
    for _ in range(6):
        logits = model.apply({"params": params}, jnp.asarray(cur),
                             method=model.logits)
        nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1))[:, None]
        cur = np.concatenate([cur, nxt], axis=1)
    np.testing.assert_array_equal(out, cur)


def test_generate_sampling_and_eos():
    model, cfg = _model("gpt2")
    params, ids = _init(model, B=2, T=4)
    engine = ds.init_inference(model=model, model_parameters=params,
                               config={"dtype": "float32"})
    out = engine.generate(ids, max_new_tokens=8, do_sample=True,
                          temperature=0.8, top_k=10, top_p=0.9, seed=3)
    assert out.shape == (2, 12)
    assert (out >= 0).all() and (out < 64).all()
    # eos early-exit: force eos to the first greedily-produced token
    g = engine.generate(ids, max_new_tokens=4)
    eos = int(g[0, 4])
    out2 = engine.generate(ids[:1], max_new_tokens=8, eos_token_id=eos)
    assert out2.shape[1] <= 12


def test_auto_tp_rules_classification():
    from deepspeed_tpu.module_inject import auto_tp_rules

    model, cfg = _model("llama")
    params, _ = _init(model)
    rules = auto_tp_rules(params, tp_size=2)
    spec = rules.spec_for("blocks/block/attn/q_proj/kernel")
    assert spec is not None and spec[-1] == "model"          # column
    spec = rules.spec_for("blocks/block/attn/o_proj/kernel")
    assert spec is not None and spec[-2] == "model"          # row
    spec = rules.spec_for("embed_tokens/embedding")
    assert spec is not None and spec[-2] == "model"          # vocab-parallel
    spec = rules.spec_for("blocks/block/mlp/down_proj/kernel")
    assert spec is not None and spec[-2] == "model"          # row


def test_tp_generate_matches_single_replica():
    from deepspeed_tpu.parallel import reset_mesh

    model, cfg = _model("llama")
    params, ids = _init(model, B=2, T=5)
    # true single-replica reference: pure data mesh, tp=1
    ref_mesh = initialize_mesh(data=8)
    ref_engine = ds.init_inference(model=model, model_parameters=params,
                                   config={"dtype": "float32"}, mesh=ref_mesh)
    assert ref_engine.mp_world_size == 1
    want = ref_engine.generate(ids, max_new_tokens=5)

    reset_mesh()
    tp_mesh = initialize_mesh(data=1, model=8)
    tp_engine = ds.init_inference(model=model, model_parameters=params,
                                  config={"dtype": "float32", "mp_size": 8},
                                  mesh=tp_mesh)
    assert tp_engine.mp_world_size == 8
    got = tp_engine.generate(ids, max_new_tokens=5)
    np.testing.assert_array_equal(got, want)


def test_transformer_lm_trains_with_engine():
    """The unified model doubles as a training model (engine convention)."""
    model, cfg = _model("llama", remat=True)
    engine, _, _, _ = ds.initialize(
        model=model,
        config={"train_micro_batch_size_per_gpu": 1,
                "gradient_accumulation_steps": 1,
                "optimizer": {"type": "Adam", "params": {"lr": 5e-3}},
                "zero_optimization": {"stage": 2}})
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(
        0, 64, (engine.train_batch_size(), 16)).astype(np.int32)}
    l0 = float(engine.train_batch(batch=batch))
    for _ in range(4):
        ln = float(engine.train_batch(batch=batch))
    assert np.isfinite(ln) and ln < l0
