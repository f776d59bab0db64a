"""Flash attention in the unified TransformerLM (full-context forward).

The per-family model exposes ``use_flash_attention`` like GPT2LMHeadModel:
``auto`` turns the Pallas flash kernel on from the tuned crossover length
on TPU; ``True`` forces it (interpret mode here, numerics only). The
streamed param-offload training path and long-context training depend on
this: the einsum formulation materializes the (B, H, T, T) logits tensor,
flash (and its custom_vjp) keeps attention memory O(T).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.lm_config import transformer_config
from deepspeed_tpu.models.transformer_lm import TransformerLM

_TINY = dict(vocab_size=64, n_embd=32, n_layer=1, n_head=2,
             max_seq_len=32, dtype=jnp.float32)


def _loss(model, params, ids):
    return model.apply({"params": params}, {"input_ids": ids},
                       deterministic=True)


def test_flash_forward_and_grads_match_einsum():
    """Forced flash tracks the einsum path for loss AND parameter grads,
    including grouped-query attention (kv heads repeated for the kernel)."""
    cfg_e = transformer_config("llama", n_kv_head=1,
                               use_flash_attention=False, **_TINY)
    cfg_f = transformer_config("llama", n_kv_head=1,
                               use_flash_attention=True, **_TINY)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 64, (2, 32)))
    m_e, m_f = TransformerLM(cfg_e), TransformerLM(cfg_f)
    params = m_e.init({"params": jax.random.PRNGKey(0)}, ids,
                      method=m_e.logits)["params"]

    # (loss and grads as one program a model, not op by op)
    (l_e, g_e), (l_f, g_f) = (
        jax.jit(jax.value_and_grad(lambda p, m=m: _loss(m, p, ids)))(params)
        for m in (m_e, m_f))
    assert abs(float(l_e) - float(l_f)) < 5e-3, (l_e, l_f)
    diffs = jax.tree_util.tree_map(
        lambda a, b: float(jnp.abs(a - b).max()), g_e, g_f)
    assert max(jax.tree_util.tree_leaves(diffs)) < 5e-3


def test_flash_rejects_alibi_and_train_dropout():
    ids = jnp.asarray(np.random.default_rng(1).integers(0, 64, (1, 32)))
    cfg = transformer_config("bloom", use_flash_attention=True, **_TINY)
    m = TransformerLM(cfg)
    with pytest.raises(ValueError, match="alibi"):
        m.init({"params": jax.random.PRNGKey(0)}, ids, method=m.logits)

    cfg = transformer_config("gpt2", use_flash_attention=True,
                             **{**_TINY, "dropout": 0.1})
    m = TransformerLM(cfg)
    with pytest.raises(ValueError, match="dropout"):
        m.init({"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)},
               {"input_ids": ids}, deterministic=False)


def test_flash_auto_off_on_cpu():
    """auto mode keeps the einsum path off-TPU (no interpret-mode crawl)."""
    cfg = transformer_config("gpt2", **_TINY)  # auto is the default
    ids = jnp.asarray(np.random.default_rng(2).integers(0, 64, (1, 32)))
    m = TransformerLM(cfg)
    params = m.init({"params": jax.random.PRNGKey(0)}, ids,
                    method=m.logits)["params"]
    assert np.isfinite(float(_loss(m, params, ids)))
