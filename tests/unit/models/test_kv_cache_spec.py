"""KV-cache container spec: the int32 sublane packing (4 head-dim rows
per word) only exists for head_dim % 4 == 0 — explicit opt-in must fail
loudly, auto mode must fall back to the plain int8 container with a
one-time warning."""

import dataclasses

import jax.numpy as jnp
import pytest

from deepspeed_tpu.models.transformer_lm import (
    _PACK_DISABLED_WARNED,
    TransformerConfig,
    kv_cache_spec,
)

# n_embd=30 / n_head=2 -> head_dim=15, not a multiple of 4
ODD = dict(vocab_size=64, max_seq_len=16, n_embd=30, n_layer=1, n_head=2,
           dtype=jnp.float32, kv_cache_quant=True)


def test_packed_explicit_raises_on_odd_head_dim():
    cfg = TransformerConfig(**ODD, kv_cache_packed=True)
    with pytest.raises(ValueError, match="head_dim % 4"):
        kv_cache_spec(cfg)


def test_packed_auto_falls_back_with_one_warning():
    cfg = TransformerConfig(**ODD, kv_cache_packed=None)
    _PACK_DISABLED_WARNED.discard(cfg.head_dim)
    dtype, cache_d, packed = kv_cache_spec(cfg)
    assert (dtype, cache_d, packed) == (jnp.int8, 15, False)
    assert cfg.head_dim in _PACK_DISABLED_WARNED  # warned this call...
    dtype2, _, _ = kv_cache_spec(cfg)  # ...and only once (set-gated)
    assert dtype2 == jnp.int8


def test_packed_auto_engages_on_aligned_head_dim():
    cfg = TransformerConfig(**{**ODD, "n_embd": 32},  # head_dim 16
                            kv_cache_packed=None)
    dtype, cache_d, packed = kv_cache_spec(cfg)
    assert packed and dtype == jnp.int32 and cache_d == 4

    off = dataclasses.replace(cfg, kv_cache_packed=False)
    dtype, cache_d, packed = kv_cache_spec(off)
    assert (dtype, cache_d, packed) == (jnp.int8, 16, False)


@pytest.mark.parametrize("routed", [False, True])
def test_remat_hands_a_block_its_layer_and_its_experts(routed):
    """``_ScanBlock`` names ``TransformerBlock``'s static arguments to
    ``nn.remat`` by POSITION (decode, deterministic) and passes the scan's
    counter and the expert leaves after the cache: with ``remat`` on, a
    model of layer kinds (and a routed FFN) gives the logits and the decode
    step of the same model without it, to the bit."""
    import jax
    import numpy as np

    from deepspeed_tpu.models.transformer_lm import (TransformerLM,
                                                     transformer_config)

    sizes = dict(vocab_size=128, max_seq_len=64, n_embd=32, n_layer=4,
                 n_head=4, n_kv_head=2, head_size=8, ffn_dim=16,
                 layer_types=["sliding_attention", "full_attention"] * 2,
                 sliding_window=16, dtype=jnp.float32,
                 **(dict(n_experts=4, experts_per_token=2) if routed else {}))
    ids = jnp.asarray(np.random.default_rng(0).integers(1, 128, (2, 12)),
                      jnp.int32)
    outs = []
    for remat in (False, True):
        model = TransformerLM(transformer_config("mellum", remat=remat,
                                                 **sizes))
        params = model.init(jax.random.PRNGKey(0), ids,
                            method=model.logits)["params"]
        logits = model.apply({"params": params}, ids, method=model.logits)
        _, cache = model.apply({"params": params}, ids, method=model.prefill,
                               mutable=["cache"])
        step, _ = model.apply({"params": params, "cache": cache["cache"]},
                              ids[:, :1], jnp.asarray(12),
                              method=model.decode, mutable=["cache"])
        outs.append((np.asarray(logits), np.asarray(step)))
    np.testing.assert_array_equal(outs[0][0], outs[1][0])
    np.testing.assert_array_equal(outs[0][1], outs[1][1])
