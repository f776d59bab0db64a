"""Fused decode-attention kernel tests (reference softmax_context analog,
pt_binding.cpp:1910-1975). Pallas runs in interpreter mode on CPU."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention.decode_attention import (
    decode_attention,
    pick_block_s,
)


def _ds(cache):
    """Tests build caches (B, KV, S, D) for readability; the kernel takes
    the model's positions-minor (B, KV, D, S) layout."""
    return cache.transpose(0, 1, 3, 2)


def _reference(q, k, v, lengths, slopes=None):
    B, H, D = q.shape
    _, KV, S, _ = k.shape
    rep = H // KV
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("bhd,bhsd->bhs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(D)
    pos = jnp.arange(S)[None, None, :]
    if slopes is not None:
        s = s + slopes[None, :, None] * (pos - (lengths[:, None, None] - 1))
    s = jnp.where(pos < lengths[:, None, None], s, -1e30)
    return jnp.einsum("bhs,bhsd->bhd", jax.nn.softmax(s, axis=-1),
                      v.astype(jnp.float32))


@pytest.mark.parametrize("B,H,KV,D,S,block", [
    (2, 4, 4, 64, 128, 64),     # MHA
    (2, 8, 2, 64, 256, 128),    # GQA 4x
    (1, 4, 1, 128, 256, 256),   # MQA
])
def test_matches_reference(B, H, KV, D, S, block):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, KV, S, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, KV, S, D)), jnp.float32)
    lengths = jnp.asarray(rng.integers(1, S + 1, B), jnp.int32)
    out = decode_attention(q, _ds(k), _ds(v), lengths, block_s=block)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_reference(q, k, v, lengths)),
                               atol=1e-4, rtol=1e-4)


def test_alibi_bias():
    rng = np.random.default_rng(1)
    B, H, D, S = 2, 4, 64, 128
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
    lengths = jnp.asarray([100, 37], jnp.int32)
    slopes = jnp.asarray(rng.standard_normal(H) * 0.1, jnp.float32)
    out = decode_attention(q, _ds(k), _ds(v), lengths, alibi_slopes=slopes,
                           block_s=64)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_reference(q, k, v, lengths, slopes)),
        atol=1e-4, rtol=1e-4)


def test_scalar_length_broadcasts():
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((3, 2, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((3, 2, 64, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((3, 2, 64, 64)), jnp.float32)
    out = decode_attention(q, _ds(k), _ds(v), jnp.asarray(17, jnp.int32),
                           block_s=64)
    expect = _reference(q, k, v, jnp.full(3, 17, jnp.int32))
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=1e-4, rtol=1e-4)


def test_pick_block_s():
    assert pick_block_s(2048) == 1024  # tuned default (PERF.md §8)
    assert pick_block_s(512) == 512
    assert pick_block_s(192) == 64
    assert pick_block_s(100) == 4   # 100 = 4 * 25
    # length-aware preference: >= 8k caches take the 4096 block the
    # round-5 sweep measured fastest (kv_int8_results.json block rows)
    assert pick_block_s(8192) == 4096
    assert pick_block_s(16384) == 4096
    assert pick_block_s(4096) == 1024
    assert pick_block_s(97) == 1
    # a caller's preference (TransformerConfig.decode_block) wins over it
    assert pick_block_s(16384, preferred=1024) == 1024


def test_model_decode_kernel_matches_jnp_path():
    """CachedAttention with decode_kernel on vs off: same generation."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.models.lm_config import TransformerConfig
    from deepspeed_tpu.models.transformer_lm import TransformerLM

    prompts = np.arange(6, dtype=np.int32)[None] % 32

    def gen(mode):
        cfg = TransformerConfig(vocab_size=32, max_seq_len=64, n_embd=64,
                                n_layer=2, n_head=2, dtype=jnp.float32,
                                decode_kernel=mode)
        eng = ds.init_inference(TransformerLM(cfg), config={"dtype": "fp32"})
        return eng.generate(prompts, max_new_tokens=8)

    out_off = gen("off")
    out_on = gen("on")
    np.testing.assert_array_equal(out_on, out_off)


def test_bf16_matches_reference():
    """bf16 inputs exercise the actual production path (round 5: MXU
    operands stay bf16 — the fp32 tests above are byte-identical to the
    pre-change kernel, so this is the only coverage of the changed dots
    and of the p -> bf16 downcast before the p.V dot)."""
    B, H, KV, D, S = 2, 4, 2, 64, 256
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((B, KV, S, D)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, KV, S, D)), jnp.bfloat16)
    lengths = jnp.asarray([S, S // 3], jnp.int32)
    out = decode_attention(q, _ds(k), _ds(v), lengths, block_s=64)
    assert out.dtype == jnp.bfloat16
    ref = _reference(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=3e-2, rtol=3e-2)


def test_mixed_dtype_query_is_harmonized():
    """fp32 queries against a bf16 cache must not raise (the wrapper
    casts q to the cache dtype and restores the caller's dtype out)."""
    B, H, KV, D, S = 1, 2, 2, 64, 128
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, KV, S, D)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, KV, S, D)), jnp.bfloat16)
    out = decode_attention(q, _ds(k), _ds(v), jnp.asarray([S], jnp.int32),
                           block_s=64)
    assert out.dtype == jnp.float32
    assert bool(jnp.all(jnp.isfinite(out)))


@pytest.mark.parametrize("B,H,KV,D,S,block", [
    (2, 4, 4, 64, 128, 64),     # MHA
    (2, 8, 2, 64, 256, 128),    # GQA 4x
])
def test_int8_kv_cache_matches_dequantized_reference(B, H, KV, D, S, block):
    """int8 cache + per-row scales: the kernel must compute EXACTLY the
    attention over the dequantized cache (int8 * scale), to fp32/bf16
    tolerance — quantization error lives in the cache contents only."""
    from deepspeed_tpu.ops.attention.decode_attention import quantize_kv_rows

    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, KV, S, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, KV, S, D)), jnp.float32)
    lengths = jnp.asarray(rng.integers(1, S + 1, B), jnp.int32)

    k8, ks = quantize_kv_rows(k)
    v8, vs = quantize_kv_rows(v)
    out = decode_attention(q, _ds(k8), _ds(v8), lengths, k_scale=ks,
                           v_scale=vs, block_s=block)
    k_deq = k8.astype(jnp.float32) * ks[..., None]
    v_deq = v8.astype(jnp.float32) * vs[..., None]
    ref = _reference(q, k_deq, v_deq, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)
    # and the quantized result tracks the full-precision one closely
    full = _reference(q, k, v, lengths)
    err = np.max(np.abs(np.asarray(out) - np.asarray(full)))
    assert err < 0.05, f"int8 KV quantization error too large: {err}"


def test_int8_kv_cache_bf16_query():
    from deepspeed_tpu.ops.attention.decode_attention import quantize_kv_rows

    rng = np.random.default_rng(2)
    B, H, KV, D, S = 1, 4, 2, 64, 128
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((B, KV, S, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, KV, S, D)), jnp.float32)
    lengths = jnp.asarray([97], jnp.int32)
    k8, ks = quantize_kv_rows(k)
    v8, vs = quantize_kv_rows(v)
    out = decode_attention(q, _ds(k8), _ds(v8), lengths, k_scale=ks,
                           v_scale=vs, block_s=64)
    assert out.dtype == jnp.bfloat16
    k_deq = k8.astype(jnp.float32) * ks[..., None]
    v_deq = v8.astype(jnp.float32) * vs[..., None]
    ref = _reference(q.astype(jnp.float32), k_deq, v_deq, lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               atol=0.03, rtol=0.03)


@pytest.mark.parametrize("kernel_mode", ["on", "off"])
@pytest.mark.parametrize("packed", [True, False])
def test_model_int8_kv_cache_generates_same_tokens(kernel_mode, packed):
    """kv_cache_quant=True end-to-end: the cache leaves are int8 (or the
    int32 packed container — the default) with per-row scales, and greedy
    generation matches the full-precision cache (tiny model: quantization
    noise below the argmax margin) on both the fused-kernel and einsum
    decode paths."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.models.lm_config import TransformerConfig
    from deepspeed_tpu.models.transformer_lm import TransformerLM

    prompts = np.arange(6, dtype=np.int32)[None] % 32

    def gen(quant):
        cfg = TransformerConfig(vocab_size=32, max_seq_len=64, n_embd=64,
                                n_layer=2, n_head=2, dtype=jnp.float32,
                                decode_kernel=kernel_mode,
                                kv_cache_quant=quant,
                                kv_cache_packed=packed)
        eng = ds.init_inference(TransformerLM(cfg), config={"dtype": "fp32"})
        toks = eng.generate(prompts, max_new_tokens=8)
        return toks, eng

    toks_q, eng_q = gen(True)
    toks_f, _ = gen(False)
    np.testing.assert_array_equal(toks_q, toks_f)

    # the cache really is int8 + scales (half the bytes of bf16); packed
    # mode stores the same bytes 4-per-int32-word with head_dim/4 lanes
    _, cache = eng_q._jit_prefill(eng_q.params, prompts)
    leaves = jax.tree_util.tree_leaves_with_path(cache)
    kv = [lf for p, lf in leaves
          if any(getattr(x, "key", None) in ("k", "v") for x in p)]
    scales = [lf for p, lf in leaves
              if any(getattr(x, "key", None) in ("k_scale", "v_scale")
                     for x in p)]
    # cache layout is positions-minor (B, KV, D, S); packed mode holds 4
    # head-dim rows per int32 word
    want_dtype = jnp.int32 if packed else jnp.int8
    want_d = (64 // 2) // 4 if packed else 64 // 2  # head_dim=32
    assert kv and all(lf.dtype == want_dtype and lf.shape[-2] == want_d
                      and lf.shape[-1] == 64 for lf in kv)
    assert scales and all(lf.dtype == jnp.float32 for lf in scales)


def test_pack_int8_sublanes_round_trip():
    """pack/unpack are exact inverses; byte j of word i is row 4i+j (the
    TPU sublane byte order, so the kernel's bitcast is a free unpack)."""
    from deepspeed_tpu.ops.attention.decode_attention import (
        pack_int8_sublanes,
        unpack_int8_sublanes,
    )

    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.integers(-127, 128, (2, 3, 8, 64)), jnp.int8)
    w = pack_int8_sublanes(x)
    assert w.dtype == jnp.int32 and w.shape == (2, 3, 2, 64)
    np.testing.assert_array_equal(np.asarray(unpack_int8_sublanes(w)),
                                  np.asarray(x))
    # byte 0 of word i is row 4i, sign bits included
    np.testing.assert_array_equal(
        np.asarray(w & 0xFF, np.uint8).astype(np.int8),
        np.asarray(x[..., ::4, :]))


@pytest.mark.parametrize("B,H,KV,D,S,block", [
    (2, 4, 4, 64, 128, 64),     # MHA
    (2, 8, 2, 64, 256, 128),    # GQA 4x
])
def test_packed_int8_kv_cache_matches_unpacked(B, H, KV, D, S, block):
    """The int32-packed cache path computes bit-identically to the plain
    int8 cache path (same quantized values, same kernel math)."""
    from deepspeed_tpu.ops.attention.decode_attention import (
        pack_int8_sublanes,
        quantize_kv_rows,
    )

    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, KV, S, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, KV, S, D)), jnp.float32)
    lengths = jnp.asarray(rng.integers(1, S + 1, B), jnp.int32)
    k8, ks = quantize_kv_rows(k)
    v8, vs = quantize_kv_rows(v)
    out_s8 = decode_attention(q, _ds(k8), _ds(v8), lengths, k_scale=ks,
                              v_scale=vs, block_s=block)
    out_i32 = decode_attention(q, pack_int8_sublanes(_ds(k8)),
                               pack_int8_sublanes(_ds(v8)),
                               lengths, k_scale=ks, v_scale=vs,
                               block_s=block)
    np.testing.assert_array_equal(np.asarray(out_i32), np.asarray(out_s8))


def test_prefill_last_matches_full_prefill():
    """The generation-only prefill (last-position logits, the engine's
    generate() path) must produce bitwise the same cache as the full
    prefill and logits equal to its last row — sampling sees no
    difference, only the (B, T, V) prompt-logits allocation disappears."""
    import deepspeed_tpu as ds
    from deepspeed_tpu.models.lm_config import TransformerConfig
    from deepspeed_tpu.models.transformer_lm import TransformerLM

    prompts = jnp.asarray(np.arange(7, dtype=np.int32)[None] % 32)
    cfg = TransformerConfig(vocab_size=32, max_seq_len=64, n_embd=64,
                            n_layer=2, n_head=2, dtype=jnp.float32,
                            kv_cache_quant=True)
    eng = ds.init_inference(TransformerLM(cfg), config={"dtype": "fp32"})
    eng.generate(np.asarray(prompts), max_new_tokens=2)  # init params
    m, p = TransformerLM(cfg), eng._params_host
    full, v1 = m.apply({"params": p}, prompts, method=m.prefill,
                       mutable=["cache"])
    last, v2 = m.apply({"params": p}, prompts, method=m.prefill_last,
                       mutable=["cache"])
    assert last.shape == (1, 1, 32)
    np.testing.assert_allclose(np.asarray(last[:, 0]),
                               np.asarray(full[:, -1]), rtol=1e-6)
    for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_leaves_with_path(v1["cache"]),
            jax.tree_util.tree_leaves_with_path(v2["cache"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=str(pa))


def test_packed_chunked_decode_matches_unpacked():
    """Multi-token decode (T > 1, the windowed einsum fallback) over a
    packed cache: prefill at an unaligned length, then a 3-token chunk —
    logits must match the plain-int8 cache bit for bit (same quantized
    rows, the fallback unpacks the container)."""
    import deepspeed_tpu  # noqa: F401  (path setup)
    from deepspeed_tpu.models.lm_config import TransformerConfig
    from deepspeed_tpu.models.transformer_lm import TransformerLM

    prompts = jnp.asarray(np.arange(7, dtype=np.int32)[None] % 32)
    chunk = jnp.asarray([[3, 1, 4]], jnp.int32)

    def run(packed):
        cfg = TransformerConfig(vocab_size=32, max_seq_len=64, n_embd=64,
                                n_layer=2, n_head=2, dtype=jnp.float32,
                                decode_kernel="off", kv_cache_quant=True,
                                kv_cache_packed=packed)
        m = TransformerLM(cfg)
        params = m.init({"params": jax.random.PRNGKey(0)}, prompts,
                        method=m.prefill)["params"]
        _, vars_ = m.apply({"params": params}, prompts, method=m.prefill,
                           mutable=["cache"])
        logits, _ = m.apply(
            {"params": params, "cache": vars_["cache"]}, chunk,
            jnp.asarray(prompts.shape[1], jnp.int32), method=m.decode,
            mutable=["cache"])
        return np.asarray(logits)

    np.testing.assert_array_equal(run(True), run(False))
