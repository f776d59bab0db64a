"""The gated short-convolution state layer (``ShortConvMixer``, PR 54) and
``qk_norm`` in ``CachedAttention``, at small sizes on the CPU: a decode row,
a chunk and a whole sequence are one code path through the carried tail,
bit for bit; a row that does not run keeps its tail; padding shifts nothing
in; a chunk boundary inside the three taps reads across it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.attention_layers import CachedAttention
from deepspeed_tpu.models.lm_config import transformer_config
from deepspeed_tpu.models.lm_parts import apply_rotary
from deepspeed_tpu.models.state_layers import ShortConvMixer, _conv_after_tail
from deepspeed_tpu.models.transformer_lm import TransformerLM

C, K, T = 16, 3, 11


def _config(**over):
    return transformer_config(
        "lfm2_moe", **{**dict(
            vocab_size=64, max_seq_len=32, n_embd=C, n_layer=4, n_head=2,
            n_kv_head=1, ffn_dim=8, n_experts=4, experts_per_token=2,
            first_k_dense=2, dense_ffn_dim=24, dtype=jnp.float32,
            layer_types=["conv", "conv", "full_attention", "conv"]), **over})


def _leaf(rows, layers=2, seed=3):
    """A stacked tail leaf holding something everywhere (what an earlier
    sequence left): a fresh entry must not read it."""
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (layers, rows, (K - 1) * C), jnp.float32)


def _run(x, splits, rows, leaf, layer=1, w=None):
    """``x`` (B, T, C) through ``_conv_after_tail`` without the silu in
    pieces of ``splits`` tokens against ``leaf``: the outputs joined and
    the leaf as the last piece left it."""
    out, at = [], 0
    for n in splits:
        cache = {"conv": leaf, "layer": jnp.int32(layer),
                 "start": jnp.full((x.shape[0],), at, jnp.int32),
                 "rows": jnp.asarray(rows, jnp.int32)}
        y, _, leaf = jax.jit(_conv_after_tail, static_argnames="silu")(
            cache, x[:, at:at + n], w, None, silu=False)
        out.append(y)
        at += n
    return jnp.concatenate(out, axis=1), leaf


def _whole(x, w):
    """Whole sequences from nothing (no cache), compiled as the pieces
    are: the same three products and two sums an element."""
    return jax.jit(lambda x, w: _conv_after_tail(None, x, w, None,
                                                 silu=False)[0])(x, w)


@pytest.fixture(scope="module")
def taps():
    return jax.random.normal(jax.random.PRNGKey(0), (K, C), jnp.float32)


@pytest.mark.parametrize("splits", [
    [T],                        # the whole sequence as one chunk
    [1] * T,                    # a decode row at a time
    [1, 1, 9],                  # a boundary one and two tokens in: inside
    [2, 9], [9, 2], [10, 1],    # the taps' reach from either side
    [4, 1, 1, 5],               # chunk, decode rows, chunk
])
def test_a_row_a_chunk_and_a_sequence_are_one_path_bit_for_bit(taps, splits):
    x = jax.random.normal(jax.random.PRNGKey(1), (2, T, C), jnp.float32)
    whole = _whole(x, taps)
    from_definition = sum(
        taps[j] * jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))[:, j:j + T]
        for j in range(K))
    np.testing.assert_allclose(np.asarray(whole),
                               np.asarray(from_definition), atol=1e-6)
    got, leaf = _run(x, splits, [2, 0], _leaf(3), w=taps)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(whole))
    # what the sequences carry on: their last two inputs, time-major, in
    # their own rows of their own layer, and nothing else moved
    np.testing.assert_array_equal(
        np.asarray(leaf[1, [2, 0]]),
        np.asarray(x[:, -(K - 1):].reshape(2, -1)))
    np.testing.assert_array_equal(np.asarray(leaf[0]),
                                  np.asarray(_leaf(3)[0]))
    np.testing.assert_array_equal(np.asarray(leaf[1, 1]),
                                  np.asarray(_leaf(3)[1, 1]))


@pytest.mark.parametrize("entries", [2, 9])     # a scatter, the slab's form
def test_a_row_that_does_not_run_keeps_its_tail(taps, entries):
    x = jax.random.normal(jax.random.PRNGKey(2), (entries, 1, C))
    rows = [-1, 1] + [entries + 5] * (entries - 2)  # one runs: pool row 1
    before = _leaf(entries + 1)
    cache = {"conv": before, "layer": jnp.int32(0),
             "start": jnp.full((entries,), 7, jnp.int32),
             "rows": jnp.asarray(rows, jnp.int32)}
    _, _, after = jax.jit(_conv_after_tail, static_argnames="silu")(
        cache, x, taps, None, silu=False)
    kept = np.ones(before.shape[:2], bool)
    kept[0, 1] = False
    np.testing.assert_array_equal(np.asarray(after)[kept],
                                  np.asarray(before)[kept])
    np.testing.assert_array_equal(
        np.asarray(after[0, 1]),
        np.concatenate([np.asarray(before[0, 1, C:]), np.asarray(x[1, 0])]))


def test_padding_past_valid_shifts_nothing_into_the_tail(taps):
    """A prompt's last chunk is padded to the chunk's width: the tail is
    taken at the last REAL token."""
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 8, C))
    cache = {"conv": _leaf(1), "layer": jnp.int32(0),
             "start": jnp.zeros((1,), jnp.int32),
             "rows": jnp.zeros((1,), jnp.int32),
             "valid": jnp.asarray([5], jnp.int32)}
    y, _, leaf = jax.jit(_conv_after_tail, static_argnames="silu")(
        cache, x, taps, None, silu=False)
    np.testing.assert_array_equal(np.asarray(leaf[0, 0]),
                                  np.asarray(x[0, 3:5].reshape(-1)))
    np.testing.assert_array_equal(np.asarray(y[:, :5]),
                                  np.asarray(_whole(x, taps)[:, :5]))


def test_the_mixer_is_two_gates_around_the_taps():
    cfg = _config()
    mixer = ShortConvMixer(cfg)
    u = jax.random.normal(jax.random.PRNGKey(5), (2, T, C))
    params = mixer.init(jax.random.PRNGKey(6), u)["params"]
    assert jax.tree_util.tree_map(lambda p: p.shape, params) == {
        "in_proj": {"kernel": (C, 3 * C)}, "conv_w": (K, C),
        "out_proj": {"kernel": (C, C)}}
    got, leaves = mixer.apply({"params": params}, u)
    assert leaves is None
    bcz = u @ params["in_proj"]["kernel"]
    b, c, z = bcz[..., :C], bcz[..., C:2 * C], bcz[..., 2 * C:]
    v = jnp.pad(b * z, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(params["conv_w"][j] * v[:, j:j + T] for j in range(K))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray((c * conv) @ params["out_proj"]["kernel"]),
        atol=1e-5)


def test_a_model_decodes_what_its_full_forward_computes():
    """Prefill of a prompt, then tokens one at a time through the
    contiguous cache (the tail and rotary K/V side by side), against the
    full forward's logits at the same positions."""
    cfg = _config()
    model = TransformerLM(cfg)
    ids = jnp.asarray(np.random.default_rng(0).integers(1, 64, (2, 14)),
                      jnp.int32)
    # (each program traced and compiled once: op by op the four layers are
    # hundreds of small compiles)
    params = jax.jit(lambda: model.init(
        jax.random.PRNGKey(1), ids[:, :8], method=model.logits))()["params"]
    want = jax.jit(lambda p: model.apply({"params": p}, ids,
                                         method=model.logits))(params)
    logits, vars_ = jax.jit(lambda p: model.apply(
        {"params": p}, ids[:, :9], method=model.prefill,
        mutable=["cache"]))(params)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want[:, :9]),
                               atol=2e-5)
    assert set(vars_["cache"]["cache_store"]) == {"conv", "k", "v", "index"}
    decode = jax.jit(lambda p, cache, token, t: model.apply(
        {"params": p, "cache": cache}, token, t, method=model.decode,
        mutable=["cache"]))
    for t in range(9, 14):
        logits, vars_ = decode(params, vars_["cache"], ids[:, t:t + 1],
                               jnp.int32(t))
        np.testing.assert_allclose(np.asarray(logits[:, 0]),
                                   np.asarray(want[:, t]), atol=2e-5)


def test_qk_norm_norms_each_head_of_q_and_k_before_the_rotary():
    """With ``qk_norm`` the attention block holds a weight of ``head_dim``
    for q and one for k and computes softmax(rot(norm q) rot(norm k)^T)
    v; without it neither leaf exists and nothing of a norm is traced (the
    accepted cells' compiled programs are pinned in
    ``tests/unit/accelerator/test_chip_path.py``)."""
    cfg = _config(pos_emb="rotary")
    H, KV, D = cfg.n_head, cfg.kv_heads, cfg.head_dim
    x = jax.random.normal(jax.random.PRNGKey(7), (1, T, C))
    attn = CachedAttention(cfg)
    params = attn.init(jax.random.PRNGKey(8), x)["params"]
    assert params["q_norm"]["scale"].shape == (D,)
    assert params["k_norm"]["scale"].shape == (D,)
    params = jax.tree_util.tree_map(lambda p: p, params)
    params["q_norm"]["scale"] = 1.0 + 0.3 * jax.random.normal(
        jax.random.PRNGKey(9), (D,))
    params["k_norm"]["scale"] = 1.0 + 0.3 * jax.random.normal(
        jax.random.PRNGKey(10), (D,))
    got, _ = attn.apply({"params": params}, x)

    def norm(v, scale):
        return v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True)
                                 + cfg.layer_norm_epsilon) * scale

    pos = jnp.arange(T)[None]
    q = (x @ params["q_proj"]["kernel"]).reshape(1, T, H, D)
    k = (x @ params["k_proj"]["kernel"]).reshape(1, T, KV, D)
    v = (x @ params["v_proj"]["kernel"]).reshape(1, T, KV, D)
    q = apply_rotary(norm(q, params["q_norm"]["scale"]), pos, rotary_dim=D,
                     theta=cfg.rope_theta)
    k = apply_rotary(norm(k, params["k_norm"]["scale"]), pos, rotary_dim=D,
                     theta=cfg.rope_theta)
    att = jnp.einsum("bthd,bshd->bhts", q, jnp.repeat(k, H // KV, 2)) \
        / np.sqrt(D)
    att = jax.nn.softmax(jnp.where(jnp.tril(jnp.ones((T, T), bool)), att,
                                   -jnp.inf), -1)
    want = jnp.einsum("bhts,bshd->bthd", att, jnp.repeat(v, H // KV, 2))
    np.testing.assert_allclose(
        np.asarray(got),
        np.asarray(want.reshape(1, T, H * D) @ params["o_proj"]["kernel"]),
        atol=1e-5)

    plain = CachedAttention(_config(qk_norm=False))
    leaves = plain.init(jax.random.PRNGKey(8), x)["params"]
    assert set(leaves) == {"q_proj", "k_proj", "v_proj", "o_proj"}
    text = jax.jit(lambda p, x: plain.apply({"params": p}, x)[0]).lower(
        leaves, x).as_text()
    assert "q_norm" not in text and "k_norm" not in text
