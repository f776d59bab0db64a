"""What Qwen3-Next brought to the model (PR 60), each piece alone at a toy
size: the routed FFN's gated shared expert and the SHARE test (four chips'
routed parts, a quarter of the experts each, plus the gated shared expert
counted once add up to the uncut layer written plainly); the zero-centred
RMSNorm as a value of ``TransformerConfig.norm``, for the QK-norm too; the
output gate on ``CachedAttention``; and the Gated DeltaNet mixer's three
forms (whole sequences, a prompt by chunks, a token at a time) against each
other on its own state leaves. The model whole against its reference, and
through a server, is ``tests/unit/perf/test_reference_qwen3_next.py``'s."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import lm_parts
from deepspeed_tpu.models.gdn_layers import GatedDeltaNetMixer
from deepspeed_tpu.models.lm_config import transformer_config
from deepspeed_tpu.moe.routed_ffn import RoutedFFN

from tests.unit.kinds import kind_config

# float32 sums in another order (four parts added up where the uncut layer
# sums a token's k rows at once): a few roundings of 1e-7 at values ~1; a
# dropped or doubled expert, or a shared expert counted four times, moves
# an output by its whole size
ATOL = 2e-5
E, C, F, N, K = 16, 32, 16, 37, 5


def _uncut_layer(h, router, gate, up, down, shared, w_s):
    """The FFN as the issue writes it, every expert here, in NumPy."""
    h = np.asarray(h, np.float64)
    logit = h @ np.asarray(router, np.float64)
    p = np.exp(logit - logit.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    out = np.zeros_like(h)

    def silu(x):
        return x / (1 + np.exp(-x))

    for n in range(h.shape[0]):
        chosen = np.argsort(-p[n], kind="stable")[:K]
        for e in chosen:
            act = silu(h[n] @ np.asarray(gate[e], np.float64)) \
                * (h[n] @ np.asarray(up[e], np.float64))
            out[n] += p[n, e] / p[n, chosen].sum() \
                * (act @ np.asarray(down[e], np.float64))
        g, u, d = (np.asarray(x, np.float64) for x in shared)
        out[n] += 1 / (1 + np.exp(-(h[n] @ np.asarray(w_s, np.float64)))) \
            * ((silu(h[n] @ g) * (h[n] @ u)) @ d)
    return out


def test_the_four_shares_and_the_gated_shared_expert_add_up_to_the_layer():
    """Chip ``c`` of 4 holds experts ``[4 c, 4 c + 4)`` of 16 under a
    router 16 wide and the top 5: its leaves are the uncut ones rolled by
    ``4 c`` experts with the router's columns rolled alike (the way
    ``tests/unit/moe/test_experts_held.py`` builds Kimi's eight). Every
    chip computes the gated shared expert; the deployment counts it once:
    the four routed parts plus ONE gated shared expert are the uncut
    layer, and the gate is a token's own (not a constant)."""
    ks = jax.random.split(jax.random.PRNGKey(0), 9)
    router = jax.random.normal(ks[0], (C, E))
    gate, up = (jax.random.normal(k, (1, E, C, F)) / np.sqrt(C)
                for k in ks[1:3])
    down = jax.random.normal(ks[3], (1, E, F, C)) / np.sqrt(F)
    shared = (jax.random.normal(ks[4], (C, F)) / np.sqrt(C),
              jax.random.normal(ks[5], (C, F)) / np.sqrt(C),
              jax.random.normal(ks[6], (F, C)) / np.sqrt(F))
    w_s = jax.random.normal(ks[7], (C,)) / np.sqrt(C)
    h = jax.random.normal(ks[8], (1, N, C))
    want = _uncut_layer(h[0], router, gate[0], up[0], down[0], shared, w_s)

    routed_only = RoutedFFN(E, K, True)
    with_shared = RoutedFFN(E, K, True, shared_width=F, dtype=jnp.float32,
                            shared_gate=True)
    parts, ran = [], 0
    for chip in range(4):
        roll = lambda x, axis: jnp.roll(x, -4 * chip, axis)    # noqa: E731
        experts = {"gate_proj": roll(gate, 1)[:, :4],
                   "up_proj": roll(up, 1)[:, :4],
                   "down_proj": roll(down, 1)[:, :4]}
        y, stats = jax.jit(lambda h: routed_only.apply(
            {"params": {"router": roll(router, 1)}}, h, experts,
            jnp.asarray(0)))(h)
        parts.append(np.asarray(y[0], np.float64))
        ran += int(stats[0])
        assert int(stats[4]) == N * K and int(stats[1]) <= 4
    assert ran == N * K
    params = {"router": router, "shared_gate_w": w_s,
              "shared_gate_proj": {"kernel": shared[0]},
              "shared_up_proj": {"kernel": shared[1]},
              "shared_down_proj": {"kernel": shared[2]}}
    first = {"gate_proj": gate[:, :4], "up_proj": up[:, :4],
             "down_proj": down[:, :4]}
    y0, _ = jax.jit(lambda h: with_shared.apply(
        {"params": params}, h, first, jnp.asarray(0)))(h)
    once = np.asarray(y0[0], np.float64) - parts[0]     # the gated shared
    np.testing.assert_allclose(sum(parts) + once, want, atol=ATOL)
    # counted on every chip it would be 4 x: far from the layer
    assert np.abs(sum(parts) + 4 * once - want).max() > 100 * ATOL
    # the gate is a sigmoid of the token's own row
    plain = RoutedFFN(E, K, True, shared_width=F, dtype=jnp.float32)
    y1, _ = jax.jit(lambda h: plain.apply(
        {"params": {k: v for k, v in params.items()
                    if k != "shared_gate_w"}}, h, first, jnp.asarray(0)))(h)
    ungated = np.asarray(y1[0], np.float64) - parts[0]
    ratio = once / ungated
    gates = 1 / (1 + np.exp(-np.asarray(h[0] @ w_s, np.float64)))
    np.testing.assert_allclose(ratio, gates[:, None] * np.ones_like(ratio),
                               rtol=1e-3)
    assert gates.std() > 0.05


def test_the_zero_centred_norm_is_a_value_of_the_configurations_norm():
    cfg = kind_config("gdn_gated")
    assert cfg.norm == "rmsnorm1p" and cfg.qk_norm
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 16)) * 3 + 1
    norm = lm_parts._norm(cfg, "n")
    assert isinstance(norm, lm_parts.ZeroCentredRMSNorm)
    params = norm.init(jax.random.PRNGKey(1), x)["params"]
    w = params["scale"]
    assert w.shape == (16,) and 0.02 < float(jnp.std(w)) < 0.3
    want = np.asarray(x) / np.sqrt(
        np.mean(np.asarray(x) ** 2, -1, keepdims=True) + 1e-6) \
        * (1 + np.asarray(w))
    np.testing.assert_allclose(norm.apply({"params": params}, x), want,
                               atol=1e-5)

    class QK(nn.Module):
        @nn.compact
        def __call__(self, q, k):
            return lm_parts._norm_qk(cfg, q, k)

    q = x.reshape(3, 5, 1, 16)
    got = QK().apply({"params": {"q_norm": params, "k_norm": params}}, q, q)
    np.testing.assert_allclose(got[0].reshape(3, 5, 16), want, atol=1e-5)
    # the other families' norm is flax's, the weight a plain one
    llama = transformer_config("llama", n_embd=16, n_head=2, n_layer=1)
    assert isinstance(lm_parts._norm(llama, "n"), nn.RMSNorm)
    assert isinstance(lm_parts._rms_norm(llama, "n"), nn.RMSNorm)


@pytest.fixture(scope="module")
def mixer():
    cfg = kind_config("gdn_gated", dtype=jnp.float32)
    module = GatedDeltaNetMixer(cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, cfg.n_embd))
    params = jax.jit(lambda: module.init(jax.random.PRNGKey(3), x))()
    return cfg, module, params, x


def test_the_mixers_three_forms_are_one(mixer):
    """Whole sequences from an empty state, against the same rows through
    the cache: a chunk of 16 (row 1: 11 real tokens and padding), then a
    chunk of 8 for row 0, then row 1's remaining tokens one at a time.
    The state leaf is KDA's at the VALUE heads; a key head's q and k serve
    two of them."""
    cfg, module, params, x = mixer
    H, D = cfg.gdn_n_value_heads, cfg.gdn_d_head
    whole, none = jax.jit(lambda x: module.apply(params, x))(x)
    assert none is None and whole.shape == x.shape
    leaves = {"s": jnp.zeros((2, 3, H, D, D), jnp.float32),
              "conv": jnp.zeros((2, 3, 3 * cfg.gdn_channels), jnp.float32)}

    @jax.jit
    def step(leaves, rows, start, valid, x):
        cache = dict(leaves, layer=jnp.asarray(1, jnp.int32),
                     start=start, rows=rows, valid=valid)
        y, new = module.apply(params, x, decode=True, kv_cache=cache)
        return y, dict(leaves, **new)

    i32 = jnp.int32
    rows = jnp.asarray([2, 0], i32)
    y, leaves = step(leaves, rows, jnp.zeros((2,), i32),
                     jnp.asarray([16, 11], i32), x[:, :16])
    np.testing.assert_allclose(y[0], whole[0, :16], atol=1e-5)
    np.testing.assert_allclose(y[1, :11], whole[1, :11], atol=1e-5)
    assert (np.asarray(leaves["s"][0]) == 0).all()      # (layer 0: not ours)
    assert (np.asarray(leaves["s"][1, 1]) == 0).all()   # (row 1: nobody's)
    y, leaves = step(leaves, jnp.asarray([2, 7], i32),
                     jnp.asarray([16, 0], i32), jnp.asarray([8, 8], i32),
                     x[:, 16:24])       # (7: out of range, does not run)
    np.testing.assert_allclose(y[0], whole[0, 16:], atol=1e-5)
    for t in range(11, 16):
        y, leaves = step(leaves, jnp.asarray([9, 0], i32),
                         jnp.asarray([0, t], i32), jnp.asarray([1, 1], i32),
                         x[:, t:t + 1])
        np.testing.assert_allclose(y[1, 0], whole[1, t], atol=1e-5)
