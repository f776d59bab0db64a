"""The sigmoid form of the routed FFN (``moe/routed_ffn.py``): the choice
ordered by score + bias, the weights from the unbiased scores times the
scaling factor, a shared expert counted once; and the softmax form bit for
bit what it was."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe.routed_ffn import (CALL_STATS, RoutedFFN, call_stats,
                                          route, routed_ffn,
                                          routed_ffn_reference)

N, C, E, F, K = 24, 32, 8, 16, 3


@pytest.fixture(scope="module")
def layer():
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    h = jax.random.normal(keys[0], (N, C), jnp.float32)
    router = jax.random.normal(keys[1], (C, E), jnp.float32) / np.sqrt(C)
    gate, up = (jax.random.normal(k, (2, E, C, F), jnp.float32) / np.sqrt(C)
                for k in keys[2:4])
    down = jax.random.normal(keys[4], (2, E, F, C), jnp.float32) / np.sqrt(F)
    bias = 0.5 * jax.random.normal(keys[5], (E,), jnp.float32)
    return h, router, gate, up, down, bias


def _scores(h, router):
    return np.asarray(jax.nn.sigmoid(jnp.dot(
        h, router, precision=jax.lax.Precision.HIGHEST)))


def test_the_bias_orders_the_choice_and_leaves_the_weights_alone(layer):
    h, router, *_, bias = layer
    s = _scores(h, router)
    w, e, moved = route(h, router, K, True, scoring="sigmoid", bias=bias,
                        scaling=1.0)
    w, e = np.asarray(w), np.asarray(e)
    biased = np.argsort(-(s + np.asarray(bias)), axis=-1)[:, :K]
    assert (np.sort(e, -1) == np.sort(biased, -1)).all()
    plain = np.argsort(-s, axis=-1)[:, :K]
    differ = sum(len(set(a) - set(b)) for a, b in zip(e, plain))
    assert differ > 0 and int(moved) == differ     # the bias moved some
    # the weights: the UNBIASED scores of the chosen, renormalised
    chosen = np.take_along_axis(s, e, -1)
    np.testing.assert_allclose(
        w, chosen / (chosen.sum(-1, keepdims=True) + 1e-20), rtol=1e-6)
    # a zero bias moves nothing and chooses on the scores
    _, e0, moved0 = route(h, router, K, True, scoring="sigmoid",
                          bias=jnp.zeros((E,)), scaling=1.0)
    assert int(moved0) == 0
    assert (np.sort(np.asarray(e0), -1) == np.sort(plain, -1)).all()


def test_the_scaling_factor_multiplies_the_weights(layer):
    h, router, *_, bias = layer
    w1, e1, _ = route(h, router, K, True, scoring="sigmoid", bias=bias)
    w2, e2, _ = route(h, router, K, True, scoring="sigmoid", bias=bias,
                      scaling=2.446)
    np.testing.assert_array_equal(e1, e2)
    np.testing.assert_allclose(np.asarray(w2), 2.446 * np.asarray(w1),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w2).sum(-1), 2.446, rtol=1e-5)
    # without the renormalisation: the scores themselves, scaled
    w3, _, _ = route(h, router, K, False, scoring="sigmoid", bias=bias,
                     scaling=2.0)
    np.testing.assert_allclose(
        np.asarray(w3),
        2.0 * np.take_along_axis(_scores(h, router), np.asarray(e1), -1),
        rtol=1e-6)


def test_the_kernels_give_the_sum_over_the_chosen_experts(layer):
    h, router, gate, up, down, bias = layer
    scoring = dict(scoring="sigmoid", bias=bias, scaling=2.446)
    y, stats = routed_ffn(h, router, gate, up, down, jnp.asarray(1), k=K,
                          norm_topk_prob=True, **scoring)
    want = routed_ffn_reference(h, router, gate[1], up[1], down[1], k=K,
                                norm_topk_prob=True, **scoring)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-5)
    assert stats.shape == (4,) and int(stats[0]) == N * K
    assert int(stats[3]) == int(route(h, router, K, True, **scoring)[2])
    got = dict(zip(CALL_STATS, np.asarray(call_stats(
        jnp.stack([stats, stats]), E)).tolist()))
    assert got["bias_reordered"] == 2 * int(stats[3])
    assert got["assignments"] == 2 * N * K and got["layer_calls"] == 2


def test_the_softmax_path_is_bit_for_bit_what_it_was(layer):
    """``route`` under softmax returns two values computed as before this
    form existed, ``routed_ffn`` three counts, and a ``RoutedFFN`` with the
    defaults has the parameters it had (no bias, no shared expert)."""
    h, router, gate, up, down, _ = layer
    logits = jnp.dot(h, router, precision=jax.lax.Precision.HIGHEST)
    p = jax.nn.softmax(logits, axis=-1)
    w_old, e_old = jax.lax.top_k(p, K)
    w_old = w_old / jnp.sum(w_old, axis=-1, keepdims=True)
    w, e = route(h, router, K, True)
    np.testing.assert_array_equal(np.asarray(w), np.asarray(w_old))
    np.testing.assert_array_equal(np.asarray(e), np.asarray(e_old))
    _, stats = routed_ffn(h, router, gate, up, down, jnp.asarray(0), k=K,
                          norm_topk_prob=True)
    assert stats.shape == (3,)
    assert np.asarray(call_stats(stats[None], E)).shape == (5,)
    module = RoutedFFN(E, K, True)
    experts = {"gate_proj": gate, "up_proj": up, "down_proj": down}
    params = module.init(jax.random.PRNGKey(1), h[None], experts,
                         jnp.asarray(0))["params"]
    assert set(params) == {"router"}


def test_the_shared_expert_is_counted_once(layer):
    """The module's output is the routed sum plus ONE gated FFN of the
    input, whatever ``k``; zeroing its output matrix leaves the routed sum."""
    h, router, gate, up, down, _ = layer
    experts = {"gate_proj": gate, "up_proj": up, "down_proj": down}
    module = RoutedFFN(E, K, True, "sigmoid", 2.446, 2 * F, jnp.float32)
    x = h[None]
    params = module.init(jax.random.PRNGKey(2), x, experts,
                         jnp.asarray(1))["params"]
    assert set(params) == {"router", "router_bias", "shared_gate_proj",
                           "shared_up_proj", "shared_down_proj"}
    assert params["shared_gate_proj"]["kernel"].shape == (C, 2 * F)
    assert float(jnp.abs(params["router_bias"]).max()) > 0   # seeded, not 0
    y, _ = module.apply({"params": params}, x, experts, jnp.asarray(1))
    routed = routed_ffn_reference(
        h, params["router"], gate[1], up[1], down[1], k=K,
        norm_topk_prob=True, scoring="sigmoid", bias=params["router_bias"],
        scaling=2.446)
    with jax.default_matmul_precision("highest"):
        shared = (jax.nn.silu(h @ params["shared_gate_proj"]["kernel"])
                  * (h @ params["shared_up_proj"]["kernel"])) \
            @ params["shared_down_proj"]["kernel"]
    np.testing.assert_allclose(np.asarray(y[0]),
                               np.asarray(routed + shared), atol=5e-5)
    silent = dict(params, shared_down_proj={
        "kernel": jnp.zeros_like(params["shared_down_proj"]["kernel"])})
    y0, _ = module.apply({"params": silent}, x, experts, jnp.asarray(1))
    np.testing.assert_allclose(np.asarray(y0[0]), np.asarray(routed),
                               atol=5e-5)
