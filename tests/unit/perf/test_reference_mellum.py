"""``perf/reference/mellum.py`` against ``TransformerLM``'s ``mellum`` preset
at a small size, float32 on the CPU: two periods of three sliding layers and
one full layer, hidden 64, window 16, a sequence of 4 x the window. On the
chip the same reference judges the served tokens at the published widths."""

import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perf.reference import mellum as ref  # noqa: E402

ROPE = {"full_attention": {"rope_type": "yarn", "rope_theta": 500000,
                           "factor": 16,
                           "original_max_position_embeddings": 8192,
                           "beta_fast": 32, "beta_slow": 1,
                           "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
# float32 at "highest" on both sides, sums in another order: logits of size
# ~3 agree to a few 1e-6; one bf16 rounding of one activation is 4e-3 of
# its size, which the last test shows this tolerance refuses
ATOL = 1e-4


def small(n_experts, k, dtype=jnp.float32, **over):
    from deepspeed_tpu.models.transformer_lm import (TransformerLM,
                                                     transformer_config)

    cfg = transformer_config("mellum", **{**dict(
        vocab_size=128, max_seq_len=64, n_embd=64, n_layer=8,
        n_head=4, n_kv_head=2, head_size=32, ffn_dim=32,
        layer_types=PERIOD * 2, sliding_window=16, rope_theta=500000,
        rope_parameters=ROPE, n_experts=n_experts, experts_per_token=k,
        dtype=dtype), **over})
    model = TransformerLM(cfg)
    ids = np.random.default_rng(0).integers(1, 128, (1, 64)).astype(np.int32)
    params = jax.jit(lambda: model.init(jax.random.PRNGKey(1),
                                        jnp.asarray(ids),
                                        method=model.logits))()["params"]
    return cfg, model, params, ids


def reference_of(cfg):
    return ref.make_forward(
        n_head=cfg.n_head, n_kv_head=cfg.n_kv_head, head_dim=cfg.head_dim,
        layer_types=cfg.layer_types, sliding_window=cfg.sliding_window,
        rope_parameters=cfg.rope_parameters,
        experts_per_token=cfg.experts_per_token,
        norm_topk_prob=cfg.norm_topk_prob, eps=cfg.layer_norm_epsilon)


@pytest.mark.parametrize("n_experts,k,norm", [(8, 2, True), (64, 8, True),
                                              (8, 2, False)])
def test_reference_matches_model_logits(n_experts, k, norm):
    cfg, model, params, ids = small(n_experts, k, norm_topk_prob=norm)
    want = model.apply({"params": params}, jnp.asarray(ids),
                       method=model.logits)[0]
    got = reference_of(cfg)(params, ids[0], np.arange(64))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=ATOL)


def test_reference_sees_the_window_and_the_layer_kinds():
    """The reference is sharp where the model is new: without the window,
    with the two rotary sections swapped, or routed without
    renormalising, its logits leave the model's by far more than ATOL."""
    cfg, model, params, ids = small(8, 2)
    want = np.asarray(model.apply({"params": params}, jnp.asarray(ids),
                                  method=model.logits)[0])
    base = dict(n_head=4, n_kv_head=2, head_dim=32, layer_types=PERIOD * 2,
                sliding_window=16, rope_parameters=ROPE,
                experts_per_token=2)
    swapped = {"full_attention": ROPE["sliding_attention"],
               "sliding_attention": ROPE["full_attention"]}
    for change in (dict(sliding_window=64), dict(rope_parameters=swapped),
                   dict(norm_topk_prob=False),
                   dict(layer_types=["full_attention"] * 8)):
        got = ref.make_forward(**{**base, **change})(params, ids[0],
                                                     np.arange(64))
        assert np.abs(np.asarray(got) - want).max() > 100 * ATOL, change


def test_yarn_table_against_the_closed_form():
    """Both tables (the program's, the reference's) against the formula
    of the issue, written out here a third time, at the published
    numbers: d 128, theta 5e5, s 16, L0 8192."""
    from deepspeed_tpu.models.transformer_lm import rope_inv_freq

    d, theta, s, L0 = 128, 500000.0, 16.0, 8192.0

    def corr(beta):
        return d * math.log(L0 / (2 * math.pi * beta)) / (2 * math.log(theta))

    low, high = max(math.floor(corr(32)), 0), min(math.ceil(corr(1)), d - 1)
    i = np.arange(d // 2)
    f = theta ** (-2.0 * i / d)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    want = f / s * ramp + f * (1 - ramp)
    assert (low, high) == (18, 35)
    program, factor = rope_inv_freq(d, ROPE["full_attention"])
    assert factor == pytest.approx(0.1 * math.log(16) + 1, abs=1e-12)
    np.testing.assert_allclose(program, want, rtol=1e-6)
    theirs, factor = ref.inv_freq_and_factor(d, ROPE["full_attention"])
    np.testing.assert_allclose(np.asarray(theirs), want, rtol=1e-5)
    assert factor == pytest.approx(1.2772588722239782)
    plain, one = rope_inv_freq(d, ROPE["sliding_attention"])
    np.testing.assert_allclose(plain, f, rtol=1e-6)
    assert one == 1.0


def test_check_greedy_holds_served_tokens_to_the_logits():
    cfg, model, params, ids = small(8, 2)
    logits_fn = reference_of(cfg)
    prompt = ids[0, :20]
    seq = list(prompt)
    for _ in range(6):      # the reference's own greedy continuation
        padded = np.zeros((64,), np.int32)
        padded[:len(seq)] = seq
        lg = logits_fn(params, padded, np.asarray([len(seq) - 1]))
        seq.append(int(np.argmax(np.asarray(lg[0]))))
    out = ref.check_greedy(logits_fn, params, prompt, seq[20:], 64, 8,
                           2.0 ** -5)
    assert out["ok"] and out["positions"] == 6
    assert out["worst_shortfall"] == 0.0
    wrong = list(seq[20:])
    wrong[3] = (wrong[3] + 1) % 128
    assert not ref.check_greedy(logits_fn, params, prompt, wrong, 64, 8,
                                1e-6)["ok"]


@pytest.mark.parametrize("fault", [
    dict(sliding_window=8),
    dict(rope_parameters={"full_attention": ROPE["sliding_attention"],
                          "sliding_attention": ROPE["full_attention"]})],
    ids=["half_the_window", "rotary_sections_swapped"])
def test_a_planted_mask_or_position_fault_passes_the_worst_limit(fault):
    """What ``WORST_FACTOR`` is for: a program that masks another window or
    turns positions by the other table chooses tokens the reference puts
    far below its best. Greedy tokens of such a program, held to the
    reference of the configuration at serve.py's tolerance, lie beyond
    8 x 2**-5 of the scale at some position, so the worst limit refuses
    them whatever share of positions the share limit allows."""
    cfg, _, params, ids = small(8, 2)
    _, faulty, _, _ = small(8, 2, **fault)
    forward = jax.jit(lambda seq: faulty.apply(
        {"params": params}, seq[None], method=faulty.logits)[0])
    prompt, seq = ids[0, :24], list(ids[0, :24])
    for _ in range(32):
        padded = np.zeros((64,), np.int32)
        padded[:len(seq)] = seq
        seq.append(int(np.argmax(np.asarray(forward(jnp.asarray(padded))
                                            [len(seq) - 1]))))
    out = ref.check_greedy(reference_of(cfg), params, prompt, seq[24:], 64,
                           32, 2.0 ** -5)
    assert not out["ok"]
    assert out["worst_shortfall"] > out["tolerance_there"]
    assert out["tolerance_there"] == pytest.approx(
        ref.WORST_FACTOR * 2.0 ** -5 * out["scale_there"])


def test_a_bfloat16_computation_fails_the_float32_tolerance():
    """The tolerance the float32 tests use is one a bfloat16 run of the
    same configuration does not meet: it is a test of the arithmetic, not
    only of the wiring."""
    cfg, model, params, ids = small(8, 2)
    _, model16, _, _ = small(8, 2, dtype=jnp.bfloat16)
    got = model16.apply({"params": params}, jnp.asarray(ids),
                        method=model16.logits)[0]
    want = reference_of(cfg)(params, ids[0], np.arange(64))
    assert np.abs(np.asarray(got, np.float32)
                  - np.asarray(want)).max() > 10 * ATOL
