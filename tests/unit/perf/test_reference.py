"""The benchmark's plain references against the program's models, at a tiny
width in float32 on the CPU. On the chip the same references judge the
served tokens and the trainer's loss at the published widths."""

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

from perf.reference import gpt2 as ref_gpt2  # noqa: E402
from perf.reference import gpt_neox as ref_neox  # noqa: E402


def _randomize(tree, seed):
    """Flax zero-initialises biases; a reference that dropped one would
    pass. Give every leaf noise."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree_util.tree_unflatten(treedef, [
        leaf + 0.05 * jax.random.normal(k, leaf.shape, leaf.dtype)
        for leaf, k in zip(leaves, keys)])


def test_gpt2_reference_matches_model():
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel

    cfg = GPT2Config(vocab_size=97, n_positions=16, n_embd=32, n_layer=3,
                     n_head=4, dtype=jnp.float32, use_flash_attention=False)
    model = GPT2LMHeadModel(cfg)
    ids = np.random.default_rng(0).integers(0, 97, (2, 16)).astype(np.int32)
    params = _randomize(jax.jit(model.init)(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(0)},
        {"input_ids": ids})["params"], 1)
    want = jax.jit(lambda p, x: model.apply({"params": p}, x,
                                            method=model.logits))(
        params, jnp.asarray(ids))
    logits = ref_gpt2.make_forward(n_head=4)
    for b in range(2):
        got = logits(params, jnp.asarray(ids[b]))
        np.testing.assert_allclose(got, want[b], atol=2e-4, rtol=2e-4)

    labels, ref_loss = ref_gpt2.greedy_labels_and_loss(logits, params, ids)
    loss = jax.jit(lambda p, b: model.apply({"params": p}, b,
                                            deterministic=True))(
        params, {"input_ids": ids, "labels": labels})
    assert abs(float(loss) - ref_loss) < 1e-4
    # the labels are the sharp part: a model off by one layer is far off
    short = jax.tree_util.tree_map(lambda x: x, params)
    short["blocks"] = jax.tree_util.tree_map(lambda a: a[:2],
                                             params["blocks"])
    cut = GPT2LMHeadModel(GPT2Config(
        vocab_size=97, n_positions=16, n_embd=32, n_layer=2, n_head=4,
        dtype=jnp.float32, use_flash_attention=False))
    loss_cut = jax.jit(lambda p, b: cut.apply({"params": p}, b,
                                              deterministic=True))(
        short, {"input_ids": ids, "labels": labels})
    assert float(loss_cut) - ref_loss > 0.02


@pytest.fixture(scope="module")
def neox():
    from deepspeed_tpu.models.transformer_lm import (TransformerLM,
                                                     transformer_config)

    cfg = transformer_config("gpt-neox", vocab_size=101, max_seq_len=16,
                             n_embd=64, n_layer=2, n_head=4,
                             dtype=jnp.float32, use_flash_attention=False)
    model = TransformerLM(cfg)
    params = _randomize(model.init({"params": jax.random.PRNGKey(0)},
                                   jnp.zeros((1, 8), jnp.int32),
                                   method=model.logits)["params"], 2)
    program = jax.jit(lambda ids: model.apply(
        {"params": params}, ids[None], method=model.logits)[0])
    return program, params, ref_neox.make_forward(n_head=4, rotary_pct=0.25)


def test_gpt_neox_reference_matches_model(neox):
    program, params, logits = neox
    ids = np.random.default_rng(1).integers(1, 101, (16,)).astype(np.int32)
    want = program(jnp.asarray(ids))
    got = logits(params, ids, np.arange(16))
    # the reference computes the published exact GELU, the preset the tanh
    # form: up to 5e-4 apart per activation
    np.testing.assert_allclose(got, want, atol=5e-3, rtol=5e-3)


def test_check_greedy_accepts_the_argmax_and_refuses_another_token(neox):
    program, params, logits = neox
    prompt = np.random.default_rng(2).integers(1, 101, 9).astype(np.int32)
    seq = np.zeros((16,), np.int32)
    seq[:9] = prompt
    for n in range(9, 15):                  # greedy by the program's model
        seq[n] = int(jnp.argmax(program(jnp.asarray(seq))[n - 1]))
    out = [int(t) for t in seq[9:15]]
    good = ref_neox.check_greedy(logits, params, prompt, out, 16, 16,
                                 2 ** -5)
    assert good["ok"] and good["positions"] == 6
    bad_out = list(out)
    bad_out[3] = (bad_out[3] + 1) % 101
    bad = ref_neox.check_greedy(logits, params, prompt, bad_out, 16, 16,
                                2 ** -5)
    assert not bad["ok"]
