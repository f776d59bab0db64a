"""``GPT2LMHeadModel`` is the model it was at PR 45: the parameter tree, the
loss and the gradient's norm of the ``tests/unit/simple_model.py``-sized
model are literals saved from commit ``3169263``, whose ``Block`` still had
a second, fused LayerNorm->matmul arm (deleted in PR 46) built to register
this same tree. They guard that the arm that stayed is the plain one."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.unit.simple_model import tiny_gpt2, token_batch

# path, shape, sum of |leaf| at PRNGKey(0) (float32, CPU)
TREE = (
    ("blocks/block/attn/proj/bias", (2, 32), 0.0),
    ("blocks/block/attn/proj/kernel", (2, 32, 32), 296.0441589355469),
    ("blocks/block/attn/qkv/bias", (2, 96), 0.0),
    ("blocks/block/attn/qkv/kernel", (2, 32, 96), 878.5197143554688),
    ("blocks/block/ln_1/bias", (2, 32), 0.0),
    ("blocks/block/ln_1/scale", (2, 32), 64.0),
    ("blocks/block/ln_2/bias", (2, 32), 0.0),
    ("blocks/block/ln_2/scale", (2, 32), 64.0),
    ("blocks/block/mlp/fc/bias", (2, 128), 0.0),
    ("blocks/block/mlp/fc/kernel", (2, 32, 128), 1202.14306640625),
    ("blocks/block/mlp/proj/bias", (2, 32), 0.0),
    ("blocks/block/mlp/proj/kernel", (2, 128, 32), 594.7154541015625),
    ("ln_f/bias", (32,), 0.0),
    ("ln_f/scale", (32,), 32.0),
    ("wpe/embedding", (32, 32), 142.67771911621094),
    ("wte/embedding", (128, 32), 581.7219848632812),
)
LOSS, GRAD_NORM = 5.2771220207214355, 3.881674289703369


@pytest.mark.parametrize("remat", [None, "full", "dots", "dots_plain"])
def test_tree_loss_and_gradient_are_the_parents(remat):
    kw = {} if remat is None else dict(remat=True, remat_policy=remat)
    model, batch = tiny_gpt2(**kw), token_batch(2)
    params = model.init(jax.random.PRNGKey(0), batch,
                        deterministic=True)["params"]
    tree = [("/".join(k.key for k in path), tuple(leaf.shape),
             float(jnp.abs(leaf).sum()))
            for path, leaf in jax.tree_util.tree_leaves_with_path(params)]
    assert [t[:2] for t in tree] == [t[:2] for t in TREE]
    np.testing.assert_allclose([t[2] for t in tree], [t[2] for t in TREE],
                               rtol=1e-6)
    loss, grads = jax.value_and_grad(
        lambda p: model.apply({"params": p}, batch, deterministic=True))(
            params)
    norm = jnp.sqrt(sum(jnp.sum(g * g)
                        for g in jax.tree_util.tree_leaves(grads)))
    np.testing.assert_allclose(float(loss), LOSS, rtol=1e-5)
    np.testing.assert_allclose(float(norm), GRAD_NORM, rtol=1e-4)
