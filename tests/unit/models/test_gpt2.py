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


# (remat policy, use_flash_attention): the XLA attention under every policy,
# and the kernel (interpret mode) where its outputs are kept and where not
@pytest.mark.parametrize("remat, flash", [
    (None, False), ("full", False), ("dots", False), ("dots_plain", False),
    ("full", True), ("dots_plain", True)])
def test_tree_loss_and_gradient_are_the_parents(remat, flash):
    kw = {} if remat is None else dict(remat=True, remat_policy=remat)
    kw["use_flash_attention"] = flash
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


def _count(jaxpr, primitive):
    """Equations of one primitive in a jaxpr, scan and remat bodies included."""
    return sum((eqn.primitive.name == primitive)
               + sum(_count(sub, primitive)
                     for sub in jax.core.jaxprs_in_params(eqn.params))
               for eqn in jaxpr.eqns)


# forward + dQ + dK/dV a layer body; "dots_plain" is the oracle that keeps
# no kernel output, so its backward runs the forward kernel a second time
@pytest.mark.parametrize("remat, kernels",
                         [(None, 3), ("full", 3), ("dots", 3),
                          ("dots_plain", 4)])
def test_the_backward_runs_the_forward_kernel_only_without_its_outputs(
        remat, kernels):
    kw = {} if remat is None else dict(remat=True, remat_policy=remat)
    model, batch = tiny_gpt2(use_flash_attention=True, **kw), token_batch(2)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), batch)["params"])
    grad = jax.make_jaxpr(jax.grad(
        lambda p: model.apply({"params": p}, batch)))(params)
    assert _count(grad.jaxpr, "pallas_call") == kernels


def test_a_full_remat_block_keeps_its_arguments_and_the_kernels_outputs():
    from jax._src.ad_checkpoint import saved_residuals

    from deepspeed_tpu.models.gpt2 import _ScanBody
    config = tiny_gpt2(use_flash_attention=True, remat=True,
                       remat_policy="full").config
    body, x = _ScanBody(config), jnp.zeros((2, 16, config.n_embd))
    variables = jax.eval_shape(
        lambda: body.init(jax.random.PRNGKey(0), x, True))
    kept = saved_residuals(lambda v, x: body.apply(v, x, True)[0].sum(),
                           variables, x)
    inside = [(tuple(aval.shape), why) for aval, why in kept
              if "from the argument" not in why]
    heads, head_dim = 2 * config.n_head, config.n_embd // config.n_head
    # jax hands a kept value that the forward also reads (`out`, by
    # attn/proj) through a reduce_precision of its own dtype, which hides
    # the name; `lse` has no reader in the forward and shows it, and what is
    # kept of it is its one distinct row, not the kernel's eight sublanes
    assert len(inside) == 2, inside
    (out, why_out), (lse, why_lse) = sorted(inside, key=lambda r: "lse" in r[1])
    assert out == (heads, 16, head_dim)
    assert "flash_out" in why_out or "reduce_precision" in why_out
    assert lse == (heads, 1, 16) and "flash_lse" in why_lse
    # the block's input and its weights, and no activation besides
    args = [why for _, why in kept if "from the argument" in why]
    assert sum("argument x" in why for why in args) == 1
    assert all("argument x" in why or "['params']['block']" in why
               for why in args)
