"""Activation-checkpointing tests (≅ reference
tests/unit/runtime/activation_checkpointing/test_activation_checkpointing.py:
checkpointed fwd/bwd must match the non-checkpointed graph exactly)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec
from jax import shard_map

import deepspeed_tpu
from deepspeed_tpu.runtime.activation_checkpointing import checkpointing as ckpt


@pytest.fixture(autouse=True)
def _reset_config():
    ckpt.reset()
    yield
    ckpt.reset()


def _mlp(w):
    def f(x):
        h = jnp.tanh(x @ w)
        return jnp.sum(h * h)

    return f


def test_checkpoint_matches_uncheckpointed():
    ckpt.configure(deepspeed_config={
        "train_batch_size": 1,
        "activation_checkpointing": {"partition_activations": False},
    })
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (16, 16), dtype=jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (8, 16))

    f = _mlp(w)
    ref_val, ref_grad = jax.value_and_grad(f)(x)
    val, grad = jax.value_and_grad(lambda a: ckpt.checkpoint(f, a))(x)
    np.testing.assert_allclose(np.asarray(val), np.asarray(ref_val), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(grad), np.asarray(ref_grad), rtol=1e-6)


def test_configure_from_json_block():
    ckpt.configure(deepspeed_config={
        "train_batch_size": 1,
        "activation_checkpointing": {
            "partition_activations": True,
            "cpu_checkpointing": False,
            "number_checkpoints": 2,
        },
    })
    assert ckpt.is_configured()
    assert ckpt._CONFIG.partition_activations
    assert ckpt._CONFIG.num_checkpoints == 2


def test_checkpoint_sequential_segments():
    ckpt.configure(num_checkpoints=2)
    key = jax.random.PRNGKey(0)
    ws = [jax.random.normal(jax.random.fold_in(key, i), (8, 8)) / 3
          for i in range(5)]
    layers = [lambda h, w=w: jnp.tanh(h @ w) for w in ws]
    x = jax.random.normal(jax.random.fold_in(key, 99), (4, 8))

    def ref(h):
        for layer in layers:
            h = layer(h)
        return jnp.sum(h)

    def seq(h):
        return jnp.sum(ckpt.checkpoint_sequential(layers, h))

    ref_val, ref_grad = jax.value_and_grad(ref)(x)
    val, grad = jax.value_and_grad(seq)(x)
    np.testing.assert_allclose(np.asarray(val), np.asarray(ref_val), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(grad), np.asarray(ref_grad), rtol=1e-4, atol=1e-5)


def test_partition_activations_on_mesh():
    """partition_activations shards saved inputs over the model axis; the
    grads must be identical to the unpartitioned graph."""
    from deepspeed_tpu.parallel import initialize_mesh

    initialize_mesh(data=4, model=2)
    ckpt.configure(partition_activations=True)

    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (16, 16))
    x = jax.random.normal(jax.random.fold_in(key, 1), (8, 16))
    f = _mlp(w)

    @jax.jit
    def g(a):
        return jax.value_and_grad(lambda b: ckpt.checkpoint(f, b))(a)

    val, grad = g(x)
    ref_val, ref_grad = jax.value_and_grad(f)(x)
    np.testing.assert_allclose(np.asarray(val), np.asarray(ref_val), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(grad), np.asarray(ref_grad), rtol=1e-4, atol=1e-5)


def test_partition_helper_is_noop_without_model_axis():
    x = jnp.ones((6, 4))
    out = ckpt.partition(x)
    assert out.shape == x.shape


def test_rng_tracker_fork_and_seed():
    ckpt.model_parallel_manual_seed(1234, mp_rank=0)
    tracker = ckpt.get_rng_tracker()
    with tracker.fork() as k1:
        pass
    with tracker.fork() as k2:
        pass
    assert not np.array_equal(np.asarray(k1), np.asarray(k2))

    ckpt.model_parallel_manual_seed(1234, mp_rank=1)
    with ckpt.get_rng_tracker().fork() as k3:
        pass
    assert not np.array_equal(np.asarray(k1), np.asarray(k3))


def test_fold_in_model_parallel_rank_differs_per_rank():
    from deepspeed_tpu.parallel import initialize_mesh

    mesh = initialize_mesh(data=4, model=2)
    key = jax.random.PRNGKey(7)

    def body(k):
        return ckpt.fold_in_model_parallel_rank(k)[None, :]

    keys = shard_map(
        body, mesh=mesh,
        in_specs=PartitionSpec(),
        out_specs=PartitionSpec("model"))(key)
    ks = np.asarray(jax.device_get(keys))
    assert not np.array_equal(ks[0], ks[1])


def test_cpu_checkpointing_offload_policy():
    """cpu_checkpointing: tagged activations are offloaded (policy path);
    numerics must be unchanged."""
    ckpt.configure(checkpoint_in_cpu=True)
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (16, 16))
    x = jax.random.normal(jax.random.fold_in(key, 1), (8, 16))

    def f(a):
        h = jnp.tanh(a @ w)
        h = ckpt.checkpoint_name(h, ckpt.OFFLOAD_NAME)
        return jnp.sum(h * (a @ w))

    try:
        val, grad = jax.jit(
            jax.value_and_grad(lambda a: ckpt.checkpoint(f, a)))(x)
    except Exception:
        pytest.skip("host offload memory space unsupported on this backend")
    ref_val, ref_grad = jax.value_and_grad(f)(x)
    np.testing.assert_allclose(np.asarray(val), np.asarray(ref_val), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(grad), np.asarray(ref_grad), rtol=1e-4, atol=1e-5)
