"""Genuinely multi-process distributed tests.

Each test spawns N REAL localhost processes through ``common.run_distributed``
that rendezvous via ``init_distributed`` → ``jax.distributed.initialize``
(CPU/Gloo), then exercise collective + engine + checkpoint paths across the
process boundary. These fail if the rendezvous, the device federation, or
cross-process data movement breaks — the plane the virtual-mesh tests
cannot see (reference pattern: tests/unit/common.py:90 DistributedExec).
"""

import os
import sys
import tempfile

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from common import run_distributed  # noqa: E402

pytestmark = pytest.mark.filterwarnings("ignore")


# ---------------------------------------------------------------------------
# workers (module-level: imported by file path inside each spawned process)
# ---------------------------------------------------------------------------
def _collectives_worker(rank, world):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import deepspeed_tpu.comm.comm as dist

    assert jax.process_count() == world, jax.process_count()
    assert jax.device_count() == world  # one CPU device federated per proc

    # host-level collective plane
    dist.assert_same_across_ranks({"probe": 42}, "probe")

    # in-jit collective over the federated global mesh
    mesh = Mesh(np.asarray(jax.devices()), ("data",))
    arr = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("data")),
        np.full((2,), rank + 1.0, np.float32))
    total = float(jax.jit(lambda a: a.sum())(arr))
    expect = 2.0 * sum(range(1, world + 1))
    assert total == expect, (total, expect)

    # cross-rank divergence must be CAUGHT (the race/sanity plane)
    try:
        dist.assert_same_across_ranks({"divergent": rank}, "divergent")
    except RuntimeError:
        pass
    else:
        raise AssertionError("divergent value not detected across ranks")


def _engine_worker(rank, world):
    import jax
    import numpy as np

    import deepspeed_tpu as ds
    from deepspeed_tpu.models.lm_config import TransformerConfig
    from deepspeed_tpu.models.transformer_lm import TransformerLM

    model = TransformerLM(TransformerConfig(
        vocab_size=64, n_embd=32, n_layer=2, n_head=4, max_seq_len=32))
    engine, _, _, _ = ds.initialize(
        model=model,
        config={"train_micro_batch_size_per_gpu": 2,
                "gradient_accumulation_steps": 2,
                "zero_optimization": {"stage": 1},
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "bf16": {"enabled": True},
                "check_rank_consistency": True,
                "steps_per_print": 10 ** 9})
    assert engine.dp_world_size == world
    rng = np.random.default_rng(0)  # same data every rank (SPMD contract)
    losses = []
    for _ in range(4):
        batch = {"input_ids": rng.integers(
            0, 64, (2 * world, 32)).astype(np.int32)}
        losses.append(float(engine.train_batch(batch=batch)))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses

    # the loss is a global (replicated) value — every process must agree
    from deepspeed_tpu.comm import comm as dist
    dist.assert_same_across_ranks({"final_loss": round(losses[-1], 5)},
                                  "final loss")


def _checkpoint_worker(rank, world, ckpt_dir):
    import numpy as np

    import deepspeed_tpu as ds
    from deepspeed_tpu.models.lm_config import TransformerConfig
    from deepspeed_tpu.models.transformer_lm import TransformerLM

    config = {"train_micro_batch_size_per_gpu": 2,
              "gradient_accumulation_steps": 1,
              "zero_optimization": {"stage": 1},
              "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
              "bf16": {"enabled": True},
              "steps_per_print": 10 ** 9}

    def build():
        model = TransformerLM(TransformerConfig(
            vocab_size=64, n_embd=32, n_layer=2, n_head=4, max_seq_len=32))
        engine, _, _, _ = ds.initialize(model=model, config=dict(config))
        return engine

    rng = np.random.default_rng(1)
    batches = [{"input_ids": rng.integers(
        0, 64, (2 * world, 32)).astype(np.int32)} for _ in range(4)]

    engine = build()
    for b in batches[:2]:
        engine.train_batch(batch=b)
    engine.save_checkpoint(ckpt_dir, tag="mp")
    expected = [float(engine.train_batch(batch=b)) for b in batches[2:]]

    from deepspeed_tpu.parallel import mesh as mesh_mod
    mesh_mod.reset_mesh()
    resumed = build()
    resumed.train_batch(batch=batches[0])  # builds state (then overwritten)
    resumed.load_checkpoint(ckpt_dir, tag="mp")
    actual = [float(resumed.train_batch(batch=b)) for b in batches[2:]]
    np.testing.assert_allclose(actual, expected, rtol=1e-5)

    from deepspeed_tpu.comm import comm as dist
    dist.assert_same_across_ranks(
        {"resumed": [round(a, 5) for a in actual]}, "resumed losses")


# ---------------------------------------------------------------------------
# tests
# ---------------------------------------------------------------------------
def test_multiprocess_collectives(light_compiles):
    run_distributed(_collectives_worker, world_size=2,
                    env={"XLA_FLAGS": light_compiles})


def test_multiprocess_engine_train(light_compiles):
    run_distributed(_engine_worker, world_size=2,
                    env={"XLA_FLAGS": light_compiles})


def test_multiprocess_checkpoint_resume(light_compiles):
    with tempfile.TemporaryDirectory() as d:
        run_distributed(_checkpoint_worker, world_size=2, payload=d,
                        env={"XLA_FLAGS": light_compiles})


def _onebit_wire_worker(rank, world):
    """1-bit Adam with the compressed collective across REAL process
    boundaries: the int8 exchange must rendezvous and training must keep
    improving through the freeze boundary."""
    import numpy as np

    import deepspeed_tpu as ds
    from deepspeed_tpu.models.lm_config import TransformerConfig
    from deepspeed_tpu.models.transformer_lm import TransformerLM

    model = TransformerLM(TransformerConfig(
        vocab_size=64, n_embd=32, n_layer=2, n_head=4, max_seq_len=32))
    engine, _, _, _ = ds.initialize(
        model=model,
        config={"train_micro_batch_size_per_gpu": 2,
                "gradient_accumulation_steps": 2,
                "zero_optimization": {"stage": 0},
                "optimizer": {"type": "OneBitAdam",
                              "params": {"lr": 1e-3, "freeze_step": 2,
                                         "comm_backend_name": "compressed"}},
                "bf16": {"enabled": True},
                "steps_per_print": 10 ** 9})
    rng = np.random.default_rng(0)  # same data every rank (SPMD contract)
    batch = {"input_ids": rng.integers(0, 64, (2 * world * 2, 32)
                                       ).astype(np.int32)}
    losses = [float(engine.train_batch(batch=batch)) for _ in range(5)]
    assert all(np.isfinite(losses)), losses
    # steps 3-5 run the compressed exchange (freeze_step=2); memorizing a
    # fixed batch must keep improving DURING the compressed phase — not
    # just end-vs-start, which the uncompressed warmup steps alone satisfy
    assert losses[-1] < losses[1], losses

    from deepspeed_tpu.comm import comm as dist
    dist.assert_same_across_ranks(
        {"wire_losses": [round(l, 5) for l in losses]}, "onebit wire losses")


def test_multiprocess_onebit_compressed_wire(light_compiles):
    run_distributed(_onebit_wire_worker, world_size=2,
                    env={"XLA_FLAGS": light_compiles})


def _param_offload_worker(rank, world):
    """offload_param streaming across REAL process boundaries:
    per-layer grads reduce across processes via their replicated
    out-sharding over the global mesh; every process's host Adam must stay
    in lockstep (identical losses AND identical streamed params)."""
    import numpy as np

    import deepspeed_tpu as ds
    from deepspeed_tpu.models.lm_config import TransformerConfig
    from deepspeed_tpu.models.transformer_lm import TransformerLM

    model = TransformerLM(TransformerConfig(
        vocab_size=64, n_embd=32, n_layer=2, n_head=4, max_seq_len=32))
    engine, _, _, _ = ds.initialize(
        model=model,
        config={"train_micro_batch_size_per_gpu": 2,
                "gradient_accumulation_steps": 2,
                "zero_optimization": {"offload_param": {"device": "cpu"}},
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "steps_per_print": 10 ** 9})
    assert engine.dp_world_size == world
    rng = np.random.default_rng(0)  # same data every rank (SPMD contract)
    batch = {"input_ids": rng.integers(
        0, 64, (engine.train_batch_size(), 32)).astype(np.int32)}
    losses = [float(engine.train_batch(batch=batch)) for _ in range(4)]
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses

    from deepspeed_tpu.comm import comm as dist
    dist.assert_same_across_ranks(
        {"po_losses": [round(l, 5) for l in losses]}, "offload losses")
    # the streamed param store itself must agree across processes (the
    # host Adam runs per-process on the reduced grads)
    import jax
    leaves = jax.tree_util.tree_leaves(
        engine._param_offload.store.stacked)
    digest = float(sum(float(np.abs(np.asarray(l, np.float32)).sum())
                       for l in leaves))
    dist.assert_same_across_ranks({"param_digest": round(digest, 4)},
                                  "streamed param digest")


def test_multiprocess_param_offload(light_compiles):
    run_distributed(_param_offload_worker, world_size=2,
                    env={"XLA_FLAGS": light_compiles})
