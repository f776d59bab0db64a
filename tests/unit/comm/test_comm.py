"""Comm facade tests — the analog of reference ``tests/unit/comm/test_dist.py``.

Covers the three planes of ``deepspeed_tpu.comm``:
* host-level (eager) collectives and the ``@timed_op`` accounting,
* in-compiled-code collectives (shard_map over the virtual 8-device mesh),
* the cross-rank consistency assertions (SURVEY §5.2 analog).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.comm import comm as dist
from deepspeed_tpu.parallel import initialize_mesh


# ---------------------------------------------------------------------------
# host-plane collectives (single process: degenerate but exact semantics)
# ---------------------------------------------------------------------------
def test_all_reduce_host_ops():
    x = np.array([1.0, 2.0, 3.0])
    for op, expect in [
        (dist.ReduceOp.SUM, x), (dist.ReduceOp.AVG, x),
        (dist.ReduceOp.MIN, x), (dist.ReduceOp.MAX, x),
        (dist.ReduceOp.PRODUCT, x),
    ]:
        np.testing.assert_allclose(dist.all_reduce_host(x, op=op), expect)


def test_broadcast_and_allgather_host():
    x = np.arange(4, dtype=np.int32)
    np.testing.assert_array_equal(dist.broadcast_host(x, src=0), x)
    gathered = dist.all_gather_host(x)
    assert gathered.shape == (1, 4)  # world of one process
    np.testing.assert_array_equal(gathered[0], x)


def test_barrier_and_ranks():
    dist.barrier(name="test")  # no-op single process
    assert dist.get_rank() == 0
    assert dist.get_local_rank() == 0
    assert dist.get_world_size() == 1  # process count, not device count


def test_init_distributed_single_process():
    dist.init_distributed()
    assert dist.is_initialized()


# ---------------------------------------------------------------------------
# axis-name groups
# ---------------------------------------------------------------------------
def test_group_axes_and_sizes(eight_device_mesh):
    assert dist._axes("data") == ("data",)
    assert dist._axes(("data", "model")) == ("data", "model")
    assert dist._axes_size("data") == 8
    assert dist._axes_size(("data", "model")) == 8
    assert dist.get_world_size("data") == 8


def test_default_group_covers_zero_axes(eight_device_mesh):
    # default group = the ZeRO sharding axes (the reference's world group)
    axes = dist._axes(None)
    assert "data" in axes


# ---------------------------------------------------------------------------
# in-compiled-code collectives over the virtual mesh
# ---------------------------------------------------------------------------
@pytest.fixture
def shmap_mesh():
    return initialize_mesh(data=8)


def _shmap(mesh, fn, *args, in_specs, out_specs):
    from jax import shard_map

    return jax.jit(shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs))(*args)


def test_all_reduce_in_jit(shmap_mesh):
    x = jnp.arange(8, dtype=jnp.float32)
    out = _shmap(shmap_mesh, lambda v: dist.all_reduce(v, group="data"),
                 x, in_specs=(P("data"),), out_specs=P("data"))
    np.testing.assert_allclose(np.asarray(out), np.full(8, x.sum()))


def test_all_reduce_ops_in_jit(shmap_mesh):
    x = jnp.arange(8, dtype=jnp.float32)
    avg = _shmap(shmap_mesh, lambda v: dist.all_reduce(
        v, op=dist.ReduceOp.AVG, group="data"),
        x, in_specs=(P("data"),), out_specs=P("data"))
    np.testing.assert_allclose(np.asarray(avg), np.full(8, x.mean()))
    mx = _shmap(shmap_mesh, lambda v: dist.all_reduce(
        v, op=dist.ReduceOp.MAX, group="data"),
        x, in_specs=(P("data"),), out_specs=P("data"))
    np.testing.assert_allclose(np.asarray(mx), np.full(8, 7.0))


def test_all_gather_into_tensor_in_jit(shmap_mesh):
    x = jnp.arange(8, dtype=jnp.float32)
    out = _shmap(shmap_mesh,
                 lambda v: dist.all_gather_into_tensor(v, group="data"),
                 x, in_specs=(P("data"),), out_specs=P("data"))
    # every shard gathers the full vector; out_specs concatenates the copies
    np.testing.assert_allclose(np.asarray(out),
                               np.tile(np.arange(8, dtype=np.float32), 8))


def test_reduce_scatter_tensor_in_jit(shmap_mesh):
    # replicated ones on each rank → each rank's scattered slice sums to 8
    x = jnp.ones(8, jnp.float32)
    out = _shmap(shmap_mesh,
                 lambda v: dist.reduce_scatter_tensor(v, group="data"),
                 x, in_specs=(P(),), out_specs=P("data"))
    np.testing.assert_allclose(np.asarray(out), np.full(8, 8.0))


def test_all_to_all_single_in_jit(shmap_mesh):
    # rank r holds row r; rank r sends chunk j to rank j and receives chunk r
    # from every rank, concatenated on axis 0 — a distributed transpose
    x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
    out = _shmap(shmap_mesh,
                 lambda v: dist.all_to_all_single(
                     v, group="data", split_axis=1, concat_axis=0),
                 x, in_specs=(P("data"),), out_specs=P("data"))
    expect = np.arange(64, dtype=np.float32).reshape(8, 8).T.reshape(out.shape)
    np.testing.assert_allclose(np.asarray(out), expect)


def test_ppermute_ring_in_jit(shmap_mesh):
    x = jnp.arange(8, dtype=jnp.float32)
    perm = [(i, (i + 1) % 8) for i in range(8)]
    out = _shmap(shmap_mesh, lambda v: dist.ppermute(v, perm, group="data"),
                 x, in_specs=(P("data"),), out_specs=P("data"))
    np.testing.assert_allclose(np.asarray(out), np.roll(np.arange(8), 1))


def test_axis_index_in_jit(shmap_mesh):
    x = jnp.zeros(8, jnp.int32)
    out = _shmap(shmap_mesh,
                 lambda v: v + dist.axis_index(group="data"),
                 x, in_specs=(P("data"),), out_specs=P("data"))
    np.testing.assert_array_equal(np.asarray(out), np.arange(8))


def test_ppermute_rejects_multi_axis(shmap_mesh):
    with pytest.raises(ValueError):
        dist.ppermute(jnp.zeros(8), [(0, 1)], group=("data", "model"))


# ---------------------------------------------------------------------------
# timed_op accounting + traced-op records + log_summary
# ---------------------------------------------------------------------------
def test_timed_op_records_and_summary():
    dist.configure(enabled=True, prof_all=True, verbose=False)
    try:
        dist.all_reduce_host(np.ones(16, np.float32))
        dist.record_traced_op("all_gather_into_tensor", msg_size=1024, n_ranks=8)
        records = dist.comms_logger.comms_dict
        assert "all_reduce_host" in records
        assert "traced/all_gather_into_tensor" in records
        # record = msg-size keyed [count, [latencies], [algbw], [busbw]]
        size_entry = records["all_reduce_host"][16 * 4]
        assert size_entry[0] == 1
        summary = dist.log_summary()  # returns the records dict (via logger)
        assert "all_reduce_host" in summary
    finally:
        dist.configure(enabled=False, prof_all=False)
        dist.comms_logger.comms_dict.clear()


def test_timed_op_disabled_is_transparent():
    dist.configure(enabled=False)
    before = dict(dist.comms_logger.comms_dict)
    dist.all_reduce_host(np.ones(4))
    assert dist.comms_logger.comms_dict == before


# ---------------------------------------------------------------------------
# cross-rank consistency assertions (§5.2)
# ---------------------------------------------------------------------------
def test_stable_hash_deterministic_and_sensitive():
    a = {"input_ids": np.zeros((2, 8), np.int32)}
    b = {"input_ids": np.zeros((2, 8), np.int32)}
    c = {"input_ids": np.zeros((2, 9), np.int32)}
    assert dist.stable_hash(a) == dist.stable_hash(b)
    assert dist.stable_hash(a) != dist.stable_hash(c)
    assert dist.stable_hash({"x": 1, "y": 2}) == dist.stable_hash({"y": 2, "x": 1})


def test_assert_same_across_ranks_single_process():
    dist.assert_same_across_ranks({"anything": 1}, "noop")  # world of 1


def test_engine_consistency_flag_runs(eight_device_mesh):
    import deepspeed_tpu as ds
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel

    cfg = GPT2Config(vocab_size=64, n_positions=16, n_embd=16, n_layer=1,
                     n_head=2, dtype=jnp.float32)
    eng, _, _, _ = ds.initialize(
        model=GPT2LMHeadModel(cfg),
        config={"train_micro_batch_size_per_gpu": 1,
                "check_rank_consistency": True,
                "zero_optimization": {"stage": 0},
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}})
    batch = {"input_ids": np.zeros((eng.train_batch_size(), 16), np.int32)}
    loss = float(eng.train_batch(batch=batch))
    assert np.isfinite(loss)
