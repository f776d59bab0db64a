"""Engine API tests: forward/backward/step parity, train_batch, fp16 loss
scaling, checkpoint save/load (analog of reference
tests/unit/runtime/test_ds_initialize.py + half_precision + checkpoint)."""

import numpy as np
import pytest

import deepspeed_tpu as ds
from tests.unit.simple_model import (
    SimpleModel,
    base_config,
    random_batch,
    tiny_gpt2,
    token_batch,
)


def _make_engine(stage=0, dtype="fp32", micro=2, gas=1, extra=None):
    model = SimpleModel(hidden_dim=16)
    cfg = base_config(stage=stage, dtype=dtype, micro=micro, gas=gas, extra=extra)
    engine, _, _, _ = ds.initialize(model=model, config=cfg)
    return engine


def test_initialize_returns_tuple():
    model = SimpleModel()
    out = ds.initialize(model=model, config=base_config())
    assert len(out) == 4


def test_train_batch_loss_decreases():
    engine = _make_engine()
    batch = random_batch(16)
    losses = [float(engine.train_batch(batch=batch)) for _ in range(10)]
    assert losses[-1] < losses[0]


def test_forward_backward_step_matches_train_batch():
    """The eager triple must produce the same params as the fused path."""
    import jax

    e1 = _make_engine()
    e2 = _make_engine()
    batch = random_batch(16, seed=3)
    e1.train_batch(batch=batch)

    loss = e2.forward(batch)
    e2.backward(loss)
    e2.step()

    # same per-micro rng derivation isn't guaranteed between paths unless
    # gas=1 and the micro index is 0 — which holds here
    p1 = jax.tree_util.tree_leaves(e1.state["params"])
    p2 = jax.tree_util.tree_leaves(e2.state["params"])
    for a, b in zip(p1, p2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


def test_gradient_accumulation_boundary():
    engine = _make_engine(gas=2)
    batch = random_batch(16, seed=1)
    assert engine.is_gradient_accumulation_boundary() is False
    loss = engine.forward(batch)
    engine.backward(loss)
    engine.step()  # not a boundary: no-op
    assert engine.global_steps == 0
    loss = engine.forward(batch)
    engine.backward(loss)
    engine.step()
    assert engine.global_steps == 1


def test_fp16_dynamic_loss_scale_runs():
    engine = _make_engine(dtype="fp16", extra={
        "fp16": {"enabled": True, "initial_scale_power": 8, "loss_scale_window": 2}})
    batch = random_batch(16)
    for _ in range(4):
        loss = engine.train_batch(batch=batch)
    assert np.isfinite(float(loss))
    assert engine.state["scale"] is not None
    assert float(engine.state["scale"].loss_scale) >= 2 ** 8


def test_fp16_overflow_skips_step():
    """Force an inf gradient via a huge loss-scale and check params hold."""
    import jax

    engine = _make_engine(dtype="fp16", extra={
        "fp16": {"enabled": True, "initial_scale_power": 40, "hysteresis": 1}})
    batch = random_batch(16)
    engine.forward(batch)  # builds lazy state without updating params
    engine._pending = None
    before = jax.device_get(engine.state)
    engine.train_batch(batch=batch)
    after = jax.device_get(engine.state)
    # fp32 master unchanged (step skipped), scale halved
    b = jax.tree_util.tree_leaves(before["master"])
    a = jax.tree_util.tree_leaves(after["master"])
    for x, y in zip(b, a):
        np.testing.assert_array_equal(x, y)
    assert float(after["scale"].loss_scale) < float(before["scale"].loss_scale)


def test_lr_schedule_in_step():
    model = SimpleModel()
    cfg = base_config()
    cfg["scheduler"] = {"type": "WarmupLR",
                        "params": {"warmup_min_lr": 0.0, "warmup_max_lr": 0.01,
                                   "warmup_num_steps": 10, "warmup_type": "linear"}}
    engine, _, _, sched = ds.initialize(model=model, config=cfg)
    batch = random_batch(16)
    engine.train_batch(batch=batch)
    lr1 = engine.get_lr()[0]
    for _ in range(5):
        engine.train_batch(batch=batch)
    lr2 = engine.get_lr()[0]
    assert lr2 > lr1


@pytest.mark.parametrize("stage", [0, 1, 3])
def test_checkpoint_save_load_roundtrip(tmp_path, stage):
    import jax

    engine = _make_engine(stage=stage, dtype="bf16")
    batch = random_batch(16)
    for _ in range(3):
        engine.train_batch(batch=batch)
    engine.save_checkpoint(str(tmp_path), tag="ck")
    ref = jax.device_get(engine.state)

    engine2 = _make_engine(stage=stage, dtype="bf16")
    engine2.train_batch(batch=random_batch(16, seed=9))  # diverge
    engine2.load_checkpoint(str(tmp_path), tag="ck")
    got = jax.device_get(engine2.state)
    for a, b in zip(jax.tree_util.tree_leaves(ref["params"]),
                    jax.tree_util.tree_leaves(got["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert engine2.global_steps == 3
    # training continues identically
    l1 = float(engine.train_batch(batch=batch))
    l2 = float(engine2.train_batch(batch=batch))
    assert abs(l1 - l2) < 1e-5


def test_checkpoint_latest_tag(tmp_path):
    engine = _make_engine()
    engine.train_batch(batch=random_batch(16))
    engine.save_checkpoint(str(tmp_path))
    assert (tmp_path / "latest").read_text() == "global_step1"
    engine.load_checkpoint(str(tmp_path))  # resolves via latest


def test_gpt2_train_and_eval():
    model = tiny_gpt2()
    cfg = base_config(micro=2, gas=1)
    engine, _, _, _ = ds.initialize(model=model, config=cfg)
    batch = token_batch(16, seq=16)
    l0 = float(engine.train_batch(batch=batch))
    for _ in range(5):
        loss = engine.train_batch(batch=batch)
    assert float(loss) < l0


def test_dataloader_path():
    from tests.unit.simple_model import random_dataset

    model = SimpleModel()
    data = random_dataset(256)
    engine, _, loader, _ = ds.initialize(model=model, config=base_config(),
                                         training_data=data)
    assert loader is not None
    it = iter(loader)
    loss = engine.train_batch(data_iter=it)
    assert np.isfinite(float(loss))


# ---------------------------------------------------------------------------
# round 2: eager-path convergence parity vs the fused path
# ---------------------------------------------------------------------------
def _eager_steps(engine, batches):
    """Drive forward/backward/step over the same micro order train_batch
    uses (contiguous reshape: micro i = rows [i*m:(i+1)*m])."""
    losses = []
    gas = engine.gradient_accumulation_steps()
    for batch in batches:
        micro_rows = batch["x"].shape[0] // gas
        acc = 0.0
        for i in range(gas):
            micro = {k: v[i * micro_rows:(i + 1) * micro_rows]
                     for k, v in batch.items()}
            loss = engine.forward(micro)
            engine.backward(loss)
            acc += float(loss)
            engine.step()
        losses.append(acc / gas)
    return losses


@pytest.mark.parametrize("stage", [0, 2, 3])
def test_eager_matches_fused_trajectory(stage):
    """Multi-step, gas=2, per-ZeRO-stage: the eager triple must follow the
    fused train_batch trajectory (params AND losses)."""
    import jax

    e1 = _make_engine(stage=stage, micro=2, gas=2)
    e2 = _make_engine(stage=stage, micro=2, gas=2)
    batches = [random_batch(e1.train_batch_size(), seed=50 + i)
               for i in range(3)]
    fused = [float(e1.train_batch(batch=b)) for b in batches]
    eager = _eager_steps(e2, batches)
    np.testing.assert_allclose(eager, fused, rtol=1e-4, atol=1e-5)
    assert e1.global_steps == e2.global_steps == 3
    for a, b in zip(jax.tree_util.tree_leaves(e1.state["params"]),
                    jax.tree_util.tree_leaves(e2.state["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_eager_matches_fused_fp16_loss_scaling():
    """The dynamic loss-scale state must evolve identically on both paths
    (scale halving on overflow, growth on the window)."""
    import jax

    extra = {"fp16": {"enabled": True, "initial_scale_power": 10,
                      "loss_scale_window": 2}}
    e1 = _make_engine(dtype="fp16", micro=2, gas=2, extra=extra)
    e2 = _make_engine(dtype="fp16", micro=2, gas=2, extra=extra)
    batches = [random_batch(e1.train_batch_size(), seed=80 + i)
               for i in range(4)]
    fused = [float(e1.train_batch(batch=b)) for b in batches]
    eager = _eager_steps(e2, batches)
    np.testing.assert_allclose(eager, fused, rtol=2e-3, atol=2e-3)
    s1 = float(np.asarray(e1.state["scale"].loss_scale))
    s2 = float(np.asarray(e2.state["scale"].loss_scale))
    assert s1 == s2, (s1, s2)
