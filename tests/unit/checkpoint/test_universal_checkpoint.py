"""Universal checkpoint + zero_to_fp32 + orbax engine — analog of reference
``tests/unit/checkpoint/`` (universal/reshape/latest-tag suites)."""

import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.checkpoint import (
    convert_zero_checkpoint_to_fp32_state_dict,
    ds_to_universal,
    get_fp32_state_dict_from_zero_checkpoint,
    load_universal,
)


def _make_engine(mesh_data=-1, zero_stage=1, fp16=False, offload=False):
    from deepspeed_tpu.parallel import initialize_mesh
    from deepspeed_tpu.parallel import mesh as mesh_mod

    mesh_mod.reset_mesh()
    mesh = initialize_mesh(data=mesh_data)
    from tests.unit.simple_model import SimpleModel

    config = {
        "train_micro_batch_size_per_gpu": 4,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": zero_stage},
        "steps_per_print": 1000,
    }
    if fp16:
        config["fp16"] = {"enabled": True}
    if offload:
        config["zero_optimization"]["offload_optimizer"] = {"device": "cpu"}
    engine, _, _, _ = ds.initialize(model=SimpleModel(hidden_dim=16),
                                    config=config, mesh=mesh)
    return engine


def _batch(engine, seed=0):
    rng = np.random.default_rng(seed)
    return {"x": rng.standard_normal((engine.train_batch_size(), 16),
                                     dtype=np.float32),
            "y": rng.standard_normal((engine.train_batch_size(),),
                                     dtype=np.float32)}


def test_universal_roundtrip_same_topology(tmp_path):
    engine = _make_engine()
    b = _batch(engine)
    for _ in range(3):
        engine.train_batch(batch=b)
    engine.save_checkpoint(str(tmp_path))
    univ = ds_to_universal(str(tmp_path))
    blob = load_universal(univ)
    assert blob["meta"]["global_steps"] == 3
    assert blob["fp32"], "fp32 weights must be present"

    engine2 = _make_engine()
    engine2.train_batch(batch=b)  # build state
    engine2.load_universal_checkpoint(str(tmp_path))
    assert engine2.global_steps == 3
    # training continues from the same weights → same next loss
    l1 = float(engine.train_batch(batch=b))
    l2 = float(engine2.train_batch(batch=b))
    assert np.isclose(l1, l2, rtol=1e-4), (l1, l2)


def test_universal_resize_topology(tmp_path):
    """Save at dp=8, load at dp=4×mp=2 — the elastic re-mesh path."""
    engine = _make_engine(mesh_data=8)
    b = _batch(engine)
    for _ in range(2):
        engine.train_batch(batch=b)
    engine.save_checkpoint(str(tmp_path))
    ds_to_universal(str(tmp_path))

    from deepspeed_tpu.parallel import initialize_mesh
    from deepspeed_tpu.parallel import mesh as mesh_mod

    mesh_mod.reset_mesh()
    mesh = initialize_mesh(data=4, model=2)
    from tests.unit.simple_model import SimpleModel

    engine2, _, _, _ = ds.initialize(
        model=SimpleModel(hidden_dim=16),
        config={"train_micro_batch_size_per_gpu": 8,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
                "zero_optimization": {"stage": 1},
                "steps_per_print": 1000},
        mesh=mesh)
    b2 = {"x": b["x"], "y": b["y"]}
    engine2.train_batch(batch=b2)  # build state at new topology
    engine2.load_universal_checkpoint(str(tmp_path))
    assert engine2.global_steps == 2
    l1 = float(engine.train_batch(batch=b))
    l2 = float(engine2.train_batch(batch=b2))
    assert np.isclose(l1, l2, rtol=1e-3), (l1, l2)


def test_universal_with_fp16_master(tmp_path):
    engine = _make_engine(fp16=True)
    b = _batch(engine)
    for _ in range(2):
        engine.train_batch(batch=b)
    engine.save_checkpoint(str(tmp_path))
    univ = ds_to_universal(str(tmp_path))
    blob = load_universal(univ)
    # fp32 master + both Adam moments present
    assert blob["opt"], "expected optimizer moment trees"
    for tree in blob["fp32"].values():
        break
    engine2 = _make_engine(fp16=True)
    engine2.train_batch(batch=b)
    engine2.load_universal_checkpoint(str(tmp_path))
    l1 = float(engine.train_batch(batch=b))
    l2 = float(engine2.train_batch(batch=b))
    assert np.isclose(l1, l2, rtol=1e-3), (l1, l2)


def test_zero_to_fp32(tmp_path):
    engine = _make_engine(fp16=True)
    b = _batch(engine)
    engine.train_batch(batch=b)
    engine.save_checkpoint(str(tmp_path))
    sd = get_fp32_state_dict_from_zero_checkpoint(str(tmp_path))
    assert all(v.dtype == np.float32 for v in sd.values())
    # dotted param names like linear_0.kernel
    assert any("kernel" in k for k in sd), list(sd)
    out = tmp_path / "consolidated.npz"
    convert_zero_checkpoint_to_fp32_state_dict(str(tmp_path), str(out))
    loaded = np.load(str(out))
    assert set(loaded.files) == set(sd.keys())


def test_config_load_universal_flag(tmp_path):
    engine = _make_engine()
    b = _batch(engine)
    engine.train_batch(batch=b)
    engine.save_checkpoint(str(tmp_path))
    ds_to_universal(str(tmp_path))

    from deepspeed_tpu.parallel import initialize_mesh
    from deepspeed_tpu.parallel import mesh as mesh_mod

    mesh_mod.reset_mesh()
    from tests.unit.simple_model import SimpleModel

    engine2, _, _, _ = ds.initialize(
        model=SimpleModel(hidden_dim=16),
        config={"train_micro_batch_size_per_gpu": 4,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
                "checkpoint": {"load_universal": True},
                "steps_per_print": 1000},
        mesh=initialize_mesh())
    engine2.train_batch(batch=b)
    engine2.load_checkpoint(str(tmp_path))  # routes through universal
    assert engine2.global_steps == 1


def test_orbax_engine_sharded_roundtrip(tmp_path, eight_device_mesh):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from deepspeed_tpu.runtime.checkpoint_engine.orbax_checkpoint_engine import (
        OrbaxCheckpointEngine,
    )

    mesh = eight_device_mesh
    sh = NamedSharding(mesh, PartitionSpec("data"))
    arr = jax.device_put(jnp.arange(64, dtype=jnp.float32).reshape(8, 8), sh)
    tree = {"w": arr, "b": jnp.ones((3,), jnp.float32)}

    eng = OrbaxCheckpointEngine(use_async=True)
    path = str(tmp_path / "ckpt" / "state")
    eng.save({"arrays": tree, "meta": {"step": 7}}, path)
    eng.commit("tag")

    target = {"w": jax.ShapeDtypeStruct((8, 8), jnp.float32, sharding=sh),
              "b": jax.ShapeDtypeStruct((3,), jnp.float32)}
    out = eng.load(path, restore_target=target)
    assert out["meta"]["step"] == 7
    np.testing.assert_array_equal(np.asarray(out["arrays"]["w"]),
                                  np.asarray(arr))
    assert out["arrays"]["w"].sharding.is_equivalent_to(sh, 2)


def test_universal_from_orbax_layout(tmp_path):
    """ds_to_universal over a checkpoint saved through the ORBAX engine
    (the multi-process save layout: orbax_state dir + meta sidecar, no
    pickle files) — regression for the elastic-loop composition where a
    2-proc run's checkpoint must convert offline."""
    from deepspeed_tpu.runtime.checkpoint_engine.orbax_checkpoint_engine import (
        OrbaxCheckpointEngine,
    )

    engine = _make_engine()
    b = _batch(engine)
    for _ in range(2):
        engine.train_batch(batch=b)
    engine.checkpoint_engine = OrbaxCheckpointEngine(use_async=False)
    engine.save_checkpoint(str(tmp_path))
    import os

    tag = "global_step2"
    assert os.path.isdir(os.path.join(str(tmp_path), tag, "orbax_state"))
    assert not os.path.exists(os.path.join(
        str(tmp_path), tag, "mp_rank_00_model_states.meta"))

    univ = ds_to_universal(str(tmp_path))
    blob = load_universal(univ)
    assert blob["meta"]["global_steps"] == 2
    assert blob["fp32"], "fp32 weights missing from orbax conversion"
    assert blob["opt"], "optimizer moments missing from orbax conversion"

    engine2 = _make_engine()
    engine2.train_batch(batch=b)
    engine2.load_universal_checkpoint(str(tmp_path))
    assert engine2.global_steps == 2
    l1 = float(engine.train_batch(batch=b))
    l2 = float(engine2.train_batch(batch=b))
    assert np.isclose(l1, l2, rtol=1e-3), (l1, l2)
