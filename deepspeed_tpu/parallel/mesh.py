"""Device-mesh construction and the global "process group" registry.

This is the TPU-native replacement for the reference's process-group machinery
(``deepspeed/utils/groups.py:46 initialize``, expert-group creation at
groups.py:108/202, world-group clone at :304, and ``PipelineParallelGrid`` at
``deepspeed/runtime/pipe/topology.py:251``). Instead of NCCL communicators,
every parallel axis is a named axis of one global ``jax.sharding.Mesh``;
"creating a group" is picking an axis (or tuple of axes) name.

Axis layout (major → minor): ``pipe, data, expert, seq, model``.

  - ``data``    — ZeRO/data parallelism. Non-expert parameters/grads/optimizer
                  state shard over ("data", "expert", "seq") combined (expert
                  and seq are size-1 unless enabled, so this degenerates to
                  pure DP).
  - ``expert``  — expert parallelism: a factor of the DP world carved out for
                  MoE all-to-all, mirroring _get_expert_parallel_ranks
                  (groups.py:156) where EP groups are sub-groups of DP.
  - ``seq``     — sequence/context parallelism (ring attention / Ulysses) —
                  beyond-parity axis, size 1 by default.
  - ``model``   — tensor (Megatron-style) model parallelism, innermost so its
                  collectives ride adjacent ICI links.
  - ``pipe``    — pipeline stages, outermost (cross-slice/DCN friendly).
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import os
from typing import Optional, Sequence, Tuple

import numpy as np

from ..utils.logging import logger

PIPE_AXIS = "pipe"
DATA_AXIS = "data"
DATA_OUTER_AXIS = "data_outer"  # MiCS replica groups (hierarchical ZeRO)
EXPERT_AXIS = "expert"
SEQ_AXIS = "seq"
MODEL_AXIS = "model"

MESH_AXES = (PIPE_AXIS, DATA_AXIS, EXPERT_AXIS, SEQ_AXIS, MODEL_AXIS)
MICS_MESH_AXES = (PIPE_AXIS, DATA_OUTER_AXIS, DATA_AXIS, EXPERT_AXIS,
                  SEQ_AXIS, MODEL_AXIS)

# Axes over which ZeRO (sharded-DP) state is partitioned. `expert` and `seq`
# multiply into the ZeRO shard world when enabled: params/optimizer state may
# shard over `seq` too (grads are psummed over it by GSPMD since the sp group
# works on chunks of the SAME samples — ZeRO+Ulysses composition).
ZERO_AXES = (DATA_AXIS, EXPERT_AXIS, SEQ_AXIS)
# Axes over which the global batch (sample dim) is split. `seq` is NOT a
# batch axis: it shards the SEQUENCE dim of each sample (ring/Ulysses
# attention, ops/attention/sequence_parallel.py).
BATCH_AXES = (DATA_AXIS, EXPERT_AXIS)


def batch_axes() -> Tuple[str, ...]:
    """Batch (sample-dim) axes of the CURRENT mesh: includes the MiCS
    replica axis when present."""
    mesh = get_mesh() if has_mesh() else None
    if mesh is not None and DATA_OUTER_AXIS in mesh.axis_names:
        return (DATA_OUTER_AXIS,) + BATCH_AXES
    return BATCH_AXES


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Degrees of parallelism; -1 for data means "fill remaining devices"."""

    data: int = -1
    model: int = 1
    pipe: int = 1
    expert: int = 1
    seq: int = 1

    def resolve(self, n_devices: int) -> "MeshConfig":
        fixed = self.model * self.pipe * self.expert * self.seq
        if n_devices % fixed != 0:
            raise ValueError(
                f"device count {n_devices} not divisible by model×pipe×expert×seq = {fixed}")
        data = self.data
        if data == -1:
            data = n_devices // fixed
        if data * fixed != n_devices:
            raise ValueError(
                f"mesh {data}×{fixed} (dp×rest) != device count {n_devices}")
        return MeshConfig(data=data, model=self.model, pipe=self.pipe, expert=self.expert,
                          seq=self.seq)

    @property
    def dims(self) -> Tuple[int, ...]:
        return (self.pipe, self.data, self.expert, self.seq, self.model)


def build_mesh(config: Optional[MeshConfig] = None,
               devices: Optional[Sequence] = None,
               *,
               data: int = -1,
               model: int = 1,
               pipe: int = 1,
               expert: int = 1,
               seq: int = 1,
               mics_shard_size: int = 0):
    """Build the global ``jax.sharding.Mesh``.

    Uses ``jax.experimental.mesh_utils.create_device_mesh`` when possible so
    the logical axes map onto the physical ICI torus well.
    """
    import jax
    from jax.sharding import Mesh

    if config is None:
        config = MeshConfig(data=data, model=model, pipe=pipe, expert=expert, seq=seq)
    if devices is None:
        devices = jax.devices()
    config = config.resolve(len(devices))

    mics_shard_size = int(mics_shard_size or 0)
    dims = config.dims
    axes = MESH_AXES
    if mics_shard_size > config.data > 0:
        raise ValueError(
            f"mics_shard_size {mics_shard_size} exceeds the data-parallel "
            f"degree {config.data}")
    if mics_shard_size and 0 < mics_shard_size < config.data:
        # MiCS: factor data into (replica groups × shard group); ZeRO state
        # shards only over the inner group, replicating across groups —
        # hierarchical allgathers stay inside a group's ICI neighborhood
        if config.data % mics_shard_size != 0:
            raise ValueError(
                f"mics_shard_size {mics_shard_size} must divide data "
                f"parallel degree {config.data}")
        dims = (config.pipe, config.data // mics_shard_size,
                mics_shard_size, config.expert, config.seq, config.model)
        axes = MICS_MESH_AXES
    devices = list(devices)
    if devices[0].platform == "cpu":
        # forced host devices have no torus to respect
        device_array = np.asarray(devices).reshape(dims)
    else:
        # on an accelerator a mesh that cannot be laid onto the
        # interconnect is an error, never a silent plain reshape
        from jax.experimental import mesh_utils

        device_array = mesh_utils.create_device_mesh(dims, devices=devices)
    return Mesh(device_array, axes)


class _GroupsState:
    """Global registry, the analog of the reference's module-level group dict
    in ``deepspeed/utils/groups.py``."""

    def __init__(self):
        self.mesh = None
        self.mesh_config: Optional[MeshConfig] = None
        self.topology: Optional["ProcessTopology"] = None
        # the ZeroShardingPolicy of the engine whose program is being traced
        self.zero_policy = None


_state = _GroupsState()


def initialize_mesh(config: Optional[MeshConfig] = None, devices=None, **kwargs):
    """Create and install the global mesh (≅ ``groups.initialize``,
    reference utils/groups.py:46)."""
    mesh = build_mesh(config, devices, **kwargs)
    set_mesh(mesh)
    logger.info(f"initialized global mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}")
    return mesh


def set_mesh(mesh) -> None:
    from .topology import ProcessTopology

    _state.mesh = mesh
    dims = dict(zip(mesh.axis_names, mesh.devices.shape))
    _state.mesh_config = MeshConfig(
        data=dims.get(DATA_AXIS, 1) * dims.get(DATA_OUTER_AXIS, 1),
        model=dims.get(MODEL_AXIS, 1),
        pipe=dims.get(PIPE_AXIS, 1),
        expert=dims.get(EXPERT_AXIS, 1),
        seq=dims.get(SEQ_AXIS, 1),
    )
    _state.topology = ProcessTopology(list(mesh.axis_names), list(mesh.devices.shape))


def get_mesh():
    if _state.mesh is None:
        initialize_mesh()
    return _state.mesh


def has_mesh() -> bool:
    return _state.mesh is not None


def get_topology():
    get_mesh()
    return _state.topology


def reset_mesh() -> None:
    _state.mesh = None
    _state.mesh_config = None
    _state.topology = None
    _state.zero_policy = None


@contextlib.contextmanager
def zero_policy_scope(policy):
    """The engine traces its model under this, so that a model's layer scan
    can ask the policy to gather a layer's weights where they are used
    (``ZeroShardingPolicy.gather_at_use_site``). It is set only while a
    program traces: a model applied with no engine finds none."""
    previous, _state.zero_policy = _state.zero_policy, policy
    try:
        yield
    finally:
        _state.zero_policy = previous


def get_zero_policy():
    """The policy of the engine that is tracing, or None."""
    return _state.zero_policy


def _axis_size(axis: str) -> int:
    mesh = get_mesh()
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(axis, 1)


# --- world-size accessors, mirroring deepspeed/utils/groups.py getters ---
def get_data_parallel_world_size() -> int:
    """Number of model replicas in the batch sense — the multiplier in
    ``train_batch = micro_batch × gas × dp_world``. Excludes ``seq``: a
    sequence-parallel group cooperates on the *same* samples."""
    return math.prod(_axis_size(a) for a in batch_axes())


def get_model_parallel_world_size() -> int:
    return _axis_size(MODEL_AXIS)


def get_pipe_parallel_world_size() -> int:
    return _axis_size(PIPE_AXIS)


def get_expert_parallel_world_size() -> int:
    return _axis_size(EXPERT_AXIS)


def get_sequence_parallel_world_size() -> int:
    return _axis_size(SEQ_AXIS)


def get_world_size() -> int:
    mesh = get_mesh()
    return mesh.devices.size
