"""ZeRO stages as GSPMD sharding policies.

This replaces the reference's imperative ZeRO machinery — stage-1/2 flat
partitions + IPG bucketing (``runtime/zero/stage_1_and_2.py:90,799,900``) and
stage-3 param sharding with hook-driven gather/release
(``runtime/zero/stage3.py:65``, ``partition_parameters.py:616``,
``partitioned_param_coordinator.py:55``) — with *declarative* sharding specs
consumed by ``jax.jit``:

* **stage 1** (optimizer-state partitioning): fp32 master params + moments are
  sharded over the ZeRO axes; XLA emits one reduce-scatter of the grads into
  the shard, a local update, and an all-gather of updated compute params —
  exactly the reference's ``step()``-then-allgather (stage_1_and_2.py:1642)
  but compiler-scheduled and fused into the step.
* **stage 2** (+gradient partitioning): grads get an explicit sharding
  constraint so accumulated grads live reduce-scattered (the analog of IPG
  bucketing + ``average_tensor`` rank-sliced reduction, stage_1_and_2.py:900).
  Inside a single fused step this only changes peak memory under gradient
  accumulation — which is precisely its role in the reference.
* **stage 3** (+parameter partitioning): compute params are *persistently*
  sharded over the ZeRO axes and gathered where they are used, a layer at a
  time, then freed (the gather/release hook pair,
  parameter_offload.py:370/374), with prefetch overlap handled by XLA's
  scheduler rather than a recorded trace. Small params stay replicated below
  ``stage3_param_persistence_threshold`` (stage3 persistent-param logic,
  parameter_offload.py:339).

  The gather has to be SAID at the use site. A product ``x @ W`` inside the
  layer scan meets an activation split over ``data`` (its batch) and a
  weight split over the SAME axis (a feature dimension), and nothing in the
  shardings of the step's arguments says which operand gives way: for
  GPT-2 XL's two MLP matmuls the partitioner resharded the 105 MB
  activation (a synchronous all-to-all, which depends on the activation and
  cannot be prefetched) rather than gather a 20 MB weight. So a model's scan
  body hands its layer's slice to ``gather_at_use_site``, which constrains
  every ZeRO-sharded leaf to its *use-site* spec: the ZeRO axes taken out,
  the tensor-parallel axes kept. Inside ``remat`` the backward gathers
  again and no gathered weight is kept. The constraint's transpose asks for
  the weight's cotangent in the same layout; the compiler fuses that
  all-reduce with the slice ``constrain_grads`` takes of it into a
  reduce-scatter. (A ``custom_vjp`` that constrained the cotangent to the
  gradient's spec instead compiled to rings of collective-permutes around
  quartered dW matmuls: more bytes and 5 % slower on the chip, PERF.md §6
  PR 39.)

Tensor-parallel (model-axis) specs compose: the ZeRO axes shard a dimension
not already taken by TP.
"""

from __future__ import annotations

import functools
import math
import re
from typing import Any, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ...parallel.mesh import ZERO_AXES
from .config import DeepSpeedZeroConfig, ZeroStageEnum


def _zero_world(mesh) -> int:
    dims = dict(zip(mesh.axis_names, mesh.devices.shape))
    return int(np.prod([dims.get(a, 1) for a in ZERO_AXES]))


def _used_axes(spec: Optional[PartitionSpec]) -> set:
    used = set()
    if spec is None:
        return used
    for entry in spec:
        if entry is None:
            continue
        if isinstance(entry, (tuple, list)):
            used.update(entry)
        else:
            used.add(entry)
    return used


def _spec_dim(spec: Optional[PartitionSpec], ndim: int, i: int):
    if spec is None or i >= len(spec):
        return None
    return spec[i]


def zero_shard_spec(shape: Sequence[int],
                    mesh,
                    stage_applies: bool,
                    tp_spec: Optional[PartitionSpec] = None,
                    persistence_threshold: int = 0) -> PartitionSpec:
    """Compose a ZeRO-sharding PartitionSpec for one tensor.

    Picks the largest dimension divisible by the ZeRO world size that TP has
    not claimed and shards it over ``("data", "expert", "seq")``. Tensors at
    or below ``persistence_threshold`` elements (or with no divisible dim)
    stay at their TP spec — the analog of ZeRO-3 persistent small params.
    """
    ndim = len(shape)
    base = list(tp_spec) if tp_spec is not None else []
    base += [None] * (ndim - len(base))

    if not stage_applies:
        return PartitionSpec(*base)

    size = math.prod(shape) if shape else 1
    if persistence_threshold and size <= persistence_threshold:
        return PartitionSpec(*base)

    zero_world = _zero_world(mesh)
    if zero_world == 1:
        return PartitionSpec(*base)

    taken = _used_axes(tp_spec)
    zero_axes = tuple(a for a in ZERO_AXES if a not in taken)
    if not zero_axes:
        return PartitionSpec(*base)
    dims = dict(zip(mesh.axis_names, mesh.devices.shape))
    shard_world = int(np.prod([dims.get(a, 1) for a in zero_axes]))
    if shard_world == 1:
        return PartitionSpec(*base)

    # largest free dim divisible by the shard world
    candidates = [i for i in range(ndim) if base[i] is None and shape[i] % shard_world == 0
                  and shape[i] > 0]
    if not candidates:
        return PartitionSpec(*base)
    best = max(candidates, key=lambda i: shape[i])
    base[best] = zero_axes if len(zero_axes) > 1 else zero_axes[0]
    return PartitionSpec(*base)


class ShardingRules:
    """Regex path → PartitionSpec rules for tensor-parallel params.

    The TPU-native analog of AutoTP's layer classification
    (``module_inject/auto_tp.py:13``): instead of swapping nn.Linear for
    LinearLayer/LinearAllreduce modules, a rule maps a parameter path to the
    mesh axes each dimension shards over.
    """

    def __init__(self, rules: Optional[Sequence[Tuple[str, Sequence]]] = None):
        self.raw_rules = list(rules or [])
        self.rules = [(re.compile(pat), PartitionSpec(*spec)) for pat, spec in self.raw_rules]

    def spec_for(self, path: str) -> Optional[PartitionSpec]:
        for pat, spec in self.rules:
            if pat.search(path):
                return spec
        return None


def _path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


class ZeroShardingPolicy:
    """Maps every parameter / optimizer-state leaf to a NamedSharding.

    stage 0: params+state replicated (grads all-reduced by GSPMD)
    stage 1: master params + optimizer moments sharded
    stage 2: + gradient accumulator sharded
    stage 3: + compute params sharded
    """

    def __init__(self, zero_config: DeepSpeedZeroConfig, mesh,
                 sharding_rules: Optional[ShardingRules] = None):
        self.config = zero_config
        self.mesh = mesh
        self.rules = sharding_rules or ShardingRules()
        self.stage = int(zero_config.stage)
        # what count_use_site_gathers found as the state was placed
        self.use_site_gathers = 0
        self.use_site_gather_bytes = 0

    # --- per-leaf specs ---------------------------------------------------
    def tp_spec(self, path: str) -> Optional[PartitionSpec]:
        return self.rules.spec_for(path)

    def param_spec(self, path: str, shape) -> PartitionSpec:
        return zero_shard_spec(
            shape, self.mesh,
            stage_applies=self.stage >= ZeroStageEnum.weights,
            tp_spec=self.tp_spec(path),
            persistence_threshold=self.config.stage3_param_persistence_threshold,
        )

    def master_spec(self, path: str, shape) -> PartitionSpec:
        return zero_shard_spec(
            shape, self.mesh,
            stage_applies=self.stage >= ZeroStageEnum.optimizer_states,
            tp_spec=self.tp_spec(path),
            # master shards regardless of size when stage>=1 (flat-partition
            # analog); persistence threshold only applies to compute params
            persistence_threshold=0,
        )

    def grad_spec(self, path: str, shape) -> PartitionSpec:
        if self.stage >= ZeroStageEnum.gradients:
            return self.master_spec(path, shape)
        return self.use_site_spec(path, shape)

    # --- stage 3: a layer's weights gathered where they are used ----------
    def use_site_spec(self, path: str, shape) -> PartitionSpec:
        """The leaf's spec where it is computed with: the ZeRO axes taken out
        of ``param_spec``, the tensor-parallel axes kept."""
        return zero_shard_spec(shape, self.mesh, stage_applies=False,
                               tp_spec=self.tp_spec(path))

    def _layer_use_spec(self, path: str, stacked_shape):
        """The use-site spec of ONE layer's slice of a stacked leaf, or None
        where the slice is not ZeRO-sharded (below stage 3, a ZeRO world of
        1, a leaf under the persistence threshold, a leaf split over its
        layer dimension): such a leaf is left alone."""
        use = self.use_site_spec(path, stacked_shape)[1:]
        if self.param_spec(path, stacked_shape)[1:] == use:
            return None
        return PartitionSpec(*use)

    @property
    def gathers_at_use_site(self) -> bool:
        return self.stage >= ZeroStageEnum.weights and \
            _zero_world(self.mesh) > 1

    def gather_at_use_site(self, layer_params, path: str, layers: int):
        """Called by a model's scan body, inside its ``remat``, on one
        layer's slice of the stacked parameters at ``path`` (``layers`` is
        the scan's length, the stacked leaves' leading dimension): each
        ZeRO-sharded leaf is constrained to its use-site spec. Where nothing
        is ZeRO-sharded the argument comes back as it is and nothing is
        traced."""
        if not self.gathers_at_use_site:
            return layer_params

        def gather(leaf_path, x):
            use = self._layer_use_spec(f"{path}/{_path_str(leaf_path)}",
                                       (layers,) + np.shape(x))
            if use is None:
                return x
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(self.mesh, use))

        return jax.tree_util.tree_map_with_path(gather, layer_params)

    def count_use_site_gathers(self, params, stacked_paths) -> None:
        """As the state is placed: the leaves under ``stacked_paths`` (what
        the model says its scan body hands to ``gather_at_use_site``) that
        will be gathered, and the bytes one pass over the layers gathers
        (every layer's slice whole, once)."""
        self.use_site_gathers = self.use_site_gather_bytes = 0
        for stacked in stacked_paths:
            subtree = functools.reduce(lambda t, k: t[k], stacked.split("/"),
                                       params)
            for leaf_path, x in jax.tree_util.tree_flatten_with_path(
                    subtree)[0]:
                if self._layer_use_spec(f"{stacked}/{_path_str(leaf_path)}",
                                        np.shape(x)) is not None:
                    self.use_site_gathers += 1
                    self.use_site_gather_bytes += int(x.nbytes)

    # --- pytree-level shardings ------------------------------------------
    def _tree_shardings(self, tree, spec_fn):
        def leaf_sharding(path, leaf):
            spec = spec_fn(_path_str(path), np.shape(leaf))
            return NamedSharding(self.mesh, spec)

        return jax.tree_util.tree_map_with_path(leaf_sharding, tree)

    def param_shardings(self, params):
        return self._tree_shardings(params, self.param_spec)

    def master_shardings(self, params):
        return self._tree_shardings(params, self.master_spec)

    def grad_shardings(self, params):
        return self._tree_shardings(params, self.grad_spec)

    def opt_state_shardings(self, opt_state, params):
        """Optimizer moments follow the master-param sharding. ``opt_state``
        is any pytree whose array leaves are shaped like some param; leaves
        are matched to params by shape equality within the aligned subtree."""
        param_shardings = self.master_shardings(params)

        def match(path, leaf):
            # opt_state trees from OptimizerDef.init are built by tree_map
            # over params, so each state field subtree is congruent to params.
            return NamedSharding(self.mesh,
                                 self.master_spec(_path_str(path), np.shape(leaf)))

        del param_shardings
        return jax.tree_util.tree_map_with_path(match, opt_state)

    def describe(self) -> str:
        return (f"ZeroShardingPolicy(stage={self.stage}, "
                f"zero_world={_zero_world(self.mesh)}, "
                f"use_site_gathers={self.use_site_gathers}, "
                f"use_site_gather_bytes={self.use_site_gather_bytes})")
