"""Builds the system under test from a configuration's file: the model by
the dotted path of its constructor in the program, its weights on the device
from the seed inside one jit, in the type they are served or trained in."""

from __future__ import annotations

import importlib
from typing import Any


def resolve(dotted: str) -> Any:
    module, _, attr = dotted.rpartition(".")
    return getattr(importlib.import_module(module), attr)


def _dtype(name: str):
    import jax.numpy as jnp

    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "float16": jnp.float16}[name]


def build_model(spec: dict, overrides: dict = None, rehearsal: bool = False):
    """``spec`` is the configuration file's ``model`` group: ``factory``
    (the flax module), ``config_factory`` with ``config_args`` and
    ``config_kwargs`` (the program's own config object), ``dtype``. A cell
    may override keyword arguments (``remat_policy``); a rehearsal swaps in
    the file's toy sizes."""
    kwargs = dict(spec.get("config_kwargs", {}))
    if rehearsal:
        kwargs.update(spec["rehearsal_kwargs"])
    kwargs.update(overrides or {})
    kwargs["dtype"] = _dtype(spec["dtype"])
    cfg = resolve(spec["config_factory"])(*spec.get("config_args", []),
                                          **kwargs)
    return resolve(spec["factory"])(cfg), cfg


def spread_over(mesh, shape) -> Any:
    """Where an initial weight goes on a mesh of several chips: its largest
    dimension divisible by the device count is split over the mesh, so
    the float32 tree is never whole on one chip. Only where the weights are
    BORN; the engine then places them by its own policy."""
    from jax.sharding import NamedSharding, PartitionSpec

    n = mesh.devices.size
    axes = tuple(a for a, size in zip(mesh.axis_names, mesh.devices.shape)
                 if size > 1)
    axis = axes[0] if len(axes) == 1 else axes
    spec = [None] * len(shape)
    dims = [i for i, d in enumerate(shape) if d % n == 0 and d > 0]
    if dims and n > 1:
        spec[max(dims, key=lambda i: shape[i])] = axis
    return NamedSharding(mesh, PartitionSpec(*spec))


def init_params(model, init_args: tuple, init_kwargs: dict, seed: int,
                cast_to=None, mesh=None):
    """The parameter tree, made on the device in ONE jitted call from the
    seed. ``cast_to`` casts floating leaves inside the same program (so a
    bf16 server never holds the float32 tree). On a mesh of several chips
    every leaf is born split (``spread_over``). The key and ``init_args``
    are the program's arguments, not constants of it: one program serves
    every seed, so a run at a seed the compile cache has not met finds it
    there all the same."""
    import jax
    import jax.numpy as jnp

    def init(key, *args):
        tree = model.init({"params": key, "dropout": key}, *args,
                          **init_kwargs)["params"]
        if cast_to is None:
            return tree
        return jax.tree_util.tree_map(
            lambda x: x.astype(cast_to)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)

    key = jax.random.PRNGKey(seed)
    out_shardings = None
    if mesh is not None and mesh.devices.size > 1:
        shapes = jax.eval_shape(init, key, *init_args)
        out_shardings = jax.tree_util.tree_map(
            lambda s: spread_over(mesh, s.shape), shapes)
    return jax.block_until_ready(
        jax.jit(init, out_shardings=out_shardings)(key, *init_args))
