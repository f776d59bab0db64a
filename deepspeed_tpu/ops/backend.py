"""Which backend the kernels target — the one place that asks.

Every Pallas kernel in this package is written for the TPU (Mosaic). On a
TPU it compiles; anywhere else it runs in Pallas interpret mode, which is
how the CPU tests exercise the same kernel bodies. Kernel *selection*
(``auto`` modes: flash attention, the decode and paged-attention kernels,
block-sparse attention, the int8 GEMM) asks the same question, so that a
kernel is either compiled for the chip or not chosen at all — never
silently interpreted on it. ``chip_smoke.py`` proves the answer on the
device by counting ``tpu_custom_call`` in the compiled train and decode
steps.

Callers use the module attribute (``backend.pallas_interpret()``), not a
``from`` import, so tests can force interpret mode off in one place and
lower each kernel for the TPU from a CPU-only machine.

:func:`shard_kernel` is how a kernel runs on a mesh of several devices.
"""

from __future__ import annotations

import math

import jax


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def pallas_interpret() -> bool:
    """The ``interpret=`` argument of every ``pl.pallas_call`` here."""
    return not on_tpu()


# ---------------------------------------------------------------------------
# kernels on a mesh of several devices
# ---------------------------------------------------------------------------
BATCH = "batch"   # this dim follows the mesh's batch axes (data, expert)
HEADS = "heads"   # this dim follows the model (tensor-parallel) axis


def shard_kernel(kernel, out_dims, **operands):
    """``kernel(**arrays)``, run per shard on a mesh of several devices.

    GSPMD cannot partition a Mosaic kernel: on more than one TPU a bare
    ``pallas_call`` under ``jit`` fails to compile ("Mosaic kernels cannot
    be automatically partitioned. Please wrap the call in a shard_map" —
    first four-chip run, PR 21; interpret mode on forced host devices
    never showed it, because there the kernel is ordinary HLO). The
    attention kernels are independent per sequence and per head, so each
    device runs the kernel on the sequences and heads it holds.

    ``operands`` maps each keyword of ``kernel`` to ``(array, dims)``;
    ``dims`` labels every dimension :data:`BATCH`, :data:`HEADS` or
    ``None`` (whole on every device), ``out_dims`` likewise for the
    result. An operand that is ``None`` is passed through as ``None``. A
    label whose dimensions do not all divide by the mesh axes it follows
    falls back to ``None``: still correct, every device then computes
    that dimension in full. With no mesh, one device, or inside an
    enclosing ``shard_map`` (ring/Ulysses attention call the kernels per
    shard themselves) the kernel is called directly."""
    from jax.sharding import PartitionSpec

    from ..parallel import mesh as mesh_mod

    present = {n: x for n, (x, _) in operands.items() if x is not None}
    absent = {n: None for n in operands if n not in present}
    mesh = mesh_mod.get_mesh() if mesh_mod.has_mesh() else None
    if mesh is None or mesh.devices.size == 1 or \
            jax.sharding.get_abstract_mesh().manual_axes:
        return kernel(**present, **absent)

    def axes_for(label, axes):
        axes = tuple(a for a in axes if mesh.shape[a] > 1)
        ways = math.prod(mesh.shape[a] for a in axes)
        divides = all(present[n].shape[i] % ways == 0
                      for n in present
                      for i, d in enumerate(operands[n][1]) if d == label)
        return axes if axes and divides else None

    follows = {BATCH: axes_for(BATCH, mesh_mod.batch_axes()),
               HEADS: axes_for(HEADS, (mesh_mod.MODEL_AXIS,)),
               None: None}

    def spec(dims):
        return PartitionSpec(*(follows[d] for d in dims))

    def per_shard(*arrays):
        return kernel(**dict(zip(present, arrays)), **absent)

    return jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=tuple(spec(operands[n][1]) for n in present),
        out_specs=spec(out_dims), check_vma=False,
    )(*present.values())
