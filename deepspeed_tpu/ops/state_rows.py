"""What the kernels that update a stacked state leaf in place share
(``attention/power_retention.py``, ``state_space.py``): a leaf ``(L, rows,
...)`` that a pool hands over whole, of which a call touches the blocks of
one layer and of the rows that run.

* :func:`work_list`: the running batch entries first, so the grid is as long
  as they are and a row that does not run is no step (its blocks come back
  bit for bit);
* :func:`prefetch_operands`: the scalar-prefetch operands ``(layer, batch,
  row, fresh)`` a kernel finds its block by;
* :func:`state_spec`, :func:`by_batch`: the block specs of the leaf and
  of an operand a batch entry, found from those operands;
* :func:`in_hbm`: the leaf pinned to HBM as operand and as result;
* :func:`compiler_params`: two sequential grid axes and the raised VMEM
  limit."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import backend
from .attention.paged_attention import kept_first

__all__ = ["work_list", "prefetch_operands", "state_spec", "by_batch",
           "in_hbm", "compiler_params", "VMEM_LIMIT_BYTES"]

# a retention state's (d/2 + 2, d, d) float32 block in and one out,
# double-buffered, is 17 MB at d = 128: over the v5e's default scoped limit
# (16 MiB) and far under its VMEM (128 MiB)
VMEM_LIMIT_BYTES = 64 * 2 ** 20


def work_list(rows, num_rows: int):
    """``rows`` (B,) int32 names the pool row of each batch entry; an entry
    outside ``[0, num_rows)`` does not run. Returns ``(batch, row, total)``:
    the running entries first, in order, and how many they are."""
    rows = jnp.asarray(rows, jnp.int32)
    keep = (rows >= 0) & (rows < num_rows)
    return kept_first(keep, jnp.arange(rows.shape[0]), rows)


def prefetch_operands(layer, rows, fresh, s):
    """The scalar-prefetch operands ``(layer, batch, row, fresh)`` of a
    call, how many work items they hold, and which batch entries run."""
    rows = jnp.asarray(rows, jnp.int32)
    batch_of, row_of, total = work_list(rows, s.shape[1])
    return ((jnp.asarray(layer, jnp.int32).reshape(1), batch_of, row_of,
             jnp.asarray(fresh, jnp.int32)[batch_of]), total,
            (rows >= 0) & (rows < s.shape[1]))


def state_spec(s, count: int):
    """Block spec of the stacked leaf (L, R, n, ...): ``count`` of the
    third dimension's entries (tiles, heads) of one (layer, row) a step,
    the row from the work list, the block of ``count`` from the grid's
    second axis."""
    return pl.BlockSpec(
        (1, 1, count) + s.shape[3:],
        lambda w, j, layer, batch, row, *_: (layer[0], row[w], j)
        + (0,) * (s.ndim - 3))


def by_batch(block: tuple, stepped: bool):
    """Block spec of an operand (B, ...): the batch entry of the step's
    work item; ``stepped``: its second dimension follows the grid's second
    axis (the step's block of tiles, its head)."""
    rest = (0,) * (len(block) - 2)
    return pl.BlockSpec(
        block, lambda w, j, layer, batch, *_: (
            batch[w], j if stepped else 0) + rest)


def in_hbm(s):
    """The stacked leaf as the kernels' operand and as their result, both
    pinned to HBM. Left to itself XLA may keep a buffer that fits the chip's
    fast memory there across a layer scan (``S(1)`` on the custom call's
    operand), and a Mosaic operand aliased to its result read nothing of it
    there (chip runs, PR 32: a 33 MB leaf read wrong, every test in
    interpret mode right). The constraint is the custom call's own
    (``input_memory_space_colors`` / ``output_memory_colors``); the blocks
    still ride through VMEM as their specs say. Interpret mode has no
    memory spaces, and the constraint has no eager form: on the chip the
    kernels are called under ``jit``. Every program of the engines donates
    the pool or makes the leaf inside it. One form is left to the caller: a
    jitted call that takes such a small leaf as a parameter and does NOT
    donate it has XLA copy the parameter first, and this libtpu's
    memory-space assignment aborts on that copy beside the pinned result
    ("Conflicting pending required assignment", at compile time, on the
    chip and for a described one alike): donate the leaf."""
    shape = jax.ShapeDtypeStruct(s.shape, s.dtype)
    if backend.pallas_interpret():
        return s, shape
    return (pltpu.with_memory_space_constraint(s, pltpu.HBM),
            pltpu.HBM(s.shape, s.dtype))


def compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT_BYTES)
