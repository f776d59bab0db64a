"""Int8-weight matmul with in-kernel dequantization (Pallas TPU kernel).

TPU-native serving analog of the reference's int8 inference tier — the
fused dequant-GEMM path (``csrc/quantization/quantize.cu`` +
``csrc/transformer/inference/csrc/dequantize.cu``), where weights live in
HBM as int8 + per-channel scales and are expanded to compute precision
inside the GEMM rather than materialized.

Decode-time matmuls are HBM-bandwidth bound: activations are a few rows,
weights are the traffic. Keeping kernels int8 at rest halves the bytes the
matmul streams per step versus bf16 — the int8 tile is converted to bf16
on the VMEM-resident copy right before the MXU contraction, so
full-precision weights never touch HBM. An XLA-only formulation can fuse
the convert too, but hoists the dequant out of ``lax.scan`` decode loops
(materializing a bf16 copy); the Pallas kernel makes the fusion
structural.

Quantization is per-OUTPUT-channel (scale per column of W): the scale
multiply then applies to the f32 accumulator at flush time — one VPU
convert per weight element instead of a convert+scale+round-trip through
f32 — which is what makes the kernel beat the bf16 matmul instead of
merely matching it (1.15-2.2x at decode shapes in rounds 1-5, PERF.md §8:
a lead, no cell runs it).

Layout: x (..., K) float, w int8 (K, N), scales f32 (1, N) or (N,).
K on sublanes, N on lanes; blocks over K and N must be 128-multiples (or
the full dimension) — `int8_matmul` falls back to the jnp reference
formulation for shapes that can't tile.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import backend

DEFAULT_BLOCK_M = 128
DEFAULT_BLOCK_N = 512
DEFAULT_BLOCK_K = 1024

# VMEM the kernel may claim: ~16 MB/core on current TPUs; leave headroom
# for Mosaic's own staging. Shapes whose tile plan exceeds this run the
# jnp reference instead of failing to compile at serve time.
VMEM_BUDGET_BYTES = 10 * 1024 * 1024


def _plan_vmem_bytes(bm: int, bk: int, bn: int) -> int:
    """Worst-case VMEM for one grid step: double-buffered inputs (x bf16;
    w int8 plus its in-kernel bf16 expansion; scale row), f32 accumulator
    scratch and the output tile."""
    inputs = bm * bk * 2 + bk * bn * (1 + 2) + bn * 4
    return 2 * inputs + bm * bn * (4 + 2)


def kernel_plan(M: int, K: int, N: int, block_m: int = DEFAULT_BLOCK_M,
                block_n: int = DEFAULT_BLOCK_N,
                block_k: int = DEFAULT_BLOCK_K,
                vmem_budget: Optional[int] = VMEM_BUDGET_BYTES):
    """Tile plan (bm, bk, bn) for the Pallas kernel, or ``None`` when the
    shape should take the jnp reference: untileable K/N, or a plan (e.g.
    the full-dimension fallback for non-128-multiple dims) whose operand
    tiles would blow the VMEM budget. ``vmem_budget=None`` skips the
    budget gate (interpret mode has no VMEM)."""
    bk = _pick_block(block_k, K)
    bn = _pick_block(block_n, N)
    if bk == 0 or bn == 0:
        return None
    bm = min(block_m, max(8, -(-M // 8) * 8))
    if vmem_budget is not None and \
            _plan_vmem_bytes(bm, bk, bn) > vmem_budget:
        return None
    return bm, bk, bn


def _pick_block(limit: int, n: int, full_cap: int = 4096) -> int:
    """Mosaic block rule for a lane dimension: the block must be a
    128-multiple that divides ``n``, or the full dimension. Returns the
    largest valid choice <= limit (falling back to the full dim when it
    fits in ``full_cap``), else 0 — caller takes the jnp path."""
    best = 0
    d = 128
    while d <= min(limit, n):
        if n % d == 0:
            best = d
        d += 128
    if best == 0 and n <= full_cap:
        best = n
    return best


def quantize_columns(w, num_bits: int = 8):
    """Per-output-channel symmetric quantization: int8 values + f32 scale
    per column. numpy/jnp polymorphic; the serving-side companion of
    ``WeightQuantization`` (reference weight_quantizer.py) shaped for this
    kernel's layout."""
    import numpy as np

    v = np.asarray(w, np.float32)
    q_range = 2 ** (num_bits - 1) - 1
    scales = np.abs(v).max(axis=0, keepdims=True) / q_range    # (1, N)
    scales = np.where(scales == 0, 1.0, scales).astype(np.float32)
    q = np.clip(np.round(v / scales), -q_range - 1, q_range).astype(np.int8)
    return q, scales


def _kernel(x_ref, w_ref, s_ref, o_ref, acc_ref):
    k = pl.program_id(2)
    num_k = pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.bfloat16), w_ref[...].astype(jnp.bfloat16),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(k == num_k - 1)
    def _flush():
        o_ref[...] = (acc_ref[...] * s_ref[...]).astype(o_ref.dtype)


def int8_matmul_reference(x: jnp.ndarray, w: jnp.ndarray, scales: jnp.ndarray,
                          out_dtype=jnp.bfloat16) -> jnp.ndarray:
    """jnp formulation (dequant then dot) — numerics oracle and the
    fallback for shapes the kernel can't tile / non-TPU backends."""
    y = jax.lax.dot_general(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                            (((x.ndim - 1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    return (y * scales.reshape(1, -1)).astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("out_dtype", "block_m", "block_n",
                                             "block_k", "interpret"))
def _int8_matmul_2d(x, w, scales, *, out_dtype, block_m, block_n, block_k,
                    interpret):
    M, K = x.shape
    N = w.shape[1]
    bm = min(block_m, max(8, -(-M // 8) * 8))
    m_pad = -(-M // bm) * bm
    if m_pad != M:
        x = jnp.pad(x, ((0, m_pad - M), (0, 0)))
    grid = (m_pad // bm, N // block_n, K // block_k)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m_pad, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w, scales.reshape(1, N).astype(jnp.float32))
    return out[:M]


def int8_matmul(x: jnp.ndarray, w: jnp.ndarray, scales: jnp.ndarray,
                out_dtype=jnp.bfloat16,
                block_m: int = DEFAULT_BLOCK_M,
                block_n: int = DEFAULT_BLOCK_N,
                block_k: int = DEFAULT_BLOCK_K,
                interpret: Optional[bool] = None) -> jnp.ndarray:
    """``(x @ w_int8) * scales`` with the int8 expansion fused in-kernel.

    x: (..., K) floating; w: (K, N) int8; scales: (N,) or (1, N) f32
    per-output-channel. Returns (..., N) in ``out_dtype``. Shapes whose
    K/N can't satisfy the tiling rules (or whose plan exceeds the VMEM
    budget) run the jnp reference instead. Off-TPU the reference runs
    unless the caller forces the kernel with ``interpret=True``
    (kernel_mode='on' test forcing) — interpret-mode Pallas is orders of
    magnitude slower than the jnp formulation.
    """
    forced = interpret is True
    if interpret is None:
        if backend.pallas_interpret():
            return int8_matmul_reference(x, w, scales, out_dtype)
        interpret = False
    K, N = w.shape
    batch_shape = x.shape[:-1]
    x2 = x.reshape(-1, K)
    # forced interpret mode has no VMEM: only untileable K/N bail there
    plan = kernel_plan(x2.shape[0], K, N, block_m, block_n, block_k,
                       vmem_budget=None if forced else VMEM_BUDGET_BYTES)
    if plan is None:
        return int8_matmul_reference(x, w, scales, out_dtype)
    _, bk, bn = plan
    y = _int8_matmul_2d(x2, w, scales, out_dtype=jnp.dtype(out_dtype),
                        block_m=block_m, block_n=bn, block_k=bk,
                        interpret=interpret)
    return y.reshape(*batch_shape, N)
