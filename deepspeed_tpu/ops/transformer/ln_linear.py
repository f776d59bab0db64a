"""Fused LayerNorm -> Linear as a Pallas TPU kernel (forward + backward).

The TPU piece of the reference's fused transformer-block kernel
(csrc/transformer/ds_transformer_cuda.cpp:1055 norm_layer_fwd/bwd chains):
XLA fuses elementwise epilogues into matmuls but cannot fuse a
reduction->broadcast chain (LayerNorm) into a dot operand, so the
normalized activation makes a full HBM round-trip per LN->matmul pair
(twice per transformer block: ln_1->qkv, ln_2->fc), and the backward pays
the same for `dnorm = dy @ W^T` before the LayerNorm backward.

This kernel keeps the normalized tile in VMEM:

* forward: one grid row per (M-tile); at the first N-step the kernel
  computes fp32 row statistics, normalizes, applies (gamma, beta) and
  caches the normalized tile in VMEM scratch; every N-step then runs the
  MXU dot straight off that scratch. `y = (LN(x) * gamma + beta) @ W + b`
  never materializes LN(x) in HBM. Row stats (mean, rstd) are emitted for
  the backward.
* backward dx: `dn` accumulates in VMEM across the N-axis grid
  (`dn += dy_tile @ W_tile^T`); the final step applies the LayerNorm
  backward in-kernel and writes `dx` plus per-M-tile partial (dgamma,
  dbeta) rows — `dn` never reaches HBM.
* backward dW/db ride XLA: `n` is recomputed elementwise from the saved
  stats (one materialization in the backward only, same as the unfused
  path's remat) and fed to a standard dot.

Stats use the lse layout convention from ops/attention/flash_attention.py:
(SUBLANES, M) with values replicated across the sublane dim.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import backend

SUBLANES = 8
# checkpoint_name tags (see ops/attention/flash_attention.py ATTN_SAVE_NAMES):
# saving (y, stats) lets the "dots" remat policy skip re-running the fused
# forward kernel in the backward pass
LN_SAVE_NAMES = ("ln_linear_out", "ln_linear_stats")


def _fwd_kernel(x_ref, g_ref, b_ref, w_ref, bias_ref, y_ref, mean_ref,
                rstd_ref, n_ref, *, eps: float):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _stats():
        xf = x_ref[...].astype(jnp.float32)
        mu = jnp.mean(xf, axis=1, keepdims=True)
        xc = xf - mu
        var = jnp.mean(xc * xc, axis=1, keepdims=True)
        rstd = jax.lax.rsqrt(var + eps)
        xh = xc * rstd
        g = g_ref[...].astype(jnp.float32)
        b = b_ref[...].astype(jnp.float32)
        n_ref[...] = (xh * g + b).astype(n_ref.dtype)
        mean_ref[...] = jnp.broadcast_to(mu[:, 0][None, :], mean_ref.shape)
        rstd_ref[...] = jnp.broadcast_to(rstd[:, 0][None, :], rstd_ref.shape)

    acc = jax.lax.dot_general(n_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    y_ref[...] = (acc + bias_ref[...].astype(jnp.float32)).astype(y_ref.dtype)


def _bwd_dx_kernel(dy_ref, w_ref, x_ref, g_ref, mean_ref, rstd_ref, dx_ref,
                   dg_ref, db_ref, dn_ref):
    j = pl.program_id(1)
    num_n = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        dn_ref[...] = jnp.zeros_like(dn_ref)

    dn_ref[...] += jax.lax.dot_general(
        dy_ref[...], w_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(j == num_n - 1)
    def _finish():
        dn = dn_ref[...]
        xf = x_ref[...].astype(jnp.float32)
        mu = mean_ref[0][:, None]
        rstd = rstd_ref[0][:, None]
        xh = (xf - mu) * rstd
        g = g_ref[...].astype(jnp.float32)
        dxh = dn * g
        m1 = jnp.mean(dxh, axis=1, keepdims=True)
        m2 = jnp.mean(dxh * xh, axis=1, keepdims=True)
        dx_ref[...] = (rstd * (dxh - m1 - xh * m2)).astype(dx_ref.dtype)
        # per-M-tile partials, replicated across the 8-sublane dim (a
        # (1, C) block violates Mosaic's sublane-divisibility rule)
        dg_ref[...] = jnp.broadcast_to(
            jnp.sum(dn * xh, axis=0, keepdims=True), dg_ref.shape)
        db_ref[...] = jnp.broadcast_to(
            jnp.sum(dn, axis=0, keepdims=True), db_ref.shape)


def _pick_block(size: int, prefer: int) -> Optional[int]:
    b = prefer
    while b >= 8:
        if size % b == 0:
            return b
        b //= 2
    return None


def _ln_linear_fwd_impl(x, gamma, beta, w, bias, *, eps, block_m, block_n):
    m, c = x.shape
    n = w.shape[1]
    grid = (m // block_m, n // block_n)
    y, mean, rstd = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, c), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, c), lambda i, j: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, c), lambda i, j: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((c, block_n), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_n), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((block_m, block_n), lambda i, j: (i, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((SUBLANES, block_m), lambda i, j: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((SUBLANES, block_m), lambda i, j: (0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), x.dtype),
            jax.ShapeDtypeStruct((SUBLANES, m), jnp.float32),
            jax.ShapeDtypeStruct((SUBLANES, m), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_m, c), x.dtype)],
        interpret=backend.pallas_interpret(),
    )(x, gamma.reshape(1, c), beta.reshape(1, c), w, bias.reshape(1, n))
    return y, mean, rstd


def _ln_linear_bwd_impl(x, gamma, mean, rstd, w, dy, *, block_m, block_n):
    m, c = x.shape
    n = w.shape[1]
    grid = (m // block_m, n // block_n)
    dx, dg_parts, db_parts = pl.pallas_call(
        _bwd_dx_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_n), lambda i, j: (i, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((c, block_n), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_m, c), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, c), lambda i, j: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((SUBLANES, block_m), lambda i, j: (0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((SUBLANES, block_m), lambda i, j: (0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((block_m, c), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((SUBLANES, c), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((SUBLANES, c), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, c), x.dtype),
            jax.ShapeDtypeStruct((m // block_m * SUBLANES, c), jnp.float32),
            jax.ShapeDtypeStruct((m // block_m * SUBLANES, c), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_m, c), jnp.float32)],
        interpret=backend.pallas_interpret(),
    )(dy, w, x, gamma.reshape(1, c), mean, rstd)
    return dx, dg_parts, db_parts


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _ln_linear(x, gamma, beta, w, bias, eps, block_m, block_n):
    y, _, _ = _ln_linear_fwd_impl(x, gamma, beta, w, bias, eps=eps,
                                  block_m=block_m, block_n=block_n)
    return y


def _ln_linear_vjp_fwd(x, gamma, beta, w, bias, eps, block_m, block_n):
    from jax.ad_checkpoint import checkpoint_name

    y, mean, rstd = _ln_linear_fwd_impl(x, gamma, beta, w, bias, eps=eps,
                                        block_m=block_m, block_n=block_n)
    y = checkpoint_name(y, "ln_linear_out")
    mean = checkpoint_name(mean, "ln_linear_stats")
    rstd = checkpoint_name(rstd, "ln_linear_stats")
    return y, (x, gamma, beta, mean, rstd, w)


def _ln_linear_vjp_bwd(eps, block_m, block_n, res, dy):
    x, gamma, beta, mean, rstd, w = res
    dx, dg_parts, db_parts = _ln_linear_bwd_impl(
        x, gamma, mean, rstd, w, dy, block_m=block_m, block_n=block_n)
    # parts are replicated over the sublane dim: take row 0 of each tile
    c = x.shape[1]
    dgamma = dg_parts.reshape(-1, SUBLANES, c)[:, 0].sum(0).astype(
        gamma.dtype)
    dbeta = db_parts.reshape(-1, SUBLANES, c)[:, 0].sum(0).astype(
        beta.dtype)
    # dW/db on XLA: recompute n elementwise from the saved stats (one
    # backward-only materialization, same cost the unfused remat pays)
    xf = x.astype(jnp.float32)
    xh = (xf - mean[0][:, None]) * rstd[0][:, None]
    nmat = (xh * gamma.astype(jnp.float32) +
            beta.astype(jnp.float32)).astype(x.dtype)
    dw = jax.lax.dot_general(nmat, dy, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    db = dy.astype(jnp.float32).sum(0)
    return dx, dgamma, dbeta, dw.astype(w.dtype), db.astype(dy.dtype)


_ln_linear.defvjp(_ln_linear_vjp_fwd, _ln_linear_vjp_bwd)


def _prefer_block_m(c: int) -> int:
    """VMEM budget: the backward carries an fp32 (block_m, C) accumulator
    plus bf16 x/W tiles, so block_m shrinks as C grows."""
    if c <= 1024:
        return 512
    if c <= 2048:
        return 256
    return 128


def supports_fused(m: int, c: int, n: int) -> bool:
    """Shape gate for the fused path: exact tiling with MXU-sized blocks and
    a VMEM budget that holds a (block_m, C) tile (C <= 4096)."""
    bm = _pick_block(m, _prefer_block_m(c))
    bn = _pick_block(n, 512)
    return (c <= 4096 and c % 128 == 0 and
            bm is not None and bn is not None and bn >= 128)


def ln_linear(x, gamma, beta, w, bias, *, eps: float = 1e-5):
    """``(LN(x; gamma, beta) @ w + bias)`` fused; x: (..., C) -> (..., N).

    Falls back to the plain XLA composition when the shape gate fails
    (ragged M/N, very wide C) — numerics match either way.
    """
    *lead, c = x.shape
    n = w.shape[1]
    m = 1
    for d in lead:
        m *= d
    x2 = x.reshape(m, c)
    if not supports_fused(m, c, n):
        xf = x2.astype(jnp.float32)
        mu = jnp.mean(xf, axis=1, keepdims=True)
        xc = xf - mu
        var = jnp.mean(xc * xc, axis=1, keepdims=True)
        xh = xc * jax.lax.rsqrt(var + eps)
        nmat = (xh * gamma.astype(jnp.float32) +
                beta.astype(jnp.float32)).astype(x.dtype)
        # cast w to the activation dtype — fp32 params must not promote
        # the matmul (matches nn.Dense(dtype=...) and the fused kernel)
        y = nmat @ w.astype(x.dtype) + bias.astype(x.dtype)
        return y.reshape(*lead, n)
    block_m = _pick_block(m, _prefer_block_m(c))
    block_n = _pick_block(n, 512)
    y = _ln_linear(x2, gamma.astype(x.dtype), beta.astype(x.dtype),
                   w.astype(x.dtype), bias.astype(x.dtype), eps, block_m,
                   block_n)
    return y.reshape(*lead, n)
