"""Block-sparse flash attention as Pallas TPU kernels (splash-style).

The perf-bearing TPU analog of the reference's Triton block-sparse stack —
SDD/DSD block matmuls + sparse softmax (``ops/sparse_attention/matmul.py:17,
628``, ``softmax.py:224``) — fused into flash-attention kernels that iterate
ONLY the live key blocks of a sparsity layout.

Where the dense flash kernel's KV grid dimension walks every key block and
skips masked ones with a predicate, here the KV grid dimension has extent M
(the max live blocks over all (head, q-block) rows) and a scalar-prefetch
index array drives the K/V BlockSpec index maps: grid step m of row (h, i)
DMAs key block ``idx[h, i, m]``. Dead blocks are never fetched — both the
FLOPs and the HBM traffic scale with the layout's density, not O(T²). Rows
with fewer than M live blocks pad ``idx`` by repeating their last live
index: Pallas elides the DMA when consecutive grid steps map to the same
block, and ``m >= cnt[h, i]`` skips the compute, so padding costs only grid
iterations.

Granularity is TPU-native: the sparsity granule is the kernel block
(>=128 — the MXU/lane tile), exactly as the reference's granule is Triton's
16x16 tile. Layouts from any ``SparsityConfig`` with ``block >= 128`` run
here; finer layouts fall back to the gather formulation in
``sparse_self_attention.py`` (exact at any granule, but dense-gather cost).

Backward follows the flash recompute scheme (store per-row lse only) with
the same index-driven fetches: dq re-walks ``idx``; dk/dv walk the
TRANSPOSED layout (``idx_t[h, j]`` = query blocks attending key block j),
so every kernel touches only live tiles.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import backend
from ..attention.flash_attention import LANES, NEG_INF, SUBLANES

MIN_KERNEL_BLOCK = 128


def layout_to_schedule(layout: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """(H, nq, nk) 0/1 layout → (idx (H, nq, M) int32, cnt (H, nq) int32).

    ``idx[h, i, :cnt[h, i]]`` lists the live key blocks of row (h, i) in
    ascending order; slots past cnt repeat the last live index (DMA-elision
    padding). Rows with no live blocks point at block 0 with cnt 0.
    """
    H, nq, nk = layout.shape
    counts = layout.sum(-1).astype(np.int32)
    M = max(1, int(counts.max()))
    idx = np.zeros((H, nq, M), np.int32)
    for h in range(H):
        for i in range(nq):
            js = np.nonzero(layout[h, i])[0]
            if len(js):
                idx[h, i, :len(js)] = js
                idx[h, i, len(js):] = js[-1]
    return idx, counts


def _sparse_fwd_kernel(idx_ref, cnt_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                       acc_ref, m_ref, l_ref, *, scale: float, causal: bool,
                       block: int, num_heads: int):
    h = pl.program_id(0) % num_heads
    i = pl.program_id(1)
    m = pl.program_id(2)
    num_m = pl.num_programs(2)

    @pl.when(m == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    @pl.when(m < cnt_ref[h, i])
    def _compute():
        kb = idx_ref[h, i, m]
        # MXU operands stay in the input dtype (bf16 at full rate on v5e);
        # accumulation/statistics fp32; p cast back for the PV dot
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = i * block + jax.lax.broadcasted_iota(
                jnp.int32, (block, block), 0)
            cols = kb * block + jax.lax.broadcasted_iota(
                jnp.int32, (block, block), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = jnp.broadcast_to(
            alpha * l_prev + jnp.sum(p, axis=1, keepdims=True), l_ref.shape)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(m == num_m - 1)
    def _finish():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        # rows with no live block keep lse = -inf-ish; exp(s - lse) in the
        # backward is then 0 via the cnt predicate (those rows never run)
        lse_row = (m_ref[:, :1] + jnp.log(l))[:, 0]
        lse_ref[0] = jnp.broadcast_to(lse_row[None, :], lse_ref.shape[1:])


def _sparse_fwd(q, k, v, idx, cnt, *, scale: float, causal: bool, block: int,
                num_heads: int):
    bh, seq, d = q.shape
    nq = seq // block
    M = idx.shape[-1]
    grid = (bh, nq, M)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block, d),
                         lambda b, i, m, idx_ref, cnt_ref: (b, i, 0)),
            pl.BlockSpec((1, block, d),
                         lambda b, i, m, idx_ref, cnt_ref:
                         (b, idx_ref[b % num_heads, i, m], 0)),
            pl.BlockSpec((1, block, d),
                         lambda b, i, m, idx_ref, cnt_ref:
                         (b, idx_ref[b % num_heads, i, m], 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block, d),
                         lambda b, i, m, idx_ref, cnt_ref: (b, i, 0)),
            pl.BlockSpec((1, SUBLANES, block),
                         lambda b, i, m, idx_ref, cnt_ref: (b, 0, i)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block, d), jnp.float32),
            pltpu.VMEM((block, LANES), jnp.float32),
            pltpu.VMEM((block, LANES), jnp.float32),
        ],
    )
    out, lse = pl.pallas_call(
        functools.partial(_sparse_fwd_kernel, scale=scale, causal=causal,
                          block=block, num_heads=num_heads),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, SUBLANES, seq), jnp.float32),
        ],
        interpret=backend.pallas_interpret(),
    )(idx, cnt, q, k, v)
    return out, lse


def _sparse_dq_kernel(idx_ref, cnt_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                      delta_ref, dq_ref, dq_acc_ref, *, scale: float,
                      causal: bool, block: int, num_heads: int):
    h = pl.program_id(0) % num_heads
    i = pl.program_id(1)
    m = pl.program_id(2)
    num_m = pl.num_programs(2)

    @pl.when(m == 0)
    def _init():
        dq_acc_ref[:] = jnp.zeros_like(dq_acc_ref)

    @pl.when(m < cnt_ref[h, i])
    def _compute():
        kb = idx_ref[h, i, m]
        # bf16 MXU operands, fp32 stats/accumulator (see fwd kernel note)
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0, 0][:, None]
        delta = delta_ref[0, 0][:, None]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = i * block + jax.lax.broadcasted_iota(
                jnp.int32, (block, block), 0)
            cols = kb * block + jax.lax.broadcasted_iota(
                jnp.int32, (block, block), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(k.dtype)
        dq_acc_ref[:] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                             preferred_element_type=jnp.float32)

    @pl.when(m == num_m - 1)
    def _finish():
        dq_ref[0] = (dq_acc_ref[:] * scale).astype(dq_ref.dtype)


def _sparse_dkv_kernel(idx_t_ref, cnt_t_ref, q_ref, k_ref, v_ref, do_ref,
                       lse_ref, delta_ref, dk_ref, dv_ref, dk_acc_ref,
                       dv_acc_ref, *, scale: float, causal: bool, block: int,
                       num_heads: int):
    h = pl.program_id(0) % num_heads
    j = pl.program_id(1)
    m = pl.program_id(2)
    num_m = pl.num_programs(2)

    @pl.when(m == 0)
    def _init():
        dk_acc_ref[:] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[:] = jnp.zeros_like(dv_acc_ref)

    @pl.when(m < cnt_t_ref[h, j])
    def _compute():
        qb = idx_t_ref[h, j, m]
        # bf16 MXU operands, fp32 stats/accumulators (see fwd kernel note)
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0, 0][:, None]
        delta = delta_ref[0, 0][:, None]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = qb * block + jax.lax.broadcasted_iota(
                jnp.int32, (block, block), 0)
            cols = j * block + jax.lax.broadcasted_iota(
                jnp.int32, (block, block), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        p = jnp.exp(s - lse)
        p_lo = p.astype(do.dtype)
        dv_acc_ref[:] += jax.lax.dot_general(p_lo, do, (((0,), (0,)), ((), ())),
                                             preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(q.dtype)
        dk_acc_ref[:] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                             preferred_element_type=jnp.float32)

    @pl.when(m == num_m - 1)
    def _finish():
        # q is unscaled in the s recompute, so dk picks up the scale here
        dk_ref[0] = (dk_acc_ref[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[:].astype(dv_ref.dtype)


def _sparse_bwd(q, k, v, out, lse, do, idx, cnt, idx_t, cnt_t, *,
                scale: float, causal: bool, block: int, num_heads: int):
    bh, seq, d = q.shape
    nq = seq // block
    M = idx.shape[-1]
    Mt = idx_t.shape[-1]

    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[:, None, :], (bh, SUBLANES, seq))

    dq_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bh, nq, M),
        in_specs=[
            pl.BlockSpec((1, block, d),
                         lambda b, i, m, idx_ref, cnt_ref: (b, i, 0)),
            pl.BlockSpec((1, block, d),
                         lambda b, i, m, idx_ref, cnt_ref:
                         (b, idx_ref[b % num_heads, i, m], 0)),
            pl.BlockSpec((1, block, d),
                         lambda b, i, m, idx_ref, cnt_ref:
                         (b, idx_ref[b % num_heads, i, m], 0)),
            pl.BlockSpec((1, block, d),
                         lambda b, i, m, idx_ref, cnt_ref: (b, i, 0)),
            pl.BlockSpec((1, SUBLANES, block),
                         lambda b, i, m, idx_ref, cnt_ref: (b, 0, i)),
            pl.BlockSpec((1, SUBLANES, block),
                         lambda b, i, m, idx_ref, cnt_ref: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, block, d),
                               lambda b, i, m, idx_ref, cnt_ref: (b, i, 0)),
        scratch_shapes=[pltpu.VMEM((block, d), jnp.float32)],
    )
    dq = pl.pallas_call(
        functools.partial(_sparse_dq_kernel, scale=scale, causal=causal,
                          block=block, num_heads=num_heads),
        grid_spec=dq_spec,
        out_shape=jax.ShapeDtypeStruct((bh, seq, d), q.dtype),
        interpret=backend.pallas_interpret(),
    )(idx, cnt, q, k, v, do, lse, delta)

    dkv_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bh, nq, Mt),
        in_specs=[
            pl.BlockSpec((1, block, d),
                         lambda b, j, m, it_ref, ct_ref:
                         (b, it_ref[b % num_heads, j, m], 0)),
            pl.BlockSpec((1, block, d),
                         lambda b, j, m, it_ref, ct_ref: (b, j, 0)),
            pl.BlockSpec((1, block, d),
                         lambda b, j, m, it_ref, ct_ref: (b, j, 0)),
            pl.BlockSpec((1, block, d),
                         lambda b, j, m, it_ref, ct_ref:
                         (b, it_ref[b % num_heads, j, m], 0)),
            pl.BlockSpec((1, SUBLANES, block),
                         lambda b, j, m, it_ref, ct_ref:
                         (b, 0, it_ref[b % num_heads, j, m])),
            pl.BlockSpec((1, SUBLANES, block),
                         lambda b, j, m, it_ref, ct_ref:
                         (b, 0, it_ref[b % num_heads, j, m])),
        ],
        out_specs=[
            pl.BlockSpec((1, block, d),
                         lambda b, j, m, it_ref, ct_ref: (b, j, 0)),
            pl.BlockSpec((1, block, d),
                         lambda b, j, m, it_ref, ct_ref: (b, j, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block, d), jnp.float32),
            pltpu.VMEM((block, d), jnp.float32),
        ],
    )
    dk, dv = pl.pallas_call(
        functools.partial(_sparse_dkv_kernel, scale=scale, causal=causal,
                          block=block, num_heads=num_heads),
        grid_spec=dkv_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq, d), k.dtype),
            jax.ShapeDtypeStruct((bh, seq, d), v.dtype),
        ],
        interpret=backend.pallas_interpret(),
    )(idx_t, cnt_t, q, k, v, do, lse, delta)
    return dq, dk, dv


@functools.lru_cache(maxsize=32)
def _build_sparse_fn(layout_key, block: int, causal: bool, scale: float,
                     num_heads: int):
    """Construct the custom-VJP attention fn for one (layout, block) pair.

    The schedule arrays are closure constants (the layout is static per
    config + seq length); q/k/v are the only differentiable inputs.
    ``layout_key`` is (bytes, shape) so identical layouts share a cache
    entry across calls.
    """
    layout = np.frombuffer(layout_key[0], np.int32).reshape(layout_key[1])
    # schedule arrays stay HOST numpy in this (lru_cached) closure ON
    # PURPOSE: jnp constants built here would be tracers of whichever
    # trace first populated the cache entry, and a later trace hitting
    # the same key would receive leaked tracers (UnexpectedTracerError).
    # numpy closures materialize fresh per-trace constants on use.
    idx, cnt = layout_to_schedule(layout)
    idx_t, cnt_t = layout_to_schedule(layout.transpose(0, 2, 1))

    @jax.custom_vjp
    def attn(q, k, v):
        out, _ = _sparse_fwd(q, k, v, idx, cnt, scale=scale, causal=causal,
                             block=block, num_heads=num_heads)
        return out

    def attn_fwd(q, k, v):
        from jax.ad_checkpoint import checkpoint_name

        out, lse = _sparse_fwd(q, k, v, idx, cnt, scale=scale, causal=causal,
                               block=block, num_heads=num_heads)
        # same checkpoint_name discipline as flash_attention: lets the
        # "dots" remat policy save (out, lse) and skip re-running the
        # forward kernel in the backward pass
        out = checkpoint_name(out, "flash_out")
        lse = checkpoint_name(lse, "flash_lse")
        return out, (q, k, v, out, lse)

    def attn_bwd(res, do):
        q, k, v, out, lse = res
        return _sparse_bwd(q, k, v, out, lse, do, idx, cnt, idx_t, cnt_t,
                           scale=scale, causal=causal, block=block,
                           num_heads=num_heads)

    attn.defvjp(attn_fwd, attn_bwd)
    return attn


def supports_pallas(layout_block: int, seq_len: int) -> bool:
    """The Pallas path needs MXU-sized sparsity granules and exact tiling."""
    return (layout_block >= MIN_KERNEL_BLOCK
            and layout_block % LANES == 0
            and seq_len % layout_block == 0)


def block_sparse_flash_attention(q, k, v, layout: np.ndarray, block: int,
                                 causal: bool = False,
                                 scale: Optional[float] = None):
    """Fused block-sparse attention. q/k/v: (B, T, H, D); ``layout``: host
    numpy (H, T//block, T//block) 0/1. Returns (B, T, H, D).

    Requires ``supports_pallas(block, T)``; callers route finer layouts to
    the gather formulation.
    """
    B, T, H, D = q.shape
    if not supports_pallas(block, T):
        raise ValueError(
            f"block {block} / seq {T} not supported by the Pallas kernel "
            f"(need block >= {MIN_KERNEL_BLOCK}, block % {LANES} == 0, "
            "T % block == 0)")
    if layout.shape != (H, T // block, T // block):
        raise ValueError(f"layout shape {layout.shape} != "
                         f"{(H, T // block, T // block)}")
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    layout = np.ascontiguousarray(layout.astype(np.int32))
    fn = _build_sparse_fn((layout.tobytes(), layout.shape), block,
                          bool(causal), float(scale), H)

    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, T, D)

    out = fn(to_bh(q), to_bh(k), to_bh(v))
    return out.reshape(B, H, T, D).transpose(0, 2, 1, 3)


__all__ = [
    "block_sparse_flash_attention",
    "layout_to_schedule",
    "supports_pallas",
    "MIN_KERNEL_BLOCK",
]
