"""Fused paged-attention decode as a Pallas TPU kernel.

The vLLM PagedAttention insight, aimed at this repo's hottest serving op:
the kernel reads the :class:`~deepspeed_tpu.serving.paged_pool.PagedKVPool`
page table IN PLACE instead of gathering pages into a dense per-slot view
first. The dense round-trip (``KVCacheSpec.dense_from_pages`` gather →
dense attention → ``_scatter_cols`` writeback) materializes O(slots ×
max_seq_len) K/V every step; here the page table rides scalar prefetch
(SMEM) and the K/V BlockSpec index maps resolve ``table[slot, j]`` per
grid step, so HBM traffic is one DMA per LIVE page — the pool's physical
pages are the only cache bytes ever read.

Parity contract (the "dense oracle" discipline): the per-step compute is
op-for-op the dense decode kernel's
(:func:`~deepspeed_tpu.ops.attention.decode_attention._decode_kernel` —
same online-softmax update order, same masking, same scratch shapes) with
the position block pinned to ONE PAGE. A single-token call is therefore
bitwise-identical to ``decode_attention(q, dense_k, dense_v, lengths,
block_s=page_size)`` on the gathered dense view in interpret mode on
the CPU, which is what lets the serving tests pin the paged-kernel arm
against the dense path exactly (TransformerConfig's ``decode_block``
pins the oracle's block granule to the page size). On the TPU that twin
exists only for pages of at least 128 positions: the dense kernel puts
``block_s`` on the lane axis and Mosaic rejects a 64-wide block there,
so at the default page size (64) the pinned oracle does not lower and
the arms are compared within a bf16 tolerance instead (chip_smoke.py:
max |delta logit| 0.041 at logit scale 5.0 on a v5e, PR 21). Bitwise
equality on the chip at page size 128 has not been tried.

Garbage is masked by length, never by table lookups: dead grid steps
(pages past a slot's live length) clamp their index map to the slot's
LAST LIVE page — consecutive identical block indices elide the DMA
(Pallas revisiting rule), so bandwidth tracks the live length — and
sentinel table entries (``num_pages`` = unmapped) clip to a real page
exactly like the dense gather's ``mode="clip"``; both reads are masked
to ``NEG_INF`` before the softmax, so their values never reach the
output. Supports 1..SUBLANES query rows per slot (plain decode T=1;
speculative verify T=K+1) with per-row causal masking, GQA, ALiBi, and
the int8/int32-packed quantized cache tiers (scales paged alongside,
folded into the score/probability rows like the dense kernel).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import backend
from .flash_attention import LANES, NEG_INF, SUBLANES

__all__ = ["paged_decode_attention", "MAX_QUERY_ROWS"]

# one kernel serves decode (T=1) and speculative verify (T=K+1): query
# rows live on the SUBLANES axis of the score tile, so the row budget is
# the sublane count — pools fall back to the dense composition beyond it
MAX_QUERY_ROWS = SUBLANES


def _paged_kernel(start_ref, slope_ref, table_ref, q_ref, k_ref, v_ref,
                  o_ref, acc_ref, m_ref, l_ref, *, scale: float,
                  page_size: int, num_rows: int, alibi: bool,
                  compute_dtype=None, k_scale_ref=None, v_scale_ref=None,
                  packed: bool = False):
    # start_ref/slope_ref/table_ref are scalar-prefetch SMEM arrays:
    # (B,), (H,) and (B, pages_per_slot). The compute below mirrors
    # decode_attention._decode_kernel line for line (the bitwise-parity
    # contract in the module docstring); the ONLY differences are where
    # K/V blocks come from (page-indexed index maps, not contiguous
    # offsets) and that query rows 0..num_rows-1 carry their own causal
    # limit (row t sees cache positions <= start + t).
    j = pl.program_id(2)
    num_p = pl.num_programs(2)
    start = start_ref[pl.program_id(0)]
    slope = slope_ref[pl.program_id(1)]
    block_start = j * page_size

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    @pl.when(block_start < start + num_rows)
    def _compute():
        q = q_ref[0]                                      # (SUBLANES, D)
        k = k_ref[0, 0]                                   # (Dc, page_size)
        v = v_ref[0, 0]
        if k_scale_ref is not None:
            if packed:
                k = pltpu.bitcast(k, jnp.int8).astype(compute_dtype)
                v = pltpu.bitcast(v, jnp.int8).astype(compute_dtype)
            else:
                k = k.astype(compute_dtype)
                v = v.astype(compute_dtype)
        s = jax.lax.dot_general(q, k, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if k_scale_ref is not None:
            s = s * k_scale_ref[0, 0]                     # (1, page) scale
        pos = block_start + jax.lax.broadcasted_iota(
            jnp.int32, (SUBLANES, page_size), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, page_size), 0)
        if alibi:
            # row t's query sits at absolute position start + t
            s = s + slope * (pos - (start + row)).astype(jnp.float32)
        s = jnp.where(pos <= start + row, s, NEG_INF)
        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = jnp.broadcast_to(
            alpha * l_prev + jnp.sum(p, axis=1, keepdims=True), l_ref.shape)
        if v_scale_ref is not None:
            p = p * v_scale_ref[0, 0]                     # (1, page) scale
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(j == num_p - 1)
    def _finish():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)


def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, table: jax.Array,
                           starts: jax.Array, *,
                           scale: Optional[float] = None,
                           alibi_slopes: Optional[jax.Array] = None,
                           k_scale_pages: Optional[jax.Array] = None,
                           v_scale_pages: Optional[jax.Array] = None
                           ) -> jax.Array:
    """Cached attention over paged K/V: softmax(q·K^T + bias) · V with
    K/V resolved through a per-slot page table inside the kernel.

    Args:
      q: (B, T, H, D) current-step queries, 1 <= T <= MAX_QUERY_ROWS.
        Row ``t`` of slot ``b`` attends cache positions
        ``[0, starts[b] + t]`` (its own column included — the caller has
        already written this step's T columns into the pages).
      k_pages/v_pages: (P, KV, Dc, page_size) ONE layer's physical page
        pool, H % KV == 0 (GQA). May be int8, or int32-packed
        (Dc = D // 4) when scales are given.
      table: (B, pages_per_slot) int32 page table; ``P`` is the
        unmapped sentinel (clipped to a real page, masked by length —
        the dense gather's ``mode="clip"`` discipline).
      starts: (B,) int32 cache length BEFORE this step's tokens (the
        slot pool's ``index`` mirror at dispatch).
      alibi_slopes: optional (H,) ALiBi slopes.
      k_scale_pages/v_scale_pages: (P, KV, page_size) fp32 per-column
        dequantization scales for a quantized page pool.
    Returns (B, T, H, D) in q's dtype.
    """
    starts = jnp.broadcast_to(jnp.asarray(starts, jnp.int32),
                              (q.shape[0],))
    kernel = functools.partial(_paged_decode_attention_local, scale=scale)
    B, H = backend.BATCH, backend.HEADS
    # the page pool has no batch dim: every device holds every page of
    # its KV heads, and its slots' rows of the table
    return backend.shard_kernel(
        kernel, (B, None, H, None),
        q=(q, (B, None, H, None)), k_pages=(k_pages, (None, H, None, None)),
        v_pages=(v_pages, (None, H, None, None)), table=(table, (B, None)),
        starts=(starts, (B,)), alibi_slopes=(alibi_slopes, (H,)),
        k_scale_pages=(k_scale_pages, (None, H, None)),
        v_scale_pages=(v_scale_pages, (None, H, None)))


def _paged_decode_attention_local(q, k_pages, v_pages, table, starts, *,
                                  scale, alibi_slopes, k_scale_pages,
                                  v_scale_pages):
    """:func:`paged_decode_attention` on the slots and heads one device
    holds."""
    B, T, H, D = q.shape
    P, KV, Dc, ps = k_pages.shape
    maxP = table.shape[1]
    assert H % KV == 0, f"H={H} not a multiple of KV={KV}"
    assert 1 <= T <= MAX_QUERY_ROWS, \
        f"paged kernel handles 1..{MAX_QUERY_ROWS} query rows, got {T}"
    assert (k_scale_pages is None) == (v_scale_pages is None), \
        "provide both k_scale_pages and v_scale_pages or neither"
    quantized = k_scale_pages is not None
    packed = quantized and k_pages.dtype == jnp.int32
    assert Dc == (D // 4 if packed else D), \
        f"page head dim {Dc} vs query head dim {D} (packed={packed})"
    rep = H // KV
    out_dtype = q.dtype
    # dtype harmonization — identical to decode_attention's wrapper so
    # the two kernels' MXU operands (and thus outputs) match bitwise
    if quantized:
        compute_dtype = q.dtype if q.dtype == jnp.bfloat16 else jnp.float32
        q = q.astype(compute_dtype)
    else:
        compute_dtype = k_pages.dtype
        q = q.astype(k_pages.dtype)
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    if alibi_slopes is None:
        slopes = jnp.zeros((H,), jnp.float32)
        alibi = False
    else:
        slopes = jnp.asarray(alibi_slopes, jnp.float32)
        alibi = True
    table = jnp.asarray(table, jnp.int32)

    # query rows ride the SUBLANES axis: pad T up to the full sublane
    # tile (dead rows compute with a wider causal window and are sliced
    # off — never all-masked, so no NaN risk) and fold heads into the
    # leading grid axis like the dense kernel's q3
    q4 = q.transpose(0, 2, 1, 3)                          # (B, H, T, D)
    if T < SUBLANES:
        q4 = jnp.pad(q4, ((0, 0), (0, 0), (0, SUBLANES - T), (0, 0)))
    q3 = q4.reshape(B * H, SUBLANES, D)

    grid = (B, H, maxP)

    def kv_index(b, h, j, start_ref, slope_ref, table_ref):
        # clamp dead steps to the slot's last LIVE page (consecutive
        # identical indices elide the DMA — bandwidth tracks the live
        # length), then clip sentinel entries into range (masked reads)
        last_live = jnp.maximum(
            (start_ref[b] + T + ps - 1) // ps - 1, 0)
        pid = table_ref[b, jnp.minimum(j, last_live)]
        return (jnp.minimum(pid, P - 1), h // rep, 0, 0)

    in_specs = [
        pl.BlockSpec((1, SUBLANES, D), lambda b, h, j, *_: (b * H + h, 0, 0)),
        pl.BlockSpec((1, 1, Dc, ps), kv_index),
        pl.BlockSpec((1, 1, Dc, ps), kv_index),
    ]
    operands = [starts, slopes, table, q3, k_pages, v_pages]
    if quantized:
        # scales ride as (P, KV, 1, page_size) so the (1, 1, 1, ps)
        # block lands on LANES, matching s/p (same trick as the dense
        # kernel's (B, KV, 1, S) reshape)
        in_specs += [pl.BlockSpec((1, 1, 1, ps), kv_index),
                     pl.BlockSpec((1, 1, 1, ps), kv_index)]
        operands += [
            k_scale_pages.astype(jnp.float32).reshape(P, KV, 1, ps),
            v_scale_pages.astype(jnp.float32).reshape(P, KV, 1, ps)]

        def kernel(start_ref, slope_ref, table_ref, q_ref, k_ref, v_ref,
                   ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref):
            _paged_kernel(start_ref, slope_ref, table_ref, q_ref, k_ref,
                          v_ref, o_ref, acc_ref, m_ref, l_ref, scale=scale,
                          page_size=ps, num_rows=T, alibi=alibi,
                          compute_dtype=compute_dtype,
                          k_scale_ref=ks_ref, v_scale_ref=vs_ref,
                          packed=packed)
    else:
        kernel = functools.partial(_paged_kernel, scale=scale, page_size=ps,
                                   num_rows=T, alibi=alibi)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, SUBLANES, D),
                               lambda b, h, j, *_: (b * H + h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((SUBLANES, D), jnp.float32),
            pltpu.VMEM((SUBLANES, LANES), jnp.float32),
            pltpu.VMEM((SUBLANES, LANES), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        name="paged_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * H, SUBLANES, D), q.dtype),
        interpret=backend.pallas_interpret(),
    )(*operands)
    out = out.reshape(B, H, SUBLANES, D)[:, :, :T]
    return out.transpose(0, 2, 1, 3).astype(out_dtype)
