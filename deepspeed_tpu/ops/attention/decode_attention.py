"""Fused KV-cache decode attention as a Pallas TPU kernel.

TPU-native equivalent of the reference's generation hot path — the
``softmax_context`` fused attention-with-KV-cache kernel
(csrc/transformer/inference/csrc/pt_binding.cpp:1910-1975): one query
token per sequence attends over the cache with length masking, softmax and
the value reduction fused in a single pass. Decode is HBM-bandwidth bound
(the whole cache is read every step); fusing keeps the (H, S) score matrix
in VMEM instead of HBM and reads K/V exactly once.

Layout: q (B, H, D); k/v cache (B, KV, D, S) — the model's cache layout:
D on SUBLANES, positions on LANES. Positions-minor is deliberate: S is
always a multiple of 128, so no tile is ever lane-padded (a (S, D=64)
cache pads every 128-lane tile 2x — measured as the capacity killer in
the round-5 ladder), and the int8-packed int32 container keeps whole
positions per word so cache writes stay word-aligned plain
dynamic-update-slices. The kernel's two dots contract directly against
this orientation (q·K over D-sublanes, p·V over position-lanes) — no
transpose anywhere. Grouped-query attention maps query head h to
kv head h // (H // KV) in the BlockSpec index map. ``lengths`` (B,) masks
cache slots >= length. Optional ALiBi slopes add the reference's alibi
bias. Blocks past a sequence's length are dead: ``pl.when`` skips their
compute, and the K/V index maps CLAMP dead grid steps to the sequence's
last live block — consecutive grid steps with the same block index elide
the DMA (Pallas revisiting rule), so HBM traffic ALSO tracks the live
length (one redundant block fetch at the boundary), not the allocated
capacity. Decoding at position p costs O(p), the realistic generate()
regime where p << max_seq_len.
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

DEFAULT_BLOCK_S = 1024
LONG_CACHE_BLOCK_S = 4096  # >= 8k caches: grid overhead, not bandwidth,
# bounds the 1024 block — a block sweep of rounds 1-5 had 4096
# fastest for both bf16 and int8 at 16k (PERF.md §8);
# short live lengths only pay one partially-dead block (the index-map
# clamp elides the rest), a sub-ms cost


def pick_block_s(cache_len: int, preferred: Optional[int] = None) -> int:
    """Largest power-of-two block <= preferred that divides the cache
    length (the kernel requires S % block_s == 0). Returns the largest
    power-of-two divisor when that's below ``preferred``. Default
    preference is length-aware: 1024 below 8k, 4096 from 8k up."""
    if preferred is None:
        preferred = LONG_CACHE_BLOCK_S if cache_len >= 8192 \
            else DEFAULT_BLOCK_S
    block = preferred
    while block > 1 and cache_len % block != 0:
        block //= 2
    return block


def quantize_kv_rows(x: jax.Array):
    """Per-row symmetric int8 quantization over the last axis: returns
    (int8 values, fp32 scales) with ``x ≈ int8 * scale[..., None]``.
    The KV-cache quantizer: one scale per (batch, kv-head, position)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.round(x.astype(jnp.float32) / scale[..., None])
    q = jnp.clip(q, -127, 127).astype(jnp.int8)
    return q, scale


def pack_int8_sublanes(x8: jax.Array) -> jax.Array:
    """Pack int8 (..., R, C) into an int32 container (..., R//4, C):
    byte ``j`` of word ``(i, c)`` is element ``(4*i + j, c)``.

    Why: Mosaic stores int8 arrays in a (4, 1)-packed tiled layout; when
    an int8 KV cache rides a ``lax.scan``/while-loop carry, a
    layout-conversion copy defeats XLA's in-place buffer aliasing and the
    decode program double-buffers the cache (rounds 1-5: 485 MB over
    at int8 B=4; PERF.md §8).
    int32 carries use the native (8, 128) tiling and alias in place, so
    the same bytes in an int32 container restore O(cache) memory.

    For the (B, KV, D, S) cache this packs along D (the sublane dim), so
    each word holds 4 head-dim rows of one position and cache writes stay
    word-aligned. The byte order equals the TPU's own sublane packing, so
    inside the kernel ``pltpu.bitcast(words, int8)`` reinterprets the
    (D//4, block) int32 tile as the (D, block) int8 tile FOR FREE — no
    shifts, no relayout (verified identical on real v5e and in interpret
    mode)."""
    R = x8.shape[-2]
    assert R % 4 == 0, f"packed dim {R} not a multiple of 4"
    w = (x8.reshape(*x8.shape[:-2], R // 4, 4, x8.shape[-1])
         .astype(jnp.int32) & jnp.int32(0xFF))
    return (w[..., 0, :] | (w[..., 1, :] << 8) | (w[..., 2, :] << 16)
            | (w[..., 3, :] << 24))


def unpack_int8_sublanes(w: jax.Array, dtype=jnp.int8) -> jax.Array:
    """Inverse of :func:`pack_int8_sublanes` in plain jnp (for the einsum
    fallback and host-side round trips): (..., R//4, C) -> (..., R, C).
    Arithmetic right shift sign-extends each byte."""
    parts = jnp.stack(
        [((w << (24 - 8 * j)) >> 24) for j in range(4)], axis=-2)
    return parts.reshape(*w.shape[:-2], w.shape[-2] * 4,
                         w.shape[-1]).astype(dtype)


def _decode_kernel(len_ref, slope_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, scale: float, block_s: int,
                   alibi: bool, compute_dtype=None,
                   k_scale_ref=None, v_scale_ref=None, packed: bool = False):
    # len_ref/slope_ref are scalar-prefetch SMEM arrays: (B,) and (H,).
    # With an int8-quantized cache, k_scale_ref/v_scale_ref carry the
    # per-row (per token, per kv-head) dequantization scales and are
    # threaded in as extra INPUT refs (before o_ref at call time; bound
    # here by keyword from the wrapper's arg shuffle).
    j = pl.program_id(2)
    num_s = pl.num_programs(2)
    length = len_ref[pl.program_id(0)]
    slope = slope_ref[pl.program_id(1)]
    block_start = j * block_s

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    @pl.when(block_start < length)
    def _compute():
        # MXU operands stay in the compute dtype (bf16 at full rate on
        # v5e); fp32 stats/accumulator; scale applied to fp32 s.
        # int8 path: int8 values <= 127 are EXACT in bf16, so the cache
        # casts losslessly and the dequant scales fold into the score row
        # (k) and the probability row (v) — two (SUBLANES, block_s) VPU
        # multiplies instead of dequantizing the (block_s, D) blocks.
        q = q_ref[0]                                      # (1, D)
        qb = jnp.broadcast_to(q, (SUBLANES, q.shape[-1]))
        k = k_ref[0, 0]                                   # (D, block_s)
        v = v_ref[0, 0]
        if k_scale_ref is not None:
            if packed:
                # int32-packed int8 cache: the (D//4, block) int32 tile
                # IS the (D, block) int8 tile bit-for-bit (sublane byte
                # order) — bitcast reinterprets it for free. int8
                # magnitudes are exact in bf16, so the cast is lossless.
                k = pltpu.bitcast(k, jnp.int8).astype(compute_dtype)
                v = pltpu.bitcast(v, jnp.int8).astype(compute_dtype)
            else:
                k = k.astype(compute_dtype)
                v = v.astype(compute_dtype)
        s = jax.lax.dot_general(qb, k, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if k_scale_ref is not None:
            s = s * k_scale_ref[0, 0]                     # (1, block_s) scale
        pos = block_start + jax.lax.broadcasted_iota(
            jnp.int32, (SUBLANES, block_s), 1)
        if alibi:
            # reference alibi bias: slope * (key_pos - query_pos); the
            # decoding query sits at position length - 1
            s = s + slope * (pos - (length - 1)).astype(jnp.float32)
        s = jnp.where(pos < length, s, NEG_INF)
        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = jnp.broadcast_to(
            alpha * l_prev + jnp.sum(p, axis=1, keepdims=True), l_ref.shape)
        if v_scale_ref is not None:
            p = p * v_scale_ref[0, 0]                     # (1, block_s) scale
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(j == num_s - 1)
    def _finish():
        l = jnp.maximum(l_ref[:1, :1], 1e-30)
        o_ref[0] = (acc_ref[:1] / l).astype(o_ref.dtype)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     lengths: jax.Array, *, scale: Optional[float] = None,
                     alibi_slopes: Optional[jax.Array] = None,
                     k_scale: Optional[jax.Array] = None,
                     v_scale: Optional[jax.Array] = None,
                     block_s: int = DEFAULT_BLOCK_S) -> jax.Array:
    """Single-token cached attention: softmax(q·K^T + bias) · V.

    Args:
      q: (B, H, D) current-step queries.
      k_cache/v_cache: (B, KV, D, S) with H % KV == 0 (GQA) — positions
        minor (see module docstring: no lane padding, aligned writes).
        May be int8 (quantized KV cache) when ``k_scale``/``v_scale``
        are given, or int32 (B, KV, D//4, S) — the
        :func:`pack_int8_sublanes` container whose carries alias in
        place through ``lax.scan`` (the in-kernel unpack is a free
        ``pltpu.bitcast``).
      lengths: (B,) or scalar int32 — valid cache slots per sequence
        (INCLUDING the current token, already written to the cache).
      alibi_slopes: optional (H,) ALiBi slopes.
      k_scale/v_scale: (B, KV, S) fp32 per-row dequantization scales for
        an int8 cache (row value = int8 * scale). Halves the cache's HBM
        traffic — the resource decode is bound by; the scales fold into
        the score/probability rows, so no dequantized (block_s, D) block
        is ever materialized.
    Returns (B, H, D) in q's dtype.
    """
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32),
                               (q.shape[0],))
    kernel = functools.partial(_decode_attention_local, scale=scale,
                               block_s=block_s)
    B, H = backend.BATCH, backend.HEADS
    return backend.shard_kernel(
        kernel, (B, H, None),
        q=(q, (B, H, None)), k_cache=(k_cache, (B, H, None, None)),
        v_cache=(v_cache, (B, H, None, None)), lengths=(lengths, (B,)),
        alibi_slopes=(alibi_slopes, (H,)), k_scale=(k_scale, (B, H, None)),
        v_scale=(v_scale, (B, H, None)))


def _decode_attention_local(q, k_cache, v_cache, lengths, *, scale,
                            alibi_slopes, k_scale, v_scale, block_s):
    """:func:`decode_attention` on the sequences and heads one device
    holds."""
    B, H, D = q.shape
    _, KV, Dc, S = k_cache.shape
    assert H % KV == 0, f"H={H} not a multiple of KV={KV}"
    assert (k_scale is None) == (v_scale is None), \
        "provide both k_scale and v_scale or neither"
    quantized = k_scale is not None
    packed = quantized and k_cache.dtype == jnp.int32
    assert Dc == (D // 4 if packed else D), \
        f"cache head dim {Dc} vs query head dim {D} (packed={packed})"
    rep = H // KV
    # MXU operands must share a dtype (the kernel no longer upcasts to
    # fp32 — bf16 runs at full MXU rate); harmonize q to the cache dtype
    # (for int8 caches the compute dtype is q's own) and restore the
    # caller's dtype on the way out
    out_dtype = q.dtype
    if quantized:
        compute_dtype = q.dtype if q.dtype == jnp.bfloat16 else jnp.float32
        q = q.astype(compute_dtype)
    else:
        compute_dtype = k_cache.dtype
        q = q.astype(k_cache.dtype)
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    if alibi_slopes is None:
        slopes = jnp.zeros((H,), jnp.float32)
        alibi = False
    else:
        slopes = jnp.asarray(alibi_slopes, jnp.float32)
        alibi = True
    block_s = min(block_s, S)
    assert S % block_s == 0, f"cache length {S} % block_s {block_s} != 0"

    grid = (B, H, S // block_s)
    # q/out carry a dummy middle dim so every block's trailing two dims
    # equal the array dims (the Mosaic tiling contract); lengths/slopes ride
    # scalar prefetch (SMEM, fully resident) and index maps receive them as
    # trailing args per the PrefetchScalarGridSpec contract
    q3 = q.reshape(B * H, 1, D)

    def kv_index(b, h, j, len_ref, slope_ref):
        # clamp dead steps to the last LIVE block: consecutive identical
        # indices elide the DMA, so bandwidth tracks the live length
        last_live = jnp.maximum(
            (len_ref[b] + block_s - 1) // block_s - 1, 0)
        return (b, h // rep, 0, jnp.minimum(j, last_live))

    scale_index = kv_index

    in_specs = [
        pl.BlockSpec((1, 1, D), lambda b, h, j, *_: (b * H + h, 0, 0)),
        pl.BlockSpec((1, 1, Dc, block_s), kv_index),
        pl.BlockSpec((1, 1, Dc, block_s), kv_index),
    ]
    operands = [lengths, slopes, q3, k_cache, v_cache]
    if quantized:
        # scales ride as (B, KV, 1, S): the block (1, 1, 1, block_s) puts
        # them on LANES, matching s/p's lane layout (and Mosaic's tiling
        # contract — a (1, block_s) trailing block would not tile)
        in_specs += [pl.BlockSpec((1, 1, 1, block_s), scale_index),
                     pl.BlockSpec((1, 1, 1, block_s), scale_index)]
        operands += [k_scale.astype(jnp.float32).reshape(B, KV, 1, S),
                     v_scale.astype(jnp.float32).reshape(B, KV, 1, S)]

        def kernel(len_ref, slope_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                   o_ref, acc_ref, m_ref, l_ref):
            _decode_kernel(len_ref, slope_ref, q_ref, k_ref, v_ref, o_ref,
                           acc_ref, m_ref, l_ref, scale=scale,
                           block_s=block_s, alibi=alibi,
                           compute_dtype=compute_dtype,
                           k_scale_ref=ks_ref, v_scale_ref=vs_ref,
                           packed=packed)
    else:
        kernel = functools.partial(_decode_kernel, scale=scale,
                                   block_s=block_s, alibi=alibi)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, D),
                               lambda b, h, j, *_: (b * H + h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((SUBLANES, D), jnp.float32),
            pltpu.VMEM((SUBLANES, LANES), jnp.float32),
            pltpu.VMEM((SUBLANES, LANES), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        name="dense_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * H, 1, D), q.dtype),
        interpret=backend.pallas_interpret(),
    )(*operands)
    return out.reshape(B, H, D).astype(out_dtype)
