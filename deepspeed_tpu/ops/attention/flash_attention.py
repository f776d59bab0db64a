"""Fused flash attention (forward + backward) as Pallas TPU kernels.

TPU-native replacement for the reference's fused attention kernels — the
training transformer kernel's softmax/attention path
(csrc/transformer/softmax_kernels.cu + ds_transformer_cuda.cpp) and the
flash-style parity piece called out in SURVEY §2.2. Online-softmax tiling
(Flash-Attention-2 style) keeps the (T×T) score matrix out of HBM: scores are
computed block-by-block in VMEM, the MXU does the two matmuls per block, and
running max/sum statistics rescale the accumulator.

VMEM stays O(block), not O(seq): the KV axis is a grid dimension (TPU grids
execute sequentially, innermost-last, so VMEM scratch carries the
accumulator/stats across KV iterations of one Q block) — Pallas DMAs only the
current (block, d) tiles. Causal masking skips fully-masked blocks.

Layout: (batch, seq, heads, head_dim) in, same out. Backward follows the
standard recompute scheme: store only ``lse`` (per-row log-sum-exp); dq and
dk/dv are two kernels gridding the opposite axes.
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

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30
# checkpoint_name tags on attention-kernel outputs (see _flash_attention_fwd);
# remat policies compose save_only_these_names(*ATTN_SAVE_NAMES) so the
# backward pass reuses the forward kernel's (out, lse) instead of re-running it
ATTN_SAVE_NAMES = ("flash_out", "flash_lse")
# TPU vector layout: fp32 tiles are (8 sublanes, 128 lanes). Row statistics
# (lse, delta) are carried replicated across a size-8 sublane dim so their
# blocks satisfy the (8, 128) tiling rule; stats scratch is lane-width.
SUBLANES = 8
LANES = 128


# ---------------------------------------------------------------------------
# forward: grid (bh, q_blocks, kv_blocks), scratch carries (acc, m, l)
# ---------------------------------------------------------------------------
def _fwd_single_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                       scale: float, causal: bool):
    """One-KV-block specialization (block_k == seq_k): plain block softmax.

    The tuned table picks block_k = seq for seq <= 1024 (and 512x1024 tiles
    generally), where the KV grid axis has a single step — the online-softmax
    running stats (acc rescale, m/l scratch round-trips, alpha exps) are pure
    overhead there. This kernel computes max/exp/sum once and writes out
    directly from registers/VMEM."""
    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]
    qi = pl.program_id(1)
    q_start = qi * block_q

    q = q_ref[0]
    k = k_ref[0]
    v = v_ref[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        s = jnp.where(rows >= cols, s, NEG_INF)
    m = jnp.max(s, axis=1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.maximum(jnp.sum(p, axis=1, keepdims=True), 1e-30)
    acc = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    lse_row = (m + jnp.log(l))[:, 0]
    lse_ref[0] = jnp.broadcast_to(lse_row[None, :], lse_ref.shape[1:])


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
                scale: float, causal: bool):
    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]
    qi = pl.program_id(1)
    j = pl.program_id(2)
    num_kv = pl.num_programs(2)
    q_start = qi * block_q
    k_start = j * block_k

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # causal: skip blocks entirely above the diagonal
    live = (not causal) or (k_start < q_start + block_q)

    @pl.when(jnp.asarray(live))
    def _compute():
        # MXU operands stay in the input dtype (bf16 in training): v5e runs
        # bf16xbf16->fp32 at full rate but fp32 matmuls at a fraction of it.
        # Accumulation/statistics are fp32 (preferred_element_type); p is
        # cast back to the input dtype for the PV dot (FA2 discipline).
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(j == num_kv - 1)
    def _finish():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        lse_row = (m_ref[:, :1] + jnp.log(l))[:, 0]  # (block_q,)
        lse_ref[0] = jnp.broadcast_to(lse_row[None, :], lse_ref.shape[1:])


def _flash_fwd(q, k, v, *, causal: bool, scale: float, block_q: int, block_k: int):
    bh, seq_q, d = q.shape
    _, seq_k, _ = k.shape
    block_q = min(block_q, seq_q)
    block_k = min(block_k, seq_k)
    assert seq_q % block_q == 0 and seq_k % block_k == 0, \
        f"seq ({seq_q},{seq_k}) must be divisible by blocks ({block_q},{block_k})"

    if seq_k == block_k:
        # single KV step: no online stats needed (see _fwd_single_kernel)
        out, lse = pl.pallas_call(
            functools.partial(_fwd_single_kernel, scale=scale, causal=causal),
            name="flash_fwd",
            grid=(bh, seq_q // block_q),
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, block_k, d), lambda b, i: (b, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, block_k, d), lambda b, i: (b, 0, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, SUBLANES, block_q), lambda b, i: (b, 0, i),
                             memory_space=pltpu.VMEM),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, seq_q, d), q.dtype),
                jax.ShapeDtypeStruct((bh, SUBLANES, seq_q), jnp.float32),
            ],
            interpret=backend.pallas_interpret(),
        )(q, k, v)
        return out, lse

    grid = (bh, seq_q // block_q, seq_k // block_k)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal),
        name="flash_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, SUBLANES, block_q), lambda b, i, j: (b, 0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq_q, d), q.dtype),
            jax.ShapeDtypeStruct((bh, SUBLANES, seq_q), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
        ],
        interpret=backend.pallas_interpret(),
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_acc_ref, *, scale: float, causal: bool):
    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]
    qi = pl.program_id(1)
    j = pl.program_id(2)
    num_kv = pl.num_programs(2)
    q_start = qi * block_q
    k_start = j * block_k

    @pl.when(j == 0)
    def _init():
        dq_acc_ref[:] = jnp.zeros_like(dq_acc_ref)

    live = (not causal) or (k_start < q_start + block_q)

    @pl.when(jnp.asarray(live))
    def _compute():
        # bf16 MXU operands, fp32 stats/accumulator (see _fwd_kernel note)
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0, 0][:, None]  # stats replicated over sublane dim
        delta = delta_ref[0, 0][:, None]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(k.dtype)
        dq_acc_ref[:] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                             preferred_element_type=jnp.float32)

    @pl.when(j == num_kv - 1)
    def _finish():
        dq_ref[0] = (dq_acc_ref[:] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                    dk_acc_ref, dv_acc_ref, *, scale: float, causal: bool):
    block_k = k_ref.shape[1]
    block_q = q_ref.shape[1]
    ki = pl.program_id(1)
    i = pl.program_id(2)
    num_q = pl.num_programs(2)
    k_start = ki * block_k
    q_start = i * block_q

    @pl.when(i == 0)
    def _init():
        dk_acc_ref[:] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[:] = jnp.zeros_like(dv_acc_ref)

    # causal: this k block only receives grads from q rows >= k_start
    live = (not causal) or (q_start + block_q > k_start)

    @pl.when(jnp.asarray(live))
    def _compute():
        # bf16 MXU operands, fp32 stats/accumulators (see _fwd_kernel note)
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0, 0][:, None]
        delta = delta_ref[0, 0][:, None]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        p = jnp.exp(s - lse)  # (bq, bk)
        p_lo = p.astype(do.dtype)
        dv_acc_ref[:] += jax.lax.dot_general(p_lo, do, (((0,), (0,)), ((), ())),
                                             preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(q.dtype)
        dk_acc_ref[:] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                             preferred_element_type=jnp.float32)

    @pl.when(i == num_q - 1)
    def _finish():
        # q is unscaled in the s recompute, so dk picks up the scale here
        dk_ref[0] = (dk_acc_ref[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[:].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, out, lse, do, *, causal: bool, scale: float, block_q: int,
               block_k: int):
    bh, seq_q, d = q.shape
    _, seq_k, _ = k.shape
    block_q = min(block_q, seq_q)
    block_k = min(block_k, seq_k)

    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    # sublane-replicated stats layout (see SUBLANES note at the top)
    delta = jnp.broadcast_to(delta[:, None, :], (bh, SUBLANES, seq_q))

    grid_q = (bh, seq_q // block_q, seq_k // block_k)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal),
        name="flash_bwd_dq",
        grid=grid_q,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, SUBLANES, block_q), lambda b, i, j: (b, 0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, SUBLANES, block_q), lambda b, i, j: (b, 0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((bh, seq_q, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=backend.pallas_interpret(),
    )(q, k, v, do, lse, delta)

    grid_k = (bh, seq_k // block_k, seq_q // block_q)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal),
        name="flash_bwd_dkv",
        grid=grid_k,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, SUBLANES, block_q), lambda b, j, i: (b, 0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, SUBLANES, block_q), lambda b, j, i: (b, 0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq_k, d), k.dtype),
            jax.ShapeDtypeStruct((bh, seq_k, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=backend.pallas_interpret(),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public API with custom VJP
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention(q, k, v, causal, scale, block_q, block_k):
    out, _ = _flash_fwd(q, k, v, causal=causal, scale=scale, block_q=block_q,
                        block_k=block_k)
    return out


def _flash_attention_fwd(q, k, v, causal, scale, block_q, block_k):
    out, lse = _flash_fwd(q, k, v, causal=causal, scale=scale, block_q=block_q,
                          block_k=block_k)
    # Name the kernel outputs so activation-checkpoint policies can save
    # them: under the "dots" policy alone a rematerialized block re-runs the
    # whole forward kernel in the backward pass (pallas_call outputs are not
    # dot_general outputs). remat_policy="dots" composes
    # save_only_these_names(*ATTN_SAVE_NAMES) on top, which keeps (out, lse)
    # and skips the recompute; q/k/v re-derive cheaply from the saved qkv
    # projection dot.
    from jax.ad_checkpoint import checkpoint_name

    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return out, (q, k, v, out, lse)


def _flash_attention_bwd(causal, scale, block_q, block_k, res, do):
    q, k, v, out, lse = res
    dq, dk, dv = _flash_bwd(q, k, v, out, lse, do, causal=causal, scale=scale,
                            block_q=block_q, block_k=block_k)
    return dq, dk, dv


_flash_attention.defvjp(_flash_attention_fwd, _flash_attention_bwd)


def auto_block_sizes(seq: int) -> "tuple[int, int]":
    """(block_q, block_k) tuned on v5e with bf16 MXU operands (rounds 1-5
    sweeps, PERF.md §8):
    512x1024 wins at 1024-4096; the biggest tiles win at >=8192. Each block
    is shrunk (halved) until it divides ``seq`` — the kernel requires exact
    tiling, and an odd seq must not crash the auto path."""
    if seq >= 8192:
        bq, bk = 1024, 1024
    elif seq >= 1024:
        bq, bk = 512, 1024
    else:
        bq, bk = DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K
    while bq > 1 and seq % bq != 0:
        bq //= 2
    while bk > 1 and seq % bk != 0:
        bk //= 2
    return bq, bk


def use_flash_by_default(seq: int) -> bool:
    """Shape-based auto-selection: with bf16 MXU operands (round 5) the
    Pallas kernel beats XLA's fused attention from seq 1024 up on TPU
    (1.55x @1k, 1.33x @2k, 1.57x @4k, 1.91x @8k: rounds 1-5 runtime, a lead,
    PERF.md §8; the train cells run it at 1k); below that XLA wins. Off-TPU
    (interpret mode) it is only for tests. Shapes whose auto blocks would
    degenerate (seq with a tiny power-of-two factor) stay on XLA."""
    return backend.on_tpu() and seq >= 1024 \
        and min(auto_block_sizes(seq)) >= 128


def flash_attention(q, k, v, causal: bool = True, scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None):
    """Fused attention. q/k/v: (batch, seq, heads, head_dim) → same-shape out.

    ``scale`` defaults to 1/sqrt(head_dim); block sizes default to the
    seq-tuned table (``auto_block_sizes``).
    """
    b, t, h, d = q.shape
    _, s, _, _ = k.shape
    if causal and t != s:
        raise ValueError(
            f"causal flash attention requires seq_q == seq_k (got {t} vs {s});"
            " the mask assumes aligned positions. Use causal=False for"
            " cross-attention.")
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    # Derive block_q from t and block_k from s independently — the kernel
    # requires t % block_q == 0 and s % block_k == 0, and t != s (non-causal
    # cross-attention; causal masking assumes aligned q/k positions, so
    # causal t != s is not supported) would otherwise pick blocks tuned for
    # one length that fail to divide the other.
    auto_q, _ = auto_block_sizes(t)
    _, auto_k = auto_block_sizes(s)
    block_q = auto_q if block_q is None else block_q
    block_k = auto_k if block_k is None else block_k

    def kernel(q, k, v):
        b, _, h, _ = q.shape        # this shard's sequences and heads

        # (B, T, H, D) → (B*H, T, D)
        def to_bh(x, T):
            return x.transpose(0, 2, 1, 3).reshape(b * h, T, d)

        out = _flash_attention(to_bh(q, t), to_bh(k, s), to_bh(v, s), causal,
                               scale, block_q, block_k)
        return out.reshape(b, h, t, d).transpose(0, 2, 1, 3)

    dims = (backend.BATCH, None, backend.HEADS, None)
    return backend.shard_kernel(kernel, dims, q=(q, dims), k=(k, dims),
                                v=(v, dims))


def mha_reference(q, k, v, causal: bool = True, scale: Optional[float] = None):
    """Plain jnp attention for kernel equivalence tests (the analog of the
    reference's kernel-vs-PyTorch numerics tests, tests/unit/ops/transformer)."""
    b, t, h, d = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    s = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.tril(jnp.ones((t, k.shape[1]), dtype=bool))
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhts,bshd->bthd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)
