"""Fused flash attention (forward + backward) as Pallas TPU kernels.

TPU-native replacement for the reference's fused attention kernels — the
training transformer kernel's softmax/attention path
(csrc/transformer/softmax_kernels.cu + ds_transformer_cuda.cpp) and the
flash-style parity piece called out in SURVEY §2.2. The (T×T) score matrix
never reaches HBM: a grid step holds one (block_q, block_k) block of it in
VMEM, the MXU does the two matmuls, and when the keys span several blocks
running max/sum statistics rescale the accumulator from one to the next
(Flash-Attention-2 style; TPU grids run sequentially, innermost last, so VMEM
scratch carries them). A key block that holds every key (the tuned case up
to 2048) needs no running statistics and writes its rows out directly.

Causal work follows the diagonal inside a block. A block is walked in tiles
of ``ROWS`` query rows; a tile multiplies, exponentiates and accumulates only
the 128-key chunks that hold a key one of its rows can see, and only the
chunks the diagonal crosses take the iota / compare / select mask
(``_block_tiles``; ``visited_share`` counts them: 62.5 % of the square at
T = 1024 in the forward and dQ, 56 % in dK/dV, where the whole square was
computed before). Blocks above the diagonal are skipped on the grid and
fetch nothing. Non-causal calls visit everything.

Layout: (batch, seq, heads, head_dim) in, same out. Backward follows the
standard recompute scheme: store only ``lse`` (per-row log-sum-exp); dq and
dk/dv are two kernels gridding the opposite axes. Three ``pallas_call``s an
attention: ``flash_fwd``, ``flash_bwd_dq``, ``flash_bwd_dkv``.
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
# Query rows of a tile inside a block (my chip runs, PR 29, v5e, causal
# bf16[40,1024,64], ms a call at 128 / 256 / 512 rows). Forward 0.128 / 0.115 /
# 0.117 and dQ 0.135 / 0.135 / 0.153: the rows stream through the MXU against
# a key chunk held as weights, so 256 amortise it and 512 mask too much.
# dK/dV 0.170 / 0.193 / 0.215: its scores are transposed, the keys stream, and
# narrow tiles only skip more.
ROWS = 256
ROWS_DKV = 128
NEG_INF = -1e30
# checkpoint_name tags on attention-kernel outputs (see _flash_attention_fwd):
# every remat policy but the oracle keeps them, so the backward pass reads
# the forward kernel's (out, lse) instead of running it a second time
ATTN_SAVE_NAMES = ("flash_out", "flash_lse")
_DOTS = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
_KERNEL_OUT = jax.checkpoint_policies.save_only_these_names(*ATTN_SAVE_NAMES)
# remat_policy -> what a rematerialised block keeps besides its arguments.
# The name chooses among XLA's values; the kernel's residuals are not part
# of that choice: a ninth of what "dots" keeps, for the one operation of a
# block that runs at a third of its roofline (PERF.md section 6, PR 61).
REMAT_POLICIES = {
    # the kernel's (out, lse) alone: norms, matmuls and GELU run again
    "full": _KERNEL_OUT,
    # matmul outputs too: only elementwise operations run again
    "dots": jax.checkpoint_policies.save_from_both_policies(_DOTS, _KERNEL_OUT),
    # matmul outputs WITHOUT the kernel's: the arm in which the forward
    # kernel runs twice, kept as the tests' oracle and chosen by no cell
    "dots_plain": _DOTS,
}
# TPU vector layout: fp32 tiles are (8 sublanes, 128 lanes). Row statistics
# (lse, delta) are carried replicated across a size-8 sublane dim so their
# blocks satisfy the (8, 128) tiling rule; stats scratch is lane-width.
SUBLANES = 8
LANES = 128


# ---------------------------------------------------------------------------
# the walk: which tiles of a resident (block_q, block_k) block are visited
# ---------------------------------------------------------------------------
def _visible_chunks(rel: int, rows: int, n_chunks: int, chunk: int
                    ) -> "tuple[int, int]":
    """``(n_free, n_visit)`` for ``rows`` query rows whose first sits ``rel``
    positions after the first key of a block of ``n_chunks`` chunks: chunks
    ``[0, n_free)`` lie on or below the diagonal for every row and take no
    mask, ``[n_free, n_visit)`` are crossed by it, the rest hold no visible
    key and are not visited."""
    # chunk c is free iff its last key (c+1)*chunk - 1 <= rel, visible iff
    # its first key c*chunk <= rel + rows - 1
    clamp = lambda n: max(0, min(n, n_chunks))
    return clamp((rel + 1) // chunk), clamp((rel + rows - 1) // chunk + 1)


def _block_tiles(rel: Optional[int], block_q: int, block_k: int, rows: int
                 ) -> "list[tuple[int, int, list]]":
    """The tiles of one block: ``(r0, rows, pieces)`` for each tile of
    ``rows`` query rows (``rows`` halved until it divides the block) that
    sees a key, ``pieces`` its key ranges ``(lo, hi, ahead)``. A free piece
    has ``ahead`` None; in a crossed one entry (r, c) is visible iff
    ``r + ahead >= c``. ``rel`` is the block's first row less its first key,
    None for a block no mask touches. All of it is static."""
    rows = min(rows, block_q)
    while block_q % rows:
        rows //= 2
    chunk = min(LANES, block_k)
    n_chunks = block_k // chunk
    tiles = []
    for r0 in range(0, block_q, rows):
        n_free, n_visit = (n_chunks, n_chunks) if rel is None else \
            _visible_chunks(rel + r0, rows, n_chunks, chunk)
        free, crossed = n_free * chunk, n_visit * chunk
        pieces = [(0, free, None)] if free else []
        if crossed > free:
            pieces.append((free, crossed, rel + r0 - free))
        if pieces:
            tiles.append((r0, rows, pieces))
    return tiles


def _block_offsets(causal: bool, seq_q: int, seq_k: int, block_q: int,
                   block_k: int):
    """For each block of the grid that holds a visible score, its offset
    from the diagonal: first row less first key where the diagonal crosses
    it, None where no mask touches it (``_block_tiles``'s ``rel``)."""
    for q_start in range(0, seq_q, block_q):
        for k_start in range(0, seq_k, block_k):
            rel = q_start - k_start
            if not causal or rel >= block_k - 1:
                yield None
            elif rel + block_q > 0:
                yield rel


def visited_share(seq: int, block_q: Optional[int] = None,
                  block_k: Optional[int] = None, rows: int = ROWS,
                  causal: bool = True) -> float:
    """Share of the seq × seq square of scores that a self-attention call
    computes: the (rows × chunk) tiles its kernels visit. 1.0 non-causal;
    causal at 1024 with the tuned blocks 0.625 in tiles of 256 rows (forward,
    dQ) and 0.5625 in tiles of 128 (dK/dV); a causal call needs 0.5."""
    auto_q, auto_k = auto_block_sizes(seq)
    block_q = min(block_q or auto_q, seq)
    block_k = min(block_k or auto_k, seq)
    visited = sum(
        n * (hi - lo)
        for rel in _block_offsets(causal, seq, seq, block_q, block_k)
        for _, n, pieces in _block_tiles(rel, block_q, block_k, rows)
        for lo, hi, _ in pieces)
    return visited / (seq * seq)


def _walk_block(tile, offsets, q_start, k_start, block_q, block_k, rows):
    """Call ``tile(r0, rows, pieces)`` for every tile of the block this grid
    step holds (``_block_tiles``). A block's offset from the diagonal takes
    few values over the grid (``offsets``: the set of ``_block_offsets``);
    each gets its own straight-line body with static slices, and
    ``program_id`` picks one: the compiler schedules a body's matmuls and
    vector work across tile borders, which it does not across the
    iterations of a loop (PERF.md §6, PR 29: a ``fori_loop`` over chunks ran
    2-3.5x slower than computing the whole square)."""
    def body(rel):
        for args in _block_tiles(rel, block_q, block_k, rows):
            tile(*args)

    if offsets == {None}:       # non-causal: every block is whole
        return body(None)
    rel = q_start - k_start
    for x in sorted(offsets, key=lambda x: (x is None, x)):
        pl.when(rel >= block_k - 1 if x is None else rel == x)(
            functools.partial(body, x))


def _scores(a, b, scale=1.0, ahead=None, rows_dim=0):
    """a·bᵀ in fp32, scaled. With ``ahead`` the entries whose key lies after
    their row are masked: row r sees column c iff ``r + ahead >= c``, rows
    along ``rows_dim``."""
    s = jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if scale != 1.0:
        s = s * scale
    if ahead is not None:
        rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, rows_dim)
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - rows_dim)
        s = jnp.where(rows + ahead >= cols, s, NEG_INF)
    return s


def _matmul(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _kv_index(causal, block_q, block_k):
    """Index map of a key/value block on a (bh, q block, k block) grid. A
    causal grid step past the diagonal asks for the block it already holds,
    so the skipped step moves nothing."""
    if causal:      # the block of the query block's last row is the last seen
        return lambda b, i, j: (
            b, jnp.minimum(j, (i * block_q + block_q - 1) // block_k), 0)
    return lambda b, i, j: (b, j, 0)


# ---------------------------------------------------------------------------
# forward: grid (bh, q_blocks, kv_blocks); scratch carries (acc, m, l) from
# one key block to the next
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch, scale: float,
                offsets):
    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]
    j = pl.program_id(2)
    q_start = pl.program_id(1) * block_q
    k_start = j * block_k

    def finish(r0, rows, acc, m, l):
        l = jnp.maximum(l, 1e-30)
        o_ref[0, r0:r0 + rows, :] = (acc / l).astype(o_ref.dtype)
        lse_row = (m + jnp.log(l))[:, 0]
        lse_ref[0, :, r0:r0 + rows] = jnp.broadcast_to(
            lse_row[None, :], (lse_ref.shape[1], rows))

    if scratch:
        acc_ref, m_ref, l_ref = scratch

        @pl.when(j == 0)
        def _init():
            acc_ref[:] = jnp.zeros_like(acc_ref)
            m_ref[:] = jnp.full_like(m_ref, NEG_INF)
            l_ref[:] = jnp.zeros_like(l_ref)

    def tile(r0, rows, pieces):
        # MXU operands stay in the input dtype (bf16 in training): v5e runs
        # bf16xbf16->fp32 at full rate but fp32 matmuls at a fraction of it.
        # Accumulation/statistics are fp32 (preferred_element_type); p is
        # cast back to the input dtype for the PV dot (FA2 discipline).
        q = q_ref[0, r0:r0 + rows, :]
        ss = [_scores(q, k_ref[0, lo:hi, :], scale, ahead)
              for lo, hi, ahead in pieces]
        m = functools.reduce(
            jnp.maximum, [jnp.max(s, axis=1, keepdims=True) for s in ss])
        if scratch:
            m_prev = m_ref[r0:r0 + rows, :1]
            m = jnp.maximum(m_prev, m)
        ps = [jnp.exp(s - m) for s in ss]
        l = sum(jnp.sum(p, axis=1, keepdims=True) for p in ps)
        acc = sum(_matmul(p.astype(v_ref.dtype), v_ref[0, lo:hi, :])
                  for p, (lo, hi, _) in zip(ps, pieces))
        if not scratch:     # one key block holds every key: no running stats
            return finish(r0, rows, acc, m, l)
        alpha = jnp.exp(m_prev - m)
        l_ref[r0:r0 + rows, :] = jnp.broadcast_to(
            alpha * l_ref[r0:r0 + rows, :1] + l, (rows, LANES))
        acc_ref[r0:r0 + rows, :] = acc_ref[r0:r0 + rows, :] * alpha + acc
        m_ref[r0:r0 + rows, :] = jnp.broadcast_to(m, (rows, LANES))

    _walk_block(tile, offsets, q_start, k_start, block_q, block_k, ROWS)

    if scratch:
        @pl.when(j == pl.num_programs(2) - 1)
        def _finish():
            finish(0, block_q, acc_ref[:], m_ref[:, :1], l_ref[:, :1])


def _flash_fwd(q, k, v, *, causal: bool, scale: float, block_q: int, block_k: int):
    bh, seq_q, d = q.shape
    _, seq_k, _ = k.shape
    block_q = min(block_q, seq_q)
    block_k = min(block_k, seq_k)
    assert seq_q % block_q == 0 and seq_k % block_k == 0, \
        f"seq ({seq_q},{seq_k}) must be divisible by blocks ({block_q},{block_k})"

    q_spec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0),
                          memory_space=pltpu.VMEM)
    kv_spec = pl.BlockSpec((1, block_k, d), _kv_index(causal, block_q, block_k),
                           memory_space=pltpu.VMEM)
    out, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, scale=scale, offsets=frozenset(
                _block_offsets(causal, seq_q, seq_k, block_q, block_k))),
        name="flash_fwd",
        grid=(bh, seq_q // block_q, seq_k // block_k),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[
            q_spec,
            pl.BlockSpec((1, SUBLANES, block_q), lambda b, i, j: (b, 0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq_q, d), q.dtype),
            jax.ShapeDtypeStruct((bh, SUBLANES, seq_q), jnp.float32),
        ],
        scratch_shapes=[] if seq_k == block_k else [
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
                   dq_acc_ref, *, scale: float, offsets):
    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]
    j = pl.program_id(2)
    q_start = pl.program_id(1) * block_q
    k_start = j * block_k

    @pl.when(j == 0)
    def _init():
        dq_acc_ref[:] = jnp.zeros_like(dq_acc_ref)

    def tile(r0, rows, pieces):
        # bf16 MXU operands, fp32 stats/accumulator (see _fwd_kernel note)
        q = q_ref[0, r0:r0 + rows, :]
        do = do_ref[0, r0:r0 + rows, :]
        lse = lse_ref[0, 0, r0:r0 + rows][:, None]  # stats replicated over sublanes
        delta = delta_ref[0, 0, r0:r0 + rows][:, None]
        dq = 0.
        for lo, hi, ahead in pieces:
            k = k_ref[0, lo:hi, :]
            s = _scores(q, k, scale, ahead)
            p = jnp.exp(s - lse)
            dp = _scores(do, v_ref[0, lo:hi, :])
            ds = (p * (dp - delta)).astype(k.dtype)
            dq += _matmul(ds, k)
        dq_acc_ref[r0:r0 + rows, :] += dq

    _walk_block(tile, offsets, q_start, k_start, block_q, block_k, ROWS)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        dq_ref[0] = (dq_acc_ref[:] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                    dk_acc_ref, dv_acc_ref, *, scale: float, offsets):
    """One key block's dK and dV, a query block a grid step. The scores are
    computed transposed, (keys, rows): the two accumulating matmuls then
    contract p and ds along the lanes they lie in, and lse / delta broadcast
    from the lane-major rows they are stored in."""
    block_k = k_ref.shape[1]
    block_q = q_ref.shape[1]
    i = pl.program_id(2)
    k_start = pl.program_id(1) * block_k
    q_start = i * block_q

    @pl.when(i == 0)
    def _init():
        dk_acc_ref[:] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[:] = jnp.zeros_like(dv_acc_ref)

    def tile(r0, rows, pieces):
        # bf16 MXU operands, fp32 stats/accumulators (see _fwd_kernel note)
        q = q_ref[0, r0:r0 + rows, :]
        do = do_ref[0, r0:r0 + rows, :]
        lse = lse_ref[0, :1, r0:r0 + rows]          # (1, rows)
        delta = delta_ref[0, :1, r0:r0 + rows]
        for lo, hi, ahead in pieces:
            st = _scores(k_ref[0, lo:hi, :], q, scale, ahead, rows_dim=1)
            pt = jnp.exp(st - lse)                  # (keys, rows)
            dv_acc_ref[lo:hi, :] += _matmul(pt.astype(do.dtype), do)
            dpt = _scores(v_ref[0, lo:hi, :], do)
            dst = (pt * (dpt - delta)).astype(q.dtype)
            dk_acc_ref[lo:hi, :] += _matmul(dst, q)

    _walk_block(tile, offsets, q_start, k_start, block_q, block_k, ROWS_DKV)

    @pl.when(i == pl.num_programs(2) - 1)
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
    offsets = frozenset(_block_offsets(causal, seq_q, seq_k, block_q, block_k))

    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    # sublane-replicated stats layout (see SUBLANES note at the top)
    delta = jnp.broadcast_to(delta[:, None, :], (bh, SUBLANES, seq_q))

    q_spec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0),
                          memory_space=pltpu.VMEM)
    kv_spec = pl.BlockSpec((1, block_k, d), _kv_index(causal, block_q, block_k),
                           memory_space=pltpu.VMEM)
    stat_spec = pl.BlockSpec((1, SUBLANES, block_q), lambda b, i, j: (b, 0, i),
                             memory_space=pltpu.VMEM)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, offsets=offsets),
        name="flash_bwd_dq",
        grid=(bh, seq_q // block_q, seq_k // block_k),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, stat_spec, stat_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((bh, seq_q, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=backend.pallas_interpret(),
    )(q, k, v, do, lse, delta)

    # the same blocks on a (bh, k block, q block) grid: a query block before
    # the key block sees none of it, so it asks for the first that does
    def first_visible(j, i):
        return jnp.maximum(i, (j * block_k) // block_q) if causal else i

    q_spec = pl.BlockSpec((1, block_q, d),
                          lambda b, j, i: (b, first_visible(j, i), 0),
                          memory_space=pltpu.VMEM)
    kv_spec = pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0),
                           memory_space=pltpu.VMEM)
    stat_spec = pl.BlockSpec((1, SUBLANES, block_q),
                             lambda b, j, i: (b, 0, first_visible(j, i)),
                             memory_space=pltpu.VMEM)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, offsets=offsets),
        name="flash_bwd_dkv",
        grid=(bh, seq_k // block_k, seq_q // block_q),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, stat_spec, stat_spec],
        out_specs=[kv_spec, kv_spec],
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
    # Name the kernel outputs so that a remat policy can keep them (a
    # pallas_call's outputs are no dot_general's, so "dots" alone runs the
    # whole forward kernel again in the backward pass): REMAT_POLICIES' "full"
    # and "dots" do, and q/k/v are re-derived by the qkv projection.
    from jax.ad_checkpoint import checkpoint_name

    out = checkpoint_name(out, "flash_out")
    # what is kept of lse is its one distinct row (the kernel writes it over
    # SUBLANES, the backward kernels read sublane 0): 1/8 of the bytes a
    # layer, which is what lets GPT-2 large's step keep them on one chip
    # without XLA's own rematerialisation setting in (PERF.md section 6, PR 61)
    lse = jnp.broadcast_to(checkpoint_name(lse[:, :1], "flash_lse"), lse.shape)
    return out, (q, k, v, out, lse)


def _flash_attention_bwd(causal, scale, block_q, block_k, res, do):
    q, k, v, out, lse = res
    dq, dk, dv = _flash_bwd(q, k, v, out, lse, do, causal=causal, scale=scale,
                            block_q=block_q, block_k=block_k)
    return dq, dk, dv


_flash_attention.defvjp(_flash_attention_fwd, _flash_attention_bwd)


def auto_block_sizes(seq: int) -> "tuple[int, int]":
    """(block_q, block_k), swept on the v5e with bf16 operands (my chip runs,
    PR 29: forward + dQ + dK/dV, ms for one causal call, each kernel at its
    own ROWS; PERF.md §6 has the kernels apart). ``parent`` is the kernels
    before PR 29 at their blocks (512x1024 below 8192, 1024x1024 from there),
    computing every block the diagonal touches in full:

    ====================  ======  =========  =========  =========
    shape                 parent  1024x1024  2048x2048  1024x4096
    ====================  ======  =========  =========  =========
    bf16[40,1024,64]      0.622   0.420
    bf16[200,1024,64]     3.195   2.147
    bf16[32,2048,128]     1.592   1.356      1.022
    bf16[10,4096,64]      1.614   1.455      1.248      1.440
    bf16[4,8192,64]       2.144   2.135      1.843
    ====================  ======  =========  =========  =========

    One block for the whole sequence wins up to 2048: no running statistics,
    one grid step a head, and every tile of the diagonal is static. Beyond,
    2048x2048 blocks (a whole-sequence key block is refused VMEM at 8192 and
    slows dK/dV at 4096). Each block is shrunk (halved) until it divides
    ``seq`` — the kernel requires exact tiling, and an odd seq must not
    crash the auto path."""
    if seq >= 1024:
        bq, bk = 2048, 2048
    else:
        bq, bk = DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K
    while bq > 1 and seq % bq != 0:
        bq //= 2
    while bk > 1 and seq % bk != 0:
        bk //= 2
    return bq, bk


def use_flash_by_default(seq: int) -> bool:
    """Shape-based auto-selection: on from seq 1024 up on TPU, where both
    train cells run it (PERF.md §5; the crossover against XLA's fused
    attention was found on another runtime, before the kernels followed the
    diagonal, at 1.55x @1k: PERF.md §8, a lead; it has not been re-measured
    and can only have moved down). Off-TPU (interpret mode) it is only for
    tests. Shapes whose auto blocks would degenerate (seq with a tiny
    power-of-two factor) stay on XLA."""
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
