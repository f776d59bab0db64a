"""The absorbed read of latent attention over the page pool
(``mla_decode``): every head's query rows against ONE cached row a token.

Latent attention (``models.transformer_lm.LatentAttention``) caches, a
token a layer, the row ``[c ; k_r]``: the normed latent (``rank`` wide)
and the rotary key the heads share. With the key half of ``W_kvb``
absorbed into the query (``q~_h = W_kvb,h^K^T q_n,h``) a head scores the
cached row as it stands and reads its values off the row's first ``rank``
lanes::

    s_h(i, j) = [q~_h(i) ; q_r,h(i)] . [c_j ; k_r,j] * scale
    ctx_h(i)  = sum_j softmax_j(s_h)(i, j) c_j              (rank wide)

which is multi-query attention with one "KV head" whose key is the row
and whose value is a prefix of the same row. The caller applies the value
half of ``W_kvb`` to ``ctx``; the K/V of a cached token are never rebuilt.

**The pool's leaf** is ``(L, P, W, lanes)``, ``W = rank + rope`` (576 at
the served size), a page ``W`` sublanes of ``page_size`` positions in
whole 128-lane tiles: ``paged_attention.paged_write`` writes it (its
scale-leaf form: one "head" of ``W`` stored rows), and this kernel takes
it whole and finds a page by ``(layer, page)`` on scalar prefetch, as
``paged_decode`` does. No slice of the leaf is made.

**Grid.** One step is one live page of one slot
(``paged_attention.live_pages``: slots that map nothing are no step, a
table that maps nothing is a grid of none). A page comes in ONCE a step
and serves both products: ``q (rows, W) . page (W, page_size)``, the
online softmax, ``p (rows, page_size) . page[:rank]^T``. The query block
holds every head's rows of the slot, row ``t * H + h``: a decode step's 16
heads are one bf16 sublane tile (``mla_decode``), a chunk's 128 tokens
2,048 rows of one call (``mla_chunk``: the same kernel under the name a
trace tells it by; its blocks, accumulator and scores take ~22 MB, hence
``vmem_limit_bytes``). More than :data:`MAX_ROWS` rows go in several calls,
each ``MAX_ROWS // H`` positions further on. The output is aliased onto a
zeroed operand: the rows
of a slot that has no step come back zero, finite and defined, and are not
attention output (the served programs carry them on like any padding row).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import backend
from .flash_attention import LANES, NEG_INF
from .paged_attention import live_pages

__all__ = ["latent_attention", "MAX_ROWS"]

# query rows (tokens x heads) of one call: with W = 576 and rank = 512 the
# query, zero and output blocks (double-buffered), the float32 accumulator,
# the softmax statistics and a step's scores of 2,048 rows take ~22 MB
MAX_ROWS = 2048
VMEM_LIMIT_BYTES = 48 * 2 ** 20
ROW_TILE = 16       # a bf16 sublane tile


def _mla_kernel(slot_ref, entry_ref, page_ref, live_ref, start_ref,
                layer_ref, q_ref, c_ref, zero_ref, o_ref, acc_ref, m_ref,
                l_ref, *, page_size: int, rank: int, heads: int,
                scale: float):
    # scalar prefetch: the work list of live_pages, (B,) starts and the
    # (1,) layer (page_ref and layer_ref are read by the index maps).
    # One step folds one page of one slot into the online softmax of all
    # its query rows; row r is head r % heads of token r // heads, which
    # sees cache positions <= start + r // heads.
    w = pl.program_id(0)
    entry, slot = entry_ref[w], slot_ref[w]
    start = start_ref[slot]

    @pl.when(entry == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    q = q_ref[0]                                          # (rows, W)
    page = c_ref[0, 0][:, :page_size]                     # (W, page_size)
    s = jax.lax.dot_general(q, page, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    rows = q.shape[0]
    pos = entry * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (rows, page_size), 1)
    token = jax.lax.broadcasted_iota(jnp.int32, (rows, page_size), 0) \
        // heads
    s = jnp.where(pos <= start + token, s, NEG_INF)
    m_prev, l_prev = m_ref[:, :1], l_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[:] = jnp.broadcast_to(
        alpha * l_prev + jnp.sum(p, axis=1, keepdims=True), l_ref.shape)
    # the values are the row's first ``rank`` stored rows
    acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
        p.astype(page.dtype), page[:rank], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(entry == live_ref[slot] - 1)
    def _finish():
        o_ref[0] = (acc_ref[:] / jnp.maximum(l_ref[:, :1], 1e-30)
                    ).astype(o_ref.dtype)


def latent_attention(q: jax.Array, pages: jax.Array, table: jax.Array,
                     starts: jax.Array, *, layer=None, rank: int,
                     scale: float, page_size: Optional[int] = None
                     ) -> jax.Array:
    """``ctx`` (B, T, H, rank): the absorbed read.

    Args:
      q: (B, T, H, W) absorbed queries ``[q~ ; q_r]``: one row a slot for a
        decode step, a chunk's width for the one slot it runs. Row ``t``
        of slot ``b`` sees cache positions ``[0, starts[b] + t]`` (its own
        row included: the caller has written this step's rows).
      pages: (L, P, W, lanes) the pool's stacked leaf, read at ``layer``.
      table: (B, pages_per_slot) int32, ``P`` the unmapped sentinel.
      starts: (B,) cache length before this step's tokens.
      layer: int32 scalar (traced); ``None`` reads layer 0.
      rank: the row's leading lanes that are the values.
      page_size: positions a page holds, in its first lanes; ``None``
        when the leaf's minor dimension is the page size itself.
    Rows of a slot that maps no page come back zero and are not attention
    output."""
    B, T, H, W = q.shape
    L, P, Wc, lanes = pages.shape
    ps = lanes if page_size is None else page_size
    assert Wc == W and rank <= W and ps <= lanes, (q.shape, pages.shape)
    tokens = max(MAX_ROWS // H, 1)
    if T > tokens:
        # MAX_ROWS rows a call: the later calls' rows stand ``tokens``
        # positions further on
        return jnp.concatenate(
            [latent_attention(q[:, j:j + tokens], pages, table, starts + j,
                              layer=layer, rank=rank, scale=scale,
                              page_size=page_size)
             for j in range(0, T, tokens)], axis=1)
    starts = jnp.broadcast_to(jnp.asarray(starts, jnp.int32), (B,))
    table = jnp.asarray(table, jnp.int32)
    layer = jnp.zeros((1,), jnp.int32) if layer is None \
        else jnp.asarray(layer, jnp.int32).reshape(1)
    rows = -(-T * H // ROW_TILE) * ROW_TILE
    q3 = q.astype(pages.dtype).reshape(B, T * H, W)
    if rows > T * H:
        # (dead rows see a wider causal window and are sliced off)
        q3 = jnp.pad(q3, ((0, 0), (0, rows - T * H), (0, 0)))
    slot_of, entry_of, page_of, live, total = live_pages(
        starts, table, T, ps, P)

    def row_block(width):
        return pl.BlockSpec((1, rows, width),
                            lambda w, slot_ref, *_: (slot_ref[w], 0, 0))

    def page_index(w, slot_ref, entry_ref, page_ref, live_ref, start_ref,
                   layer_ref):
        return (layer_ref[0], page_ref[w], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(total,),
        in_specs=[row_block(W), pl.BlockSpec((1, 1, W, lanes), page_index),
                  row_block(rank)],
        out_specs=row_block(rank),
        scratch_shapes=[pltpu.VMEM((rows, rank), jnp.float32),
                        pltpu.VMEM((rows, LANES), jnp.float32),
                        pltpu.VMEM((rows, LANES), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_mla_kernel, page_size=ps, rank=rank, heads=H,
                          scale=scale),
        name="mla_decode" if T == 1 else "mla_chunk",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, rows, rank), pages.dtype),
        # a slot that is not in the work list has no step: its output
        # block is never visited and keeps the zeros it is aliased onto
        input_output_aliases={8: 0},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=backend.pallas_interpret(),
    )(slot_of, entry_of, page_of, live, starts, layer, q3, pages,
      jnp.zeros((B, rows, rank), pages.dtype))
    return out[:, :T * H].reshape(B, T, H, rank).astype(q.dtype)
