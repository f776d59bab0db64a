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
it whole and finds a page by ``(layer, page)`` from the table on scalar
prefetch. No slice of the leaf is made.

**Grid.** One step is one BLOCK of one slot: ``G`` of its live pages in
table order, the last block of a slot partial (:func:`page_blocks`, built
from ``paged_attention.live_pages``' ``live``: slots that map nothing are
no step, a table that maps nothing is a grid of none). ``G`` comes from
the call's shapes (:func:`pages_a_step`): 16 for a decode step's 16 rows,
1 for a chunk. The leaf stays in HBM and the kernel fetches the pages
itself (``make_async_copy`` into ``2 x G`` page buffers): a step starts
the NEXT block's copies, of this slot or of the next one in the list,
waits for its own and folds its pages one after another with the update
of one page a step, so the result is that kernel's bit for bit
(``tests/unit/ops/test_latent_attention.py``; on the chip too, PERF.md
section 6, PR 51). Pages past a slot's ``live`` are neither copied nor
folded. A page comes in ONCE and serves both products: ``q (rows, W) .
page (W, page_size)``, the online softmax, ``p (rows, page_size) .
page[:rank]^T``. What the blocks buy is not the grid step (0.05 us) but
the order inside it: a page's scores, statistics and values hang on each
other, and with one page a step the MXU waited through every link (0.39
us a page beside 0.18 of bytes); a block runs its pages' scores, then
their statistics, then their values, and the read follows its copies
(0.19 us a page).

The query block
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
from .paged_attention import _vmem_tile_bytes as _tile_bytes, live_pages

__all__ = ["latent_attention", "MAX_ROWS"]

# query rows (tokens x heads) of one call: with W = 576 and rank = 512 the
# query, zero and output blocks (double-buffered), the float32 accumulator,
# the softmax statistics and a step's scores of 2,048 rows take ~22 MB
MAX_ROWS = 2048
VMEM_LIMIT_BYTES = 48 * 2 ** 20
ROW_TILE = 16       # a bf16 sublane tile
# a step's block: 16 pages of a decode step's 16 rows, halved with every
# doubling of the rows (the scores of a block's pages are live at once:
# 16 pages x 32 rows were slower than 8 on the chip), one page from 256
# rows on, where a page's products outlast everything a step costs
MAX_PAGES = 16
STEP_ROWS = 256


def pages_a_step(rows: int, W: int, rank: int, lanes: int, dtype) -> int:
    """``G``, the pages ONE grid step folds, from the call's static shapes
    alone (no option, in the manner of ``paged_attention.plan_grid``): a
    power of two (a block is folded in runs of ``G``, ``G / 2``, ... 1
    pages), at most :data:`MAX_PAGES`, at most ``STEP_ROWS // rows``, and
    no more than fit, double-buffered, beside the blocks and the scratch
    of ``rows`` query rows under :data:`VMEM_LIMIT_BYTES`. What the chip
    showed best (PERF.md section 6, PR 51): 16 for a decode step of 16
    heads, 8 for one of 32, 1 for a chunk's 2,048 rows."""
    fixed = (2 * _tile_bytes(rows, W, dtype)             # q, two buffers
             + 4 * _tile_bytes(rows, rank, dtype)        # zero and out
             + _tile_bytes(rows, rank, jnp.float32)      # acc
             + 4 * _tile_bytes(rows, LANES, jnp.float32))  # m, l, scores
    room = (VMEM_LIMIT_BYTES - fixed) // (2 * _tile_bytes(W, lanes, dtype))
    g = int(max(1, min(MAX_PAGES, STEP_ROWS // rows, room)))
    return 1 << (g.bit_length() - 1)


def call_rows(T: int, H: int) -> int:
    """The query rows of ONE call for ``T`` tokens of ``H`` heads a slot:
    at most ``MAX_ROWS // H`` tokens' heads, in whole sublane tiles."""
    return -(-min(T, max(MAX_ROWS // H, 1)) * H // ROW_TILE) * ROW_TILE


def page_blocks(live: jax.Array, G: int, pages_per_slot: int):
    """The work list in blocks of ``G`` pages: ``(slot_of, entry_of,
    total)``. Step ``w < total`` folds table entries ``[entry_of[w],
    min(entry_of[w] + G, live[slot_of[w]]))`` of slot ``slot_of[w]``, slot
    by slot in table order: ``ceil(live / G)`` steps a slot, the last one
    partial, none for a slot of no live page (``live`` as
    :func:`~.paged_attention.live_pages` gives it). The lists are
    ``B * ceil(pages_per_slot / G)`` long; entries from ``total`` on are in
    range and never run."""
    B = live.shape[0]
    blocks = (live + G - 1) // G
    ends = jnp.cumsum(blocks)
    w = jnp.arange(B * -(-pages_per_slot // G), dtype=jnp.int32)
    slot_of = jnp.minimum(
        jnp.searchsorted(ends, w, side="right", method="compare_all"),
        B - 1).astype(jnp.int32)
    entry_of = jnp.clip((w - (ends - blocks)[slot_of]) * G, 0,
                        pages_per_slot - 1).astype(jnp.int32)
    return slot_of, entry_of, ends[-1]


def _mla_kernel(slot_ref, entry_ref, table_ref, live_ref, start_ref,
                layer_ref, q_ref, c_hbm, zero_ref, o_ref, buf, sems, acc_ref,
                m_ref, l_ref, *, G: int, pages_per_slot: int, page_size: int,
                rank: int, heads: int, scale: float):
    # scalar prefetch: the work list of page_blocks, the (B * pages_per_slot,)
    # table, (B,) live pages and starts and the (1,) layer. One step folds
    # one block of one slot into the online softmax of all its query rows;
    # row r is head r % heads of token r // heads, which sees cache
    # positions <= start + r // heads. The pages come by the kernel's own
    # copies, a block ahead: buf[w % 2] holds this step's.
    w = pl.program_id(0)
    slot, first = slot_ref[w], entry_ref[w]
    start = start_ref[slot]

    def pages_of(at):
        return jnp.minimum(live_ref[slot_ref[at]] - entry_ref[at], G)

    def copy(at, i):
        page = table_ref[slot_ref[at] * pages_per_slot + entry_ref[at] + i]
        return pltpu.make_async_copy(
            c_hbm.at[layer_ref[0], page], buf.at[at % 2, i], sems.at[at % 2])

    def each_page(at, act):
        """``act`` on the copy of every page of block ``at``."""
        def one(i, carry):
            act(copy(at, i))
            return carry
        jax.lax.fori_loop(0, pages_of(at), one, 0)

    @pl.when(w == 0)
    def _prime():
        each_page(w, lambda c: c.start())

    @pl.when(w + 1 < pl.num_programs(0))
    def _ahead():
        each_page(w + 1, lambda c: c.start())

    @pl.when(first == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    each_page(w, lambda c: c.wait())
    q = q_ref[0]                                          # (rows, W)
    rows = q.shape[0]

    def fold(entries):
        """The online-softmax update of one page a step for the pages
        ``entries`` of the block, a page after another in that order.
        Every page's scores first, then the statistics, then the values:
        a page's three parts hang on each other, and the MXU stood still
        through the statistics while they were written page by page
        (PERF.md section 6, PR 51)."""
        pages = [buf[w % 2, i][:, :page_size] for i in entries]  # (W, ps)
        scores = []
        for i, page in zip(entries, pages):
            s = jax.lax.dot_general(q, page, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            # (made anew a page: held across a step, 2,048 rows of
            # positions and limits cost a chunk's call 4 % on the chip)
            pos = (first + i) * page_size + jax.lax.broadcasted_iota(
                jnp.int32, (rows, page_size), 1)
            token = jax.lax.broadcasted_iota(
                jnp.int32, (rows, page_size), 0) // heads
            scores.append(jnp.where(pos <= start + token, s, NEG_INF))
        m_prev, l_prev = m_ref[:, :1], l_ref[:, :1]
        weights = []
        for s in scores:
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_prev = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            m_prev = m_new
            weights.append((p, alpha))
        acc = acc_ref[:]
        for (p, alpha), page in zip(weights, pages):
            # the values are the row's first ``rank`` stored rows
            acc = acc * alpha + jax.lax.dot_general(
                p.astype(page.dtype), page[:rank], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_prev, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_prev, l_ref.shape)
        acc_ref[:] = acc

    # the block's pages in runs of G, G / 2, ... 1 as the bits of its count
    # say, no branch inside a run
    count, run = pages_of(w), G
    while run:
        def _run(run=run):
            done = count // (2 * run) * (2 * run)
            fold([done + i for i in range(run)])
        if G == 1:
            _run()      # (behind a branch a chunk's call was 8 % slower)
        else:
            pl.when(count & run != 0)(_run)
        run //= 2

    @pl.when(first + count == live_ref[slot])
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
    rows = call_rows(T, H)
    q3 = q.astype(pages.dtype).reshape(B, T * H, W)
    if rows > T * H:
        # (dead rows see a wider causal window and are sliced off)
        q3 = jnp.pad(q3, ((0, 0), (0, rows - T * H), (0, 0)))
    per_slot = table.shape[1]
    live = live_pages(starts, table, T, ps, P)[3]
    G = pages_a_step(rows, W, rank, lanes, pages.dtype)
    slot_of, entry_of, total = page_blocks(live, G, per_slot)

    def row_block(width):
        return pl.BlockSpec((1, rows, width),
                            lambda w, slot_ref, *_: (slot_ref[w], 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(total,),
        in_specs=[row_block(W), pl.BlockSpec(memory_space=pl.ANY),
                  row_block(rank)],
        out_specs=row_block(rank),
        scratch_shapes=[pltpu.VMEM((2, G, W, lanes), pages.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.VMEM((rows, rank), jnp.float32),
                        pltpu.VMEM((rows, LANES), jnp.float32),
                        pltpu.VMEM((rows, LANES), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_mla_kernel, G=G, pages_per_slot=per_slot,
                          page_size=ps, rank=rank, heads=H, scale=scale),
        name="mla_decode" if T == 1 else "mla_chunk",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, rows, rank), pages.dtype),
        # a slot that is not in the work list has no step: its output
        # block is never visited and keeps the zeros it is aliased onto
        input_output_aliases={8: 0},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=backend.pallas_interpret(),
    )(slot_of, entry_of, table.reshape(-1), live, starts, layer, q3, pages,
      jnp.zeros((B, rows, rank), pages.dtype))
    return out[:, :T * H].reshape(B, T, H, rank).astype(q.dtype)
