"""The page read of learned block-sparse attention: what a query reads once
``sparse_index.choose_blocks`` has chosen its blocks, as Pallas kernels over
work lists, so that a read's time follows the tokens the equations read (at
most ``topk x block_size + window_size`` a query a KV head) and not the
context. A token at ``pos`` of a page is seen by the row of a query at
``hi`` iff ``pos <= hi and (its block's bit is set or pos > lo(hi))``, ``lo``
being the window's edge (``hi - window_size``; under ``dense_len`` the start
of the block that edge lies in, all of which a dense query reads): a chosen
block whole, the window's tokens by position; a page's chosen blocks ride
scalar prefetch as a bit a block, and no mask over a slot's positions
exists. The two reads have a kernel each: decode rows want PAGES in runs, a
chunk wants QUERIES in groups, and one body would serve neither.

**Decode rows** (:func:`read_rows`; ``_rows_kernel``, custom call
``sparse_read``). A (row, KV head) is one entry: its ``rep`` query heads,
one packed sublane tile at 16, all at the row's position. Its pages are
the ones THAT (row, KV head) chose plus its window's, ascending
(:func:`rows_plan`): at most ``topk + window_size / page_size + 2``, every
page up to its position under ``dense_len`` (the bits are then all set).
Nothing is unioned over the KV heads or the rows. A grid step is a BLOCK of
``G`` consecutive pages of one entry's list (``latent_attention.
page_blocks`` over the plan's counts: ``ceil(count / G)`` steps an entry,
the last partial, none for a row that does not run; ``G`` from the call's
shapes, :func:`pages_a_step`: 16 at the served widths). The pool's stacked
K and V leaves stay in HBM and the kernel fetches a page of its KV head
itself, a whole ``(Dc, lanes)`` tile (``make_async_copy`` into ``2 x G``
page buffers each for K and V): a step starts the NEXT block's copies, of
this entry or of the next one in the list, waits for its own, and folds the
block as ONE run: every page's scores, ONE set of row statistics over them
(a maximum and a sum a block, not a page), then every page's values into
the entry's accumulator, which lives in scratch and is written, normalised,
at the entry's last block. Pages past an entry's count are not copied;
their places in a last block are masked and weigh nothing. One page a step
through a ``BlockSpec`` (PR 56) was 0.46 us a page where a page's bytes (K
32 KB + V 32 KB) are 0.08: the page's scores, statistics and values hang on
each other, and the MXU waited through every link; a block of 16 reads at
its copies' own time, 0.098 us a page (PERF.md section 6, PR 57).

**A chunk** (:func:`read_chunk`; ``_read_kernel`` over :func:`chunk_plan`'s
steps, custom call ``sparse_read_chunk``): ``T`` queries of one slot, each
with its own ~``topk`` blocks. The call's queries lie in VMEM whole, a
(query, KV head) one entry of ``rep`` rows, and so do their online-softmax
accumulators (sum, row maxima, row sums): the result. A step is one page of
one KV head under a TILE of ``tq`` entries NAMED by the step (``qidx``): the
tile's rows are gathered from VMEM, folded against the page
(``paged_decode``'s update), and put back. The work list rides scalar
prefetch (physical page, KV head, table entry, the page's chosen blocks,
the tile's first position, the tile's entries), the grid is as long as the
list (known on the device alone), and the K/V blocks are ``(Dc,
page_size)`` of the pool's stacked leaf, read in place. A tile's queries
stand at consecutive positions from the step's ``base`` (a step that reads
a chosen block whole has a base past every position). Read a query at a
time the MXU would hold one page for 16 rows, 512 times; read as a union
the chunk visits every page before it, dense under a mask, and its time
grows with its position (what PR 56's first form did: 0 to ~30 ms a chunk
step from position 0 to 32k, which made the cell's 90th gap a matter of
where the window's chunks lay). Here the BLOCKS ARE THE GROUPS, as experts
are a routed FFN's: the queries that chose a block are that block's tiles,
``tq`` of them a step, whatever their places in the chunk; what every query
reads by position, its window, is steps of ``tq`` CONSECUTIVE queries over
the window's pages, in the same call. The (query, chosen block) pairs are
``T x KV x topk`` at most wherever the chunk stands, so a chunk's read does
not depend on its position past ``dense_len``."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import backend
from .flash_attention import LANES, NEG_INF
from .latent_attention import page_blocks
from .paged_attention import _vmem_tile_bytes as _tile_bytes
from .sparse_index import SparseSizes

__all__ = ["sparse_read", "read_rows", "read_chunk", "rows_plan",
           "chunk_plan", "pages_a_step"]

WHOLE = 2 ** 30         # a tile's base where its step reads a block whole
CHUNK_TILE = 16         # queries a step of a chunk
# a decode step's block of pages (pages_a_step)
MAX_PAGES = 16
STEP_ROWS = 256
ROWS_VMEM_BYTES = 8 * 2 ** 20
VMEM_LIMIT_BYTES = 64 * 2 ** 20     # (a chunk's queries and accumulators)


def _read_kernel(page_ref, kv_ref, entry_ref, bits_ref, base_ref, qidx_ref,
                 layer_ref, q_ref, k_ref, v_ref, o_ref, stat_ref, *,
                 tq: int, page_size: int, sizes: SparseSizes, scale: float):
    """A chunk's step (since PR 57 :func:`read_chunk` is the one caller:
    the decode rows have ``_rows_kernel``)."""
    # the first seven are scalar-prefetch SMEM lists (page_ref, kv_ref and
    # layer_ref are read by the index maps only); q_ref (N + 1, R, D),
    # o_ref (N + 1, R, D) float32 and stat_ref (N + 1, R, LANES) float32
    # (lane 0 the row maxima, lane 1 the row sums) are whole in VMEM, entry
    # N the one an empty place of a tile names
    w = pl.program_id(0)
    R = q_ref.shape[1]

    @pl.when(w == 0)
    def _init():
        o_ref[:] = jnp.zeros_like(o_ref)
        lane = jax.lax.broadcasted_iota(jnp.int32, stat_ref.shape, 2)
        stat_ref[:] = jnp.where(lane == 0, NEG_INF, 0.0)

    at = [qidx_ref[w * tq + i] for i in range(tq)]

    def rows(ref):
        return jnp.concatenate([ref[i] for i in at], axis=0) if tq > 1 \
            else ref[at[0]]

    k = k_ref[0, 0, 0][:, :page_size]                   # (Dc, page_size)
    v = v_ref[0, 0, 0][:, :page_size]
    s = jax.lax.dot_general(rows(q_ref), k, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, page_size), 1)
    pos = entry_ref[w] * page_size + lane
    chosen = (bits_ref[w] >> (lane // sizes.block_size)) & 1
    hi = base_ref[w] + jax.lax.broadcasted_iota(
        jnp.int32, (tq * R, 1), 0) // R
    edge = hi - sizes.window_size
    lo = jnp.where(hi + 1 < sizes.dense_len,
                   (edge + 1) // sizes.block_size * sizes.block_size - 1,
                   edge)
    seen = jnp.logical_and(pos <= hi, jnp.logical_or(chosen > 0, pos > lo))
    s = jnp.where(seen, s, NEG_INF)
    stat = rows(stat_ref)                               # (tq R, LANES)
    m_prev, l_prev = stat[:, :1], stat[:, 1:2]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    # (a row none of whose tokens is seen adds nothing, where exp(0) would)
    p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    o_new = rows(o_ref) * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    stat_new = jnp.where(
        jax.lax.broadcasted_iota(jnp.int32, stat.shape, 1) == 0, m_new,
        l_new)
    for i in range(tq):
        o_ref[at[i]] = o_new[i * R:(i + 1) * R]
        stat_ref[at[i]] = stat_new[i * R:(i + 1) * R]


def sparse_read(q, k_pages, v_pages, layer, steps, total, *, tq: int,
                page_size: int, sizes: SparseSizes, scale: float,
                name: str = "sparse_read"):
    """The kernel over a work list. ``q`` (N, rep, D): the call's (query,
    KV head) entries; ``k_pages`` / ``v_pages`` the pool's stacked leaves
    (L, P, KV, Dc, lanes); ``steps = (page, kv, entry, bits, base, qidx)``:
    int32 lists of one static length S (``qidx`` (S, tq): the entries under
    each step, N where a place is empty), of which the first ``total``
    (traced) are run. Returns ``(o (N, rep, D) float32, l (N, rep))``: each
    entry's unnormalised sum and its rows' sums (0 where no step named
    it)."""
    N, rep, D = q.shape
    L, P, KV, Dc, lanes = k_pages.shape
    assert Dc == D, (q.shape, k_pages.shape)
    R = -(-rep // 16) * 16          # whole packed tiles of a 16-bit query
    q = jnp.pad(q, ((0, 1), (0, R - rep), (0, 0)))
    layer = jnp.zeros((1,), jnp.int32) if layer is None \
        else jnp.asarray(layer, jnp.int32).reshape(1)
    # a step before the list's first clears the accumulators: it names the
    # empty entry and sees nothing
    page, kv, entry, bits, base, qidx = (
        jnp.concatenate([jnp.full((1,) + x.shape[1:], fill, jnp.int32),
                         jnp.asarray(x, jnp.int32)])
        for x, fill in zip(steps, (0, 0, 0, 0, -tq - 1, N)))

    def page_block(w, page_ref, kv_ref, entry_ref, bits_ref, base_ref,
                   qidx_ref, layer_ref):
        return layer_ref[0], page_ref[w], kv_ref[w], 0, 0

    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    o, stat = pl.pallas_call(
        functools.partial(_read_kernel, tq=tq, page_size=page_size,
                          sizes=sizes, scale=scale),
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            grid=(total + 1,),
            in_specs=[whole,
                      pl.BlockSpec((1, 1, 1, Dc, lanes), page_block),
                      pl.BlockSpec((1, 1, 1, Dc, lanes), page_block)],
            out_specs=[whole, whole]),
        out_shape=[jax.ShapeDtypeStruct((N + 1, R, D), jnp.float32),
                   jax.ShapeDtypeStruct((N + 1, R, LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=backend.pallas_interpret(),
    )(page, kv, entry, bits, base, qidx.reshape(-1), layer, q, k_pages,
      v_pages)
    return o[:N, :rep], stat[:N, :rep, 1]


def _packed_rows(rep: int) -> int:
    """The rows of a (row, KV head)'s query block: ``rep`` query heads in
    whole packed tiles of a 16-bit query."""
    return -(-rep // 16) * 16


def pages_a_step(rep: int, Dc: int, lanes: int, dtype) -> int:
    """``G``, the pages ONE grid step of the decode rows' read folds, from
    the call's static shapes alone (no option; ``latent_attention.
    pages_a_step``'s manner): a power of two, at most :data:`MAX_PAGES`,
    at most ``STEP_ROWS // rows`` for the ``rep`` query heads' rows (the
    scores of a block's pages are live at once), and no more than ``2 x
    G`` pages each of K and V take of :data:`ROWS_VMEM_BYTES`."""
    room = ROWS_VMEM_BYTES // (4 * _tile_bytes(Dc, lanes, dtype))
    g = int(max(1, min(MAX_PAGES, STEP_ROWS // _packed_rows(rep), room)))
    return 1 << (g.bit_length() - 1)


def _rows_kernel(ent_ref, first_ref, count_ref, offs_ref, hi_ref, layer_ref,
                 page_ref, entry_ref, bits_ref, q_ref, k_hbm, v_hbm, zero_ref,
                 o_ref, kbuf, vbuf, sems, acc_ref, m_ref, l_ref, *, G: int,
                 KV: int, page_size: int, sizes: SparseSizes, scale: float):
    # scalar prefetch: the block list of ``page_blocks`` (entry, first place
    # in its list), each entry's pages and first place of the flat lists,
    # each row's position, the (1,) layer, and the flat lists a place. One
    # step folds one block of one entry into the online softmax of its
    # ``rep`` query rows, all at one position. The pages come by the
    # kernel's own copies, a block ahead: buffers [w % 2] hold this step's.
    w = pl.program_id(0)
    r, first = ent_ref[w], first_ref[w]
    place = offs_ref[r] + first
    slot = w % 2

    def pages_of(at):
        return jnp.minimum(count_ref[ent_ref[at]] - first_ref[at], G)

    def each_page(at, act):
        """``act`` on the K and the V copy of every page of block ``at``."""
        entry = ent_ref[at]
        head, base = entry % KV, offs_ref[entry] + first_ref[at]

        def one(i, carry):
            page = page_ref[base + i]
            for x, (hbm, buf) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf))):
                act(pltpu.make_async_copy(
                    hbm.at[layer_ref[0], page, head], buf.at[at % 2, i],
                    sems.at[x, at % 2]))
            return carry
        jax.lax.fori_loop(0, pages_of(at), one, 0)

    @pl.when(w == 0)
    def _prime():
        # a block's last places may hold no page of this step: what they
        # held before is weighted by zero, so it has to be finite
        vbuf[:] = jnp.zeros_like(vbuf)
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
    count = pages_of(w)
    q = q_ref[0]                                        # (R, D)
    hi = hi_ref[r // KV]
    edge = hi - sizes.window_size
    lo = jnp.where(hi + 1 < sizes.dense_len,
                   (edge + 1) // sizes.block_size * sizes.block_size - 1,
                   edge)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, page_size), 1)
    # the block as ONE run: every page's scores, ONE set of statistics over
    # them, then every page's values (a page's three parts hang on each
    # other: a page at a time the MXU waits through every link, PERF.md
    # section 6, PR 51 and PR 57)
    scores, masks = [], []
    for i in range(G):
        s = jax.lax.dot_general(q, kbuf[slot, i][:, :page_size],
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        pos = entry_ref[place + i] * page_size + lane
        chosen = (bits_ref[place + i] >> (lane // sizes.block_size)) & 1
        seen = jnp.logical_and(
            jnp.logical_and(pos <= hi, i < count),
            jnp.logical_or(chosen > 0, pos > lo))        # (1, page_size)
        masks.append(seen)
        scores.append(jnp.where(seen, s, NEG_INF))
    m_prev, l_prev = m_ref[:, :1], l_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(
        functools.reduce(jnp.maximum, scores), axis=1, keepdims=True))
    # (a row none of whose tokens is seen adds nothing, where exp(0) would)
    weights = [jnp.where(seen, jnp.exp(s - m_new), 0.0)
               for s, seen in zip(scores, masks)]
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(
        functools.reduce(jnp.add, weights), axis=1, keepdims=True)
    acc = acc_ref[:] * alpha
    for i, p in enumerate(weights):
        v = vbuf[slot, i][:, :page_size]
        acc = acc + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)
    acc_ref[:] = acc

    @pl.when(first + count == count_ref[r])
    def _finish():
        o_ref[0] = acc / jnp.maximum(l_new, 1e-30)


def _read_rows(q, k_pages, v_pages, layer, lists, count, qpos, *,
               page_size: int, sizes: SparseSizes, scale: float, name: str):
    """The decode rows' kernel over :func:`rows_plan`'s lists. ``q`` (N,
    rep, D): the (row, KV head) entries, entry ``b KV + kv`` at ``qpos[b]``.
    Returns (N, rep, D) float32, normalised; zeros for an entry of no
    page."""
    N, rep, D = q.shape
    L, P, KV, Dc, lanes = k_pages.shape
    assert Dc == D, (q.shape, k_pages.shape)
    R = _packed_rows(rep)
    q = jnp.pad(q, ((0, 0), (0, R - rep), (0, 0)))
    G = pages_a_step(rep, Dc, lanes, k_pages.dtype)
    layer = jnp.zeros((1,), jnp.int32) if layer is None \
        else jnp.asarray(layer, jnp.int32).reshape(1)
    ent, first, total = page_blocks(count, G, lists[0].shape[0] // N)
    # (a block's last places are read past its entry's pages, unseen)
    page, entry, bits = (jnp.pad(jnp.asarray(x, jnp.int32), (0, G))
                         for x in lists)

    def row_block(w, ent_ref, *_):
        return ent_ref[w], 0, 0

    out = pl.pallas_call(
        functools.partial(_rows_kernel, G=G, KV=KV, page_size=page_size,
                          sizes=sizes, scale=scale),
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=9,
            grid=(total,),
            in_specs=[pl.BlockSpec((1, R, D), row_block),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, R, D), row_block),
            scratch_shapes=[pltpu.VMEM((2, G, Dc, lanes), k_pages.dtype),
                            pltpu.VMEM((2, G, Dc, lanes), v_pages.dtype),
                            pltpu.SemaphoreType.DMA((2, 2)),
                            pltpu.VMEM((R, D), jnp.float32),
                            pltpu.VMEM((R, LANES), jnp.float32),
                            pltpu.VMEM((R, LANES), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((N, R, D), jnp.float32),
        # an entry of no page has no step: its output block is never
        # visited and keeps the zeros it is aliased onto
        input_output_aliases={12: 0},
        interpret=backend.pallas_interpret(),
    )(ent, first, count, jnp.cumsum(count) - count,
      jnp.asarray(qpos, jnp.int32), layer, page, entry, bits, q, k_pages,
      v_pages, jnp.zeros((N, R, D), jnp.float32))
    return out[:, :rep]


def _ragged(count, width: int):
    """Rows of ``count[r] <= width`` steps each, one after another: for
    each of the ``len(count) x width`` places of the list, its row and its
    place in the row, and the list's length."""
    N = count.shape[0]
    ends = jnp.cumsum(count)
    w = jnp.arange(N * width, dtype=jnp.int32)
    r = jnp.minimum(jnp.searchsorted(ends, w, side="right",
                                     method="compare_all"),
                    N - 1).astype(jnp.int32)
    at = jnp.clip(w - (ends - count)[r], 0, width - 1)
    return r, at, ends[-1]


def rows_plan(blocks, qpos, table, sizes: SparseSizes, page_size: int,
              num_pages: int, running=None):
    """The decode rows' page lists. ``blocks`` (B, KV, nb): each (row, KV
    head)'s chosen blocks (every block under ``dense_len``); ``qpos`` (B,);
    ``table`` (B, E). A (row, KV head) is one entry, ``r = b KV + kv``, and
    its pages are the table entries that hold one of its chosen blocks or
    meet its window, up to its position, ascending: ``count[r]`` of them,
    at most ``width``, none for a row that is not ``running``. Returns
    ``(lists, count)``: ``lists = (page, entry, bits)``, the physical
    page, the table entry and the chosen blocks' bits of each of ``B KV
    width`` places, an entry's places one after another (entry ``r``'s
    first is ``sum(count[:r])``); places from ``sum(count)`` on are in
    range and never read."""
    B, KV, nb = blocks.shape
    E = table.shape[1]
    per = page_size // sizes.block_size
    W = sizes.window_size
    with jax.named_scope("sparse_index"):
        chosen = jnp.pad(blocks, ((0, 0), (0, 0), (0, E * per - nb))) \
            .reshape(B, KV, E, per)
        bits = jnp.sum(chosen.astype(jnp.int32)
                       << jnp.arange(per, dtype=jnp.int32), axis=-1)
        e = jnp.arange(E, dtype=jnp.int32)
        q3 = qpos.astype(jnp.int32)[:, None, None]
        need = (e * page_size <= q3) & (
            (bits != 0) | ((e + 1) * page_size - 1 > q3 - W))
        if running is not None:
            need = need & jnp.asarray(running, bool)[:, None, None]
        width = min(E, max(-(-sizes.dense_len // page_size),
                           sizes.topk + W // page_size + 2))
        order = jnp.argsort(jnp.logical_not(need), axis=-1,
                            stable=True)[..., :width].astype(jnp.int32)
        count = jnp.minimum(jnp.sum(need, axis=-1, dtype=jnp.int32),
                            width).reshape(-1)
        r, at, _ = _ragged(count, width)
        entry = order.reshape(B * KV, width)[r, at]
        lists = (jnp.minimum(table[r // KV, entry], num_pages - 1), entry,
                 bits.reshape(B * KV, E)[r, entry])
        return lists, count


def read_rows(q, k_pages, v_pages, layer, table, qpos, blocks,
              sizes: SparseSizes, *, page_size: int, scale: float,
              running=None, name: str = "sparse_read"):
    """One query a row against its own choice. ``q`` (B, H, D); ``table``
    (B, E); ``qpos`` (B,); ``blocks`` (B, KV, nb). Returns ``(y (B, H, D)
    float32, pages)``: ``pages`` the pages the plan listed, a page of a KV
    head each. A row that is not ``running`` has no step and reads zeros."""
    B, H, D = q.shape
    KV = blocks.shape[1]
    lists, count = rows_plan(blocks, qpos, table, sizes, page_size,
                             k_pages.shape[1], running)
    y = _read_rows(q.reshape(B * KV, H // KV, D), k_pages, v_pages, layer,
                   lists, count, qpos, page_size=page_size, sizes=sizes,
                   scale=scale, name=name)
    return y.reshape(B, H, D), jnp.sum(count)


def chunk_plan(far, qpos, table_row, sizes: SparseSizes, page_size: int,
               num_pages: int, tq: int):
    """A chunk's work list. ``far`` (T, KV, nb): the blocks each query
    reads WHOLE (chosen, and before its window); ``qpos`` (T,) ascending by
    one. An entry is ``t KV + kv``. First the window's steps: each tile of
    ``tq`` consecutive queries of a KV head over the pages between its
    first query's window edge and its last query's position. Then the
    blocks as groups: group ``kv nb + b`` holds the queries that read block
    ``b`` of KV head ``kv``, ``tq`` a step. Returns ``(steps, total,
    tiles)``: ``tiles`` the steps of the second kind."""
    T, KV, nb = far.shape
    G = KV * nb
    W, bk = sizes.window_size, sizes.block_size
    per = page_size // bk
    i32 = jnp.int32
    slot = jnp.arange(tq, dtype=i32)
    with jax.named_scope("sparse_index"):
        # (1) by position: tile j of KV head kv is row j KV + kv
        J = -(-T // tq)
        first = qpos[jnp.minimum(jnp.arange(J, dtype=i32) * tq, T - 1)]
        last = jnp.minimum(first + tq - 1, qpos[-1])
        low = jnp.maximum((first - W + 1) // bk * bk, 0) // page_size
        count = jnp.repeat(last // page_size - low + 1, KV)
        width = (tq + W + bk - 3) // page_size + 2
        r, at, near = _ragged(count, width)
        j, kv = r // KV, r % KV
        t = j[:, None] * tq + slot
        near_steps = (low[j] + at, kv, jnp.zeros_like(r), first[j],
                      jnp.where(t < T, t * KV + kv[:, None], T * KV))
        # (2) by block
        member = far.transpose(1, 2, 0).reshape(G, T)
        rank = jnp.cumsum(member, axis=1, dtype=i32)    # inclusive
        ntile = (rank[:, -1] + tq - 1) // tq
        tend = jnp.cumsum(ntile)
        most = min(nb, max(sizes.topk, (sizes.dense_len - W) // bk, 1))
        S = -(-T * KV * most // tq) + G     # a group wastes under a tile
        w = jnp.arange(S, dtype=i32)
        g = jnp.minimum(jnp.searchsorted(tend, w, side="right",
                                         method="compare_all"),
                        G - 1).astype(i32)
        place = ((w - (tend - ntile)[g]) * tq)[:, None] + slot
        # the (place + 1)-th member of the group: the first t of that rank
        t = jnp.sum(rank[g][:, None, :] <= place[:, :, None], axis=-1,
                    dtype=i32)
        kv, b = g // nb, g % nb
        far_steps = (b // per, kv, jnp.left_shift(1, b % per).astype(i32),
                     jnp.full((S,), WHOLE, i32),
                     jnp.where(t < T, t * KV + kv[:, None], T * KV))
        # one list: the first ``near`` of the one, then the other
        w = jnp.arange(r.shape[0] + S, dtype=i32)
        entry, kv, bits, base, qidx = (
            jnp.where((w < near).reshape((-1,) + (1,) * (a.ndim - 1)),
                      a[jnp.minimum(w, r.shape[0] - 1)],
                      c[jnp.clip(w - near, 0, S - 1)])
            for a, c in zip(near_steps, far_steps))
        page = jnp.minimum(table_row[jnp.minimum(
            entry, table_row.shape[0] - 1)], num_pages - 1)
        return (page, kv, entry, bits, base, qidx), near + tend[-1], tend[-1]


def read_chunk(q, k_pages, v_pages, layer, table_row, qpos, blocks,
               sizes: SparseSizes, *, page_size: int, scale: float,
               name: str = "sparse_read_chunk"):
    """A chunk's queries, consecutive positions of ONE slot, each against
    its own choice. ``q`` (T, H, D); ``table_row`` (E,); ``qpos`` (T,)
    ascending by one; ``blocks`` (T, KV, nb). Returns ``(y (T, H, D)
    float32, tiles)``: ``tiles`` the steps that read a chosen block, a
    block under ``CHUNK_TILE`` queries each."""
    T, H, D = q.shape
    KV, nb = blocks.shape[1], blocks.shape[2]
    qpos = qpos.astype(jnp.int32)
    # a block is read whole where it lies before the query's window (under
    # dense_len: before the block the window's first token is in)
    edge = (qpos - sizes.window_size + 1) // sizes.block_size
    far = blocks & (jnp.arange(nb, dtype=jnp.int32)[None, :]
                    < edge[:, None])[:, None, :]
    steps, total, tiles = chunk_plan(far, qpos, table_row, sizes, page_size,
                                     k_pages.shape[1], CHUNK_TILE)
    o, l = sparse_read(q.reshape(T * KV, H // KV, D), k_pages, v_pages,
                       layer, steps, total, tq=CHUNK_TILE,
                       page_size=page_size, sizes=sizes, scale=scale,
                       name=name)
    return (o / jnp.maximum(l[..., None], 1e-30)).reshape(T, H, D), tiles
