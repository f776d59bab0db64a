"""The page pool's two access points as Pallas TPU kernels: the fused
paged-attention decode (``paged_decode``) and the column write
(``paged_write``).

The vLLM PagedAttention insight, aimed at this repo's hottest serving op:
the kernels read and write the
:class:`~deepspeed_tpu.serving.paged_pool.PagedKVPool` pages IN PLACE.
Both take the pool's STACKED leaf ``(L, P, KV, Dc, page_size)`` whole and
find their block by ``(layer, page)`` from scalar prefetch; the write
returns the leaf through ``input_output_aliases``. No program of a
serving step slices, re-lays-out or copies a leaf (the gather → dense
attention → scatter composition materialized O(slots × max_seq_len) K/V
every step; the per-layer slice → XLA scatter → kernel → update-slice
that followed it made five passes over a 67 MB slice a layer, 74 % of a
busy chip: serve-pythia-1b4-chat, ledger, PR 24).

**The leaf's minor dimension.** A Mosaic operand is row-major. The TPU
client's own choice for a ``(..., 128, 64)`` bf16 leaf is the head dim
minor (no lane padded), and XLA then wraps every kernel call in a copy
of the whole operand to row-major and back. The pool therefore stores a
page in whole 128-lane tiles (``page_lanes``: a 64-wide page in the
first 64 lanes of 128, which is what its row-major layout takes in HBM
as in VMEM anyway), a shape with one layout everybody agrees on, and
the kernels are told the ``page_size`` beside the leaf: with that, the
compiled decode, chunk and admission programs hold no operation over a
leaf but the custom calls (temporaries 2 MB, 0.8 GB and 0 at the served
size, compiled for a described v5e); without it, four copies of the
stacked leaf a step at 8.5 ms each (chip run of PR 27). A leaf whose
minor dimension is the page size itself is taken too (tests, pages of
128).

**Grid.** One grid step is one LIVE page of one slot with every KV head
of the device in it. The wrapper turns ``(starts, table)`` into a work
list (:func:`live_pages`: slot, table entry and physical page of each
step, slot by slot in table order), hands it to the kernel by scalar
prefetch (SMEM) and sizes the grid by the list's length, which is known
only on the device: ``grid = (KV // kv_group, total)`` with a dynamic
second axis. Pages past a slot's live length are no step and no DMA. A
slot's live length is bounded by its row's leading mapped entries as well
as by its ``start``: the pool advances every slot's index on every decode
step, so a freed slot's ``start`` counts on towards the capacity while
its row is all sentinel. The list holds the slots that map a page and no
others: a slot whose row maps nothing is NO step (until PR 31 it was one
masked step on a clipped page, a 1 MB DMA and 16 folds for a row nobody
reads: with 62 of 64 slots freed, 0.149 of the 0.15 ms a call), and a
table that maps nothing is a grid of no step. The output blocks of such
slots are never visited, so the result is aliased onto the query operand
the wrapper builds (``input_output_aliases``): their rows come back as
the slot's own query rows, finite and defined, and are not attention
output. Compiled shapes depend on static shapes alone: the live lengths
ride scalar prefetch.

**Blocks.** K and V blocks are ``(1, kv_group, Dc, page_size)``: a page
of one layer is contiguous over its heads, so all of them arrive in one
DMA (16 heads of 128 × 64 bf16: 256 KB, 512 KB in VMEM because a 64-wide
page fills half of each 128-lane tile — in HBM too). The query and output
blocks carry the ``kv_group`` KV heads of the slot, and a KV head's rows
are those of its ``rep`` query heads one head after another, ``T`` rows
each, padded ONCE to whole sublane tiles: ``_row_tiles(rep * T)`` rows
(:func:`block_rows`; GQA's query heads of a KV head share its page, so
they are one operand of one product; until PR 55 each head's rows were
padded to a tile before the heads were stacked, ``rep * _row_tiles(T)``,
and a decode step of 4 or 8 query heads a KV head folded 32 or 64 rows
of which 4 or 8 are read). The scratch (``acc``, ``m``, ``l``) has the
same KV-head axis, and the fold runs once a KV head inside the step (a
static loop; with ``rep`` 1 a KV head is a query head).
:func:`plan_grid` picks ``kv_group`` from the shapes against
a fixed VMEM budget (:data:`VMEM_BUDGET_BYTES`, half the v5e's default
scoped limit): every KV head when one page of each fits, else the largest
divisor of ``KV`` that does, and the head-group axis comes back into the
grid. No option selects any of this.

**The order inside a step** (PR 53). The step's KV heads are independent
of each other, and a head's update is a chain: scores (a product), the
statistics (row maximum, two exponentials, row sum), values (a second
product into the accumulator). Mosaic keeps the MXU's products in the
order they are written, so written head after head every link waited for
the one before, sixteen times a page at Pythia's shape. The kernel
writes the same update a RUN of heads at a time: every head's scores,
then the statistics head after head, then every head's values
(``_paged_kernel.fold``). Each value's arithmetic is in the order it
was, so the result is bit for bit the fold of one head after another
(the parity contract below holds as it did; on the chip max |delta| 0.0
against the kernel of PR 52 at every served shape). :func:`plan_grid`
gives the run from the call's static shapes (a power of two, at most
``kv_group``: the heads whose scores take a quarter of the vector
registers, four while theirs take no more than half); a chunk of 512 or
1,024 rows a KV head folds one head at a time, as before.

**Query rows** (PR 33; PR 55). The row count is a static shape like any
other: one row a slot for a decode step, K + 1 for a verify step, and a
prefill chunk's 64 or 128 rows of the one slot it runs, which until PR 33
took a gathered dense row of ``max_seq_len`` positions in every layer
because the kernel stopped at one sublane tile. Everything that held the
tile's 8 holds ``_row_tiles(rep * T)`` (blocks, scratch, the VMEM plan:
16 KV heads of 64 rows a step at Pythia's shape, 2 of 4 KV heads of
8 x 128 = 1,024 rows at Mellum's; 8 rows a KV head for a decode step of
Mellum's, Granite's or LFM2's, 24 for a 5-row verify step at ``rep`` 4).
Row ``r`` of a KV head's block is query row ``r % T`` of its head
``r // T``: the per-row causal limit, the window mask and a head's ALiBi
slope read those two, and the rows from ``rep * T`` on are padding. With
``rep`` 1, or ``T`` in whole tiles, the layout and the compiled program
are what they were (``tests/unit/accelerator/test_chip_path.py`` holds
the decode program's text, ``tests/unit/ops/test_paged_attention.py`` the
layout's jaxpr). Rows that not even one KV head's step can hold go in
two calls of half the rows each.

**What was measured** (v5e, stand-alone at the served shape B=64, H=KV=16,
D=128, page 64, 32 table entries a slot, 256 pages; chip runs of PR 24):
the former grid ``(B, H, pages_per_slot)`` = 32,768 steps took 6.6 ms a
call with 30 slots live at 100-600 tokens and 6.1 ms with 2 slots at
~1,400, i.e. ~0.2 us an empty step; every head in one step over ``(B,
pages_per_slot)`` 0.82 / 0.55 ms; this grid 0.48 / 0.26 ms, ~2.4 us a
live page. Several page operands a step (the table row cut in blocks) were
slower with every operand added (3: 1.02 / 0.65 ms, 8: 1.15 / 0.65 ms), and
Mosaic refuses the kernel's own ``make_async_copy`` of a 64-wide page
("Slice shape along dimension 3 must be aligned to tiling (128)"), so
neither is here. The copy form lives in ``latent_attention.py`` (PR 51),
whose page is whole 128-lane tiles: blocks of pages by the kernel's own
copies, a block's scores, statistics and values each in one run (there
the page's three parts waiting on each other were the cost, not the
step); it is the pattern a 128-wide K/V page would take.

The same chain stood in this kernel's step, head after head (chip runs
of PR 53, microseconds a FURTHER live page at the served shape, 12 to 64
live pages): as it was 2.02; the scores' product, the statistics or the
values' product removed 1.56 / 1.41 / 1.39; all three removed, the page
still fetched 1.36 (the padded DMA of K and V, 1 MB); the fold kept and
the page index held constant 1.94; neither 0.25. Any ONE link removed
gave the DMA's time, so the order was the cost and not the work. In runs
of 2 / 4 / 8 / 16 heads: 1.73 / 1.66 / 1.39 / 1.37-1.42, against the
parent's 2.02 beside them; what is left is the padded DMA. Mellum's
decode rows (64 a KV head, 4 heads, pages of 128) 0.97 -> 0.88 in runs
of 4 (the statistics of 64 rows are most of its page: 0.56 without
them), Granite's (32 rows, 8 heads of 64) 0.98 -> 0.88 in runs of 4 and
1.29 in ONE run of 8; a 64-row chunk at Pythia's shape reads the same
at every run (3.1-3.3: its statistics, 1.28 without them), and a chunk
of 512 or 1,024 rows a KV head keeps the fold of one head after another
(8.1 and 7.5 a page either way).

Those 64 and 32 rows were a tile of 8 for each query head's ONE decode
row. With a KV head's rows packed into whole tiles (PR 55; chip runs of
PR 55, the same method, the parent's kernel beside it, max |delta| 0.0
at every shape): Granite's and LFM2's decode rows (8 a KV head now, 8
heads, ONE run of 8) 0.88 -> 0.65 a further page (64 slots x 15 pages,
and 256 slots x 11: 2.59 -> 1.91 ms a call), in runs of 2 / 4 / 8 0.84 /
0.76 / 0.65: at 8 rows the one run that read a third slower at 32 rows
reads best, as at Pythia's 8 rows; Mellum's (8 rows, 4 heads, runs of 4)
0.89 -> 0.63 full group, 0.85 -> 0.61 window group, in runs of 2 0.64; a
5-row verify step at ``rep`` 4 (24 rows for 32) 0.88 -> 0.83 in runs of
4 (2: 1.01, 8: 0.90), at ``rep`` 8 (40 for 64) 0.89 -> 0.67 (runs of 2:
0.63-0.66). What is left at these shapes is the step itself: one 256 KB page
a grid step, 0.32 us of bytes.

Parity contract (the "dense oracle" discipline): for each head the
per-page fold is op-for-op the dense decode kernel's
(:func:`~deepspeed_tpu.ops.attention.decode_attention._decode_kernel` —
same online-softmax update order, same masking) with the position block
pinned to ONE PAGE, one page at a time in table order. Each row of a call
is therefore bitwise-identical to ``decode_attention(q, dense_k, dense_v,
lengths, block_s=page_size)`` on the gathered dense view in interpret mode
on the CPU, which is what lets the serving tests pin the paged-kernel arm
against the dense path exactly (TransformerConfig's ``decode_block`` pins
the oracle's block granule to the page size). On the TPU that twin exists
only for pages of at least 128 positions: the dense kernel puts
``block_s`` on the lane axis and Mosaic rejects a 64-wide block there,
so at the default page size (64) the pinned oracle does not lower and
the arms are compared within a bf16 tolerance instead (chip_smoke.py:
max |delta logit| 0.041 at logit scale 5.0 on a v5e, PR 21). Bitwise
equality on the chip at page size 128 has not been tried.

**The write** (:func:`paged_write`, PR 27). One grid step is one page of
one layer for one group of KV heads: the ``(1, 1, kv_group, Dc,
page_size)`` block comes in, the columns named by the step replace its
lanes ``[lo, hi)`` and the block goes back to the same place. The new
columns arrive positions-minor in windows of ``lcm(page_size, 128)``
lanes (a lane block has to be a multiple of 128) and are rotated to
their offset in the page inside the kernel; Mosaic rotates 32-bit lanes
only, so bf16 and int8 rows ride bit-cast to words, which moves whole
columns all the same and keeps every bit (NaN payloads, signed zeros:
the write is data movement, no arithmetic). The work list
(:func:`run_work`: page, source row, window, rotation, ``lo``, ``hi`` of
each step) rides scalar prefetch and the grid is as long as the list:
what the XLA scatter's ``mode="drop"`` dropped (a sentinel page, a
position out of range) is NOT in the list, so it touches nothing. Two
steps may name one page only if they follow each other (the block is
then still in VMEM and the second goes on from it). Three shapes of
write share the kernel: a step's columns a slot into one layer
(:func:`paged_write_columns`: the model's decode, verify and, since
PR 33, chunk steps, a window of the source at a time), a run of a dense
cache's columns into every layer (:func:`paged_write_runs`: whole
prefilled rows, the kernel-off compositions), and
the fp32 scale leaves ``(L, P, KV, page_size)`` of the quantized tiers,
which go through the same call with the heads in the stored dim's place
(less code than keeping a scatter for them).

Garbage is masked by length, never by table lookups: sentinel table
entries (``num_pages`` = unmapped) clip to a real page exactly like the
dense gather's ``mode="clip"``, and stale columns past the live length
inside the last live page are masked to ``NEG_INF`` before the softmax,
so their values never reach the output. Any number of query rows a
slot (plain decode T=1; speculative verify T=K+1; a prefill chunk's
width) with per-row causal
masking, GQA, ALiBi, and the int8/int32-packed quantized cache tiers
(scales paged alongside, folded into the score/probability rows like the
dense kernel).
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

__all__ = ["paged_decode_attention", "paged_write_columns",
           "paged_write_runs", "plan_grid", "block_rows", "plan_write",
           "live_pages", "kept_first"]

# what one grid step may hold in VMEM: half of the v5e's default scoped
# limit (16 MiB), so the compiler's own temporaries fit beside it and no
# call needs a raised ``vmem_limit_bytes``
VMEM_BUDGET_BYTES = 8 * 2 ** 20
# a run of KV heads (plan_grid): the query rows whose scores fill a
# quarter of the vector registers (16 of 64, a row of 128 lanes of fp32
# an eighth of one), and the heads that go one to each of the chip's MXUs
RUN_ROWS = 128
RUN_HEADS = 4


def plan_grid(B: int, H: int, KV: int, D: int, Dc: int, page_size: int,
              pages_per_slot: int, kv_dtype, q_dtype, quantized: bool,
              query_rows: int = 1):
    """``(kv_group, run, grid)`` for one call, from its static shapes
    alone.

    One grid step holds one page of ``kv_group`` KV heads (K and V, and
    the scale rows of a quantized pool, each double-buffered by the
    pipeline) beside the query and output blocks of the group's heads
    (``query_rows`` rows a head, in whole sublane tiles) and the fp32
    accumulator and softmax statistics of as many rows. ``kv_group`` is
    all of ``KV`` when that fits :data:`VMEM_BUDGET_BYTES`, else the
    largest divisor of ``KV`` that does — the head-group axis then comes
    back into the grid — and 1 where not even one head fits (a call of
    more than one sublane tile of rows is then made in two halves by
    :func:`paged_decode_attention`). A step is always ONE page (every
    further page operand of a step cost more than the grid step it
    saved: 3 pages a step 1.02 ms against 0.82 ms a call at the served
    shape, chip run of PR 24).

    ``run`` is how many of the step's KV heads the kernel folds at a
    time (every head's scores, then the statistics, then every head's
    values: the module docstring's "The order inside a step"): a power
    of two, at most ``kv_group``; as many heads as :data:`RUN_ROWS`
    ``// rows`` for the ``rows`` query rows of a KV head, so that a
    run's scores take a quarter of the vector registers, but
    :data:`RUN_HEADS` (one for each MXU) while those four's scores take
    no more than half: all 16 heads of Pythia's decode rows (8 rows a
    head), and since PR 55 packed a GQA decode step's rows all 8 of
    Granite's and LFM2's and all 4 of Mellum's likewise (8 rows a KV
    head: 0.65 and 0.63 us a further page, 0.76 in runs of 4 and 0.64
    in runs of 2); 4 heads at 24 to 64 rows (a 5-row verify step at
    ``rep`` 4 or 8; at 32 rows Granite's 8 in one run read a third
    SLOWER than head after head on the chip), 4 of a 64-row chunk, and
    1, the fold of one head after another, from 128 rows a KV head on (a
    chunk of 512 or 1,024 at Mellum's and Granite's shapes). PERF.md
    section 6, PR 53 and PR 55, has the sweeps.

    ``grid`` is ``(KV // kv_group, B * pages_per_slot)``, the second a
    bound: the call runs one step for each entry of :func:`live_pages`,
    not for each table entry."""
    rows = block_rows(H // KV, query_rows)
    step_bytes = functools.partial(
        _step_bytes, rows=rows, D=D, Dc=Dc, page_size=page_size,
        kv_dtype=kv_dtype, q_dtype=q_dtype, quantized=quantized)
    kv_group = max((g for g in range(1, KV + 1) if KV % g == 0
                    and step_bytes(g) <= VMEM_BUDGET_BYTES), default=1)
    run = max(RUN_ROWS // rows,
              RUN_HEADS if RUN_HEADS * rows <= 2 * RUN_ROWS else 1)
    run = 1 << (min(run, kv_group).bit_length() - 1)
    return kv_group, run, (KV // kv_group, B * pages_per_slot)


def block_rows(rep: int, query_rows: int) -> int:
    """The rows of ONE KV head in the query and output blocks of a call
    of ``query_rows`` rows a slot: its ``rep`` query heads' rows one
    after another, ``rep * query_rows`` of them, in whole sublane tiles
    (8 for a decode step of up to 8 query heads a KV head, whatever
    ``rep``; ``rep`` times a chunk's 64 or 128)."""
    return _row_tiles(rep * query_rows)


def _row_tiles(rows: int) -> int:
    """``rows`` in whole sublane tiles."""
    return -(-rows // SUBLANES) * SUBLANES


def _step_bytes(kv_group: int, *, rows: int, D: int, Dc: int,
                page_size: int, kv_dtype, q_dtype, quantized: bool) -> int:
    """VMEM of one grid step of the read with ``kv_group`` KV heads,
    ``rows`` query rows each (:func:`block_rows`). A KV head's block is
    rounded to the VMEM tile as the ONE (rows, D) array it is, where the
    reckoning before PR 55 rounded each of its ``rep`` heads: at ``rep``
    > 1 with 8 or 24 bf16 rows a head (half a 16-row tile each) this
    figure is the smaller and the truer one; no served shape's
    ``kv_group`` moved by it (all far under the budget; ``rep`` 4 at 8
    rows is pinned in the tests)."""
    page = 2 * _vmem_tile_bytes(Dc, page_size, kv_dtype)      # K and V
    if quantized:
        page += 2 * _vmem_tile_bytes(1, page_size, jnp.float32)
    blocks = 2 * _vmem_tile_bytes(rows, D, q_dtype)
    scratch = _vmem_tile_bytes(rows, D, jnp.float32) \
        + 2 * _vmem_tile_bytes(rows, LANES, jnp.float32)
    return kv_group * (2 * (page + blocks) + scratch)


def _vmem_tile_bytes(rows: int, cols: int, dtype) -> int:
    """Bytes a (rows, cols) array takes in VMEM: lanes pad to 128 and
    sublanes to a whole tile (8 rows of 32 bits, 16 of bf16, 32 of
    int8) — a 64-wide page fills half of every tile it touches."""
    itemsize = jnp.dtype(dtype).itemsize
    sublanes = SUBLANES * (4 // itemsize)
    return (-(-rows // sublanes) * sublanes * -(-cols // LANES) * LANES
            * itemsize)


def live_pages(starts: jax.Array, table: jax.Array, num_rows: int,
               page_size: int, num_pages: int,
               window: Optional[int] = None):
    """The call's work list: ``(slot_of, entry_of, page_of, live, total)``
    and, with a ``window``, each slot's first entry after ``live``.

    Step ``w < total`` folds table entry ``entry_of[w]`` of slot
    ``slot_of[w]``, physical page ``page_of[w]``, slot by slot in table
    order. Slot ``b`` has ``live[b]`` steps: the entries its rows can
    see, ``ceil((start + num_rows) / page_size)``, but no more than the
    row's leading MAPPED entries — a freed slot's row is all sentinel
    while its ``start`` keeps counting (the pool advances every slot's
    index each decode step), and what is not mapped is not cached — and
    at least one while the row maps a page (a seated slot at ``start``
    0). A slot whose row maps nothing has NO step: it is not in the
    list, its output block is never visited, and ``total`` is 0 when no
    row maps a page. The lists are as long as the table
    (``B * pages_per_slot``); entries from ``total`` on are in range and
    never run.

    With a ``window`` (a group of sliding layers: query ``p`` sees keys in
    ``(p - window, p]``) a slot's steps begin at the entry that holds
    ``start - window + 1``, the oldest key its first row sees, and a row's
    MAPPED entries are those up to its last mapped one: what lies before
    the window has been recycled and is a sentinel in the table."""
    B, pages_per_slot = table.shape
    if window is not None:
        return _window_pages(starts, table, num_rows, page_size, num_pages,
                             window)
    # index of the row's first sentinel, pages_per_slot if it has none
    mapped = jnp.argmin(jnp.pad(table < num_pages, ((0, 0), (0, 1))),
                        axis=1).astype(jnp.int32)
    live = jnp.clip(
        jnp.minimum((starts + num_rows + page_size - 1) // page_size, mapped),
        jnp.minimum(mapped, 1), pages_per_slot)
    ends = jnp.cumsum(live)
    w = jnp.arange(B * pages_per_slot, dtype=jnp.int32)
    slot_of = jnp.minimum(
        jnp.searchsorted(ends, w, side="right", method="compare_all"),
        B - 1).astype(jnp.int32)
    entry_of = jnp.clip(w - (ends - live)[slot_of], 0, pages_per_slot - 1)
    # sentinel ids clip to a real page, like the dense gather's "clip"
    page_of = jnp.minimum(table[slot_of, entry_of], num_pages - 1)
    return slot_of, entry_of, page_of, live, ends[-1]


def _window_pages(starts, table, num_rows: int, page_size: int,
                  num_pages: int, window: int):
    """:func:`live_pages` for a group of sliding layers."""
    B, pages_per_slot = table.shape
    entries = jnp.arange(pages_per_slot, dtype=jnp.int32)
    # one past the row's last mapped entry, 0 where it maps nothing
    mapped = jnp.max(jnp.where(table < num_pages, entries + 1, 0), axis=1)
    first = jnp.clip((starts - window + 1) // page_size, 0,
                     pages_per_slot - 1).astype(jnp.int32)
    last = jnp.minimum((starts + num_rows + page_size - 1) // page_size,
                       mapped)
    live = jnp.clip(last - first, jnp.minimum(mapped, 1), pages_per_slot)
    ends = jnp.cumsum(live)
    w = jnp.arange(B * pages_per_slot, dtype=jnp.int32)
    slot_of = jnp.minimum(
        jnp.searchsorted(ends, w, side="right", method="compare_all"),
        B - 1).astype(jnp.int32)
    entry_of = jnp.clip(first[slot_of] + w - (ends - live)[slot_of], 0,
                        pages_per_slot - 1)
    page_of = jnp.minimum(table[slot_of, entry_of], num_pages - 1)
    return slot_of, entry_of, page_of, live, ends[-1], first


def _paged_kernel(slot_ref, entry_ref, page_ref, live_ref, start_ref,
                  slope_ref, layer_ref, *refs,
                  page_size: int, scale: float, rep: int, query_rows: int,
                  alibi: bool, quantized: bool, packed: bool, compute_dtype,
                  window: Optional[int] = None, run: int):
    # the first seven are scalar-prefetch SMEM arrays: the work list of
    # live_pages, (B,) starts, (H,) slopes and the (1,) layer of the
    # stacked leaf (page_ref and layer_ref are read by the index maps
    # only). One grid step is one live page of one slot for one group of
    # KV heads: the K/V blocks are (1, 1, kv_group, Dc, lanes), the page
    # in their first page_size lanes. For each head the fold mirrors
    # decode_attention._decode_kernel line for line (the bitwise-parity
    # contract in the module docstring); the differences are where K/V
    # blocks come from and that each query row carries its own causal
    # limit (row t sees cache positions <= start + t). A group of
    # sliding layers brings an eighth list, each slot's first entry, and
    # masks what lies ``window`` or more behind a row.
    first_ref = None
    if window is not None:
        first_ref, *refs = refs
    q_ref, k_ref, v_ref, *refs = refs
    if quantized:
        k_scale_ref, v_scale_ref, *refs = refs
    o_ref, acc_ref, m_ref, l_ref = refs
    g, w = pl.program_id(0), pl.program_id(1)
    kv_group = k_ref.shape[2]
    rows = q_ref.shape[2]           # of one KV head: block_rows(rep, T)
    entry = entry_ref[w]
    slot = slot_ref[w]
    start = start_ref[slot]
    block_start = entry * page_size

    first = 0 if first_ref is None else first_ref[slot]

    @pl.when(entry == first)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # GQA: the rep query heads of a KV head share its page, so their T
    # (= query_rows) rows each stand one head after another in ONE
    # (rows, D) operand and the fold runs once a KV head (a fold a query
    # head, 8 rows each, was 32 small products a step at 32 / 4 heads
    # and cost a decode token twice a prefill token: PERF.md section 6,
    # PR 30). Row r is query row r % T of head r // T, and the rows from
    # rep * T on are padding: the operand is padded to whole sublane
    # tiles ONCE (PR 55; until then each head's T rows were, and a
    # decode step's statistics ran over 32 or 64 rows of which 4 or 8
    # are read). A padding row stands past the last head (it takes the
    # first head's slope) and is never read; with rep == 1, or T in
    # whole tiles, this is the fold it always was.
    pos = block_start + jax.lax.broadcasted_iota(
        jnp.int32, (rows, page_size), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, page_size), 0)
    if rep > 1:
        # (a decode step's T is 1: the row is the head and its query row
        # 0, which Mosaic folds; written out as constants the CPU's
        # compiler contracted the ALiBi multiply-adds another way than
        # the dense kernel's and the bitwise test caught it)
        head_of, row = row // query_rows, row % query_rows
    def stored(ref, c):
        """Head ``c``'s (D, page_size) rows of the page as the products
        take them: a quantized tier's widened to the compute dtype."""
        x = ref[0, 0, c][:, :page_size]                   # (Dc, page_size)
        if packed:
            x = pltpu.bitcast(x, jnp.int8)
        return x.astype(compute_dtype) if quantized else x

    def fold(heads):
        """The online-softmax update of one page for the KV heads
        ``heads`` of the step: every head's scores, then the statistics
        head after head, then every head's values. Written head by head
        the three parts of a head waited on each other, head after head
        (Mosaic keeps the MXU's products in the order they are written:
        PERF.md section 6, PR 53); each value's arithmetic is in the
        order it was, so the bits are."""
        scores = []
        for c in heads:
            q = q_ref[0, c]                               # (rows, D)
            s = jax.lax.dot_general(
                q, stored(k_ref, c), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if quantized:
                s = s * k_scale_ref[0, 0, c][:, :page_size]    # (1, page)
            if alibi:
                # the dense kernel's bias for the query at ``start``, then
                # row t's own offset: slope * (pos - (start + t)). Row 0
                # subtracts an exact zero, which keeps a T=1 call bitwise
                # equal to the dense kernel's scalar expression
                slope = slope_ref[(g * kv_group + c) * rep]
                for j in range(1, rep):
                    slope = jnp.where(
                        head_of == j,
                        slope_ref[(g * kv_group + c) * rep + j], slope)
                s = s + slope * (pos - start).astype(jnp.float32) \
                    - slope * row.astype(jnp.float32)
            seen = pos <= start + row
            if window is not None:
                seen = jnp.logical_and(seen, pos > start + row - window)
            scores.append(jnp.where(seen, s, NEG_INF))
        weights = []
        for c, s in zip(heads, scores):
            m_prev = m_ref[c, :, :1]
            l_prev = l_ref[c, :, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[c] = jnp.broadcast_to(
                alpha * l_prev + jnp.sum(p, axis=1, keepdims=True),
                l_ref.shape[1:])
            weights.append((p, alpha, m_new))
        for c, (p, alpha, m_new) in zip(heads, weights):
            v = stored(v_ref, c)
            if quantized:
                p = p * v_scale_ref[0, 0, c][:, :page_size]    # (1, page)
            acc_ref[c] = acc_ref[c] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[c] = jnp.broadcast_to(m_new, m_ref.shape[1:])

    for c in range(0, kv_group, run):
        fold(range(c, min(c + run, kv_group)))

    last = live_ref[slot] - 1
    if first_ref is not None:
        last = first + last

    @pl.when(entry == last)
    def _finish():
        l = jnp.maximum(l_ref[:, :, :1], 1e-30)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)


def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, table: jax.Array,
                           starts: jax.Array, *, layer=None,
                           page_size: Optional[int] = None,
                           scale: Optional[float] = None,
                           alibi_slopes: Optional[jax.Array] = None,
                           k_scale_pages: Optional[jax.Array] = None,
                           v_scale_pages: Optional[jax.Array] = None,
                           window: Optional[int] = None,
                           active=None) -> jax.Array:
    """Cached attention over paged K/V: softmax(q·K^T + bias) · V with
    K/V resolved through a per-slot page table inside the kernel.

    Args:
      q: (B, T, H, D) current-step queries: one row a slot for a decode
        step, K + 1 for a verify step, a prefill chunk's 64 or 128 for
        the one slot it runs. Row ``t`` of slot ``b`` attends cache
        positions ``[0, starts[b] + t]`` (its own column included — the
        caller has already written this step's T columns into the
        pages).
      k_pages/v_pages: (L, P, KV, Dc, lanes) the pool's STACKED leaf,
        read at ``layer`` through the index map: no slice of it is ever
        made. H % KV == 0 (GQA). May be int8, or int32-packed
        (Dc = D // 4) when scales are given. One layer's
        (P, KV, Dc, lanes) pool is taken as a stack of one.
      table: (B, pages_per_slot) int32 page table; ``P`` is the
        unmapped sentinel (clipped to a real page, masked by length —
        the dense gather's ``mode="clip"`` discipline). A slot attends
        over its row's leading mapped entries at most: a row that maps
        nothing is an empty slot, whatever its ``starts`` says, and
        costs no grid step.
      starts: (B,) int32 cache length BEFORE this step's tokens (the
        slot pool's ``index`` mirror at dispatch).
      layer: int32 scalar, the layer of the stacked leaf to read
        (traced: the layer scan's counter). ``None`` reads layer 0.
      page_size: positions a page holds, in its first lanes; ``None``
        when the leaf's minor dimension is the page size itself.
      alibi_slopes: optional (H,) ALiBi slopes.
      k_scale_pages/v_scale_pages: (L, P, KV, lanes) fp32 per-column
        dequantization scales for a quantized page pool.
      window: the pool is a group of sliding layers' (static): row ``t``
        sees positions ``(starts[b] + t - window, starts[b] + t]`` only,
        and the slot's steps begin at the page that holds the oldest of
        them (``table`` holds sentinels before it).
      active: traced bool; False runs a grid of NO step (the layer is not
        of this pool's group) and the result is not to be read.
    Returns (B, T, H, D) in q's dtype. Rows of slots that map no page
    (and every row of a call that is not ``active``) are NOT attention
    output and are not to be read: no step visits them, and they hold
    the slot's own query rows (finite; the served programs carry every
    row through the projection, the FFN or router, the head and the
    finite guard).
    """
    starts = jnp.broadcast_to(jnp.asarray(starts, jnp.int32),
                              (q.shape[0],))
    if k_pages.ndim == 4:
        k_pages, v_pages = k_pages[None], v_pages[None]
        if k_scale_pages is not None:
            k_scale_pages, v_scale_pages = (k_scale_pages[None],
                                            v_scale_pages[None])
    layer = jnp.zeros((1,), jnp.int32) if layer is None \
        else jnp.asarray(layer, jnp.int32).reshape(1)
    kernel = functools.partial(
        _paged_decode_attention_local, scale=scale, window=window,
        page_size=k_pages.shape[-1] if page_size is None else page_size)
    if active is not None:
        kernel = functools.partial(kernel,
                                   active=jnp.asarray(active, jnp.bool_))
    B, H = backend.BATCH, backend.HEADS
    # the page pool has no batch dim: every device holds every page of
    # its KV heads, and its slots' rows of the table
    return backend.shard_kernel(
        kernel, (B, None, H, None),
        q=(q, (B, None, H, None)),
        k_pages=(k_pages, (None, None, H, None, None)),
        v_pages=(v_pages, (None, None, H, None, None)),
        table=(table, (B, None)), starts=(starts, (B,)),
        layer=(layer, (None,)), alibi_slopes=(alibi_slopes, (H,)),
        k_scale_pages=(k_scale_pages, (None, None, H, None)),
        v_scale_pages=(v_scale_pages, (None, None, H, None)))


def _paged_decode_attention_local(q, k_pages, v_pages, table, starts, layer,
                                  *, scale, page_size, alibi_slopes,
                                  k_scale_pages, v_scale_pages, window=None,
                                  active=None):
    """:func:`paged_decode_attention` on the slots and heads one device
    holds."""
    B, T, H, D = q.shape
    L, P, KV, Dc, lanes = k_pages.shape
    ps = page_size
    assert ps <= lanes, f"page of {ps} in {lanes} lanes"
    maxP = table.shape[1]
    assert H % KV == 0, f"H={H} not a multiple of KV={KV}"
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
    if T > SUBLANES and _step_bytes(
            1, rows=block_rows(rep, T), D=D, Dc=Dc, page_size=lanes,
            kv_dtype=k_pages.dtype, q_dtype=q.dtype,
            quantized=quantized) > VMEM_BUDGET_BYTES:
        # not even one KV head's rows fit a step: two calls of half the
        # query rows each, the second ``half`` positions further on
        half = _row_tiles(-(-T // 2))
        part = functools.partial(
            _paged_decode_attention_local, k_pages=k_pages, v_pages=v_pages,
            table=table, layer=layer, scale=scale, page_size=ps,
            alibi_slopes=alibi_slopes, k_scale_pages=k_scale_pages,
            v_scale_pages=v_scale_pages, window=window, active=active)
        return jnp.concatenate(
            [part(q[:, :half], starts=starts),
             part(q[:, half:], starts=starts + half)],
            axis=1).astype(out_dtype)
    if alibi_slopes is None:
        slopes = jnp.zeros((H,), jnp.float32)
        alibi = False
    else:
        slopes = jnp.asarray(alibi_slopes, jnp.float32)
        alibi = True
    table = jnp.asarray(table, jnp.int32)

    q4 = _pack_rows(q, KV)
    rows = q4.shape[2]

    kv_group, run, (groups, _) = plan_grid(
        B, H, KV, D, Dc, lanes, maxP, k_pages.dtype, q.dtype, quantized,
        query_rows=T)
    slot_of, entry_of, page_of, live, total, *first = live_pages(
        starts, table, T, ps, P, window)
    if active is not None:
        total = jnp.where(active, total, 0)

    head_block = pl.BlockSpec(
        (1, kv_group, rows, D),
        lambda g, w, slot_ref, *_: (slot_ref[w], g, 0, 0))

    def page_index(g, w, slot_ref, entry_ref, page_ref, live_ref,
                   start_ref, slope_ref, layer_ref, *_):
        return (layer_ref[0], page_ref[w], g, 0, 0)

    pools = [k_pages, v_pages]
    blocks = [(1, 1, kv_group, Dc, lanes)] * 2
    if quantized:
        # scales ride as (L, P, KV, 1, lanes) so a head's (1, ps) row
        # lands on LANES, matching s/p (same trick as the dense
        # kernel's (B, KV, 1, S) reshape)
        pools += [
            k_scale_pages.astype(jnp.float32).reshape(L, P, KV, 1, lanes),
            v_scale_pages.astype(jnp.float32).reshape(L, P, KV, 1, lanes)]
        blocks += [(1, 1, kv_group, 1, lanes)] * 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7 + len(first),
        grid=(groups, total),
        in_specs=[head_block] + [pl.BlockSpec(block, page_index)
                                 for block in blocks],
        out_specs=head_block,
        scratch_shapes=[
            pltpu.VMEM((kv_group, rows, D), jnp.float32),
            pltpu.VMEM((kv_group, rows, LANES), jnp.float32),
            pltpu.VMEM((kv_group, rows, LANES), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, page_size=ps, scale=scale, rep=rep,
                          query_rows=T, alibi=alibi,
                          quantized=quantized, packed=packed,
                          compute_dtype=compute_dtype, window=window,
                          run=run),
        name="paged_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, rows, D), q.dtype),
        # the result IS the query operand: a slot that is not in the work
        # list has no step, so its output block is never visited, and it
        # keeps its own (finite) query rows instead of whatever the
        # buffer held. A slot in the list has its query block in VMEM
        # before its first step and its output block written back after
        # its last, and no later step names that block again.
        input_output_aliases={7 + len(first): 0},
        interpret=backend.pallas_interpret(),
    )(slot_of, entry_of, page_of, live, starts, slopes, layer, *first, q4,
      *pools)
    return _unpack_rows(out, T, H).astype(out_dtype)


def _pack_rows(q: jax.Array, KV: int) -> jax.Array:
    """``(B, T, H, D)`` queries as the read's operand ``(B, KV, rows,
    D)``. Query rows ride the sublane axis: a KV head's ``rep`` query
    heads, ``T`` rows each, stand one head after another (row ``r`` is
    query row ``r % T`` of head ``r // T``) and are padded ONCE up to
    :func:`block_rows` whole sublane tiles (dead rows see position 0 at
    least and are sliced off: never all-masked, so no NaN risk)."""
    B, T, H, D = q.shape
    rep = H // KV
    live, rows = rep * T, block_rows(rep, T)
    q4 = q.transpose(0, 2, 1, 3).reshape(B, KV, live, D)
    if live < rows:
        q4 = jnp.pad(q4, ((0, 0), (0, 0), (0, rows - live), (0, 0)))
    return q4


def _unpack_rows(out: jax.Array, T: int, H: int) -> jax.Array:
    """:func:`_pack_rows` back: ``(B, KV, rows, D)`` to ``(B, T, H, D)``."""
    B, KV, _, D = out.shape
    return out[:, :, :H // KV * T].reshape(B, H, T, D).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# the write: columns into the stacked leaf, in place
# ---------------------------------------------------------------------------
def plan_write(KV: int, Dc: int, page_size: int, lanes: int, dtype):
    """``(kv_group, window)`` of a :func:`paged_write` call, from its
    static shapes alone. ``window`` is the width of the source block: a
    lane block has to be a multiple of 128 (or the whole axis), so a
    64-wide page is cut out of a 128-wide window of the source.
    ``kv_group`` follows :data:`VMEM_BUDGET_BYTES` like the read's: one
    page block in and one out, and the source window, each
    double-buffered."""
    window = math.lcm(page_size, LANES)

    def step_bytes(kv_group: int) -> int:
        return 2 * kv_group * (2 * _vmem_tile_bytes(Dc, lanes, dtype)
                               + _vmem_tile_bytes(Dc, window, dtype))

    kv_group = max((g for g in range(1, KV + 1) if KV % g == 0
                    and step_bytes(g) <= VMEM_BUDGET_BYTES), default=1)
    return kv_group, window


def _write_kernel(layer_ref, page_ref, row_ref, win_ref, shift_ref, lo_ref,
                  hi_ref, leaf_ref, src_ref, out_ref):
    # scalar prefetch: the (1,) first layer and the work list. One grid
    # step is one page of one layer for one group of KV heads: the page
    # comes in, the source window is rotated so that its columns stand
    # at their offsets in the page, lanes [lo, hi) are taken from it and
    # the page goes back. A page that the step before also wrote is
    # still in VMEM (same block index: neither fetched again nor
    # written back in between), so the step goes on from the output
    # block and not from the stale input block.
    w = pl.program_id(2)
    lanes = out_ref.shape[-1]
    again = jnp.logical_and(
        w > 0, page_ref[jnp.maximum(w - 1, 0)] == page_ref[w])

    @pl.when(jnp.logical_not(again))
    def _fetch():
        out_ref[...] = leaf_ref[...]

    lo, hi, shift = lo_ref[w], hi_ref[w], shift_ref[w]
    narrow = out_ref.dtype.itemsize < 4
    for c in range(out_ref.shape[2]):
        cols = src_ref[0, 0, c]                           # (Dc, window)
        if narrow:
            # Mosaic rotates 32-bit lanes only ("Rotate with non-32-bit
            # data" is refused): bf16 / int8 rows ride packed in words,
            # which moves whole columns all the same
            cols = pltpu.bitcast(cols, jnp.int32)
        cols = pltpu.roll(cols, shift, axis=1)[:, :lanes]
        if narrow:
            cols = pltpu.bitcast(cols, out_ref.dtype)
        lane = jax.lax.broadcasted_iota(jnp.int32, cols.shape, 1)
        out_ref[0, 0, c] = jnp.where((lane >= lo) & (lane < hi), cols,
                                     out_ref[0, 0, c])


def paged_write(leaf: jax.Array, src: jax.Array, layer, work,
                page_size: int) -> jax.Array:
    """Write columns into the pool's stacked leaf IN PLACE and return it.

    Args:
      leaf: (L, P, KV, Dc, lanes), aliased to the result: the call
        touches the pages of its work list and nothing else.
      src: (Ls, R, KV, Dc, W) the new columns, positions-minor; ``W`` a
        multiple of :func:`plan_write`'s window. Layers
        ``layer .. layer + Ls - 1`` of the leaf are written from
        ``src[0] .. src[Ls - 1]`` with the same work list.
      layer: int32 scalar (traced), the first layer written.
      work: ``(page, row, win, shift, lo, hi, total)``, the first six
        int32 lists of one length, ``total`` how many of them run. Step
        ``w`` replaces columns ``[lo, hi)`` of page ``page[w]`` by window
        ``win[w]`` of ``src[:, row[w]]`` rotated right by ``shift[w]``
        lanes. A page may stand in two steps only if they follow each
        other. What is to be dropped (a sentinel page, a position out
        of range) is not in the list: see :func:`run_work`.
      page_size: positions a page holds, in the first of its lanes.
    """
    page_of, row_of, win_of, shift_of, lo_of, hi_of, total = work
    L, P, KV, Dc, lanes = leaf.shape
    Ls = src.shape[0]
    kv_group, window = plan_write(KV, Dc, page_size, lanes, leaf.dtype)
    assert src.shape[2:4] == (KV, Dc) and src.shape[4] % window == 0, \
        (src.shape, leaf.shape, window)

    def page_index(l, g, w, layer_ref, page_ref, *_):
        return (layer_ref[0] + l, page_ref[w], g, 0, 0)

    def src_index(l, g, w, layer_ref, page_ref, row_ref, win_ref, *_):
        return (l, row_ref[w], g, 0, win_ref[w])

    page_block = pl.BlockSpec((1, 1, kv_group, Dc, lanes), page_index)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(Ls, KV // kv_group, total),
        in_specs=[page_block,
                  pl.BlockSpec((1, 1, kv_group, Dc, window), src_index)],
        out_specs=page_block,
    )
    return pl.pallas_call(
        _write_kernel,
        name="paged_write",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(leaf.shape, leaf.dtype),
        input_output_aliases={7: 0},
        interpret=backend.pallas_interpret(),
    )(jnp.asarray(layer, jnp.int32).reshape(1), page_of, row_of, win_of,
      shift_of, lo_of, hi_of, leaf, src.astype(leaf.dtype))


def kept_first(keep, *values):
    """A dynamic grid's work list: the entries of each of ``values`` that
    ``keep`` marks, first and in order, flattened to int32, zeros after
    them, and last how many they are. A kernel's grid is that long: what
    follows the kept steps never runs."""
    order = jnp.argsort(jnp.logical_not(keep).reshape(-1), stable=True)
    return tuple(jnp.where(keep, x, 0).reshape(-1)[order]
                 .astype(jnp.int32) for x in values) \
        + (jnp.sum(keep, dtype=jnp.int32),)


def run_work(table: jax.Array, first: jax.Array, count: int, src_col,
             page_size: int, num_pages: int, window: int):
    """The work list of writing positions ``[first[r], first[r] + count)``
    of every row ``r`` through its table row: one step for each page
    that such a run touches, row by row in table order.

    ``src_col(r, pos)`` is the lane of the source at which row ``r``
    keeps position ``pos``. Dropped, and so not in the list: positions
    under 0 or past the table row's end, and entries that hold the
    sentinel ``num_pages`` ("drop" stays "touches nothing")."""
    R, maxP = table.shape
    ps = page_size
    first = jnp.asarray(first, jnp.int32)
    # entries a run of `count` columns can touch, wherever it starts
    n_e = min((count + ps - 2) // ps + 1, maxP)
    begin = jnp.clip(first, 0, maxP * ps)                  # (R,)
    end = jnp.clip(first + count, 0, maxP * ps)
    entry = begin[:, None] // ps + jnp.arange(n_e, dtype=jnp.int32)[None]
    lo_pos = jnp.maximum(entry * ps, begin[:, None])       # (R, n_e)
    hi_pos = jnp.minimum((entry + 1) * ps, end[:, None])
    page = jnp.take_along_axis(jnp.asarray(table, jnp.int32),
                               jnp.minimum(entry, maxP - 1), axis=1)
    keep = (hi_pos > lo_pos) & (page >= 0) & (page < num_pages)
    row = jnp.broadcast_to(jnp.arange(R, dtype=jnp.int32)[:, None],
                           entry.shape)
    lo = lo_pos - entry * ps
    col = src_col(row, lo_pos)                 # source lane of column lo
    work = (jnp.clip(page, 0, num_pages - 1), row, col // window,
            (lo - col) % window, lo, hi_pos - entry * ps)
    return kept_first(keep, *work)


def paged_write_columns(leaf: jax.Array, layer, cols: jax.Array,
                        table: jax.Array, starts: jax.Array, *,
                        page_size: Optional[int] = None,
                        active=None) -> jax.Array:
    """One decode, verify or chunk step's new columns into ONE layer of
    the stacked leaf, in place: ``cols`` (B, KV, Dc, T) goes to positions
    ``starts[b] .. starts[b] + T - 1`` of slot ``b`` through ``table``
    (B, pages_per_slot). A scale leaf (L, P, KV, lanes) takes
    (B, KV, T). ``page_size`` as in :func:`paged_decode_attention`;
    ``active`` (traced bool) False makes the work list empty: the leaf
    comes back as it went in. Returns the leaf."""
    H = backend.HEADS
    tail = (None,) * (leaf.ndim - 3)
    local = functools.partial(
        _paged_write_columns_local,
        page_size=leaf.shape[-1] if page_size is None else page_size)
    if active is not None:
        local = functools.partial(local,
                                  active=jnp.asarray(active, jnp.bool_))
    # the pool has no batch dim and is whole on every device of a batch
    # axis: each of them writes every slot's columns
    return backend.shard_kernel(
        local,
        (None, None, H) + tail,
        leaf=(leaf, (None, None, H) + tail),
        layer=(jnp.asarray(layer, jnp.int32).reshape(1), (None,)),
        cols=(cols, (None, H) + tail),
        table=(table, (None, None)),
        starts=(jnp.asarray(starts, jnp.int32), (None,)))


def _paged_write_columns_local(leaf, layer, cols, table, starts, *,
                               page_size, active=None):
    window = math.lcm(page_size, LANES)
    if cols.shape[-1] > window:
        # a slot's columns stand inside one window of the source: a run
        # wider than that goes window by window
        for j in range(0, cols.shape[-1], window):
            leaf = _paged_write_columns_local(
                leaf, layer, cols[..., j:j + window], table, starts + j,
                page_size=page_size, active=active)
        return leaf
    scale_leaf = leaf.ndim == 4
    if scale_leaf:            # (L, P, KV, lanes): the heads take Dc's place
        leaf, cols = leaf[:, :, None], cols[:, None]
    L, P, KV, Dc, lanes = leaf.shape
    B, T = cols.shape[0], cols.shape[-1]
    # slot b's columns at lanes [b * stride, b * stride + T) of one
    # positions-minor row: a power of two, so that no slot's columns
    # straddle two windows
    stride = 1 << (T - 1).bit_length()
    width = -(-B * stride // window) * window
    src = jnp.pad(cols, ((0, 0),) * 3 + ((0, stride - T),))
    src = src.transpose(1, 2, 0, 3).reshape(KV, Dc, B * stride)
    src = jnp.pad(src, ((0, 0), (0, 0), (0, width - B * stride)))
    work = run_work(table, starts, T,
                    lambda row, pos: row * stride + pos - starts[:, None],
                    page_size, P, window)
    # every slot is row 0 of the source: its lanes tell them apart
    work = (work[0], jnp.zeros_like(work[1])) + work[2:]
    if active is not None:
        work = work[:-1] + (jnp.where(active, work[-1], 0),)
    out = paged_write(leaf, src[None, None], layer[0], work, page_size)
    return out[:, :, 0] if scale_leaf else out


def paged_write_runs(leaf: jax.Array, dense: jax.Array, table: jax.Array,
                     first: jax.Array, count: int, *,
                     page_size: Optional[int] = None) -> jax.Array:
    """Runs of a dense positions-minor cache into EVERY layer of the
    stacked leaf, in place: positions ``[first[r], first[r] + count)`` of
    ``dense`` (L, R, KV, Dc, S) row ``r`` go through ``table``
    (R, pages_per_slot) to the pages (a chunk's window, a verify step's
    columns, whole prefilled rows at ``first = 0, count = S``). A scale
    leaf (L, P, KV, lanes) takes (L, R, KV, S). Returns the leaf."""
    H = backend.HEADS
    tail = (None,) * (leaf.ndim - 3)
    return backend.shard_kernel(
        functools.partial(
            _paged_write_runs_local, count=count,
            page_size=leaf.shape[-1] if page_size is None else page_size),
        (None, None, H) + tail,
        leaf=(leaf, (None, None, H) + tail),
        dense=(dense, (None, None, H) + tail),
        table=(table, (None, None)),
        first=(jnp.asarray(first, jnp.int32), (None,)))


def _paged_write_runs_local(leaf, dense, table, first, *, count, page_size):
    scale_leaf = leaf.ndim == 4
    if scale_leaf:
        leaf, dense = leaf[:, :, None], dense[:, :, None]
    L, P, KV, Dc, lanes = leaf.shape
    _, window = plan_write(KV, Dc, page_size, lanes, leaf.dtype)
    S = dense.shape[-1]
    if S % window:            # a cache shorter than one window (tests)
        dense = jnp.pad(dense, ((0, 0),) * 4 + ((0, -S % window),))
    work = run_work(table, first, min(count, S), lambda row, pos: pos,
                    page_size, P, window)
    out = paged_write(leaf, dense, 0, work, page_size)
    return out[:, :, 0] if scale_leaf else out
