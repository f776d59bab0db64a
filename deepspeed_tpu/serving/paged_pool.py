"""Paged KV pool: fixed-size pages + per-slot page tables + refcounted
copy-on-write prefix sharing over the serving slot pool.

Terminology map (for readers coming from the reference systems):

* **vLLM PagedAttention** — our *page* is vLLM's KV *block*
  (``page_size`` token columns of K/V across every layer of ONE layer
  group: every layer, for a model whose layers all keep every position;
  see "Layer groups" below); the
  ``(num_slots, max_pages_per_slot)`` int32 *page table* is vLLM's
  per-sequence block table; the free-page heap is the block allocator;
  ``num_pages < num_slots * max_pages_per_slot`` is oversubscription —
  slots reserve nothing, so HBM holds *actual* tokens, not worst-case
  rows.
* **SGLang RadixAttention** — the token-keyed
  :class:`~deepspeed_tpu.serving.prefix_cache.PrefixCache` trie is the
  radix tree; a page's refcount counts (slots mapping it) + (trie
  nodes caching it); admission walks the trie and maps shared pages
  for free, prefilling only the uncached suffix; the first divergent
  WRITE into a shared page triggers copy-on-write (one jitted
  page-to-page copy, then the writer's table entry swings to the
  fresh copy).

Shape discipline is identical to the contiguous
:class:`~deepspeed_tpu.serving.slot_pool.SlotPool`: physical storage is
ONE statically-shaped pytree — k/v ``(L, num_pages, KV, cache_d,
lanes)``, a page in the first ``page_size`` of its lanes — and with the
kernel off every jitted entry (decode, ``verify_k``,
``prefill_chunk``, batched admission) is a gather → existing traced
attention program → scatter composition:
:meth:`KVCacheSpec.dense_from_pages` reassembles the dense ``(L, B, KV,
cache_d, max_seq_len)`` view the compiled attention already consumes
(so the math — and greedy output — is BITWISE identical to the
contiguous pool), and only the columns the step actually wrote are
written back by page id. Page churn, prefix hits, CoW forks and
preempt/resume are all data movement inside the same buffers: zero
post-warmup recompiles, watchdog-enforced. The transient dense view is
scratch the compiler can schedule; the *persistent* HBM footprint is
the page pool — which is the served-requests-per-GB lever. With the
kernel active, decode, verify and (since PR 33) the prefill chunk skip
the dense view: the model writes and reads pages in place
(``decode_paged``; ``prefill_chunk`` with a table), and the dense
composition is the oracle they are tested against, not a served path.
A chunk that does not end its prompt, beside running slots, goes with
the decode rows as ONE program (PR 48: :meth:`PagedKVPool.run_chunk_decode`
over ``TransformerLM.chunk_beside_decode``): the weights are read once
for both groups of rows, the cache through each group's own kernels.
Batched admission of short prompts still hands in a full-capacity
prefill cache (ROADMAP S4).

ONE write path leads into the pool (PR 27): ``paged_write``
(``ops/attention/paged_attention.py``), a Pallas call that takes a
stacked leaf whole, rewrites the pages its work list names and returns
the leaf aliased — from the model's decode, verify and chunk steps for
their own columns, and from :meth:`PagedKVPool._write_runs` for
admitted rows and the kernel-off compositions. A page is stored in
whole 128-lane tiles (``models.kv_cache_spec.page_lanes``: k/v
``(L, num_pages, KV, cache_d, lanes)``), the one shape whose device
layout XLA and a Mosaic operand agree on; no program of a serving step
passes over a whole leaf.

Composition with the int8 packed cache: the page pool
allocates through the same module-declared ``KVCacheSpec``, so
quantized (int8, or int32-packed with ``cache_d = head_dim // 4``)
columns page exactly like full-precision ones, with per-column scales
paged alongside — paging multiplies with the 4x packed-footprint win
rather than replacing it.

Layer groups (PR 30): a model that mixes full-attention and
sliding-window layers (``KVCacheSpec.groups``) keeps TWO kinds of cache
in one pool, each with its own stacked leaf, page table and free heap.
The full group (``k`` / ``v`` / ``table``, ``num_pages``) is everything
above, unchanged. The window group (``k_win`` / ``v_win`` /
``table_win``, :class:`WindowRing`, a whole ring a slot) keeps a slot's
last ``sliding_window`` positions only: its table has the same LOGICAL
shape (an entry a ``page_size`` positions of the slot), but an entry
whose positions have all left the window is unmapped and its page goes
back to the group's free heap in the step that moves past it
(:meth:`PagedKVPool.ensure_writable`), so a slot maps at most
``sliding_window / page_size + 1`` pages (one more while a chunk that is
not page-aligned is written). The kernels take a group's leaf and table
and, for the window group, start at the first visible entry and mask
positions ``<= i - sliding_window`` inside it. A model without layer
kinds has exactly the single group it always had. What a ring does not
compose with yet refuses at construction, in the words of the one table
(``models/cache_kinds.py``, ``CACHE_REFUSALS``): the prefix cache (what
a hit means for pages that were recycled), cross-pool page transfer.

Latent pages (PR 38): a model with latent attention
(``KVCacheSpec.latent``) keeps ONE leaf, ``c`` ``(L, num_pages, W,
lanes)``: a page is ``W`` stored rows (the normed latent and the shared
rotary key, 576 at the served size) of ``page_size`` positions, one
whole-tile block a layer, and there is no ``v``. A latent page is
position-indexed like a K/V page, so the table, the free heap, the
refcounts, the prefix trie, copy-on-write, preemption and the audit are
the ones above; ``paged_write`` writes it in its scale-leaf form (one
"head" of ``W`` rows) and the model's read is
``ops/attention/latent_attention.py``.

A state group beside the pages (PR 47): a model of mamba layers beside
attention layers (``KVCacheSpec.state_group``) keeps K/V pages over its
attention layers, everything above, and for its mamba layers two leaves
that are NOT paged: ``s`` and ``conv``, ``num_slots`` rows each, a slot's state
and the last inputs of its convolution (a state has no positions to
page). They are allocated from the spec with the pages, go to every
program whole and come back aliased like the page leaves (the model's
kernels index (layer, row)), a bucketed admission's rows are scattered
over the slots' rows, and the decode program is told the rows that run
(``run_decode(..., rows)``), as the contiguous pool's is. Nothing zeroes
a row when a slot is seated: a row at position 0 reads neither its state
nor its tail, so seating costs no device call, and a preempted request
re-prefills from position 0 into whatever row it is given. The audit
holds the two leaves to the spec's shapes and dtypes and reads ``s``, the
float32 leaf, once: the rows that have run hold float32's mantissa
(``NARROW_STATE_WORDS``). Refused for the kind (the one table): the prefix
cache (a hit would need the state at its boundary), ``verify_k``, a handoff
of pages.

Sentinel convention: table entry ``num_pages`` means "unmapped" (the
window group's: its own number of pages). The
gather reads sentinel entries with a clip-mode take (arbitrary real
page — harmless, a slot's mapped region always covers its live
``[0, index)`` columns and attention masks the rest), and a write
leaves sentinel entries out of its work list, so a dead or padding row
can never touch a real page. The kernel read leaves a row that maps
nothing out of ITS work list too (no grid step: PR 31), so a freed
slot's rows of a kernel step are not attention output (finite, the
slot's own query rows); the step computes them on through the head like
any padding row and the host drops what it samples there.
"""

from __future__ import annotations

import heapq
from typing import Any, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..inference.engine import pack_chunk_args, unpack_chunk_args
from ..models.kv_cache_spec import page_lanes
from ..ops import backend
from .prefix_cache import PrefixCache
from .slot_pool import SlotPool


# the leaves of a cache container that hold pages: K/V a head with the
# scales of a quantized tier, or the one latent row a token (``c``,
# ``KVCacheSpec.latent``: (L, num_pages, W, lanes), no ``v``)
PAGE_LEAVES = ("k", "v", "k_scale", "v_scale", "c")
# ... and the index of learned sparse attention beside K/V under the same
# table (``KVCacheSpec.sparse``: (L, num_pages, KV, groups a page, D)
# float32 group means of the keys): a page's copy, transfer and audit take
# it along; the columns' write path does not (a dense row holds no means:
# ``_paged_admit_rows`` makes them from the row's keys)
INDEX_LEAF = "kc"

# the audit of a float32 state leaf: of the words a row that has run holds
# (zeros apart), the share whose low 16 bits are empty, which is to say the
# values bfloat16's 8 bits of mantissa hold whole. A state updated in
# float32 reads about 2**-16: 1.7e-5 to 3.4e-5 a row on the chip (64 rows
# of 18.9 M words, three runs of the served cell, PR 47); one held in
# bfloat16, or rounded to it as it is written, reads 1.0. A served TOKEN
# does not tell the two apart (forty bfloat16 layers round as much: the
# readings are in perf/reference/granite_hybrid.py), so the precision the
# spec states is held here, where the state lives. 2**-4 is 1,900 x the
# one reading and 16 x under the other
NARROW_STATE_WORDS = 2.0 ** -4


@jax.jit
def _narrow_words(leaf):
    """``(words, narrow)`` a row of a float32 state leaf ``(layers, rows,
    ...)``: the words that are not zero, and those of them that carry
    nothing below bfloat16's mantissa. One pass over the leaf, nothing of
    its size is made."""
    bits = jax.lax.bitcast_convert_type(leaf, jnp.uint32)
    axes = (0,) + tuple(range(2, leaf.ndim))
    some = bits != 0
    narrow = some & ((bits & 0xFFFF) == 0)
    return (some.sum(axes, dtype=jnp.int32),
            narrow.sum(axes, dtype=jnp.int32))


class PagePoolExhausted(RuntimeError):
    """No free page and nothing evictable: the caller must preempt a
    victim (freeing its pages) and retry, or fail the allocation."""


class WindowRing:
    """Host bookkeeping of the window group's pages: a free heap, a
    ``(num_slots, pages_per_slot)`` table of LOGICAL entries of which each
    slot maps only those its window still reaches, and how many were
    recycled. A page has one owner: nothing shares a window page."""

    def __init__(self, num_slots: int, pages_per_slot: int, page_size: int,
                 window: int, num_pages: int):
        self.page_size, self.window = page_size, int(window)
        self.num_pages = int(num_pages)
        self.pages_per_slot = pages_per_slot
        self.table = np.full((num_slots, pages_per_slot), self.num_pages,
                             np.int32)
        # entries under a slot's floor left its window at its last write
        self.floor = np.zeros((num_slots,), np.int64)
        self.recycled = 0
        self.reset()

    def reset(self) -> None:
        self._free = list(range(self.num_pages))
        heapq.heapify(self._free)
        self.table[:] = self.num_pages
        self.floor[:] = 0

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def mapped_count(self) -> int:
        return self.num_pages - len(self._free)

    def unmap_slot(self, slot: int) -> None:
        for pid in self.table[slot][self.table[slot] != self.num_pages]:
            heapq.heappush(self._free, int(pid))
        self.table[slot, :] = self.num_pages
        self.floor[slot] = 0

    def make_writable(self, slot: int, start: int, end: int) -> bool:
        """Before a step writes positions ``[start, end)`` of ``slot``:
        entries whose every position lies ``window`` or more behind
        ``start`` (no row of this or a later step sees them) go back to
        the heap, then the written entries are mapped: those a later
        step can see, so a whole prompt admitted at once maps one ring
        however long it is, and the write's work list drops the rest.
        Returns whether the table changed."""
        ps, row = self.page_size, self.table[slot]
        dead = max(start - self.window + 1, 0) // ps     # entries < dead
        old = np.nonzero(row[:dead] != self.num_pages)[0]
        for e in old:
            heapq.heappush(self._free, int(row[e]))
            row[e] = self.num_pages
        self.recycled += len(old)
        self.floor[slot] = dead
        changed = bool(len(old))
        first = max(start // ps, max(end - self.window + 1, 0) // ps)
        for e in range(first, (end - 1) // ps + 1):
            if row[e] == self.num_pages:
                if not self._free:
                    raise PagePoolExhausted(
                        f"window page group exhausted: {self.num_pages} "
                        f"pages all mapped")
                row[e] = heapq.heappop(self._free)
                changed = True
        return changed

    def audit(self, starts, free_slots) -> List[str]:
        """The ring's part of ``PagedKVPool.consistency_errors``."""
        errors = []
        mapped = self.table[self.table != self.num_pages]
        if len(set(mapped.tolist())) != len(mapped):
            errors.append("window group: a page is mapped twice")
        if set(mapped.tolist()) & set(self._free):
            errors.append("window group: a mapped page is on the free heap")
        if len(mapped) + len(self._free) != self.num_pages \
                or len(set(self._free)) != len(self._free):
            errors.append(
                f"window group: {len(mapped)} mapped + {len(self._free)} "
                f"free != {self.num_pages} pages")
        ps = self.page_size
        for slot in range(self.table.shape[0]):
            row, n = self.table[slot], int(starts[slot])
            if slot in free_slots:
                if np.any(row != self.num_pages):
                    errors.append(f"window group: free slot {slot} still "
                                  f"maps pages")
                continue
            lo, hi = max(n - self.window + 1, 0) // ps, -(-n // ps)
            if np.any(row[lo:hi] == self.num_pages):
                errors.append(
                    f"window group: slot {slot} at {n} has unmapped pages "
                    f"inside its window: row[{lo}:{hi}]="
                    f"{row[lo:hi].tolist()}")
            # what had left the window at the slot's last write is gone
            # (what has left it since goes at its next)
            if np.any(row[:self.floor[slot]] != self.num_pages):
                errors.append(
                    f"window group: slot {slot} at {n} still maps pages "
                    f"that left its window (entries before "
                    f"{int(self.floor[slot])})")
        return errors


class PagedKVPool(SlotPool):
    """Drop-in :class:`SlotPool` with paged storage and prefix caching.

    The host-side API (``alloc``/``release``/``advance``/``starts``/
    ``admit``/``admit_rows``/``reset``/``consistency_errors``) is the
    SlotPool contract; the jitted decode/verify/chunk entries live HERE
    (``run_decode``/``run_verify``/``run_prefill_chunk``) because they
    compose the engine's traced model functions with the pool's
    gather/scatter — the serving engine dispatches to them when paging
    is on.
    """

    def __init__(self, spec: Any, num_slots: int,
                 num_pages: Optional[int] = None, page_size: int = 64,
                 sharding: Any = None, prefix_cache: bool = True,
                 kernel: str = "auto"):
        if kernel not in ("auto", "on", "off"):
            raise ValueError(f"kernel must be auto|on|off, got {kernel!r}")
        why = (prefix_cache and spec.refusal("prefix_cache")) \
            or spec.refusal("paged_kv")
        if why:
            raise ValueError(why)
        groups = spec.groups
        capacity = int(spec.max_seq_len)
        page_size = int(page_size)
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        if capacity % page_size != 0:
            raise ValueError(
                f"page_size ({page_size}) must divide the KV capacity "
                f"({capacity}) so page tables tile the positions axis "
                f"exactly")
        self.page_size = page_size
        self.pages_per_slot = capacity // page_size
        P = (num_slots * self.pages_per_slot if num_pages is None
             else int(num_pages))
        if P < 1:
            raise ValueError(f"num_pages must be >= 1, got {P}")
        self.num_pages = P
        # -- host page bookkeeping (device truth: cache_store["table"]) --
        self.page_refs = np.zeros((P,), np.int32)
        self._free_pages = list(range(P))
        heapq.heapify(self._free_pages)   # smallest page first: deterministic
        self._free_page_set = set(self._free_pages)
        self.table = np.full((num_slots, self.pages_per_slot), P, np.int32)
        # slots whose rows of the mirrors (this table, the ring's) the
        # device tables have not seen; whole-table republications so far
        self._stale_rows: set = set()
        self.table_puts = 0
        self.cow_copies = 0
        self.page_evictions = 0
        self.pages_allocated = 0      # lifetime pops of the free list
        self.registry = None              # optional MetricsRegistry
        self.prefix = PrefixCache(page_size) if prefix_cache else None
        # the window group (None for a model of one layer kind): sized
        # so that every slot can hold its whole ring
        self.ring = None
        if groups is not None:
            window = groups[1][2]
            self.ring = WindowRing(
                num_slots, self.pages_per_slot, page_size, window,
                num_slots * (-(-window // page_size) + 1))
        self._table_keys = ("table",) + (("table_win",) if self.ring
                                         else ())
        # what the routed FFN counted in each program of the step
        # (moe.routed_ffn.call_stats, device arrays; the engine reads
        # them after its sync)
        self.moe_stats: List[Any] = []
        super().__init__(spec, num_slots, sharding=sharding)
        # engine-bound gather/scatter jits (built on first bind_engine;
        # the copy-page program needs nothing from the engine)
        self._engine = None
        self._paged_decode_jit = None
        self._paged_verify_jit = None
        self._paged_chunk_jit = None
        # fused paged-attention kernel selection (ISSUE 13): "off" keeps
        # the gather→dense-attention→scatter composition everywhere;
        # "on" forces the in-place page-table kernel (interpret mode
        # off-TPU — the bitwise-parity/CI configuration); "auto" uses
        # the kernel on TPU only. The dense composition remains the
        # oracle either way.
        self.kernel = kernel
        self._paged_decode_kernel_jit = None
        self._paged_verify_kernel_jit = None
        # a prefill chunk beside the decode rows as ONE program (built
        # with the kernel entries: it goes through the pages in place)
        self._paged_chunk_decode_jit = None
        self._jit_copy_page = jax.jit(self._copy_page_body,
                                      donate_argnums=(0,))
        # the cross-pool transfer is two programs, not one: replicas
        # live on DISJOINT meshes, and no single jit can span two
        # device sets — the source gathers the page batch on ITS
        # devices, the block hops meshes via an explicit device_put
        # (the "wire"), and the destination scatters on its own
        self._jit_gather_pages = jax.jit(self._gather_pages_body)
        self._jit_scatter_pages = jax.jit(self._scatter_pages_body,
                                          donate_argnums=(0,))
        self._admit_rows_jit = jax.jit(self._paged_admit_rows,
                                       donate_argnums=(0,))

    # ------------------------------------------------------------------
    # state containers
    # ------------------------------------------------------------------
    def _fresh_cache(self):
        """Zeroed page pool + sentinel table, committed like the dense
        pool (see SlotPool._fresh_cache for why commitment matters).
        The page leaves are BORN in their placement: made whole on the
        default device first and moved, a pool sharded over four chips
        stood twice on chip 0 while it was built (1.5 GB short of
        loading there, chip run of PR 27)."""
        shapes = jax.eval_shape(
            lambda: self.spec.paged_cache(
                self.num_pages, self.page_size,
                self.ring.num_pages if self.ring is not None else None,
                num_slots=self.num_slots))
        cs = {key: jnp.zeros(leaf.shape, leaf.dtype,
                             device=self._leaf_sharding(key, leaf))
              for key, leaf in shapes.items()}
        cs["index"] = self._place_leaf(
            "index", jnp.zeros((self.num_slots,), jnp.int32))
        cs["table"] = self._place_leaf(
            "table", jnp.full((self.num_slots, self.pages_per_slot),
                              self.num_pages, jnp.int32))
        if self.ring is not None:
            cs["table_win"] = self._place_leaf(
                "table_win", jnp.full_like(cs["table"],
                                           self.ring.num_pages))
        return {"cache_store": cs}

    def _table_from_mirror(self, key: str = "table"):
        # (a host copy, as ``SlotPool._index_from_mirror`` says why)
        tbl = jnp.asarray(np.array(
            self.table if key == "table" else self.ring.table))
        if self._sharding is not None:
            tbl = self._place_leaf(key, tbl)
        return tbl

    def _sync_table(self) -> None:
        """Republish the WHOLE device page tables from the host mirrors
        (same committed-leaf discipline as ``_index_from_mirror``): one
        put a layer group, counted in ``table_puts``. Who publishes what:
        a prefill chunk's program patches its own slot's row from the row
        it is handed (:meth:`run_prefill_chunk`), so the chunk's
        ``ensure_writable(..., sync=False)`` publishes nothing; every
        other change of a mirror (a release, a row reset, an admission's
        mappings, a decode slot crossing a page boundary) comes through
        here at once, and a row left stale any other way is published by
        :meth:`_publish_stale` before the next program that reads the
        device tables is queued."""
        cs = dict(self.cache["cache_store"])
        with self.enqueue("table", "transfer"):
            for key in self._table_keys:
                cs[key] = self._table_from_mirror(key)
        self.cache = {"cache_store": cs}
        self._stale_rows.clear()
        self.table_puts += 1

    def _publish_stale(self, own: Optional[int] = None) -> None:
        """Before a program that reads the device tables is queued: the
        whole tables again if a row the device has not seen is any but
        ``own`` (the slot whose row the program about to run patches
        itself)."""
        stale = self._stale_rows
        if stale and (len(stale) > 1 or own not in stale):
            self._sync_table()

    @staticmethod
    def _group_tables(table, win_table=None) -> dict:
        """One table a layer group, under the keys of a cache container."""
        return {"table": table} if win_table is None \
            else {"table": table, "table_win": win_table}

    def _tables(self, cs: dict) -> dict:
        """The page tables of a cache container, one a layer group."""
        return {key: cs[key] for key in self._table_keys}

    def _window_rows(self, slots) -> tuple:
        """The slots' rows of the window group's table (on the host: the
        caller puts them on the device with its other arguments), for
        the programs that take host-passed rows as ``win_tables``;
        nothing for a pool of one group. A slot id out of range (batch
        padding) gets an all-sentinel row, which writes nothing."""
        if self.ring is None:
            return ()
        slots = np.atleast_1d(np.asarray(slots, np.int64))
        real = slots < self.num_slots
        rows = np.full((len(slots), self.pages_per_slot),
                       self.ring.num_pages, np.int32)
        rows[real] = self.ring.table[slots[real]]
        return (rows,)

    def _inc(self, name: str, amount: float = 1.0) -> None:
        if self.registry is not None:
            self.registry.counter(name).inc(amount)

    # ------------------------------------------------------------------
    # page refcounting / allocation
    # ------------------------------------------------------------------
    @property
    def free_page_count(self) -> int:
        return len(self._free_pages)

    def evictable_page_count(self) -> int:
        """Pages reclaimable WITHOUT preempting anyone (trie-only refs)."""
        return self.prefix.evictable_pages(self) \
            if self.prefix is not None else 0

    def ref_page(self, pid: int) -> None:
        if not 0 <= pid < self.num_pages:
            raise ValueError(f"page {pid} out of range [0, {self.num_pages})")
        if self.page_refs[pid] <= 0:
            raise RuntimeError(f"ref_page({pid}) on a free page (allocator "
                               f"bug: free pages have no owner to share)")
        self.page_refs[pid] += 1

    def unref_page(self, pid: int) -> bool:
        """Drop one reference; returns True when the page became free."""
        if not 0 <= pid < self.num_pages:
            raise ValueError(f"page {pid} out of range [0, {self.num_pages})")
        if pid in self._free_page_set or self.page_refs[pid] <= 0:
            raise RuntimeError(f"double free of page {pid} (already free; "
                               f"pool/trie bug)")
        self.page_refs[pid] -= 1
        if self.page_refs[pid] == 0:
            heapq.heappush(self._free_pages, pid)
            self._free_page_set.add(pid)
            return True
        return False

    def alloc_page(self) -> int:
        """Pop a free page (refcount set to 1 for the caller's mapping).
        Under pressure, least-recently-matched trie-only pages are
        reclaimed first; raises :class:`PagePoolExhausted` when even the
        trie has nothing to give — the caller's cue to preempt."""
        if not self._free_pages and self.prefix is not None:
            freed = self.prefix.evict(self, 1)
            if freed:
                self.page_evictions += freed
                self._inc("paging/evictions", freed)
        if not self._free_pages:
            raise PagePoolExhausted(
                f"page pool exhausted: {self.num_pages} pages all "
                f"referenced and nothing evictable")
        pid = heapq.heappop(self._free_pages)
        self._free_page_set.discard(pid)
        self.page_refs[pid] = 1
        self.pages_allocated += 1
        return pid

    # ------------------------------------------------------------------
    # slot mapping (the mutable side of the page table)
    # ------------------------------------------------------------------
    def _unmap_slot(self, slot: int) -> None:
        sent = self.num_pages
        for pid in self.table[slot]:
            if pid != sent:
                self.unref_page(int(pid))
        self.table[slot, :] = sent
        self._stale_rows.add(slot)
        if self.ring is not None:
            self.ring.unmap_slot(slot)

    def release(self, slot: int) -> None:
        """Free the slot AND unreference its pages: exclusively-owned
        pages (generated suffix, CoW forks) return to the free pool
        immediately; trie-cached prompt pages stay warm for the next
        request with the same prefix."""
        super().release(slot)         # range + double-free validation
        self._unmap_slot(slot)
        self._sync_table()

    def reset(self) -> None:
        self.page_refs[:] = 0
        self._free_pages = list(range(self.num_pages))
        heapq.heapify(self._free_pages)
        self._free_page_set = set(self._free_pages)
        self.table[:] = self.num_pages
        self._stale_rows.clear()          # (a fresh cache: all sentinel)
        if self.ring is not None:
            self.ring.reset()
        self.moe_stats = []
        if self.prefix is not None:
            # the cached pages died with the pool; a fresh trie (not
            # clear()) avoids walking unref_page over freed state
            self.prefix = PrefixCache(self.page_size)
        super().reset()

    def reset_row(self, slot: int) -> None:
        self._unmap_slot(slot)
        super().reset_row(slot)
        self._sync_table()

    def ensure_writable(self, slot: int, start: int, end: int,
                        sync: bool = True) -> int:
        """Make positions ``[start, end)`` of ``slot`` safely writable
        BEFORE a jitted step writes them: unmapped pages are allocated;
        shared pages (refcount > 1) are copy-on-write forked — one
        jitted page copy, table entry swung to the fork, old page
        unref'd. Returns the number of CoW copies performed. May raise
        :class:`PagePoolExhausted` (already-made mappings stay valid;
        the caller preempts a victim and retries).

        ``sync`` says who tells the device: ``True`` republishes the
        tables here if a mapping changed (:meth:`_sync_table`; the decode
        path, one step in ``page_size`` a slot); ``False`` marks the
        slot's row stale and leaves it to the caller: a prefill chunk,
        whose program writes the row it is handed into the device table,
        or an admission, which republishes once for all its rows."""
        if end <= start:
            return 0
        end = min(end, self.capacity)
        sent = self.num_pages
        ncow = 0
        changed = False
        try:
            if self.ring is not None:
                recycled = self.ring.recycled
                changed = self.ring.make_writable(slot, start, end)
                if self.ring.recycled > recycled:
                    self._inc("paging/window_pages_recycled",
                              self.ring.recycled - recycled)
            for p in range(start // self.page_size,
                           (end - 1) // self.page_size + 1):
                pid = int(self.table[slot, p])
                if pid == sent:
                    self.table[slot, p] = self.alloc_page()
                    changed = True
                elif self.page_refs[pid] > 1:
                    fork = self.alloc_page()
                    try:
                        with self.enqueue("copy_page", "transfer"):
                            pages = jax.device_put((np.int32(pid),
                                                    np.int32(fork)))
                        with self.enqueue("copy_page"):
                            cs = self._jit_copy_page(self.cache["cache_store"],
                                                     *pages)
                    except Exception:
                        # copy dispatch died before the fork was mapped:
                        # return it to the free list (fresh refcount is 1)
                        # instead of stranding it until the next reset()
                        self.unref_page(fork)
                        raise
                    self.cache = {"cache_store": cs}
                    self.table[slot, p] = fork
                    self.unref_page(pid)
                    ncow += 1
                    changed = True
        except PagePoolExhausted:
            # the mappings made before the pool ran out stay valid, and
            # the device has not seen them
            self._stale_rows.add(slot)
            raise
        if changed:
            self._stale_rows.add(slot)
            if sync:
                self._sync_table()
        if ncow:
            self.cow_copies += ncow
            self._inc("paging/cow_copies", ncow)
        return ncow

    def map_prefix(self, slot: int, page_ids: Sequence[int],
                   sync: bool = True) -> None:
        """Map a trie hit's pages into the slot's table (positions
        ``[0, len(page_ids) * page_size)``) — the near-zero-cost half of
        a prefix hit: one refcount bump per page, no prefill."""
        for i, pid in enumerate(page_ids):
            if self.table[slot, i] != self.num_pages:
                raise RuntimeError(f"map_prefix over occupied entry "
                                   f"({slot}, {i})")
            self.ref_page(int(pid))
            self.table[slot, i] = int(pid)
        if len(page_ids):
            self._stale_rows.add(slot)
            if sync:
                self._sync_table()

    def seat_prefix(self, slot: int, page_ids: Sequence[int],
                    prefill_pos: int) -> None:
        """Seat a prefix-hit admission: map the shared pages, position
        the chunked prefill at ``prefill_pos``, and up-front CoW every
        mapped page at or beyond it. The eager CoW matters: decode steps
        interleave with chunked prefill and write (masked) garbage at
        the slot's index each dispatch — those writes must never land in
        a page another request still reads."""
        self.map_prefix(slot, page_ids, sync=False)
        hit_len = len(page_ids) * self.page_size
        self.starts[slot] = prefill_pos
        self.ensure_writable(slot, prefill_pos,
                             max(hit_len, prefill_pos + 1), sync=False)
        self._publish_seat()

    def _publish_seat(self) -> None:
        """A seating's index and table republished in ONE rebind (a
        window group composes with neither seating path, so the full
        group's table is all there is)."""
        cs = dict(self.cache["cache_store"])
        cs["index"] = self._index_from_mirror()
        cs["table"] = self._table_from_mirror()
        self.cache = {"cache_store": cs}
        self._stale_rows.clear()

    def cache_prefix(self, slot: int, tokens) -> int:
        """Publish the slot's freshly-prefilled FULL prompt pages into
        the prefix trie (called once per request when its prefill
        completes). Only full pages are cached — the trailing partial
        page keeps taking this slot's decode writes."""
        if self.prefix is None:
            return 0
        n_full = int(np.asarray(tokens).reshape(-1).shape[0]) \
            // self.page_size
        if n_full == 0:
            return 0
        pages = [int(p) for p in self.table[slot, :n_full]]
        if any(p == self.num_pages for p in pages):
            raise RuntimeError(f"cache_prefix: slot {slot} prompt pages "
                               f"not fully mapped: {pages}")
        return self.prefix.insert(tokens, pages, self)

    # ------------------------------------------------------------------
    # cross-pool page transfer (disaggregated prefill -> decode handoff)
    # ------------------------------------------------------------------
    @property
    def page_nbytes(self) -> int:
        """Bytes one page occupies across every cache leaf (what a
        cross-pool transfer moves per page)."""
        cs = self.cache["cache_store"]
        return sum(int(np.prod(cs[k].shape)) * cs[k].dtype.itemsize
                   // self.num_pages
                   for k in PAGE_LEAVES + (INDEX_LEAF,) if k in cs)

    def import_pages(self, src_pool: "PagedKVPool",
                     src_page_ids: Sequence[int]) -> List[int]:
        """Copy ``src_page_ids`` out of ANOTHER pool's storage into
        freshly allocated pages here — the device half of a
        disaggregated prefill->decode handoff. One fixed-shape jitted
        gather + one donated scatter per call (id vectors sentinel-
        padded to ``pages_per_slot``, the block hopping meshes between
        them), so every transfer — any page count, any replica pair —
        reuses the same two compiled programs.

        Ownership contract: the returned destination pages carry
        refcount 1 OWNED BY THE CALLER until :meth:`seat_pages` maps
        them into a slot's table. The source pool's references are
        untouched — the source slot's ``release()`` drops them exactly
        once, after the copy. On ANY failure (allocation or copy
        dispatch) every destination page allocated so far is unref'd
        before the exception propagates (the :meth:`ensure_writable`
        unwind template), so a mid-transfer death leaks nothing on
        either pool."""
        why = self.spec.refusal("roles") or src_pool.spec.refusal("roles")
        if why:
            raise ValueError(why)
        ids = [int(p) for p in src_page_ids]
        if len(ids) > self.pages_per_slot:
            raise ValueError(
                f"import_pages: {len(ids)} pages exceed pages_per_slot "
                f"({self.pages_per_slot}) — a transfer moves at most one "
                f"slot's table per call")
        if (src_pool.page_size != self.page_size
                or src_pool.num_pages != self.num_pages
                or src_pool.pages_per_slot != self.pages_per_slot):
            raise ValueError(
                f"import_pages needs identical page geometry on both "
                f"pools (one compiled transfer program); got src="
                f"{src_pool.num_pages}x{src_pool.page_size} vs dst="
                f"{self.num_pages}x{self.page_size}")
        for pid in ids:
            if pid in src_pool._free_page_set \
                    or src_pool.page_refs[pid] <= 0:
                raise ValueError(f"import_pages: source page {pid} is "
                                 f"free (nothing to copy)")
        dst: List[int] = []
        try:
            for _ in ids:
                dst.append(self.alloc_page())
            src_vec = np.full((self.pages_per_slot,),
                              src_pool.num_pages, np.int32)
            dst_vec = np.full((self.pages_per_slot,),
                              self.num_pages, np.int32)
            src_vec[:len(ids)] = ids
            dst_vec[:len(dst)] = dst
            cs = self._dispatch_transfer(src_pool, src_vec, dst_vec)
        except Exception:
            # unwind: pages allocated for a transfer that never landed
            # go straight back to the free list (fresh refcount is 1)
            self.unref_pages(dst)
            raise
        self.cache = {"cache_store": cs}
        self._inc("paging/pages_imported", len(dst))
        return dst

    def unref_pages(self, page_ids: Sequence[int]) -> None:
        """Drop one reference on each page — the bulk unwind of an
        :meth:`import_pages` batch whose seating failed (the caller
        still owns every page in the batch; :meth:`seat_pages` is
        atomic, so failure means NONE were taken)."""
        for pid in page_ids:
            self.unref_page(int(pid))

    def _land_block(self, block: dict) -> dict:
        """Move a gathered page block onto THIS pool's devices — the
        wire hop of a disaggregated transfer (replicas live on disjoint
        meshes; a same-mesh handoff makes this a no-op). Placement goes
        through :meth:`_place_leaf` so the block the scatter sees here
        is committed exactly like the block its bind-time precompile
        saw — the difference between zero and one executable."""
        return {k: self._place_leaf(k, v) for k, v in block.items()}

    def _dispatch_transfer(self, src_pool: "PagedKVPool",
                           src_vec, dst_vec):
        """The traced dispatch of a cross-pool transfer: id vectors
        arrive already sentinel-padded to ``pages_per_slot``, so every
        call replays the SAME two compiled programs — the source pool's
        gather, then (after the block hops onto this pool's devices)
        this pool's donated scatter (graftcheck drives exactly this
        method)."""
        block = src_pool._jit_gather_pages(
            src_pool.cache["cache_store"], jnp.asarray(src_vec))
        block = self._land_block(block)
        return self._jit_scatter_pages(
            self.cache["cache_store"], block, jnp.asarray(dst_vec))

    def seat_pages(self, slot: int, page_ids: Sequence[int],
                   prefill_pos: int, first_entry: int = 0) -> None:
        """Seat imported pages into ``slot`` at ``prefill_pos``: the
        slot's table TAKES the caller's :meth:`import_pages` references
        (no refcount bump — ownership transfers to the table) and
        index+table republish in one rebind (the :meth:`seat_prefix`
        idiom). ``first_entry`` offsets the table entries — a
        prefix-affine adopt maps trie-hit pages at ``[0, first_entry)``
        via :meth:`map_prefix` and seats only the transferred tail
        here. The decode loop resumes exactly where the source
        replica's prefill stopped."""
        ids = [int(p) for p in page_ids]
        need = -(-int(prefill_pos) // self.page_size)
        if first_entry + len(ids) < need:
            raise ValueError(
                f"seat_pages: {first_entry}+{len(ids)} pages cannot back "
                f"prefill_pos={prefill_pos} (live region needs {need})")
        # validate EVERYTHING before the first table write: seating is
        # atomic, so a caller's unwind never has to ask which pages a
        # half-failed seat already took
        for i, pid in enumerate(ids):
            if self.table[slot, first_entry + i] != self.num_pages:
                raise RuntimeError(f"seat_pages over occupied entry "
                                   f"({slot}, {first_entry + i})")
            if pid in self._free_page_set or self.page_refs[pid] <= 0:
                raise RuntimeError(f"seat_pages: page {pid} is free "
                                   f"(import its data first)")
        for i, pid in enumerate(ids):
            self.table[slot, first_entry + i] = pid
        self.starts[slot] = int(prefill_pos)
        self._publish_seat()

    # ------------------------------------------------------------------
    # jitted gather/scatter programs
    # ------------------------------------------------------------------
    @staticmethod
    def _copy_page_body(cs: dict, src, dst):
        """One page-to-page K/V copy (the CoW fork), all layers in one
        program; src/dst are traced scalars so one compile covers every
        page pair."""
        out = dict(cs)
        with jax.named_scope("copy"):
            for key in PAGE_LEAVES + (INDEX_LEAF,):
                if key not in cs:
                    continue
                leaf = cs[key]
                page = jax.lax.dynamic_slice_in_dim(leaf, src, 1, 1)
                out[key] = jax.lax.dynamic_update_slice_in_dim(leaf, page,
                                                               dst, 1)
        return out

    @staticmethod
    def _gather_pages_body(src_cs: dict, src_ids):
        """Source half of a cross-pool transfer (the prefill->decode
        handoff): pull the sentinel-padded page batch out of the source
        pool's storage as one fixed-width (``pages_per_slot``) block
        per leaf — the transfer's wire format. A sentinel id clip-reads
        an arbitrary real page; its paired sentinel destination entry
        drops the write on the other side, so ONE compile covers every
        transfer size — the same trick the admission scatter uses.
        Runs on the SOURCE pool's devices."""
        with jax.named_scope("gather"):
            return {key: jnp.take(src_cs[key], src_ids, axis=1, mode="clip")
                    for key in PAGE_LEAVES + (INDEX_LEAF,)
                    if key in src_cs}

    @staticmethod
    def _scatter_pages_body(dst_cs: dict, block: dict, dst_ids):
        """Destination half: seat the gathered block at ``dst_ids``
        (sentinel entries drop), all layers in one donated in-place
        program. Runs on the DESTINATION pool's devices — the block
        arrived via :meth:`_land_block`."""
        out = dict(dst_cs)
        with jax.named_scope("scatter"):
            for key in PAGE_LEAVES + (INDEX_LEAF,):
                if key not in dst_cs:
                    continue
                out[key] = dst_cs[key].at[:, dst_ids].set(
                    block[key].astype(dst_cs[key].dtype), mode="drop")
        return out

    def pages_touched(self, slots, first, count: int) -> int:
        """How many pages a write of positions ``[first[i], first[i] +
        count)`` of ``slots[i]`` touches, from the host's mirror of the
        table: the length of the write's work list for one leaf of one
        layer (``pool_writes`` on the dispatch spans). Unmapped entries
        and positions out of range are dropped there and not counted
        here."""
        slots = np.atleast_1d(np.asarray(slots, np.int64))
        first = np.atleast_1d(np.asarray(first, np.int64))
        lo = np.clip(first, 0, self.capacity)[:, None]
        hi = np.clip(first + count, 0, self.capacity)[:, None]
        entry = np.arange(self.pages_per_slot)[None, :] * self.page_size
        spanned = (entry < hi) & (entry + self.page_size > lo)
        touched = int(np.sum(spanned
                             & (self.table[slots] != self.num_pages)))
        if self.ring is not None:       # (a layer of either group)
            touched += int(np.sum(
                spanned & (self.ring.table[slots] != self.ring.num_pages)))
        return touched

    def reads_in_place(self, count: int) -> bool:
        """Whether a dispatch of ``count`` query rows a slot reads and
        writes the pages in place (``paged_decode`` / ``paged_write``) or
        is the dense composition's: the kernel is active, and where there
        is a window group no row's keys have left the ring before the
        step is over. A ring maps the entries a LATER step can see
        (:meth:`WindowRing.make_writable`), so a step of ``window`` rows
        or more writes columns its own later rows need into entries the
        ring does not hold; the dense row holds them."""
        return self._paged_decode_kernel_jit is not None and (
            self.ring is None or count < self.ring.window)

    def pages_a_read_step(self, count: int) -> int:
        """The pages ONE grid step of the kernel read folds for a
        dispatch of ``count`` query rows a slot: a block of the latent
        read (``latent_attention.pages_a_step``, from the same shapes the
        call gives it), one page of K/V."""
        if not self.spec.latent:
            return 1
        from ..ops.attention.latent_attention import call_rows, pages_a_step

        return pages_a_step(
            call_rows(count, self.spec.kv_heads), self.spec.latent,
            self.spec.latent_rank, page_lanes(self.page_size),
            self.spec.dtype)

    def pages_read(self, count: int, slots=None, starts=None):
        """``(steps, slots, pages)`` of the kernel read's work list for a
        dispatch of ``count`` query rows a slot, from the host's mirror
        of the table and ``starts``: the grid steps of ONE layer of each
        page group (``live_pages`` / ``_window_pages`` in NumPy; the
        latent read's steps are blocks, ``page_blocks``), how many slots
        have a step at all, and the pages the steps fold (``pool_reads``
        / ``read_slots`` / ``pool_read_pages`` on the decode, verify and
        chunk spans; pages over steps x :meth:`pages_a_read_step` is how
        full a block is, and a step is a page where that is 1). Every
        slot at its position
        for a decode or verify step; ``slots`` at ``starts`` for a chunk,
        which runs one. A slot whose row maps nothing is not in the list;
        ``num_slots - slots`` is how many steps a call does not make.
        ``None`` where the dispatch is the dense composition's
        (:meth:`reads_in_place`), which has no work list."""
        if not self.reads_in_place(count):
            return None
        per_slot, ps = self.pages_per_slot, self.page_size
        if slots is None:
            slots = np.arange(self.num_slots)
            starts = self.positions()
        slots = np.atleast_1d(np.asarray(slots, np.int64))
        start = np.atleast_1d(np.asarray(starts, np.int64))
        seen = -(-(start + count) // ps)
        # the row's leading mapped entries (argmax: its first sentinel)
        unmapped = np.pad(self.table[slots] == self.num_pages,
                          ((0, 0), (0, 1)), constant_values=True)
        mapped = np.argmax(unmapped, axis=1)
        live = np.clip(np.minimum(seen, mapped), np.minimum(mapped, 1),
                       per_slot)
        if self.ring is not None:
            ring = self.ring
            mapped = np.max(np.where(ring.table[slots] != ring.num_pages,
                                     np.arange(per_slot) + 1, 0), axis=1)
            first = np.clip((start - ring.window + 1) // ps, 0,
                            per_slot - 1)
            live = live + np.clip(np.minimum(seen, mapped) - first,
                                  np.minimum(mapped, 1), per_slot)
        # (a block is one page wherever there is a ring)
        steps = -(-live // self.pages_a_read_step(count))
        return int(steps.sum()), int(np.count_nonzero(live)), \
            int(live.sum())

    def _write_runs(self, pool: dict, dense: dict, tables: dict, first,
                    count: int):
        """Traced: write positions ``[first[r], first[r] + count)`` of
        every row ``r`` of the dense view ((L, B, KV, cd, S), aligned
        with ``tables`` (a (B, max_pages_per_slot) table a layer group:
        each group's layers of the dense view go through its table into
        its leaf) into the page pool —
        the ONE write path into it beside the model's own column write,
        and the same Pallas call (``paged_write``): it takes each
        stacked leaf whole, rewrites the pages its work list names and
        returns the leaf aliased. Positions out of range and sentinel
        table entries are not in the list — they touch nothing."""
        from ..ops.attention.paged_attention import paged_write_runs

        out = dict(pool)
        with jax.named_scope("scatter"):
            for key in PAGE_LEAVES:
                if key not in pool:
                    continue
                if self.ring is None:
                    out[key] = paged_write_runs(
                        pool[key], dense[key], tables["table"], first,
                        count, page_size=self.page_size)
                    continue
                for suffix, layers, _ in self.spec.groups:
                    out[key + suffix] = paged_write_runs(
                        pool[key + suffix],
                        dense[key][np.asarray(layers)],
                        tables["table" + suffix], first, count,
                        page_size=self.page_size)
        return out

    def _paged_admit_rows(self, pool: dict, pre: dict, rows_tables,
                          slots, lengths, win_tables=None):
        """Batched paged admission: write every column of the (full-
        capacity) prefill cache through host-passed per-row tables.
        Padding rows are ALL-sentinel tables (not just a sentinel slot
        id — indexing the device table with a clamped sentinel slot
        would alias a real slot's pages), so they write nothing. A state
        group's rows go over the slots' rows, as the contiguous pool
        writes them (a padding row's slot id is out of range: dropped)."""
        nB = rows_tables.shape[0]
        out = self._write_runs(pool, pre,
                               self._group_tables(rows_tables, win_tables),
                               jnp.zeros((nB,), jnp.int32), self.capacity)
        for key in self.spec.state_leaves:
            out[key] = pool[key].at[:, slots].set(
                pre[key].astype(pool[key].dtype), mode="drop")
        if INDEX_LEAF in pool:
            # the index's group means of the rows' REAL keys (a group that
            # a row's length cuts holds the part that has arrived, as the
            # steps that follow go on from it): (L, B, KV, D, S) ->
            # (L, B, entries, KV, groups a page, D) through the tables
            leaf, k = pool[INDEX_LEAF], pre["k"]
            L, B, KV, D, S = k.shape
            st, G = self.spec.index_stride, leaf.shape[3]
            real = jnp.arange(S)[None, :] < jnp.asarray(lengths)[:, None]
            means = jnp.where(real[None, :, None, None, :],
                              k.astype(jnp.float32), 0.0).reshape(
                L, B, KV, D, S // st, st).sum(-1) / st
            means = means.reshape(L, B, KV, D, S // st // G, G).transpose(
                0, 1, 4, 2, 5, 3)
            out[INDEX_LEAF] = leaf.at[:, rows_tables].set(means, mode="drop")
        out["index"] = pool["index"].at[slots].set(
            jnp.asarray(lengths, jnp.int32), mode="drop")
        return out

    def bind_engine(self, engine: Any) -> None:
        """Build the jitted decode/verify/chunk wrappers over the
        engine's traced model functions. Composition, not duplication:
        the SAME ``decode_fn`` / verify body / ``prefill_chunk`` method
        the contiguous path compiles runs against the gathered dense
        view, which is what makes paged greedy output bitwise identical
        to the contiguous pool; with the kernel active the same methods
        take the pool's leaves and tables and go through the pages
        (:meth:`reads_in_place`). Idempotent per engine (rebinding would
        shed the recompile watchdog's wrappers)."""
        if self._engine is engine and self._paged_decode_jit is not None:
            return
        if getattr(engine, "_decode_fn", None) is None:
            raise ValueError("PagedKVPool.bind_engine needs a built "
                             "InferenceEngine (LM module with decode())")
        from ..inference.engine import _filter_logits
        from .spec_decode.verify import make_verify_fn

        self._engine = engine
        spec = self.spec
        decode_fn = engine._decode_fn
        verify_body = make_verify_fn(decode_fn, _filter_logits)
        module = getattr(engine, "_serve_module", None) or engine.module
        dequant = engine._dequant
        chunk_gen = getattr(module, "prefill_chunk", None)
        write_runs = self._write_runs
        tables_of = self._tables
        grouped = self.ring is not None
        # a state group beside the pages (``KVCacheSpec.state_group``):
        # rows of the slots that the programs take whole, are told the
        # running rows for, and hand back beside the page leaves
        state_leaves = spec.state_leaves
        # a model with a routed FFN reports what it counted through the
        # "stats" collection (models/transformer_lm.py)
        want_stats = bool(getattr(getattr(module, "config", None),
                                  "n_experts", 0))
        mutable = ["cache", "stats"] if want_stats else ["cache"]

        def gather(vals, tables):
            return spec.dense_from_pages(
                vals, tables if grouped else tables["table"])

        def dense_cache(cs):
            with jax.named_scope("gather"):
                dense = gather(cs, tables_of(cs))
            dense["index"] = cs["index"]
            return {"cache_store": dense}

        # every step program takes what the device already holds from
        # the device: ``token`` is the server's (B,) current-token twin
        # as it is and the positions are the cache's own ``index``
        # (``decode_fn`` adds the axis and holds the index inside the
        # allocation, as ``positions()`` does on the host)
        def paged_decode(params, cs, token, rows=None):
            logits, new = decode_fn(params, dense_cache(cs), token,
                                    rows=rows)
            ncs = new["cache_store"]
            # one column written per row
            out = write_runs(cs, ncs, tables_of(cs), cs["index"], 1)
            out.update({key: ncs[key] for key in state_leaves})
            out["index"] = ncs["index"]
            return logits, out, None

        def paged_verify(params, cs, cur, draft, draft_len, rng,
                         temperature, greedy, top_k, top_p):
            new, out_tok, n_emit, rng = verify_body(
                params, dense_cache(cs), cur, draft, draft_len, rng,
                temperature, greedy, top_k, top_p)
            ncs = new["cache_store"]
            # K+1 columns written per row
            out = write_runs(cs, ncs, tables_of(cs), cs["index"],
                             draft.shape[1] + 1)
            out["index"] = ncs["index"]
            return out, out_tok, n_emit, rng

        def chunk_args(packed):
            # a chunk's host-built vector taken apart (``pack_chunk_args``):
            # the ids, the scalars and the slot's row of each group's
            # table as a table of one row
            ids, slot, start, length, last_idx, *rows = unpack_chunk_args(
                packed, self.pages_per_slot, len(self._table_keys))
            return ids, slot, start, length, last_idx, \
                self._group_tables(*(row[None] for row in rows))

        def patched_tables(cs, row_tables, slot):
            # the device tables with the row the program was handed: the
            # host mapped the chunk's fresh pages in its mirror alone
            return {key: jax.lax.dynamic_update_slice(
                cs[key], row, (slot, jnp.zeros((), jnp.int32)))
                for key, row in row_tables.items()}

        def paged_chunk(params, cs, packed):
            # ONE slot's chunk through its table row (and the window
            # group's where there is one), which arrive with the ids and
            # the scalars as one vector
            ids, slot, start, length, last_idx, row_tables = \
                chunk_args(packed)
            state_row = {"rows": slot[None]} if state_leaves else {}
            if self.reads_in_place(ids.shape[1]):
                # as kernel_apply does for a decode step: the model
                # takes the stacked leaves whole and a table of one row,
                # and every layer writes the chunk's columns into its
                # pages and reads them back in place. No dense row.
                vals = {k: v for k, v in cs.items()
                        if k not in row_tables}
                vals["index"] = start[None]
                out, vars_ = module.apply(
                    {"params": dequant(params),
                     "cache": {"cache_store": vals}},
                    ids, start[None], last_idx,
                    table=row_tables if grouped else row_tables["table"],
                    method=chunk_gen, mutable=mutable, **state_row)
                outcs = dict(vars_["cache"]["cache_store"])
            else:
                # the dense composition (the oracle): gather the slot's
                # dense row from its pages, run the window-masked chunk,
                # scatter back only the chunk window
                with jax.named_scope("gather"):
                    dense = gather(cs, row_tables)
                dense["index"] = start[None]
                out, vars_ = module.apply(
                    {"params": dequant(params),
                     "cache": {"cache_store": dense}},
                    ids, start[None], last_idx, method=chunk_gen,
                    mutable=mutable, **state_row)
                new = vars_["cache"]["cache_store"]
                outcs = write_runs(cs, new, row_tables, start[None],
                                   ids.shape[1])
                outcs.update({key: new[key] for key in state_leaves})
            outcs["index"] = cs["index"].at[slot].set(start + length,
                                                      mode="drop")
            outcs.update(patched_tables(cs, row_tables, slot))
            return out, outcs, vars_["stats"]["moe"] if want_stats else None

        self._paged_decode_jit = jax.jit(paged_decode, donate_argnums=(1,))
        # (the key comes back where the engine keeps it: key_sharding)
        verify_out = (None, None, None, engine.key_sharding)
        self._paged_verify_jit = jax.jit(paged_verify, donate_argnums=(1,),
                                         static_argnums=(8, 9),
                                         out_shardings=verify_out)
        self._paged_chunk_jit = (jax.jit(paged_chunk, donate_argnums=(1,))
                                 if chunk_gen is not None else None)

        # -- fused paged-attention kernel entries (ISSUE 13) -----------
        # Same jit signatures as the dense compositions above, but the
        # model step runs ``decode_paged``: column writes go through the
        # page table at the source (``paged_write``) and the Pallas kernel
        # reads pages in place, one grid step for each live page of a slot
        # with every head in it — the dense (L, B, KV, cd, S) scratch view
        # is never built, and no slice of a leaf either. Greedy decode
        # output is bitwise-identical (for each head the kernel folds one
        # page at a time in table order, which is decode_attention at
        # block_s=page_size; see ops/attention/paged_attention.py).
        if self.kernel_active \
                and getattr(module, "decode_paged", None) is not None:
            capacity = self.capacity

            def kernel_apply(params, cache, token, rows=None):
                cs = cache["cache_store"]
                more = {} if rows is None else {"rows": rows}
                if token.ndim == 1:
                    token = token[:, None]
                pos = jnp.minimum(cs["index"], capacity - 1)
                tables = tables_of(cs)
                vals = {k: v for k, v in cs.items() if k not in tables}
                logits, vars_ = module.apply(
                    {"params": dequant(params),
                     "cache": {"cache_store": vals}},
                    token, pos, tables if grouped else tables["table"],
                    method=module.decode_paged, mutable=mutable, **more)
                new = dict(vars_["cache"]["cache_store"], **tables)
                return logits, {"cache_store": new}, \
                    vars_["stats"]["moe"] if want_stats else None

            def kernel_decode_fn(params, cache, token):
                return kernel_apply(params, cache, token)[:2]

            def kernel_decode(params, cs, token, rows=None):
                logits, new, stats = kernel_apply(
                    params, {"cache_store": cs}, token, rows)
                return logits, new["cache_store"], stats

            kernel_verify_body = make_verify_fn(kernel_decode_fn,
                                                _filter_logits)

            def kernel_verify(params, cs, cur, draft, draft_len, rng,
                              temperature, greedy, top_k, top_p):
                new, out_tok, n_emit, rng = kernel_verify_body(
                    params, {"cache_store": cs}, cur, draft, draft_len,
                    rng, temperature, greedy, top_k, top_p)
                return new["cache_store"], out_tok, n_emit, rng

            def paged_chunk_beside_decode(params, cs, packed, token,
                                          rows=None):
                # ``paged_chunk`` and ``kernel_decode`` of one step as ONE
                # pass over the layers (``chunk_beside_decode``): a weight
                # is read once for the chunk's rows and the decode rows.
                # The decode rows see the device as the chunk program
                # leaves it, its slot's row in the tables and its index
                # past the chunk, and the program hands back what the
                # decode program after it would have
                ids, slot, start, length, last_idx, row_tables = \
                    chunk_args(packed)
                tables = patched_tables(cs, row_tables, slot)
                vals = {k: v for k, v in cs.items() if k not in tables}
                vals["index"] = cs["index"].at[slot].set(start + length,
                                                         mode="drop")
                more = {} if rows is None else {"rows": rows,
                                                "chunk_row": slot[None]}
                (chunk_logits, logits), vars_ = module.apply(
                    {"params": dequant(params),
                     "cache": {"cache_store": vals}},
                    ids, start[None], last_idx,
                    row_tables if grouped else row_tables["table"],
                    token, jnp.minimum(vals["index"], capacity - 1),
                    tables if grouped else tables["table"],
                    method=module.chunk_beside_decode, mutable=mutable,
                    **more)
                new = dict(vars_["cache"]["cache_store"], **tables)
                return chunk_logits, logits, new, \
                    vars_["stats"]["moe"] if want_stats else None

            self._paged_decode_kernel_jit = jax.jit(kernel_decode,
                                                    donate_argnums=(1,))
            self._paged_verify_kernel_jit = jax.jit(
                kernel_verify, donate_argnums=(1,), static_argnums=(8, 9),
                out_shardings=verify_out)
            if chunk_gen is not None and getattr(
                    module, "chunk_beside_decode", None) is not None:
                self._paged_chunk_decode_jit = jax.jit(
                    paged_chunk_beside_decode, donate_argnums=(1,))
        # pre-compile the CoW copy program with a no-op self-copy: the
        # first real fork can land arbitrarily late (a prefix hit on a
        # page some earlier request published), easily after warmup
        # traffic ends — and the strict watchdog rightly counts ANY
        # post-warmup compile
        zero = jnp.asarray(0, jnp.int32)
        self.cache = {"cache_store": self._jit_copy_page(
            self.cache["cache_store"], zero, zero)}
        # same treatment for both halves of the cross-pool transfer: a
        # decode-role replica sees its first page import whenever the
        # router's first handoff lands — typically long after warmup
        # traffic ends — and a prefill-role replica's gather fires at
        # the same moment from the other side. All-sentinel id vectors
        # make the pair a no-op (the clip-gather reads garbage, every
        # scatter write drops); the block rides _land_block so its
        # committed placement here matches what a real transfer ships.
        sent_ids = jax.device_put(jnp.full((self.pages_per_slot,),
                                           self.num_pages, jnp.int32))
        block = self._land_block(self._jit_gather_pages(
            self.cache["cache_store"], sent_ids))
        self.cache = {"cache_store": self._jit_scatter_pages(
            self.cache["cache_store"], block, sent_ids)}

    # ------------------------------------------------------------------
    # jitted entry points (the serving engine dispatches here when paged)
    # ------------------------------------------------------------------
    @property
    def kernel_active(self) -> bool:
        """Whether decode/verify dispatch to the fused paged-attention
        kernel ("on": always, interpret mode off-TPU; "auto": TPU only;
        "off": never — dense gather/scatter composition everywhere)."""
        if self.kernel == "off":
            return False
        if self.kernel == "on":
            return True
        return backend.on_tpu()

    def run_decode(self, engine: Any, tokens, *rows):
        """One masked decode step for every slot over paged storage;
        updates the pool state in place and returns the logits. ``tokens``
        is the (B,) current-token vector; the positions are the device
        ``index``, which equals the host's ``starts`` whenever a decode is
        queued (:meth:`SlotPool.positions`). ``rows``: for a pool with a
        state group, the (B,) rows that run (``ServingEngine._state_rows``);
        a K/V pool is never given it."""
        self.bind_engine(engine)
        self._publish_stale()
        # direct attribute dispatch on both arms (not `fn = a or b;
        # fn(...)`): the watchdog and graftcheck identify watched
        # programs by the attribute the call goes through; each arm
        # rebinds self.cache immediately — its cache operand is donated
        with self.enqueue("decode"):
            if self._paged_decode_kernel_jit is not None:
                logits, cs, stats = self._paged_decode_kernel_jit(
                    engine.params, self.cache["cache_store"], tokens, *rows)
                self.cache = {"cache_store": cs}
            else:
                logits, cs, stats = self._paged_decode_jit(
                    engine.params, self.cache["cache_store"], tokens, *rows)
                self.cache = {"cache_store": cs}
        if stats is not None:
            self.moe_stats.append(stats)
        return logits

    def run_verify(self, engine: Any, cur, draft, draft_len, rng,
                   temperature, greedy, top_k: int, top_p: float):
        """Speculative verify over paged storage (same semantics as
        ``InferenceEngine.verify_k``); returns ``(out, n_emit, rng')``.
        With the kernel active the K + 1 query rows a slot read and write
        the pages in place whatever K is (:meth:`reads_in_place`)."""
        self.bind_engine(engine)
        self._publish_stale()
        with self.enqueue("verify_k"):
            if self.reads_in_place(draft.shape[1] + 1):
                cs, out, n_emit, rng = self._paged_verify_kernel_jit(
                    engine.params, self.cache["cache_store"], cur, draft,
                    draft_len, rng, temperature, greedy, int(top_k),
                    float(top_p))
                self.cache = {"cache_store": cs}
            else:
                cs, out, n_emit, rng = self._paged_verify_jit(
                    engine.params, self.cache["cache_store"], cur, draft,
                    draft_len, rng, temperature, greedy, int(top_k),
                    float(top_p))
                self.cache = {"cache_store": cs}
        return out, n_emit, rng

    def run_prefill_chunk(self, engine: Any, ids, slot: int, start: int,
                          length: int, last_idx: int):
        """One bounded prefill chunk into ``slot``'s pages at offset
        ``start`` (pages covering the window must already be writable —
        the engine calls :meth:`ensure_writable` first, with
        ``sync=False``). Returns the chunk's (1, 1, V) logits.

        What only the host knows goes in ONE int32 vector: the ids, the
        four scalars and the slot's row of each group's table from the
        host mirror (``pack_chunk_args``), handed to the jitted call as
        the NumPy array it is: the chunk's one transfer, made by the call
        (on the chip's host 0.22 ms with the call against 0.36 for a put
        and the call and 1.27 for a put of the seven arrays: PERF.md §6,
        PR 35). The program reads and writes
        the pages through that row and writes it into the device table
        too, so the pages the chunk mapped are published by the program
        that fills them and the table is not put again; only a stale row
        of ANOTHER slot brings the whole table first
        (:meth:`_publish_stale`)."""
        self.bind_engine(engine)
        if self._paged_chunk_jit is None:
            raise ValueError("run_prefill_chunk requires a module with "
                             "prefill_chunk(); the TransformerLM family "
                             "has one")
        packed = self._chunk_vector(ids, slot, start, length, last_idx)
        with self.enqueue("chunk"):
            logits, cs, stats = self._paged_chunk_jit(
                engine.params, self.cache["cache_store"], packed)
        self.cache = {"cache_store": cs}
        self._stale_rows.discard(slot)
        if stats is not None:
            self.moe_stats.append(stats)
        return logits

    def _chunk_vector(self, ids, slot: int, start: int, length: int,
                      last_idx: int):
        """A chunk's arguments as its program takes them: the tables
        published but for the slot's own row (the program patches it),
        then the ONE vector with that row from the host's mirror."""
        self._publish_stale(own=slot)
        with self.enqueue("chunk", "transfer"):
            return pack_chunk_args(
                ids, slot, start, length, last_idx, self.table[slot],
                *self._window_rows([slot]))

    def fuses(self, count: int) -> bool:
        """Whether a chunk of ``count`` tokens beside a decode step can go
        as ONE program (:meth:`run_chunk_decode`): the program was built
        (the kernel is active and the model has the pass) and a chunk of
        that width reads its pages in place (:meth:`reads_in_place`)."""
        return self._paged_chunk_decode_jit is not None \
            and self.reads_in_place(count)

    def run_chunk_decode(self, engine: Any, ids, slot: int, start: int,
                         length: int, last_idx: int, tokens, *rows):
        """:meth:`run_prefill_chunk` of ``slot`` and :meth:`run_decode` of
        every slot as ONE program, one pass over the layers
        (``TransformerLM.chunk_beside_decode``). Arguments, publication of
        the tables and what the device holds afterwards are those of the
        two calls in that order: the chunk's arguments ride in one vector,
        the token twin and the state ``rows`` are taken as they are, the
        chunk's slot row is written into the device tables, its index is
        ``start + length + 1`` and every other slot's is advanced by one.
        Returns the chunk's (1, 1, V) logits and the decode rows' (the
        server drops the first: a chunk that ends a prompt keeps the two
        programs, the second of which decodes from the token the first
        one's head chose)."""
        self.bind_engine(engine)
        packed = self._chunk_vector(ids, slot, start, length, last_idx)
        with self.enqueue("chunk_decode"):
            chunk_logits, logits, cs, stats = self._paged_chunk_decode_jit(
                engine.params, self.cache["cache_store"], packed, tokens,
                *rows)
        self.cache = {"cache_store": cs}
        self._stale_rows.discard(slot)
        if stats is not None:
            self.moe_stats.append(stats)
        return chunk_logits, logits

    def warm_chunk_decode(self, engine: Any, width: int, tokens,
                          *rows) -> None:
        """Bring :meth:`run_chunk_decode`'s program in with a call that
        leaves the pool as it was: the harness's warm-up drains a request a
        pass, so no step of it carries a chunk beside a running slot, and
        the first real one would compile inside the measured window. The
        chunk is of no token through an all-sentinel row (every write of
        it drops) and the decode rows are a masked step's (``rows``: none
        runs); the row and the indices it moved are put again from the
        host's mirrors."""
        if not self.fuses(width):
            return
        nobody = self.num_slots         # (out of range: all-sentinel rows)
        packed = pack_chunk_args(
            np.zeros((1, width), np.int32), nobody, 0, 0, 0,
            np.full((self.pages_per_slot,), self.num_pages, np.int32),
            *self._window_rows([nobody]))
        _, _, cs, _ = self._paged_chunk_decode_jit(
            engine.params, self.cache["cache_store"], packed, tokens, *rows)
        cs = dict(cs)
        cs["index"] = self._index_from_mirror()
        self.cache = {"cache_store": cs}
        self._sync_table()

    # ------------------------------------------------------------------
    # admission (SlotPool API, paged storage)
    # ------------------------------------------------------------------
    def _admit_scatter(self, prefill_cache: dict, slots: np.ndarray,
                       lengths: np.ndarray) -> None:
        nB = len(slots)
        rows = np.full((nB, self.pages_per_slot), self.num_pages, np.int32)
        for i, s in enumerate(slots):
            if s < self.num_slots:
                rows[i] = self.table[s]
        self._sync_table()       # publish ensure_writable's new mappings
        with self.enqueue("admit_rows", "transfer"):
            args = jax.device_put((rows, slots, lengths)
                                  + self._window_rows(slots))
        with self.enqueue("admit_rows"):
            self.cache = {"cache_store": self._admit_rows_jit(
                self.cache["cache_store"], prefill_cache["cache_store"],
                *args)}
        real = slots < self.num_slots
        self.starts[slots[real]] = lengths[real]

    def admit(self, prefill_cache: dict, slot: int, length: int) -> None:
        if length > self.capacity:
            raise ValueError(f"sequence length {length} exceeds slot "
                             f"capacity {self.capacity}")
        self.ensure_writable(slot, 0, length, sync=False)
        self._admit_scatter(prefill_cache,
                            np.asarray([slot], np.int32),
                            np.asarray([length], np.int32))

    def admit_rows(self, prefill_cache: dict, slots, lengths) -> None:
        slots = np.asarray(slots, np.int32)
        lengths = np.asarray(lengths, np.int32)
        if slots.shape != lengths.shape or slots.ndim != 1:
            raise ValueError(f"admit_rows needs matching 1-D slots/lengths; "
                             f"got {slots.shape} vs {lengths.shape}")
        real = slots < self.num_slots
        if np.any(lengths[real] > self.capacity):
            raise ValueError(f"sequence length {int(lengths[real].max())} "
                             f"exceeds slot capacity {self.capacity}")
        for s, T in zip(slots[real], lengths[real]):
            self.ensure_writable(int(s), 0, int(T), sync=False)
        self._admit_scatter(prefill_cache, slots, lengths)

    # ------------------------------------------------------------------
    # audit / stats
    # ------------------------------------------------------------------
    def page_stats(self) -> dict:
        free = len(self._free_pages)
        stats = {"pages_total": self.num_pages,
                 "pages_free": free,
                 "pages_in_use": self.num_pages - free,
                 "refcounted_pages": int(np.sum(self.page_refs > 1)),
                 "cow_copies": self.cow_copies,
                 "page_evictions": self.page_evictions,
                 "page_size": self.page_size}
        if self.ring is not None:
            stats.update(window_pages_total=self.ring.num_pages,
                         window_pages_in_use=self.ring.mapped_count,
                         window_pages_recycled=self.ring.recycled)
        if self.prefix is not None:
            stats.update(
                prefix_hits=self.prefix.hits,
                prefix_misses=self.prefix.misses,
                prefix_hit_tokens=self.prefix.hit_tokens,
                prefix_nodes=self.prefix.num_nodes,
                prefix_evictable_pages=self.evictable_page_count())
        return stats

    def _narrow_state_rows(self, key: str, leaf) -> List[str]:
        """The audit's reading of a float32 state leaf (the one device read
        of the audit): the rows of seated slots that have run a position
        hold float32's mantissa (``NARROW_STATE_WORDS``)."""
        ran = [slot for slot in range(self.num_slots)
               if slot not in self._free_set and self.starts[slot] > 0]
        if not ran:
            return []
        words, narrow = (np.asarray(n)[ran] for n in _narrow_words(leaf))
        bad = {slot: float(m) / float(n)
               for slot, n, m in zip(ran, words, narrow)
               if m > NARROW_STATE_WORDS * n}
        if not bad:
            return []
        return [f"state leaf {key!r} is float32 and "
                f"{min(bad.values()):.3f}-{max(bad.values()):.3f} of the "
                f"words of rows {sorted(bad)[:8]} carry nothing below "
                f"bfloat16's mantissa (limit {NARROW_STATE_WORDS}): the "
                f"state is held or rounded narrower than the spec states"]

    def consistency_errors(self) -> List[str]:
        """SlotPool's audit plus the page bookkeeping invariants: the
        free-page heap/set mirrors agree, every refcount equals the
        references actually held (table entries + trie nodes), zero-ref
        pages are exactly the free ones, free slots map nothing, and
        every live slot's ``[0, index)`` columns are page-backed."""
        errors = super().consistency_errors()
        if self.ring is not None:
            errors += self.ring.audit(self.starts, self._free_set)
        if self.spec.state_leaves:
            # the state group: a row a slot beside the pages, in the
            # spec's shapes and dtypes, and a float32 leaf's rows in
            # float32's precision
            want = jax.eval_shape(lambda: self.spec.paged_cache(
                self.num_pages, self.page_size, num_slots=self.num_slots))
            cs = self.cache["cache_store"]
            for key in self.spec.state_leaves:
                got = cs.get(key)
                if got is None or (got.shape, got.dtype) != (
                        want[key].shape, want[key].dtype):
                    errors.append(
                        f"state leaf {key!r} is "
                        f"{None if got is None else (got.shape, got.dtype)}"
                        f", the spec's is "
                        f"{(want[key].shape, want[key].dtype)}")
                elif got.dtype == jnp.float32:
                    errors += self._narrow_state_rows(key, got)
        if self.spec.index_stride:
            # the index beside the pages: a leaf of group means a page, in
            # the spec's shape and float32 (the choice is made against it)
            want = (self.spec.kv_layers, self.num_pages, self.spec.kv_heads,
                    self.page_size // self.spec.index_stride,
                    self.spec.head_dim)
            got = self.cache["cache_store"].get(INDEX_LEAF)
            if got is None or (got.shape, got.dtype) != (want, jnp.float32):
                errors.append(
                    f"index leaf {INDEX_LEAF!r} is "
                    f"{None if got is None else (got.shape, got.dtype)}, "
                    f"the spec's is {(want, jnp.dtype(jnp.float32))}")
        P, sent = self.num_pages, self.num_pages
        if len(self._free_pages) != len(self._free_page_set):
            errors.append(f"free page heap ({len(self._free_pages)}) and "
                          f"set ({len(self._free_page_set)}) sizes differ")
        if set(self._free_pages) != self._free_page_set:
            errors.append("free page heap and set mirrors disagree")
        if len(set(self._free_pages)) != len(self._free_pages):
            errors.append("duplicate pages in free heap (double free)")
        bad = [p for p in self._free_page_set if not 0 <= p < P]
        if bad:
            errors.append(f"free pages out of range: {sorted(bad)}")
        held = np.zeros((P,), np.int64)
        for pid in self.table.reshape(-1):
            pid = int(pid)
            if pid == sent:
                continue
            if not 0 <= pid < P:
                errors.append(f"table references page {pid} out of range")
                continue
            held[pid] += 1
        if self.prefix is not None:
            for pid, c in self.prefix.page_counts().items():
                if not 0 <= pid < P:
                    errors.append(f"trie references page {pid} out of range")
                else:
                    held[pid] += c
        mism = np.nonzero(held != self.page_refs)[0]
        if len(mism):
            show = mism[:8].tolist()
            errors.append(
                f"page refcounts disagree with held references at pages "
                f"{show}: refs={self.page_refs[mism][:8].tolist()} "
                f"held={held[mism][:8].tolist()}")
        zero_ref = set(np.nonzero(self.page_refs == 0)[0].tolist())
        if zero_ref != self._free_page_set:
            errors.append(
                f"zero-ref pages != free pages: only-zero-ref="
                f"{sorted(zero_ref - self._free_page_set)[:8]} "
                f"only-free={sorted(self._free_page_set - zero_ref)[:8]}")
        for slot in range(self.num_slots):
            row = self.table[slot]
            if slot in self._free_set:
                if np.any(row != sent):
                    errors.append(f"free slot {slot} still maps pages "
                                  f"{row[row != sent].tolist()}")
                continue
            n_live = -(-int(self.starts[slot]) // self.page_size)
            if np.any(row[:n_live] == sent):
                errors.append(
                    f"slot {slot} live region [0, {int(self.starts[slot])})"
                    f" has unmapped pages: row={row[:n_live].tolist()}")
        return errors
