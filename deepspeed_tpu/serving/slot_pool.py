"""Fixed-shape slot pool of per-slot KV cache.

The pool owns ONE statically-shaped cache pytree in the exact layout the
model's flax ``cache`` collection uses (``{"cache_store": {...}}`` with
k/v ``(L, num_slots, KV, cache_d, max_seq_len)``), allocated through the
module-declared :class:`~deepspeed_tpu.models.kv_cache_spec.KVCacheSpec`
— batch dimension = slots. Continuous batching then never changes a
shape: admitting, retiring and reusing slots are all data movement
inside the same buffers, so the jitted decode step compiles once and is
replayed for the server's lifetime (alive-masking: a retired slot is
padding, its garbage writes and attention contributions are masked out
by the per-slot ``index`` lengths, not by a recompile).

Admission writes a single-sequence prefill cache into the slot's batch
row with a dynamic-index update (slot id is a traced operand — one
compile covers every slot). The prefill cache is allocated at full
``max_seq_len`` by ``_CacheStore``, so the row write overwrites ALL of
the retired occupant's stale state, scales and garbage included.

A model of ``power_retention`` layers keeps a recurrent state in the
K/V's place (``KVCacheSpec.state``: one leaf ``s`` ``(L, num_slots, KV,
...)`` float32 and ``index``, no ``k`` / ``v``). The same data movement
carries it: ``admit`` / ``admit_rows`` write a prefilled state over the
row. What differs is what hides a row that does not run: a stale K/V
column is behind the row's ``index``, a state is behind nothing, so the
decode program is TOLD the rows that run (``ServingEngine._decode_step``)
and touches no other, and a seated request's first chunk, at position 0,
reads no state (``ops/attention/power_retention.py``): ``reset_row``
still moves the index alone.
"""

from __future__ import annotations

import heapq
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..parallel import mesh as mesh_mod
from ..telemetry.tracer import enqueue_span


class SlotPool:
    """``num_slots`` independently-occupied rows of one shared KV cache."""

    def __init__(self, spec: Any, num_slots: int, sharding: Any = None):
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        self.spec = spec
        self.num_slots = num_slots
        self.capacity = int(spec.max_seq_len)
        # sharding the owning engine's jitted steps emit: a single
        # Sharding applied to every leaf, or a PER-LEAF resolver
        # ``fn(key, leaf) -> Sharding`` (the parallel/axis_rules seam —
        # k/v shard over (data, model) while ``index`` shards only over
        # data); falls back to replicated-on-the-global-mesh for
        # standalone pools
        if sharding is None and mesh_mod.has_mesh():
            sharding = NamedSharding(mesh_mod.get_mesh(), PartitionSpec())
        self._sharding = sharding
        # every device call the pool makes goes through this (a program's
        # gets a ``serving/enqueue`` span); a server hands in its own,
        # which keeps the step's account too
        self.enqueue = enqueue_span
        # the flax "cache" collection pytree the engine's decode consumes
        self.cache: Dict[str, Any] = self._fresh_cache()
        # host mirror of the per-slot cache index (device truth lives in
        # cache["cache_store"]["index"]); decode needs the (B,) positions
        # each step and reading them back from device would sync
        self.starts = np.zeros((num_slots,), np.int32)
        self._free = list(range(num_slots))
        heapq.heapify(self._free)  # smallest slot first: deterministic layout
        # free-SET mirror of the heap: membership checks (the double-free
        # guard) are O(1) instead of an O(n) heap scan on every release
        self._free_set = set(self._free)
        # donate the pool (updated in place in HBM); the (L, 1, ...)
        # prefill cache is NOT donated — its shapes can never alias the
        # (L, num_slots, ...) outputs, so donating it only warns
        self._admit_jit = jax.jit(self._admit_row, donate_argnums=(0,))
        self._admit_rows_jit = jax.jit(self._admit_rows, donate_argnums=(0,))

    # ------------------------------------------------------------------
    def _leaf_sharding(self, key: str, leaf):
        """Where cache leaf ``key`` goes (``None``: wherever JAX puts
        it); the resolver reads ``leaf``'s shape alone."""
        return self._sharding(key, leaf) if callable(self._sharding) \
            else self._sharding

    def _place_leaf(self, key: str, leaf):
        """Commit one cache leaf to its sharding (see ``__init__``)."""
        sh = self._leaf_sharding(key, leaf)
        return leaf if sh is None else jax.device_put(leaf, sh)

    def _fresh_cache(self) -> Dict[str, Any]:
        """Zeroed pool pytree, committed to the replicated sharding the
        engine's jitted steps emit. A bare ``jnp.zeros`` pool is
        UNCOMMITTED, so the first admission would compile against
        ``UnspecifiedValue`` input shardings — one executable for the
        cold pool and a second once decode outputs (NamedSharding-
        committed) flow back in as the donated pool argument. Committing
        up front keeps each admit jit at exactly one executable for the
        pool's lifetime (the recompile watchdog pins this)."""
        store = self.spec.stacked_cache(self.num_slots)
        if self._sharding is not None:
            store = {k: self._place_leaf(k, v) for k, v in store.items()}
        return {"cache_store": store}

    def _index_from_mirror(self):
        """Device ``index`` rebuilt from the host mirror, committed like
        every other pool leaf (see :meth:`_fresh_cache` — a bare
        ``jnp.asarray`` would flip the leaf back to uncommitted and
        fork the admit/decode executables on sharding mismatch)."""
        # a copy made HERE, on the host: the device array may alias the
        # buffer it is put from or read it only when the transfer runs,
        # and the mirror is mutated in place by later advance() calls,
        # which a step that runs ahead makes while the program that takes
        # this leaf is still queued (``jnp.array(copy=True)`` copies on
        # the device, after the put: it read the next step's mirror)
        with self.enqueue("index", "transfer"):
            idx = jnp.asarray(np.array(self.starts))
            if self._sharding is not None:
                idx = self._place_leaf("index", idx)
        return idx

    # ------------------------------------------------------------------
    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def live_count(self) -> int:
        return self.num_slots - len(self._free)

    def alloc(self) -> int:
        if not self._free:
            raise RuntimeError("slot pool exhausted (scheduler bug: admit "
                               "called without a free slot)")
        slot = heapq.heappop(self._free)
        self._free_set.discard(slot)
        return slot

    def release(self, slot: int) -> None:
        """Return a slot to the free pool. Double-releasing corrupts the
        free heap (the slot would be granted to TWO requests whose cache
        rows then clobber each other), so it raises instead of silently
        corrupting ``free_count`` — the guard is an O(1) set-membership
        check against the heap's set mirror."""
        if not 0 <= slot < self.num_slots:
            raise ValueError(f"slot {slot} out of range "
                             f"[0, {self.num_slots})")
        if slot in self._free_set:
            raise RuntimeError(f"double release of slot {slot} (already "
                               f"free; scheduler/engine bug)")
        heapq.heappush(self._free, slot)
        self._free_set.add(slot)

    def reset(self) -> None:
        """Recovery path: free every slot and reallocate a zeroed device
        cache. Used after a mid-step exception — a failed dispatch may
        have consumed the donated cache buffers, so the old pytree can't
        be trusted (or even alive) afterwards."""
        self.cache = self._fresh_cache()
        self.starts[:] = 0
        self._free = list(range(self.num_slots))
        heapq.heapify(self._free)
        self._free_set = set(self._free)

    def reset_row(self, slot: int) -> None:
        """Zero a freshly-alloc'd slot's index (host mirror AND device)
        before an incremental (chunked) prefill starts writing it: the
        retired occupant's index would otherwise offset the first chunk's
        write. Pure index movement — the stale K/V itself is dead by
        masking and gets overwritten chunk by chunk."""
        self.starts[slot] = 0
        cs = dict(self.cache["cache_store"])
        cs["index"] = self._index_from_mirror()
        self.cache = {"cache_store": cs}

    # ------------------------------------------------------------------
    @staticmethod
    def _admit_row(pool: dict, pre: dict, slot, length):
        """Write the (L, 1, ...) prefill cache into batch row ``slot`` and
        set that slot's index to the TRUE prompt length (the prefill ran
        at a padded bucket width; attention masking and the next write
        offset both key off ``index``, so right-padding stays invisible)."""

        def write(dst, src):
            idx = (jnp.zeros((), jnp.int32), jnp.asarray(slot, jnp.int32)) + \
                (jnp.zeros((), jnp.int32),) * (dst.ndim - 2)
            return jax.lax.dynamic_update_slice(dst, src.astype(dst.dtype), idx)

        out = {k: write(pool[k], pre[k]) for k in pool if k != "index"}
        out["index"] = pool["index"].at[jnp.asarray(slot, jnp.int32)].set(
            jnp.asarray(length, jnp.int32), mode="drop")
        return out

    @staticmethod
    def _admit_rows(pool: dict, pre: dict, slots, lengths):
        """Scatter a BATCHED (L, nB, ...) prefill cache into ``nB`` slot
        rows in one program. ``slots``/``lengths`` are (nB,) int32 and
        traced, so one compile covers every slot combination at a given
        batch bucket; padding rows carry slot == num_slots, which JAX's
        scatter drop-mode discards instead of writing anywhere."""
        out = {k: pool[k].at[:, slots].set(pre[k].astype(pool[k].dtype),
                                           mode="drop")
               for k in pool if k != "index"}
        out["index"] = pool["index"].at[slots].set(
            jnp.asarray(lengths, jnp.int32), mode="drop")
        return out

    def admit_rows(self, prefill_cache: dict, slots, lengths) -> None:
        """Install ``nB`` prefilled sequences into ``nB`` slots (alloc'd
        by the caller) in ONE jitted multi-row scatter — the batched
        admission path. ``slots`` may contain the sentinel ``num_slots``
        for batch-bucket padding rows (dropped, never written); real
        entries must be alloc'd and in range."""
        slots = np.asarray(slots, np.int32)
        lengths = np.asarray(lengths, np.int32)
        if slots.shape != lengths.shape or slots.ndim != 1:
            raise ValueError(f"admit_rows needs matching 1-D slots/lengths; "
                             f"got {slots.shape} vs {lengths.shape}")
        real = slots < self.num_slots
        if np.any(lengths[real] > self.capacity):
            raise ValueError(f"sequence length {int(lengths[real].max())} "
                             f"exceeds slot capacity {self.capacity}")
        with self.enqueue("admit_rows", "transfer"):
            where = jax.device_put((slots, lengths))
        with self.enqueue("admit_rows"):
            self.cache = {"cache_store": self._admit_rows_jit(
                self.cache["cache_store"], prefill_cache["cache_store"],
                *where)}
        self.starts[slots[real]] = lengths[real]

    def admit(self, prefill_cache: dict, slot: int, length: int) -> None:
        """Install a prefilled sequence into ``slot`` (alloc'd by caller)."""
        if length > self.capacity:
            raise ValueError(f"sequence length {length} exceeds slot "
                             f"capacity {self.capacity}")
        with self.enqueue("admit_row", "transfer"):
            where = jax.device_put((np.int32(slot), np.int32(length)))
        with self.enqueue("admit_row"):
            self.cache = {"cache_store": self._admit_jit(
                self.cache["cache_store"], prefill_cache["cache_store"],
                *where)}
        self.starts[slot] = length

    def advance(self, lengths) -> None:
        """Advance the cache state machine after one decode/verify step.

        * ``advance(1)`` (scalar) — the uniform plain-decode case: every
          slot moved one position and the device ``index`` was ALREADY
          advanced inside the jitted step (dead-slot writes land in
          masked padding), so only the host mirror moves here.
        * ``advance(lengths)`` ((num_slots,) array) — the speculative
          case: slots accepted DIFFERENT numbers of tokens, while the
          verify program advanced the device ``index`` uniformly by
          K+1. The mirror advances per slot and the device ``index`` is
          overwritten from it — this IS the KV rollback: rejected draft
          positions beyond a slot's accepted length become masked
          padding (invisible to attention, overwritten by the next
          write) without reshaping or recompiling anything.
        """
        if np.ndim(lengths) == 0:
            self.starts += int(lengths)
            return
        lengths = np.asarray(lengths, np.int32)
        if lengths.shape != self.starts.shape:
            raise ValueError(f"advance lengths shape {lengths.shape} != "
                             f"({self.num_slots},)")
        self.starts += lengths
        cs = dict(self.cache["cache_store"])
        cs["index"] = self._index_from_mirror()
        self.cache = {"cache_store": cs}

    def consistency_errors(self) -> list:
        """Internal-bookkeeping audit for ``check_invariants()``: the
        free heap and its set mirror must agree exactly and every free
        slot must be a valid id. Returns human-readable violation
        strings (empty = healthy) instead of raising, so the engine can
        aggregate pool problems with its own request/slot cross-checks."""
        errors = []
        if len(self._free) != len(self._free_set):
            errors.append(f"free heap ({len(self._free)}) and free set "
                          f"({len(self._free_set)}) sizes differ")
        if set(self._free) != self._free_set:
            errors.append(f"free heap {sorted(self._free)} != free set "
                          f"{sorted(self._free_set)}")
        bad = [s for s in self._free_set
               if not 0 <= s < self.num_slots]
        if bad:
            errors.append(f"free slots out of range: {sorted(bad)}")
        if len(set(self._free)) != len(self._free):
            errors.append(f"duplicate slots in free heap: "
                          f"{sorted(self._free)}")
        return errors

    def positions(self) -> np.ndarray:
        """(num_slots,) decode positions, clamped into the allocation so
        long-dead slots can't push position-embedding lookups or cache
        writes past the last (masked) column. The host's copy: the decode
        and verify programs make the same vector from the device ``index``
        (``minimum(index, capacity - 1)``), which equals ``starts`` whenever
        one is queued, so nothing is put for them."""
        return np.minimum(self.starts, self.capacity - 1).astype(np.int32)
