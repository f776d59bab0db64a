"""The KV-cache container of a ``TransformerConfig``, in one place: the
per-layer layout (:func:`kv_cache_spec`), the lane padding of a page
(:func:`page_lanes`) and :class:`KVCacheSpec`, from which the module's
cache variables, the slot pool, the page pool and ZeRO-Inference allocate
and which answers what a model of its kinds refuses
(``models/cache_kinds.py`` holds the table)."""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax.numpy as jnp
import numpy as np

from .cache_kinds import cache_kinds, kv_cache_groups, refusal
from .lm_config import TransformerConfig

_PACK_DISABLED_WARNED: set = set()


def kv_cache_spec(cfg: TransformerConfig):
    """The single source of truth for the KV-cache container: returns
    ``(cache_dtype, cache_d, kv_packed)`` — the per-layer k/v arrays are
    (B, KV, cache_d, max_seq_len). Used by CachedAttention (reads/
    writes), _CacheStore (allocation) and make_layer_kv_cache
    (ZeRO-Inference allocation) so the layout can never drift apart."""
    D = cfg.head_dim
    if cfg.kv_cache_quant and cfg.kv_cache_packed is not False and D % 4 != 0:
        if cfg.kv_cache_packed is True:
            raise ValueError(
                f"kv_cache_packed=True requires head_dim % 4 == 0 (the int32 "
                f"container packs 4 head-dim rows per word); head_dim={D}. "
                f"Use kv_cache_packed=None (auto) or False, or pad n_embd.")
        if D not in _PACK_DISABLED_WARNED:  # auto: warn once per head_dim
            _PACK_DISABLED_WARNED.add(D)
            from ..utils.logging import logger

            logger.warning(
                f"int32 KV-cache packing disabled: head_dim={D} is not a "
                f"multiple of 4; falling back to the plain int8 container "
                f"(risk: Mosaic's (4,1)-packed s8 carry layout — see "
                f"kv_cache_packed in TransformerConfig)")
    kv_packed = (cfg.kv_cache_quant and cfg.kv_cache_packed is not False
                 and D % 4 == 0)
    if kv_packed:
        return jnp.int32, D // 4, True
    if cfg.kv_cache_quant:
        return jnp.int8, D, False
    return cfg.dtype, D, False


def page_lanes(page_size: int) -> int:
    """The minor dimension a page of ``page_size`` columns is stored
    with: whole 128-lane tiles. A Mosaic kernel takes its operands
    row-major, where a 64-wide page fills half of each tile anyway; the
    TPU client, left to store a ``(..., 128, 64)`` leaf as it likes,
    puts the head dim minor instead, and XLA then wraps every kernel
    call in a copy of the whole leaf to row-major and back (``copy.107
    / .110`` of the decode program before PR 27; four copies of the
    stacked leaf a step, 8.5 ms each, once the kernels took it whole:
    chip runs of PR 27). A leaf whose minor dimension IS the lane tile
    has one layout everybody agrees on, at the bytes the row-major
    layout takes in any case: twice a page's at ``page_size`` 64, none
    extra at 128. (Pinning the layout of a 64-wide leaf with
    ``jax.experimental.layout`` works within one process and breaks the
    persistent compile cache: a deserialized executable reports default
    layouts for its row-major operands; chip run of PR 27.)"""
    return -(-page_size // 128) * 128


@dataclasses.dataclass(frozen=True)
class KVCacheSpec:
    """Module-declared KV-cache allocation contract: everything an engine
    needs to size, allocate and bound a cache WITHOUT inferring layout
    from pytree leaf shapes. ``stacked_cache``/``layer_cache``
    build zeroed containers in the exact layout CachedAttention reads and
    writes; the serving slot pool allocates through this (batch dim =
    slots) and ``InferenceEngine.generate`` takes ``max_seq_len`` as the
    authoritative capacity."""

    n_layer: int
    kv_heads: int
    head_dim: int          # logical per-head width
    cache_d: int           # stored sublane dim (head_dim, or //4 packed)
    dtype: Any
    max_seq_len: int
    quantized: bool
    packed: bool
    groups: Optional[tuple] = None     # kv_cache_groups(cfg): the layers
    # of each group of a page pool; the contiguous containers below keep
    # every layer at full length (a window layer's old columns are masked)
    latent: int = 0                    # latent attention: the width of the
    # one row a token a layer that every head reads. Such a cache holds
    # ``c`` (L, B, latent, S) positions-minor and no k / v; a page pool's
    # leaf is (L, P, latent, lanes), a page one whole-tile block a layer
    latent_rank: int = 0               # of which the values: the row's
    # leading ``kv_lora_rank`` stored rows
    kinds: tuple = ()                  # cache_kinds(cfg): what refusal() reads
    rep: int = 1                       # query heads that share a KV head
    # (GQA): the rows of the page read's block (paged_attention.block_rows)
    sparse: Optional[tuple] = None     # learned sparse attention's sizes
    # (ops/attention/sparse_index.SparseSizes). A page pool then keeps,
    # beside k / v and under the same table, the leaf ``kc`` (L, P, KV,
    # page_size // kernel_stride, head_dim) float32: the means of each
    # group of ``kernel_stride`` consecutive keys, which the choice of
    # blocks is made against. A contiguous cache keeps none: its keys lie
    # in one piece
    state_group: Optional[tuple] = None    # the layers that keep a state a
    # row (no positions) and their leaves: ``(layers, ((leaf, shape a row
    # a layer, dtype), ...))``. A leaf of the cache is ``(layers, rows,
    # *shape)``; a row belongs to a sequence (a slot of a pool: a page pool
    # keeps the group beside its page leaves, ``num_slots`` rows). A model
    # of power_retention layers: every layer, one leaf ``s`` ``(KV,
    # *state)`` float32. A model of mamba layers beside attention layers:
    # the mamba layers, ``s`` (ops/state_space.state_shape) float32 and
    # ``conv`` (the convolution's last taps - 1 inputs, time-major) in
    # ``dtype``; the attention layers keep K/V (:attr:`kv_layers`). A model
    # of kda layers beside attention layers: the kda layers, ``s`` (heads,
    # d, d) float32 and ``conv`` likewise; the attention layers keep K/V or
    # the latent row. A model of gdn layers beside attention layers: the
    # gdn layers, ``s`` (value heads, d, d) float32 and ``conv`` (the last
    # taps - 1 inputs of the one convolution over [q ; k ; v]); the
    # attention layers keep K/V. A model of conv layers beside attention
    # layers: the
    # conv layers, ONE leaf ``conv`` (the convolution's last taps - 1
    # inputs) and no ``s``. A state's size does not depend on max_seq_len,
    # which stays the bound on positions

    @property
    def index_stride(self) -> int:
        """The keys a group of the index's leaf ``kc`` averages; 0: none."""
        return self.sparse.kernel_stride if self.sparse else 0

    @property
    def state_leaves(self) -> tuple:
        """The leaves that hold a state a row (no positions): what a
        program is told the running rows for."""
        return tuple(leaf for leaf, _, _ in self.state_group[1]) \
            if self.state_group else ()

    @property
    def state(self) -> Optional[tuple]:
        """A model with a state in EVERY layer and no k / v
        (power_retention): the shape of one KV head's state
        (ops/attention/power_retention.state_shape); None otherwise."""
        if self.state_group and not self.kv_layers:
            return self.state_group[1][0][1][1:]
        return None

    @property
    def kv_layers(self) -> int:
        """The layers that keep K/V (or a latent row): the first dimension
        of those leaves. ``n_layer`` less the state group's."""
        return self.n_layer - (self.state_group[0] if self.state_group
                               else 0)

    def refusal(self, feature: str, prefill_chunk: int = 0) -> Optional[str]:
        """The sentence of ``CACHE_REFUSALS`` for ``feature`` with this
        cache's model, or None: what the constructor that turns ``feature``
        on raises. ``prefill_chunk``: the width asked of
        ``prefill_chunk_wider_than_window``."""
        why = refusal(self.kinds, feature)
        if why and feature == "prefill_chunk_wider_than_window":
            window = self.groups[1][2]
            return None if prefill_chunk <= window else (
                f"{why} (prefill_chunk {prefill_chunk}, sliding_window "
                f"{window})")
        return why

    @property
    def state_bytes_per_row(self) -> int:
        """Bytes of one sequence's state over the layers (0: a K/V cache)."""
        if not self.state_group:
            return 0
        layers, leaves = self.state_group
        return layers * sum(math.prod(shape) * np.dtype(dtype).itemsize
                            for _, shape, dtype in leaves)

    def _state_cache(self, rows: int, stacked: bool = True) -> dict:
        """The zeroed state leaves of ``rows`` sequences: one layer's, or
        ``stacked`` over the layers that keep a state."""
        layers, leaves = self.state_group
        lead = ((layers,) if stacked else ()) + (rows,)
        return {leaf: jnp.zeros(lead + shape, dtype)
                for leaf, shape, dtype in leaves}

    def layer_cache(self, batch_size: int) -> dict:
        """Zeroed single-layer k/v dict: (B, KV, cache_d, S) [+ scales]."""
        if not self.kv_layers:
            return self._state_cache(batch_size, stacked=False)
        if self.latent:
            return {"c": jnp.zeros((batch_size, self.latent,
                                    self.max_seq_len), self.dtype)}
        shape = (batch_size, self.kv_heads, self.cache_d, self.max_seq_len)
        cache = {"k": jnp.zeros(shape, self.dtype),
                 "v": jnp.zeros(shape, self.dtype)}
        if self.quantized:
            sshape = (batch_size, self.kv_heads, self.max_seq_len)
            cache["k_scale"] = jnp.zeros(sshape, jnp.float32)
            cache["v_scale"] = jnp.zeros(sshape, jnp.float32)
        return cache

    def stacked_cache(self, batch_size: int) -> dict:
        """Zeroed L-stacked cache dict matching the ``cache_store`` flax
        variables: k/v (L, B, KV, cache_d, S) [+ scales (L, B, KV, S)],
        plus a per-sequence ``index`` (B,) int32 — the vector-start form
        CachedAttention accepts for slot-pooled decode."""
        L = self.kv_layers
        if not L:
            return dict(self._state_cache(batch_size),
                        index=jnp.zeros((batch_size,), jnp.int32))
        if self.latent:
            return {"c": jnp.zeros((L, batch_size, self.latent,
                                    self.max_seq_len), self.dtype),
                    "index": jnp.zeros((batch_size,), jnp.int32),
                    **(self._state_cache(batch_size) if self.state_group
                       else {})}
        shape = (L, batch_size, self.kv_heads, self.cache_d,
                 self.max_seq_len)
        cache = {"k": jnp.zeros(shape, self.dtype),
                 "v": jnp.zeros(shape, self.dtype),
                 "index": jnp.zeros((batch_size,), jnp.int32)}
        if self.state_group:
            cache.update(self._state_cache(batch_size))
        if self.quantized:
            sshape = (L, batch_size, self.kv_heads, self.max_seq_len)
            cache["k_scale"] = jnp.zeros(sshape, jnp.float32)
            cache["v_scale"] = jnp.zeros(sshape, jnp.float32)
        return cache

    # -- paged KV (PagedAttention-style block pool) --------------------
    def paged_cache(self, num_pages: int, page_size: int,
                    window_pages: Optional[int] = None,
                    num_slots: int = 0) -> dict:
        """Zeroed PAGE-POOL k/v arrays: the positions axis is split into
        ``num_pages`` physical pages of ``page_size`` columns each, with
        NO batch axis — k/v (L, P, KV, cache_d, lanes) [+ scales
        (L, P, KV, lanes)], ``lanes = page_lanes(page_size)``: a page's
        columns stand in its first ``page_size`` lanes and the rest is
        never read. A per-slot page table maps logical positions to
        pages; :meth:`dense_from_pages` reassembles the
        ``stacked_cache`` layout the attention kernels consume. Same
        dtype/packing tiers as the contiguous container (int8/packed
        cache columns page exactly like full-precision ones). A state
        group is not paged: ``num_slots`` rows beside the page leaves."""
        why = self.refusal("paged_kv")
        if why:
            raise ValueError(why)
        lanes = page_lanes(page_size)
        if self.latent:
            return {"c": jnp.zeros((self.kv_layers, num_pages, self.latent,
                                    lanes), self.dtype),
                    **(self._state_cache(num_slots) if self.state_group
                       else {})}
        if self.groups is not None:
            # one stacked leaf a group: ``num_pages`` pages for the full
            # layers, ``window_pages`` for the window layers
            return {key + suffix: jnp.zeros(
                        (len(layers), pages, self.kv_heads, self.cache_d,
                         lanes), self.dtype)
                    for (suffix, layers, _), pages in zip(
                        self.groups, (num_pages, window_pages))
                    for key in ("k", "v")}
        shape = (self.kv_layers, num_pages, self.kv_heads, self.cache_d,
                 lanes)
        cache = {"k": jnp.zeros(shape, self.dtype),
                 "v": jnp.zeros(shape, self.dtype)}
        if self.index_stride:
            if page_size % self.index_stride:
                raise ValueError(
                    f"a page holds whole groups of the index's "
                    f"{self.index_stride} keys; got page_size {page_size}")
            cache["kc"] = jnp.zeros(
                (self.kv_layers, num_pages, self.kv_heads,
                 page_size // self.index_stride, self.head_dim), jnp.float32)
        if self.state_group:
            cache.update(self._state_cache(num_slots))
        if self.quantized:
            sshape = (self.kv_layers, num_pages, self.kv_heads, lanes)
            cache["k_scale"] = jnp.zeros(sshape, jnp.float32)
            cache["v_scale"] = jnp.zeros(sshape, jnp.float32)
        return cache

    def dense_from_pages(self, paged: dict, table) -> dict:
        """Traced paged-attention GATHER: reassemble the dense
        ``(L, B, KV, cache_d, max_seq_len)`` view of a page pool from a
        ``(B, max_pages_per_slot)`` int32 page table, so the existing
        attention programs (decode / verify / chunked prefill) run
        UNCHANGED over paged storage — bitwise-identical math, static
        shapes, zero new attention kernels. Unmapped entries carry the
        sentinel ``num_pages``; the clip-mode gather reads an arbitrary
        real page there, which is safe because a slot's mapped region
        always covers its live ``[0, index)`` columns and attention
        masks everything beyond (the same alive-masking that makes dead
        slots free). ``table`` rows must span exactly
        ``max_seq_len // page_size`` pages.

        With layer ``groups``, ``paged`` holds a leaf and ``table`` (a
        dict) a table a group; each group is gathered through its own
        table and the layers come back in model order, so the dense
        programs see the one ``(L, ...)`` stack they always saw. A window
        group's recycled entries are sentinels like any other: what they
        read is behind the window mask."""
        if self.groups is not None:
            single = dataclasses.replace(self, groups=None)
            parts = [single.dense_from_pages(
                        {key: paged[key + suffix] for key in ("k", "v")},
                        table["table" + suffix])
                     for suffix, _, _ in self.groups]
            order = np.argsort(np.concatenate(
                [np.asarray(layers, np.int64)
                 for _, layers, _ in self.groups]))
            return {key: jnp.concatenate([p[key] for p in parts])[order]
                    for key in ("k", "v")}
        B, max_pages = table.shape
        ps = self.max_seq_len // max_pages
        flat = table.reshape(-1)
        # (a state group is rows of the pool already: it rides along)
        out = {key: paged[key] for key in self.state_leaves}
        if self.latent:
            leaf = paged["c"]                       # (L, P, W, lanes)
            g = jnp.take(leaf, flat, axis=1, mode="clip")[..., :ps]
            g = g.reshape(leaf.shape[0], B, max_pages, self.latent, ps)
            return dict(out, c=g.transpose(0, 1, 3, 2, 4).reshape(
                leaf.shape[0], B, self.latent, max_pages * ps))
        for key in ("k", "v"):
            leaf = paged[key]                       # (L, P, KV, cd, lanes)
            L, _, KV, cd, _ = leaf.shape
            g = jnp.take(leaf, flat, axis=1, mode="clip")[..., :ps]
            g = g.reshape(L, B, max_pages, KV, cd, ps)
            out[key] = g.transpose(0, 1, 3, 4, 2, 5).reshape(
                L, B, KV, cd, max_pages * ps)
        if self.quantized:
            for key in ("k_scale", "v_scale"):
                leaf = paged[key]                   # (L, P, KV, lanes)
                L, _, KV, _ = leaf.shape
                g = jnp.take(leaf, flat, axis=1, mode="clip")[..., :ps]
                g = g.reshape(L, B, max_pages, KV, ps)
                out[key] = g.transpose(0, 1, 3, 2, 4).reshape(
                    L, B, KV, max_pages * ps)
        return out


def make_kv_cache_spec(cfg: TransformerConfig) -> KVCacheSpec:
    cache_dtype, cache_d, packed = kv_cache_spec(cfg)
    group = None
    if cfg.retention:
        from ..ops.attention.power_retention import state_shape

        group = (cfg.n_layer, (
            ("s", (cfg.kv_heads,) + state_shape(cfg.head_dim),
             jnp.float32),))
    if cfg.mamba:
        from ..ops.state_space import state_shape

        group = (cfg.layer_types.count("mamba"), (
            ("s", state_shape(cfg.mamba_n_heads, cfg.mamba_d_head,
                              cfg.mamba_d_state), jnp.float32),
            ("conv", ((cfg.mamba_d_conv - 1) * cfg.mamba_channels,),
             cache_dtype)))
    if cfg.kda:
        group = (cfg.layer_types.count("kda"), (
            ("s", (cfg.kda_n_heads, cfg.kda_d_head, cfg.kda_d_head),
             jnp.float32),
            ("conv", ((cfg.kda_d_conv - 1) * 3 * cfg.kda_width,),
             cache_dtype)))
    if cfg.gdn:
        # (KDA's leaf at the value heads: the kernels are ops/kda.py's)
        group = (cfg.layer_types.count("gdn"), (
            ("s", (cfg.gdn_n_value_heads, cfg.gdn_d_head, cfg.gdn_d_head),
             jnp.float32),
            ("conv", ((cfg.gdn_d_conv - 1) * cfg.gdn_channels,),
             cache_dtype)))
    if cfg.conv:
        group = (cfg.layer_types.count("conv"), (
            ("conv", ((cfg.conv_taps - 1) * cfg.n_embd,), cache_dtype),))
    if cfg.lightning:
        from ..ops.lightning import state_shape

        group = (cfg.layer_types.count("lightning"), (
            ("s", state_shape(cfg.n_head, cfg.head_dim), jnp.float32),))
    return KVCacheSpec(n_layer=cfg.n_layer, kv_heads=cfg.kv_heads,
                       head_dim=cfg.head_dim, cache_d=cache_d,
                       dtype=cache_dtype, max_seq_len=cfg.max_seq_len,
                       quantized=cfg.kv_cache_quant, packed=packed,
                       groups=kv_cache_groups(cfg), latent=cfg.latent,
                       latent_rank=cfg.kv_lora_rank if cfg.latent else 0,
                       kinds=cache_kinds(cfg),
                       rep=cfg.n_head // cfg.kv_heads, state_group=group,
                       sparse=cfg.sparse)


def make_layer_kv_cache(cfg: TransformerConfig, batch_size: int) -> dict:
    """Zeroed SINGLE-LAYER KV cache dict — the explicit functional form
    of one _CacheStore slice, for callers that stream layers one at a
    time (ZeRO-Inference) and thread the cache themselves. Add a
    ``start`` scalar before passing to TransformerBlock."""
    return make_kv_cache_spec(cfg).layer_cache(batch_size)
