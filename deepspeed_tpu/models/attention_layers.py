"""The attention layer that caches K/V a head, of a ``TransformerBlock``
(``models/transformer_lm.py``): :class:`CachedAttention`, multi-head or
grouped-query, full or sliding (``layer_types``: ``sliding_attention`` or
``full_attention``, each with its own rotary table, the kind read off the
scan's counter so the mask and the table are selected, not branched on),
over a contiguous cache or a page pool's.

KV-cache decoding uses the flax ``cache`` variable collection: ``prefill``
writes the prompt's K/V at positions [0, T), ``decode`` appends one position
via ``lax.dynamic_update_slice`` and attends over the static-shape cache with
a validity mask — static shapes keep XLA happy (the reference's
inference_context.h workspace is the moral equivalent)."""

from __future__ import annotations

import functools
import math
from typing import Union

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..ops import backend
from .kv_cache_spec import kv_cache_groups, kv_cache_spec
from .lm_config import TransformerConfig
from .lm_parts import (_by_row_group, _chunk_positions, _chunk_shaped, _dense,
                       _gated, _norm_qk, _project_qkv, _store_columns,
                       _traced_once,
                       alibi_slopes, apply_rotary, apply_rotary_table,
                       layer_rope_tables)


class CachedAttention(nn.Module):
    """Multi-head / grouped-query attention with optional KV cache.

    Modes (``decode`` is a static tri-state):
      - ``False`` — training / no-cache forward: full causal
        self-attention.
      - ``"prefill"`` — writes the prompt's k/v into the ``cache``
        collection (k, v, cache_index) and attends over the FRESH
        prompt k/v (start == 0 contract): O(T) attention memory, never
        the (B, H, T, max_seq_len) allocated-cache tensor. Use for the
        first multi-token call.
      - ``True`` — reads+updates the cache; 1-token decode takes the
        fused Pallas kernel, multi-token (chunked decode at unknown
        start) takes the window-masked einsum over the cache.
    """

    config: TransformerConfig

    def _use_flash(self, seq_len: int, deterministic: bool) -> bool:
        """Route the full-context (non-decode) forward through the Pallas
        flash kernel. ``auto``: on TPU from the tuned crossover length;
        ``True`` forces it (interpret mode off-TPU — for tests). ALiBi has
        no flash bias hook and attention-probability dropout has no kernel
        equivalent — those stay on the einsum path (forcing raises)."""
        cfg = self.config
        use = cfg.use_flash_attention
        if use is False or use == "off" or cfg.layer_types is not None:
            # (a window inside the flash kernels is not written yet: layer
            # kinds take the masked einsum in the full-context forward)
            return False
        alibi_ok = cfg.pos_emb != "alibi"
        drop_ok = cfg.dropout == 0 or deterministic
        if use == "auto":
            from ..ops.attention.flash_attention import use_flash_by_default

            return use_flash_by_default(seq_len) and alibi_ok and drop_ok
        if not alibi_ok:
            raise ValueError("use_flash_attention=True does not compose with "
                             "pos_emb='alibi' (no bias hook in the kernel)")
        if not drop_ok:
            raise ValueError("use_flash_attention=True does not support "
                             "attention-probability dropout in train mode")
        return True

    def _use_decode_kernel(self, cache_len: int,
                           deterministic: bool = True) -> bool:
        """Route 1-token decode through the fused Pallas kernel. ``auto``:
        on TPU with a kernel-compatible cache length; ``on`` forces it
        (interpret mode off-TPU — for tests); ``off`` keeps the jnp path.
        Attention-probability dropout (train-mode decode) has no kernel
        equivalent — that combination stays on the jnp path."""
        from ..ops.attention.decode_attention import pick_block_s

        cfg = self.config
        if cfg.decode_kernel == "off" or cfg.layer_types is not None:
            return False    # (the dense decode kernel knows no window)
        if cfg.dropout > 0 and not deterministic:
            return False
        if pick_block_s(cache_len) < 8:
            return False
        if cfg.decode_kernel == "on":
            return True
        return backend.on_tpu()

    def _paged_decode_step(self, kv_cache, q, k, v):
        """Decode, verify or prefill-chunk step (T = 1, K + 1, the chunk
        width) over PAGED storage, up to the output projection: ``(y,
        leaves)``, the leaves it wrote. ``kv_cache`` holds the
        pool's STACKED leaves whole ((L, P, KV, cache_d, lanes), no
        batch axis) with ``layer``, ``start`` and ``table``: this step's
        K/V columns go into this layer's pages through the table
        (``paged_write``: a Pallas call that takes the whole leaf,
        rewrites the pages it names and returns the leaf aliased;
        sentinel entries and positions out of range are not in its work
        list, so they touch nothing) and the fused paged kernel attends
        over the same leaf at the same layer. No slice, re-layout or
        copy of a leaf is made on the way: the XLA scatter this replaced
        (``buf.at[pages, :, :, offs].set`` on one layer's slice, on the
        first and the minor dimension of a positions-minor page) cost
        five passes over a 67 MB slice a layer with the slicing around
        it, 74 % of a busy chip (ledger, PR 24). The value bytes
        written and the attention math match the dense path exactly
        (same quantize/pack pipeline; for each head the kernel folds one
        page at a time in table order, op-for-op the dense decode kernel
        at a block of one page), which is what keeps paged-kernel greedy
        output bitwise-identical to the dense oracle."""
        cfg = self.config
        B, T, H, D = q.shape
        kv_packed = kv_cache_spec(cfg)[2]
        from ..ops.attention.paged_attention import (
            paged_decode_attention,
            paged_write_columns,
        )

        start = kv_cache["start"]
        assert jnp.ndim(start) == 1, \
            "paged decode is slot-pooled: start must be (B,)"
        if kv_cache_groups(cfg) is not None:
            return self._grouped_paged_step(kv_cache, q, k, v)
        table = kv_cache["table"]                  # (B, pages_per_slot)
        layer = kv_cache["layer"]
        page_size = cfg.max_seq_len // table.shape[1]
        new_cache = {}
        chunk = _chunk_shaped(q)

        def write(key, cols):
            new_cache[key] = _traced_once(
                paged_write_columns, "page_size", chunk=chunk)(
                kv_cache[key], layer, cols, table, start,
                page_size=page_size)

        k_rows = k.astype(cfg.dtype).transpose(0, 2, 1, 3)  # (B, KV, T, D)
        v_rows = v.astype(cfg.dtype).transpose(0, 2, 1, 3)
        scales = {}
        if cfg.kv_cache_quant:
            from ..ops.attention.decode_attention import (
                pack_int8_sublanes,
                quantize_kv_rows,
            )

            k_rows, k_sc = quantize_kv_rows(k_rows)       # scales (B,KV,T)
            v_rows, v_sc = quantize_kv_rows(v_rows)
            write("k_scale", k_sc)
            write("v_scale", v_sc)
            scales = dict(k_scale_pages=new_cache["k_scale"],
                          v_scale_pages=new_cache["v_scale"])
        k_cols = k_rows.transpose(0, 1, 3, 2)             # (B, KV, D, T)
        v_cols = v_rows.transpose(0, 1, 3, 2)
        if kv_packed:
            k_cols = pack_int8_sublanes(k_cols)           # (B, KV, D//4, T)
            v_cols = pack_int8_sublanes(v_cols)
        write("k", k_cols)
        write("v", v_cols)

        slopes = alibi_slopes(H) if cfg.pos_emb == "alibi" else None
        y = _traced_once(paged_decode_attention, "page_size",
                         chunk=chunk)(
            q.astype(cfg.dtype), new_cache["k"], new_cache["v"], table,
            start, layer=layer, page_size=page_size, alibi_slopes=slopes,
            **scales)
        return y.astype(cfg.dtype).reshape(B, T, H * D), new_cache

    def _grouped_paged_step(self, kv_cache, q, k, v):
        """:meth:`_paged_decode_step` over a pool of layer GROUPS
        (:func:`kv_cache_groups`): each group has its own stacked leaf
        and table (``k`` / ``table`` for the full layers, ``k_win`` /
        ``table_win`` for the window layers). The layer's kind is a
        traced value inside the scan, so the step makes the write and the
        read of EVERY group and gives the groups the layer is not in an
        empty work list (``active``: a grid of no step, a leaf returned
        as it came); a ``lax.cond`` over the leaves would copy the
        branch's pass-through operands."""
        from ..ops.attention.paged_attention import (
            paged_decode_attention,
            paged_write_columns,
        )

        cfg = self.config
        B, T, H, D = q.shape
        start, layer = kv_cache["start"], kv_cache["layer"]
        new_cache = {}
        k_cols = k.astype(cfg.dtype).transpose(0, 2, 3, 1)    # (B, KV, D, T)
        v_cols = v.astype(cfg.dtype).transpose(0, 2, 3, 1)
        y, chunk = None, _chunk_shaped(q)
        for suffix, layers, window in kv_cache_groups(cfg):
            place = np.full((cfg.n_layer,), -1, np.int32)
            place[list(layers)] = np.arange(len(layers))
            index = jnp.asarray(place)[layer]    # the layer within its group
            active = index >= 0
            table = kv_cache["table" + suffix]
            page_size = cfg.max_seq_len // table.shape[1]
            for key, cols in (("k", k_cols), ("v", v_cols)):
                new_cache[key + suffix] = _traced_once(
                    paged_write_columns, "page_size", chunk=chunk)(
                    kv_cache[key + suffix], jnp.maximum(index, 0), cols,
                    table, start, page_size=page_size, active=active)
            y_g = _traced_once(paged_decode_attention, "page_size",
                               "window", chunk=chunk)(
                q.astype(cfg.dtype), new_cache["k" + suffix],
                new_cache["v" + suffix], table, start,
                layer=jnp.maximum(index, 0), page_size=page_size,
                window=window or None, active=active)
            y = y_g if y is None else jnp.where(active, y_g, y)
        return y.astype(cfg.dtype).reshape(B, T, H * D), new_cache

    @nn.compact
    def __call__(self, x, *, decode: Union[bool, str] = False,
                 deterministic: bool = True, kv_cache=None, layer=None):
        cfg = self.config
        B, T, C = x.shape
        H, KV, D = cfg.n_head, cfg.kv_heads, cfg.head_dim
        q, k, v = _project_qkv(cfg, x)
        if cfg.qk_norm:
            q, k = _norm_qk(cfg, q, k)
        if cfg.attention_multiplier is not None:
            # every path below (and the kernels) scales by 1 / sqrt(D):
            # the query carries what the published scale differs by
            q = q * (cfg.attention_multiplier * math.sqrt(D))

        kv_packed = kv_cache_spec(cfg)[2]
        if decode:
            # This layer's KV cache arrives as an ARGUMENT (dict with
            # k/v [+ scales] and the shared ``start``) and the updated
            # one is RETURNED: the stacked cache rides the layer scan's
            # carry (_ScanBlock), as one layer's slice for the
            # contiguous cache and whole for a page pool. (The previous
            # design — per-layer flax cache variables, nn.scan
            # variable_axes — lowers to a scan whose xs/ys pair
            # double-buffers the quantized cache above ~100 MB:
            # PERF.md §8, the carry-DUS lead.)
            assert kv_cache is not None, "decode needs the kv_cache slice"
            # ``start`` is scalar () for batch-uniform decode (generate),
            # or (B,) for slot-pooled decode where every sequence sits at
            # its own cache offset (serving/ continuous batching)
            start = kv_cache["start"]
            per_slot = jnp.ndim(start) == 1
            positions = _chunk_positions(kv_cache, T) \
                if "chunk" in kv_cache \
                else (start[:, None] if per_slot else start) \
                + jnp.arange(T)[None, :]
        else:
            start = jnp.zeros((), jnp.int32)
            positions = jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))

        o_dense = _dense(cfg, C, use_bias=cfg.qkv_bias, name="o_proj")

        def o_proj(y):      # behind the output gate, where there is one
            return o_dense(_gated(cfg, y, x) if cfg.attn_output_gate else y)

        is_window = None    # traced: this layer is a sliding-window layer
        if cfg.layer_types is not None and not cfg.hybrid:
            inv_freq, factor, windows = layer_rope_tables(cfg)
            is_window = jnp.asarray(windows)[layer]
        if cfg.pos_emb == "rotary":
            rd = int(cfg.rotary_pct * D) // 2 * 2
            if is_window is not None:
                rope = (jnp.asarray(inv_freq)[layer],
                        jnp.asarray(factor)[layer], rd)
                q = apply_rotary_table(q, positions, *rope)
                k = apply_rotary_table(k, positions, *rope)
            else:
                q = apply_rotary(q, positions, rotary_dim=rd,
                                 theta=cfg.rope_theta)
                k = apply_rotary(k, positions, rotary_dim=rd,
                                 theta=cfg.rope_theta)

        if decode and kv_cache is not None and "table" in kv_cache:
            # Paged decode: K/V live in the PAGE POOL ((L, P, KV,
            # cache_d, lanes), no batch axis, every layer in one
            # leaf) and both the column writes and the attention read
            # resolve (layer, position) through the per-slot page table
            # inside Pallas calls — no dense per-slot view and no slice
            # of the leaf is ever materialized
            # (ops/attention/paged_attention.py).
            y, leaves = _by_row_group(kv_cache, self._paged_decode_step,
                                      q, k, v)
            return o_proj(y), leaves

        kv_scales = None  # set on the quantized-cache einsum fallback
        # "fresh" attention = causal over the just-computed k/v. True for
        # the training forward AND for prefill (start == 0 contract): the
        # prompt's causal window IS the fresh k/v, so prefill must NOT
        # attend over the allocated cache — the (B, H, T, S) score tensor
        # that implies OOM-crashed the worker at T=4096 / S=8192.
        fresh = (not decode) or (decode == "prefill" and T > 1)
        new_cache = None
        if decode:
            k_rows = k.astype(cfg.dtype).transpose(0, 2, 1, 3)  # (B,KV,T,D)
            v_rows = v.astype(cfg.dtype).transpose(0, 2, 1, 3)
            new_cache = dict(kv_cache)

            store = functools.partial(_store_columns, start=start)

            if cfg.kv_cache_quant:
                from ..ops.attention.decode_attention import (
                    pack_int8_sublanes,
                    quantize_kv_rows,
                )

                k_rows, k_sc = quantize_kv_rows(k_rows)
                v_rows, v_sc = quantize_kv_rows(v_rows)
                new_cache["k_scale"] = store(kv_cache["k_scale"], k_sc)
                new_cache["v_scale"] = store(kv_cache["v_scale"], v_sc)
            # positions-minor store: new rows become (B, KV, D, T) columns
            k_cols = k_rows.transpose(0, 1, 3, 2)
            v_cols = v_rows.transpose(0, 1, 3, 2)
            if kv_packed:
                k_cols = pack_int8_sublanes(k_cols)  # (B, KV, D//4, T)
                v_cols = pack_int8_sublanes(v_cols)
            new_cache["k"] = store(kv_cache["k"], k_cols)
            new_cache["v"] = store(kv_cache["v"], v_cols)
            if T == 1 and self._use_decode_kernel(cfg.max_seq_len,
                                                  deterministic):
                # fused Pallas decode attention (reference softmax_context,
                # pt_binding.cpp:1910-1975): length masking + softmax +
                # value reduction in one pass over the cache; int8 caches
                # pass their per-row scales straight through
                from ..ops.attention.decode_attention import (
                    decode_attention,
                    pick_block_s,
                )

                slopes = alibi_slopes(H) if cfg.pos_emb == "alibi" else None
                scales = dict(k_scale=new_cache["k_scale"],
                              v_scale=new_cache["v_scale"]) \
                    if cfg.kv_cache_quant else {}
                y = decode_attention(
                    q[:, 0].astype(cfg.dtype), new_cache["k"],
                    new_cache["v"], start + 1, alibi_slopes=slopes,
                    block_s=pick_block_s(cfg.max_seq_len,
                                         preferred=cfg.decode_block),
                    **scales)
                y = y.astype(cfg.dtype).reshape(B, 1, H * D)
                return o_proj(y), new_cache
            if not fresh:
                # chunked decode (decode=True, T > 1, start unknown):
                # attend over the allocated cache with a window mask
                k_all, v_all = new_cache["k"], new_cache["v"]
                S = cfg.max_seq_len
                if kv_packed:
                    from ..ops.attention.decode_attention import \
                        unpack_int8_sublanes

                    k_all = unpack_int8_sublanes(k_all)
                    v_all = unpack_int8_sublanes(v_all)
                # the shared einsum below expects (B, KV, S, D)
                k_all = k_all.transpose(0, 1, 3, 2)
                v_all = v_all.transpose(0, 1, 3, 2)
                if cfg.kv_cache_quant:
                    # do NOT dequantize the cache (a full-size bf16 copy —
                    # multiple GB at long S); fold the per-row scales into
                    # the score and probability tensors, as the kernel does
                    kv_scales = (new_cache["k_scale"], new_cache["v_scale"])
                # row t may see cache slots [0, start+t]; per-slot starts
                # make the mask batch-dependent: (B, T, S) instead of (T, S)
                if per_slot:
                    mask = (jnp.arange(S)[None, None, :]
                            <= (start[:, None]
                                + jnp.arange(T)[None, :])[:, :, None])
                else:
                    mask = (jnp.arange(S)[None, :]
                            <= (start + jnp.arange(T))[:, None])
                if is_window is not None:
                    # a sliding layer's row at position p sees keys in
                    # (p - sliding_window, p]
                    qpos = (start[:, None] if per_slot else start) \
                        + jnp.arange(T)
                    mask = mask & jnp.logical_or(
                        ~is_window, jnp.arange(S) > qpos[..., None]
                        - cfg.sliding_window)
        if fresh:
            if self._use_flash(T, deterministic):
                # fused Pallas flash attention for the full-context forward
                # (and, via its custom_vjp, the streamed/resident backward) —
                # O(T) memory instead of the (B, H, T, T) logits tensor
                from ..ops.attention.flash_attention import flash_attention

                k_f, v_f = k, v
                if KV != H:
                    k_f = jnp.repeat(k, H // KV, axis=2)
                    v_f = jnp.repeat(v, H // KV, axis=2)
                y = flash_attention(q.astype(cfg.dtype),
                                    k_f.astype(cfg.dtype),
                                    v_f.astype(cfg.dtype), causal=True)
                y = y.astype(cfg.dtype).reshape(B, T, H * D)
                return o_proj(y), new_cache
            k_all = k.transpose(0, 2, 1, 3)  # (B, KV, T, D)
            v_all = v.transpose(0, 2, 1, 3)
            S = T
            mask = jnp.tril(jnp.ones((T, T), dtype=bool))
            if is_window is not None:
                mask = mask & jnp.logical_or(
                    ~is_window, jnp.arange(T)[None, :]
                    > jnp.arange(T)[:, None] - cfg.sliding_window)

        if KV != H:
            rep = H // KV
            k_all = jnp.repeat(k_all, rep, axis=1)
            v_all = jnp.repeat(v_all, rep, axis=1)
            if kv_scales is not None:
                kv_scales = tuple(jnp.repeat(s, rep, axis=1)
                                  for s in kv_scales)

        scale = 1.0 / math.sqrt(D)
        # int8 cache: the s8->f32 cast does NOT fuse into the dot on TPU
        # (rounds 1-5: full fp32 cache copies appeared), so the quantized path casts to the compute dtype
        # instead — int8 is exact in bf16, the copy is half the bytes,
        # and the dot still accumulates in f32. The per-row scales apply
        # to the (B,H,T,S) score/probability tensors.
        if kv_scales is not None:
            att = jnp.einsum("bthd,bhsd->bhts", q.astype(cfg.dtype),
                             k_all.astype(cfg.dtype),
                             preferred_element_type=jnp.float32) * scale
            att = att * kv_scales[0][:, :, None, :]
        else:
            att = jnp.einsum("bthd,bhsd->bhts", q.astype(jnp.float32),
                             k_all.astype(jnp.float32)) * scale
        if cfg.pos_emb == "alibi":
            slopes = alibi_slopes(H)  # (H,)
            if decode and jnp.ndim(start) == 1:
                # per-slot decode: relative key offsets differ per batch row
                rel = (jnp.arange(S)[None, None, :]
                       - (start[:, None] + jnp.arange(T)[None, :])[:, :, None])
                att = att + slopes[None, :, None, None] * rel[:, None]
            else:
                kpos = jnp.arange(S)[None, :]
                qpos = (start + jnp.arange(T))[:, None]
                att = att + slopes[None, :, None, None] \
                    * (kpos - qpos)[None, None]
        att = jnp.where(mask[None, None] if mask.ndim == 2 else mask[:, None],
                        att, -1e30)
        att = jax.nn.softmax(att, axis=-1)
        if cfg.dropout > 0:
            att = nn.Dropout(cfg.dropout)(att, deterministic=deterministic)
        if kv_scales is not None:
            att = att * kv_scales[1][:, :, None, :]
            y = jnp.einsum("bhts,bhsd->bthd", att.astype(cfg.dtype),
                           v_all.astype(cfg.dtype),
                           preferred_element_type=jnp.float32)
        else:
            y = jnp.einsum("bhts,bhsd->bthd", att,
                           v_all.astype(jnp.float32))
        y = y.astype(cfg.dtype)
        y = y.reshape(B, T, H * D)
        return o_proj(y), new_cache
