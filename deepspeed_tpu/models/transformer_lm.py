"""Unified decoder-only transformer covering the reference's model families.

The reference ships 17 per-model injection "policies/containers"
(``deepspeed/module_inject/containers/``: gpt2, gptj, gptneo(x), llama, opt,
bloom, megatron, bert, ...) plus fused inference modules
(``model_implementations/transformers/ds_transformer.py:19`` and the
``ds_bloom/ds_gpt/ds_opt/ds_megatron_gpt`` variants). TPU-native, those
collapse into ONE parameterized flax module: every family is a point in a
small feature space (position encoding × norm × activation × residual
topology × GQA), and XLA fuses what the reference hand-fused in CUDA.

Families are presets of ``TransformerConfig`` (``models/lm_config.py`` has
the table of them). This file is the block, the layer scan, the cache's
variables and the model. What a block mixes with lives below it, and none
of it imports this file: ``attention_layers.py`` (K/V a head),
``state_layers.py`` (retention, mamba, KDA, the short convolution),
``gdn_layers.py`` (Gated DeltaNet) and ``lightning_sparse.py`` (Lightning
and learned block-sparse attention), on
the helpers of ``lm_parts.py``; the cache's container is
``kv_cache_spec.py``'s and what a kind refuses ``cache_kinds.py``'s. A new
layer kind is a class in a file of its own or one of those, its rows in
``cache_kinds.py`` and a branch of :class:`TransformerBlock`.

Layer kinds that differ (``layer_types``: ``sliding_attention`` or
``full_attention``, each with its own rotary table) run inside the ONE
layer scan: a layer reads its kind off the scan's counter, so the mask
and the table are selected, not branched on. The routed FFN
(``n_experts > 0``) is ``deepspeed_tpu/moe/routed_ffn.py``; its stacked
expert leaves ``(layers, E, C, F)`` are parameters of the model, not of
the scanned block, and reach every layer whole (a scanned leaf would be
sliced, that is copied, a layer). Nothing of this is reached by a
configuration with ``n_experts == 0`` and no ``layer_types``.

A model of ``power_retention`` layers keeps no K/V: the state's stacked
leaf (``KVCacheSpec.state``) rides the scan's carry whole and is updated
in place, for the rows the caller names (``rows``) and no others.

Latent attention (:class:`LatentAttention`, ``kv_lora_rank > 0``) caches
ONE row a token a layer, ``[c ; k_r]`` (the normed latent and the shared
rotary key), that every head reads: ``KVCacheSpec.latent``, one leaf ``c``
and no ``k`` / ``v``. The leading ``first_k_dense`` layers of such a model
may carry a plain gated FFN (``dense_ffn_dim``) before the routed layers:
they are a scan of their own (``dense_blocks``) in front of ``blocks``, the
layer counter and the cache running through both.

State layers of one kind (``STATE_KINDS``: ``mamba``, ``kda``, ``gdn``,
``conv``, ``lightning``) stand BESIDE ``attention`` layers (full, position-free or
rotary, K/V a head or latent, sparse under ``sparse_attention``) in one
model. The two kinds have different parameter trees, so the stack is two
stacked leaves (``<kind>_blocks``, ``attn_blocks``) run in the published
order by :meth:`TransformerLM._hybrid_layers`, and the cache is two
groups: a state group over the state layers (``KVCacheSpec.state_group``)
and K/V, or the one leaf ``c``, over the attention layers, contiguous or
paged. A page pool keeps the state group beside its pages. The FFN may be
routed, behind ``first_k_dense`` leading layers with a plain one inside
the first period (``dense_blocks``, of the leading layers' kind), and may
hold a share of its experts (``experts_held``).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Union

import flax.linen as nn
import jax
import jax.numpy as jnp

from .attention_layers import CachedAttention
from .gdn_layers import GatedDeltaNetMixer
from .kv_cache_spec import (KVCacheSpec, kv_cache_groups, kv_cache_spec,
                            make_kv_cache_spec)
from .lightning_sparse import LightningMixer, SparseAttention
from .lm_config import TransformerConfig
from .lm_parts import (_by_row_group, _chunk_positions, _chunk_shaped, _dense,
                       _norm, _settled, _store_columns, _traced_once,
                       apply_rotary)
from .state_layers import (KDAMixer, Mamba2Mixer, PowerRetention,
                           ShortConvMixer)

# not used here: perf/configs/*.json and tests/unit/perf/ (a benchmark PR's
# files) name the two by this module
from .lm_config import transformer_config  # noqa: F401
from .lm_parts import rope_inv_freq  # noqa: F401


# LatentAttention is attention_layers.py's by kind. It stays here, where it
# looks ``apply_rotary`` up, until tests/unit/perf/test_reference_moonlight.py
# (a benchmark PR's file) plants its shifted-position fault somewhere else
# than ``transformer_lm.apply_rotary`` (ROADMAP.md, D14).
class LatentAttention(nn.Module):
    """Multi-head latent attention (``kv_lora_rank > 0``): every head's K
    and V are projections of ONE normed latent ``c`` (``kv_lora_rank``
    wide) a token, and the rotary part of the key, ``k_r``, is one vector a
    token that the heads share. With ``h`` the layer's input::

        q_h = [q_n,h ; q_r,h] = W_q h        [c' ; k_r] = W_kva h
        c = RMSNorm(c')      rotary on q_r,h and on k_r
        [k_n,h ; v_h] = W_kvb,h c
        score_h(i, j) = (q_n,h(i) . k_n,h(j) + q_r,h(i) . k_r(j)) / sqrt(dn + dr)
        o_h = sum_j softmax_j(score_h)(i, j) v_h(j)        out = W_o [o_h]

    Two forms of that one mathematics. **Expanded**: K and V are built from
    ``c`` and attended to as written (the forward without a cache, and a
    prompt prefilled whole). **Absorbed**, against a cache: the cache holds
    ``[c ; k_r]`` a token, after the norm and the rotary, and nothing a
    head; ``q~_h = W_kvb,h^K^T q_n,h`` scores the cached row itself,
    ``score_h = (q~_h . c + q_r,h . k_r)``, and the values are read off the
    same row, ``o_h = W_kvb,h^V (sum_j p_h c_j)``: the K/V of a cached token
    are never rebuilt. Against a page pool the absorbed read is
    ``ops/attention/latent_attention.py`` (``mla_decode``: the heads' rows
    over one page of rows a step); modes as :class:`CachedAttention`'s."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, x, *, decode: Union[bool, str] = False,
                 deterministic: bool = True, kv_cache=None, layer=None):
        cfg = self.config
        B, T, C = x.shape
        H, R = cfg.n_head, cfg.kv_lora_rank
        dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
        q, = _settled(_dense(cfg, H * (dn + dr), use_bias=False,
                             name="q_proj")(x))
        q = q.reshape(B, T, H, dn + dr)
        ckr = _dense(cfg, R + dr, use_bias=False, name="kv_a_proj")(x)
        c = nn.RMSNorm(epsilon=cfg.layer_norm_epsilon, dtype=cfg.dtype,
                       name="kv_a_norm")(ckr[..., :R])
        # read as a matrix (its two halves are absorbed into the query and
        # the output), so a parameter of this module and no Dense
        w_kvb = self.param("kv_b_proj", nn.initializers.lecun_normal(),
                           (R, H * (dn + dv))).astype(cfg.dtype)
        w_kvb = w_kvb.reshape(R, H, dn + dv)
        o_proj = _dense(cfg, C, use_bias=False, name="o_proj")

        start = kv_cache["start"] if decode else jnp.zeros((), jnp.int32)
        per_slot = jnp.ndim(start) == 1
        positions = _chunk_positions(kv_cache, T) \
            if decode and "chunk" in kv_cache \
            else (start[:, None] if per_slot else start) \
            + jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
        # (pos_emb "none": the "rope" columns are there and nothing
        # rotates them)
        rope = functools.partial(apply_rotary, positions=positions,
                                 rotary_dim=dr, theta=cfg.rope_theta) \
            if cfg.pos_emb == "rotary" else (lambda x: x)
        q_n, q_r = q[..., :dn], rope(q[..., dn:])
        k_r = rope(ckr[..., None, R:])[:, :, 0]                 # (B, T, dr)
        scale = 1.0 / math.sqrt(dn + dr)
        f32 = jnp.float32

        def expanded():
            """Causal attention over the fresh tokens, K and V rebuilt."""
            kv = jnp.einsum("bsr,rhd->bshd", c, w_kvb)
            att = (jnp.einsum("bthd,bshd->bhts", q_n.astype(f32),
                              kv[..., :dn].astype(f32))
                   + jnp.einsum("bthd,bsd->bhts", q_r.astype(f32),
                                k_r.astype(f32))) * scale
            att = jnp.where(jnp.tril(jnp.ones((T, T), dtype=bool)), att,
                            -1e30)
            return jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(att, -1),
                              kv[..., dn:].astype(f32))

        if not decode:
            y = expanded().astype(cfg.dtype).reshape(B, T, H * dv)
            return o_proj(y), None
        assert kv_cache is not None, "decode needs the kv_cache slice"
        # this step's cached rows, stored positions-minor like every cache
        # here
        rows = jnp.concatenate([c, k_r], -1).astype(cfg.dtype)  # (B, T, R+dr)
        # the absorbed query: W_kvb^K^T q_n beside q_r, one (R + dr) row a
        # head that scores a cached row as it stands
        q_abs = jnp.concatenate(
            [jnp.einsum("bthd,rhd->bthr", q_n, w_kvb[..., :dn]), q_r],
            -1).astype(cfg.dtype)
        if "table" in kv_cache:
            from ..ops.attention.latent_attention import latent_attention
            from ..ops.attention.paged_attention import paged_write_columns

            assert per_slot, "paged decode is slot-pooled: start must be (B,)"

            def paged(cache, q_abs, rows):
                table, li = cache["table"], cache["layer"]
                page_size = cfg.max_seq_len // table.shape[1]
                chunk = _chunk_shaped(q_abs)
                leaf = _traced_once(paged_write_columns, "page_size",
                                    chunk=chunk)(
                    cache["c"], li, rows.transpose(0, 2, 1), table,
                    cache["start"], page_size=page_size)
                return _traced_once(
                    latent_attention, "page_size", "rank", "scale",
                    chunk=chunk)(
                    q_abs, leaf, table, cache["start"], layer=li,
                    page_size=page_size, rank=R, scale=scale), {"c": leaf}

            ctx, new_cache = _by_row_group(kv_cache, paged, q_abs, rows)
        else:
            leaf = _store_columns(kv_cache["c"], rows.transpose(0, 2, 1),
                                  start)                        # (B, R+dr, S)
            new_cache = dict(kv_cache, c=leaf)
            if decode == "prefill" and T > 1:
                # start == 0: the prompt's causal window IS the fresh rows
                y = expanded().astype(cfg.dtype).reshape(B, T, H * dv)
                return o_proj(y), new_cache
            S = leaf.shape[-1]
            att = jnp.einsum("bthw,bws->bhts", q_abs.astype(f32),
                             leaf.astype(f32)) * scale
            # row t may see cache positions [0, start + t]
            att = jnp.where((jnp.arange(S) <= positions[..., None])[:, None],
                            att, -1e30)
            ctx = jnp.einsum("bhts,brs->bthr", jax.nn.softmax(att, -1),
                             leaf[:, :R].astype(f32))
        y = jnp.einsum("bthr,rhd->bthd", ctx.astype(cfg.dtype),
                       w_kvb[..., dn:])
        return o_proj(y.astype(cfg.dtype).reshape(B, T, H * dv)), new_cache


class TransformerMLP(nn.Module):
    config: TransformerConfig

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        cfg = self.config
        hidden = cfg.ffn_width
        if cfg.activation == "swiglu":
            # llama sizing: 2/3 * 4d rounded — callers control via mlp_ratio
            gate = _dense(cfg, hidden, use_bias=cfg.mlp_bias, name="gate_proj")(x)
            up = _dense(cfg, hidden, use_bias=cfg.mlp_bias, name="up_proj")(x)
            h = jax.nn.silu(gate) * up
        else:
            h = _dense(cfg, hidden, use_bias=cfg.mlp_bias, name="up_proj")(x)
            h = jax.nn.gelu(h, approximate=True) if cfg.activation == "gelu" \
                else jax.nn.relu(h)
        h = _dense(cfg, cfg.n_embd, use_bias=cfg.mlp_bias, name="down_proj")(h)
        if cfg.dropout > 0:
            h = nn.Dropout(cfg.dropout)(h, deterministic=deterministic)
        return h


class TransformerBlock(nn.Module):
    config: TransformerConfig
    kind: Optional[str] = None      # a STATE_KINDS entry | "attention":
    # the layer's kind in a model of state and attention layers, whose
    # stacked leaves are one a kind; None: the configuration's one mixer

    @nn.compact
    def __call__(self, x, decode: Union[bool, str] = False,
                 deterministic: bool = True, kv_cache=None, layer=None,
                 experts=None, ffn_layer=None):
        cfg = self.config
        if self.kind == "lightning" or cfg.sparse_attention is not None:
            from .lightning_sparse import LightningMixer, SparseAttention
        attention, name = (Mamba2Mixer, "mamba") if self.kind == "mamba" \
            else (KDAMixer, "kda") if self.kind == "kda" \
            else (GatedDeltaNetMixer, "gdn") if self.kind == "gdn" \
            else (ShortConvMixer, "conv") if self.kind == "conv" \
            else (LightningMixer, "lightning") if self.kind == "lightning" \
            else (PowerRetention if cfg.retention else
                  LatentAttention if cfg.latent else
                  SparseAttention if cfg.sparse_attention is not None
                  else CachedAttention, "attn")
        a, new_cache = attention(cfg, name=name)(
            _norm(cfg, "ln_1")(x), decode=decode, deterministic=deterministic,
            kv_cache=kv_cache, layer=layer)
        stats = ()      # a routed FFN's counts follow (x, cache)

        def mlp(h):
            if not cfg.n_experts:
                return TransformerMLP(cfg, name="mlp")(h, deterministic)
            from ..moe.routed_ffn import RoutedFFN

            nonlocal stats
            # (the expert leaves stack the routed layers only; ``layer``
            # counts a kind's layers where the stack is one a kind, and
            # ``ffn_layer`` then says which routed layer this is)
            m, layer_stats = RoutedFFN(
                cfg.n_experts, cfg.experts_per_token, cfg.norm_topk_prob,
                cfg.scoring_func, cfg.routed_scaling_factor,
                cfg.n_shared_experts * cfg.ffn_width, cfg.dtype,
                cfg.topk_norm_eps, cfg.shared_expert_gate, name="mlp")(
                    h, experts, ffn_layer if ffn_layer is not None
                    else layer - cfg.first_k_dense
                    if cfg.first_k_dense else layer)
            stats = (layer_stats,)
            return m

        r = cfg.residual_multiplier

        def branch(h):      # what joins the residual stream, scaled once
            return h if r == 1.0 else h * jnp.asarray(r, h.dtype)

        if cfg.parallel_residual:
            x = x + branch(a) + branch(mlp(_norm(cfg, "ln_2")(x)))
        else:
            x = x + branch(a)
            x = x + branch(mlp(_norm(cfg, "ln_2")(x)))
        return (x, new_cache, *stats)


# beside a state's leaves in the carry: which cache row each batch entry
# is and where its real tokens end (TransformerLM._transform)
STATE_ROW_KEYS = ("rows", "valid")


class _ScanBlock(nn.Module):
    """One scanned layer. The carry is ``(x, cache, start, layer_idx)``
    and the STACKED (L-leading) KV cache rides it. Which of three access
    patterns a layer uses is read off the carry's contents:

    - the contiguous cache (``generate()``, the ``SlotPool``): each
      layer dynamic-slices its own (B, KV, cd, S) entry and
      dynamic-update-slices it back. Scanned cache VARIABLES double-
      buffer the quantized cache above ~100 MB through their xs/ys pair
      (PERF.md §8, the carry-DUS lead); the carry-DUS of a
      batch-major dense row did not.
    - a recurrent state (``KVCacheSpec.state_leaves``, a layer of
      :class:`PowerRetention`, :class:`Mamba2Mixer`, :class:`KDAMixer` or
      :class:`ShortConvMixer`):
      whole like a page pool's leaves, for the same reason and one more:
      a slice would be one layer's state of EVERY row, 0.5 GB a layer at
      the served size, read and written for the one row a chunk runs.
    - a page pool (``"table"`` in the carry): the stacked leaves and the
      layer counter go to the block whole and come back whole; the
      block reads and writes them through Pallas calls that index
      (layer, page) and alias the leaf. Carry-DUS is NOT kept in place
      here: with a scatter and a custom call between the slice and the
      update, XLA made one pass over the 67 MB slice for the
      dynamic-slice and one for the dynamic-update-slice of every layer
      and leaf, 0.96 s of a 2.81 s busy trace beside the scatter's own
      re-layouts (serve-pythia-1b4-chat, ledger, PR 24)."""

    config: TransformerConfig
    kind: Optional[str] = None      # TransformerBlock.kind; the counter of
    # such a layer counts the layers of its kind (its stacked leaves')

    @nn.compact
    def __call__(self, carry, decode, deterministic, experts=None,
                 ffn_layer=None):
        x, cache, start, li = carry
        cfg = self.config
        cls = TransformerBlock
        if cfg.remat:
            cls = nn.remat(cls, prevent_cse=False, static_argnums=(2, 3))
        block = cls(cfg, self.kind, name="block")
        # the scan's counter and the model's expert leaves reach a block
        # only where its configuration reads them
        more = (li, experts) if (cfg.layer_types is not None
                                 or cfg.n_experts) else ()
        if ffn_layer is not None:
            more += (ffn_layer,)
        # a layer's output beside the carry: what its routed FFN counted
        # (stacked over the layers by the scan), nothing for a dense FFN
        if cache is None:
            x, _, *stats = block(x, decode, deterministic, None, *more)
            return (x, None, start, li + 1 if more else li), tuple(stats)
        state_leaves = make_kv_cache_spec(cfg).state_leaves
        if "table" in cache or (state_leaves and self.kind != "attention"):
            # a page pool (the "table*" entries are the POOL-WIDE page
            # tables (slots, pages_per_slot), one a layer group, shared by
            # the group's layers and never written) or a recurrent state:
            # the stacked leaves go to the block whole and come back whole
            # (its kernels index (layer, page) or (layer, row) and alias
            # them); whatever else rides the carry passes through
            x, leaves, *stats = block(x, decode, deterministic,
                                      dict(cache, start=start, layer=li),
                                      *more)
            return (x, dict(cache, **leaves), start, li + 1), tuple(stats)
        # (an attention layer beside state layers slices its K/V alone)
        sliced = [key for key in cache
                  if key not in state_leaves + STATE_ROW_KEYS]
        kv_slice = {key: jax.lax.dynamic_index_in_dim(cache[key], li, 0,
                                                      keepdims=False)
                    for key in sliced}
        kv_slice["start"] = start
        x, new_slice, *stats = block(x, decode, deterministic, kv_slice,
                                     *more)
        cache = dict(cache, **{
            key: jax.lax.dynamic_update_slice_in_dim(
                cache[key], new_slice[key][None], li, 0) for key in sliced})
        return (x, cache, start, li + 1), tuple(stats)


class _CacheStore(nn.Module):
    """Owns the STACKED (n_layer-leading) KV-cache arrays as top-level
    flax variables in the ``cache`` collection. The stack rides the
    layer scan's CARRY (see _ScanBlock: sliced and updated per layer
    for the contiguous cache, whole for a page pool, where per-layer
    slicing measured two passes over the slice a layer) rather than
    scanned per-layer
    variables; this module is only the flax-variable home that keeps the
    engine-facing contract (prefill/decode with ``mutable=["cache"]``,
    cache an opaque pytree) unchanged. Call once to READ (returns the
    value dict + start), again with ``new_values``/``new_index`` to
    WRITE the post-scan state back."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, batch_size, new_values=None, new_index=None,
                 paged=False):
        cfg = self.config
        L, KV = cfg.n_layer, cfg.kv_heads
        cidx = self.variable("cache", "index",
                             lambda: jnp.zeros((), jnp.int32))
        if cfg.retention:
            # a recurrent state and no k / v (a provided cache passes
            # through at its own row count, as everywhere here)
            leaf = self.variable(
                "cache", "s", jnp.zeros,
                (L, batch_size, KV) + make_kv_cache_spec(cfg).state,
                jnp.float32)
            values = {"s": leaf.value}
            if new_values is not None:
                leaf.value = new_values["s"]
                cidx.value = new_index
            return values, cidx.value
        state = {}
        if cfg.hybrid:
            # K/V (or the latent row) over the attention layers and a
            # state group over the state layers (a provided cache passes
            # through at its own shapes, as everywhere here)
            spec = make_kv_cache_spec(cfg)
            L = spec.kv_layers
            state = {key: self.variable("cache", key, jnp.zeros, leaf.shape,
                                        leaf.dtype)
                     for key, leaf in jax.eval_shape(
                         lambda: spec._state_cache(batch_size)).items()}
        if cfg.latent:
            # one row a token that every head reads, and no k / v
            leaf = self.variable(
                "cache", "c", jnp.zeros,
                (L, batch_size, cfg.latent, cfg.max_seq_len), cfg.dtype)
            values = {"c": leaf.value,
                      **{key: var.value for key, var in state.items()}}
            if new_values is not None:
                leaf.value = new_values["c"]
                for key, var in state.items():
                    var.value = new_values[key]
                cidx.value = new_index
            return values, cidx.value
        cache_dtype, cache_d, _ = kv_cache_spec(cfg)
        shape = (L, batch_size, KV, cache_d, cfg.max_seq_len)
        ck = self.variable("cache", "k", jnp.zeros, shape, cache_dtype)
        cv = self.variable("cache", "v", jnp.zeros, shape, cache_dtype)
        values = {"k": ck.value, "v": cv.value,
                  **{key: var.value for key, var in state.items()}}
        if new_values is not None:
            for key, var in state.items():
                var.value = new_values[key]
        if paged and cfg.sparse_attention is not None:
            # a page pool hands in the index's group means beside k / v
            # (always provided: the initializer never runs)
            var = self.variable("cache", "kc", jnp.zeros, (0,), jnp.float32)
            values["kc"] = var.value
            if new_values is not None:
                var.value = new_values["kc"]
        if paged and kv_cache_groups(cfg) is not None:
            # a page pool of layer groups hands in a second pair of
            # leaves (always provided: the initializer never runs)
            for key in ("k_win", "v_win"):
                var = self.variable("cache", key, jnp.zeros, (0,),
                                    cache_dtype)
                values[key] = var.value
                if new_values is not None:
                    var.value = new_values[key]
        if cfg.kv_cache_quant:
            sshape = (L, batch_size, KV, cfg.max_seq_len)
            cks = self.variable("cache", "k_scale", jnp.zeros, sshape,
                                jnp.float32)
            cvs = self.variable("cache", "v_scale", jnp.zeros, sshape,
                                jnp.float32)
            values.update(k_scale=cks.value, v_scale=cvs.value)
        if new_values is not None:
            ck.value = new_values["k"]
            cv.value = new_values["v"]
            if cfg.kv_cache_quant:
                cks.value = new_values["k_scale"]
                cvs.value = new_values["v_scale"]
            cidx.value = new_index
        return values, cidx.value


class TransformerLM(nn.Module):
    """Causal LM over any family preset. Training convention matches the
    engine (``__call__(batch) -> loss``); inference uses ``prefill``/
    ``decode`` with the ``cache`` collection."""

    config: TransformerConfig

    def kv_cache_spec(self) -> KVCacheSpec:
        """Module-declared KV-cache contract (shape/dtype/capacity of the
        ``cache_store`` variables). Engines size and bound caches from
        THIS — not from inferring axis positions off pytree leaves — and
        the serving slot pool allocates through it (batch dim = slots).
        Safe to call on an unbound module: reads only ``self.config``."""
        return make_kv_cache_spec(self.config)

    def setup(self):
        cfg = self.config
        seeded = {} if cfg.embedding_init_std is None else {
            "embedding_init": nn.initializers.normal(cfg.embedding_init_std)}
        self.embed_tokens = nn.Embed(cfg.vocab_size, cfg.n_embd, dtype=cfg.dtype,
                                     name="embed_tokens", **seeded)
        if cfg.pos_emb == "learned":
            self.embed_pos = nn.Embed(cfg.max_seq_len, cfg.n_embd, dtype=cfg.dtype,
                                      name="embed_pos")
        if cfg.embed_layernorm:
            self.embed_ln = _norm(cfg, "embed_ln")
        def scan(config, length, name, kind=None):
            return nn.scan(
                _ScanBlock,
                variable_axes={"params": 0},
                split_rngs={"params": True, "dropout": True},
                length=length,
                in_axes=(nn.broadcast,) * (3 if config.n_experts else 2),
                metadata_params={nn.PARTITION_NAME: "layers"},
            )(config, kind, name=name)

        state_kind = cfg.hybrid
        if state_kind:
            # two stacked leaves, one a layer kind; the scans own them and
            # make them, :meth:`_hybrid_layers` runs them in the published
            # order (the leading layers with a plain FFN are state layers
            # and a third leaf, ``dense_blocks``)
            n_att = cfg.layer_types.count("attention")
            self.state_blocks = scan(
                cfg, cfg.n_layer - n_att - cfg.first_k_dense,
                f"{state_kind}_blocks", state_kind)
            self.attn_blocks = scan(cfg, n_att, "attn_blocks", "attention")
        if cfg.first_k_dense:
            # the leading layers with a plain FFN: a scan of their own in
            # front, the carry (cache, layer counter) running through both
            self.dense_blocks = scan(cfg.dense_layers(), cfg.first_k_dense,
                                     "dense_blocks", state_kind)
        if not state_kind:
            self.blocks = scan(cfg, cfg.n_layer - cfg.first_k_dense, "blocks")
        if cfg.n_experts:
            from ..moe.routed_ffn import ExpertLeaves

            self.experts = ExpertLeaves(cfg.n_layer - cfg.first_k_dense,
                                        cfg.experts_held or cfg.n_experts,
                                        cfg.n_embd, cfg.ffn_width,
                                        name="experts")
        self.cache_store = _CacheStore(cfg, name="cache_store")
        self.ln_f = _norm(cfg, "ln_f")
        if not cfg.tie_word_embeddings:
            head_cfg = cfg if (cfg.int8_head or not cfg.int8_weights) else \
                dataclasses.replace(cfg, int8_weights=False)
            self.lm_head = _dense(head_cfg, cfg.vocab_size, use_bias=False,
                                  dtype=jnp.float32, name="lm_head")

    def _hybrid_layers(self, carry, decode, deterministic, experts=()):
        """The layers of a model of state (mamba, kda, gdn or conv) and attention
        layers, in the published order, off the stacked leaves: a scan over
        the periods whose body scans the state layers before the period's
        attention layer, runs that one, and scans those after it
        (``[5, attention, 4]`` four times for 40 layers). The compiled
        body holds the state block twice and the attention block once,
        whatever the depth. A layer takes its slice of its kind's leaf by
        its index among the layers of that kind, which is also where its
        cache lies (``_ScanBlock``'s counter); where the FFN is routed
        (``experts``: the model's expert leaves) it is told its index among
        the routed layers too. ``first_k_dense`` leading state layers with
        a plain FFN are a leaf of their own: the first period then runs
        out of the scan, behind them. Returns ``(x, cache)`` and what the
        routed layers counted, stacked in their order (or ``()``)."""
        cfg = self.config
        x, cache, start, _ = carry
        kind, dense = cfg.hybrid, cfg.first_k_dense
        if self.is_initializing():
            # the scans make their leaves; the order of this one pass over
            # an empty cache is nobody's
            empty = (x, None, start, carry[3] + dense)
            if dense:
                (x, *_), _ = self.dense_blocks(empty, False, deterministic)
            (x, *_), _ = self.state_blocks((x,) + empty[1:], False,
                                           deterministic, *experts)
            (x, *_), _ = self.attn_blocks((x,) + empty[1:], False,
                                          deterministic, *experts)
            return (x, cache), ()
        params = self.variables["params"]
        leaves = {kind: params[f"{kind}_blocks"],
                  "attention": params["attn_blocks"]}
        blocks = {k: _ScanBlock(cfg, k, parent=None) for k in leaves}
        if not cfg.hybrid_repeats:
            # a pattern without a period (adjacent attention layers, runs
            # of unequal length): run after run in the published order, a
            # run of several layers a scan over its slice of the kind's
            # leaf. The compiled program holds a block once a run.
            def one(of, state, index):
                x, cache = state
                sliced = jax.tree_util.tree_map(lambda w: w[index],
                                                leaves[of])
                (x, cache, _, _), _ = blocks[of].apply(
                    {"params": sliced}, (x, cache, start, index), decode,
                    deterministic)
                return x, cache

            state = (x, cache)
            for of, first, count in cfg.hybrid_runs:
                if count == 1:
                    state = one(of, state, jnp.asarray(first, jnp.int32))
                else:
                    state, _ = jax.lax.scan(
                        lambda st, j, of=of: (one(of, st, j), None), state,
                        first + jnp.arange(count, dtype=jnp.int32))
            return state, ()
        before, after, periods = cfg.hybrid_period

        def layer(of, state, index, ffn_layer):
            """Layer ``index`` of kind ``of`` (the state layers' leaf starts
            behind the dense ones); ``ffn_layer``: its place among the
            routed layers. Returns the state and what its FFN counted."""
            x, cache = state
            at = index - dense if of == kind else index
            sliced = jax.tree_util.tree_map(lambda w: w[at], leaves[of])
            (x, cache, _, _), stats = blocks[of].apply(
                {"params": sliced}, (x, cache, start, index), decode,
                deterministic, *experts,
                *((ffn_layer,) if experts else ()))
            return (x, cache), stats

        def state_run(state, first, count, ffn_first):
            """``count`` state layers from the ``first``: the state, and
            their counts stacked (None for no layer)."""
            if not count:
                return state, None
            return jax.lax.scan(
                lambda st, j: layer(kind, st, first + j, ffn_first + j),
                state, jnp.arange(count, dtype=jnp.int32))

        def joined(runs):
            """The counts of runs of layers, each a tuple of (layers, ...)
            arrays (of none without a routed FFN), as one run's."""
            return tuple(jnp.concatenate(parts) for parts in zip(
                *(run for run in runs if run is not None)))

        def period(state, p, lead=0):
            """Period ``p`` from its state layer ``lead`` on."""
            first, routed = p * (before + after), p * (before + after + 1) \
                - dense
            state, s0 = state_run(state, first + lead, before - lead,
                                  routed + lead)
            state, s1 = layer("attention", state, p, routed + before)
            state, s2 = state_run(state, first + before, after,
                                  routed + before + 1)
            return state, joined([s0, tuple(s[None] for s in s1), s2])

        state, stats = (x, cache), []
        first_period = 0
        if dense:
            # the leading layers with a plain FFN, then the rest of their
            # period: what the scan over the periods cannot hold
            (x, cache, _, _), _ = self.dense_blocks(
                (x, cache, start, jnp.zeros((), jnp.int32)), decode,
                deterministic)
            state, s = period((x, cache), 0, lead=dense)
            stats.append(s)
            first_period = 1
        if periods > first_period:
            state, s = jax.lax.scan(
                period, state,
                jnp.arange(first_period, periods, dtype=jnp.int32))
            stats.append(tuple(a.reshape((-1,) + a.shape[2:]) for a in s))
        return state, joined(stats)

    def _transform(self, input_ids, positions, decode, deterministic,
                   head=True, paged_table=None, state_rows=None,
                   valid_len=None, chunk=None):
        cfg = self.config
        B, T = input_ids.shape
        x = self.embed_tokens(input_ids)
        if cfg.embedding_multiplier != 1.0:
            x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
        if cfg.pos_emb == "learned":
            x = x + self.embed_pos(positions)
        if cfg.embed_layernorm:
            x = self.embed_ln(x)
        # the stacked expert leaves go to every layer whole, as a
        # broadcast argument of the scan (cast once, outside it)
        more = (jax.tree_util.tree_map(lambda w: w.astype(cfg.dtype),
                                       self.experts()),) \
            if cfg.n_experts else ()
        if decode:
            paged = paged_table is not None
            cache, start = self.cache_store(B, paged=paged)
            if paged:
                # paged-kernel decode: the cache_store variables hold the
                # PAGE POOL (L, P, KV, cd, page_size) — provided-cache
                # shapes pass through — and the shared page table (one a
                # layer group, as a dict) joins the carry so every layer
                # resolves positions through it (stripped before
                # writeback; see _ScanBlock)
                cache = dict(cache, **(paged_table if isinstance(
                    paged_table, dict) else {"table": paged_table}))
            if cfg.retention or cfg.hybrid:
                # which cache row each batch entry is and where its real
                # tokens end ride beside the state (PowerRetention and the
                # mixers of the state layers)
                if state_rows is not None:
                    cache["rows"] = jnp.asarray(state_rows, jnp.int32)
                if valid_len is not None:
                    cache["valid"] = jnp.broadcast_to(
                        jnp.asarray(valid_len, jnp.int32), (B,))
            if chunk is not None:
                # a prefill chunk's rows ahead of the decode rows
                # (:meth:`chunk_beside_decode`): how the chunk's rows reach
                # the cache rides beside the decode rows' own addressing,
                # and a mixer takes the two apart (``_by_row_group``)
                cache["chunk"] = chunk
            carry = (x, cache, start, jnp.zeros((), jnp.int32))
            if cfg.hybrid:
                (x, cache), stats = self._hybrid_layers(
                    carry, decode, deterministic, more)
            else:
                if cfg.first_k_dense:
                    carry, _ = self.dense_blocks(carry, decode,
                                                 deterministic)
                (x, cache, _, _), stats = self.blocks(
                    carry, decode, deterministic, *more)
            if stats and self.is_mutable_collection("stats"):
                # a caller that asks for the "stats" collection gets what
                # the routed FFN counted in this call, over its layers
                from ..moe.routed_ffn import call_stats

                self.sow("stats", "moe", call_stats(
                    stats[0], cfg.experts_held or cfg.n_experts),
                    init_fn=lambda: None, reduce_fn=lambda _, new: new)
            cache = {key: val for key, val in cache.items()
                     if not key.startswith("table")
                     and key not in STATE_ROW_KEYS + ("chunk",)}
            # (beside a chunk every decode row moved one position; the
            # chunk's slot is the caller's to place)
            self.cache_store(B, new_values=cache,
                             new_index=start + (T if chunk is None else 1),
                             paged=paged)
        else:
            carry = (x, None, jnp.zeros((), jnp.int32),
                     jnp.zeros((), jnp.int32))
            if cfg.first_k_dense and not cfg.hybrid:
                # (a scan without a cache counts layers only where its
                # configuration reads the counter: set it)
                (x, *_), _ = self.dense_blocks(carry, decode, deterministic)
                carry = (x, None, carry[2],
                         jnp.full((), cfg.first_k_dense, jnp.int32))
            if cfg.hybrid:
                (x, _), _ = self._hybrid_layers(carry, decode, deterministic,
                                                more)
            else:
                (x, _, _, _), _ = self.blocks(carry, decode, deterministic,
                                              *more)
        x = self.ln_f(x)
        if not head:
            return x  # pre-projection hidden states (streaming loss path)
        return self._project_head(x)

    def _project_head(self, x):
        """The ONE vocabulary-projection path (scoring, generation
        prefill and decode all route here)."""
        if self.config.tie_word_embeddings:
            logits = self.embed_tokens.attend(x.astype(jnp.float32))
        else:
            logits = self.lm_head(x.astype(jnp.float32))
        if self.config.logits_scaling != 1.0:
            logits = logits / self.config.logits_scaling
        return logits

    def logits(self, input_ids, deterministic: bool = True):
        B, T = input_ids.shape
        pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
        return self._transform(input_ids, pos, False, deterministic)

    def prefill(self, input_ids):
        """Run the prompt, filling the KV cache. Call with
        ``mutable=["cache"]``. Returns (B, T, V) logits. The "prefill"
        mode contract (start == 0) lets attention run over the fresh
        prompt k/v (flash for long prompts) instead of the allocated
        cache — O(T) memory in the prompt, not O(T x max_seq_len)."""
        B, T = input_ids.shape
        pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
        return self._transform(input_ids, pos, "prefill", True)

    def prefill_last(self, input_ids, last_pos=None):
        """Prefill variant for GENERATION: fills the cache but projects
        only the LAST position onto the vocabulary, returning (B, 1, V)
        logits. Sampling uses only the last position, and the full
        (B, T, V) fp32 logits are the largest prefill allocation
        (~0.8 GB at B=8/T=512/V=50k, what bound the batch of a 32k-context
        server in rounds 1-5); scoring callers keep
        ``prefill``.

        ``last_pos`` (scalar or (B,) int32, optional) selects WHICH
        position to project instead of T-1 — the serving path right-pads
        prompts to a shape bucket (bounded prefill recompiles) and
        projects the true last prompt token; causal attention keeps that
        position's hidden state independent of the right padding."""
        B, T = input_ids.shape
        pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
        x = self._transform(
            input_ids, pos, "prefill", True, head=False,
            valid_len=None if last_pos is None
            else jnp.asarray(last_pos, jnp.int32) + 1)
        if last_pos is None:
            x = x[:, -1:]
        else:
            idx = jnp.broadcast_to(jnp.asarray(last_pos, jnp.int32), (B,))
            x = jax.vmap(lambda xb, i: jax.lax.dynamic_slice_in_dim(
                xb, i, 1, 0))(x, idx)
        return self._project_head(x)

    def prefill_chunk(self, input_ids, start_pos, last_idx, rows=None,
                      table=None):
        """Chunked serving prefill: process a fixed-width (B, C) token
        chunk AGAINST the allocated cache at per-slot offsets and project
        only ``last_idx`` onto the vocabulary, returning (B, 1, V).

        This is ``decode``'s multi-token path (window-masked attention
        over the allocated cache — row ``t`` of the chunk sees cache
        positions ``[0, start + t]``, which IS the causal mask against
        already-written positions), with ``prefill_last``'s head
        discipline (one projected position instead of the (B, C, V)
        logits tensor). Long prompts stream through it C tokens at a
        time, so per-step serving latency is bounded by the chunk width
        instead of the longest queued prompt (Sarathi-style stall-free
        chunked prefill; PAPERS.md). Call with ``mutable=["cache"]``;
        ``start_pos`` is scalar or (B,) — the serving path passes the
        slot's current prefill offset. Right-padding in the final
        partial chunk writes masked garbage past the true length
        (invisible to attention once the caller sets the slot index to
        the true length, exactly like the bucketed ``prefill_last``).

        A recurrent state has no index to hide padding behind: tokens past
        ``last_idx`` are padding to it and leave it alone, and ``rows``
        (B,) names the row of the provided cache each entry of the batch
        is (a server hands in its whole pool and one row's chunk).

        With ``table`` the provided cache is the PAGE POOL and the chunk
        goes through the pages as a :meth:`decode_paged` step of C rows
        does: every layer writes the chunk's columns into its pages
        through the entries' (B, pages_per_slot) table rows (one a layer
        group, as a dict) and reads them back in place, so no dense row
        of ``max_seq_len`` positions is built or scored."""
        B, T = input_ids.shape
        off = start_pos[:, None] if jnp.ndim(start_pos) == 1 else start_pos
        pos = off + jnp.broadcast_to(jnp.arange(T)[None], (B, T))
        x = self._transform(input_ids, pos, True, True, head=False,
                            paged_table=table, state_rows=rows,
                            valid_len=jnp.asarray(last_idx, jnp.int32) + 1)
        idx = jnp.broadcast_to(jnp.asarray(last_idx, jnp.int32), (B,))
        x = jax.vmap(lambda xb, i: jax.lax.dynamic_slice_in_dim(
            xb, i, 1, 0))(x, idx)
        return self._project_head(x)

    def decode(self, input_ids, start_pos, rows=None):
        """One (or few) token step against the cache; ``start_pos`` is the
        current cache length — scalar for a B-uniform batch, or (B,) for
        slot-pooled decode where every sequence sits at its own offset
        (continuous batching). Call with ``mutable=["cache"]``. ``rows``
        (B,), for a model with a recurrent state: the cache row of each
        entry, out of range for an entry that does not run, whose row's
        state stays bit for bit (a K/V model hides such an entry's column
        behind its index and takes no ``rows``)."""
        B, T = input_ids.shape
        off = start_pos[:, None] if jnp.ndim(start_pos) == 1 else start_pos
        pos = off + jnp.broadcast_to(jnp.arange(T)[None], (B, T))
        return self._transform(input_ids, pos, True, True, state_rows=rows)

    def decode_paged(self, input_ids, start_pos, table, rows=None):
        """Fused paged-kernel decode step: like :meth:`decode`, but the
        provided ``cache`` collection holds the PAGE POOL arrays
        (``KVCacheSpec.paged_cache`` layout — k/v (L, P, KV, cache_d,
        lanes), no batch axis) and ``table`` is the (B,
        pages_per_slot) int32 page table (sentinel = num_pages). Column
        writes go through the table and attention reads pages in place,
        both inside Pallas calls on the whole leaf — no dense per-slot
        view and no slice of a leaf is ever materialized. ``start_pos``
        must be the per-slot (B,) cache lengths; T is 1 for plain decode
        and K + 1 for speculative verify (a prefill chunk's rows go the
        same way through :meth:`prefill_chunk` with a ``table``).
        ``rows``, for a pool with a state group beside its pages: as
        :meth:`decode`'s. Call
        with ``mutable=["cache"]``; greedy output is bitwise-identical
        to the dense-oracle :meth:`decode` over ``dense_from_pages`` of
        the same pool."""
        B, T = input_ids.shape
        off = start_pos[:, None] if jnp.ndim(start_pos) == 1 else start_pos
        pos = off + jnp.broadcast_to(jnp.arange(T)[None], (B, T))
        return self._transform(input_ids, pos, True, True,
                               paged_table=table, state_rows=rows)

    def chunk_beside_decode(self, chunk_ids, chunk_start, last_idx,
                            chunk_table, input_ids, start_pos, table,
                            rows=None, chunk_row=None):
        """A :meth:`prefill_chunk` step of ONE slot through its table row(s)
        and a :meth:`decode_paged` step of every slot in one pass over the
        layers: ``chunk_ids`` (1, C) at ``chunk_start`` (1,) through
        ``chunk_table`` (1, pages_per_slot; a dict of one a layer group),
        ``input_ids`` (B,) at ``start_pos`` (B,) through ``table``. The
        ``C + B`` rows go through the layers side by side, so whatever
        reads a weight (the embedding, the norms, the projections, the FFN
        or the experts, the head) reads it ONCE for both; what reads the
        cache runs the chunk's rows and then the decode rows, each through
        the kernels of the separate steps (``_by_row_group``). The provided
        cache's ``index`` holds the decode rows' positions AS THE CHUNK
        LEAVES THEM (its slot's entry past the chunk) and comes back
        advanced by one. ``rows`` as :meth:`decode_paged`'s; ``chunk_row``
        (1,), for a pool with a state group: the chunk's slot. Returns the
        logits of the chunk's ``last_idx`` (1, 1, V) and of the decode rows
        (B, 1, V); call with ``mutable=["cache"]``. What each row computes
        is what the separate steps compute for it."""
        C = chunk_ids.shape[1]
        ids = jnp.concatenate([chunk_ids, input_ids[None, :]], axis=1)
        pos = jnp.concatenate(
            [chunk_start[:, None] + jnp.arange(C)[None, :],
             start_pos[None, :]], axis=1)
        chunk = dict(chunk_table if isinstance(chunk_table, dict)
                     else {"table": chunk_table}, start=chunk_start)
        if chunk_row is not None:
            chunk.update(
                rows=jnp.asarray(chunk_row, jnp.int32),
                valid=jnp.broadcast_to(
                    jnp.asarray(last_idx, jnp.int32) + 1, (1,)))
        x = self._transform(ids, pos, True, True, head=False,
                            paged_table=table, state_rows=rows,
                            chunk=chunk)[0]
        picked = jnp.concatenate(
            [jax.lax.dynamic_slice_in_dim(
                x, jnp.asarray(last_idx, jnp.int32), 1, 0), x[C:]])
        logits = self._project_head(picked[:, None])
        return logits[:1], logits[1:]

    def __call__(self, batch, deterministic: bool = False):
        cfg = self.config
        input_ids = batch["input_ids"]
        labels = batch.get("labels", input_ids) if hasattr(batch, "get") \
            else input_ids
        targets = labels[:, 1:]
        mask = (targets >= 0).astype(jnp.float32)
        targets = jnp.maximum(targets, 0)
        if cfg.loss_chunk:
            # streaming loss: never materialize the (B, T, V) logits
            from ..ops.transformer.chunked_xent import chunked_softmax_xent

            B, T = input_ids.shape
            pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
            x = self._transform(input_ids, pos, False, deterministic,
                                head=False)[:, :-1]
            if cfg.tie_word_embeddings:
                # Embed.attend promotes both operands to cfg.dtype; the
                # embedding table is never quantized (quantize_lm_params
                # converts only Dense kernels), so int8_weights+int8_head
                # is fine here — the guard below is untied-only
                w, cd = self.embed_tokens.embedding, cfg.dtype
            else:
                if cfg.int8_weights and cfg.int8_head:
                    raise ValueError(
                        "loss_chunk does not compose with an int8-quantized "
                        "untied lm_head (QuantDense stores an int8 kernel + "
                        "scale; the streaming loss reads a plain kernel). "
                        "Serve int8 with the dense loss, keep the head fp32, "
                        "or tie the embeddings.")
                if self.is_initializing():
                    # create the head's params (the streaming path reads
                    # the kernel without calling the module)
                    self.lm_head(jnp.zeros((1, x.shape[-1]), jnp.float32))
                w = self.lm_head.variables["params"]["kernel"].T
                cd = jnp.float32  # the lm_head Dense computes in fp32
            nll_sum = chunked_softmax_xent(
                x, w, targets, mask, cfg.loss_chunk, compute_dtype=cd)
            return nll_sum / jnp.maximum(mask.sum(), 1.0)
        logits = self.logits(input_ids, deterministic)
        logits = logits[:, :-1]
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
