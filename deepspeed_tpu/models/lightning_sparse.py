"""Two mixers that stand in the attention's place of a ``TransformerBlock``
(``models/transformer_lm.py``), in one layer stack: Lightning linear
attention (``layer_types`` "lightning", a state layer) and learned
block-sparse attention (``TransformerConfig.sparse_attention``, the stack's
attention layers). The state's kernels are ``ops/lightning.py``'s, the
choice of blocks and the index's cache ``ops/attention/sparse_index.py``'s,
the page read ``ops/attention/sparse_read.py``'s."""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Union

import flax.linen as nn
import jax
import jax.numpy as jnp

from .lm_config import TransformerConfig
from .lm_parts import (_by_row_group, _chunk_positions, _chunk_shaped, _dense,
                       _gated, _norm_qk, _project_qkv, _store_columns,
                       _traced_once, apply_rotary)
from .state_layers import _state_rows


def _positions(kv_cache, decode, B: int, T: int):
    """Positions (B, T) of a call's rows, as ``CachedAttention`` reads
    them off the cache it is handed."""
    if not decode:
        return jnp.broadcast_to(jnp.arange(T)[None, :], (B, T))
    if "chunk" in kv_cache:
        return _chunk_positions(kv_cache, T)
    start = kv_cache["start"]
    return jnp.broadcast_to(
        (start[:, None] if jnp.ndim(start) == 1 else start)
        + jnp.arange(T)[None, :], (B, T))


class LightningMixer(nn.Module):
    """Lightning attention (Qin et al., arXiv:2401.04658) in the attention's
    place: ``n_head`` heads of ``head_dim``, every head its own q, k and v.
    With ``u`` the normed input and ``l_h`` a head's constant decay::

        q, k, v = W u       q, k <- RMSNorm_head (qk_norm), then the rotary
        S_t = l_h S_{t-1} + k_t v_t^T       (float32, head_dim x head_dim)
        o_t = S_t^T q_t / sqrt(head_dim)    o <- RMSNorm_head(o)
        out = W_o (o (.) sigmoid(W_z u))    l_h = exp(-2^(-8 (h + 1) / H))

    which is ``ops/state_space``'s recurrence ``H <- a H + x (x) B``, ``y =
    H C`` with ``x = v``, ``B = k``, ``C = q / sqrt(head_dim)`` a head's own,
    ``dt = 1`` and ``A_h = log l_h``: the leaf and the chunk kernel are
    that module's, the decode kernel its own, under the names
    ``lightning_decode`` / ``lightning_chunk`` (``ops/lightning.py``).
    Without a cache: whole sequences from an empty
    state. With one, ``kv_cache`` holds the stacked leaf ``s`` whole with
    ``layer``, ``start``, ``rows`` and ``valid`` (as ``Mamba2Mixer``): a
    token past ``valid`` is padding and leaves the state alone, an entry at
    position 0 reads none. The layer rotates its own q and k whatever the
    attention layers beside it do."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, u, *, decode: Union[bool, str] = False,
                 deterministic: bool = True, kv_cache=None, layer=None):
        from ..ops import lightning

        cfg = self.config
        B, T, C = u.shape
        H, D = cfg.n_head, cfg.head_dim
        # (every head its own k and v, whatever the attention layers share)
        q, k, v = _project_qkv(dataclasses.replace(cfg, n_kv_head=H), u)
        if cfg.qk_norm:
            q, k = _norm_qk(cfg, q, k)
        pos = _positions(kv_cache, decode, B, T)
        q = apply_rotary(q, pos, rotary_dim=D, theta=cfg.rope_theta)
        k = apply_rotary(k, pos, rotary_dim=D, theta=cfg.rope_theta)
        q = q.astype(jnp.float32) / math.sqrt(D)

        def mix(cache, q, k, v):
            """The state of one group of rows (B, T): ``(o, leaves)``."""
            B, T = q.shape[:2]
            if cache is None:
                return lightning.lightning_sequence(q, k, v), None
            li, rows, fresh, valid = _state_rows(cache, B, T)
            if T == 1:
                o, s = lightning.lightning_decode(
                    q[:, 0], k[:, 0], v[:, 0], cache["s"], li, rows, fresh)
                return o[:, None], {"s": s}
            o, s = _traced_once(lightning.lightning_prefill,
                                chunk=_chunk_shaped(q))(
                q, k, v, cache["s"], li, rows, fresh, valid)
            return o, {"s": s}

        o, leaves = _by_row_group(kv_cache, mix, q, k, v) if decode \
            else mix(None, q, k, v)
        o = nn.RMSNorm(epsilon=cfg.layer_norm_epsilon, dtype=jnp.float32,
                       name="o_norm")(o)
        o = _gated(cfg, o.reshape(B, T, H * D), u)
        return _dense(cfg, C, use_bias=False, name="o_proj")(o), leaves


class SparseAttention(nn.Module):
    """Grouped-query attention that reads the blocks a learned index chose
    and a sliding window (``ops/attention/sparse_index.py`` has the
    equations; ``TransformerConfig.sparse_attention`` the sizes), a norm on
    each head of q and k (``qk_norm``), no positions (``pos_emb`` "none")
    and a gate on its output (``attn_output_gate``).

    Three forms of one mathematics. Without a cache, or a prompt that
    starts at 0 into a contiguous cache ("prefill"): the keys are the
    call's own. A contiguous cache row (``SlotPool``, ``generate``): the
    keys are the row's. Both are :func:`sparse_attention_dense`: group
    means from the keys themselves, the choice, a mask over the full
    scores. A page pool (``"table"`` in the cache): the step's K/V columns
    go into the layer's pages (``paged_write``), its keys join the group
    means of the index's leaf ``kc`` under the same table, the choice is
    made against that leaf's rows of the slot, and
    ``ops/attention/sparse_read.py`` reads what was chosen: a decode row's
    (row, KV head) the ~``topk`` + window pages of ITS choice
    (``sparse_read``), a chunk's queries as the rows of the blocks they
    chose beside the window they share (``sparse_read_chunk``): the
    equations' tokens whatever the context.
    Every query chooses for itself, by its own position:
    chunked prefill and decoding through the cache equal one full pass."""

    config: TransformerConfig

    def _paged_step(self, kv_cache, q, k, v):
        from ..ops.attention import sparse_index as si
        from ..ops.attention import sparse_read as sr
        from ..ops.attention.paged_attention import paged_write_columns

        cfg = self.config
        B, T, H, D = q.shape
        sizes = cfg.sparse
        start, layer, table = (kv_cache["start"], kv_cache["layer"],
                               kv_cache["table"])
        page_size = cfg.max_seq_len // table.shape[1]
        chunk = _chunk_shaped(q)
        new = {}
        for key, cols in (("k", k), ("v", v)):
            new[key] = _traced_once(
                paged_write_columns, "page_size", chunk=chunk)(
                kv_cache[key], layer,
                cols.astype(cfg.dtype).transpose(0, 2, 3, 1), table, start,
                page_size=page_size)
        rows = kv_cache.get("rows")
        running = jnp.ones((B,), bool) if rows is None or "s" not in kv_cache \
            else (rows >= 0) & (rows < kv_cache["s"].shape[1])
        valid = kv_cache.get("valid")
        valid = jnp.full((B,), T, jnp.int32) if valid is None \
            else jnp.minimum(valid, T)
        qpos = start[:, None] + jnp.arange(T)[None, :]
        with jax.named_scope("sparse_index"):
            new["kc"] = si.group_sums_write(
                kv_cache["kc"], layer, k.astype(cfg.dtype), table, start,
                valid, running, page_size=page_size,
                stride=sizes.kernel_stride)
            P = new["kc"].shape[1]
            means = new["kc"][layer, jnp.minimum(table, P - 1)]
            # (B, entries, KV, G, D): a slot's groups in the keys' order
            means = means.transpose(0, 2, 1, 3, 4).reshape(
                B, cfg.kv_heads, -1, D)
        scale = 1 / math.sqrt(D)
        blocks = si.choose_blocks(q, means, qpos, sizes, scale)
        read = dict(sizes=sizes, page_size=page_size, scale=scale)
        if chunk:
            y, _ = _traced_once(sr.read_chunk, "sizes", "page_size", "scale")(
                q[0].astype(cfg.dtype), new["k"], new["v"], layer, table[0],
                qpos[0], blocks[0], **read)
            y = y[None]
        else:
            # (rows of several queries each read a query at a time)
            y, _ = sr.read_rows(
                q.astype(cfg.dtype).reshape(B * T, H, D), new["k"], new["v"],
                layer, jnp.repeat(table, T, axis=0), qpos.reshape(-1),
                blocks.reshape(B * T, *blocks.shape[2:]),
                running=jnp.repeat(running, T), **read)
        return y.astype(cfg.dtype).reshape(B, T, H * D), new

    @nn.compact
    def __call__(self, x, *, decode: Union[bool, str] = False,
                 deterministic: bool = True, kv_cache=None, layer=None):
        from ..ops.attention import sparse_index as si

        cfg = self.config
        B, T, C = x.shape
        H, KV, D = cfg.n_head, cfg.kv_heads, cfg.head_dim
        q, k, v = _project_qkv(cfg, x)
        if cfg.qk_norm:
            q, k = _norm_qk(cfg, q, k)

        def out(y):
            y = _gated(cfg, y, x) if cfg.attn_output_gate \
                else y.astype(cfg.dtype)
            return _dense(cfg, C, use_bias=False, name="o_proj")(y)

        if decode and "table" in kv_cache:
            y, leaves = _by_row_group(kv_cache, self._paged_step, q, k, v)
            return out(y), leaves
        qpos = _positions(kv_cache, decode, B, T)
        leaves, keys, values = None, k, v
        if decode:
            store = functools.partial(_store_columns, start=kv_cache["start"])
            leaves = dict(kv_cache)
            for key, rows in (("k", k), ("v", v)):
                leaves[key] = store(kv_cache[key], rows.astype(
                    cfg.dtype).transpose(0, 2, 3, 1))   # (B, KV, D, T)
            if not (decode == "prefill" and T > 1):
                # (B, KV, D, S) -> (B, S, KV, D): the row as it stands
                keys, values = (leaves[key].transpose(0, 3, 1, 2)
                                for key in ("k", "v"))
        y, _ = si.sparse_attention_dense(q, keys, values, qpos, cfg.sparse,
                                         1 / math.sqrt(D))
        return out(y.astype(cfg.dtype).reshape(B, T, H * D)), leaves
