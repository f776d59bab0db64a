"""Mixture-of-Experts GPT (Megatron-GPT-MoE family).

Covers the reference's MoE model containers
(``module_inject/containers/megatron_gpt_moe.py`` / ``base_moe.py``) and
the DeepSpeed-MoE NLG recipe (alternating dense/MoE transformer blocks,
docs/_posts/2021-12-09-deepspeed-moe-nlg.md): a causal LM whose MLPs are
:class:`deepspeed_tpu.moe.MoE` layers on every ``moe_every``-th block
(PR-MoE-style pyramid via ``num_experts`` per MoE block). Expert
parallelism comes from the global mesh's ``expert`` axis; the engine folds
the gate aux loss via the (loss, aux) tuple convention.

Blocks are a Python loop (not nn.scan) because dense and MoE blocks have
different parameter structures — the stack depth of MoE models is modest
and per-block remat keeps activation memory flat.
"""

from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Sequence, Union

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..moe import MoE
from .gpt2 import CausalSelfAttention, GPT2Config


@dataclasses.dataclass
class GPTMoEConfig:
    vocab_size: int = 50257
    n_positions: int = 1024
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    moe_every: int = 2                 # every k-th block is MoE (NLG recipe)
    num_experts: Union[int, Sequence[int]] = 8  # int, or per-MoE-block list
    k: int = 1                         # top-k gating
    capacity_factor: float = 1.25
    drop_tokens: bool = True
    # random token selection when dropping at capacity (the reference's
    # use_rts, sharded_moe.py: breaks position bias; draws the "gating"
    # rng in train mode). False = deterministic position-order dropping
    use_rts: bool = True
    # "auto" (einsum for k=1, index for k>=2 — the measured per-k policy),
    # "index" (scatter/gather), or "einsum" (the reference's dense one-hot
    # dispatch) — see moe/layer.py and the MoE lead of PERF.md §8
    moe_dispatch_mode: str = "auto"
    # PR-MoE residual blend (arXiv:2201.05596): dense expert + learned
    # per-token coefficient alongside each MoE block
    use_residual: bool = False
    aux_loss_weight: float = 0.01
    dropout: float = 0.0
    layer_norm_epsilon: float = 1e-5
    dtype: Any = jnp.float32
    remat: bool = False


def _attention_config(cfg: "GPTMoEConfig") -> GPT2Config:
    """Reuse the GPT-2 attention (flash / sequence-parallel paths and
    dropout wiring included) instead of duplicating it."""
    return GPT2Config(vocab_size=cfg.vocab_size, n_positions=cfg.n_positions,
                      n_embd=cfg.n_embd, n_layer=cfg.n_layer,
                      n_head=cfg.n_head, dropout=cfg.dropout,
                      layer_norm_epsilon=cfg.layer_norm_epsilon,
                      dtype=cfg.dtype)


class _Block(nn.Module):
    config: GPTMoEConfig
    use_moe: bool
    num_experts: int

    @nn.compact
    def __call__(self, x, deterministic: bool):
        cfg = self.config
        ln1 = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon, dtype=cfg.dtype,
                           name="ln_1")
        ln2 = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon, dtype=cfg.dtype,
                           name="ln_2")
        x = x + CausalSelfAttention(_attention_config(cfg), name="attn")(
            ln1(x), deterministic)
        aux = jnp.asarray(0.0, jnp.float32)
        if self.use_moe:
            moe_out, aux, _ = MoE(
                hidden_size=cfg.n_embd, num_experts=self.num_experts,
                k=cfg.k, capacity_factor=cfg.capacity_factor,
                drop_tokens=cfg.drop_tokens, use_rts=cfg.use_rts,
                dispatch_mode=cfg.moe_dispatch_mode,
                use_residual=cfg.use_residual, dtype=cfg.dtype,
                name="moe")(
                    ln2(x), deterministic=deterministic)
            x = x + moe_out
        else:
            h = nn.Dense(4 * cfg.n_embd, dtype=cfg.dtype, name="mlp_fc")(
                ln2(x))
            h = jax.nn.gelu(h, approximate=True)
            h = nn.Dense(cfg.n_embd, dtype=cfg.dtype, name="mlp_proj")(h)
            if cfg.dropout > 0:
                h = nn.Dropout(cfg.dropout)(h, deterministic=deterministic)
            x = x + h
        return x, aux


class GPTMoEModel(nn.Module):
    """Causal LM with alternating dense/MoE blocks —
    ``__call__(batch) -> (loss, aux_loss)`` (engine convention)."""

    config: GPTMoEConfig

    def _experts_for_block(self, moe_index: int) -> int:
        ne = self.config.num_experts
        if isinstance(ne, int):
            return ne
        return int(ne[min(moe_index, len(ne) - 1)])

    @nn.compact
    def __call__(self, batch, deterministic: bool = False):
        cfg = self.config
        ids = batch["input_ids"]
        B, T = ids.shape
        wte = nn.Embed(cfg.vocab_size, cfg.n_embd, dtype=cfg.dtype,
                       name="wte")
        x = wte(ids)
        pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
        x = x + nn.Embed(cfg.n_positions, cfg.n_embd, dtype=cfg.dtype,
                         name="wpe")(pos)

        aux_total = jnp.asarray(0.0, jnp.float32)
        moe_index = 0
        block_cls = _Block
        if cfg.remat:
            block_cls = nn.remat(_Block, prevent_cse=False,
                                 static_argnums=(2,))
        for i in range(cfg.n_layer):
            use_moe = cfg.moe_every > 0 and (i % cfg.moe_every ==
                                             cfg.moe_every - 1)
            n_exp = self._experts_for_block(moe_index) if use_moe else 0
            x, aux = block_cls(cfg, use_moe, n_exp,
                               name=f"block_{i}")(x, deterministic)
            if use_moe:
                aux_total = aux_total + aux
                moe_index += 1

        x = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon, dtype=cfg.dtype,
                         name="ln_f")(x)
        logits = wte.attend(x.astype(jnp.float32))

        # same shifted-target convention as GPT2LMHeadModel (gpt2.py:246):
        # honor batch["labels"] when present
        labels = batch.get("labels", ids) if hasattr(batch, "get") else ids
        targets = labels[:, 1:]
        logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
        token_ll = jnp.take_along_axis(logp, targets[..., None],
                                       axis=-1)[..., 0]
        loss = -jnp.mean(token_ll)
        return loss, cfg.aux_loss_weight * aux_total
