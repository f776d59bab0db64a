"""GPT-2 family, TPU-first.

The flagship model for the Megatron-GPT2 / GPT-2 baseline configs
(reference tests/model/Megatron_GPT2, BASELINE.json "GPT-2 125M ZeRO-1").
Architecture notes (not a port — reference has no JAX model zoo):

* Transformer blocks run under ``nn.scan`` — one set of stacked block params
  with a leading layer dimension. This is the TPU-idiomatic layout: one
  compiled block body (fast compiles at depth), and under ZeRO-3 the
  per-layer slices of the stacked params are gathered layer-by-layer inside
  the scan, reproducing the reference's module-granular gather/release
  (stage3.py fetch/release hooks) as a compiler-scheduled pipeline
  (``_ScanBody`` asks the engine's ZeRO policy for it:
  ``ZeroShardingPolicy.gather_at_use_site``).
* ``remat`` enables activation checkpointing around each block
  (≅ runtime/activation_checkpointing/checkpointing.py:708).
* Tensor-parallel sharding is declared, not coded: ``gpt2_sharding_rules``
  maps parameter paths to mesh axes (Megatron-style column/row splits);
  the engine's ZeroShardingPolicy composes ZeRO axes on top.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import backend
from ..parallel.mesh import MODEL_AXIS, get_zero_policy


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    n_positions: int = 1024
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    dropout: float = 0.0
    layer_norm_epsilon: float = 1e-5
    dtype: Any = jnp.bfloat16  # activation/compute dtype
    remat: bool = False  # activation checkpointing per block
    # remat policy (ops/attention/flash_attention.py REMAT_POLICIES): "full"
    # keeps a block's input and the attention kernel's (out, lse) and
    # recomputes every XLA operation; "dots" keeps matmul outputs too and
    # recomputes only elementwise ops (cheaper recompute,
    # jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    remat_policy: str = "full"
    # Pallas flash kernel: True | False | "auto" (on-TPU when seq >= the
    # crossover in use_flash_by_default; off elsewhere)
    use_flash_attention: Any = "auto"
    # sequence/context parallelism over the `seq` mesh axis:
    # None | "ring" (ppermute KV rotation) | "ulysses" (all-to-all head swap)
    sequence_parallel: Optional[str] = None
    # lax.scan unroll factor over the stacked blocks: >1 lets XLA schedule
    # across layer boundaries (scan steps otherwise materialize the carry
    # and serialize); costs compile time proportionally
    scan_unroll: int = 1
    # block-sparse attention: a SparsityConfig (ops/sparse_attention) —
    # every attention layer computes only the layout's blocks via the
    # fused Pallas kernel (gather formulation off-TPU / fine granules).
    # The model-level analog of the reference's SparseAttentionUtils
    # module swap (module_inject; docs/_posts/2020-09-09-sparse-attention.md)
    sparse_attention: Optional[Any] = None
    # streaming cross-entropy: >0 computes the LM loss in T-chunks of
    # this size without materializing the (B, T, V) logits tensor
    # (ops/transformer/chunked_xent.py). Measured ~free at the flagship
    # (-0.3%) and lets previously-OOM configs compile (350M mbs16, 774M
    # dots_plain) — but did NOT unlock a better operating point at
    # either size (PERF.md §8). 0 = dense loss.
    loss_chunk: int = 0


# sizes for the standard family
GPT2_SIZES = {
    "gpt2-125m": dict(n_embd=768, n_layer=12, n_head=12),
    "gpt2-medium": dict(n_embd=1024, n_layer=24, n_head=16),
    "gpt2-large": dict(n_embd=1280, n_layer=36, n_head=20),
    "gpt2-xl": dict(n_embd=1600, n_layer=48, n_head=25),
    "gpt2-1.3b": dict(n_embd=2048, n_layer=24, n_head=16),
}


def gpt2_config(name: str = "gpt2-125m", **overrides) -> GPT2Config:
    return GPT2Config(**{**GPT2_SIZES[name], **overrides})


def gpt2_sharding_rules():
    """Megatron-style TP rules as (path-regex, PartitionSpec entries).

    Scanned block params carry a leading layer dim (axis 0 = None).
    The TPU-native analog of the reference's injection policies / AutoTP
    layer classification (module_inject/auto_tp.py:13,
    module_inject/layers.py:15,32): column-parallel for QKV & MLP-in,
    row-parallel for attn-out & MLP-out, vocab-parallel embedding.
    """
    M = MODEL_AXIS
    return [
        (r"wte/embedding", (M, None)),          # vocab-parallel embedding
        (r"wpe/embedding", (None, None)),
        (r"attn/qkv/kernel", (None, None, M)),  # column parallel (layer dim first)
        (r"attn/proj/kernel", (None, M, None)),  # row parallel
        (r"mlp/fc/kernel", (None, None, M)),    # column parallel
        (r"mlp/proj/kernel", (None, M, None)),  # row parallel
        (r"attn/qkv/bias", (None, M)),
        (r"mlp/fc/bias", (None, M)),
    ]


class CausalSelfAttention(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        cfg = self.config
        B, T, C = x.shape
        H = cfg.n_head
        qkv = nn.Dense(3 * C, dtype=cfg.dtype, name="qkv")(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        use_flash = cfg.use_flash_attention
        if use_flash == "auto":
            from ..ops.attention.flash_attention import use_flash_by_default

            use_flash = use_flash_by_default(T) and cfg.dropout == 0
        q = q.reshape(B, T, H, C // H)
        k = k.reshape(B, T, H, C // H)
        v = v.reshape(B, T, H, C // H)

        if cfg.sparse_attention is not None:
            if cfg.sequence_parallel:
                raise ValueError("sparse_attention does not compose with "
                                 "sequence_parallel (the layout is over the "
                                 "full sequence)")
            if cfg.dropout > 0 and not deterministic:
                raise ValueError("sparse_attention does not support "
                                 "attention-probability dropout")
            import numpy as np

            from ..ops.sparse_attention.pallas_kernel import (
                block_sparse_flash_attention,
                supports_pallas,
            )

            scfg = cfg.sparse_attention
            layout = np.asarray(scfg.make_layout(T))
            if supports_pallas(scfg.block, T) and backend.on_tpu():
                y = block_sparse_flash_attention(
                    q, k, v, layout, scfg.block, causal=True)
            else:
                # exact gather formulation (CPU tests / fine granules)
                from ..ops.sparse_attention.sparse_self_attention import (
                    block_sparse_attention,
                )

                y = block_sparse_attention(q, k, v, layout, scfg.block,
                                           causal=True)
        elif cfg.sequence_parallel:
            if cfg.sequence_parallel not in ("ring", "ulysses"):
                raise ValueError(
                    f"sequence_parallel must be 'ring' or 'ulysses', "
                    f"got {cfg.sequence_parallel!r}")
            if cfg.dropout > 0:
                raise ValueError(
                    "sequence_parallel does not support attention-probability "
                    "dropout (dropout>0)")
            from ..ops.attention.sequence_parallel import (
                ring_attention,
                ulysses_attention,
            )
            from ..parallel.mesh import get_model_parallel_world_size

            head_axes = MODEL_AXIS if get_model_parallel_world_size() > 1 else None
            if cfg.sequence_parallel == "ring":
                if cfg.use_flash_attention is True:
                    raise ValueError(
                        "sequence_parallel='ring' computes its own blockwise "
                        "softmax; use_flash_attention only composes with "
                        "'ulysses'")
                y = ring_attention(q, k, v, causal=True, head_axes=head_axes)
            else:
                attn_fn = None
                if use_flash:
                    from ..ops.attention.flash_attention import flash_attention

                    def attn_fn(q, k, v, *, causal, scale):
                        return flash_attention(q, k, v, causal=causal, scale=scale)
                y = ulysses_attention(q, k, v, causal=True, head_axes=head_axes,
                                      attn_fn=attn_fn)
        elif use_flash:
            if cfg.dropout > 0 and cfg.use_flash_attention is True:
                raise ValueError(
                    "use_flash_attention does not support attention-probability "
                    "dropout (dropout>0); use the dense path or dropout=0")
            from ..ops.attention.flash_attention import flash_attention

            y = flash_attention(q, k, v, causal=True)
        else:
            scale = 1.0 / jnp.sqrt(C // H).astype(cfg.dtype)
            att = jnp.einsum("bthd,bshd->bhts", q, k) * scale
            mask = jnp.tril(jnp.ones((T, T), dtype=bool))
            att = jnp.where(mask[None, None], att, jnp.finfo(att.dtype).min)
            att = jax.nn.softmax(att.astype(jnp.float32), axis=-1).astype(cfg.dtype)
            if cfg.dropout > 0:
                att = nn.Dropout(cfg.dropout)(att, deterministic=deterministic)
            y = jnp.einsum("bhts,bshd->bthd", att, v)
        y = y.reshape(B, T, C)
        y = nn.Dense(C, dtype=cfg.dtype, name="proj")(y)
        if cfg.dropout > 0:
            y = nn.Dropout(cfg.dropout)(y, deterministic=deterministic)
        return y


class MLP(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        cfg = self.config
        h = nn.Dense(4 * cfg.n_embd, dtype=cfg.dtype, name="fc")(x)
        h = jax.nn.gelu(h, approximate=True)
        h = nn.Dense(cfg.n_embd, dtype=cfg.dtype, name="proj")(h)
        if cfg.dropout > 0:
            h = nn.Dropout(cfg.dropout)(h, deterministic=deterministic)
        return h


class Block(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        cfg = self.config
        x = x + CausalSelfAttention(cfg, name="attn")(
            nn.LayerNorm(epsilon=cfg.layer_norm_epsilon, dtype=cfg.dtype,
                         name="ln_1")(x), deterministic)
        x = x + MLP(cfg, name="mlp")(
            nn.LayerNorm(epsilon=cfg.layer_norm_epsilon, dtype=cfg.dtype,
                         name="ln_2")(x), deterministic)
        return x


class _ScanBody(nn.Module):
    """scan body: (carry, broadcast deterministic) → (carry, None)."""

    config: GPT2Config

    @nn.compact
    def __call__(self, x, deterministic):
        block_cls = Block
        zero = get_zero_policy()
        if zero is not None and zero.gathers_at_use_site \
                and not self.is_initializing():
            # ZeRO-3: this layer's weights are gathered HERE and the
            # activation stays split over the batch (zero/policy.py says
            # why it has to be said); inside the remat below, so that the
            # backward gathers again and no gathered weight is a residual
            block_cls = nn.map_variables(
                Block, "params", trans_in_fn=functools.partial(
                    zero.gather_at_use_site,
                    path="/".join(self.path + ("block",)),
                    layers=self.config.n_layer))
        if self.config.remat:
            # whatever the policy, the attention kernel's named (out, lse)
            # are kept: the backward reads them and runs no forward kernel
            from ..ops.attention.flash_attention import REMAT_POLICIES

            block_cls = nn.remat(
                block_cls, prevent_cse=False, static_argnums=(2,),
                policy=REMAT_POLICIES[self.config.remat_policy])
        x = block_cls(self.config, name="block")(x, deterministic)
        return x, None


class GPT2LMHeadModel(nn.Module):
    """Causal LM with tied embedding head.

    ``__call__(batch)`` returns the mean cross-entropy loss — the engine's
    model convention. ``batch`` = {"input_ids": (B,T) int32,
    optional "labels": (B,T), optional "attention_mask": (B,T)}.
    """

    config: GPT2Config
    # the stacked parameters whose layer slices `_ScanBody` hands to the
    # ZeRO policy's gather_at_use_site (the engine counts them as it places
    # the state)
    use_site_gathered = ("blocks/block",)

    def setup(self):
        cfg = self.config
        self.wte = nn.Embed(cfg.vocab_size, cfg.n_embd, dtype=cfg.dtype, name="wte")
        self.wpe = nn.Embed(cfg.n_positions, cfg.n_embd, dtype=cfg.dtype, name="wpe")
        self.blocks = nn.scan(
            _ScanBody,
            variable_axes={"params": 0},
            split_rngs={"params": True, "dropout": True},
            length=cfg.n_layer,
            in_axes=nn.broadcast,
            metadata_params={nn.PARTITION_NAME: "layers"},
            unroll=cfg.scan_unroll,
        )(cfg, name="blocks")
        self.ln_f = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon, dtype=cfg.dtype,
                                 name="ln_f")

    def hidden(self, input_ids, deterministic: bool = True):
        """Final hidden states (B, T, C) before the tied-head projection."""
        B, T = input_ids.shape
        pos = jnp.arange(T)[None, :]
        x = self.wte(input_ids) + self.wpe(pos)
        # nn.scan carries (x,) through the stacked blocks
        x, _ = self.blocks(x, deterministic)
        return self.ln_f(x)

    def logits(self, input_ids, deterministic: bool = True):
        x = self.hidden(input_ids, deterministic)
        # tied head: project onto embedding matrix
        return self.wte.attend(x.astype(jnp.float32))

    def __call__(self, batch, deterministic: bool = False):
        cfg = self.config
        input_ids = batch["input_ids"]
        labels = batch.get("labels", input_ids) if hasattr(batch, "get") else input_ids
        targets = labels[:, 1:]
        mask = (targets >= 0).astype(jnp.float32)  # -100/-1 = ignore
        targets = jnp.maximum(targets, 0)
        if cfg.loss_chunk:
            # streaming loss: never materialize the (B, T, V) logits.
            # The projection runs in cfg.dtype, exactly like Embed.attend
            # (which promotes both operands to the module dtype).
            from ..ops.transformer.chunked_xent import chunked_softmax_xent

            x = self.hidden(input_ids, deterministic)[:, :-1]
            nll_sum = chunked_softmax_xent(
                x, self.wte.embedding, targets, mask, cfg.loss_chunk,
                compute_dtype=cfg.dtype)
            return nll_sum / jnp.maximum(mask.sum(), 1.0)
        logits = self.logits(input_ids, deterministic)
        # causal shift: predict token t+1
        logits = logits[:, :-1]
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
