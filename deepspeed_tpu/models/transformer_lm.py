"""Unified decoder-only transformer covering the reference's model families.

The reference ships 17 per-model injection "policies/containers"
(``deepspeed/module_inject/containers/``: gpt2, gptj, gptneo(x), llama, opt,
bloom, megatron, bert, ...) plus fused inference modules
(``model_implementations/transformers/ds_transformer.py:19`` and the
``ds_bloom/ds_gpt/ds_opt/ds_megatron_gpt`` variants). TPU-native, those
collapse into ONE parameterized flax module: every family is a point in a
small feature space (position encoding × norm × activation × residual
topology × GQA), and XLA fuses what the reference hand-fused in CUDA.

Families are presets of :class:`TransformerConfig` (see ``FAMILY_PRESETS``):

=============  ========  =========  ========  ===================
family         pos_emb   norm       act       notes
=============  ========  =========  ========  ===================
gpt2           learned   layernorm  gelu      tied head, qkv bias
gpt-neo        learned   layernorm  gelu      local attn ignored
gptj           rotary    layernorm  gelu      parallel residual
gpt-neox       rotary    layernorm  gelu      parallel residual, rotary_pct
llama          rotary    rmsnorm    swiglu    no biases, untied head, GQA
opt            learned   layernorm  relu      tied head
bloom          alibi     layernorm  gelu      embedding layernorm
megatron-gpt   learned   layernorm  gelu
mellum         rotary    rmsnorm    routed    ``head_dim`` a field, GQA,
                                              window and full layers
                                              (``layer_types``, rotary by
                                              type), top-k of E experts
brumby         rotary    rmsnorm    swiglu    every layer gated
                                              power retention of
                                              degree 2: a norm on q
                                              and k, a gate a KV
                                              head, a recurrent
                                              state in place of K/V
moonlight      rotary    rmsnorm    routed    latent attention (one
                                              cached row a token),
                                              sigmoid router with a
                                              bias, shared experts,
                                              leading dense layers
granite-hybrid none      rmsnorm    swiglu    Mamba-2 layers beside
                                              GQA layers, tied head
kimi_linear    none      rmsnorm    routed    KDA layers beside latent
                                              attention, moonlight's
                                              FFN, experts held
lfm2_moe       rotary    rmsnorm    routed    gated short-convolution
                                              layers beside QK-normed
                                              GQA, sigmoid router with
                                              a bias and no shared
                                              expert, tied head
=============  ========  =========  ========  ===================

Layer kinds that differ (``layer_types``: ``sliding_attention`` or
``full_attention``, each with its own rotary table) run inside the ONE
layer scan: a layer reads its kind off the scan's counter, so the mask
and the table are selected, not branched on. The routed FFN
(``n_experts > 0``) is ``deepspeed_tpu/moe/routed_ffn.py``; its stacked
expert leaves ``(layers, E, C, F)`` are parameters of the model, not of
the scanned block, and reach every layer whole (a scanned leaf would be
sliced, that is copied, a layer). Nothing of this is reached by a
configuration with ``n_experts == 0`` and no ``layer_types``.

A third kind, ``power_retention`` (:class:`PowerRetention`), keeps no K/V:
its cache is a state of fixed size a sequence (``KVCacheSpec.state``),
whose stacked leaf rides the scan's carry whole and is updated in place
by the kernels of ``ops/attention/power_retention.py``, for the rows the
caller names (``rows``) and no others. Today every layer of a model is of
this kind or none is.

A fourth, latent attention (:class:`LatentAttention`, ``kv_lora_rank >
0``), caches ONE row a token a layer, ``[c ; k_r]`` (the normed latent and
the shared rotary key), that every head reads: ``KVCacheSpec.latent``, one
leaf ``c`` and no ``k`` / ``v``. The leading ``first_k_dense`` layers of
such a model may carry a plain gated FFN (``dense_ffn_dim``) before the
routed layers: they are a scan of their own (``dense_blocks``) in front of
``blocks``, the layer counter and the cache running through both.

A fifth, ``mamba`` (:class:`Mamba2Mixer`), stands BESIDE ``attention``
layers (full, position-free or rotary) in one model, one attention layer a
repeating period. The two kinds have different parameter trees, so the
stack is two stacked leaves (``mamba_blocks``, ``attn_blocks``) run in the
published order by :meth:`TransformerLM._hybrid_layers`, and the cache is
two groups: a state group over the mamba layers
(``KVCacheSpec.state_group``: ``s``, the state, and ``conv``, the
convolution's last inputs; a row a sequence, no positions) and K/V over the
attention layers, contiguous or paged. A page pool keeps the state group
beside its pages.

A sixth, ``kda`` (:class:`KDAMixer`, Kimi Linear's gated delta rule with a
decay a channel), stands beside ``attention`` layers the same way (the
state layers' stacked leaf is ``kda_blocks``; ``s`` a head's (K, V) matrix,
``conv`` the last inputs of the three convolutions). The attention layers
beside state layers may be latent (``kv_lora_rank > 0``: the cache is the
state group beside the one leaf ``c``), with ``pos_emb`` "none" they rotate
nothing, and the FFN may be routed, behind ``first_k_dense`` leading layers
with a plain one inside the first period (``dense_blocks``, of the leading
layers' kind). A routed FFN may hold a share of its experts
(``experts_held``).

A seventh, ``conv`` (:class:`ShortConvMixer`, LFM2's gated short
convolution), is a state layer with NO matrix state: its state group is the
one leaf ``conv``, the convolution's last ``conv_taps - 1`` inputs (the
stacked leaf is ``conv_blocks``). The attention layers beside it may rotate
(``pos_emb`` "rotary") and norm each head of q and k (``qk_norm``).

KV-cache decoding uses the flax ``cache`` variable collection: ``prefill``
writes the prompt's K/V at positions [0, T), ``decode`` appends one position
via ``lax.dynamic_update_slice`` and attends over the static-shape cache with
a validity mask — static shapes keep XLA happy (the reference's
inference_context.h workspace is the moral equivalent).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional, Tuple, Union

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..ops import backend


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 50257
    max_seq_len: int = 2048
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    n_kv_head: Optional[int] = None     # < n_head ⇒ grouped-query attention
    pos_emb: str = "learned"            # learned | rotary | alibi | none
    rotary_pct: float = 1.0             # fraction of head_dim rotated (neox)
    rope_theta: float = 10000.0
    norm: str = "layernorm"             # layernorm | rmsnorm
    activation: str = "gelu"            # gelu | relu | swiglu
    mlp_ratio: float = 4.0
    parallel_residual: bool = False     # gptj/neox: x + attn(ln1 x) + mlp(ln2 x)
    qkv_bias: bool = True
    mlp_bias: bool = True
    embed_layernorm: bool = False       # bloom
    tie_word_embeddings: bool = True
    layer_norm_epsilon: float = 1e-5
    dropout: float = 0.0
    dtype: Any = jnp.bfloat16
    use_flash_attention: Any = "auto"   # True | False | "auto" (Pallas flash
    # for the full-context forward on TPU from the tuned crossover length;
    # alibi and train-mode attention dropout stay on the einsum path)
    remat: bool = False
    decode_kernel: str = "auto"         # auto | on | off (fused Pallas decode)
    decode_block: Optional[int] = None  # pin the fused decode kernel's block
    # granule (STATIC int). The paged-attention kernel's position block is
    # one page, so a dense arm pinned to decode_block=page_size runs the
    # SAME online-softmax blocking — the bitwise-parity oracle for the
    # paged kernel (ops/attention/paged_attention.py). None keeps the
    # allocation-based default (pick_block_s).
    kv_cache_quant: bool = False        # int8 KV cache (per-row scales):
    # halves the cache's HBM traffic — the resource decode is bound by —
    # and halves KV memory, doubling the servable context per chip
    kv_cache_packed: Optional[bool] = None  # store the int8 cache in an
    # int32 container (pack_int8_sublanes: 4 head-dim rows per word, the
    # TPU's own sublane byte order, so the kernel unpacks with a free
    # pltpu.bitcast). Same bytes in a natively-tiled dtype — insurance
    # against Mosaic's (4,1)-packed s8 layout-conversion copies (the
    # round-4/5 capacity killer; the positions-minor layout + carry-DUS
    # scan fixed the measured cases, and packed/plain now measure equal —
    # PERF.md §8). Only meaningful with
    # kv_cache_quant; requires head_dim % 4 == 0. Tri-state: None (auto,
    # the default) packs when head_dim allows and warns once when it
    # can't; True requires a packable head_dim (raises otherwise);
    # False keeps the plain int8 container.
    int8_weights: bool = False          # serve with int8-at-rest Dense kernels
    int8_kernel: str = "auto"           # auto | on | off (Pallas dequant-GEMM)
    int8_head: bool = False             # quantize lm_head too (off: the vocab
    # projection — the largest single accuracy lever — stays full precision,
    # matching the ZeRO-Inference streamed tier and reference practice)
    loss_chunk: int = 0                 # streaming cross-entropy: >0 computes
    # the LM loss in T-chunks of this size without materializing the
    # (B, T, V) logits (ops/transformer/chunked_xent.py); 0 = dense loss
    head_size: Optional[int] = None     # per-head width where it is not
    # n_embd // n_head (read it as ``head_dim``)
    ffn_dim: Optional[int] = None       # FFN width where it is not
    # mlp_ratio * n_embd; with experts, the width of ONE expert
    layer_types: Optional[Tuple[str, ...]] = None   # per layer,
    # "sliding_attention" | "full_attention"; None: every layer full
    sliding_window: Optional[int] = None    # a sliding layer's query i sees
    # key j iff 0 <= i - j < sliding_window
    rope_parameters: Optional[tuple] = None     # rotary by layer type, as
    # frozen by transformer_config from {"<layer type>": {"rope_type":
    # "default" | "yarn", "rope_theta", "factor",
    # "original_max_position_embeddings", "beta_fast", "beta_slow",
    # "attention_factor"}}; None: rope_theta for every layer
    n_experts: int = 0                  # > 0: the FFN of every layer is
    # routed (deepspeed_tpu/moe/routed_ffn.py), ffn_dim an expert's width
    experts_per_token: int = 0
    norm_topk_prob: bool = True         # renormalise the chosen experts'
    # router probabilities to sum to 1
    qk_norm: bool = False               # RMSNorm over each head of q and k
    # (a learned weight of head_dim), before the rotary
    kv_lora_rank: int = 0               # > 0: latent attention
    # (LatentAttention): K and V of every head are projections of one
    # normed latent of this width a token, which is what the cache holds
    # beside the shared rotary key
    qk_nope_head_dim: int = 0           # a head's q / k width without rotary
    qk_rope_head_dim: int = 0           # ... with rotary (k's: one a token)
    v_head_dim: int = 0
    scoring_func: str = "softmax"       # the router's: softmax | sigmoid
    # (sigmoid: the choice is ordered by score + a learned bias, the
    # weights come from the unbiased scores)
    routed_scaling_factor: float = 1.0  # multiplies the routed weights
    topk_norm_eps: float = 1e-20        # the sigmoid router's: joins the
    # chosen scores' sum before they are divided by it (norm_topk_prob)
    n_shared_experts: int = 0           # one gated FFN of this many expert
    # widths beside the routed sum, for every token
    first_k_dense: int = 0              # the first layers' FFN is a plain
    # gated FFN of dense_ffn_dim; the routed FFN starts after them
    dense_ffn_dim: Optional[int] = None
    # Mamba-2 layers (``layer_types`` "mamba", beside "attention" layers
    # without a window): heads of mamba_d_head, a state of mamba_d_state
    # columns a head, B and C shared by the heads (one group), a causal
    # depthwise convolution of mamba_d_conv taps over [x ; B ; C]
    mamba_n_heads: int = 0
    mamba_d_head: int = 0
    mamba_d_state: int = 0
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    # KDA layers (``layer_types`` "kda", beside "attention" layers): heads
    # of kda_d_head key channels and as many value channels, a causal
    # depthwise convolution of kda_d_conv taps over each of q, k and v, the
    # decay and the output gate through a low rank of kda_d_head
    # (ops/kda.py has the state's equations)
    kda_n_heads: int = 0
    kda_d_head: int = 0
    kda_d_conv: int = 4
    # gated short-convolution layers (``layer_types`` "conv", beside
    # "attention" layers): a causal depthwise convolution of conv_taps taps
    # over n_embd channels between two gates, no bias, no activation
    # (ShortConvMixer has the equations); the state is the convolution's tail
    conv_taps: int = 3
    # Lightning layers (``layer_types`` "lightning", beside "attention"
    # layers, in ANY order: the stack is run as a list of runs where it
    # does not repeat): n_head heads of head_dim with their own q, k and v,
    # a float32 state of head_dim x head_dim a head under one constant decay
    # a head (models/lightning_sparse.py, ops/lightning.py); the layer
    # rotates its own q and k whatever pos_emb says of the attention layers
    # learned block-sparse attention in the attention layers' place
    # (ops/attention/sparse_index.py has the equations), as frozen by
    # transformer_config from {"kernel_size", "kernel_stride", "block_size",
    # "init_blocks", "window_size", "topk", "dense_len"}; None: every key
    sparse_attention: Optional[tuple] = None
    attn_output_gate: bool = False      # o (.) sigmoid(W_z x) before o_proj
    # (the sparse attention layers')
    experts_held: Optional[int] = None  # the routed FFN holds experts
    # [0, experts_held) of n_experts (one chip's share of a layer that
    # several divide): the router and the top-k run over all n_experts, the
    # held experts' part of the sum goes on; None: all
    embedding_multiplier: float = 1.0   # scales the token embedding
    embedding_init_std: Optional[float] = None  # a seeded embedding's
    # spread where it is not flax's 1 / sqrt(n_embd): under a tied head a
    # position's own input token scores its row's share of the stream
    attention_multiplier: Optional[float] = None    # the softmax scale
    # where it is not 1 / sqrt(head_dim)
    residual_multiplier: float = 1.0    # x + r * a, x + r * ffn
    logits_scaling: float = 1.0         # the logits are divided by it

    def __post_init__(self):
        if self.layer_types is not None:
            kinds = set(self.layer_types) - {"sliding_attention",
                                             "full_attention",
                                             "power_retention",
                                             "mamba", "kda", "conv",
                                             "lightning", "attention"}
            if kinds or len(self.layer_types) != self.n_layer:
                raise ValueError(
                    f"layer_types names n_layer={self.n_layer} layers as "
                    f"sliding_attention | full_attention | power_retention "
                    f"| mamba | kda | conv | lightning | attention; "
                    f"got {len(self.layer_types)} entries, unknown "
                    f"{sorted(kinds)}")
            if set(STATE_KINDS + ("attention",)) & set(self.layer_types):
                self._check_hybrid()
            if "power_retention" in self.layer_types:
                if set(self.layer_types) != {"power_retention"}:
                    raise ValueError(
                        "power_retention layers beside attention layers: "
                        "the retention state is one leaf over every layer "
                        "of the model (KVCacheSpec.state; the state group "
                        "beside K/V is the mamba layers'): every layer is "
                        "power_retention or none is (ROADMAP.md, Reach)")
                if self.head_dim % 8 or self.n_head // self.kv_heads \
                        >= self.head_dim:
                    raise ValueError(
                        f"power_retention needs head_dim % 8 == 0 and fewer "
                        f"query heads a KV head than head_dim; got head_dim="
                        f"{self.head_dim}, {self.n_head} / {self.kv_heads}")
            if "sliding_attention" in self.layer_types \
                    and not self.sliding_window:
                raise ValueError("sliding_attention layers need "
                                 "sliding_window")
            if self.pos_emb not in ("rotary", "none"):
                raise ValueError(
                    f"layer_types composes with rotary or no positions, "
                    f"not pos_emb={self.pos_emb!r}")
        if self.n_experts:
            if not 0 < self.experts_per_token <= self.n_experts:
                raise ValueError(
                    f"experts_per_token={self.experts_per_token} of "
                    f"n_experts={self.n_experts}")
            if self.experts_held is not None \
                    and not 0 < self.experts_held <= self.n_experts:
                raise ValueError(
                    f"experts_held={self.experts_held} of "
                    f"n_experts={self.n_experts}")
            if self.activation != "swiglu" or self.mlp_bias:
                raise ValueError("the routed FFN is gated silu without "
                                 "bias (activation='swiglu', mlp_bias=False)")
        if self.scoring_func not in ("softmax", "sigmoid"):
            raise ValueError(f"scoring_func {self.scoring_func!r}: know "
                             f"softmax | sigmoid")
        if self.scoring_func == "softmax" and self.routed_scaling_factor != 1:
            raise ValueError(
                "routed_scaling_factor multiplies the sigmoid router's "
                "weights; the softmax router's sum to 1 (norm_topk_prob) "
                "or are probabilities")
        if self.first_k_dense:
            if not self.n_experts or not self.dense_ffn_dim \
                    or not 0 < self.first_k_dense < self.n_layer:
                raise ValueError(
                    f"first_k_dense={self.first_k_dense} names the leading "
                    f"layers of a routed model (n_experts > 0, fewer than "
                    f"n_layer={self.n_layer}) whose FFN is plain, of "
                    f"dense_ffn_dim={self.dense_ffn_dim}")
        if self.latent:
            if not (self.qk_nope_head_dim and self.qk_rope_head_dim
                    and self.v_head_dim) or self.qk_rope_head_dim % 2:
                raise ValueError(
                    "latent attention (kv_lora_rank > 0) needs "
                    "qk_nope_head_dim, an even qk_rope_head_dim and "
                    "v_head_dim")
            if self.pos_emb not in ("rotary", "none") or (
                    self.layer_types is not None and not self.hybrid):
                raise ValueError(
                    "latent attention carries its positions in the shared "
                    "rotary key (pos_emb='rotary') or none at all ('none'), "
                    "and knows no layer kinds (layer_types) but the state "
                    "layers beside it: no window and no retention yet "
                    "(ROADMAP.md, Reach)")
        if self.sparse_attention is not None:
            self.sparse.check()
            if not self.hybrid or self.latent or self.pos_emb != "none":
                raise ValueError(
                    "sparse_attention is the attention layers' of a stack "
                    "of state and attention layers (layer_types), K/V a "
                    "head, without positions (pos_emb='none'): the index "
                    "scores keys that carry none (ROADMAP.md, Reach)")
        if self.attn_output_gate and self.sparse_attention is None:
            raise ValueError("attn_output_gate is the sparse attention "
                             "layers' (sparse_attention)")
        for feature in ("kv_cache_quant", "int8_weights"):
            why = getattr(self, feature) \
                and refusal(cache_kinds(self), feature)
            if why:
                raise ValueError(why)

    def _check_hybrid(self) -> None:
        """State layers of ONE kind (:data:`STATE_KINDS`) stand beside
        ``attention`` layers (full, K/V a head or latent, with ``pos_emb``
        "rotary" or "none"; sparse under ``sparse_attention``). In a
        pattern that repeats, one attention layer a period and the same
        number of state layers before and after it in every period
        (:attr:`hybrid_period`), the FFN may be routed, and the
        ``first_k_dense`` layers with a plain one are state layers at the
        head of the first period. Any other order (adjacent attention
        layers, runs of unequal length) is run as a list of runs
        (:attr:`hybrid_runs`) with a plain FFN in every layer."""
        types = self.layer_types
        n_att = types.count("attention")
        state = set(types) - {"attention"}
        if len(state) != 1 or not state < set(STATE_KINDS) or not n_att:
            raise ValueError(
                f"state layers of ONE kind ({' | '.join(STATE_KINDS)}) "
                f"stand beside attention layers, as a pattern with ONE "
                f"attention layer that repeats over the layers or as any "
                f"list of runs of the two; got {list(types)}")
        if not self.hybrid_repeats and (self.n_experts
                                        or self.first_k_dense):
            raise ValueError(
                f"a routed FFN or leading dense layers count the layers "
                f"period by period: a pattern with ONE attention layer "
                f"that repeats over the layers; got {list(types)} (a "
                f"pattern without a period is run as a list of runs, "
                f"hybrid_runs, with a plain FFN in every layer)")
        if self.mamba and (
                not (self.mamba_n_heads and self.mamba_d_head
                     and self.mamba_d_state) or self.mamba_n_groups != 1
                or self.mamba_d_conv < 2):
            raise ValueError(
                f"mamba layers need mamba_n_heads, mamba_d_head and "
                f"mamba_d_state, one group (B and C shared by the heads) "
                f"and a convolution of two taps or more; got "
                f"{self.mamba_n_heads} x {self.mamba_d_head}, state "
                f"{self.mamba_d_state}, groups {self.mamba_n_groups}, "
                f"taps {self.mamba_d_conv}")
        if self.kda and (not (self.kda_n_heads and self.kda_d_head)
                         or self.kda_d_conv < 2):
            raise ValueError(
                f"kda layers need kda_n_heads and kda_d_head and a "
                f"convolution of two taps or more; got {self.kda_n_heads} x "
                f"{self.kda_d_head}, taps {self.kda_d_conv}")
        if self.conv and self.conv_taps < 2:
            raise ValueError(
                f"conv layers need a convolution of two taps or more (the "
                f"state is its tail); got conv_taps={self.conv_taps}")
        if self.parallel_residual:
            raise ValueError("state layers know the sequential residual")
        if self.first_k_dense and self.first_k_dense > self.hybrid_period[0]:
            raise ValueError(
                f"first_k_dense={self.first_k_dense} leading layers with a "
                f"plain FFN are state layers at the head of the first "
                f"period, which has {self.hybrid_period[0]} before its "
                f"attention layer")

    @property
    def head_dim(self) -> int:
        return self.head_size or self.n_embd // self.n_head

    @property
    def ffn_width(self) -> int:
        return self.ffn_dim or int(self.mlp_ratio * self.n_embd)

    @property
    def kv_heads(self) -> int:
        return self.n_kv_head or self.n_head

    @property
    def latent(self) -> int:
        """Width of the one cached row a token of latent attention
        (``kv_lora_rank + qk_rope_head_dim``), 0 for K/V a head."""
        return self.kv_lora_rank + self.qk_rope_head_dim \
            if self.kv_lora_rank else 0

    def dense_layers(self) -> "TransformerConfig":
        """The configuration of the leading ``first_k_dense`` layers:
        this one with a plain gated FFN of ``dense_ffn_dim``."""
        return dataclasses.replace(
            self, n_experts=0, experts_per_token=0, n_shared_experts=0,
            experts_held=None, first_k_dense=0, ffn_dim=self.dense_ffn_dim)

    @property
    def retention(self) -> bool:
        """Every layer is ``power_retention``: a state, no K/V."""
        return self.layer_types is not None \
            and "power_retention" in self.layer_types

    @property
    def mamba(self) -> bool:
        """``mamba`` layers beside ``attention`` layers: a state group
        over the former, K/V over the latter."""
        return self.layer_types is not None and "mamba" in self.layer_types

    @property
    def kda(self) -> bool:
        """``kda`` layers beside ``attention`` layers: a state group over
        the former, K/V or a latent row over the latter."""
        return self.layer_types is not None and "kda" in self.layer_types

    @property
    def conv(self) -> bool:
        """``conv`` layers beside ``attention`` layers: a state group of
        the convolution's tail alone over the former, K/V over the latter."""
        return self.layer_types is not None and "conv" in self.layer_types

    @property
    def lightning(self) -> bool:
        """``lightning`` layers beside ``attention`` layers: a state group
        of the linear attention's state over the former, K/V (and the
        index's group means, where the attention is sparse) over the
        latter."""
        return self.layer_types is not None \
            and "lightning" in self.layer_types

    @property
    def sparse(self):
        """``sparse_attention`` as ``ops.attention.sparse_index.SparseSizes``,
        or None."""
        if self.sparse_attention is None:
            return None
        from ..ops.attention.sparse_index import SparseSizes

        return SparseSizes(**dict(self.sparse_attention))

    @property
    def hybrid(self) -> Optional[str]:
        """The kind of the state layers that stand beside ``attention``
        layers, one of :data:`STATE_KINDS`; None for a model of one stack."""
        return next((kind for kind in STATE_KINDS
                     if self.layer_types is not None
                     and kind in self.layer_types), None)

    @property
    def kda_width(self) -> int:
        """``kda_n_heads * kda_d_head``: the width of q, k and v each."""
        return self.kda_n_heads * self.kda_d_head

    @property
    def hybrid_repeats(self) -> bool:
        """Whether the state and attention layers come as a pattern with
        ONE attention layer that repeats over the layers
        (:attr:`hybrid_period`); else they are :attr:`hybrid_runs`."""
        n_att = self.layer_types.count("attention")
        return not self.n_layer % n_att and self.layer_types \
            == self.layer_types[:self.n_layer // n_att] * n_att

    @property
    def hybrid_runs(self) -> tuple:
        """The stack as a list of runs, ``((kind, first, count), ...)``:
        ``count`` layers of one kind side by side, the ``first`` of them
        counted among the layers of that kind (where its slice of the
        kind's stacked leaves and its cache lie)."""
        runs, seen = [], {}
        for kind in self.layer_types:
            if runs and runs[-1][0] == kind:
                runs[-1][2] += 1
            else:
                runs.append([kind, seen.get(kind, 0), 1])
            seen[kind] = seen.get(kind, 0) + 1
        return tuple(tuple(run) for run in runs)

    @property
    def hybrid_period(self) -> tuple:
        """``(before, after, periods)``: the state layers before and after
        a period's one attention layer, and how many periods there are."""
        periods = self.layer_types.count("attention")
        period = self.layer_types[:self.n_layer // periods]
        before = period.index("attention")
        return before, len(period) - before - 1, periods

    @property
    def mamba_channels(self) -> int:
        """The convolution's channels: ``[x ; B ; C]``."""
        return self.mamba_n_heads * self.mamba_d_head \
            + 2 * self.mamba_n_groups * self.mamba_d_state


# the kinds of state layer that stand beside ``attention`` layers
STATE_KINDS = ("mamba", "kda", "conv", "lightning")

FAMILY_PRESETS = {
    "gpt2": dict(pos_emb="learned", norm="layernorm", activation="gelu"),
    "gpt-neo": dict(pos_emb="learned", norm="layernorm", activation="gelu"),
    "gptj": dict(pos_emb="rotary", norm="layernorm", activation="gelu",
                 parallel_residual=True, tie_word_embeddings=False),
    "gpt-neox": dict(pos_emb="rotary", rotary_pct=0.25, norm="layernorm",
                     activation="gelu", parallel_residual=True,
                     tie_word_embeddings=False),
    "llama": dict(pos_emb="rotary", norm="rmsnorm", activation="swiglu",
                  qkv_bias=False, mlp_bias=False, tie_word_embeddings=False,
                  layer_norm_epsilon=1e-6),
    "opt": dict(pos_emb="learned", norm="layernorm", activation="relu"),
    "bloom": dict(pos_emb="alibi", norm="layernorm", activation="gelu",
                  embed_layernorm=True),
    "megatron-gpt": dict(pos_emb="learned", norm="layernorm", activation="gelu"),
    # Mellum 2 (JetBrains): llama's block with head_dim, FFN width, layer
    # kinds, rotary by kind and the routed FFN given by the caller
    "mellum": dict(pos_emb="rotary", norm="rmsnorm", activation="swiglu",
                   qkv_bias=False, mlp_bias=False, tie_word_embeddings=False,
                   layer_norm_epsilon=1e-6),
    # Brumby (Manifest AI): Qwen3's block (llama's with a norm on q and k)
    # whose every layer is gated power retention (``layer_kind``: one kind
    # for every layer, which transformer_config spells out as layer_types
    # once it knows n_layer)
    "brumby": dict(pos_emb="rotary", norm="rmsnorm", activation="swiglu",
                   qkv_bias=False, mlp_bias=False, tie_word_embeddings=False,
                   layer_norm_epsilon=1e-6, qk_norm=True,
                   layer_kind="power_retention"),
    # Moonlight (Moonshot AI; model_type deepseek_v3): latent attention,
    # a sigmoid router ordered by score + bias, shared experts, leading
    # dense layers (``mlp_layer_types``, which transformer_config reads
    # into first_k_dense). Widths are the caller's.
    "moonlight": dict(pos_emb="rotary", norm="rmsnorm", activation="swiglu",
                      qkv_bias=False, mlp_bias=False,
                      tie_word_embeddings=False, layer_norm_epsilon=1e-5,
                      scoring_func="sigmoid"),
    # Granite 4.0 hybrid (IBM; model_type granitemoehybrid): Mamba-2 layers
    # beside position-free GQA layers (``layer_types`` as published), a
    # SwiGLU in every layer, tied head, four scalars. Widths, the pattern
    # and the scalars are the caller's.
    "granite-hybrid": dict(pos_emb="none", norm="rmsnorm",
                           activation="swiglu", qkv_bias=False,
                           mlp_bias=False, tie_word_embeddings=True,
                           layer_norm_epsilon=1e-5),
    # Kimi Linear (Moonshot AI; model_type kimi_linear): KDA layers beside
    # latent attention layers that rotate nothing (``layer_types`` "kda" |
    # "attention"), Moonlight's router and shared expert behind one leading
    # dense layer, an untied head. Widths and the pattern are the caller's.
    "kimi_linear": dict(pos_emb="none", norm="rmsnorm", activation="swiglu",
                        qkv_bias=False, mlp_bias=False,
                        tie_word_embeddings=False, layer_norm_epsilon=1e-5,
                        scoring_func="sigmoid"),
    # LFM2 MoE (Liquid AI; model_type lfm2_moe): gated short-convolution
    # layers beside rotary GQA layers with a norm on each head of q and k
    # (``layer_types`` as published, "conv" | "full_attention"), a sigmoid
    # router ordered by score + bias over ``sum + 1e-6`` with no shared
    # expert, behind leading dense layers (``first_k_dense``); the head is
    # tied. Widths and the pattern are the caller's.
    "lfm2_moe": dict(pos_emb="rotary", norm="rmsnorm", activation="swiglu",
                     qkv_bias=False, mlp_bias=False,
                     tie_word_embeddings=True, layer_norm_epsilon=1e-5,
                     qk_norm=True, scoring_func="sigmoid",
                     topk_norm_eps=1e-6),
    # MiniCPM-SALA (OpenBMB; model_type minicpm_sala): Lightning
    # linear-attention layers beside learned block-sparse GQA layers in the
    # published, irregular order (``mixer_types`` "lightning-attn" |
    # "minicpm4", or ``layer_types`` "lightning" | "sparse_attention"), a
    # norm on each head of q and k in both, an output gate on both (the
    # sparse layers' where ``sparse_attention`` is given), no
    # positions in the attention layers and a rotary in the Lightning
    # layers, MiniCPM's three scalars (``embedding_multiplier``,
    # ``residual_multiplier``, ``logits_scaling``), an untied head. Widths,
    # the pattern, the scalars and ``sparse_attention`` are the caller's.
    "minicpm_sala": dict(pos_emb="none", norm="rmsnorm",
                         activation="swiglu", qkv_bias=False,
                         mlp_bias=False, tie_word_embeddings=False,
                         layer_norm_epsilon=1e-6, qk_norm=True),
}


def _freeze(value):
    """JSON-shaped ``value`` as something a frozen dataclass can hash."""
    if isinstance(value, dict):
        return tuple(sorted((k, _freeze(v)) for k, v in value.items()))
    if isinstance(value, (list, tuple)):
        return tuple(_freeze(v) for v in value)
    return value


def transformer_config(family: str, **overrides) -> TransformerConfig:
    """Build a config from a family preset (≅ picking an injection policy,
    reference module_inject/replace_policy.py)."""
    if family not in FAMILY_PRESETS:
        raise ValueError(f"unknown family {family!r}; know {sorted(FAMILY_PRESETS)}")
    overrides = {k: _freeze(v) if k in ("layer_types", "rope_parameters",
                                        "sparse_attention")
                 else v for k, v in overrides.items()}
    cfg = {**FAMILY_PRESETS[family], **overrides}
    mixers = cfg.pop("mixer_types", None)
    if mixers is not None:
        # published a layer as "lightning-attn" | "minicpm4"
        cfg.setdefault("layer_types", tuple(mixers))
    if {"lightning", "lightning-attn"} & set(cfg.get("layer_types") or ()):
        # (beside Lightning layers the sparse layers are the stack's
        # ``attention``: what makes them sparse is ``sparse_attention``)
        names = {"lightning-attn": "lightning", "minicpm4": "attention",
                 "sparse_attention": "attention"}
        cfg["layer_types"] = tuple(names.get(kind, kind)
                                   for kind in cfg["layer_types"])
    if "conv" in (cfg.get("layer_types") or ()):
        # (published beside "conv" as "full_attention": the one attention
        # kind of a stack of state and attention layers)
        cfg["layer_types"] = tuple(
            "attention" if kind == "full_attention" else kind
            for kind in cfg["layer_types"])
    kind = cfg.pop("layer_kind", None)
    if kind is not None:
        cfg.setdefault("layer_types", (kind,) * cfg.get(
            "n_layer", TransformerConfig.n_layer))
    mlp_kinds = cfg.pop("mlp_layer_types", None)
    if mlp_kinds is not None:
        # published per layer as "dense" | "sparse"; the program runs the
        # first n_layer entries: dense layers first, then sparse ones
        kinds = list(mlp_kinds)[:cfg.get("n_layer", TransformerConfig.n_layer)]
        k = kinds.index("sparse") if "sparse" in kinds else len(kinds)
        if set(kinds[:k]) - {"dense"} or set(kinds[k:]) - {"sparse"}:
            raise ValueError(
                f"mlp_layer_types names leading dense layers, then sparse "
                f"ones; got {kinds}")
        cfg.setdefault("first_k_dense", k)
    return TransformerConfig(**cfg)


def transformer_logical_axes():
    """LOGICAL axis annotations for this module's parameter paths (≅ t5x
    ``param_with_axes`` metadata, expressed as path patterns so the flax
    modules stay annotation-free). Works for every family preset (paths
    are family-invariant). Scanned blocks carry a leading ``layers`` dim;
    ``heads`` is the fused heads*head_dim projection width and ``ffn``
    the MLP hidden width."""
    return [
        (r"embed_tokens/embedding", ("vocab", "embed")),
        (r"embed_pos/embedding", ("positions", "embed")),
        (r"attn/(q_proj|k_proj|v_proj)/kernel", ("layers", "embed", "heads")),
        (r"attn/o_proj/kernel", ("layers", "heads", "embed")),
        (r"attn/(q_proj|k_proj|v_proj)/bias", ("layers", "heads")),
        (r"mlp/(up_proj|gate_proj)/kernel", ("layers", "embed", "ffn")),
        (r"mlp/(up_proj|gate_proj)/bias", ("layers", "ffn")),
        (r"mlp/down_proj/kernel", ("layers", "ffn", "embed")),
        (r"lm_head/kernel", ("embed", "vocab")),
    ]


def transformer_sharding_rules(rules=None):
    """Megatron-style TP rules for this module's parameter paths — the
    AutoTP analog (reference module_inject/auto_tp.py:13): column-parallel
    up-projections, row-parallel down-projections, vocab-parallel
    embedding. Derived by resolving :func:`transformer_logical_axes`
    through the ``parallel/`` axis-rules table (``rules`` overrides the
    default) so one table swap re-partitions the module; the default
    table reproduces the historical hard-coded placement exactly
    (pinned by tests/unit/parallel/test_axis_rules.py)."""
    from ..parallel.axis_rules import default_axis_rules

    rules = rules if rules is not None else default_axis_rules()
    return [(pat, rules.spec_entries(axes))
            for pat, axes in transformer_logical_axes()]


def _dense(cfg: TransformerConfig, features: int, *, use_bias: bool,
           name: str, dtype=None):
    """nn.Dense, or its int8-at-rest serving twin when ``cfg.int8_weights``
    — params become int8 kernel + f32 per-channel scale consumed by the
    Pallas dequant-GEMM (ops/quantization); the inference engine's
    quantization tier builds that tree from a bf16 checkpoint."""
    if cfg.int8_weights:
        from ..ops.quantization import QuantDense

        return QuantDense(features, use_bias=use_bias, dtype=dtype or cfg.dtype,
                          kernel_mode=cfg.int8_kernel, name=name)
    return nn.Dense(features, use_bias=use_bias, dtype=dtype or cfg.dtype,
                    name=name)


def _norm(cfg: TransformerConfig, name: str):
    if cfg.norm == "rmsnorm":
        return nn.RMSNorm(epsilon=cfg.layer_norm_epsilon, dtype=cfg.dtype, name=name)
    return nn.LayerNorm(epsilon=cfg.layer_norm_epsilon, dtype=cfg.dtype, name=name)


def _rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def apply_rotary(x, positions, *, rotary_dim: int, theta: float):
    """NeoX-style rotary embedding on the first ``rotary_dim`` channels.
    x: (B, T, H, D); positions: (B, T) absolute token positions."""
    rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                                / rotary_dim))
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # (B,T,rd/2)
    angles = jnp.concatenate([angles, angles], axis=-1)[:, :, None, :]  # (B,T,1,rd)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    rot32 = rot.astype(jnp.float32)
    out = rot32 * cos + _rotate_half(rot32) * sin
    return jnp.concatenate([out.astype(x.dtype), rest], axis=-1)


def rope_inv_freq(rotary_dim: int, rope: dict):
    """``(inv_freq (rotary_dim // 2,), factor)`` of one ``rope_parameters``
    section: ``default`` is ``theta ** (-2i / d)`` with factor 1; ``yarn``
    is the static YaRN of the ``transformers`` library (``truncate`` at its
    default): frequencies under ``low`` keep ``f_i``, over ``high`` take
    ``f_i / s``, a linear ramp between, and cos and sin are multiplied by
    ``attention_factor`` (``0.1 ln s + 1`` where the section gives none)."""
    d = rotary_dim
    theta = float(rope.get("rope_theta", 10000.0))
    f = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    kind = rope.get("rope_type", "default")
    if kind == "default":
        return f.astype(np.float32), 1.0
    if kind != "yarn":
        raise ValueError(f"rope_type {kind!r}: know default | yarn")
    s = float(rope["factor"])
    L0 = float(rope["original_max_position_embeddings"])

    def corr(beta):
        return d * math.log(L0 / (2 * math.pi * beta)) / (2 * math.log(theta))

    low = max(math.floor(corr(float(rope.get("beta_fast", 32.0)))), 0)
    high = min(math.ceil(corr(float(rope.get("beta_slow", 1.0)))), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    factor = rope.get("attention_factor")
    if factor is None:
        factor = 0.1 * math.log(s) + 1.0
    return (f / s * ramp + f * (1 - ramp)).astype(np.float32), float(factor)


def layer_rope_tables(cfg: "TransformerConfig"):
    """Per layer of a ``layer_types`` configuration: ``(inv_freq (L, rd/2),
    factor (L,), window (L,) bool)`` as constants the scanned layer indexes
    by the scan's counter."""
    rd = int(cfg.rotary_pct * cfg.head_dim) // 2 * 2
    sections = {k: dict(v) for k, v in (cfg.rope_parameters or ())}
    rows, factors = [], []
    for kind in cfg.layer_types:
        inv, factor = rope_inv_freq(
            rd, sections.get(kind, {"rope_theta": cfg.rope_theta}))
        rows.append(inv)
        factors.append(factor)
    return (np.stack(rows), np.asarray(factors, np.float32),
            np.asarray([k == "sliding_attention" for k in cfg.layer_types]))


def apply_rotary_table(x, positions, inv_freq, factor, rotary_dim: int):
    """:func:`apply_rotary` with the frequencies given (a layer's row of
    :func:`layer_rope_tables`) and cos, sin scaled by ``factor``."""
    rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    angles = jnp.concatenate([angles, angles], axis=-1)[:, :, None, :]
    cos, sin = jnp.cos(angles) * factor, jnp.sin(angles) * factor
    rot32 = rot.astype(jnp.float32)
    out = rot32 * cos + _rotate_half(rot32) * sin
    return jnp.concatenate([out.astype(x.dtype), rest], axis=-1)


def alibi_slopes(n_head: int) -> jnp.ndarray:
    """Per-head ALiBi slopes (Press et al.), matching the reference's alibi
    computation used for bloom (csrc attention alibi path)."""
    def pow2_slopes(n):
        start = 2.0 ** (-(2.0 ** -(math.log2(n) - 3)))
        return [start * (start ** i) for i in range(n)]

    if math.log2(n_head).is_integer():
        return jnp.asarray(pow2_slopes(n_head), jnp.float32)
    closest = 2 ** math.floor(math.log2(n_head))
    base = pow2_slopes(closest)
    extra = pow2_slopes(2 * closest)[0::2][: n_head - closest]
    return jnp.asarray(base + extra, jnp.float32)


def _settled(*projected):
    """The results of an attention block's input projections, as values
    the compiler may not look through (``optimization_barrier``).

    What it prevents: the cache write and the read kernels take their
    operands head_dim-major, and XLA's layout assignment carried that wish
    back through ``reshape``, the rotary and the projection's dot onto the
    WEIGHT. It then computed ``W^T x^T``, and to have ``W^T`` it sliced the
    layer's matrix out of the stacked leaf into a buffer of its own and
    copied that into the other layout, a layer a projection a step (8 MB
    twice where the result is 256 KB: ``constant_dynamic-slice_fusion`` and
    ``copy`` over ``bf16[1, C, C']`` in a decode or chunk program, a
    quarter of the chat cell's busy device time, ledger PR 39). Behind the
    barrier the dot is an ordinary one with the slice of the stacked leaf
    fused into it, as ``o_proj``'s and the MLP's are, and the layout the
    kernels want is made on the small result. The values are what they
    were.

    How to see it come back: ``tests/unit/accelerator/test_chip_path.py``
    ``test_attention_projections_read_the_stacked_leaf`` compiles the step
    programs for a described v5e and looks for those two instructions."""
    return jax.lax.optimization_barrier(projected)


def _project_qkv(cfg: TransformerConfig, x):
    """``q_proj``, ``k_proj``, ``v_proj`` of ``x`` (B, T, C), settled, as
    (B, T, heads, head_dim). Called inside an attention module's
    ``__call__``: the three ``Dense`` are that module's."""
    B, T, _ = x.shape
    H, KV, D = cfg.n_head, cfg.kv_heads, cfg.head_dim
    q, k, v = _settled(*(
        _dense(cfg, heads * D, use_bias=cfg.qkv_bias, name=name)(x)
        for heads, name in ((H, "q_proj"), (KV, "k_proj"), (KV, "v_proj"))))
    return (q.reshape(B, T, H, D), k.reshape(B, T, KV, D),
            v.reshape(B, T, KV, D))


def _norm_qk(cfg: TransformerConfig, q, k):
    """``qk_norm``: RMSNorm over each head of ``q`` and of ``k`` (one
    learned weight of ``head_dim`` each), before the rotary. Called inside
    an attention module's ``__call__``: the two norms are that module's."""
    return tuple(nn.RMSNorm(epsilon=cfg.layer_norm_epsilon, dtype=cfg.dtype,
                            name=name)(x)
                 for name, x in (("q_norm", q), ("k_norm", k)))


def _store_columns(buf, new, start):
    """Write the new positions-minor columns at each row's offset: one
    DUS for scalar start; per-slot (B,) starts vmap the DUS over the batch
    (lowers to a scatter — each slot writes at its own cache offset)."""
    if jnp.ndim(start) == 1:
        return jax.vmap(
            lambda c, n, s: jax.lax.dynamic_update_slice(
                c, n, (0,) * (c.ndim - 1) + (s,)))(buf, new, start)
    return jax.lax.dynamic_update_slice(
        buf, new, (0,) * (buf.ndim - 1) + (start,))


@functools.lru_cache(maxsize=None)
def _traced_once_on(fn, mesh, interpret: bool, static: tuple):
    return jax.jit(fn, static_argnames=static)


def _traced_once(fn, *static, chunk: bool = True):
    """``fn``, one of the cache kernels' entry points, as a function JAX
    traces ONCE for each set of operand shapes: an inner ``jit``, inlined
    where the program is compiled. For a prefill CHUNK's calls alone
    (``chunk``: several query rows of ONE slot): a layer's body is traced
    twice by the scan that holds it, and ``paged_chunk`` and the one
    program of a chunk beside the decode rows trace the same kernels; the
    kernel bodies were two thirds of a step program's trace time
    (``paged_chunk`` 2.6 s, ``kernel_decode`` 2.3 s, the two as one
    program 5.2 s on the chip's host with every compile served from the
    cache: my chip run, PR 48; all of it ``setup_s``). Keyed by what the
    kernels' wrappers read while they are traced: the mesh
    (``ops.backend.shard_kernel``) and the interpret switch.

    Every other call, a decode or a verify step's over all the slots, is
    ``fn`` itself, so those programs are the parent's. Measured, not
    supposed: behind an inlined call the decode read's work list
    (``s32[slots x pages_per_slot]``, the same for every layer) is
    computed again in every layer instead of once a program, 0.4-0.6 ms a
    step in docs and ide (my chip runs, PR 48, call 6: ``serve_tok_s``
    5,199 against 5,423, 6,851 against 7,049); and the plain decode
    program's compiled text is pinned instruction for instruction
    (``tests/unit/accelerator/test_chip_path.py``, ``_KERNEL_DECODE_TEXT``),
    which an inlined call renumbers. (One path for both shapes needs the
    work list handed to the kernels, a change under ``ops/``: PERF.md
    section 7.)"""
    if not chunk:
        return fn
    from ..ops import backend
    from ..parallel import mesh as mesh_mod

    return _traced_once_on(
        fn, mesh_mod.get_mesh() if mesh_mod.has_mesh() else None,
        backend.pallas_interpret(), static)


def _chunk_shaped(rows) -> bool:
    """Whether ``rows`` (slots, T, ...) are a prefill chunk's: several of
    one slot (:func:`_traced_once`)."""
    return rows.shape[0] == 1 and rows.shape[1] > 1


def _chunk_positions(kv_cache, T: int):
    """Positions (1, T) of the rows of a step that carries a prefill chunk
    beside its decode rows (``"chunk"`` in the cache a layer is handed,
    :meth:`TransformerLM.chunk_beside_decode`): the chunk's ``C`` tokens from
    its start, then each decode row at its own."""
    start = kv_cache["start"]
    at = kv_cache["chunk"]["start"][:, None] \
        + jnp.arange(T - start.shape[0])[None, :]
    return jnp.concatenate([at, start[None, :]], axis=1)


def _by_row_group(kv_cache, step, *rows):
    """What a mixer does against its cache, ``step(kv_cache, *rows) -> (y,
    leaves)`` over ``rows`` (B, T, ...), for every group of rows of the
    call. A call has one group, itself, unless a prefill chunk rides beside
    the decode rows: then ``rows`` are (1, C + B, ...), the chunk's C rows
    of ONE slot ahead of B slots' one row each, everything that read a
    weight has run over all of them at once, and what reads the cache runs
    a group at a time with the kernels it has: the chunk's rows as (1, C)
    through the addressing under ``kv_cache["chunk"]`` (its start, its
    slot's table row(s), its state row), then the decode rows as (B, 1)
    through the call's own, on the leaves as the chunk left them. That
    order is the two programs' this replaces: the decode row of the slot in
    mid-prefill reads what the chunk wrote and writes its dead column
    behind it."""
    chunk = kv_cache.get("chunk")
    if chunk is None:
        return step(kv_cache, *rows)
    cache = {key: val for key, val in kv_cache.items() if key != "chunk"}
    C = rows[0].shape[1] - cache["start"].shape[0]
    y_chunk, leaves = step(dict(cache, **chunk), *(r[:, :C] for r in rows))
    y, leaves = step(dict(cache, **leaves),
                     *(r[0, C:][:, None] for r in rows))
    return jnp.concatenate([y_chunk, y[:, 0][None]], axis=1), leaves


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
            o_proj = _dense(cfg, C, use_bias=cfg.qkv_bias, name="o_proj")
            return o_proj(y), leaves

        kv_scales = None  # set on the quantized-cache einsum fallback
        # "fresh" attention = causal over the just-computed k/v. True for
        # the training forward AND for prefill (start == 0 contract): the
        # prompt's causal window IS the fresh k/v, so prefill must NOT
        # attend over the allocated cache — the (B, H, T, S) score tensor
        # that implies OOM-crashed the worker at T=4096 / S=8192.
        fresh = (not decode) or (decode == "prefill" and T > 1)
        new_cache = None
        o_proj = _dense(cfg, C, use_bias=cfg.qkv_bias, name="o_proj")
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


def _half_life_logit(key, shape, dtype=jnp.float32):
    """The gate's bias: ``logit(g)`` for ``g = 2 ** (-1 / half-life)`` with
    half-lives drawn log-uniformly from 16 to 4,096 tokens, one a KV head
    a layer (the scan splits the key by layer). A zero bias is ``g`` 0.5:
    the state would forget in two tokens, and nothing that compares outputs
    could see a wrong carried state."""
    half_life = 16.0 * 256.0 ** jax.random.uniform(key, shape)
    g = 2.0 ** (-1.0 / half_life)
    return (jnp.log(g) - jnp.log1p(-g)).astype(dtype)


class PowerRetention(nn.Module):
    """Gated power retention of degree 2 in the attention's place
    (``ops/attention/power_retention.py`` has the equations): ``q``, ``k``
    with their norm and the rotary, ``v``, one gate a KV head
    (``log g = log sigmoid(W_g x + b_g)``, float32), output projection.

    Modes as :class:`CachedAttention`'s. Without a cache: the attention
    form in ``jax.numpy``. With one, ``kv_cache`` holds the stacked state
    leaf ``s`` whole with ``layer``, ``start`` and, from a caller
    that runs only some rows or maps its batch to other rows, ``rows``
    (B,) (the cache row of each batch entry, out of range: the entry does
    not run and its row's state is not touched) and ``valid`` (B,) (tokens
    from there on are padding and leave the state alone). One token takes
    ``retention_decode``, more take ``retention_chunk``; an entry whose
    first position is 0 reads no state."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, x, *, decode: Union[bool, str] = False,
                 deterministic: bool = True, kv_cache=None, layer=None):
        from ..ops.attention import power_retention as pr

        cfg = self.config
        B, T, C = x.shape
        H, KV, D = cfg.n_head, cfg.kv_heads, cfg.head_dim
        q, k, v = _project_qkv(cfg, x)
        if cfg.qk_norm:
            q, k = _norm_qk(cfg, q, k)
        log_g = jax.nn.log_sigmoid(nn.Dense(
            KV, dtype=jnp.float32, bias_init=_half_life_logit,
            name="g_proj")(x))                                  # (B, T, KV)
        start = kv_cache["start"] if decode else jnp.zeros((), jnp.int32)
        if cfg.pos_emb == "rotary":
            positions = (start[:, None] if jnp.ndim(start) == 1 else start) \
                + jnp.arange(T)[None, :]
            rd = int(cfg.rotary_pct * D) // 2 * 2
            q = apply_rotary(q, positions, rotary_dim=rd,
                             theta=cfg.rope_theta)
            k = apply_rotary(k, positions, rotary_dim=rd,
                             theta=cfg.rope_theta)
        o_proj = _dense(cfg, C, use_bias=cfg.qkv_bias, name="o_proj")
        if not decode:
            y = pr.retention_attention(q, k, v, log_g)
            return o_proj(y.astype(cfg.dtype).reshape(B, T, H * D)), None
        rows = kv_cache.get("rows")
        if rows is None:
            rows = jnp.arange(B, dtype=jnp.int32)
        fresh = jnp.broadcast_to(start == 0, (B,))
        state = (kv_cache["s"], kv_cache["layer"], rows, fresh)
        if T == 1:
            y, s = pr.retention_decode(q[:, 0], k[:, 0], v[:, 0],
                                       log_g[:, 0], *state)
        else:
            y, s = pr.retention_prefill(q, k, v, log_g, *state,
                                        length=kv_cache.get("valid"))
        y = y.astype(cfg.dtype).reshape(B, T, H * D)
        return o_proj(y), {"s": s}


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


def _uniform_log(lo: float, hi: float, inverse=None):
    """An initializer: values drawn log-uniformly from ``lo`` to ``hi``,
    then through ``inverse`` (what the module applies to the parameter)."""
    def init(key, shape, dtype=jnp.float32):
        v = lo * (hi / lo) ** jax.random.uniform(key, shape)
        return (v if inverse is None else inverse(v)).astype(dtype)
    return init


def _state_rows(cache, B: int, T: int):
    """What a state layer's mixer reads off the cache it is handed for a
    group of rows (B, T): ``(layer, rows, fresh, valid)``: the layer's
    index among those that keep a state, the cache row of each entry (its
    own place where the caller names none), whether an entry stands at
    position 0 (it reads neither its state nor its tail), and how many of
    its T tokens are real."""
    rows = cache.get("rows")
    if rows is None:
        rows = jnp.arange(B, dtype=jnp.int32)
    valid = jnp.full((B,), T, jnp.int32)
    if cache.get("valid") is not None:
        valid = jnp.minimum(cache["valid"], T)
    return (cache["layer"], rows,
            jnp.broadcast_to(cache["start"] == 0, (B,)), valid)


def _conv_after_tail(cache, x, w, b, silu: bool = True):
    """A state layer's causal convolution of one group of rows ``x`` (B, T,
    C) after the tail its cache carries (``ops/state_space.causal_conv``;
    ``silu`` False: without its activation): ``(conv(x), where, conv
    leaf)``. ``cache`` None: whole sequences from nothing, no ``where`` and
    no leaf. Else ``where`` is :func:`_state_rows`'s, the tail is read from
    the leaf ``conv`` and the tail at the last REAL token is written back to
    it."""
    from ..ops import state_space

    conv = state_space.causal_conv if silu else functools.partial(
        state_space.causal_conv, silu=False)
    B, T = x.shape[:2]
    tail = jnp.zeros((B, w.shape[0] - 1, x.shape[-1]), x.dtype)
    if cache is None:
        return conv(x, tail, w, b, jnp.full((B,), T, jnp.int32))[0], \
            None, None
    where = layer, rows, fresh, valid = _state_rows(cache, B, T)
    tail = _read_rows(cache["conv"], layer, rows, fresh, tail.shape[1:])
    x, tail = conv(x, tail, w, b, valid)
    return x, where, _write_rows(cache["conv"], layer, rows,
                                 tail.reshape(B, -1))


def _read_rows(leaf, layer, rows, fresh, shape):
    """Rows ``rows`` (B,) of layer ``layer`` of ``leaf`` (L, R, W), each as
    ``shape``: zeros for an entry that is ``fresh`` or out of ``[0, R)``
    (one that does not run)."""
    R = leaf.shape[1]
    at = (layer, jnp.where((rows >= 0) & (rows < R), rows, R))
    return jnp.where(fresh[:, None, None], 0, leaf.at[at].get(
        mode="fill", fill_value=0).reshape((rows.shape[0],) + shape))


def _write_rows(leaf, layer, rows, values):
    """``leaf`` (L, R, W) with ``values`` (B, W) written to rows ``rows``
    (B,) of layer ``layer``; an entry out of ``[0, R)`` writes nothing.
    A few entries are a scatter of their rows. From ``_SLAB_FROM`` on they
    go through the layer's whole slab, each row taking the entry that names
    it (a select and ONE update of (R, W)): XLA expands a scatter of B rows
    into a loop of B single-row updates, 5.6 ms a step of 128 rows over 9
    KDA layers where the slabs' bytes are 0.2 ms (my traced run, PR 50,
    call 3: ``dynamic-update-slice`` over ``bf16[9,128,36864]`` with its
    bounds check, 17 % of the busy device; the mamba layers' tail of 64
    rows x 36 layers went the same way). The slab costs the same at every
    B and more than a scatter of two: a layer of (64, 13056) 11.9 us
    against 4.9 at B = 2, 9.3 at 8, 17.3 at 16, 55.7 at 64; a layer of
    (128, 36864) 63 us against 18 at B = 2, 66 at 8, 130 at 16, 973 at
    128 (my chip run, PR 50, call 94: each form alone, every layer once)."""
    B, R = rows.shape[0], leaf.shape[1]
    values = values.astype(leaf.dtype)
    if B < _SLAB_FROM:
        at = (layer, jnp.where((rows >= 0) & (rows < R), rows, R))
        return leaf.at[at].set(values, mode="drop")
    named = rows[None, :] == jnp.arange(R, dtype=rows.dtype)[:, None]  # R, B
    slab = jax.lax.dynamic_index_in_dim(leaf, layer, 0, keepdims=False)
    slab = jnp.where(jnp.any(named, axis=1)[:, None],
                     values[jnp.argmax(named, axis=1)], slab)
    return jax.lax.dynamic_update_slice_in_dim(leaf, slab[None], layer, 0)


# entries from which _write_rows takes the slab (where the two forms met
# on both cells' leaves, see there)
_SLAB_FROM = 8


class Mamba2Mixer(nn.Module):
    """The Mamba-2 mixer in the attention's place
    (``ops/state_space.py`` has the state's equations). With ``u`` the
    normed input::

        [z ; xBC ; dt] = W_in u         xBC <- silu(conv(xBC))
        [x ; B ; C] = xBC               dt <- softplus(dt + dt_bias)
        H_t = exp(dt_t A) H_{t-1} + dt_t x_t (x) B_t      A = -exp(A_log)
        y_t = H_t C_t + D x_t
        out = W_out (w (.) g / rms(g))          g = y (.) silu(z)

    ``conv`` is causal and depthwise over ``mamba_d_conv`` taps with a
    bias; the gate comes BEFORE the norm. Three forms of that one
    mathematics. Without a cache: whole sequences from an empty state
    (``ssm_sequence``). With one, ``kv_cache`` holds the stacked leaves
    whole, ``s`` (the state, float32) and ``conv`` (the last
    ``mamba_d_conv - 1`` inputs of the convolution, time-major on the minor
    axis: ``(L, rows, (taps - 1) * channels)`` in the model's dtype), with
    ``layer``, ``start`` and, as :class:`PowerRetention`, ``rows`` and
    ``valid``: one token takes ``ssm_decode``, more take ``ssm_chunk``
    block by block. A token at or past ``valid`` is padding: it advances
    neither the state (its ``dt`` is 0) nor the tail (taken at the last
    real token). An entry whose first position is 0 reads neither. The
    convolution and the tail's shift are XLA's, under the scope
    ``ssm_conv``."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, u, *, decode: Union[bool, str] = False,
                 deterministic: bool = True, kv_cache=None, layer=None):
        from ..ops import state_space as ss

        cfg = self.config
        B, T, C = u.shape
        H, P, N = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
        inner, ch, K = H * P, cfg.mamba_channels, cfg.mamba_d_conv
        f32 = jnp.float32
        # W_in as two leaves, [z ; xBC] and dt: the published matrix is
        # 2 * inner + 2 * N + H wide (8,512 at the published widths, no
        # multiple of 128 lanes), and the chip's client stores such a leaf
        # with the OTHER dimension minor, which every program then copies
        # whole before its layer loop (1.25 GB a step there: compiled for
        # a described v5e, PR 47). z and xBC are whole lane tiles; dt's 64
        # columns are a leaf of their own, small enough to copy
        zx = _dense(cfg, inner + ch, use_bias=False, name="in_proj")(u)
        z, xbc = zx[..., :inner], zx[..., inner:]
        dt = _dense(cfg, H, use_bias=False, name="dt_proj")(u)
        bound = 1.0 / math.sqrt(K)      # (torch's Conv1d default)
        taps = nn.initializers.uniform(2 * bound)
        conv_w = self.param("conv_w", lambda *a: taps(*a) - bound, (K, ch))
        conv_b = self.param("conv_b", lambda *a: taps(*a) - bound, (ch,))
        a = -jnp.exp(self.param("A_log", _uniform_log(1.0, 16.0, jnp.log),
                                (H,)).astype(f32))
        skip = self.param("D", nn.initializers.ones, (H,)).astype(f32)
        dt_bias = self.param(
            "dt_bias", _uniform_log(1e-3, 1e-1, lambda v: v + jnp.log(
                -jnp.expm1(-v))), (H,)).astype(f32)
        gate_norm = self.param("norm", nn.initializers.ones, (inner,))
        dt = jax.nn.softplus(dt.astype(f32) + dt_bias)          # (B, T, H)

        cached = bool(decode)

        def mix(cache, xbc, dt):
            """The convolution and the state of one group of rows (B, T):
            ``(y + D x, leaves)``."""
            B, T = dt.shape[:2]
            xbc, where, conv_leaf = _conv_after_tail(cache, xbc, conv_w,
                                                     conv_b)
            x = xbc[..., :inner].reshape(B, T, H, P)
            b, c = xbc[..., inner:inner + N], xbc[..., inner + N:]
            if not cached:
                y, leaves = ss.ssm_sequence(x, dt, a, b, c), None
            else:
                li, rows, fresh, valid = where
                if T == 1:
                    y, s = ss.ssm_decode(x[:, 0], dt[:, 0], a, b[:, 0],
                                         c[:, 0], cache["s"], li, rows, fresh)
                    y = y[:, None]
                else:
                    y, s = _traced_once(ss.ssm_prefill,
                                        chunk=_chunk_shaped(x))(
                        x, dt, a, b, c, cache["s"], li, rows, fresh,
                        length=valid)
                leaves = {"s": s, "conv": conv_leaf}
            return (y + skip[:, None] * x).reshape(B, T, inner), leaves

        y, leaves = _by_row_group(kv_cache, mix, xbc, dt) if cached \
            else mix(None, xbc, dt)
        g = ss.gated_norm(y, z, gate_norm, cfg.layer_norm_epsilon)
        out = _dense(cfg, C, use_bias=False, name="out_proj")(
            g.astype(cfg.dtype))
        return out, leaves


class KDAMixer(nn.Module):
    """Kimi Delta Attention in the attention's place (``ops/kda.py`` has
    the state's equations). With ``x`` the normed input, a head ``h`` of
    ``d = kda_d_head`` channels::

        [q ; k ; v] = silu(conv(W_qkv x))       (one convolution a channel)
        q_h <- l2norm(q_h) / sqrt(d)            k_h <- l2norm(k_h)
        g_h = -exp(A_log_h) softplus(W_f^up W_f^down x + dt_bias)_h
        beta_h = sigmoid(W_beta x)_h
        o_h = the gated delta rule over (q_h, k_h, v_h, g_h, beta_h)
        out = W_o [rmsnorm_d(o_h; w) (.) sigmoid(W_g^up W_g^down x)_h]

    ``conv`` is causal and depthwise over ``kda_d_conv`` taps, no bias; the
    decay ``g`` is a channel's, through a low rank of ``d``, and so is the
    output gate. Three forms, as :class:`Mamba2Mixer`: without a
    cache whole sequences from an empty state (``kda_sequence``); with one,
    ``kv_cache`` holds the stacked leaves whole, ``s`` (float32, (L, rows,
    H, d, d)) and ``conv`` (the last ``kda_d_conv - 1`` inputs of the
    convolution over [q ; k ; v], time-major on the minor axis), with
    ``layer``, ``start``, ``rows`` and ``valid``: one token takes
    ``kda_decode``, more take ``kda_chunk`` block by block. A token at or
    past ``valid`` is padding: it advances neither the state (its ``g``
    and ``beta`` are 0) nor the tail. An entry whose first position is 0
    reads neither. The convolution and the tail's shift are XLA's
    (``ops/state_space.causal_conv``)."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, x, *, decode: Union[bool, str] = False,
                 deterministic: bool = True, kv_cache=None, layer=None):
        from ..ops import kda

        cfg = self.config
        B, T, C = x.shape
        H, D, taps = cfg.kda_n_heads, cfg.kda_d_head, cfg.kda_d_conv
        inner, rank = cfg.kda_width, D      # (the low rank: a head's width)
        f32 = jnp.float32

        def dense(width, name):
            return _dense(cfg, width, use_bias=False, name=name)

        qkv = dense(3 * inner, "qkv_proj")(x)
        bound = 1.0 / math.sqrt(taps)       # (torch's Conv1d default)
        draw = nn.initializers.uniform(2 * bound)
        conv_w = self.param("conv_w", lambda *a: draw(*a) - bound,
                            (taps, 3 * inner))
        a = jnp.exp(self.param("A_log", _uniform_log(1.0, 16.0, jnp.log),
                               (H,)).astype(f32))
        dt_bias = self.param(
            "dt_bias", _uniform_log(1e-3, 1e-1, lambda v: v + jnp.log(
                -jnp.expm1(-v))), (inner,)).astype(f32)
        f = dense(inner, "f_b_proj")(dense(rank, "f_a_proj")(x))
        g = -a[:, None] * jax.nn.softplus(
            f.astype(f32) + dt_bias).reshape(B, T, H, D)
        beta = jax.nn.sigmoid(dense(H, "b_proj")(x).astype(f32))
        gate = dense(inner, "g_b_proj")(dense(rank, "g_a_proj")(x))
        o_norm = self.param("o_norm", nn.initializers.ones, (D,))

        cached = bool(decode)

        def unit(v):
            return v * jax.lax.rsqrt(
                jnp.sum(v * v, axis=-1, keepdims=True) + 1e-6)

        def mix(cache, qkv, g, beta):
            """The convolution and the state of one group of rows (B, T):
            ``(o, leaves)``."""
            B, T = beta.shape[:2]
            qkv, where, conv_leaf = _conv_after_tail(cache, qkv, conv_w,
                                                     None)
            q, k, v = (qkv[..., i * inner:(i + 1) * inner].reshape(
                B, T, H, D) for i in range(3))
            q, k = unit(q) * (1.0 / math.sqrt(D)), unit(k)
            if not cached:
                return kda.kda_sequence(q, k, v, g, beta), None
            li, rows, fresh, valid = where
            if T == 1:
                o, s = kda.kda_decode(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                      beta[:, 0], cache["s"], li, rows, fresh)
                o = o[:, None]
            else:
                o, s = _traced_once(kda.kda_prefill,
                                    chunk=_chunk_shaped(q))(
                    q, k, v, g, beta, cache["s"], li, rows, fresh,
                    length=valid)
            return o, {"s": s, "conv": conv_leaf}

        o, leaves = _by_row_group(kv_cache, mix, qkv, g, beta) if cached \
            else mix(None, qkv, g, beta)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + cfg.layer_norm_epsilon) * o_norm.astype(f32)
        y = o.reshape(B, T, inner) * jax.nn.sigmoid(gate.astype(f32))
        return dense(C, "o_proj")(y.astype(cfg.dtype)), leaves


class ShortConvMixer(nn.Module):
    """LFM2's gated short convolution in the attention's place. With ``u``
    the normed input (C wide)::

        [B ; C ; z] = W_in u            (three gates of C channels, this order)
        v = B (.) z
        c_t = sum_j w_j (.) v_{t - (K - 1) + j}     j = 0 .. K - 1
        out = W_out (C (.) c)

    The convolution is causal and depthwise over ``conv_taps`` = K taps, no
    bias and NO activation. There is no matrix state: what a sequence
    carries is the convolution's tail, the last K - 1 rows of ``v``, the
    one leaf ``conv`` of the state group (``(L, rows, (K - 1) * C)`` in the
    model's dtype, time-major on the minor axis), read and written through
    :func:`_conv_after_tail` with ``layer``, ``start``, ``rows`` and
    ``valid`` as :class:`Mamba2Mixer`: a decode row, a chunk and a whole
    sequence are one code path, a token at or past ``valid`` shifts nothing
    into the tail, an entry whose first position is 0 reads none, and a
    row that does not run keeps its tail. All of it is XLA's, under the
    scope ``short_conv``."""

    config: TransformerConfig

    @nn.compact
    def __call__(self, u, *, decode: Union[bool, str] = False,
                 deterministic: bool = True, kv_cache=None, layer=None):
        cfg = self.config
        C, K = u.shape[-1], cfg.conv_taps
        bcz = _dense(cfg, 3 * C, use_bias=False, name="in_proj")(u)
        bound = 1.0 / math.sqrt(K)      # (torch's Conv1d default)
        taps = nn.initializers.uniform(2 * bound)
        conv_w = self.param("conv_w", lambda *a: taps(*a) - bound, (K, C))

        def mix(cache, bcz):
            """The gates and the convolution of one group of rows (B, T):
            ``(C (.) c, leaves)``."""
            with jax.named_scope("short_conv"):
                b, c, z = (bcz[..., i * C:(i + 1) * C] for i in range(3))
                conv, _, leaf = _conv_after_tail(cache, b * z, conv_w, None,
                                                 silu=False)
                y = (c.astype(jnp.float32) * conv).astype(cfg.dtype)
            return y, None if cache is None else {"conv": leaf}

        y, leaves = _by_row_group(kv_cache, mix, bcz) if decode \
            else mix(None, bcz)
        return _dense(cfg, C, use_bias=False, name="out_proj")(y), leaves


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
                cfg.topk_norm_eps, name="mlp")(
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


def kv_cache_groups(cfg: TransformerConfig):
    """The layer groups of the cache, or None for the single group every
    model had before ``layer_types``: ``((suffix, layers, window), ...)``,
    ``""`` the full-attention layers (every position kept) and ``"_win"``
    the sliding layers (the last ``window`` positions visible). A page
    pool keeps one stacked leaf (``k<suffix>``, ``v<suffix>``) and one
    page table (``table<suffix>``) a group."""
    if cfg.layer_types is None \
            or "sliding_attention" not in cfg.layer_types:
        return None
    by_kind = {kind: tuple(i for i, k in enumerate(cfg.layer_types)
                           if k == kind)
               for kind in ("full_attention", "sliding_attention")}
    return (("", by_kind["full_attention"], 0),
            ("_win", by_kind["sliding_attention"], int(cfg.sliding_window)))


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


# What a cache kind does not run with yet, in ONE place: (kind, feature) ->
# the mechanism in its way. Every constructor that turns a feature on asks
# ``KVCacheSpec.refusal`` (``TransformerConfig``, which is what a spec is
# made from, asks :func:`refusal` itself) and raises the sentence it gets;
# none keeps a copy. Lifting a refusal is deleting its row; a new kind adds
# its rows here and edits no constructor (ROADMAP.md, Reach, has the queue).
CACHE_KINDS = {     # in the order a refusal is looked up (cache_kinds)
    "state": "a recurrent state",           # power_retention layers
    "ssm": "a state group beside K/V",      # mamba beside attention layers
    "kda": "a KDA state group",             # kda beside attention layers
    "conv": "a convolution-tail state group",   # conv beside attention layers
    "sparse": "the index of learned sparse attention",  # sparse_attention:
    # group means of the keys beside the K/V pages, a choice of blocks (it
    # stands beside lightning layers and is asked first)
    "lightning": "a Lightning state group",     # lightning beside attention
    "latent": "latent attention's cache",   # one row a token (kv_lora_rank)
    "window_only": "sliding-window layers alone",
    "window": "a window page group",        # sliding beside full layers
    "layer_types": "layer_types",           # a layer reads its kind off
    # the scan's counter (a window group is one case)
    "routed": "a routed FFN",               # the module's, not the cache's
}
FEATURES = {        # feature -> how its refusal names it, and who asks
    "spec_decode": "spec_decode",                           # ServingEngine
    "paged_kv": "paged_kv",         # ServingEngine, PagedKVPool, paged_cache
    "prefix_cache": "prefix_cache",                         # PagedKVPool
    "roles": "prefill/decode roles",    # ServingEngine, import_pages
    "tensor_parallel": "tensor-parallel inference",         # InferenceEngine
    "tensor_parallel_serving": "tensor-parallel serving",   # ServingEngine
    "prefill_chunk_wider_than_window":                      # ServingEngine
        "a prefill_chunk wider than sliding_window",
    "zero_inference": "ZeRO-Inference",             # ZeroInferenceEngine
    "kv_cache_quant": "kv_cache_quant",             # TransformerConfig
    "int8_weights": "int8_weights",                 # TransformerConfig
}
CACHE_REFUSALS = {
    ("state", "spec_decode"):
        "a rejected draft's tokens are in the state for good: verify_k's "
        "rollback moves an index, and a state has none (it would have to "
        "keep the state from before the draft)",
    ("state", "paged_kv"):
        "a state has no positions to page, a model whose every layer keeps "
        "one has no attention layer whose K/V a page pool would hold, and a "
        "prefix hit would need a snapshot of the state at the hit's "
        "boundary (a state group rides beside paged K/V where a model has "
        "both)",
    ("state", "roles"):
        "pages are the unit of a handoff and a state has none: the state "
        "itself would have to be shipped",
    ("state", "tensor_parallel"):
        "the state leaves have no placement on the model axis and the "
        "retention kernels are not wrapped for a mesh",
    ("state", "zero_inference"):
        "a power_retention layer's recurrent state is not threaded through "
        "the streamed layers",
    ("state", "kv_cache_quant"):
        "kv_cache_quant quantizes K/V columns; a power_retention layer "
        "keeps a float32 state and no column",
    ("ssm", "spec_decode"):
        "a rejected draft's tokens are in the state and the convolution's "
        "tail for good: verify_k's rollback moves an index, which hides "
        "K/V columns and nothing of a state",
    ("ssm", "prefix_cache"):
        "a hit maps the K/V pages of the prompt's start and would need the "
        "state as it stood at the hit's boundary, which nothing keeps (a "
        "snapshot a page boundary; pass paged_kv={'prefix_cache': False})",
    ("ssm", "roles"):
        "pages are the unit of a handoff: the slot's state rows would have "
        "to be shipped beside them",
    ("ssm", "tensor_parallel"):
        "the state leaves have no placement on the model axis and the "
        "state-space kernels are not wrapped for a mesh",
    ("ssm", "tensor_parallel_serving"):
        "the state leaves have no placement on the model axis and the "
        "state-space kernels are not wrapped for a mesh",
    ("ssm", "zero_inference"):
        "it streams one layer's block parameters at a time out of ONE "
        "stacked tree; mamba and attention layers are two, and the state is "
        "not threaded through the streamed layers",
    ("ssm", "kv_cache_quant"):
        "kv_cache_quant quantizes K/V columns; the state group beside them "
        "is float32 and the tier has not been run beside it",
    ("ssm", "int8_weights"):
        "int8_weights does not reach the mamba layers' convolution, A_log, "
        "D and dt_bias, which are parameters of the mixer and no Dense",
    ("kda", "spec_decode"):
        "a rejected draft's tokens are in the delta-rule state and the "
        "convolutions' tail for good: verify_k's rollback moves an index, "
        "which hides cached columns and nothing of a state",
    ("kda", "prefix_cache"):
        "a hit maps the pages of the prompt's start and would need the "
        "state as it stood at the hit's boundary, which nothing keeps (a "
        "snapshot a page boundary; pass paged_kv={'prefix_cache': False})",
    ("kda", "roles"):
        "pages are the unit of a handoff: the slot's state rows would have "
        "to be shipped beside them",
    ("kda", "tensor_parallel"):
        "the state leaves have no placement on the model axis and the KDA "
        "kernels are not wrapped for a mesh",
    ("kda", "tensor_parallel_serving"):
        "the state leaves have no placement on the model axis and the KDA "
        "kernels are not wrapped for a mesh",
    ("kda", "zero_inference"):
        "it streams one layer's block parameters at a time out of ONE "
        "stacked tree; kda and attention layers are two, and the state is "
        "not threaded through the streamed layers",
    ("kda", "kv_cache_quant"):
        "kv_cache_quant quantizes K/V columns; the state group beside them "
        "is float32 and the tier has not been run beside it",
    ("kda", "int8_weights"):
        "int8_weights does not reach the kda layers' convolution, A_log, "
        "dt_bias and output norm, which are parameters of the mixer and no "
        "Dense",
    ("conv", "spec_decode"):
        "a rejected draft's tokens are in the convolution's tail for good: "
        "verify_k's rollback moves an index, which hides K/V columns and "
        "nothing of a state",
    ("conv", "prefix_cache"):
        "a hit maps the K/V pages of the prompt's start and would need the "
        "tail as it stood at the hit's boundary, which nothing keeps (a "
        "snapshot a page boundary; pass paged_kv={'prefix_cache': False})",
    ("conv", "roles"):
        "pages are the unit of a handoff: the slot's state rows would have "
        "to be shipped beside them",
    ("conv", "tensor_parallel"):
        "the state leaf has no placement on the model axis",
    ("conv", "tensor_parallel_serving"):
        "the state leaf has no placement on the model axis",
    ("conv", "zero_inference"):
        "it streams one layer's block parameters at a time out of ONE "
        "stacked tree; conv and attention layers are two, and the tail is "
        "not threaded through the streamed layers",
    ("conv", "kv_cache_quant"):
        "kv_cache_quant quantizes K/V columns; the tail beside them is in "
        "the model's dtype and the tier has not been run beside it",
    ("conv", "int8_weights"):
        "int8_weights does not reach the conv layers' taps, which are a "
        "parameter of the mixer and no Dense",
    ("lightning", "spec_decode"):
        "a rejected draft's tokens are in the linear attention's state for "
        "good: verify_k's rollback moves an index, which hides K/V columns "
        "and nothing of a state",
    ("lightning", "prefix_cache"):
        "a hit maps the K/V pages of the prompt's start and would need the "
        "state as it stood at the hit's boundary, which nothing keeps (a "
        "snapshot a page boundary; pass paged_kv={'prefix_cache': False})",
    ("lightning", "roles"):
        "pages are the unit of a handoff: the slot's state rows would have "
        "to be shipped beside them",
    ("lightning", "tensor_parallel"):
        "the state leaf has no placement on the model axis and the "
        "Lightning kernels are not wrapped for a mesh",
    ("lightning", "tensor_parallel_serving"):
        "the state leaf has no placement on the model axis and the "
        "Lightning kernels are not wrapped for a mesh",
    ("lightning", "zero_inference"):
        "it streams one layer's block parameters at a time out of ONE "
        "stacked tree; lightning and attention layers are two, and the "
        "state is not threaded through the streamed layers",
    ("lightning", "kv_cache_quant"):
        "kv_cache_quant quantizes K/V columns; the state group beside them "
        "is float32 and the tier has not been run beside it",
    ("lightning", "int8_weights"):
        "int8_weights has not been run through the lightning layers (their "
        "norms on q, k and the output are parameters of the mixer and no "
        "Dense)",
    ("sparse", "spec_decode"):
        "a verify step's K + 1 rows of every slot would each choose their "
        "own blocks, and a rejected draft's keys are in the index's group "
        "means for good: the read of a chosen page list takes one row a "
        "slot or one slot's chunk",
    ("sparse", "prefix_cache"):
        "a page shared by a hit would have to share its group means, and a "
        "copy-on-write fork copies K/V pages while a group is still filling",
    ("sparse", "roles"):
        "a handoff ships K/V pages; the index's group means beside them "
        "have not been driven through one",
    ("sparse", "tensor_parallel_serving"):
        "the choice sums the query heads of a KV head and the chosen page "
        "list is one device's: the read has not been wrapped for a mesh",
    ("sparse", "zero_inference"):
        "the choice of blocks reads the whole row's keys, which the "
        "streamed layers' one-layer cache does not hand it",
    ("sparse", "kv_cache_quant"):
        "kv_cache_quant quantizes K/V columns; the index's group means are "
        "float32 means of the keys as written and the chosen-pages read "
        "takes no scales",
    ("latent", "spec_decode"):
        "the latent read takes one query row a slot or one slot's chunk; a "
        "verify step's K + 1 rows of every slot, each with its own causal "
        "limit, have no program yet",
    ("latent", "roles"):
        "a handoff ships K/V pages; a pool of latent pages has not been "
        "driven through one",
    ("latent", "tensor_parallel_serving"):
        "every head reads the one cached row, so the latent leaf has no "
        "placement on the model axis and the read is not wrapped for a mesh",
    ("latent", "zero_inference"):
        "latent attention's one cached row a token is not threaded through "
        "the streamed layers",
    ("latent", "kv_cache_quant"):
        "kv_cache_quant quantizes K/V columns a head; the latent cache is "
        "one row a token that every head reads, and its scales have no leaf",
    ("latent", "int8_weights"):
        "int8_weights does not reach latent attention: kv_b_proj is read as "
        "a matrix (absorbed into the query and the output), not through a "
        "Dense",
    ("window_only", "paged_kv"):
        "a model of sliding-window layers only has no full page group",
    ("window", "spec_decode"):
        "verify_k's rollback would have to un-recycle window pages",
    ("window", "prefix_cache"):
        "a hit maps pages of the prompt's start, which a ring has recycled "
        "(pass paged_kv={'prefix_cache': False})",
    ("window", "roles"):
        "a handoff would have to ship the window ring",
    ("window", "tensor_parallel_serving"):
        "the window group's leaves have no placement on the model axis",
    ("window", "prefill_chunk_wider_than_window"):
        "a chunk's rows read the pages behind it while its own are mapped, "
        "more than the ring a slot is granted",
    ("layer_types", "zero_inference"):
        "a layer reads its kind off the layer scan's counter, which the "
        "streamed layers do not carry",
    ("layer_types", "kv_cache_quant"):
        "the window group's pages and the window mask exist for the "
        "full-precision tier only",
    ("routed", "spec_decode"):
        "the drafter has no routed FFN",
    ("routed", "roles"):
        "a server of a routed model has not been driven through a handoff "
        "(the one served has a window ring too, which would have to be "
        "shipped)",
    ("routed", "tensor_parallel_serving"):
        "the expert leaves have no placement on the expert axis when served",
    ("routed", "zero_inference"):
        "it streams one layer's block parameters at a time; the routed "
        "FFN's expert leaves are stacked parameters of the model",
    ("routed", "kv_cache_quant"):
        "no routed model has run on the int8 cache tier (the one served has "
        "a window group too, which exists for the full-precision tier only)",
    ("routed", "int8_weights"):
        "int8_weights does not reach the routed FFN's expert leaves "
        "(ops/quantization quantizes Dense kernels); serve the routed "
        "model in bf16",
}


def cache_kinds(cfg: TransformerConfig) -> tuple:
    """The kinds of ``CACHE_KINDS`` a configuration is, in its order."""
    groups = kv_cache_groups(cfg)
    has = {"state": cfg.retention, "ssm": cfg.mamba, "kda": cfg.kda,
           "conv": cfg.conv, "lightning": cfg.lightning,
           "sparse": cfg.sparse_attention is not None,
           "latent": cfg.latent,
           "window_only": groups is not None and not groups[0][1],
           "window": groups is not None,
           "layer_types": cfg.layer_types is not None
           and not (cfg.retention or cfg.hybrid),
           "routed": cfg.n_experts}
    return tuple(kind for kind in CACHE_KINDS if has[kind])


def refusal(kinds: tuple, feature: str) -> Optional[str]:
    """Why a model of these ``kinds`` does not run with ``feature`` yet (the
    first of its kinds that has a row), or None where it does."""
    name = FEATURES[feature]    # a closed set: an unknown feature is a bug
    for kind in kinds:
        why = CACHE_REFUSALS.get((kind, feature))
        if why is not None:
            return (f"{name} does not compose with {CACHE_KINDS[kind]} yet: "
                    f"{why}")
    return None


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
    # the latent row. A model of conv layers beside attention layers: the
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
        """The layers of a model of state (mamba, kda or conv) and attention
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
