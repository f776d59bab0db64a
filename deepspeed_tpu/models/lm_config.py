"""The configuration of the unified decoder-only transformer
(``models/transformer_lm.py``): :class:`TransformerConfig`, the families as
its presets, and the logical axes of the parameters.

Families are presets of :class:`TransformerConfig` (``FAMILY_PRESETS``):

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
qwen3_next     rotary    rmsnorm1p  routed    Gated DeltaNet layers
                                              beside gated QK-normed
                                              GQA (rotary_pct 0.25),
                                              softmax router, a
                                              sigmoid-gated shared
                                              expert, experts held
=============  ========  =========  ========  ===================
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax.numpy as jnp

from .cache_kinds import cache_kinds, refusal


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
    norm: str = "layernorm"             # layernorm | rmsnorm | rmsnorm1p
    # (rmsnorm1p: the zero-centred RMSNorm, x / rms(x) * (1 + w) in
    # float32, for the layer norms, the final norm and the qk_norm)
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
    shared_expert_gate: bool = False    # the shared expert's output is
    # scaled by sigmoid(w_s . x) a token (w_s: n_embd -> 1)
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
    # Gated DeltaNet layers (``layer_types`` "gdn", beside "attention"
    # layers): gdn_n_value_heads heads of gdn_d_head value channels over
    # gdn_n_key_heads heads of as many key channels (a key head serves
    # value heads // key heads value heads), ONE log decay a value head, a
    # causal depthwise convolution of gdn_d_conv taps over [q ; k ; v], a
    # SiLU-gated norm on the output (models/gdn_layers.py; ops/kda.py has
    # the state's equations and kernels, in their scalar-decay form)
    gdn_n_key_heads: int = 0
    gdn_n_value_heads: int = 0
    gdn_d_head: int = 0
    gdn_d_conv: int = 4
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
    # (the attention layers' that cache K/V a head, sparse or not)
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
                                             "mamba", "kda", "gdn", "conv",
                                             "lightning", "attention"}
            if kinds or len(self.layer_types) != self.n_layer:
                raise ValueError(
                    f"layer_types names n_layer={self.n_layer} layers as "
                    f"sliding_attention | full_attention | power_retention "
                    f"| mamba | kda | gdn | conv | lightning | attention; "
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
        if self.attn_output_gate and (self.latent or self.retention):
            raise ValueError("attn_output_gate is the gate of attention "
                             "layers that cache K/V a head (CachedAttention, "
                             "SparseAttention): latent attention and "
                             "power_retention have none")
        if self.shared_expert_gate and not self.n_shared_experts:
            raise ValueError("shared_expert_gate scales the shared expert "
                             "(n_shared_experts > 0)")
        if self.norm not in ("layernorm", "rmsnorm", "rmsnorm1p"):
            raise ValueError(f"norm {self.norm!r}: know layernorm | rmsnorm "
                             f"| rmsnorm1p")
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
        if self.gdn and (
                not (self.gdn_n_key_heads and self.gdn_n_value_heads
                     and self.gdn_d_head)
                or self.gdn_n_value_heads % self.gdn_n_key_heads
                or self.gdn_d_conv < 2):
            raise ValueError(
                f"gdn layers need gdn_n_key_heads, a multiple of them as "
                f"gdn_n_value_heads, gdn_d_head and a convolution of two "
                f"taps or more; got {self.gdn_n_key_heads} key and "
                f"{self.gdn_n_value_heads} value heads of "
                f"{self.gdn_d_head}, taps {self.gdn_d_conv}")
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
            shared_expert_gate=False, experts_held=None, first_k_dense=0,
            ffn_dim=self.dense_ffn_dim)

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
    def gdn(self) -> bool:
        """``gdn`` layers beside ``attention`` layers: a state group over
        the former (KDA's leaf at the value heads), K/V over the latter."""
        return self.layer_types is not None and "gdn" in self.layer_types

    @property
    def gdn_channels(self) -> int:
        """The convolution's channels: ``[q ; k ; v]``."""
        return (2 * self.gdn_n_key_heads + self.gdn_n_value_heads) \
            * self.gdn_d_head

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
STATE_KINDS = ("mamba", "kda", "gdn", "conv", "lightning")

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
    # Qwen3-Next (Qwen; model_type qwen3_next): Gated DeltaNet layers
    # beside gated softmax GQA layers (``layer_types`` as published,
    # "linear_attention" | "full_attention", or "gdn" | "attention"), the
    # zero-centred RMSNorm everywhere but the DeltaNet output norm, a norm
    # on each head of q and k and a rotary over the first quarter of a
    # head, the output gate ``o * sigmoid(W_g x)``, a softmax router whose
    # chosen weights are renormalised, a shared expert scaled by a sigmoid
    # gate a token, an untied head. Widths, the pattern and the experts held
    # are the caller's.
    "qwen3_next": dict(pos_emb="rotary", rotary_pct=0.25, norm="rmsnorm1p",
                       activation="swiglu", qkv_bias=False, mlp_bias=False,
                       tie_word_embeddings=False, layer_norm_epsilon=1e-6,
                       qk_norm=True, attn_output_gate=True,
                       scoring_func="softmax", norm_topk_prob=True,
                       n_shared_experts=1, shared_expert_gate=True),
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
    if {"gdn", "linear_attention"} & set(cfg.get("layer_types") or ()):
        # (published as "linear_attention" | "full_attention")
        names = {"linear_attention": "gdn", "full_attention": "attention"}
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
