"""The tiny model of each layer kind, written once.

A test that needs "a small model with a state group" (or latent pages, a
window ring, a routed FFN, ...) takes it from here: :data:`KINDS` has one
row a kind, ``(family, widths)`` over :data:`TINY`, and :func:`kind_stack`
builds ``(model, params, engine)`` of a row once a process. A test that
needs a kind at other widths passes them at its call (``n_layer=4,
layer_types=[...] * 2``: more periods; a wider context), where the reader
sees why. A new layer kind is one row here.

The rows are what the page pool's four cache kinds were tested at since
PR 48 (two layers, 32 wide, four heads; pages of 8 hold a chunk of 4 twice):
small enough that a server of one traces and lowers in seconds on the CPU,
and every kind's kernels run in interpret mode at these widths.
"""

import jax
import jax.numpy as jnp

import deepspeed_tpu as ds
from deepspeed_tpu.models.lm_config import transformer_config
from deepspeed_tpu.models.transformer_lm import TransformerLM

TINY = dict(vocab_size=64, max_seq_len=64, n_embd=32, n_layer=2, n_head=4,
            dtype=jnp.float32)

_LATENT = dict(kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
               v_head_dim=8)
# the toy of MiniCPM-SALA's index: blocks of 4 keys, a window of 8, the top
# 2 blocks, dense up to 16 keys
SPARSE = dict(kernel_size=2, kernel_stride=1, block_size=4, init_blocks=1,
              window_size=8, topk=2, dense_len=16)

# The window kind as perf/reference/mellum.py is held to it: a period of
# three sliding layers and one full one, each kind with its published rotary
# table (yarn on the full layers), heads of 32, eight experts and a context
# of eight windows. Laid over the "window_routed" row.
MELLUM_PERIOD = dict(
    vocab_size=128, max_seq_len=128, n_embd=64, n_layer=4, head_size=32,
    ffn_dim=32, layer_types=["sliding_attention"] * 3 + ["full_attention"],
    n_experts=8, rope_theta=500000, rope_parameters={
        "full_attention": {"rope_type": "yarn", "rope_theta": 500000,
                           "factor": 16,
                           "original_max_position_embeddings": 8192,
                           "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}})

# kind -> (family, widths over TINY)
KINDS = {
    # K/V pages and nothing else
    "plain": ("gpt-neox", {}),
    # a window ring beside the full pages, under a routed FFN
    "window_routed": ("mellum", dict(
        n_kv_head=2, head_size=16, ffn_dim=16,
        layer_types=["sliding_attention", "full_attention"],
        sliding_window=16, n_experts=4, experts_per_token=2)),
    # latent pages behind a leading dense layer, a shared expert
    "latent_routed": ("moonlight", dict(
        _LATENT, ffn_dim=16, n_experts=4, experts_per_token=2,
        n_shared_experts=1, first_k_dense=1, dense_ffn_dim=48,
        routed_scaling_factor=2.446)),
    # every layer a recurrent state, no K/V
    "retention": ("brumby", dict(n_kv_head=2, head_size=16, ffn_dim=48)),
    # a mamba state group beside K/V pages
    "state_group": ("granite-hybrid", dict(
        n_kv_head=2, ffn_dim=48, layer_types=["mamba", "attention"],
        mamba_n_heads=4, mamba_d_head=8, mamba_d_state=8)),
    # a KDA state group beside latent pages, the routed FFN holding 2 of
    # its 8 experts behind one dense layer
    "kda_latent": ("kimi_linear", dict(
        _LATENT, n_layer=4, ffn_dim=16, n_experts=8, experts_per_token=2,
        experts_held=2, n_shared_experts=1, dense_ffn_dim=48,
        kda_n_heads=2, kda_d_head=8,
        layer_types=["kda", "kda", "kda", "attention"],
        mlp_layer_types=["dense", "sparse", "sparse", "sparse"])),
    # a Gated DeltaNet state group (two value heads a key head) beside gated
    # QK-normed K/V pages under a quarter-head rotary, the zero-centred norm,
    # the routed FFN holding 2 of its 8 experts beside a gated shared expert
    "gdn_gated": ("qwen3_next", dict(
        n_layer=4, n_kv_head=2, head_size=16, ffn_dim=16, n_experts=8,
        experts_per_token=2, experts_held=2, gdn_n_key_heads=2,
        gdn_n_value_heads=4, gdn_d_head=8,
        layer_types=["linear_attention"] * 3 + ["full_attention"])),
    # a state group of the convolution's tail alone beside QK-normed rotary
    # K/V, the routed FFN behind two dense layers
    "conv_tail": ("lfm2_moe", dict(
        n_layer=4, n_kv_head=2, ffn_dim=16, n_experts=8,
        experts_per_token=2, first_k_dense=2, dense_ffn_dim=48,
        layer_types=["conv", "conv", "full_attention", "conv"])),
    # learned block-sparse attention beside a Lightning state, an irregular
    # stack
    "sparse_lightning": ("minicpm_sala", dict(
        n_layer=4, n_kv_head=2, ffn_dim=16, sparse_attention=SPARSE,
        mixer_types=["minicpm4", "lightning-attn", "minicpm4",
                     "minicpm4"])),
}


def kind_widths(kind, **over):
    """A row's keyword arguments whole: ``TINY``, its widths, ``over``."""
    return {**TINY, **KINDS[kind][1], **over}


def kind_config(kind, **over):
    """The ``TransformerConfig`` of a row, ``over`` laid on its widths."""
    return transformer_config(KINDS[kind][0], **kind_widths(kind, **over))


def init_params(model, seed=1):
    """A model's parameters from ``PRNGKey(seed)``, initialised inside one
    jit (op by op, a stack of state layers takes many times as long)."""
    return jax.jit(lambda: model.init(
        jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32),
        method=model.logits))()["params"]


def engine_weights(model, batch, seed=1234, streams=("params", "dropout")):
    """The parameters a training engine makes of its first batch when it is
    given none (``DeepSpeedEngine._init_params_from_batch``: ``model.init``
    with every stream ``PRNGKey(config.seed)``, 1234 unless the
    configuration says otherwise), made inside ONE jit and handed to
    ``ds.initialize(model_parameters=...)``. The engine calls ``init`` op by
    op: seconds an engine, a third of a launched worker's life. The one
    line that repairs it there is ROADMAP D8 (4)'s, for a PR with a chip
    verdict (it is the train cells' ``setup_s``); until then this is the one
    copy of the work-around. ``streams`` names what else the model draws
    from at init (a routed model: ``"gating"``)."""
    key = jax.random.PRNGKey(seed)
    return jax.jit(lambda: model.init(
        {name: key for name in streams}, batch))()["params"]


def hashable(value):
    """``value`` with its dicts and lists as tuples: a key to memoise by."""
    if isinstance(value, dict):
        return tuple(sorted((k, hashable(v)) for k, v in value.items()))
    if isinstance(value, (list, tuple)):
        return tuple(hashable(v) for v in value)
    return value


_STACKS = {}


def kind_stack(kind, **over):
    """``(model, params, engine)`` of a row (float32, an inference engine
    over the parameters), built once a process for each ``over``. What a
    test gets is shared: servers are built over the engine, nothing writes
    to the three."""
    key = (kind, hashable(over))
    if key not in _STACKS:
        model = TransformerLM(kind_config(kind, **over))
        params = init_params(model)
        _STACKS[key] = (model, params, ds.init_inference(
            model=model, model_parameters=params,
            config={"dtype": "float32"}))
    return _STACKS[key]
