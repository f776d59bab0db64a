"""What kinds of cache a configuration asks for, and what each kind does
not run with yet: the window groups of ``layer_types``
(:func:`kv_cache_groups`), the kinds in the order a refusal is looked up
(:func:`cache_kinds`) and the one table of refusals (``CACHE_REFUSALS``).
Below ``models/lm_config.py``, whose ``TransformerConfig`` asks
:func:`refusal` itself: a configuration is read here by its attributes and
nothing of the package is imported."""

from __future__ import annotations

from typing import TYPE_CHECKING, Optional

if TYPE_CHECKING:       # the annotations' only
    from .lm_config import TransformerConfig


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
    "gdn": "a Gated DeltaNet state group",  # gdn beside attention layers
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
    ("gdn", "spec_decode"):
        "a rejected draft's tokens are in the delta-rule state and the "
        "convolution's tail for good: verify_k's rollback moves an index, "
        "which hides K/V columns and nothing of a state",
    ("gdn", "prefix_cache"):
        "a hit maps the K/V pages of the prompt's start and would need the "
        "state as it stood at the hit's boundary, which nothing keeps (a "
        "snapshot a page boundary; pass paged_kv={'prefix_cache': False})",
    ("gdn", "roles"):
        "pages are the unit of a handoff: the slot's state rows would have "
        "to be shipped beside them",
    ("gdn", "tensor_parallel"):
        "the state leaves have no placement on the model axis and the "
        "delta-rule kernels are not wrapped for a mesh",
    ("gdn", "tensor_parallel_serving"):
        "the state leaves have no placement on the model axis and the "
        "delta-rule kernels are not wrapped for a mesh",
    ("gdn", "zero_inference"):
        "it streams one layer's block parameters at a time out of ONE "
        "stacked tree; gdn and attention layers are two, and the state is "
        "not threaded through the streamed layers",
    ("gdn", "kv_cache_quant"):
        "kv_cache_quant quantizes K/V columns; the state group beside them "
        "is float32 and the tier has not been run beside it",
    ("gdn", "int8_weights"):
        "int8_weights does not reach the gdn layers' convolution, A_log, "
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
           "gdn": cfg.gdn, "conv": cfg.conv, "lightning": cfg.lightning,
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
