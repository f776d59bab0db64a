"""The one table of what a cache kind does not run with yet
(``models/cache_kinds.py``, ``CACHE_REFUSALS``): for EVERY row, the real
constructor that turns the feature on, over a tiny model of that kind and no
other, raises the row's own sentence. A row added without a way to reach it
here fails (``PRESETS`` / ``ASK`` have no entry for it)."""

import jax
import jax.numpy as jnp
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.inference.zero_inference import ZeroInferenceEngine
from deepspeed_tpu.models.cache_kinds import (CACHE_KINDS, CACHE_REFUSALS,
                                              FEATURES, cache_kinds)
from deepspeed_tpu.models.lm_config import transformer_config
from deepspeed_tpu.models.transformer_lm import TransformerLM
from deepspeed_tpu.serving import ServingEngine

from tests.unit.kinds import KINDS, SPARSE, TINY, init_params, kind_widths

WINDOW, PAGE = 16, 8
_LLAMA = dict(TINY, max_seq_len=128, n_layer=4, head_size=8)
# a configuration a kind, each of that kind ALONE (beside what it implies):
# the rows of tests/unit/kinds.py are the kinds as they are served, most of
# them two or three of these at once
PRESETS = {
    "state": (KINDS["retention"][0], kind_widths("retention")),
    "ssm": ("granite-hybrid", dict(
        TINY, n_layer=4, n_kv_head=2, ffn_dim=48,
        layer_types=["mamba", "attention"] * 2, mamba_n_heads=4,
        mamba_d_head=8, mamba_d_state=8)),
    "kda": ("granite-hybrid", dict(
        TINY, n_layer=4, n_kv_head=2, ffn_dim=48,
        layer_types=["kda", "attention"] * 2, kda_n_heads=2,
        kda_d_head=8)),
    "gdn": ("granite-hybrid", dict(
        TINY, n_layer=4, n_kv_head=2, ffn_dim=48,
        layer_types=["gdn", "attention"] * 2, gdn_n_key_heads=2,
        gdn_n_value_heads=4, gdn_d_head=8)),
    "conv": ("granite-hybrid", dict(
        TINY, n_layer=4, n_kv_head=2, ffn_dim=48,
        layer_types=["conv", "attention"] * 2)),
    "sparse": ("minicpm_sala", dict(
        TINY, n_layer=4, n_kv_head=2, ffn_dim=48,
        layer_types=["lightning", "sparse_attention"] * 2,
        sparse_attention=SPARSE)),
    "lightning": ("minicpm_sala", dict(
        TINY, n_layer=4, n_kv_head=2, ffn_dim=48, attn_output_gate=False,
        layer_types=["lightning", "attention"] * 2)),
    "latent": ("moonlight", dict(
        TINY, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
        v_head_dim=8, scoring_func="softmax")),
    "window_only": ("llama", dict(
        _LLAMA, layer_types=["sliding_attention"] * 4,
        sliding_window=WINDOW)),
    "window": ("llama", dict(
        _LLAMA, layer_types=["sliding_attention", "full_attention"] * 2,
        sliding_window=WINDOW)),
    "layer_types": ("llama", dict(_LLAMA,
                                  layer_types=["full_attention"] * 4)),
    "routed": ("llama", dict(TINY, ffn_dim=16, n_experts=4,
                             experts_per_token=2)),
}
IMPLIED = {"window_only": {"window", "layer_types"},
           "window": {"layer_types"},
           # (sparse attention stands beside state layers: its rows come
           # first in the table's order)
           "sparse": {"lightning"}}
_OFF = {"page_size": PAGE, "prefix_cache": False, "kernel": "off"}


def _config(kind, **over):
    family, sizes = PRESETS[kind]
    return transformer_config(family, **{**sizes, **over})


def test_each_preset_is_of_its_kind_alone():
    assert set(PRESETS) == set(CACHE_KINDS)
    for kind in PRESETS:
        assert set(cache_kinds(_config(kind))) \
            == {kind} | IMPLIED.get(kind, set()), kind


@pytest.fixture(scope="module")
def engine_of():
    """kind -> an inference engine over its tiny model, built on first
    use."""
    built = {}

    def get(kind, mesh=None):
        if (kind, mesh) not in built:
            model = TransformerLM(_config(kind))
            built[kind, mesh] = ds.init_inference(
                model=model, model_parameters=init_params(model, seed=0),
                config={"dtype": "float32"}, mesh=mesh)
        return built[kind, mesh]

    return get


class _TwoWayModelAxis:
    shape = {"model": 2, "data": 1}


def _serve(**kw):
    def ask(kind, engine_of, monkeypatch):
        ServingEngine(engine_of(kind), num_slots=2,
                      **{"prefill_chunk": PAGE, **kw})
    return ask


def _serve_on_a_model_axis(kind, engine_of, monkeypatch):
    engine = engine_of(kind)
    monkeypatch.setattr(engine, "mesh", _TwoWayModelAxis(), raising=False)
    ServingEngine(engine, num_slots=2, prefill_chunk=PAGE)


def _infer_on_a_model_axis(kind, engine_of, monkeypatch):
    from deepspeed_tpu.parallel import mesh as mesh_mod

    if jax.device_count() < 2:
        pytest.skip("needs two devices")
    mesh = mesh_mod.initialize_mesh(model=2, data=jax.device_count() // 2)
    try:
        engine_of(kind, mesh)._ensure_params(jnp.zeros((1, 2), jnp.int32))
    finally:
        mesh_mod.reset_mesh()


def _configure(feature):
    return lambda kind, engine_of, monkeypatch: _config(kind,
                                                        **{feature: True})


# feature -> the constructor that turns it on (and nothing else the kind
# refuses)
ASK = {
    "spec_decode": _serve(spec_decode={"k": 2}),
    "paged_kv": _serve(paged_kv=_OFF),
    "prefix_cache": _serve(paged_kv={"page_size": PAGE, "kernel": "off"}),
    "roles": _serve(role="decode"),
    "tensor_parallel": _infer_on_a_model_axis,
    "tensor_parallel_serving": _serve_on_a_model_axis,
    "prefill_chunk_wider_than_window": _serve(prefill_chunk=2 * WINDOW,
                                              paged_kv=_OFF),
    "zero_inference": lambda kind, engine_of, monkeypatch:
        ZeroInferenceEngine(_config(kind), {}),
    "kv_cache_quant": _configure("kv_cache_quant"),
    "int8_weights": _configure("int8_weights"),
}


@pytest.mark.parametrize("kind,feature", sorted(CACHE_REFUSALS))
def test_every_row_refuses_at_its_constructor_in_its_own_words(
        kind, feature, engine_of, monkeypatch):
    assert set(ASK) == set(FEATURES)
    with pytest.raises(ValueError) as refusal:
        ASK[feature](kind, engine_of, monkeypatch)
    said = str(refusal.value)
    assert said.startswith(f"{FEATURES[feature]} does not compose with "
                           f"{CACHE_KINDS[kind]} yet: "), said
    assert CACHE_REFUSALS[kind, feature] in said


def test_a_kind_with_no_row_constructs(engine_of):
    """The table refuses what it lists and nothing else: the plain model
    takes every server feature, a window group its pages."""
    spec = TransformerLM(transformer_config("llama", **TINY)).kv_cache_spec()
    assert spec.kinds == () and not any(
        spec.refusal(feature, 10 ** 6) for feature in FEATURES)
    srv = ServingEngine(engine_of("window"), num_slots=2,
                        prefill_chunk=PAGE, paged_kv=_OFF)
    assert srv.pool.ring is not None
    # a state group rides beside paged K/V where a model has both (PR 47):
    # what a model of state layers alone is still refused
    srv = ServingEngine(engine_of("ssm"), num_slots=2, prefill_chunk=PAGE,
                        paged_kv=_OFF)
    assert set(srv.pool.cache["cache_store"]) \
        == {"s", "conv", "k", "v", "index", "table"}
    srv = ServingEngine(engine_of("kda"), num_slots=2, prefill_chunk=PAGE,
                        paged_kv=_OFF)
    assert set(srv.pool.cache["cache_store"]) \
        == {"s", "conv", "k", "v", "index", "table"}
    # (PR 54) a state group of ONE leaf, the convolution's tail
    srv = ServingEngine(engine_of("conv"), num_slots=2, prefill_chunk=PAGE,
                        paged_kv=_OFF)
    assert set(srv.pool.cache["cache_store"]) \
        == {"conv", "k", "v", "index", "table"}
    # (PR 56) the index's group means beside K/V under the same table, and
    # the Lightning state a slot
    srv = ServingEngine(engine_of("sparse"), num_slots=2, prefill_chunk=PAGE,
                        paged_kv=_OFF)
    assert set(srv.pool.cache["cache_store"]) \
        == {"s", "k", "v", "kc", "index", "table"}
    srv.check_invariants()
    with pytest.raises(KeyError):
        spec.refusal("no_such_feature")


def test_a_kda_state_group_beside_latent_pages_refuses_in_the_groups_words():
    """PR 50's pairing (kda layers, latent attention, a routed FFN that
    holds a share): the first of its kinds that has a row answers, which is
    the state group's for everything a state is in the way of, and it
    serves on the page pool with the prefix cache off."""
    cfg = transformer_config(
        "kimi_linear", **dict(TINY, n_layer=8, kv_lora_rank=16,
                              qk_nope_head_dim=8, qk_rope_head_dim=8,
                              v_head_dim=8, ffn_dim=16, n_experts=8,
                              experts_per_token=2, experts_held=2,
                              dense_ffn_dim=48, kda_n_heads=2, kda_d_head=8),
        layer_types=["kda", "kda", "kda", "attention"] * 2,
        mlp_layer_types=["dense"] + ["sparse"] * 7)
    assert cache_kinds(cfg) == ("kda", "latent", "routed")
    spec = TransformerLM(cfg).kv_cache_spec()
    for feature in ("spec_decode", "prefix_cache", "roles", "tensor_parallel",
                    "tensor_parallel_serving", "zero_inference"):
        said = spec.refusal(feature)
        assert said.startswith(f"{FEATURES[feature]} does not compose with "
                               f"a KDA state group yet: "), said
        assert CACHE_REFUSALS["kda", feature] in said
    assert spec.refusal("paged_kv") is None
    cache = spec.paged_cache(4, PAGE, num_slots=2)
    assert set(cache) == {"c", "s", "conv"}
    # latent attention beside state layers, and without positions: what the
    # configuration refused before; a window beside it is still refused
    with pytest.raises(ValueError, match="no window and no retention"):
        transformer_config(
            "moonlight", **dict(TINY, n_layer=2, kv_lora_rank=16,
                                qk_nope_head_dim=8, qk_rope_head_dim=8,
                                v_head_dim=8, scoring_func="softmax"),
            layer_types=["sliding_attention", "full_attention"],
            sliding_window=WINDOW)
    with pytest.raises(ValueError, match="ONE attention layer that repeats"):
        transformer_config(
            "kimi_linear", **dict(TINY, n_layer=3, kda_n_heads=2,
                                  kda_d_head=8),
            layer_types=["kda", "mamba", "attention"])
    with pytest.raises(ValueError, match="head of the first period"):
        transformer_config(
            "kimi_linear", **dict(TINY, n_layer=4, ffn_dim=16, n_experts=4,
                                  experts_per_token=2, dense_ffn_dim=48,
                                  kda_n_heads=2, kda_d_head=8),
            layer_types=["kda", "attention"] * 2, first_k_dense=2)


def test_a_gdn_state_group_beside_gated_pages_refuses_in_the_groups_words():
    """PR 60's pairing (Gated DeltaNet layers, gated QK-normed rotary
    attention, a routed FFN that holds a share beside a gated shared
    expert): the state group answers for everything a state is in the way
    of, in the rows ``kda`` has, and it serves on the page pool with the
    prefix cache off."""
    sizes = dict(TINY, n_layer=8, n_kv_head=2, head_size=16, ffn_dim=16,
                 n_experts=8, experts_per_token=2, experts_held=2,
                 gdn_n_key_heads=2, gdn_n_value_heads=4, gdn_d_head=8)
    cfg = transformer_config(
        "qwen3_next", **sizes,
        layer_types=["linear_attention"] * 3 + ["full_attention"]
        + ["gdn"] * 3 + ["attention"])
    assert cfg.layer_types == ("gdn", "gdn", "gdn", "attention") * 2
    assert cache_kinds(cfg) == ("gdn", "routed")
    assert {f for k, f in CACHE_REFUSALS if k == "gdn"} \
        == {f for k, f in CACHE_REFUSALS if k == "kda"}
    spec = TransformerLM(cfg).kv_cache_spec()
    for feature in ("spec_decode", "prefix_cache", "roles", "tensor_parallel",
                    "tensor_parallel_serving", "zero_inference"):
        said = spec.refusal(feature)
        assert said.startswith(f"{FEATURES[feature]} does not compose with "
                               f"a Gated DeltaNet state group yet: "), said
        assert CACHE_REFUSALS["gdn", feature] in said
    assert spec.refusal("paged_kv") is None
    cache = spec.paged_cache(4, PAGE, num_slots=2)
    assert set(cache) == {"k", "v", "s", "conv"}
    assert cache["s"].shape == (6, 2, 4, 8, 8)
    assert cache["conv"].shape == (6, 2, 3 * (2 * 2 + 4) * 8)
    for feature in ("kv_cache_quant", "int8_weights"):
        with pytest.raises(ValueError,
                           match="a Gated DeltaNet state group yet"):
            transformer_config(
                "qwen3_next", **sizes, **{feature: True},
                layer_types=["gdn", "gdn", "gdn", "attention"] * 2)
    # value heads a multiple of the key heads; the gates and the norm are
    # refused where nothing reads them
    with pytest.raises(ValueError, match="a multiple of them as "
                                         "gdn_n_value_heads"):
        transformer_config(
            "qwen3_next", **dict(sizes, gdn_n_value_heads=3),
            layer_types=["gdn", "gdn", "gdn", "attention"] * 2)
    with pytest.raises(ValueError, match="shared_expert_gate scales the "
                                         "shared expert"):
        transformer_config(
            "qwen3_next", **dict(sizes, n_shared_experts=0),
            layer_types=["gdn", "gdn", "gdn", "attention"] * 2)
    with pytest.raises(ValueError, match="attn_output_gate is the gate of "
                                         "attention layers that cache K/V"):
        transformer_config(
            "moonlight", **dict(TINY, kv_lora_rank=16, qk_nope_head_dim=8,
                                qk_rope_head_dim=8, v_head_dim=8,
                                scoring_func="softmax"),
            attn_output_gate=True)
    with pytest.raises(ValueError, match="know layernorm | rmsnorm"):
        transformer_config("llama", **dict(TINY, norm="rmsnorm2p"))


def test_a_conv_tail_beside_rotary_pages_refuses_in_the_groups_words():
    """PR 54's pairing (conv layers, QK-normed rotary attention, a routed
    FFN behind two dense layers that are a period's whole head): the state
    group answers for everything a state is in the way of, and it serves on
    the page pool with the prefix cache off."""
    sizes = dict(TINY, n_layer=8, n_kv_head=2, ffn_dim=16, n_experts=8,
                 experts_per_token=2, dense_ffn_dim=48)
    published = ["conv", "conv", "full_attention", "conv"] * 2
    cfg = transformer_config("lfm2_moe", **sizes, layer_types=published,
                             first_k_dense=2)
    assert cache_kinds(cfg) == ("conv", "routed")
    assert cfg.pos_emb == "rotary" and cfg.qk_norm
    spec = TransformerLM(cfg).kv_cache_spec()
    for feature in ("spec_decode", "prefix_cache", "roles", "tensor_parallel",
                    "tensor_parallel_serving", "zero_inference"):
        said = spec.refusal(feature)
        assert said.startswith(f"{FEATURES[feature]} does not compose with "
                               f"a convolution-tail state group yet: "), said
        assert CACHE_REFUSALS["conv", feature] in said
    assert spec.refusal("paged_kv") is None
    assert set(spec.paged_cache(4, PAGE, num_slots=2)) == {"k", "v", "conv"}
    with pytest.raises(ValueError, match="head of the first period"):
        transformer_config("lfm2_moe", **sizes, layer_types=published,
                           first_k_dense=3)
    with pytest.raises(ValueError, match="two taps or more"):
        transformer_config("lfm2_moe", **sizes, layer_types=published,
                           first_k_dense=2, conv_taps=1)
    with pytest.raises(ValueError, match="ONE attention layer that repeats"):
        transformer_config("lfm2_moe", **dict(sizes, n_layer=3),
                           layer_types=["conv", "mamba", "full_attention"])
