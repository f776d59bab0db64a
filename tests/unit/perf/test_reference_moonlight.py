"""``perf/reference/moonlight.py`` (latent attention in its EXPANDED form,
every expert computed, no cache) against ``TransformerLM``'s ``moonlight``
preset at a small size, float32 on the CPU, comparing LOGITS: the full
forward; and ``ServingEngine`` runs on the page pool (bucketed admission,
chunked prefill, paged decode; the absorbed read as the Pallas kernel in
interpret mode and as its XLA form) against the reference's one pass over
prompt + answer. Six planted faults each break one of ``check_greedy``'s
limits. On the chip the same reference judges the served tokens at the
published widths."""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import deepspeed_tpu as ds  # noqa: E402
from deepspeed_tpu.serving import RequestState  # noqa: E402
from perf.reference import moonlight as ref  # noqa: E402

# float32 at "highest" on both sides, sums in another order, the absorbed
# form against the expanded one: logits of size ~4 agree to a few 1e-6.
# One bf16 rounding of one activation is 4e-3 of its size
ATOL = 1e-4
REL_TOL = 2.0 ** -5         # serve.py's
# the cell's depth, the dense layer and six sparse ones: how far a rounding
# carries depends on how many routed layers it passes through
SIZES = dict(vocab_size=128, max_seq_len=128, n_embd=64, n_layer=7, n_head=4,
             kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
             v_head_dim=16, ffn_dim=32, n_experts=8, experts_per_token=2,
             n_shared_experts=2, routed_scaling_factor=2.446,
             mlp_layer_types=["dense"] + ["sparse"] * 26,
             dense_ffn_dim=96, rope_theta=50000)
CHUNK = 16
PAGED = {"page_size": 16, "prefix_cache": False}


@pytest.fixture(scope="module")
def stack():
    from deepspeed_tpu.models.transformer_lm import (TransformerLM,
                                                     transformer_config)

    cfg = transformer_config("moonlight", dtype=jnp.float32, **SIZES)
    model = TransformerLM(cfg)
    ids = np.random.default_rng(0).integers(1, 128, (2, 96)).astype(np.int32)
    params = jax.jit(lambda: model.init(jax.random.PRNGKey(1),
                                        jnp.asarray(ids[:, :8]),
                                        method=model.logits))()["params"]
    # a bias as wide as the scores' own spread: the choice it orders is
    # another one for most tokens, so a fault in the rule is no rare event
    bias = params["blocks"]["block"]["mlp"]["router_bias"]
    params["blocks"]["block"]["mlp"]["router_bias"] = bias * 6.0
    return cfg, model, params, ids, reference_of(cfg)


def reference_of(cfg, **over):
    return ref.make_forward(**{**dict(
        n_head=cfg.n_head, kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        rope_theta=cfg.rope_theta, experts_per_token=cfg.experts_per_token,
        routed_scaling_factor=cfg.routed_scaling_factor,
        first_k_dense=cfg.first_k_dense, norm_topk_prob=cfg.norm_topk_prob,
        eps=cfg.layer_norm_epsilon), **over})


def reference_logits(logits_fn, params, seq):
    seq = np.asarray(seq, np.int32)
    return np.asarray(logits_fn(params, seq, np.arange(len(seq))))


def test_the_preset_is_the_published_block(stack):
    cfg, model, params, _, _ = stack
    assert cfg.first_k_dense == 1 and cfg.latent == 40
    assert cfg.scoring_func == "sigmoid" and not cfg.tie_word_embeddings
    assert set(params) == {"embed_tokens", "dense_blocks", "blocks",
                           "experts", "ln_f", "lm_head"}
    attn = params["blocks"]["block"]["attn"]
    assert set(attn) == {"q_proj", "kv_a_proj", "kv_a_norm", "kv_b_proj",
                         "o_proj"}                  # no k_proj, no v_proj
    assert attn["q_proj"]["kernel"].shape == (6, 64, 4 * 24)
    assert attn["kv_a_proj"]["kernel"].shape == (6, 64, 40)
    assert attn["kv_b_proj"].shape == (6, 32, 4 * 32)
    dense = params["dense_blocks"]["block"]["mlp"]
    assert dense["gate_proj"]["kernel"].shape == (1, 64, 96)
    sparse = params["blocks"]["block"]["mlp"]
    assert sparse["router"].shape == (6, 64, 8)
    assert sparse["router_bias"].shape == (6, 8)
    assert sparse["shared_gate_proj"]["kernel"].shape == (6, 64, 64)
    assert params["experts"]["gate_proj"].shape == (6, 8, 64, 32)
    spec = model.kv_cache_spec()
    assert spec.latent == 40
    cache = spec.stacked_cache(3)
    assert set(cache) == {"c", "index"}             # one row a token: no v
    assert cache["c"].shape == (7, 3, 40, 128)
    paged = spec.paged_cache(10, 16)
    assert set(paged) == {"c"} and paged["c"].shape == (7, 10, 40, 128)


def test_reference_matches_the_full_forward(stack):
    cfg, model, params, ids, logits_fn = stack
    got = model.apply({"params": params}, jnp.asarray(ids), method=model.logits)
    for b in range(2):
        np.testing.assert_allclose(
            np.asarray(got[b]), reference_logits(logits_fn, params, ids[b]),
            atol=ATOL)


def test_reference_is_sharp_where_the_layer_is_new(stack):
    """Another rotary base, no scaling factor, one expert fewer a token or
    the dense layer taken for a sparse one's neighbour: the reference
    leaves the model by far more than ATOL."""
    cfg, model, params, ids, _ = stack
    want = np.asarray(model.apply({"params": params}, jnp.asarray(ids[:1]),
                                  method=model.logits)[0])
    for change in (dict(rope_theta=10000.0), dict(routed_scaling_factor=1.0),
                   dict(experts_per_token=1), dict(norm_topk_prob=False)):
        got = reference_logits(reference_of(cfg, **change), params, ids[0])
        assert np.abs(got - want).max() > 20 * ATOL, change


def served(model, params, prompts, new_tokens, plant=None, kernel="off"):
    """Requests through a paged server of three slots, chunk 16;
    ``plant(srv)`` may break it first. Returns the requests."""
    srv = ds.init_serving(model, model_parameters=params,
                          config={"dtype": "float32"}, num_slots=3,
                          prefill_chunk=CHUNK,
                          paged_kv=dict(PAGED, kernel=kernel))
    if plant is not None:
        plant(srv)
    reqs = [srv.submit(p, max_new_tokens=new_tokens) for p in prompts]
    srv.run_until_drained(max_steps=600)
    assert all(r.state is RequestState.FINISHED for r in reqs)
    srv.check_invariants()
    return reqs


def worst_shortfall(logits_fn, params, reqs):
    worst = 0.0
    for r in reqs:
        seq = np.concatenate([np.asarray(r.prompt), r.output_tokens[:-1]])
        lg = reference_logits(logits_fn, params, seq)[len(r.prompt) - 1:]
        chosen = lg[np.arange(len(r.output_tokens)), r.output_tokens]
        worst = max(worst, float((lg.max(-1) - chosen).max()))
    return worst


def prompts_of(ids):
    # one under a bucket, two of one bucket (batched admission), two
    # chunked (three and six chunks), one exactly a chunk
    return [ids[0, :9], ids[1, :30], ids[0, 3:31], ids[0, :40],
            ids[1, :90], ids[1, 5:21]]


@pytest.mark.parametrize("kernel", ["off", "on"])
def test_a_mixed_server_run_agrees_with_one_pass_of_the_reference(stack,
                                                                  kernel):
    cfg, model, params, ids, logits_fn = stack
    reqs = served(model, params, prompts_of(ids), 10, kernel=kernel)
    assert worst_shortfall(logits_fn, params, reqs) <= ATOL


def fp8(tree):
    """Every weight rounded to three bits of mantissa (e4m3's)."""
    def one(a):
        a = np.asarray(a, np.float32)
        m, e = np.frexp(a)
        return jnp.asarray(np.ldexp(np.round(m * 16.0) / 16.0, e))
    return jax.tree_util.tree_map(one, tree)


def _swap_pages(srv):
    """After the prompts are in: two running slots' first table entries
    change places, so each reads the other's first page. (Two pages of ONE
    slot would do nothing: a cached row carries its position in its rotary
    key, and attention does not mind the order of what it sums.)"""
    step, done = srv.step, []

    def stepping():
        out = step()
        pool = srv.pool
        if not done and not srv._prefill_queue and len(srv._slot_req) > 1:
            a, b = sorted(srv._slot_req)[:2]
            pool.table[[a, b], 0] = pool.table[[b, a], 0]
            pool._sync_table()
            done.append((a, b))
        return out

    srv.step = stepping


FAULTS = ["wrong_page", "shifted_position", "dropped_shared_expert",
          "unscaled_weights", "choice_on_unbiased_scores",
          "float8_rounded_weights"]


@pytest.mark.parametrize("fault", FAULTS)
def test_planted_faults_break_a_limit_of_check_greedy(stack, fault,
                                                      monkeypatch):
    """The server is broken, the reference judges its tokens on the true
    weights at serve.py's tolerance: at least one request is not ok."""
    from deepspeed_tpu.models import transformer_lm as tlm

    cfg, model, params, ids, logits_fn = stack
    serve_model, serve_params, plant = model, params, None
    copy = jax.tree_util.tree_map(lambda x: x, params)
    if fault == "wrong_page":
        plant = _swap_pages
    elif fault == "shifted_position":
        rotary = tlm.apply_rotary
        monkeypatch.setattr(
            tlm, "apply_rotary",
            lambda x, positions, **kw: rotary(
                x, positions + (x.shape[1] == 1), **kw))
    elif fault == "dropped_shared_expert":
        mlp = copy["blocks"]["block"]["mlp"]
        mlp["shared_down_proj"] = {
            "kernel": jnp.zeros_like(mlp["shared_down_proj"]["kernel"])}
        serve_params = copy
    elif fault == "unscaled_weights":
        serve_model = tlm.TransformerLM(dataclasses.replace(
            cfg, routed_scaling_factor=1.0, scoring_func="sigmoid"))
    elif fault == "choice_on_unbiased_scores":
        mlp = copy["blocks"]["block"]["mlp"]
        mlp["router_bias"] = jnp.zeros_like(mlp["router_bias"])
        serve_params = copy
    elif fault == "float8_rounded_weights":
        serve_params = fp8(params)
    reqs = served(serve_model, serve_params, prompts_of(ids), 24, plant)
    checks = [ref.check_greedy(logits_fn, params, np.asarray(r.prompt),
                               list(r.output_tokens), 128, 24, REL_TOL)
              for r in reqs]
    assert not all(c["ok"] for c in checks), checks


def test_the_true_server_passes_check_greedy(stack):
    cfg, model, params, ids, logits_fn = stack
    for r in served(model, params, prompts_of(ids), 24):
        check = ref.check_greedy(logits_fn, params, np.asarray(r.prompt),
                                 list(r.output_tokens), 128, 24, REL_TOL)
        assert check["ok"] and check["positions_over_rel_tol"] == 0, check


def test_check_greedy_holds_served_tokens_to_the_logits(stack):
    """The head's blocks over the vocabulary give what the whole logits
    give: the best logit, the scale and the token's own."""
    cfg, model, params, ids, logits_fn = stack
    prompt, seq = ids[0, :20], list(ids[0, :20])
    for _ in range(6):      # the reference's own greedy continuation
        lg = reference_logits(logits_fn, params, seq)
        seq.append(int(np.argmax(lg[-1])))
    out = ref.check_greedy(logits_fn, params, prompt, seq[20:], 128, 8,
                           REL_TOL)
    assert out["ok"] and out["positions"] == 6
    assert out["worst_shortfall"] == 0.0
    short, scale = ref.shortfalls(logits_fn, params, prompt, seq[20:], 128, 8)
    full = reference_logits(logits_fn, params, seq[:-1])[19:]
    np.testing.assert_allclose(scale, np.abs(full).max(-1), rtol=1e-5)
    wrong = list(seq[20:])
    wrong[3] = (wrong[3] + 1) % 128
    assert not ref.check_greedy(logits_fn, params, prompt, wrong, 128, 8,
                                1e-6)["ok"]


def test_configuration_file_holds_the_published_widths_and_the_cache():
    with open(os.path.join(ROOT, "perf", "configs",
                           "moonlight-16b-a3b-mla.json")) as f:
        cfg = json.load(f)
    published = dict(
        attention_bias=False, ep_size=1, first_k_dense_replace=1,
        hidden_act="silu", hidden_size=2048, intermediate_size=11264,
        kv_lora_rank=512, max_position_embeddings=8192,
        model_type="deepseek_v3", moe_intermediate_size=1408,
        moe_layer_freq=1, n_group=1, n_routed_experts=64, n_shared_experts=2,
        norm_topk_prob=True, num_attention_heads=16, num_experts_per_tok=6,
        num_key_value_heads=16, num_nextn_predict_layers=0, q_lora_rank=None,
        qk_nope_head_dim=128, qk_rope_head_dim=64, rms_norm_eps=1e-05,
        rope_theta=50000, routed_scaling_factor=2.446,
        scoring_func="sigmoid", seq_aux=True, tie_word_embeddings=False,
        topk_group=1, topk_method="noaux_tc", v_head_dim=128,
        vocab_size=163840)
    for key, value in published.items():
        assert cfg[key] == value, key
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["num_hidden_layers"] == 7 == cfg["model"]["config_kwargs"][
        "n_layer"]
    assert len(cfg["assumed"]) >= 4
    per = cfg["parameters_a_layer"]
    sparse = sum(v for k, v in per.items() if k != "dense_ffn")
    dense = per["attention"] + per["norms"] + per["dense_ffn"]
    assert sparse == 584847936 and dense == 82973184
    assert dense + 6 * sparse + cfg["embedding_and_head_parameters"] + 2048 \
        == cfg["parameters"] == 4263151488
    # the cache: what the program allocates at these sizes
    from deepspeed_tpu.models.transformer_lm import (TransformerLM,
                                                     transformer_config)

    model_cfg = transformer_config("moonlight",
                                   **cfg["model"]["config_kwargs"])
    assert (model_cfg.head_dim, model_cfg.kv_heads, model_cfg.n_head,
            model_cfg.n_layer, model_cfg.first_k_dense) == (128, 16, 16, 7, 1)
    spec = TransformerLM(model_cfg).kv_cache_spec()
    paged = cfg["server"]["paged_kv"]
    leaf, = jax.eval_shape(lambda: spec.paged_cache(
        paged["num_pages"], paged["page_size"])).values()
    assert leaf.shape == (7, 3072, 576, 128) and leaf.dtype == jnp.bfloat16
    assert spec.latent * 2 == cfg["kv_bytes_per_token_a_layer"] == 1152
    assert int(np.prod(leaf.shape)) * 2 == cfg["kv_bytes"]["latent_pages"]
    assert cfg["server"]["num_slots"] == 64
