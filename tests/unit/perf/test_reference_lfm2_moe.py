"""``perf/reference/lfm2_moe.py`` (the short convolution position by position
from its definition, QK-normed rotary attention over blocks of query rows,
every expert as a dense masked sum, no cache) against ``TransformerLM``'s
``lfm2_moe`` preset at a small size, float32 on the CPU, comparing LOGITS:
the full forward; servers that mix bucketed admission (right padding),
chunked prefill (prompts that are no multiple of the chunk, which is one
page), a chunk beside running slots as ONE program, and decode over
re-seated slots, through the page pool and the tail (the dense composition
and the kernels in place) and on the contiguous pool, against the
reference's one pass over prompt + answer. Six planted faults have to fail
the comparison the configured model passes. On the chip the same reference
judges the served tokens at the published widths."""

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

from deepspeed_tpu.ops import state_space as ss  # noqa: E402
from perf.reference import lfm2_moe as ref  # noqa: E402
# (the helpers that drive a server and tap the logits it samples from)
from test_reference_granite import (PAGED_OFF, PAGED_ON,  # noqa: E402
                                    logit_error, prompts_of,
                                    reference_logits, served)

# float32 at "highest" on both sides. The program convolves shifted slices
# of a chunk after the carried tail where the reference shifts a register a
# position at a time, reads K/V through the pages where the reference
# scores blocks of fresh rows, and sums a token's four experts in another
# order: eight layers deep, logits of size ~4.5 agree to 1.1e-5 in the full
# forward and 8.5e-6 on every pool (measured, PR 54). 1e-4 is 9 x that. The
# planted faults move a logit by its whole size (the choice ordered without
# the bias 1.5, the gates read in another order 6.0), each has to pass 50 x
# the tolerance
ATOL = 1e-4
SIZES = dict(vocab_size=512, max_seq_len=128, n_embd=64, n_head=4,
             n_kv_head=2, ffn_dim=32, n_experts=8, experts_per_token=4,
             first_k_dense=2, dense_ffn_dim=96, rope_theta=1000000.0)
PUBLISHED = ("conv", "conv", "full_attention", "conv") * 2


def model_of(**change):
    from deepspeed_tpu.models.transformer_lm import (TransformerLM,
                                                     transformer_config)

    cfg = transformer_config("lfm2_moe", dtype=jnp.float32,
                             n_layer=len(PUBLISHED), layer_types=PUBLISHED,
                             **{**SIZES, **change})
    return cfg, TransformerLM(cfg)


def build():
    cfg, model = model_of()
    ids = np.random.default_rng(0).integers(1, 512, (2, 96)).astype(np.int32)
    params = jax.jit(lambda: model.init(jax.random.PRNGKey(1),
                                        jnp.asarray(ids[:, :8]),
                                        method=model.logits))()["params"]
    return cfg, model, params, ids, forward_of(cfg)


def forward_of(cfg, **change):
    return ref.make_forward(**{**dict(
        layer_types=cfg.layer_types, n_head=cfg.n_head,
        n_kv_head=cfg.kv_heads, head_dim=cfg.head_dim,
        rope_theta=cfg.rope_theta, experts_per_token=cfg.experts_per_token,
        routed_scaling_factor=cfg.routed_scaling_factor,
        first_k_dense=cfg.first_k_dense, norm_topk_prob=cfg.norm_topk_prob,
        eps=cfg.layer_norm_epsilon), **change})


@pytest.fixture(scope="module")
def stack():
    return build()


def full_forward(model, params, ids):
    return np.asarray(model.apply({"params": params}, jnp.asarray(ids[:1]),
                                  method=model.logits)[0])


def test_the_preset_is_the_published_block(stack):
    cfg, model, params, _, _ = stack
    # published as "conv" | "full_attention"; the stack's one attention kind
    assert cfg.layer_types == ("conv", "conv", "attention", "conv") * 2
    assert cfg.conv and cfg.hybrid == "conv" and not (cfg.mamba or cfg.kda)
    assert cfg.pos_emb == "rotary" and cfg.qk_norm and cfg.head_dim == 16
    assert cfg.tie_word_embeddings and "lm_head" not in params
    assert cfg.scoring_func == "sigmoid" and cfg.topk_norm_eps == 1e-6
    assert cfg.n_shared_experts == 0 and cfg.conv_taps == 3
    # both dense layers are the conv layers at the head of the first period
    assert cfg.hybrid_period == (2, 1, 2) and cfg.first_k_dense == 2
    assert set(params) == {"attn_blocks", "dense_blocks", "conv_blocks",
                           "experts", "embed_tokens", "ln_f"}
    lead = params["dense_blocks"]["block"]
    assert set(lead["mlp"]) == {"gate_proj", "up_proj", "down_proj"}
    assert lead["mlp"]["gate_proj"]["kernel"].shape == (2, 64, 96)
    for leaf, layers in (("dense_blocks", 2), ("conv_blocks", 4)):
        mixer = params[leaf]["block"]["conv"]
        assert set(mixer) == {"in_proj", "conv_w", "out_proj"}
        assert mixer["in_proj"]["kernel"].shape == (layers, 64, 3 * 64)
        assert mixer["conv_w"].shape == (layers, 3, 64)
        assert mixer["out_proj"]["kernel"].shape == (layers, 64, 64)
    attn = params["attn_blocks"]["block"]["attn"]
    assert attn["q_norm"]["scale"].shape == (2, 16)
    assert attn["k_norm"]["scale"].shape == (2, 16)
    assert attn["k_proj"]["kernel"].shape == (2, 64, 32)
    assert not any("bias" in leaf for leaf in attn)
    # six routed layers of eight experts, no shared expert beside them
    assert params["experts"]["gate_proj"].shape == (6, 8, 64, 32)
    for leaf in ("conv_blocks", "attn_blocks"):
        assert set(params[leaf]["block"]["mlp"]) == {"router", "router_bias"}
    spec = model.kv_cache_spec()
    assert spec.kinds == ("conv", "routed")
    # ONE leaf: the last two rows of v = B (.) z, and no matrix state
    assert spec.state_group == (6, (("conv", (2 * 64,), jnp.float32),))
    assert spec.state_leaves == ("conv",) and spec.state is None
    assert spec.n_layer == 8 and spec.kv_layers == 2
    assert spec.state_bytes_per_row == 6 * 2 * 64 * 4
    cache = spec.stacked_cache(3)
    assert set(cache) == {"conv", "k", "v", "index"}
    assert cache["k"].shape == (2, 3, 2, 16, 128)
    paged = spec.paged_cache(24, 16, num_slots=3)
    assert set(paged) == {"conv", "k", "v"}
    assert paged["conv"].shape == (6, 3, 128)
    assert paged["k"].shape == (2, 24, 2, 16, 128)


def test_reference_matches_the_full_forward(stack):
    """``logits`` without a cache: the convolution over shifted slices of
    the whole sequence, attention as one masked einsum."""
    cfg, model, params, ids, logits_fn = stack
    got = model.apply({"params": params}, jnp.asarray(ids),
                      method=model.logits)
    for b in range(2):
        np.testing.assert_allclose(
            np.asarray(got[b]), reference_logits(logits_fn, params, ids[b]),
            atol=ATOL)


@pytest.mark.parametrize("pool", ["paged_off", "paged_on", "contiguous"])
def test_a_mixed_server_run_agrees_with_one_pass_of_the_reference(stack,
                                                                  pool):
    """Prefill in chunks, then decode, through each pool and the tail:
    bucketed admission with right padding, chunked prefill of prompts that
    are no multiple of the chunk (three and six chunks, each boundary a
    page's and inside the three taps' reach), six requests over three
    slots; every generated position against the reference's full forward.
    On the kernels' pool a chunk rides beside the running slots as ONE
    program, whose rows read what the separate programs would."""
    cfg, model, params, ids, logits_fn = stack
    paged = {"paged_off": PAGED_OFF, "paged_on": PAGED_ON,
             "contiguous": False}[pool]
    from deepspeed_tpu.telemetry import default_tracer

    def fused():    # (one ring for every server of the process)
        return sum(1 for e in default_tracer().events()
                   if e["name"] == "serving/enqueue"
                   and (e.get("args") or {}).get("program") == "chunk_decode")

    before, start = fused(), len(default_tracer().events())
    reqs, srv, seen = served(model, params, prompts_of(ids), 8, paged)
    assert logit_error(logits_fn, params, seen) <= ATOL
    assert srv.metrics.preempted == 0
    assert (fused() > before) == (pool == "paged_on")
    steps = [e["args"] for e in default_tracer().events()[start:]
             if e["name"] == "serving/step" and e.get("args")]
    if pool != "contiguous":
        counted = [a for a in steps if a.get("moe_layer_calls")]
        # six routed layers a program; every expert is held: what the
        # kernels ran is what the router made
        assert counted and all(a["moe_layer_calls"] % 6 == 0
                               and a["moe_assignments"] % 4 == 0
                               and "moe_routed_assignments" not in a
                               for a in counted)
        assert any(a.get("moe_bias_reordered") for a in counted)
    assert any(a.get("conv_chunk_tokens") for a in steps)
    assert any(a.get("state_rows") for a in steps)
    assert not any("ssm_chunk_tokens" in a or "kda_chunk_tokens" in a
                   for a in steps)


def _rolled(params):
    """The tree with every ``in_proj``'s three gates one place on: read as
    ``[B ; C ; z]`` they are the model's ``[z ; B ; C]`` (swapping B and z
    alone changes nothing: ``v = B (.) z``)."""
    tree = jax.tree_util.tree_map(lambda x: x, params)
    for blocks in ("dense_blocks", "conv_blocks"):
        mixer = tree[blocks]["block"]["conv"]
        mixer["in_proj"] = {"kernel": jnp.roll(
            mixer["in_proj"]["kernel"], 64, axis=-1)}
    return tree


def _unbiased(params):
    tree = jax.tree_util.tree_map(lambda x: x, params)
    for blocks in ("conv_blocks", "attn_blocks"):
        mlp = tree[blocks]["block"]["mlp"]
        mlp["router_bias"] = jnp.zeros_like(mlp["router_bias"])
    return tree


# (the model computes the published layer; the reference is handed a tree
# or an equation with the fault)
EQUATION_FAULTS = {
    "gates_read_in_another_order": _rolled,
    "choice_ordered_without_the_bias": _unbiased,
}


@pytest.mark.parametrize("fault", sorted(EQUATION_FAULTS))
def test_planted_faults_in_the_equations_leave_the_model(stack, fault):
    cfg, model, params, ids, logits_fn = stack
    got = reference_logits(logits_fn, EQUATION_FAULTS[fault](params), ids[0])
    assert np.abs(got - full_forward(model, params, ids)).max() > 50 * ATOL


def _with_an_activation(conv):
    def wrong(xbc, tail, w, b, valid, silu=True):
        return conv(xbc, tail, w, b, valid, True)
    return wrong


def _tail_one_row_short(conv):
    def wrong(xbc, tail, w, b, valid, silu=True):
        # the oldest carried row is lost: a tail of conv_L_cache - 2 rows
        return conv(xbc, tail.at[:, 0].set(0), w, b, valid, silu)
    return wrong


PROGRAM_FAULTS = {
    "an_activation_in_the_convolution": _with_an_activation,
    "the_tail_one_row_short": _tail_one_row_short,
}


@pytest.mark.parametrize("fault", sorted(PROGRAM_FAULTS))
def test_planted_faults_in_the_program_leave_the_reference(stack, fault,
                                                           monkeypatch):
    """Through a server (the tail is only carried there): the reference
    computes the published layer, the program is handed the fault."""
    cfg, model, params, ids, logits_fn = stack
    monkeypatch.setattr(ss, "causal_conv",
                        PROGRAM_FAULTS[fault](ss.causal_conv))
    # (a chunked prompt that is no multiple of the chunk, and a short one)
    _, _, seen = served(model, params, prompts_of(ids)[2:4], 4, PAGED_OFF)
    assert logit_error(logits_fn, params, seen) > 50 * ATOL


def test_the_attention_layers_norm_q_and_k(stack):
    """``qk_norm`` dropped (the model built without it over the same
    tree) leaves the reference."""
    cfg, model, params, ids, logits_fn = stack
    _, plain = model_of(qk_norm=False)
    got = full_forward(plain, params, ids)
    assert np.abs(got - reference_logits(logits_fn, params, ids[0])).max() \
        > 50 * ATOL


def test_the_chosen_scores_are_divided_by_their_sum_and_1e_6(stack):
    """``+ 1e-6`` only shows where the chosen scores are small: a tree whose
    bias seats every token on experts 0-3 whatever it scores there, under a
    router forty times as steep (a token's scores there are then ~1 or
    ~1e-9). The configured model follows the reference on it; the model
    that divides by ``sum + 1e-20`` (Moonlight's rule) does not."""
    cfg, model, params, ids, logits_fn = stack
    tree = jax.tree_util.tree_map(lambda x: x, params)
    for blocks in ("conv_blocks", "attn_blocks"):
        mlp = tree[blocks]["block"]["mlp"]
        mlp["router"] = 40.0 * mlp["router"]
        mlp["router_bias"] = jnp.zeros_like(mlp["router_bias"]).at[
            :, :4].set(10.0)
    want = reference_logits(logits_fn, tree, ids[0])
    assert np.abs(full_forward(model, tree, ids) - want).max() <= ATOL
    _, other = model_of(topk_norm_eps=1e-20)
    assert np.abs(full_forward(other, tree, ids) - want).max() > 50 * ATOL


def test_check_greedy_holds_greedy_tokens_to_the_reference(stack):
    """Tokens the model's own full forward picks greedily (teacher-forced
    over a fixed sequence: position by position the best logit) pass;
    tokens that are not the best anywhere do not."""
    cfg, model, params, ids, logits_fn = stack
    prompt, rest = ids[0, :40], ids[0, 40:46]
    logits = np.asarray(model.apply(
        {"params": params}, jnp.asarray(ids[:1, :46]),
        method=model.logits)[0])
    # the token picked at position p - 1 + n, fed ids[40 + n] whatever it is
    best = logits[39:45].argmax(-1)
    for n in range(6):
        out = list(rest[:n]) + [int(best[n])]
        check = ref.check_greedy(logits_fn, params, prompt, out, 128, 8,
                                 1e-4)
        assert check["positions"] == n + 1
        assert check["positions_over_rel_tol"] <= n, check   # (the last: 0)
    worst = [int(t) for t in logits[39:45].argmin(-1)]
    assert not ref.check_greedy(logits_fn, params, prompt, worst, 128, 8,
                                1e-4)["ok"]
