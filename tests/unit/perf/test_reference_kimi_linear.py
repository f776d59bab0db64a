"""``perf/reference/kimi_linear.py`` (the delta rule's recurrence itself, one
position at a time, latent attention expanded, every held expert as a
dense masked sum, no cache) against ``TransformerLM``'s ``kimi_linear``
preset at a small size, float32 on the CPU, comparing LOGITS: the full
forward; servers that mix bucketed admission (right padding), chunked
prefill (prompts that are no multiple of the chunk, which is one page: every
chunk boundary is a page boundary), a chunk beside running slots as ONE
program, and decode over re-seated slots, on the page pool (the dense
composition and the kernels in place) and on the contiguous pool, against
the reference's one pass over prompt + answer. Planted faults have to fail
it, and a state held in bfloat16 has to fail the pool's audit. On the chip
the same reference judges the served tokens at the published widths."""

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

from deepspeed_tpu.ops import kda  # noqa: E402
from perf.reference import kimi_linear as ref  # noqa: E402
# (the helpers that drive a server and tap the logits it samples from)
from test_reference_granite import (PAGED_OFF, PAGED_ON,  # noqa: E402
                                    logit_error, prompts_of,
                                    reference_logits, running_server,
                                    narrow_share, served)

# float32 at "highest" on both sides. The program runs a prompt through the
# chunk form (an inverse and five products a block) where the reference
# steps the state a position at a time, reads the latent cache absorbed
# where the reference expands K and V, and sums a token's held experts in
# another order: eight layers deep, under a routed sum scaled 2.446, logits
# of size ~4 agree to 1.4e-5 on every pool and 3e-5 in the full forward
# (measured, PR 50). 2e-4 is 7 x that. The planted faults move a logit by
# 0.02 and more (the smallest: beta a half for every token), each
# has to pass 50 x the tolerance
ATOL = 2e-4
SIZES = dict(vocab_size=512, max_seq_len=128, n_embd=64, n_head=4,
             kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
             v_head_dim=16, ffn_dim=32, n_experts=16, experts_per_token=4,
             experts_held=4, n_shared_experts=1, routed_scaling_factor=2.446,
             dense_ffn_dim=96, kda_n_heads=4, kda_d_head=16)
PATTERN = ("kda", "kda", "kda", "attention") * 2


def build(**change):
    from deepspeed_tpu.models.transformer_lm import (TransformerLM,
                                                     transformer_config)

    cfg = transformer_config(
        "kimi_linear", dtype=jnp.float32, n_layer=len(PATTERN),
        layer_types=PATTERN,
        mlp_layer_types=["dense"] + ["sparse"] * (len(PATTERN) - 1),
        **{**SIZES, **change})
    model = TransformerLM(cfg)
    ids = np.random.default_rng(0).integers(1, 512, (2, 96)).astype(np.int32)
    params = jax.jit(lambda: model.init(jax.random.PRNGKey(1),
                                        jnp.asarray(ids[:, :8]),
                                        method=model.logits))()["params"]
    return cfg, model, params, ids, forward_of(cfg)


def forward_of(cfg, **change):
    return ref.make_forward(**{**dict(
        layer_types=cfg.layer_types, n_head=cfg.n_head,
        kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        kda_n_heads=cfg.kda_n_heads, kda_d_head=cfg.kda_d_head,
        experts_per_token=cfg.experts_per_token,
        routed_scaling_factor=cfg.routed_scaling_factor,
        first_k_dense=cfg.first_k_dense, norm_topk_prob=cfg.norm_topk_prob,
        eps=cfg.layer_norm_epsilon), **change})


@pytest.fixture(scope="module")
def stack():
    return build()


def test_the_preset_is_the_published_block(stack):
    cfg, model, params, _, _ = stack
    assert cfg.kda and cfg.hybrid == "kda" and not cfg.mamba
    assert cfg.pos_emb == "none" and cfg.latent == 40
    assert not cfg.tie_word_embeddings and "lm_head" in params
    assert cfg.hybrid_period == (3, 0, 2) and cfg.first_k_dense == 1
    # the dense layer is a KDA layer of its own leaf; five more behind it
    assert set(params) == {"attn_blocks", "dense_blocks", "kda_blocks",
                           "experts", "embed_tokens", "lm_head", "ln_f"}
    lead = params["dense_blocks"]["block"]
    assert set(lead["mlp"]) == {"gate_proj", "up_proj", "down_proj"}
    assert lead["mlp"]["gate_proj"]["kernel"].shape == (1, 64, 96)
    mixer = params["kda_blocks"]["block"]["kda"]
    assert mixer["qkv_proj"]["kernel"].shape == (5, 64, 3 * 64)
    assert mixer["conv_w"].shape == (5, 4, 3 * 64) and "conv_b" not in mixer
    assert mixer["f_a_proj"]["kernel"].shape == (5, 64, 16)
    assert mixer["f_b_proj"]["kernel"].shape == (5, 16, 64)
    assert mixer["b_proj"]["kernel"].shape == (5, 64, 4)
    assert mixer["o_norm"].shape == (5, 16)
    a = np.exp(np.asarray(mixer["A_log"]))
    assert a.shape == (5, 4) and (a >= 1).all() and (a <= 16).all()
    # the router knows 16, the leaves hold 4, over the 7 routed layers
    assert params["experts"]["gate_proj"].shape == (7, 4, 64, 32)
    for leaf in ("kda_blocks", "attn_blocks"):
        mlp = params[leaf]["block"]["mlp"]
        assert mlp["router"].shape[1:] == (64, 16)
        assert mlp["shared_up_proj"]["kernel"].shape[1:] == (64, 32)
    spec = model.kv_cache_spec()
    assert spec.kinds == ("kda", "latent", "routed")
    assert spec.state_group == (6, (("s", (4, 16, 16), jnp.float32),
                                    ("conv", (3 * 192,), jnp.float32)))
    assert spec.n_layer == 8 and spec.kv_layers == 2 and spec.latent == 40
    cache = spec.stacked_cache(3)
    assert set(cache) == {"s", "conv", "c", "index"}
    assert cache["c"].shape == (2, 3, 40, 128)
    paged = spec.paged_cache(24, 16, num_slots=3)
    assert set(paged) == {"s", "conv", "c"}
    assert paged["c"].shape == (2, 24, 40, 128)
    assert paged["s"].shape == (6, 3, 4, 16, 16)


def test_reference_matches_the_full_forward(stack):
    """``logits`` without a cache: the chunk form in ``jax.numpy`` over the
    whole sequence, latent attention expanded."""
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
    """Prefill, then decode, through each pool: bucketed admission with
    right padding, chunked prefill of prompts that are no multiple of the
    chunk (three and six chunks, each boundary a page's), six requests over
    three slots; every generated position against the reference's full
    forward. On the kernels' pool a chunk rides beside the running slots as
    ONE program, whose rows read what the separate programs would."""
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
        counted = [a for a in steps if a.get("moe_routed_assignments")]
        assert counted and all(
            0 <= a["moe_assignments"] <= a["moe_routed_assignments"]
            for a in counted)
        # 4 of 16 experts are held: about a quarter of what the router made
        share = sum(a["moe_assignments"] for a in counted) \
            / sum(a["moe_routed_assignments"] for a in counted)
        assert 0.15 < share < 0.40
    assert any(a.get("kda_chunk_tokens") for a in steps)
    assert any(a.get("latent_tokens_read") for a in steps)


def test_a_state_held_in_bfloat16_fails_the_audit(stack, monkeypatch):
    """``perf/tools/kimi_limits.py``'s ``state_bfloat16`` arm, as it wraps
    the two kernels on the chip: a server that HELD its state in bfloat16
    is refused by the pool's audit: every word of the rows that ran carries
    nothing below bfloat16's mantissa."""
    from deepspeed_tpu.serving.resilience import InvariantViolation
    from perf.tools.kimi_limits import held_in_bfloat16

    cfg, model, params, ids, _ = stack
    # (the configured server's audit passes at the end of every mixed run
    # above: ``served`` checks the invariants)
    monkeypatch.setattr(kda, "kda_decode", held_in_bfloat16(kda.kda_decode))
    monkeypatch.setattr(kda, "kda_chunk", held_in_bfloat16(kda.kda_chunk))
    srv, reqs = running_server(model, params, ids)
    assert narrow_share(srv)[[r.slot for r in reqs]].min() == 1.0
    with pytest.raises(InvariantViolation, match="narrower than the spec"):
        srv.check_invariants()


def _rotated(theta=10000.0):
    """The reference's attention with the 'rope' columns ROTATED (what
    Moonlight does and this model does not)."""
    def turn(x):
        T, d = x.shape[0], x.shape[-1]
        inv = theta ** (-2.0 * jnp.arange(d // 2, dtype=jnp.float32) / d)
        ang = (jnp.arange(T, dtype=jnp.float32)[:, None] * inv).reshape(
            (T,) + (1,) * (x.ndim - 2) + (d // 2,))
        cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)
        sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)
        return x * cos + jnp.concatenate(
            [-x[..., d // 2:], x[..., :d // 2]], -1) * sin
    return turn


FAULTS = {
    # (what is changed in the parameter tree the reference is given, or in
    # the reference's arguments: the model computes the published layer)
    "every_expert_held": lambda cfg, p: (p, {}, 16),
    "beta_is_a_half": lambda cfg, p: (_with(p, "b_proj", 0.0), {}, None),
    "unscaled_routed_weights": lambda cfg, p: (
        p, dict(routed_scaling_factor=1.0), None),
}


def _with(params, leaf, value):
    tree = jax.tree_util.tree_map(lambda x: x, params)
    for blocks in ("dense_blocks", "kda_blocks"):
        mixer = tree[blocks]["block"]["kda"]
        mixer[leaf] = {"kernel": jnp.full_like(mixer[leaf]["kernel"], value)}
    return tree


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_faults_in_the_equations_leave_the_model(stack, fault):
    """The reference with the fault against the model's full forward: each
    leaves it by far more than the tolerance. ``every_expert_held``: the
    same seeds with all 16 experts here (the model that does NOT cut its
    share) against the reference of the held four."""
    cfg, model, params, ids, logits_fn = stack
    tree, change, held = FAULTS[fault](cfg, params)
    if held:
        _, model, tree, _, _ = build(experts_held=held)
        want = np.asarray(model.apply({"params": tree}, jnp.asarray(ids[:1]),
                                      method=model.logits)[0])
        cut = jax.tree_util.tree_map(lambda x: x, tree)
        cut["experts"] = {k: v[:, :4] for k, v in tree["experts"].items()}
        got = reference_logits(logits_fn, cut, ids[0])
    else:
        want = np.asarray(model.apply(
            {"params": params}, jnp.asarray(ids[:1]), method=model.logits)[0])
        got = reference_logits(forward_of(cfg, **change), tree, ids[0])
    assert np.abs(got - want).max() > 50 * ATOL


def test_the_latent_layers_rotate_nothing(stack, monkeypatch):
    """``mla_use_nope``: the reference with its 64 'rope' columns rotated
    (Moonlight's layer) leaves the model, and the model built with
    ``pos_emb`` "rotary" leaves the reference."""
    cfg, model, params, ids, logits_fn = stack
    _, rotary, _, _, _ = build(pos_emb="rotary")
    got = np.asarray(rotary.apply({"params": params}, jnp.asarray(ids[:1]),
                                  method=rotary.logits)[0])
    assert np.abs(got - reference_logits(logits_fn, params, ids[0])).max() \
        > 50 * ATOL


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
