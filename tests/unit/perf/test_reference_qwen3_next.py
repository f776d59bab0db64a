"""``perf/reference/qwen3_next.py`` (the delta rule's recurrence itself under
one decay a head, one position at a time; gated GQA with the ``1 + w`` norm
on q and k and a quarter-head rotary; every held expert as a dense masked
sum beside a sigmoid-gated shared expert; no cache) held from both sides at
a small size, float32 on the CPU, comparing LOGITS: against a second,
independent writing in NumPy (loops a head and a token, float64) of one
Gated DeltaNet layer, one gated attention layer and their FFNs; and against
``TransformerLM``'s ``qwen3_next`` preset: the full forward, and a server
on the page pool with the kernels in place that mixes bucketed admission
(right padding), chunked prefill (prompts that are no multiple of the
chunk), a chunk beside running slots as ONE program, and decode over
re-seated slots (a new request's state starts at zero), against the
reference's one pass over prompt + answer. Planted faults have to fail it,
and a state held in bfloat16 has to fail the pool's audit. On the chip the
same reference judges the served tokens at the published widths."""

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
from perf.reference import qwen3_next as ref  # noqa: E402
# (the helpers that drive a server and tap the logits it samples from)
from test_reference_granite import (PAGED_ON, logit_error,  # noqa: E402
                                    narrow_share, prompts_of,
                                    reference_logits, running_server, served)

# float32 at "highest" on both sides. The program runs a prompt through the
# chunk form (an inverse and five products a block) where the reference
# steps the state a position at a time, reads K/V through the pages, and
# sums a token's held experts in another order: eight layers deep, logits
# of size ~4 agree to ~3e-5 (measured, PR 60). 2e-4 is 7 x that. The
# planted faults move a logit by 0.02 and more; each has to pass 50 x the
# tolerance
ATOL = 2e-4
SIZES = dict(vocab_size=512, max_seq_len=128, n_embd=64, n_head=4,
             n_kv_head=2, head_size=32, ffn_dim=32, n_experts=16,
             experts_per_token=4, experts_held=4, gdn_n_key_heads=2,
             gdn_n_value_heads=4, gdn_d_head=16)
PATTERN = ("linear_attention",) * 3 + ("full_attention",)


def build(pattern=PATTERN * 2, **change):
    from deepspeed_tpu.models.transformer_lm import (TransformerLM,
                                                     transformer_config)

    cfg = transformer_config("qwen3_next", dtype=jnp.float32,
                             n_layer=len(pattern), layer_types=pattern,
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
        kv_heads=cfg.kv_heads, head_dim=cfg.head_dim,
        rotary_pct=cfg.rotary_pct, rope_theta=cfg.rope_theta,
        gdn_n_key_heads=cfg.gdn_n_key_heads,
        gdn_n_value_heads=cfg.gdn_n_value_heads,
        gdn_d_head=cfg.gdn_d_head,
        experts_per_token=cfg.experts_per_token,
        norm_topk_prob=cfg.norm_topk_prob, eps=cfg.layer_norm_epsilon),
        **change})


@pytest.fixture(scope="module")
def stack():
    return build()


def test_the_preset_is_the_published_block(stack):
    cfg, model, params, _, _ = stack
    assert cfg.hybrid == "gdn" and cfg.hybrid_period == (3, 0, 2)
    assert cfg.layer_types == ("gdn", "gdn", "gdn", "attention") * 2
    assert (cfg.pos_emb, cfg.rotary_pct, cfg.norm) == ("rotary", 0.25,
                                                       "rmsnorm1p")
    assert cfg.qk_norm and cfg.attn_output_gate and cfg.shared_expert_gate
    assert not cfg.tie_word_embeddings and "lm_head" in params
    assert cfg.first_k_dense == 0 and "dense_blocks" not in params
    gdn = params["gdn_blocks"]["block"]["gdn"]
    # [q (32) ; k (32) ; v (64) ; z (64)] and [b (4) ; a (4)]: the
    # published projections' columns, plainly, as two leaves; ONE
    # convolution over [q ; k ; v]; a decay and a bias a VALUE head
    assert gdn["qkvz_proj"]["kernel"].shape == (6, 64, 32 + 32 + 64 + 64)
    assert gdn["ba_proj"]["kernel"].shape == (6, 64, 8)
    assert gdn["conv_w"].shape == (6, 4, 128)
    assert gdn["A_log"].shape == gdn["dt_bias"].shape == (6, 4)
    assert gdn["o_norm"].shape == (6, 16) and (gdn["o_norm"] == 1).all()
    assert gdn["o_proj"]["kernel"].shape == (6, 64, 64)
    a = np.exp(np.asarray(gdn["A_log"]))
    dt = np.asarray(jax.nn.softplus(gdn["dt_bias"]))
    assert (a >= 1).all() and (a <= 16).all()
    assert (dt >= 1e-3 * 0.999).all() and (dt <= 1e-1 * 1.001).all()
    attn = params["attn_blocks"]["block"]["attn"]
    assert attn["q_proj"]["kernel"].shape == (2, 64, 128)
    assert attn["z_proj"]["kernel"].shape == (2, 64, 128)
    assert attn["k_proj"]["kernel"].shape == (2, 64, 64)
    assert attn["q_norm"]["scale"].shape == (2, 32)
    # a seeded 1 + w differs from w and from 1
    for scale in (attn["q_norm"]["scale"], params["ln_f"]["scale"],
                  params["gdn_blocks"]["block"]["ln_1"]["scale"]):
        assert 0.03 < float(jnp.std(scale)) < 0.3
        assert abs(float(jnp.mean(scale))) < 0.1
    mlp = params["attn_blocks"]["block"]["mlp"]
    assert mlp["router"].shape == (2, 64, 16) and "router_bias" not in mlp
    assert mlp["shared_gate_w"].shape == (2, 64)
    assert params["experts"]["gate_proj"].shape == (8, 4, 64, 32)
    spec = model.kv_cache_spec()
    assert spec.kinds == ("gdn", "routed")
    assert spec.state_group == (6, (("s", (4, 16, 16), jnp.float32),
                                    ("conv", (3 * 128,), jnp.float32)))
    assert spec.kv_layers == 2 and spec.rep == 2
    cache = spec.paged_cache(8, 16, num_slots=3)
    assert set(cache) == {"s", "conv", "k", "v"}
    assert cache["s"].shape == (6, 3, 4, 16, 16)
    assert cache["k"].shape == (2, 8, 2, 32, 128)


# ---------------------------------------------------------------------------
# the second writing: NumPy, float64, a loop a head and a token
# ---------------------------------------------------------------------------
def _np(tree):
    return jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), tree)


def _norm1p(x, w, eps):
    return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * (1 + w)


def _silu(x):
    return x / (1 + np.exp(-x))


def _sigmoid(x):
    return 1 / (1 + np.exp(-x))


def np_gdn(p, x, cfg):
    Hk, H, d = cfg.gdn_n_key_heads, cfg.gdn_n_value_heads, cfg.gdn_d_head
    T, eps = x.shape[0], cfg.layer_norm_epsilon
    h = _norm1p(x, p["ln_1"]["scale"], eps)
    a = p["gdn"]
    qkvz = h @ a["qkvz_proj"]["kernel"]
    ch = (2 * Hk + H) * d
    mixed = np.zeros((T, ch))
    for t in range(T):              # the causal convolution, a tap at a time
        for j in range(4):
            if t - 3 + j >= 0:
                mixed[t] += a["conv_w"][j] * qkvz[t - 3 + j, :ch]
    mixed = _silu(mixed)
    z = qkvz[:, ch:]
    ba = h @ a["ba_proj"]["kernel"]
    out = np.zeros((T, H * d))
    for hv in range(H):
        hk = hv // (H // Hk)        # the key head this value head reads
        S = np.zeros((d, d))
        for t in range(T):
            q = mixed[t, hk * d:(hk + 1) * d]
            k = mixed[t, Hk * d + hk * d:Hk * d + (hk + 1) * d]
            v = mixed[t, 2 * Hk * d + hv * d:2 * Hk * d + (hv + 1) * d]
            q = q / np.sqrt(np.sum(q * q) + 1e-6) / np.sqrt(d)
            k = k / np.sqrt(np.sum(k * k) + 1e-6)
            beta = _sigmoid(ba[t, hv])
            g = -np.exp(a["A_log"][hv]) * np.log1p(
                np.exp(ba[t, H + hv] + a["dt_bias"][hv]))
            S = np.exp(g) * S
            u = beta * (v - S.T @ k)
            S = S + np.outer(k, u)
            o = S.T @ q
            o = o / np.sqrt(np.mean(o * o) + eps) * a["o_norm"]
            out[t, hv * d:(hv + 1) * d] = o * _silu(z[t, hv * d:(hv + 1) * d])
    return x + out @ a["o_proj"]["kernel"]


def np_attention(p, x, cfg):
    H, KV, D = cfg.n_head, cfg.kv_heads, cfg.head_dim
    T, eps = x.shape[0], cfg.layer_norm_epsilon
    rd = int(cfg.rotary_pct * D)
    h = _norm1p(x, p["ln_1"]["scale"], eps)
    a = p["attn"]
    q = (h @ a["q_proj"]["kernel"]).reshape(T, H, D)
    gate = h @ a["z_proj"]["kernel"]
    k = (h @ a["k_proj"]["kernel"]).reshape(T, KV, D)
    v = (h @ a["v_proj"]["kernel"]).reshape(T, KV, D)
    q = _norm1p(q, a["q_norm"]["scale"], eps)
    k = _norm1p(k, a["k_norm"]["scale"], eps)

    def rotate(vec, pos):
        out = vec.copy()
        for i in range(rd // 2):
            ang = pos * cfg.rope_theta ** (-2.0 * i / rd)
            lo, hi = vec[i], vec[i + rd // 2]
            out[i] = lo * np.cos(ang) - hi * np.sin(ang)
            out[i + rd // 2] = hi * np.cos(ang) + lo * np.sin(ang)
        return out

    for t in range(T):
        for j in range(H):
            q[t, j] = rotate(q[t, j], t)
        for j in range(KV):
            k[t, j] = rotate(k[t, j], t)
    out = np.zeros((T, H, D))
    for j in range(H):
        kv = j // (H // KV)
        for t in range(T):
            s = np.array([q[t, j] @ k[u, kv] for u in range(t + 1)]) \
                / np.sqrt(D)
            pr = np.exp(s - s.max())
            pr /= pr.sum()
            out[t, j] = sum(pr[u] * v[u, kv] for u in range(t + 1))
    y = out.reshape(T, H * D) * _sigmoid(gate)
    return x + y @ a["o_proj"]["kernel"]


def np_ffn(p, experts, x, cfg):
    m, eps = p["mlp"], cfg.layer_norm_epsilon
    held = experts["gate_proj"].shape[0]
    out = np.zeros_like(x)
    for t in range(x.shape[0]):
        h = _norm1p(x[t], p["ln_2"]["scale"], eps)
        logit = h @ m["router"]
        prob = np.exp(logit - logit.max())
        prob /= prob.sum()
        chosen = np.argsort(-prob, kind="stable")[:cfg.experts_per_token]
        total = prob[chosen].sum()
        y = np.zeros_like(h)
        for e in chosen:
            if e < held:            # the absent experts' part is left out
                act = _silu(h @ experts["gate_proj"][e]) \
                    * (h @ experts["up_proj"][e])
                y += prob[e] / total * (act @ experts["down_proj"][e])
        shared = (_silu(h @ m["shared_gate_proj"]["kernel"])
                  * (h @ m["shared_up_proj"]["kernel"])) \
            @ m["shared_down_proj"]["kernel"]
        out[t] = y + _sigmoid(h @ m["shared_gate_w"]) * shared
    return x + out


def np_logits(params, ids, cfg):
    """The whole toy model of ONE DeltaNet layer and ONE attention layer."""
    P = _np(params)
    x = P["embed_tokens"]["embedding"][ids]
    layer = lambda blocks, i: jax.tree_util.tree_map(   # noqa: E731
        lambda a: a[i], blocks["block"])
    expert = lambda i: {k: v[i] for k, v in P["experts"].items()}  # noqa
    g, a = layer(P["gdn_blocks"], 0), layer(P["attn_blocks"], 0)
    x = np_ffn(g, expert(0), np_gdn(g, x, cfg), cfg)
    x = np_ffn(a, expert(1), np_attention(a, x, cfg), cfg)
    return _norm1p(x, P["ln_f"]["scale"], cfg.layer_norm_epsilon) \
        @ P["lm_head"]["kernel"]


def test_the_reference_is_a_second_writing_of_each_layer():
    """One DeltaNet layer, one gated attention layer and their two FFNs
    (4 of 16 experts held, a gated shared expert), 14 tokens: the
    reference's logits against the NumPy loops', float32 against float64."""
    cfg, model, params, ids, logits_fn = build(
        ("linear_attention", "full_attention"))
    seq = ids[0, :14]
    want = np_logits(params, seq, cfg)
    got = reference_logits(logits_fn, params, seq)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # (and the choice was not trivial: some chosen experts are not held)
    assert np.abs(want).max() > 0.5


# ---------------------------------------------------------------------------
# the program against the reference
# ---------------------------------------------------------------------------
def test_reference_matches_the_full_forward(stack):
    cfg, model, params, ids, logits_fn = stack
    got = jax.jit(lambda p, i: model.apply({"params": p}, i,
                                           method=model.logits))(
        params, jnp.asarray(ids))
    for b in range(2):
        np.testing.assert_allclose(
            np.asarray(got[b]), reference_logits(logits_fn, params, ids[b]),
            atol=ATOL)


def test_a_mixed_server_run_agrees_with_one_pass_of_the_reference(stack):
    """Prefill, then decode, through the page pool with the kernels in
    place: bucketed admission with right padding, chunked prefill of
    prompts that are no multiple of the chunk (three and six chunks), six
    requests over three slots (every slot is retired and seated again: a
    new request's state starts at zero), a chunk beside the running slots
    as ONE program; every generated position against the reference's full
    forward. The step's spans count the DeltaNet layers' rows and tokens
    and the held experts' share."""
    from deepspeed_tpu.telemetry import default_tracer

    cfg, model, params, ids, logits_fn = stack
    start = len(default_tracer().events())
    reqs, srv, seen = served(model, params, prompts_of(ids), 8, PAGED_ON)
    assert logit_error(logits_fn, params, seen) <= ATOL
    assert srv.metrics.preempted == 0
    events = default_tracer().events()[start:]
    assert any(e["name"] == "serving/enqueue"
               and (e.get("args") or {}).get("program") == "chunk_decode"
               for e in events)
    steps = [e["args"] for e in events
             if e["name"] == "serving/step" and e.get("args")]
    counted = [a for a in steps if a.get("moe_routed_assignments")]
    assert counted and all(
        0 <= a["moe_assignments"] <= a["moe_routed_assignments"]
        for a in counted)
    # 4 of 16 experts are held: about a quarter of what the router made
    share = sum(a["moe_assignments"] for a in counted) \
        / sum(a["moe_routed_assignments"] for a in counted)
    assert 0.15 < share < 0.40
    assert max(a["moe_experts_touched"] / a["moe_layer_calls"]
               for a in counted) <= 4
    assert any(a.get("gdn_chunk_tokens") for a in steps)
    assert any(a.get("state_rows") for a in steps)
    assert not any("kda_chunk_tokens" in a for a in steps)


def test_a_state_held_in_bfloat16_fails_the_audit(stack, monkeypatch):
    """``perf/tools/qwen3_next_limits.py``'s ``state_bfloat16`` arm, as it
    wraps the two kernels on the chip: a server that HELD its state in
    bfloat16 is refused by the pool's audit."""
    from deepspeed_tpu.serving.resilience import InvariantViolation
    from perf.tools.qwen3_next_limits import held_in_bfloat16

    cfg, model, params, ids, _ = stack
    monkeypatch.setattr(kda, "kda_decode", held_in_bfloat16(kda.kda_decode))
    monkeypatch.setattr(kda, "kda_chunk", held_in_bfloat16(kda.kda_chunk))
    srv, reqs = running_server(model, params, ids)
    assert narrow_share(srv)[[r.slot for r in reqs]].min() == 1.0
    with pytest.raises(InvariantViolation, match="narrower than the spec"):
        srv.check_invariants()


def _with(params, path, change):
    """``params`` with the leaf at ``path`` replaced by ``change(leaf)``."""
    def walk(tree, keys):
        if not keys:
            return change(tree)
        return {k: walk(v, keys[1:]) if k == keys[0] else v
                for k, v in tree.items()}
    return walk(params, path)


# what a wrong reading of the published block would compute: each leaves
# the reference (whose equations are the issue's) by far more than ATOL
FAULTS = {
    # the norm's weight read as w, not 1 + w (every 1 + w norm)
    "plain_norm_weight": lambda cfg: dict(norm="rmsnorm"),
    # the shared expert without its sigmoid gate
    "ungated_shared_expert": lambda cfg: dict(shared_expert_gate=False),
    # the attention's output without its gate
    "ungated_attention": lambda cfg: dict(attn_output_gate=False),
    # the rotary over the whole head
    "whole_head_rotary": lambda cfg: dict(rotary_pct=1.0),
    # the chosen probabilities not renormalised
    "unnormalised_top_k": lambda cfg: dict(norm_topk_prob=False),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_faults_in_the_equations_leave_the_model(stack, fault):
    """The PROGRAM built with one rule misread, over the same parameters,
    against the reference: the comparison sees each."""
    import dataclasses

    from deepspeed_tpu.models.transformer_lm import TransformerLM

    cfg, model, params, ids, logits_fn = stack
    wrong = TransformerLM(dataclasses.replace(cfg, **FAULTS[fault](cfg)))
    if fault in ("ungated_attention",):
        # (the gate's projection is then no parameter of the model)
        params = _with(params, ("attn_blocks", "block", "attn"),
                       lambda a: {k: v for k, v in a.items()
                                  if k != "z_proj"})
    if fault == "ungated_shared_expert":
        for leaf in ("gdn_blocks", "attn_blocks"):
            params = _with(params, (leaf, "block", "mlp"),
                           lambda m: {k: v for k, v in m.items()
                                      if k != "shared_gate_w"})
    got = jax.jit(lambda p, i: wrong.apply({"params": p}, i,
                                           method=wrong.logits))(
        params, jnp.asarray(ids[:1, :48]))
    want = reference_logits(logits_fn, stack[2], ids[0, :48])
    assert np.abs(np.asarray(got[0]) - want).max() > 50 * ATOL


def test_check_greedy_holds_greedy_tokens_to_the_reference(stack):
    """The judge of the chip's runs at the toy size: the program's greedy
    tokens pass; the same tokens with a few replaced by another id fail by
    the share of positions beyond ``rel_tol``."""
    cfg, model, params, ids, logits_fn = stack
    prompt = ids[0, :20]
    seq = list(prompt)
    step = jax.jit(lambda p, i: model.apply({"params": p}, i,
                                            method=model.logits))
    padded = np.zeros((1, 48), np.int32)
    for n in range(12):
        padded[0, :len(seq)] = seq
        seq.append(int(np.argmax(np.asarray(
            step(params, jnp.asarray(padded)))[0, len(seq) - 1])))
    out = seq[20:]
    good = ref.check_greedy(logits_fn, params, prompt, out, 64, 16, 2 ** -5)
    assert good["ok"] and good["positions"] == 12
    assert good["positions_over_rel_tol"] == 0
    bad = [(t + 7) % 512 if n % 2 else t for n, t in enumerate(out)]
    worse = ref.check_greedy(logits_fn, params, prompt, bad, 64, 16, 2 ** -5)
    assert not worse["ok"]
    assert worse["positions_over_rel_tol"] > worse["positions_over_allowed"]
