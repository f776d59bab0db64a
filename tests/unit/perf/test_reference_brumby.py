"""``perf/reference/brumby.py`` (the attention form, no state) against
``TransformerLM``'s ``brumby`` preset at a small size, float32 on the CPU,
comparing LOGITS: the full forward; prefill then decode through the state
(``InferenceEngine``); and a ``ServingEngine`` run that mixes bucketed
admission, chunked prefill and decode over several slots, against the
reference's one pass over prompt + answer. Three planted faults have to
fail it. On the chip the same reference judges the served tokens at the
published widths."""

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
from perf.reference import brumby as ref  # noqa: E402

# float32 at "highest" on both sides; the program keeps a state of signed
# feature products where the reference adds squares (the sum of a row's
# weights carries ~1e-7 of |phi(q)| |z|, tests/unit/ops/
# test_power_retention.py), two layers deep: logits of size ~3 agree to a
# few 1e-5, and to ~1e-4 where a row's summed weight is small. One bf16
# rounding of one activation is 4e-3 of its size; the planted faults below
# move logits by 1e-2 to 1
ATOL = 5e-4
SIZES = dict(vocab_size=128, max_seq_len=128, n_embd=64, n_layer=2,
             n_head=4, n_kv_head=2, head_size=16, ffn_dim=96,
             rope_theta=1000000)
CHUNK = 16


@pytest.fixture(scope="module")
def stack():
    from deepspeed_tpu.models.transformer_lm import (TransformerLM,
                                                     transformer_config)

    cfg = transformer_config("brumby", dtype=jnp.float32, **SIZES)
    model = TransformerLM(cfg)
    ids = np.random.default_rng(0).integers(1, 128, (2, 96)).astype(np.int32)
    params = jax.jit(lambda: model.init(jax.random.PRNGKey(1),
                                        jnp.asarray(ids[:, :8]),
                                        method=model.logits))()["params"]
    logits_fn = ref.make_forward(
        n_head=cfg.n_head, n_kv_head=cfg.kv_heads, head_dim=cfg.head_dim,
        rope_theta=cfg.rope_theta, eps=cfg.layer_norm_epsilon)
    return cfg, model, params, ids, logits_fn


def reference_logits(logits_fn, params, seq):
    seq = np.asarray(seq, np.int32)
    return np.asarray(logits_fn(params, seq, np.arange(len(seq))))


def test_the_preset_is_the_published_block(stack):
    cfg, model, params, _, _ = stack
    assert cfg.layer_types == ("power_retention",) * 2 and cfg.retention
    assert cfg.qk_norm and not cfg.qkv_bias and not cfg.tie_word_embeddings
    attn = params["blocks"]["block"]["attn"]
    assert attn["g_proj"]["kernel"].shape == (2, 64, 2)
    assert attn["q_norm"]["scale"].shape == (2, 16)
    # half-lives of 16 to 4,096 tokens: g = sigmoid(bias) = 2 ** (-1 / h)
    g = np.asarray(jax.nn.sigmoid(attn["g_proj"]["bias"]))
    half_life = -1.0 / np.log2(g)
    assert (half_life > 15.9).all() and (half_life < 4100).all()
    assert len(np.unique(np.round(half_life, 3))) == half_life.size
    spec = model.kv_cache_spec()
    assert spec.state == (10, 16, 16)
    cache = spec.stacked_cache(3)
    assert set(cache) == {"s", "index"}            # no k, no v
    assert cache["s"].shape == (2, 3, 2, 10, 16, 16)
    assert cache["s"].dtype == jnp.float32
    assert spec.state_bytes_per_row == 2 * 2 * 10 * 16 * 16 * 4


def test_reference_matches_the_full_forward(stack):
    cfg, model, params, ids, logits_fn = stack
    got = model.apply({"params": params}, jnp.asarray(ids), method=model.logits)
    for b in range(2):
        np.testing.assert_allclose(
            np.asarray(got[b]), reference_logits(logits_fn, params, ids[b]),
            atol=ATOL)


def test_reference_is_sharp_where_the_layer_is_new(stack):
    """Without the gate, without the q/k norm or with another rotary base
    the reference leaves the model by far more than ATOL."""
    cfg, model, params, ids, _ = stack
    want = np.asarray(model.apply({"params": params}, jnp.asarray(ids[:1]),
                                  method=model.logits)[0])
    base = dict(n_head=4, n_kv_head=2, head_dim=16, rope_theta=1000000)
    no_gate = jax.tree_util.tree_map(lambda x: x, params)
    no_gate["blocks"]["block"]["attn"]["g_proj"] = {
        "kernel": jnp.zeros((2, 64, 2)), "bias": jnp.full((2, 2), 30.0)}
    no_norm = jax.tree_util.tree_map(lambda x: x, params)
    # (a uniform scale of q cancels in the normalised sum: tilt it)
    no_norm["blocks"]["block"]["attn"]["q_norm"] = {
        "scale": jnp.broadcast_to(jnp.linspace(0.2, 2.0, 16), (2, 16))}
    for change, tree in ((dict(rope_theta=10000.0), params),
                         ({}, no_gate), ({}, no_norm)):
        got = reference_logits(ref.make_forward(**{**base, **change}), tree,
                               ids[0])
        assert np.abs(got - want).max() > 20 * ATOL


def test_prefill_then_decode_through_the_state(stack):
    """``InferenceEngine``'s own programs: a prompt through ``prefill``
    (the chunk kernel from nothing, 40 tokens in chunks), eight tokens
    through ``decode`` (the decode kernel on the carried state), logits of
    every step against the reference's one pass; then ``generate``."""
    cfg, model, params, ids, logits_fn = stack
    eng = ds.init_inference(model=model, model_parameters=params,
                            config={"dtype": "float32"})
    eng._ensure_params(jnp.asarray(ids[:, :2]))
    want = np.stack([reference_logits(logits_fn, params, ids[b, :48])
                     for b in range(2)])
    logits, cache = eng._jit_prefill(eng.params, jnp.asarray(ids[:, :40]))
    np.testing.assert_allclose(np.asarray(logits), want[:, :40], atol=ATOL)
    for t in range(40, 48):
        logits, cache = eng._jit_decode(eng.params, cache,
                                        jnp.asarray(ids[:, t:t + 1]),
                                        jnp.asarray(t, jnp.int32))
        np.testing.assert_allclose(np.asarray(logits)[:, 0], want[:, t],
                                   atol=ATOL)
    out = eng.generate(ids[:, :40], max_new_tokens=6)
    for b in range(2):
        check = ref.check_greedy(logits_fn, params, ids[b, :40],
                                 list(out[b, 40:]), 128, 8, 1e-4)
        assert check["ok"], check


def served(model, params, prompts, new_tokens, plant=None, **kw):
    """Requests through a server of three slots, chunk 16; ``plant(srv)``
    may break it first. Returns the requests."""
    srv = ds.init_serving(model, model_parameters=params,
                          config={"dtype": "float32"}, num_slots=3,
                          prefill_chunk=CHUNK, **kw)
    if plant is not None:
        plant(srv)
    reqs = [srv.submit(p, max_new_tokens=new_tokens) for p in prompts]
    srv.run_until_drained(max_steps=600)
    assert all(r.state is RequestState.FINISHED for r in reqs)
    srv.check_invariants()
    return reqs


def worst_shortfall(logits_fn, params, reqs):
    """Over the requests' generated tokens: the reference's best logit at
    the position less its logit of the served token."""
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


def test_a_mixed_server_run_agrees_with_one_pass_of_the_reference(stack):
    cfg, model, params, ids, logits_fn = stack
    reqs = served(model, params, prompts_of(ids), 10)
    assert worst_shortfall(logits_fn, params, reqs) <= ATOL


@pytest.mark.parametrize("fault", ["state_zeroed_at_a_chunk_boundary",
                                   "gate_dropped",
                                   "decode_updates_a_prefilling_row"])
def test_planted_faults_leave_the_reference(stack, fault, monkeypatch):
    cfg, model, params, ids, logits_fn = stack
    from deepspeed_tpu.ops.attention import power_retention as pr

    def plant(srv):
        eng = srv.engine
        if fault == "state_zeroed_at_a_chunk_boundary":
            chunk = eng.prefill_chunk

            def zeroing(cache, input_ids, slot, start, length, last_idx):
                if int(start) == 2 * CHUNK:     # the third chunk starts
                    store = dict(cache["cache_store"])
                    store["s"] = store["s"].at[:, int(slot)].set(0.0)
                    cache = {"cache_store": store}
                return chunk(cache, input_ids, slot, start, length, last_idx)

            eng.prefill_chunk = zeroing
        elif fault == "decode_updates_a_prefilling_row":
            decode = eng._jit_decode

            def every_row(p, cache, tokens, pos, rows):
                return decode(p, cache, tokens, pos,
                              jnp.arange(rows.shape[0], dtype=jnp.int32))

            eng._jit_decode = every_row

    if fault == "gate_dropped":
        decode = pr.retention_decode
        monkeypatch.setattr(
            pr, "retention_decode",
            lambda q, k, v, log_g, *rest: decode(
                q, k, v, jnp.zeros_like(log_g), *rest))
    reqs = served(model, params, prompts_of(ids), 10, plant)
    assert worst_shortfall(logits_fn, params, reqs) > 20 * ATOL


def test_check_greedy_holds_served_tokens_to_the_logits(stack):
    cfg, model, params, ids, logits_fn = stack
    prompt, seq = ids[0, :20], list(ids[0, :20])
    for _ in range(6):      # the reference's own greedy continuation
        lg = reference_logits(logits_fn, params, seq)
        seq.append(int(np.argmax(lg[-1])))
    out = ref.check_greedy(logits_fn, params, prompt, seq[20:], 128, 8,
                           2.0 ** -5)
    assert out["ok"] and out["positions"] == 6
    assert out["worst_shortfall"] == 0.0
    wrong = list(seq[20:])
    wrong[3] = (wrong[3] + 1) % 128
    assert not ref.check_greedy(logits_fn, params, prompt, wrong, 128, 8,
                                1e-6)["ok"]


def test_configuration_file_holds_the_published_widths_and_the_state():
    import json

    with open(os.path.join(ROOT, "perf", "configs",
                           "brumby-14b-retention.json")) as f:
        cfg = json.load(f)
    published = dict(
        attention_bias=False, head_dim=128, hidden_act="silu",
        hidden_size=5120, intermediate_size=17408,
        max_position_embeddings=32768, max_window_layers=40,
        model_type="brumby", num_attention_heads=40, num_key_value_heads=8,
        rms_norm_eps=1e-06, rope_scaling=None, rope_theta=1000000,
        sliding_window=None, tie_word_embeddings=False,
        use_sliding_window=False, vocab_size=151936)
    for key, value in published.items():
        assert cfg[key] == value, key
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["num_hidden_layers"] == 8 == cfg["model"]["config_kwargs"][
        "n_layer"]
    assert len(cfg["assumed"]) >= 6
    layer = sum(cfg["parameters_a_layer"].values())
    assert layer == 330352904
    assert 8 * layer + cfg["embedding_and_head_parameters"] \
        == cfg["parameters"] == 4198652992
    # the state: what the program allocates at these sizes
    from deepspeed_tpu.models.transformer_lm import (TransformerLM,
                                                     transformer_config)

    kw = cfg["model"]["config_kwargs"]
    spec = TransformerLM(transformer_config("brumby", **kw)).kv_cache_spec()
    state = cfg["state"]
    assert list(spec.state) == state["shape_a_kv_head"]
    assert spec.state_bytes_per_row == state["bytes_a_slot"]
    assert state["bytes_a_slot"] * cfg["server"]["num_slots"] \
        == state["bytes_resident"]
    assert state["feature_rows"] <= 8704 and state["dtype"] == "float32"
    assert "paged_kv" not in cfg["server"]
