"""``perf/reference/granite_hybrid.py`` (the recurrence itself, one position
at a time, no cache) against ``TransformerLM``'s ``granite-hybrid`` preset at
a small size, float32 on the CPU, comparing LOGITS: the full forward;
servers that mix bucketed admission (right padding), chunked prefill (a
prompt that is no multiple of the chunk) and decode over re-seated slots, on
the page pool (the dense composition and the kernels in place) and on the
contiguous pool, against the reference's one pass over prompt + answer. Six
planted faults have to fail it. On the chip the same reference judges the
served tokens at the published widths."""

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
from deepspeed_tpu.ops import state_space as ss  # noqa: E402
from deepspeed_tpu.serving import RequestState  # noqa: E402
from perf.reference import granite_hybrid as ref  # noqa: E402

# float32 at "highest" on both sides. The program runs a prompt through the
# chunked form (sums of exp(L_t - L_s) products a block) where the reference
# steps the state a position at a time: eight layers deep, logits of size ~1
# agree to 3e-7 on every pool and in the full forward (measured, PR 47).
# 1e-5 is 30 x that. The planted faults move a logit by 0.012 (a state
# advanced over a chunk's padding), 0.037 (1 / sqrt(d) for the
# attention_multiplier), 0.10 (the gate after the norm), 0.19 (the
# convolution's tail taken at the padded end) and 0.72 (the
# residual_multiplier dropped): the smallest is 1,200 x the tolerance, and
# each has to pass 50 x it
ATOL = 1e-5
CHUNK = 16
SIZES = dict(vocab_size=512, max_seq_len=128, n_embd=64, n_head=4,
             n_kv_head=2, ffn_dim=96, mamba_n_heads=8, mamba_d_head=16,
             mamba_d_state=16, embedding_multiplier=12.0,
             attention_multiplier=0.0625, residual_multiplier=0.22,
             logits_scaling=8.0)
PATTERNS = {
    # two periods of a toy pattern, and one of the published period
    "toy": ("mamba", "mamba", "attention", "mamba") * 2,
    "published": ("mamba",) * 5 + ("attention",) + ("mamba",) * 4,
}
PAGED_OFF = {"kernel": "off", "page_size": CHUNK, "num_pages": 24,
             "prefix_cache": False}
PAGED_ON = dict(PAGED_OFF, kernel="on")


def build(pattern):
    from deepspeed_tpu.models.transformer_lm import (TransformerLM,
                                                     transformer_config)

    cfg = transformer_config("granite-hybrid", dtype=jnp.float32,
                             n_layer=len(pattern), layer_types=pattern,
                             **SIZES)
    model = TransformerLM(cfg)
    ids = np.random.default_rng(0).integers(1, 512, (2, 96)).astype(np.int32)
    params = jax.jit(lambda: model.init(jax.random.PRNGKey(1),
                                        jnp.asarray(ids[:, :8]),
                                        method=model.logits))()["params"]
    return cfg, model, params, ids, forward_of(cfg)


def forward_of(cfg, **change):
    return ref.make_forward(**{**dict(
        layer_types=cfg.layer_types, n_head=cfg.n_head,
        n_kv_head=cfg.kv_heads, head_dim=cfg.head_dim,
        mamba_n_heads=cfg.mamba_n_heads, mamba_d_head=cfg.mamba_d_head,
        mamba_d_state=cfg.mamba_d_state,
        embedding_multiplier=cfg.embedding_multiplier,
        attention_multiplier=cfg.attention_multiplier,
        residual_multiplier=cfg.residual_multiplier,
        logits_scaling=cfg.logits_scaling, eps=cfg.layer_norm_epsilon),
        **change})


@pytest.fixture(scope="module")
def stack():
    return build(PATTERNS["toy"])


def reference_logits(logits_fn, params, seq):
    seq = np.asarray(seq, np.int32)
    return np.asarray(logits_fn(params, seq, np.arange(len(seq))))


def test_the_preset_is_the_published_block(stack):
    cfg, model, params, _, _ = stack
    assert cfg.mamba and not cfg.retention and cfg.pos_emb == "none"
    assert cfg.tie_word_embeddings and "lm_head" not in params
    assert cfg.hybrid_period == (2, 1, 2)
    mamba = params["mamba_blocks"]["block"]["mamba"]
    # [z (128) ; xBC (128 + 2 x 16)] and dt (8): the published W_in's
    # columns, in its order, as two leaves
    assert mamba["in_proj"]["kernel"].shape == (6, 64, 128 + 160)
    assert mamba["dt_proj"]["kernel"].shape == (6, 64, 8)
    assert mamba["conv_w"].shape == (6, 4, 160)
    assert mamba["conv_b"].shape == (6, 160)
    assert mamba["norm"].shape == (6, 128)
    assert mamba["out_proj"]["kernel"].shape == (6, 128, 64)
    # A in [1, 16], dt in [1e-3, 1e-1] after the softplus, D ones: a seeded
    # state neither vanishes in a token nor never decays
    a = np.exp(np.asarray(mamba["A_log"]))
    dt = np.asarray(jax.nn.softplus(mamba["dt_bias"]))
    assert a.shape == (6, 8) and (a >= 1).all() and (a <= 16).all()
    assert (dt >= 1e-3 * 0.999).all() and (dt <= 1e-1 * 1.001).all()
    assert len(np.unique(np.round(a, 4))) == a.size
    assert (np.asarray(mamba["D"]) == 1).all()
    attn = params["attn_blocks"]["block"]["attn"]
    assert attn["q_proj"]["kernel"].shape == (2, 64, 64)
    assert attn["k_proj"]["kernel"].shape == (2, 64, 32)
    spec = model.kv_cache_spec()
    assert spec.state_group == (6, (("s", (1, 16, 128), jnp.float32),
                                    ("conv", (3 * 160,), jnp.float32)))
    assert spec.n_layer == 8 and spec.kv_layers == 2
    cache = spec.stacked_cache(3)
    assert set(cache) == {"s", "conv", "k", "v", "index"}
    assert cache["s"].shape == (6, 3, 1, 16, 128)
    assert cache["k"].shape == (2, 3, 2, 16, 128)
    assert spec.state_bytes_per_row == 6 * (8 * 16 * 16 * 4 + 480 * 4)


@pytest.mark.parametrize("pattern", sorted(PATTERNS))
def test_reference_matches_the_full_forward(pattern):
    """(a) ``logits`` without a cache, both patterns."""
    cfg, model, params, ids, logits_fn = build(PATTERNS[pattern])
    got = model.apply({"params": params}, jnp.asarray(ids),
                      method=model.logits)
    for b in range(2):
        np.testing.assert_allclose(
            np.asarray(got[b]), reference_logits(logits_fn, params, ids[b]),
            atol=ATOL)


def tap_logits(srv):
    """Every logits row the server samples a token from, with the request
    it belongs to: ``[(request, index of the generated token, row)]``. The
    sampler has four callers; each knows its requests (read off the
    caller's frame: a test's liberty)."""
    import inspect

    from deepspeed_tpu.serving import RequestState as State

    seen, count, sample = [], {}, srv._sample_dev

    def sampled(logits):
        caller = inspect.stack()[1]
        rows = np.asarray(logits)[:, -1]
        if caller.function == "_decode_step":
            reqs = {slot: req for slot, req in srv._slot_req.items()
                    if req.state is State.RUNNING}
        elif caller.function == "_prefill_chunk_step":
            reqs = {0: srv._prefill_queue[0]}
        elif caller.function == "_admit":
            reqs = {0: caller.frame.f_locals["req"]}
        else:
            assert caller.function == "_admit_batch", caller.function
            reqs = dict(enumerate(caller.frame.f_locals["group"]))
        for row, req in reqs.items():
            n = count.get(req.request_id, 0)
            count[req.request_id] = n + 1
            seen.append((req, n, rows[row]))
        return sample(logits)

    srv._sample_dev = sampled
    return seen


def served(model, params, prompts, new_tokens, paged, slots=3, srv=None):
    """Requests through a server of ``slots`` slots, chunk 16. Returns the
    requests, the server and the logits it sampled from."""
    if srv is None:
        srv = ds.init_serving(model, model_parameters=params,
                              config={"dtype": "float32"}, num_slots=slots,
                              prefill_chunk=CHUNK, paged_kv=paged)
    seen = tap_logits(srv)
    reqs = [srv.submit(p, max_new_tokens=new_tokens) for p in prompts]
    srv.run_until_drained(max_steps=800)
    assert all(r.state is RequestState.FINISHED for r in reqs)
    srv.check_invariants()
    # (a decode step after a request's last token samples one more row)
    assert all(sum(1 for r, n, _ in seen if r is req and n < new_tokens)
               == new_tokens for req in reqs)
    return reqs, srv, seen


def logit_error(logits_fn, params, seen):
    """Largest |served logit - reference logit| over every generated
    position of every request: the served rows against the reference's one
    pass over prompt + answer."""
    worst, cache = 0.0, {}
    for req, n, row in seen:
        if n >= len(req.output_tokens):
            continue
        if req.request_id not in cache:
            seq = np.concatenate([np.asarray(req.prompt),
                                  req.output_tokens[:-1]])
            cache[req.request_id] = reference_logits(logits_fn, params, seq)
        want = cache[req.request_id][len(req.prompt) - 1 + n]
        worst = max(worst, float(np.abs(row - want).max()))
    return worst


def prompts_of(ids):
    # one under a bucket (right padding), two of one bucket (batched
    # admission), two chunked that are no multiple of the chunk (three and
    # six chunks, the last one padded), one exactly a chunk: six requests
    # over three slots, so every slot is retired and seated again
    return [ids[0, :9], ids[1, :13], ids[0, 3:14], ids[0, :40],
            ids[1, :90], ids[1, 5:21]]


@pytest.mark.parametrize("pool", ["paged_off", "paged_on", "contiguous"])
def test_a_mixed_server_run_agrees_with_one_pass_of_the_reference(stack,
                                                                  pool):
    """(b), (c), (f): bucketed admission with right padding and chunked
    prefill of a prompt that is no multiple of the chunk, then decode, on
    each pool; every generated position against the reference's full
    forward."""
    cfg, model, params, ids, logits_fn = stack
    paged = {"paged_off": PAGED_OFF, "paged_on": PAGED_ON,
             "contiguous": False}[pool]
    reqs, srv, seen = served(model, params, prompts_of(ids), 8, paged)
    assert logit_error(logits_fn, params, seen) <= ATOL
    assert srv.metrics.preempted == 0


def test_the_published_period_serves_through_the_pages():
    cfg, model, params, ids, logits_fn = build(PATTERNS["published"])
    _, _, seen = served(model, params, [ids[0, :9], ids[1, :40]], 6,
                        PAGED_ON)
    assert logit_error(logits_fn, params, seen) <= ATOL


def test_a_seated_slot_starts_from_nothing(stack):
    """(d) A slot retired and seated again gives what a fresh server gives
    (the state and the tail a request left are read by nobody: a row at
    position 0 reads neither), and (e) the rows that do not run come out
    of a decode step bit for bit as they went in."""
    cfg, model, params, ids, logits_fn = stack
    first = [ids[0, :40], ids[1, :30], ids[0, 50:75]]
    _, srv, taps = served(model, params, first, 6, PAGED_OFF)
    dirty = {key: np.asarray(srv.pool.cache["cache_store"][key])
             for key in ("s", "conv")}
    assert all(np.abs(dirty["s"][:, row]).max() > 0 for row in range(3))
    req = srv.submit(ids[1, 10:45], max_new_tokens=6)
    seen = []
    while req.state is not RequestState.FINISHED:
        srv.step()
        if req.state is RequestState.RUNNING and req.output_tokens:
            seen.append({key: np.asarray(srv.pool.cache["cache_store"][key])
                         for key in ("s", "conv")})
    again, _, fresh = served(model, params, [ids[1, 10:45]], 6, PAGED_OFF)
    assert list(req.output_tokens) == list(again[0].output_tokens)
    mine = [row for r, n, row in taps if r is req and n < 6]
    for got, (_, _, want) in zip(mine, fresh):
        np.testing.assert_array_equal(got, want)
    assert len(mine) == 6
    assert logit_error(logits_fn, params, taps) <= ATOL
    assert len(seen) >= 3
    slot = [row for row in range(3)
            if not (seen[-1]["s"][:, row] == dirty["s"][:, row]).all()]
    assert len(slot) == 1       # the one row that ran
    for snap in seen:
        for key in ("s", "conv"):
            for row in set(range(3)) - set(slot):
                np.testing.assert_array_equal(snap[key][:, row],
                                              dirty[key][:, row])


def test_a_preempted_request_resumes_to_the_same_tokens(stack):
    """Release, re-queue, re-prefill from prompt + answer so far: the slot
    it is seated in again (its own or another's, with whatever state that
    held) starts from nothing, and the greedy answer is the unpreempted
    one. Resume WITHOUT re-prefill would need the state at the preemption:
    no such path exists for this kind."""
    cfg, model, params, ids, logits_fn = stack
    want, _, _ = served(model, params, [ids[0, :40]], 12, PAGED_OFF, slots=2)
    srv = ds.init_serving(model, model_parameters=params,
                          config={"dtype": "float32"}, num_slots=2,
                          prefill_chunk=CHUNK, paged_kv=PAGED_OFF)
    req = srv.submit(ids[0, :40], max_new_tokens=12)
    other = srv.submit(ids[1, :20], max_new_tokens=12)
    while len(req.output_tokens) < 5:
        srv.step()
    srv.preempt(req.request_id)
    assert req.state is RequestState.QUEUED and req.slot is None
    srv.run_until_drained(max_steps=300)
    assert req.state is RequestState.FINISHED and req.preemptions == 1
    assert list(req.output_tokens) == list(want[0].output_tokens)
    assert other.state is RequestState.FINISHED
    srv.check_invariants()
    # the audit holds the state group to the spec's shapes
    cs = dict(srv.pool.cache["cache_store"])
    cs["conv"] = cs["conv"][:, :1]
    srv.pool.cache = {"cache_store": cs}
    assert any("state leaf 'conv'" in e
               for e in srv.pool.consistency_errors())


# -- the state's precision, held where the state lives ---------------------
def running_server(model, params, ids):
    """Three rows in mid-answer (nothing drained: the audit judges seated
    rows that have run)."""
    srv = ds.init_serving(model, model_parameters=params,
                          config={"dtype": "float32"}, num_slots=4,
                          prefill_chunk=CHUNK, paged_kv=PAGED_ON)
    reqs = [srv.submit(p, max_new_tokens=40)
            for p in (ids[0, :9], ids[1, :40], ids[0, 20:50])]
    while min(len(r.output_tokens) for r in reqs) < 4:
        srv.step()
    return srv, reqs


def narrow_share(srv):
    from deepspeed_tpu.serving import paged_pool

    words, narrow = (np.asarray(n) for n in paged_pool._narrow_words(
        srv.pool.cache["cache_store"]["s"]))
    return narrow / np.maximum(words, 1)


def test_the_audit_reads_float32s_mantissa_in_the_rows_that_ran(stack):
    from deepspeed_tpu.serving import paged_pool
    from deepspeed_tpu.serving.resilience import InvariantViolation
    from perf.tools.brumby_limits import round_to_bfloat16

    cfg, model, params, ids, _ = stack
    srv, reqs = running_server(model, params, ids)
    srv.check_invariants()
    rows = [r.slot for r in reqs]
    # 12,288 words a row: 2**-16 of them is a fifth of a word
    assert narrow_share(srv)[rows].max() < 1e-3
    cs = dict(srv.pool.cache["cache_store"])
    sound = cs["s"]
    # one row of the three rounded as a kernel that held it in bfloat16
    # would leave it: the leaf is float32 still, the audit names the row
    cs["s"] = sound.at[:, rows[1]].set(round_to_bfloat16(sound[:, rows[1]]))
    srv.pool.cache = {"cache_store": cs}
    assert narrow_share(srv)[rows[1]] == 1.0
    with pytest.raises(InvariantViolation) as err:
        srv.check_invariants()
    assert "narrower than the spec states" in str(err.value)
    assert f"rows [{rows[1]}]" in str(err.value)
    assert paged_pool.NARROW_STATE_WORDS == 2.0 ** -4
    # a row nobody is seated in is nobody's: the free slot's is not judged
    free = sorted(srv.pool._free_set)[0]
    cs["s"] = sound.at[:, free].set(1.0)
    srv.pool.cache = {"cache_store": cs}
    srv.check_invariants()


def test_the_control_arm_of_the_limits_tool_fails_the_audit(stack,
                                                            monkeypatch):
    """``perf/tools/granite_limits.py``'s ``state_bfloat16`` arm, as it
    wraps the two kernels on the chip: the tokens it serves pass for the
    configured server's, the audit does not."""
    from deepspeed_tpu.serving.resilience import InvariantViolation
    from perf.tools.granite_limits import held_in_bfloat16

    cfg, model, params, ids, _ = stack
    monkeypatch.setattr(ss, "ssm_decode", held_in_bfloat16(ss.ssm_decode))
    monkeypatch.setattr(ss, "ssm_chunk", held_in_bfloat16(ss.ssm_chunk))
    srv, reqs = running_server(model, params, ids)
    assert narrow_share(srv)[[r.slot for r in reqs]].min() == 1.0
    with pytest.raises(InvariantViolation, match="narrower than the spec"):
        srv.check_invariants()


# -- (g) planted faults: each leaves the reference by far more than ATOL ----
def _gate_after_the_norm(y, z, weight, eps):
    y = y.astype(jnp.float32)
    n = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps) \
        * weight.astype(jnp.float32)
    return n * jax.nn.silu(z.astype(jnp.float32))


def _tail_at_the_padded_end(conv):
    def wrong(xbc, tail, w, b, valid):
        return conv(xbc, tail, w, b, jnp.full_like(valid, xbc.shape[1]))
    return wrong


def _state_over_the_padding(prefill):
    def wrong(*args, length=None, **kw):
        return prefill(*args, length=None, **kw)
    return wrong


PROGRAM_FAULTS = {
    "gate_after_the_norm": ("gated_norm", lambda old: _gate_after_the_norm),
    "conv_tail_at_the_padded_end": ("causal_conv", _tail_at_the_padded_end),
    "state_advanced_over_padding": ("ssm_prefill", _state_over_the_padding),
}


@pytest.mark.parametrize("fault", sorted(PROGRAM_FAULTS))
def test_planted_faults_in_the_program_leave_the_reference(stack, fault,
                                                           monkeypatch):
    cfg, model, params, ids, logits_fn = stack
    name, make = PROGRAM_FAULTS[fault]
    monkeypatch.setattr(ss, name, make(getattr(ss, name)))
    _, _, seen = served(model, params, prompts_of(ids), 8, PAGED_OFF)
    assert logit_error(logits_fn, params, seen) > 50 * ATOL


@pytest.mark.parametrize("fault", ["residual_multiplier_dropped",
                                   "attention_multiplier_is_rsqrt_d",
                                   "d_skip_dropped"])
def test_planted_faults_in_the_equations_leave_the_model(stack, fault):
    """The same three ways round: the reference with the fault against the
    model's full forward."""
    cfg, model, params, ids, _ = stack
    want = np.asarray(model.apply({"params": params}, jnp.asarray(ids[:1]),
                                  method=model.logits)[0])
    tree, change = params, {}
    if fault == "residual_multiplier_dropped":
        change = dict(residual_multiplier=1.0)
    elif fault == "attention_multiplier_is_rsqrt_d":
        change = dict(attention_multiplier=cfg.head_dim ** -0.5)
    else:
        tree = jax.tree_util.tree_map(lambda x: x, params)
        tree["mamba_blocks"]["block"]["mamba"]["D"] = jnp.zeros((6, 8))
    got = reference_logits(forward_of(cfg, **change), tree, ids[0])
    assert np.abs(got - want).max() > 50 * ATOL


def test_check_greedy_holds_served_tokens_to_the_reference(stack):
    cfg, model, params, ids, logits_fn = stack
    reqs, _, _ = served(model, params, [ids[0, :40]], 6, PAGED_OFF)
    check = ref.check_greedy(logits_fn, params, ids[0, :40],
                             list(reqs[0].output_tokens), 128, 8, 1e-4)
    assert check["ok"] and check["positions"] == 6, check
    wrong = list(reqs[0].output_tokens)
    wrong[3] = (wrong[3] + 1) % 512
    assert not ref.check_greedy(logits_fn, params, ids[0, :40], wrong, 128,
                                8, 1e-4)["ok"]
