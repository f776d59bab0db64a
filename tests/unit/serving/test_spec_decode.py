"""Speculative decoding tests: draft–verify over the slot pool must be a
pure THROUGHPUT change — greedy tokens bitwise-match the spec-off server
(and whole-batch ``generate()``) across multi-wave staggered workloads,
slot churn still never recompiles, rollback math keeps the KV state
machine consistent through eos/budget truncation, and the config block
validates its knobs up front."""

import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.serving import RequestState, SlotPool, SpecDecodeConfig
from deepspeed_tpu.serving.spec_decode import NGramDrafter, make_drafter

from .conftest import make_server


def _spec(k=4, **kw):
    return dict({"drafter": "ngram", "k": k, "max_ngram": 3}, **kw)


def _mixed_prompts(rng, n):
    """Half repetitive (drafter's home turf), half random (acceptance ~0 —
    the graceful-degradation path) — parity must hold for BOTH."""
    prompts = []
    for i in range(n):
        T = int(rng.integers(8, 28))
        if i % 2 == 0:
            motif = rng.integers(0, 64, size=int(rng.integers(3, 6)))
            prompts.append(np.tile(motif, T // len(motif) + 1)[:T]
                           .astype(np.int32))
        else:
            prompts.append(rng.integers(0, 64, size=T).astype(np.int32))
    return prompts


# ---------------------------------------------------------------- parity
def test_greedy_parity_multiwave_staggered(stack, pool):
    """The acceptance bar: n-gram-drafted speculative decode through 2
    slots (multi-wave slot reuse) with STAGGERED arrivals emits exactly
    the tokens the spec-off server — and generate() — emits."""
    _, _, engine = stack
    rng = np.random.default_rng(23)
    prompts = _mixed_prompts(rng, 7)
    budgets = [int(b) for b in rng.integers(4, 24, size=7)]

    def run(spec):
        srv = make_server(engine, pool, num_slots=2, max_queue_depth=16,
                          spec_decode=spec)
        reqs = []
        for p, b in zip(prompts, budgets):   # staggered: one per step
            reqs.append(srv.submit(p, max_new_tokens=b))
            srv.step()
        srv.run_until_drained(max_steps=300)
        return reqs, srv.stats()

    off, _ = run(None)
    on, s = run(_spec(k=4))
    assert all(r.state == RequestState.FINISHED for r in off + on)
    for a, b in zip(on, off):
        np.testing.assert_array_equal(a.tokens(), b.tokens(),
                                      err_msg=f"req {a.request_id}")
    for r, p, budget in zip(on, prompts, budgets):
        expected = engine.generate(p[None], max_new_tokens=budget)[0]
        np.testing.assert_array_equal(r.tokens(), expected)
    # the repetitive half must actually speculate (else this tests nothing)
    assert s["spec_drafted"] > 0 and s["spec_accepted"] > 0
    assert s["tokens_per_decode_step"] > 1.0
    assert s["decode_steps"] < sum(budgets)  # fewer steps than tokens


def test_eos_mid_accepted_chunk(stack, pool):
    """EOS emitted INSIDE an accepted draft chunk truncates consumption,
    retires the slot that step, and still matches generate()'s prefix."""
    _, _, engine = stack
    motif = np.array([7, 3, 11, 5], np.int32)
    prompt = np.tile(motif, 5)
    full = engine.generate(prompt[None], max_new_tokens=12)[0]
    gen = np.asarray(full[len(prompt):])
    eos = int(gen[3])
    first = int(np.argmax(gen == eos))

    srv = make_server(engine, pool, num_slots=2, max_queue_depth=4,
                      spec_decode=_spec(k=5))
    req = srv.submit(prompt, max_new_tokens=12, eos_token_id=eos)
    srv.run_until_drained(max_steps=50)
    assert req.finish_reason == "eos"
    np.testing.assert_array_equal(req.output_tokens, gen[:first + 1])


def test_do_sample_spec_smoke(stack, pool):
    """Lossless rejection sampling path: runs, respects budgets, emits
    in-vocab tokens. (Distributional identity is the verify program's
    math; this guards the plumbing.)"""
    _, _, engine = stack
    rng = np.random.default_rng(29)
    srv = make_server(engine, pool, num_slots=2, max_queue_depth=8,
                      do_sample=True, temperature=1.0, seed=5,
                      spec_decode=_spec(k=3))
    reqs = [srv.submit(p, max_new_tokens=6)
            for p in _mixed_prompts(rng, 4)]
    srv.run_until_drained(max_steps=100)
    for r in reqs:
        assert r.state == RequestState.FINISHED
        assert len(r.output_tokens) == 6
        assert all(0 <= t < 64 for t in r.output_tokens)


# ------------------------------------------------------- shape discipline
def test_spec_churn_does_not_recompile(stack, pool):
    """Slot retire/admit churn with speculation on keeps the verify jit
    (and decode/prefill jits) at a fixed program count — draft_len
    masking absorbs every live/dead/non-speculating combination."""
    _, _, engine = stack
    rng = np.random.default_rng(31)

    def wave(n):
        srv = make_server(engine, pool, own_programs=True, num_slots=2,
                          max_queue_depth=16, spec_decode=_spec(k=4))
        for p in _mixed_prompts(rng, n):
            srv.submit(p, max_new_tokens=5)
        srv.run_until_drained(max_steps=200)
        return srv

    def programs(srv):
        # what a spec-decode step dispatches: the engine's jits on the
        # contiguous pool; on the paged one the pool's own, built anew
        # with every pool, so two servers' COUNTS are what compares
        jits = (srv.pool._paged_verify_jit, srv.pool._paged_decode_jit,
                srv.pool._admit_rows_jit) if srv._paged else \
            (engine._jit_verify_k, engine._jit_decode)
        return [j._cache_size() for j in jits + (engine._jit_prefill_at,)]

    counts = programs(wave(2))  # compile: prefill buckets, verify, decode
    srv = wave(6)  # multi-wave churn through the same shapes
    assert programs(srv) == counts and counts[0] >= 1
    # the watchdog pins the same invariant at runtime: a warmed server
    # sees zero attributed compiles through another churn wave
    srv.end_warmup()
    for p in _mixed_prompts(rng, 4):
        srv.submit(p, max_new_tokens=5)
    srv.run_until_drained(max_steps=200)
    assert srv.watchdog.recompiles == 0


def test_capacity_margin_tightens_admission(stack, pool):
    """With spec on, admission reserves k positions of verify headroom:
    a request that fits the raw capacity but not capacity - k is shed
    as prompt_too_long instead of corrupting a neighbour's live KV."""
    _, _, engine = stack
    prompt = np.zeros((40,), np.int32)  # 40 + 20 = 60 <= 64 but > 64 - 6
    off = make_server(engine, pool, num_slots=2, max_queue_depth=4)
    assert off.submit(prompt, max_new_tokens=20).state == RequestState.QUEUED
    on = make_server(engine, pool, num_slots=2, max_queue_depth=4,
                     spec_decode=_spec(k=6))
    r = on.submit(prompt, max_new_tokens=20)
    assert r.state == RequestState.REJECTED
    assert r.reject_reason == "prompt_too_long"
    assert on.submit(prompt, max_new_tokens=18).state == RequestState.QUEUED


# -------------------------------------------------------------- drafters
def test_ngram_drafter_unit():
    d = NGramDrafter(max_ngram=3, min_ngram=1)
    h = np.array([1, 2, 3, 1, 2, 3, 1, 2], np.int32)
    tokens, counts = d.propose([h, None, np.array([9], np.int32)], k=3)
    assert tokens.shape == (3, 3) and counts.shape == (3,)
    # suffix [3,1,2] recurs at position 2; continuation is h[5:8]
    np.testing.assert_array_equal(tokens[0], [3, 1, 2])
    assert counts[0] == 3
    assert counts[1] == 0 and counts[2] == 0  # dead slot, too-short history

    # continuation clipped by history end -> partial count
    tokens, counts = d.propose([np.array([5, 6, 5, 6, 5], np.int32)], k=4)
    assert 0 < counts[0] <= 4
    np.testing.assert_array_equal(
        tokens[0, :counts[0]],
        np.array([6, 5, 6, 5], np.int32)[:counts[0]])

    # no repeated suffix anywhere -> no proposal
    _, counts = d.propose([np.arange(10, dtype=np.int32)], k=3)
    assert counts[0] == 0


def test_small_model_drafter_self_speculation(stack, pool):
    """Drafting with the TARGET model itself (the degenerate two-model
    setup) must keep exact parity — and accept nearly everything, since
    the draft IS the target's greedy continuation."""
    model, params, engine = stack
    draft_eng = ds.init_inference(model=model, model_parameters=params,
                                  config={"dtype": "float32"})
    rng = np.random.default_rng(37)
    prompts = _mixed_prompts(rng, 4)

    srv = make_server(engine, pool, num_slots=2, max_queue_depth=8,
                      spec_decode={"drafter": "model", "k": 4,
                                   "draft_engine": draft_eng})
    reqs = [srv.submit(p, max_new_tokens=10) for p in prompts]
    srv.run_until_drained(max_steps=100)
    for r, p in zip(reqs, prompts):
        expected = engine.generate(p[None], max_new_tokens=10)[0]
        np.testing.assert_array_equal(r.tokens(), expected)
    s = srv.stats()
    assert s["spec_acceptance_rate"] > 0.8
    assert s["tokens_per_decode_step"] > 2.0


# ------------------------------------------------------- config + rollback
def test_spec_config_validation():
    assert SpecDecodeConfig.from_value(None) is None
    assert SpecDecodeConfig.from_value(False) is None
    cfg = SpecDecodeConfig.from_value(True)
    assert cfg.enabled and cfg.drafter == "ngram" and cfg.k == 4
    assert SpecDecodeConfig.from_value({"k": 2}).k == 2
    sc = SpecDecodeConfig.from_value(cfg)
    assert sc is cfg
    with pytest.raises(TypeError, match="spec_decode"):
        SpecDecodeConfig.from_value(7)
    with pytest.raises(ValueError, match="k"):
        SpecDecodeConfig(k=0).validate(64)
    with pytest.raises(ValueError, match="capacity"):
        SpecDecodeConfig(k=63).validate(64)
    with pytest.raises(ValueError, match="min_ngram"):
        SpecDecodeConfig(min_ngram=0).validate(64)
    with pytest.raises(ValueError, match="draft_engine"):
        make_drafter(SpecDecodeConfig(drafter="model"))
    with pytest.raises(ValueError, match="unknown drafter"):
        make_drafter(SpecDecodeConfig(drafter="nope"))
    d = NGramDrafter()
    assert make_drafter(SpecDecodeConfig(drafter=d)) is d


def test_slot_pool_advance_per_slot(stack):
    """advance(array) is the rollback primitive: the host mirror AND the
    device index move per slot; advance(scalar) moves only the mirror
    (the in-jit uniform bump already moved the device side)."""
    _, _, engine = stack
    pool = SlotPool(engine.kv_cache_spec(), 3)
    pool.starts[:] = [5, 9, 2]
    pool.advance(np.array([3, 0, 1], np.int32))
    np.testing.assert_array_equal(pool.starts, [8, 9, 3])
    np.testing.assert_array_equal(
        np.asarray(pool.cache["cache_store"]["index"]), [8, 9, 3])
    pool.advance(1)  # scalar: mirror only
    np.testing.assert_array_equal(pool.starts, [9, 10, 4])
    np.testing.assert_array_equal(
        np.asarray(pool.cache["cache_store"]["index"]), [8, 9, 3])
    with pytest.raises(ValueError, match="shape"):
        pool.advance(np.zeros((2,), np.int32))
